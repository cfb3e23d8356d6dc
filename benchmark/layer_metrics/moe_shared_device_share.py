"""Layer: experts (models/moe.py). Device time of the ops under the program's
``moe_shared`` scope (the shared expert that every token meets beside the
routed ones (one SwiGLU of the experts' width), forward, recomputation and
backward; the prediction module's is counted under mtp), as self time on
device 0 over the traced window of whole steps, in per cent."""

from layer_metrics import nested_scopes


def read(run):
    return nested_scopes.share(run, "moe_shared")
