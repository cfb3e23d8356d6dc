"""Layer: experts (``models/moe.py``). Device time of the ops under the
program's ``moe_experts`` scope (the grouped gate+up and down products and
the SwiGLU between them, forward, recomputed forward and backward), as self
time on device 0 over the traced window of whole steps, in per cent."""

from layer_metrics import named_scopes


def read(run):
    return named_scopes.share(run, ("moe_experts",))
