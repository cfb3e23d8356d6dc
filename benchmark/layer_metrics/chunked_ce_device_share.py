"""Layer: loss (``ops/chunked_ce.py``). Device time of the ops under the
program's ``chunked_ce`` scope (the streamed vocabulary blocks of the fused
LM loss, forward and backward), as self time on device 0 over the traced
window of whole steps, in per cent."""

from layer_metrics import scope_ops


def read(run):
    return scope_ops.share(run, "chunked_ce")
