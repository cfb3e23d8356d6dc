"""Layer: operators (``models/lfm2.py``). Device time of the ops under the
program's ``short_conv`` scope (the whole gated short convolution: both
projections, the gates and the depthwise taps, forward and backward), as
self time on device 0 over the traced window of whole steps, in per cent."""

from layer_metrics import named_scopes


def read(run):
    return named_scopes.share(run, ("short_conv",))
