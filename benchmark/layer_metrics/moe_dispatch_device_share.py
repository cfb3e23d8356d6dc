"""Layer: experts (``models/moe.py``). Device time of the ops under the
program's ``moe_route`` and ``moe_dispatch`` scopes (router product,
sigmoid, top-k, normalisation; held mask, sort, gather into rows, weight and
scatter-add back, and their backward), as self time on device 0 over the
traced window of whole steps, in per cent."""

from layer_metrics import named_scopes


def read(run):
    return named_scopes.share(run, ("moe_route", "moe_dispatch"))
