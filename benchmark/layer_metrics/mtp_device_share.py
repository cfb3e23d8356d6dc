"""Layer: operators (models/joyai.py). Device time of the ops under the
program's ``mtp`` scope (the multi-token-prediction module: its two norms,
W_eh, its decoder layer (latent attention, shared and routed experts) and
its head norm, forward, recomputation and backward; NOT its pass through the
shared head, which is under chunked_ce), as self time on device 0 over the
traced window of whole steps, in per cent."""

from layer_metrics import nested_scopes


def read(run):
    return nested_scopes.share(run, "mtp")
