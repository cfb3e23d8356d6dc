"""Layer: operators (models/joyai.py). Device time of the ops under the
program's ``mla_proj`` scope (latent attention's projections: both low-rank
chains with the norm in the middle of each, the rotary turn, the lay-out of
the one rotary key to every head and the output projection, forward,
recomputation and backward; the prediction module's attention is counted
under mtp), as self time on device 0 over the traced window of whole steps,
in per cent."""

from layer_metrics import nested_scopes


def read(run):
    return nested_scopes.share(run, "mla_proj")
