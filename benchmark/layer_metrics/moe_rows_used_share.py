"""Layer: experts (``models/moe.py``). Rows of the sorted buffer that hold
an assignment over its static bound, worst layer of a step, in per cent:
the mean over the window's ``moe_counters`` rows. What is left is the room
before an assignment is dropped, and the rows that gathers and elementwise
passes touch for nothing."""

from layer_metrics import moe_counters


def read(run):
    share = moe_counters.mean(run, "rows_used_share")
    return None if share is None else 100.0 * share
