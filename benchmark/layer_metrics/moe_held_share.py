"""Layer: experts (``models/moe.py``). The share of a step's assignments
(experts per token x tokens) that the routing sent to the experts held
here, the expert layers' mean, in per cent: the mean over the window's
``moe_counters`` rows. Even routing gives held / published (8 of 256:
3.1%); 0 says the held experts starved and the window timed empty groups
(PERF.md section 6, PR 29). The note says the first and the last row, so
that a share that falls through the window shows."""

from layer_metrics import moe_counters
from layer_metrics import program_spans as ps


def read(run):
    share = moe_counters.mean(run, "held_share")
    if share is None:
        return None
    rows = [r["held_share"] for r in moe_counters.window_rows(run)]
    ps.say(run, f"moe_held_share: first row {rows[0]!r}, last row {rows[-1]!r}")
    return 100.0 * share
