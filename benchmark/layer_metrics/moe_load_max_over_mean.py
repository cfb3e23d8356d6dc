"""Layer: experts (``models/moe.py``). The largest held expert's count of
assignments over the held experts' mean, worst layer of a step, as the
compiled step counted it: the mean over the window's ``moe_counters`` rows.
1 is an even routing; the grouped products' time follows the sum of the
loads, a deployment's step the largest."""

from layer_metrics import moe_counters


def read(run):
    return moe_counters.mean(run, "load_max_over_mean")
