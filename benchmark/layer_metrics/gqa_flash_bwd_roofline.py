"""Layer: kernels (``ops/pallas/flash_attention.py``). The multi-block
backward flash kernels' share of their roofline at grouped-query shapes:
least time of a backward pass (``flops.flash_backward`` at the query heads)
over the kernels' device time in the trace, by their names, in per cent."""

import flops
from layer_metrics import gqa_flash


def read(run):
    return gqa_flash.roofline_share(run, gqa_flash.BACKWARD, flops.flash_backward)
