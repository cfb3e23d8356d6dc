"""Layer: kernels (``ops/pallas/flash_attention.py``). The multi-block
forward flash kernel's share of its roofline at grouped-query shapes: least
time of a call (``flops.flash_forward`` at the query heads) over its device
time in the trace, by the kernel's name, in per cent."""

import flops
from layer_metrics import gqa_flash


def read(run):
    return gqa_flash.roofline_share(run, gqa_flash.FORWARD, flops.flash_forward)
