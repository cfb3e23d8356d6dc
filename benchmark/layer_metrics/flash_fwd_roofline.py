"""Layer: kernels (``ops/pallas/flash_attention.py``). The forward flash
kernel's share of its roofline: least time from the cell's shapes over its
device time in the trace, in per cent."""

import flops
from layer_metrics import flash_kernels


def read(run):
    return flash_kernels.roofline_share(
        run, flash_kernels.FORWARD, flops.flash_forward
    )
