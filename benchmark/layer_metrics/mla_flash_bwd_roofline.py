"""Layer: kernels (``ops/pallas/flash_attention.py``). The multi-block
backward flash kernels' share of their roofline where queries and keys are
wider than values (latent attention, 192 / 128): least time of a backward
pass (``mla_flops.flash_backward``: dV and dP at 128, dQ and dK at 192)
over the kernels' device time in the trace, by their names, in per cent."""

import mla_flops
from layer_metrics import mla_flash


def read(run):
    return mla_flash.roofline_share(run, mla_flash.BACKWARD, mla_flops.flash_backward)
