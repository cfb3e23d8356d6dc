"""Layer: kernels (``ops/pallas/flash_attention.py``). The multi-block
forward flash kernel's share of its roofline where queries and keys are
wider than values (latent attention, 192 / 128): least time of a call
(``mla_flops.flash_forward``: the scores at 192, the values at 128, the
rotary key read once) over its device time in the trace, by the kernel's
name, in per cent. What the kernel pads inside itself is its own and shows
here."""

import mla_flops
from layer_metrics import mla_flash


def read(run):
    return mla_flash.roofline_share(run, mla_flash.FORWARD, mla_flops.flash_forward)
