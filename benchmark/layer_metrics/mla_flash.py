"""What the two latent-attention flash roofline metrics share: the kernels'
names and the reduction are ``gqa_flash.py``'s (``flash_fwd``,
``flash_bwd_fused`` or ``flash_bwd_dq`` + ``flash_bwd_dkv``; every call
counted, a forward recomputed under remat too, at the cell's rows a chip,
``shape["heads"]`` and the sequence length); the count is ``mla_flops.py``'s
at the configuration's three widths: ``qk_nope_head_dim`` +
``qk_rope_head_dim`` for the scores, ``v_head_dim`` for the values, the
rotary key read once. A configuration without those keys, or a trace without
the kernels, gives None."""

from layer_metrics import gqa_flash

FORWARD, BACKWARD = gqa_flash.FORWARD, gqa_flash.BACKWARD


def roofline_share(run, patterns, count):
    config = run["config"]
    widths = [
        config.get(k)
        for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
    ]
    if None in widths:
        return None
    return gqa_flash.roofline_share(
        run, patterns,
        lambda rows, heads, seq, _head_dim, causal: count(
            rows, heads, seq, *widths, causal
        ),
    )
