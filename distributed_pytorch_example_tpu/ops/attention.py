"""Multi-head scaled-dot-product attention with kernel dispatch.

Single entry point for every transformer in the zoo. The XLA path below is
already strong on TPU (XLA fuses softmax chains and tiles the matmuls onto
the MXU); the Pallas flash kernel (``ops/pallas/flash_attention.py``) is used
on TPU when shapes allow, cutting HBM traffic from O(S^2) to O(S).

Layout convention: (batch, seq, heads, head_dim) — "BSNH", the layout that
keeps the MXU matmuls contiguous and maps cleanly onto sequence sharding.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    kv_mask: Optional[jax.Array],
    causal: bool,
    softmax_scale: float,
) -> jax.Array:
    """Reference attention in pure XLA ops. q: (B, S, N, H); k/v may have
    fewer heads (GQA) as long as N divides by them."""
    if k.shape[2] != q.shape[2]:  # GQA: broadcast kv heads across groups
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k) * softmax_scale
    # Upcast the softmax: bf16 logits lose too much precision in the reduce.
    logits = logits.astype(jnp.float32)
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k_len - q_len)
        logits = jnp.where(causal_mask, logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        # mask: broadcastable to (B, N, Q, K); True = attend.
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    if kv_mask is not None:
        # kv_mask: (B, K) key-padding validity; True/nonzero = attend.
        logits = jnp.where(
            kv_mask[:, None, None, :].astype(bool),
            logits,
            jnp.finfo(jnp.float32).min,
        )
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", weights.astype(v.dtype), v)
    if kv_mask is not None:
        # batch rows with NO valid key: softmax over all-min logits yields
        # a uniform average of V; emit zeros instead, matching the flash
        # kernel's documented fully-padded behavior on every platform
        any_valid = kv_mask.astype(bool).any(axis=-1)
        out = jnp.where(any_valid[:, None, None, None], out, 0)
    return out


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Scaled dot-product attention, (B, S, N, H) in and out.

    Args:
      mask: optional boolean mask broadcastable to (B, N, Q, K); True=attend.
        General masks take the XLA path (flash doesn't stream them).
      kv_mask: optional (B, K) key-padding validity; True=attend. The form
        real (padded) BERT batches need — supported by the flash kernel.
      causal: apply a causal mask (decoder LM).
      use_flash: force (True/False) or auto-select (None) the Pallas kernel.
    """
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])

    if use_flash is None:
        # Auto-dispatch picks flash only when the kernel serves the shapes
        # natively. Misaligned sequences (e.g. ViT's 197 tokens) go to the
        # XLA path: lane-padding them into the flash kernel was measured
        # SLOWER at ViT-B/16 bench shapes (batch 128, bf16, 197 tokens:
        # ~193 ms/step padded-flash vs ~137 ms XLA — the short sequence's
        # (B, N, S, S) logits are small enough that XLA's fused softmax
        # beats flash's 30% pad overhead). The padded path stays available
        # as an explicit use_flash=True opt-in for callers who measured a
        # win at their shapes.
        use_flash = _flash_unsupported_reason(q, k, v, mask, causal) is None
    elif use_flash:
        reason = _flash_unsupported_reason(q, k, v, mask, causal)
        if reason is not None:
            if _only_seq_misaligned(q, k, v, mask, causal):
                # explicit opt-in: serve seq % 128 != 0 by lane-padding
                # (pad keys masked out, pad-query outputs sliced off; their
                # cotangents are zero, so grads stay exact)
                return _flash_lane_padded(
                    q, k, v, kv_mask, causal, softmax_scale
                )
            # forced flash must not silently degrade or crash deep in
            # lowering: surface exactly why the kernel can't serve this call
            raise ValueError(
                f"use_flash=True but the flash kernel does not support this "
                f"call: {reason}. Use use_flash=None to auto-select."
            )
    if use_flash:
        from distributed_pytorch_example_tpu.ops.pallas import flash_attention

        return flash_attention.flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask,
            softmax_scale=softmax_scale,
        )
    return _xla_attention(q, k, v, mask, kv_mask, causal, softmax_scale)


def _only_seq_misaligned(q, k, v, mask, causal) -> bool:
    """True when sequence alignment is the ONLY flash blocker (self-
    attention with seq % 128 != 0) — the case lane-padding can serve."""
    seq_q, seq_k = q.shape[1], k.shape[1]
    if seq_q != seq_k or seq_q % 128 == 0:
        return False
    padded = list(q.shape)
    padded[1] = seq_q + (-seq_q % 128)
    probe = jax.ShapeDtypeStruct(tuple(padded), q.dtype)
    kprobe = jax.ShapeDtypeStruct(
        (k.shape[0], padded[1], *k.shape[2:]), k.dtype
    )
    return _flash_unsupported_reason(probe, kprobe, kprobe, mask, causal) is None


def _flash_lane_padded(q, k, v, kv_mask, causal, softmax_scale,
                       interpret=False):
    """Flash on a lane-padded sequence: pad keys masked, pad queries
    discarded. Exact for the real positions (fully-padded rows emit zero
    output and zero gradients — see flash_attention's kv_mask contract).

    NOT on the auto-dispatch path: measured slower than the XLA fallback at
    ViT-B/16 bench shapes (see dot_product_attention). Reached only via an
    explicit ``use_flash=True``; ``interpret=True`` runs it on CPU for
    numerics tests."""
    import jax.numpy as jnp

    from distributed_pytorch_example_tpu.ops.pallas import flash_attention

    seq = q.shape[1]
    pad = -seq % 128
    pad_widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    valid = jnp.ones((q.shape[0], seq), bool) if kv_mask is None else kv_mask
    mask_p = jnp.pad(valid.astype(bool), ((0, 0), (0, pad)))
    out = flash_attention.flash_attention(
        jnp.pad(q, pad_widths), jnp.pad(k, pad_widths), jnp.pad(v, pad_widths),
        causal=causal, kv_mask=mask_p, softmax_scale=softmax_scale,
        interpret=interpret,
    )
    return out[:, :seq]


def fused_layout_eligible(
    batch: int, seq: int, heads: int, kv_heads: int, head_dim: int, dtype,
    *, causal: bool, use_flash: Optional[bool],
) -> bool:
    """True when the flash kernel would serve this self-attention AND the
    caller can use the head-major fused projection layout — project
    straight to (B, N, S, H) with einsum('bsd,dnh->bnsh') and skip the
    transpose sandwich (measured ~0.22 ms/layer at GPT-2 bench shapes,
    results/lm_mfu_analysis/bsnh_ab.json). The decision must be taken
    BEFORE the projections run, hence this static probe; masks, decode,
    RoPE, and sequence parallelism all disqualify (their paths are
    (B, S, N, H)-shaped).
    """
    if use_flash is False:
        return False
    q = jax.ShapeDtypeStruct((batch, seq, heads, head_dim), dtype)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, head_dim), dtype)
    return _flash_unsupported_reason(q, kv, kv, None, causal) is None


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # a backend that fails to initialize raises here: it must not read as
    # "not a TPU" and quietly send every attention call to XLA
    return jax.devices()[0].platform == "tpu"


# widths the flash kernels take for q/k and, independently, for v (latent
# attention asks for 192 / 128: the 192 lanes of a q/k tile sit in two
# 128-lane registers, the second half full)
FLASH_HEAD_DIMS = (64, 128, 192, 256)


def _flash_unsupported_reason(q, k, v, mask, causal) -> Optional[str]:
    """None if the flash kernel can serve this call, else a human reason."""
    if mask is not None:
        return "custom masks are not implemented in the flash kernel"
    seq_q, seq_k, head_dim = q.shape[1], k.shape[1], q.shape[-1]
    v_dim = v.shape[-1]  # the values may be narrower than queries and keys
    if causal and seq_q != seq_k:
        # flash causal masking is top-left (row >= col) aligned; the XLA
        # reference is bottom-right aligned — they only agree for seq_q==seq_k
        return f"causal with seq_q != seq_k ({seq_q} != {seq_k})"
    if q.shape[2] % k.shape[2]:
        return (
            f"q heads {q.shape[2]} not a multiple of kv heads {k.shape[2]}"
        )
    if not _on_tpu():
        return "flash kernel is TPU-only"
    if seq_q % 128 or seq_k % 128:
        return f"seq lengths ({seq_q}, {seq_k}) not multiples of 128"
    if k.shape[-1] != head_dim:
        return f"q and k head dims differ ({head_dim} != {k.shape[-1]})"
    for what, width in (("head_dim", head_dim), ("v head_dim", v_dim)):
        if width not in FLASH_HEAD_DIMS:
            return f"{what} {width} not in {FLASH_HEAD_DIMS}"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {q.dtype} not in (float32, bfloat16)"
    return None
