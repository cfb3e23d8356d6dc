"""Ulysses-style sequence parallelism: all-to-all heads <-> sequence swap.

The second sequence-parallel mode next to ring attention (ops/
ring_attention.py), covering the other side of the long-context design
space (DeepSpeed-Ulysses): instead of rotating K/V chunks around a ring,
ONE all-to-all per projection re-shards (batch, seq/P, heads, dim) into
(batch, seq, heads/P, dim) — each device then runs ordinary FULL-sequence
attention for its group of heads (the Pallas flash kernel, causal masking,
everything — no cross-chunk online-softmax bookkeeping), and a second
all-to-all restores the sequence sharding.

Trade-offs vs ring:

- communication: 2 all-to-alls of the qkv/out tensors vs (P-1) K/V
  neighbor transfers — all-to-all rides ICI efficiently and the volume is
  independent of P;
- memory: full-sequence activations for heads/P heads per device (ring
  keeps O(S_local) always) — Ulysses scales sequence length only until
  S x N/P activations fit;
- constraint: the head count must divide by the axis size (ring has no
  such constraint);
- GQA: when ``kv_heads < axis_size`` the devices form ``kv_heads`` groups
  of ``rep = P/kv_heads``; a GROUPED all-to-all routes each device only
  its group head's ``1/rep`` sequence shard — per-device KV stays at the
  fair ``kv_heads/P`` share, no replication — and an in-group ``ppermute``
  ring folds the partial attention with an online softmax
  (:func:`_ulysses_gqa_grouped`).

Both compose with the same mesh axes; ``MultiHeadAttention`` selects via
``sp_mode``. All collectives are reverse-mode differentiable (an
all-to-all's transpose is the inverse all-to-all, a ppermute's the
inverse permutation), so no custom VJP is needed.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_example_tpu.ops.attention import dot_product_attention

# (kv_heads, axis_size) pairs already warned about use_flash on the grouped
# GQA path — without this the warning fires once per attention layer per trace
_flash_warned: set = set()

NEG_INF = -1e30  # large-negative instead of -inf keeps exp() NaN-free


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """All-to-all attention; call inside ``shard_map``.

    Args:
      q, k, v: local shards (batch, seq_local, heads, head_dim), sharded on
        the sequence dim over ``axis_name``. ``heads`` must divide by the
        axis size.
      kv_mask: optional (batch, seq_local) key-padding validity shard
        (True=attend). After the heads<->sequence all-to-all each device
        attends over the FULL sequence, so the mask is all-gathered along
        the axis (it is S bits per row — negligible next to the k/v
        all-to-alls) and streams through the attention kernel's kv_mask
        port.

    Returns the local output shard (batch, seq_local, heads, head_dim).
    """
    import jax.numpy as jnp

    p = lax.axis_size(axis_name)
    if q.shape[2] % p:
        raise ValueError(
            f"ulysses needs q heads ({q.shape[2]}) divisible by the "
            f"sequence axis size ({p}); shrink the sequence axis, or use "
            f"ring attention (no head-divisibility constraint)"
        )
    kv_heads = k.shape[2]
    if kv_heads % p:
        if p % kv_heads:
            raise ValueError(
                f"ulysses needs kv heads ({kv_heads}) to divide or be "
                f"divided by the sequence axis size ({p}); shrink the "
                f"sequence axis, or use ring attention (serves GQA with "
                f"chunk-local kv expansion)"
            )
        # GQA with fewer kv heads than devices: grouped exchange keeps
        # per-device KV at the fair kv_heads/P share (no replication)
        if use_flash and (kv_heads, p) not in _flash_warned:
            _flash_warned.add((kv_heads, p))
            from distributed_pytorch_example_tpu.runtime.logging import (
                get_logger,
            )

            get_logger(__name__).warning(
                "Ulysses GQA grouped path (kv_heads %d < axis %d) runs "
                "XLA folds — use_flash=True does not apply here (shard "
                "run positions are strided past the Pallas kernel's "
                "aligned causal mask). For extreme sequence lengths "
                "prefer sp_mode='ring' (flash local folds, O(S_local) "
                "memory).", kv_heads, p,
            )
        return _ulysses_gqa_grouped(
            q, k, v, axis_name, kv_mask=kv_mask, causal=causal,
            softmax_scale=softmax_scale,
        )

    def to_heads(x):
        # (B, S/P, N, H) -> (B, S, N/P, H): split the head dim across the
        # axis, gather the full sequence
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    mask_full = None
    if kv_mask is not None:
        # heads are sharded after the swap but keys span the full sequence:
        # every device needs the whole mask
        mask_full = lax.all_gather(
            kv_mask.astype(jnp.float32), axis_name, axis=1, tiled=True
        ) > 0.0
    out = dot_product_attention(
        to_heads(q), to_heads(k), to_heads(v),
        kv_mask=mask_full, causal=causal, softmax_scale=softmax_scale,
        use_flash=use_flash,
    )
    return to_seq(out)


def _grouped_kv_exchange(x: jax.Array, axis_name: str, rep: int) -> jax.Array:
    """Grouped all-to-all for GQA K/V: route each device ONLY its group
    head's sequence sub-shard.

    Input: local shard (B, Sp, kv, H), seq-sharded over ``axis_name`` of
    size p = kv * rep; device d = g*rep + r belongs to head-group g with
    in-group rank r. Output on device (g, r): (B, p, c, H) with c = Sp/rep
    — run ``s`` is source device s's r-th seq sub-chunk of head g, i.e.
    global positions ``s*Sp + r*c + [0, c)``. Per-device KV bytes after
    the exchange: B * (Sp*p/rep) * H = the fair kv/p share of the full
    sequence — rep x less than replicating kv heads up to the axis.
    """
    B, Sp, kv, H = x.shape
    c = Sp // rep
    # send buffer slot j = g*rep + r carries MY sub-chunk r of head g
    send = (
        x.reshape(B, rep, c, kv, H)
        .transpose(0, 3, 1, 2, 4)  # (B, kv, rep, c, H): slot-major (g, r)
        .reshape(B, kv * rep, c, H)
    )
    # tiled all-to-all: slot j -> device j; received slots (one per source)
    # concatenate back along the same axis, now indexed by SOURCE
    return lax.all_to_all(send, axis_name, split_axis=1, concat_axis=1,
                          tiled=True)


def _grouped_positions(p, Sp, c, r_orig):
    """(p, c) global key positions of a shard originally at in-group rank
    ``r_orig``: run s covers ``s*Sp + r_orig*c + [0, c)``."""
    import jax.numpy as jnp

    return (
        jnp.arange(p)[:, None] * Sp + r_orig * c + jnp.arange(c)[None, :]
    )


def _grouped_logits(qt, ks, k_pos, mask_full, causal, scale):
    """(B, nq, S, p, c) fp32 masked logits of q (full seq) vs one shard."""
    import jax.numpy as jnp

    s_log = jnp.einsum(
        "bnsh,bpch->bnspc", qt, ks, preferred_element_type=jnp.float32
    ) * scale
    S = qt.shape[2]
    if causal:
        s_log = jnp.where(
            jnp.arange(S)[None, None, :, None, None]
            >= k_pos[None, None, None, :, :],
            s_log, NEG_INF,
        )
    if mask_full is not None:
        valid = mask_full[:, k_pos] > 0.0  # (B, p, c)
        s_log = jnp.where(valid[:, None, None], s_log, NEG_INF)
    return s_log


def _grouped_in_group_shift(kv: int, rep: int):
    """ppermute pairs rotating shards one hop within each head group."""
    return [
        (g * rep + r, g * rep + (r + 1) % rep)
        for g in range(kv)
        for r in range(rep)
    ]


def _grouped_fwd_impl(qt, ks, vs, mask_full, axis_name, causal, scale, rep):
    """Online-softmax folds over the in-group ring; returns (out, lse).

    qt: (B, nq, S, H) full-sequence q block; ks/vs: (B, p, c, H) exchanged
    shards. out is normalized fp32 (dead rows zeroed), lse (B, nq, S).
    """
    import jax.numpy as jnp

    B, nq, S, H = qt.shape
    p = lax.axis_size(axis_name)
    Sp, c = S // p, S // p // rep
    r0 = lax.axis_index(axis_name) % rep
    shift = _grouped_in_group_shift(p // rep, rep)

    m = jnp.full((B, nq, S), NEG_INF, jnp.float32)
    l = jnp.zeros((B, nq, S), jnp.float32)
    acc = jnp.zeros((B, nq, S, H), jnp.float32)
    for t in range(rep):  # static unroll; rep = P/kv_heads is small
        r_orig = (r0 - t) % rep  # owner rank of the shard now held
        k_pos = _grouped_positions(p, Sp, c, r_orig)
        s_log = _grouped_logits(qt, ks, k_pos, mask_full, causal, scale)
        m_new = jnp.maximum(m, jnp.max(s_log, axis=(3, 4)))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s_log - m_new[..., None, None])
        # fully-dead rows this fold: m_new stays NEG_INF and pexp is
        # exp(0)=1 garbage; zero it so l/acc never see it
        dead = (m_new == NEG_INF)[..., None, None]
        pexp = jnp.where(dead, 0.0, pexp)
        l = l * alpha + jnp.sum(pexp, axis=(3, 4))
        acc = acc * alpha[..., None] + jnp.einsum(
            "bnspc,bpch->bnsh", pexp, vs,
            preferred_element_type=jnp.float32,
        )
        m = m_new
        if t < rep - 1:
            ks = lax.ppermute(ks, axis_name, shift)
            vs = lax.ppermute(vs, axis_name, shift)

    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe_l[..., None]
    out = jnp.where((m == NEG_INF)[..., None], 0.0, out)  # dead rows -> 0
    lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(safe_l))
    return out.astype(qt.dtype), lse  # residual rides in compute dtype


def _grouped_bwd_impl(qt, ks, vs, mask_full, out, lse, g, axis_name, causal,
                      scale, rep):
    """Ring-replay backward from the saved global lse (flash delta trick).

    dK/dV accumulators travel around the in-group ring WITH their shard
    and arrive home after the full rotation — no per-fold residuals, so
    per-device KV memory stays at the exchanged-shard share in training
    too (the same scheme as ops/ring_attention.py's custom VJP).
    """
    import jax.numpy as jnp

    B, nq, S, H = qt.shape
    p = lax.axis_size(axis_name)
    Sp, c = S // p, S // p // rep
    r0 = lax.axis_index(axis_name) % rep
    shift = _grouped_in_group_shift(p // rep, rep)

    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # (B, nq, S)

    dq = jnp.zeros((B, nq, S, H), jnp.float32)
    dk = jnp.zeros_like(ks, dtype=jnp.float32)
    dv = jnp.zeros_like(vs, dtype=jnp.float32)
    for t in range(rep):
        r_orig = (r0 - t) % rep
        k_pos = _grouped_positions(p, Sp, c, r_orig)
        s_log = _grouped_logits(qt, ks, k_pos, mask_full, causal, scale)
        # GLOBAL softmax weights for this shard's keys; re-masking kills
        # the exp(NEG_INF - NEG_INF) = 1 garbage of masked/dead entries
        pexp = jnp.exp(s_log - lse[..., None, None])
        pexp = jnp.where(s_log == NEG_INF, 0.0, pexp)
        dv = dv + jnp.einsum(
            "bnspc,bnsh->bpch", pexp, gf, preferred_element_type=jnp.float32
        )
        dp = jnp.einsum(
            "bnsh,bpch->bnspc", gf, vs, preferred_element_type=jnp.float32
        )
        ds = pexp * (dp - delta[..., None, None]) * scale
        dq = dq + jnp.einsum(
            "bnspc,bpch->bnsh", ds, ks, preferred_element_type=jnp.float32
        )
        dk = dk + jnp.einsum(
            "bnspc,bnsh->bpch", ds, qt, preferred_element_type=jnp.float32
        )
        # rotate shard AND its grad accumulators together; after the full
        # cycle (rep hops) the accumulators land back home
        ks = lax.ppermute(ks, axis_name, shift)
        vs = lax.ppermute(vs, axis_name, shift)
        dk = lax.ppermute(dk, axis_name, shift)
        dv = lax.ppermute(dv, axis_name, shift)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _grouped(qt, ks, vs, mask_full, axis_name, causal, scale, rep):
    out, _ = _grouped_fwd_impl(
        qt, ks, vs, mask_full, axis_name, causal, scale, rep
    )
    return out


def _grouped_fwd(qt, ks, vs, mask_full, axis_name, causal, scale, rep):
    out, lse = _grouped_fwd_impl(
        qt, ks, vs, mask_full, axis_name, causal, scale, rep
    )
    return out, (qt, ks, vs, mask_full, out, lse)


def _grouped_bwd(axis_name, causal, scale, rep, residuals, g):
    qt, ks, vs, mask_full, out, lse = residuals
    dq, dk, dv = _grouped_bwd_impl(
        qt, ks, vs, mask_full, out, lse, g, axis_name, causal, scale, rep
    )
    # mask_full is float32 by construction (caller casts before the gather)
    dmask = None if mask_full is None else jax.numpy.zeros_like(mask_full)
    return dq.astype(qt.dtype), dk.astype(ks.dtype), dv.astype(vs.dtype), dmask


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _ulysses_gqa_grouped(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Ulysses attention for ``kv_heads < axis_size`` WITHOUT replication.

    Layout: q takes the standard heads<->sequence all-to-all — device
    d = g*rep + r computes q-head block d over the FULL sequence, and that
    block's GQA group is exactly head g (head blocks align because
    N/kv_heads = (N/p)*rep). K/V take :func:`_grouped_kv_exchange`, so the
    device holds only 1/rep of head g's sequence; an in-group ppermute
    ring (rep-1 hops) streams the remaining shards through, folded with a
    fp32 online softmax (same recurrence as the flash kernel / ring
    attention). Communication: q/out all-to-alls unchanged; K/V move
    exactly once (minimal volume — the replicating path moved rep x more).

    Memory: a ``custom_vjp`` replays the ring in backward from the saved
    global lse (dK/dV accumulators travel with their shard — the
    ops/ring_attention.py scheme), so per-device KV residuals stay at the
    exchanged-shard share in training too. The folds are XLA einsums (a
    shard's run positions are strided past the Pallas kernel's aligned
    causal mask), so ``use_flash`` does not apply and each fold
    materializes a transient (B, N/P, S, S/rep) fp32 logits buffer —
    fine at Ulysses scales (S*N/P activations must fit anyway), but for
    extreme sequence lengths prefer ``sp_mode='ring'`` (flash folds,
    O(S_local) everything). Fully-masked rows emit zeros, matching
    ``_xla_attention``'s contract.
    """
    import jax.numpy as jnp

    p = lax.axis_size(axis_name)
    B, Sp, N, H = q.shape
    kv = k.shape[2]
    rep = p // kv
    if Sp % rep:
        raise ValueError(
            f"ulysses GQA grouping needs the local sequence ({Sp}) "
            f"divisible by P/kv_heads ({rep}); pad the sequence, shrink "
            f"the sequence axis, or use ring attention"
        )
    scale = softmax_scale if softmax_scale is not None else H ** -0.5

    # (B, Sp, N, H) -> (B, S, nq, H) -> (B, nq, S, H): full sequence, my
    # q-head block (the swap differentiates natively: a2a transpose)
    q_full = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    qt = q_full.transpose(0, 2, 1, 3)
    ks = _grouped_kv_exchange(k, axis_name, rep)  # (B, p, c, H)
    vs = _grouped_kv_exchange(v, axis_name, rep)

    mask_full = None
    if kv_mask is not None:
        # S bits per row — negligible next to the K/V exchange
        mask_full = lax.all_gather(
            kv_mask.astype(jnp.float32), axis_name, axis=1, tiled=True
        )  # (B, S)

    out = _grouped(qt, ks, vs, mask_full, axis_name, causal, float(scale),
                   rep)
    out = out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, S, nq, H)
    # heads <-> sequence swap back: (B, S, nq, H) -> (B, Sp, N, H)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "sequence",
    batch_axes: Sequence[str] = ("data", "fsdp"),
    heads_axis: str = "tensor",
    kv_mask: Optional[jax.Array] = None,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Ulysses attention on global (B, S, N, H) arrays: shard, swap, attend,
    swap back. When the mesh spans a ``heads_axis`` (tensor parallelism)
    and the per-tensor-shard head count still divides the sequence axis,
    the heads dim stays sharded over it — each tensor replica computes its
    own head group instead of all-gathering heads. jit composes these specs
    with the surrounding program."""
    batch = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    tp = mesh.shape.get(heads_axis, 1)
    heads = q.shape[2]
    seq_size = mesh.shape.get(seq_axis)
    if seq_size is None:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no {seq_axis!r} axis to run "
            f"Ulysses sequence parallelism over; build the mesh with a "
            f"sequence span (MeshSpec(sequence=...)) or call the dense "
            f"attention path instead"
        )

    def _local_kv_ok() -> bool:
        lkv = k.shape[2] // tp  # kv heads per tensor shard
        return lkv % seq_size == 0 or seq_size % lkv == 0

    use_heads_axis = (
        tp > 1
        and heads % tp == 0
        and (heads // tp) % seq_size == 0
        and k.shape[2] % tp == 0
        and _local_kv_ok()
    )
    spec = P(batch, seq_axis, heads_axis if use_heads_axis else None, None)
    kernel = functools.partial(
        ulysses_attention,
        axis_name=seq_axis,
        causal=causal,
        softmax_scale=softmax_scale,
        use_flash=use_flash,
    )
    if kv_mask is None:
        fn = jax.shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )
        return fn(q, k, v)
    mask_spec = P(batch, seq_axis)
    fn = jax.shard_map(
        lambda q, k, v, m: kernel(q, k, v, kv_mask=m),
        mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
    )
    return fn(q, k, v, kv_mask)
