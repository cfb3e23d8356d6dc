"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism: ABSENT") but a TPU framework needs as
a first-class capability: when the sequence is sharded across devices on a
``sequence`` mesh axis, no device ever materializes full-sequence K/V.
Instead K/V chunks rotate around the ring via ``lax.ppermute`` (compiled to
ICI neighbor transfers) while each device folds every chunk into its local
queries' running (output, logsumexp) pair. Compute for the current chunk
overlaps with the transfer of the next (XLA's latency-hiding scheduler
handles it since the ppermute has no data dependence on the chunk fold).

Memory — forward AND backward — is O(S_local) per device:

- *forward*: each fold produces a normalized chunk output plus its
  logsumexp, merged into the running pair (``o·e^{lse-lse'} + o_i·e^{...}``);
  only (o, lse) persist between folds. Local folds use the Pallas flash
  kernel on TPU (O(block) VMEM, no S_local² logits in HBM) and an XLA
  softmax otherwise.
- *backward*: a ``custom_vjp`` replays the ring, recomputing each chunk's
  attention weights blockwise from the saved global ``lse`` (the flash
  delta trick lifted to the inter-chip level): dK/dV accumulators travel
  around the ring *with* their K/V chunk and arrive home after a full
  rotation. Without this, reverse-mode AD through the forward scan would
  save every fold's softmax weights — O(S_local · S_global) residuals,
  the very footprint ring attention exists to avoid.

``ring_attention`` is the per-device collective program (call under
``shard_map``); ``ring_attention_sharded`` wraps it for callers holding
global arrays.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# per-chunk local attention: (o, lse) forward, (dq, dk, dv) backward
# ---------------------------------------------------------------------------


def _pos_mask(idx, src, s_loc):
    """(s_loc, s_loc) bool: global causal validity of (local q, chunk k)."""
    q_pos = idx * s_loc + lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
    k_pos = src * s_loc + lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
    return (q_pos >= k_pos)[None, :, None, :]


def _expand_gqa(q, k, v):
    """Repeat kv heads up to q heads for the chunk einsums (GQA).

    Chunk-local and transient — O(S_chunk) extra memory per fold (the
    Ulysses side keeps per-device KV flat too, via its grouped exchange,
    ops/ulysses.py). q-head n reads kv-head n // group, matching the
    flash kernel's BlockSpec routing.
    """
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v, 1
    return (
        jnp.repeat(k, group, axis=2),
        jnp.repeat(v, group, axis=2),
        group,
    )


def _collapse_gqa(dk, dv, group):
    """Sum per-q-head kv grads back onto their kv head (GQA backward)."""
    if group == 1:
        return dk, dv
    b, s, n, h = dk.shape
    return (
        dk.reshape(b, s, n // group, group, h).sum(3),
        dv.reshape(b, s, n // group, group, h).sum(3),
    )


def _chunk_fwd_xla(q, k, v, mask, scale, causal, idx, src):
    """Normalized chunk attention + lse in XLA ops; (B,S,N,H) ring layout.

    ``mask``: optional (B, S_k_chunk) key-padding validity for THIS chunk's
    keys (True=attend), rotated around the ring with k/v. Rows with no
    valid key (chunk entirely above the causal diagonal, or all keys
    padded) emit lse ≈ NEG_INF, so their garbage output vanishes in the
    lse merge.
    """
    k, v, _ = _expand_gqa(q, k, v)
    logits = jnp.einsum(
        "bqnh,bknh->bqnk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        logits = jnp.where(_pos_mask(idx, src, q.shape[1]), logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bqnk,bknh->bqnh", p, v.astype(jnp.float32)) / l
    return o, m + jnp.log(l)  # lse: (B, S, N, 1)


def _chunk_bwd_xla(q, k, v, mask, g, lse, delta, scale, causal, idx, src):
    """Chunk grads from the saved global lse; all math in float32."""
    k, v, group = _expand_gqa(q, k, v)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf = g.astype(jnp.float32)
    logits = jnp.einsum("bqnh,bknh->bqnk", qf, kf) * scale
    if causal:
        logits = jnp.where(_pos_mask(idx, src, q.shape[1]), logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    # p: GLOBAL softmax weights for this chunk's keys (lse spans all chunks)
    p = jnp.exp(logits - lse)
    if mask is not None:
        # fully-padded rows carry lse = NEG_INF: exp(NEG_INF - NEG_INF)
        # garbage must not leak into dv/dk
        p = jnp.where(mask[:, None, None, :], p, 0.0)
    dv = jnp.einsum("bqnk,bqnh->bknh", p, gf)
    dp = jnp.einsum("bqnh,bknh->bqnk", gf, vf)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bqnk,bknh->bqnh", ds, kf)
    dk = jnp.einsum("bqnk,bqnh->bknh", ds, qf)
    dk, dv = _collapse_gqa(dk, dv, group)
    return dq, dk, dv


def _chunk_fwd_flash(q, k, v, mask, scale, causal, idx, src, interpret):
    """Pallas-flash chunk fold: O(block) VMEM, returns (o f32, lse).

    ``mask``: optional (B, S_k_chunk) key validity for this chunk, fed to
    the flash kernel's kv_mask port as (B, 1, S_k) float.

    The (idx, src) relation picks the static kernel variant via
    ``lax.switch``: fully-visible chunk (non-causal kernel), diagonal chunk
    (causal kernel — local offsets coincide so the local mask is exact),
    or fully-masked chunk (skip: zero output at lse=NEG_INF merges to a
    no-op).
    """
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK,
        _fit_block,
        _fwd,
    )

    s_loc = q.shape[1]
    block = _fit_block(s_loc, DEFAULT_BLOCK)  # must DIVIDE s_loc, not just cap it
    kvm = None if mask is None else mask.astype(jnp.float32)[:, None, :]

    def run(causal_flag):
        def f(q, k, v, kvm):
            qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            out, lse = _fwd(
                qt, kt, vt, kvm, causal_flag, scale, block, block, interpret
            )
            return (
                out.transpose(0, 2, 1, 3).astype(jnp.float32),
                lse.transpose(0, 2, 1, 3),  # (B, N, S, 1) -> (B, S, N, 1)
            )

        return f

    if not causal:
        return run(False)(q, k, v, kvm)

    def skip(q, k, v, kvm):
        from distributed_pytorch_example_tpu.parallel.api import pvary_like

        b, s, n, h = q.shape
        return pvary_like(
            (
                jnp.zeros((b, s, n, h), jnp.float32),
                jnp.full((b, s, n, 1), NEG_INF, jnp.float32),
            ),
            q,
        )

    mode = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
    return lax.switch(mode, [run(False), run(True), skip], q, k, v, kvm)


def _chunk_bwd_flash(q, k, v, mask, g, lse, delta, scale, causal, idx, src,
                     interpret):
    """Pallas-flash chunk backward from the global lse/delta."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK,
        _bwd,
        _fit_block,
    )

    s_loc = q.shape[1]
    block = _fit_block(s_loc, DEFAULT_BLOCK)  # must DIVIDE s_loc, not just cap it
    kvm = None if mask is None else mask.astype(jnp.float32)[:, None, :]

    def run(causal_flag):
        def f(q, k, v, kvm, g, lse, delta):
            qt, kt, vt, gt = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
            dq, dk, dv = _bwd(
                qt, kt, vt, None, lse.transpose(0, 2, 1, 3), gt, kvm,
                causal_flag, scale, block, block, interpret,
                delta=delta.transpose(0, 2, 1, 3),
            )
            return tuple(
                x.transpose(0, 2, 1, 3).astype(jnp.float32)
                for x in (dq, dk, dv)
            )

        return f

    if not causal:
        return run(False)(q, k, v, kvm, g, lse, delta)

    def skip(q, k, v, kvm, g, lse, delta):
        from distributed_pytorch_example_tpu.parallel.api import pvary_like

        return pvary_like(
            (
                jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32),
            ),
            q,
        )

    mode = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
    return lax.switch(
        mode, [run(False), run(True), skip], q, k, v, kvm, g, lse, delta
    )


# ---------------------------------------------------------------------------
# the ring program (custom VJP)
# ---------------------------------------------------------------------------


def _merge(o, lse, o_i, lse_i):
    """Merge two normalized (output, logsumexp) pairs."""
    lse_n = jnp.logaddexp(lse, lse_i)
    return (
        o * jnp.exp(lse - lse_n) + o_i * jnp.exp(lse_i - lse_n),
        lse_n,
    )


def _ring_fwd_impl(q, k, v, kv_mask, axis_name, causal, scale, flash,
                   interpret):
    n_chunks = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    batch, s_loc, heads, head_dim = q.shape
    shift = [(i, (i + 1) % n_chunks) for i in range(n_chunks)]
    has_mask = kv_mask is not None
    # the mask chunk travels around the ring WITH its k/v chunk (float32:
    # ppermute of sub-byte bools is wasteful on some backends, and the
    # flash kernel wants float anyway)
    m0 = kv_mask.astype(jnp.float32) if has_mask else None

    def fold(o, lse, k_cur, v_cur, m_cur, src):
        mask = (m_cur > 0.0) if has_mask else None
        if flash:
            o_i, lse_i = _chunk_fwd_flash(
                q, k_cur, v_cur, mask, scale, causal, idx, src, interpret
            )
        else:
            o_i, lse_i = _chunk_fwd_xla(
                q, k_cur, v_cur, mask, scale, causal, idx, src
            )
        return _merge(o, lse, o_i, lse_i)

    o0 = jnp.zeros((batch, s_loc, heads, head_dim), jnp.float32)
    lse0 = jnp.full((batch, s_loc, heads, 1), NEG_INF, jnp.float32)
    from distributed_pytorch_example_tpu.parallel.api import pvary_like

    o0, lse0 = pvary_like((o0, lse0), q)

    def body(carry, step):
        if has_mask:
            k_cur, v_cur, m_cur, o, lse = carry
        else:
            k_cur, v_cur, o, lse = carry
            m_cur = None
        # start rotating the chunk we hold, then fold it: the transfer has
        # no dependence on the fold, so XLA overlaps them
        k_nxt = lax.ppermute(k_cur, axis_name, shift)
        v_nxt = lax.ppermute(v_cur, axis_name, shift)
        src = (idx - step) % n_chunks  # ring owner of the chunk we hold
        o, lse = fold(o, lse, k_cur, v_cur, m_cur, src)
        if has_mask:
            m_nxt = lax.ppermute(m_cur, axis_name, shift)
            return (k_nxt, v_nxt, m_nxt, o, lse), None
        return (k_nxt, v_nxt, o, lse), None

    if n_chunks > 1:
        # scan folds chunks 0..n-2 with rotation; the last chunk folds
        # outside so the ring makes exactly n-1 transfers (none discarded)
        carry0 = (k, v, m0, o0, lse0) if has_mask else (k, v, o0, lse0)
        carry, _ = lax.scan(body, carry0, jnp.arange(n_chunks - 1))
        if has_mask:
            k_last, v_last, m_last, o, lse = carry
        else:
            (k_last, v_last, o, lse), m_last = carry, None
        o, lse = fold(
            o, lse, k_last, v_last, m_last, (idx - (n_chunks - 1)) % n_chunks
        )
    else:
        o, lse = fold(o0, lse0, k, v, m0, idx)
    if has_mask:
        # rows whose keys are masked in EVERY chunk: each fold emitted
        # garbage at lse ~ NEG_INF, and with no finite-lse chunk to win the
        # merge the garbage survives (the XLA fold's o is mean-of-values,
        # not zero). Dense-path parity: zero output for fully-padded rows.
        # (The backward needs no twin guard: its per-chunk re-mask already
        # zeroes p for masked columns.)
        o = jnp.where(lse <= NEG_INF * 0.5, 0.0, o)
    return o.astype(q.dtype), lse


def _ring_bwd_impl(q, k, v, kv_mask, out, lse, g, axis_name, causal, scale,
                   flash, interpret):
    n_chunks = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    shift = [(i, (i + 1) % n_chunks) for i in range(n_chunks)]
    has_mask = kv_mask is not None
    m0 = kv_mask.astype(jnp.float32) if has_mask else None
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    def chunk_bwd(k_cur, v_cur, m_cur, src):
        mask = (m_cur > 0.0) if has_mask else None
        if flash:
            return _chunk_bwd_flash(
                q, k_cur, v_cur, mask, g, lse, delta, scale, causal, idx, src,
                interpret,
            )
        return _chunk_bwd_xla(
            q, k_cur, v_cur, mask, g, lse, delta, scale, causal, idx, src
        )

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    from distributed_pytorch_example_tpu.parallel.api import pvary_like

    dq0, dk0, dv0 = pvary_like((dq0, dk0, dv0), q)

    def unpack(carry):
        if has_mask:
            return carry
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        return k_cur, v_cur, None, dk_cur, dv_cur, dq

    def accumulate(carry, step):
        k_cur, v_cur, m_cur, dk_cur, dv_cur, dq = unpack(carry)
        src = (idx - step) % n_chunks
        dq_i, dk_i, dv_i = chunk_bwd(k_cur, v_cur, m_cur, src)
        # dK/dV accumulators travel WITH their chunk: after the full
        # rotation (n_chunks steps) they arrive back at the chunk's owner
        return k_cur, v_cur, m_cur, dk_cur + dk_i, dv_cur + dv_i, dq + dq_i

    def body(carry, step):
        k_cur, v_cur, m_cur, dk_cur, dv_cur, dq = accumulate(carry, step)
        k_cur = lax.ppermute(k_cur, axis_name, shift)
        v_cur = lax.ppermute(v_cur, axis_name, shift)
        dk_cur = lax.ppermute(dk_cur, axis_name, shift)
        dv_cur = lax.ppermute(dv_cur, axis_name, shift)
        if has_mask:
            m_cur = lax.ppermute(m_cur, axis_name, shift)
            return (k_cur, v_cur, m_cur, dk_cur, dv_cur, dq), None
        return (k_cur, v_cur, dk_cur, dv_cur, dq), None

    carry = (k, v, m0, dk0, dv0, dq0) if has_mask else (k, v, dk0, dv0, dq0)
    if n_chunks > 1:
        # last step outside the scan: the K/V shards are done after it, so
        # only the dK/dV accumulators take the final homeward transfer
        carry, _ = lax.scan(body, carry, jnp.arange(n_chunks - 1))
    _, _, _, dk, dv, dq = accumulate(carry, n_chunks - 1)
    if n_chunks > 1:
        dk = lax.ppermute(dk, axis_name, shift)
        dv = lax.ppermute(dv, axis_name, shift)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring(q, k, v, kv_mask, axis_name, causal, scale, flash, interpret):
    out, _ = _ring_fwd_impl(
        q, k, v, kv_mask, axis_name, causal, scale, flash, interpret
    )
    return out


def _ring_fwd(q, k, v, kv_mask, axis_name, causal, scale, flash, interpret):
    out, lse = _ring_fwd_impl(
        q, k, v, kv_mask, axis_name, causal, scale, flash, interpret
    )
    # compact the (B, S, N, 1) lse for the RESIDUAL: the trailing
    # singleton tiles T(8, 128) at 128x the bytes (the same pathology
    # fixed at flash_attention._flash_fwd) — at long local sequence that
    # is hundreds of padded MB per layer held across the backward
    return out, (q, k, v, kv_mask, out, lse[..., 0])


def _ring_bwd(axis_name, causal, scale, flash, interpret, residuals, g):
    import numpy as np

    q, k, v, kv_mask, out, lse = residuals
    dq, dk, dv = _ring_bwd_impl(
        q, k, v, kv_mask, out, lse[..., None], g, axis_name, causal, scale,
        flash, interpret,
    )
    dmask = None
    if kv_mask is not None:
        dmask = (
            np.zeros(kv_mask.shape, dtype=jax.dtypes.float0)
            if not jnp.issubdtype(kv_mask.dtype, jnp.floating)
            else jnp.zeros_like(kv_mask)
        )
    return dq, dk, dv, dmask


_ring.defvjp(_ring_fwd, _ring_bwd)


def _flash_viable(q, interpret: bool) -> bool:
    """Static check: can the Pallas kernels serve the local folds?"""
    from distributed_pytorch_example_tpu.ops.attention import _on_tpu

    s_loc, head_dim = q.shape[1], q.shape[-1]
    shapes_ok = (
        s_loc % 128 == 0
        and head_dim in (64, 128, 256)
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )
    return shapes_ok and (interpret or _on_tpu())


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    flash_interpret: bool = False,
) -> jax.Array:
    """Exact attention with K/V ring rotation; call inside ``shard_map``.

    Args:
      q, k, v: local shards (batch, seq_local, heads, head_dim), sharded on
        the sequence dimension over ``axis_name``.
      kv_mask: optional (batch, seq_local) key-padding validity shard
        (True=attend), sharded on the sequence dim like k/v — what real
        padded BERT batches need. The mask chunk rotates around the ring
        with its k/v chunk and streams through the flash kernel's kv_mask
        port; fully-padded rows produce zero output and zero gradients.
      causal: global causal masking — positions are reconstructed from the
        ring index, so the mask is exact across shard boundaries.
      use_flash: None = auto (Pallas local folds on TPU when shapes allow),
        True/False = force. ``flash_interpret`` runs the Pallas kernels in
        interpret mode (CPU tests of the flash-in-ring path).

    Returns the local output shard (batch, seq_local, heads, head_dim).
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q heads ({q.shape[2]}) must be a multiple of kv heads "
            f"({k.shape[2]}) for GQA"
        )
    if kv_mask is not None and kv_mask.shape != (q.shape[0], k.shape[1]):
        raise ValueError(
            f"kv_mask shape {kv_mask.shape} != (batch, seq_local) "
            f"({q.shape[0]}, {k.shape[1]})"
        )
    if use_flash is None:
        flash = _flash_viable(q, flash_interpret)
    else:
        flash = use_flash
        if flash and not _flash_viable(q, flash_interpret):
            raise ValueError(
                "use_flash=True but the flash kernel cannot serve these "
                f"ring shapes (seq_local {q.shape[1]}, head_dim "
                f"{q.shape[-1]}, dtype {q.dtype})"
            )
    return _ring(
        q, k, v, kv_mask, axis_name, causal, float(softmax_scale), flash,
        flash_interpret,
    )


def ring_attention_sharded(
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
    """Ring attention on global (B, S, N, H) arrays: shard, ring, unshard.

    The batch dim shards over ``batch_axes``, the sequence dim over
    ``seq_axis``, and — when the mesh spans a ``heads_axis`` (tensor
    parallelism) and the head count divides — the heads dim over it, so
    TP+SP runs each head group once instead of all-gathering heads and
    computing them redundantly per tensor replica. jit composes these specs
    with the surrounding program's shardings.

    ``kv_mask``: optional GLOBAL (B, S) key-padding validity; sharded on
    (batch, sequence) like k/v and rotated around the ring per shard.
    """
    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    heads = q.shape[2]
    tp = mesh.shape.get(heads_axis, 1)
    # with GQA the k/v heads dim is smaller; all three arrays share one
    # spec, so the heads axis engages only when BOTH divide
    use_heads_axis = tp > 1 and heads % tp == 0 and k.shape[2] % tp == 0
    spec = P(batch_axes, seq_axis, heads_axis if use_heads_axis else None, None)
    kernel = functools.partial(
        ring_attention,
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
    mask_spec = P(batch_axes, seq_axis)
    fn = jax.shard_map(
        lambda q, k, v, m: kernel(q, k, v, kv_mask=m),
        mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
    )
    return fn(q, k, v, kv_mask)
