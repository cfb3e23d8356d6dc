"""Chunked (vocab-blockwise) softmax cross-entropy for LM heads.

The dense LM loss path materializes float32 logits of shape (B, S, V) —
~1.6 GB per GPT-2 step at batch 8x1024xV50257 — writes them to HBM, then
re-reads them for the softmax/CE reduction, and does it all again in the
backward pass. On TPU that is pure HBM-bandwidth waste: the MXU produces
logits faster than HBM can hold them.

``chunked_softmax_xent`` fuses the tied-head matmul with the cross-entropy
reduction, streaming over vocabulary blocks:

- forward: one (N, D) x (D, Vb) matmul per block (bf16 operands, float32
  accumulation on the MXU) and ONE pass over the block's logits: a single
  variadic ``lax.reduce`` over the block's columns yields the block's max,
  its sum-exp relative to that max, its argmax id and its target-logit sum
  (select-by-column-id, no dynamic gather), and the same combiner folds the
  four into the running ``(m, s, best_i, tl)`` carried across blocks. Peak
  live logits are (N, Vb) f32 instead of (N, V).
- the combiner (``_combine``) is the online softmax's merge of two partial
  results over disjoint columns, ``(m1, s1) + (m2, s2) = (max, s1 e^(m1-max)
  + s2 e^(m2-max))``, with one ``exp`` a merge (the side holding the max is
  not rescaled), the argmax id and the target sum carried beside. It is
  associative and commutative in effect, so the compiler may group a block's
  columns as it likes. Ties go to the LOWER column id, within a block and
  across blocks (``jnp.argmax``'s first occurrence). Equal maxima rescale
  by exactly 1 through a ``where``, so ``-inf - (-inf)`` never makes a NaN
  out of the ``-inf`` identity, of a ``-inf`` bias column or of a whole
  ``-inf`` block. A NaN or an inf in ``x`` still reaches the loss as a
  non-finite number (the step's sentinels and bad-step predication read it).
- backward (custom VJP): recomputes each logits block, forms
  ``(softmax - onehot) * g`` per block, accumulates ``dx`` across blocks and
  writes each embedding-gradient block to its own disjoint (Vb, D) slice —
  the (V, D) gradient is written exactly once, never read-modify-written.

How often a block of f32 logits crosses the HBM (the layer is bound by those
bytes, not by the MXU; read from the compiled v5e program, pinned in
``tests/test_chip_compile.py``):

- free schedule (the one-chip cells): XLA CSEs the backward's recomputed
  logits against the forward's, so each block is written once, kept, and
  read THREE times: by the forward's one reduction, by the ``dx`` product and
  by the table-gradient product. (The two-pass forward this replaced read it
  four times: max + argmax first, because ``exp(logits - max)`` needs the
  max before it can start.)
- serialized schedule (``serial``, below): nothing is shared with the
  backward, the compiler puts the forward's product INSIDE the reduction
  and the forward's f32 logits are never written (but for the block before
  the first barrier); the backward's recomputed product writes the bf16
  ``(softmax - onehot) * g`` once and its two products read that.
- a forward alone (evaluation): every product sits inside its reduction;
  no block of logits is written at all.

The block loop is a fully UNROLLED Python loop over static slices, not a
``lax.scan``: ~13 blocks cost nothing to unroll, while the scan's while-loop
machinery measured ~20% of a whole GPT-2 train step in the profiler (and
hid the loop FLOPs from XLA's cost analysis, wrecking MFU accounting).
Static slices also mean no padded copy of the embedding table and no
valid-column masking — the last block is simply narrower.

Loss semantics match ``optax.softmax_cross_entropy_with_integer_labels`` on
float32 logits (the reference's ``nn.CrossEntropyLoss``, reference
train.py:250) to float32 rounding; equivalence is pinned in
``tests/test_chunked_ce.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_BLOCK = 4096

# Unrolled blocks have NO data dependence between their (N, Vb) logits
# matmuls (only the scalar running reductions chain), so XLA's scheduler
# may compute MANY blocks concurrently — at 64k tokens that is 13 x 1 GB
# f32 logit blocks live at once and an HBM OOM (measured: 22.8 G needed
# on the 16 G chip). When the all-blocks-concurrent worst case (N x V f32
# — the guard must key on the TOTAL, or shrinking block_size re-creates
# the same many-small-blocks schedule) exceeds _SERIALIZE_TOTAL_BYTES,
# the loops thread an optimization_barrier through the carries so block
# k+1's matmul cannot start before block k is consumed, and blocks wider
# than _SERIALIZE_BLOCK_BYTES also shrink (XLA's remat pass clones a few
# matmuls outside any barrier chain; small blocks bound the clones too).
# The budget is deliberately ABOVE bench scale (GPT-2 1024 x batch 16 is
# 3.3 GB): when memory is rich, XLA CSEs the backward's per-block logits
# recompute against the forward's logits — a free ~1.2 TFLOP/step win the
# barriers would forfeit (measured -2.7% tok/s with a 2 GiB budget).
# Serialization is for where that trade inverts: the memory-bound
# long-context regime.
_SERIALIZE_TOTAL_BYTES = 4 * 1024 * 1024 * 1024
_SERIALIZE_BLOCK_BYTES = 384 * 1024 * 1024


def _block_logits(x, e_blk, b_blk, dtype):
    """f32 logits of one vocab block: (N, D) x (Vb, D)^T [+ bias]."""
    out = lax.dot_general(
        x, e_blk.astype(dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if b_blk is not None:
        out = out + b_blk.astype(jnp.float32)
    return out


def _blocks(vocab: int, block_size: int):
    """Static (offset, width) spans covering [0, vocab); last may be narrow."""
    spans = []
    off = 0
    while off < vocab:
        spans.append((off, min(block_size, vocab - off)))
        off += block_size
    return spans


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _chunked_xent(x, embedding, bias, targets, block_size, dtype, serial):
    loss, argmax, _ = _forward(
        x, embedding, bias, targets, block_size, dtype, serial
    )
    return loss, argmax


def _combine(a, b):
    """Online-softmax merge of two partial ``(max, sum-exp relative to
    that max, argmax id, target-logit sum)`` over disjoint column sets
    (the rules: module docstring). The side that holds the larger max keeps
    its sum; the other is rescaled by ``exp(-|m1 - m2|)``, by exactly 1
    where the maxima are equal (two ``-inf`` differ by NaN).
    """
    m1, s1, i1, t1 = a
    m2, s2, i2, t2 = b
    same = m1 == m2
    e = jnp.where(same, 1.0, jnp.exp(-jnp.abs(m1 - m2)))
    s = jnp.where(m1 >= m2, s1 + s2 * e, s1 * e + s2)
    first = (m1 > m2) | (same & (i1 < i2))
    return jnp.maximum(m1, m2), s, jnp.where(first, i1, i2), t1 + t2


# the scope sits inside the custom VJP's own functions so that the primal,
# the forward residual pass and the backward ops all carry it in their op
# metadata (the device trace's chunked_ce_device_share reads it)
@jax.named_scope("chunked_ce")
def _forward(x, embedding, bias, targets, block_size, dtype, serial):
    n = x.shape[0]
    vocab = embedding.shape[0]
    # _combine's identity: the running (max, sum-exp, argmax id, target logit)
    identity = (
        jnp.float32(-jnp.inf), jnp.float32(0.0),
        jnp.int32(np.iinfo(np.int32).max), jnp.float32(0.0),
    )
    m, s, best_i, tl = (jnp.full((n,), v, v.dtype) for v in identity)
    first = True
    for off, width in _blocks(vocab, block_size):
        if serial and not first:
            # chain this block's matmul after the previous block's
            # reduction: bounds live f32 logits at one block
            x, m = lax.optimization_barrier((x, m))
        first = False
        e_blk = lax.slice_in_dim(embedding, off, off + width)
        b_blk = None if bias is None else lax.slice_in_dim(bias, off, off + width)
        logits = _block_logits(x, e_blk, b_blk, dtype)  # (N, width) f32
        col_ids = jnp.broadcast_to(  # global vocab ids
            off + jnp.arange(width, dtype=jnp.int32), logits.shape
        )
        # gather-free target term: exactly one column matches per row (or
        # none in this block), so a masked sum IS the gathered logit
        hit = col_ids == targets[:, None]
        # ONE reduction reads the block: every column enters as the partial
        # (its logit, sum-exp 1, its id, its logit if it is the target)
        block_stats = lax.reduce(
            (logits, jnp.ones_like(logits), col_ids,
             jnp.where(hit, logits, 0.0)),
            identity, _combine, (1,),
        )
        m, s, best_i, tl = _combine((m, s, best_i, tl), block_stats)
    lse = m + jnp.log(s)
    return lse - tl, best_i, lse


def _fwd(x, embedding, bias, targets, block_size, dtype, serial):
    loss, argmax, lse = _forward(
        x, embedding, bias, targets, block_size, dtype, serial
    )
    return (loss, argmax), (x, embedding, bias, targets, lse)


@jax.named_scope("chunked_ce")
def _bwd(block_size, dtype, serial, res, g):
    x, embedding, bias, targets, lse = res
    g_loss = g[0].astype(jnp.float32)  # argmax output is int: float0, ignored
    vocab = embedding.shape[0]
    dx = jnp.zeros(x.shape, jnp.float32)
    de_blocks = []
    db_blocks = []
    first = True
    for off, width in _blocks(vocab, block_size):
        if serial and not first:
            # backward blocks are fully independent (each reuses the saved
            # lse) — without the chain XLA schedules them all at once
            x, dx = lax.optimization_barrier((x, dx))
        first = False
        e_blk = lax.slice_in_dim(embedding, off, off + width)
        b_blk = None if bias is None else lax.slice_in_dim(bias, off, off + width)
        logits = _block_logits(x, e_blk, b_blk, dtype)  # (N, width) f32
        col_ids = off + jnp.arange(width)
        p = jnp.exp(logits - lse[:, None])
        onehot = (col_ids[None, :] == targets[:, None]).astype(jnp.float32)
        gmat = (p - onehot) * g_loss[:, None]  # (N, width) f32
        dx = dx + lax.dot_general(  # (N, D) += (N, Vb) x (Vb, D)
            gmat, e_blk.astype(dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        de_blocks.append(lax.dot_general(  # (Vb, D) = (N, Vb)^T x (N, D)
            gmat, x,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))
        if b_blk is not None:
            db_blocks.append(gmat.sum(axis=0))
    de = jnp.concatenate(de_blocks, axis=0)
    dbias = None
    if bias is not None:
        dbias = jnp.concatenate(db_blocks, axis=0).astype(bias.dtype)
    return (
        dx.astype(x.dtype),
        de.astype(embedding.dtype),
        dbias,
        np.zeros(targets.shape, dtype=jax.dtypes.float0),  # int input
    )


_chunked_xent.defvjp(_fwd, _bwd)


def _local_token_count(hidden, n: int) -> int:
    """Per-chip token count of ``hidden``'s leading dims for the HBM guard.

    The operand's COMMITTED sharding is the truth when it is available (a
    placed concrete array, or an aval carrying explicit sharding): count
    the tokens of ONE shard. When the layout is unknown — the usual case
    for an activation tracer inside jit — assume all ``n`` tokens are
    chip-resident: over-serializing an actually-sharded operand costs
    only perf, while sizing a replicated operand by the mesh span (the
    old ``n // data_parallel_size(mesh)``) under-counts by the span and
    disengages the guard in exactly the memory-bound regime it protects.
    """
    try:
        sharding = getattr(hidden, "sharding", None)
    except Exception:
        sharding = None
    if sharding is None:
        try:
            sharding = getattr(jax.typeof(hidden), "sharding", None)
        except Exception:
            sharding = None
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            local = sharding.shard_shape(tuple(hidden.shape))
        except Exception:
            return n
        count = 1
        for d in local[:-1]:
            count *= int(d)
        return count
    return n


def chunked_softmax_xent(
    hidden: jax.Array,
    embedding: jax.Array,
    targets: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    block_size: int = DEFAULT_BLOCK,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Fused tied-head matmul + softmax cross-entropy, blockwise over vocab.

    Args:
      hidden: (..., D) final hidden states (any leading dims).
      embedding: (V, D) tied embedding / LM-head matrix (row-major vocab).
      targets: (...) int target token ids, same leading dims as ``hidden``.
      bias: optional (V,) logit bias (BERT's ``mlm_bias``).
      block_size: vocab block width; peak live logits are (N, block) f32.
      dtype: matmul operand dtype (bf16 keeps the MXU fed; accumulation is
        always float32).

    Returns:
      ``(loss, argmax)``: per-position f32 cross-entropy of shape (...) and
      the int32 argmax token id per position (for accuracy metrics) —
      numerically equal to the dense
      ``softmax_cross_entropy_with_integer_labels(f32_logits, targets)`` /
      ``argmax(logits)`` pair without materializing (..., V) f32 logits.
    """
    lead = hidden.shape[:-1]
    dim = hidden.shape[-1]
    if embedding.shape[-1] != dim:
        raise ValueError(
            f"hidden dim {dim} != embedding dim {embedding.shape[-1]}"
        )
    if targets.shape != lead:
        raise ValueError(
            f"targets shape {targets.shape} != hidden leading dims {lead}"
        )
    n = 1
    for d in lead:
        n *= d
    x = hidden.reshape(n, dim).astype(dtype)
    t = targets.reshape(n).astype(jnp.int32)
    # long-context guard — see the constants' comment: serialize when the
    # all-blocks-concurrent f32 logits could threaten HBM, and shrink
    # oversized blocks (lane-aligned, equal FLOPs) so XLA's remat clones
    # stay small too. The decision keys on the PER-CHIP token count,
    # derived from ``hidden``'s committed sharding when the layout is
    # known; with an unknown layout the guard assumes the full ``n`` is
    # resident. (The SP x PP chunk-local path calls this INSIDE shard_map
    # where n is already local and tiny, so the conservative fallback
    # stays off there.)
    n_shard = _local_token_count(hidden, n)
    block = int(block_size)
    serial = n_shard * embedding.shape[0] * 4 > _SERIALIZE_TOTAL_BYTES
    if serial and n_shard * block * 4 > _SERIALIZE_BLOCK_BYTES:
        max_block = _SERIALIZE_BLOCK_BYTES // (4 * max(n_shard, 1))
        block = max(512, (max_block // 512) * 512)
    loss, argmax = _chunked_xent(x, embedding, bias, t, block, dtype, serial)
    return loss.reshape(lead), argmax.reshape(lead)
