"""Fused Pallas flash-decode attention over the paged KV pool.

The XLA fallback in ``models/transformer.py::_paged_step`` decodes by
gathering every table entry out of the block pool (``jnp.take`` over
``(max_blocks,)`` indices per row) and running dense attention over the
materialized ``(batch, max_blocks * block_size, kv_heads, head_dim)``
cache — every token, every row, live or not. This kernel removes both
costs:

- **Scalar-prefetched block table** (``pltpu.PrefetchScalarGridSpec``,
  the SNIPPETS.md [1] idiom): the page table and row lengths arrive in
  SMEM before the kernel body runs, so each grid step's BlockSpec index
  map resolves ``table[b, j]`` and DMAs exactly that KV block from the
  pool in HBM into VMEM. The gathered cache is never materialized.
- **Online softmax** (the flash_attention.py running ``m``/``l``/``acc``
  pattern) over one block at a time, entirely in VMEM.
- **Live-block skip**: blocks past ``row_lens[b]`` contribute nothing,
  so their compute is skipped under ``pl.when`` (their DMA still lands —
  dead table entries point at the scratch block — but the FLOPs don't).

Grid is ``(batch, max_blocks)`` with the block sweep innermost so the
output block and the softmax scratch stay resident across the sweep.
One grid step takes ALL kv heads of a KV block — the block's trailing
dims are the pool's own ``(kv_heads, head_dim)``, which is what the TPU
lowering's (8, 128) tile rule accepts (a one-head slice of the
second-minor dim is refused). A decode query is one row per head, so
the scores are a broadcast-multiply and a lane reduce on the VPU, heads
on sublanes; grouped queries (GQA) loop over the ``group = num_heads //
kv_heads`` queries that share a KV head.

Gating mirrors ``ring_supported()`` (ops/pallas/collectives.py): the
kernel only lowers on the TPU backend, and ``paged_decode_attention``
routes to ``paged_attention_reference`` — bit-identical to the
pre-kernel ``_paged_step`` gather path by construction — off-TPU, under
an active ``with mesh:`` context (the sharded pool is partitioned by
XLA, which cannot split a ``pallas_call``; a shard_mapped variant is
future work), and for multi-token verify chunks; each such route is
logged once per compile. The 8-device virtual CPU mesh the tests run on
therefore always serves through the XLA path, while tests drive the
kernel itself with ``interpret=True`` and pin it to the reference at
tolerance (tests/test_paged_attention.py); tests/test_chip_compile.py
compiles it for a described v5e at GPT-2 width.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.logging import get_logger
from ...runtime.mesh import current_mesh
from ..attention import dot_product_attention

logger = get_logger(__name__)

NEG_INF = -1e30  # matches flash_attention.py: finite, exp() underflows to 0


def paged_decode_supported() -> bool:
    """True when the fused paged-decode kernel can lower on this backend."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Fused kernel
# ---------------------------------------------------------------------------


def _decode_kernel(
    # scalar-prefetch refs come first (PrefetchScalarGridSpec contract)
    table_ref,  # (batch, max_blocks) int32 in SMEM
    lens_ref,  # (batch,) int32 in SMEM
    q_ref,  # (1, group, kv_heads, head_dim)
    k_ref,  # (1, block_size, kv_heads, head_dim) — the block table[b, j]
    v_ref,  # (1, block_size, kv_heads, head_dim)
    o_ref,  # (1, group, kv_heads, head_dim)
    acc_ref,  # (group, kv_heads, head_dim) f32 scratch
    m_ref,  # (group, kv_heads, 128) f32 scratch, lane-replicated running max
    l_ref,  # (group, kv_heads, 128) f32 scratch, lane-replicated running sum
    *,
    block_size: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1
    pos = lens_ref[b]  # absolute position of this row's single query
    group, kv_heads, head_dim = acc_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block j covers key positions [j*bs, (j+1)*bs); live iff it holds
    # at least one visible key (key_pos <= pos)
    @pl.when(j * block_size <= pos)
    def _block():
        # one query per head is a matrix-vector product: all heads of the
        # block go through the VPU at once (broadcast-multiply + lane
        # reduce), keys on the leading dim, heads on sublanes
        k = k_ref[0].astype(jnp.float32)  # (block_size, kv_heads, head_dim)
        v = v_ref[0].astype(jnp.float32)
        key_pos = j * block_size + lax.broadcasted_iota(
            jnp.int32, (block_size, kv_heads, 1), 0
        )
        visible = key_pos <= pos
        for g in range(group):  # static: GQA queries sharing these keys
            q = q_ref[0, g].astype(jnp.float32)  # (kv_heads, head_dim)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(visible, s, NEG_INF)  # (block_size, kv_heads, 1)
            m_prev = m_ref[g]  # (kv_heads, 128), all lanes equal
            l_prev = l_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])  # (block_size, kv_heads, 1)
            m_ref[g] = m_new
            l_ref[g] = alpha * l_prev + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * alpha[:, :1] + jnp.sum(p * v, axis=0)

    @pl.when(j == last)
    def _finalize():
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_flash_decode(
    q,  # (batch, num_heads, head_dim) — the single decode query per row
    pages_k,  # (num_blocks, block_size, kv_heads, head_dim)
    pages_v,  # same
    page_table,  # (batch, max_blocks) int32, dead entries -> scratch block
    row_lens,  # (batch,) int32 — absolute position of the query per row
    *,
    interpret: bool = False,
):
    """Fused single-token paged attention; returns (batch, heads, head_dim)."""
    batch, num_heads, head_dim = q.shape
    _, block_size, kv_heads, _ = pages_k.shape
    max_blocks = page_table.shape[1]
    if num_heads % kv_heads:
        raise ValueError(f"{num_heads=} not divisible by {kv_heads=}")
    group = num_heads // kv_heads
    scale = 1.0 / math.sqrt(head_dim)

    # group-major so q_ref[0, g] is one (kv_heads, head_dim) tile aligned
    # with the KV block's trailing dims (head h = kv * group + g)
    qg = q.reshape(batch, kv_heads, group, head_dim).transpose(0, 2, 1, 3)

    # every block's trailing two dims are the array's own (kv_heads,
    # head_dim): the TPU lowering refuses a second-minor block dim of 1
    # over kv_heads, so one grid step takes all the heads of a KV block
    q_spec = pl.BlockSpec(
        (1, group, kv_heads, head_dim), lambda b, j, tbl, lens: (b, 0, 0, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_size, kv_heads, head_dim),
        lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, row_lens
        grid=(batch, max_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, kv_heads, head_dim), jnp.float32),
            pltpu.VMEM((group, kv_heads, 128), jnp.float32),
            pltpu.VMEM((group, kv_heads, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_size=block_size, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, group, kv_heads, head_dim), q.dtype
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), row_lens.astype(jnp.int32), qg, pages_k, pages_v)
    return out.transpose(0, 2, 1, 3).reshape(batch, num_heads, head_dim)


# ---------------------------------------------------------------------------
# XLA reference / fallback
# ---------------------------------------------------------------------------


def paged_attention_reference(q, pages_k, pages_v, page_table, positions):
    """Gather-based paged attention — the exact pre-kernel XLA path.

    Bit-identical to the decode branch ``_paged_step`` shipped before the
    fused kernel existed (same ``jnp.take`` gather, same mask, same
    ``dot_product_attention`` call), generalized to ``seq >= 1`` queries
    per row for the speculative-verify chunk: ``positions`` is the
    absolute position of each query, ``(batch, seq)``, and each query
    attends keys at ``key_pos <= positions[b, s]``.
    """
    batch, seq, num_heads, head_dim = q.shape
    _, block_size, kv_heads, _ = pages_k.shape
    max_blocks = page_table.shape[1]
    gk = jnp.take(pages_k, page_table, axis=0).reshape(
        batch, max_blocks * block_size, kv_heads, head_dim
    )
    gv = jnp.take(pages_v, page_table, axis=0).reshape(
        batch, max_blocks * block_size, kv_heads, head_dim
    )
    key_pos = jnp.arange(max_blocks * block_size)[None, None, None, :]
    visible = key_pos <= positions[:, None, :, None]
    return dot_product_attention(
        q, gk, gv, mask=visible, causal=False, use_flash=False
    )


def paged_decode_attention(
    q,  # (batch, seq, num_heads, head_dim)
    pages_k,
    pages_v,
    page_table,
    positions,  # (batch, seq) absolute query positions
    *,
    interpret: bool = False,
):
    """Dispatch paged attention: fused kernel when it lowers, XLA otherwise.

    The kernel path engages for single-token decode (``seq == 1``) when
    the backend is TPU and no mesh context is active (a sharded pool
    would require a shard_mapped kernel; XLA partitions the fallback
    fine, so TP-sharded KV heads keep working through it). Verify chunks
    (``seq > 1``) and everything off-TPU take the reference path, which
    is bit-exact vs the historical gather decode.
    """
    seq = q.shape[1]
    if seq != 1:
        route = f"a {seq}-token verify chunk"
    elif interpret:
        route = None
    elif not paged_decode_supported():
        route = f"backend {jax.default_backend()!r} (the kernel is TPU-only)"
    elif current_mesh() is not None:
        route = "an active mesh context (XLA partitions the sharded pool)"
    else:
        route = None
    if route is None:
        out = paged_flash_decode(
            q[:, 0],
            pages_k,
            pages_v,
            page_table,
            positions[:, 0],
            interpret=interpret,
        )
        return out[:, None]
    # trace-time: fires once per compile of the program it is traced into
    logger.info("paged attention: XLA gather reference path for %s", route)
    return paged_attention_reference(q, pages_k, pages_v, page_table, positions)
