"""Flash attention for TPU in Pallas: fused online-softmax, O(S) HBM traffic.

Forward: for each (batch, head, q-block), stream k/v blocks through VMEM,
maintaining the online-softmax running max ``m``, normalizer ``l``, and
accumulator in float32 VMEM scratch; one MXU matmul per (q-block, k-block)
pair for logits and one for the value update. Emits the per-row logsumexp so
the backward pass can reconstruct softmax weights without re-reducing.

Backward: ONE fused kernel on the k-block-major grid computes dq, dk, dv
from a single logits recompute per block pair, using the saved logsumexp
and the precomputed ``delta = rowsum(dO * O)`` (a cheap elementwise reduce
left to XLA, which fuses it). dk/dv accumulate in per-k-block VMEM scratch;
dq accumulates in a persistent VMEM scratch spanning the q sequence and is
emitted on each block's last visit (output blocks cannot accumulate across
non-consecutive revisits — Mosaic does not flush/reload them). When both
sequences fit one tile, a single-tile variant skips the grid entirely; when
the dq scratch would exceed ``_FUSED_DQ_VMEM_LIMIT``, the historical
two-kernel split (separate dq and dk/dv passes, two logits recomputes)
serves as the fallback.

Causal masking skips masked work on every path, down to static sub-tiles.
Multi-block grid (``_when_by_block_kind``: two ``pl.when`` bodies in the one
``pallas_call``, so the kernels keep their names): a (q-block, k-block) pair
above the diagonal skips its compute (``needed``) or is never scheduled (the
folded triangular grid); a pair wholly UNDER it runs the body with no mask
traced into it; a pair ON it walks sub-tiles and leaves what no query of
them sees uncomputed. Backward (fused and two-kernel): sub-tiles of
``CAUSAL_SUB`` rows, key-major, the ``sub x sub`` tile on the diagonal alone
masked (136 of 256 sub-tile pairs at S 4096, 528 of 1024 at S 8192:
``causal_visited_pairs``). Forward: query sub-tiles of
``CAUSAL_SUB_FWD_BLOCK`` rows, each one product against its key prefix,
folded into the running online softmax together (3 of 4 pairs of a block).
Blocks that are not square, or too short to cut (``_causal_sub`` gives 0),
keep the whole-block mask on the diagonal. Single tile (S == one block,
GPT-2 at 1024): the kernel body walks sub-tiles of ``CAUSAL_SUB`` rows and
gives each only the key prefix it can see (``flash_fwd_single_causal`` /
``flash_bwd_single_causal``: 10 of 16 sub-tile pairs at S 1024), masking the
diagonal sub-tiles alone. Non-causal calls run the whole-block bodies.

Widths: q and k share ``head_dim`` (what the scores contract over); v, the
output and their gradients are ``v_dim`` wide, which may differ (latent
attention trains at 192 / 128). Every BlockSpec, accumulator and gradient
output takes its width from the operand it belongs to; at equal widths the
calls are the ones a single ``head_dim`` gave.

Layout: (batch, seq, heads, head_dim) at the boundary — transposed to
(batch, heads, seq, head_dim) internally so the seq x head_dim tiles are
contiguous MXU operands.

Block sizes default to 1024x1024 (see flash_attention()'s docstring for
what was measured; _fit_block shrinks them lane-aligned for shorter
sequences). ``interpret=True`` runs the same kernels on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free

# default block size (see flash_attention()'s docstring for the v5e
# readings); ring attention's local folds import this so a retune happens
# in ONE place
DEFAULT_BLOCK = 1024


def _fit_block(seq: int, requested: int) -> int:
    """Largest block <= requested that divides seq (lane-aligned when possible)."""
    b = min(requested, seq)
    while b > 128 and seq % b:
        b -= 128
    return b if seq % b == 0 else min(requested, seq)


# rows per query sub-tile of the causal walks: one causal tile (see
# _fwd_single_causal_kernel) and the backward's blocks on the diagonal of
# the multi-block grid; fitted to the tile or block by _causal_sub
CAUSAL_SUB = 256
# ... of the multi-block FORWARD's blocks on the diagonal, where every
# sub-tile pays an online-softmax fold of its own: on the v5e 512 rows
# (3 of 4 sub-tile pairs) read faster than 256 (10 of 16) at q/k 192 and
# as fast at 64, the backward and the single tile the other way round
# (PERF.md, PR 34)
CAUSAL_SUB_FWD_BLOCK = 512


def _causal_sub(seq: int, rows: Optional[int] = None) -> int:
    """Sub-tile rows for a causal tile or diagonal block of ``seq`` rows:
    the largest multiple of 128 <= ``rows`` (CAUSAL_SUB by default) that
    divides ``seq`` into two or more sub-tiles, or 0 (the whole-tile body
    runs) where there is none."""
    b = min(CAUSAL_SUB if rows is None else rows, seq // 2) // 128 * 128
    while b and seq % b:
        b -= 128
    return b


def _causal_prefixes(seq: int, sub: int):
    """(first row, visible key prefix) of each query sub-tile: rows
    [row, row + sub) see keys [0, row + sub) and nothing after."""
    return [(row, row + sub) for row in range(0, seq, sub)]


def causal_visited_pairs(seq: int, sub: int, block: Optional[int] = None):
    """(visited, total) sub x sub tile pairs of the seq x seq causal
    square, cut in square blocks of ``block`` rows (one tile by default):
    a block under the diagonal is visited whole, a block on it gives each
    query sub-tile only its key prefix, a block above it is not visited."""
    block = block or seq
    blocks, side = seq // block, block // sub
    on = sum(prefix // sub for _, prefix in _causal_prefixes(block, sub))
    under = blocks * (blocks - 1) // 2 * side ** 2
    return under + blocks * on, (blocks * side) ** 2


def _apply_causal_mask(s, row0, col0):
    """Top-left-aligned causal mask on a logit tile whose first query is
    row ``row0`` and whose first key is column ``col0`` of the sequence.

    Valid for seq_q == seq_k (the dispatcher rejects causal cross-length
    calls); shared by the forward and both backward kernels so the
    alignment can never diverge between them.
    """
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, NEG_INF)


def _stack_rows(parts):
    """Row sub-tiles back into one block (one part: the block itself)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _when_by_block_kind(causal, i, j, block_q, block_k, needed, block,
                        diagonal, sub_rows=None):
    """Give the block pair (i, j) of a multi-block grid the body it needs.

    Not causal: ``block(False)`` where ``needed``. Causal, by where the
    pair lies (pairs above the diagonal are not ``needed`` or never
    scheduled): wholly UNDER the diagonal, its last key no later than its
    first query, ``block(False)``: no mask is traced into it; ON it,
    ``diagonal(sub)`` where the blocks are square and ``_causal_sub`` cuts
    them into static sub-tiles of ``sub`` rows (at most ``sub_rows``; the
    block's first query is then its first key, and the walk is the single
    tile's), else ``block(True)``: the whole block computed and masked.
    """
    if not causal:
        pl.when(needed)(lambda: block(False))
        return
    under = (j + 1) * block_k - 1 <= i * block_q
    sub = _causal_sub(block_q, sub_rows) if block_q == block_k else 0
    pl.when(needed & under)(lambda: block(False))
    pl.when(needed & ~under)(lambda: diagonal(sub) if sub else block(True))


def _subtile_grads(refs, rows, cols, scale, diagonal, want="qkv"):
    """(dq, dk, dv) contributions of the logits of ``rows`` x ``cols``
    (static slices of the blocks in VMEM), None for those not in ``want``;
    ``diagonal``: the square tile the causal diagonal crosses, the only one
    masked."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref = refs
    q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
    k, v = k_ref[0, 0, cols, :], v_ref[0, 0, cols, :]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if diagonal:
        s = _apply_causal_mask(s, 0, 0)
    p = jnp.exp(s - lse_ref[0, 0, rows, :])
    if mask_ref is not None:
        p = jnp.where(mask_ref[0, :, cols] > 0.0, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_ref[0, 0, rows, :]) * scale
    dq = dk = dv = None
    if "v" in want:
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    if "k" in want:
        dk = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    if "q" in want:
        dq = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fold_fwd_coords(ip, jj, ni):
    """Folded causal grid -> (i, j): q-block row ``ip`` (short, j <= ip)
    pairs with row ``ni-1-ip`` (long) so every grid step is a needed
    lower-triangular pair — jj sweeps row_a's j in [0, ip], then row_b's
    j in [0, ni-1-ip], ni+1 steps total per ip."""
    on_a = jj <= ip
    i = jnp.where(on_a, ip, ni - 1 - ip)
    j = jnp.where(on_a, jj, jj - ip - 1)
    return i, j


def _fwd_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool, folded: bool = False,
):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        mask_ref = None
    if folded:
        # causal triangular schedule: no skipped steps (see _fold_fwd_coords)
        ip, jj = pl.program_id(2), pl.program_id(3)
        ni = pl.num_programs(2) * 2
        i, j = _fold_fwd_coords(ip, jj, ni)
        init_cond = (jj == 0) | (jj == ip + 1)
        fin_cond = (jj == ip) | (jj == pl.num_programs(3) - 1)
        needed = True
    else:
        i, j = pl.program_id(2), pl.program_id(3)
        nj = pl.num_programs(3)
        init_cond = j == 0
        fin_cond = j == nj - 1
        # causal: skip blocks strictly above the diagonal
        needed = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(init_cond)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def fold(tiles):
        """Online-softmax update of m / l / acc with ``tiles`` of (row
        slice, f32 logits of those rows, their keys' values), which
        together cover the block's rows. Every tile's statistics come
        before any tile's value product and the three carries are written
        last, together: a store of l between the two phases cost a block on
        the diagonal 0.5 us of 4.2 (PERF.md, PR 34)."""
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]  # (block_q, 1)
        stats = []
        for rows, s, _ in tiles:
            m_new = jnp.maximum(
                m_prev[rows], jnp.max(s, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev[rows] - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l_prev[rows] + jnp.sum(p, axis=-1, keepdims=True)
            stats.append((m_new, alpha, p, l))
        acc = acc_ref[:]
        acc = _stack_rows([
            acc[rows] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for (rows, _, v), (_, alpha, p, _) in zip(tiles, stats)
        ])
        l_ref[:] = jnp.broadcast_to(
            _stack_rows([l for _, _, _, l in stats]), l_ref.shape
        )
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(
            _stack_rows([m_new for m_new, _, _, _ in stats]), m_ref.shape
        )

    def block(masked):
        q = q_ref[0, 0]  # (block_q, head_dim)
        k = k_ref[0, 0]  # (block_k, head_dim)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)
        if masked:
            s = _apply_causal_mask(s, i * block_q, j * block_k)
        if mask_ref is not None:
            valid = mask_ref[0, 0] > 0.0  # (block_k,) key-padding validity
            s = jnp.where(valid[None, :], s, NEG_INF)
        fold([(slice(None), s, v)])

    def diagonal(sub):
        # a row's LAST block, not its only one: each query sub-tile meets
        # its key prefix in ONE product and folds into the running m / l /
        # acc the blocks under the diagonal left. All the logits first, then
        # fold's phases: so written, the sub-tiles' products and softmax
        # passes overlap; sub-tile by sub-tile they read up to 1.3x slower
        # than the whole masked block (PERF.md, PR 34)
        tiles = []
        for row, prefix in _causal_prefixes(block_q, sub):
            s = jax.lax.dot_general(
                q_ref[0, 0, row:prefix, :], k_ref[0, 0, :prefix, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale
            s = _apply_causal_mask(s, row, 0)
            if mask_ref is not None:
                s = jnp.where(mask_ref[0, :, :prefix] > 0.0, s, NEG_INF)
            tiles.append((slice(row, prefix), s, v_ref[0, 0, :prefix, :]))
        fold(tiles)

    _when_by_block_kind(
        causal, i, j, block_q, block_k, needed, block, diagonal,
        sub_rows=CAUSAL_SUB_FWD_BLOCK,
    )

    @pl.when(fin_cond)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[:] / safe_l
        lse = m_ref[:, :1] + jnp.log(safe_l)
        if mask_ref is not None:
            # rows with no valid key: m never left NEG_INF and every p was
            # exp(0)=1 garbage — emit 0 output and NEG_INF lse so the
            # backward (which re-masks p) produces zero grads for them
            dead = m_ref[:, :1] == NEG_INF
            o = jnp.where(dead, 0.0, o)
            lse = jnp.where(dead, NEG_INF, lse)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = lse


def _fwd(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    # q: (B, N, S, H); k, v: (B, K, S_k, H) with N % K == 0 (GQA: the kv
    # index maps route q-head n to kv-head n // group); kv_mask: (B, S_k)
    # float 0/1 or None. v (and the output) may be narrower or wider than
    # q and k: ``head_dim`` is the width the scores contract over,
    # ``v_dim`` the width of the values (latent attention: 192 and 128)
    if k.shape[2] == block_k:  # whole key sequence in one block: plain softmax
        single = _fwd_single if interpret else _fwd_single_shared
        return single(
            q, k, v, kv_mask, causal, scale, block_q, block_k, interpret
        )
    multi = _fwd_multi if interpret else _fwd_multi_shared
    return multi(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret)


def _fwd_multi(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    """The online-softmax forward over a grid of key blocks (see _fwd)."""
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    ni = seq_q // block_q
    folded = (
        causal and seq_q == seq_k and block_q == block_k and ni % 2 == 0
    )
    if folded:
        # triangular schedule: pair q-block rows so every grid step is a
        # needed causal pair — ni*(ni/2+...) -> (ni/2)*(ni+1) steps instead
        # of ni^2 with ~half skipped (skipped steps still paid their grid
        # overhead + block DMA: ~18% of the 16k backward, measured)
        grid = (batch, heads, ni // 2, ni + 1)

        def qmap(b, n, ip, jj):
            i, _ = _fold_fwd_coords(ip, jj, ni)
            return (b, n, i, 0)

        def kmap(b, n, ip, jj):
            _, j = _fold_fwd_coords(ip, jj, ni)
            return (b, n // group, j, 0)

        def mmap(b, n, ip, jj):
            _, j = _fold_fwd_coords(ip, jj, ni)
            return (b, 0, j)
    else:
        grid = (batch, heads, ni, seq_k // block_k)

        def qmap(b, n, i, j):
            return (b, n, i, 0)

        def kmap(b, n, i, j):
            return (b, n // group, j, 0)

        def mmap(b, n, i, j):
            return (b, 0, j)

    qspec = pl.BlockSpec((1, 1, block_q, head_dim), qmap)
    kspec = pl.BlockSpec((1, 1, block_k, head_dim), kmap)
    vspec = pl.BlockSpec((1, 1, block_k, v_dim), kmap)
    has_mask = kv_mask is not None
    in_specs = [qspec, kspec, vspec]
    inputs = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, block_k), mmap))
        inputs.append(kv_mask)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
            folded=folded,
        ),
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, v_dim), qmap),
            # lse rides as (B, N, S, 1): block (…, block_q, 1) satisfies the
            # TPU tile rule (last dim == array dim, 2nd-to-last % 8 == 0)
            pl.BlockSpec((1, 1, block_q, 1), qmap),
        ],
        out_shape=[
            _sds((batch, heads, seq_q, v_dim), q.dtype, q),
            _sds((batch, heads, seq_q, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            _vmem((block_q, v_dim)),     # acc
            _vmem((block_q, 128)),       # running max m (lane-replicated)
            _vmem((block_q, 128)),       # running normalizer l
        ],
        interpret=interpret,
    )(*inputs)
    return out, lse


def _fwd_single(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]
    group = heads // k.shape[1]
    grid = (batch, heads, seq_q // block_q)
    qspec = pl.BlockSpec((1, 1, block_q, head_dim), lambda b, n, i: (b, n, i, 0))
    kspec = pl.BlockSpec(
        (1, 1, block_k, head_dim), lambda b, n, i: (b, n // group, 0, 0)
    )
    vspec = pl.BlockSpec(
        (1, 1, block_k, v_dim), lambda b, n, i: (b, n // group, 0, 0)
    )
    has_mask = kv_mask is not None
    in_specs = [qspec, kspec, vspec]
    inputs = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, block_k), lambda b, n, i: (b, 0, 0)))
        inputs.append(kv_mask)
    sub = _causal_sub(seq_q) if causal and seq_q == block_q == block_k else 0
    if sub:
        kernel, name = functools.partial(
            _fwd_single_causal_kernel, scale=scale, sub=sub, has_mask=has_mask,
        ), "flash_fwd_single_causal"
    else:
        kernel, name = functools.partial(
            _fwd_single_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
        ), "flash_fwd_single"
    out, lse = pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, v_dim), lambda b, n, i: (b, n, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, n, i: (b, n, i, 0)),
        ],
        out_shape=[
            _sds((batch, heads, seq_q, v_dim), q.dtype, q),
            _sds((batch, heads, seq_q, 1), jnp.float32, q),
        ],
        interpret=interpret,
    )(*inputs)
    return out, lse


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes set, so the
    kernels compose with shard_map manual axes (ring attention's folds)."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _vmem(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _tpu_compiler_params(**kwargs):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def _fwd_single_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool,
):
    """One-k-block forward: plain tile softmax, no online-softmax carries.

    When the whole key sequence fits one block (S_k == block_k — true for
    both bench LM configs at the 1024 default), the running max/normalizer
    scratch, their lane-replicated broadcasts, and the accumulator rescale
    are pure VPU overhead; this variant computes the tile softmax directly.
    """
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    i = pl.program_id(2)
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _apply_causal_mask(s, i * block_q, 0)
    if mask_ref is not None:
        valid = mask_ref[0, 0] > 0.0
        s = jnp.where(valid[None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / l
    lse = m + jnp.log(l)
    if mask_ref is not None:
        dead = m == NEG_INF  # no valid key at all
        o = jnp.where(dead, 0.0, o)
        lse = jnp.where(dead, NEG_INF, lse)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse_ref[0, 0] = lse


def _fwd_single_causal_kernel(*refs, scale: float, sub: int, has_mask: bool):
    """Causal one-tile forward that leaves the masked half uncomputed.

    The query rows are walked in static sub-tiles of ``sub`` rows; each is
    given only the key prefix it can see (static slices of the refs
    already in VMEM), in two pieces: the tiles left of the diagonal, fully
    visible, and the diagonal ``sub x sub`` tile, the only one masked.
    Every row still sees all of its keys at once, so the plain tile
    softmax of :func:`_fwd_single_kernel` stays; the entries left out are
    those whose weight was ``exp(NEG_INF - m) = 0``.
    """
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    for row, prefix in _causal_prefixes(q_ref.shape[2], sub):
        rows = diag = slice(row, prefix)  # the diagonal tile's keys too
        pieces = ([slice(0, row)] if row else []) + [diag]
        q = q_ref[0, 0, rows, :]
        logits = []
        for cols in pieces:
            s = jax.lax.dot_general(
                q, k_ref[0, 0, cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if cols is diag:
                s = _apply_causal_mask(s, 0, 0)
            if mask_ref is not None:
                s = jnp.where(mask_ref[0, :, cols] > 0.0, s, NEG_INF)
            logits.append(s)
        m = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in logits]
        )
        l, o = 0.0, 0.0
        for cols, s in zip(pieces, logits):
            p = jnp.exp(s - m)
            l += jnp.sum(p, axis=-1, keepdims=True)
            o += jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0, cols, :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
        o = o / l
        lse = m + jnp.log(l)
        if mask_ref is not None:
            dead = m == NEG_INF  # no valid key at all
            o = jnp.where(dead, 0.0, o)
            lse = jnp.where(dead, NEG_INF, lse)
        o_ref[0, 0, rows, :] = o.astype(o_ref.dtype)
        lse_ref[0, 0, rows, :] = lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool,
):
    if has_mask:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, dq_ref, dq_acc = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        mask_ref = None
    i, j = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    def block(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = _apply_causal_mask(s, i * block_q, j * block_k)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        if mask_ref is not None:
            # re-mask: for fully-padded rows lse is NEG_INF, making
            # exp(s - lse) garbage instead of 0
            p = jnp.where((mask_ref[0, 0] > 0.0)[None, :], p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def diagonal(sub):
        # query-major: a row sub-tile's dq is complete after its key prefix
        piece = functools.partial(
            _subtile_grads,
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref),
            scale=scale, want="q",
        )
        for row, prefix in _causal_prefixes(block_q, sub):
            rows = slice(row, prefix)
            dq = piece(rows, rows, diagonal=True)[0]
            if row:
                dq += piece(rows, slice(0, row), diagonal=False)[0]
            dq_acc[rows, :] += dq

    _when_by_block_kind(
        causal, i, j, block_q, block_k, needed, block, diagonal
    )

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool,
):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None
    j, i = pl.program_id(2), pl.program_id(3)  # k-block outer, q-block inner
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = ((i + 1) * block_q - 1 >= j * block_k) if causal else True

    def block(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = _apply_causal_mask(s, i * block_q, j * block_k)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        if mask_ref is not None:
            p = jnp.where((mask_ref[0, 0] > 0.0)[None, :], p, 0.0)
        # dv += p^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def diagonal(sub):
        # key-major: a key sub-tile meets only the rows that can see it
        piece = functools.partial(
            _subtile_grads,
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref),
            scale=scale, want="kv",
        )
        for col, prefix in _causal_prefixes(block_k, sub):
            cols = slice(col, prefix)
            _, dk, dv = piece(cols, cols, diagonal=True)
            if prefix < block_q:
                _, dk_below, dv_below = piece(
                    slice(prefix, block_q), cols, diagonal=False
                )
                dk += dk_below
                dv += dv_below
            dk_acc[cols, :] += dk
            dv_acc[cols, :] += dv

    _when_by_block_kind(
        causal, i, j, block_q, block_k, needed, block, diagonal
    )

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _fold_bwd_coords(jp, ii, ni):
    """Folded causal grid for the k-outer backward: short column ``jp``
    (rows i in [jp, ni-1]) pairs with long column ``ni-1-jp`` (rows
    i in [ni-1-jp, ni-1]) — ii sweeps column_a's rows then column_b's,
    ni+1 steps per jp, every one a needed lower-triangular pair."""
    on_a = ii < ni - jp
    j = jnp.where(on_a, jp, ni - 1 - jp)
    i = jnp.where(on_a, jp + ii, ii - 1)
    return i, j, on_a


def _bwd_fused_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool, folded: bool = False,
):
    """Multi-block fused backward: dq, dk, dv from ONE logits recompute.

    The separate dq and dk/dv kernels each redo the s = qk^T matmul and
    the exp — at long sequence the dominant cost. This kernel runs the
    dkv grid (k-block outer, q-block inner), accumulates dk/dv in VMEM
    scratch per k-block, and accumulates dq in a PERSISTENT VMEM scratch
    spanning the whole q sequence (scratch lives across grid steps;
    output blocks cannot be accumulated across non-consecutive revisits —
    Mosaic does not flush/reload them, measured silently-wrong). Each dq
    block is written to the output exactly once, on its last visit: the
    final k-block sweep (j == nj-1) on the square grid, or the per-row
    last-touch conditions of the triangular schedule when ``folded`` (its
    own diagonal step for rows < ni/2, the final jp's long column for the
    rest). The scratch costs seq_q*head_dim*4 bytes of VMEM (4 MB at 16k,
    head_dim 64); _bwd falls back to the two-kernel path beyond
    _FUSED_DQ_VMEM_LIMIT.

    Causal, by block pair (``_when_by_block_kind``): under the diagonal the
    body holds no mask; on it (square blocks that ``_causal_sub`` cuts) the
    walk is KEY-major, as :func:`_bwd_single_causal_kernel` walks one tile:
    key sub-tile ``c`` meets its diagonal ``sub x sub`` tile (the only one
    masked) and the rows of the block below it, ``dk_acc`` / ``dv_acc`` take
    the two pieces' sum and ``dq_acc``'s stripe each piece's rows. Init,
    emit and finalize conditions are those of the whole-block body.
    """
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, dq_acc) = refs
        mask_ref = None
    if folded:
        # causal triangular schedule (see _fold_bwd_coords): every step is
        # a needed pair. Scratch lifecycles: column_a runs ii in [0, ni-jp),
        # column_b in [ni-jp, ni]; dq rows are all first-touched (and
        # zeroed) during jp==0's column_a sweep, and each row's LAST touch
        # is either its own diagonal step (rows < ni/2: on_a, ii==0 at
        # jp==row) or the final jp's column_b (rows >= ni/2) — emit there.
        jp, ii = pl.program_id(2), pl.program_id(3)
        njp = pl.num_programs(2)
        ni = pl.num_programs(3) - 1
        i, j, on_a = _fold_bwd_coords(jp, ii, ni)
        init_kv = (ii == 0) | (ii == ni - jp)
        fin_kv = (ii == ni - jp - 1) | (ii == ni)
        init_dq = (jp == 0) & on_a
        emit_dq = (on_a & (ii == 0)) | ((jp == njp - 1) & ~on_a)
        needed = True
    else:
        j, i = pl.program_id(2), pl.program_id(3)  # k outer, q inner
        init_kv = i == 0
        fin_kv = i == pl.num_programs(3) - 1
        init_dq = j == 0
        emit_dq = j == pl.num_programs(2) - 1
        needed = ((i + 1) * block_q - 1 >= j * block_k) if causal else True
    row = pl.ds(i * block_q, block_q)  # this q-block's slice of dq_acc

    @pl.when(init_kv)
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(init_dq)
    def _init_dq():
        dq_acc[row, :] = jnp.zeros((block_q, dq_acc.shape[-1]), jnp.float32)

    def block(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = _apply_causal_mask(s, i * block_q, j * block_k)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        if mask_ref is not None:
            p = jnp.where((mask_ref[0, 0] > 0.0)[None, :], p, 0.0)
        # dv += p^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dq[i] += ds @ k — accumulated in the persistent scratch stripe
        dq_acc[row, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def diagonal(sub):
        # key-major, as the single tile's backward walks its sub-tiles:
        # key sub-tile ``cols`` meets its diagonal tile and the rows below
        piece = functools.partial(
            _subtile_grads,
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref),
            scale=scale,
        )

        def stripe(start, stop):  # rows [start, stop) of this q-block
            return pl.ds(i * block_q + start, stop - start)

        for col, prefix in _causal_prefixes(block_k, sub):
            cols = slice(col, prefix)
            dq, dk, dv = piece(cols, cols, diagonal=True)
            dq_acc[stripe(col, prefix), :] += dq
            if prefix < block_q:
                dq_below, dk_below, dv_below = piece(
                    slice(prefix, block_q), cols, diagonal=False
                )
                dk += dk_below
                dv += dv_below
                dq_acc[stripe(prefix, block_q), :] += dq_below
            dk_acc[cols, :] += dk
            dv_acc[cols, :] += dv

    _when_by_block_kind(
        causal, i, j, block_q, block_k, needed, block, diagonal
    )

    @pl.when(emit_dq)
    def _emit_dq():
        dq_ref[0, 0] = dq_acc[row, :].astype(dq_ref.dtype)

    @pl.when(fin_kv)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_single_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int,
    has_mask: bool,
):
    """One-tile fused backward: dq, dk, dv from a single logits recompute.

    When both sequences fit one block, the separate dq and dk/dv kernels
    each redo the s = qk^T matmul and the exp — the dominant VPU cost.
    This variant computes p once and emits all three gradients.
    """
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref) = refs
        mask_ref = None
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]  # (block_q, 1)
    delta = delta_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _apply_causal_mask(s, 0, 0)
    p = jnp.exp(s - lse)  # (block_q, block_k)
    if mask_ref is not None:
        p = jnp.where((mask_ref[0, 0] > 0.0)[None, :], p, 0.0)
    dv_ref[0, 0] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * scale
    dq_ref[0, 0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[0, 0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)


def _bwd_single_causal_kernel(*refs, scale: float, sub: int, has_mask: bool):
    """Causal one-tile fused backward that leaves the masked half
    uncomputed: the sub-tiling of :func:`_fwd_single_causal_kernel`, walked
    key-major.

    Key sub-tile ``c`` meets only the query rows that can see it, in two
    pieces: the diagonal ``sub x sub`` tile (the only one masked) and the
    rows below it, fully visible. Its dk/dv are complete after the two and
    written once; dq accumulates in a float32 VMEM scratch spanning the
    sequence, and a row sub-tile is written out at its diagonal tile, its
    last visit. (The query-major order, dq written once and dk/dv
    accumulated in two scratch buffers, measured 9% slower: PERF.md, PR 26.)
    """
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
        mask_ref = None
    seq = q_ref.shape[2]
    piece = functools.partial(
        _subtile_grads,
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref),
        scale=scale,
    )

    for col, prefix in _causal_prefixes(seq, sub):
        cols = slice(col, prefix)
        dq, dk, dv = piece(cols, cols, diagonal=True)
        if prefix < seq:
            below = slice(prefix, seq)
            dq_below, dk_below, dv_below = piece(below, cols, diagonal=False)
            dk += dk_below
            dv += dv_below
            if col:
                dq_acc[below, :] += dq_below
            else:  # the first key sub-tile is the first touch of every row
                dq_acc[below, :] = dq_below
        if col:
            dq += dq_acc[cols, :]
        dq_ref[0, 0, cols, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, 0, cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, cols, :] = dv.astype(dv_ref.dtype)


def _bwd_single(q, k, v, lse, do, delta, kv_mask, causal, scale, block_q,
                block_k, interpret):
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]  # of v, dO and dV; q, k, dQ, dK are head_dim wide
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    grid = (batch, heads)
    qspec = pl.BlockSpec((1, 1, block_q, head_dim), lambda b, n: (b, n, 0, 0))
    dospec = pl.BlockSpec((1, 1, block_q, v_dim), lambda b, n: (b, n, 0, 0))
    kspec = pl.BlockSpec(
        (1, 1, block_k, head_dim), lambda b, n: (b, n // group, 0, 0)
    )
    vspec = pl.BlockSpec(
        (1, 1, block_k, v_dim), lambda b, n: (b, n // group, 0, 0)
    )
    # dK/dV accumulate PER Q-HEAD; group-summed by the caller (GQA)
    kspec_out = pl.BlockSpec((1, 1, block_k, head_dim), lambda b, n: (b, n, 0, 0))
    vspec_out = pl.BlockSpec((1, 1, block_k, v_dim), lambda b, n: (b, n, 0, 0))
    rowspec = pl.BlockSpec((1, 1, block_q, 1), lambda b, n: (b, n, 0, 0))
    has_mask = kv_mask is not None
    in_specs = [qspec, kspec, vspec, dospec, rowspec, rowspec]
    inputs = [q, k, v, do, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, block_k), lambda b, n: (b, 0, 0)))
        inputs.append(kv_mask)
    sub = _causal_sub(seq_q) if causal and seq_q == seq_k else 0
    if sub:
        kernel, name = functools.partial(
            _bwd_single_causal_kernel, scale=scale, sub=sub, has_mask=has_mask,
        ), "flash_bwd_single_causal"
        scratch = [_vmem((seq_q, head_dim))]  # dq accumulator
    else:
        kernel, name = functools.partial(
            _bwd_single_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
        ), "flash_bwd_single"
        scratch = []
    return pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=[qspec, kspec_out, vspec_out],
        out_shape=[
            _sds(q.shape, q.dtype, q),
            _sds((batch, heads, seq_k, head_dim), k.dtype, q),
            _sds((batch, heads, seq_k, v_dim), v.dtype, q),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*inputs)


# The single-tile wrappers as the compiled path calls them: jitted, so that
# a model's layers share ONE trace of the kernel body (XLA inlines the
# calls; the step keeps its 24 tpu_custom_call). The unrolled causal bodies
# are ~6x the equations of the whole-tile ones and, traced once a layer,
# added 3.7 s to the GPT-2 step's tracing (PERF.md, PR 26). Interpret mode
# (the CPU tests, which call these eagerly) keeps the plain functions: with
# the jitted ones, all three tier-1 runs lost a worker to a SIGABRT in a
# later multi-device CPU program of the same process, and none without.
_fwd_single_shared = jax.jit(_fwd_single, static_argnums=(4, 5, 6, 7, 8))
_bwd_single_shared = jax.jit(_bwd_single, static_argnums=(7, 8, 9, 10, 11))


# the fused backward's persistent dq scratch (seq_q * head_dim * 4 bytes)
# must leave room for the block operands and dk/dv scratch; 8 MB covers
# 32k tokens at head_dim 64 and stays well inside v5e VMEM
_FUSED_DQ_VMEM_LIMIT = 8 * 1024 * 1024


def _kmajor_specs(kv_mask, block_q, block_k, group, head_dim, v_dim, inputs):
    """Shared spec construction for the k-block-major backward grid
    (j = k-block outer, i = q-block inner) — used by BOTH the fused kernel
    and the two-kernel fallback so their index maps can never diverge.

    Returns (in_specs, inputs, qspec, kspec_out, vspec_out): qspec doubles
    as the dq output spec; the dK / dV outputs use kspec_out / vspec_out
    (``head_dim`` / ``v_dim`` wide), which index PER Q-HEAD (kv blocks are
    read via the group map, but writes must not race across a group —
    callers group-sum afterwards).
    """
    def q_rows(width):
        return pl.BlockSpec(
            (1, 1, block_q, width), lambda b, n, j, i: (b, n, i, 0)
        )

    def k_rows(width, grouped):
        if grouped:
            return pl.BlockSpec(
                (1, 1, block_k, width),
                lambda b, n, j, i: (b, n // group, j, 0),
            )
        return pl.BlockSpec(
            (1, 1, block_k, width), lambda b, n, j, i: (b, n, j, 0)
        )

    qspec = q_rows(head_dim)
    in_specs = [
        qspec, k_rows(head_dim, True), k_rows(v_dim, True), q_rows(v_dim),
        q_rows(1), q_rows(1),
    ]
    if kv_mask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, n, j, i: (b, 0, j))
        )
        inputs = inputs + [kv_mask]
    return (
        in_specs, inputs, qspec, k_rows(head_dim, False), k_rows(v_dim, False)
    )


def _bwd_split(q, k, v, lse, do, delta, kv_mask, causal, scale, block_q,
               block_k, interpret):
    """Separate dq and dk/dv kernels (two logits recomputes): the fallback
    when the fused kernel's dq scratch would not fit VMEM."""
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    has_mask = kv_mask is not None

    qspec = pl.BlockSpec((1, 1, block_q, head_dim), lambda b, n, i, j: (b, n, i, 0))
    dospec = pl.BlockSpec((1, 1, block_q, v_dim), lambda b, n, i, j: (b, n, i, 0))
    kspec = pl.BlockSpec(
        (1, 1, block_k, head_dim), lambda b, n, i, j: (b, n // group, j, 0)
    )
    vspec = pl.BlockSpec(
        (1, 1, block_k, v_dim), lambda b, n, i, j: (b, n // group, j, 0)
    )
    rowspec = pl.BlockSpec((1, 1, block_q, 1), lambda b, n, i, j: (b, n, i, 0))

    in_specs = [qspec, kspec, vspec, dospec, rowspec, rowspec]
    inputs = [q, k, v, do, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, block_k), lambda b, n, i, j: (b, 0, j)))
        inputs.append(kv_mask)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
        ),
        name="flash_bwd_dq",
        grid=(batch, heads, seq_q // block_q, seq_k // block_k),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=_sds(q.shape, q.dtype, q),
        scratch_shapes=[_vmem((block_q, head_dim))],
        interpret=interpret,
    )(*inputs)

    # k-block-major grid: q streams innermost
    in_specs_t, inputs_t, _, kspec_out, vspec_out = _kmajor_specs(
        kv_mask, block_q, block_k, group, head_dim, v_dim,
        [q, k, v, do, lse, delta],
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
        ),
        name="flash_bwd_dkv",
        grid=(batch, heads, seq_k // block_k, seq_q // block_q),
        in_specs=in_specs_t,
        out_specs=[kspec_out, vspec_out],
        out_shape=[
            _sds((batch, heads, seq_k, head_dim), k.dtype, q),
            _sds((batch, heads, seq_k, v_dim), v.dtype, q),
        ],
        scratch_shapes=[_vmem((block_k, head_dim)), _vmem((block_k, v_dim))],
        interpret=interpret,
    )(*inputs_t)
    if group > 1:  # GQA: fold the per-q-head contributions into kv heads
        dk = dk.reshape(batch, k.shape[1], group, seq_k, head_dim).sum(2)
        dv = dv.reshape(batch, v.shape[1], group, seq_k, v_dim).sum(2)
    return dq, dk, dv


def _bwd(q, k, v, o, lse, do, kv_mask, causal, scale, block_q, block_k,
         interpret, delta=None):
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
            keepdims=True,
        )  # (B, N, S, 1), same carry layout as lse
    # else: caller supplies the global delta (ring attention's chunk
    # backward, where o/do span ALL chunks but this call sees one)
    if seq_q == block_q and seq_k == block_k:
        # both sequences in one tile: fused dq/dk/dv kernel, one logits
        # recompute + one exp instead of two of each
        single = _bwd_single if interpret else _bwd_single_shared
        dq, dk, dv = single(
            q, k, v, lse, do, delta, kv_mask, causal, scale, block_q,
            block_k, interpret,
        )
        if group > 1:
            dk = dk.reshape(batch, k.shape[1], group, seq_k, head_dim).sum(2)
            dv = dv.reshape(batch, v.shape[1], group, seq_k, v_dim).sum(2)
        return dq, dk, dv
    if seq_q * head_dim * 4 > _FUSED_DQ_VMEM_LIMIT:
        # the fused kernel's persistent dq scratch would crowd VMEM at
        # this length: fall back to the separate dq and dk/dv kernels
        return _bwd_split(
            q, k, v, lse, do, delta, kv_mask, causal, scale, block_q,
            block_k, interpret,
        )
    fused = _bwd_fused if interpret else _bwd_fused_shared
    return fused(
        q, k, v, lse, do, delta, kv_mask, causal, scale, block_q, block_k,
        interpret,
    )


def _bwd_fused(q, k, v, lse, do, delta, kv_mask, causal, scale, block_q,
               block_k, interpret):
    """ONE fused kernel on the k-block-major grid (q streams innermost):
    dk/dv accumulate in VMEM scratch per k-block; dq accumulates in a
    persistent VMEM scratch spanning the q sequence, emitted on each
    block's last visit. One logits recompute + one exp per block pair,
    instead of the two of each the separate kernels pay."""
    batch, heads, seq_q, head_dim = q.shape
    v_dim = v.shape[-1]
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    has_mask = kv_mask is not None
    ni = seq_q // block_q
    folded = (
        causal and seq_q == seq_k and block_q == block_k and ni % 2 == 0
    )
    if folded:
        # triangular schedule (see _fold_bwd_coords): ~half the grid steps
        grid = (batch, heads, ni // 2, ni + 1)

        def fqmap(b, n, jp, ii):
            i, _, _ = _fold_bwd_coords(jp, ii, ni)
            return (b, n, i, 0)

        def fkmap(b, n, jp, ii):
            _, j, _ = _fold_bwd_coords(jp, ii, ni)
            return (b, n // group, j, 0)

        def fkout(b, n, jp, ii):
            _, j, _ = _fold_bwd_coords(jp, ii, ni)
            return (b, n, j, 0)

        def fmmap(b, n, jp, ii):
            _, j, _ = _fold_bwd_coords(jp, ii, ni)
            return (b, 0, j)

        qspec_t = pl.BlockSpec((1, 1, block_q, head_dim), fqmap)
        kspec_out = pl.BlockSpec((1, 1, block_k, head_dim), fkout)
        vspec_out = pl.BlockSpec((1, 1, block_k, v_dim), fkout)
        rowspec_f = pl.BlockSpec((1, 1, block_q, 1), fqmap)
        in_specs_t = [
            qspec_t,
            pl.BlockSpec((1, 1, block_k, head_dim), fkmap),
            pl.BlockSpec((1, 1, block_k, v_dim), fkmap),
            pl.BlockSpec((1, 1, block_q, v_dim), fqmap),
            rowspec_f, rowspec_f,
        ]
        inputs_t = [q, k, v, do, lse, delta]
        if has_mask:
            in_specs_t.append(pl.BlockSpec((1, 1, block_k), fmmap))
            inputs_t.append(kv_mask)
    else:
        grid = (batch, heads, seq_k // block_k, ni)
        in_specs_t, inputs_t, qspec_t, kspec_out, vspec_out = _kmajor_specs(
            kv_mask, block_q, block_k, group, head_dim, v_dim,
            [q, k, v, do, lse, delta],
        )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, has_mask=has_mask,
            folded=folded,
        ),
        name="flash_bwd_fused",
        grid=grid,
        in_specs=in_specs_t,
        out_specs=[qspec_t, kspec_out, vspec_out],
        out_shape=[
            _sds(q.shape, q.dtype, q),
            _sds((batch, heads, seq_k, head_dim), k.dtype, q),
            _sds((batch, heads, seq_k, v_dim), v.dtype, q),
        ],
        scratch_shapes=[
            _vmem((block_k, head_dim)),
            _vmem((block_k, v_dim)),
            _vmem((seq_q, head_dim)),  # persistent dq accumulator
        ],
        # the persistent dq scratch pushes past the 16 MB default scoped
        # limit at long seq; grant headroom (v5e VMEM is 128 MB physical)
        compiler_params=_tpu_compiler_params(
            vmem_limit_bytes=32 * 1024 * 1024
        ),
        interpret=interpret,
    )(*inputs_t)
    if group > 1:  # GQA: fold the per-q-head contributions into kv heads
        dk = dk.reshape(batch, k.shape[1], group, seq_k, head_dim).sum(2)
        dv = dv.reshape(batch, v.shape[1], group, seq_k, v_dim).sum(2)
    return dq, dk, dv


# The multi-block calls as the compiled path makes them: jitted like the
# single-tile wrappers above and for their reason. The causal kernels hold
# two bodies, one of them unrolled over sub-tiles; traced anew by each of
# six layers' forward, recomputed forward and backward they added 3.6-4.0 s
# to the joyai step's tracing (PERF.md, PR 34).
_fwd_multi_shared = jax.jit(_fwd_multi, static_argnums=(4, 5, 6, 7, 8))
_bwd_fused_shared = jax.jit(_bwd_fused, static_argnums=(7, 8, 9, 10, 11))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret)
    # compact the (B, N, S, 1) lse to (B, N, S) for the RESIDUAL: the
    # trailing-singleton layout tiles T(8, 128) at 128x the bytes (a
    # 12-layer 64k-token GPT-2 saved 4.6 GB of pure lane padding across
    # the backward). The kernels keep their (…, S, 1) interface — the
    # padded buffer now lives only transiently inside each layer.
    return out, (q, k, v, kv_mask, out, lse[..., 0])


def _flash_bwd(causal, scale, block_q, block_k, interpret, residuals, g):
    q, k, v, kv_mask, out, lse = residuals
    dq, dk, dv = _bwd(
        q, k, v, out, lse[..., None], g, kv_mask, causal, scale, block_q,
        block_k, interpret,
    )
    dmask = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_over_mesh(q, k, v, kv_mask, *static):
    """:func:`_flash` on (B, N, S, H) operands, inside a region that is
    manual over every mesh axis.

    The TPU lowering refuses a Mosaic kernel that XLA would have to
    partition (any multi-device jit, or a shard_map manual over only some
    axes — the ZeRO-1 step's data-manual body). So on a mesh the kernel is
    wrapped in a shard_map over the axes that are still automatic, under
    the repo's placement convention: batch over the data axes, heads over
    ``tensor`` where both head counts divide, everything else replicated.
    Attention is independent per (batch row, head), so each shard runs
    the unmodified kernel on its slice. Callers already manual over the
    whole mesh (ring / Ulysses attention) pass straight through.
    """
    import math

    from jax.sharding import PartitionSpec as P

    from ...runtime.mesh import data_axes, free_mesh_axes

    mesh, free = free_mesh_axes()
    if not free:
        return _flash(q, k, v, kv_mask, *static)
    ctx = jax.sharding.get_abstract_mesh() if mesh is None else mesh
    shape = ctx.shape
    batch = tuple(a for a in data_axes(ctx) if a in free and shape[a] > 1)
    if q.shape[0] % math.prod(shape[a] for a in batch):
        batch = ()
    tp = shape.get("tensor", 1)
    heads = (
        "tensor"
        if "tensor" in free and tp > 1
        and q.shape[1] % tp == 0 and k.shape[1] % tp == 0
        else None
    )
    qkv = P(batch or None, heads, None, None)
    operands, specs = (q, k, v), (qkv, qkv, qkv)
    if kv_mask is not None:  # (B, 1, S_k): follows the batch rows
        operands += (kv_mask,)
        specs += (P(batch or None, None, None),)
    mapped = jax.shard_map(
        lambda q, k, v, m=None: _flash(q, k, v, m, *static),
        mesh=mesh, in_specs=specs, out_specs=qkv,
        axis_names=set(free), check_vma=False,
    )
    return mapped(*operands)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Fused flash attention; (B, S, N, H) in and out.

    ``kv_mask``: optional (B, S_k) key-padding validity (True/nonzero =
    attend), the masking real BERT batches need (reference-scope extension;
    the reference has no attention at all). Queries whose keys are ALL
    masked produce zero output and zero gradients.

    Sequence lengths must be multiples of the block sizes (the dispatcher in
    ops/attention.py guarantees this before selecting the flash path; blocks
    shrink to the sequence length when it is shorter). 1024x1024 default
    blocks measured fastest on v5e for head_dim 64 (my chip run, PR 26:
    device time a call at (16, 12, 1024, 64) bf16, forward / backward —
    causal 0.49 / 0.97 ms at 1024x1024 (one tile, sub-tiled), 1.45 / 1.38
    at 512x512, 2.54 / 2.46 at 256x256; non-causal 0.71 / 1.37, 1.87 /
    1.83, 3.89 / 3.83 ms) — small blocks pay too many grid steps and
    per-step online-softmax bookkeeping; the f32 logits tile (4 MB whole,
    1 MB a causal sub-tile row) still sits comfortably in VMEM. Causal
    work is skipped down to key prefixes of sub-tiles on every path: inside
    a single tile (``CAUSAL_SUB`` rows), and inside the blocks on the
    diagonal of the multi-block grid (``CAUSAL_SUB`` rows backward,
    ``CAUSAL_SUB_FWD_BLOCK`` forward), whose blocks under the diagonal run
    without a mask and whose blocks above it do not run (see the module
    docstring; my chip run, PR 34: a call at the joyai cell's 4 x 32 x 4096
    x 192 / 128 forward / backward 7.02 / 14.91 -> 6.16 / 13.03 ms, at the
    lfm2 cell's 4 x 32 / 8 x 8192 x 64 18.78 / 34.63 -> 18.51 / 31.99 ms).
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q, block_k = _validate_flash_shapes(
        q.shape[2], k.shape[2], seq_q, seq_k, block_q, block_k
    )
    if kv_mask is not None:
        if kv_mask.shape != (q.shape[0], seq_k):
            raise ValueError(
                f"kv_mask shape {kv_mask.shape} != (batch, seq_k) "
                f"({q.shape[0]}, {seq_k})"
            )
        kv_mask = kv_mask.astype(jnp.float32)[:, None, :]  # (B, 1, S_k): TPU tile-rule-friendly block shape
    # (B, S, N, H) -> (B, N, S, H)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _flash_over_mesh(
        qt, kt, vt, kv_mask, causal, float(softmax_scale), block_q, block_k,
        interpret,
    )
    return out.transpose(0, 2, 1, 3)


def flash_attention_bnsh(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention consuming/producing the kernel layout (B, N, S, H).

    The transpose-free entry for callers whose projections already emit
    head-major activations (the fused projection layout in
    models/transformer.py MultiHeadAttention: einsum('bsd,dnh->bnsh')
    prologue + einsum('bnsh,nhd->bsd') epilogue). Measured A/B at GPT-2
    bench shapes: the transpose sandwich costs ~0.22 ms per layer fwd+bwd
    (results/lm_mfu_analysis/bsnh_ab.json) — ~2% of the whole step at 12
    layers; a wash at BERT@512.
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    block_q, block_k = _validate_flash_shapes(
        q.shape[1], k.shape[1], q.shape[2], k.shape[2], block_q, block_k
    )
    return _flash_over_mesh(
        q, k, v, None, causal, float(softmax_scale), block_q, block_k,
        interpret,
    )


def _validate_flash_shapes(heads_q, heads_kv, seq_q, seq_k,
                           block_q, block_k):
    """Shared head/sequence validation + block fitting for both public
    entries (BSNH `flash_attention` and BNSH `flash_attention_bnsh`)."""
    if heads_q % heads_kv:
        # an indivisible group would make the kv BlockSpec index maps read
        # out-of-range head blocks (clamped, silently wrong) — refuse
        raise ValueError(
            f"q heads ({heads_q}) must be a multiple of kv heads "
            f"({heads_kv}) for GQA"
        )
    block_q = _fit_block(seq_q, block_q)
    block_k = _fit_block(seq_k, block_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            f"seq lengths ({seq_q}, {seq_k}) must divide by blocks "
            f"({block_q}, {block_k})"
        )
    return block_q, block_k
