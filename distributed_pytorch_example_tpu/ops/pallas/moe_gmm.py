"""Grouped matrix products over rows sorted by group, as Pallas TPU kernels:
the products of the dropless expert layer (``models/moe.py::moe_dropless``).

    out[i] = lhs[i] @ rhs[g]    for the group g that row i lies in

``lhs`` is (m, k) with its rows sorted by group, ``rhs`` (groups, k, n),
``group_sizes`` (groups,) int32. The groups need not fill the buffer: rows
past the last group cost no product and come back as zeros, in the output
and in the gradient of ``lhs``. The grid's length is the number of row
tiles the groups touch (a value, not a shape), so the work follows the
group sizes and not the buffer.

Two kernels, by the names they carry in a trace:

- ``moe_gmm``: the product above, and with ``rhs`` read transposed the
  gradient of the rows (``grad @ rhs[g].T``);
- ``moe_gmm_dw``: the gradient of the weights, ``lhs[rows of g].T @
  grad[rows of g]`` a group.

The algorithm is the one of ``jax.experimental.pallas.ops.tpu.megablox``
(The JAX Authors, Apache 2.0), cut to what the expert layer needs (every
group is here, nothing is added to an existing output, one tiling) and with
its visiting order computed by a search in place of a histogram: a group
visits the row tiles it has rows in; a tile that two groups share is
visited twice in a row, and each visit stores only its own group's rows.
On the v5e at the lfm2-8b-a1b cell's shapes (PERF.md section 6, PR 29) the
three products of a layer take 21.9 ms against ``jax.lax.ragged_dot``'s
23.1 (bf16 results: a tie but for the weights' gradient, 1.2-1.5x faster),
the cell's step 3.6% less in the one traced pair there is, and the calls
carry the program's scope in a trace, which XLA's ``ragged-dot`` custom
calls do not (the experts' metrics read that scope).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a tile is at most 512 rows x 1024 (contraction) x 1024 (columns): of
# megablox's 512x1024x1024, 512x512x1024 and 256x2048x512 the first was
# fastest or within 2% of it on both products (scripts/moe_gmm_shootout.py)
_ROW_TILES = (512, 256, 128, 64, 32, 16, 8)
LANE_TILE = 1024


def row_tile(m: int) -> int:
    """The largest row tile that divides ``m``; rows that do not come in
    whole tiles are refused (nothing pads them, and nothing falls back)."""
    tile = next((t for t in _ROW_TILES if m % t == 0), None)
    if tile is None:
        raise ValueError(
            f"moe_gmm takes rows in whole tiles of {_ROW_TILES[-1]}: got {m}"
        )
    return tile


def _lane_tile(size: int) -> int:
    """A tile along a contraction or column dimension: the largest multiple
    of 128 up to ``LANE_TILE`` that divides ``size`` (1792 and 3584 take
    896: no tile then hangs over the edge and multiplies padding), else
    ``LANE_TILE``."""
    if size <= LANE_TILE:
        return size
    return next(
        (t for t in range(LANE_TILE, 0, -128) if size % t == 0), LANE_TILE
    )


def _visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """The order of work: ``(offsets, group_ids, tile_ids), visits``.

    ``offsets`` (groups + 1,) is where each group's rows start; visit ``i``
    of the grid works on group ``group_ids[i]`` in row tile ``tile_ids[i]``;
    ``visits`` is how many there are. A group visits every tile it has a
    row in, so at most ``m / tm + groups - 1`` visits, the arrays' length;
    ``visit_empty`` gives an empty group one visit (the weights' gradient
    has to write its zeros)."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    if visit_empty:
        tiles = jnp.maximum(tiles, 1)
    upto = jnp.cumsum(tiles)
    visit = jnp.arange(m // tm + groups - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        jnp.searchsorted(upto, visit, side="right"), groups - 1
    ).astype(jnp.int32)
    tile_ids = first[group_ids] + visit - (upto - tiles)[group_ids]
    tile_ids = jnp.clip(tile_ids, 0, m // tm - 1).astype(jnp.int32)
    return (offsets.astype(jnp.int32), group_ids, tile_ids), upto[-1]


def _rows_of_group(visit, order, tm: int, width: int):
    """(tm, width) mask: the rows of this visit's tile that lie in its group."""
    offsets, group_ids, tile_ids = order
    group = group_ids[visit]
    row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + tile_ids[visit] * tm
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _compute_dtype(lhs, rhs):
    both_bf16 = lhs.dtype == jnp.bfloat16 and rhs.dtype == jnp.bfloat16
    return jnp.bfloat16 if both_bf16 else jnp.float32


def _zero_past_groups(out, group_sizes):
    """Tiles that no group visits are never written: make them zeros."""
    row = lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(row < jnp.sum(group_sizes), out, jnp.zeros_like(out))


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool, interpret: bool):
    """(m, k) x (groups, k, n) -> (m, n); ``transpose_rhs``: rhs is
    (groups, n, k). Rows past the last group are zeros."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = row_tile(m)
    tk, tn = _lane_tile(k), _lane_tile(n)
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    order, visits = _visits(group_sizes, m, tm, visit_empty=False)
    compute = _compute_dtype(lhs, rhs)

    def kernel(order_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tail_zeroed(x, dim):
            # the last contraction tile may reach past k: what lies there
            # is not data
            at = lax.broadcasted_iota(jnp.int32, x.shape, dim)
            return jnp.where(at < k_rem, x.astype(jnp.float32), 0.0)

        def accumulate(last: bool):
            a, b = lhs_ref[...], rhs_ref[...]
            if last and k_rem:
                a = tail_zeroed(a, 1)
                b = tail_zeroed(b, 1 if transpose_rhs else 0)
            acc_ref[...] += lax.dot_general(
                a.astype(compute), b.astype(compute),
                (((1,), (1 if transpose_rhs else 0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if last:
                mine = _rows_of_group(visit, order_ref, tm, tn)
                out_ref[...] = jnp.where(
                    mine, acc_ref[...], out_ref[...].astype(jnp.float32)
                ).astype(out_ref.dtype)

        lax.cond(
            k_i == tiles_k - 1,
            functools.partial(accumulate, True),
            functools.partial(accumulate, False),
        )

    def lhs_index(n_i, visit, k_i, order_ref):
        return order_ref[2][visit], k_i

    def rhs_index(n_i, visit, k_i, order_ref):
        group = order_ref[1][visit]
        return (group, n_i, k_i) if transpose_rhs else (group, k_i, n_i)

    def out_index(n_i, visit, k_i, order_ref):
        return order_ref[2][visit], n_i

    out = pl.pallas_call(
        kernel,
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec(
                    (None, tn, tk) if transpose_rhs else (None, tk, tn),
                    rhs_index,
                ),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(pl.cdiv(n, tn), visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize
            + rhs.size * rhs.dtype.itemsize,
        ),
        interpret=interpret,
    )(order, lhs, rhs)
    return _zero_past_groups(out, group_sizes)


def _gmm_dw(lhs, grad, group_sizes, out_dtype, interpret: bool):
    """(m, k), (m, n) -> (groups, k, n): a group's rows of ``lhs``,
    transposed, times its rows of ``grad``."""
    m, k = lhs.shape
    n = grad.shape[1]
    groups = group_sizes.shape[0]
    tm = row_tile(m)
    tk, tn = _lane_tile(k), _lane_tile(n)
    order, visits = _visits(group_sizes, m, tm, visit_empty=True)
    compute = _compute_dtype(lhs, grad)

    def kernel(order_ref, lhs_ref, grad_ref, out_ref, acc_ref):
        visit = pl.program_id(2)
        offsets, group_ids, _ = order_ref
        group = group_ids[visit]
        first = (visit == 0) | (group_ids[jnp.maximum(visit - 1, 0)] != group)
        last_visit = pl.num_programs(2) - 1
        last = (visit == last_visit) | (
            group_ids[jnp.minimum(visit + 1, last_visit)] != group
        )

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[group + 1] > offsets[group])
        def _():
            # other groups' rows of the tile, and whatever lies past the
            # last group, are not this group's data (selected in float32,
            # as the kernel this follows does)
            a = jnp.where(
                _rows_of_group(visit, order_ref, tm, tk),
                lhs_ref[...].astype(jnp.float32), 0.0,
            ).swapaxes(0, 1)
            g = jnp.where(
                _rows_of_group(visit, order_ref, tm, tn),
                grad_ref[...].astype(jnp.float32), 0.0,
            )
            acc_ref[...] += lax.dot(
                a.astype(compute), g.astype(compute),
                preferred_element_type=jnp.float32,
            )

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_index(n_i, k_i, visit, order_ref):
        return order_ref[2][visit], k_i

    def grad_index(n_i, k_i, visit, order_ref):
        return order_ref[2][visit], n_i

    def out_index(n_i, k_i, visit, order_ref):
        return order_ref[1][visit], k_i, n_i

    return pl.pallas_call(
        kernel,
        name="moe_gmm_dw",
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((tm, tn), grad_index),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize
            + groups * k * n * jnp.dtype(out_dtype).itemsize,
        ),
        interpret=interpret,
    )(order, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, interpret=False):
    """``lhs[i] @ rhs[group of row i]`` in ``lhs``'s dtype, accumulated in
    float32; zeros in the rows past the last group (module docstring)."""
    return _gmm(lhs, rhs, group_sizes, False, interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
    out = _gmm(lhs, rhs, group_sizes, False, interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    grad = grad.astype(lhs.dtype)
    d_lhs = _gmm(grad, rhs, group_sizes, True, interpret)
    d_rhs = _gmm_dw(lhs, grad, group_sizes, rhs.dtype, interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
