"""The first optimizer steps as plain float32 mathematics, and the numbers
that decide ``correct`` for a training cell.

The model is a file under ``reference/``; this file adds what every
training cell shares: the step's loss as a mean over blocks of rows (so
that float32 activations of a whole batch never have to fit), Adam as
Kingma & Ba wrote it, and the comparison by the worst leaf. It imports
nothing of the program and is handed nothing the program made except the
rows its loader served and the program's own readings.
"""

import statistics

import jax
import jax.numpy as jnp
import numpy as np

# leaves whose first reference gradient is under this share of the median
# leaf's are nought to rounding (a key's bias under softmax): Adam moves
# them by round-off alone, so their change is not compared
DEAD_GRADIENT_SHARE = 1e-3

FP8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def plain_dot(a, b):
    """Float32 product at full precision (on a TPU the default is not)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _to_fp8(x, dtype):
    """Round to fp8 with one scale for the tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX[dtype]
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_dot(a, b):
    """The control's product: the usual fp8 training recipe, operands in
    e4m3 forward and the incoming gradient in e5m2 backward, float32
    accumulation. The precision below the bfloat16 the cells state."""
    return plain_dot(_to_fp8(a, jnp.float8_e4m3fn), _to_fp8(b, jnp.float8_e4m3fn))


def _fp8_dot_fwd(a, b):
    a8, b8 = _to_fp8(a, jnp.float8_e4m3fn), _to_fp8(b, jnp.float8_e4m3fn)
    return plain_dot(a8, b8), (a8, b8)


def _fp8_dot_bwd(operands, g):
    _, pullback = jax.vjp(plain_dot, *operands)
    return pullback(_to_fp8(g, jnp.float8_e5m2))


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def leaf_norms(tree):
    """Euclidean norm of every leaf, in float32, as one small transfer."""
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree
    )


def adam_step(params, m, v, grads, t, adam):
    """One step of Adam (Kingma & Ba 2015, algorithm 1; epsilon outside the
    root), ``t`` counted from 1."""
    b1, b2, lr, eps = adam["b1"], adam["b2"], adam["lr"], adam["eps"]
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        params, m, v,
    )
    return params, m, v


class ReferenceSteps:
    """Follows the first steps of a run on the rows the loader served.

    ``rows_used`` plants a fault for the calibration and the tests: only
    those rows of each batch are trained on, the mean taken over them.

    On a cell of several chips the blocks are that many times as large and
    each chip takes its share of a block's rows (the weights replicated, the
    compiler summing the gradient), so that the reference of a four-chip
    cell takes no longer than that of a one-chip cell.

    Memory: at its fullest the device holds five float32 copies of the
    weights (the weights, Adam's two moments, the gradient summed so far
    and one block's gradient: 20 bytes a parameter) and one block's
    activations. The sum and the update are done in place (their inputs are
    donated), and the weights the steps start from wait on the host until
    the change is measured. Nothing here rematerialises: a model whose
    block of float32 activations would not fit (a few GB from 4096 tokens
    on) wraps each layer of its own ``loss_sum`` in ``jax.checkpoint``."""

    def __init__(self, model, sizes, adam, block_rows, dot=plain_dot, devices=None):
        self.model, self.sizes, self.adam = model, sizes, adam
        self.block_rows = block_rows
        self._by_rows = self._replicated = None
        if devices is not None and len(devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(np.asarray(devices), ("rows",))
            self._by_rows = NamedSharding(mesh, PartitionSpec("rows"))
            self._replicated = NamedSharding(mesh, PartitionSpec())
            self.block_rows = block_rows * len(devices)
        self._prepare = jax.jit(
            lambda tokens, key, step: model.step_rows(tokens, key, step, sizes)
        )
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, rows: model.loss_sum(p, rows, sizes, dot), has_aux=True
        ))
        self._add = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0
        )
        self._update = jax.jit(
            lambda p, m, v, g, count, t: adam_step(
                p, m, v, jax.tree_util.tree_map(lambda x: x / count, g), t, adam
            ),
            donate_argnums=(0, 1, 2),
        )
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)
        ))

    def run(self, params, batches, mask_key, rows_used=None):
        """{"losses": [...], "grad_norms": {leaf: norm of the first
        gradient}, "change_norms": {leaf: norm of the change after the last
        step}} for the steps on ``batches`` (host arrays of token rows).

        Consumes ``params``: the first update is done in place, in the
        buffers it is given. Hand it weights made for this call."""
        if self._replicated is not None:
            params = jax.device_put(params, self._replicated)
        # to the host leaf by leaf, through a copy: on the CPU the host's
        # view of a buffer is the buffer, and one still looked at is not donated
        start = jax.tree_util.tree_map(
            lambda x: jax.device_get(jnp.copy(x)), params
        )
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for step, tokens in enumerate(batches):
            rows = self._prepare(jnp.asarray(tokens), mask_key, step)
            if rows_used is not None:
                rows = {k: x[rows_used] for k, x in rows.items()}
            n = next(iter(rows.values())).shape[0]
            total, count, grads = 0.0, 0, None
            for lo in range(0, n, self.block_rows):
                block = {k: x[lo:lo + self.block_rows] for k, x in rows.items()}
                if self._by_rows is not None:
                    even = min(self.block_rows, n - lo) % self._by_rows.mesh.size == 0
                    block = jax.device_put(
                        block, self._by_rows if even else self._replicated
                    )
                (loss, scored), g = self._grad(params, block)
                if grads is None:
                    grads = g
                else:
                    # the host waits for the sum: run ahead, it would have the
                    # next block's gradient allocated while this one is still
                    # being added, a sixth copy
                    grads = jax.block_until_ready(self._add(grads, g))
                del g  # or it lives on beside the next block's
                total, count = total + float(loss), count + int(scored)
            losses.append(total / count)
            if grad_norms is None:
                grad_norms = {
                    k: float(x) / count
                    for k, x in jax.device_get(self._norms(grads)).items()
                }
            params, m, v = self._update(
                params, m, v, grads, jnp.float32(count), step + 1
            )
        del m, v, grads
        change = jax.device_get(self._change(params, jax.device_put(
            start, self._replicated
        )))
        return {
            "losses": losses,
            "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()},
        }


def worst_leaf_gap(got, want, leaves):
    """Largest gap between two norms of a leaf, against the reference's norm
    of that leaf or of the median leaf, whichever is larger; and the leaf."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for leaf in leaves:
        gap = abs(got[leaf] - want[leaf]) / max(want[leaf], floor)
        if not gap <= worst:  # a NaN is the worst there is
            worst, where = gap, leaf
    return worst, where


def compare(program, reference):
    """The numbers held to a limit, from two readings of the same steps:
    ``{name: value}`` and the leaf behind each worst-leaf number."""
    numbers, leaves = {}, {}
    for i, (got, want) in enumerate(zip(program["losses"], reference["losses"])):
        numbers[f"loss_step{i + 1}_gap"] = abs(got - want) / abs(want)
    every = sorted(reference["grad_norms"])
    numbers["first_grad_norm_gap"], leaves["first_grad_norm_gap"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"], every
    )
    floor = DEAD_GRADIENT_SHARE * statistics.median(reference["grad_norms"].values())
    alive = [k for k in every if reference["grad_norms"][k] >= floor]
    numbers["param_change_norm_gap"], leaves["param_change_norm_gap"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], alive
    )
    return numbers, leaves


def rows_missing(batches, dataset_tokens):
    """How many of the served rows are not rows of the dataset, or were
    served twice: the loader's part of ``correct``. Exact, so the limit is 0."""
    known = {row.tobytes() for row in np.asarray(dataset_tokens)}
    seen, bad = set(), 0
    for batch in batches:
        for row in np.asarray(batch):
            key = row.tobytes()
            if key not in known or key in seen:
                bad += 1
            seen.add(key)
    return bad
