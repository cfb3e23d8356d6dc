"""``ReferenceSteps`` after it was made to fit a model near the chip's size:
the same numbers to the last bit as the class it replaced (kept below), and
never more than five float32 copies of the weights alive on the device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
import train_reference
from train_reference import adam_step, leaf_norms, plain_dot


class ParentReferenceSteps:
    """``ReferenceSteps`` as it stood before (commit 28b32fa): ``start`` kept
    on the device, nothing donated. The yardstick for both tests."""

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
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        self._update = jax.jit(
            lambda p, m, v, g, count, t: adam_step(
                p, m, v, jax.tree_util.tree_map(lambda x: x / count, g), t, adam
            )
        )
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)
        ))

    def run(self, params, batches, mask_key, rows_used=None):
        if self._replicated is not None:
            params = jax.device_put(params, self._replicated)
        start = params
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        m, v = zeros, zeros
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
                grads = g if grads is None else self._add(grads, g)
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
        change = jax.device_get(self._change(params, start))
        return {
            "losses": losses,
            "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()},
        }


ADAM = tiny.traffic()["adam"]


def job(config, seed, rows=8):
    """(model, a maker of this seed's weights, three batches, masking key)."""
    model = harness.load_module("reference", config["reference"])
    rng = np.random.default_rng(seed)
    batches = [
        rng.integers(0, config["vocab_size"], (rows, 64), dtype=np.int32)
        for _ in range(3)
    ]
    make = jax.jit(lambda k: model.init_params(k, config))
    return model, lambda: make(jax.random.key(seed)), batches, jax.random.key(seed + 100)


@pytest.mark.parametrize(
    "config,chips,rows_used",
    [(tiny.GPT2, 1, None), (tiny.BERT, 1, None), (tiny.GPT2, 4, None),
     (tiny.GPT2, 1, slice(0, 3)), (tiny.GPT2, 4, slice(0, 2))],
    ids=["gpt2", "bert", "gpt2-4chips", "gpt2-3-rows", "gpt2-4chips-2-rows"],
)
def test_same_numbers_as_the_parent_to_the_last_bit(config, chips, rows_used):
    model, make, batches, key = job(config, seed=11)
    devices = jax.devices()[:chips] if chips > 1 else None
    want = ParentReferenceSteps(model, config, ADAM, 2, devices=devices).run(
        make(), batches, key, rows_used
    )
    steps = train_reference.ReferenceSteps(model, config, ADAM, 2, devices=devices)
    got = steps.run(make(), batches, key, rows_used)
    assert got["losses"] == want["losses"]
    assert got["grad_norms"] == want["grad_norms"]
    assert got["change_norms"] == want["change_norms"]
    # and again from the same object, on weights made anew (calibrate.py
    # reads many seeds through one ReferenceSteps)
    assert steps.run(make(), batches, key, rows_used) == want


def test_run_consumes_the_weights_it_is_given():
    model, make, batches, key = job(tiny.GPT2, seed=12)
    params = make()
    train_reference.ReferenceSteps(model, tiny.GPT2, ADAM, 2).run(params, batches, key)
    assert all(x.is_deleted() for x in params.values())


def live_float32_bytes():
    return sum(
        x.nbytes for x in jax.live_arrays()
        if x.dtype == jnp.float32 and not x.is_deleted()
    )


def watched(steps, samples, base):
    """Samples the float32 bytes alive on the device after each call of the
    three programs that hold trees of the weights' size."""
    def watch(fn):
        def call(*args):
            out = jax.block_until_ready(fn(*args))
            samples.append(live_float32_bytes() - base)
            return out
        return call

    for name in ("_grad", "_add", "_update"):
        setattr(steps, name, watch(getattr(steps, name)))
    return steps


@pytest.mark.parametrize(
    "cls,least,most",
    [(train_reference.ReferenceSteps, 4.9, 5.5), (ParentReferenceSteps, 7.0, 10.5)],
    ids=["change", "parent"],
)
def test_copies_of_the_weights_alive_on_the_device(cls, least, most):
    """Four blocks a step, so that the sum over blocks is exercised. The
    change peaks at 5 copies (weights, two moments, the sum, one block's
    gradient); the parent at 10 from the second step on (its start, the
    zeros the moments began as, the old and the new weights and moments, the
    sum and the last block's gradient)."""
    model, make, batches, key = job(tiny.GPT2, seed=13)
    params = make()
    weights = sum(x.nbytes for x in params.values())
    samples = []
    base = live_float32_bytes() - weights  # what other tests left alive
    steps = watched(cls(model, tiny.GPT2, ADAM, 2), samples, base)
    steps.run(params, batches, key)
    assert len(samples) == 3 * (4 + 3 + 1)
    assert least < max(samples) / weights <= most, [s / weights for s in samples]
