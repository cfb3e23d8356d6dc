"""Bucketed comm/compute overlap for the gradient sync (parallel/wire.py
``plan_buckets``/``sync_grads``).

Evidence layers, mirroring the ZeRO-1/wire test structure:

- bucket-plan structure: reverse issue order, size-targeted sealing,
  scatter/psum kind separation, non-divisible leaf sizes covered exactly;
- sync numerics on the 8-device fake CPU mesh: the UNCOMPRESSED bucketed
  path is BIT-EXACT vs the inline per-leaf path (concatenating leaves
  never changes the element-wise psum reduction), the compressed path
  within the analytic per-block quantization bound;
- K-step Adam trajectory bucketed-vs-inline within the test_zero1 bars,
  with the fused buckets visible as FEWER gradient collectives in the
  compiled step;
- checkpoint resume across a bucketed<->inline flip is bit-exact (the
  bucket schedule changes the wire, never the state contract).

(The ``inline-grad-sync`` lint rule guarding this schedule is covered in
tests/test_graft_lint.py, which scripts/precommit.sh runs backend-free.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from distributed_pytorch_example_tpu.analysis.collectives import (
    parse_collective_dtypes,
    parse_collectives,
)
from distributed_pytorch_example_tpu.models.gpt2 import GPT2
from distributed_pytorch_example_tpu.parallel.api import data_parallel
from distributed_pytorch_example_tpu.parallel.wire import (
    WireConfig,
    plan_buckets,
    sync_grads,
)
from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib
from distributed_pytorch_example_tpu.train.step import (
    build_train_step,
    init_state,
)
from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

# one quantize/dequantize pass error in units of the block amax
# (tests/test_wire.py derives the constant)
_STEP_BOUND = 1.02 / 127.0


def _tiny_model():
    return GPT2(
        vocab_size=64, max_len=32, model_dim=32, num_layers=1,
        num_heads=2, mlp_dim=64, logits_mode="hidden",
    )


def _batch(partitioner, n=16, seq=16, seed=0):
    tokens = np.random.default_rng(seed).integers(
        0, 64, (n, seq)
    ).astype(np.int32)
    return {
        "tokens": jax.device_put(tokens, partitioner.batch_sharding())
    }


def _smap(mesh, fn, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _max_diff(a, b):
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(
            jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
        ),
        a, b,
    )
    return max(jax.tree_util.tree_leaves(diffs))


# ---------------------------------------------------------------------------
# bucket plan structure (static — no mesh)
# ---------------------------------------------------------------------------


def test_plan_buckets_structure_and_boundaries():
    """Reverse issue order, kind separation, exact leaf coverage — with
    leaf sizes that divide NEITHER the bucket target NOR the block size."""
    grads = {
        "a": jax.ShapeDtypeStruct((16, 5), jnp.float32),   # scatter, 80
        "b": jax.ShapeDtypeStruct((24,), jnp.float32),     # scatter, 24
        "c": jax.ShapeDtypeStruct((7,), jnp.float32),      # psum, 7
        "e": jax.ShapeDtypeStruct((3, 3), jnp.float32),    # psum, 9
        "z": jax.ShapeDtypeStruct((0,), jnp.float32),      # zero-size
    }
    dims = {"a": 0, "b": 0, "c": None, "e": None, "z": None}
    cfg = WireConfig(bucket_bytes=64)
    plan = plan_buckets(dims, grads, cfg, axis_size=8)

    leaves = jax.tree_util.tree_leaves(grads)
    covered = [i for b in plan.buckets for i in b.leaves]
    # every non-empty leaf exactly once; the zero-size leaf never planned
    nonzero = [i for i, x in enumerate(leaves) if x.size]
    assert sorted(covered) == sorted(nonzero)
    assert len(covered) == len(set(covered))
    for b in plan.buckets:
        kinds = {
            "scatter" if jax.tree_util.tree_leaves(
                dims, is_leaf=lambda d: d is None
            )[i] is not None else "psum"
            for i in b.leaves
        }
        assert kinds == {b.kind}  # kinds never mix inside a bucket
        assert b.elements == sum(int(leaves[i].size) for i in b.leaves)
        # issue order within a bucket is reverse trace order
        assert list(b.leaves) == sorted(b.leaves, reverse=True)
    # the 64 B target actually splits the tree (not one bucket per kind)
    assert len(plan.buckets) >= 3, plan.to_json()
    # sync_grads stamps bucket k's collective with the scope
    # wire_bucket<index>: the indices ARE the issue order
    assert [b.index for b in plan.buckets] == list(range(len(plan.buckets)))
    js = plan.to_json()
    assert js["num_buckets"] == len(plan.buckets)
    assert all(b["wire_bytes"] > 0 for b in js["buckets"])


# ---------------------------------------------------------------------------
# sync numerics: bucketed vs inline on the fake 8-device mesh
# ---------------------------------------------------------------------------


def _sync_tree(mesh, config):
    """Run sync_grads over a mixed non-divisible tree; returns np leaves."""
    rng = np.random.default_rng(7)
    grads = {
        "a": rng.standard_normal((8, 16, 5)).astype(np.float32),
        "b": rng.standard_normal((8, 24)).astype(np.float32),
        "c": rng.standard_normal((8, 7)).astype(np.float32),
        "e": rng.standard_normal((8, 3, 3)).astype(np.float32),
    }
    dims = {"a": 1, "b": 1, "c": None, "e": None}

    def fn(g):
        return sync_grads(g, dims, "data", config=config, scale=0.125)

    specs = jax.tree_util.tree_map(lambda _: P("data"), grads)
    with mesh:
        out = _smap(mesh, fn, (specs,), specs)(grads)
    return {k: np.asarray(v) for k, v in out.items()}, grads


def test_bucketed_uncompressed_is_bit_exact(mesh_1d):
    """Fused fp32 buckets must be BIT-identical to the inline per-leaf
    sync: concatenation re-groups rows, never re-orders the reduction."""
    inline, _ = _sync_tree(mesh_1d, WireConfig())
    bucketed, _ = _sync_tree(mesh_1d, WireConfig(bucket_bytes=64))
    for k in inline:
        np.testing.assert_array_equal(bucketed[k], inline[k])


def test_bucketed_compressed_within_block_bound(mesh_1d):
    """Quantization blocks span leaf joins in a bucket; the error bound
    (sum of d per-source block errors, 2 passes for psum) still holds."""
    exact, grads = _sync_tree(mesh_1d, WireConfig())
    got, _ = _sync_tree(
        mesh_1d,
        WireConfig(
            compress="int8-block", block_size=64, min_size=1,
            bucket_bytes=64,
        ),
    )
    amax = max(np.abs(v).max() for v in grads.values())
    scale = 0.125
    diff = 0.0
    for k in exact:
        passes = 2 if k in ("c", "e") else 1  # psum = RS + quantized AG
        bound = passes * 8 * amax * _STEP_BOUND * scale
        d = np.abs(got[k] - exact[k]).max()
        assert d <= bound, (k, d, bound)
        diff = max(diff, d)
    assert diff > 0.0  # it really quantized


# ---------------------------------------------------------------------------
# trajectory: K Adam steps through the full train step
# ---------------------------------------------------------------------------

_RUN_CACHE = {}


def _run(mesh, *, bucket_bytes, compress="none", steps=3):
    """(final state, collectives, dtype mix, losses) per sync mode,
    memoized — each entry is a full jit compile on the one-core box."""
    key = (bucket_bytes, compress, steps)
    if key in _RUN_CACHE:
        return _RUN_CACHE[key]
    model, task, opt = _tiny_model(), CausalLMTask(), optax.adam(1e-3)
    cfg = WireConfig(
        compress=compress, min_size=1, bucket_bytes=bucket_bytes
    )
    part = data_parallel(
        mesh, dp_shard_opt_state=True, opt_shard_min_size=1, wire=cfg
    )
    batch = _batch(part)
    with mesh:
        state, _ = init_state(
            model, opt, batch["tokens"], jax.random.key(0), part
        )
        step = build_train_step(
            model, task, opt, partitioner=part, grad_accum_steps=1
        )
        text = step.lower(state, batch).compile().as_text()
        coll = parse_collectives(text)
        dtypes = parse_collective_dtypes(text)
        losses = []
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    _RUN_CACHE[key] = (state, coll, dtypes, losses)
    return _RUN_CACHE[key]


def test_bucketed_step_matches_inline(mesh_1d):
    """Params within the test_zero1 bar after K Adam steps, and the
    compiled step fuses the per-leaf reduce-scatters into buckets."""
    s_inline, coll_i, _, losses_i = _run(mesh_1d, bucket_bytes=0)
    s_bucket, coll_b, _, losses_b = _run(mesh_1d, bucket_bytes=8192)

    assert _max_diff(s_bucket.params, s_inline.params) < 5e-4
    for li, lb in zip(losses_i, losses_b):
        assert abs(li - lb) < 1e-3, (losses_i, losses_b)

    # fused buckets: strictly fewer gradient reduce-scatters than the
    # per-leaf inline step, but still at least one (no silent all-reduce)
    rs_inline = coll_i.get("reduce-scatter", {}).get("count", 0)
    rs_bucket = coll_b.get("reduce-scatter", {}).get("count", 0)
    assert rs_inline > rs_bucket >= 1, (rs_inline, rs_bucket)
    # ZeRO-1 invariant holds under bucketing: no gradient-sized AR
    grad_bytes = coll_b["reduce-scatter"]["bytes"]
    assert coll_b.get("all-reduce", {}).get("bytes", 0) < grad_bytes


def test_bucketed_compressed_trajectory(mesh_1d):
    """Bucketed int8 wire: loss trajectory within the test_wire Adam bar
    vs the uncompressed inline step, and the step really moves s8."""
    _, _, dt_plain, losses_i = _run(mesh_1d, bucket_bytes=0)
    _, _, dt_q, losses_q = _run(
        mesh_1d, bucket_bytes=8192, compress="int8-block"
    )
    for li, lq in zip(losses_i, losses_q):
        assert abs(li - lq) < 1e-3, (losses_i, losses_q)
    assert losses_i != losses_q  # identical would mean silent fp32
    assert sum(rec.get("s8", 0) for rec in dt_q.values()) > 0, dt_q
    assert sum(rec.get("s8", 0) for rec in dt_plain.values()) == 0
    # the quantized bucket RS decomposes to all-to-all, like the inline
    # compressed path
    assert "all-to-all" in dt_q


def test_checkpoint_resume_across_bucketing_flip(mesh_1d, tmp_path):
    """A bucketed run's checkpoint restores into an inline step (and
    back) bit-exact: bucketing changes the wire schedule, never the
    checkpointed state contract."""
    path = str(tmp_path / "ckpt")
    model, task = _tiny_model(), CausalLMTask()
    optimizer = optax.adam(1e-3)

    def build(bucket_bytes):
        cfg = WireConfig(min_size=1, bucket_bytes=bucket_bytes)
        part = data_parallel(
            mesh_1d, dp_shard_opt_state=True, opt_shard_min_size=1,
            wire=cfg,
        )
        batch = _batch(part)
        with mesh_1d:
            state, shardings = init_state(
                model, optimizer, batch["tokens"], jax.random.key(0), part
            )
            step = build_train_step(
                model, task, optimizer, partitioner=part,
                grad_accum_steps=1,
            )
        return batch, state, shardings, step

    batch, state, _, step = build(8192)
    with mesh_1d:
        for _ in range(2):
            state, _ = step(state, batch)
    ckpt_lib.save_checkpoint(path, state, 1, 0.0, {})

    batch_i, template_i, shardings_i, step_i = build(0)
    loaded, epoch, _ = ckpt_lib.load_checkpoint(
        path, template_i, shardings_i
    )
    assert epoch == 1
    assert _max_diff(loaded.params, state.params) == 0.0
    assert _max_diff(loaded.opt_state[0].mu, state.opt_state[0].mu) == 0.0
    with mesh_1d:
        stepped, _ = step_i(loaded, batch_i)

    ckpt_lib.save_checkpoint(path, stepped, 2, 0.0, {})
    batch_b, template_b, shardings_b, step_b = build(8192)
    loaded_b, epoch_b, _ = ckpt_lib.load_checkpoint(
        path, template_b, shardings_b
    )
    assert epoch_b == 2
    assert _max_diff(loaded_b.params, stepped.params) == 0.0
    with mesh_1d:
        step_b(loaded_b, batch_b)


# the inline-grad-sync lint rule's fixtures live in tests/
# test_graft_lint.py (test_inline_grad_sync_*), which scripts/
# precommit.sh runs backend-free; the shipped train/step.py clean gate
# is test_zero1.test_step_source_is_lint_clean
