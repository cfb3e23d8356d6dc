"""graft-wire: block-quantized collectives (parallel/wire.py) and the
Pallas async ring kernels (ops/pallas/collectives.py).

Three layers of evidence, mirroring the ZeRO-1 test structure:

- quantizer unit bounds (round-trip error per block size, stochastic
  unbiasedness) — pure math, no mesh;
- collective equivalence on the 8-device fake CPU mesh: each wire_*
  drop-in vs the raw ``lax`` collective it replaces, with analytic
  per-block error bounds for the compressed forms and EXACT equality for
  the passthrough forms;
- trajectory equivalence: K optimizer steps fp32 vs int8-block within
  the test_zero1 bars (Adam loss trajectory, SGD param parity — Adam's
  sign-sensitive moments amplify quantization noise on PARAMS far above
  what the LOSS trajectory shows, so the Adam bar is on the loss), plus
  checkpoint resume across a compress-mode flip.

The Pallas ring kernels only lower on TPU; on this CPU mesh every ring
entry point must take the identical-numerics XLA fallback, which is
asserted exactly. The TPU numerics comparison runs wherever the kernel
actually lowers (skipped here).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_pytorch_example_tpu.analysis.collectives import (
    parse_collective_dtypes,
)
from distributed_pytorch_example_tpu.models.gpt2 import GPT2
from distributed_pytorch_example_tpu.ops.pallas import collectives as ring
from distributed_pytorch_example_tpu.parallel import wire as wirelib
from distributed_pytorch_example_tpu.parallel.api import data_parallel
from distributed_pytorch_example_tpu.parallel.wire import (
    WireConfig,
    dequantize_blocks,
    grad_wire_report,
    quantize_blocks,
    wire_all_gather,
    wire_psum,
    wire_psum_scatter,
)
from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib
from distributed_pytorch_example_tpu.train.step import (
    build_train_step,
    init_state,
)
from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

# per-element round-trip bound for one quantize/dequantize pass, in units
# of the block's amax: 0.5/127 round-to-nearest plus up to 2^-8 relative
# bf16 scale error (8-bit significand) on a value up to amax — ~1.0
# quantization steps total (measured worst case ~0.82)
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


# ---------------------------------------------------------------------------
# WireConfig policy
# ---------------------------------------------------------------------------


def test_wireconfig_validation_and_floor():
    with pytest.raises(ValueError, match="compress"):
        WireConfig(compress="fp8")
    with pytest.raises(ValueError, match="param_gather"):
        WireConfig(param_gather="fp16")
    with pytest.raises(ValueError, match="ring"):
        WireConfig(ring="always")
    with pytest.raises(ValueError, match="block_size"):
        WireConfig(block_size=0)

    assert not WireConfig().active
    assert WireConfig(compress="int8-block").active
    assert WireConfig(param_gather="bf16").active

    cfg = WireConfig(compress="int8-block", min_size=2048)
    assert cfg.compresses(2048) and cfg.compresses(1 << 20)
    assert not cfg.compresses(2047)  # bias-sized leaves stay fp32
    assert not WireConfig().compresses(1 << 20)


# ---------------------------------------------------------------------------
# block quantizer: round-trip bounds per block size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size", [32, 64, 256, 1024])
def test_quantize_roundtrip_error_bound(block_size):
    rng = np.random.default_rng(block_size)
    # 3000 elements: NOT a block multiple for any tested size — the tail
    # block pads with zeros and must slice back off exactly
    x = (rng.standard_normal(3000) * rng.uniform(0.1, 10)).astype(
        np.float32
    )
    q, scales = quantize_blocks(jnp.asarray(x), block_size)
    assert q.dtype == jnp.int8 and scales.dtype == jnp.bfloat16
    out = np.asarray(dequantize_blocks(q, scales, x.shape))
    assert out.shape == x.shape

    err = np.abs(out - x)
    pad = (-x.size) % block_size
    blocks = np.pad(x, (0, pad)).reshape(-1, block_size)
    amax = np.abs(blocks).max(axis=1, keepdims=True)
    bound = np.broadcast_to(amax * _STEP_BOUND, blocks.shape)
    assert (err <= bound.reshape(-1)[: x.size] + 1e-12).all(), err.max()


def test_quantize_zero_block_exact_and_shapes():
    x = jnp.zeros((512,), jnp.float32)
    q, scales = quantize_blocks(x, 128)
    assert np.asarray(dequantize_blocks(q, scales, x.shape)).max() == 0.0
    # one scale per block, values grouped per block
    assert q.shape == (4, 128) and scales.shape == (4, 1)


def test_stochastic_rounding_is_unbiased():
    # unbiasedness is a property of the ROUNDING, so test it on the
    # integer lattice (before the bf16 scale multiplies back in, which
    # adds its own small deterministic error): E[q] must converge to the
    # exact scaled value, which round-to-nearest cannot do
    rng = np.random.default_rng(7)
    x = rng.uniform(-1.0, 1.0, 256).astype(np.float32)
    blocks = x.reshape(-1, 64)
    amax = np.abs(blocks).max(axis=1, keepdims=True)
    scaled = (blocks * (127.0 / amax)).reshape(-1)  # exact target

    rows = jnp.asarray(x)[None]
    acc = np.zeros(x.shape, np.float64)
    n = 200
    for i in range(n):
        q, _ = wirelib._quantize_rows(rows, 64, key=jax.random.key(i))
        draw = np.asarray(q[0], np.float64).reshape(-1)
        # floor(y + u), u ~ U[0,1): every draw within ONE step of y
        assert (np.abs(draw - scaled) < 1.0 + 1e-5).all()
        acc += draw
    mean_err = np.abs(acc / n - scaled).max()
    # std of the mean <= 0.5/sqrt(n) ~ 0.035 steps: 0.2 is ~5 sigma,
    # while round-to-nearest sits a deterministic ~0.5 steps off for
    # mid-step values
    assert mean_err < 0.2, mean_err
    q_det, _ = wirelib._quantize_rows(rows, 64)
    det_err = np.abs(
        np.asarray(q_det[0], np.float64).reshape(-1) - scaled
    ).max()
    assert det_err > mean_err  # nearest-rounding bias really is larger


# ---------------------------------------------------------------------------
# collective drop-ins vs the raw lax collectives (8-device fake mesh)
# ---------------------------------------------------------------------------

_INT8 = WireConfig(compress="int8-block", block_size=64, min_size=1)


def test_wire_psum_scatter_matches_lax(mesh_1d):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 256)).astype(np.float32)

    def wire_fn(v):
        return wire_psum_scatter(
            v, "data", scatter_dimension=1, config=_INT8
        )

    def lax_fn(v):
        return lax.psum_scatter(
            v, "data", scatter_dimension=1, tiled=True
        )

    with mesh_1d:
        # in_specs P("data"): each device contributes a DISTINCT (1, 256)
        # shard; out P("data") stacks each device's scattered chunk
        got = _smap(mesh_1d, wire_fn, (P("data"),), P("data"))(x)
        ref = _smap(mesh_1d, lax_fn, (P("data"),), P("data"))(x)
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape == (8, 32)
    # 8 independently quantized contributions sum: bound is the sum of
    # the per-source per-block bounds (conservatively: global amax)
    bound = 8 * np.abs(x).max() * _STEP_BOUND
    assert np.abs(got - ref).max() <= bound
    assert np.abs(got - ref).max() > 0.0  # it really quantized

    # passthrough forms are EXACT: compress="none" and the min_size floor
    for cfg in (WireConfig(), WireConfig(compress="int8-block",
                                         min_size=1 << 20)):
        with mesh_1d:
            exact = _smap(
                mesh_1d,
                lambda v, c=cfg: wire_psum_scatter(
                    v, "data", scatter_dimension=1, config=c
                ),
                (P("data"),), P("data"),
            )(x)
        np.testing.assert_array_equal(np.asarray(exact), ref)


def test_wire_psum_scatter_rejects_indivisible(mesh_1d):
    x = np.zeros((8, 12), np.float32)  # 12 % 8 != 0
    with mesh_1d:
        fn = _smap(
            mesh_1d,
            lambda v: wire_psum_scatter(
                v, "data", scatter_dimension=1, config=_INT8
            ),
            (P("data"),), P("data"),
        )
        with pytest.raises(ValueError, match="must divide"):
            fn(x)


def test_wire_psum_matches_lax(mesh_1d):
    rng = np.random.default_rng(1)
    # 300 elements per shard: NOT divisible by the 8-way axis, so the
    # compressed path exercises its pad/unpad
    x = rng.standard_normal((8, 300)).astype(np.float32)

    with mesh_1d:
        got = _smap(
            mesh_1d,
            lambda v: wire_psum(v, "data", config=_INT8),
            (P("data"),), P("data"),
        )(x)
        ref = _smap(
            mesh_1d,
            lambda v: lax.psum(v, "data"),
            (P("data"),), P("data"),
        )(x)
    got, ref = np.asarray(got), np.asarray(ref)
    # two quantized wire passes: the RS pass sums 8 quantized
    # contributions, then the reduced chunk (magnitude up to 8x the
    # input amax) quantizes once more for the gather
    bound = (8 + 8) * np.abs(x).max() * _STEP_BOUND
    assert np.abs(got - ref).max() <= bound
    assert np.abs(got - ref).max() > 0.0

    with mesh_1d:
        exact = _smap(
            mesh_1d,
            lambda v: wire_psum(v, "data", config=WireConfig()),
            (P("data"),), P("data"),
        )(x)
    np.testing.assert_array_equal(np.asarray(exact), ref)


def test_wire_all_gather_matches_lax(mesh_1d):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, 64)).astype(np.float32)

    with mesh_1d:
        got = _smap(
            mesh_1d,
            lambda v: wire_all_gather(
                v, "data", gather_dimension=0, config=_INT8
            ),
            (P("data"),), P(),
        )(x)
        ref = _smap(
            mesh_1d,
            lambda v: lax.all_gather(v, "data", axis=0, tiled=True),
            (P("data"),), P(),
        )(x)
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape == (8, 64)
    # gather does not sum: each element carries only ITS OWN shard's
    # one-pass quantization error
    assert np.abs(got - ref).max() <= np.abs(x).max() * _STEP_BOUND
    assert np.abs(got - ref).max() > 0.0


def test_ring_entry_points_fall_back_exactly_on_cpu(mesh_1d):
    """Off-TPU the ring kernels must BE the XLA collective: identical
    bits, not just close — the fallback contract every caller relies on."""
    assert not ring.ring_supported()  # fake CPU mesh
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, 2, 128)).astype(np.float32)

    with mesh_1d:
        ag = _smap(
            mesh_1d,
            lambda v: ring.ring_all_gather(v, "data"),
            (P("data"),), P(),
        )(x)
        ag_ref = _smap(
            mesh_1d,
            lambda v: lax.all_gather(v, "data", axis=0, tiled=True),
            (P("data"),), P(),
        )(x)
        # shard_map local shape is (1, 256): scatter over dim 1
        rs = _smap(
            mesh_1d,
            lambda v: ring.ring_reduce_scatter(
                v, "data", scatter_dimension=1
            ),
            (P("data"),), P("data"),
        )(np.ascontiguousarray(x.reshape(8, 256)))
        rs_ref = _smap(
            mesh_1d,
            lambda v: lax.psum_scatter(
                v, "data", scatter_dimension=1, tiled=True
            ),
            (P("data"),), P("data"),
        )(np.ascontiguousarray(x.reshape(8, 256)))
    np.testing.assert_array_equal(np.asarray(ag), np.asarray(ag_ref))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(rs_ref))


@pytest.mark.parametrize("span", [2, 4])
@pytest.mark.parametrize(
    "chunk", [2 * 128 * 8, 1000, 2 * 128 * 1100],
    ids=["one-tile", "padded", "three-tiles"],
)
def test_ring_reduce_scatter_kernel_interpreted(devices, span, chunk):
    """The ring reduce-scatter KERNEL (remote DMAs, semaphores, the row-
    tile grid and its cross-step double-buffer slots) on the CPU mesh
    under the Pallas TPU interpreter, vs ``lax.psum_scatter``: a
    lane-aligned one-tile chunk, one that needs zero padding, and one
    that spans three grid steps."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices[:span]), ("data",))
    x = np.random.default_rng(5).standard_normal(
        (span, span * chunk)
    ).astype(np.float32)

    def run(fn):
        return np.asarray(jax.jit(jax.shard_map(
            lambda v: fn(v[0])[None], mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=False,
        ))(x))

    got = run(lambda v: ring.reduce_scatter_kernel(
        v, "data", span, interpret=pltpu.InterpretParams()
    ))
    ref = run(lambda v: lax.psum_scatter(
        v, "data", scatter_dimension=0, tiled=True
    ))
    # f32 adds in ring order vs XLA's order: tight, not bit-exact
    np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_ring_all_gather_kernel_interpreted(devices, dtype):
    """The ring all-gather KERNEL under the Pallas TPU interpreter on four
    CPU devices: moving bytes is exact, for the f32 payload and the s8 one
    of the int8 wire."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh

    span, n = 4, 2 * 128 * 37
    mesh = Mesh(np.array(devices[:span]), ("data",))
    x = (np.random.default_rng(6).standard_normal(span * n) * 50).astype(dtype)
    rows = ring._half_rows(n)
    got = jax.jit(jax.shard_map(
        lambda v: ring.all_gather_kernel(
            v, "data", span, rows, interpret=pltpu.InterpretParams()
        ),
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
    ))(x)
    np.testing.assert_array_equal(np.asarray(got), x)


def test_ring_kernel_numerics_on_tpu(mesh_1d):
    """The ring kernels vs the XLA collectives where they actually lower
    (f32 adds in ring order vs XLA's order: tight but not bit-exact)."""
    if not ring.ring_supported():
        pytest.skip("Pallas ring kernels need a multi-chip TPU backend")
    rng = np.random.default_rng(4)
    x = rng.standard_normal((8, 1024)).astype(np.float32)
    with mesh_1d:
        ag = _smap(
            mesh_1d,
            lambda v: ring.ring_all_gather(v, "data"),
            (P("data"),), P(),
        )(x)
        ag_ref = _smap(
            mesh_1d,
            lambda v: lax.all_gather(v, "data", axis=0, tiled=True),
            (P("data"),), P(),
        )(x)
        rs = _smap(
            mesh_1d,
            lambda v: ring.ring_reduce_scatter(
                v, "data", scatter_dimension=1
            ),
            (P("data"),), P("data"),
        )(x)
        rs_ref = _smap(
            mesh_1d,
            lambda v: lax.psum_scatter(
                v, "data", scatter_dimension=1, tiled=True
            ),
            (P("data"),), P("data"),
        )(x)
    np.testing.assert_array_equal(np.asarray(ag), np.asarray(ag_ref))
    np.testing.assert_allclose(
        np.asarray(rs), np.asarray(rs_ref), atol=1e-5
    )


# ---------------------------------------------------------------------------
# trajectory equivalence: the compressed step trains the same model
# ---------------------------------------------------------------------------

_RUN_CACHE = {}


def _run(mesh, *, wire, opt="adam", steps=3):
    """(final state, per-step losses, compiled dtype mix) for one config."""
    key = (wire, opt, steps)
    if key in _RUN_CACHE:
        return _RUN_CACHE[key]
    model, task = _tiny_model(), CausalLMTask()
    optimizer = optax.adam(1e-3) if opt == "adam" else optax.sgd(1e-2)
    cfg = (
        WireConfig(compress="int8-block", min_size=1)
        if wire else WireConfig()
    )
    part = data_parallel(
        mesh, dp_shard_opt_state=True, opt_shard_min_size=1, wire=cfg
    )
    batch = _batch(part)
    with mesh:
        state, _ = init_state(
            model, optimizer, batch["tokens"], jax.random.key(0), part
        )
        step = build_train_step(
            model, task, optimizer, partitioner=part, grad_accum_steps=1
        )
        dtypes = parse_collective_dtypes(
            step.lower(state, batch).compile().as_text()
        )
        losses = []
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    _RUN_CACHE[key] = (state, losses, dtypes)
    return _RUN_CACHE[key]


def _max_diff(a, b):
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(
            jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
        ),
        a, b,
    )
    return max(jax.tree_util.tree_leaves(diffs))


def test_int8_step_trajectory_matches_fp32_adam(mesh_1d):
    """K-step Adam LOSS trajectory within the test_zero1 bar, and the
    compiled step really moves s8 bytes."""
    _, losses_fp32, dt_fp32 = _run(mesh_1d, wire=False)
    _, losses_int8, dt_int8 = _run(mesh_1d, wire=True)

    for lf, li in zip(losses_fp32, losses_int8):
        assert abs(lf - li) < 1e-3, (losses_fp32, losses_int8)
    # the losses must DIFFER somewhere: identical trajectories would mean
    # the compressed path silently fell back to fp32
    assert losses_fp32 != losses_int8

    s8 = sum(rec.get("s8", 0) for rec in dt_int8.values())
    assert s8 > 0, dt_int8
    assert sum(rec.get("s8", 0) for rec in dt_fp32.values()) == 0
    # the quantized RS decomposes to all-to-all; the fp32 step keeps the
    # literal reduce-scatter
    assert "all-to-all" in dt_int8 and "reduce-scatter" not in dt_int8
    assert "reduce-scatter" in dt_fp32


def test_int8_step_param_parity_sgd(mesh_1d):
    """SGD has no sign-sensitive moment accumulation, so PARAMS stay
    within the ZeRO-1 equivalence bar under quantized gradients."""
    s_fp32, _, _ = _run(mesh_1d, wire=False, opt="sgd")
    s_int8, _, _ = _run(mesh_1d, wire=True, opt="sgd")
    assert _max_diff(s_fp32.params, s_int8.params) < 5e-4


def test_checkpoint_resume_across_compress_flip(mesh_1d, tmp_path):
    """A checkpoint written by a wire-compressed run restores into an
    fp32-wire step (and back): compression changes bytes on the WIRE,
    never the checkpointed state contract."""
    path = str(tmp_path / "ckpt")
    model, task = _tiny_model(), CausalLMTask()
    optimizer = optax.adam(1e-3)

    def build(compress):
        cfg = WireConfig(compress=compress, min_size=1)
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
        return part, batch, state, shardings, step

    _, batch, state, _, step = build("int8-block")
    with mesh_1d:
        for _ in range(2):
            state, _ = step(state, batch)
    ckpt_lib.save_checkpoint(path, state, 1, 0.0, {})

    _, batch_f, template_f, shardings_f, step_f = build("none")
    loaded, epoch, _ = ckpt_lib.load_checkpoint(
        path, template_f, shardings_f
    )
    assert epoch == 1
    assert _max_diff(loaded.params, state.params) == 0.0
    assert _max_diff(loaded.opt_state[0].mu, state.opt_state[0].mu) == 0.0
    with mesh_1d:
        stepped, _ = step_f(loaded, batch_f)

    ckpt_lib.save_checkpoint(path, stepped, 2, 0.0, {})
    _, batch_q, template_q, shardings_q, step_q = build("int8-block")
    loaded_q, epoch_q, _ = ckpt_lib.load_checkpoint(
        path, template_q, shardings_q
    )
    assert epoch_q == 2
    assert _max_diff(loaded_q.params, stepped.params) == 0.0
    with mesh_1d:
        step_q(loaded_q, batch_q)


# ---------------------------------------------------------------------------
# analytic wire accounting (what telemetry_summary["wire"] and the budget
# signature read)
# ---------------------------------------------------------------------------


def test_grad_wire_report_ratio_and_bytes(mesh_1d):
    part = data_parallel(
        mesh_1d, dp_shard_opt_state=True, opt_shard_min_size=1,
        wire=WireConfig(compress="int8-block", min_size=1),
    )
    params = {
        "w": jnp.zeros((64, 64), jnp.float32),
        "b": jnp.zeros((64,), jnp.float32),
    }
    report = grad_wire_report(params, part)
    assert report["compress"] == "int8-block"
    assert report["dp_degree"] == 8
    # every leaf compresses (min_size=1): the ratio approaches
    # 4 / (1 + 2/block) regardless of the RS-vs-AR pass mix
    assert report["wire_compression_ratio"] >= 3.0
    assert (
        report["grad_wire_bytes_per_step"]
        < report["grad_wire_bytes_per_step_fp32"]
    )

    # uncompressed config: identical byte model on both sides, ratio 1
    flat = grad_wire_report(params, part, WireConfig())
    assert flat["wire_compression_ratio"] == 1.0
    assert (
        flat["grad_wire_bytes_per_step"]
        == flat["grad_wire_bytes_per_step_fp32"]
    )
    # ring accounting, fp32: scatterable leaves pay (D-1)/D * n * 4 once
    # (RS), unscatterable twice (AR = RS + AG)
    dims = part.zero1_dims(params)
    expect = 0.0
    for dim, leaf in zip(
        jax.tree_util.tree_leaves(dims, is_leaf=lambda d: d is None),
        jax.tree_util.tree_leaves(params),
    ):
        passes = 1.0 if dim is not None else 2.0
        expect += passes * (7 / 8) * leaf.size * 4.0
    assert flat["grad_wire_bytes_per_step_fp32"] == int(round(expect))


def test_min_size_floor_keeps_small_leaves_fp32(mesh_1d):
    part = data_parallel(
        mesh_1d, dp_shard_opt_state=True, opt_shard_min_size=1,
        wire=WireConfig(compress="int8-block", min_size=1 << 20),
    )
    params = {"w": jnp.zeros((64, 64), jnp.float32)}
    report = grad_wire_report(params, part)
    # everything under the floor: compressed bytes == fp32 bytes
    assert report["wire_compression_ratio"] == 1.0
