"""The readers that the ``lfm2-8b-a1b`` cell brings, on a trace, a record
and scope totals made by hand: each finds what was planted, no share of a
roofline passes 100% for work that was done, and each reads None where the
program has no such scope, kernel or counter (the parent commit)."""

import glob

import pytest

import moe_flops
import reduce as reducer
import tiny
import tiny_lfm2  # noqa: F401
from layer_metrics import (
    gqa_flash_bwd_roofline,
    gqa_flash_fwd_roofline,
    moe_counters,
    moe_dispatch_device_share,
    moe_experts_device_share,
    moe_experts_roofline,
    moe_load_max_over_mean,
    moe_rows_used_share,
    named_scopes,
    short_conv_device_share,
)

MS = 1_000_000
CONFIG = {
    "hidden_size": 2048, "moe_intermediate_size": 1792, "num_experts": 8,
    "num_experts_per_tok": 4, "published": {"num_experts": 32},
    "layer_types": ["conv"] * 5, "num_dense_layers": 1,
    "shape": {"heads": 32, "head_dim": 64, "causal": True},
}
TRAFFIC = {"rows_per_chip": 4, "seq_len": 8192}


def planted_run(ops, counters=None, scopes=None):
    """Two whole steps of 100 ms on one chip, with these ops in them."""
    modules = [["jit_train_step(1)", base, 97 * MS] for base in (0, 100 * MS, 200 * MS)]
    trace = reducer.Trace(
        [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
        [["next_batch", 0, 1]],
    )
    return {
        "trace": trace, "cell": {"name": "planted"}, "config": CONFIG,
        "traffic": TRAFFIC, "tokens_per_step": 4 * 8192, "peak": tiny.PEAK,
        "moe_counters": counters or [], "named_scopes": scopes,
    }


@pytest.mark.parametrize(
    "op_name,scope",
    [
        ("jit(train_step)/jvp(Lfm2)/layer_3.<lambda>/layer_3/moe/moe_experts/ragged_dot_general", "moe_experts"),
        ("jit(train_step)/transpose(jvp(Lfm2))/jvp(Lfm2)/checkpoint/rematted_computation/layer_3.<lambda>/layer_3/moe/moe_dispatch/gather", "moe_dispatch"),
        ("jit(train_step)/transpose(jvp(Lfm2))/jvp(Lfm2)/checkpoint/layer_2.<lambda>/layer_2/moe/moe_route/dot_general", "moe_route"),
        ("jit(f)/transpose(jvp(short_conv))/mul", "short_conv"),
        ("jit(f)/jvp(not_short_conv)/mul", named_scopes.OTHER),
        ("jit(train_step)/jvp(chunked_ce)/exp", named_scopes.OTHER),
    ],
)
def test_scope_of_an_op_name(op_name, scope):
    assert named_scopes.scope_of(op_name) == scope


def test_the_four_scopes_are_found_in_a_traces_own_hlo(tmp_path):
    """As ``scope_ops``' test: a CPU trace keeps the compiled module in its
    ``/host:metadata`` plane, and the walk finds the program's scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("moe_route"):
            y = jax.nn.sigmoid(x @ x)
        with jax.named_scope("moe_dispatch"):
            y = jnp.take(y, jnp.argsort(y[:, 0]), axis=0)
        with jax.named_scope("moe_experts"):
            y = jax.nn.silu(y @ x) * y
        with jax.named_scope("short_conv"):
            return y + jnp.pad(y, ((1, 0), (0, 0)))[:-1]

    x = jnp.ones((64, 64))
    scoped_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    scoped_program(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    with open(path, "rb") as f:
        protos = named_scopes.scope_ops.program_protos(f.read())
    (name,) = [n for n in protos if n.startswith("jit_scoped_program(")]
    scopes = named_scopes.op_scopes(protos[name])
    assert set(scopes.values()) >= set(named_scopes.SCOPES)
    # no device plane in a CPU trace, or no such program: nothing, no error
    assert named_scopes.self_times_by_scope(path, name, "/device:TPU:0", 0, 1) is None
    assert named_scopes.self_times_by_scope(path, "jit_absent(1)", "/device:TPU:0", 0, 1) is None


def test_scope_shares_and_the_experts_roofline_read_the_planted_seconds():
    # of 200 ms: experts 40, dispatch 20 + route 4, convolutions 30
    scopes = {"moe_experts": 40.0 * MS, "moe_dispatch": 20.0 * MS,
              "moe_route": 4.0 * MS, "short_conv": 30.0 * MS, "-": 106.0 * MS}
    counters = [
        {"held_share": 0.20, "load_max_over_mean": 3.0, "rows_used_share": 0.40,
         "dropped_assignments": 0.0},
        {"held_share": 0.24, "load_max_over_mean": 4.0, "rows_used_share": 0.48,
         "dropped_assignments": 0.0},
    ]
    run = planted_run([["fusion.1 = f32[8] fusion", 0, 300 * MS]], counters, scopes)
    assert moe_experts_device_share.read(run) == pytest.approx(20.0)
    assert moe_dispatch_device_share.read(run) == pytest.approx(12.0)
    assert short_conv_device_share.read(run) == pytest.approx(15.0)
    assert moe_load_max_over_mean.read(run) == pytest.approx(3.5)
    assert moe_rows_used_share.read(run) == pytest.approx(44.0)
    # the roofline counts the rows the routing sent here (0.22 x 4 x 32,768
    # a layer), not the even share's 32,768
    rows = 0.22 * 4 * 32768
    ops, _ = moe_flops.grouped_products(CONFIG, rows)
    assert ops == pytest.approx(3 * 2 * rows * 3 * 2048 * 1792)
    least = 4 * ops / tiny.PEAK["bf16_flops_per_s"]
    assert moe_experts_roofline.read(run) == pytest.approx(100 * least / 0.020)
    assert "rows a layer as routed (even routing: 32768)" in run["notes"][-1]


def test_the_experts_roofline_cannot_pass_100_for_rows_that_were_multiplied():
    """At the chip's peak rate for the rows routed, whatever their number,
    the share reads 100: the count follows the routing."""
    for held_share in (0.10, 0.25, 0.45):
        rows = held_share * 4 * 32768
        ops, _ = moe_flops.grouped_products(CONFIG, rows)
        at_peak_ns = 4 * ops / tiny.PEAK["bf16_flops_per_s"] * 1e9
        run = planted_run(
            [["fusion.1 = f32[8] fusion", 0, 300 * MS]],
            [{"held_share": held_share}],
            {"moe_experts": 2 * at_peak_ns},  # two steps in the window
        )
        assert moe_experts_roofline.read(run) == pytest.approx(100.0)


def test_the_experts_roofline_reads_0_where_nothing_was_routed_here():
    """Starved experts: the scope still has seconds (passes over empty
    rows) but no product was due; the line holds the metric, at 0."""
    run = planted_run(
        [["fusion.1 = f32[8] fusion", 0, 300 * MS]],
        [{"held_share": 0.0}, {"held_share": 0.0}],
        {"moe_experts": 44.0 * MS},
    )
    assert moe_experts_roofline.read(run) == 0.0
    assert "0 rows a layer as routed" in run["notes"][-1]


def test_gqa_flash_rooflines_match_the_kernels_by_name():
    """Two forward calls (one of them a recomputation: counted as a call),
    a fused backward, and a backward split in two kernels (half a call
    each); the single-tile kernels of the other cells are not these."""
    ops = [["fusion.1 = f32[8] fusion", 0, 100 * MS]]
    t = 100 * MS
    for name, ms in (
        [("flash_fwd.%d = (bf16[4,32,8192,64]) custom-call" % i, 20) for i in range(2)]
        + [("flash_bwd_fused.7 = (bf16[4,32,8192,64]) custom-call", 30),
           ("flash_bwd_dq.2 = bf16[4,32,8192,64] custom-call", 12),
           ("flash_bwd_dkv.2 = (bf16[4,8,8192,64]) custom-call", 18),
           ("flash_fwd_single_causal.3 = (bf16[16,12,1024,64]) custom-call", 1)]
    ):
        ops.append([name, t, ms * MS])
        t += ms * MS
    ops.append(["fusion.1 = f32[8] fusion", 200 * MS, 50 * MS])
    run = planted_run(ops)
    import flops

    fwd, _ = flops.roofline_seconds(*flops.flash_forward(4, 32, 8192, 64, True), tiny.PEAK)
    bwd, _ = flops.roofline_seconds(*flops.flash_backward(4, 32, 8192, 64, True), tiny.PEAK)
    assert gqa_flash_fwd_roofline.read(run) == pytest.approx(100 * 2 * fwd / 0.040)
    assert gqa_flash_bwd_roofline.read(run) == pytest.approx(100 * 2 * bwd / 0.060)
    assert "2 calls" in run["notes"][0] and "2 calls" in run["notes"][1]


def test_every_reader_reads_none_where_the_program_has_nothing_of_it(monkeypatch):
    """The parent commit: no scope in the step, no such kernel, no
    ``moe_counters`` row. None from each, no error, no note."""
    run = planted_run(
        [["fusion.1 = f32[8] fusion", 0, 300 * MS],
         ["flash_fwd_single_causal.3 = (bf16[16,12,1024,64]) custom-call", 110 * MS, MS]],
        counters=[], scopes=None,
    )
    for reader in (
        moe_experts_device_share, moe_dispatch_device_share,
        short_conv_device_share, moe_experts_roofline, gqa_flash_fwd_roofline,
        gqa_flash_bwd_roofline, moe_load_max_over_mean, moe_rows_used_share,
    ):
        assert reader.read(run) is None, reader.__name__
    assert "notes" not in run
    # and straight from a record that holds no ``fit``, so no such row
    del run["moe_counters"]
    monkeypatch.setattr(moe_counters.ps, "record", lambda: [])
    assert moe_counters.window_rows(run) == []
    assert moe_load_max_over_mean.read(run) is None
