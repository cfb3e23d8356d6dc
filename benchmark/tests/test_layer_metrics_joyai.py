"""The readers that the ``joyai-llm-flash`` cell brings, on a trace, a
record and scope totals made by hand: each finds what was planted, ``mtp``
wins over the scopes nested in it, neither flash share passes 100% at the
chip's peak rate, and each reads None where the program has no such scope,
kernel or counter (the parent commit)."""

import glob

import pytest

import mla_flops
import reduce as reducer
import tiny
from layer_metrics import (
    mla_flash_bwd_roofline,
    mla_flash_fwd_roofline,
    mla_proj_device_share,
    moe_counters,
    moe_held_share,
    moe_shared_device_share,
    mtp_device_share,
    nested_scopes,
)

MS = 1_000_000
CONFIG = {
    "num_attention_heads": 32, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "shape": {"heads": 32, "head_dim": 160, "causal": True},
}
TRAFFIC = {"rows_per_chip": 4, "seq_len": 4096}


def planted_run(ops, counters=None, scopes=None, config=CONFIG):
    """Two whole steps of 100 ms on one chip, with these ops in them."""
    modules = [["jit_train_step(1)", base, 97 * MS] for base in (0, 100 * MS, 200 * MS)]
    trace = reducer.Trace(
        [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
        [["next_batch", 0, 1]],
    )
    return {
        "trace": trace, "cell": {"name": "planted"}, "config": config,
        "traffic": TRAFFIC, "tokens_per_step": 4 * 4096, "peak": tiny.PEAK,
        "moe_counters": counters or [], "nested_scopes": scopes,
    }


@pytest.mark.parametrize(
    "op_name,scope",
    [
        ("jit(train_step)/jvp(JoyaiLlmFlash)/layer_1.<lambda>/layer_1/attn/mla_proj/q_b/dot_general", "mla_proj"),
        ("jit(train_step)/transpose(jvp(JoyaiLlmFlash))/checkpoint/rematted_computation/layer_3.<lambda>/layer_3/moe/moe_shared/shared/up/dot_general", "moe_shared"),
        # the prediction module's own layer: mtp wins over what nests in it
        ("jit(train_step)/jvp(JoyaiLlmFlash)/mtp/layer_40.<lambda>/layer_40/attn/mla_proj/o/dot_general", "mtp"),
        ("jit(train_step)/transpose(jvp(JoyaiLlmFlash))/mtp/layer_40.<lambda>/layer_40/moe/moe_shared/shared/mul", "mtp"),
        ("jit(train_step)/jvp(JoyaiLlmFlash)/mtp/mtp_proj/dot_general", "mtp"),
        ("jit(f)/jvp(not_mtp)/mtp_proj/mul", nested_scopes.OTHER),
        ("jit(train_step)/jvp(chunked_ce)/exp", nested_scopes.OTHER),
    ],
)
def test_scope_of_an_op_name(op_name, scope):
    assert nested_scopes.scope_of(op_name) == scope


def test_the_scopes_are_found_in_a_traces_own_hlo_and_timed_from_its_ops(tmp_path):
    """A CPU trace keeps the compiled module in its ``/host:metadata``
    plane; the walk finds the three scopes, the nested one under ``mtp``,
    and the self times come from the ops the run's ``Trace`` holds."""
    import jax
    import jax.numpy as jnp

    def projections(x):
        with jax.named_scope("mla_proj"):
            return jnp.tanh(x @ x)

    @jax.jit
    def scoped_program(x):
        y = projections(x)
        with jax.named_scope("moe_shared"):
            y = jax.nn.silu(y @ x) * y
        with jax.named_scope("mtp"):
            return jnp.sin(projections(y) @ x)

    x = jnp.ones((64, 64))
    scoped_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    scoped_program(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    with open(path, "rb") as f:
        protos = nested_scopes.scope_ops.program_protos(f.read())
    (name,) = [n for n in protos if n.startswith("jit_scoped_program(")]
    scopes = nested_scopes.op_scopes(protos[name])
    assert set(scopes.values()) >= set(nested_scopes.SCOPES)
    # planted events named as the instructions are, inside the window of
    # whole steps: a tenth of a millisecond each
    ops = [
        [f"{instruction} = f32[64,64] fusion", (101 + i * 0.1) * MS, 0.1 * MS]
        for i, instruction in enumerate(sorted(scopes))
    ]
    run = planted_run(ops)
    totals = nested_scopes.self_times_by_scope(run["trace"], protos[name])
    for scope in nested_scopes.SCOPES:
        count = sum(1 for s in scopes.values() if s == scope)
        assert totals[scope] == pytest.approx(count * 0.1 * MS), scope


def test_scope_shares_and_the_held_share_read_what_was_planted():
    # of 200 ms: projections 50, the shared expert 8, the module 30
    scopes = {"mla_proj": 50.0 * MS, "moe_shared": 8.0 * MS, "mtp": 30.0 * MS,
              "-": 112.0 * MS}
    counters = [{"held_share": 0.030}, {"held_share": 0.034}]
    run = planted_run([["fusion.1 = f32[8] fusion", 0, 300 * MS]], counters, scopes)
    assert mla_proj_device_share.read(run) == pytest.approx(25.0)
    assert moe_shared_device_share.read(run) == pytest.approx(4.0)
    assert mtp_device_share.read(run) == pytest.approx(15.0)
    assert moe_held_share.read(run) == pytest.approx(3.2)
    assert "first row 0.03, last row 0.034" in run["notes"][-1]


def test_mla_flash_rooflines_count_192_for_scores_and_128_for_values():
    """Three forward calls (two of them recomputations), two fused
    backwards; at the chip's peak rate each share reads 100, and the count
    is the issue's: scores over 192, values over 128, the rotary key's
    bytes once."""
    pairs = 4 * 32 * 4096 * 2048
    ops_f, bytes_f = mla_flops.flash_forward(4, 32, 4096, 128, 64, 128, True)
    ops_b, bytes_b = mla_flops.flash_backward(4, 32, 4096, 128, 64, 128, True)
    assert ops_f == 2 * pairs * 192 + 2 * pairs * 128
    assert ops_b == 2 * (2 * pairs * 192 + 2 * pairs * 128)
    token = 4 * 4096 * 2
    q, k, v = 32 * 192 * token, (32 * 128 + 64) * token, 32 * 128 * token
    lse = 4 * 32 * 4096 * 4
    assert bytes_f == q + k + 2 * v + lse
    assert bytes_b == 2 * q + 2 * k + 4 * v + lse
    fwd_ns = ops_f / tiny.PEAK["bf16_flops_per_s"] * 1e9  # compute-bound
    bwd_ns = ops_b / tiny.PEAK["bf16_flops_per_s"] * 1e9
    ops, t = [["fusion.1 = f32[8] fusion", 0, 100 * MS]], 100.0 * MS
    for name, ns in (
        [("flash_fwd.%d = (bf16[4,32,4096,128]) custom-call" % i, fwd_ns) for i in range(3)]
        + [("flash_bwd_fused.%d = (bf16[4,32,4096,192]) custom-call" % i, 2 * bwd_ns) for i in range(2)]
        + [("flash_fwd_single_causal.3 = (bf16[16,12,1024,64]) custom-call", MS)]
    ):
        ops.append([name, t, ns])
        t += ns
    ops.append(["fusion.1 = f32[8] fusion", 200 * MS, 50 * MS])
    run = planted_run(ops)
    assert mla_flash_fwd_roofline.read(run) == pytest.approx(100.0)
    assert mla_flash_bwd_roofline.read(run) == pytest.approx(50.0)
    assert "3 calls" in run["notes"][0] and "2 calls" in run["notes"][1]
    assert "compute-bound" in run["notes"][0]


def test_every_reader_reads_none_where_the_program_has_nothing_of_it(monkeypatch):
    """The parent commit: no scope in the step, no such kernel, no
    ``moe_counters`` row; and a configuration without the three widths."""
    run = planted_run(
        [["fusion.1 = f32[8] fusion", 0, 300 * MS],
         ["flash_fwd_single_causal.3 = (bf16[16,12,1024,64]) custom-call", 110 * MS, MS]],
        counters=[], scopes=None,
    )
    readers = (
        mla_flash_fwd_roofline, mla_flash_bwd_roofline, mla_proj_device_share,
        moe_shared_device_share, mtp_device_share, moe_held_share,
    )
    for reader in readers:
        assert reader.read(run) is None, reader.__name__
    assert "notes" not in run
    other = planted_run(
        [["flash_fwd.1 = (bf16[4,32,8192,64]) custom-call", 110 * MS, MS]],
        config={"shape": {"heads": 32, "head_dim": 64, "causal": True}},
    )
    assert mla_flash_fwd_roofline.read(other) is None
    # no trace file under benchmark/.trace/<cell>: nothing, no error
    del run["nested_scopes"]
    assert mtp_device_share.read(run) is None
    del run["moe_counters"]
    monkeypatch.setattr(moe_counters.ps, "record", lambda: [])
    assert moe_held_share.read(run) is None
