"""The readers of the program's own spans, on a small trace and a small
record made by hand: each finds what was planted, and reads None where the
program opens no spans (an older commit)."""

import pytest

import reduce as reducer
import tiny
from layer_metrics import (
    chunked_ce_device_share,
    fence_idle_share,
    fit_fixed_cost_s,
    host_work_share,
    optimizer_device_share,
    program_spans,
    scope_ops,
    setup_compile_s,
    setup_trace_lower_s,
)

MS = 1_000_000


def planted_trace(with_spans=True):
    """Two whole steps of 100 ms on one chip. Step 1: the device is busy
    all through. Step 2: busy 0-60 ms, idle 60-70 ms while the host sits in
    a ``log_fetch`` (inside JAX's own ``np.asarray``), busy 70-97, idle
    97-100 ms with no program span open. The host works 12 ms a step
    (aot_lookup 1, step 6, metrics_add 5) and waits in data_load between."""
    ops, modules, host = [], [], []
    for k, base in enumerate((0, 100 * MS, 200 * MS)):
        modules.append(["jit_train_step(1)", base, 97 * MS])
    ops += [["fusion.1 = f32[8] fusion", 0, 100 * MS]]
    ops += [["fusion.1 = f32[8] fusion", 100 * MS, 60 * MS],
            ["fusion.2 = f32[8] fusion", 170 * MS, 27 * MS],
            ["fusion.1 = f32[8] fusion", 200 * MS, 50 * MS]]
    host.append(["next_batch", 0, 1])  # the wrapper's mark: this is the thread
    if with_spans:
        for base in (0, 100 * MS):
            host += [
                ["train_step", base + 1 * MS, 13 * MS],
                ["aot_lookup", base + 1 * MS, 1 * MS],
                ["step", base + 2 * MS, 6 * MS],
                ["metrics_add", base + 8 * MS, 5 * MS],
                ["PjitFunction(add)", base + 9 * MS, 1 * MS],  # JAX's own
                ["data_load", base + 14 * MS, 80 * MS],
            ]
        host += [
            ["train_step", 155 * MS, 20 * MS],
            ["log_fetch", 158 * MS, 14 * MS],
            ["np.asarray(jax.Array)", 159 * MS, 12 * MS],
        ]
        # the second step's data_load was cut short by the third train_step
        host[-4] = ["data_load", 114 * MS, 40 * MS]
    return reducer.Trace(
        [{"name": "/device:TPU:0", "ops": ops, "modules": modules}], host
    )


def run_of(trace):
    return {"trace": trace, "cell": {"name": "planted"}}


def test_fence_idle_share_reads_the_gap_under_a_planted_log_fetch():
    run = run_of(planted_trace())
    # 10 ms of the 200 ms window are idle under log_fetch; the 3 ms at
    # 197-200 have no program span open and are not a fence's
    assert fence_idle_share.read(run) == pytest.approx(100 * 10 / 200)
    (note,) = run["notes"]
    assert "log_fetch 0.010000" in note
    assert note.rstrip().endswith("no_program_span 0.003000")
    # the device metric it is a part of reads all 13 ms
    assert run["trace"].idle_share_worst() == pytest.approx(13 / 200)


def test_host_work_share_leaves_the_waits_out():
    run = run_of(planted_trace())
    # train_step 13 + 13 + 20 ms, less the 14 ms log_fetch; data_load is a
    # wait and counts for nothing
    assert host_work_share.read(run) == pytest.approx(100 * (46 - 14) / 200)
    (note,) = run["notes"]
    assert "metrics_add 5.000" in note and "step 6.000" in note


def test_span_readers_read_none_without_the_programs_spans(monkeypatch):
    run = run_of(planted_trace(with_spans=False))
    assert fence_idle_share.read(run) is None
    assert host_work_share.read(run) is None
    monkeypatch.setattr(program_spans, "record", lambda: [])
    assert fit_fixed_cost_s.read(run) is None
    assert setup_trace_lower_s.read(run) is None
    assert setup_compile_s.read(run) is None
    assert "notes" not in run


def test_innermost_segments_of_nested_spans():
    spans = [["a", 0, 100], ["b", 10, 30], ["c", 20, 5], ["d", 60, 10],
             ["e", 200, 10]]
    assert program_spans.innermost(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 25, "c"), (25, 40, "b"),
        (40, 60, "a"), (60, 70, "d"), (70, 100, "a"), (200, 210, "e"),
    ]
    segments = program_spans.innermost(spans)
    starts = [s[0] for s in segments]
    assert program_spans.name_at(segments, starts, 22) == "c"
    assert program_spans.name_at(segments, starts, 150) == "no_program_span"


# ---------------------------------------------------------------------------
# the in-memory record
# ---------------------------------------------------------------------------


def planted_record():
    """A set-up ``fit`` (with its compiles) and the window's own: 0.5 s of
    ``fit_open``, a first ``data_load`` of 0.1 s, a ``record_compile`` of
    0.05 s inside the first step, 10 steps of 1 s with a 0.2 s wait each,
    an ``epoch_drain`` of 0.3 s and 0.02 s of ``fit_close``."""
    from distributed_pytorch_example_tpu.telemetry.trace import Span

    s = 1_000_000_000
    rows, ids = [], iter(range(1, 10_000))

    def add(name, start, end, parent=0, root=0, thread=1, args=None):
        ident = next(ids)
        rows.append(Span(name, int(start), int(end), thread, ident, parent,
                         root or ident, None, args))
        return ident

    def program(name, start, trace_s, lower_s, compile_s, hit):
        add("compile:" + name, start, start + (trace_s + lower_s + compile_s) * s,
            args={"trace_s": trace_s, "lower_s": lower_s,
                  "compile_s": compile_s, "cache_hit": hit})

    add("main_runtime", 0, 2 * s)
    add("main_data", 2 * s, 2.5 * s)
    add("init_state", 3 * s, 5 * s)
    program("init_fn", 3 * s, 0.5, 0.25, 1.0, True)
    program("train_step", 6 * s, 4.0, 2.0, 3.0, True)
    program("add", 9.5 * s, 0.001, 0.002, 0.004, None)
    first = add("fit", 5 * s, 20 * s)
    add("train_step", 6 * s, 19 * s, parent=first, root=first)

    t0 = 100 * s
    fit = add("fit", t0, t0 + 12.92 * s)
    add("fit_open", t0, t0 + 0.5 * s, fit, fit)
    epoch = add("train_epoch", t0 + 0.5 * s, t0 + 12.9 * s, fit, fit)
    add("data_load", t0 + 0.5 * s, t0 + 0.6 * s, epoch, fit)
    t = t0 + 0.6 * s
    for k in range(10):
        step = add("train_step", t, t + 1 * s, epoch, fit)
        if k == 0:
            look = add("aot_lookup", t, t + 0.06 * s, step, fit)
            add("record_compile", t + 0.01 * s, t + 0.06 * s, look, fit)
        add("data_load", t + 1 * s, t + 1.2 * s, epoch, fit)
        add("h2d", t, t + 0.5 * s, 0, fit, thread=2)  # the prefetch thread
        t += 1.2 * s
    add("epoch_drain", t, t + 0.3 * s, epoch, fit)
    add("fit_close", t0 + 12.9 * s, t0 + 12.92 * s, fit, fit)
    program("reference_step", 200 * s, 9.0, 9.0, 9.0, False)  # after the window
    return rows


def test_fit_fixed_cost_reads_the_planted_fit_open(monkeypatch):
    monkeypatch.setattr(program_spans, "record", planted_record)
    run = {}
    # fit_open 0.5 + fit_close 0.02 beside the loop, + the first data_load
    # 0.1 + record_compile 0.05 taken back out of it
    assert fit_fixed_cost_s.read(run) == pytest.approx(0.67)
    (note,) = run["notes"]
    # what it left out and what it kept add up to the fit span
    assert "fit 12.920000 s = 12.400000 s in train_step" in note
    assert "fit_open 0.500000 s" in note and "epoch_drain 0.300000 s" in note


def test_setup_metrics_read_the_programs_before_the_window(monkeypatch):
    monkeypatch.setattr(program_spans, "record", planted_record)
    run = {}
    assert setup_trace_lower_s.read(run) == pytest.approx(6.753)
    assert setup_compile_s.read(run) == pytest.approx(4.004)
    lower, compile_ = run["notes"]
    assert lower.startswith("setup_trace_lower_s: 3 programs, tracing 4.501 s")
    assert "train_step 6.000, init_fn 0.750" in lower
    assert "3 programs, 2 from the persistent cache, 0 compiled, 1 not asked" in compile_
    assert "main_runtime 2.000 s" in compile_ and "init_state 2.000 s" in compile_
    assert "reference_step" not in lower + compile_


def test_record_readers_on_a_real_tiny_run(monkeypatch):
    """The record a real run leaves (float32 on the CPU, the tiny GPT-2):
    the three readers find the window's ``fit`` and the compile log, and
    the parts add up to the ``fit`` span."""
    import time

    import jax

    import harness
    import run as run_py
    from distributed_pytorch_example_tpu.telemetry import trace

    tiny.shrink_models(monkeypatch)
    trace.clear()
    result, _ = run_py.run_cell(
        tiny.args(seed=77),
        (tiny.cell("gpt2-124m.train-s1024"), tiny.GPT2, tiny.traffic(),
         tiny.bench()),
        jax.devices()[:1], tiny.PEAK, harness.Clock(time.time()),
    )
    assert result["correct"] is True
    run = {}
    cost = fit_fixed_cost_s.read(run)
    lower = setup_trace_lower_s.read(run)
    compiled = setup_compile_s.read(run)
    assert 0 < cost < 5 and lower > 0 and compiled > 0
    fit, tree = program_spans.window_fit(trace.recorded())
    steps = [r for r in tree if r.name == "train_step"]
    assert len(steps) == result["attempted"]
    assert any("compile:train_step" == r.name for r in trace.recorded())
    assert "train_step" in run["notes"][1]
    trace.clear()


# ---------------------------------------------------------------------------
# the scopes inside the compiled step
# ---------------------------------------------------------------------------


def test_scope_names_come_from_the_traces_own_hlo_proto(tmp_path):
    """A profiler trace keeps each executed program's compiled module in its
    ``/host:metadata`` plane (here a CPU trace: the plane is the same); the
    hand-written walk of the wire format finds every instruction's
    ``op_name``, and with it the program's scopes."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("chunked_ce"):
            y = jnp.tanh(x) @ x
        with jax.named_scope("optimizer"):
            return y * 2 + 1

    x = jnp.ones((64, 64))
    scoped_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    scoped_program(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    with open(path, "rb") as f:
        protos = scope_ops.program_protos(f.read())
    (name,) = [n for n in protos if n.startswith("jit_scoped_program(")]
    rows = [
        row for rows in scope_ops.instructions(protos[name]).values()
        for row in rows
    ]
    assert any(op_name.endswith("/chunked_ce/dot_general")
               for _, _, op_name, _ in rows)
    scopes = scope_ops.op_scopes(protos[name])
    assert set(scopes.values()) >= {"chunked_ce", "optimizer"}
    # a fusion goes to the scope most of its fused instructions carry: the
    # one that holds ``y * 2 + 1`` is the optimizer's, whatever its root
    fusions = {n for n, opcode, _, called in rows if opcode == "fusion" and called}
    assert fusions and any(scopes[n] == "optimizer" for n in fusions)
    # no device plane in a CPU trace: nothing to read, and no error
    assert scope_ops.self_times_by_scope(path, name, "/device:TPU:0", 0, 1) is None
    assert scope_ops.self_times_by_scope(path, "jit_absent(1)", "/device:TPU:0", 0, 1) is None


def test_wire_format_walk():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed32; field 4 fixed64
    message = (b"\x08\xac\x02" + b"\x12\x02ab" + b"\x1d\x01\x00\x00\x00"
               + b"\x21\x02" + b"\x00" * 7)
    assert [(n, bytes(v) if not isinstance(v, int) else v)
            for n, v in scope_ops.fields(message)] == [
        (1, 300), (2, b"ab"), (3, 1), (4, 2),
    ]
    assert scope_ops.first(message, 9) is None
    assert scope_ops.scope_of("jit(f)/jvp(not_chunked_ce)/exp") == "-"
    assert scope_ops.scope_of("jit(f)/transpose(jvp(chunked_ce))/dot") == "chunked_ce"


def test_scope_shares_take_self_time_under_the_scope():
    """Adam sits inside the bad-step ``conditional``: the parent's own time
    is not the optimizer's, its children's is."""
    events = [
        (0, 50 * MS, "jit(train_step)/cond"),
        (5 * MS, 30 * MS, "jit(train_step)/cond/branch_1_fun/optimizer/mul"),
        (50 * MS, 20 * MS,
         "jit(train_step)/transpose(jvp(chunked_ce))/dot_general"),
        (70 * MS, 10 * MS, "jit(train_step)/jvp(chunked_ce)/exp"),
        (80 * MS, 20 * MS, "jit(train_step)/jvp(not_chunked_ce)/exp"),
    ]
    totals = reducer.self_times([
        (scope_ops.scope_of(op_name), start, dur)
        for start, dur, op_name in events
    ])
    run = run_of(planted_trace())
    run["scope_ops"] = totals
    assert optimizer_device_share.read(run) == pytest.approx(100 * 30 / 200)
    assert chunked_ce_device_share.read(run) == pytest.approx(100 * 30 / 200)
    assert "under scope 'optimizer'" in run["notes"][0]
    # a trace whose program names no scope: nothing to read
    run = run_of(planted_trace())
    run["scope_ops"] = None
    assert optimizer_device_share.read(run) is None
    assert "notes" not in run
