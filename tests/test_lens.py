"""graft-lens: unified train+serve request tracing, rolling latency
books, and serve-side self-arming sentinels.

The load-bearing contracts pinned here:

- the trace file is valid Chrome trace JSON through counters, instants,
  per-replica pid lanes, re-close, and abnormal teardown (``__del__``);
- a 2-replica fleet run lands router AND engine request spans across
  distinct replica pids in ONE trace file;
- ``ServeSentinels`` detectors fire at most once until ``disarm`` and
  drive the real ``StepProfiler.arm`` first-trigger-wins window;
- tracing adds a fixed count of events to a warmed workload and changes
  no result (the <= 5% wall-time bound is a claim for the chip).
"""

import gc
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.runtime.profiler import StepProfiler
from distributed_pytorch_example_tpu.serving import (
    FleetRouter,
    InferenceEngine,
    ReplicaHandle,
    Request,
)
from distributed_pytorch_example_tpu.telemetry import (
    LatencyBook,
    PrefixedTrace,
    RollingStats,
    SERVE_TRIGGER_KINDS,
    ServeSentinels,
    TraceWriter,
)

# same tiny GPT-2 as test_fleet.py: one jit cache serves both modules
GPT2_KW = dict(vocab_size=61, max_len=32, model_dim=16, num_layers=1,
               num_heads=2, mlp_dim=32)
PAGED = dict(paged_num_blocks=16, paged_block_size=4, paged_max_blocks=4)

_CACHE = {}


def _model():
    if "gpt2" not in _CACHE:
        from distributed_pytorch_example_tpu.models.gpt2 import GPT2

        params = GPT2(**GPT2_KW).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        _CACHE["gpt2"] = (GPT2(**GPT2_KW, decode=True, **PAGED), params)
    return _CACHE["gpt2"]


def _engine(**kw):
    model, params = _model()
    return InferenceEngine(
        model, params, num_slots=3, temperature=0.0, **kw
    )


def _requests(n=6, max_new=8, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=f"q{i:02d}",
            prompt=[int(t) for t in rng.integers(0, 61, 4 + i % 5)],
            max_new_tokens=max_new,
            seed=1000 + i,
        )
        for i in range(n)
    ]


@pytest.fixture(scope="module", autouse=True)
def _warm_programs():
    """Compile once so fleet heartbeats and overhead timing are steady."""
    _engine().warmup()


# ---------------------------------------------------------------------------
# rolling stats / latency book
# ---------------------------------------------------------------------------


def test_rolling_stats_window_and_percentiles():
    s = RollingStats(window=4)
    assert s.percentile(99) is None
    assert s.snapshot() == {"count": 0, "p50": None, "p99": None,
                            "max": None}
    s.extend([1.0, 2.0, 3.0, 4.0, 100.0])  # 1.0 evicted by the window
    snap = s.snapshot()
    assert snap["count"] == 5  # all-time count survives eviction
    assert snap["max"] == 100.0
    assert snap["p50"] == pytest.approx(3.5)
    assert len(s) == 4
    with pytest.raises(ValueError):
        RollingStats(window=0)


def test_latency_book_metrics_and_snapshot(tmp_path):
    book = LatencyBook(window=8)
    assert set(book.snapshot()) == set(LatencyBook.METRICS)
    book.extend("ttft_ms", [5.0, 10.0])
    book.add("kv_occupancy", 0.5)
    assert book.p99("ttft_ms") == pytest.approx(9.95)
    assert book.p99("tpot_ms") is None
    path = tmp_path / "sub" / "snap.json"
    payload = book.write_snapshot(str(path), extra={"tag": "t"})
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(payload))
    assert on_disk["tag"] == "t"
    assert on_disk["metrics"]["ttft_ms"]["count"] == 2


# ---------------------------------------------------------------------------
# trace writer: counters, instants, pid lanes, abnormal teardown
# ---------------------------------------------------------------------------


def test_trace_counter_and_instant_events(tmp_path):
    path = tmp_path / "trace.json"
    w = TraceWriter(str(path))
    w.counter("queue_depth", 3, ts_us=100)
    w.counter("kv", {"free_blocks": 7, "rows": 2}, ts_us=200)
    w.instant("trigger:kv-pressure", ts_us=300, kv_used_frac=0.97)
    w.close()
    events = json.loads(path.read_text())
    c = [e for e in events if e["ph"] == "C"]
    assert [e["args"] for e in c] == [
        {"value": 3}, {"free_blocks": 7, "rows": 2},
    ]
    (i,) = [e for e in events if e["ph"] == "i"]
    assert i["name"] == "trigger:kv-pressure"
    assert i["s"] == "p"  # process-scoped instant
    assert i["args"] == {"kv_used_frac": 0.97}


def test_trace_valid_json_after_del_without_close(tmp_path):
    import atexit

    path = tmp_path / "trace.json"
    w = TraceWriter(str(path))
    w.add_complete("step", 0, 10)
    w.counter("depth", 1)
    # the atexit hook pins the writer alive; drop it so plain GC
    # teardown exercises the __del__ -> close finalizer path
    atexit.unregister(w.close)
    del w
    gc.collect()
    events = json.loads(path.read_text())
    assert {e["name"] for e in events} >= {"step", "depth"}


def test_trace_reclose_and_post_close_drop(tmp_path):
    path = tmp_path / "trace.json"
    w = TraceWriter(str(path))
    w.add_complete("kept", 0, 5)
    w.close()
    w.close()  # atexit re-close tolerated
    w.add_complete("dropped", 0, 5)
    w.counter("dropped_c", 1)
    w.instant("dropped_i")
    names = {e["name"] for e in json.loads(path.read_text())}
    assert "kept" in names
    assert not names & {"dropped", "dropped_c", "dropped_i"}


def test_prefixed_trace_pid_lanes(tmp_path):
    path = tmp_path / "trace.json"
    base = TraceWriter(str(path))
    r0 = PrefixedTrace(base, "r0", pid=1)
    r1 = PrefixedTrace(base, "r1", pid=2, process_name="replica-one")
    r0.add_complete("decode_step", 0, 10)
    with r1.span("prefill:q"):
        pass
    r1.counter("kv", {"free_blocks": 5})
    base.close()
    events = json.loads(path.read_text())
    lanes = {
        e["args"]["name"]: e["pid"] for e in events
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert lanes["r0"] == 1 and lanes["replica-one"] == 2
    by_name = {e["name"]: e for e in events if e["ph"] != "M"}
    assert by_name["r0/decode_step"]["pid"] == 1
    assert by_name["r1/prefill:q"]["pid"] == 2
    assert by_name["r1/kv"]["pid"] == 2


# ---------------------------------------------------------------------------
# serve sentinels: fire-once, disarm, profiler arm pipeline, degrade
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def arm(self, start, stop, reason=""):
        self.calls.append((start, stop, reason))
        return True


class _FakeTrace:
    def __init__(self):
        self.instants = []

    def instant(self, name, **args):
        self.instants.append((name, args))


def test_serve_sentinels_window_validation():
    with pytest.raises(ValueError):
        ServeSentinels(recent_window=1)
    with pytest.raises(ValueError):
        ServeSentinels(baseline_window=4, recent_window=8)


def test_tpot_regression_fires_once_then_disarm_rearms():
    prof, tr = _FakeProfiler(), _FakeTrace()
    s = ServeSentinels(
        profiler=prof, trace=tr, baseline_window=8, recent_window=4,
        regression_factor=2.0, arm_offset=1, arm_span=2,
    )
    for _ in range(8):
        s.observe_tpot(1.0)
    assert s.check(10) == []  # healthy baseline: nothing fires
    for _ in range(4):
        s.observe_tpot(10.0)  # 10x the baseline median
    (trig,) = s.check(20)
    assert trig["kind"] == "tpot-regression"
    assert trig["ratio"] > 2.0
    assert prof.calls == [(21, 23, "serve tpot-regression")]
    assert tr.instants[0][0] == "trigger:tpot-regression"
    # fire-once until disarm: same regression, no new trigger
    assert s.check(21) == []
    s.disarm("tpot-regression")
    (again,) = s.check(22)
    assert again["kind"] == "tpot-regression"
    assert len(s.triggers) == 2  # history survives disarm


def test_straggler_detector_absolute_and_outlier():
    s = ServeSentinels(straggler_age_s=1.0)
    # multi-replica: absolute bound alone is not enough (everyone slow)
    assert s.check(0, heartbeat_ages={"r0": 1.2, "r1": 1.1}) == []
    # the median includes the straggler itself, so a 3x outlier needs
    # healthy company: r2 at 4.0s vs a 0.12s median is one
    (trig,) = s.check(
        1, heartbeat_ages={"r0": 0.1, "r1": 0.12, "r2": 4.0}
    )
    assert trig["kind"] == "straggler-replica"
    assert trig["replica"] == "r2"
    # single-replica fleet: absolute bound alone fires
    s2 = ServeSentinels(straggler_age_s=1.0)
    (t2,) = s2.check(0, heartbeat_ages={"r0": 1.5})
    assert t2["replica"] == "r0"


def test_kv_pressure_threshold_and_notice_lost_replica():
    tr = _FakeTrace()
    s = ServeSentinels(trace=tr, pressure_frac=0.9)
    assert s.check(0, kv_used_frac=0.85) == []
    (trig,) = s.check(1, kv_used_frac=0.93)
    assert trig["kind"] == "kv-pressure"
    # a router-declared loss is the terminal straggler, fire-once too
    assert s.notice_lost_replica("r1", 0.02, step=5)["lost"] is True
    assert s.notice_lost_replica("r1", 0.02, step=6) is None
    assert [t["kind"] for t in s.triggers] == [
        "kv-pressure", "straggler-replica",
    ]
    assert {n for n, _ in tr.instants} == {
        "trigger:kv-pressure", "trigger:straggler-replica",
    }
    assert set(SERVE_TRIGGER_KINDS) >= {t["kind"] for t in s.triggers}


def test_sentinels_degrade_without_profiler_or_trace():
    s = ServeSentinels()  # neither profiler nor trace: pure statistics
    (trig,) = s.check(0, kv_used_frac=1.0)
    assert trig["kind"] == "kv-pressure"
    assert s.summary() == {"triggers": [trig]}


def test_serve_trigger_arms_real_profiler_first_trigger_wins(tmp_path):
    prof = StepProfiler(str(tmp_path), window=(10, 13))
    # drive past the configured window WITHOUT opening it (window check
    # is start <= step < stop), so arm() sees a passed window
    prof.step(20)
    s = ServeSentinels(profiler=prof, arm_offset=1, arm_span=2)
    s.check(30, kv_used_frac=1.0)
    assert (prof.start_step, prof.stop_step) == (31, 33)
    # second trigger while the armed window is pending: arm refused,
    # first trigger wins (StepProfiler contract)
    s.check(32, heartbeat_ages={"r0": 99.0})
    assert (prof.start_step, prof.stop_step) == (31, 33)
    assert len(s.triggers) == 2  # the detection still recorded


# ---------------------------------------------------------------------------
# fleet request tracing end to end (tentpole): one trace, many pids
# ---------------------------------------------------------------------------


def test_fleet_trace_request_spans_across_replica_pids(tmp_path):
    path = tmp_path / "fleet_trace.json"
    base = TraceWriter(str(path))
    handles = [
        ReplicaHandle(
            f"r{i}",
            _engine(trace=PrefixedTrace(base, f"r{i}", pid=i + 1)),
        )
        for i in range(2)
    ]
    sentinels = ServeSentinels(trace=base, pressure_frac=0.01)
    router = FleetRouter(
        handles, trace=base, sentinels=sentinels,
        sentinel_interval_s=0.0,
    )
    # 8 requests > 6 fleet slots: some requests must queue, so the
    # queue-wait histogram gets nonzero samples
    report = router.run(_requests(n=8))
    base.close()
    assert all(
        r["status"] == "done" for r in report["results"].values()
    )

    events = json.loads(path.read_text())
    x_pids = {e["pid"] for e in events if e["ph"] == "X"}
    assert {1, 2} <= x_pids  # request spans on BOTH replica pid lanes
    names_by_pid = {}
    for e in events:
        if e["ph"] == "X":
            names_by_pid.setdefault(e["pid"], set()).add(e["name"])
    # router spans ride the host pid lane (0)
    assert any(n.startswith("router/queue:") for n in names_by_pid[0])
    # engine phase spans ride each replica's own lane
    for pid, prefix in ((1, "r0"), (2, "r1")):
        assert any(
            n.startswith(f"{prefix}/prefill:") or n == f"{prefix}/decode_step"
            for n in names_by_pid[pid]
        ), names_by_pid[pid]
    # counter tracks: router queue depth + per-replica kv pool
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert "router/queue_depth" in counters
    assert counters & {"r0/kv", "r1/kv"}
    # the low-pressure sentinel fired and stamped the timeline
    assert any(
        e["ph"] == "i" and e["name"] == "trigger:kv-pressure"
        for e in events
    )
    m = report["metrics"]
    assert m["ttft_p99_ms"] > 0.0
    assert m["queue_wait_p99_ms"] > 0.0
    assert m["kv_occupancy_max"] > 0.0
    assert [t["kind"] for t in m["sentinel_triggers"]] == ["kv-pressure"]
    assert m["latency"]["tpot_ms"]["count"] >= 0  # snapshot shape


# ---------------------------------------------------------------------------
# overhead: what tracing adds to the serving path is a COUNT of events
# ---------------------------------------------------------------------------


def test_serve_tracing_adds_a_fixed_event_count(tmp_path):
    """What a ``TraceWriter`` adds to a warmed workload is a number of
    events fixed by the requests, their prefills and the decode steps, and
    nothing in the results. (The graft-lens bound itself, <= 5% wall time
    with tracing on, is a claim for the chip: PERF.md section 7. A CPU run
    gives no time.)"""
    reqs = _requests(n=4, max_new=6)
    plain = _engine().run(reqs)

    path = tmp_path / "trace.json"
    writer = TraceWriter(str(path))
    traced = _engine(trace=writer).run(reqs)
    writer.close()
    assert traced["results"].keys() == plain["results"].keys()
    for rid, want in plain["results"].items():
        got = traced["results"][rid]
        assert got["status"] == want["status"] == "done"
        assert got["tokens"] == want["tokens"]
        assert got["preemptions"] == want["preemptions"]
    steps = traced["metrics"]["decode_steps"]
    assert steps == plain["metrics"]["decode_steps"] > 0

    with open(path) as f:
        events = [e for e in json.load(f) if e["ph"] != "M"]
    # run() writes spans only: the counters and instants are the fleet's
    assert {e["ph"] for e in events} == {"X"}
    names = [e["name"] for e in events]
    rids = [r.rid for r in reqs]
    # a request is prefilled once and once more after every preemption
    prefills = sum(1 + r["preemptions"] for r in traced["results"].values())
    assert sum(n.startswith("prefill:") for n in names) == prefills
    assert names.count("decode_step") == steps
    for kind in ("queue", "decode", "finalize"):
        assert sorted(n for n in names if n.startswith(kind + ":")) == [
            f"{kind}:{rid}" for rid in rids
        ]
    assert len(events) == prefills + steps + 3 * len(reqs)


# ---------------------------------------------------------------------------
# driver contract (slow): ONE JSON line carries the lens metrics
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli_env():
    env = dict(os.environ)
    env.pop("DPX_CHAOS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    return env


def _one_json_line(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE JSON line on stdout, got {lines!r}"
    return json.loads(lines[0])


@pytest.mark.slow
def test_serve_cli_line_and_metrics_snapshot(tmp_path):
    trace = tmp_path / "trace.json"
    snap = tmp_path / "snap.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "serve.py"),
         "--requests", "4", "--slots", "2",
         "--vocab-size", "61", "--max-len", "32", "--model-dim", "16",
         "--num-layers", "1", "--num-heads", "2",
         "--num-blocks", "16", "--block-size", "4", "--max-blocks", "4",
         "--prompt-len", "4:8", "--max-new", "4:8",
         "--trace", str(trace), "--metrics-snapshot", str(snap)],
        capture_output=True, text=True, env=_cli_env(), timeout=600,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = _one_json_line(proc.stdout)
    assert doc["ttft_p99_ms"] > 0.0
    assert doc["queue_wait_p99_ms"] >= 0.0
    # sidecar artifacts: a Perfetto-valid trace + the histogram snapshot
    events = json.loads(trace.read_text())
    assert any(e["ph"] == "X" for e in events)
    payload = json.loads(snap.read_text())
    assert set(payload) == {"metrics", "config"}
    assert payload["metrics"]["ttft_ms"]["p99"] > 0.0
