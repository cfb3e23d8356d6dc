"""graft-fleet: multi-replica router failover + scheduler drain contracts.

The load-bearing guarantee: a fleet of N engine replicas behind the
router produces tokens bit-identical to a single engine — in steady
state, across session-affine placement, and (the hard case) through a
replica dying mid-decode with its requests replayed elsewhere. Position-
folded per-request rng (serving/sampling.py) is what makes replay exact;
these tests pin that the routing machinery never leaks placement into
the tokens. The scheduler drain tests pin the host-side invariants the
replay path leans on: front-requeue seniority and exact block recycling.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.robustness import chaos
from distributed_pytorch_example_tpu.serving import (
    EngineFetchTimeout,
    FleetRouter,
    InferenceEngine,
    PagedCacheConfig,
    ReplicaHandle,
    Request,
    Scheduler,
)

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


def _engine(temperature=0.0, top_k=None, **kw):
    model, params = _model()
    return InferenceEngine(
        model, params, num_slots=3, temperature=temperature, top_k=top_k,
        **kw,
    )


@pytest.fixture(scope="module", autouse=True)
def _warm_fleet_programs():
    """XLA compile freezes replica heartbeats; warm both sampling regimes
    once so routers with tight deadlines see only steady-state beats."""
    _engine(0.0, None).warmup()
    _engine(0.9, 5).warmup()


def _requests(n=6, max_new=8, sessions=0, seed=7):
    # prompt + max_new must fit max_context (16): prompts <= 8
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=f"q{i:02d}",
            prompt=[int(t) for t in rng.integers(0, 61, 4 + i % 5)],
            max_new_tokens=max_new,
            seed=1000 + i,
            session=f"s{i % sessions}" if sessions else None,
        )
        for i in range(n)
    ]


def _fleet(n=2, temperature=0.0, top_k=None, **router_kw):
    handles = [
        ReplicaHandle(f"r{i}", _engine(temperature, top_k))
        for i in range(n)
    ]
    return FleetRouter(handles, **router_kw), handles


def _single_reference(requests, temperature=0.0, top_k=None):
    report = _engine(temperature, top_k).run(requests)
    assert all(
        r["status"] == "done" for r in report["results"].values()
    )
    return {rid: r["tokens"] for rid, r in report["results"].items()}


# ---------------------------------------------------------------------------
# steady state: fleet output == single engine, placement spreads load
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.9, 5)])
def test_fleet_bit_identical_to_single_engine(temperature, top_k):
    reqs = _requests()
    refs = _single_reference(reqs, temperature, top_k)
    router, _handles = _fleet(2, temperature, top_k)
    report = router.run(reqs)
    for r in reqs:
        got = report["results"][r.rid]
        assert got["status"] == "done"
        assert got["tokens"] == refs[r.rid], r.rid
    m = report["metrics"]
    assert m["completed"] == len(reqs)
    assert m["replicas_lost"] == 0
    # least-loaded placement actually used both replicas
    assert all(
        stats["finished"] >= 1 for stats in m["per_replica"].values()
    )
    assert all(
        stats["state"] == "stopped" for stats in m["per_replica"].values()
    )


def test_session_affinity_sticks_and_spreads():
    reqs = _requests(n=8, sessions=2)
    router, _handles = _fleet(2)
    report = router.run(reqs)
    placed = {}
    for r in reqs:
        res = report["results"][r.rid]
        assert res["status"] == "done"
        placed.setdefault(r.session, set()).add(res["replica"])
    # each session pinned to exactly one replica; sessions on distinct
    # replicas (least-loaded placed s1 away from s0's replica)
    assert all(len(reps) == 1 for reps in placed.values())
    assert len(set.union(*placed.values())) == 2


# ---------------------------------------------------------------------------
# failover: kill / stall / flaky channel
# ---------------------------------------------------------------------------


def _install(*faults):
    chaos.install(chaos.ChaosPlan(faults=list(faults)))


@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.9, 5)])
def test_kill_replica_midstream_replays_token_exact(temperature, top_k):
    reqs = _requests(n=8)
    refs = _single_reference(reqs, temperature, top_k)
    router, handles = _fleet(2, temperature, top_k,
                             heartbeat_timeout_s=2.0)
    _install(chaos.Fault("kill-replica", at="r1", step=3))
    try:
        report = router.run(reqs)
    finally:
        chaos.uninstall()
    m = report["metrics"]
    assert m["replicas_lost"] == 1
    assert m["redispatched"] >= 1
    assert m["replayed"] >= 1
    assert m["replay_token_exact"] is True
    # a dead worker thread is caught immediately, far inside the deadline
    assert m["detection_latency_s"] < 2.0
    assert handles[1].state() == "dead"
    assert "kill" in handles[1].error()
    for r in reqs:
        got = report["results"][r.rid]
        assert got["status"] == "done"
        assert got["tokens"] == refs[r.rid], r.rid


def test_kill_replica_with_speculation_replays_token_exact():
    """Journal replay stays bit-identical with speculative decoding ON:
    the accept/reject sequence is a pure function of params + prompt +
    position-folded rng, so a replica loss mid-window replays to the
    same committed tokens — checked against a NON-speculative single
    engine, the strongest form of the determinism claim."""
    model, params = _model()

    def spec_engine():
        return InferenceEngine(
            model, params, num_slots=3, temperature=0.0,
            draft_model=model, draft_params=params, spec_tokens=3,
        )

    reqs = _requests(n=8)
    refs = _single_reference(reqs)  # plain greedy engine, no speculation
    # warm the propose/verify programs (shared jit cache) so compiles
    # don't freeze replica heartbeats mid-run
    spec_engine().run(reqs)
    handles = [ReplicaHandle(f"r{i}", spec_engine()) for i in range(2)]
    router = FleetRouter(handles, heartbeat_timeout_s=2.0)
    _install(chaos.Fault("kill-replica", at="r1", step=2))
    try:
        report = router.run(reqs)
    finally:
        chaos.uninstall()
    m = report["metrics"]
    assert m["replicas_lost"] == 1
    assert m["replayed"] >= 1
    assert m["replay_token_exact"] is True
    for r in reqs:
        got = report["results"][r.rid]
        assert got["status"] == "done"
        assert got["tokens"] == refs[r.rid], r.rid


def test_stall_replica_detected_by_heartbeat_deadline():
    reqs = _requests(n=8)
    refs = _single_reference(reqs)
    router, handles = _fleet(2, heartbeat_timeout_s=0.4)
    _install(chaos.Fault("stall-replica", at="r1", step=2))
    try:
        report = router.run(reqs)
    finally:
        chaos.uninstall()
    m = report["metrics"]
    assert m["replicas_lost"] == 1
    # a stalled thread stays alive: only the beat deadline can catch it
    assert 0.4 <= m["detection_latency_s"] < 5.0
    assert handles[1].state() == "dead"
    for r in reqs:
        assert report["results"][r.rid]["tokens"] == refs[r.rid]


def test_flaky_channel_healed_by_dispatch_retry():
    reqs = _requests()
    refs = _single_reference(reqs)
    router, _handles = _fleet(2)
    fault = chaos.Fault("flaky-channel", count=2)
    _install(fault)
    try:
        report = router.run(reqs)
    finally:
        chaos.uninstall()
    m = report["metrics"]
    assert fault.fired == 2
    assert m["dispatch_retries"] == 2
    assert m["replicas_lost"] == 0
    assert m["completed"] == len(reqs)
    for r in reqs:
        assert report["results"][r.rid]["tokens"] == refs[r.rid]


# ---------------------------------------------------------------------------
# degradation: bounded queue, deadline shedding
# ---------------------------------------------------------------------------


def test_router_queue_overflow_sheds():
    reqs = _requests(n=8)
    router, _handles = _fleet(2, max_queue=2)
    report = router.run(reqs)
    m = report["metrics"]
    assert m["shed"] == 6  # all 8 arrive at t=0; the queue holds 2
    assert m["completed"] >= 2
    shed = [
        r for r in report["results"].values() if r["status"] == "shed"
    ]
    assert len(shed) == 6


def test_router_deadline_sheds_stale_queue():
    # one replica, so the tail of the burst waits past the deadline
    reqs = _requests(n=8)
    # tighter than one router tick (sleep 2ms): whatever the burst
    # leaves queued after the first dispatch round is stale next tick
    router, _handles = _fleet(1, queue_deadline_s=0.001)
    report = router.run(reqs)
    m = report["metrics"]
    assert m["shed"] >= 1
    assert m["completed"] >= 1
    assert m["completed"] + m["shed"] == len(reqs)


# ---------------------------------------------------------------------------
# bounded fetches (the engine-side timeout satellite)
# ---------------------------------------------------------------------------


def test_fetch_timeout_raises_engine_fetch_timeout():
    engine = _engine(fetch_timeout_s=0.1)
    with pytest.raises(EngineFetchTimeout, match="deadline"):
        engine._fetch(lambda: time.sleep(2.0), "hung fetch")


def test_fetch_without_deadline_unchanged():
    engine = _engine()  # fetch_timeout_s=None: straight through retries
    assert engine._fetch(lambda: 42, "plain fetch") == 42


def test_hung_fetch_surfaces_as_replica_loss():
    """A device fetch that never returns must kill the replica (bounded
    by fetch_timeout_s) instead of hanging the fleet; the router then
    replays its requests on the survivor."""
    reqs = _requests()
    refs = _single_reference(reqs)
    engines = [_engine(fetch_timeout_s=30.0), _engine(fetch_timeout_s=0.2)]
    hang = threading.Event()

    orig = engines[1]._fetch

    def hung_fetch(thunk, describe):
        def maybe_hang():
            if hang.is_set():
                time.sleep(5.0)  # a wedged runtime: the thunk never lands
            return thunk()
        return orig(maybe_hang, describe)

    engines[1]._fetch = hung_fetch
    hang.set()
    handles = [
        ReplicaHandle(f"r{i}", e) for i, e in enumerate(engines)
    ]
    router = FleetRouter(handles, heartbeat_timeout_s=5.0)
    report = router.run(reqs)
    m = report["metrics"]
    assert m["replicas_lost"] == 1
    assert "EngineFetchTimeout" in handles[1].error()
    for r in reqs:
        got = report["results"][r.rid]
        assert got["status"] == "done"
        assert got["tokens"] == refs[r.rid]


# ---------------------------------------------------------------------------
# the CLI: serve.py --replicas keeps the ONE-stdout-JSON-line contract
# ---------------------------------------------------------------------------


def test_serve_cli_fleet_emits_router_metrics_in_one_line():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("DPX_CHAOS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "serve.py"),
         "--replicas", "2", "--requests", "8", "--rate", "0",
         "--model-dim", "16", "--num-layers", "1", "--num-heads", "2",
         "--vocab-size", "61", "--max-len", "32",
         "--num-blocks", "16", "--block-size", "4", "--max-blocks", "4",
         "--slots", "3", "--prompt-len", "4:8", "--max-new", "4:8",
         "--sessions", "2"],
        capture_output=True, text=True, cwd=repo, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines  # the driver contract
    rec = json.loads(lines[0])
    assert rec["metric"] == "serve_tokens_per_sec"
    assert rec["replicas"] == 2
    assert rec["completed"] == 8
    for key in ("shed", "replayed", "redispatched", "dispatch_retries",
                "replicas_lost", "detection_latency_s", "queue_depth_max",
                "steady_per_row_ms",
                # graft-lens rolling latency summaries
                "ttft_p99_ms", "queue_wait_p99_ms", "journal_lag_p99_ms",
                "kv_occupancy_max", "sentinel_triggers"):
        assert key in rec, key
    assert rec["ttft_p99_ms"] > 0.0
    assert rec["queue_wait_p99_ms"] > 0.0
    assert rec["sentinel_triggers"] == []  # clean pass: nothing fired
    assert set(rec["per_replica"]) == {"r0", "r1"}
    for stats in rec["per_replica"].values():
        assert stats["state"] == "stopped"
        assert 0.0 <= stats["occupancy"] <= 1.0
    assert rec["config"]["replicas"] == 2


# ---------------------------------------------------------------------------
# scheduler under drain (host-side invariants the replay path leans on)
# ---------------------------------------------------------------------------


def _sched(num_blocks=8, block_size=2, max_blocks=3, num_slots=2):
    return Scheduler(PagedCacheConfig(
        num_blocks=num_blocks, block_size=block_size,
        max_blocks_per_slot=max_blocks, num_slots=num_slots,
    ))


def _req(rid, plen=3, max_new=2):
    return Request(rid=rid, prompt=list(range(plen)), max_new_tokens=max_new)


def test_preempt_youngest_front_requeues_and_recycles_blocks():
    sched = _sched()
    free0 = sched.allocator.free_count()
    for rid in ("a", "b"):
        sched.submit(_req(rid), now=0.0)
    sched.admit(now=0.0)
    assert sched.free_slots() == 0
    held = sched.allocator.free_count()
    victim = sched.preempt_youngest()
    # youngest = highest admit_order; its blocks come back exactly
    assert victim.request.rid == "b"
    assert victim.status == "queued"
    assert victim.generated == []
    assert victim.blocks == []
    assert sched.allocator.free_count() == held + 2  # blocks_for(3+1)=2
    # front-requeue: the victim keeps its seniority over later arrivals
    sched.submit(_req("c"), now=1.0)
    assert [st.request.rid for st in sched.queue] == ["b", "c"]
    admitted = sched.admit(now=1.0)
    assert admitted[0].request.rid == "b"
    # no double-allocation across the preempt/re-admit cycle
    for _slot, st in sched.active():
        sched.finish(st, "done", now=2.0)
    while sched.has_work():
        for st in sched.admit(now=3.0):
            pass
        for _slot, st in sched.active():
            sched.finish(st, "done", now=3.0)
    assert sched.allocator.free_count() == free0


def test_drain_resubmit_of_half_decoded_request_reallocates_cleanly():
    """The failover shape: a request with tokens already emitted is
    re-submitted (fresh state, same Request) after its first home
    released everything — allocation must not leak or double-count, and
    FIFO order must be preserved."""
    sched = _sched()
    free0 = sched.allocator.free_count()
    st = sched.submit(_req("a", plen=3, max_new=3), now=0.0)
    sched.admit(now=0.0)
    st.generated = [5, 6]  # half-decoded
    assert sched.grow(st)  # crosses into a second block region
    held = len(st.blocks)
    # replica dies: the engine's scheduler state is torn down wholesale
    sched.finish(st, "error", now=1.0, error="replica lost")
    assert sched.allocator.free_count() == free0
    # router replays the SAME Request on a fresh submit
    st2 = sched.submit(_req("a", plen=3, max_new=3), now=2.0)
    sched.submit(_req("z"), now=2.0)
    assert [s.request.rid for s in sched.queue] == ["a", "z"]
    sched.admit(now=2.0)
    assert st2.status == "running"
    assert st2.generated == []  # replay restarts from the prompt
    # the replay allocates afresh for the prompt only (not the half-
    # decoded footprint the first incarnation had grown to)
    assert len(st2.blocks) == 2
    assert held == 3
    sched.finish(st2, "done", now=3.0)
    for _slot, s in sched.active():
        sched.finish(s, "done", now=3.0)
    while sched.queue:
        for s in sched.admit(now=4.0):
            sched.finish(s, "done", now=4.0)
    assert sched.allocator.free_count() == free0


def test_double_allocation_impossible_under_interleaved_drain():
    """Interleaved admit/preempt/finish churn never hands the same block
    to two owners and never loses one."""
    sched = _sched(num_blocks=8, block_size=2, max_blocks=4, num_slots=2)
    free0 = sched.allocator.free_count()
    for i in range(5):
        sched.submit(_req(f"r{i}", plen=2 + i % 3, max_new=2), now=0.0)
    for round_ in range(12):
        sched.admit(now=float(round_))
        owned = [b for _s, st in sched.active() for b in st.blocks]
        assert len(owned) == len(set(owned))  # no block owned twice
        assert len(owned) + sched.allocator.free_count() == free0
        if round_ % 3 == 2 and sched.active():
            sched.preempt_youngest()
        elif sched.active():
            _slot, st = sched.active()[0]
            sched.finish(st, "done", now=float(round_))
    while sched.has_work():
        for st in sched.admit(now=99.0):
            pass
        for _slot, st in sched.active():
            sched.finish(st, "done", now=99.0)
    assert sched.allocator.free_count() == free0
