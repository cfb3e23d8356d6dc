#!/usr/bin/env python3
"""graft-armor chaos sweep: seeded fault matrix, one JSON line each.

Drives a real ``Trainer.fit`` (tiny SimpleNet on the fake 8-device CPU
mesh) through every fault class the robustness layer claims to survive
and prints ONE JSON summary line per scenario — ``ok``, the recovery
``action`` the framework took, and the evidence fields — so a CI log
shows exactly which guarantee broke. Exit code 0 iff every scenario
recovered as contracted.

Scenarios (``--fast`` runs the starred subset; the rest ride the full
matrix — tier-1 runs the fast subset via tests/test_chaos.py, the full
matrix runs under ``-m slow``):

- ``nan-skip`` *        NaN batch mid-run: update predicated out
                        device-side, trajectory deterministic (the run is
                        repeated and must match bit-for-bit).
- ``inf-skip``          Same contract for an Inf batch.
- ``budget-rollback``   Persistent NaN: bounded skips, ONE rollback to
                        the last good checkpoint, then a hard fail.
- ``corrupt-latest`` *  Bit-flipped `latest`: load falls back to the
                        newest intact ancestor, no operator action.
- ``truncate-shard``    Torn shard file: sharded load falls back to the
                        previous intact version dir.
- ``io-flake`` *        Transient OSError on checkpoint writes: the
                        async saver retries with backoff and the file
                        lands.
- ``rendezvous-flake`` * Coordinator not up yet: bounded retry with
                        exponential backoff on initialize().
- ``torn-save-kill``    Subprocess SIGKILLed between shard writes and
                        the manifest/pointer flip; the resume run lands
                        on the previous intact checkpoint.
- ``sigint``            Subprocess interrupted: checkpoint after the
                        in-flight step, exit 130.
- ``kill-slice`` *      Preempted slice (graft-elastic): a dp8 run is
                        SIGKILLed at a step boundary, the job shrinks
                        to the 4 surviving devices and resumes from the
                        last intact checkpoint under ``DPX_ELASTIC=1``;
                        the post-resume loss trajectory must match an
                        uninterrupted dp4 run batch-for-batch.
- ``poison-request`` *  Serving (graft-serve): one request's logits go
                        NaN mid-stream; the engine evicts THAT request
                        with an error status at the next decode
                        boundary, and the co-resident requests' outputs
                        are bit-identical to an uninjected replay.
- ``kill-replica-midstream`` * Fleet serving (graft-fleet): one of two
                        replicas dies mid-decode; the router detects it
                        within the heartbeat deadline, replays its
                        journaled requests elsewhere, and EVERY request
                        — survivors and replayed, greedy AND seeded
                        top-k — finishes bit-identical to an uninjected
                        fleet run. Steady-state per-row decode cost with
                        the chaos checks armed (fault never firing) must
                        stay within 5% of a clean run.
- ``corrupt-shard-midepoch`` * Input plane (graft-intake): a sealed
                        image shard is bit-flipped on disk mid-epoch;
                        the first touch fails its DPX-CRC1 sidecar,
                        the shard is quarantined, its samples are
                        deterministically remapped to intact shards,
                        and the loss trajectory + final params are
                        BIT-IDENTICAL to a control run that
                        pre-quarantined the same shard (no corrupt
                        sample is ever served). Steady-state epoch
                        iteration with seal verification armed must
                        stay within 5% of ``integrity="off"``.
- ``kill-decode-worker`` * Input plane (graft-intake): the supervised
                        prefetch worker crashes mid-epoch; the
                        consumer-side supervisor restarts it at the
                        exact batch the training loop expects next, so
                        losses and final params are bit-identical to an
                        uninjected run, with the restart in telemetry.
- ``hot-swap-midstream`` * Live weight sync (graft-swap): a fine-tuned
                        checkpoint is published and rolled through a
                        two-replica fleet mid-decode. In-flight streams
                        finish bit-identical to an unswapped control
                        (greedy AND seeded top-k), post-swap sessions
                        carry the new ``weights_version`` and match a
                        reference on the fine-tuned params, the swap
                        blackout stays under one decode-boundary p99,
                        and a corrupt commit + torn publish in the same
                        channel never reach a replica.

Usage:
  python scripts/chaos_sweep.py [--fast] [--scenarios a,b,...]

NOT a chip command. The sweep does its own JAX work on the virtual CPU mesh
AND starts child processes (``torn-save-kill``, ``sigint``, ``kill-slice``,
the serve scenarios); a chip belongs to one process at a time, so parent and
children alike are held to the CPU (``JAX_PLATFORMS=cpu``, set in
``_force_cpu_mesh`` and in every child's environment by ``_child_env``).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

FAST = (
    "nan-skip", "corrupt-latest", "io-flake", "rendezvous-flake",
    "kill-slice", "poison-request", "kill-replica-midstream",
    "corrupt-shard-midepoch", "kill-decode-worker", "hot-swap-midstream",
)
SLOW = (
    "inf-skip", "budget-rollback", "truncate-shard", "torn-save-kill",
    "sigint",
)
ALL = FAST + SLOW


def _force_cpu_mesh(n: int = 8) -> None:
    """Fake n-device CPU mesh (same knobs as tests/conftest.py); must run
    before jax initializes a backend."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _child_env(chaos_json: str = "") -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # children never touch a chip
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    if chaos_json:
        env["DPX_CHAOS"] = chaos_json
    else:
        env.pop("DPX_CHAOS", None)
    return env


def _make_trainer(ckpt_dir=None, **kw):
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.models import SimpleNet

    return dpx.train.Trainer(
        SimpleNet(input_size=16, hidden_size=32, num_classes=4),
        dpx.train.ClassificationTask(),
        optax.adam(1e-2),
        partitioner=dpx.parallel.data_parallel(kw.pop("mesh")),
        checkpoint_dir=ckpt_dir,
        log_every=kw.pop("log_every", 2),
        **kw,
    )


def _dataset(n=256, seed=0):
    import numpy as np

    from distributed_pytorch_example_tpu.data.synthetic import _ArrayDataset

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    w = rng.standard_normal((16, 4)).astype(np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return _ArrayDataset({"x": x, "y": y})


def _param_digest(state) -> str:
    import hashlib

    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state.params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _fit_with_poison(kind: str, mesh):
    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import chaos

    chaos.install(chaos.ChaosPlan(faults=[chaos.Fault(kind, step=2)]))
    try:
        trainer = _make_trainer(mesh=mesh)
        loader = dpx.data.DeviceLoader(_dataset(), 64, mesh=mesh, seed=0)
        history = trainer.fit(loader, epochs=2)
    finally:
        chaos.uninstall()
    return trainer, history


def scenario_poison_skip(kind: str) -> dict:
    """nan-skip / inf-skip: skipped update, deterministic trajectory."""
    import math

    import distributed_pytorch_example_tpu as dpx

    mesh = dpx.runtime.make_mesh()
    t1, h1 = _fit_with_poison(kind, mesh)
    detail = {
        "bad_steps": t1.recovery["bad_steps"],
        "rollbacks": t1.recovery["rollbacks"],
        "final_loss_finite": math.isfinite(h1[-1]["train_loss"]),
    }
    ok = detail["bad_steps"] >= 1 and detail["final_loss_finite"]
    if kind == "nan-batch":
        # the determinism contract: same plan, same seed ⇒ bit-identical
        # params (the skip is part of the compiled program, not a host race)
        t2, _ = _fit_with_poison(kind, mesh)
        detail["deterministic"] = _param_digest(t1.state) == _param_digest(
            t2.state
        )
        ok = ok and detail["deterministic"]
    return {"ok": ok, "action": "update-predicated-out", **detail}


def scenario_budget_rollback() -> dict:
    """Persistent NaN: skips bounded, one rollback, then hard fail."""
    import tempfile

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import (
        BadStepBudgetExceeded,
        chaos,
    )

    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("nan-batch", step=2, count=10_000)]
    ))
    hard_failed = False
    try:
        with tempfile.TemporaryDirectory() as td:
            trainer = _make_trainer(
                ckpt_dir=td, mesh=mesh, log_every=1, max_bad_steps=1,
                save_every_steps=1,
            )
            loader = dpx.data.DeviceLoader(
                _dataset(), 64, mesh=mesh, seed=0
            )
            try:
                trainer.fit(loader, epochs=3)
            except BadStepBudgetExceeded:
                hard_failed = True
    finally:
        chaos.uninstall()
    detail = {
        "bad_steps": trainer.recovery["bad_steps"],
        "rollbacks": trainer.recovery["rollbacks"],
        "hard_failed": hard_failed,
    }
    return {
        "ok": detail["rollbacks"] == 1 and hard_failed,
        "action": "rollback-then-hard-fail",
        **detail,
    }


def scenario_corrupt_latest() -> dict:
    """Bit-flipped gathered `latest`: fallback to newest intact ancestor."""
    import tempfile

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    mesh = dpx.runtime.make_mesh()
    events = []
    with tempfile.TemporaryDirectory() as td:
        trainer = _make_trainer(ckpt_dir=td, mesh=mesh)
        loader = dpx.data.DeviceLoader(_dataset(), 64, mesh=mesh, seed=0)
        trainer.fit(loader, epochs=2)
        latest = os.path.join(td, ckpt_lib.LATEST_NAME)
        chaos.corrupt_file(latest, mode="bitflip", seed=0)
        _state, epoch, _extra = ckpt_lib.load_checkpoint(
            latest, trainer.state, trainer.state_shardings,
            on_event=lambda kind, **f: events.append({"event": kind, **f}),
        )
    fallbacks = [e for e in events if e["event"] == "checkpoint_fallback"]
    return {
        "ok": len(fallbacks) == 1 and epoch >= 1,
        "action": "fallback-to-intact-ancestor",
        "restored_epoch": int(epoch),
        "skipped": fallbacks[0]["skipped"] if fallbacks else [],
    }


def scenario_truncate_shard() -> dict:
    """Truncated shard in the pointed version: fallback to older version."""
    import glob
    import tempfile

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    mesh = dpx.runtime.make_mesh()
    events = []
    with tempfile.TemporaryDirectory() as td:
        trainer = _make_trainer(
            ckpt_dir=td, mesh=mesh, checkpoint_format="sharded"
        )
        loader = dpx.data.DeviceLoader(_dataset(), 64, mesh=mesh, seed=0)
        trainer.fit(loader, epochs=3)
        latest = os.path.join(td, ckpt_lib.LATEST_NAME)
        versions = sorted(glob.glob(
            os.path.join(td, ckpt_lib.LATEST_NAME + ".shards", "*")
        ))
        shard = glob.glob(os.path.join(versions[-1], "shard_*.msgpack"))[0]
        chaos.corrupt_file(shard, mode="truncate")
        _state, epoch, _extra = ckpt_lib.load_checkpoint(
            latest, trainer.state, trainer.state_shardings,
            on_event=lambda kind, **f: events.append({"event": kind, **f}),
        )
    fallbacks = [e for e in events if e["event"] == "checkpoint_fallback"]
    return {
        "ok": len(fallbacks) == 1 and epoch >= 1,
        "action": "fallback-to-older-version",
        "restored_epoch": int(epoch),
        "versions": len(versions),
    }


def scenario_io_flake() -> dict:
    """Transient OSError on the first two `latest` writes: saver retries."""
    import tempfile

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("io-error", path_substr="latest", count=2)]
    ))
    try:
        with tempfile.TemporaryDirectory() as td:
            trainer = _make_trainer(
                ckpt_dir=td, mesh=mesh, save_every_steps=2
            )
            loader = dpx.data.DeviceLoader(
                _dataset(), 64, mesh=mesh, seed=0
            )
            trainer.fit(loader, epochs=2)
            written = os.path.exists(
                os.path.join(td, ckpt_lib.LATEST_NAME)
            )
            retries = trainer._saver.io_retries_used
    finally:
        chaos.uninstall()
    return {
        "ok": written and retries >= 1,
        "action": "retry-with-backoff",
        "io_retries_used": retries,
    }


def scenario_rendezvous_flake() -> dict:
    """First two rendezvous attempts fail: bounded backoff retry."""
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.runtime import (
        distributed as dist,
    )

    fault = chaos.Fault("rendezvous-flake", count=2)
    chaos.install(chaos.ChaosPlan(faults=[fault]))
    was_initialized = dist._initialized
    dist._initialized = False
    os.environ["DPX_RENDEZVOUS_BACKOFF"] = "0.01"
    try:
        dist.initialize()
    finally:
        dist._initialized = was_initialized or dist._initialized
        os.environ.pop("DPX_RENDEZVOUS_BACKOFF", None)
        chaos.uninstall()
    return {
        "ok": fault.fired == 2,
        "action": "retry-with-backoff",
        "attempts": fault.fired + 1,
    }


def scenario_torn_save_kill() -> dict:
    """SIGKILL mid-sharded-save (post-shards, pre-manifest/pointer): the
    resume run must land on the previous intact version."""
    import tempfile

    from distributed_pytorch_example_tpu.robustness import chaos

    with tempfile.TemporaryDirectory() as td:
        plan = chaos.ChaosPlan(faults=[
            chaos.Fault("kill", at="sharded-save:post-shards", nth=2)
        ])
        crash = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "torn-train", "--dir", td],
            env=_child_env(plan.to_json()), capture_output=True, text=True,
            cwd=REPO_ROOT, timeout=600,
        )
        killed = crash.returncode == -signal.SIGKILL
        resume = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "torn-resume", "--dir", td],
            env=_child_env(), capture_output=True, text=True,
            cwd=REPO_ROOT, timeout=600,
        )
        try:
            info = json.loads(resume.stdout.strip().splitlines()[-1])
        except (json.JSONDecodeError, IndexError):
            info = {"error": resume.stderr[-500:]}
    return {
        "ok": killed and resume.returncode == 0
        and info.get("resumed_epoch") is not None,
        "action": "resume-from-intact-ancestor",
        "killed": killed,
        **info,
    }


def scenario_sigint() -> dict:
    """SIGINT a training child: checkpoint lands, exit code 130."""
    import tempfile

    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    with tempfile.TemporaryDirectory() as td:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             "sigint-train", "--dir", td],
            env=_child_env(), stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True, cwd=REPO_ROOT,
        )
        latest = os.path.join(td, ckpt_lib.LATEST_NAME)
        deadline = time.time() + 300
        while time.time() < deadline and not os.path.exists(latest):
            if child.poll() is not None:
                break
            time.sleep(0.25)
        child.send_signal(signal.SIGINT)
        try:
            _, err = child.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            child.kill()
            _, err = child.communicate()
        written = os.path.exists(latest)
    return {
        "ok": child.returncode == 130 and written,
        "action": "checkpoint-and-exit-130",
        "exit_code": child.returncode,
        "checkpoint_written": written,
    }


def scenario_kill_slice() -> dict:
    """Kill-a-slice (graft-elastic): dp8 run SIGKILLed at a step boundary
    shrinks to the 4 surviving devices; the elastic resume's post-resume
    loss trajectory must match an uninterrupted dp4 run batch-for-batch
    (same loss tolerance tests/test_zero1.py pins for flip-resume).

    The equivalence holds because the global batch (and therefore the
    math) is mesh-shape-independent: the dp8 steps before the kill equal
    the dp4 control's steps modulo float reduction order, the sampler
    permutation is a pure function of (seed, epoch), and the step rng
    folds the restored state.step — so after reshard-on-load the two
    runs walk the same trajectory.
    """
    import re
    import tempfile

    from distributed_pytorch_example_tpu.robustness import chaos

    loss_re = re.compile(r"Epoch (\d+), Batch (\d+)/\d+, Loss: ([0-9.]+)")

    def losses(stderr: str) -> dict:
        return {
            (int(m.group(1)), int(m.group(2))): float(m.group(3))
            for m in loss_re.finditer(stderr)
        }

    def run(phase: str, td: str, env: dict):
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             phase, "--dir", td],
            env=env, capture_output=True, text=True, cwd=REPO_ROOT,
            timeout=600,
        )

    with tempfile.TemporaryDirectory() as td:
        # 4 steps/epoch; the 5th step BOUNDARY is epoch 1 batch 0, so the
        # kill lands mid-epoch with intact epoch-0 saves behind it
        plan = chaos.ChaosPlan(faults=[
            chaos.Fault("kill", at="step", nth=5)
        ])
        crash = run("elastic-train", td, _child_env(plan.to_json()))
        killed = crash.returncode == -signal.SIGKILL
        resume_env = _child_env()
        resume_env["DPX_ELASTIC"] = "1"
        resume = run("elastic-resume", td, resume_env)
        control = run("elastic-control", td, _child_env())
        got, want = losses(resume.stderr), losses(control.stderr)
    common = sorted(set(got) & set(want))
    max_diff = max(
        (abs(got[k] - want[k]) for k in common), default=None
    )
    tol = 1e-3 + 1e-4  # pinned flip-resume loss tolerance + %.4f rounding
    return {
        "ok": (
            killed and resume.returncode == 0 and control.returncode == 0
            and len(common) >= 4 and max_diff is not None
            and max_diff <= tol
        ),
        "action": "shrink-to-survivors-resume",
        "killed": killed,
        "resume_from": list(min(got)) if got else None,
        "resumed_batches": len(common),
        "max_loss_diff": max_diff,
    }


def scenario_poison_request() -> dict:
    """NaN-logits request mid-stream (graft-serve): evicted with an error
    status; co-resident requests' outputs bit-identical to an uninjected
    replay (per-row attention + per-request position-folded rng share no
    cross-row state, and the block allocator is a deterministic LIFO)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.serving import (
        InferenceEngine, Request,
    )

    kw = dict(vocab_size=61, max_len=32, model_dim=16, num_layers=1,
              num_heads=2, mlp_dim=32)
    params = GPT2(**kw).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = GPT2(**kw, decode=True, paged_num_blocks=16,
                 paged_block_size=4, paged_max_blocks=4)
    rng = np.random.default_rng(0)
    requests = [
        Request(rid=f"r{i}", prompt=[int(t) for t in rng.integers(0, 61, n)],
                max_new_tokens=8, seed=i)
        for i, n in enumerate((6, 5, 7))
    ]

    def replay(faults):
        engine = InferenceEngine(
            model, params, num_slots=3, temperature=1.0, top_k=5,
        )
        chaos.install(chaos.ChaosPlan(faults=faults))
        try:
            return engine.run(requests)
        finally:
            chaos.uninstall()

    clean = replay([])
    fault = chaos.Fault("poison-request", at="r1", step=3)
    hit = replay([fault])

    poisoned = hit["results"]["r1"]
    co_identical = all(
        hit["results"][r]["tokens"] == clean["results"][r]["tokens"]
        and clean["results"][r]["status"] == "done"
        for r in ("r0", "r2")
    )
    return {
        "ok": (
            poisoned["status"] == "error" and fault.fired >= 1
            and hit["metrics"]["errored"] == 1
            and hit["metrics"]["completed"] == 2 and co_identical
        ),
        "action": "evict-poisoned-request",
        "poisoned_status": poisoned["status"],
        "poisoned_error": poisoned["error"],
        "tokens_before_eviction": len(poisoned["tokens"]),
        "co_resident_bit_identical": co_identical,
    }


def scenario_kill_replica_midstream() -> dict:
    """Replica loss mid-decode (graft-fleet): the router's journal replay
    must reproduce every evicted request bit-identically — greedy and
    seeded top-k — because tokens depend only on (seed, prompt, absolute
    position), never on which replica or slot decoded them. The armed-
    inert arm (plan installed, fault parked at an unreachable step) pins
    the failover machinery's steady-state overhead to <= 5%."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.serving import (
        FleetRouter, InferenceEngine, Request, ReplicaHandle,
    )

    kw = dict(vocab_size=61, max_len=32, model_dim=16, num_layers=1,
              num_heads=2, mlp_dim=32)
    params = GPT2(**kw).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = GPT2(**kw, decode=True, paged_num_blocks=16,
                 paged_block_size=4, paged_max_blocks=4)
    rng = np.random.default_rng(7)
    requests = [
        Request(rid=f"q{i:02d}",
                prompt=[int(t) for t in rng.integers(0, 61, plen)],
                max_new_tokens=8, seed=1000 + i)
        for i, plen in enumerate((4, 5, 6, 7, 8, 5, 6, 7, 4, 8, 5, 6))
    ]

    def fleet_run(temperature, top_k, plan, n_replicas=2):
        engines = [
            InferenceEngine(model, params, num_slots=3,
                            temperature=temperature, top_k=top_k)
            for _ in range(n_replicas)
        ]
        handles = [
            ReplicaHandle(f"r{i}", e) for i, e in enumerate(engines)
        ]
        router = FleetRouter(handles, heartbeat_timeout_s=2.0)
        chaos.install(plan)
        try:
            return router.run(requests, timeout_s=120.0)
        finally:
            chaos.uninstall()

    def kill_plan(step):
        return chaos.ChaosPlan(faults=[
            chaos.Fault("kill-replica", at="r1", step=step)
        ])

    detail = {}
    ok = True
    for regime, temperature, top_k in (
        ("greedy", 0.0, None), ("seeded-topk", 0.9, 5),
    ):
        # XLA compile freezes replica heartbeats: warm this sampling
        # regime's programs before any router with a 2s deadline runs
        InferenceEngine(model, params, num_slots=3,
                        temperature=temperature, top_k=top_k).warmup()
        clean = fleet_run(temperature, top_k, None)
        hit = fleet_run(temperature, top_k, kill_plan(4))
        hm = hit["metrics"]
        all_match = all(
            hit["results"][r.rid]["status"] == "done"
            and clean["results"][r.rid]["status"] == "done"
            and hit["results"][r.rid]["tokens"]
            == clean["results"][r.rid]["tokens"]
            for r in requests
        )
        regime_ok = (
            all_match
            and hm["replicas_lost"] == 1
            and hm["replayed"] >= 1
            and hm["replay_token_exact"] is True
            and hm["detection_latency_s"] is not None
            and hm["detection_latency_s"] <= 2.5
        )
        detail[regime] = {
            "bit_identical_to_clean": all_match,
            "replayed": hm["replayed"],
            "redispatched": hm["redispatched"],
            "replay_token_exact": hm["replay_token_exact"],
            "detection_latency_s": hm["detection_latency_s"],
        }
        ok = ok and regime_ok

    # steady-state overhead: best-boundary per-row cost (host scheduling
    # noise only ever ADDS time, so the min moves only when the fleet
    # machinery itself gets slower), min over 5 interleaved runs per
    # arm; both arms run identical code paths except the armed (never-
    # firing) chaos check at each boundary. Measured on a ONE-replica
    # fleet: with two worker threads on a small box the min is set by
    # how the threads happen to overlap (and by which replica the
    # least-loaded tie-break favored), not by the machinery under test.
    def steady(plan_maker):
        m = fleet_run(0.0, None, plan_maker(), n_replicas=1)["metrics"]
        return m["steady_per_row_ms_min"]

    def inert_plan():
        # armed on the replica that exists, parked at an unreachable
        # step: the per-boundary check runs its full match path
        return chaos.ChaosPlan(faults=[
            chaos.Fault("kill-replica", at="r0", step=10_000)
        ])

    # drop the chaos phase's garbage first and keep the collector out of
    # the measured window (same recipe as the predication overhead gate
    # in tests/test_chaos.py: fake-mesh boundaries sit near host timer
    # jitter, and a gen-0 sweep mid-boundary lands on either arm).
    # The estimator is the MIN over pair ratios: each clean/inert pair
    # is back-to-back (~2s apart), so the slow multiplicative drift of
    # the host's floor cancels within a pair, while the machinery's
    # true overhead is present in EVERY pair and survives the min.
    import gc
    gc.collect()
    gc.disable()
    try:
        pairs = []
        for _ in range(5):
            c = steady(lambda: None)
            i = steady(inert_plan)
            if c and i is not None:
                pairs.append((c, i))
    finally:
        gc.enable()
    clean_ms, inert_ms = (
        min(pairs, key=lambda p: p[1] / p[0]) if pairs else (None, None)
    )
    ratio = inert_ms / clean_ms if pairs else None
    detail["steady_per_row_ms"] = {"clean": clean_ms, "inert": inert_ms}
    detail["steady_state_ratio"] = ratio
    ok = ok and ratio is not None and ratio <= 1.05
    return {"ok": ok, "action": "failover-replay", **detail}


def _sealed_image_dir(td: str, tag: str, n=256, hw=4, shard_size=64) -> str:
    """A sealed 4-shard image dataset, identical for every ``tag``."""
    import numpy as np

    from distributed_pytorch_example_tpu.data import streaming

    root = os.path.join(td, tag)
    os.makedirs(root)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (n, hw, hw, 3)).astype(np.uint8)
    w = rng.standard_normal((hw * hw * 3, 4)).astype(np.float32)
    y = np.argmax(
        (x.reshape(n, -1) / 255.0) @ w, axis=1
    ).astype(np.int64)
    streaming.write_image_shards(
        root,
        (
            (x[lo:lo + shard_size], y[lo:lo + shard_size])
            for lo in range(0, n, shard_size)
        ),
        shard_size=shard_size,
        seal=True,
    )
    return root


def scenario_corrupt_shard_midepoch() -> dict:
    """Bit-flipped sealed shard mid-epoch (graft-intake): quarantine +
    deterministic remap; trajectory bit-identical to a pre-quarantined
    control because verify-before-serve means no corrupt sample is EVER
    served — both runs serve the exact same remapped sample stream.
    Armed seal verification must cost <= 5% on steady-state iteration."""
    import tempfile

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.data import streaming
    from distributed_pytorch_example_tpu.robustness import chaos

    mesh = dpx.runtime.make_mesh()

    def run(root, plan=None, pre_quarantine=None):
        ds = streaming.StreamingImageShards(root)
        if pre_quarantine:
            ds.quarantine(pre_quarantine)
        trainer = _make_trainer(mesh=mesh)
        loader = dpx.data.DeviceLoader(ds, 64, mesh=mesh, seed=0)
        if plan is not None:
            chaos.install(plan)
        try:
            history = trainer.fit(loader, epochs=2)
        finally:
            if plan is not None:
                chaos.uninstall()
        return trainer, history, ds

    def inject_plan():
        return chaos.ChaosPlan(faults=[
            chaos.Fault("corrupt-shard", path_substr="images_00002", nth=1)
        ])

    with tempfile.TemporaryDirectory() as td:
        # separate dirs: the injected runs corrupt their shard ON DISK
        ct, ch, _cds = run(
            _sealed_image_dir(td, "control"), pre_quarantine={2}
        )
        t1, h1, ds1 = run(_sealed_image_dir(td, "hit1"), plan=inject_plan())
        t2, _h2, _ds2 = run(
            _sealed_image_dir(td, "hit2"), plan=inject_plan()
        )

        # steady-state overhead of seal verification: armed (sealed dir,
        # integrity="quarantine" — the default) vs verification off, one
        # epoch of prefetched iteration per sample, min over interleaved
        # pair ratios with the collector parked (the min-ratio recipe the
        # kill-replica-midstream gate pins; host noise only ADDS time)
        import gc

        bench_root = _sealed_image_dir(td, "bench", n=1024, shard_size=128)

        def epoch_s(integrity):
            ds = streaming.StreamingImageShards(
                bench_root, integrity=integrity
            )
            loader = dpx.data.DeviceLoader(
                ds, 64, mesh=mesh, seed=0, shuffle=False
            )
            t0 = time.perf_counter()
            for _ in loader:
                pass
            return time.perf_counter() - t0

        epoch_s("off")  # warm the h2d path before the first timed pair
        gc.collect()
        gc.disable()
        try:
            pairs = []
            for _ in range(5):
                clean_s = epoch_s("off")
                armed_s = epoch_s("quarantine")
                pairs.append((clean_s, armed_s))
        finally:
            gc.enable()
    clean_s, armed_s = min(pairs, key=lambda p: p[1] / p[0])
    ratio = armed_s / clean_s

    events = [
        e for e in (t1.telemetry_summary or {}).get("events", [])
        if e.get("event") == "shard_quarantine"
    ]
    max_loss_diff = max(
        abs(a["train_loss"] - b["train_loss"]) for a, b in zip(ch, h1)
    )
    digests = (_param_digest(ct.state), _param_digest(t1.state),
               _param_digest(t2.state))
    detail = {
        "quarantined": sorted(ds1.quarantined_shards),
        "quarantine_events": len(events),
        "max_loss_diff_vs_prequarantined_control": max_loss_diff,
        "params_match_control": digests[1] == digests[0],
        "deterministic": digests[1] == digests[2],
        "steady_state_ratio": round(ratio, 4),
    }
    return {
        "ok": (
            detail["quarantined"] == [2]
            and detail["quarantine_events"] >= 1
            and max_loss_diff == 0.0
            and detail["params_match_control"]
            and detail["deterministic"]
            and ratio <= 1.05
        ),
        "action": "quarantine-and-remap",
        **detail,
    }


def scenario_kill_decode_worker() -> dict:
    """Prefetch-worker crash mid-epoch (graft-intake): the consumer-side
    supervisor restarts the worker at the exact batch the training loop
    expects next (batch assembly is a pure function of the index), so
    the trajectory is bit-identical to an uninjected run."""
    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.robustness import chaos

    mesh = dpx.runtime.make_mesh()

    def run(plan=None):
        trainer = _make_trainer(mesh=mesh)
        loader = dpx.data.DeviceLoader(_dataset(), 64, mesh=mesh, seed=0)
        # init BEFORE arming the plan: fit's sample-batch iteration is
        # abandoned after one batch, and whether its prefetch worker
        # reaches the fault index first is a race — the epoch loop is
        # where the kill must land, deterministically
        trainer.init(next(iter(loader))["x"])
        if plan is not None:
            chaos.install(plan)
        try:
            history = trainer.fit(loader, epochs=2)
        finally:
            if plan is not None:
                chaos.uninstall()
        return trainer, history, loader

    def kill_plan():
        return chaos.ChaosPlan(faults=[
            chaos.Fault("kill-decode-worker", step=2)
        ])

    ct, ch, _cl = run()
    t1, h1, l1 = run(kill_plan())
    t2, _h2, _l2 = run(kill_plan())

    events = [
        e for e in (t1.telemetry_summary or {}).get("events", [])
        if e.get("event") == "decode_worker_restart"
    ]
    max_loss_diff = max(
        abs(a["train_loss"] - b["train_loss"]) for a, b in zip(ch, h1)
    )
    digests = (_param_digest(ct.state), _param_digest(t1.state),
               _param_digest(t2.state))
    detail = {
        "worker_restarts": l1.worker_restarts,
        "restart_events": len(events),
        "max_loss_diff_vs_uninjected": max_loss_diff,
        "params_match_uninjected": digests[1] == digests[0],
        "deterministic": digests[1] == digests[2],
    }
    return {
        "ok": (
            detail["worker_restarts"] >= 1
            and detail["restart_events"] >= 1
            and max_loss_diff == 0.0
            and detail["params_match_uninjected"]
            and detail["deterministic"]
        ),
        "action": "supervised-worker-restart",
        **detail,
    }


def scenario_hot_swap_midstream() -> dict:
    """Live weight hot-swap mid-decode (graft-swap): fine-tune a few
    steps, publish through the corruption-safe channel, and roll the new
    version through a two-replica fleet WHILE it decodes. In-flight
    streams must finish bit-identical to an unswapped control — greedy
    AND seeded top-k — because a replica drains before install, so no
    stream ever mixes two versions' logits; post-swap sessions must
    carry the published ``weights_version`` and match a reference fleet
    running the fine-tuned params; the measured ``swap_blackout_ms``
    must stay under one decode-boundary p99; and a corrupt commit plus a
    torn (uncommitted) publish sitting in the SAME channel must never
    reach a replica."""
    import hashlib
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.data.synthetic import _ArrayDataset
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.robustness import chaos
    from distributed_pytorch_example_tpu.robustness.publish import (
        PublishChannel,
    )
    from distributed_pytorch_example_tpu.serving import (
        FleetRouter, InferenceEngine, Request, ReplicaHandle,
        SwapController,
    )
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    kw = dict(vocab_size=61, max_len=32, model_dim=16, num_layers=1,
              num_heads=2, mlp_dim=32)
    v0_params = GPT2(**kw).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = GPT2(**kw, decode=True, paged_num_blocks=16,
                 paged_block_size=4, paged_max_blocks=4)

    # fine-tune K=4 optimizer steps on the fake mesh: the version the
    # fleet must adopt (stamped with the dp8 mesh manifest, which the
    # swap restore validates against the serve layout)
    mesh = dpx.runtime.make_mesh()
    trainer = dpx.train.Trainer(
        GPT2(**kw), dpx.train.CausalLMTask(), optax.adam(1e-2),
        partitioner=dpx.parallel.data_parallel(mesh), log_every=1,
    )
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 61, (128, 16)).astype(np.int32)
    loader = dpx.data.DeviceLoader(
        _ArrayDataset({"tokens": tokens}), 32, mesh=mesh, seed=0
    )
    history = trainer.fit(loader, epochs=1)
    tuned = jax.tree_util.tree_map(np.asarray, trainer.state.params)

    def digest(params):
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    rng_req = np.random.default_rng(7)

    def make_requests(prefix, n, seed0):
        return [
            Request(rid=f"{prefix}{i:02d}",
                    prompt=[int(t)
                            for t in rng_req.integers(0, 61, 4 + i % 5)],
                    max_new_tokens=8, seed=seed0 + i)
            for i in range(n)
        ]

    requests_a = make_requests("a", 12, 1000)  # in flight during the roll
    requests_b = make_requests("b", 6, 2000)   # post-swap new sessions

    with tempfile.TemporaryDirectory() as td:
        channel = PublishChannel(os.path.join(td, "publish"))
        good = ckpt_lib.publish_checkpoint(
            channel, trainer.state, epoch=1,
            loss=float(history[-1]["train_loss"]),
        )
        # a LATER corrupt commit: the pointer names it, so adopting it
        # would be the pointer-chasing bug — the intact-ancestor walk
        # must fall back to `good`
        chaos.install(chaos.ChaosPlan(faults=[
            chaos.Fault("corrupt-publish", nth=1)
        ]))
        try:
            ckpt_lib.publish_checkpoint(
                channel, trainer.state, epoch=1, loss=0.0
            )
        finally:
            chaos.uninstall()
        corrupt = channel.pointer_version()
        # a torn publish: artifact on disk, pointer never flipped —
        # readers must not even consider it (it is past the pointer)
        torn = f"{int(corrupt) + 1:08d}"
        os.makedirs(os.path.join(channel.versions_root, torn))
        with open(channel.artifact_path(torn), "wb") as f:
            f.write(b"\x00" * 64)
        chan_state = channel.state()

        def fleet_run(requests, temperature, top_k, *, engines=None,
                      params=v0_params, version="v0", swap=False):
            engines = engines or [
                InferenceEngine(model, params, num_slots=3,
                                temperature=temperature, top_k=top_k,
                                weights_version=version)
                for _ in range(2)
            ]
            handles = [
                ReplicaHandle(f"r{i}", e) for i, e in enumerate(engines)
            ]
            router = FleetRouter(handles, heartbeat_timeout_s=2.0)
            ctrl = SwapController(
                channel, handles, poll_s=0.05, min_decode_steps=2,
            ) if swap else None
            report = router.run(requests, timeout_s=120.0, swap=ctrl)
            return report, engines, handles, ctrl

        detail = {
            "published_good": good,
            "published_corrupt": corrupt,
            "torn_dir": torn,
            "channel_latest": chan_state["latest_intact"],
            "tuned_params_differ": digest(tuned) != digest(v0_params),
        }
        ok = (
            chan_state["latest_intact"] == good
            and not next(
                v for v in chan_state["versions"]
                if v["version"] == corrupt
            )["intact"]
            and not next(
                v for v in chan_state["versions"] if v["version"] == torn
            )["committed"]
            and detail["tuned_params_differ"]
        )
        for regime, temperature, top_k in (
            ("greedy", 0.0, None), ("seeded-topk", 0.9, 5),
        ):
            # XLA compile freezes replica heartbeats: warm this sampling
            # regime's programs before any router with a 2s deadline
            InferenceEngine(model, v0_params, num_slots=3,
                            temperature=temperature, top_k=top_k).warmup()
            control, _e, ch, _c = fleet_run(requests_a, temperature, top_k)
            reference, _e2, _h2, _c2 = fleet_run(
                requests_a + requests_b, temperature, top_k,
                params=tuned, version=good,
            )
            swapped, engines, _h3, ctrl = fleet_run(
                requests_a, temperature, top_k, swap=True,
            )
            sm = swapped["metrics"]
            res = swapped["results"]
            versions_seen = {r["weights_version"] for r in res.values()}
            old_streams = [
                rid for rid, r in res.items()
                if r["weights_version"] == "v0"
            ]
            # streams that finished on the OLD weights (in flight while
            # the fleet rolled) must be bit-identical to the unswapped
            # control; streams admitted AFTER their replica swapped must
            # match the fine-tuned reference
            co_identical = all(
                res[rid]["status"] == "done"
                and control["results"][rid]["status"] == "done"
                and res[rid]["tokens"] == control["results"][rid]["tokens"]
                for rid in old_streams
            )
            new_match = all(
                res[rid]["status"] == "done"
                and res[rid]["tokens"]
                == reference["results"][rid]["tokens"]
                for rid, r in res.items()
                if r["weights_version"] == good
            )
            # pass B: fresh sessions on the SAME (now swapped) engines —
            # every one must carry the published version's tag and the
            # fine-tuned params' tokens
            handles_b = [
                ReplicaHandle(f"r{i}", e) for i, e in enumerate(engines)
            ]
            fresh = FleetRouter(handles_b, heartbeat_timeout_s=2.0).run(
                requests_b, timeout_s=120.0
            )
            fresh_on_new = all(
                r["status"] == "done"
                and r["weights_version"] == good
                and r["tokens"] == reference["results"][rid]["tokens"]
                for rid, r in fresh["results"].items()
            )
            # blackout gate: the pause→install→readmit window must cost
            # less than one decode boundary (p99 over the control run's
            # full-occupancy boundary costs; 5 ms floor absorbs host
            # timer jitter on a loaded box — the install is a pointer
            # swap, orders of magnitude under either bound)
            boundary_ms = sorted(
                s_per_row * 3 * 1e3
                for h in ch for (_t, s_per_row) in h.step_samples()
            )
            p99_ms = (
                boundary_ms[int(0.99 * (len(boundary_ms) - 1))]
                if boundary_ms else None
            )
            blackout = sm.get("swap_blackout_ms")
            blackout_ok = (
                blackout is not None
                and blackout <= max(p99_ms or 0.0, 5.0)
            )
            regime_ok = (
                ctrl.current_version == good
                and sm["weights_version"] == good
                and sm["swaps_completed"] == 1
                and versions_seen <= {"v0", good}
                and len(old_streams) >= 1
                and co_identical and new_match and fresh_on_new
                and blackout_ok
            )
            detail[regime] = {
                "swaps_completed": sm["swaps_completed"],
                "swap_rolls": sm["swap_rolls"],
                "swap_blackout_ms": blackout,
                "decode_boundary_p99_ms": p99_ms,
                "versions_seen": sorted(versions_seen),
                "old_version_streams": len(old_streams),
                "co_resident_bit_identical": co_identical,
                "post_swap_match_reference": new_match,
                "fresh_sessions_on_new_version": fresh_on_new,
            }
            ok = ok and regime_ok
    return {"ok": ok, "action": "drain-install-readmit", **detail}


SCENARIOS = {
    "nan-skip": lambda: scenario_poison_skip("nan-batch"),
    "inf-skip": lambda: scenario_poison_skip("inf-batch"),
    "budget-rollback": scenario_budget_rollback,
    "corrupt-latest": scenario_corrupt_latest,
    "truncate-shard": scenario_truncate_shard,
    "io-flake": scenario_io_flake,
    "rendezvous-flake": scenario_rendezvous_flake,
    "torn-save-kill": scenario_torn_save_kill,
    "sigint": scenario_sigint,
    "kill-slice": scenario_kill_slice,
    "poison-request": scenario_poison_request,
    "kill-replica-midstream": scenario_kill_replica_midstream,
    "corrupt-shard-midepoch": scenario_corrupt_shard_midepoch,
    "kill-decode-worker": scenario_kill_decode_worker,
    "hot-swap-midstream": scenario_hot_swap_midstream,
}
assert set(SCENARIOS) == set(ALL)


# -- child payloads (subprocess scenarios) --------------------------------

def _run_child(phase: str, ckpt_dir: str) -> int:
    _force_cpu_mesh()
    import distributed_pytorch_example_tpu as dpx

    mesh = dpx.runtime.make_mesh()
    loader = dpx.data.DeviceLoader(_dataset(), 64, mesh=mesh, seed=0)
    if phase == "torn-train":
        # sharded + frequent saves; the DPX_CHAOS kill fault SIGKILLs this
        # process mid-save on the save's second visit
        trainer = _make_trainer(
            ckpt_dir=ckpt_dir, mesh=mesh, checkpoint_format="sharded",
            save_every_steps=1,
        )
        trainer.fit(loader, epochs=3)
        return 1  # the kill fault should have fired; surviving is a FAIL
    if phase == "torn-resume":
        from distributed_pytorch_example_tpu.train import (
            checkpoint as ckpt_lib,
        )

        trainer = _make_trainer(
            ckpt_dir=ckpt_dir, mesh=mesh, checkpoint_format="sharded",
        )
        trainer.init(next(iter(loader))["x"])
        events = []
        _state, epoch, extra = ckpt_lib.load_checkpoint(
            os.path.join(ckpt_dir, ckpt_lib.LATEST_NAME),
            trainer.state, trainer.state_shardings,
            on_event=lambda kind, **f: events.append(kind),
        )
        print(json.dumps({
            "resumed_epoch": int(epoch),
            "batch_in_epoch": (extra or {}).get("batch_in_epoch"),
            "checkpoint_fallbacks": events.count("checkpoint_fallback"),
        }))
        return 0
    if phase == "sigint-train":
        trainer = _make_trainer(
            ckpt_dir=ckpt_dir, mesh=mesh, save_every_steps=1,
        )
        try:
            trainer.fit(loader, epochs=10_000)
        except dpx.train.PreemptionInterrupt as e:
            return e.exit_code
        return 1  # ran to completion without the signal: FAIL
    if phase in ("elastic-train", "elastic-resume", "elastic-control"):
        import jax

        from distributed_pytorch_example_tpu.train import (
            checkpoint as ckpt_lib,
        )

        if phase == "elastic-train":
            emesh = mesh  # the full 8-device world
        else:
            # the shrunken world: half the devices survived the preemption
            emesh = dpx.runtime.make_mesh(devices=jax.devices()[:4])
        eloader = dpx.data.DeviceLoader(_dataset(), 64, mesh=emesh, seed=0)
        trainer = _make_trainer(
            ckpt_dir=None if phase == "elastic-control" else ckpt_dir,
            mesh=emesh, checkpoint_format="sharded", save_every_steps=1,
            log_every=1,
        )
        if phase == "elastic-resume":
            trainer.fit(eloader, epochs=2, resume=os.path.join(
                ckpt_dir, ckpt_lib.LATEST_NAME
            ))
            return 0
        trainer.fit(eloader, epochs=2)
        # elastic-train must die at the kill fault; completing is a FAIL
        return 1 if phase == "elastic-train" else 0
    raise SystemExit(f"unknown child phase {phase!r}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--fast", action="store_true",
                        help=f"only the fast subset: {', '.join(FAST)}")
    parser.add_argument("--scenarios", default=None,
                        help="comma-separated subset (default: all)")
    parser.add_argument("--child", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--dir", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.child:
        return _run_child(args.child, args.dir)

    names = (
        args.scenarios.split(",") if args.scenarios
        else list(FAST if args.fast else ALL)
    )
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        parser.error(f"unknown scenario(s) {unknown}; choices: {list(ALL)}")

    _force_cpu_mesh()
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            report = SCENARIOS[name]()
        except Exception as e:  # noqa: BLE001 - a crash is a FAIL line
            report = {"ok": False, "action": "crashed", "error": repr(e)}
        report = {
            "scenario": name,
            **report,
            "elapsed_s": round(time.time() - t0, 2),
        }
        failures += 0 if report["ok"] else 1
        print(json.dumps(report), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
