"""graft-armor: fault injection, self-healing recovery, bounded retry.

The robustness contract (ISSUE 5), each clause pinned by a real
``Trainer.fit`` (or the exact library surface the Trainer drives) under a
seeded :mod:`robustness.chaos` fault plan:

- nonfinite batch ⇒ the update is predicated out DEVICE-side (params
  bit-frozen, no recompile), the skip is counted, and the trajectory is
  deterministic;
- skips exceeding ``max_bad_steps`` ⇒ ONE rollback to the last good
  checkpoint, a second exhaustion ⇒ :class:`BadStepBudgetExceeded`;
- corrupt/torn `latest` ⇒ ``load_checkpoint`` walks back to the newest
  intact ancestor (gathered history / older sharded version) and reports
  what it skipped; nothing intact ⇒ :class:`CheckpointCorruptError`;
- transient I/O and rendezvous failures ⇒ bounded deterministic
  exponential-backoff retries; persistent failures surface at the next
  submit()/check() boundary, not minutes later.

The sweep (scripts/chaos_sweep.py) re-runs the same matrix end-to-end as
subprocess scenarios; its fast subset rides tier-1 here and the full
matrix (SIGKILL torn-save, SIGINT) is ``-m slow``.
"""

import errno
import json
import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

import distributed_pytorch_example_tpu as dpx
from distributed_pytorch_example_tpu.data.synthetic import _ArrayDataset
from distributed_pytorch_example_tpu.models import SimpleNet
from distributed_pytorch_example_tpu.robustness import (
    BadStepBudgetExceeded,
    CheckpointCorruptError,
    chaos,
    retry,
)
from distributed_pytorch_example_tpu.robustness.integrity import (
    is_sealed,
    read_verified,
    seal,
    unseal,
)
from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test leaves the process chaos-free (module-global plan)."""
    yield
    chaos.uninstall()


def learnable_dataset(n=256, d=16, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d), dtype=np.float32)
    w = rng.standard_normal((d, classes), dtype=np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return _ArrayDataset({"x": x, "y": y})


def make_trainer(mesh, ckpt=None, **kw):
    return dpx.train.Trainer(
        SimpleNet(input_size=16, hidden_size=32, num_classes=4),
        dpx.train.ClassificationTask(),
        optax.adam(1e-2),
        partitioner=dpx.parallel.data_parallel(mesh),
        checkpoint_dir=ckpt,
        log_every=kw.pop("log_every", 2),
        **kw,
    )


def _loader(mesh):
    return dpx.data.DeviceLoader(learnable_dataset(), 64, mesh=mesh, seed=0)


def _digest(tree) -> bytes:
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp_is_key(leaf):
            continue
        h.update(np.asarray(leaf).tobytes())
    return h.digest()


def jnp_is_key(x):
    import jax.numpy as jnp

    return jnp.issubdtype(jnp.asarray(x).dtype, jax.dtypes.prng_key)


# ---------------------------------------------------------------------------
# retry: deterministic exponential backoff
# ---------------------------------------------------------------------------


def test_backoff_schedule_deterministic_and_capped():
    assert retry.backoff_schedule(4, 0.05, 2.0) == [0.05, 0.1, 0.2]
    assert retry.backoff_schedule(6, 1.0, 4.0) == [1.0, 2.0, 4.0, 4.0, 4.0]
    assert retry.backoff_schedule(1, 1.0, 4.0) == []


def test_with_retries_retries_then_succeeds():
    calls, slept = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(errno.EIO, "transient")
        return "ok"

    out = retry.with_retries(
        flaky, attempts=4, base_delay=0.5, retry_on=(OSError,),
        sleep=slept.append,
    )
    assert out == "ok" and len(calls) == 3
    assert slept == [0.5, 1.0]  # deterministic: replayable chaos runs


def test_with_retries_final_failure_propagates_unchanged():
    boom = OSError(errno.EIO, "persistent")

    def always():
        raise boom

    with pytest.raises(OSError) as ei:
        retry.with_retries(
            always, attempts=3, base_delay=0, retry_on=(OSError,),
            sleep=lambda _: None,
        )
    assert ei.value is boom


def test_with_retries_non_retryable_raises_immediately():
    calls = []

    def typed():
        calls.append(1)
        raise ValueError("config error, not transient")

    with pytest.raises(ValueError):
        retry.with_retries(
            typed, attempts=5, retry_on=(OSError,), sleep=lambda _: None
        )
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# chaos plan: seeded, serializable, env-installable
# ---------------------------------------------------------------------------


def test_plan_json_roundtrip_and_preset():
    plan = chaos.ChaosPlan(faults=[
        chaos.Fault("nan-batch", step=3),
        chaos.Fault("io-error", path_substr="latest", count=2),
    ], seed=7)
    back = chaos.ChaosPlan.from_json(plan.to_json())
    assert back.seed == 7 and len(back.faults) == 2
    assert back.faults[0].kind == "nan-batch" and back.faults[0].step == 3
    assert chaos.preset("nan-step").faults[0].kind == "nan-batch"
    assert chaos.preset("io-flake").faults[0].kind == "io-error"
    with pytest.raises(ValueError):
        chaos.Fault("frobnicate")
    with pytest.raises(ValueError, match="unknown chaos preset"):
        chaos.preset("no-such-preset")


def test_env_var_installs_plan(monkeypatch):
    plan = chaos.ChaosPlan(faults=[chaos.Fault("nan-batch", step=1)])
    monkeypatch.setenv(chaos.ENV_VAR, plan.to_json())
    chaos.uninstall()  # clears the plan AND the env-checked latch
    active = chaos.active()
    assert active is not None and active.faults[0].kind == "nan-batch"
    monkeypatch.setenv(chaos.ENV_VAR, "io-flake")  # preset-name form
    chaos.uninstall()
    assert chaos.active().faults[0].kind == "io-error"


# ---------------------------------------------------------------------------
# integrity envelope
# ---------------------------------------------------------------------------


def test_seal_unseal_roundtrip_and_legacy_passthrough():
    body = b"\x00\x01payload" * 100
    sealed = seal(body)
    assert is_sealed(sealed) and unseal(sealed, "t") == body
    # legacy (pre-r10, unsealed) files pass through unverified
    assert not is_sealed(body) and unseal(body, "t") == body


@pytest.mark.parametrize("mode", ["bitflip", "truncate"])
def test_corrupted_sealed_file_raises(tmp_path, mode):
    p = str(tmp_path / "f.bin")
    with open(p, "wb") as f:
        f.write(seal(b"x" * 4096))
    assert read_verified(p) == b"x" * 4096
    chaos.corrupt_file(p, mode=mode)
    with pytest.raises(CheckpointCorruptError):
        read_verified(p)


# ---------------------------------------------------------------------------
# AsyncSaver: failure surfaces at the boundary; transient OSError healed
# ---------------------------------------------------------------------------


def test_async_saver_failure_surfaces_at_next_submit():
    saver = ckpt_lib.AsyncSaver()

    def boom():
        raise RuntimeError("disk on fire")

    saver.submit(boom)
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        saver.submit(lambda: None)  # NEXT boundary, not silence
    saver.wait()  # error already consumed; saver is reusable
    done = []
    saver.submit(lambda: done.append(1))
    saver.wait()
    assert done == [1]


def test_async_saver_check_surfaces_without_new_submit():
    saver = ckpt_lib.AsyncSaver()

    def boom():
        raise RuntimeError("gone")

    saver.submit(boom)
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        for _ in range(100):  # per-step poll; must not need a new save
            saver.check()


def test_async_saver_heals_transient_oserror():
    saver = ckpt_lib.AsyncSaver(retry_base_delay=0.01)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(errno.EIO, "flake")

    saver.submit(flaky)
    saver.wait()  # no raise: healed
    assert len(calls) == 3 and saver.io_retries_used == 2


def test_async_saver_persistent_oserror_still_fails():
    saver = ckpt_lib.AsyncSaver(io_retries=1, retry_base_delay=0.0)

    def dead():
        raise OSError(errno.ENOSPC, "disk full")

    saver.submit(dead)
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        saver.wait()


# ---------------------------------------------------------------------------
# bad-step auto-recovery (real fit)
# ---------------------------------------------------------------------------


def test_nan_batch_skipped_params_frozen_no_recompile(devices):
    """The poisoned step leaves params bit-identical, fires the bad_step
    metric, and reuses the SAME compiled executable (no recompile)."""
    mesh = dpx.runtime.make_mesh()
    trainer = make_trainer(mesh)
    loader = _loader(mesh)
    batch = next(iter(loader))
    with mesh:
        trainer.init(batch["x"])
        step = trainer.train_step.lower(trainer.state, batch).compile()
        state1, m1 = step(trainer.state, batch)
        assert float(m1["bad_step"]) == 0.0
        before = _digest(state1.params)
        step1 = int(state1.step)  # read BEFORE donation deletes state1
        chaos.install(chaos.ChaosPlan(
            faults=[chaos.Fault("nan-batch", step=0)]
        ))
        poisoned = chaos.corrupt_batch(batch, 0)
        chaos.uninstall()
        # the SAME executable accepts the poisoned batch: the layout is
        # preserved by corrupt_batch, so nothing recompiles
        state2, m2 = step(state1, poisoned)
        assert float(m2["bad_step"]) == 1.0
        assert _digest(state2.params) == before  # update predicated out
        assert int(state2.step) == step1 + 1  # step advances regardless
        # and the next clean step trains normally
        state3, m3 = step(state2, batch)
        assert float(m3["bad_step"]) == 0.0
        assert _digest(state3.params) != before


def test_fit_counts_skips_and_keeps_training(devices):
    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(faults=[chaos.Fault("nan-batch", step=2)]))
    trainer = make_trainer(mesh)
    history = trainer.fit(_loader(mesh), epochs=2)
    assert trainer.recovery["bad_steps"] == 1
    assert trainer.recovery["rollbacks"] == 0
    assert np.isfinite(history[-1]["train_loss"])


def test_budget_rollback_then_hard_fail(tmp_path, devices):
    """Persistent NaN: one rollback to `latest`, then
    BadStepBudgetExceeded — never an unbounded skip loop."""
    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("nan-batch", step=2, count=10_000)]
    ))
    trainer = make_trainer(
        mesh, ckpt=str(tmp_path), log_every=1, max_bad_steps=1,
        save_every_steps=1,
    )
    with pytest.raises(BadStepBudgetExceeded, match="again after a rollback"):
        trainer.fit(_loader(mesh), epochs=3)
    assert trainer.recovery["rollbacks"] == 1
    assert trainer.recovery["bad_steps"] >= 2


def test_budget_without_checkpoint_fails_without_rollback(devices):
    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("nan-batch", step=0, count=10_000)]
    ))
    trainer = make_trainer(mesh, log_every=1, max_bad_steps=1)
    with pytest.raises(
        BadStepBudgetExceeded, match="no checkpoint to roll back to"
    ):
        trainer.fit(_loader(mesh), epochs=1)
    assert trainer.recovery["rollbacks"] == 0


def test_skip_nonfinite_false_restores_pre_r10_step(devices):
    """Opt-out: without predication a poisoned batch poisons params."""
    mesh = dpx.runtime.make_mesh()
    trainer = make_trainer(mesh, skip_nonfinite=False)
    loader = _loader(mesh)
    batch = next(iter(loader))
    with mesh:
        trainer.init(batch["x"])
        chaos.install(chaos.ChaosPlan(
            faults=[chaos.Fault("nan-batch", step=0)]
        ))
        poisoned = chaos.corrupt_batch(batch, 0)
        chaos.uninstall()
        state, metrics = trainer.train_step(trainer.state, poisoned)
        assert "bad_step" not in metrics
        # the NaN reaches the kernels (layer-1 bias grads are zeroed by
        # relu'(NaN) == 0, so not EVERY leaf is poisoned)
        leaves = [
            np.asarray(x) for x in jax.tree_util.tree_leaves(state.params)
        ]
        assert any(not np.isfinite(x).all() for x in leaves)


# ---------------------------------------------------------------------------
# checkpoint integrity: retention + fallback walk (real files)
# ---------------------------------------------------------------------------


def _gathered_run(tmp_path, mesh, epochs=3):
    trainer = make_trainer(mesh, ckpt=str(tmp_path))
    trainer.fit(_loader(mesh), epochs=epochs)
    return trainer, os.path.join(str(tmp_path), ckpt_lib.LATEST_NAME)


def test_gathered_retention_keeps_last_k(tmp_path, devices):
    mesh = dpx.runtime.make_mesh()
    _trainer, latest = _gathered_run(tmp_path, mesh, epochs=5)
    hist = ckpt_lib._gathered_history_paths(latest)
    assert len(hist) == ckpt_lib.DEFAULT_RETAIN
    # `latest` IS the newest history entry (hard link), not a 4th copy
    assert os.path.samefile(latest, hist[0])


def test_corrupt_latest_falls_back_to_intact_ancestor(tmp_path, devices):
    mesh = dpx.runtime.make_mesh()
    trainer, latest = _gathered_run(tmp_path, mesh)
    chaos.corrupt_file(latest, mode="bitflip", seed=1)
    events = []
    _state, epoch, _extra = ckpt_lib.load_checkpoint(
        latest, trainer.state, trainer.state_shardings,
        on_event=lambda kind, **f: events.append({"event": kind, **f}),
    )
    assert epoch == 2  # newest intact ancestor (epoch-3 copy was flipped)
    fb = [e for e in events if e["event"] == "checkpoint_fallback"]
    assert len(fb) == 1 and len(fb[0]["skipped"]) == 1
    assert "checksum mismatch" in fb[0]["skipped"][0]["reason"]


def test_all_candidates_corrupt_raises_listing_attempts(tmp_path, devices):
    mesh = dpx.runtime.make_mesh()
    trainer, latest = _gathered_run(tmp_path, mesh)
    for i, p in enumerate([latest] + ckpt_lib._gathered_history_paths(latest)):
        chaos.corrupt_file(p, mode="bitflip", seed=i)
    with pytest.raises(CheckpointCorruptError, match="no intact"):
        ckpt_lib.load_checkpoint(
            latest, trainer.state, trainer.state_shardings
        )


def test_fallback_disabled_raises_first_error(tmp_path, devices):
    mesh = dpx.runtime.make_mesh()
    trainer, latest = _gathered_run(tmp_path, mesh)
    chaos.corrupt_file(latest, mode="bitflip")
    with pytest.raises(CheckpointCorruptError, match="checksum mismatch"):
        ckpt_lib.load_checkpoint(
            latest, trainer.state, trainer.state_shardings, fallback=False
        )


def test_truncated_shard_falls_back_to_previous_version(tmp_path, devices):
    import glob

    mesh = dpx.runtime.make_mesh()
    trainer = make_trainer(
        mesh, ckpt=str(tmp_path), checkpoint_format="sharded"
    )
    trainer.fit(_loader(mesh), epochs=3)
    latest = os.path.join(str(tmp_path), ckpt_lib.LATEST_NAME)
    versions = sorted(glob.glob(os.path.join(f"{latest}.shards", "*")))
    assert len(versions) == ckpt_lib.DEFAULT_RETAIN  # keep-last-K GC
    shard = glob.glob(os.path.join(versions[-1], "shard_*.msgpack"))[0]
    chaos.corrupt_file(shard, mode="truncate")
    events = []
    _state, epoch, _extra = ckpt_lib.load_checkpoint(
        latest, trainer.state, trainer.state_shardings,
        on_event=lambda kind, **f: events.append(kind),
    )
    assert epoch == 2  # previous intact version (pointer said epoch 3)
    assert events.count("checkpoint_fallback") == 1


def test_corrupt_sharded_pointer_falls_back_to_version_scan(
    tmp_path, devices
):
    """A bit-flipped POINTER (not shard) still resolves: the version-dir
    scan finds the newest intact version without the pointer's help."""
    mesh = dpx.runtime.make_mesh()
    trainer = make_trainer(
        mesh, ckpt=str(tmp_path), checkpoint_format="sharded"
    )
    trainer.fit(_loader(mesh), epochs=2)
    latest = os.path.join(str(tmp_path), ckpt_lib.LATEST_NAME)
    with open(latest, "wb") as f:  # pointer destroyed entirely
        f.write(b"garbage that is neither magic nor msgpack")
    _state, epoch, _extra = ckpt_lib.load_checkpoint(
        latest, trainer.state, trainer.state_shardings
    )
    assert epoch == 2


def test_fit_resume_from_corrupt_latest_auto_falls_back(tmp_path, devices):
    """End-to-end acceptance: corrupt `latest`, rerun fit --resume, and
    training continues from the intact ancestor with the event counted."""
    mesh = dpx.runtime.make_mesh()
    _t, latest = _gathered_run(tmp_path, mesh)
    chaos.corrupt_file(latest, mode="bitflip")
    t2 = make_trainer(mesh, ckpt=str(tmp_path))
    history = t2.fit(_loader(mesh), epochs=4, resume=latest)
    assert t2.recovery["checkpoint_fallbacks"] == 1
    # resumed from the intact epoch-2 ancestor, so epochs 2..3 train
    assert [r["epoch"] for r in history] == [2, 3]


# ---------------------------------------------------------------------------
# transient I/O + rendezvous through the real paths
# ---------------------------------------------------------------------------


def test_fit_survives_transient_checkpoint_io_errors(tmp_path, devices):
    mesh = dpx.runtime.make_mesh()
    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("io-error", path_substr="latest", count=2)]
    ))
    trainer = make_trainer(mesh, ckpt=str(tmp_path), save_every_steps=2)
    trainer.fit(_loader(mesh), epochs=2)
    assert trainer._saver.io_retries_used >= 1
    assert os.path.exists(os.path.join(str(tmp_path), ckpt_lib.LATEST_NAME))


def test_rendezvous_retries_with_backoff(monkeypatch):
    from distributed_pytorch_example_tpu.runtime import distributed as dist

    fault = chaos.Fault("rendezvous-flake", count=2)
    chaos.install(chaos.ChaosPlan(faults=[fault]))
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setenv("DPX_RENDEZVOUS_BACKOFF", "0.01")
    dist.initialize()
    assert fault.fired == 2  # two flakes healed by the third attempt


def test_rendezvous_retries_exhausted_raises(monkeypatch):
    from distributed_pytorch_example_tpu.runtime import distributed as dist

    chaos.install(chaos.ChaosPlan(
        faults=[chaos.Fault("rendezvous-flake", count=100)]
    ))
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setenv("DPX_RENDEZVOUS_BACKOFF", "0.0")
    with pytest.raises(RuntimeError, match="chaos"):
        dist.initialize(max_attempts=3)


# ---------------------------------------------------------------------------
# the sweep harness itself
# ---------------------------------------------------------------------------


def _run_sweep(extra):
    env = dict(os.environ)
    env.pop("DPX_CHAOS", None)
    env["JAX_PLATFORMS"] = "cpu"  # the sweep is a CPU-mesh harness
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_sweep.py"),
         *extra],
        capture_output=True, text=True, cwd=REPO, timeout=900, env=env,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def test_chaos_sweep_fast_subset_green():
    proc, lines = _run_sweep(["--fast"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert [r["scenario"] for r in lines] == [
        "nan-skip", "corrupt-latest", "io-flake", "rendezvous-flake",
        "kill-slice", "poison-request", "kill-replica-midstream",
        "corrupt-shard-midepoch", "kill-decode-worker",
        "hot-swap-midstream",
    ]
    assert all(r["ok"] for r in lines), lines
    by_name = {r["scenario"]: r for r in lines}
    kill_slice = by_name["kill-slice"]
    assert kill_slice["action"] == "shrink-to-survivors-resume"
    assert kill_slice["max_loss_diff"] <= 1e-3 + 1e-4
    poison = by_name["poison-request"]
    assert poison["action"] == "evict-poisoned-request"
    assert poison["co_resident_bit_identical"] is True
    fleet = by_name["kill-replica-midstream"]
    assert fleet["action"] == "failover-replay"
    assert fleet["greedy"]["bit_identical_to_clean"] is True
    assert fleet["seeded-topk"]["replay_token_exact"] is True
    assert fleet["steady_state_ratio"] <= 1.05
    shard = by_name["corrupt-shard-midepoch"]
    assert shard["action"] == "quarantine-and-remap"
    assert shard["quarantined"] == [2]
    assert shard["max_loss_diff_vs_prequarantined_control"] == 0.0
    assert shard["params_match_control"] is True
    assert shard["steady_state_ratio"] <= 1.05
    decode = by_name["kill-decode-worker"]
    assert decode["action"] == "supervised-worker-restart"
    assert decode["worker_restarts"] >= 1
    assert decode["max_loss_diff_vs_uninjected"] == 0.0
    assert decode["params_match_uninjected"] is True
    swap = by_name["hot-swap-midstream"]
    assert swap["action"] == "drain-install-readmit"
    assert swap["channel_latest"] == swap["published_good"]
    for regime in ("greedy", "seeded-topk"):
        assert swap[regime]["swaps_completed"] == 1
        assert swap[regime]["co_resident_bit_identical"] is True
        assert swap[regime]["fresh_sessions_on_new_version"] is True
        assert swap[regime]["swap_blackout_ms"] is not None


@pytest.mark.slow
def test_chaos_sweep_full_matrix_green():
    proc, lines = _run_sweep([])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert all(r["ok"] for r in lines), lines
    actions = {r["scenario"]: r["action"] for r in lines}
    assert actions["torn-save-kill"] == "resume-from-intact-ancestor"
    assert actions["sigint"] == "checkpoint-and-exit-130"


# ---------------------------------------------------------------------------
# steady-state overhead of the predication (satellite 6)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_predication_overhead_within_budget(devices):
    """skip_nonfinite adds ≤2% to the compiled step (min-of-N; the ISSUE's
    ≤1% claim is one for the chip and is not measured since PR 22; the
    fixed host-side cost this fake CPU mesh amplifies is invisible there)."""
    import gc
    import time

    mesh = dpx.runtime.make_mesh()
    rng = np.random.default_rng(0)
    batch_np = {
        "x": rng.standard_normal((64, 784)).astype(np.float32),
        "y": rng.integers(0, 10, (64,)).astype(np.int32),
    }

    def compiled_step(skip):
        trainer = dpx.train.Trainer(
            dpx.models.SimpleNet(hidden_size=512),
            dpx.train.ClassificationTask(),
            optax.adam(1e-3),
            partitioner=dpx.parallel.data_parallel(mesh),
            telemetry=False,
            skip_nonfinite=skip,
        )
        sharding = trainer.partitioner.batch_sharding()
        batch = {
            k: jax.make_array_from_process_local_data(sharding, v)
            for k, v in batch_np.items()
        }
        trainer.init(batch["x"])
        return (
            trainer.train_step.lower(trainer.state, batch).compile(),
            trainer.state,
            batch,
        )

    n_steps, rounds = 15, 8

    def run(step, state, batch):
        holder = {"state": state}
        metrics = None
        for _ in range(5):
            holder["state"], metrics = step(holder["state"], batch)
        float(metrics["loss"])
        times = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                holder["state"], metrics = step(holder["state"], batch)
            float(metrics["loss"])
            times.append(time.perf_counter() - t0)
        return min(times)

    with mesh:
        step_off, state_off, batch = compiled_step(False)
        step_on, state_on, _ = compiled_step(True)
        gc.disable()
        try:
            t_off = run(step_off, state_off, batch)
            t_on = run(step_on, state_on, batch)
        finally:
            gc.enable()
    # 2% + a 15ms absolute floor (fake-mesh step times sit near host
    # timer jitter; same floor as the graft-scope overhead gate)
    assert t_on <= t_off * 1.02 + 0.015, (t_on, t_off)
