"""Step-level resume: kill mid-epoch, restart at the exact batch.

Beyond-reference capability (the reference resumes at epoch granularity,
train.py:256-257): ``--save-every-steps`` checkpoints carry the loader
cursor (epoch, batch_in_epoch), and resume skips to that batch. The
determinism contract that makes this PROVABLE: the sampler permutation is
a pure function of (seed, epoch) (data/sampler.py), and the per-step rng
folds the checkpointed ``state.rng`` with the checkpointed ``state.step``
(train/step.py) — so a SIGKILLed-and-resumed run's per-batch losses must
equal an uninterrupted control's exactly.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 800 steps/epoch so the victim is reliably mid-epoch when the SIGKILL
# lands (a tiny run finishes before the signal can be delivered)
BASE_ARGS = [
    "--epochs", "2", "--num-samples", "12800", "--batch-size", "2",
    "--log-every", "1", "--seed", "5", "--lr", "0.01",
]

LOSS_RE = re.compile(r"Epoch (\d+), Batch (\d+)/\d+, Loss: ([0-9.]+)")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    return env


def _losses(stderr: str) -> dict:
    """{(epoch, batch): 'loss string'} from --log-every 1 output."""
    return {
        (int(m.group(1)), int(m.group(2))): m.group(3)
        for m in LOSS_RE.finditer(stderr)
    }


def _run(args, timeout=600):
    # one retry on crash-by-signal BEFORE any training step logged: under
    # a full-suite run on the 1-core box the spawned interpreter
    # occasionally SIGABRTs in XLA thread teardown before training starts
    # (observed once in ~10 suite runs; passes in isolation). The no-Loss
    # guard keeps the retry from re-running a --resume invocation whose
    # first attempt already trained past the mid-epoch checkpoint (which
    # would silently degrade this test to epoch-boundary resume). A real
    # trainer bug exits nonzero (no retry) or aborts repeatably.
    for attempt in (0, 1):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "train.py"), *args],
            capture_output=True, text=True, env=_env(), cwd=REPO,
            timeout=timeout,
        )
        if proc.returncode >= 0 or "Loss:" in proc.stderr or attempt:
            break
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stderr


@pytest.mark.slow
def test_sigkill_mid_epoch_resumes_bit_identical(tmp_path):
    ctrl_dir, vict_dir = str(tmp_path / "ctrl"), str(tmp_path / "vict")

    # 1. uninterrupted control
    ctrl_err = _run([*BASE_ARGS, "--checkpoint-dir", ctrl_dir])
    ctrl = _losses(ctrl_err)
    assert (0, 0) in ctrl and (1, 799) in ctrl  # 800 batches x 2 epochs

    # 2. victim: per-step checkpoints, SIGKILLed once batch 3 of epoch 0
    # has run (so `latest` carries a mid-epoch cursor)
    victim = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "train.py"), *BASE_ARGS,
            "--checkpoint-dir", vict_dir, "--save-every-steps", "1",
        ],
        stderr=subprocess.PIPE, text=True, env=_env(), cwd=REPO,
    )
    import threading

    seen = []
    # watchdog: a wedged victim that stops logging would block the pipe
    # read forever; killing it closes the pipe and fails the test loudly
    watchdog = threading.Timer(600, victim.kill)
    watchdog.start()
    try:
        for line in victim.stderr:
            seen.append(line)
            m = LOSS_RE.search(line)
            if m and (int(m.group(1)), int(m.group(2))) >= (0, 3):
                break
        else:
            raise AssertionError(
                "victim exited/wedged before batch 3:\n" + "".join(seen[-30:])
            )
    finally:
        watchdog.cancel()
    # no settling sleep: dozens of async per-step saves have landed by now
    victim.send_signal(signal.SIGKILL)
    victim.wait(timeout=60)
    victim.stderr.close()

    ckpt = os.path.join(vict_dir, "latest_model.ckpt")
    assert os.path.exists(ckpt), "no mid-epoch checkpoint survived the kill"

    # 3. resume: must restart MID-epoch at the checkpointed cursor
    res_err = _run(
        [*BASE_ARGS, "--checkpoint-dir", vict_dir, "--resume", ckpt]
    )
    m = re.search(r"Resuming epoch (\d+) at batch (\d+)/800", res_err)
    assert m, res_err[-2000:]
    resume_at = (int(m.group(1)), int(m.group(2)))
    assert (0, 1) <= resume_at <= (1, 799)

    # 4. bit-identical trajectory: every post-resume (epoch, batch) loss
    # equals the control's, and the pre-kill victim losses do too
    res = _losses(res_err)
    expected = {k: v for k, v in ctrl.items() if k >= resume_at}
    assert expected, "control produced no comparable steps"
    for key, loss in expected.items():
        assert res.get(key) == loss, (
            f"loss diverged at {key}: resumed {res.get(key)} != control {loss}"
        )
    vict = _losses("".join(seen))
    for key, loss in vict.items():
        assert ctrl[key] == loss, f"victim diverged at {key} pre-kill"

    # 5. final state equality: metrics.jsonl last epoch records match the
    # control exactly (full-precision floats)
    def last_record(d):
        with open(os.path.join(d, "metrics.jsonl")) as f:
            return json.loads(f.readlines()[-1])

    ctrl_rec, res_rec = last_record(ctrl_dir), last_record(vict_dir)
    for k in ("epoch", "val_loss", "val_accuracy"):
        assert ctrl_rec[k] == res_rec[k], (k, ctrl_rec[k], res_rec[k])


@pytest.mark.slow
def test_torn_sharded_save_resumes_previous_intact_checkpoint(tmp_path):
    """SIGKILL between the shard writes and the manifest/pointer flip
    (graft-armor chaos crash point): the torn version is never committed,
    so the pointer still names the previous intact version and resume
    lands on it — no operator intervention, no fallback walk needed."""
    ckdir = str(tmp_path / "ck")
    args = [
        "--epochs", "1", "--num-samples", "640", "--batch-size", "2",
        "--log-every", "1", "--seed", "5", "--checkpoint-dir", ckdir,
        "--checkpoint-format", "sharded", "--save-every-steps", "1",
    ]
    plan = json.dumps({"faults": [
        {"kind": "kill", "at": "sharded-save:post-shards", "nth": 3},
    ]})
    victim = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), *args,
         "--chaos", plan],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=600,
    )
    assert victim.returncode == -signal.SIGKILL, victim.stderr[-2000:]

    # saves 1 and 2 committed; save 3 died post-shards: its version dir
    # has shard files but no manifest, and the pointer still names save 2
    latest = os.path.join(ckdir, "latest_model.ckpt")
    assert os.path.isfile(latest)
    versions = sorted(os.listdir(latest + ".shards"))
    assert len(versions) == 3, versions
    torn = os.path.join(latest + ".shards", versions[-1])
    assert not os.path.exists(os.path.join(torn, "manifest.msgpack"))

    res_err = _run([*args, "--resume", latest])
    m = re.search(r"Resuming epoch (\d+) at batch (\d+)/40", res_err)
    assert m, res_err[-2000:]
    # batch 2 = the second (last intact) mid-epoch save's cursor
    assert (int(m.group(1)), int(m.group(2))) == (0, 2)


def test_torn_publish_sigkill_keeps_pointer_and_heals(tmp_path):
    """SIGKILL between the publish-channel artifact write and the LATEST
    pointer flip (graft-swap's torn window, robustness/publish.py): the
    torn version must stay invisible to readers — the pointer still
    names v1, so a polling fleet keeps serving it — and the next
    successful publish flips the pointer past the leftover, restoring
    the channel to fully healthy."""
    from distributed_pytorch_example_tpu.robustness.publish import (
        PublishChannel,
    )

    root = str(tmp_path / "chan")
    child = (
        "import sys\n"
        "from distributed_pytorch_example_tpu.robustness.publish import (\n"
        "    PublishChannel,\n"
        ")\n"
        "ch = PublishChannel(sys.argv[1])\n"
        "ch.publish_blob(b'payload-v1')\n"
        "ch.publish_blob(b'payload-v2')  # SIGKILLed before pointer flip\n"
        "print('UNREACHABLE')\n"
    )
    env = _env()
    env["DPX_CHAOS"] = json.dumps(
        {"faults": [{"kind": "torn-publish", "nth": 2}]}
    )
    proc = subprocess.run(
        [sys.executable, "-c", child, root],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stderr[-2000:]
    )
    assert "UNREACHABLE" not in proc.stdout

    # the torn version's artifact landed on disk, but the commit point
    # (the pointer flip) never happened: readers cannot see it
    ch = PublishChannel(root)
    assert ch.versions() == ["00000001", "00000002"]
    assert os.path.exists(ch.artifact_path("00000002"))
    assert ch.pointer_version() == "00000001"
    assert ch.latest() == "00000001"
    assert ch.read("00000001") == b"payload-v1"
    state = ch.state()
    # torn-but-uncommitted leftovers do not even degrade the channel
    assert state["ok"] is True
    assert state["latest_intact"] == "00000001"
    assert [v["committed"] for v in state["versions"]] == [True, False]

    # the next publish numbers PAST the leftover and flips the pointer:
    # the channel is healthy again with no operator intervention
    healed = ch.publish_blob(b"payload-v3")
    assert healed == "00000003"
    assert ch.pointer_version() == "00000003"
    assert ch.latest() == "00000003"
    assert ch.state()["ok"] is True


def test_iter_from_matches_tail_of_full_iteration(devices):
    """loader.iter_from(k) yields exactly the batches a full iteration
    yields from step k on (the cursor contract resume relies on)."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticClassificationDataset,
    )

    ds = SyntheticClassificationDataset(num_samples=40)
    loader = DeviceLoader(ds, 8, num_shards=1, shard_id=0, seed=3)
    loader.set_epoch(2)
    full = [
        {k: np.asarray(v) for k, v in b.items()} for b in iter(loader)
    ]
    loader.set_epoch(2)
    tail = [
        {k: np.asarray(v) for k, v in b.items()} for b in loader.iter_from(2)
    ]
    assert len(tail) == len(full) - 2
    for a, b in zip(full[2:], tail):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    with pytest.raises(ValueError, match="start_step"):
        list(loader.iter_from(len(loader) + 1))


# ---------------------------------------------------------------------------
# graft-intake mid-epoch resume matrix: exact global sample sequence —
# no repeat, no skip — across prefetch, quarantine, and elastic reshape
# ---------------------------------------------------------------------------


class _RecordingDataset:
    """Map-style dataset whose batches ARE the served sample indices, so a
    test can read the exact global sample sequence off the batch stream."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def get_batch(self, indices):
        idx = np.asarray(indices, np.int64)
        return {
            "x": idx.astype(np.float32).reshape(-1, 1),
            "y": idx.astype(np.int32),
        }


def _served(batches):
    """Per-step served global sample ids from a batch stream."""
    return [np.sort(np.asarray(b["y"]).reshape(-1)) for b in batches]


def test_resume_non_prefetch_aligned_start_with_prefetch(devices):
    """iter_from at a cursor that is NOT a multiple of the prefetch depth
    must still yield exactly the uninterrupted tail — the supervised
    worker's start cursor is the consumer cursor, not a queue boundary."""
    import threading

    from distributed_pytorch_example_tpu.data.loader import DeviceLoader

    ds = _RecordingDataset(64)
    loader = DeviceLoader(ds, 8, num_shards=1, shard_id=0, seed=9,
                          prefetch=3)
    loader.set_epoch(4)
    full = _served(iter(loader))
    loader.set_epoch(4)
    tail = _served(loader.iter_from(5))  # 5 % 3 != 0: mid-queue cursor
    assert len(tail) == len(full) - 5
    for a, b in zip(full[5:], tail):
        np.testing.assert_array_equal(a, b)
    # both iterations closed their supervised workers: no leaked threads
    assert not [
        t for t in threading.enumerate()
        if t.name.startswith("intake-") and t.is_alive()
    ]


def test_resume_with_quarantined_shard_via_loader_manifest(tmp_path, devices):
    """A checkpoint stamped after a quarantine must resume onto the SAME
    remapped sample stream: restore re-arms the quarantine set before the
    first batch, so the tail equals a control that trained with the shard
    quarantined from the start."""
    from distributed_pytorch_example_tpu.data import intake
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.streaming import (
        StreamingImageShards,
        write_image_shards,
    )

    root = str(tmp_path / "shards")
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (128, 4, 4, 3)).astype(np.uint8)
    labels = rng.integers(0, 9, 128).astype(np.int64)
    write_image_shards(root, [(imgs, labels)], shard_size=32, seal=True)

    def make_loader(quarantine):
        ds = StreamingImageShards(root)
        if quarantine:
            ds.quarantine(quarantine, reason="test")
        loader = DeviceLoader(ds, 16, shuffle=True, seed=3, prefetch=2,
                              num_shards=1, shard_id=0)
        loader.set_epoch(1)
        return ds, loader

    # control: shard 1 quarantined from the very start of the epoch
    _, control = make_loader([1])
    ctrl_batches = [
        {k: np.asarray(v) for k, v in b.items()} for b in iter(control)
    ]

    # "crashed" run stamped a manifest at batch 5 with shard 1 quarantined
    man_ds, man_loader = make_loader([1])
    man = intake.loader_manifest(man_loader, epoch=1, batch_in_epoch=5)
    assert man["quarantine"] == [1]

    # resume: FRESH dataset (no quarantine knowledge) + manifest restore
    fresh_ds, fresh = make_loader([])
    cursor = intake.restore_loader_state(fresh, man)
    assert cursor == 5 and fresh_ds.quarantined_shards == {1}
    for got, want in zip(fresh.iter_from(cursor), ctrl_batches[5:]):
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_elastic_dp8_to_dp4_resume_exact_global_sequence(devices):
    """Kill a dp8 run mid-epoch, resume on dp4: the combined pre-kill and
    post-resume global batches must serve every sample EXACTLY once, in
    the same per-step global order an uninterrupted run serves — the
    loader_manifest cursor is in global-batch steps, so it transfers
    across the reshape unchanged."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader

    n, gbs, seed, epoch, cut = 128, 16, 7, 2, 3
    ds = _RecordingDataset(n)

    def shard_loaders(num_shards):
        loaders = []
        for sid in range(num_shards):
            ld = DeviceLoader(ds, gbs, num_shards=num_shards, shard_id=sid,
                              seed=seed, prefetch=2)
            ld.set_epoch(epoch)
            loaders.append(ld)
        return loaders

    # uninterrupted single-process control: per-step global sample sets
    control = shard_loaders(1)[0]
    ctrl = _served(iter(control))
    assert len(ctrl) == n // gbs

    # dp8 "run" serves global steps [0, cut); the kill lands there
    pre = [_served(ld.iter_from(0)) for ld in shard_loaders(8)]
    # dp4 resume serves global steps [cut, end) from the stamped cursor
    post = [_served(ld.iter_from(cut)) for ld in shard_loaders(4)]

    served = []
    for step in range(cut):
        served.append(np.sort(np.concatenate(
            [pre[sid][step] for sid in range(8)]
        )))
    for step in range(len(ctrl) - cut):
        served.append(np.sort(np.concatenate(
            [post[sid][step] for sid in range(4)]
        )))

    # same per-step global batch as the uninterrupted control...
    for step, (got, want) in enumerate(zip(served, ctrl)):
        np.testing.assert_array_equal(got, want, err_msg=f"step {step}")
    # ...and the epoch as a whole repeats no sample and skips none
    all_served = np.sort(np.concatenate(served))
    np.testing.assert_array_equal(all_served, np.arange(n))
