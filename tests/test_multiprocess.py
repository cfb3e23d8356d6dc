"""True multi-process distributed training over localhost.

The TPU-native analogue of running the reference under ``torchrun
--nnodes=1 --nproc-per-node=2`` with gloo (SURVEY.md §4 "Multi-node without
a cluster"): two OS processes rendezvous through ``jax.distributed``
(runtime.initialize), each contributing one CPU device, and train with the
batch sharded across processes and params FSDP-sharded across processes —
exercising the real cross-process collective, metric-agreement, and
gathered-checkpoint paths that the fake single-process 8-device mesh cannot.

CPU-only by design, and not a chip command: the pytest parent has already
initialized JAX, and a chip belongs to one process at a time — so each
worker is held to the CPU platform through its environment.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_distributed_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_training(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            **os.environ,
            "NUM_PROCESSES": "2",
            "PROCESS_ID": str(pid),
            "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "DPX_TEST_CKPT_DIR": str(tmp_path),
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
        }
        env.pop("XLA_FLAGS", None)  # worker sets its own device count
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    results = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        results.append(json.loads(out.strip().splitlines()[-1]))

    # both processes saw the 2-device global mesh
    assert all(r["n_devices"] == 2 for r in results)
    # global metrics agree bit-for-bit across processes
    assert results[0]["train_loss"] == pytest.approx(results[1]["train_loss"])
    assert results[0]["val_loss"] == pytest.approx(results[1]["val_loss"])
    assert np.isfinite(results[0]["train_loss"])

    # graft-scope straggler telemetry: each process saw BOTH hosts' step
    # times via the boundary process_allgather, and derived the skew
    for r in results:
        straggler = r["straggler"]
        times = straggler["step_time_ms_per_host"]
        assert len(times) == 2 and all(t > 0 for t in times)
        assert straggler["step_time_ms_max_host"] >= (
            straggler["step_time_ms_median_host"]
        )
        assert straggler["step_time_skew"] >= 1.0
        assert isinstance(straggler.get("slow_hosts", []), list)
        assert r["grad_norm"] and np.isfinite(r["grad_norm"])
    assert results[0]["straggler"] == results[1]["straggler"]

    # at process_count > 1 the Trainer auto-selects the SHARDED format
    # (collective-free, async-safe): the pointer file + per-process shard
    # files must restore in THIS (single-process, different-topology)
    # interpreter via load_checkpoint's auto-detection
    ckpt = tmp_path / "latest_model.ckpt"
    assert ckpt.exists()
    from distributed_pytorch_example_tpu.train import checkpoint as _ck

    assert _ck._is_sharded(str(ckpt)), "multi-host save should be sharded"
    shard_dir = tmp_path / "latest_model.ckpt.shards"
    shard_files = [
        f for v in shard_dir.iterdir() for f in v.iterdir()
        if f.name.startswith("shard_")
    ]
    assert len(shard_files) == 2, "one shard file per process"

    import jax
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib
    from distributed_pytorch_example_tpu.train.step import init_state

    state, _ = init_state(
        dpx.models.SimpleNet(),
        optax.adam(1e-3),
        np.zeros((1, 784), np.float32),
        jax.random.key(0),
    )
    restored, epoch, extra = ckpt_lib.load_checkpoint(str(ckpt), state)
    assert epoch == 1
    assert int(restored.step) == 8  # 256 samples / 32 global batch = 8 steps
