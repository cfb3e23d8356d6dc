"""Launcher contract tests: hostname→topology derivation (SURVEY.md §4).

Runs the real entrypoint.sh with a stub training script that dumps the env
it would hand to ``jax.distributed.initialize`` via resolve_config.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_pytorch_example_tpu.runtime.distributed import (
    derive_coordinator_address,
    derive_process_id,
    resolve_config,
)

ENTRYPOINT = os.path.join(
    os.path.dirname(__file__), "..",
    "distributed_pytorch_example_tpu", "launch", "entrypoint.sh",
)


def run_entrypoint(env_extra, tmp_path):
    stub = tmp_path / "stub.py"
    stub.write_text(
        "import json, os\n"
        "print(json.dumps({k: os.environ.get(k) for k in "
        "('PROCESS_ID', 'COORDINATOR_ADDRESS', 'REPLICAS')}))\n"
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        **env_extra,
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True, timeout=30
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_single_host_no_env_needed(tmp_path):
    out = run_entrypoint({}, tmp_path)
    assert out["REPLICAS"] == "1"
    assert out["PROCESS_ID"] is None  # resolve_config defaults to 0


def test_multi_host_derivation(tmp_path):
    out = run_entrypoint(
        {"REPLICAS": "4", "HOSTNAME": "trainer-3",
         "NF_DISCOVERY_SERVICE": "svc.ns.local"},
        tmp_path,
    )
    assert out["PROCESS_ID"] == "3"
    assert out["COORDINATOR_ADDRESS"] == "trainer-0.svc.ns.local:29500"


def test_multi_host_missing_discovery_fails_fast(tmp_path):
    stub = tmp_path / "stub.py"
    stub.write_text("print('should not run')\n")
    proc = subprocess.run(
        ["bash", ENTRYPOINT],
        env={"PATH": os.environ["PATH"], "REPLICAS": "2",
             "TRAINING_SCRIPT": str(stub), "HOSTNAME": "x-1"},
        capture_output=True, text=True, timeout=30,
    )
    assert proc.returncode == 1
    assert "NF_DISCOVERY_SERVICE" in proc.stderr


def test_non_numeric_hostname_fails_fast(tmp_path):
    proc = subprocess.run(
        ["bash", ENTRYPOINT],
        env={"PATH": os.environ["PATH"], "REPLICAS": "2",
             "NF_DISCOVERY_SERVICE": "svc", "HOSTNAME": "nosuffix",
             "TRAINING_SCRIPT": "unused.py"},
        capture_output=True, text=True, timeout=30,
    )
    assert proc.returncode == 1
    assert "PROCESS_ID" in proc.stderr


def test_python_side_derivation_matches_shell():
    """resolve_config derives the same topology as entrypoint.sh."""
    assert derive_process_id("worker-7") == 7
    assert derive_process_id("nosuffix") == 0
    assert (
        derive_coordinator_address("myjob-3", "svc", 29500)
        == "myjob-0.svc:29500"
    )
    cfg = resolve_config(
        {"REPLICAS": "4", "HOSTNAME": "myjob-2", "NF_DISCOVERY_SERVICE": "svc"}
    )
    assert cfg.process_id == 2
    assert cfg.num_processes == 4
    assert cfg.coordinator_address == "myjob-0.svc:29500"


def test_custom_port(tmp_path):
    out = run_entrypoint(
        {"REPLICAS": "2", "HOSTNAME": "w-1", "NF_DISCOVERY_SERVICE": "d",
         "COORDINATOR_PORT": "12345"},
        tmp_path,
    )
    assert out["COORDINATOR_ADDRESS"] == "w-0.d:12345"


def test_max_restarts_resumes_after_crash(tmp_path):
    """MAX_RESTARTS: a crashing script is relaunched with --resume
    <CHECKPOINT_DIR>/latest_model.ckpt appended; success stops the loop."""
    stub = tmp_path / "stub.py"
    marker = tmp_path / "attempts"
    stub.write_text(
        "import pathlib, sys\n"
        f"m = pathlib.Path({str(marker)!r})\n"
        "n = int(m.read_text()) if m.exists() else 0\n"
        "m.write_text(str(n + 1))\n"
        "print('ARGS:' + ' '.join(sys.argv[1:]))\n"
        "sys.exit(1 if n < 2 else 0)\n"  # crash twice, then succeed
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "SCRIPT_ARGS": "--epochs 5",
        "MAX_RESTARTS": "3",
        "CHECKPOINT_DIR": "/ck",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    args_lines = [
        l for l in proc.stdout.splitlines() if l.startswith("ARGS:")
    ]
    assert args_lines[0] == "ARGS:--epochs 5"  # first run: no resume
    assert args_lines[1] == "ARGS:--epochs 5 --resume /ck/latest_model.ckpt"
    assert args_lines[2] == "ARGS:--epochs 5 --resume /ck/latest_model.ckpt"
    assert marker.read_text() == "3"
    assert proc.stderr.count("WARN: training exited") == 2


def test_max_restarts_exhausted_fails_with_last_rc(tmp_path):
    stub = tmp_path / "stub.py"
    stub.write_text("import sys; sys.exit(7)\n")
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "MAX_RESTARTS": "2",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 7
    assert "giving up" in proc.stderr
    assert proc.stderr.count("WARN: training exited") == 2


def test_restart_resume_dir_follows_script_args(tmp_path):
    """--checkpoint-dir inside SCRIPT_ARGS wins over $CHECKPOINT_DIR, so
    the retry resumes from where the trainer actually writes."""
    stub = tmp_path / "stub.py"
    marker = tmp_path / "attempts"
    stub.write_text(
        "import pathlib, sys\n"
        f"m = pathlib.Path({str(marker)!r})\n"
        "n = int(m.read_text()) if m.exists() else 0\n"
        "m.write_text(str(n + 1))\n"
        "print('ARGS:' + ' '.join(sys.argv[1:]))\n"
        "sys.exit(1 if n < 1 else 0)\n"
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "SCRIPT_ARGS": "--checkpoint-dir /mnt/ckpt --epochs 9",
        "MAX_RESTARTS": "2",
        "CHECKPOINT_DIR": "/wrong",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    args_lines = [
        l for l in proc.stdout.splitlines() if l.startswith("ARGS:")
    ]
    assert args_lines[1].endswith("--resume /mnt/ckpt/latest_model.ckpt")
    assert "/wrong" not in proc.stdout


def test_restart_loop_does_not_fight_signals(tmp_path):
    """A child killed by an ORCHESTRATOR signal (TERM/INT/HUP) must NOT be
    restarted — the platform is tearing the pod down."""
    stub = tmp_path / "stub.py"
    stub.write_text(
        "import os, signal\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "MAX_RESTARTS": "3",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode > 128
    assert "not restarting" in proc.stderr
    assert "WARN: training exited" not in proc.stderr


def test_restart_loop_recovers_crash_signals(tmp_path):
    """Crash-by-signal (OOM-kill 137, SIGSEGV 139) IS restarted — these are
    exactly the failures MAX_RESTARTS exists to recover; only orchestrator
    teardown signals (HUP/INT/TERM) are exempt."""
    stub = tmp_path / "stub.py"
    marker = tmp_path / "attempts"
    stub.write_text(
        "import os, pathlib, signal, sys\n"
        f"m = pathlib.Path({str(marker)!r})\n"
        "n = int(m.read_text()) if m.exists() else 0\n"
        "m.write_text(str(n + 1))\n"
        "if n == 0:\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"  # rc 137, like OOM
        "sys.exit(0)\n"
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "MAX_RESTARTS": "2",
        "CHECKPOINT_DIR": "/ck",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert marker.read_text() == "2"
    assert "restart 1/2" in proc.stderr


def test_restart_resume_dir_equals_form(tmp_path):
    """--checkpoint-dir=PATH (argparse's '=' spelling) is parsed too."""
    stub = tmp_path / "stub.py"
    marker = tmp_path / "attempts"
    stub.write_text(
        "import pathlib, sys\n"
        f"m = pathlib.Path({str(marker)!r})\n"
        "n = int(m.read_text()) if m.exists() else 0\n"
        "m.write_text(str(n + 1))\n"
        "print('ARGS:' + ' '.join(sys.argv[1:]))\n"
        "sys.exit(1 if n < 1 else 0)\n"
    )
    env = {
        "PATH": os.environ["PATH"],
        "TRAINING_SCRIPT": str(stub),
        "SCRIPT_ARGS": "--checkpoint-dir=/mnt/eq --epochs 9",
        "MAX_RESTARTS": "2",
    }
    proc = subprocess.run(
        ["bash", ENTRYPOINT], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    args_lines = [
        l for l in proc.stdout.splitlines() if l.startswith("ARGS:")
    ]
    assert args_lines[1].endswith("--resume /mnt/eq/latest_model.ckpt")


@pytest.mark.slow
def test_sigterm_graceful_preemption_checkpoint(tmp_path):
    """Graceful preemption (VERDICT r4 ask #4): SIGTERM mid-epoch finishes
    the in-flight step, writes `latest` with the loader cursor, exits with
    the teardown rc 143 (launcher does NOT restart, entrypoint.sh:133-141),
    and a relaunch resumes from that exact batch."""
    import re
    import signal
    import time

    repo = os.path.join(os.path.dirname(__file__), "..")
    ckpt_dir = str(tmp_path / "ck")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    args = [
        sys.executable, os.path.join(repo, "train.py"),
        "--epochs", "2", "--num-samples", "12800", "--batch-size", "2",
        "--log-every", "1", "--seed", "7", "--checkpoint-dir", ckpt_dir,
    ]
    victim = subprocess.Popen(
        args, stderr=subprocess.PIPE, text=True, env=env, cwd=repo
    )
    import threading

    loss_re = re.compile(r"Epoch (\d+), Batch (\d+)/\d+, Loss")
    # watchdog: a wedged victim that stops logging would block the pipe
    # read forever (tail below); kill it so the test fails loudly instead
    watchdog = threading.Timer(600, victim.kill)
    watchdog.start()
    try:
        for line in victim.stderr:
            m = loss_re.search(line)
            if m and int(m.group(2)) >= 3:
                break
        else:
            raise AssertionError("victim exited/wedged before batch 3")
    finally:
        watchdog.cancel()
    victim.send_signal(signal.SIGTERM)
    rest = victim.stderr.read()
    rc = victim.wait(timeout=300)

    assert rc == 143, (rc, rest[-2000:])
    m = re.search(
        r"Preemption checkpoint complete \(epoch (\d+), batch (\d+)\)", rest
    )
    assert m, rest[-2000:]
    saved = (int(m.group(1)), int(m.group(2)))
    ckpt = os.path.join(ckpt_dir, "latest_model.ckpt")
    assert os.path.exists(ckpt)

    # relaunch resumes at the exact saved cursor (--epochs 1 keeps the
    # rerun to the remainder of epoch 0)
    proc = subprocess.run(
        [*args, "--resume", ckpt, "--epochs", "1"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    m2 = re.search(r"Resuming epoch (\d+) at batch (\d+)/\d+", proc.stderr)
    assert m2, proc.stderr[-2000:]
    assert (int(m2.group(1)), int(m2.group(2))) == saved
