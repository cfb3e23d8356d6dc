"""run.py end to end: it refuses to report without a chip, and with the look
for a chip skipped (inside the test only) a run at a tiny size ends in a
well-formed line."""

import json
import os
import subprocess
import sys
import time

import pytest

import harness
import tiny


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2-124m.train-s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "metrics" not in proc.stdout


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(SystemExit, match="no published peaks"):
        harness.find_chips(1)


def test_every_cell_resolves_to_its_files():
    bench = tiny.bench()
    for w in bench["workloads"]:
        cell, config, traffic, _ = harness.load_cell(w["name"])
        harness.load_module("drivers", traffic["driver"])
        harness.load_module("reference", config["reference"])
        limits = harness.load_json("limits", cell["name"] + ".json")
        assert all(0 < v < 1 for v in limits.values())
    for m in bench["per_layer"]:
        assert hasattr(harness.load_module("layer_metrics", m["name"]), "read")


@pytest.mark.parametrize("config", [tiny.GPT2, tiny.BERT], ids=["gpt2", "bert"])
def test_tiny_run_ends_in_a_well_formed_line(monkeypatch, capsys, config):
    """The rest of a run, with the real cell's limits: float32 on the CPU
    agrees with the plain reference far inside them."""
    import jax

    import run as run_py

    tiny.shrink_models(monkeypatch)
    name = "gpt2-124m.train-s1024" if config is tiny.GPT2 else "bert-base.train-s512"
    bench = tiny.bench()
    result, compared = run_py.run_cell(
        tiny.args(seed=2 ** 31 + 11),
        (tiny.cell(name), config, tiny.traffic(), bench),
        jax.devices()[:1], tiny.PEAK, harness.Clock(time.time()),
    )
    harness.print_result(result, compared)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert err.rstrip().splitlines()[-1].startswith("benchmark: compared ")
    for name_, entry in line["compared"].items():
        assert entry["value"] <= entry["limit"], name_
