"""run.py end to end: it refuses to report without a chip, and with the look
for a chip skipped (inside the test only) a run at a tiny size ends in a
well-formed line; and a count of the program's that the configuration holds
to zero (``exact_zero``) decides ``correct`` with the rest."""

import json
import os
import subprocess
import sys
import time

import pytest

import harness
import tiny
from drivers import train_window


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
    # three gaps with a limit and three exact counts: no `exact_zero` key
    # in the configuration, no further entry
    assert len(line["compared"]) == 6
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert err.rstrip().splitlines()[-1].startswith("benchmark: compared ")
    for name_, entry in line["compared"].items():
        assert entry["value"] <= entry["limit"], name_


# -- counts of the program's own, held to zero (``exact_zero``) ---------------

CLEAN = [{"train_loss": 6.2, "dropped": 0.0}, {"train_loss": 6.1, "dropped": 0}]


@pytest.mark.parametrize(
    "config,records,want",
    [
        ({}, CLEAN, {}),
        ({"exact_zero": []}, CLEAN, {}),
        ({"exact_zero": ["dropped"]}, CLEAN, {"dropped": (0.0, 0)}),
        ({"exact_zero": ["dropped"]}, CLEAN + [{"dropped": 3}, {"dropped": 1}],
         {"dropped": (3.0, 0)}),
        ({"exact_zero": ["dropped", "train_loss"]}, CLEAN,
         {"dropped": (0.0, 0), "train_loss": (6.2, 0)}),
    ],
    ids=["no-key", "empty", "zero-in-every-record", "non-zero-in-one", "two-names"],
)
def test_held_to_zero_entries(config, records, want):
    assert train_window.held_to_zero(config, records) == want


def test_held_to_zero_takes_a_nan_for_the_worst():
    records = CLEAN + [{"dropped": float("nan")}, {"dropped": 2}]
    value, limit = train_window.held_to_zero(
        {"exact_zero": ["dropped"]}, records
    )["dropped"]
    assert value != value and limit == 0 and not value <= limit


def test_held_to_zero_exits_where_a_record_lacks_the_name():
    with pytest.raises(SystemExit, match=r"'dropped'.*\[1\] of 3"):
        train_window.held_to_zero(
            {"exact_zero": ["dropped"]}, [CLEAN[0], {"train_loss": 6.0}, CLEAN[1]]
        )


def test_a_count_held_to_zero_that_is_not_zero_is_not_correct(monkeypatch):
    """Through the whole of a run: ``train_loss`` stands in for a count the
    program reports in every epoch record, and it is never zero."""
    import jax

    import run as run_py

    tiny.shrink_models(monkeypatch)
    config = {**tiny.GPT2, "exact_zero": ["train_loss"]}
    result, compared = run_py.run_cell(
        tiny.args(seed=2 ** 31 + 12),
        (tiny.cell("gpt2-124m.train-s1024"), config, tiny.traffic(), tiny.bench()),
        jax.devices()[:1], tiny.PEAK, harness.Clock(time.time()),
    )
    assert result["correct"] is False
    failed = [k for k, (v, lim) in compared.items() if not v <= lim]
    assert failed == ["train_loss"]
    value, limit = compared["train_loss"]
    assert value > 5 and limit == 0
    # the largest over the three compared calls, the warm-up and the window
    assert list(compared)[-1] == "train_loss" and len(compared) == 7
