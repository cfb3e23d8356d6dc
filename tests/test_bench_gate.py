"""scripts/bench_gate.py: the perf-regression gate must actually gate.

Round 3 shipped a 29% ViT regression that nothing caught (VERDICT r3 #1);
the gate exists to make that impossible, so its failure semantics are
pinned here: throughput drops fail, errored models fail, new/missing
models don't, config drift is surfaced, and both payload formats (driver
wrapper with 'parsed'/'tail', raw bench stdout) parse.
"""

import json
import os
import subprocess
import sys

import pytest

GATE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts", "bench_gate.py",
)


def _model(name, value, unit="samples/sec/chip", config=None, error=None):
    if error is not None:
        return {"error": error}
    entry = {
        "metric": f"{name.replace('-', '_')}_samples_per_sec_per_chip",
        "value": value,
        "unit": unit,
    }
    if config:
        entry["config"] = config
    return entry


def _payload(models):
    first = next(v for v in models.values() if "error" not in v)
    return {**first, "models": models}


def _run_gate(prev, cur, tmp_path, extra=()):
    prev_path = tmp_path / "prev.json"
    prev_path.write_text(json.dumps(prev))
    # --noise '' / --scaling '' keep these hermetic: without them the
    # gate auto-discovers the repo's committed results/bench_noise and
    # results/scaling artifacts and these fixture models would pick up
    # the real per-model tolerances and curves
    proc = subprocess.run(
        [sys.executable, GATE, "--prev", str(prev_path), "--noise", "",
         "--scaling", "", *extra],
        input=json.dumps(cur), capture_output=True, text=True, timeout=120,
    )
    return proc.returncode, proc.stderr


def test_ok_within_tolerance(tmp_path):
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 980.0)})  # -2%
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 0, err
    assert "OK" in err


def test_regression_fails(tmp_path):
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 900.0)})  # -10%
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 1
    assert "REGRESSION" in err


def test_errored_model_fails(tmp_path):
    """A model that CRASHES must fail the gate, not read as 'missing'."""
    prev = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "vit-b16": _model("vit-b16", 990.0),
    })
    cur = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "vit-b16": _model("vit-b16", 0, error="compile exploded"),
    })
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 1
    assert "ERRORED" in err and "compile exploded" in err


def test_new_and_missing_models_pass(tmp_path):
    """--model single runs legitimately omit the sweep; new models have no
    baseline. Neither fails, both are visible in the report."""
    prev = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "vit-b16": _model("vit-b16", 990.0),
    })
    cur = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "llama": _model("llama", 500.0),
    })
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 0, err
    assert "MISSING" in err and "NEW" in err


def test_config_drift_is_surfaced(tmp_path):
    prev = _payload({
        "resnet50": _model(
            "resnet50", 1000.0, config={"batch_per_chip": 128, "steps": 40}
        ),
    })
    cur = _payload({
        "resnet50": _model(
            "resnet50", 960.0, config={"batch_per_chip": 64, "steps": 40}
        ),
    })
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 0  # -4% is inside tolerance; the drift itself doesn't fail
    assert "CONFIG CHANGED" in err and "batch_per_chip" in err


def test_steps_change_not_flagged_as_config_drift(tmp_path):
    """steps/warmup are measurement-window knobs, not workload config."""
    prev = _payload({
        "resnet50": _model(
            "resnet50", 1000.0, config={"batch_per_chip": 128, "steps": 20}
        ),
    })
    cur = _payload({
        "resnet50": _model(
            "resnet50", 990.0, config={"batch_per_chip": 128, "steps": 40}
        ),
    })
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 0
    assert "CONFIG CHANGED" not in err


def test_driver_wrapper_parsed_field(tmp_path):
    """Driver-wrapped BENCH_r*.json: the pre-parsed stdout line wins even
    when the tail log is truncated mid-line."""
    inner = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "vit-b16": _model("vit-b16", 990.0),
    })
    wrapper = {
        "n": 3, "cmd": "python bench.py", "rc": 0,
        "tail": json.dumps(inner)[:50],  # truncated mid-JSON
        "parsed": inner,
    }
    cur = _payload({
        "resnet50": _model("resnet50", 1000.0),
        "vit-b16": _model("vit-b16", 700.0),  # -29%: the r3 scenario
    })
    rc, err = _run_gate(wrapper, cur, tmp_path)
    assert rc == 1
    assert "vit-b16" in err and "REGRESSION" in err


def test_single_model_raw_line(tmp_path):
    """A bare single-model bench line (no 'models') compares by metric name."""
    prev = _payload({"gpt2": _model("gpt2", 130000.0, unit="tokens/sec/chip")})
    cur = _model("gpt2", 100000.0, unit="tokens/sec/chip")
    rc, err = _run_gate(prev, cur, tmp_path)
    assert rc == 1
    assert "gpt2" in err


def test_tolerance_flag(tmp_path):
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 900.0)})
    rc, _ = _run_gate(prev, cur, tmp_path, extra=("--tolerance", "0.15"))
    assert rc == 0


def test_per_model_noise_tolerances(tmp_path):
    """The measured noise floor gates per model: a drop inside a noisy
    model's floor passes while a smaller drop past a quiet model's floor
    fails — one uniform tolerance can't do both."""
    noise_path = tmp_path / "noise.json"
    noise_path.write_text(json.dumps({
        "models": {
            "resnet18": {"tolerance": 0.14},
            "vit-b16": {"tolerance": 0.03},
        }
    }))
    prev = _payload({
        "resnet18": _model("resnet18", 1000.0),
        "vit-b16": _model("vit-b16", 1000.0),
    })
    cur = _payload({
        "resnet18": _model("resnet18", 900.0),  # -10%: inside its 14% floor
        "vit-b16": _model("vit-b16", 960.0),    # -4%: past its 3% floor
    })
    prev_path = tmp_path / "prev.json"
    prev_path.write_text(json.dumps(prev))
    proc = subprocess.run(
        [sys.executable, GATE, "--prev", str(prev_path),
         "--noise", str(noise_path)],
        input=json.dumps(cur), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    lines = {ln.strip().split(":")[0]: ln for ln in proc.stderr.splitlines()
             if ln.strip().startswith(("resnet18", "vit-b16"))}
    assert "REGRESSION" in lines["vit-b16"]
    assert "REGRESSION" not in lines["resnet18"]
    assert "gate 14%" in lines["resnet18"]


def test_latest_bench_sorts_numerically(tmp_path):
    """r100 must beat r99 (lexicographic sort picks r99)."""
    sys.path.insert(0, os.path.dirname(GATE))
    try:
        from bench_gate import _latest_bench
    finally:
        sys.path.pop(0)
    for name in ("BENCH_r99.json", "BENCH_r100.json", "BENCH_r04.json"):
        (tmp_path / name).write_text("{}")
    assert _latest_bench(str(tmp_path)).endswith("BENCH_r100.json")


def test_not_a_bench_payload(tmp_path):
    prev_path = tmp_path / "prev.json"
    prev_path.write_text(json.dumps({"nonsense": True}))
    proc = subprocess.run(
        [sys.executable, GATE, "--prev", str(prev_path)],
        input="{}", capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0


def _scaling_artifact(eff_by_world, model="resnet18", mode="overlap"):
    return {
        "kind": "dp-weak-scaling",
        "host_multiplexed": True,
        "world_sizes": sorted(int(w) for w in eff_by_world),
        "baseline_models": [model],
        "models": {
            model: {"modes": {mode: {"efficiency": eff_by_world}}}
        },
    }


def test_scaling_curve_below_floor_fails_by_model_and_world(tmp_path):
    """A committed dp-scaling curve sagging below the floor fails the
    gate naming (model, world size) — the ISSUE-19 acceptance gate."""
    scaling_path = tmp_path / "scaling.json"
    scaling_path.write_text(json.dumps(_scaling_artifact(
        {"1": 1.0, "2": 0.97, "4": 0.95, "8": 0.84}
    )))
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 1000.0)})
    rc, err = _run_gate(
        prev, cur, tmp_path, extra=("--scaling", str(scaling_path)),
    )
    assert rc == 1
    assert "resnet18 (W=8, overlap)" in err
    assert "dp-scaling below floor" in err
    assert "W=4" not in err.split("FAIL")[-1]  # only W=8 named as failing


def test_scaling_curve_above_floor_passes_and_reports(tmp_path):
    scaling_path = tmp_path / "scaling.json"
    scaling_path.write_text(json.dumps(_scaling_artifact(
        {"1": 1.0, "2": 0.99, "4": 0.96, "8": 0.93}
    )))
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 1000.0)})
    rc, err = _run_gate(
        prev, cur, tmp_path, extra=("--scaling", str(scaling_path)),
    )
    assert rc == 0, err
    assert "scaling resnet18/overlap W=8" in err  # curve visible in report


def test_scaling_floor_flag_and_non_baseline_models_advisory(tmp_path):
    """--scaling-floor moves the bar; models not in baseline_models are
    exempt (experimental zoo entries don't gate)."""
    art = _scaling_artifact({"1": 1.0, "8": 0.85})
    art["models"]["llama-exp"] = {
        "modes": {"overlap": {"efficiency": {"1": 1.0, "8": 0.5}}}
    }
    scaling_path = tmp_path / "scaling.json"
    scaling_path.write_text(json.dumps(art))
    prev = _payload({"resnet50": _model("resnet50", 1000.0)})
    cur = _payload({"resnet50": _model("resnet50", 1000.0)})
    rc, err = _run_gate(
        prev, cur, tmp_path,
        extra=("--scaling", str(scaling_path), "--scaling-floor", "0.80"),
    )
    assert rc == 0, err
    assert "llama-exp" not in err
