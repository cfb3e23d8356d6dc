#!/usr/bin/env python3
"""Measure the bench noise floor and derive per-model gate thresholds.

The bench gate (scripts/bench_gate.py) shipped with one uniform 5%
tolerance — but the measured same-code spread is wildly per-model:
ResNet-18 has shown a 12.6% swing between the driver's bench run and the
gate's re-run of the SAME commit on the same v5e (VERDICT r5 weak #2),
while ViT-B/16 and GPT-2 repeat within 0.7%. One number can't serve
both: 5% silently absorbs real ViT regressions and false-alarms on
ResNet-18 noise.

This script makes the floor a committed measurement with two evidence
sources, and writes ``results/bench_noise/noise.json`` for the gate:

1. **v5e same-code pairs** (committed artifacts): the driver's
   ``BENCH_r*.json`` vs the gate's ``results/bench_gate_r*/bench.json``
   for the same commit are two bench.py runs of identical code on the
   same chip — their per-model delta IS run-to-run noise at production
   shapes. This is the basis of each model's gate tolerance:
   ``max(floor, 1.25 x worst same-code spread)``, rounded up to a
   percent.
2. **local repeats** (``--repeats-dir`` or ``--run N``): N >= 5 fresh
   ``bench.py`` sweeps on fixed code, committed under
   ``results/bench_noise/repeats/``. These measure the harness
   protocol's own run-to-run spread (process restart, recompile, timing
   window) on whatever backend is attached — on a CPU-only session they
   do NOT reproduce v5e throughput and are labeled with their platform;
   they cross-check that the protocol itself is not the noise source.

Usage:
  python scripts/bench_noise.py --repeats-dir /tmp/bench_noise \
      [--json results/bench_noise/noise.json]
  python scripts/bench_noise.py --run 5 --bench-args "--steps 8 ..." \
      [--json ...]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_gate import _extract_models  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Same-commit bench.py runs on the same v5e: (driver run, gate re-run).
# The r04->r05 gate pair rides along: the interim commits touched no
# single-chip hot path (results/bench_gate_r05/gate.txt), so it is
# same-code for every benched model.
V5E_SAME_CODE_PAIRS = (
    ("BENCH_r04.json", "results/bench_gate_r04/bench.json"),
    ("BENCH_r05.json", "results/bench_gate_r05/bench.json"),
    ("results/bench_gate_r04/bench.json", "results/bench_gate_r05/bench.json"),
)

TOLERANCE_FLOOR = 0.03
MARGIN = 1.25


def _load_models(path: str) -> dict[str, dict]:
    with open(path) as f:
        return _extract_models(f.read(), path)


def v5e_same_code_spreads() -> dict[str, dict]:
    """Per-model |relative delta| for each committed same-code v5e pair."""
    out: dict[str, dict] = {}
    for a, b in V5E_SAME_CODE_PAIRS:
        pa, pb = os.path.join(ROOT, a), os.path.join(ROOT, b)
        if not (os.path.exists(pa) and os.path.exists(pb)):
            continue
        ma, mb = _load_models(pa), _load_models(pb)
        for name in set(ma) & set(mb):
            if "error" in ma[name] or "error" in mb[name]:
                continue
            old, new = ma[name]["value"], mb[name]["value"]
            out.setdefault(name, {"pairs": {}})["pairs"][f"{a} vs {b}"] = (
                round(abs(new - old) / old, 4)
            )
    for row in out.values():
        row["worst_spread"] = max(row["pairs"].values())
    return out


def repeat_stats(files: list[str]) -> dict[str, dict]:
    """Per-model spread across N bench.py stdout files (one sweep each)."""
    runs = [_load_models(f) for f in files]
    names = sorted({n for r in runs for n in r})
    out = {}
    for name in names:
        vals = [r[name]["value"] for r in runs
                if name in r and "error" not in r[name]]
        if len(vals) < 2:
            out[name] = {"n": len(vals), "values": vals}
            continue
        mean = statistics.fmean(vals)
        out[name] = {
            "n": len(vals),
            "values": vals,
            "mean": round(mean, 2),
            "rsd": round(statistics.stdev(vals) / mean, 4),
            "spread": round((max(vals) - min(vals)) / min(vals), 4),
        }
    return out


def derive_tolerances(v5e: dict, repeats: dict) -> dict[str, dict]:
    """Gate tolerance per model: margin x worst v5e same-code spread,
    floored and rounded up to a whole percent. Local repeats are the
    cross-check, not the basis — on a CPU-only session their absolute
    throughput is a different machine class, but a protocol spread far
    above the v5e-derived tolerance would mean the harness itself is
    noisy, so that case is flagged."""
    models = sorted(set(v5e) | set(repeats))
    out = {}
    for name in models:
        row: dict = {}
        worst = v5e.get(name, {}).get("worst_spread")
        if worst is not None:
            tol = max(TOLERANCE_FLOOR, math.ceil(MARGIN * worst * 100) / 100)
            row["tolerance"] = round(tol, 2)
            row["basis"] = (
                f"max({TOLERANCE_FLOOR:.0%} floor, {MARGIN} x "
                f"{worst:.1%} worst v5e same-code spread)"
            )
            row["v5e_same_code"] = v5e[name]
        else:
            row["basis"] = "no v5e same-code evidence; gate falls back " \
                           "to its --tolerance default"
        if name in repeats:
            row["local_repeats"] = repeats[name]
            spread = repeats[name].get("spread")
            if spread is not None and "tolerance" in row \
                    and spread > row["tolerance"]:
                row["note"] = (
                    f"local repeat spread {spread:.1%} exceeds the "
                    f"v5e-derived tolerance; that indicts the harness only "
                    f"when the repeats ran at production shapes on the "
                    f"gated backend — at reduced shapes on another backend "
                    f"(repeat_protocol.config) short timing windows "
                    f"magnify, so the v5e pairs stay the basis"
                )
        out[name] = row
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--repeats-dir", default=None,
                        help="directory of repeat*.json bench.py stdout files")
    parser.add_argument("--run", type=int, default=0,
                        help="run bench.py this many times itself (>= 5 for "
                        "a committed floor)")
    parser.add_argument("--bench-args", default="",
                        help="extra bench.py flags for --run sweeps")
    parser.add_argument("--out-dir", default=None,
                        help="copy the repeat files here (commit them "
                        "alongside noise.json)")
    parser.add_argument("--json", default=None)
    args = parser.parse_args()

    files: list[str] = []
    if args.repeats_dir:
        files = sorted(
            os.path.join(args.repeats_dir, f)
            for f in os.listdir(args.repeats_dir)
            if f.startswith("repeat") and f.endswith(".json")
        )
    # This parent never imports jax, and must stay that way: a chip belongs
    # to one process at a time, so a parent that had touched JAX would hold
    # it and every bench.py child below would fail to get it (or hang).
    for i in range(args.run):
        path = f"/tmp/bench_noise_run{i + 1}.json"
        cmd = [sys.executable, os.path.join(ROOT, "bench.py")]
        cmd += args.bench_args.split()
        with open(path, "w") as f:
            subprocess.run(cmd, stdout=f, check=True, cwd=ROOT)
        files.append(path)
    if not files:
        parser.error("need --repeats-dir or --run N")

    repeats = repeat_stats(files)
    v5e = v5e_same_code_spreads()
    models = derive_tolerances(v5e, repeats)

    # platform + config of the repeat runs, from the first file's payload
    first = _load_models(files[0])
    any_row = next(iter(first.values()))
    out = {
        "models": models,
        "repeat_protocol": {
            "n_sweeps": len(files),
            "files": [os.path.basename(f) for f in files],
            "config": any_row.get("config"),
            "note": (
                "repeat sweeps measure harness run-to-run spread on the "
                "attached backend at reduced shapes; tolerances come from "
                "the v5e same-code pairs at production shapes"
            ),
        },
    }
    print(json.dumps(out, indent=1))
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
        for f in files:
            with open(f) as src, open(
                os.path.join(args.out_dir, os.path.basename(f)), "w"
            ) as dst:
                dst.write(src.read())
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
