#!/usr/bin/env python3
"""The scaling-law factory: dp-scaling curves across (world size x model
x wire x overlap), committed as ``results/scaling/`` artifacts.

Each grid cell is ONE ``bench.py`` subprocess on a host-multiplexed fake
CPU mesh of W virtual chips (``--xla_force_host_platform_device_count``,
the same virtualization the test suite's conftest uses), holding the
per-chip batch fixed — WEAK scaling, the regime the ZeRO-1 data plane
actually runs in. On a host-multiplexed mesh every virtual chip shares
the SAME physical cores, so the ideal is constant GLOBAL throughput
(the host does W x the work in W x the time), not constant per-chip
throughput — the honest efficiency is

    efficiency(W) = global_rate(W) / global_rate(1)
                  = W * per_chip_rate(W) / per_chip_rate(1)

which isolates exactly the scaling overheads (exposed wire time, sync
scheduling, per-shard dispatch) from the serialized compute. On real
hardware (one chip per W) the same artifact schema holds with
``per_chip_rate(W)/per_chip_rate(1)`` — the ``host_multiplexed`` flag in
the artifact records which ideal the curve is against. A
perfectly-hidden gradient sync keeps efficiency ~1.0 as W grows; every
exposed wire byte shows up as the curve sagging. Each cell's record also
carries the graft-prove side of the story on the SAME artifact: the
analytic per-device wire-payload prediction (``parallel/wire.py
grad_wire_report`` -> bench's ``grad_wire_bytes_per_step``) next to the
measured HLO collective accounting of the compiled step (bench's
``hlo_collectives``, the result-buffer proxy) — predicted-vs-measured
bytes, so a curve regression is attributable to schedule vs payload.

``scripts/bench_gate.py`` learns the committed curves: any BASELINE
model whose 8-chip efficiency falls below the floor (default 90%) fails
the gate by (model, world size). Serve cells (``--serve``) ride along
for the fleet curve but are advisory — the serving engine replays a
fixed workload and its rate is latency- not wire-bound.

Usage (the committed-artifact recipe, ~15 min on the one-core box; the
per-chip batch is held far below the TPU default so a W=8 cell's global
step still fits the host):
    python scripts/scaling_sweep.py --models resnet18 \
        --modes overlap,inline --world-sizes 1,2,4,8 \
        --batch-per-chip 16 --steps 10 --warmup 3 --out results/scaling
NOT a chip command: CPU-only and subprocess-isolated. Every cell is a child
process held to the CPU platform (``JAX_PLATFORMS=cpu`` in its environment),
so the sweep never takes a chip — and must not be given one: a chip belongs
to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# mode -> extra bench.py argv; "overlap" is the shipped ZeRO-1+wire
# bucketed config the ISSUE-19 acceptance gates on, "inline" its
# unbucketed control, "plain" pure replicated data-parallel
MODES = {
    "plain": [],
    "zero1": ["--zero1"],
    "inline": ["--zero1", "--wire", "int8-block"],
    "overlap": ["--zero1", "--wire", "int8-block", "--overlap-buckets", "-1"],
}


def _cell_env(world: int) -> dict:
    env = dict(os.environ)
    # a scaling cell stays on the virtual CPU mesh: force the platform in
    # the child's environment
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={world} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    return env


def run_cell(model: str, mode: str, world: int, args) -> dict:
    argv = [
        sys.executable, os.path.join(REPO, "bench.py"),
        "--model", model,
        "--steps", str(args.steps), "--warmup", str(args.warmup),
    ]
    if args.batch_per_chip:
        argv += ["--batch-per-chip", str(args.batch_per_chip)]
    if args.seq_len:
        argv += ["--seq-len", str(args.seq_len)]
    if args.image_size:
        argv += ["--image-size", str(args.image_size)]
    argv += MODES[mode]
    proc = subprocess.run(
        argv, env=_cell_env(world), cwd=REPO, capture_output=True,
        text=True, timeout=args.cell_timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{model}/{mode}/W={world} failed rc={proc.returncode}: "
            f"{proc.stderr.strip().splitlines()[-3:]}"
        )
    line = proc.stdout.strip().splitlines()[-1]
    return json.loads(line)


def run_serve_cell(world: int, args) -> dict:
    argv = [
        sys.executable, os.path.join(REPO, "bench.py"), "--serve",
    ]
    proc = subprocess.run(
        argv, env=_cell_env(world), cwd=REPO, capture_output=True,
        text=True, timeout=args.cell_timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve/W={world} failed rc={proc.returncode}: "
            f"{proc.stderr.strip().splitlines()[-3:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--models", default="resnet18")
    p.add_argument("--modes", default="overlap,inline")
    p.add_argument("--world-sizes", default="1,2,4,8")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--warmup", type=int, default=4)
    p.add_argument("--batch-per-chip", type=int, default=0,
                   help="0 = bench.py per-model default (weak scaling "
                   "holds whatever per-chip batch is used constant)")
    p.add_argument("--seq-len", type=int, default=0,
                   help="0 = bench.py default (LM models only)")
    p.add_argument("--image-size", type=int, default=0,
                   help="0 = bench.py default (vision models only)")
    p.add_argument("--serve", action="store_true",
                   help="also sweep the serving engine per world size "
                   "(advisory fleet curve)")
    p.add_argument("--out", default=os.path.join(REPO, "results", "scaling"))
    p.add_argument("--tag", default="fake-cpu-mesh")
    p.add_argument("--cell-timeout", type=int, default=1800)
    args = p.parse_args()

    models = [m for m in args.models.split(",") if m]
    modes = [m for m in args.modes.split(",") if m]
    worlds = sorted({int(w) for w in args.world_sizes.split(",")})
    for m in modes:
        if m not in MODES:
            p.error(f"unknown mode {m!r}; choices: {list(MODES)}")
    if 1 not in worlds:
        p.error("--world-sizes must include 1 (the efficiency anchor)")

    curves: dict = {}
    for model in models:
        curves[model] = {"modes": {}}
        for mode in modes:
            per_chip: dict = {}
            cells: dict = {}
            for world in worlds:
                print(
                    f"scaling_sweep: {model} {mode} W={world} ...",
                    file=sys.stderr, flush=True,
                )
                rec = run_cell(model, mode, world, args)
                per_chip[str(world)] = rec["value"]
                cell = {
                    "per_chip_rate": rec["value"],
                    "unit": rec["unit"],
                    "step_time_ms": rec["step_time_ms"],
                    "overlap_frac_scheduled": rec.get(
                        "overlap_frac_scheduled"
                    ),
                    # graft-prove predicted payload vs measured HLO
                    # result-buffer bytes, SAME compiled artifact
                    "predicted_wire_bytes_per_step": rec.get(
                        "grad_wire_bytes_per_step"
                    ),
                    "wire_compression_ratio": rec.get(
                        "wire_compression_ratio"
                    ),
                    "measured_hlo_collectives": rec.get("hlo_collectives"),
                    "config": rec.get("config"),
                }
                if rec.get("overlap_scheduled"):
                    cell["overlap_scheduled"] = rec["overlap_scheduled"]
                cells[str(world)] = cell
            # host-multiplexed ideal: constant GLOBAL rate (one physical
            # host serializes all W virtual chips) — see module docstring
            anchor = worlds[0] * per_chip[str(worlds[0])]
            efficiency = {
                w: round(int(w) * v / anchor, 4)
                for w, v in per_chip.items()
            }
            curves[model]["modes"][mode] = {
                "per_chip_rate": per_chip,
                "efficiency": efficiency,
                "cells": cells,
            }

    serve_curve = None
    if args.serve:
        serve_curve = {}
        for world in worlds:
            print(f"scaling_sweep: serve W={world} ...", file=sys.stderr,
                  flush=True)
            rec = run_serve_cell(world, args)
            serve_curve[str(world)] = {
                "tokens_per_sec_per_chip": rec["value"],
                "unit": rec["unit"],
            }

    artifact = {
        "kind": "dp-weak-scaling",
        "tag": args.tag,
        "host_multiplexed": True,
        "world_sizes": worlds,
        "baseline_models": models,
        "metric": ("global throughput vs W=1 at fixed per-chip batch "
                   "(host-multiplexed weak-scaling efficiency: ideal is "
                   "constant global rate, W virtual chips share the "
                   "physical host)"),
        "sweep_config": {
            "steps": args.steps, "warmup": args.warmup,
            "batch_per_chip": args.batch_per_chip or "bench-default",
            "modes": {m: " ".join(MODES[m]) or "(pure dp)" for m in modes},
        },
        "models": curves,
        **({"serve": serve_curve} if serve_curve else {}),
    }
    os.makedirs(args.out, exist_ok=True)
    out_json = os.path.join(args.out, "scaling.json")
    with open(out_json, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")

    # human-readable curves beside the machine artifact
    lines = [
        "# DP weak-scaling curves (fake CPU mesh)", "",
        f"Per-chip throughput efficiency vs W=1, tag `{args.tag}`.",
        "Gate: `scripts/bench_gate.py` fails any BASELINE model below",
        "its floor at any committed world size.", "",
    ]
    for model, mc in curves.items():
        for mode, curve in mc["modes"].items():
            eff = curve["efficiency"]
            row = " | ".join(f"{eff[str(w)]:.1%}" for w in worlds)
            lines.append(f"## {model} ({mode})")
            lines.append("")
            lines.append("| W | " + " | ".join(str(w) for w in worlds)
                         + " |")
            lines.append("|---|" + "---|" * len(worlds))
            lines.append(f"| efficiency | {row} |")
            cell8 = curve["cells"].get(str(worlds[-1]), {})
            pred = cell8.get("predicted_wire_bytes_per_step")
            meas = cell8.get("measured_hlo_collectives") or {}
            meas_bytes = sum(
                rec.get("bytes", 0) for rec in meas.values()
            ) or None
            lines.append(
                f"| wire bytes (W={worlds[-1]}) | predicted {pred} | "
                f"measured-HLO {meas_bytes} |" + " |" * (len(worlds) - 2)
            )
            sched = cell8.get("overlap_frac_scheduled")
            if sched is not None:
                lines.append(
                    f"| overlap_frac_scheduled | {sched} |"
                    + " |" * (len(worlds) - 1)
                )
            lines.append("")
    with open(os.path.join(args.out, "curves.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"scaling_sweep: wrote {out_json}", file=sys.stderr)
    print(json.dumps({
        "artifact": os.path.relpath(out_json, REPO),
        "models": {
            m: {mode: c["efficiency"]
                for mode, c in mc["modes"].items()}
            for m, mc in curves.items()
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
