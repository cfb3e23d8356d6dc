#!/usr/bin/env python3
"""graft-plan CLI: static auto-parallelism search over the three-tier oracle.

Ranks legal ``PlanSpec`` candidates (analysis/planner.py) for the five
BASELINE train models and the serve engine's prefill/decode programs on the
fake 8-chip CPU mesh — WITHOUT a single XLA compile. Scoring tiers:

1. traced shardflow per-collective wire bytes (int8/bf16 payload dtypes
   included) through a latency/bandwidth link model;
2. static HBM envelope vs ``--hbm-limit`` — would-OOM plans are pruned
   before any compiler ever sees them;
3. committed compiled-cost records (analysis/comm_budgets.json) override
   the traced estimate when a plan coincides with a measured config.

Driver contract (same as graft_lint.py): stdout carries exactly
ONE JSON line; per-plan rankings and event attributions go to stderr.

Usage:
    python scripts/plan_search.py                     # full grid + serve
    python scripts/plan_search.py --models gpt2 --hbm-limit 16G
    python scripts/plan_search.py --write-plans       # refresh plans.json
    python scripts/plan_search.py --diff HEAD~1       # attribute rank flips
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASELINE_MODELS = ("resnet18", "resnet50", "vit-b16", "bert-base", "gpt2")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr)


def _parse_bytes(raw):
    """'16G' / '2M' / '123456' -> bytes (mirrors envelope.hbm_limit_from_env)."""
    if raw is None:
        return None
    raw = str(raw).strip()
    mult = 1
    for suffix, m in (("K", 1 << 10), ("M", 1 << 20), ("G", 1 << 30)):
        if raw.upper().endswith(suffix):
            raw, mult = raw[:-1], m
            break
    return int(float(raw) * mult)


def _build_train_case(name: str, args):
    """Model/task/batch for one BASELINE model: bf16 everywhere, fused-CE
    hidden logits for LMs, 16 rows a chip for an LM, 256 / 128 images for
    resnet18 / the other image models unless --batch-per-chip says
    otherwise. Batch leaves are ShapeDtypeStructs: nothing is materialized."""
    import jax
    import jax.numpy as jnp

    import distributed_pytorch_example_tpu as dpx

    n = args.devices
    lm = name.startswith(("gpt", "bert", "llama"))
    if lm:
        bpc = args.batch_per_chip or 16
        model = dpx.models.get_model(
            name, dtype=jnp.bfloat16, logits_mode="hidden"
        )
        seq = min(args.seq_len, model.max_len)  # BERT caps at 512
        gb = bpc * n
        batch = {"tokens": jax.ShapeDtypeStruct((gb, seq), jnp.int32)}
        if name.startswith("bert"):
            task = dpx.train.MLMTask(
                vocab_size=model.vocab_size, mask_token_id=103
            )
        else:
            task = dpx.train.CausalLMTask()
        sample = batch["tokens"]
        kind = "lm"
        heads, layers = model.num_heads, model.num_layers
    else:
        image_size, classes = (
            (32, 10) if name == "resnet18" else (args.image_size, 1000)
        )
        bpc = args.batch_per_chip or (256 if name == "resnet18" else 128)
        gb = bpc * n
        model = dpx.models.get_model(
            name, num_classes=classes, dtype=jnp.bfloat16
        )
        batch = {
            "x": jax.ShapeDtypeStruct(
                (gb, image_size, image_size, 3), jnp.float32
            ),
            "y": jax.ShapeDtypeStruct((gb,), jnp.int32),
        }
        task = dpx.train.ClassificationTask()
        sample = batch["x"]
        kind = "image"
        heads = layers = 0
    return {
        "model": model, "task": task, "batch": batch, "sample": sample,
        "global_batch": gb, "kind": kind, "heads": heads, "layers": layers,
    }


def search_train(name: str, args, devices, budgets, hbm_limit, link):
    """Ranked PlanScores for one BASELINE model (plus the gpt2 pipeline
    variant when applicable)."""
    import jax
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.analysis import planner
    from distributed_pytorch_example_tpu.train import step as step_mod

    case = _build_train_case(name, args)
    optimizer = optax.adam(1e-3)
    state_shapes = step_mod.abstract_state(
        case["model"], optimizer, case["sample"]
    )
    max_param = max(
        (math.prod(l.shape) for l in jax.tree_util.tree_leaves(
            state_shapes.params
        )),
        default=0,
    )
    info = planner.ProgramInfo(
        global_batch=case["global_batch"], num_heads=case["heads"],
        num_layers=case["layers"], pipelineable=False,
        max_param_elems=max_param, kind=case["kind"],
    )
    # Trace-cost budget (the <60s grid contract): automatic-mode plans all
    # share ONE traced jaxpr, so they are free to add; each manual-mode
    # plan (zero1/wire) is a fresh shard_map trace (~seconds at BASELINE
    # scale). cli_plan_space keeps the manual knobs on the pure-DP mesh.
    plans = planner.cli_plan_space(len(devices), info)
    prog = f"train/{name}"
    scores = planner.rank_train_plans(
        case["model"], case["task"], optimizer, case["sample"],
        case["batch"], plans, program=prog, devices=devices, link=link,
        hbm_limit=hbm_limit, budgets=budgets, log=_log,
        state_shapes=state_shapes,
    )

    if name.startswith(("gpt", "llama")) and not args.no_pipe:
        # Pipeline candidates need the layer-stacked model variant (same
        # rebuild train.py does under --mesh-pipe); ranked with the same
        # program label and merged into one ordering.
        import jax.numpy as jnp

        pipe_model = dpx.models.get_model(
            name, dtype=jnp.bfloat16, logits_mode="hidden",
            pipe_axis="pipe", pipe_schedule="gpipe", pipe_microbatches=2,
        )
        info_pipe = planner.ProgramInfo(
            global_batch=case["global_batch"], num_heads=case["heads"],
            num_layers=case["layers"], pipelineable=True,
            max_param_elems=max_param, kind="lm",
        )
        pipe_plans = [
            p for p in planner.enumerate_plans(
                len(devices), info_pipe, families=("transformer",),
                zero1_options=(False,), wire_options=(None,),
                allow_pipe=True,
            )
            if p.mesh.pipe == 2
        ]
        scores += planner.rank_train_plans(
            pipe_model, case["task"], optimizer, case["sample"],
            case["batch"], pipe_plans, program=prog, devices=devices,
            link=link, hbm_limit=hbm_limit, budgets=budgets, log=_log,
        )
        scores = planner.sort_scores(scores)
    return scores


def search_serve(args, devices, budgets, hbm_limit, link):
    """Ranked prefill/decode PlanScores for the dryrun serve engine.

    ONE engine is built (its ctor runs the tiny plan-independent init);
    every candidate plan then re-traces the bucketed-prefill and
    slot-decode programs under its own mesh via ``engine.plan_programs``
    — zero compiles, no engine-per-plan.
    """
    import __graft_entry__ as entry
    from distributed_pytorch_example_tpu.analysis import planner
    from distributed_pytorch_example_tpu.parallel.plan import PlanSpec
    from distributed_pytorch_example_tpu.runtime.mesh import MeshSpec

    case = entry.build_serve_case(devices)
    if isinstance(case, str):
        _log(f"plan_search: serve skipped — {case}")
        return {}
    engine = case.engine
    # Serve batch dims (slots, bucketed prompt) replicate in the traced
    # programs — dp-divisibility does not gate them, so the legality batch
    # is the device count itself (every enumerable span divides it).
    info = planner.ProgramInfo(
        global_batch=len(devices), num_heads=engine.model.num_heads,
        num_layers=engine.model.num_layers, pipelineable=False, kind="lm",
    )
    plans = planner.enumerate_plans(
        len(devices), info, families=("data", "transformer"),
        zero1_options=(False,), wire_options=(None,), allow_pipe=False,
    )
    # Seed the committed serve mesh (2x2x2, __graft_entry__.build_serve_case)
    # so the tier-3 compiled-cost records for serve/prefill + serve/decode
    # can engage when mesh and knobs coincide.
    committed = PlanSpec(
        mesh=MeshSpec(data=2, fsdp=2, tensor=2), family="transformer"
    )
    if planner.legality(committed, info, len(devices)) is None:
        plans.append(committed)
    return planner.rank_serve_plans(
        engine, plans, devices=devices, link=link, hbm_limit=hbm_limit,
        budgets=budgets, log=_log,
    )


def _program_entry(scores, top: int):
    return {
        "plans_considered": len(scores),
        "feasible": sum(1 for s in scores if s.feasible),
        "top": [s.to_json() for s in scores if s.feasible][:top],
        "pruned": [
            {"plan": s.plan.name(), "tier": s.tier, "reason": s.reason}
            for s in scores if not s.feasible
        ],
    }


def _attribute(prog: str, entry) -> None:
    """Per-plan stderr attribution: the named shardflow events behind the
    winning score."""
    tops = entry.get("top") or []
    if not tops:
        _log(f"plan_search: {prog}: no feasible plan")
        return
    best = tops[0]
    _log(
        f"plan_search: {prog} -> {best['plan']} "
        f"(tier {best['tier']}, cost {best['cost_ms']}ms, "
        f"{best['comm_bytes']}B wire)"
    )
    for e in best.get("events_top", []):
        _log(
            f"plan_search:   {prog} {best['plan']} event "
            f"{e.get('collective')} axes={e.get('axes')} "
            f"bytes={e.get('bytes')} path={e.get('path') or e.get('op')}"
        )


def run_search(args, devices):
    from distributed_pytorch_example_tpu.analysis import collectives, planner

    budgets = collectives.load_budgets(
        args.budgets or collectives.DEFAULT_BUDGETS_PATH
    )
    skew = collectives.jax_version_skew(budgets) if budgets else None
    if skew:
        _log(
            f"plan_search: comm_budgets.json measured under jax {skew} — "
            f"tier-3 cached costs demoted (traced estimates used)"
        )
        budgets = None
    hbm_limit = _parse_bytes(args.hbm_limit)
    link = planner.LinkModel(
        latency_us=args.link_latency_us, bandwidth_gbps=args.link_gbps
    )

    programs = {}
    for name in args.model_list:
        scores = search_train(name, args, devices, budgets, hbm_limit, link)
        programs[f"train/{name}"] = _program_entry(scores, args.top)
    if not args.no_serve:
        for prog, scores in sorted(
            search_serve(args, devices, budgets, hbm_limit, link).items()
        ):
            programs[prog] = _program_entry(scores, args.top)
    for prog in sorted(programs):
        _attribute(prog, programs[prog])
    return programs


def write_plans(programs, args, path: str) -> None:
    import jax

    doc = {
        "_meta": {
            "jax": jax.__version__,
            "n_devices": args.devices,
            "tool": "scripts/plan_search.py --write-plans",
        },
        "programs": {
            prog: {
                "plans_considered": entry["plans_considered"],
                "feasible": entry["feasible"],
                "top": entry["top"],
            }
            for prog, entry in sorted(programs.items())
        },
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    _log(f"plan_search: wrote {path}")


def run_diff(rev: str, programs, args, path: str):
    """Rank the working tree, diff the top plan per program against the
    plans.json committed at ``rev``, and attribute each flip to the named
    shardflow events behind the new winner (same git-show plumbing as
    ``runner.diff_audit``)."""
    import subprocess

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rel = os.path.relpath(path, repo_root)
    old_raw = subprocess.run(
        ["git", "show", f"{rev}:{rel}"],
        cwd=repo_root, capture_output=True, text=True,
    )
    if old_raw.returncode != 0:
        raise SystemExit(f"cannot read {rel} at {rev}: {old_raw.stderr.strip()}")
    old_programs = (json.loads(old_raw.stdout).get("programs")) or {}

    flips, unchanged = {}, []
    for prog in sorted(set(programs) | set(old_programs)):
        new_tops = (programs.get(prog) or {}).get("top") or []
        old_tops = (old_programs.get(prog) or {}).get("top") or []
        new_top = new_tops[0]["plan"] if new_tops else None
        old_top = old_tops[0]["plan"] if old_tops else None
        if new_top == old_top:
            unchanged.append(prog)
            continue
        # the events behind the new winner, and where the old winner went
        old_rank = next(
            (i for i, s in enumerate(new_tops) if s["plan"] == old_top),
            None,
        )
        flips[prog] = {
            "old": old_top,
            "new": new_top,
            "old_plan_new_rank": old_rank,
            "attribution": (new_tops[0].get("events_top") if new_tops else []),
        }
        _log(f"plan_search: DIFF {prog}: {old_top} -> {new_top}")
        for e in flips[prog]["attribution"]:
            _log(
                f"plan_search:   {prog} flip event {e.get('collective')} "
                f"axes={e.get('axes')} bytes={e.get('bytes')} "
                f"path={e.get('path') or e.get('op')}"
            )
    return {"rev": rev, "flips": flips, "unchanged": unchanged}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument(
        "--models", default=",".join(BASELINE_MODELS),
        help="comma-separated BASELINE model names",
    )
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument(
        "--top", type=int, default=3,
        help="ranked plans kept per program in the report",
    )
    ap.add_argument(
        "--hbm-limit", default=None,
        help="per-chip HBM budget for the tier-2 envelope gate "
             "(suffixes K/M/G; default: no gate)",
    )
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument(
        "--batch-per-chip", type=int, default=None,
        help="override the per-model bench defaults (256/128/16)",
    )
    ap.add_argument("--link-latency-us", type=float, default=1.0)
    ap.add_argument("--link-gbps", type=float, default=100.0)
    ap.add_argument("--no-serve", action="store_true")
    ap.add_argument(
        "--no-pipe", action="store_true",
        help="skip the gpt2 pipeline-variant candidates",
    )
    ap.add_argument(
        "--budgets", default=None,
        help="comm-budget file for tier-3 cached costs "
             "(default: analysis/comm_budgets.json)",
    )
    ap.add_argument(
        "--plans", default=None,
        help="plans file path (default: analysis/plans.json)",
    )
    ap.add_argument(
        "--write-plans", action="store_true",
        help="overwrite the committed plans file with this run's rankings",
    )
    ap.add_argument(
        "--diff", default=None, metavar="REV",
        help="diff the working-tree ranking against the plans file "
             "committed at REV and attribute flips to shardflow events",
    )
    args = ap.parse_args()
    args.model_list = [m for m in args.models.split(",") if m]

    t0 = time.time()
    import __graft_entry__ as entry

    entry._ensure_cpu_devices(args.devices)
    import jax

    devices = jax.devices()[: args.devices]
    if len(devices) < args.devices:
        print(
            json.dumps({
                "tool": "plan_search", "error":
                f"need {args.devices} devices, have {len(devices)}",
            })
        )
        return 1

    from distributed_pytorch_example_tpu.analysis import planner

    plans_path = args.plans or planner.DEFAULT_PLANS_PATH
    programs = run_search(args, devices)
    doc = {
        "tool": "plan_search",
        "mode": "diff" if args.diff else "search",
        "jax": jax.__version__,
        "n_devices": args.devices,
        "programs": programs,
        "picked": {
            prog: (entry_["top"][0]["plan"] if entry_["top"] else None)
            for prog, entry_ in sorted(programs.items())
        },
    }
    if args.diff:
        doc["diff"] = run_diff(args.diff, programs, args, plans_path)
    if args.write_plans:
        write_plans(programs, args, plans_path)
        doc["wrote_plans"] = plans_path
    doc["elapsed_s"] = round(time.time() - t0, 2)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
