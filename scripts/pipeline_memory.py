"""Peak compiled memory: GPipe vs 1F1B pipeline schedules.

The reason 1F1B exists (VERDICT r4 #1): GPipe differentiates its schedule
scan in reverse, so autodiff saves every tick's stage internals — peak
activation memory grows with n_micro — while 1F1B stashes only stage
INPUTS for in-flight microbatches, bounded by ``2(n_stages-1)+1`` slots
regardless of n_micro (parallel/pipeline.py one_f_one_b).

This script makes that a measured number: it compiles the FULL train loss
+ gradient computation for the same GPT-2 stack under each schedule at a
fixed microbatch size (weak scaling: batch = mb_size * n_micro, the
production regime), on the 8-virtual-CPU-device data=2 x pipe=4 mesh, and
reports XLA's ``temp_size_in_bytes`` (the compiled peak temporary
allocation). Expectation: GPipe's temp grows ~linearly in n_micro with a
large slope (per-tick residuals: every attention/MLP intermediate); 1F1B's
slope is the microbatch queue + dx buffer only (a few mb activations), its
activation stash flat at ~n_stages microbatches.

Run (fake CPU mesh):
  python scripts/pipeline_memory.py \
      [--micros 8,16,32] [--json results/pipeline_1f1b/memory.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


MB = 1024 * 1024


def build(schedule: str, n_micro: int, remat: bool, n_virtual: int = 1,
          recompute: bool = True):
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    return GPT2(
        vocab_size=512, max_len=256, model_dim=256, num_layers=8,
        num_heads=8, mlp_dim=1024, pipe_axis="pipe",
        pipe_microbatches=n_micro, pipe_schedule=schedule, remat=remat,
        pipe_virtual=n_virtual, pipe_recompute=recompute,
        logits_mode="hidden",
    ), CausalLMTask()


def _flops(compiled) -> float:
    """Per-device flops from XLA's cost analysis (0 if unavailable)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0))


def measure(schedule: str, n_micro: int, mb_size: int, seq: int,
            remat: bool = False, n_virtual: int = 1,
            recompute: bool = True, data_span: int = 2) -> dict:
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

    # data_span=1 keeps every non-pipe axis at span 1, which makes the
    # schedule's shard_map effectively fully manual (the mesh shape the
    # committed frontier was measured on)
    mesh = make_mesh(
        MeshSpec(data=data_span, pipe=4),
        devices=jax.devices()[: 4 * data_span],
    )
    model, task = build(schedule, n_micro, remat, n_virtual, recompute)
    batch = mb_size * n_micro
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, size=(batch, seq)),
        jnp.int32,
    )
    with mesh:
        params = model.init(jax.random.key(0), tokens, train=False)["params"]
        # pin the PRODUCTION param shardings (contiguous dim-0 pipe blocks,
        # the Trainer's partitioner) so schedules are compared under the
        # same interface placement. Under pipe_virtual>1 this includes the
        # per-step strided param reshard the interleaved placement needs
        # (layer l lives on device (l//Lc) mod S, which no dim-0
        # NamedSharding over logical layer order can express) — that cost
        # belongs in the measurement.
        params = transformer_partitioner(mesh).shard_tree(params)

        def loss_fn(p, tok):
            loss, _, _ = task.compute_loss(
                model, p, {}, {"tokens": tok}, jax.random.key(1), train=True
            )
            return loss

        # pin grad out-shardings to the param shardings (what the Trainer
        # effectively does by feeding grads to the sharded optimizer update
        # inside the same jit) — without this XLA may replicate the grads
        # at the interface under pipe_virtual>1, polluting out_mb
        out_sh = (
            jax.tree_util.tree_map(lambda x: x.sharding, params)
        )
        lowered = jax.jit(
            jax.value_and_grad(loss_fn), out_shardings=(None, out_sh)
        ).lower(params, tokens)
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    return {
        "schedule": schedule + ("+remat" if remat else "")
        + (f"+v{n_virtual}" if n_virtual > 1 else "")
        + ("" if recompute else "-stash"),
        "n_micro": n_micro,
        "batch": batch,
        "temp_mb": round(stats.temp_size_in_bytes / MB, 2),
        "arg_mb": round(stats.argument_size_in_bytes / MB, 2),
        "out_mb": round(stats.output_size_in_bytes / MB, 2),
        "gflops": round(_flops(compiled) / 1e9, 3),
    }


def _frontier_summary(rows, micros, args) -> int:
    """The speed-memory frontier: temp MB and per-cycle compute units for
    GPipe / 1F1B-recompute / 1F1B-stash.

    XLA's CPU cost analysis counts a ``lax.scan`` (while-loop) body ONCE,
    so a 1F1B program's "flops" is effectively the cost of one steady-state
    cycle body (plus fixed prologue). The two 1F1B variants share an
    identical program skeleton differing only in the B sub-tick — the
    recompute variant's body replays exactly one stage forward that the
    stash variant reads from its rings — so their flop DELTA is a measured
    stage-forward unit, and ``flops / delta`` is each variant's cycle cost
    in forward-units: the ~4 (F + recompute + bwd) vs ~3 (F + stored-vjp
    bwd) the schedule docs quote. GPipe's skeleton (reverse-diffed scan)
    is structurally different, so its flops are reported but not
    normalized into cycle units.
    """
    from distributed_pytorch_example_tpu.parallel.pipeline import (
        gpipe_ticks,
        one_f_one_b_cycles,
    )

    S = 4
    m_ref = micros[-1]

    def sel(name, m):
        return next(r for r in rows
                    if r["schedule"] == name and r["n_micro"] == m)

    def slope(name):
        lo, hi = sel(name, micros[0]), sel(name, micros[-1])
        return (hi["temp_mb"] - lo["temp_mb"]) / (
            hi["n_micro"] - lo["n_micro"])

    # measured stage-forward unit: the only body difference between the
    # two 1F1B variants is the one forward replay per B sub-tick
    unit = (sel("1f1b", m_ref)["gflops"]
            - sel("1f1b-stash", m_ref)["gflops"])

    def cycle_units(name):
        if unit <= 0:
            return None
        return round(sel(name, m_ref)["gflops"] / unit, 2)

    summary = {
        "temp_mb_per_extra_microbatch": {
            n: round(slope(n), 3) for n in ("gpipe", "1f1b", "1f1b-stash")
        },
        "temp_mb_at_m_ref": {
            n: sel(n, m_ref)["temp_mb"]
            for n in ("gpipe", "1f1b", "1f1b-stash")
        },
        "gflops_at_m_ref": {
            n: sel(n, m_ref)["gflops"]
            for n in ("gpipe", "1f1b", "1f1b-stash")
        },
        "stage_fwd_unit_gflops": round(unit, 4),
        "cycle_cost_forward_units": {
            n: cycle_units(n) for n in ("1f1b", "1f1b-stash")
        },
        "schedule_length": {
            "gpipe_ticks": gpipe_ticks(m_ref, S),
            "one_f_one_b_cycles": one_f_one_b_cycles(m_ref, S),
        },
        "n_micro_ref": m_ref,
        "config": {"mb_size": args.mb_size, "seq": args.seq,
                   "mesh": f"data={args.data_span} x pipe=4",
                   "model": "gpt2 256d x 8L", "jax": jax.__version__},
    }
    print(json.dumps(summary), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--micros", default="8,16,32")
    parser.add_argument("--mb-size", type=int, default=4)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--json", default=None)
    parser.add_argument("--data-span", type=int, default=2)
    parser.add_argument(
        "--stash-frontier", action="store_true",
        help="measure the speed-memory frontier instead: GPipe vs "
             "1F1B-recompute vs 1F1B-stash (pipe_recompute=False), with "
             "per-device flops alongside temp memory",
    )
    args = parser.parse_args()

    micros = [int(m) for m in args.micros.split(",")]
    if args.stash_frontier:
        variants = (("gpipe", False, 1, True), ("1f1b", False, 1, True),
                    ("1f1b", False, 1, False))
    else:
        variants = (("gpipe", False, 1, True), ("gpipe", True, 1, True),
                    ("1f1b", False, 1, True), ("1f1b", False, 2, True))
    rows = []
    for schedule, remat, v, rc in variants:
        for m in micros:
            row = measure(schedule, m, args.mb_size, args.seq, remat=remat,
                          n_virtual=v, recompute=rc,
                          data_span=args.data_span)
            rows.append(row)
            print(json.dumps(row), flush=True)

    if args.stash_frontier:
        return _frontier_summary(rows, micros, args)

    # the claim under measurement: GPipe's temp grows with n_micro much
    # faster than 1F1B's (whose activation stash is m-independent)
    def slope(name, remat):
        sel = [r for r in rows
               if r["schedule"] == name + ("+remat" if remat else "")]
        return (sel[-1]["temp_mb"] - sel[0]["temp_mb"]) / (
            sel[-1]["n_micro"] - sel[0]["n_micro"])

    # interleaving's trade, both sides as numbers: the stash-memory cost
    # is MEASURED (temp at fixed m, v=2 vs v=1) and the bubble win is the
    # pinned schedule formula in stage-equivalent time units (cycles are
    # chunk-granular, each ~1/v of a stage)
    from distributed_pytorch_example_tpu.parallel.pipeline import (
        one_f_one_b_cycles,
    )

    def temp(name, m):
        return next(r["temp_mb"] for r in rows
                    if r["schedule"] == name and r["n_micro"] == m)

    m_ref = micros[-1]
    summary = {
        "temp_mb_per_extra_microbatch": {
            "gpipe": round(slope("gpipe", False), 3),
            "gpipe+remat": round(slope("gpipe", True), 3),
            "1f1b": round(slope("1f1b", False), 3),
        },
        "interleaved_v2": {
            "temp_mb_v1": temp("1f1b", m_ref),
            "temp_mb_v2": temp("1f1b+v2", m_ref),
            "stage_equiv_cycles_v1": one_f_one_b_cycles(m_ref, 4, 1),
            "stage_equiv_cycles_v2": one_f_one_b_cycles(m_ref, 4, 2) / 2,
            "n_micro": m_ref,
        },
        "config": {"mb_size": args.mb_size, "seq": args.seq,
                   "mesh": f"data={args.data_span} x pipe=4",
                   "model": "gpt2 256d x 8L"},
    }
    print(json.dumps(summary), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
