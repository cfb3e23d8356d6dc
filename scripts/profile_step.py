#!/usr/bin/env python3
"""Per-op device-time breakdown of an LM train step (xplane -> hlo_stats).

The working profiling recipe for this environment: the tensorboard-plugin
convert wrapper is broken by a protobuf clash, but the underlying pywrap
converter works — trace a few steps, convert the xplane to hlo_stats, and
aggregate self-times by (framework op, HLO category) with the compiler's
own Compute/HBM/VMEM "Bound by" attribution. This is the tool behind the
round-3/-4 perf findings (chunked-CE scan overhead, flash share at 16k,
the r4 LM-MFU residual analysis in results/lm_mfu_analysis/).

Usage:
    python scripts/profile_step.py --model gpt2 --seq-len 1024 --batch 16
    python scripts/profile_step.py --seq-len 16384 --batch 1 --remat
    python scripts/profile_step.py --zero1 --grad-accum 4  # RS+AG sync
    python scripts/profile_step.py --zero1 --wire int8-block  # graft-wire

Before tracing, prints the compiled step's collective mix (kind, count,
result bytes, per-dtype byte split) to stderr — the quick check that the
gradient sync is the one you asked for (ZeRO-1: reduce-scatter +
all-gather, no gradient all-reduce; replicated: all-reduce; --wire
int8-block: s8 all-to-all payloads plus the analytic graft-wire
bytes-on-the-wire report and compression ratio).
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="gpt2")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--image-size", type=int, default=32)
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--zero1", action="store_true",
                        help="ZeRO-1 gradient sync (reduce-scatter + "
                        "sharded update + all-gather)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="in-step microbatch accumulation")
    parser.add_argument("--wire", default="none",
                        choices=("none", "int8-block"),
                        help="graft-wire collective compression (int8 "
                        "payloads + per-block bf16 scales on the grad sync)")
    parser.add_argument("--wire-block", type=int, default=256,
                        help="elements per bf16 scale block for "
                        "--wire int8-block")
    parser.add_argument("--trace-dir", default="/tmp/profile_step")
    parser.add_argument("--trace-steps", type=int, default=3)
    parser.add_argument("--top", type=int, default=30)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.train.tasks import (
        CausalLMTask,
        ClassificationTask,
    )

    # drive the SAME Trainer train step train.py runs, so the breakdown
    # explains that step rather than a near-copy of it
    rng = np.random.default_rng(0)
    is_vision = args.model.startswith(("resnet", "vit", "mlp"))
    if is_vision:
        overrides = {"dtype": jnp.bfloat16, "num_classes": args.num_classes}
        if args.remat:  # vit supports it; unsupported models fail loudly
            overrides["remat"] = True
        model = dpx.models.get_model(args.model, **overrides)
        task = ClassificationTask()
        n = args.batch * len(jax.devices())
        batch_np = {
            "x": rng.standard_normal(
                (n, args.image_size, args.image_size, 3)
            ).astype(np.float32),
            "y": rng.integers(0, args.num_classes, (n,)).astype(np.int32),
        }
        sample_key = "x"
    else:
        model = dpx.models.get_model(
            args.model, dtype=jnp.bfloat16, logits_mode="hidden",
            max_len=args.seq_len, remat=args.remat,
        )
        task = CausalLMTask()
        batch_np = {
            "tokens": rng.integers(
                0, model.vocab_size,
                (args.batch * len(jax.devices()), args.seq_len),
            ).astype(np.int32)
        }
        sample_key = "tokens"
    mesh = dpx.runtime.make_mesh()
    partitioner = dpx.parallel.data_parallel(
        mesh, dp_shard_opt_state=args.zero1,
        wire=dpx.parallel.WireConfig(
            compress=args.wire, block_size=args.wire_block
        ),
    )
    trainer = dpx.train.Trainer(
        model, task, optax.adam(1e-3), partitioner=partitioner,
        grad_accum_steps=args.grad_accum,
    )
    batch = {
        k: jax.make_array_from_process_local_data(
            partitioner.batch_sharding(), v
        )
        for k, v in batch_np.items()
    }
    with mesh:
        trainer.init(batch[sample_key])
        compiled = trainer.train_step.lower(trainer.state, batch).compile()
        # what the gradient sync compiled to — ZeRO-1 should show
        # reduce-scatter + all-gather, replicated mode all-reduce only
        from distributed_pytorch_example_tpu.analysis.collectives import (
            parse_collective_dtypes,
            parse_collectives,
        )

        hlo = compiled.as_text()
        comms = parse_collectives(hlo)
        dtypes = parse_collective_dtypes(hlo)
        print("step collectives (kind: count / result bytes [dtype mix]):",
              file=sys.stderr)
        for kind, rec in sorted(comms.items()):
            mix = ", ".join(
                f"{dt}={b}" for dt, b in sorted(dtypes.get(kind, {}).items())
            )
            print(f"  {kind}: {rec['count']} / {rec['bytes']} [{mix}]",
                  file=sys.stderr)
        if not comms:
            print("  (none — single-device program)", file=sys.stderr)
        if args.wire != "none" and trainer.wire_report is not None:
            # analytic ring-model wire bytes (HLO result buffers under-
            # count the a2a payload; parallel/wire.py grad_wire_report)
            wr = trainer.wire_report
            print(
                f"graft-wire: compress={wr['compress']} grad sync "
                f"{wr['grad_wire_bytes_per_step']:,} B/step/device "
                f"(fp32 {wr['grad_wire_bytes_per_step_fp32']:,}, "
                f"ratio {wr['wire_compression_ratio']:.2f}x)",
                file=sys.stderr,
            )
        from distributed_pytorch_example_tpu.telemetry import (
            compiled_cost_record,
        )

        cost = compiled_cost_record(compiled, jax.devices()[0])
        print(
            f"compiled cost: flops/device={cost['flops_per_step_per_device']}"
            f" hbm_peak_bytes={cost['hbm_peak_bytes']}"
            f" code_bytes={cost.get('code_bytes')}",
            file=sys.stderr,
        )
        state = trainer.state
        metrics = None
        for _ in range(3):
            state, metrics = compiled(state, batch)
        float(metrics["loss"])  # value-fetch fence
        t0 = time.perf_counter()
        for _ in range(10):
            state, metrics = compiled(state, batch)
        float(metrics["loss"])
        dt = (time.perf_counter() - t0) / 10
        rate = (
            f"{batch_np[sample_key].shape[0]/dt:.0f} samples/s"
            if is_vision
            else f"{batch_np[sample_key].size/dt:.0f} tokens/s"
        )
        print(f"step {dt*1e3:.1f} ms, {rate}", file=sys.stderr)

        shutil.rmtree(args.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(args.trace_dir)
        for _ in range(args.trace_steps):
            state, metrics = compiled(state, batch)
        float(metrics["loss"])
        jax.profiler.stop_trace()

    # NB: import AFTER the run — tensorflow is heavy and only needed here
    from tensorflow.python.profiler.internal import (  # noqa: PLC0415
        _pywrap_profiler_plugin as pywrap,
    )

    paths = glob.glob(
        os.path.join(args.trace_dir, "plugins/profile/*/*.xplane.pb")
    )
    data, _ = pywrap.xspace_to_tools_data(paths, "hlo_stats", {})
    d = json.loads(data)
    labels = (
        d["cols"] if isinstance(d["cols"][0], str)
        else [c["label"] for c in d["cols"]]
    )
    cols = {c: i for i, c in enumerate(labels)}
    # fail LOUDLY on a column rename — a positional fallback would print a
    # plausible but wrong breakdown, the exact failure this tool exists
    # to avoid
    for required in ("Framework op name", "HLO op category",
                     "Total self time (us)"):
        if required not in cols:
            raise SystemExit(
                f"hlo_stats columns changed: {required!r} not in {labels}"
            )

    agg = collections.defaultdict(float)
    bound = {}
    total = 0.0
    for row in d["rows"]:
        r = row["c"] if isinstance(row, dict) else row
        vals = [x.get("v") if isinstance(x, dict) else x for x in r]
        name = str(vals[cols["Framework op name"]])
        cat = str(vals[cols["HLO op category"]])
        t = float(vals[cols["Total self time (us)"]] or 0)
        b = str(vals[cols["Bound by"]]) if "Bound by" in cols else "?"
        key = re.sub(r"layers_\d+|layer_\d+|_\d+", "", name)[:90] + " | " + cat
        agg[key] += t
        bound[key] = b
        total += t
    print(
        f"TOTAL self time: {total/1e3:.1f} ms over {args.trace_steps} steps"
    )
    for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"{v/total*100:5.1f}%  {v/1e3:8.2f}ms  "
              f"[{bound.get(k, '?'):9s}] {k}")


if __name__ == "__main__":
    main()
