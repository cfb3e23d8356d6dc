#!/usr/bin/env python3
"""Benchmark harness: every BASELINE.json config, with MFU.

Default run covers all five BASELINE.json workloads (ResNet-18/CIFAR,
ResNet-50/ImageNet, ViT-B/16, BERT-base MLM, GPT-2 124M) on synthetic
data. One JSON line per model goes to stderr as it completes; stdout gets
exactly ONE JSON line — the driver metric (ResNet-50 samples/sec/chip,
matching BASELINE.json) with every other model's numbers embedded under
``"models"``.

MFU (model FLOPs utilization) comes from XLA's own cost analysis of the
compiled train step (forward + backward + optimizer), divided by measured
step rate x the chip's peak bf16 FLOP/s — so "fast" is judged against the
hardware ceiling, not just a baseline anchor. NB: XLA counts Pallas
custom calls (the flash-attention kernels) as ZERO FLOPs, so LM MFU here
is CONSERVATIVE — at seq 1024 the uncounted attention FLOPs are ~8% of
the GPT-2 step (scripts/bench_longctx.py reports the analytic accounting
where the attention share grows large).

Anchors in ``BASELINES``: 60% of published torch-xla-order rates (the
BASELINE.json north star); order-of-magnitude GUESSES, not measurements —
the reference publishes no numbers (BASELINE.md). ``vs_baseline`` is kept
for the driver's line format but demoted: the stdout line carries a
``vs_baseline_note`` saying so, and MFU/HFU (XLA cost analysis of the
compiled step / chip peak bf16) is the honest utilization metric.

Usage: python bench.py [--models resnet50,gpt2,...] [--model resnet50]
                       [--batch-per-chip N] [--steps N]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# vs_baseline anchors: 60% of published torch-xla-order throughput per chip
BASELINES = {
    "resnet18": ("samples", 6_000.0),   # CIFAR-size images
    "resnet50": ("samples", 600.0),     # BASELINE.json north-star metric
    "vit-b16": ("samples", 500.0),
    "bert-base": ("tokens", 30_000.0),
    "gpt2": ("tokens", 30_000.0),
    # beyond-BASELINE zoo entry (RMSNorm/RoPE/GQA/SwiGLU, ~110M); not in
    # the default sweep — `--model llama` benches it
    "llama": ("tokens", 30_000.0),
}
DEFAULT_MODELS = ("resnet18", "resnet50", "vit-b16", "bert-base", "gpt2")

# peak-FLOPs table and the compiled cost/memory accounting now live in
# telemetry/cost.py (graft-scope's compile-time cost registry); bench
# consumes the same record the Trainer registers at each compile


def _chaos_scenario(scenario, step, state, batch, step_time_s, args) -> dict:
    """Post-timing fault-injection demo (graft-armor, --chaos).

    Runs AFTER the timed window so the headline rate is untouched, and
    drives the SAME compiled executable through the fault — the report's
    ``steady_state_ratio`` (post-fault step time / timed-window step time)
    is the in-bench evidence that recovery costs nothing at steady state
    and triggers no recompile.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.robustness import chaos

    report: dict = {"scenario": scenario}
    if scenario == "nan-step":
        if not any(
            jnp.issubdtype(v.dtype, jnp.floating) for v in batch.values()
        ):
            # LM batches are integer tokens; a NaN can't ride them in
            report["skipped"] = "no float input leaf (token-only batch)"
            return report
        chaos.install(chaos.ChaosPlan(
            faults=[chaos.Fault("nan-batch", step=0)]
        ))
        try:
            poisoned = chaos.corrupt_batch(batch, 0)
        finally:
            chaos.uninstall()
        # snapshot BEFORE the call: the compiled step donates its input
        # state, so the pre-step buffers are gone once it runs
        before = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
        bad_state, metrics = step(state, poisoned)
        report["bad_step"] = float(metrics["bad_step"])
        after = np.asarray(jax.tree_util.tree_leaves(bad_state.params)[0])
        report["params_frozen"] = bool(np.array_equal(before, after))
        clean_state, metrics = step(bad_state, batch)
        report["loss_finite_after"] = bool(
            np.isfinite(float(metrics["loss"]))
        )
        n = max(args.steps // 4, 4)
        t0 = time.perf_counter()
        for _ in range(n):
            clean_state, metrics = step(clean_state, batch)
        float(metrics["loss"])
        report["steady_state_ratio"] = round(
            (time.perf_counter() - t0) / n / step_time_s, 4
        )
    elif scenario == "io-flake":
        import os
        import tempfile

        from distributed_pytorch_example_tpu.train import (
            checkpoint as ckpt_lib,
        )

        chaos.install(chaos.ChaosPlan(
            faults=[chaos.Fault("io-error", path_substr="latest", count=2)]
        ))
        saver = ckpt_lib.AsyncSaver()
        try:
            with tempfile.TemporaryDirectory() as td:
                path = os.path.join(td, "latest_model.ckpt")
                ckpt_lib.save_checkpoint(
                    path, state, epoch=0, loss=0.0, saver=saver
                )
                saver.wait()
                report["checkpoint_written"] = os.path.exists(path)
        finally:
            chaos.uninstall()
        report["io_retries_used"] = saver.io_retries_used
    return report


def _input_plane_probe(batch_np, global_batch, mesh, step_time_s) -> dict:
    """Post-timing graft-intake probe: data_stall_ms / input_stall_frac.

    The timed loop drives a FIXED pre-built device batch (so the headline
    rate measures the step, not the host). This probe runs the real input
    plane once — a DeviceLoader prefetching over an in-memory dataset —
    while the consumer sleeps the measured step time between fetches,
    i.e. the loader sees the same demand pattern training would apply.
    The counters come from the supervised prefetch worker: ms spent on an
    empty queue, and the fraction of fetches that stalled at all.
    """
    import numpy as np

    import distributed_pytorch_example_tpu as dpx

    class _Mem:
        def __init__(self, arrays, n):
            self.arrays, self.n = arrays, n

        def __len__(self):
            return self.n

        def get_batch(self, indices):
            idx = np.asarray(indices) % len(next(iter(self.arrays.values())))
            return {k: v[idx] for k, v in self.arrays.items()}

    steps = 8
    loader = dpx.data.DeviceLoader(
        _Mem(batch_np, global_batch * steps), global_batch, mesh=mesh,
        shuffle=False, prefetch=2, num_shards=1, shard_id=0,
    )
    # cap the simulated compute so the probe stays sub-second even for
    # slow models; the stall FRACTION is what the cap can bias (a shorter
    # sleep under-feeds the prefetcher), never the headline rate
    pause = min(step_time_s, 0.1)
    for _ in loader:
        time.sleep(pause)
    served = max(loader.batches_served, 1)
    return {
        "data_stall_ms": round(loader.data_stall_ms, 3),
        "input_stall_frac": round(loader.stalled_batches / served, 4),
    }


def _shard_cache_probe(cache_mb, mesh, step_time_s) -> dict:
    """Post-timing graft-intake shard-cache probe (--shard-cache-mb).

    Writes a small sealed shard dataset to a temp dir, pins the memmap
    pool far below the shard count (so every epoch would re-touch the
    disk), injects a ``slow-shard-io`` fault at the ``chaos.shard_read``
    site, and drives two epochs of the real input plane. Epoch 1 decodes
    from (slow) disk and stalls; epoch 2 serves every row from the
    in-memory ShardCache — cache hits skip the chaos site along with the
    disk — so its stall fraction collapsing to ~0 is the cache working,
    measured end to end through the supervised prefetch worker.
    """
    import tempfile

    import numpy as np

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.data import streaming
    from distributed_pytorch_example_tpu.robustness import chaos

    rng = np.random.default_rng(0)
    shards, rows, hw, batch = 6, 64, 16, 32
    with tempfile.TemporaryDirectory() as td:
        streaming.write_image_shards(
            td,
            [(rng.integers(0, 256, (rows, hw, hw, 3)).astype(np.uint8),
              rng.integers(0, 10, (rows,)).astype(np.int64))
             for _ in range(shards)],
            shard_size=rows, seal=True,
        )
        ds = streaming.StreamingImageShards(
            td, raw_uint8=True, max_open_shards=2, cache_mb=cache_mb
        )
        chaos.install(chaos.ChaosPlan(faults=[chaos.Fault(
            "slow-shard-io", path_substr="images_",
            count=10_000, delay_s=0.05,
        )]))
        try:
            fracs = []
            for _epoch in range(2):
                loader = dpx.data.DeviceLoader(
                    ds, batch, mesh=mesh, shuffle=False, prefetch=2,
                    num_shards=1, shard_id=0,
                )
                for _ in loader:
                    time.sleep(min(step_time_s, 0.02))
                served = max(loader.batches_served, 1)
                fracs.append(round(loader.stalled_batches / served, 4))
        finally:
            chaos.uninstall()
    report = {
        "input_stall_frac_epoch1": fracs[0],
        "input_stall_frac_epoch2": fracs[1],
    }
    stats = ds.cache_stats
    if stats:
        report.update(stats)
    return report


def run_serve(args) -> dict:
    """--serve: fixed seeded 32-request replay through the paged-KV
    engine (graft-serve), continuous vs static batching.

    The replay is deterministic (seeded lengths, all arrivals at t=0), so
    round-over-round numbers compare the engine, not the workload. Both
    modes run the SAME two compiled programs; the headline metric is
    continuous-batching tokens/sec/chip, with the static-mode rate and
    the continuous/static margin embedded — the margin is the in-bench
    evidence that in-flight insertion actually buys throughput on a
    mixed-length workload.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.serving import (
        InferenceEngine, Request,
    )

    kw = dict(vocab_size=256, max_len=128, model_dim=64, num_layers=2,
              num_heads=4, mlp_dim=128)
    pool = dict(paged_num_blocks=128, paged_block_size=8,
                paged_max_blocks=16)
    slots, n_requests = 4, 32
    params = GPT2(**kw).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = GPT2(**kw, decode=True, **pool)
    n_chips = len(jax.devices())
    print(
        f"bench: serve on {n_chips} {jax.devices()[0].platform} device(s), "
        f"{n_requests} requests, {slots} slots",
        file=sys.stderr,
    )

    rng = np.random.default_rng(0)
    requests = [
        Request(
            rid=f"req{i:03d}",
            prompt=[int(t) for t in rng.integers(
                0, 256, int(rng.integers(4, 25))
            )],
            max_new_tokens=int(rng.integers(8, 33)),
            seed=i,
        )
        for i in range(n_requests)
    ]
    engine = InferenceEngine(
        model, params, num_slots=slots, temperature=1.0, top_k=40,
    )
    # untimed warmup replay compiles the two programs (and the per-bucket
    # prefill variants); the timed replays then measure steady state
    engine.run(requests)
    cont_full = engine.run(requests, mode="continuous")
    cont = cont_full["metrics"]
    stat = engine.run(requests, mode="static")["metrics"]

    # speculative before/after at GREEDY (the config speculation serves
    # in practice: an argmax draft against a temperature-1.0 target
    # accepts ~1% of proposals, so the sampled workload above is the
    # wrong yardstick). Self-speculation + exact-match acceptance keeps
    # the greedy output bit-identical to the plain greedy replay
    # (checked below); the accept rate is ~1.0, shy of it only where a
    # request's final window truncates at its token ceiling.
    greedy_engine = InferenceEngine(
        model, params, num_slots=slots, temperature=0.0,
    )
    greedy_engine.run(requests)  # untimed: compiles the greedy programs
    greedy_full = greedy_engine.run(requests, mode="continuous")
    spec_engine = InferenceEngine(
        model, params, num_slots=slots, temperature=0.0,
        draft_model=model, draft_params=params, spec_tokens=4,
    )
    spec_engine.run(requests)  # untimed: compiles propose/verify
    spec_full = spec_engine.run(requests, mode="continuous")
    spec = spec_full["metrics"]
    spec_exact = all(
        spec_full["results"][r.rid]["tokens"]
        == greedy_full["results"][r.rid]["tokens"]
        for r in requests
    )

    fleet = None
    if getattr(args, "replicas", 1) > 1:
        # graft-fleet replay: the SAME workload through N replicas behind
        # the failover router; position-folded rng means the fleet output
        # must be bit-identical to the single-engine run above
        from distributed_pytorch_example_tpu.serving import (
            FleetRouter, ReplicaHandle,
        )

        engines = [
            InferenceEngine(
                model, params, num_slots=slots, temperature=1.0, top_k=40,
            )
            for _ in range(args.replicas)
        ]
        handles = [
            ReplicaHandle(f"r{i}", e) for i, e in enumerate(engines)
        ]
        frep = FleetRouter(handles).run(requests)
        fm = frep["metrics"]
        exact = all(
            frep["results"][r.rid]["tokens"]
            == cont_full["results"][r.rid]["tokens"]
            for r in requests
        )
        fleet = {
            "replicas": args.replicas,
            "tokens_per_sec_per_chip": round(
                fm["tokens_per_sec"] / n_chips, 2
            ),
            "completed": fm["completed"],
            "token_exact_vs_single_engine": exact,
            # graft-swap roll summary (serve.py --publish-dir wires a
            # live controller; this replay runs none, so the defaults
            # report a fleet that never swapped)
            "weights_version": fm.get("weights_version", "v0"),
            "swaps_completed": fm.get("swaps_completed", 0),
            "swap_blackout_ms": (
                round(fm["swap_blackout_ms"], 3)
                if fm.get("swap_blackout_ms") is not None else None
            ),
            "replay_cross_version_exact": fm["replay_cross_version_exact"],
            "steady_per_row_ms": (
                round(fm["steady_per_row_ms"], 3)
                if fm["steady_per_row_ms"] is not None else None
            ),
            "per_replica_occupancy": {
                rep: round(stats["occupancy"], 4)
                for rep, stats in fm["per_replica"].items()
            },
        }

    rate = cont["tokens_per_sec"] / n_chips
    result = {
        "metric": "serve_tokens_per_sec_per_chip",
        "value": round(rate, 2),
        "unit": "tokens/sec/chip",
        "ttft_ms_p50": round(cont["ttft_ms"]["p50"], 3),
        "ttft_ms_p95": round(cont["ttft_ms"]["p95"], 3),
        "tpot_ms_p50": round(cont["tpot_ms"]["p50"], 3),
        "tpot_p99_ms": round(cont["tpot_ms"]["p99"], 3),
        "decode_tokens_per_sec": round(cont["decode_tokens_per_sec"], 2),
        "spec_accept_rate": (
            round(spec["spec_accept_rate"], 4)
            if spec["spec_accept_rate"] is not None else None
        ),
        "spec": {
            "spec_tokens": 4,
            "temperature": 0.0,
            "decode_tokens_per_sec": round(
                spec["decode_tokens_per_sec"], 2
            ),
            "speedup_vs_greedy_decode": (
                round(
                    spec["decode_tokens_per_sec"]
                    / greedy_full["metrics"]["decode_tokens_per_sec"], 3
                ) if greedy_full["metrics"]["decode_tokens_per_sec"]
                else None
            ),
            "token_exact_vs_greedy": spec_exact,
        },
        "slot_occupancy": round(cont["slot_occupancy"], 4),
        "static_tokens_per_sec_per_chip": round(
            stat["tokens_per_sec"] / n_chips, 2
        ),
        "continuous_vs_static": round(
            cont["tokens_per_sec"] / stat["tokens_per_sec"], 3
        ),
        "decode_steps": {
            "continuous": cont["decode_steps"],
            "static": stat["decode_steps"],
        },
        "completed": cont["completed"],
        **({"fleet": fleet} if fleet is not None else {}),
        "config": {
            "requests": n_requests, "slots": slots,
            "num_blocks": pool["paged_num_blocks"],
            "block_size": pool["paged_block_size"],
            "max_blocks": pool["paged_max_blocks"],
            "prompt_len": "4:24", "max_new": "8:32",
            "temperature": 1.0, "top_k": 40, "seed": 0,
        },
    }
    print(json.dumps(result), file=sys.stderr)
    return result


def run_model(name: str, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributed_pytorch_example_tpu as dpx

    lm = name.startswith(("gpt", "bert", "llama"))
    batch_per_chip = args.batch_per_chip or (16 if lm else 128)
    if name == "resnet18":
        image_size, num_classes = 32, 10  # BASELINE config 1: CIFAR-10
        batch_per_chip = args.batch_per_chip or 256
    else:
        image_size, num_classes = args.image_size, 1000

    n_chips = len(jax.devices())
    print(
        f"bench: {name} on {n_chips} {jax.devices()[0].platform} device(s), "
        f"batch/chip={batch_per_chip}",
        file=sys.stderr,
    )

    pipelined = args.mesh_pipe > 1
    if pipelined:
        if not name.startswith(("gpt", "llama")):
            raise ValueError(
                f"--mesh-pipe applies to gpt2/llama only, not {name!r}"
            )
        mesh = dpx.runtime.make_mesh(
            dpx.runtime.MeshSpec(
                data=n_chips // args.mesh_pipe, pipe=args.mesh_pipe
            )
        )
        from distributed_pytorch_example_tpu.parallel.partition import (
            transformer_partitioner,
        )

        partitioner = transformer_partitioner(mesh)
    else:
        mesh = dpx.runtime.make_mesh()
        partitioner = dpx.parallel.data_parallel(
            mesh, dp_shard_opt_state=args.zero1
        )
    # graft-wire: compress the gradient collectives (parallel/wire.py);
    # --overlap-buckets additionally opts the sync into the bucketed
    # comm/compute-overlap schedule (-1 = the 4 MiB default target)
    from distributed_pytorch_example_tpu.parallel.wire import (
        DEFAULT_BUCKET_BYTES,
    )

    bucket_bytes = (
        DEFAULT_BUCKET_BYTES if args.overlap_buckets < 0
        else args.overlap_buckets
    )
    partitioner.wire = dpx.parallel.WireConfig(
        compress=args.wire, block_size=args.wire_block,
        bucket_bytes=bucket_bytes,
    )
    global_batch = batch_per_chip * n_chips
    if batch_per_chip % args.grad_accum:
        raise ValueError(
            f"--grad-accum {args.grad_accum} must divide the per-chip "
            f"batch ({batch_per_chip} for {name}; set --batch-per-chip)"
        )
    rng = np.random.default_rng(0)
    if lm:
        flags_apply = True
        overrides = {"dtype": jnp.bfloat16}
        if args.lm_loss == "fused":
            # fused chunked-CE: hidden states out, vocab-blockwise loss
            overrides["logits_mode"] = "hidden"
        if args.remat:
            overrides["remat"] = True
        if args.flash != "auto":
            overrides["use_flash"] = args.flash == "on"
        if pipelined:
            # pipeline-schedule ablation: gpipe vs 1f1b (recompute) vs
            # 1f1b --pipe-no-recompute (stash) on the same mesh
            overrides["pipe_axis"] = "pipe"
            overrides["pipe_schedule"] = args.pipe_schedule
            overrides["pipe_microbatches"] = args.pipe_microbatches
            if args.pipe_no_recompute:
                overrides["pipe_recompute"] = False
        model = dpx.models.get_model(name, **overrides)
        seq_len = min(args.seq_len, model.max_len)  # BERT caps at 512
        if seq_len != args.seq_len:
            print(
                f"bench: clamping seq-len {args.seq_len} -> {seq_len} "
                f"({name} max_len)",
                file=sys.stderr,
            )
        if name.startswith("bert"):
            task = dpx.train.MLMTask(
                vocab_size=model.vocab_size, mask_token_id=103
            )
        else:
            task = dpx.train.CausalLMTask()
        batch_np = {
            "tokens": rng.integers(
                0, model.vocab_size, (global_batch, seq_len)
            ).astype(np.int32),
        }
    else:
        overrides = {"num_classes": num_classes, "dtype": jnp.bfloat16}
        if name == "vit-b16":
            # forward the ablation flags so --flash/--remat actually ablate
            # on the transformer vision model (VERDICT r3 weak #3: silently
            # ignoring them is how the r3 ViT regression went unnoticed)
            flags_apply = True
            if args.remat:
                overrides["remat"] = True
            if args.flash != "auto":
                overrides["use_flash"] = args.flash == "on"
        else:
            flags_apply = False
            if args.remat or args.flash != "auto":
                print(
                    f"bench: NOTE --flash/--remat do not apply to {name} "
                    f"(no attention / no remat knob); running the plain "
                    f"config",
                    file=sys.stderr,
                )
        model = dpx.models.get_model(name, **overrides)
        task = dpx.train.ClassificationTask()
        batch_np = {
            "x": rng.standard_normal(
                (global_batch, image_size, image_size, 3)
            ).astype(np.float32),
            "y": rng.integers(0, num_classes, (global_batch,)).astype(np.int32),
        }
    picked_plan = None
    if args.auto_mesh:
        # graft-plan: replace the flag-built mesh/partitioner with the
        # static oracle's pick (the batch shapes above are plan-neutral)
        if (
            pipelined or args.zero1 or args.wire != "none"
            or args.overlap_buckets
        ):
            raise ValueError(
                "--auto-mesh replaces --mesh-pipe/--zero1/--wire/"
                "--overlap-buckets; drop those flags"
            )
        from distributed_pytorch_example_tpu.analysis import (
            envelope,
            planner,
        )

        batch_abs = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in batch_np.items()
        }
        best, _ = planner.pick_train_plan(
            model, task, optax.adam(1e-3),
            batch_abs["tokens" if lm else "x"], batch_abs,
            kind="lm" if lm else "image",
            program=f"train/{name}",
            hbm_limit=envelope.hbm_limit_from_env(),
            wire_block=args.wire_block,
            log=lambda m: print(m, file=sys.stderr),
        )
        if best is None:
            raise ValueError(f"--auto-mesh: no feasible plan for {name}")
        picked_plan = best.plan.name()
        print(
            f"bench: --auto-mesh picked {best.plan.name()} "
            f"(tier {best.tier}, cost {best.cost_ms():.4f} ms)",
            file=sys.stderr,
        )
        mesh = dpx.runtime.make_mesh(best.plan.mesh)
        partitioner = best.plan.lower(mesh=mesh)
    trainer = dpx.train.Trainer(
        model, task, optax.adam(1e-3), partitioner=partitioner,
        grad_accum_steps=args.grad_accum,
    )
    sharding = partitioner.batch_sharding()
    batch = {
        k: jax.make_array_from_process_local_data(sharding, v)
        for k, v in batch_np.items()
    }

    with mesh:
        trainer.init(batch["tokens" if lm else "x"])
        # the ZeRO-1 observable: per-chip optimizer-state residency
        # (shrinks ~1/n_chips under --zero1 vs the replicated update)
        opt_bytes = dpx.train.opt_state_bytes_per_chip(
            trainer.state.opt_state
        )
        reshard_report = None
        if args.reshard_from:
            # graft-elastic: reload a (possibly other-mesh) checkpoint onto
            # THIS run's mesh and report the cost — reshard_ms is the full
            # reassemble + re-slice wall time, resume_gap_steps the
            # optimizer steps the restored cursor trails the newest
            # on-disk version by (None when unknowable)
            from distributed_pytorch_example_tpu.robustness import elastic
            from distributed_pytorch_example_tpu.train import (
                checkpoint as ckpt_lib,
            )

            t0 = time.perf_counter()
            restored, r_epoch, r_extra = ckpt_lib.load_checkpoint(
                args.reshard_from, trainer.state, trainer.state_shardings
            )
            # value fetch: a device->host transfer of a restored leaf is an
            # unambiguous fence for the timer
            np.asarray(jax.tree_util.tree_leaves(restored.params)[0])
            reshard_ms = (time.perf_counter() - t0) * 1000.0
            trainer.state = restored
            reshard_report = {
                "reshard_ms": round(reshard_ms, 3),
                "resume_gap_steps": elastic.resume_gap_steps(
                    args.reshard_from, r_epoch, r_extra
                ),
                "restored_epoch": r_epoch,
            }
        # AOT-compile once and drive the SAME executable for warmup and the
        # timed loop (a separate jit call would compile a second copy)
        step = trainer.train_step.lower(trainer.state, batch).compile()
        from distributed_pytorch_example_tpu.telemetry import (
            compiled_cost_record,
        )

        cost = compiled_cost_record(step, jax.devices()[0])
        flops_per_step = cost["flops_per_step_per_device"]
        if flops_per_step is None:
            print("bench: cost_analysis unavailable", file=sys.stderr)
        state = trainer.state
        for _ in range(args.warmup):
            state, metrics = step(state, batch)
        # fence by fetching a VALUE: the loss of the last dispatched step
        # cannot reach the host before the whole step chain has run
        float(metrics["loss"])

        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, metrics = step(state, batch)
        float(metrics["loss"])
        elapsed = time.perf_counter() - t0

        chaos_report = (
            _chaos_scenario(
                args.chaos, step, state, batch, elapsed / args.steps, args
            )
            if args.chaos != "none"
            else None
        )

        # post-timing probes run unguarded: one that cannot run on this
        # device is a fault to repair, not an entry to leave out
        intake_report = _input_plane_probe(
            batch_np, global_batch, mesh, elapsed / args.steps
        )

        cache_report = None
        if args.shard_cache_mb > 0:
            cache_report = _shard_cache_probe(
                args.shard_cache_mb, mesh, elapsed / args.steps
            )

    samples_per_sec = global_batch * args.steps / elapsed
    unit_kind, baseline = BASELINES[name]
    if unit_kind == "tokens":
        rate = samples_per_sec * seq_len / n_chips
        unit = "tokens/sec/chip"
    else:
        rate = samples_per_sec / n_chips
        unit = "samples/sec/chip"
    step_time_ms = elapsed / args.steps * 1000.0
    result = {
        "metric": f"{name.replace('-', '_')}_{unit_kind}_per_sec_per_chip",
        "value": round(rate, 2),
        "unit": unit,
        "vs_baseline": round(rate / baseline, 3),
        "opt_state_bytes_per_chip": opt_bytes,
        "step_time_ms": round(step_time_ms, 3),
        # graft-wire analytic accounting (parallel/wire.py
        # grad_wire_report): per-device gradient-sync payload bytes per
        # step and the fp32/compressed ratio (1.0 when --wire none)
        "grad_wire_bytes_per_step": (
            trainer.wire_report["grad_wire_bytes_per_step"]
            if trainer.wire_report else None
        ),
        "wire_compression_ratio": (
            trainer.wire_report["wire_compression_ratio"]
            if trainer.wire_report else None
        ),
        # compiler-reported HBM residency of the step (args+out+temps−alias;
        # telemetry/cost.py) — None when the backend can't answer
        "hbm_peak_bytes": cost["hbm_peak_bytes"],
        # self-describing config: round-over-round numbers are auditable
        # (VERDICT r3 weak #7 — r2->r3 batch/steps drift went unrecorded).
        # flash/remat appear only for models that CONSUMED the flags, so
        # the record describes the run, not the command line.
        "config": {
            "batch_per_chip": batch_per_chip,
            "steps": args.steps,
            "warmup": args.warmup,
            "grad_accum": args.grad_accum,
            "zero1": args.zero1,
            **(
                {"wire": args.wire, "wire_block": args.wire_block}
                if args.wire != "none"
                else {}
            ),
            **(
                {"overlap_buckets": bucket_bytes} if bucket_bytes else {}
            ),
            **(
                {"shard_cache_mb": args.shard_cache_mb}
                if args.shard_cache_mb
                else {}
            ),
            **(
                {"flash": args.flash, "remat": args.remat}
                if flags_apply
                else {}
            ),
            **(
                {"seq_len": seq_len, "lm_loss": args.lm_loss}
                if lm
                else {"image_size": image_size}
            ),
            **(
                {
                    "mesh_pipe": args.mesh_pipe,
                    "pipe_schedule": args.pipe_schedule,
                    "pipe_recompute": not args.pipe_no_recompute,
                }
                if pipelined
                else {}
            ),
            **({"chaos": args.chaos} if args.chaos != "none" else {}),
            **({"auto_mesh": picked_plan} if picked_plan else {}),
        },
    }
    # scheduler-level overlap estimate from the static bucket plan
    # (telemetry/overlap.py scheduled_overlap), gateable on the CPU mesh;
    # non-None only when --overlap-buckets armed the bucketed sync. What a
    # chip measured is the benchmark's collective_exposed_share.
    result["overlap_frac_scheduled"] = (
        trainer.overlap_report["overlap_frac_scheduled"]
        if trainer.overlap_report else None
    )
    if trainer.overlap_report is not None:
        result["overlap_scheduled"] = {
            k: trainer.overlap_report[k]
            for k in (
                "num_buckets", "hideable_wire_bytes", "total_wire_bytes",
            )
        }
    if args.zero1:
        # measured HLO collective accounting of the SAME compiled step
        # (result-buffer proxy, analysis/collectives.py) — the committed
        # scaling curves (scripts/scaling_sweep.py) plot this against the
        # analytic graft-prove payload prediction above
        from distributed_pytorch_example_tpu.analysis.collectives import (
            parse_collectives,
        )

        result["hlo_collectives"] = parse_collectives(step.as_text())
    if cache_report is not None:
        # graft-intake shard-cache evidence: epoch-2 stall collapse +
        # hit/eviction counters from the end-to-end probe
        result["shard_cache"] = cache_report
    if chaos_report is not None:
        result["chaos"] = chaos_report
    if intake_report is not None:
        # graft-intake input-plane health (post-timing probe, not the
        # timed window): consumer-side prefetch-queue stalls
        result.update(intake_report)
    if reshard_report is not None:
        result["reshard_ms"] = reshard_report["reshard_ms"]
        result["resume_gap_steps"] = reshard_report["resume_gap_steps"]
        result["restored_epoch"] = reshard_report["restored_epoch"]
        result["config"]["reshard_from"] = args.reshard_from
    peak = cost.get("peak_bf16_flops")
    if flops_per_step is not None and peak is not None:
        # cost_analysis is of the per-device partitioned executable, so
        # this is already per-chip utilization — no n_chips division.
        # Under --remat the executable's FLOPs include recomputation, so
        # the honest name is HFU (hardware), not MFU (model) — but only
        # when this model actually consumed the flag.
        steps_per_sec = args.steps / elapsed
        util = round(flops_per_step * steps_per_sec / peak, 4)
        result["hfu" if (args.remat and flags_apply) else "mfu"] = util
        result["flops_per_step_per_chip"] = flops_per_step
    # same quantity graft-scope logs per step (CostRegistry.mfu_analytic):
    # XLA-counted FLOPs / measured step time / peak bf16; null off-TPU
    result["mfu_analytic"] = (
        round(flops_per_step / (step_time_ms / 1000.0) / peak, 4)
        if flops_per_step is not None and peak is not None
        else None
    )
    print(
        f"bench: {name}: {elapsed:.2f}s for {args.steps} steps "
        f"({samples_per_sec:.1f} samples/s total)",
        file=sys.stderr,
    )
    print(json.dumps(result), file=sys.stderr)
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None,
                        help="single model (overrides --models)")
    parser.add_argument("--models", default=",".join(DEFAULT_MODELS),
                        help="comma-separated; default: every BASELINE config")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--batch-per-chip", type=int, default=None,
                        help="default: 128 (vision), 256 (resnet18), 16 (LM)")
    parser.add_argument("--warmup", type=int, default=8,
                        help="untimed steady-state steps before timing")
    parser.add_argument("--steps", type=int, default=40,
                        help="timed steps; short windows under-measure "
                        "(dispatch ramp-up is amortized over fewer steps)")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialized transformer blocks (LM models)")
    parser.add_argument("--flash", default="auto",
                        choices=("auto", "on", "off"),
                        help="Pallas flash attention (LM models)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="microbatches accumulated inside the step "
                        "before ONE gradient collective (train/step.py)")
    parser.add_argument("--wire", default="none",
                        choices=("none", "int8-block"),
                        help="graft-wire gradient-collective compression "
                        "(int8 payloads + per-block bf16 scales; "
                        "parallel/wire.py)")
    parser.add_argument("--wire-block", type=int, default=256,
                        help="elements per bf16 scale block for "
                        "--wire int8-block")
    parser.add_argument("--overlap-buckets", type=int, default=0,
                        metavar="BYTES",
                        help="bucketed comm/compute overlap for the "
                        "gradient sync (parallel/wire.py sync_grads): "
                        "target bucket payload bytes; -1 = the 4 MiB "
                        "default, 0 = the inline per-leaf path")
    parser.add_argument("--shard-cache-mb", type=int, default=0,
                        metavar="MB",
                        help="arm the in-memory decoded-shard cache probe "
                        "(data/intake.py ShardCache): drives two epochs "
                        "of the real streaming input plane under a "
                        "slow-shard-io fault and records the epoch-2 "
                        "stall fraction collapsing to ~0")
    parser.add_argument("--auto-mesh", action="store_true",
                        help="graft-plan: pick mesh + partitioner per model "
                        "via the static three-tier oracle "
                        "(analysis/planner.py) instead of "
                        "--mesh-pipe/--zero1/--wire; DPX_HBM_LIMIT gates "
                        "would-OOM plans pre-compile")
    parser.add_argument("--zero1", action="store_true",
                        help="ZeRO-1: reduce-scatter grads, shard the "
                        "optimizer state over data, all-gather params")
    parser.add_argument("--lm-loss", default="fused",
                        choices=("fused", "dense"),
                        help="LM loss path: fused chunked-CE (default) or "
                        "dense materialized logits")
    parser.add_argument("--mesh-pipe", type=int, default=1,
                        help=">1: pipeline-parallel ablation over a "
                        "data x pipe mesh (gpt2/llama; needs that many "
                        "devices to divide the chip count)")
    parser.add_argument("--pipe-schedule", default="1f1b",
                        choices=("gpipe", "1f1b"),
                        help="schedule for the --mesh-pipe ablation")
    parser.add_argument("--pipe-microbatches", type=int, default=0,
                        help="microbatches for the --mesh-pipe ablation "
                        "(0 = auto)")
    parser.add_argument("--pipe-no-recompute", action="store_true",
                        help="1f1b activation-stash backward (no stage "
                        "replay) for the --mesh-pipe ablation")
    parser.add_argument("--reshard-from", default=None, metavar="CKPT",
                        help="load this checkpoint (either format, any "
                        "stamped mesh shape) onto the bench mesh before "
                        "timing (graft-elastic); records reshard_ms (full "
                        "reassemble + re-slice wall time) and "
                        "resume_gap_steps, and runs the timed loop from "
                        "the restored state")
    parser.add_argument("--serve", action="store_true",
                        help="serving bench instead of training: fixed "
                        "32-request replay through the paged-KV "
                        "continuous-batching engine (graft-serve); the "
                        "stdout line carries continuous tokens/sec/chip "
                        "plus TTFT percentiles and the continuous/static "
                        "margin")
    parser.add_argument("--replicas", type=int, default=1,
                        help="with --serve: additionally replay the same "
                        "workload through N fleet replicas behind the "
                        "failover router (graft-fleet) and report fleet "
                        "throughput + bit-exactness vs the single engine")
    parser.add_argument("--chaos", default="none",
                        choices=("none", "nan-step", "io-flake"),
                        help="post-timing fault-injection demo (graft-"
                        "armor): drive the same compiled step through a "
                        "NaN batch (update predicated out, no recompile) "
                        "or retried checkpoint I/O; adds a 'chaos' block "
                        "to the record without touching the headline rate")
    args = parser.parse_args()
    from distributed_pytorch_example_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    if args.serve:
        print(json.dumps(run_serve(args)))
        return
    if args.warmup < 1 or args.steps < 1:
        parser.error("--warmup and --steps must be >= 1")
    if args.grad_accum < 1:
        parser.error("--grad-accum must be >= 1")
    if args.pipe_no_recompute and (
        args.mesh_pipe <= 1 or args.pipe_schedule != "1f1b"
    ):
        parser.error("--pipe-no-recompute needs --mesh-pipe > 1 and "
                     "--pipe-schedule 1f1b")
    names = [args.model] if args.model else args.models.split(",")
    for n in names:
        if n not in BASELINES:
            parser.error(f"unknown model {n!r}; choices: {list(BASELINES)}")

    # one failed model fails the run: no retry, no "error" entry beside an
    # exit code of 0
    results = {name: run_model(name, args) for name in names}

    # the driver metric stays ResNet-50 (BASELINE.json); the first model
    # stands in when it wasn't benchmarked
    primary = results.get("resnet50") or next(iter(results.values()))
    line = dict(primary)
    line["vs_baseline_note"] = (
        "anchor is a guessed 60%-of-published-torch-xla-order rate, not a "
        "measurement (the reference publishes none, BASELINE.md); mfu = "
        "XLA-counted step FLOPs / peak bf16 is the honest metric"
    )
    if len(results) > 1:
        line["models"] = results
    print(json.dumps(line))


if __name__ == "__main__":
    main()
