#!/usr/bin/env python3
"""Distributed training CLI — TPU-native counterpart of reference train.py.

Default invocation (``python train.py``) reproduces the reference's default
config (reference train.py:214-218): SimpleNet MLP, 10 epochs, per-replica
batch 64, Adam lr=1e-3, 10,000 synthetic samples, train:val 10:1, best/latest
checkpoints, epoch-granularity resume — running as one compiled XLA program
per step on whatever devices are present (CPU, one TPU chip, or a multi-host
TPU slice via the launch/entrypoint.sh topology contract).

Model/dataset/mesh selection beyond the reference is via the framework flags
(--model, --dataset, --mesh-*, --partition, --dtype); see
``distributed_pytorch_example_tpu/utils/config.py``.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax.numpy as jnp
import optax

import distributed_pytorch_example_tpu as dpx
from distributed_pytorch_example_tpu.runtime.logging import get_logger
from distributed_pytorch_example_tpu.telemetry import trace as span_lib

logger = get_logger(__name__)


def build_dataset(args, num_samples: int, seed: int, train: bool = True):
    from distributed_pytorch_example_tpu import data as dpx_data

    name = args.dataset
    if name == "synthetic":
        return dpx_data.SyntheticClassificationDataset(
            num_samples=num_samples, num_classes=args.num_classes, seed=seed
        )
    if name in ("synthetic-image", "cifar10-synthetic"):
        return dpx_data.SyntheticImageDataset(
            num_samples=num_samples,
            image_size=args.image_size,
            num_classes=args.num_classes,
            seed=seed,
        )
    if name == "synthetic-tokens":
        # the model's own vocabulary (the slice it was told, where it holds
        # a share); BERT's for a model that states none
        fields = getattr(
            dpx.models.model_class(args.model), "__dataclass_fields__", {}
        )
        vocab = args.vocab_slice or (
            fields["vocab_size"].default if "vocab_size" in fields else 30522
        )
        return dpx_data.SyntheticTokenDataset(
            num_samples=num_samples, seq_len=args.seq_len, vocab_size=vocab, seed=seed
        )
    if name == "cifar10":
        from distributed_pytorch_example_tpu.data.vision import load_cifar10

        return load_cifar10(train=train, data_dir=args.data_dir)
    if name == "digits":
        from distributed_pytorch_example_tpu.data.vision import load_digits

        return load_digits(train=train)
    if name == "image-shards":
        from distributed_pytorch_example_tpu.data.streaming import (
            StreamingImageShards,
        )
        from distributed_pytorch_example_tpu.data.vision import _data_root

        sub = "train" if train else "val"
        # ship raw uint8 all the way to the device (4x less H2D than f32;
        # [0,1] scaling runs inside the step, tasks.dequantize_inputs) —
        # this also keeps augmentation on uint8, where the native C++
        # resized-crop kernel serves it
        return StreamingImageShards(
            os.path.join(_data_root(args.data_dir), "image-shards", sub),
            raw_uint8=True,
            cache_mb=args.shard_cache_mb,
        )
    if name == "tokens-file":
        from distributed_pytorch_example_tpu.data.text import load_token_file
        from distributed_pytorch_example_tpu.data.vision import _data_root

        fname = "train.bin" if train else "val.bin"
        return load_token_file(
            os.path.join(_data_root(args.data_dir), fname),
            seq_len=args.seq_len,
            dtype=args.token_dtype,
        )
    raise ValueError(f"Unknown dataset {name!r}")


def build_task(args, model):
    from distributed_pytorch_example_tpu import train as dpx_train

    if args.dataset in (
        "synthetic", "synthetic-image", "cifar10", "cifar10-synthetic",
        "image-shards", "digits",
    ):
        return dpx_train.ClassificationTask()
    if args.model.startswith("bert"):
        vocab = getattr(model, "vocab_size", 30522)
        return dpx_train.MLMTask(
            vocab_size=vocab, mask_token_id=103,
            pad_token_id=args.pad_token_id,
        )
    return dpx_train.CausalLMTask()


def pick_auto_plan(args, parser, model, task, train_ds, global_batch):
    """graft-plan ``--auto-mesh``: rank legal PlanSpecs through the static
    three-tier oracle and lower the winner (zero XLA compiles).

    The abstract batch is derived from the dataset's own element spec, so
    the traced program is exactly the one ``Trainer.fit`` will compile.
    Returns ``(mesh, partitioner, PlanScore)``.
    """
    import jax

    from distributed_pytorch_example_tpu.analysis import envelope, planner
    from distributed_pytorch_example_tpu.train.optimizers import make_optimizer

    if (args.mesh_fsdp, args.mesh_tensor, args.mesh_sequence,
            args.mesh_expert) != (1, 1, 1, 1) or args.mesh_pipe not in (0, 1):
        parser.error("--auto-mesh replaces the --mesh-* flags; drop them")
    if args.zero1 or args.wire != "none":
        parser.error("--auto-mesh searches the zero1/wire knobs itself; "
                     "drop --zero1/--wire")
    element = train_ds[0]
    batch = {
        k: jax.ShapeDtypeStruct((global_batch,) + tuple(v.shape), v.dtype)
        for k, v in element.items()
    }
    sample = batch["tokens"] if "tokens" in batch else next(iter(batch.values()))
    # state shapes only — the schedule length never changes the plan space
    optimizer = make_optimizer(
        args.optimizer, args.lr, schedule=args.schedule,
        warmup_steps=args.warmup_steps, total_steps=1,
        weight_decay=args.weight_decay, grad_clip_norm=args.grad_clip,
        every_k=args.grad_accum,
    )
    lm = dpx.models.model_has(args.model, "head_params")
    best, scores = planner.pick_train_plan(
        model, task, optimizer, sample, batch,
        kind="lm" if lm else "image",
        program=f"train/{args.model}",
        hbm_limit=envelope.hbm_limit_from_env(),
        wire_block=args.wire_block,
        log=logger.info,
    )
    if best is None:
        reasons = "; ".join(
            f"{s.plan.name()}: {s.reason}" for s in scores[:5]
        )
        parser.error(f"--auto-mesh found no feasible plan ({reasons})")
    mesh = dpx.runtime.make_mesh(best.plan.mesh)
    return mesh, best.plan.lower(mesh=mesh), best


def main(argv=None, devices=None):
    """Run the CLI; returns the fitted Trainer.

    ``argv`` defaults to ``sys.argv[1:]``. ``devices`` (no CLI flag)
    restricts the mesh to a subset of ``jax.devices()`` — chip_smoke.py's
    one-device reference run inside a four-chip process.
    """
    parser = argparse.ArgumentParser(description=__doc__)
    dpx.utils.add_reference_args(parser)
    dpx.utils.add_framework_args(parser)
    args = parser.parse_args(argv)

    # the module-level form of Telemetry.span, for what runs before fit
    # builds its scope (telemetry/trace.py: profiler annotation + record)
    span = span_lib.no_span if args.no_telemetry else span_lib.span
    with span("main_args"):
        dpx.runtime.setup_logging()
        if args.chaos:
            # install BEFORE initialize(): rendezvous-flake faults must see the
            # plan; equivalent to launching with DPX_CHAOS=<value>
            from distributed_pytorch_example_tpu.robustness import chaos

            chaos.install(
                chaos.ChaosPlan.from_json(args.chaos)
                if args.chaos.lstrip().startswith("{")
                else chaos.preset(args.chaos)
            )
    with span("main_runtime"):
        dpx.runtime.enable_compile_cache()
        config = dpx.runtime.initialize()

        import jax

        mesh = dpx.runtime.make_mesh(
            dpx.runtime.MeshSpec(
                data=args.mesh_data,
                fsdp=args.mesh_fsdp,
                tensor=args.mesh_tensor,
                sequence=args.mesh_sequence,
                expert=args.mesh_expert,
                pipe=args.mesh_pipe,
            ),
            devices=devices,
        )
        dp_size = dpx.runtime.mesh.data_parallel_size(mesh)
        logger.info(
            "Starting distributed training with %d processes, %d devices, mesh %s",
            jax.process_count(),
            mesh.size,
            dict(mesh.shape),
        )
        logger.info(
            "Configuration: epochs=%d, batch_size=%d (global %d), lr=%s",
            args.epochs,
            args.batch_size,
            args.batch_size * dp_size,
            args.lr,
        )

    with span("main_data"):
        # Reference semantics: --batch-size is per data-parallel replica
        # (train.py:215 with one process per device); global batch scales with
        # the data-parallel size.
        global_batch = args.batch_size * dp_size
        train_ds = build_dataset(args, args.num_samples, seed=args.seed, train=True)
        val_ds = build_dataset(
            args, max(args.num_samples // 10, global_batch), seed=args.seed + 1,
            train=False,
        )
        if args.augment != "none":
            if args.dataset in ("synthetic", "synthetic-tokens", "tokens-file"):
                parser.error(f"--augment only applies to image datasets, not "
                             f"{args.dataset!r}")
            from distributed_pytorch_example_tpu.data.augment import (
                AugmentedDataset,
                pad_crop_flip,
                random_resized_crop_flip,
            )

            if args.augment == "imagenet":
                transform = random_resized_crop_flip(
                    size=args.image_size, seed=args.seed
                )
            else:
                transform = pad_crop_flip(
                    flip=args.augment == "cifar", seed=args.seed
                )
            workers = args.augment_workers or min(
                max(1, global_batch // 32), os.cpu_count() or 1
            )
            train_ds = AugmentedDataset(
                train_ds, transform, workers=workers, seed=args.seed
            )
        # real datasets know their label space; the flag default (10) must not
        # silently size a too-small classifier head for e.g. ImageNet shards
        ds_classes = getattr(train_ds, "num_classes", 0)
        if ds_classes and ds_classes != args.num_classes:
            if args.num_classes == parser.get_default("num_classes"):
                logger.info(
                    "Using num_classes=%d from the dataset (flag default %d)",
                    ds_classes, args.num_classes,
                )
                args.num_classes = ds_classes
            elif ds_classes > args.num_classes:
                parser.error(
                    f"--num-classes {args.num_classes} < dataset label space "
                    f"{ds_classes}"
                )

    with span("main_model"):
        dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
        overrides = {"dtype": dtype}
        if args.model in ("mlp",) or args.model.startswith("resnet") or args.model.startswith("vit"):
            overrides["num_classes"] = args.num_classes

        def has(what):  # a model is taken by what it has, not by its name
            return dpx.models.model_has(args.model, what)

        # ViT comes from a factory, which has no fields to ask
        is_transformer = args.model.startswith("vit") or has("use_flash")
        # the RESOLVED axis size, not the raw flag: -1 may absorb to size 1
        seq_span = mesh.shape["sequence"]
        if args.sp_mode is not None and not (is_transformer and seq_span > 1):
            parser.error("--sp-mode has no effect without a transformer model "
                         "and a sequence mesh axis spanning > 1 devices")
        if is_transformer:
            if args.remat:
                overrides["remat"] = True
            if args.flash != "auto":
                overrides["use_flash"] = args.flash == "on"
            if seq_span > 1:
                if has("use_flash") and not has("seq_axis"):
                    parser.error(f"--mesh-sequence > 1: {args.model!r} has "
                                 f"no sequence-parallel attention")
                overrides["seq_axis"] = "sequence"  # SP over the mesh
                if args.sp_mode is not None:  # None: keep the model's default
                    overrides["sp_mode"] = args.sp_mode
        if has("head_params") and args.lm_loss == "fused":
            # fused chunked-CE loss: the model returns final hidden states and
            # the task streams the tied-head matmul + softmax over vocab blocks
            overrides["logits_mode"] = "hidden"
        if args.pad_token_id is not None:
            if not args.model.startswith("bert"):
                parser.error(f"--pad-token-id is only supported for bert models, "
                             f"not {args.model!r}")
            # composes with --mesh-sequence: the padding mask streams through
            # both SP modes (ring rotates mask chunks with k/v; Ulysses
            # all-gathers the mask after its head swap)
            overrides["pad_token_id"] = args.pad_token_id
        share = {
            "--layers-kept": args.layers_kept,
            "--experts-held": args.experts_held,
            "--vocab-slice": args.vocab_slice,
        }
        for flag, value in share.items():
            if value is not None and not has("layers_kept"):
                parser.error(f"{flag} states a deployment's share, which "
                             f"{args.model!r} has none of (lfm2-8b-a1b and "
                             f"joyai-llm-flash have)")
        try:
            if args.layers_kept is not None:
                overrides["layers_kept"] = tuple(
                    int(i) for i in args.layers_kept.split(",")
                )
            if args.experts_held is not None:
                first, count = (int(i) for i in args.experts_held.split(","))
                overrides["experts_first"] = first
                overrides["experts_held"] = count
        except ValueError:
            parser.error("--layers-kept takes indices (0,2,3,4,5) and "
                         "--experts-held FIRST,COUNT (0,8)")
        if args.vocab_slice is not None:
            overrides["vocab_size"] = args.vocab_slice
        if args.moe_experts:
            if not has("moe_experts"):
                parser.error(f"--moe-experts is only supported for gpt2 and "
                             f"llama models, not {args.model!r}")
            overrides["moe_experts"] = args.moe_experts
            overrides["moe_every"] = args.moe_every
            if args.moe_top_k is not None:  # None: keep the model's default
                overrides["moe_top_k"] = args.moe_top_k
            if args.mesh_pipe not in (0, 1) and args.moe_every != 1:
                # PP x EP serves gpt2 AND llama (SwiGLU experts in the stacked
                # LLaMA decoder), but stages must be homogeneous
                parser.error("--mesh-pipe with --moe-experts needs "
                             "homogeneous stages: set --moe-every 1 "
                             "(experts on every block)")
        if args.moe_top_k is not None and not args.moe_experts:
            parser.error("--moe-top-k without --moe-experts has nothing to "
                         "route; set --moe-experts too")
        if args.mesh_expert not in (0, 1) and not args.moe_experts:
            parser.error("--mesh-expert > 1 without --moe-experts would shrink "
                         "data parallelism with nothing sharded on the expert "
                         "axis; set --moe-experts too")
        if args.mesh_pipe not in (0, 1):
            if not has("pipe_axis"):
                parser.error(f"--mesh-pipe is only supported for gpt2 and llama "
                             f"models, not {args.model!r}")
            overrides["pipe_axis"] = "pipe"
            overrides["pipe_microbatches"] = args.pipe_microbatches
            if args.pipe_schedule != "gpipe":
                overrides["pipe_schedule"] = args.pipe_schedule
            if args.pipe_virtual > 1:
                if args.pipe_schedule != "1f1b":
                    parser.error("--pipe-virtual needs --pipe-schedule 1f1b "
                                 "(interleaving is a 1F1B refinement)")
                overrides["pipe_virtual"] = args.pipe_virtual
            if args.pipe_no_recompute:
                if args.pipe_schedule != "1f1b":
                    parser.error("--pipe-no-recompute needs --pipe-schedule "
                                 "1f1b (GPipe differentiates through the whole "
                                 "schedule; the stash is a 1F1B backward mode)")
                overrides["pipe_recompute"] = False
        elif args.pipe_schedule != "gpipe":
            parser.error("--pipe-schedule 1f1b needs --mesh-pipe > 1")
        elif args.pipe_virtual > 1:
            parser.error("--pipe-virtual needs --mesh-pipe > 1 and "
                         "--pipe-schedule 1f1b")
        elif args.pipe_no_recompute:
            parser.error("--pipe-no-recompute needs --mesh-pipe > 1 and "
                         "--pipe-schedule 1f1b")
        model = dpx.models.get_model(args.model, **overrides)
        task = build_task(args, model)

        pipelined = args.mesh_pipe not in (0, 1)
        if args.auto_mesh:
            # graft-plan: the planner picks mesh AND partitioner; the chosen
            # PlanSpec carries its own zero1/wire knobs
            mesh, partitioner, picked = pick_auto_plan(
                args, parser, model, task, train_ds, global_batch
            )
            logger.info(
                "graft-plan --auto-mesh picked %s (tier %d, cost %.4f ms, "
                "%d wire bytes)",
                picked.plan.name(), picked.tier, picked.cost_ms(),
                picked.comm_bytes,
            )
        elif args.partition == "fsdp" and not pipelined:
            if args.zero1:
                parser.error("--zero1 is redundant under --partition fsdp "
                             "(FSDP already shards optimizer state with the "
                             "params)")
            partitioner = dpx.parallel.fsdp(mesh)
        elif args.partition == "tp" or pipelined:
            # pipelined runs need the stacked-param rules (stage stacks sharded
            # on 'pipe') regardless of --partition; with fsdp the unmatched
            # leaves (embeddings, norms) shard on the fsdp axis, otherwise they
            # stay replicated (DP semantics)
            from distributed_pytorch_example_tpu.parallel.partition import (
                transformer_partitioner,
            )

            partitioner = transformer_partitioner(
                mesh, fsdp_rest=args.partition == "fsdp",
                dp_shard_opt_state=args.zero1,
            )
        else:
            partitioner = dpx.parallel.data_parallel(
                mesh, dp_shard_opt_state=args.zero1
            )
        # graft-wire collective compression: carried by the partitioner so the
        # step, budgets, and telemetry all read one policy object (--auto-mesh
        # plans already lowered their own wire policy)
        if not args.auto_mesh:
            from distributed_pytorch_example_tpu.parallel.wire import (
                DEFAULT_BUCKET_BYTES,
            )

            bucket_bytes = (
                DEFAULT_BUCKET_BYTES if args.overlap_buckets < 0
                else args.overlap_buckets
            )
            partitioner.wire = dpx.parallel.WireConfig(
                compress=args.wire,
                block_size=args.wire_block,
                stochastic_rounding=args.wire_stochastic,
                param_gather=args.wire_param_gather,
                bucket_bytes=bucket_bytes,
            )

    with span("main_trainer"):
        train_loader = dpx.data.DeviceLoader(
            train_ds, global_batch, mesh=mesh, shuffle=True, seed=args.seed
        )
        val_loader = dpx.data.DeviceLoader(
            val_ds, global_batch, mesh=mesh, shuffle=False, seed=args.seed
        )
        logger.info(
            "Dataset size: %d, batches per epoch: %d", len(train_ds), len(train_loader)
        )

        try:
            profile_window = tuple(int(x) for x in args.profile_steps.split(","))
            if len(profile_window) != 2 or profile_window[0] >= profile_window[1]:
                raise ValueError
        except ValueError:
            parser.error("--profile-steps must be 'start,stop' with start < stop")
        from distributed_pytorch_example_tpu.train.optimizers import make_optimizer

        optimizer = make_optimizer(
            args.optimizer,
            args.lr,
            schedule=args.schedule,
            warmup_steps=args.warmup_steps,
            # the schedule advances once per OPTIMIZER step; with accumulation
            # that is every k-th micro-step
            total_steps=max(1, args.epochs * len(train_loader) // args.grad_accum),
            weight_decay=args.weight_decay,
            grad_clip_norm=args.grad_clip,
            every_k=args.grad_accum,
        )
        trainer = dpx.train.Trainer(
            model,
            task,
            optimizer,
            partitioner=partitioner,
            checkpoint_dir=args.checkpoint_dir,
            log_every=args.log_every,
            seed=args.seed,
            metrics_file=args.metrics_file,
            profile_dir=args.profile_dir,
            profile_window=profile_window,
            checkpoint_format=args.checkpoint_format,
            save_every_steps=args.save_every_steps,
            telemetry=not args.no_telemetry,
            telemetry_every=args.telemetry_every,
            max_bad_steps=args.max_bad_steps,
            skip_nonfinite=not args.no_skip_nonfinite,
            checkpoint_retain=args.checkpoint_retain,
            publish_dir=args.publish_dir,
        )
    try:
        trainer.fit(
            train_loader,
            val_loader,
            epochs=args.epochs,
            resume=args.resume,
        )
    except dpx.train.PreemptionInterrupt as e:
        # graceful SIGTERM/SIGINT teardown: the checkpoint landed in fit();
        # exit with the conventional rc (143 TERM / 130 INT) so the launcher
        # does NOT restart (launch/entrypoint.sh:133-141) — the next launch
        # resumes at the saved batch
        dpx.runtime.shutdown()
        sys.exit(e.exit_code)
    except dpx.train.BadStepBudgetExceeded:
        logger.exception("graft-armor: persistent nonfinite fault; aborting")
        dpx.runtime.shutdown()
        sys.exit(1)
    dpx.runtime.shutdown()
    return trainer


if __name__ == "__main__":
    main()
