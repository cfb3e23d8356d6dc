"""Two-tier configuration, matching the reference's split (SURVEY.md §5):

- **flags for science** — argparse hyperparameters, superset of the
  reference's CLI (reference train.py:213-221): ``--epochs --batch-size --lr
  --num-samples --checkpoint-dir --resume``;
- **env for topology** — ``REPLICAS`` / ``NF_DISCOVERY_SERVICE`` /
  ``COORDINATOR_PORT`` / ``PROCESS_ID``, consumed by
  ``runtime.distributed.resolve_config`` (reference entrypoint.sh:5-8 parity).
"""

from __future__ import annotations

import argparse


def add_reference_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The reference's exact flags and defaults (train.py:214-219)."""
    parser.add_argument("--epochs", type=int, default=10)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="PER-REPLICA batch size (reference semantics); "
                        "global batch = batch-size * data-parallel size")
    parser.add_argument("--lr", type=float, default=0.001)
    parser.add_argument("--num-samples", type=int, default=10000)
    parser.add_argument("--checkpoint-dir", type=str, default="./checkpoints")
    parser.add_argument("--resume", type=str, default=None)
    return parser


def add_framework_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Extensions beyond the reference: model/dataset selection, mesh shape."""
    parser.add_argument("--model", type=str, default="mlp",
                        help="mlp|resnet18|resnet50|vit-b16|bert-base|gpt2")
    parser.add_argument("--dataset", type=str, default="synthetic",
                        help="synthetic|synthetic-image|synthetic-tokens|"
                        "cifar10|digits|image-shards|tokens-file")
    parser.add_argument("--augment", type=str, default="none",
                        choices=("none", "cifar", "crop", "imagenet"),
                        help="train-time augmentation: cifar = pad-crop + "
                        "flip, crop = pad-crop only (label-asymmetric data "
                        "like digits), imagenet = random-resized-crop + flip")
    parser.add_argument("--augment-workers", type=int, default=0,
                        help="threads transforming each batch's augmentation "
                        "in parallel (reference DataLoader num_workers "
                        "analogue, train.py:112); 0 = one per 32 images, "
                        "capped at cpu count")
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--token-dtype", type=str, default="uint16",
                        choices=("uint16", "uint32", "int32"),
                        help="element dtype of raw .bin token files")
    parser.add_argument("--image-size", type=int, default=32)
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--log-every", type=int, default=10,
                        help="batches between rank-0 progress logs "
                        "(reference train.py:144)")
    parser.add_argument("--auto-mesh", action="store_true",
                        help="graft-plan: pick the mesh + partitioner by "
                        "ranking legal PlanSpecs through the static "
                        "three-tier oracle (analysis/planner.py) instead "
                        "of the --mesh-*/--zero1/--wire flags; searches "
                        "at global batch = --batch-size x device count. "
                        "DPX_HBM_LIMIT gates would-OOM plans pre-compile")
    parser.add_argument("--mesh-data", type=int, default=-1)
    parser.add_argument("--mesh-fsdp", type=int, default=1)
    parser.add_argument("--mesh-tensor", type=int, default=1)
    parser.add_argument("--mesh-sequence", type=int, default=1)
    parser.add_argument("--sp-mode", type=str, default=None,
                        choices=("ring", "ulysses"),
                        help="sequence parallelism: ring (K/V rotation, "
                        "O(S_local) memory) or ulysses (all-to-all head "
                        "swap; heads must divide the sequence axis). "
                        "Default: the model's own default (llama: ulysses, "
                        "others: ring)")
    parser.add_argument("--mesh-expert", type=int, default=1)
    parser.add_argument("--mesh-pipe", type=int, default=1,
                        help=">1: GPipe pipeline stages over the 'pipe' mesh "
                        "axis (gpt2, llama; layers split across stages)")
    parser.add_argument("--pipe-microbatches", type=int, default=0,
                        help="microbatches per pipelined step (0 = auto; "
                        "must divide batch and be a multiple of --mesh-pipe)")
    parser.add_argument("--pipe-schedule", type=str, default="gpipe",
                        choices=("gpipe", "1f1b"),
                        help="pipeline schedule: gpipe (all-forward-then-"
                        "backward) or 1f1b (interleaved; activation stash "
                        "~n_stages instead of ~n_micro — the depth "
                        "scaling schedule; gpt2/llama causal LM incl. "
                        "MoE and SP)")
    parser.add_argument("--pipe-virtual", type=int, default=1,
                        help="interleaved virtual chunks per pipeline stage "
                        "(Megatron-style; needs --pipe-schedule 1f1b; "
                        "bubble time ~/v for ~v x input-stash memory)")
    parser.add_argument("--pipe-no-recompute", action="store_true",
                        help="1f1b backward without stage replay: stash "
                        "each microbatch's vjp residuals at forward time "
                        "(~3 instead of ~4 forward-units per cycle, more "
                        "temp memory; needs --pipe-schedule 1f1b — see "
                        "results/pipeline_1f1b/ for the measured frontier)")
    parser.add_argument("--pad-token-id", type=int, default=None,
                        help="bert: mask keys at this token id out of "
                        "attention (padding); default: no padding mask")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help=">0: MoE MLP with this many experts on every "
                        "other transformer block (gpt2: gelu experts; "
                        "llama: Mixtral-style SwiGLU experts)")
    parser.add_argument("--moe-every", type=int, default=2,
                        help="MoE MLP on every Nth block (2 = Switch "
                        "cadence; 1 = every block, required for "
                        "--mesh-pipe + --moe-experts)")
    parser.add_argument("--moe-top-k", type=int, default=None,
                        help="experts per token (1 = Switch, 2 = GShard/"
                        "Mixtral); default: the model's own default "
                        "(gpt2: 1, llama: 2)")
    parser.add_argument("--layers-kept", type=str, default=None,
                        help="a deployment's share: the layers of the "
                        "published stack that this pipeline stage holds, as "
                        "rising indices (0,2,3,4,5); models with "
                        "layers_kept (lfm2-8b-a1b, joyai-llm-flash)")
    parser.add_argument("--experts-held", type=str, default=None,
                        help="a deployment's share: FIRST,COUNT of the "
                        "published experts that this chip holds in every "
                        "expert layer (0,8); the router keeps its published "
                        "width; models with experts_held (lfm2-8b-a1b, "
                        "joyai-llm-flash)")
    parser.add_argument("--vocab-slice", type=int, default=None,
                        help="a deployment's share: the size of this chip's "
                        "slice of the vocabulary (table, logits and loss "
                        "are over the slice; synthetic tokens are drawn "
                        "from it); models with layers_kept (lfm2-8b-a1b, "
                        "joyai-llm-flash)")
    parser.add_argument("--lm-loss", type=str, default="fused",
                        choices=("fused", "dense"),
                        help="LM-head loss path: fused = chunked vocab "
                        "cross-entropy, no materialized (B,S,V) f32 logits "
                        "(ops/chunked_ce.py); dense = full logits + optax CE")
    parser.add_argument("--partition", type=str, default="dp",
                        help="dp|fsdp|tp (tp uses per-model transformer rules)")
    parser.add_argument("--dtype", type=str, default="float32",
                        help="compute dtype: float32|bfloat16")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize transformer blocks (memory for FLOPs)")
    parser.add_argument("--flash", type=str, default="auto",
                        choices=("auto", "on", "off"),
                        help="Pallas flash attention: auto-select, force, or disable")
    parser.add_argument("--data-dir", type=str, default=None,
                        help="root for real datasets (cifar10); defaults to "
                        "$DPX_DATA_DIR or ./data")
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="capture an XLA trace (TensorBoard format) for "
                        "the --profile-steps window into this directory")
    parser.add_argument("--profile-steps", type=str, default="10,13",
                        help="start,stop global-step window for --profile-dir")
    parser.add_argument("--checkpoint-format", type=str, default="auto",
                        choices=("auto", "gathered", "sharded"),
                        help="gathered: single all-gathered file (reference "
                        "parity); sharded: per-process shard files, no "
                        "gather, async at any host count; auto: sharded "
                        "when multi-host")
    parser.add_argument("--metrics-file", type=str, default=None,
                        help="JSONL epoch-metrics path (default: "
                        "<checkpoint-dir>/metrics.jsonl)")
    parser.add_argument("--telemetry-every", type=int, default=0,
                        help=">0: graft-scope writes a per-N-step record "
                        "(step_time_ms, mfu_analytic, hbm_peak_bytes, "
                        "grad_norm, skew) to the metrics JSONL and a Chrome "
                        "trace-event file next to it; 0 keeps telemetry on "
                        "(sentinels, straggler watch) but logs epochs only")
    parser.add_argument("--no-telemetry", action="store_true",
                        help="disable graft-scope entirely (no sentinels, "
                        "no spans, no compiled-cost registry)")
    parser.add_argument("--save-every-steps", type=int, default=0,
                        help=">0: also write `latest` every N train batches "
                        "with the loader cursor, so --resume restarts at "
                        "the exact batch (step-level resume; a preemption "
                        "loses at most N batches instead of an epoch)")
    parser.add_argument("--optimizer", type=str, default="adam",
                        choices=("adam", "adamw", "sgd", "lamb", "adafactor"),
                        help="reference default: adam (train.py:249); "
                        "adafactor = factored moments (sub-linear optimizer "
                        "memory)")
    parser.add_argument("--schedule", type=str, default="constant",
                        choices=("constant", "cosine", "linear"))
    parser.add_argument("--warmup-steps", type=int, default=0)
    parser.add_argument("--weight-decay", type=float, default=0.0)
    parser.add_argument("--grad-clip", type=float, default=None,
                        help="global-norm gradient clipping threshold")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="accumulate k micro-steps per optimizer step")
    parser.add_argument("--zero1", action="store_true",
                        help="ZeRO-1: shard optimizer state over the data "
                        "axis (reduce-scattered grads + param "
                        "re-replication; parallel/api.py zero1_overlay)")
    parser.add_argument("--wire", type=str, default="none",
                        choices=("none", "int8-block"),
                        help="graft-wire gradient-collective compression: "
                        "int8-block = int8 payloads with per-block bf16 "
                        "scales on the gradient sync (~4x fewer wire "
                        "bytes; parallel/wire.py)")
    parser.add_argument("--wire-block", type=int, default=256,
                        help="elements per bf16 scale block for "
                        "--wire int8-block")
    parser.add_argument("--wire-stochastic", action="store_true",
                        help="stochastic rounding in the wire quantizer "
                        "(unbiased gradient mean; default round-to-nearest)")
    parser.add_argument("--wire-param-gather", type=str, default="float32",
                        choices=("float32", "bf16", "int8-block"),
                        help="payload of the ZeRO-1 param re-replication "
                        "all-gather; float32 keeps master weights exact "
                        "(lossy modes are opt-in — the gathered buffer "
                        "feeds the next update)")
    parser.add_argument("--overlap-buckets", type=int, default=0,
                        metavar="BYTES",
                        help=">0: fused comm/compute-overlap gradient sync "
                        "— grad leaves bucket to ~BYTES of fp32 each "
                        "(reverse trace order) and each bucket moves as "
                        "ONE collective the XLA scheduler hides behind "
                        "backward compute (parallel/wire.py sync_grads; "
                        "composes with --zero1/--wire). -1 = the default "
                        "4 MiB target; 0 = inline per-leaf sync")
    parser.add_argument("--shard-cache-mb", type=int, default=0,
                        metavar="MB",
                        help=">0: graft-intake in-memory LRU over decoded "
                        "sealed shards, capped at MB; repeated-epoch "
                        "workloads stop paying disk reads + CRC verify "
                        "from epoch 2 (input_stall_frac -> ~0). "
                        "Quarantined shards are evicted. 0 = off")
    parser.add_argument("--max-bad-steps", type=int, default=8,
                        help="nonfinite steps skipped device-side before "
                        "rolling back to the last good checkpoint (a second "
                        "exhaustion hard-fails); 0 disables the budget")
    parser.add_argument("--no-skip-nonfinite", action="store_true",
                        help="disable graft-armor update predication: apply "
                        "the optimizer update even when gradients are "
                        "nonfinite (pre-r10 behavior)")
    parser.add_argument("--checkpoint-retain", type=int, default=3,
                        help="intact checkpoint generations kept per root "
                        "(keep-last-K; older ones are fallback candidates "
                        "when `latest` is torn or corrupt)")
    parser.add_argument("--publish-dir", type=str, default=None,
                        help="graft-swap: also publish every checkpoint to "
                        "this PublishChannel directory; a serving fleet "
                        "started with the same --publish-dir hot-swaps "
                        "onto each committed version with zero downtime")
    parser.add_argument("--chaos", type=str, default=None,
                        help="deterministic fault injection: a preset name "
                        "(nan-step|io-flake) or a ChaosPlan JSON object; "
                        "equivalent to setting $DPX_CHAOS")
    return parser
