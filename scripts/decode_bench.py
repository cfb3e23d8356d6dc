#!/usr/bin/env python3
"""Paged-decode before/after: plain greedy decode vs speculative decode.

Runs the SAME seeded workload through two engines over one shared
model/params (so both sides hit one jit cache for the shared programs):

1. **baseline** — one decode boundary per token (the pre-speculation
   engine path, unchanged);
2. **spec** — self-speculation with a ``--spec-tokens`` window: the
   target drafts for itself with K-1 argmax proposals, then verifies the
   window in ONE bucketed step over the fixed slot array. At greedy
   (``--temperature 0``, the default) the draft's argmax IS the target's
   argmax, so the accept rate is 1.0 and the speedup is the pure
   boundary-amortization win: ~K tokens per (propose + verify) pair of
   dispatches instead of 1 token per dispatch.

Exact-match acceptance makes the two outputs bit-identical by
construction; the script CHECKS that and refuses to report a speedup on
mismatched tokens. Each engine runs the workload twice and only the
second (warm, fully compiled) pass is measured — the committed artifact
compares steady-state decode throughput, not compile time.

The committed evidence lives under ``results/paged_decode/`` (--json);
stdout gets exactly ONE JSON line (driver contract), detail on stderr.

CPU (fake mesh) invocation::

    JAX_PLATFORMS=cpu python \\
        scripts/decode_bench.py --json results/paged_decode/decode_cpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.serving import Request

    kw = dict(
        vocab_size=args.vocab_size, max_len=args.max_len,
        model_dim=args.model_dim, num_layers=args.num_layers,
        num_heads=args.num_heads, mlp_dim=2 * args.model_dim,
    )
    pool = dict(
        paged_num_blocks=args.num_blocks, paged_block_size=args.block_size,
        paged_max_blocks=args.max_blocks,
    )
    params = GPT2(**kw).init(
        jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = GPT2(**kw, decode=True, **pool)

    rng = np.random.default_rng(args.seed)
    requests = [
        Request(
            rid=f"req{i:03d}",
            prompt=[int(t) for t in rng.integers(
                0, args.vocab_size, int(rng.integers(4, 13))
            )],
            max_new_tokens=args.max_new,
            seed=args.seed * 100_003 + i,
        )
        for i in range(args.requests)
    ]
    return model, params, requests


def measure(engine, requests, tag):
    """Warmup pass + measured pass; returns the warm report."""
    engine.run(requests)  # compiles every program + per-bucket prefills
    report = engine.run(requests)
    m = report["metrics"]
    print(
        f"decode_bench: {tag}: decode {m['decode_tokens']} tokens in "
        f"{m['decode_time_s']:.3f}s -> {m['decode_tokens_per_sec']:.1f} "
        f"tok/s (accept_rate={m['spec_accept_rate']})",
        file=sys.stderr,
    )
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--vocab-size", type=int, default=97)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--model-dim", type=int, default=32)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-heads", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=4)
    ap.add_argument("--max-blocks", type=int, default=10)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--spec-tokens", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (accept rate 1.0 under "
                    "self-speculation); sampling temperatures report the "
                    "honest sub-1.0 accept rate")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the record here (committed artifact)")
    args = ap.parse_args()

    import jax

    from distributed_pytorch_example_tpu.serving import InferenceEngine

    model, params, requests = build(args)
    plat = jax.devices()[0].platform
    print(
        f"decode_bench: {len(requests)} requests x {args.max_new} tokens, "
        f"{args.slots} slots, K={args.spec_tokens}, "
        f"temperature={args.temperature}, on {len(jax.devices())} {plat} "
        f"device(s)",
        file=sys.stderr,
    )

    common = dict(
        num_slots=args.slots, temperature=args.temperature,
        top_k=args.top_k,
    )
    base = measure(
        InferenceEngine(model, params, **common), requests, "baseline"
    )
    spec = measure(
        InferenceEngine(
            model, params, **common, draft_model=model, draft_params=params,
            spec_tokens=args.spec_tokens,
        ),
        requests, f"spec(K={args.spec_tokens})",
    )

    token_exact = all(
        spec["results"][r.rid]["tokens"] == base["results"][r.rid]["tokens"]
        for r in requests
    )
    bm, sm = base["metrics"], spec["metrics"]
    if not token_exact:
        print("decode_bench: FATAL: speculative output diverged from the "
              "plain decode output — speedup would be meaningless",
              file=sys.stderr)
    speedup = (
        sm["decode_tokens_per_sec"] / bm["decode_tokens_per_sec"]
        if bm["decode_tokens_per_sec"] and token_exact else None
    )

    record = {
        "metric": "spec_decode_speedup",
        "value": round(speedup, 3) if speedup is not None else None,
        "unit": "x (warm decode tokens/sec, spec / baseline)",
        "token_exact": token_exact,
        "baseline": {
            "decode_tokens_per_sec": round(bm["decode_tokens_per_sec"], 2),
            "decode_tokens": bm["decode_tokens"],
            "decode_time_s": round(bm["decode_time_s"], 4),
            "decode_steps": bm["decode_steps"],
        },
        "spec": {
            "decode_tokens_per_sec": round(sm["decode_tokens_per_sec"], 2),
            "decode_tokens": sm["decode_tokens"],
            "decode_time_s": round(sm["decode_time_s"], 4),
            "decode_steps": sm["decode_steps"],
            "spec_accept_rate": (
                round(sm["spec_accept_rate"], 4)
                if sm["spec_accept_rate"] is not None else None
            ),
        },
        "config": {
            "family": "gpt2", "vocab_size": args.vocab_size,
            "model_dim": args.model_dim, "num_layers": args.num_layers,
            "num_heads": args.num_heads, "slots": args.slots,
            "requests": args.requests, "max_new": args.max_new,
            "spec_tokens": args.spec_tokens,
            "temperature": args.temperature, "top_k": args.top_k,
            "num_blocks": args.num_blocks, "block_size": args.block_size,
            "max_blocks": args.max_blocks, "seed": args.seed,
            "platform": plat, "devices": len(jax.devices()),
            "jax": jax.__version__,
        },
    }
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"decode_bench: wrote {args.json}", file=sys.stderr)
    print(json.dumps(record))
    return 0 if token_exact else 1


if __name__ == "__main__":
    sys.exit(main())
