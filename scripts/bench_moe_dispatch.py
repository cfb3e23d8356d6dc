#!/usr/bin/env python3
"""Dense MoE dispatch scaling in the expert count (VERDICT r4 ask #8).

The dense-dispatch design (models/moe.py) builds (B, S, E, C) one-hot
dispatch/combine tensors. The scaling worry is O(S*E*C) — but capacity is
C = ceil(top_k * S / E * cf), so E*C ~ top_k * cf * S is CONSTANT in E:
analytically the dispatch einsums' FLOPs and the dispatch tensor bytes are
flat in E at fixed token count (quadratic in S, which is the real design
limit). This script turns that argument into a measured curve:

1. one MoE layer (fwd+bwd) at fixed tokens, E in {4..128};
2. a full tiny-LM train step at E in {4, 16, 64}.

If the curve is flat, dense dispatch holds at production expert counts
and a sorted/ragged path is unjustified complexity; if it grows, the
growth IS the case for one.

Timing protocol (the r5 run's single-pass timings carried ~+-20% timing
noise — a non-monotonic E=32 spike, VERDICT r5 weak #1): every layer
config is compiled up front, then ``--repeats`` timing windows run
ROUND-ROBIN across the expert counts, so machine drift lands on every E
equally instead of on whichever E was measured during the bad seconds.
Each row reports the MEDIAN window plus the raw windows and their
spread; a spread above ~10% means the environment is too noisy to quote
single-run numbers at all.

Run on the TPU:  python scripts/bench_moe_dispatch.py \
    [--json results/moe_dispatch/scaling.json]
On a CPU-only session, shrink the shape (the curve's shape survives;
absolute ms are a different machine class):
  JAX_PLATFORMS=cpu python \
      scripts/bench_moe_dispatch.py --batch 2 --seq 256 --dim 256 \
      --mlp-dim 512 --steps 10 --model-experts "" \
      [--json results/moe_dispatch/scaling_cpu.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fence(x) -> float:
    """Fence by fetching a real value."""
    import jax.numpy as jnp

    return float(jnp.sum(x[0]) if isinstance(x, tuple) else jnp.sum(x))


def prepare_layer(E: int, *, B, S, D, M, top_k=2, cf=1.25):
    """Compile one MoE layer's fwd+bwd; return a timing-window closure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.models.moe import moe_apply

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    params = {
        "up_kernel": jnp.asarray(
            rng.standard_normal((E, D, M)) * 0.02, jnp.float32
        ),
        "up_bias": jnp.zeros((E, M), jnp.float32),
        "down_kernel": jnp.asarray(
            rng.standard_normal((E, M, D)) * 0.02, jnp.float32
        ),
        "down_bias": jnp.zeros((E, D), jnp.float32),
    }

    def loss(params, x, logits):
        y, aux = moe_apply(
            x, logits, params, top_k=top_k, capacity_factor=cf,
            dtype=jnp.bfloat16,
        )
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux["load_balancing"]

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, x, logits
    ).compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
    except Exception:
        flops = 0.0

    def window(steps: int, warmup: int) -> float:
        out = None
        for _ in range(warmup):
            out = compiled(params, x, logits)
        _fence(out[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = compiled(params, x, logits)
        _fence(out[0])
        return (time.perf_counter() - t0) / steps

    C = -(-top_k * S * cf // E)
    return window, int(C), flops


def _row(kind: str, E: int, tokens: int, dts: list[float], C=None,
         flops=None) -> dict:
    med = statistics.median(dts)
    row = {"kind": kind, "experts": E}
    if C is not None:
        row["capacity"] = C
    if flops:
        # XLA-counted program flops: flat in E == the dispatch/expert
        # einsum work really is E-independent (E*C constant); any ms
        # growth on top is execution efficiency (tile/call underfill at
        # small C), not dispatch-tensor scaling
        row["gflops"] = round(flops / 1e9, 3)
    row.update({
        "tokens": tokens,
        "ms_per_step": round(med * 1e3, 3),
        "tokens_per_sec": round(tokens / med),
        "ms_windows": [round(d * 1e3, 3) for d in dts],
        "ms_spread": round((max(dts) - min(dts)) / min(dts), 3),
    })
    return row


def bench_model(E: int, *, B=8, S=1024, steps=20, warmup=5,
                repeats=1) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = dpx.models.get_model(
        "gpt2", dtype=jnp.bfloat16, logits_mode="hidden",
        model_dim=512, num_layers=4, num_heads=8, mlp_dim=1024,
        max_len=S, moe_experts=E, moe_every=2, moe_top_k=2,
    )
    mesh = dpx.runtime.make_mesh()
    partitioner = dpx.parallel.data_parallel(mesh)
    trainer = dpx.train.Trainer(
        model, CausalLMTask(), optax.adam(1e-3), partitioner=partitioner
    )
    tokens = np.random.default_rng(0).integers(
        0, model.vocab_size, (B, S)
    ).astype(np.int32)
    batch = {
        "tokens": jax.make_array_from_process_local_data(
            partitioner.batch_sharding(), tokens
        )
    }
    dts = []
    with mesh:
        trainer.init(batch["tokens"])
        compiled = trainer.train_step.lower(trainer.state, batch).compile()
        state = trainer.state
        for _ in range(repeats):
            metrics = None
            for _ in range(warmup):
                state, metrics = compiled(state, batch)
            float(metrics["loss"])
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = compiled(state, batch)
            float(metrics["loss"])
            dts.append((time.perf_counter() - t0) / steps)
    return _row("model", E, tokens.size, dts)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--json", default=None)
    parser.add_argument("--layer-experts", default="4,8,16,32,64,128")
    parser.add_argument("--model-experts", default="4,16,64",
                        help="'' skips the full-model sweep")
    parser.add_argument("--repeats", type=int, default=5,
                        help="timing windows per config, round-robin "
                        "across expert counts; the row quotes the median")
    parser.add_argument("--steps", type=int, default=30,
                        help="timed steps per window")
    parser.add_argument("--warmup", type=int, default=5,
                        help="untimed steps before the first window; "
                        "later windows re-warm with 2")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--dim", type=int, default=512)
    parser.add_argument("--mlp-dim", type=int, default=1024)
    args = parser.parse_args()

    import jax

    layer_es = [int(e) for e in args.layer_experts.split(",") if e]
    shape = dict(B=args.batch, S=args.seq, D=args.dim, M=args.mlp_dim)
    windows = {}
    prepared = [
        (E, prepare_layer(E, **shape)) for E in layer_es
    ]
    for r in range(args.repeats):
        warm = args.warmup if r == 0 else 2
        for E, (window, _, _) in prepared:
            windows.setdefault(E, []).append(window(args.steps, warm))

    rows = []
    tokens = args.batch * args.seq
    for E, (_, C, flops) in prepared:
        row = _row("layer", E, tokens, windows[E], C=C, flops=flops)
        rows.append(row)
        print(json.dumps(row), flush=True)

    for E in (int(e) for e in args.model_experts.split(",") if e):
        row = bench_model(E, steps=max(args.steps // 2, 5),
                          repeats=args.repeats)
        rows.append(row)
        print(json.dumps(row), flush=True)

    layer = [r for r in rows if r["kind"] == "layer"]
    summary = {
        "layer_ms_E4_to_E128": [layer[0]["ms_per_step"],
                                layer[-1]["ms_per_step"]],
        "layer_growth_x": round(
            layer[-1]["ms_per_step"] / layer[0]["ms_per_step"], 3
        ),
        "worst_window_spread": max(r["ms_spread"] for r in rows),
        "config": {
            **shape, "steps": args.steps, "repeats": args.repeats,
            "platform": jax.devices()[0].platform, "jax": jax.__version__,
        },
    }
    print(json.dumps(summary), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
