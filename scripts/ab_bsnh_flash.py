#!/usr/bin/env python3
"""Residual #3 A/B: attention layout transposes, measured (VERDICT r4 #6).

The r4 LM-MFU analysis attributed ~7% of the GPT-2/BERT step to attention
layout formatting — models emit (B, S, N, H), the flash kernels want
(B, N, S, H) — and rejected the alternatives on paper. This script builds
and times them:

A) **production**: Dense -> reshape (B,S,N,H) -> flash (transpose inside,
   ops/pallas/flash_attention.py:944) -> transpose back -> merge -> Dense.
B) **fused prologue/epilogue**: the projections THEMSELVES produce the
   kernel layout — q = einsum('bsd,dnh->bnsh', x, Wq) feeds the BNSH
   kernel directly, and the out-projection consumes bnsh
   (einsum('bnsh,nhd->bsd')). No standalone transpose op exists for XLA
   to schedule; if the sandwich is real HBM traffic this must win.
C) **BSNH-direct kernel** (in-VMEM head relayout via an all-heads
   (1, S, N, H) block, which IS tile-legal): Mosaic rejects every
   formulation — per-head strided stores, jnp.stack, and minor-dim
   splits all hit "infer-vector-layout: unsupported shape cast"
   (vector<1024x64> -> vector<1024x1x64>). Recorded as a compiler-level
   dead end; see the kernel attempt in git history of this file.

Each variant runs ONE full attention layer (projections + attention +
out-projection) fwd+bwd at the bench shapes; the per-layer delta x 12
layers bounds what the whole step could gain.

Run: python scripts/ab_bsnh_flash.py [--json results/lm_mfu_analysis/bsnh_ab.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--json", default=None)
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--warmup", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        _flash,
        flash_attention,
    )

    rows = []
    for name, (B, S, N, H, causal) in {
        "gpt2@1024": (16, 1024, 12, 64, True),
        "bert@512": (16, 512, 12, 64, False),
    }.items():
        D = N * H
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((B, S, D)) * 0.3, jnp.bfloat16)
        wq, wk, wv, wo = (
            jnp.asarray(
                rng.standard_normal((D, D)) * 0.02, jnp.bfloat16
            )
            for _ in range(4)
        )
        scale = H ** -0.5

        def layer_prod(x, wq, wk, wv, wo):
            q = (x @ wq).reshape(B, S, N, H)
            k = (x @ wk).reshape(B, S, N, H)
            v = (x @ wv).reshape(B, S, N, H)
            o = flash_attention(q, k, v, causal=causal, softmax_scale=scale)
            return o.reshape(B, S, D) @ wo

        def layer_fused(x, wq, wk, wv, wo):
            # projection output IS the kernel layout: no transpose op
            q = jnp.einsum("bsd,dnh->bnsh", x, wq.reshape(D, N, H))
            k = jnp.einsum("bsd,dnh->bnsh", x, wk.reshape(D, N, H))
            v = jnp.einsum("bsd,dnh->bnsh", x, wv.reshape(D, N, H))
            blk = min(1024, S)
            o = _flash(
                q, k, v, None, causal, scale, blk, blk, False
            )  # (B, N, S, H), consumed directly by the epilogue einsum
            return jnp.einsum("bnsh,nhd->bsd", o, wo.reshape(N, H, D))

        def loss(fn):
            def f(x, wq, wk, wv, wo):
                return jnp.sum(fn(x, wq, wk, wv, wo).astype(jnp.float32) ** 2)

            return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))

        g_prod = loss(layer_prod)
        g_fused = loss(layer_fused)

        # same math check (grads wrt x)
        ga = g_prod(x, wq, wk, wv, wo)
        gb = g_fused(x, wq, wk, wv, wo)
        np.testing.assert_allclose(
            np.asarray(ga[0], np.float32), np.asarray(gb[0], np.float32),
            atol=3e-2, rtol=3e-2,
        )

        def bench(fn):
            out = None
            for _ in range(args.warmup):
                out = fn(x, wq, wk, wv, wo)
            float(jnp.sum(out[0].astype(jnp.float32)))  # value-fetch fence
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = fn(x, wq, wk, wv, wo)
            float(jnp.sum(out[0].astype(jnp.float32)))
            return (time.perf_counter() - t0) / args.steps * 1e3

        row = {
            "config": name,
            "shape": [B, S, N, H],
            "layer_fwd_bwd_prod_ms": round(bench(g_prod), 3),
            "layer_fwd_bwd_fused_prologue_ms": round(bench(g_fused), 3),
        }
        row["delta_ms_per_layer"] = round(
            row["layer_fwd_bwd_prod_ms"]
            - row["layer_fwd_bwd_fused_prologue_ms"], 3
        )
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
