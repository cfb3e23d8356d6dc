"""How much of a 1F1B cycle the last-stage head costs — and what
predicating it saves.

Without predication, the lockstep SPMD 1F1B schedule evaluates ``last_fn``
(GPT-2: final LayerNorm + fused tied-embedding CE, gpt2.py _run_1f1b) on
EVERY device EVERY cycle, where-masked to garbage on all but the last
stage's consuming ticks — wasted head FLOPs on (S-1)/S of the mesh. The
``predicate_head`` knob (parallel/pipeline.py) wraps the head in a
per-device ``lax.cond`` instead (legal: last_fn is collective-free by
contract), so non-last stages skip it at runtime.

Static XLA cost analysis counts a ``lax.cond`` branch whether or not it
runs, so the saving cannot be read off whole-program flops. This script
measures the UNITS with the real model pieces instead, on the same
GPT-2 shape as scripts/pipeline_memory.py (256d x 8L over 4 stages,
microbatch 4 x seq 128):

- stage forward / forward+backward: 2-layer StackedDecoder slice;
- head forward+backward: the exact 1F1B last_fn (models/stacked.py
  make_chunked_ce_last with gpt2.py's LayerNorm prep and tied table);

and derives the head's share of a steady-state cycle plus the per-device
average flops predication removes. The artifact-config vocab (512) is
deliberately tiny; a flagship-vocab row (50257) shows the share at real
LM-head scale, where predication is the difference between the head being
noise and the head dominating the cycle.

Run (fake CPU, no mesh needed):
  PYTHONPATH=. python \
      scripts/pipeline_head_cost.py [--json results/pipeline_1f1b/head_cost.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


S = 4  # pipeline stages (matches pipeline_memory.py's pipe=4 mesh)


def _flops(fn, *args) -> float:
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0))


def stage_units(mb_size: int, seq: int) -> dict:
    """Measured flops of one pipeline stage (2 of 8 layers at S=4)."""
    from distributed_pytorch_example_tpu.models.stacked import StackedDecoder

    model = StackedDecoder(
        num_layers=2, num_heads=8, head_dim=32, model_dim=256, mlp_dim=1024,
    )
    h = jnp.asarray(
        np.random.default_rng(0).standard_normal((mb_size, seq, 256)),
        jnp.float32,
    )
    params = model.init(jax.random.key(0), h)["params"]

    def fwd(p, hh):
        return model.apply({"params": p}, hh)

    def fwd_bwd(p, hh):
        # sum-cotangent backward: same flop count as any real cotangent
        return jax.grad(lambda a, b: fwd(a, b).sum(), argnums=(0, 1))(p, hh)

    f = _flops(fwd, params, h)
    fb = _flops(fwd_bwd, params, h)
    return {"fwd": f, "fwd_bwd": fb, "bwd_only": fb - f}


def head_unit(mb_size: int, seq: int, vocab: int) -> float:
    """Measured flops of one last_fn eval + its backward — the exact
    in-schedule GPT-2 head (LayerNorm prep + chunked fused CE)."""
    from distributed_pytorch_example_tpu.models.stacked import (
        _layer_norm,
        make_chunked_ce_last,
    )

    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((mb_size, seq, 256)), jnp.float32)
    tok = jnp.asarray(rng.integers(0, vocab, size=(mb_size, seq)), jnp.int32)
    table = jnp.asarray(rng.standard_normal((vocab, 256)) * 0.02, jnp.float32)
    scale, bias = jnp.ones((256,)), jnp.zeros((256,))

    def prep(lp, yy):
        sc, bs, tb = lp
        return _layer_norm(yy, sc, bs, 1e-5, jnp.float32), tb

    last_fn, last_args = make_chunked_ce_last(prep, tok, sp=False)

    def head(lp, yy):
        return last_fn(lp, yy, last_args)[0]

    return _flops(
        jax.value_and_grad(head, argnums=(0, 1)), (scale, bias, table), y
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mb-size", type=int, default=4)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--vocabs", default="512,50257")
    parser.add_argument("--json", default=None)
    args = parser.parse_args()

    stage = stage_units(args.mb_size, args.seq)
    rows = []
    for vocab in (int(v) for v in args.vocabs.split(",")):
        head = head_unit(args.mb_size, args.seq, vocab)
        # steady-state cycle, head unpredicated (runs on every device):
        # stash backward applies the stored vjp; recompute replays the
        # stage forward first
        cycle_stash = stage["fwd"] + stage["bwd_only"] + head
        cycle_rec = stage["fwd"] + stage["fwd_bwd"] + head
        rows.append({
            "vocab": vocab,
            "head_gflops": round(head / 1e9, 4),
            "head_frac_of_stash_cycle": round(head / cycle_stash, 4),
            "head_frac_of_recompute_cycle": round(head / cycle_rec, 4),
            # per-device average flops predication removes: (S-1)/S of
            # devices stop evaluating the head each cycle
            "predication_saving_frac": round(
                (S - 1) / S * head / cycle_stash, 4
            ),
        })
        print(json.dumps(rows[-1]), flush=True)

    out = {
        "stage_gflops": {k: round(v / 1e9, 4) for k, v in stage.items()},
        "rows": rows,
        "threshold": "predication justified at head >= 5% of a cycle",
        "config": {
            "mb_size": args.mb_size, "seq": args.seq, "stages": S,
            "model": "gpt2 256d, 2 layers/stage", "jax": jax.__version__,
        },
    }
    print(json.dumps(out), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
