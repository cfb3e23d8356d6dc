"""Long-context GPT-2 measurement on one real chip (committed evidence).

Runs the full train step (fused chunked-CE loss, Pallas flash attention,
rematerialized blocks) at growing sequence lengths on a GPT-2-124M-body
model whose position table is sized to the sequence. Prints one JSON line
per config with tokens/sec/chip and TWO utilization numbers:

- ``mfu_analytic``: 6*P_matmul*T + 6*L*S*D*T model FLOPs (the standard
  PaLM-style accounting; causal attention at half the dense S^2 cost) over
  peak — the honest long-context metric;
- ``hfu_xla``: XLA cost-analysis FLOPs over peak. XLA counts Pallas
  custom calls as ZERO FLOPs, so this UNDERCOUNTS ever more as the
  attention share grows with S — reported for transparency, not headline.

Usage: python scripts/bench_longctx.py [--seqs 2048,4096,8192] [--steps 10]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_BF16_V5E = 197e12


def run(seq_len: int, batch: int, steps: int, warmup: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = dpx.models.get_model(
        "gpt2", dtype=jnp.bfloat16, logits_mode="hidden", max_len=seq_len,
        remat=True,
    )
    task = CausalLMTask()
    tx = optax.adam(1e-3)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(
            0, 50257, (batch, seq_len)
        ).astype(np.int32)
    )
    params = model.init(jax.random.key(0), tokens, train=False)["params"]
    opt = tx.init(params)

    def step(params, opt, tokens):
        def loss_fn(p):
            loss, m, _ = task.compute_loss(
                model, p, {}, {"tokens": tokens}, jax.random.key(1),
                train=True,
            )
            return loss, m

        (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        u, new_opt = tx.update(g, opt, params)
        return optax.apply_updates(params, u), new_opt, m

    # donate params/optimizer state like the real Trainer step does —
    # without it the 32k config carries an extra ~1.7 GB of undonated
    # outputs and OOMs the 16 GB chip
    compiled = jax.jit(
        step, donate_argnums=(0, 1)
    ).lower(params, opt, tokens).compile()
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        flops = float(analysis["flops"])
    except Exception:
        flops = None
    m = None
    for _ in range(warmup):
        params, opt, m = compiled(params, opt, tokens)
    float(m["loss"])  # value-fetch fence
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, m = compiled(params, opt, tokens)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / steps

    tokens_total = batch * seq_len
    # matmul-participating params: everything but the position table
    p_matmul = sum(
        x.size for x in jax.tree_util.tree_leaves(params)
    ) - params["wpe"].size
    model_flops = tokens_total * (
        6 * p_matmul + 6 * model.num_layers * seq_len * model.model_dim
    )
    result = {
        "seq_len": seq_len,
        "batch_per_chip": batch,
        "tokens_per_sec_per_chip": round(tokens_total / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "mfu_analytic": round(model_flops / dt / PEAK_BF16_V5E, 4),
    }
    if flops is not None:
        result["hfu_xla"] = round(flops / dt / PEAK_BF16_V5E, 4)
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", default="2048,4096,8192,16384")
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--tokens-per-batch", type=int, default=16384,
                        help="batch x seq held ~constant across configs")
    args = parser.parse_args()
    for s in (int(x) for x in args.seqs.split(",")):
        batch = max(1, args.tokens_per_batch // s)
        print(json.dumps(run(s, batch, args.steps, args.warmup)), flush=True)


if __name__ == "__main__":
    main()
