"""One-off probe: where does the GPT-2 bench step's time go? (real TPU)

Times the full train step under three loss tails (fused chunked-CE, dense
CE, no-head probe loss) plus a forward-only pass, to locate the head/loss
cost inside the 124M step. Not part of the test suite.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import distributed_pytorch_example_tpu as dpx
from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

B, S = 8, 1024
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, 50257, (B, S)).astype(np.int32))


def _fence(out):
    # a real device->host transfer fences the dispatched chain
    leaf = jax.tree_util.tree_leaves(out)[-1]
    np.asarray(jax.device_get(leaf.ravel()[0] if leaf.ndim else leaf))


def time_step(fn, args, n=20, warmup=5):
    c = jax.jit(fn).lower(*args).compile()
    out = None
    for _ in range(warmup):
        out = c(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = c(*args)
    _fence(out)
    return (time.perf_counter() - t0) / n


def train_step_fn(model, task):
    tx = optax.adam(1e-3)

    def step(params, opt_state, tokens):
        def loss_fn(p):
            loss, metrics, _ = task.compute_loss(
                model, p, {}, {"tokens": tokens}, jax.random.key(1), train=True
            )
            return loss, metrics

        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, metrics

    return step


class ProbeLoss:
    """No-head loss: mean of final hidden states (upper-bounds body cost)."""

    def compute_loss(self, model, params, model_state, batch, rng, *, train):
        out = model.apply(
            {"params": params}, batch["tokens"], train=False
        )
        loss = jnp.mean(out.astype(jnp.float32)) ** 2
        return loss, {"loss": loss}, model_state


def main():
    tx = optax.adam(1e-3)
    results = {}
    for name, mode, task in (
        ("fused", "hidden", CausalLMTask()),
        ("dense", "full", CausalLMTask()),
        ("nohead", "hidden", ProbeLoss()),
    ):
        model = dpx.models.get_model(
            "gpt2", dtype=jnp.bfloat16, logits_mode=mode
        )
        params = model.init(jax.random.key(0), tokens, train=False)["params"]
        opt_state = tx.init(params)
        dt = time_step(train_step_fn(model, task), (params, opt_state, tokens))
        results[name] = dt
        print(f"{name:8s} train step: {dt * 1e3:8.2f} ms", flush=True)

    model = dpx.models.get_model("gpt2", dtype=jnp.bfloat16, logits_mode="hidden")
    params = model.init(jax.random.key(0), tokens, train=False)["params"]

    def fwd(params, tokens):
        return model.apply({"params": params}, tokens, train=False)

    dt = time_step(fwd, (params, tokens))
    print(f"{'fwd-only':8s} (no head):  {dt * 1e3:8.2f} ms", flush=True)
    head_cost = results["fused"] - results["nohead"]
    print(f"head+CE cost fused: {head_cost * 1e3:.2f} ms; "
          f"dense: {(results['dense'] - results['nohead']) * 1e3:.2f} ms")


if __name__ == "__main__":
    main()
