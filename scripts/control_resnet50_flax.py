#!/usr/bin/env python3
"""Cross-stack control: a CANONICAL flax ResNet-50 train step, timed.

The framework's ResNet-50 sits at ~0.31 MFU and the trace-backed analysis
blames XLA's conv-backward lowering (backward convs at ~32% MXU vs ~55%
forward — README perf section). That claim needs a control: this script
times a vanilla flax ResNet-50 — written from the flax examples' idiom
(plain ``nn.Conv`` NHWC, ``nn.BatchNorm``, canonical 7x7/2 + maxpool stem,
bottleneck v1.5 blocks), deliberately importing NOTHING from
``distributed_pytorch_example_tpu`` — under the same batch/dtype/optimizer
and a value-fetch fence around the timed steps.

If this lands at ~0.31 MFU too, the ceiling is XLA:TPU's conv-backward at
these shapes, not framework overhead. If it lands higher, the framework
has a gap to close. Prints one JSON line; run it on an idle chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

ModuleDef = Any


class Bottleneck(nn.Module):
    """Canonical v1.5 bottleneck: stride on the 3x3, BN after each conv."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet50(nn.Module):
    """flax-examples-style ResNet-50: 7x7/2 stem + maxpool, [3,4,6,3]."""

    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    stage_sizes: Sequence[int] = (3, 4, 6, 3)

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype,
        )
        x = x.astype(self.dtype)
        x = conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)])(x)
        x = norm()(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = Bottleneck(
                    filters=64 * 2 ** i, conv=conv, norm=norm,
                    strides=strides,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=128)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--warmup", type=int, default=8)
    args = parser.parse_args()

    model = ResNet50()
    tx = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((args.batch, args.image_size, args.image_size, 3)),
        jnp.float32,
    )
    y = jnp.asarray(rng.integers(0, 1000, (args.batch,)), jnp.int32)
    variables = model.init(jax.random.key(0), x[:2])
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    def train_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, new_opt, loss

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    compiled = step.lower(params, batch_stats, opt_state, x, y).compile()
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        flops = float(analysis["flops"])
    except Exception:
        flops = None

    for _ in range(args.warmup):
        params, batch_stats, opt_state, loss = compiled(
            params, batch_stats, opt_state, x, y
        )
    float(loss)  # value-fetch fence
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, batch_stats, opt_state, loss = compiled(
            params, batch_stats, opt_state, x, y
        )
    float(loss)
    dt = time.perf_counter() - t0

    rate = args.batch * args.steps / dt
    out = {
        "control": "canonical-flax-resnet50",
        "samples_per_sec_per_chip": round(rate, 1),
        "batch": args.batch,
        "steps": args.steps,
        "dtype": "bfloat16",
    }
    kind = getattr(jax.devices()[0], "device_kind", "").lower()
    peak = 197e12 if ("v5e" in kind or "v5 lite" in kind) else None
    if flops is not None and peak is not None:
        out["mfu"] = round(flops * (args.steps / dt) / peak, 4)
        out["flops_per_step"] = flops
    print(json.dumps(out))
    print(
        f"control: {rate:.0f} samples/s, mfu={out.get('mfu')}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
