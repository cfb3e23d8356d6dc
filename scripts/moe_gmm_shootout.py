#!/usr/bin/env python3
"""Grouped matrix product shoot-out for the dropless expert layer
(models/moe.py::moe_dropless), on the chip, at the shapes of the
lfm2-8b-a1b cell: the repo's Pallas kernels (``ops/pallas/moe_gmm.py``)
against ``jax.lax.ragged_dot`` and against the installed kernel they follow
(``jax.experimental.pallas.ops.tpu.megablox``).

    chiprun -- python scripts/moe_gmm_shootout.py

Rows sorted by expert in a buffer of ``rows`` rows of which ``used`` hold an
assignment (the rest lie past the last group, and hold NaN here: nothing of
them may reach a result); 8 groups, uneven sizes from a seed; bf16 operands
and results, f32 accumulation. Three programs a product: forward, the
gradient of the rows, the gradient of the weights. For each shape one JSON
line to ``chiprun_out/moe_gmm_shootout.jsonl``: the repo's kernels against
``ragged_dot`` on the rows that hold an assignment (largest difference,
beside the reference's largest entry), whether their rows past the last
group are zeros, how many of ``ragged_dot``'s are not (it leaves them
unspecified on the TPU), and the median milliseconds of [forward, d rows, d
weights] for each. The program keeps the faster; this script stays as the
record of how it was measured (PERF.md section 6, PR 29).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_pytorch_example_tpu.ops.pallas import moe_gmm  # noqa: E402

GROUPS = 8
PRODUCTS = {"gate_up": (2048, 3584), "down": (1792, 2048)}
# (rows in the buffer, rows that hold an assignment)
SIZES = ((32768, 32768), (65536, 32768), (131072, 32768))
MEGABLOX_TILING = (512, 1024, 1024)  # the fastest of three tried
REPEATS = 6


def group_sizes(used, seed=0):
    """Eight uneven sizes that add up to ``used``."""
    rng = np.random.default_rng(seed)
    share = rng.uniform(0.6, 1.4, GROUPS)
    sizes = np.floor(share / share.sum() * used).astype(np.int32)
    sizes[-1] += used - sizes.sum()
    return jnp.asarray(sizes)


def timed_ms(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return round(1e3 * float(np.median(times)), 3)


def programs(product):
    """forward, d rows, d weights of a product ``(lhs, rhs, sizes) -> out``."""
    def d_rows(lhs, rhs, sizes, g):
        return jax.vjp(lambda x: product(x, rhs, sizes), lhs)[1](g)[0]

    def d_weights(lhs, rhs, sizes, g):
        return jax.vjp(lambda w: product(lhs, w, sizes), rhs)[1](g)[0]

    return jax.jit(product), jax.jit(d_rows), jax.jit(d_weights)


def ragged(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)


def megablox(lhs, rhs, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.gmm(lhs, rhs, sizes, lhs.dtype, MEGABLOX_TILING)


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("moe_gmm_shootout: no TPU here; a CPU time means nothing")
    os.makedirs("chiprun_out", exist_ok=True)
    candidates = {
        "moe_gmm": programs(moe_gmm.grouped_matmul),
        "ragged_dot": programs(ragged),
        "megablox": programs(megablox),
    }
    with open("chiprun_out/moe_gmm_shootout.jsonl", "w") as out:
        for name, (k, n) in PRODUCTS.items():
            rhs = 0.02 * jax.random.normal(
                jax.random.key(2), (GROUPS, k, n), jnp.bfloat16
            )
            for rows, used in SIZES:
                lhs = jax.random.normal(jax.random.key(1), (rows, k), jnp.bfloat16)
                g = jax.random.normal(jax.random.key(3), (rows, n), jnp.bfloat16)
                dirty = lhs.at[used:].set(jnp.nan), g.at[used:].set(jnp.nan)
                clean = lhs.at[used:].set(0), g.at[used:].set(0)
                sizes = group_sizes(used)
                line = {"product": name, "rows": rows, "used": used,
                        "device": device.device_kind}
                ours, theirs = candidates["moe_gmm"], candidates["ragged_dot"]
                for i, what in enumerate(("fwd", "d_rows", "d_weights")):
                    a = ours[i](dirty[0], rhs, sizes, *dirty[1:][:i])
                    b = theirs[i](clean[0], rhs, sizes, *clean[1:][:i])
                    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                    if what != "d_weights":
                        line[what + "_tail_all_zero"] = bool((a[used:] == 0).all())
                        line[what + "_ragged_tail_nonzero"] = int((b[used:] != 0).sum())
                        a, b = a[:used], b[:used]
                    line[what + "_finite"] = bool(jnp.isfinite(a).all())
                    line[what + "_max_abs_diff"] = float(jnp.abs(a - b).max())
                    line[what + "_ref_max_abs"] = float(jnp.abs(b).max())
                for impl, (fwd, d_rows, d_weights) in candidates.items():
                    line[impl + "_ms"] = [
                        timed_ms(fwd, clean[0], rhs, sizes),
                        timed_ms(d_rows, clean[0], rhs, sizes, clean[1]),
                        timed_ms(d_weights, clean[0], rhs, sizes, clean[1]),
                    ]
                text = json.dumps(line)
                print(text, flush=True)
                out.write(text + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
