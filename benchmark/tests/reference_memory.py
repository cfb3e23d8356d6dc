#!/usr/bin/env python3
"""How much of the chip the plain reference needs for a model of a stated
size: run by hand on the chip, like ``record_trace.py``.

    python benchmark/tests/reference_memory.py --layers 24 --embd 1280

Builds ``reference/gpt2.py`` at that size from a seed (no Trainer, nothing
of the program), takes ``ReferenceSteps`` through three steps on random rows
in blocks of one row, and prints the parameters, the backend's
``peak_bytes_in_use`` and ``peak_bytes_reserved`` and their sum over
``bytes_limit`` as one JSON line (also when the device ran out of memory:
then ``error`` says so and the exit code is 1). ``--trail 1`` adds the bytes
in use after each call of the reference's programs, which says where the
peak was reached. PERF.md section 8 keeps the
readings that say how large a configuration the harness holds.
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)


def _watch(steps, device):
    """After each call of the reference's programs: [name, bytes in use,
    their peak so far, bytes of the arrays JAX itself counts alive], so
    that the reading says where the peak was reached."""
    import jax

    trail = []

    def watched(name, fn):
        def call(*args):
            out = jax.block_until_ready(fn(*args))
            stats = device.memory_stats() or {}
            trail.append([
                name, stats.get("bytes_in_use"), stats.get("peak_bytes_in_use"),
                sum(x.nbytes for x in jax.live_arrays() if not x.is_deleted()),
            ])
            return out
        return call

    for name in ("_grad", "_add", "_norms", "_update", "_change"):
        setattr(steps, name, watched(name, getattr(steps, name)))
    return trail


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--layers", type=int, default=12)
    parser.add_argument("--embd", type=int, default=768)
    parser.add_argument("--vocab", type=int, default=50257)
    parser.add_argument("--positions", type=int, default=1024)
    parser.add_argument("--rows", type=int, default=2, help="rows a step")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument(
        "--trail", type=int, choices=(0, 1), default=0,
        help="the bytes in use after each call of the reference's programs; "
        "it makes the host wait after each, so the peak may read lower",
    )
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    import train_reference
    from reference import gpt2

    sizes = {
        "n_embd": args.embd, "n_head": args.embd // 64, "n_layer": args.layers,
        "n_inner": None, "n_positions": args.positions,
        "vocab_size": args.vocab, "initializer_range": 0.02,
        "layer_norm_epsilon": 1e-5,
    }
    adam = {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    device = jax.devices()[0]
    rng = np.random.default_rng(args.seed)
    batches = [
        rng.integers(0, args.vocab, (args.rows, args.positions), dtype=np.int32)
        for _ in range(3)
    ]
    params = jax.jit(lambda key: gpt2.init_params(key, sizes))(
        jax.random.key(args.seed)
    )
    line = {
        "platform": device.platform, "kind": device.device_kind,
        "sizes": sizes, "rows_a_step": args.rows,
        "parameters": sum(x.size for x in params.values()),
    }
    steps = train_reference.ReferenceSteps(gpt2, sizes, adam, block_rows=1)
    if args.trail:
        line["trail"] = _watch(steps, device)
    try:
        line["losses"] = steps.run(params, batches, jax.random.key(0))["losses"]
    except Exception as e:  # the device ran out: what it held is the reading
        line["error"] = f"{type(e).__name__}: {str(e).splitlines()[0][:300]}"
    stats = device.memory_stats() or {}  # the CPU keeps none: not measured
    for key in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"):
        line[key] = stats.get(key)
    if stats:
        peak = stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
        line["peak_bytes"] = peak
        line["peak_over_limit"] = peak / stats["bytes_limit"]
        line["peak_bytes_a_parameter"] = peak / line["parameters"]
    print(json.dumps(line), flush=True)
    return 1 if "error" in line else 0


if __name__ == "__main__":
    sys.exit(main())
