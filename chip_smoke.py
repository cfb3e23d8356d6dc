#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py             # one chip: train, resume, serve
    python chip_smoke.py --chips 4   # four chips: the across-chip path only

ONE process drives everything through the entry points a user calls —
``train.main()`` and ``serve.main()`` — because a chip belongs to one
process at a time. It exits non-zero at the first thing that is wrong, and
without an accelerator at the device check, before any result is printed.

One chip (the default): GPT-2 124M at its published widths (12 layers, d 768,
12 heads of 64, vocab 50257), sequence 1024, bf16, 16 sequences — eight
optimizer steps on seeded synthetic tokens (four epochs over 32 sequences:
uniform random tokens hold nothing to learn but themselves, so the loss
falls only where they repeat), a checkpoint, a ``--resume`` from it; then a
GPT-2-width server answering a handful of greedy requests, whose
tokens are compared with ``train/generate.py``'s dense decode.

``--chips 4``: the same model data-parallel over four chips (global batch
64), plain and with ZeRO-1 over the Pallas ring collectives, each compared
with the same global batch on ONE device of the same process (four
accumulated micro-batches). No resume and no serve phase under this option.

Every number printed here is a smoke observation of one run, not a
benchmark. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib.metadata
import json
import logging
import math
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, ".smoke_run")  # checkpoints; removed at the end

SEQ_LEN, BATCH = 1024, 16
EPOCHS, BATCHES_PER_EPOCH = 4, 2  # 8 optimizer steps, every sequence seen 4x
STEPS = EPOCHS * BATCHES_PER_EPOCH
RESUME_EPOCHS = 2  # the resume run continues for two more
LN_VOCAB = math.log(50257)
# the first loss of a randomly initialised GPT-2 sits a little above
# ln(vocab): tied-embedding logits have a small, non-zero variance
FIRST_LOSS_BAND = 0.5
# dp4 vs one device, same global batch: bf16 matmuls reduce in a different
# order across 4 x 16 rows than across 4 micro-batches of 16
LOSS_TOLERANCE = 0.02
# greedy tokens may differ from the dense reference only where the
# reference's own top-2 logits are closer than this
MARGIN_TOLERANCE = 0.05
# a fresh run that spent longer than this in backend compiles was a cold
# one (a cache hit on the train step leaves a few seconds of retrieval)
COLD_COMPILE_S = 20.0
# the whole script must end inside the driver's 1200 s; a hung collective
# must not hold the chip until then
WATCHDOG_S = {1: 1100, 4: 900}

TRAIN_ARGV = [
    "--model", "gpt2", "--dataset", "synthetic-tokens",
    "--seq-len", str(SEQ_LEN), "--batch-size", str(BATCH),
    "--dtype", "bfloat16", "--seed", "0", "--log-every", "1",
]
SERVE_ARGV = [
    "--vocab-size", "50257", "--model-dim", "768", "--num-layers", "12",
    "--num-heads", "12", "--max-len", "1024",
    "--block-size", "16", "--max-blocks", "64", "--num-blocks", "256",
    "--slots", "8", "--requests", "6", "--rate", "0",
    "--prompt-len", "16:48", "--max-new", "8:16",
    "--temperature", "0", "--seed", "0",
]


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    say(f"{'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        sys.exit(1)


class LoopLog(logging.Handler):
    """The Trainer's own log lines: per-step losses with their arrival
    times, and the checkpoint-restore line."""

    LOSS = re.compile(r"Epoch (\d+), Batch (\d+)/(\d+), Loss: ([-\w.]+)")

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.losses, self.times, self.batches, self.loaded = [], [], [], []

    def emit(self, record):
        msg = record.getMessage()
        m = self.LOSS.search(msg)
        if m:
            self.losses.append(float(m.group(4)))
            self.times.append(record.created)
            self.batches.append((int(m.group(1)), int(m.group(2))))
        elif "heckpoint loaded" in msg:
            self.loaded.append(msg)


def compile_snapshot():
    """(persistent-cache hits, misses, seconds in backend compiles) so far,
    from the program's own compile log (telemetry/compilelog.py)."""
    from distributed_pytorch_example_tpu.telemetry import compilelog

    totals = compilelog.totals()
    return totals["cache_hits"], totals["cache_misses"], totals["compile_s"]


def run_train(argv, devices=None):
    """train.main() on ``argv``; returns (trainer, log, seconds to the
    first logged step, compile-event deltas)."""
    import train

    log = LoopLog()
    loop_logger = logging.getLogger("distributed_pytorch_example_tpu.train")
    loop_logger.addHandler(log)
    before = compile_snapshot()
    t0 = time.time()
    try:
        trainer = train.main(argv, devices=devices)
    finally:
        loop_logger.removeHandler(log)
    after = compile_snapshot()
    check(bool(log.losses), f"train.main({' '.join(argv[-6:])}) logged steps")
    check(
        all(math.isfinite(x) for x in log.losses),
        f"{len(log.losses)} losses, all finite: "
        + " ".join(f"{x:.4f}" for x in log.losses),
    )
    delta = tuple(a - b for a, b in zip(after, before))
    return trainer, log, log.times[0] - t0, delta


def train_executable(trainer):
    (exe,) = [v for k, v in trainer._compiled.items() if k[0] == "train"]
    return exe


def steady_step_seconds(log):
    """Shortest gap between two consecutive logged steps of one epoch,
    past the first (which holds the compile)."""
    gaps = [
        t1 - t0
        for (e0, b0), (e1, b1), t0, t1 in zip(
            log.batches, log.batches[1:], log.times, log.times[1:]
        )
        if e0 == e1 and b1 == b0 + 1 and (e0, b0) != log.batches[0]
    ]
    return min(gaps) if gaps else float("nan")


def distinct_devices(array) -> int:
    return len({s.device for s in array.addressable_shards})


# ---------------------------------------------------------------------------
# one chip: train, resume, serve
# ---------------------------------------------------------------------------


def phase_train_resume():
    ckpt = os.path.join(WORKDIR, "ckpt")
    samples = str(BATCH * BATCHES_PER_EPOCH)
    fresh = TRAIN_ARGV + [
        "--epochs", str(EPOCHS), "--num-samples", samples,
        "--checkpoint-dir", ckpt,
    ]
    trainer, log, first_s, (hits, misses, compile_s) = run_train(fresh)
    check(len(log.losses) >= STEPS, f"{len(log.losses)} optimizer steps (>= {STEPS})")
    check(
        abs(log.losses[0] - LN_VOCAB) < FIRST_LOSS_BAND,
        f"first loss {log.losses[0]:.4f} near ln(50257) = {LN_VOCAB:.4f}",
    )
    check(
        log.losses[-1] < log.losses[0],
        f"last loss {log.losses[-1]:.4f} < first {log.losses[0]:.4f}",
    )
    exe = train_executable(trainer)
    n_kernels = exe.as_text().count("tpu_custom_call")
    check(
        n_kernels > 0,
        f"train step HLO holds {n_kernels} tpu_custom_call (the Pallas flash "
        "kernels: auto dispatch did not give way to XLA attention)",
    )
    mem = exe.memory_analysis()
    peak = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    say(
        f"train step memory_analysis: peak {peak} bytes "
        f"({peak / 2**30:.2f} GiB: arguments {mem.argument_size_in_bytes}, "
        f"temporaries {mem.temp_size_in_bytes}, "
        f"aliased {mem.alias_size_in_bytes})"
    )
    say(
        f"fresh run: {first_s:.1f} s to first step, {compile_s:.1f} s in "
        f"backend compiles, persistent cache {hits} hits / {misses} misses"
    )
    say(
        f"steady step {steady_step_seconds(log):.3f} s "
        "(smoke, not a benchmark: the shortest of a few gaps between "
        "logged steps, one loss fetch a step)"
    )
    latest = os.path.join(ckpt, "latest_model.ckpt")
    check(os.path.exists(latest), f"checkpoint saved at {latest}")
    saved_step = int(trainer.state.step)
    check(saved_step == len(log.losses), f"saved at optimizer step {saved_step}")
    del trainer, exe

    resume = TRAIN_ARGV + [
        "--epochs", str(EPOCHS + RESUME_EPOCHS), "--num-samples", samples,
        "--checkpoint-dir", ckpt, "--resume", latest,
    ]
    trainer, rlog, r_first_s, (r_hits, r_misses, r_compile_s) = run_train(resume)
    check(bool(rlog.loaded), f"resume logged: {rlog.loaded[:1]}")
    check(
        int(trainer.state.step) == saved_step + len(rlog.losses),
        f"resume continued at step {saved_step}: now at "
        f"{int(trainer.state.step)} after {len(rlog.losses)} more",
    )
    check(
        rlog.batches[0] == (EPOCHS, 0)
        and len(rlog.losses) == RESUME_EPOCHS * BATCHES_PER_EPOCH,
        f"resume began at epoch {rlog.batches[0][0]}, batch "
        f"{rlog.batches[0][1]} and ran {len(rlog.losses)} steps",
    )
    check(
        rlog.losses[0] < log.losses[0],
        f"resumed first loss {rlog.losses[0]:.4f} below the fresh run's "
        f"first {log.losses[0]:.4f} (trained weights came back)",
    )
    say(
        f"resume run: {r_first_s:.1f} s to first step, {r_compile_s:.1f} s in "
        f"backend compiles, persistent cache {r_hits} hits / {r_misses} misses"
    )
    check(r_hits > 0, "the resume run's new Trainer hit the persistent compile cache")
    if misses and compile_s > COLD_COMPILE_S:
        # the fresh run really compiled: the cached one must be quicker
        check(
            r_compile_s < 0.5 * compile_s and r_first_s < first_s,
            f"cached start ({r_first_s:.1f} s, {r_compile_s:.1f} s compiling) "
            f"well under the cold one ({first_s:.1f} s, {compile_s:.1f} s)",
        )
    else:
        say(
            f"fresh run found its large programs in the cache already "
            f"({compile_s:.1f} s compiling): no cold time to compare with"
        )


def dense_margin(model, params, tokens):
    """Top-2 logit margin of the dense model at the position after
    ``tokens`` (1-D)."""
    import jax.numpy as jnp

    logits = model.apply({"params": params}, jnp.asarray(tokens)[None], train=False)
    top = jnp.sort(logits[0, -1].astype(jnp.float32))[-2:]
    return float(top[1] - top[0])


def phase_serve():
    import jax.numpy as jnp
    import numpy as np

    import serve
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.generate import generate

    state = {}
    rc = serve.main(SERVE_ARGV, state=state)
    check(rc == 0, "serve.main() returned 0")
    results = state["report"]["results"]
    check(
        len(results) == len(state["requests"])
        and all(r["status"] == "done" for r in results.values()),
        f"{len(results)} requests, statuses "
        f"{sorted({r['status'] for r in results.values()})}",
    )
    engine = state["engine"]
    decode = engine.lowered_programs()["serve/decode"].compile()
    n_kernels = decode.as_text().count("tpu_custom_call")
    check(
        n_kernels > 0,
        f"decode program holds {n_kernels} tpu_custom_call (the fused paged "
        "kernel, not paged_attention_reference)",
    )

    _, params, _ = state["built"]
    kw = dict(
        vocab_size=50257, max_len=1024, model_dim=768, num_layers=12,
        num_heads=12, mlp_dim=2 * 768,
    )
    dense_decode, dense = GPT2(**kw, decode=True), GPT2(**kw)
    exact = close = 0
    for req in state["requests"]:
        got = [int(t) for t in results[req.rid]["tokens"]]
        prompt = np.asarray(req.prompt, np.int32)
        ref = generate(
            dense_decode, params, jnp.asarray(prompt)[None],
            req.max_new_tokens, temperature=0.0,
        )
        ref = [int(t) for t in np.asarray(ref)[0, len(prompt):]]
        if got == ref[: len(got)] and len(got) == len(ref):
            exact += 1
            continue
        i = next(
            (j for j, (a, b) in enumerate(zip(got, ref)) if a != b),
            min(len(got), len(ref)),
        )
        check(
            i < min(len(got), len(ref)),
            f"{req.rid}: lengths differ ({len(got)} vs {len(ref)}) with no "
            "differing token",
        )
        margin = dense_margin(dense, params, list(prompt) + ref[:i])
        check(
            margin < MARGIN_TOLERANCE,
            f"{req.rid}: token {i} differs ({got[i]} vs {ref[i]}) where the "
            f"dense top-2 margin is {margin:.4f} (< {MARGIN_TOLERANCE})",
        )
        close += 1
    say(
        f"served tokens vs train/generate.py dense greedy decode: {exact} "
        f"requests token-exact, {close} diverging only inside the top-2 margin"
    )
    m = state["report"]["metrics"]
    say(
        f"serve: {m['generated_tokens']} tokens in {m['elapsed_s']:.2f} s, "
        f"{m['decode_steps']} decode steps (smoke, not a benchmark: the "
        "elapsed time includes both compiles)"
    )


# ---------------------------------------------------------------------------
# four chips: data parallel and ZeRO-1 over the ring kernels
# ---------------------------------------------------------------------------


def phase_four_chips(devices):
    import distributed_pytorch_example_tpu as dpx

    steps, accum = 3, 4
    base = TRAIN_ARGV + [
        "--epochs", "1", "--num-samples", str(BATCH * accum * steps),
        "--checkpoint-dir", "",
    ]
    # what the others are compared with: the same seeds, the same global
    # batch of 64 as four accumulated micro-batches on ONE device
    _, ref_log, _, _ = run_train(
        base + ["--mesh-data", "1", "--grad-accum", str(accum)],
        devices=devices[:1],
    )
    check(len(ref_log.losses) == steps * accum, "one-device reference ran")
    ref = [
        sum(ref_log.losses[i * accum:(i + 1) * accum]) / accum
        for i in range(steps)
    ]
    say("one device, 4 micro-batches a step: " + " ".join(f"{x:.4f}" for x in ref))

    def compare(name, argv):
        trainer, log, first_s, _ = run_train(base + ["--mesh-data", "4"] + argv)
        worst = max(abs(a - b) for a, b in zip(log.losses, ref))
        check(
            len(log.losses) == steps and worst < LOSS_TOLERANCE,
            f"{name}: per-step losses within {LOSS_TOLERANCE} of the "
            f"one-device run (worst {worst:.4f}); {first_s:.1f} s to first step",
        )
        mesh = trainer.partitioner.mesh
        loader = dpx.data.DeviceLoader(
            dpx.data.SyntheticTokenDataset(
                num_samples=4 * BATCH, seq_len=SEQ_LEN
            ),
            4 * BATCH, mesh=mesh,
        )
        tokens = next(iter(loader))["tokens"]
        check(
            distinct_devices(tokens) == 4
            and tokens.addressable_shards[0].data.shape == (BATCH, SEQ_LEN),
            f"{name}: a global batch {tokens.shape} sits on "
            f"{distinct_devices(tokens)} distinct devices in shards of "
            f"{tokens.addressable_shards[0].data.shape}",
        )
        return trainer

    compare("dp4", [])

    import jax

    # `--zero1` alone reaches no ring kernel on GPT-2 at dp4 (every leaf has
    # a dimension that divides by four, and the uncompressed per-leaf sync
    # is lax.psum_scatter): the bucketed f32 reduce-scatter and the bf16
    # param gather are the existing flags that go through both kernels
    trainer = compare(
        "dp4 + ZeRO-1 (ring)",
        ["--zero1", "--overlap-buckets", "-1", "--wire-param-gather", "bf16"],
    )
    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(trainer.state.opt_state)
        if hasattr(leaf, "addressable_shards") and leaf.ndim
        and leaf.addressable_shards[0].data.size * 4 == leaf.size
    ]
    check(
        bool(sharded) and all(distinct_devices(x) == 4 for x in sharded),
        f"ZeRO-1: {len(sharded)} optimizer-state leaves hold a quarter each "
        "on 4 distinct devices",
    )
    hlo = train_executable(trainer).as_text()
    kernels = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    ring = {
        name: sum(1 for l in kernels if name in l)
        for name in ("ring_reduce_scatter", "ring_all_gather")
    }
    check(
        all(ring.values()),
        f"ZeRO-1 step HLO holds the ring kernels: {ring} of "
        f"{len(kernels)} tpu_custom_call",
    )


# ---------------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run the across-chip path (and what it is compared with) "
        "and no other phase",
    )
    args = parser.parse_args()
    faulthandler.dump_traceback_later(WATCHDOG_S[args.chips], exit=True)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        say(f"FAIL no accelerator: jax.devices()[0].platform == {platform!r}")
        return 1
    say(
        f"ok   platform tpu, device_kind {kind!r}, {len(devices)} device(s); "
        f"jax {jax.__version__}, jaxlib {importlib.metadata.version('jaxlib')}, "
        f"libtpu {importlib.metadata.version('libtpu')}"
    )
    check(
        len(devices) == args.chips,
        f"--chips {args.chips} on a machine with {len(devices)} device(s)",
    )

    from distributed_pytorch_example_tpu import native
    from distributed_pytorch_example_tpu.runtime import enable_compile_cache
    from distributed_pytorch_example_tpu.telemetry.cost import peak_bf16_flops

    say(f"compile cache at {enable_compile_cache()}")
    check(
        peak_bf16_flops(devices[0]) is not None,
        f"telemetry/cost.py knows {kind!r}: peak bf16 "
        f"{peak_bf16_flops(devices[0]):.3g} FLOP/s",
    )
    binding = native.get_binding()
    check(
        binding is not None and os.path.exists(binding._SO),
        f"native C++ library built from dpxnative.cpp and loaded: "
        f"{getattr(binding, '_SO', None)}",
    )
    shutil.rmtree(WORKDIR, ignore_errors=True)
    try:
        if args.chips == 4:
            phase_four_chips(devices)
        else:
            phase_train_resume()
            phase_serve()
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
