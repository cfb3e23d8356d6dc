#!/usr/bin/env python3
"""Reads the two ends every limit of a training cell is set between.

    python benchmark/calibrate.py --workload <name> --seeds 12 [--control-seeds 3]

On the chip, at the cell's own size, in ONE process (set-up is long): for
each seed the program's first three steps against the plain reference (the
lower reading: the largest over the seeds); and on the first
``--control-seeds`` of them, put in the program's place, the reference in
fp8 (the control: the upper reading is its smallest) and the reference with
a fault planted (half of the batch left out; one chip's rows only, where
the cell spans chips). A state left unchanged reads 1 by construction.
Writes one JSON line a seed to ``chiprun_out/calibrate.<cell>.jsonl``. Not
run by the benchmark's own runs.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import train_reference  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=7_000_000_001)
    args = parser.parse_args(argv)

    cell, config, traffic, _ = harness.load_cell(args.workload)
    from distributed_pytorch_example_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    devices, _ = harness.find_chips(cell["chips"])
    from drivers import train_window

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"calibrate.{cell['name']}.jsonl")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    job = train_window.TrainRun(config, traffic, seeds[0], devices)
    plain = job.reference_steps()
    fp8 = job.reference_steps(train_reference.fp8_dot)
    rows = job.global_rows
    faults = {"half_batch": slice(0, rows // 2)}
    if job.chips > 1:
        faults["no_exchange"] = slice(0, rows // job.chips)
    with open(out_path, "w") as out:
        for n, seed in enumerate(seeds):
            t0 = time.time()
            if n:
                job.reseed(seed)
            program = job.program_readings()
            batches = list(job.loader.kept)
            # weights made anew for each run: `run` consumes them
            reference = plain.run(job.reference_params(), batches, job.mask_key)
            line = {"seed": seed, "cell": cell["name"]}
            line["program"], line["program_leaves"] = train_reference.compare(
                program, reference
            )
            line["losses"] = {
                "program": program["losses"], "reference": reference["losses"]
            }
            if n < args.control_seeds:
                control = fp8.run(job.reference_params(), batches, job.mask_key)
                line["control_fp8"], _ = train_reference.compare(control, reference)
                for name, used in faults.items():
                    broken = plain.run(
                        job.reference_params(), batches, job.mask_key,
                        rows_used=used,
                    )
                    line["fault_" + name], _ = train_reference.compare(
                        broken, reference
                    )
            line["seconds"] = time.time() - t0
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
