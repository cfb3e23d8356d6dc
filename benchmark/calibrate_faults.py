#!/usr/bin/env python3
"""Upper readings of a training cell's limits from faults that a
configuration's own reference can plant in itself, beside
``calibrate_controls.py`` (which reads the fp8 control and half a batch and
knows one such fault by name).

    python benchmark/calibrate_faults.py --workload <name> [--seeds 3] \
        --fault scores_without_rotary:score_dims=128 \
        --fault no_select_bias:use_select_bias=false

Builds no Trainer. A fault is ``name:key=json``: the plain reference run
with that key of the configuration replaced, put in the program's place
against the plain reference, on rows and weights made from the seed as a
run makes them. A reading must rise for a fault, else the comparison does
not check what the fault breaks. One JSON line a seed to
``chiprun_out/calibrate_faults.<cell>.jsonl``.
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


def parse_fault(text):
    name, _, change = text.partition(":")
    key, _, value = change.partition("=")
    return name, key, json.loads(value)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=7_000_000_001)
    parser.add_argument("--fault", action="append", required=True)
    args = parser.parse_args(argv)

    import jax

    cell, config, traffic, _ = harness.load_cell(args.workload)
    devices, _ = harness.find_chips(cell["chips"])
    from drivers import train_window

    model = harness.load_module("reference", config["reference"])
    rows_a_step = traffic["rows_per_chip"] * len(devices)

    def steps(sizes):
        return train_reference.ReferenceSteps(
            model, sizes, traffic["adam"], traffic["reference_block_rows"],
            train_reference.plain_dot, devices,
        )

    plain = steps(config)
    faults = {
        name: steps({**config, key: value})
        for name, key, value in map(parse_fault, args.fault)
    }
    make = jax.jit(lambda key: model.init_params(key, config))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"calibrate_faults.{cell['name']}.jsonl")
    with open(out_path, "a") as out:
        for n in range(args.seeds):
            seed = args.first_seed + 7919 * n
            t0 = time.time()
            data_seed, weight_seed, mask_seed = harness.seed_words(seed, 3)
            tokens = train_window.TokenRows(
                traffic["dataset_rows"], traffic["seq_len"],
                config["vocab_size"], data_seed,
            ).tokens
            batches = [
                tokens[i * rows_a_step:(i + 1) * rows_a_step]
                for i in range(train_window.CHECK_STEPS)
            ]
            key, mask_key = jax.random.key(weight_seed), jax.random.key(mask_seed)
            # weights made anew for each run: `run` consumes them
            reference = plain.run(make(key), batches, mask_key)
            line = {"seed": seed, "cell": cell["name"],
                    "reference_losses": reference["losses"]}
            for name, broken in faults.items():
                line[name], line[name + "_leaves"] = train_reference.compare(
                    broken.run(make(key), batches, mask_key), reference
                )
            line["seconds"] = time.time() - t0
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
