#!/usr/bin/env python3
"""The upper readings of a training cell's limits, for a configuration too
large for ``calibrate.py``, which keeps the Trainer on the device while the
reference runs (at 507.8M parameters the program's state and arena and the
reference's five copies do not fit together).

    python benchmark/calibrate_controls.py --workload <name> [--seeds 3]

Builds no Trainer. On the chip, at the cell's own size, for each seed, on
rows and weights made from the seed as a run makes them: the plain
reference, and put in the program's place against it

* ``control_fp8``: the reference in fp8 (``train_reference.fp8_dot``), the
  precision below the one the configuration states;
* ``fault_half_batch``: half of every batch left out (``rows_used``);
* ``fault_no_select_bias``: the selection bias left out of the choice (a
  copy of the configuration with ``use_expert_bias`` false), where the
  configuration has the key: a reading must rise for it, else the
  comparison does not check the routing.

The lower readings (the program against the reference) come from the
cell's own runs: ``run.py`` frees the program before the reference runs and
prints "every gap read" on stderr. One JSON line a seed to
``chiprun_out/calibrate_controls.<cell>.jsonl``.
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

CONTROLS = ("control_fp8", "fault_half_batch", "fault_no_select_bias")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=7_000_000_001)
    parser.add_argument("--controls", default=",".join(CONTROLS))
    args = parser.parse_args(argv)

    import jax

    cell, config, traffic, _ = harness.load_cell(args.workload)
    devices, _ = harness.find_chips(cell["chips"])
    from drivers import train_window

    model = harness.load_module("reference", config["reference"])
    rows_a_step = traffic["rows_per_chip"] * len(devices)

    def steps(sizes=config, dot=train_reference.plain_dot):
        return train_reference.ReferenceSteps(
            model, sizes, traffic["adam"], traffic["reference_block_rows"],
            dot, devices,
        )

    wanted = [c for c in args.controls.split(",") if c]
    if "use_expert_bias" not in config and "fault_no_select_bias" in wanted:
        wanted.remove("fault_no_select_bias")
    plain = steps()
    others = {  # name: (the steps put in the program's place, rows used)
        "control_fp8": (steps(dot=train_reference.fp8_dot), None),
        "fault_half_batch": (plain, slice(0, rows_a_step // 2)),
        "fault_no_select_bias": (
            steps(sizes={**config, "use_expert_bias": False}), None
        ),
    }
    make = jax.jit(lambda key: model.init_params(key, config))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"calibrate_controls.{cell['name']}.jsonl")
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
                    "reference_losses": reference["losses"],
                    "reference_s": time.time() - t0}
            for name in wanted:
                in_its_place, rows_used = others[name]
                broken = in_its_place.run(
                    make(key), batches, mask_key, rows_used=rows_used
                )
                line[name], leaves = train_reference.compare(broken, reference)
                line[name + "_leaves"] = leaves
            line["seconds"] = time.time() - t0
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
