#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine that holds the chips the cell
asks for. The cell's configuration, traffic mix, driver, limits and
per-layer metrics are files found by the names in ``BENCHMARK.json``; this
file knows none of them. The last line of standard output is the result.
"""

import time

STARTED = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--keep-trace", type=int, choices=(0, 1), default=0,
        help="leave the .xplane.pb under benchmark/.trace/ (for inspect_trace.py)",
    )
    return parser.parse_args(argv)


def run_cell(args, loaded, devices, peak, clock):
    """Everything after the look for a chip: (result, compared). ``loaded``
    is what ``harness.load_cell`` gave for the workload."""
    cell, config, traffic, bench = loaded
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    driver = harness.load_module("drivers", traffic["driver"])
    return driver.run(cell, config, traffic, bench, args, clock, devices, peak)


def main(argv=None):
    args = parse(argv)
    faulthandler.dump_traceback_later(harness.WATCHDOG_S, exit=True)
    loaded = harness.load_cell(args.workload)
    # the program's own rule for the compile cache: JAX's variable where it
    # is set, else .jax_cache/ inside this checkout
    from distributed_pytorch_example_tpu.runtime import enable_compile_cache

    harness.say(f"compile cache at {enable_compile_cache()}")
    devices, peak = harness.find_chips(loaded[0]["chips"])
    result, compared = run_cell(
        args, loaded, devices, peak, harness.Clock(STARTED)
    )
    faulthandler.cancel_dump_traceback_later()
    harness.print_result(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
