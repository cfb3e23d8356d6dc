#!/usr/bin/env python3
"""How ``recorded_v5e_gpt2.json.gz`` was made: a few whole steps cut out of
a trace kept by ``run.py --trace 1 --keep-trace 1`` on the chip, as the
plain lists ``reduce.Trace`` holds, names already cut by ``short_name``.

    python benchmark/tests/record_trace.py <file.xplane.pb> <out.json.gz> [steps]
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce as reducer  # noqa: E402


def main(argv):
    path, out = argv[1], argv[2]
    steps = int(argv[3]) if len(argv) > 3 else 3
    trace = reducer.load(path, 1)
    device = trace.devices[0]
    starts = trace.step_starts(device)
    lo, hi = starts[2], starts[2 + steps] + 1000  # the last step's start stays in

    data = {
        "devices": [{
            "name": device["name"],
            "ops": [e for e in device["ops"] if lo <= e[1] and e[1] + e[2] <= hi],
            "modules": [e for e in device["modules"] if lo <= e[1] < hi],
        }],
        "host": [e for e in trace.host if e[1] < hi and e[1] + e[2] > lo],
    }
    with gzip.open(out, "wt") as f:
        json.dump(data, f, separators=(",", ":"))
    print(out, os.path.getsize(out), "bytes;", len(data["devices"][0]["ops"]), "ops")


if __name__ == "__main__":
    main(sys.argv)
