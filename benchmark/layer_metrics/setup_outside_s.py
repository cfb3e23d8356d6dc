"""Layer: set-up (``setup_parts.py``). Seconds of the set-up under no
program span after ``train.py::main`` opened ``main_args``: the driver's
own work between the program's calls (rows, weights, the compared
readings' norms, ``block_until_ready``), with the seconds of any root span
that is not an import, ``main_*`` or ``fit``. Notes: the largest gaps by the
root span before each, and those other spans."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None:
        return None
    value = parts["ns"]["outside"] / 1e9
    gaps = sorted(parts["gaps"], reverse=True)
    ps.say(run, "setup_outside_s: %d gaps; largest, after: %s; other root "
           "spans: %s; compile spans at the root %.3f s" % (
               len(gaps), ", ".join(f"{root} {n / 1e9:.3f}" for n, root in gaps[:8]),
               setup_parts.largest_first(parts["names"]["outside"]) or "none",
               parts["compile_in"].get("outside", 0) / 1e9,
           ))
    return value
