"""Layer: entry (``train/loop.py``). What one ``Trainer.fit`` call costs
beside its steps, in seconds, from the program's in-memory record: the
window's own ``fit`` span less what its ``train_step``, ``data_load`` and
``epoch_drain`` spans cover, plus the first ``data_load`` (the prefetch
worker starts there) and ``record_compile`` (the cost analysis of the
compiled step, inside the first ``train_step``). The profiler's start and
stop hold the training thread inside later ``data_load``s (the benchmark
switches it from the loader's wrapper) and are so left out."""

import reduce as reducer
from layer_metrics import program_spans as ps

LEFT_OUT = ("train_step", "data_load", "epoch_drain")


def read(run):
    fit, tree = ps.window_fit(ps.record())
    if fit is None or not any(r.name == "train_step" for r in tree):
        return None
    covered = reducer.measure(reducer.union(
        [(r.start_ns, r.end_ns) for r in tree if r.name in LEFT_OUT]
    ))
    loads = sorted(
        (r for r in tree if r.name == "data_load"), key=lambda r: r.start_ns
    )
    first_load = loads[0].end_ns - loads[0].start_ns if loads else 0
    registered = sum(
        r.end_ns - r.start_ns for r in tree if r.name == "record_compile"
    )
    whole = fit.end_ns - fit.start_ns
    cost = whole - covered + first_load + registered
    parts = {
        name: sum(r.end_ns - r.start_ns for r in tree if r.name == name)
        for name in ("fit_open", "fit_close", "epoch_drain")
    }
    ps.say(run, "fit_fixed_cost_s: fit %.6f s = %.6f s in train_step, "
           "data_load and epoch_drain + %.6f s beside them; first data_load "
           "%.6f s, record_compile %.6f s; %s" % (
               whole / 1e9, covered / 1e9, (whole - covered) / 1e9,
               first_load / 1e9, registered / 1e9,
               ", ".join(f"{n} {ns / 1e9:.6f} s" for n, ns in parts.items()),
           ))
    return cost / 1e9
