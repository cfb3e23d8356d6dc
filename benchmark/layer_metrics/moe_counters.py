"""What the two routing counters share: the ``moe_counters`` rows that the
program puts into its in-memory record at each ``log_fetch`` (every 10th
step; ``train/loop.py``), under the window's own ``fit`` span. Their
``args`` are the step's counters as the compiled step made them from the
routing: ``held_share``, ``load_max_over_mean`` (worst layer),
``rows_used_share`` (worst layer), ``dropped_assignments``. The traced
seconds alone may hold no ``log_fetch``, so the whole window is read, as
``fit_fixed_cost_s`` reads it. A program without the rows gives None."""

from layer_metrics import program_spans as ps


def window_rows(run):
    if "moe_counters" not in run:
        fit, _ = ps.window_fit(ps.record())
        run["moe_counters"] = [] if fit is None else [
            r.args for r in ps.record()
            if r.name == "moe_counters" and r.root == fit.id and r.args
        ]
        if run["moe_counters"]:
            rows = run["moe_counters"]
            means = {
                k: sum(r[k] for r in rows) / len(rows) for k in sorted(rows[0])
            }
            ps.say(run, f"moe_counters: {len(rows)} rows in the window, means {means}")
    return run["moe_counters"]


def mean(run, name):
    values = [r[name] for r in window_rows(run) if name in r]
    return sum(values) / len(values) if values else None
