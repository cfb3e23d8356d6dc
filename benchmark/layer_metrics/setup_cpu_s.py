"""Layer: set-up (``setup_parts.py``). Process CPU seconds, every thread's
(XLA's compile threads among them), from the first ``import`` row's open to
the window ``fit``'s open: their ``cpu_open_s`` args. Beside the wall
seconds of the same stretch it tells seconds the host computed from seconds
it waited (disk, page cache, the device, a lock). Notes: the CPU seconds
under the import rows, the ``main_*`` spans and the set-up's ``fit`` calls,
and between them."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def _cpu(rows):
    return sum(r.args["cpu_close_s"] - r.args["cpu_open_s"] for r in rows)


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None or "cpu_open_s" not in (parts["fit"].args or {}):
        return None
    imports = sorted(parts["imports"], key=lambda r: r.start_ns)
    # an import row nested in another (train.py's around the package's,
    # where train.py imports the package first) is counted once
    outer = [r for r in imports if not any(
        o is not r and o.start_ns <= r.start_ns and r.end_ns <= o.end_ns
        for o in imports
    )]
    fit = parts["fit"]
    value = fit.args["cpu_open_s"] - imports[0].args["cpu_open_s"]
    spans = {"import rows": _cpu(outer), "main_*": _cpu(parts["mains"]),
             "set-up fits": _cpu(parts["fits"])}
    wall = (fit.start_ns - imports[0].start_ns) / 1e9
    ps.say(run, "setup_cpu_s: %.3f CPU s over %.3f wall s (%.2f a wall "
           "second); %s, between them %.3f" % (
               value, wall, value / wall,
               ", ".join(f"{n} {s:.3f}" for n, s in spans.items()),
               value - sum(spans.values()),
           ))
    return value
