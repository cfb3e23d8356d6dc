"""What the two set-up metrics share: the compile log's programs that were
recorded before the window's ``fit`` span opened
(``telemetry/compilelog.py``: one ``compile:<fun_name>`` span a program,
with ``trace_s``, ``lower_s``, ``compile_s`` and ``cache_hit``)."""

from layer_metrics import program_spans as ps


def before_window():
    """(programs before the window, every record row before it), or
    (None, []) where the record holds no compile log or no ``fit``."""
    rows = ps.record()
    fit, _ = ps.window_fit(rows)
    if fit is None:
        return None, []
    early = [r for r in rows if r.start_ns < fit.start_ns]
    programs = [r for r in early if r.name.startswith(ps.COMPILE) and r.args]
    return (programs or None), early


def by_name(programs, keys):
    totals = {}
    for r in programs:
        name = r.name[len(ps.COMPILE):]
        totals[name] = totals.get(name, 0.0) + sum(r.args[k] for k in keys)
    return sorted(totals.items(), key=lambda kv: -kv[1])
