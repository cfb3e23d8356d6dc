"""Layer: compile (``Trainer._train_executable``, telemetry's compile log).
Seconds of tracing and lowering, over every program the compile log
recorded before the window's ``fit`` span opened: what a process pays anew
at every start before the persistent cache can answer. Notes: by program."""

from layer_metrics import compile_log
from layer_metrics import program_spans as ps

KEYS = ("trace_s", "lower_s")


def read(run):
    programs, _ = compile_log.before_window()
    if programs is None:
        return None
    named = compile_log.by_name(programs, KEYS)
    ps.say(run, "setup_trace_lower_s: %d programs, tracing %.3f s, lowering "
           "%.3f s; by program: %s" % (
               len(programs), sum(r.args["trace_s"] for r in programs),
               sum(r.args["lower_s"] for r in programs),
               ", ".join(f"{n} {s:.3f}" for n, s in named[:12]),
           ))
    return sum(s for _, s in named)
