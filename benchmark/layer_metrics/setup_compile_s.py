"""Layer: compile (``Trainer._train_executable``, telemetry's compile log).
Seconds in backend compiles or loads from the persistent cache, over the
programs the compile log recorded before the window's ``fit`` span opened.
Notes: cache hits and misses, by program; and the seconds of the
``main_*`` and ``init_state`` spans of ``train.main``."""

from layer_metrics import compile_log
from layer_metrics import program_spans as ps

SETUP_SPANS = (
    "main_args", "main_runtime", "main_data", "main_model", "main_trainer",
    "init_state",
)


def read(run):
    programs, early = compile_log.before_window()
    if programs is None:
        return None
    named = compile_log.by_name(programs, ("compile_s",))
    hits = sum(r.args["cache_hit"] is True for r in programs)
    misses = sum(r.args["cache_hit"] is False for r in programs)
    spans = {
        name: sum(r.end_ns - r.start_ns for r in early if r.name == name) / 1e9
        for name in SETUP_SPANS
    }
    ps.say(run, "setup_compile_s: %d programs, %d from the persistent cache, "
           "%d compiled, %d not asked; by program: %s; spans of train.main: %s" % (
               len(programs), hits, misses, len(programs) - hits - misses,
               ", ".join(f"{n} {s:.3f}" for n, s in named[:12]),
               ", ".join(f"{n} {s:.3f} s" for n, s in spans.items()),
           ))
    return sum(s for _, s in named)
