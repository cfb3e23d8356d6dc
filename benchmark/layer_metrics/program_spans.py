"""What the readers of the program's own spans share.

The program opens every span through one call
(``telemetry/trace.py::span``), which puts it in two places a reader can
see: the profiler's trace, as a ``TraceAnnotation`` on the device planes'
clock (``run["trace"].host``: every event of the training thread, JAX's own
among them), and one in-memory record, ``telemetry.trace.recorded()``,
which holds the whole process and not only the traced seconds. A program
without the spans (an older commit) has neither: every function here then
gives nothing, and the readers return None.

The names are the program's contract (PERF.md §3). ``WAITS`` are the spans
in which the training thread blocks on the device or on the loader's
queue; everything else inside ``train_step`` is the host's own work.
"""

import bisect

import reduce as reducer

WAITS = (
    "data_load", "clock_fence", "boundary_fetch", "log_fetch",
    "bad_step_drain", "epoch_drain",
)
WORK = (
    "train_step", "aot_lookup", "record_compile", "step", "metrics_add",
    "saver_check", "checkpoint", "eval", "train_epoch", "fit", "fit_open",
    "fit_close",
)
PROGRAM = frozenset(WAITS + WORK)
LOOP = ("train_step", "data_load")  # between them they cover the epoch loop
COMPILE = "compile:"
NO_SPAN = "no_program_span"


def host_spans(run):
    """The program's spans among the training thread's events of the traced
    window, [name, start_ns, dur_ns]; empty where the program opens none."""
    spans = [e for e in run["trace"].host if e[0] in PROGRAM]
    return spans if any(e[0] == "train_step" for e in spans) else []


def intervals(spans, names, lo, hi):
    """Disjoint sorted intervals that spans of these names cover in [lo, hi)."""
    return reducer.union(reducer.clip(
        [(s, s + d) for name, s, d in spans if name in names], lo, hi
    ))


def innermost(spans):
    """Disjoint sorted (start, end, name) segments: at each instant the
    innermost of the nested spans of one thread."""
    segments, stack = [], []  # stack of [name, end]

    def emit(lo, hi):
        if stack and hi > lo:
            segments.append((lo, hi, stack[-1][0]))

    cursor = None
    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(cursor, stack[-1][1])
            cursor = stack.pop()[1]
        emit(cursor, start)
        cursor = start
        stack.append([name, start + dur])
    while stack:
        emit(cursor, stack[-1][1])
        cursor = stack.pop()[1]
    return segments


def name_at(segments, starts, t):
    """The innermost span's name at ``t`` (``starts``: the segments'
    starts, for the bisection)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and segments[i][0] <= t < segments[i][1]:
        return segments[i][2]
    return NO_SPAN


def record():
    """The program's in-memory record, or [] where it keeps none."""
    try:
        from distributed_pytorch_example_tpu.telemetry import trace

        return list(trace.recorded())
    except (ImportError, AttributeError):
        return []


def window_fit(rows):
    """(the window's own ``fit`` span, the spans of its tree on its thread):
    the last ``fit`` the process closed — the reference that follows the
    window calls none. None where the record holds no ``fit``."""
    fits = [r for r in rows if r.name == "fit"]
    if not fits:
        return None, []
    fit = fits[-1]
    return fit, [r for r in rows if r.root == fit.id and r.thread == fit.thread]


def say(run, text):
    run.setdefault("notes", []).append(text)
