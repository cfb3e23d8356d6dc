"""Layer: entry (``train/loop.py``). Device idle time that a wait span of
the program accounts for: of the idle gaps of the traced window (on the
chip where the idle share is largest, as ``device_idle_share`` takes it),
those whose start lies in a span in which the training thread blocks on
the device (``clock_fence``, ``boundary_fetch``, ``log_fetch``,
``bad_step_drain``, ``epoch_drain``) or on the loader (``data_load``), the
innermost program span deciding; over the window, in per cent. Notes: the
whole idle time split by program span, ``no_program_span`` last."""

import reduce as reducer
from layer_metrics import program_spans as ps

LONG_GAP_NS = 100_000  # the notes tell a fence's gap from the gaps between ops


def read(run):
    spans = ps.host_spans(run)
    if not spans:
        return None
    trace = run["trace"]
    worst, gaps = None, []
    for device, (lo, hi) in zip(trace.devices, trace.windows):
        busy = reducer.union(reducer.clip(
            [(s, s + d) for _, s, d in device["ops"]], lo, hi
        ))
        idle = reducer.subtract([(lo, hi)], busy)
        share = reducer.measure(idle) / (hi - lo)
        if worst is None or share > worst:
            worst, gaps, window = share, idle, hi - lo
    segments = ps.innermost(spans)
    starts = [s[0] for s in segments]
    by_name = {}
    for a, b in gaps:
        name = ps.name_at(segments, starts, a)
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    rest = by_name.pop(ps.NO_SPAN, 0.0)
    split = sorted(by_name.items(), key=lambda kv: -kv[1]) + [(ps.NO_SPAN, rest)]
    long = [b - a for a, b in gaps if b - a >= LONG_GAP_NS]
    ps.say(run, "fence_idle_share: idle %.6f s of %.6f s (worst chip) in %d "
           "gaps, %d of them of 0.1 ms or more (%.6f s); by program span: %s" % (
               worst * window / 1e9, window / 1e9, len(gaps), len(long),
               sum(long) / 1e9,
               ", ".join(f"{n} {ns / 1e9:.6f}" for n, ns in split),
           ))
    waited = sum(ns for n, ns in by_name.items() if n in ps.WAITS)
    return 100.0 * waited / window
