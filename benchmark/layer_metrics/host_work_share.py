"""Layer: entry (``train/loop.py``). Share of the traced window of whole
steps in which the training thread does the host's own work: its time
inside ``train_step`` or ``data_load`` spans less the wait spans (blocked on
the device or on the loader's queue), in per cent. How far the device can
be sped up before the host sets the pace. Notes: milliseconds a step by
span name (self time)."""

import reduce as reducer
from layer_metrics import program_spans as ps


def read(run):
    spans = ps.host_spans(run)
    trace = run["trace"]
    if not spans or trace.steps() < 1:
        return None
    lo, hi = trace.windows[0]
    loop = ps.intervals(spans, ps.LOOP, lo, hi)
    waits = ps.intervals(spans, ps.WAITS, lo, hi)
    work = reducer.measure(reducer.subtract(loop, waits))
    inside = [e for e in spans if e[1] >= lo and e[1] + e[2] <= hi]
    own = sorted(reducer.self_times(inside).items(), key=lambda kv: -kv[1])
    ps.say(run, "host_work_share: %.3f ms of host work a step of %.3f ms; "
           "self ms a step by span: %s" % (
               work / trace.steps() / 1e6, (hi - lo) / trace.steps() / 1e6,
               ", ".join(f"{n} {ns / trace.steps() / 1e6:.3f}" for n, ns in own),
           ))
    return 100.0 * work / (hi - lo)
