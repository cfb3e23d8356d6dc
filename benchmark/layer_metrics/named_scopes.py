"""Device time under the scopes that the lfm2 stack and the dropless expert
layer put in the compiled step (``jax.named_scope``: ``moe_route``,
``moe_dispatch``, ``moe_experts`` in ``models/moe.py``, ``short_conv`` in
``models/lfm2.py``), for the metrics that read them.

``scope_ops.py`` reads the trace's ``/host:metadata`` plane and knows two
scopes by name; this file takes its walk of the protobuf and its rule (an
op's self time; a fusion goes whole to the scope most of its fused
instructions carry) and applies them to these four. A program without the
scopes gives nothing, and the readers return None.
"""

import collections
import glob
import os
import re

import harness
import reduce as reducer
from layer_metrics import scope_ops

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "short_conv")
_COMPONENT = {
    scope: re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)") for scope in SCOPES
}
OTHER = scope_ops.OTHER


def scope_of(op_name):
    for scope, rx in _COMPONENT.items():
        if rx.search(op_name):
            return scope
    return OTHER


def op_scopes(hlo_proto):
    """{instruction name: scope}, a fusion by the majority of what is fused
    into it (``scope_ops.op_scopes``' rule)."""
    computations = scope_ops.instructions(hlo_proto)
    scopes = {}
    for rows in computations.values():
        for name, opcode, op_name, called in rows:
            scope = scope_of(op_name)
            if opcode == "fusion" and called:
                votes = collections.Counter(
                    scope_of(inner_op_name)
                    for ident in called
                    for _, inner_opcode, inner_op_name, _ in computations.get(ident, [])
                    if inner_opcode not in scope_ops.NOT_WORK
                )
                best = max(votes.values(), default=0)
                winners = [s for s, v in votes.items() if v == best]
                if len(winners) == 1:
                    scope = winners[0]
            scopes[name] = scope
    return scopes


def self_times_by_scope(path, step_module, plane_name, lo, hi):
    """{scope: self nanoseconds} of one device plane's ``XLA Ops`` inside
    [lo, hi), or None where the step names none of the scopes."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        protos = scope_ops.program_protos(f.read())
    if step_module not in protos:
        return None
    scopes = op_scopes(protos[step_module])
    if not any(scope != OTHER for scope in scopes.values()):
        return None
    for plane in ProfileData.from_file(path).planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != reducer.OPS_LINE:
                continue
            events = []
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                m = scope_ops._INSTRUCTION.match(e.name)
                if m and start >= lo and start + dur <= hi:
                    events.append((scopes.get(m.group(1), OTHER), start, dur))
            return reducer.self_times(events)
    return None


def seconds(run, scopes):
    """Self seconds of device 0's ops under these scopes inside the traced
    window of whole steps; None where the trace names none of them."""
    trace = run["trace"]
    lo, hi = trace.windows[0]
    if "named_scopes" not in run:
        found = glob.glob(os.path.join(
            harness.BENCH_DIR, ".trace", run["cell"]["name"],
            "plugins", "profile", "*", "*.xplane.pb",
        ))
        run["named_scopes"] = found and self_times_by_scope(
            found[0], trace.step_module, trace.devices[0]["name"], lo, hi
        )
    totals = run["named_scopes"]
    if not totals or not any(totals.get(scope) for scope in scopes):
        return None
    return sum(totals.get(scope, 0.0) for scope in scopes) / 1e9


def share(run, scopes):
    """The same as a share of the window, in per cent."""
    found = seconds(run, scopes)
    if found is None:
        return None
    lo, hi = run["trace"].windows[0]
    run.setdefault("notes", []).append(
        f"{'+'.join(scopes)}: {found:.6f} s of self time in "
        f"{(hi - lo) / 1e9:.6f} s on device 0"
    )
    return 100.0 * found * 1e9 / (hi - lo)
