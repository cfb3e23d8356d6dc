"""Device time under the scopes that the ``joyai-llm-flash`` stack puts in
the compiled step (``jax.named_scope``: ``mtp`` around the prediction
module in ``models/joyai.py``, ``mla_proj`` around latent attention's
projections, ``moe_shared`` around the shared expert in ``models/moe.py``),
for the metrics that read them.

The scopes nest: the prediction module holds a whole decoder layer, so an
op of its attention's projections carries ``mtp`` and ``mla_proj``. An op
goes to the FIRST scope of ``SCOPES`` that its ``op_name`` names, so
``mtp`` wins over what is nested in it and the three shares add up to no
more than the window. Otherwise the rule is ``scope_ops.py``'s: an op's self
time; a fusion goes whole to the scope most of its fused instructions
carry. ``scope_ops.instructions`` walks the step's HLO from the trace
file's metadata plane; the events are those the run's ``Trace`` already
holds, so the file is not parsed for them again. A program without the
scopes gives nothing, and the readers return None.
"""

import collections
import glob
import os
import re

import harness
import reduce as reducer
from layer_metrics import scope_ops

SCOPES = ("mtp", "mla_proj", "moe_shared")  # in the order they win
_COMPONENT = [
    (scope, re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)")) for scope in SCOPES
]
OTHER = scope_ops.OTHER


def scope_of(op_name):
    for scope, rx in _COMPONENT:
        if rx.search(op_name):
            return scope
    return OTHER


def op_scopes(hlo_proto):
    """{instruction name: scope}, a fusion by the majority of what is fused
    into it (``scope_ops.op_scopes``' rule, over this file's scopes)."""
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


def self_times_by_scope(trace, hlo_proto):
    """{scope: self nanoseconds} of device 0's ops inside the traced window
    of whole steps, or None where the step names none of the scopes."""
    scopes = op_scopes(hlo_proto)
    if not any(scope != OTHER for scope in scopes.values()):
        return None
    lo, hi = trace.windows[0]
    return reducer.self_times([
        (scopes.get(name.split(" = ", 1)[0], OTHER), start, dur)
        for name, start, dur in trace.devices[0]["ops"]
        if start >= lo and start + dur <= hi
    ])


def share(run, scope):
    """Self time under ``scope`` as a share of the window, in per cent."""
    trace = run["trace"]
    if "nested_scopes" not in run:
        found = glob.glob(os.path.join(
            harness.BENCH_DIR, ".trace", run["cell"]["name"],
            "plugins", "profile", "*", "*.xplane.pb",
        ))
        run["nested_scopes"] = None
        if found:
            with open(found[0], "rb") as f:
                proto = scope_ops.program_protos(f.read()).get(trace.step_module)
            if proto is not None:
                run["nested_scopes"] = self_times_by_scope(trace, proto)
    totals = run["nested_scopes"]
    if not totals or not totals.get(scope):
        return None
    lo, hi = trace.windows[0]
    run.setdefault("notes", []).append(
        f"{scope}_device_share: {totals[scope] / 1e9:.6f} s of self time "
        f"under scope {scope!r} in {(hi - lo) / 1e9:.6f} s on device 0"
    )
    return 100.0 * totals[scope] / (hi - lo)
