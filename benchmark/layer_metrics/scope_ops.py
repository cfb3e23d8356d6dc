"""What the two scope metrics share: the device time of the ops that the
program put under a ``jax.named_scope`` (``chunked_ce`` in
``ops/chunked_ce.py``, ``optimizer`` in ``train/step.py``).

A scope is metadata of an HLO instruction (``op_name``:
``jit(train_step)/transpose(jvp(chunked_ce))/dot_general``). What a v5e
trace holds of it (looked at by hand, PR 25; PERF.md §3): an ``XLA Ops``
event is named by the instruction's text WITHOUT its metadata and carries
three stats (``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``), none of them a name. But the same ``.xplane.pb`` has a plane
``/host:metadata`` with one entry per executed program, named like the
``XLA Modules`` event (``jit_train_step(<fingerprint>)``), whose stat
``Hlo Proto`` is the compiled module, every instruction with its name and
``op_name``. ``jax.profiler.ProfileData`` shows a plane's lines, and that
plane has none, so this file walks the protobuf's wire format itself (tags,
varints and lengths: stable since proto2; the field numbers below are those
of ``xplane.proto``, ``hlo.proto`` and ``xla_data.proto``) and takes nothing
but each instruction's name, opcode, ``op_name`` and fused computation of
the step program. The events come from
``ProfileData`` as everywhere else; the driver leaves the file under
``benchmark/.trace/<cell>/`` until the readers have run.

A fusion's own ``op_name`` is that of one instruction fused into it (its
root, as a rule), and XLA fuses across scopes: Adam's update of a leaf
shares a multi-output fusion with the sentinels' norm of that leaf, whose
reduction is the root. So a fusion goes, whole, to the scope that most of
the instructions of its fused computation carry (parameters and constants
aside), and its own ``op_name`` decides only a tie. An op's time is its self
time: a ``conditional`` or ``while`` spans its children on the same line
(Adam sits inside the bad-step ``conditional``).
"""

import collections
import glob
import os
import re

import harness
import reduce as reducer

SCOPES = ("chunked_ce", "optimizer")
_COMPONENT = {
    scope: re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)") for scope in SCOPES
}
OTHER = "-"
METADATA_PLANE = b"/host:metadata"
_INSTRUCTION = re.compile(r"^%(\S+) = ")

# field numbers: XSpace.planes; XPlane.name, .event_metadata (a map entry:
# key 1, value 2); XEventMetadata.name, .stats; XStat.bytes_value;
# HloProto.hlo_module; HloModuleProto.computations;
# HloComputationProto.instructions, .id; HloInstructionProto.name, .opcode,
# .metadata, .called_computation_ids; OpMetadata.op_name
PLANES, PLANE_NAME, EVENT_METADATA, MAP_VALUE = 1, 2, 4, 2
METADATA_NAME, METADATA_STATS, STAT_BYTES = 2, 5, 6
HLO_MODULE, COMPUTATIONS, INSTRUCTIONS, COMPUTATION_ID = 1, 3, 2, 5
INSTRUCTION_NAME, OPCODE, INSTRUCTION_METADATA, CALLED, OP_NAME = 1, 2, 7, 38, 2
NOT_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or a fixed-width field, a memoryview for a length-delimited one."""
    view, pos, end = memoryview(buf), 0, len(buf)

    def varint():
        nonlocal pos
        result = shift = 0
        while True:
            byte = view[pos]
            pos += 1
            result |= (byte & 0x7F) << shift
            if byte < 0x80:
                return result
            shift += 7

    while pos < end:
        tag = varint()
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            yield number, varint()
        elif wire == 2:
            size = varint()
            yield number, view[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield number, int.from_bytes(view[pos:pos + size], "little")
            pos += size
        else:
            raise ValueError(f"wire type {wire} in a protobuf message")


def first(buf, number):
    return next((v for n, v in fields(buf) if n == number), None)


def program_protos(xspace):
    """{program name: its ``Hlo Proto`` bytes} of the metadata plane."""
    found = {}
    for number, plane in fields(xspace):
        if number != PLANES or bytes(first(plane, PLANE_NAME) or b"") != METADATA_PLANE:
            continue
        for n, entry in fields(plane):
            if n != EVENT_METADATA:
                continue
            metadata = first(entry, MAP_VALUE)
            name = bytes(first(metadata, METADATA_NAME) or b"").decode()
            for m, stat in fields(metadata):
                proto = first(stat, STAT_BYTES) if m == METADATA_STATS else None
                if proto is not None:
                    found[name] = proto
    return found


def _unpack(buf):
    """The numbers of a packed repeated varint field."""
    out, value, shift = [], 0, 0
    for byte in bytes(buf):
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            out.append(value)
            value = shift = 0
        else:
            shift += 7
    return out


def instructions(hlo_proto):
    """{computation id: [(name, opcode, op_name, called computation ids)]}
    over every computation of the module."""
    found = {}
    for n, computation in fields(first(hlo_proto, HLO_MODULE)):
        if n != COMPUTATIONS:
            continue
        rows, ident = [], None
        for m, value in fields(computation):
            if m == COMPUTATION_ID:
                ident = value
            if m != INSTRUCTIONS:
                continue
            name = opcode = op_name = ""
            called = []
            for k, v in fields(value):
                if k == INSTRUCTION_NAME:
                    name = bytes(v).decode()
                elif k == OPCODE:
                    opcode = bytes(v).decode()
                elif k == INSTRUCTION_METADATA:
                    op_name = bytes(first(v, OP_NAME) or b"").decode()
                elif k == CALLED:
                    called += [v] if isinstance(v, int) else _unpack(v)
            rows.append((name, opcode, op_name, called))
        found[ident] = rows
    return found


def op_scopes(hlo_proto):
    """{instruction name: scope} of a compiled module, a fusion by the
    majority of what is fused into it."""
    computations = instructions(hlo_proto)
    scopes = {}
    for rows in computations.values():
        for name, opcode, op_name, called in rows:
            scope = scope_of(op_name)
            if opcode == "fusion" and called:
                votes = collections.Counter(
                    scope_of(inner_op_name)
                    for ident in called
                    for _, inner_opcode, inner_op_name, _ in computations.get(ident, [])
                    if inner_opcode not in NOT_WORK
                )
                best = max(votes.values(), default=0)
                winners = [s for s, v in votes.items() if v == best]
                if len(winners) == 1:
                    scope = winners[0]
            scopes[name] = scope
    return scopes


def scope_of(op_name):
    for scope, rx in _COMPONENT.items():
        if rx.search(op_name):
            return scope
    return OTHER


def self_times_by_scope(path, step_module, plane_name, lo, hi):
    """{scope: self nanoseconds} of the ``XLA Ops`` events of one device
    plane inside [lo, hi), or None where the trace names no scope."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        protos = program_protos(f.read())
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
                m = _INSTRUCTION.match(e.name)
                if m and start >= lo and start + dur <= hi:
                    events.append((scopes.get(m.group(1), OTHER), start, dur))
            return reducer.self_times(events)
    return None


def share(run, scope):
    """Self time of device 0's ops under ``scope`` over the traced window of
    whole steps, in per cent; None where the trace names no scope."""
    trace = run["trace"]
    lo, hi = trace.windows[0]
    if "scope_ops" not in run:
        found = glob.glob(os.path.join(
            harness.BENCH_DIR, ".trace", run["cell"]["name"],
            "plugins", "profile", "*", "*.xplane.pb",
        ))
        run["scope_ops"] = found and self_times_by_scope(
            found[0], trace.step_module, trace.devices[0]["name"], lo, hi
        )
    totals = run["scope_ops"]
    if not totals or not totals.get(scope):
        return None
    run.setdefault("notes", []).append(
        f"{scope}_device_share: {totals[scope] / 1e9:.6f} s of self time "
        f"under scope {scope!r} in {(hi - lo) / 1e9:.6f} s on device 0"
    )
    return 100.0 * totals[scope] / (hi - lo)
