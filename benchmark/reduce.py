"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read, with ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand on a v5e with
``inspect_trace.py``, PR 24): one plane per chip, ``/device:TPU:<n>``, whose
line ``XLA Ops`` has one event per executed HLO instruction, NAMED BY THE
INSTRUCTION'S WHOLE TEXT (``%fusion.242 = f32[16368]{0:T(1024)} fusion(...)``;
children of a ``while`` or ``conditional`` nest inside their parent's
interval on the same line), whose line ``XLA Modules`` has one event per
executed program (``jit_train_step(<fingerprint>)``), and whose line ``Async
XLA Ops`` has the copies and collectives in flight beside the ops, which
are not counted as busy. A plane ``/host:CPU`` has one line per host thread;
``jax.profiler.TraceAnnotation`` spans appear on the thread that opened
them, if they were opened after the trace began. All planes share one
clock, in nanoseconds. A Pallas kernel shows as a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` under the name of the module that
called it (``%attn._fused_layout_attention.37``), not of the kernel.

``short_name`` cuts an instruction's text to ``<name> = <shapes> <opcode>``
(layouts and operands dropped, ``tpu_custom_call`` kept), which is what
every pattern in this directory is written against.

The traced window is cut to whole steps: from the start of the first
execution of the step program (the module that took most device time) to
the start of its last. Busy time is the measure of the union of the op
intervals inside that window, so nesting and overlap count once.
"""

import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?( |$)"
)
# spans the benchmark's own wrapper opens; the line that carries them is the
# training thread
HOST_MARKS = ("train_epoch", "next_batch")
TOP = 10
NAME_LIMIT = 120

_HLO = re.compile(r"^%(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_SUFFIX = re.compile(r"\.\d+(?= = )")


def short_name(text):
    """``<name> = <output shapes> <opcode>[ tpu_custom_call]`` of an HLO
    instruction's text; any other event name unchanged."""
    m = _HLO.match(text)
    if not m:
        return text
    rest = _LAYOUT.sub("", m.group("rest"))
    op = _OPCODE.search(rest)
    if not op:
        return m.group("name")
    shapes = rest[:op.start()].strip()
    kernel = " tpu_custom_call" if 'custom_call_target="tpu_custom_call"' in text else ""
    return f"{m.group('name')} = {shapes} {op.group(1)}{kernel}"


def kind(name):
    """A short name without its instruction number, cut to a length a line
    can hold: the same op of every layer falls under one name."""
    return _SUFFIX.sub("", name)[:NAME_LIMIT]


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1][1] = hi
        else:
            merged.append([lo, hi])
    return merged


def measure(intervals):
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` that ``b`` (same
    form) does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events):
    """{name: nanoseconds not covered by an event nested inside it}, for
    events (name, start, duration) of one line."""
    totals, stack = {}, []  # stack of [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


class Trace:
    """The events of one traced window, as plain lists, and the reductions.

    ``devices``: [{"name", "ops": [[name, start_ns, dur_ns]...], "modules":
    [...]}]; ``host``: events of the training thread."""

    def __init__(self, devices, host):
        if not devices:
            raise ValueError("the trace holds no /device:TPU plane")
        self.devices, self.host = devices, host
        self.step_module = self._step_module()
        self.windows = [self._window(d) for d in devices]

    # -- the window, in whole steps ---------------------------------------

    def _step_module(self):
        totals = {}
        for name, _, dur in self.devices[0]["modules"]:
            totals[name] = totals.get(name, 0.0) + dur
        return max(totals, key=totals.get) if totals else None

    def step_starts(self, device):
        return sorted(
            s for name, s, _ in device["modules"] if name == self.step_module
        )

    def _window(self, device):
        starts = self.step_starts(device)
        if len(starts) >= 2:
            return starts[0], starts[-1]
        ops = device["ops"]
        return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)

    def steps(self):
        """Whole steps inside the window (device 0)."""
        return max(len(self.step_starts(self.devices[0])) - 1, 0)

    def window_s(self):
        return sum(hi - lo for lo, hi in self.windows) / len(self.windows) / 1e9

    # -- busy, idle, collectives, kernels ---------------------------------

    def _busy(self, i, keep=lambda name: True):
        lo, hi = self.windows[i]
        return union(clip(
            [(s, s + d) for name, s, d in self.devices[i]["ops"] if keep(name)],
            lo, hi,
        ))

    def busy_s(self):
        """Seconds an operation ran, averaged over the chips."""
        n = len(self.devices)
        return sum(measure(self._busy(i)) for i in range(n)) / n / 1e9

    def idle_share_worst(self):
        shares = []
        for i, (lo, hi) in enumerate(self.windows):
            shares.append(1.0 - measure(self._busy(i)) / (hi - lo))
        return max(shares)

    def collective_exposed_share_worst(self):
        """Time in collective ops while no other op runs, over the window,
        on the chip where that is largest; None where no collective ran."""
        shares, any_collective = [], False
        for i, (lo, hi) in enumerate(self.windows):
            coll = self._busy(i, lambda n: bool(COLLECTIVE.search(n)))
            # a parent (while, conditional) spans its children: only leaves
            # can hide a collective
            leaves = self._leaf_intervals(i)
            other = union(clip(
                [(s, e) for name, s, e in leaves if not COLLECTIVE.search(name)],
                lo, hi,
            ))
            any_collective = any_collective or bool(coll)
            shares.append(measure(subtract(coll, other)) / (hi - lo))
        return max(shares) if any_collective else None

    def _leaf_intervals(self, i):
        events = sorted(self.devices[i]["ops"], key=lambda e: (e[1], -e[2]))
        leaves = []
        for k, (name, s, d) in enumerate(events):
            nxt = events[k + 1] if k + 1 < len(events) else None
            if nxt is None or nxt[1] >= s + d:
                leaves.append((name, s, s + d))
        return leaves

    def kernel_seconds(self, pattern):
        """(seconds, calls) of the ops whose name matches, inside the window
        of device 0."""
        lo, hi = self.windows[0]
        rx = re.compile(pattern)
        hits = [
            d for name, s, d in self.devices[0]["ops"]
            if rx.search(name) and s >= lo and s + d <= hi
        ]
        return sum(hits) / 1e9, len(hits)

    # -- the breakdown the next issue's writer reads ----------------------

    def breakdown(self):
        lo, hi = self.windows[0]
        inside = [e for e in self.devices[0]["ops"] if e[1] >= lo and e[1] + e[2] <= hi]
        by_kind = {}
        for name, ns in self_times(inside).items():
            by_kind[kind(name)] = by_kind.get(kind(name), 0.0) + ns
        ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = subtract([(lo, hi)], self._busy(0))
        by_host = {}
        for a, b in gaps:
            what = self._host_activity((a + b) / 2)
            by_host[what] = by_host.get(what, 0.0) + (b - a)
        idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": [[name, ns / 1e9] for name, ns in idle],
        }

    def _host_activity(self, t):
        """The innermost span of the training thread that covers ``t``."""
        best, best_dur = "no_host_span", None
        for name, s, d in self.host:
            if s <= t < s + d and (best_dur is None or d < best_dur):
                best, best_dur = name, d
        return best

    # -- a recorded trace, for the tests ----------------------------------

    @classmethod
    def from_json_file(cls, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            data = json.load(f)
        return cls(data["devices"], data["host"])


def _events(line):
    return [
        [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
        for e in line.events
    ]


def from_profile(profile, chips):
    """A ``Trace`` from ``jax.profiler.ProfileData``: the first ``chips``
    device planes and the host thread that carries the wrapper's spans."""
    devices, host = {}, []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[int(m.group(1))] = {
                    "name": plane.name,
                    "ops": _events(lines[OPS_LINE]),
                    "modules": _events(lines[MODULES_LINE])
                    if MODULES_LINE in lines else [],
                }
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = _events(line)
                if any(e[0] in HOST_MARKS for e in events):
                    host = events
    ordered = [devices[k] for k in sorted(devices)][:chips]
    return Trace(ordered, host)


def load(path, chips):
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), chips)
