"""shardflow: a jaxpr-level abstract interpreter over PartitionSpecs.

graft-lint's collective budgets (``collectives.py``) gate compiled-HLO
collective TOTALS per mesh config — they can say "all-gather bytes grew
12%" but not WHICH op grew them, because GSPMD inserts the collectives
long after the program left Python. This module recovers the attribution
statically: it walks the traced (uncompiled) jaxpr of a train/serve step
equation by equation, propagating each value's ``PartitionSpec`` through
a per-primitive transfer function, and records a :class:`FlowEvent` at
every point where the sharding discipline forces communication:

- ``gather``    — a sharded value constrained (or consumed) replicated:
                  GSPMD materializes an all-gather of the full buffer;
- ``reshard``   — a value moves between different mesh axes on the same
                  dim (all-to-all-class layout change);
- ``slice``     — replicated -> sharded (free: every chip keeps a slice);
- ``partial-sum`` — a contraction/reduction over a dim both operands
                  shard the same way: the result is a partial sum and
                  GSPMD must all-reduce (or fuse a reduce-scatter) — this
                  is where the DP gradient sync lives, attributed to the
                  exact backward ``dot_general`` and its module path;
- ``mismatch``  — a contraction whose two operands disagree about the
                  contracted dim's sharding: GSPMD re-gathers one side
                  (the classic FSDP weight all-gather);
- ``explicit``  — a hand-written collective inside a ``shard_map`` manual
                  region (psum / psum_scatter / all_gather / all_to_all /
                  ppermute), reported with its axis names.

Every event carries the op's jax name stack (flax module scopes survive
tracing, so a backward matmul reads ``transpose(jvp(...))/decoder/h_3/
attn/query`` — the PARAM PATH that causes the collective) and the Python
source line. EQuARX (arxiv 2506.17615) and the cross-replica weight
update (arxiv 2004.13336) both optimize by locating cost in exactly this
per-op collective placement; shardflow is the static oracle that hands
the r-next auto-parallelism planner that placement without compiling.

The interpreter is deliberately CONSERVATIVE, never exhaustive: unknown
primitives fall back to an elementwise spec join (or replication), and
``FlowReport.lost`` counts the equations where propagation gave up — a
report is evidence, not proof. Nothing here executes or compiles;
``jax.make_jaxpr`` is the only jax machinery used, so the flow runs even
for configs this container's XLA cannot SPMD-partition (the pipe
schedules' PartitionId limitation).

The same walk computes a liveness-based per-chip peak-bytes estimate
(``FlowReport.peak_bytes``): vars are born at their defining equation and
die at their last use; per-chip size is the aval's bytes divided by the
propagated spec's mesh span. ``analysis/envelope.py`` turns that into the
committed static HBM envelopes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

# spec representation: one entry per dim, each a tuple of mesh axis names
# (empty tuple = unsharded dim). "Unknown" specs are plain replication
# plus a bump of FlowReport.lost.
Spec = Tuple[Tuple[str, ...], ...]

EXPLICIT_COLLECTIVES = {
    "psum": "all-reduce",
    "reduce_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pbroadcast": "collective-permute",
}

# reduction primitives whose sharded-dim reduction implies an all-reduce
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or",
}


def canon_spec(spec_like, rank: int) -> Spec:
    """Normalize a PartitionSpec/tuple/None into a rank-length Spec."""
    entries: List[Tuple[str, ...]] = []
    if spec_like is not None:
        for entry in tuple(spec_like)[:rank]:
            if entry is None or str(entry) == "UNCONSTRAINED":
                entries.append(())
            elif isinstance(entry, (tuple, list)):
                entries.append(tuple(str(a) for a in entry))
            else:
                entries.append((str(entry),))
    entries.extend([()] * (rank - len(entries)))
    return tuple(entries)


def spec_str(spec: Spec) -> str:
    return "P(" + ", ".join(
        ("+".join(e) if e else "_") for e in spec
    ) + ")"


def spec_axes(spec: Spec) -> Tuple[str, ...]:
    out: List[str] = []
    for entry in spec:
        out.extend(a for a in entry if a not in out)
    return tuple(out)


def spec_span(spec: Spec, mesh_shape: Dict[str, int]) -> int:
    span = 1
    for entry in spec:
        for axis in entry:
            span *= int(mesh_shape.get(axis, 1))
    return max(span, 1)


def classify_transition(src: Spec, dst: Spec) -> str:
    """The shardflow verdict for a value moving ``src`` -> ``dst``.

    ``keep`` (no comm), ``slice`` (replicated dim becomes sharded: free),
    ``gather`` (sharded dim becomes replicated: all-gather), ``reshard``
    (axes move between dims / swap: all-to-all-class).
    """
    if src == dst:
        return "keep"
    lost = [e for s, d in zip(src, dst) for e in s if e not in d]
    gained = [e for s, d in zip(src, dst) for e in d if e not in s]
    if lost and gained:
        return "reshard"
    if lost:
        return "gather"
    if gained:
        return "slice"
    return "keep"


_TRANSITION_COLLECTIVE = {
    "gather": "all-gather",
    "reshard": "all-to-all",
    "slice": None,
    "keep": None,
}


@dataclass
class FlowEvent:
    kind: str                      # keep|slice|gather|reshard|partial-sum|mismatch|explicit
    collective: Optional[str]      # HLO collective class this predicts
    axes: Tuple[str, ...]          # mesh axes the communication spans
    op: str                        # primitive name
    path: str                      # jax name stack (flax module / param path)
    source: str                    # python file:line (function)
    shape: Tuple[int, ...]
    bytes: int                     # result-buffer bytes (collectives.py proxy)
    from_spec: str = ""
    to_spec: str = ""

    def render(self) -> str:
        arrow = f" {self.from_spec}->{self.to_spec}" if self.from_spec else ""
        return (
            f"[{self.kind}->{self.collective or 'none'} over "
            f"{'/'.join(self.axes) or '?'}] {self.op}{arrow} "
            f"{self.shape} {self.bytes}B at {self.path or '<top>'} "
            f"({self.source})"
        )

    def to_json(self) -> Dict[str, object]:
        return {
            "kind": self.kind, "collective": self.collective,
            "axes": list(self.axes), "op": self.op, "path": self.path,
            "source": self.source, "shape": list(self.shape),
            "bytes": int(self.bytes),
        }


@dataclass
class FlowReport:
    events: List[FlowEvent] = field(default_factory=list)
    out_specs: List[Spec] = field(default_factory=list)
    peak_bytes: int = 0            # liveness-estimated per-chip peak
    arg_bytes: int = 0             # per-chip resident inputs (params/opt/batch)
    live_peak_bytes: int = 0       # per-chip activation-liveness peak
    lost: int = 0                  # eqns where propagation gave up
    eqns: int = 0

    def comm_events(self) -> List[FlowEvent]:
        return [e for e in self.events if e.collective is not None]

    def by_collective(self, kind: str) -> List[FlowEvent]:
        """Events predicting HLO collective ``kind``, largest first."""
        return sorted(
            (e for e in self.events if e.collective == kind),
            key=lambda e: -e.bytes,
        )

    def attributed_kinds(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            if e.collective:
                out[e.collective] = out.get(e.collective, 0) + 1
        return out


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()) or ())
    itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 4)
    return math.prod(shape or (1,)) * itemsize


def _summarize(eqn) -> Tuple[str, str]:
    """(name_stack, source summary) of an equation."""
    stack = ""
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:
        pass
    try:
        from jax._src import source_info_util

        src = source_info_util.summarize(eqn.source_info)
    except Exception:
        src = "<unknown>"
    return stack, src


def _sub_jaxpr(value):
    """ClosedJaxpr/Jaxpr-ish -> (jaxpr, consts) or None."""
    if hasattr(value, "jaxpr"):  # ClosedJaxpr (also has .eqns — check first)
        return value.jaxpr, tuple(getattr(value, "consts", ()))
    if hasattr(value, "eqns"):
        return value, ()
    return None


class _Flow:
    """One interpreter run over a closed jaxpr (shared event/peak state)."""

    def __init__(self, mesh_shape: Dict[str, int]):
        self.mesh_shape = dict(mesh_shape)
        self.total_devices = max(
            math.prod(self.mesh_shape.values()) if self.mesh_shape else 1, 1
        )
        self.report = FlowReport()

    # -- env helpers ------------------------------------------------------

    def _read(self, env: Dict, var) -> Spec:
        if hasattr(var, "val"):  # Literal
            return canon_spec(None, len(getattr(var.aval, "shape", ())))
        return env.get(var, canon_spec(None, len(getattr(var.aval, "shape", ()))))

    def _emit(self, eqn, kind, collective, axes, aval, from_spec=None,
              to_spec=None, bytes_=None):
        stack, src = _summarize(eqn)
        self.report.events.append(FlowEvent(
            kind=kind, collective=collective, axes=tuple(axes),
            op=eqn.primitive.name, path=stack, source=src,
            shape=tuple(getattr(aval, "shape", ()) or ()),
            bytes=int(bytes_ if bytes_ is not None else _aval_bytes(aval)),
            from_spec=spec_str(from_spec) if from_spec is not None else "",
            to_spec=spec_str(to_spec) if to_spec is not None else "",
        ))

    def _join(self, specs: Sequence[Spec], rank: int) -> Spec:
        """Elementwise join: per dim, the first non-empty entry wins."""
        out: List[Tuple[str, ...]] = [()] * rank
        for spec in specs:
            if len(spec) != rank:
                continue
            for d, entry in enumerate(spec):
                if entry and not out[d]:
                    out[d] = entry
        return tuple(out)

    # -- the walk ---------------------------------------------------------

    def run_jaxpr(self, jaxpr, consts, in_specs: Sequence[Spec],
                  manual_axes: Tuple[str, ...] = ()) -> Tuple[List[Spec], int]:
        """Interpret one jaxpr body; returns (out_specs, internal peak).

        ``internal peak`` is the liveness peak of values BORN inside this
        body (invars/consts are the caller's operands and counted there).
        ``manual_axes`` marks a shard_map region: avals are already
        per-shard, explicit collectives are events, and sharding specs no
        longer apply (the region is manual on those axes).
        """
        env: Dict[Any, Spec] = {}
        for var, spec in zip(jaxpr.invars, in_specs):
            env[var] = canon_spec(spec, len(getattr(var.aval, "shape", ())))
        for var in jaxpr.constvars:
            env[var] = canon_spec(None, len(getattr(var.aval, "shape", ())))

        # liveness: last eqn index using each var (outvars live to the end)
        last_use: Dict[Any, int] = {}
        n = len(jaxpr.eqns)
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not hasattr(v, "val"):
                    last_use[v] = i
        for v in jaxpr.outvars:
            if not hasattr(v, "val"):
                last_use[v] = n

        def chip_bytes(var, spec: Spec) -> int:
            b = _aval_bytes(var.aval)
            if manual_axes:
                return b  # already per-shard inside a manual region
            return b // spec_span(spec, self.mesh_shape)

        live = 0
        born: Dict[Any, int] = {}
        peak = 0
        for i, eqn in enumerate(jaxpr.eqns):
            self.report.eqns += 1
            out_specs, child_peak = self._eval_eqn(eqn, env, manual_axes)
            for var, spec in zip(eqn.outvars, out_specs):
                env[var] = spec
                if last_use.get(var, -1) >= i:
                    born[var] = chip_bytes(var, spec)
                    live += born[var]
            peak = max(peak, live + child_peak)
            for v in list(eqn.invars) + list(eqn.outvars):
                if hasattr(v, "val"):  # Literal: unhashable, never live
                    continue
                if last_use.get(v) == i and v in born:
                    live -= born.pop(v)
        outs = [self._read(env, v) for v in jaxpr.outvars]
        return outs, peak

    def _eval_eqn(self, eqn, env, manual_axes) -> Tuple[List[Spec], int]:
        """Transfer function; returns (outvar specs, child liveness peak)."""
        prim = eqn.primitive.name
        in_specs = [self._read(env, v) for v in eqn.invars]
        out_rank = lambda k=0: len(getattr(eqn.outvars[k].aval, "shape", ()))  # noqa: E731

        if prim in EXPLICIT_COLLECTIVES:
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if isinstance(axes, str):
                axes = (axes,)
            self._emit(
                eqn, "explicit", EXPLICIT_COLLECTIVES[prim], tuple(axes),
                eqn.outvars[0].aval,
                bytes_=_aval_bytes(eqn.outvars[0].aval) * self.total_devices,
            )
            return [canon_spec(None, len(getattr(v.aval, "shape", ())))
                    for v in eqn.outvars], 0

        if prim in ("jit", "pjit"):  # the primitive is "jit" on jax 0.9
            sub = _sub_jaxpr(eqn.params.get("jaxpr"))
            if sub is None:
                return self._fallback(eqn, in_specs, manual_axes)
            body, _ = sub
            outs, peak = self.run_jaxpr(body, (), in_specs, manual_axes)
            return outs, peak

        if prim in ("remat", "remat2", "checkpoint", "custom_vjp_call_jaxpr",
                    "custom_jvp_call", "custom_vjp_call", "closed_call",
                    "core_call", "custom_lin"):
            for key in ("jaxpr", "fun_jaxpr", "call_jaxpr"):
                sub = _sub_jaxpr(eqn.params.get(key))
                if sub is not None:
                    body, _ = sub
                    n_in = len(body.invars)
                    outs, peak = self.run_jaxpr(
                        body, (), in_specs[:n_in], manual_axes
                    )
                    return outs[:len(eqn.outvars)], peak
            return self._fallback(eqn, in_specs, manual_axes)

        if prim == "sharding_constraint":
            rank = out_rank()
            target = canon_spec(
                getattr(eqn.params.get("sharding"), "spec", None), rank
            )
            src = in_specs[0]
            kind = classify_transition(src, target)
            if kind != "keep":
                lost_axes = tuple(
                    a for a in spec_axes(src) if a not in spec_axes(target)
                ) or spec_axes(target)
                self._emit(
                    eqn, kind, _TRANSITION_COLLECTIVE[kind], lost_axes,
                    eqn.outvars[0].aval, from_spec=src, to_spec=target,
                )
            return [target], 0

        if prim == "shard_map":
            return self._eval_shard_map(eqn, in_specs)

        if prim == "dot_general":
            return self._eval_dot(eqn, in_specs), 0

        if prim in _REDUCE_PRIMS:
            axes = tuple(eqn.params.get("axes", ()))
            src = in_specs[0]
            reduced = tuple(
                a for d in axes for a in (src[d] if d < len(src) else ())
            )
            if reduced and not manual_axes:
                self._emit(eqn, "partial-sum", "all-reduce", reduced,
                           eqn.outvars[0].aval, from_spec=src)
            out = tuple(e for d, e in enumerate(src) if d not in axes)
            return [out], 0

        if prim == "broadcast_in_dim":
            dims = eqn.params.get("broadcast_dimensions", ())
            out: List[Tuple[str, ...]] = [()] * out_rank()
            for i, d in enumerate(dims):
                if i < len(in_specs[0]):
                    out[d] = in_specs[0][i]
            return [tuple(out)], 0

        if prim == "transpose":
            perm = eqn.params.get("permutation", ())
            src = in_specs[0]
            return [tuple(src[p] if p < len(src) else () for p in perm)], 0

        if prim == "squeeze":
            dims = set(eqn.params.get("dimensions", ()))
            return [tuple(
                e for d, e in enumerate(in_specs[0]) if d not in dims
            )], 0

        if prim == "reshape":
            return [self._reshape_spec(eqn, in_specs[0])], 0

        if prim == "convert_element_type" or (
            len(eqn.invars) == 1 and len(in_specs[0]) == out_rank()
        ):
            return [in_specs[0][:out_rank()]], 0

        if prim == "concatenate":
            d_cat = eqn.params.get("dimension", 0)
            rank = out_rank()
            joined = list(self._join(in_specs, rank))
            if d_cat < rank:
                joined[d_cat] = ()
            return [tuple(joined)], 0

        if prim == "scan":
            return self._eval_scan(eqn, in_specs)

        if prim == "while":
            return self._eval_while(eqn, in_specs)

        if prim == "cond":
            branches = eqn.params.get("branches", ())
            outs_all, peaks = [], [0]
            for br in branches:
                sub = _sub_jaxpr(br)
                if sub is None:
                    continue
                body, _ = sub
                outs, pk = self.run_jaxpr(body, (), in_specs[1:], manual_axes)
                outs_all.append(outs)
                peaks.append(pk)
            if not outs_all:
                return self._fallback(eqn, in_specs, manual_axes)
            joined = [
                self._join([o[k] for o in outs_all],
                           len(getattr(v.aval, "shape", ())))
                for k, v in enumerate(eqn.outvars)
            ]
            return joined, max(peaks)

        return self._fallback(eqn, in_specs, manual_axes)

    def _fallback(self, eqn, in_specs, manual_axes) -> Tuple[List[Spec], int]:
        """Unknown primitive: elementwise join when ranks line up, else
        replicated (counted in ``lost`` when that forgets a sharding)."""
        outs: List[Spec] = []
        for v in eqn.outvars:
            rank = len(getattr(v.aval, "shape", ()))
            same_rank = [s for s in in_specs if len(s) == rank]
            joined = self._join(same_rank, rank) if same_rank else canon_spec(
                None, rank
            )
            if not any(joined) and any(any(s) for s in in_specs):
                self.report.lost += 1
            outs.append(joined)
        return outs, 0

    def _reshape_spec(self, eqn, src: Spec) -> Spec:
        in_shape = tuple(eqn.invars[0].aval.shape)
        out_shape = tuple(eqn.outvars[0].aval.shape)
        if in_shape == out_shape:
            return src
        # singleton insertion/removal: align non-singleton dims in order
        in_core = [(d, s) for d, s in enumerate(in_shape) if s != 1]
        out_core = [(d, s) for d, s in enumerate(out_shape) if s != 1]
        if [s for _, s in in_core] == [s for _, s in out_core]:
            out: List[Tuple[str, ...]] = [()] * len(out_shape)
            for (di, _), (do, _) in zip(in_core, out_core):
                if di < len(src):
                    out[do] = src[di]
            return tuple(out)
        if not any(src):
            return canon_spec(None, len(out_shape))
        self.report.lost += 1  # sharded dims merged/split: give up honestly
        return canon_spec(None, len(out_shape))

    def _eval_dot(self, eqn, in_specs) -> List[Spec]:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = in_specs[0], in_specs[1]
        out_aval = eqn.outvars[0].aval

        # contracted dims: same axes on both sides -> partial sum;
        # one-sided sharding -> GSPMD re-gathers that operand
        psum_axes: List[str] = []
        for dl, dr in zip(lc, rc):
            el = lhs[dl] if dl < len(lhs) else ()
            er = rhs[dr] if dr < len(rhs) else ()
            if el and el == er:
                psum_axes.extend(a for a in el if a not in psum_axes)
            elif el or er:
                side, dim, spec = (
                    ("lhs", dl, lhs) if el else ("rhs", dr, rhs)
                )
                operand = eqn.invars[0 if el else 1]
                self._emit(
                    eqn, "mismatch", "all-gather", el or er, operand.aval,
                    from_spec=spec,
                    to_spec=canon_spec(None, len(spec)),
                )
        if psum_axes:
            self._emit(eqn, "partial-sum", "all-reduce", tuple(psum_axes),
                       out_aval, from_spec=lhs, to_spec=rhs)

        # output: batch dims, then lhs free, then rhs free
        out: List[Tuple[str, ...]] = []
        for dl, dr in zip(lb, rb):
            el = lhs[dl] if dl < len(lhs) else ()
            er = rhs[dr] if dr < len(rhs) else ()
            out.append(el or er)
        for d in range(len(lhs)):
            if d not in lc and d not in lb:
                out.append(lhs[d])
        for d in range(len(rhs)):
            if d not in rc and d not in rb:
                out.append(rhs[d])
        rank = len(getattr(out_aval, "shape", ()))
        out = out[:rank] + [()] * (rank - len(out))
        return [tuple(out)]

    def _eval_scan(self, eqn, in_specs) -> Tuple[List[Spec], int]:
        sub = _sub_jaxpr(eqn.params.get("jaxpr"))
        if sub is None:
            return self._fallback(eqn, in_specs, ())
        body, _ = sub
        n_consts = eqn.params.get("num_consts", 0)
        n_carry = eqn.params.get("num_carry", 0)
        consts = in_specs[:n_consts]
        carry = list(in_specs[n_consts:n_consts + n_carry])
        xs = [s[1:] for s in in_specs[n_consts + n_carry:]]
        peak = 0
        for _ in range(2):  # one joining pass for carry stability
            outs, peak = self.run_jaxpr(body, (), consts + carry + xs)
            new_carry = outs[:n_carry]
            joined = [
                self._join([c, nc], len(c)) if len(c) == len(nc) else c
                for c, nc in zip(carry, new_carry)
            ]
            if joined == carry:
                break
            carry = joined
        ys = outs[n_carry:]
        lead: Tuple[Tuple[str, ...], ...] = ((),)
        return list(carry) + [lead + tuple(y) for y in ys], peak

    def _eval_while(self, eqn, in_specs) -> Tuple[List[Spec], int]:
        sub = _sub_jaxpr(eqn.params.get("body_jaxpr"))
        if sub is None:
            return self._fallback(eqn, in_specs, ())
        body, _ = sub
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        consts = in_specs[cn:cn + bn]
        carry = in_specs[cn + bn:]
        outs, peak = self.run_jaxpr(body, (), list(consts) + list(carry))
        return outs, peak

    def _eval_shard_map(self, eqn, in_specs) -> Tuple[List[Spec], int]:
        body = eqn.params.get("jaxpr")
        sub = _sub_jaxpr(body)
        if sub is None:
            return self._fallback(eqn, in_specs, ())
        body, _ = sub
        in_names = eqn.params.get("in_names", ())
        out_names = eqn.params.get("out_names", ())
        mesh = eqn.params.get("mesh")
        manual = tuple(
            str(a) for a in (getattr(mesh, "axis_names", ()) or ())
        ) or tuple(self.mesh_shape)
        # inside the region every aval is per-shard; specs don't apply
        shard_specs = [
            canon_spec(None, len(getattr(v.aval, "shape", ())))
            for v in body.invars
        ]
        _, peak = self.run_jaxpr(body, (), shard_specs, manual_axes=manual)
        outs: List[Spec] = []
        for v, names in zip(eqn.outvars, out_names):
            rank = len(getattr(v.aval, "shape", ()))
            entries: List[Tuple[str, ...]] = [()] * rank
            for dim, axes in (names or {}).items():
                if int(dim) < rank:
                    ax = axes if isinstance(axes, (tuple, list)) else (axes,)
                    entries[int(dim)] = tuple(str(a) for a in ax)
            outs.append(tuple(entries))
        return outs, peak


def trace_shardings(closed_jaxpr, in_specs: Sequence,
                    mesh_shape: Dict[str, int]) -> FlowReport:
    """Run the abstract interpreter over a traced (closed) jaxpr.

    ``in_specs`` aligns with the jaxpr's flat invars (PartitionSpec-likes,
    None = replicated); ``mesh_shape`` maps axis name -> size for span and
    byte accounting.
    """
    flow = _Flow(mesh_shape)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    specs = [
        canon_spec(s, len(getattr(v.aval, "shape", ())))
        for v, s in zip(jaxpr.invars, list(in_specs) + [None] * len(jaxpr.invars))
    ]
    # seed liveness with the arguments themselves: params/opt state/batch
    # are resident for the whole step (donation frees them only when the
    # replacement exists, which the internal liveness already models
    # approximately by keeping them live until last use)
    arg_bytes = 0
    for v, s in zip(jaxpr.invars, specs):
        arg_bytes += _aval_bytes(v.aval) // spec_span(s, mesh_shape)
    outs, peak = flow.run_jaxpr(jaxpr, (), specs)
    flow.report.out_specs = outs
    flow.report.arg_bytes = arg_bytes
    flow.report.live_peak_bytes = peak
    flow.report.peak_bytes = arg_bytes + peak
    return flow.report


def committed_in_specs(args) -> List:
    """Per-leaf PartitionSpecs read off committed (placed) arrays.

    Flattens ``args`` exactly the way ``jax.make_jaxpr`` does, so the
    result aligns with the traced jaxpr's invars. Leaves without a
    NamedSharding (host numpy, uncommitted) count as replicated.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    specs = []
    for leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        specs.append(getattr(sharding, "spec", None))
    return specs


def flow_for_case(case) -> FlowReport:
    """Trace a DryrunCase's train step and run shardflow over it.

    Requires the case to be initialized (``collectives.compile_case`` or
    ``trainer.init``); traces only — works even where XLA cannot compile
    the config.
    """
    import jax

    trainer = case.trainer
    if trainer.state is None:
        with case.mesh:
            trainer.init(next(iter(case.loader))["tokens"])
    batch = next(iter(case.loader))
    with case.mesh:
        jaxpr = jax.make_jaxpr(
            lambda s, b: trainer.train_step(s, b)
        )(trainer.state, batch)
    mesh_shape = {str(k): int(v) for k, v in dict(case.mesh.shape).items()}
    specs = committed_in_specs((trainer.state, batch))
    return trace_shardings(jaxpr, specs, mesh_shape)
