"""Static SPMD-hang detection: collective congruence across cond branches.

An SPMD program is ONE program replicated on every chip; XLA collectives
are rendezvous points where every member of the group must arrive with
the same operation in the same order. The classic way to break that is a
``lax.cond``/``switch`` inside a ``shard_map`` manual region whose
predicate VARIES across devices: chips that take the true branch issue
(say) a ``psum`` the false-branch chips never reach, and the job hangs —
on real TPU only, silently, at whatever step first splits the predicate.
graft-armor (r5) can only catch this after the fact as a barrier timeout;
this module turns it into a static finding on the traced jaxpr, before
anything compiles.

The check is deliberately sharper than "branches must be identical":

1. Inside every ``shard_map`` region, track a per-value **variance taint**
   — the set of mesh axes along which a value may differ between chips.
   Region inputs are tainted by the axes they're split over
   (``in_names``), ``axis_index(a)`` introduces taint ``{a}``, ``psum``/
   ``all_gather`` over an axis REMOVE that axis (their result is
   identical across the group), and everything else unions its operands.
2. For each ``cond`` in the region, extract each branch's **collective
   sequence** — the ordered list of (collective kind, axis names) the
   branch would execute, nested control flow included.
3. Branches with different sequences are a finding. They are a **hazard**
   (would hang) only when some differing collective spans an axis the
   predicate is tainted by: a collective group along axis B only contains
   chips that agree on every other coordinate, so if the predicate only
   varies along A ∉ B, all members of any B-group pick the same branch
   and the mismatch is benign (this is exactly the shipped
   ``predicate_head`` pattern: the bad-step predicate varies on ``pipe``
   while its in-branch collectives run over ``data``). Benign mismatches
   are still reported as notes — they're one refactor away from a hang.

A uniform predicate (empty taint — e.g. a host scalar or a fully-psummed
loss) can never split the mesh, so its mismatches are all benign.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from distributed_pytorch_example_tpu.analysis.shardflow import (
    EXPLICIT_COLLECTIVES,
    _sub_jaxpr,
    _summarize,
)

# ordered (collective kind, axes) pairs — the rendezvous fingerprint
CollectiveSeq = Tuple[Tuple[str, Tuple[str, ...]], ...]

# collectives whose output is identical across the spanned axes (the
# rendezvous SYNCHRONIZES the value, clearing its variance taint there)
_TAINT_CLEARING = {"psum", "all_gather", "pbroadcast"}


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


@dataclass
class CongruenceFinding:
    hazard: bool                      # True: would deadlock on real TPU
    op: str                           # "cond"
    path: str                         # name stack of the cond
    source: str                       # python file:line
    predicate_axes: Tuple[str, ...]   # axes the predicate varies along
    mismatch_axes: Tuple[str, ...]    # axes of the differing collectives
    branch_seqs: Tuple[CollectiveSeq, ...]

    def render(self) -> str:
        seqs = " vs ".join(
            "[" + ",".join(f"{k}@{'/'.join(a)}" for k, a in s) + "]"
            for s in self.branch_seqs
        )
        level = "HAZARD" if self.hazard else "benign"
        return (
            f"[congruence:{level}] {self.op} at {self.path or '<top>'} "
            f"({self.source}): branch collective sequences differ {seqs}; "
            f"predicate varies on {'/'.join(self.predicate_axes) or '<uniform>'}"
            f", mismatch spans {'/'.join(self.mismatch_axes) or '<none>'}"
        )

    def to_json(self) -> Dict[str, object]:
        return {
            "hazard": self.hazard, "op": self.op, "path": self.path,
            "source": self.source,
            "predicate_axes": list(self.predicate_axes),
            "mismatch_axes": list(self.mismatch_axes),
            "branch_seqs": [
                [[k, list(a)] for k, a in s] for s in self.branch_seqs
            ],
        }


@dataclass
class CongruenceReport:
    findings: List[CongruenceFinding] = field(default_factory=list)
    regions: int = 0                  # shard_map regions inspected
    conds: int = 0                    # conds inside manual regions

    @property
    def hazards(self) -> List[CongruenceFinding]:
        return [f for f in self.findings if f.hazard]

    @property
    def ok(self) -> bool:
        return not self.hazards


Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()


def _collective_seq(jaxpr) -> CollectiveSeq:
    """Ordered collectives a body executes (loops/branches flattened).

    ``scan``/``while`` bodies are included once — the sequence compares
    STRUCTURE, not trip counts, and a collective inside a loop is a
    rendezvous regardless of iteration count. Nested ``cond`` branches
    are concatenated in branch order; a nested mismatch is caught by its
    own finding, so the flattening here only needs to be deterministic.
    """
    out: List[Tuple[str, Tuple[str, ...]]] = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in EXPLICIT_COLLECTIVES:
            out.append((EXPLICIT_COLLECTIVES[prim], _eqn_axes(eqn)))
            continue
        for value in eqn.params.values():
            sub = _sub_jaxpr(value)
            if sub is not None:
                out.extend(_collective_seq(sub[0]))
            elif isinstance(value, (tuple, list)):
                for item in value:
                    sub = _sub_jaxpr(item)
                    if sub is not None:
                        out.extend(_collective_seq(sub[0]))
    return tuple(out)


class _TaintWalk:
    """Variance-taint propagation + cond congruence inside one region."""

    def __init__(self, report: CongruenceReport):
        self.report = report

    def run(self, jaxpr, in_taints: Sequence[Taint]):
        env: Dict[object, Taint] = {}
        for var, taint in zip(jaxpr.invars, in_taints):
            env[var] = taint
        for var in jaxpr.constvars:
            env[var] = _EMPTY

        def read(v) -> Taint:
            if hasattr(v, "val"):
                return _EMPTY
            return env.get(v, _EMPTY)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_taint = frozenset().union(*[read(v) for v in eqn.invars]) \
                if eqn.invars else _EMPTY

            if prim == "axis_index":
                out_taint = in_taint | frozenset(_eqn_axes(eqn))
            elif prim in _TAINT_CLEARING:
                out_taint = in_taint - frozenset(_eqn_axes(eqn))
            elif prim == "cond":
                self._check_cond(eqn, read)
                # branch outputs vary wherever predicate or operands vary
                out_taint = in_taint
                for br in eqn.params.get("branches", ()):
                    sub = _sub_jaxpr(br)
                    if sub is not None:
                        self.run(sub[0], [read(v) for v in eqn.invars[1:]])
            elif prim in ("scan", "while", "jit", "pjit", "closed_call",
                          "custom_vjp_call_jaxpr", "custom_jvp_call",
                          "custom_vjp_call", "remat", "remat2"):
                for key in ("jaxpr", "body_jaxpr", "cond_jaxpr",
                            "fun_jaxpr", "call_jaxpr"):
                    sub = _sub_jaxpr(eqn.params.get(key))
                    if sub is not None:
                        body = sub[0]
                        n = len(body.invars)
                        taints = ([read(v) for v in eqn.invars] + [in_taint] * n)[:n]
                        self.run(body, taints)
                out_taint = in_taint
            else:
                out_taint = in_taint

            for v in eqn.outvars:
                env[v] = out_taint

    def _check_cond(self, eqn, read):
        self.report.conds += 1
        branches = eqn.params.get("branches", ())
        seqs: List[CollectiveSeq] = []
        for br in branches:
            sub = _sub_jaxpr(br)
            seqs.append(_collective_seq(sub[0]) if sub is not None else ())
        if len(set(seqs)) <= 1:
            return  # congruent: every chip runs the same rendezvous list

        # axes of collectives NOT common to all branches
        common = set(seqs[0])
        for s in seqs[1:]:
            common &= set(s)
        mismatch_axes: List[str] = []
        for s in seqs:
            for item in s:
                if item not in common:
                    mismatch_axes.extend(
                        a for a in item[1] if a not in mismatch_axes
                    )

        pred_taint = read(eqn.invars[0])
        hazard = bool(pred_taint & set(mismatch_axes))
        stack, src = _summarize(eqn)
        self.report.findings.append(CongruenceFinding(
            hazard=hazard, op=eqn.primitive.name, path=stack, source=src,
            predicate_axes=tuple(sorted(pred_taint)),
            mismatch_axes=tuple(mismatch_axes),
            branch_seqs=tuple(seqs),
        ))


def _find_shard_maps(jaxpr, out: List):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            out.append(eqn)
            continue  # nested shard_map inside manual region: rare, skip
        for value in eqn.params.values():
            sub = _sub_jaxpr(value)
            if sub is not None:
                _find_shard_maps(sub[0], out)
            elif isinstance(value, (tuple, list)):
                for item in value:
                    sub = _sub_jaxpr(item)
                    if sub is not None:
                        _find_shard_maps(sub[0], out)
    return out


def check_congruence(closed_jaxpr) -> CongruenceReport:
    """Audit every shard_map region of a traced jaxpr for branch-split
    collective sequences. Pure jaxpr walk — no compile, no backend."""
    report = CongruenceReport()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in _find_shard_maps(jaxpr, []):
        report.regions += 1
        sub = _sub_jaxpr(eqn.params.get("jaxpr"))
        if sub is None:
            continue
        body = sub[0]
        in_names = eqn.params.get("in_names", ())
        taints: List[Taint] = []
        for i, var in enumerate(body.invars):
            names = in_names[i] if i < len(in_names) else {}
            axes: List[str] = []
            for dim_axes in (names or {}).values():
                ax = dim_axes if isinstance(dim_axes, (tuple, list)) \
                    else (dim_axes,)
                axes.extend(str(a) for a in ax)
            taints.append(frozenset(axes))
        _TaintWalk(report).run(body, taints)
    return report


def congruence_for_case(case) -> CongruenceReport:
    """Trace a DryrunCase's train step and audit it. Trace-only, so this
    runs even for configs the backend cannot SPMD-partition (the pipe
    schedules on CPU) — exactly the configs whose hang class this check
    exists for."""
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
    return check_congruence(jaxpr)
