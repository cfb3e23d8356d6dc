"""graft-plan: static auto-parallelism planner over :class:`PlanSpec`.

Generalizes the cross-replica weight-update sharding search of Xu et al.
(arxiv 2004.13336) to the full (data, fsdp, tensor, pipe, zero1,
grad_accum, wire) space: enumerate the legal plans for a topology, score
every one WITHOUT compiling or executing, and hand the ranked list to
``--auto-mesh`` (train.py / serve.py) or the
``scripts/plan_search.py`` report.

The three-tier oracle (cheapest first, each tier refining the last):

1. **shardflow bytes** — trace the train/serve program once per plan
   (``jax.make_jaxpr`` over ShapeDtypeStructs; ``train.step.abstract_state``
   keeps even state init off the backend), walk the jaxpr with
   ``analysis/shardflow.py``, and push every predicted collective through a
   latency/bandwidth :class:`LinkModel`. Wire-compressed plans are priced
   automatically: the traced all_to_all/all_gather avals carry the int8
   payload dtype, so compressed bytes < fp32 bytes by construction.
2. **envelope HBM** — ``FlowReport.peak_bytes`` vs the ``--hbm-limit``
   would-OOM pre-gate (``analysis/envelope.py``); infeasible plans are
   pruned before anything would ever compile.
3. **compiled-cost records** — when a plan coincides with a committed
   ``analysis/comm_budgets.json`` entry (compiled-HLO collective bytes,
   incl. the ``parse_collective_dtypes`` payload breakdown), the measured
   bytes replace the traced estimate in the ranking cost.

Zero XLA compiles for uncached plans is a hard contract: everything here
is ``eval_shape`` + ``make_jaxpr`` + pure-Python jaxpr walks.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from distributed_pytorch_example_tpu.analysis import envelope as env_mod
from distributed_pytorch_example_tpu.analysis import shardflow
from distributed_pytorch_example_tpu.parallel.plan import PlanSpec
from distributed_pytorch_example_tpu.parallel.wire import WireConfig
from distributed_pytorch_example_tpu.runtime.mesh import MeshSpec, make_mesh

_MESH_AXES = ("data", "fsdp", "tensor", "sequence", "expert", "pipe")


# -- tier-1 cost model -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """Ring latency/bandwidth model for predicted collectives.

    Deliberately simple — the planner ranks plans against EACH OTHER on one
    homogeneous interconnect, so only relative cost matters. Each event
    costs a fixed launch latency plus its per-device ring traffic
    (:func:`event_wire_bytes`) over the link bandwidth; plans with many
    small per-leaf collectives pay the latency term, plans with fat
    payloads pay the bandwidth term.
    """

    latency_us: float = 1.0
    bandwidth_gbps: float = 100.0

    def event_ms(self, wire_bytes: float) -> float:
        if wire_bytes <= 0:
            return 0.0
        return (
            self.latency_us * 1e-3
            + (wire_bytes / 1e9) / self.bandwidth_gbps * 1e3
        )


# ring passes over the payload: an all-reduce moves it twice
# (reduce-scatter + all-gather decomposition), everything else once
_PASSES = {"all-reduce": 2.0}


def event_wire_bytes(event, span: int, total_devices: int) -> float:
    """Per-device ring traffic (bytes) a predicted collective moves.

    Normalizes shardflow's result-buffer byte conventions to the physical
    payload: explicit events carry ``result_aval_bytes * total_devices``
    (the compiled-budget proxy), where a reduce-scatter's result is the
    1/span OUTPUT shard — so its payload is scaled back up — while
    inferred (GSPMD-propagation) events carry the global result bytes
    directly. Each ring pass moves ``(span-1)/span`` of the payload per
    device. This is what makes the oracle monotone in payload dtype: an
    int8 all_to_all genuinely scores ~4x fewer wire bytes than the fp32
    reduce-scatter of the same gradient.
    """
    if span <= 1:
        return 0.0
    if event.kind == "explicit":
        payload = event.bytes / max(total_devices, 1)
        if event.collective == "reduce-scatter":
            payload *= span
    else:
        payload = float(event.bytes)
    passes = _PASSES.get(event.collective, 1.0)
    return passes * (span - 1) / span * payload


def _span(axes: Tuple[str, ...], mesh_shape: Dict[str, int]) -> int:
    return math.prod(mesh_shape.get(a, 1) for a in axes or ())


# -- plan space ------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramInfo:
    """What legality needs to know about the program being planned."""

    global_batch: int
    num_heads: int = 0
    num_layers: int = 0
    pipelineable: bool = False
    max_param_elems: int = 0  # largest leaf, for the wire floor
    kind: str = "image"  # "image" | "lm"


def legality(plan: PlanSpec, info: ProgramInfo, n_devices: int) -> Optional[str]:
    """None if the plan is legal on this topology, else the reason it isn't."""
    try:
        spec = plan.mesh.resolve(n_devices)
    except ValueError as exc:
        return str(exc)
    dp = spec.data * spec.fsdp
    if info.global_batch % max(dp, 1):
        return (
            f"global batch {info.global_batch} not divisible by the "
            f"data span {dp}"
        )
    if plan.grad_accum > 1 and (info.global_batch // max(dp, 1)) % plan.grad_accum:
        return (
            f"per-shard batch {info.global_batch // dp} not divisible by "
            f"grad_accum {plan.grad_accum}"
        )
    if spec.tensor > 1:
        if plan.family != "transformer":
            return f"tensor axis needs the transformer rule family, got {plan.family!r}"
        if info.num_heads == 0 or info.num_heads % spec.tensor:
            return (
                f"tensor span {spec.tensor} does not divide "
                f"{info.num_heads} attention heads"
            )
    if spec.pipe > 1:
        if not info.pipelineable:
            return "model has no pipeline axis"
        if info.num_layers % spec.pipe:
            return (
                f"pipe span {spec.pipe} leaves {info.num_layers} layers "
                f"unbalanced across stages"
            )
    if plan.zero1 and dp <= 1:
        return "zero1 is a no-op without a data span > 1"
    if plan.wire is not None and plan.wire.compress != "none":
        if dp <= 1:
            return "wire compression is a no-op without a data span > 1"
        if info.max_param_elems and info.max_param_elems < plan.wire.min_size:
            return (
                f"wire floor: largest param leaf ({info.max_param_elems} "
                f"elems) is below min_size {plan.wire.min_size}"
            )
    if _plan_bucketed(plan) and dp <= 1:
        return "bucketed overlap is a no-op without a data span > 1"
    return None


def _plan_bucketed(plan: PlanSpec) -> bool:
    """Whether the plan's gradient sync runs the fused bucket schedule."""
    return plan.bucket_bytes > 0 or (
        plan.wire is not None and plan.wire.bucketed
    )


def _scheduled_hidden_frac(plan: PlanSpec, data_wire_bytes: float) -> float:
    """Scheduler-level hidden fraction of the bucketed grad sync.

    Needs no leaf tree: K roughly-equal buckets hide the first K-1 behind
    remaining backward compute, so the hidden fraction is (K-1)/K with
    K estimated from the traced data-axis wire bytes over the per-bucket
    wire payload (the fp32 ``bucket_bytes`` target scaled by the wire
    config's compression factor). Conservative: capped at 0.9 — the
    link model should never score comm as entirely free.
    """
    from distributed_pytorch_example_tpu.parallel import wire as wirelib

    target = plan.bucket_bytes or (
        plan.wire.bucket_bytes if plan.wire is not None else 0
    ) or wirelib.DEFAULT_BUCKET_BYTES
    config = plan.wire or wirelib.WireConfig()
    # fp32 target -> wire-byte target under the payload compression
    per_elem = 1.0 + 2.0 / config.block_size if (
        config.compress == "int8-block"
    ) else 4.0
    bucket_wire = max(target * per_elem / 4.0, 1.0)
    k = max(1, int(round(data_wire_bytes / bucket_wire)))
    return min(0.9, (k - 1) / k)


def _axis_splits(n: int, k: int):
    """All ordered factorizations of ``n`` into ``k`` positive factors."""
    if k == 1:
        yield (n,)
        return
    for d in range(1, n + 1):
        if n % d == 0:
            for rest in _axis_splits(n // d, k - 1):
                yield (d,) + rest


def enumerate_plans(
    n_devices: int,
    info: ProgramInfo,
    families: Sequence[str] = ("data", "fsdp", "transformer"),
    zero1_options: Sequence[bool] = (False, True),
    wire_options: Sequence[Optional[WireConfig]] = (None,),
    grad_accum_options: Sequence[int] = (1,),
    opt_shard_min_size: Optional[int] = None,
    allow_pipe: bool = True,
) -> List[PlanSpec]:
    """The legal PlanSpecs for this topology, deduped by plan name.

    Enumeration is per-family so degenerate meshes never arise (a "data"
    plan puts every device on the data axis; "fsdp" requires an fsdp span
    > 1; "transformer" requires a tensor or pipe span > 1 — the pure-DP
    transformer mesh is identical to the "data" plan and is skipped).
    ZeRO-1 / wire / grad-accum knobs apply where the manual data-sync path
    supports them (no pipe composition — the dryrun table has no such
    config and the planner will not invent one).
    """
    min_kw = (
        {} if opt_shard_min_size is None
        else {"opt_shard_min_size": opt_shard_min_size}
    )
    plans: List[PlanSpec] = []
    seen = set()

    def add(plan: PlanSpec) -> None:
        name = plan.name()
        if name in seen or legality(plan, info, n_devices) is not None:
            return
        seen.add(name)
        plans.append(plan)

    def knob_grid(mesh: MeshSpec, family: str, fsdp_rest: bool = False):
        pipe_free = mesh.pipe == 1
        for zero1 in zero1_options if pipe_free else (False,):
            for wire in wire_options if pipe_free else (None,):
                for ga in grad_accum_options if pipe_free else (1,):
                    add(PlanSpec(
                        mesh=mesh, family=family, fsdp_rest=fsdp_rest,
                        zero1=zero1, wire=wire, grad_accum=ga,
                        schedule="gpipe" if mesh.pipe > 1 else None,
                        **min_kw,
                    ))

    if "data" in families:
        knob_grid(MeshSpec(data=n_devices), "data")
    if "fsdp" in families:
        for data, fs in _axis_splits(n_devices, 2):
            if fs > 1:
                # fsdp family: params born sharded — zero1/wire knobs do
                # not compose with the manual data-sync path here
                add(PlanSpec(mesh=MeshSpec(data=data, fsdp=fs), family="fsdp"))
    if "transformer" in families and info.kind == "lm":
        for data, tensor, pipe in _axis_splits(n_devices, 3):
            if tensor == 1 and pipe == 1:
                continue  # identical shardings to the "data" plan
            if pipe > 1 and (not allow_pipe or pipe < 2):
                continue
            knob_grid(
                MeshSpec(data=data, tensor=tensor, pipe=pipe), "transformer"
            )
    return plans


# -- scoring ---------------------------------------------------------------


@dataclasses.dataclass
class PlanScore:
    plan: PlanSpec
    program: str
    feasible: bool
    reason: str = ""
    tier: int = 1
    comm_ms: float = 0.0
    comm_bytes: int = 0
    bytes_by_collective: Dict[str, int] = dataclasses.field(default_factory=dict)
    predicted_peak_bytes: int = 0
    arg_bytes: int = 0
    cached_config: Optional[str] = None
    cached_comm_ms: Optional[float] = None
    overlap_hidden_frac: Optional[float] = None
    events_top: List[Dict[str, object]] = dataclasses.field(default_factory=list)

    def cost_ms(self) -> float:
        """Ranking cost: measured (tier 3) when cached, traced otherwise."""
        return self.cached_comm_ms if self.cached_comm_ms is not None else self.comm_ms

    def to_json(self) -> Dict[str, object]:
        return {
            "plan": self.plan.name(),
            "spec": self.plan.to_json(),
            "program": self.program,
            "feasible": self.feasible,
            "reason": self.reason,
            "tier": self.tier,
            "cost_ms": round(self.cost_ms(), 6),
            "comm_ms": round(self.comm_ms, 6),
            "comm_bytes": int(self.comm_bytes),
            "bytes_by_collective": {
                k: int(v) for k, v in sorted(self.bytes_by_collective.items())
            },
            "predicted_peak_bytes": int(self.predicted_peak_bytes),
            "arg_bytes": int(self.arg_bytes),
            "cached_config": self.cached_config,
            "cached_comm_ms": (
                None if self.cached_comm_ms is None
                else round(self.cached_comm_ms, 6)
            ),
            "overlap_hidden_frac": (
                None if self.overlap_hidden_frac is None
                else round(self.overlap_hidden_frac, 4)
            ),
            # named shardflow events behind the score — `plan_search --diff`
            # attributes ranking flips to these
            "events_top": list(self.events_top),
        }


def analytic_floors(
    plan: PlanSpec,
    n_devices: int,
    param_bytes: int = 0,
    global_batch: int = 0,
    seq_len: int = 0,
    model_dim: int = 0,
    num_layers: int = 0,
    dtype_bytes: int = 2,
) -> Dict[Tuple[str, ...], Tuple[str, float]]:
    """Analytic lower-bound wire bytes for collectives the trace can miss.

    The pipeline schedules run their stages inside a shard_map MANUAL
    region; GSPMD's inferred resharding events stop at that boundary, so
    shardflow sees the explicit stage-handoff ppermutes but NOT the
    data-axis gradient all-reduce or the per-layer Megatron activation
    all-reduces happening inside. Scoring such a trace at face value would
    rank a pipeline plan as near-free. These bounds are keyed by mesh
    axes; :func:`score_flow` charges each one ONLY when the traced flow
    shows zero traffic on those axes — visible traffic means the region
    was auto-partitioned and the real events are already priced.

    - data/fsdp: ring all-reduce of the gradients, ``2(dp-1)/dp`` x the
      param bytes (grads carry the param dtype).
    - tensor: the Megatron schedule's 2-forward + 2-backward activation
      all-reduces per layer over the local ``(B, S, D)`` block.
    """
    try:
        spec = plan.mesh.resolve(n_devices)
    except ValueError:
        return {}
    if spec.pipe <= 1:
        # no manual pipeline region in the program: GSPMD-inferred events
        # (auto plans) and explicit shard_map collectives (zero1/wire
        # plans) are both fully visible — the trace IS the schedule, and a
        # dtype-blind floor would overcharge compressed wire payloads
        return {}
    floors: Dict[Tuple[str, ...], Tuple[str, float]] = {}
    dp = spec.data * spec.fsdp
    if dp > 1 and param_bytes:
        floors[("data", "fsdp")] = (
            "all-reduce", 2.0 * (dp - 1) / dp * param_bytes,
        )
    if spec.tensor > 1 and global_batch and seq_len and model_dim and num_layers:
        local_act = (
            (global_batch // max(dp, 1)) * seq_len * model_dim * dtype_bytes
        )
        per_ar = 2.0 * (spec.tensor - 1) / spec.tensor * local_act
        floors[("tensor",)] = ("all-reduce", 4.0 * num_layers * per_ar)
    return floors


def score_flow(
    plan: PlanSpec,
    program: str,
    flow,
    mesh_shape: Dict[str, int],
    link: Optional[LinkModel] = None,
    hbm_limit: Optional[int] = None,
    cached: Optional[Tuple[str, Dict[str, object]]] = None,
    floors: Optional[Dict[Tuple[str, ...], Tuple[str, float]]] = None,
) -> PlanScore:
    """Tiers 1–3 over one traced program's FlowReport."""
    link = link or LinkModel()
    score = PlanScore(
        plan=plan, program=program, feasible=True,
        predicted_peak_bytes=flow.peak_bytes, arg_bytes=flow.arg_bytes,
    )
    # tier 2: would-OOM pre-gate — infeasible plans never reach a compiler
    gate = env_mod.gate_envelope(plan.name(), flow.peak_bytes, hbm_limit)
    if gate is not None:
        score.feasible = False
        score.reason = gate.detail
        score.tier = 2
        return score
    # tier 1: traced collective wire bytes through the link model
    total_devices = math.prod(mesh_shape.values()) or 1
    axis_bytes: Dict[str, float] = {}
    grad_sync_ms = 0.0  # event_ms on the data axis (the bucketable sync)
    for e in flow.comm_events():
        span = _span(e.axes, mesh_shape)
        wb = event_wire_bytes(e, span, total_devices)
        if span > 1:
            for a in e.axes:
                axis_bytes[str(a)] = axis_bytes.get(str(a), 0.0) + wb
        if wb <= 0:
            continue
        score.bytes_by_collective[e.collective] = int(
            score.bytes_by_collective.get(e.collective, 0) + wb
        )
        score.comm_bytes += int(wb)
        score.comm_ms += link.event_ms(wb)
        if "data" in (str(a) for a in e.axes):
            grad_sync_ms += link.event_ms(wb)
    # bucketed plans hide (K-1)/K of the grad-sync wire time behind the
    # backward segments still computing when early buckets issue
    # (_scheduled_hidden_frac) — discount the data-axis
    # comm so --auto-mesh scores overlap instead of treating bucketed and
    # inline syncs as equal-cost
    if _plan_bucketed(plan) and grad_sync_ms > 0:
        hidden = _scheduled_hidden_frac(plan, axis_bytes.get("data", 0.0))
        score.overlap_hidden_frac = hidden
        score.comm_ms -= hidden * grad_sync_ms
    score.events_top = [
        e.to_json()
        for e in sorted(
            flow.comm_events(),
            key=lambda e: -event_wire_bytes(
                e, _span(e.axes, mesh_shape), total_devices
            ),
        )[:5]
        if _span(e.axes, mesh_shape) > 1
    ]
    # analytic floors for axes whose collectives the trace could not see:
    # charge the SHORTFALL between the bound and the traffic actually
    # observed on those axes, so fully-visible (auto-partitioned) traces
    # are never double-charged
    for axes_key, (kind, bound) in (floors or {}).items():
        observed = sum(axis_bytes.get(a, 0.0) for a in axes_key)
        wb = max(0.0, bound - observed)
        if wb <= 0:
            continue
        score.bytes_by_collective[kind] = int(
            score.bytes_by_collective.get(kind, 0) + wb
        )
        score.comm_bytes += int(wb)
        score.comm_ms += link.event_ms(wb)
        score.events_top.append({
            "kind": "analytic-floor",
            "collective": kind,
            "axes": list(axes_key),
            "bytes": int(wb),
            "path": "analytic lower bound (manual-region collectives "
                    "invisible to shardflow)",
        })
    score.tier = 2  # envelope consulted and passed
    # tier 3: committed compiled-HLO bytes override the traced estimate
    if cached is not None:
        name, record = cached
        total_span = math.prod(v for v in mesh_shape.values() if v > 1) or 1
        ring = (total_span - 1) / total_span if total_span > 1 else 0.0
        measured = 0.0
        for kind, entry in (record.get("collectives") or {}).items():
            wb = _PASSES.get(kind, 1.0) * ring * int(entry.get("bytes", 0))
            count = max(int(entry.get("count", 1)), 1)
            measured += count * link.latency_us * 1e-3 + (
                link.event_ms(wb) - link.latency_us * 1e-3
            )
        score.cached_config = name
        score.cached_comm_ms = measured
        score.tier = 3
    return score


def match_budget_record(
    plan: PlanSpec,
    n_devices: int,
    budgets: Optional[Dict[str, object]],
    global_batch: Optional[int] = None,
) -> Optional[Tuple[str, Dict[str, object]]]:
    """The committed comm-budget record this plan coincides with, if any.

    A dryrun budget entry matches when its recorded mesh equals the plan's
    resolved mesh, the zero1/wire knobs agree, AND (when both sides know
    it) the global batch matches — the compiled bytes then describe the
    same collective schedule the plan would compile to. Records from a
    different program scale must NOT override the traced estimate.
    """
    if not budgets:
        return None
    try:
        spec = plan.mesh.resolve(n_devices)
    except ValueError:
        return None
    sizes = {a: getattr(spec, a) for a in _MESH_AXES}
    wire_on = plan.wire is not None and plan.wire.compress != "none"
    for name, record in (budgets.get("configs") or {}).items():
        mesh = record.get("mesh")
        if not isinstance(mesh, dict) or {
            a: int(mesh.get(a, 1)) for a in _MESH_AXES
        } != sizes:
            continue
        rec_zero1 = "zero1" in name
        rec_wire = record.get("wire") is not None or "wire" in name
        if rec_zero1 != plan.zero1 or rec_wire != wire_on:
            continue
        # bucketed and inline syncs compile different collective schedules
        # (fused per-bucket vs per-leaf) — never cross-match them
        if ("overlap" in name.split("+")) != _plan_bucketed(plan):
            continue
        rec_gb = record.get("global_batch")
        if (
            rec_gb is not None and global_batch is not None
            and int(rec_gb) != int(global_batch)
        ):
            continue
        return name, record
    return None


# -- per-plan tracing (zero compiles) --------------------------------------


def _unused_axes(partitioner, state_shapes) -> List[str]:
    """Mesh axes sized > 1 that no state spec or batch axis touches.

    A plan that pays for an axis no sharding uses is strictly dominated
    (same per-chip compute as the plan without the axis, plus reshards) —
    prune it before tracing. ``sequence``/``expert`` are exempt: models
    use them via internal constraints invisible to the state tree.
    """
    import jax

    mesh = partitioner.mesh
    used = set()
    batch_axes = partitioner.batch_spec()[0]
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    used.update(batch_axes or ())
    from jax.sharding import PartitionSpec as P

    for spec in jax.tree_util.tree_leaves(
        partitioner.tree_specs(state_shapes),
        is_leaf=lambda s: isinstance(s, P),
    ):
        for entry in spec:
            if entry is None:
                continue
            used.update(entry if isinstance(entry, tuple) else (entry,))
    return [
        str(a) for a in mesh.axis_names
        if mesh.shape[a] > 1 and str(a) not in used
        and str(a) not in ("sequence", "expert")
    ]


def trace_train_plan(
    model, task, optimizer, sample_inputs, batch, plan: PlanSpec,
    devices=None, state_shapes=None, jaxpr_cache: Optional[dict] = None,
):
    """(flow, mesh_shape, partitioner) for one train plan — trace only.

    ``jaxpr_cache`` (optional dict) shares the traced jaxpr across plans
    whose compiled program is identical: every automatic-mode plan (no
    ZeRO-1 / wire / accumulation) traces the same step regardless of mesh,
    so the grid pays one big trace instead of one per plan. Manual-mode
    plans embed the partitioner in the shard_map and trace individually.
    """
    import jax

    from distributed_pytorch_example_tpu.train import step as step_mod

    devices = list(devices) if devices is not None else list(jax.devices())
    mesh = make_mesh(plan.mesh, devices=devices)
    partitioner = plan.lower(mesh=mesh)
    if state_shapes is None:
        state_shapes = step_mod.abstract_state(model, optimizer, sample_inputs)
    unused = _unused_axes(partitioner, state_shapes)
    if unused:
        raise PlanPruned(f"mesh axes {unused} unused by any sharding")

    manual = plan.zero1 or plan.grad_accum > 1 or _plan_bucketed(plan) or (
        plan.wire is not None and plan.wire.active
    )
    cache_key = plan.name() if manual else ("auto", plan.grad_accum)
    jaxpr = None if jaxpr_cache is None else jaxpr_cache.get(cache_key)
    if jaxpr is None:
        step_fn = step_mod.build_train_step(
            model, task, optimizer, partitioner=partitioner,
            grad_accum_steps=plan.grad_accum,
        )
        with mesh:
            jaxpr = jax.make_jaxpr(lambda s, b: step_fn(s, b))(
                state_shapes, batch
            )
        if jaxpr_cache is not None:
            jaxpr_cache[cache_key] = jaxpr
    from jax.sharding import PartitionSpec as P

    state_specs = partitioner.tree_specs(state_shapes)
    batch_specs = jax.tree_util.tree_map(
        lambda _: partitioner.batch_spec(), batch
    )
    in_specs = jax.tree_util.tree_leaves(
        (state_specs, batch_specs), is_leaf=lambda s: isinstance(s, P)
    )
    mesh_shape = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    flow = shardflow.trace_shardings(jaxpr, in_specs, mesh_shape)
    return flow, mesh_shape, partitioner


class PlanPruned(Exception):
    """Raised when a plan is statically dominated/illegal at trace time."""


def rank_train_plans(
    model, task, optimizer, sample_inputs, batch,
    plans: Sequence[PlanSpec],
    program: str = "train",
    devices=None,
    link: Optional[LinkModel] = None,
    hbm_limit: Optional[int] = None,
    budgets: Optional[Dict[str, object]] = None,
    log=None,
    state_shapes=None,
) -> List[PlanScore]:
    """Score + rank train plans for one model. Feasible plans first,
    cheapest ranking cost first; infeasible plans trail with reasons."""
    import jax

    from distributed_pytorch_example_tpu.train import step as step_mod

    devices = list(devices) if devices is not None else list(jax.devices())
    if state_shapes is None:
        state_shapes = step_mod.abstract_state(
            model, optimizer, sample_inputs
        )
    param_leaves = jax.tree_util.tree_leaves(state_shapes.params)
    param_bytes = sum(
        math.prod(l.shape) * l.dtype.itemsize for l in param_leaves
    )
    dtype_bytes = param_leaves[0].dtype.itemsize if param_leaves else 2
    batch_leaves = jax.tree_util.tree_leaves(batch)
    global_batch = int(batch_leaves[0].shape[0]) if batch_leaves else 0
    seq_len = (
        int(batch_leaves[0].shape[1])
        if batch_leaves and len(batch_leaves[0].shape) > 1 else 0
    )
    jaxpr_cache: dict = {}
    scores: List[PlanScore] = []
    for plan in plans:
        try:
            flow, mesh_shape, _ = trace_train_plan(
                model, task, optimizer, sample_inputs, batch, plan,
                devices=devices, state_shapes=state_shapes,
                jaxpr_cache=jaxpr_cache,
            )
        except PlanPruned as exc:
            scores.append(PlanScore(
                plan=plan, program=program, feasible=False,
                reason=str(exc),
            ))
            continue
        except Exception as exc:  # trace failure = infeasible, not fatal
            scores.append(PlanScore(
                plan=plan, program=program, feasible=False,
                reason=f"{type(exc).__name__}: {str(exc).splitlines()[0][:200]}",
            ))
            continue
        cached = match_budget_record(
            plan, len(devices), budgets, global_batch=global_batch or None
        )
        floors = analytic_floors(
            plan, len(devices), param_bytes=param_bytes,
            global_batch=global_batch, seq_len=seq_len,
            model_dim=int(getattr(model, "model_dim", 0) or 0),
            num_layers=int(getattr(model, "num_layers", 0) or 0),
            dtype_bytes=dtype_bytes,
        )
        score = score_flow(
            plan, program, flow, mesh_shape,
            link=link, hbm_limit=hbm_limit, cached=cached, floors=floors,
        )
        scores.append(score)
        if log is not None:
            log(
                f"graft_plan: {program} {plan.name()} tier={score.tier} "
                f"cost_ms={score.cost_ms():.4f} comm_bytes={score.comm_bytes} "
                f"peak={score.predicted_peak_bytes}B feasible={score.feasible}"
            )
    return sort_scores(scores)


def rank_serve_plans(
    engine,
    plans: Sequence[PlanSpec],
    devices=None,
    link: Optional[LinkModel] = None,
    hbm_limit: Optional[int] = None,
    budgets: Optional[Dict[str, object]] = None,
    log=None,
) -> Dict[str, List[PlanScore]]:
    """Rank plans for the engine's prefill and decode programs SEPARATELY
    (``{"serve/prefill": [...], "serve/decode": [...]}``) — the two have
    different collective profiles, reusing the engine's representative
    traced args via :meth:`InferenceEngine.plan_programs`."""
    import jax

    devices = list(devices) if devices is not None else list(jax.devices())
    out: Dict[str, List[PlanScore]] = {}
    for plan in plans:
        try:
            mesh = make_mesh(plan.mesh, devices=devices)
            partitioner = plan.lower(mesh=mesh)
            programs = engine.plan_programs(partitioner)
        except Exception as exc:
            for prog in ("serve/prefill", "serve/decode"):
                out.setdefault(prog, []).append(PlanScore(
                    plan=plan, program=prog, feasible=False,
                    reason=f"{type(exc).__name__}: "
                           f"{str(exc).splitlines()[0][:200]}",
                ))
            continue
        mesh_shape = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        for prog, (jaxpr, in_specs) in programs.items():
            flow = shardflow.trace_shardings(jaxpr, in_specs, mesh_shape)
            cached = None
            rec = (budgets or {}).get("configs", {}).get(prog)
            if rec is not None and match_budget_record(
                plan, len(devices), {"configs": {prog: rec}}
            ):
                cached = (prog, rec)
            score = score_flow(
                plan, prog, flow, mesh_shape,
                link=link, hbm_limit=hbm_limit, cached=cached,
            )
            out.setdefault(prog, []).append(score)
            if log is not None:
                log(
                    f"graft_plan: {prog} {plan.name()} tier={score.tier} "
                    f"cost_ms={score.cost_ms():.4f} "
                    f"comm_bytes={score.comm_bytes} feasible={score.feasible}"
                )
    return {prog: sort_scores(s) for prog, s in out.items()}


def sort_scores(scores: Sequence[PlanScore]) -> List[PlanScore]:
    """Feasible-first, then (ranking cost, peak bytes, name) ascending."""
    return sorted(
        scores,
        key=lambda s: (
            not s.feasible, s.cost_ms(), s.predicted_peak_bytes,
            s.plan.name(),
        ),
    )


def best_plan(scores: Sequence[PlanScore]) -> Optional[PlanScore]:
    """Top-ranked FEASIBLE score, or None when every plan was pruned."""
    for s in sort_scores(scores):
        if s.feasible:
            return s
    return None


def cli_plan_space(
    n_devices: int, info: ProgramInfo, wire_block: int = 256
) -> List[PlanSpec]:
    """The ``--auto-mesh`` search space shared by train.py /
    scripts/plan_search.py: every automatic-mode mesh family (one shared
    trace) plus the zero1 / int8-wire knobs on the pure-DP mesh (one trace
    each — where train.py's --zero1/--wire run), never wire without zero1.
    Every pure-DP ZeRO-1 plan also enters in its comm/compute-overlap
    variant (``bucket_bytes`` at the default target) so the oracle can
    pick bucketing when the hidden grad-sync time wins."""
    from distributed_pytorch_example_tpu.parallel.wire import (
        DEFAULT_BUCKET_BYTES,
    )

    wire = WireConfig(compress="int8-block", block_size=wire_block)
    plans = enumerate_plans(
        n_devices, info,
        families=("data", "fsdp", "transformer"),
        zero1_options=(False, True),
        wire_options=(None, wire),
        allow_pipe=False,
    )
    plans = [
        p for p in plans
        if (p.family == "data" or (not p.zero1 and p.wire is None))
        and (p.wire is None or p.zero1)
    ]
    bucketed = [
        dataclasses.replace(p, bucket_bytes=DEFAULT_BUCKET_BYTES)
        for p in plans
        if p.family == "data" and p.zero1
    ]
    return plans + [
        b for b in bucketed
        if legality(b, info, n_devices) is None
    ]


def pick_train_plan(
    model, task, optimizer, sample_inputs, batch,
    kind: str = "image",
    program: str = "train",
    devices=None,
    hbm_limit: Optional[int] = None,
    wire_block: int = 256,
    log=None,
) -> Tuple[Optional[PlanScore], List[PlanScore]]:
    """One-call ``--auto-mesh`` entry point: ``(winner, all scores)``.

    Enumerates :func:`cli_plan_space` for the program's topology, ranks it
    through the three-tier oracle (committed comm budgets engage when the
    recorded jax version matches the runtime), and returns the best
    feasible score — None when the envelope gate pruned everything.
    """
    import jax

    from distributed_pytorch_example_tpu.analysis import collectives

    devices = list(devices) if devices is not None else list(jax.devices())
    leaves = jax.tree_util.tree_leaves(batch)
    info = ProgramInfo(
        global_batch=int(leaves[0].shape[0]) if leaves else 0,
        num_heads=int(getattr(model, "num_heads", 0) or 0),
        num_layers=int(getattr(model, "num_layers", 0) or 0),
        pipelineable=False,
        kind=kind,
    )
    plans = cli_plan_space(len(devices), info, wire_block=wire_block)
    budgets = collectives.load_budgets()
    if budgets is not None and collectives.jax_version_skew(budgets):
        budgets = None
    scores = rank_train_plans(
        model, task, optimizer, sample_inputs, batch, plans,
        program=program, devices=devices, hbm_limit=hbm_limit,
        budgets=budgets, log=log,
    )
    return best_plan(scores), scores


def pick_serve_plan(
    engine,
    devices=None,
    hbm_limit: Optional[int] = None,
    budgets: Optional[Dict[str, object]] = None,
    log=None,
    extra_plans: Sequence[PlanSpec] = (),
) -> Tuple[Optional[PlanSpec], Optional[float], Dict[str, List[PlanScore]]]:
    """``--auto-mesh`` for serving: ``(plan, summed cost_ms, rankings)``.

    Prefill and decode are ranked SEPARATELY (different collective
    profiles); one engine must run both, so the pick minimizes the summed
    program cost over plans feasible for BOTH. Serve batch dims (slots,
    bucketed prompt) replicate in the traced programs, so the legality
    batch is the device count itself. Pass ``budgets=None`` (the default)
    unless the engine IS the committed dryrun engine — the budget records
    match by mesh alone and would pollute across model scales.
    """
    import jax

    devices = list(devices) if devices is not None else list(jax.devices())
    info = ProgramInfo(
        global_batch=len(devices),
        num_heads=int(getattr(engine.model, "num_heads", 0) or 0),
        num_layers=int(getattr(engine.model, "num_layers", 0) or 0),
        pipelineable=False,
        kind="lm",
    )
    plans = enumerate_plans(
        len(devices), info, families=("data", "transformer"),
        zero1_options=(False,), wire_options=(None,), allow_pipe=False,
    )
    seen = {p.name() for p in plans}
    for p in extra_plans:
        if p.name() not in seen and legality(p, info, len(devices)) is None:
            plans.append(p)
    ranked = rank_serve_plans(
        engine, plans, devices=devices, hbm_limit=hbm_limit,
        budgets=budgets, log=log,
    )
    by_name: Dict[str, Dict[str, PlanScore]] = {}
    for prog, scores in ranked.items():
        for s in scores:
            by_name.setdefault(s.plan.name(), {})[prog] = s
    best_spec, best_cost, best_name = None, None, None
    for nm in sorted(by_name):
        progs = by_name[nm]
        if len(progs) < len(ranked) or not all(
            s.feasible for s in progs.values()
        ):
            continue
        cost = sum(s.cost_ms() for s in progs.values())
        if best_cost is None or cost < best_cost:
            best_name, best_cost = nm, cost
            best_spec = next(iter(progs.values())).plan
    return best_spec, best_cost, ranked


# -- committed plan rankings (analysis/plans.json) -------------------------

# Committed beside comm_budgets.json: top-ranked plans per program on the
# 8-chip fake mesh, written by `scripts/plan_search.py --write-plans`.
DEFAULT_PLANS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "plans.json"
)


def load_plans(path: str = DEFAULT_PLANS_PATH) -> Optional[Dict[str, object]]:
    """Parsed committed plan rankings, or None when absent/corrupt."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None
