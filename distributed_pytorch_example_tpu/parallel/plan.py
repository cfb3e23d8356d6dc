"""PlanSpec: the declarative parallelism plan every entry point lowers.

Historically each surface assembled its own Partitioner: ``train.py`` picked
a factory from CLI flags, serve.py
hand-built a transformer partitioner from ``--mesh``, and the ZeRO-1/wire
knobs rode along as ad-hoc keyword overlays. A static planner cannot search
a space that only exists as scattered call sites — so the whole contract is
collapsed here into one frozen, composable value:

    PlanSpec(mesh=MeshSpec(data=4, tensor=2), family="transformer",
             zero1=True, wire=WireConfig(compress="int8-block"))

``lower()`` is the ONLY place partition rules are constructed (the
``plan-overlay`` graft-lint rule enforces that ``parallel/api.py`` and
``train/step.py`` never build axis-name PartitionSpecs behind its back).
The legacy factories (``data_parallel``, ``fsdp``,
``transformer_partitioner``) are now one-line lowerings of a PlanSpec, so
they stay bit-identical: the committed ``analysis/comm_budgets.json``
structural signatures gate that equivalence without regeneration.

``analysis/planner.py`` (graft-plan) enumerates PlanSpecs, prunes illegal
ones, and scores the survivors through the trace-only three-tier oracle;
``--auto-mesh`` on train.py/serve.py lowers the winner.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_example_tpu.parallel.api import (
    DEFAULT_OPT_SHARD_MIN_SIZE,
    Partitioner,
    Rule,
    shard_largest_axis,
)
from distributed_pytorch_example_tpu.parallel.wire import WireConfig
from distributed_pytorch_example_tpu.runtime.mesh import MeshSpec, make_mesh

# rule-table families a plan can lower into; "transformer" covers TP, PP
# (layer-stacked), EP and vocab parallelism via the shared rule table
FAMILIES: Tuple[str, ...] = ("data", "fsdp", "transformer")

_MESH_AXES = ("data", "fsdp", "tensor", "sequence", "expert", "pipe")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """One point in the parallelism-plan space.

    Fields compose: ``family`` picks the base rule table, ``zero1`` adds the
    optimizer-state overlay on top of it, ``wire`` compresses the gradient
    collectives the overlay implies, ``grad_accum`` multiplies the per-step
    microbatches. ``schedule`` is informational (the pipeline runner is
    selected by the caller, not the partitioner) but participates in plan
    naming/legality so the planner can reason about 1F1B stash memory.

    ``bucket_bytes`` > 0 opts the gradient sync into the fused
    comm/compute-overlap bucket schedule (``parallel/wire.py
    sync_grads``): ``lower()`` merges it into the wire config (creating a
    compression-free ``WireConfig`` when ``wire`` is None), so bucketing
    is a plan-level knob the planner can score (``LinkModel`` discounts
    hidden grad-sync time for bucketed plans) and ``--overlap-buckets``
    can set from the CLI without touching the wire payload choice.
    """

    mesh: MeshSpec = MeshSpec()
    family: str = "data"
    fsdp_rest: bool = False
    fsdp_axis: str = "fsdp"
    zero1: bool = False
    opt_shard_min_size: int = DEFAULT_OPT_SHARD_MIN_SIZE
    grad_accum: int = 1
    wire: Optional[WireConfig] = None
    schedule: Optional[str] = None
    bucket_bytes: int = 0

    # -- lowering ----------------------------------------------------------

    def lower(self, mesh: Optional[Mesh] = None, devices=None) -> Partitioner:
        """Build the Partitioner this plan denotes.

        ``mesh`` short-circuits mesh construction (the legacy factories pass
        the one they were handed); otherwise ``self.mesh`` is resolved over
        ``devices`` (default: all local devices).
        """
        if self.family not in FAMILIES:
            raise ValueError(
                f"unknown plan family {self.family!r}; expected one of {FAMILIES}"
            )
        if mesh is None:
            mesh = make_mesh(self.mesh, devices=devices)
        rules, default = self._rules_for(mesh)
        wire = self.wire
        if self.bucket_bytes > 0:
            # bucketing is a plan knob, payload choice a wire knob — merge
            # here so the partitioner sees ONE effective WireConfig
            wire = dataclasses.replace(
                wire or WireConfig(), bucket_bytes=self.bucket_bytes
            )
        return Partitioner(
            mesh,
            rules=rules,
            default=default,
            dp_shard_opt_state=self.zero1,
            opt_shard_min_size=self.opt_shard_min_size,
            wire=wire,
        )

    def _rules_for(self, mesh: Mesh):
        """(rules, default) for the family — the one rule-assembly site."""
        if self.family == "data":
            return (), P()
        if self.family == "fsdp":
            return ((r".*", shard_largest_axis(self.fsdp_axis, mesh)),), P()
        # family == "transformer" — the Megatron TP/PP/EP table plus the
        # shape-dependent vocab-parallel embeddings/head (moved here from
        # partition.transformer_partitioner; behavior identical)
        from distributed_pytorch_example_tpu.parallel.partition import (
            TRANSFORMER_TP_RULES,
        )

        default = shard_largest_axis(self.fsdp_axis, mesh) if self.fsdp_rest else P()

        def _default_spec(shape):
            return default(shape) if callable(default) else default

        tsize = mesh.shape.get("tensor", 1)

        def vocab_embed(shape):  # (V, D)
            if tsize > 1 and shape and shape[0] % tsize == 0:
                return P("tensor", None)
            return _default_spec(shape)

        def vocab_head(shape):  # (D, V)
            if tsize > 1 and shape and shape[-1] % tsize == 0:
                return P(None, "tensor")
            return _default_spec(shape)

        rules: list = list(TRANSFORMER_TP_RULES) + [
            (r"(wte|tok_embed)/embedding$", vocab_embed),
            (r"lm_head$", vocab_head),
        ]
        return rules, default

    # -- identity / serialization ------------------------------------------

    def name(self) -> str:
        """Stable human-readable id, e.g. ``tf:data2,tensor2,pipe2+zero1+int8``."""
        axes = ",".join(
            f"{ax}{getattr(self.mesh, ax)}"
            for ax in _MESH_AXES
            if getattr(self.mesh, ax) not in (1,)
        ) or "single"
        tag = {"data": "dp", "fsdp": "fsdp", "transformer": "tf"}[self.family]
        parts = [f"{tag}:{axes}"]
        if self.fsdp_rest:
            parts.append("rest-fsdp")
        if self.zero1:
            parts.append("zero1")
        if self.wire is not None and self.wire.compress != "none":
            parts.append(self.wire.compress)
        if self.bucket_bytes > 0 or (
            self.wire is not None and self.wire.bucketed
        ):
            parts.append("overlap")
        if self.grad_accum > 1:
            parts.append(f"ga{self.grad_accum}")
        if self.schedule:
            parts.append(self.schedule)
        return "+".join(parts)

    def to_json(self) -> dict:
        d = {
            "mesh": dataclasses.asdict(self.mesh),
            "family": self.family,
            "fsdp_rest": self.fsdp_rest,
            "fsdp_axis": self.fsdp_axis,
            "zero1": self.zero1,
            "opt_shard_min_size": self.opt_shard_min_size,
            "grad_accum": self.grad_accum,
            "wire": dataclasses.asdict(self.wire) if self.wire else None,
            "schedule": self.schedule,
            "bucket_bytes": self.bucket_bytes,
        }
        return d

    @classmethod
    def from_json(cls, d: dict) -> "PlanSpec":
        d = dict(d)
        mesh = MeshSpec(**d.pop("mesh", {}))
        wire = d.pop("wire", None)
        return cls(
            mesh=mesh,
            wire=WireConfig(**wire) if wire else None,
            **{k: v for k, v in d.items() if k in {
                f.name for f in dataclasses.fields(cls)
            }},
        )
