"""Partitioner: path-rule → PartitionSpec assignment over pytrees.

The core mechanism: every leaf of the train state (params, optimizer moments,
batch stats) gets a ``PartitionSpec`` chosen by the first matching rule on its
'/'-joined tree path. Optimizer moments (optax ``mu``/``nu``) mirror the param
tree structure, so the same name rules match them automatically — this is how
ZeRO-style optimizer sharding falls out for free.

Rules are ``(regex, spec)`` where spec is a ``PartitionSpec`` or a callable
``(shape) -> PartitionSpec`` for shape-dependent placement (FSDP's
"shard the largest divisible axis").

ZeRO-1 (``dp_shard_opt_state=True``): optimizer-state leaves additionally
shard over the ``data`` axis — the cross-replica weight-update sharding of
Xu et al. (arxiv 2004.13336). The overlay composes with whatever the path
rules chose (TP/SP/pipe axes stay where they are): each opt-state leaf gets
``data`` on its LARGEST still-unsharded divisible dim, falling back to
replicated below a size floor (tiny biases/scalars aren't worth a
collective). Params themselves stay replicated over ``data`` — only the
update is sharded; ``train/step.py`` reduce-scatters grads into this layout
and all-gathers updated params back.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_pytorch_example_tpu.runtime import mesh as mesh_lib

SpecLike = Union[P, Callable[[Tuple[int, ...]], P]]
Rule = Tuple[str, SpecLike]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def pvary_like(tree: Any, like: jax.Array, extra_axes: Sequence[str] = ()) -> Any:
    """Mark constant arrays as device-varying to match ``like``'s vma set.

    Under ``shard_map``, scan carries initialized from constants must carry
    the same varying-manual-axes type as the per-step outputs derived from
    sharded inputs; this stamps them (used by ring attention and the
    pipeline schedule).
    """
    from jax import lax

    target = set(jax.typeof(like).vma) | set(extra_axes)

    def mark(x):
        missing = tuple(target - set(jax.typeof(x).vma))
        if not missing:
            return x
        return lax.pcast(x, missing, to="varying")

    return jax.tree_util.tree_map(mark, tree)


def shard_largest_axis(axis_name: str, mesh: Mesh) -> Callable[[Tuple[int, ...]], P]:
    """Spec factory: place ``axis_name`` on the leaf's largest divisible dim.

    Ties break toward the last (usually output/feature) dimension, which is
    the contiguous one on TPU. Leaves with no divisible dim stay replicated.
    """
    size = mesh.shape[axis_name]

    def spec(shape: Tuple[int, ...]) -> P:
        if size == 1 or not shape:
            return P()
        best = None
        for dim, extent in enumerate(shape):
            if extent % size == 0 and (best is None or extent >= shape[best]):
                best = dim
        if best is None:
            return P()
        entries: list = [None] * len(shape)
        entries[best] = axis_name
        return P(*entries)

    return spec


# opt-state leaves live under this prefix in the TrainState tree
# (``opt_state/0/mu/...``); standalone opt-state trees pass the prefix to
# ``tree_specs(path_prefix=...)`` explicitly
_OPT_STATE_RE = re.compile(r"(^|/)opt_state(/|$)")

# ZeRO-1 floor: opt-state leaves below this many ELEMENTS stay replicated
# (64 KB at f32 — mirrors the XLA donation-aliasing floor rationale: a
# reduce-scatter of a bias costs more in latency than its shard saves)
DEFAULT_OPT_SHARD_MIN_SIZE = 1 << 14


class Partitioner:
    """Assigns shardings to state pytrees and batches over a mesh."""

    def __init__(
        self,
        mesh: Mesh,
        rules: Sequence[Rule] = (),
        default: SpecLike = P(),
        dp_shard_opt_state: bool = False,
        opt_shard_axis: str = "data",
        opt_shard_min_size: int = DEFAULT_OPT_SHARD_MIN_SIZE,
        wire=None,
    ):
        self.mesh = mesh
        self.rules = [(re.compile(pattern), spec) for pattern, spec in rules]
        self.default = default
        self.dp_shard_opt_state = dp_shard_opt_state
        self.opt_shard_axis = opt_shard_axis
        self.opt_shard_min_size = opt_shard_min_size
        # collective-compression policy (parallel/wire.py WireConfig or
        # None = fp32 payloads); the step picks it up from here so one
        # partitioner object carries the whole gradient-sync contract
        self.wire = wire
        self._warned_fallbacks: set = set()  # one line per distinct cause

    def _fits(self, spec: P, shape: Tuple[int, ...]) -> bool:
        """Whether ``spec`` is applicable to a leaf of this shape.

        Rules match by PATH, but some state trees reuse param paths with
        different ranks (optax adafactor's factored v_row/v_col are rank-1
        under rank-2 param paths) — a fixed-rank spec must then fall back
        rather than crash device_put.
        """
        import math

        if len(spec) > len(shape):
            return False
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = math.prod(self.mesh.shape[a] for a in axes)
            if shape[dim] % size:
                return False
        return True

    def spec_for(self, path: str, shape: Tuple[int, ...]) -> P:
        base = self._base_spec(path, shape)
        if self.dp_shard_opt_state and _OPT_STATE_RE.search(path):
            return self.zero1_overlay(base, shape)
        return base

    def _base_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        for pattern, spec in self.rules:
            if pattern.search(path):
                s = spec(shape) if callable(spec) else spec
                if self._fits(s, shape):
                    return s
                # matched rule unfit for this rank/shape: fall back, but
                # say so — this is right for adafactor's rank-1 factored
                # stats under rank-2 param paths, and a misconfiguration
                # signal everywhere else (e.g. tensor axis > head dim)
                self._warn_fallback(path, s, shape, "rule")
                break
        d = self.default
        s = d(shape) if callable(d) else d
        if self._fits(s, shape):
            return s
        if s != P():
            self._warn_fallback(path, s, shape, "default")
        return P()

    # -- ZeRO-1 overlay ----------------------------------------------------

    def zero1_overlay(self, spec: P, shape: Tuple[int, ...]) -> P:
        """``spec`` with the ``data`` axis added on the overlay dim (if any).

        Composes with the base rules: TP/SP/pipe placements are untouched;
        ``data`` lands on the LARGEST dim the base spec leaves unsharded
        whose extent the axis size divides. Leaves below the element floor,
        with no divisible free dim, or already touching the axis stay as-is
        (their grads all-reduce and their moments replicate — correct,
        just unsharded).
        """
        dim = self.zero1_dim(spec, shape)
        if dim is None:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        entries[dim] = self.opt_shard_axis
        return P(*entries)

    def zero1_dim(self, spec: P, shape: Tuple[int, ...]) -> Optional[int]:
        """The dim ``zero1_overlay`` would shard, or None (stays as-is)."""
        if not self.dp_shard_opt_state or not shape:
            return None
        size = self.mesh.shape.get(self.opt_shard_axis, 1)
        if size <= 1 or math.prod(shape) < self.opt_shard_min_size:
            return None
        entries = list(spec) + [None] * (len(shape) - len(spec))
        for entry in entries:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            if self.opt_shard_axis in axes:
                return None  # base rules already placed the axis
        best = None
        for dim, extent in enumerate(shape):
            if entries[dim] is None and extent % size == 0 and (
                best is None or extent > shape[best]
            ):
                best = dim
        return best

    def zero1_dims(self, params: Any) -> Any:
        """Per-PARAM-leaf overlay dims (None = all-reduce/replicated leaf).

        Drives the step's gradient reduce-scatter: grads mirror the param
        tree, so the dim that shards a param's optimizer moments is the
        scatter dimension of that param's gradient collective.
        """

        def leaf_dim(path, leaf):
            shape = tuple(getattr(leaf, "shape", ()) or ())
            return self.zero1_dim(self._base_spec(_path_str(path), shape), shape)

        return jax.tree_util.tree_map_with_path(leaf_dim, params)

    def _warn_fallback(self, path, spec, shape, kind: str) -> None:
        from distributed_pytorch_example_tpu.runtime.logging import get_logger

        log = get_logger(__name__)
        if len(spec) > len(shape):
            # the expected case: optax state reusing a param path at lower
            # rank (adafactor's factored v_row/v_col) — visible, not noisy
            log.debug(
                "partitioner: %s spec %s outranks %s at %r — replicated",
                kind, spec, shape, path,
            )
            return
        key = (kind, str(spec), shape)
        if key in self._warned_fallbacks:
            return
        self._warned_fallbacks.add(key)
        log.warning(
            "partitioner: %s spec %s does not divide %s (e.g. at %r) — "
            "such leaves fall back to %s (replication); check the mesh "
            "axis sizes if this is unexpected",
            kind, spec, shape, path,
            "the default" if kind == "rule" else "P()",
        )

    def tree_specs(self, tree: Any, path_prefix: str = "") -> Any:
        """PartitionSpec per leaf (tree may hold arrays or ShapeDtypeStructs).

        ``path_prefix`` scopes path-sensitive policies for SUBTREES handed
        in standalone: a bare opt-state tree has paths like ``0/mu/...``,
        so the ZeRO-1 overlay only engages when the caller prepends
        ``"opt_state/"`` (the step does, when re-constraining the updated
        optimizer state).
        """

        def leaf_spec(path, leaf):
            shape = tuple(getattr(leaf, "shape", ()) or ())
            return self.spec_for(path_prefix + _path_str(path), shape)

        return jax.tree_util.tree_map_with_path(leaf_spec, tree)

    def tree_shardings(self, tree: Any, path_prefix: str = "") -> Any:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            self.tree_specs(tree, path_prefix=path_prefix),
        )

    # -- manual (shard_map) gradient-sync contract -------------------------
    # train/step.py's data-manual region derives every spec and axis name
    # from these helpers, so axis placement has a single source of truth:
    # the PlanSpec lowering that built this partitioner (the plan-overlay
    # graft-lint rule rejects hand-built axis-name specs in the step).

    def grad_sync_axis(self) -> str:
        """Mesh axis the manual gradient collectives run over."""
        return self.opt_shard_axis

    def manual_batch_spec(self) -> P:
        """Batch in_spec for the data-manual region (leading dim sharded)."""
        return P((self.opt_shard_axis,))

    def manual_axis_spec(self) -> P:
        """Spec of a 1-D array with one element per sync-axis shard."""
        return P(self.opt_shard_axis)

    def grad_scatter_spec(self, dim: Optional[int], ndim: int) -> P:
        """out_spec of one synced grad leaf.

        ``dim`` is the leaf's ZeRO-1 overlay dim (``zero1_dims``): the
        psum_scatter lands the shard there; None means the leaf psums to
        replicated.
        """
        if dim is None:
            return P()
        entries: list = [None] * ndim
        entries[dim] = self.opt_shard_axis
        return P(*entries)

    def batch_spec(self) -> P:
        """Leading-dim sharding over the joint data axes (global batch)."""
        return P(mesh_lib.data_axes(self.mesh))

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_tree(self, tree: Any) -> Any:
        """Place an existing (host or device) pytree per the rules."""
        return jax.device_put(tree, self.tree_shardings(tree))


def data_parallel(
    mesh: Mesh,
    dp_shard_opt_state: bool = False,
    opt_shard_min_size: int = DEFAULT_OPT_SHARD_MIN_SIZE,
    wire=None,
) -> Partitioner:
    """Pure DP: everything replicated; batch on (data, fsdp).

    Semantics parity with the reference: params identical on every replica,
    gradients mean-reduced across the data axes each step (DDP default,
    train.py:233). ``dp_shard_opt_state=True`` flips the update to ZeRO-1:
    grads reduce-scatter, optimizer state shards over ``data``, updated
    params all-gather back (see module docstring). ``wire`` (a
    ``parallel.wire.WireConfig``) compresses those gradient collectives.

    Lowers ``PlanSpec(family="data", ...)`` (parallel/plan.py) — the spec is
    the single source of the rule set; this wrapper keeps the legacy call
    signature.
    """
    from distributed_pytorch_example_tpu.parallel.plan import PlanSpec

    return PlanSpec(
        family="data",
        zero1=dp_shard_opt_state,
        opt_shard_min_size=opt_shard_min_size,
        wire=wire,
    ).lower(mesh=mesh)


def fsdp(mesh: Mesh, axis: str = "fsdp") -> Partitioner:
    """ZeRO-3-style: every param/moment leaf sharded on its largest dim.

    Lowers ``PlanSpec(family="fsdp", fsdp_axis=axis)`` (parallel/plan.py).
    """
    from distributed_pytorch_example_tpu.parallel.plan import PlanSpec

    return PlanSpec(family="fsdp", fsdp_axis=axis).lower(mesh=mesh)
