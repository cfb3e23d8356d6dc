"""Jaxpr/placement lints: replication, f32 upcasts, dropped donation.

Three regression classes that never fail a numeric test:

- a large param left FULLY REPLICATED under a multi-axis mesh when a
  partition rule would shard it (2x..Nx param HBM + a silent all-gather
  in the step);
- a bf16→f32 ``convert_element_type`` of a LARGE array inside the
  loss/backward path that is not one of the deliberate f32 islands
  (optimizer moments, norm/softmax statistics, metric sums) — the classic
  accidental-upcast that doubles activation bytes;
- a donated argument the compiled executable did not actually alias
  (donation silently dropped = the updated state materializes NEXT TO the
  old one: 2x param+optimizer memory).

All entry points are static — they walk jaxprs, committed shardings, and
compiled-HLO metadata; nothing executes.
"""

from __future__ import annotations

import math
import re
from typing import Any, Iterable, List, Optional, Sequence, Tuple

from distributed_pytorch_example_tpu.analysis.findings import Finding

# bf16→f32 promotions whose SOURCE matches one of these regexes are
# deliberate f32 islands, not bugs. Matched against jax's source summary
# ("path/to/file.py:line (function)") of the convert_element_type site.
DEFAULT_UPCAST_ALLOWLIST: Tuple[str, ...] = (
    r"optax",                      # optimizer moments/updates are f32
    r"flax/linen/normalization",   # LayerNorm/RMSNorm statistics
    r"normalization\.py",
    r"jax/_src/nn",                # softmax/logsumexp accumulators
    r"chunked_ce\.py",             # the fused CE's own f32 accumulation
    r"metrics",                    # metric sums
    r"train/(tasks|step)\.py",     # loss reduction / metric assembly
    # graft-scope sentinels: param/grad-norm squares accumulate in f32 by
    # contract (telemetry/sentinels.py global_norm) — large bf16 param
    # leaves upcast once per step inside the compiled step
    r"telemetry/sentinels\.py",
    r"ops/attention\.py",          # deliberate f32 softmax (commented)
    # flax layers under the mixed-precision policy: f32 master params are
    # cast to bf16 compute, so AD emits a bf16->f32 convert per kernel
    # GRADIENT (master-weight accumulation), and LayerNorm statistics
    # upcast inside the module __call__ — both attributed by jax's source
    # summary to the CALLER line in models/ ("file.py:line:col
    # (Class.__call__)") or, for the kernel casts, to flax's own
    # promote_dtype frame
    r"models/\S+\.py:\d+(:\d+)? \((\w+\.)?__call__\)",
    r"flax/linen/dtypes\.py:\d+(:\d+)? \(promote_dtype\)",
)

# arrays smaller than this are metric/statistic sums, not activations —
# 64k elements is far above any scalar bookkeeping and far below the
# smallest per-chip activation at bench scale (16 x 1024 x 768 = 12.6M)
DEFAULT_UPCAST_MIN_ELEMENTS = 1 << 16

DEFAULT_REPLICATED_MIN_BYTES = 1 << 20  # 1 MB

# XLA declines to alias tiny donated buffers (copying a bias is cheaper
# than constraining the schedule) — that is backend policy, not a dropped
# donation. 64 KB keeps every real param/optimizer leaf (MBs at flagship
# scale) in scope while ignoring bias/scale/scalar noise.
DEFAULT_DONATION_MIN_BYTES = 1 << 16


def _jaxpr_types():
    try:
        from jax.extend import core as jex_core

        return (jex_core.Jaxpr, jex_core.ClosedJaxpr)
    except Exception:
        import jax

        return (jax.core.Jaxpr, jax.core.ClosedJaxpr)


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Every equation of a (closed) jaxpr, recursing into sub-jaxprs
    (pjit/scan/while/cond/custom_vjp/shard_map bodies)."""
    types = _jaxpr_types()
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (
                value if isinstance(value, (list, tuple)) else (value,)
            ):
                if isinstance(sub, types):
                    yield from iter_eqns(sub)


def _summarize_source(eqn) -> str:
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def lint_dtype_promotions(
    jaxpr,
    allowlist: Sequence[str] = DEFAULT_UPCAST_ALLOWLIST,
    min_elements: int = DEFAULT_UPCAST_MIN_ELEMENTS,
    config: Optional[str] = None,
) -> List[Finding]:
    """Flag large off-allowlist bf16→f32 converts anywhere in ``jaxpr``."""
    import jax.numpy as jnp

    patterns = [re.compile(p) for p in allowlist]
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        if eqn.params.get("new_dtype") != jnp.float32:
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or getattr(aval, "dtype", None) != jnp.bfloat16:
            continue
        size = math.prod(getattr(aval, "shape", ()) or (1,))
        if size < min_elements:
            continue
        source = _summarize_source(eqn)
        if any(p.search(source) for p in patterns):
            continue
        findings.append(Finding(
            rule="bf16-upcast",
            where=source,
            message=(
                f"bf16->f32 convert of shape {tuple(aval.shape)} "
                f"({size} elements) outside the f32-island allowlist — "
                f"if deliberate, extend the allowlist with a why"
            ),
            config=config,
        ))
    return findings


def _leaf_path_str(path) -> str:
    from distributed_pytorch_example_tpu.parallel.api import _path_str

    return _path_str(path)


def lint_replicated_params(
    params: Any,
    partitioner,
    min_bytes: int = DEFAULT_REPLICATED_MIN_BYTES,
    config: Optional[str] = None,
    path_prefix: str = "",
) -> List[Finding]:
    """Flag large fully-replicated params that ``partitioner`` would shard.

    ``params`` is a COMMITTED (placed) param tree; ``partitioner`` is the
    reference ruleset declaring intent. A leaf is a violation when it is
    at least ``min_bytes``, its committed sharding is fully replicated,
    and the rules map it to a spec that actually spans a >1-size mesh
    axis (rules landing on size-1 axes are vacuously replicated).

    ``path_prefix`` prepends a tree location to every leaf path before
    the rules are consulted — pass ``"opt_state"`` to run the rule over
    optimizer-state trees, where ``Partitioner.spec_for`` additionally
    applies the ZeRO-1 overlay (``parallel/api.py _OPT_STATE_RE``): a
    large replicated Adam moment is then judged against the OVERLAID
    spec, so opt shards the rules would dp-shard get flagged too.
    Leaves the overlay's ``opt_shard_min_size`` floor keeps replicated
    (strictly below the floor) resolve to a span of 1 and stay clean.
    """
    import jax

    mesh = partitioner.mesh
    findings: List[Finding] = []
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()) or ())
        nbytes = getattr(leaf, "size", 0) * getattr(
            leaf.dtype, "itemsize", 0
        ) if hasattr(leaf, "dtype") else 0
        if nbytes < min_bytes:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or not sharding.is_fully_replicated:
            continue
        path_str = _leaf_path_str(path)
        if path_prefix:
            path_str = f"{path_prefix}/{path_str}"
        spec = partitioner.spec_for(path_str, shape)
        span = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            span *= math.prod(mesh.shape[a] for a in axes)
        if span <= 1:
            continue  # the rules would replicate it too (or axis is 1)
        findings.append(Finding(
            rule="replicated-large-param",
            where=path_str,
            message=(
                f"{nbytes / 2**20:.1f} MB param is fully replicated but "
                f"partition rules map it to {spec} ({span}-way) — "
                f"replication wastes {(span - 1) * nbytes / 2**20:.1f} MB "
                f"per {span} chips and implies a silent all-gather"
            ),
            config=config,
        ))
    return findings


_ALIAS_ENTRY_RE = re.compile(
    r"\((\d+),\s*\{[^}]*\},\s*(?:may|must)-alias\)"
)


def aliased_parameter_numbers(hlo_text: str) -> Optional[set]:
    """HLO parameter numbers aliased to outputs, from the module header.

    Returns None when the module carries no ``input_output_alias`` field
    at all (distinct from an empty alias set: None means the compiler
    recorded nothing, so every donation was dropped).
    """
    for line in hlo_text.splitlines():
        if line.startswith("HloModule"):
            if "input_output_alias=" not in line:
                return None
            return {int(m) for m in _ALIAS_ENTRY_RE.findall(line)}
    return None


def lint_dropped_donation(
    lowered, compiled, config: Optional[str] = None,
    min_bytes: int = DEFAULT_DONATION_MIN_BYTES,
) -> List[Finding]:
    """Flag donated arguments the executable did not alias to any output.

    Compares the jit's declared donations (``lowered.args_info``) against
    the compiled module's ``input_output_alias`` map. Arguments the jit
    PRUNED (unused) are skipped — an unused donated arg is dead weight,
    not a doubled live buffer — as are leaves under ``min_bytes`` (XLA
    deliberately copies tiny buffers instead of aliasing them).
    """
    import math as _math

    import jax

    def _nbytes(info) -> int:
        shape = tuple(getattr(info, "shape", ()) or ())
        itemsize = getattr(getattr(info, "dtype", None), "itemsize", 4)
        return _math.prod(shape or (1,)) * itemsize

    flat = jax.tree_util.tree_flatten_with_path(lowered.args_info)[0]
    donated = [
        (idx, _leaf_path_str(path))
        for idx, (path, info) in enumerate(flat)
        if getattr(info, "donated", False) and _nbytes(info) >= min_bytes
    ]
    if not donated:
        return []
    executable = getattr(compiled, "_executable", None)
    kept = getattr(executable, "_kept_var_idx", None)
    kept_order = sorted(kept) if kept is not None else None
    aliased = aliased_parameter_numbers(compiled.as_text())
    findings: List[Finding] = []
    for flat_idx, path_str in donated:
        if kept_order is not None:
            if flat_idx not in kept:
                continue  # pruned: never a live buffer
            param_number = kept_order.index(flat_idx)
        else:
            param_number = flat_idx
        if aliased is None or param_number not in aliased:
            info = flat[flat_idx][1]
            shape = tuple(getattr(info, "shape", ()) or ())
            findings.append(Finding(
                rule="dropped-donation",
                where=path_str,
                message=(
                    f"donated argument {shape} was not aliased by the "
                    f"compiled executable — the update materializes next "
                    f"to the old buffer (2x memory for this leaf)"
                ),
                config=config,
            ))
    return findings


def case_jaxpr(case):
    """The (closed) jaxpr of a DryrunCase's train step, traced (not run).

    Requires ``case.trainer.init`` to have happened (``compile_case`` does
    it); traces under the case's mesh so mesh-aware ops resolve.
    """
    import jax

    trainer = case.trainer
    assert trainer.state is not None, "init the case first (compile_case)"
    batch = next(iter(case.loader))
    with case.mesh:
        return jax.make_jaxpr(
            lambda state, b: trainer.train_step(state, b)
        )(trainer.state, batch)


def flagship_numerics_jaxpr():
    """Traced jaxpr of a bf16 flagship-shaped train step for numerics lints.

    The dryrun configs run f32 tiny models (their job is collectives);
    the bf16-upcast lint needs a bf16 path with activations big enough to
    clear ``DEFAULT_UPCAST_MIN_ELEMENTS`` — a scaled-down single-device
    GPT-2 with the fused-CE loss (the ``__graft_entry__.entry`` program's
    shape class) traced in seconds.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.step import build_train_step
    from distributed_pytorch_example_tpu.train.state import TrainState
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = GPT2(
        vocab_size=512, max_len=128, model_dim=256, num_layers=2,
        num_heads=4, mlp_dim=512, dtype=jnp.bfloat16,
        logits_mode="hidden",
    )
    optimizer = optax.adam(1e-3)
    tokens = jnp.zeros((8, 128), jnp.int32)

    def init_fn(rng):
        params = model.init(rng, tokens, train=False)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            model_state={},
            rng=jax.random.key(1),
        )

    state = jax.eval_shape(init_fn, jax.random.key(0))
    step = build_train_step(model, CausalLMTask(), optimizer)
    return jax.make_jaxpr(lambda s, b: step(s, b))(
        state, {"tokens": tokens}
    )
