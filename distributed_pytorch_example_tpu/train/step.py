"""Jit-compiled train/eval steps and sharded state initialization.

The train step is the whole distributed program: forward, backward, gradient
collective, optimizer update. The input state is donated so params and
optimizer moments update in place in HBM.

Gradient-sync modes over the ``data`` axis (the reference's DDP surface,
reference train.py:233,138):

- replicated (default): the gradient all-reduce is inserted by XLA from the
  batch's data-axis sharding — the compiled equivalent of DDP's bucketed
  backward hooks — and every chip runs the full optax update on full
  optimizer state.
- ZeRO-1 (``partitioner.dp_shard_opt_state``): the all-reduce is decomposed
  into reduce-scatter → sharded update → all-gather (Xu et al., arxiv
  2004.13336). Each chip reduce-scatters 1/D of every gradient, updates the
  1/D optimizer-state shard the partitioner's overlay assigns it
  (parallel/api.py ``zero1_overlay``), and the updated params all-gather
  back to replicated. Same wire bytes as a ring all-reduce (RS + AG), but
  weight-update FLOPs and optimizer memory shrink by the data-parallel
  degree D.
- ``grad_accum_steps=N``: microbatch accumulation INSIDE the jitted step —
  a ``lax.scan`` over N microbatches accumulates f32 grads locally and the
  gradient collective fires ONCE per step, after the scan (not once per
  microbatch), so large effective batches pay the sync once.

ZeRO-1 and accumulation share one mechanism: the loss/backward runs in a
``shard_map`` manual over {``data``} (every other mesh axis stays under
automatic GSPMD, so TP rules compose unchanged) and the gradient collective
is an EXPLICIT ``psum_scatter``/``psum``. This is deliberate: relying on
sharding constraints alone lets the partitioner lower the partial-sum →
tiled reshard as all-reduce + dynamic-slice (the CPU backend always does;
TPU needs the ReduceScatterCreator pass to fire), whereas the explicit
collective IS a reduce-scatter in the compiled HLO on every backend.

All gradient collectives route through ``parallel/wire.py``'s ONE
dispatcher, ``sync_grads`` (graft-wire): a ``WireConfig`` threaded from
the partitioner (or passed directly) selects fp32 payloads (default,
byte-identical to the raw ``lax`` collectives) or int8-block compression,
for the ZeRO-1 reduce-scatter AND the plain-DP psum fallback alike — and
``bucket_bytes > 0`` switches the sync to fused size-targeted buckets
issued in reverse trace order so the collectives overlap backward compute
(comm/compute overlap, the DDP-bucketed-hooks analogue). Two graft-lint
rules pin the dispatch: ``wire-raw-collective`` (no raw ``lax.psum*``
here) and ``inline-grad-sync`` (no per-leaf ``wire_psum_scatter`` /
``wire_all_gather`` calls here either — only ``sync_grads``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from distributed_pytorch_example_tpu.parallel.api import Partitioner
from distributed_pytorch_example_tpu.train.state import TrainState


def _make_init_fn(model, optimizer, sample_inputs):
    """The pure TrainState-constructing function shared by ``init_state``
    (which jits it) and ``abstract_state`` (which only eval_shapes it)."""

    def init_fn(rng):
        from distributed_pytorch_example_tpu.train.tasks import (
            dequantize_inputs,
        )

        rng_params, rng_dropout, rng_state = jax.random.split(rng, 3)
        variables = dict(
            model.init(
                {"params": rng_params, "dropout": rng_dropout},
                jax.tree_util.tree_map(dequantize_inputs, sample_inputs),
                train=False,
            )
        )
        params = variables.pop("params")
        variables.pop("losses", None)  # sown aux losses are not model state
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            model_state=variables,
            rng=rng_state,
        )

    return init_fn


def abstract_state(
    model,
    optimizer: optax.GradientTransformation,
    sample_inputs: Any,
) -> Any:
    """ShapeDtypeStruct TrainState — ``eval_shape`` only, ZERO compiles.

    graft-plan's entry point (analysis/planner.py): candidate plans are
    scored from a trace of the step over this abstract state, so the
    planner never touches a backend. ``sample_inputs`` may itself be
    abstract (ShapeDtypeStructs).
    """
    # the sample goes through eval_shape as an ARGUMENT (not a closure
    # capture) so ShapeDtypeStruct samples are abstracted like any tracer
    return jax.eval_shape(
        lambda rng, sample: _make_init_fn(model, optimizer, sample)(rng),
        jax.random.key(0),
        sample_inputs,
    )


def init_state(
    model,
    optimizer: optax.GradientTransformation,
    sample_inputs: Any,
    rng: jax.Array,
    partitioner: Optional[Partitioner] = None,
) -> Tuple[TrainState, Any]:
    """Create a TrainState, placed per the partitioner's rules.

    Initialization runs under jit with ``out_shardings`` derived from the
    partition rules, so large sharded params are *born* sharded — no host
    materialization of the full model (essential for FSDP/TP configs).
    Under ZeRO-1 the optimizer state is likewise born sharded over ``data``
    (the overlay engages on the ``opt_state/...`` paths of the state tree).

    Returns (state, state_shardings) — shardings are reused by the step jit
    and by checkpoint restore.
    """
    init_fn = _make_init_fn(model, optimizer, sample_inputs)
    if partitioner is None:
        return jax.jit(init_fn)(rng), None
    shapes = jax.eval_shape(init_fn, rng)
    shardings = partitioner.tree_shardings(shapes)
    state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def _split_microbatches(batch, n: int):
    """Reshape every batch leaf (B, ...) -> (n, B/n, ...) for the scan."""

    def split(x):
        b = x.shape[0]
        if b % n:
            raise ValueError(
                f"grad_accum_steps={n} must divide the per-data-shard "
                f"batch size {b} (batch leaf shape {x.shape})"
            )
        return x.reshape((n, b // n) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def _mean_metrics(metrics):
    """Mean the scan-stacked (N, ...) per-microbatch metrics."""
    return jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), metrics)


def _pmean_inexact(tree, axis: str):
    """pmean float leaves over ``axis``; pass integral leaves through
    (batch counters are identical on every shard by construction)."""

    def one(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
            return jax.lax.pmean(x, axis)
        return x

    return jax.tree_util.tree_map(one, tree)


def build_train_step(
    model,
    task,
    optimizer: optax.GradientTransformation,
    partitioner: Optional[Partitioner] = None,
    grad_accum_steps: int = 1,
    sentinels: bool = True,
    skip_nonfinite: bool = True,
    wire=None,
):
    """One compiled optimization step: (state, batch) -> (state, metrics).

    ``partitioner`` selects the gradient-sync mode (module docstring); with
    the default replicated mode and ``grad_accum_steps=1`` the compiled
    program is byte-identical to the historical step. ``grad_accum_steps=N``
    scans N microbatches before ONE deferred gradient collective.

    ``wire`` (a ``parallel.wire.WireConfig``; defaults to the
    partitioner's, else fp32) selects the gradient collective's payload.
    ``compress="int8-block"`` forces the data axis manual even without
    ZeRO-1/accumulation — compression needs the explicit collective —
    and ``param_gather`` other than ``"float32"`` swaps the ZeRO-1
    re-replication constraint for the explicit compressed all-gather.

    ``sentinels`` (default on) merges the graft-scope health scalars —
    global grad-norm, param-norm, nonfinite-grad count
    (``telemetry/sentinels.py``) — into the step's metrics dict. They are
    computed inside the compiled program on the post-sync gradients and
    updated params (a few fused reductions; under sharded configs their
    partial-sum all-reduces are part of the committed comm budgets) and
    fetched only at log boundaries, so health monitoring adds no host syncs.

    ``skip_nonfinite`` (default on) is graft-armor's bad-step predication:
    a ``lax.cond`` on the in-step nonfinite-grad count keeps the params /
    optimizer state / model state of a poisoned step UNCHANGED, device-side
    — no host sync, no recompile, the same single executable runs clean and
    poisoned steps. ``step`` and the rng still advance (the trajectory
    moves past the bad batch), and ``metrics["bad_step"]`` records the
    skip so the Trainer can count it against ``max_bad_steps``. The
    predicate reuses the sentinel reduction (XLA CSE), so the cond adds
    compute only, no collectives — the comm budgets are unchanged.
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    from distributed_pytorch_example_tpu.parallel import wire as wirelib

    if wire is None:
        wire = getattr(partitioner, "wire", None) or wirelib.WireConfig()
    zero1 = bool(partitioner is not None and partitioner.dp_shard_opt_state)
    wire_active = wire.compress != "none"
    # All four modes need the data axis MANUAL: ZeRO-1 for the explicit
    # reduce-scatter, accumulation so the per-microbatch backward carries
    # no implicit data collective inside the scan (XLA's while-loop
    # all-reduce motion would have to hoist it; manual mode never emits
    # it), wire compression because only the explicit collective can
    # carry an int8 payload, and bucketing because the fused per-bucket
    # issue order only exists as explicit collectives
    manual_data = partitioner is not None and (
        zero1 or grad_accum_steps > 1 or wire_active or wire.bucketed
    )

    def compute_loss_grads(params, model_state, batch, rng):
        """Local (or global, in automatic mode) grads + metrics + new
        model_state, with the f32 accumulation contract applied."""

        def loss_fn(p):
            loss, metrics, new_ms = task.compute_loss(
                model, p, model_state, batch, rng, train=True
            )
            return loss, (metrics, new_ms)

        grads, (metrics, new_ms) = jax.grad(loss_fn, has_aux=True)(params)
        # f32 island: under a mixed-precision policy microbatch grads can
        # arrive bf16; summing those across microbatches collapses after
        # ~256 increments (8-bit mantissa), so the accumulator contract is
        # cast-then-add (the bf16-accum graft-lint rule guards the pattern)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads
        )
        return grads, metrics, new_ms

    def accumulate_grads(params, model_state, batch, rng):
        """lax.scan over microbatches: f32 grad sum, stacked metrics."""
        micro = _split_microbatches(batch, grad_accum_steps)
        acc0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def scan_body(carry, idx_mb):
            ms, acc = carry
            idx, mb = idx_mb
            g, metrics, ms = compute_loss_grads(
                params, ms, mb, jax.random.fold_in(rng, idx)
            )
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (ms, acc), metrics

        # unroll=N (full): the unrolled scan keeps the accumulate-then-sync
        # structure with no while op inside the data-manual region, at
        # compile time linear in N (N is single-digit)
        (new_ms, grads), metrics = jax.lax.scan(
            scan_body,
            (model_state, acc0),
            (jnp.arange(grad_accum_steps), micro),
            unroll=grad_accum_steps,
        )
        return grads, _mean_metrics(metrics), new_ms

    def manual_grads(params, model_state, batch, rng):
        """Grads via a data-manual shard_map: each shard runs its local
        (micro)batches, then ONE explicit collective per param leaf —
        psum_scatter into the ZeRO-1 layout where the optimizer state is
        sharded, psum where it stays replicated."""
        from jax.sharding import PartitionSpec as P

        mesh = partitioner.mesh
        # every axis name and spec below comes off the partitioner (i.e.
        # the PlanSpec lowering that built it) — the plan-overlay lint rule
        # keeps hand-written axis placements out of this module
        axis = partitioner.grad_sync_axis()
        dsize = mesh.shape.get(axis, 1)
        if zero1:
            dims = partitioner.zero1_dims(params)
        else:
            dims = jax.tree_util.tree_map(lambda _: None, params)
        is_dim_leaf = lambda d: d is None  # noqa: E731 - tree of Optional[int]

        def body(params, model_state, batch, shard_id, rng):
            # per-shard rng: the shard id rides in as the local slice of an
            # arange sharded over 'data'. Decorrelates dropout/MLM masking
            # draws across data shards.
            rng = jax.random.fold_in(rng, shard_id[0])
            if grad_accum_steps > 1:
                grads, metrics, new_ms = accumulate_grads(
                    params, model_state, batch, rng
                )
            else:
                grads, metrics, new_ms = compute_loss_grads(
                    params, model_state, batch, rng
                )

            # the ONE deferred gradient sync per step: local grads are
            # d(local mean loss), so the global mean gradient is
            # psum(...) / (data span * microbatch count). ALL gradient
            # collectives go through sync_grads (the inline-grad-sync
            # lint rule pins this) — per-leaf collectives when
            # bucket_bytes == 0, the fused reverse-trace-order bucket
            # schedule otherwise, payload per the WireConfig either way.
            scale = 1.0 / (dsize * grad_accum_steps)
            wire_rng = (
                jax.random.fold_in(rng, 0x77697265)  # b"wire"
                if wire.stochastic_rounding and wire_active
                else None
            )
            grads = wirelib.sync_grads(
                grads, dims, axis, config=wire, key=wire_rng, scale=scale
            )
            # loss/accuracy become means over the GLOBAL batch (equal
            # shard sizes by the sampler's padding contract — same
            # reduction the replicated path's global mean computes)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m.astype(jnp.float32), axis),
                metrics,
            )
            new_ms = _pmean_inexact(new_ms, axis)
            return grads, metrics, new_ms

        grad_out_specs = jax.tree_util.tree_map(
            lambda dim, g: partitioner.grad_scatter_spec(dim, g.ndim),
            dims, params, is_leaf=is_dim_leaf,
        )
        shard_ids = jnp.arange(max(dsize, 1), dtype=jnp.int32)
        mapped = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(), P(),
                partitioner.manual_batch_spec(),
                partitioner.manual_axis_spec(),
                P(),
            ),
            out_specs=(grad_out_specs, P(), P()),
            axis_names={axis},
            # the body works in per-shard partials and reduces them itself:
            # grads wrt the replicated params must STAY local until
            # sync_grads (the varying-axes type system would psum them in
            # the transpose, and rejects chunked_ce's custom-VJP partial
            # for an unvarying head), and the quantized all-reduce ends in
            # an all-gather whose result is replicated but cannot be typed
            # so through a public API
            check_vma=False,
        )
        return mapped(params, model_state, batch, shard_ids, rng)

    def train_step(state: TrainState, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)

        if manual_data:
            grads, metrics, new_ms = manual_grads(
                state.params, state.model_state, batch, step_rng
            )
        elif grad_accum_steps > 1:
            # no partitioner: automatic-mode accumulation (single-chip or
            # GSPMD-managed; any implied data collective repeats per
            # microbatch — use a partitioner to get the deferred form)
            grads, metrics, new_ms = accumulate_grads(
                state.params, state.model_state, batch, step_rng
            )
            grads = jax.tree_util.tree_map(
                lambda g: g / grad_accum_steps, grads
            )
        else:
            grads, metrics, new_ms = compute_loss_grads(
                state.params, state.model_state, batch, step_rng
            )

        if skip_nonfinite:
            from distributed_pytorch_example_tpu.telemetry.sentinels import (
                nonfinite_count,
            )

            # graft-armor bad-step predication: a poisoned batch (NaN/Inf
            # anywhere in the synced grads) must not touch params, moments,
            # or model state. The predicate is a global reduction over the
            # post-sync grads — identical on every shard, so every process
            # takes the same branch; XLA CSEs it with the sentinel below.
            update_ok = nonfinite_count(grads) == 0

            def apply_update(grads, opt_state, params, ms, _old_ms):
                with jax.named_scope("optimizer"):
                    updates, opt2 = optimizer.update(grads, opt_state, params)
                    return optax.apply_updates(params, updates), opt2, ms

            def skip_update(_grads, opt_state, params, _ms, old_ms):
                return params, opt_state, old_ms

            new_params, new_opt_state, new_ms = jax.lax.cond(
                update_ok, apply_update, skip_update,
                grads, state.opt_state, state.params, new_ms,
                state.model_state,
            )
        else:
            with jax.named_scope("optimizer"):
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
        if zero1:
            # pin the ZeRO-1 layout: the sharded-gradient update must KEEP
            # the moments sharded (a propagation choice to replicate them
            # would silently undo the memory win — the comm-budget gate
            # also watches for this), and the updated params re-replicate
            # over 'data' — this constraint IS the ZeRO-1 all-gather.
            # param_gather other than "float32" swaps the constraint for
            # the explicit lossy gather (opt-in: the gathered buffer is
            # next step's master weights, so compression error there
            # accumulates — parallel/wire.py module docstring)
            if wire.param_gather != "float32":
                new_params = wirelib.replicate_params(
                    new_params, partitioner, wire
                )
            else:
                new_params = jax.lax.with_sharding_constraint(
                    new_params, partitioner.tree_shardings(new_params)
                )
            new_opt_state = jax.lax.with_sharding_constraint(
                new_opt_state,
                partitioner.tree_shardings(
                    new_opt_state, path_prefix="opt_state/"
                ),
            )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            model_state=new_ms,
        )
        if sentinels:
            from distributed_pytorch_example_tpu.telemetry.sentinels import (
                sentinel_metrics,
            )

            # post-sync grads + updated params: global values on every
            # shard, async device scalars until a log-boundary fetch
            metrics = {**metrics, **sentinel_metrics(grads, new_params)}
        if skip_nonfinite:
            # 1.0 exactly on skipped steps; summed host-side against the
            # max_bad_steps budget at log boundaries (train/loop.py)
            metrics = {
                **metrics,
                "bad_step": 1.0 - update_ok.astype(jnp.float32),
            }
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=0)


def build_eval_step(model, task):
    """One compiled eval step: (state, batch, batch_idx) -> metrics.

    Reference parity: ``validate`` under ``model.eval()`` + ``no_grad``
    (train.py:154-175). ``batch_idx`` is folded into the eval rng so tasks
    that draw randomness at eval time (e.g. MLM masking) see a different
    draw per validation batch instead of one repeated pattern.
    """

    def eval_step(state: TrainState, batch, batch_idx=0):
        rng = jax.random.fold_in(state.rng, batch_idx)
        _, metrics, _ = task.compute_loss(
            model, state.params, state.model_state, batch, rng, train=False
        )
        return metrics

    return jax.jit(eval_step)
