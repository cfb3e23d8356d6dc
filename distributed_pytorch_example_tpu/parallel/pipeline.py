"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Beyond-reference capability (SURVEY.md §2: the reference has DP only).
Homogeneous stages — each holding an equal slice of a stack of identical
blocks — live on consecutive devices of the ``pipe`` axis; microbatches
stream through the classic GPipe schedule: at tick ``t`` stage ``s``
processes microbatch ``t - s`` and hands its activation to stage ``s + 1``
via ``lax.ppermute`` (a neighbor ICI transfer). The whole schedule is a
``lax.scan`` inside ``shard_map``, so it is jit-compatible and reverse-mode
differentiable — the backward pass replays the pipeline in reverse with the
transposed permutes, no hand-written adjoint needed.

Memory design (what makes activation memory actually drop with stage
count): the microbatch stack is **sharded over the pipe axis**, never
replicated —

- *input queue*: each stage holds ``m = n_micro / n_stages`` input
  microbatches; the queue rotates one slot toward stage 0 per tick, so
  stage 0 always finds microbatch ``t`` at its queue head at tick ``t``;
- *output delivery ring*: the last stage emits each finished microbatch
  into a one-register-per-device ring that shifts one stage per tick;
  every stage stores the microbatches whose final resting place it is
  (microbatch ``u`` lands on stage ``u // m``), so the outputs come back
  sharded over ``pipe`` exactly like the inputs. No full-batch ``psum``.

The shard_map is *manual over the pipe axis only* (``axis_names={pipe}``):
data/fsdp batch sharding and Megatron tensor parallelism inside the stage
function stay automatic (GSPMD inserts their collectives as usual), so
PP composes with DP / TP / FSDP.

SPMD realities: every device computes at every tick (inactive ticks produce
garbage that is never consumed — the store predicates guarantee only
microbatches a stage produced while active are kept), so utilization is the
usual GPipe ``n_micro / n_ticks``; choose ``n_micro >> n_stages``. Stage
params must be a stacked pytree with leading dim ``n_stages``, and the
stage function must preserve activation shape.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_pytorch_example_tpu.parallel.api import pvary_like

StageFn = Callable[[Any, jax.Array], jax.Array]


def gpipe_ticks(n_micro: int, n_stages: int) -> int:
    """Total schedule ticks: fill/drain plus the delivery-ring tail.

    Every device runs ``stage_fn`` at every tick (SPMD), so useful work is
    ``n_micro`` of ``gpipe_ticks`` per stage — see :func:`bubble_fraction`.
    """
    m = n_micro // n_stages
    return max(n_micro + n_stages - 1, (n_stages - 1) * m + 2 * n_stages - 3)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """Fraction of stage executions that are pipeline bubble (wasted).

    Each microbatch visits each stage exactly once, so of the
    ``gpipe_ticks * n_stages`` stage invocations only
    ``n_micro * n_stages`` are useful: bubble = 1 - n_micro / ticks.
    The classic GPipe trade — shrink it by raising ``n_micro`` (at the
    dryrun's 4-microbatch/2-stage shape the bubble is 20%; at 16/2 it is
    5.9%). Asserted against the schedule in tests/test_pipeline.py.
    """
    return 1.0 - n_micro / gpipe_ticks(n_micro, n_stages)


def _store(buf, y, slot, cond):
    """buf[slot] = y where cond (traced slot index, predicate scalar)."""
    updated = lax.dynamic_update_index_in_dim(
        buf, y.astype(buf.dtype), jnp.clip(slot, 0, buf.shape[0] - 1), 0
    )
    return jnp.where(cond, updated, buf)


def _gpipe_local(stage_params, in_buf, *, stage_fn: StageFn, axis_name: str,
                 n_micro: int, aux_init: Any = None):
    """Per-device pipeline program; call under shard_map (manual on pipe).

    stage_params: local slice (1, ...) of the stage-stacked params.
    in_buf: (m, microbatch, ...) — this stage's shard of the microbatch
    queue (stage d initially holds microbatches [d*m, (d+1)*m)).

    ``aux_init``: when given (a pytree of f32 scalar zeros), ``stage_fn``
    returns ``(h, aux)`` and the schedule accumulates aux ONLY for useful
    ticks — every device computes at every tick (SPMD), and a bubble
    tick's garbage routing must not pollute e.g. MoE load-balancing
    losses. Stage s's tick t processes microbatch t - s, which is real
    iff 0 <= t - s < n_micro. The per-stage sums are psum'd over the pipe
    axis, so the returned aux is the total over all (layer, microbatch)
    contributions.
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = in_buf.shape[0]
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    shift_up = [(i, i + 1) for i in range(n_stages - 1)]  # activations
    ring_down = [(i, (i - 1) % n_stages) for i in range(n_stages)]  # inputs
    ring_up = [(i, (i + 1) % n_stages) for i in range(n_stages)]  # delivery

    # ticks: last stage emits microbatch u at tick u + n_stages - 1; a ring
    # delivery to stage d takes d more ticks (stage n_stages-1 self-stores
    # its own block at emission). The last ring-delivered block is block
    # n_stages-2, finished at (n_stages-1)*m - 1 + (n_stages-1) + (n_stages-2).
    n_ticks = gpipe_ticks(n_micro, n_stages)

    def tick(carry, t):
        incoming, in_buf, out_buf, reg_y, reg_u, aux_acc = carry

        # stage 0 feeds from its queue head; later stages from upstream.
        # The queue is circular (head slot = t % m): the head is ppermuted
        # toward stage 0 and the received slot written back in place —
        # one microbatch of traffic per tick, not a full-queue copy.
        head_slot = t % m
        head = lax.dynamic_index_in_dim(in_buf, head_slot, 0, keepdims=False)
        x_in = jnp.where(stage == 0, head, incoming)
        if aux_init is None:
            y = stage_fn(params, x_in)
        else:
            y, aux_tick = stage_fn(params, x_in)
            u_proc = t - stage
            useful = (u_proc >= 0) & (u_proc < n_micro)
            aux_acc = jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(useful, b, 0.0),
                aux_acc, aux_tick,
            )

        u_emit = t - (n_stages - 1)  # microbatch the last stage finishes now
        emitting = (u_emit >= 0) & (u_emit < n_micro)
        is_last = stage == n_stages - 1
        # the last stage's own block ([n_micro-m, n_micro)) never rides the
        # ring: store it directly at emission
        out_buf = _store(
            out_buf, y, u_emit % m,
            is_last & emitting & (u_emit // m == stage),
        )

        # delivery ring: the last stage replaces the register with its fresh
        # output (nothing routes *through* the last stage — ring targets are
        # stages 0..n_stages-2, reached going up from the wrap to stage 0);
        # other stages relay what they hold
        send_y = jnp.where(is_last, y, reg_y)
        send_u = jnp.where(is_last, jnp.where(emitting, u_emit, -1), reg_u)
        reg_y = lax.ppermute(send_y, axis_name, ring_up)
        reg_u = lax.ppermute(send_u, axis_name, ring_up)
        out_buf = _store(
            out_buf, reg_y, reg_u % m,
            (reg_u >= 0) & (reg_u // m == stage) & ~is_last,
        )

        # inter-stage activation handoff
        if n_stages > 1:
            incoming = lax.ppermute(y, axis_name, shift_up)
        # input queue rotation: the consumed head slot refills from the
        # upstream device, so stage 0's next head holds microbatch t+1
        received = lax.ppermute(head, axis_name, ring_down)
        in_buf = lax.dynamic_update_index_in_dim(
            in_buf, received, head_slot, 0
        )
        return (incoming, in_buf, out_buf, reg_y, reg_u, aux_acc), None

    # carries become pipe-varying through the stage params / ppermute, so
    # constant inits must carry that vma too
    def pv(x):
        return pvary_like(x, in_buf, (axis_name,))

    incoming0 = pv(jnp.zeros(in_buf.shape[1:], in_buf.dtype))
    outputs0 = pv(jnp.zeros_like(in_buf))
    reg_y0 = pv(jnp.zeros(in_buf.shape[1:], in_buf.dtype))
    reg_u0 = pv(jnp.full((), -1, jnp.int32))
    aux0 = None if aux_init is None else pv(aux_init)
    (_, _, out_buf, _, _, aux_acc), _ = lax.scan(
        tick, (incoming0, in_buf, outputs0, reg_y0, reg_u0, aux0),
        jnp.arange(n_ticks),
    )
    if aux_init is None:
        return out_buf
    aux_total = jax.tree_util.tree_map(
        lambda a: lax.psum(a, axis_name), aux_acc
    )
    return out_buf, aux_total


def gpipe(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    n_micro: int,
    *,
    pipe_axis: str = "pipe",
    batch_axes: Sequence[str] = ("data", "fsdp"),
    aux_init: Any = None,
    seq_axis: Optional[str] = None,
) -> jax.Array:
    """Run ``x`` through ``n_stages`` pipelined stages of ``stage_fn``.

    Args:
      stage_fn: ``(stage_param_slice, activation) -> activation`` — shape
        preserving (homogeneous stages). With ``aux_init`` set it returns
        ``(activation, aux)`` instead.
      stage_params: pytree whose leaves are stacked on a leading
        ``n_stages`` dim; sharded over ``pipe_axis`` (one stage per device).
        Shardings over other mesh axes (e.g. ``tensor``) stay automatic.
      x: global batch (batch, ...); split into ``n_micro`` microbatches on
        the leading dim (``n_micro`` must divide the batch and be a
        multiple of the pipe-axis size).
      mesh: mesh containing ``pipe_axis`` (and optionally data axes the
        batch dim is sharded over).
      aux_init: optional pytree of f32 scalar zeros matching the aux
        structure ``stage_fn`` emits per microbatch (e.g. MoE auxiliary
        losses). Bubble-tick garbage is excluded; the returned aux is the
        SUM over every (stage layer, microbatch) contribution — divide by
        ``n_micro`` for per-batch means.
      seq_axis: SP x PP composition — when the mesh spans this axis, the
        schedule's shard_map goes manual over {pipe, seq} and ``stage_fn``
        receives SEQUENCE-LOCAL activation chunks (dim 2 sharded over
        ``seq_axis``); its attention must then run the chunk-local SP
        collectives (ring/Ulysses with ``axis_name=seq_axis``) itself.
        One flat manual region, no nested shard_map — differentiating
        through nested shard_maps whose bodies hold custom VJPs mis-builds
        residual shardings (duplicate-axis PartitionSpecs).

    Returns activations of the final stage, same shape as ``x``; with
    ``aux_init``, the tuple ``(activations, aux_totals)``.
    """
    batch = x.shape[0]
    n_stages = mesh.shape[pipe_axis]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    if n_micro % n_stages:
        raise ValueError(
            f"n_micro {n_micro} not divisible by pipe size {n_stages}"
        )
    seq = seq_axis if (seq_axis and mesh.shape.get(seq_axis, 1) > 1) else None
    if seq is not None and x.ndim < 3:
        raise ValueError(
            f"seq_axis={seq!r} needs (batch, seq, ...) activations, got "
            f"rank {x.ndim}"
        )
    if seq is not None and aux_init is not None:
        raise NotImplementedError(
            "aux accumulation (MoE) does not compose with seq_axis inside "
            "the pipeline; drop one (the models reject PP x SP x EP)"
        )
    x_stack = x.reshape(n_micro, batch // n_micro, *x.shape[1:])
    # the microbatch queue lives sharded over the pipe axis (dim 0); the
    # per-microbatch batch dim keeps the usual data sharding (dim 1), and
    # under SP x PP the sequence dim (dim 2) is manual over seq_axis
    data = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    # two specs: the GSPMD constraint may mention auto axes (data), the
    # shard_map specs may only mention the MANUAL axes (pipe, seq)
    queue_spec = P(pipe_axis, data or None, seq)
    smap_spec = P(pipe_axis) if seq is None else P(pipe_axis, None, seq)
    x_stack = lax.with_sharding_constraint(
        x_stack, NamedSharding(mesh, queue_spec)
    )

    fn = jax.shard_map(
        functools.partial(
            _gpipe_local, stage_fn=stage_fn, axis_name=pipe_axis,
            n_micro=n_micro, aux_init=aux_init,
        ),
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(pipe_axis), stage_params),
            smap_spec,
        ),
        # aux is psum'd over the pipe axis inside: replicated on the way out
        out_specs=smap_spec if aux_init is None else (
            smap_spec,
            jax.tree_util.tree_map(lambda _: P(), aux_init),
        ),
        axis_names={pipe_axis} | ({seq} if seq else set()),
    )

    # pin the output queue to the input queue's spec: without this, GSPMD
    # may propagate a downstream consumer's compound batch sharding onto
    # the microbatch dim, which collides with the pipe-sharded dim 0
    # inside the schedule's scan
    def pin(o):
        return lax.with_sharding_constraint(
            o, NamedSharding(mesh, queue_spec)
        )

    if aux_init is None:
        out = pin(fn(stage_params, x_stack))
        return out.reshape(x.shape)
    out, aux = fn(stage_params, x_stack)
    return pin(out).reshape(x.shape), aux


def stack_stage_params(per_stage_params: Sequence[Any]) -> Any:
    """Stack per-stage param pytrees into the leading-stage-dim layout."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


# ---------------------------------------------------------------------------
# 1F1B (one-forward-one-backward) schedule
# ---------------------------------------------------------------------------
#
# GPipe above runs ALL forwards, then differentiates the scan in reverse —
# so every tick's stage internals are saved as autodiff residuals and peak
# activation memory grows with n_micro while the bubble only shrinks with
# it. 1F1B (PipeDream-flush / Megatron-LM's production schedule) interleaves
# each microbatch's backward as soon as its forward reaches the last stage,
# which bounds in-flight activations at ~n_stages microbatches REGARDLESS of
# n_micro. The price of interleaving: the loss must be computable per
# microbatch INSIDE the schedule (the last stage needs the loss gradient of
# microbatch u in the same cycle it finishes u's forward), so this entry
# point takes the model tail — final norm + head + loss — as ``last_fn``
# instead of returning activations for an outer loss.
#
# Lockstep SPMD formulation: one ``lax.scan`` over cycles inside a
# shard_map manual on the pipe axis; in cycle c every stage s runs
#
#   F sub-tick:  forward  of microbatch u_F = c - s
#   B sub-tick:  backward of microbatch u_B = c - 2(S-1) + s
#
# (both predicated on 0 <= u < n_micro; inactive sub-ticks compute garbage
# that is never stored — the usual SPMD pipeline deal). At the last stage
# u_F == u_B: its F computes per-microbatch loss + dL/dy via
# ``jax.value_and_grad`` over ``last_fn`` and its B consumes that seed in
# the same cycle — this is what makes the schedule 1F1B rather than
# all-F-then-all-B. Backwards are per-microbatch ``jax.vjp``, in one of two
# selectable modes (``recompute``):
#
# - ``recompute=True`` (Megatron's selective recompute): the only thing a
#   stage keeps per in-flight microbatch is its INPUT, in a ring of
#   ``2(S-1)+1`` slots, and B replays the stage forward to rebuild the vjp
#   — cheapest memory, cycle cost ~4 forward-units.
# - ``recompute=False`` (activation stash, production Megatron's default):
#   F runs the stage UNDER ``jax.vjp`` and stashes the residual
#   intermediates in per-leaf rings of the same ``2(S-1)+1`` depth; B
#   restores the saved vjp and applies it — no replay, cycle cost ~3
#   forward-units. Residual leaves that are verbatim stage params (the
#   transpose's weight operands) are NOT ringed: params are constant
#   within a step, so B substitutes the live leaves; the stage-input leaf
#   rides the existing input ring. Peak stash stays independent of
#   n_micro in both modes — the ~n_micro -> ~n_stages drop measured in
#   scripts/pipeline_memory.py.
#
# Communication per cycle (all neighbor ICI): activations ppermute up,
# cotangents ppermute down, the input queue rotates toward stage 0 (as in
# GPipe), and finished dx microbatches ride a delivery ring up from stage 0
# so dL/dx leaves sharded over pipe exactly like the input queue came in.
#
# Wall-clock (measured frontier: results/pipeline_1f1b/ — temp MB and
# stage-equivalent cycle cost for GPipe / 1F1B-recompute / 1F1B-stash at
# m=32): a recompute cycle costs ~4 forward-units and a stash cycle ~3
# over n_micro + 3(S-1) cycles, vs GPipe-without-remat's ~3 units x
# (n_micro + S - 1) ticks. So 1F1B-stash matches no-remat GPipe's compute
# asymptotically while keeping the n_micro-INDEPENDENT activation
# footprint, and 1F1B-recompute trades ~33% more compute for the smallest
# stash of all — pick by which side of the speed-memory frontier binds.
# The head cost is predicated away: only the last stage evaluates
# ``last_fn`` (``predicate_head``, a per-device ``lax.cond`` — legal
# because ``last_fn`` is collective-free by contract; measured in
# results/pipeline_1f1b/head_cost.json).
#
# Differentiation contract: ``one_f_one_b`` is wrapped in jax.custom_vjp
# whose FORWARD pass runs the schedule and computes the parameter/input
# gradients eagerly (that is the point of 1F1B); the residuals ARE the
# gradients, and the backward pass just scales them by the incoming loss
# cotangent. Consequently the aux-loss outputs (MoE balancing losses) are
# REPORTING-ONLY values: their gradient contribution is seeded inside the
# schedule via ``aux_weights`` (the fixed coefficients the trainer would
# multiply them by), and cotangents arriving on the aux/metric outputs are
# ignored — do not scale aux losses outside by anything but their declared
# weights.


def one_f_one_b_cycles(n_micro: int, n_stages: int,
                       n_virtual: int = 1) -> int:
    """Total schedule cycles (chunk-granularity when ``n_virtual > 1``).

    Wave formulation (see the interleaving note in the module comment):
    microbatches run in waves of ``n_stages``; wave w slot r's forward of
    chunk c fires at cycle ``w*V + r + c`` and its backward at
    ``w*V + r + 2(V-1) - c`` where ``V = n_stages * n_virtual`` (both maps
    are conflict-free per device). The last backward (wave W-1, slot S-1,
    chunk 0) lands at ``(W-1)V + S-1 + 2(V-1)``; the dx delivery ring adds
    ``S-1`` more. At ``n_virtual=1`` this reduces exactly to the classic
    ``n_micro + 3(n_stages-1)``, which is returned for ANY ``n_micro``
    (the non-interleaved 1F1B count needs no whole waves; keeping the
    formula total preserves its long-standing public behavior) — only the
    interleaved schedule (``n_virtual > 1``) structurally requires
    ``n_micro % n_stages == 0`` and raises otherwise.
    """
    if n_virtual == 1:
        return n_micro + 3 * (n_stages - 1)
    if n_micro % n_stages:
        raise ValueError(
            f"n_micro {n_micro} not divisible by n_stages {n_stages} — the "
            f"interleaved (n_virtual={n_virtual}) wave schedule requires "
            "whole waves"
        )
    V = n_stages * n_virtual
    waves = n_micro // n_stages
    return (waves - 1) * V + 2 * n_stages + 2 * V - 3


def one_f_one_b_stash_slots(n_stages: int, n_virtual: int = 1) -> int:
    """Stage-input stash ring size: the F->B age of chunk c's input is
    ``2(V-1-c)`` cycles, maximal at chunk 0 — one live slot more. Grows
    with ``n_virtual`` (x ~v more in-flight chunk inputs): the interleaved
    schedule's known memory-for-bubble trade."""
    return 2 * (n_stages * n_virtual - 1) + 1


def one_f_one_b_bubble(n_micro: int, n_stages: int,
                       n_virtual: int = 1) -> float:
    """Fraction of cycles that are fill/drain bubble (per sub-tick).

    Each device runs one chunk-forward (+ one chunk-backward) per cycle
    and owes ``n_micro * n_virtual`` of each; with cycles only ~1/v the
    length, interleaving shrinks the bubble TIME by ~v while the fraction
    formula stays comparable.
    """
    return 1.0 - (n_micro * n_virtual) / one_f_one_b_cycles(
        n_micro, n_stages, n_virtual
    )


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b
    )


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _zeros_of(struct):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), struct
    )


def _1f1b_local(stage_params, last_params, in_buf, last_args, *,
                stage_fn: StageFn, last_fn, axis_name: str, n_micro: int,
                aux_desc, seq_axis=None, n_virtual: int = 1,
                recompute: bool = True, predicate_head: bool = True):
    """Per-device 1F1B program; call under shard_map (manual on pipe).

    in_buf: (m_s, microbatch, ...) — this stage's shard of the input queue
    (same layout/rotation as the GPipe queue: stage 0's head holds
    microbatch c at cycle c). last_args: (n_micro, ...) per-microbatch
    arguments for ``last_fn`` (e.g. target tokens), replicated over pipe.

    ``seq_axis`` — SP x PP x 1F1B: the shard_map is ALSO manual over this
    axis; activations/last_args arrive sequence-chunked (``stage_fn`` runs
    the chunk-local ring/Ulysses collectives itself, ``last_fn`` must be
    chunk-local — see one_f_one_b). Stage/tail params are replicated over
    seq, so their per-chunk partial gradients (and the chunk-partial
    loss/metric sums) are psum'd over ``seq_axis`` on the way out.

    ``n_virtual`` — Megatron-style interleaved schedule: each device owns
    ``v`` non-contiguous model chunks (chunk ``c = j*S + d`` on device
    ``d``, ``stage_params`` leaves ``(1, v, layers/chunk, ...)`` locally);
    microbatches run in WAVES of S. Closed-form conflict-free cycle maps
    (wave w, slot r in [0,S), chunk c, V = S*v):

      forward  of (w, r, c) at cycle  w*V + r + c
      backward of (w, r, c) at cycle  w*V + r + 2(V-1) - c

    Per device+cycle both maps select at most one chunk each — invert via
    ``(t - d) mod V`` (forward) / ``(t + d - 2(V-1))`` decomposition
    (backward). Activations/cotangents ride FULL rings (the d = S-1 -> 0
    wrap carries chunk jS+S-1 -> (j+1)S handoffs); the input queue rotates
    only on chunk-0 injection cycles (``t mod V < S``). At ``v = 1``
    every map, ring, and buffer reduces exactly to the classic 1F1B
    program (same cycle count, same stash ring), so the non-interleaved
    tests pin this program's degenerate case. The trade (see
    one_f_one_b_stash_slots): bubble TIME shrinks ~v, input stash grows
    ~v, activation ring traffic grows ~v, and every device still pays one
    ``last_fn`` eval per cycle (now ~v times more cycles of ~1/v the
    stage work) — pick v so layers/chunk stays >> the head cost. Param
    placement: the strided assignment (layer l on device (l//Lc) mod S)
    is not expressible as a dim-0 NamedSharding over the logical layer
    order, so with the partitioner's contiguous pipe blocks GSPMD inserts
    ONE param-tree reshard per step ahead of the schedule — amortized
    over all microbatches, and measured in scripts/pipeline_memory.py
    (the v=2 rows carry it); storing master params chunk-permuted would
    remove it at the cost of placement-dependent checkpoints.

    Returns (loss_sum, metric_sums, aux_sums, d_stage(1, ...), d_last,
    dx_buf) — loss/metrics/aux psum'd over pipe (and seq); d_stage/dx stay
    sharded over pipe (d_stage seq-reduced, dx seq-chunked).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    is_last = stage == n_stages - 1
    is_first = stage == 0
    m_s = in_buf.shape[0]
    V = n_stages * n_virtual
    K = one_f_one_b_stash_slots(n_stages, n_virtual)
    n_cycles = one_f_one_b_cycles(n_micro, n_stages, n_virtual)
    # v=1: chunks is THE stage's params (layers, ...); v>1: (v, layers/chunk,
    # ...) with the device's j-th virtual chunk selected per cycle
    chunks = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    # last_params arrive pipe-UNVARYING (replicated); differentiating a
    # varying loss wrt an unvarying value makes the transpose psum the
    # cotangent over pipe — which would fold other stages' masked-out
    # garbage evaluations into every dlast_u. Stamp them varying so grads
    # stay per-device until the explicit masked psum at the end. Under
    # seq_axis the same applies to the STAGE params on the seq axis (they
    # arrive seq-unvarying): without the stamp every per-cycle vjp would
    # auto-psum its cotangent over seq — double-counting against the end
    # psum AND paying a collective per cycle instead of one at the end.
    chunks = pvary_like(chunks, in_buf, (axis_name,))
    last_params = pvary_like(last_params, in_buf, (axis_name,))

    if n_virtual == 1:
        pick = lambda j: chunks
    else:
        def pick(j):
            return jax.tree_util.tree_map(
                lambda p: lax.dynamic_index_in_dim(p, j, 0, keepdims=False),
                chunks,
            )

    if aux_desc is None:
        aux_zero = aux_weights = None
    else:
        treedef, weights = aux_desc
        leaves = [jnp.float32(w) for w in weights]
        aux_weights = jax.tree_util.tree_unflatten(treedef, leaves)
        aux_zero = pvary_like(
            jax.tree_util.tree_map(jnp.zeros_like, aux_weights), in_buf,
            (axis_name,),
        )

    # FULL rings: the wrap links carry the interleaved chunk handoffs
    # (chunk jS+S-1 on device S-1 -> chunk (j+1)S on device 0 for
    # activations, and the reverse for cotangents); at v=1 the wrapped
    # values are never consumed (chunk-0 reads the queue, chunk V-1 seeds
    # from dy) so the classic schedule is unchanged.
    ring_down = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    ring_up = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    mb_shape, mb_dtype = in_buf.shape[1:], in_buf.dtype

    def slice_args(u):
        cu = jnp.clip(u, 0, n_micro - 1)
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, cu, 0, keepdims=False),
            last_args,
        )

    def last_loss(y, lp, a):
        return last_fn(lp, y, a)

    # metric accumulator structure, discovered abstractly
    y_proto = jax.ShapeDtypeStruct(mb_shape, mb_dtype)
    _, mets_struct = jax.eval_shape(
        last_loss, y_proto, last_params, slice_args(jnp.int32(0))
    )
    # full head output structure ((loss, metrics), (dy, dlast)) for the
    # last-stage predication's skip branch
    head_struct = jax.eval_shape(
        lambda y_: jax.value_and_grad(
            last_loss, argnums=(0, 1), has_aux=True
        )(y_, last_params, slice_args(jnp.int32(0))),
        y_proto,
    )

    def pv(x):
        return pvary_like(x, in_buf, (axis_name,))

    if recompute:
        res_src = res_structs = None
    else:
        # Trace the stage forward + vjp ONCE into a jaxpr whose outputs are
        # (stage outputs, vjp residual leaves), and run THAT jaxpr at every
        # F sub-tick: the residual list's order then cannot depend on the
        # tracing context (jax.vjp called directly inside the scan body
        # orders closed-over params differently than a standalone trace
        # does). The jaxpr also classifies the leaves exactly: a residual
        # outvar that IS an invar is a forwarded input — a stage param
        # (restored at B time from the LIVE params, constant within a
        # step) or the stage input (rides the existing input ring); every
        # other leaf — the true forward intermediates — gets its own
        # K-slot ring in the scan carry. Traced on a REAL (pipe-varying)
        # microbatch, not an abstract prototype, so the avals carry the
        # schedule's varying-axes types.
        from jax.extend import core as jex_core

        probe: dict = {}

        def _fwd_res(p, x_):
            out, vjp_fn = jax.vjp(stage_fn, p, x_)
            leaves, probe["vjp_treedef"] = jax.tree_util.tree_flatten(vjp_fn)
            outs, probe["out_treedef"] = jax.tree_util.tree_flatten(out)
            probe["n_out"] = len(outs)
            return outs + leaves

        p0 = pick(0)
        fwd_res = jax.make_jaxpr(_fwd_res)(p0, in_buf[0])
        n_out, vjp_treedef = probe["n_out"], probe["vjp_treedef"]
        n_params = len(jax.tree_util.tree_leaves(p0))
        in_pos = {v: i for i, v in enumerate(fwd_res.jaxpr.invars)}
        res_vars = fwd_res.jaxpr.outvars[n_out:]

        def _src(v):
            i = in_pos.get(v) if isinstance(v, jex_core.Var) else None
            if i is None:
                return ("ring", None)
            return ("param", i) if i < n_params else ("x", None)

        res_src = tuple(_src(v) for v in res_vars)
        res_structs = tuple(
            jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v, (kind, _) in zip(res_vars, res_src) if kind == "ring"
        )

    def cycle(carry, t):
        (incoming, cot_in, in_buf, stash, res_rings, dx_buf, reg_dx, reg_du,
         d_stage, d_last, loss_acc, mets_acc, aux_acc) = carry

        # ---- F sub-tick: invert t = w*V + r + j*S + stage ----
        phase = t - stage
        pm = jnp.mod(phase, V)
        w_f = (phase - pm) // V
        r_f = jnp.mod(pm, n_stages)
        j_f = pm // n_stages
        u_f = w_f * n_stages + r_f
        active_f = (u_f >= 0) & (u_f < n_micro)
        first_chunk_f = is_first & (j_f == 0)
        last_chunk_f = is_last & (j_f == n_virtual - 1)

        # input queue: rotates one microbatch toward stage 0 per chunk-0
        # injection cycle (t mod V < S; at v=1 that is every cycle), so
        # device 0's head holds microbatch inj(t) whenever it runs a
        # chunk-0 forward
        rot = jnp.mod(t, V) < n_stages
        inj = n_stages * (t // V) + jnp.minimum(jnp.mod(t, V), n_stages)
        head_slot = jnp.mod(inj, m_s)
        head = lax.dynamic_index_in_dim(in_buf, head_slot, 0, keepdims=False)
        x_in = jnp.where(first_chunk_f, head, incoming)
        stash = _store(stash, x_in, jnp.mod(t, K), active_f)
        params_f = pick(j_f)
        aux_tick = None
        if recompute:
            if aux_desc is None:
                y = stage_fn(params_f, x_in)
            else:
                y, aux_tick = stage_fn(params_f, x_in)
        else:
            # capture this forward's vjp; its residual intermediates ride
            # per-leaf rings to the matching B sub-tick (no stage replay)
            flat = jax.core.eval_jaxpr(
                fwd_res.jaxpr, fwd_res.consts,
                *jax.tree_util.tree_leaves(params_f), x_in,
            )
            out = jax.tree_util.tree_unflatten(
                probe["out_treedef"], flat[:n_out]
            )
            y, aux_tick = out if aux_desc is not None else (out, None)
            leaves_f = flat[n_out:]
            ringed_f = tuple(
                l for l, (kind, _) in zip(leaves_f, res_src)
                if kind == "ring"
            )
            res_rings = tuple(
                _store(r, l, jnp.mod(t, K), active_f)
                for r, l in zip(res_rings, ringed_f)
            )
        if aux_desc is not None:
            aux_acc = _tree_add(
                aux_acc, _tree_where(active_f, aux_tick, aux_zero)
            )

        # last chunk: per-microbatch loss, metrics, and the backward seed.
        # Only evaluated where the result is KEPT (``predicate_head``):
        # ``last_fn`` is collective-free by contract, so the per-device
        # ``lax.cond`` is legal SPMD and the other S-1 stages (and the
        # fill/drain bubble cycles) skip the head's cost instead of
        # computing a masked-out loss every cycle — measured in
        # results/pipeline_1f1b/head_cost.json.
        keep = last_chunk_f & active_f

        def _head_eval(y_, args_u):
            return jax.value_and_grad(
                last_loss, argnums=(0, 1), has_aux=True
            )(y_, last_params, args_u)

        # the microbatch's last_args are sliced OUTSIDE the cond: under a
        # partial-auto shard_map GSPMD may have to re-lay them out over
        # the auto (data) axes, and a collective-permute inside a branch
        # that only the last stage takes never completes its rendezvous
        args_u = slice_args(u_f)
        if predicate_head:
            (loss_u, mets_u), (dy_u, dlast_u) = lax.cond(
                keep,
                _head_eval,
                lambda y_, _: jax.tree_util.tree_map(
                    lambda s: pv(jnp.zeros(s.shape, s.dtype)), head_struct
                ),
                y, args_u,
            )
        else:
            (loss_u, mets_u), (dy_u, dlast_u) = _head_eval(y, args_u)
        loss_acc = loss_acc + jnp.where(keep, loss_u, 0.0)
        mets_acc = _tree_add(
            mets_acc, _tree_where(keep, mets_u, _zeros_of(mets_struct))
        )
        d_last = _tree_add(
            d_last,
            _tree_where(
                keep, dlast_u,
                jax.tree_util.tree_map(jnp.zeros_like, dlast_u),
            ),
        )

        # ---- B sub-tick: invert t = w*V + r + 2(V-1) - (j*S + stage) ----
        q = t + stage - 2 * (V - 1)
        r_b = jnp.mod(q, n_stages)
        s2 = (q - r_b) // n_stages  # = w*v - j
        j_b = jnp.mod(-s2, n_virtual)
        w_b = (s2 + j_b) // n_virtual
        u_b = w_b * n_stages + r_b
        active_b = (u_b >= 0) & (u_b < n_micro)
        c_b = j_b * n_stages + stage
        first_chunk_b = is_first & (j_b == 0)
        last_chunk_b = is_last & (j_b == n_virtual - 1)
        # this B's matching F ran 2(V-1-c_b) cycles ago (same-cycle for
        # chunk V-1, whose dy seed is the one just computed above)
        slot_b = jnp.mod(t - 2 * (V - 1) + 2 * c_b, K)
        x_saved = lax.dynamic_index_in_dim(stash, slot_b, 0, keepdims=False)
        cot = jnp.where(last_chunk_b, dy_u, cot_in)
        params_b = pick(j_b)
        if recompute:
            with jax.named_scope("1f1b_recompute_apply"):
                if aux_desc is None:
                    _, vjp_fn = jax.vjp(stage_fn, params_b, x_saved)
                    dparams_u, dx_u = vjp_fn(cot)
                else:
                    (_, aux_primal), vjp_fn = jax.vjp(
                        stage_fn, params_b, x_saved
                    )
                    # each weight seed must carry exactly its aux output's
                    # varying-manual-axes type (a constant aux stays
                    # unvarying)
                    aux_ct = jax.tree_util.tree_map(
                        lambda w, a: pvary_like(w, a, ()), aux_weights,
                        aux_primal,
                    )
                    dparams_u, dx_u = vjp_fn((cot, aux_ct))
        else:
            with jax.named_scope("1f1b_stash_apply"):
                # restore the saved vjp: live param leaves + the stashed
                # input + the ringed intermediates, rebuilt with THIS
                # trace's treedef (the transpose program is identical
                # every cycle; only the residual values differ)
                p_leaves = jax.tree_util.tree_leaves(params_b)
                ring_read = iter(
                    lax.dynamic_index_in_dim(r, slot_b, 0, keepdims=False)
                    for r in res_rings
                )
                restored = [
                    p_leaves[i] if kind == "param"
                    else x_saved if kind == "x"
                    else next(ring_read)
                    for kind, i in res_src
                ]
                vjp_saved = jax.tree_util.tree_unflatten(
                    vjp_treedef, restored
                )
                if aux_desc is None:
                    dparams_u, dx_u = vjp_saved(cot)
                else:
                    aux_ct = jax.tree_util.tree_map(
                        lambda w, a: pvary_like(w, a, ()), aux_weights,
                        aux_tick,
                    )
                    dparams_u, dx_u = vjp_saved((cot, aux_ct))
        if n_virtual == 1:
            d_stage = _tree_add(
                d_stage,
                _tree_where(
                    active_b, dparams_u,
                    jax.tree_util.tree_map(jnp.zeros_like, dparams_u),
                ),
            )
        else:
            d_stage = jax.tree_util.tree_map(
                lambda acc, g: lax.dynamic_update_index_in_dim(
                    acc,
                    lax.dynamic_index_in_dim(acc, j_b, 0, keepdims=False)
                    + jnp.where(active_b, g, jnp.zeros_like(g)),
                    j_b, 0,
                ),
                d_stage, dparams_u,
            )

        # chunk 0's dx (device 0) is final: self-store its own block, ring
        # the rest up; on j_b>0 cycles device 0 relays like everyone else
        # (stale wrapped entries re-store idempotently at their owner)
        dx_final = first_chunk_b & active_b
        dx_buf = _store(dx_buf, dx_u, u_b % m_s, dx_final & (u_b // m_s == 0))
        send_dx = jnp.where(first_chunk_b, dx_u, reg_dx)
        send_du = jnp.where(
            first_chunk_b, jnp.where(active_b, u_b, -1), reg_du
        )
        reg_dx = lax.ppermute(send_dx, axis_name, ring_up)
        reg_du = lax.ppermute(send_du, axis_name, ring_up)
        dx_buf = _store(
            dx_buf, reg_dx, reg_du % m_s,
            (reg_du >= 0) & (reg_du // m_s == stage) & ~is_first,
        )

        # ---- ring comms for the next cycle ----
        if n_stages > 1:
            incoming = lax.ppermute(y, axis_name, ring_up)
            cot_in = lax.ppermute(dx_u, axis_name, ring_down)
        # only S of every V cycles rotate the input ring (all of them at
        # n_virtual == 1). The ppermute is issued UNCONDITIONALLY: every
        # device must run the same collectives in the same order, and a
        # ppermute under ``lax.cond`` is unordered against the permutes
        # above (XLA:CPU deadlocks at the rendezvous). Non-rotating cycles
        # write ``head`` back over itself instead.
        received = lax.ppermute(head, axis_name, ring_down)
        in_buf = lax.dynamic_update_index_in_dim(
            in_buf, jnp.where(rot, received, head), head_slot, 0
        )
        return (incoming, cot_in, in_buf, stash, res_rings, dx_buf, reg_dx,
                reg_du, d_stage, d_last, loss_acc, mets_acc, aux_acc), None

    carry0 = (
        pv(jnp.zeros(mb_shape, mb_dtype)),          # incoming activation
        pv(jnp.zeros(mb_shape, mb_dtype)),          # incoming cotangent
        in_buf,
        pv(jnp.zeros((K, *mb_shape), mb_dtype)),    # input stash ring
        () if recompute else tuple(                 # vjp-residual rings
            pv(jnp.zeros((K, *s.shape), s.dtype)) for s in res_structs
        ),
        pv(jnp.zeros_like(in_buf)),                 # dx out queue
        pv(jnp.zeros(mb_shape, mb_dtype)),          # dx ring register
        pv(jnp.full((), -1, jnp.int32)),            # dx ring mb index
        pv(jax.tree_util.tree_map(jnp.zeros_like, chunks)),      # d_stage
        pv(jax.tree_util.tree_map(jnp.zeros_like, last_params)),  # d_last
        pv(jnp.zeros((), jnp.float32)),             # loss sum
        pv(_zeros_of(mets_struct)),                 # metric sums
        pv(aux_zero) if aux_desc is not None else None,
    )
    (_, _, _, _, _, dx_buf, _, _, d_stage, d_last, loss_acc, mets_acc,
     aux_acc) = lax.scan(cycle, carry0, jnp.arange(n_cycles))[0]

    # loss/metrics/aux/d_last sum over pipe (masked to last-stage entries)
    # AND over seq chunks; d_stage stays pipe-sharded but each seq peer
    # holds only its chunk's partial — reduce over seq only.
    axes = (axis_name,) if seq_axis is None else (axis_name, seq_axis)
    psum = lambda t: jax.tree_util.tree_map(
        lambda a: lax.psum(a, axes), t
    )
    if seq_axis is not None:
        d_stage = jax.tree_util.tree_map(
            lambda g: lax.psum(g, seq_axis), d_stage
        )
    aux_out = psum(aux_acc) if aux_desc is not None else {}
    return (
        psum(loss_acc), psum(mets_acc), aux_out,
        jax.tree_util.tree_map(lambda g: g[None], d_stage),
        psum(d_last), dx_buf,
    )


def _1f1b_run(stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes,
              aux_desc, seq, n_virtual, recompute, predicate_head,
              stage_params, last_params, x_stack, last_args):
    """Trace the 1F1B shard_map; returns outputs AND gradients."""
    mets_struct = jax.eval_shape(
        lambda lp, y, a: last_fn(lp, y, a)[1],
        last_params,
        jax.ShapeDtypeStruct(x_stack.shape[1:], x_stack.dtype),
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), last_args
        ),
    )
    aux_struct = (
        aux_desc[0].unflatten(list(aux_desc[1]))
        if aux_desc is not None else {}
    )
    # SP x PP: the queue is (n_micro, mb, S, ...) — dim 2 manual over seq;
    # last_args leaves with a sequence dim (rank >= 3: (n_micro, mb, S...))
    # are chunked the same way, scalar-per-microbatch leaves replicate.
    x_spec = P(pipe_axis) if seq is None else P(pipe_axis, None, seq)
    arg_spec = (
        (lambda a: P())
        if seq is None
        else (lambda a: P(None, None, seq) if a.ndim >= 3 else P())
    )
    fn = jax.shard_map(
        functools.partial(
            _1f1b_local, stage_fn=stage_fn, last_fn=last_fn,
            axis_name=pipe_axis, n_micro=n_micro, aux_desc=aux_desc,
            seq_axis=seq, n_virtual=n_virtual, recompute=recompute,
            predicate_head=predicate_head,
        ),
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(pipe_axis), stage_params),
            jax.tree_util.tree_map(lambda _: P(), last_params),
            x_spec,
            jax.tree_util.tree_map(arg_spec, last_args),
        ),
        out_specs=(
            P(),
            jax.tree_util.tree_map(lambda _: P(), mets_struct),
            jax.tree_util.tree_map(lambda _: P(), aux_struct),
            jax.tree_util.tree_map(lambda _: P(pipe_axis), stage_params),
            jax.tree_util.tree_map(lambda _: P(), last_params),
            x_spec,
        ),
        axis_names={pipe_axis} | ({seq} if seq else set()),
    )
    return fn(stage_params, last_params, x_stack, last_args)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10))
def _1f1b_loss(stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes,
               aux_desc, seq, n_virtual, recompute, predicate_head,
               stage_params, last_params, x_stack, last_args):
    loss, mets, aux, _, _, _ = _1f1b_run(
        stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes, aux_desc,
        seq, n_virtual, recompute, predicate_head, stage_params,
        last_params, x_stack, last_args,
    )
    return loss, mets, aux


def _1f1b_loss_fwd(stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes,
                   aux_desc, seq, n_virtual, recompute, predicate_head,
                   stage_params, last_params, x_stack, last_args):
    loss, mets, aux, d_stage, d_last, dx = _1f1b_run(
        stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes, aux_desc,
        seq, n_virtual, recompute, predicate_head, stage_params,
        last_params, x_stack, last_args,
    )
    int_args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), last_args
    )
    return (loss, mets, aux), (d_stage, d_last, dx, int_args)


def _1f1b_loss_bwd(stage_fn, last_fn, mesh, n_micro, pipe_axis, data_axes,
                   aux_desc, seq, n_virtual, recompute, predicate_head,
                   res, cts):
    import numpy as np

    d_stage, d_last, dx, int_args = res
    ct_loss = cts[0]  # aux/metric cotangents are ignored by contract

    def scale(t):
        return jax.tree_util.tree_map(lambda g: g * ct_loss, t)

    # non-differentiable (int/bool) leaves take float0 cotangents
    zeros_args = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype)
        if jnp.issubdtype(s.dtype, jnp.inexact)
        else np.zeros(s.shape, jax.dtypes.float0),
        int_args,
    )
    return scale(d_stage), scale(d_last), scale(dx), zeros_args


_1f1b_loss.defvjp(_1f1b_loss_fwd, _1f1b_loss_bwd)


def one_f_one_b(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    n_micro: int,
    *,
    last_fn,
    last_params: Any,
    last_args: Any,
    pipe_axis: str = "pipe",
    batch_axes: Sequence[str] = ("data", "fsdp"),
    aux_weights: Any = None,
    seq_axis: Optional[str] = None,
    n_virtual: int = 1,
    recompute: bool = True,
    predicate_head: bool = True,
) -> tuple:
    """1F1B pipeline train pass: per-microbatch loss computed at the last
    stage, backward interleaved one cycle behind forward.

    Args:
      stage_fn: ``(stage_param_slice, activation) -> activation`` (or
        ``(activation, aux)`` with ``aux_weights``); shape-preserving.
      stage_params: stacked (n_stages, ...) pytree sharded over
        ``pipe_axis``.
      x: global input activations (batch, ...), split into ``n_micro``
        microbatches on the leading dim.
      last_fn: ``(last_params, y_mb, args_mb) -> (loss, metrics)`` — the
        model tail (final norm, head, loss) applied to one microbatch's
        final activations at the LAST stage. ``loss`` must be a scalar;
        ``metrics`` a pytree of scalars. Sums over microbatches are
        returned — normalize by ``n_micro`` (or token counts) outside.
      last_params: pytree of tail parameters (replicated over pipe;
        gradients are returned through the custom VJP).
      last_args: pytree of per-microbatch arrays stacked on a leading
        ``n_micro`` dim (e.g. target tokens), replicated over pipe.
        Integer/bool leaves get float0 cotangents (non-differentiable).
      aux_weights: optional pytree of PYTHON FLOAT coefficients matching
        the aux structure ``stage_fn`` emits; they seed the aux cotangents
        inside the schedule (see module comment — aux outputs are
        reporting-only). Normalization contract: the gradients delivered
        through the custom VJP are ``d(loss_sum + sum_k w_k * aux_sum_k)``
        scaled by the cotangent arriving on ``loss_sum`` — so an outer
        objective of ``(loss_sum + sum_k w_k * aux_sum_k) / n_micro``
        (mean loss + weighted mean aux, the trainer's convention) gets
        exactly the right gradients, while any OTHER outer scaling of the
        aux terms is silently ignored.
      seq_axis: SP x PP x 1F1B — when the mesh spans this axis, the
        schedule's shard_map goes manual over {pipe, seq} (the GPipe
        ``seq_axis`` contract, same no-nested-shard_map rationale):
        ``stage_fn`` sees SEQUENCE-LOCAL chunks (dim 2 sharded) and runs
        the chunk-local SP collectives itself, and ``last_fn`` must be
        CHUNK-LOCAL: called on a sequence shard of one microbatch's final
        activations with the same shard of every rank >= 3 ``last_args``
        leaf (rank < 3 leaves replicate), returning this chunk's loss/
        metric partial sums — the schedule psums them over seq. For a
        causal-LM loss that means pre-shifted targets plus a validity
        mask instead of an in-``last_fn`` shift (the shift would cross
        chunk boundaries). Chunk-local ``jax.value_and_grad`` seeds are
        exact because softmax-CE is position-local.
      recompute: ``True`` (default) replays the stage forward from the
        input stash at B time (activation memory ~ the input ring only;
        cycle cost ~4 forward-units). ``False`` stashes the stage's full
        vjp residuals at F time in K-slot rings riding the scan carry
        (same n_micro-independent depth ``one_f_one_b_stash_slots``) and
        applies the STORED transpose at B — no replay, cycle cost ~3
        forward-units, temp memory up by the residual footprint per slot.
        Param-leaf residuals are substituted live (never ringed) and the
        stage-input leaf reuses the existing input ring, so the extra
        memory is the true intermediates only. Numerics are identical to
        an ordinary ``jax.grad`` of the stage (it applies the same
        transpose); see results/pipeline_1f1b/ for the measured frontier.
      predicate_head: run ``last_fn`` under a per-device ``lax.cond`` so
        only the last stage (on cycles where its forward microbatch is
        live) evaluates the model tail. Legal because ``last_fn`` is
        collective-free by contract; non-last stages previously computed
        and masked the full head every cycle. Default on; the ``False``
        arm exists for the head-cost A/B (scripts/pipeline_head_cost.py).

    Returns ``(loss_sum, metric_sums, aux_sums)``, differentiable wrt
    (stage_params, last_params, x).
    """
    batch = x.shape[0]
    n_stages = mesh.shape[pipe_axis]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    if n_micro % n_stages:
        raise ValueError(
            f"n_micro {n_micro} not divisible by pipe size {n_stages}"
        )
    seq = seq_axis if (seq_axis and mesh.shape.get(seq_axis, 1) > 1) else None
    if seq is not None and x.ndim < 3:
        raise ValueError(
            f"seq_axis={seq!r} needs (batch, seq, ...) activations, got "
            f"rank {x.ndim}"
        )
    if seq is not None and aux_weights is not None:
        raise NotImplementedError(
            "aux accumulation (MoE) does not compose with seq_axis inside "
            "the pipeline; drop one (the models reject PP x SP x EP)"
        )
    x_stack = x.reshape(n_micro, batch // n_micro, *x.shape[1:])
    data = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    x_stack = lax.with_sharding_constraint(
        x_stack, NamedSharding(mesh, P(pipe_axis, data or None, seq))
    )
    mb = batch // n_micro

    def stack_arg(a):
        if a.shape[:1] != (batch,):
            return a
        # same microbatch layout as the activation queue (mb dim over the
        # data axes): the head then sees operands that already agree, and
        # GSPMD has no reason to put a resharding collective inside the
        # last-stage-only ``lax.cond`` branch (a collective-permute there
        # is joined by half the devices and never completes on XLA:CPU)
        a = a.reshape(n_micro, mb, *a.shape[1:])
        spec = P(None, data or None, seq) if a.ndim >= 3 else P(None, data or None)
        return lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    last_args = jax.tree_util.tree_map(stack_arg, last_args)
    if aux_weights is None:
        aux_desc = None
    else:
        leaves, treedef = jax.tree_util.tree_flatten(aux_weights)
        if not all(isinstance(w, (int, float)) for w in leaves):
            raise TypeError("aux_weights must be python floats (static)")
        aux_desc = (treedef, tuple(float(w) for w in leaves))
    return _1f1b_loss(
        stage_fn, last_fn, mesh, n_micro, pipe_axis, data, aux_desc, seq,
        n_virtual, bool(recompute), bool(predicate_head), stage_params,
        last_params, x_stack, last_args,
    )
