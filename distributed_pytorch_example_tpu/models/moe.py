"""Mixture-of-Experts MLP with expert parallelism (Switch / GShard top-k).

Beyond-reference capability (the reference is a dense MLP, SURVEY.md §2):
scales model capacity by replacing transformer MLPs with E experts of which
each token uses ``top_k`` (1 = Switch, 2 = GShard). TPU-first design — the
dense-dispatch formulation: routing builds (tokens → expert, capacity-slot)
one-hot dispatch/combine tensors and the whole layer is einsums, so under a
mesh with the expert dim of the weights sharded on the ``expert`` axis XLA
partitions the expert computation and inserts the token all-to-alls. No
gather/scatter, no dynamic shapes, fully jit/remat/grad compatible.

Expert-count scaling is MEASURED, not assumed: E*C ~ top_k*cf*S is
constant in E, and the committed curve (results/moe_dispatch/, single
v5e) shows +14% full-model step time from E=4 to E=64 — the growth is
MXU tile underfill at small per-expert capacity, which a sorted/ragged
dispatch would not fix (same skinny matmuls plus unfusable gathers);
expert parallelism and larger per-chip token budgets do.

Auxiliary losses emitted via ``self.sow("losses", ...)`` and added to the
task loss by ``train.tasks`` (models stay single-output):

- load balancing (Switch form, E * Σ_e f_e * P_e, with f_e from each
  token's FIRST choice);
- router z-loss (ST-MoE): mean(logsumexp(logits)^2) keeps router logits
  from drifting to magnitudes where bf16 activations saturate.

Capacity: each expert processes at most C = ceil(top_k * S / E *
capacity_factor) tokens per batch row. First choices (across the whole
sequence) claim slots before any second choice; overflow tokens pass
through the residual unchanged (standard Switch/GShard behavior).

A second path, for models whose published routing drops nothing
(:func:`moe_dropless`, :class:`DroplessMoE`): sigmoid scores with a
selection bias that picks but does not weigh, an expert layer that is told
which experts of the published count it holds, assignments sorted by expert
into rows under a static bound, grouped matrix products over the rows
(:func:`grouped_dot`), and counters of what the routing did. The
capacity path above stays for the models that use it.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def dropless_rows_bound(tokens: int, top_k: int, held: int, experts: int) -> int:
    """Rows the dropless path lays its sorted assignments into, derived and
    not a flag: twice the even share of the experts held here, ``2 x top_k
    x tokens x held / experts``, and never more than the worst case ``top_k
    x tokens`` (a layer that holds every expert gets that, and a drop is
    then impossible). The worst case for a quarter of the experts fits the
    chip at the benchmark's 4 x 8192 tokens, but only the products follow
    the group sizes: the gathers, the scatter-add and the elementwise
    passes run over the buffer, and the step takes 721 ms where it takes
    611 (PERF.md section 6, PR 29). At twice the even share an assignment
    is dropped, and counted, only where the router sends this chip more
    than twice what an even routing would."""
    return min(top_k * tokens, -(-2 * top_k * tokens * held // experts))


def grouped_dot(rows, weights, group_sizes):
    """``rows[i] @ weights[g]`` for the group ``g`` that row ``i`` lies in
    (rows sorted by group, ``group_sizes`` rows each); rows past the last
    group give zeros, take zeros as their gradient and cost no product.

    On the TPU the Pallas kernels of ``ops/pallas/moe_gmm.py``, which
    refuse rows that do not come in whole tiles; off it
    ``jax.lax.ragged_dot``, masked on the way in and on the way out (on the
    TPU it leaves the rows past the last group unspecified, NaN on one run
    and finite on the next: PERF.md section 6, PR 29)."""
    from distributed_pytorch_example_tpu.ops.attention import _on_tpu
    from distributed_pytorch_example_tpu.ops.pallas import moe_gmm

    if _on_tpu():
        return moe_gmm.grouped_matmul(rows, weights, group_sizes)
    in_a_group = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = lax.ragged_dot(
        jnp.where(in_a_group, rows, 0), weights, group_sizes,
        preferred_element_type=rows.dtype,
    )
    return jnp.where(in_a_group, out, 0)


def moe_route_sigmoid(
    x, router_kernel, select_bias, *, top_k: int,
    norm_topk: bool = True, scaling: float = 1.0,
):
    """Sigmoid routing over every published expert: ``(weights, chosen)``,
    both (T, top_k). Float32 throughout. The ``top_k`` experts with the
    largest ``score + select_bias`` are chosen; their weights are the scores
    WITHOUT the bias, over (their sum + 1e-6) when ``norm_topk``, times
    ``scaling``. The bias is a buffer: no gradient reaches it."""
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ))
        choice = scores
        if select_bias is not None:
            choice = scores + lax.stop_gradient(
                select_bias.astype(jnp.float32)
            )
        _, chosen = lax.top_k(lax.stop_gradient(choice), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        return weights * scaling, chosen


def moe_dropless(
    x, weights, chosen, params: dict, *, first_held: int,
    rows_bound: int, dtype=jnp.float32,
):
    """The held experts' part of a dropless expert layer: ``(y, counters)``.

    ``x`` is (T, D); ``weights`` / ``chosen`` (T, k) come from the router,
    over ALL published experts; ``params`` holds ``gate_kernel``,
    ``up_kernel`` (H, D, M) and ``down_kernel`` (H, M, D) of the H experts
    held here, published experts ``first_held .. first_held + H - 1``. Only
    chosen experts that are held contribute; what the absent ones would
    have added is left out (their chips add it in a deployment).

    route -> keep the held assignments -> stable sort by expert -> gather
    the tokens into ``rows_bound`` rows -> group sizes -> grouped gate+up
    product (one product of width 2M), SwiGLU, grouped down product ->
    weight and scatter-add back to the tokens. Held assignments beyond
    ``rows_bound`` are dropped and counted. ``counters``: float32 scalars
    ``dropped_assignments``, ``held_share`` (held assignments over k x T),
    ``load_max_over_mean`` (the busiest held expert over the held mean) and
    ``rows_used_share`` (rows that hold an assignment over the bound).
    """
    tokens, _ = x.shape
    k = chosen.shape[-1]
    held = params["gate_kernel"].shape[0]
    with jax.named_scope("moe_dispatch"):
        local = chosen.reshape(-1) - first_held  # (T k,)
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held)  # the absent sort last
        order = jnp.argsort(group, stable=True)[:rows_bound]
        counts = jnp.sum(
            group[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32,
        )  # (H,) assignments of each held expert
        ends = jnp.minimum(jnp.cumsum(counts), rows_bound)
        group_sizes = jnp.diff(ends, prepend=0)
        used = ends[-1]
        token = order // k
        rows = jnp.take(x, token, axis=0).astype(dtype)
        row_weight = jnp.where(
            jnp.arange(rows_bound) < used,
            jnp.take(weights.reshape(-1), order), 0.0,
        )
    with jax.named_scope("moe_experts"):
        gate_up = jnp.concatenate(
            [params["gate_kernel"], params["up_kernel"]], axis=-1
        ).astype(dtype)
        gate, up = jnp.split(grouped_dot(rows, gate_up, group_sizes), 2, axis=-1)
        out = grouped_dot(
            nn.silu(gate) * up, params["down_kernel"].astype(dtype),
            group_sizes,
        )
    with jax.named_scope("moe_dispatch"):
        y = jnp.zeros(x.shape, jnp.float32).at[token].add(
            out.astype(jnp.float32) * row_weight[:, None]
        ).astype(dtype)
    total = jnp.sum(counts)
    mean_load = jnp.maximum(total, 1).astype(jnp.float32) / held
    counters = {
        "dropped_assignments": (total - used).astype(jnp.float32),
        "held_share": total.astype(jnp.float32) / (k * tokens),
        "load_max_over_mean": jnp.max(counts).astype(jnp.float32) / mean_load,
        "rows_used_share": used.astype(jnp.float32) / rows_bound,
    }
    return y, counters


def moe_apply(
    x,
    router_logits,
    params: dict,
    *,
    top_k: int,
    capacity_factor: float,
    dtype=jnp.float32,
    swiglu: bool = False,
):
    """The MoE layer as a pure function: ``(y, aux)`` from explicit params.

    The single source of truth for the routing/dispatch math — the flax
    :class:`MoEMlpBlock` wraps it (adding param creation and sow), and the
    layer-stacked pipelined decoder (models/stacked.py) calls it directly
    with scan-sliced params, so both paths share one implementation.

    Args:
      x: (B, S, D) activations.
      router_logits: (B, S, E) float32 routing logits (callers own the
        router projection so their param paths stay stable).
      params: ``up_kernel`` (E, D, M), ``down_kernel`` (E, M, D); gelu
        experts add ``up_bias``/``down_bias``, SwiGLU experts add
        ``gate_kernel``.

    Returns ``(y, aux)`` with RAW (unweighted) scalars in ``aux``:
    ``load_balancing``, ``router_z``, ``dropped_fraction``.
    """
    batch, seq, dim = x.shape
    n_exp = router_logits.shape[-1]
    k = top_k
    if not 1 <= k <= n_exp:
        raise ValueError(f"top_k {k} must be in [1, num_experts {n_exp}]")
    capacity = max(1, math.ceil(k * seq * capacity_factor / n_exp))

    router_logits = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_probs, top_idx = lax.top_k(probs, k)  # (B, S, K)
    if k > 1:
        # GShard: gates renormalized over the selected experts
        gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    else:
        gates = top_probs  # Switch: raw router prob

    onehot_k = jax.nn.one_hot(top_idx, n_exp, dtype=jnp.float32)
    # Switch load-balancing loss, f_e from first choices only
    tokens_per_expert = onehot_k[:, :, 0].mean(axis=(0, 1))  # (E,)
    prob_per_expert = probs.mean(axis=(0, 1))  # (E,)
    aux_lb = n_exp * jnp.sum(tokens_per_expert * prob_per_expert)
    z = jax.nn.logsumexp(router_logits, axis=-1)  # (B, S)
    aux_z = jnp.mean(jnp.square(z))

    # capacity-slot assignment: cumulative position of each (choice,
    # token) in its expert's queue, ordered k-major so every first
    # choice outranks every second choice; slot >= capacity one_hots to
    # all-zeros, which IS the drop (token rides the residual)
    oh_flat = onehot_k.transpose(0, 2, 1, 3).reshape(
        batch, k * seq, n_exp
    )  # (B, K*S, E), k-major priority order
    pos = (jnp.cumsum(oh_flat, axis=1) - 1.0) * oh_flat
    slot = (
        jnp.sum(pos, axis=-1)
        .reshape(batch, k, seq)
        .transpose(0, 2, 1)
    )  # (B, S, K)
    dispatch_k = (
        onehot_k[..., None]
        * jax.nn.one_hot(
            slot.astype(jnp.int32), capacity, dtype=jnp.float32
        )[:, :, :, None, :]
    )  # (B, S, K, E, C) one-hot; slots are disjoint across k
    dispatch = jnp.sum(dispatch_k, axis=2)  # (B, S, E, C)
    combine = jnp.sum(
        dispatch_k * gates[..., None, None], axis=2
    )  # weighted return path
    kept = jnp.sum(dispatch)  # each kept (token, choice) contributes 1
    dropped_fraction = 1.0 - kept / (batch * seq * k)

    w_up = params["up_kernel"].astype(dtype)
    w_down = params["down_kernel"].astype(dtype)
    # dispatch → expert MLP → combine: all einsums, XLA inserts the
    # all-to-alls when 'expert' spans devices
    expert_in = jnp.einsum(
        "bsec,bsd->ebcd", dispatch.astype(dtype), x
    )  # (E, B, C, D)
    up = jnp.einsum("ebcd,edf->ebcf", expert_in, w_up)
    if swiglu:
        w_gate = params["gate_kernel"].astype(dtype)
        h = nn.silu(jnp.einsum("ebcd,edf->ebcf", expert_in, w_gate)) * up
    else:
        h = nn.gelu(up + params["up_bias"].astype(dtype)[:, None, None, :])
    expert_out = jnp.einsum("ebcf,efd->ebcd", h, w_down)
    if not swiglu:
        expert_out = (
            expert_out + params["down_bias"].astype(dtype)[:, None, None, :]
        )
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(dtype), expert_out)
    return y, {
        "load_balancing": aux_lb,
        "router_z": aux_z,
        "dropped_fraction": dropped_fraction,
    }


class MoEMlpBlock(nn.Module):
    """Drop-in replacement for models.transformer.MlpBlock."""

    num_experts: int
    mlp_dim: int
    model_dim: int
    top_k: int = 1
    capacity_factor: float = 1.25
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    # SwiGLU experts (Mixtral-style, for the LLaMA family): each expert is
    # silu(x @ gate) * (x @ up) -> down instead of gelu(x @ up) -> down
    swiglu: bool = False

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        _, _, dim = x.shape
        n_exp = self.num_experts
        lecun_e = nn.initializers.lecun_normal(batch_axis=(0,))

        # routing in float32: small tensors, and router stability matters;
        # the Dense child keeps the historical 'router/kernel' param path
        router_logits = nn.Dense(n_exp, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # (B, S, E)

        # expert weights: leading expert dim is the EP sharding target.
        # Bias convention mirrors the dense MLP each expert replaces: gelu
        # experts (transformer MlpBlock) carry biases, SwiGLU experts
        # (llama SwiGluMlp, Mixtral) are bias-free throughout.
        params = {
            "up_kernel": self.param(
                "up_kernel", lecun_e, (n_exp, dim, self.mlp_dim)
            ),
            "down_kernel": self.param(
                "down_kernel", lecun_e, (n_exp, self.mlp_dim, dim)
            ),
        }
        if self.swiglu:
            params["gate_kernel"] = self.param(
                "gate_kernel", lecun_e, (n_exp, dim, self.mlp_dim)
            )
        else:
            params["up_bias"] = self.param(
                "up_bias", nn.initializers.zeros_init(),
                (n_exp, self.mlp_dim),
            )
            params["down_bias"] = self.param(
                "down_bias", nn.initializers.zeros_init(), (n_exp, dim)
            )

        out, aux = moe_apply(
            x, router_logits, params, top_k=self.top_k,
            capacity_factor=self.capacity_factor, dtype=self.dtype,
            swiglu=self.swiglu,
        )
        self.sow(
            "losses", "load_balancing",
            self.aux_loss_weight * aux["load_balancing"],
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        self.sow(
            "losses", "router_z",
            self.z_loss_weight * aux["router_z"],
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        # observability: capacity-dropped (token, choice) pairs ride the
        # residual silently — surface the fraction so a mis-tuned
        # capacity_factor shows up in metrics (train/tasks.py averages the
        # sown values into `moe_dropped_fraction`); init must not bake a
        # stale value
        if not self.is_initializing():
            self.sow(
                "moe_metrics", "dropped_fraction", aux["dropped_fraction"]
            )
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out


class DroplessMoE(nn.Module):
    """Sigmoid top-k expert layer that drops nothing and holds a share of
    the published experts (:func:`moe_route_sigmoid`, :func:`moe_dropless`).

    ``num_experts`` is the router's published width; this chip holds
    ``experts_held`` of them from ``first_held`` on (None: all). The
    selection bias ``select_bias`` sits in the parameter tree so that a
    checkpoint carries it, but it is a buffer: no gradient reaches it and
    Adam leaves it as it was. The row bound is derived
    (:func:`dropless_rows_bound`).

    ``shared_mlp_dim`` > 0 adds a shared expert: one SwiGLU of that width
    that every token meets, whole on every chip, whatever the routing; its
    output is added to the held experts' part (scope ``moe_shared``)."""

    num_experts: int
    mlp_dim: int
    top_k: int = 4
    first_held: int = 0
    experts_held: Optional[int] = None
    use_select_bias: bool = True
    norm_topk: bool = True
    scaling: float = 1.0
    shared_mlp_dim: int = 0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        batch, seq, dim = x.shape
        held = self.num_experts if self.experts_held is None else self.experts_held
        if not 0 <= self.first_held <= self.num_experts - held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + held - 1} "
                f"are not among the {self.num_experts} published"
            )
        normal = nn.initializers.normal(stddev=0.02)
        router = self.param("router_kernel", normal, (dim, self.num_experts))
        bias = (
            self.param("select_bias", nn.initializers.zeros_init(),
                       (self.num_experts,))
            if self.use_select_bias else None
        )
        params = {
            "gate_kernel": self.param("gate_kernel", normal, (held, dim, self.mlp_dim)),
            "up_kernel": self.param("up_kernel", normal, (held, dim, self.mlp_dim)),
            "down_kernel": self.param("down_kernel", normal, (held, self.mlp_dim, dim)),
        }
        flat = x.reshape(batch * seq, dim)
        weights, chosen = moe_route_sigmoid(
            flat, router, bias, top_k=self.top_k, norm_topk=self.norm_topk,
            scaling=self.scaling,
        )
        bound = dropless_rows_bound(
            batch * seq, self.top_k, held, self.num_experts
        )
        y, counters = moe_dropless(
            flat, weights, chosen, params, first_held=self.first_held,
            rows_bound=bound, dtype=self.dtype,
        )
        if not self.is_initializing():
            for name, value in counters.items():
                self.sow("moe_metrics", name, value)
        y = y.reshape(batch, seq, dim)
        if self.shared_mlp_dim:
            from distributed_pytorch_example_tpu.models.llama import SwiGluMlp

            with jax.named_scope("moe_shared"):
                y = y + SwiGluMlp(
                    mlp_dim=self.shared_mlp_dim, model_dim=dim,
                    dtype=self.dtype, name="shared",
                )(x, train=train)
        return y
