"""Tasks: loss + metrics definitions binding a model to a batch format.

A task computes ``(loss, metrics, new_model_state)`` from (model, params,
batch). Everything here runs INSIDE the jitted step — including MLM masking —
so the host never touches per-step data (contrast with the reference's eager
loop, train.py:132-141).

Metric semantics parity: loss/accuracy are means over the GLOBAL batch. With
the batch sharded over the data axes this equals the reference's
"per-shard metric, then cross-rank mean" reduction (train.py:275-277) when
shards are equal-sized — which they are, by the sampler's padding contract.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

Metrics = Dict[str, jax.Array]


def dequantize_inputs(x: jax.Array) -> jax.Array:
    """uint8 image batches -> float32 in [0, 1], ON DEVICE.

    The TPU-first input layout: the host pipeline ships raw uint8 (4x less
    host->device traffic than float32) and the [0,255] -> [0,1] scaling the
    reference does on host (implicitly via torchvision-style loaders) runs
    inside the compiled step. Non-uint8 inputs (float images, int32 token
    ids) pass through untouched.

    FRAMEWORK CONTRACT: a uint8 model input IS a [0,255] image. This is
    applied uniformly — tree-mapped over model inputs in ``_apply_model``
    (every task, train and eval) and in ``train.step.init_state`` — so
    init and step always trace the model with identical dtypes. The
    contract is ENFORCED, not assumed: images are rank >= 3 ((..., H, W, C)
    batches); a uint8 input of lower rank (e.g. byte-valued token ids,
    (B, S)) would be silently corrupted by the rescale, so it raises at
    trace time instead — ship such inputs as int32.
    """
    if x.dtype == jnp.uint8:
        if x.ndim < 3:
            raise TypeError(
                f"uint8 model input of shape {x.shape} is not an image "
                f"batch (rank < 3); the framework rescales uint8 inputs "
                f"to [0,1] float32 as images. Cast non-image inputs "
                f"(e.g. token ids) to int32 on the host."
            )
        return x.astype(jnp.float32) / 255.0
    return x


def _fused_head(model) -> bool:
    """True when the model returns hidden states for the fused chunked-CE
    loss (``logits_mode='hidden'`` + ``head_params``, see ops/chunked_ce.py)
    instead of materialized (B, S, V) logits."""
    return getattr(model, "logits_mode", "full") == "hidden"


def _train_mutable(model_state) -> list:
    """Mutable collections a train-mode apply must request: the carried
    model state plus the sown aux-loss / MoE-observability collections."""
    mutable = list(model_state.keys()) if model_state else []
    return mutable + ["losses", "moe_metrics"]


# how the expert layers' sown scalars of one name combine over the layers
# into ``moe_<name>``: a count adds up, a share is the layers' mean, and an
# imbalance or a fill is the worst layer's
_MOE_REDUCE = {
    "dropped_fraction": lambda v: sum(v) / len(v),
    "dropped_assignments": sum,
    "held_share": lambda v: sum(v) / len(v),
    "load_max_over_mean": lambda v: jnp.max(jnp.stack(v)),
    "rows_used_share": lambda v: jnp.max(jnp.stack(v)),
}


def _pop_sown(new_vars, model_state):
    """Extract (aux_loss_sum, extra_metrics, remaining_state) from a
    mutable-apply result: ``losses`` sums into the aux loss, the
    ``moe_metrics`` scalars combine by name over the layers into
    ``moe_<name>`` (``_MOE_REDUCE``) — reported, never added to the loss.
    One implementation for the outer-loss and 1F1B paths so their
    reporting cannot diverge."""
    new_vars = dict(new_vars)
    losses = new_vars.pop("losses", {})
    aux = sum(jax.tree_util.tree_leaves(losses)) if losses else 0.0
    by_name = {}
    for path, value in jax.tree_util.tree_leaves_with_path(
        new_vars.pop("moe_metrics", {})
    ):
        names = [p.key for p in path if hasattr(p, "key")]
        by_name.setdefault(names[-1], []).append(value)
    extra = {
        f"moe_{name}": _MOE_REDUCE[name](values)
        for name, values in by_name.items()
    }
    return aux, extra, (new_vars or (model_state or {}))


def _apply_model(model, params, model_state, inputs, rng, train: bool):
    """Run model.apply handling mutable collections + dropout rng.

    Returns ``(logits, new_model_state, aux_loss, extra_metrics)``. In
    train mode the ``losses`` collection is requested so modules can
    contribute auxiliary losses via ``self.sow("losses", ...)`` (e.g. MoE
    load balancing); aux_loss is their sum and is NOT part of the carried
    model state. The ``moe_metrics`` collection carries observability
    scalars (e.g. capacity-drop fractions), averaged across layers into
    ``extra_metrics`` — reported, never added to the loss.
    """
    variables = {"params": params, **(model_state or {})}
    inputs = jax.tree_util.tree_map(dequantize_inputs, inputs)
    rngs = {"dropout": rng} if train else {}
    if train:
        logits, new_vars = model.apply(
            variables, inputs, train=train, rngs=rngs,
            mutable=_train_mutable(model_state),
        )
        aux, extra, new_ms = _pop_sown(new_vars, model_state)
        return logits, new_ms, aux, extra
    out = model.apply(variables, inputs, train=train, rngs=rngs, mutable=False)
    return out, (model_state or {}), 0.0, {}


class ClassificationTask:
    """Cross-entropy classification on dict batches {'x', 'y'}.

    Reference parity: CrossEntropyLoss (train.py:250) + top-1 accuracy as a
    percentage (train.py:169-174).
    """

    batch_keys = ("x", "y")

    def compute_loss(
        self, model, params, model_state, batch, rng, *, train: bool
    ) -> Tuple[jax.Array, Metrics, Any]:
        logits, new_ms, aux, extra = _apply_model(
            model, params, model_state, batch["x"], rng, train
        )
        labels = batch["y"]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean() + aux
        accuracy = 100.0 * jnp.mean(jnp.argmax(logits, axis=-1) == labels)
        return loss, {"loss": loss, "accuracy": accuracy, **extra}, new_ms


class CausalLMTask:
    """Next-token LM on dict batches {'tokens'} (GPT-2 config).

    The model sees the FULL sequence (keeping seq_len block-aligned so the
    flash kernel stays eligible); position t's logits predict token t+1, and
    the final position's logits are simply excluded from the loss.

    A model with a multi-token-prediction module returns a pair in training:
    its second member's position t predicts token t+2 (the last two
    positions excluded) through the SAME head, and the loss is ``loss_next
    + model.mtp_loss_weight * loss_mtp``; both parts ride in the metrics.
    """

    batch_keys = ("tokens",)

    def compute_loss(
        self, model, params, model_state, batch, rng, *, train: bool
    ) -> Tuple[jax.Array, Metrics, Any]:
        tokens = batch["tokens"]
        if train and getattr(model, "pipe_schedule", "gpipe") == "1f1b":
            return self._pipelined_1f1b(
                model, params, model_state, tokens, rng
            )
        out, new_ms, aux, extra = _apply_model(
            model, params, model_state, tokens, rng, train
        )
        out, mtp_out = out if isinstance(out, tuple) else (out, None)
        fused = _fused_head(model)
        if fused:
            from distributed_pytorch_example_tpu.ops.chunked_ce import (
                chunked_softmax_xent,
            )

            embedding, bias = type(model).head_params(params)

        def ahead(out, n):
            """Position t against token t + n: (losses, argmax, targets)."""
            targets = tokens[:, n:]
            if fused:
                return *chunked_softmax_xent(
                    out[:, :-n], embedding, targets, bias=bias,
                    dtype=model.dtype,
                ), targets
            logits = out[:, :-n]
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), targets
            )
            return per_tok, jnp.argmax(logits, axis=-1), targets

        per_tok, argmax, targets = ahead(out, 1)
        loss = per_tok.mean()
        if mtp_out is not None:
            loss_mtp = ahead(mtp_out, 2)[0].mean()
            extra = {**extra, "loss_next": loss, "loss_mtp": loss_mtp}
            loss = loss + model.mtp_loss_weight * loss_mtp
        loss = loss + aux
        accuracy = 100.0 * jnp.mean(argmax == targets)
        return loss, {"loss": loss, "accuracy": accuracy, **extra}, new_ms

    def _pipelined_1f1b(self, model, params, model_state, tokens, rng):
        """Train step for ``pipe_schedule='1f1b'`` models: the loss runs
        INSIDE the pipeline schedule (the last stage needs each
        microbatch's loss gradient the cycle it finishes its forward —
        parallel/pipeline.py), so the model is applied with ``targets``
        and returns ``(mean loss, {'correct': count})`` instead of
        activations. Metric semantics match the outer-loss path: mean
        next-token loss, accuracy over all target positions."""
        variables = {"params": params, **(model_state or {})}
        (loss, mets), new_vars = model.apply(
            variables, tokens, train=True, targets=tokens,
            rngs={"dropout": rng}, mutable=_train_mutable(model_state),
        )
        # sown aux losses (MoE balancing/z): their VALUES complete the
        # reported objective; their gradients were already seeded inside
        # the 1F1B schedule (aux_weights — the schedule's custom VJP
        # ignores cotangents arriving here, so nothing double-counts)
        aux, extra, new_ms = _pop_sown(new_vars, model_state)
        loss = loss + aux
        n_targets = tokens.shape[0] * (tokens.shape[1] - 1)
        accuracy = 100.0 * mets["correct"] / n_targets
        return loss, {"loss": loss, "accuracy": accuracy, **extra}, new_ms


class MLMTask:
    """BERT-style masked-LM on dict batches {'tokens'}.

    On-device BERT masking recipe: select ``mask_rate`` of positions; of
    those, 80% → [MASK], 10% → random token, 10% → unchanged; loss only on
    selected positions. ``pad_token_id`` (real padded corpora) excludes pad
    positions from masking and from the loss — pair it with the model's
    own ``pad_token_id`` so padding is also out of attention.
    """

    batch_keys = ("tokens",)

    def __init__(
        self,
        vocab_size: int,
        mask_token_id: int,
        mask_rate: float = 0.15,
        pad_token_id: int | None = None,
    ):
        self.vocab_size = vocab_size
        self.mask_token_id = mask_token_id
        self.mask_rate = mask_rate
        self.pad_token_id = pad_token_id

    def compute_loss(
        self, model, params, model_state, batch, rng, *, train: bool
    ) -> Tuple[jax.Array, Metrics, Any]:
        tokens = batch["tokens"]
        rng_sel, rng_kind, rng_rand, rng_drop = jax.random.split(
            jax.random.fold_in(rng, 1), 4
        )
        selected = jax.random.uniform(rng_sel, tokens.shape) < self.mask_rate
        if self.pad_token_id is not None:
            selected &= tokens != self.pad_token_id
        kind = jax.random.uniform(rng_kind, tokens.shape)
        if self.pad_token_id is None:
            random_tokens = jax.random.randint(
                rng_rand, tokens.shape, 0, self.vocab_size, dtype=tokens.dtype
            )
        else:
            # the 10% random-replacement draw must never inject a fake pad
            # into a real scored position (the model would drop it from
            # attention keys): sample [0, vocab-1) and skip over pad_id
            r = jax.random.randint(
                rng_rand, tokens.shape, 0, self.vocab_size - 1,
                dtype=tokens.dtype,
            )
            random_tokens = jnp.where(r >= self.pad_token_id, r + 1, r)
        masked_inputs = jnp.where(
            selected & (kind < 0.8),
            jnp.asarray(self.mask_token_id, tokens.dtype),
            jnp.where(selected & (kind >= 0.9), random_tokens, tokens),
        )
        out, new_ms, aux, extra = _apply_model(
            model, params, model_state, masked_inputs, rng_drop, train
        )
        denom = jnp.maximum(selected.sum(), 1)
        if _fused_head(model):
            from distributed_pytorch_example_tpu.ops.chunked_ce import (
                chunked_softmax_xent,
            )

            embedding, bias = type(model).head_params(params)
            per_tok, argmax = chunked_softmax_xent(
                out, embedding, tokens, bias=bias, dtype=model.dtype
            )
            loss = jnp.where(selected, per_tok, 0.0).sum() / denom + aux
            correct = jnp.where(selected, argmax == tokens, False)
            accuracy = 100.0 * correct.sum() / denom
            return loss, {"loss": loss, "accuracy": accuracy, **extra}, new_ms
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            out.astype(jnp.float32), tokens
        )
        loss = jnp.where(selected, per_tok, 0.0).sum() / denom + aux
        correct = jnp.where(selected, jnp.argmax(out, axis=-1) == tokens, False)
        accuracy = 100.0 * correct.sum() / denom
        return loss, {"loss": loss, "accuracy": accuracy, **extra}, new_ms
