"""LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``): gated short
convolutions and grouped-query attention in one stack, dense SwiGLU in the
leading layers and sigmoid top-4 sparse experts in the rest.

Pre-norm residual stack, no biases anywhere, no position table (positions
enter through RoPE in the attention layers only):

    h = x + operator(operator_norm(x));   y = h + ffn(ffn_norm(h))

- operator, by ``layer_types[i]``: ``"conv"`` is the gated short
  convolution (:class:`ShortConv`); ``"full_attention"`` is causal
  grouped-query attention with RMSNorm over each head of q and of k before
  RoPE (``transformer.MultiHeadAttention`` with ``qk_norm``);
- feed-forward: ``llama.SwiGluMlp`` in the first ``num_dense_layers``
  published layers, ``moe.DroplessMoE`` in the others;
- a final RMSNorm, then logits against the tied token table.

The defaults are the published configuration, written once here. A
deployment's share is said by three fields (``train.py``: ``--layers-kept``,
``--experts-held``, ``--vocab-slice``): ``layers_kept`` (indices of the
published stack that this pipeline stage holds; a layer keeps its published
index in its name and so its kind), ``experts_first`` / ``experts_held``
(the experts of every expert layer that this chip holds; the router keeps
its published width) and ``vocab_size`` (the slice of the vocabulary).
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_example_tpu.models.llama import RMSNorm, SwiGluMlp
from distributed_pytorch_example_tpu.models.moe import DroplessMoE
from distributed_pytorch_example_tpu.models.transformer import (
    MultiHeadAttention,
    tied_head_logits,
)

# 24 layers: 18 short convolutions, 6 attention layers
LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24)
)


class ShortConv(nn.Module):
    """Gated short convolution: ``B, C, u = split3(in_proj(x))``;
    ``z = B * u``; a depthwise causal convolution of ``taps`` taps a channel
    along the sequence (zeros before the row's first token); ``out_proj(C *
    conv)``. The gates and the taps are elementwise and run in float32."""

    model_dim: int
    taps: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        with jax.named_scope("short_conv"):
            seq = x.shape[1]
            bcu = nn.Dense(
                3 * self.model_dim, use_bias=False, dtype=self.dtype,
                name="in_proj",
            )(x).astype(jnp.float32)
            gate_in, gate_out, u = jnp.split(bcu, 3, axis=-1)
            kernel = self.param(
                "conv_kernel", nn.initializers.normal(stddev=0.02),
                (self.model_dim, self.taps),
            ).astype(jnp.float32)
            z = jnp.pad(gate_in * u, ((0, 0), (self.taps - 1, 0), (0, 0)))
            conv = sum(
                kernel[:, j] * z[:, j:j + seq] for j in range(self.taps)
            )
            return nn.Dense(
                self.model_dim, use_bias=False, dtype=self.dtype,
                name="out_proj",
            )((gate_out * conv).astype(self.dtype))


class Lfm2Block(nn.Module):
    operator: str  # "conv" | "full_attention"
    experts: bool  # sparse experts, else the dense SwiGLU
    model_dim: int
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    moe_mlp_dim: int
    num_experts: int
    top_k: int
    experts_first: int
    experts_held: Optional[int]
    use_expert_bias: bool
    norm_topk_prob: bool
    routed_scaling_factor: float
    conv_taps: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        if self.operator == "conv":
            operator = ShortConv(
                self.model_dim, self.conv_taps, self.dtype, name="conv"
            )
        elif self.operator == "full_attention":
            operator = MultiHeadAttention(
                num_heads=self.num_heads,
                head_dim=self.model_dim // self.num_heads,
                model_dim=self.model_dim,
                causal=True,
                dtype=self.dtype,
                use_flash=self.use_flash,
                num_kv_heads=self.num_kv_heads,
                rope=True,
                rope_theta=self.rope_theta,
                qk_norm=True,
                qk_norm_epsilon=self.norm_eps,
                use_bias=False,
                name="attn",
            )
        else:
            raise ValueError(f"unknown layer type {self.operator!r}")
        if self.experts:
            ffn = DroplessMoE(
                num_experts=self.num_experts,
                mlp_dim=self.moe_mlp_dim,
                top_k=self.top_k,
                first_held=self.experts_first,
                experts_held=self.experts_held,
                use_select_bias=self.use_expert_bias,
                norm_topk=self.norm_topk_prob,
                scaling=self.routed_scaling_factor,
                dtype=self.dtype,
                name="moe",
            )
        else:
            ffn = SwiGluMlp(
                mlp_dim=self.mlp_dim, model_dim=self.model_dim,
                dtype=self.dtype, name="mlp",
            )
        norm_op = RMSNorm(self.norm_eps, self.dtype, name="operator_norm")
        norm_ffn = RMSNorm(self.norm_eps, self.dtype, name="ffn_norm")
        x = x + operator(norm_op(x), train=train)
        return x + ffn(norm_ffn(x), train=train)


class Lfm2(nn.Module):
    """LFM2-8B-A1B; the defaults are the published configuration."""

    vocab_size: int = 65536
    model_dim: int = 2048
    layer_types: Tuple[str, ...] = LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 7168
    moe_mlp_dim: int = 1792
    num_experts: int = 32
    top_k: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_taps: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    # the deployment's share (module docstring); None: the whole
    layers_kept: Optional[Tuple[int, ...]] = None
    experts_first: int = 0
    experts_held: Optional[int] = None
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None
    remat: bool = False
    # "full": (B, S, V) logits. "hidden": final hidden states for the fused
    # chunked-CE loss (train/tasks.py + ``head_params``).
    logits_mode: str = "full"

    @staticmethod
    def head_params(params):
        """Tied LM-head weights for the fused loss: ((V, D) table, bias)."""
        return params["tok_embed"]["embedding"], None

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, targets=None):
        del targets  # no pipelined schedule here
        if self.logits_mode not in ("full", "hidden"):
            raise ValueError(
                f"logits_mode must be 'full' or 'hidden', got "
                f"{self.logits_mode!r}"
            )
        published = len(self.layer_types)
        kept = (
            tuple(range(published)) if self.layers_kept is None
            else tuple(self.layers_kept)
        )
        if list(kept) != sorted(set(kept)) or not all(
            0 <= i < published for i in kept
        ):
            raise ValueError(
                f"layers_kept {kept} must be rising indices of the "
                f"{published} published layers"
            )
        embed = nn.Embed(
            self.vocab_size,
            self.model_dim,
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="tok_embed",
        )
        x = embed(tokens).astype(self.dtype)
        for i in kept:
            block = Lfm2Block(
                operator=self.layer_types[i],
                experts=i >= self.num_dense_layers,
                model_dim=self.model_dim,
                num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                mlp_dim=self.mlp_dim,
                moe_mlp_dim=self.moe_mlp_dim,
                num_experts=self.num_experts,
                top_k=self.top_k,
                experts_first=self.experts_first,
                experts_held=self.experts_held,
                use_expert_bias=self.use_expert_bias,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                conv_taps=self.conv_taps,
                rope_theta=self.rope_theta,
                norm_eps=self.norm_eps,
                dtype=self.dtype,
                use_flash=self.use_flash,
                name=f"layer_{i}",
            )
            if self.remat:
                # prevent_cse stays on: the layers are unrolled, not scanned,
                # and XLA would otherwise merge a layer's recomputation with
                # its first pass and keep what remat was to free
                x = nn.remat(
                    lambda mdl, h: Lfm2Block.__call__(mdl, h, train=train)
                )(block, x)
            else:
                x = block(x, train=train)
        x = RMSNorm(self.norm_eps, self.dtype, name="embedding_norm")(x)
        if self.logits_mode == "hidden":
            return x
        return tied_head_logits(x, embed.embedding, self.dtype)
