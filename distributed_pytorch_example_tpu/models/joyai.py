"""JoyAI-LLM-Flash (jdopensource, ``model_type`` ``joyai_llm_flash``,
48B-A2.7B): latent attention (MLA), a shared expert beside sigmoid top-8 of
256 routed ones, and a multi-token-prediction module on the shared head.

Pre-norm residual stack, RMSNorm everywhere, no biases, no position table
(positions enter through RoPE on the rotary part of queries and keys):

    h = x + attention(attn_norm(x));   y = h + ffn(ffn_norm(h))

- attention, :class:`LatentAttention`: queries and keys/values come through
  low-rank chains with an RMSNorm in the middle of each; a head's query and
  key are ``qk_nope_head_dim`` wide without position plus
  ``qk_rope_head_dim`` rotary, the rotary key ONE head shared by all; the
  values are ``v_head_dim`` wide, so the flash kernels run at 192 / 128;
- feed-forward: ``llama.SwiGluMlp`` in the first ``first_dense`` published
  layers, ``moe.DroplessMoE`` with its shared expert in the others;
- a final RMSNorm and an untied head (``lm_head``, (vocab, hidden));
- multi-token prediction (``mtp_layers`` 1, as the DeepSeek-V3 report §2.2
  composes it): ``h' = W_eh [norm_e(Emb(t_{i+1})) ; norm_h(h_i)]``, one
  further decoder layer, a norm of its own, then the main model's head:
  position i predicts token i+2. In training the model returns the pair
  (main, prediction module), both hidden states or both logits, and
  ``train/tasks.py::CausalLMTask`` adds ``mtp_loss_weight`` times the
  second loss. The module's block takes the full-length sequence (the
  embeddings moved one to the left; the last two positions are no target)
  so that the attention kernels stay block-aligned.

The defaults are the published configuration, written once here. A
deployment's share is said as ``models/lfm2.py`` says it: ``layers_kept``,
``experts_first`` / ``experts_held`` and ``vocab_size`` (embedding and head
are both over the slice); the prediction module stays with the head.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_example_tpu.models.llama import RMSNorm, SwiGluMlp
from distributed_pytorch_example_tpu.models.moe import DroplessMoE
from distributed_pytorch_example_tpu.models.transformer import _DenseParams
from distributed_pytorch_example_tpu.ops.attention import (
    dot_product_attention,
    fused_layout_eligible,
)
from distributed_pytorch_example_tpu.ops.rope import rope


class LatentAttention(nn.Module):
    """Multi-head latent attention as it trains (nothing absorbed, no
    compressed cache): ``c_q = norm(x W_qa)``; ``q = c_q W_qb``;
    ``[c_kv | k_r] = x W_kva``; ``[k_nope | v] = norm(c_kv) W_kvb``; RoPE on
    adjacent pairs of ``q_rope`` and of the one ``k_r``, which is laid out
    to every head; causal softmax of ``q k^T / sqrt(nope + rope)``."""

    model_dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        batch, seq, _ = x.shape
        heads, nope, rot, v_dim = (
            self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim,
            self.v_head_dim,
        )
        dt = self.dtype
        # where the flash kernel serves the call, q, k and v are built in its
        # head-major (B, N, S, H) layout and W_o reads its output as it
        # comes, so no transpose runs on either side of the kernel
        # (MultiHeadAttention's fused layout); elsewhere (B, S, N, H) through
        # dot_product_attention. Static, and the tree is the same either way:
        # the head-major products read nn.Dense's kernels through reshapes
        head_major = fused_layout_eligible(
            batch, seq, heads, heads, nope + rot, jnp.dtype(dt), causal=True,
            use_flash=self.use_flash, v_head_dim=v_dim,
        )
        seq_axis = 2 if head_major else 1

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=dt, name=name)

        def kernel(features, in_features, name):
            return _DenseParams(features, use_bias=False, name=name)(
                in_features
            )[0].astype(dt)

        def per_head(h, w, width):  # (B, S, r) -> (B, N, S, width)
            return jnp.einsum(
                "bsr,rnh->bnsh", h.astype(dt), w.reshape(-1, heads, width)
            )

        with jax.named_scope("mla_proj"):
            c_q = RMSNorm(self.norm_eps, dt, name="q_a_norm")(
                dense(self.q_lora_rank, "q_a")(x)
            )
            if head_major:
                q = per_head(
                    c_q, kernel(heads * (nope + rot), self.q_lora_rank, "q_b"),
                    nope + rot,
                )
            else:
                q = dense(heads * (nope + rot), "q_b")(c_q).reshape(
                    batch, seq, heads, nope + rot
                )
            c_kv, k_rot = jnp.split(
                dense(self.kv_lora_rank + rot, "kv_a")(x),
                [self.kv_lora_rank], axis=-1,
            )
            c_kv = RMSNorm(self.norm_eps, dt, name="kv_a_norm")(c_kv)
            if head_major:
                w_kv = kernel(
                    heads * (nope + v_dim), self.kv_lora_rank, "kv_b"
                ).reshape(self.kv_lora_rank, heads, nope + v_dim)
                k_nope = per_head(c_kv, w_kv[..., :nope], nope)
                v = per_head(c_kv, w_kv[..., nope:], v_dim)
                k_rot = k_rot[:, None, :, :]
            else:
                k_nope, v = jnp.split(
                    dense(heads * (nope + v_dim), "kv_b")(c_kv).reshape(
                        batch, seq, heads, nope + v_dim
                    ),
                    [nope], axis=-1,
                )
                k_rot = k_rot[:, :, None, :]
            q_rot = rope(
                q[..., nope:], theta=self.rope_theta, interleaved=True,
                seq_axis=seq_axis,
            )
            k_rot = rope(
                k_rot, theta=self.rope_theta, interleaved=True,
                seq_axis=seq_axis,
            )
            q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rot, k_nope.shape[:-1] + (rot,))],
                axis=-1,
            )
        if head_major:
            from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
                flash_attention_bnsh,
            )

            out = flash_attention_bnsh(
                q, k, v, causal=True, softmax_scale=(nope + rot) ** -0.5
            )
            with jax.named_scope("mla_proj"):
                return jnp.einsum(
                    "bnsh,nhd->bsd", out,
                    kernel(self.model_dim, heads * v_dim, "o").reshape(
                        heads, v_dim, self.model_dim
                    ),
                )
        out = dot_product_attention(
            q, k, v, causal=True, softmax_scale=(nope + rot) ** -0.5,
            use_flash=self.use_flash,
        )
        with jax.named_scope("mla_proj"):
            return dense(self.model_dim, "o")(
                out.reshape(batch, seq, heads * v_dim)
            )


class JoyaiBlock(nn.Module):
    """One decoder layer. Every field but ``experts`` is the stack's field
    of the same name (``JoyaiLlmFlash._block`` hands them over)."""

    experts: bool  # routed + shared experts, else the dense SwiGLU
    model_dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    mlp_dim: int
    moe_mlp_dim: int
    num_experts: int
    top_k: int
    shared_experts: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rope_theta: float
    norm_eps: float
    experts_first: int
    experts_held: Optional[int]
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        attention = LatentAttention(
            model_dim=self.model_dim, num_heads=self.num_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            use_flash=self.use_flash, name="attn",
        )
        if self.experts:
            ffn = DroplessMoE(
                num_experts=self.num_experts, mlp_dim=self.moe_mlp_dim,
                top_k=self.top_k, first_held=self.experts_first,
                experts_held=self.experts_held, use_select_bias=True,
                norm_topk=self.norm_topk_prob,
                scaling=self.routed_scaling_factor,
                shared_mlp_dim=self.shared_experts * self.moe_mlp_dim,
                dtype=self.dtype, name="moe",
            )
        else:
            ffn = SwiGluMlp(
                mlp_dim=self.mlp_dim, model_dim=self.model_dim,
                dtype=self.dtype, name="mlp",
            )
        x = x + attention(
            RMSNorm(self.norm_eps, self.dtype, name="attn_norm")(x), train=train
        )
        return x + ffn(
            RMSNorm(self.norm_eps, self.dtype, name="ffn_norm")(x), train=train
        )


class JoyaiLlmFlash(nn.Module):
    """JoyAI-LLM-Flash; the defaults are the published configuration."""

    vocab_size: int = 129280
    model_dim: int = 2048
    num_layers: int = 40
    first_dense: int = 1
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 7168
    moe_mlp_dim: int = 768
    num_experts: int = 256
    top_k: int = 8
    shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    mtp_layers: int = 1
    mtp_loss_weight: float = 0.3
    # the deployment's share (models/lfm2.py's fields); None: the whole
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
        """The untied head for the fused loss: ((V, D) table, no bias)."""
        return params["lm_head"], None

    def _block(self, index, x, train):
        shared = {
            f: getattr(self, f) for f in JoyaiBlock.__dataclass_fields__
            if f in type(self).__dataclass_fields__ and f not in ("name", "parent")
        }
        block = JoyaiBlock(
            experts=index >= self.first_dense, name=f"layer_{index}", **shared
        )
        if not self.remat:
            return block(x, train=train)
        # prevent_cse stays on: the layers are unrolled, not scanned
        # (models/lfm2.py has the reason)
        return nn.remat(
            lambda mdl, h: JoyaiBlock.__call__(mdl, h, train=train)
        )(block, x)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, targets=None):
        del targets  # no pipelined schedule here
        if self.logits_mode not in ("full", "hidden"):
            raise ValueError(
                f"logits_mode must be 'full' or 'hidden', got "
                f"{self.logits_mode!r}"
            )
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers is 0 or 1: the published module")
        kept = (
            tuple(range(self.num_layers)) if self.layers_kept is None
            else tuple(self.layers_kept)
        )
        if list(kept) != sorted(set(kept)) or not all(
            0 <= i < self.num_layers for i in kept
        ):
            raise ValueError(
                f"layers_kept {kept} must be rising indices of the "
                f"{self.num_layers} published layers"
            )
        embed = nn.Embed(
            self.vocab_size, self.model_dim,
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="tok_embed",
        )
        head = self.param(
            "lm_head", nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.model_dim),
        )

        def out(hidden):
            if self.logits_mode == "hidden":
                return hidden
            return jnp.einsum(
                "bsd,vd->bsv", hidden.astype(self.dtype),
                head.astype(self.dtype), preferred_element_type=jnp.float32,
            )

        x = embed(tokens).astype(self.dtype)
        for i in kept:
            x = self._block(i, x, train)
        x = RMSNorm(self.norm_eps, self.dtype, name="final_norm")(x)
        if not self.mtp_layers or not (train or self.is_initializing()):
            return out(x)
        with jax.named_scope("mtp"):
            # position i: the next token's embedding beside this position's
            # hidden state; the last position wraps round and is no target
            following = embed(jnp.roll(tokens, -1, axis=1)).astype(self.dtype)
            both = jnp.concatenate([
                RMSNorm(self.norm_eps, self.dtype, name="mtp_embed_norm")(following),
                RMSNorm(self.norm_eps, self.dtype, name="mtp_hidden_norm")(x),
            ], axis=-1)
            y = nn.Dense(
                self.model_dim, use_bias=False, dtype=self.dtype,
                name="mtp_proj",
            )(both)
            y = self._block(self.num_layers, y, train)
            y = RMSNorm(self.norm_eps, self.dtype, name="mtp_head_norm")(y)
        return out(x), out(y)
