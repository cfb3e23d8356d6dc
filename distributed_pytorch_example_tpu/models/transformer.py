"""Shared transformer building blocks for ViT / BERT / GPT-2.

The reference has no transformer (its model is a 3-layer MLP, reference
train.py:32-50); these blocks exist for the BASELINE.json workload configs.
They are written TPU-first:

- attention routes through ``ops.attention.dot_product_attention`` so kernel
  selection (XLA / Pallas flash / ring) is centralized and swappable;
- projections are named ``q/k/v/o`` and ``up/down`` so the tensor-parallel
  partition rules in ``parallel/partition.py`` can target them by path regex
  (Megatron-style column/row split, expressed as GSPMD shardings — XLA
  propagates activation shardings and inserts the collectives);
- compute dtype is a field (bfloat16 on TPU keeps the MXU fed); params stay
  float32 (flax ``param_dtype`` default) for stable optimizer math;
- optional ``remat`` wraps each block in ``nn.remat`` to trade FLOPs for HBM
  on long sequences.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_example_tpu.models.moe import MoEMlpBlock
from distributed_pytorch_example_tpu.ops.attention import (
    dot_product_attention,
    fused_layout_eligible,
)
from distributed_pytorch_example_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
)


class _DenseParams(nn.Module):
    """Owns an nn.Dense-compatible (kernel, bias) WITHOUT applying them.

    The fused projection layout needs the raw arrays (it contracts them in
    a reshaped einsum); names/init mirror nn.Dense exactly so the param
    tree — and therefore checkpoints — stay identical whichever attention
    path a platform takes.
    """

    features: int

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (in_features, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        return kernel, bias


def tied_head_logits(x, embedding, dtype) -> jax.Array:
    """LM-head logits against a tied embedding matrix.

    bf16 operands on the MXU with float32 accumulation: float32 logits for
    a stable softmax at bf16 matmul speed. Shared by GPT-2 and BERT.
    """
    return jax.lax.dot_general(
        x, embedding.astype(dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


class MultiHeadAttention(nn.Module):
    """Self-attention with centralized kernel dispatch.

    Layout is (batch, seq, heads, head_dim) end to end — the MXU/sequence-
    sharding friendly layout (see ops/attention.py).

    ``seq_axis``: name of a mesh axis to run ring attention over (sequence/
    context parallelism). The active mesh comes from the enclosing
    ``with mesh:`` context; no device ever holds full-sequence K/V.
    """

    num_heads: int
    head_dim: int
    model_dim: int
    causal: bool = False
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None  # None = auto-select
    seq_axis: Optional[str] = None  # mesh axis for ring attention
    num_kv_heads: Optional[int] = None  # < num_heads = GQA (None = MHA)
    rope: bool = False  # rotary embeddings on q/k (LLaMA-style)
    rope_theta: float = 10000.0
    # RMSNorm over each head's dims of q and of k, with a learned gain,
    # BEFORE RoPE (models/lfm2.py); off, the module traces what it traced
    qk_norm: bool = False
    qk_norm_epsilon: float = 1e-5
    use_bias: bool = True  # biases on the four projections
    sp_mode: str = "ring"  # sequence parallelism: "ring" | "ulysses"
    decode: bool = False  # autoregressive KV-cache mode (train/generate.py)
    # paged KV cache (graft-serve, serving/engine.py). > 0 switches decode
    # mode from the contiguous per-call cache to a fixed block pool +
    # per-row page tables: ``paged_num_blocks`` blocks of
    # ``paged_block_size`` tokens shared by every resident request, with
    # at most ``paged_max_blocks`` table entries per batch row. Block 0 is
    # a scratch block: unallocated table entries point at it, so writes
    # past a row's true length land harmlessly.
    paged_num_blocks: int = 0
    paged_block_size: int = 16
    paged_max_blocks: int = 0
    # speculative-verify mode (serving/engine.py): seq > 1 calls are a
    # multi-token DECODE chunk (the target model scoring drafted tokens
    # at positions row_lens..row_lens+seq-1) instead of a fresh-row
    # prefill. Static, so the verify program compiles separately from
    # the prefill program (the engine clones the model with this set).
    paged_verify: bool = False

    @nn.compact
    def __call__(self, x, mask=None, *, kv_mask=None, train: bool = False):
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got {self.sp_mode!r}"
            )
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by num_kv_heads "
                f"{kv_heads}"
            )
        features = self.num_heads * self.head_dim
        kv_features = kv_heads * self.head_dim
        batch, seq = x.shape[0], x.shape[1]
        # fused projection layout: when the flash kernel will serve this
        # call anyway, project straight to its head-major (B, N, S, H)
        # layout (einsum prologue/epilogue) instead of paying the
        # transpose sandwich — measured ~0.22 ms/layer fwd+bwd at GPT-2
        # bench shapes (results/lm_mfu_analysis/bsnh_ab.json). Static
        # decision (shapes/dtype/platform), so a given model instance
        # always creates the same param tree; the `_DenseParams` modules
        # mirror nn.Dense's names/init exactly, keeping checkpoints
        # interchangeable between the paths.
        fused = (
            not self.decode
            and not self.rope
            and not self.qk_norm
            and self.use_bias
            and mask is None
            and kv_mask is None
            and self.seq_axis is None
            and fused_layout_eligible(
                batch, seq, self.num_heads, kv_heads, self.head_dim,
                jnp.dtype(self.dtype), causal=self.causal,
                use_flash=self.use_flash,
            )
        )
        if fused:
            return self._fused_layout_attention(
                x, features, kv_features, kv_heads, train
            )
        def dense(width, name):
            return nn.Dense(
                width, use_bias=self.use_bias, dtype=self.dtype, name=name
            )

        q = dense(features, "q")(x)
        k = dense(kv_features, "k")(x)
        v = dense(kv_features, "v")(x)
        q = q.reshape(batch, seq, self.num_heads, self.head_dim)
        k = k.reshape(batch, seq, kv_heads, self.head_dim)
        v = v.reshape(batch, seq, kv_heads, self.head_dim)
        if self.qk_norm:
            from distributed_pytorch_example_tpu.models.llama import RMSNorm

            q = RMSNorm(self.qk_norm_epsilon, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.qk_norm_epsilon, self.dtype, name="k_norm")(k)

        if self.decode:
            if not self.causal or mask is not None or kv_mask is not None \
                    or self.seq_axis is not None:
                raise ValueError(
                    "decode mode supports causal attention only, without "
                    "masks or sequence parallelism"
                )
            if self.paged_num_blocks > 0:
                out = self._paged_step(q, k, v, batch, seq, kv_heads)
            else:
                out = self._decode_step(q, k, v, batch, seq, kv_heads)
            out = out.reshape((batch, seq, features))
            out = dense(self.model_dim, "o")(out)
            return out

        if self.rope:
            from distributed_pytorch_example_tpu.ops.rope import rope

            q = rope(q, theta=self.rope_theta)
            k = rope(k, theta=self.rope_theta)
        # NB: RoPE above runs on the GLOBAL (pre-shard_map) arrays, so
        # positions are globally correct under either SP mode.
        ring_mesh = self._ring_mesh(mask)
        if ring_mesh is not None and self.sp_mode == "ulysses":
            from distributed_pytorch_example_tpu.ops.ulysses import (
                ulysses_attention_sharded,
            )

            out = ulysses_attention_sharded(
                q, k, v, ring_mesh, seq_axis=self.seq_axis,
                kv_mask=kv_mask, causal=self.causal,
                use_flash=self.use_flash,
            )
        elif ring_mesh is not None:
            from distributed_pytorch_example_tpu.ops.ring_attention import (
                ring_attention_sharded,
            )

            out = ring_attention_sharded(
                q, k, v, ring_mesh, seq_axis=self.seq_axis,
                kv_mask=kv_mask, causal=self.causal,
                use_flash=self.use_flash,
            )
        else:
            out = dot_product_attention(
                q, k, v, mask=mask, kv_mask=kv_mask, causal=self.causal,
                use_flash=self.use_flash,
            )
        out = out.reshape((batch, seq, features))
        out = dense(self.model_dim, "o")(out)
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out

    def _fused_layout_attention(self, x, features, kv_features, kv_heads,
                                train):
        """Head-major attention: projections emit (B, N, S, H) directly.

        einsum('bsd,dnh->bnsh') prologue + einsum('bnsh,nhd->bsd')
        epilogue around the transpose-free flash entry
        (ops/pallas/flash_attention.flash_attention_bnsh) — no standalone
        transpose op for XLA to schedule. A/B-measured worth ~2% of the
        GPT-2 bench step (results/lm_mfu_analysis/bsnh_ab.json).
        """
        from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
            flash_attention_bnsh,
        )

        n, kv_n, h = self.num_heads, kv_heads, self.head_dim
        in_dim = x.shape[-1]
        dt = self.dtype
        kq, bq = _DenseParams(features, name="q")(in_dim)
        kk, bk = _DenseParams(kv_features, name="k")(in_dim)
        kv_w, bv = _DenseParams(kv_features, name="v")(in_dim)
        ko, bo = _DenseParams(self.model_dim, name="o")(features)
        xd = x.astype(dt)

        def project(w, b, heads):
            return jnp.einsum(
                "bsd,dnh->bnsh", xd, w.reshape(in_dim, heads, h).astype(dt)
            ) + b.reshape(heads, h).astype(dt)[None, :, None, :]

        q = project(kq, bq, n)
        k = project(kk, bk, kv_n)
        v = project(kv_w, bv, kv_n)
        out = flash_attention_bnsh(q, k, v, causal=self.causal)
        out = jnp.einsum(
            "bnsh,nhd->bsd", out, ko.reshape(n, h, self.model_dim).astype(dt)
        ) + bo.astype(dt)
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out

    def _decode_step(self, q, k, v, batch, seq, kv_heads):
        """KV-cache attention: write this call's K/V at the cache cursor,
        attend the new queries against everything cached so far.

        The cache is created at init time with the full sequence length
        (``generate`` inits the model on a max-length dummy); decode calls
        then feed 1..n new tokens. Positions come from the cursor, so RoPE
        stays globally consistent across incremental calls.
        """
        from jax import lax

        is_init = self.has_variable("cache", "cached_key")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (batch, seq, kv_heads, self.head_dim), self.dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (batch, seq, kv_heads, self.head_dim), self.dtype,
        )
        cursor = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if not is_init:  # init pass: just size the cache, output is unused
            return jnp.zeros(
                (batch, seq, self.num_heads, self.head_dim), self.dtype
            )

        idx = cursor.value
        positions = idx + jnp.arange(seq)
        if self.rope:
            from distributed_pytorch_example_tpu.ops.rope import rope

            q = rope(q, positions=positions, theta=self.rope_theta)
            k = rope(k, positions=positions, theta=self.rope_theta)
        cached_k.value = lax.dynamic_update_slice(
            cached_k.value, k.astype(cached_k.value.dtype), (0, idx, 0, 0)
        )
        cached_v.value = lax.dynamic_update_slice(
            cached_v.value, v.astype(cached_v.value.dtype), (0, idx, 0, 0)
        )
        cursor.value = idx + seq
        cache_len = cached_k.value.shape[1]
        # causal against the cursor: new query t may see keys [0, idx + t]
        key_pos = jnp.arange(cache_len)[None, None, None, :]
        visible = key_pos <= positions[None, None, :, None]
        return dot_product_attention(
            q, cached_k.value, cached_v.value, mask=visible, causal=False,
            use_flash=False,  # 1..n-token queries: XLA path is right-sized
        )

    def _paged_step(self, q, k, v, batch, seq, kv_heads):
        """Paged-KV attention (graft-serve): a fixed block pool shared by
        all resident requests, addressed through per-row page tables.

        Cache variables per attention layer:

        - ``pages_k`` / ``pages_v`` (num_blocks, block_size, kv_heads,
          head_dim) — the pool. Sharded like the contiguous cache: the
          kv-heads dim over ``tensor``; the block dim takes the batch
          row's place over the data axes (serving/engine.py constrains
          both, and its allocator keeps a slot's blocks on the slot's
          data shard).
        - ``page_table`` (batch, max_blocks) int32 — block j of row b
          lives in pool block ``page_table[b, j]``. Entry 0 (the scratch
          block) absorbs writes past a row's allocation.
        - ``row_lens`` (batch,) int32 — tokens already cached per row.

        Unlike the contiguous path's ``cache_index`` cursor, the table
        and lengths are OWNED BY THE HOST scheduler: the engine rewrites
        them between steps (insertion/eviction), so this method never
        updates them. Static shape split: ``seq > 1`` is the bucketed
        prefill program (or, under ``paged_verify``, the speculative
        verify program), ``seq == 1`` the one-token-per-slot decode
        program — together the compiled programs of the engine.
        """
        nb, bs = self.paged_num_blocks, self.paged_block_size
        mb = self.paged_max_blocks
        if nb < 2 or bs < 1 or mb < 1:
            raise ValueError(
                "paged decode needs paged_num_blocks >= 2 (block 0 is "
                "scratch), paged_block_size >= 1 and paged_max_blocks >= "
                f"1; got {nb}/{bs}/{mb}"
            )
        is_init = self.has_variable("cache", "pages_k")
        pages_k = self.variable(
            "cache", "pages_k", jnp.zeros,
            (nb, bs, kv_heads, self.head_dim), self.dtype,
        )
        pages_v = self.variable(
            "cache", "pages_v", jnp.zeros,
            (nb, bs, kv_heads, self.head_dim), self.dtype,
        )
        table = self.variable(
            "cache", "page_table", jnp.zeros, (batch, mb), jnp.int32
        )
        lens = self.variable(
            "cache", "row_lens", jnp.zeros, (batch,), jnp.int32
        )
        if not is_init:  # init pass: just size the pool, output is unused
            return jnp.zeros(
                (batch, seq, self.num_heads, self.head_dim), self.dtype
            )

        positions = lens.value[:, None] + jnp.arange(seq)[None, :]  # (B, S)
        if self.rope:
            from distributed_pytorch_example_tpu.ops.rope import rope

            q = rope(q, positions=positions, theta=self.rope_theta)
            k = rope(k, positions=positions, theta=self.rope_theta)

        if seq > 1 and not self.paged_verify:
            # ---- prefill: fresh rows (row_lens == 0 by engine contract),
            # bucket-padded to a multiple of the block size. Attention is
            # plain causal self-attention over this call's tokens (pad
            # tokens sit at later positions, so real logits never see
            # them); K/V land in the rows' pool blocks via ONE batched
            # scatter over the (row, block) table entries, so XLA compile
            # time no longer scales with the bucket's block count the way
            # the old unrolled dynamic_update_slice loop did.
            if seq % bs:
                raise ValueError(
                    f"prefill length {seq} must be a multiple of "
                    f"paged_block_size {bs}"
                )
            n_blk = seq // bs
            if n_blk > mb:
                raise ValueError(
                    f"prefill bucket {seq} needs {n_blk} blocks > "
                    f"paged_max_blocks {mb}"
                )
            kb = k.astype(pages_k.value.dtype).reshape(
                batch * n_blk, bs, kv_heads, self.head_dim
            )
            vb = v.astype(pages_v.value.dtype).reshape(
                batch * n_blk, bs, kv_heads, self.head_dim
            )
            block_ids = table.value[:, :n_blk].reshape(-1)  # (B * n_blk,)
            pages_k.value = pages_k.value.at[block_ids].set(kb)
            pages_v.value = pages_v.value.at[block_ids].set(vb)
            return dot_product_attention(
                q, k, v, causal=True, use_flash=False,
            )

        # ---- decode (seq == 1) / speculative verify (seq > 1): token s of
        # row b sits at absolute position positions[b, s]. One vectorized
        # scatter into (block, offset) pairs; inactive rows' tables are
        # all-scratch, so their writes pile up on block 0 and are never
        # read by a live row. Verify chunks can run past a row's true
        # length near the context limit — out-of-table block indices are
        # routed to the scratch block explicitly (those queries' logits
        # are discarded by the host-side acceptance loop).
        blk_j = positions // bs  # (B, S)
        block_idx = jnp.where(
            blk_j < mb,
            jnp.take_along_axis(table.value, jnp.minimum(blk_j, mb - 1), axis=1),
            0,
        )
        off = positions % bs
        pages_k.value = pages_k.value.at[block_idx, off].set(
            k.astype(pages_k.value.dtype)
        )
        pages_v.value = pages_v.value.at[block_idx, off].set(
            v.astype(pages_v.value.dtype)
        )
        # pooled key j*bs + o is exactly the token at position j*bs + o,
        # so visibility is the same `key_pos <= position` predicate the
        # contiguous path uses — numerics match token-for-token. The
        # fused Pallas kernel (ops/pallas/paged_attention.py) reads live
        # blocks straight from the pool via the scalar-prefetched table;
        # off-TPU the dispatcher's XLA fallback gathers the pool exactly
        # like the historical decode path (bit-identical).
        with jax.named_scope("paged_decode_fused"):
            return paged_decode_attention(
                q, pages_k.value, pages_v.value, table.value, positions
            )

    def _ring_mesh(self, mask):
        """The active mesh when sequence parallelism should run, else None.

        ``seq_axis`` set but no active mesh is a configuration error, not a
        fallback: silently taking the dense path would materialize the full
        S x S logits the user sharded the sequence to avoid. Key-padding
        ``kv_mask``s stream through both SP modes; only full (Q, K)
        attention-matrix masks are unsupported.
        """
        if self.seq_axis is None:
            return None
        if mask is not None:
            raise NotImplementedError(
                "custom (Q, K) attention-matrix masks are not supported on "
                "the sequence-parallel paths; key-padding masks go through "
                "kv_mask"
            )
        from distributed_pytorch_example_tpu.runtime.mesh import current_mesh

        mesh = current_mesh()
        if mesh is None or self.seq_axis not in mesh.axis_names:
            # a mesh that lacks the axis entirely is the missing-context
            # case too (framework meshes always carry every axis, span-1
            # axes included) — silently tracing the dense path here would
            # materialize the S x S logits the user sharded to avoid
            raise RuntimeError(
                f"seq_axis={self.seq_axis!r} requires an active `with mesh:` "
                "context whose mesh has that axis (Trainer.train_epoch "
                "enters it automatically; wrap manual apply()/train_step "
                "calls yourself)."
            )
        if mesh.shape[self.seq_axis] <= 1:
            return None  # axis present but span 1: dense path is exact
        return mesh


class MlpBlock(nn.Module):
    """Position-wise feed-forward: up-project → activation → down-project."""

    mlp_dim: int
    model_dim: int
    activation: Callable = nn.gelu
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = nn.Dense(self.mlp_dim, dtype=self.dtype, name="up")(x)
        x = self.activation(x)
        x = nn.Dense(self.model_dim, dtype=self.dtype, name="down")(x)
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        return x


class TransformerBlock(nn.Module):
    """One encoder/decoder block; pre-LN (GPT/ViT) or post-LN (BERT)."""

    num_heads: int
    head_dim: int
    model_dim: int
    mlp_dim: int
    causal: bool = False
    prenorm: bool = True
    dropout_rate: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None
    seq_axis: Optional[str] = None
    sp_mode: str = "ring"
    decode: bool = False
    paged_num_blocks: int = 0  # >0: paged KV cache (serving/engine.py)
    paged_block_size: int = 16
    paged_max_blocks: int = 0
    paged_verify: bool = False  # seq>1 = speculative verify chunk
    moe_experts: int = 0  # >0: Mixture-of-Experts MLP with this many experts
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, mask=None, *, kv_mask=None, train: bool = False):
        attn = MultiHeadAttention(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            model_dim=self.model_dim,
            causal=self.causal,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            use_flash=self.use_flash,
            seq_axis=self.seq_axis,
            sp_mode=self.sp_mode,
            decode=self.decode,
            paged_num_blocks=self.paged_num_blocks,
            paged_block_size=self.paged_block_size,
            paged_max_blocks=self.paged_max_blocks,
            paged_verify=self.paged_verify,
            name="attn",
        )
        if self.moe_experts:
            mlp = MoEMlpBlock(
                num_experts=self.moe_experts,
                mlp_dim=self.mlp_dim,
                model_dim=self.model_dim,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                name="moe",
            )
        else:
            mlp = MlpBlock(
                mlp_dim=self.mlp_dim,
                model_dim=self.model_dim,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                name="mlp",
            )
        ln1 = nn.LayerNorm(epsilon=self.layer_norm_epsilon, dtype=self.dtype, name="ln1")
        ln2 = nn.LayerNorm(epsilon=self.layer_norm_epsilon, dtype=self.dtype, name="ln2")
        if self.prenorm:
            x = x + attn(ln1(x), mask, kv_mask=kv_mask, train=train)
            x = x + mlp(ln2(x), train=train)
        else:  # post-LN (original BERT)
            x = ln1(x + attn(x, mask, kv_mask=kv_mask, train=train))
            x = ln2(x + mlp(x, train=train))
        return x


class TransformerStack(nn.Module):
    """N homogeneous transformer blocks.

    With ``remat=True`` each block is rematerialized (``jax.checkpoint``
    lifted through flax): activations are recomputed in the backward pass,
    trading FLOPs for HBM — the standard TPU long-sequence memory lever.
    The ``train`` flag stays a static closure capture, never a traced arg.
    """

    num_layers: int
    num_heads: int
    head_dim: int
    model_dim: int
    mlp_dim: int
    causal: bool = False
    prenorm: bool = True
    dropout_rate: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None
    seq_axis: Optional[str] = None
    sp_mode: str = "ring"
    decode: bool = False
    paged_num_blocks: int = 0  # >0: paged KV cache (serving/engine.py)
    paged_block_size: int = 16
    paged_max_blocks: int = 0
    paged_verify: bool = False  # seq>1 = speculative verify chunk
    remat: bool = False
    moe_experts: int = 0
    moe_every: int = 2  # MoE MLP on every Nth block (Switch uses 2)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, mask=None, *, kv_mask=None, train: bool = False):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(
                f"moe_every must be >= 1 when moe_experts > 0, got "
                f"{self.moe_every}"
            )
        for i in range(self.num_layers):
            is_moe = self.moe_experts > 0 and i % self.moe_every == self.moe_every - 1
            block = TransformerBlock(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                model_dim=self.model_dim,
                mlp_dim=self.mlp_dim,
                causal=self.causal,
                prenorm=self.prenorm,
                dropout_rate=self.dropout_rate,
                layer_norm_epsilon=self.layer_norm_epsilon,
                dtype=self.dtype,
                use_flash=self.use_flash,
                seq_axis=self.seq_axis,
                sp_mode=self.sp_mode,
                decode=self.decode,
                paged_num_blocks=self.paged_num_blocks,
                paged_block_size=self.paged_block_size,
                paged_max_blocks=self.paged_max_blocks,
                paged_verify=self.paged_verify,
                moe_experts=self.moe_experts if is_moe else 0,
                moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                name=f"layer_{i}",
            )
            if self.remat:
                apply = nn.remat(
                    lambda mdl, h, m, km: TransformerBlock.__call__(
                        mdl, h, m, kv_mask=km, train=train
                    ),
                    prevent_cse=False,
                )
                x = apply(block, x, mask, kv_mask)
            else:
                x = block(x, mask, kv_mask=kv_mask, train=train)
        return x
