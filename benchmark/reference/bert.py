"""BERT-base (Devlin et al. 2018) with its masked-LM head, in plain float32
jax.numpy: the yardstick the timed training step is held to.

Written from the paper and the released ``config.json``
(google-bert/bert-base-uncased): token + learned position embeddings, an
embedding LayerNorm, ``num_hidden_layers`` post-LayerNorm blocks
(bidirectional multi-head attention, then a 4x GELU MLP), and the MLM head:
dense, GELU, LayerNorm, logits against the tied token embedding plus a bias.
The loss is the mean cross-entropy over the positions selected for masking.
Nothing here imports the program.

Departures, each shared with the program and stated in the configuration
file: GELU in its tanh form (google-research/bert ``modeling.py gelu()``;
the transformers library computes the erf form); no segment embedding and
no next-sentence head (single-segment masked-LM pre-training only); random
weights (normal 0.02, zero biases); dropout 0.

The masking is made inside the program's step from a key in its state, so
the recipe (15% of positions; of those 80% [MASK], 10% a random token, 10%
kept) and the order of its draws are the one thing this file takes from the
program's text: ``mask_batch`` draws with ``jax.random`` from the key the
benchmark itself put into the state.
"""

import jax
import jax.numpy as jnp

from reference.gpt2 import attention, gelu_tanh, layer_norm, mlp, token_losses

MASK_TOKEN_ID = 103
MASK_RATE = 0.15


def init_params(key, sizes):
    """Flat dict name -> float32 array, made on the device from ``key``."""
    d, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    inner, std = sizes["intermediate_size"], sizes["initializer_range"]
    shapes = {
        "tok": (sizes["vocab_size"], d),
        "pos": (sizes["max_position_embeddings"], d),
        "head.dense.w": (d, d),
    }
    for i in range(layers):
        for name in ("q", "k", "v", "o"):
            shapes[f"h{i}.{name}.w"] = (d, d)
        shapes[f"h{i}.up.w"] = (d, inner)
        shapes[f"h{i}.down.w"] = (inner, d)
    params = {
        name: std * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32
        )
        for n, (name, shape) in enumerate(sorted(shapes.items()))
    }
    norms = ["emb_ln", "head.ln"]
    for i in range(layers):
        norms += [f"h{i}.ln1", f"h{i}.ln2"]
        for name, width in (("q", d), ("k", d), ("v", d), ("o", d),
                            ("up", inner), ("down", d)):
            params[f"h{i}.{name}.b"] = jnp.zeros((width,), jnp.float32)
    for name in norms:
        params[name + ".g"] = jnp.ones((d,), jnp.float32)
        params[name + ".b"] = jnp.zeros((d,), jnp.float32)
    params["head.dense.b"] = jnp.zeros((d,), jnp.float32)
    params["head.bias"] = jnp.zeros((sizes["vocab_size"],), jnp.float32)
    return params


def mask_batch(tokens, mask_key, step, sizes):
    """(inputs after masking, which positions are scored) for the whole
    batch of optimizer step ``step`` (0-based)."""
    key = jax.random.fold_in(jax.random.fold_in(mask_key, step), 1)
    k_select, k_kind, k_random, _ = jax.random.split(key, 4)
    selected = jax.random.uniform(k_select, tokens.shape) < MASK_RATE
    kind = jax.random.uniform(k_kind, tokens.shape)
    random_tokens = jax.random.randint(
        k_random, tokens.shape, 0, sizes["vocab_size"], dtype=tokens.dtype
    )
    inputs = jnp.where(
        selected & (kind < 0.8), jnp.asarray(MASK_TOKEN_ID, tokens.dtype),
        jnp.where(selected & (kind >= 0.9), random_tokens, tokens),
    )
    return inputs, selected


def step_rows(tokens, mask_key, step, sizes):
    """What ``loss_sum`` needs of one step's batch, whole; the caller cuts
    every entry into the same blocks of rows."""
    inputs, selected = mask_batch(tokens, mask_key, step, sizes)
    return {"inputs": inputs, "targets": tokens, "selected": selected}


def loss_sum(params, rows, sizes, dot):
    """(sum of the masked-LM losses of a block of rows, how many positions
    were scored). The step's loss is the sum over blocks over the count."""
    inputs = rows["inputs"]
    eps, heads = sizes["layer_norm_eps"], sizes["num_attention_heads"]
    seq = inputs.shape[1]
    x = params["tok"][inputs] + params["pos"][:seq]
    x = layer_norm(x, params["emb_ln.g"], params["emb_ln.b"], eps)
    for i in range(sizes["num_hidden_layers"]):
        pre = f"h{i}."
        x = layer_norm(
            x + attention(x, params, pre, heads, False, dot),
            params[pre + "ln1.g"], params[pre + "ln1.b"], eps,
        )
        x = layer_norm(
            x + mlp(x, params, pre, dot),
            params[pre + "ln2.g"], params[pre + "ln2.b"], eps,
        )
    x = gelu_tanh(dot(x, params["head.dense.w"]) + params["head.dense.b"])
    x = layer_norm(x, params["head.ln.g"], params["head.ln.b"], eps)
    losses = token_losses(
        x, params["tok"], params["head.bias"], rows["targets"], dot
    )
    selected = rows["selected"]
    return jnp.where(selected, losses, 0.0).sum(), selected.sum()


def program_names(sizes):
    """This file's leaf names in the nesting the program's BertBase module
    keeps its weights in (flax names; kernels are (in, out) on both sides,
    and the program's position table carries a leading axis of 1)."""
    def dense(prefix):
        return {"kernel": prefix + ".w", "bias": prefix + ".b"}

    def norm(prefix):
        return {"scale": prefix + ".g", "bias": prefix + ".b"}

    encoder = {}
    for i in range(sizes["num_hidden_layers"]):
        pre = f"h{i}."
        encoder[f"layer_{i}"] = {
            "attn": {n: dense(pre + n) for n in ("q", "k", "v", "o")},
            "ln1": norm(pre + "ln1"),
            "ln2": norm(pre + "ln2"),
            "mlp": {"up": dense(pre + "up"), "down": dense(pre + "down")},
        }
    return {
        "embed_ln": norm("emb_ln"),
        "encoder": encoder,
        "mlm_bias": "head.bias",
        "mlm_dense": dense("head.dense"),
        "mlm_ln": norm("head.ln"),
        "pos_embed": "pos",
        "tok_embed": {"embedding": "tok"},
    }
