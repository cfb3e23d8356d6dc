"""GPT-2 (Radford et al. 2019) in plain float32 jax.numpy: the yardstick the
timed training step is held to.

Written from the published description and the released ``config.json``
(openai-community/gpt2): token + learned position embeddings, ``n_layer``
pre-LayerNorm blocks (causal multi-head attention, then a 4x MLP with the
tanh form of GELU, "gelu_new"), a final LayerNorm, and logits against the
tied token embedding. The loss is the mean next-token cross-entropy over
every position but the last. No kernels, no cache, no mixed precision;
nothing here imports the program.

``dot`` is the one matrix product every layer goes through, so that the
control (the same mathematics in fp8) swaps one function.

Departures from the release: weights are random (normal 0.02, residual
projections scaled by 1/sqrt(2 n_layer) as the paper says, zero biases),
and dropout is 0 — the program trains without it, as the configuration
file states.
"""

import math

import jax
import jax.numpy as jnp

def init_params(key, sizes):
    """Flat dict name -> float32 array, made on the device from ``key``."""
    d, layers = sizes["n_embd"], sizes["n_layer"]
    inner = sizes["n_inner"] or 4 * d
    std = sizes["initializer_range"]
    shapes = {"wte": (sizes["vocab_size"], d), "wpe": (sizes["n_positions"], d)}
    for i in range(layers):
        for name in ("q", "k", "v", "o"):
            shapes[f"h{i}.{name}.w"] = (d, d)
        shapes[f"h{i}.up.w"] = (d, inner)
        shapes[f"h{i}.down.w"] = (inner, d)
    params = {}
    for n, (name, shape) in enumerate(sorted(shapes.items())):
        scale = std
        if name.endswith(("o.w", "down.w")):
            scale = std / math.sqrt(2 * layers)
        params[name] = scale * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32
        )
    for i in range(layers):
        for ln in ("ln1", "ln2"):
            params[f"h{i}.{ln}.g"] = jnp.ones((d,), jnp.float32)
            params[f"h{i}.{ln}.b"] = jnp.zeros((d,), jnp.float32)
        for name, width in (("q", d), ("k", d), ("v", d), ("o", d),
                            ("up", inner), ("down", d)):
            params[f"h{i}.{name}.b"] = jnp.zeros((width,), jnp.float32)
    params["ln_f.g"] = jnp.ones((d,), jnp.float32)
    params["ln_f.b"] = jnp.zeros((d,), jnp.float32)
    return params


def layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def attention(x, p, prefix, heads, causal, dot):
    rows, seq, d = x.shape
    hd = d // heads

    def split(t):
        return t.reshape(rows, seq, heads, hd).transpose(0, 2, 1, 3)

    q = split(dot(x, p[prefix + "q.w"]) + p[prefix + "q.b"])
    k = split(dot(x, p[prefix + "k.w"]) + p[prefix + "k.b"])
    v = split(dot(x, p[prefix + "v.w"]) + p[prefix + "v.b"])
    scores = dot(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
    if causal:
        keep = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    out = dot(jax.nn.softmax(scores, axis=-1), v)
    out = out.transpose(0, 2, 1, 3).reshape(rows, seq, d)
    return dot(out, p[prefix + "o.w"]) + p[prefix + "o.b"]


def mlp(x, p, prefix, dot):
    h = gelu_tanh(dot(x, p[prefix + "up.w"]) + p[prefix + "up.b"])
    return dot(h, p[prefix + "down.w"]) + p[prefix + "down.b"]


def token_losses(hidden, table, bias, targets, dot):
    """Cross-entropy of each position against the tied embedding."""
    logits = dot(hidden, table.T)
    if bias is not None:
        logits = logits + bias
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def loss_sum(params, rows, sizes, dot):
    """(sum of the next-token losses of ``rows``, how many there are).

    ``rows`` is ``{"tokens": (r, s) int32}``: a block of a step's batch. The
    step's loss is the sum over its blocks divided by the count."""
    tokens = rows["tokens"]
    eps, heads = sizes["layer_norm_epsilon"], sizes["n_head"]
    seq = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:seq]
    for i in range(sizes["n_layer"]):
        pre = f"h{i}."
        x = x + attention(
            layer_norm(x, params[pre + "ln1.g"], params[pre + "ln1.b"], eps),
            params, pre, heads, True, dot,
        )
        x = x + mlp(
            layer_norm(x, params[pre + "ln2.g"], params[pre + "ln2.b"], eps),
            params, pre, dot,
        )
    x = layer_norm(x, params["ln_f.g"], params["ln_f.b"], eps)
    losses = token_losses(x[:, :-1], params["wte"], None, tokens[:, 1:], dot)
    return losses.sum(), losses.size


def step_rows(tokens, mask_key, step, sizes):
    """What ``loss_sum`` needs of one step's batch, whole. GPT-2 draws
    nothing at random in a step."""
    del mask_key, step, sizes
    return {"tokens": tokens}


def program_names(sizes):
    """This file's leaf names in the nesting the program's GPT2 module keeps
    its weights in (flax names; kernels are (in, out) on both sides, and the
    program's position table carries a leading axis of 1)."""
    def dense(prefix):
        return {"kernel": prefix + ".w", "bias": prefix + ".b"}

    def norm(prefix):
        return {"scale": prefix + ".g", "bias": prefix + ".b"}

    decoder = {}
    for i in range(sizes["n_layer"]):
        pre = f"h{i}."
        decoder[f"layer_{i}"] = {
            "attn": {n: dense(pre + n) for n in ("q", "k", "v", "o")},
            "ln1": norm(pre + "ln1"),
            "ln2": norm(pre + "ln2"),
            "mlp": {"up": dense(pre + "up"), "down": dense(pre + "down")},
        }
    return {
        "decoder": decoder,
        "final_ln": norm("ln_f"),
        "wpe": "wpe",
        "wte": {"embedding": "wte"},
    }
