"""LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``) in plain float32
jax.numpy: the yardstick the timed training step is held to.

Written from the released ``config.json``'s keys and the layer equations
(the dense sibling's operators, norms and tied head; the expert block from
``use_expert_bias``, ``norm_topk_prob``, ``routed_scaling_factor``,
``num_experts_per_tok``). ``x`` is (rows, seq, hidden); no biases anywhere;
RMSNorm(x) = x / sqrt(mean x^2 + norm_eps) * gain.

* Layer: ``h = x + operator(norm_op(x))``; ``y = h + ffn(norm_ffn(h))``.
  After the last layer a final RMSNorm, then logits against the tied table.
  Positions enter through RoPE in the attention layers only.
* ``layer_types[i] == "conv"``: ``B, C, u = split3(x W_in)``; ``z = B u``;
  ``c_t = sum_j taps[:, j] z_{t - (L-1) + j}`` (depthwise, causal,
  ``conv_L_cache`` taps a channel, zeros before the row's first token);
  ``(C c) W_out``.
* ``"full_attention"``: q (``num_attention_heads`` of hidden / heads), k, v
  (``num_key_value_heads``); RMSNorm with a gain over each head of q and of
  k BEFORE RoPE (``rope_theta``, rotate-half); causal softmax(q k^T /
  sqrt(head)) v, each key head serving heads / key-heads query heads.
* Feed-forward of layers before ``num_dense_layers``: ``(silu(x W1) x W3)
  W2`` at ``intermediate_size``.
* Every other layer, experts: ``s = sigmoid(x Wr)`` over all published
  experts; the ``num_experts_per_tok`` with the largest ``s + bias`` are
  chosen (the bias picks and does not weigh; it is a buffer, not trained);
  their weights are ``s`` at the chosen over (their sum + 1e-6) where
  ``norm_topk_prob``, times ``routed_scaling_factor``; ``y = sum_chosen
  weight_e SwiGLU_e(x)`` at ``moe_intermediate_size``.

The share. ``num_experts`` in the configuration's file counts the experts
HELD (published experts ``experts_first`` ..), ``published.num_experts`` is
the router's width. Router, choice and normalisation run over all the
published experts; only chosen experts that are held contribute, and what
the absent ones would have added is left out. The experts are computed the
plain way: every held expert on every token, times a weight that is zero
where the expert was not chosen. No sort, no gather, no bound.

Memory at 8192 tokens a row: attention goes one query head at a time
(``lax.map`` with ``jax.checkpoint``: one head's scores are 268 MB, all 32
would be 8.6 GB), and every layer is wrapped in ``jax.checkpoint``.

``dot`` is the one matrix product everything goes through (router, experts,
attention's two products, the head), so that the control (the same
mathematics in fp8) swaps one function. Nothing here imports the program.

Departures from the release: weights are random from the seed (matrices and
taps normal(0, ``init.std``), the output projections of operators and
feed-forwards scaled by 1/sqrt(2 layers), unit gains). The selection bias
is normal(0, ``init.select_bias_std``), so that it changes which experts are
chosen (zero, as the release starts it, would leave it untested), and is a
buffer after that: the release's balancing update is no part of the config.
Random router columns give some experts several times the load of others
(every token's normalised input shares a component, and a column's product
with it is an offset to that expert's score), so the held experts' load
follows the seed: 0.20 to 0.37 of a layer's assignments to the 8 held where
even is 0.25, on the chip (PERF.md section 6, PR 29).
"""

import math

import jax
import jax.numpy as jnp


def _kinds(sizes):
    """[(operator, has experts)] of the layers in the file, in order."""
    return [
        (kind, i >= sizes["num_dense_layers"])
        for i, kind in enumerate(sizes["layer_types"])
    ]


def init_params(key, sizes):
    """Flat dict name -> float32 array, made on the device from ``key``."""
    d = sizes["hidden_size"]
    head = d // sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * head
    wide, narrow = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, published = sizes["num_experts"], sizes["published"]["num_experts"]
    std = sizes["init"]["std"]
    shapes, gains = {"embed": (sizes["vocab_size"], d)}, ["norm_f.g"]
    for i, (kind, experts) in enumerate(_kinds(sizes)):
        pre = f"l{i}."
        gains += [pre + "norm_op.g", pre + "norm_ffn.g"]
        if kind == "conv":
            shapes[pre + "conv.in.w"] = (d, 3 * d)
            shapes[pre + "conv.taps"] = (d, sizes["conv_L_cache"])
            shapes[pre + "conv.out.w"] = (d, d)
        else:
            shapes[pre + "q.w"] = (d, d)
            shapes[pre + "k.w"] = (d, kv)
            shapes[pre + "v.w"] = (d, kv)
            shapes[pre + "o.w"] = (d, d)
        if experts:
            shapes[pre + "router.w"] = (d, published)
            shapes[pre + "experts.w1"] = (held, d, narrow)
            shapes[pre + "experts.w3"] = (held, d, narrow)
            shapes[pre + "experts.w2"] = (held, narrow, d)
            shapes[pre + "select_bias"] = (published,)
        else:
            shapes[pre + "w1"] = (d, wide)
            shapes[pre + "w3"] = (d, wide)
            shapes[pre + "w2"] = (wide, d)
    layers = len(sizes["layer_types"])
    params = {}
    for n, (name, shape) in enumerate(sorted(shapes.items())):
        scale = std
        if name.endswith(("conv.out.w", "o.w", "w2")):
            scale = std / math.sqrt(2 * layers)
        elif name.endswith("select_bias"):
            scale = sizes["init"]["select_bias_std"]
        params[name] = scale * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32
        )
    for name in gains:
        params[name] = jnp.ones((d,), jnp.float32)
    for i, (kind, _) in enumerate(_kinds(sizes)):
        if kind != "conv":
            params[f"l{i}.q_norm.g"] = jnp.ones((head,), jnp.float32)
            params[f"l{i}.k_norm.g"] = jnp.ones((head,), jnp.float32)
    return params


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def short_conv(x, p, pre, taps, dot):
    seq = x.shape[1]
    b, c, u = jnp.split(dot(x, p[pre + "conv.in.w"]), 3, axis=-1)
    z = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p[pre + "conv.taps"]
    conv = sum(w[:, j] * z[:, j:j + seq] for j in range(taps))
    return dot(c * conv, p[pre + "conv.out.w"])


def rotate(x, theta):
    """RoPE, rotate-half form, on (rows, seq, heads, head)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, pre, sizes, dot):
    rows, seq, d = x.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head, eps = d // heads, sizes["norm_eps"]
    q = dot(x, p[pre + "q.w"]).reshape(rows, seq, heads, head)
    k = dot(x, p[pre + "k.w"]).reshape(rows, seq, kv_heads, head)
    v = dot(x, p[pre + "v.w"]).reshape(rows, seq, kv_heads, head)
    q = rotate(rms_norm(q, p[pre + "q_norm.g"], eps), sizes["rope_theta"])
    k = rotate(rms_norm(k, p[pre + "k_norm.g"], eps), sizes["rope_theta"])
    keep = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv  # (rows, seq, head) each
        scores = dot(qh, kh.transpose(0, 2, 1)) / math.sqrt(head)
        scores = jnp.where(keep, scores, -jnp.inf)
        return dot(jax.nn.softmax(scores, axis=-1), vh)

    group = heads // kv_heads
    by_head = (
        q.transpose(2, 0, 1, 3),
        jnp.repeat(k.transpose(2, 0, 1, 3), group, axis=0),
        jnp.repeat(v.transpose(2, 0, 1, 3), group, axis=0),
    )
    out = jax.lax.map(one_head, by_head)  # (heads, rows, seq, head)
    out = out.transpose(1, 2, 0, 3).reshape(rows, seq, d)
    return dot(out, p[pre + "o.w"])


def swiglu(x, w1, w3, w2, dot):
    return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)


def route(x, p, pre, sizes, dot):
    """(weights, chosen), both (..., experts per token), over all the
    published experts."""
    scores = jax.nn.sigmoid(dot(x, p[pre + "router.w"]))
    choice = scores
    if sizes["use_expert_bias"]:
        choice = scores + jax.lax.stop_gradient(p[pre + "select_bias"])
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(choice), sizes["num_experts_per_tok"]
    )
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return weights * sizes["routed_scaling_factor"], chosen


def experts(x, p, pre, sizes, dot):
    """The held experts' part of the layer's output."""
    weights, chosen = route(x, p, pre, sizes, dot)
    out = jnp.zeros_like(x)
    for e in range(sizes["num_experts"]):
        weight = jnp.sum(
            jnp.where(chosen == sizes["experts_first"] + e, weights, 0.0), axis=-1
        )
        out = out + weight[..., None] * swiglu(
            x, p[pre + "experts.w1"][e], p[pre + "experts.w3"][e],
            p[pre + "experts.w2"][e], dot,
        )
    return out


def layer(x, p, i, kind, has_experts, sizes, dot):
    pre, eps = f"l{i}.", sizes["norm_eps"]
    h = rms_norm(x, p[pre + "norm_op.g"], eps)
    if kind == "conv":
        x = x + short_conv(h, p, pre, sizes["conv_L_cache"], dot)
    else:
        x = x + attention(h, p, pre, sizes, dot)
    h = rms_norm(x, p[pre + "norm_ffn.g"], eps)
    if has_experts:
        return x + experts(h, p, pre, sizes, dot)
    return x + swiglu(h, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], dot)


def token_losses(hidden, table, targets, dot):
    """Cross-entropy of each position against the tied table."""
    logits = dot(hidden, table.T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def loss_sum(params, rows, sizes, dot):
    """(sum of the next-token losses of ``rows``, how many there are).

    ``rows`` is ``{"tokens": (r, s) int32}``: a block of a step's batch."""
    tokens = rows["tokens"]
    x = params["embed"][tokens]
    for i, (kind, has_experts) in enumerate(_kinds(sizes)):
        x = jax.checkpoint(
            lambda x, p, i=i, kind=kind, has_experts=has_experts: layer(
                x, p, i, kind, has_experts, sizes, dot
            )
        )(x, {k: v for k, v in params.items() if k.startswith(f"l{i}.")})
    x = rms_norm(x, params["norm_f.g"], sizes["norm_eps"])
    losses = token_losses(x[:, :-1], params["embed"], tokens[:, 1:], dot)
    return losses.sum(), losses.size


def step_rows(tokens, mask_key, step, sizes):
    """What ``loss_sum`` needs of one step's batch, whole. Nothing is drawn
    at random in a step."""
    del mask_key, step, sizes
    return {"tokens": tokens}


def program_names(sizes):
    """This file's leaf names in the nesting the program's Lfm2 module keeps
    its weights in (flax names; kernels are (in, out) on both sides, the
    experts' (expert, in, out), the taps (channel, tap)). A layer of the
    program keeps its published index (``layers_kept``) in its name."""
    def dense(name):
        return {"kernel": name}

    tree = {
        "tok_embed": {"embedding": "embed"},
        "embedding_norm": {"scale": "norm_f.g"},
    }
    for i, ((kind, has_experts), published) in enumerate(
        zip(_kinds(sizes), sizes["layers_kept"])
    ):
        pre = f"l{i}."
        block = {
            "operator_norm": {"scale": pre + "norm_op.g"},
            "ffn_norm": {"scale": pre + "norm_ffn.g"},
        }
        if kind == "conv":
            block["conv"] = {
                "in_proj": dense(pre + "conv.in.w"),
                "conv_kernel": pre + "conv.taps",
                "out_proj": dense(pre + "conv.out.w"),
            }
        else:
            block["attn"] = {n: dense(pre + n + ".w") for n in "qkvo"}
            block["attn"]["q_norm"] = {"scale": pre + "q_norm.g"}
            block["attn"]["k_norm"] = {"scale": pre + "k_norm.g"}
        if has_experts:
            block["moe"] = {
                "router_kernel": pre + "router.w",
                "select_bias": pre + "select_bias",
                "gate_kernel": pre + "experts.w1",
                "up_kernel": pre + "experts.w3",
                "down_kernel": pre + "experts.w2",
            }
        else:
            block["mlp"] = {
                "gate": dense(pre + "w1"), "up": dense(pre + "w3"),
                "down": dense(pre + "w2"),
            }
        tree[f"layer_{published}"] = block
    return tree
