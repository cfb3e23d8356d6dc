"""JoyAI-LLM-Flash (jdopensource, ``model_type`` ``joyai_llm_flash``) in
plain float32 jax.numpy: the yardstick the timed training step is held to.

Written from the released ``config.json``'s keys and the layer equations
(the keys are DeepSeek-V3's letter for letter, and so are the equations: its
report, sections 2.1 and 2.2). ``x`` is (rows, seq, hidden), d = hidden; no
biases anywhere; RMSNorm(x) = x / sqrt(mean x^2 + rms_norm_eps) * gain.

* Layer: ``h = x + attention(norm_attn(x))``; ``y = h + ffn(norm_ffn(h))``.
  After the last layer a final RMSNorm, then logits against the UNTIED head.
* Attention (latent, MLA), per token: ``c_q = norm(x W_qa)`` (``q_lora_rank``);
  ``q = c_q W_qb`` -> heads x [nope ``qk_nope_head_dim`` | rope
  ``qk_rope_head_dim``]; ``[c_kv | k_r] = x W_kva`` (``kv_lora_rank`` + rope);
  ``[k_nope | v] = norm(c_kv) W_kvb`` -> heads x (nope + ``v_head_dim``);
  RoPE (``rope_theta``; ``rope_interleave``: adjacent pairs (2i, 2i+1) turn
  by position x theta^(-2i/rope)) on q's rope part and on the ONE k_r, which
  every head uses; ``k = [k_nope | k_r]``; causal softmax(q k^T / sqrt(nope +
  rope)) v; heads x ``v_head_dim`` -> d through ``W_o``. ``rope_scaling`` is
  null: no YaRN factor anywhere.
* Feed-forward of published layers before ``first_k_dense_replace``:
  ``(silu(x W1) x W3) W2`` at ``intermediate_size``.
* Every other layer: ``s = sigmoid(x Wr)`` over all published experts; the
  ``num_experts_per_tok`` with the largest ``s + bias`` are chosen
  (``topk_method`` ``noaux_tc``: the bias picks and does not weigh, and is a
  buffer; ``n_group`` = ``topk_group`` = 1, so no group limit); their weights
  are ``s`` at the chosen over (their sum + 1e-6) where ``norm_topk_prob``,
  times ``routed_scaling_factor``; ``y = shared(x) + sum_chosen weight_e
  SwiGLU_e(x)`` at ``moe_intermediate_size``, ``n_shared_experts`` shared
  SwiGLUs of that width side by side (one here).
* Multi-token prediction (``num_nextn_predict_layers`` 1): for positions i
  with a following token, ``h'_i = W_eh [norm_e(Emb(t_{i+1})) ; norm_h(h_i)]``
  (h_i: the main model's output after its final norm), one further decoder
  layer of the expert kind over h', a norm of its own, the main model's
  head: position i predicts token i+2. ``L = L_next + mtp_loss_weight x
  L_next-but-one``, each a mean over its own positions.

The share. ``n_routed_experts`` in the configuration's file counts the
experts HELD (published experts ``experts_first`` ..),
``published.n_routed_experts`` is the router's width. Router, choice and
normalisation run over all the published experts; only chosen experts that
are held contribute, and what the absent ones would have added is left out;
the shared expert is whole here. ``vocab_size`` is the slice: embedding and
head are both over it. The experts are computed the plain way: every held
expert on every token, times a weight that is zero where the expert was not
chosen. No sort, no gather, no bound.

Memory at 4096 tokens a row: attention goes one head at a time (``lax.map``
with ``jax.checkpoint``: one head's scores are 67 MB), and every layer and
each of the two passes through the head is wrapped in ``jax.checkpoint``.

``dot`` is the one matrix product everything goes through, so that the
control (the same mathematics in fp8) swaps one function. Nothing here
imports the program. Two keys no configuration's file carries plant a fault
for the calibration (``benchmark/calibrate_faults.py``): ``score_dims``
(the scores are taken over the first so many of a head's query/key dims:
128 leaves the rotary part out) and ``use_select_bias`` false.

Departures from the release: weights are random from the seed (matrices
normal(0, ``init.std``), the output projections of attention and
feed-forwards scaled by 1/sqrt(2 layers), unit gains); the selection bias is
normal(0, ``init.select_bias_std``), a buffer; the 1e-6 in the routing
weights' normalisation is assumed (the configuration's file says so; the
sum of eight sigmoids is over 1, so the constant moves a weight by 1e-6).
"""

import math

import jax
import jax.numpy as jnp


def _prefixes(sizes):
    """[(leaf prefix, has experts)] of the layers held, in order, and the
    prediction module's block last where the configuration has one."""
    layers = [
        (f"l{n}.", published >= sizes["first_k_dense_replace"])
        for n, published in enumerate(sizes["layers_kept"])
    ]
    if sizes["num_nextn_predict_layers"]:
        layers.append(("mtp.", True))
    return layers


def init_params(key, sizes):
    """Flat dict name -> float32 array, made on the device from ``key``."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rot, v = (
        sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    )
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    wide, narrow = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, published = sizes["n_routed_experts"], sizes["published"]["n_routed_experts"]
    shared = sizes["n_shared_experts"] * narrow
    std = sizes["init"]["std"]
    vocab = sizes["vocab_size"]
    shapes = {"embed": (vocab, d), "head": (vocab, d)}
    gains = {"norm_f.g": d}
    for pre, experts in _prefixes(sizes):
        gains.update({
            pre + "norm_attn.g": d, pre + "norm_ffn.g": d,
            pre + "q_a_norm.g": q_rank, pre + "kv_a_norm.g": kv_rank,
        })
        shapes[pre + "q_a.w"] = (d, q_rank)
        shapes[pre + "q_b.w"] = (q_rank, heads * (nope + rot))
        shapes[pre + "kv_a.w"] = (d, kv_rank + rot)
        shapes[pre + "kv_b.w"] = (kv_rank, heads * (nope + v))
        shapes[pre + "o.w"] = (heads * v, d)
        if experts:
            shapes[pre + "router.w"] = (d, published)
            shapes[pre + "select_bias"] = (published,)
            shapes[pre + "experts.w1"] = (held, d, narrow)
            shapes[pre + "experts.w3"] = (held, d, narrow)
            shapes[pre + "experts.w2"] = (held, narrow, d)
            shapes[pre + "shared.w1"] = (d, shared)
            shapes[pre + "shared.w3"] = (d, shared)
            shapes[pre + "shared.w2"] = (shared, d)
        else:
            shapes[pre + "w1"] = (d, wide)
            shapes[pre + "w3"] = (d, wide)
            shapes[pre + "w2"] = (wide, d)
    if sizes["num_nextn_predict_layers"]:
        shapes["mtp.eh.w"] = (2 * d, d)
        gains.update({"mtp.norm_e.g": d, "mtp.norm_h.g": d, "mtp.norm_out.g": d})
    layers = len(_prefixes(sizes))
    params = {}
    for n, (name, shape) in enumerate(sorted(shapes.items())):
        scale = std
        if name.endswith(("o.w", "w2")):
            scale = std / math.sqrt(2 * layers)
        elif name.endswith("select_bias"):
            scale = sizes["init"]["select_bias_std"]
        params[name] = scale * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32
        )
    for name, width in gains.items():
        params[name] = jnp.ones((width,), jnp.float32)
    return params


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotate_pairs(x, theta):
    """RoPE on adjacent pairs of (rows, seq, heads, width): the pair (2i,
    2i+1) of position p turns by p x theta^(-2i / width)."""
    width = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def attention(x, p, pre, sizes, dot):
    rows, seq, _ = x.shape
    heads, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rot, v_dim = (
        sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    )
    if not sizes["rope_interleave"] or sizes["rope_scaling"] is not None:
        raise NotImplementedError("written for rope_interleave, no rope_scaling")
    c_q = rms_norm(dot(x, p[pre + "q_a.w"]), p[pre + "q_a_norm.g"], eps)
    q = dot(c_q, p[pre + "q_b.w"]).reshape(rows, seq, heads, nope + rot)
    kv_a = dot(x, p[pre + "kv_a.w"])
    c_kv, k_r = kv_a[..., :sizes["kv_lora_rank"]], kv_a[..., sizes["kv_lora_rank"]:]
    kv = dot(
        rms_norm(c_kv, p[pre + "kv_a_norm.g"], eps), p[pre + "kv_b.w"]
    ).reshape(rows, seq, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    theta = sizes["rope_theta"]
    q = jnp.concatenate(
        [q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1
    )
    k_r = rotate_pairs(k_r[:, :, None, :], theta)  # one head, for all
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (rows, seq, heads, rot))], axis=-1
    )
    used = sizes.get("score_dims", nope + rot)  # the calibration's fault

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv  # (rows, seq, width) each
        scores = dot(qh[..., :used], kh[..., :used].transpose(0, 2, 1))
        # made here from two counters: a (seq, seq) constant would be kept
        # in the program once for every pass of every layer
        keep = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(keep, scores / math.sqrt(nope + rot), -jnp.inf)
        return dot(jax.nn.softmax(scores, axis=-1), vh)

    out = jax.lax.map(one_head, tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v)))
    out = out.transpose(1, 2, 0, 3).reshape(rows, seq, heads * v_dim)
    return dot(out, p[pre + "o.w"])


def swiglu(x, w1, w3, w2, dot):
    return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)


def route(x, p, pre, sizes, dot):
    """(weights, chosen), both (..., experts per token), over all the
    published experts."""
    scores = jax.nn.sigmoid(dot(x, p[pre + "router.w"]))
    choice = scores
    if sizes.get("use_select_bias", True):
        choice = scores + jax.lax.stop_gradient(p[pre + "select_bias"])
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(choice), sizes["num_experts_per_tok"]
    )
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return weights * sizes["routed_scaling_factor"], chosen


def routed(x, p, pre, sizes, dot):
    """The held routed experts' part of the layer's output."""
    weights, chosen = route(x, p, pre, sizes, dot)
    out = jnp.zeros_like(x)
    for e in range(sizes["n_routed_experts"]):
        weight = jnp.sum(
            jnp.where(chosen == sizes["experts_first"] + e, weights, 0.0), axis=-1
        )
        out = out + weight[..., None] * swiglu(
            x, p[pre + "experts.w1"][e], p[pre + "experts.w3"][e],
            p[pre + "experts.w2"][e], dot,
        )
    return out


def shared(x, p, pre, dot):
    return swiglu(
        x, p[pre + "shared.w1"], p[pre + "shared.w3"], p[pre + "shared.w2"], dot
    )


def layer(x, p, pre, has_experts, sizes, dot):
    eps = sizes["rms_norm_eps"]
    x = x + attention(rms_norm(x, p[pre + "norm_attn.g"], eps), p, pre, sizes, dot)
    h = rms_norm(x, p[pre + "norm_ffn.g"], eps)
    if has_experts:
        return x + shared(h, p, pre, dot) + routed(h, p, pre, sizes, dot)
    return x + swiglu(h, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], dot)


def token_losses(hidden, head, targets, dot):
    """Cross-entropy of each position against the untied head."""
    logits = dot(hidden, head.T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def _layer(x, params, pre, has_experts, sizes, dot):
    """One layer under ``jax.checkpoint``, handed only its own weights."""
    return jax.checkpoint(
        lambda x, p: layer(x, p, pre, has_experts, sizes, dot)
    )(x, {k: v for k, v in params.items() if k.startswith(pre)})


def part_losses(params, rows, sizes, dot):
    """(next-token losses (r, s-1), next-but-one losses (r, s-2) or None)
    of ``rows`` = ``{"tokens": (r, s) int32}``."""
    tokens, eps = rows["tokens"], sizes["rms_norm_eps"]
    losses = jax.checkpoint(
        lambda hidden, head, targets: token_losses(hidden, head, targets, dot)
    )
    x = params["embed"][tokens]
    for pre, has_experts in _prefixes(sizes):
        if pre != "mtp.":
            x = _layer(x, params, pre, has_experts, sizes, dot)
    x = rms_norm(x, params["norm_f.g"], eps)
    following = losses(x[:, :-1], params["head"], tokens[:, 1:])
    if not sizes["num_nextn_predict_layers"]:
        return following, None
    both = jnp.concatenate([
        rms_norm(params["embed"][tokens[:, 1:]], params["mtp.norm_e.g"], eps),
        rms_norm(x[:, :-1], params["mtp.norm_h.g"], eps),
    ], axis=-1)
    y = _layer(dot(both, params["mtp.eh.w"]), params, "mtp.", True, sizes, dot)
    y = rms_norm(y, params["mtp.norm_out.g"], eps)
    return following, losses(y[:, :-1], params["head"], tokens[:, 2:])


def loss_sum(params, rows, sizes, dot):
    """(sum over ``rows``' next-token positions of the step's loss, how many
    there are): ``L_next + mtp_loss_weight x L_next-but-one``, the second
    mean brought to the first one's count so that blocks of rows add up."""
    following, further = part_losses(params, rows, sizes, dot)
    total = following.sum()
    if further is not None:
        total = total + sizes["mtp_loss_weight"] * further.sum() * (
            following.size / further.size
        )
    return total, following.size


def step_rows(tokens, mask_key, step, sizes):
    """What ``loss_sum`` needs of one step's batch, whole. Nothing is drawn
    at random in a step."""
    del mask_key, step, sizes
    return {"tokens": tokens}


def program_names(sizes):
    """This file's leaf names in the nesting the program's JoyaiLlmFlash
    module keeps its weights in (flax names; kernels are (in, out) on both
    sides, the experts' (expert, in, out), embedding and head (vocab, d)).
    A layer of the program keeps its published index (``layers_kept``) in
    its name; the prediction module's block is the layer after the last
    published one."""
    def dense(name):
        return {"kernel": name}

    def gain(name):
        return {"scale": name}

    tree = {
        "tok_embed": {"embedding": "embed"},
        "lm_head": "head",
        "final_norm": gain("norm_f.g"),
    }
    indices = list(sizes["layers_kept"]) + [sizes["published"]["num_hidden_layers"]]
    for (pre, has_experts), index in zip(_prefixes(sizes), indices):
        block = {
            "attn_norm": gain(pre + "norm_attn.g"),
            "ffn_norm": gain(pre + "norm_ffn.g"),
            "attn": {
                **{n: dense(f"{pre}{n}.w") for n in ("q_a", "q_b", "kv_a", "kv_b", "o")},
                "q_a_norm": gain(pre + "q_a_norm.g"),
                "kv_a_norm": gain(pre + "kv_a_norm.g"),
            },
        }
        if has_experts:
            block["moe"] = {
                "router_kernel": pre + "router.w",
                "select_bias": pre + "select_bias",
                "gate_kernel": pre + "experts.w1",
                "up_kernel": pre + "experts.w3",
                "down_kernel": pre + "experts.w2",
                "shared": {
                    "gate": dense(pre + "shared.w1"),
                    "up": dense(pre + "shared.w3"),
                    "down": dense(pre + "shared.w2"),
                },
            }
        else:
            block["mlp"] = {
                "gate": dense(pre + "w1"), "up": dense(pre + "w3"),
                "down": dense(pre + "w2"),
            }
        tree[f"layer_{index}"] = block
    if sizes["num_nextn_predict_layers"]:
        tree.update({
            "mtp_embed_norm": gain("mtp.norm_e.g"),
            "mtp_hidden_norm": gain("mtp.norm_h.g"),
            "mtp_proj": dense("mtp.eh.w"),
            "mtp_head_norm": gain("mtp.norm_out.g"),
        })
    return tree
