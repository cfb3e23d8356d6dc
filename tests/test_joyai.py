"""JoyAI-LLM-Flash at a tiny size on the CPU: the program
(``models/joyai.py``, the shared expert of ``models/moe.py``'s dropless
layer, the prediction module's second loss in ``train/tasks.py``) against
the plain reference (``benchmark/reference/joyai_llm_flash.py``, written
from the layer equations and from nothing in the program) on seeded weights.

Tolerances are ``tests/test_lfm2.py``'s, for its reasons: both sides are
float32 and compute the same mathematics in another order, so 1e-5 on a
loss and 2e-4 of a leaf's largest entry on its gradient.
"""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_joyai  # noqa: E402  (puts benchmark/ on sys.path)
import train_reference  # noqa: E402
from reference import joyai_llm_flash as ref  # noqa: E402

from distributed_pytorch_example_tpu.models import moe  # noqa: E402
from distributed_pytorch_example_tpu.models.joyai import LatentAttention  # noqa: E402
from distributed_pytorch_example_tpu.ops.rope import rope  # noqa: E402
from distributed_pytorch_example_tpu.train.tasks import CausalLMTask  # noqa: E402

SHARE = tiny_joyai.JOYAI
# the model uncut in its experts: all 16 published ones held
WHOLE = {**SHARE, "n_routed_experts": 16}
DOT = train_reference.plain_dot


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.integers(0, SHARE["vocab_size"], (3, 32)), jnp.int32)


def by_name(tree, sizes):
    """{reference leaf name: the program's leaf} of a program tree."""
    names = ref.program_names(sizes)
    return dict(zip(
        jax.tree_util.tree_leaves(names), jax.tree_util.tree_leaves(tree)
    ))


def program_loss(model, params, tokens):
    loss, metrics, _ = CausalLMTask().compute_loss(
        model, params, {}, {"tokens": tokens}, jax.random.key(0), train=True
    )
    return loss, metrics


# -- the whole stack: loss, both part losses, every leaf's gradient ----------


@pytest.mark.parametrize(
    "sizes,mode",
    [(SHARE, "full-logits"), (SHARE, "fused-loss-remat"), (WHOLE, "fused-loss-remat")],
    ids=["share-full-logits", "share-fused-loss-remat", "whole-fused-loss-remat"],
)
def test_loss_both_parts_and_every_gradient_agree(tokens, sizes, mode):
    fused = mode == "fused-loss-remat"
    flat = ref.init_params(jax.random.key(7), sizes)
    model = tiny_joyai.program_model(
        sizes, logits_mode="hidden" if fused else "full", remat=fused
    )
    params = tiny_joyai.program_params(ref, flat, sizes)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(model, p, tokens), has_aux=True
    ))(params)

    def reference_loss(flat):
        total, count = ref.loss_sum(flat, {"tokens": tokens}, sizes, DOT)
        return total / count

    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(flat)
    following, further = ref.part_losses(flat, {"tokens": tokens}, sizes, DOT)
    assert further.shape == (3, 30)  # the last two positions are no target
    for got, expected in (
        (loss, want_loss), (metrics["loss_next"], following.mean()),
        (metrics["loss_mtp"], further.mean()),
        (loss, following.mean() + 0.3 * further.mean()),
    ):
        assert abs(float(got) - float(expected)) < 1e-5 * float(expected)
    got = by_name(grads, sizes)
    assert sorted(got) == sorted(want)
    for name in sorted(want):
        scale = float(jnp.max(jnp.abs(want[name])))
        np.testing.assert_allclose(
            np.asarray(got[name]).reshape(want[name].shape), want[name],
            atol=2e-4 * scale + 1e-9, rtol=0, err_msg=name,
        )
        if name.endswith("select_bias"):  # picks, does not weigh: a buffer
            assert scale == 0.0 and float(jnp.abs(got[name]).sum()) == 0.0
    # embedding and head are two tables, each with a gradient of its own
    assert float(jnp.abs(got["head"] - got["embed"]).max()) > 0.0
    assert float(metrics["moe_dropped_assignments"]) == 0.0
    held = sizes["n_routed_experts"] / sizes["published"]["n_routed_experts"]
    assert abs(float(metrics["moe_held_share"]) - held) < (0.1 if held < 1 else 1e-6)


# -- the share ties to the model ---------------------------------------------


def test_the_32_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """32 published experts, one held by each of 32 chips: every share's
    routed part through the program's dropless path, summed, plus the
    shared expert counted ONCE, is the uncut reference's layer output."""
    whole = {**SHARE, "n_routed_experts": 32, "experts_first": 0,
             "published": {"num_hidden_layers": 40, "n_routed_experts": 32}}
    flat = ref.init_params(jax.random.key(11), whole)
    x = jax.random.normal(jax.random.key(4), (96, SHARE["hidden_size"]))
    want = ref.shared(x, flat, "l1.", DOT) + ref.routed(x, flat, "l1.", whole, DOT)
    k = SHARE["num_experts_per_tok"]
    weights, chosen = moe.moe_route_sigmoid(
        x, flat["l1.router.w"], flat["l1.select_bias"], top_k=k,
        scaling=SHARE["routed_scaling_factor"],
    )
    total, held_shares = jnp.zeros_like(x), []
    for first in range(32):
        params = {
            "gate_kernel": flat["l1.experts.w1"][first:first + 1],
            "up_kernel": flat["l1.experts.w3"][first:first + 1],
            "down_kernel": flat["l1.experts.w2"][first:first + 1],
        }
        part, counters = moe.moe_dropless(
            x, weights, chosen, params, first_held=first,
            rows_bound=k * x.shape[0],
        )
        assert float(counters["dropped_assignments"]) == 0.0
        held_shares.append(float(counters["held_share"]))
        total = total + part
    np.testing.assert_allclose(
        total + ref.shared(x, flat, "l1.", DOT), want, atol=2e-6
    )
    assert abs(sum(held_shares) - 1.0) < 1e-6
    # and the program's layer of ONE share adds the shared expert whole
    layer = moe.DroplessMoE(
        num_experts=32, mlp_dim=SHARE["moe_intermediate_size"], top_k=k,
        first_held=5, experts_held=1, scaling=SHARE["routed_scaling_factor"],
        shared_mlp_dim=SHARE["moe_intermediate_size"],
    )
    params = {
        "router_kernel": flat["l1.router.w"], "select_bias": flat["l1.select_bias"],
        "gate_kernel": flat["l1.experts.w1"][5:6], "up_kernel": flat["l1.experts.w3"][5:6],
        "down_kernel": flat["l1.experts.w2"][5:6],
        "shared": {n: {"kernel": flat[f"l1.shared.{w}"]}
                   for n, w in (("gate", "w1"), ("up", "w3"), ("down", "w2"))},
    }
    got = layer.apply({"params": params}, x[None], mutable=["moe_metrics"])[0][0]
    one = {**whole, "n_routed_experts": 1, "experts_first": 5}
    cut = {**flat, **{f"l1.experts.{w}": flat[f"l1.experts.{w}"][5:6]
                      for w in ("w1", "w2", "w3")}}
    np.testing.assert_allclose(
        got, ref.shared(x, flat, "l1.", DOT) + ref.routed(x, cut, "l1.", one, DOT),
        atol=2e-6,
    )


# -- latent attention alone --------------------------------------------------


def _turn(x, theta):
    """Adjacent pairs as complex numbers, turned by position x frequency:
    another way to write interleaved RoPE than either side's."""
    seq, width = x.shape[0], x.shape[-1]
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    freqs = theta ** (-np.arange(0, width, 2) / width)
    z = z * np.exp(1j * np.arange(seq)[:, None] * freqs)
    return np.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def test_latent_attention_against_head_by_head_attention():
    """One row through ``LatentAttention`` against a loop over heads in
    numpy float64: the low-rank chains with their inner norms, interleaved
    RoPE, the ONE rotary key every head uses, the 1/sqrt(16 + 8) scale,
    values 16 wide under queries and keys 24 wide."""
    heads, nope, rot, v_dim, kv_rank, eps = 4, 16, 8, 16, 32, 1e-6
    flat = ref.init_params(jax.random.key(9), SHARE)
    p = {k[3:]: np.asarray(v, np.float64) for k, v in flat.items() if k.startswith("l0.")}
    x = np.asarray(jax.random.normal(jax.random.key(2), (48, 64)), np.float64)

    def norm(h, g):
        return h / np.sqrt((h * h).mean(-1, keepdims=True) + eps) * g

    q = (norm(x @ p["q_a.w"], p["q_a_norm.g"]) @ p["q_b.w"]).reshape(48, heads, nope + rot)
    kv_a = x @ p["kv_a.w"]
    k_r = _turn(kv_a[:, kv_rank:], 32e6)  # (seq, rot): one head
    kv = (norm(kv_a[:, :kv_rank], p["kv_a_norm.g"]) @ p["kv_b.w"]).reshape(
        48, heads, nope + v_dim
    )
    out = np.zeros((48, heads, v_dim))
    for h in range(heads):
        qh = np.concatenate([q[:, h, :nope], _turn(q[:, h, nope:], 32e6)], -1)
        kh = np.concatenate([kv[:, h, :nope], k_r], -1)
        scores = qh @ kh.T / np.sqrt(nope + rot)
        scores[np.triu_indices(48, 1)] = -np.inf
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, h] = weights / weights.sum(-1, keepdims=True) @ kv[:, h, nope:]
    want = out.reshape(48, heads * v_dim) @ p["o.w"]

    attention = LatentAttention(
        model_dim=64, num_heads=heads, q_lora_rank=48, kv_lora_rank=kv_rank,
        qk_nope_head_dim=nope, qk_rope_head_dim=rot, v_head_dim=v_dim,
        rope_theta=32e6, norm_eps=eps,
    )
    names = ref.program_names(SHARE)["layer_0"]["attn"]
    params = jax.tree_util.tree_map(lambda n: flat[n], names)
    got = attention.apply({"params": params}, jnp.asarray(x, jnp.float32)[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(
        ref.attention(jnp.asarray(x, jnp.float32)[None], flat, "l0.", SHARE, DOT)[0],
        want, atol=2e-6,
    )


def test_interleaved_rope_turns_adjacent_pairs_and_leaves_them_in_place():
    x = jax.random.normal(jax.random.key(1), (2, 12, 3, 8))
    got = rope(x, theta=32e6, interleaved=True)
    for b in range(2):
        for h in range(3):
            np.testing.assert_allclose(
                got[b, :, h], _turn(np.asarray(x[b, :, h], np.float64), 32e6),
                atol=1e-6,
            )
    # not the rotate-half form, which pairs dim i with dim i + 4
    assert float(jnp.abs(got - rope(x, theta=32e6)).max()) > 1e-2
    with pytest.raises(ValueError, match="interleaved"):
        rope(x, positions=jnp.zeros((2, 12), jnp.int32), interleaved=True)


# -- multi-token prediction's targets ----------------------------------------


class _TwoLogits(nn.Module):
    """A model that returns a pair of logits, each a parameter."""

    dtype = jnp.float32
    logits_mode = "full"
    mtp_loss_weight = 0.3

    @nn.compact
    def __call__(self, tokens, *, train=False):
        shape = tokens.shape + (11,)
        return tuple(
            self.param(name, nn.initializers.normal(1.0), shape)
            for name in ("next", "further")
        )


def test_position_i_predicts_i_plus_2_and_the_last_two_are_no_target():
    tokens = jax.random.randint(jax.random.key(5), (2, 9), 0, 11)
    model = _TwoLogits()
    params = model.init(jax.random.key(6), tokens)["params"]
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, tokens), has_aux=True
    )(params)

    def xent(logits, targets):
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1
        ).mean()

    want_next = xent(params["next"][:, :-1], tokens[:, 1:])
    want_further = xent(params["further"][:, :-2], tokens[:, 2:])
    assert float(metrics["loss_next"]) == pytest.approx(float(want_next), rel=1e-6)
    assert float(metrics["loss_mtp"]) == pytest.approx(float(want_further), rel=1e-6)
    assert float(loss) == pytest.approx(float(want_next + 0.3 * want_further), rel=1e-6)
    assert float(jnp.abs(grads["further"][:, -2:]).max()) == 0.0
    assert float(jnp.abs(grads["further"][:, :-2]).min()) > 0.0
    assert float(jnp.abs(grads["next"][:, -1:]).max()) == 0.0


def test_without_the_module_the_model_has_none_of_its_weights():
    model = tiny_joyai.program_model(mtp_layers=0, layers_kept=(0,))
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))["params"]
    assert sorted(shapes) == ["final_norm", "layer_0", "lm_head", "tok_embed"]
    out = jax.eval_shape(
        lambda p: model.apply({"params": p}, tokens, train=True), shapes
    )
    assert out.shape == (1, 8, 512)  # one output, so one loss


def test_the_module_sees_the_next_tokens_embedding_beside_this_positions_state():
    """Changing token j moves the module's output from position j - 1 on
    (its embedding is moved one to the left) and the main output from
    position j on; nothing before. In evaluation the model returns the main
    output alone."""
    model = tiny_joyai.program_model(logits_mode="hidden")
    tokens = jax.random.randint(jax.random.key(8), (1, 16), 0, 512)
    params = model.init(jax.random.key(0), tokens)["params"]
    apply = jax.jit(lambda t: model.apply(
        {"params": params}, t, train=True, mutable=["moe_metrics"]
    )[0])
    main, further = apply(tokens)
    j = 9
    main2, further2 = apply(tokens.at[0, j].add(1))
    moved = lambda a, b: np.abs(np.asarray(a - b)).max(-1)[0] > 0  # noqa: E731
    assert not moved(main, main2)[:j].any() and moved(main, main2)[j:].all()
    assert not moved(further, further2)[:j - 1].any()
    assert moved(further, further2)[j - 1:].all()
    alone = model.apply({"params": params}, tokens, train=False)
    np.testing.assert_allclose(alone, main, atol=1e-5)


# -- the registry, train.py and the share's flags ----------------------------


def test_the_defaults_are_the_published_configuration_and_the_share_counts():
    import distributed_pytorch_example_tpu as dpx

    cls = dpx.models.model_class("joyai-llm-flash")
    fields = {k: f.default for k, f in cls.__dataclass_fields__.items()}
    assert (fields["num_layers"], fields["num_experts"], fields["vocab_size"],
            fields["mtp_layers"], fields["top_k"]) == (40, 256, 129280, 1, 8)
    assert (fields["qk_nope_head_dim"] + fields["qk_rope_head_dim"],
            fields["v_head_dim"]) == (192, 128)
    # the benchmark's share at the published widths: 491,696,128 trained
    # parameters and five 256-wide biases
    model = dpx.models.get_model(
        "joyai-llm-flash", layers_kept=(0, 1, 2, 3, 4), experts_held=8,
        vocab_size=16160,
    )
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    )["params"]
    sizes = {
        jax.tree_util.keystr(path): leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    }
    biases = sum(n for path, n in sizes.items() if "select_bias" in path)
    assert biases == 5 * 256
    assert sum(sizes.values()) - biases == 491_696_128
    assert shapes["lm_head"].shape == shapes["tok_embed"]["embedding"].shape == (16160, 2048)


SHARE_ARGV = [
    "--model", "joyai-llm-flash", "--layers-kept", "0,1,2", "--experts-held",
    "4,4", "--vocab-slice", "512", "--dataset", "synthetic-tokens",
    "--seq-len", "16", "--batch-size", "8", "--num-samples", "32", "--epochs",
    "1", "--remat", "--checkpoint-dir", "",
]


def test_train_main_trains_the_share_it_is_told(devices, monkeypatch):
    """``train.py`` builds the share from the lfm2 stack's three flags,
    takes the fused loss over the untied, sliced head, and every epoch
    record carries the two losses apart beside the routing's counters."""
    import train

    import distributed_pytorch_example_tpu as dpx

    built = {}
    real = dpx.models.get_model

    def tiny(name, **overrides):
        built.update(overrides)
        return real(name, **{**overrides, **tiny_joyai.JOYAI_MODEL})

    monkeypatch.setattr(dpx.models, "get_model", tiny)
    trainer = train.main(SHARE_ARGV)
    assert built["layers_kept"] == (0, 1, 2)
    assert (built["experts_first"], built["experts_held"]) == (4, 4)
    assert built["vocab_size"] == 512 and built["remat"] is True
    assert built["logits_mode"] == "hidden"  # the fused chunked-CE loss
    params = trainer.state.params
    assert sorted(k for k in params if k.startswith("layer_")) == [
        "layer_0", "layer_1", "layer_2", "layer_40"
    ]
    assert params["tok_embed"]["embedding"].shape == (512, 64)
    assert params["lm_head"].shape == (512, 64)
    assert params["layer_40"]["moe"]["gate_kernel"].shape[0] == 4
    assert params["layer_1"]["moe"]["router_kernel"].shape == (64, 16)
    assert "shared" in params["layer_1"]["moe"]
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]
    history = trainer.fit(
        dpx.data.DeviceLoader(
            dpx.data.SyntheticTokenDataset(
                num_samples=32, seq_len=16, vocab_size=512, seed=1
            ),
            8, mesh=trainer.partitioner.mesh,
        ),
        None, epochs=1,
    )
    record = history[0]
    assert record["train_loss"] == pytest.approx(
        record["train_loss_next"] + 0.3 * record["train_loss_mtp"], rel=1e-5
    )
    for name in ("dropped_assignments", "held_share", "load_max_over_mean",
                 "rows_used_share"):
        assert f"train_moe_{name}" in record
