"""LFM2-8B-A1B at a tiny size on the CPU: the program (``models/lfm2.py``,
``models/moe.py``'s dropless path, ``MultiHeadAttention`` with ``qk_norm``)
against the plain reference (``benchmark/reference/lfm2_moe.py``, written
from the layer equations and from nothing in the program) on seeded weights.

Tolerances. Both sides are float32 on the CPU and compute the same
mathematics in another order (the program sorts assignments into rows and
uses grouped products; the reference runs every held expert on every token),
so they agree to float32 rounding accumulated over five layers: 1e-5 on the
loss, 2e-4 of a leaf's largest entry on its gradient (an expert's gradient
is a sum over the tokens routed to it, in another order). After three Adam
steps a weight has moved by about 3 lr = 3e-3 whatever its gradient's size,
and where a gradient entry is near zero Adam's m / sqrt(v) amplifies the
rounding of its sign, so the change is held to 5% of its own norm by leaf,
not entry by entry (the benchmark's ``param_change_norm_gap`` has the same
reason).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_lfm2  # noqa: E402  (puts benchmark/ on sys.path)
import train_reference  # noqa: E402
from reference import lfm2_moe as ref  # noqa: E402

from distributed_pytorch_example_tpu.models import moe  # noqa: E402
from distributed_pytorch_example_tpu.models.lfm2 import ShortConv  # noqa: E402
from distributed_pytorch_example_tpu.models.transformer import (  # noqa: E402
    MultiHeadAttention,
)
from distributed_pytorch_example_tpu.train.tasks import CausalLMTask  # noqa: E402

SIZES = tiny_lfm2.LFM2
DOT = train_reference.plain_dot
ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


@pytest.fixture(scope="module")
def flat():
    return ref.init_params(jax.random.key(7), SIZES)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.integers(0, SIZES["vocab_size"], (3, 32)), jnp.int32)


def by_name(tree):
    """{reference leaf name: the program's leaf} of a program tree."""
    names = ref.program_names(SIZES)
    return dict(zip(
        jax.tree_util.tree_leaves(names), jax.tree_util.tree_leaves(tree)
    ))


def program_loss(model, params, tokens):
    loss, metrics, _ = CausalLMTask().compute_loss(
        model, params, {}, {"tokens": tokens}, jax.random.key(0), train=True
    )
    return loss, metrics


def reference_loss(flat, tokens):
    total, count = ref.loss_sum(flat, {"tokens": tokens}, SIZES, DOT)
    return total / count


# -- the whole stack: loss, every leaf's gradient, three Adam steps ----------


@pytest.mark.parametrize("mode", ["full-logits", "fused-loss-remat"])
def test_loss_and_every_gradient_agree(flat, tokens, mode):
    fused = mode == "fused-loss-remat"
    model = tiny_lfm2.program_model(
        logits_mode="hidden" if fused else "full", remat=fused
    )
    params = tiny_lfm2.program_params(ref, flat)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(model, p, tokens), has_aux=True
    ))(params)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(flat, tokens)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = by_name(grads)
    assert sorted(got) == sorted(want)
    for name in sorted(want):
        scale = float(jnp.max(jnp.abs(want[name])))
        np.testing.assert_allclose(
            np.asarray(got[name]).reshape(want[name].shape), want[name],
            atol=2e-4 * scale + 1e-9, rtol=0, err_msg=name,
        )
    # the selection bias picks and does not weigh: no gradient on either side
    for name in want:
        if name.endswith("select_bias"):
            assert float(jnp.abs(want[name]).sum()) == 0.0
            assert float(jnp.abs(got[name]).sum()) == 0.0
    assert float(metrics["moe_dropped_assignments"]) == 0.0
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert 0.0 < float(metrics["moe_rows_used_share"]) <= 1.0


def test_three_adam_steps_agree(flat, tokens):
    model = tiny_lfm2.program_model(logits_mode="hidden")
    params = tiny_lfm2.program_params(ref, flat)
    optimizer = optax.adam(ADAM["lr"], b1=ADAM["b1"], b2=ADAM["b2"], eps=ADAM["eps"])
    opt_state = optimizer.init(params)
    want, m, v = flat, *(jax.tree_util.tree_map(jnp.zeros_like, flat),) * 2

    @jax.jit
    def program_step(params, opt_state):
        grads = jax.grad(lambda p: program_loss(model, p, tokens)[0])(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    reference_grad = jax.jit(jax.grad(reference_loss))
    for step in range(3):
        params, opt_state = program_step(params, opt_state)
        want, m, v = train_reference.adam_step(
            want, m, v, reference_grad(want, tokens), step + 1, ADAM
        )
    got = by_name(params)
    for name in sorted(want):
        moved = np.asarray(want[name] - flat[name])
        gap = np.asarray(got[name]).reshape(moved.shape) - np.asarray(want[name])
        if name.endswith("select_bias"):
            assert not moved.any() and not gap.any()  # a buffer: Adam leaves it
            continue
        assert np.linalg.norm(gap) <= 0.05 * np.linalg.norm(moved), name


# -- each operator alone -----------------------------------------------------


def test_short_convolution_alone(flat):
    """Layer 0's operator, with the row's first two positions (the taps
    reach before the row's first token there: zeros)."""
    x = jax.random.normal(jax.random.key(1), (2, 9, SIZES["hidden_size"]))
    conv = ShortConv(SIZES["hidden_size"], SIZES["conv_L_cache"])
    params = {
        "in_proj": {"kernel": flat["l0.conv.in.w"]},
        "conv_kernel": flat["l0.conv.taps"],
        "out_proj": {"kernel": flat["l0.conv.out.w"]},
    }
    got = conv.apply({"params": params}, x)
    want = ref.short_conv(x, flat, "l0.", SIZES["conv_L_cache"], DOT)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # by hand at position 0 and 1: only the last (and last two) taps see a token
    b, c, u = jnp.split(x @ flat["l0.conv.in.w"], 3, axis=-1)
    z, w = b * u, flat["l0.conv.taps"]
    first = (c[:, 0] * (w[:, 2] * z[:, 0])) @ flat["l0.conv.out.w"]
    second = (c[:, 1] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1])) @ flat["l0.conv.out.w"]
    np.testing.assert_allclose(got[:, 0], first, atol=1e-6)
    np.testing.assert_allclose(got[:, 1], second, atol=1e-6)


def attention_module(**fields):
    heads = SIZES["num_attention_heads"]
    return MultiHeadAttention(
        num_heads=heads, head_dim=SIZES["hidden_size"] // heads,
        model_dim=SIZES["hidden_size"], causal=True,
        num_kv_heads=SIZES["num_key_value_heads"], rope=True,
        rope_theta=float(SIZES["rope_theta"]), use_flash=False, **fields,
    )


def test_attention_with_qk_norm_and_grouped_keys_alone(flat):
    """q/k RMSNorm per head before RoPE, 4 query heads a key head; gains
    other than 1 so that the norm's weight is checked."""
    x = jax.random.normal(jax.random.key(2), (2, 16, SIZES["hidden_size"]))
    head = SIZES["hidden_size"] // SIZES["num_attention_heads"]
    gains = {
        "l1.q_norm.g": 1.0 + 0.1 * jnp.arange(head, dtype=jnp.float32),
        "l1.k_norm.g": 2.0 - 0.1 * jnp.arange(head, dtype=jnp.float32),
    }
    p = {**flat, **gains}
    params = {n: {"kernel": p[f"l1.{n}.w"]} for n in "qkvo"}
    params["q_norm"] = {"scale": p["l1.q_norm.g"]}
    params["k_norm"] = {"scale": p["l1.k_norm.g"]}
    module = attention_module(qk_norm=True, use_bias=False)
    got = module.apply({"params": params}, x)
    want = ref.attention(x, p, "l1.", SIZES, DOT)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_qk_norm_off_traces_what_it_traced():
    """gpt2, bert and llama: the parameter tree it had, and the very jaxpr
    of the module as it is built without the new fields."""
    x = jnp.ones((2, 16, SIZES["hidden_size"]))
    before = attention_module()
    off = attention_module(qk_norm=False, use_bias=True)
    variables = before.init(jax.random.key(0), x)
    tree = jax.tree_util.tree_map(lambda a: a.shape, variables["params"])
    assert sorted(tree) == ["k", "o", "q", "v"]
    assert all(sorted(tree[n]) == ["bias", "kernel"] for n in tree)
    text = str(jax.make_jaxpr(lambda v: off.apply(v, x))(variables))
    assert text == str(jax.make_jaxpr(lambda v: before.apply(v, x))(variables))
    assert "rsqrt" not in text and "sqrt" not in text
    on = attention_module(qk_norm=True)
    normed = str(jax.make_jaxpr(
        lambda v: on.apply(v, x))(on.init(jax.random.key(0), x)))
    assert "sqrt" in normed


def test_routing_with_a_bias_that_changes_the_choice(flat):
    """The bias picks and does not weigh: with it some tokens choose other
    experts, and the chosen experts' weights are the scores without it."""
    x = jax.random.normal(jax.random.key(3), (64, SIZES["hidden_size"]))
    k = SIZES["num_experts_per_tok"]
    bias = jnp.asarray([0.5, -0.5, 0.3, -0.3, 0.2, -0.2, 0.1, -0.1])
    router = flat["l1.router.w"]
    weights, chosen = moe.moe_route_sigmoid(x, router, bias, top_k=k)
    _, unbiased = moe.moe_route_sigmoid(x, router, None, top_k=k)
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()
    p = {**flat, "l1.select_bias": bias}
    want_weights, want_chosen = ref.route(x, p, "l1.", SIZES, DOT)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_allclose(weights, want_weights, atol=1e-6)
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-6
    )


# -- the share, the bound and the work ---------------------------------------


def expert_params(flat, pre, first, held, published_flat):
    """The dropless path's params for experts first .. first + held - 1 of a
    layer whose every published expert is in ``published_flat``."""
    return {
        name: published_flat[pre + f"experts.{w}"][first:first + held]
        for name, w in (("gate_kernel", "w1"), ("up_kernel", "w3"),
                        ("down_kernel", "w2"))
    }


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 of an 8-expert layer, each share through
    the program's dropless path, sum to what the uncut reference (all 8
    held) gives for the whole layer."""
    whole = {**SIZES, "num_experts": 8, "experts_first": 0}
    flat = ref.init_params(jax.random.key(11), whole)
    x = jax.random.normal(jax.random.key(4), (96, SIZES["hidden_size"]))
    want = ref.experts(x, flat, "l1.", whole, DOT)
    k = SIZES["num_experts_per_tok"]
    weights, chosen = moe.moe_route_sigmoid(
        x, flat["l1.router.w"], flat["l1.select_bias"], top_k=k
    )
    total = jnp.zeros_like(x)
    held_shares = []
    for first in (0, 2, 4, 6):
        part, counters = moe.moe_dropless(
            x, weights, chosen, expert_params(flat, "l1.", first, 2, flat),
            first_held=first, rows_bound=k * x.shape[0],
        )
        cut = {**whole, "num_experts": 2, "experts_first": first}
        cut_flat = {
            **flat,
            **{f"l1.experts.{w}": flat[f"l1.experts.{w}"][first:first + 2]
               for w in ("w1", "w2", "w3")},
        }
        np.testing.assert_allclose(
            part, ref.experts(x, cut_flat, "l1.", cut, DOT), atol=1e-6
        )
        assert float(counters["dropped_assignments"]) == 0.0
        held_shares.append(float(counters["held_share"]))
        total = total + part
    np.testing.assert_allclose(total, want, atol=1e-6)
    assert abs(sum(held_shares) - 1.0) < 1e-6


@pytest.mark.parametrize("rows_bound", [0, 5, 17, "derived", "worst"])
def test_a_small_bound_drops_and_counts_exactly_what_it_drops(flat, rows_bound):
    x = jax.random.normal(jax.random.key(5), (48, SIZES["hidden_size"]))
    k, held = SIZES["num_experts_per_tok"], SIZES["num_experts"]
    weights, chosen = moe.moe_route_sigmoid(
        x, flat["l1.router.w"], flat["l1.select_bias"], top_k=k
    )
    held_here = int(((chosen >= 0) & (chosen < held)).sum())
    if rows_bound == "derived":
        rows_bound = moe.dropless_rows_bound(48, k, held, 8)
        assert rows_bound == 2 * k * 48 * held // 8
    elif rows_bound == "worst":
        rows_bound = moe.dropless_rows_bound(48, k, 8, 8)
        assert rows_bound == k * 48
    rows_bound = max(rows_bound, 1)
    y, counters = moe.moe_dropless(
        x, weights, chosen, expert_params(flat, "l1.", 0, held, flat),
        first_held=0, rows_bound=rows_bound,
    )
    dropped = max(held_here - rows_bound, 0)
    assert float(counters["dropped_assignments"]) == dropped
    assert float(counters["held_share"]) == pytest.approx(held_here / (k * 48))
    assert float(counters["rows_used_share"]) == pytest.approx(
        min(held_here, rows_bound) / rows_bound
    )
    # what was kept is exact: the sorted order's first rows_bound assignments
    order = np.argsort(
        np.where(np.asarray(chosen).reshape(-1) < held,
                 np.asarray(chosen).reshape(-1), held), kind="stable",
    )[:min(held_here, rows_bound)]
    keep = np.zeros(k * 48, bool)
    keep[order] = True
    kept_weights = jnp.where(keep.reshape(48, k), weights, 0.0)
    out = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == e, kept_weights, 0.0), axis=-1)
        out = out + w_e[:, None] * ref.swiglu(
            x, flat["l1.experts.w1"][e], flat["l1.experts.w3"][e],
            flat["l1.experts.w2"][e], DOT,
        )
    np.testing.assert_allclose(y, out, atol=1e-6)
    if rows_bound >= held_here:
        assert dropped == 0


def test_the_work_follows_the_group_sizes_not_the_bound(flat):
    """The products are ragged: they take the group sizes and the rows, no
    per-expert dense product over the whole buffer; rows past the last
    group cost nothing and give nothing (an empty layer's output is 0)."""
    x = jax.random.normal(jax.random.key(6), (48, SIZES["hidden_size"]))
    k, held = SIZES["num_experts_per_tok"], SIZES["num_experts"]
    params = expert_params(flat, "l1.", 0, held, flat)
    weights, chosen = moe.moe_route_sigmoid(
        x, flat["l1.router.w"], flat["l1.select_bias"], top_k=k
    )

    def layer(x, weights, chosen):
        return moe.moe_dropless(
            x, weights, chosen, params, first_held=0, rows_bound=k * 48
        )[0]

    text = str(jax.make_jaxpr(layer)(x, weights, chosen))
    import re

    # gate+up as one product, and down; no other matrix product
    assert len(re.findall(r"= ragged_dot(?:_general)?\[", text)) == 2
    assert not re.findall(r"= dot_general\[", text)
    # every choice sent to absent experts: all groups empty, nothing computed
    absent = jnp.full_like(chosen, held)
    y, counters = moe.moe_dropless(
        x, weights, absent, params, first_held=0, rows_bound=k * 48
    )
    assert not np.asarray(y).any()
    assert float(counters["rows_used_share"]) == 0.0
    # the cost the compiler counts for the products does not grow with the
    # bound (XLA's CPU cost of a ragged dot is by the rows it is handed, so
    # the check is on the op's operands: group sizes of 0 past the held)
    sizes = jnp.asarray([3, 0, 5, 0])
    rows = jnp.ones((64, 4))
    out = moe.grouped_dot(rows, jnp.ones((4, 4, 2)), sizes)
    assert not np.asarray(out[8:]).any() and np.asarray(out[:8]).all()


# -- the chip's grouped product (ops/pallas/moe_gmm.py), interpreted ---------

def _dense_grouped(lhs, rhs, sizes):
    """Every row times its group's matrix; zeros past the last group."""
    group = np.searchsorted(np.cumsum(sizes), np.arange(lhs.shape[0]), side="right")
    inside = group < len(sizes)
    picked = rhs[np.minimum(group, len(sizes) - 1)]
    return jnp.where(inside[:, None], jnp.einsum("mk,mkn->mn", lhs, picked), 0.0)


@pytest.mark.parametrize(
    "m,k,n,sizes",
    [
        (64, 32, 48, [5, 0, 20, 7]),  # an empty group, groups sharing a tile
        (64, 32, 48, [16, 16, 16, 16]),  # the buffer full
        (128, 256, 384, [0, 0, 100, 3]),  # leading empty groups
        (64, 256, 384, [10, 30, 0, 9]),  # several tiles a dimension
        (64, 320, 200, [33, 0, 20, 0]),  # a contraction tile past k
        (64, 32, 48, [0, 0, 0, 0]),  # nothing routed here
    ],
    ids=["shared-tile", "full", "leading-empty", "many-tiles", "ragged-k", "empty"],
)
def test_the_pallas_grouped_product_agrees_with_the_dense_one(
    monkeypatch, m, k, n, sizes
):
    """Forward, the rows' gradient and the weights' gradient of
    ``moe_gmm.grouped_matmul`` under the Pallas interpreter, against every
    row times its group's matrix; what lies past the last group in the
    buffer is NaN here and must reach nothing. Tiles of 16 x 128 x 128 at
    most, so that these sizes have several a dimension."""
    from distributed_pytorch_example_tpu.ops.pallas import moe_gmm

    monkeypatch.setattr(moe_gmm, "LANE_TILE", 128)
    monkeypatch.setattr(moe_gmm, "_ROW_TILES", (16, 8))
    lhs = jax.random.normal(jax.random.key(0), (m, k))
    rhs = jax.random.normal(jax.random.key(1), (len(sizes), k, n)) / np.sqrt(k)
    ct = jax.random.normal(jax.random.key(2), (m, n))
    used = sum(sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(
        lambda l, r: moe_gmm.grouped_matmul(l, r, group_sizes, True),
        lhs.at[used:].set(jnp.nan), rhs,
    )
    d_lhs, d_rhs = vjp(ct.at[used:].set(jnp.nan))
    want, want_vjp = jax.vjp(
        lambda l, r: _dense_grouped(l, r, np.asarray(sizes)),
        lhs.at[used:].set(0.0), rhs,
    )
    want_d_lhs, want_d_rhs = want_vjp(ct.at[used:].set(0.0))
    for got, ref in ((out, want), (d_lhs, want_d_lhs), (d_rhs, want_d_rhs)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, ref, atol=2e-4 * np.sqrt(k), rtol=1e-4)
    assert not np.asarray(out[used:]).any() and not np.asarray(d_lhs[used:]).any()


def test_the_pallas_grouped_product_refuses_rows_not_in_whole_tiles():
    """On the chip ``grouped_dot`` has one route, and it does not fall back:
    a row count that no tile divides is an error, not another product."""
    from distributed_pytorch_example_tpu.ops.pallas import moe_gmm

    with pytest.raises(ValueError, match="whole tiles"):
        moe_gmm.grouped_matmul(
            jnp.zeros((63, 32)), jnp.zeros((4, 32, 48)),
            jnp.asarray([5, 0, 20, 7], jnp.int32), True,
        )


def test_the_pallas_grid_follows_the_group_sizes_not_the_buffer():
    """The kernels' grid along the rows is the number of (group, row tile)
    visits, a value made from the group sizes: growing the buffer does not
    grow it, and an empty routing visits nothing (the weights' gradient
    visits each group once, to write its zeros)."""
    from distributed_pytorch_example_tpu.ops.pallas import moe_gmm

    sizes = jnp.asarray([700, 0, 512, 300], jnp.int32)
    for m in (2048, 65536):
        _, visits = moe_gmm._visits(sizes, m, 512, visit_empty=False)
        # rows 0-699: tiles 0, 1; 700-1211: tiles 1, 2; 1212-1511: tile 2
        assert int(visits) == 5
        order, with_empty = moe_gmm._visits(sizes, m, 512, visit_empty=True)
        assert int(with_empty) == 6
        offsets, group_ids, tile_ids = (np.asarray(a) for a in order)
        assert offsets.tolist() == [0, 700, 700, 1212, 1512]
        assert group_ids[:6].tolist() == [0, 0, 1, 2, 2, 3]
        assert tile_ids[:6].tolist() == [0, 1, 1, 1, 2, 2]
    none = jnp.zeros((4,), jnp.int32)
    assert int(moe_gmm._visits(none, 2048, 512, visit_empty=False)[1]) == 0
    assert int(moe_gmm._visits(none, 2048, 512, visit_empty=True)[1]) == 4


# -- the normal path: train.py's flags for a deployment's share ---------------

SHARE_ARGV = [
    "--model", "lfm2-8b-a1b", "--layers-kept", "0,2,3,4,5", "--experts-held",
    "0,4", "--vocab-slice", "512", "--dataset", "synthetic-tokens",
    "--seq-len", "16", "--batch-size", "8", "--num-samples", "32", "--epochs",
    "1", "--remat", "--checkpoint-dir", "",
]


def test_train_main_trains_the_share_it_is_told(devices, monkeypatch):
    """``train.py`` builds the share from its three flags, draws the
    synthetic tokens from the slice, takes the fused loss and reports the
    routing's counters in the epoch record."""
    import train

    import distributed_pytorch_example_tpu as dpx

    built = {}
    real = dpx.models.get_model

    def tiny(name, **overrides):
        built.update(overrides)
        return real(name, **{**overrides, **tiny_lfm2.LFM2_MODEL})

    monkeypatch.setattr(dpx.models, "get_model", tiny)
    trainer = train.main(SHARE_ARGV)
    assert built["layers_kept"] == (0, 2, 3, 4, 5)
    assert (built["experts_first"], built["experts_held"]) == (0, 4)
    assert built["vocab_size"] == 512 and built["remat"] is True
    assert built["logits_mode"] == "hidden"  # the fused chunked-CE loss
    params = trainer.state.params
    assert sorted(k for k in params if k.startswith("layer_")) == [
        f"layer_{i}" for i in (0, 2, 3, 4, 5)
    ]
    assert params["tok_embed"]["embedding"].shape == (512, 64)
    assert params["layer_2"]["moe"]["gate_kernel"].shape[0] == 4
    assert params["layer_2"]["moe"]["router_kernel"].shape == (64, 8)
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]


@pytest.mark.parametrize(
    "flag,value",
    [("--layers-kept", "0,1"), ("--experts-held", "0,2"), ("--vocab-slice", "128")],
)
def test_a_model_without_a_share_refuses_the_share_flags(capsys, flag, value):
    import train

    with pytest.raises(SystemExit):
        train.main([
            "--model", "gpt2", "--dataset", "synthetic-tokens", "--epochs", "0",
            "--num-samples", "8", "--checkpoint-dir", "", flag, value,
        ])
    assert f"{flag} states a deployment's share" in capsys.readouterr().err


@pytest.mark.parametrize(
    "model,vocab_slice,vocab",
    [
        ("gpt2", None, 50257), ("llama", None, 32000), ("bert-base", None, 30522),
        ("vit-b16", None, 30522), ("lfm2-8b-a1b", None, 65536),
        ("lfm2-8b-a1b", 16384, 16384),
    ],
)
def test_synthetic_tokens_come_from_the_models_own_vocabulary(
    model, vocab_slice, vocab
):
    import argparse

    import train

    args = argparse.Namespace(
        dataset="synthetic-tokens", model=model, seq_len=8,
        vocab_slice=vocab_slice,
    )
    assert train.build_dataset(args, 4, seed=0).vocab_size == vocab


def test_the_selection_bias_as_drawn_picks_and_is_not_trained(flat, tokens):
    """The reference draws the bias normal(0, ``select_bias_std``): wide
    enough that it changes which experts are chosen and that the held
    experts' loads differ, and a buffer: no gradient reaches it."""
    published = SIZES["published"]["num_experts"]
    biases = np.stack([v for n, v in flat.items() if n.endswith("select_bias")])
    assert biases.shape == (4, published)
    assert 0.5 < biases.std() / SIZES["init"]["select_bias_std"] < 1.5
    h = jax.random.normal(jax.random.key(5), (256, SIZES["hidden_size"]))
    _, chosen = ref.route(h, flat, "l1.", SIZES, DOT)
    _, unbiased = ref.route(h, flat, "l1.", {**SIZES, "use_expert_bias": False}, DOT)
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()
    loads = np.bincount(np.asarray(chosen).reshape(-1), minlength=published)
    assert loads[:SIZES["num_experts"]].max() > loads[:SIZES["num_experts"]].min()
    grads = jax.grad(
        lambda p: ref.loss_sum(p, {"tokens": tokens}, SIZES, DOT)[0]
    )(flat)
    assert all(
        not np.any(g) for n, g in grads.items() if n.endswith("select_bias")
    )
