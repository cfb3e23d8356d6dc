"""Flash attention numerics vs the pure-XLA reference (interpret mode on CPU).

Forward and full VJP (dq, dk, dv) must match ``ops.attention._xla_attention``
for causal and non-causal, including multi-block sequence lengths that
exercise the online-softmax accumulation across k-blocks and the block-skip
logic on the causal diagonal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.ops.attention import _xla_attention
from distributed_pytorch_example_tpu.ops.pallas.flash_attention import flash_attention


def make_qkv(batch=2, seq=256, heads=2, head_dim=64, seed=0):
    rng = np.random.default_rng(seed)
    shape = (batch, seq, heads, head_dim)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 256, 384])
def test_forward_matches_xla(causal, seq):
    q, k, v = make_qkv(seq=seq)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, causal, scale)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = make_qkv(seq=256)
    scale = q.shape[-1] ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, None, causal, scale) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_multiblock_fused_backward_grads(causal, gqa, masked):
    """The fused multi-block backward (one logits recompute for dq/dk/dv,
    persistent dq scratch): explicit 128x64 blocks at seq 256 force the
    multi-block grid the default-blocks tests never reach."""
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    kvh = 2 if gqa else 4
    k = jnp.asarray(rng.standard_normal((2, 256, kvh, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, kvh, 64)), jnp.float32)
    kv_mask = make_kv_mask(seq=256, seed=22) if masked else None
    scale = 64 ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, None, kv_mask, causal, scale) ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, kv_mask=kv_mask, interpret=True,
                block_q=128, block_k=64,
            ) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("gqa", [False, True])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("ni", [2, 4, 6])
def test_folded_causal_grid_forward_and_grads(gqa, masked, ni):
    """The triangular (folded) causal schedule — equal square blocks, even
    block count — must match the XLA reference exactly like the square
    grid it replaces (every grid step a needed pair, no skipped ticks)."""
    seq = 128 * ni
    rng = np.random.default_rng(31 + ni)
    q = jnp.asarray(rng.standard_normal((2, seq, 4, 64)), jnp.float32)
    kvh = 2 if gqa else 4
    k = jnp.asarray(rng.standard_normal((2, seq, kvh, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, kvh, 64)), jnp.float32)
    kv_mask = make_kv_mask(seq=seq, seed=32) if masked else None
    scale = 64 ** -0.5

    # one trace a side: the output rides out of the loss as its aux
    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, None, kv_mask, True, scale)
        return jnp.sum(out ** 2), out

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, kv_mask=kv_mask, interpret=True,
            block_q=128, block_k=128,
        )
        return jnp.sum(out ** 2), out

    (_, expected), g_ref = jax.value_and_grad(loss_ref, (0, 1, 2), has_aux=True)(q, k, v)
    (_, got), g_flash = jax.value_and_grad(loss_flash, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
def test_multiblock_split_fallback_grads(causal, monkeypatch):
    """The two-kernel fallback (_bwd_split, used when the fused kernel's
    dq scratch would exceed VMEM) must stay numerically identical — forced
    here by shrinking the limit below seq*head_dim*4."""
    from distributed_pytorch_example_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_LIMIT", 0)
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    kv_mask = make_kv_mask(seq=256, seed=24)
    scale = 64 ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, None, kv_mask, causal, scale) ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.flash_attention(
                q, k, v, causal=causal, kv_mask=kv_mask, interpret=True,
                block_q=128, block_k=64,
            ) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


# ---------------------------------------------------------------------------
# the multi-block causal kernels: no mask under the diagonal, prefix-only
# sub-tiles in the blocks on it
# ---------------------------------------------------------------------------

# case -> (seq, kv heads of 4, key padding, force the two-kernel backward);
# blocks of 256 rows, which _causal_sub cuts in two sub-tiles of 128: block
# counts 2 and 4 run the folded grid, 3 the square one with ``needed``
# (count 2 with no mask: WIDTH_PATHS' "multi-block-subtiled-diagonal")
DIAGONAL_CASES = {
    "folded-4-gqa": (1024, 2, None, False),
    "folded-2-gqa-padded-row": (512, 2, "padded-row", False),
    "needed-3-masked": (768, 4, "random", False),
    "two-kernel-2-gqa-padded-row": (512, 2, "padded-row", True),
    "two-kernel-3": (768, 4, None, True),
}


@pytest.mark.parametrize("case", sorted(DIAGONAL_CASES))
def test_multiblock_causal_subtiled_diagonal_matches_xla(case, monkeypatch):
    """Forward and all three gradients against the XLA reference where the
    blocks on the diagonal walk static sub-tiles and the blocks under it
    run without a mask: folded and square grids, grouped keys, key padding
    (one batch row with no valid key: zero output, zero gradients), the
    fused backward and the two-kernel fallback."""
    from distributed_pytorch_example_tpu.ops.pallas import flash_attention as fa

    seq, kvh, masked, split = DIAGONAL_CASES[case]
    assert fa._causal_sub(256) == 128
    if split:
        monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_LIMIT", 0)
    rng = np.random.default_rng(51 + seq)
    batch = 2 if masked == "padded-row" else 1
    q = jnp.asarray(rng.standard_normal((batch, seq, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((batch, seq, kvh, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((batch, seq, kvh, 64)), jnp.float32)
    kv_mask = None
    if masked:
        kv_mask = np.array(make_kv_mask(batch=batch, seq=seq, seed=52))
        if masked == "padded-row":
            kv_mask[1, :] = False
        kv_mask = jnp.asarray(kv_mask)
    scale = 64 ** -0.5

    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, None, kv_mask, True, scale)
        return jnp.sum(out ** 2), out

    def loss_flash(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=True, kv_mask=kv_mask, interpret=True,
            block_q=256, block_k=256,
        )
        return jnp.sum(out ** 2), out

    (_, want), g_ref = jax.value_and_grad(loss_ref, (0, 1, 2), has_aux=True)(q, k, v)
    (_, got), g_flash = jax.value_and_grad(loss_flash, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if masked == "padded-row":
        np.testing.assert_array_equal(np.asarray(got)[1], 0.0)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )
        if masked == "padded-row":
            np.testing.assert_array_equal(np.asarray(gf)[1], 0.0)


# ---------------------------------------------------------------------------
# the single-tile causal kernels: prefix-only sub-tiles inside the tile
# ---------------------------------------------------------------------------


def _pallas_kernels(fn, *args):
    """{kernel name: kernel jaxpr} of every ``pallas_call`` ``fn`` traces."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn.params["jaxpr"]
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _dot_flops(jaxpr):
    """Operations of the ``dot_general``s a kernel body holds (a CPU run
    gives counts: what the kernel computes, not how fast)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            depth = np.prod([eqn.invars[0].aval.shape[d] for d in contract])
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * int(depth)
    return total


def _flash_kernels(seq, causal):
    """The forward and backward kernels a training call at ``seq`` traces
    at the default blocks (one tile up to 1024; over it the multi-block
    grid, folded when causal); nothing runs."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention_bnsh,
    )

    x = jax.ShapeDtypeStruct((1, 2, seq, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention_bnsh(q, k, v, causal=causal, interpret=True)
        return out.astype(jnp.float32).sum()

    return _pallas_kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


@pytest.mark.parametrize("masked", [None, "random", "padded-row"])
@pytest.mark.parametrize("gqa", [False, True])
@pytest.mark.parametrize("seq", [256, 384, 1024])
def test_causal_single_tile_subtiles_match_xla(seq, gqa, masked):
    """Two, three and four query sub-tiles, each given only its key
    prefix: forward and all three gradients against the XLA reference,
    with GQA and with key padding (one batch row fully padded: zero
    output, zero gradients)."""
    rng = np.random.default_rng(41 + seq)
    kvh = 2 if gqa else 4
    q = jnp.asarray(rng.standard_normal((2, seq, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, seq, kvh, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, kvh, 64)), jnp.float32)
    kv_mask = None
    if masked:
        kv_mask = np.array(make_kv_mask(seq=seq, seed=42))
        if masked == "padded-row":
            kv_mask[1, :] = False
        kv_mask = jnp.asarray(kv_mask)
    scale = 64 ** -0.5

    # one trace a side: the output rides out of the loss as its aux
    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, None, kv_mask, True, scale)
        return jnp.sum(out ** 2), out

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, kv_mask=kv_mask, interpret=True
        )
        return jnp.sum(out ** 2), out

    (_, expected), g_ref = jax.value_and_grad(loss_ref, (0, 1, 2), has_aux=True)(q, k, v)
    (_, got), g_flash = jax.value_and_grad(loss_flash, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)
    if masked == "padded-row":
        np.testing.assert_array_equal(np.asarray(got)[1], 0.0)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )
        if masked == "padded-row":
            np.testing.assert_array_equal(np.asarray(gf)[1], 0.0)


@pytest.mark.parametrize(
    "seq,causal,suffix",
    [
        (256, True, "_causal"), (384, True, "_causal"), (1024, True, "_causal"),
        (128, True, ""),  # fewer than two sub-tiles: the whole-tile body
        (512, False, ""), (1024, False, ""),
    ],
)
def test_single_tile_kernel_names_say_which_body_ran(seq, causal, suffix):
    """The kernel's name is the engagement counter a device trace shows:
    the sub-tiled causal bodies have names of their own."""
    assert set(_flash_kernels(seq, causal)) == {
        "flash_fwd_single" + suffix, "flash_bwd_single" + suffix,
    }


@pytest.mark.parametrize(
    "sub,visited,total", [(512, 3, 4), (256, 10, 16), (128, 36, 64)]
)
def test_causal_visited_pairs(sub, visited, total):
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        causal_visited_pairs,
    )

    assert causal_visited_pairs(1024, sub) == (visited, total)


@pytest.mark.parametrize(
    "seq,sub,visited,total",
    [
        # the backward's sub-tiles of 256 rows, the forward's of 512
        (4096, 256, 136, 256), (8192, 256, 528, 1024),
        (4096, 512, 36, 64), (8192, 512, 136, 256),
        # whole blocks, as before the diagonal was told apart: 10 and 36
        # blocks of 16 sub-tile pairs each
        (4096, 1024, 10, 16), (8192, 1024, 36, 64),
    ],
)
def test_causal_visited_pairs_in_blocks_of_1024(seq, sub, visited, total):
    """The multi-block case: blocks under the diagonal whole, blocks on it
    by key prefixes of their sub-tiles (the joyai cell's S 4096 and the
    lfm2 cell's S 8192 at the kernels' block and sub-tile sizes)."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK,
        causal_visited_pairs,
    )

    assert causal_visited_pairs(seq, sub, DEFAULT_BLOCK) == (visited, total)


@pytest.mark.parametrize(
    "seq,sub",
    [(64, 0), (128, 0), (192, 0), (256, 128), (320, 0), (384, 128),
     (512, 256), (640, 128), (768, 256), (1024, 256)],
)
def test_causal_sub_fits_the_sequence(seq, sub):
    """The largest multiple of 128 under the module's constant that cuts
    the sequence into two or more sub-tiles; 0 = the whole-tile body."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        _causal_sub,
    )

    assert _causal_sub(seq) == sub


@pytest.mark.parametrize(
    "kernel,equations,flops",
    [("flash_fwd_single", 20, 268435456), ("flash_bwd_single", 26, 671088640)],
)
def test_causal_single_tile_computes_under_two_thirds(kernel, equations, flops):
    """At S 1024 the causal kernel's products are at most 0.65 of the
    non-causal kernel's (10 of 16 sub-tile pairs at sub 256), and the
    non-causal body is the one it was before the causal one was sub-tiled
    (equations and operations counted on the parent commit)."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        _causal_sub,
        causal_visited_pairs,
    )

    full = _flash_kernels(1024, False)[kernel]
    assert (len(full.eqns), _dot_flops(full)) == (equations, flops)
    tiled = _dot_flops(_flash_kernels(1024, True)[kernel + "_causal"])
    visited, total = causal_visited_pairs(1024, _causal_sub(1024))
    assert tiled * total == flops * visited
    assert tiled <= 0.65 * flops


def _primitives(jaxpr):
    """Names of every primitive under ``jaxpr`` (``jnp.where`` traces as a
    ``jit`` around its ``select_n``)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize(
    "kernel,equations,flops,sub,share",
    [
        ("flash_fwd", 36, 268435456, 512, 0.75),
        ("flash_bwd_fused", 44, 671088640, 256, 0.65),
    ],
)
def test_causal_multiblock_tells_the_diagonal_apart(
    kernel, equations, flops, sub, share
):
    """The counter that says the mechanism engaged (the kernels' names
    cannot: the benchmark's readers match them), at the joyai cell's S 4096
    in blocks of 1024. Of the causal kernel's two
    ``pl.when`` bodies that hold products, the one for blocks under the
    diagonal computes a whole block pair with no iota and no select, and
    the one for blocks on it only its sub-tiles' key prefixes (backward: 10
    of 16 sub-tile pairs at sub 256; forward: 3 of 4 at sub 512) with the
    mask in it; the non-causal kernel is the one it was (equations and
    operations counted on the parent commit)."""
    from distributed_pytorch_example_tpu.ops.pallas import flash_attention as fa

    full = _flash_kernels(4096, False)[kernel]
    assert (len(full.eqns), _dot_flops(full)) == (equations, flops)
    bodies = [
        max(eqn.params["branches"], key=lambda b: len(b.jaxpr.eqns)).jaxpr
        for eqn in _flash_kernels(4096, True)[kernel].eqns
        if eqn.primitive.name == "cond"
    ]
    on, under = sorted(
        (body for body in bodies if _dot_flops(body)), key=_dot_flops
    )
    assert _dot_flops(under) == flops
    assert not _primitives(under) & {"iota", "select_n"}
    assert {"iota", "select_n"} <= _primitives(on)
    rows = fa.CAUSAL_SUB_FWD_BLOCK if kernel == "flash_fwd" else fa.CAUSAL_SUB
    assert fa._causal_sub(fa.DEFAULT_BLOCK, rows) == sub
    visited, total = fa.causal_visited_pairs(fa.DEFAULT_BLOCK, sub)
    assert _dot_flops(on) * total == flops * visited
    assert _dot_flops(on) <= share * flops


def test_uneven_blocks_rejected():
    q, k, v = make_qkv(seq=200)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)


def test_small_seq_shrinks_blocks():
    # seq < block: block shrinks to seq, single-block path
    q, k, v = make_qkv(seq=64)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, True, scale)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_forced_flash_unsupported_raises():
    """use_flash=True must fail loudly, not silently degrade (CPU here)."""
    from distributed_pytorch_example_tpu.ops.attention import dot_product_attention

    q, k, v = make_qkv(seq=128)
    with pytest.raises(ValueError, match="flash"):
        dot_product_attention(q, k, v, use_flash=True)  # CPU → unsupported


def test_causal_cross_length_not_auto_selected():
    """Causal seq_q != seq_k disagrees between kernels; auto must pick XLA."""
    from distributed_pytorch_example_tpu.ops.attention import (
        _flash_unsupported_reason,
    )

    q, _, _ = make_qkv(seq=128)
    k, v, _ = make_qkv(seq=256)
    assert _flash_unsupported_reason(q, k, v, None, True) is not None


def make_kv_mask(batch=2, seq=256, seed=5, min_valid=1):
    """Random key-padding mask with >= min_valid valid keys per row."""
    rng = np.random.default_rng(seed)
    mask = rng.random((batch, seq)) > 0.3
    mask[:, :min_valid] = True  # no fully-padded rows by default
    return jnp.asarray(mask)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_forward_matches_xla(causal):
    q, k, v = make_qkv(seq=256)
    kv_mask = make_kv_mask(seq=256)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, kv_mask, causal, scale)
    got = flash_attention(
        q, k, v, causal=causal, kv_mask=kv_mask, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_kv_mask_grads_match_xla():
    q, k, v = make_qkv(seq=256, seed=7)
    kv_mask = make_kv_mask(seq=256, seed=8)
    scale = q.shape[-1] ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, kv_mask, False, scale) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, kv_mask=kv_mask, interpret=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_kv_mask_fully_padded_batch_row_is_finite():
    """A batch row with ZERO valid keys: zero output, zero grads, no NaNs."""
    q, k, v = make_qkv(seq=128, seed=9)
    mask = np.ones((2, 128), bool)
    mask[1, :] = False  # batch row 1 fully padded
    kv_mask = jnp.asarray(mask)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, kv_mask=kv_mask, interpret=True) ** 2
        )

    out = flash_attention(q, k, v, kv_mask=kv_mask, interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out)[1], 0.0)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, name in zip(grads, "qkv"):
        g = np.asarray(g)
        assert np.all(np.isfinite(g)), f"d{name} has non-finite values"
        np.testing.assert_array_equal(g[1], 0.0, err_msg=f"d{name} row 1")


def test_kv_mask_via_dispatcher_keeps_xla_on_cpu():
    """kv_mask through dot_product_attention matches the masked reference."""
    from distributed_pytorch_example_tpu.ops.attention import (
        dot_product_attention,
    )

    q, k, v = make_qkv(seq=128)
    kv_mask = make_kv_mask(seq=128)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, kv_mask, False, scale)
    got = dot_product_attention(q, k, v, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_fully_padded_rows_zero_on_xla_path_too():
    """XLA and flash paths must agree on fully-padded rows (both zero)."""
    q, k, v = make_qkv(seq=128, seed=11)
    mask = np.ones((2, 128), bool)
    mask[0, :] = False
    kv_mask = jnp.asarray(mask)
    scale = q.shape[-1] ** -0.5
    xla = _xla_attention(q, k, v, None, kv_mask, False, scale)
    np.testing.assert_array_equal(np.asarray(xla)[0], 0.0)
    flash = flash_attention(q, k, v, kv_mask=kv_mask, interpret=True)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(xla), atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_forward_and_grads_match_xla(causal):
    """Grouped-query attention: 4 q-heads sharing 2 kv-heads."""
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    scale = 64 ** -0.5

    expected = _xla_attention(q, k, v, None, None, causal, scale)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, None, causal, scale) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [197, 100])
def test_lane_padded_forward_matches_xla(causal, seq):
    """Explicit-opt-in lane-padded flash at seq % 128 != 0 (ViT's 197)."""
    from distributed_pytorch_example_tpu.ops.attention import _flash_lane_padded

    q, k, v = make_qkv(seq=seq)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, causal, scale)
    got = _flash_lane_padded(q, k, v, None, causal, scale, interpret=True)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_lane_padded_grads_match_xla(causal):
    """Padded queries' cotangents are zero: grads at 197 tokens are exact."""
    from distributed_pytorch_example_tpu.ops.attention import _flash_lane_padded

    q, k, v = make_qkv(seq=197, seed=3)
    scale = q.shape[-1] ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, None, causal, scale) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            _flash_lane_padded(q, k, v, None, causal, scale, interpret=True)
            ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_lane_padded_kv_mask_and_fully_padded_row():
    """kv_mask streams through the pad; a fully-padded batch row emits
    zero output and zero grads (the flash kv_mask contract survives
    lane-padding)."""
    from distributed_pytorch_example_tpu.ops.attention import _flash_lane_padded

    q, k, v = make_qkv(seq=197, seed=13)
    mask = np.ones((2, 197), bool)
    mask[0, 150:] = False  # partial padding on row 0
    mask[1, :] = False     # row 1 fully padded
    kv_mask = jnp.asarray(mask)
    scale = q.shape[-1] ** -0.5

    expected = _xla_attention(q, k, v, None, kv_mask, False, scale)
    got = _flash_lane_padded(q, k, v, kv_mask, False, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[1], 0.0)

    def loss(q, k, v):
        return jnp.sum(
            _flash_lane_padded(q, k, v, kv_mask, False, scale, interpret=True)
            ** 2
        )

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, name in zip(grads, "qkv"):
        g = np.asarray(g)
        assert np.all(np.isfinite(g)), f"d{name} has non-finite values"
        np.testing.assert_array_equal(g[1], 0.0, err_msg=f"d{name} row 1")


def test_misaligned_seq_auto_dispatch_takes_xla(monkeypatch):
    """Auto dispatch at seq % 128 != 0 must use the XLA path — the
    lane-padded flash path measured SLOWER at ViT bench shapes and is
    opt-in only (VERDICT r3 #1)."""
    from distributed_pytorch_example_tpu.ops import attention

    def _boom(*a, **kw):  # pragma: no cover - fails the test if reached
        raise AssertionError("auto dispatch took the lane-padded flash path")

    monkeypatch.setattr(attention, "_flash_lane_padded", _boom)
    # pretend we're on TPU so seq misalignment is the ONLY flash blocker —
    # otherwise the r3 (regressing) dispatch would also skip the padded
    # path here (CPU rig) and the guard would pass vacuously
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q, k, v = make_qkv(seq=197)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, False, scale)
    got = attention.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_gqa_indivisible_heads_not_selected():
    from distributed_pytorch_example_tpu.ops.attention import (
        _flash_unsupported_reason,
    )

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 6, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    assert "heads" in _flash_unsupported_reason(q, k, k, None, False)


def test_fused_layout_attention_matches_classic(monkeypatch):
    """The fused projection layout (einsum prologue -> BNSH kernel ->
    einsum epilogue, models/transformer.py) computes the SAME attention
    as the classic Dense -> reshape -> flash path, with the identical
    param tree (checkpoints interchangeable between platforms/paths).
    CPU drive: eligibility forced, kernel in interpret mode."""
    import functools

    import numpy as np
    import optax

    from distributed_pytorch_example_tpu.models import transformer as tf_mod
    from distributed_pytorch_example_tpu.ops.pallas import (
        flash_attention as fa_mod,
    )

    mha = tf_mod.MultiHeadAttention(
        num_heads=2, head_dim=64, model_dim=128, causal=True,
    )
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 128, 128)) * 0.3,
        jnp.float32,
    )
    params = mha.init(jax.random.key(0), x, train=False)["params"]
    classic = mha.apply({"params": params}, x, train=False)

    monkeypatch.setattr(tf_mod, "fused_layout_eligible", lambda *a, **k: True)
    monkeypatch.setattr(
        fa_mod, "flash_attention_bnsh",
        functools.partial(fa_mod.flash_attention_bnsh, interpret=True),
    )
    fused_params = mha.init(jax.random.key(0), x, train=False)["params"]
    # identical param tree and values between the two paths
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params, fused_params,
    )
    fused = mha.apply({"params": params}, x, train=False)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(classic), atol=2e-5
    )

    # gradients agree too (the custom-VJP backward under the new layout)
    g_fused = jax.grad(lambda p: jnp.sum(
        mha.apply({"params": p}, x, train=False) ** 2
    ))(params)
    monkeypatch.undo()
    g_classic = jax.grad(lambda p: jnp.sum(
        mha.apply({"params": p}, x, train=False) ** 2
    ))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        ),
        g_fused, g_classic,
    )


# ---------------------------------------------------------------------------
# on a mesh: the kernel runs inside a fully-manual shard_map
# ---------------------------------------------------------------------------


def _mesh_case(seed=7):
    rng = np.random.default_rng(seed)
    shape = (4, 128, 4, 64)  # (B, S, N, H): batch over data x fsdp, heads over tensor
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


def _loss(fn):
    return lambda q, k, v: (fn(q, k, v) ** 2).sum()


@pytest.mark.parametrize("nested", [False, True], ids=["auto", "data-manual"])
def test_flash_on_mesh_matches_reference(mesh_2x2x2, nested):
    """A Mosaic kernel cannot be partitioned by XLA, so on a mesh
    ``flash_attention`` wraps itself in a shard_map over the axes that are
    still automatic (batch over data x fsdp, heads over tensor). Forward
    and grads must equal the dense reference both from plain jit under
    ``with mesh:`` and from inside a region that is manual over ``data``
    only — the ZeRO-1 step's body, where the wrap covers the rest."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _mesh_case()
    scale = q.shape[-1] ** -0.5
    ref = lambda q, k, v: _xla_attention(q, k, v, None, None, True, scale)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True
    )
    if nested:
        spec = P("data")
        flash = jax.shard_map(
            flash, mesh=mesh_2x2x2, in_specs=(spec,) * 3, out_specs=spec,
            axis_names={"data"}, check_vma=False,
        )
    with mesh_2x2x2:
        got = jax.jit(flash)(q, k, v)
        g_got = jax.jit(jax.grad(_loss(flash), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref(q, k, v)), atol=2e-5
    )
    g_ref = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_on_mesh_is_a_shard_map(mesh_2x2x2):
    """The wrap is really there (and absent off-mesh): on the chip its
    absence is a lowering error, which no CPU run would show."""
    q, k, v = _mesh_case()
    fn = lambda q, k, v: flash_attention(q, k, v, interpret=True)
    assert "shard_map" not in str(jax.make_jaxpr(fn)(q, k, v))
    with mesh_2x2x2:
        assert "shard_map" in str(jax.make_jaxpr(fn)(q, k, v))


# -- q/k heads wider than v heads (latent attention: 192 / 128) ---------------

# path -> (seq, causal, blocks, kv heads of 4, force the two-kernel backward)
WIDTH_PATHS = {
    "single-tile": (256, False, None, 4, False),
    "single-tile-causal-subtiles": (256, True, None, 4, False),
    "multi-block-folded-causal": (256, True, (128, 128), 4, False),
    "multi-block-square-gqa": (256, False, (128, 64), 2, False),
    "multi-block-subtiled-diagonal": (512, True, (256, 256), 4, False),
    "two-kernel-backward": (256, True, (128, 64), 4, True),
}


@pytest.mark.parametrize("dims", [(192, 128), (64, 64)], ids=["192-128", "64-64"])
@pytest.mark.parametrize("path", sorted(WIDTH_PATHS))
def test_value_width_apart_from_query_key_width(dims, path, monkeypatch):
    """Forward and all three gradients against the XLA attention where the
    values are narrower than queries and keys, on every kernel path: the
    single tile (whole and causal sub-tiles), the fused multi-block backward
    (folded and square grids, grouped keys, 256-blocks whose diagonal walks
    sub-tiles) and the two-kernel fallback; and at equal widths, where
    nothing may change."""
    from distributed_pytorch_example_tpu.ops.pallas import (
        flash_attention as fa,
    )

    qk_dim, v_dim = dims
    seq, causal, blocks, kv_heads, split = WIDTH_PATHS[path]
    if split:
        monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_LIMIT", 0)
    rng = np.random.default_rng(41)
    q = jnp.asarray(rng.standard_normal((2, seq, 4, qk_dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, seq, kv_heads, qk_dim)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, kv_heads, v_dim)), jnp.float32)
    scale = qk_dim ** -0.5
    kwargs = {} if blocks is None else dict(block_q=blocks[0], block_k=blocks[1])

    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, None, None, causal, scale)
        return jnp.sum(out ** 2), out

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=True, **kwargs)
        return jnp.sum(out ** 2), out

    (_, want), g_ref = jax.value_and_grad(loss_ref, (0, 1, 2), has_aux=True)(q, k, v)
    (_, got), g_flash = jax.value_and_grad(loss_flash, (0, 1, 2), has_aux=True)(q, k, v)
    assert got.shape == (2, seq, 4, v_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for gr, gf, name in zip(g_ref, g_flash, "qkv"):
        assert gf.shape == gr.shape, name
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_dispatcher_admits_192_with_128_wide_values(monkeypatch):
    from distributed_pytorch_example_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((4, 4096, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16)
    assert attention._flash_unsupported_reason(q, q, v, None, True) is None
    odd = jax.ShapeDtypeStruct((4, 4096, 32, 96), jnp.bfloat16)
    assert "v head_dim 96" in attention._flash_unsupported_reason(q, q, odd, None, True)
    assert "differ" in attention._flash_unsupported_reason(q, v, v, None, True)
