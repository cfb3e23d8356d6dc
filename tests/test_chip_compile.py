"""AOT-compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached here: ``topologies.get_topology_desc`` describes a
``v5e:2x2`` host and the installed TPU compiler compiles for it, so a
kernel the chip would refuse (tile shapes, VMEM, CompilerParams) is
refused in this file, at no chip time. Nothing runs — these say nothing
about results or speed (the interpret-mode tests pin numerics;
``chip_smoke.py`` is the run on the chip).

Shapes are GPT-2 124M's: 12 heads of 64, sequence 1024, bf16, 16
sequences a chip (the benchmark's gpt2 cells), and the serve shapes chip_smoke.py
uses. The topology is described inside a module-scoped fixture — never
at import — because only one process may hold libtpu, and every xdist
worker imports every test file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

BATCH, SEQ, HEADS, HEAD_DIM = 16, 1024, 12, 64
# serve.py at GPT-2 width as chip_smoke.py drives it: 8 slots, 1024-token
# context in 16-token blocks
SLOTS, BLOCK_SIZE, MAX_BLOCKS, NUM_BLOCKS = 8, 16, 64, 256


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, seq=SEQ):
    return tuple(
        jax.ShapeDtypeStruct(
            (BATCH, HEADS, seq, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )
        for _ in range(3)
    )


def _placed(where, one_chip, data_mesh):
    """(batch, sharding, context) of a flash call on one chip, or under the
    four-chip data mesh with 16 sequences a chip."""
    import contextlib

    if where == "one-chip":
        return BATCH, one_chip, contextlib.nullcontext()
    return 4 * BATCH, NamedSharding(data_mesh, P("data")), data_mesh


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_causal_compiles(one_chip, no_persistent_cache, direction):
    """GPT-2's attention: causal, head-major layout, single 1024 block
    (the fused single-tile forward and one-recompute backward)."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention_bnsh,
    )

    fwd = functools.partial(flash_attention_bnsh, causal=True)
    if direction == "fwd":
        _compile(fwd, *_qkv(one_chip))
    else:
        loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_multiblock_causal_compiles(
    one_chip, no_persistent_cache, direction
):
    """Sequence 2048 = two 1024 blocks: the online-softmax forward and the
    fused multi-block backward (the long-context path)."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention_bnsh,
    )

    fwd = functools.partial(flash_attention_bnsh, causal=True)
    if direction == "fwd":
        _compile(fwd, *_qkv(one_chip, seq=2048))
    else:
        loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip, seq=2048))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kv_mask_compiles(one_chip, no_persistent_cache, direction):
    """BERT-style: bidirectional with a key-padding mask, (B, S, N, H)."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    shape = (BATCH, 512, HEADS, HEAD_DIM)
    q, k, v = (
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        for _ in range(3)
    )
    mask = jax.ShapeDtypeStruct((BATCH, 512), jnp.bool_, sharding=one_chip)
    fwd = lambda q, k, v, m: flash_attention(q, k, v, kv_mask=m)
    if direction == "fwd":
        _compile(fwd, q, k, v, mask)
    else:
        loss = lambda q, k, v, m: fwd(q, k, v, m).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v, mask)


@pytest.mark.parametrize("where", ["one-chip", "dp4-mesh"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_causal_kv_mask_compiles(
    one_chip, data_mesh, no_persistent_cache, direction, where
):
    """The sub-tiled causal single-tile bodies with the key-padding port:
    static ref slices of q/k/v/lse/delta and of the mask's lanes, and the
    backward's dq scratch, at GPT-2's shapes."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    batch, sharding, ctx = _placed(where, one_chip, data_mesh)
    q, k, v = (
        jax.ShapeDtypeStruct(
            (batch, SEQ, HEADS, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )
        for _ in range(3)
    )
    mask = jax.ShapeDtypeStruct((batch, SEQ), jnp.bool_, sharding=sharding)
    fwd = lambda q, k, v, m: flash_attention(q, k, v, causal=True, kv_mask=m)
    loss = lambda q, k, v, m: fwd(q, k, v, m).astype(jnp.float32).sum()
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    with ctx:
        names = _kernel_names(_compile(fn, q, k, v, mask))
    assert all("_single_causal" in n for n in names), names


@pytest.mark.parametrize(
    "kv_heads,dtype",
    [(12, jnp.float32), (12, jnp.bfloat16), (4, jnp.bfloat16)],
    ids=["mha-f32", "mha-bf16", "gqa-bf16"],
)
def test_paged_decode_compiles(one_chip, no_persistent_cache, kv_heads, dtype):
    """The fused paged flash-decode at GPT-2 width: f32 as serve.py builds
    its model, bf16, and a GQA grouping."""
    from distributed_pytorch_example_tpu.ops.pallas.paged_attention import (
        paged_flash_decode,
    )

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = sds((NUM_BLOCKS, BLOCK_SIZE, kv_heads, HEAD_DIM), dtype)
    _compile(
        paged_flash_decode,
        sds((SLOTS, HEADS, HEAD_DIM), dtype),
        pool, pool,
        sds((SLOTS, MAX_BLOCKS), jnp.int32),
        sds((SLOTS,), jnp.int32),
    )


@pytest.fixture(scope="module")
def data_mesh(topo):
    """The mesh train.py builds for ``--mesh-data 4``: all six named axes,
    five of them of size 1."""
    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=4), devices=list(topo.devices))


def _data_manual(fn, mesh, out_spec):
    """``fn`` as train/step.py runs the wire collectives: in a region that
    is manual over ``data`` ONLY. The kernels' own ``_fully_manual`` wrap
    has to cover the other axes, or the TPU lowering refuses them."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=out_spec,
        axis_names={"data"}, check_vma=False,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["f32", "s8"])
def test_ring_all_gather_compiles(data_mesh, no_persistent_cache, dtype):
    """The async bidirectional-ring all-gather over four chips: the f32
    payload and the s8 one the int8 wire gathers carry."""
    from distributed_pytorch_example_tpu.ops.pallas import collectives

    n = 768 * 768  # one GPT-2 attention projection
    rows = collectives._half_rows(n // 4)
    fn = _data_manual(
        lambda x: collectives._fully_manual(
            lambda x: collectives.all_gather_kernel(x, "data", 4, rows)
        )(x),
        data_mesh, P(),
    )
    x = jax.ShapeDtypeStruct(
        (n,), dtype, sharding=NamedSharding(data_mesh, P("data"))
    )
    _compile(fn, x)


def test_ring_reduce_scatter_compiles(data_mesh, no_persistent_cache):
    """The ring reduce-scatter over four chips at one overlap bucket's
    size (parallel/wire.py DEFAULT_BUCKET_BYTES = 4 MiB of f32)."""
    from distributed_pytorch_example_tpu.ops.pallas import collectives

    chunk = 4 * 1024 * 1024 // 4 // 4  # elements per destination chunk
    fn = _data_manual(
        lambda x: collectives._fully_manual(
            lambda x: collectives.reduce_scatter_kernel(x[0], "data", 4)
        )(x),
        data_mesh, P("data"),
    )
    x = jax.ShapeDtypeStruct(
        (4, 4 * chunk), jnp.float32,
        sharding=NamedSharding(data_mesh, P("data")),
    )
    _compile(fn, x)


def test_flash_compiles_on_a_four_chip_mesh(data_mesh, no_persistent_cache):
    """``--mesh-data 4``: XLA cannot partition a Mosaic kernel, so the
    flash call has to arrive wrapped in its own shard_map (batch over
    ``data``) — from plain jit under the mesh, as plain data parallelism
    traces it."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention_bnsh,
    )

    sharding = NamedSharding(data_mesh, P("data"))
    q, k, v = (
        jax.ShapeDtypeStruct(
            (4 * BATCH, HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )
        for _ in range(3)
    )
    with data_mesh:
        _compile(functools.partial(flash_attention_bnsh, causal=True), q, k, v)


def _kernel_names(compiled):
    """The HLO instruction names of the compiled program's Pallas calls,
    without their numbers: ``pallas_call(name=...)`` under the transforms
    around it (``jvp_flash_fwd_single_``)."""
    return {
        line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    }


# the multi-block kernels of a forward call and of a training call
MULTIBLOCK_NAMES = {"fwd": {"flash_fwd"}, "bwd": {"flash_fwd", "flash_bwd_fused"}}


def _program_names(compiled):
    r"""The kernels' names as the program gave them (``name=`` on each
    ``pallas_call``): :func:`_kernel_names` with the transforms a bare
    ``jax.grad`` wraps around them stripped (``transpose_jvp_flash_bwd_fused__``
    -> ``flash_bwd_fused``). In a train step the instruction is
    ``flash_bwd_fused.N``, and the benchmark's ``gqa_flash_*`` /
    ``mla_flash_*`` readers match ``^flash_fwd(\.\d+)? = `` and
    ``^flash_bwd_fused(\.\d+)? = `` whole: a suffix on either name makes
    them read nothing."""
    return {
        re.fullmatch(r"(?:(?:jvp|transpose|vmap)_)*(.*?)_*", name).group(1)
        for name in _kernel_names(compiled)
    }


@pytest.mark.parametrize("where", ["one-chip", "dp4-mesh"])
@pytest.mark.parametrize("seq", [SEQ, 2 * SEQ], ids=["single", "multiblock"])
def test_flash_forward_and_backward_differ_by_name(
    topo, one_chip, data_mesh, no_persistent_cache, where, seq
):
    """What the device trace names an op by is the instruction's name: the
    forward and the backward kernel of one training step must be told
    apart by it (``name=`` on each ``pallas_call``), on one chip and under
    the shard_map that wraps the kernels on a mesh."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention_bnsh,
    )

    fwd = functools.partial(flash_attention_bnsh, causal=True)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    batch, sharding, ctx = _placed(where, one_chip, data_mesh)
    args = tuple(
        jax.ShapeDtypeStruct(
            (batch, HEADS, seq, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )
        for _ in range(3)
    )
    with ctx:
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    names = _kernel_names(compiled)
    forward = {n for n in names if "flash_fwd" in n}
    backward = {n for n in names if "flash_bwd" in n}
    assert forward and backward, names
    assert forward | backward == names and not forward & backward, names
    # one causal tile runs the sub-tiled bodies, under names of their own
    # (the count of calls by name is what says they engaged); the
    # multi-block grid skips whole blocks and keeps its names
    causal_tile = {n for n in names if "_single_causal" in n}
    assert causal_tile == (names if seq == SEQ else set()), names
    # ... letter for letter: the benchmark's readers match them whole
    assert _program_names(compiled) == (
        {"flash_fwd_single_causal", "flash_bwd_single_causal"}
        if seq == SEQ else MULTIBLOCK_NAMES["bwd"]
    )


# -- the lfm2-8b-a1b cell's kernels at its own shapes ------------------------
# 4 rows of 8192 tokens, 32 query / 8 key heads of 64; 8 held experts of
# 2048 x 1792, sorted assignments in twice the even share's rows

LFM2_ROWS, LFM2_SEQ, LFM2_Q_HEADS, LFM2_KV_HEADS = 4, 8192, 32, 8
LFM2_HELD, LFM2_DIM, LFM2_EXPERT_DIM = 8, 2048, 1792


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_gqa_8k_compiles(one_chip, no_persistent_cache, direction):
    """Grouped-query causal flash at 8192 tokens: the multi-block kernels,
    each key head serving four query heads, by the names the benchmark's
    ``gqa_flash_*_roofline`` readers match."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    sds = functools.partial(
        jax.ShapeDtypeStruct, dtype=jnp.bfloat16, sharding=one_chip
    )
    q = sds((LFM2_ROWS, LFM2_SEQ, LFM2_Q_HEADS, HEAD_DIM))
    k = v = sds((LFM2_ROWS, LFM2_SEQ, LFM2_KV_HEADS, HEAD_DIM))
    fwd = functools.partial(flash_attention, causal=True)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    # both causal bodies (no mask under the diagonal, sub-tiles on it)
    # compile under the two names the readers match, letter for letter
    assert _program_names(_compile(fn, q, k, v)) == MULTIBLOCK_NAMES[direction]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_mla_192_128_compiles(one_chip, no_persistent_cache, direction):
    """Latent attention as it trains: 32 heads, queries and keys 192 wide,
    values 128 wide, causal at 4096 tokens (the ``joyai-llm-flash`` cell's
    call): the multi-block kernels, by the names the benchmark's
    ``mla_flash_*_roofline`` readers match, with no value padded to 192."""
    from distributed_pytorch_example_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    sds = functools.partial(
        jax.ShapeDtypeStruct, dtype=jnp.bfloat16, sharding=one_chip
    )
    q = k = sds((4, 4096, 32, 192))
    v = sds((4, 4096, 32, 128))
    fwd = functools.partial(flash_attention, causal=True)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    compiled = _compile(fn, q, k, v)
    assert _program_names(compiled) == MULTIBLOCK_NAMES[direction]
    if direction == "bwd":
        dq, dk, dv = compiled.out_info
        assert (dq.shape[-1], dk.shape[-1], dv.shape[-1]) == (192, 192, 128)
    else:
        assert compiled.out_info.shape == (4, 4096, 32, 128)


def _layout_copies(compiled, batch, seq, heads, widths):
    """The ``copy`` and ``transpose`` instructions of a compiled program whose
    result has the (B, S, N, H) or (B, N, S, H) shape of a tensor ``widths``
    wide: each a layout change of a whole kernel operand, its output or
    their gradients (a copy keeps the logical shape and moves the bytes)."""
    shapes = {
        f"[{batch},{a},{b},{w}]"
        for w in widths for a, b in ((seq, heads), (heads, seq))
    }
    found = [
        re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+(\[[\d,]*\])\S* (\w+)\(", line)
        for line in compiled.as_text().splitlines()
    ]
    return sum(
        1 for m in found
        if m and m.group(2) in ("copy", "transpose") and m.group(1) in shapes
    )


@pytest.mark.parametrize("layout", ["head-major", "sequence-major"])
def test_latent_attention_calls_the_kernel_without_transposes(
    one_chip, no_persistent_cache, monkeypatch, layout
):
    """``LatentAttention`` at the ``joyai-llm-flash`` cell's widths (32 heads,
    q/k 192, v 128, ranks 1536 / 512), forward and gradient, 2 rows of 2048
    tokens (the multi-block kernels, as the cell's 4096): where the flash
    kernel serves the call, q, k and v are projected head-major and no
    instruction transposes them, the output or their gradients between
    (B, S, N, H) and (B, N, S, H). The (B, S, N, H) path it replaces, forced
    here, compiles six such copies: four 128 wide (v, the output and their
    gradients), the query gradient (192) and the concatenated key/value
    gradient (256). Nothing runs: a count of the compiler's."""
    from distributed_pytorch_example_tpu.models import joyai
    from distributed_pytorch_example_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    if layout == "sequence-major":
        monkeypatch.setattr(joyai, "fused_layout_eligible", lambda *a, **k: False)
    batch, seq, dim = 2, 2048, 2048
    module = joyai.LatentAttention(
        model_dim=dim, num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=32e6, norm_eps=1e-6, dtype=jnp.bfloat16,
    )
    x = jax.ShapeDtypeStruct((batch, seq, dim), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(module.init, jax.random.key(0), x)["params"],
    )
    loss = lambda p, x: module.apply({"params": p}, x).astype(jnp.float32).sum()
    compiled = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    assert _program_names(compiled) == MULTIBLOCK_NAMES["bwd"]
    copies = _layout_copies(compiled, batch, seq, 32, (128, 192, 256))
    assert copies == (0 if layout == "head-major" else 6), copies


WINDOW_NAMES = {
    "fwd": {"flash_fwd_window"},
    "bwd": {"flash_fwd_window", "flash_bwd_window_fused"},
    "split": {"flash_fwd_window", "flash_bwd_window_dq", "flash_bwd_window_dkv"},
}


@pytest.mark.parametrize("layer", ["window", "full"])
@pytest.mark.parametrize("layout", ["head-major", "sequence-major"])
def test_gated_window_attention_calls_the_kernel_without_transposes(
    one_chip, no_persistent_cache, monkeypatch, layout, layer
):
    """Trinity's attention at the ``trinity-mini`` cell's call (4 rows of 8192
    tokens, 32 query / 4 key heads of 128, RMSNorm on q and k, the output
    gate, no biases; the window layer with RoPE and a 2048-key band, the full
    layer without positions), forward and gradient under ``jax.checkpoint`` as
    the cell's remat runs it: where the flash kernel serves the call, q, k, v
    and the gate are projected head-major and no instruction copies or
    transposes them, the output or their gradients between (B, S, N, H) and
    (B, N, S, H). The (B, S, N, H) path it replaces, forced here, compiles
    eight such copies: q and the kernel's output in float32 (for ``q_norm``
    and the gate's product), k in float32 (``k_norm``), the output's and
    ``q_norm``'s gradients, k, v and their gradients. The band and causal
    kernels keep the names their readers match. Nothing runs: a count of the
    compiler's."""
    from distributed_pytorch_example_tpu.models import transformer
    from distributed_pytorch_example_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    if layout == "sequence-major":
        monkeypatch.setattr(
            transformer, "fused_layout_eligible", lambda *a, **k: False
        )
    batch, seq, dim = 4, 8192, 2048
    window = layer == "window"
    module = transformer.MultiHeadAttention(
        num_heads=32, num_kv_heads=4, head_dim=128, model_dim=dim,
        causal=True, dtype=jnp.bfloat16, rope=window, qk_norm=True,
        use_bias=False, output_gate=True, window=2048 if window else None,
    )
    x = jax.ShapeDtypeStruct((batch, seq, dim), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(module.init, jax.random.key(0), x)["params"],
    )
    apply = jax.checkpoint(lambda p, x: module.apply({"params": p}, x))
    loss = lambda p, x: apply(p, x).astype(jnp.float32).sum()
    compiled = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    assert _program_names(compiled) == (
        WINDOW_NAMES["bwd"] if window else MULTIBLOCK_NAMES["bwd"]
    )
    copies = sum(
        _layout_copies(compiled, batch, seq, heads, (128,)) for heads in (32, 4)
    )
    assert copies == (0 if layout == "head-major" else 8), copies


@pytest.mark.parametrize("direction", ["fwd", "bwd", "split"])
def test_flash_window_8k_compiles(
    one_chip, no_persistent_cache, monkeypatch, direction
):
    """The band kernels at the ``trinity-mini`` cell's call: 4 rows of 8192
    tokens, 32 query / 4 key heads of 128, a 2048-key sliding window; by
    the names the ``window_flash_*_roofline`` readers match, and the
    two-kernel backward the fused one falls back to."""
    from distributed_pytorch_example_tpu.ops.pallas import flash_attention as fa

    if direction == "split":
        monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_LIMIT", 0)
    sds = functools.partial(
        jax.ShapeDtypeStruct, dtype=jnp.bfloat16, sharding=one_chip
    )
    q = sds((4, 8192, 32, 128))
    k = v = sds((4, 8192, 4, 128))
    fwd = functools.partial(fa.flash_attention, causal=True, window=2048)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    assert _program_names(_compile(fn, q, k, v)) == WINDOW_NAMES[direction]


@pytest.mark.parametrize("product", ["gate_up", "down"])
@pytest.mark.parametrize("program", ["fwd", "d_rows", "d_weights"])
def test_grouped_product_compiles(one_chip, no_persistent_cache, product, program):
    """The dropless expert layer's grouped products (``ops/pallas/moe_gmm``,
    which ``moe.grouped_dot`` calls on the chip), forward and both backward
    products, at the cell's shapes, under the names the trace carries."""
    from distributed_pytorch_example_tpu.models import moe
    from distributed_pytorch_example_tpu.ops.pallas import moe_gmm

    tokens = LFM2_ROWS * LFM2_SEQ
    rows = moe.dropless_rows_bound(tokens, 4, LFM2_HELD, 32)
    k, n = {
        "gate_up": (LFM2_DIM, 2 * LFM2_EXPERT_DIM),
        "down": (LFM2_EXPERT_DIM, LFM2_DIM),
    }[product]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    lhs = sds((rows, k), jnp.bfloat16)
    rhs = sds((LFM2_HELD, k, n), jnp.bfloat16)
    sizes = sds((LFM2_HELD,), jnp.int32)
    g = sds((rows, n), jnp.bfloat16)
    product_of = moe_gmm.grouped_matmul
    fn = {
        "fwd": lambda lhs, rhs, sizes, g: product_of(lhs, rhs, sizes),
        "d_rows": lambda lhs, rhs, sizes, g: jax.vjp(
            lambda x: product_of(x, rhs, sizes), lhs)[1](g)[0],
        "d_weights": lambda lhs, rhs, sizes, g: jax.vjp(
            lambda w: product_of(lhs, w, sizes), rhs)[1](g)[0],
    }[program]
    names = _kernel_names(_compile(fn, lhs, rhs, sizes, g))
    wanted = "moe_gmm_dw" if program == "d_weights" else "moe_gmm"
    assert any(wanted in n for n in names), names


# --- the chunked cross-entropy (ops/chunked_ce.py), alone, at the cells' shapes


def _hlo_computations(text):
    """``{computation: [(name, shape, opcode, operands, attributes)]}`` of a
    compiled module's text, and the entry computation's name."""
    import re

    def closes(s):  # index past the parenthesis that closes s[0]
        depth = 0
        for i, ch in enumerate(s):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return i + 1

    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
            continue
        ins = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)$", line)
        if line.startswith("}") or cur is None or not ins:
            cur = None if line.startswith("}") else cur
            continue
        name, rest = ins.groups()
        cut = closes(rest) if rest.startswith("(") else rest.index(" ")
        shape, rest = rest[:cut], rest[cut:].lstrip()
        opcode = rest[:rest.index("(")]
        cut = len(opcode) + closes(rest[len(opcode):])
        operands = re.findall(r"%([\w.\-]+)", rest[len(opcode):cut])
        comps[cur].append((name, shape, opcode, operands, rest[cut:]))
    return comps, entry


def _forward_statistics(compiled, n, widths):
    """How the compiled op reads its forward logits. Returns ``(readers,
    fused)``: for each forward logits block the program materialises (an
    f32 ``(n, width)`` result of a fusion that holds the product), the
    fusions that read it and reduce it to row statistics (results of length
    ``n`` only: the backward's products and the bias gradient are not such);
    and the number of row-statistics fusions that hold the product
    themselves, so that their block is never written."""
    import re

    comps, entry = _hlo_computations(compiled.as_text())

    def opcodes(comp):
        found = set()
        for _, _, opcode, _, attrs in comps.get(comp, ()):
            found.add(opcode)
            if opcode == "fusion":
                found |= opcodes(re.search(r"calls=%([\w.\-]+)", attrs).group(1))
        return found

    alias = {
        name: operands[0]
        for name, _, opcode, operands, _ in comps[entry]
        if opcode in ("copy", "copy-start", "copy-done", "bitcast",
                      "get-tuple-element")
    }

    def origin(name):
        while name in alias:
            name = alias[name]
        return name

    fusions = [
        (name, shape, [origin(o) for o in operands],
         opcodes(re.search(r"calls=%([\w.\-]+)", attrs).group(1)))
        for name, shape, opcode, operands, attrs in comps[entry]
        if opcode == "fusion"
    ]
    blocks = [
        name for name, shape, _, held in fusions
        if "convolution" in held
        and any(shape.startswith(f"f32[{n},{w}]") for w in widths)
    ]
    statistics = [
        (name, operands, held) for name, shape, operands, held in fusions
        if "reduce" in held
        and all(dims == str(n) for dims in re.findall(r"\w+\[([\d,]*)\]", shape))
    ]
    readers = {
        b: [name for name, operands, _ in statistics if b in operands]
        for b in blocks
    }
    fused = sum("convolution" in held for _, _, held in statistics)
    return readers, fused


@pytest.mark.parametrize("cell,n,vocab,bias,serial", [
    ("gpt2", BATCH * (SEQ - 1), 50257, False, False),
    ("bert", 32 * 512, 30522, True, False),
    ("gpt2-serial", BATCH * (SEQ - 1), 50257, False, True),
])
def test_chunked_ce_reads_each_forward_block_once(
    one_chip, no_persistent_cache, monkeypatch, cell, n, vocab, bias, serial
):
    """Value-and-grad of the mean loss at the gpt2 and bert cells' shapes
    (and once with the block chain forced): every forward logits block has
    exactly ONE reducing reader (the two-pass forward had two), or none
    because the product sits inside the one reduction; XLA still CSEs the
    backward's recomputed logits against the forward's where it did (three
    products counted, all but four when serialized); and the bytes the compiler
    counts at gpt2's shapes stay under 17.5e9 (the two-pass forward:
    20.65e9). Nothing runs: a count of the compiler's, not a time."""
    from distributed_pytorch_example_tpu.ops import chunked_ce as cc

    if serial:
        monkeypatch.setattr(cc, "_SERIALIZE_TOTAL_BYTES", 0)
    dim = HEADS * HEAD_DIM
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = [sds((n, dim), jnp.bfloat16), sds((vocab, dim), jnp.float32),
            sds((n,), jnp.int32)]
    if bias:
        args.append(sds((vocab,), jnp.float32))

    def mean_loss(x, table, targets, b=None):
        loss, argmax = cc.chunked_softmax_xent(x, table, targets, bias=b)
        return loss.mean(), argmax

    compiled = jax.jit(jax.value_and_grad(
        mean_loss, argnums=(0, 1, 3) if bias else (0, 1), has_aux=True
    )).lower(*args).compile()

    spans = cc._blocks(vocab, cc.DEFAULT_BLOCK)
    readers, fused = _forward_statistics(
        compiled, n, {width for _, width in spans}
    )
    assert all(len(r) == 1 for r in readers.values()), readers
    assert len(readers) + fused == len(spans), (readers, fused)
    if serial:
        assert fused >= len(spans) - 1, (readers, fused)
    else:
        assert fused == 0 and len(readers) == len(spans), (readers, fused)

    cost = compiled.cost_analysis()
    product = 2.0 * n * dim * vocab
    products = cost["flops"] / product
    # serialized, only the block before the first barrier is still shared
    low = 4.0 - 1.5 / len(spans) if serial else 3.0
    assert low <= products < low + 0.2, products
    if cell == "gpt2":
        assert cost["bytes accessed"] < 17.5e9, cost["bytes accessed"]
