"""Ulysses all-to-all sequence parallelism vs dense attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_pytorch_example_tpu.ops.attention import _xla_attention
from distributed_pytorch_example_tpu.ops.ulysses import (
    ulysses_attention,
    ulysses_attention_sharded,
)
from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh


def make_qkv(batch=2, seq=256, heads=4, head_dim=32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (batch, seq, heads, head_dim)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_matches_full_attention(devices, causal):
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, k, v = make_qkv()
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, causal, scale)
    got = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_full_attention(devices, causal):
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, k, v = make_qkv(seq=128)
    scale = q.shape[-1] ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, None, causal, scale) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention_sharded(q, k, v, mesh, causal=causal) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for gr, gg, name in zip(g_ref, g_uly, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(gr), atol=1e-4, err_msg=f"d{name}"
        )


def test_gqa_under_ulysses(devices):
    """GQA works through the all-to-all path (ring serves it too — see
    tests/test_ring_attention.py — with different memory trade-offs)."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, _, _ = make_qkv(heads=8)
    _, k, v = make_qkv(heads=4, seed=1)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, True, scale)
    got = ulysses_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_grouped_matches_dense(devices, causal):
    """kv_heads < axis size takes the GROUPED path (no replication):
    kv=2 over a 4-device sequence axis (rep=2) must match dense GQA."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, _, _ = make_qkv(heads=8)
    _, k, v = make_qkv(heads=2, seed=1)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, None, causal, scale)
    got = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_grouped_grads_match_dense(devices, causal):
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, _, _ = make_qkv(heads=8, seq=128)
    _, k, v = make_qkv(heads=2, seq=128, seed=3)
    scale = q.shape[-1] ** -0.5

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, None, causal, scale) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention_sharded(q, k, v, mesh, causal=causal) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for gr, gg, name in zip(g_ref, g_uly, "qkv"):
        assert gg.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(gr), atol=1e-4, err_msg=f"d{name}"
        )


def test_gqa_grouped_kv_mask_and_dead_rows(devices):
    """Key-padding masks stream through the grouped path; a fully-padded
    batch row emits zeros (the _xla_attention contract)."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, _, _ = make_qkv(heads=8)
    _, k, v = make_qkv(heads=2, seed=5)
    mask = np.ones((2, 256), bool)
    mask[0, 100:] = False
    mask[1, :] = False  # fully padded row
    kv_mask = jnp.asarray(mask)
    scale = q.shape[-1] ** -0.5
    expected = _xla_attention(q, k, v, None, kv_mask, False, scale)
    got = ulysses_attention_sharded(q, k, v, mesh, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[1], 0.0)


def test_gqa_grouped_bf16_forward_and_grads(devices):
    """The custom-VJP grouped path in the training dtype (bfloat16)."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, _, _ = make_qkv(heads=8, seq=128)
    _, k, v = make_qkv(heads=2, seq=128, seed=7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    scale = q.shape[-1] ** -0.5

    expected = _xla_attention(qb, kb, vb, None, None, True, scale)
    got = ulysses_attention_sharded(qb, kb, vb, mesh, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32),
        atol=2e-2,
    )

    def loss_ref(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, None, None, True, scale)
            .astype(jnp.float32) ** 2
        )

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention_sharded(q, k, v, mesh, causal=True)
            .astype(jnp.float32) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qb, kb, vb)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(qb, kb, vb)
    for gr, gg, name in zip(g_ref, g_uly, "qkv"):
        assert gg.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(gg, np.float32), np.asarray(gr, np.float32),
            atol=0.15, rtol=0.05, err_msg=f"d{name}",
        )


def test_gqa_grouped_exchange_layout_and_bytes(devices):
    """The grouped K/V exchange routes each device exactly its group
    head's 1/rep sequence shard: content pinned against manual slicing,
    and per-device KV bytes are rep x SMALLER than the replicating
    layout's (B, S, 1, H)."""
    from distributed_pytorch_example_tpu.ops.ulysses import (
        _grouped_kv_exchange,
    )

    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    p, rep, kv = 4, 2, 2
    B, S, H = 2, 64, 8
    Sp, c = S // p, S // p // rep
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.standard_normal((B, S, kv, H)), jnp.float32)

    fn = jax.shard_map(
        lambda x: _grouped_kv_exchange(x, "sequence", rep)[None],
        mesh=mesh,
        in_specs=P(None, "sequence", None, None),
        out_specs=P("sequence"),
    )
    per_dev = np.asarray(fn(k))  # (p, B, p, c, H): leading dim = device
    for d in range(p):
        g, r = d // rep, d % rep
        for s in range(p):
            expect = np.asarray(k)[:, s * Sp + r * c : s * Sp + (r + 1) * c, g]
            np.testing.assert_array_equal(per_dev[d, :, s], expect)
    # per-device KV: S/rep positions vs the replicated layout's S
    local_bytes = per_dev[0].nbytes
    assert local_bytes == B * (S // rep) * H * 4
    assert local_bytes * rep == B * S * H * 4  # rep x reduction


def test_indivisible_heads_raise(devices):
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, k, v = make_qkv(heads=6)
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, k, v, mesh)


def test_llama_sequence_parallel_matches_dense(devices):
    """Full LLaMA (RoPE + GQA) under ulysses SP == no-SP output."""
    from distributed_pytorch_example_tpu.models.llama import Llama

    kw = dict(vocab_size=101, max_len=64, model_dim=32, num_layers=2,
              num_heads=4, num_kv_heads=2, mlp_dim=64)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 101, (2, 64)), jnp.int32
    )
    dense = Llama(**kw)
    sp = Llama(seq_axis="sequence", sp_mode="ulysses", **kw)
    variables = dense.init(jax.random.key(0), tokens)
    expected = dense.apply(variables, tokens)
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    with mesh:
        got = sp.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_llama_gqa_grouped_through_trainer(devices):
    """The grouped GQA path (kv_heads < sequence axis) inside the real
    training graph: custom VJP + shard_map + jit + donated state."""
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.models.llama import Llama

    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    model = Llama(
        vocab_size=64, max_len=64, model_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, mlp_dim=64, seq_axis="sequence",
        sp_mode="ulysses",  # kv=2 < axis 4 -> grouped exchange + ring
    )
    ds = dpx.data.SyntheticTokenDataset(num_samples=16, seq_len=32, vocab_size=64)
    loader = dpx.data.DeviceLoader(ds, 4, mesh=mesh, num_shards=1, shard_id=0)
    trainer = dpx.train.Trainer(
        model, dpx.train.CausalLMTask(), optax.adam(1e-3),
        partitioner=dpx.parallel.data_parallel(mesh),
    )
    # the mesh context is REQUIRED for the SP dispatch to see the axis:
    # without it _ring_mesh raises instead of silently tracing dense
    # attention (the raw train_step is jitted outside Trainer.train_epoch)
    with mesh:
        it = iter(loader)
        trainer.init(next(it)["tokens"])
        state = trainer.state
        losses = []
        for batch in loader:
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
    assert len(losses) >= 3
    assert all(np.isfinite(l) for l in losses)
    with pytest.raises(RuntimeError, match="with mesh"):
        # no mesh context: loud error, not a silent dense fallback
        model.init(jax.random.key(0), jnp.zeros((2, 32), jnp.int32))


def test_gpt2_ulysses_through_trainer(devices):
    """GPT-2 with sp_mode=ulysses trains on a data x sequence mesh."""
    import optax

    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    model = GPT2(vocab_size=64, max_len=32, model_dim=32, num_layers=1,
                 num_heads=4, mlp_dim=64, seq_axis="sequence",
                 sp_mode="ulysses")
    ds = dpx.data.SyntheticTokenDataset(num_samples=16, seq_len=16, vocab_size=64)
    loader = dpx.data.DeviceLoader(ds, 4, mesh=mesh, num_shards=1, shard_id=0)
    trainer = dpx.train.Trainer(
        model, dpx.train.CausalLMTask(), optax.adam(1e-3),
        partitioner=dpx.parallel.data_parallel(mesh),
    )
    with mesh:  # required for SP dispatch (see the llama twin above)
        it = iter(loader)
        trainer.init(next(it)["tokens"])
        _, metrics = trainer.train_step(trainer.state, next(it))
    assert np.isfinite(float(metrics["loss"]))
