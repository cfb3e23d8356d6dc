"""Fused paged flash-decode kernel vs the XLA gather reference.

The kernel (ops/pallas/paged_attention.py) scalar-prefetches the block
table and reads only live KV blocks from the pool; the reference gathers
the whole table and runs dense attention — the exact pre-kernel decode
path. These tests pin the two together (interpret mode stands in for the
TPU lowering, the flash_attention.py convention), check the dispatcher's
off-TPU fallback is the reference BITWISE, and run the kernel under a
tensor=2 shard_map over kv heads — the sharding the serving engine's
page pool uses.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.ops.pallas.paged_attention import (
    paged_attention_reference,
    paged_decode_attention,
    paged_decode_supported,
    paged_flash_decode,
)

BLOCK_SIZE = 4


def make_case(
    batch=3, num_heads=4, kv_heads=4, head_dim=16, num_blocks=16,
    max_blocks=5, seed=0,
):
    """Random pool + a permuted block table with dead tails -> scratch 0.

    Row lengths straddle block boundaries (first/last position of a
    block, single-block rows) so the mask and the live-block sweep are
    both exercised off the easy aligned cases.
    """
    rng = np.random.default_rng(seed)
    q = jnp.asarray(
        rng.standard_normal((batch, num_heads, head_dim)), jnp.float32
    )
    pages_k = jnp.asarray(
        rng.standard_normal((num_blocks, BLOCK_SIZE, kv_heads, head_dim)),
        jnp.float32,
    )
    pages_v = jnp.asarray(
        rng.standard_normal((num_blocks, BLOCK_SIZE, kv_heads, head_dim)),
        jnp.float32,
    )
    # non-identity placement: each row's live blocks are scattered through
    # the pool (block 0 is the scratch block dead entries point at)
    perm = rng.permutation(np.arange(1, num_blocks))
    lens = np.asarray([2, BLOCK_SIZE - 1, 4 * BLOCK_SIZE], np.int32)[:batch]
    table = np.zeros((batch, max_blocks), np.int32)
    k = 0
    for b in range(batch):
        live = int(lens[b]) // BLOCK_SIZE + 1
        for j in range(min(live, max_blocks)):
            table[b, j] = perm[k]
            k += 1
    return q, pages_k, pages_v, jnp.asarray(table), jnp.asarray(lens)


@pytest.mark.parametrize(
    "num_heads,kv_heads", [(4, 4), (4, 2)], ids=["mha", "gqa"]
)
def test_kernel_matches_reference_interpret(num_heads, kv_heads):
    """Online-softmax kernel == dense gather reference at tolerance."""
    q, pk, pv, table, lens = make_case(
        num_heads=num_heads, kv_heads=kv_heads
    )
    ref = paged_attention_reference(
        q[:, None], pk, pv, table, lens[:, None]
    )[:, 0]
    got = paged_flash_decode(q, pk, pv, table, lens, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5
    )


def test_kernel_ignores_garbage_in_dead_blocks():
    """Dead table entries point at the scratch block; poisoning it (and
    every block past a row's length) must not move the output — the
    live-block skip plus the position mask make dead KV unreachable."""
    q, pk, pv, table, lens = make_case()
    base = paged_flash_decode(q, pk, pv, table, lens, interpret=True)
    poisoned_k = pk.at[0].set(1e4)
    poisoned_v = pv.at[0].set(1e4)
    got = paged_flash_decode(
        q, poisoned_k, poisoned_v, table, lens, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_dispatcher_fallback_is_reference_bitwise():
    """Off-TPU with no interpret override the dispatcher must return the
    gather reference EXACTLY — this is the bit-exactness gate that keeps
    every token-equivalence test meaningful on the fake CPU mesh."""
    assert not paged_decode_supported()  # CPU backend under conftest
    q, pk, pv, table, lens = make_case(seed=1)
    ref = paged_attention_reference(q[:, None], pk, pv, table, lens[:, None])
    got = paged_decode_attention(q[:, None], pk, pv, table, lens[:, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_dispatcher_interpret_forces_kernel():
    """``interpret=True`` drives the fused path off-TPU through the
    dispatcher; output stays at-tolerance vs the reference route."""
    q, pk, pv, table, lens = make_case(seed=2)
    ref = paged_decode_attention(q[:, None], pk, pv, table, lens[:, None])
    got = paged_decode_attention(
        q[:, None], pk, pv, table, lens[:, None], interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5
    )


def test_verify_chunk_takes_reference_path():
    """seq > 1 (the speculative verify window) always dispatches to the
    reference, kernel forced or not — per-position causal masking over a
    window is the reference's job."""
    q, pk, pv, table, lens = make_case(seed=3)
    qw = jnp.stack([q, q * 0.5], axis=1)  # (batch, 2, heads, head_dim)
    pos = jnp.stack([lens, lens + 1], axis=1)
    ref = paged_attention_reference(qw, pk, pv, table, pos)
    got = paged_decode_attention(qw, pk, pv, table, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_kernel_tensor2_sharded_kv_heads(devices):
    """The kernel under shard_map with kv heads split over tensor=2 (the
    engine's pool sharding) matches the unsharded reference — the grid
    never indexes across the head shard, so each shard runs a standalone
    kernel over its local heads."""
    import functools

    from jax.sharding import PartitionSpec as P

    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

    q, pk, pv, table, lens = make_case(
        num_heads=4, kv_heads=2, head_dim=16, seed=4
    )
    ref = paged_attention_reference(
        q[:, None], pk, pv, table, lens[:, None]
    )[:, 0]
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    sharded = jax.shard_map(
        functools.partial(paged_flash_decode, interpret=True),
        mesh=mesh,
        in_specs=(
            P(None, "tensor", None),  # q: heads (group-aligned) split
            P(None, None, "tensor", None),  # pages_k: kv heads split
            P(None, None, "tensor", None),
            P(None, None),  # table replicated
            P(None,),  # lens replicated
        ),
        out_specs=P(None, "tensor", None),
        # the pallas HLO interpreter does not propagate varying manual
        # axes (test_ring_attention.py convention); TPU runs fully checked
        check_vma=False,
    )
    got = sharded(q, pk, pv, table, lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5
    )
