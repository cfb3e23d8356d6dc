"""Chunked vocab-blockwise cross-entropy vs the dense reference path.

Pins the fused LM loss (ops/chunked_ce.py) to the semantics of the dense
``tied_head_logits -> optax.softmax_cross_entropy_with_integer_labels``
pipeline it replaces (the reference's ``nn.CrossEntropyLoss``, reference
train.py:250): values, argmax, and gradients w.r.t. hidden states,
embedding, and bias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_pytorch_example_tpu.ops.chunked_ce import chunked_softmax_xent


def _dense(hidden, embedding, targets, bias=None, dtype=jnp.bfloat16):
    logits = jax.lax.dot_general(
        hidden.astype(dtype), embedding.astype(dtype),
        (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return loss, jnp.argmax(logits, axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("vocab,block", [(1000, 256), (1000, 1000), (777, 128)])
@pytest.mark.parametrize("bias", [False, True])
def test_matches_dense(vocab, block, bias):
    k = jax.random.PRNGKey(0)
    kx, ke, kt, kb = jax.random.split(k, 4)
    hidden = jax.random.normal(kx, (4, 9, 32), jnp.float32)
    embedding = jax.random.normal(ke, (vocab, 32), jnp.float32) * 0.1
    targets = jax.random.randint(kt, (4, 9), 0, vocab)
    b = jax.random.normal(kb, (vocab,)) * 0.1 if bias else None

    ref_loss, ref_argmax = _dense(hidden, embedding, targets, b)
    loss, argmax = chunked_softmax_xent(
        hidden, embedding, targets, bias=b, block_size=block
    )
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(argmax, ref_argmax)


@pytest.mark.parametrize("bias", [False, True])
def test_grads_match_dense(bias):
    vocab, dim = 500, 16
    k = jax.random.PRNGKey(1)
    kx, ke, kt, kb = jax.random.split(k, 4)
    hidden = jax.random.normal(kx, (3, 7, dim), jnp.float32)
    embedding = jax.random.normal(ke, (vocab, dim)) * 0.1
    targets = jax.random.randint(kt, (3, 7), 0, vocab)
    b = jax.random.normal(kb, (vocab,)) * 0.1 if bias else None

    def loss_chunked(h, e, bb):
        loss, _ = chunked_softmax_xent(
            h, e, targets, bias=bb, block_size=128
        )
        return loss.mean()

    def loss_dense(h, e, bb):
        loss, _ = _dense(h, e, targets, bb)
        return loss.mean()

    args = (hidden, embedding, b) if bias else (hidden, embedding, None)
    argnums = (0, 1, 2) if bias else (0, 1)
    g_chunk = jax.grad(loss_chunked, argnums=argnums)(*args)
    g_dense = jax.grad(loss_dense, argnums=argnums)(*args)
    for gc, gd in zip(g_chunk, g_dense):
        # both sides do bf16 matmuls; backward orders differ slightly
        np.testing.assert_allclose(gc, gd, rtol=6e-3, atol=6e-5)


def test_bf16_hidden_states():
    """bf16 hidden states (the model's compute dtype) round-trip cleanly."""
    vocab, dim = 300, 24
    k = jax.random.PRNGKey(2)
    kx, ke, kt = jax.random.split(k, 3)
    hidden = jax.random.normal(kx, (2, 5, dim), jnp.bfloat16)
    embedding = jax.random.normal(ke, (vocab, dim)) * 0.1
    targets = jax.random.randint(kt, (2, 5), 0, vocab)
    ref_loss, ref_argmax = _dense(hidden, embedding, targets)
    loss, argmax = chunked_softmax_xent(
        hidden, embedding, targets, block_size=128
    )
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(argmax, ref_argmax)

    def f(h, e):
        l, _ = chunked_softmax_xent(h, e, targets, block_size=128)
        return l.mean()

    gh, ge = jax.grad(f, argnums=(0, 1))(hidden, embedding)
    assert gh.dtype == jnp.bfloat16 and ge.dtype == embedding.dtype


def test_argmax_tie_breaks_first():
    """Duplicate embedding rows: argmax picks the lowest id, like dense."""
    dim = 8
    emb_row = jnp.ones((1, dim))
    embedding = jnp.concatenate([emb_row] * 6, axis=0)  # all identical
    hidden = jnp.ones((1, 1, dim))
    targets = jnp.zeros((1, 1), jnp.int32)
    _, argmax = chunked_softmax_xent(
        hidden, embedding, targets, block_size=2
    )
    assert int(argmax[0, 0]) == 0


def test_shape_validation():
    hidden = jnp.zeros((2, 3, 8))
    embedding = jnp.zeros((10, 9))
    targets = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="hidden dim"):
        chunked_softmax_xent(hidden, embedding, targets)
    with pytest.raises(ValueError, match="targets shape"):
        chunked_softmax_xent(
            jnp.zeros((2, 3, 9)), embedding, jnp.zeros((2, 4), jnp.int32)
        )


def test_serialized_long_context_path_matches(monkeypatch):
    """The memory-bound serialization path (optimization_barrier threading
    + block shrink, engaged above _SERIALIZE_TOTAL_BYTES) is numerically
    identical to the free-scheduling path: loss, argmax, and grads match
    with the thresholds forced to zero."""
    from distributed_pytorch_example_tpu.ops import chunked_ce as cc

    rng = np.random.default_rng(0)
    n, d, v = 64, 32, 517
    hidden = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    emb = jnp.asarray(rng.standard_normal((v, d)) * 0.1, jnp.float32)
    tg = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)

    def f(h, e):
        loss, am = cc.chunked_softmax_xent(
            h, e, tg, block_size=128, dtype=jnp.float32
        )
        return loss.sum(), am

    (l0, am0), g0 = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
        hidden, emb
    )
    monkeypatch.setattr(cc, "_SERIALIZE_TOTAL_BYTES", 0)
    monkeypatch.setattr(cc, "_SERIALIZE_BLOCK_BYTES", 0)
    (l1, am1), g1 = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
        hidden, emb
    )
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(am0), np.asarray(am1))
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
        )


def test_local_token_count_committed_sharding(mesh_2x2x2):
    """The HBM guard sizes tokens from the operand's COMMITTED sharding
    when one is available (ADVICE r5): a batch-sharded placement counts
    one shard, a replicated placement counts every token."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_pytorch_example_tpu.ops import chunked_ce as cc

    sharded = jax.device_put(
        jnp.zeros((8, 16, 8), jnp.float32),
        NamedSharding(mesh_2x2x2, P(("data", "fsdp"))),
    )
    assert cc._local_token_count(sharded, 128) == 32  # 4-way batch shard
    replicated = jax.device_put(
        jnp.zeros((8, 16, 8), jnp.float32),
        NamedSharding(mesh_2x2x2, P()),
    )
    assert cc._local_token_count(replicated, 128) == 128


def test_serialize_guard_engages_for_replicated_batch(monkeypatch, mesh_2x2x2):
    """ADVICE r5 regression: a replicated-layout trace under an ACTIVE
    multi-chip mesh must not divide the token count by the mesh span —
    the old ``n // data_parallel_size(mesh)`` guess disengaged the HBM
    guard exactly where all ``n`` tokens are chip-resident. With the
    layout unknown at trace time the guard now assumes the full ``n``
    and threads its optimization barriers."""
    from distributed_pytorch_example_tpu.analysis.shardlint import iter_eqns
    from distributed_pytorch_example_tpu.ops import chunked_ce as cc

    n, d, v = 64, 8, 64
    # global all-blocks f32 logits: 64 * 64 * 4 = 16384 bytes. Threshold
    # between that and the old mesh-span estimate (16384 / dp4 = 4096):
    # the fixed guard serializes, the old guess would not.
    monkeypatch.setattr(cc, "_SERIALIZE_TOTAL_BYTES", 8192)
    hidden = jnp.zeros((4, 16, d), jnp.float32)
    emb = jnp.zeros((v, d), jnp.float32)
    tg = jnp.zeros((4, 16), jnp.int32)
    with mesh_2x2x2:
        jaxpr = jax.make_jaxpr(
            lambda h, e, t: cc.chunked_softmax_xent(
                h, e, t, block_size=32, dtype=jnp.float32
            )
        )(hidden, emb, tg)
    barriers = [
        e for e in iter_eqns(jaxpr)
        if e.primitive.name == "optimization_barrier"
    ]
    assert barriers, "guard must engage when the layout is unknown"


# --- the one-pass forward (PR 32): one reduction a block whose combiner is
# the online softmax's. Rows of ``hidden`` are unit vectors and the products
# run in float32, so the embedding's columns ARE the logits, to the bit.

_V, _BLOCK = 300, 128  # blocks of 128, 128 and a narrow last one of 44


def _planted(case):
    """(logits (n, V), bias or None, targets) of one named case."""
    rng = np.random.default_rng(7)
    n, vocab = 6, _V
    bias = None
    if case == "small_vocab":
        vocab = 50  # narrower than one block
    logits = rng.uniform(-80.0, 70.0, (n, vocab)).astype(np.float32)
    targets = rng.integers(0, vocab, n)
    if case == "spread80":
        # the row's max in the first, a middle and the narrow last block
        for row, col in enumerate([5, 200, 290, 0, 127, 299]):
            logits[row, col] = 80.0
    elif case == "neg_inf_bias_columns":
        bias = np.zeros(vocab, np.float32)
        bias[[0, 3, 130, 255, 256, 299]] = -np.inf
        targets = np.array([1, 2, 129, 131, 257, 298])
    elif case == "neg_inf_bias_block":
        bias = rng.uniform(-1.0, 1.0, vocab).astype(np.float32)
        bias[_BLOCK:2 * _BLOCK] = -np.inf  # the whole second block
        targets = np.array([0, 127, 256, 299, 5, 270])
    elif case == "tie_across_blocks":
        for row, cols in enumerate([(10, 140), (127, 128), (100, 299),
                                    (0, 256), (255, 256), (3, 130, 290)]):
            logits[row, list(cols)] = 75.0
    elif case == "tie_within_block":
        for row, cols in enumerate([(130, 200), (0, 1), (256, 299),
                                    (126, 127), (128, 255), (257, 258, 259)]):
            logits[row, list(cols)] = 75.0
    return logits, bias, targets


@pytest.fixture
def serial_forced(request, monkeypatch):
    from distributed_pytorch_example_tpu.ops import chunked_ce as cc

    if request.param:
        monkeypatch.setattr(cc, "_SERIALIZE_TOTAL_BYTES", 0)
    return request.param


@pytest.mark.parametrize("serial_forced", [False, True], indirect=True,
                         ids=["free", "serial"])
@pytest.mark.parametrize("case", [
    "spread80", "neg_inf_bias_columns", "neg_inf_bias_block",
    "tie_across_blocks", "tie_within_block", "small_vocab",
])
def test_one_pass_forward_matches_dense(case, serial_forced):
    """Loss, argmax and gradients against the dense float32 loss and
    ``jnp.argmax``: logits over +-80, ``-inf`` columns and a whole ``-inf``
    block, equal maxima (the first index wins, within a block and across
    blocks), a vocabulary under one block; each on both block schedules."""
    logits, bias, targets = _planted(case)
    n = logits.shape[0]
    hidden = jnp.eye(n, dtype=jnp.float32)
    embedding = jnp.asarray(logits.T)
    targets = jnp.asarray(targets, jnp.int32)
    b = None if bias is None else jnp.asarray(bias)

    def chunked(h, e, bb):
        loss, argmax = chunked_softmax_xent(
            h, e, targets, bias=bb, block_size=_BLOCK, dtype=jnp.float32
        )
        return loss.mean(), (loss, argmax)

    def dense(h, e, bb):
        loss, argmax = _dense(h, e, targets, bb, dtype=jnp.float32)
        return loss.mean(), (loss, argmax)

    argnums = (0, 1) if b is None else (0, 1, 2)
    (_, (loss, argmax)), grads = jax.value_and_grad(
        chunked, argnums=argnums, has_aux=True)(hidden, embedding, b)
    (_, (ref_loss, ref_argmax)), ref_grads = jax.value_and_grad(
        dense, argnums=argnums, has_aux=True)(hidden, embedding, b)
    assert np.isfinite(np.asarray(loss)).all()
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-6, atol=2e-5)
    np.testing.assert_array_equal(argmax, ref_argmax)
    for got, want in zip(grads, ref_grads):
        assert np.isfinite(np.asarray(got)).all()
        # the backward sums +-80 x p over the vocabulary in another order
        scale = float(np.abs(np.asarray(want)).max())
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6 * scale)


@pytest.mark.parametrize("serial_forced", [False, True], indirect=True,
                         ids=["free", "serial"])
@pytest.mark.parametrize("planted", [np.nan, np.inf, -np.inf])
def test_non_finite_hidden_reaches_the_loss(planted, serial_forced):
    """A NaN or an inf in ``x`` gives a non-finite loss for that row (the
    step's sentinels and the bad-step predication read it) and leaves the
    other rows' losses finite and as they were."""
    rng = np.random.default_rng(3)
    vocab, dim = 517, 16
    hidden = rng.standard_normal((2, 4, dim)).astype(np.float32)
    embedding = jnp.asarray(rng.standard_normal((vocab, dim)) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.integers(0, vocab, (2, 4)), jnp.int32)
    clean, _ = chunked_softmax_xent(
        jnp.asarray(hidden), embedding, targets, block_size=128
    )
    hidden[0, 2, 3] = planted
    hidden[1, 1, 0] = planted
    loss, _ = chunked_softmax_xent(
        jnp.asarray(hidden), embedding, targets, block_size=128
    )
    bad = np.zeros((2, 4), bool)
    bad[0, 2] = bad[1, 1] = True
    loss, clean = np.asarray(loss), np.asarray(clean)
    assert not np.isfinite(loss[bad]).any()
    np.testing.assert_array_equal(loss[~bad], clean[~bad])


@pytest.mark.parametrize("serial_forced", [False, True], indirect=True,
                         ids=["free", "serial"])
@pytest.mark.parametrize("vocab,block", [(_V, _BLOCK), (50, _BLOCK), (512, 64)])
def test_forward_holds_one_reduction_per_block(vocab, block, serial_forced):
    """The lowered forward reads each block of logits in ONE reduction (max,
    sum-exp, argmax and target logit together); the two-pass body had four."""
    hidden = jnp.zeros((4, 8), jnp.float32)
    embedding = jnp.zeros((vocab, 8), jnp.float32)
    targets = jnp.zeros((4,), jnp.int32)
    text = jax.jit(
        lambda h, e, t: chunked_softmax_xent(h, e, t, block_size=block)
    ).lower(hidden, embedding, targets).as_text()
    n_blocks = -(-vocab // block)
    assert text.count("stablehlo.reduce") == n_blocks
    assert text.count("stablehlo.dot_general") == n_blocks
    barriers = text.count("stablehlo.optimization_barrier")
    assert barriers == (n_blocks - 1 if serial_forced else 0)
