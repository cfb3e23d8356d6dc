"""Stacked decoders under the 1F1B schedules with MoE / interleaved chunks.

Split out of test_stacked.py (PR 22) for the same reason as
test_stacked_sp.py: one file is one xdist worker's job under
``--dist loadfile``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh


# slow (PR 22): ~105 s a case on the CPU mesh; 1F1B x MoE stays in tier-1
# through test_interleaved_1f1b_moe_matches_plain and dryrun config 10
@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_1f1b_moe_matches_gpipe_schedule(devices, family):
    """PP x EP under 1F1B: aux-loss gradients are seeded inside the
    schedule with the model's weights; total loss and grads equal the
    GPipe schedule's (whose MoE path is pinned against sequential)."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.models.llama import Llama
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))
    task = CausalLMTask()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(8, 16)), jnp.int32
    )
    common = dict(
        vocab_size=64, max_len=32, model_dim=32, num_layers=2, mlp_dim=64,
        pipe_axis="pipe", pipe_microbatches=4, logits_mode="hidden",
        moe_experts=4, moe_every=1, moe_top_k=2,
        # big capacity: no dropped tokens, so schedules are exactly
        # comparable (drops are order-dependent at the margin)
        moe_capacity_factor=8.0,
    )
    if family == "gpt2":
        mk = lambda sched: GPT2(num_heads=4, pipe_schedule=sched, **common)
    else:
        mk = lambda sched: Llama(
            num_heads=4, num_kv_heads=2, pipe_schedule=sched, **common
        )
    m_1f1b, m_gpipe = mk("1f1b"), mk("gpipe")
    with mesh:
        params = m_1f1b.init(jax.random.key(0), tokens, train=False)["params"]
    rng = jax.random.key(1)

    def loss_fn(model):
        def f(p):
            with mesh:
                loss, mets, _ = task.compute_loss(
                    model, p, {}, {"tokens": tokens}, rng, train=True
                )
            return loss, mets

        return f

    (l1, mets1), g1 = jax.value_and_grad(
        loss_fn(m_1f1b), has_aux=True
    )(params)
    (l2, mets2), g2 = jax.value_and_grad(
        loss_fn(m_gpipe), has_aux=True
    )(params)
    # total loss includes the weighted aux values on both schedules
    np.testing.assert_allclose(float(l1), float(l2), rtol=3e-5)
    assert "moe_dropped_fraction" in mets1 and "moe_dropped_fraction" in mets2
    np.testing.assert_allclose(
        float(mets1["moe_dropped_fraction"]),
        float(mets2["moe_dropped_fraction"]), atol=1e-6,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=7e-4
        ),
        g1, g2,
    )


def test_interleaved_1f1b_moe_matches_plain(devices):
    """PP x EP under INTERLEAVED 1F1B (pipe_virtual=2): the per-cycle aux
    accumulation and in-schedule aux-gradient seeding behave identically
    under the virtual-chunk layout — loss (incl. weighted aux) and grads
    equal the plain 1F1B MoE (itself pinned against GPipe -> sequential)."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))
    task = CausalLMTask()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(8, 16)), jnp.int32
    )
    mk = lambda v: GPT2(
        vocab_size=64, max_len=32, model_dim=32, num_layers=4, num_heads=4,
        mlp_dim=64, pipe_axis="pipe", pipe_schedule="1f1b",
        pipe_microbatches=4, pipe_virtual=v, logits_mode="hidden",
        moe_experts=4, moe_every=1, moe_top_k=2, moe_capacity_factor=8.0,
    )
    m_il, m_pl = mk(2), mk(1)
    with mesh:
        params = m_il.init(jax.random.key(0), tokens, train=False)["params"]
    rng = jax.random.key(1)

    def loss_fn(model):
        def f(p):
            with mesh:
                loss, mets, _ = task.compute_loss(
                    model, p, {}, {"tokens": tokens}, rng, train=True
                )
            return loss, mets

        return f

    (l1, mets1), g1 = jax.value_and_grad(loss_fn(m_il), has_aux=True)(params)
    (l2, mets2), g2 = jax.value_and_grad(loss_fn(m_pl), has_aux=True)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=3e-5)
    np.testing.assert_allclose(
        float(mets1["moe_dropped_fraction"]),
        float(mets2["moe_dropped_fraction"]), atol=1e-6,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=7e-4
        ),
        g1, g2,
    )


def test_interleaved_1f1b_matches_plain_1f1b(devices):
    """pipe_virtual=2 (Megatron-style interleaved chunks: device d holds
    layer chunks {d, d+S}) vs pipe_virtual=1 on the same GPT-2: identical
    flax param tree (the interleaved layout is internal to the runner),
    matching loss/accuracy and grads. 12 layers / (2 stages x 2 chunks)
    = 3 LAYERS PER CHUNK — the multi-layer-chunk shape class (a CLI drive
    caught the Lc>1 reshape leaking into the GPipe eval path; this pins
    both the 1F1B layout and the contiguous eval split)."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=4, pipe=2))
    task = CausalLMTask()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(16, 16)), jnp.int32
    )
    mk = lambda v: GPT2(
        vocab_size=64, max_len=32, model_dim=32, num_layers=12, num_heads=4,
        mlp_dim=64, pipe_axis="pipe", pipe_schedule="1f1b",
        pipe_microbatches=4, pipe_virtual=v, logits_mode="hidden",
    )
    m_il, m_plain = mk(2), mk(1)
    with mesh:
        params = m_il.init(jax.random.key(0), tokens, train=False)["params"]
    rng = jax.random.key(1)

    def loss(model):
        def f(p):
            with mesh:
                l, mets, _ = task.compute_loss(
                    model, p, {}, {"tokens": tokens}, rng, train=True
                )
            return l, mets

        return f

    (l_il, mets_il), g_il = jax.value_and_grad(
        loss(m_il), has_aux=True
    )(params)
    (l_pl, mets_pl), g_pl = jax.value_and_grad(
        loss(m_plain), has_aux=True
    )(params)
    np.testing.assert_allclose(float(l_il), float(l_pl), rtol=2e-5)
    np.testing.assert_allclose(
        float(mets_il["accuracy"]), float(mets_pl["accuracy"]), atol=1e-3
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        ),
        g_il, g_pl,
    )
