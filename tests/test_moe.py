"""MoE layer: routing math, capacity, aux loss, expert parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_pytorch_example_tpu.models.moe import MoEMlpBlock
from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh


def make_block(**kw):
    defaults = dict(num_experts=4, mlp_dim=64, model_dim=32)
    defaults.update(kw)
    return MoEMlpBlock(**defaults)


def apply_block(block, x, train=False):
    variables = block.init(jax.random.key(0), x, train=False)
    out = block.apply(
        variables, x, train=train, mutable=["losses"] if train else False
    )
    if train:
        return out  # (y, {"losses": ...})
    return out, None


def test_output_shape_and_finite():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 32)), jnp.float32)
    out, _ = apply_block(make_block(), x, train=True)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


def test_aux_loss_emitted_and_bounded():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 64, 32)), jnp.float32)
    block = make_block(aux_loss_weight=1.0, z_loss_weight=0.0)
    variables = block.init(jax.random.key(0), x, train=False)
    _, state = block.apply(variables, x, train=True, mutable=["losses"])
    aux = float(
        np.asarray(state["losses"]["load_balancing"]).reshape(())
    )
    # Switch aux loss is minimized at 1.0 (uniform routing); random init
    # should be close to, and never far below, that bound
    assert 0.9 < aux < 4.0


def test_router_z_loss_emitted():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, 32)), jnp.float32)
    block = make_block(z_loss_weight=1.0)
    variables = block.init(jax.random.key(0), x, train=False)
    _, state = block.apply(variables, x, train=True, mutable=["losses"])
    z = float(np.asarray(state["losses"]["router_z"]).reshape(()))
    assert z > 0  # mean squared logsumexp of real logits is positive


def test_every_surviving_token_routed_once():
    """With generous capacity, output is each token's gated expert output."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 8, 32)), jnp.float32)
    block = make_block(capacity_factor=8.0)  # capacity >= tokens: no drops
    variables = block.init(jax.random.key(0), x, train=False)
    out = block.apply(variables, x, train=False)
    # manual recompute from the router and expert params
    p = variables["params"]
    logits = x @ p["router"]["kernel"] + p["router"]["bias"]
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)[0]  # (S,)
    gate = jnp.max(probs, axis=-1)[0]
    expected = []
    for t in range(8):
        e = int(idx[t])
        h = jax.nn.gelu(x[0, t] @ p["up_kernel"][e] + p["up_bias"][e])
        expected.append(gate[t] * (h @ p["down_kernel"][e] + p["down_bias"][e]))
    np.testing.assert_allclose(
        np.asarray(out[0]), np.stack(expected), atol=1e-5
    )


def test_capacity_drops_pass_through_as_zero():
    """Over-capacity tokens contribute zero from the MoE branch."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 64, 32)), jnp.float32)
    tight = make_block(capacity_factor=0.25)
    variables = tight.init(jax.random.key(0), x, train=False)
    out = tight.apply(variables, x, train=False)
    assert out.shape == x.shape
    # some rows must be exactly zero (dropped tokens)
    row_norms = np.linalg.norm(np.asarray(out[0]), axis=-1)
    assert (row_norms == 0).any()


def test_gradients_flow_to_experts_and_router():
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 16, 32)), jnp.float32)
    block = make_block()
    variables = block.init(jax.random.key(0), x, train=False)

    def loss_fn(params):
        out, state = block.apply(
            {"params": params}, x, train=True, mutable=["losses"]
        )
        aux = sum(jax.tree_util.tree_leaves(state["losses"]))
        return jnp.sum(out**2) + aux

    grads = jax.grad(loss_fn)(variables["params"])
    for name in ("router", "up_kernel", "down_kernel"):
        g = grads[name]
        leaves = jax.tree_util.tree_leaves(g)
        assert any(float(jnp.abs(l).max()) > 0 for l in leaves), name


def test_expert_parallel_matches_single_device(devices):
    """EP-sharded weights under jit == unsharded reference output."""
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    mesh = make_mesh(MeshSpec(data=2, expert=4))
    model = GPT2(vocab_size=101, max_len=32, model_dim=32, num_layers=2,
                 num_heads=4, mlp_dim=64, moe_experts=4, moe_every=2)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 101, (4, 16)), jnp.int32)
    variables = model.init(jax.random.key(0), tokens, train=False)
    expected = model.apply(variables, tokens, train=False)

    part = transformer_partitioner(mesh)
    specs = part.tree_specs(variables)["params"]["decoder"]["layer_1"]["moe"]
    assert specs["up_kernel"] == jax.sharding.PartitionSpec("expert", None, None)
    sharded = jax.device_put(variables, part.tree_shardings(variables))
    out = jax.jit(lambda v, t: model.apply(v, t, train=False))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-4)


def test_moe_gpt2_trains_end_to_end(devices):
    """Full Trainer loop with MoE + aux loss on the fake mesh."""
    import distributed_pytorch_example_tpu as dpx

    mesh = make_mesh(MeshSpec(data=2, expert=4))
    model = dpx.models.get_model(
        "gpt2", vocab_size=64, max_len=32, model_dim=32, num_layers=2,
        num_heads=4, mlp_dim=64, moe_experts=4,
    )
    ds = dpx.data.SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = dpx.data.DeviceLoader(ds, 8, mesh=mesh, num_shards=1, shard_id=0)
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )

    trainer = dpx.train.Trainer(
        model, dpx.train.CausalLMTask(), optax.adam(1e-3),
        partitioner=transformer_partitioner(mesh),
    )
    history = trainer.fit(loader, epochs=1)
    assert np.isfinite(history[-1]["train_loss"])


def test_top2_matches_per_token_recompute():
    """Generous capacity: output == sum of the two gated expert outputs."""
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 8, 32)), jnp.float32)
    block = make_block(top_k=2, capacity_factor=8.0)
    variables = block.init(jax.random.key(0), x, train=False)
    out = block.apply(variables, x, train=False)

    p = variables["params"]
    logits = x @ p["router"]["kernel"] + p["router"]["bias"]
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))[0]  # (S, E)
    expected = []
    for t in range(8):
        top2 = np.argsort(probs[t])[::-1][:2]
        gsum = probs[t][top2].sum()
        acc = np.zeros(32, np.float32)
        for e in top2:
            h = jax.nn.gelu(x[0, t] @ p["up_kernel"][e] + p["up_bias"][e])
            y = h @ p["down_kernel"][e] + p["down_bias"][e]
            acc += (probs[t][e] / gsum) * np.asarray(y)
        expected.append(acc)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.stack(expected), atol=1e-5
    )


def test_top2_first_choices_outrank_second_choices():
    """Under tight capacity, a token's FIRST choice is never displaced by
    an earlier token's SECOND choice (k-major priority)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 64, 32)), jnp.float32)
    block = make_block(top_k=2, capacity_factor=0.5)
    variables = block.init(jax.random.key(0), x, train=False)
    out = block.apply(variables, x, train=False)
    assert np.isfinite(np.asarray(out)).all()

    # recompute slots with numpy: first choices over all tokens first
    p = variables["params"]
    logits = np.asarray(x[0] @ p["router"]["kernel"] + p["router"]["bias"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    order = np.argsort(probs, axis=-1)[:, ::-1][:, :2]  # (S, 2)
    import math

    capacity = max(1, math.ceil(2 * 64 * 0.5 / 4))
    counts = {e: 0 for e in range(4)}
    kept = set()
    for k in range(2):  # k-major: all first choices, then all second
        for t in range(64):
            e = int(order[t, k])
            if counts[e] < capacity:
                counts[e] += 1
                kept.add((t, k))
    # every token with BOTH choices dropped must be an exact-zero row
    zero_rows = {
        t for t in range(64)
        if (t, 0) not in kept and (t, 1) not in kept
    }
    row_norms = np.linalg.norm(np.asarray(out[0]), axis=-1)
    for t in zero_rows:
        assert row_norms[t] == 0.0, t


def test_top2_ep_sharded_matches_single_device(devices):
    """Top-2 routing under the expert-parallel mesh == unsharded output."""
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    mesh = make_mesh(MeshSpec(data=2, expert=4))
    model = GPT2(vocab_size=101, max_len=32, model_dim=32, num_layers=2,
                 num_heads=4, mlp_dim=64, moe_experts=4, moe_top_k=2)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 101, (4, 16)), jnp.int32
    )
    variables = model.init(jax.random.key(0), tokens, train=False)
    expected = model.apply(variables, tokens, train=False)
    part = transformer_partitioner(mesh)
    sharded = jax.device_put(variables, part.tree_shardings(variables))
    out = jax.jit(lambda v, t: model.apply(v, t, train=False))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-4)


def test_invalid_top_k_rejected():
    x = jnp.zeros((1, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="top_k"):
        make_block(top_k=5).init(jax.random.key(0), x, train=False)


def test_dropped_fraction_metric_monotone_in_capacity():
    """Capacity-dropped tokens are observable (VERDICT r2 #7): the sown
    moe_metrics/dropped_fraction shrinks monotonically as capacity_factor
    grows, and vanishes once every (token, choice) pair fits."""
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, 64, 32)), jnp.float32
    )

    def dropped(cf):
        block = make_block(capacity_factor=cf, top_k=2)
        variables = block.init(jax.random.key(0), x, train=False)
        _, state = block.apply(
            variables, x, train=True, mutable=["losses", "moe_metrics"]
        )
        leaves = jax.tree_util.tree_leaves(state["moe_metrics"])
        assert len(leaves) == 1
        return float(leaves[0])

    fracs = [dropped(cf) for cf in (0.25, 0.5, 1.0, 4.0)]
    assert all(0.0 <= f <= 1.0 for f in fracs)
    assert all(a >= b for a, b in zip(fracs, fracs[1:])), fracs
    assert fracs[0] > 0.0  # starved capacity must actually drop
    assert fracs[-1] == pytest.approx(0.0)  # capacity 4x: nothing dropped


def test_dropped_fraction_surfaces_in_train_metrics(devices):
    """The metric reaches the train-step metrics dict via the task layer."""
    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=8))
    model = dpx.models.get_model(
        "gpt2", vocab_size=64, max_len=32, model_dim=32, num_layers=2,
        num_heads=4, mlp_dim=64, moe_experts=4, moe_top_k=2,
        moe_capacity_factor=0.5, use_flash=False,
    )
    trainer = dpx.train.Trainer(
        model, CausalLMTask(), optax.adam(1e-3),
        partitioner=dpx.parallel.data_parallel(mesh),
    )
    tokens = np.random.default_rng(0).integers(0, 64, (8, 16)).astype(np.int32)
    sharding = trainer.partitioner.batch_sharding()
    batch = {"tokens": jax.make_array_from_process_local_data(sharding, tokens)}
    with mesh:
        trainer.init(batch["tokens"])
        _, metrics = trainer.train_step(trainer.state, batch)
    assert "moe_dropped_fraction" in metrics
    frac = float(metrics["moe_dropped_fraction"])
    assert 0.0 <= frac <= 1.0


def test_swiglu_experts_match_per_token_recompute():
    """Mixtral-style SwiGLU experts: output == gated sum of
    silu(x @ gate) * (x @ up + b) @ down per selected expert."""
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((1, 8, 32)), jnp.float32
    )
    block = make_block(top_k=2, capacity_factor=8.0, swiglu=True)
    variables = block.init(jax.random.key(0), x, train=False)
    out = block.apply(variables, x, train=False)

    p = variables["params"]
    assert p["gate_kernel"].shape == (4, 32, 64)
    assert "up_bias" not in p  # SwiGLU experts are bias-free (llama parity)
    logits = x @ p["router"]["kernel"] + p["router"]["bias"]
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))[0]  # (S, E)
    expected = []
    for t in range(8):
        top2 = np.argsort(probs[t])[::-1][:2]
        gsum = probs[t][top2].sum()
        acc = np.zeros(32, np.float32)
        for e in top2:
            up = x[0, t] @ p["up_kernel"][e]  # bias-free: Mixtral parity
            g = jax.nn.silu(x[0, t] @ p["gate_kernel"][e])
            y = (np.asarray(g) * np.asarray(up)) @ p["down_kernel"][e]
            acc += (probs[t][e] / gsum) * np.asarray(y)
        expected.append(acc)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.stack(expected), atol=1e-5
    )


def test_llama_moe_trains_under_expert_mesh(devices):
    """Mixtral-style LLaMA (GQA + RoPE + SwiGLU MoE) trains end-to-end
    with the expert axis spanning devices; aux losses and the
    drop-fraction metric flow through the task layer."""
    import distributed_pytorch_example_tpu as dpx
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=4, expert=2))
    model = dpx.models.get_model(
        "llama", vocab_size=64, max_len=32, model_dim=32, num_layers=2,
        num_heads=4, num_kv_heads=2, mlp_dim=64, moe_experts=4,
        moe_top_k=2, use_flash=False,
    )
    trainer = dpx.train.Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    tokens = np.random.default_rng(0).integers(0, 64, (8, 16)).astype(np.int32)
    sharding = trainer.partitioner.batch_sharding()
    batch = {"tokens": jax.make_array_from_process_local_data(sharding, tokens)}
    with mesh:
        trainer.init(batch["tokens"])
        # expert weights (incl. the SwiGLU gate) must live expert-sharded
        gk = trainer.state.params["layer_1"]["moe"]["gate_kernel"]
        assert gk.sharding.spec[0] == "expert"
        losses = []
        state = trainer.state
        for _ in range(4):
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
    assert "moe_dropped_fraction" in metrics


# slow (PR 22): ~100 s on the CPU mesh; SP x EP stays in tier-1 through
# dryrun config 14 (data x sequence x expert runs a full step)
@pytest.mark.slow
def test_sp_ep_matches_dense_mesh(devices):
    """SP x EP without a pipeline: ring attention over the sequence axis
    + expert-parallel MoE MLPs in one program (the per-layer path — ring
    opens its own manual region, expert sharding stays automatic). Loss
    and grads equal the same model on a sequence-span-1 mesh."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    task = CausalLMTask()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(8, 16)), jnp.int32
    )
    mk = lambda sp: GPT2(
        vocab_size=64, max_len=32, model_dim=32, num_layers=2, num_heads=4,
        mlp_dim=64, seq_axis=sp, sp_mode="ring",
        moe_experts=4, moe_every=1, moe_top_k=2, moe_capacity_factor=8.0,
        logits_mode="hidden",
    )
    mesh_sp = make_mesh(MeshSpec(data=2, sequence=2, expert=2))
    mesh_d = make_mesh(MeshSpec(data=4, expert=2))
    m_sp, m_d = mk("sequence"), mk(None)
    with mesh_sp:
        params = m_sp.init(jax.random.key(0), tokens, train=False)["params"]

    def loss(model, mesh):
        def f(p):
            with mesh:
                l, _, _ = task.compute_loss(
                    model, p, {}, {"tokens": tokens}, jax.random.key(1),
                    train=True,
                )
            return l

        return f

    l_sp, g_sp = jax.value_and_grad(loss(m_sp, mesh_sp))(params)
    l_d, g_d = jax.value_and_grad(loss(m_d, mesh_d))(params)
    np.testing.assert_allclose(float(l_sp), float(l_d), rtol=3e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        ),
        g_sp, g_d,
    )
