"""Stacked decoder: scan-over-layers params, pipelined vs sequential."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_pytorch_example_tpu.models.stacked import StackedDecoder
from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

CFG = dict(
    num_layers=4, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32
)


def _init_and_input(model, seed=0, batch=8, seq=8):
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((batch, seq, 16)),
        jnp.float32,
    )
    params = model.init(jax.random.key(0), x)["params"]
    return params, x


def test_param_shapes_are_layer_stacked(devices):
    model = StackedDecoder(**CFG)
    params, _ = _init_and_input(model)
    assert params["q_kernel"].shape == (4, 16, 16)
    assert params["down_kernel"].shape == (4, 32, 16)
    assert params["ln1_scale"].shape == (4, 16)


def test_stacked_init_std_matches_per_layer(devices):
    """Stacked kernels must init like the per-layer blocks they mirror:
    leading layer (and expert) dims are batch axes, NOT fan-in — otherwise
    init std shrinks by sqrt(L) (sqrt(L*E) for experts) and a pipelined
    model trained from init differs from the sequential reference."""
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    model = StackedDecoder(**CFG, moe_experts=4, moe_top_k=2)
    params, _ = _init_and_input(model)
    expect = 1.0 / np.sqrt(16)  # lecun: sqrt(1/fan_in), fan_in = model_dim
    got = float(np.std(np.asarray(params["q_kernel"])))
    np.testing.assert_allclose(got, expect, rtol=0.2)
    got_e = float(np.std(np.asarray(params["moe_up_kernel"])))
    np.testing.assert_allclose(got_e, expect, rtol=0.2)

    lmodel = StackedLlamaDecoder(**LLAMA_MOE_CFG)
    lp = lmodel.init(
        jax.random.key(0), jnp.zeros((2, 8, 16), jnp.float32)
    )["params"]
    np.testing.assert_allclose(
        float(np.std(np.asarray(lp["moe_gate_kernel"]))), expect, rtol=0.2
    )


def test_pipelined_matches_sequential(devices):
    seq_model = StackedDecoder(**CFG)
    pipe_model = StackedDecoder(**CFG, pipe_axis="pipe")
    params, x = _init_and_input(seq_model)
    expected = seq_model.apply({"params": params}, x)
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    with mesh:
        got = jax.jit(
            lambda p, x: pipe_model.apply({"params": p}, x)
        )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)


def test_pipelined_grads_match_sequential(devices):
    seq_model = StackedDecoder(**CFG)
    pipe_model = StackedDecoder(**CFG, pipe_axis="pipe")
    params, x = _init_and_input(seq_model, seed=1)
    mesh = make_mesh(MeshSpec(data=2, pipe=4))

    def loss_seq(p):
        return jnp.mean(seq_model.apply({"params": p}, x) ** 2)

    def loss_pipe(p):
        return jnp.mean(pipe_model.apply({"params": p}, x) ** 2)

    g_seq = jax.grad(loss_seq)(params)
    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4
        ),
        g_pipe,
        g_seq,
    )


def test_remat_pipelined_matches(devices):
    seq_model = StackedDecoder(**CFG)
    pipe_model = StackedDecoder(**CFG, pipe_axis="pipe", remat=True)
    params, x = _init_and_input(seq_model, seed=2)
    expected = seq_model.apply({"params": params}, x)
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    with mesh:
        got = jax.jit(lambda p, x: pipe_model.apply({"params": p}, x))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)


def test_matches_per_layer_transformer_stack(devices):
    """Stacked block math == TransformerBlock math with copied weights."""
    from distributed_pytorch_example_tpu.models.transformer import (
        TransformerStack,
    )

    ref = TransformerStack(
        num_layers=2, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32,
        causal=True, prenorm=True,
    )
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((2, 8, 16)), jnp.float32
    )
    ref_params = ref.init(jax.random.key(1), x, train=False)["params"]

    # copy per-layer module weights into the stacked layout
    def layer(i, name, leaf):
        return ref_params[f"layer_{i}"][name][leaf]

    stacked_params = {}
    for new, (mod, leaf) in {
        "q_kernel": ("attn/q", "kernel"), "q_bias": ("attn/q", "bias"),
        "k_kernel": ("attn/k", "kernel"), "k_bias": ("attn/k", "bias"),
        "v_kernel": ("attn/v", "kernel"), "v_bias": ("attn/v", "bias"),
        "o_kernel": ("attn/o", "kernel"), "o_bias": ("attn/o", "bias"),
        "up_kernel": ("mlp/up", "kernel"), "up_bias": ("mlp/up", "bias"),
        "down_kernel": ("mlp/down", "kernel"), "down_bias": ("mlp/down", "bias"),
        "ln1_scale": ("ln1", "scale"), "ln1_bias": ("ln1", "bias"),
        "ln2_scale": ("ln2", "scale"), "ln2_bias": ("ln2", "bias"),
    }.items():
        parts = mod.split("/")
        leaves = []
        for i in range(2):
            node = ref_params[f"layer_{i}"]
            for p in parts:
                node = node[p]
            leaves.append(node[leaf])
        stacked_params[new] = jnp.stack(leaves)

    model = StackedDecoder(
        num_layers=2, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32,
        causal=True,
    )
    expected = ref.apply({"params": ref_params}, x, train=False)
    got = model.apply({"params": stacked_params}, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=1e-5
    )


def test_gpt2_pipelined_through_trainer(devices):
    """Tiny pipelined GPT-2 trains end-to-end on a data x pipe mesh."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=2,
        mlp_dim=32, pipe_axis="pipe",
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        # stage stacks must actually live sharded on the pipe axis
        q_sharding = trainer.state.params["decoder"]["q_kernel"].sharding
        assert "pipe" in (q_sharding.spec[0],)
        losses = []
        state = trainer.state
        for _ in range(4):
            batch = next(iter(loader))
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_gpt2_pipe_rejects_conflicting_features(devices):
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=2,
        mlp_dim=32, pipe_axis="pipe", moe_experts=4,
    )
    with pytest.raises(ValueError, match="pipe_axis"):
        model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))


# -- 1F1B schedule at the model level ----------------------------------------


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_1f1b_model_matches_gpipe_schedule(devices, family):
    """Same model under pipe_schedule='1f1b' vs 'gpipe' (4 stages x 8
    microbatches): identical param trees, matching train loss/accuracy and
    matching grads — the GPipe side is itself pinned against sequential,
    so this transitively gives the sequential-equivalence bar."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.models.llama import Llama
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    task = CausalLMTask()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(16, 16)), jnp.int32
    )
    common = dict(
        vocab_size=64, max_len=32, model_dim=32, num_layers=4,
        mlp_dim=64, pipe_axis="pipe", pipe_microbatches=8,
        logits_mode="hidden",
    )
    if family == "gpt2":
        mk = lambda sched: GPT2(
            num_heads=4, pipe_schedule=sched, **common
        )
    else:
        mk = lambda sched: Llama(
            num_heads=4, num_kv_heads=2, pipe_schedule=sched, **common
        )
    m_1f1b, m_gpipe = mk("1f1b"), mk("gpipe")
    with mesh:
        params = m_1f1b.init(jax.random.key(0), tokens, train=False)["params"]
        params_g = m_gpipe.init(
            jax.random.key(0), tokens, train=False
        )["params"]
    # schedules must be checkpoint-compatible: identical param trees
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params, params_g,
    )
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
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)
    np.testing.assert_allclose(
        float(mets1["accuracy"]), float(mets2["accuracy"]), atol=1e-3
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        ),
        g1, g2,
    )


def test_1f1b_through_trainer(devices):
    """1F1B GPT-2 trains end-to-end through the Trainer on a data x pipe
    mesh (4 stages, 8 microbatches) and eval still works (GPipe forward)."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=2,
        mlp_dim=32, pipe_axis="pipe", pipe_schedule="1f1b",
        pipe_microbatches=8, logits_mode="hidden",
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 16, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        q_sharding = trainer.state.params["decoder"]["q_kernel"].sharding
        assert "pipe" in (q_sharding.spec[0],)
        losses = []
        state = trainer.state
        for _ in range(4):
            batch = next(iter(loader))
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        # eval path (train=False) uses the GPipe forward on the same params
        val_loss, val_mets, _ = trainer.task.compute_loss(
            model, state.params, {}, next(iter(loader)), jax.random.key(3),
            train=False,
        )
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
    assert np.isfinite(float(val_loss))


# -- SP x PP composition -----------------------------------------------------


def test_1f1b_composes_with_tensor_parallelism(devices):
    """Megatron TP stays automatic inside the pipe-manual region under
    the 1F1B schedule exactly as under GPipe: a data x pipe x tensor mesh
    trains end-to-end and the loss decreases."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, tensor=2))
    model = GPT2(
        vocab_size=64, max_len=32, model_dim=32, num_layers=2, num_heads=4,
        mlp_dim=64, pipe_axis="pipe", pipe_schedule="1f1b",
        pipe_microbatches=2, logits_mode="hidden",
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        # TP rules actually engaged: q kernels sharded on 'tensor'
        q_sharding = trainer.state.params["decoder"]["q_kernel"].sharding
        assert "tensor" in tuple(q_sharding.spec)
        losses = []
        state = trainer.state
        for _ in range(3):
            state, m = trainer.train_step(state, next(iter(loader)))
            losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_1f1b_seq_axis_moe_rejected(devices):
    """PP x SP x EP stays rejected on the 1F1B schedule (as on GPipe):
    SP alone now composes (test_sp_pp_1f1b_matches_dense_pipelined), but
    aux accumulation over sequence chunks does not."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=2,
        mlp_dim=32, pipe_axis="pipe", pipe_schedule="1f1b",
        seq_axis="sequence", moe_experts=4,
    )
    with pytest.raises(ValueError, match="MoE"):
        model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))


def test_interleaved_requires_1f1b_and_divisible_layers(devices):
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    with pytest.raises(ValueError, match="pipe_virtual"):
        GPT2(
            vocab_size=64, max_len=32, model_dim=16, num_layers=4,
            num_heads=2, mlp_dim=32, pipe_axis="pipe", pipe_virtual=2,
        ).init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=6, num_heads=2,
        mlp_dim=32, pipe_axis="pipe", pipe_schedule="1f1b", pipe_virtual=4,
        pipe_microbatches=4, logits_mode="hidden",
    )
    tokens = jnp.zeros((8, 8), jnp.int32)
    with mesh, pytest.raises(ValueError, match="divisible"):
        jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), tokens, train=True,
                targets=tokens,
            )
        )


def test_1f1b_stash_composes_with_tensor_parallelism(devices):
    """pipe_recompute=False under data x pipe x tensor: the stashed vjp
    residuals are TP-sharded arrays riding through the pipe-manual scan
    carry while Megatron TP stays automatic inside the stage, exactly as
    with the recompute backward — and the two backward modes produce the
    SAME loss trajectory from the same init."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, tensor=2))
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)

    def run(recompute):
        model = GPT2(
            vocab_size=64, max_len=32, model_dim=32, num_layers=2,
            num_heads=4, mlp_dim=64, pipe_axis="pipe", pipe_schedule="1f1b",
            pipe_microbatches=2, pipe_recompute=recompute,
            logits_mode="hidden",
        )
        loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
        trainer = Trainer(
            model, CausalLMTask(), optax.adam(1e-2),
            partitioner=transformer_partitioner(mesh),
        )
        losses = []
        with mesh:
            trainer.init(next(iter(loader))["tokens"])
            q_sharding = trainer.state.params["decoder"]["q_kernel"].sharding
            assert "tensor" in tuple(q_sharding.spec)
            state = trainer.state
            for _ in range(3):
                state, m = trainer.train_step(state, next(iter(loader)))
                losses.append(float(m["loss"]))
        return losses

    l_stash, l_rec = run(False), run(True)
    assert all(np.isfinite(l) for l in l_stash)
    assert l_stash[-1] < l_stash[0], l_stash
    np.testing.assert_allclose(l_stash, l_rec, rtol=1e-5)


@pytest.mark.parametrize("save_recompute", [True, False])
def test_checkpoint_resume_across_pipe_recompute_flip(
    tmp_path, devices, save_recompute
):
    """A checkpoint saved under one 1F1B backward mode resumes under the
    other with the SAME loss trajectory (both flip directions): the vjp
    stash is schedule state inside a single step, never train state, so
    the checkpoint format is mode-independent — the two modes' TrainState
    treedefs are identical."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2), devices=devices[:4])
    dataset = SyntheticTokenDataset(num_samples=64, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 16, mesh=mesh, num_shards=1, shard_id=0)
    batches = [b for _, b in zip(range(4), iter(loader))]

    def make(recompute):
        model = GPT2(
            vocab_size=64, max_len=32, model_dim=32, num_layers=2,
            num_heads=4, mlp_dim=64, pipe_axis="pipe", pipe_schedule="1f1b",
            pipe_microbatches=4, pipe_recompute=recompute,
            logits_mode="hidden",
        )
        trainer = Trainer(
            model, CausalLMTask(), optax.adam(1e-2),
            partitioner=transformer_partitioner(mesh),
        )
        with mesh:
            trainer.init(batches[0]["tokens"])
        return trainer

    t_save, t_flip = make(save_recompute), make(not save_recompute)
    # mode-independent checkpoint format: identical state treedef
    assert jax.tree_util.tree_structure(
        t_save.state
    ) == jax.tree_util.tree_structure(t_flip.state)

    state = t_save.state
    with mesh:
        for b in batches[:2]:
            state, _ = t_save.train_step(state, b)
    path = str(tmp_path / "flip.ckpt")
    save_checkpoint(path, state, epoch=1, loss=0.0)

    def resume(trainer):
        st, epoch, _ = load_checkpoint(path, trainer.state)
        assert epoch == 1
        losses = []
        with mesh:
            for b in batches[2:]:
                st, m = trainer.train_step(st, b)
                losses.append(float(m["loss"]))
        return losses

    l_flip, l_cont = resume(t_flip), resume(t_save)
    np.testing.assert_allclose(l_flip, l_cont, rtol=1e-6)


# -- LLaMA-family stacked decoder (RMSNorm/RoPE/GQA/SwiGLU) -----------------

LLAMA_CFG = dict(
    num_layers=4, num_heads=4, num_kv_heads=2, head_dim=8, model_dim=16,
    mlp_dim=32,
)


def _llama_init_and_input(model, seed=0, batch=8, seq=8):
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((batch, seq, 16)),
        jnp.float32,
    )
    params = model.init(jax.random.key(0), x)["params"]
    return params, x


def test_llama_param_shapes_are_layer_stacked(devices):
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    model = StackedLlamaDecoder(**LLAMA_CFG)
    params, _ = _llama_init_and_input(model)
    assert params["q_kernel"].shape == (4, 16, 32)  # (L, D, heads*hd)
    assert params["k_kernel"].shape == (4, 16, 16)  # GQA: kv_heads*hd
    assert params["gate_kernel"].shape == (4, 16, 32)
    assert params["ln1_scale"].shape == (4, 16)
    assert "q_bias" not in params  # LLaMA family: no biases


def test_llama_pipelined_matches_sequential(devices):
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    seq_model = StackedLlamaDecoder(**LLAMA_CFG)
    pipe_model = StackedLlamaDecoder(**LLAMA_CFG, pipe_axis="pipe")
    params, x = _llama_init_and_input(seq_model)
    expected = seq_model.apply({"params": params}, x)
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    with mesh:
        got = jax.jit(
            lambda p, x: pipe_model.apply({"params": p}, x)
        )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)


def test_llama_pipelined_grads_match_sequential(devices):
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    seq_model = StackedLlamaDecoder(**LLAMA_CFG)
    pipe_model = StackedLlamaDecoder(**LLAMA_CFG, pipe_axis="pipe")
    params, x = _llama_init_and_input(seq_model, seed=1)
    mesh = make_mesh(MeshSpec(data=2, pipe=4))

    def loss_seq(p):
        return jnp.mean(seq_model.apply({"params": p}, x) ** 2)

    def loss_pipe(p):
        return jnp.mean(pipe_model.apply({"params": p}, x) ** 2)

    g_seq = jax.grad(loss_seq)(params)
    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        g_pipe, g_seq,
    )


def test_llama_stacked_matches_per_layer_blocks(devices):
    """Stacked block math == models/llama.py LlamaBlock with copied kernels.

    The per-layer blocks carry (zero-initialized) attention biases the
    true-LLaMA stacked layout omits; at init the math must agree exactly.
    """
    from distributed_pytorch_example_tpu.models.llama import Llama
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    ref = Llama(
        vocab_size=64, max_len=32, model_dim=16, num_layers=2, num_heads=4,
        num_kv_heads=2, mlp_dim=32, logits_mode="hidden",
    )
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, (2, 8)), jnp.int32
    )
    ref_params = ref.init(jax.random.key(2), tokens)["params"]

    stacked_params = {}
    for new, path in {
        "q_kernel": ("attn", "q"), "k_kernel": ("attn", "k"),
        "v_kernel": ("attn", "v"), "o_kernel": ("attn", "o"),
        "gate_kernel": ("mlp", "gate"), "up_kernel": ("mlp", "up"),
        "down_kernel": ("mlp", "down"),
    }.items():
        stacked_params[new] = jnp.stack([
            ref_params[f"layer_{i}"][path[0]][path[1]]["kernel"]
            for i in range(2)
        ])
    for new, mod in {"ln1_scale": "ln1", "ln2_scale": "ln2"}.items():
        stacked_params[new] = jnp.stack([
            ref_params[f"layer_{i}"][mod]["scale"] for i in range(2)
        ])

    x = ref_params["tok_embed"]["embedding"][tokens]
    model = StackedLlamaDecoder(
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=4, model_dim=16,
        mlp_dim=32,
    )
    got = model.apply({"params": stacked_params}, x)

    # reference: run the per-layer blocks only (strip embed + final head)
    from distributed_pytorch_example_tpu.models.llama import LlamaBlock

    expected = x
    for i in range(2):
        block = LlamaBlock(
            num_heads=4, num_kv_heads=2, head_dim=4, model_dim=16,
            mlp_dim=32,
        )
        expected = block.apply(
            {"params": ref_params[f"layer_{i}"]}, expected
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=1e-5
    )


def test_llama_pipelined_through_trainer(devices):
    """Tiny pipelined LLaMA trains end-to-end on a data x pipe mesh."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.llama import Llama
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    model = Llama(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=4,
        num_kv_heads=2, mlp_dim=32, pipe_axis="pipe",
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        q_sharding = trainer.state.params["decoder"]["q_kernel"].sharding
        assert "pipe" in (q_sharding.spec[0],)
        losses = []
        state = trainer.state
        for _ in range(4):
            batch = next(iter(loader))
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_llama_pipe_rejects_conflicting_features(devices):
    """PP x SP is supported since r5; the remaining exclusion is all three
    of PP x SP x EP in one stack."""
    from distributed_pytorch_example_tpu.models.llama import Llama

    model = Llama(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=4,
        num_kv_heads=2, mlp_dim=32, pipe_axis="pipe", seq_axis="sequence",
        moe_experts=4, moe_every=1,
    )
    with pytest.raises(ValueError, match="PP x SP x EP"):
        model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))


# -- MoE inside the layer-stacked decoder (PP x EP) -------------------------

MOE_CFG = dict(
    num_layers=4, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32,
    moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
)


def _moe_apply_collect(model, params, x):
    out, state = model.apply(
        {"params": params}, x, mutable=["losses", "moe_metrics"]
    )
    losses = sum(jax.tree_util.tree_leaves(state["losses"]))
    metric = sum(jax.tree_util.tree_leaves(state.get("moe_metrics", {})))
    return out, losses, metric


def test_moe_stacked_matches_per_layer_blocks(devices):
    """Stacked every-block-MoE math == TransformerStack(moe_every=1) with
    copied weights — outputs AND aux losses."""
    from distributed_pytorch_example_tpu.models.transformer import (
        TransformerStack,
    )

    ref = TransformerStack(
        num_layers=2, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32,
        causal=True, prenorm=True, moe_experts=4, moe_every=1, moe_top_k=2,
        moe_capacity_factor=8.0,
    )
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((2, 8, 16)), jnp.float32
    )
    ref_params = ref.init(jax.random.key(5), x, train=False)["params"]

    stacked_params = {}
    plain = {
        "q_kernel": ("attn", "q", "kernel"), "q_bias": ("attn", "q", "bias"),
        "k_kernel": ("attn", "k", "kernel"), "k_bias": ("attn", "k", "bias"),
        "v_kernel": ("attn", "v", "kernel"), "v_bias": ("attn", "v", "bias"),
        "o_kernel": ("attn", "o", "kernel"), "o_bias": ("attn", "o", "bias"),
        "ln1_scale": ("ln1", "scale"), "ln1_bias": ("ln1", "bias"),
        "ln2_scale": ("ln2", "scale"), "ln2_bias": ("ln2", "bias"),
        "router_kernel": ("moe", "router", "kernel"),
        "router_bias": ("moe", "router", "bias"),
        "moe_up_kernel": ("moe", "up_kernel"),
        "moe_up_bias": ("moe", "up_bias"),
        "moe_down_kernel": ("moe", "down_kernel"),
        "moe_down_bias": ("moe", "down_bias"),
    }
    for new, path in plain.items():
        leaves = []
        for i in range(2):
            node = ref_params[f"layer_{i}"]
            for part in path:
                node = node[part]
            leaves.append(node)
        stacked_params[new] = jnp.stack(leaves)

    model = StackedDecoder(
        num_layers=2, num_heads=2, head_dim=8, model_dim=16, mlp_dim=32,
        causal=True, moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
    )
    got, got_losses, _ = _moe_apply_collect(model, stacked_params, x)
    expected, ref_state = ref.apply(
        {"params": ref_params}, x, train=False,
        mutable=["losses", "moe_metrics"],
    )
    exp_losses = sum(jax.tree_util.tree_leaves(ref_state["losses"]))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=1e-5
    )
    np.testing.assert_allclose(
        float(got_losses), float(exp_losses), rtol=1e-5
    )


def test_moe_pipelined_matches_sequential(devices):
    """PP x EP: pipelined every-block-MoE == the same stacked params run
    sequentially PER MICROBATCH — outputs, aux losses (bubble ticks
    excluded), metric, and gradients.

    Routing statistics (load balancing, capacity drops) are computed per
    microbatch inside the pipeline — a different, equally valid estimator
    than the full-batch statistic (identical to gradient-accumulation
    semantics) — so the sequential reference is microbatched too; the
    main-path outputs are microbatch-invariant and compared full-batch."""
    n_micro = 4
    seq_model = StackedDecoder(**MOE_CFG)
    pipe_model = StackedDecoder(
        **MOE_CFG, pipe_axis="pipe", pipe_microbatches=n_micro
    )
    x = jnp.asarray(
        np.random.default_rng(7).standard_normal((8, 8, 16)), jnp.float32
    )
    params = seq_model.init(jax.random.key(0), x)["params"]
    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))

    def seq_micro(p, xs):
        outs, tot_losses, tot_metric = [], 0.0, 0.0
        for i in range(n_micro):
            xm = xs[i * 2 : (i + 1) * 2]
            out, losses, metric = _moe_apply_collect(seq_model, p, xm)
            outs.append(out)
            tot_losses = tot_losses + losses
            tot_metric = tot_metric + metric
        return (
            jnp.concatenate(outs), tot_losses / n_micro,
            tot_metric / n_micro,
        )

    exp_out, exp_losses, exp_metric = seq_micro(params, x)
    with mesh:
        got_out, got_losses, got_metric = jax.jit(
            lambda p, x: _moe_apply_collect(pipe_model, p, x)
        )(params, x)
    np.testing.assert_allclose(
        np.asarray(got_out), np.asarray(exp_out), atol=2e-5
    )
    np.testing.assert_allclose(
        float(got_losses), float(exp_losses), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(got_metric), float(exp_metric), rtol=1e-5, atol=1e-7
    )

    def loss_seq(p):
        out, losses, _ = seq_micro(p, x)
        return jnp.mean(out ** 2) + losses

    def loss_pipe(p):
        out, losses, _ = _moe_apply_collect(pipe_model, p, x)
        return jnp.mean(out ** 2) + losses

    g_seq = jax.grad(loss_seq)(params)
    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        g_pipe, g_seq,
    )


LLAMA_MOE_CFG = dict(
    num_layers=4, num_heads=4, num_kv_heads=2, head_dim=8, model_dim=16,
    mlp_dim=32, moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
)


def test_llama_moe_stacked_matches_per_layer_blocks(devices):
    """Stacked SwiGLU-expert math == LlamaBlock(moe_experts) with copied
    weights — outputs AND aux losses."""
    from distributed_pytorch_example_tpu.models.llama import LlamaBlock
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    x = jnp.asarray(
        np.random.default_rng(8).standard_normal((2, 8, 16)), jnp.float32
    )
    blocks, ref_params = [], []
    for i in range(2):
        block = LlamaBlock(
            num_heads=4, num_kv_heads=2, head_dim=4, model_dim=16,
            mlp_dim=32, moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
        )
        p = block.init(jax.random.key(10 + i), x)["params"]
        blocks.append(block)
        ref_params.append(p)

    stacked_params = {}
    for new, path in {
        "q_kernel": ("attn", "q", "kernel"), "k_kernel": ("attn", "k", "kernel"),
        "v_kernel": ("attn", "v", "kernel"), "o_kernel": ("attn", "o", "kernel"),
        "ln1_scale": ("ln1", "scale"), "ln2_scale": ("ln2", "scale"),
        "router_kernel": ("moe", "router", "kernel"),
        "router_bias": ("moe", "router", "bias"),
        "moe_gate_kernel": ("moe", "gate_kernel"),
        "moe_up_kernel": ("moe", "up_kernel"),
        "moe_down_kernel": ("moe", "down_kernel"),
    }.items():
        leaves = []
        for p in ref_params:
            node = p
            for part in path:
                node = node[part]
            leaves.append(node)
        stacked_params[new] = jnp.stack(leaves)

    model = StackedLlamaDecoder(
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=4, model_dim=16,
        mlp_dim=32, moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
    )
    got, got_losses, _ = _moe_apply_collect(model, stacked_params, x)

    expected, exp_losses = x, 0.0
    for block, p in zip(blocks, ref_params):
        expected, state = block.apply(
            {"params": p}, expected, mutable=["losses", "moe_metrics"]
        )
        exp_losses = exp_losses + sum(
            jax.tree_util.tree_leaves(state["losses"])
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=1e-5
    )
    np.testing.assert_allclose(float(got_losses), float(exp_losses), rtol=1e-5)


def test_llama_moe_pipelined_matches_sequential(devices):
    """PP x EP for the LLaMA family: pipelined SwiGLU-expert stack == the
    same stacked params run sequentially per microbatch — outputs, aux
    losses, metric, and gradients (microbatched reference for the routing
    statistics, as in the GPT-2 twin above)."""
    n_micro = 4
    from distributed_pytorch_example_tpu.models.stacked import (
        StackedLlamaDecoder,
    )

    seq_model = StackedLlamaDecoder(**LLAMA_MOE_CFG)
    pipe_model = StackedLlamaDecoder(
        **LLAMA_MOE_CFG, pipe_axis="pipe", pipe_microbatches=n_micro
    )
    x = jnp.asarray(
        np.random.default_rng(9).standard_normal((8, 8, 16)), jnp.float32
    )
    params = seq_model.init(jax.random.key(0), x)["params"]
    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))

    def seq_micro(p, xs):
        outs, tot_losses, tot_metric = [], 0.0, 0.0
        for i in range(n_micro):
            xm = xs[i * 2 : (i + 1) * 2]
            out, losses, metric = _moe_apply_collect(seq_model, p, xm)
            outs.append(out)
            tot_losses = tot_losses + losses
            tot_metric = tot_metric + metric
        return (
            jnp.concatenate(outs), tot_losses / n_micro,
            tot_metric / n_micro,
        )

    exp_out, exp_losses, exp_metric = seq_micro(params, x)
    with mesh:
        got_out, got_losses, got_metric = jax.jit(
            lambda p, x: _moe_apply_collect(pipe_model, p, x)
        )(params, x)
    np.testing.assert_allclose(
        np.asarray(got_out), np.asarray(exp_out), atol=2e-5
    )
    np.testing.assert_allclose(float(got_losses), float(exp_losses), rtol=1e-5)
    np.testing.assert_allclose(
        float(got_metric), float(exp_metric), rtol=1e-5, atol=1e-7
    )

    def loss_seq(p):
        out, losses, _ = seq_micro(p, x)
        return jnp.mean(out ** 2) + losses

    def loss_pipe(p):
        out, losses, _ = _moe_apply_collect(pipe_model, p, x)
        return jnp.mean(out ** 2) + losses

    g_seq = jax.grad(loss_seq)(params)
    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        g_pipe, g_seq,
    )


def test_llama_moe_pipelined_through_trainer(devices):
    """PP x EP x DP for the modern-LM family: pipelined SwiGLU-expert
    LLaMA trains end-to-end, expert weights sharded P('pipe','expert')."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.llama import Llama
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))
    model = Llama(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=4,
        num_kv_heads=2, mlp_dim=32, pipe_axis="pipe", moe_experts=4,
        moe_every=1, moe_top_k=2,
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        spec = (
            trainer.state.params["decoder"]["moe_gate_kernel"].sharding.spec
        )
        assert spec[0] == "pipe" and spec[1] == "expert"
        losses = []
        state = trainer.state
        for _ in range(4):
            batch = next(iter(loader))
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        assert "moe_dropped_fraction" in metrics
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_llama_pipe_moe_needs_every_block(devices):
    """moe_every != 1 cannot pipeline (heterogeneous stages) — loud error."""
    from distributed_pytorch_example_tpu.models.llama import Llama

    model = Llama(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=4,
        num_kv_heads=2, mlp_dim=32, pipe_axis="pipe", moe_experts=4,
        moe_every=2,
    )
    with pytest.raises(ValueError, match="moe_every=1"):
        model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))


def test_gpt2_moe_pipelined_through_trainer(devices):
    """PP x EP x DP in one program: pipelined every-block-MoE GPT-2 trains
    end-to-end with expert weights sharded on 'expert' and stage stacks on
    'pipe'; aux losses and the drop metric flow."""
    from distributed_pytorch_example_tpu.data.loader import DeviceLoader
    from distributed_pytorch_example_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.train.loop import Trainer
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    mesh = make_mesh(MeshSpec(data=2, pipe=2, expert=2))
    model = GPT2(
        vocab_size=64, max_len=32, model_dim=16, num_layers=4, num_heads=2,
        mlp_dim=32, pipe_axis="pipe", moe_experts=4, moe_every=1,
        moe_top_k=2,
    )
    dataset = SyntheticTokenDataset(num_samples=32, seq_len=16, vocab_size=64)
    loader = DeviceLoader(dataset, 8, mesh=mesh, num_shards=1, shard_id=0)
    trainer = Trainer(
        model, CausalLMTask(), optax.adam(1e-2),
        partitioner=transformer_partitioner(mesh),
    )
    with mesh:
        trainer.init(next(iter(loader))["tokens"])
        spec = trainer.state.params["decoder"]["moe_up_kernel"].sharding.spec
        assert spec[0] == "pipe" and spec[1] == "expert"
        losses = []
        state = trainer.state
        for _ in range(4):
            batch = next(iter(loader))
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        assert "moe_dropped_fraction" in metrics
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
