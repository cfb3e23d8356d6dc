"""A tiny stand-in for the ``lfm2-8b-a1b`` configuration, for tests on the
CPU: the keys of ``benchmark/configs/lfm2-8b-a1b.json`` at sizes a test can
hold (five layers of all three kinds: convolution + dense MLP, attention +
experts, three of convolution + experts; 4 query heads a key head; 4 of 8
experts held, 2 a token), and the fields that make the program's registry
build the same model."""

import tiny  # noqa: F401  (puts the checkout and benchmark/ on sys.path)

CELL = "lfm2-8b-a1b.train-ep4share-s8192"

LFM2 = {
    "reference": "lfm2_moe",
    "train_argv": ["--model", "lfm2-8b-a1b", "--layers-kept", "0,2,3,4,5",
                   "--experts-held", "0,4", "--vocab-slice", "512",
                   "--dataset", "synthetic-tokens", "--dtype", "float32"],
    "exact_zero": ["train_moe_dropped_assignments"],
    "conv_L_cache": 3, "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512,
    "layers_kept": [0, 2, 3, 4, 5], "experts_first": 0,
    "published": {"num_experts": 8},
    "init": {"std": 0.02, "select_bias_std": 0.1},
    "shape": {"d_model": 64, "heads": 8, "head_dim": 8, "vocab": 512,
              "causal": True, "head_token_share": 1.0,
              "layer_kinds": [
                  {"name": "conv_dense", "count": 1, "attention": False,
                   "matmul_params": 4 * 64 * 64 + 3 * 64 * 128},
                  {"name": "attention_experts", "count": 1, "attention": True,
                   "matmul_params": 2 * 64 * 64 + 2 * 64 * 16 + 64 * 8
                   + 2 * 3 * 64 * 32 * 4 // 8},
                  {"name": "conv_experts", "count": 3, "attention": False,
                   "matmul_params": 4 * 64 * 64 + 64 * 8
                   + 2 * 3 * 64 * 32 * 4 // 8},
              ]},
}
# what the registry's published defaults are replaced by; the share
# (layers kept, experts held, vocabulary slice) comes from ``train_argv``
LFM2_MODEL = dict(model_dim=64, num_heads=8, num_kv_heads=2, mlp_dim=128,
                  moe_mlp_dim=32, num_experts=8, top_k=2)


def shrink_models(monkeypatch):
    """Make ``train.main`` build the tiny model."""
    import distributed_pytorch_example_tpu as dpx

    real = dpx.models.get_model
    monkeypatch.setattr(
        dpx.models, "get_model",
        lambda name, **overrides: real(name, **{**overrides, **LFM2_MODEL}),
    )


def program_model(config=LFM2, **overrides):
    """The program's model of a tiny configuration, float32, by the same
    fields ``train.py`` sets from the configuration's ``train_argv``."""
    import distributed_pytorch_example_tpu as dpx

    fields = dict(
        LFM2_MODEL, vocab_size=config["vocab_size"],
        layers_kept=tuple(config["layers_kept"]),
        experts_first=config["experts_first"],
        experts_held=config["num_experts"],
        num_experts=config["published"]["num_experts"],
        use_expert_bias=config["use_expert_bias"],
    )
    return dpx.models.get_model("lfm2-8b-a1b", **{**fields, **overrides})


def program_params(reference, flat, config=LFM2):
    """The reference's flat weights in the program's tree."""
    import jax

    names = reference.program_names(config)
    return jax.tree_util.tree_map(lambda name: flat[name], names)
