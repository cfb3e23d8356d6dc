"""A tiny stand-in for the ``joyai-llm-flash`` configuration, for tests on
the CPU: the keys of ``benchmark/configs/joyai-llm-flash.json`` at sizes a
test can hold (a dense layer, two expert layers and the prediction module;
4 heads whose queries and keys are 16 + 8 wide and whose values are 16; 4
of 16 routed experts held, 4 a token, one shared), and the fields that make
the program's registry build the same model."""

import tiny  # noqa: F401  (puts the checkout and benchmark/ on sys.path)

CELL = "joyai-llm-flash.train-ep32share-s4096"

# MLA's products at the tiny widths, a token
MLA = 64 * 48 + 48 * 96 + 64 * 40 + 32 * 128 + 64 * 64

JOYAI = {
    "reference": "joyai_llm_flash",
    "train_argv": ["--model", "joyai-llm-flash", "--layers-kept", "0,1,2",
                   "--experts-held", "0,4", "--vocab-slice", "512",
                   "--dataset", "synthetic-tokens", "--dtype", "float32"],
    "exact_zero": ["train_moe_dropped_assignments"],
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_theta": 32000000, "rope_interleave": True, "rope_scaling": None,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1, "vocab_size": 512,
    "layers_kept": [0, 1, 2], "experts_first": 0, "mtp_loss_weight": 0.3,
    "published": {"num_hidden_layers": 40, "n_routed_experts": 16},
    "init": {"std": 0.02, "select_bias_std": 0.02},
    "shape": {"d_model": 64, "heads": 4, "head_dim": 20, "vocab": 512,
              "causal": True, "head_token_share": 2.0,
              "layer_kinds": [
                  {"name": "mla_dense", "count": 1, "attention": True,
                   "matmul_params": MLA + 3 * 64 * 128},
                  {"name": "mla_experts", "count": 2, "attention": True,
                   "matmul_params": MLA + 64 * 16 + 3 * 64 * 32
                   + 4 * 3 * 64 * 32 * 4 // 16},
                  {"name": "mtp_mla_experts", "count": 1, "attention": True,
                   "matmul_params": MLA + 64 * 16 + 3 * 64 * 32
                   + 4 * 3 * 64 * 32 * 4 // 16 + 128 * 64},
              ]},
}
# what the registry's published defaults are replaced by; the share
# (layers kept, experts held, vocabulary slice) comes from ``train_argv``
JOYAI_MODEL = dict(
    model_dim=64, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, mlp_dim=128,
    moe_mlp_dim=32, num_experts=16, top_k=4,
)


def shrink_models(monkeypatch):
    """Make ``train.main`` build the tiny model."""
    import distributed_pytorch_example_tpu as dpx

    real = dpx.models.get_model
    monkeypatch.setattr(
        dpx.models, "get_model",
        lambda name, **overrides: real(name, **{**overrides, **JOYAI_MODEL}),
    )


def program_model(config=JOYAI, **overrides):
    """The program's model of a tiny configuration, float32, by the same
    fields ``train.py`` sets from the configuration's ``train_argv``."""
    import distributed_pytorch_example_tpu as dpx

    fields = dict(
        JOYAI_MODEL, vocab_size=config["vocab_size"],
        layers_kept=tuple(config["layers_kept"]),
        experts_first=config["experts_first"],
        experts_held=config["n_routed_experts"],
        num_experts=config["published"]["n_routed_experts"],
        mtp_layers=config["num_nextn_predict_layers"],
    )
    return dpx.models.get_model("joyai-llm-flash", **{**fields, **overrides})


def program_params(reference, flat, config=JOYAI):
    """The reference's flat weights in the program's tree."""
    import jax

    names = reference.program_names(config)
    return jax.tree_util.tree_map(lambda name: flat[name], names)
