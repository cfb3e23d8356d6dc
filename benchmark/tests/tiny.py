"""Tiny stand-ins for the cells, for tests on the CPU: the same files and
code paths as a run on the chip, at sizes a test can hold. The program's
registry builds GPT-2 and BERT only at their published sizes, so the tests
(and only they) wrap ``get_model`` with the tiny sizes."""

import argparse
import json
import os
import sys

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS_DIR)
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

GPT2 = {
    "reference": "gpt2",
    "train_argv": ["--model", "gpt2", "--dataset", "synthetic-tokens",
                   "--dtype", "float32"],
    "initializer_range": 0.02, "layer_norm_epsilon": 1e-5, "n_embd": 64,
    "n_head": 2, "n_inner": None, "n_layer": 2, "n_positions": 64,
    "vocab_size": 512,
    "shape": {"layers": 2, "d_model": 64, "d_ff": 256, "heads": 2,
              "head_dim": 32, "vocab": 512, "causal": True,
              "head_token_share": 1.0},
}
GPT2_MODEL = dict(vocab_size=512, max_len=64, model_dim=64, num_layers=2,
                  num_heads=2, mlp_dim=256)

BERT = {
    "reference": "bert",
    "train_argv": ["--model", "bert-base", "--dataset", "synthetic-tokens",
                   "--dtype", "float32"],
    "initializer_range": 0.02, "layer_norm_eps": 1e-12, "hidden_size": 64,
    "num_attention_heads": 2, "intermediate_size": 256,
    "num_hidden_layers": 2, "max_position_embeddings": 64, "vocab_size": 512,
    "shape": {"layers": 2, "d_model": 64, "d_ff": 256, "heads": 2,
              "head_dim": 32, "vocab": 512, "causal": False,
              "head_token_share": 0.15, "head_extra_matmul_params": 4096},
}
BERT_MODEL = GPT2_MODEL


def bench():
    """The committed BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def traffic(chips=1, rows_per_chip=4):
    return {
        "driver": "train_window", "chips": chips,
        "rows_per_chip": rows_per_chip, "seq_len": 64, "dataset_rows": 256,
        "adam": {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-8},
        "train_argv": ["--mesh-data", "-1"] if chips > 1 else [],
        "reference_block_rows": 2, "trace_seconds": 0.5,
    }


def shrink_models(monkeypatch):
    """Make ``train.main`` build the tiny models."""
    import distributed_pytorch_example_tpu as dpx

    real = dpx.models.get_model

    def tiny(name, **overrides):
        sizes = GPT2_MODEL if name.startswith("gpt") else BERT_MODEL
        return real(name, **{**overrides, **sizes})

    monkeypatch.setattr(dpx.models, "get_model", tiny)


def cell(name, chips=1):
    return {"name": name, "config": "tiny", "traffic": "tiny", "chips": chips,
            "why": "test"}


def args(seed=5, seconds=0.5, trace=0):
    return argparse.Namespace(
        workload="tiny", seed=seed, seconds=seconds, trace=trace,
        keep_trace=0,
    )
