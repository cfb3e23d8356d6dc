"""The benchmark's own tests that every ``model_config`` PR stands on, in
tier-1, and the checks a new cell needs before its first run on the chip.

``benchmark/tests/`` is run by hand (``python -m pytest benchmark/tests``):
its conftest asks for four virtual devices and puts ``benchmark/`` first on
``sys.path``, which this suite's process must not take over. So the
selections run as they are, each in a process of its own: the operation
counts (``test_flops.py``), the reference's three steps and its memory shape
(``test_reference_steps.py``), the counts of the program's that are held to
zero (``exact_zero``: ``test_run.py -k held_to_zero``) and the readers the
lfm2 cell brings (``test_layer_metrics_lfm2.py``), those the joyai cell
brings (``test_layer_metrics_joyai.py``) and that cell's ``correct`` at a
tiny size, sound and with its own two faults (``test_correct_joyai.py``);
the same two for the trinity cell (``test_layer_metrics_trinity.py``,
``test_correct_trinity.py``: sound, the window left out, bf16 for f32);
and the six parts of ``setup_s`` (``test_setup_parts.py``).
The cells' files are read here, in this process, by the harness' own
``load_cell``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")

LFM2_CELL = "lfm2-8b-a1b.train-ep4share-s8192"
JOYAI_CELL = "joyai-llm-flash.train-ep32share-s4096"
TRINITY_CELL = "trinity-mini.train-ep16share-s8192"


def _bench_module(name):
    """A file of ``benchmark/`` as a module, without ``benchmark/`` on the
    path (its names are short: ``run``, ``reduce``, ``flops``)."""
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{name}", os.path.join(BENCH_DIR, name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def harness():
    return _bench_module("harness")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize(
    "selection",
    [
        ["tests/test_flops.py"],
        ["tests/test_reference_steps.py"],
        ["tests/test_run.py"],
        ["tests/test_layer_metrics_lfm2.py"],
        ["tests/test_reduce.py"],
        ["tests/test_program_spans.py"],
        ["tests/test_layer_metrics_joyai.py"],
        ["tests/test_correct_joyai.py", "-k", "sound or rotary or bfloat16"],
        ["tests/test_layer_metrics_trinity.py"],
        ["tests/test_correct_trinity.py", "-k", "sound or window or bfloat16"],
        ["tests/test_setup_parts.py"],
    ],
    ids=[
        "flops", "reference_steps", "run", "layer_metrics_lfm2", "reduce",
        "program_spans", "layer_metrics_joyai", "correct_joyai",
        "layer_metrics_trinity", "correct_trinity", "setup_parts",
    ],
)
def test_the_benchmarks_own_tests_pass(selection):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "PYTEST_XDIST_WORKER", "PYTEST_XDIST_WORKER_COUNT")
    }
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", *selection, "-q", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=BENCH_DIR, env=env, capture_output=True, text=True, timeout=600,
    )
    tail = done.stdout[-4000:] + done.stderr[-2000:]
    assert done.returncode == 0, tail
    assert " passed" in done.stdout and " failed" not in done.stdout, tail


def test_every_cell_loads_with_its_files(harness, bench):
    """``load_cell`` finds each cell's configuration and traffic by the
    names in ``BENCHMARK.json``, and each has its limits and the keys the
    harness reads (PERF.md section 8)."""
    for cell in bench["workloads"]:
        loaded, config, traffic, _ = harness.load_cell(cell["name"])
        assert loaded == cell
        assert {"reference", "train_argv", "vocab_size", "shape"} <= set(config)
        assert traffic["driver"] and traffic["chips"] == cell["chips"]
        assert os.path.exists(
            os.path.join(BENCH_DIR, "reference", config["reference"] + ".py")
        )
        limits = harness.load_json("limits", cell["name"] + ".json")
        assert limits and all(0 < v <= 1 for v in limits.values()), limits


def test_the_lfm2_cell_counts_what_the_issue_counted(harness):
    """The new cell's ``shape`` by hand: 199,491,584 matmul weights a token
    with the head, 1,297,612,800 operations a token at 8192; each layer
    kind's count worked from the widths in the file."""
    flops = _bench_module("flops")
    cell, config, traffic, _ = harness.load_cell(LFM2_CELL)
    shape = config["shape"]
    assert cell["chips"] == 1 and traffic["seq_len"] == 8192
    assert traffic["rows_per_chip"] == 4 and traffic["reference_block_rows"] == 1
    assert "--remat" in traffic["train_argv"]
    # Adam as the other cells, as ISSUE 29 names it
    gpt2 = harness.load_cell("gpt2-124m.train-s1024")[2]
    assert traffic["adam"] == gpt2["adam"] == {
        "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8
    }
    assert flops.matmul_params(shape) == 199_491_584
    assert flops.train_flops_per_token(shape, traffic["seq_len"]) == 1_297_612_800

    d, wide, narrow = (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"],
    )
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    held, published = config["num_experts"], config["published"]["num_experts"]
    conv = d * 3 * d + d * d
    attention = 2 * d * d + 2 * d * (d // heads) * kv_heads
    experts = (
        d * published
        + config["num_experts_per_tok"] * 3 * d * narrow * held // published
    )
    kinds = {k["name"]: k for k in shape["layer_kinds"]}
    assert kinds["conv_dense"]["matmul_params"] == conv + 3 * d * wide
    assert kinds["attention_experts"]["matmul_params"] == attention + experts
    assert kinds["conv_experts"]["matmul_params"] == conv + experts
    # the kinds are the layers of the file, in their numbers
    of_file = [
        ("conv" if t == "conv" else "attention")
        + ("_dense" if i < config["num_dense_layers"] else "_experts")
        for i, t in enumerate(config["layer_types"])
    ]
    assert {n: of_file.count(n) for n in set(of_file)} == {
        n: k["count"] for n, k in kinds.items()
    }
    assert [k["attention"] for k in shape["layer_kinds"]] == [False, True, False]


def test_the_lfm2_configuration_states_its_share(harness, bench):
    """The cut is in the file: what was reduced beside what was published,
    the share the program is told on its command line, and the count held
    to zero."""
    _, config, _, _ = harness.load_cell(LFM2_CELL)
    (entry,) = [c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b"]
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced"]) == set(config["published"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key], key
    argv = config["train_argv"]
    said = {flag: argv[argv.index(flag) + 1] for flag in argv if flag.startswith("--")}
    assert said["--model"] == "lfm2-8b-a1b"
    assert said["--layers-kept"] == ",".join(map(str, config["layers_kept"]))
    assert said["--experts-held"] == f"{config['experts_first']},{config['num_experts']}"
    assert int(said["--vocab-slice"]) == config["vocab_size"] == config["shape"]["vocab"]
    assert [config["published"]["layer_types"][i] for i in config["layers_kept"]] == (
        config["layer_types"]
    )
    assert config["exact_zero"] == ["train_moe_dropped_assignments"]
    assert config["deployment"]["parameters_here"] == 507_820_160


def test_the_lfm2_cells_metrics_have_their_readers(bench):
    """Each per-layer metric that names the cell has its reader's file, and
    the experts' roofline counts what the issue wrote down."""
    named = [m for m in bench["per_layer"] if LFM2_CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in named) == [
        "gqa_flash_bwd_roofline", "gqa_flash_fwd_roofline",
        "moe_dispatch_device_share", "moe_experts_device_share",
        "moe_experts_roofline", "moe_load_max_over_mean",
        "moe_rows_used_share", "short_conv_device_share",
    ]
    for metric in named:
        assert metric["moves"] == "tokens_per_s_per_chip"
        assert os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py")
        )
    moe_flops = _bench_module("moe_flops")
    with open(os.path.join(BENCH_DIR, "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    tokens = 4 * 8192
    assert moe_flops.expert_layers(config) == 4
    assert moe_flops.even_rows(config, tokens) == 32_768
    ops, nbytes = moe_flops.grouped_products(config, 32_768)
    assert ops == 3 * 2 * 32_768 * 3 * 2048 * 1792
    weights = 8 * 3 * 2048 * 1792
    rows = 32_768 * ((2048 + 2 * 1792) + (1792 + 2048))
    assert nbytes == 3 * 2 * (weights + rows)


def test_the_joyai_cell_counts_what_the_issue_counted(harness):
    """The cell's ``shape`` by hand: 308,805,632 matmul weights a token with
    the head twice, 2,607,808,512 operations a token at 4096; each layer
    kind's count worked from the widths in the file; ``head_dim`` 160
    counts the two attention products at 192 and 128."""
    flops = _bench_module("flops")
    cell, config, traffic, _ = harness.load_cell(JOYAI_CELL)
    shape = config["shape"]
    assert cell["chips"] == 1 and traffic["seq_len"] == 4096
    assert traffic["rows_per_chip"] == 4 and traffic["reference_block_rows"] == 1
    assert traffic["dataset_rows"] == 512 and traffic["trace_seconds"] == 4.0
    assert "--remat" in traffic["train_argv"]
    assert traffic["adam"] == {"lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    assert flops.matmul_params(shape) == 308_805_632
    assert flops.train_flops_per_token(shape, traffic["seq_len"]) == 2_607_808_512

    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rot, v = (
        config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    )
    assert nope + rot == config["qk_head_dim"] == 192 and v == 128
    mla = (
        d * config["q_lora_rank"] + config["q_lora_rank"] * heads * (nope + rot)
        + d * (config["kv_lora_rank"] + rot)
        + config["kv_lora_rank"] * heads * (nope + v) + heads * v * d
    )
    assert mla == 26_345_472
    narrow = config["moe_intermediate_size"]
    held, published = config["n_routed_experts"], config["published"]["n_routed_experts"]
    experts = (
        d * published + config["n_shared_experts"] * 3 * d * narrow
        + config["num_experts_per_tok"] * 3 * d * narrow * held // published
    )
    kinds = {k["name"]: k for k in shape["layer_kinds"]}
    assert kinds["mla_dense"]["matmul_params"] == mla + 3 * d * config["intermediate_size"]
    assert kinds["mla_experts"]["matmul_params"] == mla + experts == 32_768_000
    assert kinds["mtp_mla_experts"]["matmul_params"] == mla + experts + 2 * d * d
    dense = sum(i < config["first_k_dense_replace"] for i in config["layers_kept"])
    assert [k["count"] for k in shape["layer_kinds"]] == [
        dense, len(config["layers_kept"]) - dense, config["num_nextn_predict_layers"],
    ]
    assert all(k["attention"] for k in shape["layer_kinds"])
    assert shape["heads"] * shape["head_dim"] * 2 == heads * (nope + rot + v)
    assert shape["head_token_share"] == 1 + config["num_nextn_predict_layers"]
    # the rows' bound at the cell's tokens, derived and not a flag
    sys.path.insert(0, ROOT)
    from distributed_pytorch_example_tpu.models.moe import dropless_rows_bound

    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    assert dropless_rows_bound(
        tokens, config["num_experts_per_tok"], held, published
    ) == 8192


def test_the_joyai_configuration_states_its_source_and_its_share(harness, bench):
    """Every number of the catalog's config is in the file under its key,
    but for the reduced ones, which stand beside their published values;
    the share is the one the program is told on its command line."""
    _, config, _, _ = harness.load_cell(JOYAI_CELL)
    (entry,) = [c for c in bench["configs"] if c["name"] == "joyai-llm-flash"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    assert entry["source"] == config["source"]
    assert config["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280
    }
    for key in config["reduced"]:
        assert config[key] != config["published"][key], key
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "moe_intermediate_size": 768, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_nextn_predict_layers": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rms_norm_eps": 1e-6,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "model_type": "joyai_llm_flash",
    }
    assert {k: config[k] for k in published} == published
    argv = config["train_argv"]
    said = {flag: argv[argv.index(flag) + 1] for flag in argv if flag.startswith("--")}
    assert said["--model"] == "joyai-llm-flash"
    assert said["--layers-kept"] == ",".join(map(str, config["layers_kept"]))
    assert said["--experts-held"] == f"{config['experts_first']},{config['n_routed_experts']}"
    assert int(said["--vocab-slice"]) == config["vocab_size"] == config["shape"]["vocab"]
    assert len(config["layers_kept"]) == config["num_hidden_layers"]
    assert config["exact_zero"] == ["train_moe_dropped_assignments"]
    assert config["deployment"]["chips_sharing_a_layer"] == 32
    assert config["deployment"]["parameters_here"] == 491_696_128
    assert config["init"] == {"std": 0.02, "select_bias_std": 0.02}
    for assumed in ("mtp_composition", "mtp_eh_input_order", "mtp_hidden_state",
                    "mtp_loss_weight", "select_bias", "aux_loss",
                    "initializer_range", "routing_weight_epsilon",
                    "max_position_embeddings"):
        assert config["assumed"][assumed]


def test_the_joyai_cells_metrics_have_their_readers(bench):
    """Each per-layer metric that names the cell, and only the six this
    cell brings do, has its reader's file; no accepted list gained it."""
    named = [m for m in bench["per_layer"] if JOYAI_CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in named) == [
        "mla_flash_bwd_roofline", "mla_flash_fwd_roofline",
        "mla_proj_device_share", "moe_held_share", "moe_shared_device_share",
        "mtp_device_share",
    ]
    for metric in named:
        assert metric["moves"] == "tokens_per_s_per_chip"
        assert metric["workloads"] == [JOYAI_CELL] and metric["unit"] == "%"
        assert os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py")
        )
    assert bench["per_layer"][-16:-10] == named  # appended, nothing moved
    without_a_list = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert len(without_a_list) == 16


def test_the_trinity_cell_counts_what_the_issue_counted(harness):
    """The cell's ``shape`` by hand: 322,834,432 matmul weights a token,
    2,138,333,184 operations a trained token at 8192; the window layers
    fold their two products over the 1792.125 keys a query sees under a
    2048 window into ``matmul_params``, the full layer's are counted by
    ``flops.py`` over half the keys."""
    flops = _bench_module("flops")
    cell, config, traffic, _ = harness.load_cell(TRINITY_CELL)
    shape = config["shape"]
    assert cell["chips"] == 1 and traffic["seq_len"] == 8192
    assert traffic["rows_per_chip"] == 4 and traffic["reference_block_rows"] == 1
    assert traffic["dataset_rows"] == 512 and traffic["trace_seconds"] == 4.0
    assert traffic["train_argv"] == ["--remat"]
    assert traffic["adam"] == {"lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    assert flops.matmul_params(shape) == 322_834_432
    assert flops.train_flops_per_token(shape, traffic["seq_len"]) == 2_138_333_184

    d, heads, kv_heads, width = (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
    )
    attention = 3 * d * heads * width + 2 * d * kv_heads * width  # q, gate, o; k, v
    assert attention == 27_262_976
    seq, window = traffic["seq_len"], config["sliding_window"]
    keys = sum(min(i + 1, window) for i in range(seq)) / seq
    assert keys == 1792.125
    fold = 2 * keys * heads * width
    narrow = config["moe_intermediate_size"]
    held, published = config["num_experts"], config["published"]["num_experts"]
    experts = (
        d * published + config["num_shared_experts"] * 3 * d * narrow
        + config["num_experts_per_tok"] * 3 * d * narrow * held // published
    )
    kinds = {k["name"]: k for k in shape["layer_kinds"]}
    assert kinds["window_dense"]["matmul_params"] == (
        attention + 3 * d * config["intermediate_size"] + fold
    )
    assert kinds["window_experts"]["matmul_params"] == attention + experts + fold
    assert kinds["full_experts"]["matmul_params"] == attention + experts
    types = [config["layer_types"][i] for i in config["layers_kept"]]
    dense = [i < config["num_dense_layers"] for i in config["layers_kept"]]
    assert [k["count"] for k in shape["layer_kinds"]] == [
        sum(t == "sliding_attention" and d for t, d in zip(types, dense)),
        sum(t == "sliding_attention" and not d for t, d in zip(types, dense)),
        types.count("full_attention"),
    ] == [1, 3, 1]
    assert [k["attention"] for k in shape["layer_kinds"]] == [False, False, True]
    assert (shape["heads"], shape["head_dim"]) == (heads, width)
    sys.path.insert(0, ROOT)
    from distributed_pytorch_example_tpu.models.moe import dropless_rows_bound
    from distributed_pytorch_example_tpu.models.trinity import TrinityMini

    # four times the held experts' even share (8 of 128 at 32,768 tokens)
    tokens = traffic["rows_per_chip"] * seq
    assert dropless_rows_bound(
        tokens, config["num_experts_per_tok"], held, published,
        TrinityMini.rows_factor,
    ) == 4 * 16_384 == 65_536


def test_the_trinity_configuration_states_its_source_and_its_share(harness, bench):
    """Every number of the catalog's config is in the file under its key,
    but for the reduced ones, which stand beside their published values;
    the share is the one the program is told on its command line."""
    _, config, _, _ = harness.load_cell(TRINITY_CELL)
    (entry,) = [c for c in bench["configs"] if c["name"] == "trinity-mini"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"
    ]
    assert entry["source"] == config["source"]
    assert config["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 200192
    }
    for key in config["reduced"]:
        assert config[key] != config["published"][key], key
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True,
    }
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == [
        "full_attention" if i % 4 == 3 else "sliding_attention" for i in range(32)
    ]
    argv = config["train_argv"]
    said = {flag: argv[argv.index(flag) + 1] for flag in argv if flag.startswith("--")}
    assert said["--model"] == "trinity-mini"
    assert said["--layers-kept"] == ",".join(map(str, config["layers_kept"])) == "0,2,3,4,5"
    assert said["--experts-held"] == f"{config['experts_first']},{config['num_experts']}"
    assert int(said["--vocab-slice"]) == config["vocab_size"] == config["shape"]["vocab"]
    assert len(config["layers_kept"]) == config["num_hidden_layers"]
    assert config["exact_zero"] == ["train_moe_dropped_assignments"]
    assert config["deployment"]["chips_sharing_a_layer"] == 16
    assert config["deployment"]["parameters_here"] == 504_147_200
    assert config["init"] == {"std": 0.02, "select_bias_std": 0.02}
    for assumed in ("attention_output_gate", "qk_norm", "nope_on_full_layers",
                    "four_norms", "embedding_scale", "sliding_window_convention",
                    "select_bias", "load_balance_coeff",
                    "routing_weight_epsilon", "initializer_range",
                    "max_position_embeddings"):
        assert config["assumed"][assumed]


def test_the_trinity_cells_metrics_have_their_readers(bench):
    """The four metrics the cell brings, appended last, each with its
    reader's file; no accepted list gained the cell."""
    named = [m for m in bench["per_layer"] if TRINITY_CELL in m.get("workloads", [])]
    assert [m["name"] for m in named] == [
        "window_flash_fwd_roofline", "window_flash_bwd_roofline",
        "attn_window_device_share", "attn_full_device_share",
    ]
    assert bench["per_layer"][-10:-6] == named
    for metric in named:
        assert metric["moves"] == "tokens_per_s_per_chip"
        assert metric["workloads"] == [TRINITY_CELL] and metric["unit"] == "%"
        assert metric["source"] == "device_trace"
        assert os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py")
        )
    assert bench["workloads"][-1]["name"] == TRINITY_CELL
    assert bench["configs"][-1]["name"] == "trinity-mini"


def test_the_setup_parts_have_their_readers_in_every_cell(bench):
    """The six parts of ``setup_s``, appended last: seconds from the
    program's spans, read in every cell (no list of cells), each with its
    reader's file."""
    parts = bench["per_layer"][-6:]
    assert [m["name"] for m in parts] == [
        "setup_import_s", "setup_before_main_s", "setup_main_s",
        "setup_steps_s", "setup_outside_s", "setup_cpu_s",
    ]
    assert len({m["layer"] for m in parts}) == 1
    for metric in parts:
        assert "workloads" not in metric and metric["moves"] == "setup_s"
        assert (metric["unit"], metric["better"], metric["source"]) == (
            "s", "lower", "program_span")
        assert os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py")
        )
