"""flops.py against numbers worked by hand for both configurations."""

import json
import os

import pytest

import flops
import tiny


def shape(name):
    with open(os.path.join(tiny.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)["shape"]


def test_gpt2_124m_per_token():
    s = shape("gpt2-124m")
    # a block: q, k, v, o (4 x 768 x 768) + up and down (2 x 768 x 3072)
    block = 4 * 768 * 768 + 2 * 768 * 3072
    assert block == 7_077_888
    # tied head counted once, as the head's product
    assert flops.matmul_params(s) == 12 * block + 50257 * 768 == 123_532_032
    # two attention products, 2 ops a multiply-add, half the keys under the
    # causal mask: 12 layers x 2 x 2 x 512 x 768
    assert flops.attention_flops_per_token(s, 1024) == 18_874_368
    assert flops.train_flops_per_token(s, 1024) == 3 * (2 * 123_532_032 + 18_874_368)
    assert flops.train_flops_per_token(s, 1024) == 797_815_296


def test_bert_base_per_token():
    s = shape("bert-base")
    head = 30522 * 768 + 768 * 768  # tied decoder + the head's dense layer
    assert flops.matmul_params(s) == pytest.approx(12 * 7_077_888 + 0.15 * head)
    # no causal mask: all 512 keys
    assert flops.attention_flops_per_token(s, 512) == 12 * 2 * 2 * 512 * 768
    assert flops.train_flops_per_token(s, 512) == pytest.approx(587_858_688)


def test_flash_kernel_counts():
    # GPT-2's call: 16 rows x 12 heads, 1024 x 1024 scores halved by the mask
    ops, nbytes = flops.flash_forward(16, 12, 1024, 64, True)
    assert ops == 2 * 2 * 16 * 12 * 1024 * 512 * 64 == 25_769_803_776
    assert nbytes == 4 * 16 * 12 * 1024 * 64 * 2 + 16 * 12 * 1024 * 4
    ops_b, bytes_b = flops.flash_backward(16, 12, 1024, 64, True)
    assert ops_b == 2 * ops
    assert bytes_b == 8 * 16 * 12 * 1024 * 64 * 2 + 16 * 12 * 1024 * 4
    # BERT's call does the same work: twice the rows, half the length, no mask
    assert flops.flash_forward(32, 12, 512, 64, False)[0] == ops


def test_roofline_names_the_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1, peak) == (1.0, "compute")
    assert flops.roofline_seconds(1, 819e9, peak) == (1.0, "memory")


def test_peaks_are_keyed_by_exact_device_kind():
    import harness

    peaks = harness.load_peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v5" not in peaks and "cpu" not in peaks
