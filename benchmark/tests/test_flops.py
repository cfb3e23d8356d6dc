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


def by_kinds(shape, kinds):
    """``shape`` with its stack written out by kind; the keys of the
    uniform stack taken away, so that nothing can fall back on them."""
    out = {k: v for k, v in shape.items() if k not in ("layers", "d_ff")}
    out["layer_kinds"] = kinds
    return out


@pytest.mark.parametrize("name,seq_len", [("gpt2-124m", 1024), ("bert-base", 512)])
def test_layer_kinds_written_out_for_a_uniform_stack_change_nothing(name, seq_len):
    s = shape(name)
    block = {"name": "block", "count": 12, "attention": True,
             "matmul_params": 4 * 768 * 768 + 2 * 768 * 3072}
    # one kind of twelve, or the same twelve split into two kinds
    for kinds in ([block], [{**block, "count": 5}, {**block, "name": "b", "count": 7}]):
        k = by_kinds(s, kinds)
        assert flops.matmul_params(k) == flops.matmul_params(s)
        assert flops.attention_flops_per_token(k, seq_len) == \
            flops.attention_flops_per_token(s, seq_len)
        assert flops.train_flops_per_token(k, seq_len) == \
            flops.train_flops_per_token(s, seq_len)


# a stack of three kinds, every number below worked by hand: hidden 2048;
# one layer in four attends, 32 query heads of 64 over 8 key heads; the
# others are gated convolutions; the first layer's MLP is dense and gated,
# 7168 wide; the others route each token to 4 of 32 gated experts 1792
# wide, of which this chip holds 8
D = 2048
ATTN = 2 * D * (32 * 64) + 2 * D * (8 * 64)  # q and o; k and v: 10,485,760
CONV = 3 * D * D + D * D  # in-projection to three gates' width, and out
DENSE = 3 * D * 7168  # gate, up, down: 44,040,192
EXPERTS = 4 * (3 * D * 1792) * (8 / 32) + D * 32  # routed here, + the router
MIXED = {
    "d_model": D, "heads": 32, "head_dim": 64, "vocab": 8192, "causal": True,
    "head_token_share": 1.0,
    "layer_kinds": [
        {"name": "conv+dense", "count": 1, "matmul_params": CONV + DENSE,
         "attention": False},
        {"name": "conv+experts", "count": 3, "matmul_params": CONV + EXPERTS,
         "attention": False},
        {"name": "attention+experts", "count": 1,
         "matmul_params": ATTN + EXPERTS, "attention": True},
    ],
}


def test_a_stack_of_mixed_layer_kinds_by_hand():
    assert (ATTN, CONV, DENSE, EXPERTS) == (10_485_760, 16_777_216, 44_040_192,
                                            11_075_584)
    body = (16_777_216 + 44_040_192) + 3 * (16_777_216 + 11_075_584) \
        + (10_485_760 + 11_075_584)
    assert body == 165_937_152
    assert flops.matmul_params(MIXED) == body + 8192 * 2048 == 182_714_368
    # ONE layer attends: 2 products x 2 ops x 2048 visible keys x 2048 wide,
    # at the query heads' width though the keys have 8 heads
    assert flops.attention_flops_per_token(MIXED, 4096) == 2 * 2 * 2048 * 2048
    assert flops.train_flops_per_token(MIXED, 4096) == \
        3 * (2 * 182_714_368 + 16_777_216) == 1_146_617_856


def test_the_head_keeps_its_share_and_its_extra_under_layer_kinds():
    mlm = {**MIXED, "causal": False, "head_token_share": 0.15,
           "head_extra_matmul_params": D * D}
    assert flops.matmul_params(mlm) == pytest.approx(
        165_937_152 + 0.15 * (8192 * 2048 + 2048 * 2048)
    )
    assert flops.attention_flops_per_token(mlm, 512) == 2 * 2 * 512 * 2048


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
