"""``correct`` for the ``lfm2-8b-a1b`` cell, at a tiny size on the CPU: a
sound run through the whole of ``run.py``'s cell is correct and carries the
count held to zero; the control and the planted faults are not correct.

* The control: the plain reference computed in fp8 in the program's place.
* Half of the batch left out, the mean taken over the rest.
* This model's own fault: the selection bias left out of the choice (the
  program routes by the scores alone). A reading has to rise for it, or
  the comparison does not check the routing.
* An expert layer that drops assignments (its row bound forced small):
  ``train_moe_dropped_assignments`` is over its limit of 0.
"""

import time

import jax
import numpy as np
import pytest

import harness
import tiny
import tiny_lfm2
import train_reference
from test_correct import drop_rows, failing


def run_cell(monkeypatch, seed=99):
    import run as run_py

    tiny_lfm2.shrink_models(monkeypatch)
    result, compared = run_py.run_cell(
        tiny.args(seed=seed),
        (tiny.cell(tiny_lfm2.CELL), tiny_lfm2.LFM2, tiny.traffic(), tiny.bench()),
        jax.devices()[:1], tiny.PEAK, harness.Clock(time.time()),
    )
    failed = sorted(k for k, (v, lim) in compared.items() if not v <= lim)
    return result, compared, failed


def test_a_sound_run_is_correct_and_holds_the_drops_to_zero(monkeypatch):
    result, compared, failed = run_cell(monkeypatch, seed=2 ** 31 + 29)
    assert result["correct"] is True and failed == []
    assert compared["train_moe_dropped_assignments"] == (0.0, 0)
    assert result["attempted"] > 0 and result["failed"] == 0


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    drop_rows(monkeypatch, 0.5)
    result, _, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed


def test_bias_left_out_of_the_choice_is_not_correct(monkeypatch):
    from distributed_pytorch_example_tpu.models import moe

    real = moe.moe_route_sigmoid
    monkeypatch.setattr(
        moe, "moe_route_sigmoid",
        lambda x, router, bias, **kw: real(x, router, None, **kw),
    )
    result, _, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed


def test_dropped_assignments_are_not_correct(monkeypatch):
    from distributed_pytorch_example_tpu.models import moe

    monkeypatch.setattr(moe, "dropless_rows_bound", lambda *a: 8)
    result, compared, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "train_moe_dropped_assignments" in failed
    assert compared["train_moe_dropped_assignments"][0] > 0


# fp8's error grows with the length of the sums: the control keeps the
# published hidden width and head size at two layers (convolution + experts,
# attention + experts), 8 of 32 experts held, 8 rows of 64 tokens
PUBLISHED_WIDTHS = dict(
    hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
    moe_intermediate_size=1792, num_experts=8, num_experts_per_tok=4,
    published={"num_experts": 32}, layer_types=["conv", "full_attention"],
    layers_kept=[1, 2], num_dense_layers=0, num_hidden_layers=2,
)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_fp8_control_is_not_correct(seed):
    config = {**tiny_lfm2.LFM2, **PUBLISHED_WIDTHS}
    model = harness.load_module("reference", config["reference"])
    adam = tiny.traffic()["adam"]
    rng = np.random.default_rng(seed)
    batches = [
        rng.integers(0, config["vocab_size"], (8, 64), dtype=np.int32)
        for _ in range(3)
    ]
    make = jax.jit(lambda k: model.init_params(k, config))

    def params():  # made anew for each run, which consumes them
        return make(jax.random.key(seed))

    key = jax.random.key(seed + 100)
    plain = train_reference.ReferenceSteps(model, config, adam, 4)
    fp8 = train_reference.ReferenceSteps(
        model, config, adam, 4, train_reference.fp8_dot
    )
    reference = plain.run(params(), batches, key)
    again, _ = train_reference.compare(plain.run(params(), batches, key), reference)
    assert failing(again, tiny_lfm2.CELL) == []
    control, _ = train_reference.compare(fp8.run(params(), batches, key), reference)
    assert failing(control, tiny_lfm2.CELL), control
