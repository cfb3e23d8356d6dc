"""``correct`` for the ``joyai-llm-flash`` cell, at a tiny size on the CPU:
a sound run through the whole of ``run.py``'s cell is correct, carries the
count held to zero and both part losses in its records; this model's own
fault, a precision swap and the shared planted faults are not correct.

* The rotary part left out of the scores (the program's attention is handed
  queries and keys cut to their first ``qk_nope_head_dim`` dims): the fault
  the cell's limits were read against on the chip
  (``calibrate_faults.py --fault scores_without_rotary:score_dims=128``).
* bfloat16 where the tiny configuration states float32: the step's losses
  and gradients then carry bf16's rounding, which the float32 limits of a
  tiny run (its own, a tenth of the cell's) do not pass.
* Half of the batch left out; an expert layer that drops assignments.
* The control: the plain reference in fp8 at the published widths.
"""

import time

import jax
import numpy as np
import pytest

import harness
import tiny
import tiny_joyai
import train_reference
from test_correct import drop_rows, failing


def run_cell(monkeypatch, seed=99, config=tiny_joyai.JOYAI):
    import run as run_py

    tiny_joyai.shrink_models(monkeypatch)
    result, compared = run_py.run_cell(
        tiny.args(seed=seed),
        (tiny.cell(tiny_joyai.CELL), config, tiny.traffic(), tiny.bench()),
        jax.devices()[:1], tiny.PEAK, harness.Clock(time.time()),
    )
    failed = sorted(k for k, (v, lim) in compared.items() if not v <= lim)
    return result, compared, failed


def test_a_sound_run_is_correct_and_reports_both_losses(monkeypatch):
    from drivers import train_window

    records = []
    real = train_window.TrainRun.fit

    def fit(self, **kw):
        records.append(real(self, **kw))
        return records[-1]

    monkeypatch.setattr(train_window.TrainRun, "fit", fit)
    result, compared, failed = run_cell(monkeypatch, seed=2 ** 31 + 33)
    assert result["correct"] is True and failed == []
    assert compared["train_moe_dropped_assignments"] == (0.0, 0)
    assert result["attempted"] > 0 and result["failed"] == 0
    # a float32 program sits within a tenth of the cell's limits (the
    # precision swap below is held to that tenth)
    assert all(v <= lim / 10 for v, lim in compared.values() if lim), compared
    for record in records:  # every epoch record carries the two losses apart
        both = record["train_loss_next"] + 0.3 * record["train_loss_mtp"]
        assert abs(record["train_loss"] - both) < 1e-4 * both
        assert 0.0 < record["train_moe_held_share"] < 1.0


def test_rotary_part_left_out_of_the_scores_is_not_correct(monkeypatch):
    from distributed_pytorch_example_tpu.models import joyai

    real = joyai.dot_product_attention
    nope = tiny_joyai.JOYAI["qk_nope_head_dim"]
    monkeypatch.setattr(
        joyai, "dot_product_attention",
        lambda q, k, v, **kw: real(q[..., :nope], k[..., :nope], v, **kw),
    )
    result, _, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed


def test_bfloat16_for_float32_is_not_correct(monkeypatch):
    """The tiny configuration states float32; a program in bfloat16 is the
    precision below it. Held to a tenth of the cell's limits (the float32
    program's own readings are 1e-3 of them)."""
    argv = [
        "bfloat16" if a == "float32" else a
        for a in tiny_joyai.JOYAI["train_argv"]
    ]
    swapped = {**tiny_joyai.JOYAI, "train_argv": argv}
    result, compared, _ = run_cell(monkeypatch, config=swapped)
    tenth = sorted(
        k for k, (v, lim) in compared.items() if lim and not v <= lim / 10
    )
    assert tenth, compared


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    drop_rows(monkeypatch, 0.5)
    result, _, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed


def test_dropped_assignments_are_not_correct(monkeypatch):
    from distributed_pytorch_example_tpu.models import moe

    monkeypatch.setattr(moe, "dropless_rows_bound", lambda *a: 8)
    result, compared, failed = run_cell(monkeypatch)
    assert result["correct"] is False
    assert "train_moe_dropped_assignments" in failed


# fp8's error grows with the length of the sums: the control keeps the
# published hidden width, ranks and head sizes at one expert layer and the
# prediction module, 8 of 256 experts held, 8 rows of 64 tokens
PUBLISHED_WIDTHS = dict(
    hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, moe_intermediate_size=768, n_routed_experts=8,
    num_experts_per_tok=8, published={"num_hidden_layers": 40,
                                      "n_routed_experts": 256},
    layers_kept=[1], num_hidden_layers=1,
)


@pytest.mark.parametrize("seed", [3, 4])
def test_fp8_control_and_the_reference_fault_are_not_correct(seed):
    config = {**tiny_joyai.JOYAI, **PUBLISHED_WIDTHS}
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

    def steps(sizes=config, dot=train_reference.plain_dot):
        return train_reference.ReferenceSteps(model, sizes, adam, 4, dot)

    key = jax.random.key(seed + 100)
    reference = steps().run(params(), batches, key)
    again, _ = train_reference.compare(steps().run(params(), batches, key), reference)
    assert failing(again, tiny_joyai.CELL) == []
    control, _ = train_reference.compare(
        steps(dot=train_reference.fp8_dot).run(params(), batches, key), reference
    )
    assert failing(control, tiny_joyai.CELL), control
    fault, _ = train_reference.compare(
        steps(sizes={**config, "score_dims": 128}).run(params(), batches, key),
        reference,
    )
    assert failing(fault, tiny_joyai.CELL), fault
