"""``correct`` has to come out false when it should.

* The control: the plain reference computed in fp8, put in the program's
  place, fails at least one number of each configuration under the real
  cells' limits (at a size a test can hold; the readings at the cells' own
  sizes, on the chip, are in PERF.md).
* The timed path broken underneath, the rest of a run driven as it is: a
  step that returns its state unchanged; half of the batch left out, the
  mean taken over the rest; the exchange between chips left out (every chip
  would train on its own rows: the program planted with one chip's rows).
"""

import time

import jax
import pytest

import harness
import tiny
import train_reference


def limits(cell):
    return harness.load_json("limits", cell + ".json")


def failing(numbers, cell):
    lim = limits(cell)
    return sorted(k for k, v in numbers.items() if k in lim and not v <= lim[k])


# fp8's error grows with the length of the sums, so the control is kept at
# the published widths (768, 3072, 12 heads), cut to 3 layers, 512 words and
# 8 rows of 64 tokens: what a test on the CPU can hold
PUBLISHED_WIDTHS = {
    "gpt2": dict(n_embd=768, n_head=12, n_layer=3),
    "bert": dict(hidden_size=768, intermediate_size=3072,
                 num_attention_heads=12, num_hidden_layers=3),
}


@pytest.mark.parametrize(
    "config,cell",
    [(tiny.GPT2, "gpt2-124m.train-s1024"), (tiny.BERT, "bert-base.train-s512"),
     (tiny.GPT2, "gpt2-124m.train-dp4-s1024")],
    ids=["gpt2", "bert", "gpt2-dp4"],
)
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_fp8_control_is_not_correct(config, cell, seed):
    import numpy as np

    config = {**config, **PUBLISHED_WIDTHS[config["reference"]]}
    model = harness.load_module("reference", config["reference"])
    traffic = tiny.traffic(rows_per_chip=8)
    rng = np.random.default_rng(seed)
    batches = [
        rng.integers(0, config["vocab_size"], (8, 64), dtype=np.int32)
        for _ in range(3)
    ]
    make = jax.jit(lambda k: model.init_params(k, config))

    def params():  # made anew for each run, which consumes them
        return make(jax.random.key(seed))

    key = jax.random.key(seed + 100)
    plain = train_reference.ReferenceSteps(model, config, traffic["adam"], 4)
    fp8 = train_reference.ReferenceSteps(
        model, config, traffic["adam"], 4, train_reference.fp8_dot
    )
    reference = plain.run(params(), batches, key)
    again, _ = train_reference.compare(plain.run(params(), batches, key), reference)
    assert failing(again, cell) == []
    control, _ = train_reference.compare(fp8.run(params(), batches, key), reference)
    assert failing(control, cell), control


def run_broken(monkeypatch, cell_name, chips=1):
    import run as run_py

    tiny.shrink_models(monkeypatch)
    bench = tiny.bench()
    result, compared = run_py.run_cell(
        tiny.args(seed=99),
        (tiny.cell(cell_name, chips), tiny.GPT2, tiny.traffic(chips), bench),
        jax.devices()[:chips], tiny.PEAK, harness.Clock(time.time()),
    )
    failed = sorted(k for k, (v, lim) in compared.items() if not v <= lim)
    return result, failed


def test_a_sound_run_on_four_devices_is_correct(monkeypatch):
    result, failed = run_broken(monkeypatch, "gpt2-124m.train-dp4-s1024", chips=4)
    assert result["correct"] is True and failed == []
    assert result["device"]["count"] == 4


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from distributed_pytorch_example_tpu.train import loop

    real = loop.build_train_step

    def broken(*a, **k):
        step = real(*a, **k).__wrapped__

        def unchanged(state, batch):
            new_state, metrics = step(state, batch)
            return state.replace(step=new_state.step), metrics

        return jax.jit(unchanged)

    monkeypatch.setattr(loop, "build_train_step", broken)
    result, failed = run_broken(monkeypatch, "gpt2-124m.train-s1024")
    assert result["correct"] is False
    assert "param_change_norm_gap" in failed and "first_grad_norm_gap" in failed


def drop_rows(monkeypatch, keep_share):
    """The program's loss on the first ``keep_share`` of a batch's rows."""
    from distributed_pytorch_example_tpu.train import tasks

    real = tasks.CausalLMTask.compute_loss

    def fewer(self, model, params, model_state, batch, rng, *, train):
        rows = batch["tokens"].shape[0]
        batch = {"tokens": batch["tokens"][: int(rows * keep_share)]}
        return real(self, model, params, model_state, batch, rng, train=train)

    monkeypatch.setattr(tasks.CausalLMTask, "compute_loss", fewer)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    drop_rows(monkeypatch, 0.5)
    result, failed = run_broken(monkeypatch, "gpt2-124m.train-s1024")
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed


def test_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    drop_rows(monkeypatch, 0.25)
    result, failed = run_broken(monkeypatch, "gpt2-124m.train-dp4-s1024", chips=4)
    assert result["correct"] is False
    assert "first_grad_norm_gap" in failed
