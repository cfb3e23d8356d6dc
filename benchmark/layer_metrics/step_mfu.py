"""Layer: train step (``train/step.py``). The whole step's share of the
chips' peak: model operations per token (``flops.train_flops_per_token``:
matrix products and attention, no optimizer, nothing recomputed) times the
tokens per second of the traced window's whole steps, over chips times the
published bf16 peak, in per cent."""

import flops


def read(run):
    trace = run["trace"]
    if trace.steps() < 1:
        return None
    tokens_per_s = trace.steps() * run["tokens_per_step"] / trace.window_s()
    per_token = flops.train_flops_per_token(
        run["config"]["shape"], run["traffic"]["seq_len"]
    )
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * tokens_per_s * per_token / peak
