"""What the two flash-attention roofline metrics share: the kernels' shapes
from the cell, their least time on the chip from ``flops.py``, and their
device time from the trace.

The patterns are written against ``reduce.short_name`` and tell the kernels
by what they return, not yet by the names the program gives them (PERF.md
section 7): a ``tpu_custom_call`` that returns (output (b, h, s, d), float32
log-sum-exp (b, h, s, 1)) is the forward kernel, and one that returns three
(b, h, s, d) tensors (dq, dk, dv) the backward. Every call found is counted
at ONE shape: the cell's rows a chip, ``shape["heads"]``, its sequence
length and ``shape["head_dim"]``. So the two metrics list their cells in
``BENCHMARK.json``: a cell whose attention has another shape (grouped keys,
a window, layers of two lengths), or whose program holds another Pallas
kernel of either signature, brings metric files of its own."""

import flops

_T4 = r"\w+\[\d+,\d+,\d+,\d+\]"
FORWARD = rf"^\S+ = \({_T4}, f32\[\d+,\d+,\d+,1\]\) custom-call tpu_custom_call$"
BACKWARD = rf"^\S+ = \({_T4}, {_T4}, {_T4}\) custom-call tpu_custom_call$"


def roofline_share(run, pattern, count):
    shape, traffic = run["config"]["shape"], run["traffic"]
    seconds, calls = run["trace"].kernel_seconds(pattern)
    if not calls:
        return None
    ops, nbytes = count(
        traffic["rows_per_chip"], shape["heads"], traffic["seq_len"],
        shape["head_dim"], shape["causal"],
    )
    least, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
    run.setdefault("notes", []).append(
        f"{pattern}: {calls} calls, {seconds:.6f} s on the device, least "
        f"{least * 1e6:.1f} us a call, {bound}-bound"
    )
    return 100.0 * least * calls / seconds
