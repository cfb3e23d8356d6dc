"""What the two grouped-query flash roofline metrics share. The kernels are
told by the names the program gives its ``pallas_call``s, as they arrive in
a v5e trace (the HLO instruction's name: ``flash_fwd.N``, ``flash_bwd_fused.N``
or ``flash_bwd_dq.N`` + ``flash_bwd_dkv.N``: the multi-block kernels, for
sequences over one tile). Every call is counted at the cell's rows a chip,
the QUERY heads (``shape["heads"]``: each key head serves several), the
sequence length and ``shape["head_dim"]``. A forward call recomputed under
remat is a call and is counted as one. Of a backward pass split in two
kernels each does two of the four products and is counted as half a call."""

import flops

FORWARD = {r"^flash_fwd(\.\d+)? = ": 1.0}
BACKWARD = {
    r"^flash_bwd_fused(\.\d+)? = ": 1.0,
    r"^flash_bwd_dq(\.\d+)? = ": 0.5,
    r"^flash_bwd_dkv(\.\d+)? = ": 0.5,
}


def roofline_share(run, patterns, count):
    shape, traffic = run["config"]["shape"], run["traffic"]
    seconds = calls = 0.0
    for pattern, weight in patterns.items():
        s, n = run["trace"].kernel_seconds(pattern)
        seconds, calls = seconds + s, calls + weight * n
    if not calls:
        return None
    ops, nbytes = count(
        traffic["rows_per_chip"], shape["heads"], traffic["seq_len"],
        shape["head_dim"], shape["causal"],
    )
    least, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
    run.setdefault("notes", []).append(
        f"{'|'.join(patterns)}: {calls:g} calls, {seconds:.6f} s on the "
        f"device, least {least * 1e6:.1f} us a call, {bound}-bound"
    )
    return 100.0 * least * calls / seconds
