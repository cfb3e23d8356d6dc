"""Scheduler-level comm/compute overlap of the bucketed gradient sync.

The wire collectives run INSIDE jitted shard_map manual regions
(``parallel/wire.py``, ``ops/pallas/collectives.py``), so host-side timing
around the call sites can never see how much of the collective time the
XLA scheduler hid behind compute. :func:`scheduled_overlap` is the static,
backend-free estimate from the bucket plan; the measured counterpart lives
with the benchmark (``collective_exposed_share``), read from the device
plane of a profiler trace.
"""

from __future__ import annotations

from typing import Optional


def scheduled_overlap(plan, grad_accum_steps: int = 1,
                      trace=None) -> Optional[dict]:
    """Scheduler-level overlap estimate from a static wire BucketPlan.

    A measured overlap needs a device plane, which a CPU trace does not
    have, so CI on the fake 8-chip mesh cannot gate issue ORDER from a
    profile. This estimate is the deterministic complement: the fused
    bucket schedule
    (``parallel/wire.py sync_grads``) issues bucket k's collective on an
    independent dataflow chain as soon as the backward segment feeding it
    completes, so every bucket EXCEPT the last one has remaining backward
    compute (the segments feeding buckets k+1..K-1 of the final
    microbatch) for the XLA latency-hiding scheduler to slide it behind.
    The last-issued bucket has nothing left to hide behind — its wire
    time is the exposed tail:

        overlap_frac_scheduled = hideable wire bytes / total wire bytes
                               = 1 - wire_bytes(last bucket) / total

    Byte-weighted because wire time is bandwidth-dominated at bucket
    sizes (that is what bucketing is FOR). ``grad_accum_steps`` does not
    change the ratio — the sync runs once per optimizer step, after the
    LAST microbatch's backward, whose per-segment structure is identical.
    This is the quantity the ISSUE-19 CI gate checks (>= 0.5 for
    ZeRO-1+wire configs). What a chip measured is the benchmark's
    ``collective_exposed_share`` (interval arithmetic on the device plane,
    ``benchmark/reduce.py``).

    ``trace`` (a ``telemetry.trace.TraceWriter``, optional) gets one
    complete event per bucket in the modeled issue order — the
    bucket-level timeline the ISSUE's "bucket issue/complete spans" CI
    artifact asks for — with the bucket's kind/bytes/hideability in args.
    ``plan`` is treated as unbucketed (estimate 0.0: ONE inline sync
    chain, nothing reorderable) when None or empty.
    """
    if plan is None or not getattr(plan, "buckets", ()):
        return {
            "overlap_frac_scheduled": 0.0,
            "num_buckets": 0,
            "hideable_wire_bytes": 0,
            "total_wire_bytes": 0,
            "grad_accum_steps": int(grad_accum_steps),
            "per_bucket": [],
        }
    buckets = list(plan.buckets)
    total = float(sum(b.wire_bytes for b in buckets))
    exposed = float(buckets[-1].wire_bytes)
    frac = 0.0 if total <= 0 else max(0.0, 1.0 - exposed / total)
    per_bucket = []
    t_us = 0.0
    for k, b in enumerate(buckets):
        hideable = k < len(buckets) - 1
        # modeled issue timeline: unit time per bucket, byte-proportional
        # span — a schedule visualization, not a latency prediction
        dur_us = max(1.0, b.wire_bytes / 1e3)
        per_bucket.append({
            "scope": f"wire_bucket{b.index}",
            "kind": b.kind,
            "wire_bytes": int(b.wire_bytes),
            "elements": int(b.elements),
            "num_leaves": len(b.leaves),
            "hideable": hideable,
        })
        if trace is not None:
            try:
                trace.add_complete(
                    f"wire_bucket{b.index}/issue", ts_us=t_us,
                    dur_us=dur_us, pid=0,
                    args={
                        "kind": b.kind,
                        "wire_bytes": int(b.wire_bytes),
                        "hideable": hideable,
                    },
                )
            except Exception:  # trace writer closed mid-run: estimate wins
                trace = None
        t_us += dur_us
    return {
        "overlap_frac_scheduled": round(frac, 4),
        "num_buckets": len(buckets),
        "hideable_wire_bytes": int(total - exposed),
        "total_wire_bytes": int(total),
        "grad_accum_steps": int(grad_accum_steps),
        "per_bucket": per_bucket,
    }
