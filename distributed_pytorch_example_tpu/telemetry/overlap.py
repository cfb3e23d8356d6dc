"""Measured comm/compute overlap accounting (graft-lens).

The wire collectives run INSIDE jitted shard_map manual regions
(``parallel/wire.py``, ``ops/pallas/collectives.py``), so host-side
timing around the call sites can never see how much of the collective
time the XLA scheduler actually hid behind compute. The only ground
truth is the profiler: capture a short ``jax.profiler`` trace over a few
steps, convert the xplane protos to per-op HLO self times (the
``scripts/profile_step.py`` recipe, via TensorFlow's
``_pywrap_profiler_plugin`` — import guarded, TF is heavy and optional),
split them into collective vs compute by HLO op category, and compare
against the host-measured wall time of the same window:

    overlap_frac = clamp((compute + collective - wall) / collective, 0, 1)

If nothing overlapped, wall ~= compute + collective and the fraction is
0; if every collective byte moved behind compute, wall ~= compute and
the fraction is 1. The wire/pallas dispatch sites carry ``named_scope``
markers (``wire_psum_scatter`` etc.) so the per-op attribution also
rolls up per dispatch boundary — ``by_scope`` in the result.

Everything degrades to ``None``: no TF, no xplane converter, an empty
trace, or a zero-collective program all report "unmeasured", never
raise. The gate ROADMAP 5(c) consumes ``overlap_frac`` from bench.py's
JSON line.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Callable, Dict, Optional

# HLO op categories the profiler labels communication with (hlo_stats
# "HLO op category" column values across jax/XLA versions)
COLLECTIVE_CATEGORY_RE = re.compile(
    r"all[- ]?reduce|all[- ]?gather|all[- ]?to[- ]?all|reduce[- ]?scatter"
    r"|collective|permute|send|recv",
    re.IGNORECASE,
)

# the graft-wire/pallas dispatch-boundary named scopes (parallel/wire.py,
# ops/pallas/collectives.py) — per-boundary attribution keys.
# "wire_bucket" matches the per-bucket scopes of the fused overlap path
# (sync_grads stamps wire_bucket0, wire_bucket1, ...); the regex below
# rolls those up per bucket index so overlap_frac attributes buckets.
WIRE_SCOPES = (
    "wire_psum_scatter", "wire_all_gather", "wire_psum",
    "wire_replicate_params", "ring_all_gather", "ring_reduce_scatter",
    "wire_bucket",
)

_BUCKET_SCOPE_RE = re.compile(r"wire_bucket\d+")


def is_collective(category: str, op_name: str = "") -> bool:
    """Whether an hlo_stats row is communication, by category first and
    the framework op name's named scopes as a fallback."""
    if category and COLLECTIVE_CATEGORY_RE.search(category):
        return True
    return any(scope in op_name for scope in WIRE_SCOPES)


def overlap_frac_from_times(
    wall_us: float, collective_us: float, compute_us: float
) -> Optional[float]:
    """The fraction of collective time hidden behind compute; None when
    there was no collective time to hide."""
    if collective_us <= 0:
        return None
    hidden = compute_us + collective_us - wall_us
    return max(0.0, min(1.0, hidden / collective_us))


def _hlo_stats_rows(trace_dir: str):
    """(framework op name, category, self time us) rows from the xplane
    protos under ``trace_dir`` — the profile_step.py pywrap recipe."""
    paths = glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")
    )
    if not paths:
        return None
    # TF's xplane->tools converter; the tensorboard-plugin wrapper has a
    # protobuf clash in this image, the pywrap entry point works
    from tensorflow.python.profiler.internal import (  # noqa: PLC0415
        _pywrap_profiler_plugin as pywrap,
    )

    data, _ = pywrap.xspace_to_tools_data(paths, "hlo_stats", {})
    d = json.loads(data)
    labels = [
        c["label"] if isinstance(c, dict) else str(c) for c in d["cols"]
    ]
    idx = {name: labels.index(name) for name in (
        "Framework op name", "HLO op category", "Total self time (us)",
    ) if name in labels}
    if len(idx) < 3:
        return None
    rows = []
    for row in d.get("rows", []):
        cells = row.get("c", row) if isinstance(row, dict) else row
        vals = [
            c.get("v") if isinstance(c, dict) else c for c in cells
        ]
        rows.append((
            str(vals[idx["Framework op name"]] or ""),
            str(vals[idx["HLO op category"]] or ""),
            float(vals[idx["Total self time (us)"]] or 0.0),
        ))
    return rows


def split_trace_times(trace_dir: str) -> Optional[Dict[str, float]]:
    """Aggregate a captured trace into collective vs compute self time
    (us, totals over the whole traced window), plus per-wire-scope
    attribution. None when the converter or trace is unavailable."""
    try:
        rows = _hlo_stats_rows(trace_dir)
    except Exception:  # TF missing / converter drift: degrade, don't raise
        return None
    if not rows:
        return None
    collective_us = compute_us = 0.0
    by_scope: Dict[str, float] = {}
    for op_name, category, self_us in rows:
        if is_collective(category, op_name):
            collective_us += self_us
            m = _BUCKET_SCOPE_RE.search(op_name)
            if m:  # per-bucket attribution: wire_bucket<k> keys
                key = m.group(0)
                by_scope[key] = by_scope.get(key, 0.0) + self_us
                continue
            for scope in WIRE_SCOPES:
                if scope in op_name:
                    by_scope[scope] = by_scope.get(scope, 0.0) + self_us
                    break
        else:
            compute_us += self_us
    return {
        "collective_us": collective_us,
        "compute_us": compute_us,
        "by_scope": by_scope,
    }


def measure_overlap(
    run_steps: Callable[[int], None],
    trace_dir: str,
    steps: int = 2,
    clock: Callable[[], float] = time.perf_counter,
) -> Optional[dict]:
    """Capture an XLA trace around ``run_steps(steps)`` and compute the
    measured per-step overlap accounting.

    ``run_steps`` must execute exactly ``steps`` already-compiled,
    fully-fenced steps (fetch a scalar: a device->host transfer of a
    result is an unambiguous fence). Returns ``{overlap_frac, wall_us_per_step,
    collective_us_per_step, compute_us_per_step, by_scope, steps}`` or
    None when the profiler/converter is unavailable.
    """
    import jax  # noqa: PLC0415 - keep module importable backend-free

    try:
        jax.profiler.start_trace(trace_dir)
    except Exception:
        return None
    try:
        t0 = clock()
        run_steps(steps)
        wall_s = clock() - t0
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            return None
    split = split_trace_times(trace_dir)
    if split is None:
        return None
    wall_us = wall_s * 1e6
    frac = overlap_frac_from_times(
        wall_us, split["collective_us"], split["compute_us"]
    )
    return {
        "overlap_frac": frac,
        "steps": int(steps),
        "wall_us_per_step": wall_us / max(steps, 1),
        "collective_us_per_step": split["collective_us"] / max(steps, 1),
        "compute_us_per_step": split["compute_us"] / max(steps, 1),
        "by_scope": {
            k: v / max(steps, 1) for k, v in split["by_scope"].items()
        },
    }


# -- scheduler-level overlap (static, backend-free) -------------------------


def scheduled_overlap(plan, grad_accum_steps: int = 1,
                      trace=None) -> Optional[dict]:
    """Scheduler-level overlap estimate from a static wire BucketPlan.

    The HLO-profile ``overlap_frac`` above needs a device plane, which a
    CPU trace does not have — on the fake 8-chip mesh it degrades to
    ``None`` and CI cannot gate issue ORDER at all. This estimate is the
    deterministic complement: the fused bucket schedule
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
    ZeRO-1+wire configs); the HLO-profile number stays authoritative
    whenever a TPU plane exists.

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
