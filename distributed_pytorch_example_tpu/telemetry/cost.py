"""Compile-time cost registry: XLA cost/memory analysis as run telemetry.

Every train/eval-step compile records what the compiler itself knows about
the program — per-device FLOPs, bytes accessed, argument/output/temp sizes
(an HBM-residency estimate), and the collective mix parsed from the
compiled HLO (``analysis/collectives.py``). Analytical MFU and HBM headroom
then come for free with each measured step time, instead of the offline
one-off analysis the r3/r5 perf rounds had to reconstruct by hand.

Everything is best-effort: backends that cannot answer an analysis query
(or an aborted AOT compile) degrade to ``None`` fields, never an error in
the training path.
"""

from __future__ import annotations

from typing import Dict, Optional

# peak dense bf16 FLOP/s per chip by PJRT device_kind substring (read by
# scope.py's `mfu_analytic` and chip_smoke.py). A v5e reports device_kind "TPU v5 lite".
PEAK_BF16 = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


def peak_bf16_flops(device) -> Optional[float]:
    """Peak dense bf16 FLOP/s for one chip; None off-TPU (no peak to
    judge against). An unknown TPU kind raises — returning None there
    would make MFU silently vanish from every record."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAK_BF16.items():
        if key in kind:
            return peak
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"unknown TPU device_kind {device.device_kind!r}: add its peak "
            f"bf16 FLOP/s to telemetry/cost.py PEAK_BF16"
        )
    return None


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        return dict(analysis)
    except Exception:
        return {}


def _memory_analysis(compiled) -> Dict[str, int]:
    out: Dict[str, int] = {}
    try:
        stats = compiled.memory_analysis()
    except Exception:
        return out
    for attr, key in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "code_bytes"),
    ):
        val = getattr(stats, attr, None)
        if val is not None:
            out[key] = int(val)
    return out


def compiled_cost_record(compiled, device=None) -> Dict[str, object]:
    """One compile's cost/memory/collective record (all fields best-effort).

    ``hbm_peak_bytes`` is the residency estimate args + outputs + temps −
    aliased (donated buffers counted once) — the same accounting
    ``scripts/pipeline_memory.py`` reads off ``memory_analysis()``.
    """
    cost = _cost_analysis(compiled)
    mem = _memory_analysis(compiled)
    flops = cost.get("flops")
    record: Dict[str, object] = {
        "flops_per_step_per_device": float(flops) if flops else None,
        "bytes_accessed": (
            float(cost["bytes accessed"])
            if "bytes accessed" in cost else None
        ),
        **mem,
    }
    if {"argument_bytes", "output_bytes", "temp_bytes"} <= mem.keys():
        record["hbm_peak_bytes"] = (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem.get("alias_bytes", 0)
        )
    else:
        record["hbm_peak_bytes"] = None
    try:
        from distributed_pytorch_example_tpu.analysis.collectives import (
            parse_collectives,
        )

        record["collectives"] = parse_collectives(compiled.as_text())
    except Exception:
        record["collectives"] = None
    if device is not None:
        record["device_kind"] = getattr(device, "device_kind", None)
        record["peak_bf16_flops"] = peak_bf16_flops(device)
    return record


def measured_hbm_peak(compiled) -> Optional[int]:
    """The compiler's own per-chip residency estimate for one program —
    args + outputs + temps − aliased — or None when the backend cannot
    answer. This is the measurement ``analysis/envelope.py`` cross-
    validates its static predictions against."""
    mem = _memory_analysis(compiled)
    if {"argument_bytes", "output_bytes", "temp_bytes"} <= mem.keys():
        return (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem.get("alias_bytes", 0)
        )
    return None


class CostRegistry:
    """Per-run registry of compile cost records, keyed by tag.

    Tags are the Trainer's program names ("train_step", "eval_step"); a tag
    recompiled for a new batch shape overwrites its record (the latest
    program is the one the loop is driving).
    """

    def __init__(self):
        self.records: Dict[str, Dict[str, object]] = {}

    def record(self, tag: str, compiled, device=None,
               extra: Optional[Dict[str, object]] = None):
        rec = compiled_cost_record(compiled, device)
        if extra:
            rec.update(extra)
        return self.register(tag, rec)

    def register(self, tag: str, rec: Dict[str, object]):
        """Take a finished record under ``tag``: what ``record`` made when
        the program was compiled, handed to a later run's registry."""
        rec["tag"] = tag
        self.records[tag] = rec
        return rec

    def get(self, tag: str) -> Optional[Dict[str, object]]:
        return self.records.get(tag)

    def export(self, path: str) -> None:
        """Dump all records as JSON (measured peaks for offline
        cross-validation against the committed static envelopes)."""
        import json

        with open(path, "w") as f:
            json.dump(self.records, f, indent=2, sort_keys=True, default=str)
            f.write("\n")

    def mfu_analytic(
        self, tag: str, step_time_ms: Optional[float]
    ) -> Optional[float]:
        """flops / (step_time * peak bf16); None when either is unknown."""
        rec = self.records.get(tag)
        if not rec or not step_time_ms:
            return None
        flops = rec.get("flops_per_step_per_device")
        peak = rec.get("peak_bf16_flops")
        if not flops or not peak:
            return None
        return float(flops) / (step_time_ms / 1000.0) / float(peak)
