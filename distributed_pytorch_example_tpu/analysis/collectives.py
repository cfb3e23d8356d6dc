"""Static collective auditor: compiled-HLO comm budgets per mesh config.

EQuARX (arxiv 2506.17615) and cross-replica sharding (arxiv 2004.13336)
both locate distributed-training cost in the SHAPE and BYTE VOLUME of the
collectives XLA emits — which is exactly what silent sharding regressions
change without failing a single numeric test (an accidentally replicated
weight turns into an all-gather; a widened layout doubles all-reduce
bytes). This module pins that surface statically:

1. lower + compile the jitted train step of a dryrun mesh config
   (``__graft_entry__.build_dryrun_case``) on the fake CPU mesh — no step
   is executed;
2. parse ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
   ``all-to-all`` / ``collective-permute`` out of the compiled
   (post-SPMD-partitioning) HLO with their result shapes;
3. reduce to ``{kind: {count, bytes}}`` and compare against the committed
   budgets in ``analysis/comm_budgets.json`` — any count increase, or a
   byte increase beyond tolerance, is a violation.

Byte volume is the collective's RESULT buffer size — a deliberate,
consistent proxy (for all-gather it is the gathered size, for
reduce-scatter the scattered size); the gate cares about deltas, not an
exact wire-byte model. ``-start``/``-done`` async pairs count once.

graft-wire makes the machinery compression-aware: ``parse_collective_
dtypes`` breaks the same proxy down per payload dtype, and wire-
compressed configs carry a ``wire-int8-step`` signature whose gate
requires an ``s8`` collective payload plus the analytic >=3x ratio from
``parallel/wire.py grad_wire_report`` (the result-buffer proxy alone
cannot express the wire win: an int8 all-to-all's RESULT is n bytes
while a tiled fp32 reduce-scatter's is n/D*4 — larger, though the wire
moves ~4x less).
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Dict, List, Optional, Tuple

from distributed_pytorch_example_tpu.analysis.findings import Finding

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

DEFAULT_BYTE_TOLERANCE = 0.05

DEFAULT_BUDGETS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "comm_budgets.json"
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%name = <shape> <op>(...)` — shape is a single typed array or a
# parenthesized tuple of them (no nested parens in HLO shape syntax)
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(\([^)]*\)|[^\s(]+)\s+"
    r"([a-z][a-z0-9-]*)\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO result shape string (array or tuple)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue  # token[], opaque[]: not data volume
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """``{kind: {count, bytes}}`` over a compiled HLO module's text."""
    out: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line)
        if m is None:
            continue
        shape_str, op = m.groups()
        if op.endswith("-done"):
            continue  # counted at the matching -start
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op not in COLLECTIVE_KINDS:
            continue
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(shape_str)
    return out


def parse_collective_dtypes(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """``{kind: {dtype: bytes}}`` — the collective mix broken down by
    payload dtype. This is what makes the budget machinery
    compression-aware: a wire-compressed config must show its gradient
    bytes moving as ``s8`` (+ ``bf16`` scales); an all-f32 breakdown on
    such a config is the silent-fallback failure the ``wire-int8-step``
    signature gates on. Same result-buffer byte proxy as
    ``parse_collectives``; ``-start``/``-done`` pairs count once.
    """
    out: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line)
        if m is None:
            continue
        shape_str, op = m.groups()
        if op.endswith("-done"):
            continue
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op not in COLLECTIVE_KINDS:
            continue
        rec = out.setdefault(op, {})
        for dtype, dims in _SHAPE_RE.findall(shape_str):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            rec[dtype] = rec.get(dtype, 0) + n * _DTYPE_BYTES[dtype]
    return out


# Schedule-implementation markers: jax.named_scope names that the 1F1B
# backward modes stamp into op metadata (parallel/pipeline.py). They
# survive into the compiled module's text, so the budget file can pin a
# config to the backward mode it claims to exercise.
SCHEDULE_MARKERS = ("1f1b_stash_apply", "1f1b_recompute_apply")

# Serving-implementation markers, same mechanism: the paged decode
# attention dispatch (models/transformer.py ``_paged_step``) stamps
# ``paged_decode_fused`` so the serve/decode budget entry can pin the
# fused-dispatch path (vs silently re-materializing the gathered cache).
SERVE_MARKERS = ("paged_decode_fused",)


def parse_markers(hlo_text: str) -> Dict[str, bool]:
    """Presence of each schedule/serve marker name in a compiled module."""
    return {m: m in hlo_text for m in SCHEDULE_MARKERS + SERVE_MARKERS}


def compile_case(case) -> Tuple[object, object]:
    """(lowered, compiled) for a DryrunCase's train step — never executed.

    Mirrors ``__graft_entry__.dryrun_multichip``'s init/step sequence
    exactly (init on the first batch, step args from the second) so the
    audited program IS the dryrun program, then stops at ``.compile()``.
    """
    with case.mesh:
        case.trainer.init(next(iter(case.loader))["tokens"])
        batch = next(iter(case.loader))
        lowered = case.trainer.train_step.lower(case.trainer.state, batch)
        compiled = lowered.compile()
    return lowered, compiled


def collective_record(case, compiled) -> Dict[str, object]:
    """One budget-file entry for a compiled case."""
    text = compiled.as_text()
    record = {
        "mesh": {k: int(v) for k, v in dict(case.mesh.shape).items()},
        "global_batch": int(case.global_batch),
        "collectives": parse_collectives(text),
    }
    parts = case.name.split("+")
    if "zero1" in parts:
        # structural contract, stronger than count/byte deltas: the gate
        # additionally requires RS+AG to be PRESENT (see compare_budgets)
        record["signature"] = "zero1-dp-step"
    if "wire-int8" in parts:
        # wire compression replaces the zero1 signature (the quantized
        # reduce-scatter compiles to all-to-all, so RS-presence would
        # fail by design): the gate instead requires an s8 collective
        # payload + the analytic >=3x wire ratio (see compare_budgets)
        record["signature"] = "wire-int8-step"
        record["dtypes"] = parse_collective_dtypes(text)
        if getattr(case.trainer, "wire_report", None):
            record["wire"] = dict(case.trainer.wire_report)
    markers = parse_markers(text)
    if "stash1f1b" in parts:
        # pin the no-recompute config to its stash marker: a silent
        # fallback to the replay backward stays under every byte budget
        # (it REMOVES nothing) and only the signature can catch it
        record["signature"] = "1f1b-stash"
    if any(markers.values()):
        record["markers"] = markers
    return record


def compare_budgets(
    committed: Dict[str, Dict[str, int]],
    measured: Dict[str, Dict[str, int]],
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
    config: Optional[str] = None,
    signature: Optional[str] = None,
    markers: Optional[Dict[str, bool]] = None,
    dtypes: Optional[Dict[str, Dict[str, int]]] = None,
    wire: Optional[Dict[str, object]] = None,
) -> Tuple[List[Finding], List[str]]:
    """(violations, notes) of a measured collective set vs its budget.

    Count increases and >tolerance byte increases are violations (a new
    collective kind is both). Decreases are improvement notes — commit a
    budget refresh (``scripts/graft_lint.py --write-budgets``) to ratchet
    them in.

    ``signature`` enforces a STRUCTURAL contract on top of the deltas.
    ``"zero1-dp-step"`` (a ZeRO-1 config, Xu et al. arxiv 2004.13336):
    gradient sync must stay reduce-scatter → all-gather; both kinds must
    be present, whatever their counts did. Count/byte ratchets alone
    cannot catch the failure mode where the whole decomposition collapses
    back to all-reduce + full update (e.g. the optimizer state silently
    re-replicated) while staying under a stale budget.
    ``"1f1b-stash"`` (the no-recompute 1F1B config): the compiled step's
    op metadata must carry the ``1f1b_stash_apply`` named-scope marker
    and must NOT carry ``1f1b_recompute_apply`` (``markers`` — see
    ``parse_markers``). A silent fallback to the replay backward changes
    no collective counts at all, so only this marker check can catch it.
    ``"wire-int8-step"`` (a wire-compressed config, parallel/wire.py):
    the compiled HLO must move gradient bytes as int8 — some collective
    payload in ``dtypes`` must be ``s8`` — and ``wire`` (the analytic
    ``grad_wire_report``) must show the >=3x compression ratio, with the
    ZeRO-1 re-replication all-gather still present. A config that
    silently falls back to fp32 payloads (WireConfig lost between the
    partitioner and the step, or every leaf under ``min_size``) changes
    nothing a count/byte ratchet can see — only this signature fails.
    """
    violations: List[Finding] = []
    notes: List[str] = []
    if signature == "wire-int8-step":
        s8_bytes = sum(
            rec.get("s8", 0) for rec in (dtypes or {}).values()
        )
        if s8_bytes == 0:
            violations.append(Finding(
                rule="comm-wire-signature",
                where="s8-payload",
                message=(
                    "wire-compressed config compiled with NO s8 "
                    "collective payload: the gradient sync silently fell "
                    "back to full-precision traffic (WireConfig not "
                    "reaching train/step.py's sync dispatch, or "
                    "compress='none' where 'int8-block' was committed)"
                ),
                config=config,
            ))
        if measured.get("all-gather", {}).get("count", 0) == 0:
            violations.append(Finding(
                rule="comm-wire-signature",
                where="all-gather",
                message=(
                    "wire-compressed ZeRO-1 config compiled with NO "
                    "all-gather: the param re-replication disappeared — "
                    "the compression must shrink the gradient sync, not "
                    "drop the weight-update gather"
                ),
                config=config,
            ))
        ratio = float((wire or {}).get("wire_compression_ratio", 0.0) or 0.0)
        if ratio < 3.0:
            violations.append(Finding(
                rule="comm-wire-signature",
                where="wire_compression_ratio",
                message=(
                    f"wire-compressed config reports grad-traffic "
                    f"compression {ratio:.2f}x < 3x (parallel/wire.py "
                    f"grad_wire_report): the int8-block payload must cut "
                    f"gradient wire bytes at least 3x — check min_size / "
                    f"block_size and the partitioner's WireConfig"
                ),
                config=config,
            ))
    if signature == "1f1b-stash":
        mk = markers or {}
        if not mk.get("1f1b_stash_apply", False):
            violations.append(Finding(
                rule="comm-1f1b-stash-signature",
                where="1f1b_stash_apply",
                message=(
                    "no-recompute 1F1B config compiled WITHOUT the "
                    "stash-apply marker: the backward is not applying "
                    "stashed vjp residuals (pipe_recompute=False lost on "
                    "the way to one_f_one_b, or the named scope was "
                    "renamed — keep parallel/pipeline.py and "
                    "analysis/collectives.py SCHEDULE_MARKERS in sync)"
                ),
                config=config,
            ))
        if mk.get("1f1b_recompute_apply", False):
            violations.append(Finding(
                rule="comm-1f1b-stash-signature",
                where="1f1b_recompute_apply",
                message=(
                    "no-recompute 1F1B config compiled WITH the replay "
                    "backward marker: the schedule silently fell back to "
                    "stage recompute (~4 forward-units per cycle instead "
                    "of ~3) — no byte budget moves, only this signature "
                    "catches it"
                ),
                config=config,
            ))
    if signature == "paged-decode-fused":
        mk = markers or {}
        if not mk.get("paged_decode_fused", False):
            violations.append(Finding(
                rule="comm-paged-decode-signature",
                where="paged_decode_fused",
                message=(
                    "serve/decode program compiled WITHOUT the fused "
                    "paged-decode marker: the decode step is not routing "
                    "attention through the paged dispatch "
                    "(models/transformer.py _paged_step lost the "
                    "named scope, or the serve program stopped using the "
                    "paged cache) — no byte budget moves when the gather "
                    "path re-materializes the cache, only this signature "
                    "catches it; keep the scope name and "
                    "analysis/collectives.py SERVE_MARKERS in sync"
                ),
                config=config,
            ))
    if signature == "zero1-dp-step":
        for kind in ("reduce-scatter", "all-gather"):
            if measured.get(kind, {}).get("count", 0) == 0:
                violations.append(Finding(
                    rule="comm-zero1-signature",
                    where=kind,
                    message=(
                        f"ZeRO-1 config compiled with NO {kind}: the "
                        f"gradient sync must stay reduce-scatter + "
                        f"all-gather (the sharded weight update of Xu et "
                        f"al., arxiv 2004.13336). Its disappearance "
                        f"usually means the optimizer state was silently "
                        f"re-replicated (check dp_shard_opt_state and the "
                        f"step's opt-state sharding constraint) and every "
                        f"chip is back to the full-moment update."
                    ),
                    config=config,
                ))
    for kind in sorted(set(committed) | set(measured)):
        c = committed.get(kind, {"count": 0, "bytes": 0})
        m = measured.get(kind, {"count": 0, "bytes": 0})
        if m["count"] > c["count"]:
            extra = ""
            if signature == "zero1-dp-step" and kind == "all-reduce":
                extra = (
                    " — on a ZeRO-1 config extra all-reduces usually mean "
                    "part of the gradient tree fell off the "
                    "reduce-scatter path (overlay floor, indivisible "
                    "dims) or the opt state re-replicated"
                )
            violations.append(Finding(
                rule="comm-budget-count",
                where=kind,
                message=(
                    f"{kind} count {c['count']} -> {m['count']} "
                    f"(+{m['count'] - c['count']}){extra}"
                ),
                config=config,
            ))
        elif m["count"] < c["count"]:
            notes.append(
                f"{config or ''} {kind}: count {c['count']} -> {m['count']} "
                f"(improvement; refresh budgets to ratchet)"
            )
        budget = c["bytes"] * (1.0 + byte_tolerance)
        if m["bytes"] > budget:
            violations.append(Finding(
                rule="comm-budget-bytes",
                where=kind,
                message=(
                    f"{kind} bytes {c['bytes']} -> {m['bytes']} "
                    f"(+{_pct(c['bytes'], m['bytes'])}, tolerance "
                    f"{byte_tolerance:.0%})"
                ),
                config=config,
            ))
        elif m["bytes"] < c["bytes"] * (1.0 - byte_tolerance):
            notes.append(
                f"{config or ''} {kind}: bytes {c['bytes']} -> {m['bytes']} "
                f"(improvement; refresh budgets to ratchet)"
            )
    return violations, notes


def _pct(old: int, new: int) -> str:
    if old == 0:
        return "new"
    return f"{(new - old) / old:+.1%}"


def load_budgets(path: str = DEFAULT_BUDGETS_PATH) -> Dict[str, object]:
    with open(path) as f:
        return json.load(f)


def write_budgets(
    path: str,
    records: Dict[str, Dict[str, object]],
    n_devices: int,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
) -> None:
    """Commit a fresh budget file (sorted keys: reviewable diffs)."""
    import jax

    payload = {
        "_meta": {
            "n_devices": n_devices,
            "jax": jax.__version__,
            "byte_tolerance": byte_tolerance,
            "tool": "scripts/graft_lint.py --write-budgets",
        },
        "configs": {k: records[k] for k in sorted(records)},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def jax_version_skew(budgets: Dict[str, object]) -> Optional[str]:
    """The committed jax version when it differs from the runtime's.

    Collective counts are only comparable against budgets generated by
    the same jax/XLA — under skew the gate degrades to warnings (the
    alternative is a hard failure on every toolchain bump).
    """
    import jax

    committed = budgets.get("_meta", {}).get("jax")
    if committed is not None and committed != jax.__version__:
        return str(committed)
    return None


def budget_staleness(
    budgets_path: str = DEFAULT_BUDGETS_PATH,
    repo_root: Optional[str] = None,
) -> Optional[str]:
    """Human note when sources are newer than the committed budget file.

    mtime-based — a hint for the graft-lint report, not a gate: a
    source edit that changes no collective legitimately leaves budgets
    untouched.
    """
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
    if not os.path.exists(budgets_path):
        return f"no committed budgets at {budgets_path}"
    budget_mtime = os.path.getmtime(budgets_path)
    newest: Tuple[float, str] = (-math.inf, "")
    pkg = os.path.join(repo_root, "distributed_pytorch_example_tpu")
    candidates = [os.path.join(repo_root, "__graft_entry__.py")]
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        candidates.extend(
            os.path.join(dirpath, f) for f in filenames if f.endswith(".py")
        )
    for path in candidates:
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            continue
        if mtime > newest[0]:
            newest = (mtime, path)
    if newest[0] > budget_mtime:
        rel = os.path.relpath(newest[1], repo_root)
        return (
            f"comm_budgets.json is older than {rel} — if the change "
            f"touched sharding/collectives, refresh with "
            f"`python scripts/graft_lint.py --write-budgets`"
        )
    return None
