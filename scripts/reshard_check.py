#!/usr/bin/env python3
"""Offline checkpoint doctor for mesh-shape-agnostic resume (graft-elastic).

Inspects one checkpoint — either format — WITHOUT building a mesh or
touching devices, and prints ONE JSON line:

- the format-3 ``mesh_manifest`` stamp (mesh axes, format, epoch);
- the graft-intake ``loader_manifest`` stamp when present (input-plane
  cursor, sampler seed, quarantined-shard set) — what resume will re-arm;
- per-artifact seal status (gathered payload / manifest + every shard
  file): ``sealed`` (carries the CRC envelope) and ``intact`` (envelope
  verifies);
- when ``--target`` names a mesh shape: whether the checkpoint is
  resumable onto it and the per-leaf reshard plan — ``keep`` (every
  sharded axis keeps its size), ``replicate`` (unsharded leaf),
  ``repartition-zero1`` (ZeRO-1 opt-state leaf scattered over a resized
  ``data`` axis), ``rebalance-pipe`` (leaf stacked over a resized
  ``pipe`` axis), or ``reshard`` (any other re-slice);
- a graft-swap publish channel (``robustness/publish.py``) is
  auto-detected and reported as format ``publish-channel``: the
  ``channel`` block is ``PublishChannel.state()`` verbatim (pointer
  integrity, per-version seal/intact status, the version a fleet would
  actually serve), and the manifest/loader/target checks run against
  that servable version's payload.

Usage:
  python scripts/reshard_check.py <ckpt-or-channel> [--target data=4,...]

Exit code 0 iff every artifact is intact (for a publish channel: the
pointed version itself is servable — a degraded channel limping on an
intact ancestor exits 1) and, with ``--target``, the checkpoint is
resumable onto it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# no device work happens here: hold jax (flax pulls it in) to the CPU so a
# host-side checkpoint check never takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"

from flax import serialization  # noqa: E402

from distributed_pytorch_example_tpu.data import intake  # noqa: E402
from distributed_pytorch_example_tpu.robustness import elastic  # noqa: E402
from distributed_pytorch_example_tpu.robustness import publish  # noqa: E402
from distributed_pytorch_example_tpu.robustness.integrity import (  # noqa: E402
    is_sealed,
    unseal,
)

_OPT_STATE_RE = re.compile(r"(^|/)opt_state(/|$)")


def _inspect_artifact(path: str) -> dict:
    """Seal/intact status plus the verified body (None when corrupt)."""
    name = os.path.basename(path)
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as err:
        return {"name": name, "sealed": False, "intact": False,
                "error": str(err), "body": None}
    sealed = is_sealed(data)
    try:
        body = unseal(data, source=path)
        return {"name": name, "sealed": sealed, "intact": True, "body": body}
    except Exception as err:
        return {"name": name, "sealed": sealed, "intact": False,
                "error": str(err), "body": None}


def parse_target(text: str) -> dict:
    """``data=4,tensor=2`` → {"data": 4, "tensor": 2}."""
    axes = {}
    for part in text.split(","):
        if not part.strip():
            continue
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def leaf_plan(
    path: str, entries, stamped: dict, target: dict
) -> str:
    """Reshard action for one leaf's stamped PartitionSpec entries."""
    sharded_axes = [a for e in entries for a in elastic._entry_axes(e)]
    if not sharded_axes:
        return "replicate"
    resized = [
        a for a in sharded_axes
        if int(target.get(a, 1)) != int(stamped.get(a, 1))
    ]
    if not resized:
        return "keep"
    if "data" in resized and _OPT_STATE_RE.search(path):
        return "repartition-zero1"
    if "pipe" in resized:
        return "rebalance-pipe"
    return "reshard"


def inspect_checkpoint(path: str, target: dict | None) -> dict:
    from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib

    report: dict = {
        "tool": "reshard_check",
        "path": path,
        "format": None,
        "ok": False,
        "manifest": None,
        "loader_manifest": None,
        "artifacts": [],
        "target": target or None,
        "resumable": None,
        "reshard_plan": None,
    }
    if not os.path.exists(path):
        report["error"] = "no such checkpoint"
        return report

    stamp = None
    version = None
    channel_ok = None
    if publish.is_publish_channel(path):
        report["format"] = "publish-channel"
        channel = publish.PublishChannel(path)
        state = channel.state()
        report["channel"] = state
        version = state["latest_intact"]
        artifacts = [
            {
                "name": f"{v['version']}/{publish.ARTIFACT_NAME}",
                "sealed": v["sealed"], "intact": v["intact"],
                **({"error": v["error"]} if v.get("error") else {}),
                "body": None,
            }
            for v in state["versions"]
        ]
        blob = None
        if version is not None:
            try:
                blob = serialization.msgpack_restore(channel.read(version))
            except Exception as err:  # CRC-intact but not a checkpoint
                report["error"] = (
                    f"version {version} payload is not msgpack: {err}"
                )
        # channel health is the POINTED version being servable — a fleet
        # limping on an intact ancestor (corrupt head) is degraded even
        # though every remaining artifact verifies
        channel_ok = bool(state["ok"])
    elif ckpt_lib._is_sharded(path):
        report["format"] = "sharded"
        step_dir = ckpt_lib._pointed_version_dir(path)
        if step_dir is None or not os.path.isdir(step_dir):
            report["error"] = "pointer names no committed version dir"
            return report
        version = os.path.basename(step_dir)
        manifest_art = _inspect_artifact(
            os.path.join(step_dir, "manifest.msgpack")
        )
        artifacts = [manifest_art]
        blob = None
        if manifest_art["body"] is not None:
            blob = serialization.msgpack_restore(manifest_art["body"])
        nproc = int(blob.get("nproc", 0)) if isinstance(blob, dict) else 0
        for i in range(nproc):
            artifacts.append(_inspect_artifact(
                os.path.join(step_dir, f"shard_{i:05d}.msgpack")
            ))
    else:
        report["format"] = "gathered"
        art = _inspect_artifact(path)
        artifacts = [art]
        blob = (
            serialization.msgpack_restore(art["body"])
            if art["body"] is not None else None
        )

    report["artifacts"] = [
        {k: v for k, v in a.items() if k != "body"} for a in artifacts
    ]
    intact = (
        channel_ok if channel_ok is not None
        else all(a["intact"] for a in artifacts)
    ) and blob is not None
    if isinstance(blob, dict):
        raw_stamp = blob.get(elastic.MANIFEST_KEY)
        stamp = raw_stamp if isinstance(raw_stamp, dict) else None
        report["manifest"] = {
            "format": (
                int(stamp["format"]) if stamp else 2 if artifacts[0]["sealed"]
                else 1
            ),
            "axes": dict(stamp["axes"]) if stamp else None,
            "epoch": int(blob.get("epoch", -1)),
            "version": version,
        }
        # graft-intake loader_manifest (rides in the checkpoint's extra
        # dict): the exact input-plane cursor and quarantine set resume
        # will re-arm — unstamped (pre-intake) checkpoints report null
        extra = blob.get("extra")
        lman = (
            extra.get(intake.LOADER_MANIFEST_KEY)
            if isinstance(extra, dict) else None
        )
        if isinstance(lman, dict):
            report["loader_manifest"] = {
                "epoch": int(lman.get("epoch", -1)),
                "batch_in_epoch": int(lman.get("batch_in_epoch", 0)),
                "seed": lman.get("seed"),
                "quarantine": sorted(
                    int(s) for s in lman.get("quarantine", ())
                ),
                "quarantine_digest": lman.get("quarantine_digest"),
            }

    if target:
        if stamp is None:
            # an unstamped (pre-format-3) checkpoint only resumes on the
            # mesh it was saved under, which is unknowable offline
            report["resumable"] = None
        else:
            report["resumable"] = bool(intact)
            report["reshard_plan"] = {
                p: {
                    "spec": entries,
                    "action": leaf_plan(
                        p, entries, stamp.get("axes", {}), target
                    ),
                }
                for p, entries in sorted(stamp.get("specs", {}).items())
            }
    report["ok"] = bool(intact and report["resumable"] is not False)
    return report


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("ckpt", help="checkpoint path (pointer or file)")
    parser.add_argument(
        "--target", default=None,
        help="target mesh shape, e.g. data=4,tensor=2",
    )
    args = parser.parse_args()
    target = parse_target(args.target) if args.target else None
    report = inspect_checkpoint(args.ckpt, target)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
