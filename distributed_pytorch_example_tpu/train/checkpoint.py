"""Checkpoint save / load with best+latest policy and epoch-level resume.

Parity contract (reference train.py:178-209, 252-308; SURVEY.md §3.4):

- the on-disk checkpoint is a SINGLE-LOGICAL-VIEW of the model — the analogue
  of the reference's DDP-unwrapped state dict (train.py:181-183). Sharded
  state (FSDP/TP) restores at any other parallelism config;
- payload = {epoch, state (params + optimizer + mutable model state + rng),
  loss} — optimizer state included, matching train.py:185-190;
- host 0 writes, every host reads (train.py:253,256);
- writes are atomic (tmp + rename) so a killed job never leaves a torn
  ``latest`` checkpoint;
- resume continues AFTER the last finished epoch: the loop stamps each
  checkpoint with ``epoch + 1`` (train/loop.py, epoch-end save), so a run
  killed after epoch 2 resumes at epoch 3. This is a deliberate deviation
  from the reference, which stamps the epoch it just finished and then
  RE-RUNS it on resume (reference train.py:185,209,257 — the saved epoch is
  both "work done" and "start point", double-training one epoch). Pinned
  by tests/test_train.py::test_resume_continues_after_finished_epoch.
- STEP-level resume (beyond-reference, r5): with ``save_every_steps`` the
  loop also writes ``latest`` mid-epoch, stamped with the CURRENT epoch
  plus ``extra["batch_in_epoch"]`` (the loader cursor). On resume the
  trainer skips to that exact batch; the sampler permutation is a pure
  function of (seed, epoch) and the step rng folds ``state.rng`` with the
  restored ``state.step``, so the loss trajectory is bit-identical to the
  uninterrupted run (tests/test_step_resume.py kills a run with SIGKILL
  mid-epoch and proves it).

Two on-disk formats, both flax-msgpack (no torch, no pickle — portable and
introspectable), auto-detected on load:

- **gathered** (default; single file): sharded state is all-gathered to
  full arrays and host 0 writes one msgpack blob. Maximum portability,
  but the gather is a collective (all hosts must enter) and re-materializes
  the full model — the wrong trade at FSDP/multi-host scale.
- **sharded** (directory + pointer file): every process independently
  fetches only the addressable shards it owns (replica 0 of each) and
  writes its own shard file — NO collectives, so it is safe from the
  async background thread at any process count, and no host ever holds
  the full state. Process 0 commits the checkpoint by writing the
  manifest after all shard files land (a filesystem rendezvous, not a
  barrier) and atomically flipping a pointer file. The loader reassembles
  global leaves and re-shards onto the target mesh, so a checkpoint saved
  under one mesh shape restores under any other.

Both formats restore through ``state_shardings`` (device_put to the
TARGET layout), so gradient-sync mode flips across resume for free: a
checkpoint written replicated restores into a ZeRO-1 run (moments get
sharded over ``data`` on load) and vice versa (shards reassemble to full
leaves, then replicate) — pinned by tests/test_zero1.py round-trips.

Integrity, retention, and self-healing fallback (graft-armor, r10):

- every artifact (gathered payload, shard file, manifest) is written
  inside a CRC32 envelope (``robustness/integrity.py``), so a torn or
  bit-flipped file fails LOUDLY at read time instead of deserializing
  into a silently wrong pytree; pre-envelope files load unverified;
- keep-last-K retention (``retain``): the gathered format keeps a
  ``{path}.history/{seq}.ckpt`` trail (``latest`` is a hard link to the
  newest entry); the sharded format's GC keeps the newest ``retain``
  version dirs instead of exactly one. Mid-epoch sharded saves get a
  UNIQUE ``{epoch}.{batch}`` version (zero-padded, so lexicographic
  string order is still age order) — a crash mid-save can therefore
  never destroy the previous intact version, which older code reused
  and rmtree'd in-place;
- ``load_checkpoint`` verifies integrity and, when the newest candidate
  is torn/corrupt, walks back to the newest intact ancestor (sharded
  version dirs, then gathered history), logging exactly what was
  skipped and why. Only when NO candidate restores does it raise.
- checkpoint writes go through chaos hooks (``robustness/chaos.py``)
  so the fault matrix can inject transient ``OSError`` / mid-save
  SIGKILL deterministically; without a plan installed the hooks are
  no-ops.

Mesh-shape-agnostic resume (graft-elastic, r11): every save — both
formats — is stamped with a format-3 ``mesh_manifest`` (mesh axis
names/sizes, per-leaf PartitionSpecs, ZeRO-1 scatter dims; see
``robustness/elastic.py``). Loaders validate the stamp against the
target mesh (cross-mesh restores are logged; ``DPX_ELASTIC=1`` resume
from an UNSTAMPED pre-format-3 checkpoint raises
``MissingMeshManifestError``), the sharded loader streams reassembly
per leaf to bound host memory, and the fallback walk-back prefers
same-mesh ancestors unless elastic mode asks for newest-intact-wins.
"""

from __future__ import annotations

import os
import re
import shutil
import threading
import time
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from distributed_pytorch_example_tpu.robustness import chaos
from distributed_pytorch_example_tpu.robustness import elastic
from distributed_pytorch_example_tpu.robustness.integrity import (
    CheckpointCorruptError,
    read_verified,
    seal,
)
from distributed_pytorch_example_tpu.robustness.retry import with_retries
from distributed_pytorch_example_tpu.runtime.logging import get_logger

logger = get_logger(__name__)

BEST_NAME = "best_model.ckpt"
LATEST_NAME = "latest_model.ckpt"

# pointer-file magic marking the sharded format (a gathered checkpoint is
# raw msgpack, which can never begin with this line)
SHARDED_MAGIC = b"DPX-SHARDED-V1\n"
SHARD_WAIT_TIMEOUT_S = 600.0

# keep-last-K retention default: current + two ancestors. 1 = only the
# live checkpoint (pre-r10 behavior); 0 disables the gathered history.
DEFAULT_RETAIN = 3

_VERSION_RE = re.compile(r"\d{8}(\.\d{8})?")
_HISTORY_RE = re.compile(r"\d{8}\.ckpt")


class AsyncSaver:
    """Runs checkpoint writes on a background thread, one in flight.

    Device→host transfer plus serialization of a full train state can take
    a long time at scale (GPT-2 124M's state is already 1.5 GB). The Trainer snapshots the state ON DEVICE (cheap HBM
    copy, immune to later donation) and hands the fetch+serialize+write to
    this saver, so training continues while the checkpoint drains.

    Works at any process count for the SHARDED format (its writes are
    collective-free; the begin-of-save barrier runs on the main thread in
    ``save_checkpoint`` before submission). The GATHERED format needs a
    collective all-gather, which must not race train-step collectives from
    another thread, so it backgrounds only at ``jax.process_count() == 1``
    and is synchronous multi-host.

    Transient ``OSError``s (flaky shared filesystem) are retried with
    bounded exponential backoff INSIDE the background thread
    (``io_retries`` re-attempts); only a persistent failure is recorded.
    A recorded failure surfaces at the next ``submit()``/``wait()``, and
    the Trainer additionally polls ``check()`` once per train step so a
    broken checkpoint path fails the run near the fault, not minutes
    later at the end of ``fit``.
    """

    def __init__(self, io_retries: int = 2, retry_base_delay: float = 0.1):
        self._pending: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._io_retries = io_retries
        self._retry_base_delay = retry_base_delay
        self.io_retries_used = 0  # healed transient failures (telemetry)

    def submit(self, fn: Callable[[], None]) -> None:
        self.wait()  # one in flight; also surfaces a prior failure

        def run():
            try:
                with_retries(
                    fn,
                    attempts=self._io_retries + 1,
                    base_delay=self._retry_base_delay,
                    retry_on=(OSError,),
                    describe="async checkpoint write",
                    on_retry=self._on_retry,
                )
            except BaseException as e:  # re-raised on next check/wait
                self._error = e

        self._pending = threading.Thread(target=run, daemon=True)
        self._pending.start()

    def _on_retry(self, attempt: int, err: BaseException) -> None:
        self.io_retries_used += 1

    def check(self) -> None:
        """Non-blocking: raise if a background save already FAILED.

        Unlike ``wait()`` this never blocks on an in-flight save, so the
        Trainer can call it every step at zero cost.
        """
        if self._pending is not None and self._pending.is_alive():
            return
        self.wait()

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("async checkpoint save failed") from err


def _gather_to_host(tree: Any) -> Any:
    """Full logical (unsharded) numpy view of a possibly-sharded pytree.

    Single-host shardings are assembled locally; multi-host shardings go
    through a process_allgather collective — so this must be called by every
    process, symmetric with the reference's all-ranks-read contract.

    The device→host transfer is ONE batched ``jax.device_get`` of the whole
    tree, not a per-leaf fetch — per-leaf round trips (hundreds of leaves ×
    transfer latency) otherwise dominate checkpoint time.
    """

    def pre(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)  # typed PRNG keys → raw uint32
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return multihost_utils.process_allgather(x, tiled=True)
        return x

    return jax.device_get(jax.tree_util.tree_map(pre, tree))


def _next_history_seq(hist_dir: str) -> int:
    seqs = [
        int(n[:8]) for n in os.listdir(hist_dir) if _HISTORY_RE.fullmatch(n)
    ]
    return max(seqs, default=-1) + 1


def _gathered_history_paths(path: str) -> List[str]:
    """History entries newest-first (fallback candidates)."""
    hist_dir = f"{path}.history"
    if not os.path.isdir(hist_dir):
        return []
    names = sorted(
        (n for n in os.listdir(hist_dir) if _HISTORY_RE.fullmatch(n)),
        reverse=True,
    )
    return [os.path.join(hist_dir, n) for n in names]


def _payload_blob(
    host_state, epoch: int, loss: float, extra,
    mesh_manifest: Optional[dict] = None,
) -> bytes:
    """Sealed gathered-payload blob — shared by the latest/best file
    write and the graft-swap publish channel, so a published version is
    byte-compatible with a gathered checkpoint restore."""
    payload = {
        "epoch": epoch,
        "loss": float(loss),
        "state": serialization.to_state_dict(host_state),
        "extra": extra or {},
    }
    if mesh_manifest is not None:
        # format-3 mesh stamp (graft-elastic): what topology this state
        # was sharded under at save time — validate_resume reads it back
        payload[elastic.MANIFEST_KEY] = mesh_manifest
    return seal(serialization.msgpack_serialize(payload))


def _write_payload(
    path: str, host_state, epoch: int, loss: float, extra,
    retain: int = DEFAULT_RETAIN, mesh_manifest: Optional[dict] = None,
) -> None:
    blob = _payload_blob(host_state, epoch, loss, extra, mesh_manifest)
    if retain > 0:
        # retention trail: the sealed blob lands in {path}.history/ first,
        # then `path` is committed as a hard link (copy on filesystems
        # without links) — one physical write, K restorable generations
        hist_dir = f"{path}.history"
        os.makedirs(hist_dir, exist_ok=True)
        hist_path = os.path.join(
            hist_dir, f"{_next_history_seq(hist_dir):08d}.ckpt"
        )
        _atomic_write(hist_path, blob)
        chaos.crash_point("gathered-save:pre-commit")
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            if os.path.lexists(tmp):
                os.remove(tmp)
            os.link(hist_path, tmp)
        except OSError:
            shutil.copyfile(hist_path, tmp)
        os.replace(tmp, path)
        for stale in _gathered_history_paths(path)[retain:]:
            try:
                os.remove(stale)
            except OSError:
                pass
    else:
        _atomic_write(path, blob)
    # a job that switched from --checkpoint-format sharded to gathered
    # mid-life would otherwise strand {path}.shards forever: once the
    # gathered file is committed at `path`, the old shard root is
    # unreferenced (the pointer it served was just overwritten)
    stale = f"{path}.shards"
    if os.path.isdir(stale):
        shutil.rmtree(stale, ignore_errors=True)
        logger.info("Removed stale shard root %s (format switch)", stale)
    logger.info("Checkpoint saved to %s", path)


# ---------------------------------------------------------------------------
# sharded format
# ---------------------------------------------------------------------------


def _path_str(key_path) -> str:
    parts = []
    for p in key_path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def _raw_leaves(tree: Any) -> Any:
    """Typed PRNG keys → raw uint32 data (shape-stable flatten basis)."""

    def pre(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            return jax.random.key_data(x)
        return x

    return jax.tree_util.tree_map(pre, tree)


def _atomic_write(path: str, blob: bytes) -> None:
    chaos.on_write(path)  # deterministic fault injection (no-op unarmed)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def _version(epoch: int, batch: Optional[int] = None) -> str:
    """Checkpoint version name; zero-padded so string order is age order.

    Mid-epoch saves (``batch`` from ``extra["batch_in_epoch"]``) get a
    UNIQUE ``{epoch:08d}.{batch:08d}`` version instead of reusing the
    epoch's name — a crashed mid-epoch save can then never clobber the
    previous intact version (it targets a fresh dir). String order stays
    age order: mid-epoch saves of epoch E (``0000000E.b``) sort after
    the save that OPENED epoch E (the epoch-end commit of E-1, stamped
    ``epoch+1`` = ``0000000E`` by the loop, a strict prefix and thus
    smaller) and before the epoch-end commit of E (``0000000(E+1)``).
    """
    if not batch:
        return f"{epoch:08d}"
    return f"{epoch:08d}.{int(batch):08d}"


def _begin_sharded_save(path: str, version: str) -> None:
    """Main-thread prologue making the filesystem rendezvous sound.

    A step_dir surviving a crashed save (or an identical rerun) would let
    process 0's wait loop see the OLD shard files and commit a manifest
    over a torn old/new mix. Process 0 deletes any such dir, and a barrier
    ensures no process starts writing before the cleanup — the barrier is
    cheap and runs on the main thread, so the expensive fetch/serialize/
    write still backgrounds collective-free.
    """
    from distributed_pytorch_example_tpu.runtime import distributed as dist

    step_dir = os.path.join(f"{path}.shards", version)
    if jax.process_index() == 0 and os.path.isdir(step_dir):
        shutil.rmtree(step_dir, ignore_errors=True)
    if jax.process_count() > 1:
        dist.barrier(f"ckpt-begin-{os.path.basename(path)}-{version}")


def _save_sharded(
    path: str, state: Any, epoch: int, loss: float, extra,
    retain: int = DEFAULT_RETAIN, version: Optional[str] = None,
    mesh_manifest: Optional[dict] = None,
) -> None:
    """Collective-free sharded save; every process writes only its shards.

    Layout: ``{path}.shards/{version}/shard_{proc}.msgpack`` plus a
    ``manifest.msgpack`` committed by process 0 once every shard file has
    landed (filesystem rendezvous on the shared checkpoint store — the
    reference's all-ranks-read contract presumes one, train.py:253,256).
    ``{path}`` itself becomes a small pointer file flipped atomically last,
    so readers never observe a torn checkpoint. Every file is CRC-sealed;
    versions strictly older than the newest ``retain`` are GC'd.
    """
    proc, nproc = jax.process_index(), jax.process_count()
    if version is None:
        version = _version(epoch, (extra or {}).get("batch_in_epoch"))
    step_dir = os.path.join(f"{path}.shards", version)
    os.makedirs(step_dir, exist_ok=True)

    flat, _ = jax.tree_util.tree_flatten_with_path(_raw_leaves(state))
    # collect device handles first, then ONE batched device_get: per-shard
    # round trips otherwise dominate (same rationale as _gather_to_host's
    # batched fetch)
    entries: list = []  # (path, starts, device_data)
    meta: dict = {}
    host_leaves: dict = {}
    for key_path, leaf in flat:
        p = _path_str(key_path)
        if not isinstance(leaf, jax.Array):
            host_leaves[p] = np.asarray(leaf)
            continue
        meta[p] = {"shape": list(leaf.shape), "dtype": str(leaf.dtype)}
        try:
            # global distinct-chunk count (replica-0 shards across ALL
            # processes): lets the loader stream — device_put each leaf
            # the moment its last chunk lands and free the host buffer,
            # instead of holding the whole state on the host at once
            index_map = leaf.sharding.devices_indices_map(leaf.shape)
            meta[p]["chunks"] = len({
                tuple((s.start or 0, s.stop) for s in idx)
                for idx in index_map.values()
            })
        except Exception:  # non-fatal: loader falls back to bulk mode
            pass
        for shard in leaf.addressable_shards:
            if shard.replica_id != 0:
                continue  # exactly one device globally owns replica 0
            starts = [
                int(s.start) if s.start is not None else 0 for s in shard.index
            ]
            entries.append((p, starts, shard.data))
    fetched = jax.device_get([data for _, _, data in entries])
    chunks: dict = {}
    for (p, starts, _), data in zip(entries, fetched):
        chunks.setdefault(p, []).append(
            {"start": starts, "data": np.asarray(data)}
        )
    _atomic_write(
        os.path.join(step_dir, f"shard_{proc:05d}.msgpack"),
        seal(serialization.msgpack_serialize(chunks)),
    )
    # torn-save injection site: this process's shard is on disk, the
    # manifest/pointer commit has not happened — the window a preempted
    # host dies in. The pointer still names the previous intact version.
    chaos.crash_point("sharded-save:post-shards")

    if proc != 0:
        return
    deadline = time.monotonic() + SHARD_WAIT_TIMEOUT_S
    missing = [
        os.path.join(step_dir, f"shard_{i:05d}.msgpack") for i in range(nproc)
    ]
    while missing:
        missing = [f for f in missing if not os.path.exists(f)]
        if not missing:
            break
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"sharded checkpoint: {len(missing)} shard files still "
                f"missing after {SHARD_WAIT_TIMEOUT_S}s: {missing[:3]}..."
            )
        time.sleep(0.1)
    manifest = {
        "epoch": epoch,
        "loss": float(loss),
        "extra": extra or {},
        "nproc": nproc,
        "leaves": meta,
        "host_leaves": host_leaves,
    }
    if mesh_manifest is not None:
        manifest[elastic.MANIFEST_KEY] = mesh_manifest
    _atomic_write(
        os.path.join(step_dir, "manifest.msgpack"),
        seal(serialization.msgpack_serialize(manifest)),
    )
    chaos.crash_point("sharded-save:post-manifest")
    _atomic_write(path, SHARDED_MAGIC + version.encode())
    # GC: versions strictly OLDER than this commit are dead (per-process
    # save ordering means every process finished writing them) EXCEPT the
    # newest retain-1, kept as fallback ancestors. Newer dirs may already
    # hold in-flight shards from a save this slow process has not reached
    # yet — zero-padded names make `<` the age comparison.
    base = f"{path}.shards"
    older = sorted(
        n for n in os.listdir(base)
        if _VERSION_RE.fullmatch(n) and n < version
    )
    for name in older[: max(len(older) - max(retain - 1, 0), 0)]:
        shutil.rmtree(os.path.join(base, name), ignore_errors=True)
    logger.info(
        "Sharded checkpoint saved to %s (version %s)", path, version
    )


def _pointed_version_dir(path: str) -> Optional[str]:
    """The version dir the pointer file names, or None if unparseable."""
    try:
        with open(path, "rb") as f:
            version = f.read()[len(SHARDED_MAGIC):].decode(
                "utf-8", errors="replace"
            ).strip()
    except OSError:
        return None
    if not _VERSION_RE.fullmatch(version):
        logger.warning(
            "Corrupt sharded pointer %s (version %r); falling back to the "
            "version-dir scan", path, version[:40],
        )
        return None
    return os.path.join(f"{path}.shards", version)


def _sharded_version_dirs(path: str) -> List[str]:
    """Committed-or-torn version dirs newest-first (fallback candidates)."""
    base = f"{path}.shards"
    if not os.path.isdir(base):
        return []
    names = sorted(
        (n for n in os.listdir(base) if _VERSION_RE.fullmatch(n)),
        reverse=True,
    )
    return [os.path.join(base, n) for n in names]


def _load_sharded_version(
    step_dir: str, state_template: Any, shardings,
    target_axes: Optional[dict] = None,
) -> Tuple[Any, int, dict]:
    """Restore one sharded version dir (CRC-verified manifest + shards).

    Reassembly STREAMS per leaf when the manifest carries global chunk
    counts (format 3): as soon as a leaf's last chunk is filled it is
    device_put onto its target sharding and the host buffer freed, so
    peak host memory is bounded by the largest leaf plus whatever is
    still partially assembled — not the whole state. Manifests without
    chunk counts (r10 and older) fall back to whole-state assembly.
    """
    manifest = serialization.msgpack_restore(
        read_verified(os.path.join(step_dir, "manifest.msgpack"))
    )
    if not isinstance(manifest, dict) or "leaves" not in manifest:
        raise CheckpointCorruptError(
            f"{step_dir}: manifest is not a checkpoint manifest"
        )
    elastic.validate_resume(
        manifest.get(elastic.MANIFEST_KEY), target_axes, step_dir
    )

    if shardings is None:
        shardings = jax.tree_util.tree_map(
            lambda t: t.sharding if isinstance(t, jax.Array) else None,
            state_template,
        )
    flat_t, treedef = jax.tree_util.tree_flatten_with_path(state_template)
    # None IS a valid per-leaf sharding entry ("leave on host"); a plain
    # tree_leaves would silently drop it and misalign the zip below
    flat_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: x is None
    )
    by_path = {
        _path_str(key_path): (tmpl, sh)
        for (key_path, tmpl), sh in zip(flat_t, flat_s)
    }

    def place(p, val):
        tmpl, sh = by_path[p]
        if isinstance(tmpl, jax.Array) and jnp.issubdtype(
            tmpl.dtype, jax.dtypes.prng_key
        ):
            val = jax.random.wrap_key_data(jnp.asarray(val))
        return jax.device_put(val, sh) if sh is not None else jnp.asarray(val)

    leaves_meta = manifest["leaves"]
    buffers: dict = {}
    ready: dict = {}
    remaining = {
        p: int(m["chunks"])
        for p, m in leaves_meta.items()
        if isinstance(m, dict) and m.get("chunks")
    }
    for i in range(int(manifest["nproc"])):
        chunks = serialization.msgpack_restore(
            read_verified(os.path.join(step_dir, f"shard_{i:05d}.msgpack"))
        )
        for p, entries in chunks.items():
            m = leaves_meta.get(p)
            if m is None:
                continue  # stale leaf from an older tree; final loop errors
            buf = buffers.get(p)
            if buf is None:
                buf = buffers[p] = np.empty(
                    tuple(m["shape"]), np.dtype(m["dtype"])
                )
            for entry in entries:
                data = np.asarray(entry["data"])
                idx = tuple(
                    slice(int(s), int(s) + d)
                    for s, d in zip(entry["start"], data.shape)
                )
                buf[idx] = data
            if p in remaining and p in by_path:
                remaining[p] -= len(entries)
                if remaining[p] <= 0:
                    ready[p] = place(p, buffers.pop(p))

    restored = []
    for (key_path, tmpl), sh in zip(flat_t, flat_s):
        p = _path_str(key_path)
        if p in ready:
            restored.append(ready.pop(p))
        elif p in buffers:
            restored.append(place(p, buffers.pop(p)))
        elif p in manifest["host_leaves"]:
            restored.append(place(p, manifest["host_leaves"][p]))
        else:
            raise KeyError(f"checkpoint is missing leaf {p!r}")
    state = jax.tree_util.tree_unflatten(treedef, restored)
    logger.info(
        "Sharded checkpoint loaded from %s, epoch %s",
        step_dir, manifest["epoch"],
    )
    return state, int(manifest["epoch"]), dict(manifest.get("extra", {}))


def _load_gathered_file(
    path: str, state_template: Any, shardings,
    target_axes: Optional[dict] = None,
) -> Tuple[Any, int, dict]:
    """Restore one gathered checkpoint file (CRC-verified)."""
    payload = serialization.msgpack_restore(read_verified(path))
    if not isinstance(payload, dict) or "state" not in payload:
        raise CheckpointCorruptError(
            f"{path}: not a gathered checkpoint payload"
        )
    elastic.validate_resume(
        payload.get(elastic.MANIFEST_KEY), target_axes, path
    )
    state = serialization.from_state_dict(state_template, payload["state"])

    if shardings is None:
        shardings = jax.tree_util.tree_map(
            lambda t: t.sharding if isinstance(t, jax.Array) else None,
            state_template,
        )

    def restore_leaf(tmpl, val, sh):
        if isinstance(tmpl, jax.Array) and jnp.issubdtype(
            tmpl.dtype, jax.dtypes.prng_key
        ):
            val = jax.random.wrap_key_data(jnp.asarray(val))
        return jax.device_put(val, sh) if sh is not None else val

    state = jax.tree_util.tree_map(restore_leaf, state_template, state, shardings)
    logger.info("Checkpoint loaded from %s, epoch %s", path, payload["epoch"])
    return state, int(payload["epoch"]), dict(payload.get("extra", {}))


def _is_sharded(path: str) -> bool:
    try:
        with open(path, "rb") as f:
            return f.read(len(SHARDED_MAGIC)) == SHARDED_MAGIC
    except OSError:
        return False


def _peek_stamped_axes(desc: str) -> Optional[dict]:
    """Canonical stamped mesh axes of one fallback candidate, or None.

    Cheap for sharded version dirs (manifest only); the gathered peek
    deserializes the payload, acceptable because peeking only happens on
    the rare fallback path. Unreadable/unstamped candidates return None
    (sorted after known-same-mesh ones).
    """
    try:
        artifact = (
            os.path.join(desc, "manifest.msgpack")
            if os.path.isdir(desc)
            else desc
        )
        blob = serialization.msgpack_restore(read_verified(artifact))
        stamp = blob.get(elastic.MANIFEST_KEY) if isinstance(blob, dict) else None
        if isinstance(stamp, dict):
            return elastic.canonical_axes(stamp.get("axes", {}))
    except Exception:
        return None
    return None


def _order_fallback_candidates(
    queue: List[Tuple[str, Callable]], target_axes: Optional[dict]
) -> List[Tuple[str, Callable]]:
    """Order surviving fallback candidates per the elastic mode.

    ``DPX_ELASTIC=1``: newest intact wins regardless of stamped mesh —
    keep the age order. Otherwise prefer candidates stamped with the
    TARGET mesh shape (stable partition, age order within each bucket):
    without an explicit elastic opt-in, an older same-mesh ancestor is
    the conservative restore.
    """
    target = elastic.canonical_axes(target_axes)
    if elastic.elastic_enabled() or target is None:
        return queue
    same_mesh: List[Tuple[str, Callable]] = []
    other: List[Tuple[str, Callable]] = []
    for cand in queue:
        (same_mesh if _peek_stamped_axes(cand[0]) == target else other).append(
            cand
        )
    if same_mesh and other:
        logger.info(
            "Checkpoint fallback ordering: preferring %d same-mesh "
            "ancestor(s) over %d cross-mesh one(s) (set %s=1 for "
            "newest-intact-wins)",
            len(same_mesh), len(other), elastic.ELASTIC_ENV,
        )
    return same_mesh + other


def publish_checkpoint(
    channel,
    state: Any,
    epoch: int,
    loss: float,
    extra: Optional[dict] = None,
    saver: Optional[AsyncSaver] = None,
) -> Optional[str]:
    """Publish the train state to a graft-swap ``PublishChannel``.

    The published artifact is the SAME sealed, mesh-manifest-stamped
    gathered payload ``save_checkpoint`` writes (``_payload_blob``), so a
    serving fleet's SwapController restores it through the ordinary
    gathered path — ``elastic.validate_resume`` + per-leaf reshard onto
    the serve layout (serving/swap.py).

    Collective rules mirror the gathered save: the host gather is a
    collective, so EVERY process must enter; only process 0 writes the
    channel. With ``saver`` (process_count == 1 only — same constraint
    as the async gathered save) the fetch+serialize+publish runs on the
    AsyncSaver thread and None is returned; otherwise the committed
    version name is returned on process 0.
    """
    stamp = elastic.mesh_manifest(state)
    if saver is not None and jax.process_count() == 1:
        snap = jax.tree_util.tree_map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x, state
        )
        saver.submit(
            lambda: channel.publish_blob(
                _payload_blob(_gather_to_host(snap), epoch, loss, extra, stamp)
            )
        )
        return None
    host_state = _gather_to_host(state)
    if jax.process_index() != 0:
        return None
    return channel.publish_blob(
        _payload_blob(host_state, epoch, loss, extra, stamp)
    )


def save_checkpoint(
    path: str,
    state: Any,
    epoch: int,
    loss: float,
    extra: Optional[dict] = None,
    saver: Optional[AsyncSaver] = None,
    sharded: bool = False,
    retain: int = DEFAULT_RETAIN,
    publish=None,
) -> None:
    """Write a checkpoint; see module docstring for the two formats.

    Async (``saver``) rules: the gathered format needs a collective
    all-gather, so it backgrounds only at process_count == 1; the sharded
    format is collective-free and backgrounds at ANY process count.
    ``retain`` keeps the newest K generations restorable (fallback
    ancestors for ``load_checkpoint``); 1 reproduces the pre-r10
    only-the-live-checkpoint behavior.

    ``publish`` (graft-swap): also publish the gathered payload to the
    given ``PublishChannel``. On the gathered paths this reuses the
    already-gathered host state (async: inside the same background job);
    on the sharded paths it runs ``publish_checkpoint`` on the MAIN
    thread first, because the publish gather is a collective the
    background shard writer must never issue.
    """
    version = _version(epoch, (extra or {}).get("batch_in_epoch"))
    # format-3 mesh stamp (graft-elastic): derived from the live state's
    # NamedShardings on the MAIN thread — an async snapshot preserves
    # shardings, but stamping here keeps the manifest identical for the
    # sync and async paths
    stamp = elastic.mesh_manifest(state)

    def gathered_write(snap):
        host_state = _gather_to_host(snap)
        _write_payload(
            path, host_state, epoch, loss, extra, retain=retain,
            mesh_manifest=stamp,
        )
        if publish is not None:
            publish.publish_blob(
                _payload_blob(host_state, epoch, loss, extra, stamp)
            )

    write = (
        (lambda snap: _save_sharded(
            path, snap, epoch, loss, extra, retain=retain, version=version,
            mesh_manifest=stamp,
        ))
        if sharded
        else gathered_write
    )
    if sharded:
        # a still-draining PREVIOUS async write may target the same
        # version dir (a crash-rerun repeats a version name); it must
        # land before the cleanup rmtree below, or the old writer crashes
        # mid-write / stale shards leak into the new manifest
        if saver is not None:
            saver.wait()
        _begin_sharded_save(path, version)  # main thread: cleanup + barrier
        if publish is not None:
            publish_checkpoint(publish, state, epoch, loss, extra=extra)
    if saver is not None and (sharded or jax.process_count() == 1):
        # HBM-side copy: later donated train steps cannot invalidate it
        snap = jax.tree_util.tree_map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x, state
        )
        saver.submit(lambda: write(snap))
        return
    if sharded:
        _save_sharded(
            path, state, epoch, loss, extra, retain=retain, version=version,
            mesh_manifest=stamp,
        )
        return
    host_state = _gather_to_host(state)
    if jax.process_index() != 0:
        return
    _write_payload(
        path, host_state, epoch, loss, extra, retain=retain,
        mesh_manifest=stamp,
    )
    if publish is not None:
        publish.publish_blob(
            _payload_blob(host_state, epoch, loss, extra, stamp)
        )


def load_checkpoint(
    path: str,
    state_template: Any,
    shardings: Optional[Any] = None,
    fallback: bool = True,
    on_event: Optional[Callable[..., None]] = None,
) -> Tuple[Any, int, dict]:
    """Restore (state, epoch, extra) onto devices, re-sharded per template.

    Every process reads the same file (reference train.py:256: resume runs on
    ALL ranks before the start barrier). Device placement comes from
    ``shardings`` when given, else from the template's live shardings.
    The format (gathered file vs sharded pointer) is auto-detected, so a
    job can resume from either regardless of its own save format.

    Self-healing (``fallback=True``): every candidate is CRC-verified;
    when the newest is torn/corrupt/unreadable the loader walks back to
    the newest intact ancestor — the pointed sharded version first, then
    older version dirs, then gathered history entries — logging exactly
    what was skipped and why, and firing
    ``on_event("checkpoint_fallback", restored=..., skipped=[...])`` so
    the Trainer can count the recovery. Raises
    :class:`CheckpointCorruptError` listing every attempt only when no
    candidate restores. ``fallback=False`` restores the strict pre-r10
    behavior (first failure propagates).

    Elastic fallback ordering (graft-elastic): the newest candidate is
    always tried first. When it fails AND ``DPX_ELASTIC`` is unset, the
    remaining ancestors are reordered so intact SAME-mesh checkpoints
    (per their format-3 stamp) are preferred over cross-mesh ones — the
    conservative choice when nobody asked for a topology change. Under
    ``DPX_ELASTIC=1`` the newest intact checkpoint wins regardless of
    its stamped mesh shape (minimum work lost; the reshard-on-load path
    absorbs the shape change).
    """
    target_axes = elastic.tree_mesh_axes(shardings)
    if target_axes is None:
        target_axes = elastic.tree_mesh_axes(state_template)
    candidates: List[Tuple[str, Callable[[], Tuple[Any, int, dict]]]] = []

    def add_sharded_candidates(primary_first: bool) -> None:
        pointed = _pointed_version_dir(path) if primary_first else None
        if pointed is not None:
            candidates.append((
                pointed,
                lambda d=pointed: _load_sharded_version(
                    d, state_template, shardings, target_axes
                ),
            ))
        for d in _sharded_version_dirs(path):
            if pointed is not None and os.path.basename(
                d
            ) == os.path.basename(pointed):
                continue
            candidates.append((
                d,
                lambda d=d: _load_sharded_version(
                    d, state_template, shardings, target_axes
                ),
            ))

    if _is_sharded(path):
        add_sharded_candidates(primary_first=True)
    else:
        candidates.append((
            path,
            lambda: _load_gathered_file(
                path, state_template, shardings, target_axes
            ),
        ))
        for p in _gathered_history_paths(path):
            try:
                if os.path.samefile(p, path):
                    continue  # `path` hard-links the newest history entry
            except OSError:
                pass
            candidates.append((
                p,
                lambda p=p: _load_gathered_file(
                    p, state_template, shardings, target_axes
                ),
            ))
        # a bit-flipped pointer file no longer matches SHARDED_MAGIC and
        # parses as (corrupt) gathered; intact version dirs still restore
        add_sharded_candidates(primary_first=False)

    if not fallback:
        candidates = candidates[:1]
    if not candidates:
        raise FileNotFoundError(f"no checkpoint candidates at {path}")

    skipped: List[Tuple[str, str]] = []
    queue = list(candidates)
    reordered = False
    while queue:
        desc, thunk = queue.pop(0)
        try:
            state, epoch, extra = thunk()
        except elastic.MissingMeshManifestError:
            # a config error, not corruption: every unstamped ancestor
            # would raise the same, and silently restoring an OLDER one
            # under elastic mode hides that the resume contract is unmet —
            # surface the clear remediation message instead
            raise
        except Exception as err:
            if not fallback:
                raise
            reason = f"{type(err).__name__}: {err}"
            skipped.append((desc, reason))
            logger.warning(
                "Checkpoint candidate %s unusable (%s); trying the "
                "next-newest ancestor", desc, reason,
            )
            if not reordered and queue:
                reordered = True  # one reorder per load, fallback-only
                queue = _order_fallback_candidates(queue, target_axes)
            continue
        if skipped:
            logger.warning(
                "Checkpoint fallback: restored %s (epoch %d) after "
                "skipping %d corrupt/torn candidate(s): %s",
                desc, epoch, len(skipped),
                "; ".join(f"{d} ({r})" for d, r in skipped),
            )
            if on_event is not None:
                on_event(
                    "checkpoint_fallback",
                    restored=desc,
                    epoch=epoch,
                    skipped=[
                        {"candidate": d, "reason": r} for d, r in skipped
                    ],
                )
        return state, epoch, extra
    raise CheckpointCorruptError(
        f"no intact checkpoint at {path}: all {len(skipped)} candidate(s) "
        "failed — "
        + "; ".join(f"{d} ({r})" for d, r in skipped)
    )
