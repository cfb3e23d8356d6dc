"""graft-armor's deterministic fault-injection harness.

A :class:`ChaosPlan` is a seeded, serializable list of faults; production
code calls the tiny hook functions below at its fault-relevant points
(batch ingestion, checkpoint writes, sharded-save commit, rendezvous).
With no plan installed every hook is a no-op costing one global read —
the harness is compiled out of nothing and adds no steady-state work.

Faults are injected at exact, named sites rather than randomly in time,
so every scenario in ``scripts/chaos_sweep.py`` replays bit-identically:
the same plan always poisons the same global step, fails the same write,
and kills the same save. Plans travel to child training processes via the
``DPX_CHAOS`` environment variable (JSON).

Fault kinds:

- ``nan-batch`` / ``inf-batch`` — overwrite the first float leaf of the
  training batch with NaN/Inf for ``count`` steps starting at ``step``
  (exercises the bad-step predicated update, train/step.py);
- ``io-error`` — raise a transient ``OSError`` on the next ``count``
  checkpoint writes whose path contains ``path_substr`` (exercises the
  AsyncSaver retry path);
- ``kill`` — SIGKILL the current process the ``nth`` time the named
  crash point is reached (e.g. ``sharded-save:post-shards`` — between
  shard-file writes and the manifest/pointer commit: a torn save; or
  ``step`` — the per-step boundary in ``train/loop.py``, the
  kill-a-slice site graft-elastic's shrink-to-survivors scenario uses);
- ``rendezvous-flake`` — fail (after an optional delay) the next
  ``count`` entries into the named transient site (e.g. coordinator
  rendezvous in ``runtime/distributed.initialize``);
- ``poison-request`` — NaN-poison the logits of serving request ``at``
  (the request id) for ``count`` sampled tokens starting at generated-
  token index ``step`` (exercises graft-serve's bad-request isolation:
  the request is evicted with an error status, co-resident requests are
  untouched — serving/engine.py, scripts/chaos_sweep.py);
- ``kill-replica`` / ``stall-replica`` — fleet faults (graft-fleet): at
  decode boundary ``step`` (1-based) of serving replica ``at``, the
  replica worker dies abruptly (kill: in-flight requests lost, exactly a
  SIGKILLed serving container) or stops making progress without dying
  (stall: the hang class heartbeats exist for). The router must detect
  either within its heartbeat deadline and replay the lost requests
  elsewhere bit-identically (serving/fleet.py, serving/router.py);
- ``flaky-channel`` — transient ``OSError`` on the next ``count``
  dispatches to replica ``at`` (empty = any replica), exercising the
  router's bounded dispatch retry (robustness/retry.py);
- ``corrupt-shard`` — bit-flip the data-shard file whose path contains
  ``path_substr`` on the ``nth`` read touch (graft-intake: the sealed
  sidecar catches it at first verification and the shard is
  quarantined, data/streaming.py);
- ``slow-shard-io`` — sleep ``delay_s`` on the next ``count`` shard
  read touches matching ``path_substr`` (input-bound steps must show up
  as ``data_stall_ms``, not silently stretch the step time);
- ``kill-decode-worker`` — crash the supervised prefetch worker at the
  first produced batch index ``>= step`` (fires once; the supervisor
  must restart it re-producing the exact batch, data/intake.py);
- ``corrupt-publish`` — bit-flip the ``nth`` published checkpoint
  artifact AFTER it is fully written but before the pointer flips
  (graft-swap: the version commits but its CRC is broken, so the fleet's
  intact-ancestor walk must skip it — robustness/publish.py);
- ``torn-publish`` — SIGKILL the publisher between the version-dir
  artifact write and the pointer flip on the ``nth`` publish (the torn
  window; the fleet must keep serving the previous version and the next
  publish must heal the channel);
- ``kill-during-swap`` — abort the SwapController mid-roll at the
  ``nth`` visit of the named roll stage ``at`` (e.g. ``pre-install``:
  after the replica drained but before new weights install), simulating
  a controller crash between replicas; the next tick must resume and
  complete the roll with the fleet still consistent (serving/swap.py).
"""

from __future__ import annotations

import dataclasses
import errno
import json
import os
import signal
import time
from typing import Any, List, Optional

from distributed_pytorch_example_tpu.runtime.logging import get_logger

logger = get_logger(__name__)

ENV_VAR = "DPX_CHAOS"
KINDS = (
    "nan-batch", "inf-batch", "io-error", "kill", "rendezvous-flake",
    "poison-request", "kill-replica", "stall-replica", "flaky-channel",
    "corrupt-shard", "slow-shard-io", "kill-decode-worker",
    "corrupt-publish", "torn-publish", "kill-during-swap",
)


@dataclasses.dataclass
class Fault:
    """One seeded fault; see module docstring for per-kind semantics."""

    kind: str
    step: int = -1          # nan/inf-batch: first poisoned global step
    count: int = 1          # nan/inf-batch: steps; io/rendezvous: failures
    path_substr: str = ""   # io-error: only writes whose path contains this
    at: str = ""            # kill: crash-point name
    nth: int = 1            # kill: trigger on the Nth visit of that point
    delay_s: float = 0.0    # rendezvous-flake: sleep before failing
    fired: int = 0          # live counter (io/rendezvous firings, kill visits)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown chaos fault kind {self.kind!r} (one of {KINDS})"
            )


class ChaosPlan:
    """A seeded list of faults, serializable for child processes."""

    def __init__(self, faults: List[Fault], seed: int = 0):
        self.faults = list(faults)
        self.seed = int(seed)

    @classmethod
    def from_json(cls, text: str) -> "ChaosPlan":
        spec = json.loads(text)
        return cls(
            [Fault(**f) for f in spec.get("faults", [])],
            seed=spec.get("seed", 0),
        )

    def to_json(self) -> str:
        return json.dumps({
            "seed": self.seed,
            "faults": [
                {
                    k: v
                    for k, v in dataclasses.asdict(f).items()
                    if k != "fired"
                }
                for f in self.faults
            ],
        })

    def __repr__(self):
        return f"ChaosPlan(seed={self.seed}, faults={self.faults!r})"


def preset(name: str) -> ChaosPlan:
    """Named plans for `train.py --chaos` and `scripts/chaos_sweep.py`."""
    if name == "nan-step":
        # poison one batch well past warmup; the predicated update skips it
        return ChaosPlan([Fault("nan-batch", step=3)])
    if name == "io-flake":
        # two transient write failures on `latest`; retry heals both
        return ChaosPlan([Fault("io-error", path_substr="latest", count=2)])
    if name == "kill-replica":
        # fleet replica r1 dies at its 8th decode boundary: late enough
        # that requests are mid-stream, early enough that survivors still
        # carry real load after the loss
        return ChaosPlan([Fault("kill-replica", at="r1", step=8)])
    if name == "stall-replica":
        # same boundary, but the replica hangs instead of dying — only
        # the heartbeat deadline can catch this one
        return ChaosPlan([Fault("stall-replica", at="r1", step=8)])
    if name == "flaky-channel":
        # two transient dispatch failures; the router's bounded retry heals
        return ChaosPlan([Fault("flaky-channel", count=2)])
    raise ValueError(f"unknown chaos preset {name!r}")


# ---------------------------------------------------------------------------
# plan installation (module-global; one plan active per process)
# ---------------------------------------------------------------------------

_plan: Optional[ChaosPlan] = None
_env_checked = False


def install(plan: Optional[ChaosPlan]) -> None:
    global _plan, _env_checked
    _plan = plan
    _env_checked = True  # an explicit install wins over the env var
    if plan is not None:
        logger.warning("chaos: fault plan installed: %s", plan)


def uninstall() -> None:
    global _plan, _env_checked
    _plan = None
    _env_checked = False


def active() -> Optional[ChaosPlan]:
    """The installed plan, lazily parsing ``DPX_CHAOS`` on first use."""
    global _plan, _env_checked
    if not _env_checked:
        _env_checked = True
        spec = os.environ.get(ENV_VAR)
        if spec:
            try:
                _plan = (
                    ChaosPlan.from_json(spec)
                    if spec.lstrip().startswith("{")
                    else preset(spec)
                )
                logger.warning(
                    "chaos: fault plan from $%s: %s", ENV_VAR, _plan
                )
            except (ValueError, TypeError, KeyError) as err:
                raise ValueError(
                    f"malformed ${ENV_VAR} chaos spec: {err}"
                ) from err
    return _plan


# ---------------------------------------------------------------------------
# hooks (called from production code; no-ops without a matching fault)
# ---------------------------------------------------------------------------


def corrupt_batch(batch: Any, step: int) -> Any:
    """Poison the first float leaf of ``batch`` if a fault targets ``step``.

    The replacement is placed with ``jax.device_put`` onto the original
    leaf's sharding, so the poisoned step compiles/runs identically to a
    clean one (no resharding, no new executables — required for the
    no-recompile recovery contract).
    """
    plan = active()
    if plan is None:
        return batch
    fault = next(
        (
            f for f in plan.faults
            if f.kind in ("nan-batch", "inf-batch")
            and f.step <= step < f.step + f.count
        ),
        None,
    )
    if fault is None:
        return batch
    import jax
    import jax.numpy as jnp
    import numpy as np

    val = np.nan if fault.kind == "nan-batch" else np.inf
    out = dict(batch)
    for key, leaf in batch.items():
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            poisoned = np.full(leaf.shape, val, dtype=leaf.dtype)
            sharding = getattr(leaf, "sharding", None)
            out[key] = (
                jax.device_put(poisoned, sharding)
                if sharding is not None
                else poisoned
            )
            fault.fired += 1
            logger.warning(
                "chaos: %s injected into batch leaf %r at step %d",
                fault.kind, key, step,
            )
            return out
    logger.warning(
        "chaos: %s fault at step %d found no float batch leaf to poison "
        "(integer-token task?); batch left clean", fault.kind, step,
    )
    return batch


def on_write(path: str) -> None:
    """Transient-``OSError`` injection point (top of ``_atomic_write``)."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if (
            fault.kind == "io-error"
            and fault.fired < fault.count
            and fault.path_substr in path
        ):
            fault.fired += 1
            logger.warning(
                "chaos: injected transient OSError on write %d/%d to %s",
                fault.fired, fault.count, path,
            )
            raise OSError(
                errno.EIO, "chaos: injected transient I/O error", path
            )


def crash_point(name: str) -> None:
    """SIGKILL this process at a named site when a kill fault matches."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if fault.kind == "kill" and fault.at == name:
            fault.fired += 1
            if fault.fired == fault.nth:
                logger.warning(
                    "chaos: SIGKILL at crash point %r (visit %d)",
                    name, fault.fired,
                )
                os.kill(os.getpid(), signal.SIGKILL)


def transient_failure(name: str) -> None:
    """Named transient-failure site (rendezvous); raises while armed."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if (
            fault.kind == "rendezvous-flake"
            and (not fault.at or fault.at == name)
            and fault.fired < fault.count
        ):
            fault.fired += 1
            if fault.delay_s:
                time.sleep(fault.delay_s)
            logger.warning(
                "chaos: injected transient failure at %r (%d/%d)",
                name, fault.fired, fault.count,
            )
            raise RuntimeError(
                f"chaos: injected transient failure at {name!r}"
            )


def poison_request(request_id: str, token_index: int) -> bool:
    """Whether a serving request's logits should be NaN-poisoned for the
    generated token at ``token_index`` (0-based). The engine feeds the
    returned flag into its compiled step as a regular input, so the
    poisoned step runs the SAME executable as a clean one — the
    no-recompile injection contract the other hooks follow."""
    plan = active()
    if plan is None:
        return False
    for fault in plan.faults:
        if (
            fault.kind == "poison-request"
            and fault.at == str(request_id)
            and fault.step <= token_index < fault.step + fault.count
        ):
            fault.fired += 1
            logger.warning(
                "chaos: poisoning request %r at generated token %d",
                request_id, token_index,
            )
            return True
    return False


def replica_fault(replica_id: str, decode_step: int) -> Optional[str]:
    """Fleet fault poll, called by each replica worker at its decode
    boundaries (``decode_step`` is 1-based): ``"kill"`` — die abruptly,
    losing in-flight state; ``"stall"`` — stop making progress without
    dying; ``None`` — keep serving. Fires once per fault, at the first
    boundary ``>= step`` (boundary counts differ run-to-run only under
    preemption, so `>=` keeps the plan replayable)."""
    plan = active()
    if plan is None:
        return None
    for fault in plan.faults:
        if (
            fault.kind in ("kill-replica", "stall-replica")
            and fault.at == str(replica_id)
            and fault.fired == 0
            and 0 <= fault.step <= decode_step
        ):
            fault.fired += 1
            action = "kill" if fault.kind == "kill-replica" else "stall"
            logger.warning(
                "chaos: %s replica %r at decode boundary %d",
                action, replica_id, decode_step,
            )
            return action
    return None


def flaky_channel(replica_id: str) -> None:
    """Transient-``OSError`` injection on the router->replica dispatch
    channel (top of the router's retried submit); ``at`` empty matches
    any replica."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if (
            fault.kind == "flaky-channel"
            and (not fault.at or fault.at == str(replica_id))
            and fault.fired < fault.count
        ):
            fault.fired += 1
            logger.warning(
                "chaos: injected flaky channel to replica %r (%d/%d)",
                replica_id, fault.fired, fault.count,
            )
            raise OSError(
                errno.EIO,
                f"chaos: injected flaky channel to replica {replica_id}",
            )


def shard_read(path: str) -> None:
    """Data-shard read touch (graft-intake): ``corrupt-shard`` bit-flips
    the file on disk at the ``nth`` matching touch (the sealed sidecar
    must catch it on verification); ``slow-shard-io`` sleeps ``delay_s``
    for the next ``count`` matching touches."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if fault.kind == "corrupt-shard" and fault.path_substr in path:
            fault.fired += 1
            if fault.fired == fault.nth:
                logger.warning(
                    "chaos: corrupting shard %s (touch %d)",
                    path, fault.fired,
                )
                corrupt_file(path, mode="bitflip", seed=plan.seed)
        elif (
            fault.kind == "slow-shard-io"
            and fault.path_substr in path
            and fault.fired < fault.count
        ):
            fault.fired += 1
            delay = fault.delay_s or 0.05
            logger.warning(
                "chaos: slow shard I/O on %s — sleeping %.3fs (%d/%d)",
                path, delay, fault.fired, fault.count,
            )
            time.sleep(delay)


def decode_worker(batch_index: int) -> None:
    """Supervised-prefetch-worker crash site (graft-intake): a
    ``kill-decode-worker`` fault raises inside the producer at the first
    produced batch index ``>= step``, once (`>=` keeps the plan
    replayable when the restart re-produces earlier indices)."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if (
            fault.kind == "kill-decode-worker"
            and fault.fired == 0
            and 0 <= fault.step <= batch_index
        ):
            fault.fired += 1
            logger.warning(
                "chaos: killing decode worker at batch %d", batch_index
            )
            raise RuntimeError(
                f"chaos: decode worker killed at batch {batch_index}"
            )


def publish_fault(stage: str, path: str) -> None:
    """Publish-channel attack points (robustness/publish.py). Called
    twice per publish, with the artifact path: stage ``post-artifact``
    (version fully written, pointer not yet flipped — where
    ``corrupt-publish`` bit-flips the artifact so the commit carries a
    broken CRC) and stage ``pre-pointer`` (where ``torn-publish``
    SIGKILLs the publisher, leaving an uncommitted version dir). Both
    count matching visits and fire on the ``nth``; ``path_substr``
    optionally narrows to one channel."""
    plan = active()
    if plan is None:
        return
    for fault in plan.faults:
        if fault.path_substr and fault.path_substr not in path:
            continue
        if fault.kind == "corrupt-publish" and stage == "post-artifact":
            fault.fired += 1
            if fault.fired == fault.nth:
                logger.warning(
                    "chaos: corrupting published artifact %s (publish %d)",
                    path, fault.fired,
                )
                corrupt_file(path, mode="bitflip", seed=plan.seed)
        elif fault.kind == "torn-publish" and stage == "pre-pointer":
            fault.fired += 1
            if fault.fired == fault.nth:
                logger.warning(
                    "chaos: SIGKILL mid-publish (torn) before pointer "
                    "flip of %s (publish %d)", path, fault.fired,
                )
                os.kill(os.getpid(), signal.SIGKILL)


def swap_fault(stage: str) -> bool:
    """SwapController roll-stage poll (serving/swap.py): a
    ``kill-during-swap`` fault whose ``at`` matches ``stage`` (empty =
    any stage) returns True at its ``nth`` matching visit — the
    controller must abandon the current roll as if it crashed there and
    finish it on a later tick."""
    plan = active()
    if plan is None:
        return False
    for fault in plan.faults:
        if fault.kind == "kill-during-swap" and (
            not fault.at or fault.at == stage
        ):
            fault.fired += 1
            if fault.fired == fault.nth:
                logger.warning(
                    "chaos: aborting swap roll at stage %r (visit %d)",
                    stage, fault.fired,
                )
                return True
    return False


# ---------------------------------------------------------------------------
# offline corruption (tests / chaos_sweep attacking files between runs)
# ---------------------------------------------------------------------------


def corrupt_file(path: str, mode: str = "bitflip", seed: int = 0) -> None:
    """Deterministically damage an existing file.

    ``bitflip`` flips one bit at a seed-chosen offset (checksum mismatch);
    ``truncate`` cuts the file to half (torn write).
    """
    size = os.path.getsize(path)
    if size == 0:
        raise ValueError(f"cannot corrupt empty file {path}")
    if mode == "truncate":
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
        logger.warning("chaos: truncated %s to %d bytes", path, size // 2)
    elif mode == "bitflip":
        # LCG keeps this dependency-free and reproducible across runs
        offset = (seed * 2654435761 + 12345) % size
        with open(path, "r+b") as f:
            f.seek(offset)
            byte = f.read(1)
            f.seek(offset)
            f.write(bytes([byte[0] ^ 0x40]))
        logger.warning("chaos: flipped bit at offset %d of %s", offset, path)
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")
