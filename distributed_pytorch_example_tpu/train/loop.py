"""The epoch loop: train → validate → reduce → checkpoint → barrier.

Behavioral parity with the reference's ``main()`` orchestration
(train.py:212-318), rebuilt for compiled steps:

- per-epoch reshuffle via ``loader.set_epoch`` (train.py:267);
- rank-0 progress log every N batches (train.py:144-148) — fetching ONLY
  that step's loss, steps in between stay async (no per-step item() sync);
- validation on a disjoint shard per process with global-mean metrics
  (train.py:154-175, 275-277 — here the means are global by construction
  since metrics are computed on the globally-sharded batch inside jit);
- host-0 best/latest checkpoints keyed on validation accuracy
  (train.py:292-308) and epoch-granularity resume (train.py:256-257);
- cross-process barrier per epoch and around resume (train.py:259,310);
- epoch / total wall-time logs (train.py:265,283,286,312-316).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import optax

from distributed_pytorch_example_tpu.data import intake
from distributed_pytorch_example_tpu.parallel.api import Partitioner
from distributed_pytorch_example_tpu.robustness import (
    BadStepBudgetExceeded,
    chaos,
)
from distributed_pytorch_example_tpu.runtime import distributed as dist
from distributed_pytorch_example_tpu.runtime.logging import get_logger
from distributed_pytorch_example_tpu.train import checkpoint as ckpt_lib
from distributed_pytorch_example_tpu.train.metrics import (
    MetricAccumulator,
    fetch_scalars,
)
from distributed_pytorch_example_tpu.train.state import TrainState
from distributed_pytorch_example_tpu.train.step import (
    build_eval_step,
    build_train_step,
    init_state,
)
from distributed_pytorch_example_tpu.telemetry import (
    Telemetry,
    TelemetryConfig,
)
from distributed_pytorch_example_tpu.telemetry import trace as span_lib

logger = get_logger(__name__)


def _span(scope: Optional[Telemetry], name: str, step: Optional[int] = None):
    """A graft-scope span (telemetry/trace.py: profiler annotation,
    in-memory record, Chrome event), or a no-op when telemetry is off."""
    if scope is None:
        return span_lib.no_span(name)
    return scope.span(name, step)


def _record_moe_counters(scope: Optional[Telemetry], metrics) -> None:
    """One ``moe_counters`` row of the in-memory record with the expert
    layers' counters of this step as ``args``. Called inside ``log_fetch``
    once the step's loss has come: the step is done, so this is a copy and
    no wait. A model without experts has no such metric and pays a scan of
    the metrics' names."""
    names = [k for k in metrics if k.startswith("moe_")]
    if scope is None or not names:
        return
    now = time.perf_counter_ns()
    span_lib.add(
        "moe_counters", now, now,
        args={k[len("moe_"):]: v for k, v in fetch_scalars(metrics, names).items()},
    )


def _spanned_batches(iterator, scope: Optional[Telemetry]):
    """Wrap an iterator so each ``next()`` is timed as a "data_load" span
    (the consumer-side wait on the loader's prefetch queue)."""
    while True:
        with _span(scope, "data_load"):
            try:
                item = next(iterator)
            except StopIteration:
                return
        yield item


class PreemptionInterrupt(BaseException):
    """Raised inside ``fit`` after a signal-triggered checkpoint landed.

    SIGTERM (orchestrator preemption) and SIGINT (Ctrl-C on a dev box)
    both unwind through here once the in-flight step has checkpointed;
    ``exit_code`` carries the conventional rc for the CLI — 143 for TERM
    (the rc the launcher treats as orchestrator teardown, NOT restarted,
    launch/entrypoint.sh:133-141) and 130 for INT. BaseException so
    blanket ``except Exception`` recovery logic cannot swallow a teardown.
    """

    def __init__(self, exit_code: int = 143):
        super().__init__(exit_code)
        self.exit_code = exit_code


class Trainer:
    """Binds (model, task, optimizer, partitioner) into a runnable job."""

    def __init__(
        self,
        model,
        task,
        optimizer: optax.GradientTransformation,
        partitioner: Optional[Partitioner] = None,
        checkpoint_dir: Optional[str] = None,
        log_every: int = 10,
        seed: int = 0,
        metrics_file: Optional[str] = None,
        profile_dir: Optional[str] = None,
        profile_window: tuple = (10, 13),
        checkpoint_format: str = "auto",
        save_every_steps: int = 0,
        grad_accum_steps: int = 1,
        telemetry: Union[bool, TelemetryConfig] = True,
        telemetry_every: int = 0,
        max_bad_steps: int = 8,
        skip_nonfinite: bool = True,
        checkpoint_retain: int = ckpt_lib.DEFAULT_RETAIN,
        publish_dir: Optional[str] = None,
        wire=None,
    ):
        self.model = model
        self.task = task
        self.optimizer = optimizer
        self.partitioner = partitioner
        self.checkpoint_dir = checkpoint_dir
        self.log_every = log_every
        self.seed = seed
        if grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {grad_accum_steps}"
            )
        # N>1: the step scans N microbatches of batch/N samples before ONE
        # deferred gradient collective (train/step.py) — in-step counterpart
        # of the optimizer-level optax.MultiSteps every_k (which pays the
        # gradient sync on every micro-step)
        self.grad_accum_steps = grad_accum_steps
        # graft-armor bad-step auto-recovery: the step predicates the
        # update out device-side when grads go nonfinite (train/step.py);
        # the host counts those skips against max_bad_steps at log
        # boundaries — exceed ⇒ one rollback to the last good checkpoint,
        # exceed again ⇒ BadStepBudgetExceeded. 0 disables the budget
        # (skips are unlimited); skip_nonfinite=False removes the
        # predication entirely (pre-r10 step program).
        self.max_bad_steps = max_bad_steps
        self.skip_nonfinite = skip_nonfinite
        # keep-last-K checkpoint generations (fallback ancestors for
        # corrupt-latest auto-recovery, train/checkpoint.py)
        self.checkpoint_retain = checkpoint_retain
        # graft-swap: every checkpoint also lands in this PublishChannel
        # (corruption-safe pointer-flip commit) for live fleet hot-swap;
        # construction is side-effect-free and publish_checkpoint itself
        # restricts the write to process 0, so every process may hold one
        if publish_dir:
            from distributed_pytorch_example_tpu.robustness.publish import (
                PublishChannel,
            )

            self._publish_channel = PublishChannel(publish_dir)
        else:
            self._publish_channel = None
        # graft-wire collective compression (parallel/wire.py): explicit
        # arg wins, else the partitioner's, else fp32 payloads
        from distributed_pytorch_example_tpu.parallel.wire import WireConfig

        if wire is None:
            wire = getattr(partitioner, "wire", None) or WireConfig()
        self.wire = wire
        self.train_step = build_train_step(
            model, task, optimizer,
            partitioner=partitioner, grad_accum_steps=grad_accum_steps,
            skip_nonfinite=skip_nonfinite, wire=wire,
        )
        self.eval_step = build_eval_step(model, task)
        self.state: Optional[TrainState] = None
        self.state_shardings = None
        if metrics_file is None and checkpoint_dir:
            metrics_file = os.path.join(checkpoint_dir, "metrics.jsonl")
        self._metrics_file = metrics_file
        self._profile_dir = profile_dir
        self._profile_window = profile_window
        self._profiler = None  # armed in fit()
        self._saver = ckpt_lib.AsyncSaver()
        self._global_step = 0
        if checkpoint_format not in ("auto", "gathered", "sharded"):
            raise ValueError(
                f"checkpoint_format must be auto|gathered|sharded, got "
                f"{checkpoint_format!r}"
            )
        self._checkpoint_format = checkpoint_format
        # graft-scope (telemetry/): cost registry at compile, device-side
        # health sentinels fetched at log boundaries, rate-limited step
        # clock + cross-host straggler exchange, Chrome-trace spans. True
        # uses the defaults (epoch records only); telemetry_every>0 adds a
        # metrics.jsonl record every N steps; a TelemetryConfig wins over
        # both; False disables the scope entirely.
        if isinstance(telemetry, TelemetryConfig):
            self._telemetry_cfg: Optional[TelemetryConfig] = telemetry
        elif telemetry:
            self._telemetry_cfg = TelemetryConfig(every=telemetry_every)
        else:
            self._telemetry_cfg = None
        self.scope: Optional[Telemetry] = None
        self.telemetry_summary: Dict[str, Any] = {}
        self.wire_report: Optional[Dict[str, Any]] = None  # set in init()
        self._compiled: Dict[Any, Any] = {}  # AOT executables by shape key
        # the cost record made at each one's compile, under the same key:
        # plain numbers and strings, no reference to the executable
        self._cost_records: Dict[Any, Dict[str, Any]] = {}
        # >0: write `latest` every N train batches WITH the loader cursor
        # (epoch, batch_in_epoch) so resume restarts at the exact batch —
        # step-level resume on top of the reference's epoch granularity
        # (reference train.py:256-257; an epoch at long-context scale is
        # too much to lose to a preemption)
        self.save_every_steps = save_every_steps
        self._best_accuracy = 0.0
        self._preempt_requested = False
        self._preempt_rc = 143
        # recovery observability (reset per fit): how often each
        # graft-armor surface fired
        self.recovery: Dict[str, int] = {
            "bad_steps": 0, "rollbacks": 0, "checkpoint_fallbacks": 0,
        }
        self._pending_bad: List[Any] = []  # device flags, drained at bounds
        self._bad_since_recovery = 0
        self._rolled_back = False
        # input-plane events fired before fit's scope exists (see
        # _record_event); flushed into the scope on creation
        self._pending_events: List[Any] = []

    def _sharded_ckpt(self) -> bool:
        """auto: sharded at multi-host scale (collective-free async saves,
        no full-state gather); gathered single file otherwise (reference
        single-file parity, train.py:185-192)."""
        if self._checkpoint_format == "auto":
            return jax.process_count() > 1
        return self._checkpoint_format == "sharded"

    def _mesh_ctx(self):
        """Enter the partitioner's mesh so mesh-aware ops (ring attention)
        can find it via ``runtime.mesh.current_mesh`` at trace time."""
        if self.partitioner is not None:
            return self.partitioner.mesh
        return contextlib.nullcontext()

    # -- state ------------------------------------------------------------

    def _bare_span(self, name: str, cpu: bool = False):
        """A span outside ``fit``'s scope (``init``, the head and tail of
        ``fit``): the module-level form of ``Telemetry.span``."""
        if self._telemetry_cfg is None:
            return span_lib.no_span(name)
        return span_lib.span(name, cpu=cpu)

    def init(self, sample_inputs: Any) -> TrainState:
        with self._bare_span("init_state"):
            return self._init(sample_inputs)

    def _init(self, sample_inputs: Any) -> TrainState:
        with self._mesh_ctx():
            self.state, self.state_shardings = init_state(
                self.model,
                self.optimizer,
                sample_inputs,
                jax.random.key(self.seed),
                self.partitioner,
            )
        n_params = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(self.state.params)
        )
        logger.info("Model parameters: %s", f"{n_params:,}")
        # analytic gradient-sync wire accounting (parallel/wire.py):
        # per-device bytes per step + compression ratio, surfaced in the
        # telemetry summary (`telemetry_summary["wire"]`)
        if self.partitioner is not None:
            from distributed_pytorch_example_tpu.parallel.wire import (
                grad_wire_report,
            )

            self.wire_report = grad_wire_report(
                self.state.params, self.partitioner, self.wire
            )
            if self.wire.compress != "none":
                logger.info(
                    "graft-wire: %s block=%d — grad sync %s B/step/device "
                    "(fp32 %s, ratio %.2fx)",
                    self.wire.compress, self.wire.block_size,
                    f"{self.wire_report['grad_wire_bytes_per_step']:,}",
                    f"{self.wire_report['grad_wire_bytes_per_step_fp32']:,}",
                    self.wire_report["wire_compression_ratio"],
                )
        else:
            self.wire_report = None
        return self.state

    def _sample_inputs_from(self, loader) -> Any:
        batch = next(iter(loader))
        inputs_key = self.task.batch_keys[0]
        return batch[inputs_key]

    # -- AOT step executables (graft-scope cost registry) -----------------

    @staticmethod
    def _shape_key(tag: str, batch) -> tuple:
        return (tag, tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in batch.items()
        )))

    def _train_executable(self, batch):
        """AOT-compile the train step ONCE per batch shape and register its
        cost/memory/collective record with graft-scope. The compiled
        program is the same one ``jax.jit`` would cache — AOT just exposes
        ``cost_analysis()``/``memory_analysis()`` at the moment the compile
        happens. A compile failure raises: a compiler refusal is a fault
        of the program, not something a second compile through ``jit``
        would cure.

        The ``record_compile`` span opens only where the analysis runs: at
        a compile. A later ``fit`` that finds the executable here gives
        its new scope the record kept beside it and opens no such span."""
        if self.scope is None:
            return None, self.train_step
        key = self._shape_key("train", batch)
        exe = self._compiled.get(key)
        if exe is None:
            exe = self.train_step.lower(self.state, batch).compile()
            with self.scope.span("record_compile"):
                self._cost_records[key] = self.scope.record_compile(
                    "train_step", exe
                )
            self._compiled[key] = exe
        else:
            self._give_cost_record(key, "train_step")
        return key, exe

    def _eval_executable(self, batch):
        if self.scope is None:
            return None, self.eval_step
        key = self._shape_key("eval", batch)
        exe = self._compiled.get(key)
        if exe is None:
            exe = self.eval_step.lower(
                self.state, batch, jnp.asarray(0, jnp.int32)
            ).compile()
            self._cost_records[key] = self.scope.record_compile(
                "eval_step", exe
            )
            self._compiled[key] = exe
        else:
            self._give_cost_record(key, "eval_step")
        return key, exe

    def _give_cost_record(self, key, tag: str) -> None:
        """A new ``fit``'s scope starts with an empty registry: hand it
        the record made when this shape's executable was compiled (also
        after ``_dispatch`` handed the shape back to ``jax.jit``)."""
        if self.scope.costs.get(tag) is None and key in self._cost_records:
            self.scope.register_compile(tag, self._cost_records[key])

    def _dispatch(self, key, exe, jit_fn, *args):
        """Call an AOT step executable, recovering from sharding drift.

        A partitioner that re-lays-out the state inside the step (expert
        parallelism re-sharding a freshly initialised replicated router,
        say) leaves post-step-1 state with shardings that differ from what
        step 1's AOT executable was compiled against. ``jax.jit`` would
        transparently compile a second specialisation; an AOT executable
        raises instead. The mismatch is detected during argument
        validation — before any buffer is donated — so the state is intact
        and the plain jit path can take over dispatch for this shape (the
        cost record from the original compile is already registered).
        """
        if exe is jit_fn:
            return exe(*args)
        try:
            return exe(*args)
        except ValueError as err:
            if "compiled for input shardings" not in str(err):
                raise
            logger.info(
                "graft-scope: input shardings drifted from the AOT "
                "compile; handing this step shape back to jax.jit"
            )
            self._compiled[key] = jit_fn
            return jit_fn(*args)

    # -- epochs -----------------------------------------------------------

    def train_epoch(
        self, loader, epoch: int, start_batch: int = 0
    ) -> Dict[str, float]:
        loader.set_epoch(epoch)
        # graft-intake: every host must derive the SAME epoch plan from
        # (seed, epoch, quarantine set); a diverged host silently trains on
        # the wrong samples, so the digest is cross-checked at the epoch
        # boundary and a mismatch hard-fails naming the divergent host
        intake.crosscheck_epoch_plan(loader, epoch)
        acc = MetricAccumulator()
        num_batches = len(loader)
        if start_batch:
            # mid-epoch resume: the sampler's permutation is a pure
            # function of (seed, epoch), so skipping reproduces exactly
            # the uninterrupted run's remaining batches; this epoch's
            # logged train metrics cover the post-resume batches only
            logger.info(
                "Resuming epoch %d at batch %d/%d",
                epoch, start_batch, num_batches,
            )
            it = loader.iter_from(start_batch)
        else:
            it = iter(loader)
        scope = self.scope
        for batch_idx, batch in enumerate(
            _spanned_batches(iter(it), scope), start=start_batch
        ):
            with _span(scope, "train_step", self._global_step):
                if self._profiler is not None:
                    self._profiler.step(self._global_step)
                # deterministic fault injection (no-op without a chaos plan):
                # the poisoned batch keeps its sharding, so the same compiled
                # step executes it — the bad-step cond handles the rest
                batch = chaos.corrupt_batch(batch, self._global_step)
                with self._mesh_ctx():
                    with _span(scope, "aot_lookup"):
                        step_key, step_fn = self._train_executable(batch)
                    with _span(scope, "step"):
                        self.state, metrics = self._dispatch(
                            step_key, step_fn, self.train_step,
                            self.state, batch,
                        )
                self._global_step += 1
                with _span(scope, "metrics_add"):
                    acc.append(metrics)
                if "bad_step" in metrics:
                    # device scalar, no sync — summed against the budget at
                    # the log boundary below
                    self._pending_bad.append(metrics["bad_step"])
                # a FAILED background save surfaces here, within one step of
                # the fault, instead of minutes later at fit's final wait()
                with _span(scope, "saver_check"):
                    self._saver.check()
                # kill-a-slice injection site (graft-elastic): a "kill" fault
                # at="step" SIGKILLs on the nth step BOUNDARY — the in-flight
                # step finished, saves for it may be mid-flight — modeling a
                # preempted slice; no-op without a chaos plan
                chaos.crash_point("step")
                if scope is not None:
                    # rate-limited clock tick + (at boundaries) the one-fetch
                    # health check, straggler exchange, and per-N-step record.
                    # The fence fetches a live VALUE: a device->host transfer
                    # of a step output cannot complete before the step has.
                    # The clock calls it on the steps where it really blocks.
                    def fence(m=metrics):
                        with scope.span("clock_fence"):
                            return float(m["loss"])

                    scope.on_step(self._global_step, metrics, fence=fence)
                if batch_idx % self.log_every == 0 and dist.is_coordinator():
                    with _span(scope, "log_fetch"):
                        loss = float(metrics["loss"])
                        _record_moe_counters(scope, metrics)
                    logger.info(
                        "Epoch %d, Batch %d/%d, Loss: %.4f",
                        epoch, batch_idx, num_batches, loss,
                    )
                if batch_idx % self.log_every == 0:
                    # EVERY process, same cadence (pure function of the batch
                    # index): budget decisions — rollback, hard-fail — must be
                    # taken identically on all hosts
                    self._drain_bad_steps()
                if (
                    self.save_every_steps
                    and self.checkpoint_dir
                    and (batch_idx + 1) % self.save_every_steps == 0
                    and batch_idx + 1 < num_batches  # epoch-end save follows
                ):
                    self._save_mid_epoch(loader, epoch, batch_idx, metrics)
                if self._preempt_requested:
                    # graceful preemption (SIGTERM): the in-flight step has
                    # finished — write `latest` with the cursor, drain the
                    # saver, and unwind. The launcher still treats the exit as
                    # orchestrator teardown (rc 143, no restart); the NEXT
                    # launch resumes from this exact batch.
                    #
                    # Multi-process scope: signal delivery is NOT synchronized
                    # across hosts, so ranks may be at different steps — a save
                    # here would mix per-rank states (and its begin-save
                    # barrier would mismatch in-flight train-step collectives).
                    # Multi-process jobs get bounded loss from the
                    # DETERMINISTICALLY coordinated --save-every-steps saves
                    # (every rank saves at the same batch index) and exit
                    # cleanly here without an extra save.
                    if self.checkpoint_dir and jax.process_count() == 1:
                        self._save_mid_epoch(loader, epoch, batch_idx, metrics)
                        self._saver.wait()
                        logger.info(
                            "Preemption checkpoint complete (epoch %d, batch "
                            "%d)", epoch, batch_idx + 1,
                        )
                    elif self.checkpoint_dir:
                        logger.warning(
                            "SIGTERM on a multi-process job: skipping the "
                            "uncoordinated preemption save; latest periodic "
                            "checkpoint (--save-every-steps) is the resume "
                            "point"
                        )
                    raise PreemptionInterrupt(self._preempt_rc)
        # waits for every dispatched step: the last drain (an epoch tail
        # shorter than log_every) and the fetch of the running sums
        with _span(scope, "epoch_drain"):
            self._drain_bad_steps()
            return acc.result()

    # -- bad-step budget (graft-armor) ------------------------------------

    def _record_event(self, kind: str, **fields) -> None:
        """Recovery-event sink: counts per-surface firings and forwards to
        graft-scope as a first-class record (telemetry/scope.py). Events
        fired before fit creates the scope (e.g. a shard quarantined while
        init samples the first batch) buffer until it exists."""
        if kind == "checkpoint_fallback":
            self.recovery["checkpoint_fallbacks"] += 1
        if self.scope is not None:
            self.scope.record_event(kind, **fields)
        elif len(self._pending_events) < 256:  # bounded: scope may never come
            self._pending_events.append((kind, fields))

    def _drain_bad_steps(self) -> None:
        """Sum the bad-step flags accumulated since the last boundary (ONE
        host fetch of tiny scalars, log cadence) and enforce the budget:
        exceed ⇒ one rollback to the last good checkpoint, exceed again ⇒
        :class:`BadStepBudgetExceeded`. The flags are global reductions —
        identical on every shard — and the cadence is a pure function of
        the batch index, so every process takes the same decision."""
        if not self._pending_bad:
            return
        with _span(self.scope, "bad_step_drain"):
            flags = jax.device_get(self._pending_bad)
        self._pending_bad = []
        new = int(round(sum(float(f) for f in flags)))
        if new == 0:
            return
        self.recovery["bad_steps"] += new
        self._bad_since_recovery += new
        logger.warning(
            "graft-armor: %d nonfinite step(s) skipped device-side "
            "(%d since last recovery, budget %s)",
            new, self._bad_since_recovery,
            self.max_bad_steps or "unlimited",
        )
        self._record_event(
            "bad_step_skip", step=self._global_step, new_skips=new,
            since_recovery=self._bad_since_recovery,
            budget=self.max_bad_steps,
        )
        if self.max_bad_steps and (
            self._bad_since_recovery > self.max_bad_steps
        ):
            self._rollback_or_fail()

    def _rollback_or_fail(self) -> None:
        """One-shot rollback to `latest`, then hard-fail on re-exhaustion.

        The skipped updates never touched params (predication), so the
        rollback discards only the GOOD updates since the checkpoint —
        the price of retrying a fault that by now looks persistent. A
        second exhaustion (or no checkpoint at all) means retrying cannot
        help: surface the fault instead of burning accelerator time.
        """
        latest = (
            os.path.join(self.checkpoint_dir, ckpt_lib.LATEST_NAME)
            if self.checkpoint_dir else None
        )
        if self._rolled_back or not latest or not os.path.exists(latest):
            raise BadStepBudgetExceeded(
                f"{self.recovery['bad_steps']} nonfinite step(s) skipped; "
                f"budget max_bad_steps={self.max_bad_steps} exhausted "
                + ("again after a rollback" if self._rolled_back
                   else "with no checkpoint to roll back to")
                + " — persistent fault (diverged optimization, bad data "
                "shard, or a real numerics bug)"
            )
        self._saver.wait()  # an in-flight save must land before the read
        self.state, epoch, _extra = ckpt_lib.load_checkpoint(
            latest, self.state, self.state_shardings,
            on_event=self._record_event,
        )
        self._rolled_back = True
        self._bad_since_recovery = 0
        self.recovery["rollbacks"] += 1
        logger.warning(
            "graft-armor: bad-step budget exceeded — rolled back to %s "
            "(epoch %d); the next budget exhaustion hard-fails",
            latest, epoch,
        )
        self._record_event(
            "rollback", step=self._global_step, checkpoint=latest,
            epoch=epoch,
        )

    def _save_mid_epoch(self, loader, epoch, batch_idx, metrics):
        """Write `latest` stamped with the CURRENT epoch + loader cursor
        (end-of-epoch saves stamp epoch+1, cursor 0)."""
        extra = {
            "best_accuracy": self._best_accuracy,
            "batch_in_epoch": batch_idx + 1,
        }
        # graft-intake loader_manifest: the full input-plane cursor (epoch,
        # global-batch step, sampler seed, quarantine set) — resume repeats
        # no sample and skips none, even across an elastic reshape (the
        # cursor is in GLOBAL batches, mesh-shape-agnostic)
        man = intake.loader_manifest(loader, epoch, batch_idx + 1)
        if man is not None:
            extra[intake.LOADER_MANIFEST_KEY] = man
        with _span(self.scope, "checkpoint"):
            ckpt_lib.save_checkpoint(
                os.path.join(self.checkpoint_dir, ckpt_lib.LATEST_NAME),
                self.state,
                epoch,
                float(metrics["loss"]),
                extra,
                saver=self._saver,
                sharded=self._sharded_ckpt(),
                retain=self.checkpoint_retain,
                publish=self._publish_channel,
            )

    def validate(self, loader) -> Dict[str, float]:
        acc = MetricAccumulator()
        for batch_idx, batch in enumerate(loader):
            with self._mesh_ctx():
                # device scalar index: one trace for all batches, distinct
                # eval rng per batch (MLM masks must not repeat across val)
                eval_key, eval_fn = self._eval_executable(batch)
                with _span(self.scope, "eval"):
                    acc.append(
                        self._dispatch(
                            eval_key, eval_fn, self.eval_step,
                            self.state, batch,
                            jnp.asarray(batch_idx, jnp.int32),
                        )
                    )
        return acc.result()

    # -- full fit ---------------------------------------------------------

    def fit(
        self,
        train_loader,
        val_loader=None,
        epochs: int = 10,
        resume: Optional[str] = None,
    ) -> List[Dict[str, float]]:
        # `fit` is the root of every span this call opens; `fit_open` is
        # everything before the epoch loop (_fit closes it there, or the
        # stack does when an exception unwinds first)
        with self._bare_span("fit", cpu=True) as fit_span, \
                contextlib.ExitStack() as opening:
            opening.enter_context(self._bare_span("fit_open"))
            return self._fit(
                train_loader, val_loader, epochs, resume,
                getattr(fit_span, "id", None), opening,
            )

    def _fit(self, train_loader, val_loader, epochs, resume, fit_id, opening):
        if self._telemetry_cfg is not None:
            # arm the input-plane event sink BEFORE anything touches the
            # loader (init's sample batch below can already quarantine a
            # corrupt shard); events fired before the scope exists are
            # buffered by _record_event and flushed into it on creation
            self._pending_events = []
            intake.set_event_sink(self._record_event)
        if self.state is None:
            self.init(self._sample_inputs_from(train_loader))

        if self.checkpoint_dir and dist.is_coordinator():
            os.makedirs(self.checkpoint_dir, exist_ok=True)

        from distributed_pytorch_example_tpu.runtime.profiler import StepProfiler
        from distributed_pytorch_example_tpu.train.metrics_writer import MetricsWriter

        self._profiler = (
            StepProfiler(
                self._profile_dir, self._profile_window, dist.process_index()
            )
            if self._profile_dir
            else None
        )
        self._saver.wait()  # a prior fit's pending write must land first
        resuming = bool(resume and os.path.exists(resume))
        writer = MetricsWriter(
            self._metrics_file,
            enabled=dist.is_coordinator(),
            append=resuming,  # fresh runs truncate; resume continues the file
        )

        if self._telemetry_cfg is not None:
            cfg = self._telemetry_cfg
            if cfg.trace_file is None and self._metrics_file:
                # trace-event stream lands next to metrics.jsonl
                cfg = dataclasses.replace(cfg, trace_file=os.path.join(
                    os.path.dirname(self._metrics_file) or ".",
                    "trace_events.json",
                ))
            self.scope = Telemetry(
                cfg,
                writer=writer,
                profiler=self._profiler,
                process_index=dist.process_index(),
                fallback_every=self.log_every,
                root=fit_id,
            )
            # h2d spans from the loaders' transfer path (prefetch thread)
            for loader in (train_loader, val_loader):
                if loader is not None and hasattr(loader, "telemetry"):
                    loader.telemetry = self.scope
            # input-plane events that fired before the scope existed
            # (sink armed at the top of fit) land in the event stream now
            for kind, fields in self._pending_events:
                self.scope.record_event(kind, **fields)
            self._pending_events = []

        start_epoch = 0
        start_batch = 0
        best_accuracy = 0.0
        self.recovery = {
            "bad_steps": 0, "rollbacks": 0, "checkpoint_fallbacks": 0,
        }
        self._pending_bad = []
        self._bad_since_recovery = 0
        self._rolled_back = False
        if resuming:
            # fallback-enabled: a torn/corrupt `latest` walks back to the
            # newest intact ancestor instead of aborting the run; the
            # skip reasons land in the log and the recovery counters
            self.state, saved_epoch, extra = ckpt_lib.load_checkpoint(
                resume, self.state, self.state_shardings,
                on_event=self._record_event,
            )
            start_epoch = saved_epoch
            best_accuracy = float(extra.get("best_accuracy", 0.0))
            # mid-epoch checkpoints (save_every_steps) carry the loader
            # cursor; resume restarts at that exact batch. graft-intake
            # checkpoints stamp the full loader_manifest (seed + quarantine
            # set, validated on restore); unstamped r12-era checkpoints
            # keep today's bare batch_in_epoch behavior.
            man = extra.get(intake.LOADER_MANIFEST_KEY)
            if isinstance(man, dict):
                start_batch = intake.restore_loader_state(
                    train_loader, man, on_event=self._record_event,
                )
            else:
                start_batch = int(extra.get("batch_in_epoch", 0))
            if start_batch >= len(train_loader):
                start_epoch, start_batch = start_epoch + 1, 0
        dist.barrier("pre-train")

        history: List[Dict[str, float]] = []
        start_time = time.time()

        # global step continues from the (possibly restored) state so
        # telemetry records carry true step ids across resume; the profile
        # window is run-relative — rebase re-anchors it at the resumed step
        # (a resume landing past an absolute window would never capture)
        self._global_step = int(jax.device_get(self.state.step))
        if self._profiler is not None:
            self._profiler.rebase(self._global_step)
        # graceful preemption: SIGTERM (orchestrator) and SIGINT (Ctrl-C
        # on a dev box) finish the in-flight step, write `latest` with the
        # loader cursor, and unwind as PreemptionInterrupt (the CLI exits
        # 143 / 130 respectively). Handler installation needs the main
        # thread (tests drive fit() from worker threads: skip there).
        self._preempt_requested = False
        self._preempt_rc = 143
        prev_term = prev_int = None
        if threading.current_thread() is threading.main_thread():
            def _on_signal(signum, frame):
                self._preempt_requested = True
                self._preempt_rc = 130 if signum == signal.SIGINT else 143
                if signum == signal.SIGINT:
                    # a second Ctrl-C must still be able to kill a wedged
                    # run: restore the prior disposition after the first
                    signal.signal(signal.SIGINT, prev_int)
                logger.info(
                    "%s received: checkpointing after the in-flight "
                    "step, then exiting %d",
                    signal.Signals(signum).name, self._preempt_rc,
                )

            prev_term = signal.signal(signal.SIGTERM, _on_signal)
            prev_int = signal.signal(signal.SIGINT, _on_signal)
        opening.close()
        try:
            history, best_accuracy = self._epoch_loop(
                train_loader, val_loader, start_epoch, epochs,
                best_accuracy, writer, start_batch,
            )
        finally:
            with self._bare_span("fit_close"):
                if prev_term is not None:
                    signal.signal(signal.SIGTERM, prev_term)
                if prev_int is not None:
                    signal.signal(signal.SIGINT, prev_int)
                # an exception mid-window must not leave a dangling active
                # jax trace, an unflushed metrics file, or a half-queued save
                intake.set_event_sink(None)  # armed at the top of fit
                if self.scope is not None:
                    self.telemetry_summary = self.scope.close()
                    if self.wire_report is not None:
                        self.telemetry_summary["wire"] = dict(self.wire_report)
                    cache_stats = getattr(
                        getattr(train_loader, "dataset", None),
                        "cache_stats", None,
                    )
                    if cache_stats:
                        self.telemetry_summary["shard_cache"] = dict(cache_stats)
                    for loader in (train_loader, val_loader):
                        if loader is not None and hasattr(loader, "telemetry"):
                            loader.telemetry = None
                    self.scope = None
                if self._profiler is not None:
                    self._profiler.close()
                writer.close()
                if sys.exc_info()[1] is not None:
                    # already unwinding a training exception: a checkpoint-save
                    # failure must not replace it as the primary error
                    try:
                        self._saver.wait()
                    except Exception:
                        logger.exception(
                            "async checkpoint save failed while handling a "
                            "training exception (training error follows)"
                        )
                else:
                    self._saver.wait()

        total_time = time.time() - start_time
        if dist.is_coordinator():
            logger.info("Training completed in %.2fs", total_time)
            if val_loader is not None:
                # best_accuracy carries across resume (checkpoint extra)
                logger.info("Best validation accuracy: %.2f%%", best_accuracy)
        return history

    def _epoch_loop(
        self, train_loader, val_loader, start_epoch, epochs,
        best_accuracy, writer, start_batch=0,
    ):
        """Runs epochs; returns (history, best_accuracy-so-far incl. resume).

        ``self._best_accuracy`` is the single live copy (mid-epoch saves
        read it); the parameter only seeds it across resume.
        """
        history: List[Dict[str, float]] = []
        self._best_accuracy = best_accuracy
        for epoch in range(start_epoch, epochs):
            epoch_start = time.time()
            with _span(self.scope, "train_epoch"):
                train_metrics = self.train_epoch(
                    train_loader, epoch,
                    start_batch=start_batch if epoch == start_epoch else 0,
                )
            train_time = time.time() - epoch_start
            val_metrics = self.validate(val_loader) if val_loader is not None else {}
            epoch_time = time.time() - epoch_start

            global_batch = getattr(train_loader, "global_batch_size", None)
            record = {
                "epoch": epoch,
                "epoch_time": epoch_time,
                "train_time": train_time,
                "train_loss": train_metrics.get("loss", float("nan")),
                "val_loss": val_metrics.get("loss", float("nan")),
                "val_accuracy": val_metrics.get("accuracy", float("nan")),
            }
            # task-specific observability scalars (e.g. MoE
            # moe_dropped_fraction) ride along under their own names
            record.update({
                f"train_{k}": v for k, v in train_metrics.items()
                if k not in ("loss", "accuracy")
            })
            if global_batch:
                # training throughput only: validation time excluded; a
                # mid-epoch-resumed first epoch ran fewer batches
                batches_run = len(train_loader) - (
                    start_batch if epoch == start_epoch else 0
                )
                record["samples_per_sec"] = (
                    batches_run * global_batch / train_time
                )
            history.append(record)
            writer.write(record)

            if dist.is_coordinator():
                logger.info("Epoch %d completed in %.2fs", epoch, epoch_time)
                if "samples_per_sec" in record:
                    logger.info(
                        "  Throughput: %.1f samples/sec",
                        record["samples_per_sec"],
                    )
                logger.info("  Train Loss: %.4f", record["train_loss"])
                if val_loader is not None:
                    logger.info(
                        "  Val Loss: %.4f, Val Accuracy: %.2f%%",
                        record["val_loss"],
                        record["val_accuracy"],
                    )

            is_best = (
                val_loader is not None
                and record["val_accuracy"] > self._best_accuracy
            )
            if is_best:
                self._best_accuracy = record["val_accuracy"]
            if self.checkpoint_dir:
                extra = {"best_accuracy": self._best_accuracy}
                # stamp the input-plane cursor at the NEXT epoch's start —
                # resume re-derives epoch+1's plan plus today's quarantine
                # set, so no quarantined sample sneaks back in after resume
                man = intake.loader_manifest(train_loader, epoch + 1, 0)
                if man is not None:
                    extra[intake.LOADER_MANIFEST_KEY] = man
                with _span(self.scope, "checkpoint"):
                    # epoch+1 so resume continues AFTER the finished epoch
                    if is_best:
                        ckpt_lib.save_checkpoint(
                            os.path.join(
                                self.checkpoint_dir, ckpt_lib.BEST_NAME
                            ),
                            self.state,
                            epoch + 1,
                            record["train_loss"],
                            extra,
                            saver=self._saver,
                            sharded=self._sharded_ckpt(),
                            retain=self.checkpoint_retain,
                        )
                    # publish rides the LATEST save only — best would
                    # double-publish the same params and roll the fleet
                    # twice in one epoch
                    ckpt_lib.save_checkpoint(
                        os.path.join(
                            self.checkpoint_dir, ckpt_lib.LATEST_NAME
                        ),
                        self.state,
                        epoch + 1,
                        record["train_loss"],
                        extra,
                        saver=self._saver,
                        sharded=self._sharded_ckpt(),
                        retain=self.checkpoint_retain,
                        publish=self._publish_channel,
                    )
            dist.barrier("epoch-end")
        return history, self._best_accuracy
