"""The graft-scope facade the Trainer drives.

One :class:`Telemetry` instance per ``fit()``: it owns the cost registry,
the rate-limited step clock, the trace-event writer, and the boundary
logic — fetch the sentinel scalars once, exchange per-host step times,
write an optional per-N-step metrics record, and auto-arm the XLA profiler
(``runtime/profiler.py``) when a health trigger fires (nonfinite grads, or
cross-host skew above threshold). Everything degrades to a no-op when
unconfigured, and the per-step hot path is a counter compare plus (every
``sample_every`` steps) one fenced clock sample.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

from distributed_pytorch_example_tpu.runtime.logging import get_logger
from distributed_pytorch_example_tpu.telemetry.cost import CostRegistry
from distributed_pytorch_example_tpu.telemetry.steptime import (
    StepClock,
    exchange_step_times,
)
from distributed_pytorch_example_tpu.telemetry import compilelog
from distributed_pytorch_example_tpu.telemetry import trace as trace_lib
from distributed_pytorch_example_tpu.telemetry.trace import TraceWriter

logger = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """graft-scope knobs (Trainer kwarg ``telemetry=TelemetryConfig(...)``).

    ``every``: write a metrics.jsonl record every N steps (0 = epoch records
    only — the default keeps the historical file shape). Health checks and
    the straggler exchange still run at the fallback (log) boundary when 0.
    ``sample_every``: true device-fence cadence of the step clock.
    ``trace_file``: Chrome trace-event JSON path (default: next to
    ``metrics.jsonl``; None disables span tracing).
    ``skew_threshold``: max/median per-host step-time ratio that flags slow
    hosts and (with ``auto_arm_profiler``) arms a trace window.
    """

    every: int = 0
    sample_every: int = 8
    trace_file: Optional[str] = None
    skew_threshold: float = 1.5
    auto_arm_profiler: bool = True
    profile_arm_offset: int = 2
    profile_arm_span: int = 2


class Telemetry:
    """Per-run telemetry scope; created by ``Trainer.fit``."""

    def __init__(
        self,
        config: TelemetryConfig,
        writer=None,
        profiler=None,
        process_index: int = 0,
        fallback_every: int = 10,
        root: Optional[int] = None,
    ):
        self.config = config
        # the id of the `fit` span this scope lives under: spans opened on
        # another thread (the loader's prefetch thread) take it as root
        self.root = root
        self._programs_at_open = compilelog.totals()["programs"]
        self.writer = writer
        self.profiler = profiler
        self.costs = CostRegistry()
        self.clock = StepClock(config.sample_every)
        self.trace = (
            TraceWriter(config.trace_file, process_index)
            if config.trace_file and process_index == 0
            else None
        )
        # health checks + straggler exchange cadence: the per-N-step record
        # cadence when enabled, the Trainer's log boundary otherwise (the
        # cadence must be a pure function of the step index — it paces a
        # collective identically on every host)
        self.boundary_every = config.every if config.every > 0 else max(
            int(fallback_every), 1
        )
        self.last_record: Dict[str, object] = {}
        self.last_straggler: Dict[str, object] = {}
        self.overhead_s = 0.0
        self.events: list = []  # recovery/fault events (graft-armor)
        # graft-intake window counters: consumer-side waits on the input
        # plane's prefetch queue since the last boundary (reset per record)
        self._data_wait_ms = 0.0
        self._data_waits = 0
        self._data_stalls = 0
        self._closed = False

    # -- spans ------------------------------------------------------------

    def span(self, name: str, step: Optional[int] = None):
        """The one span call (``telemetry/trace.py::span``): a profiler
        annotation on the device trace's clock, a row of the in-memory
        record, and the Chrome event when a trace file is configured."""
        return trace_lib.span(name, step, self.trace, self.root)

    # -- compiles ---------------------------------------------------------

    def record_compile(self, tag: str, compiled, device=None,
                       extra: Optional[Dict[str, object]] = None):
        """Analyse one AOT compile (XLA's cost and memory analysis, the
        collectives of its HLO text) and register the record."""
        if device is None:
            import jax

            devices = jax.devices()
            device = devices[0] if devices else None
        return self._announce(self.costs.record(tag, compiled, device, extra))

    def register_compile(self, tag: str, rec: Dict[str, object]):
        """Register the record of an executable analysed before (the
        Trainer keeps it beside the executable): the same log line and
        JSONL row, once a scope, and no analysis."""
        return self._announce(self.costs.register(tag, rec))

    def _announce(self, rec: Dict[str, object]):
        tag = rec["tag"]
        flops = rec.get("flops_per_step_per_device")
        logger.info(
            "graft-scope compile[%s]: flops/device=%s, hbm_peak=%s bytes, "
            "collectives=%s",
            tag,
            f"{flops:.3e}" if flops else "n/a",
            rec.get("hbm_peak_bytes"),
            sorted((rec.get("collectives") or {}).keys()) or "none",
        )
        if self.writer is not None and self.config.every > 0:
            self.writer.write({
                "event": "compile",
                "tag": tag,
                "flops_per_step_per_device": flops,
                "hbm_peak_bytes": rec.get("hbm_peak_bytes"),
                "bytes_accessed": rec.get("bytes_accessed"),
                "collectives": rec.get("collectives"),
            })
        return rec

    # -- recovery events --------------------------------------------------

    def record_event(self, kind: str, **fields) -> Dict[str, object]:
        """First-class recovery record (graft-armor): bad-step skips,
        rollbacks, checkpoint fallbacks, retried I/O. Written to the
        metrics JSONL unconditionally (recovery events are rare and
        operationally load-bearing — unlike the per-N-step records they
        are not gated on ``config.every``) and kept on ``self.events``
        for the close() summary."""
        record: Dict[str, object] = {"event": kind, **fields}
        self.events.append(record)
        if self.writer is not None:
            self.writer.write(record)
        return record

    # -- input plane (graft-intake) ---------------------------------------

    def record_data_wait(self, waited_ms: float, stalled: bool) -> None:
        """One consumer-side wait on the input plane's prefetch queue.

        Called by :class:`~..data.intake.PrefetchWorker` from the training
        thread (NOT the worker thread — no locking needed). ``stalled``
        means the queue was empty when the consumer arrived, i.e. this
        step boundary genuinely waited on data rather than compute.
        """
        self._data_waits += 1
        if stalled:
            self._data_wait_ms += waited_ms
            self._data_stalls += 1

    # -- per-step ---------------------------------------------------------

    def on_step(
        self,
        step: int,
        metrics: Dict[str, object],
        fence: Optional[Callable[[], object]] = None,
    ) -> None:
        """Once per train step, after dispatch. ``step`` is the 1-based
        global step; ``fence`` blocks until the step's result is live (the
        clock calls it only every ``sample_every`` steps)."""
        t0 = time.perf_counter()
        self.clock.tick(step, fence or (lambda: None))
        if step % self.boundary_every == 0:
            self._boundary(step, metrics)
        self.overhead_s += time.perf_counter() - t0

    def _boundary(self, step: int, metrics: Dict[str, object]) -> None:
        # ONE host fetch for every boundary scalar (loss + sentinels)
        from distributed_pytorch_example_tpu.train.metrics import (
            fetch_scalars,
        )

        with self.span("boundary_fetch"):
            scalars = fetch_scalars(metrics, keys=(
                "loss", "grad_norm", "param_norm", "nonfinite_grads",
            ))
        straggler = exchange_step_times(
            self.clock.step_time_ms, self.config.skew_threshold
        )
        if straggler:
            self.last_straggler = straggler
        nonfinite = scalars.get("nonfinite_grads")
        if nonfinite:
            logger.warning(
                "graft-scope: %d nonfinite gradient elements at step %d "
                "(grad_norm=%s)",
                int(nonfinite), step, scalars.get("grad_norm"),
            )
        self._maybe_arm_profiler(step, nonfinite, straggler)

        cost = self.costs.get("train_step") or {}
        record: Dict[str, object] = {
            "step": step,
            "step_time_ms": (
                round(self.clock.step_time_ms, 3)
                if self.clock.step_time_ms is not None else None
            ),
            "mfu_analytic": self.costs.mfu_analytic(
                "train_step", self.clock.step_time_ms
            ),
            "flops_per_step_per_device": cost.get(
                "flops_per_step_per_device"
            ),
            "hbm_peak_bytes": cost.get("hbm_peak_bytes"),
            **scalars,
            **straggler,
        }
        if self._data_waits:
            # per-boundary input-plane health: total ms the consumer sat on
            # an empty prefetch queue, and the fraction of batch fetches in
            # this window that stalled at all
            record["data_stall_ms"] = round(self._data_wait_ms, 3)
            record["input_stall_frac"] = round(
                self._data_stalls / self._data_waits, 4
            )
            self._data_wait_ms = 0.0
            self._data_waits = 0
            self._data_stalls = 0
        self.last_record = record
        if self.writer is not None and self.config.every > 0:
            self.writer.write(record)

    def _maybe_arm_profiler(self, step, nonfinite, straggler) -> None:
        if (
            self.profiler is None
            or not self.config.auto_arm_profiler
            or not hasattr(self.profiler, "arm")
        ):
            return
        skew = straggler.get("step_time_skew")
        reason = None
        if nonfinite:
            reason = f"nonfinite grads ({int(nonfinite)} elements)"
        elif skew is not None and skew > self.config.skew_threshold:
            reason = f"cross-host step-time skew {skew:.2f}x"
        if reason:
            self.profiler.arm(
                step + self.config.profile_arm_offset,
                step + self.config.profile_arm_offset
                + self.config.profile_arm_span,
                reason=reason,
            )

    # -- teardown ---------------------------------------------------------

    def close(self) -> Dict[str, object]:
        """Flush the trace and return the run's telemetry summary."""
        if self._closed:
            return {}
        self._closed = True
        if self.trace is not None:
            self.trace.close()
        return {
            "last_record": dict(self.last_record),
            "straggler": dict(self.last_straggler),
            "overhead_s": round(self.overhead_s, 6),
            "events": list(self.events),
            # every program built or fetched while this scope was open (the
            # compile log's names): a step that compiled again mid-run
            # shows here, not as a mystery step time
            "compiles_during_fit": compilelog.names_since(
                self._programs_at_open
            ),
            "compiles": {
                tag: {
                    "flops_per_step_per_device": rec.get(
                        "flops_per_step_per_device"
                    ),
                    "hbm_peak_bytes": rec.get("hbm_peak_bytes"),
                }
                for tag, rec in self.costs.records.items()
            },
        }
