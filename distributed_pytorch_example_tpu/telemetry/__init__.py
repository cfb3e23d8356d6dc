"""graft-scope: always-on, low-overhead training telemetry.

The reference's observability is print-lines and wall-clock epoch timing
(reference train.py:265,283-290; SURVEY.md §5 "Tracing/profiling: ABSENT").
graft-scope rebuilds that surface TPU-first around four pillars:

- **compile-time cost registry** (:mod:`~.cost`): every train/eval-step
  compile records XLA's ``cost_analysis()`` / ``memory_analysis()`` plus the
  compiled collective mix, so analytical MFU and HBM headroom are per-run
  telemetry instead of offline analysis;
- **device-side health sentinels** (:mod:`~.sentinels`): global grad-norm,
  param-norm and nonfinite-grad count computed INSIDE the jitted step and
  fetched once per log boundary — no added per-step host syncs (the
  ``host-sync`` graft-lint rule stays clean over the instrumented step);
- **step-time + straggler telemetry** (:mod:`~.steptime`): a rate-limited
  host clock (true fence every K steps, async otherwise) with per-host step
  times exchanged via ``process_allgather`` at log boundaries, emitting
  max/median skew and flagging slow hosts (gracefully absent at world
  size 1);
- **span tracing** (:mod:`~.trace`): every span the program opens goes
  through ONE call, ``Telemetry.span(name)`` (module-level
  ``trace.span(name)`` before ``fit`` has built its scope), which (1) opens
  a ``jax.profiler.TraceAnnotation`` of the same name — a
  ``StepTraceAnnotation("train_step", step_num=...)`` for the per-step
  span — so that whenever a profiler session runs (``--profile-dir``, an
  auto-armed window, the benchmark's ``--trace 1``) the span lies in the
  ``.xplane.pb`` on the device planes' clock and a device gap can be laid
  against it; (2) appends a :class:`~.trace.Span` (name, start_ns, end_ns,
  thread, id, parent, root, step) to one bounded in-memory record,
  ``telemetry.trace.recorded()`` (``clear()`` for tests); (3) streams the
  Chrome trace-event JSON (Perfetto / chrome://tracing) next to
  ``metrics.jsonl`` when a trace file is configured. With
  ``--no-telemetry`` all three are off. The span names:

  ====================================  =====================================
  ``main_args`` ``main_runtime``        ``train.py::main`` (distributed init,
  ``main_data`` ``main_model``          mesh and compile cache; datasets;
  ``main_trainer``                      model + partitioner; loaders,
                                        optimizer, Trainer)
  ``init_state``                        ``Trainer.init``
  ``fit`` > ``fit_open``,               ``Trainer.fit``: before the epoch
  ``train_epoch``, ``fit_close``        loop / one epoch / record + teardown
  ``data_load`` (wait)                  the training thread's ``next()`` on
                                        the loader's prefetch queue
  ``train_step`` > ``aot_lookup``       one loop body (step annotation);
  (> ``record_compile``), ``step``,     the executable's lookup, the
  ``metrics_add``, ``saver_check``      dispatch, the running metric sums,
                                        the background saver's check.
                                        ``record_compile`` opens only where
                                        the step was compiled and analysed:
                                        a later ``fit`` on the Trainer is
                                        given the kept record, with no span
  ``clock_fence`` ``boundary_fetch``    the four places the training thread
  ``log_fetch`` ``bad_step_drain``      blocks on the device: step clock's
  (wait)                                fence (every 8th step), the boundary
                                        scalars, the log line's loss, the
                                        bad-step flags (every 10th)
  ``epoch_drain`` (wait)                the epoch's last drain + metric
                                        means: waits for every step
  ``assemble`` ``h2d``                  the loader's prefetch thread
  ``eval`` ``checkpoint``               validation dispatch, saves
  ``compile:<fun_name>``                the compile log, below
  ====================================  =====================================

- **compile log** (:mod:`~.compilelog`): one ``jax.monitoring`` listener,
  installed when this package is imported, records a span
  ``compile:<fun_name>`` per program built or fetched, with the seconds of
  tracing, lowering and backend compile (or persistent-cache load) and
  whether the cache hit; ``Telemetry.close()``'s summary lists the
  programs of the run as ``compiles_during_fit``.

graft-lens extends the same substrate to serving:

- **request tracing + rolling latency histograms** (:mod:`~.trace`
  counters/instants + :mod:`~.lens`): router→replica→engine request
  spans on per-replica Perfetto pids, queue-depth/KV-occupancy counter
  tracks, and bounded p50/p99 windows for TTFT/TPOT/queue-wait/journal
  lag surfaced in ``serve.py``'s JSON line;
- **serve-side self-arming sentinels** (:mod:`~.sentinels`
  ``ServeSentinels``): TPOT p99 regression, straggler replica, KV-pool
  pressure — auto-arm the XLA profiler and stamp ``trigger`` events.

:class:`~.scope.Telemetry` is the facade the Trainer drives; everything here
degrades to a no-op when unconfigured.
"""

from distributed_pytorch_example_tpu.telemetry.lens import (  # noqa: F401
    LatencyBook,
    RollingStats,
)

from distributed_pytorch_example_tpu.telemetry.cost import (  # noqa: F401
    CostRegistry,
    compiled_cost_record,
    peak_bf16_flops,
)
from distributed_pytorch_example_tpu.telemetry.scope import (  # noqa: F401
    Telemetry,
    TelemetryConfig,
)
from distributed_pytorch_example_tpu.telemetry import compilelog
from distributed_pytorch_example_tpu.telemetry.sentinels import (  # noqa: F401
    SENTINEL_KEYS,
    SERVE_TRIGGER_KINDS,
    ServeSentinels,
    sentinel_metrics,
)
from distributed_pytorch_example_tpu.telemetry.steptime import (  # noqa: F401
    StepClock,
    exchange_step_times,
)
from distributed_pytorch_example_tpu.telemetry.trace import (  # noqa: F401
    PrefixedTrace,
    TraceWriter,
)

compilelog.install()
