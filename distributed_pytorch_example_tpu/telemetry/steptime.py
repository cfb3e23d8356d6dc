"""Rate-limited step timing + cross-host straggler detection.

The naive way to time steps — fence the device every step — serializes
dispatch and costs exactly the per-step sync the async metrics design
avoids (SURVEY.md §3.2). :class:`StepClock` instead fences TRULY every
``sample_every`` steps (the caller passes a fence that fetches a live value
— a real device->host transfer, an unambiguous fence on any platform) and
amortizes the measured wall time over the
window; steps in between stay fully async.

:func:`exchange_step_times` gathers the per-host sample via
``process_allgather`` (the same collective the checkpoint layer uses,
train/checkpoint.py:140) at log boundaries only, and derives max/median
skew + a slow-host list. At world size 1 it returns ``{}`` — no skew fields
are emitted, by contract.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional


class StepClock:
    """Windowed step timer: a true fence every ``sample_every`` steps.

    ``tick(step, fence)`` once per step, AFTER the step is dispatched. The
    first tick only anchors the window (so compile/warmup time never
    pollutes the first sample); each subsequent window of ``sample_every``
    steps fences once and records the mean per-step wall time.
    """

    def __init__(self, sample_every: int = 8):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, got {sample_every}")
        self.sample_every = sample_every
        self.step_time_ms: Optional[float] = None  # latest true sample
        self._anchor_t: Optional[float] = None
        self._anchor_step: Optional[int] = None

    def tick(self, step: int, fence: Callable[[], object]) -> None:
        if self._anchor_step is None:
            fence()
            self._anchor_t = time.perf_counter()
            self._anchor_step = step
            return
        if step - self._anchor_step < self.sample_every:
            return
        fence()
        now = time.perf_counter()
        self.step_time_ms = (
            (now - self._anchor_t) / (step - self._anchor_step) * 1000.0
        )
        self._anchor_t = now
        self._anchor_step = step


def exchange_step_times(
    step_time_ms: Optional[float], skew_threshold: float = 1.5
) -> Dict[str, object]:
    """Per-host step times + skew at a log boundary; ``{}`` at world size 1.

    Collective: every process must call this at the same boundary (the
    Trainer's boundary cadence is a pure function of the step index, so the
    call pattern is symmetric by construction). ``step_time_ms`` of None
    (no sample yet) skips the exchange — symmetric for the same reason.
    """
    import jax

    if jax.process_count() == 1 or step_time_ms is None:
        return {}
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray([step_time_ms], np.float32)
    )
    times = np.asarray(gathered, np.float64).reshape(-1)
    median = float(np.median(times))
    worst = float(np.max(times))
    out: Dict[str, object] = {
        "step_time_ms_per_host": [round(float(t), 3) for t in times],
        "step_time_ms_median_host": round(median, 3),
        "step_time_ms_max_host": round(worst, 3),
    }
    if median > 0:
        skew = worst / median
        out["step_time_skew"] = round(skew, 4)
        out["slow_hosts"] = [
            i for i, t in enumerate(times) if t > skew_threshold * median
        ]
    return out
