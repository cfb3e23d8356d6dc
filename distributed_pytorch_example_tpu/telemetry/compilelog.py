"""The program's compile log: one ``compile:<fun_name>`` span per program
JAX built or fetched, in the in-memory span record (:mod:`~.trace`).

One listener on ``jax.monitoring``, installed once a process when the
``telemetry`` package is imported. JAX reports a program in three timed
phases, each with the function's name: tracing
(``/jax/core/compile/jaxpr_trace_duration``, ``fun_name`` ``f``), lowering
(``.../jaxpr_to_mlir_module_duration``, ``jit(f)``) and the backend compile
or the persistent cache's load (``.../backend_compile_duration``,
``jit(f)``), with ``/jax/compilation_cache/cache_hits`` or ``cache_misses``
in between where the cache was asked. The span closes at the third; its
``args`` hold ``trace_s``, ``lower_s``, ``compile_s`` and ``cache_hit``
(None where the persistent cache was not asked). Tracing of a jit nested
inside ``f`` is inside ``f``'s own tracing time and opens no span: only what
is lowered is a program.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List

from jax import monitoring

from distributed_pytorch_example_tpu.telemetry import trace

PREFIX = "compile:"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_PENDING_LIMIT = 256  # traced names that never lowered (eval_shape)

_totals = {
    "programs": 0, "cache_hits": 0, "cache_misses": 0,
    "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
}
_names: collections.deque = collections.deque(maxlen=4096)
_state = threading.local()  # a program's phases arrive on one thread
_lock = threading.Lock()
_installed = False


def _bare(fun_name: str) -> str:
    """``jit(f)`` -> ``f``: lowering and compile wrap the traced name."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_duration(event: str, secs: float, **kwargs) -> None:
    if event == _TRACE:
        traced = getattr(_state, "traced", None)
        if traced is None or len(traced) >= _PENDING_LIMIT:
            traced = _state.traced = {}
        now = time.perf_counter_ns()
        traced[str(kwargs.get("fun_name", "?"))] = (
            secs, now - int(secs * 1e9)
        )
    elif event == _LOWER:
        name = _bare(str(kwargs.get("fun_name", "?")))
        now = time.perf_counter_ns()
        trace_s, start_ns = getattr(_state, "traced", {}).pop(
            name, (0.0, now - int(secs * 1e9))
        )
        _state.program = {
            "name": name, "start_ns": start_ns, "trace_s": trace_s,
            "lower_s": secs, "cache_hit": None,
        }
    elif event == _COMPILE:
        program = getattr(_state, "program", None)
        _state.program = None
        name = _bare(str(kwargs.get("fun_name", "?")))
        now = time.perf_counter_ns()
        if program is None or program["name"] != name:
            # compiled without a lowering of ours (an executable built from
            # a lowered module kept elsewhere)
            program = {
                "name": name, "start_ns": now - int(secs * 1e9),
                "trace_s": 0.0, "lower_s": 0.0, "cache_hit": None,
            }
        args = {
            "trace_s": program["trace_s"], "lower_s": program["lower_s"],
            "compile_s": secs, "cache_hit": program["cache_hit"],
        }
        with _lock:
            _totals["programs"] += 1
            _totals["trace_s"] += args["trace_s"]
            _totals["lower_s"] += args["lower_s"]
            _totals["compile_s"] += secs
            _names.append(name)
        trace.add(PREFIX + name, program["start_ns"], now, args)


def _on_event(event: str, **_kwargs) -> None:
    if event != _HIT and event != _MISS:
        return
    hit = event == _HIT
    program = getattr(_state, "program", None)
    if program is not None:
        program["cache_hit"] = hit
    with _lock:
        _totals["cache_hits" if hit else "cache_misses"] += 1


def install() -> None:
    """Register the listener; once a process, however often called."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def totals() -> Dict[str, float]:
    """Programs, persistent-cache hits and misses, and the seconds of each
    phase, since the process started."""
    with _lock:
        return dict(_totals)


def names_since(programs: int) -> List[str]:
    """Names of the programs logged after the first ``programs`` (a
    ``totals()["programs"]`` read earlier), oldest first."""
    with _lock:
        new = _totals["programs"] - programs
        return list(_names)[-new:] if new > 0 else []
