"""What every driver of the benchmark shares: where things are, which chip
this is, what compiled, and the one line a run ends with.

Nothing here knows a model, a traffic mix or a metric: those are files that
``run.py`` finds by the names in ``BENCHMARK.json``.
"""

import importlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# a first run of a cell may take 1200 s, because it compiles; a hung
# collective must not hold the chip beyond that, and should say where it hung
WATCHDOG_S = 1150


class NoChip(SystemExit):
    """No accelerator, too few of them, or one that ``peaks.json`` lacks."""


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_cell(workload):
    """(cell, configuration, traffic mix, the whole BENCHMARK.json)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"benchmark: no workload {workload!r}; have {sorted(cells)}"
        )
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(
            f"benchmark: {workload} asks for {cell['chips']} chips but its "
            f"traffic file is written for {traffic['chips']}"
        )
    return cell, config, traffic, bench


def load_peaks():
    return load_json("peaks.json")


def find_chips(chips):
    """The first ``chips`` devices and their peaks; exits non-zero, having
    printed no result, where there is no TPU, fewer chips than the cell
    asks for, or a device kind without published peaks."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(
            f"benchmark: no accelerator: jax.devices()[0].platform is "
            f"{first.platform!r}; a time from this machine would mean nothing"
        )
    if len(devices) < chips:
        raise NoChip(
            f"benchmark: the cell needs {chips} chips, this machine has "
            f"{len(devices)}"
        )
    peaks = load_peaks()
    if first.device_kind not in peaks:
        raise NoChip(
            f"benchmark: no published peaks for device_kind "
            f"{first.device_kind!r} in benchmark/peaks.json"
        )
    return devices[:chips], peaks[first.device_kind]


class CompileLog:
    """JAX's own compile events: how many programs were built or fetched
    from the persistent cache, and the seconds that took."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = self.programs = 0
        self.compile_s = 0.0
        self.names = []  # what was built, in order, for the error message
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += secs
        elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.names.append(str(kwargs.get("fun_name", "?")))

    def snapshot(self):
        return {
            "programs": self.programs, "cache_hits": self.hits,
            "cache_misses": self.misses, "compile_s": self.compile_s,
        }


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest chip, as the backend reports them.

    The TPU runtime counts live buffers (``peak_bytes_in_use``) apart from
    the arena it reserves for a loaded program's temporaries
    (``peak_bytes_reserved``: equal to the compiled step's
    ``memory_analysis()`` temporaries, and held from the program's first
    call on), so what the chip held at its fullest is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0))
        )
    return max(peaks)


def seed_words(seed, n):
    """``n`` 32-bit words drawn from ``--seed`` (any whole number), for the
    places that take only 32 bits."""
    import numpy as np

    return [int(w) for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def say(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def load_module(package, name):
    """``benchmark/<package>/<name>.py``, found by the name in a data file."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    return importlib.import_module(f"{package}.{name}")


def print_result(result, compared):
    """The compared numbers beside their limits as the last lines on
    standard error, then the result as the last line of standard output,
    with the same numbers under a key of its own that comes last."""
    sys.stdout.flush()
    for name, (value, limit) in compared.items():
        print(
            f"benchmark: compared {name} = {value!r} (limit {limit!r})",
            file=sys.stderr,
        )
    sys.stderr.flush()
    line = dict(result)
    line["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in compared.items()
    }
    print(json.dumps(line), flush=True)


class Clock:
    """Seconds since the process started, by the host's clock."""

    def __init__(self, started):
        self.started = started

    def since_start(self):
        return time.time() - self.started
