"""The six set-up metrics (``layer_metrics/setup_parts.py``): on a record
made by hand each reads what was planted and the parts add up to the
set-up exactly; a record without an ``import`` row (an older commit) reads
None; and on a real tiny run, in a process of its own so that its record
holds the package's ``import`` row, the parts add up to the window ``fit``'s
open less ``STARTED``."""

import json
import os
import subprocess
import sys

import pytest

from layer_metrics import (
    program_spans,
    setup_before_main_s,
    setup_cpu_s,
    setup_import_s,
    setup_main_s,
    setup_outside_s,
    setup_parts,
    setup_steps_s,
)

MS = 1_000_000
READERS = (setup_import_s, setup_before_main_s, setup_main_s,
           setup_steps_s, setup_outside_s, setup_cpu_s)


def planted_record(with_imports=True):
    """Milliseconds on the training thread (1), from the package's import
    at 1000 to the window's ``fit`` at 21000:

    - ``import`` 1000-4000 (a compile 2000-2500 inside), ``import``
      5000-5500: 3000 ms under the rows, 1500 ms of gaps before main;
    - ``main_args`` 6000-6100, ``main_runtime`` 6100-8000 (a compile
      7000-7500), ``main_data``, ``main_model``, ``main_trainer`` 500 each
      to 9500: 3000 ms;
    - ``fit`` 10000-14000 (``fit_open`` > ``init_state`` > a compile of
      1000 ms, ``fit_close``) and ``fit`` 15000-20000 (``train_step`` > a
      compile of 2000 ms; the prefetch thread's ``h2d`` beside it): 6000 ms;
    - gaps 9500-10000, 14000-15000 (a root compile 14200-14600 in it) and
      20000-21000: 2100 ms outside; 4400 ms of compile wall;
    - a compile at 31000, after the window.
    """
    from distributed_pytorch_example_tpu.telemetry.trace import Span

    rows, ids = [], iter(range(1, 10_000))

    def add(name, start, end, parent=0, root=0, thread=1, args=None):
        ident = next(ids)
        rows.append(Span(name, start * MS, end * MS, thread, ident, parent,
                         root or ident, None, args))
        return ident

    def cpu(open_s, close_s):
        return {"cpu_open_s": open_s, "cpu_close_s": close_s}

    def compile_(name, start, end, parent=0, root=0):
        wall = (end - start) / 1000
        add("compile:" + name, start, end, parent, root, args={
            "trace_s": wall / 2, "lower_s": wall / 4, "compile_s": wall / 4,
            "cache_hit": True,
        })

    if with_imports:
        package = add("import", 1000, 4000, args=cpu(0.1, 3.0))
        compile_("jax_warmup", 2000, 2500, package, package)
        add("import", 5000, 5500, args=cpu(3.2, 3.6))
    add("main_args", 6000, 6100, args=cpu(4.0, 4.1))
    runtime = add("main_runtime", 6100, 8000, args=cpu(4.1, 5.9))
    compile_("mesh_probe", 7000, 7500, runtime, runtime)
    for k, name in enumerate(("main_data", "main_model", "main_trainer")):
        add(name, 8000 + 500 * k, 8500 + 500 * k, args=cpu(6.0, 6.4))
    first = add("fit", 10000, 14000, args=cpu(7.0, 10.0))
    opening = add("fit_open", 10000, 13000, first, first)
    init = add("init_state", 10500, 12500, opening, first)
    compile_("init_fn", 11000, 12000, init, first)
    add("fit_close", 13000, 14000, first, first)
    compile_("make", 14200, 14600)
    second = add("fit", 15000, 20000, args=cpu(11.0, 16.0))
    step = add("train_step", 15500, 19500, second, second)
    compile_("train_step", 16000, 18000, step, second)
    add("h2d", 16000, 17000, 0, second, thread=2)
    window = add("fit", 21000, 30000, args=cpu(20.0, 29.0))
    add("train_step", 21100, 29000, window, window)
    compile_("reference_step", 31000, 40000)  # after the window
    return rows


@pytest.fixture()
def planted(monkeypatch):
    monkeypatch.delattr(sys.modules["__main__"], "STARTED", raising=False)
    monkeypatch.setattr(program_spans, "record", planted_record)


def test_each_part_reads_what_was_planted_and_they_add_up(planted):
    run = {}
    values = [reader.read(run) for reader in READERS]
    assert values == pytest.approx([3.0, 1.5, 3.0, 6.0, 2.1, 19.9])
    parts = setup_parts.read_parts(run)
    assert parts["ns"]["compile"] == 4400 * MS
    # exactly, in nanoseconds: the five parts and the compile wall are the
    # window fit's open less the first import row's start
    assert sum(parts["ns"].values()) == parts["total"] == 20000 * MS
    assert parts["whence"] == "the first import row's start"
    total, imports, before, main, steps, outside, cpu = run["notes"]
    assert ("import 3.000 + before_main 1.500 + main 3.000 + steps 6.000 + "
            "outside 2.100 + compile wall 4.400 = 20.000 s; total 20.000 s") in total
    # the compile rows' own seconds, the reference's after the window left out
    assert "trace + lower + compile args 4.400 s" in total
    assert "2 import rows, wall / process CPU s: 3.000 / 2.900, 0.500 / 0.400" in imports
    assert "0.000 s before the first import row, 1.500 s between" in before
    assert "main_runtime 1.400" in main and "compile spans inside them 0.500" in main
    assert "2 fit calls of 4.000, 5.000 s" in steps
    assert "train_step 2.000" in steps and "init_state 1.000" in steps
    assert "compile spans inside them 3.000" in steps
    assert ("4 gaps; largest, after: fit 2 1.000, main_trainer 0.500, "
            "compile:make 0.400, fit 1 0.200") in outside
    assert "compile spans at the root 0.400" in outside
    assert "19.900 CPU s over 20.000 wall s" in cpu
    assert ("import rows 3.300, main_* 3.100, set-up fits 8.000, "
            "between them 5.500") in cpu


def test_a_record_without_an_import_row_reads_none(monkeypatch):
    monkeypatch.setattr(
        program_spans, "record", lambda: planted_record(with_imports=False)
    )
    run = {}
    assert [reader.read(run) for reader in READERS] == [None] * 6
    assert "notes" not in run


REAL_RUN = r"""
import json, time
STARTED = time.time()
import sys
sys.path.insert(0, "tests")
import tiny
import jax
import pytest
import harness
import run as run_py
from layer_metrics import program_spans, setup_parts
patch = pytest.MonkeyPatch()
tiny.shrink_models(patch)
result, _ = run_py.run_cell(
    tiny.args(seed=2**33 + 7),
    (tiny.cell("gpt2-124m.train-s1024"), tiny.GPT2, tiny.traffic(), tiny.bench()),
    jax.devices()[:1], tiny.PEAK, harness.Clock(STARTED),
)
fit, _ = program_spans.window_fit(program_spans.record())
started_ns = int(STARTED * 1e9) - (time.time_ns() - time.perf_counter_ns())
run = {}
values = {}
for name in ("setup_import_s", "setup_before_main_s", "setup_main_s",
             "setup_steps_s", "setup_outside_s", "setup_cpu_s"):
    values[name] = harness.load_module("layer_metrics", name).read(run)
parts = setup_parts.read_parts(run)
print(json.dumps({
    "correct": result["correct"], "values": values,
    "compile_s": parts["ns"]["compile"] / 1e9,
    "total_s": (fit.start_ns - started_ns) / 1e9,
    "whence": parts["whence"], "imports": len(parts["imports"]),
    "fits": len(parts["fits"]), "notes": run["notes"],
}))
"""


def test_the_parts_add_up_on_a_real_tiny_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", REAL_RUN], cwd=bench_dir, env=env,
        capture_output=True, text=True, timeout=500,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["correct"] is True
    values = got["values"]
    assert all(v is not None and v >= 0 for v in values.values()), values
    five = sum(v for k, v in values.items() if k != "setup_cpu_s")
    assert five + got["compile_s"] == pytest.approx(got["total_s"], abs=0.010)
    assert got["whence"] == "benchmark/run.py's STARTED"
    # the package's row and train.py's; train.main's own fit, the three
    # compared calls and the warm-up
    assert got["imports"] == 2 and got["fits"] == 5
    assert values["setup_import_s"] > 0 and values["setup_steps_s"] > 0
    assert values["setup_cpu_s"] > 0
