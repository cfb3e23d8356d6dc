"""The program's own spans: one call, one clock, one in-memory record.

``telemetry/trace.py::span`` (which ``Telemetry.span`` calls) opens a
profiler annotation, appends to the bounded process-wide record and writes
the Chrome event; ``telemetry/compilelog.py`` adds one ``compile:<name>``
span a program. Pinned here: what a record row holds, the parent/root
rules, where an instrumented ``fit`` puts each span (names are a contract
the benchmark's per-layer readers rely on), that the same names land in a
``jax.profiler`` trace on the training thread, the compile log, and the
``chunked_ce`` / ``optimizer`` scopes inside the lowered step, the expert
layer's and the short convolution's scopes, latent attention's, the shared
expert's and the prediction module's (``mla_proj``, ``moe_shared``, ``mtp``),
and the ``moe_counters`` rows.
"""

import collections
import copy
import gc
import glob
import json
import os
import re
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_pytorch_example_tpu as dpx
from distributed_pytorch_example_tpu.telemetry import (
    Telemetry,
    TelemetryConfig,
    compilelog,
    trace,
)
from distributed_pytorch_example_tpu.train.loop import _span

WAITS = ("clock_fence", "boundary_fetch", "log_fetch", "bad_step_drain")
STEPS = 24  # batches of the shared fit: the 8th and the 10th step, twice


@pytest.fixture()
def record():
    trace.clear()
    yield trace
    trace.clear()


def _dur(span):
    return span.end_ns - span.start_ns


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------


def test_record_row_keeps_name_times_thread_parent_step(record):
    with trace.span("outer") as outer:
        with trace.span("inner", step=7):
            pass
    inner, out = record.recorded()  # closed in that order
    assert (inner.name, out.name) == ("inner", "outer")
    assert inner.step == 7 and out.step is None
    assert inner.parent == out.id == outer.id and out.parent == 0
    assert inner.root == out.root == out.id
    assert inner.thread == out.thread == threading.get_ident()
    assert out.start_ns <= inner.start_ns <= inner.end_ns <= out.end_ns
    assert inner.args is None


def test_record_drops_the_oldest_beyond_its_bound(record, monkeypatch):
    monkeypatch.setattr(trace, "_record", collections.deque(maxlen=4))
    for i in range(10):
        with trace.span(f"s{i}"):
            pass
    assert [s.name for s in trace.recorded()] == ["s6", "s7", "s8", "s9"]
    assert trace.RECORD_LIMIT >= 1 << 14  # hours of steps, not minutes


def test_parent_is_the_span_open_on_the_same_thread(record):
    seen = {}

    def other():
        with trace.span("elsewhere") as s:
            seen["span"] = s

    with trace.span("here") as here:
        t = threading.Thread(target=other)
        t.start()
        t.join()
        with trace.span("child"):
            pass
    by_name = {s.name: s for s in record.recorded()}
    # another thread's stack is its own: no parent, its own root
    assert by_name["elsewhere"].parent == 0
    assert by_name["elsewhere"].root == seen["span"].id != here.id
    assert by_name["elsewhere"].thread != by_name["here"].thread
    assert by_name["child"].parent == here.id


def test_root_adopts_another_threads_span(record):
    """The loader's prefetch thread opens spans with the ``fit`` span's id
    as root: its spans belong to that call's tree without a parent."""
    with trace.span("fit_like") as root:
        def worker():
            with trace.span("h2d_like", root=root.id):
                with trace.span("nested"):
                    pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by_name = {s.name: s for s in record.recorded()}
    assert by_name["h2d_like"].parent == 0
    assert by_name["h2d_like"].root == root.id
    assert by_name["nested"].root == root.id
    assert by_name["nested"].parent == by_name["h2d_like"].id


def test_span_closes_on_an_exception_and_the_stack_unwinds(record):
    with pytest.raises(KeyError):
        with trace.span("outer"):
            with trace.span("raises"):
                raise KeyError("x")
    with trace.span("after"):
        pass
    by_name = {s.name: s for s in record.recorded()}
    assert set(by_name) == {"outer", "raises", "after"}
    assert by_name["after"].parent == 0  # nothing left open


def test_telemetry_span_feeds_record_and_chrome_file_together(
        record, tmp_path):
    path = tmp_path / "trace_events.json"
    scope = Telemetry(TelemetryConfig(trace_file=str(path)), root=41)
    with scope.span("step"):
        with scope.span("train_step", step=3):
            pass
    scope.close()
    rows = record.recorded()
    assert [(s.name, s.step) for s in rows] == [("train_step", 3), ("step", None)]
    events = [e for e in json.loads(path.read_text()) if e["ph"] == "X"]
    assert [e["name"] for e in events] == ["train_step", "step"]
    # one clock: the Chrome event is the record's row in microseconds
    for row, event in zip(rows, events):
        assert event["ts"] == row.start_ns // 1000
        assert event["dur"] == max(_dur(row) // 1000, 1)
    # no parent on this thread: the scope's root (the `fit` span) adopts
    assert rows[-1].root == 41


def _tiny_fit_parts(checkpoint_dir="", **kw):
    mesh = dpx.runtime.make_mesh()
    trainer = dpx.train.Trainer(
        dpx.models.SimpleNet(hidden_size=32),
        dpx.train.ClassificationTask(),
        optax.adam(1e-3),
        partitioner=dpx.parallel.data_parallel(mesh),
        checkpoint_dir=checkpoint_dir, **kw,
    )
    ds = dpx.data.SyntheticClassificationDataset(num_samples=64, input_size=784)
    return trainer, lambda: dpx.data.DeviceLoader(ds, 16, mesh=mesh, seed=0)


def test_telemetry_off_opens_no_span(devices, record, tmp_path):
    assert _span(None, "step") is trace.no_span("step")
    with _span(None, "train_step", 3):
        pass
    trainer, loader = _tiny_fit_parts(telemetry=False)
    trainer.fit(loader(), epochs=1)
    names = {s.name for s in record.recorded()}
    # the compile log is the process's, not the Trainer's: it stays on
    assert {n for n in names if not n.startswith(compilelog.PREFIX)} == set()


# ---------------------------------------------------------------------------
# one instrumented fit: where each span goes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fit_record(tmp_path_factory):
    """One tiny ``fit`` of ``STEPS`` steps under a ``jax.profiler`` trace:
    (the record's rows, the trace directory, the telemetry summary)."""
    tmp = tmp_path_factory.mktemp("spans")
    mesh = dpx.runtime.make_mesh()
    trainer = dpx.train.Trainer(
        dpx.models.SimpleNet(hidden_size=256),
        dpx.train.ClassificationTask(),
        optax.adam(1e-3),
        partitioner=dpx.parallel.data_parallel(mesh),
        checkpoint_dir="",
    )
    ds = dpx.data.SyntheticClassificationDataset(
        num_samples=64 * STEPS, input_size=784
    )
    loader = dpx.data.DeviceLoader(ds, 64, mesh=mesh, seed=0)
    trainer.init(next(iter(loader))["x"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    trace.clear()
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    try:
        trainer.fit(loader, None, epochs=1)
    finally:
        jax.profiler.stop_trace()
    rows = trace.recorded()
    trace.clear()
    return rows, str(tmp), trainer.telemetry_summary


def _children(rows, parent):
    return [s for s in rows if s.parent == parent.id]


def _covered(rows, parent):
    """Share of ``parent`` that its children cover."""
    return sum(_dur(s) for s in _children(rows, parent)) / _dur(parent)


def test_fit_is_one_root_with_open_epoch_close(devices, fit_record):
    rows, _, _ = fit_record
    (fit,) = [s for s in rows if s.name == "fit"]
    assert fit.parent == 0 and fit.root == fit.id
    kids = sorted(_children(rows, fit), key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["fit_open", "train_epoch", "fit_close"]
    # everything the call opened, on either thread, hangs under it
    assert {s.root for s in rows if not s.name.startswith(compilelog.PREFIX)} \
        == {fit.id}
    (epoch,) = [s for s in rows if s.name == "train_epoch"]
    assert {s.name for s in _children(rows, epoch)} == {
        "train_step", "data_load", "epoch_drain",
    }


def test_one_train_step_span_a_step_with_consecutive_numbers(
        devices, fit_record):
    rows, _, _ = fit_record
    steps = [s for s in rows if s.name == "train_step"]
    assert [s.step for s in steps] == list(range(STEPS))
    for step in steps:
        names = [s.name for s in _children(rows, step)]
        assert names[:2] == ["aot_lookup", "step"] or names[:1] == ["step"], names
        for name in ("aot_lookup", "step", "metrics_add", "saver_check"):
            assert names.count(name) == 1, (step.step, names)
    # the loader's wait: one next() a step and the one that ends the epoch
    assert sum(s.name == "data_load" for s in rows) == STEPS + 1


def test_wait_spans_fall_where_their_cadence_says(devices, fit_record):
    rows, _, _ = fit_record
    by_id = {s.id: s for s in rows}
    where = {name: [] for name in WAITS}
    for s in rows:
        if s.name in WAITS and by_id[s.parent].name == "train_step":
            where[s.name].append(by_id[s.parent].step)
    # the step clock anchors on the first step and fences every 8th after
    assert where["clock_fence"] == [0, 8, 16]
    # boundary: the 10th, 20th step taken (1-based); log line and bad-step
    # drain: batch 0, 10, 20
    assert where["boundary_fetch"] == [9, 19]
    assert where["log_fetch"] == [0, 10, 20]
    assert where["bad_step_drain"] == [0, 10, 20]
    (drain,) = [s for s in rows if s.name == "epoch_drain"]
    assert by_id[drain.parent].name == "train_epoch"
    # the epoch's tail (steps 21..23) is drained inside epoch_drain
    assert [s.name for s in _children(rows, drain)] == ["bad_step_drain"]


def test_children_tile_their_parent_within_five_per_cent(devices, fit_record):
    rows, _, _ = fit_record
    (fit,) = [s for s in rows if s.name == "fit"]
    (epoch,) = [s for s in rows if s.name == "train_epoch"]
    assert _covered(rows, fit) >= 0.95
    assert _covered(rows, epoch) >= 0.95
    steps = [s for s in rows if s.name == "train_step"]
    inside = sum(
        _dur(c) for step in steps for c in _children(rows, step)
    )
    assert inside / sum(_dur(s) for s in steps) >= 0.95


def test_loader_spans_come_from_the_prefetch_thread(devices, fit_record):
    rows, _, _ = fit_record
    (fit,) = [s for s in rows if s.name == "fit"]
    loads = [s for s in rows if s.name in ("assemble", "h2d")]
    assert sum(s.name == "assemble" for s in loads) >= STEPS
    assert sum(s.name == "h2d" for s in loads) >= STEPS
    assert {s.thread for s in loads}.isdisjoint({fit.thread})
    assert all(s.parent == 0 and s.root == fit.id for s in loads)


def test_summary_lists_the_programs_compiled_during_fit(devices, fit_record):
    rows, _, summary = fit_record
    logged = [
        s.name[len(compilelog.PREFIX):] for s in rows
        if s.name.startswith(compilelog.PREFIX)
    ]
    assert "train_step" in logged
    assert summary["compiles_during_fit"] == logged
    # the step's compile hangs under the lookup that triggered it
    by_id = {s.id: s for s in rows}
    (step_compile,) = [s for s in rows if s.name == "compile:train_step"]
    assert by_id[step_compile.parent].name == "aot_lookup"
    (registered,) = [s for s in rows if s.name == "record_compile"]
    assert by_id[registered.parent].name == "aot_lookup"


# ---------------------------------------------------------------------------
# the cost record lives with the executable: a later fit is given it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def two_fits(tmp_path_factory):
    """Two ``fit`` calls with validation on ONE Trainer, ``every=1``. For
    each call: the record's rows, the summary, the registry the scope held
    when it closed, the JSONL's compile rows, and how many cost analyses
    had run by its end."""
    from distributed_pytorch_example_tpu.telemetry import cost

    ckpt = tmp_path_factory.mktemp("two_fits") / "ckpt"
    trainer, loader = _tiny_fit_parts(
        str(ckpt), telemetry=TelemetryConfig(every=1)
    )
    registries, analysed, calls = [], [], []
    close, analyse = Telemetry.close, cost.compiled_cost_record

    def closing(scope):
        registries.append(copy.deepcopy(scope.costs.records))
        return close(scope)

    def analysing(compiled, device=None):
        analysed.append(1)
        return analyse(compiled, device)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(Telemetry, "close", closing)
        patch.setattr(cost, "compiled_cost_record", analysing)
        for _ in range(2):
            trace.clear()
            trainer.fit(loader(), loader(), epochs=1)
            rows = [
                json.loads(line)
                for line in (ckpt / "metrics.jsonl").read_text().splitlines()
            ]
            calls.append({
                "rows": trace.recorded(),
                "summary": trainer.telemetry_summary,
                "registry": registries[-1],
                "jsonl": {
                    r["tag"]: r for r in rows if r.get("event") == "compile"
                },
                "analysed": len(analysed),
            })
    trace.clear()
    return calls


@pytest.mark.parametrize("tag", ["train_step", "eval_step"])
def test_a_later_fit_is_given_the_record_and_analyses_nothing(
        devices, two_fits, tag):
    first, second = two_fits
    by_id = {s.id: s for s in first["rows"]}
    (registered,) = [s for s in first["rows"] if s.name == "record_compile"]
    assert by_id[registered.parent].name == "aot_lookup"
    # the second call finds both executables: no analysis, no such span,
    # nothing compiled
    assert first["analysed"] == 2 and second["analysed"] == 2
    assert not [s for s in second["rows"] if s.name == "record_compile"]
    assert second["summary"]["compiles_during_fit"] == []
    # and its scope holds what the first one derived, value for value
    record = second["registry"][tag]
    assert record == first["registry"][tag]
    assert set(record) >= {
        "tag", "flops_per_step_per_device", "bytes_accessed",
        "hbm_peak_bytes", "collectives", "device_kind", "peak_bf16_flops",
    }
    assert record["tag"] == tag and record["flops_per_step_per_device"] > 0
    assert second["summary"]["compiles"][tag] == first["summary"]["compiles"][tag]
    assert second["summary"]["compiles"][tag] == {
        "flops_per_step_per_device": record["flops_per_step_per_device"],
        "hbm_peak_bytes": record["hbm_peak_bytes"],
    }
    # every run's JSONL says what was compiled, once a scope
    assert second["jsonl"][tag] == first["jsonl"][tag]
    assert first["jsonl"][tag]["flops_per_step_per_device"] == \
        record["flops_per_step_per_device"]
    assert set(first["jsonl"]) == {"train_step", "eval_step"}


def test_compiled_stays_a_map_of_executables_that_clear_frees(devices, record):
    """What the benchmark's driver and ``chip_smoke.py`` lean on:
    ``_compiled`` maps ``("train", ...)`` to the executable itself, and
    ``clear()`` lets it go, the kept record holding no reference to it;
    the next ``fit`` then compiles, analyses and records anew."""
    trainer, loader = _tiny_fit_parts()
    trainer.fit(loader(), None, epochs=1)
    ((key, exe),) = trainer._compiled.items()
    assert key[0] == "train" and exe.memory_analysis() is not None
    kept = trainer._cost_records[key]
    summary = trainer.telemetry_summary["compiles"]["train_step"]
    assert summary.items() <= kept.items()
    gone = weakref.ref(exe)
    del exe
    trainer._compiled.clear()
    gc.collect()
    assert gone() is None
    record.clear()
    trainer.fit(loader(), None, epochs=1)
    rows = record.recorded()
    by_id = {s.id: s for s in rows}
    (registered,) = [s for s in rows if s.name == "record_compile"]
    assert by_id[registered.parent].name == "aot_lookup"
    # (jax's own in-process cache may answer the compile: no compile-log row)
    assert list(trainer._compiled) == [key]
    assert trainer._compiled[key].memory_analysis() is not None
    assert trainer._cost_records[key] is not kept
    assert trainer._cost_records[key] == kept


def test_a_shape_handed_back_to_jit_keeps_its_compile_record(devices, record):
    """After ``_dispatch``'s sharding-drift fallback the shape's entry is
    the ``jit`` function; a later ``fit`` still reports the compile's
    record, and analyses nothing."""
    trainer, loader = _tiny_fit_parts()
    trainer.fit(loader(), None, epochs=1)
    first = trainer.telemetry_summary["compiles"]
    (key,) = trainer._compiled
    trainer._compiled[key] = trainer.train_step  # what the fallback leaves
    record.clear()
    trainer.fit(loader(), None, epochs=1)
    assert trainer.telemetry_summary["compiles"] == first
    assert first["train_step"]["flops_per_step_per_device"] > 0
    assert not [s for s in record.recorded() if s.name == "record_compile"]


def test_profiler_trace_holds_the_same_names_on_the_training_thread(
        devices, fit_record):
    """Whenever a profiler session runs, the spans lie in the .xplane.pb
    (read with ``ProfileData``) on the thread that opened them."""
    from jax.profiler import ProfileData

    rows, directory, _ = fit_record
    (path,) = glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")
    )
    lines = [
        line for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for line in plane.lines
    ]
    training = [
        line for line in lines
        if any(e.name == "train_step" for e in line.events)
    ]
    assert len(training) == 1  # one thread carries every step
    events = list(training[0].events)
    names = collections.Counter(e.name for e in events)
    (fit,) = [s for s in rows if s.name == "fit"]
    recorded = collections.Counter(
        s.name for s in rows
        if s.thread == fit.thread and not s.name.startswith(compilelog.PREFIX)
    )
    for name, count in recorded.items():
        assert names[name] == count, (name, names[name], count)
    # the step annotation carries its number
    numbers = sorted(
        int(dict(e.stats)["step_num"]) for e in events if e.name == "train_step"
    )
    assert numbers == list(range(STEPS))
    # and the prefetch thread's spans sit on another line
    others = collections.Counter(
        e.name for line in lines if line is not training[0]
        for e in line.events
    )
    assert others["h2d"] >= STEPS and names["h2d"] == 0


# ---------------------------------------------------------------------------
# the compile log
# ---------------------------------------------------------------------------


def test_compile_log_records_a_fresh_jit_once(record):
    @jax.jit
    def fresh_program_for_the_log(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.ones((4, 4))  # its own little programs compile here
    before = compilelog.totals()
    record.clear()
    fresh_program_for_the_log(x)
    (span,) = [
        s for s in record.recorded()
        if s.name == "compile:fresh_program_for_the_log"
    ]
    assert set(span.args) == {"trace_s", "lower_s", "compile_s", "cache_hit"}
    assert span.args["trace_s"] > 0 and span.args["lower_s"] > 0
    assert span.args["compile_s"] > 0
    total = span.args["trace_s"] + span.args["lower_s"] + span.args["compile_s"]
    assert _dur(span) >= 0.9 * total * 1e9
    after = compilelog.totals()
    assert after["programs"] == before["programs"] + 1
    assert compilelog.names_since(before["programs"]) == [
        "fresh_program_for_the_log"
    ]
    # the second call is served by jit's own cache: nothing is logged
    record.clear()
    fresh_program_for_the_log(x)
    assert record.recorded() == []
    assert compilelog.totals()["programs"] == after["programs"]


def test_compile_log_span_hangs_under_the_open_span(record):
    with trace.span("aot_lookup_like") as parent:
        jax.jit(lambda x: x * 5.0 - 2.0).lower(jnp.ones(3)).compile()
    compiled = [
        s for s in record.recorded() if s.name.startswith(compilelog.PREFIX)
    ]
    assert compiled and all(s.parent == parent.id for s in compiled)
    assert all(s.root == parent.id for s in compiled)


def test_compile_log_install_is_idempotent(record):
    compilelog.install()
    compilelog.install()
    x = jnp.ones(5)  # its own program compiles here
    before = compilelog.totals()["programs"]
    jax.jit(lambda x: x / 7.0 + 0.5)(x)
    assert compilelog.totals()["programs"] == before + 1  # not doubled


# ---------------------------------------------------------------------------
# names inside the compiled step
# ---------------------------------------------------------------------------


def test_lowered_step_carries_chunked_ce_and_optimizer_scopes():
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = dpx.models.get_model(
        "gpt2", logits_mode="hidden", vocab_size=64, max_len=16,
        model_dim=16, num_layers=1, num_heads=2, mlp_dim=32,
    )
    trainer = dpx.train.Trainer(model, CausalLMTask(), optax.adam(1e-3))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    trainer.init(batch["tokens"])
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True
    )
    scopes = {
        re.sub(r"/[^/]*$", "", name)
        for name in re.findall(r'loc\("(jit\(train_step\)[^"]*)"', text)
    }
    # forward, and the transposed ops of the custom VJP's backward
    assert "jit(train_step)/jvp(chunked_ce)" in scopes
    assert "jit(train_step)/transpose(jvp(chunked_ce))" in scopes
    # Adam inside the bad-step cond's taken branch
    assert any(s.endswith("/optimizer") for s in scopes), scopes


TINY_LFM2 = dict(
    vocab_size=64, model_dim=16, num_heads=4, num_kv_heads=1, mlp_dim=32,
    moe_mlp_dim=8, num_experts=8, top_k=2, layers_kept=(0, 2, 3),
    num_dense_layers=1, experts_first=0, experts_held=4,
    logits_mode="hidden",
)
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "short_conv")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lowered_step_carries_the_expert_and_convolution_scopes(remat):
    """The names the benchmark's ``named_scopes`` reader looks for, as path
    components of the lowered step's op names, forward and backward."""
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = dpx.models.get_model("lfm2-8b-a1b", remat=remat, **TINY_LFM2)
    trainer = dpx.train.Trainer(model, CausalLMTask(), optax.adam(1e-3))
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32)}
    trainer.init(batch["tokens"])
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True
    )
    names = re.findall(r'loc\("(jit\(train_step\)[^"]*)"', text)
    for scope in MOE_SCOPES + ("chunked_ce", "optimizer"):
        component = re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)")
        found = [n for n in names if component.search(n)]
        assert found, scope
        if scope != "optimizer":  # the backward pass carries the name too
            assert any("transpose(" in n for n in found), scope


TINY_JOYAI = dict(
    vocab_size=64, model_dim=16, num_heads=2, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, mlp_dim=32,
    moe_mlp_dim=8, num_experts=8, top_k=2, layers_kept=(0, 1),
    experts_first=0, experts_held=4, logits_mode="hidden",
)
JOYAI_SCOPES = ("mla_proj", "moe_shared", "mtp")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lowered_step_carries_the_latent_attention_and_module_scopes(remat):
    """The names the benchmark's ``nested_scopes`` reader looks for, as
    path components of the lowered step's op names, forward and backward;
    the prediction module's own layer carries ``mtp`` AND what nests in it,
    its pass through the head carries ``chunked_ce`` and not ``mtp``; the
    step's metrics hold the two losses apart."""
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = dpx.models.get_model("joyai-llm-flash", remat=remat, **TINY_JOYAI)
    trainer = dpx.train.Trainer(model, CausalLMTask(), optax.adam(1e-3))
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32)}
    trainer.init(batch["tokens"])
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True
    )
    names = re.findall(r'loc\("(jit\(train_step\)[^"]*)"', text)

    def carrying(scope, among=names):
        component = re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)")
        return [n for n in among if component.search(n)]

    for scope in JOYAI_SCOPES + MOE_SCOPES[:3] + ("chunked_ce", "optimizer"):
        found = carrying(scope)
        assert found, scope
        if scope != "optimizer":  # the backward pass carries the name too
            assert any("transpose(" in n for n in found), scope
    inside = carrying("mtp")
    assert carrying("mla_proj", inside) and carrying("moe_shared", inside)
    assert any("layer_40" in n for n in inside)
    assert not carrying("chunked_ce", inside)
    assert len(carrying("mla_proj")) > len(carrying("mla_proj", inside))
    _, metrics = trainer.train_step(trainer.state, batch)
    assert {"loss_next", "loss_mtp", "moe_held_share"} <= set(metrics)


def test_fit_leaves_moe_counters_rows_at_each_log_fetch(devices, record):
    """One ``moe_counters`` row per ``log_fetch``, inside it, with the
    step's four counters as ``args``; the epoch record carries their means
    as ``train_moe_<name>``; a model without experts leaves none."""
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    class Rows:
        tokens = np.random.default_rng(0).integers(0, 64, (48, 16)).astype(np.int32)

        def __len__(self):
            return len(self.tokens)

        def __getitem__(self, i):
            return {"tokens": self.tokens[i]}

    mesh = dpx.runtime.make_mesh(devices=jax.devices()[:1])
    trainer = dpx.train.Trainer(
        dpx.models.get_model("lfm2-8b-a1b", **TINY_LFM2), CausalLMTask(),
        optax.adam(1e-3), partitioner=dpx.parallel.data_parallel(mesh),
        checkpoint_dir="", log_every=5,
    )
    loader = dpx.data.DeviceLoader(Rows(), 4, mesh=mesh, seed=0)
    history = trainer.fit(loader, None, epochs=1)
    rows = record.recorded()
    counters = [s for s in rows if s.name == "moe_counters"]
    fetches = [s for s in rows if s.name == "log_fetch"]
    assert len(counters) == len(fetches) == 3  # steps 0, 5, 10 of 12
    by_id = {s.id: s for s in rows}
    (fit,) = [s for s in rows if s.name == "fit"]
    for row in counters:
        assert by_id[row.parent].name == "log_fetch" and row.root == fit.id
        assert sorted(row.args) == [
            "dropped_assignments", "held_share", "load_max_over_mean",
            "rows_used_share",
        ]
        assert row.args["dropped_assignments"] == 0.0
        assert 0.0 < row.args["held_share"] < 1.0
    for name in row.args:
        assert f"train_moe_{name}" in history[0]
    assert history[0]["train_moe_dropped_assignments"] == 0.0


def test_a_model_without_experts_leaves_no_moe_counters(devices, fit_record):
    rows, _, _ = fit_record
    assert any(s.name == "log_fetch" for s in rows)
    assert not any(s.name == "moe_counters" for s in rows)


# ---------------------------------------------------------------------------
# train.main's own spans
# ---------------------------------------------------------------------------


MAIN_ARGV = [
    "--epochs", "1", "--num-samples", "64", "--batch-size", "8",
    "--checkpoint-dir", "",
]


@pytest.mark.parametrize("telemetry_on", [True, False], ids=["on", "off"])
def test_train_main_opens_its_setup_spans(devices, record, telemetry_on):
    import train

    train.main(MAIN_ARGV + ([] if telemetry_on else ["--no-telemetry"]))
    rows = [
        s for s in record.recorded()
        if not s.name.startswith(compilelog.PREFIX)
    ]
    if not telemetry_on:
        assert rows == []
        return
    order = [s.name for s in sorted(rows, key=lambda s: s.start_ns)
             if s.parent == 0]
    assert order[:5] == [
        "main_args", "main_runtime", "main_data", "main_model", "main_trainer",
    ]
    assert order[5] == "fit"
    (init,) = [s for s in rows if s.name == "init_state"]
    by_id = {s.id: s for s in rows}
    assert by_id[init.parent].name == "fit_open"
