"""The reduction from trace events to numbers, on intervals worked by hand
and on a small trace recorded on the chip (``recorded_v5e_gpt2.json.gz``)."""

import os

import pytest

import reduce as reducer
import tiny

RECORDED = os.path.join(tiny.TESTS_DIR, "recorded_v5e_gpt2.json.gz")


def test_union_subtract_measure():
    u = reducer.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]]
    assert reducer.measure(u) == 6
    assert reducer.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5), (8, 10)]
    assert reducer.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert reducer.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_self_times_take_children_out_of_parents():
    events = [["while", 0, 100], ["a", 10, 20], ["b", 40, 30], ["c", 120, 5]]
    assert reducer.self_times(events) == {"while": 50, "a": 20, "b": 30, "c": 5}


FLASH_FWD = "attn.f.9 = (bf16[1,2,128,64], f32[1,2,128,1]) custom-call tpu_custom_call"


def hand_trace():
    """Two steps of 100 ns on one chip: compute 0-40, an all-reduce 40-60 of
    which 50-60 runs beside a fusion (nested in a while), idle 70-100."""
    ops = []
    for base in (1000, 1100):
        ops += [
            ["fusion.1 = f32[8] fusion", base, 40],
            ["all-reduce.7 = f32[8] all-reduce", base + 40, 20],
            ["while.2 = (f32[8]) while", base + 50, 20],
            ["fusion.3 = f32[8] fusion", base + 50, 10],
            [FLASH_FWD, base + 62, 6],
        ]
    modules = [["jit_train_step(1)", 1000, 70], ["jit_train_step(1)", 1100, 70],
               ["jit_train_step(1)", 1200, 70], ["jit_add(2)", 1075, 1]]
    host = [["train_epoch", 900, 400], ["next_batch", 1072, 20],
            ["next_batch", 1172, 20]]
    return reducer.Trace([{"name": "/device:TPU:0", "ops": ops,
                           "modules": modules}], host)


def test_hand_trace_numbers():
    t = hand_trace()
    assert t.step_module == "jit_train_step(1)"
    assert t.steps() == 2
    assert t.window_s() == pytest.approx(200e-9)
    assert t.busy_s() == pytest.approx(140e-9)
    assert t.idle_share_worst() == pytest.approx(0.30)
    # all-reduce 40-60; the fusion nested in the while hides 50-60
    assert t.collective_exposed_share_worst() == pytest.approx(0.10)
    from layer_metrics import flash_kernels

    seconds, calls = t.kernel_seconds(flash_kernels.FORWARD)
    assert (calls, seconds) == (2, pytest.approx(12e-9))
    b = t.breakdown()
    # fusion.1 and fusion.3 fall under one kind: 2 x (40 + 10)
    assert b["device_ops"][0] == ["fusion = f32[8] fusion", pytest.approx(100e-9)]
    # both gaps (70-100 of each step) fall inside a next_batch span, which
    # is inside train_epoch: the innermost span is what the host was doing
    assert b["idle_gaps"] == [["next_batch", pytest.approx(60e-9)]]


def test_no_collective_nothing_to_read():
    t = reducer.Trace(
        [{"name": "/device:TPU:0", "ops": [["fusion", 0, 10]], "modules": []}], []
    )
    assert t.collective_exposed_share_worst() is None


def test_recorded_chip_trace():
    """Three steps of GPT-2 124M on a v5e (PR 24's first traced run)."""
    t = reducer.Trace.from_json_file(RECORDED)
    assert t.step_module.startswith("jit_train_step")
    assert t.steps() >= 2
    assert 0.0 < t.busy_s() <= t.window_s()
    assert 0.0 <= t.idle_share_worst() < 0.5
    assert t.collective_exposed_share_worst() is None  # one chip
    from layer_metrics import flash_kernels

    fwd_s, fwd_calls = t.kernel_seconds(flash_kernels.FORWARD)
    bwd_s, bwd_calls = t.kernel_seconds(flash_kernels.BACKWARD)
    assert fwd_calls == bwd_calls == 12 * t.steps()
    assert 0 < fwd_s < bwd_s
    b = t.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 10000 }
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 40000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 70000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 70000 }
  }
  lines {
    id: 3
    name: "Async XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%attn.f.3 = (bf16[1,2,128,64]{3,2,1,0:T(8,128)(2,1)}, f32[1,2,128,1]{3,2,1,0}) custom-call(bf16[1,2,128,64]{3,2,1,0} %q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "jit_train_step(1)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %p)" } }
}
planes {
  id: 2
  name: "/device:TPU:1"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 99000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={{0,1}}" } }
}
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 7
    name: "other-thread"
    timestamp_ns: 900
    events { metadata_id: 2 offset_ps: 0 duration_ps: 400000 }
  }
  lines {
    id: 8
    name: "python"
    timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "next_batch" } }
  event_metadata { key: 2 value { id: 2 name: "D2H Dispatch" } }
}
'''


def test_loader_picks_planes_lines_and_cuts_names():
    from jax.profiler import ProfileData

    from layer_metrics import flash_kernels

    profile = ProfileData.from_text_proto(XSPACE)
    one = reducer.from_profile(profile, 1)
    assert [d["name"] for d in one.devices] == ["/device:TPU:0"]
    names = [e[0] for e in one.devices[0]["ops"]]
    assert names[0] == "fusion.1 = f32[8] fusion"  # the async line is left out
    assert names[1] == (
        "attn.f.3 = (bf16[1,2,128,64], f32[1,2,128,1]) custom-call tpu_custom_call"
    )
    assert one.host == [["next_batch", 900.0, 400.0]]
    assert one.steps() == 1 and one.window_s() == pytest.approx(100e-9)
    assert one.kernel_seconds(flash_kernels.FORWARD) == (pytest.approx(10e-9), 1)
    assert one.kernel_seconds(flash_kernels.BACKWARD) == (0, 0)
    two = reducer.from_profile(profile, 2)
    assert len(two.devices) == 2
    assert reducer.COLLECTIVE.search(two.devices[1]["ops"][0][0])
    # chip 1 has no module line: its window is its ops' extent, all exposed
    assert two.collective_exposed_share_worst() == pytest.approx(1.0)
