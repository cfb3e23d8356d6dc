"""graft-plan: the static auto-parallelism planner (analysis/planner.py).

Unit matrix over the three-tier oracle: the legality filter rejects
indivisible topologies, the tier-2 envelope gate prunes would-OOM plans
BEFORE any compile, int8 wire never scores more bytes than fp32 on the
same plan, and the PlanSpec lowering is bit-identical to the legacy
factory overlays for every dryrun mesh shape. The ``--auto-mesh``
subprocess contract tests (train/bench/serve end-to-end) run under
``-m slow``; everything pure-static carries the ``lint`` mark so the
pre-commit fast path (scripts/precommit.sh) covers the planner too.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_pytorch_example_tpu.analysis import planner
from distributed_pytorch_example_tpu.parallel.plan import PlanSpec
from distributed_pytorch_example_tpu.parallel.wire import WireConfig
from distributed_pytorch_example_tpu.runtime.mesh import MeshSpec, make_mesh

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lm_info(**kw):
    base = dict(global_batch=16, num_heads=4, num_layers=2,
                pipelineable=False, max_param_elems=1 << 20, kind="lm")
    base.update(kw)
    return planner.ProgramInfo(**base)


# ---------------------------------------------------------------------------
# legality filter (pure static — no backend, no trace)
# ---------------------------------------------------------------------------


@pytest.mark.lint
def test_legality_rejects_indivisible_tensor():
    # 6 heads on a tensor span of 4: Megatron head split impossible
    plan = PlanSpec(mesh=MeshSpec(data=2, tensor=4), family="transformer")
    reason = planner.legality(plan, _lm_info(num_heads=6), 8)
    assert reason is not None and "heads" in reason


@pytest.mark.lint
def test_legality_rejects_batch_and_knob_misuse():
    # batch not divisible by the data span
    plan = PlanSpec(mesh=MeshSpec(data=8), family="data")
    reason = planner.legality(plan, _lm_info(global_batch=12), 8)
    assert reason is not None and "divisible" in reason
    # tensor axis demands the transformer rule family
    plan = PlanSpec(mesh=MeshSpec(data=4, tensor=2), family="data")
    assert "transformer" in planner.legality(plan, _lm_info(), 8)
    # zero1 without a data span is a no-op, not a plan
    plan = PlanSpec(mesh=MeshSpec(tensor=8), family="transformer", zero1=True)
    assert "zero1" in planner.legality(plan, _lm_info(num_heads=8), 8)
    # pipe needs a pipelineable model with balanced stages
    plan = PlanSpec(mesh=MeshSpec(data=4, pipe=2), family="transformer")
    assert "pipeline" in planner.legality(plan, _lm_info(), 8)


@pytest.mark.lint
def test_enumerate_plans_emits_only_legal_plans():
    info = _lm_info(num_heads=6)  # 6 heads: tensor spans 2/3/6 only
    plans = planner.enumerate_plans(8, info)
    assert plans, "search space empty"
    for p in plans:
        assert planner.legality(p, info, 8) is None, p.name()
    # and the tensor-span filter actually bit: no span-4 mesh survived
    assert all(p.mesh.resolve(8).tensor != 4 for p in plans)
    # names are unique (the dedup key)
    names = [p.name() for p in plans]
    assert len(names) == len(set(names))


@pytest.mark.lint
def test_cli_plan_space_knob_discipline():
    # the CLI grid never emits wire without zero1, and manual knobs stay
    # on the pure-DP mesh (the shapes bench's --zero1/--wire flags run)
    plans = planner.cli_plan_space(8, _lm_info())
    assert any(p.zero1 and p.wire is not None for p in plans)
    for p in plans:
        if p.wire is not None:
            assert p.zero1, p.name()
        if p.zero1 or p.wire is not None:
            assert p.family == "data", p.name()
        assert p.mesh.resolve(8).pipe == 1, p.name()


@pytest.mark.lint
def test_plan_json_roundtrip():
    plan = PlanSpec(
        mesh=MeshSpec(data=4, tensor=2), family="transformer",
        zero1=True, wire=WireConfig(compress="int8-block", block_size=128),
    )
    back = PlanSpec.from_json(json.loads(json.dumps(plan.to_json())))
    assert back == plan and back.name() == plan.name()


# ---------------------------------------------------------------------------
# zero1 floor boundary on PARAM paths (regression: the floor was pinned
# only through the opt_state overlay; the step's grad reduce-scatter dims
# come from zero1_dims over the PARAM tree and must agree)
# ---------------------------------------------------------------------------


def test_zero1_floor_boundary_param_paths(devices):
    from distributed_pytorch_example_tpu.parallel.api import data_parallel

    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    n = 128 * 128
    params = {
        "dense": {"kernel": jax.ShapeDtypeStruct((128, 128), jnp.float32)},
        "bias": jax.ShapeDtypeStruct((8,), jnp.float32),
    }
    at_floor = data_parallel(
        mesh, dp_shard_opt_state=True, opt_shard_min_size=n
    )
    dims = at_floor.zero1_dims(params)
    # EXACTLY at the floor: the kernel's gradient reduce-scatters onto a
    # real dim (the `<` in zero1_dim is strict)...
    assert dims["dense"]["kernel"] is not None
    # ...while the tiny bias stays on the all-reduce path
    assert dims["bias"] is None

    one_under = data_parallel(
        mesh, dp_shard_opt_state=True, opt_shard_min_size=n + 1
    )
    dims = one_under.zero1_dims(params)
    # one element under the floor: replicated BY DESIGN, not an off-by-one
    assert dims["dense"]["kernel"] is None


# ---------------------------------------------------------------------------
# tier 2: the envelope gate prunes would-OOM plans before any compile
# ---------------------------------------------------------------------------


def _toy_lm(model_dim=64, vocab=128):
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2
    from distributed_pytorch_example_tpu.train.tasks import CausalLMTask

    model = GPT2(
        vocab_size=vocab, max_len=64, model_dim=model_dim, num_layers=2,
        num_heads=4, mlp_dim=2 * model_dim, logits_mode="hidden",
    )
    return model, CausalLMTask(), optax.adam(1e-3)


def _toy_batch(global_batch=16, seq=32):
    tokens = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
    return tokens, {"tokens": tokens}


def test_hbm_gate_prunes_infeasible_plans_precompile(devices):
    model, task, optimizer = _toy_lm(model_dim=128, vocab=256)
    tokens, batch = _toy_batch()
    info = planner.ProgramInfo(
        global_batch=16, num_heads=4, num_layers=2, kind="lm",
    )
    plans = planner.cli_plan_space(8, info)
    scores = planner.rank_train_plans(
        model, task, optimizer, tokens, batch, plans,
        devices=devices, hbm_limit=2 << 20,
    )
    gated = [
        s for s in scores
        if s.predicted_peak_bytes and s.predicted_peak_bytes > (2 << 20)
    ]
    assert gated, "fixture model too small to trip the 2 MiB gate"
    for s in gated:
        # pruned AT tier 2 — the reason is the envelope, never a compile
        assert not s.feasible and s.tier == 2, s.plan.name()
        assert "HBM limit" in s.reason, s.reason
    assert planner.best_plan(scores) is None or all(
        s.predicted_peak_bytes <= (2 << 20)
        for s in scores if s.feasible
    )


def test_wire_int8_never_scores_more_bytes_than_fp32(devices):
    model, task, optimizer = _toy_lm()
    tokens, batch = _toy_batch()
    base = dict(mesh=MeshSpec(data=8), family="data", zero1=True,
                opt_shard_min_size=1)
    fp32 = PlanSpec(**base)
    int8 = PlanSpec(
        **base, wire=WireConfig(compress="int8-block", min_size=1),
    )
    scores = {
        s.plan.name(): s
        for s in planner.rank_train_plans(
            model, task, optimizer, tokens, batch, [fp32, int8],
            devices=devices,
        )
    }
    s_fp32, s_int8 = scores[fp32.name()], scores[int8.name()]
    assert s_fp32.feasible and s_int8.feasible
    # the compressed payload is counted at its wire width: never MORE
    # traffic than the fp32 schedule of the identical plan. (cost_ms can
    # legitimately go the other way at toy scale: the int8 schedule emits
    # extra per-block scale collectives, and their fixed link latency
    # outweighs the byte savings on KB-sized grads — the BYTES invariant
    # is what pins the quantizer accounting.)
    assert s_int8.comm_bytes <= s_fp32.comm_bytes


# ---------------------------------------------------------------------------
# PlanSpec <-> legacy factory equivalence: the refactor is sharding-neutral
# for every dryrun mesh shape (the committed budget signatures gate the
# same fact post-compile; this pins it at the spec level, pre-compile)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_state_shapes():
    from distributed_pytorch_example_tpu.train import step as step_mod

    model, task, optimizer = _toy_lm()
    return step_mod.abstract_state(
        model, optimizer, jax.ShapeDtypeStruct((16, 32), jnp.int32)
    )


def _spec_trees_equal(a, b):
    from jax.sharding import PartitionSpec as P

    la = jax.tree_util.tree_leaves(a, is_leaf=lambda s: isinstance(s, P))
    lb = jax.tree_util.tree_leaves(b, is_leaf=lambda s: isinstance(s, P))
    return len(la) == len(lb) and all(x == y for x, y in zip(la, lb))


def test_planspec_matches_legacy_factories_per_dryrun_config(
    devices, toy_state_shapes
):
    sys.path.insert(0, REPO_ROOT)
    import __graft_entry__ as entry

    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )

    checked = 0
    for config in entry.DRYRUN_CONFIGS:
        priority = config
        tags = set()
        while priority and priority[-1] in entry._VARIANT_TAGS:
            tags.add(priority[-1])
            priority = priority[:-1]
        sizes = entry._alloc_axes(8, priority)
        mesh = make_mesh(MeshSpec(**sizes), devices=devices)
        zero1 = "zero1" in tags
        wire = (
            WireConfig(compress="int8-block", min_size=1)
            if "wire-int8" in tags else None
        )
        kw = dict(opt_shard_min_size=1, wire=wire) if zero1 else {}
        legacy = transformer_partitioner(
            mesh, fsdp_rest=True, dp_shard_opt_state=zero1, **kw
        )
        direct = PlanSpec(
            mesh=MeshSpec(**sizes), family="transformer", fsdp_rest=True,
            zero1=zero1, **kw,
        ).lower(mesh=mesh)
        assert _spec_trees_equal(
            legacy.tree_specs(toy_state_shapes),
            direct.tree_specs(toy_state_shapes),
        ), f"{config}: PlanSpec lowering diverged from the legacy factory"
        assert legacy.batch_spec() == direct.batch_spec(), config
        checked += 1
    assert checked == len(entry.DRYRUN_CONFIGS)


def test_data_and_fsdp_factories_are_planspec_lowerings(
    devices, toy_state_shapes
):
    from distributed_pytorch_example_tpu.parallel.api import (
        data_parallel,
        fsdp,
    )

    mesh = make_mesh(MeshSpec(data=4, fsdp=2), devices=devices)
    assert _spec_trees_equal(
        data_parallel(mesh).tree_specs(toy_state_shapes),
        PlanSpec(family="data").lower(mesh=mesh).tree_specs(toy_state_shapes),
    )
    assert _spec_trees_equal(
        fsdp(mesh).tree_specs(toy_state_shapes),
        PlanSpec(family="fsdp").lower(mesh=mesh).tree_specs(toy_state_shapes),
    )


# ---------------------------------------------------------------------------
# the committed plans.json
# ---------------------------------------------------------------------------


@pytest.mark.lint
@pytest.mark.parametrize("content", [None, "{not json"], ids=["missing", "corrupt"])
def test_load_plans_absent_or_corrupt_is_none(tmp_path, content):
    path = tmp_path / "plans.json"
    if content is not None:
        path.write_text(content)
    assert planner.load_plans(str(path)) is None


@pytest.mark.lint
def test_committed_plans_json_is_loadable_and_ranked():
    doc = planner.load_plans()
    assert doc is not None, "analysis/plans.json missing or unreadable"
    programs = doc.get("programs", {})
    # every BASELINE train program plus both serve programs are committed
    for prog in (
        "train/resnet18", "train/resnet50", "train/vit-b16",
        "train/bert-base", "train/gpt2", "serve/prefill", "serve/decode",
    ):
        entry = programs.get(prog)
        assert entry and entry.get("top"), prog
        costs = [t["cost_ms"] for t in entry["top"]]
        assert costs == sorted(costs), f"{prog}: top plans not ranked"
        assert all(t["feasible"] for t in entry["top"]), prog


# ---------------------------------------------------------------------------
# --auto-mesh subprocess contract (end-to-end CLIs; slow set)
# ---------------------------------------------------------------------------


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    return env


def _one_json_line(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE JSON line on stdout, got {lines!r}"
    return json.loads(lines[0])


def test_train_auto_mesh_rejects_conflicting_flags():
    # fast path: the conflict dies in argparse before any backend work
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "train.py"),
         "--auto-mesh", "--mesh-tensor", "2"],
        capture_output=True, text=True, env=_cli_env(), timeout=120,
    )
    assert proc.returncode != 0
    assert "--auto-mesh" in proc.stderr


@pytest.mark.slow
def test_train_auto_mesh_end_to_end(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "train.py"),
         "--auto-mesh", "--model", "mlp", "--epochs", "1",
         "--num-samples", "64", "--batch-size", "2", "--log-every", "1",
         "--checkpoint-dir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, env=_cli_env(), timeout=600,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "auto-mesh" in proc.stderr and "dp:" in proc.stderr


@pytest.mark.slow
def test_serve_auto_mesh_one_json_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "serve.py"),
         "--auto-mesh", "--requests", "4", "--slots", "2",
         "--max-len", "32", "--max-blocks", "4",
         "--prompt-len", "4:8", "--max-new", "4:8"],
        capture_output=True, text=True, env=_cli_env(), timeout=600,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = _one_json_line(proc.stdout)
    assert doc["config"]["auto_mesh"], "picked plan missing from config"
