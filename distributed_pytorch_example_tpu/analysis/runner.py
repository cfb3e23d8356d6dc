"""graft-lint orchestration: AST + jaxpr + collective audits in one pass.

Glues the three analysis layers to the dryrun mesh-config table
(``__graft_entry__.DRYRUN_CONFIGS``) and the committed budgets:

- AST lints (``pylint_rules``) run first — no jax, milliseconds;
- numerics lints (``shardlint.lint_dtype_promotions``) trace the bf16
  flagship-shaped step once;
- per-config audits lower+compile each requested mesh config on the fake
  CPU mesh (never executing a step) and check collective budgets,
  dropped donations, and large replicated params.

Configs the toolchain cannot compile produce ``{"error": ...}`` records:
the committed budget file documents the gap, and an error matching the
committed error is a note, not a violation. Budget comparisons degrade to
warnings entirely when the runtime jax differs from the budget file's
``_meta.jax`` (collective counts are only stable within one toolchain).
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from distributed_pytorch_example_tpu.analysis import collectives as coll
from distributed_pytorch_example_tpu.analysis import congruence as cong_mod
from distributed_pytorch_example_tpu.analysis import envelope as env_mod
from distributed_pytorch_example_tpu.analysis import pylint_rules
from distributed_pytorch_example_tpu.analysis import shardflow
from distributed_pytorch_example_tpu.analysis import shardlint
from distributed_pytorch_example_tpu.analysis.findings import Finding


@dataclass
class AuditResult:
    violations: List[Finding] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)
    records: Dict[str, Dict[str, object]] = field(default_factory=dict)
    # graft-prove static layers, keyed like records (not budget-serialized)
    flows: Dict[str, object] = field(default_factory=dict)
    envelope_records: Dict[str, Dict[str, object]] = field(
        default_factory=dict
    )
    configs_audited: int = 0
    configs_errored: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations

    def rule_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.violations:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def error_record(exc: BaseException) -> Dict[str, object]:
    first = str(exc).splitlines()[0] if str(exc) else ""
    return {"error": f"{type(exc).__name__}: {first[:200]}"}


def _resolve_configs(names: Optional[Sequence[str]]):
    import __graft_entry__ as entry

    table = {
        entry.dryrun_config_name(c): c for c in entry.DRYRUN_CONFIGS
    }
    if names is None:
        return list(table.items())
    missing = [n for n in names if n not in table]
    if missing:
        raise SystemExit(
            f"unknown config(s) {missing}; known: {sorted(table)}"
        )
    return [(n, table[n]) for n in names]


def _case_jaxpr_specs(case):
    """(closed_jaxpr, in_specs, mesh_shape) of a case's train step —
    trace-only, so this works even for configs XLA cannot partition."""
    import jax

    trainer = case.trainer
    if trainer.state is None:
        with case.mesh:
            trainer.init(next(iter(case.loader))["tokens"])
    batch = next(iter(case.loader))
    with case.mesh:
        jaxpr = jax.make_jaxpr(
            lambda s, b: trainer.train_step(s, b)
        )(trainer.state, batch)
    specs = shardflow.committed_in_specs((trainer.state, batch))
    mesh_shape = {str(k): int(v) for k, v in dict(case.mesh.shape).items()}
    return jaxpr, specs, mesh_shape


def _audit_static(
    result: AuditResult,
    name: str,
    jaxpr,
    in_specs,
    mesh_shape: Dict[str, int],
    case_mesh,
    envelopes: Optional[Dict[str, object]],
    env_skew: Optional[str],
    hbm_limit: Optional[int],
    log,
) -> Optional[object]:
    """The trace-only graft-prove layers for one program: shardflow +
    congruence + the would-OOM pre-gate. Returns the FlowReport (None if
    the would-OOM gate refused the config — the caller must then skip
    the compile)."""
    flow = shardflow.trace_shardings(jaxpr, in_specs, mesh_shape)
    result.flows[name] = flow
    kinds = flow.attributed_kinds()
    log(f"graft_prove: {name} shardflow eqns={flow.eqns} "
        f"comm_events={len(flow.comm_events())} kinds={kinds} "
        f"lost={flow.lost} predicted_peak={flow.peak_bytes}B")

    cong = cong_mod.check_congruence(jaxpr)
    for f in cong.findings:
        if f.hazard:
            result.violations.append(Finding(
                rule="spmd-hang", where=f"{name}:{f.path or f.source}",
                message=f.render(), config=name,
            ))
        else:
            result.notes.append(f"{name}: {f.render()}")

    committed_env = (envelopes or {}).get("configs", {}).get(name)
    if committed_env is not None:
        for v in env_mod.compare_envelope(
            name, committed_env, flow.peak_bytes, None
        ):
            if env_skew is not None:
                result.notes.append(f"(skew-demoted) {v.render()}")
            else:
                result.violations.append(Finding(
                    rule=v.rule, where=name, message=v.detail, config=name,
                ))

    gate = env_mod.gate_envelope(name, flow.peak_bytes, hbm_limit)
    if gate is not None:
        result.violations.append(Finding(
            rule=gate.rule, where=name, message=gate.detail, config=name,
        ))
        return None
    return flow


def _check_envelope_measured(
    result: AuditResult,
    name: str,
    flow,
    measured: Optional[int],
    envelopes: Optional[Dict[str, object]],
    env_skew: Optional[str],
) -> None:
    """The measured half of envelope cross-validation (ratio band)."""
    if flow is None or not measured:
        return
    for v in env_mod.compare_envelope(name, {}, flow.peak_bytes, measured):
        if env_skew is not None:
            result.notes.append(f"(skew-demoted) {v.render()}")
        else:
            result.violations.append(Finding(
                rule=v.rule, where=name, message=v.detail, config=name,
            ))


def audit_configs(
    config_names: Optional[Sequence[str]] = None,
    budgets: Optional[Dict[str, object]] = None,
    envelopes: Optional[Dict[str, object]] = None,
    n_devices: int = 8,
    byte_tolerance: float = coll.DEFAULT_BYTE_TOLERANCE,
    check_placement: bool = True,
    check_flow: bool = True,
    hbm_limit: Optional[int] = None,
    log=lambda msg: print(msg, file=sys.stderr),
) -> AuditResult:
    """Compile each config and audit collectives / donation / placement,
    preceded by the trace-only graft-prove layers (shardflow sharding
    propagation, congruence hang check, static HBM envelope).

    With ``budgets=None`` no budget comparison happens (measure-only —
    the ``--update-budgets`` path); otherwise each measured record is
    gated against ``budgets["configs"][name]``. Same for ``envelopes``.
    The static layers run BEFORE any compile, so they cover the configs
    this toolchain cannot partition, and the would-OOM envelope gate can
    refuse a config without paying for its compile.
    """
    import __graft_entry__ as entry

    entry._ensure_cpu_devices(n_devices)
    import jax

    from distributed_pytorch_example_tpu.telemetry import cost

    devices = jax.devices()[:n_devices]
    result = AuditResult()
    skew = coll.jax_version_skew(budgets) if budgets else None
    if skew is not None:
        result.notes.append(
            f"budgets were generated under jax {skew}, runtime is "
            f"{jax.__version__}: budget comparisons degraded to warnings"
        )
    env_skew = coll.jax_version_skew(envelopes) if envelopes else None
    if env_skew is not None:
        result.notes.append(
            f"envelopes were generated under jax {env_skew}, runtime is "
            f"{jax.__version__}: envelope comparisons degraded to warnings"
        )
    committed_configs = (budgets or {}).get("configs", {})

    for name, config in _resolve_configs(config_names):
        case = entry.build_dryrun_case(config, devices)
        if isinstance(case, str):
            result.records[name] = {"skip": case}
            result.notes.append(f"{name}: skipped ({case})")
            continue

        flow = None
        if check_flow:
            try:
                jaxpr, in_specs, mesh_shape = _case_jaxpr_specs(case)
            except Exception as e:
                result.notes.append(
                    f"{name}: static trace failed "
                    f"({type(e).__name__}: {str(e)[:120]})"
                )
            else:
                flow = _audit_static(
                    result, name, jaxpr, in_specs, mesh_shape, case.mesh,
                    envelopes, env_skew, hbm_limit, log,
                )
                if flow is None:  # would-OOM: refuse before compiling
                    result.records[name] = {
                        "skip": "would-oom (static envelope gate)"
                    }
                    continue
                result.envelope_records[name] = env_mod.envelope_record(
                    case, flow, None
                )

        try:
            lowered, compiled = coll.compile_case(case)
            record = coll.collective_record(case, compiled)
        except Exception as e:  # compile failures become budget records
            record = error_record(e)
            result.records[name] = record
            result.configs_errored += 1
            committed = committed_configs.get(name)
            if budgets is None or (
                committed is not None and "error" in committed
            ):
                result.notes.append(
                    f"{name}: does not compile here ({record['error']})"
                )
            elif skew is not None:
                result.notes.append(
                    f"{name}: compile error under skewed jax "
                    f"({record['error']})"
                )
            else:
                result.violations.append(Finding(
                    rule="comm-compile-error", where=name,
                    message=record["error"], config=name,
                ))
            continue
        result.records[name] = record
        result.configs_audited += 1
        log(f"graft_lint: {name} compiled; "
            f"collectives={record['collectives']}")

        measured = cost.measured_hbm_peak(compiled)
        if flow is not None:
            result.envelope_records[name] = env_mod.envelope_record(
                case, flow, measured
            )
            _check_envelope_measured(
                result, name, flow, measured, envelopes, env_skew
            )

        if budgets is not None:
            committed = committed_configs.get(name)
            if committed is None:
                result.violations.append(Finding(
                    rule="comm-budget-missing", where=name,
                    message="no committed budget for this config; run "
                            "scripts/graft_lint.py --update-budgets",
                    config=name,
                ))
            elif "error" in committed:
                result.notes.append(
                    f"{name}: compiles now but budget records an error — "
                    f"refresh budgets to ratchet the gain in"
                )
            else:
                v, n = coll.compare_budgets(
                    committed["collectives"], record["collectives"],
                    byte_tolerance=byte_tolerance, config=name,
                    signature=committed.get(
                        "signature", record.get("signature")
                    ),
                    markers=record.get("markers"),
                    # measured values, not the committed ones: the
                    # wire-int8-step signature must fail when THIS
                    # compile lost the s8 payload or the >=3x ratio
                    dtypes=record.get("dtypes"),
                    wire=record.get("wire"),
                )
                if skew is not None:
                    result.notes.extend(
                        f"(skew-demoted) {f.render()}" for f in v
                    )
                else:
                    result.violations.extend(v)
                result.notes.extend(n)

        if check_placement:
            result.violations.extend(shardlint.lint_dropped_donation(
                lowered, compiled, config=name
            ))
            result.violations.extend(shardlint.lint_replicated_params(
                case.trainer.state.params, case.trainer.partitioner,
                config=name,
            ))
            # the same rule over the optimizer tree: the ZeRO-1 overlay
            # (parallel/api.py) only engages on opt_state/... paths, so a
            # large replicated Adam moment the overlay would dp-shard is
            # a violation too (satellite of graft-prove; regression for
            # the overlay's min-size floor lives in test_graft_lint.py)
            result.violations.extend(shardlint.lint_replicated_params(
                case.trainer.state.opt_state, case.trainer.partitioner,
                config=name, path_prefix="opt_state",
            ))
    return result


def audit_serve(
    budgets: Optional[Dict[str, object]] = None,
    envelopes: Optional[Dict[str, object]] = None,
    n_devices: int = 8,
    byte_tolerance: float = coll.DEFAULT_BYTE_TOLERANCE,
    check_flow: bool = True,
    hbm_limit: Optional[int] = None,
    log=lambda msg: print(msg, file=sys.stderr),
) -> AuditResult:
    """Budget/envelope audit of the serving engine's two programs.

    Bucketed prefill and slot decode become first-class entries
    (``serve/prefill``, ``serve/decode``) gated exactly like train
    configs: collective budgets off the compiled HLO, shardflow +
    congruence + envelopes off the traced jaxprs.
    """
    import __graft_entry__ as entry

    entry._ensure_cpu_devices(n_devices)
    import jax

    from distributed_pytorch_example_tpu.telemetry import cost

    devices = jax.devices()[:n_devices]
    result = AuditResult()
    skew = coll.jax_version_skew(budgets) if budgets else None
    env_skew = coll.jax_version_skew(envelopes) if envelopes else None
    committed_configs = (budgets or {}).get("configs", {})

    case = entry.build_serve_case(devices)
    if isinstance(case, str):
        result.notes.append(f"serve: skipped ({case})")
        return result
    mesh_shape = {str(k): int(v) for k, v in dict(case.mesh.shape).items()}

    flows: Dict[str, object] = {}
    if check_flow:
        for name, (jaxpr, in_specs) in case.engine.traced_programs().items():
            flow = _audit_static(
                result, name, jaxpr, in_specs, mesh_shape, case.mesh,
                envelopes, env_skew, hbm_limit, log,
            )
            if flow is not None:
                flows[name] = flow
                result.envelope_records[name] = env_mod.envelope_record(
                    case, flow, None
                )

    for name, lowered in case.engine.lowered_programs().items():
        try:
            compiled = lowered.compile()
        except Exception as e:
            record = error_record(e)
            result.records[name] = record
            result.configs_errored += 1
            result.notes.append(
                f"{name}: does not compile here ({record['error']})"
            )
            continue
        text = compiled.as_text()
        record = {
            "mesh": {k: int(v) for k, v in dict(case.mesh.shape).items()},
            "collectives": coll.parse_collectives(text),
        }
        if name == "serve/decode":
            # structural contract: decode attention must go through the
            # fused paged dispatch (its named scope survives into the
            # compiled module) — a silent fall-back to gathering the
            # whole cache moves no collective bytes, only this signature
            record["signature"] = "paged-decode-fused"
        markers = coll.parse_markers(text)
        if any(markers.values()):
            record["markers"] = markers
        result.records[name] = record
        result.configs_audited += 1
        log(f"graft_lint: {name} compiled; "
            f"collectives={record['collectives']}")

        measured = cost.measured_hbm_peak(compiled)
        flow = flows.get(name)
        if flow is not None:
            result.envelope_records[name] = env_mod.envelope_record(
                case, flow, measured
            )
            _check_envelope_measured(
                result, name, flow, measured, envelopes, env_skew
            )

        if budgets is not None:
            committed = committed_configs.get(name)
            if committed is None:
                result.violations.append(Finding(
                    rule="comm-budget-missing", where=name,
                    message="no committed budget for this serve program; "
                            "run scripts/graft_lint.py --update-budgets",
                    config=name,
                ))
            elif "error" not in committed:
                v, n = coll.compare_budgets(
                    committed["collectives"], record["collectives"],
                    byte_tolerance=byte_tolerance, config=name,
                    signature=committed.get(
                        "signature", record.get("signature")
                    ),
                    markers=record.get("markers"),
                )
                if skew is not None:
                    result.notes.extend(
                        f"(skew-demoted) {f.render()}" for f in v
                    )
                else:
                    result.violations.extend(v)
                result.notes.extend(n)
    return result


def audit_numerics() -> List[Finding]:
    """bf16-upcast lint over the flagship-shaped bf16 train step."""
    jaxpr = shardlint.flagship_numerics_jaxpr()
    return shardlint.lint_dtype_promotions(jaxpr)


def _merge(result: AuditResult, sub: AuditResult) -> None:
    result.violations.extend(sub.violations)
    result.notes.extend(sub.notes)
    result.records.update(sub.records)
    result.flows.update(sub.flows)
    result.envelope_records.update(sub.envelope_records)
    result.configs_audited += sub.configs_audited
    result.configs_errored += sub.configs_errored


def run_audit(
    config_names: Optional[Sequence[str]] = None,
    budgets_path: str = coll.DEFAULT_BUDGETS_PATH,
    envelopes_path: str = env_mod.DEFAULT_ENVELOPES_PATH,
    write_budgets: bool = False,
    write_envelopes: bool = False,
    n_devices: int = 8,
    with_collectives: bool = True,
    with_numerics: bool = True,
    with_ast: bool = True,
    with_serve: bool = True,
    with_flow: bool = True,
    hbm_limit: Optional[int] = None,
    log=lambda msg: print(msg, file=sys.stderr),
) -> AuditResult:
    """The full graft-lint pass (the CLI and pytest wrapper entry point)."""
    result = AuditResult()

    if with_ast:
        result.violations.extend(pylint_rules.lint_package())

    if with_numerics or with_collectives:
        import __graft_entry__ as entry

        entry._ensure_cpu_devices(n_devices)

    if with_numerics:
        result.violations.extend(audit_numerics())

    if with_collectives:
        budgets = None
        if not write_budgets:
            try:
                budgets = coll.load_budgets(budgets_path)
            except FileNotFoundError:
                result.notes.append(
                    f"no committed budgets at {budgets_path}; "
                    f"measuring without a gate (--update-budgets to commit)"
                )
        envelopes = None
        if with_flow and not write_envelopes:
            envelopes = env_mod.load_envelopes(envelopes_path)
            if envelopes is None:
                result.notes.append(
                    f"no committed envelopes at {envelopes_path}; "
                    f"measuring without a gate (--update-envelopes to "
                    f"commit)"
                )
        _merge(result, audit_configs(
            config_names, budgets=budgets, envelopes=envelopes,
            n_devices=n_devices, check_flow=with_flow,
            hbm_limit=hbm_limit, log=log,
        ))
        if with_serve and config_names is None:
            _merge(result, audit_serve(
                budgets=budgets, envelopes=envelopes, n_devices=n_devices,
                check_flow=with_flow, hbm_limit=hbm_limit, log=log,
            ))
        if write_budgets:
            coll.write_budgets(budgets_path, result.records, n_devices)
            result.notes.append(f"wrote budgets to {budgets_path}")
        if write_envelopes and result.envelope_records:
            env_mod.write_envelopes(
                envelopes_path, result.envelope_records, n_devices
            )
            result.notes.append(f"wrote envelopes to {envelopes_path}")

    stale = coll.budget_staleness(budgets_path)
    if stale and not write_budgets:
        result.notes.append(stale)
    return result


def diff_audit(
    rev: str,
    config_names: Optional[Sequence[str]] = None,
    budgets_path: str = coll.DEFAULT_BUDGETS_PATH,
    n_devices: int = 8,
    top: int = 5,
    log=lambda msg: print(msg, file=sys.stderr),
) -> Dict[str, object]:
    """Differential audit: measure the working tree, diff against the
    budget file committed at ``rev``, and attribute each collective
    count/byte delta to named ops via the shardflow report.

    The old side is read straight out of git (``git show
    rev:analysis/comm_budgets.json``) — no checkout, no second compile.
    For every (config, collective-kind) whose count or bytes moved, the
    current flow report's events of that kind are listed largest-first:
    the op, its flax module/param path, and its source line. That list is
    the answer to "which op grew the bytes" that a config-level budget
    delta cannot give.
    """
    import json
    import os
    import subprocess

    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    rel = os.path.relpath(budgets_path, repo_root)
    old_raw = subprocess.run(
        ["git", "show", f"{rev}:{rel}"],
        cwd=repo_root, capture_output=True, text=True,
    )
    if old_raw.returncode != 0:
        raise SystemExit(
            f"cannot read {rel} at {rev}: {old_raw.stderr.strip()}"
        )
    old = json.loads(old_raw.stdout)
    old_configs = old.get("configs", {})

    current = audit_configs(
        config_names, budgets=None, envelopes=None,
        n_devices=n_devices, check_flow=True, log=log,
    )

    diff: Dict[str, object] = {}
    for name, record in sorted(current.records.items()):
        new_coll = record.get("collectives")
        old_coll = (old_configs.get(name) or {}).get("collectives")
        if not new_coll or not old_coll:
            continue
        per_kind = {}
        for kind in sorted(set(new_coll) | set(old_coll)):
            n_new = new_coll.get(kind, {})
            n_old = old_coll.get(kind, {})
            d_count = int(n_new.get("count", 0)) - int(n_old.get("count", 0))
            d_bytes = int(n_new.get("bytes", 0)) - int(n_old.get("bytes", 0))
            if not d_count and not d_bytes:
                continue
            entry: Dict[str, object] = {
                "count_delta": d_count, "bytes_delta": d_bytes,
            }
            flow = current.flows.get(name)
            if flow is not None:
                entry["attribution"] = [
                    e.to_json() for e in flow.by_collective(kind)[:top]
                ]
            per_kind[kind] = entry
        if per_kind:
            diff[name] = per_kind
            for kind, entry in per_kind.items():
                log(f"graft_lint --diff: {name} {kind} "
                    f"count{entry['count_delta']:+d} "
                    f"bytes{entry['bytes_delta']:+d}")
                for att in entry.get("attribution", []):
                    log(f"    <- {att['op']} {att['bytes']}B at "
                        f"{att['path'] or '<top>'} ({att['source']})")

    return {
        "rev": rev,
        "old_jax": (old.get("_meta") or {}).get("jax"),
        "changed_configs": len(diff),
        "diff": diff,
    }
