#!/usr/bin/env python3
"""graft-lint CLI: static sharding/collective/numerics/memory auditor.

Runs the analysis layers (AST lints, jaxpr numerics lints, graft-prove's
trace-only shardflow/congruence/envelope passes, per-mesh-config
collective/donation/placement audits) without executing a single train
step, and gates against the committed ``analysis/comm_budgets.json`` and
``analysis/memory_envelopes.json``.

Driver contract: stdout carries exactly ONE JSON line;
every detail — per-config collective tables, shardflow attributions,
violation renderings, notes — goes to stderr. Exit status is non-zero iff
there are violations.

Usage:
    python scripts/graft_lint.py                    # full audit
    python scripts/graft_lint.py --configs data+fsdp+expert
    python scripts/graft_lint.py --no-collectives   # AST + numerics only
    python scripts/graft_lint.py --update-budgets   # refresh budget file
    python scripts/graft_lint.py --update-envelopes # refresh HBM envelopes
    python scripts/graft_lint.py --diff HEAD~1      # attribute budget deltas
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument(
        "--configs", default=None,
        help="comma-separated dryrun config names (default: all + serve)",
    )
    ap.add_argument(
        "--budgets", default=None,
        help="budget file path (default: analysis/comm_budgets.json)",
    )
    ap.add_argument(
        "--envelopes", default=None,
        help="envelope file path (default: analysis/memory_envelopes.json)",
    )
    ap.add_argument(
        "--update-budgets", "--write-budgets", action="store_true",
        dest="update_budgets",
        help="measure and overwrite the budget file instead of gating "
             "(records the running jax version in _meta)",
    )
    ap.add_argument(
        "--update-envelopes", action="store_true",
        help="recompute and overwrite the static HBM envelope file "
             "(records the running jax version in _meta)",
    )
    ap.add_argument(
        "--diff", default=None, metavar="REV",
        help="differential audit: diff measured collectives against the "
             "budget file committed at REV and attribute each delta to "
             "named ops via the shardflow report",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="machine-readable mode (explicit; the one-JSON-line stdout "
             "contract always holds)",
    )
    ap.add_argument(
        "--hbm-limit", default=None,
        help="per-chip HBM limit (bytes; K/M/G suffixes) for the "
             "would-OOM envelope pre-gate (default: $DPX_HBM_LIMIT)",
    )
    ap.add_argument("--devices", type=int, default=8,
                    help="fake CPU mesh size (default 8)")
    ap.add_argument("--no-collectives", action="store_true",
                    help="skip the per-config compile audits")
    ap.add_argument("--no-numerics", action="store_true",
                    help="skip the bf16-upcast jaxpr lint")
    ap.add_argument("--no-ast", action="store_true",
                    help="skip the AST lints")
    ap.add_argument("--no-serve", action="store_true",
                    help="skip the serving prefill/decode program audits")
    ap.add_argument("--no-flow", action="store_true",
                    help="skip graft-prove (shardflow/congruence/envelope)")
    args = ap.parse_args()

    from distributed_pytorch_example_tpu.analysis import collectives as coll
    from distributed_pytorch_example_tpu.analysis import envelope as env_mod
    from distributed_pytorch_example_tpu.analysis import runner

    config_names = args.configs.split(",") if args.configs else None

    if args.diff:
        summary = runner.diff_audit(
            args.diff,
            config_names=config_names,
            budgets_path=args.budgets or coll.DEFAULT_BUDGETS_PATH,
            n_devices=args.devices,
        )
        print(json.dumps({"tool": "graft_lint", "mode": "diff", **summary}))
        return 0

    if args.hbm_limit:
        os.environ["DPX_HBM_LIMIT"] = args.hbm_limit
    hbm_limit = env_mod.hbm_limit_from_env()

    result = runner.run_audit(
        config_names=config_names,
        budgets_path=args.budgets or coll.DEFAULT_BUDGETS_PATH,
        envelopes_path=args.envelopes or env_mod.DEFAULT_ENVELOPES_PATH,
        write_budgets=args.update_budgets,
        write_envelopes=args.update_envelopes,
        n_devices=args.devices,
        with_collectives=not args.no_collectives,
        with_numerics=not args.no_numerics,
        with_ast=not args.no_ast,
        with_serve=not args.no_serve,
        with_flow=not args.no_flow,
        hbm_limit=hbm_limit,
    )

    for f in result.violations:
        print(f"VIOLATION {f.render()}", file=sys.stderr)
    for n in result.notes:
        print(f"note: {n}", file=sys.stderr)

    jax_version = None
    if not (args.no_collectives and args.no_numerics):
        import jax

        jax_version = jax.__version__
    flow_summary = {
        name: flow.attributed_kinds()
        for name, flow in sorted(result.flows.items())
    }
    print(json.dumps({
        "tool": "graft_lint",
        "ok": result.ok,
        "violations": len(result.violations),
        "rules": result.rule_counts(),
        "notes": len(result.notes),
        "configs_audited": result.configs_audited,
        "configs_errored": result.configs_errored,
        "flow_collectives": flow_summary,
        "wrote_budgets": bool(args.update_budgets),
        "wrote_envelopes": bool(args.update_envelopes),
        "jax": jax_version,
    }))
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
