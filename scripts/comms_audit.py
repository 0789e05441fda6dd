"""Comms-audit CI stage: communication and HBM budgets, proven from HLO.

Lowers and compiles the real fsdp train step, multi-step scan body,
serve decode step, and the dp comms-overlap pair (monolithic
``train_step_dp`` vs bucketed ``train_step_dp_overlap`` /
``multi_step_dp_overlap`` — parallel/overlap.py) on 8 virtual CPU
devices under a :class:`analysis.comms_audit.CommsWatcher`,
machine-reads each executable's HLO for collectives, schedule slack,
and cost/memory analysis, and applies the same suppression-baseline
ratchet as ``dlcfn lint`` (scripts/lint_baseline.json, DLC51x
namespace only):

- a program whose collective op count or bytes regress over the
  committed budget (scripts/comms_budget.json) -> DLC510 -> exit 1
- an fsdp step containing an all-gather the strategy doesn't predict
  -> DLC511 -> exit 1 (unless baselined)
- a program whose schedule overlap_score falls below the committed
  number, or a ``*_overlap`` program that fails to strictly beat its
  monolithic baseline -> DLC512 -> exit 1 (unless baselined)
- a baseline entry whose DLC51x finding no longer fires -> stale nag

``--write-budget`` re-measures and rewrites scripts/comms_budget.json —
the deliberate act that moves the ratchet.  Exit 0 and one JSON report
line on success.  docs/STATIC_ANALYSIS.md has the "reading a comms
report" runbook for when this stage goes red.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# The audit's question is partitioner-layer, not numerics: CPU answers
# it, but only with a real mesh to partition over.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--k", type=int, default=2, help="multi-step span")
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help="suppression baseline (default scripts/lint_baseline.json)",
    )
    parser.add_argument(
        "--budget",
        type=Path,
        default=None,
        help="committed comms budget (default scripts/comms_budget.json)",
    )
    parser.add_argument(
        "--write-budget",
        action="store_true",
        help="re-measure and rewrite the committed budget, then exit 0",
    )
    args = parser.parse_args(argv)

    from deeplearning_cfn_tpu.analysis.collectives import AUDIT_RULE_IDS
    from deeplearning_cfn_tpu.analysis.comms_audit import (
        DEFAULT_BUDGET_PATH,
        run_comms_audit,
        write_budget,
    )
    from deeplearning_cfn_tpu.analysis.runner import apply_audit_baseline

    budget_path = args.budget if args.budget is not None else DEFAULT_BUDGET_PATH
    report = run_comms_audit(k=args.k, budget_path=budget_path)

    if args.write_budget:
        payload = write_budget(
            report.programs, budget_path, device_count=report.device_count
        )
        print(json.dumps({"written": str(budget_path), **payload}, allow_nan=False))
        return 0

    # This stage owns only the dynamic DLC51x namespace; lint owns the rest.
    fresh, stale = apply_audit_baseline(
        report.violations, args.baseline, AUDIT_RULE_IDS
    )

    for rule, rel, message in stale:
        print(
            f"comms-audit: stale baseline entry: {rule} {rel}: {message}",
            file=sys.stderr,
        )
    for v in fresh:
        print(f"comms-audit: {v.format()}", file=sys.stderr)

    print(json.dumps(report.to_dict(), allow_nan=False))
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
