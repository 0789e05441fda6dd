"""Compile-audit CI stage: steady-state zero-retrace, proven by running.

Runs the real ``Trainer.fit()`` single-step path and the bench
multi-step path for a few CPU steps under a
:class:`analysis.compile_audit.CompileWatcher`, then applies the same
suppression-baseline ratchet as ``dlcfn lint`` (scripts/lint_baseline.json):

- a function that recompiles after warmup -> DLC410 finding -> exit 1
- a step whose state donation deleted zero bytes -> DLC411 -> exit 1
- a baseline entry whose DLC41x finding no longer fires -> stale nag

Exit 0 and one JSON report line on success.  docs/STATIC_ANALYSIS.md has
the "reading a retrace report" runbook for when this stage goes red.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# The audit's question is dispatch-layer, not numerics: CPU answers it.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Honest compile counts need the persistent cache out of the way.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=4, help="steady-state steps")
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--k", type=int, default=2, help="multi-step span")
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help="suppression baseline (default scripts/lint_baseline.json)",
    )
    args = parser.parse_args(argv)

    from deeplearning_cfn_tpu.analysis.compile_audit import (
        run_compile_audit,
        run_serve_audit,
    )
    from deeplearning_cfn_tpu.analysis.runner import apply_audit_baseline
    from deeplearning_cfn_tpu.analysis.sharding import AUDIT_RULE_IDS

    report = run_compile_audit(
        steady_steps=args.steps, warmup_steps=args.warmup, k=args.k
    )
    # The serving plane rides the same ratchet: its continuous-batching
    # decode must stay on one compiled step across mixed-length traffic.
    serve_report = run_serve_audit()
    report.paths.extend(serve_report.paths)
    report.violations.extend(serve_report.violations)
    for key in ("compile_count", "retrace_count", "backend_compiles"):
        report.watcher[key] = report.watcher.get(key, 0) + serve_report.watcher.get(
            key, 0
        )

    # This stage owns only the dynamic DLC41x namespace; lint owns the rest.
    fresh, stale = apply_audit_baseline(
        report.violations, args.baseline, AUDIT_RULE_IDS
    )

    for rule, rel, message in stale:
        print(
            f"compile-audit: stale baseline entry: {rule} {rel}: {message}",
            file=sys.stderr,
        )
    for v in fresh:
        print(f"compile-audit: {v.format()}", file=sys.stderr)

    print(json.dumps(report.to_dict(), allow_nan=False))
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
