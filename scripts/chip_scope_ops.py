"""The device operations of a traced benchmark run under named scopes, by
`op_name`: for each operation its class (forward, backward, recompute), its
calls and milliseconds a step on device 0, the bytes of the result its HLO text
names, and the `op_name`.  What says which operations a scope's time is.

    chiprun -- sh -c 'python3 -m benchmarks.run --workload <cell> --seed <n> \
        --seconds 30 --trace 1 && python3 scripts/chip_scope_ops.py <cell> ssm/conv ssm/gate_norm'

Reads the trace the run left under benchmarks/out/<cell>/trace of the working
directory (the root of the tree that ran); a scope is its
parts joined by `/`, each of which has to stand on the operation's name stack.
One JSON line an operation of 0.05 ms a step or more, the scope's sum last;
every operation of the steady programs (calls, ns, `op_name`, HLO text) goes to
chiprun_out/scope_ops/<cell>.json.
"""

from __future__ import annotations

import collections
import json
import math
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "f16": 2, "s8": 1, "u8": 1, "pred": 1}


def result_bytes(hlo: str) -> int | None:
    """The bytes of the arrays an instruction's HLO text gives as its result
    (`%name = type[dims]{layout} op(...)`, a tuple's arrays summed)."""
    result = hlo.split(" = ", 1)[-1]
    result = result[: result.index(")") + 1] if result.startswith("(") else result.split(" ", 1)[0]
    total = 0
    for kind, dims in re.findall(r"(\w+)\[([\d,]*)\]", result):
        total += _ITEMSIZE.get(kind, 0) * math.prod(int(d) for d in dims.split(",") if d)
    return total or None


def main(argv: list[str]) -> int:
    from benchmarks import scope_reduce, trace_reduce

    cell, scopes = argv[0], argv[1:]
    trace_dir = Path.cwd() / "benchmarks" / "out" / cell / "trace"
    rows = [r for r in trace_reduce.load_events(trace_dir) if r[0] == "/device:TPU:0"]
    # The executed programs the trace holds whole, the first left out (the
    # profiler settles in it), and the operations inside them.
    spans = sorted((r[3], r[3] + r[4]) for r in rows if r[1] == trace_reduce.MODULE_LINE)[1:]
    programs = len(spans)
    rows = [r for r in rows if any(a <= r[3] and r[3] + r[4] <= b for a, b in spans)]
    names = scope_reduce.load_op_names(trace_dir)
    # Every operation of the steady programs, for whoever reads another scope later.
    table = collections.defaultdict(lambda: [0, 0, ""])
    for _, line, name, _, duration in rows:
        if line == trace_reduce.OP_LINE:
            entry = table[trace_reduce.short_name(name)]
            entry[0], entry[1], entry[2] = entry[0] + 1, entry[1] + duration, name
    out = ROOT / "chiprun_out" / "scope_ops"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell}.json").write_text(json.dumps({
        "programs": programs,
        "ops": {k: [c, ns, names.get(k, ""), hlo[:400]] for k, (c, ns, hlo) in table.items()},
    }, allow_nan=False))
    for scope in scopes:
        parts = scope.split("/")
        under = {
            k: v for k, v in table.items()
            if all(scope_reduce.has_scope(names.get(k, ""), part) for part in parts)
        }
        for key, (calls, ns, hlo) in sorted(under.items(), key=lambda kv: -kv[1][1]):
            if ns / 1e6 / programs >= 0.05:
                print(json.dumps({
                    "scope": scope, "op": key, "class": scope_reduce.classify(names[key]),
                    "calls_per_step": calls / programs, "ms_per_step": round(ns / 1e6 / programs, 3),
                    "result_bytes": result_bytes(hlo), "op_name": names[key],
                }, allow_nan=False), flush=True)
        total = sum(ns for _, ns, _ in under.values()) / 1e6 / programs
        print(json.dumps({"scope": scope, "programs": programs, "ms_per_step": round(total, 3)}, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
