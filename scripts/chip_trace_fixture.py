"""One traced benchmark run in this process, then what the tests keep of it:
the last programs of device 0's rows, the program's host rows beside them and
the operations' op_names, compact, under chiprun_out/.

`python3 scripts/chip_trace_fixture.py --workload <cell> --seed <n> [--programs 11]`
through chiprun.  Prints the benchmark's own lines; the recording goes to
chiprun_out/fixtures/<cell>.json and the untrimmed notes beside it."""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--programs", type=int, default=11,
                   help="how many of device 0's last programs the recording keeps")
    args = p.parse_args()

    from benchmarks import host_spans, run as bench_run, scope_reduce, trace_reduce

    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        rc = bench_run.main([
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "1",
        ])
    text = captured.getvalue()
    print(text, end="")
    if rc != 0:
        return rc
    out = ROOT / "chiprun_out" / "fixtures"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.lines.txt").write_text(text)

    trace_dir = ROOT / "benchmarks" / "out" / args.workload / "trace"
    rows = [r for r in trace_reduce.load_events(trace_dir) if r[0] == "/device:TPU:0"]
    programs = sorted(r[3] for r in rows if r[1] == trace_reduce.MODULE_LINE)
    cut = programs[-args.programs] if len(programs) > args.programs else programs[0]
    rows = [r for r in rows if r[3] >= cut and r[1] in (trace_reduce.OP_LINE, trace_reduce.MODULE_LINE)]
    host, origin = host_spans.load_rows(trace_dir)
    end = max(r[3] + r[4] for r in rows)
    host = [r for r in host if r[2] + r[3] >= cut - 60_000_000 and r[2] <= end + 5_000_000]
    names = scope_reduce.load_op_names(trace_dir)
    planes, lines, short = ["/device:TPU:0"], [trace_reduce.MODULE_LINE, trace_reduce.OP_LINE], {}
    compact = []
    for _, line, name, start, duration in rows:
        key = trace_reduce.short_name(name) if line == trace_reduce.OP_LINE else name
        compact.append([0, lines.index(line), short.setdefault(key, len(short)), start - cut, duration])
    threads = {}
    recording = {
        "cell": args.workload, "device": "TPU v5 lite", "origin_of_host_rows": origin,
        "planes": planes, "lines": lines, "names": list(short), "rows": compact,
        "host_rows": [
            [threads.setdefault(t, len(threads)), n, s - cut, d] for t, n, s, d in host
        ],
        "op_names": {k: v for k, v in names.items() if k in short},
    }
    path = out / f"trace_{args.workload.replace('.', '_').replace('-', '_')}.json"
    path.write_text(json.dumps(recording, separators=(",", ":"), allow_nan=False))
    print(json.dumps({"fixture": str(path), "bytes": path.stat().st_size,
                      "rows": len(compact), "host_rows": len(recording["host_rows"]),
                      "op_names": len(recording["op_names"])}, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
