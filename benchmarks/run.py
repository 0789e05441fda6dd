"""`python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`

One run of one cell of `BENCHMARK.json`, in one process, on the machine it
is started on.  `main` is the only place that looks for the chip: it
refuses any platform but `tpu`, a device kind that `peaks.json` does not
know, and a chip count other than the cell's (non-zero exit, no result
line).  Beneath it `run_cell` is a plain function, which the tests drive at
toy size on the CPU.

The run goes the operator's way: `cli.main(["run", template, ...])` ->
provision -> contract -> launch plan -> `benchmarks.job` -> `Trainer.fit`.
The last line of stdout is the result; earlier lines say what was compared
and what the step times were.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before jax is imported: set-up starts here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from benchmarks.manifest import DEFAULT, ROOT, Manifest  # noqa: E402


def load_peaks() -> dict:
    return json.loads((Path(__file__).parent / "peaks.json").read_text())


def require_device(chips: int) -> dict:
    """The device JAX found by itself, or a refusal."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" or device["kind"] not in load_peaks():
        raise SystemExit(
            f"benchmarks.run: refusing to run: needs platform 'tpu' with a device "
            f"kind in benchmarks/peaks.json, found {device}"
        )
    if device["count"] != chips:
        raise SystemExit(
            f"benchmarks.run: refusing to run: the cell asks for {chips} chip(s), "
            f"JAX sees {device['count']}"
        )
    return device


def run_cell(
    manifest_path: Path,
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: int,
    device: dict,
    peaks: dict,
    t_process: float,
) -> tuple[dict, list[dict]]:
    """(result line, earlier lines) of one run."""
    from benchmarks import trace_reduce
    from deeplearning_cfn_tpu import cli

    manifest = Manifest(manifest_path)
    cell = manifest.workload(workload)
    traffic = manifest.json("traffic", cell["traffic"])
    out = Path("benchmarks") / "out" / workload
    # The contract is published under $DLCFN_ROOT; it stays pointed at the
    # run's own directory for the rest of the process.
    os.environ["DLCFN_ROOT"] = str(ROOT / out / "cluster")
    argv = ["run", str(Path(__file__).parent / "template.json")]
    for name, value in (
        ("Workers", cell["chips"]),
        ("Batch", traffic["global_batch"]),
        ("Manifest", os.path.relpath(manifest_path, ROOT)),
        ("Workload", workload),
        ("Seed", seed),
        ("Seconds", seconds),
        ("Trace", trace),
        ("Out", out),
    ):
        argv += ["-P", f"{name}={value}"]
    captured = io.StringIO()
    t_cli = time.perf_counter()
    with contextlib.redirect_stdout(captured):
        rc = cli.main(argv)
    if rc != 0:
        raise RuntimeError(f"dlcfn run exited {rc}: {captured.getvalue()[-2000:]}")
    run = json.loads(captured.getvalue().strip().splitlines()[-1])["result"]
    run.update(t_process=t_process, t_cli=t_cli, peaks=peaks, device=device, manifest=manifest)
    notes = [{"check": run["check"], "check_seconds": run["check_seconds"],
              "loss": {"program": run["program"]["loss"], "reference": run["reference"]["loss"]}},
             {"conditions": run["conditions"], "compile": run["compile"]}]
    if trace:
        rows = trace_reduce.steady_rows(
            trace_reduce.load_events(run["trace_dir"]), run["trace_skip_steps"]
        )
        run["trace_rows"] = rows
        run["trace"] = trace_reduce.reduce(rows)
    read = {}
    for folder, entries in (
        ("end_to_end", manifest.end_to_end_for(workload)),
        ("layer_metrics", manifest.per_layer_for(workload)),
    ):
        read[folder] = {}
        for entry in entries:
            value = manifest.module(folder, entry["name"]).read(run)
            if value is not None:
                read[folder][entry["name"]] = {"value": value, "unit": entry["unit"]}
    # The result line carries one kind; the other kind, as far as this run
    # can read it (no trace, no trace's metrics), goes on an earlier line.
    metrics = read.pop("layer_metrics" if trace else "end_to_end")
    notes.append({"notes": run.get("notes", {}), "not_in_the_result": read})

    window = run["window"]
    attempted = window[1] - window[0] if window else 0
    failed = (
        sum(not math.isfinite(v) for v in run["losses"][window[0] + 1 : window[1] + 1])
        if window else 0
    )
    step = run["step_memory"]
    peak = max(
        max(m["peak_bytes_in_use"], m["bytes_in_use"] + step["temp_size_in_bytes"])
        for m in run["memory"].values()
    )
    line = {
        "correct": bool(all(run["conditions"].values()) and all(r["ok"] for r in run["check"])),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak},
    }
    if trace:
        reduced = run["trace"]
        line["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        first = reduced["per_device"][0] if reduced["per_device"] else {}
        line["breakdown"] = {
            "device_ops": first.get("device_ops", []),
            "idle_gaps": first.get("idle_gaps", []),
        }
    return line, notes


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    manifest = Manifest(DEFAULT)
    cell = manifest.workload(args.workload)
    device = require_device(int(cell["chips"]))
    line, notes = run_cell(
        DEFAULT,
        args.workload,
        seed=args.seed,
        seconds=float(args.seconds if args.seconds is not None else manifest.data["run_seconds"]),
        trace=args.trace,
        device=device,
        peaks=load_peaks()[device["kind"]],
        t_process=T_PROCESS,
    )
    for note in notes:
        print(json.dumps(note))
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
