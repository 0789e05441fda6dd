"""From a `jax.profiler` trace to numbers: device busy time, the operations
that took most of it, the idle gaps, collectives' exposed time and a named
kernel's time.

`load_events` turns the profiler's `.xplane.pb` into plain rows
`[plane, line, name, start_ns, duration_ns]` for the device planes; every
reduction works on such rows, so the tests drive them with a trimmed
recording kept as JSON.

On a TPU each chip is a plane `/device:TPU:<n>`.  Its `XLA Ops` line holds
one event per executed HLO operation (fusions, custom calls, copies) and
is what busy time is the union of; `XLA Modules` holds one event per
executed program and `Steps` the profiler's own step grouping, neither of
which is an operation.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# Operations that only hold others (a scan is a `while` whose body's
# operations are events of their own): busy all the same, but not listed.
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast"
)


def load_events(trace_dir: str | Path) -> list[list]:
    """Rows for every event on a device plane of the newest trace under
    `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for event in line.events:
                rows.append(
                    [plane.name, line.name, event.name, int(event.start_ns), int(event.duration_ns)]
                )
    return rows


def short_name(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)`, the HLO text the trace gives an
    operation as its name, cut to `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def steady_rows(rows: list[list], skip_programs: int) -> list[list]:
    """The rows from the start of each device's `skip_programs`-th executed
    program on: what ran before the profiler had settled is left out.  The
    `XLA Modules` line has one event per executed program."""
    if not skip_programs:
        return rows
    kept = []
    for device in devices(rows):
        plane = f"/device:TPU:{device}"
        programs = sorted(r[3] for r in rows if r[0] == plane and r[1] == MODULE_LINE)
        if len(programs) <= skip_programs:
            continue
        kept += [r for r in rows if r[0] == plane and r[3] >= programs[skip_programs]]
    return kept


def devices(rows: list[list]) -> list[int]:
    return sorted({int(DEVICE_PLANE.match(r[0]).group(1)) for r in rows})


def op_intervals(rows: list[list], device: int, keep=None) -> list[tuple[int, int, str]]:
    """(start, end, name) of the operations on one device, by start."""
    plane = f"/device:TPU:{device}"
    out = [
        (r[3], r[3] + r[4], short_name(r[2]))
        for r in rows
        if r[0] == plane and r[1] == OP_LINE and (keep is None or keep(short_name(r[2])))
    ]
    return sorted(out)


def union(intervals) -> list[tuple[int, int]]:
    """Merged, non-overlapping (start, end) pairs."""
    merged: list[list[int]] = []
    for start, end, *_ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list[tuple[int, int]]:
    """The parts of merged `intervals` that no merged `holes` covers; both
    sorted, one sweep."""
    out = []
    holes = list(holes)
    j = 0
    for a, b in intervals:
        cursor = a
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < b:
            out.append((cursor, b))
    return out


def reduce_device(rows: list[list], device: int) -> dict:
    """Busy and idle of one device over its traced window, which runs from
    its first operation's start to its last one's end."""
    ops = op_intervals(rows, device)
    plane = f"/device:TPU:{device}"
    programs = sum(1 for r in rows if r[0] == plane and r[1] == MODULE_LINE)
    if not ops:
        return {"device": device, "window_s": 0.0, "busy_s": 0.0, "ops": 0, "programs": programs}
    busy = union(ops)
    window = (busy[0][0], busy[-1][1])
    by_name: dict[str, int] = {}
    for start, end, name in ops:
        if not CONTAINER.match(name):
            by_name[name] = by_name.get(name, 0) + (end - start)
    starts = [start for start, _, _ in ops]
    gaps = []
    for (_, a_end), (b_start, _) in zip(busy, busy[1:]):
        following = ops[bisect.bisect_left(starts, b_start)][2]
        gaps.append((b_start - a_end, f"before {following}"))
    gaps.sort(reverse=True)
    collective = union([o for o in ops if COLLECTIVE.search(o[2])])
    other = union([o for o in ops if not COLLECTIVE.search(o[2])])
    return {
        "device": device,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": total(busy) / 1e9,
        "ops": len(ops),
        # Executed programs in the window: the train steps it holds.
        "programs": programs,
        "device_ops": [
            [name, ns / 1e9]
            for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:10]],
        "collective_s": total(collective) / 1e9,
        "collective_exposed_s": total(subtract(collective, other)) / 1e9,
    }


def kernel_seconds(rows: list[list], device: int, pattern: str) -> tuple[float, int]:
    """Summed device time and count of the operations whose name matches."""
    rx = re.compile(pattern)
    hits = op_intervals(rows, device, keep=lambda name: bool(rx.search(name)))
    return sum(e - s for s, e, _ in hits) / 1e9, len(hits)


def reduce(rows: list[list]) -> dict:
    """Every device's reduction, and the averages the result line carries."""
    per_device = [reduce_device(rows, d) for d in devices(rows)]
    busy = [d["busy_s"] for d in per_device]
    window = [d["window_s"] for d in per_device]
    return {
        "per_device": per_device,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": sum(window) / len(window) if window else 0.0,
    }
