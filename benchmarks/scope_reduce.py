"""Device time by the step's part: forward, backward, recompute, optimizer,
input, from the names the program's `jax.named_scope`s put on its operations.

On this runtime (libtpu 0.0.34) an operation's event on the `XLA Ops` line is
named by its HLO text, and the HLO `op_name` (`jit(train_step)/loss/
transpose(jvp(...))/...`) is the stat `tf_op` of the event's *metadata*, which
`jax.profiler.ProfileData` does not hand out.  `load_op_names` therefore reads
the `.xplane.pb` itself, through protobuf with the few messages of
`xplane.proto` it needs described here, and gives `{operation: op_name}` for
one device plane, the operation by `trace_reduce.short_name` (`fusion.12`); a
program that names nothing gives an empty map.

The rule, in this order (a fusion carries its root's `op_name`):

1. `optimizer`: the `op_name` has the scope `optimizer` (a scope stands on
   the name stack as a component, `.../loss/...`, or inside a
   transformation's wrapper, `transpose(jvp(mlp))`);
2. `recompute`: it has `rematted_computation`: JAX puts a rematerialised
   forward pass under `transpose(jvp(...))/.../checkpoint/rematted_computation/`,
   so it has to be asked before `transpose(`;
3. `backward`: it has `transpose(`;
4. `forward`: it is under the scope `loss` otherwise;
5. `input`: the scope `input`;
6. `unscoped`: anything else (copies and operations the compiler made up).

Containers (`while`, `conditional`, `call`) only hold others and are left
out, as `trace_reduce` leaves them out of its list.
"""

from __future__ import annotations

import re
from pathlib import Path

from benchmarks import host_spans, trace_reduce

CLASSES = ("forward", "backward", "recompute", "optimizer", "input", "unscoped")
STEP_SCOPES = ("loss", "optimizer", "input")


def has_scope(op_name: str, scope: str) -> bool:
    """Whether a named scope is on the operation's name stack.  It stands
    there as a component of its own (`.../loss/...`) or, where a
    transformation has wrapped the function it was entered in, inside the
    wrapper's parentheses (`jvp(mlp)`, `transpose(jvp(attn))`)."""
    return re.search(rf"(?<![A-Za-z0-9_]){re.escape(scope)}(?![A-Za-z0-9_])", op_name) is not None


def classify(op_name: str) -> str:
    if has_scope(op_name, "optimizer"):
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if has_scope(op_name, "loss"):
        return "forward"
    if has_scope(op_name, "input"):
        return "input"
    return "unscoped"


def _xspace_class():
    """The message class for an XSpace, from the fields of tsl's
    `xplane.proto` that hold names and stats (unknown fields are skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_scope_reduce_xplane.proto", package="benchmarks_xplane", syntax="proto3"
    )
    T = descriptor_pb2.FieldDescriptorProto

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for number, field, kind, repeated, type_name in fields:
            m.field.add(
                name=field, number=number, type=kind,
                label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                type_name=type_name,
            )

    pkg = ".benchmarks_xplane."
    message("XStat", (1, "metadata_id", T.TYPE_INT64, False, None),
            (5, "str_value", T.TYPE_STRING, False, None),
            (7, "ref_value", T.TYPE_UINT64, False, None))
    message("XEventMetadata", (1, "id", T.TYPE_INT64, False, None),
            (2, "name", T.TYPE_STRING, False, None),
            (5, "stats", T.TYPE_MESSAGE, True, pkg + "XStat"))
    message("XStatMetadata", (1, "id", T.TYPE_INT64, False, None),
            (2, "name", T.TYPE_STRING, False, None))
    # A proto map is a repeated message of (key = 1, value = 2).
    message("EventMetadataEntry", (1, "key", T.TYPE_INT64, False, None),
            (2, "value", T.TYPE_MESSAGE, False, pkg + "XEventMetadata"))
    message("StatMetadataEntry", (1, "key", T.TYPE_INT64, False, None),
            (2, "value", T.TYPE_MESSAGE, False, pkg + "XStatMetadata"))
    message("XPlane", (2, "name", T.TYPE_STRING, False, None),
            (4, "event_metadata", T.TYPE_MESSAGE, True, pkg + "EventMetadataEntry"),
            (5, "stat_metadata", T.TYPE_MESSAGE, True, pkg + "StatMetadataEntry"))
    message("XSpace", (1, "planes", T.TYPE_MESSAGE, True, pkg + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchmarks_xplane.XSpace"))


def load_op_names(trace_dir: str | Path, device: int = 0) -> dict[str, str]:
    """`{operation: op_name}` for the operations of one device plane of the
    newest trace under `trace_dir`."""
    space = _xspace_class()()
    space.ParseFromString(host_spans.newest_capture(trace_dir).read_bytes())
    out: dict[str, str] = {}
    for plane in space.planes:
        if plane.name != f"/device:TPU:{device}":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) != "tf_op":
                    continue
                # A string stat is given inline or as a reference to a stat
                # metadata's name; the value is `<op_name>:<op type>`, the
                # type empty for XLA's operations.
                value = stat.str_value or stat_names.get(stat.ref_value, "")
                out[trace_reduce.short_name(entry.value.name)] = value.rsplit(":", 1)[0]
    return out


def op_names(run: dict) -> dict[str, str]:
    """The run's map, read once; a test puts its own under `op_names`."""
    if "op_names" not in run:
        run["op_names"] = load_op_names(run["trace_dir"]) if run.get("trace_dir") else {}
    return run["op_names"]


def reduce(rows: list[list], names: dict[str, str], device: int = 0) -> dict | None:
    """Device time by class on one device, from steady-state rows and the
    name map.  None where no operation carries a scope of the step (the
    program names nothing: the parent of the PR that added the scopes, or an
    executable that a cache kept from before)."""
    plane = f"/device:TPU:{device}"
    by_class: dict[str, list] = {c: [] for c in CLASSES}
    scoped: list = []
    attention_backward: list = []
    unscoped_kinds: dict[str, int] = {}
    for r in rows:
        if r[0] != plane or r[1] != trace_reduce.OP_LINE:
            continue
        operation = trace_reduce.short_name(r[2])
        if trace_reduce.CONTAINER.match(operation):
            continue
        op_name = names.get(operation, "")
        interval = (r[3], r[3] + r[4])
        found = classify(op_name)
        by_class[found].append(interval)
        if found == "unscoped":
            kind = re.sub(r"\.\d+$", "", operation)  # `copy-done.757` -> `copy-done`
            unscoped_kinds[kind] = unscoped_kinds.get(kind, 0) + r[4]
        if any(has_scope(op_name, s) for s in STEP_SCOPES):
            scoped.append(interval)
        if has_scope(op_name, "attn_bwd"):
            attention_backward.append(interval)
    if not scoped:
        return None
    busy = trace_reduce.union(i for c in CLASSES for i in by_class[c])
    seconds = {c: trace_reduce.total(trace_reduce.union(by_class[c])) / 1e9 for c in CLASSES}
    classified = trace_reduce.union(i for c in CLASSES if c != "unscoped" for i in by_class[c])
    return {
        "seconds": seconds,
        "busy_s": trace_reduce.total(busy) / 1e9,
        "classified_s": trace_reduce.total(classified) / 1e9,
        "attention_backward_s": trace_reduce.total(trace_reduce.union(attention_backward)) / 1e9,
        "operations": sum(len(v) for v in by_class.values()),
        # What the unscoped time is, by kind of operation (`copy-done`): on
        # this compiler mostly copies between memory spaces it put in itself.
        "unscoped_kinds_s": {
            k: ns / 1e9 for k, ns in sorted(unscoped_kinds.items(), key=lambda kv: -kv[1])[:5]
        },
    }


def reduced(run: dict) -> dict | None:
    """The reduction of the run's traced window on device 0, once; the
    table goes to the notes."""
    if "scope_reduce" not in run:
        rows = run.get("trace_rows")
        out = reduce(rows, op_names(run)) if rows else None
        if out is not None:
            programs = run["trace"]["per_device"][0]["programs"]
            out["programs"] = programs
            run.setdefault("notes", {})["scope_reduce"] = {
                "ms_per_step": {
                    c: 1e3 * s / programs for c, s in out["seconds"].items()
                } if programs else None,
                "busy_s": out["busy_s"], "programs": programs, "operations": out["operations"],
                "unscoped_kinds_s": out["unscoped_kinds_s"],
            }
        run["scope_reduce"] = out
    return run["scope_reduce"]


def class_ms_per_step(run: dict, name: str) -> float | None:
    """Device milliseconds of one class per executed program; None where the
    class has no operation (nothing is rematerialised, say)."""
    out = reduced(run)
    if out is None or not out["programs"] or not out["seconds"][name]:
        return None
    return 1e3 * out["seconds"][name] / out["programs"]

