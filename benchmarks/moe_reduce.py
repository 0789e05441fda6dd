"""Device time under the scopes a routed-experts model adds, and its routing
counters: what the `moe_*`, `mla_*` and `mtp_*` per-layer metrics read.

A scope is found on an operation's `op_name` as `scope_reduce.has_scope` finds
the step's own (`.../moe/experts/...`, `transpose(jvp(moe))/...`).  Times are of
device 0's steady rows, overlaps counted once, every class of the step
(forward, recomputed, backward) alike.  The counters are the program's
(`obs.tracing.counters()`: `moe.assignments`, `moe.assignments_held`,
`moe.expert_load_max`, `moe.expert_load_mean`, `moe.dropped`, one observation a
step, folded at `fit.log`).  A program that has neither (the parent of the PR
that added them) gives None everywhere."""

from __future__ import annotations

from benchmarks import scope_reduce, trace_reduce

# The scopes whose device time goes to the notes, each under its parent.
SCOPES = {
    "moe": ("router", "dispatch", "experts", "combine", "shared"),
    "attn": ("q_down", "q_up", "kv_down", "kv_up", "rope", "core", "out"),
    "mtp": ("join", "block", "final_norm", "head", "xent"),
    "loss": ("embed", "attn_norm", "mlp_norm", "mlp", "final_norm", "head", "xent"),
}
COUNTERS = (
    "moe.assignments", "moe.assignments_held", "moe.expert_load_max", "moe.expert_load_mean",
    "moe.dropped",
)


def _named_operations(run: dict) -> tuple[list[tuple[int, int, str]], int]:
    """Device 0's steady operations as (start, end, op_name), and the programs
    they ran in; once a run (two dozen scopes are read from them)."""
    if "moe_operations" not in run:
        rows, names = run.get("trace_rows"), None
        per_device = (run.get("trace") or {}).get("per_device")
        programs = per_device[0]["programs"] if rows and per_device else 0
        if programs:
            names = scope_reduce.op_names(run)
        operations = []
        if names:
            plane = f"/device:TPU:{trace_reduce.devices(rows)[0]}"
            for r in rows:
                if r[0] != plane or r[1] != trace_reduce.OP_LINE:
                    continue
                operation = trace_reduce.short_name(r[2])
                if not trace_reduce.CONTAINER.match(operation):
                    operations.append((r[3], r[3] + r[4], names.get(operation, "")))
        run["moe_operations"] = (operations, programs)
    return run["moe_operations"]


def scope_ms_per_step(
    run: dict, all_of: tuple[str, ...], any_of: tuple[str, ...] = ()
) -> float | None:
    """Milliseconds per executed program of the operations that carry every
    scope of `all_of` and, where given, one of `any_of`."""
    operations, programs = _named_operations(run)
    hits = [
        (start, end) for start, end, op_name in operations
        if all(scope_reduce.has_scope(op_name, s) for s in all_of)
        and (not any_of or any(scope_reduce.has_scope(op_name, s) for s in any_of))
    ]
    if not hits:
        return None
    return trace_reduce.total(trace_reduce.union(hits)) / 1e6 / programs


def scope_table(run: dict) -> dict | None:
    """Milliseconds per step under each scope of `SCOPES`, once; to the notes."""
    if "moe_scopes" not in run:
        table = {
            f"{parent}/{scope}": scope_ms_per_step(run, (parent, scope))
            for parent, scopes in SCOPES.items() for scope in scopes
        }
        table = {k: v for k, v in table.items() if v is not None}
        if table:
            run.setdefault("notes", {})["scope_ms_per_step"] = table
        run["moe_scopes"] = table or None
    return run["moe_scopes"]


def routing(run: dict) -> dict | None:
    """The routing counters as means per step, once; to the notes too, with
    the share of assignments on which program and reference differed where the
    reference counted it."""
    if "moe_routing" not in run:
        from deeplearning_cfn_tpu.obs import tracing

        read = getattr(tracing, "counters", None)
        counted = read() if read else {}
        out = None
        if all(counted.get(name, {}).get("count") for name in COUNTERS):
            out = {
                name: counted[name]["total"] / counted[name]["count"] for name in COUNTERS
            }
            out["steps"] = counted[COUNTERS[0]]["count"]
            note = dict(out)
            try:
                reference = run["manifest"].module("reference", run["config"]["kind"])
                note["differing_from_reference"] = getattr(reference, "last_routing", None)
            except KeyError:
                pass
            run.setdefault("notes", {})["moe_routing"] = note
        run["moe_routing"] = out
    return run["moe_routing"]
