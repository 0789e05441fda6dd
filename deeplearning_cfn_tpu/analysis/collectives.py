"""The DLC5xx comms/memory discipline rules (gated: ``dlcfn lint --comms``).

PR 7/8 made retraces and step phases visible; this pass makes the other
two MFU killers statically checkable — unintended collectives and HBM
pressure introduced by sharding mistakes.  The MLPerf-at-pod-scale
result (arxiv 1909.09756) and the CUDA-aware-MPI characterization
(arxiv 1810.11112) agree on the mechanism: communication *volume*
discipline, not kernel speed, separates flat scaling from linear.  Each
rule anchors on a concrete accidental-collective shape:

DLC500 spec-axis drift / in-out mismatch   DLC503 cross-mesh leakage
DLC501 unconstrained large intermediate    DLC504 unsummed shard_map reduce
DLC502 host materialization of sharded     DLC505 donated buffer read after
       arrays                                     the donating call

Scope: everywhere shardings are authored or consumed — ``train/``,
``parallel/``, ``models/``, ``ops/``, ``serve/``, and ``bench.py``
(``parallel/`` is new relative to DLC4xx: the sharding-rule tables and
mesh builders are where axis vocabularies drift first).

The static half is paired with a dynamic comms-audit sentinel
(analysis/comms_audit.py) that lowers the real train/serve programs and
machine-reads their HLO for collectives; its findings use the reserved
ids DLC510 (comms-budget regression), DLC511 (unpredicted fsdp
all-gather), and DLC512 (serialized collective the bucketed overlap
schedule should hide — overlap_score ratchet) so all halves share one
baseline ratchet (scripts/lint_baseline.json).
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterator

from deeplearning_cfn_tpu.analysis.core import (
    FileContext,
    Rule,
    Violation,
    call_name,
    dotted_name,
    has_keyword,
    keyword,
    register,
    walk_skipping_nested_functions,
)
from deeplearning_cfn_tpu.analysis.sharding import (
    _JIT_CORE,
    _FnDef,
    canonical_mesh_axes,
    traced_functions,
)

GATE = "comms"
RULE_IDS = ("DLC500", "DLC501", "DLC502", "DLC503", "DLC504", "DLC505")

# Reserved for the dynamic comms-audit sentinel (analysis/comms_audit.py):
# same namespace, same baseline ratchet, but findings come from lowering
# the real programs and reading their HLO rather than from this AST pass.
AUDIT_RULE_BUDGET = "DLC510"
AUDIT_RULE_UNPREDICTED = "DLC511"
AUDIT_RULE_OVERLAP = "DLC512"
AUDIT_RULE_IDS = (
    AUDIT_RULE_BUDGET,
    AUDIT_RULE_UNPREDICTED,
    AUDIT_RULE_OVERLAP,
)

# DLC4xx covers the compute tree; comms adds parallel/ — the sharding
# rule tables and mesh builders author the axis vocabulary everything
# else consumes.
_COMMS_DIRS = ("train", "parallel", "models", "ops", "serve")


def _applies_comms_paths(path: Path) -> bool:
    return path.name == "bench.py" or any(d in path.parts for d in _COMMS_DIRS)


# --- shared matchers ---------------------------------------------------------

_SHARDING_KWARGS = ("in_shardings", "out_shardings")
_CONSTRAINT_CALLS = (
    "with_sharding_constraint",
    "jax.lax.with_sharding_constraint",
    "lax.with_sharding_constraint",
    "maybe_shard",
    "sharding.maybe_shard",
)


def _spec_call(name: str | None) -> bool:
    if name is None:
        return False
    return name.rsplit(".", 1)[-1] in ("P", "PartitionSpec")


def _literal_specs(node: ast.AST) -> list[ast.Call]:
    """P(...)/PartitionSpec(...) calls under node."""
    return [
        n
        for n in ast.walk(node)
        if isinstance(n, ast.Call) and _spec_call(call_name(n))
    ]


def _spec_axes(node: ast.AST) -> Iterator[ast.Constant]:
    """String constants inside P(...)/PartitionSpec(...) calls under node."""
    for spec in _literal_specs(node):
        for sub in ast.walk(spec):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                yield sub


# --- DLC500: spec axes across a pjit in/out pair -----------------------------
# in_shardings and out_shardings are two halves of ONE layout contract.
# An axis that appears on the way in but not on the way out (or vice
# versa) makes XLA reshard at the program boundary — an all-gather or
# all-to-all on EVERY call that no line of user code shows.  And an axis
# name outside AXIS_ORDER (machine-read from parallel/mesh.py, like
# DLC403) silently degrades that side to replication.  Only literal
# P(...) specs are compared: passing the same shardings object for both
# kwargs (the trainer idiom) is by construction consistent.


def _check_inout_spec_consistency(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    try:
        canonical = set(canonical_mesh_axes())
    except (OSError, ValueError, SyntaxError):
        canonical = None  # DLC403 owns reporting extraction failure
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if call_name(node) not in _JIT_CORE and not has_keyword(
            node, *_SHARDING_KWARGS
        ):
            continue
        kw_in = keyword(node, "in_shardings")
        kw_out = keyword(node, "out_shardings")
        if kw_in is None or kw_out is None:
            continue
        axes_in = list(_spec_axes(kw_in.value))
        axes_out = list(_spec_axes(kw_out.value))
        if canonical is not None:
            for const in axes_in + axes_out:
                if const.value not in canonical:
                    shown = "/".join(sorted(canonical))
                    yield ctx.violation(
                        "DLC500",
                        const,
                        f"axis {const.value!r} in a pjit sharding spec does "
                        f"not resolve against the mesh axes ({shown}) "
                        "machine-read from parallel/mesh.py AXIS_ORDER: "
                        "that side of the layout contract silently "
                        "degrades to replication",
                    )
        # Compare the two halves only when both carry literal specs: a
        # bare name (state_shardings passed to both kwargs) is
        # consistent by construction.  P(None, ...) counts as a literal
        # spec — dropping every axis on the way out IS the mismatch.
        if not _literal_specs(kw_in.value) or not _literal_specs(kw_out.value):
            continue
        set_in = {c.value for c in axes_in}
        set_out = {c.value for c in axes_out}
        for missing in sorted(set_in - set_out):
            yield ctx.violation(
                "DLC500",
                kw_out.value,
                f"axis {missing!r} is sharded by in_shardings but absent "
                "from this literal out_shardings spec: XLA inserts an "
                "all-gather over that axis at the program boundary on "
                "every call; carry the axis through (or spell the "
                "resharding explicitly)",
            )
        for extra in sorted(set_out - set_in):
            yield ctx.violation(
                "DLC500",
                kw_out.value,
                f"axis {extra!r} appears only in out_shardings of this "
                "pjit in/out pair: the output is resharded onto an axis "
                "the inputs never occupied — a per-call all-to-all no "
                "line of user code shows; shard the inputs to match",
            )


register(
    Rule(
        id="DLC500",
        name="pjit-inout-spec-consistency",
        doc="pjit in/out literal specs must use known axes and agree",
        check=_check_inout_spec_consistency,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)

# --- DLC501: large intermediate feeding compute without a constraint ---------
# Inside sharded traced code, a matmul/attention output that directly
# feeds another matmul-family op with no with_sharding_constraint /
# maybe_shard between them leaves the intermediate's layout to GSPMD
# inference — which, at a propagation conflict, resolves to REPLICATED:
# the classic accidental all-gather of the largest activation in the
# model.  The rule is deliberately shape-anchored: it fires only on a
# direct producer->consumer chain of matmul-family calls inside a traced
# function, and only in files that author shardings at all (a file with
# no constraint/in_shardings anywhere is single-device code where layout
# inference has nothing to get wrong).

_MATMUL_CALLS = (
    "jnp.matmul",
    "jnp.dot",
    "jnp.einsum",
    "jax.numpy.matmul",
    "jax.numpy.dot",
    "jax.numpy.einsum",
    "lax.dot_general",
    "jax.lax.dot_general",
    "dot_product_attention",
    "jax.nn.dot_product_attention",
)


def _is_matmul_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
        return True
    if isinstance(node, ast.Call):
        name = call_name(node)
        if name in _MATMUL_CALLS:
            return True
        if name is not None and name.rsplit(".", 1)[-1] == "einsum":
            return True
    return False


def _file_authors_shardings(ctx: FileContext) -> bool:
    cached = getattr(ctx, "_dlc501_authors", None)
    if cached is not None:
        return cached
    found = False
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _CONSTRAINT_CALLS or has_keyword(node, *_SHARDING_KWARGS):
                found = True
                break
    ctx._dlc501_authors = found  # type: ignore[attr-defined]
    return found


def _names_loaded(node: ast.AST) -> set[str]:
    return {
        n.id
        for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _check_unconstrained_intermediate(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    if not _file_authors_shardings(ctx):
        return
    for fn, why in traced_functions(ctx).items():
        # Producer names: name -> assignment statement, in body order.
        statements = list(walk_skipping_nested_functions(fn.body))
        # Nested matmul: consumer wraps producer in one expression —
        # there is nowhere a constraint could even have been applied.
        for node in statements:
            if not _is_matmul_expr(node):
                continue
            inner = (
                [node.left, node.right]
                if isinstance(node, ast.BinOp)
                else list(getattr(node, "args", []))
            )
            for operand in inner:
                if _is_matmul_expr(operand):
                    yield ctx.violation(
                        "DLC501",
                        operand,
                        f"matmul/attention output feeds another matmul "
                        f"directly inside traced {fn.name}() ({why}) with "
                        "no with_sharding_constraint on the intermediate: "
                        "GSPMD resolves propagation conflicts to "
                        "REPLICATED — the accidental all-gather of the "
                        "largest activation; name the intermediate and "
                        "constrain it (parallel.sharding.maybe_shard)",
                    )
        # Named chain: walk_skipping is stack-order, so producer /
        # kill (rebind or constraint) / consumer events are resolved by
        # line number, not visit order.
        produced: dict[str, list[int]] = {}
        killed: dict[str, list[int]] = {}
        for stmt in statements:
            if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
                continue
            target = stmt.targets[0]
            if not isinstance(target, ast.Name):
                continue
            if _is_matmul_expr(stmt.value):
                produced.setdefault(target.id, []).append(stmt.lineno)
            else:
                # Any rebinding — through a constraint call or otherwise
                # — launders the name for lines below it.
                killed.setdefault(target.id, []).append(stmt.lineno)
        if not produced:
            continue
        for node in statements:
            if not _is_matmul_expr(node):
                continue
            operands = (
                [node.left, node.right]
                if isinstance(node, ast.BinOp)
                else list(getattr(node, "args", []))
            )
            for op in operands:
                if not (isinstance(op, ast.Name) and op.id in produced):
                    continue
                use_line = getattr(node, "lineno", 0)
                producer_line = max(
                    (ln for ln in produced[op.id] if ln < use_line),
                    default=None,
                )
                if producer_line is None or any(
                    producer_line < ln < use_line
                    for ln in killed.get(op.id, ())
                ):
                    continue
                yield ctx.violation(
                    "DLC501",
                    node,
                    f"matmul/attention output {op.id!r} feeds another "
                    f"matmul inside traced {fn.name}() ({why}) with no "
                    "with_sharding_constraint between producer and "
                    "consumer: GSPMD resolves propagation conflicts "
                    "to REPLICATED — the accidental all-gather shape; "
                    "constrain the intermediate "
                    "(parallel.sharding.maybe_shard)",
                )
                break


register(
    Rule(
        id="DLC501",
        name="unconstrained-large-intermediate",
        doc="matmul chains in sharded traced code need a layout constraint",
        check=_check_unconstrained_intermediate,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)

# --- DLC502: host materialization of a sharded array -------------------------
# device_get / np.asarray / .item() on an array the SAME scope placed
# with a NamedSharding (device_put with a sharding, or a constraint
# call) is a full all-gather PLUS a device->host copy of the assembled
# global array — on a pod, gigabytes through one host NIC.  The rule
# tracks only scope-local evidence: a name is "known sharded" when this
# scope assigned it from device_put(x, <sharding>) or a constraint call.

_HOST_MATERIALIZE = (
    "jax.device_get",
    "device_get",
    "np.asarray",
    "numpy.asarray",
    "np.array",
    "numpy.array",
)
_DEVICE_PUT = ("jax.device_put", "device_put")


def _scopes(tree: ast.Module) -> Iterator[_FnDef]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _check_sharded_host_materialization(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    for fn in _scopes(tree):
        sharded: dict[str, int] = {}  # name -> line it became sharded
        for stmt in walk_skipping_nested_functions(fn.body):
            if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
                continue
            target = stmt.targets[0]
            if not isinstance(target, ast.Name) or not isinstance(
                stmt.value, ast.Call
            ):
                continue
            name = call_name(stmt.value)
            if name in _DEVICE_PUT and len(stmt.value.args) >= 2:
                sharded[target.id] = stmt.lineno
            elif name in _CONSTRAINT_CALLS:
                sharded[target.id] = stmt.lineno
        if not sharded:
            continue
        for node in walk_skipping_nested_functions(fn.body):
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            victim: str | None = None
            if (
                cname in _HOST_MATERIALIZE
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in sharded
                and node.lineno > sharded[node.args[0].id]
            ):
                victim = node.args[0].id
                what = f"{cname}({victim})"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in sharded
                and node.lineno > sharded[node.func.value.id]
            ):
                victim = node.func.value.id
                what = f"{victim}.item()"
            if victim is not None:
                yield ctx.violation(
                    "DLC502",
                    node,
                    f"{what} materializes an array this scope placed with "
                    "a sharding: the host assembles the full global array "
                    "(an implicit all-gather through one host's NIC); "
                    "read per-shard via addressable_shards, or reduce "
                    "on-device first",
                )


register(
    Rule(
        id="DLC502",
        name="sharded-host-materialization",
        doc="no device_get/np.asarray/.item() on scope-local sharded arrays",
        check=_check_sharded_host_materialization,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)

# --- DLC503: cross-mesh leakage ----------------------------------------------
# The ambient mesh is part of the jit dispatch-cache key.  A compiled
# callable warmed under ``with set_mesh(A)`` and then dispatched bare —
# or under a different mesh — misses its own cache entry and compiles
# the whole program a second time (the PR 7 bench double-compile,
# generalized).  Worse than the compile bill: the two executables can
# carry different collective schedules.  The rule is per-scope: every
# dispatch of a compiled callable in one function must run under the
# same set_mesh expression.

_SET_MESH_CALLS = ("set_mesh", "jax.set_mesh", "jax.sharding.use_mesh")


def _mesh_ctx_expr(stmt: ast.With) -> ast.expr | None:
    for item in stmt.items:
        call = item.context_expr
        if isinstance(call, ast.Call) and call_name(call) in _SET_MESH_CALLS:
            return call.args[0] if call.args else None
    return None


def _compiled_callable_names(fn: _FnDef) -> set[str]:
    """Names this scope binds to compiled callables: jit wrappers, AOT
    ``.lower(...).compile()`` results, and the trainer's ``step_fn`` /
    ``multi_step_fn`` family."""
    from deeplearning_cfn_tpu.analysis.sharding import _is_jit_expr

    out: set[str] = set()
    for stmt in walk_skipping_nested_functions(fn.body):
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            continue
        target = stmt.targets[0]
        if not isinstance(target, ast.Name):
            continue
        value = stmt.value
        terminal: str | None = None
        if isinstance(value, ast.Call):
            terminal = (call_name(value) or "").rsplit(".", 1)[-1]
            if _is_jit_expr(value) or _is_jit_expr(value.func):
                out.add(target.id)
                continue
        elif isinstance(value, ast.Attribute):
            terminal = value.attr
        if terminal is not None and (
            terminal == "compile" or terminal.endswith("step_fn")
        ):
            out.add(target.id)
    return out


def _check_cross_mesh_leakage(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    for fn in _scopes(tree):
        compiled = _compiled_callable_names(fn)
        if not compiled:
            continue
        # name -> {mesh expression dump or None (bare)} -> first call node
        dispatches: dict[str, dict[str | None, ast.Call]] = {}
        for node in walk_skipping_nested_functions(fn.body):
            if not isinstance(node, ast.Call):
                continue
            if not (
                isinstance(node.func, ast.Name) and node.func.id in compiled
            ):
                continue
            mesh_key: str | None = None
            cur = ctx.parents.get(node)
            while cur is not None and cur is not fn:
                if isinstance(cur, ast.With):
                    expr = _mesh_ctx_expr(cur)
                    if expr is not None:
                        mesh_key = ast.dump(expr)
                        break
                cur = ctx.parents.get(cur)
            dispatches.setdefault(node.func.id, {}).setdefault(mesh_key, node)
        for name, by_mesh in dispatches.items():
            if len(by_mesh) < 2:
                continue
            meshes = sorted(k for k in by_mesh if k is not None)
            if not meshes:
                continue  # never dispatched under set_mesh: out of scope
            for mesh_key, node in sorted(
                by_mesh.items(), key=lambda kv: kv[1].lineno
            ):
                if mesh_key == meshes[0]:
                    continue
                how = (
                    "bare (no ambient mesh)"
                    if mesh_key is None
                    else "under a different set_mesh"
                )
                yield ctx.violation(
                    "DLC503",
                    node,
                    f"compiled callable {name}() is dispatched {how} here "
                    "but under set_mesh elsewhere in this scope: the "
                    "ambient mesh is part of the jit cache key, so the "
                    "two dispatches compile two executables with "
                    "independent collective schedules (the bench "
                    "double-compile, generalized); dispatch every call "
                    "under the same mesh",
                )


register(
    Rule(
        id="DLC503",
        name="cross-mesh-leakage",
        doc="every dispatch of a compiled callable must use one ambient mesh",
        check=_check_cross_mesh_leakage,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)

# --- DLC504: shard_map reduction without a named collective ------------------
# Inside shard_map every array is the LOCAL shard.  jnp.sum/mean over a
# sharded axis without a psum/pmean over the mesh axis returns the
# partial reduction of one shard, silently treated as the global value —
# a loss that is 1/N of the truth, gradients that never see the other
# shards.  The lockset-style anchor: a shard_map body that reduces but
# never names a collective over any mesh axis.

_REDUCE_CALLS = ("sum", "mean", "prod", "max", "min")
_COLLECTIVE_CALLS = (
    "psum",
    "pmean",
    "pmax",
    "pmin",
    "all_gather",
    "psum_scatter",
    "ppermute",
    "all_to_all",
)


def _shard_map_bodies(tree: ast.Module) -> Iterator[_FnDef]:
    from deeplearning_cfn_tpu.analysis.sharding import _defs_by_name

    defs = _defs_by_name(tree)
    seen: set[_FnDef] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name is None or name.rsplit(".", 1)[-1] != "shard_map":
            continue
        if node.args and isinstance(node.args[0], ast.Name):
            for fn in defs.get(node.args[0].id, ()):
                if fn not in seen:
                    seen.add(fn)
                    yield fn


def _reduce_call(node: ast.Call) -> str | None:
    name = call_name(node)
    if name is None:
        return None
    head, _, terminal = name.rpartition(".")
    if terminal in _REDUCE_CALLS and head in ("jnp", "jax.numpy", "np", "numpy"):
        return name
    return None


def _check_shard_map_reduction(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    for fn in _shard_map_bodies(tree):
        has_collective = any(
            isinstance(n, ast.Call)
            and (call_name(n) or "").rsplit(".", 1)[-1] in _COLLECTIVE_CALLS
            for n in ast.walk(fn)
        )
        if has_collective:
            continue  # the body is axis-aware; trust its reductions
        for node in walk_skipping_nested_functions(fn.body):
            if not isinstance(node, ast.Call):
                continue
            name = _reduce_call(node)
            if name is None:
                continue
            yield ctx.violation(
                "DLC504",
                node,
                f"{name}() inside shard_map body {fn.name}() with no "
                "psum/pmean anywhere in the body: arrays here are LOCAL "
                "shards, so this reduces one shard and silently treats "
                "it as the global value; follow the reduction with "
                "lax.psum/pmean over the mesh axis",
            )


register(
    Rule(
        id="DLC504",
        name="shard-map-partial-reduction",
        doc="reductions in shard_map bodies need a named collective",
        check=_check_shard_map_reduction,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)

# --- DLC505: donated buffer read after the donating call ---------------------
# donate_argnums hands the input buffer to XLA: after the call the
# Python name still exists but its buffer is deleted — touching it
# raises at best, and at worst (when dispatch is still in flight) reads
# freed device memory on some backends.  The repo idiom rebinds the name
# through the call (``state, _ = step(state, ...)``); the rule flags the
# other shape: a donated argument read again below the call without
# rebinding.


def _donated_positions(tree: ast.Module) -> dict[str, set[int]]:
    """Callable name -> positional indices its jit donates (same-file)."""
    from deeplearning_cfn_tpu.analysis.sharding import _is_jit_expr

    out: dict[str, set[int]] = {}

    def positions(call: ast.Call) -> set[int]:
        kw = keyword(call, "donate_argnums")
        nums: set[int] = set()
        if kw is not None:
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and type(n.value) is int:
                    nums.add(n.value)
        return nums

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                if isinstance(d, ast.Call) and _is_jit_expr(d):
                    nums = positions(d)
                    if nums:
                        out.setdefault(node.name, set()).update(nums)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            value = node.value
            if (
                isinstance(target, ast.Name)
                and isinstance(value, ast.Call)
                and _is_jit_expr(value.func)
            ):
                nums = positions(value)
                if nums:
                    out.setdefault(target.id, set()).update(nums)
    return out


def _assigned_names(stmt: ast.stmt) -> set[str]:
    out: set[str] = set()
    targets: list[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.With):
        targets = [
            item.optional_vars
            for item in stmt.items
            if item.optional_vars is not None
        ]
    for t in targets:
        for n in ast.walk(t):
            name = dotted_name(n)
            if name is not None:
                out.add(name)
    return out


def _statement_chain(ctx: FileContext, node: ast.AST, scope: _FnDef):
    """The statement of ``scope.body`` (or a nested body list) holding
    ``node``, plus that body list — where "after the call" is defined."""
    cur = node
    parent = ctx.parents.get(cur)
    while parent is not None and parent is not scope:
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None, None  # different scope
        cur = parent
        parent = ctx.parents.get(cur)
    if parent is None:
        return None, None
    body = scope.body
    if cur in body:
        return cur, body
    return None, None


def _check_donated_read_after_call(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    donors = _donated_positions(tree)
    if not donors:
        return
    for fn in _scopes(tree):
        for node in walk_skipping_nested_functions(fn.body):
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            terminal = (cname or "").rsplit(".", 1)[-1]
            if terminal not in donors:
                continue
            stmt, body = _statement_chain(ctx, node, fn)
            if stmt is None or body is None:
                continue
            rebound = _assigned_names(stmt)
            for pos in sorted(donors[terminal]):
                if pos >= len(node.args):
                    continue
                donated = dotted_name(node.args[pos])
                if donated is None or donated in rebound:
                    continue
                for later in body[body.index(stmt) + 1 :]:
                    if donated in _assigned_names(later):
                        break
                    read = next(
                        (
                            n
                            for n in ast.walk(later)
                            if isinstance(n, (ast.Name, ast.Attribute))
                            and isinstance(
                                getattr(n, "ctx", ast.Load()), ast.Load
                            )
                            and dotted_name(n) == donated
                        ),
                        None,
                    )
                    if read is not None:
                        yield ctx.violation(
                            "DLC505",
                            read,
                            f"{donated!r} is read after {terminal}() donated "
                            f"it (donate_argnums position {pos}): the "
                            "buffer is deleted the moment the compiled "
                            "program consumes it, so this read races "
                            "dispatch at best and raises at worst; rebind "
                            "the name through the call "
                            "(`x, ... = f(x, ...)`)",
                        )
                        break


register(
    Rule(
        id="DLC505",
        name="donated-read-after-call",
        doc="donated arguments must not be read after the donating call",
        check=_check_donated_read_after_call,
        applies=_applies_comms_paths,
        gate=GATE,
    )
)
