"""The DLC4xx JAX/SPMD trace-safety rules (gated: ``dlcfn lint --sharding``).

Bench sat at ~0.30 MFU for three rounds with the multi-step path losing
to single-step, and none of DLC0xx/1xx/2xx/3xx could say why: the
classic step-loop killers — silent retraces, missing buffer donation,
host syncs inside the loop, impure traced code — live in the *JAX
dispatch layer*, invisible to lockset or protocol checks.  DLC4xx makes
that layer statically checkable, the way DLC2xx did for threads:

DLC400 traced-code impurity     DLC403 mesh-axis consistency
DLC401 undonated train-state jit DLC404 host sync in the step loop
DLC402 retrace hazards           DLC405 nested jit / device_put in trace

Like every gated pass the rules are conservative: each matcher anchors
on the specific shape of the bug.  The static half is paired with a
dynamic compile-audit sentinel (analysis/compile_audit.py) that runs the
real trainer and *proves* steady-state zero-retrace; its findings use
the reserved ids DLC410/DLC411 so both halves share one baseline
ratchet.

Scope: the compute tree (``train/``, ``models/``, ``ops/``, ``bench.py``)
— the only places jit/pjit/shard_map call sites live.

What "traced" means here
------------------------
A function is considered traced when the file shows it entering the JAX
tracer: jit/pjit/pmap-decorated, passed by name to a jit wrapper or to a
tracing transform (``lax.scan``/``while_loop``/``fori_loop``/``cond``,
``vmap``/``grad``/``checkpoint``/``shard_map``), nested inside a traced
function, or called by bare name from one.  This is a same-file closure
— deliberately: cross-module call graphs would need whole-program
resolution and the false-positive risk that comes with it.

DLC403's ground truth is cross-module, like the DLC1xx broker checker:
the canonical axis vocabulary is machine-read from ``AXIS_ORDER`` in
``parallel/mesh.py`` (itself validated against ``ClusterContract``
topology at mesh build time), so a spec axis that drifts from the
cluster contract fails lint, not a 3am pod run.
"""

from __future__ import annotations

import ast
from functools import lru_cache
from pathlib import Path
from typing import Iterator

from deeplearning_cfn_tpu.analysis.core import (
    FileContext,
    Rule,
    Violation,
    call_name,
    dotted_name,
    keyword,
    has_keyword,
    register,
    walk_skipping_nested_functions,
)

GATE = "sharding"
RULE_IDS = ("DLC400", "DLC401", "DLC402", "DLC403", "DLC404", "DLC405")

# Reserved for the dynamic compile-audit sentinel (analysis/compile_audit.py):
# same namespace, same baseline ratchet, but findings come from running the
# real trainer rather than from this AST pass.
AUDIT_RULE_RETRACE = "DLC410"
AUDIT_RULE_DONATION = "DLC411"
AUDIT_RULE_IDS = (AUDIT_RULE_RETRACE, AUDIT_RULE_DONATION)

_COMPUTE_DIRS = ("train", "models", "ops", "serve")


def _applies_compute_paths(path: Path) -> bool:
    return path.name == "bench.py" or any(d in path.parts for d in _COMPUTE_DIRS)


# --- traced-function discovery ---------------------------------------------

# Names that wrap a callable into a compiled function.  pmap counts for
# traced-ness even though the repo idiom is jit+shardings.
_JIT_WRAPPERS = (
    "jax.jit",
    "jit",
    "pjit",
    "pjit.pjit",
    "jax.experimental.pjit.pjit",
    "jax.pmap",
    "pmap",
)
# Core jit spellings for rules about the jit call itself (DLC401/402/405).
_JIT_CORE = ("jax.jit", "jit", "pjit", "pjit.pjit", "jax.experimental.pjit.pjit")

# transform dotted name -> positional indices holding traced callables.
_TRACED_CALLABLE_POSITIONS: dict[str, tuple[int, ...]] = {
    "jax.lax.scan": (0,),
    "lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "lax.cond": (1, 2),
    "jax.vmap": (0,),
    "vmap": (0,),
    "jax.grad": (0,),
    "grad": (0,),
    "jax.value_and_grad": (0,),
    "value_and_grad": (0,),
    "jax.checkpoint": (0,),
    "checkpoint": (0,),
    "jax.remat": (0,),
    "remat": (0,),
    "shard_map": (0,),
    "jax.shard_map": (0,),
    "jax.experimental.shard_map.shard_map": (0,),
}


def _is_jit_expr(expr: ast.AST) -> bool:
    name = dotted_name(expr)
    if name in _JIT_WRAPPERS:
        return True
    if isinstance(expr, ast.Call):
        fname = call_name(expr)
        if fname in _JIT_WRAPPERS:
            return True  # decorator factory form: @jax.jit(...)
        if fname in ("partial", "functools.partial") and expr.args:
            return _is_jit_expr(expr.args[0])
    return False


def _jit_decorated(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    return any(_is_jit_expr(d) for d in fn.decorator_list)


_FnDef = ast.FunctionDef | ast.AsyncFunctionDef


def _defs_by_name(tree: ast.Module) -> dict[str, list[_FnDef]]:
    out: dict[str, list[_FnDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def traced_functions(ctx: FileContext) -> dict[_FnDef, str]:
    """Every function def the file shows entering the tracer -> why.

    Cached on the FileContext so the six rules share one computation.
    """
    cached = getattr(ctx, "_dlc4_traced", None)
    if cached is not None:
        return cached
    defs = _defs_by_name(ctx.tree)
    traced: dict[_FnDef, str] = {}
    stack: list[_FnDef] = []

    def mark(fn: _FnDef, why: str) -> None:
        if fn not in traced:
            traced[fn] = why
            stack.append(fn)

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if _jit_decorated(node):
                mark(node, "jit-decorated")
        elif isinstance(node, ast.Call):
            name = call_name(node)
            if name in _JIT_WRAPPERS:
                positions: tuple[int, ...] = (0,)
            else:
                positions = _TRACED_CALLABLE_POSITIONS.get(name or "", ())
            for pos in positions:
                if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                    for fn in defs.get(node.args[pos].id, ()):
                        mark(fn, f"passed to {name}")

    # Transitive closure: nested defs and same-file bare-name calls from
    # traced code run under the same trace.
    while stack:
        fn = stack.pop()
        for node in ast.walk(fn):
            if (
                node is not fn
                and isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            ):
                mark(node, f"nested in traced {fn.name}")
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                for callee in defs.get(node.func.id, ()):
                    mark(callee, f"called from traced {fn.name}")

    ctx._dlc4_traced = traced  # type: ignore[attr-defined]
    return traced


# --- DLC400: traced-code impurity ------------------------------------------
# Host-side effects inside traced code do not "run every step" — they run
# ONCE, at trace time, and their results are baked into the compiled
# program as constants.  A wall-clock read becomes a frozen timestamp, an
# np.random draw becomes the same "random" numbers every step, and a
# `global` write silently never happens again.  All three have the same
# deadly property: the code *looks* like it works.

_WALL_CLOCK_CALLS = (
    "time.time",
    "time.time_ns",
    "time.perf_counter",
    "time.perf_counter_ns",
    "time.monotonic",
    "time.monotonic_ns",
    "time.process_time",
    "datetime.now",
    "datetime.utcnow",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
)
_HOST_RANDOM_PREFIXES = ("np.random.", "numpy.random.", "random.")


def _check_traced_impurity(tree: ast.Module, ctx: FileContext) -> Iterator[Violation]:
    for fn, why in traced_functions(ctx).items():
        for node in walk_skipping_nested_functions(fn.body):
            if isinstance(node, ast.Global):
                names = ", ".join(node.names)
                yield ctx.violation(
                    "DLC400",
                    node,
                    f"`global {names}` inside traced {fn.name}() ({why}): "
                    "the write happens once at trace time and silently "
                    "never again; thread values through arguments/returns",
                )
                continue
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name is None:
                continue
            if name in _WALL_CLOCK_CALLS:
                yield ctx.violation(
                    "DLC400",
                    node,
                    f"{name}() inside traced {fn.name}() ({why}) is baked "
                    "in as a trace-time constant — every compiled step "
                    "replays the same timestamp; measure host-side around "
                    "the dispatch",
                )
            elif any(name.startswith(p) for p in _HOST_RANDOM_PREFIXES):
                yield ctx.violation(
                    "DLC400",
                    node,
                    f"{name}() inside traced {fn.name}() ({why}) freezes "
                    "host randomness into the compiled program (identical "
                    "draws every step); thread a jax.random key instead",
                )


register(
    Rule(
        id="DLC400",
        name="traced-impurity",
        doc="no wall-clock/np.random/global-write inside traced functions",
        check=_check_traced_impurity,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)

# --- DLC401: train-state jit without donation ------------------------------
# DLC008 (ungated) catches the two exact trainer shapes it was written
# for: a jit-DECORATED fn whose first arg is literally named `state`, and
# the call form carrying both in_shardings and out_shardings.  DLC401
# widens to what slips past it: call-form `jax.jit(step_fn)` where
# `step_fn`'s def (resolved same-file) has a train-state-typed first
# parameter — by name (`state`/`train_state`) or by annotation ending in
# `State` — without donate_argnums/donate_argnames.  Eval-style sites are
# exempt by name: a read-only jit must NOT donate (it would delete the
# caller's state).

_DONATE_KWARGS = ("donate_argnums", "donate_argnames")
_STATE_PARAM_NAMES = ("state", "train_state")
_EVAL_NAME_MARKERS = ("eval", "infer", "predict")


def _annotation_is_state(arg: ast.arg) -> bool:
    ann = arg.annotation
    if ann is None:
        return False
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        text = ann.value
    else:
        text = dotted_name(ann) or ""
    return text.rsplit(".", 1)[-1].endswith("State")


def _state_typed_first_param(fn: _FnDef) -> ast.arg | None:
    args = fn.args.args
    if args and args[0].arg == "self":
        args = args[1:]
    if not args:
        return None
    first = args[0]
    if first.arg in _STATE_PARAM_NAMES or _annotation_is_state(first):
        return first
    return None


def _eval_like(name: str) -> bool:
    low = name.lower()
    return any(marker in low for marker in _EVAL_NAME_MARKERS)


def _check_undonated_state_jit(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            first = _state_typed_first_param(node)
            if first is None or _eval_like(node.name) or not _jit_decorated(node):
                continue
            if any(
                isinstance(d, ast.Call) and _is_jit_expr(d) and has_keyword(d, *_DONATE_KWARGS)
                for d in node.decorator_list
            ):
                continue
            if first.arg == "state":
                continue  # exact DLC008 decorator shape — one finding, not two
            yield ctx.violation(
                "DLC401",
                node,
                f"jit-decorated {node.name}() threads a train-state first "
                f"arg ({first.arg!r}) without donate_argnums: both state "
                "copies stay live across the update; donate the input "
                "state (read-only eval jits are exempt by name)",
            )
        elif isinstance(node, ast.Call) and call_name(node) in _JIT_CORE:
            if not node.args or not isinstance(node.args[0], ast.Name):
                continue
            if has_keyword(node, *_DONATE_KWARGS):
                continue
            if has_keyword(node, "in_shardings") and has_keyword(node, "out_shardings"):
                continue  # exact DLC008 call shape — one finding, not two
            fname = node.args[0].id
            if _eval_like(fname):
                continue
            enclosing = ctx.enclosing_function(node)
            if enclosing is not None and _eval_like(enclosing.name):
                continue
            for fn in _defs_by_name(tree).get(fname, ()):
                first = _state_typed_first_param(fn)
                if first is not None and not _eval_like(fn.name):
                    yield ctx.violation(
                        "DLC401",
                        node,
                        f"jax.jit({fname}) threads a train-state first arg "
                        f"({first.arg!r}) without donate_argnums: both "
                        "state copies stay live across the update; donate "
                        "the input state (read-only eval jits are exempt "
                        "by name)",
                    )
                    break


register(
    Rule(
        id="DLC401",
        name="undonated-train-state-jit",
        doc="train-state-typed jits must donate (eval sites exempt)",
        check=_check_undonated_state_jit,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)

# --- DLC402: retrace hazards ------------------------------------------------
# jit keys its cache on the *Python value* of non-array arguments: a bool
# flag retraces on every flip, an int used in `if`/`range` retraces per
# distinct value — silently, per call, which is exactly the failure mode
# behind "multi-step loses to single-step".  The fix is one kwarg
# (static_argnums/static_argnames), so the rule insists on it.  It also
# flags branching on an f-string under trace: the string formats static
# shape info at trace time, so the branch is frozen forever.


def _jit_sites(tree: ast.Module) -> Iterator[tuple[_FnDef, ast.Call | None]]:
    """(function def, jit call carrying its kwargs) for every jit root."""
    defs = _defs_by_name(tree)
    seen: set[_FnDef] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                if _is_jit_expr(d):
                    if node not in seen:
                        seen.add(node)
                        yield node, d if isinstance(d, ast.Call) else None
                    break
        elif isinstance(node, ast.Call) and call_name(node) in _JIT_CORE:
            if node.args and isinstance(node.args[0], ast.Name):
                for fn in defs.get(node.args[0].id, ()):
                    if fn not in seen:
                        seen.add(fn)
                        yield fn, node


def _static_decls(call: ast.Call | None) -> tuple[set[str], set[int]]:
    names: set[str] = set()
    nums: set[int] = set()
    if call is None:
        return names, nums
    kw = keyword(call, "static_argnames")
    if kw is not None:
        for n in ast.walk(kw.value):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                names.add(n.value)
    kw = keyword(call, "static_argnums")
    if kw is not None:
        for n in ast.walk(kw.value):
            if isinstance(n, ast.Constant) and isinstance(n.value, int):
                nums.add(n.value)
    return names, nums


def _defaults_by_arg(fn: _FnDef) -> dict[str, ast.expr]:
    out: dict[str, ast.expr] = {}
    pos = fn.args.args
    for arg, default in zip(pos[len(pos) - len(fn.args.defaults) :], fn.args.defaults):
        out[arg.arg] = default
    for arg, default in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if default is not None:
            out[arg.arg] = default
    return out


def _annotation_terminal(arg: ast.arg) -> str | None:
    ann = arg.annotation
    if ann is None:
        return None
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return ann.value.rsplit(".", 1)[-1]
    name = dotted_name(ann)
    return name.rsplit(".", 1)[-1] if name else None


def _used_in_python_control(fn: _FnDef, pname: str) -> bool:
    def names_param(sub: ast.AST) -> bool:
        return any(
            isinstance(n, ast.Name) and n.id == pname for n in ast.walk(sub)
        )

    for node in walk_skipping_nested_functions(fn.body):
        if isinstance(node, (ast.If, ast.While)) and names_param(node.test):
            return True
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "range"
            and any(names_param(a) for a in node.args)
        ):
            return True
    return False


def _check_retrace_hazards(tree: ast.Module, ctx: FileContext) -> Iterator[Violation]:
    for fn, call in _jit_sites(tree):
        static_names, static_nums = _static_decls(call)
        defaults = _defaults_by_arg(fn)
        args = fn.args.args
        if args and args[0].arg == "self":
            args = args[1:]
        for idx, arg in enumerate(args):
            if arg.arg in static_names or idx in static_nums:
                continue
            ann = _annotation_terminal(arg)
            default = defaults.get(arg.arg)
            is_bool = ann == "bool" or (
                isinstance(default, ast.Constant) and isinstance(default.value, bool)
            )
            is_int = not is_bool and (
                ann == "int"
                or (
                    isinstance(default, ast.Constant)
                    and type(default.value) is int
                )
            )
            if is_bool:
                yield ctx.violation(
                    "DLC402",
                    arg,
                    f"{fn.name}() parameter {arg.arg!r} is a Python bool "
                    "entering jit without static_argnums/static_argnames: "
                    "every flag flip retraces silently; declare it static",
                )
            elif is_int and _used_in_python_control(fn, arg.arg):
                yield ctx.violation(
                    "DLC402",
                    arg,
                    f"{fn.name}() parameter {arg.arg!r} is a Python int "
                    "driving `if`/`range` under trace without "
                    "static_argnums: each distinct value retraces "
                    "silently; declare it static (or lax-ify the loop)",
                )
    for fn, why in traced_functions(ctx).items():
        for node in walk_skipping_nested_functions(fn.body):
            if isinstance(node, ast.If) and any(
                isinstance(n, ast.JoinedStr) for n in ast.walk(node.test)
            ):
                yield ctx.violation(
                    "DLC402",
                    node,
                    f"if-test built from an f-string inside traced "
                    f"{fn.name}() ({why}): the string formats static "
                    "shape info at trace time, so the branch is frozen "
                    "into the compiled program; branch on the "
                    "values/shapes directly",
                )


register(
    Rule(
        id="DLC402",
        name="retrace-hazard",
        doc="python scalars/bools entering jit must be declared static",
        check=_check_retrace_hazards,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)

# --- DLC403: mesh-axis consistency ------------------------------------------
# A PartitionSpec axis name is a stringly-typed foreign key into the mesh
# topology.  A typo ('fspd', 'data') does not error — jit treats the
# unknown axis as unsharded and the layout silently degrades to
# replication.  The canonical vocabulary is machine-read from AXIS_ORDER
# in parallel/mesh.py (validated against ClusterContract topology at mesh
# build), so this check is cross-module ground truth, not a hardcoded
# list in the linter.

_MESH_PY = Path(__file__).resolve().parents[1] / "parallel" / "mesh.py"
_AXIS_KWARGS = ("axis_name", "axis_names")


@lru_cache(maxsize=8)
def canonical_mesh_axes(mesh_py: str | None = None) -> tuple[str, ...]:
    """Extract AXIS_ORDER from parallel/mesh.py by AST, not import."""
    path = Path(mesh_py) if mesh_py is not None else _MESH_PY
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        for target in targets:
            if isinstance(target, ast.Name) and target.id == "AXIS_ORDER":
                value = node.value
                if isinstance(value, (ast.Tuple, ast.List)):
                    axes = tuple(
                        e.value
                        for e in value.elts
                        if isinstance(e, ast.Constant) and isinstance(e.value, str)
                    )
                    if axes:
                        return axes
    raise ValueError(f"could not extract AXIS_ORDER from {path}")


def _spec_call(name: str | None) -> bool:
    if name is None:
        return False
    terminal = name.rsplit(".", 1)[-1]
    return terminal in ("P", "PartitionSpec")


def _str_constants(node: ast.AST) -> Iterator[ast.Constant]:
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n


def _check_mesh_axis_consistency(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    try:
        canonical = set(canonical_mesh_axes())
    except (OSError, ValueError, SyntaxError) as e:
        yield ctx.violation(
            "DLC403",
            tree,
            f"cannot machine-read AXIS_ORDER from parallel/mesh.py ({e}); "
            "the mesh-axis vocabulary must stay statically extractable",
        )
        return
    shown = "/".join(sorted(canonical))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        sources: list[ast.AST] = []
        if _spec_call(call_name(node)):
            sources.extend(node.args)
        sources.extend(
            kw.value for kw in node.keywords if kw.arg in _AXIS_KWARGS
        )
        for source in sources:
            for const in _str_constants(source):
                if const.value not in canonical:
                    yield ctx.violation(
                        "DLC403",
                        const,
                        f"axis {const.value!r} does not resolve against "
                        f"the mesh topology axes ({shown}) machine-read "
                        "from parallel/mesh.py AXIS_ORDER: an unknown "
                        "axis silently degrades the layout to replication",
                    )


register(
    Rule(
        id="DLC403",
        name="mesh-axis-consistency",
        doc="PartitionSpec/shard_map axis names must exist in AXIS_ORDER",
        check=_check_mesh_axis_consistency,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)

# --- DLC404: host sync in the step loop -------------------------------------
# DLC003 guards the inside of jitted functions; this rule guards the HOST
# side: the loop that dispatches steps.  An unguarded .item()/float()/
# device_get/block_until_ready in the loop body serializes host and
# device every iteration — the async dispatch queue drains, MFU caps at
# whatever the host round-trip allows.  The repo idiom (train/trainer.py
# fit(), bench.py) is to batch readbacks behind a periodic `if` (sync
# boundary), so anything under an `if` inside the loop is deliberately
# exempt.

_SYNC_CALL_NAMES = (
    "jax.device_get",
    "device_get",
    "jax.block_until_ready",
    "block_until_ready",
)


def _is_step_loop(loop: ast.For | ast.While, ctx: FileContext) -> bool:
    fn = ctx.enclosing_function(loop)
    if fn is not None and fn.name == "fit":
        return True
    for node in walk_skipping_nested_functions(loop.body):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name is not None and "step" in name.rsplit(".", 1)[-1].lower():
                return True
    return False


def _guarded_or_rescoped(node: ast.AST, loop: ast.AST, ctx: FileContext) -> bool:
    cur = ctx.parents.get(node)
    while cur is not None and cur is not loop:
        if isinstance(cur, ast.If):
            return True  # periodic sync boundary — the sanctioned idiom
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return True  # different scope; not executed per iteration here
        cur = ctx.parents.get(cur)
    return False


def _sync_shape(node: ast.Call) -> str | None:
    name = call_name(node)
    if name in _SYNC_CALL_NAMES:
        return f"{name}()"
    if (
        isinstance(node.func, ast.Attribute)
        and node.func.attr == "item"
        and not node.args
    ):
        return ".item()"
    if (
        isinstance(node.func, ast.Name)
        and node.func.id == "float"
        and len(node.args) == 1
        and not isinstance(node.args[0], ast.Constant)
    ):
        return "float(<device value>)"
    return None


def _check_step_loop_host_sync(
    tree: ast.Module, ctx: FileContext
) -> Iterator[Violation]:
    reported: set[int] = set()
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        if not _is_step_loop(loop, ctx):
            continue
        for stmt in loop.body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call) or id(node) in reported:
                    continue
                what = _sync_shape(node)
                if what is None or _guarded_or_rescoped(node, loop, ctx):
                    continue
                reported.add(id(node))
                yield ctx.violation(
                    "DLC404",
                    node,
                    f"{what} runs unguarded on every iteration of a step "
                    "loop: it drains the async dispatch queue and "
                    "serializes host with device; batch readbacks behind "
                    "a periodic `if` sync boundary (fit()'s sync_every "
                    "idiom)",
                )


register(
    Rule(
        id="DLC404",
        name="step-loop-host-sync",
        doc="no unguarded host sync inside the step-dispatch loop",
        check=_check_step_loop_host_sync,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)

# --- DLC405: nested jit / device_put under trace ----------------------------
# jit inside jit does not compose the way it reads: the inner wrapper
# re-traces on every outer trace and fragments the compilation cache
# (each outer variant compiles its own inner copy).  device_put under
# trace is a no-op at best (placement is the sharding system's job) and a
# host round-trip at worst.  Both are hoist-one-line fixes.

_DEVICE_PUT_CALLS = (
    "jax.device_put",
    "device_put",
    "device_put_tree",
    "jax.device_put_replicated",
    "jax.device_put_sharded",
)


def _check_nested_dispatch(tree: ast.Module, ctx: FileContext) -> Iterator[Violation]:
    for fn, why in traced_functions(ctx).items():
        for node in walk_skipping_nested_functions(fn.body):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and _jit_decorated(node)
            ):
                yield ctx.violation(
                    "DLC405",
                    node,
                    f"jit-decorated {node.name}() defined inside traced "
                    f"{fn.name}() ({why}): the inner jit re-traces per "
                    "outer trace and fragments the compilation cache; "
                    "hoist the wrapper out of the traced scope",
                )
                continue
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name in _JIT_CORE:
                yield ctx.violation(
                    "DLC405",
                    node,
                    f"{name}() called inside traced {fn.name}() ({why}): "
                    "nested jit re-traces per outer trace and fragments "
                    "the compilation cache; hoist the wrapper to "
                    "module/init scope",
                )
            elif name in _DEVICE_PUT_CALLS:
                yield ctx.violation(
                    "DLC405",
                    node,
                    f"{name}() inside traced {fn.name}() ({why}) is a "
                    "no-op at best under trace (placement belongs to "
                    "shardings) and a host round-trip at worst; place "
                    "inputs before dispatch",
                )


register(
    Rule(
        id="DLC405",
        name="nested-dispatch-under-trace",
        doc="no jit()/device_put() inside already-traced code",
        check=_check_nested_dispatch,
        applies=_applies_compute_paths,
        gate=GATE,
    )
)
