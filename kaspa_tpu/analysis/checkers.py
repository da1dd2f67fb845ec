"""The graftlint per-file checkers (see package docstring for the catalog).

Each checker is registered under its id and returns findings for ONE
file; anything project-wide (the fixpoint call graph, the fault-point
catalog, the metric-name census) is computed once and cached on the
Project.  Checkers never import the modules they analyze — everything is
AST-only, so linting a file with a seeded deadlock cannot hang the lint.
(The two project-level checkers that DO import runtime modules — the
kernel-shape audit and the env-knob catalog — live in shapes.py and
envknobs.py and run once per project, the former only when gated on.)
"""

from __future__ import annotations

import ast
import re

from kaspa_tpu.analysis.blocking import (
    _terminal_name,
    _walk_shallow,
    blocking_reason,
    is_lock_expr,
    lock_guard_name,
)
from kaspa_tpu.analysis.callgraph import NO_EXPAND, CallSite, render_chain
from kaspa_tpu.analysis.core import Finding, Project, SourceFile, register_checker

# ----------------------------------------------------------------------
# 1. blocking-under-lock (fixpoint transitive expansion)
# ----------------------------------------------------------------------


def walk_with_context(tree: ast.AST):
    """Yield (node, enclosing_class_name, enclosing_function_ast) for every
    node — the resolution context the call graph needs at a use site."""
    stack = [(tree, "", None)]
    while stack:
        node, cls, fn = stack.pop()
        yield node, cls, fn
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                stack.append((child, child.name, fn))
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.append((child, cls, child))
            else:
                stack.append((child, cls, fn))


@register_checker(
    "blocking-under-lock",
    "device dispatch / Future.result / sleep / socket recv / thread join "
    "inside a `with <lock>` body, at ANY call depth (whole-program "
    "fixpoint expansion through the module-qualified call graph)",
)
def check_blocking_under_lock(project: Project, f: SourceFile) -> list[Finding]:
    out: list[Finding] = []
    graph = project.callgraph
    for node, cls, _fn in walk_with_context(f.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        lock_names = [
            lock_guard_name(item.context_expr)
            for item in node.items
            if is_lock_expr(item.context_expr)
        ]
        if not lock_names:
            continue
        held = "/".join(lock_names)
        for inner in _body_calls(node):
            reason = blocking_reason(inner)
            name = _terminal_name(inner.func)
            if reason is not None:
                out.append(
                    Finding(
                        f.rel, inner.lineno, "blocking-under-lock",
                        f"{name}() while holding {held}: {reason}",
                    )
                )
                continue
            # transitive expansion: resolve the callee through the
            # module-qualified call graph; its fixpoint may-block fact
            # carries the full chain down to the primitive blocking call
            if name in NO_EXPAND or name.startswith("__"):
                continue
            site = _site_for(inner)
            target = graph.resolve_site(site, f.rel, cls)
            if target is not None and target.block_chain:
                out.append(
                    Finding(
                        f.rel, inner.lineno, "blocking-under-lock",
                        f"{name}() while holding {held} blocks transitively "
                        f"(depth {len(target.block_chain)}): "
                        f"{render_chain(target.block_chain)}",
                    )
                )
    return out


def _site_for(call: ast.Call) -> CallSite:
    name = _terminal_name(call.func)
    if isinstance(call.func, ast.Attribute):
        return CallSite(call.lineno, name, _terminal_name(call.func.value), True)
    return CallSite(call.lineno, name, "", False)


def _body_calls(with_node):
    """Call nodes lexically inside the with body (nested defs excluded)."""
    for stmt in with_node.body:
        for n in [stmt, *_walk_shallow(stmt)]:
            if isinstance(n, ast.Call):
                yield n


# ----------------------------------------------------------------------
# 2. raw-lock
# ----------------------------------------------------------------------


@register_checker(
    "raw-lock",
    "threading.Lock()/RLock()/bare Condition() construction outside "
    "utils/sync.py — use a ranked LockCtx (utils.sync.RANKS)",
)
def check_raw_lock(project: Project, f: SourceFile) -> list[Finding]:
    if f.rel.endswith("utils/sync.py"):
        return []  # the one module allowed to touch the primitives
    out = []
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and _terminal_name(fn.value) == "threading"):
            continue
        if fn.attr in ("Lock", "RLock"):
            out.append(
                Finding(
                    f.rel, node.lineno, "raw-lock",
                    f"raw threading.{fn.attr}() — construct a ranked LockCtx "
                    "(utils/sync.py) so the inversion detector covers this lock",
                )
            )
        elif fn.attr == "Condition" and not node.args:
            out.append(
                Finding(
                    f.rel, node.lineno, "raw-lock",
                    "bare threading.Condition() hides an unranked lock — build "
                    "it from a LockCtx via .condition()",
                )
            )
    return out


# ----------------------------------------------------------------------
# 3. tracer-hazard
# ----------------------------------------------------------------------

UNROLL_THRESHOLD = 64  # the PR 11 compile cliff: XLA:CPU goes superlinear


def _module_dict_names(tree: ast.Module) -> dict[str, int]:
    out: dict[str, int] = {}
    for stmt in tree.body:
        targets, value = [], None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        if value is None:
            continue
        is_dict = isinstance(value, (ast.Dict, ast.DictComp)) or (
            isinstance(value, ast.Call) and _terminal_name(value.func) == "dict"
        )
        if not is_dict:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out[t.id] = stmt.lineno
    return out


def _decorator_names(fn_node) -> list[str]:
    names = []
    for dec in fn_node.decorator_list:
        names.append(_terminal_name(dec))
        if isinstance(dec, ast.Call):
            for a in dec.args:  # partial(jax.jit, ...)
                names.append(_terminal_name(a))
    return [n for n in names if n]


def _jitted_functions(tree: ast.Module):
    """FunctionDef nodes whose bodies run under a JAX trace: decorated
    with jit/partial(jit) or passed by name to jit()/shard_map()."""
    defs: dict[str, list] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)
    jitted: dict[int, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            decs = _decorator_names(node)
            if "jit" in decs or "shard_map" in decs:
                jitted[id(node)] = node
        elif isinstance(node, ast.Call) and _terminal_name(node.func) in ("jit", "shard_map"):
            if node.args and isinstance(node.args[0], ast.Name):
                for fn in defs.get(node.args[0].id, []):
                    jitted[id(fn)] = fn
    return list(jitted.values())


def _lru_cached_names(tree: ast.Module) -> set[str]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            if any(n in ("lru_cache", "cache") for n in _decorator_names(node)):
                out.add(node.name)
    return out


def _range_trip_count(call: ast.Call) -> int | None:
    if _terminal_name(call.func) != "range":
        return None
    vals = []
    for a in call.args:
        if isinstance(a, ast.Constant) and isinstance(a.value, int):
            vals.append(a.value)
        else:
            return None
    if len(vals) == 1:
        return vals[0]
    if len(vals) >= 2:
        step = vals[2] if len(vals) == 3 and vals[2] else 1
        return max(0, (vals[1] - vals[0]) // step)
    return None


@register_checker(
    "tracer-hazard",
    "module caches / lru_cache / host coercions / unrolled constant loops "
    "inside jit-traced function bodies (RewriteTracer poisoning, compile cliffs)",
)
def check_tracer_hazard(project: Project, f: SourceFile) -> list[Finding]:
    tree = f.tree
    if not isinstance(tree, ast.Module):
        return []
    dict_names = _module_dict_names(tree)
    lru_names = _lru_cached_names(tree)
    out: list[Finding] = []
    for fn in _jitted_functions(tree):
        local_args = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        for node in _walk_shallow(fn):
            if isinstance(node, ast.Name) and node.id in dict_names and node.id not in local_args:
                out.append(
                    Finding(
                        f.rel, node.lineno, "tracer-hazard",
                        f"jitted `{fn.name}` touches module-level dict `{node.id}` "
                        f"(defined line {dict_names[node.id]}): a trace can memoize "
                        "RewriteTracers into it, poisoning later calls",
                    )
                )
            elif isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if name in lru_names:
                    out.append(
                        Finding(
                            f.rel, node.lineno, "tracer-hazard",
                            f"jitted `{fn.name}` calls lru_cache'd `{name}`: tracer "
                            "arguments poison the cache across traces",
                        )
                    )
                elif (
                    name in ("int", "float", "bool")
                    and isinstance(node.func, ast.Name)
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)
                ):
                    out.append(
                        Finding(
                            f.rel, node.lineno, "tracer-hazard",
                            f"jitted `{fn.name}` coerces with {name}(): concretizes "
                            "a tracer (ConcretizationTypeError at best, silently "
                            "frozen constant at worst)",
                        )
                    )
                elif isinstance(node.func, ast.Attribute) and _root_name(node.func) in ("np", "numpy"):
                    out.append(
                        Finding(
                            f.rel, node.lineno, "tracer-hazard",
                            f"jitted `{fn.name}` calls {_root_name(node.func)}.{node.func.attr}: "
                            "numpy executes on host at trace time, not on device",
                        )
                    )
            elif isinstance(node, ast.For) and isinstance(node.iter, ast.Call):
                trips = _range_trip_count(node.iter)
                if trips is not None and trips >= UNROLL_THRESHOLD:
                    out.append(
                        Finding(
                            f.rel, node.lineno, "tracer-hazard",
                            f"jitted `{fn.name}` unrolls a {trips}-iteration Python "
                            f"loop (threshold {UNROLL_THRESHOLD}): XLA:CPU compile "
                            "time goes superlinear — use lax.scan/fori_loop",
                        )
                    )
    return out


def _root_name(attr: ast.Attribute) -> str:
    node: ast.AST = attr
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


# ----------------------------------------------------------------------
# 4. trace-ctx-handoff
# ----------------------------------------------------------------------

_INSTRUMENTED = ("pipeline/", "ingest/", "serving/", "fabric/", "ops/dispatch.py")
_HANDOFF_METHODS = ("put", "put_nowait", "send")


def _mentions_ctx(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and "ctx" in n.id.lower():
            return True
        if isinstance(n, ast.Attribute) and "ctx" in n.attr.lower():
            return True
        if isinstance(n, ast.Call) and _terminal_name(n.func) == "context":
            return True
    return False


@register_checker(
    "trace-ctx-handoff",
    "queue .put/.send in instrumented subsystems must carry the "
    "flight-recorder trace context (the PR 7 connected-span-tree invariant)",
)
def check_trace_ctx_handoff(project: Project, f: SourceFile) -> list[Finding]:
    if not any(part in f.rel for part in _INSTRUMENTED):
        return []
    out = []
    for node in ast.walk(f.tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
            continue
        if node.func.attr not in _HANDOFF_METHODS or not node.args:
            continue
        payload = node.args[0]
        # only literal containers are checkable: packing fields into a
        # tuple/dict and forgetting the ctx is exactly the regression shape
        # that broke span-tree connectivity; an object payload is assumed
        # to carry its ctx as an attribute (Task.ctx, Notification.ctx)
        if not isinstance(payload, (ast.Tuple, ast.List, ast.Dict)):
            continue
        if _mentions_ctx(node):
            continue
        out.append(
            Finding(
                f.rel, node.lineno, "trace-ctx-handoff",
                f".{node.func.attr}() hands a literal payload across a queue "
                "boundary without a trace ctx: the consumer's spans detach "
                "from the block's tree (include the TraceContext in the payload)",
            )
        )
    return out


# ----------------------------------------------------------------------
# 5. registry-hygiene
# ----------------------------------------------------------------------

_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_METRIC_METHODS = ("counter", "counter_family", "histogram", "histogram_family", "gauge", "gauge_family")


def _hygiene_census(project: Project) -> dict:
    """Project-wide pass, computed once: fault points used vs declared,
    metric registrations by name."""
    cache = getattr(project, "_hygiene", None)
    if cache is not None:
        return cache
    used_points: dict[str, list[tuple[str, int]]] = {}
    metrics: dict[str, list[tuple[str, int]]] = {}
    collectors: dict[str, list[tuple[str, int]]] = {}
    declared: dict[str, int] = {}
    catalog_file = None
    for f in project.files:
        if f.rel.endswith("resilience/faults.py"):
            catalog_file = f.rel
            for node in ast.walk(f.tree):
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, ast.AnnAssign):
                    targets, value = [node.target], node.value
                else:
                    continue
                if (
                    any(isinstance(t, ast.Name) and t.id == "FAULT_POINTS" for t in targets)
                    and isinstance(value, ast.Dict)
                ):
                    for k in value.keys:
                        if isinstance(k, ast.Constant) and isinstance(k.value, str):
                            declared[k.value] = k.lineno
        for node in ast.walk(f.tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                continue
            recv = _terminal_name(node.func.value)
            if node.func.attr == "fire" and recv == "FAULTS":
                if node.args and isinstance(node.args[0], ast.Constant) and isinstance(node.args[0].value, str):
                    used_points.setdefault(node.args[0].value, []).append((f.rel, node.lineno))
            elif recv == "REGISTRY" and node.func.attr in _METRIC_METHODS:
                if node.args and isinstance(node.args[0], ast.Constant) and isinstance(node.args[0].value, str):
                    metrics.setdefault(node.args[0].value, []).append((f.rel, node.lineno))
            elif recv == "REGISTRY" and node.func.attr == "register_collector":
                if node.args and isinstance(node.args[0], ast.Constant) and isinstance(node.args[0].value, str):
                    collectors.setdefault(node.args[0].value, []).append((f.rel, node.lineno))
    project._hygiene = {
        "used": used_points,
        "declared": declared,
        "catalog_file": catalog_file,
        "metrics": metrics,
        "collectors": collectors,
    }
    return project._hygiene


@register_checker(
    "registry-hygiene",
    "fault points used in code must appear in the resilience/faults.py "
    "FAULT_POINTS catalog and vice versa; metric names follow the "
    "snake_case convention and are registered exactly once",
)
def check_registry_hygiene(project: Project, f: SourceFile) -> list[Finding]:
    census = _hygiene_census(project)
    out: list[Finding] = []
    # fault-point checks only when the catalog module is in the lint set
    if census["catalog_file"] is not None:
        declared, used = census["declared"], census["used"]
        if f.rel == census["catalog_file"]:
            if not declared:
                out.append(
                    Finding(
                        f.rel, 1, "registry-hygiene",
                        "resilience/faults.py declares no FAULT_POINTS catalog "
                        "(dict literal of point name -> description)",
                    )
                )
            for point, line in declared.items():
                if point not in used:
                    out.append(
                        Finding(
                            f.rel, line, "registry-hygiene",
                            f"fault point {point!r} is cataloged but no FAULTS.fire "
                            "site uses it: delete the dead point",
                        )
                    )
        for point, sites in used.items():
            if point in declared:
                continue
            for rel, line in sites:
                if rel == f.rel:
                    out.append(
                        Finding(
                            f.rel, line, "registry-hygiene",
                            f"fault point {point!r} fired here is missing from the "
                            "FAULT_POINTS catalog in resilience/faults.py",
                        )
                    )
    # metric naming + duplicate registration
    for kind in ("metrics", "collectors"):
        for name, sites in census[kind].items():
            canonical = min(sites)
            for rel, line in sites:
                if rel != f.rel:
                    continue
                if not _METRIC_NAME_RE.match(name):
                    out.append(
                        Finding(
                            f.rel, line, "registry-hygiene",
                            f"metric name {name!r} violates the snake_case "
                            "convention ^[a-z][a-z0-9_]*$",
                        )
                    )
                if len(sites) > 1 and (rel, line) != canonical:
                    out.append(
                        Finding(
                            f.rel, line, "registry-hygiene",
                            f"duplicate registration of {name!r} (first at "
                            f"{canonical[0]}:{canonical[1]}): one name, one series",
                        )
                    )
    return out


# ----------------------------------------------------------------------
# 6. unbounded-queue
# ----------------------------------------------------------------------

_QUEUE_CTORS = ("Queue", "LifoQueue", "PriorityQueue")


def _is_unbounded_arg(node: ast.AST | None) -> bool:
    """A bound argument that is literally 0/None is no bound at all."""
    if node is None:
        return True
    return isinstance(node, ast.Constant) and node.value in (0, None)


@register_checker(
    "unbounded-queue",
    "deque()/queue.Queue() constructed without an explicit bound outside "
    "utils/ — every buffer in the node must state its overflow policy "
    "(maxlen/maxsize, a capacity check at the producer, or a justified pragma)",
)
def check_unbounded_queue(project: Project, f: SourceFile) -> list[Finding]:
    if f.rel.startswith("utils/") or "/utils/" in f.rel:
        return []  # primitives layer: sync.py's waiter deque etc. are leaf internals
    out: list[Finding] = []
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal_name(node.func)
        if name == "deque":
            # deque(iterable, maxlen) — bounded iff maxlen is present and real
            maxlen = node.args[1] if len(node.args) >= 2 else None
            if maxlen is None:
                for kw in node.keywords:
                    if kw.arg == "maxlen":
                        maxlen = kw.value
            if _is_unbounded_arg(maxlen):
                out.append(
                    Finding(
                        f.rel, node.lineno, "unbounded-queue",
                        "deque() without maxlen: under sustained overload this "
                        "buffer grows until the process dies — bound it, enforce "
                        "a capacity check at the producer, or pragma with the "
                        "reason it cannot overflow",
                    )
                )
        elif name in _QUEUE_CTORS:
            maxsize = node.args[0] if node.args else None
            if maxsize is None:
                for kw in node.keywords:
                    if kw.arg == "maxsize":
                        maxsize = kw.value
            if _is_unbounded_arg(maxsize):
                out.append(
                    Finding(
                        f.rel, node.lineno, "unbounded-queue",
                        f"{name}() without maxsize: an unbounded handoff queue "
                        "turns overload into memory exhaustion — give it a "
                        "maxsize and an overflow policy, or pragma with the "
                        "reason the producer is naturally bounded",
                    )
                )
        elif name == "SimpleQueue":
            out.append(
                Finding(
                    f.rel, node.lineno, "unbounded-queue",
                    "SimpleQueue() has no bound at all — use Queue(maxsize=...) "
                    "with an overflow policy, or pragma with the reason the "
                    "producer is naturally bounded",
                )
            )
    return out
