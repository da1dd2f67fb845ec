"""Shared AST heuristics: what blocks, and what is a lock.

One vocabulary for both the blocking-under-lock checker and the one-hop
call-graph table, so "blocking" means the same thing at depth 0 and
depth 1.  Everything here is a lexical heuristic tuned to THIS repo's
naming conventions (documented in README "Static analysis"); pragmas are
the escape hatch, not special cases in the matcher.
"""

from __future__ import annotations

import ast

# device-dispatch entry points: one of these inside a lock body means a
# jit compile or an XLA execution can serialize every other lock waiter
# behind the device (the PR 8 / PR 12 bug class)
DEVICE_CALLS = {
    "verify_batch",
    "host_verify_batch",
    "block_until_ready",
    "device_put",
    "dryrun_multichip",
}

# receivers that name a condition variable: .wait() on these RELEASES the
# lock (that is the point of a condvar) and is exempt; .wait() on
# anything else (Event, Ticket, Future) keeps the lock held while parked
_CONDITION_HINTS = ("cv", "cond", "wake", "idle", "empty", "full", "nonempty")

# with-item names that denote a lock / mutex guard
_LOCK_NAME_HINTS = ("lock", "mutex")


def _terminal_name(node: ast.AST) -> str:
    """x -> "x"; a.b.c -> "c"; f(...) -> f's terminal name; else ""."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Call):
        return _terminal_name(node.func)
    return ""


def lock_guard_name(node: ast.AST) -> str:
    """What a with-item's lock goes by: ``x.locked_for(who)`` (LockCtx: the
    lock, its wait timed as a span) is a guard on ``x``."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and node.func.attr == "locked_for":
        return _terminal_name(node.func.value)
    return _terminal_name(node)


def is_lock_expr(node: ast.AST) -> bool:
    """Does this with-item expression look like a lock guard?"""
    name = lock_guard_name(node).lower()
    if not name:
        return False
    if isinstance(node, ast.Call) and name in ("lockctx", "ranked_lock"):
        return True
    if any(h in name for h in _LOCK_NAME_HINTS):
        return True
    # bare mutex names: _mu / mu / commit_mu ... and condvar guards (a
    # `with self._cv:` holds the underlying lock exactly like `with mu:`)
    stripped = name.strip("_")
    if stripped == "mu" or name.endswith("_mu") or name.endswith("mu"):
        return True
    return any(stripped == h or name.endswith("_" + h) for h in ("cv", "cond"))


def _is_condition_receiver(node: ast.AST) -> bool:
    name = _terminal_name(node).lower()
    return any(h in name for h in _CONDITION_HINTS)


def _numeric_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and isinstance(node.value, (int, float))


def blocking_reason(call: ast.Call) -> str | None:
    """Why this call blocks, or None.  The single source of truth for the
    blocking-under-lock bug class."""
    fn = call.func
    name = _terminal_name(fn)
    if not name:
        return None
    # time.sleep / bare sleep
    if name == "sleep":
        return "time.sleep blocks every waiter on the held lock"
    # Future / DispatchHandle result
    if name == "result" and isinstance(fn, ast.Attribute):
        return ".result() parks on a device/worker future"
    # synchronous verify dispatch (the historical pipeline.virtual bug)
    if name == "dispatch" and isinstance(fn, ast.Attribute):
        return ".dispatch() runs a device round-trip synchronously"
    if name in DEVICE_CALLS:
        return f"{name}() enters the device runtime (jit compile / XLA dispatch)"
    # socket reads
    if name in ("recv", "recvfrom", "recv_into", "accept") and isinstance(fn, ast.Attribute):
        return f".{name}() blocks on the network"
    # thread joins: obj.join() / obj.join(timeout).  str.join(iterable) and
    # os.path.join(...) take non-numeric arguments and are skipped.
    if name == "join" and isinstance(fn, ast.Attribute):
        if isinstance(fn.value, ast.Constant):
            return None  # ", ".join(...)
        if _terminal_name(fn.value) in ("path", "posixpath", "ntpath"):
            return None  # os.path.join
        args_ok = not call.args or (len(call.args) == 1 and _numeric_const(call.args[0]))
        kw_ok = all(k.arg == "timeout" for k in call.keywords)
        if args_ok and kw_ok:
            return ".join() waits for a thread"
        return None
    # parked waits that do NOT release the lock (Event/Ticket/Future.wait);
    # condvar waits are exempt by receiver-name convention
    if name in ("wait", "wait_for") and isinstance(fn, ast.Attribute):
        if _is_condition_receiver(fn.value):
            return None
        return f".{name}() parks the thread without releasing the lock"
    return None


def direct_blocking_calls(fn_node: ast.AST) -> list[tuple[int, str]]:
    """(line, reason) for every blocking call lexically inside this
    function body (nested defs excluded — they run later, elsewhere)."""
    out: list[tuple[int, str]] = []
    for node in _walk_shallow(fn_node):
        if isinstance(node, ast.Call):
            reason = blocking_reason(node)
            if reason is not None:
                out.append((node.lineno, reason))
    return out


def _walk_shallow(root: ast.AST):
    """ast.walk, but do not descend into nested function/class defs."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def called_names(body_nodes) -> list[tuple[int, str]]:
    """(line, bare name) of every call in the given statement list, again
    without descending into nested defs."""
    out = []
    for stmt in body_nodes:
        for node in [stmt, *_walk_shallow(stmt)]:
            if isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if name:
                    out.append((node.lineno, name))
    return out
