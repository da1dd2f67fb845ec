"""graftlint tests: the checkers on seeded fixtures, pragma semantics,
the v2 whole-program fixpoint engine (transitive chains, recursion,
cross-module dispatch), lifecycle/exception-path/env-knob protocols, the
CI ratchet, and the full-repo self-run.

Fixtures are written to tmp_path and linted with run_project — the lint
is AST-only, so fixture code is never imported or executed (a fixture may
freely reference names that don't resolve).
"""

from __future__ import annotations

import json
import os
import textwrap

import kaspa_tpu.analysis.checkers  # noqa: F401 - registers the checkers
from kaspa_tpu.analysis import run_project
from kaspa_tpu.analysis.__main__ import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, files: dict[str, str]) -> dict:
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return run_project([str(tmp_path)], root=str(tmp_path))


def _ids(report: dict) -> set[str]:
    return {f["checker"] for f in report["findings"]}


# --- blocking-under-lock --------------------------------------------------


def test_blocking_under_lock_direct(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import time

        def bad(self):
            with self._lock:
                time.sleep(0.1)
                self.fut.result()

        def fine(self):
            with self._lock:
                x = 1
            self.fut.result()
    """})
    lines = [(f["line"], f["checker"]) for f in report["findings"]]
    assert (6, "blocking-under-lock") in lines  # sleep under lock
    assert (7, "blocking-under-lock") in lines  # .result() under lock
    assert not any(line > 9 for line, _ in lines)
    assert report["ok"] is False


def test_blocking_under_lock_names_the_lock_behind_locked_for(tmp_path):
    # `with x.locked_for(who):` (LockCtx: the lock, its wait timed as a span)
    # is a guard on x; a guard on something that is no lock stays none
    report = _lint(tmp_path, {"mod.py": """
        def bad(self):
            with self.node.lock.locked_for("block"):
                self.fut.result()
            with self._dispatch_lock.locked_for("getInfo"):
                self.fut.result()

        def fine(self):
            with self.spans.locked_for("block"):
                self.fut.result()
    """})
    found = {f["line"]: f["message"] for f in report["findings"] if f["checker"] == "blocking-under-lock"}
    assert sorted(found) == [4, 6]
    assert "while holding lock:" in found[4] and "while holding _dispatch_lock:" in found[6]


def test_blocking_under_lock_condvar_wait_exempt(tmp_path):
    # a condition-variable wait RELEASES the lock — exempt by receiver
    # naming convention; an Event.wait parks while still holding it
    report = _lint(tmp_path, {"mod.py": """
        def ok(self):
            with self._mu:
                self._cv.wait(0.5)

        def bad(self):
            with self._mu:
                self._event.wait(0.5)
    """})
    lines = [f["line"] for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert lines == [8]


def test_blocking_under_lock_one_hop_expansion(tmp_path):
    report = _lint(tmp_path, {"a.py": """
        import time

        def helper():
            time.sleep(1.0)

        def caller(self):
            with self._lock:
                helper()
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert len(msgs) == 1 and msgs[0]["line"] == 9
    assert "blocks transitively" in msgs[0]["message"]
    assert "a.py:5" in msgs[0]["message"]


def test_one_hop_skips_ambiguous_names(tmp_path):
    # two project-wide definitions of the same bare name: not expanded
    report = _lint(tmp_path, {
        "a.py": """
            import time

            def helper():
                time.sleep(1.0)
        """,
        "b.py": """
            def helper():
                return 1

            def caller(self):
                with self._lock:
                    helper()
        """,
    })
    assert not [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]


# --- raw-lock -------------------------------------------------------------


def test_raw_lock_flags_constructions(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import threading

        a = threading.Lock()
        b = threading.RLock()
        c = threading.Condition()
        d = threading.Condition(a)
        e = threading.Event()
    """})
    lines = sorted(f["line"] for f in report["findings"] if f["checker"] == "raw-lock")
    assert lines == [4, 5, 6]  # bound Condition(a) and Event are fine


def test_raw_lock_exempts_sync_module(tmp_path):
    report = _lint(tmp_path, {"utils/sync.py": """
        import threading

        a = threading.Lock()
    """})
    assert not report["findings"]


# --- tracer-hazard --------------------------------------------------------


def test_tracer_hazard_in_jit_bodies(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import functools
        import jax
        import numpy as np

        _CACHE = {}

        @functools.lru_cache(maxsize=None)
        def cached_helper(x):
            return x

        @jax.jit
        def traced(x):
            _CACHE[1] = x
            y = int(x)
            z = np.add(x, x)
            w = cached_helper(x)
            for i in range(100):
                y = y + i
            return y + z + w
    """})
    msgs = [f["message"] for f in report["findings"] if f["checker"] == "tracer-hazard"]
    assert any("module-level dict" in m for m in msgs)
    assert any("coerces with int()" in m for m in msgs)
    assert any("np.add" in m for m in msgs)
    assert any("lru_cache'd" in m for m in msgs)
    assert any("100-iteration" in m for m in msgs)


def test_tracer_hazard_ignores_host_code_and_factories(tmp_path):
    # the mesh.py idiom: an lru_cache'd FACTORY that builds a jit callable
    # is consulted outside the trace; hazards only count inside jit bodies
    report = _lint(tmp_path, {"mod.py": """
        import functools
        import jax
        import numpy as np

        _CACHE = {}

        @functools.lru_cache(maxsize=None)
        def kernel_factory(n):
            def inner(x):
                return x + n
            return jax.jit(inner)

        def host_only(x):
            _CACHE[1] = int(x)
            return np.add(x, x)
    """})
    hits = [f for f in report["findings"] if f["checker"] == "tracer-hazard"]
    assert not hits


def test_tracer_hazard_catches_shard_map_reference(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import numpy as np
        from jax.experimental.shard_map import shard_map

        def kernel(x):
            return np.square(x)

        sharded = shard_map(kernel, mesh=None, in_specs=None, out_specs=None)
    """})
    hits = [f for f in report["findings"] if f["checker"] == "tracer-hazard"]
    assert len(hits) == 1 and "np.square" in hits[0]["message"]


# --- trace-ctx-handoff ----------------------------------------------------


def test_trace_ctx_handoff(tmp_path):
    report = _lint(tmp_path, {
        "pipeline/stage.py": """
            def bad(self, q, item):
                q.put((item, 1))

            def good(self, q, item, ctx):
                q.put((item, ctx))

            def object_payload(self, q, task):
                q.put(task)
        """,
        "other/stage.py": """
            def uninstrumented(self, q, item):
                q.put((item, 1))
        """,
    })
    hits = [(f["path"], f["line"]) for f in report["findings"] if f["checker"] == "trace-ctx-handoff"]
    assert hits == [("pipeline/stage.py", 3)]


# --- registry-hygiene -----------------------------------------------------


def test_registry_hygiene_fault_points_both_directions(tmp_path):
    report = _lint(tmp_path, {
        "resilience/faults.py": """
            FAULT_POINTS = {
                "a.live": "used below",
                "b.dead": "nothing fires this",
            }
        """,
        "mod.py": """
            from resilience.faults import FAULTS

            def f():
                FAULTS.fire("a.live")
                FAULTS.fire("c.uncataloged")
        """,
    })
    msgs = [f["message"] for f in report["findings"] if f["checker"] == "registry-hygiene"]
    assert any("'b.dead'" in m and "dead point" in m for m in msgs)
    assert any("'c.uncataloged'" in m and "missing from" in m for m in msgs)
    assert not any("'a.live'" in m for m in msgs)


def test_registry_hygiene_metric_names(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        from observability.core import REGISTRY

        _A = REGISTRY.counter("good_name", help="x")
        _B = REGISTRY.counter("Bad-Name", help="x")
        _C = REGISTRY.histogram("good_name", (1, 2), help="dup of _A")
    """})
    msgs = [f["message"] for f in report["findings"] if f["checker"] == "registry-hygiene"]
    assert any("'Bad-Name'" in m and "convention" in m for m in msgs)
    assert any("duplicate registration of 'good_name'" in m for m in msgs)


# --- unbounded-queue ------------------------------------------------------


def test_unbounded_queue_flags_missing_bounds(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import queue
        from collections import deque

        a = deque()
        b = deque([], None)
        c = deque([], 32)
        d = deque(maxlen=8)
        e = queue.Queue()
        f = queue.Queue(0)
        g = queue.Queue(maxsize=128)
        h = queue.SimpleQueue()
    """})
    lines = sorted(f["line"] for f in report["findings"] if f["checker"] == "unbounded-queue")
    # deque()/deque([], None), Queue()/Queue(0), SimpleQueue(); the bounded
    # constructions on lines 7, 8, 11 are the stated overflow policy
    assert lines == [5, 6, 9, 10, 12]


def test_unbounded_queue_exempts_utils_layer(tmp_path):
    # the primitives layer (utils/sync.py waiter deques etc.) owns its
    # buffers as leaf internals — the policy applies to subsystem queues
    report = _lint(tmp_path, {"utils/sync.py": """
        from collections import deque

        waiters = deque()
    """})
    assert not [f for f in report["findings"] if f["checker"] == "unbounded-queue"]


def test_unbounded_queue_pragma_suppression(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import queue

        q = queue.SimpleQueue()  # graftlint: allow(unbounded-queue) -- drained same-call, bounded by caller batch
    """})
    assert report["ok"] is True
    assert not report["findings"]
    assert [s["checker"] for s in report["suppressed"]] == ["unbounded-queue"]


# --- pragmas --------------------------------------------------------------


def test_pragma_suppresses_with_justification(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import threading

        a = threading.Lock()  # graftlint: allow(raw-lock) -- fixture leaf lock
    """})
    assert report["ok"] is True
    assert not report["findings"]
    assert len(report["suppressed"]) == 1
    assert report["suppressed"][0]["justification"] == "fixture leaf lock"


def test_pragma_on_preceding_comment_line(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import threading

        # graftlint: allow(raw-lock) -- covers the next line
        a = threading.Lock()
    """})
    assert report["ok"] is True and len(report["suppressed"]) == 1


def test_pragma_without_justification_is_an_error(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import threading

        a = threading.Lock()  # graftlint: allow(raw-lock)
    """})
    assert report["ok"] is False
    checkers = {f["checker"] for f in report["findings"]}
    # the raw-lock finding stays active AND the naked pragma is flagged
    assert checkers == {"raw-lock", "pragma"}


def test_pragma_only_matching_checker(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import time

        def f(self):
            with self._lock:
                time.sleep(1)  # graftlint: allow(raw-lock) -- wrong id, must not suppress
    """})
    assert any(f["checker"] == "blocking-under-lock" for f in report["findings"])


# --- CLI + self-run -------------------------------------------------------


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "seeded"
    bad.mkdir()
    (bad / "mod.py").write_text("import threading\nx = threading.Lock()\n")
    out = tmp_path / "LINT.json"
    rc = lint_main([str(bad), "--root", str(tmp_path), "--json", str(out), "-q"])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["ok"] is False and doc["counts"] == {"raw-lock": 1}

    good = tmp_path / "clean"
    good.mkdir()
    (good / "mod.py").write_text("x = 1\n")
    assert lint_main([str(good), "--root", str(tmp_path), "-q"]) == 0


def test_full_repo_self_run_is_clean():
    """The acceptance gate: the repo lints clean, and every suppression
    carries a justification."""
    report = run_project([os.path.join(REPO, "kaspa_tpu")], root=REPO)
    assert report["findings"] == [], [f["path"] + ":" + str(f["line"]) for f in report["findings"]]
    assert report["ok"] is True
    assert all(s["justification"] for s in report["suppressed"])
    # the migration actually happened: suppressions are the documented
    # exceptions, not the hot subsystems
    hot = [s for s in report["suppressed"]
           if s["checker"] == "raw-lock" and any(
               part in s["path"] for part in ("pipeline/", "ingest/", "serving/", "ops/dispatch"))]
    assert hot == []


# --- v2 engine: transitive chains, recursion, cross-module dispatch -------


def test_transitive_chain_depth_three(tmp_path):
    """A depth-3 chain the v1 one-hop expansion could not see."""
    report = _lint(tmp_path, {"mod.py": """
        import time

        def leaf():
            time.sleep(1.0)

        def mid():
            leaf()

        def top():
            mid()

        def caller(self):
            with self._lock:
                top()
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert len(msgs) == 1 and msgs[0]["line"] == 15
    assert "depth 3" in msgs[0]["message"]
    # the rendered chain names every hop down to the primitive sleep
    assert "mid" in msgs[0]["message"] and "leaf" in msgs[0]["message"]


def test_transitive_chain_across_modules(tmp_path):
    report = _lint(tmp_path, {
        "dev.py": """
            import time

            def wait_device():
                time.sleep(1.0)
        """,
        "svc.py": """
            from dev import wait_device

            def run():
                wait_device()

            def caller(self):
                with self._lock:
                    run()
        """,
    })
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert [f["line"] for f in msgs] == [9]
    assert "dev.py" in msgs[0]["message"]


def test_recursion_cycle_terminates_and_propagates(tmp_path):
    # self-recursion must not hang the fixpoint; the blocking fact still
    # propagates out of the cycle
    report = _lint(tmp_path, {"mod.py": """
        import time

        def walk(n):
            if n:
                walk(n - 1)
            time.sleep(0.1)

        def caller(self):
            with self._lock:
                walk(3)
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert [f["line"] for f in msgs] == [11]


def test_mutual_recursion_terminates(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        import time

        def ping(n):
            if n:
                pong(n - 1)

        def pong(n):
            time.sleep(0.1)
            ping(n)

        def caller(self):
            with self._lock:
                ping(2)
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert [f["line"] for f in msgs] == [14]


def test_cross_module_method_dispatch_by_receiver_name(tmp_path):
    # self.engine.submit() resolves to Engine.submit by the receiver-name
    # heuristic even though the class lives in another module
    report = _lint(tmp_path, {
        "engine.py": """
            import time

            class Engine:
                def submit(self, job):
                    time.sleep(1.0)
        """,
        "node.py": """
            def caller(self):
                with self._lock:
                    self.engine.submit(None)
        """,
    })
    msgs = [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert [f["line"] for f in msgs] == [4]
    assert "engine.py" in msgs[0]["message"]


def test_pragma_covers_decorated_multiline_statement(tmp_path):
    # the pragma sits on the decorator line; the offending call is three
    # lines into the statement span
    report = _lint(tmp_path, {"mod.py": """
        import time

        def caller(self):
            with self._lock:
                # graftlint: allow(blocking-under-lock) -- fixture: spans cover the whole statement
                x = time.sleep(
                    1.0,
                )
        return x
    """})
    assert not [f for f in report["findings"] if f["checker"] == "blocking-under-lock"]
    assert any(s["checker"] == "blocking-under-lock" for s in report["suppressed"])


# --- exception-path -------------------------------------------------------


def test_exception_path_leaks_lock_on_raise(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        def risky():
            raise ValueError("boom")

        def bad(self):
            self._mu.acquire()
            risky()
            self._mu.release()

        def good(self):
            self._mu.acquire()
            try:
                risky()
            finally:
                self._mu.release()
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "exception-path"]
    assert [f["line"] for f in msgs] == [6]


# --- resource-lifecycle ---------------------------------------------------


def test_lifecycle_ticket_dropped_on_early_return(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        def bad(self, job):
            t = self.pool.submit(job)
            if self.closed:
                return None
            t.resolve(1)
            return t
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "resource-lifecycle"]
    assert len(msgs) == 1
    assert "t" in msgs[0]["message"]


def test_lifecycle_double_resolve(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        def bad(self, job):
            t = self.pool.submit(job)
            t.resolve(1)
            t.resolve(2)
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "resource-lifecycle"]
    assert len(msgs) == 1 and msgs[0]["line"] == 5


def test_lifecycle_clean_paths_are_clean(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        def both_branches(self, job):
            t = self.pool.submit(job)
            if self.ok:
                t.resolve(1)
            else:
                t.cancel()

        def raise_exit_needs_no_resolution(self, job):
            t = self.pool.submit(job)
            if self.closed:
                raise RuntimeError("shutting down")
            t.resolve(1)

        def escapes_to_caller(self, job):
            t = self.pool.submit(job)
            return t

        def consumer_side(self, t):
            t.wait(1.0)
            t.raise_for_status()
    """})
    assert not [f for f in report["findings"] if f["checker"] == "resource-lifecycle"]


def test_lifecycle_span_and_suppress_must_be_context_managers(tmp_path):
    report = _lint(tmp_path, {"mod.py": """
        from kaspa_tpu.observability import trace
        from kaspa_tpu.resilience import faults

        def bad(self):
            trace.span("validate")
            faults.suppress()

        def good(self):
            with trace.span("validate"):
                with faults.suppress():
                    pass
    """})
    msgs = [f for f in report["findings"] if f["checker"] == "resource-lifecycle"]
    assert [f["line"] for f in msgs] == [6, 7]


# --- env-knob -------------------------------------------------------------


def test_env_knob_reconciles_both_directions(tmp_path):
    (tmp_path / "KNOBS.md").write_text(
        "| Knob | Default | Owner | Doc |\n"
        "|------|---------|-------|-----|\n"
        "| `KASPA_TPU_ALPHA` | `'1'` | `mod.py` | documented knob |\n"
        "| `KASPA_TPU_GONE` | `'9'` | `mod.py` | reads nothing anymore |\n"
        "| `KASPA_TPU_BARE` | `'2'` | `mod.py` |  |\n"
    )
    report = _lint(tmp_path, {"mod.py": """
        import os

        A = os.environ.get("KASPA_TPU_ALPHA", "1")
        B = os.environ.get("KASPA_TPU_MISSING", "0")
        C = os.environ.get("KASPA_TPU_ALPHA", "7")
        D = os.environ.get("KASPA_TPU_BARE", "2")
    """})
    msgs = sorted(
        (f["path"], f["line"], f["message"]) for f in report["findings"] if f["checker"] == "env-knob"
    )
    texts = [m[2] for m in msgs]
    assert any("KASPA_TPU_MISSING" in t and "missing from KNOBS.md" in t for t in texts)
    assert any("KASPA_TPU_GONE" in t and "no longer read" in t for t in texts)
    assert any("KASPA_TPU_ALPHA" in t and "'7'" in t for t in texts)
    assert any("KASPA_TPU_BARE" in t and "Doc" in t for t in texts)


def test_knobs_md_regen_preserves_docs(tmp_path):
    from kaspa_tpu.analysis.core import Project, collect_files
    from kaspa_tpu.analysis.envknobs import render_knobs_md, scan_knob_sites

    (tmp_path / "mod.py").write_text(
        'import os\nX = os.environ.get("KASPA_TPU_ALPHA", "1")\n'
    )
    files = collect_files([str(tmp_path)], str(tmp_path))
    census = scan_knob_sites(Project(str(tmp_path), files))
    first = render_knobs_md(census, None)
    edited = first.replace(
        "| `KASPA_TPU_ALPHA` | `'1'` | `mod.py` |  |",
        "| `KASPA_TPU_ALPHA` | `'1'` | `mod.py` | hand-written doc |",
    )
    assert "hand-written doc" in edited
    again = render_knobs_md(census, edited)
    assert "hand-written doc" in again


# --- kernel catalog -------------------------------------------------------


def test_kernel_catalog_enumeration():
    from kaspa_tpu.ops import kernel_catalog as cat

    rows = cat.enumerate_signatures()
    fams = {r["family"] for r in rows}
    assert fams == {"ladder", "ecdsa", "muhash"}
    for r in rows:
        assert r["bucket"] % r["mesh"] == 0
        assert r["shard"] >= 8
        assert cat.covered(r["family"], r["bucket"]), r
    assert all(r["mesh"] == 1 for r in rows if r["family"] == "muhash")
    # every coverage rule is live
    reach = {(r["family"], r["bucket"]) for r in rows}
    for fam, lo, hi in cat.WARM_COVERAGE:
        assert any(f == fam and lo <= b <= hi for f, b in reach), (fam, lo, hi)


# --- ratchet --------------------------------------------------------------


def test_ratchet_blocks_growth_allows_shrink():
    from kaspa_tpu.analysis.__main__ import check_ratchet

    base = {"suppressed": [{}] * 3, "counts": {"raw-lock": 1}}
    same = {"suppressed": [{}] * 3, "counts": {"raw-lock": 1}}
    assert check_ratchet(base, same) == []
    shrunk = {"suppressed": [{}] * 2, "counts": {"raw-lock": 0}}
    assert check_ratchet(base, shrunk) == []
    more_supp = {"suppressed": [{}] * 4, "counts": {}}
    assert any("suppression count grew" in f for f in check_ratchet(base, more_supp))
    more_findings = {"suppressed": [{}] * 3, "counts": {"raw-lock": 2}}
    assert any("raw-lock" in f for f in check_ratchet(base, more_findings))
    new_checker = {"suppressed": [], "counts": {"env-knob": 1}}
    assert any("env-knob" in f for f in check_ratchet(base, new_checker))
    assert check_ratchet(None, same) != []
