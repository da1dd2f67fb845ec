"""The device-work ledger: what the program's own counters and spans say
about who answered the jobs of a window.  The logic is ``chip_smoke.py``'s
``DeviceLedger`` / ``device_work_failures`` / ``_tally_jax_events`` (proved on
the chip in PR 22), copied so that later PRs cannot move the yardstick; the
smoke's timings are not copied.
"""

from __future__ import annotations

# JAX's own compile events, tallied process-wide from the first ledger on
# (jax.monitoring has no public unregister: one listener pair, registered once)
_JAX_EVENTS = {"hits": 0, "misses": 0, "backend_compiles": []}


def tally_jax_events() -> None:
    if "listening" in _JAX_EVENTS:
        return
    import jax.monitoring as monitoring

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            _JAX_EVENTS["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _JAX_EVENTS["misses"] += 1

    def on_duration(name, secs, **_kw):
        # a persistent-cache hit never reaches the backend compiler
        if name == "/jax/core/compile/backend_compile_duration":
            _JAX_EVENTS["backend_compiles"].append(secs)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _JAX_EVENTS["listening"] = True


def compile_tally() -> dict:
    return {
        "hits": _JAX_EVENTS["hits"], "misses": _JAX_EVENTS["misses"],
        "backend_compiles": len(_JAX_EVENTS["backend_compiles"]),
        "backend_compile_seconds": sum(_JAX_EVENTS["backend_compiles"]),
    }


def counters() -> dict:
    """Every counter of the program's registry: {name: number} for a plain
    counter, {name: {label: number}} for a family.  Taken whole, so that a
    metric over a counter a later PR adds needs no edit here."""
    from kaspa_tpu.observability.core import REGISTRY

    return {name: dict(v) if isinstance(v, dict) else v for name, v in REGISTRY.snapshot()["counters"].items()}


def delta(after: dict, before: dict) -> dict:
    """Counter movement between two ``counters()`` readings; what did not
    move is left out."""
    out = {}
    for name, a in after.items():
        if isinstance(a, dict):
            b = before.get(name, {})
            moved = {k: v - b.get(k, 0) for k, v in a.items() if v - b.get(k, 0)}
        else:
            moved = a - before.get(name, 0)
        if moved:
            out[name] = moved
    return out


def compile_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def breaker_state() -> str:
    from kaspa_tpu.resilience.breaker import device_breaker

    return device_breaker().state


def device_work_checks(d: dict, breaker: str) -> dict:
    """Numbers that are all 0 when the device answered every job queued in
    the window: {name: value}.  A run in which the host lane answered has
    timed the wrong thing."""
    def family(name):
        return sum(d.get(name, {}).values())

    queued = family("txscript_batch_jobs")
    return {
        "degraded_jobs": d.get("secp_degraded_jobs", 0) + d.get("secp_degraded_dispatches", 0),
        "watchdog_timeouts": family("secp_watchdog_timeouts"),
        "breaker_trips": family("breaker_trips") + (0 if breaker == "closed" else 1),
        "jobs_not_answered_by_device": abs(queued - d.get("secp_device_jobs", 0)),
        "no_signature_job_queued": 0 if queued else 1,
    }
