"""Process-wide JAX configuration for the framework.

Call ``setup()`` once from every entry point (tests, bench, node, tools).
Enables the persistent XLA compilation cache so the big crypto ladders
compile once per checkout rather than once per process.

Where the cache lives is decided from outside: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and this module
sets no directory in code; otherwise the cache goes to the one fixed,
git-ignored ``.jax_cache/`` at the root of the checkout (the path is part
of a cache entry's key, so it must never move between runs).

``KASPA_TPU_HOST_DEVICES=N`` splits the host CPU backend into N XLA
devices (the ergonomic spelling of
``XLA_FLAGS=--xla_force_host_platform_device_count=N``): it lets
``--mesh auto`` / ``--mesh N`` / ``--mesh RxC`` find N devices on a
CPU-only box without the caller hand-assembling XLA_FLAGS.  It must be
seen before the first ``import jax`` in the process, so every entry
point calls ``setup()`` at module import time, ahead of any jax-touching
import.  An explicit device-count flag already present in XLA_FLAGS
wins — the knob never overrides a deliberate setting.
"""

from __future__ import annotations

import os

_DONE = False


_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def cache_dir() -> str:
    """The persistent compilation-cache directory (no jax import — the
    warm-kernel manifest lives next to the XLA cache entries)."""
    return os.environ.get(_CACHE_ENV) or _CHECKOUT_CACHE


def _apply_host_devices() -> None:
    """Fold KASPA_TPU_HOST_DEVICES=N into XLA_FLAGS (pre-`import jax`)."""
    knob = os.environ.get("KASPA_TPU_HOST_DEVICES", "").strip()
    if not knob:
        return
    try:
        n = int(knob)
    except ValueError:
        raise SystemExit(f"KASPA_TPU_HOST_DEVICES must be an integer, got {knob!r}")
    if n < 1:
        raise SystemExit(f"KASPA_TPU_HOST_DEVICES must be >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return  # an explicit XLA_FLAGS setting wins over the knob
    os.environ["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n}").strip()


def setup() -> None:
    global _DONE
    if _DONE:
        return
    _DONE = True
    _apply_host_devices()
    import jax

    if not os.environ.get(_CACHE_ENV):
        os.makedirs(_CHECKOUT_CACHE, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
