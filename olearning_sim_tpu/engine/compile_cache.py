"""Persistent XLA compilation-cache plumbing + hit/miss telemetry.

The engine compiles a *grid* of round-program variants — (deadline, attack,
defense-structure) x algorithm x model family — and every process start
(a chip-tool call, a supervisor relaunch after a crash) would otherwise pay
full XLA compilation for each variant again. :func:`enable_compile_cache`
turns on jax's persistent compilation cache so a second process compiling
an already-cached variant deserializes the executable instead.

Where the cache lives — one rule, one name:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax already has the directory from
  its own environment handling; this module sets **no** directory in code
  (only the two thresholds and the telemetry listener). Whoever launches
  the process (the chip tool, a deployment) places the cache.
- unset: the fixed ``artifacts/xla_compile_cache`` inside the checkout
  (git-ignored). Never a temporary, pid- or time-derived path: the path is
  part of the cache key, so a directory that moves never hits.

Cache keying is jax's own: a hash of the optimized HLO module, compile
options, device topology, and jax/XLA versions — so a changed model shape,
mesh, defense structure, or library upgrade misses cleanly and never
collides. The directory can be deleted at any time at the cost of
re-compiling (docs/performance.md#compile-cache).

Observability: jax emits ``/jax/compilation_cache/cache_hits`` (persistent
entry deserialized) and ``/jax/compilation_cache/cache_misses`` (entry
compiled and written) monitoring events; a process-wide listener mirrors
them into the cataloged ``ols_engine_compile_cache_hits_total`` /
``ols_engine_compile_cache_misses_total`` counters.

``OLS_COMPILE_CACHE=0`` disables the whole feature.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Optional

_lock = threading.Lock()
_state = {"dir": None, "listener": False}

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``artifacts/xla_compile_cache`` inside the checkout — the directory
    used when ``JAX_COMPILATION_CACHE_DIR`` is not set."""
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo_root, "artifacts", "xla_compile_cache")


def enabled_dir() -> Optional[str]:
    """The directory the cache was enabled at in this process (None when
    not enabled)."""
    return _state["dir"]


def enable_compile_cache() -> Optional[str]:
    """Enable jax's persistent compilation cache (module docstring: where)
    and install the hit/miss telemetry listener. Idempotent; safe to call
    from every entry point (task bridge, bench, supervisor relaunch).
    Returns the active directory, or None when disabled
    (``OLS_COMPILE_CACHE=0``) or the directory cannot be created (reported
    on stderr; the process then compiles without a persistent cache)."""
    if os.environ.get("OLS_COMPILE_CACHE") == "0":
        return None
    import jax

    with _lock:
        if _state["dir"] is not None:
            return _state["dir"]
        directory = os.environ.get(CACHE_DIR_ENV)
        if not directory:
            directory = default_cache_dir()
            try:
                os.makedirs(directory, exist_ok=True)
            except OSError as e:
                print(f"compile cache disabled: cannot create {directory}: "
                      f"{e}", file=sys.stderr)
                return None
            jax.config.update("jax_compilation_cache_dir", directory)
        # Cache EVERY executable: the variant grid's small programs compile
        # under jax's default 1 s floor yet still dominate a cold process
        # start in aggregate.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _install_listener()
        _state["dir"] = directory
    return directory


def _install_listener() -> None:
    """Mirror jax's compilation-cache monitoring events into the metric
    catalog (one listener per process; jax offers no unregister-by-name,
    so the flag guards double counting). Counters always land in the
    PROCESS-DEFAULT registry, resolved per event — a per-caller registry
    would silently bind to whichever entry point enabled the cache first."""
    if _state["listener"]:
        return
    from jax import monitoring

    from olearning_sim_tpu.telemetry import instrument

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            instrument("ols_engine_compile_cache_hits_total").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            instrument("ols_engine_compile_cache_misses_total").inc()

    monitoring.register_event_listener(_on_event)
    _state["listener"] = True


def cache_stats() -> dict:
    """{"hits": n, "misses": n} as counted by the telemetry listener in
    this process (both 0 before the first compile after enabling). Reads
    the process-default registry — where the listener writes."""
    from olearning_sim_tpu.telemetry import instrument

    def _value(counter):
        return float(sum(child.value for _k, child in counter.children()))

    return {
        "hits": _value(instrument("ols_engine_compile_cache_hits_total")),
        "misses": _value(instrument("ols_engine_compile_cache_misses_total")),
    }
