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
``ols_engine_compile_cache_misses_total`` counters, and stamps jax's own
compile durations onto the default span tracer as ``compile.trace``,
``compile.lower``, ``compile.backend`` and ``compile.cache_load`` spans
(:func:`install_listener`), children of whatever span the compiling
thread has open.

``OLS_COMPILE_CACHE=0`` disables the cache; the listener stays.
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
    install_listener()
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
        _state["dir"] = directory
    return directory


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def program_name(fun_name: str) -> str:
    """jax's ``fun_name`` in one form for the four compile events, so that
    a program's spans are found by one key: the trace event names the
    Python function (``round_step``), the lowering, the backend compile
    and the cache load its module (``jit(round_step)``; ``jit_round_step``
    in older releases), and a qualified name ends in the function's
    (``FedCore._build.<locals>.round_step``). All become ``round_step``."""
    name = str(fun_name)
    for wrapper in ("jit", "pmap"):
        if name.startswith(wrapper + "(") and name.endswith(")"):
            name = name[len(wrapper) + 1:-1]
        elif name.startswith(wrapper + "_"):
            name = name[len(wrapper) + 1:]
    return name.rsplit(".", 1)[-1]


def install_listener() -> None:
    """Mirror jax's compilation monitoring events into the metric catalog
    and the span tree (one set of listeners per process; jax offers no
    unregister-by-name, so the flag guards double counting). Idempotent;
    :func:`enable_compile_cache` and every runner call it. Counters and
    spans always land in the PROCESS-DEFAULT registry and tracer, resolved
    per event — a per-caller sink would silently bind to whichever entry
    point enabled the cache first.

    The spans are recorded when jax reports the duration, in the compiling
    thread, so their parent is the span that thread has open (round 0's
    ``round.<operator>.train``), whose ``task_id`` and ``round_idx`` they
    copy; a recompile in a later round names its round, phase and
    ``fun_name``, and ``program`` is that name in one form for the four
    events (:func:`program_name`): the engine's own jits give
    ``round_step``, ``evaluate``, ``make`` (the initialiser) and
    ``partial_body`` (the streamed partial step); anything else is an
    eager operation's. Only the OUTERMOST interval of a thread is
    recorded: jax
    reports one duration per traced jit and the functions of ``jax.numpy``
    are jits, so a round program's trace holds hundreds of nested ones, its
    lowering traces more, and a trace that runs an operation eagerly
    compiles inside itself; each lies inside its caller's interval and the
    sum would count the time many times over. jax's scalar event at an
    interval's start carries the depth.

    - ``compile.trace``: Python function to jaxpr.
    - ``compile.lower``: jaxpr to MLIR module.
    - ``compile.backend`` / ``compile.cache_load``: jax's backend-compile
      interval wraps its persistent-cache lookup, so one interval is one or
      the other: ``compile.cache_load`` (key hashing, read, deserialize;
      ``retrieval_s`` is jax's own retrieval time) when the lookup hit,
      else ``compile.backend`` (XLA compilation and the cache write)."""
    with _lock:
        if _state["listener"]:
            return
        _state["listener"] = True
    from jax import monitoring

    from olearning_sim_tpu.telemetry import default_tracer, instrument

    local = threading.local()

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            instrument("ols_engine_compile_cache_hits_total").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            instrument("ols_engine_compile_cache_misses_total").inc()

    names = {TRACE_EVENT: "compile.trace", LOWER_EVENT: "compile.lower",
             BACKEND_EVENT: "compile.backend"}

    def _on_start(event: str, value, **kwargs) -> None:
        if event in names:
            local.depth = getattr(local, "depth", 0) + 1

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == CACHE_LOAD_EVENT:
            local.retrieval_s = duration
            return
        if event not in names:
            return
        name, attrs = names[event], kwargs
        if event == BACKEND_EVENT:
            retrieval_s = getattr(local, "retrieval_s", None)
            local.retrieval_s = None
            if retrieval_s is not None:
                name = "compile.cache_load"
                attrs = dict(kwargs, retrieval_s=retrieval_s)
        local.depth = depth = getattr(local, "depth", 1) - 1
        if depth > 0:
            return
        if "fun_name" in attrs:
            attrs = dict(attrs, program=program_name(attrs["fun_name"]))
        tracer = default_tracer()
        parent = tracer.current()
        if parent is not None:
            # The task and round the compile belongs to, so a reader finds
            # a task's compiles without walking parent links.
            attrs = dict(attrs, **{k: parent.attrs[k]
                                   for k in ("task_id", "round_idx")
                                   if k in parent.attrs})
        tracer.record(name, tracer.now() - duration, duration, **attrs)

    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)


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
