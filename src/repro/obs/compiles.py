"""Lowering counter: one count per program jax lowers to MLIR.

jax reports ``/jax/core/compile/jaxpr_to_mlir_module_duration`` once per
executable missing from its in-memory cache — every new jitted program
and every eager operation at a new shape or dtype — on the thread that
lowers it, whether or not the persistent compile cache then skips the
backend compile. :func:`install_lowering_listener` subscribes to that
event once per process. Each event bumps the process-wide
``jax.lowerings`` counter of the default registry and, when the current
tracer on the lowering thread is enabled, records a ``jax.lower`` span
of the event's duration there, its ``program`` attribute the name jax
gives the lowered function (``jit(reshape)`` for an eager reshape).
jax reports no duration for a call its caches serve, so a step that
lowers nothing never calls the listener.

jax is imported inside :func:`install_lowering_listener` only, so
``repro.obs`` stays importable without it.
"""

from __future__ import annotations

import threading

from repro.obs.metrics import default_registry
from repro.obs.tracer import get_tracer

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

jax_lowerings = default_registry().counter(
    "jax.lowerings",
    help="programs lowered to MLIR (in-memory compile cache misses)")

_installed = False
_install_lock = threading.Lock()


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != LOWER_EVENT:
        return
    jax_lowerings.inc()
    tr = get_tracer()
    if tr.enabled:
        tr.record("jax.lower", duration_secs,
                  program=kwargs.get("fun_name", ""))


def install_lowering_listener() -> None:
    """Subscribe the counter to jax's lowering events (idempotent)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
