"""The executor's staging queue and the config checks it shares.

``run_staged`` overlaps host prepass with device execution: with
``staging_depth > 1`` the prepass of unit i+1 (an image under
``per_tile`` dispatch, a fused-group segment of the whole batch under
``batch_fused``) runs on a worker thread while unit i's kernels are
dispatched. ``validate_dispatch_config`` and ``clamp_tile_config`` are
the :class:`~repro.runtime.fused_exec.GraphConfig` checks and the
entry points' tile clamp.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout

from repro.obs import Tracer, default_registry, get_tracer, use_tracer


# Process-wide like core.scheduler.host_schedule_builds: callers that
# need a per-engine view keep a construction-time baseline and report
# their delta.
staging_watchdog_failovers = default_registry().counter(
    "staging.watchdog_failovers",
    help="staged prepasses that missed the watchdog deadline and were "
         "re-run synchronously on the driving thread")


def run_staged(n: int, prepass, execute, depth: int, overlap,
               tracer: Tracer | None = None,
               watchdog_s: float | None = None, faults=None) -> list:
    """The executor's staging queue.

    ``prepass(i)`` builds unit i's host-side artifacts, ``execute(i,
    art)`` dispatches its kernels. With ``depth > 1`` up to ``depth - 1``
    prepasses run ahead on a single worker thread while the main thread
    executes (jax dispatch is itself async, so the device stays busy
    under the host-side schedule build); ``overlap`` (an
    :class:`~repro.runtime.trace.OverlapSpans`) is re-derived from the
    ``prepass`` / ``prepass.wait`` spans this queue records through
    ``tracer`` (always measured; stored only when the tracer is
    enabled). Returns the per-image execute results.

    ``watchdog_s`` bounds each wait on the staging worker: a prepass
    that does not deliver within the deadline is treated as wedged — the
    queue fails over to synchronous prepass for the rest of the run
    (``staging.watchdog_failover`` instant marker + process counter),
    the stuck worker is abandoned (never joined), and batch-fused
    callers' sequential prepass state stays consistent because their
    epoch-guarded commit discards any late duplicate (see
    ``_run_graph_batch_fused``). ``faults`` is a test-only injector
    (``repro.testing.faults``) consulted for ``worker_stall`` sleeps.
    """
    tr = tracer if tracer is not None else get_tracer()

    def staged(i: int):
        # On the staging worker too, spans opened through get_tracer()
        # (packing, schedule kernels, lowerings) land in ``tr``.
        if faults is not None:
            faults.stall("worker_stall")
        with use_tracer(tr), tr.timed("prepass", unit=i) as sp:
            art = prepass(i)
        return art, sp

    outs = []
    if depth == 1 or n == 1:
        for i in range(n):
            # Serial mode: the execute loop blocks on the whole prepass,
            # so the wait span wraps it (host_overlap_frac == 0).
            with tr.timed("prepass.wait", unit=i) as wsp:
                art, sp = staged(i)
            overlap.add_span(sp)
            overlap.add_span(wsp)
            outs.append(execute(i, art))
        return outs
    pool = ThreadPoolExecutor(max_workers=1)
    failed_over = False
    try:
        futs: deque = deque()
        nxt = 0
        while nxt < n and len(futs) < depth - 1:
            futs.append(pool.submit(staged, nxt))
            nxt += 1
        for i in range(n):
            with tr.timed("prepass.wait", unit=i) as wsp:
                if failed_over or not futs:
                    art, sp = staged(i)
                else:
                    try:
                        art, sp = futs.popleft().result(
                            timeout=watchdog_s)
                    except _FutTimeout:
                        failed_over = True
                        staging_watchdog_failovers.bump()
                        tr.instant("staging.watchdog_failover", unit=i)
                        art, sp = staged(i)
            overlap.add_span(sp)
            overlap.add_span(wsp)
            if not failed_over and nxt < n:
                futs.append(pool.submit(staged, nxt))
                nxt += 1
            outs.append(execute(i, art))
    finally:
        # A wedged worker would hang the context-manager shutdown; after
        # a failover, abandon it (queued-but-unstarted work is
        # cancelled, the running thread exits on its own — injected
        # stalls are finite by contract).
        pool.shutdown(wait=not failed_over, cancel_futures=failed_over)
    return outs


def validate_dispatch_config(cfg) -> None:
    """``GraphConfig.__post_init__`` checks: tile sides, dispatch mode,
    schedule backend and staging depth."""
    cfg.tile_hw                          # validates tile sides
    if cfg.dispatch not in ("batch_fused", "per_tile"):
        raise ValueError(f"unknown dispatch mode: {cfg.dispatch!r}")
    if cfg.schedule_backend not in ("host", "device"):
        raise ValueError(
            f"unknown schedule backend: {cfg.schedule_backend!r}")
    if cfg.staging_depth < 1:
        raise ValueError(
            f"staging_depth must be >= 1, got {cfg.staging_depth}")
    if cfg.watchdog_s is not None and cfg.watchdog_s <= 0:
        raise ValueError(
            f"watchdog_s must be > 0 (or None), got {cfg.watchdog_s}")
    dp = cfg.data_parallel
    if dp is not None and dp < 1:
        raise ValueError(f"data_parallel must be >= 1, got {dp}")
    if ((cfg.mesh is not None or (dp or 1) > 1)
            and cfg.dispatch != "batch_fused"):
        raise ValueError(
            "mesh=/data_parallel= sharding only applies to "
            f"dispatch='batch_fused', got dispatch={cfg.dispatch!r}")
    if cfg.autotune not in ("off", "offline", "cached-only"):
        raise ValueError(
            f"autotune must be 'off', 'offline' or 'cached-only', "
            f"got {cfg.autotune!r}")
    if cfg.autotune_budget < 1:
        raise ValueError(
            f"autotune_budget must be >= 1, got {cfg.autotune_budget}")


def clamp_tile_config(cfg, h: int, w: int):
    """Clamp a ``GraphConfig``'s tile to an (h, w) input plane — the
    model and serving entry points accept any image size, while
    ``run_graph`` and ``dcn_pipeline`` reject tile > plane (a silent
    1-tile grid otherwise)."""
    th, tw = cfg.tile_hw
    if th <= h and tw <= w:
        return cfg
    return dataclasses.replace(cfg, tile=(min(th, h), min(tw, w)))
