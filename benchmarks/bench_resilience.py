"""Chaos benchmark: serving resilience under deterministic fault injection.

Drives ``DcnServingEngine`` through a seeded fault campaign
(``repro.testing.faults``) and verifies the resilience contract the unit
tests pin piecewise, end to end and under load:

  * **exactly-once** — every submitted request resolves exactly once:
    nothing lost, nothing duplicated, every failure a typed
    ``RequestFailedError`` on the handle;
  * **bounded blast radius** — healthy requests (those no fault touched)
    keep p99 latency within 1.5x of a fault-free run of the same
    workload;
  * **isolation** — a tagged fault in a coalesced step fails only the
    offending request while its step-mates complete reference-exact;
  * **honest accounting** — on every non-faulted step the executed
    trace still equals the DRAM simulator exactly (resilience machinery
    must not perturb the model);
  * **liveness** — nothing deadlocks: every drain completes within its
    step budget, including under backpressure shedding and deadline
    expiry.

The chaos phase runs the injector in ``"step"`` mode at ``fault_rate``
(default 0.1): each step arms each fault kind independently, so the
faulted-step fraction stays ~``1-(1-rate)^kinds`` and the healthy
population is large enough for the p99 gate to mean something. All
draws are pure functions of the seed — reruns reproduce the exact same
failure pattern.
"""

from __future__ import annotations

import os
import sys

import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # allow `python benchmarks/bench_resilience.py`
    sys.path.insert(0, _ROOT)

from benchmarks.bench_graph import _case
from repro.core.simulator import simulate_network
from repro.runtime import GraphConfig
from repro.runtime.fused_exec import network_sim_specs
from repro.serving import (DcnServingEngine, DrainTimeout,
                           RequestFailedError)
from repro.testing import ALL_FAULT_KINDS, FaultInjector


def _trace_matches(tr) -> bool:
    """Executed trace == DRAM simulator, exactly (the ISSUE 3-6 cross-
    check, reasserted on non-faulted steps of the chaos run)."""
    sim = simulate_network(network_sim_specs(tr),
                           boundary_bytes=tr.boundary_bytes, fused=True)
    if tr.total_dram_bytes != sim.total_dram_bytes:
        return False
    return all(gt.fifo_replay().loads == rep.tile_loads
               for gt, rep in zip(tr.groups, sim.groups))


def _closed_loop(eng, xs, inj=None, trace_check=False):
    """Serve ``xs`` one request at a time; returns accounting dict.

    With ``inj``, each image first passes through ``corrupt`` (the
    nan_image fault) — a rejected submit counts as resolved at the
    front door, which is the isolation under test.
    """
    acc = dict(submitted=0, nan_rejected=0, resolved_rids=[],
               healthy_lat=[], failed=[], deadlocked=False,
               trace_checked=0, trace_exact=0)
    for x in xs:
        # "Healthy" = neither a fault nor a watchdog failover touched
        # this request: the p99 gate measures blast radius onto
        # untouched traffic, so stalled/retried/failed-over requests
        # don't dilute it (a failover can also fire spuriously on a
        # transient scheduling hiccup — environmental noise, excluded
        # symmetrically in both phases).
        f0 = inj.total_fired if inj is not None else 0
        if inj is not None:
            x = inj.corrupt(x)
        try:
            r = eng.submit(x)
        except ValueError:
            acc["nan_rejected"] += 1
            continue
        acc["submitted"] += 1
        s0 = eng.stats
        try:
            done = eng.drain(max_steps=50)
        except DrainTimeout as e:
            acc["deadlocked"] = True
            done = e.finished
        acc["resolved_rids"].extend(q.rid for q in done)
        s1 = eng.stats
        if trace_check:
            clean = (not eng.last_step_faulted
                     and s1["degraded_steps"] == s0["degraded_steps"])
            if clean and eng.last_trace is not None:
                acc["trace_checked"] += 1
                acc["trace_exact"] += int(_trace_matches(eng.last_trace))
        if r.failed:
            acc["failed"].append(r)
        elif r.done:
            untouched = ((inj is None or inj.total_fired == f0)
                         and s1["watchdog_failovers"]
                         == s0["watchdog_failovers"])
            if untouched:
                acc["healthy_lat"].append(r.latency_s)
    return acc


def run(csv=print, img: int = 13, n_deform: int = 2,
        width_mult: float = 0.125, tile: int = 4, slots: int = 4,
        n_requests: int = 24, fault_rate: float = 0.1, seed: int = 0,
        stall_s: float = 0.6, watchdog_s: float = 0.25):
    """Fault-free baseline + seeded chaos run + isolation/backpressure
    scenarios; csv three records smoke.py gates on."""
    cfg, params, _ = _case(img, n_deform, width_mult, seed)
    rng = np.random.default_rng(seed + 1)
    xs = [rng.normal(size=(img, img, 3)).astype(np.float32)
          for _ in range(n_requests)]

    def engine(**kw):
        kw.setdefault("graph", GraphConfig(tile=tile,
                                           watchdog_s=watchdog_s))
        kw.setdefault("slots", slots)
        return DcnServingEngine(params, cfg, **kw)

    # Warm every compile path the chaos run can reach: fused widths the
    # coalesced/retry steps use, and the degraded one-image-at-a-time
    # path (forced via one untagged fault — the jit cache is process-global,
    # so this compile never lands mid-measurement).
    warm = engine()
    for w in (1, slots - 1, slots):
        for k in range(w):
            warm.submit(xs[k % len(xs)])
        warm.drain()
    force = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=1,
                          tag_image=False, seed=seed)
    warm_deg = engine(faults=force)
    warm_deg.submit(xs[0])
    warm_deg.drain()

    # -- phase 1: fault-free baseline (same workload, own engine/cache)
    base = _closed_loop(engine(), xs)
    p99_base = float(np.percentile(base["healthy_lat"], 99))

    # -- phase 2: chaos — all fault kinds, step-scoped arming
    inj = FaultInjector(kinds=ALL_FAULT_KINDS, rate=fault_rate,
                        seed=seed + 2, stall_s=stall_s, mode="step")
    eng = engine(faults=inj)
    chaos = _closed_loop(eng, xs, inj=inj, trace_check=True)
    p99_faulted = (float(np.percentile(chaos["healthy_lat"], 99))
                   if chaos["healthy_lat"] else float("nan"))
    p99_ratio = p99_faulted / p99_base if p99_base else float("inf")
    # Snapshot NOW: watchdog_failovers is a process-wide delta and the
    # scenario engines below would otherwise leak into it.
    s = eng.stats

    # -- phase 3: isolation — one tagged fault in a coalesced step
    inj_iso = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=1,
                            seed=seed + 3)
    eng_iso = engine(faults=inj_iso)
    iso_reqs = [eng_iso.submit(x) for x in xs[:slots]]
    iso_done = eng_iso.drain()
    iso_failed = [r for r in iso_reqs if r.failed]
    ref = np.asarray(engine().infer(jnp.asarray(np.stack(xs[:slots]))))
    iso_ok = (len(iso_done) == slots and len(iso_failed) == 1
              and isinstance(iso_failed[0].error, RequestFailedError)
              and all(np.allclose(r.result()[0], ref[i],
                                  rtol=2e-4, atol=2e-4)
                      for i, r in enumerate(iso_reqs) if not r.failed))

    # -- phase 4: backpressure shedding + deadline expiry, no deadlock
    eng_bp = engine(slots=1, max_queue=4, queue_policy="shed-oldest")
    bp_reqs = [eng_bp.submit(x) for x in xs[:6]]          # sheds 2
    bp_done = eng_bp.drain()
    rd = eng_bp.submit(xs[6], deadline_s=1e-6)            # expires queued
    bp_done += eng_bp.drain()
    shed = [r for r in bp_reqs if r.failed]
    bp_rids = [r.rid for r in bp_done] + [r.rid for r in shed]
    bp_ok = (sorted(bp_rids + []) == sorted(r.rid for r in bp_reqs + [rd])
             and rd.failed
             and eng_bp.stats["queue_shed"] == len(shed)
             and all(isinstance(r.error, RequestFailedError)
                     for r in shed + [rd]))

    # -- accounting and gates data
    lost = (chaos["submitted"] - len(chaos["resolved_rids"])
            + base["submitted"] - len(base["resolved_rids"]))
    duplicated = (len(chaos["resolved_rids"])
                  - len(set(chaos["resolved_rids"])))
    typed_ok = all(isinstance(r.error, RequestFailedError)
                   for r in chaos["failed"])
    deadlocked = base["deadlocked"] or chaos["deadlocked"]
    trace_exact = chaos["trace_exact"] == chaos["trace_checked"]

    csv(f"resilience_bench,n_requests={n_requests},"
        f"submitted={chaos['submitted']},"
        f"nan_rejected={chaos['nan_rejected']},"
        f"requests_lost={lost},duplicated={duplicated},"
        f"typed_errors={'yes' if typed_ok else 'NO'},"
        f"healthy={len(chaos['healthy_lat'])},"
        f"p99_base_s={p99_base:.4f},p99_faulted_s={p99_faulted:.4f},"
        f"healthy_p99_ratio={p99_ratio:.3f},"
        f"deadlocked={'YES' if deadlocked else 'no'}")
    csv(f"resilience_faults,rate={fault_rate},"
        f"total_fired={inj.total_fired},"
        f"prepass={inj.fired.get('prepass', 0)},"
        f"dispatch={inj.fired.get('dispatch', 0)},"
        f"worker_stall={inj.fired.get('worker_stall', 0)},"
        f"cache_miss={inj.fired.get('cache_miss', 0)},"
        f"nan_image={inj.fired.get('nan_image', 0)},"
        f"step_retries={s['step_retries']},"
        f"degraded_steps={s['degraded_steps']},"
        f"watchdog_failovers={s['watchdog_failovers']}")
    csv(f"resilience_engine,steps={s['steps']},"
        f"requests_failed={s['requests_failed']},"
        f"trace_checked={chaos['trace_checked']},"
        f"trace_exact={'yes' if trace_exact else 'NO'},"
        f"isolation_ok={'yes' if iso_ok else 'NO'},"
        f"queue_shed={eng_bp.stats['queue_shed']},"
        f"deadline_expired={eng_bp.stats['deadline_expired']},"
        f"backpressure_ok={'yes' if bp_ok else 'NO'}")
    return eng, chaos


if __name__ == "__main__":
    run()
