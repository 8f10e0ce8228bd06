"""Benchmark smoke driver: tiny configs -> ``BENCH_*.json`` artifacts.

Runs bench_scheduling, bench_fusion, bench_graph and bench_serving on
configurations small enough for a CPU CI worker (a couple of minutes
total) and writes one JSON file per benchmark so the CI can archive the
perf trajectory:

  PYTHONPATH=src python benchmarks/smoke.py --out bench-artifacts

Each file carries the emitted csv lines verbatim plus parsed key=value
fields, so downstream tooling can diff runs without re-parsing logs.
BENCH_graph.json additionally carries top-level ``dispatch_count`` /
``per_tile_dispatch_count`` / ``host_overlap_frac`` fields, and
BENCH_scheduling.json carries the host-vs-device scheduling-backend
numbers (``sched_host_s_per_img`` etc.). The run exits nonzero (failing
the CI bench-smoke job) if:

  * the batch-fused dispatch count regresses to or above the per-tile
    baseline (ISSUE 3 gate);
  * the device scheduling backend is not bit-exact vs the host, or does
    not strictly reduce host scheduling time per image (ISSUE 4 gate);
  * batch-fused dispatch does not hit exactly ONE kernel dispatch per
    layer segment (below the same images run one at a time), or
    disagrees numerically with per-tile dispatch on either scheduling
    backend (ISSUE 5 gate);
  * continuous-batching serving (slot pool >= 4) does not beat the
    serve-one-at-a-time baseline by >= 1.5x requests/sec on the
    open-loop arrival benchmark (ISSUE 6 gate — BENCH_serving.json
    carries the p50/p95/p99 latencies of both modes);
  * the serving telemetry is broken (ISSUE 7 gate): the exported
    Chrome-trace JSON fails schema validation, the ``serve.step`` span
    wall diverges more than 10% from the measured step wall, or the
    engine's ``metrics_snapshot()`` disagrees with ``stats`` — the
    trace / timeline / metrics snapshot are written as
    ``TELEMETRY_serving_*.json`` next to the bench artifacts;
  * the resilience chaos bench (ISSUE 8 gate) loses or duplicates a
    request, fails a request with an untyped error, deadlocks, lets
    healthy-request p99 exceed 1.5x the fault-free baseline, breaks
    the executor-trace == DRAM-simulator cross-check on a non-faulted
    step, or fails the isolation / backpressure scenario checks;
  * the multi-device scale-out sweep (ISSUE 9 gate) does not reach a
    near-linear >= 2.5x modeled requests/sec at 4 forced host devices
    over the single-device baseline — the speedup is the accelerator
    model applied to the MEASURED per-replica counters (DRAM bytes,
    SPMD dispatches, all-gather bytes) of the real sharded serving
    engine, so an unbalanced replica placement or a chatty collective
    fails the gate even though forced host devices share the CI
    worker's cores (wall-clock rps is reported, never gated);
  * the autotuner bench (ISSUE 10 gate) lets a tuned plan lose to the
    greedy baseline on ANY swept (net, img_size) case, shows no case
    with a >5% executed-DRAM reduction, breaks the executed-trace ==
    DRAM-simulator equality under a tuned plan, diverges numerically
    from the greedy run, or fails to serve the persisted plan from a
    FRESH plan cache over the same directory (second-run disk hit);
  * ``--compare BASELINE_DIR`` is given (previous main-branch
    ``BENCH_*.json`` artifacts) and scheduled DRAM tile loads or a
    dispatch count (batch-fused, or serving dispatches/step) regress
    more than 10% against the
    baseline, or serving requests/sec or the serving schedule-cache
    image hit rate drops more than 10% below it (direction-aware:
    rps and hit rate are higher-is-better), or the chaos bench loses
    a request (fails on >0) or its healthy p99 ratio climbs high, or
    the tuned total DRAM bytes / tuned-vs-greedy max ratio (floor 1.0)
    / best rectangular-tile DRAM bytes regress against the baseline.

``--suite {all,core,resilience,scaleout,autotune}`` selects which
benches run: ``core`` is the perf suite above, ``resilience`` only the
chaos bench (its own CI leg), ``scaleout`` only the multi-device sweep
(the ``multidevice`` CI leg; the sweep spawns its own forced-device
subprocesses, so any host can run it), ``autotune`` the tile-shape
sweep + simulator-guided autotuner bench (its own CI leg), ``all``
(default) everything. Gates and ``--compare`` checks apply only to
suites that ran.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:          # allow `python benchmarks/smoke.py`
    sys.path.insert(0, _ROOT)

from benchmarks import (bench_autotune, bench_fusion, bench_graph,
                        bench_platforms, bench_resilience,
                        bench_scheduling, bench_serving,
                        bench_tile_size)

TINY_TDT = dict(h=16, w=16, c=16, tiles_per_side=4)


def _parse_fields(line: str) -> dict:
    fields = {}
    for part in line.split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            fields[k] = v
    return fields


def _collect(name: str, steps) -> dict:
    lines: list[str] = []
    for fn, kwargs in steps:
        fn(csv=lines.append, **kwargs)
    for ln in lines:
        print(ln)
    return {
        "bench": name,
        "config": "smoke-tiny",
        "lines": lines,
        "records": [dict(label=ln.split(",", 1)[0], **_parse_fields(ln))
                    for ln in lines],
    }


def _record(payload: dict, label: str) -> dict | None:
    return next((r for r in payload["records"] if r["label"] == label),
                None)


def _compare_baseline(baseline_dir: str, suites: dict) -> int:
    """CI bench-regression gate: scheduled DRAM tile loads and the
    batch-fused dispatch count must stay within 10% of the previous
    main-branch artifacts. A missing baseline (first run, expired
    artifact) is a warning, not a failure."""
    rc = 0
    # direction "lower": regression is new > base*1.10 (counts, loads);
    # direction "higher": regression is new < base*0.90 (requests/sec).
    # An optional 5th element is an absolute floor on the limit — used
    # for inherently noisy ratios so run-to-run jitter below the floor
    # can never flake the gate (requests_lost has no floor: baseline is
    # 0, so ANY lost request is limit-exceeding, i.e. fails on >0).
    checks = [
        ("BENCH_scheduling.json", "scheduled DRAM tile loads",
         lambda p: int(_record(p, "fig16_layer")["scheduled_loads"]),
         "lower"),
        ("BENCH_graph.json", "batch-fused dispatch count",
         lambda p: int(p["dispatch_count"]), "lower"),
        ("BENCH_graph.json", "batch-fused dispatch count (batch>1)",
         lambda p: int(p["batch_fused_dispatch_count"]), "lower"),
        ("BENCH_serving.json", "serving requests/sec (batched)",
         lambda p: float(p["serving_batched_rps"]), "higher"),
        ("BENCH_serving.json", "serving dispatches per step",
         lambda p: float(p["serving_dispatches_per_step"]), "lower"),
        ("BENCH_serving.json", "serving image hit rate",
         lambda p: float(p["serving_image_hit_rate"]), "higher"),
        ("BENCH_resilience.json", "resilience requests lost",
         lambda p: int(p["resilience_requests_lost"]), "lower"),
        ("BENCH_resilience.json", "resilience healthy p99 ratio",
         lambda p: float(p["resilience_p99_ratio"]), "lower", 1.5),
        ("BENCH_platforms.json", "scale-out modeled speedup",
         lambda p: float(p["scaleout_modeled_speedup"]), "higher"),
        ("BENCH_platforms.json", "scale-out all-gather bytes",
         lambda p: int(p["scaleout_allgather_bytes"]), "lower"),
        ("BENCH_autotune.json", "tuned total DRAM bytes",
         lambda p: int(p["autotune_tuned_total_bytes"]), "lower"),
        # ratio can only flake by run-to-run search jitter; the floor
        # keeps anything <= 1.0 (never losing) from ever failing.
        ("BENCH_autotune.json", "tuned-vs-greedy max DRAM ratio",
         lambda p: float(p["autotune_max_ratio"]), "lower", 1.0),
        ("BENCH_tiles.json", "best rectangular-tile DRAM bytes",
         lambda p: int(p["tiles_best_dram_bytes"]), "lower"),
    ]
    for fname, what, extract, direction, *floor in checks:
        if fname not in suites:
            continue          # suite not run (--suite core/resilience)
        path = os.path.join(baseline_dir, fname)
        if not os.path.exists(path):
            print(f"WARNING: no baseline {path}; skipping {what} check")
            continue
        try:
            with open(path) as f:
                base = extract(json.load(f))
        except (KeyError, TypeError, ValueError) as e:
            print(f"WARNING: unreadable baseline {path} ({e}); skipping")
            continue
        try:
            new = extract(suites[fname])
        except (KeyError, TypeError, ValueError) as e:
            # Current payload incomplete (e.g. an earlier gate already
            # flagged a missing record): fail the gate, keep going so
            # the artifacts still get written.
            print(f"ERROR: current {fname} missing comparison field "
                  f"({e})")
            rc = 1
            continue
        if direction == "higher":
            limit = base * 0.90
            regressed = new < limit
        else:
            limit = base * 1.10
            if floor:
                limit = max(limit, floor[0])
            regressed = new > limit
        verdict = "REGRESSED" if regressed else "ok"
        print(f"bench-regression: {what} new={new} baseline={base} "
              f"(limit {limit:.1f}) -> {verdict}")
        if regressed:
            rc = 1
    return rc


def _gate_graph(suites: dict) -> int:
    """ISSUE 3 + 5 gates: the batch-fused grid dispatch must stay
    strictly below the per-tile baseline, and the whole-batch fused path
    must issue exactly ONE kernel dispatch per layer segment, strictly
    below the same images run one at a time."""
    if "BENCH_graph.json" not in suites:
        return 0
    rc = 0
    graph_payload = suites["BENCH_graph.json"]
    bench = _record(graph_payload, "dispatch_bench")
    if bench is None:
        print("ERROR: dispatch_bench record missing from bench_graph")
        rc = 1
    else:
        per_tile = int(bench["per_tile_dispatches"])
        fused = int(bench["batch_fused_dispatches"])
        graph_payload["dispatch_count"] = fused
        graph_payload["per_tile_dispatch_count"] = per_tile
        graph_payload["host_overlap_frac"] = float(
            bench["host_overlap_frac"])
        if fused >= per_tile:
            print(f"ERROR: dispatch_count regressed: batch_fused={fused} "
                  f">= per_tile baseline={per_tile}")
            rc = 1
        if bench["dispatches_le_segments"] != "yes":
            print("ERROR: batch-fused dispatches exceed layer-segment "
                  "bound")
            rc = 1

    bf = _record(graph_payload, "batch_fused_bench")
    if bf is None:
        print("ERROR: batch_fused_bench record missing from bench_graph")
        rc = 1
    else:
        bf_dispatches = int(bf["dispatches_per_batch"])
        graph_payload["batch_fused_dispatch_count"] = bf_dispatches
        graph_payload["batch_fused_dispatches_per_batch"] = bf_dispatches
        graph_payload["batch_fused_batch"] = int(bf["batch"])
        graph_payload["n_layer_segments"] = int(bf["n_segments"])
        if bf["one_dispatch_per_segment"] != "yes":
            print(f"ERROR: batch-fused dispatches ({bf_dispatches}) != "
                  f"one per layer segment ({bf['n_segments']}) at "
                  f"batch={bf['batch']}")
            rc = 1
        if bf_dispatches >= int(bf["one_at_a_time_dispatches"]):
            print(f"ERROR: batch-fused dispatch count regressed: "
                  f"{bf_dispatches} >= one image at a time "
                  f"{bf['one_at_a_time_dispatches']}")
            rc = 1
    return rc


def _gate_scheduling(suites: dict) -> int:
    """ISSUE 4 gate: the device scheduler must be bit-exact vs the host
    and strictly reduce the host-side scheduling time per image; the
    single-layer batch-fused records must match per-tile numerics at
    one dispatch per batch."""
    if "BENCH_scheduling.json" not in suites:
        return 0
    rc = 0
    sched_payload = suites["BENCH_scheduling.json"]
    backend = _record(sched_payload, "sched_backend")
    if backend is None:
        print("ERROR: sched_backend record missing from bench_scheduling")
        rc = 1
    else:
        sched_payload["sched_host_s_per_img"] = float(
            backend["host_sched_s_per_img"])
        sched_payload["sched_device_host_s_per_img"] = float(
            backend["device_host_s_per_img"])
        sched_payload["sched_device_kernel_s_per_img"] = float(
            backend["device_kernel_s_per_img"])
        sched_payload["sched_backend_match"] = backend["match"]
        sched_payload["sched_host_prepass_reduced"] = (
            backend["host_prepass_reduced"])
        if backend["match"] != "yes":
            print("ERROR: device schedule backend is not bit-exact vs host")
            rc = 1
        if backend["host_prepass_reduced"] != "yes":
            print("ERROR: schedule_backend='device' did not reduce host "
                  "scheduling time per image")
            rc = 1

    bf_sched = [r for r in sched_payload["records"]
                if r["label"] == "batch_fused"]
    if not bf_sched:
        print("ERROR: batch_fused records missing from bench_scheduling")
        rc = 1
    for r in bf_sched:
        sched_payload[f"batch_fused_{r['backend']}_dispatches"] = int(
            r["dispatches_per_batch"])
        sched_payload[f"batch_fused_{r['backend']}_residue_s"] = float(
            r["host_prepass_residue_s"])
        if r["match"] != "yes":
            print(f"ERROR: batch-fused != per-tile numerics "
                  f"(backend={r['backend']})")
            rc = 1
        if int(r["dispatches_per_batch"]) >= int(r["per_tile_dispatches"]):
            print(f"ERROR: single-layer batch-fused dispatches "
                  f"({r['dispatches_per_batch']}) not below per-tile "
                  f"({r['per_tile_dispatches']})")
            rc = 1
    return rc


def _gate_serving(suites: dict) -> int:
    """ISSUE 6 + 7 gates: continuous-batching serving must beat the
    sequential baseline >= 1.5x at slot pool >= 4, and the telemetry
    (Chrome trace schema, serve.step span wall, metrics snapshot vs
    stats) must hold together."""
    if "BENCH_serving.json" not in suites:
        return 0
    rc = 0
    serving_payload = suites["BENCH_serving.json"]
    sv = _record(serving_payload, "serving_bench")
    if sv is None:
        print("ERROR: serving_bench record missing from bench_serving")
        rc = 1
    else:
        speedup = float(sv["speedup"])
        serving_payload["serving_slots"] = int(sv["slots"])
        serving_payload["serving_speedup"] = speedup
        serving_payload["serving_batched_rps"] = float(sv["batched_rps"])
        serving_payload["serving_sequential_rps"] = float(sv["seq_rps"])
        for r in serving_payload["records"]:
            if r["label"] == "serving_latency":
                for q in ("p50_s", "p95_s", "p99_s"):
                    serving_payload[f"serving_{r['mode']}_{q}"] = float(
                        r[q])
        if sv["batched_beats_sequential"] != "yes":
            print("ERROR: batched serving does not beat sequential infer")
            rc = 1
        if int(sv["slots"]) >= 4 and speedup < 1.5:
            print(f"ERROR: serving speedup {speedup:.2f}x < 1.5x at "
                  f"slot pool {sv['slots']}")
            rc = 1

    tr_rec = _record(serving_payload, "serving_trace")
    if tr_rec is None:
        print("ERROR: serving_trace record missing from bench_serving")
        rc = 1
    else:
        frac = float(tr_rec["span_wall_frac"])
        serving_payload["serving_trace_events"] = int(tr_rec["events"])
        serving_payload["serving_span_wall_frac"] = frac
        if tr_rec["schema_ok"] != "yes":
            print("ERROR: serving Chrome-trace export failed schema "
                  "validation")
            rc = 1
        if not 0.90 <= frac <= 1.10:
            print(f"ERROR: serve.step span wall diverges from measured "
                  f"step wall: span_wall_frac={frac:.3f} outside "
                  f"[0.90, 1.10]")
            rc = 1
    mt_rec = _record(serving_payload, "serving_metrics")
    if mt_rec is None:
        print("ERROR: serving_metrics record missing from bench_serving")
        rc = 1
    else:
        serving_payload["serving_dispatches_per_step"] = float(
            mt_rec["dispatches_per_step"])
        serving_payload["serving_image_hit_rate"] = float(
            mt_rec["image_hit_rate"])
        serving_payload["serving_timeline_steps"] = int(
            mt_rec["timeline_steps"])
        if mt_rec["metrics_match_stats"] != "yes":
            print("ERROR: engine metrics_snapshot() disagrees with "
                  "engine stats")
            rc = 1
    return rc


def _gate_resilience(suites: dict) -> int:
    """ISSUE 8 gate: under the seeded chaos campaign the engine must
    lose/duplicate zero requests, fail every faulted request with a
    typed error, never deadlock, keep healthy-request p99 <= 1.5x the
    fault-free baseline, keep the executor-trace == DRAM-simulator
    cross-check exact on non-faulted steps, and pass the isolation and
    backpressure scenario checks."""
    if "BENCH_resilience.json" not in suites:
        return 0
    rc = 0
    payload = suites["BENCH_resilience.json"]
    rb = _record(payload, "resilience_bench")
    if rb is None:
        print("ERROR: resilience_bench record missing from "
              "bench_resilience")
        rc = 1
    else:
        lost = int(rb["requests_lost"])
        duplicated = int(rb["duplicated"])
        ratio = float(rb["healthy_p99_ratio"])
        payload["resilience_requests_lost"] = lost
        payload["resilience_duplicated"] = duplicated
        payload["resilience_p99_ratio"] = ratio
        payload["resilience_p99_base_s"] = float(rb["p99_base_s"])
        payload["resilience_p99_faulted_s"] = float(rb["p99_faulted_s"])
        if lost > 0:
            print(f"ERROR: chaos bench lost {lost} request(s)")
            rc = 1
        if duplicated > 0:
            print(f"ERROR: chaos bench resolved {duplicated} request(s) "
                  f"more than once")
            rc = 1
        if rb["typed_errors"] != "yes":
            print("ERROR: a faulted request failed with an untyped error "
                  "(not RequestFailedError)")
            rc = 1
        if rb["deadlocked"] != "no":
            print("ERROR: chaos bench deadlocked (drain exhausted its "
                  "step budget)")
            rc = 1
        if ratio > 1.5:
            print(f"ERROR: healthy-request p99 ratio {ratio:.3f} > 1.5x "
                  f"fault-free baseline")
            rc = 1
    rf = _record(payload, "resilience_faults")
    if rf is None:
        print("ERROR: resilience_faults record missing from "
              "bench_resilience")
        rc = 1
    else:
        payload["resilience_faults_fired"] = int(rf["total_fired"])
        payload["resilience_watchdog_failovers"] = int(
            rf["watchdog_failovers"])
        if int(rf["total_fired"]) == 0:
            print("ERROR: chaos campaign fired zero faults — the bench "
                  "gated nothing")
            rc = 1
    re_rec = _record(payload, "resilience_engine")
    if re_rec is None:
        print("ERROR: resilience_engine record missing from "
              "bench_resilience")
        rc = 1
    else:
        payload["resilience_trace_checked"] = int(re_rec["trace_checked"])
        if re_rec["trace_exact"] != "yes":
            print("ERROR: executor trace != DRAM simulator on a "
                  "non-faulted chaos step")
            rc = 1
        if int(re_rec["trace_checked"]) == 0:
            print("ERROR: chaos run cross-checked zero traces")
            rc = 1
        if re_rec["isolation_ok"] != "yes":
            print("ERROR: tagged fault was not isolated to the offending "
                  "request (step-mates lost or inexact)")
            rc = 1
        if re_rec["backpressure_ok"] != "yes":
            print("ERROR: backpressure/deadline scenario failed "
                  "(shed/expired requests not accounted exactly once)")
            rc = 1
    return rc


def _gate_scaleout(suites: dict) -> int:
    """ISSUE 9 gate: the sharded serving engine must scale near-
    linearly — >= 2.5x modeled requests/sec at 4 forced host devices
    over single-device, with the speedup computed from the MEASURED
    per-replica counters (slowest-replica DRAM + dispatch time plus the
    logits all-gather), so unbalanced replica placement or collective
    bloat fails here even on a one-core worker."""
    if "BENCH_platforms.json" not in suites:
        return 0
    rc = 0
    payload = suites["BENCH_platforms.json"]
    summary = _record(payload, "scaleout_summary")
    if summary is None:
        print("ERROR: scaleout_summary record missing from "
              "bench_platforms")
        return 1
    modeled = float(summary["modeled_speedup"])
    devices_max = int(summary["devices_max"])
    payload["scaleout_devices_max"] = devices_max
    payload["scaleout_modeled_speedup"] = modeled
    payload["scaleout_measured_speedup"] = float(
        summary["measured_speedup"])
    points = [r for r in payload["records"] if r["label"] == "scaleout"]
    peak = next((r for r in points
                 if int(r["devices"]) == devices_max), None)
    payload["scaleout_allgather_bytes"] = (
        int(peak["allgather_bytes"]) if peak else 0)
    imgs = [int(r["images"]) for r in payload["records"]
            if (r["label"] == "scaleout_device"
                and int(r["devices"]) == devices_max)]
    if devices_max >= 4 and modeled < 2.5:
        print(f"ERROR: scale-out modeled speedup {modeled:.2f}x < 2.5x "
              f"at {devices_max} devices")
        rc = 1
    if imgs and max(imgs) - min(imgs) > 1:
        print(f"ERROR: replica placement unbalanced at "
              f"{devices_max} devices: per-replica images {imgs}")
        rc = 1
    if summary["near_linear"] != ("yes" if modeled >= 2.5 else "no"):
        print("ERROR: scaleout_summary near_linear flag disagrees with "
              "its own modeled_speedup")
        rc = 1
    return rc


def _gate_autotune(suites: dict) -> int:
    """ISSUE 10 gate: simulator-guided tuned plans must never lose to
    the greedy baseline on executed DRAM traffic for any swept
    (net, img_size) case, at least one case must show a >5% reduction,
    tuned executed traces must stay EXACTLY equal to the DRAM
    simulator, tuned numerics must match greedy, and the persisted plan
    must hit from a FRESH plan cache on the second run."""
    rc = 0
    if "BENCH_autotune.json" in suites:
        payload = suites["BENCH_autotune.json"]
        summary = _record(payload, "autotune_summary")
        if summary is None:
            print("ERROR: autotune_summary record missing from "
                  "bench_autotune")
            rc = 1
        else:
            max_ratio = float(summary["max_ratio"])
            min_ratio = float(summary["min_ratio"])
            payload["autotune_max_ratio"] = max_ratio
            payload["autotune_min_ratio"] = min_ratio
            payload["autotune_tuned_total_bytes"] = int(
                summary["tuned_total_bytes"])
            payload["autotune_greedy_total_bytes"] = int(
                summary["greedy_total_bytes"])
            payload["autotune_search_s_total"] = float(
                summary["search_s_total"])
            if max_ratio > 1.0:
                print(f"ERROR: tuned plan LOSES to greedy on a swept "
                      f"case: max tuned/greedy DRAM ratio "
                      f"{max_ratio:.4f} > 1.0")
                rc = 1
            if min_ratio >= 0.95:
                print(f"ERROR: no swept case shows a >5% tuned DRAM "
                      f"reduction (best ratio {min_ratio:.4f})")
                rc = 1
            if summary["plan_cache_hit_on_second_run"] != "yes":
                print("ERROR: persisted plan missed from a fresh plan "
                      "cache on the second run")
                rc = 1
            if summary["all_trace_exact"] != "yes":
                print("ERROR: tuned executed trace != DRAM simulator")
                rc = 1
            if summary["all_numerics_ok"] != "yes":
                print("ERROR: tuned run diverges numerically from the "
                      "greedy run")
                rc = 1
    if "BENCH_tiles.json" in suites:
        payload = suites["BENCH_tiles.json"]
        best = _record(payload, "rect_best")
        if best is None:
            print("ERROR: rect_best record missing from bench_tile_size")
            rc = 1
        else:
            payload["tiles_best_dram_bytes"] = int(best["dram_bytes"])
            payload["tiles_best_tile"] = (f"{best['tile_h']}x"
                                          f"{best['tile_w']}")
            payload["tiles_spread"] = float(best["spread"])
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=".", help="output directory")
    ap.add_argument("--compare", default=None, metavar="BASELINE_DIR",
                    help="directory of previous-main BENCH_*.json "
                         "artifacts; fail on >10%% regression of "
                         "scheduled loads / dispatch count")
    ap.add_argument("--suite", default="all",
                    choices=("all", "core", "resilience", "scaleout",
                             "autotune"),
                    help="which bench suites to run (default: all)")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    suites = {}
    if args.suite in ("all", "core"):
        suites = {
            "BENCH_scheduling.json": _collect("scheduling", [
                (bench_scheduling.run, dict(tdt_kwargs=TINY_TDT,
                                            channels=16, c_out=16,
                                            buffer_bytes=4096)),
                (bench_scheduling.run_executor, dict(h=16, w=16, c=8,
                                                     c_out=8, tile=8,
                                                     buffer_tiles=2)),
                (bench_scheduling.run_backends, dict(h=16, w=16, c=8,
                                                     c_out=8, tile=8,
                                                     buffer_tiles=2,
                                                     repeats=3)),
                (bench_scheduling.run_batch_fused, dict(h=16, w=16, c=8,
                                                        c_out=8, tile=8,
                                                        buffer_tiles=2,
                                                        batch=4,
                                                        repeats=2)),
            ]),
            "BENCH_fusion.json": _collect("fusion", [
                (bench_fusion.run, dict(tdt_kwargs=TINY_TDT, channels=16,
                                        c_out=16)),
                (bench_fusion.run_executor, dict(h=16, w=16, c=8, c_out=8,
                                                 tile=8)),
            ]),
            "BENCH_graph.json": _collect("graph", [
                (bench_graph.run, dict(img=13, n_deform=2,
                                       width_mult=0.125, tile=4)),
                (bench_graph.run_dispatch, dict(img=13, n_deform=2,
                                                width_mult=0.125, tile=4,
                                                batch=4, repeats=2)),
                (bench_graph.run_model_backend, dict(img=16, n_deform=2,
                                                     width_mult=0.125,
                                                     tile=4)),
            ]),
            "BENCH_serving.json": _collect("serving", [
                (bench_serving.run, dict(
                    img=13, n_deform=2, width_mult=0.125, tile=4, slots=8,
                    n_requests=16,
                    trace_out=os.path.join(
                        args.out, "TELEMETRY_serving_trace.json"),
                    timeline_out=os.path.join(
                        args.out, "TELEMETRY_serving_timeline.json"),
                    metrics_out=os.path.join(
                        args.out, "TELEMETRY_serving_metrics.json"))),
            ]),
        }
    if args.suite in ("all", "resilience"):
        suites["BENCH_resilience.json"] = _collect("resilience", [
            (bench_resilience.run, dict(img=13, n_deform=2,
                                        width_mult=0.125, tile=4,
                                        slots=4, n_requests=24,
                                        fault_rate=0.1, seed=0)),
        ])
    if args.suite in ("all", "scaleout"):
        suites["BENCH_platforms.json"] = _collect("platforms", [
            (bench_platforms.run, {}),
            (bench_platforms.run_scaleout, dict(
                device_counts=(1, 2, 4), n_requests=12, img=16,
                n_deform=2, width_mult=0.125, tile=4, slots=4)),
        ])
    if args.suite in ("all", "autotune"):
        suites["BENCH_tiles.json"] = _collect("tiles", [
            (bench_tile_size.run, dict(h=16, w=16, c=16,
                                       tiles_per_side=(2, 4, 8),
                                       buffer_bytes=4096)),
            # rect config picked so the best shape is an INTERIOR point
            # (8x8, spread ~2x) — the sweep demonstrates a real search
            # space, not a degenerate whole-plane winner.
            (bench_tile_size.run_rect, dict(h=24, w=24, c=24,
                                            sides=(2, 4, 8, 16),
                                            buffer_bytes=2048)),
        ])
        suites["BENCH_autotune.json"] = _collect("autotune", [
            (bench_autotune.run, dict(
                cache_dir=os.path.join(args.out, "plan-cache"))),
        ])

    # Gates apply only to suites that ran (--suite). The CI bench-smoke
    # job fails on the nonzero exit.
    rc = 0
    rc = max(rc, _gate_graph(suites))
    rc = max(rc, _gate_scheduling(suites))
    rc = max(rc, _gate_serving(suites))
    rc = max(rc, _gate_resilience(suites))
    rc = max(rc, _gate_scaleout(suites))
    rc = max(rc, _gate_autotune(suites))

    if args.compare:
        rc = max(rc, _compare_baseline(args.compare, suites))

    meta = {"python": platform.python_version(),
            "platform": platform.platform()}
    for fname, payload in suites.items():
        payload["meta"] = meta
        path = os.path.join(args.out, fname)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {path}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
