"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``bench/configs``), its traffic mix
(``bench/traffic/<name>.json``, read by ``bench/traffic.py``) and, with
``--trace 1``, one reader per per-layer metric (``bench/metrics``).

One run: device check (a TPU, Pallas compiled, enough chips, a known
peak), set-up (weights on the device from the seed, the serving engine
with its defaults, warm-up of every step width the mix drives), the
measured window, then the comparison with the plain reference that
decides ``correct``. The last line of stdout is one JSON object; the
numbers compared, each beside its limit, are also the last lines of
stderr. A run that finds no TPU exits 1 and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import model, program, xtrace  # noqa: E402
from bench.traffic import Mix  # noqa: E402

# Seconds past the window's close that an open loop waits for the answers
# still due before it counts them as missing.
DRAIN_LIMIT_S = 60.0
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    pass


# -- lookup -----------------------------------------------------------------


def load_cell(root: str, name: str) -> dict:
    """The cell, its configuration and its mix, found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in spec[kind]:
            if name in m.get("workloads", [name]):
                metrics[kind].append(m)
    return {"cell": cell, "config": config, "metrics": metrics,
            "traffic": os.path.join(root, "bench", "traffic",
                                    cell["traffic"] + ".json"),
            "root": root}


def load_reader(root: str, metric: str):
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- device -----------------------------------------------------------------


def device_check(chips: int, peaks: dict) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: the first device is {d.platform!r}")
    if program.pallas_interprets():
        raise NoDevice("Pallas would run in interpret mode")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found {len(devs)}")
    if d.device_kind not in peaks:
        raise NoDevice(f"no peak for device kind {d.device_kind!r} in "
                       "bench/peaks.json")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- the window -------------------------------------------------------------


@dataclasses.dataclass
class Step:
    start: float
    dur: float
    width: int


@dataclasses.dataclass
class Window:
    """What a run measured, handed to every per-layer reader."""

    net: model.Net
    slots: int
    seconds: float
    steps: list[Step]
    window_s: float = 0.0
    images: int = 0                       # images served in the window
    cache: tuple[int, int] = (0, 0)       # schedule-cache hits, lookups
    spans: list = dataclasses.field(default_factory=list)
    trace: xtrace.Trace | None = None
    trace_bounds: tuple[int, int] | None = None   # window, profiler ns
    peak: dict = dataclasses.field(default_factory=dict)

    @property
    def layers(self) -> list[model.Layer]:
        return model.layers(self.net)

    def device_ops(self) -> list[xtrace.Event]:
        if self.trace is None or not self.trace.device_ops:
            return []
        return [e for evs in self.trace.device_ops.values() for e in evs]


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def timed_step(engine, steps: list[Step]) -> list:
    """One ``step()``; it returns once its results are on the host."""
    images0 = engine.images
    t = time.perf_counter()
    with annotate("step"):
        finished = engine.step()
    steps.append(Step(t, time.perf_counter() - t, engine.images - images0))
    return finished


def warm_up(engine, mix: Mix, slots: int) -> None:
    """Serve every step width the window will drive."""
    k = 0
    for width, from_traffic in mix.warmup_steps(slots):
        for _ in range(width):
            if from_traffic:
                engine.submit(mix.image(mix.next_frame()))
            else:
                engine.submit(mix.warmup_image(k))
                k += 1
        t = time.perf_counter()
        finished = engine.step()
        log(f"warm-up step of {width}: {time.perf_counter() - t:.2f} s")
        if len(finished) != width:
            raise RuntimeError(f"warm-up step of {width} images finished "
                               f"{len(finished)} requests")


def open_loop(engine, mix: Mix, seconds: float, steps: list[Step]):
    """Requests due at the mix's times. Drains after the close, for up to
    ``DRAIN_LIMIT_S``. Returns [(image id, due, request, latency)], with
    the time waited so far as the latency of an answer that never came,
    and the window's start."""
    due = mix.due_times(seconds)
    sent: list = []
    ready: dict[int, float] = {}
    t0 = time.perf_counter()
    k = 0
    while True:
        now = time.perf_counter()
        with annotate("submit"):
            while k < len(due) and t0 + due[k] <= now:
                img = mix.next_frame()
                sent.append((img, t0 + due[k], engine.submit(mix.image(img))))
                k += 1
        if engine.queue_depth:
            finished = timed_step(engine, steps)
            now = time.perf_counter()
            for r in finished:
                ready[r.rid] = now
        elif k < len(due):
            with annotate("wait_arrival"):
                time.sleep(max(0.0, t0 + due[k] - time.perf_counter()))
        else:
            break
        if time.perf_counter() > t0 + seconds + DRAIN_LIMIT_S:
            break
    end = time.perf_counter()
    return [(img, t, r, ready.get(r.rid, end) - t)
            for img, t, r in sent], t0


def closed_loop(engine, mix: Mix, seconds: float, slots: int,
                steps: list[Step]):
    """Full steps back to back until the first step boundary after
    ``seconds``; returns [(image id, request)] served and the window."""
    target = mix.queue_target(slots)
    queued: list = []

    def top_up():
        with annotate("submit"):
            while engine.queue_depth < target:
                img = mix.next_frame()
                queued.append((img, engine.submit(mix.image(img))))

    top_up()
    t0 = time.perf_counter()
    while True:
        timed_step(engine, steps)
        if time.perf_counter() - t0 >= seconds:
            break
        top_up()
    t1 = time.perf_counter()
    return [(img, r) for img, r in queued if r.done], t0, t1


# -- correctness ------------------------------------------------------------


def rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def compare(net, params, mix: Mix, answers, precision: str) -> float:
    """Largest relative error of the served logits against the plain
    reference, one image at a time."""
    import jax.numpy as jnp
    worst = 0.0
    for img, y in answers:
        ref = np.asarray(model.reference(
            net, params, jnp.asarray(mix.image(img)[None]), precision))[0]
        worst = max(worst, rel_err(np.asarray(y), ref))
    return worst


# -- one run ----------------------------------------------------------------


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; a missing answer counts as infinite."""
    v = sorted(values)
    return v[max(0, int(np.ceil(q / 100 * len(v))) - 1)]


def start_trace(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@dataclasses.dataclass
class Served:
    """One run's window, before the comparison."""

    net: model.Net
    params: dict
    mix: Mix
    answers: list                         # (image id, served logits)
    attempted: int
    missing: int
    failed: int
    metrics: dict
    device: dict
    breakdown: dict | None


def serve(found: dict, seed: int, seconds: float, trace: bool,
          device: dict) -> Served:
    """Set-up and the measured window of one run."""
    import jax
    cell, config = found["cell"], found["config"]
    net = model.Net.from_config(config)
    slots = int(config["slots"])
    with open(os.path.join(found["root"], "bench", "peaks.json")) as f:
        peak = json.load(f).get(device["kind"], {})

    params, offsets_px = model.build_weights(net, seed)
    jax.block_until_ready(params)
    log(f"weights built at {time.perf_counter() - PROCESS_START:.2f} s; "
        f"mean |offset| px {np.round(np.asarray(offsets_px), 3).tolist()}")
    mix = Mix.from_file(found["traffic"], seed,
                        (net.img_size, net.img_size, net.in_channels))
    engine = program.make_engine(net, params, slots, traced=trace)
    warm_up(engine, mix, slots)
    log(f"warm-up done at {time.perf_counter() - PROCESS_START:.2f} s")
    failed0 = program.failures(engine)
    hits0, lookups0 = program.cache_counts(engine)
    if engine.tracer.enabled:
        engine.tracer.clear()
    steps: list[Step] = []
    trace_dir = os.path.join(TRACE_DIR, cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        start_trace(trace_dir)
    metrics: dict[str, float] = {}
    with annotate("window"):
        if mix.arrivals == "poisson":
            t_first = time.perf_counter()
            sent, t0 = open_loop(engine, mix, seconds, steps)
            setup_s = t0 - PROCESS_START
            lat = [latency for *_, latency in sent]
            answers = [(img, r.result()) for img, _, r, _ in sent
                       if r.done and r.error is None]
            attempted = len(sent)
            missing = attempted - len(answers)
            t1 = max([s.start + s.dur for s in steps] or [t_first])
            metrics["p50_latency_s"] = percentile(lat, 50)
            metrics["p90_latency_s"] = percentile(lat, 90)
            late = [r.submit_s - due for _, due, r, _ in sent]
            log(f"{attempted} requests, {len(steps)} steps of widths "
                f"{[s.width for s in steps]}; generator late p50 "
                f"{statistics.median(late):.4f} s, max {max(late):.4f} s")
        else:
            served, t0, t1 = closed_loop(engine, mix, seconds, slots, steps)
            setup_s = t0 - PROCESS_START
            answers = [(img, r.result()) for img, r in served
                       if r.error is None]
            attempted = sum(s.width for s in steps)
            missing = attempted - len(answers)
            metrics["images_per_s"] = len(answers) / (t1 - t0)
            log(f"{attempted} images in {len(steps)} steps, window "
                f"{t1 - t0:.3f} s")
    if trace:
        jax.profiler.stop_trace()
    metrics["setup_s"] = setup_s
    failed = program.failures(engine) - failed0 + missing
    hits, lookups = program.cache_counts(engine)
    window = Window(net=net, slots=slots, seconds=seconds, steps=steps,
                    window_s=t1 - t0, images=sum(s.width for s in steps),
                    cache=(hits - hits0, lookups - lookups0),
                    spans=program.spans(engine), peak=peak)
    out_device = dict(device, memory_peak_bytes=memory_peak_bytes())
    del engine
    gc.collect()

    breakdown = None
    if trace:
        path = xtrace.find_xplane(trace_dir)
        window.trace = xtrace.load(path) if path else None
        breakdown = reduce_trace(window, out_device)
        names = [m["name"] for m in found["metrics"]["per_layer"]]
        metrics = {}
        for name in names:
            v = load_reader(found["root"], name)(window)
            if v is not None:
                metrics[name] = v

    return Served(net, params, mix, answers, attempted, missing, failed,
                  metrics, out_device, breakdown)


def run_cell(found: dict, seed: int, seconds: float, trace: bool,
             device: dict) -> dict:
    """One run: its window, then the comparison that decides
    ``correct``; the result line as a dict."""
    s = serve(found, seed, seconds, trace, device)
    config = found["config"]
    limit = float(config["correct"]["max_rel_err"])
    worst = compare(s.net, s.params, s.mix, s.answers, config["precision"])
    checks = {"max_rel_err": {"value": worst, "limit": limit},
              "answers_missing": {"value": s.missing, "limit": 0}}
    units = {m["name"]: m["unit"] for kind in found["metrics"].values()
             for m in kind}
    result = {
        "correct": bool(worst <= limit and s.missing == 0),
        "attempted": int(s.attempted),
        "failed": int(s.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in s.metrics.items()},
        "device": s.device,
    }
    if s.breakdown is not None:
        result["breakdown"] = s.breakdown
    result["checks"] = checks
    return result


def reduce_trace(window: Window, device: dict) -> dict:
    """Busy and window seconds into ``device``; the breakdown."""
    tr = window.trace
    notes = tr.annotations if tr is not None else []
    win = [e for e in notes if e.name == "bench.window"]
    if tr is None or not tr.device_ops or not win:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = win[0].start_ns, win[0].end_ns
    window.trace_bounds = (lo, hi)
    busy = [xtrace.busy_ns(evs, lo, hi) for evs in tr.device_ops.values()]
    device["busy_s"] = sum(busy) / len(busy) / 1e9
    device["window_s"] = (hi - lo) / 1e9
    by_module: dict[str, float] = {}
    for e in tr.modules:
        if lo <= e.start_ns < hi:
            by_module[e.name] = by_module.get(e.name, 0.0) + e.dur_ns / 1e9
    first = next(iter(tr.device_ops.values()))
    gap_list = [(b - a, xtrace.label_at(notes, (a + b) // 2))
                for a, b in xtrace.gaps(first, lo, hi)]
    gap_list.sort(reverse=True)
    return {
        "device_ops": sorted(([k, v] for k, v in by_module.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[label, ns / 1e9] for ns, label in gap_list[:10]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    found = load_cell(ROOT, args.workload)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      found["config"]["precision"])
    cache_dir = program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    try:
        device = device_check(int(found["cell"]["chips"]), peaks)
    except NoDevice as e:
        log(f"FAILED: {e}")
        return 1
    log(f"{args.workload} seed {args.seed} on {device['kind']} "
        f"x{device['count']}; compile cache {cache_dir}")
    result = run_cell(found, args.seed, args.seconds, bool(args.trace),
                      device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
