"""Telemetry layer (`repro.obs`): tracer, metrics, Chrome-trace export.

Unit-level contracts (disabled-path no-op, nested/threaded span
parenting, percentile edge cases, registry typing) plus the integration
acceptance of ISSUE 7: a real executor/serving run records the expected
span names, exports schema-valid Perfetto JSON, and the engine's
``metrics_snapshot()`` reproduces every counter the benchmark gates.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.core.deform import DeformableConvParams, randomize_offset_conv
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.obs import (Histogram, MetricsRegistry, Span, Stopwatch,
                       Tracer, chrome_trace, default_registry, get_tracer,
                       global_tracer, install_lowering_listener,
                       jax_lowerings, percentile, use_tracer,
                       validate_chrome_trace, write_chrome_trace)
from repro.runtime import GraphConfig, build_graph
from repro.runtime.graph import FusedGroup, partition_graph_cached
from repro.runtime.fused_exec import run_graph
from repro.runtime.trace import OverlapSpans
from repro.serving import DcnServingEngine


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nested_span_parenting(self):
        tr = Tracer(enabled=True)
        with tr.span("outer", k=1):
            with tr.span("inner"):
                pass
            with tr.span("inner2"):
                pass
        spans = {s.name: s for s in tr.snapshot()}
        assert set(spans) == {"outer", "inner", "inner2"}
        outer = spans["outer"]
        assert outer.parent is None and outer.attrs == {"k": 1}
        assert spans["inner"].parent == outer.sid
        assert spans["inner2"].parent == outer.sid
        # children finish (and record) before the enclosing span
        assert spans["inner"].dur <= outer.dur

    def test_threaded_spans_are_roots_on_own_track(self):
        tr = Tracer(enabled=True)

        def worker():
            with tr.span("worker.prepass"):
                pass

        with tr.span("main.execute"):
            t = threading.Thread(target=worker, name="stager")
            t.start()
            t.join()
        spans = {s.name: s for s in tr.snapshot()}
        w, m = spans["worker.prepass"], spans["main.execute"]
        # parenting never crosses threads: the worker span is a root on
        # its own thread track even though it ran inside main.execute.
        assert w.parent is None
        assert w.tid != m.tid
        assert w.thread_name == "stager"

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("a", k=1) as sp:
            sp.set(more=2)
        tr.instant("marker")
        with tr.timed("b") as sw:
            pass
        assert len(tr) == 0
        assert tr.snapshot() == []
        # span() hands back one shared null singleton: no allocation
        assert tr.span("x") is tr.span("y")
        # ...but timed() still measured
        assert isinstance(sw, Stopwatch) and sw.dur >= 0.0

    def test_disabled_span_overhead_bounded(self):
        """ISSUE 7 acceptance: the disabled path must be a near-free
        no-op. 200k disabled spans in well under a second (~µs each)
        is a generous ceiling that still catches an accidental clock
        read or allocation per call."""
        tr = Tracer(enabled=False)
        t0 = time.perf_counter()
        for _ in range(200_000):
            with tr.span("hot"):
                pass
        wall = time.perf_counter() - t0
        assert len(tr) == 0
        assert wall < 1.0

    def test_timed_measures_duration_when_disabled(self):
        tr = Tracer(enabled=False)
        with tr.timed("prepass", unit=3) as sw:
            time.sleep(0.002)
        assert sw.dur >= 0.002
        assert sw.name == "prepass" and sw.attrs == {"unit": 3}
        assert len(tr) == 0        # measured, not recorded

    def test_use_tracer_is_thread_local(self):
        tr = Tracer(enabled=True)
        assert get_tracer() is global_tracer()
        seen = {}

        def worker():
            seen["worker"] = get_tracer()

        with use_tracer(tr):
            assert get_tracer() is tr
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            inner = Tracer(enabled=True)
            with use_tracer(inner):
                assert get_tracer() is inner
            assert get_tracer() is tr
        assert get_tracer() is global_tracer()
        # the override never leaks onto other threads
        assert seen["worker"] is global_tracer()

    def test_spans_since_and_clear(self):
        tr = Tracer(enabled=True)
        with tr.span("a"):
            pass
        mark = len(tr)
        with tr.span("b"):
            pass
        assert [s.name for s in tr.spans_since(mark)] == ["b"]
        tr.clear()
        assert len(tr) == 0

    def test_concurrent_recording_is_complete(self):
        tr = Tracer(enabled=True)
        n_threads, per_thread = 8, 50

        def worker(t):
            for k in range(per_thread):
                with tr.span(f"w{t}", k=k):
                    pass

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = tr.snapshot()
        assert len(spans) == n_threads * per_thread
        assert len({s.sid for s in spans}) == len(spans)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class TestPercentile:
    def test_empty_is_none(self):
        assert percentile([], 50) is None

    def test_singleton_is_the_sample(self):
        for q in (0, 50, 99, 100):
            assert percentile([0.7], q) == 0.7

    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.default_rng(0)
        xs = rng.normal(size=37).tolist()
        for q in (0, 10, 50, 90, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), abs=1e-12)


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("c", help="a counter")
        c.inc()
        c.inc(3)
        c.bump()                       # pre-registry alias
        assert c.value == c.count == 5
        g = reg.gauge("g")
        g.set(2.5)
        g.add(0.5)
        assert g.value == 3.0
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        assert h.count == 3 and h.mean == 2.0
        snap = reg.snapshot()
        assert snap["c"] == 5 and snap["g"] == 3.0
        assert snap["h"]["count"] == 3 and snap["h"]["p50"] == 2.0

    def test_get_or_create_identity_and_kind_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_register_external_metric(self):
        reg = MetricsRegistry()
        h = Histogram("lat")
        reg.register("lat", h)
        reg.register("lat", h)        # same object: idempotent
        with pytest.raises(ValueError, match="already registered"):
            reg.register("lat", Histogram("other"))
        assert reg.get("lat") is h
        assert "lat" in reg.names()

    def test_empty_histogram_summary_is_none(self):
        s = Histogram("h").summary()
        assert s == {"count": 0, "mean": None, "p50": None, "p95": None,
                     "p99": None}


# ---------------------------------------------------------------------------
# OverlapSpans re-derivation
# ---------------------------------------------------------------------------

class TestOverlapSpans:
    def _span(self, name, dur, **attrs):
        return Span(name=name, ts=0.0, dur=dur, attrs=attrs)

    def test_from_spans_and_device_split(self):
        o = OverlapSpans.from_spans([
            self._span("prepass", 0.5),
            self._span("prepass.wait", 0.2),
            self._span("prepass.schedule", 0.3, backend="host"),
            self._span("prepass.schedule", 0.1, backend="device"),
            self._span("dispatch.batched", 9.0),   # unrelated: ignored
        ])
        assert o.prepass_s == pytest.approx(0.5)
        assert o.prepass_wait_s == pytest.approx(0.2)
        assert o.schedule_s == pytest.approx(0.4)
        assert o.schedule_device_s == pytest.approx(0.1)

    def test_merge_accumulates(self):
        a = OverlapSpans.from_spans([self._span("prepass", 1.0)])
        b = OverlapSpans.from_spans(
            [self._span("prepass.schedule", 0.25, backend="device")])
        a.merge(b)
        assert a.prepass_s == pytest.approx(1.0)
        assert a.schedule_s == pytest.approx(0.25)
        assert a.schedule_device_s == pytest.approx(0.25)

    def test_add_span_accepts_stopwatch(self):
        """timed() degrades to Stopwatch when tracing is off; the
        overlap accounting must keep working on it."""
        o = OverlapSpans()
        with Stopwatch("prepass") as sw:
            time.sleep(0.001)
        o.add_span(sw)
        assert o.prepass_s == pytest.approx(sw.dur)


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

class TestChromeTraceExport:
    def _trace(self):
        tr = Tracer(enabled=True)
        with tr.timed("serve.step", step=0, width=2) as sp:
            with tr.span("dispatch.batch_fused", grid_rows=8):
                pass
            sp.set(dispatches=4, dram_bytes=1024)
        tr.instant("serve.submit", rid=1)

        def worker():
            with tr.span("prepass", unit=0):
                pass

        t = threading.Thread(target=worker, name="stager")
        t.start()
        t.join()
        return tr

    def test_schema_valid_and_track_layout(self):
        tr = self._trace()
        doc = chrome_trace(tr)
        assert validate_chrome_trace(doc) == []
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        names = {(e["pid"], e["name"], e["args"]["name"]) for e in meta}
        assert (0, "process_name", "host threads") in names
        assert (1, "process_name", "engine steps") in names
        assert (1, "thread_name", "step 0") in names
        assert any(n == (0, "thread_name", "stager") for n in names)
        # serve.step is duplicated onto the per-step track (pid 1)
        steps = [e for e in evs
                 if e["ph"] == "X" and e["name"] == "serve.step"]
        assert sorted(e["pid"] for e in steps) == [0, 1]
        assert all(e["args"]["dispatches"] == 4 for e in steps)
        # complete events: µs timebase relative to the earliest span
        xs = [e for e in evs if e["ph"] == "X"]
        assert min(e["ts"] for e in xs) == 0.0
        assert all(e["dur"] >= 0 for e in xs)
        # compact thread ids in first-appearance order
        tids = {e["tid"] for e in xs if e["pid"] == 0}
        assert tids == set(range(len(tids)))
        inst = [e for e in evs if e["ph"] == "i"]
        assert len(inst) == 1 and inst[0]["name"] == "serve.submit"

    def test_write_chrome_trace_roundtrip(self, tmp_path):
        path = tmp_path / "trace.json"
        doc = write_chrome_trace(str(path), self._trace())
        with open(path) as f:
            loaded = json.load(f)
        assert loaded == json.loads(json.dumps(doc))
        assert validate_chrome_trace(loaded) == []

    def test_validate_rejects_malformed(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"no": "events"}) != []
        bad = {"traceEvents": [
            {"name": "x", "ph": "X", "ts": -1.0, "dur": 1.0,
             "pid": 0, "tid": 0},
            {"name": "y", "ph": "Z", "pid": 0, "tid": 0},
            {"ph": "X", "ts": 0.0, "dur": "oops", "pid": 0, "tid": "a"},
        ]}
        problems = validate_chrome_trace(bad)
        assert len(problems) >= 3

    def test_empty_tracer_still_valid(self):
        doc = chrome_trace(Tracer(enabled=True))
        assert validate_chrome_trace(doc) == []


# ---------------------------------------------------------------------------
# Integration: executor + serving runs through the telemetry layer
# ---------------------------------------------------------------------------

def _dcn_case(n_deform=2, img=16, seed=2, offset_scale=2.0):
    cfg = DcnNetConfig(name="vgg19", n_deform=n_deform, img_size=img,
                       width_mult=0.125, num_classes=4)
    key = jax.random.PRNGKey(seed)
    params = init_dcn_net(key, cfg)
    params["convs"] = [
        randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                              offset_scale / p.w.shape[2])
        if isinstance(p, DeformableConvParams) else p
        for i, p in enumerate(params["convs"])]
    return cfg, params


@pytest.fixture(scope="module")
def dcn_setup():
    return _dcn_case()


class TestExecutorTelemetry:
    def test_run_graph_records_expected_spans(self, dcn_setup):
        cfg, params = dcn_setup
        graph = build_graph(cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 16, 16, 3)).astype(np.float32))
        tr = Tracer(enabled=True)
        y, trace = run_graph(params["convs"], graph, x,
                             config=GraphConfig(tile=4,
                                                use_schedule_cache=False),
                             return_trace=True, tracer=tr)
        jax.block_until_ready(y)
        names = {s.name for s in tr.snapshot()}
        assert {"prepass", "prepass.wait", "prepass.tdt",
                "prepass.schedule", "pack"} <= names
        assert any(n.startswith("dispatch.") for n in names)
        # the trace's overlap accounting is re-derived from these spans
        derived = OverlapSpans.from_spans(tr.snapshot())
        assert trace.overlap.prepass_s == pytest.approx(
            derived.prepass_s)
        assert trace.overlap.schedule_s == pytest.approx(
            derived.schedule_s)
        # ...and the whole run exports as loadable Perfetto JSON
        assert validate_chrome_trace(chrome_trace(tr)) == []

    def test_disabled_tracer_keeps_overlap_exact(self, dcn_setup):
        """With tracing off the executors still measure overlap via
        Stopwatch degradation: zero spans, non-zero accounting."""
        cfg, params = dcn_setup
        graph = build_graph(cfg)
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 16, 16, 3)).astype(np.float32))
        tr = Tracer(enabled=False)
        _, trace = run_graph(params["convs"], graph, x,
                             config=GraphConfig(tile=4),
                             return_trace=True, tracer=tr)
        assert len(tr) == 0
        assert trace.overlap.prepass_s > 0.0

    def test_registry_counts_host_schedule_builds(self, dcn_setup):
        """The smoke-gated counter lives in the default registry and
        stays flat on the device-scheduling hot path."""
        cfg, params = dcn_setup
        graph = build_graph(cfg)
        x = jnp.asarray(np.random.default_rng(2).normal(
            size=(1, 16, 16, 3)).astype(np.float32))
        reg = default_registry()
        assert reg.get("host_schedule_builds") is \
            scheduler.host_schedule_builds
        gcfg = GraphConfig(tile=4, dispatch="batch_fused",
                           schedule_backend="device",
                           use_schedule_cache=False)
        run_graph(params["convs"], graph, x, config=gcfg)  # warm compile
        c0 = reg.snapshot()["host_schedule_builds"]
        y = run_graph(params["convs"], graph, x, config=gcfg)
        jax.block_until_ready(y)
        assert reg.snapshot()["host_schedule_builds"] == c0


class TestServingTelemetry:
    def _images(self, n, seed=0):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(n, 16, 16, 3)).astype(np.float32)

    def _run(self, dcn_setup, tracer):
        cfg, params = dcn_setup
        eng = DcnServingEngine(params, cfg, graph=GraphConfig(tile=4),
                               slots=4, tracer=tracer)
        for i in range(3):
            eng.submit(self._images(1, seed=i))
        eng.submit(self._images(1, seed=0))     # replay: cache hit
        eng.step()
        eng.drain()
        return eng

    def test_serving_spans_timeline_and_export(self, dcn_setup):
        tr = Tracer(enabled=True)
        eng = self._run(dcn_setup, tr)
        names = {s.name for s in tr.snapshot()}
        assert {"serve.submit", "serve.admit", "serve.step",
                "serve.drain"} <= names
        steps = [s for s in tr.snapshot() if s.name == "serve.step"]
        assert steps and all("dispatches" in s.attrs
                             and "dram_bytes" in s.attrs for s in steps)
        # per-step timeline mirrors the spans
        assert len(eng.timeline) == len(steps) == eng.steps
        for entry in eng.timeline:
            assert {"step", "width", "wall_s", "dispatches",
                    "dram_bytes", "image_hits",
                    "schedule_backend"} <= set(entry)
            assert entry["dispatches"] > 0 and entry["wall_s"] > 0
        doc = chrome_trace(tr)
        assert validate_chrome_trace(doc) == []
        # every serving step shows up on the engine-steps process
        pid1 = [e for e in doc["traceEvents"]
                if e.get("pid") == 1 and e.get("ph") == "X"]
        assert len(pid1) == len(steps)

    def test_metrics_snapshot_reproduces_stats(self, dcn_setup):
        """ISSUE 7 acceptance: every counter the smoke gates read off
        ``stats`` is reproduced by ``metrics_snapshot()``."""
        eng = self._run(dcn_setup, Tracer(enabled=True))
        s = eng.stats
        snap = eng.metrics_snapshot()
        assert snap["serving.requests"] == s["requests"]
        assert snap["serving.images"] == s["images"]
        assert snap["serving.steps"] == s["steps"]
        assert snap["serving.kernel_dispatches"] == s["kernel_dispatches"]
        assert snap["schedule_cache.hits"] == s["schedule_cache_hits"]
        assert snap["schedule_cache.misses"] == s["schedule_cache_misses"]
        assert snap["schedule_cache.image_hit_rate"] == pytest.approx(
            s["image_hit_rate"])
        assert snap["serving.host_schedule_builds"] == \
            s["host_schedule_builds"]
        assert snap["serving.dispatches_per_batch"] == pytest.approx(
            s["dispatches_per_batch"])
        assert snap["serving.queue_depth"] == s["queue_depth"] == 0
        assert snap["serving.latency_s"]["count"] == \
            s["latency"]["count"] == s["requests"]

    def test_disabled_tracer_serving_stays_quiet(self, dcn_setup):
        tr = Tracer(enabled=False)
        eng = self._run(dcn_setup, tr)
        assert len(tr) == 0
        assert eng.timeline == []
        assert eng.stats["requests"] == 4


# ---------------------------------------------------------------------------
# Profiler mirror, step-level spans, lowering counter
# ---------------------------------------------------------------------------

MIRRORED = ("serve.step", "prepass.wait", "prepass.stage1", "exec.segment",
            "serve.fetch")


def _one_step(dcn_setup, tracer, width=2, seed=0):
    cfg, params = dcn_setup
    eng = DcnServingEngine(params, cfg, graph=GraphConfig(tile=4),
                           slots=4, tracer=tracer)
    rng = np.random.default_rng(seed)
    for _ in range(width):
        eng.submit(rng.normal(size=(16, 16, 3)).astype(np.float32))
    assert len(eng.step()) == width
    return eng


def _host_event_names(log_dir) -> set[str]:
    from jax.profiler import ProfileData
    path = next(log_dir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    return {e.name for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


class TestProfilerMirror:
    def test_enabled_tracer_annotates_profile(self, tmp_path):
        tr = Tracer(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.timed("serve.step", step=7, width=2):
                with tr.span("pack", dispatch="batch_fused", hits=None):
                    pass
        finally:
            jax.profiler.stop_trace()
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
        evs = {e.name: dict(e.stats) for plane in pd.planes
               if plane.name == "/host:CPU"
               for line in plane.lines for e in line.events}
        # bare span names; scalar attrs as stats, None left out
        assert evs["serve.step"]["step_num"] == 7
        assert evs["serve.step"]["width"] == 2
        assert evs["pack"] == {"dispatch": "batch_fused"}

    @pytest.mark.parametrize("enabled", [True, False])
    def test_engine_step_spans_on_host_plane(self, dcn_setup, tmp_path,
                                             enabled):
        _one_step(dcn_setup, Tracer(enabled=True))        # compile first
        jax.profiler.start_trace(str(tmp_path))
        try:
            _one_step(dcn_setup, Tracer(enabled=enabled), seed=1)
        finally:
            jax.profiler.stop_trace()
        found = _host_event_names(tmp_path) & set(MIRRORED)
        assert found == (set(MIRRORED) if enabled else set())

    def test_disabled_timed_opens_no_annotation(self):
        tr = Tracer(enabled=False)
        sw = tr.timed("serve.step", step=0)
        assert isinstance(sw, Stopwatch)
        assert tr.span("serve.fetch") is tr.span("exec.segment")


class TestStepSpans:
    def test_one_fetch_and_a_span_per_segment_and_group(self, dcn_setup):
        tr = Tracer(enabled=True)
        eng = _one_step(dcn_setup, tr)
        segments = partition_graph_cached(
            eng.net_graph, eng._step_cfg.onchip_budget_bytes)
        groups = [s for s in segments if isinstance(s, FusedGroup)]
        spans = tr.snapshot()

        def named(name):
            return [s for s in spans if s.name == name]

        assert len(named("serve.fetch")) == 1
        seg = named("exec.segment")
        assert sorted(s.attrs["segment"] for s in seg) == \
            list(range(len(segments)))
        assert sum(s.attrs["kind"] == "group" for s in seg) == len(groups)
        assert {s.attrs["kind"] for s in seg} <= {"group", "pool",
                                                   "upsample"}
        stage1 = named("prepass.stage1")
        assert len(stage1) == len(groups)
        assert [s.attrs["layers"] for s in stage1] == \
            [g.n_layers for g in groups]
        assert all(s.attrs["batch"] == 2 for s in stage1)
        # stage 1 is not part of the schedule/pack spans the benchmark's
        # prepass metric sums
        by_sid = {s.sid: s for s in spans}
        for s in stage1:
            assert by_sid.get(s.parent) is None or by_sid[
                s.parent].name not in ("prepass.schedule", "pack")
        # the main thread's spans nest under the step
        step = named("serve.step")[0]
        assert named("serve.fetch")[0].tid == step.tid
        assert all(s.tid == step.tid for s in seg)

    def test_staging_worker_spans_reach_the_tracer(self, dcn_setup):
        cfg, params = dcn_setup
        x = jnp.asarray(np.random.default_rng(3).normal(
            size=(2, 16, 16, 3)).astype(np.float32))
        tr = Tracer(enabled=True)
        run_graph(params["convs"], build_graph(cfg), x,
                  config=GraphConfig(tile=4, dispatch="batch_fused",
                                     staging_depth=2,
                                     use_schedule_cache=False),
                  tracer=tr)
        main = threading.get_ident()
        packed = [s for s in tr.snapshot()
                  if s.name == "pack.batch_schedules"]
        assert packed and all(s.tid != main for s in packed)


class TestLowerings:
    def test_first_step_lowers_repeat_step_does_not(self):
        # A plane no other test serves: its first step must lower.
        setup = _dcn_case(n_deform=1, img=24, seed=5)
        cfg, params = setup
        tr = Tracer(enabled=True)
        eng = DcnServingEngine(params, cfg, graph=GraphConfig(tile=4),
                               slots=4, tracer=tr)
        rng = np.random.default_rng(0)

        def step_lowerings():
            mark = len(tr)
            for _ in range(2):
                eng.submit(rng.normal(size=(24, 24, 3)).astype(np.float32))
            assert len(eng.step()) == 2
            return [s for s in tr.spans_since(mark) if s.name == "jax.lower"]

        first = step_lowerings()
        assert first and all(s.dur >= 0.0 for s in first)
        assert step_lowerings() == []
        lowered = [s for s in tr.snapshot() if s.name == "jax.lower"]
        assert eng.stats["compiles"] == len(lowered) == len(first)
        assert eng.metrics_snapshot()["serving.compiles"] == len(first)

    def test_listener_is_idempotent_and_counts_without_tracer(self):
        install_lowering_listener()
        install_lowering_listener()
        x = jnp.arange(5.0)
        c0 = jax_lowerings.count
        tr = Tracer(enabled=False)
        with use_tracer(tr):
            jax.jit(lambda v: v * 3 + 1)(x)
        # one program lowered, counted once, recorded nowhere
        assert jax_lowerings.count - c0 == 1
        assert len(tr) == 0

    def test_record_parents_on_open_span(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            tr.record("jax.lower", 0.25, what="x")
        spans = {s.name: s for s in tr.snapshot()}
        low = spans["jax.lower"]
        assert low.parent == spans["outer"].sid
        assert low.dur == 0.25 and low.attrs == {"what": "x"}
        Tracer(enabled=False).record("jax.lower", 1.0)
