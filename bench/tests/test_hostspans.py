"""Device idle time put down to the program's main-thread spans
(``bench/hostspans.py``), on hand-made events and on traces recorded on a
TPU v5e: ``data/tiny.xplane.pb.gz`` from a program that mirrors no spans,
``data/spans.xplane.pb.gz`` from one that does (one traced serving step
of a VGG19-8 at width 0.125 and 32x32, two images, through
``bench/run.py``'s own window)."""

import gzip
import os

import pytest

from bench import hostspans, model, run, xtrace

E = xtrace.Event
DATA = os.path.join(os.path.dirname(__file__), "data")
OLD_FIXTURE = os.path.join(DATA, "tiny.xplane.pb.gz")
SPANS_FIXTURE = os.path.join(DATA, "spans.xplane.pb.gz")
IDLE = ("idle_waiting_schedule_frac", "idle_dispatching_frac")
PER_IMAGE = {"stage1_ms_per_image": "prepass.stage1",
             "tdt_ms_per_image": "prepass.tdt",
             "exec_ms_per_image": "exec.segment",
             "fetch_ms_per_image": "serve.fetch"}


def load_metric(name):
    return run.load_reader(run.ROOT, name)


def window(bounds=None, spans=(), images=4):
    net = model.Net("vgg19", 8, "dcn2", 32, 10)
    w = run.Window(layers=model.layers(net),
                   flops_per_image=model.forward_flops(net), slots=4,
                   seconds=1.0, steps=[run.Step(0.0, 1.0, images)],
                   window_s=1.0, images=images, spans=list(spans))
    w.trace_bounds = bounds
    return w


def hand_trace():
    # Device busy [10, 20) and [50, 60) of [0, 100): idle 80 ns.
    device = [E("%a", 10, 10), E("%b", 50, 10)]
    staging = [E("prepass.wait", 0, 100)]       # no serve.step: not main
    main = [E("serve.step", 5, 90), E("prepass.wait", 15, 25),
            E("exec.segment", 40, 15), E("serve.fetch", 60, 30)]
    return hostspans.HostTrace((0, 100), device, [staging, main])


def test_idle_split_by_hand():
    split = hostspans.idle_split(hand_trace(), 0, 100)
    # idle [0,10) [20,50) [60,100); wait [15,40) -> [20,40) 20 ns;
    # segment [40,55) -> [40,50) 10; fetch [60,90) 30; the rest of the
    # step [5,10) and [90,95) 10; outside it [0,5) and [95,100) 10.
    assert split == pytest.approx({
        "prepass.wait": 20 / 80, "exec.segment": 10 / 80,
        "serve.fetch": 30 / 80, "serve.step.other": 10 / 80,
        "outside": 10 / 80})
    assert sum(split.values()) == pytest.approx(1.0)


def test_idle_split_needs_the_main_thread_and_idle_time():
    ht = hand_trace()
    assert hostspans.idle_split(ht._replace(lines=[ht.lines[0]]),
                                0, 100) is None
    busy = ht._replace(device=[E("%a", 0, 100)])
    assert hostspans.idle_split(busy, 0, 100) is None
    assert hostspans.idle_split(ht._replace(device=[]), 0, 100) is None


def test_overlap_of_interval_lists():
    assert hostspans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert hostspans.overlap_ns([], [(0, 5)]) == 0


def unpack(fixture, tmp_path):
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    path = out / "host.xplane.pb"
    with gzip.open(fixture, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_trace_without_program_spans_reads_none(tmp_path, monkeypatch):
    path = unpack(OLD_FIXTURE, tmp_path)
    monkeypatch.setattr(hostspans, "TRACE_ROOT", str(tmp_path))
    ht = hostspans.load(path)
    assert ht.window is None and ht.device
    step = [e for e in xtrace.load(path).annotations
            if e.name == "bench.step"][0]
    bounds = (step.start_ns, step.end_ns)
    # no serve.step on the host plane: None, not 0
    assert hostspans.idle_split(ht, *bounds) is None
    for name in IDLE:
        assert load_metric(name)(window(bounds)) is None


def test_idle_readers_want_the_runs_window(tmp_path, monkeypatch):
    monkeypatch.setattr(hostspans, "TRACE_ROOT", str(tmp_path))
    for name in IDLE:
        assert load_metric(name)(window(None)) is None
        assert load_metric(name)(window((0, 1))) is None   # no file


def test_recorded_trace_with_program_spans(tmp_path, monkeypatch):
    path = unpack(SPANS_FIXTURE, tmp_path)
    monkeypatch.setattr(hostspans, "TRACE_ROOT", str(tmp_path))
    ht = hostspans.load(path)
    assert ht.window is not None and ht.device
    main = hostspans.main_thread(ht)
    assert {e.name for e in main} >= {"serve.step", "exec.segment",
                                     "serve.fetch"}
    w = window(ht.window)
    got = {name: load_metric(name)(w) for name in IDLE}
    assert all(v is not None and 0.0 <= v <= 1.0 for v in got.values())
    assert sum(got.values()) <= 1.0
    assert load_metric(IDLE[0])(window((ht.window[0], ht.window[1] + 1))) \
        is None


def test_per_image_span_readers():
    spans = [("prepass.stage1", 0.2), ("prepass.tdt", 0.1),
             ("exec.segment", 0.3), ("exec.segment", 0.1),
             ("serve.fetch", 0.05), ("prepass.schedule", 9.0)]
    w = window(spans=spans, images=4)
    assert load_metric("stage1_ms_per_image")(w) == pytest.approx(50.0)
    assert load_metric("tdt_ms_per_image")(w) == pytest.approx(25.0)
    assert load_metric("exec_ms_per_image")(w) == pytest.approx(100.0)
    assert load_metric("fetch_ms_per_image")(w) == pytest.approx(12.5)
    # the prepass metric still reads only prepass.schedule + pack
    assert load_metric("prepass_ms_per_image")(w) == pytest.approx(2250.0)
    for name in PER_IMAGE:
        assert load_metric(name)(window(spans=[])) is None


def test_compiles_in_window():
    read = load_metric("compiles_in_window")
    # a program without the new spans: nothing to read
    assert read(window(spans=[("serve.step", 1.0)])) is None
    assert read(window(spans=[("serve.fetch", 0.1)])) == 0.0
    assert read(window(spans=[("serve.fetch", 0.1), ("jax.lower", 0.2),
                              ("jax.lower", 0.1)])) == 2.0
