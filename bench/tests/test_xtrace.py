"""Trace-to-metric reduction, on hand-made events and on a small trace
recorded on a TPU v5e (``data/tiny.xplane.pb.gz``: one traced serving
step of a VGG19-8 at width 0.125 and 32x32, two images)."""

import gzip
import os

import pytest

from bench import model, run, xtrace
from bench.kernels import dcn_fused_batch as kernel

E = xtrace.Event
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tiny.xplane.pb.gz")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load_metric(name):
    return run.load_reader(run.ROOT, name)


def test_busy_union_and_gaps():
    evs = [E("a", 10, 10), E("b", 15, 10), E("c", 40, 5), E("d", 0, 3)]
    # [0,3) [10,25) [40,45), clipped to [2, 42)
    assert xtrace.busy_ns(evs, 2, 42) == 1 + 15 + 2
    assert xtrace.gaps(evs, 2, 42) == [(3, 10), (25, 40)]
    assert xtrace.gaps([], 0, 5) == [(0, 5)]


def test_label_at_takes_innermost_open_span():
    notes = [E("bench.window", 0, 100), E("bench.step", 10, 20),
             E("bench.submit", 50, 5)]
    assert xtrace.label_at(notes, 15) == "bench.step"
    assert xtrace.label_at(notes, 40) == "bench.window"
    assert xtrace.label_at(notes, 200) == "outside"


def window_with(events, steps, net=None, chips=1, planes=None):
    net = net or model.Net("segnet", 8, "dcn2", 224, 11)
    w = run.Window(layers=model.layers(net),
                   flops_per_image=model.forward_flops(net), slots=8,
                   seconds=1.0, steps=[run.Step(0.0, 1.0, n) for n in steps],
                   chips=chips, window_s=1.0, images=sum(steps), peak=PEAK)
    w.trace = xtrace.Trace(planes or {"/device:TPU:0": events}, [])
    w.trace_bounds = (0, 10**12)
    return w


def test_roofline_arithmetic_by_hand():
    net = model.Net("segnet", 8, "dcn2", 224, 11)
    ns = 2 * 10**9                   # 2 s of kernel time for one step of 8
    op = kernel.TRACE_NAME + ".1 = f32[8,64,64] custom-call(s32[8] %a)"
    w = window_with([E(op, 0, ns), E("%copy.1 = f32[8] copy(%a)", 0, ns),
                     E(kernel.TRACE_NAME + ".2 = f32[8] copy(%b)", 0, ns)],
                    [8], net)
    least = 0.0
    for l in model.layers(net):
        if l.deform:
            f = kernel.flops(l.hw, l.c_in, l.c_out, 3, 8)
            b = kernel.bytes_moved(l.hw, l.c_in, l.c_out, 3, 8)
            least += max(f / 197e12, b / 819e9)
    # Layer 31 alone: 31,444,697,088 FLOPs -> 159.6 us; 321,274,112 B
    # -> 392.3 us, so that call is memory-bound.
    assert 321_274_112 / 819e9 > 31_444_697_088 / 197e12
    got = load_metric("dcn_fused_batch_roofline")(w)
    assert got == pytest.approx(100 * least / 2.0)
    assert load_metric("dcn_fused_batch_ms_per_image")(w) == \
        pytest.approx(2000.0 / 8)


def test_idle_share_by_hand():
    w = window_with([E("a", 0, 100), E("b", 50, 100), E("c", 400, 100)],
                    [8])
    w.trace_bounds = (0, 1000)
    assert load_metric("device_idle_frac")(w) == pytest.approx(0.75)


def test_replica_idle_frac_max_reads_the_idler_plane():
    # Plane 0 busy 600 of 1000 ns, plane 1 busy 100: idle 0.4 and 0.9.
    planes = {"/device:TPU:0": [E("a", 0, 400), E("b", 500, 200)],
              "/device:TPU:1": [E("c", 100, 60), E("d", 150, 50)]}
    w = window_with([], [8], chips=2, planes=planes)
    w.trace_bounds = (0, 1000)
    assert load_metric("replica_idle_frac_max")(w) == pytest.approx(0.9)
    assert load_metric("device_idle_frac")(w) == pytest.approx(0.65)


def test_mfu_is_a_share_of_the_cells_chips():
    one = load_metric("mfu")(window_with([], [8]))
    assert one == 100.0 * 8 * model.forward_flops(
        model.Net("segnet", 8, "dcn2", 224, 11)) / 197e12
    four = load_metric("mfu")(window_with([], [8], chips=4))
    assert four == one / 4


def test_readers_without_a_trace_return_nothing():
    w = window_with([], [8])
    w.trace, w.trace_bounds = None, None
    for name in ("device_idle_frac", "dcn_fused_batch_roofline",
                 "dcn_fused_batch_ms_per_image", "replica_idle_frac_max"):
        assert load_metric(name)(w) is None


def test_recorded_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f:
        path.write_bytes(f.read())
    tr = xtrace.load(str(path))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    ops = tr.device_ops["/device:TPU:0"]
    kern = [e for e in ops if kernel.in_trace(e.name)]
    # One traced step of VGG19-8: one kernel call per deformable layer.
    assert len(kern) == 8
    steps = [e for e in tr.annotations if e.name == "bench.step"]
    assert len(steps) == 1
    lo, hi = steps[0].start_ns, steps[0].end_ns
    busy = xtrace.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    merged = xtrace.union((e.start_ns, e.end_ns) for e in ops)
    assert all(a < b for a, b in merged)
    assert all(merged[i][1] < merged[i + 1][0]
               for i in range(len(merged) - 1))
    assert "jit__dcn_fused_batch_jit" in {m.name for m in tr.modules}
