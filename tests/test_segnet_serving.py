"""SegNet-8 DCN-II served through ``DcnServingEngine`` with its defaults.

A small SegNet (the paper's VGG19 encoder and mirrored decoder, last 8
convs deformable, 32x32, width 0.125, 11 classes) with seeded weights
whose offsets average 2 px, as ``bench/model.py`` draws them. Checks the
per-pixel maps of continuous batching (full step, partial step, a
request split across steps, requests coalesced into one step) against
two references, and the ``prepass.alg1`` span and ``alg1_tiles`` counter
that time and count Algorithm 1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import model, program
from repro.models.dcn_models import dcn_net_apply
from repro.obs import Tracer
from repro.serving import DcnServingEngine

NET = model.Net(arch="segnet", n_deform=8, variant="dcn2", img_size=32,
                num_classes=11, width_mult=0.125)
SLOTS = 4

# Relative error (max |served - reference| / max |reference|) allowed per
# image. The served maps read 7e-7 of ``bench/model.py`` at this size:
# f32 throughout, summed in another order over 32 convs, 8 of them
# deformable. The reference's own ``high`` precision (three bf16 passes)
# reads 1.3e-5 to 3.2e-5 of its ``highest`` here, so a path that computed
# below f32 would fail.
REL_TOL = 4e-6


@pytest.fixture(scope="module")
def segnet():
    params, offsets_px = model.build_weights(NET, 1_500_000_017)
    np.testing.assert_allclose(np.asarray(offsets_px), NET.offset_px,
                               rtol=1e-3)
    return program.program_params(params), program.program_config(NET), \
        params


def _images(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, NET.img_size, NET.img_size, NET.in_channels)).astype(np.float32)


def _rel_err(y, ref):
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def _reference(kind, segnet, x):
    pp, cfg, params = segnet
    with jax.default_matmul_precision("highest"):
        if kind == "xla":
            return np.asarray(dcn_net_apply(pp, cfg, jnp.asarray(x),
                                            backend="xla"))
        return np.asarray(model.forward(NET, params, jnp.asarray(x),
                                        "highest"))


@pytest.mark.parametrize("reference", ["xla", "bench"])
def test_continuous_batching_per_pixel_maps(segnet, reference):
    pp, cfg, _ = segnet
    eng = DcnServingEngine(pp, cfg, slots=SLOTS)
    x = _images(7, 5)
    # Step 1 (full): a0 a1 b0 b1. Step 2 (partial, 3 of 4): b2 c0 d0 —
    # b split across steps, three requests coalesced in one.
    parts = {"a": x[0:2], "b": x[2:5], "c": x[5:6], "d": x[6:7]}
    reqs = {k: eng.submit(v) for k, v in parts.items()}
    widths = []
    while eng.queue_depth:
        before = eng.images
        eng.step()
        widths.append(eng.images - before)
    assert widths == [4, 3]
    ref = _reference(reference, segnet, x)
    at = 0
    for k, part in parts.items():
        r = reqs[k]
        assert r.done and r.error is None
        y = r.result()
        assert y.shape == (len(part), NET.img_size, NET.img_size,
                           NET.num_classes)
        for j in range(len(part)):
            assert _rel_err(y[j], ref[at + j]) < REL_TOL, (k, j)
        at += len(part)


def _alg1_expected(trace):
    """Tiles of the (image, group) schedules that missed the cache."""
    return sum(g.grid.num_tiles for g in trace.groups
               if g.schedule_cache_hit is False)


def test_alg1_spans_nest_in_schedule_and_count_misses(segnet):
    pp, cfg, _ = segnet
    tr = Tracer(enabled=True)
    eng = DcnServingEngine(pp, cfg, slots=SLOTS, tracer=tr)
    x = _images(SLOTS, 6)
    for img in x:
        eng.submit(img)
    eng.step()
    spans = tr.snapshot()
    by_sid = {s.sid: s for s in spans}
    alg1 = [s for s in spans if s.name == "prepass.alg1"]
    assert alg1
    assert all(by_sid[s.parent].name == "prepass.schedule" for s in alg1)
    assert {s.attrs["image"] for s in alg1} == set(range(SLOTS))
    missed = _alg1_expected(eng.last_trace)
    assert missed > 0
    assert eng.stats["alg1_tiles"] == missed
    assert sum(s.attrs["tiles"] for s in alg1) == missed
    assert eng.metrics_snapshot()["serving.alg1_tiles"] == missed

    # The same images again: every schedule hits, Algorithm 1 never runs.
    tr.clear()
    for img in x:
        eng.submit(img)
    eng.step()
    assert _alg1_expected(eng.last_trace) == 0
    assert not [s for s in tr.snapshot() if s.name == "prepass.alg1"]
    assert eng.stats["alg1_tiles"] == missed


def test_alg1_counter_and_spans_quiet_without_tracing(segnet):
    pp, cfg, _ = segnet
    tr = Tracer(enabled=False)
    eng = DcnServingEngine(pp, cfg, slots=SLOTS, tracer=tr)
    for img in _images(2, 7):
        eng.submit(img)
    eng.step()
    assert _alg1_expected(eng.last_trace) > 0
    assert eng.stats["alg1_tiles"] == 0
    assert not tr.snapshot()
