"""Smoke run of the DCN serving path on a TPU, through its entry points.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # data-parallel replicas, 4 chips

Model: VGG19-8 DCN-II (the paper's Table III) at full width, 224x224
inputs, 1000 classes, random weights from ``--seed``. Every deformable
layer's offset conv is re-drawn so its sampling offsets average a few
pixels: the tile dependency tables and the Algorithm-1 schedules are
then genuinely irregular.

One chip (default):
  1. device check: the first device is a TPU and Pallas kernels compile
     (not interpret mode); otherwise exit 1 before any result;
  2. reference logits from ``dcn_net_apply(..., backend="xla")``;
  3. ``DcnServingEngine`` with the default ``GraphConfig()`` (batch-fused
     dispatch, host scheduling) answers one ``infer()``;
  4. ``DcnServingEngine`` with ``dispatch="batch_fused"``,
     ``schedule_backend="device"`` and 4 slots serves requests of 1-3
     images through ``submit()``/``drain()``, twice (cold, then warm);
  5. every result matches the reference within ``REL_BOUND`` and the
     engine reports no failed, retried or degraded work.

``--chips 4`` runs only the scale-out path: a ``data_parallel=4``
``batch_fused`` engine (8 slots), compared bit for bit with the same
images served on one device in this process, and with the reference.

The last line of stdout is one JSON object; it is printed only when
every check passed. Timings are one smoke reading each, not a benchmark.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Relative bound on max|y - ref| / max|ref|. Every path is f32 with the
# "highest" matmul precision, so the executors differ from the XLA
# reference only in the order of f32 sums (tiled convs, the BLI 4-hot
# matmul, the tap-split contraction): relative rounding ~1e-7 per
# accumulation, ~1e-5 after 16 layers. A wrong tap, tile, dependency or
# weight shows up as an O(1) relative error, far above this bound.
REL_BOUND = 1e-3
# Target mean |offset| (pixels) of every deformable layer's sampling
# offsets: a few pixels crosses 8x8 tile borders.
OFFSET_PX = 2.0
REQUEST_SIZES = (1, 3, 2, 2)          # 8 images in 4 requests
FOUR_CHIP_SIZES = (2, 1, 3, 2, 1, 1)  # 10 images in 6 requests


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int):
    """The run's device, or exit 1: never carry on on the CPU or in
    Pallas interpret mode."""
    import jax

    from repro.kernels.ops import resolve_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is "
             f"{devs[0].platform!r}")
    if resolve_interpret(None):
        fail("resolve_interpret(None) is True: kernels would run in "
             "Pallas interpret mode")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} devices, found {len(devs)}")
    return devs


def model_config():
    from repro.models.dcn_models import DcnNetConfig

    return DcnNetConfig(name="vgg19", n_deform=8, variant="dcn2",
                        img_size=224, width_mult=1.0, num_classes=1000)


def build_model(cfg, seed: int, x_probe):
    """Seeded parameters with every deformable layer's offset conv
    re-drawn so its offsets on ``x_probe`` average ``OFFSET_PX``.

    Returns ``(params, [mean |offset| per deformable layer])``. The
    whole build is one jitted program: run op by op, the 224x224 layers
    would each compile on their own.
    """
    import jax
    import jax.numpy as jnp

    params, offsets_px = jax.jit(functools.partial(_seeded_model, cfg))(
        jax.random.PRNGKey(seed), jnp.asarray(x_probe))
    return params, [float(o) for o in offsets_px]


def _seeded_model(cfg, key, x):
    """Walks the backbone in ``dcn_net_apply``'s order (VGG: conv/DCN,
    ReLU, pool after each stage) to calibrate each deformable layer's
    offset scale on its real input."""
    import jax
    import jax.numpy as jnp

    from repro.core.deform import (DeformableConvParams, conv2d,
                                   deformable_conv2d,
                                   randomize_offset_conv)
    from repro.models.dcn_models import _pool_positions, init_dcn_net

    params = init_dcn_net(key, cfg)
    pools = _pool_positions(cfg)
    convs, offsets_px = [], []
    for i, p in enumerate(params["convs"]):
        if isinstance(p, DeformableConvParams):
            unit = randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                                         1.0)
            raw = jnp.abs(conv2d(x, unit.w_off)).mean()
            p = randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                                      OFFSET_PX / raw)
            offsets_px.append(jnp.abs(conv2d(x, p.w_off, p.b_off)).mean())
            x = deformable_conv2d(x, p, variant=cfg.variant,
                                  max_displacement=cfg.max_displacement)
        else:
            x = conv2d(x, p["w"], p["b"])
        x = jax.nn.relu(x)
        if i in pools and x.shape[1] >= 2:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        convs.append(p)
    params["convs"] = convs
    return params, jnp.stack(offsets_px)


def reference(params, cfg, x):
    import jax

    from repro.models.dcn_models import dcn_net_apply

    fn = jax.jit(lambda p, xs: dcn_net_apply(p, cfg, xs, backend="xla"))
    t0 = time.perf_counter()
    y = jax.block_until_ready(fn(params, x))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(params, x))
    warm = time.perf_counter() - t0
    return np.asarray(y), cold, warm


def rel_err(y, ref) -> float:
    return float(np.abs(np.asarray(y) - ref).max() / np.abs(ref).max())


def check_engine(eng, reqs, name: str) -> dict:
    """Resilience counters must be zero and every request clean."""
    st = eng.stats
    for k in ("requests_failed", "step_retries", "degraded_steps",
              "watchdog_failovers"):
        if st[k] != 0:
            fail(f"{name}: stats[{k!r}] = {st[k]}")
    for r in reqs:
        if not r.done or r.error is not None:
            fail(f"{name}: request {r.rid} done={r.done} error={r.error!r}")
    return st


def partition(trace) -> list[str]:
    """Fusion groups of image 0 as executed: layer widths and tile."""
    out = []
    for gt in trace.groups:
        if gt.image != 0:
            continue
        g = gt.grid
        out.append(f"{g.h}x{g.w}/tile{g.th}x{g.tw}:"
                   + "+".join(f"{ci}->{co}" for ci, co in gt.layer_channels))
    return out


def serve_requests(eng, xs, sizes):
    """Submit ``xs`` split into requests of ``sizes`` images, drain, and
    return (requests, stacked results, wall seconds)."""
    reqs, at = [], 0
    t0 = time.perf_counter()
    for n in sizes:
        reqs.append(eng.submit(xs[at:at + n]))
        at += n
    eng.drain()
    wall = time.perf_counter() - t0
    return reqs, np.concatenate([r.result() for r in reqs]), wall


def run_one_chip(cfg, params, x, ref) -> None:
    import jax.numpy as jnp

    from repro.runtime import GraphConfig
    from repro.serving import DcnServingEngine

    # Step 3: the default engine configuration, one synchronous request.
    eng = DcnServingEngine(params, cfg)
    t0 = time.perf_counter()
    y = np.asarray(eng.infer(jnp.asarray(x[:1])))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(eng.infer(jnp.asarray(x[:1])))
    warm = time.perf_counter() - t0
    st = check_engine(eng, [], "default engine")
    err = rel_err(y, ref[:1])
    log(f"default engine (dispatch={st['dispatch']}, "
        f"schedule_backend={st['schedule_backend']}): partition "
        f"{len(partition(eng.last_trace))} groups "
        f"{partition(eng.last_trace)}")
    log(f"default engine: first infer {cold:.2f} s (compile + run), "
        f"second infer {warm:.3f} s (smoke reading); rel err {err:.3e}; "
        f"dispatches_per_batch {st['dispatches_per_batch']:.1f}, "
        f"image_hits {st['image_hits']}, host_schedule_builds "
        f"{st['host_schedule_builds']}")
    if not err <= REL_BOUND:
        fail(f"default engine rel err {err:.3e} > {REL_BOUND}")

    # Step 4: continuous batching, batch-fused dispatch, device schedule.
    graph = GraphConfig(dispatch="batch_fused", schedule_backend="device")
    eng = DcnServingEngine(params, cfg, graph=graph, slots=4)
    n = sum(REQUEST_SIZES)
    for rnd in ("cold", "warm"):
        reqs, y, wall = serve_requests(eng, x[:n], REQUEST_SIZES)
        st = check_engine(eng, reqs, f"batch_fused engine ({rnd})")
        err = rel_err(y, ref[:n])
        lat = ", ".join(f"{r.latency_s:.3f}" for r in reqs)
        log(f"batch_fused/device engine, {rnd} round: {n} images in "
            f"{len(reqs)} requests, drain {wall:.2f} s "
            f"({'compile + run' if rnd == 'cold' else 'smoke reading'}); "
            f"per-request latency s [{lat}]; rel err {err:.3e}")
        if not err <= REL_BOUND:
            fail(f"batch_fused engine ({rnd}) rel err {err:.3e} > "
                 f"{REL_BOUND}")
    log(f"batch_fused/device engine: partition {partition(eng.last_trace)}")
    log(f"batch_fused/device engine counters: steps {st['steps']}, "
        f"dispatches_per_batch {st['dispatches_per_batch']:.2f}, "
        f"image_hits {st['image_hits']}/{st['image_lookups']}, "
        f"host_schedule_builds {st['host_schedule_builds']}, "
        f"schedule_device_frac {st['schedule_device_frac']:.2f}")


def run_four_chip(cfg, params, x, ref, devices) -> None:
    from repro.runtime import GraphConfig
    from repro.runtime.shard import resolve_shard_mesh
    from repro.serving import DcnServingEngine

    mesh = resolve_shard_mesh(None, 4)
    mesh_devs = [d.id for d in mesh.devices.flat]
    log(f"data mesh {dict(mesh.shape)} on device ids {mesh_devs}")
    if len(set(mesh_devs)) != 4:
        fail(f"data mesh repeats a device: {mesh_devs}")

    n = sum(FOUR_CHIP_SIZES)
    single = DcnServingEngine(params, cfg,
                              graph=GraphConfig(dispatch="batch_fused"),
                              slots=8)
    reqs1, y1, wall1 = serve_requests(single, x[:n], FOUR_CHIP_SIZES)
    check_engine(single, reqs1, "single-device engine")
    sharded = DcnServingEngine(
        params, cfg, graph=GraphConfig(dispatch="batch_fused",
                                       data_parallel=4),
        slots=8)
    reqs4, y4, wall4 = serve_requests(sharded, x[:n], FOUR_CHIP_SIZES)
    st = check_engine(sharded, reqs4, "sharded engine")
    log(f"single-device drain {wall1:.2f} s, sharded drain {wall4:.2f} s "
        f"(both cold: compile + run; smoke readings)")
    per = [r["images"] for r in st["per_replica"]]
    log(f"sharded engine: replicas {st['replicas']}, per-replica images "
        f"{per}, allgather_bytes {st['allgather_bytes']}, steps "
        f"{st['steps']}, dispatches_per_batch "
        f"{st['dispatches_per_batch']:.2f}")
    if st["replicas"] != 4 or min(per) < 1:
        fail(f"every replica must serve images: per-replica {per}")
    # A replica that silently ran on device 0 leaves its own chip empty.
    stats = [d.memory_stats() for d in devices[:4]]
    if all(s is not None for s in stats):
        peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
        log(f"peak bytes in use per device {peaks}")
        if min(peaks) <= 0:
            fail(f"a device held no data: peak bytes {peaks}")
    else:
        log("peak bytes in use per device: not reported by this backend")
    diff = float(np.abs(y4 - y1).max())
    err1, err4 = rel_err(y1, ref[:n]), rel_err(y4, ref[:n])
    log(f"sharded vs single-device max |diff| {diff!r}; rel err vs "
        f"reference: single {err1:.3e}, sharded {err4:.3e}")
    if diff != 0.0:
        fail(f"sharded logits differ from single-device by {diff!r}")
    if not max(err1, err4) <= REL_BOUND:
        fail(f"rel err {max(err1, err4):.3e} > {REL_BOUND}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: engine paths on one chip; 4: only the "
                         "data_parallel=4 scale-out path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"cannot import the program: {e}")
    jax.config.update("jax_default_matmul_precision", "highest")
    cache_dir = enable_compile_cache()
    devices = device_check(args.chips)
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    cfg = model_config()
    n = sum(FOUR_CHIP_SIZES if args.chips == 4 else REQUEST_SIZES)
    rng = np.random.default_rng(args.seed)
    x = rng.normal(size=(n, cfg.img_size, cfg.img_size,
                         cfg.in_channels)).astype(np.float32)
    t0 = time.perf_counter()
    params, offsets_px = build_model(cfg, args.seed, x)
    log(f"model {cfg.name}-{cfg.n_deform} {cfg.variant} "
        f"{cfg.img_size}x{cfg.img_size} width {cfg.width_mult}: built in "
        f"{time.perf_counter() - t0:.2f} s; mean |offset| px per "
        f"deformable layer {[round(o, 3) for o in offsets_px]}")

    ref, cold, warm = reference(params, cfg, x)
    if not np.isfinite(ref).all():
        fail("reference logits are not finite")
    log(f"reference (backend=xla) logits {ref.shape}: first call "
        f"{cold:.2f} s (compile + run), second {warm:.3f} s")

    if args.chips == 4:
        run_four_chip(cfg, params, x, ref, devices)
    else:
        run_one_chip(cfg, params, x, ref)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
