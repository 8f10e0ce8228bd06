"""The one module of the benchmark that imports the program under test.

It hands the program what the benchmark made (weights, images) in the
program's own types, builds the serving engine with its defaults, and
reads the program's counters and spans. Nothing here feeds the
reference.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

# Engine counters of work not served cleanly; a request that failed
# outright is counted by the harness as a missing answer instead.
FAILURE_COUNTERS = ("step_retries", "degraded_steps", "watchdog_failovers")


def enable_compile_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache as enable
    return enable()


def pallas_interprets() -> bool:
    from repro.kernels.ops import resolve_interpret
    return resolve_interpret(None)


def program_config(net):
    from repro.models.dcn_models import DcnNetConfig
    return DcnNetConfig(name=net.arch, n_deform=net.n_deform,
                        variant=net.variant, img_size=net.img_size,
                        width_mult=net.width_mult,
                        num_classes=net.num_classes,
                        in_channels=net.in_channels)


def program_params(params):
    """The benchmark's weights in the program's parameter types."""
    from repro.core.deform import DeformableConvParams
    convs = [DeformableConvParams(p["w_off"], p["b_off"], p["w"], p["b"])
             if "w_off" in p else {"w": p["w"], "b": p["b"]}
             for p in params["convs"]]
    return {**{k: v for k, v in params.items() if k != "convs"},
            "convs": convs}


def make_engine(net, params, slots: int, traced: bool):
    """``DcnServingEngine`` with its default ``GraphConfig``; a traced run
    routes the program's spans into a tracer of its own."""
    from repro.obs import Tracer
    from repro.serving import DcnServingEngine
    tracer = Tracer(enabled=True) if traced else None
    return DcnServingEngine(program_params(params), program_config(net),
                            slots=slots, tracer=tracer)


def failures(engine) -> int:
    st = engine.stats
    return sum(int(st[k]) for k in FAILURE_COUNTERS)


def cache_counts(engine) -> tuple[int, int]:
    """(image hits, image lookups) of the engine's schedule cache."""
    info = engine.cache.info()
    return int(info["image_hits"]), int(info["image_lookups"])


def spans(engine) -> list:
    """(name, seconds) of every span the program recorded."""
    if engine.tracer is None or not engine.tracer.enabled:
        return []
    return [(s.name, s.dur) for s in engine.tracer.snapshot()]
