"""The one module of the benchmark that imports the program under test.

It hands the program what the benchmark made (weights, images) in the
program's own types, builds the serving engine a configuration states,
and reads the program's counters and spans. Nothing here feeds the
reference.
"""

from __future__ import annotations

import dataclasses
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


def program_config(model):
    """``DcnNetConfig`` from a configuration's ``model`` keys, each passed
    by its own name, with ``arch`` as ``name``. A reference's ``Net`` is
    taken as the fields it shares with ``DcnNetConfig``."""
    from repro.models.dcn_models import DcnNetConfig
    if not isinstance(model, dict):
        known = {f.name for f in dataclasses.fields(DcnNetConfig)}
        model = {k: v for k, v in dataclasses.asdict(model).items()
                 if k in known | {"arch"}}
    keys = dict(model)
    return DcnNetConfig(name=keys.pop("arch"), **keys)


def program_params(params):
    """The benchmark's weights in the program's parameter types."""
    from repro.core.deform import DeformableConvParams
    convs = [DeformableConvParams(**p) if "w_off" in p
             else {"w": p["w"], "b": p["b"]}
             for p in params["convs"]]
    return {**{k: v for k, v in params.items() if k != "convs"},
            "convs": convs}


def make_engine(config: dict, params, traced: bool):
    """``DcnServingEngine`` for a configuration: its ``model``, its
    ``slots`` and its optional ``graph`` (``GraphConfig`` keys; the
    harness admits only the replica count). Every other setting is the
    program's default. A traced run routes the program's spans into a
    tracer of its own."""
    from repro.obs import Tracer
    from repro.runtime import GraphConfig
    from repro.serving import DcnServingEngine
    graph = config.get("graph")
    tracer = Tracer(enabled=True) if traced else None
    return DcnServingEngine(program_params(params),
                            program_config(config["model"]),
                            graph=GraphConfig(**graph) if graph else None,
                            slots=int(config["slots"]), tracer=tracer)


def failures(engine) -> int:
    st = engine.stats
    return sum(int(st[k]) for k in FAILURE_COUNTERS)


def cache_counts(engine) -> tuple[int, int]:
    """(image hits, image lookups) of the engine's schedule cache."""
    info = engine.cache.info()
    return int(info["image_hits"]), int(info["image_lookups"])


def replica_images(engine) -> list[int]:
    """Images each data replica of the engine has served."""
    return [int(r["images"]) for r in engine.stats["per_replica"]]


def spans(engine) -> list:
    """(name, seconds) of every span the program recorded."""
    if engine.tracer is None or not engine.tracer.enabled:
        return []
    return [(s.name, s.dur) for s in engine.tracer.snapshot()]
