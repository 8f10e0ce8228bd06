"""The comparison that decides ``correct``, at a size a CPU test holds.

A run whose timed path serves the control (the plain reference computed
in ``high`` precision, three bf16 passes, in place of the program) or is
broken underneath must come out not correct, while a sound run comes out
correct. These runs skip the look for a chip; everything else is the
benchmark's own run.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, program, run

SMALL = {"img_size": 32, "width_mult": 0.125, "num_classes": 10}
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(autouse=True, scope="module")
def highest_precision():
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", before)


def small_config(name: str, slots: int = 2) -> dict:
    with open(os.path.join(run.ROOT, "bench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    config["model"].update(SMALL)
    config["slots"] = slots
    return config


def backlog_run(monkeypatch, fault=None, seed=123456789012):
    """One closed-loop run of a small VGG19-8 with slots 2; ``fault``
    rewrites the logits of every step after the warm-up, given the step's
    images, the weights and the previous step's logits in ``state``."""
    found = {"cell": {"name": "test.backlog", "chips": 1},
             "config": small_config("vgg19-8-dcn2"),
             "traffic": os.path.join(run.ROOT, "bench", "traffic",
                                     "backlog.json"),
             "metrics": {"end_to_end": [
                 {"name": "images_per_s", "unit": "images/s"},
                 {"name": "setup_s", "unit": "s"}], "per_layer": []},
             "root": run.ROOT}
    if fault is not None:
        make = program.make_engine

        def broken(net, params, *args, **kwargs):
            engine = make(net, params, *args, **kwargs)
            inner = engine._run_batch
            state = {"calls": 0, "last": None, "net": net, "params": params}

            def run_batch(images, step_cfg, shard_sizes=None):
                out, trace = inner(images, step_cfg, shard_sizes)
                state["calls"] += 1
                state["images"] = images
                bad = out if state["calls"] == 1 else fault(out, state)
                state["last"] = out
                return bad, trace

            engine._run_batch = run_batch
            return engine

        monkeypatch.setattr(program, "make_engine", broken)
    return run.run_cell(found, seed, 1.0, False, CPU)


def test_sound_run_is_correct(monkeypatch):
    result = backlog_run(monkeypatch)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert list(result["checks"]) == ["max_rel_err", "answers_missing"]
    assert list(result)[-1] == "checks"


def _control(out, state):
    """The plain reference at ``high`` over the step's own images."""
    x = jnp.asarray(np.stack(state["images"]))
    low = model.reference(state["net"], state["params"], x, "high")
    return np.asarray(low, dtype=np.asarray(out).dtype)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 90817263544])
def test_control_fails_the_limit(monkeypatch, seed):
    """The control in the program's place reads about 1.2e-5 to 2.8e-5
    at this size (CPU), four times the limit or more."""
    result = backlog_run(monkeypatch, _control, seed)
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["answers_missing"]["value"] == 0
    assert checks["max_rel_err"]["value"] > checks["max_rel_err"]["limit"]


def _altered(out, state):
    out = np.array(out)
    out[0].flat[0] += np.abs(out).max()
    return out


def _half_left_out(out, state):
    return out[:len(out) // 2]


def _stale(out, state):
    return state["last"]


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _stale],
                         ids=["answer_altered", "half_batch_left_out",
                              "previous_step_returned"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    result = backlog_run(monkeypatch, fault)
    assert not result["correct"], result["checks"]
