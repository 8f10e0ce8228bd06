"""The comparison that decides ``correct``, at a size a CPU test holds.

A run whose timed path serves the control (the plain reference computed
in ``high`` precision, three bf16 passes, in place of the program) or is
broken underneath must come out not correct, while a sound run comes out
correct. These runs skip the look for a chip; everything else is the
benchmark's own run.

Each window is one full step (``seconds`` 0: the window closes at the
first step boundary), so every run compares a fixed set of images. The
limit is the chip's 3e-6; at this size on the CPU a sound run's error
varies by image, 0.6e-6 to 3.6e-6 over a window of 96, so a window that
grows with the CPU's speed would hold the tail and fail the chip's
limit. The control reads 1.2e-5 or more on every image.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, run

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


def small_cell(config: dict, chips: int = 1) -> dict:
    """What ``run.load_cell`` finds for a backlog cell of ``config``."""
    return {"cell": {"name": "test.backlog", "chips": chips},
            "config": config,
            "traffic": os.path.join(run.ROOT, "bench", "traffic",
                                    "backlog.json"),
            "metrics": {"end_to_end": [
                {"name": "images_per_s", "unit": "images/s"},
                {"name": "setup_s", "unit": "s"}], "per_layer": []},
            "root": run.ROOT}


def backlog_run(monkeypatch, fault=None, seed=123456789012):
    """One closed-loop run of a small VGG19-8 with slots 2 and a window
    of one step; ``fault`` rewrites the logits of every step after the
    warm-up, given the step's images, the weights and the previous
    step's logits in ``state``."""
    found = small_cell(small_config("vgg19-8-dcn2"))
    if fault is not None:
        make = program.make_engine

        def broken(config, params, *args, **kwargs):
            engine = make(config, params, *args, **kwargs)
            inner = engine._run_batch
            ref = run.reference_module(run.ROOT, config)
            state = {"calls": 0, "last": None, "ref": ref,
                     "net": ref.Net.from_config(config), "params": params}

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
    return run.run_cell(found, seed, 0.0, False, CPU)


def test_sound_run_is_correct(monkeypatch):
    result = backlog_run(monkeypatch)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["attempted"] == 2
    assert list(result["checks"]) == ["max_rel_err", "answers_missing"]
    assert list(result)[-1] == "checks"


def _control(out, state):
    """The plain reference at ``high`` over the step's own images."""
    x = jnp.asarray(np.stack(state["images"]))
    low = state["ref"].reference(state["net"], state["params"], x, "high")
    return np.asarray(low, dtype=np.asarray(out).dtype)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 90817263544])
def test_control_fails_the_limit(monkeypatch, seed):
    """The control in the program's place reads 1.2e-5 or more at this
    size (CPU), four times the limit or more."""
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


# A VGG19-8 served by two data replicas, on two CPU devices forced in a
# child process (the parent keeps its one device), then by one device on
# the same seed. ``FAULT`` names a fault planted in the program before
# the runs, or is empty. Seed 78: on seed 77 one image reads 3.96e-6 at
# this size on the CPU, with one replica and with two alike (the tail of
# the module docstring's spread, over the chip's limit).
DP_RUN = """
import json, sys
sys.path.insert(0, ROOT)
import jax
jax.config.update("jax_default_matmul_precision", "highest")
import jax.numpy as jnp
from bench import run
from bench.tests.test_correct import small_cell, small_config
from repro.runtime import fused_exec
from repro.runtime.shard import unstack_rows


def exchange_left_out(stacked, plan, rows):
    # Every replica's rows but the first never come back: zeros instead.
    return unstack_rows(stacked.at[1:].set(0), plan, rows)


if FAULT == "exchange_left_out":
    fused_exec.unstack_rows = exchange_left_out
config = small_config("vgg19-8-dcn2", slots=4)
cpu = {"platform": "cpu", "kind": "TPU v5 lite", "count": 2}
one = run.run_cell(small_cell(config), 78, 0.0, False, cpu)
config["graph"] = {"data_parallel": 2}
two = run.run_cell(small_cell(config, chips=2), 78, 0.0, False, cpu)
print(json.dumps([two, one]))
"""


def dp_run(fault: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([os.path.join(run.ROOT, "src"),
                                           run.ROOT]))
    body = f"ROOT = {run.ROOT!r}\nFAULT = {fault!r}\n" + DP_RUN
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_two_replicas_serve_correctly():
    (result, one), log = dp_run()
    assert result["correct"], result["checks"]
    assert result["attempted"] == 4
    assert result["checks"]["replicas_idle"] == {"value": 0, "limit": 0}
    assert "images served by each data replica: [2, 2]" in log
    # Sharded logits are bit-equal to one device's.
    assert result["checks"]["max_rel_err"] == one["checks"]["max_rel_err"]
    assert "replicas_idle" not in one["checks"]


def test_exchange_between_replicas_left_out_is_not_correct():
    (result, _), _ = dp_run("exchange_left_out")
    assert not result["correct"], result["checks"]
    assert result["checks"]["max_rel_err"]["value"] > \
        result["checks"]["max_rel_err"]["limit"]
