"""Readings that set the limit of ``correct``: the program's comparison
and its control's, over many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 11,12,... \
        --seconds <s>

Per seed, one short window of the cell as ``bench/run.py`` runs it (the
engine, its batch widths and load, the answers it served), then two
numbers over the same images: the program's largest relative error
against the plain reference at the configuration's precision, and the
control's: the reference computed at ``high`` (three bf16 passes) in the
program's place. The set-up's compiled programs are shared by the seeds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import program, run  # noqa: E402


def control_reading(served: run.Served) -> float:
    import jax.numpy as jnp
    worst = 0.0
    for img, _ in served.answers:
        x = jnp.asarray(served.mix.image(img)[None])
        ref = np.asarray(served.ref.reference(served.net, served.params,
                                              x))
        low = np.asarray(served.ref.reference(served.net, served.params,
                                              x, "high"))
        worst = max(worst, run.rel_err(low, ref))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    found = run.load_cell(run.ROOT, args.workload)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      found["config"]["precision"])
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(run.BENCH_DIR, "peaks.json")) as f:
        device = run.device_check(int(found["cell"]["chips"]), json.load(f))
    for seed in (int(s) for s in args.seeds.split(",")):
        served = run.serve(found, seed, args.seconds, False, device)
        row = {"seed": seed, "answers": len(served.answers),
               "missing": served.missing, "failed": served.failed,
               "replicas": served.replicas,
               "program": run.compare(served.ref, served.net,
                                      served.params, served.mix,
                                      served.answers,
                                      found["config"]["precision"]),
               "control": control_reading(served)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
