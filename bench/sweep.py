"""Find the knee of an open-loop cell: the highest offered rate with no
growing backlog. Run once, when a cell's rate is chosen; the benchmark's
own runs never sweep.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.4,0.6,0.8

One process: the cell's set-up once, then one open-loop window per rate
(the cell's mix with its ``rate_per_s`` replaced), each drained before
the next. Per rate it prints p50/p90 latency, the images still waiting
when the window closed, and whether the second half of the window's
requests waited longer than the first (a growing backlog).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import program, run  # noqa: E402
from bench.traffic import Mix  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    found = run.load_cell(run.ROOT, args.workload)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      found["config"]["precision"])
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(run.BENCH_DIR, "peaks.json")) as f:
        run.device_check(int(found["cell"]["chips"]), json.load(f))
    config = found["config"]
    ref = run.reference_module(found["root"], config)
    net = ref.Net.from_config(config)
    slots = int(config["slots"])
    with open(found["traffic"]) as f:
        spec = json.load(f)
    params, _ = ref.build_weights(net, args.seed)
    engine = program.make_engine(config, params, traced=False)
    shape = (net.img_size, net.img_size, net.in_channels)
    run.warm_up(engine, Mix(spec, args.seed, shape), slots)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = Mix(dict(spec, rate_per_s=rate), args.seed + k + 1, shape)
        steps: list[run.Step] = []
        sent, t0 = run.open_loop(engine, mix, args.seconds, steps)
        lat = [latency for *_, latency in sent]
        half = len(lat) // 2
        close = t0 + args.seconds
        waiting = sum(1 for _, due, r, latency in sent
                      if due + latency > close)
        first = run.percentile(lat[:half], 50) if half else 0.0
        second = run.percentile(lat[half:], 50)
        row = {"rate_per_s": rate, "requests": len(sent),
               "p50_latency_s": run.percentile(lat, 50),
               "p90_latency_s": run.percentile(lat, 90),
               "waiting_at_close": waiting,
               "p50_first_half_s": first, "p50_second_half_s": second,
               "steps": len(steps),
               "mean_width": sum(s.width for s in steps) / len(steps),
               "step_s_p50": run.percentile([s.dur for s in steps], 50),
               "failed": program.failures(engine),
               "drained_s": time.perf_counter() - close}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
