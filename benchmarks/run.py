"""Benchmark driver: one harness per paper table/figure + roofline.

``PYTHONPATH=src python -m benchmarks.run`` prints name,value CSV rows for
  Figs 11-12  platform performance/energy comparison (bench_platforms)
  Figs 14-16  tile-scheduling ablation               (bench_scheduling)
  Fig  17     tile-size sweep                        (bench_tile_size)
  Fig  18     BLI(+)conv fusion                      (bench_fusion)
  kernels     microbench + allclose gates            (bench_kernels)
  roofline    3-term per (arch x shape) table        (roofline; reads
              benchmarks/artifacts/dryrun — run launch.dryrun first)
"""

from __future__ import annotations

import sys
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks import (bench_access_pattern, bench_fusion,
                            bench_kernels, bench_platforms,
                            bench_scheduling, bench_tile_size, roofline)

    sections = [
        ("access_pattern(fig3)", bench_access_pattern.run),
        ("platforms(fig11-12)", bench_platforms.run),
        ("scheduling(fig14-16)", bench_scheduling.run),
        ("tile_size(fig17)", bench_tile_size.run),
        ("fusion(fig18)", bench_fusion.run),
        ("kernels", bench_kernels.run),
        ("roofline", roofline.run),
    ]
    failures = 0
    for name, fn in sections:
        print(f"### {name}")
        t0 = time.time()
        try:
            fn()
            print(f"### {name} done in {time.time()-t0:.1f}s\n")
        except Exception as e:  # keep the suite running
            failures += 1
            print(f"### {name} FAILED: {type(e).__name__}: {e}\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
