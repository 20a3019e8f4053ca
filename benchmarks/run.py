"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines:
  bench_solver_speed   — Table I / Fig 7  (LLAMP vs DES throughput)
  bench_validation     — Fig 9 / Table II (RRMSE of predictions under ΔL)
  bench_tolerance      — Fig 1            (per-arch tolerance zones)
  bench_collectives    — Fig 10           (ring vs recursive doubling)
  bench_topology       — Fig 11           (fat-tree/dragonfly/torus wires)
  bench_placement      — Fig 20           (Algorithm 3 rank placement:
                                           scalar reference vs the batched
                                           MultiPlan-scored loop, plus the
                                           grid-robust scenarios/topk mode)
  bench_sweep          — repro.sweep      (1k-scenario batched grid vs
                                           scalar LevelPlan loop; 4-variant
                                           × 250-scenario packed study vs
                                           the per-variant jit loop; cache)
  bench_explore        — repro.explore    (packed search generations:
                                           warm-stamper replay compiles 0
                                           programs, packed best ==
                                           solo rebuild bit-for-bit,
                                           GA vs random at equal budget)

``python -m benchmarks.bench_sweep --smoke`` runs the sweep module alone
with tiny grids (the CI smoke step).
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache

    from . import (bench_collectives, bench_explore, bench_placement,
                   bench_solver_speed, bench_sweep, bench_tolerance,
                   bench_topology, bench_validation)

    setup_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_solver_speed, bench_validation, bench_tolerance,
                bench_collectives, bench_topology, bench_placement,
                bench_sweep, bench_explore):
        try:
            mod.run(lambda line: print(line, flush=True))
        except Exception:
            failures += 1
            name = mod.__name__.split(".")[-1]
            print(f"{name}.ERROR,0,{traceback.format_exc(limit=1)!r}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
