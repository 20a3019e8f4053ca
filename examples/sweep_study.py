"""Scenario-sweep study: thousands of what-if network designs in one call.

The paper answers "how much latency can this application absorb?" one LP at
a time; ``repro.sweep`` turns the question into a grid: compile the
execution graph once, then evaluate a cartesian latency × bandwidth LogGPS
grid — plus collective-algorithm graph variants — in batched jit+vmap
max-plus passes, reading T, λ_L and ρ_L for every scenario.

    PYTHONPATH=src python examples/sweep_study.py
"""

import numpy as np

from repro import sweep
from repro.core import synth
from repro.core.loggps import pod_model


def main():
    # an HPCG-like CG solve on 2 TPU pods: class 0 = ICI, class 1 = DCN
    p = pod_model(pod_size=8, L_ici_us=1.0, L_dcn_us=10.0).params()
    g = synth.cg_like(4, 4, 6, params=p)
    print(f"workload: {g.summary()}\n")

    eng = sweep.Engine(g, params=p)      # one engine; G/K/S batch axes

    # 1) 2,000-point cartesian grid: DCN latency delta × DCN bandwidth scale
    grid = sweep.cartesian_grid(
        p,
        lat_deltas={1: np.linspace(0.0, 200.0, 200)},
        gscales={1: [1.0, 1.5, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0]},
    )
    res = eng.run(grid)
    print(f"evaluated {res.S} scenarios in one batched call "
          f"(backend={res.backend})")
    i_best, i_worst = res.argbest(), int(np.argmax(res.T))
    print(f"  best : T={res.T[i_best]:10.1f} µs  at {grid.meta[i_best]}")
    print(f"  worst: T={res.T[i_worst]:10.1f} µs  at {grid.meta[i_worst]}")

    # 2) how much of the critical path is DCN latency, across the grid?
    rho_dcn = res.rho[:, 1]
    print(f"  ρ_L[dcn] ranges {rho_dcn.min():.3f} → {rho_dcn.max():.3f}\n")

    # 3) the same grid again is a content-hash cache hit
    res2 = eng.run(grid)
    print(f"re-run from cache: {res2.from_cache}\n")

    # 4) collective-algorithm axis (Fig 10): the graph itself changes, so
    #    each algorithm is a compiled plan lifted onto a shared structure
    #    envelope — the whole study is ONE XLA program (B × S axes)
    deltas = np.linspace(0.0, 100.0, 50)
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(16, 4, params=p, algo=a),
        ["ring", "recursive_doubling", "recursive_halving"], p)
    sb = sweep.StructureBatch.from_plans(
        [sweep.compile_plan(v.graph, v.params) for v in variants],
        names=[v.name for v in variants])
    out = sweep.Engine(sb).run(
        sweep.Query(scenarios=sweep.latency_grid(p, deltas))).split()
    print("allreduce algorithm under rising ICI latency (T µs):")
    print(f"  {'ΔL':>6} " + " ".join(f"{v.name:>24}" for v in variants))
    for k in (0, 24, 49):
        row = " ".join(f"{out[v.name].T[k]:24.1f}" for v in variants)
        print(f"  {deltas[k]:6.1f} {row}")
    lam0 = {v.name: out[v.name].lam[0, 0] for v in variants}
    print(f"\nλ_L at base point per algorithm: "
          + ", ".join(f"{k.split('=')[1]}={v:.0f}" for k, v in lam0.items()))


if __name__ == "__main__":
    main()
