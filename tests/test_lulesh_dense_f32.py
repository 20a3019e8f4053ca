"""The dense Pallas float32 forward on the benchmark's LULESH skeleton, held
to the ``grid_f32`` contract (``bench/kinds/grid_f32.py``) against the
benchmark's float64 reference (``bench/reference.py``): T within the kind's
relative limit, and lambda inside the bracket that any path within that
limit of the critical path has.  Interpret mode on the CPU, at small
sizes; the chip runs the same check at ``lulesh_64r``'s.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's ``check``, ``reference`` and ``grid_f32`` kind, and
    ``lulesh_64r``'s configuration."""
    sys.path.insert(0, BENCH)
    try:
        import check
        import reference
        import registry
        kind = registry.module("kinds", "grid_f32")
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "lulesh_64r.json")) as f:
        cfg = json.load(f)
    return types.SimpleNamespace(check=check, reference=reference,
                                 kind=kind, cfg=cfg)


@pytest.mark.parametrize("tp,cycles,seed", [(2, 2, 0), (2, 2, 1), (2, 2, 2),
                                            (4, 1, 0)])
def test_dense_f32_lulesh_meets_the_grid_f32_contract(bench, lulesh_graph,
                                                      tp, cycles, seed):
    from repro import sweep
    g, p = lulesh_graph(tp, cycles, 0.1, seed)
    spec = copy.deepcopy(bench.cfg["graphs"][0])
    spec["args"].update(tp=tp, cycles=cycles)
    # the same jitter draw as the lulesh_graph fixture's
    sk = bench.reference.registry.module("skeletons", spec["skeleton"])
    jit = np.random.default_rng(seed).uniform(
        -0.1, 0.1, size=sk.jitter_shape(**spec["args"]))
    ref = bench.reference.build_graph(
        bench.reference.Net(bench.cfg["network"]), spec, jit)
    assert (ref[0].nv, ref[0].ne) == (g.num_vertices, g.num_edges)

    mix = {"kind": "grid_f32", "outputs": ["T", "lam"], "lat_points": 6,
           "lat_range": [0.0, 60.0], "gscale_points": 4,
           "gscale_range": [1.0, 4.0], "offset_max": 5.0}
    req = bench.kind.make(mix, 0, np.random.default_rng(seed), None)
    assert req["kind"] == "grid_f32"
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(
        **bench.cfg["policy"], cache=None))
    prog = types.SimpleNamespace(params=p, entry=eng, on_axis=["lulesh"])
    res, ok = bench.kind.call(prog, req)
    assert ok
    assert eng.run(sweep.latency_grid(p, [0.0])).dtype == "float32"

    gaps = bench.check.Gaps()
    gaps.declare(bench.kind.LIMITS)
    bench.kind.check({"req": req, "res": res}, {"lulesh": ref},
                     {"names": ["lulesh"],
                      "L0": bench.cfg["network"]["L_us"]}, gaps)
    assert set(gaps.value) == set(bench.kind.LIMITS)
    for name, v in gaps.value.items():
        assert v <= gaps.limit[name], (name, v)
    assert gaps.value["lam_bracket_err"] == 0.0
