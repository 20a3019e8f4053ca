import os
import sys

import pytest

# smoke tests and benches must see ONE device; only launch/dryrun.py sets
# the 512-placeholder-device flag (per spec). Pipeline/dryrun tests that
# need multiple devices spawn subprocesses with their own XLA_FLAGS.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def lulesh_graph():
    """:func:`bench_lulesh_graph`, as a fixture."""
    return bench_lulesh_graph


def bench_lulesh_graph(tp: int, cycles: int, jitter: float = 0.0,
                       seed: int = 0):
    """(graph, params) of the benchmark's LULESH cycle skeleton
    (``bench/skeletons/lulesh.py``, built by ``bench/build.py``) at
    ``tp``³ ranks and ``cycles`` cycles, with ``lulesh_512r``'s network
    and compute split; ``jitter`` scales each compute vertex by 1 + u,
    u uniform in ±jitter from ``seed``."""
    import importlib.util
    import json

    import numpy as np

    from repro.core.loggps import cluster_params

    bench = os.path.join(os.path.dirname(__file__), "..", "bench")
    sys.path.insert(0, bench)          # build.py imports the registry
    try:
        spec = importlib.util.spec_from_file_location(
            "_bench_build_for_tests", os.path.join(bench, "build.py"))
        build = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(build)
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "configs", "lulesh_512r.json")) as f:
        cfg = json.load(f)
    net = cfg["network"]
    p = cluster_params(L_us=net["L_us"], G_ns_per_byte=net["G_ns_per_byte"],
                       o_us=net["o_us"], S_bytes=net["S_bytes"])
    gspec = dict(cfg["graphs"][0])
    gspec["args"] = dict(gspec["args"], tp=tp, cycles=cycles)
    sk = build.registry.module("skeletons", gspec["skeleton"])
    shape = sk.jitter_shape(**gspec["args"])
    jit = np.random.default_rng(seed).uniform(-jitter, jitter, size=shape)
    g, _ = build.build(p, gspec, jit)
    return g, p
