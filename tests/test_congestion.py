"""N-class network registry + congestion fixed point (the PR 8 surface).

Covers the pluggable :class:`NetworkModel` registry (named classes,
per-class L/G and congestion α/β), the per-edge physical-link ids the
builder interns, the iterated congestion fixed point on the batched
forward (``ExecPolicy(congestion="fixed_point")``), its validation
against the discrete-event contention injector, and the two satellite
fixes (NaN gap-share guard, configurable auto-sparse threshold).

Zero-congestion bit-identity across every conformance case lives in
``test_conformance.py::test_zero_congestion_fixed_point_bit_identical``.
"""

import dataclasses
import json

import numpy as np
import pytest

pytest.importorskip("jax")

from repro import obs, sweep
from repro.core import sensitivity, simulator, synth
from repro.core.graph import GraphBuilder, edge_gap_shares
from repro.core.loggps import (NetClass, NetworkModel, cluster_params,
                               pod_model, resolve_class)
from repro.launch.analysis import (AnalysisRequest, AnalysisService)
from repro.sweep.api import Engine, ExecPolicy


def _incast(p, n=6, nbytes=1e6):
    """n concurrent messages rank 0 → rank 1 over one physical link."""
    b = GraphBuilder(nclass=p.nclass, nranks=2)
    for _ in range(n):
        b.add_message(0, 1, nbytes=nbytes, params=p)
    return b.finalize()


# -- the class registry -------------------------------------------------------

def test_registry_basics():
    m = pod_model(pod_size=4, ranks_per_host=2,
                  alpha={"dcn": 2.0}, beta={"ici": 0.5})
    p = m.params()
    assert p.class_names == ("node", "ici", "dcn")
    assert p.nclass == 3
    assert p.class_index("dcn") == 2
    assert resolve_class(p, "node") == 0
    assert resolve_class(p, 1) == 1
    with pytest.raises(ValueError, match="unknown network class"):
        resolve_class(p, "infiniband")
    with pytest.raises(ValueError, match="out of range"):
        resolve_class(p, 7)
    # α/β land on the named classes, zero elsewhere
    assert p.alpha_full == (0.0, 0.0, 2.0)
    assert p.beta_full == (0.0, 0.5, 0.0)
    # the rank mapping: same host → node, same pod → ici, else dcn
    assert p.link_class(0, 1) == 0
    assert p.link_class(0, 2) == 1
    assert p.link_class(0, 4) == 2


def test_netclass_from_gbps():
    c = NetClass.from_gbps("ici", L_us=1.0, gbps=50.0)
    # G is µs per byte: 50 GB/s = 5e4 B/µs
    assert c.G_us_per_byte == pytest.approx(1.0 / 50e3, rel=1e-12)
    m = NetworkModel(classes=(c, NetClass("dcn", 10.0, 1e-4)),
                     rank_of_class=lambda a, b: 0)
    p = m.params()
    assert p.class_names == ("ici", "dcn")
    assert p.L == (1.0, 10.0)


# -- link interning (the physical-link axis congestion aggregates over) -------

def test_builder_interns_links():
    p = pod_model(pod_size=2).params()
    g = _incast(p, n=4)
    assert g.elink is not None and g.nlinks == 1
    msg = g.ebytes > 0
    # every message edge shares the single interned 0→1 link
    assert set(g.elink[msg].tolist()) == {0}
    # non-message (dep/handshake) edges carry no link
    assert np.all(g.elink[~msg] == -1)
    assert g.link_classes is not None and g.link_classes.shape == (1,)

    # distinct (src, dst) pairs intern distinct links; class recorded
    p3 = pod_model(pod_size=4, ranks_per_host=2).params()
    g3 = synth.stencil2d(4, 2, 3, params=p3)
    assert g3.nlinks > 1
    lc = g3.link_classes
    el = g3.elink[g3.ebytes > 0]
    assert np.all(el >= 0) and np.all(el < g3.nlinks)
    # the interned link's class matches the edge's gap class
    np.testing.assert_array_equal(lc[el], g3.egclass[g3.ebytes > 0])


def test_compiled_plans_carry_links():
    p = pod_model(pod_size=4, ranks_per_host=2).params()
    g = synth.stencil2d(4, 2, 3, params=p)
    c = sweep.compile_plan(g, p)
    assert c.vlink is not None and c.elinkp is not None
    assert c.nlinks == g.nlinks
    # pad slots land in the dummy bin (= nlinks), never a real link
    assert int(c.vlink.max()) <= c.nlinks
    sp = sweep.compile_sparse(g, p)
    assert sp.elink is not None and sp.nlinks == g.nlinks
    # the sparse layout derived from the dense plan agrees edge-for-edge
    from repro.sweep.compile import SparsePlan
    sp2 = SparsePlan.from_plan(c)
    np.testing.assert_array_equal(sp.elink, sp2.elink)


# -- the congestion fixed point ----------------------------------------------

def test_congestion_inflates_and_converges():
    pm = pod_model(pod_size=1, alpha={"dcn": 1.0})
    p = pm.params()
    g = _incast(p)
    batch = sweep.latency_grid(p, np.linspace(0.0, 40.0, 16))
    base = Engine(g, params=p, policy=ExecPolicy(cache=None)).run(batch)
    res = Engine(g, params=p,
                 policy=ExecPolicy(congestion="fixed_point", max_iters=32,
                                   tol=1e-9, cache=None)).run(batch)
    # the overloaded link inflates every scenario, and the closure converged
    assert np.all(res.T > base.T)
    assert res.congestion_iters is not None
    assert res.congestion_iters.shape == (batch.S,)
    assert np.all(res.congestion_iters >= 2)
    assert np.all(res.congestion_iters < 32)
    # stronger feedback → more inflation (monotone in α)
    p2 = pod_model(pod_size=1, alpha={"dcn": 2.0}).params()
    hot = Engine(g, params=p2,
                 policy=ExecPolicy(congestion="fixed_point", max_iters=32,
                                   tol=1e-9, cache=None)).run(batch)
    assert np.all(hot.T > res.T)


def test_congestion_one_program_cold_zero_warm():
    """The acceptance bar: an S=250 congested sweep compiles exactly ONE
    XLA program, re-running costs zero, and every convergence knob
    (max_iters, tol, α, β — runtime operands, not trace constants)
    changes results without recompiling."""
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    batch = sweep.latency_grid(p, np.linspace(0.0, 60.0, 250))
    eng = Engine(g, params=p,
                 policy=ExecPolicy(congestion="fixed_point", cache=None))
    w = obs.CompileWatcher()
    with w.watch("congestion.cold") as cold:
        res = eng.run(batch)
    assert cold.new_programs == 1
    with w.watch("congestion.warm") as warm:
        eng.run(batch)
    assert warm.new_programs == 0
    with w.watch("congestion.knobs") as knobs:
        p2 = pod_model(pod_size=1, alpha={"dcn": 3.0}, beta={"dcn": 0.1}) \
            .params()
        r2 = Engine(g, params=p2,
                    policy=ExecPolicy(congestion="fixed_point", max_iters=9,
                                      tol=1e-3, cache=None)).run(batch)
    assert knobs.new_programs == 0
    assert not np.array_equal(r2.T, res.T)
    assert np.all(r2.congestion_iters <= 9)


def test_congestion_composes_with_candidate_axis():
    """K cost blocks × S scenarios through the fixed point: each block
    converges independently, iteration counts ride the K axis."""
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    plan = sweep.compile_plan(g, p)
    rng = np.random.default_rng(3)
    extras = np.where(g.ebytes[None] > 0,
                      rng.uniform(0.0, 10.0, (3, g.num_edges)), 0.0)
    batch = sweep.latency_grid(p, np.linspace(0.0, 30.0, 7))
    eng = Engine(plan, params=p,
                 policy=ExecPolicy(congestion="fixed_point", cache=None))
    res = eng.run(batch, costs=plan.patch_costs(extras))
    assert res.T.shape == (3, batch.S)
    assert res.congestion_iters.shape == (3, batch.S)
    assert np.all(res.congestion_iters >= 1)


def test_congestion_validates_policy_and_query():
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    with pytest.raises(ValueError, match="segment backend only"):
        ExecPolicy(congestion="fixed_point", backend="pallas").validate()
    with pytest.raises(ValueError, match="congestion mode"):
        ExecPolicy(congestion="bursty").validate()
    with pytest.raises(ValueError, match="max_iters"):
        ExecPolicy(congestion="fixed_point", max_iters=0).validate()
    with pytest.raises(ValueError, match="tol"):
        ExecPolicy(congestion="fixed_point", tol=0.0).validate()
    eng = Engine(sweep.compile_plan(g, p),
                 policy=ExecPolicy(congestion="fixed_point", cache=None))
    # a bare plan has no bound params → no (α, β) registry to close over
    with pytest.raises(ValueError, match="bound LogGPS params"):
        eng.run(sweep.latency_grid(p, [0.0, 10.0]))


def test_congestion_cache_keys_distinct():
    """Congestion on/off and different (α, β) registries never collide in
    the result cache; a repeat query hits and keeps the iteration counts."""
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    cache = sweep.SweepCache()
    batch = sweep.latency_grid(p, np.linspace(0.0, 20.0, 9))
    base = Engine(g, params=p, policy=ExecPolicy(cache=cache)).run(batch)
    cong = Engine(g, params=p,
                  policy=ExecPolicy(congestion="fixed_point",
                                    cache=cache)).run(batch)
    assert not np.array_equal(base.T, cong.T)        # no collision
    again = Engine(g, params=p,
                   policy=ExecPolicy(congestion="fixed_point",
                                     cache=cache)).run(batch)
    assert again.from_cache
    np.testing.assert_array_equal(again.T, cong.T)
    np.testing.assert_array_equal(again.congestion_iters,
                                  cong.congestion_iters)
    # a different α registry is a different key (same graph, same grid)
    p2 = pod_model(pod_size=1, alpha={"dcn": 2.0}).params()
    other = Engine(g, params=p2,
                   policy=ExecPolicy(congestion="fixed_point",
                                     cache=cache)).run(batch)
    assert not other.from_cache
    assert not np.array_equal(other.T, cong.T)


def test_congestion_fd_lambda_total_derivative():
    """λ under congestion with ``lam="fd"`` is the TOTAL derivative dT*/dL
    of the congested fixed point (it includes the negative feedback: L↑ →
    T↑ → utilization↓ → effective gaps↓), so it is ≤ the exact critical-
    message count taken at the converged link scales.  Both are meaningful;
    they agree when congestion is inactive."""
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    batch = sweep.latency_grid(p, np.linspace(0.0, 30.0, 8))
    exact = Engine(g, params=p,
                   policy=ExecPolicy(congestion="fixed_point",
                                     cache=None)).run(batch)
    fd = Engine(g, params=p,
                policy=ExecPolicy(congestion="fixed_point", lam="fd",
                                  cache=None)).run(batch)
    assert fd.lam.shape == exact.lam.shape
    np.testing.assert_array_equal(fd.T, exact.T)     # same values program
    dcn = p.class_index("dcn")
    assert np.all(fd.lam[:, dcn] <= exact.lam[:, dcn] + 1e-9)
    assert np.all(np.isfinite(fd.lam))


def test_congestion_validated_against_contention_sim():
    """The acceptance validation loop: on the incast skeleton the DES
    contention injector is ground truth, and the congestion fixed point
    must land strictly closer to it than the load-blind baseline."""
    p = pod_model(pod_size=1, alpha={"dcn": 1.0}).params()
    g = _incast(p)
    batch = sweep.base_batch(p)
    base_T = float(Engine(g, params=p,
                          policy=ExecPolicy(cache=None)).run(batch).T[0])
    cong_T = float(Engine(g, params=p,
                          policy=ExecPolicy(congestion="fixed_point",
                                            max_iters=32, tol=1e-9,
                                            cache=None)).run(batch).T[0])
    sim_T = simulator.simulate(g, p, injector="contention").T
    assert sim_T > base_T                   # the skeleton is congested
    assert base_T < cong_T <= sim_T * 1.5
    assert abs(cong_T - sim_T) < abs(base_T - sim_T)


# -- DES contention injector --------------------------------------------------

def test_simulator_contention_injector():
    p = pod_model(pod_size=2).params()
    g = _incast(p, n=4)
    flow = simulator.simulate(g, p, injector="flow")
    cont = simulator.simulate(g, p, injector="contention")
    assert cont.T > flow.T                  # the shared link serializes
    # ΔL still injects flow-style on top of the queueing
    delayed = simulator.simulate(g, p, 10.0, injector="contention")
    assert delayed.T > cont.T
    # graphs without recorded link ids fall back to per-(class, src, dst)
    # interning and reproduce the same schedule
    bare = dataclasses.replace(g, elink=None, nlinks=0, link_classes=None)
    assert simulator.simulate(bare, p, injector="contention").T \
        == pytest.approx(cont.T, rel=1e-12)
    with pytest.raises(ValueError, match="injector"):
        simulator.simulate(g, p, injector="teleport")
    # an uncontended chain is untouched by the link server
    g2 = synth.allreduce_chain(4, 2, params=p)
    assert simulator.simulate(g2, p, injector="contention").T \
        == pytest.approx(simulator.simulate(g2, p, injector="flow").T)


# -- satellite 1: NaN gap-share guard ----------------------------------------

def test_nan_egap_warns_at_build_and_bandwidth_curve_raises():
    p = cluster_params(L_us=3.0, o_us=5.0)
    b = GraphBuilder(nclass=1, nranks=2)
    u = b.add_calc(0, 1.0)
    v = b.add_calc(1, 1.0)
    with pytest.warns(RuntimeWarning, match="without a gap_us share"):
        b.add_edge(u, v, const_us=50.0, nbytes=4e6, lat=((0, 1),))
        g = b.finalize()
    assert np.isnan(g.egap).sum() == 1
    # params-backed reconstruction keeps the curves finite...
    c = sensitivity.bandwidth_curve(g, p, [1.0, 2.0, 4.0], engine="scalar")
    assert np.all(np.isfinite(c.T))
    # ...but a share that resolves non-finite must raise, not poison
    poisoned = dataclasses.replace(
        g, egap=np.where(np.isnan(g.egap), np.inf, g.egap))
    with pytest.raises(ValueError, match="non-finite"):
        sensitivity.bandwidth_curve(poisoned, p, [1.0, 2.0], engine="scalar")
    bad_params = p.replace(G=(float("nan"),))
    with pytest.raises(ValueError, match="non-finite"):
        sensitivity.bandwidth_curve(g, bad_params, [1.0, 2.0],
                                    engine="scalar")


# -- satellite 2: configurable auto-sparse threshold --------------------------

def test_max_dense_bytes_policy_and_env(monkeypatch):
    p = cluster_params(L_us=3.0, o_us=5.0)
    g = synth.stencil2d(3, 3, 4, params=p)
    batch = sweep.latency_grid(p, [0.0, 10.0])
    # policy threshold below this graph's dense envelope → auto-sparse warns
    with pytest.warns(RuntimeWarning, match="auto-switching"):
        eng = Engine(g, params=p,
                     policy=ExecPolicy(max_dense_bytes=1, cache=None))
    assert eng.MAX_DENSE_BYTES == 1
    res = eng.run(batch)
    assert res.backend == "sparse"
    # the env var configures the same threshold...
    monkeypatch.setenv("REPRO_MAX_DENSE_BYTES", "1")
    with pytest.warns(RuntimeWarning, match="auto-switching"):
        eng2 = Engine(g, params=p, policy=ExecPolicy(cache=None))
    assert eng2.MAX_DENSE_BYTES == 1
    # ...and an explicit policy value wins over it
    eng3 = Engine(g, params=p,
                  policy=ExecPolicy(max_dense_bytes=1 << 30, cache=None))
    assert eng3.MAX_DENSE_BYTES == 1 << 30
    assert eng3.run(batch).backend == "segment"
    monkeypatch.delenv("REPRO_MAX_DENSE_BYTES")
    # above-threshold graphs stay dense and silent
    eng4 = Engine(g, params=p, policy=ExecPolicy(cache=None))
    assert eng4.run(batch).backend == "segment"
    # the sparse run is still bit-identical to the dense one
    np.testing.assert_array_equal(res.T, eng4.run(batch).T)


# -- N-class grids + congestion through the service wire ----------------------

def test_congestion_and_class_names_through_service():
    pm = pod_model(pod_size=1, alpha={"dcn": 1.0})
    p = pm.params()
    g = _incast(p)
    svc = AnalysisService(default_deltas=(0.0, 10.0, 20.0))
    svc.register_graph("incast", g, p)
    line = json.dumps({"kind": "curve", "cls": "dcn",
                       "policy": {"congestion": "fixed_point",
                                  "max_iters": 24, "tol": 1e-8}})
    req = AnalysisRequest.from_json(line)
    resp = svc.handle(req)
    assert resp.ok, resp.error
    assert resp.payload["cls"] == p.class_index("dcn")
    base = svc.handle(AnalysisRequest(kind="curve", cls="dcn"))
    assert np.all(np.asarray(resp.payload["T"])
                  > np.asarray(base.payload["T"]))
    # a malformed congestion block is a protocol error, not a crash
    with pytest.raises(ValueError, match="congestion"):
        AnalysisRequest.from_json(
            json.dumps({"kind": "curve",
                        "policy": {"congestion": "bursty"}}))
    # unknown class names surface per the registry
    bad = svc.handle(AnalysisRequest(kind="curve", cls="infiniband"))
    assert not bad.ok and "unknown network class" in bad.error


def test_sensitivity_resolves_class_names():
    p = pod_model(pod_size=4, ranks_per_host=2).params()
    g = synth.stencil2d(4, 2, 3, params=p)
    deltas = np.linspace(0.0, 40.0, 9)
    by_name = sensitivity.latency_curve(g, p, deltas, cls="dcn")
    by_idx = sensitivity.latency_curve(g, p, deltas, cls=2)
    np.testing.assert_array_equal(by_name.T, by_idx.T)
    np.testing.assert_array_equal(by_name.lam, by_idx.lam)
    tol = sensitivity.latency_tolerance(g, p, (0.01, 0.02, 0.05, 0.1),
                                        cls="ici")
    assert set(tol) == {0.01, 0.02, 0.05, 0.1}
    # scenario grids accept names too (engine- and scalar-path alike)
    grid = sweep.latency_grid(p, deltas, cls="dcn")
    np.testing.assert_array_equal(grid.L[:, 2], p.L[2] + deltas)
    cart = sweep.cartesian_grid(p, lat_deltas={"node": [0.0, 1.0]},
                                gscales={"dcn": [1.0, 2.0]})
    assert cart.S == 4
    # engines memoize per congestion registry — α must split the key
    k1 = sensitivity._params_memo_key(g, p)
    k2 = sensitivity._params_memo_key(
        g, pod_model(pod_size=4, ranks_per_host=2,
                     alpha={"dcn": 1.0}).params())
    assert k1 != k2
