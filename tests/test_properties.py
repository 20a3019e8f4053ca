"""Hypothesis property tests on LLAMP's invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional dep: property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import dag, lp, simulator, synth
from repro.core.loggps import LogGPS


@st.composite
def random_graph(draw):
    seed = draw(st.integers(0, 2**31 - 1))
    nranks = draw(st.integers(2, 6))
    nops = draw(st.integers(8, 80))
    p_msg = draw(st.floats(0.1, 0.7))
    params = LogGPS(L=(draw(st.floats(0.1, 10.0)),),
                    G=(draw(st.floats(1e-6, 1e-3)),),
                    o=draw(st.floats(0.0, 5.0)), S=1e9)
    rng = np.random.default_rng(seed)
    g = synth.random_dag(rng, nranks=nranks, nops=nops, p_msg=p_msg,
                         params=params)
    return g, params


@given(random_graph())
@settings(max_examples=40, deadline=None)
def test_dag_equals_des_random(gp):
    g, params = gp
    assert dag.evaluate(g, params).T == pytest.approx(
        simulator.simulate(g, params).T, rel=1e-12)


@given(random_graph())
@settings(max_examples=25, deadline=None)
def test_dag_equals_lp_random(gp):
    g, params = gp
    sol = lp.predict_runtime(g, params, solver="highs")
    assert sol.T == pytest.approx(dag.evaluate(g, params).T, rel=1e-8)


#: ΔL points on a 1e-3 µs grid over [0, 100]: a slope between two points
#: closer than that is float64 rounding of T (up to ~4e3 µs here), not
#: the graph's — at 1e-6 µs apart it already reads 1.8e-6 of noise
_deltas = st.lists(st.integers(0, 100_000), min_size=3, max_size=6,
                   unique=True).map(lambda xs: [x * 1e-3 for x in xs])


@given(random_graph(), _deltas)
@settings(max_examples=25, deadline=None)
def test_T_monotone_convex_in_L(gp, deltas):
    """T(L) is nondecreasing and convex piecewise-linear in L."""
    g, params = gp
    plan = dag.LevelPlan(g)
    ds = sorted(set(deltas))
    Ts = [plan.forward(params.with_delta(d)).T for d in ds]
    for a, b in zip(Ts[:-1], Ts[1:]):
        assert b >= a - 1e-9                      # monotone
    # convexity: slopes nondecreasing
    slopes = [(Ts[i + 1] - Ts[i]) / (ds[i + 1] - ds[i])
              for i in range(len(ds) - 1) if ds[i + 1] > ds[i]]
    for a, b in zip(slopes[:-1], slopes[1:]):
        assert b >= a - 1e-6


@given(random_graph())
@settings(max_examples=20, deadline=None)
def test_lambda_is_right_derivative(gp):
    g, params = gp
    plan = dag.LevelPlan(g)
    s = plan.forward(params)
    eps = 1e-4
    T_eps = plan.forward(params.with_delta(eps)).T
    assert (T_eps - s.T) / eps == pytest.approx(s.lam[0], abs=1e-3)


@given(random_graph(), st.floats(0.005, 0.1))
@settings(max_examples=20, deadline=None)
def test_tolerance_inversion_random(gp, p):
    g, params = gp
    plan = dag.LevelPlan(g)
    T0 = plan.forward(params).T
    tol = dag.tolerance(g, params, p, plan=plan)
    if np.isinf(tol):
        # λ stays 0: runtime independent of L — verify at a huge L
        assert plan.forward(params.with_delta(1e6)).T == pytest.approx(
            T0, rel=1e-9)
    else:
        assert plan.forward(params.with_delta(tol)).T == pytest.approx(
            (1 + p) * T0, rel=1e-5)


@given(random_graph())
@settings(max_examples=15, deadline=None)
def test_ipm_duality(gp):
    """IPM primal equals HiGHS primal; duals feasible (λ ≥ 0)."""
    g, params = gp
    prob = lp.build_lp(g, params)
    from repro.core.ipm import solve_ipm
    sol = solve_ipm(prob)
    ref = lp.solve_highs(prob)
    assert sol.T == pytest.approx(ref.T, rel=1e-4, abs=1e-4)
    assert (sol.lam >= -1e-6).all()


# -- zero-recompile cost patching: patched ≡ rebuilt, bit for bit -------------

_PATCH_CACHE: dict = {}


def _placement_fixture():
    """One biased placement workload + its compiled base plan and warm
    engine, built once (the property below replays many swap sequences
    against it — exactly the greedy loop's access pattern)."""
    if "fix" not in _PATCH_CACHE:
        from repro.core import placement
        from repro.core.graph import GraphBuilder
        from repro import sweep as sweep_mod

        P = 8
        zero = LogGPS(L=(0.0,), G=(0.0,), o=0.5, S=1e18)
        b = GraphBuilder(P, 1)
        for it in range(4):
            for idx, r in enumerate(range(0, P, 2)):
                b.add_calc(r, 1.0)
                sz = 65536.0 * (1.0 + 0.5 * idx)
                b.add_message(r, r + 1, sz, zero)
                b.add_message(r + 1, r, sz, zero)
        g = b.finalize()
        phi = placement.ArchTopology.two_tier(P, 4, L_fast=1.0, L_slow=20.0,
                                              G_fast=1e-5, G_slow=4e-5)
        base = sweep_mod.compile_plan(g)
        eng = sweep_mod.Engine(base, policy=sweep_mod.ExecPolicy(cache=None))
        batch = sweep_mod.ScenarioBatch(L=np.asarray([[0.0], [5.0], [10.0]]),
                                        gscale=np.ones((3, 1)))
        _PATCH_CACHE["fix"] = (g, phi, base, eng, batch)
    return _PATCH_CACHE["fix"]


@given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 7)),
                min_size=1, max_size=6))
@settings(max_examples=12, deadline=None)
def test_patched_costs_bit_equal_rebuilt_random_swaps(swaps):
    """Random swap sequences (the greedy placement loop's candidate
    mappings): T/λ/ρ of the once-compiled patched plan must be bit-equal
    to freshly rebuilt plans for every prefix mapping of the sequence."""
    pytest.importorskip("jax")
    from repro.core import placement
    from repro import sweep as sweep_mod

    g, phi, base, eng, batch = _placement_fixture()
    pi = np.arange(g.nranks)
    extras = []
    for (i, j) in swaps:
        pi[i], pi[j] = pi[j], pi[i]
        extras.append(placement.mapping_edge_cost(g, phi, pi))
    res = eng.run(batch, costs=base.patch_costs(np.stack(extras)))
    for k, ex in enumerate(extras):
        reb = sweep_mod.compile_plan(g, extra_edge_cost=ex)
        ref = sweep_mod.Engine(
            reb, policy=sweep_mod.ExecPolicy(cache=None)).run(batch)
        np.testing.assert_array_equal(res.T[k], ref.T)
        np.testing.assert_array_equal(res.lam[k], ref.lam)
        np.testing.assert_array_equal(res.rho[k], ref.rho)


# -- zero-recompile structure patching: patched ≡ rebuilt, bit for bit --------

def _rewire_fixture():
    """One random-DAG workload + compiled base plan + warm engine, built
    once (the property below replays many rewiring batches against it —
    exactly a topology study's access pattern)."""
    if "rewire" not in _PATCH_CACHE:
        pytest.importorskip("jax")
        from repro import sweep as sweep_mod
        p = LogGPS(L=(3.0,), G=(1e-5,), o=1.0, S=1e9)
        g = synth.random_dag(np.random.default_rng(5), nranks=4, nops=36,
                             p_msg=0.5, params=p)
        base = sweep_mod.compile_plan(g, p)
        eng = sweep_mod.Engine(base, params=p,
                               policy=sweep_mod.ExecPolicy(cache=None))
        batch = sweep_mod.latency_grid(p, [0.0, 10.0, 30.0])
        _PATCH_CACHE["rewire"] = (g, p, base, eng, batch)
    return _PATCH_CACHE["rewire"]


def _filtered(g, keep, src):
    """Ground-up rebuild oracle: the graph with edges removed/re-sourced,
    levels and in-edge CSR recomputed from scratch (the independent
    construction a structure patch must be bit-equal to)."""
    import dataclasses as dc
    from repro.core.graph import _topo_levels
    nv = g.num_vertices
    esrc = src[keep].astype(np.int32)
    edst = g.edst[keep]
    level = _topo_levels(nv, esrc, edst)
    in_ptr = np.zeros(nv + 1, np.int64)
    np.cumsum(np.bincount(edst, minlength=nv), out=in_ptr[1:])
    return dc.replace(
        g, esrc=esrc, edst=edst, econst=g.econst[keep],
        ebytes=g.ebytes[keep], elat=g.elat[keep],
        egap=None if g.egap is None else g.egap[keep],
        egclass=None if g.egclass is None else g.egclass[keep],
        in_ptr=in_ptr,
        in_edge=np.argsort(edst, kind="stable").astype(np.int32),
        level=level, nlevels=int(level.max(initial=0)) + 1)


@given(st.lists(
    st.tuples(st.lists(st.integers(0, 10**6), max_size=6),
              st.lists(st.tuples(st.integers(0, 10**6),
                                 st.integers(0, 10**6)), max_size=4)),
    min_size=1, max_size=4))
@settings(max_examples=12, deadline=None)
def test_patched_structure_bit_equal_rebuilt_random_rewiring(variants):
    """Random edge rewirings (removals + level-respecting source moves —
    a topology study's candidate structures): T/λ/ρ of the once-compiled
    structure-batched run must be bit-equal to freshly rebuilt graphs
    compiled from scratch, per variant, even though the rebuilds settle
    on different (tighter) level schedules."""
    from repro import sweep as sweep_mod

    g, p, base, eng, batch = _rewire_fixture()
    ne = g.num_edges
    keeps, srcs = [], []
    for removals, rewires in variants:
        keep = np.ones(ne, dtype=bool)
        for i in removals:
            keep[i % ne] = False
        src = g.esrc.astype(np.int64).copy()
        for ei, vi in rewires:
            e = ei % ne
            # any vertex strictly below the destination's envelope level
            # is a legal new source (the class of rewirings the patch
            # supports); the rebuild re-levels from scratch regardless
            cand = np.nonzero(g.level < g.level[g.edst[e]])[0]
            if cand.size:
                src[e] = cand[vi % cand.size]
        keeps.append(keep)
        srcs.append(src)
    sb = base.patch_structure(src=np.stack(srcs), keep=np.stack(keeps))
    res = eng.run(batch, structure=sb)
    assert res.axes == ("B", "S")
    for b in range(len(keeps)):
        reb = sweep_mod.compile_plan(_filtered(g, keeps[b], srcs[b]), p)
        ref = sweep_mod.Engine(
            reb, params=p,
            policy=sweep_mod.ExecPolicy(cache=None)).run(batch)
        np.testing.assert_array_equal(res.T[b], ref.T)
        np.testing.assert_array_equal(res.lam[b], ref.lam)
        np.testing.assert_array_equal(res.rho[b], ref.rho)


@given(st.integers(2, 5), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_injection_equivalence(pdim, iters):
    """DES with flow injection ΔL ≡ analytical model at L+ΔL (Fig 8D)."""
    params = LogGPS(L=(2.0,), G=(1e-4,), o=1.0, S=1e9)
    g = synth.stencil2d(pdim, pdim, iters, params=params)
    for dL in (0.0, 3.5, 17.0):
        assert simulator.simulate(g, params, dL, injector="flow").T == \
            pytest.approx(dag.evaluate(g, params.with_delta(dL)).T, rel=1e-12)
