"""The sparse float64 forward's two level steps, pinned bit-exact.

A plan whose ``[Vmax_lv, Dmax]`` in-edge view pads at most twice the edge
window runs the ``indeg`` step (maxima and masked selects over each
slot's in-edges, indices shared by all scenarios); any other runs the
``segment`` step (``segment_max`` over the window's destinations).  Both
must give T, λ and ρ bit-identical to the segment backend and to the
scalar oracle ``dag.LevelPlan.forward``, ties included: values within
ATOL, equal slopes with different λ rows, exact symmetric ties.  Each
case also checks which step its dispatch recorded on ``sweep.execute``.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro import obs, sweep
from repro.core import dag, synth
from repro.core.graph import GraphBuilder
from repro.core.loggps import LogGPS
from repro.sweep import engine as sweep_engine

ATOL = sweep_engine.ATOL


def _lattice_params(nclass: int) -> LogGPS:
    """Integer latencies and overheads, no gap term: path lengths land on
    a lattice, so exact ties are common; with two classes of equal L the
    tied paths differ in which class's hops they count."""
    return LogGPS(L=(2.0,) * nclass, G=(0.0,) * nclass, o=1.0, S=1e9,
                  class_names=tuple("ab"[:nclass]),
                  rank_of_class=(None if nclass == 1
                                 else lambda s, d: (s + d) % 2))


def _tie_dag(seed: int, p: LogGPS, nranks: int = 5, nops: int = 90):
    """Random rank-chained DAG on the lattice, compute costs nudged by
    multiples of 2e-13: values tie exactly or within ATOL, not beyond."""
    rng = np.random.default_rng(seed)
    b = GraphBuilder(nranks, p.nclass)
    for _ in range(nops):
        if rng.random() < 0.5:
            src, dst = rng.choice(nranks, size=2, replace=False)
            b.add_message(int(src), int(dst), 64.0, p)
        else:
            b.add_calc(int(rng.integers(nranks)),
                       float(rng.integers(1, 4))
                       + 2e-13 * float(rng.integers(-2, 3)))
    return b.finalize()


def _gather(P: int, p: LogGPS):
    """Every rank computes and sends to one join vertex on rank 0: an
    in-degree of P, which would pad the in-edge view past twice the edge
    window."""
    b = GraphBuilder(P, p.nclass)
    x = b.add_sync_vertex(0)
    for r in range(P):
        b.add_calc(r, float(1 + r % 3))
        s = b.add_send_vertex(r, p.o)
        b.add_edge(s, x, const_us=0.0, lat=((0, 1),), gap_us=0.0)
    c = b.add_calc(0, 1.0)
    b.add_dep(x, c)
    return b.finalize()


def _case(name, lulesh_graph):
    if name == "lulesh_tp2_symmetric":        # exact ties everywhere
        return lulesh_graph(2, 2)
    if name == "lulesh_tp4_jitter":
        return lulesh_graph(4, 1, jitter=0.1, seed=3_100_007_919)
    if name == "atol_ties":
        p = _lattice_params(1)
        return _tie_dag(16, p), p
    if name == "slope_ties_2class":
        p = _lattice_params(2)
        return _tie_dag(13, p), p
    if name == "allreduce_chain":
        p = _lattice_params(1)
        return synth.allreduce_chain(8, 3, params=p), p
    p = _lattice_params(1)                       # "gather"
    return _gather(32, p), p


CASES = [("lulesh_tp2_symmetric", "indeg"), ("lulesh_tp4_jitter", "indeg"),
         ("atol_ties", "indeg"), ("slope_ties_2class", "indeg"),
         ("allreduce_chain", "indeg"), ("gather", "segment")]


def _ties(g, p) -> tuple:
    """(exact, within ATOL but not equal): vertices with two such in-edge
    candidates, in the oracle's forward at the base parameters."""
    t = dag.LevelPlan(g).forward(p).t_end
    cand = t[g.esrc] + g.econst + g.elat @ np.asarray(p.L)
    exact = near = 0
    for v in np.unique(g.edst):
        c = cand[g.edst == v]
        d = np.abs(c[:, None] - c[None, :])[np.triu_indices(len(c), 1)]
        exact += bool(np.any(d == 0))
        near += bool(np.any((d > 0) & (d <= ATOL)))
    return exact, near


@pytest.mark.parametrize("name,step", CASES, ids=[c for c, _ in CASES])
def test_sparse_step_bit_exact(name, step, lulesh_graph):
    g, p = _case(name, lulesh_graph)
    sp_plan = sweep.compile_sparse(g, p)
    assert sp_plan.step == step
    if name in ("atol_ties", "slope_ties_2class"):
        assert min(_ties(g, p)) > 0
    batch = sweep.latency_grid(p, np.array([0.0, 1.0, 2.5, 7.0, 40.0]))
    seg = sweep.Engine(g, params=p,
                       policy=sweep.ExecPolicy(cache=None)).run(batch)
    eng = sweep.Engine(sp_plan, params=p,
                       policy=sweep.ExecPolicy(backend="sparse", cache=None))
    with obs.collect() as spans:
        res = eng.run(batch)
    (ex,) = [e for e in spans if e.name == "sweep.execute"]
    assert ex.args["step"] == step
    np.testing.assert_array_equal(res.T, seg.T)
    np.testing.assert_array_equal(res.lam, seg.lam)
    np.testing.assert_array_equal(res.rho, seg.rho)
    plan = dag.LevelPlan(g)
    for s_i in range(batch.S):
        s = plan.forward(p.replace(L=tuple(batch.L[s_i])))
        assert res.T[s_i] == s.T, s_i
        np.testing.assert_array_equal(res.lam[s_i], s.lam)
        np.testing.assert_array_equal(res.rho[s_i], s.rho())


@pytest.mark.parametrize("name", [c for c, s in CASES if s == "indeg"])
@pytest.mark.parametrize("want_lam", [True, False])
def test_indeg_step_matches_segment_step(name, want_lam, lulesh_graph):
    """The two steps' programs over the same staged slot lists agree bit
    for bit, values-only runs included."""
    import jax
    g, p = _case(name, lulesh_graph)
    sp = sweep.compile_sparse(g, p)
    batch = sweep.latency_grid(p, np.array([0.0, 3.0, 19.0]))
    eng = sweep.Engine(sp, params=p,
                       policy=sweep.ExecPolicy(backend="sparse", cache=None))
    with jax.enable_x64():
        arrs = eng._arrays("sparse")
        L, GS = jax.numpy.asarray(batch.L), jax.numpy.asarray(batch.gscale)
        dims = (sp.Emax_lv, sp.Vmax_lv)
        T0, lam0 = sweep_engine._get_forward(
            "sparse", want_lam, sparse_dims=dims)(*arrs, L, GS)
        T1, lam1 = sweep_engine._get_forward(
            "sparse", want_lam, sparse_dims=dims + (sp.Dmax,))(
                *arrs, *eng._arrays("indeg"), L, GS)
        np.testing.assert_array_equal(np.asarray(T1), np.asarray(T0))
        np.testing.assert_array_equal(np.asarray(lam1), np.asarray(lam0))


def test_indeg_plan_arrays_and_occupancy(lulesh_graph):
    """``vin0``/``vdeg`` delimit each slot's in-edge run; the dispatch
    reports the view's occupancy on the envelope gauge."""
    from repro.obs import metrics as obs_metrics
    g, p = lulesh_graph(2, 1)
    sp = sweep.compile_sparse(g, p)
    ne, nv = sp.ne, sp.nv
    for v in range(sp.vcost.shape[0]):
        run = sp.edst_slot[sp.vin0[v]:sp.vin0[v] + sp.vdeg[v]]
        assert np.all(run == v)
    assert sp.vdeg[:nv].sum() == ne and not sp.vdeg[nv:].any()
    assert sp.Dmax >= sp.vdeg.max() and sp.Dmax & (sp.Dmax - 1) == 0
    sweep.Engine(sp, params=p, policy=sweep.ExecPolicy(
        backend="sparse", cache=None)).run(sweep.base_batch(p))
    gauge = obs_metrics.REGISTRY.get("sweep_envelope_occupancy")
    assert gauge.value(axis="indeg") == pytest.approx(
        ne / (sp.nlevels * sp.Vmax_lv * sp.Dmax))
