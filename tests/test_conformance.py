"""Cross-backend conformance suite: backend × output × packing, one matrix.

The repo's equivalence guarantees used to live as scattered asserts in
``test_sweep.py``; this file pins them in one parametrized matrix over

    backend ∈ {scalar, segment, pallas}
    output  ∈ {T, λ, ρ}
    packing ∈ {solo, multi (packed MultiPlan), patched (candidate-cost axis)}

on a shared case set (single- and two-class params, a tie-heavy collective
chain, random-DAG matrix) so a new backend or a new packing mode has one
place to conform to.

Tolerance contract (no looser than PR 3's):

* segment vs scalar — **bit-exact** for solo and multi (same float64 ops,
  same ATOL tie-breaks, and MultiPlan padding only adds masked −∞
  candidates).  Patched cells compare at 1e-12 relative: the scalar engine
  adds ``extra_edge_cost`` after ``econst + elat @ L`` while the compiled
  path bakes it into ``econst`` first — same terms, different float
  association.  The compiled-vs-compiled patched guarantee IS bit-exact
  (patched ≡ rebuilt plan, asserted below and property-tested in
  ``test_properties.py``).
* pallas vs scalar — ≤1e-5 relative on T/λ (float32 kernel accumulators),
  ρ at 1e-4 (a ratio of the two).
* scalar itself anchors against *independent* oracles: the explicit HiGHS
  LP's duals (solo/multi) and a graph rebuilt with the extra costs baked
  into ``econst`` (patched).
* sparse vs segment — **bit-exact** on T/λ/ρ (same float64 reductions over
  compact slot lists instead of padded dense tensors); vs pallas at the
  pallas tolerances.  Dedicated tests below (the sparse backend is
  solo-only: one graph per compiled program).
"""

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import dag, lp, synth
from repro.core.loggps import LogGPS, cluster_params, pod_model
from repro import sweep

# Shim coverage: this suite deliberately drives the deprecated
# SweepEngine/MultiSweepEngine surface to pin the shims bit-identical to
# the unified Engine — CI's -W error::DeprecationWarning is relaxed here.
pytestmark = pytest.mark.filterwarnings("default::DeprecationWarning")

BACKENDS = ("scalar", "segment", "pallas")
OUTPUTS = ("T", "lam", "rho")
PACKINGS = ("solo", "multi", "patched")
#: populated-axis combinations of the unified Engine (S is always there)
AXISSETS = ("S", "KS", "GS", "GKS")
K = 3                                    # candidate cost blocks per case


@dataclasses.dataclass
class Case:
    name: str
    g: object
    params: LogGPS
    batch: sweep.ScenarioBatch
    extras: np.ndarray                   # [K, ne] placement-style Φ costs


def _make_cases():
    p1 = cluster_params(L_us=3.0, o_us=5.0)
    p2 = pod_model(pod_size=2).params()
    # 3-class registry (intra-node / ICI / DCN): 8 ranks = 2 ranks/host,
    # 4 ranks/pod, 2 pods — every class appears on some message edge
    p3 = pod_model(pod_size=4, ranks_per_host=2).params()
    specs = [
        ("stencil", synth.stencil2d(3, 3, 4, params=p1), p1),
        ("cg", synth.cg_like(2, 2, 3, params=p1), p1),
        ("allreduce", synth.allreduce_chain(8, 3, params=p1), p1),  # tie-heavy
        ("stencil2c", synth.stencil2d(2, 2, 3, params=p2), p2),     # 2-class
        ("stencil3c", synth.stencil2d(4, 2, 3, params=p3), p3),     # 3-class
    ]
    rng = np.random.default_rng(42)
    cases = []
    for name, g, p in specs:
        batch = sweep.latency_grid(p, np.linspace(0.0, 60.0, 5))
        extras = np.where(g.ebytes[None, :] > 0,
                          rng.uniform(0.0, 10.0, size=(K, g.num_edges)),
                          0.0)
        cases.append(Case(name=name, g=g, params=p, batch=batch,
                          extras=extras))
    return cases


CASES = _make_cases()


def _scalar_run(case, extra=None):
    """The scalar oracle: one LevelPlan, one forward per scenario row."""
    plan = dag.LevelPlan(case.g)
    S, nc = case.batch.S, case.g.nclass
    T = np.empty(S)
    lam = np.empty((S, nc))
    rho = np.empty((S, nc))
    for i in range(S):
        s = plan.forward(case.params.replace(L=tuple(case.batch.L[i])),
                         extra_edge_cost=extra)
        T[i], lam[i], rho[i] = s.T, s.lam, s.rho()
    return {"T": T, "lam": lam, "rho": rho}


@pytest.fixture(scope="module")
def scalar_ref():
    """Oracle outputs per (case, packing): solo ≡ multi for the scalar
    engine (no packing); patched stacks the K per-extra evaluations."""
    ref = {}
    for c in CASES:
        base = _scalar_run(c)
        ref[(c.name, "solo")] = base
        ref[(c.name, "multi")] = base
        runs = [_scalar_run(c, extra=c.extras[k]) for k in range(K)]
        ref[(c.name, "patched")] = {
            out: np.stack([r[out] for r in runs]) for out in OUTPUTS}
    return ref


@pytest.fixture(scope="module")
def computed():
    """Engine outputs per (backend, packing, case) — computed once, the
    parametrized matrix below only compares slices."""
    out = {}
    plans = {c.name: sweep.compile_plan(c.g, c.params) for c in CASES}
    for be in ("segment", "pallas"):
        for c in CASES:
            eng = sweep.SweepEngine(compiled=plans[c.name], params=c.params,
                                    backend=be, cache=None)
            r = eng.run(c.batch)
            out[(be, "solo", c.name)] = {"T": r.T, "lam": r.lam, "rho": r.rho}
            rc = eng.run(c.batch, costs=plans[c.name].patch_costs(c.extras))
            out[(be, "patched", c.name)] = {"T": rc.T, "lam": rc.lam,
                                            "rho": rc.rho}
        plan_list = [plans[c.name] for c in CASES]
        for idx in sweep.group_plans(plan_list):
            meng = sweep.MultiSweepEngine(
                multi=sweep.pack_plans([plan_list[i] for i in idx]),
                names=[CASES[i].name for i in idx], backend=be, cache=None)
            res = meng.run([CASES[i].batch for i in idx])
            for j, i in enumerate(idx):
                out[(be, "multi", CASES[i].name)] = {
                    "T": res.T[j], "lam": res.lam[j], "rho": res.rho[j]}
    return out


def _scalar_anchor(case, packing):
    """Independent oracle for the scalar row of the matrix."""
    if packing in ("solo", "multi"):
        # the explicit HiGHS LP: primal T and the reduced costs of ℓ (λ);
        # two scenario rows keep the LP solves bounded
        rows = (0, case.batch.S - 1)
        T = np.empty(len(rows))
        lam = np.empty((len(rows), case.g.nclass))
        for n, i in enumerate(rows):
            p = case.params.replace(L=tuple(case.batch.L[i]))
            if packing == "solo":
                sol = lp.solve_highs(lp.build_lp(case.g, p))
                T[n], lam[n] = sol.T, sol.lam
            else:
                # fresh-plan construction path (dag.evaluate) — plan reuse
                # inside the oracle must not change a single bit
                s = dag.evaluate(case.g, p)
                T[n], lam[n] = s.T, s.lam
        L = case.batch.L[list(rows)]
        rho = np.where(T[:, None] > 0, L * lam / T[:, None], 0.0)
        return rows, {"T": T, "lam": lam, "rho": rho}
    # patched: a graph REBUILT with the extra baked into econst — the
    # independent construction the patch must be equivalent to
    runs = []
    for k in range(K):
        g2 = dataclasses.replace(case.g,
                                 econst=case.g.econst + case.extras[k])
        c2 = Case(name=case.name, g=g2, params=case.params,
                  batch=case.batch, extras=case.extras)
        runs.append(_scalar_run(c2))
    return None, {out: np.stack([r[out] for r in runs]) for out in OUTPUTS}


def _tol(backend, packing, output):
    """Comparison tolerance vs the scalar oracle ("exact" = bit-equal)."""
    if backend == "segment":
        if packing == "patched":
            # compiled path bakes the extra into econst before adding
            # elat@L; scalar adds it after — same terms, different float
            # association, so ulp-level (not bit) equality
            return dict(rtol=1e-12, atol=1e-12)
        return "exact"
    return {"T": dict(rtol=1e-5, atol=1e-7),
            "lam": dict(rtol=1e-5, atol=1e-5),
            "rho": dict(rtol=1e-4, atol=1e-5)}[output]


@pytest.mark.parametrize("packing", PACKINGS)
@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix(backend, output, packing, scalar_ref, computed):
    for c in CASES:
        ref = scalar_ref[(c.name, packing)][output]
        if backend == "scalar":
            rows, anchor = _scalar_anchor(c, packing)
            got = anchor[output]
            want = ref[list(rows)] if rows is not None else ref
            tol = (dict(rtol=1e-6, atol=1e-6) if packing == "solo"
                   else dict(rtol=1e-12, atol=1e-12))
            np.testing.assert_allclose(got, want, err_msg=c.name, **tol)
            continue
        got = computed[(backend, packing, c.name)][output]
        tol = _tol(backend, packing, output)
        if tol == "exact":
            np.testing.assert_array_equal(got, ref, err_msg=c.name)
        else:
            np.testing.assert_allclose(got, ref, err_msg=c.name, **tol)


def test_patched_bit_equal_rebuilt():
    """The compiled-vs-compiled tentpole guarantee: row k of a cost-batched
    run is bit-identical to a solo run of a plan rebuilt with
    ``compile_plan(extra_edge_cost=extras[k])`` — per backend, per output.
    (The scalar comparison above is ulp-level; THIS one is exact, because
    both compiled paths perform the identical baked addition.)"""
    for c in CASES:
        base = sweep.compile_plan(c.g, c.params)
        for be in ("segment", "pallas"):
            eng = sweep.SweepEngine(compiled=base, params=c.params,
                                    backend=be, cache=None)
            res = eng.run(c.batch, costs=base.patch_costs(c.extras))
            for k in range(K):
                reb = sweep.compile_plan(c.g, c.params,
                                         extra_edge_cost=c.extras[k])
                assert reb.shape_key == base.shape_key  # same XLA program
                ref = sweep.SweepEngine(compiled=reb, params=c.params,
                                        backend=be, cache=None).run(c.batch)
                np.testing.assert_array_equal(res.T[k], ref.T,
                                              err_msg=f"{c.name}/{be}")
                np.testing.assert_array_equal(res.lam[k], ref.lam,
                                              err_msg=f"{c.name}/{be}")
                np.testing.assert_array_equal(res.rho[k], ref.rho,
                                              err_msg=f"{c.name}/{be}")


def test_with_extra_cost_shares_structure():
    """``with_extra_cost`` = a 1-candidate patch that keeps every structure
    array shared (same shape bucket → same compiled program) while the
    content hash moves with the cost block."""
    c = CASES[0]
    base = sweep.compile_plan(c.g, c.params)
    patched = base.with_extra_cost(c.extras[0])
    assert patched.shape_key == base.shape_key
    assert patched.vsrc is base.vsrc and patched.emask is base.emask
    assert patched.content_hash() != base.content_hash()
    a = sweep.SweepEngine(compiled=patched, params=c.params, cache=None) \
        .run(c.batch)
    b = sweep.SweepEngine(
        compiled=sweep.compile_plan(c.g, c.params,
                                    extra_edge_cost=c.extras[0]),
        params=c.params, cache=None).run(c.batch)
    np.testing.assert_array_equal(a.T, b.T)
    np.testing.assert_array_equal(a.lam, b.lam)


def test_random_graph_matrix():
    """The ≥100 random graph × scenario matrix (PR 1/PR 3 headline tests,
    absorbed here): segment bit-exact vs scalar, pallas ≤1e-5 vs segment —
    T, λ and ρ on every combination."""
    rng = np.random.default_rng(7)
    combos = 0
    for i in range(25):
        p = LogGPS(L=(float(rng.uniform(0.5, 8.0)),),
                   G=(float(rng.uniform(1e-6, 1e-4)),),
                   o=float(rng.uniform(0.0, 4.0)), S=1e9)
        g = synth.random_dag(rng, nranks=int(rng.integers(2, 5)), nops=40,
                             p_msg=float(rng.uniform(0.2, 0.6)), params=p)
        eng = sweep.SweepEngine(g, p, cache=None)
        deltas = np.sort(rng.uniform(0.0, 60.0, size=4))
        batch = sweep.latency_grid(p, deltas)
        seg = eng.run(batch)
        plan = dag.LevelPlan(g)
        for s_i in range(batch.S):
            s = plan.forward(p.replace(L=tuple(batch.L[s_i])))
            assert seg.T[s_i] == s.T, (i, s_i)
            np.testing.assert_array_equal(seg.lam[s_i], s.lam)
            np.testing.assert_array_equal(seg.rho[s_i], s.rho())
        pal = eng.run(batch, backend="pallas")
        assert pal.backend == "pallas"
        np.testing.assert_allclose(pal.T, seg.T, rtol=1e-5)
        np.testing.assert_allclose(pal.lam, seg.lam, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(pal.rho, seg.rho, rtol=1e-4, atol=1e-5)
        combos += batch.S
    assert combos >= 100


def test_sparse_backend_conformance():
    """The sparse rows of the matrix: compact slot lists (float64 segment
    reductions, no dense padding) are **bit-exact** with the segment
    backend on T, λ and ρ for every case — whether the sparse layout was
    compiled directly from the graph or derived lazily from a bound dense
    plan — and within the pallas tolerances of the pallas backend."""
    for c in CASES:
        seg = sweep.Engine(c.g, params=c.params,
                           policy=sweep.ExecPolicy(cache=None)).run(c.batch)
        # direct compile_sparse path (what million-edge graphs take)
        eng = sweep.Engine(c.g, params=c.params,
                           policy=sweep.ExecPolicy(backend="sparse",
                                                   cache=None))
        sp = eng.run(c.batch)
        assert sp.backend == "sparse"
        np.testing.assert_array_equal(sp.T, seg.T, err_msg=c.name)
        np.testing.assert_array_equal(sp.lam, seg.lam, err_msg=c.name)
        np.testing.assert_array_equal(sp.rho, seg.rho, err_msg=c.name)
        # lazily-derived layout (dense engine, per-run backend override)
        eng2 = sweep.Engine(sweep.compile_plan(c.g, c.params),
                            params=c.params,
                            policy=sweep.ExecPolicy(cache=None))
        sp2 = eng2.run(c.batch, backend="sparse")
        np.testing.assert_array_equal(sp2.T, seg.T, err_msg=c.name)
        np.testing.assert_array_equal(sp2.lam, seg.lam, err_msg=c.name)
        pal = eng2.run(c.batch, backend="pallas")
        np.testing.assert_allclose(sp.T, pal.T, rtol=1e-5, atol=1e-7,
                                   err_msg=c.name)
        np.testing.assert_allclose(sp.lam, pal.lam, rtol=1e-5, atol=1e-5,
                                   err_msg=c.name)
        np.testing.assert_allclose(sp.rho, pal.rho, rtol=1e-4, atol=1e-5,
                                   err_msg=c.name)
        # dtype="float32" pins the slot-list (max,+) Pallas kernel flavor
        # of the sparse backend — the pallas tolerances apply
        spk = sweep.Engine(c.g, params=c.params,
                           policy=sweep.ExecPolicy(
                               backend="sparse", dtype="float32",
                               cache=None)).run(c.batch)
        assert spk.backend == "sparse"
        np.testing.assert_allclose(spk.T, seg.T, rtol=1e-5, atol=1e-7,
                                   err_msg=c.name)
        np.testing.assert_allclose(spk.lam, seg.lam, rtol=1e-5, atol=1e-5,
                                   err_msg=c.name)
        np.testing.assert_allclose(spk.rho, seg.rho, rtol=1e-4, atol=1e-5,
                                   err_msg=c.name)


def test_sparse_random_graph_matrix():
    """Random-DAG sweep for the sparse backend: bit-exact vs the scalar
    oracle (the same guarantee the segment rows carry)."""
    rng = np.random.default_rng(17)
    for i in range(8):
        p = LogGPS(L=(float(rng.uniform(0.5, 8.0)),),
                   G=(float(rng.uniform(1e-6, 1e-4)),),
                   o=float(rng.uniform(0.0, 4.0)), S=1e9)
        g = synth.random_dag(rng, nranks=int(rng.integers(2, 5)), nops=40,
                             p_msg=float(rng.uniform(0.2, 0.6)), params=p)
        batch = sweep.latency_grid(p, np.sort(rng.uniform(0.0, 60.0, 4)))
        res = sweep.Engine(g, params=p,
                           policy=sweep.ExecPolicy(backend="sparse",
                                                   cache=None)).run(batch)
        plan = dag.LevelPlan(g)
        for s_i in range(batch.S):
            s = plan.forward(p.replace(L=tuple(batch.L[s_i])))
            assert res.T[s_i] == s.T, (i, s_i)
            np.testing.assert_array_equal(res.lam[s_i], s.lam)
            np.testing.assert_array_equal(res.rho[s_i], s.rho())


def test_lambda_matches_highs_marginals():
    """λ from the batched backtrace ≡ reduced costs of ℓ (lower-bound
    marginals) from the explicit HiGHS LP (absorbed from test_sweep)."""
    p = cluster_params(L_us=3.0, o_us=5.0)
    g = synth.stencil2d(3, 3, 3, params=p)
    eng = sweep.SweepEngine(g, p, cache=None)
    for dL in (0.0, 10.0):
        pt = p.with_delta(dL)
        res = eng.run(sweep.base_batch(pt))
        sol = lp.solve_highs(lp.build_lp(g, pt))
        assert res.T[0] == pytest.approx(sol.T, rel=1e-8)
        assert res.lam[0, 0] == pytest.approx(sol.lam[0], abs=1e-6)


def test_rejections():
    """Conformance of the error surface: unknown backends, mismatched cost
    envelopes, view-limited batches on the wrong backend, plans without
    edge-position records."""
    c = CASES[0]
    base = sweep.compile_plan(c.g, c.params)
    eng = sweep.SweepEngine(compiled=base, params=c.params, cache=None)
    with pytest.raises(ValueError, match="backend"):
        eng.run(c.batch, backend="cuda")
    with pytest.raises(ValueError, match="edges"):
        base.patch_costs(np.zeros((2, c.g.num_edges + 1)))
    with pytest.raises(ValueError, match="views"):
        base.patch_costs(c.extras, views=("diagonal",))
    # view-limited batches refuse the other backend
    vb = base.patch_costs(c.extras, views=("vertex",))
    with pytest.raises(ValueError, match="vertex view only"):
        eng.run(c.batch, costs=vb, backend="pallas")
    eb = base.patch_costs(c.extras, views=("edge",))
    with pytest.raises(ValueError, match="edge view only"):
        eng.run(c.batch, costs=eb, backend="segment")
    # a cost block minted on ANOTHER plan is refused — by envelope when
    # shapes differ, by the stamped plan hash when bucketing made two
    # distinct graphs share an envelope
    other = sweep.compile_plan(CASES[2].g, CASES[2].params)
    with pytest.raises(ValueError, match="envelope|different plan"):
        eng.run(c.batch, costs=other.patch_costs(
            np.zeros(CASES[2].g.num_edges)))
    g_twin = synth.stencil2d(3, 3, 4, params=c.params, jitter=0.1, seed=9)
    twin = sweep.compile_plan(g_twin, c.params)
    if twin.vconst.shape == base.vconst.shape:       # same shape bucket
        with pytest.raises(ValueError, match="different plan"):
            eng.run(c.batch, costs=twin.patch_costs(
                np.zeros(g_twin.num_edges)))
    # hand-assembled plans (no epos records) cannot patch
    stripped = dataclasses.replace(base, epos_lvl=None, epos_dst=None,
                                   epos_d=None, epos_e=None)
    with pytest.raises(ValueError, match="edge-position"):
        stripped.patch_costs(c.extras)
    # the old costs × shard rejection is GONE: the unified engine shards
    # whichever populated axis the policy picks (scenarios by default;
    # single-device in-process, so this degrades to an unsharded run)
    sharded = eng.run(c.batch, costs=base.patch_costs(c.extras), shard=True)
    plain = eng.run(c.batch, costs=base.patch_costs(c.extras))
    np.testing.assert_array_equal(sharded.T, plain.T)
    # sharding an axis the query does not populate is still an error
    eng2 = sweep.Engine(base, params=c.params,
                        policy=sweep.ExecPolicy(shard=True, shard_axis="K",
                                                cache=None))
    with pytest.raises(ValueError, match="candidate axis"):
        eng2.run(c.batch)
    with pytest.raises(ValueError, match="graph axis"):
        eng2.run(c.batch, costs=base.patch_costs(c.extras),
                 shard_axis="G")


# -- the unified Engine: full G×K×S populated-axis matrix ---------------------

def _bucketable_cases():
    """The single-class cases share nclass and can ride one graph axis."""
    cs = [c for c in CASES if c.params.nclass == 1][:2]
    assert len(cs) == 2
    return cs


@pytest.fixture(scope="module")
def unified_ref():
    """Legacy-path references: per (case, k) a SOLO run of a plan REBUILT
    with cost block k (the equivalent legacy solo/rebuild runs every
    populated-axis combination must reproduce), per backend."""
    ref = {}
    for c in _bucketable_cases():
        for be in ("segment", "pallas"):
            solo = sweep.SweepEngine(c.g, c.params, backend=be,
                                     cache=None).run(c.batch)
            ref[(c.name, be, None)] = solo
            for k in range(K):
                reb = sweep.compile_plan(c.g, c.params,
                                         extra_edge_cost=c.extras[k])
                ref[(c.name, be, k)] = sweep.SweepEngine(
                    compiled=reb, params=c.params, backend=be,
                    cache=None).run(c.batch)
    return ref


@pytest.mark.parametrize("axisset", AXISSETS)
@pytest.mark.parametrize("backend", ("segment", "pallas"))
def test_unified_axis_matrix(backend, axisset, unified_ref):
    """Every populated-axis combination of the unified Engine against the
    equivalent legacy-path runs: segment rows bit-equal, pallas ≤1e-5
    relative — T, λ and ρ alike.  The G×K×S cell is the combination NO
    legacy engine supported (per-graph candidate axes on a packed graph
    axis); its reference is the cartesian product of solo rebuild runs."""
    cases = _bucketable_cases()
    pol = sweep.ExecPolicy(backend=backend, cache=None)
    has_G, has_K = "G" in axisset, "K" in axisset

    if has_G:
        eng = sweep.Engine([sweep.compile_plan(c.g, c.params) for c in cases],
                           names=[c.name for c in cases], policy=pol)
        targets = cases
    else:
        targets = cases[:1]
        eng = sweep.Engine(sweep.compile_plan(targets[0].g,
                                              targets[0].params),
                           params=targets[0].params, policy=pol)

    q = sweep.Query(
        scenarios=(targets[0].batch if not has_G
                   else [c.batch for c in targets]),
        costs=(None if not has_K
               else (targets[0].extras if not has_G
                     else [c.extras for c in targets])))
    res = eng.run(q)
    assert res.axes == ((("G",) if has_G else ())
                        + (("K",) if has_K else ()) + ("S",))
    assert res.backend == backend

    def check(got_T, got_lam, got_rho, ref, name):
        if backend == "segment":
            np.testing.assert_array_equal(got_T, ref.T, err_msg=name)
            np.testing.assert_array_equal(got_lam, ref.lam, err_msg=name)
            np.testing.assert_array_equal(got_rho, ref.rho, err_msg=name)
        else:
            np.testing.assert_allclose(got_T, ref.T, rtol=1e-5,
                                       atol=1e-7, err_msg=name)
            np.testing.assert_allclose(got_lam, ref.lam, rtol=1e-5,
                                       atol=1e-5, err_msg=name)
            np.testing.assert_allclose(got_rho, ref.rho, rtol=1e-4,
                                       atol=1e-5, err_msg=name)

    for gi, c in enumerate(targets):
        lead = (gi,) if has_G else ()
        if has_K:
            for k in range(K):
                idx = lead + (k,)
                check(res.T[idx], res.lam[idx], res.rho[idx],
                      unified_ref[(c.name, backend, k)],
                      f"{c.name}/k={k}/{axisset}")
        else:
            check(res.T[lead] if lead else res.T,
                  res.lam[lead] if lead else res.lam,
                  res.rho[lead] if lead else res.rho,
                  unified_ref[(c.name, backend, None)],
                  f"{c.name}/{axisset}")


# -- dense pallas λ under exact ties ------------------------------------------

#: every cost a small integer (µs; 1,025 B messages cost 1 µs of gap), so
#: float32 and float64 sums are both exact
INT_P = LogGPS(L=(3.0,), G=(2.0 ** -10,), o=5.0, S=1e9, class_names=("ib",))


def _hop_tie(comp_us: float):
    """Five ping-pong hops between ranks 0 and 1 race two equal single-hop
    messages that ranks 2 and 3 send after ``comp_us`` of compute.  At the
    L where the paths meet (a multiple of 1/4: the hop counts differ by 4)
    the values tie, and only the slope sum carried from earlier levels
    picks the five-hop path, as float64 segment does."""
    from repro.core.graph import GraphBuilder
    b = GraphBuilder(4, 1)
    for i in range(5):
        b.add_message(i % 2, 1 - i % 2, 1025.0, INT_P)
    for r in (2, 3):
        b.add_calc(r, comp_us)
        b.add_message(r, 1, 1025.0, INT_P)
    b.add_calc(1, 1.0)
    return b.finalize()


def _integer_tie_cases():
    """Tie-heavy integer-cost graphs: a symmetric stencil, whose many
    structurally equal paths tie in value and slope sum alike, and two
    hop races, on absolute L from 0 to 64 µs in steps of 1/4."""
    graphs = [("stencil", synth.stencil2d(3, 3, 4, halo_bytes=1025.0,
                                          comp_us=500.0, params=INT_P)),
              ("hops150", _hop_tie(150.0)), ("hops200", _hop_tie(200.0))]
    batch = sweep.latency_grid(INT_P, np.arange(0.0, 64.0, 0.25),
                               absolute=True)
    rng = np.random.default_rng(5)
    cases = []
    for name, g in graphs:
        extras = np.where(g.ebytes[None, :] > 0,
                          rng.integers(0, 4, size=(K, g.num_edges)), 0)
        cases.append(Case(name=name, g=g, params=INT_P, batch=batch,
                          extras=extras.astype(np.float64)))
    return cases


@pytest.mark.parametrize("axisset", ("S", "GS", "KS"))
def test_pallas_lam_exact_under_integer_ties(axisset):
    """On integer-cost graphs the dense float32 forward answers exactly what
    the float64 segment forward answers — T and λ equal, not close — for
    solo engines, a packed graph axis (``_dense_core_multi``) and a cost
    block axis (``_dense_core_costs``): the slope sums the argmax kernel
    carries from level to level pick the same path among exact ties."""
    cases = _integer_tie_cases()
    for c in cases:
        assert np.array_equal(c.g.econst, np.round(c.g.econst))
    pols = {be: sweep.ExecPolicy(backend=be, cache=None)
            for be in ("segment", "pallas")}
    if axisset == "GS":
        runs = [(lambda pol: sweep.Engine(
                    [sweep.compile_plan(c.g, c.params) for c in cases],
                    names=[c.name for c in cases], policy=pol),
                 sweep.Query(scenarios=[c.batch for c in cases]))]
    else:
        runs = [(lambda pol, c=c: sweep.Engine(
                    sweep.compile_plan(c.g, c.params), params=c.params,
                    policy=pol),
                 sweep.Query(scenarios=c.batch,
                             costs=c.extras if axisset == "KS" else None))
                for c in (cases[2:] if axisset == "KS" else cases)]
    lams = set()
    for make, q in runs:
        seg, pal = (make(pols[be]).run(q) for be in ("segment", "pallas"))
        assert (pal.backend, pal.dtype) == ("pallas", "float32")
        assert (seg.backend, seg.dtype) == ("segment", "float64")
        np.testing.assert_array_equal(pal.T, seg.T)
        np.testing.assert_array_equal(pal.lam, seg.lam)
        lams.update(np.unique(seg.lam).tolist())
    assert len(lams) > 1                 # the races change λ along L

def test_unified_engine_shards_any_axis():
    """Sharded G and K (and S) axes on a forced multi-device CPU mesh are
    bit-equal to the single-device run, for the full G×K×S query on both
    backends.  Subprocess: the XLA device-count flag must be set before
    jax initializes."""
    import os
    import pathlib
    import subprocess
    import sys
    prog = (
        "import numpy as np, jax\n"
        "assert len(jax.devices()) == 2, jax.devices()\n"
        "from repro.core import synth\n"
        "from repro.core.loggps import cluster_params\n"
        "from repro import sweep\n"
        "p = cluster_params(L_us=3.0, o_us=5.0)\n"
        "gs = [synth.stencil2d(3, 3, 4, params=p, jitter=0.1, seed=s)\n"
        "      for s in (1, 2)]\n"
        "rng = np.random.default_rng(0)\n"
        "exs = [np.where(g.ebytes[None] > 0,\n"
        "                rng.uniform(0, 5, (4, g.num_edges)), 0.0)\n"
        "       for g in gs]\n"
        "grid = sweep.latency_grid(p, np.linspace(0.0, 40.0, 8))\n"
        "eng = sweep.Engine([sweep.compile_plan(g, p) for g in gs],\n"
        "                   policy=sweep.ExecPolicy(cache=None))\n"
        "q = sweep.Query(scenarios=grid, costs=exs)\n"
        "for be in ('segment', 'pallas'):\n"
        "    base = eng.run(q, backend=be)\n"
        "    for ax in ('G', 'K', 'S'):\n"
        "        sh = eng.run(q, backend=be, shard=True, shard_axis=ax)\n"
        "        assert np.array_equal(base.T, sh.T), (be, ax)\n"
        "        assert np.array_equal(base.lam, sh.lam), (be, ax)\n"
        "        assert np.array_equal(base.rho, sh.rho), (be, ax)\n"
        "print('OK')\n"
    )
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ,
           "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=2")}
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0 and res.stdout.strip() == "OK", res.stderr


def test_shims_bit_identical_to_engine():
    """The deprecation contract: SweepEngine/MultiSweepEngine delegate to
    the unified Engine and stay bit-identical — and they DO warn."""
    c = CASES[0]
    with pytest.warns(DeprecationWarning, match="SweepEngine is deprecated"):
        leg = sweep.SweepEngine(c.g, c.params, cache=None)
    new = sweep.Engine(c.g, params=c.params,
                       policy=sweep.ExecPolicy(cache=None))
    a, b = leg.run(c.batch), new.run(c.batch)
    np.testing.assert_array_equal(a.T, b.T)
    np.testing.assert_array_equal(a.lam, b.lam)
    np.testing.assert_array_equal(a.rho, b.rho)
    cases = _bucketable_cases()
    with pytest.warns(DeprecationWarning,
                      match="MultiSweepEngine is deprecated"):
        mleg = sweep.MultiSweepEngine([(x.g, x.params) for x in cases],
                                      names=[x.name for x in cases],
                                      cache=None)
    mnew = sweep.Engine([(x.g, x.params) for x in cases],
                        names=[x.name for x in cases],
                        policy=sweep.ExecPolicy(cache=None))
    ma = mleg.run([x.batch for x in cases])
    mb = mnew.run([x.batch for x in cases])
    np.testing.assert_array_equal(ma.T, mb.T)
    np.testing.assert_array_equal(ma.lam, mb.lam)


def test_zero_congestion_fixed_point_bit_identical():
    """``ExecPolicy(congestion="fixed_point")`` with all-zero α (the
    registry default) must be **bit-identical** (f64) to the plain segment
    forward on every case — T, λ and ρ — and converge in exactly one
    iteration: the fixed point's per-link scale is exactly 1.0, and the
    damped update is an exact identity there.  This pins the congestion
    refactor as a pure extension: congestion off (or α = 0) can never
    perturb a pre-existing result."""
    for c in CASES:
        base = sweep.Engine(c.g, params=c.params,
                            policy=sweep.ExecPolicy(cache=None)).run(c.batch)
        cong = sweep.Engine(
            c.g, params=c.params,
            policy=sweep.ExecPolicy(congestion="fixed_point",
                                    cache=None)).run(c.batch)
        np.testing.assert_array_equal(cong.T, base.T, err_msg=c.name)
        np.testing.assert_array_equal(cong.lam, base.lam, err_msg=c.name)
        np.testing.assert_array_equal(cong.rho, base.rho, err_msg=c.name)
        assert cong.congestion_iters is not None
        assert np.all(cong.congestion_iters == 1), c.name
