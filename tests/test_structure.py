"""Structure patching (the B axis) + the sparse backend: contracts.

The tentpole guarantees pinned here:

* ``CompiledPlan.patch_structure`` variants are **bit-exact** (segment)
  / ≤1e-5 (pallas) against ground-up rebuilds of the rewired graphs —
  T, λ and ρ — even though rebuilds settle on tighter level schedules.
* A whole topology study (B variants × S scenarios) compiles exactly
  ONE XLA program, and re-running another study in the same B bucket
  compiles ZERO more (the zero-recompile contract, CompileWatcher-
  enforced — the random-rewiring property twin lives in
  ``test_properties.py``).
* ``StructureBatch.from_plans`` stacks separately-compiled plans onto
  their union envelope and reproduces each solo run bit-exactly.
* Cache keys fold the structure hash: two studies differing only in
  their structure blocks never collide.
* The B axis composes with the K (cost) axis for patched variants and
  is rejected for ``from_plans`` batches, multi-graph engines, and the
  sparse backend — the full rejection surface is pinned.
* Byte accounting: ``dense_bytes``/``segment_bytes`` cover the λ
  tie-break arrays, ``padding_ratio`` is bytes-weighted, and runs stamp
  the ``sweep_dense_bytes`` gauge.
"""

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import synth
from repro.core.graph import _topo_levels
from repro.core.loggps import LogGPS, cluster_params
from repro import sweep
from repro.obs import REGISTRY
from repro.obs.compile import CompileWatcher


@pytest.fixture(scope="module")
def params():
    return cluster_params(L_us=3.0, o_us=5.0)


@pytest.fixture(scope="module")
def fixture(params):
    """One random-DAG workload, its base plan, a warm engine, and a grid."""
    g = synth.random_dag(np.random.default_rng(3), nranks=4, nops=40,
                         p_msg=0.5, params=params)
    base = sweep.compile_plan(g, params)
    eng = sweep.Engine(base, params=params,
                       policy=sweep.ExecPolicy(cache=None))
    batch = sweep.latency_grid(params, np.linspace(0.0, 40.0, 6))
    return g, base, eng, batch


def _removals(g, rng, n, bmax=4):
    """B keep-masks, each dropping a few random message edges."""
    ne = g.num_edges
    keeps = np.ones((n, ne), dtype=bool)
    for b in range(n):
        drop = rng.choice(ne, size=rng.integers(1, bmax + 1), replace=False)
        keeps[b, drop] = False
    return keeps


def _rebuilt(g, keep):
    """Ground-up rebuild: edges filtered, levels/CSR recomputed."""
    nv = g.num_vertices
    esrc, edst = g.esrc[keep], g.edst[keep]
    level = _topo_levels(nv, esrc, edst)
    in_ptr = np.zeros(nv + 1, np.int64)
    np.cumsum(np.bincount(edst, minlength=nv), out=in_ptr[1:])
    return dataclasses.replace(
        g, esrc=esrc, edst=edst, econst=g.econst[keep],
        ebytes=g.ebytes[keep], elat=g.elat[keep],
        egap=None if g.egap is None else g.egap[keep],
        egclass=None if g.egclass is None else g.egclass[keep],
        in_ptr=in_ptr,
        in_edge=np.argsort(edst, kind="stable").astype(np.int32),
        level=level, nlevels=int(level.max(initial=0)) + 1)


def test_patched_structure_matches_rebuilt(fixture, params):
    """Per backend: B edge-removal variants through ONE compiled program
    vs per-variant rebuilt plans — segment bit-exact, pallas ≤1e-5."""
    g, base, eng, batch = fixture
    keeps = _removals(g, np.random.default_rng(11), 3)
    sb = base.patch_structure(keep=keeps, names=["a", "b", "c"])
    for be, exact in (("segment", True), ("pallas", False)):
        res = eng.run(batch, structure=sb, backend=be)
        assert res.axes == ("B", "S") and res.B == 3
        assert res.names == ("a", "b", "c")
        for b in range(3):
            reb = sweep.compile_plan(_rebuilt(g, keeps[b]), params)
            ref = sweep.Engine(reb, params=params,
                               policy=sweep.ExecPolicy(backend=be,
                                                       cache=None)).run(batch)
            if exact:
                np.testing.assert_array_equal(res.T[b], ref.T)
                np.testing.assert_array_equal(res.lam[b], ref.lam)
                np.testing.assert_array_equal(res.rho[b], ref.rho)
            else:
                np.testing.assert_allclose(res.T[b], ref.T, rtol=1e-5)
                np.testing.assert_allclose(res.lam[b], ref.lam, rtol=1e-5,
                                           atol=1e-5)
                np.testing.assert_allclose(res.rho[b], ref.rho, rtol=1e-4,
                                           atol=1e-5)
        # split()/indexing sugar mirrors the G axis
        assert res["b"].T.shape == (batch.S,)
        np.testing.assert_array_equal(res.split()["a"].T, res.T[0])


def test_structure_study_is_one_program(fixture):
    """The zero-recompile contract: a whole variant study = exactly one
    new XLA program; a DIFFERENT study in the same B bucket = zero more.
    (B=5 → the Bp=8 bucket, which no other test touches on this envelope,
    so the cold count is deterministic across test orderings; the bench's
    ``structure_patch`` section pins the same contract for a 4-variant
    study in a fresh process.)"""
    g, base, _, batch = fixture
    eng = sweep.Engine(base, policy=sweep.ExecPolicy(cache=None))
    rng = np.random.default_rng(21)
    w = CompileWatcher()
    with w.watch("cold-structure") as cold:
        r1 = eng.run(batch, structure=base.patch_structure(
            keep=_removals(g, rng, 5)))
    assert cold.new_programs == 1, w.snapshot()
    with w.watch("warm-structure") as warm:
        r2 = eng.run(batch, structure=base.patch_structure(
            keep=_removals(g, rng, 8)))
    assert warm.new_programs == 0, w.snapshot()
    assert r1.T.shape == (5, batch.S) and r2.T.shape == (8, batch.S)
    assert not np.array_equal(r1.T, r2.T[:5])  # genuinely different studies
    occ = REGISTRY.get("sweep_envelope_occupancy")
    assert 0.0 < occ.value(axis="B") <= 1.0


def test_from_plans_matches_solo(params):
    """from_plans: separately-compiled plans on their union envelope give
    each member's solo numbers bit-exactly."""
    gs = [synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=s)
          for s in (1, 2, 3)]
    plans = [sweep.compile_plan(g, params) for g in gs]
    batch = sweep.latency_grid(params, [0.0, 12.0, 33.0])
    sb = sweep.StructureBatch.from_plans(plans, names=["s1", "s2", "s3"])
    res = sweep.Engine(sb, policy=sweep.ExecPolicy(cache=None)).run(batch)
    assert res.axes == ("B", "S")
    for i, plan in enumerate(plans):
        solo = sweep.Engine(plan, params=params,
                            policy=sweep.ExecPolicy(cache=None)).run(batch)
        np.testing.assert_array_equal(res.T[i], solo.T)
        np.testing.assert_array_equal(res.lam[i], solo.lam)
        np.testing.assert_array_equal(res.rho[i], solo.rho)
    order = res.rank(reduce="final")
    assert len(order) == 3 and order[0][1] <= order[-1][1]


def test_structure_composes_with_costs(fixture, params):
    """B×K×S: patched structure variants × patched cost blocks, every cell
    bit-equal (segment) to the rebuilt-graph × rebuilt-cost solo run."""
    g, base, eng, batch = fixture
    rng = np.random.default_rng(31)
    keeps = _removals(g, rng, 2)
    extras = np.where(g.ebytes[None] > 0,
                      rng.uniform(0.0, 8.0, (2, g.num_edges)), 0.0)
    sb = base.patch_structure(keep=keeps)
    res = eng.run(batch, structure=sb, costs=base.patch_costs(extras))
    assert res.axes == ("B", "K", "S")
    for b in range(2):
        g2 = _rebuilt(g, keeps[b])
        for k in range(2):
            reb = sweep.compile_plan(g2, params,
                                     extra_edge_cost=extras[k][keeps[b]])
            ref = sweep.Engine(reb, params=params,
                               policy=sweep.ExecPolicy(cache=None)).run(batch)
            np.testing.assert_array_equal(res.T[b, k], ref.T)
            np.testing.assert_array_equal(res.lam[b, k], ref.lam)


def test_cache_folds_structure_hash(fixture):
    """Two studies differing ONLY in structure blocks must never collide;
    replaying one is a patched hit."""
    g, base, _, batch = fixture
    cache = sweep.SweepCache(capacity=16)
    eng = sweep.Engine(base, policy=sweep.ExecPolicy(cache=cache))
    rng = np.random.default_rng(41)
    sb1 = base.patch_structure(keep=_removals(g, rng, 2))
    sb2 = base.patch_structure(keep=_removals(g, rng, 2))
    r1 = eng.run(batch, structure=sb1)
    r2 = eng.run(batch, structure=sb2)
    assert not r2.from_cache and cache.stats.misses == 2
    assert not np.array_equal(r1.T, r2.T)
    r1b = eng.run(batch, structure=sb1)
    assert r1b.from_cache and cache.stats.patched_hits == 1
    np.testing.assert_array_equal(r1b.T, r1.T)
    # and distinct from the unbatched plan's own entry
    r0 = eng.run(batch)
    assert not r0.from_cache


def test_query_key_structure_regression():
    """Unit pin on the key derivation itself (cache.query_key)."""
    from repro.sweep.cache import query_key
    batch = sweep.ScenarioBatch(L=np.zeros((2, 1)), gscale=np.ones((2, 1)))
    a = query_key("p", [batch], True, "segment")
    b = query_key("p", [batch], True, "segment", structure_hash="s1")
    c = query_key("p", [batch], True, "segment", structure_hash="s2")
    assert len({a, b, c}) == 3


def test_structure_rejections(fixture, params):
    g, base, eng, batch = fixture
    keeps = _removals(g, np.random.default_rng(51), 2)
    sb = base.patch_structure(keep=keeps)
    # not a StructureBatch
    with pytest.raises(ValueError, match="StructureBatch"):
        eng.run(batch, structure=keeps)
    # foreign batch, same envelope bucket → caught by the stamped hash
    g2 = synth.random_dag(np.random.default_rng(4), nranks=4, nops=40,
                          p_msg=0.5, params=params)
    other = sweep.compile_plan(g2, params)
    probe = other.patch_structure(keep=np.ones((1, g2.num_edges), bool))
    if probe.vsrc.shape[1:] == base.vsrc.shape:
        with pytest.raises(ValueError, match="different plan"):
            eng.run(batch, structure=probe)
    else:
        with pytest.raises(ValueError, match="envelope"):
            eng.run(batch, structure=probe)
    # multi-graph engine + structure: pick one variant axis
    meng = sweep.Engine([base, base], names=["x", "y"],
                        policy=sweep.ExecPolicy(cache=None))
    with pytest.raises(ValueError, match="multi-graph"):
        meng.run([batch, batch], structure=sb)
    # from_plans + costs: no shared base plan to patch into
    fp = sweep.StructureBatch.from_plans([base, base])
    with pytest.raises(ValueError, match="from_plans"):
        eng.run(batch, structure=fp,
                costs=base.patch_costs(np.zeros((1, g.num_edges))))
    # sharding the B axis is not supported yet
    with pytest.raises(ValueError, match="shard"):
        eng.run(batch, structure=sb, shard=True)
    # sparse backend takes neither structure nor cost blocks
    with pytest.raises(ValueError, match="structure"):
        eng.run(batch, structure=sb, backend="sparse")
    with pytest.raises(ValueError, match="cost"):
        eng.run(batch, costs=base.patch_costs(np.zeros((1, g.num_edges))),
                backend="sparse")
    # level-schedule violation: a source at/above its destination's level
    lvl_dst = g.level[g.edst]
    bad_e = int(np.argmax(lvl_dst == lvl_dst.min()))
    same_lvl = np.nonzero(g.level >= lvl_dst[bad_e])[0]
    src = g.esrc.astype(np.int64).copy()
    src[bad_e] = same_lvl[0]
    with pytest.raises(ValueError, match="level schedule"):
        base.patch_structure(src=src)
    # patch_structure needs src and/or keep
    with pytest.raises(ValueError, match="src and/or keep"):
        base.patch_structure()


def test_auto_sparse_switch(params, monkeypatch):
    """A graph whose estimated dense envelope exceeds MAX_DENSE_BYTES is
    never laid out dense: float64 policies warn once and switch to the
    sparse backend; an explicit float32 (pallas-pinned) policy raises."""
    g = synth.stencil2d(3, 3, 3, params=params)
    est = sweep.estimate_dense_bytes(g)
    assert est > 0
    monkeypatch.setattr(sweep.Engine, "MAX_DENSE_BYTES", est - 1)
    with pytest.warns(RuntimeWarning, match="sparse"):
        eng = sweep.Engine(g, params=params,
                           policy=sweep.ExecPolicy(cache=None))
    assert eng.policy.backend == "sparse" and eng.plan is None
    batch = sweep.latency_grid(params, [0.0, 15.0])
    res = eng.run(batch)
    assert res.backend == "sparse"
    ref = sweep.Engine(sweep.compile_plan(g, params), params=params,
                       policy=sweep.ExecPolicy(cache=None)).run(batch)
    np.testing.assert_array_equal(res.T, ref.T)
    np.testing.assert_array_equal(res.lam, ref.lam)
    # dense backends cannot evaluate a sparse-only engine
    with pytest.raises(ValueError, match="sparse-only"):
        eng.run(batch, backend="segment")
    with pytest.raises(ValueError, match="float32"):
        sweep.Engine(g, params=params,
                     policy=sweep.ExecPolicy(dtype="float32", cache=None))


def test_sparse_float32_does_not_drift_with_depth():
    """The float32 slot-list flavour keeps its level carry in float64 and
    takes the exact value of the candidate its kernel selects, so T stays
    within one float32 rounding of the float64 forward however deep the
    graph (a float32 carry drifted ~1e-5 relative over these 4096
    levels)."""
    p = cluster_params(L_us=3.0, o_us=5.0)
    g = synth.allreduce_chain(4, 200, nbytes=2e6, comp_us=4000.0, params=p,
                              algo="ring")
    sp = sweep.compile_sparse(g, p)
    grid = sweep.latency_grid(p, [0.0, 7.3, 51.1])

    def run(dtype):
        return sweep.Engine(sp, params=p, policy=sweep.ExecPolicy(
            backend="sparse", dtype=dtype, cache=None)).run(grid)

    f64, f32 = run("float64"), run("float32")
    np.testing.assert_allclose(f32.T, f64.T, rtol=1e-7)
    np.testing.assert_array_equal(f32.lam, f64.lam)


def test_byte_accounting_and_gauge(fixture):
    """dense_bytes ⊃ segment_bytes ⊃ 0 (the pallas view adds the dense
    indicator; both cover the λ tie-break arrays), padding_ratio =
    padded/real ≥ 1, sparse_bytes < dense for compact graphs, and runs
    stamp the ``sweep_dense_bytes`` gauge per view."""
    g, base, eng, batch = fixture
    seg_b, dense_b = base.segment_bytes(), base.dense_bytes()
    assert 0 < seg_b < dense_b
    assert base.padding_ratio >= 1.0
    sp = sweep.SparsePlan.from_plan(base)
    assert sp.sparse_bytes() < dense_b
    # the gauge is stamped when an engine first stages a view's arrays, so
    # read it through a fresh engine (the module fixture's engine — and any
    # engine another test built — already staged and stamped its own totals)
    fresh = sweep.Engine(base, policy=sweep.ExecPolicy(cache=None))
    fresh.run(batch)
    gauge = REGISTRY.get("sweep_dense_bytes")
    # dense views stamp the full dense footprint (what the auto-switch
    # compares to MAX_DENSE_BYTES); the sparse view its compact layout
    assert gauge.value(view="segment") == float(dense_b)
    fresh.run(batch, backend="sparse")
    assert gauge.value(view="sparse") == float(sp.sparse_bytes())


def test_sweep_variants_shim_is_thin(params):
    """The deprecated sweep_variants batched path ≡ a hand-built
    Query(structure=) run, bit for bit — it IS that call now."""
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 2, params=params, algo=a),
        ["ring", "recursive_doubling", "tree"], params)
    batch = sweep.latency_grid(params, np.linspace(0.0, 30.0, 5))
    with pytest.warns(DeprecationWarning, match="StructureBatch"):
        out = sweep.sweep_variants(variants, lambda v: batch, cache=None)
    plans = [sweep.compile_plan(v.graph, v.params) for v in variants]
    sb = sweep.StructureBatch.from_plans(
        plans, names=[v.name for v in variants])
    res = sweep.Engine(sb, policy=sweep.ExecPolicy(cache=None)) \
        .run(sweep.Query(scenarios=batch))
    for i, v in enumerate(variants):
        np.testing.assert_array_equal(out[v.name].T, res.T[i])
        np.testing.assert_array_equal(out[v.name].lam, res.lam[i])
