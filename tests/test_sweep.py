"""Batched scenario-sweep engine (repro.sweep): features and regressions.

The backend-equivalence guarantees (scalar vs segment vs pallas × T/λ/ρ ×
solo/MultiPlan/patched-costs) live in ``tests/test_conformance.py`` as one
parametrized matrix; this file covers the engine's *feature* surface —
grids, caching, dispatch, sharding, packing mechanics, guards.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import dag, sensitivity, synth
from repro.core.loggps import cluster_params, pod_model
from repro import sweep
from repro.sweep import cache as sweep_cache
from repro.sweep import engine as sweep_engine


@pytest.fixture(scope="module")
def params():
    return cluster_params(L_us=3.0, o_us=5.0)


def _variant_engine(variants, cache):
    """One multi-graph Engine: the variants' graphs on the G axis, under
    the variants' names."""
    return sweep.Engine([(v.graph, v.params) for v in variants],
                        names=[v.name for v in variants],
                        policy=sweep.ExecPolicy(cache=cache))


def _structure_study(variants, batch):
    """A variant study on the structure axis: one StructureBatch per
    ``group_plans`` bucket, one Engine call each.  Returns
    ({name: Result}, bucket count, compiled calls)."""
    plans = [sweep.compile_plan(v.graph, v.params) for v in variants]
    groups = sweep.group_plans(plans)
    out, calls = {}, 0
    for idx in groups:
        sb = sweep.StructureBatch.from_plans(
            [plans[i] for i in idx], names=[variants[i].name for i in idx])
        eng = sweep.Engine(sb, policy=sweep.ExecPolicy(cache=None))
        out.update(eng.run(batch).split())
        calls += eng.calls
    return out, len(groups), calls


def test_bandwidth_scenarios_match_rebuilt_graph(params):
    """γ·G scenarios ≡ rebuilding the graph with scaled G (exact gap split)."""
    g = synth.cg_like(2, 2, 3, params=params)
    eng = sweep.Engine(g, params=params)
    res = eng.run(sweep.bandwidth_grid(params, [1.0, 2.0, 4.0]))
    for i, gs in enumerate([1.0, 2.0, 4.0]):
        p2 = params.replace(G=tuple(gs * x for x in params.G))
        g2 = synth.cg_like(2, 2, 3, params=p2)
        ref = dag.evaluate(g2, p2.replace(L=params.L)).T
        assert res.T[i] == pytest.approx(ref, rel=1e-12), gs


def test_cartesian_grid_shapes(params):
    batch = sweep.cartesian_grid(params, lat_deltas={0: [0.0, 5.0, 10.0]},
                                 gscales={0: [1.0, 2.0]})
    assert batch.S == 6
    assert batch.meta[0] == {"dL[0]": 0.0, "gscale[0]": 1.0}
    g = synth.stencil2d(2, 2, 2, params=params)
    res = sweep.Engine(g, params=params).run(batch)
    assert res.T.shape == (6,)
    # T monotone in both ΔL and γ
    assert res.T[1] >= res.T[0] and res.T[5] >= res.T[4]


def test_cartesian_grid_rejects_duplicate_class_axes():
    """The same class passed under two spellings (index and registered
    name) must raise, not silently clobber the earlier axis."""
    from repro.core.loggps import pod_model
    p = pod_model(4).params()          # classes ("ici", "dcn")
    with pytest.raises(ValueError, match="dcn"):
        sweep.cartesian_grid(p, lat_deltas={1: [0.0, 5.0], "dcn": [0.0, 9.0]})
    with pytest.raises(ValueError, match="ici"):
        sweep.cartesian_grid(p, gscales={"ici": [1.0, 2.0], 0: [1.0, 4.0]})
    # the same class on the L axis and the G axis is fine (distinct axes)
    batch = sweep.cartesian_grid(p, lat_deltas={"dcn": [0.0, 5.0]},
                                 gscales={1: [1.0, 2.0]})
    assert batch.S == 4


def test_collective_variants(params):
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 2, params=params, algo=a),
        ["ring", "recursive_doubling"], params)
    out, _, _ = _structure_study(variants,
                                 sweep.latency_grid(params, [0.0, 20.0]))
    # recursive doubling has fewer latency-critical rounds: λ smaller, and
    # under +20µs latency it beats ring (the Fig 10 ordering)
    ring, rd = out["algo=ring"], out["algo=recursive_doubling"]
    assert rd.lam[0, 0] < ring.lam[0, 0]
    assert rd.T[1] < ring.T[1]


def test_tolerance_batched_matches_scalar(params):
    g = synth.stencil2d(3, 3, 4, params=params)
    degr = (0.01, 0.02, 0.05, 0.1)
    eng = sweep.Engine(g, params=params)
    batched = sweep_engine.tolerance_batched(eng, params, degr)
    for p_ in degr:
        ref = dag.tolerance(g, params, p_)
        assert batched[p_] == pytest.approx(ref, rel=1e-9, abs=1e-9)


def test_breakpoints_batched_matches_scalar(params):
    g = synth.sweep2d(3, 3, 3, params=params)
    eng = sweep.Engine(g, params=params)
    batched = sweep_engine.breakpoints_batched(eng, params, 0.5, 500.0)
    ref = dag.breakpoints(g, params, 0.5, 500.0)
    assert len(batched) == len(ref)
    np.testing.assert_allclose(batched, ref, rtol=1e-6)


def test_sensitivity_dispatch_equivalence(params):
    """sensitivity.* auto-dispatch returns the scalar path's numbers."""
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = np.linspace(0.0, 100.0, 10)
    auto = sensitivity.latency_curve(g, params, deltas)
    scalar = sensitivity.latency_curve(g, params, deltas, engine="scalar")
    np.testing.assert_allclose(auto.T, scalar.T, atol=1e-9)
    np.testing.assert_allclose(auto.lam, scalar.lam, atol=1e-9)
    np.testing.assert_allclose(auto.rho, scalar.rho, atol=1e-9)

    degr = (0.01, 0.02, 0.05, 0.1)
    t_auto = sensitivity.latency_tolerance(g, params, degr)
    t_scalar = sensitivity.latency_tolerance(g, params, degr, engine="scalar")
    for k in degr:
        assert t_auto[k] == pytest.approx(t_scalar[k], rel=1e-9)

    lcs_sweep = sensitivity.critical_latencies(g, params, 0.5, 300.0,
                                               engine="sweep")
    lcs_scalar = sensitivity.critical_latencies(g, params, 0.5, 300.0,
                                                engine="scalar")
    np.testing.assert_allclose(lcs_sweep, lcs_scalar, rtol=1e-6)


def test_result_cache(params):
    g = synth.stencil2d(2, 2, 2, params=params)
    cache = sweep_cache.SweepCache(capacity=8)
    eng = sweep.Engine(g, params=params, policy=sweep.ExecPolicy(cache=cache))
    batch = sweep.latency_grid(params, [0.0, 5.0, 10.0])
    r1 = eng.run(batch)
    assert not r1.from_cache and cache.stats.hits == 0
    r2 = eng.run(batch)
    assert r2.from_cache and cache.stats.hits == 1
    np.testing.assert_array_equal(r1.T, r2.T)
    ref = r1.T.copy()
    # both miss and hit results are private copies: caller mutation of
    # either must not poison the cache
    r1.T[:] = -2.0
    r2.T[:] = -1.0
    np.testing.assert_array_equal(eng.run(batch).T, ref)
    # structurally identical graph, fresh engine → same content hash → hit
    g2 = synth.stencil2d(2, 2, 2, params=params)
    eng2 = sweep.Engine(g2, params=params,
                        policy=sweep.ExecPolicy(cache=cache))
    r3 = eng2.run(batch)
    assert r3.from_cache
    # different scenarios miss
    r4 = eng.run(sweep.latency_grid(params, [0.0, 7.0]))
    assert not r4.from_cache


def test_compiled_plan_bucketing(params):
    """Graphs of similar size share one XLA program (shape_key equality)."""
    g1 = synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=1)
    g2 = synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=2)
    c1 = sweep.compile_plan(g1, params)
    c2 = sweep.compile_plan(g2, params)
    assert c1.shape_key == c2.shape_key
    assert c1.content_hash() != c2.content_hash()  # costs differ
    assert c1.padding_ratio < 64  # sanity: padding stays bounded


def test_engine_rejects_mismatched_classes(params):
    g = synth.stencil2d(2, 2, 2, params=params)
    eng = sweep.Engine(g, params=params)
    two_cls = pod_model(pod_size=2).params()
    with pytest.raises(ValueError, match="classes"):
        eng.run(sweep.latency_grid(two_cls, [0.0, 1.0]))
    with pytest.raises(ValueError, match="engine"):
        sensitivity.latency_curve(g, params, [0.0, 1.0], engine="batched")


# -- multi-graph packing (MultiPlan): packed ≡ solo, bit for bit -------------

def _collective_topology_variants():
    """3 collective algorithms × 2 two-class topologies = 6 GraphVariants
    sharing one latency-class count (so they can pack)."""
    out = []
    for pod, tag in ((2, "pod2"), (4, "pod4")):
        p = pod_model(pod_size=pod).params()
        for algo in ("ring", "recursive_doubling", "tree"):
            g = synth.allreduce_chain(8, 2, params=p, algo=algo)
            out.append(sweep.GraphVariant(name=f"{tag}/{algo}", graph=g,
                                          params=p,
                                          meta={"algo": algo, "pod": pod}))
    return out


def test_multiplan_getitem_by_index_and_name():
    """__getitem__ by index and by name give the same slice (the packed ≡
    solo value equivalence itself lives in the conformance matrix)."""
    variants = _collective_topology_variants()[:2]
    meng = _variant_engine(variants, cache=None)
    res = meng.run(sweep.latency_grid(variants[0].params, [0.0, 10.0]))
    for i, v in enumerate(variants):
        np.testing.assert_array_equal(res[i].T, res[v.name].T)


def test_multiplan_repad_is_exact(params):
    """A plan re-padded onto a larger envelope runs bit-identically."""
    from repro.sweep.compile import repad_plan
    g = synth.stencil2d(3, 3, 3, params=params)
    c = sweep.compile_plan(g, params)
    grid = sweep.latency_grid(params, np.linspace(0.0, 40.0, 7))
    pol = sweep.ExecPolicy(cache=None)
    base = sweep.Engine(c, params=params, policy=pol).run(grid)
    nlv, V, D = c.vsrc.shape
    big = repad_plan(c, nlv * 2, V * 2, D * 2, c.esrc.shape[1] * 2)
    res = sweep.Engine(big, params=params, policy=pol).run(grid)
    np.testing.assert_array_equal(res.T, base.T)
    np.testing.assert_array_equal(res.lam, base.lam)
    with pytest.raises(ValueError, match="smaller"):
        repad_plan(c, nlv // 2, V, D, c.esrc.shape[1])


def test_group_plans_buckets_and_inflation(params):
    small = sweep.compile_plan(synth.stencil2d(2, 2, 2, params=params), params)
    huge = sweep.compile_plan(synth.allreduce_chain(16, 6, params=params),
                              params)
    # same nclass but wildly different volume: inflation bound splits them
    groups = sweep.group_plans([small, huge, small], max_inflation=4.0)
    assert [0, 2] in groups and [1] in groups
    # everything fits one bucket when the bound is loose
    assert sweep.group_plans([small, small], max_inflation=64.0) == [[0, 1]]
    # different latency-class counts never pack
    p2 = pod_model(pod_size=2).params()
    two = sweep.compile_plan(synth.stencil2d(2, 2, 2, params=p2), p2)
    assert sweep.group_plans([small, two]) == [[0], [1]]
    with pytest.raises(ValueError, match="class"):
        sweep.pack_plans([small, two])


def test_sweep_variants_batched_call_count(params):
    """A variant study costs one compiled call per shape bucket."""
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 1, params=params, algo=a),
        ["ring", "bidir_ring", "recursive_doubling", "tree"], params)
    batch = sweep.latency_grid(params, np.linspace(0, 50, 20))
    batched, groups, calls = _structure_study(variants, batch)
    assert groups < len(variants)               # buckets merged variants
    assert calls == groups <= len(variants)
    loop, loop_calls = {}, 0
    for v in variants:
        eng = sweep.Engine(v.graph, params=v.params,
                           policy=sweep.ExecPolicy(cache=None))
        loop[v.name] = eng.run(batch)
        loop_calls += eng.calls
    assert loop_calls == len(variants)
    for name, ref in loop.items():
        np.testing.assert_array_equal(batched[name].T, ref.T)
        np.testing.assert_array_equal(batched[name].lam, ref.lam)


def test_multisweep_rank_and_broadcast(params):
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 2, params=params, algo=a),
        ["ring", "recursive_doubling"], params)
    meng = _variant_engine(variants, cache=None)
    # one ScenarioBatch broadcasts to every graph
    res = meng.run(sweep.latency_grid(params, np.linspace(0, 40, 10)))
    order = res.rank(reduce="final")
    assert order[0][0] == "algo=recursive_doubling"   # Fig 10 ordering
    assert order[0][1] <= order[1][1]
    with pytest.raises(ValueError, match="reduce"):
        res.rank(reduce="median")
    with pytest.raises(ValueError, match="scenario batches"):
        meng.run([sweep.latency_grid(params, [0.0])])


def test_multisweep_result_cache(params):
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 1, params=params, algo=a),
        ["ring", "tree"], params)
    cache = sweep_cache.SweepCache(capacity=4)
    meng = _variant_engine(variants, cache=cache)
    grid = sweep.latency_grid(params, [0.0, 10.0, 20.0])
    r1 = meng.run(grid)
    assert not r1.from_cache
    r2 = meng.run(grid)
    assert r2.from_cache and meng.calls == 1
    np.testing.assert_array_equal(r1.T, r2.T)
    ref = r1.T.copy()
    r1.T[:] = -2.0                      # miss result is a private copy too
    r2.T[:] = -1.0                      # hits hand out copies
    np.testing.assert_array_equal(meng.run(grid).T, ref)
    # a different engine over the same plans hits content-addressed — but
    # the result must carry THAT engine's names, not the cached ones
    meng2 = _variant_engine(variants, cache=cache)
    meng2.names = ("renamed_ring", "renamed_tree")
    r3 = meng2.run(grid)
    assert r3.from_cache and r3.names == ("renamed_ring", "renamed_tree")
    np.testing.assert_array_equal(r3["renamed_ring"].T, ref[0])


# -- gap decomposition: build-time shares recorded on the graph ---------------

def test_gap_shares_survive_params_drift(params):
    """Regression for the ROADMAP caveat: bandwidth scenarios must be exact
    even when the params handed to compile_plan differ from the build-time
    ones — the graph's recorded egap/egclass are authoritative."""
    g = synth.cg_like(2, 2, 3, params=params)
    assert g.egap is not None and g.egclass is not None
    assert float(g.egap.sum()) > 0
    drifted = params.replace(G=tuple(7.0 * x for x in params.G))
    eng = sweep.Engine(sweep.compile_plan(g, drifted), params=params,
                       policy=sweep.ExecPolicy(cache=None))
    res = eng.run(sweep.bandwidth_grid(params, [1.0, 2.0, 4.0]))
    for i, gs in enumerate([1.0, 2.0, 4.0]):
        p2 = params.replace(G=tuple(gs * x for x in params.G))
        g2 = synth.cg_like(2, 2, 3, params=p2)
        ref = dag.evaluate(g2, p2.replace(L=params.L)).T
        assert res.T[i] == pytest.approx(ref, rel=1e-12), gs


def test_gap_shares_on_traced_graphs():
    """Graphs built by core.tracer record per-edge gap shares, and the
    scalar bandwidth_curve path consumes them."""
    from repro import configs
    from repro.core.tracer import TraceSpec, trace_step
    from repro.models.config import TRAIN_4K
    cfg, _ = configs.get("llama3.2-3b")
    ts = TraceSpec(pods=1, data=2, model=2)
    g = trace_step(cfg, TRAIN_4K, ts)
    assert g.egap is not None
    assert float(g.egap.sum()) > 0
    p = ts.params()
    curve = sensitivity.bandwidth_curve(g, p, [1.0, 3.0], engine="scalar")
    assert curve.T[1] > curve.T[0]      # slower links ⇒ longer step
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    res = eng.run(sweep.bandwidth_grid(p, [1.0, 3.0]))
    np.testing.assert_allclose(res.T, curve.T, rtol=1e-9)


def test_recorded_zero_gap_is_authoritative(params):
    """A graph built under G=0 recorded zero gap shares — bandwidth sweeps
    must stay flat on BOTH dispatch paths even when the caller now holds
    nonzero-G params (reconstruction must not override explicit zeros)."""
    p0 = params.replace(G=(0.0,))
    g = synth.stencil2d(3, 3, 3, params=p0)
    assert float(np.nansum(g.egap)) == 0.0
    gs = np.linspace(1.0, 4.0, 9)        # ≥ SWEEP_MIN_POINTS → auto=sweep
    swept = sensitivity.bandwidth_curve(g, params, gs, engine="sweep")
    scalar = sensitivity.bandwidth_curve(g, params, gs, engine="scalar")
    np.testing.assert_allclose(swept.T, scalar.T, rtol=1e-12)
    assert float(np.ptp(swept.T)) == 0.0          # flat: no gap to scale


def test_gap_reconstruction_backstops_raw_add_edge(params):
    """Message edges added via raw add_edge() without gap_us (the pre-gap-
    recording idiom) still get the params-based gap split — recorded zeros
    must not shadow the reconstruction."""
    from repro.core.graph import GraphBuilder

    def build(p):
        b = GraphBuilder(2, 1)
        b.add_calc(0, 5.0)
        sv = b.add_send_vertex(0, p.o)
        rv = b.add_recv_vertex(1, p.o)
        b.add_edge(sv, rv, const_us=p.gap_cost(8192.0), nbytes=8192.0,
                   lat=((0, 1),))                    # note: no gap_us
        b.add_calc(1, 5.0)
        return b.finalize()

    g = build(params)
    # the raw message edge recorded NaN = "share unknown", not a zero
    assert np.isnan(g.egap[g.ebytes > 0]).all()
    eng = sweep.Engine(g, params=params, policy=sweep.ExecPolicy(cache=None))
    res = eng.run(sweep.bandwidth_grid(params, [1.0, 3.0]))
    for i, gs in enumerate([1.0, 3.0]):
        p2 = params.replace(G=tuple(gs * x for x in params.G))
        ref = dag.evaluate(build(p2), p2.replace(L=params.L)).T
        assert res.T[i] == pytest.approx(ref, rel=1e-12), gs


def test_topology_stamper_gap_excludes_switch_constant(params):
    """TopologyStamper folds h·d_switch into econst; only the (s-1)·G share
    may scale with γ (the gap share must not swallow the hop constant)."""
    from repro.core import topology
    topo = topology.fat_tree(4)
    p = topology.topology_params(topo)
    stamp = topology.TopologyStamper(topo, p)
    from repro.core.graph import GraphBuilder
    b = GraphBuilder(4, topo.nclasses)
    b.add_calc(0, 1.0)
    stamp.message(b, 0, 2, 4096.0)
    g = b.finalize()
    msg = int(np.nonzero(g.ebytes > 0)[0][0])
    assert 0 < g.egap[msg] < g.econst[msg]


# -- cache: canonical-byte hashing, eviction, stats ---------------------------

def test_content_hash_stable_across_processes(params):
    """The compiled-plan hash is a function of canonical bytes, never of
    Python object identity — a fresh process mints the same key."""
    import os
    import pathlib
    import subprocess
    import sys
    prog = (
        "from repro.core import synth\n"
        "from repro.core.loggps import cluster_params\n"
        "from repro.sweep.compile import compile_plan\n"
        "p = cluster_params(L_us=3.0, o_us=5.0)\n"
        "g = synth.stencil2d(2, 2, 2, params=p)\n"
        "print(compile_plan(g, p).content_hash())\n"
    )
    local_hash = sweep.compile_plan(
        synth.stencil2d(2, 2, 2, params=params), params).content_hash()
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ,
           "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", "")}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, check=True, env=env)
    assert out.stdout.strip() == local_hash


def test_canonical_bytes_disambiguates_layouts():
    a = np.arange(6, dtype=np.float64).reshape(2, 3)
    assert a.tobytes() == a.reshape(3, 2).tobytes()      # the trap
    assert (b"".join(sweep_cache.canonical_bytes(a))
            != b"".join(sweep_cache.canonical_bytes(a.reshape(3, 2))))
    assert (b"".join(sweep_cache.canonical_bytes(a))
            != b"".join(sweep_cache.canonical_bytes(a.astype(np.float32))))
    # F-order view hashes like its C-order copy (same logical array)
    f = np.asfortranarray(a)
    assert (b"".join(sweep_cache.canonical_bytes(f))
            == b"".join(sweep_cache.canonical_bytes(a)))


def test_cache_eviction_and_stats(params):
    cache = sweep_cache.SweepCache(capacity=2)
    g = synth.stencil2d(2, 2, 2, params=params)
    eng = sweep.Engine(g, params=params, policy=sweep.ExecPolicy(cache=cache))
    grids = [sweep.latency_grid(params, [float(k)]) for k in range(3)]
    for b in grids:
        eng.run(b)
    assert len(cache) == 2
    st = cache.stats
    assert (st.hits, st.misses, st.evictions) == (0, 3, 1)
    # grid 0 was evicted (LRU): re-running it misses and evicts grid 1
    assert not eng.run(grids[0]).from_cache
    assert cache.stats.misses == 4 and cache.stats.evictions == 2
    # grids 2 and 0 are resident: hits, and hit_rate reflects 2/6
    assert eng.run(grids[2]).from_cache and eng.run(grids[0]).from_cache
    assert cache.stats.hits == 2
    assert cache.stats.hit_rate == pytest.approx(2 / 6)
    snap = cache.stats.snapshot()
    assert snap["evictions"] == 2
    cache.clear()
    assert len(cache) == 0 and cache.stats.misses == 0


# -- PR 3/4: λ layouts, sharding, guards, patched-cost caching ---------------

def test_two_pass_lambda_bit_identical_to_fused(params):
    """The default two-pass segment λ (next-pointer records + reverse
    pointer chase) reproduces the fused single-loop backtrace bit-for-bit —
    tie-heavy collective graphs and multi-class params included."""
    import jax
    import jax.numpy as jnp
    p2 = pod_model(pod_size=2).params()
    cases = [(synth.allreduce_chain(8, 3, params=params), params),
             (synth.stencil2d(3, 3, 4, params=params), params),
             (synth.stencil2d(2, 2, 3, params=p2), p2)]
    for g, p in cases:
        eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
        grid = sweep.latency_grid(p, np.linspace(0.0, 60.0, 9))
        res = eng.run(grid)                        # two-pass default
        S = grid.S
        Sp = sweep_engine._bucket(S, lo=4)
        Lm = np.repeat(grid.L[-1:], Sp, axis=0)
        Lm[:S] = grid.L
        GS = np.repeat(grid.gscale[-1:], Sp, axis=0)
        GS[:S] = grid.gscale
        with jax.enable_x64():
            fwd = sweep_engine._get_forward("segment", True, fused=True)
            Tf, lf = fwd(*eng._arrays("segment"), jnp.asarray(Lm),
                         jnp.asarray(GS))
        np.testing.assert_array_equal(np.asarray(Tf)[:S], res.T)
        np.testing.assert_array_equal(np.asarray(lf)[:S], res.lam)


def test_sharded_matches_single_device():
    """Sharded runs (shard_map over the MultiPlan graph axis / the
    single-graph scenario axis) are bit-equal to single-device runs on a
    forced ≥2-device CPU mesh.  Subprocess: the XLA flag must be set
    before jax initializes."""
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
        "variants = sweep.collective_variants(\n"
        "    lambda a: synth.allreduce_chain(8, 1, params=p, algo=a),\n"
        "    ['ring', 'recursive_doubling'], p)\n"
        "pol = sweep.ExecPolicy(cache=None)\n"
        "meng = sweep.Engine([(v.graph, v.params) for v in variants],\n"
        "                    names=[v.name for v in variants], policy=pol)\n"
        "grid = sweep.latency_grid(p, np.linspace(0.0, 40.0, 8))\n"
        "base = meng.run(grid)\n"
        "sh = meng.run(grid, shard=True)\n"
        "assert np.array_equal(base.T, sh.T)\n"
        "assert np.array_equal(base.lam, sh.lam)\n"
        "g = synth.stencil2d(2, 2, 3, params=p)\n"
        "eng = sweep.Engine(g, params=p, policy=pol)\n"
        "b = eng.run(grid)\n"
        "s = eng.run(grid, shard=True)\n"
        "assert np.array_equal(b.T, s.T) and np.array_equal(b.lam, s.lam)\n"
        "bp = eng.run(grid, backend='pallas')\n"
        "sp = eng.run(grid, backend='pallas', shard=True)\n"
        "assert np.array_equal(bp.T, sp.T)\n"
        "assert np.array_equal(bp.lam, sp.lam)\n"
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


def test_resolve_shard_divisor_walkdown(params):
    """shard requests resolve to a divisor of the batch axis (or None)."""
    assert sweep_engine._resolve_shard(None, 8) is None
    assert sweep_engine._resolve_shard(False, 8) is None
    assert sweep_engine._resolve_shard(1, 8) is None
    # single local device in-process: every request degrades to None
    assert sweep_engine._resolve_shard(True, 8) in (None, 2, 4, 8)


def test_scenario_batch_validation():
    """Shape/NaN validation raises real ValueErrors (not -O-stripped
    asserts) naming the offending shapes / rows."""
    with pytest.raises(ValueError, match="shapes disagree"):
        sweep.ScenarioBatch(L=np.zeros((3, 2)), gscale=np.ones((2, 2)))
    L = np.ones((4, 1))
    L[2, 0] = np.nan
    with pytest.raises(ValueError, match=r"non-finite scenario rows \[2\]"):
        sweep.ScenarioBatch(L=L, gscale=np.ones((4, 1)))
    G = np.ones((3, 1))
    G[1, 0] = np.inf
    with pytest.raises(ValueError, match=r"rows \[1\]"):
        sweep.ScenarioBatch(L=np.ones((3, 1)), gscale=G)


def test_auto_dispatch_warns_once_then_falls_back(params, monkeypatch):
    """engine='auto' never serves a failing batched path with the scalar
    loop: a run-time engine failure raises under 'auto' (no warning, no
    scalar answer) exactly as under engine='sweep'."""
    import warnings as warnings_mod
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = np.linspace(0.0, 20.0, 10)

    def boom(self, *a, **k):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(sweep.Engine, "run", boom)
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error", RuntimeWarning)
        for _ in range(2):                       # every call, not just once
            with pytest.raises(RuntimeError, match="injected engine failure"):
                sensitivity.latency_curve(g, params, deltas)
    with pytest.raises(RuntimeError, match="injected"):
        sensitivity.latency_curve(g, params, deltas, engine="sweep")


def test_auto_dispatch_survives_engine_construction_failure(params,
                                                            monkeypatch):
    """Engine *construction* failures follow the same contract as run-time
    ones: engine='auto' and engine='sweep' both surface the error."""
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = np.linspace(0.0, 20.0, 10)

    def boom(self, *a, **k):
        raise RuntimeError("injected construction failure")

    monkeypatch.setattr(sweep.Engine, "__init__", boom)
    with pytest.raises(RuntimeError, match="injected construction failure"):
        sensitivity.latency_curve(g, params, deltas)
    with pytest.raises(RuntimeError, match="injected construction"):
        sensitivity.latency_curve(g, params, deltas, engine="sweep")


def test_auto_dispatch_scalar_only_without_jax(params, monkeypatch):
    """The one quiet scalar path: JAX itself is absent
    (``ModuleNotFoundError`` naming "jax").  Every dispatch site then
    returns the scalar answer under engine='auto'."""
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = np.linspace(0.0, 20.0, 10)
    ref = sensitivity.latency_curve(g, params, deltas, engine="scalar")
    tol_ref = sensitivity.latency_tolerance(g, params, (0.01, 0.02, 0.05,
                                                        0.1), engine="scalar")

    def no_jax(self, *a, **k):
        raise ModuleNotFoundError("No module named 'jax'", name="jax")

    monkeypatch.setattr(sweep.Engine, "run", no_jax)
    auto = sensitivity.latency_curve(g, params, deltas)
    np.testing.assert_array_equal(auto.T, ref.T)
    np.testing.assert_array_equal(auto.lam, ref.lam)
    assert sensitivity.latency_tolerance(
        g, params, (0.01, 0.02, 0.05, 0.1)) == tol_ref
    with pytest.raises(ModuleNotFoundError):
        sensitivity.latency_curve(g, params, deltas, engine="sweep")


def test_auto_dispatch_raises_on_other_import_errors(params, monkeypatch):
    """An ImportError that is not "JAX is missing" — an API that moved
    inside an installed JAX, a sibling module that is absent — is a broken
    device path and raises under engine='auto' (sensitivity and placement
    alike)."""
    from repro.core import placement
    from repro.core.graph import GraphBuilder
    from repro.core.loggps import LogGPS
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = np.linspace(0.0, 20.0, 10)
    errors = [ImportError("cannot import name 'enable_x64' from "
                          "'jax.experimental'"),
              ModuleNotFoundError("No module named 'jax.experimental.x'",
                                  name="jax.experimental.x")]
    for err in errors:
        def broken(self, *a, _err=err, **k):
            raise _err

        monkeypatch.setattr(sweep.Engine, "run", broken)
        with pytest.raises(ImportError):
            sensitivity.latency_curve(g, params, deltas)

    zero = LogGPS(L=(0.0,), G=(0.0,), o=0.5, S=1e18)
    b = GraphBuilder(4, 1)
    for r in range(0, 4, 2):
        b.add_calc(r, 1.0)
        b.add_message(r, r + 1, 65536.0, zero)
        b.add_message(r + 1, r, 65536.0, zero)
    gz = b.finalize()
    phi = placement.ArchTopology.two_tier(4, 2, L_fast=1.0, L_slow=20.0,
                                          G_fast=1e-5, G_slow=4e-5)

    def broken_run(self, *a, **k):
        raise ImportError("cannot import name 'shard_map'")

    from repro.sweep import api as sweep_api
    monkeypatch.setattr(sweep_api.Engine, "run", broken_run)
    stats: dict = {}
    with pytest.raises(ImportError, match="shard_map"):
        placement.place(gz, phi, params=zero,
                        pi0=np.array([0, 2, 1, 3]), stats=stats)
    assert stats["scalar_fallbacks"] == 0


def test_pallas_lam_override_warns_once(params, monkeypatch):
    """If the argmax kernel can't be built, an explicit backend='pallas'
    λ request raises — it is never re-routed to segment."""
    g = synth.stencil2d(2, 2, 2, params=params)
    eng = sweep.Engine(g, params=params, policy=sweep.ExecPolicy(cache=None))
    batch = sweep.latency_grid(params, [0.0, 5.0])

    real = sweep_engine._get_forward

    def fake(kind, want_lam=False, multi=False, fused=False, mesh=None,
             **kw):
        if kind == "pallas" and want_lam:
            raise ImportError("no argmax kernel in this build")
        return real(kind, want_lam, multi, fused, mesh, **kw)

    monkeypatch.setattr(sweep_engine, "_get_forward", fake)
    for _ in range(2):
        with pytest.raises(ImportError, match="no argmax kernel"):
            eng.run(batch, backend="pallas", use_cache=False)
    # the values-only pallas program is unaffected
    res = eng.run(batch, backend="pallas", outputs=("T",))
    assert res.backend == "pallas"


def test_sensitivity_memo_key_is_content_based():
    """Regression for the id(rank_of_class) memo key: logically-equal
    params built twice (distinct callables, same class mapping) share one
    compiled engine; a different mapping gets its own."""
    p1 = pod_model(pod_size=2).params()
    g = synth.stencil2d(2, 2, 2, params=p1)
    deltas = np.linspace(0.0, 10.0, 10)
    sensitivity.latency_curve(g, p1, deltas, cls=1)
    p2 = pod_model(pod_size=2).params()          # fresh, content-equal
    assert p2.rank_of_class is not p1.rank_of_class
    sensitivity.latency_curve(g, p2, deltas, cls=1)
    memo = getattr(g, "_sweep_engines")
    assert len(memo) == 1, "content-equal params must share one engine"
    p3 = pod_model(pod_size=4).params()          # different class mapping
    sensitivity.latency_curve(g, p3, deltas, cls=1)
    assert len(memo) == 2


def test_sensitivity_memoizes_engine(params):
    """Repeated dispatched calls reuse one compiled engine per graph."""
    g = synth.stencil2d(2, 2, 2, params=params)
    deltas = np.linspace(0.0, 10.0, 10)
    sensitivity.latency_curve(g, params, deltas)
    memo = getattr(g, "_sweep_engines")
    assert len(memo) == 1
    eng = next(iter(memo.values()))
    sensitivity.latency_curve(g, params, deltas)
    assert next(iter(memo.values())) is eng


def test_curve_and_resilience_share_one_engine(params):
    """The default path (``policy=None``) runs under ``ExecPolicy()`` and
    shares its memoized engine with an explicit default policy: a curve
    and then a resilience report on one graph compile one plan."""
    g = synth.cg_like(2, 2, 3, params=params)
    sensitivity.latency_curve(g, params, np.linspace(0.0, 10.0, 10))
    sensitivity.resilience_curve(
        g, params, [sweep.LinkFault(cls=0, extra_L_us=20.0)])
    assert len(getattr(g, "_sweep_engines")) == 1


@pytest.mark.parametrize("fn", ["latency_curve", "latency_tolerance",
                                "critical_latencies"])
def test_default_path_bit_identical_to_explicit_engine(params, fn):
    """The sensitivity API's default batched path is an
    ``Engine(g, params=p, policy=ExecPolicy())`` and nothing more: bit for
    bit the same answers, and no warning on the way."""
    import warnings as warnings_mod
    g = synth.cg_like(2, 2, 3, params=params)
    eng = sweep.Engine(g, params=params, policy=sweep.ExecPolicy(cache=None))
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error")
        if fn == "latency_curve":
            deltas = np.linspace(0.0, 100.0, 10)
            got = sensitivity.latency_curve(g, params, deltas)
            ref = eng.run(sweep.latency_grid(params, deltas))
            np.testing.assert_array_equal(got.T, ref.T)
            np.testing.assert_array_equal(got.lam, ref.lam[:, 0])
            np.testing.assert_array_equal(got.rho, ref.rho[:, 0])
        elif fn == "latency_tolerance":
            degr = (0.01, 0.02, 0.05, 0.1)
            assert sensitivity.latency_tolerance(g, params, degr) \
                == sweep.tolerance_batched(eng, params, degr)
        else:
            # engine="sweep": the batched path on a graph this small
            assert sensitivity.critical_latencies(
                g, params, 0.5, 300.0, engine="sweep") \
                == sweep.breakpoints_batched(eng, params, 0.5, 300.0)


def test_multisweep_override_warns_once_per_engine_instance(params,
                                                            monkeypatch):
    """Regression: a pallas λ request whose argmax kernel cannot be built
    raises on every run of every engine instance (multi-graph and
    single-graph) — no backend override, no warning."""
    import warnings as warnings_mod
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 1, params=params, algo=a),
        ["ring", "tree"], params)
    grid = sweep.latency_grid(params, [0.0, 5.0])

    real = sweep_engine._get_forward

    def fake(kind, want_lam=False, multi=False, fused=False, mesh=None,
             **kw):
        if kind == "pallas" and want_lam:
            raise ImportError("no argmax kernel in this build")
        return real(kind, want_lam, multi, fused, mesh, **kw)

    monkeypatch.setattr(sweep_engine, "_get_forward", fake)
    g = synth.stencil2d(2, 2, 2, params=params)
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error", RuntimeWarning)
        for make in (lambda: _variant_engine(variants, cache=None),
                     lambda: sweep.Engine(
                         g, params=params,
                         policy=sweep.ExecPolicy(cache=None))):
            for _ in range(2):                   # fresh instance, twice each
                eng = make()
                for _ in range(2):
                    with pytest.raises(ImportError, match="no argmax"):
                        eng.run(grid, backend="pallas", use_cache=False)


def test_cache_patched_cost_stats_and_eviction(params):
    """Patched-cost lookups are counted in the dedicated stats subset, and
    entries that differ ONLY in the cost block are distinct cache citizens
    (their keys carry the CostBatch hash) with normal LRU eviction."""
    g = synth.stencil2d(2, 2, 2, params=params)
    base = sweep.compile_plan(g, params)
    cache = sweep_cache.SweepCache(capacity=2)
    eng = sweep.Engine(base, params=params,
                       policy=sweep.ExecPolicy(cache=cache))
    batch = sweep.latency_grid(params, [0.0, 5.0])
    rng = np.random.default_rng(3)
    exs = [np.where(g.ebytes > 0, rng.uniform(0.0, 5.0, g.num_edges), 0.0)
           for _ in range(3)]

    r1 = eng.run(batch, costs=base.patch_costs(exs[0]))
    assert not r1.from_cache
    r2 = eng.run(batch, costs=base.patch_costs(exs[0]))
    assert r2.from_cache
    np.testing.assert_array_equal(r1.T, r2.T)
    st = cache.stats
    assert (st.patched_hits, st.patched_misses) == (1, 1)
    assert st.snapshot()["patched_hits"] == 1
    # keys are per backend VIEW: a raw-extras run (engine patches only the
    # vertex view) hits the entry a full patch_costs() run stored
    r_raw = eng.run(batch, costs=exs[0])
    assert r_raw.from_cache
    np.testing.assert_array_equal(r_raw.T, r1.T)
    assert cache.stats.patched_hits == 2
    # a different cost block over the SAME plan and scenarios is a miss
    assert not eng.run(batch, costs=base.patch_costs(exs[1])).from_cache
    assert cache.stats.patched_misses == 2
    # capacity 2: a third cost block evicts the first (LRU)
    assert not eng.run(batch, costs=base.patch_costs(exs[2])).from_cache
    assert cache.stats.evictions == 1
    assert not eng.run(batch, costs=base.patch_costs(exs[0])).from_cache
    assert cache.stats.patched_misses == 4
    # un-patched lookups don't touch the patched counters
    eng.run(batch)
    eng.run(batch)
    assert cache.stats.patched_misses == 4 and cache.stats.patched_hits == 2
    assert cache.stats.hits == 3 and cache.stats.misses == 5
    # caller mutation of a patched result must not poison later hits
    ra = eng.run(batch, costs=base.patch_costs(exs[0]), use_cache=False)
    rb = eng.run(batch, costs=base.patch_costs(exs[0]))
    ref = rb.T.copy()
    rb.T[:] = -1.0
    np.testing.assert_array_equal(
        eng.run(batch, costs=base.patch_costs(exs[0])).T, ref)
    np.testing.assert_array_equal(ra.T, ref)


def test_placement_patch_stats_and_cache(params):
    """The zero-recompile greedy loop: one plan compile for the whole
    search, candidate evaluations served through cost patching (and, when
    a cache is supplied, memoized under patched-cost keys)."""
    from repro.core import placement
    from repro.core.graph import GraphBuilder
    from repro.core.loggps import LogGPS

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
    pi0 = np.argsort(np.concatenate([np.arange(0, P, 2),
                                     np.arange(1, P, 2)]))

    st_patch, st_reb = {}, {}
    pi_p, h_p = placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                stats=st_patch)
    pi_r, h_r = placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                cost_eval="rebuild", stats=st_reb)
    np.testing.assert_array_equal(pi_p, pi_r)     # bit-identical mapping
    assert h_p == h_r
    assert st_patch["steps"] >= 2                 # a real search happened
    assert st_patch["plan_compiles"] == 1         # compile once, patch ever
    # one engine dispatch per attempted step (the last attempt may fail
    # the improvement test and not count as a step)
    assert st_patch["steps"] <= st_patch["engine_calls"] \
        <= st_patch["steps"] + 1
    assert st_reb["plan_compiles"] == st_reb["candidates"]  # K per step
    assert st_patch["scalar_fallbacks"] == 0
    with pytest.raises(ValueError, match="cost_eval"):
        placement.place(g, phi, params=zero, cost_eval="magic")
    # a backend typo must fail loudly, not silently degrade every step
    # to the scalar fallback
    with pytest.raises(ValueError, match="backend"):
        placement.place(g, phi, params=zero, backend="pallsa")
    # repeated identical searches through a shared cache hit patched keys
    cache = sweep_cache.SweepCache(capacity=32)
    placement.place(g, phi, params=zero, pi0=pi0.copy(), cache=cache)
    assert cache.stats.patched_misses > 0
    placement.place(g, phi, params=zero, pi0=pi0.copy(), cache=cache)
    assert cache.stats.patched_hits >= cache.stats.patched_misses

