"""The unified sweep API (repro.sweep.api): Query/ExecPolicy/Engine.

Axis-equivalence guarantees live in ``tests/test_conformance.py`` (the
G×K×S matrix); this file covers the API surface itself — policy
validation and wire parsing, query normalization, the relaxed
finite-difference λ mode, and the policy plumbing through
``core.sensitivity`` and ``core.placement``.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import dag, sensitivity, synth
from repro.core.loggps import cluster_params, pod_model
from repro import sweep
from repro.sweep import engine as sweep_engine
from repro.sweep.api import Engine, ExecPolicy, Query


@pytest.fixture(scope="module")
def params():
    return cluster_params(L_us=3.0, o_us=5.0)


# -- ExecPolicy ---------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError, match="backend"):
        ExecPolicy(backend="cuda").validate()
    with pytest.raises(ValueError, match="shard_axis"):
        ExecPolicy(shard_axis="Z").validate()
    with pytest.raises(ValueError, match="lam mode"):
        ExecPolicy(lam="approx").validate()
    with pytest.raises(ValueError, match="fd_eps"):
        ExecPolicy(fd_eps=0.0).validate()
    with pytest.raises(ValueError, match="dtype"):
        ExecPolicy(dtype="bfloat16").validate()
    # dtype pins the backend's numeric contract: a mismatch is an error,
    # not a silent downgrade
    with pytest.raises(ValueError, match="float64"):
        ExecPolicy(backend="segment", dtype="float32").validate()
    with pytest.raises(ValueError, match="float32"):
        ExecPolicy(backend="pallas", dtype="float64").validate()
    ExecPolicy(backend="segment", dtype="float64").validate()
    ExecPolicy(backend="pallas", dtype="float32").validate()


def test_policy_from_dict_rejects_unknown_and_wire_fields():
    with pytest.raises(ValueError, match=r"bakend"):
        ExecPolicy.from_dict({"bakend": "pallas"})
    # the error lists every offending key
    with pytest.raises(ValueError, match=r"\['bakend', 'sahrd'\]"):
        ExecPolicy.from_dict({"bakend": "pallas", "sahrd": 2})
    # cache is a process-local object, never wire state
    with pytest.raises(ValueError, match="cache"):
        ExecPolicy.from_dict({"cache": None})
    pol = ExecPolicy.from_dict({"backend": "pallas", "lam": "fd"},
                               base=ExecPolicy(shard=2))
    assert (pol.backend, pol.lam, pol.shard) == ("pallas", "fd", 2)


# -- Query / Engine surface ---------------------------------------------------

def test_query_outputs_validation(params):
    g = synth.stencil2d(2, 2, 2, params=params)
    eng = Engine(g, params=params, policy=ExecPolicy(cache=None))
    batch = sweep.latency_grid(params, [0.0, 5.0])
    with pytest.raises(ValueError, match="outputs"):
        eng.run(Query(scenarios=batch, outputs=("T", "sigma")))
    with pytest.raises(ValueError, match="scenarios"):
        eng.run(Query())
    r = eng.run(Query(scenarios=batch, outputs=("T",)))
    assert r.lam is None and r.rho is None
    # requesting rho computes lam too (a free ratio)
    r2 = eng.run(Query(scenarios=batch, outputs=("T", "rho")))
    assert r2.lam is not None and r2.rho is not None


def test_detached_query_and_module_run(params):
    """A Query can carry its own graphs — the declarative one-shot form."""
    g = synth.stencil2d(2, 2, 2, params=params)
    batch = sweep.latency_grid(params, [0.0, 5.0, 10.0])
    res = sweep.run(Query(graphs=g, params=params, scenarios=batch),
                    policy=ExecPolicy(cache=None))
    ref = Engine(g, params=params, policy=ExecPolicy(cache=None)).run(batch)
    np.testing.assert_array_equal(res.T, ref.T)
    np.testing.assert_array_equal(res.lam, ref.lam)
    with pytest.raises(ValueError, match="graphs"):
        sweep.run(Query(scenarios=batch))


def test_engine_result_helpers(params):
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 2, params=params, algo=a),
        ["ring", "recursive_doubling"], params)
    eng = Engine([(v.graph, v.params) for v in variants],
                 names=[v.name for v in variants],
                 policy=ExecPolicy(cache=None))
    res = eng.run(sweep.latency_grid(params, np.linspace(0, 40, 10)))
    assert res.axes == ("G", "S") and res.G == 2
    order = res.rank(reduce="final")
    assert order[0][0] == "algo=recursive_doubling"     # Fig 10 ordering
    by_name = res["algo=ring"]
    by_idx = res[0]
    np.testing.assert_array_equal(by_name.T, by_idx.T)
    assert by_name.axes == ("S",)
    assert set(res.split()) == {v.name for v in variants}
    with pytest.raises(ValueError, match="reduce"):
        res.rank(reduce="median")


def test_multi_engine_rejects_single_costbatch(params):
    cases = [synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=s)
             for s in (1, 2)]
    plans = [sweep.compile_plan(g, params) for g in cases]
    eng = Engine(plans, policy=ExecPolicy(cache=None))
    batch = sweep.latency_grid(params, [0.0, 5.0])
    cb = plans[0].patch_costs(np.zeros((2, cases[0].num_edges)))
    with pytest.raises(ValueError, match="per graph"):
        eng.run(Query(scenarios=batch, costs=cb))
    # per-graph batches must share K
    with pytest.raises(ValueError, match="share K"):
        eng.run(Query(scenarios=batch, costs=[
            np.zeros((2, cases[0].num_edges)),
            np.zeros((3, cases[1].num_edges))]))
    # a batch minted on the WRONG member plan is refused by content
    with pytest.raises(ValueError, match="different plan"):
        eng.run(Query(scenarios=batch, costs=[
            plans[1].patch_costs(np.zeros((2, cases[1].num_edges))),
            plans[0].patch_costs(np.zeros((2, cases[0].num_edges)))]))


# -- relaxed λ: finite-difference mode ---------------------------------------

def test_fd_lambda_matches_exact_at_non_breakpoints(params):
    """ExecPolicy(lam="fd"): λ from the (nc+1)× expanded values grid
    equals the exact backtrace λ at non-breakpoint scenarios (T is
    piecewise linear; λ is its exact right-derivative), T bit-identically
    (it IS the values program), ρ to the same tolerance — including
    two-class params and the candidate-cost axis."""
    p2 = pod_model(pod_size=2).params()
    cases = [(synth.stencil2d(3, 3, 4, params=params), params),
             (synth.cg_like(2, 2, 3, params=params), params),
             (synth.stencil2d(2, 2, 3, params=p2), p2)]
    for g, p in cases:
        # off-grid deltas: nothing here lands on a breakpoint
        grid = sweep.latency_grid(p, [0.317, 7.713, 23.131])
        exact = Engine(g, params=p, policy=ExecPolicy(cache=None)).run(grid)
        fd = Engine(g, params=p,
                    policy=ExecPolicy(lam="fd", cache=None)).run(grid)
        assert fd.lam_mode == "fd"
        np.testing.assert_array_equal(fd.T, exact.T)
        np.testing.assert_allclose(fd.lam, exact.lam, atol=1e-6)
        np.testing.assert_allclose(fd.rho, exact.rho, atol=1e-6)

    # composes with the candidate axis
    g, p = cases[0]
    rng = np.random.default_rng(5)
    extras = np.where(g.ebytes[None] > 0,
                      rng.uniform(0.0, 5.0, (3, g.num_edges)), 0.0)
    grid = sweep.latency_grid(p, [0.317, 7.713])
    plan = sweep.compile_plan(g, p)
    ex_res = Engine(plan, params=p, policy=ExecPolicy(cache=None)).run(
        Query(scenarios=grid, costs=extras))
    fd_res = Engine(plan, params=p,
                    policy=ExecPolicy(lam="fd", cache=None)).run(
        Query(scenarios=grid, costs=extras))
    np.testing.assert_array_equal(fd_res.T, ex_res.T)
    np.testing.assert_allclose(fd_res.lam, ex_res.lam, atol=1e-6)


def test_fd_lambda_never_compiles_a_lambda_program(params):
    """The fd mode's whole point: it reuses the VALUES program (an
    (nc+1)× taller scenario batch) — the λ-bearing program, whose compile
    is the measured ~2.5-3× values-only cost, is never built."""
    g = synth.stencil2d(3, 3, 4, params=params, jitter=0.2, seed=77)
    grid = sweep.latency_grid(params, [0.4, 6.7, 19.2])
    lam_fwd = sweep_engine._get_forward("segment", True)
    vals_fwd = sweep_engine._get_forward("segment", False)
    n_lam = lam_fwd._cache_size()
    eng = Engine(g, params=params, policy=ExecPolicy(lam="fd", cache=None))
    res = eng.run(grid)
    assert res.lam is not None
    assert lam_fwd._cache_size() == n_lam, \
        "fd λ compiled a λ-bearing program"
    # and re-running at a different grid size inside the padded envelope
    # (3 points → expanded 6 → bucket 8; 4 points → expanded 8 → bucket 8)
    # adds no values programs either
    n_vals = vals_fwd._cache_size()
    eng.run(sweep.latency_grid(params, [0.4, 6.7, 13.1, 21.9]))
    assert vals_fwd._cache_size() == n_vals


def test_fd_cache_key_is_distinct(params):
    """fd and exact results must never collide in the cache (different
    numeric contract), but identical fd queries must hit."""
    g = synth.stencil2d(2, 2, 2, params=params)
    cache = sweep.SweepCache(capacity=8)
    grid = sweep.latency_grid(params, [0.3, 5.7])
    ex_eng = Engine(g, params=params, policy=ExecPolicy(cache=cache))
    fd_eng = Engine(g, params=params,
                    policy=ExecPolicy(lam="fd", cache=cache))
    assert not ex_eng.run(grid).from_cache
    r_fd = fd_eng.run(grid)
    assert not r_fd.from_cache            # distinct key from the exact run
    assert fd_eng.run(grid).from_cache    # identical fd query hits
    assert fd_eng.run(grid).lam_mode == "fd"
    # a different step size is a different contract → different key
    assert not Engine(g, params=params,
                      policy=ExecPolicy(lam="fd", fd_eps=2.0 ** -8,
                                        cache=cache)).run(grid).from_cache


# -- downstream policy plumbing ----------------------------------------------

def test_sensitivity_policy_argument(params):
    """sensitivity.* take one policy object instead of loose kwargs; the
    fd policy returns the scalar path's numbers away from breakpoints."""
    g = synth.cg_like(2, 2, 3, params=params)
    deltas = [0.41, 3.77, 9.13, 17.9]
    scalar = sensitivity.latency_curve(g, params, deltas, engine="scalar")
    pol = ExecPolicy(lam="fd", cache=None)
    fd = sensitivity.latency_curve(g, params, deltas, policy=pol)
    np.testing.assert_allclose(fd.T, scalar.T, rtol=1e-12)
    np.testing.assert_allclose(fd.lam, scalar.lam, atol=1e-6)
    # policy-built engines are memoized separately per policy content
    memo = getattr(g, "_sweep_engines")
    n = len(memo)
    sensitivity.latency_curve(g, params, deltas, policy=pol)
    assert len(memo) == n
    sensitivity.latency_curve(g, params, deltas,
                              policy=ExecPolicy(cache=None))
    assert len(memo) == n + 1
    # bandwidth/tolerance accept it too
    bw = sensitivity.bandwidth_curve(g, params, [1.0, 2.0, 3.0], policy=pol)
    bw_s = sensitivity.bandwidth_curve(g, params, [1.0, 2.0, 3.0],
                                       engine="scalar")
    np.testing.assert_allclose(bw.T, bw_s.T, rtol=1e-12)
    tol = sensitivity.latency_tolerance(g, params, (0.05,), policy=pol)
    ref = dag.tolerance(g, params, 0.05)
    assert tol[0.05] == pytest.approx(ref, rel=1e-6)


def test_placement_policy_argument(params):
    """place(policy=) supersedes the loose backend/cache kwargs and keeps
    the zero-recompile accounting."""
    from repro.core import placement
    from repro.core.graph import GraphBuilder
    from repro.core.loggps import LogGPS

    P = 8
    zero = LogGPS(L=(0.0,), G=(0.0,), o=0.5, S=1e18)
    b = GraphBuilder(P, 1)
    for _ in range(4):
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
    cache = sweep.SweepCache(capacity=32)
    st: dict = {}
    pi_a, h_a = placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                policy=ExecPolicy(cache=cache), stats=st)
    assert st["plan_compiles"] == 1 and st["scalar_fallbacks"] == 0
    assert cache.stats.patched_misses > 0       # policy cache was used
    pi_b, h_b = placement.place(g, phi, params=zero, pi0=pi0.copy())
    np.testing.assert_array_equal(pi_a, pi_b)
    assert h_a == h_b
    with pytest.raises(ValueError, match="backend"):
        placement.place(g, phi, params=zero,
                        policy=ExecPolicy(backend="pallsa"))


# -- review regressions -------------------------------------------------------

def test_policy_shard_validation_and_wire(params):
    """shard is validated at policy level (and so at the protocol edge) —
    a {"shard": "always"} typo must not surface as a deep int() failure."""
    with pytest.raises(ValueError, match="shard"):
        ExecPolicy(shard="always").validate()
    with pytest.raises(ValueError, match="shard"):
        ExecPolicy.from_dict({"shard": "always"})
    ExecPolicy(shard="auto").validate()
    ExecPolicy(shard=2).validate()


def test_compute_lam_flag_wins_over_query_defaults(params, monkeypatch):
    """run(Query(...), outputs=("T",)) must not silently pay for λ — the
    keyword overrides the Query's defaulted outputs tuple, and no λ
    program is built or run."""
    g = synth.stencil2d(2, 2, 2, params=params)
    eng = Engine(g, params=params, policy=ExecPolicy(cache=None))
    batch = sweep.latency_grid(params, [0.0, 5.0])
    asked = []
    real = sweep_engine._get_forward

    def spy(kind, want_lam=False, *a, **kw):
        asked.append(want_lam)
        return real(kind, want_lam, *a, **kw)

    monkeypatch.setattr(sweep_engine, "_get_forward", spy)
    res = eng.run(Query(scenarios=batch), outputs=("T",))
    assert res.lam is None and res.rho is None
    assert asked == [False]


def test_argbest_rejects_bare_graph_axis(params):
    g1 = synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=1)
    g2 = synth.stencil2d(3, 3, 4, params=params, jitter=0.1, seed=2)
    eng = Engine([sweep.compile_plan(g, params) for g in (g1, g2)],
                 policy=ExecPolicy(cache=None))
    res = eng.run(sweep.latency_grid(params, [0.0, 5.0]))
    with pytest.raises(TypeError, match="rank"):
        res.argbest()
    assert res[0].argbest() in (0, 1)            # sliced: scenario index


def test_pinned_dtype_refuses_pallas_lambda_fallback(params, monkeypatch):
    """A pallas λ query whose argmax kernel is unavailable raises — pinned
    dtype='float32' or not; it is never served by the float64 segment
    backend."""
    g = synth.stencil2d(2, 2, 2, params=params)
    batch = sweep.latency_grid(params, [0.0, 5.0])

    real = sweep_engine._get_forward

    def fake(kind, want_lam=False, multi=False, fused=False, mesh=None,
             **kw):
        if kind == "pallas" and want_lam:
            raise ImportError("no argmax kernel in this build")
        return real(kind, want_lam, multi, fused, mesh, **kw)

    monkeypatch.setattr(sweep_engine, "_get_forward", fake)
    for dtype in ("float32", "auto"):
        eng = Engine(g, params=params,
                     policy=ExecPolicy(backend="pallas", dtype=dtype,
                                       cache=None))
        with pytest.raises(ImportError, match="no argmax kernel"):
            eng.run(batch)


def test_explicit_policy_failures_surface(params, monkeypatch):
    """Engine failures raise whether or not a policy= was given: an
    explicit policy pins the batched path, and the default 'auto' path
    never hides a failure behind a scalar loop either."""
    from repro.sweep import api as sweep_api

    g = synth.cg_like(2, 2, 2, params=params)   # fresh graph: empty memo

    def boom(self, *a, **k):
        raise RuntimeError("injected unified-engine failure")

    monkeypatch.setattr(sweep_api.Engine, "run", boom)
    with pytest.raises(RuntimeError, match="injected unified-engine"):
        sensitivity.latency_curve(g, params, [0.1, 2.3],
                                  policy=ExecPolicy(cache=None))
    with pytest.raises(RuntimeError, match="injected unified-engine"):
        # the shim delegates to Engine.run, so the boom hits 'auto' too
        sensitivity.latency_curve(g, params, np.linspace(0, 20, 10))
