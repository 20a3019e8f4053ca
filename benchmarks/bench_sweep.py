"""Batched scenario-sweep engine vs looping the scalar LevelPlan.

Two acceptance bars, measured here:

* single graph: a 1,000-scenario LogGPS grid must evaluate ≥10× faster per
  scenario than calling ``dag.LevelPlan.forward`` in a Python loop, with
  identical results (1e-6).
* variant study (``structure_patch``): a 4-variant × 250-scenario
  collective study — four graphs in four *different* shape buckets — runs
  as one ``Query(structure=)`` dispatch on a
  :class:`~repro.sweep.StructureBatch` envelope and compiles one XLA
  program, where the per-variant loop pays one compile per distinct
  shape.  Results must agree bit-for-bit.

Also reported: the values-only fast path, the Pallas (max,+) backend on a
small grid (values AND λ — the argmax-emitting kernel, no segment
redirect), the content-hash cache hit, AOT compile times of the λ-bearing
segment layouts (two-pass vs fused vs values-only), and a multi-device
smoke proving sharded runs bit-equal single-device ones (on the host's own
devices, or a forced CPU mesh in a CPU-only child).

CLI (used by CI)::

    PYTHONPATH=src python -m benchmarks.bench_sweep --smoke

``--smoke`` shrinks the grids so the whole file runs in seconds and asserts
only correctness invariants (exactness, call counts) — never wall-clock
ratios, which CI machines can't promise.
"""

from __future__ import annotations

import time

import numpy as np

from repro import sweep
from repro.core import dag, synth
from repro.core.loggps import cluster_params

from .common import csv_line, timeit

N_SCENARIOS = 1_000
STUDY_ALGOS = ("ring", "bidir_ring", "recursive_doubling", "tree")
STUDY_SCENARIOS = 250


def single_graph(out, n_scenarios=N_SCENARIOS):
    p = cluster_params(L_us=3.0, o_us=5.0)
    g = synth.stencil2d(4, 4, 20, params=p)
    ev = g.num_events
    deltas = np.linspace(0.0, 100.0, n_scenarios)
    grid = sweep.latency_grid(p, deltas)

    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    t_batch, res = timeit(lambda: eng.run(grid), repeats=2, warmup=1)
    t_vals, _ = timeit(lambda: eng.run(grid, outputs=("T",)),
                       repeats=2, warmup=1)

    plan = dag.LevelPlan(g)

    def scalar_loop():
        return np.asarray([plan.forward(p.with_delta(float(d))).T
                           for d in deltas])

    t_loop, Ts_scalar = timeit(scalar_loop, repeats=1, warmup=0)
    err = float(np.max(np.abs(res.T - Ts_scalar)))
    assert err < 1e-6, f"batched sweep diverged from scalar engine: {err}"
    speedup = t_loop / t_batch
    out(csv_line(f"sweep.batched.{n_scenarios}", t_batch * 1e6,
                 f"events={ev};speedup_vs_loop={speedup:.1f}x;max_err={err:.1e}"))
    out(csv_line(f"sweep.values_only.{n_scenarios}", t_vals * 1e6,
                 f"events={ev};us_per_scenario={t_vals * 1e6 / n_scenarios:.2f}"))
    out(csv_line(f"sweep.scalar_loop.{n_scenarios}", t_loop * 1e6,
                 f"events={ev};us_per_scenario={t_loop * 1e6 / n_scenarios:.2f}"))

    # cached re-run: content-hash hit, no forward pass
    eng_c = sweep.Engine(g, params=p,
                         policy=sweep.ExecPolicy(cache=sweep.SweepCache()))
    eng_c.run(grid)
    t_hit, res_hit = timeit(lambda: eng_c.run(grid), repeats=3, warmup=0)
    assert res_hit.from_cache
    out(csv_line("sweep.cache_hit", t_hit * 1e6, f"scenarios={n_scenarios}"))


def pallas_backend(out, n_scenarios=64):
    # pallas (max,+) inner-scatter backend, small graph + grid (on the CPU
    # backend the kernel runs in interpret mode, so keep this smoke-scale)
    p = cluster_params(L_us=3.0, o_us=5.0)
    g_small = synth.cg_like(2, 2, 3, params=p)
    eng_p = sweep.Engine(g_small, params=p, policy=sweep.ExecPolicy(cache=None))
    grid_small = sweep.latency_grid(p, np.linspace(0.0, 50.0, n_scenarios))
    seg = eng_p.run(grid_small)
    t_pal, pal = timeit(lambda: eng_p.run(grid_small, backend="pallas",
                                          outputs=("T",)),
                        repeats=2, warmup=1)
    rel = float(np.max(np.abs(pal.T - seg.T) / seg.T))
    # float32 accumulators (TPU VPU layout) → relative tolerance
    assert rel < 1e-5, f"pallas backend diverged from segment: {rel}"
    out(csv_line(f"sweep.pallas.{n_scenarios}", t_pal * 1e6,
                 f"rel_vs_segment={rel:.1e}"))

    # λ/ρ straight from the argmax-emitting kernel — no segment redirect
    t_lam, pal_lam = timeit(lambda: eng_p.run(grid_small, backend="pallas"),
                            repeats=2, warmup=1)
    assert pal_lam.backend == "pallas", pal_lam.backend
    rel_l = float(np.max(np.abs(pal_lam.lam - seg.lam)))
    assert rel_l < 1e-4, f"pallas λ diverged from segment: {rel_l}"
    out(csv_line(f"sweep.pallas_lam.{n_scenarios}", t_lam * 1e6,
                 f"lam_err_vs_segment={rel_l:.1e}"))


def lam_compile(out, n_scenarios=256):
    """AOT compile-time of the λ-bearing segment programs vs values-only.

    Fresh jit wrappers + ``.lower().compile()`` per measurement, so every
    number is a real XLA compile of that (shape, layout) cell: the
    values-only forward, the default two-pass λ layout (next-pointer
    records + reverse pointer-chase), and the original fused backtrace
    (``fused=True`` reference).  The two-pass layout must never compile
    slower than the fused one it replaced; the honest finding recorded
    here is that ANY bit-exact λ program pays for the tie-break
    arithmetic itself (hit/slope/ordinal reductions per level), not for
    the fused slope carry — so λ compile stays well above the ISSUE's
    1.2× values-only target on XLA:CPU (~2.5-3×) in either layout.
    """
    import jax
    import jax.numpy as jnp

    from repro.sweep import engine as sweep_engine

    p = cluster_params(L_us=3.0, o_us=5.0)
    g = synth.stencil2d(4, 4, 20, params=p)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    grid = sweep.latency_grid(p, np.linspace(0.0, 100.0, n_scenarios))
    S = grid.S
    Sp = sweep_engine._bucket(S, lo=4)
    Lmat = np.repeat(grid.L[-1:], Sp, axis=0)
    Lmat[:S] = grid.L
    GSmat = np.ones_like(Lmat)

    def compile_ms(want_lam, fused=False, repeats=2):
        best = np.inf
        with jax.enable_x64():
            arrs = eng._arrays("segment")
            L, GS = jnp.asarray(Lmat), jnp.asarray(GSmat)
            for _ in range(repeats):
                fn = jax.jit(sweep_engine._segment_core(want_lam, fused))
                t0 = time.perf_counter()
                fn.lower(*arrs, L, GS).compile()
                best = min(best, time.perf_counter() - t0)
        return best * 1e3

    t_vals = compile_ms(False)
    t_two = compile_ms(True)
    t_fused = compile_ms(True, fused=True)
    out(csv_line("sweep.lam_compile.values", t_vals * 1e3,
                 f"scenarios={n_scenarios}"))
    out(csv_line("sweep.lam_compile.twopass", t_two * 1e3,
                 f"vs_values={t_two / t_vals:.2f}x;"
                 f"vs_fused={t_two / t_fused:.2f}x"))
    out(csv_line("sweep.lam_compile.fused", t_fused * 1e3,
                 f"vs_values={t_fused / t_vals:.2f}x"))


def _biased_placement_workload(P, iters):
    """Chatty rank pairs with distinct message sizes and an adversarial
    start mapping that splits every pair across pods — the greedy search
    has real work to do (the bench_placement fixture, parameterized)."""
    from repro.core import placement
    from repro.core.graph import GraphBuilder
    from repro.core.loggps import LogGPS

    zero = LogGPS(L=(0.0,), G=(0.0,), o=0.5, S=1e18)
    b = GraphBuilder(P, 1)
    for it in range(iters):
        for idx, r in enumerate(range(0, P, 2)):
            b.add_calc(r, 1.0)
            sz = 65536.0 * (1.0 + 0.25 * idx)
            b.add_message(r, r + 1, sz, zero)
            b.add_message(r + 1, r, sz, zero)
    g = b.finalize()
    phi = placement.ArchTopology.two_tier(P, P // 2, L_fast=1.0,
                                          L_slow=20.0, G_fast=1e-5,
                                          G_slow=4e-5)
    pi0 = np.argsort(np.concatenate([np.arange(0, P, 2),
                                     np.arange(1, P, 2)]))
    return g, zero, phi, pi0


def placement_patch(out, smoke: bool = False):
    """Zero-recompile placement search (Algorithm 3 with patchable costs).

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * the whole greedy search performs exactly ONE plan compile — every
      candidate swap of every step is evaluated by patching Φ costs into
      the warm plan (``stats["plan_compiles"] == 1``);
    * after the first search warmed the XLA program, a re-run adds ZERO
      compiled programs (a :class:`repro.obs.CompileWatcher` scoped to
      the candidate-cost forward cell — the same recompile definition
      ``Engine.run`` reports against in production);
    * the final mapping and objective history are bit-identical to the
      rebuild loop (K fresh CompiledPlans per step).

    Full mode additionally asserts the ≥5× per-step candidate-evaluation
    speedup over the rebuild loop (wall-clock — not asserted in CI).
    """
    import jax  # noqa: F401 — the engine path needs it; fail loud here
    from repro import obs
    from repro.core import placement
    from repro.sweep import ScenarioBatch, compile_plan
    from repro.sweep.api import Engine, ExecPolicy

    P, iters, topk = (8, 4, 4) if smoke else (32, 12, 16)
    g, zero, phi, pi0 = _biased_placement_workload(P, iters)

    st_p: dict = {}
    t_cold, (pi_p, hist_p) = timeit(
        lambda: placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                topk=topk, stats=st_p),
        repeats=1, warmup=0)
    # the candidate-cost forward cell the loop compiled (vertex-view patch
    # on the segment backend): its program count must not grow on re-runs
    watcher = obs.CompileWatcher(cells=[obs.forward_cell(
        "segment", False, costs=(0, None, None, None, None))])
    n_prog = watcher.programs()
    with watcher.watch("placement.rerun") as rec:
        t_warm, _ = timeit(
            lambda: placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                    topk=topk, stats={}),
            repeats=1, warmup=0)
    assert rec.new_programs == 0, \
        "placement re-run recompiled the candidate-cost forward"
    assert st_p["plan_compiles"] == 1, st_p
    assert st_p["scalar_fallbacks"] == 0, st_p
    assert st_p["steps"] >= 2, f"search converged trivially: {st_p}"

    st_r: dict = {}
    t_reb, (pi_r, hist_r) = timeit(
        lambda: placement.place(g, phi, params=zero, pi0=pi0.copy(),
                                topk=topk, cost_eval="rebuild", stats=st_r),
        repeats=1, warmup=1)
    assert np.array_equal(pi_p, pi_r), "patched ≠ rebuild final mapping"
    assert hist_p == hist_r, "patched ≠ rebuild objective history"
    assert st_r["plan_compiles"] == st_r["candidates"], st_r

    # per-step candidate evaluation, warm (the cost the tentpole removed:
    # K plan rebuilds + MultiPlan pack + restage vs one patched dispatch)
    base = compile_plan(g)
    eng = Engine(base, policy=ExecPolicy(cache=None))
    scen = ScenarioBatch(L=np.asarray([zero.L]),
                         gscale=np.ones((1, g.nclass)))
    rng = np.random.default_rng(0)
    extras = [placement.mapping_edge_cost(g, phi, rng.permutation(P))
              for _ in range(topk)]
    EX = np.stack(extras)
    t_patch_step, res = timeit(
        lambda: eng.run(scen, costs=EX, outputs=("T",)),
        repeats=5, warmup=2)
    t_reb_step, ref = timeit(
        lambda: placement._candidate_objectives(g, scen, extras, "segment"),
        repeats=5, warmup=2)
    assert np.array_equal(res.T.mean(axis=1), ref), \
        "patched candidate objectives diverged from rebuild"
    speedup = t_reb_step / t_patch_step
    if not smoke:
        assert speedup >= 5.0, \
            f"per-step patch speedup {speedup:.1f}x < 5x target"

    out(csv_line("sweep.placement_patch.search", t_warm * 1e6,
                 f"P={P};topk={topk};steps={st_p['steps']};"
                 f"plan_compiles={st_p['plan_compiles']};"
                 f"xla_programs={n_prog};"
                 f"same_mapping_as_rebuild=1"))
    out(csv_line("sweep.placement_patch.step", t_patch_step * 1e6,
                 f"candidates={topk};"
                 f"rebuild_us={t_reb_step * 1e6:.0f};"
                 f"per_step_speedup={speedup:.1f}x"))
    out(csv_line("sweep.placement_patch.cold", t_cold * 1e6,
                 f"rebuild_cold_us={t_reb * 1e6:.0f}"))


def unified_axes(out, smoke: bool = False):
    """One engine, three axes (the PR-5 API): a G×K×S query through the
    unified ``repro.sweep.api.Engine``.

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * re-running a warm query with different K and S sizes *inside the
      padded envelope* adds ZERO new XLA programs, reported by the same
      :class:`repro.obs.CompileWatcher` production uses (K and S are
      bucketed, G/K/S compose in one jit cell — the combinatorial growth
      the old two-engine split would have paid is gone);
    * the G×K×S segment result is bit-identical to the equivalent legacy
      solo/rebuild runs (spot-checked on one (g, k) slice here; the full
      matrix lives in tests/test_conformance.py);
    * relaxed λ (``ExecPolicy(lam="fd")``) never compiles a λ-bearing
      program — sensitivities at values-program compile cost (ratio ~1.0
      vs the measured ~2.5-3× for bit-exact λ, see ``lam_compile``);
    * tracing on vs off returns bit-identical results (full mode also
      asserts the ≤2% warm-path overhead budget — wall-clock, so never
      asserted under ``--smoke``).
    """
    from repro import obs
    from repro.sweep.api import Engine, ExecPolicy, Query

    p = cluster_params(L_us=3.0, o_us=5.0)
    n_sc = 6 if smoke else 200
    gs = [synth.stencil2d(3, 3, 4, params=p, jitter=0.1, seed=s)
          for s in (1, 2)]
    plans = [sweep.compile_plan(g, p) for g in gs]
    rng = np.random.default_rng(0)
    extras = [np.where(g.ebytes[None] > 0,
                       rng.uniform(0.0, 5.0, (3, g.num_edges)), 0.0)
              for g in gs]
    eng = Engine(plans, policy=ExecPolicy(cache=None))
    grid = sweep.latency_grid(p, np.linspace(0.0, 50.0, n_sc))

    t_cold, res = timeit(lambda: eng.run(Query(scenarios=grid,
                                               costs=extras)),
                         repeats=1, warmup=0)
    assert res.axes == ("G", "K", "S") and res.T.shape == (2, 3, n_sc)

    # the cell the query compiled: G present, vconst patched on K
    watcher = obs.CompileWatcher(cells=[obs.forward_cell(
        "segment", True, multi=True, costs=(0, None, None, None, None))])
    # different K (3→4 pads to the same K bucket) and different S (within
    # the same scenario bucket): ZERO new programs
    extras4 = [np.concatenate([ex, ex[:1]]) for ex in extras]
    grid_small = sweep.latency_grid(p, np.linspace(0.0, 50.0,
                                                   max(n_sc - 1, 5)))
    with watcher.watch("gks.warm_rerun") as rec:
        t_warm, res2 = timeit(lambda: eng.run(Query(scenarios=grid_small,
                                                    costs=extras4)),
                              repeats=2, warmup=0)
    assert rec.new_programs == 0, \
        "warm G×K×S re-run within the padded envelope recompiled"

    # legacy-equivalence spot check (bit-exact): graph 1, cost block 2
    reb = sweep.compile_plan(gs[1], p, extra_edge_cost=extras[1][2])
    ref = Engine(reb, params=p, policy=ExecPolicy(cache=None)).run(grid)
    assert np.array_equal(res.T[1, 2], ref.T)
    assert np.array_equal(res.lam[1, 2], ref.lam)

    # relaxed λ: fd mode reuses the values program — no λ cell ever built
    # (watcher scoped to the λ cell alone: the fresh fd engine legitimately
    # compiles a *values* program for its expanded grid)
    lam_watcher = obs.CompileWatcher(
        cells=[obs.forward_cell("segment", True)])
    fd_eng = Engine(plans[0], params=p,
                    policy=ExecPolicy(lam="fd", cache=None))
    with lam_watcher.watch("fd.lam") as lam_rec:
        t_fd, fd_res = timeit(lambda: fd_eng.run(grid), repeats=1, warmup=0)
    assert fd_res.lam is not None
    assert lam_rec.new_programs == 0, "fd λ built a λ program"

    # observability gates: tracing on vs off must be bit-identical on the
    # warm G×K×S path, and the span overhead must fit the ≤2% budget
    # (wall-clock ratio: full mode only, CI machines can't promise it)
    q = Query(scenarios=grid_small, costs=extras4)
    was_enabled = obs.enabled()
    try:
        obs.disable()
        t_off, res_off = timeit(lambda: eng.run(q), repeats=3, warmup=1)
        obs.enable()
        t_on, res_on = timeit(lambda: eng.run(q), repeats=3, warmup=1)
    finally:
        obs.enable() if was_enabled else obs.disable()
    assert np.array_equal(res_on.T, res_off.T), \
        "tracing changed the result tensor"
    assert np.array_equal(res_on.lam, res_off.lam), \
        "tracing changed the λ tensor"
    overhead = t_on / t_off
    if not smoke:
        assert overhead <= 1.02, \
            f"tracing overhead {overhead:.3f}x exceeds the 2% budget"

    out(csv_line("sweep.unified_axes.gks_cold", t_cold * 1e6,
                 f"G=2;K=3;S={n_sc};zero_recompile_rerun=1;"
                 f"bit_equal_rebuild=1"))
    out(csv_line("sweep.unified_axes.gks_warm", t_warm * 1e6,
                 f"K=4;S={grid_small.S};new_xla_programs=0"))
    out(csv_line("sweep.unified_axes.fd_lam", t_fd * 1e6,
                 f"S={n_sc};lam_programs_compiled=0"))
    out(csv_line("sweep.unified_axes.obs_overhead", t_on * 1e6,
                 f"ratio_vs_untraced={overhead:.3f}x;"
                 f"bit_identical=1;budget=1.02x"))


def structure_patch(out, smoke: bool = False):
    """Zero-recompile topology study (the structural half of the PR-7
    tentpole): a 4-variant collective study as ONE ``Query(structure=)``
    dispatch on a :class:`repro.sweep.StructureBatch` envelope.

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * the whole 4-variant study compiles exactly ONE new XLA program — the
      structure-batched forward cell — reported by the same
      :class:`repro.obs.CompileWatcher` production uses;
    * a DIFFERENT study on the same envelope (the variants reordered)
      compiles ZERO more programs and returns the same rows, permuted,
      bit for bit;
    * every variant's T/λ/ρ row is bit-identical to a freshly rebuilt
      per-variant plan run solo (the loop the batch replaced — it also
      clocks the per-variant cost: one XLA compile per shape).
    """
    from repro import obs

    p = cluster_params(L_us=3.0, o_us=5.0)
    n_sc = 16 if smoke else STUDY_SCENARIOS
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 2, params=p, algo=a),
        list(STUDY_ALGOS), p)
    grid = sweep.latency_grid(p, np.linspace(0.0, 60.0, n_sc))

    plans = [sweep.compile_plan(v.graph, v.params) for v in variants]
    sb = sweep.StructureBatch.from_plans(
        plans, names=[v.name for v in variants])
    eng = sweep.Engine(sb, policy=sweep.ExecPolicy(cache=None))
    w = obs.CompileWatcher()
    with w.watch("structure.cold") as cold:
        t_cold, res = timeit(lambda: eng.run(grid), repeats=1, warmup=0)
    assert cold.new_programs == 1, \
        f"4-variant study built {cold.new_programs} XLA programs, want 1"
    assert res.axes == ("B", "S") and res.T.shape == (len(variants), n_sc)

    # a different study in the same envelope: reversed variant order →
    # zero new programs, same rows permuted (bit-exact per member)
    sb_rev = sweep.StructureBatch.from_plans(
        plans[::-1], names=[v.name for v in variants[::-1]])
    eng_rev = sweep.Engine(sb_rev, policy=sweep.ExecPolicy(cache=None))
    with w.watch("structure.warm") as warm:
        t_warm, res_rev = timeit(lambda: eng_rev.run(grid),
                                 repeats=1, warmup=0)
    assert warm.new_programs == 0, \
        "second study on the warmed envelope recompiled"
    assert np.array_equal(res_rev.T, res.T[::-1])

    # the loop the batch replaced: per-variant rebuilds, bit-equal rows
    t0 = time.perf_counter()
    for i, (v, plan) in enumerate(zip(variants, plans)):
        ref = sweep.Engine(plan, params=v.params,
                           policy=sweep.ExecPolicy(cache=None)).run(grid)
        assert np.array_equal(res.T[i], ref.T), v.name
        assert np.array_equal(res.lam[i], ref.lam), v.name
        assert np.array_equal(res.rho[i], ref.rho), v.name
    t_pv = time.perf_counter() - t0

    out(csv_line("sweep.structure_patch.study", t_cold * 1e6,
                 f"variants={len(variants)};scenarios={n_sc};"
                 f"xla_programs=1;bit_equal_rebuild=1"))
    out(csv_line("sweep.structure_patch.warm", t_warm * 1e6,
                 f"variants={len(variants)};new_xla_programs=0"))
    out(csv_line("sweep.structure_patch.pervariant", t_pv * 1e6,
                 f"compiles_per_shape=1;"
                 f"cold_speedup={t_pv / t_cold:.1f}x"))


def sparse_scale(out, smoke: bool = False):
    """Slot-list sparse backend: largest graph at fixed memory (the sparse
    half of the PR-7 tentpole).

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * the study graph's padded dense envelope is ≥4× its sparse slot-list
      footprint — at the memory where the dense layout hits
      ``Engine.MAX_DENSE_BYTES``, the sparse backend still holds a ≥4×
      larger graph;
    * sparse T/λ agree with the segment backend within 1e-5 relative
      (measured bit-exact — tests/test_conformance.py pins equality);
    * with the ceiling lowered under this graph's dense estimate, building
      a dense engine warns (RuntimeWarning) and auto-switches to sparse —
      the dense envelope is never allocated — and the switched engine's
      results match the explicit sparse run bit for bit.
    """
    import warnings

    p = cluster_params(L_us=3.0, o_us=5.0)
    g = (synth.random_dag(np.random.default_rng(7), nranks=16, nops=1200,
                          p_msg=0.6, params=p) if smoke
         else synth.stencil2d(8, 8, 30, params=p))
    est = sweep.estimate_dense_bytes(g)
    sp = sweep.compile_sparse(g, p)
    ratio = est / sp.sparse_bytes()
    assert ratio >= 4.0, \
        f"dense/sparse footprint ratio {ratio:.1f}x < 4x target"

    n_sc = 8 if smoke else 64
    grid = sweep.latency_grid(p, np.linspace(0.0, 40.0, n_sc))
    eng_sp = sweep.Engine(sp, params=p, policy=sweep.ExecPolicy(
        backend="sparse", cache=None))
    t_sp, res_sp = timeit(lambda: eng_sp.run(grid),
                          repeats=1 if smoke else 2, warmup=1)

    # segment reference — feasible dense at bench scale, so correctness
    # is checked on the SAME graph the sparse path evaluates
    eng_seg = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    t_seg, res_seg = timeit(lambda: eng_seg.run(grid),
                            repeats=1 if smoke else 2, warmup=1)
    rel = float(np.max(np.abs(res_sp.T - res_seg.T) /
                       np.maximum(np.abs(res_seg.T), 1.0)))
    assert rel <= 1e-5, f"sparse diverged from segment: {rel}"
    bit = int(np.array_equal(res_sp.T, res_seg.T) and
              np.array_equal(res_sp.lam, res_seg.lam))

    # auto-switch: lower the ceiling under this graph's dense estimate —
    # the engine must warn, switch to sparse, and never lay out dense
    orig = sweep.Engine.MAX_DENSE_BYTES
    try:
        sweep.Engine.MAX_DENSE_BYTES = max(est // 4, 1)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            t0 = time.perf_counter()
            eng_auto = sweep.Engine(g, params=p,
                                    policy=sweep.ExecPolicy(cache=None))
            t_auto = time.perf_counter() - t0
        assert any(issubclass(r.category, RuntimeWarning)
                   and "sparse" in str(r.message) for r in rec), \
            "auto-switch to sparse did not warn"
        assert eng_auto.policy.backend == "sparse" and eng_auto.plan is None
        res_auto = eng_auto.run(grid)
        assert np.array_equal(res_auto.T, res_sp.T)
    finally:
        sweep.Engine.MAX_DENSE_BYTES = orig

    out(csv_line(f"sweep.sparse_scale.{n_sc}", t_sp * 1e6,
                 f"nv={g.num_vertices};ne={g.num_edges};"
                 f"dense_bytes={est};sparse_bytes={sp.sparse_bytes()};"
                 f"graph_per_memory={ratio:.1f}x;"
                 f"rel_vs_segment={rel:.1e};bit_exact={bit}"))
    out(csv_line(f"sweep.sparse_scale.segment_ref.{n_sc}", t_seg * 1e6,
                 f"dense_bytes={est}"))
    out(csv_line("sweep.sparse_scale.auto_switch", t_auto * 1e6,
                 f"ceiling={max(est // 4, 1)};backend=sparse;"
                 f"bit_equal_sparse=1"))


def congestion(out, smoke: bool = False):
    """Congestion-aware effective gaps (the PR-8 tentpole): the iterated
    fixed point (evaluate → per-link load → inflate effective G →
    re-evaluate) as ONE jitted program, validated against the DES
    contention injector (``core/simulator.py``).

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * the fixed point converges in ≤5 iterations on the synth incast
      skeleton at the bench tolerance (``ExecPolicy(tol=1e-2)`` — 0.1%
      T drift vs a 1e-9 solve, measured);
    * the whole S-scenario congested sweep compiles exactly ONE new XLA
      program cold and ZERO warm (α/β/max_iters/tol are runtime inputs),
      reported by the production :class:`repro.obs.CompileWatcher`;
    * the zero-congestion path (α = 0) is bit-equal to the plain segment
      baseline and reports exactly one iteration per scenario.

    Reported for ``--json``: relative error of the congested vs the
    uncongested prediction against the contention-injector DES ground
    truth on the incast (the fixed point must shrink it).
    """
    from repro import obs
    from repro.core.graph import GraphBuilder
    from repro.core.loggps import pod_model
    from repro.core.simulator import simulate

    # 6-flow incast on one DCN link: the canonical skeleton where the
    # uncongested LogGPS bound is most wrong (all gap shares overlap)
    alpha = 0.25
    p = pod_model(pod_size=1, alpha={"dcn": alpha}).params()
    b = GraphBuilder(nclass=p.nclass, nranks=2)
    nflows = 6
    for _ in range(nflows):
        b.add_message(0, 1, nbytes=1e6, params=p)
    g = b.finalize()

    n_sc = 16 if smoke else STUDY_SCENARIOS
    grid = sweep.latency_grid(p, np.linspace(0.0, 60.0, n_sc))
    pol = sweep.ExecPolicy(congestion="fixed_point", tol=1e-2, cache=None)
    eng = sweep.Engine(g, params=p, policy=pol)
    w = obs.CompileWatcher()
    with w.watch("congestion.cold") as cold:
        t_cold, res = timeit(lambda: eng.run(grid), repeats=1, warmup=0)
    assert cold.new_programs == 1, \
        f"congested sweep built {cold.new_programs} XLA programs, want 1"
    iters = np.asarray(res.congestion_iters)
    assert iters.max() <= 5, \
        f"fixed point took {iters.max()} iterations on the incast, want ≤5"
    with w.watch("congestion.warm") as warm:
        t_warm, res2 = timeit(lambda: eng.run(grid), repeats=1, warmup=0)
    assert warm.new_programs == 0, "re-run on the warmed engine recompiled"
    assert np.array_equal(res2.T, res.T)

    # zero congestion (α=0 params): bit-equal to the plain segment
    # baseline, one iteration per scenario — the fixed point degrades to
    # a pure pass-through
    p0 = pod_model(pod_size=1).params()
    b0 = GraphBuilder(nclass=p0.nclass, nranks=2)
    for _ in range(nflows):
        b0.add_message(0, 1, nbytes=1e6, params=p0)
    g0 = b0.finalize()
    grid0 = sweep.latency_grid(p0, np.linspace(0.0, 60.0, n_sc))
    base = sweep.Engine(g0, params=p0,
                        policy=sweep.ExecPolicy(cache=None)).run(grid0)
    zero = sweep.Engine(
        g0, params=p0,
        policy=sweep.ExecPolicy(congestion="fixed_point", tol=1e-2,
                                cache=None)).run(grid0)
    assert np.array_equal(zero.T, base.T), "α=0 fixed point != baseline"
    assert np.array_equal(zero.lam, base.lam)
    assert np.all(np.asarray(zero.congestion_iters) == 1)

    # DES validation: per-link single-server contention replay is ground
    # truth; the fixed point must land closer to it than the uncongested
    # bound does (ΔL=0 column)
    t_sim = simulate(g, p, injector="contention").T
    t_base = float(base.T[0])
    t_cong = float(res.T[0])
    err_base = abs(t_base - t_sim) / t_sim
    err_cong = abs(t_cong - t_sim) / t_sim
    assert err_cong < err_base, \
        f"congestion did not improve on DES: {err_cong:.3f} vs {err_base:.3f}"

    out(csv_line(f"sweep.congestion.fixed_point.{n_sc}", t_cold * 1e6,
                 f"flows={nflows};alpha={alpha};tol=1e-2;"
                 f"iters_max={int(iters.max())};xla_programs=1"))
    out(csv_line(f"sweep.congestion.warm.{n_sc}", t_warm * 1e6,
                 "new_xla_programs=0;bit_equal=1"))
    out(csv_line("sweep.congestion.zero_alpha", 0.0,
                 "bit_equal_baseline=1;iters=1"))
    out(csv_line("sweep.congestion.des_validation", t_sim,
                 f"T_sim={t_sim:.1f};T_base={t_base:.1f};"
                 f"T_congested={t_cong:.1f};"
                 f"rel_err_base={err_base:.3f};"
                 f"rel_err_congested={err_cong:.3f}"))


def resilience(out, smoke: bool = False):
    """Resilience scenario family (the PR-9 tentpole): a fault
    distribution — stragglers (K axis), degraded/flapping links (S axis),
    failed devices with checkpoint-restart recovery (B axis + K) — as ONE
    batched ``sensitivity.resilience_curve`` query.

    Asserted in BOTH modes (the ``--smoke`` CI gate):

    * the whole ≥3-fault-family grid (4 stragglers × 50 link scenarios ×
      2 device faults — a B×K×S cube of >1000 cells) compiles exactly ONE
      new XLA program cold and ZERO warm, reported by the production
      :class:`repro.obs.CompileWatcher`;
    * the zero-fault cell (0, 0, 0) is bit-identical to the plain scalar
      forward (``dag.evaluate``);
    * straggler predictions match the DES fault injector
      (``simulate(injector="fault")``) — the relative error is asserted
      ≤5% and reported for ``--json``.
    """
    from repro import obs
    from repro.core import sensitivity
    from repro.core.graph import CALC
    from repro.core.loggps import pod_model
    from repro.core.simulator import simulate

    p = pod_model(pod_size=4).params()
    g = (synth.stencil2d(3, 3, 3, params=p) if smoke
         else synth.stencil2d(4, 4, 10, params=p))
    nv = g.num_vertices
    indeg = np.bincount(g.edst, minlength=nv)

    # 4 stragglers on compute vertices that have in-edges (expressible as
    # patch_costs rows), spread across the graph
    calc = np.nonzero((g.kind == CALC) & (indeg > 0) & (g.vcost > 0))[0]
    picks = calc[:: max(1, len(calc) // 4)][:4]
    stragglers = [sweep.StragglerFault(vertices=(int(v),), slowdown=s,
                                       name=f"strag[v{int(v)}]x{s}")
                  for v, s in zip(picks, (1.5, 2.0, 3.0, 4.0))]
    # 50 link-degradation scenarios: ΔL severity sweep × both classes
    links = [sweep.LinkFault(cls=c, extra_L_us=float(dl), gscale=1.5,
                             duty=duty, name=f"{c}+{dl:.0f}us@{duty}")
             for c in ("ici", "dcn")
             for dl in np.linspace(5.0, 120.0, 5 if smoke else 25)
             for duty in ((1.0, 0.5) if not smoke else (1.0, 0.5, 0.25,
                                                        0.75, 0.1))]
    # 2 failed devices, recovery cost from checkpoint-restart accounting
    # (one "step" = one pass over this graph; restore = half a step)
    T_plain = dag.evaluate(g, p).T
    rec_us = sweep.recovery_cost_us(step_us=T_plain,
                                    restore_us=0.5 * T_plain, ckpt_every=4)
    devices = [sweep.DeviceFault(rank=r, recovery_us=rec_us,
                                 name=f"dev{r}-down")
               for r in (1, g.nranks - 1)]
    faults = stragglers + links + devices

    pol = sweep.ExecPolicy(cache=None)
    w = obs.CompileWatcher()
    with w.watch("resilience.cold") as cold:
        t_cold, rep = timeit(lambda: sensitivity.resilience_curve(
            g, p, faults, policy=pol), repeats=1, warmup=0)
    assert cold.new_programs == 1, \
        f"resilience fault grid built {cold.new_programs} XLA programs, want 1"
    B, K, S = rep.result.T.shape
    assert rep.result.axes == ("B", "K", "S") and S >= 51

    with w.watch("resilience.warm") as warm:
        t_warm, rep2 = timeit(lambda: sensitivity.resilience_curve(
            g, p, faults, policy=pol), repeats=1, warmup=0)
    assert warm.new_programs == 0, "re-run of the fault grid recompiled"
    assert np.array_equal(rep2.T_fault, rep.T_fault)

    # zero-fault cell: bit-identical to the plain scalar forward
    assert rep.T0 == T_plain, \
        f"zero-fault cell {rep.T0} != plain forward {T_plain}"

    # DES cross-validation: the straggler rows against the fault injector
    errs = []
    for f, T_pred in zip(stragglers, rep.T_fault[:len(stragglers)]):
        des = simulate(g, p, injector="fault",
                       fault={"slowdown": {f.vertices[0]: f.slowdown}}).T
        errs.append(abs(T_pred - des) / des)
    err_max = float(max(errs))
    assert err_max <= 0.05, \
        f"straggler prediction diverged from DES: rel err {err_max:.3f}"

    out(csv_line(f"sweep.resilience.fault_grid.{B}x{K}x{S}", t_cold * 1e6,
                 f"faults={len(faults)};families=3;cells={B * K * S};"
                 f"xla_programs=1;E_slowdown={rep.expected_slowdown:.4f};"
                 f"p99={rep.quantiles['p99']:.4f}"))
    out(csv_line("sweep.resilience.warm", t_warm * 1e6,
                 "new_xla_programs=0;bit_equal=1"))
    out(csv_line("sweep.resilience.zero_fault", 0.0,
                 "bit_equal_plain_forward=1"))
    out(csv_line("sweep.resilience.des_validation", err_max,
                 f"stragglers={len(stragglers)};"
                 f"rel_err_max={err_max:.2e}"))


def _shard_smoke(S: int) -> None:
    """Multi-graph sweeps sharded on the MultiPlan graph axis and
    single-graph sweeps sharded on the scenario axis must be bit-equal to
    single-device runs, on both backends."""
    p = cluster_params(L_us=3.0, o_us=5.0)
    variants = sweep.collective_variants(
        lambda a: synth.allreduce_chain(8, 1, params=p, algo=a),
        ["ring", "recursive_doubling"], p)
    grid = sweep.latency_grid(p, np.linspace(0.0, 40.0, S))
    pol = sweep.ExecPolicy(cache=None)
    meng = sweep.Engine([(v.graph, v.params) for v in variants],
                        names=[v.name for v in variants], policy=pol)
    base = meng.run(grid)
    sh = meng.run(grid, shard=True)
    assert np.array_equal(base.T, sh.T), "sharded T diverged"
    assert np.array_equal(base.lam, sh.lam), "sharded lam diverged"
    eng = sweep.Engine(synth.stencil2d(3, 3, 3, params=p), params=p,
                       policy=pol)
    for backend in ("segment", "pallas"):
        b1 = eng.run(grid, backend=backend)
        s1 = eng.run(grid, backend=backend, shard=True)
        assert np.array_equal(b1.T, s1.T), backend
        assert np.array_equal(b1.lam, s1.lam), backend


def sharded(out, n_scenarios=16, ndev=2):
    """shard_map smoke on ``ndev`` devices.  With that many real devices
    (a TPU host) it runs in this process, on the chips this process
    already holds.  Otherwise a child process gets a forced ``ndev``-device
    CPU mesh (``JAX_PLATFORMS=cpu`` — it never needs an accelerator, so a
    parent holding the chip cannot block it; the XLA flag must be set
    before JAX initializes, hence the child)."""
    import os
    import pathlib
    import subprocess
    import sys

    import jax

    t0 = time.perf_counter()
    if len(jax.devices()) >= ndev:
        _shard_smoke(n_scenarios)
        where = f"{jax.devices()[0].platform}"
    else:
        root = pathlib.Path(__file__).resolve().parents[1]
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   [str(root / "src"), str(root),
                    os.environ.get("PYTHONPATH", "")]),
               "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                             f" --xla_force_host_platform_device_count={ndev}")}
        res = subprocess.run(
            [sys.executable, "-c",
             "from benchmarks.bench_sweep import _shard_smoke; "
             f"_shard_smoke({int(n_scenarios)}); print('OK')"],
            capture_output=True, text=True, env=env)
        assert res.returncode == 0 and res.stdout.strip() == "OK", res.stderr
        where = "forced-cpu"
    out(csv_line(f"sweep.sharded.{ndev}dev", (time.perf_counter() - t0) * 1e6,
                 f"scenarios={n_scenarios};devices={where};bit_equal=1"))


def run(out, smoke: bool = False):
    if smoke:
        single_graph(out, n_scenarios=64)
        pallas_backend(out, n_scenarios=16)
        lam_compile(out, n_scenarios=32)
        sharded(out, n_scenarios=16)
        placement_patch(out, smoke=True)
        unified_axes(out, smoke=True)
        structure_patch(out, smoke=True)
        sparse_scale(out, smoke=True)
        congestion(out, smoke=True)
        resilience(out, smoke=True)
        return
    single_graph(out)
    pallas_backend(out)
    lam_compile(out)
    sharded(out, n_scenarios=64)
    placement_patch(out)
    unified_axes(out)
    structure_patch(out)
    sparse_scale(out)
    congestion(out)
    resilience(out)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="sweep-engine benchmarks (single-graph grid + packed "
                    "variant study + zero-recompile placement search)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids, correctness asserts only (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the records as JSON (uploaded as a "
                         "CI workflow artifact)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record repro.obs spans for the whole run and "
                         "write a Chrome-trace/Perfetto JSON (open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the repro.obs metrics registry snapshot "
                         "(cache hit rates, compile counts, envelope "
                         "occupancy) as JSON after the run")
    args = ap.parse_args(argv)
    records: list = []

    def out(line):
        print(line)
        records.append(line)

    from repro import obs
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.trace:
        obs.enable()
    print("name,us_per_call,derived")
    run(out, smoke=args.smoke)
    if args.trace:
        obs.TRACER.export(args.trace)
        print(f"[bench_sweep] wrote {len(obs.TRACER.events())} spans "
              f"to {args.trace}")
    if args.metrics_json:
        import json as _json
        with open(args.metrics_json, "w") as f:
            _json.dump(obs.metrics.snapshot(), f, indent=2)
        print(f"[bench_sweep] wrote metrics snapshot to {args.metrics_json}")
    if args.json:
        import json
        import platform
        parsed = []
        for line in records:
            name, us, derived = line.split(",", 2)
            parsed.append({"name": name, "us_per_call": float(us),
                           "derived": derived})
        with open(args.json, "w") as f:
            json.dump({"bench": "sweep", "smoke": bool(args.smoke),
                       "python": platform.python_version(),
                       "records": parsed}, f, indent=2)
        print(f"[bench_sweep] wrote {len(parsed)} records to {args.json}")


if __name__ == "__main__":
    main()
