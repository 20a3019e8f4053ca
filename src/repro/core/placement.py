"""Rank placement via LP sensitivity matrices (paper Appendix I/J, Alg. 3).

Heterogeneous LogGP: L and G become P×P matrices (here: generated from an
architecture topology Φ — e.g. intra-pod ICI vs cross-pod DCN).  Each LP
solve yields pairwise sensitivity matrices D_L (critical-path message counts
per rank pair) and D_G (bytes); Algorithm 3 greedily swaps the rank pair
with the best predicted gain, re-solves, and stops when the objective stops
improving — exactly the paper's loop, with our DAG engine playing Gurobi.

Two implementations of the greedy loop:

``place(engine="scalar")`` — the reference loop: one scalar forward per
step, per-pair Python ``swap_gain`` scoring (O(P³) per step).

``place(engine="auto")`` (default) — the batched loop: pairwise counts are
aggregated over a *scenario grid* (robust placement — a mapping that only
wins at the build-time latency point can lose under the sweep the operator
actually cares about), all P² candidate swaps are scored at once from the
vectorized gain matrix (:func:`swap_gain_matrix`), and the top-k candidate
mappings are evaluated exactly in ONE compiled engine call per greedy
step instead of scalar re-solves.  Candidate evaluation is
**zero-recompile** by default (``cost_eval="patch"``): the graph compiles
ONCE and each candidate mapping's Φ link costs patch into the warm plan's
cost block as a runtime input
(:meth:`~repro.sweep.compile.CompiledPlan.patch_costs` +
``SweepEngine.run(costs=...)``) — bit-identical objectives, and therefore
the same final mapping, as ``cost_eval="rebuild"`` (K fresh CompiledPlans
packed into a MultiPlan per step, the previous formulation, kept as the
reference).  With the default single-point grid and ``topk=1`` it
reproduces the reference loop's final mapping exactly (asserted in tests).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np

from . import dag
from .graph import ExecutionGraph
from .loggps import LogGPS


@dataclasses.dataclass
class ArchTopology:
    """Φ: physical pairwise latency/bandwidth between processor slots."""

    L: np.ndarray   # (P, P) µs
    G: np.ndarray   # (P, P) µs/byte

    @staticmethod
    def two_tier(P: int, pod: int, L_fast: float = 1.0, L_slow: float = 10.0,
                 G_fast: float = 2e-5, G_slow: float = 4e-5) -> "ArchTopology":
        idx = np.arange(P)
        same = (idx[:, None] // pod) == (idx[None, :] // pod)
        L = np.where(same, L_fast, L_slow)
        G = np.where(same, G_fast, G_slow)
        np.fill_diagonal(L, 0.0)
        np.fill_diagonal(G, 0.0)
        return ArchTopology(L=L, G=G)


def evaluate_mapping(g: ExecutionGraph, params: LogGPS, phi: ArchTopology,
                     pi: np.ndarray, plan: Optional[dag.LevelPlan] = None):
    """Objective value (predicted runtime) for a process mapping π.

    π[i] = physical slot of rank i.  We re-cost message edges with the
    pairwise L/G of the mapped slots (extra_edge_cost keeps the graph
    immutable — one array per evaluation, the analog of re-assigning
    variable lower bounds in the paper's LP).
    """
    plan = plan or dag.LevelPlan(g)
    # build graphs for placement with L=(0,), G=(0,) so the built-in cost
    # is 0 and the mapped Φ cost is the whole network cost
    sched = plan.forward(params,
                         extra_edge_cost=mapping_edge_cost(plan.g, phi, pi))
    return sched, plan


def sensitivity_matrices(g: ExecutionGraph, sched, plan: dag.LevelPlan):
    """D_L, D_G from the critical path (Appendix I reduced costs)."""
    return plan.pairwise_counts(sched)


def mapping_edge_cost(g: ExecutionGraph, phi: ArchTopology,
                      pi: np.ndarray) -> np.ndarray:
    """Per-edge Φ link cost of mapping π, in *original* edge order.

    The batched analog of ``evaluate_mapping``'s extra array — fed to
    ``dag.LevelPlan.forward(extra_edge_cost=)`` or
    ``sweep.compile_plan(extra_edge_cost=)`` interchangeably.
    """
    is_msg = g.ebytes > 0
    ps, pd = pi[g.vrank[g.esrc]], pi[g.vrank[g.edst]]
    return np.where(is_msg,
                    phi.L[ps, pd] + phi.G[ps, pd] * np.maximum(g.ebytes - 1, 0),
                    0.0)


def swap_gain_matrix(D_L: np.ndarray, D_G: np.ndarray, pi: np.ndarray,
                     phi: ArchTopology) -> np.ndarray:
    """All-pairs first-order swap gains in one shot (vectorized Alg. 3 l.15).

    gain[i, j] = Σ_{k≠i,j} (A_ik − A_jk)(D_L,ik − D_L,jk)
                          + (B_ik − B_jk)(D_G,ik − D_G,jk)

    with A/B the mapped pairwise L/G — algebraically identical to summing
    :func:`swap_gain`'s old−new terms over both swap directions.  O(P³)
    memory/work as dense numpy (placement instances are small; the scalar
    loop was O(P³) *Python*).
    """
    A = phi.L[np.ix_(pi, pi)]
    B = phi.G[np.ix_(pi, pi)]
    dA = A[:, None, :] - A[None, :, :]          # [P, P, P] over (i, j, k)
    dL = D_L[:, None, :] - D_L[None, :, :]
    dB = B[:, None, :] - B[None, :, :]
    dG = D_G[:, None, :] - D_G[None, :, :]
    terms = dA * dL + dB * dG
    P = pi.shape[0]
    idx = np.arange(P)
    terms[idx, :, idx] = 0.0                    # k == i
    terms[:, idx, idx] = 0.0                    # k == j
    return terms.sum(axis=2)


def swap_gain(i: int, j: int, D_L: np.ndarray, D_G: np.ndarray,
              pi: np.ndarray, phi: ArchTopology) -> float:
    """Predicted runtime reduction from swapping ranks i and j (Alg. 3 l.15).

    First-order estimate: messages between (i,k) will traverse
    (π[j],π[k]) links after the swap; gain = Σ_k D[i,k]·(L_old − L_new) + …
    """
    P = D_L.shape[0]
    gain = 0.0
    for k in range(P):
        if k == i or k == j:
            continue
        for (a, b) in ((i, j), (j, i)):
            dl = D_L[a, k]
            db = D_G[a, k]
            if dl or db:
                old = phi.L[pi[a], pi[k]] * dl + phi.G[pi[a], pi[k]] * db
                new = phi.L[pi[b], pi[k]] * dl + phi.G[pi[b], pi[k]] * db
                gain += old - new
    return gain


def _select_swap(gains: np.ndarray) -> tuple:
    """The reference loop's pair selection: scan i<j in lexicographic order,
    keep the pair that beats the running best by >1e-12 (so fp-noise ties
    resolve identically to the scalar implementation)."""
    P = gains.shape[0]
    best, bi, bj = 0.0, -1, -1
    for i in range(P):
        for j in range(i + 1, P):
            gv = gains[i, j]
            if gv > best + 1e-12:
                best, bi, bj = gv, i, j
    return best, bi, bj


def _place_scalar(g, phi, params, pi0, max_iters, verbose):
    """Reference Algorithm 3 (the seed implementation, kept verbatim)."""
    P = g.nranks
    pi = np.arange(P) if pi0 is None else pi0.copy()
    plan = dag.LevelPlan(g)

    sched, plan = evaluate_mapping(g, params, phi, pi, plan)
    f_star = sched.T
    history = [f_star]
    prev_pi = pi.copy()

    for _ in range(max_iters):
        D_L, D_G = plan.pairwise_counts(sched)
        best, bi, bj = 0.0, -1, -1
        for i in range(P):
            for j in range(i + 1, P):
                gv = swap_gain(i, j, D_L, D_G, pi, phi)
                if gv > best + 1e-12:
                    best, bi, bj = gv, i, j
        if bi < 0:
            break  # no positive-gain swap (termination cond. 1)
        prev_pi = pi.copy()
        pi[bi], pi[bj] = pi[bj], pi[bi]
        sched, plan = evaluate_mapping(g, params, phi, pi, plan)
        f = sched.T
        if verbose:
            print(f"swap ({bi},{bj}) predicted_gain={best:.2f} T={f:.2f}")
        if f >= f_star - 1e-9:
            pi = prev_pi  # revert (termination cond. 2)
            sched, plan = evaluate_mapping(g, params, phi, pi, plan)
            break
        f_star = f
        history.append(f)
    return pi, history


def _candidate_objectives(g, scen_batch, extras, backend):
    """Rebuild-loop candidate evaluation (the pre-patching formulation,
    kept as the equivalence reference and bench baseline): each candidate's
    Φ costs bake into a fresh CompiledPlan and the K plans pack onto the
    unified engine's graph axis (identical structure ⇒ identical shape
    bucket, so the XLA program is reused — the per-step cost is the K
    numpy recompiles, the re-pack, and the device restage)."""
    from repro.sweep import compile_plan
    from repro.sweep.api import Engine, ExecPolicy

    plans = [compile_plan(g, extra_edge_cost=ex) for ex in extras]
    eng = Engine(plans, policy=ExecPolicy(backend=backend, cache=None))
    res = eng.run(scen_batch, compute_lam=False)
    return res.T.mean(axis=1)                  # [K] mean over the grid


def _place_batched(g, phi, params, pi0, max_iters, verbose, scenario_points,
                   topk, engine="auto", backend="segment",
                   cost_eval="patch", cache=None, stats=None, policy=None):
    """Batched Algorithm 3: grid-aggregated D matrices, vectorized gains,
    one engine call per greedy step for exact candidate evaluation.

    ``cost_eval="patch"`` (default) compiles ONE plan up front and issues a
    ``Query(costs=swap_candidates)`` against the warm unified engine per
    greedy step (every candidate's Φ costs patch into the plan's cost
    block as a runtime input) — zero plan recompiles after the first step,
    bit-identical objectives (and therefore final mapping) to
    ``cost_eval="rebuild"``, which recompiles K plans per step (the PR-2
    formulation, kept as the reference).  ``stats`` (a dict, if given) is
    filled with the loop's cost accounting.
    """
    from repro.sweep import ScenarioBatch, compile_plan
    from repro.sweep.api import Engine, ExecPolicy, Query

    P = g.nranks
    pi = np.arange(P) if pi0 is None else pi0.copy()
    plan = dag.LevelPlan(g)
    pts = list(scenario_points) if scenario_points else [params]
    nc = g.nclass
    scen_batch = ScenarioBatch(
        L=np.asarray([pt.L for pt in pts], dtype=np.float64),
        gscale=np.ones((len(pts), nc)))
    st = stats if stats is not None else {}
    st.update({"cost_eval": cost_eval, "steps": 0, "plan_compiles": 0,
               "engine_calls": 0, "candidates": 0, "scalar_fallbacks": 0})

    # engine='auto' evaluates candidates with the exact scalar forward
    # only when JAX is not installed; any other failure raises
    from .sensitivity import jax_missing
    eng, use_jax = None, True
    if cost_eval == "patch":
        st["plan_compiles"] += 1
        eng = Engine(compile_plan(g),
                     policy=(policy if policy is not None else
                             ExecPolicy(backend=backend, cache=cache)))

    def device_objectives(extras):
        if eng is None:
            fs = _candidate_objectives(g, scen_batch, extras, backend)
            st["plan_compiles"] += len(extras)
        else:
            # zero-recompile path: K candidate cost blocks through the
            # once-compiled plan (structure unbatched inside the vmap; raw
            # extras → the engine patches only its backend's view)
            fs = eng.run(Query(scenarios=scen_batch, costs=np.stack(extras),
                               outputs=("T",))).T.mean(axis=1)
        st["engine_calls"] += 1
        return fs

    def forwards(pi_):
        ex = mapping_edge_cost(g, phi, pi_)
        return [plan.forward(pt, extra_edge_cost=ex) for pt in pts]

    scheds = forwards(pi)
    f_star = float(np.mean([s.T for s in scheds]))
    history = [f_star]

    for _ in range(max_iters):
        D_L = np.zeros((P, P))
        D_G = np.zeros((P, P))
        for s in scheds:                       # grid-aggregated sensitivities
            dl, dgm = plan.pairwise_counts(s)
            D_L += dl
            D_G += dgm
        D_L /= len(scheds)
        D_G /= len(scheds)
        gains = swap_gain_matrix(D_L, D_G, pi, phi)
        best, bi, bj = _select_swap(gains)
        if bi < 0:
            break  # no positive-gain swap (termination cond. 1)
        # top-k predicted swaps, best-first (k=1 ≡ the reference loop)
        iu, ju = np.triu_indices(P, k=1)
        order = np.argsort(-gains[iu, ju], kind="stable")
        cand = [(bi, bj)]
        for o in order[:max(int(topk), 1)]:
            pair = (int(iu[o]), int(ju[o]))
            if pair != (bi, bj) and len(cand) < max(int(topk), 1):
                cand.append(pair)
        extras = []
        for (ci, cj) in cand:
            pc = pi.copy()
            pc[ci], pc[cj] = pc[cj], pc[ci]
            extras.append(mapping_edge_cost(g, phi, pc))
        st["candidates"] += len(cand)
        fs = None
        if use_jax:
            try:
                fs = device_objectives(extras)
            except ImportError as e:
                if engine == "sweep" or not jax_missing(e):
                    raise
                use_jax = False
        if fs is None:
            fs = np.asarray([np.mean([plan.forward(pt, extra_edge_cost=ex).T
                                      for pt in pts]) for ex in extras])
            st["scalar_fallbacks"] += 1
        k = int(np.argmin(fs))
        f = float(fs[k])
        if verbose:
            print(f"swap {cand[k]} predicted_gain={best:.2f} T={f:.2f} "
                  f"(evaluated {len(cand)} candidates)")
        if f >= f_star - 1e-9:
            break  # best candidate doesn't improve (termination cond. 2)
        ci, cj = cand[k]
        pi[ci], pi[cj] = pi[cj], pi[ci]
        scheds = forwards(pi)
        f_star = f
        history.append(f)
        st["steps"] += 1
    return pi, history


def place(g: ExecutionGraph, phi: ArchTopology, params: Optional[LogGPS] = None,
          pi0: Optional[np.ndarray] = None, max_iters: int = 64,
          verbose: bool = False, engine: str = "auto",
          scenarios: Optional[Sequence[LogGPS]] = None,
          topk: int = 1, backend: str = "segment",
          cost_eval: str = "patch", cache=None,
          stats: Optional[dict] = None,
          policy=None) -> tuple[np.ndarray, list]:
    """Algorithm 3. Returns (mapping, history of objective values).

    The graph should be built with zero link costs (L=(0,), G=(0,)) so that
    all network cost comes from Φ via the mapping.

    ``engine="auto"`` (default) runs the batched loop: swap gains for all
    P² pairs come from one vectorized gain matrix, candidate mappings are
    verified in one engine call per greedy step, and ``scenarios`` (a
    sequence of LogGPS points, e.g. ``latency_points(params, deltas)``)
    aggregates the sensitivity matrices over a grid instead of the single
    build-time point.  Defaults (single point, ``topk=1``) reproduce the
    reference loop exactly; ``engine="scalar"`` forces the reference loop.

    ``cost_eval="patch"`` (default) is the zero-recompile path: the graph
    compiles ONCE and every candidate mapping's Φ costs patch into the
    warm plan as a runtime input (``SweepEngine.run(costs=...)``);
    ``cost_eval="rebuild"`` recompiles K plans per step (the equivalence
    reference — same objectives bit for bit, so the same final mapping).
    ``backend`` picks the compiled evaluator, ``cache`` (a ``SweepCache``)
    memoizes candidate evaluations across repeated queries, and ``stats``
    (a dict) receives the loop's cost accounting — plan_compiles,
    engine_calls, candidates, steps.

    ``policy`` (a :class:`repro.sweep.api.ExecPolicy`) supersedes the
    loose ``backend``/``cache`` kwargs when given — the greedy loop's
    candidate queries then execute under it wholesale (backend, device
    sharding over the candidate axis, cache).
    """
    if engine not in ("auto", "scalar", "sweep"):
        raise ValueError(f"engine must be 'auto', 'scalar' or 'sweep', "
                         f"got {engine!r}")
    if cost_eval not in ("patch", "rebuild"):
        raise ValueError(f"cost_eval must be 'patch' or 'rebuild', "
                         f"got {cost_eval!r}")
    if policy is not None:
        backend = policy.backend
        cache = policy.cache
    if backend not in ("segment", "pallas"):
        raise ValueError(f"backend must be 'segment' or 'pallas', "
                         f"got {backend!r}")
    params = params or LogGPS(L=(0.0,), G=(0.0,), o=0.5, S=1e18)
    if engine == "scalar":
        if scenarios is not None or topk != 1:
            raise ValueError("scenario grids / topk need the batched engine")
        return _place_scalar(g, phi, params, pi0, max_iters, verbose)
    return _place_batched(g, phi, params, pi0, max_iters, verbose,
                          scenarios, topk, engine=engine, backend=backend,
                          cost_eval=cost_eval, cache=cache, stats=stats,
                          policy=policy)


def latency_points(params: LogGPS, deltas: Sequence[float],
                   cls: int = 0) -> list:
    """ΔL grid as LogGPS points — the ``scenarios=`` axis of :func:`place`."""
    return [params.with_delta(float(d), cls) for d in deltas]


def block_mapping(P: int) -> np.ndarray:
    """Default scheme the paper compares against (ranks in order)."""
    return np.arange(P)


def random_mapping(P: int, rng) -> np.ndarray:
    """A uniformly random rank→slot permutation from an EXPLICIT stream.

    ``rng`` is an int seed or ``numpy.random.Generator``
    (:func:`repro.core.rng.as_rng`; ``None`` raises) — the "placement
    seed" knob of a design space lowers through here, and search
    trajectories must be bit-reproducible from their seed alone, so the
    global ``np.random`` state is never consulted.
    """
    from .rng import as_rng
    return as_rng(rng).permutation(int(P))


def volume_greedy_mapping(g: ExecutionGraph, phi: ArchTopology) -> np.ndarray:
    """Scotch-like baseline: group heavy-traffic rank pairs onto fast links,
    using *total* traffic volume (ignores temporal structure — the paper's
    point is that this can mis-rank placements)."""
    P = g.nranks
    vol = np.zeros((P, P))
    msg = g.ebytes > 0
    np.add.at(vol, (g.vrank[g.esrc[msg]], g.vrank[g.edst[msg]]), g.ebytes[msg])
    vol = vol + vol.T
    # greedy: order pairs by volume, pack into pods
    pod = int(np.sqrt(P)) if phi.L.shape[0] == P else P
    # find pod size from phi: count of fast links per row
    fast = (phi.L[0] <= phi.L[0].min() + 1e-12).sum()
    pod = max(int(fast), 1)
    order = np.argsort(-vol.sum(axis=1))
    pi = np.empty(P, dtype=int)
    pi[order] = np.arange(P)
    return pi
