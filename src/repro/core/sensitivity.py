"""High-level sensitivity / tolerance API (paper §II-B, §II-D, Figs 1 & 9).

Wraps the DAG engine (default, exact & fast) and the explicit-LP solvers
(HiGHS / our IPM — the paper-faithful path) behind one interface:

    report = analyze(graph, params)           # T, λ_L, ρ_L at the base point
    curve  = latency_curve(graph, params, deltas)   # Fig 9 top panels
    tol    = latency_tolerance(graph, params, 0.01) # Fig 1 green zone
    lcs    = critical_latencies(graph, params, lo, hi)  # Algorithm 2

Multi-point queries dispatch to the batched scenario-sweep engine
(``repro.sweep``: one jit+vmap max-plus pass over the whole grid) whenever
it pays off — ≥ :data:`SWEEP_MIN_POINTS` curve points, ≥
:data:`SWEEP_MIN_DEGRADATIONS` tolerance levels, or large graphs for the
breakpoint search.  ``engine="scalar"`` forces the numpy path,
``engine="sweep"`` forces the batched path; the default ``"auto"`` takes
the scalar path only when JAX is not installed (an expected install
state).  Any other failure of the batched path raises under every
engine setting: a broken device path must never be served, and timed, as
the slow-but-correct scalar loop.

How the batched path executes is one object, not loose kwargs: pass
``policy=`` (a :class:`repro.sweep.api.ExecPolicy`) to pick the backend,
device sharding, λ mode (``lam="fd"`` finite-difference sensitivities at
values-program compile cost) and result cache — the same policy object the
sweep engine and the analysis service take.  Without a policy the memoized
default engine is used (one compiled engine per (graph, params) content).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from . import dag
from .graph import ExecutionGraph
from .loggps import LogGPS, resolve_class


@dataclasses.dataclass
class SensitivityReport:
    T: float                     # predicted runtime (µs)
    lam: np.ndarray              # λ per latency class (messages on critical path)
    rho: np.ndarray              # ρ per class (latency share of critical path)
    params: LogGPS

    def __str__(self):
        rows = [f"T = {self.T:.3f} µs"]
        for c, name in enumerate(self.params.class_names):
            rows.append(f"  λ_L[{name}] = {self.lam[c]:.1f}   "
                        f"ρ_L[{name}] = {100 * self.rho[c]:.2f}%")
        return "\n".join(rows)


def analyze(g: ExecutionGraph, params: LogGPS,
            plan: Optional[dag.LevelPlan] = None) -> SensitivityReport:
    s = dag.evaluate(g, params, plan=plan)
    return SensitivityReport(T=s.T, lam=s.lam.copy(), rho=s.rho(), params=params)


@dataclasses.dataclass
class LatencyCurve:
    deltas: np.ndarray
    T: np.ndarray
    lam: np.ndarray
    rho: np.ndarray

    def rrmse_vs(self, measured: np.ndarray) -> float:
        """Relative RMSE (paper Fig 9 / Table II metric)."""
        m = np.asarray(measured, dtype=np.float64)
        return float(np.sqrt(np.mean((self.T - m) ** 2)) / np.mean(m))


#: dispatch thresholds for the batched sweep engine (repro.sweep)
SWEEP_MIN_POINTS = 8
SWEEP_MIN_DEGRADATIONS = 4
SWEEP_MIN_EDGES_BREAKPOINTS = 20_000


def _check_engine_arg(engine: str) -> None:
    if engine not in ("auto", "scalar", "sweep"):
        raise ValueError(f"engine must be 'auto', 'scalar' or 'sweep', "
                         f"got {engine!r}")


def jax_missing(err: BaseException) -> bool:
    """True only for "JAX is not installed" — the one state in which
    ``engine="auto"`` takes the scalar path.  Any other ``ImportError``
    (an API that moved inside an installed JAX, a kernel that fails to
    import) is a broken device path and must surface."""
    return isinstance(err, ModuleNotFoundError) and err.name == "jax"


def _scalar_ok(err: BaseException, engine: str, policy) -> bool:
    """Whether a batched-path failure may be served by the scalar loop:
    only under plain ``engine="auto"`` (no explicit policy, which pins a
    backend/λ-mode contract the scalar loop cannot honor) and only when
    JAX is absent."""
    return engine == "auto" and policy is None and jax_missing(err)


def _params_memo_key(g: ExecutionGraph, params: LogGPS) -> tuple:
    """Content-addressed memo key for a (graph, params) compiled engine.

    ``rank_of_class`` is an opaque callable, so it is keyed by what it
    *computes* — the evaluated rank→rank class matrix over the graph's
    ranks (canonical bytes, as in ``sweep.cache``) — never by ``id()``:
    after GC, CPython reuses ids, so an id key can alias a *different*
    mapping to a stale compiled engine, and logically-equal params built
    twice would never share one.
    """
    if params.rank_of_class is None:
        cls_key = None
    else:
        # evaluating P² rank pairs is not free — cache the evaluated
        # matrix bytes on the params instance (its callable is fixed, so
        # per-instance caching is content-correct; an equal params built
        # elsewhere recomputes once and lands on the same key)
        P = int(g.nranks)
        cache = getattr(params, "_class_matrix_bytes", None)
        if cache is None:
            cache = {}
            object.__setattr__(params, "_class_matrix_bytes", cache)
        cls_key = cache.get(P)
        if cls_key is None:
            from repro.sweep.cache import canonical_bytes
            m = np.asarray([[params.link_class(i, j) for j in range(P)]
                            for i in range(P)], dtype=np.int32)
            cls_key = cache[P] = b"".join(canonical_bytes(m))
    # α/β are runtime congestion inputs, but the compiled engine snapshots
    # its params object — two registries differing only in congestion
    # coefficients must not alias one memoized engine
    return (tuple(params.L), tuple(params.G), params.o, params.g, params.S,
            tuple(params.alpha_full), tuple(params.beta_full), cls_key)


def _sweep_engine(g: ExecutionGraph, params: LogGPS, policy=None):
    """Build (or reuse) a batched engine.

    Compiled engines are memoized on the graph object per parameter set
    (content-keyed, see :func:`_params_memo_key`) and per execution
    policy, so repeated sensitivity calls on one graph pay compile_plan
    once.  With ``policy=None`` the engine is the legacy ``SweepEngine``
    shim (its DeprecationWarning suppressed — this module's own surface is
    the ``engine=``/``policy=`` kwargs, not the shim); an explicit
    :class:`repro.sweep.api.ExecPolicy` builds the unified
    :class:`repro.sweep.api.Engine` directly.
    """
    from repro.sweep import SweepEngine
    memo = getattr(g, "_sweep_engines", None)
    if memo is None:
        memo = {}
        object.__setattr__(g, "_sweep_engines", memo)
    key = _params_memo_key(g, params) \
        + (None if policy is None else policy.key(),)
    eng = memo.get(key)
    if eng is None:
        if policy is None:
            import warnings
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                eng = SweepEngine(g, params)
        else:
            from repro.sweep.api import Engine
            eng = Engine(g, params=params, policy=policy)
        memo[key] = eng
    return eng


def latency_curve(g: ExecutionGraph, params: LogGPS, deltas: Sequence[float],
                  cls=0, plan: Optional[dag.LevelPlan] = None,
                  engine: str = "auto", policy=None) -> LatencyCurve:
    """ΔL curve on latency class ``cls`` (an index, or a registered class
    name like ``"dcn"``)."""
    _check_engine_arg(engine)
    cls = resolve_class(params, cls)
    deltas_arr = np.asarray(deltas, dtype=np.float64)
    want_sweep = (engine == "sweep" or policy is not None
                  or (engine == "auto" and deltas_arr.size >= SWEEP_MIN_POINTS))
    if want_sweep:
        try:
            from repro.sweep import latency_grid
            res = _sweep_engine(g, params, policy).run(
                latency_grid(params, deltas_arr, cls=cls))
            return LatencyCurve(deltas=deltas_arr, T=res.T,
                                lam=res.lam[:, cls], rho=res.rho[:, cls])
        except ImportError as e:
            if not _scalar_ok(e, engine, policy):
                raise
    plan = plan or dag.LevelPlan(g)
    Ts, lams, rhos = [], [], []
    for d in deltas_arr:
        s = plan.forward(params.with_delta(float(d), cls))
        Ts.append(s.T)
        lams.append(float(s.lam[cls]))
        rhos.append(float(s.rho()[cls]))
    return LatencyCurve(deltas=deltas_arr,
                        T=np.asarray(Ts), lam=np.asarray(lams), rho=np.asarray(rhos))


@dataclasses.dataclass
class ResilienceReport:
    """Expected slowdown under a fault distribution (one batched query).

    ``T_fault``/``slowdown`` are aligned with ``faults``; ``weights`` are
    the per-fault probabilities (their shortfall from 1 is the no-fault
    mass at slowdown 1.0).  ``quantiles`` are weighted quantiles of the
    slowdown distribution; ``result`` is the full B?×K?×S sweep
    :class:`~repro.sweep.api.Result` for drill-down, with ``cells``
    naming each fault's cell in it.
    """

    T0: float                          # intact-system makespan (µs)
    faults: list
    names: tuple
    weights: np.ndarray
    T_fault: np.ndarray                # per-fault makespan (µs)
    slowdown: np.ndarray               # T_fault / T0
    expected_slowdown: float
    quantiles: dict                    # {"p50": …, "p95": …, "p99": …}
    result: object
    cells: list

    def rank(self) -> list:
        """Faults ordered most-damaging first: (name, slowdown)."""
        order = np.argsort(-self.slowdown, kind="stable")
        return [(self.names[i], float(self.slowdown[i])) for i in order]

    def __str__(self):
        rows = [f"T0 = {self.T0:.3f} µs   "
                f"E[slowdown] = {self.expected_slowdown:.4f}"]
        for p, v in self.quantiles.items():
            rows.append(f"  {p} slowdown = {v:.4f}")
        for name, s in self.rank():
            rows.append(f"  {name}: ×{s:.4f}")
        return "\n".join(rows)


def _weighted_quantiles(values: np.ndarray, weights: np.ndarray,
                        qs: Sequence[float]) -> dict:
    """Weighted quantiles by inverted CDF (first value whose cumulative
    weight reaches q of the total)."""
    order = np.argsort(values, kind="stable")
    v, w = values[order], weights[order]
    cum = np.cumsum(w)
    total = cum[-1]
    out = {}
    for q in qs:
        i = int(np.searchsorted(cum, q * total, side="left"))
        out[f"p{int(round(q * 100))}"] = float(v[min(i, v.size - 1)])
    return out


def resilience_curve(g: ExecutionGraph, params: LogGPS, faults: Sequence,
                     weights: Optional[Sequence[float]] = None,
                     quantiles: Sequence[float] = (0.50, 0.95, 0.99),
                     engine: str = "auto", policy=None) -> ResilienceReport:
    """Expected slowdown under a fault distribution, as ONE batched query.

    ``faults`` is a list of :class:`~repro.sweep.scenarios.StragglerFault`
    / :class:`~repro.sweep.scenarios.LinkFault` /
    :class:`~repro.sweep.scenarios.DeviceFault`; each family rides one
    engine batch axis (K / S / B), so the whole distribution — plus the
    intact baseline at cell (0, 0, 0) — evaluates in a single compiled
    program (see :func:`repro.sweep.scenarios.fault_axes`).

    ``weights`` are per-fault probabilities: nonnegative, summing to
    ≤ 1; the shortfall is the no-fault mass (slowdown 1.0).  ``None``
    means uniform over ``faults`` (the conditional-on-a-fault
    distribution).  The report carries E[slowdown] and weighted
    p50/p95/p99 over the distribution.

    Device faults need the structural (B) axis and therefore the batched
    engine; the scalar path (JAX not installed, or ``engine="scalar"``)
    handles straggler and link faults only and raises otherwise.  Sharded
    policies are rejected by the engine when the B axis is populated.
    """
    _check_engine_arg(engine)
    faults = list(faults)
    if not faults:
        raise ValueError("resilience_curve needs at least one fault")
    if weights is None:
        w = np.full(len(faults), 1.0 / len(faults))
    else:
        w = np.asarray(weights, dtype=np.float64).ravel()
        if w.shape[0] != len(faults):
            raise ValueError(f"{len(faults)} faults but {w.shape[0]} weights")
        if (w < 0).any() or w.sum() > 1.0 + 1e-9:
            raise ValueError("weights must be nonnegative and sum to ≤ 1 "
                             "(the shortfall is the no-fault mass)")

    from repro.sweep.scenarios import DeviceFault, fault_axes
    has_device = any(isinstance(f, DeviceFault) for f in faults)

    res = None
    if engine != "scalar":
        try:
            from repro.sweep.api import ExecPolicy, Query
            # an explicit unified-Engine policy (the legacy shim has no
            # structure axis)
            eng = _sweep_engine(g, params,
                                policy if policy is not None
                                else ExecPolicy())
            ax = fault_axes(g, params, faults, plan=eng.plan)
            res = eng.run(Query(scenarios=ax.scenarios, costs=ax.extras,
                                structure=ax.structure))
        except ImportError as e:
            if has_device or not _scalar_ok(e, engine, policy):
                raise              # no scalar path can serve these

    if res is not None:
        def cell_T(b, k, s):
            idx = []
            if "B" in res.axes:
                idx.append(b)
            if "K" in res.axes:
                idx.append(k)
            idx.append(s)
            return float(res.T[tuple(idx)])

        T0 = cell_T(0, 0, 0)
        T_fault = np.asarray([cell_T(*c) for c in ax.cells])
        names, cells = ax.names, ax.cells
    else:                              # scalar path: K/S families only
        if has_device:
            raise ValueError(
                "device faults need the batched sweep engine (structural "
                "B axis) — the scalar path cannot evaluate them")
        ax = fault_axes(g, params, faults)
        plan = dag.LevelPlan(g)
        T0 = plan.forward(params).T
        T_fault = np.empty(len(faults))
        for i, (b, k, s) in enumerate(ax.cells):
            extra = None if ax.extras is None or k == 0 else ax.extras[k]
            p = params.replace(L=tuple(ax.scenarios.L[s]))
            gs = ax.scenarios.gscale[s]
            if (gs != 1.0).any():
                from .graph import edge_gap_shares
                egap, egclass = edge_gap_shares(g, p)
                gextra = egap * (gs[egclass] - 1.0)
                extra = gextra if extra is None else extra + gextra
            T_fault[i] = plan.forward(p, extra_edge_cost=extra).T
        names, cells = ax.names, ax.cells

    slow = T_fault / T0
    vals = np.concatenate([[1.0], slow])
    ws = np.concatenate([[max(0.0, 1.0 - w.sum())], w])
    return ResilienceReport(
        T0=T0, faults=faults, names=names, weights=w, T_fault=T_fault,
        slowdown=slow,
        expected_slowdown=float((vals * ws).sum() / ws.sum()),
        quantiles=_weighted_quantiles(vals, ws, quantiles),
        result=res, cells=list(cells))


def latency_tolerance(g: ExecutionGraph, params: LogGPS,
                      degradations: Sequence[float] = (0.01, 0.02, 0.05),
                      cls=0, plan: Optional[dag.LevelPlan] = None,
                      engine: str = "auto", policy=None) -> dict:
    """The Fig 1 colored zones: ΔL tolerable before each p% degradation.

    ``cls`` is a class index or registered name.  With ≥
    :data:`SWEEP_MIN_DEGRADATIONS` levels the bisections run in
    lockstep on the batched engine — one sweep call per probe round instead
    of one scalar forward per probe per level.
    """
    _check_engine_arg(engine)
    cls = resolve_class(params, cls)
    degr = list(degradations)
    want_sweep = (engine == "sweep" or policy is not None
                  or (engine == "auto" and len(degr) >= SWEEP_MIN_DEGRADATIONS))
    if want_sweep:
        try:
            from repro.sweep import tolerance_batched
            return tolerance_batched(_sweep_engine(g, params, policy),
                                     params, degr, cls=cls)
        except ImportError as e:
            if not _scalar_ok(e, engine, policy):
                raise
    plan = plan or dag.LevelPlan(g)
    return {p: dag.tolerance(g, params, p, cls=cls, plan=plan)
            for p in degr}


def bandwidth_curve(g: ExecutionGraph, params: LogGPS,
                    gscales: Sequence[float], cls=0,
                    plan: Optional[dag.LevelPlan] = None,
                    engine: str = "auto", policy=None) -> LatencyCurve:
    """T(γ·G) over bandwidth scales (γ > 1 = slower links on class ``cls``,
    an index or a registered class name).

    Both paths resolve per-edge gap shares through
    :func:`repro.core.graph.edge_gap_shares` — build-time recorded shares
    are authoritative, unknown shares reconstruct from ``params`` — so the
    compiled sweep path and the scalar path always agree.  The sweep
    engine re-scales the shares inside the compiled forward; the scalar
    path feeds ``egap·(γ−1)`` through ``extra_edge_cost`` — no graph
    rebuild either way.

    Raises ``ValueError`` if any resolved share is non-finite (an inf/NaN
    recorded ``g.egap`` entry, or non-finite ``params.G`` feeding the
    reconstruction): one bad share would silently poison the whole curve
    through the γ·G scaling on either path.
    """
    from .graph import edge_gap_shares
    _check_engine_arg(engine)
    cls = resolve_class(params, cls)
    # resolve shares up front (cheap, O(ne) numpy) so BOTH paths are
    # guarded — the compiled sweep engine bakes these same shares in
    egap, egclass = edge_gap_shares(g, params)
    bad = ~np.isfinite(egap)
    if bad.any():
        raise ValueError(
            f"bandwidth_curve: {int(bad.sum())}/{egap.size} edge gap "
            "share(s) resolved non-finite — a γ·G sweep would return NaN/"
            "inf curves.  Recorded shares (GraphBuilder gap_us=...) are "
            "used as-is and unknown shares (raw add_edge(nbytes=...) "
            "calls) reconstruct as (s−1)·G from params: check g.egap for "
            "hand-set NaN/inf entries and params.G for non-finite values")
    gs = np.asarray(gscales, dtype=np.float64)
    want_sweep = (engine == "sweep" or policy is not None
                  or (engine == "auto" and gs.size >= SWEEP_MIN_POINTS))
    if want_sweep:
        try:
            from repro.sweep import bandwidth_grid
            res = _sweep_engine(g, params, policy).run(
                bandwidth_grid(params, gs, cls=cls))
            return LatencyCurve(deltas=gs, T=res.T,
                                lam=res.lam[:, cls], rho=res.rho[:, cls])
        except ImportError as e:
            if not _scalar_ok(e, engine, policy):
                raise
    plan = plan or dag.LevelPlan(g)
    scale = np.where(egclass == cls, 1.0, 0.0) * egap
    Ts, lams, rhos = [], [], []
    for gamma in gs:
        s = plan.forward(params, extra_edge_cost=scale * (gamma - 1.0))
        Ts.append(s.T)
        lams.append(float(s.lam[cls]))
        rhos.append(float(s.rho()[cls]))
    return LatencyCurve(deltas=gs, T=np.asarray(Ts), lam=np.asarray(lams),
                        rho=np.asarray(rhos))


def critical_latencies(g: ExecutionGraph, params: LogGPS, L_min: float,
                       L_max: float, cls=0,
                       plan: Optional[dag.LevelPlan] = None,
                       engine: str = "auto", policy=None) -> list:
    """Algorithm 2's kink search on class ``cls`` (index or registered
    name); big graphs probe whole interval frontiers per batched sweep
    call instead of one scalar forward per interval."""
    _check_engine_arg(engine)
    cls = resolve_class(params, cls)
    want_sweep = (engine == "sweep" or policy is not None
                  or (engine == "auto"
                      and g.num_edges >= SWEEP_MIN_EDGES_BREAKPOINTS))
    if want_sweep:
        try:
            from repro.sweep import breakpoints_batched
            return breakpoints_batched(_sweep_engine(g, params, policy),
                                       params, L_min, L_max, cls=cls)
        except ImportError as e:
            if not _scalar_ok(e, engine, policy):
                raise
    return dag.breakpoints(g, params, L_min, L_max, cls=cls, plan=plan)
