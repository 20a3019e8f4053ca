"""Warm-plan analysis service: what-if latency queries over compiled sweeps.

The LLAMP workflow an operator actually runs is interactive: "here are my
candidate collective algorithms / topologies / placements — how does each
behave as DCN latency degrades, and which one should I deploy?"  Answering
that cold means re-compiling a sweep program per question.  This service
keeps the expensive artifacts warm — one :class:`~repro.sweep.Engine`
per registered variant, one packed multi-graph
:class:`~repro.sweep.Engine` per shape bucket, and a shared
:class:`~repro.sweep.SweepCache` of results — so every query after the
first is a jit dispatch (or a cache hash) instead of a compile.

Request/response API (JSON-friendly dataclasses)::

    svc = AnalysisService()
    svc.register(variant)                  # GraphVariant, or register_graph()
    svc.warm()                             # compile + pack now (optional)
    resp = svc.handle(AnalysisRequest(kind="rank", deltas=[0, 50, 100]))
    resp.payload["ranking"]                # best-first [(name, objective)]

Query kinds: ``curve`` (T/λ/ρ over ΔL), ``bandwidth`` (T over γ·G),
``tolerance`` (p%-degradation ΔL budgets), ``rank`` (variant ordering over
a shared grid — one compiled call per shape bucket), ``placement``
(Algorithm-3 rank-mapping suggestion on a two-tier Φ), ``resilience``
(expected slowdown + p50/p95/p99 under a fault distribution — straggler /
degraded-link / failed-device specs lowered onto the engine's K/S/B axes,
one batched call; see ``sensitivity.resilience_curve``), ``explore``
(design-space search — a ``repro.explore`` preset space + ask/tell
searcher runs its generations through the packed
:class:`~repro.explore.Stamper`, which stays warm on the service so
follow-up searches replay compiled envelopes), ``stats``, ``metrics``
(the ``repro.obs`` registry snapshot + cache stats).

Observability (``repro.obs``): every request carries a trace id — the
client's ``trace`` field when present, a fresh id otherwise — echoed on
the response, and every successful response carries ``timings``, a
per-phase span breakdown (``analysis.<kind>`` plus the engine's
``sweep.*`` spans) captured per-request without enabling tracing
process-wide.  ``--metrics HOST:PORT`` serves the Prometheus text
exposition at ``/metrics`` (JSON snapshot at ``/metrics.json``) on a
daemon thread next to either serve loop.

Execution policy rides each request as one ``policy`` block (parsed into a
:class:`repro.sweep.api.ExecPolicy` — unknown keys are rejected with the
offending names, so a ``"bakend"`` typo fails loudly instead of silently
running under defaults)::

    {"kind": "curve", "policy": {"backend": "pallas", "lam": "fd"}}

The legacy top-level ``backend``/``shard`` fields are still honored (they
overlay the policy block).

CLI (a JSON-lines request/response protocol): one-shot

    PYTHONPATH=src python -m repro.launch.analysis --demo --query rank

a stdin/stdout serve loop — one request object per line, one response
object per line:

    PYTHONPATH=src python -m repro.launch.analysis --demo --serve

or the same protocol over real transport — a TCP or UNIX-domain socket
serving concurrent connections against ONE warm service (all connections
share the compiled engines and the result cache):

    PYTHONPATH=src python -m repro.launch.analysis --demo \\
        --serve-socket 127.0.0.1:0        # or a filesystem path (UNIX)

(The model-serving driver in ``launch.serve`` is unrelated — that is the
prefill/decode loop for traced architectures.)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional, Sequence

import numpy as np

from repro.core import placement as placement_mod
from repro.core.graph import ExecutionGraph
from repro.core.loggps import LogGPS, resolve_class
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.sweep import (Engine, ExecPolicy, GraphVariant,  # noqa: F401
                         SweepCache, group_plans, latency_grid,
                         bandwidth_grid, tolerance_batched)

_REQUESTS = _obs_metrics.counter(
    "analysis_requests_total", "Analysis requests by kind and outcome.",
    labels=("kind", "ok"))
_REQUEST_SECONDS = _obs_metrics.histogram(
    "analysis_request_seconds", "Analysis request latency by kind.",
    labels=("kind",))


@dataclasses.dataclass
class AnalysisRequest:
    """One what-if query.  Unused fields are ignored by other kinds."""

    kind: str                                   # see module docstring
    variant: Optional[str] = None               # default: first registered
    cls: object = 0                             # latency class under study
                                                # (index, or a registered
                                                # class name like "dcn")
    deltas: Optional[Sequence[float]] = None    # ΔL grid (curve / rank)
    gscales: Optional[Sequence[float]] = None   # γ grid (bandwidth)
    degradations: Optional[Sequence[float]] = None  # p levels (tolerance)
    reduce: str = "mean"                        # rank objective: mean|max|final
    topo: Optional[dict] = None                 # placement Φ spec (two_tier kw)
    topk: int = 1                               # placement candidate width
    faults: Optional[Sequence[dict]] = None     # fault specs (resilience):
                                                # {"type": "straggler"|"link"
                                                #  |"device", ...field kwargs}
    weights: Optional[Sequence[float]] = None   # per-fault probabilities
                                                # (resilience; sum ≤ 1)
    space: Optional[str] = None                 # explore: preset name
    space_args: Optional[dict] = None           # explore: preset kwargs
                                                # (P, iters, pod, ...)
    searcher: Optional[str] = None              # explore: random|evolution
                                                # |halving
    generations: int = 4                        # explore: search generations
    population: int = 16                        # explore: candidates per gen
    seed: int = 0                               # explore: search rng seed
    budget: int = 50                            # explore: scenario-grid size
    objective: Optional[dict] = None            # explore: ObjectiveSpec wire
                                                # dict (default robust q95)
    policy: Optional[dict] = None               # ExecPolicy block (wire fields)
    backend: Optional[str] = None               # legacy: overlays policy
    shard: Optional[int] = None                 # legacy: overlays policy
    trace: Optional[str] = None                 # client trace id (echoed back;
                                                # auto-stamped when absent)

    @staticmethod
    def from_json(line: str) -> "AnalysisRequest":
        d = json.loads(line)
        known = {f.name for f in dataclasses.fields(AnalysisRequest)}
        bad = set(d) - known
        if bad:
            raise ValueError(f"unknown request fields: {sorted(bad)}")
        req = AnalysisRequest(**d)
        if req.policy is not None:
            # validate the nested block at the protocol edge: a typo like
            # {"policy": {"bakend": ...}} must come back as a bad-request
            # error naming the field, never execute under defaults
            if not isinstance(req.policy, dict):
                raise ValueError("policy must be an object of ExecPolicy "
                                 f"fields, got {type(req.policy).__name__}")
            ExecPolicy.from_dict(req.policy)
        return req

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


@dataclasses.dataclass
class AnalysisResponse:
    kind: str
    ok: bool
    payload: dict
    elapsed_ms: float
    error: Optional[str] = None
    trace: Optional[str] = None                 # request trace id (always set)
    #: per-phase span breakdown {name: {"ms", "n"}} — ``analysis.<kind>``
    #: plus the engine's ``sweep.*`` spans; None on pre-dispatch failures
    timings: Optional[dict] = None

    def to_json(self) -> str:
        return json.dumps(_jsonable(dataclasses.asdict(self)),
                          allow_nan=False)


def _jsonable(x):
    """Recursively coerce a payload to strict JSON: numpy → builtins, and
    non-finite floats → the strings "inf"/"-inf"/"nan" (bare ``Infinity``
    tokens would break every strict consumer of the JSON-lines protocol —
    unbounded tolerances are a legitimate answer, e.g. a class that never
    reaches the critical path)."""
    if isinstance(x, np.ndarray):
        x = x.tolist()
    if isinstance(x, (np.floating, np.integer)):
        x = x.item()
    if isinstance(x, float) and not np.isfinite(x):
        return repr(x)                          # 'inf' / '-inf' / 'nan'
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    return x


def _reduce_T(T: np.ndarray, reduce: str) -> float:
    """Scalar makespan objective over a scenario-only T — same reduce
    vocabulary as :meth:`repro.sweep.api.Result.rank`."""
    if reduce == "mean":
        return float(T.mean())
    if reduce == "max":
        return float(T.max())
    if reduce == "final":
        return float(T.ravel()[-1])
    raise ValueError(f"unknown reduce {reduce!r}")


class AnalysisService:
    """Registered variants + warm compiled plans behind a query API.

    All engines are unified :class:`repro.sweep.api.Engine` instances
    executing under one service-level :class:`~repro.sweep.api.ExecPolicy`
    (shared result cache included); per-request ``policy`` blocks overlay
    it field-by-field *once*, at parse time — no kwarg threading.
    """

    def __init__(self, backend: str = "segment",
                 cache: Optional[SweepCache] = None,
                 default_deltas: Sequence[float] = (0.0, 25.0, 50.0, 100.0),
                 policy: Optional[ExecPolicy] = None):
        from repro.sweep import DEFAULT_CACHE
        if cache is None and policy is not None \
                and policy.cache is not None \
                and policy.cache is not DEFAULT_CACHE:
            # a policy carrying an explicit cache object IS the caller's
            # cache choice (e.g. sharing one cache across services) —
            # don't shadow it with a fresh private one
            cache = policy.cache
        self.cache = cache if cache is not None else SweepCache(capacity=256)
        self.policy = (policy if policy is not None
                       else ExecPolicy(backend=backend)).replace(
                           cache=self.cache)
        self.backend = self.policy.backend
        self.default_deltas = tuple(default_deltas)
        self._variants: dict = {}               # name → GraphVariant (ordered)
        self._engines: dict = {}                # name → Engine (single graph)
        self._groups: Optional[list] = None     # cached bucket index groups
        self._multi: dict = {}                  # group key → Engine (G axis)
        self._stamper = None                    # warm explore Stamper (lazy)

    # -- registration --------------------------------------------------------
    def register(self, variant: GraphVariant) -> str:
        if variant.name in self._variants:
            raise ValueError(f"variant {variant.name!r} already registered")
        self._variants[variant.name] = variant
        self._groups = None                     # packing is stale
        self._multi.clear()
        return variant.name

    def register_graph(self, name: str, graph: ExecutionGraph,
                       params: LogGPS, **meta) -> str:
        return self.register(GraphVariant(name=name, graph=graph,
                                          params=params, meta=dict(meta)))

    @property
    def variant_names(self) -> tuple:
        return tuple(self._variants)

    def _variant(self, name: Optional[str]) -> GraphVariant:
        if not self._variants:
            raise ValueError("no variants registered")
        if name is None:
            return next(iter(self._variants.values()))
        if name not in self._variants:
            raise ValueError(f"unknown variant {name!r} "
                             f"(have {list(self._variants)})")
        return self._variants[name]

    def _policy(self, req: AnalysisRequest) -> ExecPolicy:
        """Resolve one request's effective ExecPolicy: the service policy,
        overlaid by the request's ``policy`` block (unknown keys rejected),
        overlaid by the legacy top-level ``backend``/``shard`` fields."""
        pol = self.policy
        if req.policy is not None:
            pol = ExecPolicy.from_dict(req.policy, base=pol)
        if req.backend is not None:
            pol = pol.replace(backend=req.backend)
        if req.shard is not None:
            pol = pol.replace(shard=req.shard)
        return pol

    # -- warm plans ----------------------------------------------------------
    def engine(self, name: Optional[str] = None) -> Engine:
        """Per-variant warm engine (compiled on first use, then cached)."""
        v = self._variant(name)
        eng = self._engines.get(v.name)
        if eng is None:
            eng = self._engines[v.name] = Engine(v.graph, params=v.params,
                                                 policy=self.policy)
        return eng

    def _bucket_engines(self) -> list:
        """[(names, Engine)] — one packed graph-axis engine per shape
        bucket."""
        if self.policy.backend == "sparse":
            # sparse plans are one-graph-per-program (no dense packing
            # envelope to share) — rank traffic loops per-variant engines
            return []
        if self._groups is None:
            names = list(self._variants)
            plans = [self.engine(n).plan for n in names]
            self._groups = group_plans(plans)
            self._multi = {}
            for gi, idx in enumerate(self._groups):
                self._multi[gi] = Engine(
                    [plans[i] for i in idx],
                    names=[names[i] for i in idx], policy=self.policy)
        names = list(self._variants)
        return [([names[i] for i in idx], self._multi[gi])
                for gi, idx in enumerate(self._groups)]

    def warm(self, jit: bool = True) -> dict:
        """Compile every variant plan and pack every bucket now (instead of
        lazily on the first query).  With ``jit=True`` every engine — each
        per-variant engine (curve/bandwidth/tolerance queries) and each
        packed bucket engine (rank queries) — also runs a probe over the
        default ΔL grid so the XLA programs are built before the first
        real query hits them (grids of other sizes still jit on first use
        — the scenario axis is shape-bucketed).  Returns packing stats."""
        t0 = time.perf_counter()
        buckets = self._bucket_engines()
        if jit:
            deltas = np.asarray(self.default_deltas, dtype=np.float64)
            for name, v in self._variants.items():
                self.engine(name).run(latency_grid(v.params, deltas),
                                      use_cache=False)
            for names, meng in buckets:
                batches = [latency_grid(self._variants[n].params, deltas)
                           for n in names]
                meng.run(batches, use_cache=False)
                # rank queries run values-only — pre-build that program too
                meng.run(batches, outputs=("T",), use_cache=False)
        return {"variants": len(self._variants), "buckets": len(buckets),
                "bucket_sizes": [len(ns) for ns, _ in buckets],
                "warm_s": time.perf_counter() - t0}

    # -- queries -------------------------------------------------------------
    def curve(self, req: AnalysisRequest) -> dict:
        """T/λ/ρ over a ΔL grid.  The request's policy block picks the
        compiled path per query (backend, λ mode, scenario-axis device
        fan-out) — λ is first-class on both segment and pallas."""
        v = self._variant(req.variant)
        cls = resolve_class(v.params, req.cls)
        deltas = np.asarray(req.deltas if req.deltas is not None
                            else self.default_deltas, dtype=np.float64)
        res = self.engine(v.name).run(latency_grid(v.params, deltas,
                                                   cls=cls),
                                      policy=self._policy(req))
        return {"variant": v.name, "cls": cls, "deltas": deltas,
                "backend": res.backend,
                "T": res.T, "lam": res.lam[:, cls],
                "rho": res.rho[:, cls], "from_cache": res.from_cache}

    def bandwidth(self, req: AnalysisRequest) -> dict:
        v = self._variant(req.variant)
        cls = resolve_class(v.params, req.cls)
        gs = np.asarray(req.gscales if req.gscales is not None
                        else (1.0, 2.0, 4.0), dtype=np.float64)
        # values-only: the payload exposes T alone, so don't pay for the
        # λ-backtrace program
        res = self.engine(v.name).run(bandwidth_grid(v.params, gs,
                                                     cls=cls),
                                      outputs=("T",),
                                      policy=self._policy(req))
        return {"variant": v.name, "cls": cls, "gscales": gs,
                "backend": res.backend,
                "T": res.T, "from_cache": res.from_cache}

    def tolerance(self, req: AnalysisRequest) -> dict:
        v = self._variant(req.variant)
        cls = resolve_class(v.params, req.cls)
        degr = tuple(req.degradations if req.degradations is not None
                     else (0.01, 0.02, 0.05))
        tol = tolerance_batched(self.engine(v.name), v.params, degr,
                                cls=cls,
                                backend=self._policy(req).backend)
        return {"variant": v.name, "cls": cls, "tolerance": tol}

    def rank(self, req: AnalysisRequest) -> dict:
        """Order every registered variant over a shared ΔL grid — one
        compiled call per shape bucket, not one per variant.  Ranking needs
        only T, so the run is values-only (the cheap program: no λ
        backtrace compiled into the packed forward)."""
        if not self._variants:
            raise ValueError("no variants registered")
        deltas = np.asarray(req.deltas if req.deltas is not None
                            else self.default_deltas, dtype=np.float64)
        # resolve per variant — a class *name* may map to different indexes
        # across registries, but every variant must know it
        lacking = []
        for n, v in self._variants.items():
            try:
                resolve_class(v.params, req.cls)
            except (ValueError, KeyError):
                lacking.append(n)
        if lacking:
            raise ValueError(
                f"cls={req.cls!r} is unknown to variants {lacking} — "
                "a ranking must sweep every variant on the same class")
        scored: list = []
        calls = 0
        pol = self._policy(req)
        if pol.backend == "sparse" or self.policy.backend == "sparse":
            # no packed graph axis sparse-side: one compact-slot-list call
            # per variant, same ranking contract
            for name, v in self._variants.items():
                eng = self.engine(name)
                before = eng.calls
                res = eng.run(latency_grid(v.params, deltas, cls=req.cls),
                              outputs=("T",), policy=pol)
                calls += eng.calls - before
                scored.append((name, _reduce_T(res.T, req.reduce)))
            scored.sort(key=lambda kv: kv[1])
            return {"cls": req.cls, "deltas": deltas, "reduce": req.reduce,
                    "ranking": scored, "best": scored[0][0],
                    "compiled_calls": calls}
        for names, meng in self._bucket_engines():
            batches = [latency_grid(self._variants[n].params, deltas,
                                    cls=req.cls)
                       for n in names]
            before = meng.calls
            # shard rides the packed graph axis by default (the natural
            # shard_map mesh axis): big variant studies split across devices
            res = meng.run(batches, outputs=("T",), policy=pol)
            calls += meng.calls - before
            scored.extend(res.rank(reduce=req.reduce))
        scored.sort(key=lambda kv: kv[1])
        return {"cls": req.cls, "deltas": deltas, "reduce": req.reduce,
                "ranking": scored, "best": scored[0][0],
                "compiled_calls": calls}

    def placement(self, req: AnalysisRequest) -> dict:
        """Algorithm-3 rank-mapping suggestion on a two-tier Φ.

        Placement's cost model requires the variant's graph to be built
        with zero link costs (``core.placement`` contract: ALL network
        cost comes from Φ via the mapping) — registering a variant with
        real LogGPS link parameters and then asking for a placement would
        double-count every message (built-in elat/econst AND Φ), so that
        is rejected rather than answered wrongly.
        """
        v = self._variant(req.variant)
        if np.any(np.asarray(v.params.L)) or np.any(np.asarray(v.params.G)):
            raise ValueError(
                f"variant {v.name!r} was registered with nonzero link "
                "params — placement queries need a zero-link-cost build "
                "(L=0, G=0; all network cost comes from the Φ topology; "
                "see core.placement)")
        spec = dict(req.topo or {})
        P = int(spec.pop("P", v.graph.nranks))
        pod = int(spec.pop("pod", max(P // 2, 1)))
        phi = placement_mod.ArchTopology.two_tier(P, pod, **spec)
        pts = (placement_mod.latency_points(v.params, req.deltas,
                                            cls=resolve_class(v.params,
                                                              req.cls))
               if req.deltas is not None else None)
        # zero-recompile loop: ONE compiled plan, candidates patched in;
        # the shared service cache memoizes candidate evaluations (patched
        # costs participate in the content-hash keys), so re-asking the
        # same placement question costs hash lookups, not forwards
        stats: dict = {}
        pi, hist = placement_mod.place(v.graph, phi, params=v.params,
                                       scenarios=pts, topk=req.topk,
                                       policy=self._policy(req),
                                       stats=stats)
        return {"variant": v.name, "mapping": pi, "history": hist,
                "improvement": (1.0 - hist[-1] / hist[0]) if hist[0] else 0.0,
                "stats": stats}

    @staticmethod
    def _parse_faults(specs: Sequence[dict]) -> list:
        """Wire fault specs → fault dataclasses (protocol-edge validation:
        an unknown type or field comes back as a bad-request error naming
        the offending spec, never a server traceback)."""
        from repro.sweep import DeviceFault, LinkFault, StragglerFault
        kinds = {"straggler": StragglerFault, "link": LinkFault,
                 "device": DeviceFault}
        out = []
        for i, d in enumerate(specs):
            if not isinstance(d, dict):
                raise ValueError(f"fault[{i}] must be an object, "
                                 f"got {type(d).__name__}")
            d = dict(d)
            typ = d.pop("type", None)
            cls = kinds.get(typ)
            if cls is None:
                raise ValueError(f"fault[{i}]: type must be one of "
                                 f"{sorted(kinds)}, got {typ!r}")
            try:
                out.append(cls(**d))
            except TypeError as e:
                raise ValueError(f"fault[{i}] ({typ}): {e}") from None
        return out

    def resilience(self, req: AnalysisRequest) -> dict:
        """Expected slowdown under a fault distribution, as ONE batched
        query per variant: the request's ``faults`` list (straggler /
        link / device specs) lowers onto the engine's K/S/B axes and the
        whole distribution — intact baseline included — evaluates in a
        single compiled program (``sensitivity.resilience_curve``).
        ``weights`` are per-fault probabilities (sum ≤ 1; the shortfall
        is the no-fault mass)."""
        from repro.core import sensitivity
        v = self._variant(req.variant)
        if not req.faults:
            raise ValueError(
                "resilience queries need a nonempty 'faults' list, e.g. "
                '[{"type": "straggler", "vertices": [5], "slowdown": 2}]')
        faults = self._parse_faults(req.faults)
        rep = sensitivity.resilience_curve(v.graph, v.params, faults,
                                           weights=req.weights,
                                           policy=self._policy(req))
        return {"variant": v.name, "T0": rep.T0,
                "faults": list(rep.names),
                "T_fault": rep.T_fault, "slowdown": rep.slowdown,
                "expected_slowdown": rep.expected_slowdown,
                "quantiles": rep.quantiles, "rank": rep.rank(),
                "axes": None if rep.result is None else list(rep.result.axes),
                "cells": rep.cells}

    def explore(self, req: AnalysisRequest) -> dict:
        """Design-space search over a ``repro.explore`` preset.

        ``space`` names the preset (default ``"codesign"``),
        ``space_args`` parameterizes it (``P``, ``iters``, ``pod``, …),
        ``searcher``/``generations``/``population``/``seed`` drive the
        ask/tell loop, ``budget`` sizes the scenario grid (``deltas``,
        when given, bound its ΔL range) and ``objective`` is an
        :class:`~repro.explore.ObjectiveSpec` wire dict.  The service
        keeps ONE warm :class:`~repro.explore.Stamper`, so a follow-up
        search over the same preset replays compiled envelopes instead
        of recompiling them."""
        from repro import explore as explore_mod
        from repro.core.loggps import LogGPS
        from repro.sweep import sample_grid
        kw = dict(req.space_args or {})
        P = int(kw.pop("P", 16))
        iters = int(kw.pop("iters", 3))
        params = kw.pop("params", None) or LogGPS()
        space, lower = explore_mod.preset(req.space or "codesign",
                                          P=P, iters=iters, params=params,
                                          **kw)
        objective = (explore_mod.ObjectiveSpec.from_dict(req.objective)
                     if req.objective else explore_mod.robust_makespan())
        lo, hi = ((min(req.deltas), max(req.deltas))
                  if req.deltas else (0.0, 100.0))
        scen = sample_grid(params, int(req.budget), rng=int(req.seed),
                           lat_deltas=(lo, hi))
        name = req.searcher or "random"
        skw = ({"population_size": max(2, int(req.population))}
               if name == "evolution" else {})
        searcher = explore_mod.make_searcher(name, space, int(req.seed),
                                             **skw)
        if self._stamper is None:
            self._stamper = explore_mod.Stamper(policy=self._policy(req))
        res = explore_mod.run_search(
            searcher, lower, scen, generations=int(req.generations),
            population=int(req.population), objective=objective,
            stamper=self._stamper)
        return {"space": req.space or "codesign", "searcher": searcher.name,
                "best": res.best, "best_objective": res.best_objective,
                "n_evaluated": res.n_evaluated,
                "generations": res.generations,
                "objective": objective.to_dict(),
                "history": [{"gen": h["gen"],
                             "best_objective": h["best_objective"],
                             "stamp": h["stamp"]} for h in res.history],
                "stamper": dict(self._stamper.stats)}

    def stats(self, req: AnalysisRequest) -> dict:
        return {"variants": list(self._variants),
                "warm_engines": list(self._engines),
                "buckets": None if self._groups is None else len(self._groups),
                "cache": self.cache.stats.snapshot(),
                "cache_entries": len(self.cache)}

    def metrics(self, req: AnalysisRequest) -> dict:
        """The process-global ``repro.obs`` registry snapshot — every
        counter/gauge/histogram series (cache hit rates, request latency,
        compile counts, envelope occupancy) in the same shape the
        ``/metrics.json`` HTTP endpoint serves."""
        return {"metrics": _obs_metrics.snapshot(),
                "cache": self.cache.stats.snapshot(),
                "trace_enabled": _obs_trace.TRACER.enabled}

    _KINDS = {"curve": curve, "bandwidth": bandwidth, "tolerance": tolerance,
              "rank": rank, "placement": placement,
              "resilience": resilience, "explore": explore,
              "stats": stats, "metrics": metrics}

    def handle(self, req: AnalysisRequest) -> AnalysisResponse:
        """Dispatch one request; errors come back as ``ok=False`` responses
        (a malformed query must not take the serve loop down).

        Every response carries the request's trace id (``req.trace`` or a
        fresh one) and — on dispatch — a per-phase ``timings`` breakdown
        collected from this thread's spans, tracer enabled or not.
        """
        t0 = time.perf_counter()
        trace_id = req.trace or _obs_trace.new_trace_id()
        fn = self._KINDS.get(req.kind)
        if fn is None:
            _REQUESTS.inc(kind="?", ok="false")
            return AnalysisResponse(
                kind=req.kind, ok=False, payload={},
                elapsed_ms=0.0, trace=trace_id,
                error=f"unknown kind {req.kind!r} "
                      f"(have {sorted(self._KINDS)})")
        try:
            with _obs_trace.collect() as spans, \
                    _obs_trace.trace_context(trace_id), \
                    _obs_trace.span(f"analysis.{req.kind}"):
                payload = fn(self, req)
            elapsed = time.perf_counter() - t0
            _REQUESTS.inc(kind=req.kind, ok="true")
            _REQUEST_SECONDS.observe(elapsed, kind=req.kind)
            return AnalysisResponse(
                kind=req.kind, ok=True, payload=payload,
                elapsed_ms=elapsed * 1e3, trace=trace_id,
                timings=_obs_trace.summarize(spans))
        except Exception as e:  # noqa: BLE001 — serve loop must survive
            elapsed = time.perf_counter() - t0
            _REQUESTS.inc(kind=req.kind, ok="false")
            _REQUEST_SECONDS.observe(elapsed, kind=req.kind)
            return AnalysisResponse(
                kind=req.kind, ok=False, payload={},
                elapsed_ms=elapsed * 1e3, trace=trace_id,
                error=f"{type(e).__name__}: {e}")

    def handle_json(self, line: str) -> str:
        """One serve-loop turn: JSON request line → JSON response line."""
        try:
            req = AnalysisRequest.from_json(line)
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            return AnalysisResponse(kind="?", ok=False, payload={},
                                    elapsed_ms=0.0,
                                    error=f"bad request: {e}").to_json()
        return self.handle(req).to_json()


# -- socket transport ---------------------------------------------------------

def serve_socket(svc: AnalysisService, address: str, poll_s: float = 0.5):
    """Serve the JSON-lines protocol over a TCP or UNIX-domain socket.

    ``address``: ``"host:port"`` (TCP; port 0 picks a free one) or a
    filesystem path (UNIX socket).  Connections are handled on threads,
    but every request executes under one lock against the ONE warm
    service — all clients share the compiled engines and the result
    cache, so a curve another client already asked for is a hash lookup.
    (The engines drive a single jit dispatch per query; serializing them
    trades no real parallelism for a service that needs no thread-safe
    engine state.)

    Prints ``[analysis] listening on <bound-address>`` to stderr once the
    socket is bound (the round-trip test and shell scripts parse it — with
    port 0 the chosen port is only known here).  Runs until interrupted.
    """
    import socketserver
    import threading

    lock = threading.Lock()

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            for raw in self.rfile:
                line = raw.decode("utf-8", "replace").strip()
                if not line:
                    continue
                with lock:
                    out = svc.handle_json(line)
                self.wfile.write(out.encode("utf-8") + b"\n")
                self.wfile.flush()

    if ":" in address and "/" not in address:
        host, port = address.rsplit(":", 1)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        srv = Server((host or "127.0.0.1", int(port)), Handler)
        bound = "%s:%d" % srv.server_address[:2]
    else:
        if not hasattr(socketserver, "ThreadingUnixStreamServer"):
            raise SystemExit("UNIX-domain sockets are not available on "
                             "this platform; use host:port")
        import os

        class Server(socketserver.ThreadingUnixStreamServer):  # type: ignore[name-defined]
            daemon_threads = True

        if os.path.exists(address):
            os.unlink(address)
        srv = Server(address, Handler)
        bound = address
    print(f"[analysis] listening on {bound}", file=sys.stderr, flush=True)
    try:
        srv.serve_forever(poll_interval=poll_s)
    finally:
        srv.server_close()
    return srv


# -- metrics transport ---------------------------------------------------------

def serve_metrics(address: str):
    """Serve the ``repro.obs`` metrics registry over HTTP on a daemon
    thread: ``GET /metrics`` (and ``/``) returns the Prometheus text
    exposition, ``GET /metrics.json`` the JSON snapshot.

    ``address`` is ``host:port`` (port 0 picks a free one).  Prints
    ``[analysis] metrics on http://<bound>/metrics`` to stderr once bound
    (tests and scrape configs parse it).  Returns the server object (its
    ``server_address`` carries the chosen port); the thread dies with the
    process — metrics are a read-only side channel, never worth blocking
    shutdown for.
    """
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            path = self.path.split("?", 1)[0]
            if path in ("/", "/metrics"):
                body = _obs_metrics.render().encode("utf-8")
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/metrics.json":
                body = json.dumps(_jsonable(_obs_metrics.snapshot())) \
                    .encode("utf-8")
                ctype = "application/json"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):            # scrapes are not log events
            pass

    host, port = address.rsplit(":", 1)
    srv = http.server.ThreadingHTTPServer(
        (host or "127.0.0.1", int(port)), Handler)
    srv.daemon_threads = True
    bound = "%s:%d" % srv.server_address[:2]
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="analysis-metrics")
    t.start()
    print(f"[analysis] metrics on http://{bound}/metrics",
          file=sys.stderr, flush=True)
    return srv


# -- CLI ----------------------------------------------------------------------

def _demo_service(backend: str) -> AnalysisService:
    """A small self-contained study: four allreduce expansions of the same
    compute/collective chain (the Fig 10 axis at toy scale)."""
    from repro.core import synth
    from repro.core.loggps import cluster_params
    from repro.sweep import collective_variants

    p = cluster_params(L_us=3.0, o_us=5.0)
    svc = AnalysisService(backend=backend)
    for v in collective_variants(
            lambda a: synth.allreduce_chain(8, 3, params=p, algo=a),
            ["ring", "bidir_ring", "recursive_doubling", "tree"], p):
        svc.register(v)
    return svc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="what-if analysis over warm compiled sweep plans")
    ap.add_argument("--demo", action="store_true",
                    help="register the built-in 4-variant collective study")
    ap.add_argument("--backend", default="segment",
                    choices=("segment", "pallas", "sparse"))
    ap.add_argument("--serve", action="store_true",
                    help="JSON-lines request/response loop on stdin/stdout")
    ap.add_argument("--serve-socket", default=None, metavar="ADDR",
                    help="serve the JSON-lines protocol on a socket: "
                         "host:port (TCP, port 0 = pick free) or a "
                         "filesystem path (UNIX); connections share one "
                         "warm service + result cache")
    ap.add_argument("--metrics", default=None, metavar="HOST:PORT",
                    help="serve the repro.obs metrics registry over HTTP "
                         "(Prometheus text at /metrics, JSON at "
                         "/metrics.json) on a daemon thread next to "
                         "either serve loop; port 0 picks a free one")
    ap.add_argument("--query", default=None,
                    help="one-shot query kind (curve/tolerance/rank/...)")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--cls", default=0,
                    type=lambda s: int(s) if s.lstrip("-").isdigit() else s,
                    help="latency class index or registered name (e.g. dcn)")
    ap.add_argument("--deltas", default=None,
                    help="ΔL grid as start:stop:num, e.g. 0:100:25")
    ap.add_argument("--shard", type=int, default=None,
                    help="split one-shot queries over this many local "
                         "devices (scenario axis for curve/bandwidth, "
                         "graph axis for rank)")
    args = ap.parse_args(argv)

    if not args.demo:
        raise SystemExit("no workload source: pass --demo (or embed "
                         "AnalysisService in your own driver)")
    from .compile_cache import setup_compile_cache
    setup_compile_cache()
    svc = _demo_service(args.backend)
    t0 = time.perf_counter()
    info = svc.warm()
    print(f"[analysis] warmed {info['variants']} variants into "
          f"{info['buckets']} shape bucket(s) in "
          f"{time.perf_counter() - t0:.2f}s",
          file=sys.stderr)

    if args.metrics:
        serve_metrics(args.metrics)

    if args.serve_socket:
        serve_socket(svc, args.serve_socket)
        return svc

    if args.serve:
        print("[analysis] serving; one JSON request per line "
              '(e.g. {"kind": "rank"})', file=sys.stderr)
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            print(svc.handle_json(line), flush=True)
        return svc

    deltas = None
    if args.deltas:
        lo, hi, num = args.deltas.split(":")
        deltas = np.linspace(float(lo), float(hi), int(num)).tolist()
    req = AnalysisRequest(kind=args.query or "rank", variant=args.variant,
                          cls=args.cls, deltas=deltas, shard=args.shard)
    resp = svc.handle(req)
    print(resp.to_json())
    return svc


if __name__ == "__main__":
    main()
