"""Unified axis-oriented sweep API: one :class:`Query`, one
:class:`ExecPolicy`, one :class:`Engine`.

LLAMP's core operation is "evaluate execution graphs under many LogGPS
scenarios".  Four PRs of growth split that one idea across two engine
classes with diverging feature matrices and five spellings of execution
policy; this module folds them back into three objects:

:class:`Query`
    *What* to evaluate — the populated batch axes.  ``graphs`` [G] (one
    plan, a sequence of plans, or a packed ``MultiPlan``), ``costs`` [K]
    (candidate cost blocks patched into warm plan structure),
    ``structure`` [B] (edge-rewired structural variant blocks inside one
    super-envelope — a whole topology study through ONE XLA program),
    ``scenarios`` [S] (LogGPS parameter rows), and the requested
    ``outputs`` ⊆ {"T", "lam", "rho"}.

:class:`ExecPolicy`
    *How* to evaluate it — backend ("segment"/"pallas"/"sparse"), device
    sharding
    (``shard`` count + ``shard_axis`` ∈ {"auto", "G", "K", "S"}), λ mode
    (``"exact"`` backtrace or ``"fd"`` finite-difference over an expanded
    values grid), result cache, dtype contract.

:class:`Engine`
    One evaluator.  The jitted core treats G/K/S as ordinary batch axes:
    the vmap/shard_map composition is derived from which axes the query
    populates (``repro.sweep.engine._get_forward``), not from which class
    was instantiated — so a G×K×S query (per-graph candidate axes on a
    packed MultiPlan, sharded over any axis) runs through the same code
    path as a plain scenario sweep, bit-identically (segment) to the
    equivalent solo/rebuild runs.

    >>> eng = Engine([plan_a, plan_b], policy=ExecPolicy(backend="segment"))
    >>> res = eng.run(Query(scenarios=grid, costs=[extras_a, extras_b]))
    >>> res.T.shape                     # [G, K, S]

The legacy ``SweepEngine`` / ``MultiSweepEngine`` classes are thin
deprecation-warned shims over this engine (bit-identical results, verified
by ``tests/test_conformance.py``); ``core.sensitivity``,
``core.placement.place`` and ``launch.analysis`` all build a
``Query`` + ``ExecPolicy`` instead of threading loose kwargs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import threading
import time
import warnings
from collections import OrderedDict
from typing import Optional, Sequence, Union

import numpy as np

from repro.obs import metrics as _obs_metrics
from repro.obs.compile import WATCHER as _WATCHER
from repro.obs.trace import span as _span

from . import engine as _eng
from .cache import (DEFAULT_CACHE, SweepCache, graph_content_key,
                    query_key)
from .compile import (CompiledPlan, CostBatch, MultiPlan, SparsePlan,
                      StructureBatch, _bucket, compile_plan, compile_sparse,
                      estimate_dense_bytes, pack_plans)
from .scenarios import ScenarioBatch

#: ExecPolicy fields that may arrive over the wire (JSON ``policy`` blocks
#: of ``launch.analysis`` requests).  ``cache`` deliberately excluded — a
#: result cache is a process-local object, never serialized state.
POLICY_WIRE_FIELDS = ("backend", "shard", "shard_axis", "lam", "fd_eps",
                      "dtype", "congestion", "max_iters", "tol",
                      "max_dense_bytes")

_OUTPUTS = ("T", "lam", "rho")

_QUERIES = _obs_metrics.counter(
    "sweep_queries_total", "Engine.run calls by backend/axes/cache outcome.",
    labels=("backend", "axes", "cache"))
_OCCUPANCY = _obs_metrics.gauge(
    "sweep_envelope_occupancy",
    "Fraction of the padded envelope carrying real work (1 - padding "
    "waste), per batch axis, as of the last uncached dispatch.",
    labels=("axis",))
_DENSE_BYTES = _obs_metrics.gauge(
    "sweep_dense_bytes",
    "Bytes of plan tensors staged per backend view (dense views report "
    "the full padded footprint, λ tie-break arrays included — the number "
    "the dense→sparse auto-switch compares to MAX_DENSE_BYTES; the "
    "sparse view reports its compact slot-list bytes).",
    labels=("view",))
_CONGESTION_ITERS = _obs_metrics.histogram(
    "sweep_congestion_iters",
    "Fixed-point iterations to convergence per scenario lane "
    "(congestion='fixed_point' dispatches only).",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))


@dataclasses.dataclass(frozen=True)
class ExecPolicy:
    """How a query executes — everything that is *not* the workload.

    ``backend``
        "segment" (pure-jnp float64, the bit-exact reference), "pallas"
        (the (max,+) TPU kernel, float32 accumulators, ≤1e-5 relative),
        or "sparse" (compact CSR-style slot lists at O(nv + ne) memory
        instead of the padded dense envelope; the Engine auto-selects it
        when a graph's estimated dense footprint exceeds
        ``MAX_DENSE_BYTES``).  Sparse computes float64 by default — T and
        λ bit-identical to segment — while ``dtype="float32"`` selects
        the slot-list (max,+) Pallas kernel for the level reductions
        (scenarios on the 128-wide lane axis, in-kernel lexicographic
        argmax for λ — the sparse twin of the dense pallas backend,
        ≤1e-5 relative).
    ``shard`` / ``shard_axis``
        Device fan-out: ``shard`` is None/False (off), True/"auto" (all
        local devices) or an int cap; ``shard_axis`` picks which populated
        batch axis splits across the mesh — "G" (graphs), "K" (candidate
        cost blocks), "S" (scenarios), or "auto" (G when populated, else
        S).  Per-element arithmetic is unchanged, so sharded results are
        bit-identical to single-device runs.
    ``lam``
        "exact" — the argmax critical-path backtrace (bit-compatible with
        the scalar engine, compiles the λ-bearing program at ~2.5-3× the
        values-only cost on XLA:CPU).  "fd" — finite-difference λ from an
        (nc+1)× expanded *values* grid: λ_c = (T(L + h·e_c) − T(L))/h with
        h = ``fd_eps``.  T is piecewise linear in L and λ is its exact
        right-derivative, so away from breakpoints fd λ equals exact λ to
        float round-off (~ulp(T)/h) while only ever compiling the cheap
        values program (compile ratio ~1.0).  At a breakpoint the two may
        legitimately differ (exact λ applies the max-slope tie-break over
        *all* classes; fd probes one class at a time).
    ``fd_eps``
        The fd step in µs.  Must stay inside the current linear segment;
        the default 2⁻¹⁰ ≈ 1e-3 µs is far below any realistic breakpoint
        spacing.  On the float32 pallas backend, fd λ noise is
        ~ulp(T)/fd_eps — prefer the segment backend for fd sensitivities.
    ``congestion`` / ``max_iters`` / ``tol``
        "none" (default) — the plain LogGPS forward, links uncongested.
        "fixed_point" (segment backend only) — wrap the forward in an
        iterated per-link congestion closure: evaluate, aggregate each
        physical link's offered gap-time, inflate effective gaps by
        ``1 + α_c·max(util − β_c, 0)`` (α, β from the bound params'
        network-class registry), re-evaluate — a damped ``while_loop``
        *inside* the one jitted program, all scenario (and K) lanes in
        lockstep.  ``max_iters``/``tol`` are runtime knobs (changing them
        never recompiles).  With every α = 0 the result is bit-identical
        to ``congestion="none"``.
    ``max_dense_bytes``
        Per-engine override of :data:`Engine.MAX_DENSE_BYTES` (the dense-
        envelope auto-sparse threshold).  None defers to the
        ``REPRO_MAX_DENSE_BYTES`` environment variable, then the class
        attribute.
    ``cache``
        A :class:`~repro.sweep.cache.SweepCache` (or None to disable).
    ``dtype``
        "auto" (backend-native: segment→float64, pallas→float32,
        sparse→float64).  An explicit dtype is validated against the
        backend's contract so a query can *pin* the numeric guarantee it
        relies on; on the sparse backend ``dtype="float32"`` additionally
        *selects* the Pallas slot-list kernel flavor (see ``backend``).
    """

    backend: str = "segment"
    shard: Union[None, bool, int, str] = None
    shard_axis: str = "auto"
    lam: str = "exact"
    fd_eps: float = 2.0 ** -10
    dtype: str = "auto"
    congestion: str = "none"
    max_iters: int = 16
    tol: float = 1e-6
    max_dense_bytes: Optional[int] = None
    cache: Optional[SweepCache] = DEFAULT_CACHE

    def validate(self) -> "ExecPolicy":
        if self.backend not in ("segment", "pallas", "sparse"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.shard_axis not in ("auto", "G", "K", "S"):
            raise ValueError(f"unknown shard_axis {self.shard_axis!r} "
                             "(use 'auto', 'G', 'K' or 'S')")
        if self.lam not in ("exact", "fd"):
            raise ValueError(f"unknown lam mode {self.lam!r} "
                             "(use 'exact' or 'fd')")
        if not float(self.fd_eps) > 0.0:
            raise ValueError(f"fd_eps must be positive, got {self.fd_eps!r}")
        if self.shard is not None and self.shard != "auto" \
                and not isinstance(self.shard, (bool, int, np.integer)):
            # validated here so a wire-format typo ({"shard": "always"})
            # fails at the protocol edge, not deep inside _resolve_shard
            raise ValueError("shard must be None, a bool, an int device "
                             f"count or 'auto', got {self.shard!r}")
        if self.dtype not in ("auto", "float64", "float32"):
            raise ValueError(f"unknown dtype {self.dtype!r} "
                             "(use 'auto', 'float64' or 'float32')")
        if self.congestion not in ("none", "fixed_point"):
            raise ValueError(f"unknown congestion mode {self.congestion!r} "
                             "(use 'none' or 'fixed_point')")
        if self.congestion != "none" and self.backend != "segment":
            raise ValueError(
                "congestion='fixed_point' runs on the segment backend only "
                f"(got backend={self.backend!r}) — the fixed point wraps "
                "the float64 gather/max core")
        if int(self.max_iters) < 1:
            raise ValueError(f"max_iters must be >= 1, got "
                             f"{self.max_iters!r}")
        if not float(self.tol) > 0.0:
            raise ValueError(f"tol must be positive, got {self.tol!r}")
        if self.max_dense_bytes is not None \
                and int(self.max_dense_bytes) <= 0:
            raise ValueError("max_dense_bytes must be a positive byte "
                             f"count, got {self.max_dense_bytes!r}")
        native = {"segment": "float64", "pallas": "float32",
                  "sparse": "float64"}[self.backend]
        if self.backend == "sparse":
            # float64 (native) = the bit-exact jnp slot-list forward;
            # float32 pins the Pallas slot-list kernel flavor instead
            return self
        if self.dtype not in ("auto", native):
            raise ValueError(
                f"backend {self.backend!r} computes in {native}; "
                f"dtype={self.dtype!r} is not available on it")
        return self

    def replace(self, **kw) -> "ExecPolicy":
        return dataclasses.replace(self, **kw).validate()

    @classmethod
    def from_dict(cls, d: dict,
                  base: Optional["ExecPolicy"] = None) -> "ExecPolicy":
        """Parse a wire-format policy block, rejecting unknown keys — a
        typo like ``{"bakend": "pallas"}`` must fail loudly, never execute
        silently under the default policy."""
        bad = sorted(set(d) - set(POLICY_WIRE_FIELDS))
        if bad:
            raise ValueError(
                f"unknown ExecPolicy fields: {bad} "
                f"(known: {sorted(POLICY_WIRE_FIELDS)})")
        return dataclasses.replace(base if base is not None else cls(),
                                   **d).validate()

    def key(self) -> tuple:
        """Hashable identity for engine memoization (content fields plus
        the cache *object* — two policies sharing every knob but pointing
        at different caches must not share a memoized engine)."""
        return (self.backend, self.shard, self.shard_axis, self.lam,
                float(self.fd_eps), self.dtype, self.congestion,
                int(self.max_iters), float(self.tol), self.max_dense_bytes,
                None if self.cache is None else id(self.cache))


@dataclasses.dataclass
class Query:
    """A declarative sweep: which batch axes are populated, nothing else.

    ``scenarios``
        One :class:`~repro.sweep.scenarios.ScenarioBatch` (broadcast to
        every graph) or a per-graph sequence with equal S.
    ``costs``
        The candidate axis [K]: a :class:`~repro.sweep.compile.CostBatch`
        (or raw ``[K, ne]`` extra edge costs) for a single-graph engine; a
        per-graph sequence of those for a multi-graph engine.  All graphs
        must share K.
    ``structure``
        The variant axis [B]: a
        :class:`~repro.sweep.compile.StructureBatch`
        (``CompiledPlan.patch_structure()`` for edge rewirings of the
        engine's plan, ``StructureBatch.from_plans()`` for
        separately-compiled plans on their union envelope) — B structural
        variants vmapped through ONE compiled program, zero recompiles.
        Mutually exclusive with a multi-graph engine's G axis.
    ``outputs``
        Any subset of ("T", "lam", "rho").  Requesting "lam" or "rho"
        computes both (ρ is a free ratio of λ and T).
    ``graphs`` / ``params``
        Optional detached-workload override: when set, :func:`run` (or
        ``Engine.run``) compiles/packs these instead of the engine's bound
        graphs — one plan, a sequence of plans / (graph, params) pairs, or
        a ``MultiPlan``.
    """

    scenarios: object = None
    costs: object = None
    structure: object = None
    outputs: Sequence[str] = _OUTPUTS
    graphs: object = None
    params: object = None


@dataclasses.dataclass
class Result:
    """Axis-shaped sweep tensors: ``T`` has one dim per populated axis, in
    canonical [G?|B?, K?, S] order (``axes`` names them); ``lam``/``rho``
    carry a trailing latency-class dim."""

    T: np.ndarray
    lam: Optional[np.ndarray]
    rho: Optional[np.ndarray]
    axes: tuple                       # subset of ("G"|"B", "K", "S"), in order
    scenarios: object                 # ScenarioBatch, or per-graph list
    backend: str
    names: Optional[tuple] = None     # graph/variant names on a leading G/B axis
    from_cache: bool = False
    lam_mode: str = "exact"
    #: [K?, S] fixed-point iteration counts (congestion dispatches only)
    congestion_iters: Optional[np.ndarray] = None
    #: float dtype the forward computed in ("float64" / "float32")
    dtype: str = ""
    #: platform of the devices that produced T ("tpu", "cpu", ...)
    platform: str = ""
    #: how many devices the forward spanned (> 1 only when sharded)
    devices: int = 0

    @property
    def S(self) -> int:
        return int(self.T.shape[-1])

    @property
    def K(self) -> Optional[int]:
        if "K" not in self.axes:
            return None
        return int(self.T.shape[self.axes.index("K")])

    @property
    def G(self) -> Optional[int]:
        return int(self.T.shape[0]) if "G" in self.axes else None

    @property
    def B(self) -> Optional[int]:
        return int(self.T.shape[0]) if "B" in self.axes else None

    def __getitem__(self, key) -> "Result":
        """Slice off the leading graph/variant axis (by index or name)."""
        if not self.axes or self.axes[0] not in ("G", "B"):
            raise TypeError("result has no graph or variant axis to index")
        g = self.names.index(key) if isinstance(key, str) else int(key)
        # a structure-batched run shares one scenario batch; a multi-graph
        # run carries one per graph
        scen = self.scenarios[g] if self.axes[0] == "G" else self.scenarios
        return Result(
            T=self.T[g].copy(),
            lam=None if self.lam is None else self.lam[g].copy(),
            rho=None if self.rho is None else self.rho[g].copy(),
            axes=self.axes[1:], scenarios=scen,
            backend=self.backend, from_cache=self.from_cache,
            lam_mode=self.lam_mode, dtype=self.dtype,
            platform=self.platform, devices=self.devices)

    def split(self) -> dict:
        """{name: per-graph (or per-variant) Result} — the variant-study
        return shape."""
        return {name: self[i] for i, name in enumerate(self.names)}

    def _objective(self, reduce: str, axis: int) -> np.ndarray:
        """Collapse every axis but ``axis`` to a makespan objective."""
        T = np.moveaxis(self.T, axis, 0).reshape(self.T.shape[axis], -1)
        if reduce == "mean":
            return T.mean(axis=1)
        if reduce == "max":
            return T.max(axis=1)
        if reduce == "final":
            return T[:, -1]
        raise ValueError(f"unknown reduce {reduce!r}")

    def rank(self, reduce: str = "mean") -> list:
        """Graphs (or structural variants) ordered best-first by makespan
        objective over the grid."""
        if not self.axes or self.axes[0] not in ("G", "B"):
            raise TypeError("result has no graph or variant axis to rank")
        obj = self._objective(reduce, 0)
        order = np.argsort(obj, kind="stable")
        return [(self.names[i], float(obj[i])) for i in order]

    def argbest(self, reduce: str = "mean") -> int:
        """Candidate index minimizing the objective (K axis), or the
        scenario index with the smallest makespan (scenario-only result).
        A graph-axis result without K has no single best index — ``rank()``
        the graphs or slice one out first."""
        if "K" in self.axes:
            return int(np.argmin(self._objective(reduce,
                                                 self.axes.index("K"))))
        if self.axes[0] in ("G", "B"):
            raise TypeError("argbest() on a graph/variant-axis result is "
                            "ambiguous (a flat index would conflate it "
                            "with scenarios) — use rank(), or index one "
                            "out first: res[g].argbest()")
        return int(np.argmin(self.T))


def _copy(res: Result, **replace) -> Result:
    return dataclasses.replace(
        res, T=res.T.copy(),
        lam=None if res.lam is None else res.lam.copy(),
        rho=None if res.rho is None else res.rho.copy(),
        congestion_iters=(None if res.congestion_iters is None
                          else res.congestion_iters.copy()), **replace)


def _ran_on(T) -> tuple:
    """(dtype, platform, device count) of a forward's device output — what
    really ran, stamped on the Result so callers can assert it."""
    devs = T.devices()
    return str(T.dtype), next(iter(devs)).platform, len(devs)


def _variant_names(sb: StructureBatch) -> tuple:
    return sb.names if sb.names is not None else tuple(
        f"v{i}" for i in range(sb.B))


# -- detached-engine memo -----------------------------------------------------
#
# ``Engine.run(Query(graphs=...))`` and the module-level :func:`run` used to
# build a throwaway sub-Engine per call: a study script (or an explore
# generation) that *rebuilds* the same graph content paid a fresh
# ``compile_plan`` + array staging every time, even though the shared
# ``SweepCache`` already had the results.  The memo below keys engines by
# CONTENT — graph/plan hashes + params + policy — never ``id()``, so a
# rebuilt graph with identical arrays lands on the warm engine (0 new XLA
# programs, no plan recompile).  Bounded LRU; unkeyable inputs (an exotic
# ``rank_of_class`` callable, hand-rolled plan-likes) just build fresh,
# which is exactly the old behavior.

_DETACHED_ENGINES: OrderedDict = OrderedDict()
_DETACHED_LOCK = threading.Lock()
_DETACHED_CAP = 16
_DETACHED_STATS = {"hits": 0, "misses": 0}


def _params_content_key(params, nranks: Optional[int] = None):
    """Content key for a LogGPS params object, or None if unkeyable.

    Mirrors ``core.sensitivity._params_memo_key``: an opaque
    ``rank_of_class`` callable is keyed by the rank→rank class matrix it
    computes (cached on the instance under ``_class_matrix_bytes``, the
    same slot sensitivity uses), never by ``id()``.
    """
    if params is None:
        return ("none",)
    parts = []
    for f in dataclasses.fields(params):
        v = getattr(params, f.name)
        if f.name == "rank_of_class":
            continue
        if callable(v):
            return None
        try:
            hash(v)
        except TypeError:
            return None
        parts.append((f.name, v))
    roc = getattr(params, "rank_of_class", None)
    if roc is not None:
        if nranks is None:
            return None
        cache = getattr(params, "_class_matrix_bytes", None)
        if cache is None:
            cache = {}
            object.__setattr__(params, "_class_matrix_bytes", cache)
        cls_key = cache.get(int(nranks))
        if cls_key is None:
            from .cache import canonical_bytes
            m = np.asarray([[params.link_class(i, j)
                             for j in range(int(nranks))]
                            for i in range(int(nranks))], dtype=np.int32)
            cls_key = cache[int(nranks)] = b"".join(canonical_bytes(m))
        parts.append(("rank_of_class", cls_key))
    return (type(params).__name__, tuple(parts))


def _graphs_content_key(graphs, params):
    """Content key for everything ``Engine(graphs=...)`` accepts, or None
    when a member can't be content-addressed."""
    if isinstance(graphs, StructureBatch):
        base = graphs.base
        if base is None:
            return None
        return ("sb", graphs.content_hash(), base.content_hash())
    if isinstance(graphs, MultiPlan):
        return ("multi",) + tuple(graphs.plan_hashes)
    if isinstance(graphs, CompiledPlan):
        return ("plan", graphs.content_hash())
    if isinstance(graphs, SparsePlan):
        return None
    if isinstance(graphs, (list, tuple)):
        keys = []
        for item in graphs:
            if isinstance(item, CompiledPlan):
                keys.append(("plan", item.content_hash()))
            elif isinstance(item, (list, tuple)) and len(item) == 2:
                pk = _params_content_key(item[1],
                                         getattr(item[0], "nranks", None))
                if pk is None:
                    return None
                keys.append(("graph", graph_content_key(item[0]), pk))
            else:
                keys.append(("graph", graph_content_key(item)))
        return ("seq",) + tuple(keys)
    # a bare ExecutionGraph (anything with the build-time arrays)
    try:
        return ("graph", graph_content_key(graphs))
    except AttributeError:
        return None


def detached_engine(graphs, params, policy: "ExecPolicy") -> "Engine":
    """The content-keyed warm engine for a detached workload (building and
    memoizing one if this content was never seen).  Falls back to a fresh
    un-memoized engine when the inputs can't be content-addressed."""
    gk = _graphs_content_key(graphs, params)
    key = None
    if gk is not None:
        pk = _params_content_key(params, getattr(graphs, "nranks", None))
        if pk is not None:
            key = (gk, pk, policy.key())
    if key is None:
        return Engine(graphs, params=params, policy=policy)
    with _DETACHED_LOCK:
        eng = _DETACHED_ENGINES.get(key)
        if eng is not None:
            _DETACHED_ENGINES.move_to_end(key)
            _DETACHED_STATS["hits"] += 1
            return eng
        _DETACHED_STATS["misses"] += 1
    eng = Engine(graphs, params=params, policy=policy)
    with _DETACHED_LOCK:
        _DETACHED_ENGINES[key] = eng
        _DETACHED_ENGINES.move_to_end(key)
        while len(_DETACHED_ENGINES) > _DETACHED_CAP:
            _DETACHED_ENGINES.popitem(last=False)
    return eng


def detached_engine_stats() -> dict:
    """Hit/miss counters + live size of the detached-engine memo."""
    with _DETACHED_LOCK:
        return {**_DETACHED_STATS, "size": len(_DETACHED_ENGINES)}


class Engine:
    """Compile once, evaluate any populated combination of G×K×S axes.

    ``graphs``: an ``ExecutionGraph`` (with ``params``), a
    :class:`~repro.sweep.compile.CompiledPlan`, a
    :class:`~repro.sweep.compile.MultiPlan`, a
    :class:`~repro.sweep.compile.StructureBatch` (its base plan is bound
    and the batch becomes the engine's default ``structure=`` axis), a
    :class:`~repro.sweep.compile.SparsePlan`, or a sequence of plans /
    graphs / (graph, params) pairs (packed into a MultiPlan, members
    retained so per-graph cost extras can be patched).

    An ``ExecutionGraph`` whose *estimated* dense envelope exceeds
    :data:`MAX_DENSE_BYTES` is never laid out dense: the engine warns
    once, compiles it with :func:`~repro.sweep.compile.compile_sparse`,
    and switches the policy to ``backend="sparse"`` (raising instead if
    ``dtype="float32"`` pinned the pallas contract).

    The engine stages plan tensors per backend once, resolves each run's
    populated axes, and dispatches through the shared jit cells of
    ``repro.sweep.engine._get_forward`` — the *same* compiled programs the
    legacy engines used for their combinations, which is what makes the
    legacy shims bit-identical by construction.
    """

    MAX_DENSE_BYTES = 256 << 20

    def __init__(self, graphs=None, params=None,
                 policy: Optional[ExecPolicy] = None, names=None):
        self.policy = (policy if policy is not None else ExecPolicy()) \
            .validate()
        # dense-envelope guard resolution: policy field, then the
        # REPRO_MAX_DENSE_BYTES environment variable, then the class
        # attribute.  Overrides land on the *instance* so class-level
        # monkeypatches (benchmarks) and subclass overrides keep working.
        mdb = self.policy.max_dense_bytes
        if mdb is None:
            env = os.environ.get("REPRO_MAX_DENSE_BYTES", "")
            mdb = int(env) if env else None
        if mdb is not None:
            self.MAX_DENSE_BYTES = int(mdb)
        plan = multi = plans = None
        sparse = structure = None
        if isinstance(graphs, StructureBatch):
            structure = graphs
            if structure.base is None:
                raise ValueError(
                    "StructureBatch carries no base plan — build it with "
                    "CompiledPlan.patch_structure() or "
                    "StructureBatch.from_plans()")
            if names is not None:
                structure = dataclasses.replace(structure,
                                                names=tuple(names))
            plan = structure.base
        elif isinstance(graphs, MultiPlan):
            multi = graphs
        elif isinstance(graphs, CompiledPlan):
            plan = graphs
        elif isinstance(graphs, SparsePlan):
            sparse = graphs
        elif isinstance(graphs, (list, tuple)):
            if not graphs:
                raise ValueError("need at least one graph or plan")
            plans = []
            for item in graphs:
                if isinstance(item, CompiledPlan):
                    plans.append(item)
                elif isinstance(item, (list, tuple)) and len(item) == 2:
                    plans.append(compile_plan(item[0], item[1]))
                else:
                    plans.append(compile_plan(item, params))
            multi = pack_plans(plans)
        elif graphs is not None:
            if self.policy.backend == "sparse":
                sparse = compile_sparse(graphs, params)
            else:
                est = estimate_dense_bytes(graphs)
                if est > self.MAX_DENSE_BYTES:
                    # the dense materialization IS the memory cliff — the
                    # switch must happen before compile_plan, off degree
                    # statistics alone
                    if self.policy.dtype == "float32":
                        raise ValueError(
                            f"graph's padded dense envelope needs "
                            f"~{est >> 20} MiB (> "
                            f"{self.MAX_DENSE_BYTES >> 20} MiB) and "
                            "dtype='float32' pins the pallas contract — "
                            "pass backend='sparse' (float64) explicitly, "
                            "or raise Engine.MAX_DENSE_BYTES")
                    warnings.warn(
                        f"graph's padded dense envelope needs ~{est >> 20} "
                        f"MiB (> {self.MAX_DENSE_BYTES >> 20} MiB); "
                        "auto-switching to backend='sparse' (compact slot "
                        "lists, T/λ bit-identical to segment)",
                        RuntimeWarning, stacklevel=2)
                    self.policy = self.policy.replace(backend="sparse")
                    sparse = compile_sparse(graphs, params)
                else:
                    plan = compile_plan(graphs, params)
        else:
            raise ValueError("need a graph, plan(s), or a MultiPlan")
        self.plan = plan
        self.multi = multi
        self.plans = plans            # member plans (cost patching); or None
        self.sparse = sparse          # SparsePlan; or None until first use
        self.structure = structure    # default StructureBatch; or None
        self.params = params
        if multi is not None:
            self.names = tuple(names) if names else tuple(
                f"g{i}" for i in range(multi.G))
            if len(self.names) != multi.G:
                raise ValueError(
                    f"{len(self.names)} names for {multi.G} graphs")
        else:
            self.names = None
        self.calls = 0                # compiled dispatches (cache hits excluded)
        self._dev: dict = {}
        self._occupancy: Optional[float] = None   # slot-occupancy memo

    # -- introspection -------------------------------------------------------
    @property
    def G(self) -> Optional[int]:
        return None if self.multi is None else self.multi.G

    @property
    def nclass(self) -> int:
        if self.multi is not None:
            return self.multi.nclass
        if self.plan is not None:
            return self.plan.nclass
        return self.sparse.nclass

    def _sparse_plan(self) -> SparsePlan:
        """The engine's sparse layout, derived lazily from a bound dense
        plan on the first ``backend="sparse"`` run."""
        if self.sparse is None:
            if self.plan is None:
                raise ValueError(
                    "the sparse backend evaluates one graph at a time — "
                    "build a single-graph Engine (or one per MultiPlan "
                    "member)")
            self.sparse = SparsePlan.from_plan(self.plan)
        return self.sparse

    def _arrays(self, kind: str) -> tuple:
        if kind not in self._dev:
            if kind in ("sparse", "indeg"):
                sp = self._sparse_plan()
                self._dev[kind] = _eng._stage_arrays(
                    sp, kind, self.MAX_DENSE_BYTES)
                _DENSE_BYTES.set(float(sp.sparse_bytes()), view="sparse")
            else:
                plan0 = self.plan if self.multi is None else self.multi
                if plan0 is None:
                    raise ValueError(
                        "this engine compiled its graph sparse-only (dense "
                        "envelope over MAX_DENSE_BYTES) — only "
                        "backend='sparse' can evaluate it")
                self._dev[kind] = _eng._stage_arrays(
                    plan0, kind, self.MAX_DENSE_BYTES)
                _DENSE_BYTES.set(float(plan0.dense_bytes()), view=kind)
        return self._dev[kind]

    # -- normalization -------------------------------------------------------
    def _batches(self, scenarios) -> list:
        """One ScenarioBatch per graph (broadcast a single one)."""
        if self.multi is None:
            if not isinstance(scenarios, ScenarioBatch):
                raise ValueError("a single-graph engine takes one "
                                 "ScenarioBatch")
            if scenarios.nclass != self.nclass:
                raise ValueError(
                    f"scenario batch has {scenarios.nclass} classes, "
                    f"graph has {self.nclass}")
            return [scenarios]
        if isinstance(scenarios, ScenarioBatch):
            batches = [scenarios] * self.multi.G
        else:
            batches = list(scenarios)
        if len(batches) != self.multi.G:
            raise ValueError(f"{len(batches)} scenario batches for "
                             f"{self.multi.G} graphs")
        S = batches[0].S
        for b in batches:
            if b.nclass != self.nclass:
                raise ValueError(f"scenario batch has {b.nclass} classes, "
                                 f"packed graphs have {self.nclass}")
            if b.S != S:
                raise ValueError("per-graph scenario batches must share S "
                                 f"(got {b.S} vs {S})")
        return batches

    def _check_view(self, cb: CostBatch, backend: str) -> None:
        """A view-limited patch (``patch_costs(views=...)``) carries real
        costs only in one backend's constants — refuse the other."""
        v_b = cb.vconst.strides[0] != 0
        e_b = cb.econst.strides[0] != 0
        if (backend == "segment" and e_b and not v_b) or \
                (backend == "pallas" and v_b and not e_b):
            raise ValueError(
                f"cost batch was patched for the "
                f"{'edge' if e_b else 'vertex'} view only and cannot run "
                f"on backend={backend!r}")

    def _costs(self, costs, backend: str) -> Optional[list]:
        """Normalize the K axis to a per-graph list of validated
        CostBatches (repadded onto the MultiPlan envelope when G is
        populated); None when the axis is unpopulated."""
        if costs is None:
            return None
        views = ("vertex",) if backend == "segment" else ("edge",)
        if self.multi is None:
            cb = costs
            if not isinstance(cb, CostBatch):
                # raw [K, ne] extras: patch only the view this backend
                # evaluates (half the host work of a full patch)
                cb = self.plan.patch_costs(cb, views=views)
            if cb.vconst.shape[1:] != self.plan.vconst.shape:
                raise ValueError(
                    f"cost block envelope {cb.vconst.shape[1:]} does not "
                    f"match the plan's {self.plan.vconst.shape} — "
                    "patch_costs() the same plan this engine compiled")
            if cb.plan_hash is not None and \
                    cb.plan_hash != self.plan.content_hash():
                # bucketing makes DISTINCT graphs share envelopes, so the
                # shape check alone cannot catch a foreign batch
                raise ValueError(
                    "cost batch was patched from a different plan than "
                    "this engine compiled (same envelope, different "
                    "content) — patch_costs() the engine's own plan")
            self._check_view(cb, backend)
            return [cb]
        if isinstance(costs, CostBatch):
            raise ValueError(
                "a multi-graph engine needs one cost batch (or [K, ne] "
                "extras array) per graph — got a single CostBatch; pass a "
                f"length-{self.multi.G} sequence")
        cbs = list(costs)
        if len(cbs) != self.multi.G:
            raise ValueError(f"{len(cbs)} cost batches for "
                             f"{self.multi.G} graphs")
        env = self.multi.vsrc.shape[1:]          # (nlv_p, Vmax, Dmax)
        Emax = self.multi.esrc.shape[2]
        out = []
        for i, cb in enumerate(cbs):
            if not isinstance(cb, CostBatch):
                if self.plans is None:
                    raise ValueError(
                        "raw cost extras need the member plans; construct "
                        "the Engine from plans/graphs (not a bare "
                        "MultiPlan), or pass per-graph CostBatches")
                cb = self.plans[i].patch_costs(cb, views=views)
            if cb.plan_hash is not None and \
                    cb.plan_hash != self.multi.plan_hashes[i]:
                raise ValueError(
                    f"cost batch {i} was patched from a different plan "
                    f"than graph {i} of this MultiPlan — patch_costs() "
                    "the member plan it rides")
            self._check_view(cb, backend)
            out.append(cb.repad(*env, Emax))
        K = out[0].K
        if any(cb.K != K for cb in out):
            raise ValueError("per-graph cost batches must share K (got "
                             f"{[cb.K for cb in out]})")
        return out

    def _structure(self, structure) -> Optional[StructureBatch]:
        """Normalize the B axis: an explicit batch wins, else the engine's
        bound default (an Engine built from a StructureBatch); validated
        against the staged base plan the variants ride."""
        sb = structure if structure is not None else self.structure
        if sb is None:
            return None
        if not isinstance(sb, StructureBatch):
            raise ValueError(
                "structure must be a StructureBatch — mint one with "
                "CompiledPlan.patch_structure() or "
                "StructureBatch.from_plans()")
        if self.multi is not None:
            raise ValueError(
                "structure blocks and a multi-graph engine cannot combine "
                "(pick one variant axis: pack plans into a MultiPlan OR "
                "batch them with StructureBatch.from_plans)")
        if self.plan is None:
            raise ValueError(
                "this engine compiled its graph sparse-only; structure "
                "batching needs a dense base plan")
        if sb.vsrc.shape[1:] != self.plan.vsrc.shape:
            raise ValueError(
                f"structure block envelope {sb.vsrc.shape[1:]} does not "
                f"match the plan's {self.plan.vsrc.shape} — patch or "
                "re-batch onto the plan this engine compiled")
        if sb.plan_hash is not None and \
                sb.plan_hash != self.plan.content_hash():
            # bucketing makes DISTINCT graphs share envelopes, so the
            # shape check alone cannot catch a foreign batch; from_plans
            # batches (plan_hash None) materialize every tensor per
            # variant, so the envelope check alone is sound for them
            raise ValueError(
                "structure batch was patched from a different plan than "
                "this engine compiled (same envelope, different content) "
                "— patch_structure() the engine's own plan")
        return sb

    # -- the run -------------------------------------------------------------
    def run(self, query=None, *, scenarios=None, costs=None, structure=None,
            outputs=None, compute_lam=None, backend=None, shard=None,
            shard_axis=None, use_cache: bool = True,
            policy: Optional[ExecPolicy] = None) -> Result:
        """Evaluate one query; returns a numpy-backed :class:`Result`.

        ``query`` may be a :class:`Query`, a bare ``ScenarioBatch`` (or
        per-graph sequence), or None with keyword axes.  ``policy``
        replaces the engine's policy wholesale for this run; the
        individual ``backend``/``shard``/``shard_axis`` keywords override
        single fields.  ``compute_lam`` is the legacy spelling of
        ``outputs`` (True → T/λ/ρ, False → T only).
        """
        if isinstance(query, Query):
            if query.graphs is not None:
                sub = detached_engine(
                    query.graphs,
                    (query.params if query.params is not None
                     else self.params),
                    policy if policy is not None else self.policy)
                return sub.run(dataclasses.replace(query, graphs=None,
                                                   params=None),
                               structure=structure, outputs=outputs,
                               compute_lam=compute_lam, backend=backend,
                               shard=shard, shard_axis=shard_axis,
                               use_cache=use_cache)
            scenarios = query.scenarios if scenarios is None else scenarios
            costs = query.costs if costs is None else costs
            structure = query.structure if structure is None else structure
            outputs = query.outputs if outputs is None else outputs
        elif query is not None:
            if scenarios is not None:
                raise ValueError("pass scenarios positionally or by "
                                 "keyword, not both")
            scenarios = query
        if scenarios is None:
            raise ValueError("a query needs scenarios")

        pol = (policy if policy is not None else self.policy)
        over = {k: v for k, v in (("backend", backend), ("shard", shard),
                                  ("shard_axis", shard_axis))
                if v is not None}
        if over:
            pol = dataclasses.replace(pol, **over)
        pol.validate()

        if compute_lam is not None:
            # the legacy flag is an explicit ask — it wins even over a
            # Query's (defaulted) outputs tuple, so run(q, compute_lam=
            # False) never silently pays for the λ program
            outputs = _OUTPUTS if compute_lam else ("T",)
        elif outputs is None:
            outputs = _OUTPUTS
        outputs = tuple(outputs)
        bad = set(outputs) - set(_OUTPUTS)
        if bad or not outputs:
            raise ValueError(f"outputs must name a subset of {_OUTPUTS}, "
                             f"got {outputs}")
        want_lam = "lam" in outputs or "rho" in outputs
        fd = want_lam and pol.lam == "fd"
        kind = pol.backend

        sb = self._structure(structure)
        has_B = sb is not None
        if kind == "sparse":
            if has_B:
                raise ValueError("the sparse backend does not take "
                                 "structure blocks yet — use "
                                 "backend='segment'")
            if costs is not None:
                raise ValueError("the sparse backend does not take cost "
                                 "blocks yet — use backend='segment'")
            if self.multi is not None:
                raise ValueError("the sparse backend evaluates one graph "
                                 "at a time — build a single-graph Engine "
                                 "per member")
            if pol.shard:
                raise ValueError("the sparse backend does not shard yet")
        elif self.plan is None and self.multi is None:
            raise ValueError(
                "this engine compiled its graph sparse-only (dense "
                f"envelope over MAX_DENSE_BYTES); backend={kind!r} cannot "
                "evaluate it — run with backend='sparse'")
        if has_B and pol.shard:
            raise ValueError("sharding a structure-batched query is not "
                             "supported yet")
        if has_B and costs is not None and sb.plan_hash is None:
            raise ValueError(
                "a from_plans() StructureBatch cannot combine with cost "
                "blocks — its variants share no base plan to patch costs "
                "into (use patch_structure() variants for B×K studies)")

        cong = pol.congestion == "fixed_point"
        if cong:
            if has_B:
                raise ValueError("congestion='fixed_point' populates the "
                                 "S and K axes only — no structure blocks "
                                 "yet (run variants through separate "
                                 "engines)")
            if self.multi is not None:
                raise ValueError("congestion='fixed_point' populates the "
                                 "S and K axes only — no multi-graph G "
                                 "axis (build one engine per graph)")
            if pol.shard:
                raise ValueError("congestion='fixed_point' does not shard "
                                 "yet (the while_loop lanes must stay in "
                                 "lockstep on one device)")
            if self.params is None:
                raise ValueError(
                    "congestion needs the engine's bound LogGPS params "
                    "for the per-class (α, β) congestion registry — "
                    "construct Engine(graph_or_plan, params=...)")

        with _span("sweep.canonicalize"):
            batches = self._batches(scenarios)
        if costs is not None:
            with _span("sweep.cost_patch", backend=kind):
                cbs = self._costs(costs, kind)
        else:
            cbs = None
        has_G = self.multi is not None
        has_K = cbs is not None
        cache = pol.cache if use_cache else None
        axes_s = ("G" if has_G else "") + ("B" if has_B else "") \
            + ("K" if has_K else "") + "S"

        # -- cache lookup ----------------------------------------------------
        key = None
        if cache is not None:
            with _span("sweep.cache_lookup", axes=axes_s):
                fields = (_eng._SEG_COST_FIELDS if kind == "segment"
                          else _eng._PAL_COST_FIELDS)
                cost_hash = None
                if has_K:
                    # hash only the tensors this backend consumes: a
                    # raw-extras run and a full patch_costs() of the same
                    # extras collide
                    hashes = [cb.content_hash(fields=fields) for cb in cbs]
                    cost_hash = (hashes[0] if len(hashes) == 1
                                 else hashlib.sha1(
                                     "|".join(hashes).encode()).hexdigest())
                structure_hash = None
                if has_B:
                    # like costs: hash only the view this backend consumes
                    sfields = (_eng._SEG_STRUCT_FIELDS if kind == "segment"
                               else _eng._PAL_STRUCT_FIELDS)
                    structure_hash = sb.content_hash(fields=sfields)
                ph = (self._sparse_plan().content_hash()
                      if kind == "sparse"
                      else self.plan.content_hash() if not has_G
                      else self.multi.content_hash())
                # the sparse f32 kernel flavor returns different floats
                # than the f64 forward — it must never share cache entries
                kkey = ("sparse_pallas" if kind == "sparse"
                        and pol.dtype == "float32"
                        else "congestion" if cong else kind)
                congestion_hash = None
                if cong:
                    ch = hashlib.sha1(b"congestion-v1|")
                    ch.update(self.plan.link_hash().encode())
                    ch.update(repr((tuple(self.params.alpha_full),
                                    tuple(self.params.beta_full),
                                    int(pol.max_iters),
                                    float(pol.tol))).encode())
                    congestion_hash = ch.hexdigest()
                key = query_key(ph, batches, want_lam, kkey, cost_hash,
                                lam_mode=pol.lam if want_lam else "exact",
                                fd_eps=pol.fd_eps,
                                structure_hash=structure_hash,
                                congestion_hash=congestion_hash)
                hit = cache.get(key, patched=has_K or has_B)
            if hit is not None:
                _QUERIES.inc(backend=kind, axes=axes_s, cache="hit")
                # copy the arrays (callers may mutate results in place) and
                # restamp scenarios/names: the key is content-addressed, so
                # the hit may come from an engine naming the plans
                # differently
                return _copy(hit,
                             scenarios=(batches[0] if not has_G
                                        else batches),
                             names=(_variant_names(sb) if has_B
                                    else self.names),
                             from_cache=True)

        _QUERIES.inc(backend=kind, axes=axes_s,
                     cache="miss" if cache is not None else "off")
        res = self._run_uncached(batches, cbs, sb, want_lam, fd, kind, pol)
        if cache is not None:
            # store a private copy: caller mutation of the returned arrays
            # must never poison later cache hits
            cache.put(key, _copy(res))
        return res

    # -- the uncached forward ------------------------------------------------
    def _run_uncached(self, batches, cbs, sb, want_lam, fd, kind,
                      pol: ExecPolicy) -> Result:
        has_G = self.multi is not None
        has_K = cbs is not None
        has_B = sb is not None
        cong = pol.congestion == "fixed_point"
        iters = None
        sparse = kind == "sparse"
        sp = self._sparse_plan() if sparse else None
        G = self.multi.G if has_G else None
        K = cbs[0].K if has_K else None
        Kp = _bucket(K, lo=1) if has_K else None
        B = sb.B if has_B else None
        Bp = _bucket(B, lo=1) if has_B else None
        nc = self.nclass
        S = batches[0].S
        h = float(pol.fd_eps)

        def expand(L, gs):
            """(nc+1)× values grid: base rows then one +h·e_c block per
            class — λ_c recovered as a forward difference."""
            if not fd:
                return L, gs
            blocks = [L] + [L + h * np.eye(nc)[c] for c in range(nc)]
            return np.concatenate(blocks), np.concatenate([gs] * (nc + 1))

        Sext = S * (nc + 1) if fd else S
        Sp = _bucket(Sext, lo=4)
        with _span("sweep.stage", backend=kind):
            if not has_G:
                L0, G0 = expand(batches[0].L, batches[0].gscale)
                Lmat = np.repeat(L0[-1:], Sp, axis=0)
                Lmat[:Sext] = L0
                GSmat = np.repeat(G0[-1:], Sp, axis=0)
                GSmat[:Sext] = G0
            else:
                Lmat = np.empty((G, Sp, nc))
                GSmat = np.empty((G, Sp, nc))
                for i, b in enumerate(batches):
                    L0, G0 = expand(b.L, b.gscale)
                    Lmat[i, :Sext] = L0
                    Lmat[i, Sext:] = L0[-1]
                    GSmat[i, :Sext] = G0
                    GSmat[i, Sext:] = G0[-1]

        # -- envelope occupancy: padding-waste gauges ------------------------
        plan0 = sp if sparse else (self.plan if not has_G else self.multi)
        if self._occupancy is None:
            vf = sp.valid if sparse else plan0.valid_flat
            self._occupancy = float(np.count_nonzero(vf) / vf.size)
        _OCCUPANCY.set(self._occupancy, axis="slots")
        # the float64 sparse forward's level step (compile.SparsePlan.step)
        step = (sp.step if sparse and pol.dtype != "float32" else None)
        if step == "indeg":
            _OCCUPANCY.set(sp.ne / (sp.nlevels * sp.Vmax_lv * sp.Dmax),
                           axis="indeg")
        _OCCUPANCY.set(Sext / Sp, axis="S")
        if has_K:
            _OCCUPANCY.set(K / Kp, axis="K")
        if has_B:
            _OCCUPANCY.set(B / Bp, axis="B")

        # -- device sharding: any populated axis -----------------------------
        axis = pol.shard_axis
        if axis == "auto":
            axis = "G" if has_G else "S"
        mesh = None
        if pol.shard:
            if axis == "G" and not has_G:
                raise ValueError("shard_axis='G' needs a multi-graph "
                                 "engine (no graph axis is populated)")
            if axis == "K" and not has_K:
                raise ValueError("shard_axis='K' needs a cost batch "
                                 "(no candidate axis is populated)")
            size = {"G": G, "K": Kp, "S": Sp}[axis]
            ndev = _eng._resolve_shard(pol.shard, size)
            mesh = _eng._device_mesh(ndev) if ndev else None

        # -- cost-tensor staging: only genuinely per-candidate tensors ride
        #    the vmapped K axis; broadcast (unpatched) fields pass one
        #    block, reusing the engine's staged device arrays -----------------
        seg = kind == "segment"
        want_lam_compiled = want_lam and not fd
        names_f = _eng._SEG_COST_FIELDS if seg else _eng._PAL_COST_FIELDS
        pos = _eng._SEG_COST_POS if seg else _eng._PAL_COST_POS
        f32 = {"econst": np.float32, "egap": np.float32,
               "elat": np.float32, "egclass": None}
        kaxes = None
        cost_arrs = ()
        if has_K:
            padded = [cb.padded(Kp) for cb in cbs]
            kaxes = tuple(
                0 if any(getattr(cb, n).strides[0] != 0 for cb in padded)
                else None for n in names_f)
            if all(ax is None for ax in kaxes):   # vmap needs ≥1 batched input
                kaxes = (0,) + kaxes[1:]

        jnp = _eng._jax().numpy

        def stage_costs(staged):
            out = []
            for j, (n, ax) in enumerate(zip(names_f, kaxes)):
                dtype = None if seg else f32[n]
                if not has_G:
                    a = getattr(padded[0], n)
                    if ax is None:
                        a = a[0]
                        if _eng._same_buffer(a, getattr(self.plan, n)):
                            out.append(staged[pos[n]])
                            continue
                    out.append(jnp.asarray(
                        np.ascontiguousarray(a) if dtype is None
                        else np.asarray(a, dtype=dtype)))
                    continue
                if ax is None:
                    # unpatched in every graph ⇒ the MultiPlan's own cost
                    # tensor (member blocks are its repadded rows)
                    out.append(staged[pos[n]])
                    continue
                blocks = [np.broadcast_to(getattr(cb, n)[:1],
                                          (Kp,) + getattr(cb, n).shape[1:])
                          if getattr(cb, n).strides[0] == 0
                          else getattr(cb, n) for cb in padded]
                # segment composes G outermost ([G, K, ...]); pallas vmaps
                # K over the graph-batched kernel ([K, G, ...])
                arr = np.stack(blocks, axis=0 if seg else 1)
                out.append(jnp.asarray(
                    arr if dtype is None else arr.astype(dtype)))
            return tuple(out)

        # -- structure-tensor staging: only genuinely per-variant tensors
        #    ride the vmapped B axis (patch_structure materializes just
        #    vsrc/vmaskd/esrc/emask; from_plans batches every field) --------
        saxes = sbp = None
        if has_B:
            sbp = sb.padded(Bp)
            spos = _eng._SEG_STRUCT_POS if seg else _eng._PAL_STRUCT_POS
            ax = [None] * (_eng._N_PLAN_ARGS + 2)
            for n, p in spos.items():
                if getattr(sbp, n).strides[0] != 0:
                    ax[p] = 0
            if not seg and (sbp.emask.strides[0] != 0
                            or sbp.edstl.strides[0] != 0):
                ax[0] = 0              # per-variant 0/−inf indicator
            if all(a is None for a in ax):     # vmap needs ≥1 batched input
                ax[spos["vsrc" if seg else "esrc"]] = 0
            saxes = tuple(ax)
        f32_struct = {"econst", "egap", "elat", "vcost_lv"}

        def stage_structure(args):
            args = list(args)
            spos = _eng._SEG_STRUCT_POS if seg else _eng._PAL_STRUCT_POS
            for n, p in spos.items():
                if saxes[p] != 0:
                    continue
                a = getattr(sbp, n)
                if a.strides[0] == 0:          # forced-batched fallback
                    a = np.broadcast_to(a[:1], (Bp,) + a.shape[1:])
                if not seg and n in f32_struct:
                    a = np.asarray(a, dtype=np.float32)
                args[p] = jnp.asarray(np.ascontiguousarray(a))
            if not seg and saxes[0] == 0:
                # the pallas scatter indicator is derived structure:
                # rebuild it per variant from the patched masks
                em = sbp.emask
                edl = np.broadcast_to(sbp.edstl, em.shape)
                nlv, Emax = em.shape[1:]
                A = np.full((Bp, nlv, self.plan.Vmax, Emax), -_eng.BIG,
                            dtype=np.float32)
                bb, lv, sl = np.nonzero(em)
                A[bb, lv, edl[bb, lv, sl], sl] = 0.0
                args[0] = jnp.asarray(A)
            return tuple(args)

        fwd_kw = {}
        if kaxes is not None:
            fwd_kw["costs"] = kaxes
        if saxes is not None:
            fwd_kw["structure"] = saxes
        if mesh is not None and axis != ("G" if has_G else "S"):
            fwd_kw["shard_axis"] = axis

        # watcher bracketing: any growth in the XLA program count across
        # this dispatch is attributed to this query's signature (the span
        # waits for jax's async dispatch, so the window covers compile +
        # execute)
        axes_s = ("G" if has_G else "") + ("B" if has_B else "") \
            + ("K" if has_K else "") + "S"
        if sparse:
            env_s = f"ne{sp.esrc_slot.shape[0]}v{sp.vcost.shape[0]}"
            trips = sp.level_ptr.shape[0] - 1
        else:
            nlv_p, Vmax, Dmax = plan0.vsrc.shape[-3:]
            env_s = f"{nlv_p}x{Vmax}x{Dmax}"
            trips = nlv_p
        # the forward that runs: on the sparse backend dtype="float32"
        # pins the Pallas slot-list kernel, float64 the bit-exact jnp one
        view = ("sparse_pallas" if sparse and pol.dtype == "float32"
                else kind)
        n_prog0 = _WATCHER.programs()
        t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        with _span("sweep.execute", backend=kind, axes=axes_s) as ex:
            # phases, recorded on the span (not as child spans, which would
            # cut its self time): staging the inputs, the forward call, the
            # wait for the device, the copies back to the host
            t_stage = time.perf_counter_ns()
            with (_eng._jax().enable_x64() if sparse or seg
                  else contextlib.nullcontext()):
                if sparse:
                    arrs = self._arrays("sparse")
                    # both views take the same staged arrays — the kernel
                    # core casts at the (max,+) reduction boundary
                    dims = (sp.Emax_lv, sp.Vmax_lv)
                    if step == "indeg":
                        arrs = arrs + self._arrays("indeg")
                        dims = dims + (sp.Dmax,)
                    fwd = _eng._get_forward(view, want_lam_compiled,
                                            sparse_dims=dims)
                    args = arrs + (jnp.asarray(Lmat), jnp.asarray(GSmat))
                elif seg:
                    arrs = self._arrays("congestion" if cong else "segment")
                    if has_K:
                        cost_arrs = stage_costs(arrs)
                        args = arrs[:2] + cost_arrs + arrs[7:]
                    else:
                        args = arrs
                    if has_B:
                        args = stage_structure(args)
                    if cong:
                        pp = self.params
                        fwd = _eng._get_forward(
                            "congestion", want_lam_compiled, costs=kaxes)
                        args = args + (
                            jnp.asarray(np.asarray(pp.alpha_full,
                                                   dtype=np.float64)),
                            jnp.asarray(np.asarray(pp.beta_full,
                                                   dtype=np.float64)),
                            jnp.asarray(np.int32(pol.max_iters)),
                            jnp.asarray(np.float64(pol.tol)))
                    else:
                        fwd = _eng._get_forward(
                            "segment", want_lam_compiled, has_G, False,
                            mesh, **fwd_kw)
                    args = args + (jnp.asarray(Lmat), jnp.asarray(GSmat))
                else:
                    arrs = self._arrays("pallas")
                    if has_K:
                        cost_arrs = stage_costs(arrs)
                        args = arrs[:3] + cost_arrs + arrs[7:]
                    else:
                        args = arrs
                    if has_B:
                        args = stage_structure(args)
                    fwd = _eng._get_forward("pallas", want_lam_compiled,
                                            has_G, False, mesh, **fwd_kw)
                    args = args + (jnp.asarray(Lmat, dtype=jnp.float32),
                                   jnp.asarray(GSmat, dtype=jnp.float32))
                t_call = time.perf_counter_ns()
                with (_span("sweep.congestion_fixed_point",
                            max_iters=int(pol.max_iters)) if cong
                      else contextlib.nullcontext()):
                    out = fwd(*args)
                # the copies to the host queue behind the forward, so the
                # wait below puts no round trip before them.  The per-call
                # inputs are freed once the call returns and the outputs
                # once copied, inside this span: their frees are the
                # forward's host time, not the caller's
                for a in out:
                    a.copy_to_host_async()
                del args
                t_ret = time.perf_counter_ns()
                _eng._jax().block_until_ready(out)
                t_ready = time.perf_counter_ns()
                T, lam = out[:2]
                ran = _ran_on(T)
                if cong:
                    iters = np.asarray(out[2])
                del out
                if seg:
                    T = np.asarray(T)
                    lam = np.asarray(lam)
                else:
                    T = np.asarray(T).astype(np.float64)
                    lam = np.asarray(lam).astype(np.float64)
                if not (sparse or seg) and has_G and has_K:
                    T = T.swapaxes(0, 1)          # [K, G, ...] → [G, K, ...]
                    lam = lam.swapaxes(0, 1)
                t_done = time.perf_counter_ns()
            # levels: the graph's (the longest graph's in a batch), the
            # work; trips: the loop's bucketed trip count, what it costs
            ex.set(stage_ns=t_call - t_stage, dispatch_ns=t_ret - t_call,
                   wait_ns=t_ready - t_ret, readback_ns=t_done - t_ready,
                   levels=(int(plan0.nlevels.max()) if has_G
                           else plan0.nlevels),
                   view=view, trips=trips,
                   **({"step": step} if step else {}))
        _WATCHER.attribute(
            n_prog0, time.perf_counter() - t0, t0_ns=t0_ns,
            backend=kind, axes=axes_s,
            lam=("exact" if want_lam_compiled else
                 "fd" if fd else "none"),
            envelope=env_s, S=Sp,
            **({"K": Kp} if has_K else {}), **({"G": G} if has_G else {}),
            **({"B": Bp} if has_B else {}))
        self.calls += 1

        # -- slice padding, reduce fd, derive ρ ------------------------------
        idx = ((slice(None),) if has_G else ()) \
            + ((slice(0, B),) if has_B else ()) \
            + ((slice(0, K),) if has_K else ()) + (slice(0, Sext),)
        T = T[idx]
        if iters is not None:
            iters = iters[idx]
            if fd:
                # fd expands scenarios (nc+1)×; each expanded lane ran its
                # own fixed point — report the base rows' counts
                iters = iters.reshape(
                    iters.shape[:-1] + (nc + 1, S))[..., 0, :]
            for v in iters.ravel():
                _CONGESTION_ITERS.observe(float(v))
        if want_lam_compiled:
            lam = lam[idx]
        if want_lam:
            # fd implies want_lam, so the reduction nests under the span
            with _span("sweep.lam_backtrace", mode=pol.lam):
                if fd:
                    Tr = T.reshape(T.shape[:-1] + (nc + 1, S))
                    T = Tr[..., 0, :]
                    lam = np.moveaxis(
                        (Tr[..., 1:, :] - T[..., None, :]) / h, -2, -1)
                if not has_G:
                    Lb = batches[0].L
                    if has_K:
                        Lb = Lb[None]
                else:
                    Lb = np.stack([b.L for b in batches])
                    if has_K:
                        Lb = Lb[:, None]
                rho = np.where(T[..., None] > 0,
                               Lb * lam / np.maximum(T[..., None], 1e-300),
                               0.0)
        else:
            lam, rho = None, None
        axes = (("G",) if has_G else ()) + (("B",) if has_B else ()) \
            + (("K",) if has_K else ()) + ("S",)
        # np.array: np.asarray of a jax buffer is a read-only view; results
        # must be writable (and consistent with the writable cache-hit copies)
        return Result(T=np.array(T),
                      lam=None if lam is None else np.array(lam),
                      rho=rho, axes=axes,
                      scenarios=batches[0] if not has_G else batches,
                      backend=kind,
                      names=_variant_names(sb) if has_B else self.names,
                      lam_mode=pol.lam if want_lam else "exact",
                      congestion_iters=(None if iters is None
                                        else np.array(iters)),
                      dtype=ran[0], platform=ran[1], devices=ran[2])


def run(query: Query, policy: Optional[ExecPolicy] = None,
        params=None) -> Result:
    """One-shot declarative evaluation: compile ``query.graphs``, run,
    return the :class:`Result`.  Engines are memoized by *content*
    (:func:`detached_engine`): re-running a query whose graphs were rebuilt
    with identical arrays reuses the warm engine — no plan recompile, 0 new
    XLA programs — so one-shot calls in a loop cost what a kept-warm
    :class:`Engine` costs."""
    if query.graphs is None:
        raise ValueError("a detached run() needs query.graphs")
    eng = detached_engine(
        query.graphs,
        query.params if query.params is not None else params,
        policy if policy is not None else ExecPolicy())
    return eng.run(dataclasses.replace(query, graphs=None, params=None))
