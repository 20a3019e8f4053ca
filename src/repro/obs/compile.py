"""CompileWatcher — supported XLA-recompile accounting for the sweep engine.

``bench_sweep`` used to detect recompiles by reaching into a jitted
forward's ``_cache_size()`` by hand; this module promotes that trick into
an API both bench and production share, so "did this query compile a new
program?" has exactly one definition.

The engine's compiled forwards live in ``repro.sweep.engine._FWD_CACHE``
(one jitted fn per (kind, want_lam, multi, fused, mesh, costs-signature,
shard_axis) cell); each fn exposes ``_cache_size()`` — the number of XLA
programs JAX has built for it across input shapes.  A watcher sums those
counts over its cells (all live cells by default) and attributes any
growth across a dispatch to the query that triggered it:

    w = CompileWatcher()
    with w.watch("warm-rerun") as rec:
        eng.run(q)
    assert rec.new_programs == 0          # warm path must not recompile

``Engine.run`` itself calls :data:`WATCHER` ``.attribute(...)`` around
every device dispatch, stamping new compiles with the query's backend /
axes / envelope signature, bumping the ``sweep_compiles_total`` counter
and ``sweep_compile_seconds`` histogram, and emitting a retrospective
``sweep.compile`` span.

``repro.sweep.engine`` is imported lazily (inside functions only):
``sweep.cache`` and ``sweep.api`` import ``repro.obs`` at module top, so
a top-level import here would cycle.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Optional

from . import metrics as _metrics
from . import trace as _trace

COMPILES = _metrics.counter(
    "sweep_compiles_total",
    "New XLA programs built by sweep forward dispatches.",
    labels=("backend",))
COMPILE_SECONDS = _metrics.histogram(
    "sweep_compile_seconds",
    "Wall time of sweep dispatches that built new XLA programs.",
    labels=("backend",))


def _forward_cells() -> dict:
    """The engine's live compiled-forward cells (empty if sweep.engine
    was never imported — watching costs nothing until it is)."""
    import sys
    eng = sys.modules.get("repro.sweep.engine")
    if eng is None:
        return {}
    return dict(eng._FWD_CACHE)


def forward_cell(kind: str, want_lam: bool = False, multi: bool = False,
                 fused: bool = False, mesh=None, costs=None,
                 shard_axis: Optional[str] = None, structure=None,
                 sparse_dims=None):
    """The jitted forward for one engine cell (building it if needed) —
    for watchers scoped to a single program family, e.g. "did fd λ build
    a λ-backtrace program?".  ``structure`` (per-staged-arg vmap axes) and
    ``sparse_dims`` ((Emax_lv, Vmax_lv) window sizes, plus ``Dmax`` for
    the in-edge-view step) select the structure-batched and sparse
    cells."""
    from repro.sweep import engine as _eng
    kw = {}
    if structure is not None:
        kw["structure"] = tuple(structure)
    if sparse_dims is not None:
        kw["sparse_dims"] = tuple(sparse_dims)
    return _eng._get_forward(kind, want_lam, multi=multi, fused=fused,
                             mesh=mesh, costs=costs, shard_axis=shard_axis,
                             **kw)


def _cache_size(fn) -> int:
    try:
        return int(fn._cache_size())
    except Exception:
        return 0


@dataclasses.dataclass
class CompileEvent:
    """One dispatch that built ≥1 new XLA program."""

    signature: dict
    new_programs: int
    wall_s: float


class WatchResult:
    """Mutable result handle yielded by :meth:`CompileWatcher.watch`."""

    __slots__ = ("label", "new_programs", "wall_s")

    def __init__(self, label: Optional[str]):
        self.label = label
        self.new_programs = 0
        self.wall_s = 0.0


class CompileWatcher:
    """Counts XLA programs across engine forward cells and attributes
    growth to the dispatch that caused it.

    ``cells=None`` (the default, and what the global :data:`WATCHER`
    uses) watches every live cell; pass an explicit list of jitted
    forwards (see :func:`forward_cell`) to scope the count.
    """

    def __init__(self, cells: Optional[list] = None, max_events: int = 256):
        self._cells = list(cells) if cells is not None else None
        self._events: deque = deque(maxlen=max_events)
        self._lock = threading.Lock()

    def programs(self) -> int:
        """Total XLA programs currently compiled across watched cells."""
        cells = self._cells if self._cells is not None \
            else _forward_cells().values()
        return sum(_cache_size(fn) for fn in cells)

    def snapshot(self) -> dict:
        """Per-cell program counts keyed by the engine's cell signature
        (global scope) or positional index (explicit cells)."""
        if self._cells is not None:
            return {f"cell[{i}]": _cache_size(fn)
                    for i, fn in enumerate(self._cells)}
        return {repr(key): _cache_size(fn)
                for key, fn in _forward_cells().items()}

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def attribute(self, before: int, wall_s: float,
                  t0_ns: Optional[int] = None, **signature) -> int:
        """Compare the current program count against ``before``; if it
        grew, record a :class:`CompileEvent` carrying ``signature``, bump
        the compile metrics, and emit a ``sweep.compile`` trace span over
        the dispatch window.  Returns the number of new programs."""
        new = self.programs() - before
        if new <= 0:
            return 0
        with self._lock:
            self._events.append(CompileEvent(
                signature=dict(signature), new_programs=new,
                wall_s=float(wall_s)))
        backend = str(signature.get("backend", "unknown"))
        COMPILES.inc(new, backend=backend)
        COMPILE_SECONDS.observe(wall_s, backend=backend)
        if t0_ns is not None:
            _trace.TRACER.add_event(
                "sweep.compile", t0_ns, t0_ns + int(wall_s * 1e9),
                new_programs=new, **signature)
        return new

    @contextlib.contextmanager
    def watch(self, label: Optional[str] = None, **signature):
        """Measure a block: yields a :class:`WatchResult` whose
        ``new_programs`` / ``wall_s`` are filled in on exit.  Compiles
        are attributed (events + metrics) just like engine-internal
        dispatches."""
        rec = WatchResult(label)
        before = self.programs()
        t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec.wall_s = time.perf_counter() - t0
            sig = dict(signature)
            if label:
                sig.setdefault("label", label)
            sig.setdefault("backend", "unknown")
            rec.new_programs = self.attribute(
                before, rec.wall_s, t0_ns=t0_ns, **sig)


#: Process-global watcher over all live forward cells — what
#: ``Engine.run`` reports dispatches to.
WATCHER = CompileWatcher()
