"""Content-addressed memoization of sweep results.

Key = SHA1(compiled-plan tensors) ⊕ SHA1(scenario grid ⊕ flags): two
structurally identical graphs (however they were built) with the same
parameter grid share one entry, so re-running a study script — or the
breakpoint search re-probing a grid it has already seen — costs a hash
instead of a forward pass.  LRU-bounded and in-memory; results are small
([S] + [S, nclass] float64), the *inputs* were the expensive part.

Hashes are computed over *canonical bytes* — dtype tag + shape + C-order
buffer — never over Python object identities, so a key minted in one
process matches the same logical inputs hashed in another (a prerequisite
for sharing a cache across workers or persisting it).
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from repro.obs import metrics as _obs_metrics

_HITS = _obs_metrics.counter(
    "sweep_cache_hits_total", "Sweep result-cache hits.",
    labels=("patched",))
_MISSES = _obs_metrics.counter(
    "sweep_cache_misses_total", "Sweep result-cache misses.",
    labels=("patched",))
_EVICTIONS = _obs_metrics.counter(
    "sweep_cache_evictions_total", "Sweep result-cache LRU evictions.")


def canonical_bytes(arr) -> tuple:
    """Stable byte encoding of an array as a (header, buffer) chunk pair:
    dtype tag + shape, then the C-order data buffer.

    ``tobytes()`` alone is ambiguous — a [2, 3] and a [3, 2] array of the
    same values serialize identically — and id()-derived keys differ per
    process.  This encoding is collision-safe across shapes/dtypes and
    reproducible everywhere.  Feed the chunks to a hash incrementally
    (``for chunk in canonical_bytes(a): sha.update(chunk)``) or join them.
    """
    a = np.ascontiguousarray(arr)
    return (f"{a.dtype.str}|{a.shape}|".encode(), a.tobytes())


def _update(sha, arr) -> None:
    for chunk in canonical_bytes(arr):
        sha.update(chunk)


def result_key(plan_hash: str, scenarios, compute_lam: bool,
               backend: str, cost_hash: Optional[str] = None) -> str:
    """``cost_hash`` (a ``CostBatch.content_hash``) folds patched costs into
    the key: a plan evaluated under two different cost blocks must never
    collide, and the same patched costs minted anywhere hit."""
    sha = hashlib.sha1(b"sweep-result-v2|")
    sha.update(plan_hash.encode())
    _update(sha, scenarios.L)
    _update(sha, scenarios.gscale)
    sha.update(f"|{int(compute_lam)}|{backend}".encode())
    if cost_hash is not None:
        sha.update(f"|costs:{cost_hash}".encode())
    return sha.hexdigest()


def query_key(plan_hash: str, batches: Sequence, want_lam: bool,
              backend: str, cost_hash: Optional[str] = None,
              lam_mode: str = "exact",
              fd_eps: Optional[float] = None,
              structure_hash: Optional[str] = None,
              congestion_hash: Optional[str] = None) -> str:
    """Key for a unified :class:`repro.sweep.api.Engine` query: the plan (or
    MultiPlan) content hash, the per-graph scenario batches in order, the
    requested sensitivity flag, the backend, the λ mode (finite-difference
    λ is a *different numeric contract* than the exact backtrace, so the
    two must never collide — and fd keys fold the step size in), the
    cost-batch hash when a candidate axis is populated, and the
    structure-batch hash when a variant axis is — bucketing makes distinct
    variant sets share the plan's super-envelope, so two studies differing
    only in their structure blocks must never collide."""
    sha = hashlib.sha1(b"sweep-query-v1|")
    sha.update(plan_hash.encode())
    for b in batches:
        _update(sha, b.L)
        _update(sha, b.gscale)
    sha.update(f"|{int(want_lam)}|{backend}|{lam_mode}".encode())
    if lam_mode == "fd":
        sha.update(repr(float(fd_eps)).encode())
    if cost_hash is not None:
        sha.update(f"|costs:{cost_hash}".encode())
    if structure_hash is not None:
        sha.update(f"|structure:{structure_hash}".encode())
    if congestion_hash is not None:
        # link topology + (α, β) registry + convergence knobs: two runs
        # differing only in congestion parameters must never collide
        sha.update(f"|congestion:{congestion_hash}".encode())
    return sha.hexdigest()


def graph_content_key(g) -> str:
    """Content hash of an :class:`~repro.core.graph.ExecutionGraph`.

    Hashes the build-time arrays (vertices, edges, latency classes, gap
    decomposition, interned links) — everything :func:`compile_plan`
    consumes — so two graphs built independently with identical content
    share one key.  The CSR/level arrays are derived from those inputs and
    deliberately excluded.  This is what lets detached ``Query(graphs=)``
    runs and explore generations that *rebuild* a graph land on the same
    memoized engine instead of recompiling the plan.
    """
    sha = hashlib.sha1(b"graph-content-v1|")
    for arr in (g.kind, g.vcost, g.vrank, g.esrc, g.edst, g.econst,
                g.ebytes, g.elat):
        _update(sha, arr)
    for opt in (g.egap, g.egclass, g.elink, g.link_classes):
        if opt is None:
            sha.update(b"|none")
        else:
            sha.update(b"|arr")
            _update(sha, opt)
    sha.update(f"|{int(g.nclass)}|{int(g.nranks)}|{int(g.nlinks)}".encode())
    return sha.hexdigest()


def multi_result_key(multi_hash: str, batches: Sequence, compute_lam: bool,
                     backend: str) -> str:
    """Key for a MultiPlan run: per-graph scenario batches hashed in order."""
    sha = hashlib.sha1(b"sweep-multi-result-v1|")
    sha.update(multi_hash.encode())
    for b in batches:
        _update(sha, b.L)
        _update(sha, b.gscale)
    sha.update(f"|{int(compute_lam)}|{backend}".encode())
    return sha.hexdigest()


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    #: subset counters for patched-cost lookups (``run(costs=...)`` —
    #: zero-recompile placement search traffic); included in hits/misses
    patched_hits: int = 0
    patched_misses: int = 0

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate,
                "patched_hits": self.patched_hits,
                "patched_misses": self.patched_misses}


class SweepCache:
    """LRU map: result key → :class:`~repro.sweep.api.Result`.

    Thread-safe: the analysis service's threaded socket server shares one
    instance across connections, so every read-modify-write on the LRU
    ``OrderedDict`` and the stats counters happens under one lock.
    """

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self._store: OrderedDict = OrderedDict()
        self.stats = CacheStats()
        self._lock = threading.Lock()

    def get(self, key: str, patched: bool = False):
        with self._lock:
            hit = self._store.get(key)
            if hit is None:
                self.stats.misses += 1
                self.stats.patched_misses += patched
            else:
                self._store.move_to_end(key)
                self.stats.hits += 1
                self.stats.patched_hits += patched
        patched_s = "true" if patched else "false"
        if hit is None:
            _MISSES.inc(patched=patched_s)
            return None
        _HITS.inc(patched=patched_s)
        return hit

    def put(self, key: str, value) -> None:
        evicted = 0
        with self._lock:
            self._store[key] = value
            self._store.move_to_end(key)
            while len(self._store) > self.capacity:
                self._store.popitem(last=False)
                self.stats.evictions += 1
                evicted += 1
        if evicted:
            _EVICTIONS.inc(evicted)

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self.stats = CacheStats()

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)


#: Shared default instance (engines opt out with ``cache=None`` or
#: ``run(use_cache=False)``).
DEFAULT_CACHE = SweepCache()
