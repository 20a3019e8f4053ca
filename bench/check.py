"""Decides ``correct``: the answers the timed path returned, compared with
the plain reference (``reference.py``).

Once the window has closed, a sample of its requests drawn from the seed,
``PER_KIND`` of each kind, is recomputed by the reference, every answer of
each, by the kind's own ``check`` (``bench/kinds/<kind>.py``).  A kind
reports the worst gap of each number it compares and declares that
number's limit in its ``LIMITS``:

* ``T_rel_err`` — the widest relative gap of a makespan (T of a scenario
  row, a rank objective, a resilience T) from the reference's;
* ``lam_err`` — the widest gap of a latency sensitivity lambda (a count of
  latency hops on the critical path) from the reference's.  Exact: 0;
* ``tol_err`` — the widest relative gap between the reference's T at a
  returned tolerance and the budget it was asked for.

The limits and the readings they were set from are in PERF.md.
"""

from __future__ import annotations

import numpy as np

import generate
import registry

PER_KIND = 3


def _sample(records: list, seed: int) -> list:
    """Per kind, up to ``PER_KIND`` records drawn from the seed (requests
    that failed are counted as failed, not sampled)."""
    rng = generate.rng_for(seed, 2)
    by_kind: dict = {}
    for r in records:
        by_kind.setdefault(r["req"]["kind"], []).append(r)
    out = []
    for kind in sorted(by_kind):
        rs = by_kind[kind]
        idx = rng.choice(len(rs), size=min(PER_KIND, len(rs)), replace=False)
        out.extend(rs[i] for i in sorted(idx))
    return out


class Gaps:
    """The worst reading of each number, and its limit."""

    def __init__(self):
        self.value: dict = {}
        self.limit: dict = {}

    def declare(self, limits: dict) -> None:
        for k, v in limits.items():
            if self.limit.setdefault(k, v) != v:
                raise ValueError(f"two request kinds give {k!r} the limits "
                                 f"{self.limit[k]!r} and {v!r}")

    def worst(self, name: str, x: float) -> None:
        x = float(x) if np.isfinite(x) else np.inf
        self.value[name] = max(self.value.get(name, 0.0), x)

    def fail(self, name: str) -> None:
        self.worst(name, np.inf)

    def _pair(self, name, got, want):
        got = np.asarray(got, dtype=np.float64).ravel()
        want = np.asarray(want, dtype=np.float64).ravel()
        if got.shape != want.shape or not np.isfinite(got).all():
            self.fail(name)
            return None
        return got, want

    def rel(self, name: str, got, want) -> None:
        """Widest |got - want| / max(|want|, 1)."""
        p = self._pair(name, got, want)
        if p is not None:
            got, want = p
            self.worst(name, np.max(np.abs(got - want)
                                    / np.maximum(np.abs(want), 1.0),
                                    initial=0.0))

    def abs(self, name: str, got, want) -> None:
        """Widest |got - want|."""
        p = self._pair(name, got, want)
        if p is not None:
            got, want = p
            self.worst(name, np.max(np.abs(got - want), initial=0.0))


def compare(records: list, refs: dict, ctx: dict, seed: int) -> dict:
    """{number: (value, limit)} over a seeded sample of ``records`` (each
    with the request ``req`` and the answer ``res`` the timed path
    returned).  ``ctx``: ``names`` (the graphs on the entry's axis, in
    order) and ``L0`` (the configuration's latency, us)."""
    gaps = Gaps()
    for rec in _sample(records, seed):
        kind = registry.module("kinds", rec["req"]["kind"])
        gaps.declare(kind.LIMITS)
        kind.check(rec, refs, ctx, gaps)
    return {k: (v, gaps.limit[k]) for k, v in sorted(gaps.value.items())}


def verdict(numbers: dict, attempted: int, failed: int) -> bool:
    return (failed == 0 and attempted > 0 and bool(numbers)
            and all(v <= lim for v, lim in numbers.values()))
