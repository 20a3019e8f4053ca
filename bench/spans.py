"""Self time of the program's ``repro.obs`` spans.

A span's self time is its duration minus the part of its interval that the
spans nested in it cover (children, and through them every descendant).
Spans of one thread nest, so the nested spans are those of the same thread
that lie inside its interval.
"""

from __future__ import annotations


def _union_ns(intervals) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def self_ns(events: list) -> list:
    """``[(event, self time in ns)]`` for every event of ``events``
    (objects with ``name``, ``t0_ns``, ``t1_ns`` and ``tid``)."""
    by_tid: dict = {}
    for e in events:
        by_tid.setdefault(e.tid, []).append(e)
    out = []
    for evs in by_tid.values():
        # outer spans first where two start together
        evs = sorted(evs, key=lambda e: (e.t0_ns, -e.t1_ns))
        for i, e in enumerate(evs):
            inner = []
            for f in evs[i + 1:]:
                if f.t0_ns >= e.t1_ns:
                    break
                if f.t1_ns <= e.t1_ns:
                    inner.append((f.t0_ns, f.t1_ns))
            out.append((e, (e.t1_ns - e.t0_ns) - _union_ns(inner)))
    return out


def self_ms_by_name(events: list) -> dict:
    """{span name: summed self time in ms}."""
    out: dict = {}
    for e, ns in self_ns(events):
        out[e.name] = out.get(e.name, 0.0) + ns / 1e6
    return out


def self_ms_per(events: list, names, count: int):
    """Summed self time of the spans named ``names`` (names ending in ``.``
    match as prefixes), over ``count`` requests or queries; None when no
    such span was recorded or nothing was counted."""
    names = tuple(names)
    total, seen = 0.0, False
    for name, ms in self_ms_by_name(events).items():
        if name in names or any(n.endswith(".") and name.startswith(n)
                                for n in names):
            total += ms
            seen = True
    if not seen or count <= 0:
        return None
    return total / count
