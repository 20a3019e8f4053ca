"""The plain reference the benchmark's answers are compared with.

It imports nothing of the program.  It builds each configuration's execution
graph itself, from the skeleton's schedule (``bench/skeletons/``), the
configuration's sizes and the seed's jitter, with the LogGPS cost rules of
LLAMP (arXiv:2404.14193, Sec. II): a compute vertex
costs its compute time, a send or receive vertex costs ``o``, an eager
message edge costs ``L + (s - 1) G`` and every rank's operations are chained
in program order.  Then it evaluates the longest path level by level in
float64 numpy, scenarios side by side:

    t_start(v) = max(0, max over in-edges e = (u, v) of t_end(u) + w_e)
    t_end(v)   = t_start(v) + cost(v)
    T          = max over v of t_end(v)

and the latency sensitivity lambda as the right derivative of T in L: along
the maximising in-edges (ties within ``TIE_RTOL``) the largest count of
latency hops.
"""

from __future__ import annotations

import numpy as np

import registry

#: relative width of a tie between two paths of the reference's own float64
#: arithmetic (sums of the same costs in another order differ by a few ulp)
TIE_RTOL = 1e-12


class RefGraph:
    """Edge list of one application graph, in the reference's own numbering."""

    def __init__(self, nranks: int):
        self.cost: list = []
        self.src: list = []
        self.dst: list = []
        self.const: list = []
        self.hops: list = []
        self.gap: list = []
        self._tail = [-1] * nranks

    def vertex(self, rank: int, cost: float) -> int:
        v = len(self.cost)
        self.cost.append(float(cost))
        if self._tail[rank] >= 0:
            self.edge(self._tail[rank], v, 0.0, 0, 0.0)
        self._tail[rank] = v
        return v

    def edge(self, u: int, v: int, const: float, hops: int, gap: float):
        self.src.append(u)
        self.dst.append(v)
        self.const.append(const)
        self.hops.append(hops)
        self.gap.append(gap)

    def freeze(self) -> "Frozen":
        return Frozen(self)


class Frozen:
    """Arrays and the level schedule of a built :class:`RefGraph`."""

    def __init__(self, g: RefGraph):
        self.cost = np.asarray(g.cost, dtype=np.float64)
        src = np.asarray(g.src, dtype=np.int64)
        dst = np.asarray(g.dst, dtype=np.int64)
        nv = self.cost.shape[0]
        # every edge runs from an older vertex to a newer one, and edges are
        # appended in order of their destination, so one pass sets levels
        level = [0] * nv
        for u, v in zip(g.src, g.dst):
            if u >= v:
                raise ValueError("reference graph edge against creation order")
            lu = level[u] + 1
            if lu > level[v]:
                level[v] = lu
        level = np.asarray(level, dtype=np.int64)
        order = np.lexsort((dst, level[dst]))
        self.src = src[order]
        self.dst = dst[order]
        self.const = np.asarray(g.const, dtype=np.float64)[order]
        self.hops = np.asarray(g.hops, dtype=np.float64)[order]
        self.gap = np.asarray(g.gap, dtype=np.float64)[order]
        self.nv = nv
        self.ne = int(self.src.shape[0])
        self.nlevels = int(level.max()) + 1 if nv else 0
        elev = level[self.dst]
        self.level_ptr = np.searchsorted(elev, np.arange(self.nlevels + 1))
        self.sources = np.nonzero(level == 0)[0]
        # per level: segment starts of equal destinations, and the vertices
        self._segs = []
        for lv in range(1, self.nlevels):
            a, b = self.level_ptr[lv], self.level_ptr[lv + 1]
            d = self.dst[a:b]
            starts = np.nonzero(np.r_[True, d[1:] != d[:-1]])[0]
            self._segs.append((a, b, starts, d[starts]))

    def forward(self, L, gscale=None, vextra=None, lam: bool = True):
        """T [S] and lambda [S] (or None) for absolute latencies ``L`` [S]
        and gap scales ``gscale`` [S].  ``vextra``: optional list of
        (scenario, vertex, extra cost) added to one vertex in one scenario."""
        L = np.asarray(L, dtype=np.float64).ravel()
        S = L.shape[0]
        gs = (np.ones(S) if gscale is None
              else np.asarray(gscale, dtype=np.float64).ravel())
        cost = np.repeat(self.cost[:, None], S, axis=1)
        for s, v, x in (vextra or ()):
            cost[v, s] += x
        t_end = np.zeros((self.nv, S))
        t_end[self.sources] = cost[self.sources]
        slope = np.zeros((self.nv, S)) if lam else None
        for a, b, starts, verts in self._segs:
            w = (self.const[a:b, None] + self.hops[a:b, None] * L[None, :]
                 + self.gap[a:b, None] * (gs[None, :] - 1.0))
            cand = t_end[self.src[a:b]] + w
            best = np.maximum.reduceat(cand, starts, axis=0)
            start = np.maximum(best, 0.0)
            t_end[verts] = start + cost[verts]
            if lam:
                rep = np.repeat(best, np.diff(np.r_[starts, b - a]), axis=0)
                hit = cand >= rep - TIE_RTOL * np.abs(rep)
                cs = np.where(hit, slope[self.src[a:b]]
                              + self.hops[a:b, None], -np.inf)
                slope[verts] = np.maximum.reduceat(cs, starts, axis=0)
        T = t_end.max(axis=0)
        if not lam:
            return T, None
        sink = t_end >= T[None, :] - TIE_RTOL * np.abs(T[None, :])
        return T, np.where(sink, slope, -np.inf).max(axis=0)


class Net:
    """Single-class LogGPS constants of a configuration (times in us)."""

    def __init__(self, net: dict):
        self.L = float(net["L_us"])
        self.G = float(net["G_ns_per_byte"]) * 1e-3
        self.o = float(net["o_us"])
        self.S = float(net["S_bytes"])


def build_graph(net: Net, spec: dict, jitter) -> tuple:
    """(Frozen, calc) of one graph entry: its skeleton's schedule
    (``bench/skeletons/<skeleton>.py``) built under the cost rules above.
    A round posts every send of its messages, then every receive."""
    sk = registry.module("skeletons", spec["skeleton"])
    P, steps = sk.schedule(**spec["args"])
    g = RefGraph(P)
    calc = np.empty(jitter.shape, dtype=np.int64)
    for step in steps:
        if step[0] == "compute":
            _, row, cost = step
            for r in range(P):
                calc[row, r] = g.vertex(r, cost * (1.0 + jitter[row, r]))
            continue
        msgs = step[1]
        sends = [g.vertex(src, net.o) for (src, _, _) in msgs]
        for (src, dst, nbytes), s in zip(msgs, sends):
            if nbytes >= net.S:
                raise ValueError("the reference builds eager messages only "
                                 f"({nbytes} B >= S = {net.S} B)")
            r = g.vertex(dst, net.o)
            gap = max(nbytes - 1.0, 0.0) * net.G
            g.edge(s, r, gap, 1, gap)
    return g.freeze(), calc


def build(config: dict, jitter: dict) -> dict:
    """{graph name: (Frozen, calc)} for a configuration and the seed's
    jitter arrays (keyed like the configuration's graphs)."""
    net = Net(config["network"])
    return {spec["name"]: build_graph(net, spec, jitter[spec["name"]])
            for spec in config["graphs"]}
