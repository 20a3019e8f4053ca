"""The program's input: a configuration's skeleton schedule
(``bench/skeletons/<skeleton>.py``) built into an ``ExecutionGraph``
through the program's public ``GraphBuilder``.

A compute step adds one vertex a rank, of cost ``cost_us * (1 +
jitter[row, rank])``; a round posts every send of its messages, then every
receive, and joins each pair by an eager LogGPS message edge (``L + (s - 1)
G``, the builder's own cost rule, recorded with its gap share and link).
``build`` returns the graph and ``calc[row, rank]``, the compute vertices'
ids.
"""

from __future__ import annotations

import numpy as np

import registry


def _round(b, msgs, p) -> None:
    svs = [b.add_send_vertex(src, p.o) for (src, _, _) in msgs]
    for (src, dst, nbytes), sv in zip(msgs, svs):
        if nbytes >= p.S:
            raise ValueError(f"a {nbytes} B message is not eager "
                             f"(S = {p.S} B)")
        rv = b.add_recv_vertex(dst, p.o)
        cls = p.link_class(src, dst)
        gcost = p.gap_cost(nbytes, src, dst)
        b.add_edge(sv, rv, const_us=gcost, nbytes=nbytes, lat=((cls, 1),),
                   gap_us=gcost, gclass=cls,
                   link=b.intern_link(cls, src, dst))


def build(params, spec: dict, jitter=None):
    """(ExecutionGraph, calc) of one graph entry of a configuration."""
    from repro.core.graph import GraphBuilder
    sk = registry.module("skeletons", spec["skeleton"])
    shape = sk.jitter_shape(**spec["args"])
    jitter = np.zeros(shape) if jitter is None else jitter
    P, steps = sk.schedule(**spec["args"])
    b = GraphBuilder(P, params.nclass)
    calc = np.empty(shape, dtype=np.int64)
    for step in steps:
        if step[0] == "compute":
            _, row, cost = step
            for r in range(P):
                calc[row, r] = b.add_calc(r, cost * (1.0 + jitter[row, r]))
        else:
            _round(b, step[1], params)
    return b.finalize(), calc
