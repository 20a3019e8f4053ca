"""Bytes that one forward over a graph and a query has to move.

Counted from the graph and the query alone, never from how an
implementation pads or lays them out, so the count is the same whichever
backend (dense segment, sparse slot lists, Pallas) runs the forward:

* every edge reads its source's finish time once per scenario,
* every vertex writes its finish time once per scenario,
* each edge's static description is read once: source and destination
  index (4 B each), constant cost and gap share (8 B each), gap class
  (4 B) and one latency-hop count per network class (2 B each).

Scenario values are ``width`` bytes: the configuration's contract dtype
(8 for float64).  There is no operation count: the longest-path forward is
additions and maxima on the vector unit, whose rate the chip's published
peaks do not give (and float64 is emulated there), so its roofline is bound
by HBM bandwidth alone.
"""

from __future__ import annotations

WIDTH = {"float64": 8, "float32": 4}


def static_edge_bytes(nclass: int = 1) -> int:
    return 4 + 4 + 8 + 8 + 4 + 2 * nclass


def forward_bytes(edges: int, vertices: int, scenarios: int, width: int,
                  nclass: int = 1) -> int:
    """Bytes one forward of ``scenarios`` scenarios moves at least."""
    return (edges * scenarios * width + vertices * scenarios * width
            + edges * static_edge_bytes(nclass))
