"""Service ``resilience`` request on one variant: a straggler on a compute
vertex drawn from the seed (slowdown drawn from ``slowdown_range``) and a
link fault adding a latency drawn from ``link_extra_range`` on class 0.
Checked: T of the intact system and T under each fault."""

from __future__ import annotations

import numpy as np

LIMITS = {"T_rel_err": 1e-9}


def make(spec, k, rng, gen, variant=None, points=None):
    variant = gen.variant(spec, k, variant)
    calc = gen.calc[variant]
    # row >= 1: a straggler rides the vertex's in-edges, and the first
    # compute vertex of a rank may have none
    row = int(rng.integers(1, calc.shape[0]))
    rank = int(rng.integers(0, calc.shape[1]))
    slow = float(rng.uniform(*spec["slowdown_range"]))
    extra = float(rng.uniform(*spec["link_extra_range"]))
    return {"kind": "resilience",
            "meta": {"straggler": [row, rank], "slowdown": slow,
                     "extra_L_us": extra},
            "json": {"kind": "resilience", "variant": variant,
                     "faults": [
                         {"type": "straggler",
                          "vertices": [int(calc[row, rank])],
                          "slowdown": slow},
                         {"type": "link", "cls": 0, "extra_L_us": extra}]}}


def check(rec, refs, ctx, gaps) -> None:
    req, meta, pay = rec["req"]["json"], rec["req"]["meta"], rec["res"]
    ref, calc = refs[req["variant"]]
    row, rank = meta["straggler"]
    v = int(calc[row, rank])
    L0 = ctx["L0"]
    L = np.asarray([L0, L0, L0 + meta["extra_L_us"]])
    T = ref.forward(L, vextra=[(1, v, (meta["slowdown"] - 1.0)
                                * ref.cost[v])], lam=False)[0]
    gaps.rel("T_rel_err", np.r_[pay["T0"], pay["T_fault"]], T)
