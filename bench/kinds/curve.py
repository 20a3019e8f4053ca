"""Service ``curve`` request: T and lambda of one variant over ``points``
latency deltas spread over ``range`` (plus a seeded offset below
``offset_max``).  ``variant: rotate`` walks the configuration's graphs in
order; ``points`` is a menu walked the same way.  Checked: every T and
lambda of the curve."""

from __future__ import annotations

import numpy as np

LIMITS = {"T_rel_err": 1e-9, "lam_err": 0.0}


def make(spec, k, rng, gen, variant=None, points=None):
    variant = gen.variant(spec, k, variant)
    n = points if points is not None else gen.pick(spec["points"], k)
    return {"kind": "curve", "meta": {},
            "json": {"kind": "curve", "variant": variant,
                     "deltas": gen.deltas(spec, n, rng)}}


def check(rec, refs, ctx, gaps) -> None:
    req, pay = rec["req"]["json"], rec["res"]
    Tr, lr = refs[req["variant"]][0].forward(
        ctx["L0"] + np.asarray(req["deltas"]))
    gaps.rel("T_rel_err", pay["T"], Tr)
    gaps.abs("lam_err", pay["lam"], lr)
