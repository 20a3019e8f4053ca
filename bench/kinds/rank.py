"""Service ``rank`` request: every variant's mean T over ``points`` latency
deltas spread over ``range`` (plus a seeded offset).  Checked: each
variant's objective."""

from __future__ import annotations

import numpy as np

LIMITS = {"T_rel_err": 1e-9}


def make(spec, k, rng, gen, variant=None, points=None):
    n = points if points is not None else gen.pick(spec["points"], k)
    return {"kind": "rank", "meta": {},
            "json": {"kind": "rank", "deltas": gen.deltas(spec, n, rng)}}


def check(rec, refs, ctx, gaps) -> None:
    req, pay = rec["req"]["json"], rec["res"]
    got = dict((n, v) for n, v in pay["ranking"])
    if set(got) != set(refs):
        gaps.fail("T_rel_err")
        return
    L = ctx["L0"] + np.asarray(req["deltas"])
    for name, (ref, _) in refs.items():
        gaps.rel("T_rel_err", got[name], ref.forward(L, lam=False)[0].mean())
