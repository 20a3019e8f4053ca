"""Service ``tolerance`` request: the latency increase at which one
variant's T reaches (1 + p) T(L0), for each budget p of ``budgets`` moved
by a seeded amount below ``budget_jitter``.  Checked: the reference's T at
each returned tolerance against its budget.  The program's search stops
once it is within 1e-6 of the budget, relative, which is the limit."""

from __future__ import annotations

import numpy as np

LIMITS = {"tol_err": 1e-6}


def make(spec, k, rng, gen, variant=None, points=None):
    variant = gen.variant(spec, k, variant)
    w = float(spec["budget_jitter"])
    b = np.asarray(spec["budgets"]) + rng.uniform(-w, w,
                                                  len(spec["budgets"]))
    return {"kind": "tolerance", "meta": {},
            "json": {"kind": "tolerance", "variant": variant,
                     "degradations": b.tolist()}}


def check(rec, refs, ctx, gaps) -> None:
    req, pay = rec["req"]["json"], rec["res"]
    L0 = ctx["L0"]
    ps = np.asarray(req["degradations"])
    tol = {float(k): float(v) for k, v in pay["tolerance"].items()}
    x = np.asarray([tol.get(float(p), np.nan) for p in ps])
    if not np.isfinite(x).all():
        gaps.fail("tol_err")
        return
    T = refs[req["variant"]][0].forward(np.r_[L0, L0 + x], lam=False)[0]
    budget = (1.0 + ps) * T[0]
    gaps.worst("tol_err", float(np.max(np.abs(T[1:] - budget) / budget)))
