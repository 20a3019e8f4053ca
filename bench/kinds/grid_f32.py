"""The ``grid`` study query (``kinds/grid.py``: the same request, call,
cells and bytes) held to a float32 contract, for a policy whose forward
computes in float32 (``ExecPolicy(backend="pallas")``).

The limits, and why (their readings are in PERF.md, section 2):

* ``T_rel_err`` — the widest relative gap of T from the float64
  reference's, as ``grid`` reads it.  4e-6: 6.2x the largest reading of
  the program over 24 seeds on a TPU v5e (6.4e-7), and far under 1e-3, so
  that T rounded to bfloat16 (a relative step of 3.9e-3) fails.
* ``lam_bracket_err`` — how far the program's lambda lies outside the
  bracket that any path within the T limit of the critical path has.
  Float32 can break a near-tie between two critical paths otherwise than
  the float64 reference, so lambda is not held to the exact count.  T(L)
  is convex and piecewise linear in L at fixed gap scale (the longest of
  paths, each affine in L), so a path within ``eps`` of T(L) has a slope in

      [(T(L) - T(L - h) - eps) / h, (T(L + h) - T(L) + eps) / h],

  both ends computed by the reference in float64, with ``eps`` the T limit
  times max(T, 1) and ``h = H_PER_EPS * eps``: the bracket is then 0.2 hop
  wide wherever no breakpoint lies within ``h``, and a lambda one hop out
  lies 0.9 hop outside it.  Limit 0.
* ``lam_inexact_pct`` — reported, not judged: the largest share, over the
  sampled requests, of lambda that differ from the reference's exact count
  (a share cannot pass its limit of 100).
"""

from __future__ import annotations

import numpy as np

import registry

LIMITS = {"T_rel_err": 4e-6, "lam_bracket_err": 0.0,
          "lam_inexact_pct": 100.0}

#: the lambda bracket's step h over the T tolerance eps
H_PER_EPS = 10.0

_grid = registry.module("kinds", "grid")
call = _grid.call
cells = _grid.cells
forward_bytes = _grid.forward_bytes


def make(spec, k, rng, gen, variant=None, points=None):
    return dict(_grid.make(spec, k, rng, gen), kind="grid_f32")


def bracket(ref, L, gs, T):
    """(lo, hi): the slopes in L that a path within the T limit of the
    longest, at latencies ``L`` [S] and gap scales ``gs`` [S], can have;
    ``T`` [S] the reference's T there."""
    eps = LIMITS["T_rel_err"] * np.maximum(np.abs(T), 1.0)
    h = H_PER_EPS * eps
    Tm, _ = ref.forward(L - h, gs, lam=False)
    Tp, _ = ref.forward(L + h, gs, lam=False)
    return (T - Tm - eps) / h, (Tp - T + eps) / h


def check(rec, refs, ctx, gaps) -> None:
    req, res = rec["req"], rec["res"]
    # scenario rows: latency axis outer, gap-scale axis inner
    L = np.repeat(ctx["L0"] + req["lat"], len(req["gs"]))
    gs = np.tile(req["gs"], len(req["lat"]))
    want_lam = "lam" in req["outputs"]
    T = np.asarray(res["T"])
    rows = T if T.ndim == 2 else T[None]
    if rows.shape[0] != len(ctx["names"]):
        gaps.fail("T_rel_err")
        return
    lams = None
    if want_lam:
        if res["lam"] is None:
            gaps.fail("lam_bracket_err")
            return
        lams = np.asarray(res["lam"])[..., 0]
        lams = lams if lams.ndim == 2 else lams[None]
    for i, name in enumerate(ctx["names"]):
        ref = refs[name][0]
        Tr, lr = ref.forward(L, gs, lam=want_lam)
        gaps.rel("T_rel_err", rows[i], Tr)
        if not want_lam:
            continue
        lam = np.asarray(lams[i], dtype=np.float64).ravel()
        if lam.shape != lr.shape or not np.isfinite(lam).all():
            gaps.fail("lam_bracket_err")
            continue
        lo, hi = bracket(ref, L, gs, Tr)
        gaps.worst("lam_bracket_err",
                   np.max(np.maximum(np.maximum(lo - lam, lam - hi), 0.0),
                          initial=0.0))
        gaps.worst("lam_inexact_pct", 100.0 * np.mean(lam != lr))
