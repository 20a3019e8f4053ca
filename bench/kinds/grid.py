"""Study query into ``Engine.run``: a latency axis of ``lat_points`` over
``lat_range`` (plus a seeded offset below ``offset_max``) on network class
0, times a gap-scale axis of ``gscale_points`` over ``gscale_range``, with
the spec's ``outputs``.  Checked: T of every scenario row of every graph on
the engine's axis, and lambda where it was asked for."""

from __future__ import annotations

import numpy as np

LIMITS = {"T_rel_err": 1e-9, "lam_err": 0.0}


def make(spec, k, rng, gen, variant=None, points=None):
    off = rng.uniform(0.0, float(spec["offset_max"]))
    return {"kind": "grid",
            "lat": np.linspace(*spec["lat_range"], int(spec["lat_points"]))
            + off,
            "gs": np.linspace(*spec["gscale_range"],
                              int(spec["gscale_points"])),
            "outputs": list(spec["outputs"])}


def call(prog, req):
    from repro.sweep import Query, cartesian_grid
    grid = cartesian_grid(prog.params, lat_deltas={0: req["lat"]},
                          gscales={0: req["gs"]})
    res = prog.entry.run(Query(scenarios=grid,
                               outputs=tuple(req["outputs"])))
    return {"T": res.T, "lam": res.lam}, True


def cells(prog, req) -> int:
    return len(req["lat"]) * len(req["gs"]) * len(prog.on_axis)


def forward_bytes(prog, req, width: int) -> int:
    import work
    S = len(req["lat"]) * len(req["gs"])
    return sum(work.forward_bytes(prog.graphs[n].num_edges,
                                  prog.graphs[n].num_vertices, S, width)
               for n in prog.on_axis)


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
            gaps.fail("lam_err")
            return
        lams = np.asarray(res["lam"])[..., 0]
        lams = lams if lams.ndim == 2 else lams[None]
    for i, name in enumerate(ctx["names"]):
        Tr, lr = refs[name][0].forward(L, gs, lam=want_lam)
        gaps.rel("T_rel_err", rows[i], Tr)
        if want_lam:
            gaps.abs("lam_err", lams[i], lr)
