#!/usr/bin/env python3
"""Chip smoke test: drive the analysis path once on a TPU at real graph sizes.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py               # one chip: dense, service, sparse
    python chip_smoke.py --four-chips  # four chips: the sharded path only

Phases (one chip):

* **dense** — a LULESH-like 3-D stencil (64 ranks, ~61k edges, ~51 MB dense
  envelope) through ``Engine`` on ``segment`` (float64) and ``pallas``
  (float32), a 512-point ΔL × γ grid with T and λ.
* **service** — an ``AnalysisService`` holding the four allreduce
  expansions of an ICON-like chain (64 ranks) plus the stencil (and its
  zero-link-cost build for placement) answers curve, tolerance, rank,
  placement and resilience requests through ``handle_json``.
* **sparse** — the traced llama3.2-3b 4k-token training step on a
  2 × 4 × 8 mesh (~462k edges, past the dense cliff, so the engine switches
  to the sparse backend itself), float64 and float32, a 128-point DCN ΔL
  curve plus the 1/2/5 % tolerance query.

Every phase checks T and λ at a few scenario points against the scalar
oracle (``core.dag.LevelPlan.forward``): 1e-9 relative for float64, 1e-5
for float32.  Each prints one JSON line (backend and dtype that ran, S,
first-call and warm seconds, XLA programs and compile seconds, peak device
bytes, oracle errors, whether the Pallas program holds a compiled TPU
kernel).  ``--four-chips`` runs G-, K- and S-sharded queries against the
same queries on one device (bit-equal on ``segment``).

The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or if any check fails, the script exits non-zero and prints
no such line.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

F64_RTOL = 1e-9
F32_RTOL = 1e-5
DEGRADATIONS = (0.01, 0.02, 0.05)

#: the sizes the script runs at; tests pass smaller ones to the phases
FULL = {
    "stencil": (4, 4, 4, 50),           # px, py, pz, iterations
    "grid": (32, 16),                   # ΔL points × γ points
    "chain": (64, 8),                   # ranks, steps
    "arch": "llama3.2-3b",
    "mesh": (2, 4, 8),                  # pods, data, model
    "sparse_S": 128,
    "max_dense_bytes": None,            # None: the engine's own threshold
}


# -- measurement helpers ------------------------------------------------------

class _CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache retrievals
    included, so a warm cache shows as fewer seconds) and cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_CLOCK: "_CompileClock | None" = None


def _clock() -> _CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = _CompileClock()
    return _CLOCK


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _timed(fn, warm_reps: int = 3) -> tuple:
    """Run ``fn`` cold, then ``warm_reps`` times warm.  ``fn`` returns host
    (numpy) arrays, so every timing ends after the device results were
    read back.  The cold call starts from empty in-process compile
    caches, so ``first_s`` and ``xla_programs`` are that call's own
    whatever the process ran before; the persistent compilation cache
    stays, and ``compile_s`` includes its retrievals.  Returns (first
    result, stats dict)."""
    import jax
    from repro.obs.compile import CompileWatcher
    jax.clear_caches()
    clock = _clock()
    c0 = clock.seconds
    with CompileWatcher().watch("chip_smoke") as rec:
        t0 = time.perf_counter()
        out = fn()
        first = time.perf_counter() - t0
    warm = []
    for _ in range(warm_reps):
        t0 = time.perf_counter()
        fn()
        warm.append(time.perf_counter() - t0)
    return out, {"first_s": first, "warm_s": statistics.median(warm),
                 "xla_programs": rec.new_programs,
                 "compile_s": clock.seconds - c0}


_PLANS: dict = {}


def _plan(g):
    """The scalar engine's plan for ``g`` (built once per graph)."""
    from repro.core import dag
    if id(g) not in _PLANS:
        _PLANS[id(g)] = (g, dag.LevelPlan(g))
    return _PLANS[id(g)][1]


def _oracle(g, params, batch, idx) -> tuple:
    """Scalar-engine T [n] and λ [n, nclass] at scenario rows ``idx``."""
    from repro.core.graph import edge_gap_shares
    plan = _plan(g)
    egap, egclass = edge_gap_shares(g, params)
    T, lam = [], []
    for s in idx:
        gs = batch.gscale[s]
        extra = egap * (gs[egclass] - 1.0) if (gs != 1.0).any() else None
        r = plan.forward(params.replace(L=tuple(batch.L[s])),
                         extra_edge_cost=extra)
        T.append(r.T)
        lam.append(r.lam)
    return np.asarray(T), np.asarray(lam)


def _check(name: str, T, lam, oT, olam, dtype: str) -> dict:
    """Compare engine T/λ with the oracle; raise past the dtype's bound."""
    rtol = F64_RTOL if dtype == "float64" else F32_RTOL
    t_err = float(np.max(np.abs(T - oT) / np.maximum(np.abs(oT), 1.0)))
    lam_err = float(np.max(np.abs(lam - olam))) if lam is not None else 0.0
    lam_rel = (float(np.max(np.abs(lam - olam) / np.maximum(np.abs(olam),
                                                            1.0)))
               if lam is not None else 0.0)
    if not (t_err <= rtol and lam_rel <= rtol):
        raise AssertionError(
            f"{name}: oracle mismatch ({dtype}): T rel err {t_err:.3e}, "
            f"λ abs err {lam_err:.3e} (bound {rtol:g} relative)")
    exact = bool(np.array_equal(T, oT)
                 and (lam is None or np.array_equal(lam, olam)))
    return {"T_rel_err": t_err, "lam_abs_err": lam_err, "bit_exact": exact}


def _spread(n: int, k: int) -> np.ndarray:
    return np.unique(np.linspace(0, n - 1, k).round().astype(int))


def _has_tpu_kernel(eng, S: int) -> bool:
    """Whether the engine's Pallas λ program (dense, or the sparse
    slot-list flavour) lowers to a compiled TPU kernel, and not to
    interpret-mode XLA ops."""
    import jax
    import jax.numpy as jnp
    from repro.sweep import engine as sweep_engine
    Sp = sweep_engine._bucket(S, lo=4)
    if eng.sparse is None:
        L = jnp.zeros((Sp, eng.nclass), jnp.float32)
        fwd = sweep_engine._get_forward("pallas", True)
        return "tpu_custom_call" in fwd.lower(*eng._arrays("pallas"), L,
                                              L).as_text()
    sp = eng.sparse
    with jax.enable_x64():
        L = jnp.zeros((Sp, eng.nclass), jnp.float64)
        fwd = sweep_engine._get_forward("sparse_pallas", True,
                                        sparse_dims=(sp.Emax_lv, sp.Vmax_lv))
        return "tpu_custom_call" in fwd.lower(*eng._arrays("sparse"), L,
                                              L).as_text()


def _expect_ran(res, backend: str, dtype: str, platform) -> None:
    if (res.backend, res.dtype) != (backend, dtype):
        raise AssertionError(f"expected {backend}/{dtype}, ran "
                             f"{res.backend}/{res.dtype}")
    if platform is not None and res.platform != platform:
        raise AssertionError(f"expected the {platform} device, ran on "
                             f"{res.platform}")


# -- workloads ----------------------------------------------------------------

def stencil(sizes: dict, params):
    from repro.core import synth
    px, py, pz, iters = sizes["stencil"]
    return synth.stencil3d(px, py, pz, iters, halo_bytes=96e3,
                           comp_us=800.0, params=params)


def chain_variants(sizes: dict, params) -> list:
    from repro.core import synth
    from repro.sweep import collective_variants
    P, steps = sizes["chain"]
    return collective_variants(
        lambda a: synth.allreduce_chain(P, steps, nbytes=2e6,
                                        comp_us=4000.0, params=params,
                                        algo=a),
        ["ring", "bidir_ring", "recursive_doubling", "tree"], params)


def base_params():
    from repro.core.loggps import cluster_params
    return cluster_params(L_us=3.0, o_us=5.0)


def zero_params():
    from repro.core.loggps import LogGPS
    return LogGPS(L=(0.0,), G=(0.0,), o=5.0, S=256e3)


# -- phases -------------------------------------------------------------------

def phase_dense(sizes: dict = FULL, platform="tpu"):
    """Stencil through ``Engine`` on segment (f64) and pallas (f32)."""
    from repro import sweep
    p = base_params()
    g = stencil(sizes, p)
    nL, nG = sizes["grid"]
    grid = sweep.cartesian_grid(p, lat_deltas={0: np.linspace(0, 60, nL)},
                                gscales={0: np.linspace(1, 4, nG)})
    idx = _spread(grid.S, 8)
    oT, olam = _oracle(g, p, grid, idx)
    for backend, dtype in (("segment", "float64"), ("pallas", "float32")):
        eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(
            backend=backend, cache=None))
        res, st = _timed(lambda: eng.run(grid))
        _expect_ran(res, backend, dtype, platform)
        rec = {"phase": "dense", "backend": backend, "dtype": dtype,
               "platform": res.platform, "S": grid.S,
               "edges": int(g.num_edges), **st,
               **_check(f"dense/{backend}", res.T[idx], res.lam[idx], oT,
                        olam, dtype),
               "peak_bytes": _peak_bytes()}
        if backend == "pallas":
            rec["tpu_custom_call"] = _has_tpu_kernel(eng, grid.S)
            if platform == "tpu" and not rec["tpu_custom_call"]:
                raise AssertionError("dense/pallas: no compiled TPU kernel "
                                     "in the lowered program")
        yield rec


def build_service(sizes: dict):
    """The service the service phase queries (and its variants' graphs)."""
    from repro.launch.analysis import AnalysisService
    p = base_params()
    svc = AnalysisService(backend="segment")
    graphs = {}
    for v in chain_variants(sizes, p):
        svc.register(v)
        graphs[v.name] = (v.graph, p)
    g = stencil(sizes, p)
    svc.register_graph("stencil", g, p)
    graphs["stencil"] = (g, p)
    zp = zero_params()
    gz = stencil(sizes, zp)
    svc.register_graph("stencil_zero", gz, zp)
    graphs["stencil_zero"] = (gz, zp)
    return svc, graphs


def phase_service(sizes: dict = FULL, platform="tpu"):
    """Curve, tolerance, rank, placement and resilience requests through
    ``AnalysisService.handle_json``; every reply must be ok."""
    from repro import sweep
    from repro.core import placement
    svc, graphs = build_service(sizes)
    ring = next(n for n in graphs if "ring" in n and "bidir" not in n)
    deltas = np.linspace(0.0, 100.0, 8).tolist()
    P = graphs["stencil_zero"][0].nranks
    topo = {"pod": max(P // 8, 2), "L_fast": 1.0, "L_slow": 20.0,
            "G_fast": 2e-5, "G_slow": 8e-5}
    # a straggler on a compute vertex that has in-edges (a source vertex
    # cannot ride the cost axis)
    gr = graphs[ring][0]
    slow = int(gr.edst[np.argmax(gr.vcost[gr.edst] > 0)])
    requests = [
        {"kind": "curve", "variant": ring, "deltas": deltas},
        {"kind": "tolerance", "variant": ring,
         "degradations": list(DEGRADATIONS)},
        {"kind": "rank", "deltas": deltas},
        {"kind": "placement", "variant": "stencil_zero", "topo": topo},
        {"kind": "resilience", "variant": ring,
         "faults": [{"type": "straggler", "vertices": [slow],
                     "slowdown": 2.0},
                    {"type": "link", "cls": 0, "extra_L_us": 20.0,
                     "gscale": 2.0}]},
    ]
    for req in requests:
        t0 = time.perf_counter()
        c0 = _clock().seconds
        reply = json.loads(svc.handle_json(json.dumps(req)))
        wall = time.perf_counter() - t0
        if not reply["ok"]:
            raise AssertionError(f"service/{req['kind']}: {reply['error']}")
        compile_s = _clock().seconds - c0
        # the same request again: answered from the service's warm
        # engines and result cache
        t0 = time.perf_counter()
        svc.handle_json(json.dumps(req))
        pay = reply["payload"]
        rec = {"phase": "service", "kind": req["kind"], "backend": "segment",
               "dtype": "float64", "first_s": wall,
               "repeat_s": time.perf_counter() - t0, "compile_s": compile_s,
               "timings": reply["timings"]}
        kind = req["kind"]
        if kind == "curve":
            g, p = graphs[ring]
            batch = sweep.latency_grid(p, deltas)
            idx = np.arange(batch.S)
            oT, olam = _oracle(g, p, batch, idx)
            rec["S"] = batch.S
            rec.update(_check("service/curve", np.asarray(pay["T"]),
                              np.asarray(pay["lam"]), oT, olam[:, 0],
                              "float64"))
        elif kind == "tolerance":
            g, p = graphs[ring]
            base = sweep.latency_grid(p, [0.0])
            T0 = _oracle(g, p, base, [0])[0][0]
            tol = {float(k): float(v) for k, v in pay["tolerance"].items()}
            pts = sweep.latency_grid(p, [tol[d] for d in DEGRADATIONS])
            oT = _oracle(g, p, pts, range(pts.S))[0]
            budget = np.asarray([(1 + d) * T0 for d in DEGRADATIONS])
            err = float(np.max(np.abs(oT - budget) / budget))
            if err > 1e-5:
                raise AssertionError(f"service/tolerance: oracle T at the "
                                     f"returned ΔL misses the budget by "
                                     f"{err:.3e}")
            rec["budget_rel_err"] = err
        elif kind == "rank":
            objs = dict((n, v) for n, v in pay["ranking"])
            errs = []
            for name, (g, p) in graphs.items():
                batch = sweep.latency_grid(p, deltas)
                oT = _oracle(g, p, batch, range(batch.S))[0]
                errs.append(abs(objs[name] - oT.mean()) / oT.mean())
            rec["T_rel_err"] = float(max(errs))
            if rec["T_rel_err"] > F64_RTOL:
                raise AssertionError(f"service/rank: objective off the "
                                     f"oracle by {rec['T_rel_err']:.3e}")
            rec["variants"] = len(objs)
        elif kind == "placement":
            st = pay["stats"]
            rec["stats"] = st
            if st["scalar_fallbacks"] != 0 or st["plan_compiles"] != 1:
                raise AssertionError(f"service/placement: stats {st}")
            g, p = graphs["stencil_zero"]
            phi = placement.ArchTopology.two_tier(
                P, topo["pod"], **{k: v for k, v in topo.items()
                                   if k != "pod"})
            pi = np.asarray(pay["mapping"])
            oT = _plan(g).forward(
                p, extra_edge_cost=placement.mapping_edge_cost(g, phi, pi)).T
            err = abs(pay["history"][-1] - oT) / oT
            if err > F64_RTOL:
                raise AssertionError(f"service/placement: final objective "
                                     f"off the oracle by {err:.3e}")
            rec["T_rel_err"] = err
            rec["improvement"] = pay["improvement"]
        elif kind == "resilience":
            g, p = graphs[ring]
            T0 = _oracle(g, p, sweep.latency_grid(p, [0.0]), [0])[0][0]
            err = abs(pay["T0"] - T0) / T0
            if err > F64_RTOL or min(pay["slowdown"]) < 1.0:
                raise AssertionError(f"service/resilience: T0 err {err:.3e},"
                                     f" slowdowns {pay['slowdown']}")
            rec["T_rel_err"] = err
            rec["expected_slowdown"] = pay["expected_slowdown"]
        rec["peak_bytes"] = _peak_bytes()
        yield rec


def phase_sparse(sizes: dict = FULL, platform="tpu"):
    """The traced training step past the dense cliff: the default policy
    must switch to the sparse backend itself (f64); the f32 run pins the
    Pallas slot-list kernel.  DCN ΔL curve + tolerance query."""
    from repro import configs, sweep
    from repro.core.tracer import TraceSpec, trace_step
    from repro.models.config import TRAIN_4K
    cfg, _ = configs.get(sizes["arch"])
    pods, data, model = sizes["mesh"]
    ts = TraceSpec(pods=pods, data=data, model=model)
    p = ts.params()
    g = trace_step(cfg, TRAIN_4K, ts, p)
    dcn = p.class_names.index("dcn")
    grid = sweep.latency_grid(p, np.linspace(0.0, 200.0, sizes["sparse_S"]),
                              cls=dcn)
    idx = _spread(grid.S, 4)
    oT, olam = _oracle(g, p, grid, idx)
    T0 = _oracle(g, p, sweep.latency_grid(p, [0.0], cls=dcn), [0])[0][0]
    budget = np.asarray([(1 + d) * T0 for d in DEGRADATIONS])
    for dtype in ("float64", "float32"):
        pol = sweep.ExecPolicy(cache=None,
                               max_dense_bytes=sizes["max_dense_bytes"])
        if dtype == "float32":
            pol = pol.replace(backend="sparse", dtype="float32")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)   # auto-sparse
            eng = sweep.Engine(g, params=p, policy=pol)
        res, st = _timed(lambda: eng.run(grid))
        _expect_ran(res, "sparse", dtype, platform)
        rec = {"phase": "sparse", "backend": "sparse", "dtype": dtype,
               "platform": res.platform, "S": grid.S,
               "edges": int(g.num_edges), **st,
               **_check(f"sparse/{dtype}", res.T[idx], res.lam[idx], oT,
                        olam, dtype)}
        if dtype == "float32":
            rec["tpu_custom_call"] = _has_tpu_kernel(eng, grid.S)
            if platform == "tpu" and not rec["tpu_custom_call"]:
                raise AssertionError("sparse/float32: no compiled TPU kernel "
                                     "in the lowered program")
        t0 = time.perf_counter()
        tol = sweep.tolerance_batched(eng, p, DEGRADATIONS, cls=dcn)
        rec["tolerance_s"] = time.perf_counter() - t0
        pts = sweep.latency_grid(p, [tol[d] for d in DEGRADATIONS], cls=dcn)
        err = float(np.max(np.abs(_oracle(g, p, pts, range(pts.S))[0]
                                  - budget) / budget))
        if err > F32_RTOL:
            raise AssertionError(f"sparse/{dtype} tolerance: oracle T at the "
                                 f"returned ΔL misses the budget by {err:.3e}")
        rec["tolerance_budget_rel_err"] = err
        rec["peak_bytes"] = _peak_bytes()
        yield rec


def phase_four_chips(sizes: dict = FULL, ndev: int = 4, platform="tpu"
                     ) -> list:
    """G-sharded rank over the four packed variants, K-sharded placement
    candidates, S-sharded ΔL × γ curve — each bit-equal (segment) to the
    same query on one device, and each really spanning ``ndev`` devices."""
    import jax
    from repro import sweep
    from repro.core import placement
    if len(jax.devices()) != ndev:
        raise AssertionError(f"need {ndev} devices, found "
                             f"{len(jax.devices())}")
    p = base_params()
    records = []

    def compare(name, eng, query, axis):
        one = eng.run(query, use_cache=False)
        res, st = _timed(lambda: eng.run(query, shard=ndev, shard_axis=axis,
                                         use_cache=False))
        if res.devices != ndev:
            raise AssertionError(f"{name}: sharded run spanned "
                                 f"{res.devices} device(s), not {ndev}")
        _expect_ran(res, "segment", "float64", platform)
        equal = bool(np.array_equal(one.T, res.T) and
                     (one.lam is None or np.array_equal(one.lam, res.lam)))
        if not equal:
            raise AssertionError(f"{name}: sharded result differs from the "
                                 "single-device run")
        records.append({"phase": "four_chips", "query": name, "axis": axis,
                        "devices": res.devices, "platform": res.platform,
                        "bit_equal": equal, "shape": list(res.T.shape),
                        **st, "peak_bytes": _peak_bytes()})
        return res

    variants = chain_variants(sizes, p)
    eng = sweep.Engine([(v.graph, v.params) for v in variants],
                       names=[v.name for v in variants],
                       policy=sweep.ExecPolicy(cache=None))
    grid = sweep.latency_grid(p, np.linspace(0.0, 100.0, 8))
    res = compare("rank", eng, sweep.Query(scenarios=grid, outputs=("T",)),
                  "G")
    records[-1]["ranking"] = res.rank()

    zp = zero_params()
    gz = stencil(sizes, zp)
    phi = placement.ArchTopology.two_tier(gz.nranks, max(gz.nranks // 8, 2),
                                          L_slow=20.0)
    rng = np.random.default_rng(0)
    extras = np.stack([placement.mapping_edge_cost(
        gz, phi, rng.permutation(gz.nranks)) for _ in range(2 * ndev)])
    engz = sweep.Engine(gz, params=zp, policy=sweep.ExecPolicy(cache=None))
    compare("placement_candidates", engz,
            sweep.Query(scenarios=sweep.latency_grid(zp, [0.0, 10.0]),
                        costs=extras, outputs=("T",)), "K")

    g = stencil(sizes, p)
    nL, nG = sizes["grid"]
    grid = sweep.cartesian_grid(p, lat_deltas={0: np.linspace(0, 60, nL)},
                                gscales={0: np.linspace(1, 4, nG)})
    engs = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    compare("curve", engs, sweep.Query(scenarios=grid), "S")
    return records


# -- entry point --------------------------------------------------------------

def _emit(rec: dict) -> None:
    print(json.dumps(rec, default=lambda x: x.item()
                     if isinstance(x, np.generic) else str(x)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "this script runs only on the chip", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    _clock()
    t0 = time.perf_counter()
    phases = ([phase_four_chips] if args.four_chips
              else [phase_dense, phase_service, phase_sparse])
    for phase in phases:
        for rec in phase():
            _emit(rec)
    _emit({"total_s": time.perf_counter() - t0,
           "compile_s": _clock().seconds,
           "persistent_cache_hits": _clock().cache_hits,
           "compile_cache_dir": cache_dir})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
