"""CPU tests of the chip benchmark's harness, at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The harness refuses to run without a TPU; these tests hand ``run.run`` a
device check that accepts the CPU and shrink each configuration's graphs
(Pallas kernels run in interpret mode there).
"""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import build  # noqa: E402
import check  # noqa: E402
import devtrace  # noqa: E402
import generate  # noqa: E402
import reference  # noqa: E402
import registry  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402
import work  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
BIG_SEED = 2 ** 33 + 12345
TINY = {"tp": 2, "cycles": 2}

#: a service cell that sends every request kind there is, over two variants
KINDS_MIX = {
    "target": "service",
    "cycle": [
        {"kind": "curve", "variant": "rotate", "points": [4, 6],
         "range": [0.0, 100.0], "offset_max": 5.0},
        {"kind": "rank", "points": [4], "range": [0.0, 100.0],
         "offset_max": 5.0},
        {"kind": "tolerance", "variant": "rotate", "budgets": [0.01, 0.05],
         "budget_jitter": 0.002},
        {"kind": "resilience", "variant": "rotate",
         "slowdown_range": [1.5, 3.0], "link_extra_range": [5.0, 40.0]}]}


def tiny_cell(name: str) -> dict:
    """The cell as BENCHMARK.json names it, with its graphs shrunk;
    ``"kinds"`` is the all-kinds service cell over two shrunk variants."""
    cell = run.load_cell(CELLS[-1] if name == "kinds" else name)
    cfg = copy.deepcopy(cell["config"])
    for g in cfg["graphs"]:
        g["args"].update(TINY)
    if name == "kinds":
        other = copy.deepcopy(cfg["graphs"][0])
        other["name"] += ".s20"
        other["args"]["s"] = 20
        cfg["graphs"].append(other)
        # resilience needs cost blocks, which the sparse backend lacks:
        # the dense paths, float64 and the float32 Pallas control
        cfg["policy"] = {}
        cfg["control_policy"] = {"backend": "pallas"}
        cell["mix"] = KINDS_MIX
    cell["config"] = cfg
    return cell


def cpu_devices(chips):
    import jax
    return jax.devices()


def run_tiny(name, seed=BIG_SEED, seconds=1.0, trace=0, control=0):
    args = types.SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                                 trace=trace, control=control)
    return run.run(args, cell=tiny_cell(name), device_check=cpu_devices)


@pytest.fixture(autouse=True)
def _no_trace_dir_left(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")


def _dump(reqs) -> str:
    return json.dumps(reqs, default=lambda a: np.asarray(a).tolist())


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("name", CELLS + ["kinds"])
def test_traffic_is_a_function_of_the_seed(name):
    cell = tiny_cell(name)
    names = [g["name"] for g in cell["config"]["graphs"]]
    calc = {n: np.arange(2 * 8).reshape(2, 8) for n in names}

    def draw(seed):
        gen = generate.Generator(cell["mix"], names, calc, seed)
        return _dump([gen.next() for _ in range(12)] + gen.warmup())

    assert draw(BIG_SEED) == draw(BIG_SEED)
    assert draw(BIG_SEED) != draw(BIG_SEED + 1)
    reqs = json.loads(draw(7))[:12]
    assert len({json.dumps(r) for r in reqs}) == 12     # no repeats


def test_a_mix_cycles_its_kinds_in_order_and_warms_every_shape():
    names = ["a", "b"]
    calc = {n: np.arange(16).reshape(2, 8) for n in names}
    gen = generate.Generator(KINDS_MIX, names, calc, 3)
    reqs = [gen.next() for _ in range(8)]
    assert [r["kind"] for r in reqs] == \
        [c["kind"] for c in KINDS_MIX["cycle"]] * 2
    assert [r["json"].get("variant") for r in reqs[::4]] == names
    for r in reqs:
        if r["kind"] == "resilience":
            row, rank = r["meta"]["straggler"]
            assert row >= 1
            assert r["json"]["faults"][0]["vertices"] == \
                [int(calc[r["json"]["variant"]][row, rank])]
    warm = [(r["kind"], r["json"].get("variant"), len(r["json"].get(
        "deltas", []))) for r in gen.warmup()]
    # every variant of a rotating spec, every size of its menu
    assert warm == [("curve", "a", 4), ("curve", "a", 6), ("curve", "b", 4),
                    ("curve", "b", 6), ("rank", None, 4),
                    ("tolerance", "a", 0), ("tolerance", "b", 0),
                    ("resilience", "a", 0), ("resilience", "b", 0)]


def test_a_mix_can_repeat_requests():
    mix = dict(KINDS_MIX, repeat=0.5)
    calc = {"a": np.arange(16).reshape(2, 8)}
    gen = generate.Generator(mix, ["a"], calc, 11)
    reqs = [_dump(gen.next()) for _ in range(40)]
    assert 5 < 40 - len(set(reqs)) < 35


def test_every_mix_file_loads():
    for path in sorted((BENCH / "traffic").glob("*.json")):
        mix = generate.load_mix(path.stem)
        for spec in mix["cycle"]:
            assert hasattr(registry.module("kinds", spec["kind"]), "check")
    with pytest.raises(FileNotFoundError):
        registry.module("kinds", "no_such_kind")


# -- graphs and the reference -------------------------------------------------

def _params():
    from repro.core.loggps import cluster_params
    return cluster_params(L_us=3.0, o_us=5.0)


def _spec(**kw):
    args = {"tp": 2, "s": 30, "cycles": 1, "cycle_us": 27000.0,
            "phase_share": [0.4, 0.1, 0.25, 0.25]}
    args.update(kw)
    return {"name": "g", "skeleton": "lulesh", "args": args}


def test_lulesh_messages_follow_the_source():
    sk = registry.module("skeletons", "lulesh")
    with pytest.raises(ValueError):        # recursive doubling: 2^k ranks
        sk.schedule(**_spec(tp=3)["args"])
    P, steps = sk.schedule(**_spec(tp=4, s=30)["args"])
    rounds = [st[1] for st in steps if st[0] == "round"]
    dt, (sbn, posvel, monoq) = rounds[:6], rounds[6:]
    assert P == 64 and all(len(r) == 64 for r in dt)
    assert {m[2] for r in dt for m in r} == {8.0}
    # directed neighbour pairs on a 4^3 cube, not periodic: 6 face offsets
    # x 3*4*4, 12 edge offsets x 3*3*4, 8 corner offsets x 3*3*3
    assert len(sbn) == 6 * 48 + 12 * 36 + 8 * 27
    assert len(posvel) * 2 == len(sbn)
    assert all(dst < src for src, dst, _ in posvel)
    assert len(monoq) == 6 * 48
    assert sorted({m[2] for m in sbn}) == [3 * 8, 3 * 31 * 8, 3 * 31 ** 2 * 8]
    assert sorted({m[2] for m in posvel}) == \
        [6 * 8, 6 * 31 * 8, 6 * 31 ** 2 * 8]
    assert {m[2] for m in monoq} == {3 * 30 ** 2 * 8}
    computes = [st for st in steps if st[0] == "compute"]
    assert [c[1] for c in computes] == [0, 1, 2, 3]
    assert sum(c[2] for c in computes) == pytest.approx(27000.0)


def test_the_dt_allreduce_equals_the_programs_recursive_doubling():
    from repro.core import collectives
    from repro.core.graph import GraphBuilder
    p = _params()
    sk = registry.module("skeletons", "lulesh")
    P, steps = sk.schedule(**_spec(tp=2)["args"])
    ours = GraphBuilder(P, p.nclass)
    for st in steps[:3]:                          # the three dt rounds
        build._round(ours, st[1], p)
    theirs = GraphBuilder(P, p.nclass)
    collectives.allreduce(theirs, list(range(P)), 8.0, p,
                          algo="recursive_doubling")
    a, b = ours.finalize(), theirs.finalize()
    for f in ("kind", "vcost", "vrank", "esrc", "edst", "econst", "ebytes",
              "elat", "egap", "egclass", "elink", "level"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


@pytest.mark.parametrize("kw", [dict(tp=2, s=30, cycles=2),
                                dict(tp=2, s=4, cycles=3)])
def test_reference_agrees_with_the_scalar_engine(kw):
    from repro.core import dag
    p = _params()
    spec = _spec(**kw)
    rng = np.random.default_rng(0)
    jit = rng.uniform(-0.1, 0.1, registry.module(
        "skeletons", "lulesh").jitter_shape(**spec["args"]))
    g, calc = build.build(p, spec, jit)
    net = reference.Net({"L_us": 3.0, "G_ns_per_byte": 0.018, "o_us": 5.0,
                         "S_bytes": 256e3})
    ref, rcalc = reference.build_graph(net, spec, jit)
    assert (ref.nv, ref.ne) == (g.num_vertices, g.num_edges)
    assert (g.kind[calc.ravel()] == 0).all()
    np.testing.assert_array_equal(ref.cost[rcalc], g.vcost[calc])
    plan = dag.LevelPlan(g)
    Ls = np.array([3.0, 10.5, 47.25])
    T, lam = ref.forward(Ls)
    for i, L in enumerate(Ls):
        r = plan.forward(p.replace(L=(L,)))
        assert abs(T[i] - r.T) <= 1e-12 * r.T
        assert lam[i] == r.lam[0]


def test_work_counts_a_hand_checked_graph():
    # one cycle on 2^3 ranks: each rank has 7 neighbours (3 faces, 3 edges,
    # 1 corner), so 56 force messages, 28 position messages (one way),
    # 24 face messages and 3 allreduce rounds of 8: 132 messages, a send
    # and a receive vertex each, plus 4 compute vertices a rank:
    # 264 + 32 = 296 vertices; 132 message edges and each rank's 37
    # operations chained (36 edges a rank): 132 + 288 = 420 edges
    g, _ = build.build(_params(), _spec(tp=2, cycles=1))
    assert (g.num_vertices, g.num_edges) == (296, 420)
    b = work.forward_bytes(g.num_edges, g.num_vertices, scenarios=4,
                           width=8)
    assert work.static_edge_bytes(1) == 30
    assert b == 420 * 4 * 8 + 296 * 4 * 8 + 420 * 30 == 35512


# -- spans --------------------------------------------------------------------

def _ev(name, t0, t1, tid=1):
    return types.SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, tid=tid)


def test_self_time_subtracts_the_union_of_nested_spans():
    evs = [_ev("analysis.curve", 0, 100),
           _ev("sweep.stage", 10, 30), _ev("sweep.execute", 25, 60),
           _ev("sweep.compile", 40, 50),            # nested in execute
           _ev("sweep.stage", 120, 130),            # outside
           _ev("analysis.rank", 0, 50, tid=2)]      # other thread
    got = {(e.name, e.t0_ns): ns for e, ns in spans.self_ns(evs)}
    assert got[("analysis.curve", 0)] == 100 - 50
    assert got[("sweep.execute", 25)] == 35 - 10
    assert got[("analysis.rank", 0)] == 50
    assert spans.self_ms_per(evs, ["analysis."], 2) == (50 + 50) / 1e6 / 2
    assert spans.self_ms_per(evs, ["sweep.nothing"], 2) is None


# -- metric readers -----------------------------------------------------------

def _ctx(trace=True, spans_=True, forward_bytes=819e6):
    evs = [_ev("analysis.curve", 0, 4_000_000),
           _ev("sweep.canonicalize", 0, 1_000_000),
           _ev("sweep.execute", 1_000_000, 3_000_000)]
    return {"spans": evs if spans_ else [], "answered": 2,
            "records": [{"t0": 0.0, "t1": 0.5, "cells": 10},
                        {"t0": 0.5, "t1": 1.5, "cells": 10}],
            "elapsed_s": 2.0, "forward_bytes": forward_bytes,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": ({"busy_s": 1.5, "window_s": 2.0, "forward_s": 0.1}
                      if trace else None)}


@pytest.mark.parametrize("m", json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"],
    ids=lambda m: m["name"])
def test_every_per_layer_reader(m):
    read = run.reader("metrics", m["name"])
    v = read(_ctx())
    expect = {"service_self_ms": 0.5, "engine_host_ms": 0.5,
              "execute_ms": 1.0, "device_idle_pct": 25.0,
              "forward_roofline_pct": 1.0}[m["name"].split(".")[0]]
    assert v == pytest.approx(expect)
    # a reader that finds nothing to read returns nothing, never 0
    if m["source"] == "device_trace":
        assert read(_ctx(trace=False)) is None
    else:
        assert read(_ctx(spans_=False)) is None
    if m["name"].startswith("forward_roofline_pct"):
        assert read(_ctx(forward_bytes=0)) is None


def test_every_end_to_end_reader():
    ctx = _ctx()
    assert run.reader("end_to_end", "cells_per_s")(ctx) == 10.0
    assert run.reader("end_to_end", "request_ms")(ctx) == 1000.0
    assert run.reader("end_to_end", "request_p95_ms")(ctx) == \
        pytest.approx(975.0)        # inclusive quantile of [500, 1000]


# -- the device trace ---------------------------------------------------------

FIXTURE = BENCH / "tests" / "data" / "chip_trace.xplane.pb.gz"


def test_recorded_chip_trace_reduces_the_same_way_every_time():
    # recorded on a TPU v5 lite by record_trace.py (gzipped there after)
    want = json.loads((FIXTURE.parent / "chip_trace.json").read_text())
    for _ in range(2):
        got = devtrace.reduce_file(FIXTURE)
        assert got["busy_s"] == want["busy_s"]
        assert got["window_s"] == want["window_s"]
        assert got["forward_s"] == want["forward_s"]
        assert got["device_ops"] == want["device_ops"]
        assert 0 < got["busy_s"] <= got["window_s"]
        # a module spans its ops and the short gaps between them
        assert 0 < got["forward_s"] <= got["window_s"]


# -- the harness --------------------------------------------------------------

def test_refuses_to_run_without_a_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "TPU" in out.err


@pytest.mark.parametrize("name", CELLS + ["kinds"])
def test_a_tiny_run_is_correct_and_its_control_is_not(name):
    out = run_tiny(name, seconds=2.0 if name == "kinds" else 1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    for k, c in out["checks"].items():
        assert c["value"] <= c["limit"]
    assert "setup_s" in out["metrics"]
    if name == "kinds":
        assert set(out["latency_ms"]) == {"curve", "rank", "tolerance",
                                          "resilience"}
        assert set(out["checks"]) == {"T_rel_err", "lam_err", "tol_err"}
    ctl = run_tiny(name, control=1)
    assert not ctl["correct"], ctl["checks"]


def test_an_unknown_device_kind_has_no_peaks():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("cpu")


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_tiny_run_reports_its_span_metrics(name, monkeypatch):
    monkeypatch.setattr(run, "peaks_for", lambda kind: {"hbm_bytes_per_s":
                                                         1.0})
    out = run_tiny(name, trace=1)
    assert out["correct"]
    by_source = {}
    for m in run.load_cell(name)["per_layer"]:
        by_source.setdefault(m["source"], set()).add(m["name"])
    assert by_source["program_span"] <= set(out["metrics"])
    # the CPU has no device trace: no device number, no busy seconds
    assert not by_source["device_trace"] & set(out["metrics"])
    assert "busy_s" not in out["device"]


def _broken(monkeypatch, how):
    """Break ``Engine.run`` underneath the harness."""
    from repro.sweep import api
    orig = api.Engine.run

    def run_(self, *a, **kw):
        res = orig(self, *a, **kw)
        T = np.array(res.T)
        if how == "altered":
            T[..., T.shape[-1] // 2] *= 1.0 + 1e-6
        elif how == "half_batch":
            h = T.shape[-1] // 2
            T[..., T.shape[-1] - h:] = T[..., :h]
        return dataclasses.replace(res, T=T)

    monkeypatch.setattr(api.Engine, "run", run_)


@pytest.mark.parametrize("how", ["altered", "half_batch"])
@pytest.mark.parametrize("name", CELLS + ["kinds"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, how):
    _broken(monkeypatch, how)
    out = run_tiny(name)
    assert not out["correct"], out["checks"]


def test_a_wrong_lambda_or_tolerance_is_not_correct(monkeypatch):
    from repro.sweep import api, engine
    orig = api.Engine.run

    def run_(self, *a, **kw):
        res = orig(self, *a, **kw)
        if res.lam is None:
            return res
        lam = np.array(res.lam)
        lam[..., 0, :] += 1.0
        return dataclasses.replace(res, lam=lam)

    monkeypatch.setattr(api.Engine, "run", run_)
    assert run_tiny("lulesh512.grid")["checks"]["lam_err"]["value"] == 1.0
    monkeypatch.setattr(api.Engine, "run", orig)

    tol = engine.tolerance_batched

    def tol_(*a, **kw):
        return {p: v * 1.01 for p, v in tol(*a, **kw).items()}

    from repro.launch import analysis
    monkeypatch.setattr(analysis, "tolerance_batched", tol_)
    out = run_tiny("kinds", seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["tol_err"]["value"] > out["checks"]["tol_err"][
        "limit"]
