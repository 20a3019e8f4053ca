"""CPU tests of the ``grid_f32`` request kind's limits and of the readers of
the dense forward's metrics (``metrics/*.dense.py``), with readings checked
by hand.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The limits must pass the float64 reference's own answer and fail T rounded
to bfloat16, the precision below the contract's float32, and a lambda one
hop off where no breakpoint of T(L) lies within the bracket's step.
"""

from __future__ import annotations

import copy
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import check  # noqa: E402
import generate  # noqa: E402
import reference  # noqa: E402
import registry  # noqa: E402
import run  # noqa: E402

KIND = registry.module("kinds", "grid_f32")
CELL = "lulesh64.grid_f32"


@pytest.fixture(scope="module")
def case():
    """The cell's configuration at 2^3 ranks and 2 cycles, its reference
    graph, and one request of the cell's own traffic (32 x 16 points)."""
    cfg = copy.deepcopy(run.load_cell(CELL)["config"])
    cfg["graphs"][0]["args"].update(tp=2, cycles=2)
    jit = run.jitter(cfg, 2 ** 33 + 7)
    refs = reference.build(cfg, jit)
    mix = generate.load_mix("grid_f32")
    req = KIND.make(mix["cycle"][0], 0, np.random.default_rng(5), None)
    assert req["kind"] == "grid_f32" and len(req["lat"]) * len(req["gs"]) \
        == 512
    L0 = cfg["network"]["L_us"]
    L = np.repeat(L0 + req["lat"], len(req["gs"]))
    gs = np.tile(req["gs"], len(req["lat"]))
    T, lam = refs["lulesh"][0].forward(L, gs)
    return types.SimpleNamespace(refs=refs, req=req, L0=L0, L=L, gs=gs,
                                 T=T, lam=lam)


def _check(case, T, lam) -> dict:
    gaps = check.Gaps()
    gaps.declare(KIND.LIMITS)
    KIND.check({"req": case.req, "res": {"T": T, "lam": lam[:, None]}},
               case.refs, {"names": ["lulesh"], "L0": case.L0}, gaps)
    return {k: (v, gaps.limit[k]) for k, v in gaps.value.items()}


def test_the_limits_stay_under_bfloat16():
    # the T limit catches bfloat16's relative step (2^-8) with room, and
    # the bracket's step makes a one-hop error stand out of it
    assert KIND.LIMITS["T_rel_err"] < 1e-3
    assert KIND.LIMITS["lam_bracket_err"] == 0.0
    assert KIND.H_PER_EPS >= 10.0


def test_the_exact_float64_answer_passes(case):
    got = _check(case, case.T, case.lam)
    assert set(got) == set(KIND.LIMITS)
    assert all(v <= lim for v, lim in got.values()), got
    assert got["T_rel_err"][0] == 0.0
    assert got["lam_bracket_err"][0] == 0.0
    assert got["lam_inexact_pct"][0] == 0.0


def test_T_rounded_to_bfloat16_fails(case):
    import jax.numpy as jnp
    T16 = np.asarray(jnp.asarray(case.T, jnp.bfloat16), np.float64)
    assert not np.array_equal(T16, case.T)
    got = _check(case, T16, case.lam)
    assert got["T_rel_err"][0] > got["T_rel_err"][1], got
    assert not check.verdict(got, 1, 0)


@pytest.mark.parametrize("hop", [1.0, -1.0])
def test_a_lambda_one_hop_off_fails_away_from_breakpoints(case, hop):
    lo, hi = KIND.bracket(case.refs["lulesh"][0], case.L, case.gs, case.T)
    assert (lo <= case.lam).all() and (case.lam <= hi).all()
    # no breakpoint within h: T is linear over [L - h, L + h], and the
    # bracket is 2 / H_PER_EPS of a hop wide
    straight = np.nonzero(hi - lo <= 2.0 / KIND.H_PER_EPS + 1e-9)[0]
    assert straight.size >= 16
    for s in straight[:: max(1, straight.size // 8)]:
        lam = case.lam.copy()
        lam[s] += hop
        got = _check(case, case.T, lam)
        assert got["lam_bracket_err"][0] >= 1.0 - 1.0 / KIND.H_PER_EPS - 1e-9
        assert got["lam_inexact_pct"][0] == pytest.approx(100.0 / 512)
        assert not check.verdict(got, 1, 0)


# -- the readers of the dense forward's metrics -------------------------------

DENSE = ["execute_ms.dense", "level_us.dense", "trip_us.dense",
         "forward_roofline_pct.dense", "device_idle_pct.dense"]

#: of the pallas dispatch's 2 ms, 0.5 ms lie in a nested span (1.5 ms of
#: self time over 2 queries: 0.75 ms); 1.5 ms of wait over 300 levels (5
#: us) and 500 trips (3 us); 819 MB at 819 GB/s over 0.1 s of forward
#: module time (1%); 1.5 s busy of a 2 s window (25% idle)
EXPECT = {"execute_ms.dense": 0.75, "level_us.dense": 5.0,
          "trip_us.dense": 3.0, "forward_roofline_pct.dense": 1.0,
          "device_idle_pct.dense": 25.0}

PALLAS = {"backend": "pallas", "axes": "S", "stage_ns": 100_000,
          "dispatch_ns": 200_000, "wait_ns": 1_500_000,
          "readback_ns": 200_000, "levels": 300, "view": "pallas",
          "trips": 500}


def _ev(name, t0, t1, args=None):
    return types.SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, tid=1,
                                 args=args)


def _ctx(execute_args=PALLAS, other=True):
    evs = [_ev("sweep.canonicalize", 0, 1_000_000),
           _ev("sweep.execute", 1_000_000, 3_000_000, args=execute_args),
           _ev("sweep.congestion_fixed_point", 1_500_000, 2_000_000)]
    if other:
        # a dispatch of another view counts in none of them
        evs.append(_ev("sweep.execute", 4_000_000, 9_000_000, args=dict(
            PALLAS, backend="sparse", view="sparse", wait_ns=4_000_000,
            trips=1)))
    return {"spans": evs, "answered": 2, "forward_bytes": 819e6,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": 1.5, "window_s": 2.0, "forward_s": 0.1}}


def test_the_benchmark_lists_the_dense_readers_for_the_cell():
    names = [m["name"] for m in run.load_cell(CELL)["per_layer"]]
    assert sorted(names) == sorted(DENSE)


@pytest.mark.parametrize("name", DENSE)
def test_a_dense_reader_reads_the_hand_checked_value(name):
    assert run.reader("metrics", name)(_ctx()) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", DENSE)
@pytest.mark.parametrize("args", [
    # a program whose sweep.execute spans name no view and no trips
    {k: v for k, v in PALLAS.items() if k not in ("view", "trips")},
    {k: v for k, v in PALLAS.items() if k != "trips"},
    {k: v for k, v in PALLAS.items() if k != "view"},
    dict(PALLAS, view="sparse_pallas")], ids=["parent", "no_trips",
                                             "no_view", "other_view"])
def test_a_dense_reader_reads_nothing_without_a_dense_dispatch(name, args):
    assert run.reader("metrics", name)(_ctx(args, other=False)) is None
