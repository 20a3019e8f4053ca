"""CPU tests of the readers of the forward's span attributes
(``forward_host_ms.*``, ``level_us.*``), with readings checked by hand.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The readers take the ``sweep.execute`` spans of a run's context, as
``bench/run.py`` hands them over; ``test_bench.py``'s
``test_a_traced_tiny_run_reports_its_span_metrics`` runs them end to end.
"""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import run  # noqa: E402

NAMES = [m["name"] for m in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
    if m["name"].split(".")[0] in ("forward_host_ms", "level_us")]

#: what sweep.execute records of its 2 ms: 0.5 ms on the host (0.25 ms a
#: request of 2) and 1.5 ms of wait for the device over 300 levels (5 us)
EXECUTE_ARGS = {"backend": "sparse", "axes": "S", "stage_ns": 100_000,
                "dispatch_ns": 200_000, "wait_ns": 1_500_000,
                "readback_ns": 200_000, "levels": 300}

EXPECT = {"forward_host_ms": 0.25, "level_us": 5.0}


def _ev(name, t0, t1, args=None):
    return types.SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, tid=1,
                                 args=args)


def _ctx(execute_args=EXECUTE_ARGS, spans_=True, answered=2):
    evs = [_ev("analysis.curve", 0, 4_000_000),
           _ev("sweep.canonicalize", 0, 1_000_000),
           _ev("sweep.execute", 1_000_000, 3_000_000, args=execute_args)]
    return {"spans": evs if spans_ else [], "answered": answered}


def test_the_benchmark_lists_both_cells_of_each_reader():
    assert sorted(NAMES) == ["forward_host_ms.service",
                             "forward_host_ms.study", "level_us.service",
                             "level_us.study"]


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_reads_the_hand_checked_attributes(name):
    assert run.reader("metrics", name)(_ctx()) == \
        pytest.approx(EXPECT[name.split(".")[0]])


@pytest.mark.parametrize("name", NAMES)
def test_the_readers_sum_over_every_dispatch(name):
    # a second dispatch of 100 levels with 0.1 ms of host and 0.9 ms of
    # wait: (0.5 + 0.1) ms / 2 requests, (1.5 + 0.9) ms / 400 levels
    second = dict(EXECUTE_ARGS, stage_ns=50_000, dispatch_ns=30_000,
                  readback_ns=20_000, wait_ns=900_000, levels=100)
    ctx = _ctx()
    ctx["spans"].append(_ev("sweep.execute", 3_000_000, 4_000_000,
                            args=second))
    expect = {"forward_host_ms": 0.3, "level_us": 6.0}
    assert run.reader("metrics", name)(ctx) == \
        pytest.approx(expect[name.split(".")[0]])


@pytest.mark.parametrize("ctx", [
    _ctx(spans_=False),
    # a program whose sweep.execute spans carry no phases or levels
    _ctx(execute_args={"backend": "sparse", "axes": "S"}),
    _ctx(execute_args=None),
], ids=["no_spans", "no_attributes", "no_args"])
@pytest.mark.parametrize("name", NAMES)
def test_a_reader_that_finds_nothing_reads_none(name, ctx):
    assert run.reader("metrics", name)(ctx) is None
