#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine with a TPU.  The cell names a
configuration (``bench/configs/<config>.json``: graphs, network, execution
policy) and a traffic mix (``bench/traffic/<mix>.json``, read by
``generate.py``).  The run builds the configuration's graphs from the seed
(``build.py``), hands them to the entry the mix targets
(``bench/targets/<target>.py``: ``repro.sweep.Engine`` or
``repro.launch.analysis.AnalysisService``), sends one request of each
program shape the mix uses (set-up), then sends requests in a closed loop
for ``--seconds``.  After the window a sample of the answers is recomputed
by the plain reference (``check.py``) to decide ``correct``.

With ``--trace 0`` the result line carries the cell's end-to-end metrics
(``bench/end_to_end/<name>.py``); with ``--trace 1`` the window runs under
the JAX profiler and under ``repro.obs`` span collection, and the line
carries the cell's per-layer metrics (``bench/metrics/<name>.py``), the
device's busy and window seconds and a breakdown.  ``readings.py`` runs
the configuration's lower-precision control policy through ``run``; the
benchmark's runs never use it.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits with 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import build  # noqa: E402
import check  # noqa: E402
import generate  # noqa: E402
import reference  # noqa: E402
import registry  # noqa: E402
import work  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- what BENCHMARK.json names ------------------------------------------------

def load_cell(name: str) -> dict:
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    return {"workload": w, "config": cfg, "mix": generate.load_mix(
        w["traffic"]), "end_to_end": e2e, "per_layer": per_layer}


def reader(kind: str, name: str):
    """The ``read(ctx)`` of ``bench/<kind>/<name>.py``."""
    return registry.module(kind, name).read


def devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def peaks_for(kind: str) -> dict:
    """The published peaks of a device kind (``bench/peaks.json``); a kind
    that is not in the table is an error, never a default."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return peaks[kind]


def jitter(cfg: dict, seed: int) -> dict:
    """Per-graph [row, rank] compute jitter, uniform in +-jitter, drawn
    from the run's seed."""
    rng = generate.rng_for(seed, 3)
    return {spec["name"]: rng.uniform(
        -cfg["jitter"], cfg["jitter"],
        registry.module("skeletons", spec["skeleton"]).jitter_shape(
            **spec["args"]))
        for spec in cfg["graphs"]}


# -- the system under test ----------------------------------------------------

class Program:
    """The configuration's graphs behind the entry the mix targets."""

    def __init__(self, cfg: dict, mix: dict, jit: dict, control: bool):
        from repro.core.loggps import cluster_params
        from repro.sweep import ExecPolicy
        net = cfg["network"]
        self.params = cluster_params(L_us=net["L_us"],
                                     G_ns_per_byte=net["G_ns_per_byte"],
                                     o_us=net["o_us"], S_bytes=net["S_bytes"])
        self.names, self.graphs, self.calc = [], {}, {}
        for spec in cfg["graphs"]:
            g, calc = build.build(self.params, spec, jit[spec["name"]])
            self.names.append(spec["name"])
            self.graphs[spec["name"]] = g
            self.calc[spec["name"]] = calc
        policy = ExecPolicy(**cfg["control_policy" if control else "policy"])
        self.target = registry.module("targets", mix["target"])
        self.on_axis: list = []          # graphs on the entry's axis
        self.entry = self.target.build(self, policy, mix)

    @staticmethod
    def kind(req: dict):
        return registry.module("kinds", req["kind"])

    def call(self, req: dict):
        """(answer, ok) for one request."""
        call = getattr(self.kind(req), "call", None) or self.target.call
        return call(self, req)

    def cells(self, req: dict) -> int:
        kind = self.kind(req)
        return kind.cells(self, req) if hasattr(kind, "cells") else 1

    def forward_bytes(self, req: dict, width: int) -> int:
        """Bytes the request's forward moves at least, where its kind
        counts them (else 0)."""
        kind = self.kind(req)
        return (kind.forward_bytes(self, req, width)
                if hasattr(kind, "forward_bytes") else 0)


# -- one run ------------------------------------------------------------------

def _trace_ctx(on: bool):
    if not on:
        return contextlib.nullcontext(None)
    from repro import obs
    return obs.collect()


def window(prog: Program, gen, seconds: float, trace: bool):
    """Closed-loop requests for ``seconds``; a request started inside the
    window runs to its end and counts.  With ``trace`` every request's
    program spans are collected, and the profiler records the requests
    that start in the first ``TRACE_SECONDS`` and at least one whole cycle
    of the mix, so every kind is in it (a second of device time makes some
    hundred thousand op events)."""
    import jax
    records, spans, starts, failed = [], [], [], 0
    profiling = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        profiling = jax.profiler.TraceAnnotation("bench.window")
        profiling.__enter__()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    try:
        while time.perf_counter() < t_end:
            if (profiling and time.perf_counter() >= t0 + TRACE_SECONDS
                    and len(starts) >= len(gen.mix["cycle"])):
                profiling.__exit__(None, None, None)
                jax.profiler.stop_trace()
                profiling = None
            req = gen.next()
            with _trace_ctx(trace) as sp, \
                    jax.profiler.TraceAnnotation(f"bench.{req['kind']}"):
                s0 = time.perf_counter()
                if profiling:
                    starts.append(time.perf_counter_ns())
                res, ok = prog.call(req)
                s1 = time.perf_counter()
            if sp is not None:
                spans.extend(sp)
            if not ok:
                failed += 1
                print(f"request failed: {res}", file=sys.stderr)
                continue
            records.append({"req": req, "res": res, "t0": s0, "t1": s1,
                            "cells": prog.cells(req),
                            "traced": profiling is not None})
    finally:
        if profiling:
            profiling.__exit__(None, None, None)
            jax.profiler.stop_trace()
    t1 = records[-1]["t1"] if records else time.perf_counter()
    return records, spans, starts, failed, max(t1, t_end) - t0


class CompileClock:
    """JAX's backend-compile seconds (persistent-cache reads included) and
    compile-cache hits, from ``jax.monitoring`` events (as ``chip_smoke.py``
    counts them)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


class GcClock:
    """Pauses of Python's cyclic garbage collector (``gc.callbacks``), on
    the window's clock: to tell a collection from other stalls."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.pauses: list = []            # (start s in window, ms, gen)
        self._start = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.pauses.append((self._start - self.t0,
                                1e3 * (now - self._start), info["generation"]))
            self._start = None

    def close(self) -> dict:
        gc.callbacks.remove(self._cb)
        top = max(self.pauses, key=lambda p: p[1], default=(0.0, 0.0, 0))
        return {"collections": len(self.pauses),
                "total_ms": sum(p[1] for p in self.pauses),
                "max_ms": top[1], "max_at_s": top[0], "max_gen": top[2]}


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(args, cell=None, device_check=devices) -> dict:
    """One run of one cell; returns the result line's object.  ``cell``
    (``load_cell``'s dict) defaults to the workload ``BENCHMARK.json``
    names; ``device_check`` returns the devices the cell runs on (or
    raises :class:`NoChip`)."""
    cell = cell if cell is not None else load_cell(args.workload)
    cfg, mix = cell["config"], cell["mix"]
    devs = device_check(cell["workload"]["chips"])
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    # every program the run compiles goes to the persistent cache, so a
    # cell's second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    clock = CompileClock()
    jit = jitter(cfg, args.seed)
    prog = Program(cfg, mix, jit, bool(getattr(args, "control", 0)))
    gen = generate.Generator(mix, prog.names, prog.calc, args.seed)
    for req in gen.warmup():
        res, ok = prog.call(req)
        if not ok:
            raise RuntimeError(f"warm-up request failed: {res}")
    # set-up's garbage (the graph builders' lists) is collected in set-up,
    # not by a collection that happens to fall inside the window
    gc.collect()
    setup_s = time.perf_counter() - T_START
    in_setup = clock.snapshot()

    trace = bool(args.trace)
    gc_clock = GcClock(time.perf_counter())
    records, spans, starts, failed, elapsed = window(prog, gen, args.seconds,
                                                     trace)
    gc_window = gc_clock.close()
    # nothing may compile inside the window: reported, so a shape the
    # warm-up missed shows
    in_window = {k: v - in_setup[k] for k, v in clock.snapshot().items()}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}

    ctx = {"records": records, "elapsed_s": elapsed,
           "answered": len(records), "spans": spans, "trace": None}
    breakdown = None
    if trace:
        import devtrace
        ctx["peaks"] = peaks_for(device["kind"])
        ctx["trace"] = devtrace.reduce_dir(TRACE_DIR, spans, starts)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        width = work.WIDTH[cfg["contract"]["dtype"]]
        ctx["forward_bytes"] = sum(prog.forward_bytes(r["req"], width)
                                   for r in records if r["traced"])
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
        elif device["platform"] == "tpu":
            raise RuntimeError("the profiler trace holds no operation on "
                               "the TPU")

    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = reader("metrics", m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            v = (setup_s if m["name"] == "setup_s"
                 else reader("end_to_end", m["name"])(ctx))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs
    prog.entry = None
    refs = reference.build(cfg, jit)
    numbers = check.compare(records, refs, {"names": prog.on_axis,
                                            "L0": cfg["network"]["L_us"]},
                            args.seed)
    attempted = len(records) + failed
    out = {"correct": check.verdict(numbers, attempted, failed),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    lat: dict = {}
    for r in records:
        lat.setdefault(r["req"]["kind"], []).append(1e3 * (r["t1"] - r["t0"]))
    # every latency in window order, to find a stall's place
    out["latency_ms"] = {k: {"n": len(v), "min": min(v), "max": max(v),
                             "sum": sum(v), "each": v}
                         for k, v in lat.items()}
    out["compile"] = {"setup": in_setup, "window": in_window}
    out["gc"] = gc_window
    out["checks"] = {k: {"value": _finite(v), "limit": lim}
                     for k, (v, lim) in numbers.items()}
    return out


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e300


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
