"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

The traced window is the harness's ``bench.window`` annotation on the host.
On each ``/device:TPU:<n>`` plane:

* busy: the union of the intervals in which an operation ran (the
  ``XLA Ops`` line; a ``while`` op holds the ops of its body, so ops nest),
  averaged over the devices that ran anything;
* forward: the summed device time of the forward program's executions, the
  ``XLA Modules`` events whose name matches ``FORWARD_MODULES``;
* device_ops: the operations with the most self time (their time less that
  of the ops nested in them), by HLO instruction name;
* idle_gaps: the longest stretches with no operation, each named by what the
  host was doing in its middle: the innermost of the program's ``repro.obs``
  spans (put on the profiler's clock through the harness's per-request
  annotations) or else the harness's request annotation.

A trace holds a few hundred thousand op events per second of device time,
so every line is reduced in one streaming pass.
"""

from __future__ import annotations

import gzip
import heapq
import re
from pathlib import Path

#: the jitted forwards of ``repro.sweep.engine``: ``jax.jit`` of the vmapped
#: per-scenario ``one`` (segment and sparse cores) or of ``fwd`` (Pallas)
FORWARD_MODULES = re.compile(r"^jit_(one|fwd)(\(|$)")
TOP = 10


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir, spans=None, request_t0_ns=None):
    return reduce_file(find(trace_dir), spans, request_t0_ns)


def _ops(line, lo: float, hi: float):
    """One pass over an op line: busy ns, self ns by op name, and the
    TOP longest idle gaps (ns, midpoint) inside [lo, hi]."""
    busy = 0.0
    own: dict = {}
    gaps: list = []
    cur_a = cur_b = None              # the busy interval being merged
    last = lo                         # end of busy time so far
    stack: list = []                  # [end, name, nested ns]
    prev = -float("inf")
    for e in line.events:
        a = float(e.start_ns)
        if a < prev:
            raise ValueError("op events out of start order")
        prev = a
        b = a + float(e.duration_ns)
        if b <= lo or a >= hi:
            continue
        a, b = max(a, lo), min(b, hi)
        while stack and stack[-1][0] <= a:
            end, name, nested, dur = stack.pop()
            own[name] = own.get(name, 0.0) + dur - nested
        if stack:
            stack[-1][2] += b - a
        stack.append([b, _op_name(e.name), 0.0, b - a])
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                busy += cur_b - cur_a
            gap = a - last
            if gap > 0:
                item = (gap, (a + last) / 2)
                if len(gaps) < TOP:
                    heapq.heappush(gaps, item)
                else:
                    heapq.heappushpop(gaps, item)
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
        last = max(last, b)
    for end, name, nested, dur in stack:
        own[name] = own.get(name, 0.0) + dur - nested
    if cur_b is not None:
        busy += cur_b - cur_a
        if hi - last > 0:
            item = (hi - last, (hi + last) / 2)
            if len(gaps) < TOP:
                heapq.heappush(gaps, item)
            else:
                heapq.heappushpop(gaps, item)
    return busy, own, gaps


def reduce_file(path, spans=None, request_t0_ns=None):
    """The device numbers of one trace file (``.xplane.pb``, or the same
    gzipped), or None where no TPU ran an
    operation in the window.  ``spans`` (``repro.obs`` events on the
    ``perf_counter_ns`` clock) and ``request_t0_ns`` (the harness's clock
    at each traced request's start, in order) name the idle gaps."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    host, devs = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devs.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    win = [(a, b) for n, a, b in host if n == "bench.window"]
    if not win:
        raise ValueError(f"{path}: no bench.window annotation")
    lo, hi = win[0]
    named = sorted((a, b, n) for n, a, b in host if n != "bench.window")
    if spans and request_t0_ns and len(request_t0_ns) == len(named):
        offs = sorted(a - t for (a, _, _), t in zip(named, request_t0_ns))
        off = offs[len(offs) // 2]
        named += [(e.t0_ns + off, e.t1_ns + off, e.name) for e in spans]

    busy_s = fwd_s = 0.0
    own_all: dict = {}
    gaps_all: list = []
    n_dev = 0
    for plane in devs:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        busy, own, gaps = _ops(lines["XLA Ops"], lo, hi)
        if busy <= 0:
            continue
        n_dev += 1
        busy_s += busy / 1e9
        for k, v in own.items():
            own_all[k] = own_all.get(k, 0.0) + v / 1e9
        gaps_all.extend(gaps)
        if "XLA Modules" in lines:
            for e in lines["XLA Modules"].events:
                a = float(e.start_ns)
                b = a + float(e.duration_ns)
                if FORWARD_MODULES.match(e.name) and b > lo and a < hi:
                    fwd_s += (min(b, hi) - max(a, lo)) / 1e9
    if n_dev == 0:
        return None            # no device ran an operation: not measured
    gap_rows = []
    for dur, mid in sorted(gaps_all, reverse=True)[:TOP]:
        cover = [(b - a, n) for a, b, n in named if a <= mid <= b]
        gap_rows.append([min(cover)[1] if cover else "between requests",
                         dur / 1e9])
    return {"busy_s": busy_s / n_dev, "window_s": (hi - lo) / 1e9,
            "forward_s": fwd_s / n_dev,
            "device_ops": [[n, s] for n, s in sorted(
                own_all.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": gap_rows}
