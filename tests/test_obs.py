"""repro.obs: span tracer, metrics registry, compile watcher — plus the
thread-safety regression for the shared SweepCache."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.trace import Tracer, summarize
from repro.sweep.cache import SweepCache


# -- tracer -------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    tr = Tracer()
    s1 = tr.span("a")
    s2 = tr.span("b", k=1)
    assert s1 is s2                       # the _NOOP singleton: no per-call
    with s1:                              # allocation on the disabled path
        pass
    assert tr.events() == []


def test_span_nesting_records_parent_and_order():
    tr = Tracer()
    tr.enable()
    with tr.span("outer"):
        with tr.span("inner", k="v"):
            pass
    evs = tr.events()
    assert [e.name for e in evs] == ["inner", "outer"]   # exit order
    inner, outer = evs
    assert inner.parent == "outer" and outer.parent is None
    assert inner.args == {"k": "v"}
    assert inner.t0_ns >= outer.t0_ns and inner.t1_ns <= outer.t1_ns
    assert inner.dur_ms >= 0.0


def test_collect_works_while_disabled_and_is_thread_local():
    tr = Tracer()
    assert not tr.enabled
    with tr.collect() as spans:
        with tr.span("only-here"):
            pass
        # another thread's spans must not leak into this sink
        def other():
            with tr.span("other-thread"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert [e.name for e in spans] == ["only-here"]
    assert tr.events() == []              # global buffer untouched
    # sink removed: spans after the scope are no-ops again
    with tr.span("after"):
        pass
    assert len(spans) == 1


def test_trace_context_stamps_events():
    tr = Tracer()
    with tr.collect() as spans, tr.trace_context("req-7"):
        with tr.span("a"):
            pass
    assert spans[0].trace == "req-7"
    # generated id when none given, restored after scope
    with tr.collect() as spans2, tr.trace_context() as tid:
        assert len(tid) == 16
        with tr.span("b"):
            pass
    assert spans2[0].trace == tid
    with tr.collect() as spans3:          # the id is gone after its scope
        with tr.span("c"):
            pass
    assert spans3[0].trace is None


def test_chrome_trace_export(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.trace_context("t-1"):
        with tr.span("phase", size=3):
            pass
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "phase"
    assert ev["dur"] >= 0 and "ts" in ev and "pid" in ev and "tid" in ev
    assert ev["args"]["trace"] == "t-1" and ev["args"]["size"] == 3


def test_set_on_a_live_span_lands_in_its_event_and_export():
    tr = Tracer()
    tr.enable()
    with tr.span("phase", size=3) as sp:
        sp.set(levels=7, done=True)
        sp.set(levels=8)                  # later values win
    with tr.span("bare") as sp:           # a span opened without args
        sp.set(count=4)
    phase, bare = tr.events()
    assert phase.args == {"size": 3, "levels": 8, "done": True}
    assert bare.args == {"count": 4}
    recs = {r["name"]: r for r in tr.to_chrome_trace()["traceEvents"]}
    assert recs["phase"]["args"]["levels"] == 8
    assert recs["phase"]["args"]["done"] is True
    assert recs["bare"]["args"] == {"count": 4}


def test_set_on_the_disabled_span_is_a_noop_on_the_shared_object():
    tr = Tracer()
    with tr.span("a") as sp:
        sp.set(levels=3, wait_ns=10)
    assert sp is obs_trace._NOOP          # still the one shared object,
    assert not hasattr(sp, "__dict__")    # which has nowhere to keep them
    assert tr.events() == []


def test_importing_obs_loads_no_jax():
    # the profiler mirror looks JAX up only where the process loaded it
    code = ("import sys, repro.obs; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=60).returncode == 0


def test_summarize_aggregates_by_name():
    tr = Tracer()
    with tr.collect() as spans:
        for _ in range(3):
            with tr.span("x"):
                pass
        with tr.span("y"):
            pass
    s = summarize(spans)
    assert s["x"]["n"] == 3 and s["y"]["n"] == 1
    assert s["x"]["ms"] >= 0.0


def test_add_event_retrospective():
    tr = Tracer()
    with tr.collect() as spans:
        tr.add_event("compile", 1000, 5_001_000, new_programs=2)
    (ev,) = spans
    assert ev.name == "compile" and ev.args == {"new_programs": 2}
    assert abs(ev.dur_ms - 5.0) < 1e-9


def test_tracer_bounded_buffer():
    tr = Tracer(max_events=4)
    tr.enable()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 4 and evs[0].name == "s6"
    tr.clear()
    assert tr.events() == []


# -- metrics ------------------------------------------------------------------

def test_counter_render_and_snapshot():
    reg = Registry()
    c = reg.counter("foo_total", "Foo happened.", labels=("k",))
    c.inc(k="a")
    c.inc(2, k="a")
    c.inc(k="b")
    text = reg.render()
    assert "# HELP foo_total Foo happened." in text
    assert "# TYPE foo_total counter" in text
    assert 'foo_total{k="a"} 3' in text
    assert 'foo_total{k="b"} 1' in text
    snap = reg.snapshot()
    assert snap["foo_total"]["type"] == "counter"
    assert {"labels": {"k": "a"}, "value": 3.0} in snap["foo_total"]["series"]
    assert c.value(k="a") == 3.0


def test_gauge_set_and_unlabeled_render():
    reg = Registry()
    g = reg.gauge("temp")
    g.set(1.5)
    g.inc(0.5)
    assert "temp 2\n" in reg.render()     # whole floats render short
    assert g.value() == 2.0


def test_histogram_cumulative_buckets():
    reg = Registry()
    h = reg.histogram("lat_seconds", "Latency.", labels=("kind",),
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, kind="q")
    text = reg.render()
    assert 'lat_seconds_bucket{kind="q",le="0.1"} 1' in text
    assert 'lat_seconds_bucket{kind="q",le="1"} 3' in text
    assert 'lat_seconds_bucket{kind="q",le="10"} 4' in text
    assert 'lat_seconds_bucket{kind="q",le="+Inf"} 5' in text
    assert 'lat_seconds_count{kind="q"} 5' in text
    snap = reg.snapshot()["lat_seconds"]["series"][0]
    assert snap["count"] == 5 and snap["sum"] == pytest.approx(56.05)


def test_registry_get_or_create_and_type_mismatch():
    reg = Registry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    assert reg.get("x_total") is a
    assert reg.get("missing") is None


def test_label_validation():
    reg = Registry()
    c = reg.counter("y_total", labels=("a", "b"))
    with pytest.raises(ValueError, match="expects labels"):
        c.inc(a="1")                      # missing b
    with pytest.raises(ValueError, match="expects labels"):
        c.inc(a="1", b="2", c="3")        # extra label


def test_registry_reset_keeps_metric_objects():
    reg = Registry()
    c = reg.counter("z_total")
    c.inc()
    reg.reset()
    assert reg.counter("z_total") is c
    assert c.value() == 0.0


def test_metric_increments_are_thread_safe():
    reg = Registry()
    c = reg.counter("hammer_total", labels=("t",))
    n_threads, n_incs = 8, 2000

    def work():
        for _ in range(n_incs):
            c.inc(t="x")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(t="x") == n_threads * n_incs


# -- SweepCache thread-safety (satellite regression) --------------------------

def test_sweep_cache_concurrent_hammer():
    cache = SweepCache(capacity=8)
    keys = [f"k{i}" for i in range(32)]
    n_threads, n_ops = 8, 500
    errors: list = []

    def hammer(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(n_ops):
                k = keys[int(rng.integers(len(keys)))]
                if cache.get(k) is None:
                    cache.put(k, ("v", k))
        except Exception as e:  # noqa: BLE001 — any corruption must surface
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(cache) <= 8
    st = cache.stats
    assert st.hits + st.misses == n_threads * n_ops
    assert st.evictions > 0               # capacity 8 << 32 keys: LRU churned


def test_sweep_cache_metrics_flow_to_registry():
    before_h = obs.REGISTRY.get("sweep_cache_hits_total") \
        .value(patched="false")
    before_m = obs.REGISTRY.get("sweep_cache_misses_total") \
        .value(patched="false")
    cache = SweepCache(capacity=4)
    assert cache.get("nope") is None
    cache.put("yes", 1)
    assert cache.get("yes") == 1
    assert obs.REGISTRY.get("sweep_cache_hits_total") \
        .value(patched="false") == before_h + 1
    assert obs.REGISTRY.get("sweep_cache_misses_total") \
        .value(patched="false") == before_m + 1


# -- engine integration -------------------------------------------------------

jax = pytest.importorskip("jax")


def _stencil(p):
    from repro.core import synth
    # a distinctive shape (odd iters) so this module's programs are its own
    return synth.stencil2d(5, 4, 11, params=p)


@pytest.fixture(scope="module")
def warm_engine():
    from repro import sweep
    from repro.core.loggps import cluster_params

    p = cluster_params(L_us=3.0, o_us=5.0)
    g = _stencil(p)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    grid = sweep.latency_grid(p, np.linspace(0.0, 40.0, 7))
    eng.run(grid)                         # compile before the tests measure
    return eng, grid, p


def test_compile_watcher_cold_then_warm(warm_engine):
    eng, grid, p = warm_engine
    w = obs.CompileWatcher()
    assert w.programs() >= 1              # the fixture's compile is visible
    with w.watch("warm") as rec:
        eng.run(grid)
    assert rec.new_programs == 0          # warm re-run: no new programs
    assert rec.wall_s > 0.0
    snap = w.snapshot()
    assert snap and all(isinstance(v, int) for v in snap.values())


def test_compile_watcher_scoped_cell(warm_engine):
    eng, grid, p = warm_engine
    cell = obs.forward_cell("segment", True)
    w = obs.CompileWatcher(cells=[cell])
    total = obs.CompileWatcher()
    assert w.programs() <= total.programs()
    with w.watch("warm") as rec:
        eng.run(grid)
    assert rec.new_programs == 0


def test_engine_emits_spans_under_collect(warm_engine):
    eng, grid, p = warm_engine
    assert not obs.enabled()              # collect() alone must suffice
    with obs.collect() as spans:
        eng.run(grid)
    names = {e.name for e in spans}
    assert {"sweep.canonicalize", "sweep.stage",
            "sweep.execute", "sweep.lam_backtrace"} <= names
    ex = next(e for e in spans if e.name == "sweep.execute")
    assert ex.args["backend"] == "segment"


def test_results_bit_identical_tracing_on_vs_off(warm_engine):
    eng, grid, p = warm_engine
    was = obs.enabled()
    try:
        obs.disable()
        off = eng.run(grid)
        obs.enable()
        on = eng.run(grid)
    finally:
        obs.enable() if was else obs.disable()
    assert np.array_equal(on.T, off.T)
    assert np.array_equal(on.lam, off.lam)
    assert np.array_equal(on.rho, off.rho)


def test_query_counter_and_occupancy_gauge(warm_engine):
    from repro import sweep
    eng, grid, p = warm_engine
    qc = obs.REGISTRY.get("sweep_queries_total")
    before_off = qc.value(backend="segment", axes="S", cache="off")
    eng.run(grid)                         # cache=None policy → "off"
    assert qc.value(backend="segment", axes="S",
                    cache="off") == before_off + 1
    occ = obs.REGISTRY.get("sweep_envelope_occupancy")
    assert 0.0 < occ.value(axis="slots") <= 1.0
    assert 0.0 < occ.value(axis="S") <= 1.0
    # hit/miss outcomes through a private cache
    cached = sweep.Engine(eng.plan, policy=sweep.ExecPolicy(
        cache=sweep.SweepCache()))
    before_miss = qc.value(backend="segment", axes="S", cache="miss")
    before_hit = qc.value(backend="segment", axes="S", cache="hit")
    cached.run(grid)
    cached.run(grid)
    assert qc.value(backend="segment", axes="S",
                    cache="miss") == before_miss + 1
    assert qc.value(backend="segment", axes="S",
                    cache="hit") == before_hit + 1


def test_compile_events_carry_query_signature(warm_engine):
    from repro import sweep
    from repro.core import synth
    from repro.core.loggps import cluster_params

    p = cluster_params(L_us=2.0, o_us=4.0)
    # a fresh distinctive shape: forces a compile attributed via WATCHER
    g = synth.stencil2d(2, 7, 5, params=p)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    grid = sweep.latency_grid(p, np.linspace(0.0, 30.0, 13))
    n_before = len(obs.WATCHER.events())
    eng.run(grid)
    evs = obs.WATCHER.events()[n_before:]
    assert evs, "fresh-shape dispatch did not attribute a compile"
    sig = evs[-1].signature
    assert sig["backend"] == "segment" and sig["axes"] == "S"
    assert "envelope" in sig and "S" in sig
    assert evs[-1].new_programs >= 1 and evs[-1].wall_s > 0.0


#: the forward that runs (the span's ``view``) under each policy
VIEWS = {"segment": {"backend": "segment"}, "pallas": {"backend": "pallas"},
         "sparse": {"backend": "sparse"},
         "sparse_pallas": {"backend": "sparse", "dtype": "float32"}}


@pytest.mark.parametrize("view", list(VIEWS))
def test_execute_span_records_its_phases_and_work(view, warm_engine):
    from repro import sweep
    _, grid, p = warm_engine
    g = _stencil(p)                  # the fixture's shape: warm programs
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(
        cache=None, **VIEWS[view]))
    with obs.collect() as spans:
        eng.run(grid)
    (ex,) = [e for e in spans if e.name == "sweep.execute"]
    phases = [ex.args[k] for k in ("stage_ns", "dispatch_ns", "wait_ns",
                                   "readback_ns")]
    # a float64 sparse dispatch also names its level step
    assert set(ex.args) == {"backend", "axes", "stage_ns", "dispatch_ns",
                            "wait_ns", "readback_ns", "levels", "view",
                            "trips"} | (
                                {"step"} if view == "sparse" else set())
    if view == "sparse":
        assert ex.args["step"] == eng._sparse_plan().step
    assert ex.args["backend"] == VIEWS[view]["backend"]
    assert ex.args["view"] == view
    assert all(isinstance(v, int) and v >= 0 for v in phases)
    assert sum(phases) <= ex.t1_ns - ex.t0_ns
    # the graph's own level count, and the loop's bucketed trip count
    trips = (eng._sparse_plan().level_ptr.shape[0] - 1
             if view.startswith("sparse") else eng.plan.vsrc.shape[0])
    assert ex.args["levels"] == g.nlevels < trips == ex.args["trips"]
    assert isinstance(ex.args["trips"], int)
    # phases are attributes, never child spans of sweep.execute
    assert not [e for e in spans if e.parent == "sweep.execute"]


def test_a_span_is_mirrored_only_while_the_profiler_records(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("before") as sp:         # JAX loaded, no session
        assert sp._annotation is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("during") as sp:
            assert sp._annotation is not None
    finally:
        jax.profiler.stop_trace()
    assert [e.name for e in tr.events()] == ["before", "during"]


def test_spans_land_on_the_profiler_host_plane(tmp_path, warm_engine):
    from jax.profiler import ProfileData
    eng, grid, p = warm_engine
    with obs.collect() as spans:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("test.request"):
                eng.run(grid)
        finally:
            jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                host.setdefault(e.name, []).append(
                    (plane.name, line.name, e.start_ns,
                     e.start_ns + e.duration_ns))
    assert {e.name for e in spans} <= set(host)
    (ex,) = host["sweep.execute"]
    (req,) = host["test.request"]
    assert ex[:2] == req[:2]              # one thread's line of the host
    assert req[2] <= ex[2] and ex[3] <= req[3]
    (ev,) = [e for e in spans if e.name == "sweep.execute"]
    assert ev.parent == "test.request"
