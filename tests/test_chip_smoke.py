"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The phases are the same functions the script runs on the TPU; here the
(max,+) kernels run in Pallas interpret mode, which ``maxplus/ops.py``
resolves for the CPU backend.  The script itself must refuse to run without
a TPU.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = {"stencil": (2, 2, 2, 3), "grid": (4, 2), "chain": (8, 2),
        "arch": "llama3.2-3b", "mesh": (2, 2, 2), "sparse_S": 8,
        # below the tiny trace's envelope: the auto-sparse switch fires
        "max_dense_bytes": 1 << 16}


@pytest.fixture(autouse=True)
def _cpu_interpret():
    from repro.kernels.maxplus.ops import resolve_interpret
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True


def test_dense_phase_matches_oracle():
    recs = list(chip_smoke.phase_dense(TINY, platform="cpu"))
    assert [(r["backend"], r["dtype"]) for r in recs] == [
        ("segment", "float64"), ("pallas", "float32")]
    assert recs[0]["bit_exact"] and recs[0]["T_rel_err"] == 0.0
    assert recs[1]["T_rel_err"] <= chip_smoke.F32_RTOL
    assert all(r["xla_programs"] == 1 for r in recs)
    assert recs[1]["tpu_custom_call"] is False       # interpreted on CPU


def test_dense_phase_program_count_independent_of_history():
    """The cold call's program count is its own: a second run of the
    phase in one process, after every program it needs was built, still
    counts one new program per backend."""
    for _ in range(2):
        recs = list(chip_smoke.phase_dense(TINY, platform="cpu"))
        assert [(r["backend"], r["xla_programs"]) for r in recs] == [
            ("segment", 1), ("pallas", 1)]


def test_service_phase_replies_ok_without_fallbacks():
    recs = list(chip_smoke.phase_service(TINY, platform="cpu"))
    assert [r["kind"] for r in recs] == ["curve", "tolerance", "rank",
                                         "placement", "resilience"]
    place = next(r for r in recs if r["kind"] == "placement")
    assert place["stats"]["scalar_fallbacks"] == 0
    assert place["stats"]["plan_compiles"] == 1
    curve = recs[0]
    assert curve["bit_exact"] and curve["S"] == 8


def test_sparse_phase_auto_switches_and_matches_oracle():
    recs = list(chip_smoke.phase_sparse(TINY, platform="cpu"))
    assert [(r["backend"], r["dtype"]) for r in recs] == [
        ("sparse", "float64"), ("sparse", "float32")]
    assert recs[0]["bit_exact"]
    assert recs[1]["T_rel_err"] <= chip_smoke.F32_RTOL
    assert recs[1]["tpu_custom_call"] is False       # interpreted on CPU


def test_script_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        assert not json.loads(line).get("ok"), line


def test_interpret_mode_only_on_cpu(monkeypatch):
    from repro.kernels.maxplus import ops
    assert ops.resolve_interpret(False) is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="TPU only"):
        ops.resolve_interpret()


def test_compile_cache_dir_rule(monkeypatch):
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.setup_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
