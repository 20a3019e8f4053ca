"""Compile-only checks against a described TPU v5e (no chip needed).

Every (max,+) entry point is compiled for one chip of a described
``v5e:2x2`` topology at 1024 scenario lanes and a 512-wide envelope, and so
is one float64 segment forward — what the TPU compiler refuses (VMEM
overflow, unsupported layouts) fails here instead of on the chip.  The
topology is described inside a module-scoped fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

S, ENV, G = 1024, 512, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's programs can be written to the persistent cache
    # but never read back here: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _entry_points():
    from repro.kernels.maxplus import ops
    f32 = jnp.float32
    return {
        "plain": (lambda A, t: ops.maxplus_matvec(A, t, interpret=False),
                  [(ENV, ENV), (ENV, S)], [f32, f32]),
        "argmax": (lambda A, t, c: ops.maxplus_matvec_argmax(
                       A, t, c, interpret=False),
                   [(ENV, ENV), (ENV, S), (ENV, S)], [f32] * 3),
        "batched": (lambda A, t: ops.maxplus_matvec_batched(
                        A, t, interpret=False),
                    [(G, ENV, ENV), (G, ENV, S)], [f32, f32]),
        "batched_argmax": (lambda A, t, c: ops.maxplus_matvec_argmax_batched(
                               A, t, c, interpret=False),
                           [(G, ENV, ENV), (G, ENV, S), (G, ENV, S)],
                           [f32] * 3),
        "slotlist": (lambda d, t, c: ops.maxplus_slotlist_argmax(
                         d, t, c, M=ENV, interpret=False),
                     [(ENV, 1), (ENV, S), (ENV, S)],
                     [jnp.int32, f32, f32]),
    }


@pytest.mark.parametrize("name", ["plain", "argmax", "batched",
                                  "batched_argmax", "slotlist"])
def test_maxplus_entry_point_compiles_for_v5e(name, one_chip):
    fn, shapes, dtypes = _entry_points()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    compiled = _compile(fn, *args)
    # the scenario axis is tiled: scoped VMEM stays bounded at any S
    assert compiled.memory_analysis() is not None


def test_segment_f64_forward_compiles_for_v5e(one_chip):
    """The default backend's float64 λ program compiles for the TPU."""
    from repro import sweep
    from repro.core import synth
    from repro.core.loggps import cluster_params
    from repro.sweep import engine as sweep_engine
    p = cluster_params()
    g = synth.stencil2d(4, 4, 6, params=p)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(cache=None))
    with jax.enable_x64():
        args = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
                for a in eng._arrays("segment")]
        L = jax.ShapeDtypeStruct((128, 1), jnp.float64, sharding=one_chip)
        compiled = sweep_engine._get_forward("segment", True).lower(
            *args, L, L).compile()
    assert "f64" in compiled.as_text()


def test_sparse_f32_forward_compiles_for_v5e(one_chip, monkeypatch):
    """The float32 sparse flavour runs the slot-list kernel inside the
    engine's ``enable_x64`` scope: the kernel must stay free of float64
    constants there."""
    from repro import sweep
    from repro.core import synth
    from repro.core.loggps import cluster_params
    from repro.sweep import engine as sweep_engine
    p = cluster_params()
    g = synth.stencil2d(4, 4, 6, params=p)
    sp = sweep.compile_sparse(g, p)
    eng = sweep.Engine(sp, params=p, policy=sweep.ExecPolicy(cache=None))
    with jax.enable_x64():
        args = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
                for a in eng._arrays("sparse")]
        L = jax.ShapeDtypeStruct((128, 1), jnp.float64, sharding=one_chip)
        fwd = sweep_engine._get_forward("sparse_pallas", True,
                                        sparse_dims=(sp.Emax_lv, sp.Vmax_lv))
        # the forward resolves interpret mode from the (CPU) default
        # backend; compile the kernel itself as on the chip
        from repro.kernels.maxplus import ops
        monkeypatch.setattr(ops, "resolve_interpret",
                            lambda interpret=None: False)
        compiled = fwd.lower(*args, L, L).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_f32_forward_compiles_for_v5e(one_chip, lulesh_graph,
                                           monkeypatch):
    """The dense Pallas λ forward at ``lulesh_64r``'s envelope (LULESH at
    4³ ranks, 6 cycles: 1,024 trips of 64 vertices and 128 edges) and the
    ``grid_f32`` traffic's 512 scenarios compiles for the TPU, kernel
    included, and fits one chip."""
    from repro import sweep
    from repro.kernels.maxplus import ops
    from repro.sweep import engine as sweep_engine
    g, p = lulesh_graph(4, 6, 0.1, 1)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(
        backend="pallas", cache=None))
    args = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
            for a in eng._arrays("pallas")]
    assert args[0].shape == (1024, 64, 128)
    L = jax.ShapeDtypeStruct((512, 1), jnp.float32, sharding=one_chip)
    # the forward resolves interpret mode from the (CPU) default backend;
    # compile the kernel itself as on the chip
    monkeypatch.setattr(ops, "resolve_interpret",
                        lambda interpret=None: False)
    compiled = sweep_engine._get_forward("pallas", True).lower(
        *args, L, L).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1 << 30


def _level_loop_ops(hlo: str, scope: str) -> list:
    """(opcode, index-operand dims) of every gather and scatter a
    forward's level loop (its ``scope`` name scope) compiled to."""
    import re
    dims = {m.group(1): [int(d) for d in m.group(2).split(",") if d]
            for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]",
                                 hlo, re.M)}
    ops = []
    for line in hlo.splitlines():
        m = re.search(r"= \S+ (gather|scatter)\(%[\w.\-]+, %([\w.\-]+)", line)
        if m and f"/{scope}/" in line:
            ops.append((m.group(1), dims.get(m.group(2))))
    return ops


def test_sparse_f64_level_loop_has_no_per_scenario_gather(one_chip,
                                                          lulesh_graph):
    """The float64 sparse λ forward on a LULESH plan takes the in-edge
    view: its level loop gathers only through indices every scenario
    shares (no index operand carries the scenario axis) and holds no
    ``scatter-max``."""
    from repro import sweep
    from repro.sweep import engine as sweep_engine
    g, p = lulesh_graph(2, 1)
    sp = sweep.compile_sparse(g, p)
    assert sp.step == "indeg"
    S = 128
    assert S not in (sp.Emax_lv, sp.Vmax_lv, sp.Dmax)
    eng = sweep.Engine(sp, params=p, policy=sweep.ExecPolicy(cache=None,
                                                             backend="sparse"))
    with jax.enable_x64():
        arrs = eng._arrays("sparse") + eng._arrays("indeg")
        args = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
                for a in arrs]
        L = jax.ShapeDtypeStruct((S, 1), jnp.float64, sharding=one_chip)
        fwd = sweep_engine._get_forward(
            "sparse", True, sparse_dims=(sp.Emax_lv, sp.Vmax_lv, sp.Dmax))
        hlo = fwd.lower(*args, L, L).compile().as_text()
    ops = _level_loop_ops(hlo, "sparse_level")
    assert any(op == "gather" for op, _ in ops)
    assert not [d for op, d in ops if op == "scatter"]
    assert not [d for op, d in ops if op == "gather" and S in d]


def test_dense_f32_level_loop_has_no_per_scenario_gather(one_chip,
                                                         lulesh_graph,
                                                         monkeypatch):
    """The dense Pallas λ forward at ``lulesh_64r``'s envelope and the
    ``grid_f32`` traffic's 512 scenarios takes each level's new slope sums
    from the argmax kernel's key output: its level loop (``dense_level``
    scope) gathers only through indices every scenario shares, and still
    holds the kernel."""
    from repro import sweep
    from repro.kernels.maxplus import ops as kops
    from repro.sweep import engine as sweep_engine
    g, p = lulesh_graph(4, 6, 0.1, 1)
    eng = sweep.Engine(g, params=p, policy=sweep.ExecPolicy(
        backend="pallas", cache=None))
    args = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
            for a in eng._arrays("pallas")]
    S = 512
    assert S not in args[0].shape
    L = jax.ShapeDtypeStruct((S, 1), jnp.float32, sharding=one_chip)
    monkeypatch.setattr(kops, "resolve_interpret",
                        lambda interpret=None: False)
    hlo = sweep_engine._get_forward("pallas", True).lower(
        *args, L, L).compile().as_text()
    ops = _level_loop_ops(hlo, "dense_level")
    assert any(op == "gather" for op, _ in ops)
    assert not [d for op, d in ops if op == "gather" and S in d]
    assert any("tpu_custom_call" in line and "/dense_level/" in line
               for line in hlo.splitlines())
