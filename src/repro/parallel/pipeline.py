"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

The production mesh's ``pod`` axis can run as pure DP (default) or as a
pipeline-stage axis (``--pipeline``): each pod holds a contiguous slice of
periods and microbatch activations flow pod→pod over DCN via
``collective_permute`` — the LogGPS tracer models exactly this schedule
(one DCN message per microbatch per stage boundary), which is how the
LLAMP analysis compares PP-over-DCN vs DP-over-DCN latency tolerance.

Implementation: ``shard_map`` over the stage axis; `lax.scan` over
T = n_micro + n_stages − 1 ticks; each tick ppermutes the previous tick's
output forward and applies this stage's blocks to whatever is in flight.
Bubble fraction = (S−1)/T — choose n_micro ≥ 4·S to amortize (§Perf).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec


def pipeline_run(stage_fn: Callable, params_stage, x_micro, *, axis: str,
                 n_stages: int):
    """Run inside shard_map over `axis`.

    stage_fn(params_stage, x) -> x        (this stage's chunk of layers)
    x_micro: [n_micro, mb, ...] microbatched activations (stage 0's input;
             other stages ignore their local copy).
    Returns [n_micro, mb, ...] outputs valid on the LAST stage.
    """
    idx = jax.lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        prev_out, = carry
        # receive activation from the previous stage (stage 0 receives junk)
        recv = jax.lax.ppermute(prev_out, axis, fwd_perm)
        mb_idx = jnp.clip(t - idx, 0, n_micro - 1)
        my_in = jnp.where(idx == 0,
                          x_micro[mb_idx],
                          recv)
        active = (t >= idx) & (t < idx + n_micro)
        out = stage_fn(params_stage, my_in)
        out = jnp.where(active, out, prev_out)
        return (out,), out

    zero = jnp.zeros_like(x_micro[0])
    # mark the carry as axis-varying (each stage holds different data)
    zero = jax.lax.pcast(zero, (axis,), to="varying")
    (_,), outs = jax.lax.scan(tick, (zero,), jnp.arange(T))
    # last stage emits microbatch m at tick m + (n_stages-1)
    take = jnp.arange(n_micro) + (n_stages - 1)
    return outs[take]


def build_pipeline_fn(stage_fn: Callable, mesh, axis: str = "pod"):
    """shard_map wrapper: params sharded by stage on `axis` leading dim,
    x replicated; output gathered from the last stage."""
    n_stages = mesh.shape[axis]

    def run(params_stages, x_micro):
        # params_stages leaves: [n_stages, ...] sharded on axis
        def inner(p, xm):
            p_local = jax.tree.map(lambda a: a[0], p)   # this stage's slice
            out = pipeline_run(stage_fn, p_local, xm, axis=axis,
                               n_stages=n_stages)
            # only the last stage holds valid outputs: broadcast them so the
            # result is replicated (valid under out_specs P())
            idx = jax.lax.axis_index(axis)
            out = jax.lax.psum(
                jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out)), axis)
            return out

        pspecs = jax.tree.map(lambda _: PSpec(axis), params_stages)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(pspecs, PSpec()),
            out_specs=PSpec(),
        )(params_stages, x_micro)

    return run
