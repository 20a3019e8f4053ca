"""(max,+)-semiring blocked mat-vec — LLAMP's level-relaxation hot loop.

The DAG engine's inner operation per topological level is
    t'[i] = max_j (A[i,j] + t[j])
over the level's dense-banded adjacency (A = cost of edge j→i, -inf when
absent).  A latency *sweep* evaluates K parameter points at once, so t is
[N, K] and the kernel is a (max,+) "matmul" — the TPU twist is that the MXU
can't run semirings, so the reduction runs on the VPU with the same
[bm × bn] VMEM blocking a matmul would use; K rides the 128-wide lane axis
(sweep points are embarrassingly lane-parallel).

Grid: (M/bm, K/bk, N/bn) with the reduction axis N innermost and the
scenario lanes tiled in ``bk`` = 128-wide blocks, so VMEM use is
independent of the scenario count.  Inside a grid step the [bm, bn] block
is reduced in ``bj``-column chunks: the [bm, bj, bk] candidate cube of one
chunk is the largest intermediate, sized to stay well inside the scoped
VMEM limit.  The dense kernels carry a leading graph axis (G = 1 for the
single-graph entry points).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
#: byte budget of one [bm, bj, bk] candidate cube (several are live at once
#: in the argmax kernels; the scoped VMEM limit is 16 MiB on v5e)
_CUBE_BYTES = 1 << 20


def _blocks(M: int, N: int, K: int, bm: int, bn: int):
    bm, bn, bk = min(bm, M), min(bn, N), min(LANES, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    bj = max(8, min(bn, _CUBE_BYTES // (4 * bm * bk)))
    while bn % bj:
        bj //= 2
    return bm, bn, bk, bj


def _params(n_axes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_axes - 1) + ("arbitrary",))


def _cols(x, j0: int, n: int):
    # static slices (plain indexing mixed with None lowers to a gather,
    # which Mosaic refuses)
    return jax.lax.slice_in_dim(x, j0, j0 + n, axis=1)


def _rows(x, j0: int, n: int):
    return jax.lax.slice_in_dim(x, j0, j0 + n, axis=0)


def _lex_argmax(vals, keys, ordinal0, hit=None):
    """Chunk-local lexicographic argmax of (value, key, ordinal) over axis 1
    of ``vals`` [bm, bj, bk] (keys broadcast against it; ``hit`` masks
    candidates out).  Exact comparisons keep the cross-chunk merge
    associative, so any blocking gives the same answer."""
    bv = jnp.max(vals, axis=1)                          # [bm, bk]
    tie = vals >= bv[:, None, :]
    if hit is not None:
        tie &= hit
    bk = jnp.max(jnp.where(tie, keys, NEG_INF), axis=1)
    tie &= keys >= bk[:, None, :]
    idx = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1) + ordinal0
    bi = jnp.max(jnp.where(tie, idx, -1), axis=1)
    return bv, bk, bi


def _lex_merge(acc, new):
    (av, ak, ai), (bv, bk, bi) = acc, new
    better = (bv > av) | ((bv == av) & ((bk > ak) | ((bk == ak) & (bi > ai))))
    return (jnp.where(better, bv, av), jnp.where(better, bk, ak),
            jnp.where(better, bi, ai))


def _maxplus_kernel(A_ref, t_ref, o_ref, acc_ref, *, n_n: int, bj: int):
    jn = pl.program_id(3)

    @pl.when(jn == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, NEG_INF)

    A = A_ref[0]                         # [bm, bn]
    t = t_ref[0]                         # [bn, bk]
    acc = acc_ref[...]
    # (max,+) product: acc[i,k] = max(acc[i,k], max_j A[i,j] + t[j,k])
    for j0 in range(0, A.shape[1], bj):
        acc = jnp.maximum(acc, jnp.max(_cols(A, j0, bj)[:, :, None]
                                       + _rows(t, j0, bj)[None], axis=1))
    acc_ref[...] = acc

    @pl.when(jn == n_n - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def maxplus_matvec_batched_kernel(A, t, *, bm: int = 128, bn: int = 128,
                                  interpret: bool = False):
    """Graph-batched (max,+) mat-vec: A [G, M, N], t [G, N, K] → [G, M, K].

    The graph axis rides the outermost grid dimension (one [bm, bn] block
    pipeline per graph), so a MultiPlan's per-level scatter-max over every
    packed graph is a single kernel launch; K (scenarios) rides the
    128-wide lane axis in ``bk``-wide blocks.
    """
    G, M, N = A.shape
    K = t.shape[2]
    bm, bn, bk, bj = _blocks(M, N, K, bm, bn)
    kernel = functools.partial(_maxplus_kernel, n_n=N // bn, bj=bj)
    return pl.pallas_call(
        kernel,
        grid=(G, M // bm, K // bk, N // bn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda g, i, k, j: (g, i, j)),
            pl.BlockSpec((1, bn, bk), lambda g, i, k, j: (g, j, k)),
        ],
        out_specs=pl.BlockSpec((1, bm, bk), lambda g, i, k, j: (g, i, k)),
        out_shape=jax.ShapeDtypeStruct((G, M, K), t.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        compiler_params=_params(4),
        interpret=interpret,
    )(A, t)


def maxplus_matvec_kernel(A, t, *, bm: int = 128, bn: int = 128,
                          interpret: bool = False):
    """A: [M, N] (−inf = no edge); t: [N, K] → [M, K]."""
    return maxplus_matvec_batched_kernel(A[None], t[None], bm=bm, bn=bn,
                                         interpret=interpret)[0]


def _maxplus_argmax_kernel(A_ref, t_ref, c_ref, o_ref, i_ref, k_ref,
                           accv_ref, acck_ref, acci_ref,
                           *, n_n: int, bn: int, bj: int):
    jn = pl.program_id(3)

    @pl.when(jn == 0)
    def _init():
        accv_ref[...] = jnp.full_like(accv_ref, NEG_INF)
        acck_ref[...] = jnp.full_like(acck_ref, NEG_INF)
        acci_ref[...] = jnp.full_like(acci_ref, -1)

    A = A_ref[0]                         # [bm, bn]
    t = t_ref[0]                         # [bn, bk]
    c = c_ref[0]                         # [bn, bk] tie key per candidate
    acc = (accv_ref[...], acck_ref[...], acci_ref[...])
    for j0 in range(0, bn, bj):
        cand = _cols(A, j0, bj)[:, :, None] + _rows(t, j0, bj)[None]
        # ordinal = column of the full N axis
        acc = _lex_merge(acc, _lex_argmax(cand, _rows(c, j0, bj)[None],
                                          jn * bn + j0))
    accv_ref[...], acck_ref[...], acci_ref[...] = acc

    @pl.when(jn == n_n - 1)
    def _finish():
        v = accv_ref[...]
        o_ref[0] = v.astype(o_ref.dtype)
        i_ref[0] = acci_ref[...]
        # the −∞ sentinel chain carries no key
        k_ref[0] = jnp.where(v > NEG_INF, acck_ref[...], NEG_INF)


def maxplus_matvec_argmax_batched_kernel(A, t, c, *, bm: int = 128,
                                         bn: int = 128,
                                         interpret: bool = False):
    """Graph-batched argmax-emitting (max,+): A [G, M, N], t/c [G, N, K] →
    (out [G, M, K], idx [G, M, K] int32, key [G, M, K] float32).  Graphs
    ride the outermost grid axis (as in
    :func:`maxplus_matvec_batched_kernel`); K (scenarios) rides the
    128-wide lane axis."""
    G, M, N = A.shape
    K = t.shape[2]
    bm, bn, bk, bj = _blocks(M, N, K, bm, bn)
    kernel = functools.partial(_maxplus_argmax_kernel, n_n=N // bn, bn=bn,
                               bj=bj)
    return pl.pallas_call(
        kernel,
        grid=(G, M // bm, K // bk, N // bn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda g, i, k, j: (g, i, j)),
            pl.BlockSpec((1, bn, bk), lambda g, i, k, j: (g, j, k)),
            pl.BlockSpec((1, bn, bk), lambda g, i, k, j: (g, j, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, k, j: (g, i, k)),
            pl.BlockSpec((1, bm, bk), lambda g, i, k, j: (g, i, k)),
            pl.BlockSpec((1, bm, bk), lambda g, i, k, j: (g, i, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, M, K), t.dtype),
            jax.ShapeDtypeStruct((G, M, K), jnp.int32),
            jax.ShapeDtypeStruct((G, M, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, bk), jnp.int32)],
        compiler_params=_params(4),
        interpret=interpret,
    )(A, t, c)


def maxplus_matvec_argmax_kernel(A, t, c, *, bm: int = 128, bn: int = 128,
                                 interpret: bool = False):
    """(max,+) mat-vec that also emits the realizing candidate's ordinal
    and tie key.

    A: [M, N] (−inf = no edge); t: [N, K] candidate values; c: [N, K]
    tie keys → (out [M, K], idx [M, K] int32, key [M, K] float32) where
    ``idx[i, k]`` is the lexicographic argmax over j of
    ``(A[i,j]+t[j,k], c[j,k], j)`` — the λ backtrace's "max cumulative
    slope, then max ordinal" rule among exact value ties — and
    ``key[i, k] = c[idx[i, k], k]``, the winner's key itself, so a caller
    that carries the key forward needs no gather through ``idx``.  Rows
    with no finite candidate return idx of the −∞ sentinel chain and key
    ``NEG_INF`` (mask both with ``out >= 0`` downstream).
    """
    o, i, k = maxplus_matvec_argmax_batched_kernel(
        A[None], t[None], c[None], bm=bm, bn=bn, interpret=interpret)
    return o[0], i[0], k[0]


def _maxplus_slotlist_argmax_kernel(d_ref, t_ref, c_ref, o_ref, i_ref,
                                    accv_ref, acck_ref, acci_ref,
                                    *, n_e: int, bm: int, be: int, bj: int):
    im, je = pl.program_id(0), pl.program_id(2)

    @pl.when(je == 0)
    def _init():
        accv_ref[...] = jnp.full_like(accv_ref, NEG_INF)
        acck_ref[...] = jnp.full_like(acck_ref, NEG_INF)
        acci_ref[...] = jnp.full_like(acci_ref, -1)

    d = d_ref[...]                       # [be, 1] int32 destination rows
    cand = t_ref[...]                    # [be, bk]
    c = c_ref[...]                       # [be, bk] tie key per slot
    # which of this block's slots land in this row block
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, be), 0) + im * bm
    hit = d[:, 0][None, :] == rows                      # [bm, be]
    acc = (accv_ref[...], acck_ref[...], acci_ref[...])
    for j0 in range(0, be, bj):
        h = _cols(hit, j0, bj)[:, :, None]
        vals = jnp.where(h, _rows(cand, j0, bj)[None], NEG_INF)
        # ordinal = position in the full E axis; hits only
        acc = _lex_merge(acc, _lex_argmax(vals, _rows(c, j0, bj)[None],
                                          je * be + j0, hit=h))
    accv_ref[...], acck_ref[...], acci_ref[...] = acc

    @pl.when(je == n_e - 1)
    def _finish():
        o_ref[...] = accv_ref[...].astype(o_ref.dtype)
        i_ref[...] = acci_ref[...]


def maxplus_slotlist_argmax_kernel(dst, cand, c, *, M: int, bm: int = 128,
                                   be: int = 128, interpret: bool = False):
    """Slot-list (CSR-style) (max,+) segment reduction with argmax.

    The dense kernels above pad every level to a rectangular [M, N]
    adjacency; this one consumes the compact edge list directly — the
    sparse backend's layout, where a level is E (slot → destination-row)
    pairs and nothing is materialized per absent edge.

    dst: [E, 1] int32 destination row per slot (point pad slots at a row
    ≥ M — they can never hit); cand: [E, K] candidate values (already
    source-value + edge-cost); c: [E, K] tie keys → (out [M, K],
    idx [M, K] int32) where ``out[m, k] = max over {e : dst[e] = m}`` of
    ``cand[e, k]`` (−∞ when the row has no slot) and ``idx[m, k]`` is the
    lexicographic argmax over those e of ``(cand[e,k], c[e,k], e)`` — the
    λ backtrace's "max cumulative slope, then max ordinal" rule among
    exact value ties (−1 when the row has no slot).

    Grid: (M/bm, K/bk, E/be) with slots innermost; compute is [bm × be]
    rectangular per block but *memory* is the O(E) slot list — the whole
    point at million-edge scale.
    """
    E, K = cand.shape
    bm, be, bk, bj = _blocks(M, E, K, bm, be)
    kernel = functools.partial(_maxplus_slotlist_argmax_kernel,
                               n_e=E // be, bm=bm, be=be, bj=bj)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, K // bk, E // be),
        in_specs=[
            pl.BlockSpec((be, 1), lambda i, k, j: (j, 0)),
            pl.BlockSpec((be, bk), lambda i, k, j: (j, k)),
            pl.BlockSpec((be, bk), lambda i, k, j: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, j: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, k, j: (i, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), cand.dtype),
            jax.ShapeDtypeStruct((M, K), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, bk), jnp.int32)],
        compiler_params=_params(3),
        interpret=interpret,
    )(dst, cand, c)
