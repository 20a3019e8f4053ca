"""Pallas kernel sweeps: shapes × dtypes vs pure-jnp oracles (interpret)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, linear_scan, maxplus_matvec
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.linear_scan.ref import linear_scan_ref
from repro.kernels.maxplus.ref import maxplus_matvec_ref

ATTN_CASES = [
    # B, Tq, Tk, H, Hkv, d, dv, causal
    (2, 128, 128, 4, 2, 64, 64, True),
    (1, 256, 256, 8, 8, 128, 128, True),
    (2, 128, 256, 4, 1, 64, 32, False),
    (1, 64, 512, 2, 2, 128, 128, False),
    (1, 128, 128, 16, 4, 192, 128, True),   # MLA-like dk≠dv
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Tq, Tk, H, Hkv, d, dv, causal = case
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Tq, H, d), dtype)
    k = jax.random.normal(ks[1], (B, Tk, Hkv, d), dtype)
    v = jax.random.normal(ks[2], (B, Tk, Hkv, dv), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Tq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Tk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Tk, dv)
    ref = jnp.moveaxis(
        flash_attention_ref(qf, kf, vf, causal=causal).reshape(B, H, Tq, dv),
        1, 2)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


SCAN_CASES = [(2, 64, 128, 8), (1, 128, 256, 16), (3, 32, 64, 4)]


@pytest.mark.parametrize("case", SCAN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan_matches_ref(case, dtype):
    B, T, D, S = case
    ks = jax.random.split(jax.random.key(1), 4)
    a = jax.random.uniform(ks[0], (B, T, D, S), dtype, 0.5, 0.99)
    b = (jax.random.normal(ks[1], (B, T, D, S)) * 0.1).astype(dtype)
    c = jax.random.normal(ks[2], (B, T, S), dtype)
    h0 = jax.random.normal(ks[3], (B, D, S), jnp.float32)
    y, h = linear_scan(a, b, c, h0, bd=64, ct=32)
    yr, hr = linear_scan_ref(a, b, c, h0)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=atol)


@pytest.mark.parametrize("M,N,K", [(128, 128, 8), (256, 384, 16), (64, 64, 128)])
def test_maxplus_matches_ref(M, N, K):
    ks = jax.random.split(jax.random.key(2), 2)
    A = jnp.where(jax.random.uniform(ks[0], (M, N)) < 0.3,
                  jax.random.uniform(ks[0], (M, N)) * 10, -1e30)
    t = jax.random.uniform(ks[1], (N, K)) * 100
    o = maxplus_matvec(A, t, bm=64, bn=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(maxplus_matvec_ref(A, t)),
                               atol=1e-5)


def test_maxplus_semiring_identity():
    """(max,+) with A = 0 on the diagonal, -inf off it, is the identity."""
    n, K = 64, 8
    A = jnp.full((n, n), -1e30).at[jnp.arange(n), jnp.arange(n)].set(0.0)
    t = jax.random.uniform(jax.random.key(3), (n, K)) * 50
    o = maxplus_matvec(A, t, bm=32, bn=32)
    np.testing.assert_allclose(np.asarray(o), np.asarray(t), atol=1e-6)


@pytest.mark.parametrize("M,N,K,bm,bn", [(64, 128, 8, 32, 32),
                                         (128, 128, 16, 64, 64)])
def test_maxplus_argmax_matches_ref(M, N, K, bm, bn):
    """The argmax-emitting kernel returns the lexicographic
    (value, tie-key, ordinal) argmax across blocked reductions — exact ties
    injected on purpose so the key and ordinal stages both fire."""
    from repro.kernels.maxplus import (maxplus_matvec_argmax,
                                      maxplus_matvec_argmax_ref)
    rng = np.random.default_rng(11)
    A = np.where(rng.random((M, N)) < 0.3,
                 rng.uniform(0.0, 10.0, (M, N)), -1e30).astype(np.float32)
    t = rng.uniform(0.0, 100.0, (N, K)).astype(np.float32)
    c = rng.integers(0, 6, (N, K)).astype(np.float32)
    # exact value ties across block boundaries: identical columns + edges
    t[3] = t[N - 5]
    A[7, 3] = A[7, N - 5] = 1.0
    c[3] = c[N - 5]                      # key tie too → ordinal decides
    o, i, k = maxplus_matvec_argmax(A, t, c, bm=bm, bn=bn)
    ro, ri, rk = maxplus_matvec_argmax_ref(jnp.asarray(A), jnp.asarray(t),
                                           jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(rk))
    assert int(np.asarray(i)[7].max()) >= 0


@pytest.mark.parametrize("M,E,K,bm,be", [(64, 128, 8, 32, 32),
                                         (128, 256, 16, 64, 64)])
def test_maxplus_slotlist_argmax_matches_ref(M, E, K, bm, be):
    """The slot-list segment kernel reduces a compact edge list (no dense
    [M, N] padding) to the same lexicographic (value, tie-key, ordinal)
    argmax the dense kernel produces — ties injected across slot-block
    boundaries, plus empty rows and out-of-range pad slots."""
    from repro.kernels.maxplus import (maxplus_slotlist_argmax,
                                       maxplus_slotlist_argmax_ref)
    rng = np.random.default_rng(13)
    dst = rng.integers(0, M - 4, E).astype(np.int32)  # rows M-4..M-1 empty
    dst[-3:] = M                                      # pad slots: never hit
    cand = rng.uniform(0.0, 100.0, (E, K)).astype(np.float32)
    c = rng.integers(0, 5, (E, K)).astype(np.float32)
    # exact value ties across slot-block boundaries: same row, same value,
    # dominating the row so the tie chain (not some third slot) realizes it
    dst[3] = dst[E - 5] = 7
    cand[3] = cand[E - 5] = 1000.0
    c[3] = c[E - 5]                      # key tie too → ordinal decides
    o, i = maxplus_slotlist_argmax(jnp.asarray(dst[:, None]),
                                   jnp.asarray(cand), jnp.asarray(c),
                                   M=M, bm=bm, be=be)
    ro, ri = maxplus_slotlist_argmax_ref(jnp.asarray(dst), jnp.asarray(cand),
                                         jnp.asarray(c), M)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # empty rows report the no-slot sentinels
    assert np.all(np.asarray(i)[M - 4:] == -1)
    assert np.all(np.asarray(o)[M - 4:] <= -1e29)
    assert int(np.asarray(i)[7, 0]) == E - 5          # ordinal tie-break


def test_maxplus_argmax_batched_matches_ref():
    from repro.kernels.maxplus import (maxplus_matvec_argmax_batched,
                                      maxplus_matvec_argmax_ref)
    rng = np.random.default_rng(12)
    G, M, N, K = 3, 32, 64, 8
    A = np.where(rng.random((G, M, N)) < 0.4,
                 rng.uniform(0.0, 5.0, (G, M, N)), -1e30).astype(np.float32)
    t = rng.uniform(0.0, 50.0, (G, N, K)).astype(np.float32)
    c = rng.integers(0, 4, (G, N, K)).astype(np.float32)
    o, i, k = maxplus_matvec_argmax_batched(A, t, c, bm=16, bn=16)
    for g in range(G):
        ro, ri, rk = maxplus_matvec_argmax_ref(jnp.asarray(A[g]),
                                               jnp.asarray(t[g]),
                                               jnp.asarray(c[g]))
        np.testing.assert_array_equal(np.asarray(o[g]), np.asarray(ro))
        np.testing.assert_array_equal(np.asarray(i[g]), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(k[g]), np.asarray(rk))


def _tied_argmax_case(rng, G, M, N, K):
    """A 0/−inf adjacency with several in-edges a row and the last four
    rows empty, integer candidate values and small integer keys: exact
    value ties, and key ties among them, on most rows and lanes."""
    A = np.full((G, M, N), -1e30, np.float32)
    for g in range(G):
        for m in range(M - 4):
            A[g, m, rng.choice(N, size=rng.integers(2, 9),
                               replace=False)] = 0.0
    t = rng.integers(0, 4, (G, N, K)).astype(np.float32)
    c = rng.integers(0, 3, (G, N, K)).astype(np.float32)
    return A, t, c


@pytest.mark.parametrize("G,M,N,K,bm,bn", [(1, 64, 128, 8, 32, 32),
                                           (1, 128, 256, 128, 64, 64),
                                           (3, 32, 64, 16, 16, 16)])
def test_maxplus_argmax_key_is_the_winners_key(G, M, N, K, bm, bn):
    """The key output is the tie key of the candidate the index names, bit
    for bit, on every row and lane with a finite candidate — under forced
    value and key ties across block boundaries — and ``NEG_INF`` on rows
    with no in-edge; the reference agrees on all three outputs."""
    from repro.kernels.maxplus import (maxplus_matvec_argmax,
                                      maxplus_matvec_argmax_batched,
                                      maxplus_matvec_argmax_ref)
    A, t, c = _tied_argmax_case(np.random.default_rng(17 + G + M), G, M, N,
                                K)
    if G == 1:
        outs = [x[None] for x in maxplus_matvec_argmax(A[0], t[0], c[0],
                                                       bm=bm, bn=bn)]
    else:
        outs = maxplus_matvec_argmax_batched(A, t, c, bm=bm, bn=bn)
    o, i, k = (np.asarray(x) for x in outs)
    assert k.dtype == np.float32
    lanes = np.arange(K)[None, :]
    for g in range(G):
        ro, ri, rk = maxplus_matvec_argmax_ref(
            jnp.asarray(A[g]), jnp.asarray(t[g]), jnp.asarray(c[g]))
        np.testing.assert_array_equal(o[g], np.asarray(ro))
        np.testing.assert_array_equal(i[g], np.asarray(ri))
        np.testing.assert_array_equal(k[g], np.asarray(rk))
        live = o[g] >= 0.0
        assert live[:M - 4].all() and not live[M - 4:].any()
        assert (i[g][live] >= 0).all()
        won = c[g][np.where(live, i[g], 0), lanes]
        np.testing.assert_array_equal(k[g][live].view(np.uint32),
                                      won[live].view(np.uint32))
        assert (k[g][M - 4:] == np.float32(-1e30)).all()
        # ties really fired: some row's max is reached by several in-edges
        hits = (A[g][:, :, None] + t[g][None]) == o[g][:, None, :]
        assert (hits.sum(axis=1)[live] > 1).any()


def test_model_attention_consistent_with_kernel():
    """models.layers.sdpa (XLA twin) ≡ Pallas flash kernel on GQA shapes."""
    from repro.models.layers import sdpa
    B, Tq, H, Hkv, d = 2, 128, 8, 2, 64
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (B, Tq, H, d))
    k = jax.random.normal(ks[1], (B, Tq, Hkv, d))
    v = jax.random.normal(ks[2], (B, Tq, Hkv, d))
    a = sdpa(q, k, v, causal=True, chunk=64)
    bm = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bm), atol=3e-5)
