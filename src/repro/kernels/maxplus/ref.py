"""Pure-jnp oracle for the (max,+) mat-vec."""

from __future__ import annotations

import jax.numpy as jnp


def maxplus_matvec_ref(A, t):
    """A: [M, N]; t: [N, K] → out[i,k] = max_j A[i,j] + t[j,k]."""
    return jnp.max(A[:, :, None] + t[None, :, :], axis=1)


def maxplus_slotlist_argmax_ref(dst, cand, c, M: int):
    """Oracle for the slot-list segment kernel: per output row m, the max
    over slots e with dst[e] = m of cand[e, k], plus the lexicographic
    (value, key, ordinal) argmax among exact ties (−∞ / −1 for rows with
    no slot)."""
    NEG_INF = -1e30
    d = jnp.asarray(dst).reshape(-1)                 # [E]
    hit = d[None, :] == jnp.arange(M, dtype=d.dtype)[:, None]   # [M, E]
    vals = jnp.where(hit[:, :, None], cand[None, :, :], NEG_INF)
    out = jnp.max(vals, axis=1)                      # [M, K]
    tie = (vals >= out[:, None, :]) & hit[:, :, None]
    bk = jnp.max(jnp.where(tie, c[None, :, :], NEG_INF), axis=1)
    tie &= c[None, :, :] >= bk[:, None, :]
    eidx = jnp.arange(cand.shape[0], dtype=jnp.int32)[None, :, None]
    idx = jnp.max(jnp.where(tie, eidx, -1), axis=1)
    return out, idx


def maxplus_matvec_argmax_ref(A, t, c):
    """Oracle for the argmax-emitting kernel: lexicographic argmax over j of
    (A[i,j]+t[j,k], c[j,k], j) with exact comparisons, plus the max value
    and the winner's key (−1e30 where no candidate is above −1e30)."""
    NEG_INF = -1e30
    cand = A[:, :, None] + t[None, :, :]             # [M, N, K]
    out = jnp.max(cand, axis=1)
    tie = cand >= out[:, None, :]
    bk = jnp.max(jnp.where(tie, c[None, :, :], -jnp.inf), axis=1)
    tie &= c[None, :, :] >= bk[:, None, :]
    jidx = jnp.arange(A.shape[1], dtype=jnp.int32)[None, :, None]
    idx = jnp.max(jnp.where(tie, jidx, -1), axis=1)
    key = jnp.where(out > NEG_INF, bk, NEG_INF).astype(jnp.float32)
    return out, idx, key
