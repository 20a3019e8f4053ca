"""jit'd wrappers for the (max,+) kernels.

The kernels compile for the TPU.  On the CPU backend (tests, laptops) they
run in Pallas interpret mode; any other platform raises rather than fall
back to the interpreter without a word — see :func:`resolve_interpret`.
"""

from __future__ import annotations

import functools

import jax

from .kernel import (maxplus_matvec_argmax_batched_kernel,
                     maxplus_matvec_argmax_kernel,
                     maxplus_matvec_batched_kernel, maxplus_matvec_kernel,
                     maxplus_slotlist_argmax_kernel)


def resolve_interpret(interpret=None) -> bool:
    """Interpret mode for a kernel call: an explicit bool wins; otherwise
    compiled on the TPU, interpreted on the CPU, and an error anywhere
    else."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the (max,+) Pallas kernels compile for the TPU only; backend "
        f"{backend!r} would run them in interpret mode — use "
        "backend='segment' or run with JAX_PLATFORMS=cpu")


def _x32():
    """Trace kernels with 64-bit types off: callers (the sparse backend)
    may run inside ``jax.enable_x64``, where Python scalars in kernel
    bodies and block index maps would become 64-bit values that Mosaic
    cannot lower.  The kernels take and return 32-bit arrays only."""
    return jax.enable_x64(False)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def maxplus_matvec(A, t, *, bm: int = 128, bn: int = 128, interpret: bool = None):
    with _x32():
        return maxplus_matvec_kernel(A, t, bm=bm, bn=bn,
                                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def maxplus_matvec_argmax(A, t, c, *, bm: int = 128, bn: int = 128,
                          interpret: bool = None):
    """(max,+) mat-vec emitting the realizing candidate's ordinal and tie
    key: the λ backtrace consumes the [M, K] int32 index plane
    (lexicographic argmax of (value, tie key c, ordinal)), the forward's
    slope bookkeeping the [M, K] winning key."""
    with _x32():
        return maxplus_matvec_argmax_kernel(
            A, t, c, bm=bm, bn=bn, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("M", "bm", "be", "interpret"))
def maxplus_slotlist_argmax(dst, cand, c, *, M: int, bm: int = 128,
                            be: int = 128, interpret: bool = None):
    """Slot-list segment (max,+) with lexicographic argmax — the compact
    per-level edge-list reduction behind ``ExecPolicy(backend="sparse")``:
    dst [E, 1] int32, cand/c [E, K] → (out [M, K], idx [M, K] int32)."""
    with _x32():
        return maxplus_slotlist_argmax_kernel(
            dst, cand, c, M=M, bm=bm, be=be,
            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def maxplus_matvec_argmax_batched(A, t, c, *, bm: int = 128, bn: int = 128,
                                  interpret: bool = None):
    """[G, M, N] ⊗ [G, N, K] → ([G, M, K], [G, M, K] int32 argmax,
    [G, M, K] winning tie key)."""
    with _x32():
        return maxplus_matvec_argmax_batched_kernel(
            A, t, c, bm=bm, bn=bn, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def maxplus_matvec_batched(A, t, *, bm: int = 128, bn: int = 128,
                           interpret: bool = None):
    """[G, M, N] ⊗ [G, N, K] → [G, M, K]; graphs on the outer grid axis."""
    with _x32():
        return maxplus_matvec_batched_kernel(
            A, t, bm=bm, bn=bn, interpret=resolve_interpret(interpret))
