"""Batched max-plus evaluation of LogGPS scenario grids (jit + vmap).

This module owns the jitted cores and the populated-axis forward cache
(:func:`_get_forward`): graph [G], candidate-cost [K] and scenario [S]
batch axes compose freely via vmap (and any one of them can shard across
devices).  The user-facing evaluator is :class:`repro.sweep.api.Engine`.

One call evaluates a whole :class:`~repro.sweep.scenarios.ScenarioBatch`
against a :class:`~repro.sweep.compile.CompiledPlan`:

    T[s]        makespan per scenario
    λ[s, c]     ∂T/∂L_c — messages of class c on the critical path, recovered
                by the same argmax critical-path backtrace (with the scalar
                engine's max-slope tie-break) so results match
                ``core.dag.LevelPlan.forward`` to float64 round-off, and the
                HiGHS lower-bound marginals of the explicit LP
    ρ[s, c]     latency share L_c·λ_c / T

Backends
--------
``segment`` (default): pure-``jnp`` per-level relaxation over the compiled
per-vertex in-edge tensors — gather, max-reduce, ``dynamic_update_slice``;
no scatters, which is what makes it fast on CPU and TPU alike.  Runs in
float64 inside a scoped ``enable_x64`` so the sweep is bit-compatible with
the numpy engine.  The per-scenario axis is ``vmap``'d.

``pallas``: the ``repro.kernels.maxplus`` TPU kernel as the inner scatter —
each level's scatter-max is a (max,+) mat-vec of a 0/−inf incidence matrix
with per-edge candidate values, scenarios riding the 128-wide lane axis.
λ/ρ requests run the argmax-emitting kernel variant (per-level realizing
edge slots recorded forward, consumed by a reverse backtrace scan), so the
pallas backend serves T *and* sensitivities natively — no segment
redispatch.  Float32 accumulators (like the TPU VPU): tolerance ≈ 1e-6
relative vs segment.

``sparse``: compact CSR-style slot lists (``compile.SparsePlan``) — each
level is a fixed-size window of the level-sorted edge list, so memory is
O(nv + ne) with no dense padding at all.  Each destination's in-edges are
one run of that list; where the plan's ``[Dmax, Vmax_lv]`` in-edge view
pads at most twice the window (``SparsePlan.step == "indeg"``), the level
max and the λ argmax are maxima and masked selects over that view,
through window positions every scenario shares — no scatter and no
per-scenario gather in the level loop.  A plan with a high-in-degree
vertex (a many-to-one gather) keeps a ``segment_max`` over window-local
destinations instead.  Float64 with the same tie-break comparisons as
``segment`` (T and λ bit-identical) on either step; the scenario axis is
``vmap``'d and is the only batch axis.  :class:`repro.sweep.api.Engine`
auto-switches to it when a graph's dense envelope would blow past
``MAX_DENSE_BYTES``.

λ on the segment backend is **two-pass** by default: a values-only
``fori_loop`` forward recording per-level argmax slots, then a reverse
backtrace scan — bit-identical to the original fused single-loop backtrace
(kept under ``fused=True`` as the reference) at roughly the values-only
program's compile cost.

Device sharding: ``ExecPolicy(shard=...)`` splits one populated axis — the
scenarios, the MultiPlan's leading graph axis or the candidate-cost axis —
across local devices with ``shard_map``; per-element arithmetic is
unchanged, so sharded results are bit-identical to single-device runs.

Candidate costs: ``run(..., costs=CostBatch)`` adds a third batch axis —
K patched cost blocks (same plan structure, new per-edge constants) vmap
alongside the scenario axis on either backend, λ/ρ included.  Structure
tensors stay unbatched inside the vmap, so every cost block of every call
reuses the ONE compiled program of the plan's shape bucket: the zero-
recompile path behind ``core.placement``'s greedy search.

Structure blocks: ``Query(structure=StructureBatch)`` adds a B variant
axis over the *structure* tensors instead — rewired slot sources and edge
masks batched, everything untouched broadcast — so a whole topology study
(edge re-wirings, or separately-compiled plans stamped onto a union
envelope) runs as ONE compiled program per super-envelope, λ tie-breaks
re-derived per variant in-kernel.

Also here: lockstep-batched versions of the bisection loops from
``core.dag`` (``tolerance_batched``, ``breakpoints_batched``) — every probe
round becomes ONE engine call over all active intervals.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Sequence

import numpy as np

from repro.core.loggps import LogGPS

from .compile import _bucket
from .scenarios import latency_grid

if TYPE_CHECKING:
    from .api import Engine

BIG = 1e30          # matches kernels.maxplus NEG_INF magnitude
ATOL = 1e-12        # the scalar engine's tie tolerances (dag.LevelPlan)


# -- jitted forwards (module level: the jit cache is shared across engines,
#    and CompiledPlan's bucketed shapes make distinct graphs reuse programs) --

def _jax():
    import jax  # deferred: repro.core must import without jax present
    return jax


#: latency-count × latency matmuls run at full precision: the TPU's default
#: f32 matmul rounds its inputs to bfloat16, which would put ~0.4% error on
#: every edge cost (CPU results are unchanged)
_HIGHEST = "highest"


def _dot(a, b):
    return _jax().numpy.matmul(a, b, precision=_HIGHEST)


def _make_segment_one(want_lam: bool, fused: bool = False):
    """The single-(graph, scenario) gather/max forward.

    Vertices live at level-major flat slots, each owning a padded row of
    in-edges, so one level is a gather → max over the in-edge axis →
    ``dynamic_update_slice`` of the level's slot block — scatter-free, which
    is what makes the sweep fast on CPU/TPU alike.  ``vmap``'d over the
    scenario axis (and, for a packed MultiPlan, the graph axis:
    padding only adds masked −∞ candidates and max is exact, so a packed
    graph's outputs are bit-identical to its solo run).

    λ layouts (``want_lam``): the default is **two-pass** — a values
    forward that records, per slot, the chosen in-edge's source slot
    (critical-path next pointer) and latency row under the scalar engine's
    value/slope/ordinal tie-breaks, then a reverse backtrace *pointer
    chase* from the sink, then an ascending-level accumulation of the
    visited rows.  The ascending final sum replays the fused layout's
    exact float addition order, so results are *bit-identical* to
    ``fused=True`` — the original single-loop backtrace that drags a
    [nflat, nclass] slope accumulation through every level (kept as the
    equivalence reference).  The recorded rows are plain per-level writes,
    so the two-pass loop body stays close to the values-only body; on
    XLA:CPU the two layouts measure within ±15% of each other on compile
    and runtime, because the tie-break arithmetic itself (not the slope
    carry) is what keeps any bit-exact λ program well above the
    values-only compile cost — see ``benchmarks/bench_sweep.py``'s
    ``lam_compile`` lines.
    """
    jax = _jax()
    jnp = jax.numpy
    dus = jax.lax.dynamic_update_slice

    def one(vsrc, vmaskd, vconst, vgap, vgclass, vlat, vlat_sum, vcost_lv,
            valid_flat, vert_of_slot, Lrow, gsrow, *link):
        nlv, Vmax, Dmax = vsrc.shape
        nc = vlat.shape[3]
        nflat = valid_flat.shape[0]          # nlv·Vmax + 1 (dummy tail)
        didx = jnp.arange(Dmax, dtype=jnp.int32)

        def relax(lv, t_end):
            """[Vmax, Dmax] candidate ends and [Vmax] level start times."""
            gse = gsrow[vgclass[lv]]
            if link:
                # congestion closure: per-link effective-gap inflation.
                # lscale ≡ 1.0 multiplies exactly, so a zero-congestion
                # fixed point is bit-identical to the plain forward.
                vlink, lscale = link
                gse = gse * lscale[vlink[lv]]
            w = (vconst[lv] + vgap[lv] * (gse - 1.0)
                 + _dot(vlat[lv], Lrow))
            cand = jnp.where(vmaskd[lv], t_end[vsrc[lv]] + w, -BIG)
            ts = jnp.maximum(jnp.max(cand, axis=1), 0.0)   # t_start ≥ 0
            return cand, ts

        def choose(lv, t_end, ssum):
            """Per-vertex chosen in-edge ordinal for one level.

            The scalar LevelPlan.forward rule: realizing edges (value within
            ATOL of the level max), max-total-slope tie-break, then max
            ordinal.  Shared by the fused and two-pass layouts so their
            tie-break arithmetic is literally the same ops.
            """
            cand, ts = relax(lv, t_end)
            hit = vmaskd[lv] & (cand >= ts[:, None] - ATOL)
            cs = ssum[vsrc[lv]] + vlat_sum[lv]
            best = jnp.max(jnp.where(hit, cs, -BIG), axis=1)
            sel = hit & (cs >= best[:, None] - ATOL)
            chosen = jnp.max(jnp.where(sel, didx, -1), axis=1)   # [Vmax]
            return ts, chosen, sel

        def sink_slot(t_end, ssum):
            """The scalar rule: among makespan sinks, the max-ssum one with
            the smallest original vertex id."""
            T = jnp.max(jnp.where(valid_flat, t_end, -BIG))
            sink = valid_flat & (t_end >= T - ATOL)
            mx = jnp.max(jnp.where(sink, ssum, -BIG))
            top = sink & (ssum >= mx)
            v = jnp.argmin(jnp.where(top, vert_of_slot,
                                     jnp.iinfo(jnp.int32).max))
            return T, v

        if want_lam and not fused:
            # -- pass 1: values + slope-sum carry, recording per slot the
            #    chosen in-edge's *source slot* (a critical-path next
            #    pointer) and its latency row — no [nflat, nc] slope
            #    accumulation in the loop.  The per-edge reads are ordinal
            #    gathers of exactly the elements the fused layout's one-hot
            #    reductions sum, so every recorded value is bit-identical --
            def body(lv, carry):
                t_end, ssum, nxt, lrow = carry
                ts, chosen, _ = choose(lv, t_end, ssum)
                has = chosen >= 0
                ch = jnp.where(has, chosen, 0)[:, None]
                srcslot = jnp.take_along_axis(vsrc[lv], ch, axis=1)[:, 0]
                vls = jnp.take_along_axis(vlat_sum[lv], ch, axis=1)[:, 0]
                ss_new = jnp.where(has, ssum[srcslot] + vls, 0.0)
                off = lv * Vmax
                own = off + jnp.arange(Vmax, dtype=jnp.int32)
                nxt_row = jnp.where(has, srcslot.astype(jnp.int32), own)
                row = jnp.where(
                    has[:, None],
                    jnp.take_along_axis(vlat[lv], ch[:, :, None],
                                        axis=1)[:, 0], 0.0)
                return (dus(t_end, ts + vcost_lv[lv], (off,)),
                        dus(ssum, ss_new, (off,)),
                        dus(nxt, nxt_row, (off,)),
                        dus(lrow, row, (off, 0)))

            init = (jnp.zeros(nflat), jnp.zeros(nflat),
                    jnp.arange(nflat, dtype=jnp.int32),
                    jnp.zeros((nflat, nc)))
            t_end, ssum, nxt, lrow = jax.lax.fori_loop(0, nlv, body, init)
            T, v = sink_slot(t_end, ssum)

            # -- pass 2: reverse backtrace = pointer chase from the sink
            #    slot (slots without a chosen edge self-point, with zero
            #    latency rows, so stalled steps are exact no-ops) ------------
            _, visited = jax.lax.scan(lambda cur, _: (nxt[cur], cur),
                                      jnp.int32(v), None, length=nlv)
            # -- pass 3: ascending-level accumulation — flipping the walk
            #    replays the fused layout's exact float addition order
            #    (leading stall levels add exact +0.0), so λ is
            #    bit-identical to ``fused=True`` ----------------------------
            lam, _ = jax.lax.scan(lambda acc, r: (acc + r, 0.0),
                                  jnp.zeros(nc), lrow[visited][::-1])
            return T, lam

        if want_lam:
            # -- fused reference layout: [nflat, nc] slope carry in-loop,
            #    one-hot masked reductions (the original formulation) --------
            def body(lv, carry):
                t_end, slope, ssum = carry
                ts, chosen, sel = choose(lv, t_end, ssum)
                onehot = sel & (didx[None, :] == chosen[:, None])
                srcv = jnp.max(jnp.where(onehot, vsrc[lv], 0), axis=1)
                has = chosen >= 0
                sl_new = jnp.where(
                    has[:, None], slope[srcv]
                    + jnp.sum(jnp.where(onehot[:, :, None], vlat[lv], 0.0),
                              axis=1), 0.0)
                ss_new = jnp.where(
                    has, ssum[srcv]
                    + jnp.sum(jnp.where(onehot, vlat_sum[lv], 0.0), axis=1),
                    0.0)
                off = lv * Vmax
                return (dus(t_end, ts + vcost_lv[lv], (off,)),
                        dus(slope, sl_new, (off, 0)),
                        dus(ssum, ss_new, (off,)))

            init = (jnp.zeros(nflat), jnp.zeros((nflat, nc)), jnp.zeros(nflat))
            t_end, slope, ssum = jax.lax.fori_loop(0, nlv, body, init)
            T, v = sink_slot(t_end, ssum)
            return T, slope[v]

        def body(lv, t_end):
            _, ts = relax(lv, t_end)
            return dus(t_end, ts + vcost_lv[lv], (lv * Vmax,))

        t_end = jax.lax.fori_loop(0, nlv, body, jnp.zeros(nflat))
        T = jnp.max(jnp.where(valid_flat, t_end, -BIG))
        return T, jnp.zeros((vlat.shape[3],))

    return one


def _segment_core_axes(want_lam: bool, multi: bool, costs: Optional[tuple],
                       fused: bool = False,
                       structure: Optional[tuple] = None):
    """The generalized segment forward: one vmap per populated batch axis.

    The innermost vmap always rides scenarios [S]; ``costs`` (a
    per-``_SEG_COST_FIELDS`` vmap-axis tuple, 0 = patched/batched, None =
    shared) adds the candidate axis [K] over ONLY the patched cost
    tensors; ``multi`` adds the MultiPlan graph axis [G] over every input
    (cost tensors then carry [G, K, ...] when both axes are populated).
    Composition order fixes the canonical output layout [G?, K?, S] — and
    because each added vmap leaves the per-element arithmetic untouched,
    every populated-axis combination is bit-identical to the equivalent
    solo/rebuild runs (the conformance matrix's contract).
    """
    jax = _jax()
    one = _make_segment_one(want_lam, fused)
    core = jax.vmap(one, in_axes=(None,) * 10 + (0, 0))          # S
    if costs is not None:
        core = jax.vmap(core, in_axes=(None, None) + tuple(costs)
                        + (None,) * 3 + (None, None))            # K
    if structure is not None:
        core = jax.vmap(core, in_axes=tuple(structure))          # B
    if multi:
        core = jax.vmap(core, in_axes=(0,) * 12)                 # G
    return core


def _segment_core(want_lam: bool, fused: bool = False):
    """Unjitted forward over one graph × S scenarios → T [S], λ [S, nc]."""
    return _segment_core_axes(want_lam, False, None, fused)


def _segment_core_multi(want_lam: bool, fused: bool = False):
    """Unjitted forward over G graphs × S scenarios → T [G, S], λ [G, S, nc].

    Inner vmap rides scenarios, outer vmap rides the MultiPlan's graph axis
    (every plan tensor gains a leading G dim, and scenarios are per-graph
    [G, S, ·] so variant groups with different base points batch together).
    """
    return _segment_core_axes(want_lam, True, None, fused)


def _congestion_core_axes(want_lam: bool, costs: Optional[tuple] = None):
    """Congestion-aware segment forward: an iterated fixed point per lane.

    The LogGPS gap term models an uncongested link; when many messages
    share one physical link (``CompiledPlan.vlink``), their gap shares
    contend.  We close the loop with a standard utilization model: evaluate
    the forward, aggregate each link's *offered* gap-time ``busy_l``
    (scatter-add of ``vgap · gscale`` over link ids — a constant of the
    scenario, computed once), read utilization ``u_l = busy_l / T``, and
    inflate each link's effective gap by ``1 + α_c·max(u_l − β_c, 0)``
    (α, β per network class) before re-evaluating.  Iteration runs as a
    ``lax.while_loop`` *inside* the jitted program with 0.5 damping and a
    runtime (max_iters, tol) stopping rule — no recompile across knob
    values, and under vmap all S scenarios (and K cost blocks) advance in
    lockstep with converged lanes frozen (their lscale no longer updates;
    per-lane iteration counts are reported).

    With α ≡ 0 the update is the identity (lscale stays exactly 1.0) and
    the loop exits after one iteration — the final evaluation multiplies
    every gap by exactly 1.0, so a zero-congestion run is bit-identical
    to the plain segment backend (the conformance contract).

    λ comes from one final λ-recording evaluation at the converged lscale:
    the fixed point's sensitivities are read at its solution (the inner
    loop stays values-only, which keeps the program small).
    """
    jax = _jax()
    jnp = jax.numpy
    one_vals = _make_segment_one(False)
    one_fin = _make_segment_one(want_lam)

    def fixed_point(vsrc, vmaskd, vconst, vgap, vgclass, vlat, vlat_sum,
                    vcost_lv, valid_flat, vert_of_slot, vlink, link_cls,
                    link_mask, alpha, beta, max_iters, tol, Lrow, gsrow):
        Lp = link_mask.shape[0]
        # offered gap-time per physical link (pad/dep slots carry vgap = 0
        # and land in the dummy bin, which link_mask zeroes out below)
        busy = jax.ops.segment_sum((vgap * gsrow[vgclass]).ravel(),
                                   vlink.ravel(), num_segments=Lp)
        a_l = jnp.where(link_mask, alpha[link_cls], 0.0)
        b_l = beta[link_cls]

        def cond(c):
            _, it, done = c
            return (it < max_iters) & ~done

        def body(c):
            ls, it, done = c
            T, _ = one_vals(vsrc, vmaskd, vconst, vgap, vgclass, vlat,
                            vlat_sum, vcost_lv, valid_flat, vert_of_slot,
                            Lrow, gsrow, vlink, ls)
            util = busy / jnp.maximum(T, 1e-30)
            tgt = 1.0 + a_l * jnp.maximum(util - b_l, 0.0)
            new = ls + 0.5 * (tgt - ls)          # damped update
            fin = jnp.max(jnp.abs(new - ls)) <= tol
            return (jnp.where(done, ls, new), it + jnp.where(done, 0, 1),
                    done | fin)

        ls, iters, _ = jax.lax.while_loop(
            cond, body, (jnp.ones(Lp), jnp.int32(0), jnp.bool_(False)))
        T, lam = one_fin(vsrc, vmaskd, vconst, vgap, vgclass, vlat,
                         vlat_sum, vcost_lv, valid_flat, vert_of_slot,
                         Lrow, gsrow, vlink, ls)
        return T, lam, iters

    core = jax.vmap(fixed_point, in_axes=(None,) * 17 + (0, 0))     # S
    if costs is not None:
        core = jax.vmap(core, in_axes=(None, None) + tuple(costs)
                        + (None,) * 10 + (None, None))              # K
    return core


#: cost tensors each backend's forward consumes, in positional order
#: (subset of ``compile.COST_FIELDS``; the rest of the 10 plan args is
#: immutable structure).  The dicts map field name → position in the
#: backend's 10 staged plan args (``_stage_arrays`` order).
_SEG_COST_FIELDS = ("vconst", "vgap", "vgclass", "vlat", "vlat_sum")
_PAL_COST_FIELDS = ("econst", "egap", "egclass", "elat")
_SEG_COST_POS = {n: i for i, n in enumerate(_SEG_COST_FIELDS, start=2)}
_PAL_COST_POS = {n: i for i, n in enumerate(_PAL_COST_FIELDS, start=3)}

#: structure-batch tensors each backend stages, mapped to their position in
#: the 10 staged plan args.  The pallas 0/−inf indicator (position 0) is
#: derived from emask/edstl and handled separately by the engine; ``edstl``
#: itself is consumed only through the indicator.
_SEG_STRUCT_POS = {"vsrc": 0, "vmaskd": 1, "vconst": 2, "vgap": 3,
                   "vgclass": 4, "vlat": 5, "vlat_sum": 6, "vcost_lv": 7,
                   "valid_flat": 8, "vert_of_slot": 9}
_PAL_STRUCT_POS = {"esrc": 1, "emask": 2, "econst": 3, "egap": 4,
                   "egclass": 5, "elat": 6, "vcost_lv": 7,
                   "valid_flat": 8, "vert_of_slot": 9}
#: structure tensors that determine one backend's results — the view the
#: engine hashes a StructureBatch under when keying cached results
_SEG_STRUCT_FIELDS = tuple(_SEG_STRUCT_POS)
_PAL_STRUCT_FIELDS = ("esrc", "edstl", "emask", "econst", "egap",
                      "egclass", "elat", "vcost_lv", "valid_flat",
                      "vert_of_slot")


def _same_buffer(a: np.ndarray, b: np.ndarray) -> bool:
    """True iff two arrays are literally the same memory view (start,
    layout, dtype) — the test that lets a cost-batched run reuse the
    engine's staged device copy of an unpatched cost tensor.  Strides on
    size-≤1 axes are ignored: they address no memory, and broadcast views
    report 0 there where the base array reports its natural stride."""
    def eff(x):
        return tuple(s for s, n in zip(x.strides, x.shape) if n > 1)

    return (a.shape == b.shape and a.dtype == b.dtype
            and eff(a) == eff(b)
            and a.__array_interface__["data"][0]
            == b.__array_interface__["data"][0])


def _segment_core_costs(want_lam: bool, axes: tuple, fused: bool = False):
    """Forward over K cost blocks × S scenarios → T [K, S], λ [K, S, nc].

    The candidate axis vmaps ONLY the patched cost tensors (``axes``: one
    entry per ``_SEG_COST_FIELDS`` member, 0 = batched, None = shared);
    structure and unpatched costs ride along unbatched.  The per-element
    arithmetic is the single-(graph, scenario) ``one`` unchanged, so row k
    is bit-identical to a solo run of a plan rebuilt with cost block k
    (the placement loop's exactness guarantee)."""
    return _segment_core_axes(want_lam, False, axes, fused)


def _dense_core_axes(want_lam: bool, multi: bool, costs: Optional[tuple],
                     structure: Optional[tuple] = None):
    """The generalized pallas forward.  The scenario axis rides the
    kernel's 128-wide lanes and the graph axis (``multi``) rides the
    batched kernel's outer grid axis, so neither is a vmap; ``costs`` adds
    the candidate axis by vmapping ONLY the patched cost tensors over the
    (graph-batched) kernel core — output layout [K?, G?, S], which the
    engine transposes to the canonical [G?, K?, S].  ``structure`` adds
    the B variant axis outermost (per-staged-arg vmap axes, indicator
    included) — output layout [B, K?, S]."""
    jax = _jax()
    core = (_dense_core_multi if multi else _dense_core)(want_lam)
    if costs is not None:
        core = jax.vmap(core, in_axes=(None,) * 3 + tuple(costs)
                        + (None,) * 3 + (None, None))
    if structure is not None:
        core = jax.vmap(core, in_axes=tuple(structure))           # B
    return core


def _dense_core_costs(want_lam: bool, axes: tuple):
    """Pallas forward over K cost blocks × S scenarios: the (max,+) kernel
    is vmapped on the candidate axis (the 0/−inf indicator is structure and
    stays unbatched); λ via the argmax kernel exactly as in solo runs.
    ``axes``: per-``_PAL_COST_FIELDS`` vmap axis (0 or None)."""
    return _dense_core_axes(want_lam, False, axes)


def _dense_core(want_lam: bool = False):
    """Forward with the Pallas (max,+) kernel as the inner scatter.

    Values-only runs the plain kernel; with ``want_lam`` the argmax-emitting
    kernel variant records each level's realizing edge slot (tie keys =
    cumulative slope sums, mirroring the segment rule) and returns the
    winner's key, which is the new slope sum itself (no per-scenario
    gather); a reverse backtrace scan over the recorded slots recovers λ —
    T/λ/ρ straight from the pallas backend, no segment redispatch.  The λ
    level loop runs under the ``dense_level`` name scope.  Float32
    accumulators (TPU VPU layout) → T matches segment to ~1e-6 relative.
    Tie caveat: the kernel compares candidates *exactly* (a
    tolerance-grouped tie set is not associative across its blocked
    reduction), where segment groups float64 candidates within ATOL —
    structurally tied paths still compare equal in f32 (identical op
    sequences), but a pair of paths whose f64 sums tie only to within ATOL
    can resolve differently and shift λ by a whole count; segment is the
    bit-exact reference when that matters.
    """
    jax = _jax()
    jnp = jax.numpy
    from repro.kernels.maxplus.ops import (maxplus_matvec,
                                           maxplus_matvec_argmax)

    def fwd(A, esrc, emask, econst, egap, egclass, elat, vcost_lv,
            valid_flat, vert_of_slot, Lmat, GSmat):
        nlv, Emax = esrc.shape
        Vmax = vcost_lv.shape[1]
        S = Lmat.shape[0]
        nc = elat.shape[2]
        nflat = valid_flat.shape[0]
        elat_sum = jnp.sum(elat, axis=2)                        # [nlv, Emax]

        def edge_cand(lv, t_end):
            gse = GSmat[:, egclass[lv]].T                       # [Emax, S]
            w = (econst[lv][:, None] + egap[lv][:, None] * (gse - 1.0)
                 + _dot(elat[lv], Lmat.T))
            cand = t_end[esrc[lv]] + w
            return jnp.where(emask[lv][:, None], cand,
                             -BIG).astype(jnp.float32)

        if not want_lam:
            def body(lv, t_end):
                ts = maxplus_matvec(A[lv], edge_cand(lv, t_end))
                ts = jnp.maximum(ts, 0.0)                       # [Vmax, S]
                return jax.lax.dynamic_update_slice(
                    t_end, ts + vcost_lv[lv][:, None], (lv * Vmax, 0))

            t_end = jax.lax.fori_loop(0, nlv, body,
                                      jnp.zeros((nflat, S), jnp.float32))
            T = jnp.max(jnp.where(valid_flat[:, None], t_end, -BIG), axis=0)
            return T, jnp.zeros((S, nc), jnp.float32)

        def body(lv, carry):
            with jax.named_scope("dense_level"):
                t_end, ssum, chosen_all = carry
                cand = edge_cand(lv, t_end)
                cs = (ssum[esrc[lv]]
                      + elat_sum[lv][:, None]).astype(jnp.float32)  # [Emax, S]
                # key = cs at the winning edge: the chosen path's slope sum
                raw, eidx, key = maxplus_matvec_argmax(A[lv], cand, cs)
                ts = jnp.maximum(raw, 0.0)                      # [Vmax, S]
                chosen = jnp.where(raw >= 0.0, eidx, -1)
                ss_new = jnp.where(raw >= 0.0, key, 0.0)
                off = lv * Vmax
                return (jax.lax.dynamic_update_slice(
                            t_end, ts + vcost_lv[lv][:, None], (off, 0)),
                        jax.lax.dynamic_update_slice(ssum, ss_new, (off, 0)),
                        jax.lax.dynamic_update_slice(chosen_all, chosen[None],
                                                     (lv, 0, 0)))

        init = (jnp.zeros((nflat, S), jnp.float32),
                jnp.zeros((nflat, S), jnp.float32),
                jnp.full((nlv, Vmax, S), -1, jnp.int32))
        t_end, ssum, chosen_all = jax.lax.fori_loop(0, nlv, body, init)
        T = jnp.max(jnp.where(valid_flat[:, None], t_end, -BIG), axis=0)
        sink = valid_flat[:, None] & (t_end >= T[None, :])
        mx = jnp.max(jnp.where(sink, ssum, -BIG), axis=0)
        top = sink & (ssum >= mx[None, :])
        vsel = jnp.argmin(jnp.where(top, vert_of_slot[:, None],
                                    jnp.iinfo(jnp.int32).max), axis=0)

        sidx = jnp.arange(S)

        def back(i, carry):
            cur, lam = carry
            lv = nlv - 1 - i
            onlvl = (cur >= lv * Vmax) & (cur < (lv + 1) * Vmax)
            off = jnp.where(onlvl, cur - lv * Vmax, 0)
            e = chosen_all[lv, off, sidx]                       # [S]
            take = onlvl & (e >= 0)
            e_s = jnp.where(take, e, 0)
            lam = lam + jnp.where(take[:, None], elat[lv, e_s, :], 0.0)
            cur = jnp.where(take, esrc[lv, e_s], cur)
            return cur, lam

        _, lam = jax.lax.fori_loop(
            0, nlv, back,
            (vsel.astype(jnp.int32), jnp.zeros((S, nc), jnp.float32)))
        return T, lam

    return fwd


def _dense_core_multi(want_lam: bool = False):
    """Multi-graph pallas forward: the batched (max,+) kernel runs every
    packed graph's level scatter in one launch (graphs on the kernel's
    outer grid axis, scenarios on the 128-wide lane axis); with
    ``want_lam`` the batched argmax kernel records the realizing edge slots
    and the reverse backtrace runs per (graph, scenario)."""
    jax = _jax()
    jnp = jax.numpy
    from repro.kernels.maxplus.ops import (maxplus_matvec_argmax_batched,
                                           maxplus_matvec_batched)

    def fwd(A, esrc, emask, econst, egap, egclass, elat, vcost_lv,
            valid_flat, vert_of_slot, Lmat, GSmat):
        G, nlv, Emax = esrc.shape
        Vmax = vcost_lv.shape[2]
        S = Lmat.shape[1]
        nc = elat.shape[3]
        nflat = valid_flat.shape[1]
        elat_sum = jnp.sum(elat, axis=3)                     # [G, nlv, Emax]

        def edge_cand(lv, t_end):
            # gse[g, e, s] = GSmat[g, s, egclass[g, lv, e]]
            gse = jnp.take_along_axis(
                jnp.swapaxes(GSmat, 1, 2), egclass[:, lv][:, :, None], axis=1)
            w = (econst[:, lv][:, :, None]
                 + egap[:, lv][:, :, None] * (gse - 1.0)
                 + jnp.einsum("gec,gsc->ges", elat[:, lv], Lmat,
                              precision=_HIGHEST))
            cand = jnp.take_along_axis(t_end, esrc[:, lv][:, :, None],
                                       axis=1) + w
            return jnp.where(emask[:, lv][:, :, None], cand,
                             -BIG).astype(jnp.float32)

        if not want_lam:
            def body(lv, t_end):
                ts = maxplus_matvec_batched(A[:, lv], edge_cand(lv, t_end))
                ts = jnp.maximum(ts, 0.0)                    # [G, Vmax, S]
                return jax.lax.dynamic_update_slice(
                    t_end, ts + vcost_lv[:, lv][:, :, None], (0, lv * Vmax, 0))

            t_end = jax.lax.fori_loop(0, nlv, body,
                                      jnp.zeros((G, nflat, S), jnp.float32))
            T = jnp.max(jnp.where(valid_flat[:, :, None], t_end, -BIG), axis=1)
            return T, jnp.zeros((G, S, nc), jnp.float32)

        def body(lv, carry):
            t_end, ssum, chosen_all = carry
            cand = edge_cand(lv, t_end)
            cs = (jnp.take_along_axis(ssum, esrc[:, lv][:, :, None], axis=1)
                  + elat_sum[:, lv][:, :, None]).astype(jnp.float32)
            raw, eidx, key = maxplus_matvec_argmax_batched(A[:, lv], cand,
                                                           cs)
            ts = jnp.maximum(raw, 0.0)                       # [G, Vmax, S]
            chosen = jnp.where(raw >= 0.0, eidx, -1)
            ss_new = jnp.where(raw >= 0.0, key, 0.0)
            off = lv * Vmax
            return (jax.lax.dynamic_update_slice(
                        t_end, ts + vcost_lv[:, lv][:, :, None], (0, off, 0)),
                    jax.lax.dynamic_update_slice(ssum, ss_new, (0, off, 0)),
                    jax.lax.dynamic_update_slice(chosen_all, chosen[:, None],
                                                 (0, lv, 0, 0)))

        init = (jnp.zeros((G, nflat, S), jnp.float32),
                jnp.zeros((G, nflat, S), jnp.float32),
                jnp.full((G, nlv, Vmax, S), -1, jnp.int32))
        t_end, ssum, chosen_all = jax.lax.fori_loop(0, nlv, body, init)
        T = jnp.max(jnp.where(valid_flat[:, :, None], t_end, -BIG), axis=1)
        sink = valid_flat[:, :, None] & (t_end >= T[:, None, :])
        mx = jnp.max(jnp.where(sink, ssum, -BIG), axis=1)
        top = sink & (ssum >= mx[:, None, :])
        vsel = jnp.argmin(jnp.where(top, vert_of_slot[:, :, None],
                                    jnp.iinfo(jnp.int32).max), axis=1)

        def back(i, carry):
            cur, lam = carry                                  # [G, S], [G, S, nc]
            lv = nlv - 1 - i
            onlvl = (cur >= lv * Vmax) & (cur < (lv + 1) * Vmax)
            off = jnp.where(onlvl, cur - lv * Vmax, 0)
            e = jnp.take_along_axis(chosen_all[:, lv], off[:, None, :],
                                    axis=1)[:, 0, :]          # [G, S]
            take = onlvl & (e >= 0)
            e_s = jnp.where(take, e, 0)
            rows = jnp.take_along_axis(elat[:, lv], e_s[:, :, None],
                                       axis=1)                # [G, S, nc]
            lam = lam + jnp.where(take[:, :, None], rows, 0.0)
            cur = jnp.where(take,
                            jnp.take_along_axis(esrc[:, lv], e_s, axis=1),
                            cur)
            return cur, lam

        _, lam = jax.lax.fori_loop(
            0, nlv, back,
            (vsel.astype(jnp.int32), jnp.zeros((G, S, nc), jnp.float32)))
        return T, lam

    return fwd


def _make_sparse_one(want_lam: bool, Emax_lv: int, Vmax_lv: int,
                     Dmax: int = 0):
    """The single-scenario sparse (slot-list) forward.

    Levels are walked with fixed ``[Emax_lv]`` windows of the level-sorted
    edge lists and ``[Vmax_lv]`` vertex windows.  Every candidate of a
    level is computed over the edge window; :class:`~repro.sweep.compile.
    SparsePlan`'s padding invariants make the windows safe: real levels
    never clamp, padded levels' windows touch only pad slots, and
    overrun writes into later-level slots are overwritten by that level's
    own full-window write before anything reads them.

    With ``Dmax`` (the plan's ``step == "indeg"``) the per-destination
    reductions run over the **in-edge view**: slot ``v0 + i``'s in-edges
    are the run ``vin0 + d``, ``d < vdeg``, so ``[Dmax, Vmax_lv]``
    window-local positions index the window, the same for every scenario.
    The level max, the max slope among value hits and the max in-edge
    ordinal among the selected are maxima over ``d``, and the chosen
    edge's source, slope sum and λ row are masked selects over ``d`` — no
    scatter and no per-scenario gather in the level loop (under the
    scenario ``vmap`` a gather through shared indices moves whole
    scenario columns).  ``Dmax = 0`` keeps the ``segment_max`` step over
    window-local destinations (``edst − v_ptr[lv]``, computed in-kernel;
    pad/foreign edges land at destinations ≥ the level's true size, and
    out-of-window ones are dropped by scatter OOB semantics): the plan
    picks it where a high-in-degree vertex would pad the view past twice
    the edge window.

    λ mirrors the segment backend's two-pass backtrace with the argmax in
    the *edge* domain: among value hits (within ATOL of the level max),
    max cumulative slope, then max global edge index — which, with edges
    sorted by (destination level, destination, original id), IS the max
    in-edge ordinal.  Both steps compare the same float64 values in the
    same way and take maxima, which are exact ⇒ T and λ are bit-identical
    to ``segment``.  The level loop runs under the ``sparse_level`` name
    scope: a stable name for its ops in profiles and compiled HLO.
    """
    jax = _jax()
    jnp = jax.numpy
    dus = jax.lax.dynamic_update_slice
    dsl = jax.lax.dynamic_slice
    view = Dmax > 0

    def one(esrc, edst, emask, econst, egap, egclass, elat, elat_sum,
            vcost, valid, vert_of_slot, level_ptr, v_ptr, *rest):
        if view:
            vin0, vdeg, Lrow, gsrow = rest
        else:
            Lrow, gsrow = rest
        nlv = level_ptr.shape[0] - 1
        nv_p = vcost.shape[0]
        nc = elat.shape[1]
        eidx = jnp.arange(Emax_lv, dtype=jnp.int32)
        vidx = jnp.arange(Vmax_lv, dtype=jnp.int32)
        didx = jnp.arange(Dmax, dtype=jnp.int32)

        def relax(lv, t):
            e0 = level_ptr[lv]
            es = dsl(esrc, (e0,), (Emax_lv,))
            em = dsl(emask, (e0,), (Emax_lv,))
            w = (dsl(econst, (e0,), (Emax_lv,))
                 + dsl(egap, (e0,), (Emax_lv,))
                 * (gsrow[dsl(egclass, (e0,), (Emax_lv,))] - 1.0)
                 + _dot(dsl(elat, (e0, jnp.int32(0)), (Emax_lv, nc)), Lrow))
            cand = jnp.where(em, t[es] + w, -BIG)
            return e0, es, em, cand

        if view:
            def level_max(lv, e0, em, cand):
                """The level max per slot over its in-edge view, and the
                view: window-local positions (d-major, so slots stay the
                minor axis), their validity (past vdeg, or outside the
                window for overrun slots) and candidates."""
                v0 = v_ptr[lv]
                p = dsl(vin0, (v0,), (Vmax_lv,)) - e0 + didx[:, None]
                inv = (didx[:, None] < dsl(vdeg, (v0,), (Vmax_lv,))) \
                    & (p >= 0) & (p < Emax_lv)
                p = jnp.clip(p, 0, Emax_lv - 1)
                candv = jnp.where(inv, cand[p], -BIG)    # [Dmax, Vmax_lv]
                return jnp.maximum(jnp.max(candv, axis=0), 0.0), \
                    (p, inv, candv)

            def choose(e0, es, em, cand, ts, ssum, vw):
                """Chosen in-edge per slot: masked maxima and selects
                over d, every index shared by scenarios.  Masked edges
                carry −BIG, never within ATOL of ts ≥ 0."""
                p, inv, candv = vw
                hit = inv & (candv >= ts - ATOL)
                esv = es[p]                              # [Dmax, Vmax_lv]
                cs = ssum[esv] + dsl(elat_sum, (e0,), (Emax_lv,))[p]
                best = jnp.max(jnp.where(hit, cs, -BIG), axis=0)
                sel = hit & (cs >= best - ATOL)
                dsel = jnp.max(jnp.where(sel, didx[:, None], -1), axis=0)
                pick = didx[:, None] == dsel             # one d, or none
                elat_v = dsl(elat, (e0, jnp.int32(0)), (Emax_lv, nc))[p]
                return (dsel >= 0,
                        jnp.max(jnp.where(pick, esv, -1), axis=0),
                        jnp.max(jnp.where(pick, cs, -jnp.inf), axis=0),
                        jnp.max(jnp.where(pick[:, :, None], elat_v,
                                          -jnp.inf), axis=0))
        else:
            def level_max(lv, e0, em, cand):
                """The level max per slot by ``segment_max`` over the
                window's destinations, and those destinations."""
                dloc = dsl(edst, (e0,), (Emax_lv,)) - v_ptr[lv]
                seg = jax.ops.segment_max(cand, dloc, num_segments=Vmax_lv)
                return jnp.maximum(seg, 0.0), dloc

            def choose(e0, es, em, cand, ts, ssum, dloc):
                """Chosen in-edge per slot by ``segment_max`` over the
                window's destinations."""
                dsafe = jnp.clip(dloc, 0, Vmax_lv - 1)
                hit = em & (cand >= ts[dsafe] - ATOL)
                cs = ssum[es] + dsl(elat_sum, (e0,), (Emax_lv,))
                best = jax.ops.segment_max(jnp.where(hit, cs, -BIG), dloc,
                                           num_segments=Vmax_lv)
                sel = hit & (cs >= best[dsafe] - ATOL)
                chosen = jax.ops.segment_max(
                    jnp.where(sel, e0 + eidx, -1), dloc,
                    num_segments=Vmax_lv)
                has = chosen >= 0
                ce = jnp.where(has, chosen, 0)
                srcslot = esrc[ce]
                return has, srcslot, ssum[srcslot] + elat_sum[ce], elat[ce]

        def vwin(lv):
            return dsl(vcost, (v_ptr[lv],), (Vmax_lv,))

        if not want_lam:
            def body(lv, t):
                with jax.named_scope("sparse_level"):
                    e0, _, em, cand = relax(lv, t)
                    ts, _ = level_max(lv, e0, em, cand)
                    return dus(t, ts + vwin(lv), (v_ptr[lv],))

            t = jax.lax.fori_loop(0, nlv, body, jnp.zeros(nv_p))
            T = jnp.max(jnp.where(valid, t, -BIG))
            return T, jnp.zeros((nc,))

        def body(lv, carry):
            with jax.named_scope("sparse_level"):
                t, ssum, nxt, lrow = carry
                e0, es, em, cand = relax(lv, t)
                ts, aux = level_max(lv, e0, em, cand)
                has, srcslot, ss, row = choose(e0, es, em, cand, ts, ssum,
                                               aux)
                own = v_ptr[lv] + vidx
                nxt_row = jnp.where(has, srcslot, own).astype(jnp.int32)
                v0 = v_ptr[lv]
                return (dus(t, ts + vwin(lv), (v0,)),
                        dus(ssum, jnp.where(has, ss, 0.0), (v0,)),
                        dus(nxt, nxt_row, (v0,)),
                        dus(lrow, jnp.where(has[:, None], row, 0.0),
                            (v0, jnp.int32(0))))

        init = (jnp.zeros(nv_p), jnp.zeros(nv_p),
                jnp.arange(nv_p, dtype=jnp.int32),
                jnp.zeros((nv_p, nc)))
        t, ssum, nxt, lrow = jax.lax.fori_loop(0, nlv, body, init)
        T = jnp.max(jnp.where(valid, t, -BIG))
        sink = valid & (t >= T - ATOL)
        mx = jnp.max(jnp.where(sink, ssum, -BIG))
        top = sink & (ssum >= mx)
        v = jnp.argmin(jnp.where(top, vert_of_slot,
                                 jnp.iinfo(jnp.int32).max))
        _, visited = jax.lax.scan(lambda cur, _: (nxt[cur], cur),
                                  jnp.int32(v), None, length=nlv)
        lam, _ = jax.lax.scan(lambda acc, r: (acc + r, 0.0),
                              jnp.zeros(nc), lrow[visited][::-1])
        return T, lam

    return one


def _sparse_core_axes(want_lam: bool, dims: tuple):
    """Sparse forward over S scenarios — the only batch axis the sparse
    backend populates (graphs past the dense cliff are evaluated solo).
    ``dims`` = (Emax_lv, Vmax_lv), the static window sizes, plus ``Dmax``
    for the in-edge-view step, which then takes ``vin0, vdeg`` after the
    13 slot-list arrays."""
    jax = _jax()
    one = _make_sparse_one(want_lam, *dims)
    nplan = 13 + 2 * (len(dims) > 2)
    return jax.vmap(one, in_axes=(None,) * nplan + (0, 0))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sparse_pallas_core(want_lam: bool, dims: tuple):
    """Sparse slot-list forward through the Pallas kernel — the float32
    flavor of the sparse backend (``ExecPolicy(backend="sparse",
    dtype="float32")``).

    Same compact per-level windows as :func:`_make_sparse_one`, but the
    level scatter-max runs the slot-list (max,+) kernel with scenarios on
    the 128-wide lane axis (no per-scenario vmap) and the in-kernel
    lexicographic (value, cumulative-slope key, ordinal) argmax drives the
    λ backtrace — the sparse twin of ``_dense_core``.  The kernel reduces
    float32 candidates; its argmax then selects the candidate's exact value
    and the level carry stays in the float dtype of the caller's scope
    (float64 under the engine), so rounding does not compound with depth
    (a float32 carry drifted 3.2e-5 relative over the 8192 levels of a
    traced training step).  T comes back as float32, within ~1e-7
    relative of the float64 slot-list forward; the same exact-tie caveat
    as the dense kernel applies (tolerance-grouped tie sets aren't
    associative across blocked reductions), so segment/sparse-f64 stay the
    bit-exact references.
    """
    jax = _jax()
    jnp = jax.numpy
    from repro.kernels.maxplus.ops import maxplus_slotlist_argmax

    Emax_lv, Vmax_lv = dims
    dsl = jax.lax.dynamic_slice
    dus = jax.lax.dynamic_update_slice
    E_pad = _round_up(Emax_lv, min(128, _round_up(Emax_lv, 8)))
    be = min(128, E_pad)
    E_pad = _round_up(E_pad, be)
    M_pad = _round_up(Vmax_lv, min(128, _round_up(Vmax_lv, 8)))
    bm = min(128, M_pad)
    M_pad = _round_up(M_pad, bm)

    def fwd(esrc, edst, emask, econst, egap, egclass, elat, elat_sum,
            vcost, valid, vert_of_slot, level_ptr, v_ptr, Lmat, GSmat):
        nlv = level_ptr.shape[0] - 1
        nv_p = vcost.shape[0]
        nc = elat.shape[1]
        S = Lmat.shape[0]
        ft = Lmat.dtype                  # level-carry dtype (see docstring)
        vidx = jnp.arange(Vmax_lv, dtype=jnp.int32)

        def relax(lv, t):
            e0 = level_ptr[lv]
            es = dsl(esrc, (e0,), (Emax_lv,))
            em = dsl(emask, (e0,), (Emax_lv,))
            gcls = dsl(egclass, (e0,), (Emax_lv,))
            w = (dsl(econst, (e0,), (Emax_lv,))[:, None]
                 + dsl(egap, (e0,), (Emax_lv,))[:, None]
                 * (jnp.take(GSmat, gcls, axis=1).T - 1.0)
                 + _dot(dsl(elat, (e0, jnp.int32(0)), (Emax_lv, nc)),
                        Lmat.T))
            cand = jnp.where(em[:, None], t[es] + w, -BIG)   # [Emax_lv, S]
            dloc = dsl(edst, (e0,), (Emax_lv,)) - v_ptr[lv]
            return e0, es, cand, dloc

        def reduce(cand, dloc, key):
            # pad to the kernel's block multiples; pad slots point past
            # every row (never hit), pad rows come back −∞/−1 and are
            # sliced off
            cf = jnp.pad(cand.astype(jnp.float32),
                         ((0, E_pad - Emax_lv), (0, 0)),
                         constant_values=-BIG)
            kf = jnp.pad(key.astype(jnp.float32),
                         ((0, E_pad - Emax_lv), (0, 0)))
            d = jnp.pad(dloc.astype(jnp.int32), (0, E_pad - Emax_lv),
                        constant_values=M_pad)[:, None]
            _, idx = maxplus_slotlist_argmax(d, cf, kf, M=M_pad,
                                             bm=bm, be=be)
            idx = idx[:Vmax_lv]
            # the exact value of the candidate the kernel selected
            raw = jnp.where(idx >= 0, jnp.take_along_axis(
                cand, jnp.maximum(idx, 0), axis=0), -BIG)
            return raw, idx

        def vwin(lv):
            return dsl(vcost, (v_ptr[lv],), (Vmax_lv,))

        if not want_lam:
            def body(lv, t):
                _, _, cand, dloc = relax(lv, t)
                raw, _ = reduce(cand, dloc, jnp.zeros_like(cand))
                ts = jnp.maximum(raw, 0.0)
                return dus(t, (ts + vwin(lv)[:, None]).astype(ft),
                           (v_ptr[lv], jnp.int32(0)))

            t = jax.lax.fori_loop(0, nlv, body, jnp.zeros((nv_p, S), ft))
            T = jnp.max(jnp.where(valid[:, None], t, -BIG), axis=0)
            return T.astype(jnp.float32), jnp.zeros((S, nc), jnp.float32)

        def body(lv, carry):
            t, ssum, nxt, lrow = carry
            e0, es, cand, dloc = relax(lv, t)
            cs = (jnp.take(ssum, es, axis=0)
                  + dsl(elat_sum, (e0,), (Emax_lv,))[:, None])
            raw, eidx = reduce(cand, dloc, cs)               # [Vmax_lv, S]
            ts = jnp.maximum(raw, 0.0)
            has = (raw >= 0.0) & (eidx >= 0)
            ce = jnp.where(has, eidx, 0)
            srcslot = es[ce]                                 # [Vmax_lv, S]
            ss_new = jnp.where(
                has,
                jnp.take_along_axis(ssum, srcslot, axis=0)
                + dsl(elat_sum, (e0,), (Emax_lv,))[ce], 0.0)
            own = v_ptr[lv] + vidx
            nxt_row = jnp.where(has, srcslot, own[:, None]).astype(jnp.int32)
            elat_w = dsl(elat, (e0, jnp.int32(0)), (Emax_lv, nc))
            row = jnp.where(has[:, :, None], elat_w[ce], 0.0)
            v0 = v_ptr[lv]
            z = jnp.int32(0)
            return (dus(t, (ts + vwin(lv)[:, None]).astype(ft), (v0, z)),
                    dus(ssum, ss_new.astype(jnp.float32), (v0, z)),
                    dus(nxt, nxt_row, (v0, z)),
                    dus(lrow, row.astype(jnp.float32), (v0, z, z)))

        init = (jnp.zeros((nv_p, S), ft),
                jnp.zeros((nv_p, S), jnp.float32),
                jnp.broadcast_to(jnp.arange(nv_p, dtype=jnp.int32)[:, None],
                                 (nv_p, S)),
                jnp.zeros((nv_p, S, nc), jnp.float32))
        t, ssum, nxt, lrow = jax.lax.fori_loop(0, nlv, body, init)
        T = jnp.max(jnp.where(valid[:, None], t, -BIG), axis=0)
        sink = valid[:, None] & (t >= T[None, :])
        mx = jnp.max(jnp.where(sink, ssum, -BIG), axis=0)
        top = sink & (ssum >= mx[None, :])
        vsel = jnp.argmin(jnp.where(top, vert_of_slot[:, None],
                                    jnp.iinfo(jnp.int32).max), axis=0)
        sidx = jnp.arange(S)

        def step(cur, _):
            return nxt[cur, sidx], cur

        _, visited = jax.lax.scan(step, vsel.astype(jnp.int32), None,
                                  length=nlv)                # [nlv, S]
        lam = jnp.sum(lrow[visited, sidx[None, :], :], axis=0)
        return T.astype(jnp.float32), lam

    return fwd


_FWD_CACHE: dict = {}
_MESHES: dict = {}


def _device_mesh(ndev: int):
    """1-D device mesh over the first ``ndev`` local devices (cached)."""
    jax = _jax()
    if ndev not in _MESHES:
        _MESHES[ndev] = jax.sharding.Mesh(
            np.asarray(jax.devices()[:ndev]), ("x",))
    return _MESHES[ndev]


def _resolve_shard(shard, size: int) -> Optional[int]:
    """Normalize a ``shard=`` request to a device count that divides the
    batch axis (None = unsharded).  ``True``/"auto" = all local devices;
    an int = at most that many.  The count is walked down to the largest
    divisor of ``size`` so sharded and single-device runs stay bit-equal
    (no pad rows, no uneven splits)."""
    if not shard:
        return None
    jax = _jax()
    avail = len(jax.devices())
    ndev = avail if shard is True or shard == "auto" else min(int(shard), avail)
    ndev = max(min(ndev, size), 1)
    while size % ndev:
        ndev -= 1
    return ndev if ndev > 1 else None


def _stage_arrays(plan, kind: str, max_dense_bytes: int) -> tuple:
    """Device-stage a plan's tensors for one backend.  CompiledPlan and
    MultiPlan share field names (the latter just carries a leading graph
    axis), so single- and multi-graph engines stage through this one
    helper."""
    jnp = _jax().numpy
    if kind == "segment":
        return tuple(jnp.asarray(a) for a in (
            plan.vsrc, plan.vmaskd, plan.vconst, plan.vgap, plan.vgclass,
            plan.vlat, plan.vlat_sum, plan.vcost_lv, plan.valid_flat,
            plan.vert_of_slot))
    if kind == "indeg":
        return (jnp.asarray(plan.vin0), jnp.asarray(plan.vdeg))
    if kind == "sparse":
        return tuple(jnp.asarray(a) for a in (
            plan.esrc_slot, plan.edst_slot, plan.emask, plan.econst,
            plan.egap, plan.egclass, plan.elat, plan.elat_sum, plan.vcost,
            plan.valid, plan.vert_of_slot, plan.level_ptr, plan.v_ptr))
    if kind == "congestion":
        if plan.vlink is None:
            raise ValueError(
                "congestion needs per-edge link ids, but this plan carries "
                "none (the graph was built without link interning — use "
                "GraphBuilder.add_message / intern_link, or recompile from "
                "a graph with elink populated)")
        # link bins: [0, nlinks) real links, nlinks = dummy (dep edges,
        # pad slots), bucketed up to Lp; masked bins keep lscale ≡ 1.0
        Lp = _bucket(plan.nlinks + 1, lo=8)
        link_cls = np.zeros(Lp, dtype=np.int32)
        if plan.link_classes is not None and plan.nlinks:
            link_cls[:plan.nlinks] = plan.link_classes
        link_mask = np.arange(Lp) < plan.nlinks
        return tuple(jnp.asarray(a) for a in (
            plan.vsrc, plan.vmaskd, plan.vconst, plan.vgap, plan.vgclass,
            plan.vlat, plan.vlat_sum, plan.vcost_lv, plan.valid_flat,
            plan.vert_of_slot, plan.vlink, link_cls, link_mask))
    if plan.dense_bytes() > max_dense_bytes:
        raise ValueError(
            f"dense pallas backend needs {plan.dense_bytes() >> 20} MiB "
            f"of indicator tensors (> {max_dense_bytes >> 20}); "
            "use backend='segment' or backend='sparse'")
    return tuple(jnp.asarray(a) for a in (
        plan.dense_indicator(-BIG), plan.esrc, plan.emask,
        plan.econst.astype(np.float32), plan.egap.astype(np.float32),
        plan.egclass, plan.elat.astype(np.float32),
        plan.vcost_lv.astype(np.float32), plan.valid_flat,
        plan.vert_of_slot))


#: positional plan args every core takes ahead of (Lmat, GSmat)
_N_PLAN_ARGS = 10


def _shard_specs(kind: str, multi: bool, costs: Optional[tuple],
                 shard_axis: str) -> tuple:
    """Per-argument shard_map partition specs for one populated-axis cell.

    Every forward takes ``_N_PLAN_ARGS`` plan tensors + (L, GS); the dim
    that carries ``shard_axis`` differs per argument and per backend:

    * "S" — only the scenario tensors split (dim 1 under a graph axis);
    * "G" — every tensor splits on its graph dim (0 everywhere, except
      pallas patched cost tensors, which are staged [K, G, ...]);
    * "K" — only the *patched* cost tensors split on their candidate dim
      (structure, unpatched costs and scenarios replicate).

    Output layouts: segment [G?, K?, S], pallas [K?, G?, S].
    """
    P = _jax().sharding.PartitionSpec

    def spec(d):
        return P() if d is None else P(*([None] * d + ["x"]))

    K = costs is not None
    dims: list = [None] * (_N_PLAN_ARGS + 2)
    cost0 = 2 if kind == "segment" else 3      # first cost-field position
    if shard_axis == "S":
        dims[10] = dims[11] = 1 if multi else 0
    elif shard_axis == "G":
        dims = [0] * (_N_PLAN_ARGS + 2)
        if K and kind == "pallas":             # patched costs are [K, G, ...]
            for j, ax in enumerate(costs):
                if ax == 0:
                    dims[cost0 + j] = 1
    else:                                      # "K"
        for j, ax in enumerate(costs):
            if ax == 0:
                dims[cost0 + j] = (1 if multi else 0) \
                    if kind == "segment" else 0
    if kind == "segment":
        od = {"G": 0, "K": 1 if multi else 0,
              "S": int(multi) + int(K)}[shard_axis]
    else:
        od = {"K": 0, "G": 1 if K else 0,
              "S": int(multi) + int(K)}[shard_axis]
    return tuple(spec(d) for d in dims), (spec(od), spec(od))


def _get_forward(kind: str, want_lam: bool = False, multi: bool = False,
                 fused: bool = False, mesh=None,
                 costs: Optional[tuple] = None,
                 shard_axis: Optional[str] = None,
                 structure: Optional[tuple] = None,
                 sparse_dims: Optional[tuple] = None):
    """Build (or fetch) the jitted forward for one populated-axis cell.

    The cell is keyed on (backend, λ, G axis, K axes, mesh, shard axis):
    vmap composition over the populated batch axes is derived here
    (``_segment_core_axes`` / ``_dense_core_axes``) — graph [G],
    candidate-cost [K] and scenario [S] axes compose freely, including all
    at once.

    With ``mesh`` the composed core is wrapped in ``shard_map`` before
    jit; ``shard_axis`` picks which populated axis splits across devices
    (default: the MultiPlan graph axis when present, else scenarios).
    Per-element arithmetic is unchanged either way, so sharded results are
    bit-identical to single-device runs.

    ``costs`` (a per-cost-field vmap-axis tuple, see ``_SEG_COST_FIELDS``
    / ``_PAL_COST_FIELDS``) selects the candidate-cost-axis cells:
    patched cost tensors batched, structure and unpatched costs unbatched,
    scenarios broadcast.
    """
    jax = _jax()
    mesh_key = None if mesh is None else tuple(
        d.id for d in np.asarray(mesh.devices).flat)
    fused = bool(fused and want_lam and kind == "segment")
    if kind in ("sparse", "sparse_pallas"):
        if multi or costs is not None or structure is not None:
            raise ValueError("sparse backend populates the scenario axis "
                             "only (no G/K/B batching yet)")
        if mesh is not None:
            raise ValueError("sparse backend does not shard yet")
        if sparse_dims is None:
            raise ValueError("sparse forward needs sparse_dims="
                             "(Emax_lv, Vmax_lv[, Dmax])")
    if kind == "congestion":
        if multi or structure is not None:
            raise ValueError("the congestion fixed point populates the S "
                             "and K axes only (no G/B batching)")
        if mesh is not None:
            raise ValueError("the congestion fixed point does not shard "
                             "yet (while_loop lanes must stay lockstep on "
                             "one device)")
    if structure is not None and multi:
        raise ValueError("structure blocks and a MultiPlan graph axis "
                         "cannot combine (pick one variant axis)")
    if structure is not None and mesh is not None:
        raise ValueError("sharding a structure-batched query is not "
                         "supported yet")
    if mesh is None:
        shard_axis = None
    elif shard_axis is None:
        shard_axis = "G" if multi else "S"
    if shard_axis == "G" and not multi:
        raise ValueError("shard_axis='G' needs a multi-graph forward "
                         "(no graph axis is populated)")
    if shard_axis == "K" and costs is None:
        raise ValueError("shard_axis='K' needs a cost-batched forward "
                         "(no candidate axis is populated)")
    key = (kind, want_lam, multi, fused, mesh_key, costs, shard_axis,
           structure, sparse_dims)
    if key in _FWD_CACHE:
        return _FWD_CACHE[key]
    if kind == "segment":
        core = _segment_core_axes(want_lam, multi, costs, fused, structure)
    elif kind == "congestion":
        core = _congestion_core_axes(want_lam, costs)
    elif kind == "sparse":
        core = _sparse_core_axes(want_lam, sparse_dims)
    elif kind == "sparse_pallas":
        core = _sparse_pallas_core(want_lam, sparse_dims)
    else:
        core = _dense_core_axes(want_lam, multi, costs, structure)
    if mesh is not None:
        in_specs, out_specs = _shard_specs(kind, multi, costs, shard_axis)
        core = jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    fn = jax.jit(core)
    _FWD_CACHE[key] = fn
    return fn


# -- lockstep-batched bisections (the dag.py loops, one engine call/round) ----

def _probe(eng: Engine, params: LogGPS, Lvals, cls: int,
           backend: Optional[str] = None):
    batch = latency_grid(params, np.asarray(Lvals, dtype=np.float64),
                         cls=cls, absolute=True)
    res = eng.run(batch, outputs=("T", "lam"), use_cache=False,
                  backend=backend)
    return res.T, res.lam[:, cls]


def tolerance_batched(eng: Engine, params: LogGPS,
                      degradations: Sequence[float], cls: int = 0,
                      L_hi: float = 1e7, tol: float = 1e-6,
                      max_iter: int = 200,
                      backend: Optional[str] = None) -> dict:
    """All of ``dag.tolerance``'s bisections in lockstep: each round probes
    every still-active degradation level in one batched forward."""
    degr = np.asarray(list(degradations), dtype=np.float64)
    S = degr.shape[0]
    L0 = float(params.L[cls])
    T0 = _probe(eng, params, [L0], cls, backend)[0][0]
    budgets = (1.0 + degr) * T0
    Thi = _probe(eng, params, [L_hi], cls, backend)[0][0]

    out = np.empty(S)
    done = Thi <= budgets
    out[done] = np.inf
    a = np.full(S, L0)
    b = np.full(S, L_hi)
    for _ in range(max_iter):
        act = np.nonzero(~done)[0]
        if act.size == 0:
            break
        Tb, lb = _probe(eng, params, b[act], cls, backend)
        x = np.where(lb > 0, b[act] + (budgets[act] - Tb) / np.where(lb > 0, lb, 1.0),
                     (a[act] + b[act]) / 2)
        x = np.clip(x, a[act], b[act])
        Tx, _ = _probe(eng, params, x, cls, backend)
        conv = np.abs(Tx - budgets[act]) <= tol * np.maximum(1.0, budgets[act])
        out[act[conv]] = x[conv] - L0
        done[act[conv]] = True
        rest = act[~conv]
        hi = Tx[~conv] > budgets[rest]
        b[rest[hi]] = x[~conv][hi]
        a[rest[~hi]] = x[~conv][~hi]
        narrow = ~done & (b - a < tol)
        out[narrow] = a[narrow] - L0
        done |= narrow
    out[~done] = a[~done] - L0
    return {float(p): float(v) for p, v in zip(degr, out)}


def breakpoints_batched(eng: Engine, params: LogGPS, L_min: float,
                        L_max: float, cls: int = 0, tol: float = 1e-9,
                        max_bp: int = 10_000, max_depth: int = 80,
                        backend: Optional[str] = None) -> list:
    """``dag.breakpoints`` with the recursion flattened level-by-level: all
    frontier intervals' probe points are evaluated in one batched call."""
    (ya, yb), (sa, sb) = _probe(eng, params, [L_min, L_max], cls, backend)
    frontier = [(L_min, float(ya), float(sa), L_max, float(yb), float(sb), 0)]
    out: list = []
    while frontier and len(out) < max_bp:
        work = [iv for iv in frontier
                if abs(iv[2] - iv[5]) > tol and iv[6] <= max_depth]
        if not work:
            break
        xs = []
        for (A, yA, sA, B, yB, sB, _) in work:
            x = (yB - sB * B - (yA - sA * A)) / (sA - sB)
            xs.append(min(max(x, A + tol), B - tol))
        ys, ss = _probe(eng, params, xs, cls, backend)
        frontier = []
        for (A, yA, sA, B, yB, sB, d), x, yx, sx in zip(work, xs, ys, ss):
            if len(out) >= max_bp:
                break
            line = yA + sA * (x - A)
            if yx <= line + max(1e-7, 1e-9 * abs(line)):
                out.append(float(x))
            else:
                frontier.append((A, yA, sA, float(x), float(yx), float(sx), d + 1))
                frontier.append((float(x), float(yx), float(sx), B, yB, sB, d + 1))
    return sorted(out)
