"""LevelPlan → padded dense per-level tensors (the sweep engine's "program").

The scalar engine (``core.dag.LevelPlan``) walks topological levels with
ragged numpy slices and ``np.maximum.at`` scatters — great for one
evaluation, hostile to XLA.  This module re-lays the same schedule out as
*rectangular* tensors in two views:

Per-vertex view (the fast ``segment`` backend): every vertex owns a padded
row of in-edges, and vertices live at level-major *flat slots*
(``slot = level·Vmax + offset``), so one jit'd ``fori_loop`` iteration is a
pure gather → max-reduce → ``dynamic_update_slice`` — no scatter anywhere:

    vsrc    [nlv, Vmax, Dmax]      flat slot of each in-edge's source
    vmaskd  [nlv, Vmax, Dmax]      real-edge mask
    vconst  [nlv, Vmax, Dmax]      constant edge cost incl. build-time (s-1)G
    vgap    [nlv, Vmax, Dmax]      the (s-1)·G share (bandwidth sweeps)
    vgclass [nlv, Vmax, Dmax]      latency class of the gap term
    vlat    [nlv, Vmax, Dmax, nc]  latency-class multiplicities
    vcost_lv[nlv, Vmax]            vertex cost by slot

Per-edge view (the Pallas ``maxplus`` backend): edges grouped by level with
level-local destination ids, from which :meth:`CompiledPlan.dense_indicator`
derives the 0/−inf scatter matrices the (max,+) kernel consumes.

All dims are rounded up to power-of-two *buckets* so graphs of similar size
share one compiled XLA program (the jit cache keys on shapes) — a sweep over
100 random graphs costs a handful of compiles, not 100.

Edge weights at a scenario (L, γ) are reconstructed as

    w = const + gap·(γ_gclass − 1) + lat @ L

so that γ = 1 (build-time bandwidth) reproduces the built edge constant
*bitwise* — the decomposition can never perturb latency-only sweeps.  γ
scales the effective gap/byte G (γ > 1 = slower links).  Graphs finalized
by ``GraphBuilder`` record their per-edge gap shares (``g.egap``/
``g.egclass``) and those are authoritative; the ``params``-based
reconstruction backstops message edges without a recorded share —
hand-built graphs and raw ``add_edge(nbytes=...)`` callers that didn't
pass ``gap_us`` (see :func:`compile_plan`).

Multi-graph packing: several :class:`CompiledPlan`\\ s whose bucketed shapes
fit a common level/edge envelope re-pad into one :class:`MultiPlan` whose
tensors carry a leading graph axis — a whole variant study (collectives ×
topologies × scenario grid) then runs as ONE compiled XLA program instead
of one call per variant.  See :func:`pack_plans` / :func:`group_plans`.

Structure vs cost: a compiled plan is two disjoint tensor sets.  The
*structure* (slots, masks, tie-break ordinals — ``vsrc``/``vmaskd``/
``valid_flat``/``vert_of_slot``/``esrc``/``edstl``/``emask``/``vcost_lv``)
fixes the XLA program; the *cost block* (``COST_FIELDS``: econst, gap
shares, latency-class rows) is plain data the program consumes.  Because
``compile_plan`` records each edge's slot coordinates in original edge
order (``epos_*``), new per-edge costs patch into a warm plan as a runtime
input instead of a rebuild: :meth:`CompiledPlan.patch_costs` stacks K
candidate cost blocks into a :class:`CostBatch` that
``Engine.run(costs=...)`` vmaps alongside scenarios — the zero-
recompile path behind the Algorithm-3 placement search (every swap
candidate of every greedy step reuses ONE compiled program).  Patched
costs are bit-identical to rebuilding the plan with
``compile_plan(extra_edge_cost=...)``: both add the extra to the baked
edge constant in float64 before anything else touches it.

The same split now runs in the other direction: *structure itself* is
patchable inside a bounded super-envelope.  :meth:`CompiledPlan.patch_structure`
/ :class:`StructureBatch` stack B edge-rewired variant blocks (slot source
indices and edge masks as runtime inputs; λ tie-break ordinals re-derived
in-kernel from the patched masks) that vmap alongside K cost blocks and S
scenarios — a whole topology study is ONE XLA program.  And past the dense
memory cliff, :class:`SparsePlan` / :func:`compile_sparse` lay the schedule
out as compact CSR-style slot lists with no ``[nlv, Vmax, Dmax]`` padding
at all (the ``sparse`` backend).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional, Sequence

import numpy as np

from repro.core.graph import ExecutionGraph, edge_gap_shares
from repro.core.loggps import LogGPS


def _bucket(n: int, lo: int = 8) -> int:
    """Next power of two ≥ max(n, lo)."""
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


#: The patchable cost tensors of a compiled plan, in the order the engine
#: forwards consume them (per-vertex view first, then the pallas per-edge
#: view).  Everything else on a plan is immutable structure.
COST_FIELDS = ("vconst", "vgap", "vgclass", "vlat", "vlat_sum",
               "econst", "egap", "egclass", "elat")

#: Every plan tensor the engine forwards consume (per-vertex view first,
#: then the pallas per-edge view).  A :class:`StructureBatch` stacks B
#: variant blocks of ALL of them — rewired fields materialized, untouched
#: fields stride-0 broadcast — so edge rewirings vmap like cost blocks do.
STRUCT_FIELDS = ("vsrc", "vmaskd", "vconst", "vgap", "vgclass", "vlat",
                 "vlat_sum", "vcost_lv", "valid_flat", "vert_of_slot",
                 "esrc", "edstl", "emask", "econst", "egap", "egclass",
                 "elat", "vlink", "elinkp")


def _segment_view_bytes(nlv_p: int, Vmax: int, Dmax: int, nc: int) -> int:
    """Footprint of the padded per-vertex (segment) tensors, λ tie-break
    slope array (``vlat_sum``) included."""
    slot = nlv_p * Vmax * Dmax
    return (slot * (4 + 1 + 8 + 8 + 4 + 8 * nc + 8)  # vsrc..vlat_sum
            + nlv_p * Vmax * 8                        # vcost_lv
            + (nlv_p * Vmax + 1) * 5)                 # valid_flat+vert_of_slot


def _pallas_view_bytes(nlv_p: int, Vmax: int, Emax: int, nc: int) -> int:
    """Footprint of the pallas per-edge view: the [nlv, Vmax, Emax] 0/−inf
    indicator, the f32 edge tensors, and the per-level λ argmax plane."""
    edge = nlv_p * Emax
    return (nlv_p * Vmax * Emax * 4                   # indicator
            + edge * (4 + 4 + 1 + 4 + 4 + 4 + 4 * nc)
            + nlv_p * Vmax * 4 * 2                    # vcost f32 + argmax
            + (nlv_p * Vmax + 1) * 5)


@dataclasses.dataclass
class CostBatch:
    """K patchable cost blocks sharing one :class:`CompiledPlan` structure.

    Leading axis = candidate index (e.g. the K swap candidates of one
    greedy placement step).  Tensors that a patch did not touch are
    broadcast views of the parent plan's — only the patched constants are
    materialized K times.  ``Engine.run(costs=...)`` vmaps the blocks
    alongside the scenario axis through the plan's already-compiled
    forward; the structure tensors ride along unbatched, so no new XLA
    program is ever built for a new cost block.
    """

    vconst: np.ndarray     # [K, nlv_p, Vmax, Dmax] float64
    vgap: np.ndarray       # [K, nlv_p, Vmax, Dmax] float64
    vgclass: np.ndarray    # [K, nlv_p, Vmax, Dmax] int32
    vlat: np.ndarray       # [K, nlv_p, Vmax, Dmax, nclass] float64
    vlat_sum: np.ndarray   # [K, nlv_p, Vmax, Dmax] float64
    econst: np.ndarray     # [K, nlv_p, Emax] float64
    egap: np.ndarray       # [K, nlv_p, Emax] float64
    egclass: np.ndarray    # [K, nlv_p, Emax] int32
    elat: np.ndarray       # [K, nlv_p, Emax, nclass] float64
    #: content hash of the plan this batch was patched from — bucketing
    #: makes DISTINCT graphs share envelopes, so the engine must be able
    #: to refuse a cost block minted on a different plan of the same
    #: shape (None on hand-assembled batches: shape check only)
    plan_hash: Optional[str] = None

    @property
    def K(self) -> int:
        return int(self.vconst.shape[0])

    @property
    def shape_key(self) -> tuple:
        """Envelope of the parent plan (no K: any K shares its programs)."""
        return self.vconst.shape[1:] + self.econst.shape[2:] + \
            (self.vlat.shape[4],)

    def content_hash(self, fields: Optional[Sequence[str]] = None) -> str:
        """SHA1 over the cost tensors — patched costs participate in sweep
        result keys exactly like baked ones (see ``cache.result_key``).

        ``fields`` restricts the hash to the tensors one backend actually
        consumes; the engine keys cached results per backend view, so a
        raw-extras run (view-limited patch) and an explicit full
        ``patch_costs`` of the same extras hash identically on the backend
        that evaluates them.  Broadcast fields (unpatched — K identical
        blocks, stride 0 on the candidate axis) hash one block plus the
        count instead of K copies, so keying a placement step costs
        O(patched tensors), not O(K × cost block).
        """
        names = tuple(fields) if fields is not None else COST_FIELDS
        memo = getattr(self, "_hashes", None)
        if memo is None:
            memo = {}
            object.__setattr__(self, "_hashes", memo)
        h = memo.get(names)
        if h is None:
            from .cache import canonical_bytes
            sha = hashlib.sha1(b"cost-batch-v1")
            for name in names:
                a = getattr(self, name)
                chunks = ((f"|bcast{a.shape[0]}|".encode(),)
                          + canonical_bytes(a[0])
                          if a.strides[0] == 0 else canonical_bytes(a))
                for chunk in chunks:
                    sha.update(chunk)
            h = memo[names] = sha.hexdigest()
        return h

    def padded(self, Kp: int) -> "CostBatch":
        """Pad the candidate axis to ``Kp`` by repeating the last block, so
        varying candidate counts share one bucketed XLA program (results
        for the pad rows are discarded by the engine).  Broadcast fields
        stay broadcasts — padding never materializes unpatched tensors."""
        K = self.K
        if Kp == K:
            return self
        if Kp < K:
            raise ValueError(f"cannot pad {K} cost blocks down to {Kp}")

        def pad(a):
            if a.strides[0] == 0:                # unpatched: keep stride-0
                return np.broadcast_to(a[:1], (Kp,) + a.shape[1:])
            return np.concatenate(
                [a, np.broadcast_to(a[-1:], (Kp - K,) + a.shape[1:])])

        return CostBatch(**{name: pad(getattr(self, name))
                            for name in COST_FIELDS},
                         plan_hash=self.plan_hash)

    def repad(self, nlv_p: int, Vmax: int, Dmax: int,
              Emax: int) -> "CostBatch":
        """Zero-fill the structural dims onto a larger envelope — the
        cost-block analog of :func:`repad_plan`, used when per-graph cost
        batches ride a packed :class:`MultiPlan`'s common envelope.  Padded
        slots are masked out of every reduction (exactly as in
        ``repad_plan``'s zero-fill of the cost tensors), so a repadded
        block evaluates bit-identically.  Broadcast (unpatched) fields stay
        stride-0 on the candidate axis."""
        K = self.K
        nlv0, V0, D0 = self.vconst.shape[1:]
        E0 = self.econst.shape[2]
        if (nlv_p, Vmax, Dmax, Emax) == (nlv0, V0, D0, E0):
            return self
        if nlv_p < nlv0 or Vmax < V0 or Dmax < D0 or Emax < E0:
            raise ValueError(
                f"target envelope {(nlv_p, Vmax, Dmax, Emax)} smaller than "
                f"cost batch's {(nlv0, V0, D0, E0)}")
        nc = self.vlat.shape[4]
        shapes = {
            "vconst": (nlv_p, Vmax, Dmax), "vgap": (nlv_p, Vmax, Dmax),
            "vgclass": (nlv_p, Vmax, Dmax),
            "vlat": (nlv_p, Vmax, Dmax, nc),
            "vlat_sum": (nlv_p, Vmax, Dmax),
            "econst": (nlv_p, Emax), "egap": (nlv_p, Emax),
            "egclass": (nlv_p, Emax), "elat": (nlv_p, Emax, nc),
        }

        def grow(a, shape):
            inner = tuple(slice(0, s) for s in a.shape[1:])
            if a.strides[0] == 0:                # unpatched: keep stride-0
                out = np.zeros(shape, dtype=a.dtype)
                out[inner] = a[0]
                return np.broadcast_to(out[None], (K,) + shape)
            out = np.zeros((K,) + shape, dtype=a.dtype)
            out[(slice(None),) + inner] = a
            return out

        return CostBatch(**{n: grow(getattr(self, n), shapes[n])
                            for n in COST_FIELDS},
                         plan_hash=self.plan_hash)


@dataclasses.dataclass
class StructureBatch:
    """B *structural* variant blocks sharing one bounded super-envelope.

    The :class:`CostBatch` idiom applied to the structure tensors: slot
    source indices (``vsrc``/``esrc``) and edge masks (``vmaskd``/
    ``emask``) become runtime inputs with a leading variant axis, so a
    whole topology study (collective-algorithm swaps, link re-routes)
    vmaps through ONE compiled XLA program — B structure blocks alongside
    K cost blocks and S scenarios.  λ tie-break ordinals need no extra
    tensor: the in-edge ordinal IS the position along ``Dmax`` (the edge
    slot along ``Emax`` on the pallas view), so the kernels re-derive it
    from the patched masks and tie-breaks stay bit-exact per variant.

    Two constructors: :meth:`CompiledPlan.patch_structure` rewires edges
    of one plan (only ``vsrc``/``vmaskd``/``esrc``/``emask`` are
    materialized B times — everything else stays a stride-0 broadcast
    view of the parent's tensors), and :meth:`from_plans` stamps
    separately-compiled plans onto their union envelope (the
    zero-recompile replacement for per-bucket ``MultiPlan`` studies).
    """

    vsrc: np.ndarray       # [B, nlv_p, Vmax, Dmax] int32
    vmaskd: np.ndarray     # [B, nlv_p, Vmax, Dmax] bool
    vconst: np.ndarray     # [B, nlv_p, Vmax, Dmax] float64
    vgap: np.ndarray       # [B, nlv_p, Vmax, Dmax] float64
    vgclass: np.ndarray    # [B, nlv_p, Vmax, Dmax] int32
    vlat: np.ndarray       # [B, nlv_p, Vmax, Dmax, nclass] float64
    vlat_sum: np.ndarray   # [B, nlv_p, Vmax, Dmax] float64
    vcost_lv: np.ndarray   # [B, nlv_p, Vmax] float64
    valid_flat: np.ndarray  # [B, nlv_p·Vmax + 1] bool
    vert_of_slot: np.ndarray  # [B, nlv_p·Vmax + 1] int32
    esrc: np.ndarray       # [B, nlv_p, Emax] int32
    edstl: np.ndarray      # [B, nlv_p, Emax] int32
    emask: np.ndarray      # [B, nlv_p, Emax] bool
    econst: np.ndarray     # [B, nlv_p, Emax] float64
    egap: np.ndarray       # [B, nlv_p, Emax] float64
    egclass: np.ndarray    # [B, nlv_p, Emax] int32
    elat: np.ndarray       # [B, nlv_p, Emax, nclass] float64
    vlink: np.ndarray = None   # [B, nlv_p, Vmax, Dmax] int32 link ids
    elinkp: np.ndarray = None  # [B, nlv_p, Emax] int32 link ids
    #: the plan whose envelope (and, for broadcast fields, tensors) the
    #: variants share — the engine stages it once and overwrites the
    #: batched positions
    base: Optional["CompiledPlan"] = None
    #: content hash of the patched-from plan (None for :meth:`from_plans`
    #: batches, whose structure hash covers every member tensor)
    plan_hash: Optional[str] = None
    #: optional per-variant display names (drive ``Result.split()``)
    names: Optional[tuple] = None

    @property
    def B(self) -> int:
        return int(self.vsrc.shape[0])

    @property
    def nclass(self) -> int:
        return int(self.vlat.shape[4])

    @property
    def shape_key(self) -> tuple:
        """Envelope of the super-plan (no B: any B shares its programs)."""
        return self.vsrc.shape[1:] + self.esrc.shape[2:] + (self.nclass,)

    def content_hash(self, fields: Optional[Sequence[str]] = None) -> str:
        """SHA1 over the structure tensors — patched structure participates
        in sweep result keys exactly like patched costs do (two variants
        sharing a super-envelope must never collide in the cache).
        ``fields`` restricts the hash to one backend's view; broadcast
        (unvaried) fields hash one block plus the count, so keying a study
        costs O(patched tensors), not O(B × plan)."""
        names = tuple(fields) if fields is not None else STRUCT_FIELDS
        memo = getattr(self, "_hashes", None)
        if memo is None:
            memo = {}
            object.__setattr__(self, "_hashes", memo)
        h = memo.get(names)
        if h is None:
            from .cache import canonical_bytes
            sha = hashlib.sha1(b"structure-batch-v1")
            for name in names:
                a = getattr(self, name)
                if a is None:           # optional link tensors
                    sha.update(f"|none:{name}|".encode())
                    continue
                chunks = ((f"|bcast{a.shape[0]}|".encode(),)
                          + canonical_bytes(a[0])
                          if a.strides[0] == 0 else canonical_bytes(a))
                for chunk in chunks:
                    sha.update(chunk)
            h = memo[names] = sha.hexdigest()
        return h

    def padded(self, Bp: int) -> "StructureBatch":
        """Pad the variant axis to ``Bp`` by repeating the last block, so
        varying variant counts share one bucketed XLA program (pad rows are
        sliced off by the engine).  Broadcast fields stay broadcasts."""
        B = self.B
        if Bp == B:
            return self
        if Bp < B:
            raise ValueError(f"cannot pad {B} structure blocks down to {Bp}")

        def pad(a):
            if a is None:
                return None
            if a.strides[0] == 0:
                return np.broadcast_to(a[:1], (Bp,) + a.shape[1:])
            return np.concatenate(
                [a, np.broadcast_to(a[-1:], (Bp - B,) + a.shape[1:])])

        return StructureBatch(**{n: pad(getattr(self, n))
                                 for n in STRUCT_FIELDS},
                              base=self.base, plan_hash=self.plan_hash,
                              names=self.names)

    @classmethod
    def from_plans(cls, plans: Sequence["CompiledPlan"],
                   names: Optional[Sequence[str]] = None
                   ) -> "StructureBatch":
        """Stack separately-compiled plans onto their union envelope.

        Every tensor is materialized B times (independently built graphs
        share nothing), but the batch still evaluates as ONE XLA program;
        repadding is exact (see :func:`repad_plan`), so results are
        bit-identical to evaluating each plan alone.
        """
        if not plans:
            raise ValueError("from_plans needs at least one plan")
        nc = plans[0].nclass
        if any(p.nclass != nc for p in plans):
            raise ValueError("cannot batch plans with different latency-"
                             "class counts into one StructureBatch")
        if names is not None and len(names) != len(plans):
            raise ValueError(f"{len(names)} names for {len(plans)} plans")
        nlv = max(p.vsrc.shape[0] for p in plans)
        Vm = max(p.vsrc.shape[1] for p in plans)
        Dm = max(p.vsrc.shape[2] for p in plans)
        Em = max(p.esrc.shape[1] for p in plans)
        padded = [repad_plan(p, nlv, Vm, Dm, Em) for p in plans]

        def stack(name):
            if any(getattr(p, name) is None for p in padded):
                return None             # optional link tensors
            return np.stack([getattr(p, name) for p in padded])

        return cls(**{n: stack(n) for n in STRUCT_FIELDS},
                   base=padded[0], plan_hash=None,
                   names=tuple(names) if names is not None else None)


@dataclasses.dataclass
class CompiledPlan:
    """Padded per-level tensors for batched max-plus relaxation.

    Flat slot ``nlv_p·Vmax`` (``flat_dummy``) is a scratch cell: padded
    in-edge gathers read it; it is excluded from reductions via
    ``valid_flat``.
    """

    # per-vertex in-edge tensors (segment backend)
    vsrc: np.ndarray       # [nlv_p, Vmax, Dmax] int32 (flat slots, pad → flat_dummy)
    vmaskd: np.ndarray     # [nlv_p, Vmax, Dmax] bool
    vconst: np.ndarray     # [nlv_p, Vmax, Dmax] float64
    vgap: np.ndarray       # [nlv_p, Vmax, Dmax] float64
    vgclass: np.ndarray    # [nlv_p, Vmax, Dmax] int32
    vlat: np.ndarray       # [nlv_p, Vmax, Dmax, nclass] float64
    vlat_sum: np.ndarray   # [nlv_p, Vmax, Dmax] float64 (tie-break slopes)
    vcost_lv: np.ndarray   # [nlv_p, Vmax] float64
    valid_flat: np.ndarray  # [nlv_p·Vmax + 1] bool
    vert_of_slot: np.ndarray  # [nlv_p·Vmax + 1] int32 (original id, pad → nv)
    # per-edge tensors (pallas backend)
    esrc: np.ndarray       # [nlv_p, Emax] int32 (flat slots, pad → flat_dummy)
    edstl: np.ndarray      # [nlv_p, Emax] int32 (level-local slot, pad → Vmax)
    emask: np.ndarray      # [nlv_p, Emax] bool
    econst: np.ndarray     # [nlv_p, Emax] float64
    egap: np.ndarray       # [nlv_p, Emax] float64
    egclass: np.ndarray    # [nlv_p, Emax] int32
    elat: np.ndarray       # [nlv_p, Emax, nclass] float64
    # scalars
    nv: int
    nclass: int
    nlevels: int
    # edge → slot coordinates in ORIGINAL edge order (immutable structure;
    # all level-local, so they survive repadding unchanged).  None only on
    # hand-assembled plans, which then cannot patch costs.
    epos_lvl: Optional[np.ndarray] = None   # [ne] int32 destination level
    epos_dst: Optional[np.ndarray] = None   # [ne] int32 level-local dst slot
    epos_d: Optional[np.ndarray] = None     # [ne] int32 in-edge ordinal
    epos_e: Optional[np.ndarray] = None     # [ne] int32 level-local edge slot
    # physical-link slot tensors (congestion fixed point): the dense link id
    # of each in-edge slot / pallas edge slot; dummy bin = ``nlinks`` (pad
    # slots and dependency edges land there, and the fixed point pins its
    # scale to 1).  Auxiliary — staged only under congestion, and excluded
    # from the dense_bytes/padding_ratio accounting.  None on hand-
    # assembled plans (congestion then refuses to run).
    vlink: Optional[np.ndarray] = None      # [nlv_p, Vmax, Dmax] int32
    elinkp: Optional[np.ndarray] = None     # [nlv_p, Emax] int32
    nlinks: int = 0
    link_classes: Optional[np.ndarray] = None  # [nlinks] int32

    @property
    def Vmax(self) -> int:
        return int(self.vsrc.shape[1])

    @property
    def flat_dummy(self) -> int:
        return int(self.vsrc.shape[0]) * self.Vmax

    @property
    def shape_key(self) -> tuple:
        """Bucketed shapes — two plans with equal keys share one XLA program."""
        return self.vsrc.shape + self.esrc.shape[1:] + (self.nclass,)

    @property
    def padding_ratio(self) -> float:
        """Padded bytes / real-work bytes across the dense per-vertex
        tensors, λ tie-break arrays (``vlat``/``vlat_sum``) included — the
        compile-quality diagnostic feeding the dense→sparse auto-switch
        alongside :meth:`dense_bytes`."""
        per_slot = 33 + 8 * self.nclass       # one in-edge slot, all fields
        per_vert = 12                          # vcost_lv + λ argmax plane
        nlv, Vmax, _ = self.vsrc.shape
        real = (max(int(self.vmaskd.sum()), 1) * per_slot
                + max(self.nv, 1) * per_vert)
        padded = self.vmaskd.size * per_slot + nlv * Vmax * per_vert
        return padded / real

    def dense_indicator(self, neg: float = -1e30) -> np.ndarray:
        """[nlv_p, Vmax, Emax] float32 0/−inf scatter matrix for the Pallas
        backend: row v of level lv is 0 at the slots of v's in-edges.  The
        (max,+) product of this matrix with per-edge candidate values is
        exactly the level's scatter-max."""
        nlv, Emax = self.esrc.shape
        A = np.full((nlv, self.Vmax, Emax), neg, dtype=np.float32)
        lv, sl = np.nonzero(self.emask)
        A[lv, self.edstl[lv, sl], sl] = 0.0
        return A

    def segment_bytes(self) -> int:
        """Bytes the segment backend stages (padded per-vertex tensors,
        λ tie-break slope array included)."""
        nlv, Vmax, Dmax = self.vsrc.shape
        return _segment_view_bytes(nlv, Vmax, Dmax, self.nclass)

    def dense_bytes(self) -> int:
        """Total padded dense footprint across both backend views — the
        segment per-vertex tensors plus the pallas 0/−inf indicator, f32
        edge tensors, and λ argmax planes.  This (not just the indicator)
        is what the dense→sparse auto-switch compares to
        ``MAX_DENSE_BYTES``."""
        nlv, Emax = self.esrc.shape
        return (self.segment_bytes()
                + _pallas_view_bytes(nlv, self.Vmax, Emax, self.nclass))

    def content_hash(self) -> str:
        """SHA1 over the compiled tensors — keys memoized sweep results.

        Hashes canonical bytes (dtype + shape + C-order buffer, see
        :func:`repro.sweep.cache.canonical_bytes`), so the key is stable
        across processes and collision-safe across tensor layouts.
        """
        h = getattr(self, "_hash", None)
        if h is None:
            from .cache import canonical_bytes
            sha = hashlib.sha1(b"compiled-plan-v3")
            sha.update(np.int64([self.nv, self.nclass, self.nlevels]).tobytes())
            for a in (self.vsrc, self.vmaskd, self.vconst, self.vgap,
                      self.vgclass, self.vlat, self.vcost_lv, self.vert_of_slot):
                for chunk in canonical_bytes(a):
                    sha.update(chunk)
            h = sha.hexdigest()
            object.__setattr__(self, "_hash", h)
        return h

    def link_hash(self) -> str:
        """SHA1 over the link-id tensors and per-link classes — folded into
        query keys only when the congestion fixed point is on (plain runs
        never consume links, so ``content_hash`` stays link-blind)."""
        h = getattr(self, "_lhash", None)
        if h is None:
            from .cache import canonical_bytes
            sha = hashlib.sha1(b"plan-links-v1")
            sha.update(np.int64([self.nlinks]).tobytes())
            for a in (self.vlink, self.link_classes):
                if a is None:
                    sha.update(b"|none|")
                    continue
                for chunk in canonical_bytes(a):
                    sha.update(chunk)
            h = sha.hexdigest()
            object.__setattr__(self, "_lhash", h)
        return h

    # -- cost patching (zero-recompile variant evaluation) -------------------

    def patch_costs(self, extra_edge_cost: np.ndarray,
                    views: Sequence[str] = ("vertex", "edge")) -> CostBatch:
        """Stack K candidate cost blocks: baked costs + per-edge extras.

        ``extra_edge_cost``: [ne] or [K, ne] µs in *original* edge order —
        the same array :func:`compile_plan`'s ``extra_edge_cost=`` takes.
        Row k of the result is bit-identical to the cost block of
        ``compile_plan(g, extra_edge_cost=extra[k])``: the extra is added
        to the baked float64 edge constant at its recorded slot, exactly
        the addition the rebuild performs before scattering.

        ``views`` limits which backend's constants are materialized —
        ``("vertex",)`` patches only ``vconst`` (segment backend),
        ``("edge",)`` only ``econst`` (pallas).  The engine uses this
        internally (``run(costs=<[K, ne] array>)``) so a placement step
        never pays for the view it won't evaluate; the engine refuses a
        view-limited batch on the other backend.
        """
        if self.epos_lvl is None:
            raise ValueError(
                "plan carries no edge-position records (hand-assembled?); "
                "recompile with compile_plan() to enable cost patching")
        bad = set(views) - {"vertex", "edge"}
        if bad or not views:
            raise ValueError(f"views must name 'vertex' and/or 'edge', "
                             f"got {tuple(views)}")
        ex = np.atleast_2d(np.asarray(extra_edge_cost, dtype=np.float64))
        K, ne = ex.shape
        if ne != self.epos_lvl.shape[0]:
            raise ValueError(f"extra_edge_cost has {ne} edges, plan was "
                             f"compiled from {self.epos_lvl.shape[0]}")

        def rest(a):
            return np.broadcast_to(a[None], (K,) + a.shape)

        if "vertex" in views:
            vconst = np.repeat(self.vconst[None], K, axis=0)
            vconst[:, self.epos_lvl, self.epos_dst, self.epos_d] += ex
        else:
            vconst = rest(self.vconst)
        if "edge" in views:
            econst = np.repeat(self.econst[None], K, axis=0)
            econst[:, self.epos_lvl, self.epos_e] += ex
        else:
            econst = rest(self.econst)

        return CostBatch(vconst=vconst, vgap=rest(self.vgap),
                         vgclass=rest(self.vgclass), vlat=rest(self.vlat),
                         vlat_sum=rest(self.vlat_sum), econst=econst,
                         egap=rest(self.egap), egclass=rest(self.egclass),
                         elat=rest(self.elat),
                         plan_hash=self.content_hash())

    def with_extra_cost(self, extra_edge_cost: np.ndarray) -> "CompiledPlan":
        """A new plan with ``extra_edge_cost`` patched into the baked edge
        constants — structure arrays shared, so it lands in the same shape
        bucket (same XLA program) as its parent.  Bit-identical to
        ``compile_plan(g, extra_edge_cost=...)`` on the same graph."""
        cb = self.patch_costs(
            np.asarray(extra_edge_cost, dtype=np.float64).ravel())
        return dataclasses.replace(self, vconst=cb.vconst[0],
                                   econst=cb.econst[0])

    # -- structure patching (zero-recompile topology studies) ----------------

    def patch_structure(self, src: Optional[np.ndarray] = None,
                        keep: Optional[np.ndarray] = None,
                        names: Optional[Sequence[str]] = None
                        ) -> StructureBatch:
        """Stack B edge-rewired structural variants of this plan.

        ``src``: [ne] or [B, ne] *original vertex ids* in original edge
        order — the new source of each edge (``None`` keeps every baked
        source).  ``keep``: [ne] or [B, ne] bool — ``False`` removes the
        edge from that variant.  Destinations, per-edge costs, and the
        level schedule are fixed by the envelope; every kept edge's new
        source must sit at a strictly lower topological level than its
        destination (checked), which is exactly the class of rewirings a
        topology study sweeps: collective-algorithm swaps and link
        re-routes on a fixed super-graph.

        λ stays bit-exact per variant: removals leave surviving edges at
        their baked in-edge ordinals, and the tie-break consumes only the
        ordinals' *relative* order per destination — which matches a
        ground-up rebuild, whose compaction also preserves original edge
        order.
        """
        if self.epos_lvl is None:
            raise ValueError(
                "plan carries no edge-position records (hand-assembled?); "
                "recompile with compile_plan() to enable structure patching")
        if src is None and keep is None:
            raise ValueError("patch_structure needs src and/or keep")
        ne = self.epos_lvl.shape[0]
        if src is not None:
            src = np.atleast_2d(np.asarray(src, dtype=np.int64))
        if keep is not None:
            keep = np.atleast_2d(np.asarray(keep, dtype=bool))
        B = src.shape[0] if src is not None else keep.shape[0]
        if keep is None:
            keep = np.broadcast_to(np.ones(ne, dtype=bool), (B, ne))
        lvl = self.epos_lvl.astype(np.int64)
        dst = self.epos_dst.astype(np.int64)
        d = self.epos_d.astype(np.int64)
        es = self.epos_e.astype(np.int64)
        if src is None:
            baked = self.vert_of_slot[self.vsrc[lvl, dst, d]].astype(np.int64)
            src = np.broadcast_to(baked, (B, ne))
        if src.shape != (B, ne) or keep.shape != (B, ne):
            raise ValueError(
                f"src/keep must be [B, {ne}] in original edge order, got "
                f"{src.shape} / {keep.shape}")
        # original vertex id → flat slot (inverse of vert_of_slot)
        slots = np.nonzero(self.valid_flat[:self.flat_dummy])[0]
        sov = np.full(self.nv, -1, dtype=np.int64)
        sov[self.vert_of_slot[slots]] = slots
        ok = (src >= 0) & (src < self.nv)
        if not bool(np.all(ok | ~keep)):
            raise ValueError("src names vertex ids outside [0, nv)")
        srcslot = sov[np.where(keep & ok, src, 0)]
        if bool(np.any(keep & (srcslot // self.Vmax >= lvl))):
            raise ValueError(
                "structure patch violates the level schedule: every kept "
                "edge's new source must sit at a strictly lower "
                "topological level than its destination")
        new_src = np.where(keep, srcslot, self.flat_dummy).astype(np.int32)
        vsrc = np.repeat(self.vsrc[None], B, axis=0)
        vsrc[:, lvl, dst, d] = new_src
        vmaskd = np.repeat(self.vmaskd[None], B, axis=0)
        vmaskd[:, lvl, dst, d] = keep
        esrc = np.repeat(self.esrc[None], B, axis=0)
        esrc[:, lvl, es] = new_src
        emask = np.repeat(self.emask[None], B, axis=0)
        emask[:, lvl, es] = keep

        def rest(a):
            if a is None:
                return None
            return np.broadcast_to(a[None], (B,) + a.shape)

        done = {"vsrc": vsrc, "vmaskd": vmaskd, "esrc": esrc, "emask": emask}
        return StructureBatch(
            **done,
            **{n: rest(getattr(self, n)) for n in STRUCT_FIELDS
               if n not in done},
            base=self, plan_hash=self.content_hash(),
            names=tuple(names) if names is not None else None)


def compile_plan(g: ExecutionGraph, params: Optional[LogGPS] = None,
                 bucket: bool = True,
                 extra_edge_cost: Optional[np.ndarray] = None) -> CompiledPlan:
    """Compile an execution graph into a :class:`CompiledPlan`.

    Gap decomposition (the γ·G bandwidth-scenario axis) prefers the per-edge
    shares the graph recorded at build time (``g.egap``/``g.egclass`` — exact
    regardless of what parameters the caller now holds).  ``params`` is
    consulted as a fallback for message edges without a recorded share
    (hand-built graphs, or raw ``add_edge(nbytes=...)`` calls that didn't
    pass ``gap_us``); with neither, the gap share is 0 and bandwidth
    scenarios become no-ops (latency sweeps are unaffected either way).

    ``extra_edge_cost`` (original edge order, µs) is added to each edge's
    constant — the compiled analog of ``LevelPlan.forward(extra_edge_cost=)``,
    used by the placement search to bake a candidate rank mapping's Φ link
    costs into a plan.
    """
    nv, ne, nc = g.num_vertices, g.num_edges, g.nclass
    if nv == 0:
        raise ValueError("cannot compile an empty graph")
    nlevels = g.nlevels

    # -- sort edges by (destination level, destination, original id), the
    #    scalar LevelPlan order — preserved so argmax tie-breaks agree -------
    lvl_of_edge = g.level[g.edst]
    eorder = np.lexsort((g.edst, lvl_of_edge))
    esrc_s = g.esrc[eorder].astype(np.int64)
    edst_s = g.edst[eorder].astype(np.int64)
    econst_s = g.econst[eorder].astype(np.float64)
    if extra_edge_cost is not None:
        econst_s = econst_s + np.asarray(extra_edge_cost,
                                         dtype=np.float64)[eorder]
    ebytes_s = g.ebytes[eorder].astype(np.float64)
    elat_s = g.elat[eorder].astype(np.float64)
    elvl_s = lvl_of_edge[eorder].astype(np.int64)
    level_ptr = np.searchsorted(elvl_s, np.arange(nlevels + 1))

    # -- group vertices by level (ascending id within a level) --------------
    vorder = np.argsort(g.level, kind="stable").astype(np.int64)
    vlvl_s = g.level[vorder].astype(np.int64)
    v_ptr = np.searchsorted(vlvl_s, np.arange(nlevels + 1))

    # in-degree runs: edges of one destination are contiguous in eorder
    indeg = np.bincount(edst_s, minlength=nv)
    ecnt = np.diff(level_ptr)
    vcnt = np.diff(v_ptr)
    Emax = _bucket(ecnt.max(initial=1)) if bucket else max(int(ecnt.max(initial=1)), 1)
    Vmax = _bucket(vcnt.max(initial=1)) if bucket else max(int(vcnt.max(initial=1)), 1)
    Dmax = _bucket(indeg.max(initial=1), lo=2) if bucket else max(int(indeg.max(initial=1)), 1)
    nlv_p = _bucket(nlevels) if bucket else nlevels
    flat_dummy = nlv_p * Vmax

    # -- gap decomposition (bandwidth scenarios): recorded shares are
    #    authoritative, unknown shares reconstruct from params ------------
    egap_o, egclass_o = edge_gap_shares(g, params)
    egap_s = egap_o[eorder]
    egclass_s = egclass_o[eorder]

    # -- link interning (congestion): -1 / missing info → dummy bin --------
    if g.elink is not None and g.elink.shape[0] == ne:
        nlinks = int(g.nlinks)
        elink_s = g.elink[eorder].astype(np.int64)
        elink_s = np.where((elink_s < 0) | (elink_s >= nlinks), nlinks,
                           elink_s)
        link_classes = (g.link_classes.astype(np.int32)
                        if g.link_classes is not None
                        else np.zeros(nlinks, dtype=np.int32))
    else:
        nlinks = 0
        elink_s = np.zeros(ne, dtype=np.int64)
        link_classes = np.zeros(0, dtype=np.int32)

    # -- vertex → (level, offset) flat slots --------------------------------
    vslot = np.arange(nv, dtype=np.int64) - v_ptr[vlvl_s]     # offset of vorder[i]
    slot_of_vertex = np.empty(nv, dtype=np.int64)
    slot_of_vertex[vorder] = vlvl_s * Vmax + vslot

    # -- per-edge placement: (level, local dst slot, in-edge ordinal) -------
    eslot = np.arange(ne, dtype=np.int64) - level_ptr[elvl_s]
    dst_slot_flat = slot_of_vertex[edst_s]
    edstl_s = dst_slot_flat - elvl_s * Vmax                    # level-local
    ekey = elvl_s * np.int64(nv + 1) + edst_s                  # sorted by construction
    run_start = np.searchsorted(ekey, ekey)                    # first edge of dst run
    d_idx = np.arange(ne, dtype=np.int64) - run_start          # in-edge ordinal

    # -- per-vertex view ----------------------------------------------------
    vsrc = np.full((nlv_p, Vmax, Dmax), flat_dummy, dtype=np.int32)
    vmaskd = np.zeros((nlv_p, Vmax, Dmax), dtype=bool)
    vconst = np.zeros((nlv_p, Vmax, Dmax))
    vgap = np.zeros((nlv_p, Vmax, Dmax))
    vgclass = np.zeros((nlv_p, Vmax, Dmax), dtype=np.int32)
    vlat = np.zeros((nlv_p, Vmax, Dmax, nc))
    vsrc[elvl_s, edstl_s, d_idx] = slot_of_vertex[esrc_s]
    vmaskd[elvl_s, edstl_s, d_idx] = True
    vconst[elvl_s, edstl_s, d_idx] = econst_s
    vgap[elvl_s, edstl_s, d_idx] = egap_s
    vgclass[elvl_s, edstl_s, d_idx] = egclass_s
    vlat[elvl_s, edstl_s, d_idx] = elat_s
    vlink = np.full((nlv_p, Vmax, Dmax), nlinks, dtype=np.int32)
    vlink[elvl_s, edstl_s, d_idx] = elink_s

    vcost_lv = np.zeros((nlv_p, Vmax))
    vcost_lv[vlvl_s, vslot] = g.vcost[vorder]
    valid_flat = np.zeros(flat_dummy + 1, dtype=bool)
    valid_flat[vlvl_s * Vmax + vslot] = True
    vert_of_slot = np.full(flat_dummy + 1, nv, dtype=np.int32)
    vert_of_slot[vlvl_s * Vmax + vslot] = vorder

    # -- per-edge view (pallas backend) -------------------------------------
    esrc_p = np.full((nlv_p, Emax), flat_dummy, dtype=np.int32)
    edstl_p = np.full((nlv_p, Emax), Vmax, dtype=np.int32)
    emask = np.zeros((nlv_p, Emax), dtype=bool)
    econst_p = np.zeros((nlv_p, Emax))
    egap_p = np.zeros((nlv_p, Emax))
    egclass_p = np.zeros((nlv_p, Emax), dtype=np.int32)
    elat_p = np.zeros((nlv_p, Emax, nc))
    esrc_p[elvl_s, eslot] = slot_of_vertex[esrc_s]
    edstl_p[elvl_s, eslot] = edstl_s
    emask[elvl_s, eslot] = True
    econst_p[elvl_s, eslot] = econst_s
    egap_p[elvl_s, eslot] = egap_s
    egclass_p[elvl_s, eslot] = egclass_s
    elat_p[elvl_s, eslot] = elat_s
    elinkp = np.full((nlv_p, Emax), nlinks, dtype=np.int32)
    elinkp[elvl_s, eslot] = elink_s

    # -- edge slot coordinates back in original order (cost patching) -------
    def unsort(a):
        out = np.empty(ne, dtype=np.int32)
        out[eorder] = a
        return out

    return CompiledPlan(
        vsrc=vsrc, vmaskd=vmaskd, vconst=vconst, vgap=vgap, vgclass=vgclass,
        vlat=vlat, vlat_sum=vlat.sum(axis=3), vcost_lv=vcost_lv,
        valid_flat=valid_flat, vert_of_slot=vert_of_slot,
        esrc=esrc_p, edstl=edstl_p, emask=emask, econst=econst_p,
        egap=egap_p, egclass=egclass_p, elat=elat_p,
        nv=nv, nclass=nc, nlevels=nlevels,
        epos_lvl=unsort(elvl_s), epos_dst=unsort(edstl_s),
        epos_d=unsort(d_idx), epos_e=unsort(eslot),
        vlink=vlink, elinkp=elinkp, nlinks=nlinks,
        link_classes=link_classes,
    )


# -- multi-graph packing ------------------------------------------------------

def repad_plan(c: CompiledPlan, nlv_p: int, Vmax: int, Dmax: int,
               Emax: int) -> CompiledPlan:
    """Re-lay a compiled plan onto a larger (nlv_p, Vmax, Dmax, Emax) envelope.

    Flat slots are recomputed for the new Vmax (``slot = lv·Vmax + offset``;
    level-local offsets are envelope-independent), so the repadded plan's
    forward pass produces *identical* floating-point results — padding only
    adds masked −∞ candidates, and max-reductions are exact.
    """
    nlv0, V0, D0 = c.vsrc.shape
    E0 = c.esrc.shape[1]
    if (nlv_p, Vmax, Dmax, Emax) == (nlv0, V0, D0, E0):
        return c
    if nlv_p < nlv0 or Vmax < V0 or Dmax < D0 or Emax < E0:
        raise ValueError(f"target envelope {(nlv_p, Vmax, Dmax, Emax)} smaller "
                         f"than plan's {(nlv0, V0, D0, E0)}")
    dummy0, dummy1 = c.flat_dummy, nlv_p * Vmax

    def remap_slots(a):
        """Old flat slots → new flat slots (pad slots → new dummy)."""
        lv, off = a // V0, a % V0
        return np.where(a == dummy0, dummy1, lv * Vmax + off).astype(np.int32)

    vsrc = np.full((nlv_p, Vmax, Dmax), dummy1, dtype=np.int32)
    vsrc[:nlv0, :V0, :D0] = remap_slots(c.vsrc.astype(np.int64))
    vmaskd = np.zeros((nlv_p, Vmax, Dmax), dtype=bool)
    vmaskd[:nlv0, :V0, :D0] = c.vmaskd

    def grow(a, shape, fill=0.0):
        out = np.full(shape, fill, dtype=a.dtype)
        out[tuple(slice(0, s) for s in a.shape)] = a
        return out

    nc = c.nclass
    vconst = grow(c.vconst, (nlv_p, Vmax, Dmax))
    vgap = grow(c.vgap, (nlv_p, Vmax, Dmax))
    vgclass = grow(c.vgclass, (nlv_p, Vmax, Dmax))
    vlat = grow(c.vlat, (nlv_p, Vmax, Dmax, nc))
    vlat_sum = grow(c.vlat_sum, (nlv_p, Vmax, Dmax))
    vcost_lv = grow(c.vcost_lv, (nlv_p, Vmax))

    valid_flat = np.zeros(dummy1 + 1, dtype=bool)
    vert_of_slot = np.full(dummy1 + 1, c.nv, dtype=np.int32)
    old = np.nonzero(c.valid_flat[:dummy0])[0]
    new = (old // V0) * Vmax + old % V0
    valid_flat[new] = True
    vert_of_slot[new] = c.vert_of_slot[old]

    esrc = np.full((nlv_p, Emax), dummy1, dtype=np.int32)
    esrc[:nlv0, :E0] = remap_slots(c.esrc.astype(np.int64))
    edstl = np.full((nlv_p, Emax), Vmax, dtype=np.int32)
    edstl[:nlv0, :E0] = np.where(c.emask, c.edstl, Vmax)
    emask = np.zeros((nlv_p, Emax), dtype=bool)
    emask[:nlv0, :E0] = c.emask
    econst = grow(c.econst, (nlv_p, Emax))
    egap = grow(c.egap, (nlv_p, Emax))
    egclass = grow(c.egclass, (nlv_p, Emax))
    elat = grow(c.elat, (nlv_p, Emax, nc))
    # link pad slots must land in the dummy bin (= nlinks), never link 0
    vlink = None if c.vlink is None else \
        grow(c.vlink, (nlv_p, Vmax, Dmax), fill=c.nlinks)
    elinkp = None if c.elinkp is None else \
        grow(c.elinkp, (nlv_p, Emax), fill=c.nlinks)

    return CompiledPlan(
        vsrc=vsrc, vmaskd=vmaskd, vconst=vconst, vgap=vgap, vgclass=vgclass,
        vlat=vlat, vlat_sum=vlat_sum, vcost_lv=vcost_lv,
        valid_flat=valid_flat, vert_of_slot=vert_of_slot,
        esrc=esrc, edstl=edstl, emask=emask, econst=econst,
        egap=egap, egclass=egclass, elat=elat,
        nv=c.nv, nclass=nc, nlevels=c.nlevels,
        # level-local coordinates are envelope-independent: patching keeps
        # working on a repadded plan
        epos_lvl=c.epos_lvl, epos_dst=c.epos_dst,
        epos_d=c.epos_d, epos_e=c.epos_e,
        vlink=vlink, elinkp=elinkp, nlinks=c.nlinks,
        link_classes=c.link_classes,
    )


@dataclasses.dataclass
class MultiPlan:
    """G compiled plans stacked on a leading graph axis (common envelope).

    Field names and meanings mirror :class:`CompiledPlan` with one extra
    leading dimension; scalar per-plan metadata becomes per-graph arrays.
    One MultiPlan = one XLA program for the whole variant group.
    """

    vsrc: np.ndarray       # [G, nlv_p, Vmax, Dmax] int32
    vmaskd: np.ndarray     # [G, nlv_p, Vmax, Dmax] bool
    vconst: np.ndarray
    vgap: np.ndarray
    vgclass: np.ndarray
    vlat: np.ndarray       # [G, nlv_p, Vmax, Dmax, nclass]
    vlat_sum: np.ndarray
    vcost_lv: np.ndarray   # [G, nlv_p, Vmax]
    valid_flat: np.ndarray  # [G, nlv_p·Vmax + 1]
    vert_of_slot: np.ndarray
    esrc: np.ndarray       # [G, nlv_p, Emax]
    edstl: np.ndarray
    emask: np.ndarray
    econst: np.ndarray
    egap: np.ndarray
    egclass: np.ndarray
    elat: np.ndarray       # [G, nlv_p, Emax, nclass]
    nv: np.ndarray         # [G] int64
    nlevels: np.ndarray    # [G] int64
    nclass: int
    plan_hashes: tuple     # member CompiledPlan content hashes, in order

    @property
    def G(self) -> int:
        return int(self.vsrc.shape[0])

    @property
    def Vmax(self) -> int:
        return int(self.vsrc.shape[2])

    @property
    def shape_key(self) -> tuple:
        return self.vsrc.shape + self.esrc.shape[2:] + (self.nclass,)

    def dense_indicator(self, neg: float = -1e30) -> np.ndarray:
        """[G, nlv_p, Vmax, Emax] 0/−inf scatter matrices (Pallas backend)."""
        G, nlv, Emax = self.esrc.shape
        A = np.full((G, nlv, self.Vmax, Emax), neg, dtype=np.float32)
        gi, lv, sl = np.nonzero(self.emask)
        A[gi, lv, self.edstl[gi, lv, sl], sl] = 0.0
        return A

    def dense_bytes(self) -> int:
        G, nlv, Emax = self.esrc.shape
        _, _, Vmax, Dmax = self.vsrc.shape
        return G * (_segment_view_bytes(nlv, Vmax, Dmax, self.nclass)
                    + _pallas_view_bytes(nlv, Vmax, Emax, self.nclass))

    def content_hash(self) -> str:
        """Order-sensitive hash over the member plans + envelope."""
        h = getattr(self, "_hash", None)
        if h is None:
            sha = hashlib.sha1(b"multi-plan-v1")
            sha.update(repr(self.shape_key).encode())
            for ph in self.plan_hashes:
                sha.update(ph.encode())
            h = sha.hexdigest()
            object.__setattr__(self, "_hash", h)
        return h


def pack_plans(plans: Sequence[CompiledPlan]) -> MultiPlan:
    """Pad compiled plans to their common envelope and stack on a graph axis.

    All plans must share ``nclass`` (the scenario row width).  The envelope is
    the per-dimension max — already power-of-two bucketed, so packing never
    invents new shapes beyond what the largest member compiled to.
    """
    if not plans:
        raise ValueError("pack_plans needs at least one plan")
    nc = plans[0].nclass
    if any(p.nclass != nc for p in plans):
        raise ValueError("cannot pack plans with different latency-class "
                         "counts into one MultiPlan")
    nlv = max(p.vsrc.shape[0] for p in plans)
    Vm = max(p.vsrc.shape[1] for p in plans)
    Dm = max(p.vsrc.shape[2] for p in plans)
    Em = max(p.esrc.shape[1] for p in plans)
    hashes = tuple(p.content_hash() for p in plans)
    padded = [repad_plan(p, nlv, Vm, Dm, Em) for p in plans]

    def stack(name):
        return np.stack([getattr(p, name) for p in padded])

    return MultiPlan(
        vsrc=stack("vsrc"), vmaskd=stack("vmaskd"), vconst=stack("vconst"),
        vgap=stack("vgap"), vgclass=stack("vgclass"), vlat=stack("vlat"),
        vlat_sum=stack("vlat_sum"), vcost_lv=stack("vcost_lv"),
        valid_flat=stack("valid_flat"), vert_of_slot=stack("vert_of_slot"),
        esrc=stack("esrc"), edstl=stack("edstl"), emask=stack("emask"),
        econst=stack("econst"), egap=stack("egap"), egclass=stack("egclass"),
        elat=stack("elat"),
        nv=np.asarray([p.nv for p in plans], dtype=np.int64),
        nlevels=np.asarray([p.nlevels for p in plans], dtype=np.int64),
        nclass=nc, plan_hashes=hashes,
    )


def group_plans(plans: Sequence[CompiledPlan],
                max_inflation: float = 64.0) -> list:
    """Partition plan indices into packable groups (the "shape buckets").

    Plans pack together when they share ``nclass`` and no member's padded
    tensor volume inflates beyond ``max_inflation``× its natural size (so a
    toy graph never rides a 156M-event envelope).  Returns a list of index
    lists covering ``range(len(plans))`` in order; a variant study runs one
    compiled call per returned group.
    """
    def volume(shape4):
        nlv, V, D, E = shape4
        return nlv * V * max(D, E)

    groups: list = []
    meta: list = []           # (nclass, envelope shape4) per group
    for i, p in enumerate(plans):
        nat = p.vsrc.shape + (p.esrc.shape[1],)
        placed = False
        for gidx, (nc, env) in enumerate(meta):
            if nc != p.nclass:
                continue
            new_env = tuple(max(a, b) for a, b in zip(env, nat))
            members = [plans[j].vsrc.shape + (plans[j].esrc.shape[1],)
                       for j in groups[gidx]] + [nat]
            if all(volume(new_env) <= max_inflation * volume(m)
                   for m in members):
                groups[gidx].append(i)
                meta[gidx] = (nc, new_env)
                placed = True
                break
        if not placed:
            groups.append([i])
            meta.append((p.nclass, nat))
    return groups


# -- sparse slot-list layout (beyond the dense envelope) ----------------------


@dataclasses.dataclass
class SparsePlan:
    """Compact CSR-style slot lists — no ``[nlv, Vmax, Dmax]`` padding.

    Vertices live at compact level-major slots ``0..nv-1`` (level
    ascending, original id ascending within a level — the same order the
    dense views use, so tie-breaks agree); edges sort by (destination
    level, destination, original id) exactly like :func:`compile_plan`.
    ``level_ptr``/``v_ptr`` delimit each level's edge and vertex runs, and
    the forward walks levels with fixed ``[Emax_lv]``/``[Vmax_lv]``
    windows (bucketed per-level maxima) via dynamic slices — memory is
    O(nv + ne), not O(nlv·Vmax·max(Dmax, Emax)).

    Each destination slot's in-edges form one run of that order:
    ``vin0``/``vdeg`` give its start and length, and ``Dmax`` (the
    bucketed maximum in-degree) sizes the scenario-shared
    ``[Dmax, Vmax_lv]`` in-edge view the forward reduces over wherever
    :attr:`step` is ``"indeg"``; elsewhere it runs a ``segment_max`` over
    the edge window.

    Padding invariants the sparse forward relies on:

    - ``ne_p ≥ ne + Emax_lv`` and ``nv_p ≥ nv + Vmax_lv``: real levels'
      windows never clamp, and padded levels' windows (which start at
      ``ne``/``nv``) only ever touch pad slots.
    - pad edges carry ``edst_slot = nv + Vmax_lv``, so their window-local
      destination is ≥ ``Vmax_lv`` at every level — dropped by JAX's
      scatter out-of-bounds semantics (and never negative).
    """

    esrc_slot: np.ndarray   # [ne_p] int32 compact slot of the edge source
    edst_slot: np.ndarray   # [ne_p] int32 compact slot of the destination
    emask: np.ndarray       # [ne_p] bool
    econst: np.ndarray      # [ne_p] float64
    egap: np.ndarray        # [ne_p] float64
    egclass: np.ndarray     # [ne_p] int32
    elat: np.ndarray        # [ne_p, nclass] float64
    elat_sum: np.ndarray    # [ne_p] float64 (λ tie-break slopes)
    vcost: np.ndarray       # [nv_p] float64
    valid: np.ndarray       # [nv_p] bool
    vert_of_slot: np.ndarray  # [nv_p] int32 (original id, pad → nv)
    level_ptr: np.ndarray   # [nlv_p + 1] int32 edge run starts (pad → ne)
    v_ptr: np.ndarray       # [nlv_p + 1] int32 vertex run starts (pad → nv)
    nv: int
    ne: int
    nclass: int
    nlevels: int
    Emax_lv: int            # bucketed max edges in one level (window size)
    Vmax_lv: int            # bucketed max vertices in one level
    vin0: np.ndarray        # [nv_p] int32 position of the slot's 1st in-edge
    vdeg: np.ndarray        # [nv_p] int32 in-degree (pad → 0)
    Dmax: int               # bucketed max in-degree (in-edge view width)
    # physical-link ids per edge (congestion carriage; pad → nlinks dummy)
    elink: Optional[np.ndarray] = None  # [ne_p] int32
    nlinks: int = 0
    link_classes: Optional[np.ndarray] = None  # [nlinks] int32

    @property
    def shape_key(self) -> tuple:
        """Bucketed shapes + window sizes — equal keys share XLA programs."""
        return (self.esrc_slot.shape[0], self.vcost.shape[0],
                self.level_ptr.shape[0], self.Emax_lv, self.Vmax_lv,
                self.Dmax, self.nclass)

    @property
    def step(self) -> str:
        """The float64 forward's level step: ``"indeg"`` (the in-edge
        view) where it pads at most twice the edge window, i.e.
        ``Vmax_lv · Dmax ≤ 2 · Emax_lv``; else ``"segment"`` (a
        ``segment_max`` over the window): a high-in-degree vertex, such
        as a many-to-one gather's join, would pad the view far past the
        edges it holds."""
        return ("indeg" if self.Vmax_lv * self.Dmax <= 2 * self.Emax_lv
                else "segment")

    def sparse_bytes(self) -> int:
        """Bytes the sparse backend stages for this plan."""
        return sum(getattr(self, n).nbytes for n in (
            "esrc_slot", "edst_slot", "emask", "econst", "egap", "egclass",
            "elat", "elat_sum", "vcost", "valid", "vert_of_slot",
            "level_ptr", "v_ptr")
            + (("vin0", "vdeg") if self.step == "indeg" else ()))

    def content_hash(self) -> str:
        h = getattr(self, "_hash", None)
        if h is None:
            from .cache import canonical_bytes
            sha = hashlib.sha1(b"sparse-plan-v1")
            sha.update(np.int64([self.nv, self.ne, self.nclass,
                                 self.nlevels]).tobytes())
            for n in ("esrc_slot", "edst_slot", "emask", "econst", "egap",
                      "egclass", "elat", "vcost", "valid", "vert_of_slot",
                      "level_ptr", "v_ptr"):
                for chunk in canonical_bytes(getattr(self, n)):
                    sha.update(chunk)
            h = sha.hexdigest()
            object.__setattr__(self, "_hash", h)
        return h

    @classmethod
    def from_plan(cls, c: CompiledPlan) -> "SparsePlan":
        """Re-lay a dense plan as slot lists (the ``run(backend="sparse")``
        per-call override path).  Produces exactly what
        :func:`compile_sparse` builds from the source graph: the dense
        plan's ``epos_*`` records recover every edge in original order,
        and ascending flat-slot order IS compact level-major order."""
        if c.epos_lvl is None:
            raise ValueError(
                "plan carries no edge-position records (hand-assembled?); "
                "recompile with compile_plan() or use compile_sparse()")
        Vmax, dummy = c.Vmax, c.flat_dummy
        slots = np.nonzero(c.valid_flat[:dummy])[0]
        compact = np.full(dummy + 1, -1, dtype=np.int64)
        compact[slots] = np.arange(c.nv, dtype=np.int64)
        lvl = c.epos_lvl.astype(np.int64)
        es = c.epos_e.astype(np.int64)
        esrc_c = compact[c.esrc[lvl, es].astype(np.int64)]
        edst_c = compact[lvl * Vmax + c.epos_dst.astype(np.int64)]
        eorder = np.argsort(edst_c, kind="stable")
        vlvl_s = slots // Vmax
        v_ptr = np.searchsorted(vlvl_s, np.arange(c.nlevels + 1))
        elvl_s = lvl[eorder]
        level_ptr = np.searchsorted(elvl_s, np.arange(c.nlevels + 1))
        return _assemble_sparse(
            nv=c.nv, nc=c.nclass, nlevels=c.nlevels,
            esrc_s=esrc_c[eorder], edst_s=edst_c[eorder],
            econst_s=c.econst[lvl, es][eorder],
            egap_s=c.egap[lvl, es][eorder],
            egclass_s=c.egclass[lvl, es][eorder],
            elat_s=c.elat[lvl, es][eorder],
            vcost_s=c.vcost_lv[vlvl_s, slots % Vmax],
            vert_s=c.vert_of_slot[slots],
            level_ptr=level_ptr, v_ptr=v_ptr,
            elink_s=(None if c.elinkp is None
                     else c.elinkp[lvl, es][eorder]),
            nlinks=c.nlinks, link_classes=c.link_classes)


def _assemble_sparse(nv: int, nc: int, nlevels: int,
                     esrc_s: np.ndarray, edst_s: np.ndarray,
                     econst_s: np.ndarray, egap_s: np.ndarray,
                     egclass_s: np.ndarray, elat_s: np.ndarray,
                     vcost_s: np.ndarray, vert_s: np.ndarray,
                     level_ptr: np.ndarray, v_ptr: np.ndarray,
                     elink_s: Optional[np.ndarray] = None, nlinks: int = 0,
                     link_classes: Optional[np.ndarray] = None) -> SparsePlan:
    """Pad level-sorted compact-slot arrays into a :class:`SparsePlan`
    honouring the class's padding invariants."""
    ne = int(esrc_s.shape[0])
    Emax_lv = _bucket(int(np.diff(level_ptr).max(initial=1)))
    Vmax_lv = _bucket(int(np.diff(v_ptr).max(initial=1)))
    nlv_p = _bucket(nlevels)
    ne_p = _bucket(ne + Emax_lv)
    nv_p = _bucket(nv + Vmax_lv)

    def padv(a, n, fill, dtype=None):
        out = np.full((n,) + a.shape[1:], fill,
                      dtype=a.dtype if dtype is None else dtype)
        out[:a.shape[0]] = a
        return out

    elat_p = padv(elat_s.astype(np.float64), ne_p, 0.0)
    # edges sort by destination slot, so each slot's in-edges are one run
    vdeg = np.bincount(edst_s, minlength=nv_p).astype(np.int32)
    vin0 = (np.cumsum(vdeg) - vdeg).astype(np.int32)
    return SparsePlan(
        esrc_slot=padv(esrc_s, ne_p, 0, np.int32),
        edst_slot=padv(edst_s, ne_p, nv + Vmax_lv, np.int32),
        emask=padv(np.ones(ne, dtype=bool), ne_p, False),
        econst=padv(econst_s.astype(np.float64), ne_p, 0.0),
        egap=padv(egap_s.astype(np.float64), ne_p, 0.0),
        egclass=padv(egclass_s, ne_p, 0, np.int32),
        elat=elat_p, elat_sum=elat_p.sum(axis=1),
        vcost=padv(vcost_s.astype(np.float64), nv_p, 0.0),
        valid=padv(np.ones(nv, dtype=bool), nv_p, False),
        vert_of_slot=padv(vert_s, nv_p, nv, np.int32),
        level_ptr=padv(level_ptr, nlv_p + 1, ne, np.int32),
        v_ptr=padv(v_ptr, nlv_p + 1, nv, np.int32),
        nv=nv, ne=ne, nclass=nc, nlevels=nlevels,
        Emax_lv=Emax_lv, Vmax_lv=Vmax_lv,
        vin0=vin0, vdeg=vdeg, Dmax=_bucket(int(vdeg.max(initial=1)), lo=1),
        elink=(None if elink_s is None
               else padv(elink_s.astype(np.int32), ne_p, nlinks, np.int32)),
        nlinks=nlinks, link_classes=link_classes)


def compile_sparse(g: ExecutionGraph,
                   params: Optional[LogGPS] = None) -> SparsePlan:
    """Compile an execution graph straight into a :class:`SparsePlan`.

    Same edge/vertex orders and gap decomposition as :func:`compile_plan`
    (so T and λ agree bit-for-bit with the segment backend), but nothing
    is ever laid out dense — this is the entry point for graphs whose
    padded envelope would blow past ``MAX_DENSE_BYTES``.
    """
    nv, ne, nc = g.num_vertices, g.num_edges, g.nclass
    if nv == 0:
        raise ValueError("cannot compile an empty graph")
    nlevels = g.nlevels
    lvl_of_edge = g.level[g.edst]
    eorder = np.lexsort((g.edst, lvl_of_edge))
    elvl_s = lvl_of_edge[eorder].astype(np.int64)
    level_ptr = np.searchsorted(elvl_s, np.arange(nlevels + 1))
    vorder = np.argsort(g.level, kind="stable").astype(np.int64)
    vlvl_s = g.level[vorder].astype(np.int64)
    v_ptr = np.searchsorted(vlvl_s, np.arange(nlevels + 1))
    slot_of_vertex = np.empty(nv, dtype=np.int64)
    slot_of_vertex[vorder] = np.arange(nv, dtype=np.int64)
    egap_o, egclass_o = edge_gap_shares(g, params)
    if g.elink is not None and g.elink.shape[0] == ne:
        nlinks = int(g.nlinks)
        el = g.elink[eorder].astype(np.int64)
        elink_s = np.where((el < 0) | (el >= nlinks), nlinks, el)
        link_classes = (g.link_classes.astype(np.int32)
                        if g.link_classes is not None
                        else np.zeros(nlinks, dtype=np.int32))
    else:
        nlinks, elink_s, link_classes = 0, None, None
    return _assemble_sparse(
        nv=nv, nc=nc, nlevels=nlevels,
        esrc_s=slot_of_vertex[g.esrc[eorder].astype(np.int64)],
        edst_s=slot_of_vertex[g.edst[eorder].astype(np.int64)],
        econst_s=g.econst[eorder].astype(np.float64),
        egap_s=egap_o[eorder], egclass_s=egclass_o[eorder],
        elat_s=g.elat[eorder].astype(np.float64),
        vcost_s=g.vcost[vorder].astype(np.float64),
        vert_s=vorder, level_ptr=level_ptr, v_ptr=v_ptr,
        elink_s=elink_s, nlinks=nlinks, link_classes=link_classes)


def estimate_dense_bytes(g: ExecutionGraph) -> int:
    """What :meth:`CompiledPlan.dense_bytes` would report for ``g``,
    computed from degree statistics WITHOUT materializing the dense
    envelope — the dense materialization is itself the memory cliff, so
    the dense→sparse auto-switch must decide before compiling."""
    nv = g.num_vertices
    indeg = np.bincount(g.edst, minlength=nv)
    ecnt = np.bincount(g.level[g.edst], minlength=g.nlevels)
    vcnt = np.bincount(g.level, minlength=g.nlevels)
    Emax = _bucket(int(ecnt.max(initial=1)))
    Vmax = _bucket(int(vcnt.max(initial=1)))
    Dmax = _bucket(int(indeg.max(initial=1)), lo=2)
    nlv_p = _bucket(g.nlevels)
    return (_segment_view_bytes(nlv_p, Vmax, Dmax, g.nclass)
            + _pallas_view_bytes(nlv_p, Vmax, Emax, g.nclass))
