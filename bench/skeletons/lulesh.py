"""The communication skeleton of one LULESH 2.0 cycle, as a schedule.

LULESH 2.0 (LLNL-TR-641973; source ``lulesh.cc``, ``lulesh-comm.cc``) runs
one cube domain of ``s`` x ``s`` x ``s`` elements per MPI rank on a
``tp`` x ``tp`` x ``tp`` rank cube (rank = col + row tp + plane tp^2).  The
domain is not periodic: a rank exchanges with the up to 26 ranks that share
a face, an edge or a corner with it.  One cycle (``TimeIncrement`` then
``LagrangeLeapFrog``, built with ``SEDOV_SYNC_POS_VEL_EARLY``) is:

1. ``MPI_Allreduce`` (MIN) of the new time step: one double.  Eight bytes
   take recursive doubling, MPICH's algorithm for short messages
   (Thakur, Rabenseifner, Gropp 2005), ``log2(P)`` pairwise rounds.
2. ``CalcVolumeForceForElems``, then ``MSG_COMM_SBN``: the nodal forces
   (3 fields) to all 26 neighbours, summed on arrival (``CommSBN``).
3. Acceleration, velocity and position of the nodes, then
   ``MSG_SYNC_POS_VEL``: positions and velocities (6 fields), sent with
   ``doSend = false``, so only towards the neighbours of lower rank.
4. ``CalcLagrangeElements`` and the monotonic-Q gradients, then
   ``MSG_MONOQ``: the gradients (3 element fields) across the 6 faces only
   (``planeOnly = true``).
5. The monotonic Q, the material update and the time constraints.

Nodal messages carry ``(s + 1)^2`` values a face, ``s + 1`` an edge and one
a corner; element messages ``s^2`` a face; 8 bytes each.  Each exchange posts
all its sends, then waits for all its receives.  The compute phases cost
``cycle_us`` split by ``phase_share``.

``schedule`` returns the rank count and the steps of ``cycles`` cycles:
``("compute", row, cost_us)`` adds one compute vertex on every rank (row
``row`` of the jitter and of ``calc``), ``("round", msgs)`` one exchange of
``(src, dst, bytes)`` messages.  The program's input (``build.py``) and the
reference (``reference.py``) both walk it.
"""

from __future__ import annotations

import math

WORD = 8
OFFSETS = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
           for dx in (-1, 0, 1) if (dx, dy, dz) != (0, 0, 0)]


def jitter_shape(tp, cycles, phase_share, **_) -> tuple:
    return (cycles * len(phase_share), tp ** 3)


def _msg_values(off, n: int) -> int:
    """Values a message across a face / edge / corner carries for ``n``
    points along a side."""
    shared = 3 - sum(1 for d in off if d)       # 2 face, 1 edge, 0 corner
    return n ** shared


def _exchange(tp, fields, n, offsets, lower_only=False) -> list:
    msgs = []
    for r in range(tp ** 3):
        x, y, z = r % tp, (r // tp) % tp, r // (tp * tp)
        for off in offsets:
            nx, ny, nz = x + off[0], y + off[1], z + off[2]
            if not (0 <= nx < tp and 0 <= ny < tp and 0 <= nz < tp):
                continue
            dst = nx + ny * tp + nz * tp * tp
            if lower_only and dst > r:
                continue
            msgs.append((r, dst, float(fields * _msg_values(off, n) * WORD)))
    return msgs


def _allreduce_rd(P: int, nbytes: float) -> list:
    if P & (P - 1):
        raise ValueError(f"recursive doubling needs a power-of-two rank "
                         f"count, got {P}")
    rounds = []
    for k in range(int(math.log2(P))):
        msgs = []
        for i in range(P):
            j = i ^ (1 << k)
            if i < j:
                msgs += [(i, j, nbytes), (j, i, nbytes)]
        rounds.append(msgs)
    return rounds


def schedule(*, tp, s, cycles, cycle_us, phase_share):
    P = tp ** 3
    faces = [o for o in OFFSETS if sum(1 for d in o if d) == 1]
    sbn = _exchange(tp, 3, s + 1, OFFSETS)
    posvel = _exchange(tp, 6, s + 1, OFFSETS, lower_only=True)
    monoq = _exchange(tp, 3, s, faces)
    dt = _allreduce_rd(P, float(WORD))
    if len(phase_share) != 4:
        raise ValueError("a LULESH cycle has 4 compute phases, got "
                         f"{len(phase_share)} shares")
    cost = [cycle_us * f for f in phase_share]
    nph = len(cost)
    steps = []
    for c in range(cycles):
        steps += [("round", m) for m in dt]
        for ph, msgs in enumerate((sbn, posvel, monoq, None)):
            steps.append(("compute", c * nph + ph, cost[ph]))
            if msgs is not None:
                steps.append(("round", msgs))
    return P, steps
