"""repro.sweep — batched scenario-sweep engine (JAX/Pallas max-plus).

LLAMP's workhorse loop is "re-evaluate execution graphs under many LogGPS
parameter points" (latency curves, tolerance bisections, the Algorithm-2
breakpoint search, collective/topology variant studies, placement
candidate evaluation).  The scalar path pays a full Python/numpy level
walk per point; this subsystem compiles graphs ONCE into padded dense
per-level tensors and evaluates whole grids in single jit+vmap max-plus
forward passes.

**One engine, four axes.**  Every sweep is one :class:`~repro.sweep.api.
Engine` evaluating a :class:`~repro.sweep.api.Query` whose populated batch
axes — graphs [G] × structural variants [B] × candidate cost blocks [K] ×
scenarios [S] — compose freely (G and B are mutually exclusive leading
axes), under an :class:`~repro.sweep.api.ExecPolicy` (backend, device
sharding over any populated axis, exact-vs-finite-difference λ, cache):

    from repro import sweep

    # one graph × many scenarios
    eng  = sweep.Engine(graph, params=params)        # compile once
    grid = sweep.latency_grid(params, deltas)        # or cartesian_grid(...)
    res  = eng.run(grid)                             # T/λ/ρ for every scenario

    # graphs × candidate costs × scenarios, sharded over any axis
    eng  = sweep.Engine([plan_a, plan_b],
                        policy=sweep.ExecPolicy(shard=True, shard_axis="K"))
    res  = eng.run(sweep.Query(scenarios=grid, costs=[extras_a, extras_b]))
    res.T.shape                                      # [G, K, S]

Public surface (re-exported here):
    Engine / Query / ExecPolicy / Result — the unified axis-oriented API
                                        (repro.sweep.api)
    CompiledPlan / compile_plan       — graph → bucketed rectangular tensors
                                        (immutable structure + patchable
                                        cost block, see COST_FIELDS)
    CostBatch / CompiledPlan.patch_costs — K candidate cost blocks for one
                                        plan structure; the Query costs axis
                                        (zero recompiles)
    StructureBatch / CompiledPlan.patch_structure — B structural variants
                                        (edge rewirings, or separately
                                        compiled plans via ``from_plans``)
                                        inside one super-envelope; the Query
                                        structure axis (zero recompiles)
    SparsePlan / compile_sparse / estimate_dense_bytes — compact per-level
                                        slot lists for graphs whose dense
                                        envelope exceeds MAX_DENSE_BYTES
                                        (``ExecPolicy(backend="sparse")``;
                                        auto-selected off degree statistics)
    MultiPlan / pack_plans / group_plans — pad plans to a common envelope and
                                        stack them on a leading graph axis
    ScenarioBatch + grid builders     — latency_grid / bandwidth_grid /
                                        cartesian_grid / base_batch
    GraphVariant stamping             — collective_variants / topology_variants
                                        (axes that change the graph itself;
                                        run them through StructureBatch)
    Fault & straggler scenarios       — StragglerFault / LinkFault /
                                        DeviceFault + fault_axes /
                                        recovery_cost_us: a fault
                                        distribution lowered onto the B/K/S
                                        axes as ONE batched Query
                                        (``sensitivity.resilience_curve``)
    tolerance_batched / breakpoints_batched — dag.py's bisection loops in
                                        lockstep, one engine call per round
    SweepCache / DEFAULT_CACHE        — content-hash LRU memo of results
                                        (canonical-bytes keys, process-stable)

Results match ``core.dag`` exactly (same argmax tie-breaks, float64) — a
graph packed on the G axis returns bit-identical T/λ to its solo run, and
a cost block patched on the K axis returns bit-identical results to a
plan rebuilt with those costs — and λ matches the explicit LP's reduced
costs; ``core.sensitivity`` dispatches here automatically for multi-point
sweeps (``policy=`` forwards an ExecPolicy).  The Pallas ``maxplus``
kernel is the inner-scatter backend (``ExecPolicy(backend="pallas")``;
graphs ride the kernel's outer grid axis) and serves λ/ρ natively via its
argmax-emitting variant.  ``ExecPolicy(lam="fd")`` trades the bit-exact λ
backtrace for finite-difference λ over an (nc+1)× expanded values grid —
the same compiled values program, compile ratio ~1.0.
``launch.analysis.AnalysisService`` serves what-if queries over warm
engines built from these pieces (per-request ``policy`` blocks), over
stdin/stdout JSON lines or a TCP/UNIX socket.
"""

from .api import (Engine, ExecPolicy, Query, Result,  # noqa: F401
                  detached_engine, detached_engine_stats, run)
from .cache import (DEFAULT_CACHE, SweepCache, canonical_bytes,  # noqa: F401
                    graph_content_key)
from .compile import (COST_FIELDS, STRUCT_FIELDS, CompiledPlan,  # noqa: F401
                      CostBatch, MultiPlan, SparsePlan, StructureBatch,
                      compile_plan, compile_sparse, estimate_dense_bytes,
                      group_plans, pack_plans, repad_plan)
from .engine import breakpoints_batched, tolerance_batched  # noqa: F401
from .scenarios import (DeviceFault, FaultAxes, GraphVariant,  # noqa: F401
                        LinkFault, ScenarioBatch, StragglerFault,
                        bandwidth_grid, base_batch, cartesian_grid,
                        collective_variants, fault_axes, latency_grid,
                        recovery_cost_us, sample_grid, topology_variants)
