"""LLAMP core: execution graphs + LogGPS + LP = latency tolerance analysis.

Public API:
    graph.GraphBuilder / ExecutionGraph      — Schedgen-style DAGs
    loggps.LogGPS / NetworkModel / NetClass / cluster_params / pod_model
    collectives.allreduce / all_gather / ...  — collective → p2p expansion
    dag.evaluate / tolerance / breakpoints   — exact parametric engine
    lp.build_lp / predict_runtime / tolerance_lp  — Algorithm 1 + HiGHS
    ipm.solve_ipm                            — Mehrotra barrier solver
    simulator.simulate                       — LogGOPSim-analog DES + injector
    sensitivity.analyze / latency_curve / latency_tolerance
    topology / placement / synth / tracer / hlo

Batched scenario sweeps live in the sibling package ``repro.sweep``: an
Engine compiles an ExecutionGraph once into padded per-level tensors
and evaluates thousands of LogGPS parameter points (latency deltas ×
bandwidth scales, plus stamped collective/topology graph variants) in one
jit+vmap max-plus pass, with results identical to ``dag.evaluate``.  The
``sensitivity`` wrappers here dispatch to it automatically for multi-point
queries and fall back to the scalar engine when JAX is unavailable.
"""

from . import (collectives, dag, graph, hlo, ipm, loggps, lp, placement,  # noqa: F401
               sensitivity, simulator, synth, topology)
from .graph import ExecutionGraph, GraphBuilder  # noqa: F401
from .loggps import (LogGPS, NetClass, NetworkModel, cluster_params,  # noqa: F401
                     pod_model, resolve_class)
from .sensitivity import analyze, latency_curve, latency_tolerance  # noqa: F401
