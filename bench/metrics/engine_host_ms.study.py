"""Host time of the engine around the forward program, per query or
request (summed over every Engine.run call it makes): self time of
sweep.canonicalize, cache_lookup, cost_patch, stage and lam_backtrace.
The ``.service`` metric reads the same."""

import spans

NAMES = ["sweep.canonicalize", "sweep.cache_lookup", "sweep.cost_patch",
         "sweep.stage", "sweep.lam_backtrace"]


def read(ctx):
    return spans.self_ms_per(ctx["spans"], NAMES, ctx["answered"])
