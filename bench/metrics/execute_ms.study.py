"""Self time of sweep.execute (dispatch of the forward program to the read
of its results), per query or request (summed over every Engine.run call
it makes).  The ``.service`` metric reads the same."""

import spans


def read(ctx):
    return spans.self_ms_per(ctx["spans"], ["sweep.execute"], ctx["answered"])
