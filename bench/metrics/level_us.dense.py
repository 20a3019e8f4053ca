"""The host's wait for the dense Pallas forward (``view`` ``pallas``) per
level of the graph, in microseconds: summed ``wait_ns`` over summed
``levels`` of its sweep.execute spans, as ``level_us.study`` reads them.
None where no span names that view."""

import views


def read(ctx):
    done = [e.args for e in views.dispatches(ctx["spans"], "pallas")
            if "wait_ns" in e.args and "levels" in e.args]
    levels = sum(a["levels"] for a in done)
    if levels <= 0:
        return None
    return sum(a["wait_ns"] for a in done) / levels / 1e3
