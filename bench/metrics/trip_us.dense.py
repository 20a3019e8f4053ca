"""The host's wait for the dense Pallas forward (``view`` ``pallas``) per
trip of its level loop, in microseconds: summed ``wait_ns`` over summed
``trips`` (the padded level count the loop runs, 1,024 on ``lulesh_64r``)
of its sweep.execute spans.  A padded trip costs what a real one does, so
this is the per-trip cost that the kernel's block sizes move.  None where
no span names that view."""

import views


def read(ctx):
    done = [e.args for e in views.dispatches(ctx["spans"], "pallas")
            if "wait_ns" in e.args]
    trips = sum(a["trips"] for a in done)
    if trips <= 0:
        return None
    return sum(a["wait_ns"] for a in done) / trips / 1e3
