"""Self time of the dense Pallas forward's sweep.execute spans (``view``
``pallas``), per query: what ``execute_ms.study`` reads, on the dense
float32 path.  None where no span names that view."""

import spans
import views


def read(ctx):
    dense = {id(e) for e in views.dispatches(ctx["spans"], "pallas")}
    if not dense or ctx["answered"] <= 0:
        return None
    ns = sum(n for e, n in spans.self_ns(ctx["spans"]) if id(e) in dense)
    return ns / 1e6 / ctx["answered"]
