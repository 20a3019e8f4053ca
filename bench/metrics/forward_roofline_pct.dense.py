"""``forward_roofline_pct.study`` where the dense Pallas forward ran: the
bytes the traced requests' kinds count (``work.forward_bytes``, at the
contract's width, 4 bytes for float32) at the chip's peak HBM bandwidth,
over the device time of the forward program's modules (``jit_fwd``, which
``devtrace.FORWARD_MODULES`` matches).  None where no span names the
``pallas`` view."""

import registry
import views

_study = registry.module("metrics", "forward_roofline_pct.study").read


def read(ctx):
    if not views.dispatches(ctx["spans"], "pallas"):
        return None
    return _study(ctx)
