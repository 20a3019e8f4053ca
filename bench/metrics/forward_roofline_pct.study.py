"""The forward program's share of its HBM roofline: the time the bytes
that the traced requests' kinds count (``work.forward_bytes``) take at the
chip's peak HBM bandwidth, over the device time of the forward program's
events in the trace.  HBM bounds it: the vector unit's rate is not
published, and float64 is emulated, so there is no compute bound to
compare with."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["forward_s"] > 0 or not ctx["forward_bytes"]:
        return None
    least_s = ctx["forward_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["forward_s"]
