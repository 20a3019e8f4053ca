"""``device_idle_pct.study`` where the dense Pallas forward ran; None where
no span names the ``pallas`` view."""

import registry
import views

_study = registry.module("metrics", "device_idle_pct.study").read


def read(ctx):
    if not views.dispatches(ctx["spans"], "pallas"):
        return None
    return _study(ctx)
