"""Host time of the forward dispatch, per query or request (summed over
every Engine.run call it makes): the ``stage_ns``, ``dispatch_ns`` and
``readback_ns`` that each sweep.execute span records, that is the span
less its wait for the device.  None where no span carries them.  The
``.service`` metric reads the same."""

PHASES = ("stage_ns", "dispatch_ns", "readback_ns")


def read(ctx):
    done = [e.args for e in ctx["spans"] if e.name == "sweep.execute"
            and e.args and all(k in e.args for k in PHASES)]
    if not done or ctx["answered"] <= 0:
        return None
    return sum(a[k] for a in done for k in PHASES) / 1e6 / ctx["answered"]
