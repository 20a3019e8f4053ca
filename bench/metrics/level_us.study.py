"""The host's wait for the forward's outputs per level of the graph, in
microseconds: the ``wait_ns`` over the ``levels`` (the graph's own level
count, not the loop's bucketed trip count) that the sweep.execute spans
record, each summed over the spans.  The wait is the device's work plus
any runtime stall before ``block_until_ready`` returns.  None where no
span carries them.  The ``.service`` metric reads the same."""


def read(ctx):
    done = [e.args for e in ctx["spans"] if e.name == "sweep.execute"
            and e.args and "wait_ns" in e.args and "levels" in e.args]
    levels = sum(a["levels"] for a in done)
    if levels <= 0:
        return None
    return sum(a["wait_ns"] for a in done) / levels / 1e3
