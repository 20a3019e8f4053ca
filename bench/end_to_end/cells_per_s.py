"""Answers per second: every (graph x cost block x scenario) cell the
window's queries answered, over all the time of the window (from its start
to the end of the last query started inside it)."""


def read(ctx):
    if ctx["elapsed_s"] <= 0 or not ctx["records"]:
        return None
    return sum(r["cells"] for r in ctx["records"]) / ctx["elapsed_s"]
