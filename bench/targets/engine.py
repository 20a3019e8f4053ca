"""Study target: one ``repro.sweep.Engine`` over the configuration's graph,
or, where the mix sets ``pack``, over all its graphs packed on the graph
axis.  Study kinds call it themselves (``kinds/grid.py``)."""

from __future__ import annotations


def build(prog, policy, mix):
    from repro.sweep import Engine
    if mix.get("pack"):
        prog.on_axis = list(prog.names)
        return Engine([(prog.graphs[n], prog.params) for n in prog.names],
                      names=prog.names, policy=policy)
    if len(prog.names) != 1:
        raise ValueError("an unpacked study mix needs a configuration of "
                         "one graph")
    prog.on_axis = list(prog.names)
    return Engine(prog.graphs[prog.names[0]], params=prog.params,
                  policy=policy)
