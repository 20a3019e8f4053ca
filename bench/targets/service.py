"""Service target: one ``repro.launch.analysis.AnalysisService`` holding
every graph of the configuration as a variant; a request is its JSON."""

from __future__ import annotations

import json


def build(prog, policy, mix):
    from repro.launch.analysis import AnalysisService
    svc = AnalysisService(policy=policy)
    for n in prog.names:
        svc.register_graph(n, prog.graphs[n], prog.params)
    prog.on_axis = list(prog.names)
    return svc


def call(prog, req):
    """(answer payload or error, ok) of ``handle_json``."""
    reply = json.loads(prog.entry.handle_json(json.dumps(req["json"])))
    return (reply["payload"] if reply["ok"] else reply["error"]), reply["ok"]
