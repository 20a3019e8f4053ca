"""Self time of the analysis service's request span (``analysis.<kind>``)
per request: its time minus what the engine's spans inside it cover."""

import spans


def read(ctx):
    return spans.self_ms_per(ctx["spans"], ["analysis."], ctx["answered"])
