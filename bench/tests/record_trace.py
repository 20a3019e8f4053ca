#!/usr/bin/env python3
"""Records the small chip trace that ``test_bench.py`` reduces.

    python3 bench/tests/record_trace.py [OUT_DIR]    # on a machine with a TPU

writes ``chip_trace.xplane.pb`` and ``chip_trace.json`` to OUT_DIR (default
``bench/tests/data``); the test reads the trace gzipped
(``gzip -9 -n chip_trace.xplane.pb``).

Two 8-scenario queries of one LULESH cycle on 2 x 2 x 2 ranks of 2^3
elements, under the profiler and under the harness's annotations, so the
file stays small; then
``devtrace.reduce_file`` of it, written beside it as the expected numbers.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import build  # noqa: E402
import devtrace  # noqa: E402


def main(argv) -> int:
    import jax
    import numpy as np
    from repro.core.loggps import cluster_params
    from repro.sweep import Engine, ExecPolicy, latency_grid
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    p = cluster_params(L_us=3.0, o_us=5.0)
    g, _ = build.build(p, {"skeleton": "lulesh", "args": {
        "tp": 2, "s": 2, "cycles": 1, "cycle_us": 8.0,
        "phase_share": [0.4, 0.1, 0.25, 0.25]}})
    eng = Engine(g, params=p, policy=ExecPolicy(cache=None))
    grids = [latency_grid(p, np.linspace(0, 100, 8) + k) for k in range(3)]
    eng.run(grids[0])                                   # compile
    dest = Path(argv[0]) if argv else HERE / "data"
    dest.mkdir(parents=True, exist_ok=True)
    tmp = dest / "_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("bench.window"):
        for grid in grids[1:]:
            with jax.profiler.TraceAnnotation("bench.grid"):
                eng.run(grid)
            time.sleep(0.01)
    jax.profiler.stop_trace()
    out = dest / "chip_trace.xplane.pb"
    shutil.copy(devtrace.find(tmp), out)
    shutil.rmtree(tmp)
    got = devtrace.reduce_file(out)
    (dest / "chip_trace.json").write_text(json.dumps(got, indent=1))
    print(json.dumps(got), out.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
