"""Finds the benchmark's parts by name: ``bench/<part>/<name>.py``.

Configurations name their graphs' skeletons (``skeletons/``); mixes name
their target (``targets/``) and request kinds (``kinds/``); cells name
their metrics (``metrics/``, ``end_to_end/``).  A later change adds a file
there and an entry in BENCHMARK.json, and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parent
_LOADED: dict = {}


def module(part: str, name: str):
    key = (part, name)
    if key not in _LOADED:
        path = BENCH / part / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {part} {name!r} at {path}")
        spec = importlib.util.spec_from_file_location(
            f"_bench_{part}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]
