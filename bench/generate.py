"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into the requests of a run.

A mix names its ``target``, the entry the requests go to
(``bench/targets/<target>.py``: ``engine`` for study queries into
``repro.sweep.Engine.run``, where ``pack`` puts every graph on the graph
axis; ``service`` for JSON requests to ``AnalysisService.handle_json``), and
a ``cycle`` of request specs sent one after another, round and round.  Each
spec names its ``kind``, a file ``bench/kinds/<kind>.py`` that makes the
request from the spec and the seed (``make``), may send it itself
(``call``; else the target's ``call`` does), counts its answers
(``cells``, default 1) and the bytes its forward moves (``forward_bytes``),
and checks its answers against the reference (``check``, with the limits
of the numbers it compares in ``LIMITS``).  A new kind, mix or cell is new
files and entries; nothing here changes.

Sizes and variants walk fixed menus (``points``; ``variant: rotate`` walks
the configuration's graphs) in the same order for every seed, so every seed
asks for the same work; values (offsets, budgets, faults) are drawn from
the seed.  ``repeat`` (default 0) is the share of requests that resend an
earlier request of the run.  Set-up sends one request of every shape the
cycle uses (every variant of a ``rotate`` or ``all`` spec, every size of
its menu), or the specs a ``warmup`` list names.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import registry

MIX_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    registry.module("targets", mix["target"])
    for spec in mix["cycle"] + mix.get("warmup", []):
        registry.module("kinds", spec["kind"])
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one seed (any integer, also past 2**32)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


class Generator:
    """Requests of one run.  ``calc`` maps each graph name to its
    ``[row, rank]`` array of the program graph's compute vertex ids."""

    def __init__(self, mix: dict, variants: list, calc: dict, seed: int):
        self.mix = mix
        self.variants = list(variants)
        self.calc = calc
        self.seed = seed
        self._rng = rng_for(seed, 0)
        self._n = 0
        self._count: dict = {}
        self._sent: list = []

    # -- helpers for the kinds -----------------------------------------------
    @staticmethod
    def pick(menu: list, k: int):
        return menu[k % len(menu)]

    def variant(self, spec: dict, k: int, variant=None):
        if variant is not None:
            return variant
        v = spec.get("variant")
        return self.pick(self.variants, k) if v == "rotate" else v

    @staticmethod
    def deltas(spec: dict, n: int, rng) -> list:
        off = rng.uniform(0.0, float(spec["offset_max"]))
        return (np.linspace(*spec["range"], int(n)) + off).tolist()

    def _make(self, spec, k, rng, variant=None, points=None) -> dict:
        return registry.module("kinds", spec["kind"]).make(
            spec, k, rng, self, variant=variant, points=points)

    # -- the run's streams ---------------------------------------------------
    def next(self) -> dict:
        """The window's next request."""
        share = float(self.mix.get("repeat", 0.0))
        if share and self._sent and self._rng.random() < share:
            return self._sent[int(self._rng.integers(len(self._sent)))]
        cycle = self.mix["cycle"]
        spec = cycle[self._n % len(cycle)]
        self._n += 1
        k = self._count.get(spec["kind"], 0)      # this kind's k-th request
        self._count[spec["kind"]] = k + 1
        req = self._make(spec, k, self._rng)
        if share:
            self._sent.append(req)
        return req

    def warmup(self) -> list:
        """Set-up's requests: one per program shape the window uses."""
        rng = rng_for(self.seed, 1)
        out = []
        for spec in self.mix.get("warmup", self.mix["cycle"]):
            v = spec.get("variant")
            names = self.variants if v in ("all", "rotate") else [v]
            for name in names:
                for n in spec.get("points", [None]):
                    out.append(self._make(spec, 0, rng, variant=name,
                                          points=n))
        return out
