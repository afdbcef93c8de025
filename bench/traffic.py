"""The one traffic generator: every mix is a JSON file of parameters under
``bench/traffic/``, read here.

Keys of a mix:

- ``client``: which client in ``bench/clients/`` serves it (``ingest``,
  ``export``);
- ``in_flight``: requests outstanding in the closed loop; the next is sent
  when one completes;
- ``sizes``: ``[[side, weight], ...]`` — square level-0 sides in pixels and
  how many of each a deck of slides holds;
- ``pool``: distinct slides rendered per side; later slides re-land them
  under fresh keys;
- ``drain_s``: how long work still in flight at the window's end is
  followed before it counts as failed;
- ``check``: how much of the window's output the comparison samples;
- ``trace_s`` (optional): with ``--trace 1``, trace only the window's
  first ``trace_s`` seconds (a trace that holds one event per step of a
  long device loop is slow to collect and read).

Every seed gets the same multiset of sizes, in a seeded order, so that
seeds change which pixels and which order, not how much work a window
holds.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    """The mix ``bench/traffic/<name>.json``."""
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any integer seed."""
    return np.random.default_rng([seed % (1 << 63), stream])


def deck(mix: dict) -> list[int]:
    """One deck of level-0 sides, in the weights the mix gives."""
    return [int(side) for side, n in mix["sizes"] for _ in range(int(n))]


def sides(mix: dict, seed: int):
    """Endless closed-loop sequence of sides: decks shuffled by the seed."""
    g = rng(seed, 1)
    while True:
        d = deck(mix)
        g.shuffle(d)
        yield from d


def scanner_seeds(mix: dict, seed: int) -> dict[int, list[float]]:
    """Per side, the scanner seeds of the ``pool`` distinct slides."""
    g = rng(seed, 3)
    return {int(side): [float(x) for x in g.uniform(0, 1000, int(mix["pool"]))]
            for side, _ in mix["sizes"]}
