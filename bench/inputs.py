"""The one general generator: a cell's inputs from its configuration, its
traffic mix and the seed.

A configuration names a structure family (``bench/families/<family>.py``),
its sizes, the seed of its structures and its value distribution. A
traffic mix says how many distinct structures and value sets the request
stream cycles through. Structures come from the configuration's
``structure_seed``, so every run of a cell multiplies the same structures
and compiles the same shapes (the persistent compile cache then serves
every run after a checkout's first); values come from the run's seed.
Everything is drawn during set-up, so the timed window draws nothing; the
same seed gives the same inputs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent

# independent random streams per purpose, so adding a value set never
# moves a structure
STRUCTURE_STREAM, VALUES_STREAM, SAMPLE_STREAM = 1, 2, 3


def rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    """A generator keyed on the seed (any integer) and a stream id."""
    return np.random.default_rng([stream, int(seed) % (1 << 64), *more])


def load_module(kind: str, name: str, base: Path = BENCH):
    """Import ``<base>/<kind>/<name>.py`` by path: families, semirings and
    metric readers are found by the name a configuration or
    ``BENCHMARK.json`` gives them."""
    path = Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Structure:
    indptr: np.ndarray    # (n + 1,) int64
    indices: np.ndarray   # (nnz,) int64, sorted within each column
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


@dataclasses.dataclass
class Inputs:
    structures: List[Structure]
    values: List[List[np.ndarray]]   # values[s][v]: float32, one per entry

    def request(self, i: int) -> Tuple[int, int]:
        """(structure, value set) of request ``i``, counting the set-up's
        warm-up requests. Consecutive requests differ in whatever the mix
        varies."""
        return i % len(self.structures), i % len(self.values[0])


def draw_values(cfg: dict, st: Structure, g: np.random.Generator
                ) -> np.ndarray:
    """One value set for ``st`` as the configuration's ``values`` says."""
    spec = cfg["values"]
    if spec["dist"] == "normal":
        vals = g.standard_normal(st.nnz)
    elif spec["dist"] == "uniform":
        vals = g.uniform(spec["low"], spec["high"], st.nnz)
    else:
        raise ValueError(f"unknown value distribution {spec['dist']!r}")
    vals = vals.astype(np.float32)
    if "diagonal" in spec:
        cols = np.repeat(np.arange(st.shape[1]), np.diff(st.indptr))
        vals[st.indices == cols] = np.float32(spec["diagonal"])
    return vals


def generate(cfg: dict, traffic: dict, seed: int,
             base: Path = BENCH) -> Inputs:
    family = load_module("families", cfg["family"], base)
    structures = []
    for s in range(int(traffic["structures"])):
        indptr, indices, shape = family.structure(
            cfg, rng(cfg["structure_seed"], STRUCTURE_STREAM, s))
        structures.append(Structure(indptr, indices, shape))
    values = [[draw_values(cfg, st, rng(seed, VALUES_STREAM, s, v))
               for v in range(int(traffic["value_sets"]))]
              for s, st in enumerate(structures)]
    return Inputs(structures, values)
