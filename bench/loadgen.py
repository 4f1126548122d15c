"""The one load generator: a traffic mix is a data file, this reads it.

A mix lives in `bench/traffic/<name>.json`:

    {"loop": "closed", "concurrency": 4,
     "prompt": {"dist": "loguniform", "min": 384, "max": 768},
     "output": {"dist": "uniform", "min": 128, "max": 256},
     "requests": 256}

    {"loop": "open", "rate": 3.2, "preroll_s": 60, "warm_rows": [8, 64],
     "prompt": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                "min": 16, "max": 768},
     "output": {...},
     "requests": 512}

An open loop's arrivals start `preroll_s` before the window opens (the
harness runs them as set-up), so the window measures a server in steady
state. Length distributions: `uniform` and `loguniform` (min, max),
`lognormal` (median, sigma, clipped to min..max). Every request is greedy.

Seeds change the order of the work, not the work: prompt lengths, output
lengths and inter-arrival gaps are drawn once from BASE_SEED, and the run's
seed only permutes them within consecutive blocks and draws the token ids.
A closed loop's blocks are `concurrency` requests, so every seed starts its
clients on the same lengths. An open loop's are single requests: its tails
are set by which requests arrive together, so every seed keeps the same
schedule.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

BASE_SEED = 20240229


@dataclasses.dataclass
class Request:
    due: float            # seconds after the window opens (open loop only)
    prompt: List[int]
    max_new: int


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, size=n).astype(float)
    elif kind == "loguniform":
        x = np.exp(rng.uniform(np.log(spec["min"]), np.log(spec["max"] + 1),
                               size=n))
    elif kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def _blockwise(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """A permutation of range(n) that moves items only within consecutive
    blocks of `block`."""
    return np.concatenate([i + rng.permutation(min(block, n - i))
                           for i in range(0, n, block)])


def min_prompt(mix: dict) -> int:
    return int(mix["prompt"]["min"])


def max_prompt(mix: dict) -> int:
    return int(mix["prompt"]["max"])


def requests(mix: dict, seed: int, vocab: int) -> List[Request]:
    """The run's requests, in order. Closed loops send them as slots free
    up; open loops send request i at `due`."""
    n = int(mix["requests"])
    base = np.random.default_rng(BASE_SEED)
    plens = _lengths(mix["prompt"], n, base)
    olens = _lengths(mix["output"], n, base)
    gaps = (base.exponential(1.0 / mix["rate"], size=n)
            if mix["loop"] == "open" else np.zeros(n))
    rng = np.random.default_rng(int(seed))
    block = concurrency(mix) or 1
    order = _blockwise(rng, n, block)
    due = np.cumsum(gaps[_blockwise(rng, n, block)])
    return [Request(float(due[i]) if mix["loop"] == "open" else 0.0,
                    rng.integers(0, vocab, size=int(plens[j])).tolist(),
                    int(olens[j]))
            for i, j in enumerate(order)]


def concurrency(mix: dict) -> Optional[int]:
    return int(mix["concurrency"]) if mix["loop"] == "closed" else None
