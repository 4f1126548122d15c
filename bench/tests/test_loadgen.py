"""The load generator: deterministic from the seed, lengths in range, and
the same work for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = loadgen.requests(_mix(name), 2 ** 31 + 17, 50257)
    b = loadgen.requests(_mix(name), 2 ** 31 + 17, 50257)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range(name):
    mix = _mix(name)
    reqs = loadgen.requests(mix, 5, 50257)
    assert len(reqs) == mix["requests"]
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert all(0 <= t < 50257 for t in r.prompt[:8])
    if mix["loop"] == "open":
        due = np.array([r.due for r in reqs])
        assert (np.diff(due) >= 0).all() and due[0] >= 0


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_the_same_work(name):
    mix = _mix(name)
    a = loadgen.requests(mix, 1, 50257)
    b = loadgen.requests(mix, 2, 50257)
    block = loadgen.concurrency(mix) or 1
    for i in range(0, len(a), block):
        pa, pb = a[i:i + block], b[i:i + block]
        assert sorted(len(r.prompt) for r in pa) == \
            sorted(len(r.prompt) for r in pb)
        assert sorted(r.max_new for r in pa) == sorted(r.max_new for r in pb)
    if block > 1:
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    if mix["loop"] == "open":
        ga = np.diff([0.0] + [r.due for r in a])
        gb = np.diff([0.0] + [r.due for r in b])
        np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)


def test_lognormal_median():
    mix = {"loop": "open", "rate": 10.0,
           "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.5,
                      "min": 1, "max": 10 ** 6},
           "output": {"dist": "uniform", "min": 7, "max": 7},
           "requests": 4000}
    reqs = loadgen.requests(mix, 3, 1000)
    assert 90 <= np.median([len(r.prompt) for r in reqs]) <= 110
    assert all(r.max_new == 7 for r in reqs)
    due = np.array([r.due for r in reqs])
    assert 350 <= due[-1] <= 450   # 4000 arrivals at 10/s
