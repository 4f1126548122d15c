"""BENCHMARK.json and the files it names: every cell, mix, configuration and
metric is found by name, and every cell reads at least one metric of each
kind."""

import json
import re
from pathlib import Path

import pytest

from bench import harness, loadgen

ROOT = Path(__file__).resolve().parents[2]
BJ = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BJ["workloads"]]


def test_names_and_files():
    for c in BJ["configs"]:
        assert NAME.match(c["name"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
    for m in BJ["end_to_end"] + BJ["per_layer"]:
        assert NAME.match(m["name"])
        assert harness.metric_path(m["name"]).exists()
    for w in BJ["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").exists()


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_each_kind(name):
    cell = harness.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
    assert set(cell.limits) == {"max_gap", "mean_gap"}


@pytest.mark.parametrize("name", CELLS)
def test_warm_shapes_cover_the_mix(name):
    """Every width a window of this mix can take has its bucket warmed: any
    chunk in a closed loop, a whole prompt in an open one."""
    from repro.serving.engine import _bucket
    cell = harness.load_cell(name)
    mix, e = cell.traffic, cell.config["engine"]
    shapes = set(harness.warm_shapes(cell.config, mix))
    top = min(loadgen.max_prompt(mix), e["max_prefill_tokens"])
    cap = e["max_prefill_batch"] + e["max_decode_batch"]
    if loadgen.concurrency(mix):
        rows, low = [loadgen.concurrency(mix)], 1
    else:
        rows, low = range(mix["warm_rows"][0], mix["warm_rows"][1] + 1), \
            loadgen.min_prompt(mix)
    for n in rows:
        for w in [1] + list(range(low, top + 1)):
            assert (_bucket(n, cap), _bucket(w, 0)) in shapes
