"""bench/work.py: the bytes a pattern moves, from the pattern alone."""
import json
import os

import numpy as np
import pytest

from bench import work

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _pattern(cfg, name):
    return next(p for p in _config(cfg)["patterns"] if p["name"] == name)


def test_lulesh_g0_reuse_by_hand():
    # index 0..15 at delta 1: each element is read 16 times over 2^20 ops
    p = _pattern("lulesh", "LULESH-G0")
    assert work.distinct(work.parse_index(p["pattern"]), 1, 2**20) \
        == 2**20 + 15
    assert work.useful_bytes(p) == 2**24 * 4
    assert work.least_bytes(p) == (2**20 + 15 + 2**24) * 4


def test_lulesh_g7_no_reuse_by_hand():
    # index 0..15 at delta 41: no element is read twice
    p = _pattern("lulesh", "LULESH-G7")
    assert work.distinct(work.parse_index(p["pattern"]), 41, 2**20) == 2**24
    assert work.useful_bytes(p) == 2**24 * 4
    assert work.least_bytes(p) == 2 * 2**24 * 4


def test_scatter_least_bytes_by_hand():
    # LULESH-S3 (delta 0): 2^24 lanes land in 16 rows; 16 values, 16 rows
    p = _pattern("lulesh", "LULESH-S3")
    assert work.least_bytes(p) == 2 * 16 * 4
    # PENNANT-G4: [0,0,0,0,1,1,1,1,...] at delta 4 reads each element 4x
    g4 = _pattern("pennant", "PENNANT-G4")
    assert work.distinct(work.parse_index(g4["pattern"]), 4, 2**21) == 2**23


@pytest.mark.parametrize("cfg", ["lulesh", "pennant"])
@pytest.mark.parametrize("count", [1, 3, 37])
def test_distinct_matches_brute_force(cfg, count):
    for p in _config(cfg)["patterns"]:
        index = work.parse_index(p["pattern"])
        addr = (np.arange(count)[:, None] * p["delta"]
                + np.asarray(index)[None, :])
        assert work.distinct(index, p["delta"], count) \
            == np.unique(addr).size, p["name"]
        assert work.footprint(index, p["delta"], count) == addr.max() + 1


def test_parse_index_refuses_generators():
    assert work.parse_index("CUSTOM:3,1,2") == [3, 1, 2]
    assert work.parse_index([0, 4]) == [0, 4]
    with pytest.raises(ValueError):
        work.parse_index("UNIFORM:8:1")
