"""PENNANT-G8 to G15, Table 5's sparse gathers (``appdb.PENNANT_GATHERS[8:]``),
configuration ``pennant_sparse`` and cell ``pennant.sparse.xla``:
spatterd serves each at the largest count whose footprint is at most
``MAX_PATTERN_LANES`` (2^28 elements), and, driven by the harness on the
CPU with every footprint cut to 2^22 elements, returns the plain
reference's digests bit for bit on every backend."""
import dataclasses
import os

import pytest

from bench import reference, run
from repro.core import appdb
from repro.core.pattern import Pattern
from repro.core.plan import SuitePlan
from repro.serve.schema import MAX_PATTERN_LANES, SuiteRequest

SEED = 3_000_000_019          # larger than 32 signed bits hold
SPARSE = appdb.PENNANT_GATHERS[8:]
NAMES = [f"PENNANT-G{k}" for k in range(8, 16)]
REQUEST = {"backend": "xla", "mode": "store", "runs": 10, "digest": True}


def largest_count(p: Pattern, limit: int) -> int:
    """The largest count whose footprint is at most ``limit``."""
    return (limit - p.span) // p.delta + 1


def at_footprint(limit: int) -> list[Pattern]:
    return [dataclasses.replace(p, count=largest_count(p, limit))
            for p in SPARSE]


def test_g8_to_g15_are_the_sparse_gathers():
    assert [p.name for p in SPARSE] == NAMES
    assert all(p.kind == "gather" for p in SPARSE)
    assert [p.index_len for p in SPARSE] == [15] + [16] * 7
    assert min(p.delta for p in SPARSE) == 129_608


def _config() -> dict:
    return run.load_json(os.path.join(run.BENCH, "configs",
                                      "pennant_sparse.json"))


def test_config_holds_table5_verbatim():
    pats = [Pattern.from_json(d) for d in _config()["patterns"]]
    assert [(p.name, p.kind, p.index, p.delta) for p in pats] \
        == [(p.name, p.kind, p.index, p.delta) for p in SPARSE]


def test_config_counts_are_the_largest_under_2_28():
    cfg = _config()
    pats = [Pattern.from_json(d) for d in cfg["patterns"]]
    assert [p.count for p in pats] == [p.count for p in
                                       at_footprint(MAX_PATTERN_LANES)]
    for p in pats:
        assert p.footprint() <= MAX_PATTERN_LANES < p.footprint() + p.delta
        assert cfg["sizes"][p.name] == {
            "useful_elements": p.useful_elements(),
            "footprint": p.footprint()}
    assert set(cfg["sizes"]) == set(NAMES)


def test_reduced_is_the_eight_counts():
    spec = run.load_json(run.SPEC)
    entry = next(c for c in spec["configs"] if c["name"] == "pennant_sparse")
    want = [f"{n}.count" for n in NAMES]
    assert entry["reduced"] == want
    assert _config()["reduced"] == want
    assert entry["file"] == "bench/configs/pennant_sparse.json"


def test_cell_loads_and_cycles_g8_to_g15():
    cell = run.load_cell("pennant.sparse.xla")
    assert cell.chips == 1
    assert [p["name"] for p in cell.cycle()] == NAMES
    assert cell.options == REQUEST
    assert cell.row_width == 1
    assert "table_rows_per_lane" in [m["name"] for m in cell.per_layer]


def test_largest_counts_spatterd_serves():
    pats = at_footprint(MAX_PATTERN_LANES)
    assert [p.count for p in pats] == [2072, 691, 691, 691, 518, 518,
                                       259, 143]
    for p in pats:
        assert p.footprint() <= MAX_PATTERN_LANES < p.footprint() + p.delta
        SuiteRequest(patterns=(p.to_json(),)).build_patterns()
        one_more = dataclasses.replace(p, count=p.count + 1)
        with pytest.raises(ValueError, match="too large to serve"):
            SuiteRequest(patterns=(one_more.to_json(),)).build_patterns()


# -- spatterd against the reference, footprints cut to 2^22 -----------------

SMALL = 2**22
SMALL_COUNTS = [33, 11, 11, 11, 9, 9, 5, 3]


def small_cell(backend: str) -> run.Cell:
    """PENNANT-G8..G15 at footprints of at most 2^22, one request each on
    ``backend``, as the harness drives a cell."""
    return run.Cell(
        name="pennant.sparse.cpu", chips=1,
        config={"patterns": [p.to_json() for p in at_footprint(SMALL)]},
        traffic={"select": {"kernel": "gather"},
                 "request": dict(REQUEST, backend=backend)},
        end_to_end=[], per_layer=[])


@pytest.fixture(scope="module", params=["xla", "pallas", "scalar"])
def served(request):
    """One cycle of the cut patterns through an in-process spatterd on
    ``backend``: (cell, requests)."""
    cell = small_cell(request.param)
    daemon, client = run.start_daemon()
    try:
        opts = cell.options
        for p in cell.cycle():
            client.warm([p], backend=opts["backend"], mode=opts["mode"],
                        runs=opts["runs"])
        reqs, _ = run.drive(client, cell.cycle(), opts, SEED, 0.0)
    finally:
        daemon.stop()
    return cell, reqs


def test_small_counts_keep_three_ops():
    pats = at_footprint(SMALL)
    assert [p.count for p in pats] == SMALL_COUNTS
    assert all(p.footprint() <= SMALL for p in pats)
    assert [p["name"] for p in small_cell("xla").cycle()] == NAMES


def test_served_digests_equal_the_reference(served):
    cell, reqs = served
    assert [r.pattern["name"] for r in reqs] == NAMES
    assert all(r.ok for r in reqs), [r.error for r in reqs]
    checks = run.check(reqs, SEED, cell.row_width)
    assert checks["digest_mismatches"]["value"] == 0, checks
    assert run.passes(checks)
    # the bfloat16 reference fails on the same requests
    bf16 = {r.pattern["name"]: reference.digest(r.pattern, SEED,
                                                cell.row_width, "bfloat16")
            for r in reqs}
    control = run.check(reqs, SEED, cell.row_width, expected=bf16)
    assert control["digest_mismatches"]["value"] == len(reqs)
    assert not run.passes(control)


def test_g8_fifteen_lanes_padded_and_trimmed(served):
    """G8's index buffer has 15 offsets: 33 ops are 495 lanes, launched
    in a 512-lane bucket; the served output is the 495 lanes alone."""
    _, reqs = served
    g8 = next(r for r in reqs if r.pattern["name"] == "PENNANT-G8")
    pat = Pattern.from_json(g8.pattern)
    assert pat.index_len == 15 and pat.count * pat.index_len == 495
    assert SuitePlan.build([pat]).buckets[0].spec.idx_len == 512
    out = reference.output(g8.pattern, SEED)
    assert out.shape == (495, 1)
    assert g8.digest == reference.sha256(out)
