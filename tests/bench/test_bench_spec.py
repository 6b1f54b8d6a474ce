"""BENCHMARK.json and the files it names: they load, they agree, and
each configuration's counts give the sizes it states."""
import json
import os
import subprocess
import sys

import pytest

from bench import run, work

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_loads(spec):
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        cycle = cell.cycle()
        assert cycle and all(cycle)
        kinds = {p["kernel"] for p in cycle}
        assert kinds == {cell.traffic["select"]["kernel"]}
        assert cell.end_to_end and cell.per_layer
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2


def test_every_metric_has_a_reader(spec):
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.reader(m["name"])), m["name"]


@pytest.mark.parametrize("name", ["lulesh", "pennant"])
def test_config_counts_give_stated_sizes(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert {p["name"] for p in cfg["patterns"]} == set(cfg["sizes"])
    for p in cfg["patterns"]:
        index = work.parse_index(p["pattern"])
        stated = cfg["sizes"][p["name"]]
        assert len(index) * p["count"] == stated["useful_elements"]
        assert work.footprint(index, p["delta"], p["count"]) \
            == stated["footprint"]
        # spatterd serves a pattern whose footprint is at most 2^28
        # elements (serve/schema.py, MAX_PATTERN_LANES)
        assert stated["footprint"] <= 2**28


def test_lulesh_and_pennant_sizes():
    cfg = {n: run.load_json(os.path.join(ROOT, "bench", "configs",
                                         n + ".json"))
           for n in ("lulesh", "pennant")}
    sizes = cfg["lulesh"]["sizes"]
    assert all(s["useful_elements"] == 2**24 for s in sizes.values())
    assert sizes["LULESH-G7"]["footprint"] == 42991591
    assert set(cfg["lulesh"]["reduced"]) == {p + ".count" for p in sizes}
    pen = cfg["pennant"]["sizes"]
    assert pen["PENNANT-G6"]["useful_elements"] == 559240 * 16
    assert pen["PENNANT-G7"]["useful_elements"] == 556920 * 16
    # the largest counts whose footprint stays within 2^28
    for g, delta in (("PENNANT-G6", 480), ("PENNANT-G7", 482)):
        assert pen[g]["footprint"] <= 2**28 < pen[g]["footprint"] + delta
    assert set(cfg["pennant"]["reduced"]) == {"PENNANT-G6.count",
                                              "PENNANT-G7.count"}


def test_peaks_table():
    peaks = run.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")


def test_no_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lulesh.gather.pallas",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_traffic_generator_selects_and_groups(tmp_path):
    cell = run.load_cell("lulesh.gather.pallas")
    assert [p["name"] for p in cell.cycle()] == [
        f"LULESH-G{k}" for k in range(8)]
    cell.traffic = dict(cell.traffic, select={"kernel": "scatter",
                                              "delta": 8})
    assert [p["name"] for p in cell.cycle()] == ["LULESH-S1"]
    # a traffic key the generator does not honour is refused
    for d in ("bench/traffic", "bench/configs"):
        os.makedirs(tmp_path / d)
    for f in ("BENCHMARK.json", "bench/configs/lulesh.json"):
        with open(os.path.join(ROOT, f)) as src, open(tmp_path / f, "w") as dst:
            dst.write(src.read())
    mix = run.load_json(os.path.join(ROOT, "bench/traffic/gather.pallas.json"))
    with open(tmp_path / "bench/traffic/gather.pallas.json", "w") as f:
        json.dump(dict(mix, clients=4), f)
    with pytest.raises(ValueError, match="clients"):
        run.load_cell("lulesh.gather.pallas",
                      str(tmp_path / "BENCHMARK.json"))
