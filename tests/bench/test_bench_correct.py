"""The check that decides ``correct``: a sound run passes, and the
control and each fault the cells can have fail it.

Every run here drives the whole harness (daemon, warm-up, window, check)
on the CPU at a small count, with the look for a chip skipped; faults are
planted in the program's batched gather/scatter before its executables
are traced.
"""
import os

import jax.numpy as jnp
import pytest

from bench import reference, run
from repro.core import backends as B

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 3_000_000_019          # larger than 32 signed bits hold


def tiny_cell(name: str, count: int = 64) -> run.Cell:
    cell = run.load_cell(name)
    cell.config = dict(cell.config, patterns=[
        dict(p, count=count) for p in cell.config["patterns"]])
    return cell


def serve(cell, tmp_path, seed=SEED):
    return run.run(cell, seed, 0.0, False,
                   trace_dir=str(tmp_path / "trace"), device=CPU)


@pytest.mark.parametrize("name", ["lulesh.gather.pallas",
                                  "pennant.gather.xla",
                                  "lulesh.scatter.xla"])
def test_sound_run_is_correct(name, tmp_path):
    cell = tiny_cell(name)
    doc = serve(cell, tmp_path)
    assert doc["correct"], doc["checks"]
    assert doc["attempted"] == len(cell.cycle()) and doc["failed"] == 0
    assert list(doc)[-1] == "checks"
    m = doc["metrics"]
    assert set(m) == {"served_gbs", "setup_s"}
    assert m["served_gbs"]["value"] > 0 and m["setup_s"]["value"] > 0


@pytest.mark.parametrize("name", ["lulesh.gather.pallas",
                                  "pennant.gather.xla",
                                  "lulesh.scatter.xla"])
def test_bfloat16_control_fails(name):
    cell = tiny_cell(name)
    rw = cell.row_width
    control = [run.Request(p, 10, 0.0, digest=reference.digest(
        p, SEED, rw, "bfloat16")) for p in cell.cycle()]
    sound = [run.Request(p, 10, 0.0, digest=reference.digest(p, SEED, rw))
             for p in cell.cycle()]
    assert run.passes(run.check(sound, SEED, rw))
    checks = run.check(control, SEED, rw)
    assert checks["digest_mismatches"]["value"] == len(control)
    assert not run.passes(checks)


def _gather_fault(kind):
    orig = B.gather_batched

    def broken(src, idx, **kw):
        out = orig(src, idx, **kw)
        if kind == "unchanged":           # the output is never written
            return jnp.zeros_like(out)
        if kind == "half":                # half the lanes are left out
            return out.at[:, out.shape[1] // 2:].set(0)
        return out.at[0, 0].add(1.0)      # one answer altered
    return broken


def _scatter_fault(kind):
    orig = B.scatter_batched

    def broken(dst, idx, vals, **kw):
        if kind == "unchanged":           # the table comes back as it went in
            return dst
        if kind == "half":                # half the lanes are left out
            keep = kw.pop("keep")
            n = idx.shape[1] // 2
            return orig(dst, idx[:, :n], vals[:, :n], keep=keep[:, :n], **kw)
        return orig(dst, idx, vals.at[0, 0].add(1.0), **kw)
    return broken


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", ["pennant.gather.xla",
                                  "lulesh.scatter.xla"])
def test_planted_fault_is_not_correct(name, fault, tmp_path, monkeypatch):
    cell = tiny_cell(name)
    if cell.traffic["select"]["kernel"] == "gather":
        monkeypatch.setattr(B, "gather_batched", _gather_fault(fault))
    else:
        monkeypatch.setattr(B, "scatter_batched", _scatter_fault(fault))
    doc = serve(cell, tmp_path)
    assert not doc["correct"]
    assert doc["checks"]["digest_mismatches"]["value"] > 0


def test_traced_run_reports_breakdown(tmp_path):
    cell = tiny_cell("pennant.gather.xla")
    doc = run.run(cell, SEED, 0.0, True, trace_dir=str(tmp_path / "trace"),
                  device=CPU)
    assert doc["correct"]
    dev = doc["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert doc["breakdown"]["device_ops"] and doc["breakdown"]["idle_gaps"]
    assert len(doc["breakdown"]["device_ops"]) <= 10
    m = doc["metrics"]
    assert set(m) <= {x["name"] for x in cell.per_layer}
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["device_idle_pct"]["value"] < 100
    assert m["http_overhead_ms"]["value"] > 0


class _Clock:
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


class _Client:
    """Answers every request after one second of a fake clock."""

    def __init__(self, clock):
        self.clock = clock

    def run_suite(self, pats, seed, **options):
        self.clock.t += 1.0
        return {"elapsed_s": 0.9, "cache": {"misses": 0},
                "stats": {"table": [{"digest": "d"} for _ in pats]}}


@pytest.mark.parametrize("seconds,cycles", [(0, 1), (10, 3), (11, 4),
                                            (14, 5)])
def test_window_is_whole_cycles_nearest_seconds(seconds, cycles,
                                                monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(run, "time", clock)
    cycle = tiny_cell("lulesh.scatter.xla").cycle()[:3]    # 3 s a cycle
    reqs, window_s = run.drive(_Client(clock), cycle, {"runs": 10}, SEED,
                               seconds)
    assert len(reqs) == 3 * cycles and window_s == 3.0 * cycles
    assert [r.pattern["name"] for r in reqs[:3]] == [
        p["name"] for p in cycle]
    assert all(r.ok and r.digest == "d" for r in reqs)


def test_trace_must_match_the_requests():
    from bench.reduce import REQUEST, Event, Trace
    cycle = tiny_cell("pennant.gather.xla").cycle()[:2]
    reqs = [run.Request(p, 10, 1.0, digest="d") for p in cycle]
    host = [Event(f"{REQUEST} {p['name']}", k * 1e9, (k + 1) * 1e9)
            for k, p in enumerate(cycle)]
    mods = [Event("jit_fn(3)", k * 8e7, k * 8e7 + 5e7) for k in range(11)]
    trace = Trace(ops=[], modules=mods, host=host, devices=[0])
    with pytest.raises(ValueError, match="no execution"):
        run.count_executions(reqs, trace)     # the second ran nothing
    trace.modules += [Event("jit_fn(4)", 1.5e9, 1.6e9)]
    run.count_executions(reqs, trace)
    assert [r.executions for r in reqs] == [11, 1]
    trace.host = host[:1]
    with pytest.raises(ValueError, match="do not match"):
        run.count_executions(reqs, trace)
    trace.modules = []                        # a CPU trace: left unset
    reqs = [run.Request(p, 10, 1.0, digest="d") for p in cycle]
    run.count_executions(reqs, trace)
    assert all(r.executions is None for r in reqs)
