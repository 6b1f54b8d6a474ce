"""kernels/autotune — the deterministic per-geometry tile search (§16).

Three contracts, counter-proven:

  * Determinism: the choice is a pure function of the TileKey — same
    answer after a memo reset, and the same answer in a fresh process
    (no timing, no RNG, no dict-order dependence).
  * Purity of the kernels w.r.t. the tile: EVERY candidate tiling is
    bit-identical on ragged geometries (N < block, D not a lane
    multiple, OOB padding lanes) — the tile choice may change speed,
    never bits, which is why it must not enter ExecKey.
  * Persistence: DiskTier entries carry the tiles their executable
    baked in; a restored entry re-seeds the memo so a warm restart
    never searches (``searched == 0``) and ``disk_hits`` stays exact.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DiskTier, ExecutorCache, SuitePlan, make_pattern
from repro.core.plan import run_plan
from repro.kernels import autotune
from repro.kernels.gather_rows import ops as gops
from repro.kernels.scatter_rows import ops as sops

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _clean_memo():
    autotune.reset()
    yield
    autotune.reset()


def _keys():
    """A small grid of representative geometries, every op covered."""
    out = []
    for op, rows in (("gather", 256), ("gather", 4096), ("scatter", 96)):
        for batch, lanes, width in ((1, 64, 1), (4, 1000, 8), (2, 7, 520)):
            out.append(autotune.TileKey(op=op, batch=batch, lanes=lanes,
                                        rows=rows, width=width,
                                        dtype="float32",
                                        platform="interpret"))
    return out


# ---------------------------------------------------------------------------
# determinism


def test_choice_survives_memo_reset():
    for key in _keys():
        first = autotune.choose(key)
        autotune.reset()
        assert autotune.choose(key) == first


def test_memo_hit_does_not_research():
    key = _keys()[0]
    autotune.choose(key)
    autotune.choose(key)
    s = autotune.stats()
    assert s["searched"] == 1 and s["hits"] == 1


def test_choices_are_powers_of_two():
    for key in _keys():
        c = autotune.choose(key)
        for b in (c.block, c.block_v):
            assert b == 0 or (b & (b - 1)) == 0, (key, c)


_CHILD = """
import json, sys
sys.path.insert(0, %(src)r)
from repro.kernels import autotune
keys = [autotune.TileKey(**k) for k in json.loads(sys.argv[1])]
print(json.dumps(autotune.to_wire({k: autotune.choose(k) for k in keys})))
"""


def test_cross_process_determinism():
    # two fresh interpreters, no shared memo: identical wire dicts,
    # identical to the in-process answer
    payload = json.dumps([vars(k) for k in _keys()])
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _CHILD % {"src": SRC},
                            payload], capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        outs.append(json.loads(r.stdout))
    assert outs[0] == outs[1]
    here = autotune.to_wire({k: autotune.choose(k) for k in _keys()})
    assert here == outs[0]


def test_legacy_mirrors_kernel_defaults():
    # the disabled() path serves what the kernels shipped with — pinned
    # against the _DEFAULT_BLOCK_* constants so neither drifts alone
    assert autotune.LEGACY["gather"].block == gops._DEFAULT_BLOCK
    assert autotune.LEGACY["scatter"].block_v == sops._DEFAULT_BLOCK_V
    assert autotune.LEGACY["scatter"].block == sops._DEFAULT_BLOCK_N


def test_disabled_serves_legacy_without_memo():
    key = _keys()[0]
    with autotune.disabled():
        assert autotune.choose(key) == autotune.LEGACY[key.op]
    assert autotune.stats() == {"searched": 0, "hits": 0, "seeded": 0}
    assert autotune.lookup(key) is None


# ---------------------------------------------------------------------------
# wire format


def test_wire_round_trip_and_seed_priority():
    entries = {k: autotune.choose(k) for k in _keys()}
    wire = autotune.to_wire(entries)
    json.dumps(wire)                       # must be JSON-clean as-is
    autotune.reset()
    assert autotune.seed_wire(wire) == len(entries)
    for k, v in entries.items():
        assert autotune.lookup(k) == v
    # existing memo entries win over a later (conflicting) seed
    key = _keys()[0]
    fake = dict(wire)
    fake[next(iter(fake))] = [1, 0]
    assert autotune.seed_wire(fake) == 0
    assert autotune.lookup(key) == entries[key]


def test_seed_wire_skips_malformed_entries():
    assert autotune.seed_wire(None) == 0
    # a wrong-length choice or an op of an older wire (whose blocks
    # meant something else) is skipped, not seeded
    assert autotune.seed_wire({"not:a:key": [64, 0],
                               "gather:1:64:256:1:float32:interpret":
                                   ["x", 0],
                               "gather:1:64:512:1:float32:interpret":
                                   [0, 8, 512],
                               "gather_vmem:1:64:256:1:float32:interpret":
                                   [1024, 0]}) == 0
    good = {"gather:1:64:256:1:float32:interpret": [1024, 0]}
    assert autotune.seed_wire(good) == 1


# ---------------------------------------------------------------------------
# bit-identity: every candidate tiling computes the same bits


# Each family below is sized so that its candidate tiles legalize to
# different grids (blocks are whole 1024-index units capped at the padded
# index count, so at tiny sizes every candidate is one kernel);
# test_candidates_legalize_apart pins that.
GATHER_BLOCKS = (1024, 2048, 4096)
SCATTER_BLOCKS = ((8, 1024), (16, 2048), (32, 4096))


def _ragged_gather(rng, n=700, v=19, d=7, batch=2):
    table = rng.normal(size=(batch, v, d)).astype(np.float32)
    idx = rng.integers(0, v, size=(batch, n)).astype(np.int32)
    return table, idx


def _fetches(n, d):
    """Lines or elements the gather kernel fetches for n rows of width d."""
    return n * (d if d < 128 else -(-d // 128))


def _dma_rows(d):
    # above 4096 fetches, and ragged against every block
    return -(-4200 // _fetches(1, d))


def _scatter_geometry(v, n, d):
    """(output lines or rows, index elements) the scatter kernel sees."""
    return (-(-v * d // 128), n * d) if d < 128 else (v, n)


STORE = dict(batch=2, n=500, v=600, d=7)         # 33 lines, 3500 elements
ADD = {5: dict(batch=2, n=1000, v=900),          # 36 lines, 5000 elements
       130: dict(batch=2, n=4500, v=64)}         # 64 rows, 4500 rows in


@pytest.mark.parametrize("family", ["gather_vmem", "gather_dma_6",
                                    "gather_dma_128", "gather_dma_200",
                                    "scatter_store", "scatter_add_5",
                                    "scatter_add_130"])
def test_candidates_legalize_apart(family):
    if family.startswith("gather"):
        d = 7 if family == "gather_vmem" else int(family.rsplit("_", 1)[1])
        n = 700 if family == "gather_vmem" else _dma_rows(d)
        grids = [gops._legal_block(b, _fetches(n, d)) for b in GATHER_BLOCKS]
        assert len(set(grids)) == len(GATHER_BLOCKS), grids
        return
    if family == "scatter_store":
        g = STORE
        pairs = [(bv, bn) for bv, _ in SCATTER_BLOCKS
                 for _, bn in SCATTER_BLOCKS]
    else:
        d = int(family.rsplit("_", 1)[1])
        g = dict(ADD[d], d=d)
        pairs = list(SCATTER_BLOCKS)
    rows, n_e = _scatter_geometry(g["v"], g["n"], g["d"])
    grids = [sops._legal_blocks(rows, n_e, bv, bn) for bv, bn in pairs]
    assert len(set(grids)) == len(pairs), grids
    assert all(rows > bv for bv, _, _ in grids), grids     # several tiles
    assert any(n_pad > bn for _, bn, n_pad in grids), grids  # and chunks


@pytest.mark.parametrize("block", GATHER_BLOCKS)
def test_gather_vmem_bit_identical_across_tiles(block):
    # 700 rows of D=7 are 4900 element fetches, ragged against every
    # block: padding lanes index element 0 and are sliced off — the tile
    # must never leak into the bits
    table, idx = _ragged_gather(np.random.default_rng(0))
    ref = np.take_along_axis(table, idx[..., None], axis=1)
    out = gops.gather_rows_batched(jnp.asarray(table), jnp.asarray(idx),
                                   mode="vmem", block=block)
    assert (np.asarray(out) == ref).all()


@pytest.mark.parametrize("block_i", GATHER_BLOCKS)
@pytest.mark.parametrize("d", (6, 128, 200))
def test_gather_dma_bit_identical_across_tiles(block_i, d):
    # dma path: D=6 gathers element by element from the lane-dense flat
    # table, D=128 one line per row, D=200 pads each row to two lines;
    # about 4200 fetches each, so every block is a multi-step grid
    table, idx = _ragged_gather(np.random.default_rng(1), n=_dma_rows(d),
                                v=33, d=d)
    ref = np.take_along_axis(table, idx[..., None], axis=1)
    out = gops.gather_rows_batched(jnp.asarray(table), jnp.asarray(idx),
                                   mode="dma", block=block_i)
    assert (np.asarray(out) == ref).all()


@pytest.mark.parametrize("block_n", [bn for _, bn in SCATTER_BLOCKS])
@pytest.mark.parametrize("block_v", [bv for bv, _ in SCATTER_BLOCKS])
def test_scatter_store_bit_identical_across_tiles(block_v, block_n):
    # unique in-range indices (the store-mode contract), one deliberately
    # OOB lane, plus the OOB padding lanes every non-divisible block adds;
    # 33 output lines over several tiles, 3500 elements over several chunks
    rng = np.random.default_rng(2)
    batch, n, v, d = (STORE[k] for k in ("batch", "n", "v", "d"))
    dst = rng.normal(size=(batch, v, d)).astype(np.float32)
    idx = np.stack([rng.permutation(v)[:n] for _ in range(batch)]
                   ).astype(np.int32)
    idx[0, 3] = v + 5                       # dropped, not wrapped
    vals = rng.normal(size=(batch, n, d)).astype(np.float32)
    ref = dst.copy()
    for b in range(batch):
        for j in range(n):
            if 0 <= idx[b, j] < v:
                ref[b, idx[b, j]] = vals[b, j]
    out = sops.scatter_store_rows_batched(
        jnp.asarray(dst), jnp.asarray(idx), jnp.asarray(vals),
        block_v=block_v, block_n=block_n)
    assert (np.asarray(out) == ref).all()


@pytest.mark.parametrize("d", sorted(ADD))
@pytest.mark.parametrize("block_v,block_n", SCATTER_BLOCKS)
def test_scatter_add_bit_identical_across_tiles(block_v, block_n, d):
    # integer payloads: every partial sum is exact, so accumulation over
    # several chunks and several output tiles must give the same bits
    rng = np.random.default_rng(3)
    batch, n, v = (ADD[d][k] for k in ("batch", "n", "v"))
    idx = rng.integers(0, v, size=(batch, n)).astype(np.int32)
    vals = rng.integers(-100, 100, size=(batch, n, d)).astype(np.float32)
    ref = np.zeros((batch, v, d), np.float32)
    for b in range(batch):
        np.add.at(ref[b], idx[b], vals[b])
    out = sops.scatter_add_rows_batched(jnp.asarray(idx), jnp.asarray(vals),
                                        v, block_v=block_v, block_n=block_n)
    assert (np.asarray(out) == ref).all()


def test_gather_property_bit_identical():
    # hypothesis sweep over ragged geometries x candidate tiles; skipped
    # (not xfailed) where hypothesis isn't installed — the parametrized
    # tests above keep the deterministic floor
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(deadline=None, max_examples=25)
    @hyp.given(n=st.integers(1, 800), v=st.integers(1, 50),
               d=st.integers(1, 16), bi=st.integers(0, 9),
               seed=st.integers(0, 2 ** 16))
    def run(n, v, d, bi, seed):
        rng = np.random.default_rng(seed)
        table, idx = _ragged_gather(rng, n=n, v=v, d=d)
        block = GATHER_BLOCKS[bi % len(GATHER_BLOCKS)]
        ref = np.take_along_axis(table, idx[..., None], axis=1)
        out = gops.gather_rows_batched(jnp.asarray(table), jnp.asarray(idx),
                                       mode="vmem", block=block)
        assert (np.asarray(out) == ref).all()

    run()


# ---------------------------------------------------------------------------
# persistence through DiskTier


PLAN = SuitePlan.build([
    make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16),
    make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16),
])


def _digests(cache):
    return [r.out_digest for r in run_plan(PLAN, runs=1, backend="pallas",
                                           cache=cache, digest=True)]


def test_disk_restore_skips_search_and_disk_hits_exact(tmp_path):
    root = str(tmp_path)
    cold = ExecutorCache(disk=DiskTier(root))
    ref = _digests(cold)
    assert cold.disk.stats()["stores"] == PLAN.n_buckets
    assert autotune.stats()["searched"] > 0          # the cold run searched

    # "restart": fresh memo, fresh cache over the same directory
    autotune.reset()
    warm = ExecutorCache()
    assert warm.attach_disk(DiskTier(root), preload=True) == PLAN.n_buckets
    s = autotune.stats()
    assert s["seeded"] > 0                           # headers re-seeded it
    assert _digests(warm) == ref                     # bit-identical
    assert autotune.stats()["searched"] == 0         # never searched again
    assert warm.stats().misses == 0
    assert warm.stats().disk_hits == PLAN.n_buckets  # exact, per bucket


def test_disk_header_carries_tiles_wire(tmp_path):
    root = str(tmp_path)
    with autotune.recording() as rec:
        cache = ExecutorCache(disk=DiskTier(root))
        _digests(cache)
    assert rec                                       # pallas traces chose
    wire = autotune.to_wire(rec)
    # a fresh process seeded ONLY from disk resolves every recorded key
    autotune.reset()
    warm = ExecutorCache()
    warm.attach_disk(DiskTier(root), preload=True)
    for ks, v in wire.items():
        key = autotune._key_from_wire(ks)
        assert autotune.lookup(key) == autotune.TileChoice.from_wire(v)
