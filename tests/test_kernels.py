"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

All kernels run in interpret mode on CPU (the TPU lowering is exercised by
the same pallas_call + BlockSpec on real hardware).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gather_rows import ops as gops
from repro.kernels.gather_rows.ref import gather_rows_ref
from repro.kernels.paged_decode import ops as pops
from repro.kernels.paged_decode.ref import paged_decode_attention_ref
from repro.kernels.scatter_rows import ops as sops
from repro.kernels.scatter_rows.ref import (scatter_add_rows_ref,
                                            scatter_store_rows_ref)

RNG = np.random.default_rng(42)

OOB = np.iinfo(np.int32).max


def _deduped_idx(v, n):
    """Random indices with duplicates routed out of range (the host
    keep-mask contract scatter_store_rows expects)."""
    from repro.core.backends import keep_last_mask
    idx = RNG.integers(0, v, n).astype(np.int32)
    keep = keep_last_mask(idx)
    return np.where(keep, idx, OOB).astype(np.int32), idx, keep


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-6)


class TestGatherRows:
    @pytest.mark.parametrize("v,d,n", [
        (8, 8, 1), (64, 16, 37), (128, 128, 128), (1000, 256, 300),
        (33, 48, 7), (4096, 64, 513),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("mode", ["vmem", "dma"])
    def test_sweep(self, v, d, n, dtype, mode):
        table = jnp.asarray(RNG.standard_normal((v, d)), dtype)
        idx = jnp.asarray(RNG.integers(0, v, n), jnp.int32)
        out = gops.gather_rows(table, idx, mode=mode)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(gather_rows_ref(table, idx), np.float32),
            **_tol(dtype))

    def test_duplicate_and_boundary_indices(self):
        table = jnp.asarray(RNG.standard_normal((16, 8)), jnp.float32)
        idx = jnp.asarray([0, 15, 15, 0, 7, 7, 7], jnp.int32)
        for mode in ("vmem", "dma"):
            out = gops.gather_rows(table, idx, mode=mode)
            np.testing.assert_allclose(out, np.asarray(table)[idx])

    @pytest.mark.parametrize("d", [1, 128])
    @pytest.mark.parametrize("mode", ["vmem", "dma"])
    def test_bit_exact_signed_zero(self, mode, d):
        # a gather copies bits: -0.0, inf and NaN payloads come back as
        # they went in (a seeded float32 table of 2^26 normals holds a
        # few exact -0.0s, so paper-scale digests depend on it)
        table = np.asarray(RNG.standard_normal((256, d)), np.float32)
        table[3] = -0.0
        table[5] = np.inf
        table[7] = np.float32(1e-40)                    # subnormal
        idx = np.asarray([3, 5, 7, 3, 0, 255], np.int32)
        out = gops.gather_rows(jnp.asarray(table), jnp.asarray(idx),
                               mode=mode)
        assert np.asarray(out).tobytes() == table[idx].tobytes()

    def test_auto_mode_selection(self):
        small = jnp.zeros((64, 16), jnp.float32)
        big = jnp.zeros((1 << 15, 512), jnp.float32)    # > VMEM budget
        i = jnp.zeros((4,), jnp.int32)
        assert gops.gather_rows(small, i).shape == (4, 16)
        assert gops.gather_rows(big, i).shape == (4, 512)


class TestScatterAddRows:
    @pytest.mark.parametrize("v,d,n", [
        (8, 8, 8), (64, 16, 200), (130, 100, 57), (128, 128, 1000),
        (1000, 32, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_sweep(self, v, d, n, dtype):
        idx = jnp.asarray(RNG.integers(0, v, n), jnp.int32)
        vals = jnp.asarray(RNG.standard_normal((n, d)), dtype)
        out = sops.scatter_add_rows(idx, vals, v)
        ref = scatter_add_rows_ref(idx, vals, v)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_all_same_index(self):
        """LULESH-S3 regime: every write lands on one row (delta 0)."""
        n, v, d = 256, 16, 32
        idx = jnp.full((n,), 3, jnp.int32)
        vals = jnp.ones((n, d), jnp.float32)
        out = sops.scatter_add_rows(idx, vals, v)
        np.testing.assert_allclose(np.asarray(out)[3], np.full(d, n))
        assert np.abs(np.asarray(out)[[i for i in range(v) if i != 3]]).max() == 0

    def test_out_of_range_dropped(self):
        idx = jnp.asarray([0, 99, 1], jnp.int32)
        vals = jnp.ones((3, 4), jnp.float32)
        out = sops.scatter_add_rows(idx, vals, 8)
        assert np.asarray(out).sum() == 8.0


class TestScatterStoreRows:
    """Single-pass store kernel: one launch, host-pre-deduped indices."""

    @pytest.mark.parametrize("v,d,n", [
        (8, 8, 8), (64, 16, 200), (130, 100, 57), (128, 128, 1000),
        (1000, 32, 64),
        (5, 3, 2),        # N and V both below the block sizes
        (257, 130, 301),  # ragged D, ragged V, ragged N all at once
    ])
    def test_sweep(self, v, d, n):
        safe_idx, _, _ = _deduped_idx(v, n)
        vals = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
        dst = jnp.asarray(RNG.standard_normal((v, d)), jnp.float32)
        out = sops.scatter_store_rows(dst, jnp.asarray(safe_idx), vals)
        ref = scatter_store_rows_ref(dst, jnp.asarray(safe_idx), vals)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_matches_sequential_lww(self):
        """With the keep mask applied, the kernel equals a sequential
        last-write-wins loop over the RAW (duplicate-laden) indices."""
        v, d, n = 40, 12, 150
        safe_idx, raw_idx, _ = _deduped_idx(v, n)
        vals = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
        dst = jnp.asarray(RNG.standard_normal((v, d)), jnp.float32)
        ref = np.asarray(dst).copy()
        for i, j in enumerate(raw_idx):
            ref[j] = np.asarray(vals)[i]
        out = sops.scatter_store_rows(dst, jnp.asarray(safe_idx), vals)
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_untouched_rows_pass_through(self):
        v, d = 64, 8
        dst = jnp.asarray(RNG.standard_normal((v, d)), jnp.float32)
        idx = jnp.asarray([3, 10], jnp.int32)
        vals = jnp.ones((2, d), jnp.float32)
        out = np.asarray(sops.scatter_store_rows(dst, idx, vals))
        np.testing.assert_array_equal(out[[3, 10]], np.ones((2, d)))
        rest = [i for i in range(v) if i not in (3, 10)]
        np.testing.assert_array_equal(out[rest], np.asarray(dst)[rest])

    def test_padding_lanes_dropped(self):
        """keep-mask padding rows: OOB lanes (dropped duplicates and lane
        padding alike) never touch the table, wherever they fall."""
        v, d = 16, 4
        dst = jnp.zeros((v, d), jnp.float32)
        idx = jnp.asarray([OOB, 2, OOB, OOB, 5, OOB], jnp.int32)
        vals = jnp.asarray(RNG.standard_normal((6, d)), jnp.float32)
        out = np.asarray(sops.scatter_store_rows(dst, idx, vals))
        np.testing.assert_array_equal(out[2], np.asarray(vals)[1])
        np.testing.assert_array_equal(out[5], np.asarray(vals)[4])
        assert np.abs(out[[i for i in range(v) if i not in (2, 5)]]).max() == 0


class TestBatchedKernels:
    """Batch-native bucket kernels: one launch per pattern batch."""

    @pytest.mark.parametrize("b,v,d,n", [
        (1, 8, 8, 8), (4, 64, 16, 33), (3, 130, 100, 57), (8, 32, 8, 5),
    ])
    def test_gather_batched(self, b, v, d, n):
        table = jnp.asarray(RNG.standard_normal((b, v, d)), jnp.float32)
        idx = jnp.asarray(RNG.integers(0, v, (b, n)), jnp.int32)
        for mode in ("vmem", "dma"):
            out = gops.gather_rows_batched(table, idx, mode=mode)
            ref = np.stack([np.asarray(table)[i][np.asarray(idx)[i]]
                            for i in range(b)])
            np.testing.assert_array_equal(np.asarray(out), ref,
                                          err_msg=mode)

    @pytest.mark.parametrize("b,v,d,n", [
        (1, 8, 8, 8), (4, 64, 16, 200), (3, 130, 100, 57), (8, 32, 8, 5),
    ])
    def test_scatter_add_batched(self, b, v, d, n):
        idx = jnp.asarray(RNG.integers(0, v, (b, n)), jnp.int32)
        vals = jnp.asarray(RNG.standard_normal((b, n, d)), jnp.float32)
        out = sops.scatter_add_rows_batched(idx, vals, v)
        ref = np.stack([np.asarray(scatter_add_rows_ref(
            idx[i], vals[i], v)) for i in range(b)])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)

    @pytest.mark.parametrize("b,v,d,n", [
        (1, 8, 8, 8), (4, 64, 16, 200), (3, 130, 100, 57), (8, 32, 8, 5),
    ])
    def test_scatter_store_batched(self, b, v, d, n):
        rows = [_deduped_idx(v, n)[0] for _ in range(b)]
        safe_idx = jnp.asarray(np.stack(rows))
        vals = jnp.asarray(RNG.standard_normal((b, n, d)), jnp.float32)
        dst = jnp.asarray(RNG.standard_normal((b, v, d)), jnp.float32)
        out = sops.scatter_store_rows_batched(dst, safe_idx, vals)
        ref = np.stack([np.asarray(scatter_store_rows_ref(
            dst[i], safe_idx[i], vals[i])) for i in range(b)])
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_batched_matches_per_pattern_launches(self):
        """The one-launch bucket kernel is bit-identical to B separate
        single-pattern launches (vmap-replacement contract)."""
        b, v, d, n = 5, 48, 24, 70
        table = jnp.asarray(RNG.standard_normal((b, v, d)), jnp.float32)
        idx = jnp.asarray(RNG.integers(0, v, (b, n)), jnp.int32)
        batched = gops.gather_rows_batched(table, idx)
        for i in range(b):
            single = gops.gather_rows(table[i], idx[i])
            np.testing.assert_array_equal(np.asarray(batched)[i],
                                          np.asarray(single))


class TestGatherMultiRowBlocking:
    """dma regime multi-row blocking (``block`` indices per grid step)."""

    @pytest.mark.parametrize("n", [1, 3, 7, 8, 9, 64, 513])
    @pytest.mark.parametrize("block", [1024, 2048, 4096])
    def test_ragged_n(self, n, block):
        table = jnp.asarray(RNG.standard_normal((100, 16)), jnp.float32)
        idx = jnp.asarray(RNG.integers(0, 100, n), jnp.int32)
        out = gops.gather_rows(table, idx, mode="dma", block=block)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(table)[np.asarray(idx)])

    def test_block_i_invariance(self):
        """Results are invariant to the blocking factor: 100 rows of 48
        elements are 4800 fetches, so each block is its own grid."""
        table = jnp.asarray(RNG.standard_normal((64, 48)), jnp.float32)
        idx = jnp.asarray(RNG.integers(0, 64, 100), jnp.int32)
        ref = np.asarray(gops.gather_rows(table, idx, mode="dma",
                                          block=1024))
        grids = {gops._legal_block(b, 4800) for b in (1024, 2048, 4096,
                                                       8192)}
        assert len(grids) == 4
        for block in (2048, 4096, 8192):
            out = gops.gather_rows(table, idx, mode="dma", block=block)
            np.testing.assert_array_equal(np.asarray(out), ref)


def _spatter(index, delta: int, count: int) -> np.ndarray:
    """A Spatter pattern's absolute indices, ``delta * i + index[j]``,
    i-major (``core/pattern.py``)."""
    return (np.arange(count)[:, None] * delta
            + np.asarray(index)[None, :]).reshape(-1).astype(np.int32)


def _special_words(v: int, d: int = 1) -> np.ndarray:
    """A float32 table holding -0.0, inf, a subnormal and a NaN payload."""
    t = np.asarray(RNG.standard_normal((v, d)), np.float32)
    t[3] = -0.0
    t[5] = np.inf
    t[7] = np.float32(1e-40)
    t[9] = np.frombuffer(np.uint32(0x7FC0_1234).tobytes(), np.float32)[0]
    return t


LULESH_G7 = list(range(16))                     # delta 41
LULESH_G3 = [24 * i for i in range(16)]         # delta 8


class TestGatherWindow:
    """The narrow DMA regime's window path: a grid step whose elements
    lie in ``window_lines`` consecutive lines fetches them with one DMA;
    any other step fetches one line per element.  Block 1024, as on the
    chip, so a launch holds several steps."""

    @staticmethod
    def _gather(table, idx, **kw):
        return np.asarray(gops.gather_rows_batched(
            jnp.asarray(table), jnp.asarray(idx), mode="dma", block=1024,
            **kw))

    @staticmethod
    def _steps(idx, v, d=1):
        return gops.window_steps(idx, v, d, mode="dma", block=1024)

    @pytest.mark.parametrize("index,delta", [
        (list(range(16)), 0), (list(range(16)), 1), (LULESH_G3, 8),
        (LULESH_G7, 41)])
    def test_window_path_bit_exact(self, index, delta):
        idx = _spatter(index, delta, 320)                # 5 steps
        v = max(int(idx.max()) + 1, 40_000)              # > 32 lines
        table = _special_words(v)
        idx[[0, 17, 2000]] = [3, 9, 5]                   # special words
        idx[4000] = 7
        out = self._gather(table[None], idx[None])
        ref = gather_rows_ref(jnp.asarray(table), jnp.asarray(idx))
        assert out[0].tobytes() == np.asarray(ref).tobytes()
        assert out[0].tobytes() == table[idx].tobytes()
        # the special words sit in lines 0 and 15: steps 0 and 1 reach
        # back to them and leave the window (delta 41 spans 21 lines)
        hits, steps = self._steps(idx[None], v)
        assert steps == 5 and hits >= 3

    def test_fallback_on_random_indices(self):
        v = 1 << 16                                      # 512 lines
        table = _special_words(v)
        idx = RNG.integers(0, v, 3000).astype(np.int32)
        idx[[1, 2]] = [3, 9]
        out = self._gather(table[None], idx[None])
        assert out.tobytes() == table[idx][None].tobytes()
        assert self._steps(idx, v) == (0, 3)

    def test_mixed_launch(self):
        """One dense member and one random one in one launch: each step
        takes its own path."""
        v = 1 << 16
        table = np.stack([_special_words(v), _special_words(v)])
        dense = _spatter(LULESH_G3, 8, 256)
        rand = RNG.integers(0, v, dense.size).astype(np.int32)
        idx = np.stack([dense, rand])
        out = self._gather(table, idx)
        ref = np.stack([table[b][idx[b]] for b in range(2)])
        assert out.tobytes() == ref.tobytes()
        assert self._steps(dense, v) == (4, 4)
        assert self._steps(idx, v) == (4, 8)

    @pytest.mark.parametrize("n", [1, 1000, 2500])
    def test_ragged_last_step(self, n):
        """The padded tail repeats the last index, so a ragged last step
        still fits its window."""
        v = 1 << 16
        table = _special_words(v)
        idx = _spatter(LULESH_G7, 41, -(-n // 16))[:n] + 30_000
        out = self._gather(table[None], idx[None])
        assert out.tobytes() == table[idx][None].tobytes()
        steps = -(-n // 1024)
        assert self._steps(idx, v) == (steps, steps)

    @pytest.mark.parametrize("v", [8, 1000, 4095])
    def test_table_smaller_than_the_window(self, v):
        """Forced into the DMA regime, a table of fewer lines than the
        window is fetched whole: every step fits."""
        table = _special_words(max(v, 10))[:v]
        idx = RNG.integers(0, v, 2100).astype(np.int32)
        out = self._gather(table[None], idx[None])
        assert out.tobytes() == table[idx][None].tobytes()
        assert self._steps(idx, v) == (3, 3)

    @pytest.mark.parametrize("d", [1, 4, 48])
    def test_counter_matches_device_flags(self, d):
        """``window_steps`` (numpy) counts the steps whose device-side
        base is not -1, on Spatter and random indices."""
        v = 1 << 14
        table = jnp.zeros((3, v, d), jnp.float32)
        idx = np.stack([
            _spatter(LULESH_G7, 41, 300) % v,
            _spatter(LULESH_G3, 8, 300),
            RNG.integers(0, v, 4800).astype(np.int32)])
        _, _, blk, bases, _ = gops._launch_operands(table, jnp.asarray(idx),
                                                    True, 1024)
        assert blk == 1024
        hits, steps = self._steps(idx, v, d)
        assert (hits, steps) == (int((np.asarray(bases) >= 0).sum()),
                                 bases.size)
        assert 0 < hits < steps

    @pytest.mark.parametrize("name", [f"LULESH-G{k}" for k in range(8)])
    def test_every_lulesh_gather_fits(self, name):
        """Table 5's LULESH gathers fit the window in every step at block
        1024 (at 2^14 of their 2^20 i-steps: the fit repeats)."""
        from repro.core import appdb
        pat = next(p for p in appdb.LULESH_GATHERS if p.name == name)
        idx = _spatter(pat.index, pat.delta, 1 << 14)
        rows = int(idx.max()) + 2                     # + the scratch row
        hits, steps = gops.window_steps(idx, rows, 1, block=1024,
                                        mode="dma")
        assert hits == steps == (1 << 18) // 1024

    def test_no_window_path_outside_the_narrow_dma_regime(self):
        idx = np.arange(4096, dtype=np.int32)
        assert gops.window_steps(idx, 1 << 16, 128, mode="dma") == (0, 0)
        assert gops.window_steps(idx, 4096, 1, mode="vmem") == (0, 0)
        assert gops.window_steps(idx, 4096, 1) == (0, 0)     # auto: vmem
        assert gops.window_lines(1024, 8) == 8
        assert gops.window_lines(1024, 1 << 20) == 32


class TestOneHotWords:
    """One-hot stores and gathers move 32-bit words: the Pallas store
    kernel and the onehot backend return every bit pattern unchanged."""

    @staticmethod
    def _special(shape):
        x = np.asarray(RNG.standard_normal(shape), np.float32)
        x[0] = -0.0
        x[1] = np.inf
        x[2] = np.float32(1e-40)                        # subnormal
        x[3] = np.nan
        return x

    @pytest.mark.parametrize("d", [1, 128])
    def test_bit_exact_signed_zero(self, d):
        # a special value lands as it is and leaves its neighbours in the
        # same 128-lane slice alone (no 0 * inf = NaN spread)
        v = 256
        dst = np.asarray(RNG.standard_normal((v, d)), np.float32)
        vals = self._special((6, d))
        idx = np.asarray([3, 5, 7, 9, 11, 200], np.int32)
        ref = dst.copy()
        ref[idx] = vals
        out = sops.scatter_store_rows(jnp.asarray(dst), jnp.asarray(idx),
                                      jnp.asarray(vals))
        assert np.asarray(out).tobytes() == ref.tobytes()

    @pytest.mark.parametrize("d", [1, 8])
    def test_onehot_backend_bit_exact(self, d):
        from repro.core import backends as B
        f = 64
        src = self._special((f, d))
        idx = np.asarray([0, 1, 2, 3, 9, 0], np.int32)
        got = B.gather_onehot(jnp.asarray(src), jnp.asarray(idx))
        assert np.asarray(got).tobytes() == src[idx].tobytes()
        dst = np.asarray(RNG.standard_normal((f, d)), np.float32)
        dst[40] = np.inf                                 # never written
        vals = self._special((5, d))
        sidx = np.asarray([7, 8, 7, 20, 30], np.int32)   # 7 twice: last wins
        ref = dst.copy()
        for i, j in enumerate(sidx):
            ref[j] = vals[i]
        out = B.scatter_onehot(jnp.asarray(dst), jnp.asarray(sidx),
                               jnp.asarray(vals), mode="store")
        assert np.asarray(out).tobytes() == ref.tobytes()


class TestPagedDecode:
    @pytest.mark.parametrize("b,kvh,g,dh,pages,page,pps", [
        (1, 1, 1, 16, 4, 8, 2), (2, 2, 4, 16, 12, 8, 3),
        (4, 2, 2, 64, 32, 16, 4), (2, 4, 1, 32, 8, 8, 2),
    ])
    def test_sweep(self, b, kvh, g, dh, pages, page, pps):
        q = jnp.asarray(RNG.standard_normal((b, kvh, g, dh)), jnp.float32)
        kp = jnp.asarray(RNG.standard_normal((kvh, pages, page, dh)),
                         jnp.float32)
        vp = jnp.asarray(RNG.standard_normal((kvh, pages, page, dh)),
                         jnp.float32)
        pt = jnp.asarray(RNG.integers(0, pages, (b, pps)), jnp.int32)
        ln = jnp.asarray(RNG.integers(1, page * pps + 1, (b,)), jnp.int32)
        out = pops.paged_decode_attention(q, kp, vp, pt, ln)
        ref = paged_decode_attention_ref(q, kp, vp, pt, ln,
                                         scale=1.0 / dh ** 0.5)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        b, kvh, g, dh, pages, page, pps = 2, 2, 2, 32, 8, 8, 2
        q = jnp.asarray(RNG.standard_normal((b, kvh, g, dh)), jnp.bfloat16)
        kp = jnp.asarray(RNG.standard_normal((kvh, pages, page, dh)),
                         jnp.bfloat16)
        vp = jnp.asarray(RNG.standard_normal((kvh, pages, page, dh)),
                         jnp.bfloat16)
        pt = jnp.asarray(RNG.integers(0, pages, (b, pps)), jnp.int32)
        ln = jnp.full((b,), page * pps, jnp.int32)
        out = pops.paged_decode_attention(q, kp, vp, pt, ln)
        ref = paged_decode_attention_ref(q, kp, vp, pt, ln,
                                         scale=1.0 / dh ** 0.5)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestFlashAttention:
    @pytest.mark.parametrize("b,kvh,g,s,t,dh,causal,window,cap", [
        (2, 2, 2, 64, 64, 16, True, 0, 0.0),
        (1, 1, 4, 128, 128, 32, True, 32, 0.0),
        (2, 1, 1, 64, 64, 16, True, 0, 50.0),     # gemma2 softcap
        (1, 2, 2, 96, 96, 16, False, 0, 0.0),     # bidirectional (whisper)
    ])
    def test_fwd_and_grad(self, b, kvh, g, s, t, dh, causal, window, cap):
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.flash_attention.ref import flash_attention_ref
        q = jnp.asarray(RNG.standard_normal((b, kvh, g, s, dh)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, kvh, t, dh)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, kvh, t, dh)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cap, block_q=32, block_k=32)
        ref = flash_attention_ref(q, k, v, scale=1 / dh ** 0.5,
                                  causal=causal, window=window, softcap=cap)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        gk = jax.grad(lambda q: flash_attention(
            q, k, v, causal=causal, window=window, softcap=cap,
            block_q=32, block_k=32).sum())(q)
        gr = jax.grad(lambda q: flash_attention_ref(
            q, k, v, scale=1 / dh ** 0.5, causal=causal, window=window,
            softcap=cap).sum())(q)
        np.testing.assert_allclose(gk, gr, rtol=1e-4, atol=1e-5)

    def test_matches_model_attention(self):
        """flash == the model's chunked_attention on a GQA case."""
        from repro.kernels.flash_attention import flash_attention
        from repro.models.common import chunked_attention
        b, s, kvh, g, dh = 2, 64, 2, 2, 16
        q = jnp.asarray(RNG.standard_normal((b, s, kvh, g, dh)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, s, kvh, dh)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, s, kvh, dh)), jnp.float32)
        ref = chunked_attention(q, k, v, chunk=16, causal=True)
        qf = jnp.moveaxis(q, 1, 3)                     # (B,KVH,G,S,dh)
        kf = jnp.moveaxis(k, 1, 2)                     # (B,KVH,T,dh)
        vf = jnp.moveaxis(v, 1, 2)
        out = flash_attention(qf, kf, vf, causal=True, block_q=16,
                              block_k=16)
        np.testing.assert_allclose(jnp.moveaxis(out, 3, 1), ref,
                                   rtol=1e-4, atol=1e-5)


class TestSelectiveScan:
    @pytest.mark.parametrize("b,l,d,n,bl", [
        (2, 32, 16, 8, 8), (1, 64, 32, 16, 16), (2, 128, 8, 4, 32),
        (1, 48, 16, 8, 16),
    ])
    def test_matches_ref(self, b, l, d, n, bl):
        from repro.kernels.selective_scan import selective_scan
        from repro.kernels.selective_scan.ref import selective_scan_ref
        u = jnp.asarray(RNG.standard_normal((b, l, d)), jnp.float32)
        dt = jnp.asarray(np.abs(RNG.standard_normal((b, l, d))) * 0.1,
                         jnp.float32)
        bi = jnp.asarray(RNG.standard_normal((b, l, n)), jnp.float32)
        ci = jnp.asarray(RNG.standard_normal((b, l, n)), jnp.float32)
        a = jnp.asarray(-np.abs(RNG.standard_normal((n, d))), jnp.float32)
        dsk = jnp.asarray(RNG.standard_normal((1, d)), jnp.float32)
        y, h = selective_scan(u, dt, bi, ci, a, dsk, block_l=bl)
        yr, hr = selective_scan_ref(u, dt, bi, ci, a, dsk)
        np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h, hr, rtol=1e-5, atol=1e-5)

    def test_kernel_path_in_model(self):
        """mamba_apply(use_scan_kernel=True) == default XLA path."""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models.ssm import mamba_apply, mamba_defs
        from repro.models.common import init_tree
        cfg = dataclasses.replace(get_smoke_config("falcon-mamba-7b"),
                                  dtype="float32")
        p = init_tree(jax.random.PRNGKey(0), mamba_defs(cfg), jnp.float32)
        x = jnp.asarray(RNG.standard_normal((2, 32, cfg.d_model)),
                        jnp.float32)
        y0 = mamba_apply(cfg, p, x)
        y1 = mamba_apply(cfg, p, x, use_scan_kernel=True)
        np.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-4)
