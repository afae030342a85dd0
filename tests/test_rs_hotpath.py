"""RS data-plane hot-path gate (CI: `pytest -m rs_hotpath`).

Pins the streamed/tiled/sharded/grouped RS paths bit-identical to the
numpy reference (ops/gf256.rs_encode_ref / rs_decode_ref) across
RS(2,1) and RS(12,4), odd-tail widths, every RS(2,1) erasure pattern,
and mixed per-segment patterns — plus the one-shape invariant: a
multi-tile stream traces each GF(256) kernel exactly once
(rs.COMPILE_COUNTS, the same trace-time counter pattern as
proof/fused.py)."""

from __future__ import annotations

import time

import numpy as np
import pytest

from cess_tpu.ops import gf256, rs
from cess_tpu.parallel import make_mesh

pytestmark = pytest.mark.rs_hotpath

PATHS = ("bitplane", "gather")
RS21_PATTERNS = ([0, 1], [0, 2], [1, 2])  # every 2-of-3 survivor set


def stage_samples(reg) -> dict:
    """{sample name: value} of a registry's unlabelled samples."""
    return {name: v for metric in reg.metrics()
            for name, labels, v in metric.samples() if not labels}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _roundtrip_case(k, m, n, seed):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    parity = gf256.rs_encode_ref(data, k, m)
    return data, np.concatenate([data, parity], axis=0)


# ------------------------------------------------------------ bit identity


class TestTiledBitIdentity:
    @pytest.mark.parametrize("path", PATHS)
    @pytest.mark.parametrize("k,m", [(2, 1), (12, 4)])
    @pytest.mark.parametrize("n", [16, 100, 1021, 4096])
    def test_encode_matches_reference(self, path, k, m, n):
        data, _ = _roundtrip_case(k, m, n, seed=n)
        code = rs.RSCode(k, m, path=path)
        got = np.asarray(code.encode(data))
        assert np.array_equal(got, gf256.rs_encode_ref(data, k, m))

    @pytest.mark.parametrize("path", PATHS)
    @pytest.mark.parametrize("present", RS21_PATTERNS)
    def test_rs21_every_erasure_pattern(self, path, present):
        data, allsh = _roundtrip_case(2, 1, 777, seed=3)
        code = rs.RSCode(2, 1, path=path)
        got = np.asarray(code.reconstruct(allsh[present], present))
        assert np.array_equal(got, data)
        assert np.array_equal(
            got, gf256.rs_decode_ref(allsh[present], present, 2, 1)
        )

    @pytest.mark.parametrize("path", PATHS)
    def test_rs124_random_patterns(self, path):
        rng = np.random.default_rng(7)
        data, allsh = _roundtrip_case(12, 4, 250, seed=9)
        code = rs.RSCode(12, 4, path=path)
        for _ in range(5):
            present = sorted(rng.choice(16, size=12, replace=False).tolist())
            got = np.asarray(code.reconstruct(allsh[present], present))
            assert np.array_equal(got, data)


class TestStreamedBitIdentity:
    """Multi-tile streams (odd tail) == whole-array reference."""

    @pytest.mark.parametrize("path", PATHS)
    def test_stream_encode_odd_tail(self, path):
        # 4096-byte tiles over a 3.3-tile stream
        data, _ = _roundtrip_case(2, 1, 13_500, seed=5)
        code = rs.RSCode(2, 1, path=path, tile=4096)
        got = rs.RSStream(code).run(data)
        assert np.array_equal(got, gf256.rs_encode_ref(data, 2, 1))

    @pytest.mark.parametrize("path", PATHS)
    @pytest.mark.parametrize("present", RS21_PATTERNS)
    def test_stream_reconstruct_every_pattern(self, path, present):
        data, allsh = _roundtrip_case(2, 1, 10_000, seed=6)
        code = rs.RSCode(2, 1, path=path, tile=4096)
        got = rs.RSStream(code, present=present).run(allsh[present])
        assert np.array_equal(got, data)

    @pytest.mark.parametrize("path", PATHS)
    def test_stream_rs124(self, path):
        data, allsh = _roundtrip_case(12, 4, 9_001, seed=8)
        code = rs.RSCode(12, 4, path=path, tile=2048)
        present = [0, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15]
        got = rs.RSStream(code, present=present).run(allsh[present])
        assert np.array_equal(
            got, gf256.rs_decode_ref(allsh[present], present, 12, 4)
        )

    def test_stream_encode_rejects_extra_rows(self):
        code = rs.RSCode(2, 1, path="gather")
        bad = np.zeros((3, 64), dtype=np.uint8)
        with pytest.raises(ValueError, match="exactly 2 data rows"):
            rs.RSStream(code).run(bad)


class TestGroupedRecovery:
    """Per-segment survivor lists: grouped per-pattern recovery is
    bit-identical to per-item gf256.rs_decode_ref."""

    def _mixed_batch(self, k, m, b, n, seed):
        rng = np.random.default_rng(seed)
        data = rng.integers(0, 256, size=(b, k, n), dtype=np.uint8)
        allsh = np.stack(
            [np.concatenate(
                [data[i], gf256.rs_encode_ref(data[i], k, m)], axis=0
            ) for i in range(b)]
        )
        pats = [
            sorted(rng.choice(k + m, size=k, replace=False).tolist())
            for _ in range(b)
        ]
        surv = np.stack([allsh[i, pats[i]] for i in range(b)])
        return data, pats, surv

    @pytest.mark.parametrize("path", PATHS)
    @pytest.mark.parametrize("k,m,n", [(2, 1, 501), (12, 4, 129)])
    def test_host_grouped_matches_per_item_reference(self, path, k, m, n):
        data, pats, surv = self._mixed_batch(k, m, 11, n, seed=k * 100 + n)
        code = rs.RSCode(k, m, path=path)
        got = code.reconstruct_batch(surv, pats)
        assert isinstance(got, np.ndarray)
        for i in range(len(pats)):
            want = gf256.rs_decode_ref(surv[i], pats[i], k, m)
            assert np.array_equal(got[i], want), f"segment {i}"
        assert np.array_equal(got, data)

    @pytest.mark.parametrize("path", PATHS)
    def test_mesh_grouped_matches_host(self, path, mesh):
        data, pats, surv = self._mixed_batch(2, 1, 13, 333, seed=42)
        code = rs.RSCode(2, 1, path=path)
        host = code.reconstruct_batch(surv, pats)
        meshed = code.reconstruct_batch(surv, pats, mesh=mesh)
        assert np.array_equal(np.asarray(meshed), np.asarray(host))
        assert np.array_equal(np.asarray(meshed), data)

    def test_grouped_encode_stream(self):
        rng = np.random.default_rng(12)
        data = rng.integers(0, 256, size=(9, 2, 700), dtype=np.uint8)
        code = rs.RSCode(2, 1, path="gather")
        got = rs.RSStream(code, slab=4).run_batch(data)
        want = np.stack(
            [gf256.rs_encode_ref(data[i], 2, 1) for i in range(9)]
        )
        assert np.array_equal(got, want)

    def test_pattern_count_mismatch(self):
        code = rs.RSCode(2, 1, path="gather")
        surv = np.zeros((3, 2, 32), dtype=np.uint8)
        with pytest.raises(ValueError, match="survivor lists for"):
            code.reconstruct_batch(surv, [[0, 1], [1, 2]])


class TestMeshSharded:
    """Byte-axis and batch-axis sharding over the 8-device virtual mesh."""

    @pytest.mark.parametrize("path", PATHS)
    def test_cols_sharded_encode_reconstruct(self, path, mesh):
        data, allsh = _roundtrip_case(2, 1, 1000, seed=2)  # not /8: pads
        code = rs.RSCode(2, 1, path=path)
        par = np.asarray(code.encode(data, mesh=mesh))
        assert np.array_equal(par, gf256.rs_encode_ref(data, 2, 1))
        got = np.asarray(code.reconstruct(allsh[[0, 2]], [0, 2], mesh=mesh))
        assert np.array_equal(got, data)

    @pytest.mark.parametrize("path", PATHS)
    def test_batch_sharded_shared_pattern(self, path, mesh):
        rng = np.random.default_rng(21)
        data = rng.integers(0, 256, size=(16, 2, 257), dtype=np.uint8)
        code = rs.RSCode(2, 1, path=path)
        par = np.asarray(code.encode_batch(data, mesh=mesh))
        surv = np.concatenate([data[:, 1:2], par], axis=1)
        got = np.asarray(code.reconstruct_batch(surv, [1, 2], mesh=mesh))
        assert np.array_equal(got, data)

    def test_mesh_stream_matches_host_stream(self, mesh):
        data, allsh = _roundtrip_case(2, 1, 20_000, seed=30)
        code = rs.RSCode(2, 1, path="gather", tile=4096)
        host = rs.RSStream(code, present=[1, 2]).run(allsh[[1, 2]])
        meshed = rs.RSStream(code, present=[1, 2], mesh=mesh).run(
            allsh[[1, 2]]
        )
        assert np.array_equal(meshed, host)
        assert np.array_equal(meshed, data)


# ------------------------------------------------------- one-shape counter


class TestOneShapeInvariant:
    def test_multi_tile_stream_compiles_once(self):
        """A fresh (k, m, tile) geometry traces its kernel exactly once
        for the whole multi-tile stream, and NOT AT ALL on a second
        stream at the same geometry — the measurable one-shape
        invariant (trace-time counter, proof/fused.py pattern)."""
        rng = np.random.default_rng(17)
        # geometry no other test uses, so the count delta is this test's
        code = rs.RSCode(3, 2, path="gather", tile=1024)
        data = rng.integers(0, 256, size=(3, 10_240 + 13), dtype=np.uint8)
        before = dict(rs.COMPILE_COUNTS)
        first = rs.RSStream(code).run(data)  # 11 tiles incl. padded tail
        delta = {
            k: rs.COMPILE_COUNTS[k] - before[k] for k in rs.COMPILE_COUNTS
        }
        assert delta == {"bitplane": 0, "gather": 1}
        again = rs.RSStream(code).run(data)
        assert rs.COMPILE_COUNTS["gather"] - before["gather"] == 1
        assert np.array_equal(first, again)
        assert np.array_equal(first, gf256.rs_encode_ref(data, 3, 2))

    def test_grouped_slabs_share_one_executable(self):
        """Every recovery group dispatches the same (slab, k, n) shape,
        so three groups with three distinct masks add at most one
        trace (zero when an earlier test already traced it)."""
        rng = np.random.default_rng(19)
        code = rs.RSCode(5, 3, path="gather")
        data = rng.integers(0, 256, size=(9, 5, 640), dtype=np.uint8)
        allsh = np.stack(
            [np.concatenate(
                [data[i], gf256.rs_encode_ref(data[i], 5, 3)], axis=0
            ) for i in range(9)]
        )
        pats = [sorted({0, 1, 2, 3, 4, 5, 6, 7} - {i % 3, 5 + i % 3})[:5]
                for i in range(9)]
        surv = np.stack([allsh[i, pats[i]] for i in range(9)])
        before = rs.COMPILE_COUNTS["gather"]
        got = rs.RSStream(code, present=pats, slab=4).run_batch(surv)
        assert rs.COMPILE_COUNTS["gather"] - before <= 1
        assert np.array_equal(got, data)


# ------------------------------------------------------------- validation


class TestValidation:
    @pytest.mark.parametrize("present,msg", [
        ([1, 1], "duplicate"),
        ([0, 5], "out of range"),
        ([-1, 2], "out of range"),
        ([0], "need 2 shards"),
    ])
    def test_bad_present_fails_loudly(self, present, msg):
        code = rs.RSCode(2, 1, path="gather")
        shards = np.zeros((2, 64), dtype=np.uint8)
        with pytest.raises(ValueError, match=msg):
            code.reconstruct(shards, present)
        with pytest.raises(ValueError, match=msg):
            code.recovery_matrix(present)

    def test_bad_shard_arrays(self):
        code = rs.RSCode(2, 1, path="gather")
        with pytest.raises(ValueError, match="2-D"):
            code.encode(np.zeros(64, dtype=np.uint8))
        with pytest.raises(ValueError, match="empty"):
            code.encode(np.zeros((2, 0), dtype=np.uint8))
        with pytest.raises(ValueError, match="3-D"):
            code.encode_batch(np.zeros((2, 64), dtype=np.uint8))
        with pytest.raises(ValueError, match="need 2 shard rows"):
            code.reconstruct(np.zeros((1, 64), dtype=np.uint8), [0, 1])


# --------------------------------------------------- caches + telemetry


class TestConstantCacheAndTelemetry:
    def test_device_constants_shared_across_codes(self):
        a = rs.RSCode(12, 4, path="bitplane")
        b = rs.RSCode(12, 4, path="bitplane")
        assert a._mul_table is b._mul_table
        assert a._parity_bits is b._parity_bits
        assert a._parity_dev is b._parity_dev

    def test_stage_histograms_populate(self):
        reg = rs.rs_stage_registry()
        rendered = reg.render()
        for name in rs.RS_STAGE_NAMES:
            assert f"cess_rs_{name}_seconds" in rendered
        code = rs.RSCode(2, 1, path="gather", tile=2048)
        data = np.random.default_rng(23).integers(
            0, 256, size=(2, 9000), dtype=np.uint8
        )
        before = stage_samples(reg)
        rs.RSStream(code).run(data)
        after = stage_samples(reg)
        for name in rs.RS_STAGE_NAMES:
            key = f"cess_rs_{name}_seconds"
            assert after[key + "_count"] > before.get(key + "_count", 0)
            assert after[key + "_sum"] >= before.get(key + "_sum", 0.0)
        assert "cess_rs_bytes_total" in reg.render()

    def test_slab_counts_and_nested_stages(self, monkeypatch):
        """run_batch over three survivor patterns (3, 2 and 2 segments,
        slabs of 2): exact slab counters; gather and put inside pack,
        pull and scatter inside unpack, each a `cess.rs.*` span."""
        from cess_tpu.utils import stages

        spans = []

        class Span:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *exc):
                spans.append((self.name, self.t0, time.perf_counter()))

        monkeypatch.setattr(stages, "TraceAnnotation", Span)
        code = rs.RSCode(2, 1, path="gather")
        rng = np.random.default_rng(5)
        present = [[0, 1]] * 3 + [[0, 2]] * 2 + [[1, 2]] * 2
        surv = rng.integers(0, 256, size=(7, 2, 64), dtype=np.uint8)
        reg = rs.rs_stage_registry()
        before = stage_samples(reg)
        got = rs.RSStream(code, present=present, slab=2).run_batch(surv)
        after = stage_samples(reg)
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        for s in range(7):
            want = gf256.rs_decode_ref(surv[s], present[s], 2, 1)
            assert np.array_equal(got[s], want)
        assert delta["cess_rs_slab_segments_total"] == 7
        assert delta["cess_rs_slab_slots_total"] == 8  # slabs 2+2, 2, 2
        assert delta["cess_rs_pack_seconds_count"] == 4
        assert delta["cess_rs_scatter_seconds_count"] == 3  # one a group

        def total(stage):
            return delta[f"cess_rs_{stage}_seconds_sum"]

        assert total("gather") + total("put") <= total("pack")
        assert total("pull") + total("scatter") <= total("unpack")
        names = {name for name, _, _ in spans}
        assert names == {"cess.rs." + n for n in rs.RS_STAGE_NAMES}

        def within(inner, outer):
            outs = [(a, b) for n, a, b in spans if n == "cess.rs." + outer]
            return all(any(a <= s <= e <= b for a, b in outs)
                       for n, s, e in spans if n == "cess.rs." + inner)

        assert within("gather", "pack") and within("put", "pack")
        assert within("pull", "unpack") and within("scatter", "unpack")
