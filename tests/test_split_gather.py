"""Vectorized shuffled-read gather (ISSUE 6 tentpole).

The contract under test: every shuffle mode (record/batch/window) rides
ONE windowed emission path whose order is bit-identical to the
pre-change ``shuffle='record'`` loop for the same (seed, epoch) — on v1
AND compressed containers, through the zero-copy ``next_gather_batch``
handoff AND the framed-bytes fallback, via the fused native producer AND
the generic batcher, with fault:// chaos healed by retries — and the
gather path must actually BEAT the legacy per-record loop (so the 13x
shuffled-read wall can't silently regress).
"""

import os
import time

import numpy as np
import pytest

from dmlc_core_tpu.io import (
    IndexedRecordIOSplitter,
    MemoryStream,
    RecordIOWriter,
    TemporaryDirectory,
)
from dmlc_core_tpu.io import split as io_split
from dmlc_core_tpu.io.recordio import RecordIOChunkReader
from dmlc_core_tpu.utils import Error


def make_indexed_rec(tmp, records, name="data", codec=None):
    """Write records + sidecar index; codec=None → v1 container,
    else compressed blocks (IndexedRecordIOWriter)."""
    if codec is not None:
        from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
        from dmlc_core_tpu.io.stream import FileStream

        p = os.path.join(tmp, f"{name}.rec")
        idx = os.path.join(tmp, f"{name}.idx")
        with FileStream(p, "w") as d, FileStream(idx, "w") as i:
            w = IndexedRecordIOWriter(d, i, codec=codec, block_bytes=512)
            for r in records:
                w.write_record(r)
            w.flush()
        return p, idx
    ms = MemoryStream()
    w = RecordIOWriter(ms)
    offsets = []
    for r in records:
        offsets.append(ms.tell())
        w.write_record(r)
    p = os.path.join(tmp, f"{name}.rec")
    with open(p, "wb") as f:
        f.write(ms.getvalue())
    idx = os.path.join(tmp, f"{name}.idx")
    with open(idx, "w") as f:
        for i, off in enumerate(offsets):
            f.write(f"{i}\t{off}\n")
    return p, idx


def records_of(n, tag="g"):
    return [f"{tag}rec{i:04d}".encode() * (i % 7 + 1) for i in range(n)]


def drain_records(split):
    out = []
    while True:
        rec = split.next_record()
        if rec is None:
            return out
        out.append(bytes(rec))


def drain_gather(split, n=13):
    """Drain via the zero-copy emission; returns the record payloads in
    emission order (frames parsed back out of the handed views)."""
    out = []
    while True:
        g = split.next_gather_batch(n)
        if g is None:
            return out
        buf, starts, sizes = g
        assert starts.dtype == np.int64 and sizes.dtype == np.int64
        for s, z in zip(starts.tolist(), sizes.tolist()):
            framed = buf[s : s + z].tobytes()
            recs = [bytes(r) for r in RecordIOChunkReader(framed, 0, 1)]
            assert len(recs) == 1  # each slice is one whole record
            out.append(recs[0])


@pytest.mark.parametrize("codec", (None, "zlib"))
@pytest.mark.parametrize("mode", ("record", "window"))
def test_gather_order_bit_identical_to_legacy_record(codec, mode):
    """Acceptance: gather-path epoch order == pre-change
    shuffle='record' for the same (seed, epoch), v1 and compressed
    containers, for both full-permutation modes, both emission paths."""
    records = records_of(137)
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records, codec=codec)
        pv, iv = make_indexed_rec(tmp.path, records, name="v1")
        for epoch in (0, 2):
            legacy = IndexedRecordIOSplitter(
                pv, iv, 0, 1, batch_size=9, shuffle="record", seed=5,
                epoch=epoch, legacy_shuffle=True,
            )
            ref = drain_records(legacy)
            legacy.close()
            kw = dict(batch_size=9, shuffle=mode, seed=5, epoch=epoch,
                      window=32)
            s = IndexedRecordIOSplitter(p, idx, 0, 1, **kw)
            assert drain_records(s) == ref, (codec, mode, epoch, "bytes")
            s.close()
            s = IndexedRecordIOSplitter(p, idx, 0, 1, **kw)
            assert drain_gather(s) == ref, (codec, mode, epoch, "gather")
            stats = s.io_stats()
            s.close()
            assert stats["gather_batches"] > 0
            assert stats["gather_fallback_batches"] == 0


def test_batch_mode_gather_equals_bytes_emission():
    """Batch mode rides the same machinery: the gather emission and the
    framed-bytes emission agree record for record, and span-internal
    file order survives."""
    records = records_of(83)
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records)
        a = IndexedRecordIOSplitter(
            p, idx, 0, 1, batch_size=10, shuffle="batch", seed=4
        )
        via_bytes = drain_records(a)
        a.close()
        b = IndexedRecordIOSplitter(
            p, idx, 0, 1, batch_size=10, shuffle="batch", seed=4
        )
        via_gather = drain_gather(b)
        b.close()
        assert via_gather == via_bytes
        assert sorted(via_gather) == sorted(records)
        # spans of 10 keep file order internally; the remainder (3
        # records) reads last
        pos = {r: i for i, r in enumerate(records)}
        order = [pos[r] for r in via_gather]
        for s in range(0, 80, 10):
            span = order[s : s + 10]
            assert span == list(range(span[0], span[0] + 10)), s
        assert order[-3:] == [80, 81, 82]


def test_record_mode_resumes_at_any_position():
    """Record mode keeps its resume-anywhere contract on the windowed
    path: skip_records slices the shard-wide window, never replays."""
    records = records_of(101)
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records)
        s = IndexedRecordIOSplitter(
            p, idx, 0, 1, batch_size=7, shuffle="record", seed=9, epoch=1
        )
        full = drain_records(s)
        s.close()
        for skip in (1, 37, 100, 101):
            s = IndexedRecordIOSplitter(
                p, idx, 0, 1, batch_size=7, shuffle="record", seed=9,
                epoch=1, skip_records=skip,
            )
            assert drain_records(s) == full[skip:], skip
            assert s.records_consumed == len(records), skip
            s.close()


def test_gather_beats_legacy_per_record_loop():
    """Bench invariant (tier-1-safe): on a small synthetic shard the
    gather path must beat the legacy per-record seek loop — the 13x
    shuffled-read wall of the pre-PR-1 chip runs cannot silently come
    back. Generous
    margin: the gap is >10x on every host measured; 1.5x catches a
    dead fast path without flaking on a loaded CI box."""
    records = [bytes([i % 251]) * 120 for i in range(20000)]
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records)

        def timed(**kw):
            t0 = time.perf_counter()
            s = IndexedRecordIOSplitter(
                p, idx, 0, 1, batch_size=4096, shuffle="record", seed=3,
                **kw,
            )
            n = 0
            while True:
                chunk = s.next_batch_ex(4096)
                if chunk is None:
                    break
                n += 1
            dt = time.perf_counter() - t0
            s.close()
            return dt

        legacy = timed(legacy_shuffle=True)
        gather = timed()
        assert gather * 1.5 < legacy, (gather, legacy)


def test_gather_counters_mirrored_into_telemetry():
    from dmlc_core_tpu.telemetry import default_registry

    reg = default_registry()
    before_b = reg.counter("io.split.gather_batches").value()
    before_by = reg.counter("io.split.gather_bytes").value()
    records = records_of(50)
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records)
        s = IndexedRecordIOSplitter(
            p, idx, 0, 1, batch_size=8, shuffle="record", seed=1
        )
        drain_gather(s)
        stats = s.io_stats()
        s.close()
        nbytes = os.path.getsize(p)
    assert stats["gather_batches"] > 0
    assert stats["gather_bytes"] == nbytes
    assert (
        reg.counter("io.split.gather_batches").value() - before_b
        == stats["gather_batches"]
    )
    assert (
        reg.counter("io.split.gather_bytes").value() - before_by
        == stats["gather_bytes"]
    )


def test_gather_needs_windowed_mode():
    records = records_of(10)
    with TemporaryDirectory() as tmp:
        p, idx = make_indexed_rec(tmp.path, records)
        s = IndexedRecordIOSplitter(p, idx, 0, 1, batch_size=4)
        assert not s.supports_gather()
        with pytest.raises(Error, match="windowed shuffle"):
            s.next_gather_batch(4)
        s.close()
        s = IndexedRecordIOSplitter(
            p, idx, 0, 1, batch_size=4, shuffle="record",
            legacy_shuffle=True,
        )
        assert not s.supports_gather()
        s.close()


@pytest.mark.parametrize("codec", (None, "zlib"))
@pytest.mark.parametrize("mode", ("record", "batch", "window"))
def test_parallel_fetch_order_identical_to_serial(
    mode, codec, tmp_path, monkeypatch
):
    """ISSUE 9: the concurrent span fetcher on a remote-shaped source
    emits the exact local serial-path epoch order and bytes through the
    zero-copy gather emission, for every shuffle mode on both
    containers — completion-order arrival must never leak into
    emission order."""
    monkeypatch.setenv("DMLC_FETCH_THREADS", "4")  # env-proof parallel
    records = records_of(130, tag="pl")
    p, idx = make_indexed_rec(str(tmp_path), records, codec=codec)
    kw = dict(batch_size=9, shuffle=mode, seed=7, window=28, merge_gap=0)
    ref = IndexedRecordIOSplitter(p, idx, 0, 1, **kw)
    want = drain_gather(ref)
    ref.close()
    s = IndexedRecordIOSplitter(f"fault://seed=5{p}", idx, 0, 1, **kw)
    got = drain_gather(s)
    stats = s.io_stats()
    s.close()
    assert got == want, (mode, codec)
    assert stats["gather_batches"] > 0
    if codec is None:
        # v1 windows plan scattered record spans: the engine must have
        # carried them (zlib block contiguity can collapse to one span)
        assert stats["fetch_spans"] > 0, mode


def test_chaos_gather_identical_to_clean(tmp_path):
    """fault:// chaos with retries > 0: the gather emission heals to
    the exact clean-path order and bytes (record AND window modes)."""
    from dmlc_core_tpu.io.faults import wrap_uri

    records = records_of(90, tag="f")
    p, idx = make_indexed_rec(str(tmp_path), records)
    for mode in ("record", "window"):
        clean = io_split.create(
            f"{p}?index={idx}&shuffle={mode}&seed=6&window=32",
            type="recordio",
        )
        want = drain_gather(clean)
        clean.close()
        uri = wrap_uri(p, "resets=2,short=1,errors=1,seed=11")
        chaotic = io_split.create(
            f"{uri}?index={idx}&shuffle={mode}&seed=6&window=32",
            type="recordio",
        )
        got = drain_gather(chaotic)
        stats = chaotic.io_stats()
        chaotic.close()
        assert got == want, mode
        assert stats["faults_injected"] > 0, mode
        assert stats["retries"] > 0, mode


@pytest.mark.parametrize("codec", (None, "zlib"))
def test_fused_and_generic_batchers_agree_on_gather_order(codec, tmp_path):
    """Staged tensor values: fused gather producer == generic
    parser→FixedShapeBatcher == fused legacy per-record stream, across
    containers (host Batch level; the device golden lives in
    tests/test_staging_sharded.py)."""
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import encode_rows
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream
    from dmlc_core_tpu.data import native
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    if not native.HAS_GATHER_ELL:
        pytest.skip("native gather kernel not loaded")
    n, k = 75, 3
    rng = np.random.default_rng(2)
    blk = RowBlock(
        offset=np.arange(n + 1, dtype=np.int64) * k,
        label=np.arange(n).astype(np.float32),
        index=rng.integers(0, 99, n * k).astype(np.uint32),
        value=rng.normal(size=n * k).astype(np.float32),
    )
    rec = str(tmp_path / f"t{codec}.rec")
    idx = str(tmp_path / f"t{codec}.idx")
    with FileStream(rec, "w") as d, FileStream(idx, "w") as i:
        w = IndexedRecordIOWriter(
            d, i, **({"codec": codec, "block_bytes": 256} if codec else {})
        )
        for payload in encode_rows(blk):
            w.write_record(payload)
    spec = BatchSpec(batch_size=16, layout="ell", max_nnz=k)
    base = f"{rec}?index={idx}&shuffle=record&seed=12"

    def batches(uri, force_generic=False):
        if force_generic:
            from dmlc_core_tpu.data import create_parser
            from dmlc_core_tpu.staging.batcher import FixedShapeBatcher

            parser = create_parser(uri, 0, 1, type="rowrec")
            src = FixedShapeBatcher(spec).batches(iter(parser))
            out = [
                {kk: np.array(v) for kk, v in b.as_dict().items()}
                for b in src
            ]
            parser.close()
            return out
        s = ell_batches(uri, spec)
        out = [
            {kk: np.array(v) for kk, v in b.as_dict().items()} for b in s
        ]
        stats = s.io_stats()
        s.close()
        return out, stats

    fused, stats = batches(base)
    assert stats["gather_batches"] > 0
    assert stats["gather_fallback_batches"] == 0
    legacy, _ = batches(base + "&legacy_shuffle=1")
    generic = batches(base, force_generic=True)
    assert len(fused) == len(legacy) == len(generic) == -(-n // 16)
    for a, b, c in zip(fused, legacy, generic):
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
            np.testing.assert_array_equal(a[key], c[key], err_msg=key)


def test_sharded_fused_gather_coverage(tmp_path):
    """nthread fan-out (ShardedFusedBatches) over a shuffled gather
    stream: disjoint sub-shard permutations, full coverage, summed
    gather counters."""
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import encode_rows
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    n, k = 64, 2
    rng = np.random.default_rng(8)
    blk = RowBlock(
        offset=np.arange(n + 1, dtype=np.int64) * k,
        label=np.arange(n).astype(np.float32),
        index=rng.integers(0, 40, n * k).astype(np.uint32),
        value=rng.normal(size=n * k).astype(np.float32),
    )
    rec = str(tmp_path / "s.rec")
    idx = str(tmp_path / "s.idx")
    with FileStream(rec, "w") as d, FileStream(idx, "w") as i:
        w = IndexedRecordIOWriter(d, i)
        for payload in encode_rows(blk):
            w.write_record(payload)
    spec = BatchSpec(batch_size=8, layout="ell", max_nnz=k)
    s = ell_batches(
        f"{rec}?index={idx}&shuffle=record&seed=3", spec, nthread=2, ring=12
    )
    labels = []
    for b in s:
        labels.extend(np.asarray(b.labels)[: b.n_valid].tolist())
    stats = s.io_stats()
    s.close()
    assert sorted(int(x) for x in labels) == list(range(n))
    assert labels != sorted(labels)  # actually shuffled
    assert stats.get("gather_batches", 0) >= 2  # both sub-shards gathered


def test_gather_numpy_fallback_counts_and_matches(tmp_path, monkeypatch):
    """Stale .so (no gather kernel): the fused consumer re-frames via
    the numpy gather — same staged values, and the emissions are
    COUNTED as fallback batches so the missing fast path is visible in
    io_stats/telemetry."""
    from dmlc_core_tpu.data import native
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import encode_rows
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    if not (native.HAS_ELL and native.HAS_GATHER_ELL):
        pytest.skip("native ELL kernels not loaded")
    n, k = 50, 3
    rng = np.random.default_rng(5)
    blk = RowBlock(
        offset=np.arange(n + 1, dtype=np.int64) * k,
        label=np.arange(n).astype(np.float32),
        index=rng.integers(0, 60, n * k).astype(np.uint32),
        value=rng.normal(size=n * k).astype(np.float32),
    )
    rec = str(tmp_path / "fb.rec")
    idx = str(tmp_path / "fb.idx")
    with FileStream(rec, "w") as d, FileStream(idx, "w") as i:
        w = IndexedRecordIOWriter(d, i)
        for payload in encode_rows(blk):
            w.write_record(payload)
    spec = BatchSpec(batch_size=16, layout="ell", max_nnz=k)
    uri = f"{rec}?index={idx}&shuffle=record&seed=9"

    def collect():
        s = ell_batches(uri, spec)
        out = [
            {kk: np.array(v) for kk, v in b.as_dict().items()} for b in s
        ]
        stats = s.io_stats()
        s.close()
        return out, stats

    ref, fast_stats = collect()
    assert fast_stats["gather_fallback_batches"] == 0
    monkeypatch.setattr(native, "HAS_GATHER_ELL", False)
    got, slow_stats = collect()
    assert slow_stats["gather_fallback_batches"] > 0
    assert slow_stats["gather_batches"] > 0  # views were still handed out
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        for key in b:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
