"""Compressed-block RecordIO: codec registry, block header/crc,
round-trip property tests across every codec × container × read path,
fault-injection chaos, the parallel decode pool and the decoded-block
cache (ISSUE 5 tentpole).

The load-bearing invariant everywhere: the DECODED record stream is
byte-identical to what the uncompressed writer emits for the same
records — including records containing the RecordIO magic word (the
multipart escape) — and corruption/missing codecs surface as checked
errors, never garbage records.
"""

import os
import struct

import numpy as np
import pytest

from dmlc_core_tpu.io import codec as codec_mod
from dmlc_core_tpu.io import split as io_split
from dmlc_core_tpu.io.codec import (
    DecodedBlockCache,
    available_codecs,
    decode_block,
    encode_block,
    get_codec,
)
from dmlc_core_tpu.io.recordio import (
    KMAGIC,
    IndexedRecordIOWriter,
    RecordIOChunkReader,
    RecordIOReader,
    chunk_has_compressed,
    decode_chunk,
)
from dmlc_core_tpu.io.stream import FileStream
from dmlc_core_tpu.utils.logging import Error

MAGIC = struct.pack("<I", KMAGIC)

# every codec this host has; raw/zlib/gzip are stdlib-backed and always
# present, zstd/lz4 join when their packages are installed
CODECS = available_codecs()


def _records(n=300, seed=0):
    """Mixed-size records, ~1 in 9 carrying an ALIGNED magic word so
    multipart escape chains occur inside block payloads."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        body = bytearray(rng.bytes(16 + (i * 7) % 53))
        if i % 9 == 0:
            body[4:8] = MAGIC
        if i % 31 == 0:
            body[0:4] = MAGIC  # magic at offset 0
        out.append(bytes(body) + str(i).encode())
    out[0] = b""  # empty record edge case
    return out


RECORDS = _records()


def _write(tmp_path, codec, records=RECORDS, block_bytes=768, name=None):
    rec = str(tmp_path / (name or f"d_{codec or 'v1'}.rec"))
    idx = rec + ".idx"
    with FileStream(rec, "w") as f, FileStream(idx, "w") as fi:
        w = IndexedRecordIOWriter(
            f, fi, codec=codec, block_bytes=block_bytes
        )
        for r in records:
            w.write_record(r)
        w.flush()
    return rec, idx


# -- registry ----------------------------------------------------------------
def test_registry_stdlib_codecs_always_available():
    assert {"raw", "zlib", "gzip"} <= set(CODECS)
    for name in CODECS:
        c = get_codec(name)
        assert get_codec(c.codec_id) is c and get_codec(c) is c


def test_registry_unknown_and_unavailable_fail_loudly():
    with pytest.raises(Error, match="unknown codec"):
        get_codec("snappy")
    with pytest.raises(Error, match="codec id"):
        get_codec(250)
    for name in ("zstd", "lz4"):
        if name not in CODECS:
            with pytest.raises(Error, match="unavailable"):
                get_codec(name)


@pytest.mark.parametrize("codec", CODECS)
def test_codec_compress_roundtrip_and_levels(codec):
    c = get_codec(codec)
    data = b"abc" * 5000 + os.urandom(256)
    assert c.decompress(c.compress(data), len(data)) == data
    if c.default_level is not None:
        small = c.compress(data, c.default_level)
        assert c.decompress(small, len(data)) == data


@pytest.mark.parametrize("codec", CODECS)
def test_codec_streaming_matches_whole_buffer(codec):
    c = get_codec(codec)
    chunks = [os.urandom(100), b"x" * 4096, b"", b"tail"]
    whole = b"".join(chunks)
    streamed = b"".join(c.compress_stream(iter(chunks)))
    assert b"".join(c.decompress_stream([streamed])) == whole
    # chunked decompress too
    halves = [streamed[: len(streamed) // 2], streamed[len(streamed) // 2 :]]
    assert b"".join(c.decompress_stream(halves)) == whole


# -- block header / crc ------------------------------------------------------
def test_block_header_roundtrip_and_corruption():
    raw = b"payload" * 100
    blob = encode_block(raw, 7, "zlib")
    got, n = decode_block(blob)
    assert got == raw and n == 7

    # flip a bit in the compressed payload: either the codec framing or
    # the crc must catch it — checked Error, never silent garbage
    bad = bytearray(blob)
    bad[-3] ^= 0xFF
    with pytest.raises(Error):
        decode_block(bytes(bad))

    # corrupt the stored crc itself: decode succeeds, checksum doesn't
    bad = bytearray(blob)
    bad[12] ^= 0xFF  # crc32 field of the 16-byte header
    with pytest.raises(Error, match="crc"):
        decode_block(bytes(bad))

    with pytest.raises(Error, match="shorter"):
        decode_block(blob[:10])
    with pytest.raises(Error, match="version"):
        decode_block(bytes([blob[0], 99]) + blob[2:])


def test_truncated_block_detected():
    raw = os.urandom(4096)
    blob = encode_block(raw, 1, "raw")
    with pytest.raises(Error):
        decode_block(blob[:-100])


# -- round-trip property: codec × container × read path ----------------------
@pytest.mark.parametrize("codec", CODECS)
def test_plain_container_all_read_paths(codec, tmp_path):
    rec, _ = _write(tmp_path, codec)
    data = open(rec, "rb").read()
    # stream reader decodes transparently
    with FileStream(rec, "r") as f:
        assert list(RecordIOReader(f)) == RECORDS
    # decode_chunk + sub-split chunk reader (the thread fan-out path):
    # every (part, num_parts) covers each record exactly once
    assert chunk_has_compressed(data)
    dec = decode_chunk(data)
    for nparts in (1, 2, 3, 7):
        got = []
        for p in range(nparts):
            got.extend(bytes(r) for r in RecordIOChunkReader(dec, p, nparts))
        assert got == RECORDS, nparts
    # sharded byte-range splitter (magic scan over compressed heads)
    for nparts in (1, 3):
        got = []
        for p in range(nparts):
            sp = io_split.create(rec, p, nparts, type="recordio",
                                 threaded=False)
            sp.hint_chunk_size(512)  # many tiny chunks
            got.extend(bytes(r) for r in sp)
            sp.close()
        assert sorted(got) == sorted(RECORDS), nparts


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("shuffle", ("0", "record", "batch", "window"))
def test_indexed_container_all_modes_sharded(codec, shuffle, tmp_path):
    rec, idx = _write(tmp_path, codec)
    for nparts in (1, 2):
        got = []
        for p in range(nparts):
            sp = io_split.create(
                f"{rec}?index={idx}&shuffle={shuffle}&seed=5"
                f"&window=64&merge_gap=96&batch_size=32",
                p, nparts, type="recordio", threaded=False,
            )
            got.extend(bytes(r) for r in sp)
            sp.close()
        assert sorted(got) == sorted(RECORDS), (shuffle, nparts)


def test_window_order_identical_to_uncompressed_record_shuffle(tmp_path):
    """Same (seed, epoch) ⇒ the compressed window shuffle must emit the
    EXACT v1 per-record permutation order — compression changes how the
    bytes travel, never the order they leave."""
    v1rec, v1idx = _write(tmp_path, None)
    rec, idx = _write(tmp_path, "zlib")

    def stream(rc, ix, mode):
        sp = io_split.create(
            f"{rc}?index={ix}&shuffle={mode}&seed=11&window=64",
            0, 1, type="recordio", threaded=False,
        )
        out = [bytes(r) for r in sp]
        sp.close()
        return out

    want = stream(v1rec, v1idx, "record")
    assert stream(rec, idx, "window") == want
    assert stream(rec, idx, "record") == want


def test_uncompressed_files_read_bit_identically(tmp_path):
    """Format safety: the v1 path through the compressed-aware readers
    is bit-identical — decode_chunk passes a v1 chunk through as the
    SAME object, and the sidecar keeps plain offsets."""
    rec, idx = _write(tmp_path, None)
    data = open(rec, "rb").read()
    assert not chunk_has_compressed(data)
    assert decode_chunk(data) is data
    assert ":" not in open(idx).read()
    with FileStream(rec, "r") as f:
        assert list(RecordIOReader(f, allow_compressed=False)) == RECORDS


def test_threaded_and_cached_wrappers_over_compressed(tmp_path):
    """The prefetch thread pulls chunks that decode on the producer
    side (network/decode overlap), and a #cachefile caches the DECODED
    chunks — replay costs no second decompression."""
    rec, _ = _write(tmp_path, "zlib")
    sp = io_split.create(rec, 0, 1, type="recordio")  # threaded default
    assert sorted(bytes(r) for r in sp) == sorted(RECORDS)
    sp.close()

    cache = str(tmp_path / "chunks.cache")
    sp = io_split.create(rec + "#" + cache, 0, 1, type="recordio")
    first = [bytes(r) for r in sp]
    sp.before_first()  # replays from the cache file
    second = [bytes(r) for r in sp]
    sp.close()
    assert first == second and sorted(first) == sorted(RECORDS)


# -- loud failure on old readers ---------------------------------------------
def test_v1_only_readers_reject_compressed_blocks(tmp_path):
    rec, idx = _write(tmp_path, "zlib")
    data = open(rec, "rb").read()
    with FileStream(rec, "r") as f:
        with pytest.raises(Error, match="v1-only"):
            RecordIOReader(f, allow_compressed=False).next_record()
    with pytest.raises(Error, match="decode_chunk"):
        RecordIOChunkReader(data, 0, 1).next_record()
    # a v1 index parser chokes on the block:in-offset column — loudly
    with pytest.raises(ValueError):
        [int(tok) for tok in open(idx).read().split()]


def test_compressed_index_requires_consistency(tmp_path):
    rec, idx = _write(tmp_path, "zlib")
    broken = str(tmp_path / "mixed.idx")
    lines = open(idx).read().splitlines()
    lines[1] = "1\t64"  # a v1 offset amid block:in pairs
    open(broken, "w").write("\n".join(lines) + "\n")
    with pytest.raises(Error, match="mixes"):
        io_split.create(f"{rec}?index={broken}", 0, 1, type="recordio",
                        threaded=False)


# -- corruption through the read path ----------------------------------------
def test_corrupt_block_surfaces_checked_error(tmp_path):
    rec, idx = _write(tmp_path, "zlib")
    blob = bytearray(open(rec, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # inside some block's compressed bytes
    bad = str(tmp_path / "bad.rec")
    open(bad, "wb").write(bytes(blob))
    sp = io_split.create(bad, 0, 1, type="recordio", threaded=False)
    with pytest.raises(Error):
        list(sp)
    sp.close()


# -- fault-injection chaos (PR 2 suite over compressed spans) ----------------
@pytest.mark.parametrize("shuffle", ("0", "window"))
def test_fault_injected_reads_heal_byte_identical(shuffle, tmp_path):
    from dmlc_core_tpu.io.faults import wrap_uri

    rec, idx = _write(tmp_path, "zlib")
    sugar = f"?index={idx}&shuffle={shuffle}&seed=2&window=64"

    def run(uri):
        codec_mod.default_decode_cache().clear()
        sp = io_split.create(uri + sugar, 0, 1, type="recordio",
                             threaded=False)
        out = [bytes(r) for r in sp]
        stats = sp.io_stats()
        sp.close()
        return out, stats

    clean, _ = run(rec)
    chaos, stats = run(wrap_uri(rec, "resets=2,short=2,errors=1,seed=7"))
    assert chaos == clean == [
        r for r in clean
    ] and sorted(clean) == sorted(RECORDS)
    assert stats["faults_injected"] > 0 and stats["retries"] > 0


def test_latency_spike_schedule_decodes_identically(tmp_path):
    """The fault-free latency-spike schedule (pure delay, no error):
    the codec path must return identical bytes (the codec wins when the
    link, not the CPU, is the bottleneck)."""
    from dmlc_core_tpu.io.faults import wrap_uri

    rec, _ = _write(tmp_path, "zlib")
    sp = io_split.create(
        wrap_uri(rec, "latency_ms=1,spikes=2,seed=3"), 0, 1,
        type="recordio", threaded=False,
    )
    assert sorted(bytes(r) for r in sp) == sorted(RECORDS)
    sp.close()


# -- decoded-block cache ------------------------------------------------------
def test_decoded_block_cache_lru_bounds():
    c = DecodedBlockCache(100)
    c.put("a", b"x" * 40)
    c.put("b", b"y" * 40)
    assert c.get("a") == b"x" * 40
    c.put("c", b"z" * 40)  # evicts LRU ("b" — "a" was touched)
    assert c.get("b") is None and c.get("a") is not None
    assert c.nbytes <= 100
    c.put("big", b"q" * 101)  # larger than the budget: not retained
    assert c.get("big") is None
    c.clear()
    assert len(c) == 0 and c.nbytes == 0


def test_second_epoch_serves_from_cache(tmp_path):
    """Acceptance: decoded-block cache hit rate > 0.9 on a second epoch
    of shuffle='window' over the same shard."""
    rec, idx = _write(tmp_path, "zlib")
    codec_mod.default_decode_cache().clear()
    sp = io_split.create(
        f"{rec}?index={idx}&shuffle=window&seed=4&window=64",
        0, 1, type="recordio", threaded=False,
    )
    e1 = [bytes(r) for r in sp]
    h1, m1 = sp.decode_cache_hits, sp.decode_cache_misses
    assert m1 > 0  # first epoch decoded blocks
    sp.before_first()
    e2 = [bytes(r) for r in sp]
    h2 = sp.decode_cache_hits - h1
    m2 = sp.decode_cache_misses - m1
    st = sp.io_stats()
    sp.close()
    assert sorted(e1) == sorted(e2) == sorted(RECORDS)
    assert h2 / max(h2 + m2, 1) > 0.9
    assert st["decode_cache_hits"] == sp.decode_cache_hits


def test_telemetry_counters_tick(tmp_path):
    from dmlc_core_tpu.telemetry import default_registry

    reg = default_registry()
    raw0 = reg.counter("io.codec.bytes_raw").value()
    comp0 = reg.counter("io.codec.bytes_compressed").value()
    dec0 = reg.histogram("io.codec.decode_seconds").snapshot()["count"]
    rec, _ = _write(tmp_path, "zlib")
    with FileStream(rec, "r") as f:
        assert list(RecordIOReader(f)) == RECORDS
    assert reg.counter("io.codec.bytes_raw").value() > raw0
    assert reg.counter("io.codec.bytes_compressed").value() > comp0
    assert (
        reg.histogram("io.codec.decode_seconds").snapshot()["count"] > dec0
    )


# -- generic parser over compressed rowrec ------------------------------------
def test_rowrec_codec_roundtrip(tmp_path):
    from dmlc_core_tpu.data import create_row_block_iter
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec

    rng = np.random.default_rng(1)
    n, k = 64, 3
    blk = RowBlock(
        offset=np.arange(n + 1, dtype=np.int64) * k,
        label=rng.integers(0, 2, n).astype(np.float32),
        index=rng.integers(0, 100, n * k).astype(np.uint32),
        value=rng.normal(size=n * k).astype(np.float32),
    )
    rec = str(tmp_path / "rows.rec")
    with FileStream(rec, "w") as f:
        assert write_rowrec(f, [blk], codec="zlib") == n
    labels = []
    vals = []
    for b in create_row_block_iter(rec + "?format=rowrec"):
        labels.extend(np.asarray(b.label).tolist())
        vals.extend(np.asarray(b.value).tolist())
    assert labels == blk.label.tolist()
    np.testing.assert_array_equal(np.asarray(vals, np.float32), blk.value)


# -- resume / skip_records on compressed windows ------------------------------
def test_skip_records_window_boundary_compressed(tmp_path):
    rec, idx = _write(tmp_path, "zlib")
    full = io_split.create(
        f"{rec}?index={idx}&shuffle=window&seed=6&window=50",
        0, 1, type="recordio", threaded=False,
    )
    want = [bytes(r) for r in full]
    full.close()
    resumed = io_split.create(
        f"{rec}?index={idx}&shuffle=window&seed=6&window=50"
        f"&skip_records=100",
        0, 1, type="recordio", threaded=False,
    )
    got = [bytes(r) for r in resumed]
    resumed.close()
    assert got == want[100:]
