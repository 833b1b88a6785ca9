"""The robustness subsystem: RetryPolicy semantics, the fault://
injection filesystem, and the chaos round-trip acceptance — a golden
RecordIO dataset read through seeded resets + 5xx + short reads must be
byte-identical to the clean read, on both the sequential and the
windowed-shuffle paths, with the healed retries visible in io_stats().
"""

import random

import numpy as np
import pytest

from dmlc_core_tpu.io import retry
from dmlc_core_tpu.io import split as io_split
from dmlc_core_tpu.io.faults import FaultSpec, wrap_uri
from dmlc_core_tpu.io.filesystem import FileSystem, MemoryFileSystem
from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
from dmlc_core_tpu.io.retry import (
    HttpError,
    RetryingReadStream,
    RetryPolicy,
    is_transient,
)
from dmlc_core_tpu.io.stream import FileStream, MemoryStream, Stream
from dmlc_core_tpu.utils.logging import Error


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    """Policies read env at construction: run every retry at test speed."""
    monkeypatch.setenv("DMLC_RETRY_BASE_SECS", "0.001")
    monkeypatch.setenv("DMLC_RETRY_CAP_SECS", "0.01")


# -- classifier ---------------------------------------------------------------


def test_transient_classifier():
    import http.client
    import urllib.error

    assert is_transient(HttpError("m", status=500))
    assert is_transient(HttpError("m", status=503))
    assert is_transient(HttpError("m", status=429))
    assert is_transient(HttpError("m", status=408))
    assert not is_transient(HttpError("m", status=404))
    assert not is_transient(HttpError("m", status=403))
    assert is_transient(urllib.error.URLError(ConnectionResetError()))
    assert is_transient(urllib.error.URLError(TimeoutError()))
    assert not is_transient(urllib.error.URLError("bad url"))
    assert is_transient(http.client.IncompleteRead(b"xx"))
    assert is_transient(ConnectionResetError())
    assert is_transient(BrokenPipeError())
    assert is_transient(TimeoutError())
    assert not is_transient(ValueError("nope"))
    assert not is_transient(KeyError("nope"))


# -- RetryPolicy --------------------------------------------------------------


def test_policy_retries_then_succeeds():
    sleeps = []
    p = RetryPolicy(
        max_attempts=4, base_secs=0.01, cap_secs=0.05, budget_secs=10,
        sleep=sleeps.append, rng=random.Random(7),
    )
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("boom")
        return "ok"

    assert p.run(flaky) == "ok"
    assert p.retries == 2 and len(sleeps) == 2
    # decorrelated jitter stays within [base, cap]
    assert all(0.01 <= s <= 0.05 for s in sleeps)


def test_policy_exhaustion_reraises_last_error():
    p = RetryPolicy(
        max_attempts=3, base_secs=0.001, budget_secs=10, sleep=lambda d: None
    )
    boom = ConnectionResetError("the last one")
    with pytest.raises(ConnectionResetError, match="the last one"):
        p.run(lambda: (_ for _ in ()).throw(boom))
    assert p.retries == 2  # attempts-1 retries, then re-raise


def test_policy_nontransient_raises_immediately():
    p = RetryPolicy(max_attempts=5, sleep=lambda d: None)
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        p.run(bad)
    assert len(calls) == 1 and p.retries == 0


def test_policy_budget_bounds_total_backoff():
    """The per-stream cumulative budget caps the SUM of sleeps across
    operations; the would-be over-budget retry re-raises the cause."""
    sleeps = []
    p = RetryPolicy(
        max_attempts=100, base_secs=0.04, cap_secs=0.05, budget_secs=0.1,
        sleep=sleeps.append, rng=random.Random(3),
    )
    with pytest.raises(ConnectionResetError):
        p.run(lambda: (_ for _ in ()).throw(ConnectionResetError("x")))
    assert sum(sleeps) <= 0.1
    assert p.backoff_secs <= 0.1


def test_policy_counters_feed_global_stats():
    before = retry.stats()
    p = RetryPolicy(max_attempts=2, base_secs=0.001, sleep=lambda d: None)
    with pytest.raises(ConnectionResetError):
        p.run(lambda: (_ for _ in ()).throw(ConnectionResetError()))
    d = retry.stats_delta(before)
    assert d["retries"] == 1 and d["backoff_secs"] > 0


# -- RetryingReadStream -------------------------------------------------------


class _ExplodingStream(MemoryStream):
    """Seekable stream raising scripted exceptions at given GLOBAL read
    ordinals (the counter is shared across reopens, like a schedule)."""

    def __init__(self, data, explode_at, counter):
        super().__init__(data)
        self.explode_at = explode_at
        self.counter = counter

    def read(self, n=-1):
        self.counter[0] += 1
        if self.counter[0] in self.explode_at:
            raise ConnectionResetError("mid-read reset")
        return super().read(min(n, 10) if n > 0 else 10)


def test_retrying_read_stream_resumes_at_offset():
    data = bytes(range(200))
    streams = []
    counter = [0]

    def open_fn():
        s = _ExplodingStream(data, explode_at={3, 7}, counter=counter)
        streams.append(s)
        return s

    r = RetryingReadStream(open_fn, policy=RetryPolicy(sleep=lambda d: None))
    out = r.read(-1)
    assert out == data, "healed read must be byte-identical"
    assert len(streams) == 3  # two resets -> two reopens
    r.close()


def test_retrying_read_stream_open_failures_then_success():
    attempts = []

    def open_fn():
        attempts.append(1)
        if len(attempts) < 3:
            raise HttpError("GET x -> HTTP 503: busy", status=503)
        return MemoryStream(b"hello")

    r = RetryingReadStream(
        open_fn,
        policy=RetryPolicy(max_attempts=4, sleep=lambda d: None),
    )
    assert r.read(-1) == b"hello"


# -- fault:// unit behavior ---------------------------------------------------


def test_fault_spec_rejects_unknown_options():
    with pytest.raises(Error, match="unknown fault"):
        FaultSpec({"tyop": "1"})
    with pytest.raises(Error, match="not an integer"):
        FaultSpec({"resets": "many"})


def test_wrap_uri_forms():
    assert wrap_uri("/d/x.rec", "resets=2,seed=7") == (
        "fault://resets=2,seed=7/d/x.rec"
    )
    assert wrap_uri("file:///d/x.rec", "resets=1") == (
        "fault://resets=1/d/x.rec"
    )
    assert wrap_uri("/d/x.rec", "") == "/d/x.rec"
    with pytest.raises(Error, match="only wraps local paths"):
        wrap_uri("s3://b/k", "resets=1")


def test_fault_passthrough_and_stat_list(tmp_path):
    p = tmp_path / "plain.bin"
    p.write_bytes(b"abcdef" * 100)
    uri = f"fault://seed=1{p}"
    fs = FileSystem.get_instance(uri)
    info = fs.get_path_info(uri)
    assert info.size == 600 and info.type == "file"
    listing = fs.list_directory(f"fault://seed=1{tmp_path}")
    assert any(f.path == uri for f in listing)
    s = fs.open(uri, "r")
    assert s.read(-1) == b"abcdef" * 100
    s.close()


def test_fault_open_errors_then_success(tmp_path):
    p = tmp_path / "o.bin"
    p.write_bytes(b"payload")
    before = retry.stats()
    s = Stream.create(f"fault:///{str(p).lstrip('/')}?errors=2&seed=3", "r")
    assert s.read(-1) == b"payload"
    s.close()
    d = retry.stats_delta(before)
    assert d["faults_injected"] == 2 and d["retries"] == 2


def test_fault_exhausts_policy_past_attempt_cap(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_RETRY_ATTEMPTS", "2")
    p = tmp_path / "o2.bin"
    p.write_bytes(b"payload")
    with pytest.raises(HttpError, match="HTTP 503"):
        Stream.create(f"fault://errors=5,seed=3{p}", "r").read(1)


def test_fault_truncated_write_raises(tmp_path):
    p = tmp_path / "w.bin"
    w = Stream.create(f"fault://wresets=1,seed=5{p}", "w")
    w.write(b"A" * 100)
    with pytest.raises(ConnectionResetError):
        for _ in range(50):
            w.write(b"B" * 100)
    # the truncation landed a partial object — exactly the crash shape
    # _write_atomic's verify-then-commit must keep away from final keys
    assert 0 < len(p.read_bytes()) < 5100


def test_fault_mem_inner_roundtrip():
    MemoryFileSystem._store["mem://bkt/obj"] = b"mem-bytes"
    try:
        s = Stream.create("fault://inner=mem,seed=2/bkt/obj", "r")
        assert s.read(-1) == b"mem-bytes"
        s.close()
    finally:
        MemoryFileSystem.reset()


# -- checkpoint crash consistency over fault:// -------------------------------


def test_write_atomic_crash_never_exposes_final_key(tmp_path):
    """A truncated write mid-save must leave the FINAL uri absent (only
    .tmp debris) — the crash-consistency contract of _write_atomic's
    remote path."""
    from dmlc_core_tpu.checkpoint import _write_atomic, load_pytree

    base = f"fault://wresets=1,seed=11{tmp_path}/ck.bin"
    tree = {"w": np.zeros(4096, dtype=np.float64)}  # big enough to split
    with pytest.raises(ConnectionResetError):
        _write_atomic(base, tree)
    assert not (tmp_path / "ck.bin").exists()
    # clean save through the same (now fault-free) path commits
    ok = f"fault://seed=11{tmp_path}/ck.bin"
    _write_atomic(ok, {"w": np.arange(8)})
    out = load_pytree(str(tmp_path / "ck.bin"))
    np.testing.assert_array_equal(out["w"], np.arange(8))
    assert not (tmp_path / "ck.bin.tmp").exists(), "tmp debris after commit"


# -- chaos round-trip acceptance ----------------------------------------------


@pytest.fixture
def golden_rec(tmp_path):
    """Golden rowrec-agnostic RecordIO dataset + count index."""
    rng = np.random.default_rng(3)
    recs = [
        rng.integers(0, 255, int(rng.integers(20, 200)), dtype=np.uint8)
        .tobytes()
        for _ in range(400)
    ]
    path = str(tmp_path / "golden.rec")
    idx = path + ".idx"
    with FileStream(path, "w") as f, FileStream(idx, "w") as fi:
        w = IndexedRecordIOWriter(f, fi)
        for i, r in enumerate(recs):
            w.write_record(r, i)
    return path, idx, recs


CHAOS = "resets=3,short=4,errors=2,seed=7"


def test_chaos_sequential_read_byte_identical(golden_rec):
    path, _idx, recs = golden_rec
    s = io_split.create(path, type="recordio", threaded=False)
    clean = [bytes(r) for r in s]
    s.close()
    assert clean == recs

    before = retry.stats()
    s = io_split.create(wrap_uri(path, CHAOS), type="recordio", threaded=False)
    chaos = [bytes(r) for r in s]
    stats = s.io_stats()
    s.close()
    assert chaos == recs, "chaos read diverged from the clean read"
    assert stats["retries"] > 0
    assert stats["faults_injected"] > 0
    assert stats["backoff_secs"] > 0
    assert retry.stats_delta(before)["retries"] == stats["retries"]


def test_chaos_windowed_shuffle_byte_identical(golden_rec):
    """The same seeded permutation must come back record-for-record
    identical through injected resets/5xx/short reads — order included
    (the windowed path re-reads coalesced spans via seek+read, so a
    mis-resumed offset would scramble records, not just corrupt one)."""
    path, idx, _recs = golden_rec
    sugar = f"?index={idx}&shuffle=window&window=64&merge_gap=4096&seed=5"
    s = io_split.create(path + sugar, type="recordio", threaded=False)
    clean = [bytes(r) for r in s]
    s.close()
    assert len(clean) == 400

    s = io_split.create(
        wrap_uri(path, CHAOS) + sugar, type="recordio", threaded=False
    )
    chaos = [bytes(r) for r in s]
    stats = s.io_stats()
    s.close()
    assert chaos == clean, "chaos windowed read diverged (rows or order)"
    assert stats["mode"] == "window"
    assert stats["retries"] > 0
    assert stats["faults_injected"] > 0


@pytest.mark.parametrize("codec", (None, "zlib"))
@pytest.mark.parametrize("mode", ("record", "batch", "window"))
def test_chaos_parallel_fetch_byte_identical(mode, codec, tmp_path):
    """ISSUE 9 acceptance: the CONCURRENT span fetcher under fault://
    mid-read resets + latency spikes + short reads heals to the exact
    clean serial-path order and bytes, across all three shuffle modes
    on v1 AND zlib containers, with retries > 0 — parallelism must
    change when bytes arrive, never what they are."""
    from tests.test_split_gather import (
        drain_records,
        make_indexed_rec,
        records_of,
    )

    records = records_of(110, tag="pf")
    p, idx = make_indexed_rec(str(tmp_path), records, codec=codec)
    sugar = dict(
        shuffle=mode, seed=8, window=24, merge_gap=0, batch_size=8
    )
    clean = io_split.IndexedRecordIOSplitter(p, idx, 0, 1, **sugar)
    want = drain_records(clean)
    clean.close()
    chaos_uri = wrap_uri(
        p, "resets=2,short=2,latency_ms=2,spikes=3,errors=1,seed=17"
    )
    chaotic = io_split.IndexedRecordIOSplitter(
        chaos_uri, idx, 0, 1, **sugar
    )
    got = drain_records(chaotic)
    stats = chaotic.io_stats()
    chaotic.close()
    assert got == want, (mode, codec)
    assert stats["faults_injected"] > 0, (mode, codec)
    assert stats["retries"] > 0, (mode, codec)
    # the parallel engine actually carried the window loads (fault://
    # is remote-shaped, so the fetcher engages unless env pinned it
    # off). v1 only: the zlib corpus here is small enough that a
    # window's missing BLOCKS form one contiguous run, which correctly
    # collapses to a single sequential span and skips the engine — the
    # zlib engagement case is pinned by
    # test_chaos_parallel_equals_serial_baseline below.
    if codec is None and io_split._spanfetch.fetch_threads() > 1:
        assert stats["fetch_spans"] > 0, (mode, codec)


def test_chaos_parallel_equals_serial_baseline(tmp_path, monkeypatch):
    """The DMLC_FETCH_THREADS=1 serial baseline and the concurrent
    fetch produce identical bytes UNDER THE SAME chaos spec."""
    from tests.test_split_gather import (
        drain_records,
        make_indexed_rec,
        records_of,
    )

    from dmlc_core_tpu.io import codec as io_codec

    records = records_of(90, tag="sb")
    p, idx = make_indexed_rec(str(tmp_path), records, codec="zlib")
    uri = wrap_uri(p, "resets=1,short=2,seed=23")
    kw = dict(shuffle="window", seed=4, window=16, merge_gap=0)

    def private_ctx():
        # a per-drain decode context: the process-global decoded-block
        # LRU would serve the second drain from memory and the fetcher
        # would never read a byte
        return io_codec.DecodeContext(
            cache=io_codec.DecodedBlockCache(64 << 20), shared=None
        )

    monkeypatch.setenv("DMLC_FETCH_THREADS", "1")
    serial = io_split.IndexedRecordIOSplitter(
        uri, idx, 0, 1, decode_ctx=private_ctx(), **kw
    )
    want = drain_records(serial)
    serial.close()
    monkeypatch.setenv("DMLC_FETCH_THREADS", "6")
    parallel = io_split.IndexedRecordIOSplitter(
        uri, idx, 0, 1, decode_ctx=private_ctx(), **kw
    )
    got = drain_records(parallel)
    stats = parallel.io_stats()
    parallel.close()
    assert got == want
    assert stats["fetch_spans"] > 0


def test_chaos_query_form_equivalent(golden_rec):
    """The query-param grammar drives the same schedule for direct
    opens (Stream.create passes the full URI to the filesystem)."""
    path, _idx, _recs = golden_rec
    clean = open(path, "rb").read()
    before = retry.stats()
    s = Stream.create(f"fault://{path}?resets=2&seed=9", "r")
    assert s.read(-1) == clean
    s.close()
    assert retry.stats_delta(before)["faults_injected"] == 2


def test_chaos_through_ell_batches_io_stats(golden_rec, tmp_path):
    """The io_stats plumbing end to end: a rowrec dataset staged through
    the fused/generic producer over fault:// surfaces the retry counters
    at the stream level (split -> producer -> bench hook)."""
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    n, k = 256, 4
    rng = np.random.default_rng(5)
    blk = RowBlock(
        offset=np.arange(0, (n + 1) * k, k, dtype=np.int64),
        label=rng.normal(size=n).astype(np.float32),
        index=rng.integers(0, 50, n * k).astype(np.uint32),
        value=rng.normal(size=n * k).astype(np.float32),
    )
    rec = str(tmp_path / "rows.rec")
    with FileStream(rec, "w") as f:
        write_rowrec(f, [blk])
    spec = BatchSpec(batch_size=64, layout="ell", max_nnz=k)

    stream = ell_batches(rec, spec)
    clean = [np.array(b.values) for b in stream]
    stream.close()

    # cap=512: enough read ordinals over the ~13KB file for the
    # scheduled events to land before EOF
    stream = ell_batches(wrap_uri(rec, "resets=2,short=2,seed=13,cap=512"), spec)
    chaos = [np.array(b.values) for b in stream]
    stats = stream.io_stats()
    stream.close()
    assert len(chaos) == len(clean)
    for a, b in zip(clean, chaos):
        np.testing.assert_array_equal(a, b)
    assert stats is not None and stats["retries"] > 0
