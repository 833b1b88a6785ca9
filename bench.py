"""Benchmark: both BASELINE.md north stars, staged end-to-end into HBM.

1. HIGGS-like libsvm → fused native parse→dense-batch kernel → async
   device_put (``higgs_staged_rows_per_sec``, the headline metric).
2. Criteo-like RecordIO (rowrec binary sparse rows, 13 dense + 26
   categorical features) → fused native frame-scan→ELL kernel →
   async device_put (``recordio_staged_rows_per_sec`` +
   ``recordio_staged_mb_per_sec``).

Prints ONE JSON line:

    {"metric": "higgs_staged_rows_per_sec", "value": N,
     "unit": "rows/sec", "vs_baseline": N / 1_000_000,
     "f32_rows_per_sec": N, "recordio_staged_rows_per_sec": N, ...}

vs_baseline is against the 1M rows/sec target (the reference publishes no
numbers of its own — SURVEY §6). Headline numbers stage feature values as
float16 (halves infeed DMA; labels/weights stay f32); float32 numbers are
reported alongside so dtype choices stay visible round over round.

Run on the TPU host as-is (default jax device). Synthetic data is cached
under /tmp between runs. Use BENCH_ROWS / BENCH_EPOCHS to resize.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from statistics import median

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", "400000"))
N_FEATURES = 28  # HIGGS
EPOCHS = int(os.environ.get("BENCH_EPOCHS", "3"))
# 32768: the largest per-DMA batch of the pre-PR-1 sweeps (8192, 16384
# and 32768 measured alike then). Not re-measured on the chip host yet.
BATCH = int(os.environ.get("BENCH_BATCH", "32768"))
# producer ring sized for the depth-3 pipeline below INCLUDING the
# sharded fan-out case: ShardedFusedBatches advertises ring-(prefetch+1)
# slots, and StagingPipeline(depth=3, prefetch=2) keeps 8 alive
_RING = 12
# parse fan-out: >1 engages ShardedFusedBatches (threads; native kernels
# release the GIL). Defaults to the USABLE core count (affinity mask and
# cgroup cpu quota aware — utils/cpus.py; a containerized bench must not
# size its pool to a host it can't run on), capped PER STREAM so every
# sub-shard still covers several full batches — otherwise a many-core
# host over-shards the fixed-size data into padded tails and the bench
# measures padding, not throughput. BENCH_NTHREAD then DMLC_PARSE_THREADS
# override.
_nt_env = int(os.environ.get("BENCH_NTHREAD", "0"))


def _nthread_for(rows: int):
    from dmlc_core_tpu.utils.cpus import parse_threads

    nt = _nt_env or parse_threads(max(1, rows // (BATCH * 4)))
    return nt if nt > 1 else None


def _avail_cpus() -> int:
    from dmlc_core_tpu.utils.cpus import available_cpus

    return available_cpus()


DATA = os.environ.get(
    "BENCH_DATA", f"/tmp/dmlc_tpu_bench_higgs_{N_ROWS}.libsvm"
)
# Criteo-like: 13 dense ("integer") + 26 categorical features per row,
# categorical ids hashed into a 1M space (BASELINE.md north star #2)
REC_ROWS = int(os.environ.get("BENCH_REC_ROWS", str(N_ROWS)))
REC_DENSE, REC_CAT, REC_SPACE = 13, 26, 1 << 20
REC_K = REC_DENSE + REC_CAT
REC_DATA = os.environ.get(
    "BENCH_REC_DATA", f"/tmp/dmlc_tpu_bench_criteo_{REC_ROWS}.rec"
)
LIBFM_DATA = os.environ.get(
    "BENCH_LIBFM_DATA", f"/tmp/dmlc_tpu_bench_criteo_{REC_ROWS}.libfm"
)


def ensure_native() -> None:
    """Build/refresh the native core. An unusable native library is a
    bench failure, not a silent 5x-slower fallback (VERDICT r1 weak #3);
    a failed *build* is tolerated only when the prebuilt .so that loads
    matches the current source (hash stamp), never a stale one."""
    build_err = None
    try:
        proc = subprocess.run(
            ["make", "-C", os.path.join(REPO, "native")],
            capture_output=True,
            text=True,
        )
        if proc.returncode != 0:
            build_err = proc.stdout + proc.stderr
    except OSError as e:  # no make on this host
        build_err = str(e)
    from dmlc_core_tpu.data import native

    if not native.load():
        if build_err:
            sys.stderr.write(build_err + "\n")
        raise RuntimeError("native library unavailable (build log above)")
    import hashlib

    src = os.path.join(REPO, "native", "fastparse.cc")
    want = hashlib.sha256(open(src, "rb").read()).hexdigest()
    got = native.source_hash()
    if got != want and build_err is None:
        # an up-to-date-by-mtime .so without a (current) stamp: force a
        # relink and re-open the fresh .so
        proc = subprocess.run(
            ["make", "-B", "-C", os.path.join(REPO, "native")],
            capture_output=True, text=True,
        )
        if proc.returncode == 0 and native.load(force=True):
            got = native.source_hash()
    if got != want:
        if build_err:
            sys.stderr.write(build_err + "\n")
        raise RuntimeError(
            f"native .so is stale (built from {got[:12] or 'unstamped'}, "
            f"source is {want[:12]}); refusing to benchmark it"
        )
    if build_err:
        sys.stderr.write(
            "warning: native rebuild failed; the prebuilt library matches "
            "the source hash, benchmarking it\n"
        )


def ensure_data() -> None:
    if os.path.exists(DATA) and os.path.getsize(DATA) > 0:
        return
    rng = np.random.default_rng(42)
    tmp = DATA + ".tmp"
    with open(tmp, "w") as f:
        chunk = 10000
        for start in range(0, N_ROWS, chunk):
            n = min(chunk, N_ROWS - start)
            vals = rng.normal(size=(n, N_FEATURES))
            labels = rng.integers(0, 2, n)
            lines = []
            for i in range(n):
                feats = " ".join(
                    f"{j}:{vals[i, j]:.7f}" for j in range(N_FEATURES)
                )
                lines.append(f"{labels[i]} {feats}\n")
            f.write("".join(lines))
    os.replace(tmp, DATA)


def ensure_libfm_data() -> None:
    """Criteo-like libfm text: 39 ``field:feat[:val]`` tokens per row
    (13 dense fields with values, 26 categorical bare pairs) — the FM
    ingestion analogue of the RecordIO shard (reference treats libfm as
    a first-class hot path, libfm_parser.h:67-144)."""
    if os.path.exists(LIBFM_DATA) and os.path.getsize(LIBFM_DATA) > 0:
        return
    rng = np.random.default_rng(11)
    tmp = LIBFM_DATA + ".tmp"
    with open(tmp, "w") as f:
        chunk = 50000
        for start in range(0, REC_ROWS, chunk):
            n = min(chunk, REC_ROWS - start)
            # vectorized like ensure_rec_data: per-COLUMN np.char ops,
            # not 39 f-strings per row
            cols = [np.char.mod("%d", rng.integers(0, 2, n))]
            dvals = rng.uniform(0, 1, (n, REC_DENSE))
            for j in range(REC_DENSE):
                cols.append(np.char.mod(f"{j}:{j}:%.6f", dvals[:, j]))
            cats = rng.integers(REC_DENSE, REC_SPACE, (n, REC_CAT))
            for j in range(REC_CAT):
                cols.append(np.char.mod(f"{REC_DENSE + j}:%d", cats[:, j]))
            lines = cols[0]
            for c in cols[1:]:
                lines = np.char.add(np.char.add(lines, " "), c)
            f.write("\n".join(lines.tolist()) + "\n")
    os.replace(tmp, LIBFM_DATA)


def ensure_rec_data() -> None:
    """Synthetic Criteo-like rowrec RecordIO, generated vectorized.

    Every row has exactly 39 features; values are small floats and ids
    < 2^20, so no payload word can collide with the RecordIO magic —
    asserted below, which keeps every frame single-part (cflag 0) and the
    whole shard expressible as one fixed-stride numpy record array.
    (Multipart correctness is covered by tests/test_rowrec.py; writer
    parity of this fast generator is asserted against RecordIOWriter.)
    """
    if os.path.exists(REC_DATA) and os.path.getsize(REC_DATA) > 0:
        return
    from dmlc_core_tpu.io.recordio import KMAGIC, encode_lrec

    rng = np.random.default_rng(7)
    payload_len = 12 + REC_K * 8
    frame = np.dtype(
        [
            ("magic", "<u4"),
            ("lrec", "<u4"),
            ("label", "<f4"),
            ("weight", "<f4"),
            ("nnz", "<u4"),
            ("idx", "<u4", (REC_K,)),
            ("val", "<f4", (REC_K,)),
        ]
    )
    assert frame.itemsize == 8 + payload_len
    tmp = REC_DATA + ".tmp"
    chunk = 100_000
    with open(tmp, "wb") as f:
        for start in range(0, REC_ROWS, chunk):
            n = min(chunk, REC_ROWS - start)
            arr = np.zeros(n, dtype=frame)
            arr["magic"] = KMAGIC
            arr["lrec"] = encode_lrec(0, payload_len)
            arr["label"] = rng.integers(0, 2, n)
            arr["weight"] = 1.0
            arr["nnz"] = REC_K
            arr["idx"][:, :REC_DENSE] = np.arange(REC_DENSE)
            arr["idx"][:, REC_DENSE:] = rng.integers(
                REC_DENSE, REC_SPACE, (n, REC_CAT)
            )
            arr["val"][:, :REC_DENSE] = rng.uniform(0, 1, (n, REC_DENSE))
            arr["val"][:, REC_DENSE:] = 1.0
            # no in-payload aligned word may equal the magic (keeps cflag 0)
            words = arr.view("<u4").reshape(n, frame.itemsize // 4)
            assert not (words[:, 2:] == KMAGIC).any()
            f.write(arr.tobytes())
    # generator parity: the first frames must be byte-identical to what
    # RecordIOWriter would emit for the same payloads
    from dmlc_core_tpu.io.recordio import RecordIOReader, RecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream, MemoryStream

    with FileStream(tmp, "r") as f:
        reader = RecordIOReader(f)
        payloads = [reader.next_record() for _ in range(3)]
    ms = MemoryStream()
    w = RecordIOWriter(ms)
    for p in payloads:
        w.write_record(p)
    head = open(tmp, "rb").read(len(ms.getvalue()))
    assert head == ms.getvalue(), "fast .rec generator diverges from writer"
    os.replace(tmp, REC_DATA)


def _make_higgs_stream(value_dtype: str):
    from dmlc_core_tpu.staging import BatchSpec, dense_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="dense",
        num_features=N_FEATURES + 1,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        dense_batches(DATA, spec, nthread=_nthread_for(N_ROWS), ring=_RING),
        "x",
        DATA,
    )


CSV_DATA = os.environ.get(
    "BENCH_CSV_DATA", f"/tmp/dmlc_tpu_bench_higgs_{N_ROWS}.csv"
)


def ensure_csv_data() -> None:
    """HIGGS-like dense CSV (label column 0 + 28 feature columns)."""
    if os.path.exists(CSV_DATA) and os.path.getsize(CSV_DATA) > 0:
        return
    rng = np.random.default_rng(21)
    tmp = CSV_DATA + ".tmp"
    with open(tmp, "w") as f:
        chunk = 20000
        for start in range(0, N_ROWS, chunk):
            n = min(chunk, N_ROWS - start)
            vals = rng.normal(size=(n, N_FEATURES))
            labels = rng.integers(0, 2, n)
            f.write(
                "".join(
                    "%d,%s\n" % (
                        labels[i],
                        ",".join(f"{v:.6f}" for v in vals[i]),
                    )
                    for i in range(n)
                )
            )
    os.replace(tmp, CSV_DATA)


def _make_csv_stream(value_dtype: str):
    from dmlc_core_tpu.staging import BatchSpec, dense_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="dense",
        num_features=N_FEATURES,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        dense_batches(
            CSV_DATA + "?format=csv&label_column=0", spec,
            nthread=_nthread_for(N_ROWS), ring=_RING,
        ),
        "x",
        CSV_DATA,
    )


def _make_rec_stream(value_dtype: str):
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="ell",
        max_nnz=REC_K,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        ell_batches(
            _fault_wrapped(REC_DATA), spec,
            nthread=_nthread_for(REC_ROWS), ring=_RING,
        ),
        "values",
        REC_DATA,
    )


REC_INDEX = REC_DATA + ".idx"
# 1 MB compressed blocks (vs the 256 KB writer default): the right
# packing for a sequential-epoch corpus — better ratio, fewer block
# headers, and per-block costs (decode dispatch, shared-cache segment
# attach) amortize over 4x the payload. The filename carries the block
# size so a packing change can never silently reuse stale data.
REC_ZLIB_BLOCK = 1 << 20
REC_ZLIB_DATA = os.environ.get(
    "BENCH_REC_ZLIB_DATA",
    f"/tmp/dmlc_tpu_bench_criteo_{REC_ROWS}.zlib1m.rec",
)
REC_ZLIB_INDEX = REC_ZLIB_DATA + ".idx"


def ensure_rec_zlib_data() -> None:
    """zlib-compressed-block copy of the bench .rec (+ block index):
    the codec-path config (`rec_zlib`) tracks decode throughput and
    compression_ratio round over round. Conversion feeds the uniform-
    stride frames to write_framed_block in bulk (arithmetic offsets, no
    per-record re-framing) — one pass, compression is the only cost."""
    if (os.path.exists(REC_ZLIB_DATA) and os.path.getsize(REC_ZLIB_DATA) > 0
            and os.path.exists(REC_ZLIB_INDEX)
            and os.path.getsize(REC_ZLIB_INDEX) > 0):
        return
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream

    stride = 8 + 12 + REC_K * 8  # frame header + payload (ensure_rec_data)
    tmp, tmpi = REC_ZLIB_DATA + ".tmp", REC_ZLIB_INDEX + ".tmp"
    with open(REC_DATA, "rb") as src, FileStream(tmp, "w") as f, FileStream(
        tmpi, "w"
    ) as fi:
        w = IndexedRecordIOWriter(
            f, fi, codec="zlib", block_bytes=REC_ZLIB_BLOCK
        )
        while True:
            buf = src.read(stride * 4096)
            if not buf:
                break
            n = len(buf) // stride
            assert n * stride == len(buf), "bench .rec is not stride-uniform"
            w.write_framed_block(
                buf, np.arange(n, dtype=np.int64) * stride
            )
        w.flush_block()
    os.replace(tmp, REC_ZLIB_DATA)
    os.replace(tmpi, REC_ZLIB_INDEX)


def _make_rec_zlib_stream(value_dtype: str):
    """Compressed-block RecordIO → fused ELL staging: chunks decode on
    the codec layer (parallel block decompress) before the native frame
    scan, so the whole fused path rides unchanged. data_path is the
    UNCOMPRESSED .rec — mb_per_sec is then effective DECODED MB/s, the
    number the codec must beat when the link (not the CPU) is the
    bottleneck."""
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="ell",
        max_nnz=REC_K,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        ell_batches(
            _fault_wrapped(REC_ZLIB_DATA), spec,
            nthread=_nthread_for(REC_ROWS), ring=_RING,
        ),
        "values",
        REC_DATA,
    )


# dsserve_remote corpus (ISSUE 12): a quarter-size zlib slice of the
# bench .rec — the full 400k-row corpus makes the latency-dominated
# A/B drains pay ~2 minutes of injected sleeps for the same ratio
DSSERVE_ROWS = int(os.environ.get("BENCH_DSSERVE_ROWS", "100000"))
DSSERVE_DATA = f"/tmp/dmlc_tpu_bench_dsserve_{DSSERVE_ROWS}.zlib.rec"
DSSERVE_INDEX = DSSERVE_DATA + ".idx"


def ensure_dsserve_data() -> None:
    """First DSSERVE_ROWS records of the bench .rec, recompressed into
    zlib blocks (same bulk-framed conversion as ensure_rec_zlib_data)."""
    if (os.path.exists(DSSERVE_DATA) and os.path.getsize(DSSERVE_DATA) > 0
            and os.path.exists(DSSERVE_INDEX)
            and os.path.getsize(DSSERVE_INDEX) > 0):
        return
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream

    stride = 8 + 12 + REC_K * 8
    tmp, tmpi = DSSERVE_DATA + ".tmp", DSSERVE_INDEX + ".tmp"
    left = DSSERVE_ROWS
    with open(REC_DATA, "rb") as src, FileStream(tmp, "w") as f, FileStream(
        tmpi, "w"
    ) as fi:
        w = IndexedRecordIOWriter(f, fi, codec="zlib")
        while left > 0:
            buf = src.read(stride * min(4096, left))
            if not buf:
                break
            n = len(buf) // stride
            left -= n
            w.write_framed_block(buf, np.arange(n, dtype=np.int64) * stride)
        w.flush_block()
    os.replace(tmp, DSSERVE_DATA)
    os.replace(tmpi, DSSERVE_INDEX)


# rec_remote_latency corpus (ISSUE 9): a small zlib shard packed with
# MANY small blocks (4 KB raw), so a shuffled window's missing blocks
# scatter into many non-contiguous file spans — the access shape where
# parallel ranged reads beat one serial connection. The big-block
# rec_zlib corpus is wrong for this: a window there touches nearly
# every block and the planner correctly collapses the read into one
# contiguous span (which the fetcher serves on ONE stream by design).
REC_REMOTE_ROWS = int(os.environ.get("BENCH_REMOTE_ROWS", "20000"))
# filename carries the record shape (128B incompressible payloads, 4KB
# blocks) so a packing change can never silently reuse stale data
REC_REMOTE_DATA = os.environ.get(
    "BENCH_REC_REMOTE_DATA",
    f"/tmp/dmlc_tpu_bench_remote_{REC_REMOTE_ROWS}.zlib4k-r128.rec",
)
REC_REMOTE_INDEX = REC_REMOTE_DATA + ".idx"
# per-span latency injection: fault:// fires a 20 ms sleep every ~2.5
# read ordinals (spikes budget far above the read count); cap=2048
# makes a typical 1-2-block span cost 2-4 reads, so every span pays
# ranged-read latency — the remote shape the fetcher exists to overlap
REMOTE_FAULT_SPEC = "latency_ms=20,spikes=4000,cap=2048,seed=3"


def ensure_rec_remote_data() -> None:
    if (os.path.exists(REC_REMOTE_DATA)
            and os.path.getsize(REC_REMOTE_DATA) > 0
            and os.path.exists(REC_REMOTE_INDEX)
            and os.path.getsize(REC_REMOTE_INDEX) > 0):
        return
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream

    # INCOMPRESSIBLE payloads: a digits-only corpus deflates 4KB blocks
    # to ~100 disk bytes, and a drain over those is all fixed overhead
    # — real shards keep blocks KB-sized on disk, which is the shape
    # whose span reads the latency injection must hit
    rng = np.random.default_rng(31)
    tmp, tmpi = REC_REMOTE_DATA + ".tmp", REC_REMOTE_INDEX + ".tmp"
    with FileStream(tmp, "w") as f, FileStream(tmpi, "w") as fi:
        w = IndexedRecordIOWriter(
            f, fi, codec="zlib", block_bytes=1 << 12
        )
        payloads = rng.integers(
            0, 255, (REC_REMOTE_ROWS, 120), dtype=np.uint8
        )
        for i in range(REC_REMOTE_ROWS):
            w.write_record(
                (b"%08d" % i) + payloads[i].tobytes(), i
            )
        w.flush_block()
    os.replace(tmp, REC_REMOTE_DATA)
    os.replace(tmpi, REC_REMOTE_INDEX)


def _remote_latency_bench() -> dict:
    """The ``rec_remote_latency`` config (ISSUE 9 acceptance): a
    shuffled window drain over the small-block zlib corpus behind
    ``fault://`` 20 ms latency spikes — concurrent ranged fetch
    (``DMLC_FETCH_THREADS=8``) vs the serial one-connection baseline
    (``DMLC_FETCH_THREADS=1``), same (seed, epoch). The invariant is
    twofold: the drains are bit-identical (sha256 over the emitted
    framed bytes — completion order must never leak into epoch order)
    and the parallel side is >= 3x faster. Host-side only (split
    layer), so the number is pure fetch overlap, no device noise."""
    import hashlib

    from dmlc_core_tpu.io import codec as io_codec
    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.io.faults import wrap_uri

    ensure_rec_remote_data()
    uri = wrap_uri(REC_REMOTE_DATA, REMOTE_FAULT_SPEC)
    # 2 windows: enough spans (~250) for the AIMD ramp to reach its
    # ceiling, few enough that the private decode cache has not yet
    # absorbed the block population (later windows miss fewer blocks,
    # which shrinks the parallelizable span count and dilutes the
    # ratio toward fixed per-window overhead)
    n_windows = int(os.environ.get("BENCH_REMOTE_WINDOWS", "2"))

    def drain(threads: int) -> dict:
        prior = os.environ.get("DMLC_FETCH_THREADS")
        os.environ["DMLC_FETCH_THREADS"] = str(threads)
        try:
            sp = io_split.IndexedRecordIOSplitter(
                uri, REC_REMOTE_INDEX, 0, 1,
                shuffle="window", seed=11, window=256, merge_gap=0,
                readahead=False,
                # private decode context: the process-global decoded-
                # block LRU would serve the second drain from memory
                # and measure nothing
                decode_ctx=io_codec.DecodeContext(
                    cache=io_codec.DecodedBlockCache(256 << 20),
                    shared=None,
                ),
            )
            h = hashlib.sha256()
            t0 = time.perf_counter()
            for _ in range(n_windows):
                chunk = sp.next_batch_ex(256)
                if chunk is None:
                    break
                h.update(chunk)
            dt = time.perf_counter() - t0
            stats = sp.io_stats()
            sp.close()
            return {
                "secs": round(dt, 3),
                "sha": h.hexdigest(),
                "rows": stats.get("records", 0),
                "spans": stats.get("spans", 0),
                "fetch_concurrency_peak": stats.get(
                    "fetch_concurrency_peak", 1
                ),
                "retries": stats.get("retries", 0),
            }
        finally:
            # restore (not pop): a user-pinned DMLC_FETCH_THREADS must
            # survive this config for the rest of the bench process
            if prior is None:
                os.environ.pop("DMLC_FETCH_THREADS", None)
            else:
                os.environ["DMLC_FETCH_THREADS"] = prior

    def best_of(n: int, threads: int) -> dict:
        # fastest of n: injected sleeps dominate both sides, but on a
        # loaded 1-core box sleep() overshoot and scheduler hiccups can
        # swing one sample 2x — the min is the least-contended reading
        # (the _shared_cache_bench idiom). The sha must agree across
        # repeats regardless.
        runs = [drain(threads) for _ in range(n)]
        assert len({r["sha"] for r in runs}) == 1, "drain not deterministic"
        return min(runs, key=lambda r: r["secs"])

    serial = best_of(2, 1)
    parallel = best_of(2, 8)
    return {
        "serial": serial,
        "parallel": parallel,
        "bit_identical": serial["sha"] == parallel["sha"],
        "remote_fetch_speedup": round(
            serial["secs"] / max(parallel["secs"], 1e-9), 2
        ),
        "latency_ms": 20,
    }


def _point_lookup_bench() -> dict:
    """The ``point_lookup_zipf`` config (ISSUE 13 acceptance): a
    Zipfian(α≈1.1) batched point-read workload over the latency-injected
    small-block zlib corpus — ``RecordLookup`` (vectorized key resolve,
    one cache round trip per batch, coalesced parallel miss fetch) vs
    the naive per-key open-seek-read loop a user writes without the
    API. Three invariants: bytes bit-identical for the same key
    sequence, batched >= 5x naive, and — against the WARM serve
    daemon — a p99 latency ceiling at a target QPS (the served
    histogram lands in the telemetry snapshot as
    ``io.lookup.request_seconds``). Hot-set skew is what "millions of
    users" actually looks like; the permuted key space scatters the hot
    set across blocks the way a real id space does instead of letting
    the first few blocks absorb it."""
    import hashlib

    from dmlc_core_tpu.io import codec as io_codec
    from dmlc_core_tpu.io import lookup as io_lookup
    from dmlc_core_tpu.io import recordio as io_recordio
    from dmlc_core_tpu.io.faults import wrap_uri
    from dmlc_core_tpu.io.stream import Stream

    ensure_rec_remote_data()
    uri = wrap_uri(REC_REMOTE_DATA, REMOTE_FAULT_SPEC)
    n = REC_REMOTE_ROWS
    rng = np.random.default_rng(29)
    alpha = float(os.environ.get("BENCH_LOOKUP_ALPHA", "1.1"))
    scatter = rng.permutation(n)
    p = 1.0 / np.arange(1, n + 1) ** alpha
    p /= p.sum()
    # sized so the Zipf hot set repeats enough for the L1 to matter on
    # the batched side (sublinear cost) while the naive loop stays
    # strictly linear — the injected sleeps dominate both sides, so the
    # ratio is robust to a loaded box
    n_keys = int(os.environ.get("BENCH_LOOKUP_KEYS", "360"))
    batch = int(os.environ.get("BENCH_LOOKUP_BATCH", "60"))
    keys = scatter[rng.choice(n, size=n_keys, p=p)].tolist()
    # a few honest negatives ride along: both sides must answer None
    keys[7::61] = [n * 10 + i for i in range(len(keys[7::61]))]

    def run_batched() -> dict:
        prior = os.environ.get("DMLC_FETCH_THREADS")
        os.environ["DMLC_FETCH_THREADS"] = "8"
        try:
            h = io_lookup.RecordLookup(
                uri, REC_REMOTE_INDEX,
                # merge_gap=0: a point-read batch touches SCATTERED
                # blocks; merging across 64 KB gaps here re-reads most
                # of the file through cap-limited ranged reads, each
                # paying the injected latency — tight per-block spans
                # fanned out on 8 connections is the winning shape
                merge_gap=0,
                # private decode context: the process-global L1 would
                # carry state between configs and measure nothing
                decode_ctx=io_codec.DecodeContext(
                    cache=io_codec.DecodedBlockCache(256 << 20),
                    shared=None,
                ),
            )
            sha = hashlib.sha256()
            t0 = time.perf_counter()
            for at in range(0, n_keys, batch):
                chunk = keys[at : at + batch]
                for k, v in zip(chunk, h.lookup(chunk)):
                    sha.update(b"%d:" % k)
                    sha.update(b"<none>" if v is None else v)
            dt = time.perf_counter() - t0
            stats = h.io_stats()
            return {"handle": h, "secs": round(dt, 3),
                    "sha": sha.hexdigest(), "stats": stats}
        finally:
            if prior is None:
                os.environ.pop("DMLC_FETCH_THREADS", None)
            else:
                os.environ["DMLC_FETCH_THREADS"] = prior

    def run_naive(handle) -> dict:
        """The reference random-access idiom, deliberately unimproved:
        per key, open the shard, seek to the record's block, read it,
        decode it, slice the record — no batching, no cache, no
        coalescing, no parallelism. Key->position resolution reuses the
        handle's index (resolution is not what's being measured)."""
        sp = handle._sp
        sha = hashlib.sha256()
        t0 = time.perf_counter()
        for k in keys:
            hit, recs = handle._resolve([k])
            sha.update(b"%d:" % k)
            if not bool(hit[0]):
                sha.update(b"<none>")
                continue
            rec = int(recs[0])
            bid = int(sp._rec_block[rec])
            boff = int(sp._block_offs[bid])
            bsz = int(sp._block_sizes[bid])
            with Stream.create(uri, "r") as s:
                s.seek(boff)
                data = bytearray()
                while len(data) < bsz:
                    got = s.read(bsz - len(data))
                    if not got:
                        break
                    data += got
            blob, _end = io_recordio.scan_compressed_blob(
                memoryview(bytes(data)), 0
            )
            raw, _cnt = io_codec.decode_block(blob)
            start = int(sp._rec_inoff[rec])
            end = int(sp._rec_next[rec])
            framed = raw[start:] if end < 0 else raw[start:end]
            payload = io_recordio.RecordIOChunkReader(
                framed, 0, 1
            ).next_record()
            sha.update(bytes(payload))
        return {
            "secs": round(time.perf_counter() - t0, 3),
            "sha": sha.hexdigest(),
        }

    batched = run_batched()
    handle = batched.pop("handle")
    try:
        naive = run_naive(handle)

        # -- served phase: the warm daemon under a paced request load --
        n_req = int(os.environ.get("BENCH_LOOKUP_REQUESTS", "300"))
        req_batch = int(os.environ.get("BENCH_LOOKUP_REQ_BATCH", "16"))
        p99_ceiling_ms = float(os.environ.get("BENCH_LOOKUP_P99_MS", "50"))
        target_qps = float(os.environ.get("BENCH_LOOKUP_QPS", "100"))
        req_keys = scatter[rng.choice(n, size=(n_req, req_batch), p=p)]
        # warm the request working set through the cache tier first —
        # the ceiling is a statement about the WARM daemon (cold-block
        # latency is the batched config's subject, measured above)
        handle.warm(req_keys.ravel().tolist())
        srv = io_lookup.LookupServer(handle, port=0)
        try:
            client = io_lookup.LookupClient("127.0.0.1", srv.port)
            lat = []
            t0 = time.perf_counter()
            for r in range(n_req):
                t1 = time.perf_counter()
                client.lookup(req_keys[r].tolist())
                lat.append(time.perf_counter() - t1)
            total = time.perf_counter() - t0
            client.close()
        finally:
            srv.close()
        lat.sort()
        served = {
            "requests": n_req,
            "keys_per_request": req_batch,
            "qps": round(n_req / max(total, 1e-9), 1),
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(lat[int(len(lat) * 0.99) - 1] * 1e3, 3),
        }
    finally:
        handle.close()

    stats = batched.pop("stats")
    return {
        "alpha": alpha,
        "keys": n_keys,
        "batch": batch,
        "batched_secs": batched["secs"],
        "naive_secs": naive["secs"],
        "batched_speedup": round(
            naive["secs"] / max(batched["secs"], 1e-9), 2
        ),
        "bit_identical": batched["sha"] == naive["sha"],
        "negatives": stats.get("negatives", 0),
        "block_cache_hits": stats.get("block_cache_hits", 0),
        "block_cache_misses": stats.get("block_cache_misses", 0),
        "spans": stats.get("spans", 0),
        "served": served,
        "p99_ceiling_ms": p99_ceiling_ms,
        "target_qps": target_qps,
        "latency_ms": int(
            dict(
                kv.split("=") for kv in REMOTE_FAULT_SPEC.split(",")
            )["latency_ms"]
        ),
    }


# dynamic-shard straggler corpus: plain (uncompressed) indexed rowrec,
# sized so one epoch is seconds, not minutes, with the latency fault on
# the straggler dominating both modes' makespan
DYN_ROWS = int(os.environ.get("BENCH_DYN_ROWS", "48000"))
DYN_DATA = os.environ.get(
    "BENCH_DYN_DATA", f"/tmp/dmlc_tpu_bench_dyn_{DYN_ROWS}.rec"
)
DYN_INDEX = DYN_DATA + ".idx"
# worker 0's handicap: 100 ms latency spikes on every ~2.5th read, read
# size capped so the spike schedule covers its whole static share. The
# handicap is sized so the STATIC straggler's injected latency (~10s)
# dominates box noise: static makespan grows with the full handicap
# while dynamic self-balances (the straggler leases fewer shards), so
# the ratio clears the 1.5x invariant with margin even when the 3
# concurrent dynamic workers contend for a small box's cores
DYN_FAULT_SPEC = os.environ.get(
    "BENCH_DYN_FAULT", "latency_ms=100,spikes=400,cap=8192,seed=13"
)


def ensure_dyn_shard_data() -> None:
    if (os.path.exists(DYN_DATA) and os.path.getsize(DYN_DATA) > 0
            and os.path.exists(DYN_INDEX)
            and os.path.getsize(DYN_INDEX) > 0):
        return
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream

    rng = np.random.default_rng(17)
    tmp, tmpi = DYN_DATA + ".tmp", DYN_INDEX + ".tmp"
    with FileStream(tmp, "w") as f, FileStream(tmpi, "w") as fi:
        w = IndexedRecordIOWriter(f, fi)
        payloads = rng.integers(0, 255, (DYN_ROWS, 120), dtype=np.uint8)
        for i in range(DYN_ROWS):
            w.write_record((b"%08d" % i) + payloads[i].tobytes(), i)
        w.flush_block()
    os.replace(tmp, DYN_DATA)
    os.replace(tmpi, DYN_INDEX)


def _dynamic_shard_drain_main(mode: str, rec: str, idx: str) -> None:
    """Worker mode (``bench.py --dynamic-shard-drain static|dynamic rec
    idx``): drain this worker's share of the oversharded corpus
    host-side and print one JSON line with per-micro-shard row counts
    and shas. ``static`` = the contiguous micro-shard range
    ``part_index`` assignment would pin to this worker; ``dynamic`` =
    tracker-leased via DynamicShardSource (commits on the exactly-once
    ``recorded`` ack). DMLC_DYN_FAULT (set by the parent on the
    straggler only) wraps the DATA path in fault:// latency."""
    import hashlib

    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.io.faults import wrap_uri

    task = int(os.environ.get("DMLC_TASK_ID", "0"))
    n_workers = int(os.environ.get("BENCH_DYN_WORKERS", "3"))
    n_shards = int(os.environ.get("BENCH_DYN_NUM_SHARDS", "12"))
    fault = os.environ.get("DMLC_DYN_FAULT", "")
    data = wrap_uri(rec, fault) if fault else rec
    uri = f"{data}?index={idx}&shuffle=record&seed=7"
    shards: dict = {}
    t0 = time.perf_counter()
    if mode == "static":
        per = n_shards // n_workers
        for shard in range(task * per, (task + 1) * per):
            sp = io_split.create(uri, type="recordio", part_index=shard,
                                 num_parts=n_shards, threaded=False)
            h = hashlib.sha256()
            while True:
                chunk = sp.next_batch_ex(4096)
                if chunk is None:
                    break
                h.update(chunk)
            stats = sp.io_stats()
            sp.close()
            shards[shard] = {"rows": stats.get("records", 0),
                             "sha": h.hexdigest()}
        extra = {}
    else:
        src = io_split.create(uri + "&dynamic_shards=1", type="recordio",
                              threaded=False)
        cur: dict = {}

        def on_lease(shard, num_shards):
            cur["shard"], cur["h"], cur["rows"] = shard, hashlib.sha256(), 0

        def on_done(shard, status):
            if status == "recorded":
                shards[shard] = {"rows": cur["rows"],
                                 "sha": cur["h"].hexdigest()}

        src.on_lease = on_lease
        src.on_shard_done = on_done
        while True:
            # per-shard sha needs shard-bounded emission: gather batches
            # never cross a shard (or window) boundary
            g = src.next_gather_batch(4096)
            if g is None:
                break
            buf, starts, sizes = g
            flat = buf.reshape(-1) if buf.ndim > 1 else buf
            for s, z in zip(starts.tolist(), sizes.tolist()):
                cur["h"].update(flat[s:s + z].tobytes())
            cur["rows"] += len(starts)
        stats = src.io_stats()
        src.close()
        extra = {
            "leases": stats.get("leases", 0),
            "lease_wait_secs": stats.get("lease_wait_secs", 0.0),
        }
    print(json.dumps({
        "task": task,
        "mode": mode,
        "secs": round(time.perf_counter() - t0, 3),
        "rows": sum(s["rows"] for s in shards.values()),
        "shards": shards,
        **extra,
    }))


def _dynamic_shard_bench() -> dict:
    """The ``dynamic_shard_straggler`` config (ISSUE 10 acceptance): 3
    REAL worker processes over a 24-micro-shard corpus (oversplit 8),
    worker 0 behind ``fault://`` latency spikes. Static ``part_index``
    assignment pins 8 micro-shards to the straggler and the epoch
    makespan is its drain time; tracker-leased dynamic sharding lets
    the fast workers steal, so the straggler takes only what it can
    actually finish.
    ``straggler_speedup`` = static makespan / dynamic makespan (>= 1.5
    invariant), with identical total rows and per-micro-shard bytes sha
    between the two runs."""
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    ensure_dyn_shard_data()
    # oversplit 8 (not the default 4): the epoch tail is the straggler's
    # LAST leased shard — finer micro-shards shrink exactly that tail,
    # which is the knob's documented tradeoff (docs/sharding.md)
    n_workers, oversplit = 3, 8
    n_shards = n_workers * oversplit

    def run_mode(mode: str, tracker_port=None) -> dict:
        procs = []
        t0 = time.perf_counter()
        for task in range(n_workers):
            env = {
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "DMLC_TASK_ID": str(task),
                "BENCH_DYN_WORKERS": str(n_workers),
                "BENCH_DYN_NUM_SHARDS": str(n_shards),
                # serial reads: the concurrent span fetcher would
                # overlap the injected latency away, and this config
                # measures PLACEMENT, not fetch overlap (ISSUE 9 owns
                # that number)
                "DMLC_FETCH_THREADS": "1",
            }
            if task == 0:
                env["DMLC_DYN_FAULT"] = DYN_FAULT_SPEC
            if tracker_port is not None:
                env["DMLC_TRACKER_URI"] = "127.0.0.1"
                env["DMLC_TRACKER_PORT"] = str(tracker_port)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO, "bench.py"),
                 "--dynamic-shard-drain", mode, DYN_DATA, DYN_INDEX],
                env=env, stdout=subprocess.PIPE, text=True,
            ))
        outs = []
        failed = None
        for task, p in enumerate(procs):
            out, _ = p.communicate()
            if p.returncode != 0 and failed is None:
                failed = (task, p.returncode, out)
            elif failed is None:
                outs.append(json.loads(out))
        if failed is not None:
            # the siblings were reaped above, so their lease-connection
            # tracebacks (the tracker dies in the caller's finally)
            # can't interleave with — and mask — the real failure
            task, rc, out = failed
            raise RuntimeError(
                f"dynamic-shard drain worker task={task} failed (rc={rc}); "
                f"stdout tail: {out[-500:]!r}"
            )
        wall = time.perf_counter() - t0
        shards: dict = {}
        for o in outs:
            for k, v in o["shards"].items():
                assert k not in shards, f"micro-shard {k} served twice"
                shards[k] = v
        return {
            # epoch makespan = the slowest worker's DRAIN time (the
            # workers start together; interpreter startup is identical
            # noise on both modes and 3 concurrent imports on a small
            # box would otherwise dominate the ratio); wall_secs keeps
            # the raw spawn-to-exit number visible
            "makespan_secs": round(max(o["secs"] for o in outs), 3),
            "wall_secs": round(wall, 3),
            "worker_secs": [o["secs"] for o in outs],
            "rows": sum(o["rows"] for o in outs),
            "shards": shards,
            "lease_wait_secs": round(
                sum(o.get("lease_wait_secs", 0.0) for o in outs), 3
            ),
        }

    # explicit, not setdefault: an inherited DMLC_SHARD_OVERSPLIT would
    # change the tracker's micro-shard count while the workers'
    # BENCH_DYN_NUM_SHARDS stays pinned — the two MUST agree for the
    # static/dynamic sha comparison to mean anything
    prev_oversplit = os.environ.get("DMLC_SHARD_OVERSPLIT")
    os.environ["DMLC_SHARD_OVERSPLIT"] = str(oversplit)
    tracker = None
    try:
        static = run_mode("static")
        tracker = RabitTracker("127.0.0.1", n_workers)
        tracker.start(n_workers)
        dynamic = run_mode("dynamic", tracker_port=tracker.port)
        shard_summary = tracker.shards.summary()
    finally:
        if tracker is not None:
            tracker.close()
        if prev_oversplit is None:
            os.environ.pop("DMLC_SHARD_OVERSPLIT", None)
        else:
            os.environ["DMLC_SHARD_OVERSPLIT"] = prev_oversplit
    identical = (
        static["rows"] == dynamic["rows"]
        and static["shards"] == dynamic["shards"]
    )
    return {
        "static": {k: v for k, v in static.items() if k != "shards"},
        "dynamic": {k: v for k, v in dynamic.items() if k != "shards"},
        "n_shards": n_shards,
        "fault": DYN_FAULT_SPEC,
        "identical": identical,
        "leases_stolen": shard_summary.get("stolen", 0),
        "leases_granted": shard_summary.get("granted", 0),
        "straggler_speedup": round(
            static["makespan_secs"] / max(dynamic["makespan_secs"], 1e-9), 2
        ),
    }


def _dsserve_drain_main(mode: str, rec: str, idx: str) -> None:
    """Worker mode (``bench.py --dsserve-drain local|client rec idx``):
    the trainer-side drain of the gather-shuffled zlib corpus over
    ``BENCH_DSSERVE_EPOCHS`` epochs, printing one JSON line with
    per-(epoch, micro-shard) packed-slot shas. ``local`` = the
    all-local pipeline (fetch→decode→gather-parse→pack in THIS
    process, shard-aligned so the shas are comparable); ``client`` =
    the same rows through ``dsserve://`` — this process only receives
    finished slots (the preprocessing ran on the server tier named by
    ``DMLC_DSSERVE``). ``BENCH_DSSERVE_FAULT`` (set identically for
    this drain and for the servers) wraps the corpus reads in fault://
    injected latency — see ``_dsserve_remote_bench`` for why the
    measured axis is deterministic injected latency."""
    import hashlib

    from dmlc_core_tpu.dsserve import wire as _wire
    from dmlc_core_tpu.io.faults import wrap_uri
    from dmlc_core_tpu.staging import fused
    from dmlc_core_tpu.staging.batcher import BatchSpec
    from dmlc_core_tpu.staging.pipeline import adoptable_slot

    n_shards = int(os.environ.get("BENCH_DSSERVE_NUM_SHARDS", "8"))
    epochs = int(os.environ.get("BENCH_DSSERVE_EPOCHS", "2"))
    # a batch that divides the micro-shard row count: every slot is
    # fully valid, so neither side pays pack/wire/crc for padding rows
    batch = int(os.environ.get("BENCH_DSSERVE_BATCH", "6250"))
    fault = os.environ.get("BENCH_DSSERVE_FAULT", "")

    spec = BatchSpec(
        batch_size=batch, layout="ell", max_nnz=REC_K,
        value_dtype=np.dtype("float16"),
    )
    data = wrap_uri(rec, fault) if fault else rec
    # windowed gather shuffle with shard-spanning windows: each window
    # load is a fresh latency-paying ranged read plus a real zlib
    # decode + gather-parse + pack — the preprocessing whose placement
    # this config measures
    uri = (
        f"{data}?index={idx}&shuffle=window&window=4096&merge_gap=4096"
        "&seed=5"
    )
    shards: dict = {}
    extra: dict = {}
    rows = 0
    warm_secs = 0.0
    epoch_secs = []
    alloc0 = wire0 = raw0 = None
    copies = 0
    t0 = time.perf_counter()
    # epoch 0 is the UNTIMED warmup + identity epoch: per-shard slot
    # shas are recorded here (hashing is bench verification, not
    # pipeline work), and one-time costs (interpreter, index sidecar)
    # drop out of the measured ratio on BOTH sides identically
    for epoch in range(epochs + 1):
        timed = epoch > 0
        t_ep = time.perf_counter()
        if mode == "local":
            ep_uri = uri + (f"&epoch={epoch}" if epoch else "")
            for shard in range(n_shards):
                p = fused.ell_batches(
                    ep_uri, spec, part_index=shard, num_parts=n_shards
                )
                h = hashlib.sha256() if not timed else None
                for b in p:
                    rows += b.n_valid
                    if not timed:
                        h.update(b.packed.tobytes())
                p.close()
                if not timed:
                    shards[str(shard)] = h.hexdigest()
        else:
            from dmlc_core_tpu.dsserve import DsServeBatches

            if timed and alloc0 is None:
                # the slot pool is warm after the untimed epoch: from
                # here on the recv path must allocate NOTHING (the
                # ISSUE 18 zero-copy acceptance surface), and the
                # wire/raw byte deltas below are the adaptive codec's
                # per-connection verdict over the timed drain
                alloc0 = _wire.recv_alloc_bytes()
                wire0 = _wire._BYTES_WIRE.value()
                raw0 = _wire._BYTES_RAW.value()
            src = DsServeBatches(
                "dsserve://" + os.environ["DMLC_DSSERVE"]
                + ("" if uri.startswith("/") else "/") + uri, spec,
                mode="lease", epoch=epoch,
            )
            if not timed:
                shas: dict = {}
                src.on_slot = lambda shard, seq, p: shas.setdefault(
                    shard, hashlib.sha256()
                ).update(p.tobytes())
            for b in src:
                rows += b.n_valid
                if timed and not adoptable_slot(b):
                    # a received slot the staging pipeline could NOT
                    # device_put verbatim (unaligned / non-contiguous
                    # / unpacked) — a copy the zero-copy plane promised
                    # away
                    copies += 1
            stats = src.io_stats()
            src.close()
            if not timed:
                shards = {str(s): h.hexdigest() for s, h in shas.items()}
            for k in ("recv_wait_secs", "reconnects"):
                extra[k] = round(extra.get(k, 0) + stats.get(k, 0), 4)
            for k in ("shm_slots", "tcp_slots"):
                extra[k] = extra.get(k, 0) + int(stats.get(k, 0))
            extra["slot_mb"] = round(
                extra.get("slot_mb", 0)
                + stats.get("bytes_recv", 0) / 1e6, 1,
            )
        if timed:
            epoch_secs.append(round(time.perf_counter() - t_ep, 3))
        else:
            warm_secs = time.perf_counter() - t0
            t0 = time.perf_counter()
    if mode != "local":
        # timed-epoch deltas only: the warmup epoch's one-time costs
        # (pool growth to the observed slot size, shm handshake, codec
        # probe) are excluded by construction
        extra["recv_alloc_bytes_timed"] = int(
            _wire.recv_alloc_bytes() - alloc0
        )
        extra["slot_copies"] = copies
        extra["bytes_wire_mb"] = round(
            (_wire._BYTES_WIRE.value() - wire0) / 1e6, 2
        )
        extra["bytes_raw_mb"] = round(
            (_wire._BYTES_RAW.value() - raw0) / 1e6, 2
        )
    print(json.dumps({
        "mode": mode,
        "secs": round(time.perf_counter() - t0, 3),
        # best-of scoring (the rec_zlib_shared_cache idiom, at zero
        # extra wall): the fastest timed epoch is the run's score —
        # this box's CPU weather only ever ADDS time, so the min is
        # the estimator of the deterministic latency+work core
        "best_epoch_secs": round(min(epoch_secs), 3),
        "epoch_secs": epoch_secs,
        "warm_secs": round(warm_secs, 3),
        "rows": rows,
        "epochs": epochs,
        "shards": shards,
        **extra,
    }))


def _dsserve_tier_drain(
    env: dict, n_servers: int = 2, oversplit: int = 8
) -> tuple:
    """One tracker + ``DsServeTier`` launch + client drain under
    ``env`` → (drain JSON, tracker shard-ledger summary). The shared
    scaffolding of the dsserve A/B configs: every run pays the same
    tier spin-up, and the per-run tracker gives each drain a fresh
    exactly-once ledger to audit."""
    from dmlc_core_tpu.tracker.backends.local import DsServeTier
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    prev_oversplit = os.environ.get("DMLC_SHARD_OVERSPLIT")
    os.environ["DMLC_SHARD_OVERSPLIT"] = str(oversplit)
    tracker = None
    tier = None
    try:
        tracker = RabitTracker("127.0.0.1", 1)
        tracker.start(1)
        tracker_env = {
            "DMLC_TRACKER_URI": "127.0.0.1",
            "DMLC_TRACKER_PORT": str(tracker.port),
        }
        # the same tier launcher dmlc-submit --dsserve uses (port-file
        # readiness, 1000+ task ids, terminate/kill teardown)
        tier = DsServeTier(n_servers, {**env, **tracker_env})
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--dsserve-drain", "client", DSSERVE_DATA, DSSERVE_INDEX],
            env={**env, **tracker_env, "DMLC_DSSERVE": tier.endpoints},
            stdout=subprocess.PIPE, text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"dsserve client drain failed (rc={proc.returncode}); "
                f"stdout tail: {proc.stdout[-500:]!r}"
            )
        drain = json.loads(proc.stdout)
        summary = tracker.shards.summary()
    finally:
        if tier is not None:
            tier.stop()
        if tracker is not None:
            tracker.close()
        if prev_oversplit is None:
            os.environ.pop("DMLC_SHARD_OVERSPLIT", None)
        else:
            os.environ["DMLC_SHARD_OVERSPLIT"] = prev_oversplit
    return drain, summary


def _dsserve_remote_bench() -> dict:
    """The ``dsserve_remote`` config (ISSUE 12 acceptance): a trainer
    drain fed by 2 REAL preprocessing-worker processes vs the all-local
    pipeline, on the CPU-bound zlib gather-shuffled corpus (decode +
    gather-parse + pack dominate; the wire ships finished slots).

    The instrument rides the repo's established robust idiom (the
    PR-9 ``rec_remote_latency`` and PR-10 ``dynamic_shard_straggler``
    configs): the corpus sits behind ``fault://`` injected read
    latency with the span fetcher serialized (``DMLC_FETCH_THREADS=1``
    — ISSUE 9 owns fetch overlap; this config measures PLACEMENT), so
    both sides are dominated by the same deterministic injected
    latency plus the same real decode/parse/pack work — naive
    contended-CPU A/B reads this box's ±40% weather as signal (the
    PR-8 lesson). The all-local trainer pays every window's latency
    and every decode serially in ONE process; the 2-worker tier pays
    them CONCURRENTLY, two pipelines wide — preprocessing capacity
    (CPU and IO concurrency alike) scaling with worker count, the
    disaggregation claim. Epoch 0 is an untimed warmup + identity
    epoch (slot shas recorded there; interpreter/index startup drops
    out of both sides identically); the timed epochs measure steady
    state.

    ``dsserve_speedup`` = local timed secs / dsserve timed secs
    (>= 1.5 invariant) with per-micro-shard packed-slot shas asserted
    IDENTICAL — the remote pipeline is the local one, relocated."""
    ensure_dsserve_data()
    n_servers = int(os.environ.get("BENCH_DSSERVE_SERVERS", "2"))
    oversplit = 8
    env_common = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_DSSERVE_NUM_SHARDS": str(oversplit),
        # ~every 2-3rd read of every window pays this — the
        # deterministic axis both drains share (spikes sized to cover
        # a whole stream's reads without the per-open schedule-build
        # cost of an absurd count; the PR-9 sizing)
        "BENCH_DSSERVE_FAULT": os.environ.get(
            "BENCH_DSSERVE_FAULT", "latency_ms=6,spikes=4000"
        ),
        # serial fetch: the concurrent span fetcher would overlap the
        # injected latency away inside ONE process (that number is
        # ISSUE 9's); here concurrency must come from tier workers
        "DMLC_FETCH_THREADS": "1",
        # the decoded-block LRU must not turn the timed epochs into a
        # warm-cache replay (the whole decoded corpus fits the 256 MB
        # default): capped so every epoch pays the zlib decode — the
        # CPU-bound work whose placement this config measures. Applied
        # to BOTH sides; intra-epoch window reuse still hits.
        "DMLC_DECODE_CACHE_MB": "16",
        # same-host servers would ride the shm transport and dodge the
        # wire entirely — dsserve_local_shm owns that axis. This config
        # measures PLACEMENT over a real socket, and its zero-copy
        # invariants (recv_alloc_bytes == 0, slot_copies == 0) are
        # specifically about the pooled TCP receive path.
        "DMLC_DSSERVE_SHM": "off",
    }

    def run_drain(mode: str, extra_env: dict) -> dict:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--dsserve-drain", mode, DSSERVE_DATA, DSSERVE_INDEX],
            env={**env_common, **extra_env},
            stdout=subprocess.PIPE, text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"dsserve {mode} drain failed (rc={proc.returncode}); "
                f"stdout tail: {proc.stdout[-500:]!r}"
            )
        return json.loads(proc.stdout)

    local = run_drain("local", {})
    remote, shard_summary = _dsserve_tier_drain(
        env_common, n_servers=n_servers, oversplit=oversplit
    )
    identical = (
        local["rows"] == remote["rows"]
        and local["shards"] == remote["shards"]
    )
    return {
        "local": {k: v for k, v in local.items() if k != "shards"},
        "dsserve": {k: v for k, v in remote.items() if k != "shards"},
        "n_servers": n_servers,
        "n_shards": oversplit,
        "identical": identical,
        "completed": shard_summary.get("completed", 0),
        "duplicates": shard_summary.get("duplicates", 0),
        "dsserve_speedup": round(
            local["best_epoch_secs"]
            / max(remote["best_epoch_secs"], 1e-9), 2
        ),
    }


def _dsserve_local_shm_bench() -> dict:
    """The ``dsserve_local_shm`` config (ISSUE 18 acceptance): the
    same-host 2-server drain with the shared-memory slot transport on
    vs off, everything else identical. The wire is the measured axis,
    so it is made deterministic the way this file's other A/Bs inject
    their bottleneck: ``DMLC_DSSERVE_WIRE_BPS`` paces every TCP payload
    byte at a modest NIC budget (box weather can only ADD time to
    either side), the codec is pinned off (it has its own config
    below), and the fault/cache knobs stay default (transport, not
    placement, is under test — the servers replay a warm decode cache).
    Over shm the same slots travel as ~100-byte descriptors, so the
    pacing never engages and the ratio isolates exactly what the
    zero-copy plane removes: the payload's trip through the socket.

    ``shm_speedup`` = TCP best timed epoch / shm best timed epoch
    (>= 1.8 invariant), per-shard slot shas identical across the two
    transports, both run ledgers exactly-once, and the shm run must
    have actually moved slots over shared memory."""
    from dmlc_core_tpu.io.shm import shm_available

    if not shm_available():
        raise OSError("host has no POSIX shared-memory support")
    ensure_dsserve_data()
    env_common = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_DSSERVE_NUM_SHARDS": "8",
        "DMLC_DSSERVE_WIRE_CODEC": "off",
        # 6 MB/s per connection: an epoch packs ~25 MB of slots, so
        # each server's TCP epoch floor is ~2 s — far above the ~0.8 s
        # warm parse+pack epoch the shm side pays, far below annoying
        # wall clock
        "DMLC_DSSERVE_WIRE_BPS": os.environ.get(
            "DMLC_DSSERVE_WIRE_BPS", "6000000"
        ),
    }
    tcp, tcp_led = _dsserve_tier_drain(
        {**env_common, "DMLC_DSSERVE_SHM": "off"}
    )
    shm, shm_led = _dsserve_tier_drain(
        {**env_common, "DMLC_DSSERVE_SHM": "on"}
    )
    identical = (
        tcp["rows"] == shm["rows"] and tcp["shards"] == shm["shards"]
    )
    return {
        "tcp": {k: v for k, v in tcp.items() if k != "shards"},
        "shm": {k: v for k, v in shm.items() if k != "shards"},
        "identical": identical,
        "duplicates": (
            tcp_led.get("duplicates", 0) + shm_led.get("duplicates", 0)
        ),
        "completed": [
            tcp_led.get("completed", 0), shm_led.get("completed", 0)
        ],
        "shm_slots": shm.get("shm_slots", 0),
        "shm_speedup": round(
            tcp["best_epoch_secs"] / max(shm["best_epoch_secs"], 1e-9), 2
        ),
    }


def _dsserve_wire_codec_bench() -> dict:
    """The ``dsserve_wire_codec`` config (ISSUE 18 acceptance): the
    adaptive wire codec's two promises, measured with NO knob change
    between bandwidth regimes — ``DMLC_DSSERVE_WIRE_CODEC`` stays
    ``auto`` (the default) and only the paced wire budget differs, so
    the per-connection decision machinery is what's under test.

    (a) Low bandwidth (5 MB/s — a congested-link shape, well under
    the ~13 MB/s where zlib at its measured ~30 MB/s stops paying),
    small slots so one connection spans many decision windows: auto
    must engage after its first window and beat codec=off >= 1.3x on
    the best timed epoch. (b) High bandwidth (60 MB/s — decisively
    past the engage threshold for any plausible codec estimate), the
    default slot size: auto must decline — within 3% of codec=off,
    i.e. the probe/decision overhead is free on the path that ships
    plain.

    One server per run: a single connection makes the windowed
    engage-point deterministic (no lease-split variance between the
    A and B runs). Shm is pinned off — descriptors would dodge the
    wire this config meters. Identity (rows + per-shard slot shas) is
    asserted within each same-slot-size pair: compressed frames must
    decode bit-identical."""
    ensure_dsserve_data()
    env_common = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_DSSERVE_NUM_SHARDS": "8",
        "DMLC_DSSERVE_SHM": "off",
    }
    low_bps, high_bps = 5_000_000, 60_000_000

    def run(bps: int, codec: str, batch: int, epochs: int) -> dict:
        drain, _led = _dsserve_tier_drain(
            {
                **env_common,
                "BENCH_DSSERVE_BATCH": str(batch),
                "BENCH_DSSERVE_EPOCHS": str(epochs),
                "DMLC_DSSERVE_WIRE_BPS": str(bps),
                "DMLC_DSSERVE_WIRE_CODEC": codec,
            },
            n_servers=1,
        )
        return drain

    # 1250-row slots -> 80 sends/epoch on the one connection: the
    # engage decision at send 8 still leaves 90% of the epoch's bytes
    # to win on. 6250-row slots for the fast wire: the default shape.
    low_off = run(low_bps, "off", 1250, 2)
    low_auto = run(low_bps, "auto", 1250, 2)
    high_off = run(high_bps, "off", 6250, 3)
    high_auto = run(high_bps, "auto", 6250, 3)
    runs = {
        "low_off": low_off, "low_auto": low_auto,
        "high_off": high_off, "high_auto": high_auto,
    }
    identical = (
        low_off["rows"] == low_auto["rows"]
        and low_off["shards"] == low_auto["shards"]
        and high_off["rows"] == high_auto["rows"]
        and high_off["shards"] == high_auto["shards"]
    )
    return {
        **{
            k: {kk: vv for kk, vv in r.items() if kk != "shards"}
            for k, r in runs.items()
        },
        "low_bps_mb": low_bps // 1_000_000,
        "high_bps_mb": high_bps // 1_000_000,
        "identical": identical,
        "low_auto_wire_mb": low_auto.get("bytes_wire_mb", 0.0),
        "low_auto_raw_mb": low_auto.get("bytes_raw_mb", 0.0),
        "codec_low_bw_win": round(
            low_off["best_epoch_secs"]
            / max(low_auto["best_epoch_secs"], 1e-9), 2
        ),
        "codec_high_bw_ratio": round(
            high_auto["best_epoch_secs"]
            / max(high_off["best_epoch_secs"], 1e-9), 3
        ),
    }


# autoscale_phase_shift corpus (ISSUE 16): a small raw .rec whose drain
# cost is set by injected fault:// latency, not CPU — the phase shift
# (cheap -> expensive) is a URI swap, deterministic on any box
AUTOSCALE_ROWS = int(os.environ.get("BENCH_AS_ROWS", "2000"))
AUTOSCALE_DATA = f"/tmp/dmlc_tpu_bench_autoscale_{AUTOSCALE_ROWS}.rec"
AUTOSCALE_INDEX = AUTOSCALE_DATA + ".idx"


def ensure_autoscale_data() -> None:
    if (os.path.exists(AUTOSCALE_DATA)
            and os.path.getsize(AUTOSCALE_DATA) > 0
            and os.path.exists(AUTOSCALE_INDEX)
            and os.path.getsize(AUTOSCALE_INDEX) > 0):
        return
    from dmlc_core_tpu.data.rowrec import encode_row
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream

    tmp, tmpi = AUTOSCALE_DATA + ".tmp", AUTOSCALE_INDEX + ".tmp"
    with FileStream(tmp, "w") as f, FileStream(tmpi, "w") as fi:
        w = IndexedRecordIOWriter(f, fi)
        rng = np.random.default_rng(7)
        for i in range(AUTOSCALE_ROWS):
            w.write_record(encode_row(
                float(i % 2), rng.integers(0, 500, 8, dtype=np.int64),
                rng.normal(size=8).astype(np.float32),
            ), i)
        w.flush_block()
    os.replace(tmp, AUTOSCALE_DATA)
    os.replace(tmpi, AUTOSCALE_INDEX)


def _autoscale_drain_main(rec: str, idx: str) -> None:
    """Worker mode (``bench.py --autoscale-drain rec idx``): one PACED
    trainer draining a dsserve tier through a two-phase workload —
    cheap epochs (plain reads; the paced consume loop is the
    bottleneck, so the tier idles) then expensive epochs (every read
    behind ``fault://`` injected latency; the tier is the bottleneck
    and the trainer's recv-wait stall is the controller's scale-up
    signal). Heartbeats ride the drain so the tracker SEES the stall
    mid-epoch. Host-side only, no jax. Prints per-phase epoch secs,
    total rows, and per-micro-shard slot shas from each phase's first
    epoch (the cross-run identity anchor)."""
    import hashlib

    from dmlc_core_tpu.dsserve import DsServeBatches
    from dmlc_core_tpu.staging.batcher import BatchSpec
    from dmlc_core_tpu.tracker.client import RabitWorker

    cheap = int(os.environ.get("BENCH_AS_CHEAP_EPOCHS", "2"))
    expensive = int(os.environ.get("BENCH_AS_EXP_EPOCHS", "4"))
    # sustained slow phase: spikes far above the per-open read count
    # (the default 2 is two blips, not a phase) and a SMALL cap so one
    # shard is many read ordinals — a spike lands every ~2.5 reads
    # (io/faults.py schedule), so cap=512 puts ~3.4s of injected sleep
    # per epoch on a 1-worker tier, well above the pacing floor
    fault = os.environ.get(
        "BENCH_AS_FAULT", "latency_ms=25,spikes=400,cap=512,seed=5"
    )
    pace_ms = float(os.environ.get("BENCH_AS_PACE_MS", "25"))
    spec = BatchSpec(batch_size=64, layout="ell", max_nnz=8)
    query = f"?index={idx}&shuffle=record&seed=3"
    phase_uris = (
        ("cheap", cheap, f"{rec}{query}"),
        ("expensive", expensive, f"fault://{fault}{rec}{query}"),
    )
    w = RabitWorker()
    w.start()
    rows = 0
    last_hb = 0.0
    epoch = 0
    phase_secs: dict = {}
    shards: dict = {}
    for phase, n_epochs, uri in phase_uris:
        phase_secs[phase] = []
        for i in range(n_epochs):
            t0 = time.perf_counter()
            src = DsServeBatches(
                "dsserve://" + os.environ["DMLC_DSSERVE"] + "/" + uri,
                spec, mode="lease", epoch=epoch,
            )
            if i == 0:  # the phase's identity epoch
                shas: dict = {}
                src.on_slot = lambda shard, seq, p, _s=shas: _s.setdefault(
                    shard, hashlib.sha256()
                ).update(p.tobytes())
            for b in src:
                rows += b.n_valid
                if pace_ms:
                    time.sleep(pace_ms / 1000.0)  # the simulated step
                now = time.monotonic()
                if now - last_hb > 0.2:
                    w.heartbeat()
                    last_hb = now
            src.close()
            if i == 0:
                shards[phase] = {
                    str(s): h.hexdigest() for s, h in shas.items()
                }
            phase_secs[phase].append(round(time.perf_counter() - t0, 3))
            epoch += 1
    w.heartbeat()
    w.shutdown()
    print(json.dumps({
        "rows": rows, "phase_secs": phase_secs, "shards": shards,
    }))


def _autoscale_phase_shift_bench() -> dict:
    """The ``autoscale_phase_shift`` config (ISSUE 16 acceptance): the
    paced two-phase drain twice over REAL dsserve worker processes —

    - **oracle**: a fixed fleet pre-sized at max (2 workers), no
      controller — the hindsight-optimal capacity for the expensive
      phase;
    - **autoscaled**: the fleet opens at min (1 worker) with the
      tracker's closed-loop controller live (DMLC_AUTOSCALE=1:2, the
      elastic DsServeTier actuator); the fault://-latency phase must
      provoke the scale-up.

    Both runs sleep through the same injected latency and the same
    pacing, so the expensive-phase makespan ratio measures the
    CONTROLLER'S reaction cost (detection window + worker spawn), not
    box weather. Invariants: autoscaled expensive-phase makespan
    <= 1.25x oracle, >= 1 scale-up, <= 2 direction changes, and
    rows + per-micro-shard slot shas IDENTICAL across runs (elastic
    join mid-epoch is loss-free through the shard ledger)."""
    from dmlc_core_tpu.tracker import autoscale as _as
    from dmlc_core_tpu.tracker.backends.local import (
        DsServeTier,
        ElasticActuator,
    )
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    ensure_autoscale_data()
    env_common = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "DMLC_TS_INTERVAL": "0.1",
        "DMLC_TASK_ID": "0",
    }
    knobs = (
        "DMLC_SHARD_OVERSPLIT", "DMLC_AUTOSCALE",
        "DMLC_AUTOSCALE_INTERVAL", "DMLC_AUTOSCALE_WINDOW",
        "DMLC_AUTOSCALE_DWELL",
    )
    saved = {k: os.environ.get(k) for k in knobs}

    def run_mode(autoscaled: bool) -> tuple:
        # tracker-process knobs, set BEFORE the tracker exists (the
        # ShardService pins oversplit and the controller reads its
        # config at start)
        os.environ["DMLC_SHARD_OVERSPLIT"] = "6"
        if autoscaled:
            os.environ["DMLC_AUTOSCALE"] = "1:2"
            os.environ["DMLC_AUTOSCALE_INTERVAL"] = "0.25"
            os.environ["DMLC_AUTOSCALE_WINDOW"] = "1.5"
            # dwell does NOT delay the first action, it spaces the
            # ones after it: the scale-up lands as soon as the stall
            # window fills, then a run-length dwell pins the fleet so
            # windowed stall oscillation at 2 workers (and the low-
            # stall drain tail) can't flap it back down mid-measure
            os.environ["DMLC_AUTOSCALE_DWELL"] = "10"
        else:
            os.environ.pop("DMLC_AUTOSCALE", None)
        tracker = None
        tier = None
        try:
            tracker = RabitTracker("127.0.0.1", 1)
            tracker.start(1)
            tracker_env = {
                "DMLC_TRACKER_URI": "127.0.0.1",
                "DMLC_TRACKER_PORT": str(tracker.port),
            }
            tier = DsServeTier(
                1 if autoscaled else 2, {**env_common, **tracker_env}
            )
            client_env = {
                **env_common, **tracker_env,
                "DMLC_DSSERVE": tier.endpoints,
            }
            if autoscaled:
                # the controller inside THIS process's tracker drives
                # the tier; the client learns of joins from the
                # endpoints file (the dmlc-submit wiring, in-process)
                _as.set_actuator(ElasticActuator(tier))
                client_env["DMLC_DSSERVE_FILE"] = tier.endpoints_file
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO, "bench.py"),
                 "--autoscale-drain", AUTOSCALE_DATA, AUTOSCALE_INDEX],
                env=client_env, stdout=subprocess.PIPE, text=True,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"autoscale drain (autoscaled={autoscaled}) failed "
                    f"(rc={proc.returncode}); stdout tail: "
                    f"{proc.stdout[-500:]!r}"
                )
            out = json.loads(proc.stdout)
            status = (
                tracker.autoscaler.status() if tracker.autoscaler
                else None
            )
            summary = tracker.shards.summary()
            return out, status, summary
        finally:
            _as.set_actuator(None)
            if tier is not None:
                tier.stop()
            if tracker is not None:
                tracker.close()

    try:
        oracle, _unused, oracle_sum = run_mode(False)
        auto, status, auto_sum = run_mode(True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    exp_oracle = sum(oracle["phase_secs"]["expensive"])
    exp_auto = sum(auto["phase_secs"]["expensive"])
    identical = (
        oracle["rows"] == auto["rows"]
        and oracle["shards"] == auto["shards"]
    )
    return {
        "oracle": {
            "phase_secs": oracle["phase_secs"], "rows": oracle["rows"],
            "duplicates": oracle_sum.get("duplicates", 0),
        },
        "autoscaled": {
            "phase_secs": auto["phase_secs"], "rows": auto["rows"],
            "duplicates": auto_sum.get("duplicates", 0),
        },
        "identical": identical,
        "scale_ups": (status or {}).get("decisions", {}).get(
            "scale_up", 0
        ),
        "direction_changes": (status or {}).get("direction_changes", 0),
        "cost_spent": (status or {}).get("cost_spent", 0.0),
        "expensive_makespan_oracle": round(exp_oracle, 3),
        "expensive_makespan_autoscaled": round(exp_auto, 3),
        "makespan_ratio": round(exp_auto / max(exp_oracle, 1e-9), 2),
    }


def _allreduce_sgd_main(out: str) -> None:
    """Worker mode (``bench.py --allreduce-sgd out``): one rank of the
    ``allreduce_recovery`` SGD job — per-step "gradients" summed across
    ranks by the tracker-topology collective (tree path pinned: faulted
    ring rounds retry over the tree, whose float fold order differs by
    rounding, and the config asserts BIT equality), params checkpointed
    in memory every SAVE_EVERY rounds, bootstrap-from-peer + replay on
    relaunch (DMLC_NUM_ATTEMPT > 0). Host-side only: numpy, no jax.
    Steps are paced (BENCH_ALLREDUCE_STEP_MS) so both the clean and the
    chaos run are sleep-dominated and the makespan ratio measures
    RECOVERY cost, not box weather."""
    from dmlc_core_tpu.tracker.client import RabitWorker
    from dmlc_core_tpu.tracker.collective import Collective

    steps = int(os.environ.get("BENCH_ALLREDUCE_STEPS", "24"))
    save_every = int(os.environ.get("BENCH_ALLREDUCE_SAVE_EVERY", "4"))
    step_ms = float(os.environ.get("BENCH_ALLREDUCE_STEP_MS", "60"))
    dim = int(os.environ.get("BENCH_ALLREDUCE_DIM", "65536"))

    t0 = time.perf_counter()
    w = RabitWorker()
    rank = w.start()
    world = w.world_size
    c = Collective(w, io_timeout=120)
    params = np.zeros(dim, dtype=np.float64)
    step0 = 0
    if int(os.environ.get("DMLC_NUM_ATTEMPT", "0") or 0) > 0:
        version, state = c.load_checkpoint()
        if state:
            params = np.frombuffer(state, dtype=np.float64).copy()
            step0 = int(version)
    for s in range(step0, steps):
        # deterministic per-(rank, step) gradient: replay after a
        # bootstrap recomputes the identical contribution
        g = np.sin(np.arange(dim) * (rank + 1) + s)
        total = c.allreduce(g, "sum", path="tree")
        params -= 0.01 * (total / world)
        if (s + 1) % save_every == 0:
            c.checkpoint(params.tobytes(), version=s + 1)
        time.sleep(step_ms / 1000.0)
    tmp = f"{out}.rank{rank}.tmp{os.getpid()}.npy"
    np.save(tmp, params)
    os.replace(tmp, f"{out}.rank{rank}.npy")
    recoveries = c.recoveries
    c.close()
    w.shutdown()
    print(json.dumps({
        "rank": rank,
        "secs": round(time.perf_counter() - t0, 3),
        "recoveries": recoveries,
    }))


def _allreduce_recovery_bench() -> dict:
    """The ``allreduce_recovery`` config (ISSUE 11 acceptance): a
    3-worker allreduce-SGD job under a real Supervisor, run clean and
    then with rank 2 SIGKILLed at the start of round 6 (a peer
    checkpoint exists at round 4, so the relaunch exercises true
    bootstrap-from-peer + replay through the survivors' result caches).
    Invariants: the kill-and-recover job completes within 2x the
    clean-run makespan AND every rank's final model is bit-identical to
    the clean run's."""
    import shutil
    import tempfile

    from dmlc_core_tpu.tracker import collective as _collective
    from dmlc_core_tpu.tracker import shardsvc as _shardsvc
    from dmlc_core_tpu.tracker.supervisor import Supervisor
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    n_workers = 3
    tmpdir = tempfile.mkdtemp(prefix="bench_allreduce_")

    def run_drill(tag: str, faults: str) -> dict:
        tracker = RabitTracker("127.0.0.1", n_workers)
        tracker.start(n_workers)
        out = os.path.join(tmpdir, f"model_{tag}")

        def launch(task_id, host, attempt):
            env = {
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "DMLC_TRACKER_URI": "127.0.0.1",
                "DMLC_TRACKER_PORT": str(tracker.port),
                "DMLC_TASK_ID": str(task_id),
                "DMLC_NUM_ATTEMPT": str(attempt),
            }
            env.pop("DMLC_COLLECTIVE_FAULTS", None)
            if faults:
                env["DMLC_COLLECTIVE_FAULTS"] = faults
            return subprocess.Popen(
                [sys.executable, os.path.join(REPO, "bench.py"),
                 "--allreduce-sgd", out],
                env=env, stdout=subprocess.DEVNULL,
            )

        # exactly what backends/local.py registers: shard-lease reclaim
        # and instant collective peer-death notification, coexisting on
        # the observer list
        sup = Supervisor(
            launch, hosts=["localhost"], max_attempt=3,
            host_fail_limit=float("inf"), relaunch_backoff=0.1,
            on_task_failure=[
                _shardsvc.reclaim_task,
                _collective.notify_task_failure,
            ],
        )
        t0 = time.perf_counter()
        try:
            sup.run(n_workers)
        finally:
            tracker.close()
        makespan = time.perf_counter() - t0
        models = [
            np.load(f"{out}.rank{r}.npy") for r in range(n_workers)
        ]
        for r in range(1, n_workers):
            assert np.array_equal(models[r], models[0]), (
                f"{tag}: rank {r} final model differs from rank 0 — "
                "allreduce did not converge ranks"
            )
        return {
            "makespan_secs": round(makespan, 3),
            "relaunches": sup.relaunches,
            "model": models[0],
        }

    try:
        clean = run_drill("clean", "")
        chaos = run_drill(
            "chaos", "kill_seq=6,kill_rank=2,kill_phase=start"
        )
        assert chaos["relaunches"] >= 1, (
            "the injected SIGKILL never fired (no supervisor relaunch)"
        )
        identical = bool(np.array_equal(chaos["model"], clean["model"]))
        return {
            "clean_makespan_secs": clean["makespan_secs"],
            "recovery_makespan_secs": chaos["makespan_secs"],
            "relaunches": chaos["relaunches"],
            "identical": identical,
            "recovery_makespan_ratio": round(
                chaos["makespan_secs"]
                / max(clean["makespan_secs"], 1e-9),
                2,
            ),
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _shard_lease_drain_main(out: str, fileset: str) -> None:
    """Worker mode (``bench.py --shard-lease-drain out fileset``): one
    leaseholder of the ``tracker_kill_recovery`` drill — no rabit
    rendezvous, just the dynamic-shard lease protocol against a
    (possibly dying and relaunching) standalone journaled tracker.
    Each granted shard is "trained" for one paced step, its
    deterministic per-shard contribution written to a tmp file, and
    the commit protocol is write-tmp -> done() -> rename-on-recorded:
    the rename happens only when the ledger says this completion is
    the one that counts, so a post-crash journal replay can never
    double-commit a shard. Steps are paced
    (BENCH_TRACKER_KILL_STEP_MS) so both runs are sleep-dominated and
    the makespan ratio measures RECOVERY cost. Host-side only: numpy,
    no jax."""
    from dmlc_core_tpu.tracker.shardsvc import ShardLeaseClient

    rank = int(os.environ.get("DMLC_TASK_ID", "0"))
    step_ms = float(os.environ.get("BENCH_TRACKER_KILL_STEP_MS", "500"))
    dim = int(os.environ.get("BENCH_TRACKER_KILL_DIM", "4096"))
    t0 = time.perf_counter()
    c = ShardLeaseClient(rank=rank)
    committed = []
    while True:
        r = c.lease(0, fileset)
        status = r.get("status")
        if status == "done":
            break
        if status == "wait":
            time.sleep(float(r.get("backoff", 0.05)))
            continue
        if status != "lease":
            raise RuntimeError(
                f"rank {rank}: unexpected lease reply {r}"
            )
        shard = int(r["shard"])
        time.sleep(step_ms / 1000.0)
        # deterministic per-shard contribution: the fold is a function
        # of WHICH shards completed, never of which rank ran them or
        # in what order — bit-identity across the crash is exact
        part = np.sin(np.arange(dim, dtype=np.float64) * (shard + 1))
        tmp = f"{out}.shard{shard}.tmp{os.getpid()}.npy"
        np.save(tmp, part)
        ack = c.done(0, shard, fileset)
        if ack.get("status") == "recorded":
            os.replace(tmp, f"{out}.shard{shard}.npy")
            committed.append(shard)
        else:
            # duplicate: a peer already owns this shard's commit
            os.unlink(tmp)
    print(json.dumps({
        "rank": rank,
        "secs": round(time.perf_counter() - t0, 3),
        "committed": sorted(committed),
    }))


def _tracker_kill_recovery_bench() -> dict:
    """The ``tracker_kill_recovery`` config (ISSUE 17 acceptance): a
    3-worker dynamic-shard job against a STANDALONE journaled tracker,
    run clean and then with the tracker SIGKILLed mid-epoch and
    relaunched on the SAME port with the SAME journal. Workers ride
    ``connect_worker_retry`` through the outage; the relaunch replays
    the journal with conservative lease expiry. Invariants: every
    micro-shard committed exactly once across the crash, the folded
    final model bit-identical to the clean run's, and the
    kill-and-recover makespan within 2x clean."""
    import shutil
    import signal
    import tempfile

    n_workers = 3
    oversplit = 3
    n_shards = n_workers * oversplit
    tmpdir = tempfile.mkdtemp(prefix="bench_trackerkill_")

    def spawn_tracker(jdir, endpoint, port, port_end):
        if os.path.exists(endpoint):
            os.unlink(endpoint)
        return subprocess.Popen(
            [sys.executable, "-m", "dmlc_core_tpu.tracker.tracker",
             "--host-ip", "127.0.0.1", "--port", str(port),
             "--port-end", str(port_end),
             "--num-workers", str(n_workers), "--journal", jdir,
             "--endpoint-file", endpoint],
            cwd=REPO, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            # oversplit is a TRACKER-side knob (the ledger decides the
            # shard count) — the workers' env alone would be ignored
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "DMLC_SHARD_OVERSPLIT": str(oversplit)},
        )

    def await_endpoint(endpoint, proc, timeout=15.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(endpoint):
                with open(endpoint) as f:
                    ep = json.load(f)
                return int(ep["port"])
            if proc.poll() is not None:
                raise RuntimeError(
                    "standalone tracker died before publishing its "
                    f"endpoint rc={proc.returncode}"
                )
            time.sleep(0.02)
        raise RuntimeError("standalone tracker endpoint never published")

    def run_drill(tag: str, kill_after: float) -> dict:
        jdir = os.path.join(tmpdir, f"journal_{tag}")
        endpoint = os.path.join(tmpdir, f"endpoint_{tag}.json")
        out = os.path.join(tmpdir, f"fold_{tag}")
        t0 = time.perf_counter()
        tracker = spawn_tracker(jdir, endpoint, 9091, 9999)
        port = await_endpoint(endpoint, tracker)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "DMLC_TRACKER_URI": "127.0.0.1",
            "DMLC_TRACKER_PORT": str(port),
            "DMLC_SHARD_OVERSPLIT": str(oversplit),
            "DMLC_TRACKER_RETRY_SECS": "30",
        }
        workers = [
            subprocess.Popen(
                [sys.executable, os.path.join(REPO, "bench.py"),
                 "--shard-lease-drain", out, f"bench://{tag}"],
                env={**env, "DMLC_TASK_ID": str(r)},
                stdout=subprocess.PIPE, text=True,
            )
            for r in range(n_workers)
        ]
        relaunches = 0
        try:
            if kill_after > 0:
                time.sleep(kill_after)
                done_before = sum(
                    os.path.exists(f"{out}.shard{s}.npy")
                    for s in range(n_shards)
                )
                assert done_before < n_shards, (
                    "chaos kill fired after the epoch drained — "
                    "nothing was left to recover"
                )
                tracker.send_signal(signal.SIGKILL)
                tracker.wait()
                # relaunch pinned to the SAME port with the SAME
                # journal — exactly what TrackerSupervisor does
                tracker = spawn_tracker(jdir, endpoint, port, port + 1)
                await_endpoint(endpoint, tracker)
                relaunches = 1
            outs = [w.communicate()[0] for w in workers]
            makespan = time.perf_counter() - t0
            for w in workers:
                assert w.returncode == 0, (
                    f"{tag}: drill worker exited rc={w.returncode}"
                )
        finally:
            tracker.terminate()
            try:
                tracker.wait(timeout=10)
            except subprocess.TimeoutExpired:
                tracker.kill()
        committed: dict = {}
        for o in outs:
            rep = json.loads(o.strip().splitlines()[-1])
            for s in rep["committed"]:
                committed[int(s)] = committed.get(int(s), 0) + 1
        model = np.sum(
            np.stack([
                np.load(f"{out}.shard{s}.npy") for s in range(n_shards)
            ]),
            axis=0,
        )
        return {
            "makespan_secs": round(makespan, 3),
            "relaunches": relaunches,
            "committed": committed,
            "model": model,
        }

    def exactly_once(drill: dict) -> bool:
        return sorted(drill["committed"]) == list(
            range(n_shards)
        ) and all(v == 1 for v in drill["committed"].values())

    try:
        clean = run_drill("clean", 0.0)
        chaos = run_drill("chaos", kill_after=1.5)
        identical = bool(np.array_equal(chaos["model"], clean["model"]))
        return {
            "clean_makespan_secs": clean["makespan_secs"],
            "recovery_makespan_secs": chaos["makespan_secs"],
            "relaunches": chaos["relaunches"],
            "exactly_once": exactly_once(clean) and exactly_once(chaos),
            "identical": identical,
            "recovery_makespan_ratio": round(
                chaos["makespan_secs"]
                / max(clean["makespan_secs"], 1e-9),
                2,
            ),
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _stream_online_bench() -> dict:
    """The ``stream_online`` config (ISSUE 19 acceptance): a paced
    generator process appends to a live stream directory while a
    tail-following trainer drains it through ``StreamSource``. The
    trainer samples its own staleness (``lag_seconds``) at every batch;
    the p99 must stay under the pinned bound — the whole point of the
    manifest watermark is that a follower is never more than a commit
    cadence behind a healthy writer. Afterwards the sealed directory is
    drained post-hoc: rows, order and per-generation sha256 must be
    IDENTICAL to what the live follower saw (tail reads never tear or
    reorder)."""
    import hashlib
    import shutil
    import tempfile
    import threading

    from dmlc_core_tpu.stream import StreamSource, StreamWriter
    from dmlc_core_tpu.stream import manifest as _sm

    n_rows = 4000
    pace_chunk, pace_sleep = 25, 0.01  # ~2500 rows/s generator
    lag_bound_p99 = 2.0

    def row(i: int) -> bytes:
        return (b"online-%07d|" % i) * (1 + i % 3)

    tmpdir = tempfile.mkdtemp(prefix="dmlc_stream_online_")
    try:
        def produce():
            with StreamWriter(
                tmpdir, codec="zlib", block_bytes=4096,
                rotate_bytes=8 << 10, commit_records=50,
            ) as w:
                for i in range(n_rows):
                    w.append(row(i))
                    if i % pace_chunk == pace_chunk - 1:
                        time.sleep(pace_sleep)

        gen_thread = threading.Thread(target=produce)
        t0 = time.perf_counter()
        gen_thread.start()
        src = StreamSource(tmpdir, poll_secs=0.005, max_idle_secs=60.0)
        live = []
        lags = []
        while True:
            b = src.next_batch(64)
            if b is None:
                break
            live.extend(src.extract_records(b))
            lags.append(src.lag_seconds())
        stats = src.io_stats()
        src.close()
        gen_thread.join()
        makespan = time.perf_counter() - t0

        post = StreamSource(tmpdir)
        sealed = []
        while True:
            r = post.next_record()
            if r is None:
                break
            sealed.append(r)
        post.close()

        m = _sm.read_manifest(tmpdir)
        def by_gen_sha(rows):
            out, nxt = [], 0
            for ent in m["sealed"]:
                h = hashlib.sha256()
                for r in rows[nxt:nxt + ent["records"]]:
                    h.update(r)
                out.append(h.hexdigest())
                nxt += ent["records"]
            return out

        lags.sort()
        p99 = lags[min(len(lags) - 1, int(len(lags) * 0.99))] if lags else 0.0
        return {
            "rows": len(live),
            "bit_identical": live == sealed,
            "per_gen_sha_identical": by_gen_sha(live) == by_gen_sha(sealed),
            "lag_p99_seconds": round(p99, 4),
            "lag_max_seconds": round(lags[-1], 4) if lags else 0.0,
            "lag_bound_p99_seconds": lag_bound_p99,
            "rotations": len(m["sealed"]) - 1,
            "commits_seen": stats["commits_seen"],
            "tail_wait_secs": stats["tail_wait_secs"],
            "makespan_secs": round(makespan, 3),
            "follow_rows_per_sec": round(len(live) / max(makespan, 1e-9), 1),
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def ensure_rec_index() -> None:
    """Index file for the bench .rec (uniform frame stride → arithmetic
    offsets; format = IndexedRecordIOWriter's ``key<TAB>offset``)."""
    if os.path.exists(REC_INDEX) and os.path.getsize(REC_INDEX) > 0:
        return
    stride = 8 + 12 + REC_K * 8  # frame header + payload (ensure_rec_data)
    tmp = REC_INDEX + ".tmp"
    with open(tmp, "w") as f:
        chunk = 200_000
        for start in range(0, REC_ROWS, chunk):
            n = min(chunk, REC_ROWS - start)
            ids = np.arange(start, start + n, dtype=np.int64)
            lines = np.char.add(
                np.char.add(np.char.mod("%d", ids), "\t"),
                np.char.mod("%d", ids * stride),
            )
            f.write("\n".join(lines.tolist()) + "\n")
    os.replace(tmp, REC_INDEX)


# window-shuffle knobs for the rec_shuffled_window config: the window is
# the client-side shuffle buffer (records), the merge gap the coalescer's
# waste bound (bytes). A window of 2^18 records over the 400k-row shard
# means ~2 windows/epoch, so the coalesced spans re-read each byte at
# most ~2x — sequential I/O for a full per-record permutation.
WINDOW = int(os.environ.get("BENCH_WINDOW", str(1 << 18)))
MERGE_GAP = int(os.environ.get("BENCH_MERGE_GAP", str(64 << 10)))

# chaos knob: BENCH_FAULT="resets=2,errors=1,seed=7" routes the recordio
# configs through the fault:// injection layer (docs/robustness.md), so
# the staged numbers measure the retry layer healing seeded faults and
# io_stats carries retries/backoff_secs/faults_injected alongside the
# seek/span shape counters.
BENCH_FAULT = os.environ.get("BENCH_FAULT", "")


def _fault_wrapped(path: str) -> str:
    if not BENCH_FAULT:
        return path
    from dmlc_core_tpu.io.faults import wrap_uri

    return wrap_uri(path, BENCH_FAULT)


# escape hatch for A/B: BENCH_LEGACY_SHUFFLE=1 forces the rec_shuffled
# config itself onto the reference's per-record seek loop (the
# rec_shuffled_legacy config always measures it regardless, so the
# gather/legacy ratio stays in every run's JSON)
BENCH_LEGACY_SHUFFLE = os.environ.get("BENCH_LEGACY_SHUFFLE", "") == "1"


def _make_rec_shuffled_stream(mode: str):
    """Shuffled-epoch staging — the access pattern training actually
    uses. mode='record' = full per-record permutation on the gather
    fast path (one shard-wide window, ISSUE 6 tentpole: the split hands
    (buf, starts, sizes) batches to the native gather kernel);
    mode='legacy' = the reference's per-record seek loop
    (&legacy_shuffle=1), kept as the A/B baseline `shuffled_gather_
    speedup` is scored against; mode='batch' = coalesced span shuffle
    (VERDICT r3 #5); mode='window' = the same permutation as 'record'
    with memory bounded to `window` records (ISSUE 1 tentpole). All
    non-legacy modes ride the gather emission."""
    def make(value_dtype: str):
        from dmlc_core_tpu.staging import BatchSpec, ell_batches

        spec = BatchSpec(
            batch_size=BATCH,
            layout="ell",
            max_nnz=REC_K,
            value_dtype=np.dtype(value_dtype),
        )
        shuffle = "record" if mode == "legacy" else mode
        uri = (
            f"{_fault_wrapped(REC_DATA)}?index={REC_INDEX}"
            f"&shuffle={shuffle}&batch_size=4096"
        )
        if mode == "legacy" or (mode == "record" and BENCH_LEGACY_SHUFFLE):
            uri += "&legacy_shuffle=1"
        if mode == "window":
            uri += f"&window={WINDOW}&merge_gap={MERGE_GAP}"
        return (
            ell_batches(uri, spec, nthread=_nthread_for(REC_ROWS), ring=_RING),
            "values",
            REC_DATA,
        )

    return make


LIBSVM_SPARSE_DATA = os.environ.get(
    "BENCH_LIBSVM_DATA_SPARSE",
    f"/tmp/dmlc_tpu_bench_criteo_{REC_ROWS}.libsvm",
)


def ensure_libsvm_sparse_data() -> None:
    """Criteo-like SPARSE libsvm text: 39 ``idx[:val]`` tokens per row,
    ids hashed into the 1M space — the premier reference text format
    (libsvm_parser.h:86-169) in its sparse form, staged to ELL by the
    fused dmlc_parse_libsvm_ell kernel."""
    if (os.path.exists(LIBSVM_SPARSE_DATA)
            and os.path.getsize(LIBSVM_SPARSE_DATA) > 0):
        return
    rng = np.random.default_rng(13)
    tmp = LIBSVM_SPARSE_DATA + ".tmp"
    with open(tmp, "w") as f:
        chunk = 50000
        for start in range(0, REC_ROWS, chunk):
            n = min(chunk, REC_ROWS - start)
            cols = [np.char.mod("%d", rng.integers(0, 2, n))]
            dvals = rng.uniform(0, 1, (n, REC_DENSE))
            for j in range(REC_DENSE):
                cols.append(np.char.mod(f"{j}:%.6f", dvals[:, j]))
            cats = rng.integers(REC_DENSE, REC_SPACE, (n, REC_CAT))
            for j in range(REC_CAT):
                cols.append(np.char.mod("%d", cats[:, j]))  # bare: val 1.0
            lines = cols[0]
            for c in cols[1:]:
                lines = np.char.add(np.char.add(lines, " "), c)
            f.write("\n".join(lines.tolist()) + "\n")
    os.replace(tmp, LIBSVM_SPARSE_DATA)


def _make_libsvm_ell_stream(value_dtype: str):
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="ell",
        max_nnz=REC_K,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        ell_batches(
            LIBSVM_SPARSE_DATA + "?format=libsvm", spec,
            nthread=_nthread_for(REC_ROWS), ring=_RING,
        ),
        "values",
        LIBSVM_SPARSE_DATA,
    )


def _make_libfm_stream(value_dtype: str):
    from dmlc_core_tpu.staging import BatchSpec, ell_batches

    spec = BatchSpec(
        batch_size=BATCH,
        layout="ell",
        max_nnz=REC_K,
        value_dtype=np.dtype(value_dtype),
    )
    return (
        ell_batches(
            LIBFM_DATA + "?format=libfm", spec,
            nthread=_nthread_for(REC_ROWS), ring=_RING,
        ),
        "values",
        LIBFM_DATA,
    )


def run_epoch(make_stream, value_dtype: str) -> dict:
    """One full file → device epoch; rows/sec, file MB/sec, the
    TRANSFERRED bytes/sec (per-batch device bytes × batches — the number
    the infeed-utilization ratio compares against the link probe), and
    the pipeline's per-stage wall-clock breakdown (VERDICT r4 weak #1)."""
    import jax

    from dmlc_core_tpu.staging import StagingPipeline

    stream, block_key, data_path = make_stream(value_dtype)
    # depth 3 measured ~3% over depth 2 steady-state before PR 1 (a
    # deeper in-flight window rides out link jitter); 4 was equal at
    # more HBM. Ring (12 slots) stays > prefetch+depth+2.
    # timer covers pipeline construction: its prefetch thread starts
    # parsing immediately, so an after-construction t0 would let real
    # staging work escape the measurement
    t0 = time.perf_counter()
    pipe = StagingPipeline(stream, depth=3)
    last = None
    batch_bytes = 0
    n_batches = 0
    for dev in pipe:
        last = dev
        n_batches += 1
        if batch_bytes == 0:
            batch_bytes = sum(int(v.nbytes) for v in dev.values())
    if last is not None:
        jax.block_until_ready(last[block_key])
    dt = time.perf_counter() - t0
    # I/O-shape counters: the split's (spans ≪ records proves the
    # coalescer is engaged, seeks=0 proves the local pread fast path
    # carried them) merged with the pipeline's staging counters under
    # "staging" (put counts, packed/per-array path mix, unpack-cache LRU)
    io_stats = pipe.io_stats()
    # pipeline first, source second — and only when the teardown join
    # completed (close_timed_out): an orphaned producer thread may still
    # be reading the stream's ring/mmap buffers
    from dmlc_core_tpu.staging import drain_close

    drain_close(pipe, stream)
    return {
        **({"io_stats": io_stats} if io_stats else {}),
        "rows": pipe.rows_staged,
        "secs": dt,
        "rows_per_sec": pipe.rows_staged / dt,
        "mb_per_sec": os.path.getsize(data_path) / dt / 1e6,
        "xfer_mb_per_sec": batch_bytes * n_batches / dt / 1e6,
        "batch_bytes": batch_bytes,
        "n_batches": n_batches,
        "stage_secs": {
            k: round(v, 4) for k, v in pipe.stage_seconds.items()
        },
    }


def host_epoch(make_stream, value_dtype: str = "float16") -> dict:
    """One host-side-only epoch (iterate the fused producer, no device):
    the parse kernel's ceiling for the matching staged metric. Runs
    INTERLEAVED with the staged epochs (same rotation) so both see the
    same cache and link state — an un-matched window let r3's staged
    number exceed its own ceiling."""
    t0 = time.perf_counter()
    stream, _key, _path = make_stream(value_dtype)
    n = sum(b.n_valid for b in stream)
    dt = time.perf_counter() - t0
    stream.close()
    return {"rows": n, "secs": dt, "rows_per_sec": n / dt}


def raw_infeed_probe(batch_bytes: int, n_batches: int) -> dict:
    """Upper bound for north star #2: device_put of prestaged buffers —
    identical per-batch byte count and in-flight depth as the staged
    recordio epoch, zero parse. The staged/raw ratio is the
    infeed-utilization number BASELINE.md's 'saturate infeed' claim is
    scored by (VERDICT r3 #2)."""
    import jax

    rng = np.random.default_rng(3)
    ring = [
        rng.integers(0, 255, batch_bytes, dtype=np.uint8) for _ in range(3)
    ]
    depth = 3
    inflight = []
    t0 = time.perf_counter()
    for i in range(n_batches):
        inflight.append(jax.device_put(ring[i % len(ring)]))  # noqa: L007 (raw link probe)
        if len(inflight) >= depth:
            jax.block_until_ready(inflight.pop(0))
    for dev in inflight:
        jax.block_until_ready(dev)
    dt = time.perf_counter() - t0
    return {
        "secs": dt,
        "mb_per_sec": batch_bytes * n_batches / dt / 1e6,
    }


class LinkProbe:
    """Host→HBM link heartbeat + sustained anchor (VERDICT r4 #1/#3).

    Where short transfers run faster than sustained ones, a single raw
    probe is no utilization anchor. Two instruments: a 2-put probe runs
    immediately before EVERY task (``link_probe_series``, the spread
    across the run), and one long ``sustained()`` run measures the
    steady rate — the anchor ``infeed_utilization`` is scored against,
    since a staged epoch is sustained traffic. Whether the chip host's
    link needs either is not measured yet (ROADMAP speed item 1)."""

    def __init__(self, nbytes: int, depth: int = 2) -> None:
        rng = np.random.default_rng(9)
        self._bufs = [
            rng.integers(0, 255, nbytes, dtype=np.uint8)
            for _ in range(depth)
        ]
        self._n = 0
        self.samples: list = []  # (tag, mb_per_sec)

    def measure(self, tag: str) -> float:
        import jax

        nb = 0
        t0 = time.perf_counter()
        for b in self._bufs:
            # dirty the head so no layer can dedupe repeat transfers
            b[:8] = np.frombuffer(
                np.int64(self._n).tobytes(), dtype=np.uint8
            )
            self._n += 1
            jax.block_until_ready(jax.device_put(b))  # noqa: L007 (raw link probe)
            nb += b.nbytes
        dt = max(time.perf_counter() - t0, 1e-9)
        mb = nb / dt / 1e6
        self.samples.append((tag, round(mb, 1)))
        return mb

    def stats(self) -> dict:
        vals = sorted(
            mb for tag, mb in self.samples if tag != "warmup"
        )
        return {
            "min": vals[0],
            "median": round(median(vals), 1),
            "max": vals[-1],
            "n": len(vals),
        }

    def sustained(self, total_mb: int = 600) -> dict:
        """Measure the steady host→HBM rate over ``total_mb``.

        A staged epoch is sustained traffic, so utilization is scored
        against THIS, not a 2-put probe reading. Reports the whole-run
        rate and the last-half rate (past any start-up transient)."""
        import jax

        n = max(4, int(total_mb * 1e6 / self._bufs[0].nbytes))
        times = []
        for _i in range(n):
            b = self._bufs[_i % len(self._bufs)]
            b[:8] = np.frombuffer(
                np.int64(self._n).tobytes(), dtype=np.uint8
            )
            self._n += 1
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(b))  # noqa: L007 (raw link probe)
            times.append(time.perf_counter() - t0)
        nb = self._bufs[0].nbytes
        half = times[len(times) // 2:]
        return {
            "mb_per_sec": round(nb * n / sum(times) / 1e6, 1),
            "steady_mb_per_sec": round(
                nb * len(half) / sum(half) / 1e6, 1
            ),
            "n_puts": n,
        }


def run_series(tasks, rounds: int, probe: "LinkProbe"):
    """Round-robin the task list with the start offset ROTATED each
    round, stride len(tasks)/rounds so every task's run positions are
    SPREAD across the early and late windows of the run (a +1 stride
    would leave late-listed tasks always late) — fixed-order runs
    confounded dtype cost with link drift in r3 (VERDICT r3 #6).
    Each config is WARMED before its probe samples: a discarded warmup
    transfer runs first, so the sampled probe reads post-warm link
    state instead of whatever window the previous task left behind —
    an unwarmed first touch spread the pre-PR-1 probe readings 27.9x
    (min/median), drowning real regressions. The sampled
    probe reading is attached to the task's result as ``link_before``.
    Returns {name: [result, ...]}."""
    from dmlc_core_tpu.telemetry import default_registry

    results = {name: [] for name, _fn in tasks}
    for r in range(rounds):
        off = (r * len(tasks)) // max(rounds, 1) % len(tasks)
        order = tasks[off:] + tasks[:off]
        for name, fn in order:
            probe.measure("warmup")  # discarded: warms the link state
            link = probe.measure(name)
            # high-water-mark gauges (io.fetch.concurrency_peak, ...)
            # rewind at the config boundary so each run records ITS
            # peak, not the run-global max the first heavy config set
            default_registry().reset_peak_gauges()
            res = fn()
            peaks = {
                k: v
                for k, v in default_registry().peak_gauge_values().items()
                if v
            }
            if peaks:
                res["peak_gauges"] = peaks
            res["link_before"] = round(link, 1)
            results[name].append(res)
    return results


def _shared_cache_drain_main(rec: str, idx: str) -> None:
    """Worker mode (``python bench.py --shared-cache-drain rec idx``):
    drain one compressed indexed shard host-side through the split
    layer and print one JSON line — rows, secs, this process's decode
    count and shared-tier hits. The parent runs it as a REAL separate
    process so the two-level lookup behaves exactly as N colocated
    trainers would (per-process L1, shared daemon L2 via
    DMLC_BLOCK_CACHE_SOCK / DMLC_BLOCK_CACHE in the environment)."""
    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.telemetry import default_registry

    t0 = time.perf_counter()
    sp = io_split.IndexedRecordIOSplitter(rec, idx, 0, 1)
    t1 = time.perf_counter()
    nbytes = 0
    # steady-state drain rate: construction (index parse — one-time,
    # identical with and without a daemon) is reported separately so
    # the speedup isolates what the shared tier actually changes
    while True:
        chunk = sp.next_batch_ex(16384)
        if chunk is None:
            break
        nbytes += len(chunk)
    dt = time.perf_counter() - t1
    stats = sp.io_stats()
    sp.close()
    reg = default_registry()
    print(json.dumps({
        "rows": stats.get("records", 0),
        "bytes": nbytes,
        "secs": round(dt, 4),
        "construct_secs": round(t1 - t0, 4),
        "mb_per_sec": round(nbytes / dt / 1e6, 2),
        "decodes": reg.histogram("io.codec.decode_seconds").snapshot()[
            "count"
        ],
        "blockcache_hits": sum(
            reg.counter_values("io.blockcache.hits").values()
        ),
    }))


def _shared_cache_bench() -> dict:
    """The ``rec_zlib_shared_cache`` config (ISSUE 7): decode-once-per-
    host, measured with real processes. A private daemon serves a
    job-local socket; reader 1 publishes every decoded block, reader 2
    (the number that matters — the second colocated trainer) should
    serve entirely from shared memory, and a control reader runs with
    the tier forced off. ``shared_cache_speedup`` is reader 2's
    throughput over the control's; ``daemon_hit_rate`` comes from the
    daemon's own counters."""
    import tempfile

    from dmlc_core_tpu.io.blockcache import (
        BlockCacheClient,
        BlockCacheDaemon,
    )

    sock_dir = tempfile.mkdtemp(prefix="dmlc-bench-cache-")
    sock = os.path.join(sock_dir, "cache.sock")
    daemon = BlockCacheDaemon(sock, max_bytes=2 << 30).start()

    def run(extra_env: dict) -> dict:
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--shared-cache-drain", REC_ZLIB_DATA, REC_ZLIB_INDEX],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **extra_env},
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"shared-cache drain worker failed: {out.stderr[-2000:]}"
            )
        return json.loads(out.stdout)

    def best_of(n: int, extra_env: dict) -> dict:
        # fastest of n runs: the drain is ~1-3s on a small shared box,
        # where one scheduler hiccup swings a single sample 2x — the
        # min is the least-contended (honest) reading for both sides
        runs = [run(extra_env) for _ in range(n)]
        return min(runs, key=lambda r: r["secs"])

    # daemon-on runs pin DMLC_BLOCK_CACHE=auto so an operator-level
    # `off` in the outer environment cannot silently measure the
    # fallback path as the feature; the control pins `off` likewise
    on = {"DMLC_BLOCK_CACHE": "auto", "DMLC_BLOCK_CACHE_SOCK": sock}
    try:
        publisher = run(on)
        second = best_of(2, on)
        control = best_of(2, {"DMLC_BLOCK_CACHE": "off"})
        stats = BlockCacheClient(sock).stats() or {}
    finally:
        daemon.close()
        import shutil

        shutil.rmtree(sock_dir, ignore_errors=True)
    lookups = stats.get("hits", 0) + stats.get("misses", 0)
    return {
        "publisher": publisher,
        "second_reader": second,
        "no_daemon": control,
        "shared_cache_speedup": round(
            control["secs"] / max(second["secs"], 1e-9), 2
        ),
        "daemon_hit_rate": round(
            stats.get("hits", 0) / lookups, 4
        ) if lookups else None,
        "daemon_publishes": stats.get("publishes", 0),
        "second_reader_decodes": second["decodes"],
    }


def _telemetry_snapshot() -> dict:
    from dmlc_core_tpu.telemetry import to_json

    return to_json()


def _trace_overhead() -> dict:
    """Flight-recorder cost on rec-bench throughput (ISSUE 8
    acceptance: <=3% vs ``DMLC_TRACE=off``, asserted as a bench
    invariant).

    Protocol: measure (a) how many events one rec shuffled-drain epoch
    actually records with the recorder ON (the real instrumentation
    density — a handful of window/refill spans, since the hot loop
    records per BATCH/WINDOW, never per row), (b) the recorder's
    per-event cost from a tight span loop (min over windows — pure CPU,
    the one number here a shared box cannot inflate honestly), and (c)
    the epoch's row time with the recorder OFF. The reported ``ratio``
    is off-throughput retained = 1 / (1 + events*cost / epoch_secs).

    Why composed instead of a naive on/off A/B: the recorder's true
    cost on this config is ~10 events per 400k-row epoch (<0.01%), and
    direct A/B drains on a noisy shared host measured 0.6-1.16x
    ratios round to round — pure scheduler/page-cache weather, 100x
    the signal. The composed form multiplies two MEASURED quantities
    whose product bounds the A/B difference, and stays falsifiable:
    instrument the per-row path and ``events_per_epoch`` explodes,
    slow the recorder and ``event_cost_us`` does."""
    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.telemetry import tracing

    def drain() -> tuple:
        sp = io_split.create(
            f"{REC_DATA}?index={REC_INDEX}&shuffle=record",
            type="recordio", threaded=False,
        )
        t0 = time.perf_counter()
        rows = 0
        while True:
            g = sp.next_gather_batch(4096)
            if g is None:
                break
            rows += len(g[1])
        dt = time.perf_counter() - t0
        sp.close()
        return rows, dt

    try:
        tracing.set_enabled(True)
        drain()  # discarded: page-cache warmup
        ev0 = tracing.stats()["total_events"]
        rows, dt_on = drain()
        events = tracing.stats()["total_events"] - ev0
        tracing.set_enabled(False)
        r1, d1 = drain()
        r2, d2 = drain()
        off_secs = min(d1 / r1, d2 / r2) * rows  # best-of-2 row time
        # per-event recorder cost: span enter/exit is two clock reads
        # plus a ring append; min over 3 windows rejects preemption
        tracing.set_enabled(True)
        n = 20000
        costs = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _i in range(n):
                with tracing.span("bench:trace_calibration"):
                    pass
            costs.append((time.perf_counter() - t0) / n)
        cost = min(costs)
    finally:
        tracing.set_enabled(None)  # back to the DMLC_TRACE env default
    overhead = (events * cost) / max(off_secs, 1e-9)
    return {
        "events_per_epoch": events,
        "event_cost_us": round(cost * 1e6, 3),
        "on_rows_per_sec": round(rows / dt_on, 1),
        "off_rows_per_sec": round(rows / off_secs, 1),
        "overhead_fraction": round(overhead, 6),
        "ratio": round(1.0 / (1.0 + overhead), 4),
    }


def _codec_summary() -> dict:
    """Codec-path numbers for the perf trajectory: the compression
    ratio actually moved through the codec layer this run (bytes_raw /
    bytes_compressed — encode at data-gen time and decode during the
    rec_zlib epochs tick the same counters with the same ratio) and the
    per-block decode-time percentiles from the
    io.codec.decode_seconds histogram."""
    from dmlc_core_tpu.telemetry import default_registry

    reg = default_registry()
    raw_b = reg.counter("io.codec.bytes_raw").value()
    comp_b = reg.counter("io.codec.bytes_compressed").value()
    hist = reg.histogram("io.codec.decode_seconds").snapshot()
    return {
        "compression_ratio": (
            round(raw_b / comp_b, 4) if comp_b else None
        ),
        "codec_decode_seconds": {
            k: hist[k]
            for k in ("count", "p50", "p90", "p99")
            if k in hist
        },
    }


def main() -> None:
    # time-series sampling stays ON for the whole run (ISSUE 14): the
    # trace_overhead invariant below is measured WITH the 2 s sampler
    # live, proving the windowed-rate layer rides inside the recorder's
    # <=3% budget; the ring's last-window view lands in the report
    from dmlc_core_tpu.telemetry import timeseries as _timeseries
    from dmlc_core_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    _ts_ring = _timeseries.TimeSeriesRing()
    _ts_ring.start()
    ensure_native()
    ensure_data()
    ensure_rec_data()
    ensure_rec_index()
    ensure_rec_zlib_data()
    ensure_csv_data()
    ensure_libfm_data()
    ensure_libsvm_sparse_data()
    from dmlc_core_tpu.data import native

    rounds = EPOCHS
    tasks = [
        ("higgs_f16", lambda: run_epoch(_make_higgs_stream, "float16")),
        ("higgs_host", lambda: host_epoch(_make_higgs_stream)),
        ("rec_f16", lambda: run_epoch(_make_rec_stream, "float16")),
        ("rec_host", lambda: host_epoch(_make_rec_stream)),
        ("higgs_f32", lambda: run_epoch(_make_higgs_stream, "float32")),
        ("rec_f32", lambda: run_epoch(_make_rec_stream, "float32")),
        ("csv_f16", lambda: run_epoch(_make_csv_stream, "float16")),
        ("libfm_f16", lambda: run_epoch(_make_libfm_stream, "float16")),
        ("libsvm_ell_f16",
         lambda: run_epoch(_make_libsvm_ell_stream, "float16")),
        ("rec_shuffled",
         lambda: run_epoch(_make_rec_shuffled_stream("record"), "float16")),
        ("rec_shuffled_legacy",
         lambda: run_epoch(_make_rec_shuffled_stream("legacy"), "float16")),
        ("rec_shuffled_batch",
         lambda: run_epoch(_make_rec_shuffled_stream("batch"), "float16")),
        ("rec_shuffled_window",
         lambda: run_epoch(_make_rec_shuffled_stream("window"), "float16")),
        ("rec_zlib",
         lambda: run_epoch(_make_rec_zlib_stream, "float16")),
    ]
    # probe buffer ≈ the rec f16 packed batch (indices i32 + values f16
    # + label/weight f32, 8-byte aligned sections)
    probe = LinkProbe(BATCH * (REC_K * 6 + 8) + 64)
    probe.measure("warmup")  # first-transfer setup cost stays out
    series = run_series(tasks, rounds, probe)

    def med(name, key="rows_per_sec"):
        return round(median([r[key] for r in series[name]]), 1)

    # raw link upper bound with the recordio epoch's exact transfer
    # shape (kept for r1-r4 comparability; the LinkProbe series is the
    # real anchor now)
    rec_runs = series["rec_f16"]
    batch_bytes = rec_runs[0]["batch_bytes"]
    n_batches = rec_runs[0]["n_batches"]
    raw_mb = raw_infeed_probe(batch_bytes, n_batches)["mb_per_sec"]
    staged_xfer = median([r["xfer_mb_per_sec"] for r in rec_runs])
    link = probe.stats()
    sustained = probe.sustained()
    # utilization scored against the SUSTAINED link rate, since an
    # epoch is sustained traffic. The r4 single-probe version compared a
    # sustained staged measurement against one short-probe window and
    # reported 0.14 for a pipeline that was link-bound (VERDICT r4 weak
    # #1). Can exceed 1.0 when epochs run faster than the sustained
    # anchor did.
    util_samples = [
        r["xfer_mb_per_sec"] / sustained["steady_mb_per_sec"]
        for r in rec_runs
    ]
    infeed_utilization = median(util_samples)
    link_ceiling = max(link["max"], raw_mb)
    stage_secs_rec = {
        k: round(sum(r["stage_secs"][k] for r in rec_runs), 4)
        for k in rec_runs[0]["stage_secs"]
    }

    # f32-vs-f16 staging cost (VERDICT r4 weak #2): on a link-bound
    # pipeline the expected rows/s penalty is exactly the byte ratio,
    # i.e. both dtypes should move the same TRANSFER MB/s. An xfer
    # ratio ≈ 1 proves the f32 gap is pure bytes, not a kernel
    # post-pass (the kernels convert at fill time, fastparse.cc).
    f32_bytes = series["rec_f32"][0]["batch_bytes"]
    rec_byte_ratio = batch_bytes / f32_bytes
    f32_xfer = median(
        [r["xfer_mb_per_sec"] for r in series["rec_f32"]]
    )

    # decode-once-per-host: two real reader processes over the same
    # zlib shard against a job-local daemon + one control without it.
    # A host without AF_UNIX/shm support skips THIS config, not the
    # whole report (the rest of the series already ran).
    try:
        shared_cache = _shared_cache_bench()
    except Exception as e:
        shared_cache = {"skipped": repr(e)}

    # concurrent ranged span fetch vs the one-connection serial
    # baseline at 20 ms injected span latency (ISSUE 9 acceptance:
    # >= 3x AND bit-identical). Injected sleeps dominate both sides, so
    # the ratio is robust to a loaded box; a failure here is the
    # fetcher, not the weather.
    try:
        remote_latency = _remote_latency_bench()
    except Exception as e:
        from dmlc_core_tpu.utils.logging import Error as _DmlcError

        remote_latency = {"skipped": repr(e)}
        # the guard exists for capability-missing hosts; a CHECKED I/O
        # error (truncated span) or a diverging drain (best_of's sha
        # assert) is a fetcher regression and must not silently skip
        # the acceptance invariant
        if isinstance(e, (_DmlcError, AssertionError)):
            remote_latency["failed"] = True

    # dynamic shard service vs static part_index under a straggler
    # (ISSUE 10 acceptance): 3 real worker processes, worker 0 behind
    # fault:// latency — leasing must beat static placement >= 1.5x on
    # epoch makespan with identical rows and per-shard bytes
    try:
        dynamic_shards = _dynamic_shard_bench()
    except Exception as e:
        dynamic_shards = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            # a micro-shard served twice (AssertionError) or a drain
            # worker exiting nonzero (run_mode's RuntimeError) is a
            # shard-service regression, never a capability skip
            dynamic_shards["failed"] = True

    # disaggregated preprocessing vs the all-local pipeline (ISSUE 12
    # acceptance): a 2-worker dsserve tier must drain the latency-
    # dominated zlib gather-shuffled corpus >= 1.5x faster than one
    # local process, with per-micro-shard slot bytes identical
    try:
        dsserve_remote = _dsserve_remote_bench()
    except Exception as e:
        dsserve_remote = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            # a drain worker crashing or diverging is a dsserve
            # regression, never a capability skip
            dsserve_remote["failed"] = True

    # zero-copy same-host transport (ISSUE 18 acceptance): the 2-server
    # drain over the shared-memory slot ring must beat the identically
    # paced loopback-TCP baseline >= 1.8x, slot shas identical, both
    # ledgers exactly-once (a host without POSIX shm skips the config)
    try:
        dsserve_local_shm = _dsserve_local_shm_bench()
    except Exception as e:
        dsserve_local_shm = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            dsserve_local_shm["failed"] = True

    # adaptive wire compression (ISSUE 18 acceptance): codec auto must
    # win >= 1.3x on the paced low-bandwidth wire and stay within 3% of
    # codec=off on the fast wire — per connection, no knob change
    try:
        dsserve_wire_codec = _dsserve_wire_codec_bench()
    except Exception as e:
        dsserve_wire_codec = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            dsserve_wire_codec["failed"] = True

    # closed-loop autoscaling under a phase shift (ISSUE 16
    # acceptance): cheap epochs then a fault://-latency input-bound
    # phase; the tracker's controller must grow the dsserve tier and
    # land within 1.25x of an oracle fixed fleet on the expensive-phase
    # makespan, rows and slot shas identical, <= 2 direction changes
    try:
        autoscale_shift = _autoscale_phase_shift_bench()
    except Exception as e:
        autoscale_shift = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            # a drain worker crashing, a diverging drain or a dead
            # controller is an autoscale regression, never a
            # capability skip
            autoscale_shift["failed"] = True

    # batched point reads vs the naive per-key open-seek-read loop over
    # the latency-injected corpus, plus the warm serve daemon under a
    # paced request load (ISSUE 13 acceptance: >= 5x, bytes
    # bit-identical, served p99 under the ceiling at target QPS)
    try:
        point_lookup = _point_lookup_bench()
    except Exception as e:
        # this config has NO capability dependency (pure CPU I/O, the
        # native kernel has a numpy fallback), so ANY exception is a
        # lookup regression — there is no legitimate skip
        point_lookup = {"skipped": repr(e), "failed": True}

    # worker-side collective under a mid-round SIGKILL (ISSUE 11
    # acceptance): kill-and-recover SGD must finish within 2x the clean
    # makespan with a bit-identical final model
    try:
        allreduce_recovery = _allreduce_recovery_bench()
    except Exception as e:
        allreduce_recovery = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            # diverged ranks / a drill worker crashing is a collective
            # regression, never a capability skip
            allreduce_recovery["failed"] = True

    # control-plane death (ISSUE 17 acceptance): SIGKILL the journaled
    # standalone tracker mid-epoch, relaunch on the same port with the
    # same journal — every micro-shard exactly once, fold bit-identical
    # to the clean run, makespan within 2x clean
    try:
        tracker_kill = _tracker_kill_recovery_bench()
    except Exception as e:
        tracker_kill = {"skipped": repr(e)}
        if isinstance(e, (AssertionError, RuntimeError)):
            # a lost/doubled shard commit or a wedged drill worker is a
            # durability regression, never a capability skip (pure CPU
            # sockets + numpy)
            tracker_kill["failed"] = True

    # streaming follow (ISSUE 19 acceptance): a paced generator vs a
    # live tail-following trainer — staleness p99 under the pinned
    # bound, and the live drain bit-identical (rows, order, per-
    # generation sha) to a post-hoc read of the sealed directory
    try:
        stream_online = _stream_online_bench()
    except Exception as e:
        # pure local CPU I/O + threads: there is no legitimate
        # capability skip, any exception is a streaming regression
        stream_online = {"skipped": repr(e), "failed": True}

    # flight-recorder attribution of this very run (ISSUE 8): snapshot
    # the rings BEFORE the overhead probe (its calibration loop wraps
    # the main thread's ring), then measure the recorder's cost — the
    # trajectory records WHERE time went, not just totals
    from dmlc_core_tpu.telemetry import tracing as _tracing

    _trace_attrib = _tracing.stall_report(_tracing.to_chrome_trace())
    trace_overhead = _trace_overhead()

    # per-config link-probe medians: the global min/median/max collapses
    # every config's window into one undiagnosable spread number
    # (link_variability); per-config medians show WHICH configs ran in
    # degraded link windows
    link_by_config = {
        name: round(
            median([mb for tag, mb in probe.samples if tag == name]), 1
        )
        for name, _fn in tasks
    }

    value = med("higgs_f16")
    host_higgs = med("higgs_host")
    rec_med = med("rec_f16")
    host_rec = med("rec_host")
    # medians are the honest headline on a link whose rate swings >20x
    # under external load; per-task bests record what a fast window
    # achieves (and keep r1-r4 best-of numbers comparable)
    best = {
        name: round(max(r["rows_per_sec"] for r in runs), 1)
        for name, runs in series.items()
    }

    # measurement invariants (VERDICT r3 #6): a staged pipeline cannot
    # out-run its own parser measured in the same window, nor move bytes
    # faster than the fastest link state any probe saw. Small tolerance
    # for timer jitter.
    failures = []
    if value > host_higgs * 1.05:
        failures.append(
            f"higgs staged {value} > host ceiling {host_higgs}"
        )
    if rec_med > host_rec * 1.05:
        failures.append(f"rec staged {rec_med} > host ceiling {host_rec}")
    if staged_xfer > link_ceiling * 1.05:
        failures.append(
            f"staged xfer {staged_xfer:.0f} MB/s > link ceiling "
            f"{link_ceiling:.0f}"
        )
    # falsifiable lower bound: catches a zeroed/NaN ratio (empty runs,
    # broken key) — `not (x > 0)` is True for NaN where `x <= 0` is not
    if not (0.0 < infeed_utilization < float("inf")):
        failures.append(f"infeed_utilization {infeed_utilization:.3f}")
    # the always-on flight recorder must stay within its 3% budget on
    # rec throughput (ISSUE 8 acceptance; NaN-proof form as above)
    if not (trace_overhead["ratio"] >= 0.97):
        failures.append(
            f"flight recorder overhead: traced drain at "
            f"{trace_overhead['ratio']:.4f}x of DMLC_TRACE=off "
            f"(budget >= 0.97)"
        )
    # rec_remote_latency invariant (ISSUE 9): parallel fetch must beat
    # the DMLC_FETCH_THREADS=1 serial baseline >= 3x at 20 ms injected
    # span latency AND drain bit-identical bytes. Only enforced when
    # the config ran (exotic hosts skip the config, not the report) —
    # but a correctness-shaped exception fails the invariant outright.
    if remote_latency.get("failed"):
        failures.append(
            f"rec_remote_latency: {remote_latency['skipped']}"
        )
    if "skipped" not in remote_latency:
        if not remote_latency["bit_identical"]:
            failures.append(
                "rec_remote_latency: parallel drain diverged from the "
                "serial baseline (order/bytes)"
            )
        if not (remote_latency["remote_fetch_speedup"] >= 3.0):
            failures.append(
                f"rec_remote_latency: concurrent fetch only "
                f"{remote_latency['remote_fetch_speedup']}x the serial "
                f"baseline (invariant >= 3x at 20 ms span latency)"
            )
    # dynamic_shard_straggler invariant (ISSUE 10): tracker-leased
    # placement must beat static part_index assignment >= 1.5x on epoch
    # makespan with one worker latency-degraded, and both runs must
    # drain identical rows and per-micro-shard bytes
    if dynamic_shards.get("failed"):
        failures.append(f"dynamic_shard_straggler: {dynamic_shards['skipped']}")
    if "skipped" not in dynamic_shards:
        if not dynamic_shards["identical"]:
            failures.append(
                "dynamic_shard_straggler: dynamic drain diverged from "
                "static (rows or per-shard sha)"
            )
        if not (dynamic_shards["straggler_speedup"] >= 1.5):
            failures.append(
                f"dynamic_shard_straggler: dynamic leasing only "
                f"{dynamic_shards['straggler_speedup']}x static placement "
                "(invariant >= 1.5x with one latency-degraded worker)"
            )
    # dsserve_remote invariant (ISSUE 12): 2 real preprocessing-worker
    # processes must beat the all-local pipeline >= 1.5x on the
    # latency-dominated zlib gather-shuffled drain, with per-micro-
    # shard packed-slot bytes identical and the ledger exactly-once
    if dsserve_remote.get("failed"):
        failures.append(f"dsserve_remote: {dsserve_remote['skipped']}")
    if "skipped" not in dsserve_remote:
        if not dsserve_remote["identical"]:
            failures.append(
                "dsserve_remote: remote drain diverged from the local "
                "pipeline (rows or per-shard slot sha)"
            )
        if not (dsserve_remote["dsserve_speedup"] >= 1.5):
            failures.append(
                f"dsserve_remote: the 2-worker tier only "
                f"{dsserve_remote['dsserve_speedup']}x the all-local "
                f"pipeline (invariant >= 1.5x)"
            )
        # zero-copy receive invariants (ISSUE 18): the pool is warm
        # after the untimed epoch, so the timed drain must receive
        # every payload into pooled memory and every received slot
        # must be adoption-capable — one regression anywhere on the
        # recv-into path flips these off zero
        if dsserve_remote["dsserve"].get("recv_alloc_bytes_timed") != 0:
            failures.append(
                f"dsserve_remote: timed epochs allocated "
                f"{dsserve_remote['dsserve'].get('recv_alloc_bytes_timed')}"
                f" payload bytes off-pool (invariant 0 on the pooled "
                f"recv-into path)"
            )
        if dsserve_remote["dsserve"].get("slot_copies") != 0:
            failures.append(
                f"dsserve_remote: "
                f"{dsserve_remote['dsserve'].get('slot_copies')} received"
                f" slots would force a dispatch_pack copy (invariant 0: "
                f"pooled slots are page-aligned and adoption-capable)"
            )
    # dsserve_local_shm invariants (ISSUE 18): same-host shm transport
    # >= 1.8x the identically paced TCP baseline, bit-identical slots,
    # exactly-once ledgers, and shm must actually have engaged
    if dsserve_local_shm.get("failed"):
        failures.append(f"dsserve_local_shm: {dsserve_local_shm['skipped']}")
    if "skipped" not in dsserve_local_shm:
        if not dsserve_local_shm["identical"]:
            failures.append(
                "dsserve_local_shm: shm drain diverged from the TCP "
                "drain (rows or per-shard slot sha)"
            )
        if dsserve_local_shm["duplicates"]:
            failures.append(
                f"dsserve_local_shm: ledger served "
                f"{dsserve_local_shm['duplicates']} micro-shards twice "
                f"(exactly-once invariant)"
            )
        if not (dsserve_local_shm["shm_slots"] >= 1):
            failures.append(
                "dsserve_local_shm: the shm run moved no slots over "
                "shared memory (transport never engaged)"
            )
        if not (dsserve_local_shm["shm_speedup"] >= 1.8):
            failures.append(
                f"dsserve_local_shm: shm transport only "
                f"{dsserve_local_shm['shm_speedup']}x the paced "
                f"loopback-TCP baseline (invariant >= 1.8x)"
            )
    # dsserve_wire_codec invariants (ISSUE 18): auto engages and wins
    # >= 1.3x where the wire is slow, declines and stays within 3%
    # where it is fast — same knobs both times, bit-identical slots
    if dsserve_wire_codec.get("failed"):
        failures.append(f"dsserve_wire_codec: {dsserve_wire_codec['skipped']}")
    if "skipped" not in dsserve_wire_codec:
        if not dsserve_wire_codec["identical"]:
            failures.append(
                "dsserve_wire_codec: drains diverged across codec "
                "settings (rows or per-shard slot sha)"
            )
        if not (dsserve_wire_codec["codec_low_bw_win"] >= 1.3):
            failures.append(
                f"dsserve_wire_codec: codec auto only "
                f"{dsserve_wire_codec['codec_low_bw_win']}x codec=off on "
                f"the {dsserve_wire_codec['low_bps_mb']} MB/s wire "
                f"(invariant >= 1.3x)"
            )
        if not (dsserve_wire_codec["codec_high_bw_ratio"] <= 1.03):
            failures.append(
                f"dsserve_wire_codec: codec auto at "
                f"{dsserve_wire_codec['codec_high_bw_ratio']}x codec=off "
                f"on the {dsserve_wire_codec['high_bps_mb']} MB/s wire "
                f"(invariant <= 1.03 — auto must decline to compress)"
            )
        if not (
            dsserve_wire_codec["low_auto_wire_mb"]
            < dsserve_wire_codec["low_auto_raw_mb"]
        ):
            failures.append(
                "dsserve_wire_codec: auto never engaged on the "
                "low-bandwidth wire (bytes_wire == bytes_raw)"
            )
    # autoscale_phase_shift invariants (ISSUE 16): the closed-loop
    # controller must react to the input-bound phase (>= 1 scale-up),
    # not thrash (<= 2 direction changes), land within 1.25x of the
    # oracle fixed fleet on the expensive-phase makespan, and elastic
    # joins must be loss-free (rows + slot shas identical across runs)
    if autoscale_shift.get("failed"):
        failures.append(
            f"autoscale_phase_shift: {autoscale_shift['skipped']}"
        )
    if "skipped" not in autoscale_shift:
        if not autoscale_shift["identical"]:
            failures.append(
                "autoscale_phase_shift: autoscaled drain diverged from "
                "the oracle fixed fleet (rows or per-shard slot sha)"
            )
        if not (autoscale_shift["scale_ups"] >= 1):
            failures.append(
                "autoscale_phase_shift: the input-bound phase provoked "
                "no scale-up"
            )
        if not (autoscale_shift["direction_changes"] <= 2):
            failures.append(
                f"autoscale_phase_shift: controller thrashed "
                f"({autoscale_shift['direction_changes']} direction "
                f"changes, invariant <= 2)"
            )
        if not (autoscale_shift["makespan_ratio"] <= 1.25):
            failures.append(
                f"autoscale_phase_shift: expensive-phase makespan "
                f"{autoscale_shift['makespan_ratio']}x the oracle "
                f"fixed fleet (invariant <= 1.25x)"
            )
    # point_lookup_zipf invariants (ISSUE 13): batched lookup must beat
    # the naive per-key open-seek-read loop >= 5x on the Zipfian
    # workload with bit-identical bytes, and the WARM serve daemon must
    # hold its p99 under the ceiling at at least the target QPS
    if point_lookup.get("failed"):
        failures.append(f"point_lookup_zipf: {point_lookup['skipped']}")
    if "skipped" not in point_lookup:
        if not point_lookup["bit_identical"]:
            failures.append(
                "point_lookup_zipf: batched lookup bytes diverged from "
                "the naive per-key baseline"
            )
        if not (point_lookup["batched_speedup"] >= 5.0):
            failures.append(
                f"point_lookup_zipf: batched lookup only "
                f"{point_lookup['batched_speedup']}x the naive per-key "
                f"open-seek-read baseline (invariant >= 5x)"
            )
        if not (
            point_lookup["served"]["p99_ms"]
            <= point_lookup["p99_ceiling_ms"]
        ):
            failures.append(
                f"point_lookup_zipf: served p99 "
                f"{point_lookup['served']['p99_ms']} ms over the "
                f"{point_lookup['p99_ceiling_ms']} ms ceiling"
            )
        if not (
            point_lookup["served"]["qps"] >= point_lookup["target_qps"]
        ):
            failures.append(
                f"point_lookup_zipf: served "
                f"{point_lookup['served']['qps']} QPS under the "
                f"{point_lookup['target_qps']} target"
            )
    # allreduce_recovery invariant (ISSUE 11): a mid-round worker kill
    # + supervisor relaunch + bootstrap-from-peer must land on the SAME
    # final model as the clean run (bit-wise — tree path pinned) and
    # complete within 2x the clean makespan
    if allreduce_recovery.get("failed"):
        failures.append(
            f"allreduce_recovery: {allreduce_recovery['skipped']}"
        )
    if "skipped" not in allreduce_recovery:
        if not allreduce_recovery["identical"]:
            failures.append(
                "allreduce_recovery: final model with injected kill + "
                "relaunch != clean run (bit-wise, tree path)"
            )
        if not (allreduce_recovery["recovery_makespan_ratio"] <= 2.0):
            failures.append(
                f"allreduce_recovery: kill-and-recover makespan "
                f"{allreduce_recovery['recovery_makespan_ratio']}x the "
                "clean run (invariant <= 2x)"
            )
    # tracker_kill_recovery invariant (ISSUE 17): a tracker SIGKILL +
    # journal replay must keep exactly-once shard commits, land on the
    # clean run's fold bit-wise, and recover within 2x the clean
    # makespan
    if tracker_kill.get("failed"):
        failures.append(
            f"tracker_kill_recovery: {tracker_kill['skipped']}"
        )
    if "skipped" not in tracker_kill:
        if not tracker_kill["exactly_once"]:
            failures.append(
                "tracker_kill_recovery: micro-shards not committed "
                "exactly once across the tracker crash"
            )
        if not tracker_kill["identical"]:
            failures.append(
                "tracker_kill_recovery: folded model with tracker "
                "kill + relaunch != clean run (bit-wise)"
            )
        if not (tracker_kill["recovery_makespan_ratio"] <= 2.0):
            failures.append(
                f"tracker_kill_recovery: kill-and-recover makespan "
                f"{tracker_kill['recovery_makespan_ratio']}x the "
                "clean run (invariant <= 2x)"
            )

    # stream_online invariant (ISSUE 19): the live follow must drain
    # the exact sealed corpus (rows, order, per-generation hashes),
    # keep lag_seconds p99 under the pinned bound, and see the writer
    # actually rotate mid-follow
    if stream_online.get("failed"):
        failures.append(f"stream_online: {stream_online['skipped']}")
    if "skipped" not in stream_online:
        if not stream_online["bit_identical"]:
            failures.append(
                "stream_online: live tail-follow drain != post-hoc "
                "read of the sealed corpus (bit-wise)"
            )
        if not stream_online["per_gen_sha_identical"]:
            failures.append(
                "stream_online: per-generation content hashes differ "
                "between live follow and sealed shards"
            )
        if not (
            stream_online["lag_p99_seconds"]
            <= stream_online["lag_bound_p99_seconds"]
        ):
            failures.append(
                f"stream_online: lag_seconds p99 "
                f"{stream_online['lag_p99_seconds']}s over the "
                f"{stream_online['lag_bound_p99_seconds']}s bound"
            )
        if stream_online["rotations"] < 1:
            failures.append(
                "stream_online: the writer never rotated mid-follow "
                "(bench lost its dataset-switch coverage)"
            )

    print(
        json.dumps(
            {
                "metric": "higgs_staged_rows_per_sec",
                "value": value,
                "unit": "rows/sec",
                "vs_baseline": round(value / 1_000_000, 4),
                "best_rows_per_sec": best["higgs_f16"],
                "f32_rows_per_sec": med("higgs_f32"),
                "recordio_staged_rows_per_sec": rec_med,
                "recordio_staged_mb_per_sec": med("rec_f16", "mb_per_sec"),
                "recordio_f32_rows_per_sec": med("rec_f32"),
                "recordio_shuffled_rows_per_sec": med("rec_shuffled"),
                "recordio_shuffled_legacy_rows_per_sec": med(
                    "rec_shuffled_legacy"
                ),
                "recordio_shuffled_batch_rows_per_sec": med(
                    "rec_shuffled_batch"
                ),
                "recordio_shuffled_window_rows_per_sec": med(
                    "rec_shuffled_window"
                ),
                # codec path: rows/s through zlib-compressed blocks and
                # the effective DECODED MB/s (scored against the
                # uncompressed .rec size — the codec wins whenever the
                # link, not the CPU, is the bottleneck), plus the
                # ratio/percentiles from the io.codec.* telemetry
                "recordio_zlib_rows_per_sec": med("rec_zlib"),
                "recordio_zlib_decoded_mb_per_sec": med(
                    "rec_zlib", "mb_per_sec"
                ),
                # host-shared decoded-block cache (ISSUE 7 acceptance):
                # a SECOND process over the same compressed shard served
                # from the per-host daemon vs decoding alone
                "rec_zlib_shared_cache": shared_cache,
                "shared_cache_speedup": shared_cache.get(
                    "shared_cache_speedup"
                ),
                # concurrent span fetch vs serial at 20 ms injected
                # span latency (ISSUE 9): >= 3x, bit-identical
                "rec_remote_latency": remote_latency,
                "remote_fetch_speedup": remote_latency.get(
                    "remote_fetch_speedup"
                ),
                # tracker-leased dynamic sharding vs static part_index
                # under a straggler (ISSUE 10): >= 1.5x on makespan,
                # identical rows + per-micro-shard shas
                "dynamic_shard_straggler": dynamic_shards,
                "straggler_speedup": dynamic_shards.get(
                    "straggler_speedup"
                ),
                # disaggregated preprocessing tier vs the all-local
                # pipeline (ISSUE 12): 2 real dsserve workers >= 1.5x
                # on the latency-dominated drain, slot bytes identical
                "dsserve_remote": dsserve_remote,
                "dsserve_speedup": dsserve_remote.get("dsserve_speedup"),
                # same-host shared-memory slot transport vs the
                # identically paced loopback-TCP baseline (ISSUE 18):
                # >= 1.8x, shas identical, exactly-once, shm engaged
                "dsserve_local_shm": dsserve_local_shm,
                "dsserve_shm_speedup": dsserve_local_shm.get(
                    "shm_speedup"
                ),
                # adaptive wire compression (ISSUE 18): auto wins
                # >= 1.3x on the slow wire, within 3% of off on the
                # fast wire — per connection, no knob change
                "dsserve_wire_codec": dsserve_wire_codec,
                "wire_codec_low_bw_win": dsserve_wire_codec.get(
                    "codec_low_bw_win"
                ),
                "wire_codec_high_bw_ratio": dsserve_wire_codec.get(
                    "codec_high_bw_ratio"
                ),
                # closed-loop autoscaling under a cheap -> fault://-
                # latency phase shift (ISSUE 16): >= 1 scale-up, <= 2
                # direction changes, expensive-phase makespan <= 1.25x
                # the oracle fixed fleet, rows/shas identical
                "autoscale_phase_shift": autoscale_shift,
                "autoscale_makespan_ratio": autoscale_shift.get(
                    "makespan_ratio"
                ),
                # batched point reads vs naive per-key random access on
                # the Zipfian hot-set workload (ISSUE 13): >= 5x,
                # bit-identical, served p99 ceiling at target QPS
                "point_lookup_zipf": point_lookup,
                "point_lookup_speedup": point_lookup.get(
                    "batched_speedup"
                ),
                # worker-side collective under a mid-round SIGKILL
                # (ISSUE 11): kill-and-recover within 2x the clean
                # makespan, final model bit-identical
                "allreduce_recovery": allreduce_recovery,
                "recovery_makespan_ratio": allreduce_recovery.get(
                    "recovery_makespan_ratio"
                ),
                # control-plane death (ISSUE 17): SIGKILL the journaled
                # tracker mid-epoch + same-port relaunch — exactly-once
                # shard commits, bit-identical fold, within 2x clean
                "tracker_kill_recovery": tracker_kill,
                "tracker_recovery_makespan_ratio": tracker_kill.get(
                    "recovery_makespan_ratio"
                ),
                # streaming follow (ISSUE 19): paced generator vs a
                # live tail-following reader — p99 staleness under the
                # pinned bound, drain bit-identical to the sealed reads
                "stream_online": stream_online,
                "stream_lag_p99_seconds": stream_online.get(
                    "lag_p99_seconds"
                ),
                **_codec_summary(),
                # gather/legacy speedup is THE tentpole acceptance
                # number (ISSUE 6: >= 10x): the shuffled record-mode
                # config on the gather fast path vs the reference's
                # per-record seek loop, measured in the same run. The
                # window ratio (ISSUE 1's acceptance number) is scored
                # against the SAME legacy baseline now that record mode
                # itself rides the window machinery. The io shapes
                # prove WHY — spans ≪ records under coalescing, seeks=0
                # on the pread fast path, gather_batches > 0 with
                # gather_fallback_batches == 0 on the native kernel.
                "shuffled_gather_speedup": round(
                    med("rec_shuffled")
                    / max(med("rec_shuffled_legacy"), 1e-9),
                    2,
                ),
                "window_vs_record_shuffle_speedup": round(
                    med("rec_shuffled_window")
                    / max(med("rec_shuffled_legacy"), 1e-9),
                    2,
                ),
                "shuffle_io_shapes": {
                    name: series[name][0].get("io_stats")
                    for name in (
                        "rec_shuffled",
                        "rec_shuffled_legacy",
                        "rec_shuffled_batch",
                        "rec_shuffled_window",
                    )
                },
                "shuffle_window": WINDOW,
                "shuffle_merge_gap": MERGE_GAP,
                "csv_staged_rows_per_sec": med("csv_f16"),
                "libfm_staged_rows_per_sec": med("libfm_f16"),
                "libsvm_ell_staged_rows_per_sec": med("libsvm_ell_f16"),
                "host_parse_rows_per_sec": host_higgs,
                "host_parse_rec_rows_per_sec": host_rec,
                "raw_infeed_mb_per_sec": round(raw_mb, 1),
                "staged_xfer_mb_per_sec": round(staged_xfer, 1),
                "infeed_utilization": round(infeed_utilization, 4),
                "infeed_utilization_samples": [
                    round(u, 4) for u in util_samples
                ],
                "infeed_utilization_vs_burst": round(
                    staged_xfer / link_ceiling, 4
                ),
                "link_sustained_mb_per_sec": sustained,
                "link_probe_mb_per_sec": link,
                "link_variability": round(link["max"] / link["min"], 2),
                "link_probe_by_config": link_by_config,
                "link_probe_series": probe.samples,
                "stage_secs_rec": stage_secs_rec,
                "rec_f32_f16_byte_ratio": round(rec_byte_ratio, 4),
                "rec_f32_xfer_mb_per_sec": round(f32_xfer, 1),
                "rec_f32_f16_xfer_ratio": round(
                    f32_xfer / staged_xfer, 4
                ),
                "invariants_ok": not failures,
                "invariant_failures": failures,
                "best": best,
                "native": native.AVAILABLE,
                "fused_dense_kernel": native.HAS_DENSE,
                "fused_ell_kernel": native.HAS_ELL,
                "fused_csv_kernel": native.HAS_CSV_DENSE,
                "fused_libfm_kernel": native.HAS_LIBFM_ELL,
                "fused_libsvm_ell_kernel": native.HAS_LIBSVM_ELL,
                # staging transfer shape for the headline recordio config:
                # device_puts ≈ n_batches (ONE DMA per batch on the packed
                # path — the whole ISSUE 3 point), dispatch ring depth,
                # and the unpacker-cache LRU counters
                "staging_rec": series["rec_f16"][0]
                .get("io_stats", {})
                .get("staging"),
                # flight recorder (ISSUE 8): overhead invariant inputs
                # and the trace-derived attribution of this very run —
                # stall seconds (wait-shaped stages: host_pull,
                # slot/transfer waits, retry backoff) vs busy seconds
                # per stage, straight off the span rings
                "trace_overhead": trace_overhead,
                "stall_seconds_by_stage": _trace_attrib[
                    "stall_seconds_by_stage"
                ],
                "busy_seconds_by_stage": _trace_attrib[
                    "busy_seconds_by_stage"
                ],
                # windowed time series (ISSUE 14): the sampler ran for
                # the whole bench; the last-30s view is the trajectory
                # shape /metrics.json?window= serves on a live job
                "timeseries_window_30s": _ts_ring.window(30.0),
                "timeseries_samples": len(_ts_ring.samples()),
                "host_cpus": os.cpu_count(),
                # usable CPUs: affinity-mask + cgroup-quota aware — what
                # the parse pools are actually sized from (utils/cpus.py,
                # DMLC_PARSE_THREADS overrides)
                "avail_cpus": _avail_cpus(),
                "parse_threads": _nthread_for(N_ROWS) or 1,
                # full telemetry snapshot (docs/observability.md): the
                # registry every producer ticked during the run — stage
                # duration HISTOGRAMS with percentiles (not just the
                # stage_secs_* sums), io.split shape, retry/fault
                # counters, staging path mix. The perf trajectory now
                # captures tails round over round.
                "telemetry": _telemetry_snapshot(),
            }
        )
    )


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--shared-cache-drain":
        # worker mode: host-side drain only, no jax, no data generation
        _shared_cache_drain_main(sys.argv[2], sys.argv[3])
    elif len(sys.argv) >= 5 and sys.argv[1] == "--dynamic-shard-drain":
        # worker mode: host-side drain of this worker's (static or
        # leased) micro-shards, no jax, no data generation
        _dynamic_shard_drain_main(sys.argv[2], sys.argv[3], sys.argv[4])
    elif len(sys.argv) >= 5 and sys.argv[1] == "--dsserve-drain":
        # worker mode: one trainer-side drain (all-local pipeline or
        # dsserve:// client), host-side only, no jax, no data generation
        _dsserve_drain_main(sys.argv[2], sys.argv[3], sys.argv[4])
    elif len(sys.argv) >= 4 and sys.argv[1] == "--autoscale-drain":
        # worker mode: the paced two-phase (cheap -> fault-latency)
        # dsserve drain of the autoscale bench, host-side only, no jax
        _autoscale_drain_main(sys.argv[2], sys.argv[3])
    elif len(sys.argv) >= 3 and sys.argv[1] == "--allreduce-sgd":
        # worker mode: one rank of the allreduce_recovery SGD drill,
        # numpy-only, no data generation
        _allreduce_sgd_main(sys.argv[2])
    elif len(sys.argv) >= 4 and sys.argv[1] == "--shard-lease-drain":
        # worker mode: one leaseholder of the tracker_kill_recovery
        # drill, numpy-only, no rabit rendezvous
        _shard_lease_drain_main(sys.argv[2], sys.argv[3])
    else:
        main()
