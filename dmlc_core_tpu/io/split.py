"""Record-aligned sharded input splits — data parallelism over byte ranges.

Reference: include/dmlc/io.h:155-302 (InputSplit interface),
src/io/input_split_base.{h,cc} (partition math), line_split.cc,
recordio_split.cc, indexed_recordio_split.cc, single_file_split.h,
threaded_input_split.h, cached_input_split.h, input_split_shuffle.h.

Every worker reads a disjoint, record-aligned slice of a URI set:
``create(uri, part_index, num_parts, type)``. This is the reference's only
model-training parallelism (SURVEY §2.9) and the axis the TPU staging layer
sources from the process mesh (``parallel/``): rank ↔ jax.process_index().

Semantics ported exactly (this is where the bugs live — SURVEY §7 hard part
3); the *implementation* is Pythonic: chunks are bytes, records are bytes
views, hot scans are vectorized numpy, and the native C++ core replaces the
inner loops when present.
"""

from __future__ import annotations

import bisect
import hashlib
import mmap
import os
import random
import re
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..concurrency.threaded_iter import ThreadedIter
from ..telemetry import default_registry as _default_registry
from ..telemetry import tracing as _tracing
from ..utils.logging import Error, check, check_eq
from ..utils.profiler import annotate
from . import codec as _codec
from . import retry as _retry
from . import serializer
from . import spanfetch as _spanfetch
from .filesystem import FileInfo, FileSystem
from .recordio import (
    RecordIOChunkReader,
    decode_chunk,
    first_head_in_words,
    last_head_in_words,
    scan_compressed_blob,
)
from .stream import SeekStream, Stream
from .uri import URISpec, uri_int

__all__ = [
    "InputSplit",
    "InputSplitBase",
    "LineSplitter",
    "RecordIOSplitter",
    "IndexedRecordIOSplitter",
    "SingleFileSplit",
    "ThreadedInputSplit",
    "CachedInputSplit",
    "InputSplitShuffle",
    "DynamicShardSource",
    "create",
    "fileset_signature",
    "normalize_shuffle",
    "plan_coalesced_spans",
]

# 8 MB chunk buffer (reference kBufferSize = 2<<20 uint32 words,
# src/io/input_split_base.h:39-40)
DEFAULT_BUFFER_BYTES = (2 << 20) * 4

# telemetry mirrors of the per-instance I/O-shape counters: the same
# increments feed both the split's io_stats() (per-instance, exact) and
# these process-global registry series (fleet view via heartbeats);
# coalescing shows up globally as spans ≪ records, the pread fast path
# as a flat io.split.seeks
_REG = _default_registry()
_SPANS = _REG.counter("io.split.spans", help="positioned reads issued")
_SEEKS = _REG.counter("io.split.seeks", help="stream seek() calls")
_BYTES_READ = _REG.counter("io.split.bytes_read", help="bytes read by splits")
_INDEX_EVICTIONS = _REG.counter(
    "io.split.index_cache_evictions",
    help="parsed sidecar indexes evicted from the bytes-bounded LRU",
)
_RECORDS = _REG.counter("io.split.records", help="records emitted by splits")
_GATHER_BATCHES = _REG.counter(
    "io.split.gather_batches",
    help="zero-copy (buf, starts, sizes) gather batches emitted",
)
_GATHER_BYTES = _REG.counter(
    "io.split.gather_bytes", help="record bytes referenced by gather batches"
)
_GATHER_FALLBACK = _REG.counter(
    "io.split.gather_fallback_batches",
    help="shuffled emissions that re-framed bytes instead of gathering",
)


class InputSplit:
    """Public interface (reference io.h:155-302)."""

    def next_record(self) -> Optional[bytes]:
        """Next record or None at end of split. For text: one line (no
        trailing newline). For recordio: one record payload, header stripped."""
        raise NotImplementedError

    def next_chunk(self) -> Optional[bytes]:
        """A chunk of whole records (parse fan-out unit), or None."""
        raise NotImplementedError

    def next_batch(self, n_records: int) -> Optional[bytes]:
        """Chunk with a record-count hint.

        The default IGNORES the hint by design — exact parity with the
        reference, whose base InputSplit::NextBatch is ``return
        NextChunk(out_chunk)`` (io.h:230-232) and whose InputSplitBase::
        NextBatchEx forwards to NextChunkEx (input_split_base.h:115-117).
        Only IndexedRecordIOSplitter honors n_records (there as here:
        next_batch_ex below), because only count-indexed splits can seek
        per record."""
        return self.next_chunk()

    def before_first(self) -> None:
        raise NotImplementedError

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        raise NotImplementedError

    def total_size(self) -> int:
        raise NotImplementedError

    def hint_chunk_size(self, nbytes: int) -> None:
        pass

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        """Split a chunk produced by next_chunk back into records."""
        raise NotImplementedError

    def __iter__(self) -> Iterator[bytes]:
        while True:
            rec = self.next_record()
            if rec is None:
                return
            yield rec

    def close(self) -> None:
        pass


def _expand_uris(filesys: FileSystem, uri: str) -> List[str]:
    """';'-separated URI list with regex glob expansion (reference
    ConvertToURIs, input_split_base.cc:96-147, DMLC_USE_REGEX)."""
    out: List[str] = []
    for part in uri.split(";"):
        if not part:
            continue
        name = part
        pos = name.rfind("/")
        if pos < 0 or pos + 1 == len(name):
            out.append(name)
            continue
        parent = name[:pos]
        try:
            listing = filesys.list_directory(parent)
        except (OSError, Error):
            out.append(name)  # parent unlistable: let GetPathInfo report
            continue
        stripped = name.rstrip("/")
        exact = [f for f in listing if f.path.rstrip("/") == stripped]
        if exact:
            out.append(exact[0].path)
            continue
        try:
            pattern = re.compile(stripped)
        except re.error as e:
            raise Error(f"bad regex {stripped!r} in input URI: {e}") from e
        matched = False
        for f in listing:
            if f.type != "file" or f.size == 0:
                continue
            if pattern.fullmatch(f.path.rstrip("/")):
                out.append(f.path)
                matched = True
        if not matched and not exact:
            out.append(name)  # fall through to the missing-file error
    return out


class InputSplitBase(InputSplit):
    """Byte-range sharding core (reference src/io/input_split_base.{h,cc}).

    Subclasses define the record format via ``_align``, ``_is_text``,
    ``seek_record_begin``, ``find_last_record_begin``, ``extract_records``.
    """

    _align = 1
    _is_text = False

    def __init__(
        self,
        uri: str,
        part_index: int = 0,
        num_parts: int = 1,
        filesys: Optional[FileSystem] = None,
        recurse_directories: bool = False,
    ) -> None:
        self.filesys = filesys or FileSystem.get_instance(uri.split(";")[0])
        # retry/fault counters are process-global (io/retry.py); the
        # snapshot makes io_stats() report this split's delta — same
        # idiom for the remote-stream reopen counter (io/spanfetch.py)
        self._retry_snap = _retry.stats()
        self._reopen_snap = _spanfetch.reopens_total()
        self._init_files(uri, recurse_directories)
        self.buffer_size = DEFAULT_BUFFER_BYTES
        self._fs: Optional[Stream] = None
        self._file_ptr = 0
        self.offset_begin = 0
        self.offset_end = 0
        self.offset_curr = 0
        self._overflow = b""
        self._rec_iter: Optional[Iterator[bytes]] = None
        self.reset_partition(part_index, num_parts)

    # -- file table ----------------------------------------------------------
    def _init_files(self, uri: str, recurse: bool) -> None:
        """Reference InitInputFileInfo (input_split_base.cc:149-175):
        expand URIs, descend directories, keep non-empty files."""
        files: List[FileInfo] = []
        for path in _expand_uris(self.filesys, uri):
            try:
                info = self.filesys.get_path_info(path)
            except (OSError, Error):
                continue  # missing candidates fall to the aggregate error
            if info.type == "directory":
                listing = (
                    self.filesys.list_directory_recursive(info.path)
                    if recurse
                    else self.filesys.list_directory(info.path)
                )
                files.extend(
                    f for f in listing if f.type == "file" and f.size != 0
                )
            elif info.size != 0:
                files.append(info)
        if not files:
            raise Error(f"Cannot find any files that match the URI pattern {uri!r}")
        self.files = files
        offsets = [0]
        for f in files:
            if f.size % self._align != 0:
                raise Error(f"file {f.path} does not align by {self._align} bytes")
            offsets.append(offsets[-1] + f.size)
        self.file_offset = offsets

    def total_size(self) -> int:
        return self.file_offset[-1]

    def hint_chunk_size(self, nbytes: int) -> None:
        self.buffer_size = max(nbytes, 1024)

    # -- format hooks --------------------------------------------------------
    def seek_record_begin(self, stream: Stream) -> int:
        """Bytes to skip from the stream's position to the next record
        start."""
        raise NotImplementedError

    def find_last_record_begin(self, data: bytes) -> int:
        """Offset of the last record start within data (0 if none)."""
        raise NotImplementedError

    # -- partition math ------------------------------------------------------
    def reset_partition(self, part_index: int, num_parts: int) -> None:
        """Byte-range computation + record alignment (reference
        ResetPartition, input_split_base.cc:30-64)."""
        ntotal = self.file_offset[-1]
        nstep = (ntotal + num_parts - 1) // num_parts
        nstep = ((nstep + self._align - 1) // self._align) * self._align
        self.offset_begin = min(nstep * part_index, ntotal)
        self.offset_end = min(nstep * (part_index + 1), ntotal)
        self.offset_curr = self.offset_begin
        self._overflow = b""
        self._rec_iter = None
        if self.offset_begin == self.offset_end:
            self._close_fs()
            return
        file_ptr = bisect.bisect_right(self.file_offset, self.offset_begin) - 1
        file_ptr_end = bisect.bisect_right(self.file_offset, self.offset_end) - 1
        # snap the END forward to the next record boundary, unless it already
        # sits on a file boundary (file starts are record starts)
        if self.offset_end != self.file_offset[file_ptr_end]:
            with self._open(file_ptr_end) as fs:
                fs.seek(self.offset_end - self.file_offset[file_ptr_end])
                self.offset_end += self.seek_record_begin(fs)
        # snap the BEGIN forward the same way
        if self.offset_begin != self.file_offset[file_ptr]:
            with self._open(file_ptr) as fs:
                fs.seek(self.offset_begin - self.file_offset[file_ptr])
                self.offset_begin += self.seek_record_begin(fs)
        self.offset_curr = self.offset_begin
        self.before_first()

    def _open(self, file_ptr: int) -> SeekStream:
        s = self.filesys.open(self.files[file_ptr].path, "r")
        check(isinstance(s, SeekStream), "input files must be seekable")
        return s  # type: ignore[return-value]

    def _close_fs(self) -> None:
        if self._fs is not None:
            self._fs.close()
            self._fs = None

    def before_first(self) -> None:
        """Seek back to the partition start (reference
        input_split_base.cc:66-82)."""
        if self.offset_begin >= self.offset_end:
            return
        fp = bisect.bisect_right(self.file_offset, self.offset_begin) - 1
        self._close_fs()
        self._file_ptr = fp
        self._fs = self._open(fp)
        self._fs.seek(self.offset_begin - self.file_offset[fp])
        self.offset_curr = self.offset_begin
        self._overflow = b""
        self._rec_iter = None

    # -- reading -------------------------------------------------------------
    def _read(self, size: int) -> bytes:
        """Multi-file read with NOEOL newline injection at text file joins
        (reference Read, input_split_base.cc:177-219 and PR#385)."""
        # snapping can push offset_begin past offset_end (degenerate tail
        # partition) — reference Read guards this (input_split_base.cc:183)
        if (
            self._fs is None
            or self.offset_begin >= self.offset_end
            or self.offset_curr >= self.offset_end
        ):
            return b""
        size = min(size, self.offset_end - self.offset_curr)
        if size == 0:
            return b""
        out: List[bytes] = []
        nleft = size
        while nleft > 0:
            data = self._fs.read(nleft)
            if data:
                out.append(data)
                nleft -= len(data)
                self.offset_curr += len(data)
                continue
            # current file exhausted
            if self._is_text:
                out.append(b"\n")  # join NOEOL text files safely
                nleft -= 1
            check_eq(
                self.offset_curr,
                self.file_offset[self._file_ptr + 1],
                "file offset not calculated correctly",
            )
            if self._file_ptr + 1 >= len(self.files):
                break
            self._file_ptr += 1
            self._fs.close()
            self._fs = self._open(self._file_ptr)
        return b"".join(out)

    def _read_chunk(self, max_size: int) -> Optional[bytes]:
        """One buffer of COMPLETE records; keeps the partial-record tail as
        overflow (reference ReadChunk, input_split_base.cc:221-258).

        Returns None at end of split, b'' when the buffer is too small for
        one record (caller doubles), else the record bytes.
        """
        olen = len(self._overflow)
        if max_size <= olen:
            return b""
        data = self._overflow + self._read(max_size - olen)
        if len(data) == 0:
            return None
        self._overflow = b""
        if self._is_text:
            if len(data) == olen:
                # no new bytes: the final record has no trailing newline
                # (reference PR#452 NOEOL-at-EOF fix)
                data += b"\n"
        elif len(data) != max_size:
            # non-text last buffer: partition end is a record boundary
            return data
        cut = self.find_last_record_begin(data)
        self._overflow = data[cut:]
        return data[:cut]

    def _next_chunk_ex(self) -> Optional[bytes]:
        """Grow-on-zero buffer loop (reference Chunk::Load,
        input_split_base.cc:260-277)."""
        size = self.buffer_size
        while True:
            chunk = self._read_chunk(size)
            if chunk is None:
                return None
            if len(chunk) == 0:
                size *= 2
                continue
            return chunk

    def next_chunk(self) -> Optional[bytes]:
        return self._next_chunk_ex()

    def next_record(self) -> Optional[bytes]:
        while True:
            if self._rec_iter is not None:
                rec = next(self._rec_iter, None)
                if rec is not None:
                    return rec
            chunk = self._next_chunk_ex()
            if chunk is None:
                return None
            self._rec_iter = self.extract_records(chunk)

    def io_stats(self) -> Dict[str, object]:
        """Robustness counters since construction: transient-failure
        ``retries`` healed, ``backoff_secs`` slept, ``faults_injected``
        by a fault:// source. Counters are process-global deltas —
        exact when one split is active, overlapping otherwise.
        IndexedRecordIOSplitter extends this with its I/O-shape
        counters (spans/seeks/bytes). ``reopens``: remote stream
        connections torn down by a repositioning seek since
        construction (io.fetch.reopens — a serial seek storm over an
        HTTP backend pays one reconnect per count)."""
        return {
            "mode": "sequential",
            "reopens": _spanfetch.reopens_total() - self._reopen_snap,
            **_retry.stats_delta(self._retry_snap),
        }

    def close(self) -> None:
        self._close_fs()


class LineSplitter(InputSplitBase):
    """record = text line (reference src/io/line_split.{h,cc}); align=1."""

    _align = 1
    _is_text = True

    def seek_record_begin(self, stream: Stream) -> int:
        """Skip to just after the next newline run (reference
        line_split.cc:9-26); buffered instead of byte-at-a-time."""
        nstep = 0
        seen_newline = False
        while True:
            buf = stream.read(65536)
            if not buf:
                return nstep
            i = 0
            if not seen_newline:
                j = _find_newline(buf)
                if j < 0:
                    nstep += len(buf)
                    continue
                nstep += j + 1
                seen_newline = True
                i = j + 1
            while i < len(buf) and buf[i] in (0x0A, 0x0D):
                nstep += 1
                i += 1
            if i < len(buf):
                return nstep

    def find_last_record_begin(self, data: bytes) -> int:
        """Reference line_split.cc:27-34."""
        cut = max(data.rfind(b"\n"), data.rfind(b"\r"))
        return cut + 1 if cut > 0 else 0

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        """Non-empty lines; consecutive newlines collapse (reference
        ExtractNextRecord, line_split.cc:36-55 absorbs newline runs)."""
        for line in chunk.replace(b"\r", b"\n").split(b"\n"):
            if line:
                yield line


def _find_newline(buf: bytes) -> int:
    a, b = buf.find(b"\n"), buf.find(b"\r")
    if a < 0:
        return b
    if b < 0:
        return a
    return min(a, b)


class RecordIOSplitter(InputSplitBase):
    """record = RecordIO frame (reference src/io/recordio_split.{h,cc});
    align=4.

    Compressed-block-aware: chunks are decoded (io/recordio.decode_chunk
    — one vectorized detection pass for v1 files, parallel per-block
    decompression for compressed ones) before leaving ``next_chunk``,
    so every downstream consumer — extract_records, the fused native
    kernels, RowRecParser, the staging layer — sees pure v1 frames and
    works on compressed files unchanged. Byte-range sharding still
    snaps to heads via the magic scan (compressed blocks are heads with
    their reserved cflags), and a block is atomic to one shard."""

    _align = 4
    _is_text = False

    def __init__(
        self,
        uri: str,
        part_index: int = 0,
        num_parts: int = 1,
        filesys: Optional[FileSystem] = None,
        recurse_directories: bool = False,
        decode_ctx: Optional[_codec.DecodeContext] = None,
    ) -> None:
        """``decode_ctx``: the block-decode seam (L1 LRU + shared host
        tier + pool, io/codec.py DecodeContext) — injectable so tests
        can pin a private cache or a fake daemon; defaults to the
        process-global two-level context."""
        # set BEFORE super().__init__: reset_partition runs inside it
        # and the decode paths must already have their seam
        self._decode_ctx = (
            decode_ctx
            if decode_ctx is not None
            else _codec.default_decode_context()
        )
        super().__init__(
            uri,
            part_index,
            num_parts,
            filesys=filesys,
            recurse_directories=recurse_directories,
        )

    def _next_chunk_ex(self) -> Optional[bytes]:
        chunk = super()._next_chunk_ex()
        if chunk is None:
            return None
        return decode_chunk(chunk, ctx=self._decode_ctx)

    def seek_record_begin(self, stream: Stream) -> int:
        """Scan forward for a record head (reference recordio_split.cc:9-25),
        buffered with one-word overlap across blocks."""
        pos = 0  # absolute offset of buf[0] from the scan start
        buf = b""
        while True:
            data = stream.read(1 << 16)
            buf += data
            usable = len(buf) & ~3
            if usable >= 8:
                words = np.frombuffer(buf[:usable], dtype="<u4")
                hit = first_head_in_words(words)
                if hit >= 0:
                    return pos + hit * 4
            if not data:
                return pos + len(buf)  # EOF: skip everything (reference :12)
            # keep the last word: it may be the magic of a header whose lrec
            # arrives in the next block
            keep = max(usable - 4, 0)
            pos += keep
            buf = buf[keep:]

    def find_last_record_begin(self, data: bytes) -> int:
        """Reference recordio_split.cc:26-42 (backward scan → we take the
        last forward hit; same record head)."""
        usable = len(data) & ~3
        hit = last_head_in_words(np.frombuffer(data[:usable], dtype="<u4"))
        return hit * 4 if hit >= 0 else 0

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        for rec in RecordIOChunkReader(chunk, 0, 1):
            yield bytes(rec)


def normalize_shuffle(v):
    """Canonicalize a shuffle option (keyword arg or URI string).

    None/0/False → False (off); 'record'/1/True → per-record shuffle
    (reference semantics); 'batch'/2 → coalesced span shuffle;
    'window'/3 → windowed shuffle with coalesced I/O. One resolver for
    the factory and every URI-sugar guard, so option parsing cannot
    drift between call sites."""
    if v in (None, False, 0, "0", ""):
        return False
    if v in ("batch", 2, "2"):
        return "batch"
    if v in ("window", 3, "3"):
        return "window"
    if v in ("record", "1", 1, True):
        return "record"
    raise Error(f"invalid shuffle={v!r}: use 0/1/record/batch/window")


def _plan_span_bounds(
    offs: np.ndarray, sizes: np.ndarray, merge_gap: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized span-planner core: offset-sort the records, then cut
    the sorted run wherever the byte gap from the running span end to
    the next record's start exceeds ``merge_gap``.

    Returns ``(order, starts, ends)``: ``order`` indexes the inputs
    offset-sorted; span j covers sorted positions
    ``order[starts[j]:ends[j]]``. This is the hot path (one call per
    shuffle window, arrays the size of the window); the tuple-level
    ``plan_coalesced_spans`` wraps it for callers and tests."""
    order = np.argsort(offs, kind="stable")
    soffs = offs[order]
    # running max handles entries contained inside a predecessor
    run_end = np.maximum.accumulate(soffs + sizes[order])
    breaks = np.flatnonzero(soffs[1:] - run_end[:-1] > merge_gap) + 1
    starts = np.concatenate(([0], breaks))
    ends = np.concatenate((breaks, [len(offs)]))
    return order, starts, ends


def plan_coalesced_spans(
    entries: List[Tuple[int, int, int]], merge_gap: int
) -> List[Tuple[int, int, List[Tuple[int, int, int]]]]:
    """Coalesce record reads into large contiguous spans.

    ``entries`` is ``[(offset, size, tag), ...]`` in any order; the
    planner sorts by offset and merges a record into the preceding span
    when the gap between the span's end and the record's start is at
    most ``merge_gap`` bytes (0 merges only byte-adjacent records).
    Returns ``[(span_begin, span_end, members)]`` with ``members`` the
    entries the span covers, offset-sorted — one positioned read per
    span serves every member, trading at most ``merge_gap`` wasted
    bytes per merge for one less seek."""
    if not entries:
        return []
    offs = np.asarray([e[0] for e in entries], dtype=np.int64)
    sizes = np.asarray([e[1] for e in entries], dtype=np.int64)
    order, starts, ends = _plan_span_bounds(offs, sizes, merge_gap)
    out: List[Tuple[int, int, List[Tuple[int, int, int]]]] = []
    for s, e in zip(starts.tolist(), ends.tolist()):
        members = [entries[i] for i in order[s:e].tolist()]
        span_end = max(m[0] + m[1] for m in members)
        out.append((members[0][0], span_end, members))
    return out


def _native_shuffle(rnd: random.Random, perm: np.ndarray) -> bool:
    """Shuffle ``perm`` in place bit-identically to ``rnd.shuffle``
    via the native MT19937 kernel; False = caller must fall back to
    ``rnd.shuffle`` (kernel missing, or the permutation is too large
    for the single-word getrandbits rule)."""
    try:
        from ..data import native as _native
    except ImportError:  # data layer unavailable (minimal installs)
        return False
    return _native.shuffle_mt19937(rnd, perm)


def _index_stat_key(index_uri: str, total: int):
    """Cache key for a LOCAL index file — (uri, mtime_ns, size, total)
    — or None (remote/unstattable: no caching, a stat per construction
    there would be the network round trip the cache exists to avoid and
    a stale remote index served forever is worse than a re-read)."""
    path = (
        index_uri[len("file://"):]
        if index_uri.startswith("file://")
        else index_uri
    )
    if "://" in path:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (index_uri, st.st_mtime_ns, st.st_size, total)


def _load_index_uri(index_uri: str, total: int) -> Dict[str, np.ndarray]:
    stream = Stream.create(index_uri, "r")
    with stream:
        text = stream.read().decode()
    return _parse_index_text(text, total, index_uri)


# parsed-index LRU: keyed by (uri, mtime_ns, size, total), bounded by
# TOTAL ARRAY BYTES (DMLC_INDEX_CACHE_MB, default 256) — an lru_cache
# by entry count would pin multi-GB parses for the process lifetime
# long after every splitter referencing them closed
_INDEX_CACHE: "OrderedDict[Tuple, Dict[str, np.ndarray]]" = OrderedDict()
_INDEX_CACHE_BYTES = 0
_INDEX_CACHE_LOCK = threading.Lock()


def _index_cache_budget() -> int:
    return max(0, int(os.environ.get("DMLC_INDEX_CACHE_MB", "256"))) << 20


def _load_index_cached(stat_key) -> Dict[str, np.ndarray]:
    """Parsed index arrays keyed by (uri, mtime_ns, size, total): a
    sharded/threaded fan-out constructs one splitter per sub-shard and
    must not re-read and re-parse the same (possibly large) index file
    per thread — but a rewritten local file re-parses (mtime key). The
    arrays are shared read-only across splitters; parses bigger than
    the whole budget are served uncached."""
    global _INDEX_CACHE_BYTES
    with _INDEX_CACHE_LOCK:
        data = _INDEX_CACHE.get(stat_key)
        if data is not None:
            _INDEX_CACHE.move_to_end(stat_key)
            return data
    data = _load_index_uri(stat_key[0], stat_key[3])
    nbytes = sum(v.nbytes for v in data.values())
    budget = _index_cache_budget()
    if nbytes <= budget:
        with _INDEX_CACHE_LOCK:
            if stat_key not in _INDEX_CACHE:
                _INDEX_CACHE[stat_key] = data
                _INDEX_CACHE_BYTES += nbytes
            _INDEX_CACHE.move_to_end(stat_key)
            while _INDEX_CACHE_BYTES > budget and len(_INDEX_CACHE) > 1:
                _k, old = _INDEX_CACHE.popitem(last=False)
                _INDEX_CACHE_BYTES -= sum(v.nbytes for v in old.values())
                # a many-corpus serve daemon cycling indexes shows up
                # here, not as silent RSS growth (docs/observability.md)
                _INDEX_EVICTIONS.inc()
    return data


def _parse_index_keys(kvals: List[str], index_uri: str) -> np.ndarray:
    """The sidecar's key column as a numpy array (int64 when every key
    parses as an integer — the writer's default ordinals and the common
    user-key shape — else the raw strings), REJECTING duplicates with a
    checked Error: the epoch paths never read keys, but the point-read
    path (io/lookup.py) resolves by them, and a duplicated key silently
    serving whichever record sorts last is a wrong-answer hazard, not a
    formatting nit."""
    try:
        keys = np.asarray(kvals, dtype=np.int64)
    except (ValueError, OverflowError):
        keys = np.asarray(kvals)
    ks = np.sort(keys)
    dup = np.nonzero(ks[1:] == ks[:-1])[0]
    if dup.size:
        raise Error(
            f"index file {index_uri!r}: duplicate key {ks[int(dup[0])]!r} "
            f"({dup.size + 1 if dup.size == 1 else 'several'} keys repeat) "
            f"— a point lookup would silently return an arbitrary one of "
            f"the records sharing it"
        )
    return keys


def _parse_index_text(
    text: str, total: int, index_uri: str
) -> Dict[str, np.ndarray]:
    """Vectorized index parse → read-only numpy arrays. v1 sidecar
    (``key offset``): {'offs', 'sizes'}; compressed-block sidecar
    (``key block:inoff``, docs/recordio.md): the record→block geometry.
    Both carry ``keys`` — the key column in the SAME record order as the
    offset arrays, so the point-read path (io/lookup.py) resolves
    key→position without a second parse. One C-speed str→int64
    conversion instead of a 2-per-record Python loop — the index parse
    sits on every indexed construction's critical path (it gated the
    shuffled-epoch rebuild)."""
    toks = text.split()
    vals = toks[1::2]
    if not vals:
        raise Error(f"empty index file {index_uri!r}")
    check(
        len(toks) % 2 == 0,
        f"index file {index_uri!r}: odd token count (truncated or "
        f"malformed key/offset pairs)",
    )
    keys = _parse_index_keys(toks[0::2], index_uri)
    mixed = Error(
        f"index file {index_uri!r} mixes v1 and compressed-block offsets"
    )
    if ":" in vals[0]:
        out = _parse_compressed_index(vals, keys, total, index_uri, mixed)
    else:
        try:
            raw = np.asarray(vals, dtype=np.int64)
        except ValueError:
            raise mixed from None
        order = np.argsort(raw, kind="stable")
        offs = raw[order]
        sizes = np.concatenate(
            (np.diff(offs), [total - int(offs[-1])])
        ).astype(np.int64)
        out = {"offs": offs, "sizes": sizes, "keys": keys[order]}
    for v in out.values():
        v.setflags(write=False)  # cached arrays are shared across splits
    return out


_COMPRESSED_INDEX_RE = re.compile(r"\d+:\d+(?: \d+:\d+)*")


def _parse_compressed_index(
    vals: List[str], keys: np.ndarray, total: int, index_uri: str,
    mixed: Error,
) -> Dict[str, np.ndarray]:
    """Compressed sidecar: ``key  <block>:<in>`` per record — the block
    frame's file offset and the record's frame start inside the DECODED
    block. Records sort by (block, in-offset), i.e. file order,
    matching the v1 offset sort. Fully vectorized — one C-speed
    ``:``→space rewrite, one numeric text parse, one lexsort: the
    Python tuple-sort this replaces cost ~1s per 400k records and sat
    on every indexed construction, so both shared-cache bench readers
    were paying it before a single block decoded."""
    joined = " ".join(vals)
    # exactly `int:int` per entry, validated in ONE C-speed regex pass:
    # a v1 entry mixed in ('12345'), junk, or a double-colon entry all
    # fail here — an aggregate token-count check alone can be fooled by
    # counts that coincidentally balance ('1:2:3' next to '4'), and
    # np.fromstring's early-stop-with-warning path must never be
    # reached (warnings filters are process-global and index parses run
    # on fan-out threads)
    if _COMPRESSED_INDEX_RE.fullmatch(joined) is None:
        raise mixed
    nums = np.fromstring(
        joined.replace(":", " "), dtype=np.int64, sep=" "
    )
    check_eq(nums.size, 2 * len(vals), "compressed index parse")
    boff = nums[0::2]
    inoff = nums[1::2]
    order = np.lexsort((inoff, boff))
    rec_boff = boff[order]
    rec_inoff = inoff[order]
    boffs, inv = np.unique(rec_boff, return_inverse=True)
    rec_block = inv.astype(np.int64)
    block_sizes = np.concatenate(
        (np.diff(boffs), [total - int(boffs[-1])])
    ).astype(np.int64)
    check(
        bool((block_sizes > 0).all()) and int(boffs[0]) >= 0,
        f"index file {index_uri!r}: block offsets outside the "
        f"{total}-byte dataset",
    )
    # next record's in-block offset within the same block; -1 = the
    # block's last record (slice runs to the decoded end)
    nxt = np.full(len(rec_boff), -1, dtype=np.int64)
    same = rec_block[1:] == rec_block[:-1]
    nxt[:-1][same] = rec_inoff[1:][same]
    return {
        "rec_block": rec_block,
        "rec_inoff": rec_inoff,
        "rec_next": nxt,
        "block_offs": boffs,
        "block_sizes": block_sizes,
        "keys": keys[order],
    }


class _SpanReader:
    """Positioned span reads over a split's file table, by absolute
    dataset offset (spans may cross file boundaries — the index is
    global).

    Local files are served as ZERO-COPY ``mmap`` views: a span "read"
    is a memoryview of the page cache — no buffer allocation, no
    memcpy, no seek syscall, and no shared stream cursor, so the
    window-shuffle readahead thread can plan while the consumer thread
    drains without racing ``InputSplitBase._fs`` — and the gather
    kernel parses shuffled records straight out of the mapping. Views
    stay valid until ``close()`` (which defers unmapping while any
    view is still exported — the ``BufferError`` guard below). Files
    that cannot map (empty, special) fall back to ``os.pread`` on a
    cached descriptor; remote backends fall back to one private
    SeekStream per file (seek+read pairs, counted in ``seeks``)."""

    def __init__(
        self,
        files: List[FileInfo],
        file_offset: List[int],
        filesys: FileSystem,
    ) -> None:
        self._files = files
        self._file_offset = file_offset
        self._filesys = filesys
        self._fds: Dict[int, int] = {}
        self._mmaps: Dict[int, mmap.mmap] = {}
        self._streams: Dict[int, SeekStream] = {}
        self.seeks = 0

    def _local_path(self, fp: int) -> Optional[str]:
        path = self._files[fp].path
        if path.startswith("file://"):
            return path[len("file://"):]
        return None if "://" in path else path

    def _read_in_file(self, fp: int, rel_off: int, size: int):
        mm = self._mmaps.get(fp)
        if mm is not None:
            return memoryview(mm)[rel_off : rel_off + size]
        fd = self._fds.get(fp)
        if fd is None and fp not in self._streams:
            local = self._local_path(fp)
            if local is not None:
                fd = os.open(local, os.O_RDONLY)
                try:
                    mm = mmap.mmap(fd, 0, access=mmap.ACCESS_READ)
                except (OSError, ValueError):
                    self._fds[fp] = fd  # unmappable: pread fallback
                else:
                    os.close(fd)
                    fd = None
                    self._mmaps[fp] = mm
                    return memoryview(mm)[rel_off : rel_off + size]
            else:
                s = self._filesys.open(self._files[fp].path, "r")
                check(
                    isinstance(s, SeekStream), "input files must be seekable"
                )
                self._streams[fp] = s  # type: ignore[assignment]
        out: List[bytes] = []
        if fd is not None:
            while size > 0:
                data = os.pread(fd, size, rel_off)
                if not data:
                    break
                out.append(data)
                rel_off += len(data)
                size -= len(data)
        else:
            stream = self._streams[fp]
            stream.seek(rel_off)
            self.seeks += 1
            _SEEKS.inc()
            while size > 0:
                data = stream.read(size)
                if not data:
                    break
                out.append(data)
                size -= len(data)
        return out[0] if len(out) == 1 else b"".join(out)

    def read(self, offset: int, size: int):
        """Span bytes at absolute dataset ``offset`` — a zero-copy
        memoryview when one mmapped file covers the span, else joined
        bytes. File-boundary walk shared with the fetcher
        (``spanfetch.iter_file_segments``)."""
        out: List[bytes] = []
        for fp, rel, take, _base in _spanfetch.iter_file_segments(
            self._file_offset, len(self._files), offset, size
        ):
            data = self._read_in_file(fp, rel, take)
            if not data:
                break
            out.append(data)
            if len(data) < take:
                break
        return out[0] if len(out) == 1 else b"".join(out)

    def readinto(self, offset: int, out: memoryview) -> int:
        """Fill ``out`` with the span at absolute dataset ``offset``;
        returns bytes written. The readinto form of ``read`` for the
        preallocated window buffer: the mmap fast path copies straight
        from the page cache into the caller's buffer (one memcpy, no
        intermediate bytes object), so a multi-span window never holds
        both a parts list and its join."""
        written = 0
        for fp, rel, take, base in _spanfetch.iter_file_segments(
            self._file_offset, len(self._files), offset, len(out)
        ):
            data = self._read_in_file(fp, rel, take)
            if not data:
                break
            out[base : base + len(data)] = data
            written = base + len(data)
            if len(data) < take:
                break
        return written

    def close(self) -> None:
        for fd in self._fds.values():
            try:
                os.close(fd)
            except OSError:
                pass
        self._fds.clear()
        for mm in self._mmaps.values():
            try:
                mm.close()
            except BufferError:
                pass  # a handed-out span view is still alive; GC finishes
        self._mmaps.clear()
        for s in self._streams.values():
            s.close()
        self._streams.clear()


class IndexedRecordIOSplitter(RecordIOSplitter):
    """Shards by RECORD COUNT via an external index file, with optional
    per-epoch shuffled batched reads (reference
    src/io/indexed_recordio_split.{h,cc}).

    Index file: whitespace-separated ``index offset`` pairs
    (ReadIndexFile, indexed_recordio_split.cc:43-62).

    ``shuffle`` modes — all three ride ONE emission path (the window
    machinery below: coalesced-span loads into a client-side buffer,
    vectorized/index-driven emission, optional zero-copy
    ``next_gather_batch``); they differ only in the permutation they
    emit and how it is cut into windows:

    - ``True`` / ``'record'``: full per-record permutation — the
      reference's NextBatchEx shuffle order
      (indexed_recordio_split.cc:159-191) served as ONE window covering
      the whole shard on local uncompressed files: every byte is read
      once through coalesced spans (a zero-copy mmap of the page
      cache) and records leave the buffer in permutation order.
      Compressed or remote sources bound the window to ``window``
      records instead (same order — windows only cut the global
      permutation — but a shard-wide buffer there would materialize
      the whole shard in RAM).
      ``legacy_shuffle=True`` (URI: ``&legacy_shuffle=1``) forces the
      reference's literal one-seek-per-record loop instead — same
      order, kept for A/B measurement of the gather fast path.
    - ``'batch'``: permute SPANS of ``batch_size`` contiguous records
      (records inside a span keep file order) — the chunk-shuffle trade
      every production reader makes; the span-expanded per-record
      permutation is served through the same windowed loader, so each
      window's spans coalesce and prefetch like window mode.
    - ``'window'``: full per-record permutation (identical epoch order
      to ``'record'`` for the same seed) with bounded memory — the
      permutation is cut into windows of ``window`` records, each
      window's index entries are sorted by byte offset and merged into
      large spans (``plan_coalesced_spans``, gap threshold
      ``merge_gap``), the spans are read with one positioned read each
      (``os.pread``/mmap on local files — no seek syscalls,
      thread-safe; REMOTE files ride the concurrent span fetcher,
      io/spanfetch.py — parallel ranged reads on pooled retrying
      connections, ``DMLC_FETCH_THREADS``/``DMLC_FETCH_INFLIGHT_MB``,
      with fetch→decode overlap on compressed shards), and the
      window's records are emitted from the client-side buffer
      in permutation order. A ThreadedIter readahead stage loads window
      k+1's spans while the consumer drains window k. Memory is bounded
      by ~2-3 windows of records; read amplification is bounded by the
      merged gap bytes.

    Emission from the buffer is batched and index-driven, never
    per-record Python: ``next_batch_ex`` re-frames whole batches with
    one fancy-index gather (the NumPy fallback path), and
    ``next_gather_batch`` hands ``(buf, starts, sizes)`` views straight
    to a native gather kernel (staging/fused.py) with zero copies —
    docs/shuffle.md.
    """

    KRAND_MAGIC = 111  # reference indexed_recordio_split.h:82

    def __init__(
        self,
        uri: str,
        index_uri: str,
        part_index: int = 0,
        num_parts: int = 1,
        batch_size: int = 256,
        shuffle=False,
        seed: int = 0,
        epoch: int = 0,
        skip_records: int = 0,
        window: int = 65536,
        merge_gap: int = 65536,
        readahead: bool = True,
        legacy_shuffle: bool = False,
        filesys: Optional[FileSystem] = None,
        decode_ctx: Optional[_codec.DecodeContext] = None,
    ) -> None:
        """``epoch``/``skip_records``: data-position fast-forward (§5.4
        mid-epoch resume). The permutation is derived from (seed, epoch)
        alone — a DOCUMENTED divergence from the reference's persistent
        RNG (indexed_recordio_split.cc:221-233 reshuffles with carried
        state), which makes any epoch's read order reproducible without
        replaying the epochs before it. ``skip_records`` skips that many
        records of the starting epoch arithmetically (no I/O); in
        ``shuffle='batch'`` mode it must land on a span boundary and in
        ``shuffle='window'`` on a window boundary — the positions a
        batch-/window-granular consumer naturally checkpoints at.

        ``window``/``merge_gap``/``readahead`` apply to
        ``shuffle='window'``: records per shuffle window, the byte gap
        up to which adjacent reads coalesce into one span, and whether
        a background thread prefetches the next window's spans."""
        # one resolver with the factory/URI path (normalize_shuffle), so
        # a typo'd mode raises here too instead of silently degrading to
        # the per-record seek storm
        mode = normalize_shuffle(shuffle)
        self.shuffle_mode: Optional[str] = mode if mode else None
        self.shuffle = self.shuffle_mode is not None
        # legacy escape hatch: the reference's literal per-record seek
        # loop for shuffle='record' (A/B baseline for the gather path)
        self._legacy_record = bool(legacy_shuffle) and mode == "record"
        self.batch_size = batch_size
        check(window >= 1, f"window={window} must be >= 1")
        check(merge_gap >= 0, f"merge_gap={merge_gap} must be >= 0")
        self.window = window
        self.merge_gap = merge_gap
        self._readahead = readahead
        # window-shuffle pipeline state (set before super().__init__ —
        # reset_partition/before_first run inside it and tear these
        # down). A loaded window is (buf, rel, size): span bytes plus
        # per-record start/length in permutation order.
        _WinBuf = Tuple[np.ndarray, np.ndarray, np.ndarray]
        self._win_iter: Optional[ThreadedIter[_WinBuf]] = None
        self._win_gen: Optional[Iterator[_WinBuf]] = None
        self._win_buf: Optional[_WinBuf] = None
        self._win_pos = 0
        self._win_start = 0
        self._win_skip = 0
        self._all_local: Optional[bool] = None  # resolved lazily from files
        self._span_reader: Optional[_SpanReader] = None
        self._span_fetcher: Optional[_spanfetch.SpanFetcher] = None
        # I/O-shape counters (cumulative across epochs; io_stats())
        self.spans_read = 0
        self.seek_calls = 0
        self.bytes_read = 0
        self.records_emitted = 0
        self.gather_batches = 0
        self.gather_bytes = 0
        self.gather_fallback_batches = 0
        self._seed = seed
        self.epoch = epoch - 1  # before_first() increments into `epoch`
        self._skip_next = skip_records
        self.records_consumed = 0
        self._index_loaded = False
        # numpy index mirror: per-record file offsets and framed sizes
        # (vectorized span planning + arithmetic range reads; no
        # per-record tuple list — the parse is one C-speed conversion,
        # shared across sub-shard splitters via _load_index_cached)
        self._index_offs = np.empty(0, dtype=np.int64)
        self._index_sizes = np.empty(0, dtype=np.int64)
        # the sidecar's key column, record order (None until the index
        # loads) — the point-read path (io/lookup.py) resolves by it
        self._index_keys: Optional[np.ndarray] = None
        # compressed-block geometry (set by _read_index_file when the
        # sidecar carries block:in-offset pairs — docs/recordio.md)
        self._compressed = False
        self._rec_block = np.empty(0, dtype=np.int64)  # block id per record
        self._rec_inoff = np.empty(0, dtype=np.int64)  # offset in decoded blk
        self._rec_next = np.empty(0, dtype=np.int64)  # next rec's inoff | -1
        self._block_offs = np.empty(0, dtype=np.int64)  # block file offsets
        self._block_sizes = np.empty(0, dtype=np.int64)  # on-disk framed size
        self._cache_key: object = None
        self.decode_cache_hits = 0
        self.decode_cache_misses = 0
        self._index_uri = index_uri
        self.index_begin = 0
        self.index_end = 0
        self._current = 0
        self._n_overflow = 0
        self._permutation: List[int] = []
        super().__init__(
            uri, part_index, num_parts, filesys=filesys,
            decode_ctx=decode_ctx,
        )

    def _read_index_file(self) -> None:
        total = self.file_offset[-1]
        skey = _index_stat_key(self._index_uri, total)
        data = (
            _load_index_cached(skey)
            if skey is not None
            else _load_index_uri(self._index_uri, total)
        )
        self._index_loaded = True
        self._index_keys = data.get("keys")
        if "offs" in data:
            self._index_offs = data["offs"]
            self._index_sizes = data["sizes"]
            return
        self._compressed = True
        self._rec_block = data["rec_block"]
        self._rec_inoff = data["rec_inoff"]
        self._rec_next = data["rec_next"]
        self._block_offs = data["block_offs"]
        self._block_sizes = data["block_sizes"]
        # decoded-block cache identity: per-file (path, size, local
        # mtime_ns, backend etag) + total size + block-layout digest +
        # (per lookup) the block's file offset. The mtime term makes an
        # IN-PLACE rewrite of a local file a different cache identity
        # even when the new content reproduces the exact block
        # geometry; remote backends carry whatever change token their
        # stat surfaced (S3/GCS/HTTP ETag, WebHDFS modificationTime —
        # FileInfo.etag), so an in-place remote rewrite misses instead
        # of serving stale decoded bytes; backends with no token fall
        # back to path+size+layout identity. Every component is a plain
        # str/int and the layout term a sha1 digest (NOT Python's
        # seeded hash()), so the identity is stable ACROSS processes —
        # the shared host tier (io/blockcache.py) keys on it.
        sig = []
        for f in self.files:
            path = f.path
            local = (
                path[len("file://"):]
                if path.startswith("file://")
                else (None if "://" in path else path)
            )
            mtime = 0
            if local is not None:
                try:
                    mtime = os.stat(local).st_mtime_ns
                except OSError:
                    pass
            sig.append(
                (path, int(f.size), mtime, getattr(f, "etag", "") or "")
            )
        self._cache_key = (
            tuple(sig),
            int(total),
            hashlib.sha1(self._block_offs.tobytes()).hexdigest(),
        )
        # byte-offset anchors: a record 'sits at' its block's file
        # offset, which keeps reset_partition's offset_begin/offset_end
        # bookkeeping meaningful (sizes are a compressed-path no-op)
        self._index_offs = self._block_offs[self._rec_block]
        self._index_sizes = np.zeros(len(self._rec_block), dtype=np.int64)

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        """Record-count range (reference indexed_recordio_split.cc:12-41)."""
        if not self._index_loaded:
            self._read_index_file()
        ntotal = len(self._index_offs)
        nstep = (ntotal + num_parts - 1) // num_parts
        if part_index * nstep >= ntotal:
            self.offset_begin = self.offset_end = self.offset_curr = 0
            self.index_begin = self.index_end = 0
            self._permutation = []
            self._current = 0
            self._n_overflow = 0
            self._overflow = b""
            self._rec_iter = None
            self._teardown_window_pipeline()
            self._close_fs()
            return
        self.index_begin = part_index * nstep
        self.offset_begin = int(self._index_offs[self.index_begin])
        self.index_end = min((part_index + 1) * nstep, ntotal)
        if self.index_end < ntotal:
            self.offset_end = int(self._index_offs[self.index_end])
        else:
            self.offset_end = self.file_offset[-1]
        self._n_overflow = 0
        self.before_first()

    def before_first(self) -> None:
        """Starts the next epoch: derives the permutation from
        (seed, epoch) — deterministic per epoch, so a resume can rebuild
        epoch N's exact read order directly (reference
        indexed_recordio_split.cc:221-233 reshuffles with persistent RNG
        state instead; divergence documented on __init__)."""
        if self.index_end <= self.index_begin:
            return
        self.epoch += 1
        rnd = random.Random(
            self.KRAND_MAGIC + self._seed + 1_000_003 * self.epoch
        )
        if self.shuffle_mode == "batch":
            # tear the previous epoch's readahead down FIRST: a live
            # producer slicing a half-built permutation would issue (and
            # count) span reads for a window that is about to be thrown
            # away
            self._teardown_window_pipeline()
            # permute span STARTS; each span is batch_size contiguous
            # records served in file order. Only FULL spans are
            # shuffled — the remainder span (ntotal % batch_size
            # records) always reads last, so every multiple of
            # batch_size is a span boundary and therefore a resumable
            # position (skip_records would otherwise land inside the
            # short span whenever the shuffle placed it early). The
            # span permutation is then expanded to a per-record
            # permutation so batch mode rides the same windowed
            # gather emission as record/window.
            total = self.index_end - self.index_begin
            full_end = self.index_begin + (total // self.batch_size) * (
                self.batch_size
            )
            span_starts = list(
                range(self.index_begin, full_end, self.batch_size)
            )
            rnd.shuffle(span_starts)
            if full_end < self.index_end:
                span_starts.append(full_end)
            starts = np.asarray(span_starts, dtype=np.int64)
            counts = np.minimum(starts + self.batch_size, self.index_end) - (
                starts
            )
            pos = np.arange(int(counts.sum()), dtype=np.int64)
            self._permutation = np.repeat(starts, counts) + (
                pos - np.repeat(np.cumsum(counts) - counts, counts)
            )
            self._current = 0
        elif self.shuffle_mode in ("record", "window"):
            self._teardown_window_pipeline()
            if self._legacy_record:
                self._permutation = list(
                    range(self.index_begin, self.index_end)
                )
                rnd.shuffle(self._permutation)
            else:
                # window mode emits the SAME (seed, epoch) permutation
                # as record mode — the window machinery only changes
                # how the bytes reach the buffer, never the order they
                # leave it. The native MT19937 kernel replays
                # random.Random's exact draw/swap sequence (parity
                # tested), so the permutation stays bit-identical to
                # the legacy loop's whichever path computes it.
                perm = np.arange(
                    self.index_begin, self.index_end, dtype=np.int64
                )
                if not _native_shuffle(rnd, perm):
                    rnd.shuffle(perm)  # same swaps, interpreter speed
                self._permutation = perm
            self._current = 0
        else:
            self._current = self.index_begin
        self._n_overflow = 0
        self.records_consumed = 0
        if self._skip_next:
            self._fast_forward(self._skip_next)
            self._skip_next = 0
        super().before_first()

    def _fast_forward(self, n: int) -> None:
        """Skip ``n`` records of the CURRENT epoch arithmetically."""
        total = self.index_end - self.index_begin
        check(
            0 <= n <= total,
            f"skip_records={n} outside this shard's {total} records",
        )
        if self.windowed:
            if self.shuffle_mode == "batch":
                # only FULL spans shuffle (the remainder span reads
                # last), so resumable positions are exactly the
                # batch_size multiples inside the full-span range, plus
                # end-of-shard
                full = (total // self.batch_size) * self.batch_size
                check(
                    (n % self.batch_size == 0 and n <= full) or n == total,
                    f"skip_records={n} lands inside a shuffled span "
                    f"(checkpoint at span boundaries — batch_size="
                    f"{self.batch_size} multiples)",
                )
            elif self.shuffle_mode == "window":
                check(
                    n % self.window == 0 or n == total,
                    f"skip_records={n} lands inside a shuffled window of "
                    f"{self.window} (checkpoint at window boundaries — "
                    f"window={self.window} multiples)",
                )
            # record mode: any position resumes (the first window is
            # simply sliced from n on, so skipped records are never read)
            W = self._eff_window()
            self._win_start = n // W
            self._win_skip = n - self._win_start * W
        elif self._legacy_record:
            self._current = n
        else:
            self._current = self.index_begin + n
        self.records_consumed = n

    def _read_at(self, offset: int, size: int) -> bytes:
        """Seek to an absolute dataset offset and read (the shuffle path's
        per-record random I/O, reference indexed_recordio_split.cc:163-191)."""
        fp = bisect.bisect_right(self.file_offset, offset) - 1
        if fp != self._file_ptr or self._fs is None:
            self._close_fs()
            self._file_ptr = fp
            self._fs = self._open(fp)
        self._fs.seek(offset - self.file_offset[fp])
        self.offset_curr = offset
        out: List[bytes] = []
        nleft = size
        while nleft > 0:
            data = self._fs.read(nleft)
            if not data:
                if self._file_ptr + 1 >= len(self.files):
                    break
                self._file_ptr += 1
                self._fs.close()
                self._fs = self._open(self._file_ptr)
                continue
            out.append(data)
            nleft -= len(data)
            self.offset_curr += len(data)
        self.seek_calls += 1
        self.spans_read += 1
        self.bytes_read += size - nleft
        _SEEKS.inc()
        _SPANS.inc()
        _BYTES_READ.inc(size - nleft)
        return b"".join(out)

    # -- compressed-block machinery ------------------------------------------
    def _block_key(self, bid: int) -> object:
        return (self._cache_key, int(self._block_offs[bid]))

    def _get_fetcher(self) -> Optional[_spanfetch.SpanFetcher]:
        """The concurrent ranged-read engine (io/spanfetch.py) for
        REMOTE files, or None: local files keep the zero-copy
        mmap/pread ``_SpanReader`` fast path untouched, and
        ``DMLC_FETCH_THREADS=1`` pins the serial baseline."""
        if self._files_all_local() or _spanfetch.fetch_threads() <= 1:
            return None
        if self._span_fetcher is None:
            self._span_fetcher = _spanfetch.SpanFetcher(
                self.files, self.file_offset, self.filesys
            )
        return self._span_fetcher

    def _get_span_reader(self) -> _SpanReader:
        if self._span_reader is None:
            self._span_reader = _SpanReader(
                self.files, self.file_offset, self.filesys
            )
        return self._span_reader

    def _fetch_blocks(self, missing: List[int]) -> Dict[int, bytes]:
        """Read, decode and publish the given MISSING block ids — the
        one miss path under ``_load_window_compressed`` and
        ``_emit_range`` after the two-level lookup answered empty.

        The blocks' file ranges coalesce into spans at block
        granularity (``merge_gap`` waste bound). Remote files read them
        as parallel ranged fetches (span fetcher) delivered in
        COMPLETION order; local files read them serially off the
        mmap/pread span reader. Either way each span's blocks are
        submitted to the shared decode pool AS THE SPAN LANDS, so fetch
        and decompress overlap inside one window instead of decoding
        only after the whole window joined."""
        ctx = self._decode_ctx
        marr = np.asarray(missing, dtype=np.int64)
        offs = self._block_offs[marr]
        sizes = self._block_sizes[marr]
        order, starts, ends = _plan_span_bounds(
            offs, sizes, self.merge_gap
        )
        span_begin = offs[order][starts]
        run_end = np.maximum.accumulate(offs[order] + sizes[order])
        span_len = run_end[ends - 1] - span_begin
        spans = list(zip(span_begin.tolist(), span_len.tolist()))
        pending: List[Tuple[int, object]] = []  # (bid, decode Future)

        def on_span(si: int, data) -> None:
            nbytes = spans[si][1]
            check_eq(len(data), nbytes, "span read truncated")
            self.spans_read += 1
            self.bytes_read += nbytes
            _SPANS.inc()
            _BYTES_READ.inc(nbytes)
            mv = memoryview(data)
            begin = spans[si][0]
            for k in order[starts[si] : ends[si]].tolist():
                rel = int(offs[k]) - begin
                blob, _end = scan_compressed_blob(
                    mv[rel : rel + int(sizes[k])], 0
                )
                pending.append((int(marr[k]), ctx.submit_decode(blob)))

        fetcher = self._get_fetcher() if len(spans) > 1 else None
        if fetcher is not None:
            for si, data in fetcher.fetch_iter(spans):
                on_span(si, data)
        else:
            reader = self._get_span_reader()
            for si, (begin, nbytes) in enumerate(spans):
                on_span(si, reader.read(begin, nbytes))
        out: Dict[int, bytes] = {}
        for bid, fut in pending:
            raw, _n = fut.result()
            out[bid] = raw
            ctx.put_block(self._block_key(bid), raw)
        return out

    def _emit_range(self, lo: int, hi: int) -> bytes:
        """Framed v1 bytes of records [lo, hi) of a compressed file:
        decode each covered block (cache-served), slice by the index's
        in-block offsets. The range's blocks go through the decode
        context in ONE batched lookup (L1 then one shared-tier round
        trip), then misses ride the coalesced ``_fetch_blocks`` miss
        path (parallel ranged reads on remote files, decode overlapped
        span by span). Output is byte-identical to the uncompressed
        writer's framing for the same records."""
        runs: List[Tuple[int, int, int]] = []  # (bid, first, last) recs
        i = lo
        while i < hi:
            b = int(self._rec_block[i])
            j = i + 1
            while j < hi and int(self._rec_block[j]) == b:
                j += 1
            runs.append((b, i, j))
            i = j
        uniq = {b for b, _i, _j in runs}
        found = self._decode_ctx.get_blocks(
            [self._block_key(b) for b in uniq]
        )
        blocks: Dict[int, bytes] = {}
        for b in uniq:
            raw = found.get(self._block_key(b))
            if raw is not None:
                self.decode_cache_hits += 1
                blocks[b] = raw
        missing = sorted(b for b in uniq if b not in blocks)
        if missing:
            self.decode_cache_misses += len(missing)
            blocks.update(self._fetch_blocks(missing))
        views: Dict[int, memoryview] = {}
        out: List[memoryview] = []
        for b, i, j in runs:
            mv = views.get(b)
            if mv is None:
                mv = views[b] = memoryview(blocks[b])
            start = int(self._rec_inoff[i])
            end = int(self._rec_next[j - 1])
            # memoryview slices: the only copy is the final join (the
            # bytes-slice version copied every run twice)
            out.append(mv[start:] if end < 0 else mv[start:end])
        return b"".join(out)

    def _read_spans(
        self, span_begin: np.ndarray, span_len: np.ndarray
    ) -> np.ndarray:
        """A window's planned spans as ONE uint8 buffer, spans at their
        planned offsets. A single span stays a zero-copy wrap of the
        span reader's view (an mmap of the page cache on local files);
        multiple spans fill one PREALLOCATED buffer in place — readinto
        on the serial path, parallel ranged reads through the span
        fetcher on remote backends (``fetch_into`` writes each span at
        its base as it lands). Either way peak memory is the window
        buffer itself: no parts list + full-window join copy."""
        spans = list(zip(span_begin.tolist(), span_len.tolist()))
        total = int(span_len.sum())
        n_spans = len(spans)
        self.spans_read += n_spans
        self.bytes_read += total
        _SPANS.inc(n_spans)
        _BYTES_READ.inc(total)
        fetcher = self._get_fetcher() if n_spans > 1 else None
        if fetcher is not None:
            buf = np.empty(total, dtype=np.uint8)
            bases = np.concatenate(([0], np.cumsum(span_len)[:-1]))
            fetcher.fetch_into(spans, memoryview(buf), bases.tolist())
            return buf
        reader = self._get_span_reader()
        if n_spans == 1:
            begin, nbytes = spans[0]
            data = reader.read(begin, nbytes)
            check_eq(len(data), nbytes, "span read truncated")
            return np.frombuffer(data, dtype=np.uint8)
        buf = np.empty(total, dtype=np.uint8)
        mv = memoryview(buf)
        base = 0
        for begin, nbytes in spans:
            got = reader.readinto(begin, mv[base : base + nbytes])
            check_eq(got, nbytes, "span read truncated")
            base += nbytes
        return buf

    def _load_window_compressed(
        self, perm: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Window shuffle over compressed blocks: span coalescing is
        valid at BLOCK granularity — the window's unique blocks are
        read via coalesced file spans (merge_gap bytes of waste bound),
        decompressed in parallel on the shared codec pool (overlapped
        with the consumer by the window readahead thread), and served
        from the two-level decode context — the in-process LRU first,
        then the host daemon's shared tier (a colocated process already
        decoded the window? zero decode, zero remote bytes), then
        span-read + pool-decode + publish. The emission buffer
        concatenates decoded blocks; per-record (start, size) come from
        the index's in-block offsets, in permutation order."""
        bids = self._rec_block[perm]
        uniq = np.unique(bids)
        ctx = self._decode_ctx
        decoded: Dict[int, bytes] = {}
        missing: List[int] = []
        found = ctx.get_blocks(
            [self._block_key(b) for b in uniq.tolist()]
        )
        for b in uniq.tolist():
            data = found.get(self._block_key(b))
            if data is None:
                missing.append(b)
            else:
                self.decode_cache_hits += 1
                decoded[b] = data
        self.decode_cache_misses += len(missing)
        if missing:
            # timeline span with the miss count: a window served from
            # the caches skips this entirely, so the Perfetto row shows
            # exactly which windows paid a fetch+decode and how long
            with _tracing.span(
                "dmlc:window_span_decode", blocks=len(missing)
            ):
                decoded.update(self._fetch_blocks(missing))
        lens = np.asarray(
            [len(decoded[b]) for b in uniq.tolist()], dtype=np.int64
        )
        base = np.concatenate(([0], np.cumsum(lens)[:-1]))
        buf = np.frombuffer(
            b"".join(decoded[b] for b in uniq.tolist()), dtype=np.uint8
        )
        pos = np.searchsorted(uniq, bids)
        rec_start = base[pos] + self._rec_inoff[perm]
        nxt = self._rec_next[perm]
        rec_end = base[pos] + np.where(nxt >= 0, nxt, lens[pos])
        idt = np.int32 if len(buf) < (1 << 31) else np.int64
        return (
            buf,
            rec_start.astype(idt),
            (rec_end - rec_start).astype(idt),
        )

    # -- window-shuffle machinery -------------------------------------------
    @property
    def windowed(self) -> bool:
        """True when this split serves its shuffle through the unified
        window/gather machinery (record without the legacy escape
        hatch, batch, window) — i.e. ``next_gather_batch`` is live and
        the split prefetches internally (create() returns it bare)."""
        return (
            self.shuffle_mode in ("record", "batch", "window")
            and not self._legacy_record
        )

    def supports_gather(self) -> bool:
        """Whether ``next_gather_batch`` serves this configuration."""
        return self.windowed

    def _eff_window(self) -> int:
        """Records per shuffle window on the unified path: record mode
        is one window covering the shard — but ONLY where that window
        is a zero-copy mmap of local uncompressed files (resident =
        page cache, each byte read once). On compressed or remote
        sources a shard-wide window would MATERIALIZE the whole shard
        (decoded blocks / downloaded spans) in one buffer, so record
        mode bounds itself to ``self.window``-record windows there —
        the emitted order is IDENTICAL for any window size (the
        permutation is global; windows only cut it), memory stays
        bounded, and the cost is window-count read passes like window
        mode. batch/window modes always use ``self.window``."""
        if self.shuffle_mode == "record":
            if not self._compressed and self._files_all_local():
                return max(1, len(self._permutation))
            return max(1, self.window)
        return self.window

    def _files_all_local(self) -> bool:
        if self._all_local is None:
            self._all_local = all(
                f.path.startswith("file://") or "://" not in f.path
                for f in self.files
            )
        return self._all_local

    def _n_windows(self) -> int:
        return -(-len(self._permutation) // self._eff_window())

    def _teardown_window_pipeline(self) -> None:
        if self._win_iter is not None:
            self._win_iter.destroy()
            self._win_iter = None
        self._win_gen = None
        self._win_buf = None
        self._win_pos = 0
        self._win_start = 0
        self._win_skip = 0

    def _load_window(
        self, lo: int, hi: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Read the records at permutation positions [lo, hi) via
        coalesced spans. Returns the client-side shuffle buffer
        ``(buf, rel, size)``: one uint8 buffer of span bytes plus each
        record's start offset and length in PERMUTATION order — the
        emission path gathers records out with vectorized fancy
        indexing, no per-record Python.

        When the merged gaps more than double the buffer (aggressive
        ``merge_gap`` over a sparse window), the buffer is compacted to
        the records' own bytes with one extra gather, bounding resident
        memory at ~the window's record bytes."""
        with annotate("dmlc:window_load"):
            return self._load_window_inner(lo, hi)

    def _load_window_inner(
        self, lo: int, hi: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        perm = np.asarray(self._permutation[lo:hi], dtype=np.int64)
        if self._compressed:
            return self._load_window_compressed(perm)
        offs = self._index_offs[perm]
        sizes = self._index_sizes[perm]
        order, starts, ends = _plan_span_bounds(
            offs, sizes, self.merge_gap
        )
        soffs = offs[order]
        s_sorted = sizes[order]
        run_end = np.maximum.accumulate(soffs + s_sorted)
        span_begin = soffs[starts]
        span_len = run_end[ends - 1] - span_begin
        buf = self._read_spans(span_begin, span_len)
        # each sorted entry's start inside buf: offset within its span
        # + the span's base in the concatenation
        counts = ends - starts
        span_base = np.concatenate(([0], np.cumsum(span_len)[:-1]))
        rel_sorted = (
            soffs - np.repeat(span_begin, counts)
            + np.repeat(span_base, counts)
        )
        idt = np.int32 if len(buf) < (1 << 31) else np.int64
        rec_bytes = int(s_sorted.sum())
        if len(buf) > 2 * rec_bytes:
            base = np.cumsum(s_sorted) - s_sorted
            gather = np.arange(rec_bytes, dtype=idt) + np.repeat(
                (rel_sorted - base).astype(idt), s_sorted
            )
            buf = buf[gather]
            rel_sorted = base
        rel = np.empty(len(rel_sorted), dtype=idt)
        rel[order] = rel_sorted.astype(idt)  # sorted → permutation order
        stride = int(sizes[0]) if len(sizes) else 0
        if (
            stride
            and int(sizes.min()) == stride == int(sizes.max())
            and len(buf) % stride == 0
            and not (rel % stride).any()
        ):
            # uniform-stride window (fixed-size records, the common
            # RecordIO-shard shape): emit via 2D row gather — one fancy
            # index at memcpy speed, no per-byte index arrays
            return buf.reshape(-1, stride), rel // stride, None
        return buf, rel, sizes.astype(idt)

    def _window_stream(
        self,
    ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        # _win_start/_win_skip were fixed by before_first/_fast_forward
        # before the first pull starts this generator; the skip applies
        # to the first window only (record-mode resume at any position)
        W = self._eff_window()
        n = len(self._permutation)
        skip = self._win_skip
        for k in range(self._win_start, self._n_windows()):
            lo = min(k * W + skip, n)
            hi = min((k + 1) * W, n)
            skip = 0
            if lo >= hi:
                continue
            yield self._load_window(lo, hi)

    def _refill_window(self) -> bool:
        """Pull the next loaded window into the emission buffer; False
        at end of epoch."""
        if self._readahead:
            if self._win_iter is None:
                # lazy start: before_first/_fast_forward have fixed
                # _win_start by the time the first record is pulled
                self._win_iter = ThreadedIter(
                    self._window_stream,
                    max_capacity=2,
                    name="split-window-readahead",
                )
            nxt = self._win_iter.next()
        else:
            if self._win_gen is None:
                self._win_gen = self._window_stream()
            nxt = next(self._win_gen, None)
        if nxt is None:
            return False
        self._win_buf = nxt
        self._win_pos = 0
        return True

    def _emit_from_window(self, n: int) -> Tuple[int, List[bytes]]:
        """Gather up to ``n`` records (in permutation order) out of the
        buffered windows; returns (count, chunks). One vectorized fancy
        index per window touched — no per-record Python."""
        got = 0
        chunks: List[bytes] = []
        while got < n:
            buf_state = self._win_buf
            if buf_state is None or self._win_pos >= len(buf_state[1]):
                with annotate("dmlc:gather_refill"):
                    refilled = self._refill_window()
                if not refilled:
                    break
                buf_state = self._win_buf
            buf, rel, size = buf_state  # type: ignore[misc]
            take = min(n - got, len(rel) - self._win_pos)
            r = rel[self._win_pos : self._win_pos + take]
            if size is None:
                # uniform-stride: r holds row indices into the 2D buffer
                chunks.append(buf[r].tobytes())
            else:
                s = size[self._win_pos : self._win_pos + take]
                total = int(s.sum())
                # output cursor per record, then shift each run to its
                # record's start in buf
                base = np.cumsum(s, dtype=r.dtype) - s
                gather = np.arange(total, dtype=r.dtype) + np.repeat(
                    r - base, s
                )
                chunks.append(buf[gather].tobytes())
            self._win_pos += take
            got += take
        return got, chunks

    def next_gather_batch(
        self, n_records: int
    ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Zero-copy batched emission for the unified shuffle path:
        returns ``(buf, starts, sizes)`` — a uint8 view of the current
        window's span bytes plus int64 byte offsets/lengths of up to
        ``n_records`` framed records IN PERMUTATION ORDER — or None at
        end of epoch. No record bytes are copied or re-framed; the
        caller parses straight out of the window buffer (the native
        gather kernel, staging/fused.py) and must finish with the views
        before pulling past the current window (the buffer is recycled
        when the window drains). A call never crosses a window
        boundary, so short returns are normal — keep calling until the
        batch is full or None arrives. Only valid when ``windowed``
        (``supports_gather()``)."""
        check(self.windowed, "next_gather_batch needs a windowed shuffle")
        buf_state = self._win_buf
        if buf_state is None or self._win_pos >= len(buf_state[1]):
            # the refill is the part worth a timeline span: it blocks on
            # the readahead thread (or loads inline) — a long one IS the
            # window pipeline starving the consumer. The in-window slice
            # below is a couple of numpy views; tracing it per batch
            # would cost more than it shows.
            with annotate("dmlc:gather_refill"):
                if not self._refill_window():
                    return None
            buf_state = self._win_buf
        buf, rel, size = buf_state  # type: ignore[misc]
        take = min(n_records, len(rel) - self._win_pos)
        r = rel[self._win_pos : self._win_pos + take]
        if size is None:
            # uniform-stride window: rel holds row indices into the 2D
            # buffer; flatten the view and expand to byte offsets
            stride = buf.shape[1]
            starts = r.astype(np.int64) * stride
            sizes = np.full(take, stride, dtype=np.int64)
            out = (buf.reshape(-1), starts, sizes)
        else:
            s = size[self._win_pos : self._win_pos + take]
            out = (buf, r.astype(np.int64), s.astype(np.int64))
        self._win_pos += take
        self.records_consumed += take
        self.records_emitted += take
        self.gather_batches += 1
        nbytes = int(out[2].sum())
        self.gather_bytes += nbytes
        _RECORDS.inc(take)
        _GATHER_BATCHES.inc()
        _GATHER_BYTES.inc(nbytes)
        return out

    def count_gather_fallback(self, n: int = 1) -> None:
        """Consumers that pulled ``next_gather_batch`` views but had to
        RE-FRAME them (native gather kernel absent in the loaded .so)
        report it here, so ``gather_fallback_batches`` keeps its
        meaning — 'emissions that paid the framed-bytes copy' — across
        layers, and a stale binary can't masquerade as the zero-copy
        fast path in io_stats/telemetry."""
        self.gather_fallback_batches += n
        _GATHER_FALLBACK.inc(n)

    def io_stats(self) -> Dict[str, object]:
        """I/O-shape counters, cumulative since construction: ``spans``
        positioned reads issued, ``seeks`` stream seek() calls (0 on
        the local pread fast path), ``bytes_read``, and ``records`` —
        records actually emitted (skip_records fast-forward excluded) —
        plus the robustness counters (``retries``/``backoff_secs``/
        ``faults_injected`` deltas, see InputSplitBase.io_stats).
        Coalescing shows up as spans ≪ records."""
        seeks = self.seek_calls
        if self._span_reader is not None:
            seeks += self._span_reader.seeks
        if self._span_fetcher is not None:
            seeks += self._span_fetcher.seeks
        out = {
            "mode": self.shuffle_mode or "sequential",
            "records": self.records_emitted,
            "spans": self.spans_read,
            "seeks": seeks,
            "bytes_read": self.bytes_read,
            "reopens": _spanfetch.reopens_total() - self._reopen_snap,
            **_retry.stats_delta(self._retry_snap),
        }
        if self._span_fetcher is not None:
            # concurrent-fetch shape (remote sources only): spans
            # actually fetched in parallel and the peak concurrency the
            # AIMD ramp reached — fetch_spans == spans with peak 1
            # means the ramp never engaged (contiguous plan or
            # DMLC_FETCH_THREADS=1 would not create a fetcher at all)
            out["fetch_spans"] = self._span_fetcher.spans
            out["fetch_bytes"] = self._span_fetcher.bytes
            out["fetch_concurrency_peak"] = (
                self._span_fetcher.concurrency_peak
            )
        if self.windowed:
            # gather-emission shape: batches/bytes handed out zero-copy
            # vs emissions that fell back to the framed-bytes gather
            # (generic parsers, native kernel absent) — docs/shuffle.md
            out["gather_batches"] = self.gather_batches
            out["gather_bytes"] = self.gather_bytes
            out["gather_fallback_batches"] = self.gather_fallback_batches
        if self._compressed:
            # decoded-block cache shape: hits ≫ misses on a second epoch
            # proves each block decompressed once (DMLC_DECODE_CACHE_MB)
            out["decode_cache_hits"] = self.decode_cache_hits
            out["decode_cache_misses"] = self.decode_cache_misses
        return out

    def next_batch_ex(self, n_records: int) -> Optional[bytes]:
        """Reference NextBatchEx (indexed_recordio_split.cc:159-212):
        every shuffle mode (record/batch/window) = coalesced spans
        refilling a client-side shuffle buffer (readahead thread) with
        one vectorized re-framing gather per emission — the NumPy
        fallback to ``next_gather_batch``; legacy record mode =
        per-record seeks (the reference's literal loop, kept for A/B);
        sequential = one span."""
        if self.windowed:
            n = self._n_overflow or n_records
            got, chunks = self._emit_from_window(n)
            if not got:
                return None
            self._n_overflow = n - got
            self.records_consumed += got
            self.records_emitted += got
            self.gather_fallback_batches += 1
            _RECORDS.inc(got)
            _GATHER_FALLBACK.inc()
            return chunks[0] if len(chunks) == 1 else b"".join(chunks)
        if self._legacy_record:
            n = self._n_overflow or n_records
            parts: List[bytes] = []
            while len(parts) < n and self._current < len(self._permutation):
                idx = self._permutation[self._current]
                if self._compressed:
                    parts.append(self._emit_range(idx, idx + 1))
                else:
                    parts.append(
                        self._read_at(
                            int(self._index_offs[idx]),
                            int(self._index_sizes[idx]),
                        )
                    )
                self._current += 1
            if not parts:
                return None
            self._n_overflow = n - len(parts)
            self.records_consumed += len(parts)
            self.records_emitted += len(parts)
            _RECORDS.inc(len(parts))
            return b"".join(parts)
        n = self._n_overflow or n_records
        last = min(self._current + n, self.index_end)
        self._n_overflow = self._current + n - last
        if last <= self._current:
            return None
        if self._compressed:
            chunk = self._emit_range(self._current, last)
        else:
            begin_off = int(self._index_offs[self._current])
            end_off = (
                int(self._index_offs[last])
                if last < len(self._index_offs)
                else self.file_offset[-1]
            )
            chunk = self._read_at(begin_off, end_off - begin_off)
        if chunk:
            self.records_consumed += last - self._current
            self.records_emitted += last - self._current
            _RECORDS.inc(last - self._current)
        self._current = last
        return chunk if chunk else None

    def close(self) -> None:
        self._teardown_window_pipeline()
        if self._span_reader is not None:
            self._span_reader.close()
            self._span_reader = None
        if self._span_fetcher is not None:
            self._span_fetcher.close()
            self._span_fetcher = None
        super().close()

    def next_chunk(self) -> Optional[bytes]:
        return self.next_batch_ex(self.batch_size)

    def next_batch(self, n_records: int) -> Optional[bytes]:
        return self.next_batch_ex(n_records)

    def next_record(self) -> Optional[bytes]:
        while True:
            if self._rec_iter is not None:
                rec = next(self._rec_iter, None)
                if rec is not None:
                    return rec
            chunk = self.next_batch_ex(self.batch_size)
            if chunk is None:
                return None
            self._rec_iter = self.extract_records(chunk)


class SingleFileSplit(InputSplit):
    """stdin / single-file text split without sharding (reference
    src/io/single_file_split.h)."""

    def __init__(self, path: str = "-") -> None:
        self._path = path
        self._stream = None
        self._buffer = b""
        self._eof = False
        self._rec_iter: Optional[Iterator[bytes]] = None
        self._size = 0
        self.before_first()

    def _open(self):
        if self._path == "-":
            import sys

            return sys.stdin.buffer
        return open(self._path, "rb")

    def before_first(self) -> None:
        if self._path == "-" and self._stream is not None:
            raise Error("cannot rewind stdin")
        if self._stream is not None and self._path != "-":
            self._stream.close()
        self._stream = self._open()
        self._eof = False
        self._rec_iter = None
        self._overflow = b""

    def total_size(self) -> int:
        if self._path == "-":
            return 0
        import os

        return os.path.getsize(self._path)

    def next_chunk(self) -> Optional[bytes]:
        while not self._eof:
            data = self._stream.read(DEFAULT_BUFFER_BYTES)
            if not data:
                self._eof = True
                if self._overflow:
                    out, self._overflow = self._overflow + b"\n", b""
                    return out
                return None
            data = self._overflow + data
            cut = max(data.rfind(b"\n"), data.rfind(b"\r"))
            if cut <= 0:
                self._overflow = data
                continue
            self._overflow = data[cut + 1 :]
            return data[: cut + 1]
        return None

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        for line in chunk.replace(b"\r", b"\n").split(b"\n"):
            if line:
                yield line

    def next_record(self) -> Optional[bytes]:
        while True:
            if self._rec_iter is not None:
                rec = next(self._rec_iter, None)
                if rec is not None:
                    return rec
            chunk = self.next_chunk()
            if chunk is None:
                return None
            self._rec_iter = self.extract_records(chunk)

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        check_eq(num_parts, 1, "SingleFileSplit does not shard")


class ThreadedInputSplit(InputSplit):
    """Read-ahead wrapper: prefetch chunks on a background thread with
    double buffering (reference src/io/threaded_input_split.h,
    set_max_capacity(2) at :33)."""

    def __init__(self, base: InputSplitBase, max_capacity: int = 2) -> None:
        self._base = base
        self._cap = max_capacity
        self._rec_iter: Optional[Iterator[bytes]] = None
        self._first_epoch = True
        self._iter: ThreadedIter[bytes] = ThreadedIter(
            self._produce, max_capacity=max_capacity, name="split-prefetch"
        )

    def _produce(self):
        if not self._first_epoch:
            self._base.before_first()
        self._first_epoch = False
        while True:
            chunk = self._base.next_chunk()
            if chunk is None:
                return
            yield chunk

    def next_chunk(self) -> Optional[bytes]:
        return self._iter.next()

    def next_record(self) -> Optional[bytes]:
        while True:
            if self._rec_iter is not None:
                rec = next(self._rec_iter, None)
                if rec is not None:
                    return rec
            chunk = self._iter.next()
            if chunk is None:
                return None
            self._rec_iter = self._base.extract_records(chunk)

    def before_first(self) -> None:
        self._rec_iter = None
        self._iter.before_first()

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        self._iter.destroy()
        self._base.reset_partition(part_index, num_parts)
        self._first_epoch = True
        self._rec_iter = None
        self._iter = ThreadedIter(
            self._produce, max_capacity=self._cap, name="split-prefetch"
        )

    def total_size(self) -> int:
        return self._base.total_size()

    def hint_chunk_size(self, nbytes: int) -> None:
        self._base.hint_chunk_size(nbytes)

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        return self._base.extract_records(chunk)

    def io_stats(self) -> Dict[str, object]:
        """Forward the wrapped split's I/O-shape counters (indexed
        splits); empty dict when the base doesn't track them — every
        io_stats() implementation returns a dict (ISSUE 4 satellite:
        callers assume one)."""
        fn = getattr(self._base, "io_stats", None)
        out = fn() if fn is not None else None
        return out if out else {}

    def close(self) -> None:
        self._iter.destroy()
        self._base.close()


class CachedInputSplit(InputSplit):
    """First epoch streams chunks to a local cache file while serving them;
    later epochs replay the cache (reference src/io/cached_input_split.h:
    InitPreprocIter :148-164, InitCachedIter :166-189)."""

    def __init__(self, base: InputSplit, cache_file: str) -> None:
        self._base = base
        self._cache_file = cache_file
        self._cache_complete = False
        self._rec_iter: Optional[Iterator[bytes]] = None
        self._iter: ThreadedIter[bytes] = ThreadedIter(
            self._produce_preproc, name="split-cache-build"
        )

    def _produce_preproc(self):
        out = Stream.create(self._cache_file, "w")
        try:
            while True:
                chunk = self._base.next_chunk()
                if chunk is None:
                    break
                serializer.write_bytes(out, chunk)
                yield chunk
            self._cache_complete = True
        finally:
            out.close()

    def _produce_cached(self):
        stream = Stream.create(self._cache_file, "r")
        try:
            while True:
                n = serializer.try_read_scalar(stream, "uint64")
                if n is None:
                    return
                yield stream.read_exact(n)
        finally:
            stream.close()

    def before_first(self) -> None:
        self._rec_iter = None
        if self._cache_complete:
            self._iter.destroy()
            self._iter = ThreadedIter(self._produce_cached, name="split-cache-replay")
        else:
            # first pass didn't finish: rebuild the cache from scratch
            self._iter.destroy()
            self._base.before_first()
            self._iter = ThreadedIter(self._produce_preproc, name="split-cache-build")

    def next_chunk(self) -> Optional[bytes]:
        return self._iter.next()

    def next_record(self) -> Optional[bytes]:
        while True:
            if self._rec_iter is not None:
                rec = next(self._rec_iter, None)
                if rec is not None:
                    return rec
            chunk = self._iter.next()
            if chunk is None:
                return None
            self._rec_iter = self._base.extract_records(chunk)

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        self._cache_complete = False
        self._iter.destroy()
        self._base.reset_partition(part_index, num_parts)
        self._iter = ThreadedIter(self._produce_preproc, name="split-cache-build")
        self._rec_iter = None

    def total_size(self) -> int:
        return self._base.total_size()

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        return self._base.extract_records(chunk)

    def io_stats(self) -> Dict[str, object]:
        fn = getattr(self._base, "io_stats", None)
        out = fn() if fn is not None else None
        return out if out else {}

    def close(self) -> None:
        self._iter.destroy()
        self._base.close()


class InputSplitShuffle(InputSplit):
    """Macro-shuffle: over-partition into num_parts * num_shuffle_parts
    sub-parts and visit this rank's sub-parts in a seeded shuffled order,
    reshuffled each epoch (reference include/dmlc/input_split_shuffle.h:
    24-33, 100-119; kRandMagic_=666 :151)."""

    KRAND_MAGIC = 666

    def __init__(
        self,
        base: InputSplit,
        part_index: int,
        num_parts: int,
        num_shuffle_parts: int,
        seed: int = 0,
    ) -> None:
        check(num_shuffle_parts > 0, "num_shuffle_parts must be positive")
        self._base = base
        self._num_total = num_parts * num_shuffle_parts
        self._sub_parts = [
            part_index * num_shuffle_parts + i for i in range(num_shuffle_parts)
        ]
        self._rnd = random.Random(self.KRAND_MAGIC + seed)
        self._order: List[int] = []
        self._cursor = 0
        self.before_first()

    def before_first(self) -> None:
        self._order = list(self._sub_parts)
        self._rnd.shuffle(self._order)
        self._cursor = 0
        self._base.reset_partition(self._order[0], self._num_total)

    def _advance(self) -> bool:
        self._cursor += 1
        if self._cursor >= len(self._order):
            return False
        self._base.reset_partition(self._order[self._cursor], self._num_total)
        return True

    def next_record(self) -> Optional[bytes]:
        while True:
            rec = self._base.next_record()
            if rec is not None:
                return rec
            if not self._advance():
                return None

    def next_chunk(self) -> Optional[bytes]:
        while True:
            chunk = self._base.next_chunk()
            if chunk is not None:
                return chunk
            if not self._advance():
                return None

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        nsp = len(self._sub_parts)
        self._sub_parts = [part_index * nsp + i for i in range(nsp)]
        self._num_total = num_parts * nsp
        self.before_first()

    def total_size(self) -> int:
        return self._base.total_size()

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        return self._base.extract_records(chunk)

    def io_stats(self) -> Dict[str, object]:
        fn = getattr(self._base, "io_stats", None)
        out = fn() if fn is not None else None
        return out if out else {}

    def close(self) -> None:
        self._base.close()


class DynamicShardSource(InputSplit):
    """Tracker-leased dynamic sharding: an InputSplit whose shard →
    worker placement is decided at RUN time by the tracker's shard
    service (tracker/shardsvc.py, docs/sharding.md) instead of a
    ``part_index/num_parts`` fixed at open.

    The file set is oversharded into ``K x num_workers`` micro-shards;
    a micro-shard IS ``(part_index=i, num_parts=M)`` of the standard
    byte-range/magic-scan planner, so shard CONTENT — including the
    per-shard ``(seed, epoch)`` shuffle permutation — is bit-identical
    to a static run over the same ``M`` parts; only which worker drains
    which shard changes. The driver pulls a lease, opens the standard
    (windowed) splitter for that micro-shard via ``make_splitter``,
    drains it, reports ``shard_done``, and pulls the next — so a slow
    worker simply takes fewer shards and an idle worker steals the
    reclaimed ones. Waiting for a grantable shard is surfaced as the
    ``dmlc:shard_lease_wait`` stall stage on the flight recorder.

    Semantics: committed work is exactly-once (the ``on_shard_done``
    hook sees ``recorded`` exactly once per micro-shard, cluster-wide);
    record emission is at-least-once only if a LIVE worker outlives its
    lease TTL without renewing (renewal rides every pull and every
    tracker heartbeat). ``before_first()`` starts the next epoch — a
    fresh cluster-wide ledger — mirroring the static splitters'
    epoch-increment contract.

    ``make_splitter(shard, num_shards, epoch)`` must build the shard's
    splitter exactly as the static path would (``create`` wires this
    up; ``dynamic_shards=True`` / ``&dynamic_shards=1``).

    Hooks (settable attributes): ``on_lease(shard, num_shards)`` fires
    after a lease is granted, ``on_shard_done(shard, status)`` after
    the tracker acks a completed shard (status ``recorded`` |
    ``duplicate``) — tests and bench commit per-shard outputs on
    ``recorded`` for end-to-end exactly-once accounting.
    """

    def __init__(
        self,
        make_splitter,
        client=None,
        epoch: int = 0,
        fileset: Optional[str] = None,
        windowed_hint: bool = False,
        renew_frac: float = 3.0,
        make_probe=None,
    ) -> None:
        if client is None:
            # lazy import: the lease protocol (sockets) lives with the
            # tracker — io/ only drives it (lint L010 keeps raw sockets
            # out of this layer)
            from ..tracker.shardsvc import ShardLeaseClient

            client = ShardLeaseClient()
        self._client = client
        self._make_splitter = make_splitter
        # introspection-only builder (total_size before any lease):
        # must NOT start read-ahead, so callers whose make_splitter
        # wraps in ThreadedInputSplit pass the bare construction here
        self._make_probe = make_probe or make_splitter
        self._fileset = fileset
        self._windowed_hint = windowed_hint
        self._renew_frac = max(1.5, renew_frac)
        self.epoch = epoch
        self._started = False
        self._exhausted = False
        self._split: Optional[InputSplit] = None
        self._probe: Optional[InputSplit] = None
        self._total_size: Optional[int] = None
        self._chunk_hint: Optional[int] = None
        self._lease: Optional[Dict] = None
        self._last_renew = 0.0
        self.num_shards: Optional[int] = None
        self.current_shard: Optional[int] = None
        # worker-side shape counters (io_stats)
        self.leases = 0
        self.shards_recorded = 0
        self.shards_duplicate = 0
        self.lease_wait_secs = 0.0
        self.renews_lost = 0
        self._closed_stats: Dict[str, float] = {}
        self.on_lease = None
        self.on_shard_done = None

    # -- lease machinery -----------------------------------------------------
    def _ensure_split(self) -> bool:
        """Hold a live per-shard splitter; False at end of epoch."""
        while self._split is None:
            if self._exhausted:
                return False
            # the lease RPC (and any "come back later" backoff) IS the
            # wait: recording both under the stall span means every
            # shard_lease_wait slice encloses the request's flow-start,
            # so a merged timeline draws the arrow straight to the
            # tracker's shard_lease handler span (docs/observability.md)
            with annotate("dmlc:shard_lease_wait"):
                resp = self._client.lease(self.epoch, self._fileset)
                status = resp.get("status")
                if status == "wait":
                    # every micro-shard is leased out: park (visibly —
                    # this IS the straggler signal on a merged
                    # timeline) until one completes or a lease expires
                    # and is reclaimed
                    backoff = float(resp.get("backoff", 0.1))
                    time.sleep(min(1.0, max(0.01, backoff)))
                    self.lease_wait_secs += backoff
            if status == "lease":
                shard = int(resp["shard"])
                self.num_shards = int(resp["num_shards"])
                self._lease = resp
                self.current_shard = shard
                self.leases += 1
                self._last_renew = time.monotonic()
                split = self._make_splitter(
                    shard, self.num_shards, self.epoch
                )
                if self._chunk_hint:
                    split.hint_chunk_size(self._chunk_hint)
                self._split = split
                if self.on_lease is not None:
                    self.on_lease(shard, self.num_shards)
            elif status == "wait":
                pass  # already parked inside the stall span above
            elif status == "done":
                self._exhausted = True
                return False
            else:
                raise Error(
                    "shard lease request failed: "
                    f"{resp.get('error', resp)!r}"
                )
        return True

    def _maybe_renew(self) -> None:
        if self._lease is None:
            return
        now = time.monotonic()
        ttl = float(self._lease.get("ttl", 30.0))
        interval = ttl / self._renew_frac
        if now - self._last_renew < interval:
            return
        self._last_renew = now
        try:
            # short reconnect budget: a renew rides the READ path, so
            # it must not park the consumer for the full crash-recovery
            # window — this cadence (below) is the real retry loop
            resp = self._client.renew(self.epoch, retry_secs=2.0)
        except (OSError, ConnectionError):
            # transient: retry SOON (1s, not a full interval — two
            # hiccups in a row must not eat the whole TTL), but not on
            # every pull (each attempt can pay a connect timeout)
            self._last_renew = now - interval + min(1.0, interval / 2.0)
            return
        if resp.get("status") == "lost":
            # keep draining: shard_done dedupes (first finisher wins),
            # but count it — a nonzero renews_lost means the TTL is too
            # tight for this worker's stall profile
            self.renews_lost += 1

    @staticmethod
    def _merge_stats(dst: Dict[str, object], stats: Dict) -> None:
        """Numeric counters sum, first non-numeric value wins — ONE
        merge rule for drained and live shards."""
        for k, v in stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                dst[k] = dst.get(k, 0) + v
            elif k not in dst:
                dst[k] = v

    def _accumulate_stats(self, split: InputSplit) -> None:
        stats = getattr(split, "io_stats", lambda: None)() or {}
        self._merge_stats(self._closed_stats, stats)

    def _release_lease(self) -> None:
        """Hand an UNFINISHED lease back to the queue (close /
        mid-epoch restart). Best-effort on purpose — but not optional
        in spirit: a process whose rabit heartbeat outlives this source
        would renew the abandoned lease forever. A refused dial gets a
        SHORT reconnect budget (a tracker mid-relaunch comes back in
        seconds, and a dropped release otherwise waits out a TTL) —
        only a tracker that stays unreachable past it is left to the
        TTL / supervisor reclaim."""
        lease = self._lease
        self._lease = None
        if lease is None:
            return
        try:
            self._client.release(
                int(lease.get("epoch", self.epoch)), int(lease["shard"]),
                self._fileset, retry_secs=5.0,
            )
        except (OSError, ConnectionError, ValueError, KeyError):
            pass

    def _shard_finished(self) -> None:
        split, lease = self._split, self._lease
        self._split = None
        self._lease = None
        if split is not None:
            self._accumulate_stats(split)
            split.close()
        if lease is None:
            return
        shard = int(lease["shard"])
        # the signature rides along so a straggler's done from before a
        # dataset switch can't land on the new dataset's ledger
        resp = self._client.done(self.epoch, shard, self._fileset)
        status = resp.get("status", "error")
        if status == "recorded":
            self.shards_recorded += 1
        elif status == "duplicate":
            self.shards_duplicate += 1
        else:
            # a fully-drained shard the tracker refuses to account
            # (aged-out epoch, stale dataset signature) means this
            # worker's rows may double-count a peer's — stop loudly,
            # don't keep feeding the consumer as if the shard committed
            raise Error(
                f"tracker refused shard_done for micro-shard {shard} "
                f"(epoch {self.epoch}): {resp.get('error', resp)}"
            )
        if self.on_shard_done is not None:
            self.on_shard_done(shard, status)

    def _pull(self, op):
        """The one leased pull loop behind every emission method:
        ensure a leased shard is open, keep its lease renewed, delegate
        to the open splitter, and commit the shard when the delegate
        drains (None)."""
        while True:
            if not self._ensure_split():
                return None
            self._maybe_renew()
            out = op(self._split)
            if out is not None:
                self._started = True
                return out
            self._shard_finished()

    # -- InputSplit contract -------------------------------------------------
    def next_record(self) -> Optional[bytes]:
        return self._pull(lambda s: s.next_record())

    def next_chunk(self) -> Optional[bytes]:
        return self._pull(lambda s: s.next_chunk())

    def next_batch(self, n_records: int) -> Optional[bytes]:
        return self._pull(lambda s: s.next_batch(n_records))

    def next_gather_batch(self, n_records: int):
        """Zero-copy gather emission, delegated per micro-shard (the
        fused staging path). A call never crosses a shard boundary —
        short returns at shard edges are normal, like window edges."""
        check(
            self._windowed_hint,
            "next_gather_batch needs a windowed shuffle configuration",
        )
        return self._pull(lambda s: s.next_gather_batch(n_records))

    @property
    def windowed(self) -> bool:
        return self._windowed_hint

    def supports_gather(self) -> bool:
        return self._windowed_hint

    def count_gather_fallback(self, n: int = 1) -> None:
        if self._split is not None and hasattr(
            self._split, "count_gather_fallback"
        ):
            self._split.count_gather_fallback(n)

    def before_first(self) -> None:
        """Next epoch: a fresh cluster-wide ledger. Before anything was
        pulled this is a no-op (the constructor's ``epoch`` is the
        first epoch), mirroring the static splitters' increment-per-
        rewind contract. A live lease is released back to the queue
        (cmd=shard_release); normal flow drains to None first, so this
        only costs work on an explicit mid-epoch restart."""
        if not self._started and not self._exhausted:
            return
        if self._split is not None:
            self._accumulate_stats(self._split)
            self._split.close()
            self._split = None
        self._release_lease()
        self.epoch += 1
        self._exhausted = False
        self._started = False
        self.current_shard = None

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        raise Error(
            "DynamicShardSource has no static partition to reset: shard "
            "placement is leased from the tracker (docs/sharding.md); "
            "open a static split (part_index/num_parts) if you need "
            "pinned placement"
        )

    def _get_probe(self) -> InputSplit:
        """A (0, 1) splitter used only for whole-set introspection
        (total_size, extract_records before any lease) — never read."""
        if self._probe is None:
            self._probe = self._make_probe(0, 1, self.epoch)
        return self._probe

    def total_size(self) -> int:
        if self._total_size is None:
            src = self._split if self._split is not None else self._get_probe()
            self._total_size = src.total_size()
        return self._total_size

    def hint_chunk_size(self, nbytes: int) -> None:
        self._chunk_hint = nbytes
        if self._split is not None:
            self._split.hint_chunk_size(nbytes)

    def extract_records(self, chunk: bytes) -> Iterator[bytes]:
        src = self._split if self._split is not None else self._get_probe()
        return src.extract_records(chunk)

    def io_stats(self) -> Dict[str, object]:
        """Numeric counters summed across every drained micro-shard's
        splitter plus the live one, with the lease shape on top
        (``leases``/``shards_recorded``/``shards_duplicate``/
        ``lease_wait_secs``/``renews_lost``) — docs/sharding.md."""
        out: Dict[str, object] = dict(self._closed_stats)
        if self._split is not None:
            live = getattr(self._split, "io_stats", lambda: None)() or {}
            self._merge_stats(out, live)
        inner_mode = out.get("mode", "sequential")
        out["mode"] = f"dynamic:{inner_mode}"
        out["leases"] = self.leases
        out["shards_recorded"] = self.shards_recorded
        out["shards_duplicate"] = self.shards_duplicate
        out["lease_wait_secs"] = round(self.lease_wait_secs, 4)
        out["renews_lost"] = self.renews_lost
        if self.num_shards is not None:
            out["num_shards"] = self.num_shards
        return out

    def close(self) -> None:
        # a live lease is released, not completed — the partially
        # drained shard goes back to the queue to be re-served in full
        # (TTL / supervisor reclaim only cover a tracker we can't reach)
        if self._split is not None:
            self._accumulate_stats(self._split)
            self._split.close()
            self._split = None
        self._release_lease()
        if self._probe is not None:
            self._probe.close()
            self._probe = None


def fileset_signature(
    data_uri: str, index_uri: Optional[str] = None, type: str = "recordio"
) -> str:
    """Canonical dataset identity for the shard-lease protocol
    (docs/sharding.md): mismatched workers (different URIs on the same
    tracker) must fail loudly, not drain different bytes. fault://
    wrappers are normalized away — a chaos-wrapped worker reads the
    SAME dataset as its clean peers — and local paths are canonicalized
    the way ``faults.wrap_uri`` canonicalizes them (strip ``file://``,
    lead with ``/``) so a clean ``file:///d/x.rec`` peer signs
    identically to a faulted ``/d/x.rec`` one. Shared by the dynamic
    create() path and the dsserve preprocessing tier (both lease and
    commit under this signature, so they can never disagree)."""
    from .faults import unwrap_uri as _unwrap

    def _sig_norm(u: str) -> str:
        u = _unwrap(u)
        if u.startswith("file://"):
            u = u[len("file://"):]
        if u and "://" not in u and not u.startswith("/"):
            u = "/" + u
        return u

    return hashlib.sha1(
        f"{_sig_norm(data_uri)}|{_sig_norm(index_uri or '')}|{type}"
        .encode()
    ).hexdigest()


def create(
    uri: str,
    part_index: int = 0,
    num_parts: int = 1,
    type: str = "text",
    index_uri: Optional[str] = None,
    shuffle=None,  # None | bool | 'record' | 'batch'
    seed: int = 0,
    batch_size: Optional[int] = None,
    recurse_directories: bool = False,
    num_shuffle_parts: int = 0,
    threaded: bool = True,
    epoch: int = 0,
    skip_records: int = 0,
    window: Optional[int] = None,
    merge_gap: Optional[int] = None,
    dynamic_shards: Optional[bool] = None,
) -> InputSplit:
    """InputSplit factory (reference InputSplit::Create, src/io.cc:81-130).

    - ``uri`` may carry ``#cachefile`` sugar → CachedInputSplit
      (reference io.cc:120-124)
    - default wraps the split in a read-ahead thread (reference
      io.cc:119-122); ``shuffle='window'`` splits prefetch internally
      (their readahead thread loads coalesced spans) and are returned
      bare — cached OR threaded OR window-readahead, never stacked
    - ``type``: 'text' | 'recordio' | 'indexed_recordio'
    - ``window``/``merge_gap``: shuffle='window' knobs
      (``?shuffle=window&window=N&merge_gap=B`` as URI sugar)
    - ``dynamic_shards`` (``&dynamic_shards=1``): ignore the static
      ``part_index/num_parts`` placement and pull tracker-leased
      micro-shards instead (DynamicShardSource, docs/sharding.md) —
      each micro-shard opens the standard splitter with the same
      options, so per-shard order matches the static path bit-for-bit.
      Requires a running tracker (``DMLC_TRACKER_URI``/``PORT``).
      The driver is returned bare; each leased micro-shard's splitter
      gets the same wrapper a static drain would (windowed splitters
      prefetch internally, others ride ``ThreadedInputSplit`` when
      ``threaded``).
    """
    check(
        num_parts >= 1 and 0 <= part_index < num_parts,
        f"invalid shard ({part_index}, {num_parts}): need "
        "0 <= part_index < num_parts (reference io.cc CHECK)",
    )
    spec = URISpec(uri, part_index, num_parts)
    # streaming sugar: pointing at a stream's manifest (or &stream=1 /
    # type='stream' on the directory) follows the LIVE stream — a
    # tail-following StreamSource instead of a sealed-file splitter
    # (stream/source.py, docs/streaming.md). Lazy import: stream/
    # imports this module for the InputSplit contract.
    from ..stream.manifest import MANIFEST_NAME as _stream_manifest_name

    if (
        type == "stream"
        or bool(uri_int(spec.args, "stream", 0))
        or spec.uri.rstrip("/").endswith("/" + _stream_manifest_name)
    ):
        from ..stream.source import StreamSource

        check(
            not spec.cache_file,
            "a #cachefile would freeze a growing stream's first read; "
            "streams are followed live, not cached",
        )
        dir_uri = spec.uri.rstrip("/")
        if dir_uri.endswith("/" + _stream_manifest_name):
            dir_uri = dir_uri[: -(len(_stream_manifest_name) + 1)]
        if dynamic_shards is None:
            dynamic_shards = bool(uri_int(spec.args, "dynamic_shards", 0))
        check(
            dynamic_shards or (part_index == 0 and num_parts == 1),
            "a static stream follow drains everything (one reader); "
            "multi-worker streaming uses &dynamic_shards=1 leased "
            "micro-shards (docs/streaming.md)",
        )
        if shuffle is None:
            shuffle = spec.args.get("shuffle", "0")
        return StreamSource(
            dir_uri,
            shuffle=normalize_shuffle(shuffle),
            seed=seed if seed else uri_int(spec.args, "seed", 0),
            window=(
                window
                if window is not None
                else uri_int(spec.args, "window", 8192, minimum=1)
            ),
            batch_size=(
                batch_size
                if batch_size is not None
                else uri_int(spec.args, "batch_size", 256)
            ),
            dynamic=dynamic_shards,
            threaded=threaded,
        )
    # per-dataset options ride the URI (reference-style sugar); explicit
    # keyword args win when both are given:
    #   ?shuffle_parts=N&seed=S       macro-shuffle, any record type
    #   ?index=<uri>[&shuffle=1][&batch_size=N]   count-indexed recordio
    if num_shuffle_parts == 0:
        num_shuffle_parts = uri_int(spec.args, "shuffle_parts", 0)
    if type == "recordio" and (index_uri is not None or "index" in spec.args):
        if index_uri is None:
            index_uri = str(spec.args["index"])
        type = "indexed_recordio"
    if seed == 0:
        seed = uri_int(spec.args, "seed", 0)
    if type == "indexed_recordio":
        if shuffle is None:
            shuffle = spec.args.get("shuffle", "0")
        shuffle = normalize_shuffle(shuffle)
        if batch_size is None:
            batch_size = uri_int(spec.args, "batch_size", 256)
        if window is None:
            window = uri_int(spec.args, "window", 65536, minimum=1)
        if merge_gap is None:
            merge_gap = uri_int(spec.args, "merge_gap", 65536, minimum=0)
        # &legacy_shuffle=1: force the reference's per-record seek loop
        # for shuffle=record (A/B baseline against the gather fast path)
        legacy_shuffle = bool(uri_int(spec.args, "legacy_shuffle", 0))
        # data-position resume sugar (?epoch=E&skip_records=N): start at
        # epoch E's deterministic permutation, N records in (§5.4)
        if epoch == 0:
            epoch = uri_int(spec.args, "epoch", 0)
        if skip_records == 0:
            skip_records = uri_int(spec.args, "skip_records", 0)
        check(
            not (shuffle and spec.cache_file),
            "indexed shuffle with a #cachefile would freeze the first "
            "epoch's shuffle order into the cache; pick one",
        )
    else:
        shuffle = normalize_shuffle(shuffle)
        # position fast-forward needs count-indexed access; silently
        # starting at record 0 would make a resume retrain duplicate
        # data — refuse loudly (the check() idiom of the sugar below)
        check(
            epoch == 0
            and skip_records == 0
            and "epoch" not in spec.args
            and "skip_records" not in spec.args,
            f"epoch/skip_records require an indexed recordio source "
            f"(?index=<uri>), not type={type!r}",
        )
    batch_size = 256 if batch_size is None else batch_size
    if type == "text" and spec.uri == "-":
        return SingleFileSplit("-")
    if type not in ("text", "recordio", "indexed_recordio"):
        raise Error(f"unknown InputSplit type {type!r}")
    if type == "indexed_recordio":
        check(index_uri is not None, "indexed_recordio requires index_uri")
    legacy = legacy_shuffle if type == "indexed_recordio" else False

    def _build_base(pi: int, nparts: int, ep: int) -> InputSplitBase:
        """The one construction site for both placements: the static
        path calls it once with (part_index, num_parts, epoch); the
        dynamic driver calls it per leased micro-shard with
        (shard, K*num_workers, current_epoch) — identical options, so
        shard content and per-shard shuffle order never depend on who
        drains it."""
        if type == "text":
            return LineSplitter(
                spec.uri, pi, nparts,
                recurse_directories=recurse_directories,
            )
        if type == "recordio":
            return RecordIOSplitter(
                spec.uri, pi, nparts,
                recurse_directories=recurse_directories,
            )
        return IndexedRecordIOSplitter(
            spec.uri,
            index_uri,  # type: ignore[arg-type]
            pi,
            nparts,
            batch_size=batch_size,
            shuffle=shuffle,
            seed=seed,
            epoch=ep,
            skip_records=skip_records,
            # the indexed branch above resolved both (kwarg > URI >
            # default), so they are never None here
            window=window,  # type: ignore[arg-type]
            merge_gap=merge_gap,  # type: ignore[arg-type]
            legacy_shuffle=legacy,
        )

    if dynamic_shards is None:
        dynamic_shards = bool(uri_int(spec.args, "dynamic_shards", 0))
    if dynamic_shards:
        check(
            not spec.cache_file,
            "dynamic_shards with a #cachefile would freeze one worker's "
            "shard sequence into the cache; pick one",
        )
        check(
            num_shuffle_parts == 0,
            "dynamic_shards already shuffles placement; num_shuffle_parts "
            "composes only with static shards",
        )
        check(
            skip_records == 0,
            "skip_records requires static sharding: mid-epoch resume "
            "under dynamic shards is ledger-owned (completed micro-shards "
            "are simply not re-served — docs/sharding.md)",
        )
        windowed_hint = (
            type == "indexed_recordio"
            and shuffle in ("record", "batch", "window")
            and not legacy
        )
        sig = fileset_signature(spec.uri, index_uri, type)
        try:
            from ..tracker.shardsvc import ShardLeaseClient

            client = ShardLeaseClient()
        except KeyError as e:
            raise Error(
                "dynamic_shards needs a tracker: set DMLC_TRACKER_URI/"
                f"DMLC_TRACKER_PORT (missing {e}) — docs/sharding.md"
            ) from None

        def _make_leased(pi: int, nparts: int, ep: int) -> InputSplit:
            # same wrapper rule as the static tail below: windowed
            # splitters prefetch internally, everything else keeps the
            # read-ahead thread a static drain would have
            b = _build_base(pi, nparts, ep)
            if threaded and not (
                isinstance(b, IndexedRecordIOSplitter) and b.windowed
            ):
                return ThreadedInputSplit(b)
            return b

        return DynamicShardSource(
            _make_leased,
            client=client,
            epoch=epoch,
            fileset=sig,
            windowed_hint=windowed_hint,
            make_probe=_build_base,
        )
    base: InputSplitBase = _build_base(part_index, num_parts, epoch)
    split: InputSplit = base
    if num_shuffle_parts > 0:
        check(
            not spec.cache_file,
            "num_shuffle_parts with a #cachefile would freeze the first "
            "epoch's shuffle order into the cache; pick one",
        )
        shuffled = InputSplitShuffle(
            base, part_index, num_parts, num_shuffle_parts, seed
        )
        # shuffling must not cost the read-ahead thread the unshuffled
        # path gets
        return ThreadedInputSplit(shuffled) if threaded else shuffled
    if spec.cache_file:
        # cached OR threaded, never both: CachedInputSplit prefetches
        # internally (reference io.cc:119-124 chooses exactly one wrapper)
        return CachedInputSplit(base, spec.cache_file)
    if isinstance(base, IndexedRecordIOSplitter) and base.windowed:
        # every unified-path shuffle mode (record/batch/window) already
        # prefetches on its own readahead thread (coalesced spans for
        # window k+1 load while k drains); stacking a ThreadedInputSplit
        # would add a queue without overlap — and would hide
        # next_gather_batch from the fused consumer
        return base
    if threaded:
        return ThreadedInputSplit(base)
    return split
