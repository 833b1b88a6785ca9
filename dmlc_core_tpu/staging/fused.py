"""Fused native staging: text/RecordIO chunks → fixed-shape batches.

The single-pass hot paths for both north-star metrics (BASELINE.md: ≥1M
libsvm rows/s into HBM; RecordIO infeed saturation). Where the generic
path materializes CSR RowBlocks and re-shapes them in Python (parser →
RowBlock → FixedShapeBatcher), these hand each chunk straight to a native
kernel (native/fastparse.cc), which fills a ring of preallocated batch
buffers — no CSR arrays, no copies, no per-row Python. The ring is the
reference's recycle-cell discipline (threadediter.h:155-172) applied to
whole batches.

- FusedDenseLibSVMBatches: libsvm text → dense [B,D]
  (dmlc_parse_libsvm_dense). Semantics match LibSVMParser +
  FixedShapeBatcher('dense') composed, with two documented divergences:
  libsvm auto indexing (indexing_mode=-1) is resolved ONCE from the head
  of the FILE (the generic path re-applies the min-index heuristic per
  chunk slice), and qid tokens are consumed but not carried.
- FusedEllRowRecBatches: rowrec RecordIO → ELL [B,K]
  (dmlc_parse_rowrec_ell). Semantics match RowRecParser +
  FixedShapeBatcher('ell') composed; rows wider than K keep their first K
  features (counted in ``truncated_nnz``).
- FusedEllLibSVMBatches: libsvm text → ELL [B,K]
  (dmlc_parse_libsvm_ell) — sparse Criteo-style libsvm straight to the
  device layout, no CSR detour (the reference's premier text hot path,
  libsvm_parser.h:86-169).
- FusedEllLibFMBatches: libfm text → ELL [B,K] (dmlc_parse_libfm_ell);
  fields are validated, and staged as ``fields`` [B,K] beside the ids
  when ``BatchSpec.fields`` asks for them (dropped otherwise).

Producers expose ``ring_slots`` so consumers composing them with a
prefetch/in-flight pipeline (StagingPipeline) can validate the ring is
deep enough — a yielded batch is only valid until ``ring_slots - 1``
further batches have been produced. That is the whole handoff contract:
the pipeline's dispatch ring copies ``Batch.packed`` into its own slot
buffer at pack time (docs/staging.md), so a producer's slot is free for
recycling the moment the pipeline starts the NEXT batch — but the
pipeline still validates rings against its conservative worst case
(prefetch + depth + 3) because per-array-fallback batches (no usable
packed layout) stay referenced until their DMA completes.
"""

from __future__ import annotations

import mmap
import os
from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..data import native
from ..io import split as io_split
from ..io.filesystem import FileSystem
from ..io.recordio import CFLAG_COMPRESSED, KMAGIC, decode_flag
from ..io.uri import URISpec, rejoin_query, uri_int
from ..telemetry import default_registry as _default_registry
from ..utils.logging import Error, check
from ..utils.profiler import annotate
from .batcher import (
    Batch,
    BatchSpec,
    alloc_packed_slot,
    ell_sections,
    gather_slices,
)

# registry mirrors of the per-producer counters (the per-instance
# attributes stay authoritative for io_stats(); these give the fleet
# view over heartbeats/scrapes)
_REG = _default_registry()
_ROWS_OUT = _REG.counter(
    "staging.rows_out", help="rows emitted in fixed-shape batches"
)
_TRUNCATED = _REG.counter(
    "staging.truncated_nnz", help="features dropped by fixed-shape overflow"
)
_BAD_RECORDS = _REG.counter(
    "staging.bad_records", help="malformed records skipped by fused parsers"
)

__all__ = [
    "FusedDenseCSVBatches",
    "FusedDenseLibSVMBatches",
    "FusedEllRowRecBatches",
    "ShardedFusedBatches",
    "dense_batches",
    "ell_batches",
]

_BOM = b"\xef\xbb\xbf"
_MMAP_CHUNK = 32 << 20


def _plain_local_path(uri: str) -> Optional[str]:
    """Path if the URI is a single un-sharded local file, else None."""
    if any(ch in uri for ch in "?#;*"):
        return None
    path = uri[7:] if uri.startswith("file://") else uri
    if "://" in path:
        return None
    return path if os.path.isfile(path) else None


_REC_SNIFF_BYTES = 4 << 20


def _rec_file_compressed(path: str) -> bool:
    """True when a .rec file carries compressed-block frames in its
    leading window (one vectorized scan of up to 4 MB): compressed
    shards must take the splitter path (decoded chunks), never the raw
    mmap feed — the native kernel walks v1 frames only. Writers emit
    uniform files, so the leading window decides routing; a compressed
    section appearing later (hand-concatenated mixed shards) is caught
    at parse time with an actionable error (_iter_mmap)."""
    from ..io.recordio import chunk_has_compressed

    with open(path, "rb") as f:
        head = f.read(_REC_SNIFF_BYTES)
    return chunk_has_compressed(head)


def _stall_is_compressed_frame(chunk, off: int) -> bool:
    """Does the undecodable tail start with a compressed-block head?"""
    import struct

    head = bytes(memoryview(chunk)[off : off + 8])
    if len(head) != 8:
        return False
    magic, lrec = struct.unpack("<II", head)
    return magic == KMAGIC and bool(decode_flag(lrec) & CFLAG_COMPRESSED)


class _MmapChunks:
    """Zero-copy line-aligned chunks over a local file via mmap.

    The kernel reads pages straight from the page cache — no per-chunk
    bytes allocation or memcpy, which on a single-core TPU host costs as
    much as the parse itself. Boundary scans use mmap.rfind (C speed).
    """

    def __init__(self, path: str, chunk_bytes: int = _MMAP_CHUNK) -> None:
        self._f = open(path, "rb")
        self._size = os.fstat(self._f.fileno()).st_size
        self._mm = (
            mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
            if self._size
            else None
        )
        self._chunk = chunk_bytes
        self._pos = 0

    def next_chunk(self):
        if self._mm is None or self._pos >= self._size:
            return None
        begin = self._pos
        end = min(begin + self._chunk, self._size)
        if end < self._size:
            nl = self._mm.rfind(b"\n", begin, end)
            if nl < begin:
                nl = self._mm.find(b"\n", end)
                end = self._size if nl < 0 else nl + 1
            else:
                end = nl + 1
        self._pos = end
        return memoryview(self._mm)[begin:end]

    def close(self) -> None:
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                pass  # a yielded memoryview is still alive; GC will finish
            self._mm = None
        self._f.close()


from functools import lru_cache


def _read_uri_head(uri: str, nbytes: int = 262144) -> bytes:
    """Head of the FIRST file of a (possibly multi-file) URI.

    Probing at offset 0 (not at a shard's own first chunk) keeps any
    head-resolved setting identical across all (part_index, num_parts)
    shards — different shards must never disagree and silently shift
    feature columns against each other.
    """
    fs = FileSystem.get_instance(uri.split(";")[0])
    first = io_split._expand_uris(fs, uri)[0]
    stream = fs.open(first, "r")
    try:
        return stream.read(nbytes)
    finally:
        stream.close()


def _probe_cache_key(uri: str):
    """(uri, mtime, size) for plain LOCAL files, so a file rewritten at
    the same path (tests, regenerated datasets) never resolves a stale
    cached indexing base (ADVICE r3). Remote and wildcard URIs keep the
    uri-only per-process key — a stat per producer construction there
    would cost a network round trip per sub-shard, the exact cost the
    cache exists to avoid."""
    base = uri.split(";")[0].split("?")[0]
    if "://" not in base:
        try:
            st = os.stat(base)
            return (uri, st.st_mtime_ns, st.st_size)
        except OSError:
            pass
    return (uri, 0, -1)


@lru_cache(maxsize=64)
def _probe_base_cached(key) -> int:
    return _probe_base(_read_uri_head(key[0]))


def _probe_base_from_uri(uri: str) -> int:
    """Resolve libsvm auto indexing from the file head. Cached per
    (uri, mtime, size): a threaded fan-out constructs one producer per
    sub-shard and must not re-read (possibly remote) file heads per
    thread — but a rewritten file must re-probe."""
    return _probe_base_cached(_probe_cache_key(uri))


def _probe_base(chunk) -> int:
    """Resolve the libsvm auto indexing mode from the head of a chunk.

    Reference heuristic (libsvm_parser.h:159-168, à la sklearn): data is
    1-based iff no 0 feature id appears; sampled over the first ~256KB.
    """
    head = bytes(memoryview(chunk)[:262144])
    min_idx: Optional[int] = None
    for line in head.splitlines()[:2000]:
        body = line.split(b"#", 1)[0]
        toks = body.split()
        for tok in toks[1:]:
            if tok.startswith(b"qid:"):
                continue
            try:
                idx = int(tok.split(b":", 1)[0])
            except ValueError:
                continue
            if idx == 0:
                return 0
            if min_idx is None or idx < min_idx:
                min_idx = idx
    return 1 if (min_idx is not None and min_idx > 0) else 0


class _FusedTextBatches:
    """Shared machinery for fused text → fixed-shape-batch producers.

    Yields Batch views into a ring of ``ring`` preallocated buffer sets
    (each one contiguous buffer, so the staging pipeline can issue a
    single DMA per batch); a yielded batch stays valid until
    ``ring_slots - 1`` further batches have been produced. Subclasses
    implement the slot layout (``_alloc_slot``/``_emit``/``_pad_tail``)
    and ``_parse`` (one resumable native call), and optionally
    ``_first_chunk``.
    """

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        ring: int = 10,
    ) -> None:
        check(spec.value_dtype in (np.dtype(np.float32), np.dtype(np.float16)),
              f"fused path supports f32/f16 values, not {spec.value_dtype}")
        self.spec = spec
        self.uspec = URISpec(uri, part_index, num_parts)
        # the split opens lazily (first iteration): subclass __init__s
        # still validate URI args, and a validation failure must not leak
        # an open mmap/fd
        self._split_args = (part_index, num_parts)
        self._split = None
        with annotate("dmlc:producer_open"):
            self._ring: List[Tuple[np.ndarray, ...]] = [
                self._alloc_slot() for _ in range(max(2, ring))
            ]
        self.ring_slots = len(self._ring)
        self._slot = 0
        self.rows_in = 0
        self.rows_out = 0
        self.truncated_nnz = 0

    # -- subclass hooks ------------------------------------------------------
    def _alloc_slot(self) -> Tuple[np.ndarray, ...]:
        """One ring slot: views into a packed buffer, packed buffer last."""
        raise NotImplementedError

    def _first_chunk(self, chunk, off: int) -> int:
        """Inspect the first chunk (BOM, format probes); returns new off."""
        if bytes(memoryview(chunk)[:3]) == _BOM:
            off += 3  # UTF-8 BOM skip (text_parser.h:81-95)
        return off

    def _parse(self, chunk, off, slot, fill, cr_hint):
        """One resumable native call → (rows, consumed, cr_hint), updating
        truncation/error counters on self."""
        raise NotImplementedError

    def _emit(self, slot, n_valid: int) -> Batch:
        raise NotImplementedError

    def _pad_tail(self, slot, fill: int) -> None:
        """Zero the padding rows of a final partial batch."""
        raise NotImplementedError

    # -- shared loop ---------------------------------------------------------
    def _ensure_split(self):
        if self._split is None:
            part_index, num_parts = self._split_args
            local = (
                _plain_local_path(self.uspec.uri) if num_parts == 1 else None
            )
            with annotate("dmlc:producer_open"):
                self._split = (
                    _MmapChunks(local)
                    if local is not None
                    else io_split.create(
                        self.uspec.uri, part_index, num_parts, type="text"
                    )
                )
        return self._split

    def __iter__(self) -> Iterator[Batch]:
        split = self._ensure_split()
        B = self.spec.batch_size
        slot = self._ring[self._slot]
        fill = 0
        first = True
        while True:
            with annotate("dmlc:fetch"):
                chunk = split.next_chunk()
            if chunk is None:
                break
            off = 0
            if first:
                off = self._first_chunk(chunk, off)
                first = False
            n = len(chunk)
            cr_hint = -1  # probe once per chunk, cache across resumed calls
            while off < n:
                with annotate("dmlc:parse"):
                    rows, consumed, cr_hint = self._parse(
                        chunk, off, slot, fill, cr_hint
                    )
                if consumed == 0 and rows == 0:
                    break  # defensive: no forward progress
                off += consumed
                fill += rows
                self.rows_in += rows
                if fill == B:
                    yield self._emit(slot, B)
                    self._slot = (self._slot + 1) % len(self._ring)
                    slot = self._ring[self._slot]
                    fill = 0
        if fill:
            # zero-pad the tail batch; padding rows carry weight 0
            self._pad_tail(slot, fill)
            yield self._emit(slot, fill)
            self._slot = (self._slot + 1) % len(self._ring)

    def close(self) -> None:
        if self._split is not None:
            self._split.close()


class _FusedDenseTextBatches(_FusedTextBatches):
    """Dense-slot specialization: ring slots are (x, labels, weights,
    packed) views over one contiguous buffer per slot."""

    def __init__(self, uri, spec, part_index=0, num_parts=1, ring=10):
        check(spec.layout == "dense", "fused path requires layout='dense'")
        super().__init__(uri, spec, part_index, num_parts, ring)

    def _alloc_slot(self):
        spec = self.spec
        B, D = spec.batch_size, int(spec.num_features)  # type: ignore[arg-type]
        buf, v = alloc_packed_slot(
            [
                ("x", (B, D), spec.value_dtype),
                ("labels", (B,), np.float32),
                ("weights", (B,), np.float32),
            ]
        )
        return (v["x"], v["labels"], v["weights"], buf)

    def _emit(self, slot, n_valid: int) -> Batch:
        x, labels, weights, packed = slot
        self.rows_out += n_valid
        _ROWS_OUT.inc(n_valid)
        if self.spec.overflow == "error" and self.truncated_nnz:
            raise Error(
                f"{self.truncated_nnz} features outside [0, "
                f"{self.spec.num_features}) with overflow='error'"
            )
        return Batch(labels=labels, weights=weights, n_valid=n_valid, x=x,
                     packed=packed)

    def _pad_tail(self, slot, fill: int) -> None:
        x, labels, weights, _packed = slot
        x[fill:] = 0
        labels[fill:] = 0
        weights[fill:] = 0


class FusedDenseLibSVMBatches(_FusedDenseTextBatches):
    """libsvm text → dense [B,D] via dmlc_parse_libsvm_dense."""

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        indexing_mode: int = 0,
        ring: int = 10,
    ) -> None:
        check(native.HAS_DENSE, "native fused kernel not loaded")
        super().__init__(uri, spec, part_index, num_parts, ring)
        if "indexing_mode" in self.uspec.args:
            # per-dataset options ride the URI (reference uri_spec.h), same
            # as the generic LibSVMParser path
            indexing_mode = int(self.uspec.args["indexing_mode"])
        if indexing_mode < 0 and num_parts > 1:
            # auto mode must resolve identically on every shard: probe the
            # head of the file, not this shard's mid-file first chunk
            indexing_mode = _probe_base_from_uri(self.uspec.uri)
        self._indexing_mode = indexing_mode
        self._base: Optional[int] = (
            None if indexing_mode < 0 else (1 if indexing_mode > 0 else 0)
        )

    def _first_chunk(self, chunk, off: int) -> int:
        off = super()._first_chunk(chunk, off)
        if self._base is None:
            self._base = _probe_base(chunk)
        return off

    def _parse(self, chunk, off, slot, fill, cr_hint):
        x, labels, weights, _packed = slot
        rows, consumed, trunc, cr_hint = native.parse_libsvm_dense(
            chunk, off, self._base or 0, x, labels, weights, fill, cr_hint
        )
        self.truncated_nnz += trunc
        if trunc:
            _TRUNCATED.inc(trunc)
        return rows, consumed, cr_hint


class FusedDenseCSVBatches(_FusedDenseTextBatches):
    """csv text → dense [B,D] via dmlc_parse_csv_dense.

    Semantics match CSVParser + FixedShapeBatcher('dense') composed
    (reference csv_parser.h:98-111): per-cell longest-prefix float parse;
    ``label_column`` (default -1 = none, label 0.0, matching
    CSVParserParam), ``weight_column`` and ``delimiter`` ride the URI
    query or the constructor; a non-empty line with no delimiter raises,
    as the generic parser does on a malformed file.
    """

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        label_column: int = -1,
        weight_column: int = -1,
        delimiter: str = ",",
        ring: int = 10,
    ) -> None:
        check(native.HAS_CSV_DENSE, "native fused csv kernel not loaded")
        super().__init__(uri, spec, part_index, num_parts, ring)
        args = self.uspec.args
        self._label_col = int(args.get("label_column", label_column))
        self._weight_col = int(args.get("weight_column", weight_column))
        # same validations as CSVParserParam/CSVParser, so fused and
        # generic paths accept/reject identical URIs
        check(
            self._label_col != self._weight_col or self._label_col < 0,
            "Must have distinct columns for labels and instance weights",
        )
        delim = str(args.get("delimiter", delimiter))
        check(len(delim) == 1, f"delimiter must be one char, got {delim!r}")
        check(ord(delim) < 128,
              f"fused csv path requires an ASCII delimiter, got {delim!r}")
        self._delim = ord(delim)
        self.bad_lines = 0

    def _parse(self, chunk, off, slot, fill, cr_hint):
        x, labels, weights, _packed = slot
        rows, consumed, trunc, cr_hint, bad = native.parse_csv_dense(
            chunk, off, self._delim, self._label_col, self._weight_col,
            x, labels, weights, fill, cr_hint,
        )
        self.truncated_nnz += trunc
        if trunc:
            _TRUNCATED.inc(trunc)
        if bad:
            raise Error(
                "Delimiter not found in the line. "
                "Expected it to separate fields."
            )
        return rows, consumed, cr_hint


class _EllSlotMixin:
    """Shared ELL ring-slot layout for the fused ELL producers: each slot
    is (indices, values, nnz, labels, weights[, fields], packed) views
    over ONE contiguous buffer (``batcher.ell_sections``; ``fields`` only
    when the spec stages field ids) → one DMA per staged batch. Classes
    using it carry ``spec``, ``rows_out`` and ``truncated_nnz``."""

    def _alloc_ell_slot(self):
        buf, v = alloc_packed_slot(ell_sections(self.spec))
        return tuple(v.values()) + (buf,)

    @staticmethod
    def _slot_fields(slot):
        """The slot's ``fields`` view, or None when it stages none."""
        return slot[5] if len(slot) == 7 else None

    def _emit_ell(self, slot, n_valid: int) -> Batch:
        indices, values, nnz, labels, weights = slot[:5]
        fields, packed = self._slot_fields(slot), slot[-1]
        self.rows_out += n_valid
        _ROWS_OUT.inc(n_valid)
        if self.spec.overflow == "error" and self.truncated_nnz:
            raise Error(
                f"{self.truncated_nnz} features beyond max_nnz="
                f"{self.spec.max_nnz} with overflow='error'"
            )
        return Batch(
            labels=labels, weights=weights, n_valid=n_valid,
            indices=indices, values=values, nnz=nnz, fields=fields,
            packed=packed,
        )

    def _pad_ell_tail(self, slot, fill: int) -> None:
        for a in slot[:-1]:
            a[fill:] = 0


class FusedEllRowRecBatches(_EllSlotMixin):
    """Iterator of ELL Batches over a rowrec RecordIO URI via the fused
    native kernel (native/fastparse.cc dmlc_parse_rowrec_ell).

    The RecordIO→HBM hot path (BASELINE.md north star #2): RecordIO frame
    scan + binary rowrec decode + ELL fill in one native pass, writing into
    a ring of preallocated buffer sets. For a single local file the kernel
    consumes raw mmap windows directly (it stops cleanly at a trailing
    partial record, so no boundary pre-scan is needed); sharded/remote URIs
    go through RecordIOSplitter chunks (record-aligned byte-range sharding,
    reference src/io/recordio_split.cc). Shuffled-epoch reads ride the URI
    sugar (``?index=<uri>&shuffle=record|batch|window``) and take the
    GATHER fast path: the windowed split (coalesced spans + readahead,
    io/split.py) hands ``(buf, starts, sizes)`` batch views and the
    native gather kernel parses records straight out of the window
    buffer in permutation order — full per-record randomness at
    near-sequential read cost with zero per-record Python
    (``&legacy_shuffle=1`` forces the reference's per-record seek loop
    for A/B). ``io_stats()`` exposes the split's seek/span/gather
    counters so the I/O shape is observable.

    A yielded batch stays valid until ``ring_slots - 1`` further batches
    have been produced.
    """

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        ring: int = 10,
    ) -> None:
        check(native.HAS_ELL, "native fused ELL kernel not loaded")
        check(spec.layout == "ell", "fused rowrec path requires layout='ell'")
        check(not spec.fields, "rowrec records carry no field ids")
        check(spec.value_dtype in (np.dtype(np.float32), np.dtype(np.float16)),
              f"fused path supports f32/f16 values, not {spec.value_dtype}")
        check(spec.index_dtype == np.dtype(np.int32),
              "fused ELL path stages int32 indices")
        self.spec = spec
        uspec = URISpec(uri, part_index, num_parts)
        # only path+query are forwarded below — a #cachefile would be
        # SILENTLY ignored; fail loudly like the shuffle+cachefile guards
        check(
            not uspec.cache_file,
            "fused rowrec staging does not take a #cachefile (it already "
            "reads the binary shard at full speed); drop the fragment",
        )
        # epoch shuffling (?shuffle_parts=N&seed=S) and count-indexed
        # access (?index=...&shuffle=1) ride the URI; both reorder reads,
        # so the sequential mmap fast path is only taken without them
        shuffle_parts = uri_int(uspec.args, "shuffle_parts", 0)
        local = (
            _plain_local_path(uspec.uri)
            if num_parts == 1 and shuffle_parts == 0
            and "index" not in uspec.args
            else None
        )
        if local is not None and _rec_file_compressed(local):
            # compressed-block shard: the native kernel walks v1 frames
            # only, so route through RecordIOSplitter — its chunks come
            # back DECODED (parallel block decompress, io/recordio.py
            # decode_chunk) and feed the same kernel unchanged
            local = None
        self._mmap = local is not None
        # forward path + query (fragment stripped, matching the mmap fast
        # path): io_split.create resolves the sugar (shuffle_parts /
        # index / seed) itself
        with annotate("dmlc:producer_open"):
            self._split = (
                _MmapRawChunks(local)
                if local is not None
                else io_split.create(
                    uspec.uri + rejoin_query(uspec.args),
                    part_index, num_parts, type="recordio",
                )
            )
            self._ring: List[Tuple[np.ndarray, ...]] = [
                self._alloc_ell_slot() for _ in range(max(2, ring))
            ]
        self.ring_slots = len(self._ring)
        self._slot = 0
        self.rows_in = 0
        self.rows_out = 0
        self.truncated_nnz = 0
        self.bad_records = 0
        # shuffled gather fast path: a windowed shuffle split
        # (shuffle=record/batch/window, io/split.py) hands whole
        # batches as (buf, starts, sizes) views — parsed straight out
        # of the window buffer by the native gather kernel, no
        # per-record Python and no re-framing copy
        sg = getattr(self._split, "supports_gather", None)
        self._gather = bool(sg is not None and sg())

    def io_stats(self):
        """Counters from the underlying split — seek/span shape on
        indexed shuffled reads, retry/fault deltas on every split-backed
        path — or an empty dict on the mmap fast path (every io_stats()
        implementation returns a dict, ISSUE 4 satellite)."""
        fn = getattr(self._split, "io_stats", None)
        out = fn() if fn is not None else None
        return out if out else {}

    def _emit(self, bufs, n_valid: int) -> Batch:
        return self._emit_ell(bufs, n_valid)

    def _feed(self, chunk, off: int, fill: int):
        """Parse chunk[off:] into the current slot; returns updated
        (off, fill, made_progress)."""
        indices, values, nnz, labels, weights = self._ring[self._slot][:5]
        with annotate("dmlc:parse"):
            rows, consumed, trunc, bad, corrupt = native.parse_rowrec_ell(
                chunk, off, indices, values, nnz, labels, weights, fill
            )
        self.rows_in += rows
        self.truncated_nnz += trunc
        self.bad_records += bad
        if trunc:
            _TRUNCATED.inc(trunc)
        if bad:
            _BAD_RECORDS.inc(bad)
        if corrupt:
            # bad magic with a full header in view: the stream is broken
            # HERE — fail fast instead of carrying the rest of the shard
            # as a 'partial record' until end-of-split (ADVICE r3)
            raise Error(
                "rowrec: corrupt RecordIO frame (bad magic) at byte "
                f"{off + consumed} of the current chunk"
            )
        return off + consumed, fill + rows, (rows > 0 or consumed > 0)

    def __iter__(self) -> Iterator[Batch]:
        B = self.spec.batch_size
        fill = 0
        if self._mmap:
            yield from self._iter_mmap()
            return
        if self._gather:
            yield from self._iter_gather()
            return
        carry = b""
        while True:
            with annotate("dmlc:fetch"):
                chunk = self._split.next_chunk()
            if chunk is None:
                break
            if carry:
                chunk = carry + bytes(chunk)
                carry = b""
            off, n = 0, len(chunk)
            while off < n:
                off, fill, progressed = self._feed(chunk, off, fill)
                if fill == B:
                    yield self._emit(self._ring[self._slot], B)
                    self._slot = (self._slot + 1) % len(self._ring)
                    fill = 0
                elif not progressed:
                    # trailing partial record (a chain straddling the
                    # chunk boundary): carry the tail into the next
                    # chunk. (A corrupt frame raised inside _feed — it
                    # can never reach here.)
                    carry = bytes(memoryview(chunk)[off:])
                    break
        if carry:
            raise Error(
                "rowrec: truncated RecordIO stream "
                f"({len(carry)} undecodable trailing bytes)"
            )
        if fill:
            yield from self._tail(fill)

    def _iter_gather(self) -> Iterator[Batch]:
        """Shuffled gather fast path (docs/shuffle.md): the windowed
        split emits ``(buf, starts, sizes)`` — span bytes plus
        per-record offsets in permutation order — and the native gather
        kernel parses every record straight out of the window buffer
        into the ring slot: ONE native call per batch, no per-record
        Python, no re-framing memcpy. When the loaded .so predates the
        gather entry point, the batch is re-framed with one vectorized
        numpy gather (``gather_slices``) and fed to the sequential
        chunk kernel instead — same rows, one extra copy."""
        B = self.spec.batch_size
        fill = 0
        use_native = native.HAS_GATHER_ELL
        while True:
            with annotate("dmlc:fetch"):
                g = self._split.next_gather_batch(B - fill)
            if g is None:
                break
            buf, starts, sizes = g
            if not use_native:
                self._split.count_gather_fallback()
                chunk = gather_slices(buf, starts, sizes)
                off, fill, progressed = self._feed(chunk, 0, fill)
                check(
                    progressed and off == len(chunk),
                    "rowrec: truncated record in shuffled gather batch "
                    "(index and data disagree)",
                )
                if fill == B:
                    yield self._emit(self._ring[self._slot], B)
                    self._slot = (self._slot + 1) % len(self._ring)
                    fill = 0
                continue
            off, n = 0, len(starts)
            while off < n:
                slot = self._ring[self._slot]
                indices, values, nnz, labels, weights = slot[:5]
                with annotate("dmlc:parse"):
                    rows, consumed, trunc, bad, corrupt = (
                        native.parse_rowrec_gather_ell(
                            buf, starts, sizes, off, n - off,
                            indices, values, nnz, labels, weights, fill,
                        )
                    )
                self.rows_in += rows
                self.truncated_nnz += trunc
                self.bad_records += bad
                if trunc:
                    _TRUNCATED.inc(trunc)
                if bad:
                    _BAD_RECORDS.inc(bad)
                if corrupt:
                    raise Error(
                        "rowrec: corrupt RecordIO frame in shuffled "
                        f"gather slice {off + consumed} (the index and "
                        "the data disagree)"
                    )
                check(consumed > 0 or rows > 0, "gather made no progress")
                off += consumed
                fill += rows
                if fill == B:
                    yield self._emit(slot, B)
                    self._slot = (self._slot + 1) % len(self._ring)
                    fill = 0
        if fill:
            yield from self._tail(fill)

    def _iter_mmap(self) -> Iterator[Batch]:
        B = self.spec.batch_size
        fill = 0
        while True:
            with annotate("dmlc:fetch"):
                chunk = self._split.window()
            if chunk is None:
                break
            off, n = 0, len(chunk)
            stalled = False
            while off < n:
                off, fill, progressed = self._feed(chunk, off, fill)
                if fill == B:
                    yield self._emit(self._ring[self._slot], B)
                    self._slot = (self._slot + 1) % len(self._ring)
                    fill = 0
                elif not progressed:
                    if _stall_is_compressed_frame(chunk, off):
                        # mixed v1+compressed file past the routing
                        # sniff window (hand-concatenated shards): the
                        # native kernel cannot walk compressed frames —
                        # name the fix instead of a 'truncated' error
                        raise Error(
                            "rowrec: compressed RecordIO block mid-file; "
                            "the mmap fast path reads v1 frames only — "
                            "read via a sharded/indexed URI (splitter "
                            "path decodes blocks) or normalize with "
                            "`tools recompress`"
                        )
                    stalled = True
                    break
            self._split.advance(off)
            if stalled and off == 0:
                # not one complete record fit the window: widen it (a
                # window that already reaches EOF means a truncated
                # file; corrupt frames raise inside _feed)
                if not self._split.grow():
                    raise Error(
                        "rowrec: truncated RecordIO stream (record "
                        "extends past end of file)"
                    )
        if fill:
            yield from self._tail(fill)

    def _tail(self, fill: int) -> Iterator[Batch]:
        # zero-pad the final partial batch; padding rows carry weight 0
        self._pad_ell_tail(self._ring[self._slot], fill)
        yield self._emit(self._ring[self._slot], fill)
        self._slot = (self._slot + 1) % len(self._ring)

    def close(self) -> None:
        self._split.close()


class _MmapRawChunks:
    """Raw byte windows over a local file via mmap, with caller-driven
    consumption: the fused RecordIO kernel stops at a trailing partial
    record and reports bytes consumed, so windows need no record-boundary
    pre-scan — ``advance(consumed)`` moves the cursor, ``grow()`` widens
    the window when a single record exceeds it."""

    def __init__(self, path: str, chunk_bytes: int = _MMAP_CHUNK) -> None:
        self._f = open(path, "rb")
        self._size = os.fstat(self._f.fileno()).st_size
        self._mm = (
            mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
            if self._size
            else None
        )
        self._chunk = chunk_bytes
        self._pos = 0
        self._width = chunk_bytes

    def window(self):
        """Current memoryview window, or None at EOF."""
        if self._mm is None or self._pos >= self._size:
            return None
        end = min(self._pos + self._width, self._size)
        return memoryview(self._mm)[self._pos:end]

    def advance(self, consumed: int) -> None:
        self._pos += consumed
        if consumed:
            self._width = self._chunk  # reset growth once progress resumes

    def grow(self) -> bool:
        """Widen the window (a record straddles it). False if the window
        already reaches EOF — the file is truncated/corrupt."""
        if self._pos + self._width >= self._size:
            return False
        self._width *= 2
        return True

    def close(self) -> None:
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                pass  # a yielded memoryview is still alive; GC will finish
            self._mm = None
        self._f.close()


class ShardedFusedBatches:
    """Fan a fused producer out across threads (VERDICT r2 weak #7: the
    fused kernels are single-threaded; a v5e host has many cores).

    The (part_index, num_parts) range is over-partitioned into
    ``nthread`` sub-shards (the InputSplitShuffle trick, reference
    input_split_shuffle.h:24-33, applied to threads); each sub-shard gets
    its own fused producer running under a ThreadedIter (the native
    kernels release the GIL, so parses genuinely overlap), and batches
    interleave round-robin.

    Divergences from the single-producer stream, both documented and
    coverage-preserving: row ORDER interleaves across sub-shards, and
    each sub-shard pads its own tail batch (up to ``nthread`` partial
    batches instead of one).
    """

    def __init__(self, make_producer, subparts: int, prefetch: int = 2):
        from ..concurrency.threaded_iter import ThreadedIter

        self._producers = []
        self._iters = []
        try:
            for t in range(subparts):
                self._producers.append(make_producer(t, subparts))
            min_ring = min(p.ring_slots for p in self._producers)
            # a sub-shard's producer runs ahead of the combined stream by
            # its queue depth + one blocked put; the ring guarantee we can
            # advertise downstream shrinks by exactly that much (the
            # consumer-side check in StagingPipeline composes with this)
            self.ring_slots = min_ring - (prefetch + 1)
            check(
                self.ring_slots >= 2,
                f"sub-producer rings ({min_ring}) must exceed the "
                f"per-shard prefetch ({prefetch}) + 1 by at least 2",
            )
            for t, p in enumerate(self._producers):
                self._iters.append(
                    ThreadedIter(
                        (lambda prod: (lambda: iter(prod)))(p),
                        max_capacity=prefetch,
                        name=f"fused-shard-{t}",
                    )
                )
        except BaseException:
            self.close()
            raise

    @property
    def truncated_nnz(self) -> int:
        return sum(p.truncated_nnz for p in self._producers)

    @property
    def rows_in(self) -> int:
        return sum(p.rows_in for p in self._producers)

    @property
    def rows_out(self) -> int:
        return sum(p.rows_out for p in self._producers)

    @property
    def bad_records(self) -> int:
        """Aggregated corrupt-record count (ELL sub-producers)."""
        return sum(getattr(p, "bad_records", 0) for p in self._producers)

    @property
    def bad_lines(self) -> int:
        """Aggregated malformed-line count (CSV sub-producers)."""
        return sum(getattr(p, "bad_lines", 0) for p in self._producers)

    def io_stats(self):
        """Summed seek/span counters across sub-producers that track
        them (numeric fields add; the mode tag carries over), or an
        empty dict when no sub-producer does."""
        stats = [
            s
            for p in self._producers
            for s in [getattr(p, "io_stats", lambda: None)()]
            if s
        ]
        if not stats:
            return {}
        out: dict = {}
        for s in stats:
            for k, v in s.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
                else:
                    out.setdefault(k, v)
        return out

    def __iter__(self) -> Iterator[Batch]:
        active = list(self._iters)
        while active:
            still = []
            for it in active:
                batch = it.next()
                if batch is None:
                    continue
                still.append(it)
                yield batch
            active = still

    def close(self) -> None:
        for it in self._iters:
            it.destroy()
        for p in self._producers:
            p.close()


@lru_cache(maxsize=64)
def _probe_libfm_base_cached(key) -> int:
    return _probe_libfm_base(_read_uri_head(key[0]))


def _probe_libfm_base_from_uri(uri: str) -> int:
    """Resolve libfm auto indexing from the file head (same caching and
    shard-consistency rationale as ``_probe_base_from_uri``, same
    (uri, mtime, size) staleness key)."""
    return _probe_libfm_base_cached(_probe_cache_key(uri))


def _probe_libfm_base(chunk) -> int:
    """libfm auto indexing from a head sample: 1-based iff every field id
    AND feature id seen is > 0 (the native CSR parser's auto rule,
    native/fastparse.cc dmlc_parse_libfm; reference
    libfm_parser.h:67-144 requires both). Tokens are accepted/rejected by
    the same parse_triple rule the parsers use — a junk token the parsers
    would skip must not decide the base."""
    from ..data.strtonum import parse_triple

    head = bytes(memoryview(chunk)[:262144])
    seen = False
    for line in head.splitlines()[:2000]:
        for tok in line.split()[1:]:
            triple = parse_triple(tok)
            if triple is None:
                continue
            fid, feat, _v = triple
            if fid <= 0 or feat <= 0:  # native auto rule: min of BOTH > 0
                return 0
            seen = True
    return 1 if seen else 0


class FusedEllLibFMBatches(_EllSlotMixin, _FusedTextBatches):
    """libfm text → ELL [B,K] via dmlc_parse_libfm_ell.

    Semantics match LibFMParser + FixedShapeBatcher('ell') composed
    (reference libfm_parser.h:67-144 tolerant tokenization). Field ids
    are validated, then staged as ``fields`` [B,K] when the spec asks
    (``BatchSpec.fields``; the same base as the feature ids, padding
    slots field 0) and dropped otherwise, exactly like the generic
    batcher. ``indexing_mode`` rides the
    constructor or ``?indexing_mode=`` on the URI; auto (-1) resolves
    ONCE against the file head so shards can never disagree.
    """

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        indexing_mode: int = 0,
        ring: int = 10,
    ) -> None:
        check(native.HAS_LIBFM_ELL, "native fused libfm kernel not loaded")
        check(spec.layout == "ell", "fused libfm path requires layout='ell'")
        check(spec.index_dtype == np.dtype(np.int32),
              "fused ELL path stages int32 indices")
        super().__init__(uri, spec, part_index, num_parts, ring)
        if "indexing_mode" in self.uspec.args:
            indexing_mode = int(self.uspec.args["indexing_mode"])
        if indexing_mode < 0 and num_parts > 1:
            indexing_mode = _probe_libfm_base_from_uri(self.uspec.uri)
        self._base: Optional[int] = (
            None if indexing_mode < 0 else (1 if indexing_mode > 0 else 0)
        )

    def _first_chunk(self, chunk, off: int) -> int:
        off = super()._first_chunk(chunk, off)
        if self._base is None:
            self._base = _probe_libfm_base(chunk)
        return off

    def _alloc_slot(self):
        return self._alloc_ell_slot()

    def _parse(self, chunk, off, slot, fill, cr_hint):
        indices, values, nnz, labels, weights = slot[:5]
        rows, consumed, trunc, cr_hint = native.parse_libfm_ell(
            chunk, off, self._base or 0, indices, values, nnz, labels,
            weights, fill, cr_hint, self._slot_fields(slot),
        )
        self.truncated_nnz += trunc
        return rows, consumed, cr_hint

    def _emit(self, slot, n_valid: int) -> Batch:
        return self._emit_ell(slot, n_valid)

    def _pad_tail(self, slot, fill: int) -> None:
        self._pad_ell_tail(slot, fill)


class FusedEllLibSVMBatches(_EllSlotMixin, _FusedTextBatches):
    """libsvm text → ELL [B,K] via dmlc_parse_libsvm_ell.

    Semantics match LibSVMParser + FixedShapeBatcher('ell') composed —
    the sparse layout a real Criteo-libsvm file needs (reference
    src/data/libsvm_parser.h:86-169 is the reference's premier text hot
    path). '#' comments and a second 'qid:N' token are consumed like the
    dense kernel; ``indexing_mode`` rides the constructor or
    ``?indexing_mode=`` on the URI; auto (-1) resolves ONCE against the
    file head so shards can never disagree.
    """

    def __init__(
        self,
        uri: str,
        spec: BatchSpec,
        part_index: int = 0,
        num_parts: int = 1,
        indexing_mode: int = 0,
        ring: int = 10,
    ) -> None:
        check(native.HAS_LIBSVM_ELL,
              "native fused libsvm ELL kernel not loaded")
        check(spec.layout == "ell", "fused libsvm path requires layout='ell'")
        check(not spec.fields, "libsvm text carries no field ids")
        check(spec.index_dtype == np.dtype(np.int32),
              "fused ELL path stages int32 indices")
        super().__init__(uri, spec, part_index, num_parts, ring)
        if "indexing_mode" in self.uspec.args:
            indexing_mode = int(self.uspec.args["indexing_mode"])
        if indexing_mode < 0 and num_parts > 1:
            indexing_mode = _probe_base_from_uri(self.uspec.uri)
        self._base: Optional[int] = (
            None if indexing_mode < 0 else (1 if indexing_mode > 0 else 0)
        )

    def _first_chunk(self, chunk, off: int) -> int:
        off = super()._first_chunk(chunk, off)
        if self._base is None:
            self._base = _probe_base(chunk)
        return off

    def _alloc_slot(self):
        return self._alloc_ell_slot()

    def _parse(self, chunk, off, slot, fill, cr_hint):
        indices, values, nnz, labels, weights = slot[:5]
        rows, consumed, trunc, cr_hint = native.parse_libsvm_ell(
            chunk, off, self._base or 0, indices, values, nnz, labels,
            weights, fill, cr_hint,
        )
        self.truncated_nnz += trunc
        return rows, consumed, cr_hint

    def _emit(self, slot, n_valid: int) -> Batch:
        return self._emit_ell(slot, n_valid)

    def _pad_tail(self, slot, fill: int) -> None:
        self._pad_ell_tail(slot, fill)


def ell_batches(
    uri: str,
    spec: BatchSpec,
    part_index: int = 0,
    num_parts: int = 1,
    ring: int = 10,
    nthread: Optional[int] = None,
    format: str = "auto",
    indexing_mode: int = 0,
):
    """Best-available ELL Batch stream for a rowrec RecordIO URI or a
    libsvm/libfm text URI.

    ``format``: 'rowrec' | 'libsvm' | 'libfm' | 'auto' (``?format=``
    from the URI, defaulting to rowrec). ``indexing_mode`` applies to
    the libsvm/libfm paths (same contract as ``dense_batches``;
    ``?indexing_mode=`` on the URI wins). Uses the fused native kernel
    when loaded, otherwise the generic parser → FixedShapeBatcher path
    with the same semantics. Either way the result is iterable and has
    ``.close()``. ``nthread`` > 1 fans the fused parse out over threads
    (ShardedFusedBatches: interleaved sub-shard order, one padded tail
    per sub-shard).
    """
    if uri.startswith("dsserve://"):
        # remote preprocessing tier (dmlc_core_tpu/dsserve/): the
        # servers run THIS factory for their shards; the trainer side
        # only receives finished packed slots (docs/dsserve.md). The
        # static shard args don't apply — striping is per endpoint /
        # per tracker lease.
        check(
            part_index == 0 and num_parts == 1,
            "dsserve:// sources stripe across servers (or tracker "
            "leases), not part_index/num_parts",
        )
        from ..dsserve.client import DsServeBatches

        return DsServeBatches(uri, spec, format=format)
    uspec = URISpec(uri, part_index, num_parts)
    if format == "auto":
        format = str(uspec.args.get("format", "rowrec"))
    check(format in ("rowrec", "libsvm", "libfm"),
          f"ell_batches supports rowrec/libsvm/libfm, not {format!r}")
    fusable = (
        spec.layout == "ell"
        and spec.value_dtype in (np.dtype(np.float32), np.dtype(np.float16))
        and spec.index_dtype == np.dtype(np.int32)
        and spec.overflow == "truncate"
    )
    if format == "libsvm":
        if native.HAS_LIBSVM_ELL and fusable:
            if nthread is not None and nthread > 1:
                return ShardedFusedBatches(
                    lambda t, n: FusedEllLibSVMBatches(
                        uri, spec, part_index * n + t, num_parts * n,
                        indexing_mode=indexing_mode, ring=ring,
                    ),
                    nthread,
                )
            return FusedEllLibSVMBatches(
                uri, spec, part_index, num_parts,
                indexing_mode=indexing_mode, ring=ring,
            )
        from ..data import create_parser
        from .batcher import FixedShapeBatcher

        if indexing_mode and "indexing_mode" not in uspec.args:
            head, sep, frag = uri.partition("#")
            head += ("&" if "?" in head else "?") + (
                f"indexing_mode={indexing_mode}"
            )
            uri = head + sep + frag
        parser = create_parser(
            uri, part_index, num_parts, type="libsvm", nthread=nthread
        )
        return _GenericBatchStream(parser, FixedShapeBatcher(spec))
    if format == "libfm":
        if native.HAS_LIBFM_ELL and fusable:
            if nthread is not None and nthread > 1:
                return ShardedFusedBatches(
                    lambda t, n: FusedEllLibFMBatches(
                        uri, spec, part_index * n + t, num_parts * n,
                        indexing_mode=indexing_mode, ring=ring,
                    ),
                    nthread,
                )
            return FusedEllLibFMBatches(
                uri, spec, part_index, num_parts,
                indexing_mode=indexing_mode, ring=ring,
            )
        from ..data import create_parser
        from .batcher import FixedShapeBatcher

        if indexing_mode and "indexing_mode" not in uspec.args:
            # parser params ride the URI (URI-provided values keep
            # winning); insert before any #cachefile fragment
            head, sep, frag = uri.partition("#")
            head += ("&" if "?" in head else "?") + (
                f"indexing_mode={indexing_mode}"
            )
            uri = head + sep + frag
        parser = create_parser(
            uri, part_index, num_parts, type="libfm", nthread=nthread
        )
        return _GenericBatchStream(parser, FixedShapeBatcher(spec))
    if native.HAS_ELL and fusable:
        if nthread is not None and nthread > 1:
            return ShardedFusedBatches(
                lambda t, n: FusedEllRowRecBatches(
                    uri, spec, part_index * n + t, num_parts * n, ring
                ),
                nthread,
            )
        return FusedEllRowRecBatches(uri, spec, part_index, num_parts, ring)
    from ..data import create_parser
    from .batcher import FixedShapeBatcher

    parser = create_parser(
        uri, part_index, num_parts, type="rowrec", nthread=nthread
    )
    return _GenericBatchStream(parser, FixedShapeBatcher(spec))


class _GenericBatchStream:
    """Fallback Batch stream: generic parser → FixedShapeBatcher.

    Same iterate/close surface as the fused producers, so callers can
    always close the underlying parser (parse-ahead thread + input file).
    """

    def __init__(self, parser, batcher) -> None:
        self._parser = parser
        self._batcher = batcher

    @property
    def truncated_nnz(self) -> int:
        return self._batcher.truncated_nnz

    def io_stats(self):
        """Seek/span counters from the parser's source split (indexed
        shuffled reads), or an empty dict — same hook as the fused
        producers, so a caller sees the I/O shape whichever path
        served the rows."""
        parser = getattr(self._parser, "_base", self._parser)
        source = getattr(
            parser, "source", getattr(parser, "_source", None)
        )
        fn = getattr(source, "io_stats", None)
        out = fn() if fn is not None else None
        return out if out else {}

    def __iter__(self) -> Iterator[Batch]:
        return self._batcher.batches(iter(self._parser))

    def close(self) -> None:
        self._parser.close()


def dense_batches(
    uri: str,
    spec: BatchSpec,
    part_index: int = 0,
    num_parts: int = 1,
    nthread: Optional[int] = None,
    indexing_mode: int = 0,
    ring: int = 10,
    format: str = "auto",
):
    """Best-available dense Batch stream for a libsvm or csv URI.

    ``format``: 'libsvm' | 'csv' | 'auto' (``?format=`` from the URI,
    defaulting to libsvm — same resolution as the parser factory,
    reference data.cc:68-76). Uses the fused native kernel when loaded,
    otherwise the generic parser → FixedShapeBatcher path with the same
    semantics (including ``indexing_mode``, whether passed here or as
    ``?indexing_mode=`` on the URI). Either way the result is iterable
    and has ``.close()``.
    """
    if uri.startswith("dsserve://"):
        # remote preprocessing tier — see the ell_batches route
        check(
            part_index == 0 and num_parts == 1,
            "dsserve:// sources stripe across servers (or tracker "
            "leases), not part_index/num_parts",
        )
        from ..dsserve.client import DsServeBatches

        return DsServeBatches(uri, spec, format=format)
    uspec = URISpec(uri, part_index, num_parts)
    if format == "auto":
        format = str(uspec.args.get("format", "libsvm"))
    check(format in ("libsvm", "csv"),
          f"dense_batches supports libsvm/csv, not {format!r}")
    fusable = spec.layout == "dense" and spec.value_dtype in (
        np.dtype(np.float32), np.dtype(np.float16)
    )
    fan_out = nthread is not None and nthread > 1
    csv_delim = str(uspec.args.get("delimiter", ","))
    if (format == "csv" and native.HAS_CSV_DENSE and fusable
            and len(csv_delim) == 1 and ord(csv_delim) < 128):
        # non-ASCII delimiters fall through to the generic parser (the
        # native kernel scans single bytes)
        if fan_out:
            return ShardedFusedBatches(
                lambda t, n: FusedDenseCSVBatches(
                    uri, spec, part_index * n + t, num_parts * n, ring=ring
                ),
                nthread,
            )
        return FusedDenseCSVBatches(
            uri, spec, part_index, num_parts, ring=ring
        )
    if format == "libsvm" and native.HAS_DENSE and fusable:
        if fan_out:
            return ShardedFusedBatches(
                lambda t, n: FusedDenseLibSVMBatches(
                    uri, spec, part_index * n + t, num_parts * n,
                    indexing_mode, ring,
                ),
                nthread,
            )
        return FusedDenseLibSVMBatches(
            uri, spec, part_index, num_parts, indexing_mode, ring
        )
    from ..data import create_parser
    from .batcher import FixedShapeBatcher

    if (format == "libsvm" and "indexing_mode" not in uspec.args
            and indexing_mode != 0):
        sep = "?" if "?" not in uri.split("#", 1)[0] else "&"
        head, _, frag = uri.partition("#")
        uri = f"{head}{sep}indexing_mode={indexing_mode}" + (
            f"#{frag}" if frag else ""
        )
    parser = create_parser(
        uri, part_index, num_parts, type=format, nthread=nthread
    )
    return _GenericBatchStream(parser, FixedShapeBatcher(spec))
