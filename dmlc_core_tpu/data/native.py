"""ctypes bridge to the native C++ parse core (native/libdmlc_tpu_native.so).

The native library implements the hot loops — libsvm/csv/libfm chunk parsing
into CSR arrays — releasing the GIL so TextParserBase's thread fan-out gets
real parallelism (the reference gets this from std::thread,
src/data/text_parser.h:110-146). Every entry point has a pure-Python
fallback in the corresponding parser module; if the library is missing or
fails to load, AVAILABLE stays False and nothing breaks.

Calling convention: the caller passes the chunk buffer; the library parses
into library-owned growable buffers and returns sizes; the bridge copies
into fresh numpy arrays and frees the native buffers. One copy per ~8MB
chunk is noise next to parse cost, and fresh arrays keep ownership simple.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "AVAILABLE",
    "HAS_DENSE",
    "HAS_ELL",
    "HAS_CSV_DENSE",
    "HAS_GATHER_ELL",
    "HAS_LIBFM_ELL",
    "HAS_LIBSVM_ELL",
    "parse_libsvm",
    "parse_csv",
    "parse_libfm",
    "parse_libsvm_dense",
    "parse_csv_dense",
    "parse_rowrec_ell",
    "parse_rowrec_gather_ell",
    "parse_libfm_ell",
    "parse_libsvm_ell",
    "shuffle_mt19937",
    "source_hash",
    "walk_record_spans",
    "load",
]

AVAILABLE = False
HAS_DENSE = False      # fused libsvm->dense-batch kernel present in the .so
HAS_ELL = False        # fused recordio rowrec->ELL-batch kernel present
HAS_CSV_DENSE = False  # fused csv->dense-batch kernel present
HAS_GATHER_ELL = False  # shuffled-read (buf,starts,sizes)->ELL gather kernel
HAS_LIBFM_ELL = False  # fused libfm->ELL-batch kernel present
HAS_LIBSVM_ELL = False  # fused libsvm->ELL-batch kernel present
HAS_SHUFFLE = False    # CPython-parity MT19937 Fisher-Yates kernel present
HAS_WALK_SPANS = False  # batched point-read frame walk kernel present
_LIB = None
_LOCK = threading.Lock()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_CANDIDATES = (
    os.path.join(_REPO_ROOT, "native", "libdmlc_tpu_native.so"),
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "libdmlc_tpu_native.so"),
)


class _ParseResult(ctypes.Structure):
    """Mirrors native/fastparse.cc struct ParseResult."""

    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_elems", ctypes.c_int64),
        ("offset", ctypes.POINTER(ctypes.c_int64)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("qid", ctypes.POINTER(ctypes.c_int64)),
        ("field", ctypes.POINTER(ctypes.c_int64)),
        ("index", ctypes.POINTER(ctypes.c_uint64)),
        ("value", ctypes.POINTER(ctypes.c_float)),
        ("has_weight", ctypes.c_int32),
        ("has_qid", ctypes.c_int32),
        ("has_field", ctypes.c_int32),
        ("has_value", ctypes.c_int32),
        ("error", ctypes.c_char_p),
    ]


class _DenseResult(ctypes.Structure):
    """Mirrors native/fastparse.cc struct DenseResult."""

    _fields_ = [
        ("rows_written", ctypes.c_int64),
        ("bytes_consumed", ctypes.c_int64),
        ("truncated", ctypes.c_int64),
        ("has_cr", ctypes.c_int64),
    ]


class _EllResult(ctypes.Structure):
    """Mirrors native/fastparse.cc struct EllResult."""

    _fields_ = [
        ("rows_written", ctypes.c_int64),
        ("bytes_consumed", ctypes.c_int64),
        ("truncated", ctypes.c_int64),
        ("bad_records", ctypes.c_int64),
        ("corrupt", ctypes.c_int64),
    ]


class _CsvDenseResult(ctypes.Structure):
    """Mirrors native/fastparse.cc struct CsvDenseResult."""

    _fields_ = [
        ("rows_written", ctypes.c_int64),
        ("bytes_consumed", ctypes.c_int64),
        ("truncated", ctypes.c_int64),
        ("has_cr", ctypes.c_int64),
        ("bad_lines", ctypes.c_int64),
    ]


def load(path: Optional[str] = None, force: bool = False) -> bool:
    """Load the native library (idempotent). Returns availability.

    ``force`` re-opens the .so even if one is already loaded — used after
    an in-session rebuild (the rebuilt file is a new inode, so dlopen
    returns a fresh handle; the old one is left to the process lifetime).
    """
    global AVAILABLE, HAS_DENSE, HAS_ELL, HAS_CSV_DENSE, HAS_GATHER_ELL, \
        HAS_LIBFM_ELL, HAS_LIBSVM_ELL, HAS_SHUFFLE, HAS_WALK_SPANS, _LIB
    with _LOCK:
        if _LIB is not None and not force:
            return AVAILABLE
        if force:
            _LIB = None
            AVAILABLE = HAS_DENSE = HAS_ELL = HAS_CSV_DENSE = False
            HAS_GATHER_ELL = HAS_LIBFM_ELL = HAS_LIBSVM_ELL = False
            HAS_SHUFFLE = HAS_WALK_SPANS = False
        if os.environ.get("DMLC_TPU_NO_NATIVE", "0") == "1":
            return False
        paths = (path,) if path else _CANDIDATES
        for p in paths:
            if p is None or not os.path.exists(p):
                continue
            try:
                lib = ctypes.CDLL(p)
            except OSError:
                continue
            for fn in ("dmlc_parse_libsvm", "dmlc_parse_csv", "dmlc_parse_libfm"):
                getattr(lib, fn).restype = ctypes.POINTER(_ParseResult)
            lib.dmlc_parse_libsvm.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32]
            lib.dmlc_parse_csv.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32]
            lib.dmlc_parse_libfm.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32]
            lib.dmlc_free_result.argtypes = [ctypes.POINTER(_ParseResult)]
            lib.dmlc_free_result.restype = None
            # fused dense kernel: absent in older builds of the .so
            if hasattr(lib, "dmlc_parse_libsvm_dense"):
                lib.dmlc_parse_libsvm_dense.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                    ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_int32,
                    ctypes.POINTER(_DenseResult)]
                lib.dmlc_parse_libsvm_dense.restype = None
                HAS_DENSE = True
            # fused csv->dense kernel: absent in older builds
            if hasattr(lib, "dmlc_parse_csv_dense"):
                lib.dmlc_parse_csv_dense.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                    ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
                    ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int32, ctypes.POINTER(_CsvDenseResult)]
                lib.dmlc_parse_csv_dense.restype = None
                HAS_CSV_DENSE = True
            # fused recordio rowrec->ELL kernel: absent in older builds
            if hasattr(lib, "dmlc_parse_rowrec_ell"):
                lib.dmlc_parse_rowrec_ell.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_int64, ctypes.c_int64,
                    ctypes.POINTER(_EllResult)]
                lib.dmlc_parse_rowrec_ell.restype = None
                HAS_ELL = True
            # shuffled-read gather kernel: absent in older builds
            if hasattr(lib, "dmlc_parse_rowrec_gather_ell"):
                lib.dmlc_parse_rowrec_gather_ell.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_int64, ctypes.POINTER(_EllResult)]
                lib.dmlc_parse_rowrec_gather_ell.restype = None
                HAS_GATHER_ELL = True
            # CPython-parity shuffle kernel: absent in older builds
            if hasattr(lib, "dmlc_shuffle_mt19937"):
                lib.dmlc_shuffle_mt19937.argtypes = [
                    ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64,
                    ctypes.c_void_p]
                lib.dmlc_shuffle_mt19937.restype = None
                HAS_SHUFFLE = True
            # fused libfm->ELL kernel: absent in older builds
            if hasattr(lib, "dmlc_parse_libfm_ell"):
                lib.dmlc_parse_libfm_ell.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                    ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_int32,
                    ctypes.POINTER(_DenseResult)]
                lib.dmlc_parse_libfm_ell.restype = None
                HAS_LIBFM_ELL = True
            if hasattr(lib, "dmlc_parse_libsvm_ell"):
                lib.dmlc_parse_libsvm_ell.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                    ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int32, ctypes.POINTER(_DenseResult)]
                lib.dmlc_parse_libsvm_ell.restype = None
                HAS_LIBSVM_ELL = True
            # batched point-read frame walk: absent in older builds
            if hasattr(lib, "dmlc_walk_record_spans"):
                lib.dmlc_walk_record_spans.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.POINTER(ctypes.c_int64),
                    ctypes.POINTER(ctypes.c_int64)]
                lib.dmlc_walk_record_spans.restype = None
                HAS_WALK_SPANS = True
            if hasattr(lib, "dmlc_source_hash"):
                lib.dmlc_source_hash.restype = ctypes.c_char_p
                lib.dmlc_source_hash.argtypes = []
            _LIB = lib
            AVAILABLE = True
            return True
        return False


def source_hash() -> str:
    """sha256 of the fastparse.cc the loaded .so was built from, or ''
    (older builds). The chip benchmark compares this against the on-disk
    source so a failed rebuild can't silently benchmark a stale binary."""
    if not AVAILABLE or not hasattr(_LIB, "dmlc_source_hash"):
        return ""
    return _LIB.dmlc_source_hash().decode()


def _memmove_out(ptr, n: int, dtype) -> np.ndarray:
    """Copy n elements from a native pointer into a fresh numpy array.

    ctypes.memmove is a plain memcpy; the np.ctypeslib.as_array route used
    previously built a ctypes array *type* per call, which cost more than
    the copy itself on large chunks.
    """
    arr = np.empty(n, dtype=dtype)
    if n:
        ctypes.memmove(arr.ctypes.data, ctypes.cast(ptr, ctypes.c_void_p),
                       n * arr.itemsize)
    return arr


def _copy_out(res_ptr):
    """ParseResult → numpy arrays (copies), then free native buffers."""
    res = res_ptr.contents
    try:
        if res.error:
            from ..utils.logging import Error

            raise Error(res.error.decode())
        n, m = res.n_rows, res.n_elems
        offset = _memmove_out(res.offset, n + 1, np.int64)
        label = _memmove_out(res.label, n, np.float32)
        weight = _memmove_out(res.weight, n, np.float32) if res.has_weight else None
        qid = _memmove_out(res.qid, n, np.int64) if res.has_qid else None
        field = _memmove_out(res.field, m, np.int64) if res.has_field else None
        index = _memmove_out(res.index, m, np.uint64)
        value = _memmove_out(res.value, m, np.float32) if res.has_value else None
        return offset, label, weight, qid, field, index, value
    finally:
        _LIB.dmlc_free_result(res_ptr)


def parse_libsvm(data: bytes, indexing_mode: int):
    """→ (offset, label, weight, qid, index, value) or None if unavailable."""
    if not AVAILABLE:
        return None
    res = _LIB.dmlc_parse_libsvm(data, len(data), indexing_mode)
    offset, label, weight, qid, _field, index, value = _copy_out(res)
    return offset, label, weight, qid, index, value


def parse_csv(data: bytes, delimiter: int, label_column: int, weight_column: int):
    """→ (offset, label, weight, index, value) or None if unavailable."""
    if not AVAILABLE:
        return None
    res = _LIB.dmlc_parse_csv(data, len(data), delimiter, label_column, weight_column)
    offset, label, weight, _qid, _field, index, value = _copy_out(res)
    return offset, label, weight, index, value


def parse_libfm(data: bytes, indexing_mode: int):
    """→ (offset, label, weight, field, index, value) or None."""
    if not AVAILABLE:
        return None
    res = _LIB.dmlc_parse_libfm(data, len(data), indexing_mode)
    offset, label, weight, _qid, field, index, value = _copy_out(res)
    return offset, label, weight, field, index, value


def parse_libsvm_dense(
    chunk,
    offset: int,
    base: int,
    x: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
    cr_hint: int = -1,
) -> Optional[Tuple[int, int, int, int]]:
    """Fused libsvm parse → dense batch rows, zero-copy in and out.

    Parses ``chunk[offset:]`` (bytes/bytearray/memoryview, not sliced — the
    native side receives a pointer at the offset) into rows
    ``row_start..`` of the caller-owned buffers:

    - ``x``: C-contiguous [capacity, D] float32 or float16
    - ``labels``/``weights``: float32 [capacity]

    ``base`` is the resolved indexing base (0 or 1 — subtracted from every
    parsed feature id; callers resolve the libsvm auto mode themselves).
    ``cr_hint``: -1 on the first call for a chunk (the kernel probes for
    '\\r' once); pass the returned ``has_cr`` on resumed calls for the
    same chunk so the probe isn't repeated. Stops at buffer-full or
    chunk-end. Returns (rows_written, bytes_consumed, truncated_features,
    has_cr), or None if the kernel is missing. The rows written are fully
    initialized (zeroed before scatter), so ring buffers can be reused
    without clearing.
    """
    if not HAS_DENSE:
        return None
    from ..utils.logging import check

    mem = np.frombuffer(chunk, dtype=np.uint8)  # no copy, works on bytes
    # memory-safety preconditions: the kernel writes through raw pointers
    # assuming contiguous f32/f16 layout — never assert (stripped under -O)
    check(x.flags.c_contiguous and x.dtype in (np.float32, np.float16),
          "x must be C-contiguous float32/float16")
    check(labels.flags.c_contiguous and labels.dtype == np.float32
          and weights.flags.c_contiguous and weights.dtype == np.float32,
          "labels/weights must be C-contiguous float32")
    capacity, D = x.shape
    check(len(labels) >= capacity and len(weights) >= capacity,
          "labels/weights shorter than x capacity")
    res = _DenseResult()
    _LIB.dmlc_parse_libsvm_dense(
        ctypes.c_void_p(mem.ctypes.data + offset),
        ctypes.c_int64(mem.size - offset),
        ctypes.c_int32(base),
        ctypes.c_int64(D),
        ctypes.c_int32(1 if x.dtype == np.float16 else 0),
        ctypes.c_void_p(x.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.c_int32(cr_hint),
        ctypes.byref(res),
    )
    return res.rows_written, res.bytes_consumed, res.truncated, res.has_cr


def parse_csv_dense(
    chunk,
    offset: int,
    delimiter: int,
    label_column: int,
    weight_column: int,
    x: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
    cr_hint: int = -1,
) -> Optional[Tuple[int, int, int, int, int]]:
    """Fused csv parse → dense batch rows (same buffer contract as
    ``parse_libsvm_dense``). ``weight_column`` -1 = none. Returns
    (rows_written, bytes_consumed, truncated, has_cr, bad_lines) — a
    nonzero ``bad_lines`` means a non-empty line had no delimiter, which
    the generic CSVParser treats as a malformed file. None if missing."""
    if not HAS_CSV_DENSE:
        return None
    from ..utils.logging import check

    mem = np.frombuffer(chunk, dtype=np.uint8)
    check(x.flags.c_contiguous and x.dtype in (np.float32, np.float16),
          "x must be C-contiguous float32/float16")
    check(labels.flags.c_contiguous and labels.dtype == np.float32
          and weights.flags.c_contiguous and weights.dtype == np.float32,
          "labels/weights must be C-contiguous float32")
    capacity, D = x.shape
    check(len(labels) >= capacity and len(weights) >= capacity,
          "labels/weights shorter than x capacity")
    res = _CsvDenseResult()
    _LIB.dmlc_parse_csv_dense(
        ctypes.c_void_p(mem.ctypes.data + offset),
        ctypes.c_int64(mem.size - offset),
        ctypes.c_int32(delimiter),
        ctypes.c_int32(label_column),
        ctypes.c_int32(weight_column),
        ctypes.c_int64(D),
        ctypes.c_int32(1 if x.dtype == np.float16 else 0),
        ctypes.c_void_p(x.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.c_int32(cr_hint),
        ctypes.byref(res),
    )
    return (res.rows_written, res.bytes_consumed, res.truncated,
            res.has_cr, res.bad_lines)


def parse_rowrec_ell(
    chunk,
    offset: int,
    indices: np.ndarray,
    values: np.ndarray,
    nnz: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
) -> Optional[Tuple[int, int, int, int]]:
    """Fused RecordIO frame scan + rowrec decode → ELL batch rows.

    Parses complete RecordIO records from ``chunk[offset:]`` into rows
    ``row_start..`` of the caller-owned ELL buffers:

    - ``indices``: C-contiguous [capacity, K] int32
    - ``values``: C-contiguous [capacity, K] float32 or float16
    - ``nnz``: int32 [capacity]; ``labels``/``weights``: float32 [capacity]

    Stops at buffer-full or at a trailing partial record (the caller's next
    window must resume at ``offset + bytes_consumed``). Rows with more than
    K features keep the first K (dropped count in ``truncated``). Returns
    (rows_written, bytes_consumed, truncated, bad_records, corrupt) —
    ``corrupt`` set when a full frame header is present but carries no
    magic (broken stream, fail fast; a trailing partial is NOT corrupt) —
    or None if the kernel is missing.
    """
    if not HAS_ELL:
        return None
    mem = np.frombuffer(chunk, dtype=np.uint8)
    capacity, K = _check_ell_buffers(indices, values, nnz, labels, weights)
    res = _EllResult()
    _LIB.dmlc_parse_rowrec_ell(
        ctypes.c_void_p(mem.ctypes.data + offset),
        ctypes.c_int64(mem.size - offset),
        ctypes.c_int64(K),
        ctypes.c_int32(1 if values.dtype == np.float16 else 0),
        ctypes.c_void_p(indices.ctypes.data),
        ctypes.c_void_p(values.ctypes.data),
        ctypes.c_void_p(nnz.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.byref(res),
    )
    return (res.rows_written, res.bytes_consumed, res.truncated,
            res.bad_records, res.corrupt)


def _check_ell_buffers(indices, values, nnz, labels, weights):
    """Shared memory-safety preconditions for the ELL-output kernels."""
    from ..utils.logging import check

    check(indices.flags.c_contiguous and indices.dtype == np.int32,
          "indices must be C-contiguous int32")
    check(values.flags.c_contiguous
          and values.dtype in (np.float32, np.float16),
          "values must be C-contiguous float32/float16")
    check(nnz.flags.c_contiguous and nnz.dtype == np.int32,
          "nnz must be C-contiguous int32")
    check(labels.flags.c_contiguous and labels.dtype == np.float32
          and weights.flags.c_contiguous and weights.dtype == np.float32,
          "labels/weights must be C-contiguous float32")
    capacity, K = indices.shape
    check(values.shape == (capacity, K), "values shape != indices shape")
    check(len(nnz) >= capacity and len(labels) >= capacity
          and len(weights) >= capacity, "1-D buffers shorter than capacity")
    return capacity, K


def parse_rowrec_gather_ell(
    buf: np.ndarray,
    starts: np.ndarray,
    sizes: np.ndarray,
    lo: int,
    n_recs: int,
    indices: np.ndarray,
    values: np.ndarray,
    nnz: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
) -> Optional[Tuple[int, int, int, int, int]]:
    """Shuffled-read gather: parse framed rowrec records at
    ``(starts[lo + i], sizes[lo + i])`` byte slices of ``buf`` — the
    ``next_gather_batch`` emission of a windowed shuffle
    (io/split.py) — straight into rows ``row_start..`` of the
    caller-owned ELL buffers (contract of ``parse_rowrec_ell``). One
    call per batch, no per-record Python, no re-framing copy.

    ``buf`` is uint8 1-D; ``starts``/``sizes`` are int64, consumed from
    position ``lo`` (pointer offset — resumed calls never re-slice).
    Stops at buffer-full. Returns (rows_written, recs_consumed,
    truncated, bad_records, corrupt) — ``corrupt`` set when a slice
    holds no complete record (the index and data disagree; callers fail
    fast) — or None if the kernel is missing.
    """
    if not HAS_GATHER_ELL:
        return None
    from ..utils.logging import check

    capacity, K = _check_ell_buffers(indices, values, nnz, labels, weights)
    check(buf.flags.c_contiguous and buf.dtype == np.uint8,
          "gather buf must be C-contiguous uint8")
    check(starts.flags.c_contiguous and starts.dtype == np.int64
          and sizes.flags.c_contiguous and sizes.dtype == np.int64,
          "starts/sizes must be C-contiguous int64")
    check(0 <= lo and lo + n_recs <= len(starts)
          and len(sizes) >= len(starts),
          "gather range outside starts/sizes")
    res = _EllResult()
    _LIB.dmlc_parse_rowrec_gather_ell(
        ctypes.c_void_p(buf.ctypes.data),
        ctypes.c_void_p(starts.ctypes.data + lo * 8),
        ctypes.c_void_p(sizes.ctypes.data + lo * 8),
        ctypes.c_int64(n_recs),
        ctypes.c_int64(K),
        ctypes.c_int32(1 if values.dtype == np.float16 else 0),
        ctypes.c_void_p(indices.ctypes.data),
        ctypes.c_void_p(values.ctypes.data),
        ctypes.c_void_p(nnz.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.byref(res),
    )
    return (res.rows_written, res.bytes_consumed, res.truncated,
            res.bad_records, res.corrupt)


def shuffle_mt19937(rnd, perm: np.ndarray) -> bool:
    """Fisher-Yates shuffle ``perm`` (int64, C-contiguous) in place,
    BIT-IDENTICAL to ``rnd.shuffle(perm)`` for a CPython
    ``random.Random`` — same Mersenne-Twister draws, same rejection
    sampling, same swaps — at native speed (the shuffled-read
    permutation is pinned to random.Random order, docs/shuffle.md).

    Returns False (caller falls back to ``rnd.shuffle``) when the
    kernel is missing or ``len(perm) >= 2**31`` (getrandbits there
    consumes multiple words per call, which the kernel does not
    mirror). ``rnd`` is left untouched — callers derive a fresh
    (seed, epoch) Random per epoch, so its post-shuffle state is never
    observed.
    """
    if not HAS_SHUFFLE or len(perm) >= (1 << 31):
        return False
    from ..utils.logging import check

    check(perm.flags.c_contiguous and perm.dtype == np.int64,
          "shuffle perm must be C-contiguous int64")
    state = rnd.getstate()
    check(
        state[0] == 3 and len(state[1]) == 625,
        "unsupported random.Random state version",
    )
    key = np.asarray(state[1][:624], dtype=np.uint32)
    _LIB.dmlc_shuffle_mt19937(
        ctypes.c_void_p(key.ctypes.data),
        ctypes.c_int32(state[1][624]),
        ctypes.c_int64(len(perm)),
        ctypes.c_void_p(perm.ctypes.data),
    )
    return True


def walk_record_spans(
    buf: np.ndarray, starts: np.ndarray, sizes: np.ndarray
) -> Optional[Tuple[np.ndarray, np.ndarray, int, int]]:
    """Batched point-read frame walk (io/lookup.py): each
    ``(starts[i], sizes[i])`` byte slice of ``buf`` must begin at a
    RecordIO frame head; returns ``(payload_offs, payload_lens,
    n_multipart, n_corrupt)`` with ``payload_offs[i]`` the record's
    payload offset into ``buf`` for single-frame records, ``-2`` for a
    multi-part chain (the caller reassembles those few in Python — the
    payload is not a contiguous slice), ``-1`` for a slice that holds
    no valid head (index/data mismatch; callers fail fast). One native
    call per block in place of a per-record Python walk. None if the
    kernel is missing."""
    if not HAS_WALK_SPANS:
        return None
    from ..utils.logging import check

    check(buf.flags.c_contiguous and buf.dtype == np.uint8,
          "walk buf must be C-contiguous uint8")
    check(starts.flags.c_contiguous and starts.dtype == np.int64
          and sizes.flags.c_contiguous and sizes.dtype == np.int64
          and len(sizes) == len(starts),
          "starts/sizes must be matching C-contiguous int64")
    n = len(starts)
    out_off = np.empty(n, dtype=np.int64)
    out_len = np.empty(n, dtype=np.int64)
    nm = ctypes.c_int64()
    nc = ctypes.c_int64()
    _LIB.dmlc_walk_record_spans(
        ctypes.c_void_p(buf.ctypes.data),
        ctypes.c_void_p(starts.ctypes.data),
        ctypes.c_void_p(sizes.ctypes.data),
        ctypes.c_int64(n),
        ctypes.c_void_p(out_off.ctypes.data),
        ctypes.c_void_p(out_len.ctypes.data),
        ctypes.byref(nm),
        ctypes.byref(nc),
    )
    return out_off, out_len, int(nm.value), int(nc.value)


def parse_libfm_ell(
    chunk,
    offset: int,
    base: int,
    indices: np.ndarray,
    values: np.ndarray,
    nnz: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
    cr_hint: int = -1,
    fields: Optional[np.ndarray] = None,
) -> Optional[Tuple[int, int, int, int]]:
    """Fused libfm text parse → ELL batch rows (buffer contract of
    ``parse_rowrec_ell``, resumable-chunk contract of
    ``parse_libsvm_dense``). ``base`` is the resolved indexing base —
    callers resolve libfm auto mode against the file head. ``fields``
    (int32 [capacity, K], or None to drop them) receives each kept
    feature's field id at the feature's position. Returns
    (rows_written, bytes_consumed, truncated, has_cr), or None if the
    kernel is missing."""
    if not HAS_LIBFM_ELL:
        return None
    from ..utils.logging import check

    mem = np.frombuffer(chunk, dtype=np.uint8)
    capacity, K = _check_ell_buffers(indices, values, nnz, labels, weights)
    if fields is not None:
        check(fields.flags.c_contiguous and fields.dtype == np.int32
              and fields.shape == indices.shape,
              "fields must be C-contiguous int32 shaped as indices")
    res = _DenseResult()
    _LIB.dmlc_parse_libfm_ell(
        ctypes.c_void_p(mem.ctypes.data + offset),
        ctypes.c_int64(mem.size - offset),
        ctypes.c_int32(base),
        ctypes.c_int64(K),
        ctypes.c_int32(1 if values.dtype == np.float16 else 0),
        ctypes.c_void_p(indices.ctypes.data),
        ctypes.c_void_p(values.ctypes.data),
        ctypes.c_void_p(nnz.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_void_p(None if fields is None else fields.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.c_int32(cr_hint),
        ctypes.byref(res),
    )
    return res.rows_written, res.bytes_consumed, res.truncated, res.has_cr


def parse_libsvm_ell(
    chunk,
    offset: int,
    base: int,
    indices: np.ndarray,
    values: np.ndarray,
    nnz: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    row_start: int,
    cr_hint: int = -1,
) -> Optional[Tuple[int, int, int, int]]:
    """Fused libsvm text parse → ELL batch rows (buffer contract of
    ``parse_rowrec_ell``, resumable-chunk contract of
    ``parse_libsvm_dense``). ``base`` is the resolved indexing base —
    callers resolve libsvm auto mode against the file head. Returns
    (rows_written, bytes_consumed, truncated, has_cr), or None if the
    kernel is missing."""
    if not HAS_LIBSVM_ELL:
        return None
    mem = np.frombuffer(chunk, dtype=np.uint8)
    capacity, K = _check_ell_buffers(indices, values, nnz, labels, weights)
    res = _DenseResult()
    _LIB.dmlc_parse_libsvm_ell(
        ctypes.c_void_p(mem.ctypes.data + offset),
        ctypes.c_int64(mem.size - offset),
        ctypes.c_int32(base),
        ctypes.c_int64(K),
        ctypes.c_int32(1 if values.dtype == np.float16 else 0),
        ctypes.c_void_p(indices.ctypes.data),
        ctypes.c_void_p(values.ctypes.data),
        ctypes.c_void_p(nnz.ctypes.data),
        ctypes.c_void_p(labels.ctypes.data),
        ctypes.c_void_p(weights.ctypes.data),
        ctypes.c_int64(row_start),
        ctypes.c_int64(capacity),
        ctypes.c_int32(cr_hint),
        ctypes.byref(res),
    )
    return res.rows_written, res.bytes_consumed, res.truncated, res.has_cr


load()
