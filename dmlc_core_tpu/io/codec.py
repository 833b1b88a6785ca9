"""Compression codec layer for RecordIO compressed blocks.

The reference RecordIO container (include/dmlc/recordio.h:16-45) frames
raw bytes only, so every epoch re-reads every payload byte over the
remote link. This module is the repo's SINGLE compression site (lint
L009 bans zlib/gzip/zstandard/lz4 imports anywhere else): a codec
registry with level control, streaming helpers, the compressed-block
wire header (codec id, raw length, crc32 content checksum), a parallel
decode pool sized from the usable-CPU count (utils/cpus.py), and a
bytes-bounded LRU cache of decoded blocks so windowed shuffle and
multi-epoch runs decode each block once.

Codecs: ``raw`` (identity, id 0) and ``zlib``/``gzip`` (ids 1/2) ride
the stdlib and are always available; ``zstd``/``lz4`` (ids 3/4) sit
behind import guards — ``get_codec`` raises a checked Error naming the
missing package, and ``available_codecs()`` lists only what this host
can actually decode (surfaced by ``tools info`` and the
``dryrun_multichip`` report so deploy targets can be checked remotely).

Block wire format (the payload of a cflag-4 RecordIO frame,
docs/recordio.md)::

    codec_id  u8     registry id (0 raw, 1 zlib, 2 gzip, 3 zstd, 4 lz4)
    version   u8     block-header version, currently 1
    reserved  u16    zero
    n_records u32    records framed inside the decoded bytes
    raw_len   u32    decoded byte count
    crc32     u32    crc32 of the DECODED bytes (content checksum:
                     catches corrupt blocks AND codec bugs)
    <compressed bytes>

Env knobs: ``DMLC_DECODE_CACHE_MB`` (decoded-block LRU budget, default
256), ``DMLC_DECODE_THREADS`` (decode pool size, default the
affinity/cgroup-aware usable-CPU count).

Telemetry (docs/observability.md): ``io.codec.bytes_raw`` /
``io.codec.bytes_compressed`` counters (both directions — their ratio
is the compression ratio), the
``io.codec.decode_seconds`` histogram, and
``io.codec.cache_hits``/``cache_misses``.
"""

from __future__ import annotations

import hashlib
import struct
import threading
import time as _time
import zlib as _zlib
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, List, Optional, Tuple, Union

from ..telemetry import default_registry as _default_registry
from ..telemetry import tracing as _tracing
from ..utils.cpus import available_cpus
from ..utils.env import get_env
from ..utils.logging import Error, check

__all__ = [
    "BLOCK_HEADER",
    "Codec",
    "DecodeContext",
    "DecodedBlockCache",
    "available_codecs",
    "crc32",
    "decode_block",
    "decode_blocks",
    "decode_threads",
    "default_decode_cache",
    "default_decode_context",
    "default_decode_pool",
    "encode_block",
    "get_codec",
    "register_codec",
    "submit_decode",
    "wire_block_key",
]

# codec_id, version, reserved, n_records, raw_len, crc32
BLOCK_HEADER = struct.Struct("<BBHIII")
BLOCK_VERSION = 1

_REG = _default_registry()
_BYTES_RAW = _REG.counter(
    "io.codec.bytes_raw", help="uncompressed bytes through the codec layer"
)
_BYTES_COMPRESSED = _REG.counter(
    "io.codec.bytes_compressed", help="compressed bytes through the codec layer"
)
_DECODE_SECONDS = _REG.histogram(
    "io.codec.decode_seconds", help="per-block decompress wall time"
)
_CACHE_HITS = _REG.counter(
    "io.codec.cache_hits", help="decoded-block cache hits"
)
_CACHE_MISSES = _REG.counter(
    "io.codec.cache_misses", help="decoded-block cache misses"
)


def crc32(data) -> int:
    """crc32 content checksum (masked to u32 for the block header)."""
    return _zlib.crc32(data) & 0xFFFFFFFF


class Codec:
    """One compression algorithm: name, wire id, (de)compress, and
    incremental streaming helpers.

    ``compress``/``decompress`` are whole-buffer (blocks are bounded by
    the writer's ``block_bytes``, so buffering one is cheap);
    ``compress_stream``/``decompress_stream`` consume chunk iterators
    for callers converting data too large to hold (``tools
    recompress`` streams block by block on top of these semantics).
    Codec errors surface as checked ``Error``s, never raw codec
    exceptions.
    """

    name = "?"
    codec_id = -1
    default_level: Optional[int] = None

    def _compress(self, data: bytes, level: Optional[int]) -> bytes:
        raise NotImplementedError

    def _decompress(self, data: bytes, raw_len: Optional[int]) -> bytes:
        raise NotImplementedError

    def compress(self, data: bytes, level: Optional[int] = None) -> bytes:
        try:
            return self._compress(bytes(data), level)
        except Exception as e:  # codec internals differ per backend
            raise Error(f"codec {self.name!r}: compress failed: {e}") from e

    def decompress(
        self, data: bytes, raw_len: Optional[int] = None
    ) -> bytes:
        try:
            return self._decompress(bytes(data), raw_len)
        except Exception as e:
            raise Error(f"codec {self.name!r}: decompress failed: {e}") from e

    # -- streaming ------------------------------------------------------------
    def compress_stream(
        self, chunks: Iterable[bytes], level: Optional[int] = None
    ) -> Iterator[bytes]:
        """Incremental compress: yields output as input chunks arrive.
        The base implementation buffers (guarded codecs without an
        incremental API); zlib/gzip override with true streaming."""
        buf = b"".join(chunks)
        if buf:
            yield self.compress(buf, level)

    def decompress_stream(self, chunks: Iterable[bytes]) -> Iterator[bytes]:
        buf = b"".join(chunks)
        if buf:
            yield self.decompress(buf)


class RawCodec(Codec):
    """Identity codec (id 0): block framing + crc without compression —
    the cheapest way to get checksummed blocks, and the degenerate case
    every round-trip property test includes."""

    name = "raw"
    codec_id = 0

    def _compress(self, data: bytes, level: Optional[int]) -> bytes:
        return data

    def _decompress(self, data: bytes, raw_len: Optional[int]) -> bytes:
        return data

    def compress_stream(self, chunks, level=None):
        for c in chunks:
            if c:
                yield bytes(c)

    def decompress_stream(self, chunks):
        for c in chunks:
            if c:
                yield bytes(c)


class ZlibCodec(Codec):
    name = "zlib"
    codec_id = 1
    default_level = 6
    _wbits = 15  # zlib wrapper

    def _compress(self, data: bytes, level: Optional[int]) -> bytes:
        co = _zlib.compressobj(
            self.default_level if level is None else level, _zlib.DEFLATED,
            self._wbits,
        )
        return co.compress(data) + co.flush()

    def _decompress(self, data: bytes, raw_len: Optional[int]) -> bytes:
        return _zlib.decompress(data, self._wbits)

    def compress_stream(self, chunks, level=None):
        co = _zlib.compressobj(
            self.default_level if level is None else level, _zlib.DEFLATED,
            self._wbits,
        )
        for c in chunks:
            out = co.compress(bytes(c))
            if out:
                yield out
        out = co.flush()
        if out:
            yield out

    def decompress_stream(self, chunks):
        do = _zlib.decompressobj(self._wbits)
        for c in chunks:
            out = do.decompress(bytes(c))
            if out:
                yield out
        out = do.flush()
        if out:
            yield out


class GzipCodec(ZlibCodec):
    """zlib with the gzip wrapper (wbits 16+15) — same deflate stream,
    but the on-disk block payload is a valid .gz member, convenient for
    external tooling poking at extracted blobs."""

    name = "gzip"
    codec_id = 2
    _wbits = 16 + 15


class ZstdCodec(Codec):
    name = "zstd"
    codec_id = 3
    default_level = 3

    def __init__(self, mod) -> None:
        self._mod = mod

    def _compress(self, data: bytes, level: Optional[int]) -> bytes:
        level = self.default_level if level is None else level
        return self._mod.ZstdCompressor(level=level).compress(data)

    def _decompress(self, data: bytes, raw_len: Optional[int]) -> bytes:
        dctx = self._mod.ZstdDecompressor()
        if raw_len is not None:
            return dctx.decompress(data, max_output_size=raw_len)
        return dctx.decompress(data)


class Lz4Codec(Codec):
    name = "lz4"
    codec_id = 4
    default_level = 0

    def __init__(self, mod) -> None:
        self._mod = mod  # lz4.frame

    def _compress(self, data: bytes, level: Optional[int]) -> bytes:
        level = self.default_level if level is None else level
        return self._mod.compress(data, compression_level=level)

    def _decompress(self, data: bytes, raw_len: Optional[int]) -> bytes:
        return self._mod.decompress(data)


_CODECS: Dict[str, Codec] = {}
_BY_ID: Dict[int, Codec] = {}
_MISSING: Dict[str, str] = {}  # name -> reason (guarded import failed)


def register_codec(codec: Codec) -> None:
    _CODECS[codec.name] = codec
    _BY_ID[codec.codec_id] = codec


register_codec(RawCodec())
register_codec(ZlibCodec())
register_codec(GzipCodec())

try:  # optional, never a hard dependency
    import zstandard as _zstd_mod

    register_codec(ZstdCodec(_zstd_mod))
except ImportError:
    _MISSING["zstd"] = "python package 'zstandard' is not installed"

try:
    import lz4.frame as _lz4_frame

    register_codec(Lz4Codec(_lz4_frame))
except ImportError:
    _MISSING["lz4"] = "python package 'lz4' is not installed"


def available_codecs() -> List[str]:
    """Codec names this process can encode AND decode, id order."""
    return [c.name for c in sorted(_CODECS.values(), key=lambda c: c.codec_id)]


def get_codec(name: Union[str, int, Codec]) -> Codec:
    """Resolve a codec by name, wire id, or instance; checked Error for
    unknown names/ids and for guarded codecs whose package is missing
    (a compressed file must fail loudly on a host that cannot decode
    it, never produce garbage)."""
    if isinstance(name, Codec):
        return name
    if isinstance(name, int):
        codec = _BY_ID.get(name)
        if codec is None:
            known = {c.codec_id: c.name for c in _CODECS.values()}
            missing = [f"{k} ({v})" for k, v in sorted(_MISSING.items())]
            raise Error(
                f"unknown or unavailable codec id {name} (available: "
                f"{known}{'; missing: ' + ', '.join(missing) if missing else ''})"
            )
        return codec
    key = str(name).lower()
    codec = _CODECS.get(key)
    if codec is None:
        if key in _MISSING:
            raise Error(f"codec {key!r} unavailable: {_MISSING[key]}")
        raise Error(
            f"unknown codec {name!r} (available: {available_codecs()})"
        )
    return codec


# -- block encode/decode ------------------------------------------------------
def encode_block(
    raw: bytes,
    n_records: int,
    codec: Union[str, Codec],
    level: Optional[int] = None,
) -> bytes:
    """Raw framed record bytes → block blob (header + compressed)."""
    c = get_codec(codec)
    comp = c.compress(raw, level)
    _BYTES_RAW.inc(len(raw))
    _BYTES_COMPRESSED.inc(len(comp))
    return (
        BLOCK_HEADER.pack(
            c.codec_id, BLOCK_VERSION, 0, n_records, len(raw), crc32(raw)
        )
        + comp
    )


def decode_block(blob) -> Tuple[bytes, int]:
    """Block blob → (raw framed record bytes, n_records); verifies the
    declared raw length and the crc32 content checksum, raising a
    checked Error on any mismatch (corruption must never decode to
    garbage records)."""
    blob = bytes(blob)
    check(
        len(blob) >= BLOCK_HEADER.size,
        f"compressed block shorter than its {BLOCK_HEADER.size}-byte header",
    )
    codec_id, version, _res, n_records, raw_len, want_crc = (
        BLOCK_HEADER.unpack_from(blob)
    )
    check(
        version == BLOCK_VERSION,
        f"unsupported compressed-block version {version} "
        f"(this reader supports {BLOCK_VERSION})",
    )
    codec = get_codec(codec_id)
    # flight-recorder span per decode-pool job: the Perfetto timeline
    # shows each codec-decode worker's occupancy next to the window
    # loader waiting on it (the registry histogram keeps the aggregate)
    t0 = _time.perf_counter()
    with _tracing.span(
        "dmlc:decode_block", codec=codec.name, raw_len=raw_len
    ):
        raw = codec.decompress(blob[BLOCK_HEADER.size:], raw_len)
    _DECODE_SECONDS.observe(_time.perf_counter() - t0)
    check(
        len(raw) == raw_len,
        f"compressed block decoded to {len(raw)} bytes, header says "
        f"{raw_len} (truncated or corrupt block)",
    )
    got_crc = crc32(raw)
    check(
        got_crc == want_crc,
        f"compressed block crc mismatch: got {got_crc:#010x}, header says "
        f"{want_crc:#010x} (corrupt block)",
    )
    _BYTES_RAW.inc(raw_len)
    _BYTES_COMPRESSED.inc(len(blob) - BLOCK_HEADER.size)
    return raw, n_records


# -- parallel decode pool -----------------------------------------------------
def decode_threads() -> int:
    """Decode pool size: ``DMLC_DECODE_THREADS`` wins, else the
    affinity/cgroup-quota-aware usable-CPU count (utils/cpus.py) — the
    stdlib codecs release the GIL inside (de)compress, so the pool gets
    real parallelism."""
    env = get_env("DMLC_DECODE_THREADS", 0)
    if env > 0:
        return env
    return available_cpus()


_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def default_decode_pool() -> ThreadPoolExecutor:
    """Process-global decompress pool (lazy; shared by every reader so
    concurrent splits don't multiply thread counts)."""
    global _POOL
    if _POOL is None:
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = ThreadPoolExecutor(
                    max_workers=decode_threads(),
                    thread_name_prefix="codec-decode",
                )
    return _POOL


def decode_blocks(blobs: List[bytes]) -> List[Tuple[bytes, int]]:
    """Decode many block blobs, overlapping decompression on the shared
    pool when it helps; order preserved. Worker errors re-raise here."""
    if len(blobs) <= 1 or decode_threads() <= 1:
        return [decode_block(b) for b in blobs]
    return list(default_decode_pool().map(decode_block, blobs))


def submit_decode(blob) -> "Future":
    """Submit ONE block decode to the shared pool; returns its Future.

    The fetch→decode overlap seam: the concurrent span fetcher
    (io/spanfetch.py) hands each span's blocks here as the span LANDS,
    so decompression of early spans runs while later spans are still in
    flight. With a single-thread pool the decode runs inline and the
    Future comes back already resolved — same results, serial timing."""
    if decode_threads() <= 1:
        f: "Future" = Future()
        try:
            f.set_result(decode_block(blob))
        except Exception as e:  # surfaces at .result(), like a pool job
            f.set_exception(e)
        return f
    return default_decode_pool().submit(decode_block, blob)


# -- decoded-block LRU cache --------------------------------------------------
class DecodedBlockCache:
    """Bytes-bounded LRU of decoded block payloads.

    Keys are caller-chosen identities (the indexed splitter uses
    ``(file paths, total size, block file offset)``); values are the
    decoded raw framed bytes. Thread-safe — the window-shuffle
    readahead thread fills while the consumer thread reads. An entry
    larger than the whole budget is served but not retained.
    """

    def __init__(self, max_bytes: int) -> None:
        check(max_bytes >= 0, f"cache budget {max_bytes} must be >= 0")
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._items: "OrderedDict[object, bytes]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    def get(self, key) -> Optional[bytes]:
        with self._lock:
            data = self._items.get(key)
            if data is None:
                self.misses += 1
                _CACHE_MISSES.inc()
                return None
            self._items.move_to_end(key)
            self.hits += 1
            _CACHE_HITS.inc()
            return data

    def put(self, key, data: bytes) -> None:
        n = len(data)
        with self._lock:
            old = self._items.pop(key, None)
            if old is not None:
                self._bytes -= len(old)
            if n > self.max_bytes:
                return  # larger than the whole budget: serve, don't retain
            self._items[key] = data
            self._bytes += n
            while self._bytes > self.max_bytes and self._items:
                _k, evicted = self._items.popitem(last=False)
                self._bytes -= len(evicted)

    def clear(self) -> None:
        with self._lock:
            self._items.clear()
            self._bytes = 0

    @property
    def nbytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._items)


_CACHE: Optional[DecodedBlockCache] = None
_CACHE_LOCK = threading.Lock()


def default_decode_cache() -> DecodedBlockCache:
    """Process-global decoded-block cache, budget
    ``DMLC_DECODE_CACHE_MB`` (default 256) — sized at first use."""
    global _CACHE
    if _CACHE is None:
        with _CACHE_LOCK:
            if _CACHE is None:
                _CACHE = DecodedBlockCache(
                    get_env("DMLC_DECODE_CACHE_MB", 256) * (1 << 20)
                )
    return _CACHE


# -- the decode seam: two-level cache + pool behind one object ----------------
def wire_block_key(key: object) -> str:
    """Flatten a structured block identity to the content-addressed
    string the host daemon keys on. The identity must be built from
    plain strings/ints/tuples (the splitter's file-set signature +
    layout digest + block offset) — ``repr`` of those is deterministic
    ACROSS processes, which Python's seeded ``hash()`` is not, and
    cross-process agreement is the whole point of the shared tier."""
    if isinstance(key, str):
        return key
    return hashlib.sha1(repr(key).encode()).hexdigest()


class DecodeContext:
    """The single seam every block-decode consumer rides: in-process
    LRU (L1), then the host-shared daemon tier (L2, io/blockcache.py),
    then decode — plus the shared decompress pool. The window loader,
    the splitter's ``_fetch_blocks`` miss path, and ``decode_chunk``
    all go through one of these instead of reaching into module
    globals, so tests can inject
    a fake daemon or a private LRU, and the two-level policy lives in
    exactly one place.

    ``shared='auto'`` (the default) resolves the process-wide daemon
    client lazily (one connect attempt, cached negative result —
    blockcache.default_client); ``shared=None`` pins the context to
    in-process-only behavior; any client-shaped object (``get``/
    ``publish``) is used as given.
    """

    _AUTO = "auto"

    def __init__(
        self,
        cache: Optional[DecodedBlockCache] = None,
        shared: object = "auto",
    ) -> None:
        self._cache = cache
        self._shared = shared

    def cache(self) -> DecodedBlockCache:
        return self._cache if self._cache is not None else (
            default_decode_cache()
        )

    def shared(self):
        """The L2 client, or None (disabled/absent daemon)."""
        if self._shared == self._AUTO:
            from .blockcache import default_client

            return default_client()
        return self._shared

    def get_block(self, key: object) -> Optional[bytes]:
        """L1 then L2; an L2 hit is promoted into L1 so repeats inside
        one process stay memory-local."""
        data = self.cache().get(key)
        if data is not None:
            return data
        shared = self.shared()
        if shared is not None:
            try:
                data = shared.get(wire_block_key(key))
            except Exception:  # the shared tier is best-effort, always
                data = None
            if data is not None:
                self.cache().put(key, data)
        return data

    def get_blocks(self, keys) -> Dict[object, bytes]:
        """Bulk ``get_block``: L1 each key, then ONE shared-tier round
        trip for all L1 misses (client.get_many) — the batched path the
        window loader and range emission ride so per-block IPC can't
        eat the decode win. Returns only the keys found; callers decode
        the rest."""
        cache = self.cache()
        out: Dict[object, bytes] = {}
        missing = []
        for key in keys:
            data = cache.get(key)
            if data is not None:
                out[key] = data
            else:
                missing.append(key)
        if missing:
            shared = self.shared()
            if shared is not None:
                by_wire = {wire_block_key(k): k for k in missing}
                try:
                    got = shared.get_many(list(by_wire))
                except Exception:
                    got = {}
                for wire, data in got.items():
                    key = by_wire[wire]
                    cache.put(key, data)
                    out[key] = data
        return out

    def put_block(self, key: object, raw: bytes) -> None:
        """Retain decoded bytes in L1 and offer them to the host tier
        (a lost publish race or absent daemon is a silent no-op)."""
        self.cache().put(key, raw)
        shared = self.shared()
        if shared is not None:
            try:
                shared.publish(wire_block_key(key), raw)
            except Exception:
                pass

    # pool access rides the context too, so a future per-context pool
    # (or a test's serial fake) needs no caller changes
    def decode_block(self, blob) -> Tuple[bytes, int]:
        return decode_block(blob)

    def decode_blocks(self, blobs: List[bytes]) -> List[Tuple[bytes, int]]:
        return decode_blocks(blobs)

    def submit_decode(self, blob) -> "Future":
        return submit_decode(blob)


_CTX: Optional[DecodeContext] = None
_CTX_LOCK = threading.Lock()


def default_decode_context() -> DecodeContext:
    """Process-global two-level decode context (L1 = the default LRU,
    L2 = the host daemon when reachable)."""
    global _CTX
    if _CTX is None:
        with _CTX_LOCK:
            if _CTX is None:
                _CTX = DecodeContext()
    return _CTX
