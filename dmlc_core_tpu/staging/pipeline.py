"""Double-buffered staging of host batches into TPU HBM.

The TPU-native replacement for the reference's terminal consumer (SURVEY §7
step 5, hard part 2): where dmlc-core hands RowBlocks to a CPU learner, this
hands jax Arrays in HBM to a jitted step, overlapping three stages:

  parse threads → host Batch queue (ThreadedIter, depth ``prefetch``)
                → transfer thread packing each batch into a dispatch-ring
                  slot and issuing the device transfer on a small worker
                  pool (a backend's device_put may block during dispatch,
                  and serial dispatch on one thread would then cap
                  throughput at one transfer at a time; the ring keeps
                  ``depth`` dispatches in flight)
                → device queue (``depth`` staged batches in flight)
                → consumer (training step)

Transfer shapes (docs/staging.md):

- single device + ``Batch.packed``: the whole batch rides ONE u8 DMA and
  is bitcast-unpacked in HBM (``_unpacker``).
- mesh + ``Batch.packed``: the batch is repacked shard-major into the
  ring slot and rides ``len(addressable devices)`` u8 DMAs — one
  row-contiguous segment per device — assembled with
  ``jax.make_array_from_single_device_arrays`` and unpacked by a
  layout-per-shard jitted bitcast, instead of ``n_arrays × n_devices``
  small transfers.
- anything else: per-array ``device_put`` fallback.

Sharded mode: given a Mesh and a PartitionSpec, each batch lands as a
global array sharded over the mesh's data axis. In multi-process runs each
process stages only its local rows (`jax.make_array_from_process_local_data`)
— the (part_index, num_parts) InputSplit axis maps onto
jax.process_index()/process_count() so collectives ride ICI, never the host
network (SURVEY §5.8). The packed-shard path is single-process only (the
local-rows→global-position mapping is owned by
make_array_from_process_local_data there).
"""

from __future__ import annotations

import logging
import os
import threading
from collections import OrderedDict
from concurrent.futures import CancelledError, Future, ThreadPoolExecutor
from typing import Any, Dict, Iterable, Iterator, Optional

import numpy as np

from ..concurrency.threaded_iter import ThreadedIter
from ..telemetry import default_registry as _default_registry
from ..utils.profiler import annotate
from ..utils.timer import get_time
from .batcher import Batch, packed_shard_layout

__all__ = [
    "StagingPipeline",
    "StagingStats",
    "device_put",
    "drain_close",
    "packed_layout",
    "stage_batch",
    "unpack_cache_stats",
]

logger = logging.getLogger("dmlc_core_tpu.staging")

_PAGE = 4096  # dispatch-ring slot buffers are page-aligned (DMA-friendly)

# telemetry series (docs/observability.md). The per-pipeline
# ``stage_seconds`` sums stay for r1-r5 bench comparability; the
# registry carries the same stage timings as log-bucketed duration
# HISTOGRAMS (one series per stage label), so the tail — a stalled
# host_pull, one 2-second dispatch — is visible, not averaged away.
_REG = _default_registry()
_ROWS_STAGED = _REG.counter("staging.rows", help="rows staged to device")
_BYTES_STAGED = _REG.counter("staging.bytes", help="bytes staged to device")
_DEVICE_PUTS = _REG.counter("staging.device_puts", help="device transfers")
_SLOTS_ADOPTED = _REG.counter(
    "staging.adopted_slots",
    help="packed slots device_put straight from the producer's buffer "
    "(dispatch_pack copy skipped)",
)
_SLOT_COPIES = _REG.counter(
    "dsserve.slot_copies",
    help="received dsserve slots that took the dispatch_pack memcpy "
    "anyway (0 on the zero-copy adopt path)",
)
_UNPACK_EVICT = _REG.counter(
    "staging.unpack_evictions", help="jitted-unpacker LRU evictions"
)


# resolved once: tick_batch runs per staged batch on the transfer
# thread — it must not pay a registry get-or-create (lock + label-key
# build) per batch
_BATCH_COUNTERS = {
    kind: _REG.counter(
        "staging.batches",
        help="staged batches by transfer path",
        labels={"path": kind},
    )
    for kind in ("packed", "packed_shard", "per_array")
}


def _stage_hist(stage: str):
    return _REG.histogram(
        "staging.stage_seconds",
        help="per-stage staging durations (secs)",
        labels={"stage": stage},
    )


def _require_jax():
    import jax  # deferred so the data layer stays importable without jax

    return jax


def device_put(tree, target=None):
    """The repo's sanctioned ``jax.device_put`` call site.

    Lint rule L007 (tools/lint.py) bans direct ``jax.device_put`` outside
    ``dmlc_core_tpu/staging/`` so nothing can bypass the coalesced
    transfer layer by accident; code with a legitimate non-batch transfer
    (parameter placement, spmd.py) routes through this wrapper instead —
    the exception is then greppable at its single definition.
    """
    jax = _require_jax()
    return jax.device_put(tree, target)


def _safe_host(v: np.ndarray, platform: str) -> np.ndarray:
    """Defend against CPU-backend zero-copy aliasing of host buffers.

    jax's CPU client may adopt a suitably-aligned numpy buffer zero-copy
    in device_put; producers that recycle a ring of host buffers
    (staging/fused.py) would then mutate the "device" array in place. On
    CPU backends we copy first (alignment — and therefore aliasing — is
    allocation-dependent, so this must be unconditional). Real accelerator
    backends copy to device memory during the transfer; no copy needed.
    """
    if platform == "cpu":
        return np.array(v, copy=True)
    return v


# -- jitted unpacker cache (LRU) ---------------------------------------------
# keyed by full layout tuples, so varying batch shapes mint new entries;
# unbounded growth under shape churn was real (ISSUE 3 satellite) — the
# cache is an LRU sized by DMLC_UNPACK_CACHE (default 64) with a
# process-global eviction counter surfaced through io_stats().

_UNPACKERS: "OrderedDict[Any, Any]" = OrderedDict()
_UNPACK_EVICTIONS = 0
_UNPACK_LOCK = threading.Lock()


def _unpack_cache_capacity() -> int:
    return max(1, int(os.environ.get("DMLC_UNPACK_CACHE", "64")))


def _cached_unpacker(key, make):
    global _UNPACK_EVICTIONS
    with _UNPACK_LOCK:
        fn = _UNPACKERS.get(key)
        if fn is not None:
            _UNPACKERS.move_to_end(key)
            return fn
    fn = make()  # jit tracing outside the lock; duplicate makes are benign
    with _UNPACK_LOCK:
        _UNPACKERS[key] = fn
        _UNPACKERS.move_to_end(key)
        cap = _unpack_cache_capacity()
        while len(_UNPACKERS) > cap:
            _UNPACKERS.popitem(last=False)
            _UNPACK_EVICTIONS += 1
            _UNPACK_EVICT.inc()
    return fn


def unpack_cache_stats() -> Dict[str, int]:
    """Process-global jitted-unpacker cache shape (size/capacity/evictions)."""
    with _UNPACK_LOCK:
        return {
            "unpack_cache_size": len(_UNPACKERS),
            "unpack_cache_capacity": _unpack_cache_capacity(),
            "unpack_cache_evictions": _UNPACK_EVICTIONS,
        }


def _packed_layout(batch: Batch):
    """(name, offset, nbytes, shape, dtype) per array, derived from the
    views' addresses inside ``batch.packed`` — or None if any array is
    not a dense C-contiguous view into it (then the per-array path must
    be used).

    The C-contiguity check matters: ``byte_bounds`` is happy with a
    reversed (negative-stride) or otherwise strided view whose BOUNDS lie
    inside the packed buffer but whose bytes are not the dense run
    ``[off, off+nbytes)`` — bitcasting that run would stage garbage in
    the right shape. Reject; the per-array path handles any layout.
    """
    from numpy.lib.array_utils import byte_bounds

    packed = batch.packed
    if packed is None or not packed.flags.c_contiguous:
        return None
    base, end = byte_bounds(packed)
    layout = []
    for k, v in batch.as_dict().items():
        if not v.flags.c_contiguous:
            return None
        lo, hi = byte_bounds(v)
        if lo < base or hi > end:
            return None
        layout.append((k, lo - base, v.nbytes, v.shape, str(v.dtype)))
    return tuple(layout)


def packed_layout(batch: Batch):
    """Public name for :func:`_packed_layout`: the exact
    (name, offset, nbytes, shape, dtype) byte layout of a packed batch,
    or None when the batch cannot ride a single-buffer path. The dsserve
    wire (dmlc_core_tpu/dsserve/wire.py) ships this descriptor next to
    the packed bytes so a remote consumer rebuilds bit-identical views."""
    return _packed_layout(batch)


def _unpacker(layout, platform: str):
    """Jitted u8[n] → dict-of-arrays bitcast unpack (runs in HBM; slicing
    and bitcasting on device are bandwidth-trivial next to the transfer
    they replace).

    The u8 input is NOT donated: XLA donates buffer-to-buffer, and no
    single unpack output can alias the whole packed buffer (the outputs
    are several smaller arrays), so donation can never be honored — it
    only emits per-layout warnings. The packed buffer's lifetime ends
    when the unpack completes; XLA frees it then.
    """

    def make():
        jax = _require_jax()
        import jax.numpy as jnp
        from jax import lax

        def unpack(u8):
            out = {}
            for name, off, nb, shape, dtype in layout:
                item = np.dtype(dtype).itemsize
                seg = u8[off : off + nb].reshape(-1, item)
                out[name] = lax.bitcast_convert_type(
                    seg, jnp.dtype(dtype)
                ).reshape(shape)
            return out

        return jax.jit(unpack)

    return _cached_unpacker((layout, platform), make)


def _shard_unpacker(shard_entries, stride, mesh, data_axis, platform):
    """Layout-per-shard variant of ``_unpacker``: global u8
    [n_shards*stride] sharded over ``data_axis`` → dict of leading-dim
    sharded arrays.

    Built on ``shard_map`` so every slice/bitcast/reshape is explicitly
    SHARD-LOCAL — zero collectives by construction. (A plain jit with
    pinned in/out shardings is not enough: GSPMD could not prove the
    ``(n_shards*stride,) → (n_shards, stride)`` reshape local and
    inserted an all-gather; two ring workers then executing unpacks
    concurrently deadlocked in the collective rendezvous on the CPU
    backend — and any collective here would also contend with the
    consumer's training step on real meshes.) Output shardings are
    ``P(data_axis, None, …)``, bit-compatible with the per-array
    ``NamedSharding`` path.
    """
    n_shards = mesh.shape[data_axis]

    def make():
        jax = _require_jax()
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec

        out_specs = {
            name: PartitionSpec(data_axis, *([None] * (len(shape) - 1)))
            for name, _off, _nb, shape, _dtype in shard_entries
        }

        def unpack_local(u8):  # u8: (stride,) — ONE shard's bytes
            out = {}
            for name, off, nb, shape, dtype in shard_entries:
                item = np.dtype(dtype).itemsize
                seg = u8[off : off + nb].reshape(nb // item, item)
                local = (shape[0] // n_shards,) + tuple(shape[1:])
                out[name] = lax.bitcast_convert_type(
                    seg, jnp.dtype(dtype)
                ).reshape(local)
            return out

        # jit-level out_shardings pin the EXACT specs (shard_map alone
        # normalizes away trailing Nones — P('data',) vs
        # P('data', None) — breaking strict sharding equality with the
        # per-array path; the placements are identical, so this is
        # metadata, not a reshard)
        return jax.jit(
            jax.shard_map(
                unpack_local,
                mesh=mesh,
                in_specs=PartitionSpec(data_axis),
                out_specs=out_specs,
            ),
            out_shardings={
                name: NamedSharding(mesh, spec)
                for name, spec in out_specs.items()
            },
        )

    key = (shard_entries, stride, mesh, data_axis, platform)
    return _cached_unpacker(key, make)


# -- staging counters ---------------------------------------------------------


class StagingStats:
    """Thread-safe transfer-shape counters (ticked from ring workers).

    ``packed_shard_dma`` latches True the first time a batch rides the
    packed-shard mesh path — the observable proof the coalesced sharded
    transfer is engaged (dryrun_multichip reports it).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.device_puts = 0
        self.puts_per_device: Dict[str, int] = {}
        self.packed_batches = 0
        self.packed_shard_batches = 0
        self.per_array_batches = 0
        self.packed_shard_dma = False

    def tick_puts(self, devices) -> None:
        n = 0
        with self._lock:
            for d in devices:
                n += 1
                self.device_puts += 1
                key = str(d)
                self.puts_per_device[key] = (
                    self.puts_per_device.get(key, 0) + 1
                )
        _DEVICE_PUTS.inc(n)

    def tick_raw_puts(self, n: int) -> None:
        """Count ``n`` transfers not attributed to a specific device
        (per-array fallback paths)."""
        with self._lock:
            self.device_puts += n
        _DEVICE_PUTS.inc(n)

    def tick_batch(self, kind: str) -> None:
        with self._lock:
            if kind == "packed":
                self.packed_batches += 1
            elif kind == "packed_shard":
                self.packed_shard_batches += 1
                self.packed_shard_dma = True
            else:
                kind = "per_array"
                self.per_array_batches += 1
        _BATCH_COUNTERS[kind].inc()

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "device_puts": self.device_puts,
                "puts_per_device": dict(self.puts_per_device),
                "packed_batches": self.packed_batches,
                "packed_shard_batches": self.packed_shard_batches,
                "per_array_batches": self.per_array_batches,
                "packed_shard_dma": self.packed_shard_dma,
                **unpack_cache_stats(),
            }


# -- pack / put primitives ----------------------------------------------------
# Split so the pipeline's transfer thread can PACK (host memcpy into a
# stable ring-slot buffer) separately from PUT (the possibly-blocking
# device dispatch, run on ring workers); stage_batch() composes them
# synchronously for one-shot callers.


class _SlotBuf:
    """One dispatch-ring slot: a reusable page-aligned host staging
    buffer plus the future of the dispatch currently reading it. On CPU
    backends the buffer is NOT reused (``get`` hands out fresh memory):
    the CPU client may adopt the source zero-copy for the device array's
    whole lifetime, so a recycled slot would alias live device data —
    the same hazard ``_safe_host`` guards against."""

    def __init__(self) -> None:
        self._raw: Optional[np.ndarray] = None
        self.pending: Optional[Future] = None

    def get(self, nbytes: int, platform: str) -> np.ndarray:
        if platform == "cpu":
            return np.zeros(nbytes, dtype=np.uint8)
        if self._raw is None or self._raw.nbytes < nbytes + _PAGE:
            self._raw = np.zeros(nbytes + _PAGE, dtype=np.uint8)
        off = (-self._raw.ctypes.data) % _PAGE
        return self._raw[off : off + nbytes]


def _shard_plan(batch: Batch, mesh, data_axis: str):
    """(shard_entries, stride, n_shards) when the packed-shard path
    applies, else None (per-array fallback).

    Applies when: single process (multi-process local→global placement
    is owned by make_array_from_process_local_data), ``Batch.packed``
    present (the producer staged into one buffer), the data axis exists,
    every array is C-contiguous, and every leading dim divides by the
    shard count (the batcher emits fixed batch_size rows, so this is a
    once-per-config property, not per-batch luck).
    """
    if batch.packed is None:
        return None
    jax = _require_jax()
    if jax.process_count() > 1:
        return None
    n_shards = dict(mesh.shape).get(data_axis)
    if not n_shards:
        return None
    arrays = batch.as_dict()
    if any(not v.flags.c_contiguous for v in arrays.values()):
        return None
    plan = packed_shard_layout(
        [(k, v.shape, str(v.dtype)) for k, v in arrays.items()], n_shards
    )
    if plan is None:
        return None
    shard_entries, stride = plan
    return shard_entries, stride, n_shards


def _adopt_enabled() -> bool:
    """``DMLC_STAGING_ADOPT`` gate (default on): off forces the
    dispatch_pack copy even for adopt-capable producers — the A/B lever
    for the zero-copy receive benches."""
    return os.environ.get("DMLC_STAGING_ADOPT", "on").strip().lower() not in (
        "off", "0", "false", "no",
    )


def adoptable_slot(batch: Batch) -> bool:
    """True when this batch's packed buffer can be ``device_put``
    directly, skipping the dispatch_pack memcpy. Callers first check
    the producer declared ``adopt_slots`` — the promise that a
    delivered buffer is stable until every view over it dies (dsserve's
    pooled recv banks and shm segments are liveness-tracked by
    finalizers). Per batch all that remains is shape: page-aligned and
    contiguous, so the accelerator path sees the same DMA-friendly
    source a ring slot would give it. The CPU client zero-copy ALIASES
    the buffer for the device array's lifetime but also holds a
    reference to it, which composes with liveness-tracked sources —
    an adopted bank cannot be recycled (hence rewritten) while the
    device array lives, unlike the untracked ``_SlotBuf`` ring that
    must hand CPU fresh memory."""
    packed = batch.packed
    return (
        packed is not None
        and packed.flags.c_contiguous
        and packed.ctypes.data % _PAGE == 0
    )


def _pack_single(batch: Batch, platform: str, slot: Optional[_SlotBuf]):
    """Copy ``batch.packed`` once into a stable aligned source; the
    producer's ring slot is recyclable the moment this returns."""
    if slot is None:
        return _safe_host(batch.packed, platform)
    buf = slot.get(batch.packed.nbytes, platform)
    np.copyto(buf, batch.packed)
    return buf


def _pack_shards(
    batch: Batch, shard_entries, stride: int, n_shards: int,
    platform: str, slot: Optional[_SlotBuf],
) -> np.ndarray:
    """Repack the section-major host batch shard-major: out[d] is the
    contiguous byte block device d will receive — every array's rows for
    shard d at PACK_ALIGN-aligned offsets (``packed_shard_layout``).
    One vectorized copy per array; this is the single host-side copy the
    dispatch ring mandates anyway for source stability."""
    if slot is None:
        out = np.zeros((n_shards, stride), dtype=np.uint8)
    else:
        out = slot.get(n_shards * stride, platform).reshape(n_shards, stride)
    arrays = batch.as_dict()
    for name, off, nb, _shape, _dtype in shard_entries:
        src = arrays[name].view(np.uint8).reshape(n_shards, nb)
        out[:, off : off + nb] = src
    return out


def _put_packed(src, layout, device, stats: Optional[StagingStats]):
    """One u8 DMA + on-device bitcast unpack (single-device path)."""
    jax = _require_jax()
    u8 = jax.device_put(src, device)
    if stats is not None:
        stats.tick_puts([device])
        stats.tick_batch("packed")
    return _unpacker(layout, device.platform)(u8)


def _put_packed_shards(
    src: np.ndarray, shard_entries, stride: int, mesh, data_axis: str,
    stats: Optional[StagingStats],
):
    """One u8 DMA per addressable device (its row-contiguous shard-major
    segment), assembled into a global sharded u8 array and bitcast-unpacked
    per shard. Devices replicated along non-data axes receive the same
    segment — the put count is len(addressable devices), never
    n_arrays × n_devices."""
    jax = _require_jax()
    from jax.sharding import NamedSharding, PartitionSpec

    n_shards = mesh.shape[data_axis]
    platform = mesh.devices.flat[0].platform
    sharding = NamedSharding(mesh, PartitionSpec(data_axis))
    gshape = (n_shards * stride,)
    idx_map = sharding.addressable_devices_indices_map(gshape)
    devs = list(idx_map)
    arrs = []
    for dev, idx in idx_map.items():
        start = idx[0].start or 0
        arrs.append(jax.device_put(src[int(start) // stride], dev))
    garr = jax.make_array_from_single_device_arrays(gshape, sharding, arrs)
    if stats is not None:
        stats.tick_puts(devs)
        stats.tick_batch("packed_shard")
    return _shard_unpacker(shard_entries, stride, mesh, data_axis, platform)(
        garr
    )


def _stage_per_array_mesh(
    batch: Batch, mesh, data_axis: str, stats: Optional[StagingStats]
):
    """Fallback mesh path: one NamedSharding device_put per array (or the
    multi-process local-rows assembly)."""
    jax = _require_jax()
    from jax.sharding import NamedSharding, PartitionSpec

    platform = mesh.devices.flat[0].platform
    n_local = len(
        [d for d in mesh.devices.flat
         if d.process_index == jax.process_index()]
    ) or int(mesh.devices.size)
    out = {}
    arrays = batch.as_dict()
    for k, v in arrays.items():
        v = _safe_host(v, platform)
        spec = PartitionSpec(data_axis, *([None] * (v.ndim - 1)))
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() > 1:
            out[k] = jax.make_array_from_process_local_data(sharding, v)
        else:
            out[k] = jax.device_put(v, sharding)
    if stats is not None:
        # per-array sharded staging lands one transfer per array on each
        # addressable device — the n_arrays × n_devices shape the
        # packed-shard path exists to collapse
        stats.tick_raw_puts(len(arrays) * n_local)
        stats.tick_batch("per_array")
    return out


def stage_batch(
    batch: Batch,
    device=None,
    mesh=None,
    data_axis: str = "data",
    stats: Optional[StagingStats] = None,
) -> Dict[str, Any]:
    """One host Batch → dict of jax Arrays (async transfer).

    - default: committed to ``device`` (or the first local device). When
      the producer packed its arrays into one contiguous buffer
      (Batch.packed), the whole batch rides a single DMA and is
      bitcast-unpacked on device — small-transfer overhead dominates the
      host↔device link otherwise.
    - with a mesh: every array is sharded on its leading (batch) dim over
      ``data_axis`` and replicated on the rest. Packed single-process
      batches ride the packed-shard path (one DMA per addressable
      device); otherwise one transfer per array, and in multi-process
      runs each process contributes its local rows of the global batch.
    """
    jax = _require_jax()
    if mesh is not None:
        plan = _shard_plan(batch, mesh, data_axis)
        if plan is not None:
            shard_entries, stride, n_shards = plan
            platform = mesh.devices.flat[0].platform
            src = _pack_shards(
                batch, shard_entries, stride, n_shards, platform, None
            )
            return _put_packed_shards(
                src, shard_entries, stride, mesh, data_axis, stats
            )
        return _stage_per_array_mesh(batch, mesh, data_axis, stats)
    if batch.packed is not None:
        layout = _packed_layout(batch)
        if layout is not None:
            if device is None:
                device = jax.local_devices()[0]
            src = _pack_single(batch, device.platform, None)
            return _put_packed(src, layout, device, stats)
    if device is None:
        device = jax.local_devices()[0]
    out = {
        k: jax.device_put(_safe_host(v, device.platform), device)
        for k, v in batch.as_dict().items()
    }
    if stats is not None:
        stats.tick_raw_puts(len(out))
        stats.tick_batch("per_array")
    return out


class _Ready:
    """Future-shaped wrapper for a synchronously staged batch."""

    __slots__ = ("_v",)

    def __init__(self, v) -> None:
        self._v = v

    def result(self):
        return self._v


class StagingPipeline:
    """Iterator of device-resident batch dicts with double buffering.

    ``host_batches`` is any iterable of Batch (e.g.
    ``FixedShapeBatcher.batches(parser)``); it is pulled on a background
    thread. ``depth`` device transfers are kept in flight, so parse, DMA
    and compute overlap (the reference's read-ahead depth 2,
    threaded_input_split.h:33, applied at the host→HBM boundary).

    Packed batches ride the dispatch ring: the transfer thread copies
    ``Batch.packed`` into a reusable page-aligned slot buffer
    (``dispatch_pack``) and hands the possibly-blocking ``device_put``
    to one of ``depth`` ring workers (``dispatch_put`` is the hand-off;
    the blocking dispatch itself overlaps ``depth``-wide and its
    completion is observed by the consumer's ``transfer_wait``). A slot
    is rewritten only after its previous dispatch finished
    (``dispatch_slot_wait``).
    """

    def __init__(
        self,
        host_batches: Iterable[Batch],
        device=None,
        mesh=None,
        data_axis: str = "data",
        depth: int = 2,
        prefetch: int = 2,
    ) -> None:
        self._jax = _require_jax()
        self._source = host_batches
        self._device = device
        self._mesh = mesh
        self._data_axis = data_axis
        self._depth = max(1, depth)
        # ring-buffer producers (staging/fused.py) recycle host buffers; a
        # ring shallower than everything this pipeline keeps in flight
        # (prefetch queue + the batch on the transfer thread + device
        # transfers + the batch handed to the consumer) would silently
        # corrupt staged batches — reject it here. (Packed batches are
        # copied into a dispatch-ring slot at pack time and release their
        # producer slot early, but the bound must hold for the per-array
        # fallback too, so the conservative accounting stays.)
        ring_slots = getattr(host_batches, "ring_slots", None)
        if ring_slots is not None:
            # worst-case live batches under full backpressure: the
            # producer thread holding one blocked in its queue put +
            # `prefetch` queued + the transfer thread's batch (transfer
            # dispatched, blocked handing it downstream) + `depth` in
            # the device queue with DMAs possibly incomplete + the one
            # the consumer is blocking on
            need = prefetch + self._depth + 3
            from ..utils.logging import check

            check(
                ring_slots >= need,
                f"producer ring has {ring_slots} slots but the pipeline "
                f"keeps up to {need} batches alive "
                f"(1 in producer + prefetch={prefetch} + 1 staging + "
                f"depth={self._depth} + 1 consumed)",
            )
        self.rows_staged = 0
        self.batches_staged = 0
        self.bytes_staged = 0
        # zero-copy slot adoption: only when the producer PROMISES its
        # packed buffers stay stable until every view dies (dsserve's
        # pooled/shm recv banks — see DsServeBatches.adopt_slots); ring
        # producers recycle eagerly and must keep taking the pack copy
        self._adopt = bool(
            getattr(host_batches, "adopt_slots", False)
        ) and _adopt_enabled()
        self.slots_adopted = 0
        # sticky flag set by close() when a bounded teardown join timed
        # out: an orphaned producer thread may still be reading the host
        # batch source, so callers must defer tearing down mmap-backed
        # producers (fused rings, _MmapRawChunks) while this is set
        self.close_timed_out = False
        # per-stage wall-clock accumulators (seconds). host_pull /
        # dispatch_* tick on the transfer thread, transfer_wait on the
        # consumer thread, and the ring workers' blocking dispatches
        # overlap all of them — the sum may exceed wall-clock.
        # stage_dispatch = dispatch_pack + dispatch_put is kept as an
        # explicit key for r1-r5 comparability (bench aggregates these).
        self.stage_seconds: Dict[str, float] = {
            "host_pull": 0.0,
            "dispatch_pack": 0.0,
            "dispatch_put": 0.0,
            "dispatch_slot_wait": 0.0,
            "stage_dispatch": 0.0,
            "transfer_wait": 0.0,
        }
        # registry duration histograms, one per REAL stage (the derived
        # stage_dispatch sum is not re-observed — it would double-count
        # pack+put samples); ISSUE 4: timing splits become histograms
        self._stage_hists = {
            k: _stage_hist(k)
            for k in (
                "host_pull",
                "dispatch_pack",
                "dispatch_put",
                "dispatch_slot_wait",
                "transfer_wait",
            )
        }
        self.staging = StagingStats()
        # _shard_plan is a once-per-config property (the batcher emits
        # fixed shapes); memoized by shape/dtype signature so the hot
        # loop doesn't re-derive it per batch (contiguity, the one
        # per-batch degree of freedom, is still rechecked each time)
        self._plan_memo: Dict[Any, Any] = {}
        self._t_start: Optional[float] = None
        # dispatch ring: `depth` workers (one in-flight dispatch per
        # slot), depth+2 slots — the transfer thread packs into one
        # while `depth` futures sit in the device queue and one batch is
        # with the consumer
        self._exec = ThreadPoolExecutor(
            max_workers=self._depth, thread_name_prefix="staging-put"
        )
        self._slots = [_SlotBuf() for _ in range(self._depth + 2)]
        self._slot_i = 0
        self._host_iter: ThreadedIter[Batch] = ThreadedIter(
            lambda: iter(host_batches), max_capacity=prefetch, name="staging"
        )
        # device_put may BLOCK during dispatch (a backend is free to
        # complete the "async" transfer before device_put returns).
        # Staging inline on the consumer thread would then serialize
        # transfers with the consumer's compute and the in-flight `depth`
        # would overlap nothing. The transfer thread + ring workers keep
        # the overlap whatever the platform's dispatch semantics: parse
        # threads, packing, device_put, and consumer compute each run on
        # their own thread, meeting at bounded queues (the reference's
        # pipeline discipline, threaded_input_split.h:33, one level
        # further down).
        self._xfer_iter: ThreadedIter[Any] = ThreadedIter(
            self._staged, max_capacity=self._depth, name="staging-xfer"
        )

    def _platform(self) -> str:
        if self._mesh is not None:
            return self._mesh.devices.flat[0].platform
        if self._device is None:
            self._device = self._jax.local_devices()[0]
        return self._device.platform

    def _plan_for(self, host: Batch):
        """Memoized ``_shard_plan`` for this pipeline's mesh."""
        if host.packed is None:
            return None
        arrays = host.as_dict()
        key = tuple((k, v.shape, str(v.dtype)) for k, v in arrays.items())
        if key in self._plan_memo:
            plan = self._plan_memo[key]
        else:
            plan = _shard_plan(host, self._mesh, self._data_axis)
            self._plan_memo[key] = plan
        if plan is not None and any(
            not v.flags.c_contiguous for v in arrays.values()
        ):
            return None
        return plan

    def _observe(self, key: str, dt: float, dispatch: bool = False) -> None:
        """One stage timing sample: tick the legacy per-pipeline sum
        (bench r1-r5 comparability) and the registry duration histogram
        (``staging.stage_seconds{stage=...}``). ``dispatch`` also feeds
        the derived ``stage_dispatch`` sum (= pack + put)."""
        self.stage_seconds[key] += dt
        if dispatch:
            self.stage_seconds["stage_dispatch"] += dt
        self._stage_hists[key].observe(dt)

    def _next_slot(self) -> _SlotBuf:
        """Round-robin slot claim; waits out the slot's previous
        dispatch so the buffer is never rewritten under a live DMA."""
        slot = self._slots[self._slot_i]
        self._slot_i = (self._slot_i + 1) % len(self._slots)
        if slot.pending is not None:
            t0 = get_time()
            with annotate("dmlc:dispatch_slot_wait"):
                try:
                    self._jax.block_until_ready(slot.pending.result())
                except (Exception, CancelledError):
                    pass  # the consumer re-raises from its own future
            slot.pending = None
            self._observe("dispatch_slot_wait", get_time() - t0)
        return slot

    def _staged(self) -> Iterator[Any]:
        """Transfer-thread producer: pull host batches, pack into ring
        slots, dispatch on the ring workers, hand future-shaped handles
        to the bounded depth queue."""
        jax = self._jax
        while True:
            t0 = get_time()
            with annotate("dmlc:host_pull"):
                host = self._host_iter.next()
            self._observe("host_pull", get_time() - t0)
            if host is None:
                return
            platform = self._platform()
            plan = None
            layout = None
            if self._mesh is not None:
                plan = self._plan_for(host)
            elif host.packed is not None:
                layout = _packed_layout(host)
            if plan is not None:
                shard_entries, stride, n_shards = plan
                slot = self._next_slot()
                t0 = get_time()
                with annotate("dmlc:dispatch_pack"):
                    src = _pack_shards(
                        host, shard_entries, stride, n_shards, platform,
                        slot,
                    )
                self._observe("dispatch_pack", get_time() - t0, dispatch=True)
                t0 = get_time()
                with annotate("dmlc:dispatch_put"):
                    item = self._exec.submit(
                        _put_packed_shards, src, shard_entries, stride,
                        self._mesh, self._data_axis, self.staging,
                    )
                if platform != "cpu":
                    slot.pending = item
                self._observe("dispatch_put", get_time() - t0, dispatch=True)
            elif layout is not None:
                if self._adopt and adoptable_slot(host):
                    # zero-copy adopt: device_put straight from the
                    # producer's page-aligned buffer. No ring slot and
                    # no slot.pending — the submitted future holds the
                    # source array, and on CPU jax's zero-copy alias
                    # additionally pins it for the device array's life,
                    # so the producer's finalizer-based recycling can't
                    # fire under an in-flight transfer.
                    t0 = get_time()
                    with annotate("dmlc:dispatch_put"):
                        item = self._exec.submit(
                            _put_packed, host.packed, layout, self._device,
                            self.staging,
                        )
                    self.slots_adopted += 1
                    _SLOTS_ADOPTED.inc()
                    self._observe(
                        "dispatch_put", get_time() - t0, dispatch=True
                    )
                else:
                    if self._adopt:
                        # adopt-capable producer but this buffer failed
                        # the shape check (unaligned fallback alloc)
                        _SLOT_COPIES.inc()
                    slot = self._next_slot()
                    t0 = get_time()
                    with annotate("dmlc:dispatch_pack"):
                        src = _pack_single(host, platform, slot)
                    self._observe(
                        "dispatch_pack", get_time() - t0, dispatch=True
                    )
                    t0 = get_time()
                    with annotate("dmlc:dispatch_put"):
                        item = self._exec.submit(
                            _put_packed, src, layout, self._device,
                            self.staging,
                        )
                    if platform != "cpu":
                        slot.pending = item
                    self._observe(
                        "dispatch_put", get_time() - t0, dispatch=True
                    )
            else:
                # per-array fallback: host buffers stay referenced until
                # the DMA completes, so dispatch stays on this thread and
                # the producer-ring accounting above keeps it safe (the
                # plan/layout decision is already made — call the
                # fallback stage directly, don't re-derive it)
                t0 = get_time()
                with annotate("dmlc:stage"):
                    if self._mesh is not None:
                        dev = _stage_per_array_mesh(
                            host, self._mesh, self._data_axis,
                            self.staging,
                        )
                    else:
                        dev = {
                            k: jax.device_put(
                                _safe_host(v, platform), self._device
                            )
                            for k, v in host.as_dict().items()
                        }
                        self.staging.tick_raw_puts(len(dev))
                        self.staging.tick_batch("per_array")
                    item = _Ready(dev)
                self._observe("dispatch_put", get_time() - t0, dispatch=True)
            self.rows_staged += host.n_valid
            self.batches_staged += 1
            nbytes = sum(v.nbytes for v in host.as_dict().values())
            self.bytes_staged += nbytes
            _ROWS_STAGED.inc(host.n_valid)
            _BYTES_STAGED.inc(nbytes)
            del host  # release the producer slot before blocking downstream
            yield item

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        if self._t_start is None:
            self._t_start = get_time()
        # the finally tears the threads down when the consumer abandons
        # the iterator (early stop, exception unwind) as well as at
        # normal exhaustion — without it an unclosed pipeline pins
        # depth+1 staged batches of HBM plus two threads forever (the
        # running threads keep the pipeline reachable, so __del__ never
        # fires)
        try:
            while True:
                item = self._xfer_iter.next()
                if item is None:
                    return
                # Force this batch's transfer to complete before handing
                # it out (resolving the ring future, then blocking on the
                # arrays). Transfers for the batches still in the depth
                # queue proceed concurrently (that's the overlap); what
                # this guarantees is a bound on host-buffer lifetime, so
                # producers that recycle a ring of host buffers
                # (staging/fused.py) can size the ring as
                # prefetch + depth + 2 instead of "unbounded, because
                # async dispatch may read the host buffer arbitrarily
                # late".
                t0 = get_time()
                with annotate("dmlc:transfer_wait"):
                    dev = item.result()
                    self._jax.block_until_ready(dev)
                self._observe("transfer_wait", get_time() - t0)
                yield dev
        finally:
            self.close()

    def throughput(self) -> Dict[str, float]:
        """rows/sec and MB/sec since first iteration (SURVEY §5.1 metric
        hook; the reference logs MB/sec from BasicRowIter)."""
        dt = max(get_time() - (self._t_start or get_time()), 1e-9)
        return {
            "rows_per_sec": self.rows_staged / dt,
            "mb_per_sec": self.bytes_staged / dt / 1e6,
            "seconds": dt,
            "rows": float(self.rows_staged),
            "batches": float(self.batches_staged),
            **{f"secs_{k}": v for k, v in self.stage_seconds.items()},
        }

    def close(self) -> bool:
        # host iterator first: its destroy() wakes the transfer thread
        # if it is blocked pulling the parse queue (stalled upstream IO),
        # so the xfer teardown's join can actually complete. Bounded
        # joins: a producer stalled in uninterruptible IO is orphaned
        # after the timeout rather than wedging close() for the stall's
        # duration (the daemon thread exits at its next queue put).
        # Returns False — and latches ``close_timed_out`` — when either
        # join timed out: the orphaned thread may still touch the host
        # batch source, so the caller must not tear down mmap-backed
        # producers until it has actually exited.
        host_joined = self._host_iter.destroy(timeout=1.0)
        xfer_joined = self._xfer_iter.destroy(timeout=1.0)
        # ring workers read only pipeline-owned slot buffers (never the
        # producer's ring), so an unfinished dispatch can drain after the
        # sources are gone; no join needed beyond letting them finish
        self._exec.shutdown(wait=False, cancel_futures=True)
        if not (host_joined and xfer_joined):
            self.close_timed_out = True
        return host_joined and xfer_joined

    def staging_stats(self) -> Dict[str, Any]:
        """Transfer-shape counters: put counts (total and per device),
        which path each batch rode, the packed_shard_dma flag, the
        dispatch ring depth and the unpacker-cache LRU shape."""
        out = self.staging.snapshot()
        out["dispatch_ring_depth"] = self._depth
        out["dispatch_ring_slots"] = len(self._slots)
        out["slots_adopted"] = self.slots_adopted
        return out

    def io_stats(self) -> Dict[str, Any]:
        """The batch source's counters (split I/O shape + retry/fault
        deltas) merged with this pipeline's staging counters under
        ``"staging"`` — the last hop of the io_stats plumbing
        (split → fused staging → pipeline → bench/dryrun)."""
        fn = getattr(self._source, "io_stats", None)
        src = fn() if fn is not None else None
        out: Dict[str, Any] = dict(src) if src else {}
        out["staging"] = self.staging_stats()
        return out


def drain_close(pipe: StagingPipeline, *sources) -> bool:
    """Close a StagingPipeline, then its batch source(s) — honoring
    ``close_timed_out``.

    When the bounded teardown join timed out, an orphaned producer
    thread may still be reading the sources' buffers (mmap windows,
    fused ring slots); ``source.close()`` here would unmap them under a
    live reader. Instead the sources are deliberately leaked: the
    daemon thread exits at its next queue put and the mappings fall to
    GC/process teardown. Returns True when everything closed cleanly.
    """
    clean = pipe.close()
    if not clean:
        logger.warning(
            "staging teardown join timed out; deferring close of %d "
            "batch source(s) to process teardown (orphaned producer "
            "thread may still be reading their buffers)",
            len(sources),
        )
        return False
    for s in sources:
        close = getattr(s, "close", None)
        if close is not None:
            close()
    return True
