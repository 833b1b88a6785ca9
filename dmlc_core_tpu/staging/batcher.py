"""Fixed-shape batching of ragged RowBlocks — the TPU-specific reshaping.

No reference analogue (SURVEY §7 hard part 1): the reference feeds ragged
CSR RowBlocks to a CPU learner; XLA needs static shapes. This module turns a
stream of RowBlocks into fixed-shape numpy batches ready for device_put:

- ``ell`` layout: capped-CSR / ELL — ``indices i32[B,K]``, ``values
  f32[B,K]`` with zero-padding and per-row ``nnz`` counts. ``K`` =
  max nnz per row; overflow policy 'truncate' (drop extra features,
  counted in stats) or 'error'. With ``BatchSpec.fields`` each slot's
  field id (libfm's ``field:index:value``) rides beside it as
  ``fields i32[B,K]`` (padding slots: field 0, value 0).
- ``dense`` layout: scatter into ``x f32[B,D]`` — right for dense-ish data
  (HIGGS: 28 features) and the MXU, which wants large dense matmuls.

Partial final batches are zero-padded to exactly B rows with weight 0, so
every batch compiles to the same XLA program; models must use ``weights``
as the validity mask (padding rows contribute zero loss).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import numpy as np

from ..data.row_block import RowBlock
from ..utils.logging import Error, check

__all__ = [
    "FIELD_DTYPE",
    "Batch",
    "BatchSpec",
    "FixedShapeBatcher",
    "alloc_packed_slot",
    "gather_slices",
    "packed_shard_layout",
]

# every section (and every per-shard segment) starts on an 8-byte
# boundary: the widest staged dtype is 8 bytes, so both the host numpy
# views and the on-device bitcast unpack (pipeline.py) always see
# aligned data, whole-batch or per-shard
PACK_ALIGN = 8
# staged field ids (``BatchSpec.fields``): int32, as the feature ids
FIELD_DTYPE = np.dtype(np.int32)


def _aligned(nbytes: int) -> int:
    return (nbytes + PACK_ALIGN - 1) & ~(PACK_ALIGN - 1)


def alloc_packed_slot(sections):
    """One contiguous uint8 buffer + named views into it.

    ``sections`` is [(name, shape, dtype)]; each section's offset is
    PACK_ALIGN-aligned. Returns (buf, views). The single buffer is what
    lets the staging pipeline move a whole batch as ONE device transfer
    (or one per mesh shard) instead of one per array.
    """
    offs = []
    off = 0
    for _name, shape, dtype in sections:
        nb = int(np.prod(shape)) * np.dtype(dtype).itemsize
        offs.append((off, nb))
        off += _aligned(nb)
    buf = np.zeros(off, dtype=np.uint8)
    views = {}
    for (o, nb), (name, shape, dtype) in zip(offs, sections):
        views[name] = buf[o : o + nb].view(dtype).reshape(shape)
    return buf, views


def ell_sections(spec: "BatchSpec"):
    """The packed ELL slot's sections, in order: every ELL producer (the
    fused kernels and ``FixedShapeBatcher``) stages this one layout."""
    B, K = spec.batch_size, int(spec.max_nnz)  # type: ignore[arg-type]
    sections = [
        ("indices", (B, K), spec.index_dtype),
        ("values", (B, K), spec.value_dtype),
        ("nnz", (B,), np.int32),
        ("labels", (B,), np.float32),
        ("weights", (B,), np.float32),
    ]
    if spec.fields:
        sections.append(("fields", (B, K), FIELD_DTYPE))
    return sections


def gather_slices(
    buf: np.ndarray, starts: np.ndarray, sizes: np.ndarray
) -> np.ndarray:
    """Vectorized byte gather: one contiguous uint8 array holding
    ``buf[starts[i] : starts[i] + sizes[i]]`` back to back, via
    ``np.repeat`` index expansion — no per-slice Python loop.

    The NumPy fallback for the shuffled-read gather handoff
    (``IndexedRecordIOSplitter.next_gather_batch``) when the native
    gather kernel is absent: the re-framed result feeds the sequential
    chunk parsers unchanged (staging/fused.py).
    """
    starts = np.asarray(starts, dtype=np.int64)
    sizes = np.asarray(sizes, dtype=np.int64)
    total = int(sizes.sum())
    if not total:
        return np.empty(0, dtype=np.uint8)
    base = np.cumsum(sizes) - sizes
    gather = np.arange(total, dtype=np.int64) + np.repeat(
        starts - base, sizes
    )
    return buf[gather]


def packed_shard_layout(entries, n_shards: int):
    """Per-shard packing plan for a leading-dim sharded batch.

    ``entries`` is [(name, shape, dtype)] with every shape's leading dim
    divisible by ``n_shards`` (the batcher emits fixed ``batch_size``
    rows, so callers pick batch sizes that divide; anything else returns
    None and the caller falls back to per-array transfers). Returns
    (shard_entries, stride): ``shard_entries`` is
    [(name, seg_off, seg_nbytes, global_shape, dtype)] where ``seg_off``
    is the PACK_ALIGN-aligned offset of the array's rows INSIDE one
    shard's contiguous block, and ``stride`` is the aligned size of that
    block — shard ``d`` of the whole batch occupies bytes
    ``[d*stride, (d+1)*stride)`` of the repacked staging buffer, so each
    shard rides one contiguous DMA. Alignment padding bytes are sliced
    off again by the on-device unpack.
    """
    shard_entries = []
    off = 0
    for name, shape, dtype in entries:
        if not shape or shape[0] % n_shards:
            return None
        rows = shape[0] // n_shards
        nb = rows * int(np.prod(shape[1:], dtype=np.int64)) * np.dtype(
            dtype
        ).itemsize
        shard_entries.append((name, off, int(nb), tuple(shape), str(dtype)))
        off += _aligned(int(nb))
    return tuple(shard_entries), off


@dataclass(frozen=True)
class Batch:
    """One fixed-shape host batch. Arrays are numpy, ready for device_put.

    ``n_valid`` rows are real; rows beyond that are zero padding with
    weight 0. For 'ell': indices/values are [B,K] (and fields, when the
    spec stages them); for 'dense': x is [B,D].
    """

    labels: np.ndarray
    weights: np.ndarray
    n_valid: int
    indices: Optional[np.ndarray] = None
    values: Optional[np.ndarray] = None
    nnz: Optional[np.ndarray] = None
    x: Optional[np.ndarray] = None
    fields: Optional[np.ndarray] = None
    # single contiguous uint8 buffer the other arrays are views into
    # (fused producers set this): lets the staging pipeline issue ONE
    # device transfer per batch and bitcast-unpack in HBM, instead of
    # five small DMAs (staging/pipeline.py packed path)
    packed: Optional[np.ndarray] = None

    @property
    def batch_size(self) -> int:
        return len(self.labels)

    def as_dict(self) -> Dict[str, np.ndarray]:
        """Pytree-friendly dict (what lands on device)."""
        out = {"labels": self.labels, "weights": self.weights}
        if self.x is not None:
            out["x"] = self.x
        else:
            out["indices"] = self.indices
            out["values"] = self.values
            out["nnz"] = self.nnz
            if self.fields is not None:
                out["fields"] = self.fields
        return out


@dataclass
class BatchSpec:
    """Static-shape contract for a batch stream.

    batch_size: rows per batch (pick a multiple of the mesh's data-parallel
    size × 8 so per-device shards stay MXU/VPU friendly).
    layout: 'ell' or 'dense'.
    max_nnz: K for 'ell' (required there).
    num_features: D for 'dense' (required there); indices >= D follow
    ``overflow`` policy.
    overflow: 'truncate' | 'error'.
    fields: 'ell' only — also stage each slot's field id as
    ``FIELD_DTYPE`` [B, K] (libfm input; a field outside int32 after
    the indexing base drops the slot as an id outside it does). Off,
    every staged byte is what it is without the option.
    """

    batch_size: int
    layout: str = "ell"
    max_nnz: Optional[int] = None
    num_features: Optional[int] = None
    overflow: str = "truncate"
    index_dtype: np.dtype = np.dtype(np.int32)
    # dtype of the feature VALUES staged to the device (labels/weights stay
    # float32 — they're tiny). float16 halves infeed DMA bytes; models
    # upcast on device (standard TPU infeed trick; values like HIGGS's
    # N(0,1) features lose nothing that bf16 compute wouldn't lose anyway)
    value_dtype: np.dtype = np.dtype(np.float32)
    fields: bool = False

    def __post_init__(self) -> None:
        check(self.layout in ("ell", "dense"), f"bad layout {self.layout!r}")
        check(self.overflow in ("truncate", "error"),
              f"bad overflow policy {self.overflow!r}")
        if self.layout == "ell":
            check(self.max_nnz is not None and self.max_nnz > 0,
                  "ell layout requires max_nnz")
        else:
            check(not self.fields, "field ids ride the ell layout only")
            check(self.num_features is not None and self.num_features > 0,
                  "dense layout requires num_features")


def _fits_field(field: np.ndarray) -> np.ndarray:
    """Field ids that ``FIELD_DTYPE`` holds: in [0, 2^31) (a negative id
    is a 1-based field 0, or junk)."""
    f = np.asarray(field, dtype=np.int64)
    return (f >= 0) & (f <= np.iinfo(FIELD_DTYPE).max)


class FixedShapeBatcher:
    """RowBlock stream → fixed-shape Batch stream (all-numpy, vectorized).

    Carries a partial-row remainder between input blocks so batches are
    exactly ``batch_size`` rows; the final batch is zero-padded.
    """

    def __init__(self, spec: BatchSpec) -> None:
        self.spec = spec
        self.rows_in = 0
        self.rows_out = 0
        self.truncated_nnz = 0
        self._pending: list[RowBlock] = []
        self._pending_rows = 0

    # -- conversion cores ----------------------------------------------------
    # f32→f16 value staging uses IEEE round-to-nearest with overflow
    # saturating to ±inf — the same single-round semantics as the native
    # fused kernels (fastparse.cc f32_to_f16). numpy warns on the overflow
    # by default; the policy is chosen, so the warning is suppressed at
    # the cast sites below via np.errstate(over='ignore').

    def _to_ell(self, blk: RowBlock, n_valid: int) -> Batch:
        spec = self.spec
        B, K = spec.batch_size, int(spec.max_nnz)  # type: ignore[arg-type]
        nnz_per_row = np.diff(blk.offset)
        over = nnz_per_row - K
        n_over = int(over[over > 0].sum()) if len(over) else 0
        if n_over:
            if spec.overflow == "error":
                raise Error(
                    f"row nnz exceeds max_nnz={K} "
                    f"(worst row has {int(nnz_per_row.max())})"
                )
            self.truncated_nnz += n_over
        # one contiguous buffer per batch (fresh — nothing recycles it),
        # same slot layout as the fused ELL producers: the staging
        # pipeline stages generic-parser batches with the same single-DMA
        # (and packed-shard mesh) fast path the native kernels get
        packed, v = alloc_packed_slot(ell_sections(spec))
        indices, values = v["indices"], v["values"]
        fields = v.get("fields")
        if fields is not None and blk.nnz:
            check(blk.field is not None,
                  "BatchSpec.fields needs a parser that keeps field ids")
        m = len(nnz_per_row)
        # fast path: uniform row width that fits K and the index dtype →
        # plain reshape+copy, no position scatter
        k0 = int(nnz_per_row[0]) if m else 0
        if (
            blk.nnz
            and 0 < k0 <= K
            and np.all(nnz_per_row == k0)
            and blk.index.size
            and int(blk.index.astype(np.uint64, copy=False).max())
            <= np.iinfo(spec.index_dtype).max
            and (fields is None or _fits_field(blk.field).all())
        ):
            indices[:m, :k0] = blk.index.reshape(m, k0).astype(
                spec.index_dtype, copy=False
            )
            if fields is not None:
                fields[:m, :k0] = blk.field.reshape(m, k0)
            vals = (
                blk.value
                if blk.value is not None
                else np.ones(blk.nnz, dtype=np.float32)
            )
            with np.errstate(over="ignore"):
                values[:m, :k0] = vals.reshape(m, k0)
            nnz_kept = np.full(m, k0, dtype=np.int64)
        elif blk.nnz:
            row_ids = np.repeat(np.arange(m), nnz_per_row)
            pos = np.arange(blk.nnz) - np.repeat(blk.offset[:-1], nnz_per_row)
            keep = pos < K
            # feature ids that don't fit the on-device index dtype (or
            # wrapped-negative uint64s) must not silently alias another
            # feature via astype truncation
            idx64 = blk.index.astype(np.uint64, copy=False)
            fits = idx64 <= np.uint64(np.iinfo(spec.index_dtype).max)
            if fields is not None:
                # a field id that does not fit drops its slot as an id does
                fits &= _fits_field(blk.field)
            n_unfit = int((keep & ~fits).sum())
            if n_unfit:
                if spec.overflow == "error":
                    raise Error(
                        f"feature index {int(idx64.max())} does not fit "
                        f"index dtype {spec.index_dtype}"
                    )
                self.truncated_nnz += n_unfit
                keep &= fits
            r, p = row_ids[keep], pos[keep]
            indices[r, p] = idx64[keep].astype(spec.index_dtype)
            if fields is not None:
                fields[r, p] = blk.field[keep]
            vals = (
                blk.value[keep]
                if blk.value is not None
                else np.ones(int(keep.sum()), dtype=np.float32)
            )
            with np.errstate(over="ignore"):
                values[r, p] = vals
            # per-row counts reflect dropped unfit features too
            nnz_kept = np.zeros(m, dtype=np.int64)
            np.add.at(nnz_kept, row_ids[keep], 1)
        else:
            nnz_kept = np.zeros(m, dtype=np.int64)
        nnz, labels, weights = v["nnz"], v["labels"], v["weights"]
        nnz[:m] = nnz_kept
        labels[:m] = blk.label
        weights[:m] = 1.0 if blk.weight is None else blk.weight
        return Batch(
            labels=labels, weights=weights, n_valid=n_valid,
            indices=indices, values=values, nnz=nnz, fields=fields,
            packed=packed,
        )

    def _to_dense(self, blk: RowBlock, n_valid: int) -> Batch:
        spec = self.spec
        B, D = spec.batch_size, int(spec.num_features)  # type: ignore[arg-type]
        # same contiguous layout as the fused dense producers (one DMA /
        # packed-shard staging for generic-parser batches too)
        packed, v = alloc_packed_slot(
            [
                ("x", (B, D), spec.value_dtype),
                ("labels", (B,), np.float32),
                ("weights", (B,), np.float32),
            ]
        )
        x = v["x"]
        m = blk.size
        if blk.nnz:
            nnz_per_row = np.diff(blk.offset)
            # compare in uint64 so wrapped-negative ids (e.g. a parsed
            # '-5' feature) register as out of range instead of indexing
            # from the end of the row
            keep = blk.index.astype(np.uint64, copy=False) < np.uint64(D)
            idx = blk.index.astype(np.int64)
            n_over = int((~keep).sum())
            if n_over:
                if spec.overflow == "error":
                    raise Error(
                        f"feature index {int(idx.max())} >= num_features={D}"
                    )
                self.truncated_nnz += n_over
            vals = (
                blk.value
                if blk.value is not None
                else np.ones(blk.nnz, dtype=np.float32)
            )
            # fast path: uniform row width + strictly-increasing indices
            # (every tabular format: HIGGS, Criteo, CSV output) → one fancy
            # assignment instead of the much slower np.add.at scatter
            k0 = int(nnz_per_row[0]) if m else 0
            uniform = k0 > 0 and not n_over and np.all(nnz_per_row == k0)
            if uniform:
                idx2 = idx.reshape(m, k0)
                if k0 == 1 or np.all(idx2[:, 1:] > idx2[:, :-1]):
                    with np.errstate(over="ignore"):
                        x[np.arange(m)[:, None], idx2] = vals.reshape(m, k0)
                else:
                    uniform = False
            if not uniform:
                row_ids = np.repeat(np.arange(m), nnz_per_row)
                # duplicate indices within a row accumulate, matching
                # sparse dot semantics
                with np.errstate(over="ignore"):
                    np.add.at(x, (row_ids[keep], idx[keep]), vals[keep])
        labels, weights = v["labels"], v["weights"]
        labels[:m] = blk.label
        weights[:m] = 1.0 if blk.weight is None else blk.weight
        return Batch(labels=labels, weights=weights, n_valid=n_valid, x=x,
                     packed=packed)

    def _emit(self, blk: RowBlock) -> Batch:
        n_valid = blk.size
        self.rows_out += n_valid
        if self.spec.layout == "ell":
            return self._to_ell(blk, n_valid)
        return self._to_dense(blk, n_valid)

    # -- streaming -----------------------------------------------------------
    def push(self, blk: RowBlock) -> Iterator[Batch]:
        """Feed one RowBlock; yields zero or more full batches."""
        if blk.size == 0:
            return
        self.rows_in += blk.size
        self._pending.append(blk)
        self._pending_rows += blk.size
        B = self.spec.batch_size
        while self._pending_rows >= B:
            merged = (
                self._pending[0]
                if len(self._pending) == 1
                else RowBlock.concat(self._pending)
            )
            head = merged.slice(0, B)
            rest_rows = merged.size - B
            self._pending = [merged.slice(B, merged.size)] if rest_rows else []
            self._pending_rows = rest_rows
            yield self._emit(head)

    def flush(self) -> Optional[Batch]:
        """Emit the final zero-padded partial batch, if any."""
        if not self._pending_rows:
            return None
        merged = (
            self._pending[0]
            if len(self._pending) == 1
            else RowBlock.concat(self._pending)
        )
        self._pending = []
        self._pending_rows = 0
        return self._emit(merged)

    def batches(self, blocks: Iterator[RowBlock]) -> Iterator[Batch]:
        """Convenience: full stream → batches, flushing at the end."""
        for blk in blocks:
            yield from self.push(blk)
        tail = self.flush()
        if tail is not None:
            yield tail
