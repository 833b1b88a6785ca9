"""Staging layer: fixed-shape batching + device staging on the virtual
8-device CPU mesh (conftest sets XLA_FLAGS/JAX_PLATFORMS)."""

import numpy as np
import pytest

from dmlc_core_tpu.data.row_block import RowBlock
from dmlc_core_tpu.staging import (
    BatchSpec,
    FixedShapeBatcher,
    StagingPipeline,
    stage_batch,
)


def ragged_block(sizes, base=0):
    offset = np.zeros(len(sizes) + 1, dtype=np.int64)
    np.cumsum(sizes, out=offset[1:])
    nnz = int(offset[-1])
    return RowBlock(
        offset=offset,
        label=np.arange(base, base + len(sizes), dtype=np.float32),
        index=np.arange(nnz, dtype=np.uint64) % 16,
        value=np.linspace(1, 2, nnz, dtype=np.float32) if nnz else None,
    )


# -- ELL layout --------------------------------------------------------------

def test_ell_shapes_and_padding():
    spec = BatchSpec(batch_size=4, layout="ell", max_nnz=3)
    b = FixedShapeBatcher(spec)
    blk = ragged_block([2, 3, 1])  # 3 rows < batch_size
    batches = list(b.push(blk))
    assert batches == []
    tail = b.flush()
    assert tail.batch_size == 4 and tail.n_valid == 3
    assert tail.indices.shape == (4, 3) and tail.values.shape == (4, 3)
    np.testing.assert_array_equal(tail.nnz, [2, 3, 1, 0])
    np.testing.assert_array_equal(tail.weights, [1, 1, 1, 0])  # pad masked
    # row 0 has 2 real slots, third is zero padding
    assert tail.values[0, 2] == 0.0


def test_ell_round_trip_values():
    spec = BatchSpec(batch_size=2, layout="ell", max_nnz=4)
    b = FixedShapeBatcher(spec)
    blk = ragged_block([4, 2])
    (batch,) = list(b.push(blk))
    for i in range(2):
        row = blk[i]
        k = len(row)
        np.testing.assert_array_equal(batch.indices[i, :k], row.index)
        np.testing.assert_allclose(batch.values[i, :k], row.value)


def test_ell_truncation_policy():
    spec = BatchSpec(batch_size=1, layout="ell", max_nnz=2, overflow="truncate")
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(ragged_block([5])))
    assert batch.nnz[0] == 2
    assert b.truncated_nnz == 3
    spec_err = BatchSpec(batch_size=1, layout="ell", max_nnz=2, overflow="error")
    with pytest.raises(Exception, match="max_nnz"):
        list(FixedShapeBatcher(spec_err).push(ragged_block([5])))


def test_streaming_remainder_carry():
    """Rows flow across block boundaries into exact-size batches."""
    spec = BatchSpec(batch_size=8, layout="ell", max_nnz=4)
    b = FixedShapeBatcher(spec)
    out = list(b.batches(iter([ragged_block([1] * 5), ragged_block([2] * 10, 5),
                               ragged_block([1] * 3, 15)])))
    assert [x.n_valid for x in out] == [8, 8, 2]
    assert b.rows_in == 18 and b.rows_out == 18
    # labels arrive in order across the whole stream
    all_labels = np.concatenate([x.labels[: x.n_valid] for x in out])
    np.testing.assert_array_equal(all_labels[:5], [0, 1, 2, 3, 4])
    np.testing.assert_array_equal(all_labels[5:15], np.arange(5, 15))


# -- dense layout ------------------------------------------------------------

def test_dense_scatter_and_duplicate_accumulate():
    spec = BatchSpec(batch_size=2, layout="dense", num_features=8)
    b = FixedShapeBatcher(spec)
    blk = RowBlock(
        offset=np.array([0, 3, 4]),
        label=np.array([1.0, 0.0], np.float32),
        index=np.array([1, 1, 5, 7], np.uint64),  # dup index in row 0
        value=np.array([0.5, 0.25, 2.0, 3.0], np.float32),
    )
    (batch,) = list(b.push(blk))
    assert batch.x.shape == (2, 8)
    assert batch.x[0, 1] == pytest.approx(0.75)  # accumulated
    assert batch.x[0, 5] == 2.0 and batch.x[1, 7] == 3.0


def test_dense_overflow_policies():
    blk = RowBlock(
        offset=np.array([0, 1]), label=np.array([1.0], np.float32),
        index=np.array([100], np.uint64), value=np.array([1.0], np.float32),
    )
    spec = BatchSpec(batch_size=1, layout="dense", num_features=8)
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(blk))
    assert batch.x.sum() == 0 and b.truncated_nnz == 1
    spec_err = BatchSpec(
        batch_size=1, layout="dense", num_features=8, overflow="error"
    )
    with pytest.raises(Exception, match="num_features"):
        list(FixedShapeBatcher(spec_err).push(blk))


def test_binary_features_default_value_one():
    blk = RowBlock(
        offset=np.array([0, 2]), label=np.array([1.0], np.float32),
        index=np.array([3, 6], np.uint64), value=None,
    )
    spec = BatchSpec(batch_size=1, layout="dense", num_features=8)
    (batch,) = list(FixedShapeBatcher(spec).push(blk))
    assert batch.x[0, 3] == 1.0 and batch.x[0, 6] == 1.0


# -- device staging ----------------------------------------------------------

def test_stage_batch_single_device():
    import jax

    spec = BatchSpec(batch_size=4, layout="dense", num_features=8)
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(ragged_block([2, 2, 1, 3])))
    dev = stage_batch(batch)
    assert isinstance(dev["x"], jax.Array)
    np.testing.assert_allclose(np.asarray(dev["x"]), batch.x)
    np.testing.assert_allclose(np.asarray(dev["labels"]), batch.labels)


def test_stage_batch_sharded_over_mesh():
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()).reshape(8)
    mesh = Mesh(devices, ("data",))
    spec = BatchSpec(batch_size=16, layout="ell", max_nnz=4)
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(ragged_block([2] * 16)))
    dev = stage_batch(batch, mesh=mesh)
    x = dev["values"]
    assert x.shape == (16, 4)
    # batch dim sharded 8 ways, feature dim replicated
    assert len(x.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in x.addressable_shards}
    assert shard_shapes == {(2, 4)}
    np.testing.assert_allclose(np.asarray(x), batch.values)


def test_staging_pipeline_end_to_end():
    spec = BatchSpec(batch_size=8, layout="dense", num_features=16)
    batcher = FixedShapeBatcher(spec)
    blocks = [ragged_block([2] * 6, base=6 * i) for i in range(5)]  # 30 rows
    pipe = StagingPipeline(batcher.batches(iter(blocks)), depth=2)
    seen_rows = 0
    labels = []
    for dev in pipe:
        arr = np.asarray(dev["labels"])
        w = np.asarray(dev["weights"])
        labels.extend(arr[w > 0].tolist())
        seen_rows += int((w > 0).sum())
    assert seen_rows == 30
    assert pipe.rows_staged == 30 and pipe.batches_staged == 4
    stats = pipe.throughput()
    assert stats["rows"] == 30 and stats["rows_per_sec"] > 0
    # per-stage breakdown (VERDICT r4 weak #1), with the dispatch split
    # into pack/put by the dispatch ring (ISSUE 3): every phase ticked
    # and reported both on the attribute and through throughput()
    assert set(pipe.stage_seconds) == {
        "host_pull", "dispatch_pack", "dispatch_put",
        "dispatch_slot_wait", "transfer_wait",
    }
    assert all(v >= 0 for v in pipe.stage_seconds.values())
    assert pipe.stage_seconds["dispatch_pack"] > 0
    assert pipe.stage_seconds["dispatch_put"] > 0
    for k, v in pipe.stage_seconds.items():
        assert stats[f"secs_{k}"] == v
    assert "secs_stage_dispatch" not in stats
    # packed single-DMA path engaged (the generic batcher packs too now)
    st = pipe.staging_stats()
    assert st["packed_batches"] == 4 and st["per_array_batches"] == 0
    assert st["device_puts"] == 4  # ONE put per batch, not one per array
    assert st["packed_shard_dma"] is False
    assert pipe.io_stats()["staging"]["packed_batches"] == 4
    pipe.close()


def test_pipeline_rejects_shallow_ring():
    """The ring contract counts every concurrent holding point:
    1 in the producer thread + prefetch queued + 1 on the transfer
    thread + depth in the device queue + 1 being consumed
    (= prefetch + depth + 3)."""

    class _RingStream:
        ring_slots = 5

        def __iter__(self):  # pragma: no cover — rejected before use
            return iter(())

    with pytest.raises(Exception, match="ring has 5 slots"):
        StagingPipeline(_RingStream(), depth=2, prefetch=1)
    ok = StagingPipeline(_RingStream(), depth=1, prefetch=1)
    ok.close()


def test_dense_wrapped_negative_index_is_overflow():
    """A parsed '-5' feature wraps to 2^64-5; it must count as overflow,
    not scatter into column D-5."""
    blk = RowBlock(
        offset=np.array([0, 1]), label=np.array([1.0], np.float32),
        index=np.array([np.uint64(2**64 - 5)], np.uint64),
        value=np.array([3.0], np.float32),
    )
    spec = BatchSpec(batch_size=1, layout="dense", num_features=8)
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(blk))
    assert batch.x.sum() == 0 and b.truncated_nnz == 1
    spec_err = BatchSpec(
        batch_size=1, layout="dense", num_features=8, overflow="error"
    )
    with pytest.raises(Exception):
        list(FixedShapeBatcher(spec_err).push(blk))


def test_ell_index_dtype_overflow_guard():
    """Feature ids beyond int32 must not silently wrap in the ELL array."""
    blk = RowBlock(
        offset=np.array([0, 2]), label=np.array([1.0], np.float32),
        index=np.array([3, 3_000_000_000], np.uint64),
        value=np.array([1.0, 2.0], np.float32),
    )
    spec = BatchSpec(batch_size=1, layout="ell", max_nnz=4)
    b = FixedShapeBatcher(spec)
    (batch,) = list(b.push(blk))
    assert b.truncated_nnz == 1
    assert batch.nnz[0] == 1
    assert (batch.indices >= 0).all()
    spec_err = BatchSpec(
        batch_size=1, layout="ell", max_nnz=4, overflow="error"
    )
    with pytest.raises(Exception, match="does not fit"):
        list(FixedShapeBatcher(spec_err).push(blk))


# -- fault injection (SURVEY §7 step 7: fault-injection producers) -----------

class _FaultyProducer:
    """Yields ``good`` real batches then raises — the disk/parse failure
    modes (IO error, corrupt shard) surfacing mid-epoch inside the
    prefetch thread."""

    def __init__(self, good: int, exc: Exception):
        self.good = good
        self.exc = exc
        self.closed = False

    def __iter__(self):
        spec = BatchSpec(batch_size=2, layout="ell", max_nnz=3)
        b = FixedShapeBatcher(spec)
        for i in range(self.good):
            for out in b.push(ragged_block([1, 2], base=2 * i)):
                yield out
        raise self.exc

    def close(self):
        self.closed = True


@pytest.mark.jax
def test_pipeline_propagates_producer_fault_midstream():
    """A producer raising mid-epoch (after real batches staged) must
    surface THAT exception to the consumer — not hang the prefetch
    thread, not truncate silently — and the pipeline must still close."""
    boom = OSError("disk died mid-shard")
    prod = _FaultyProducer(good=3, exc=boom)
    pipe = StagingPipeline(prod)
    staged = []
    with pytest.raises(OSError, match="disk died"):
        for dev in pipe:
            staged.append(np.asarray(dev["labels"]))
    # batches already handed out arrived intact; the batch still in
    # flight behind the fault is dropped WITH the exception (the epoch is
    # poisoned — consumers restart from checkpoint, never trust a tail)
    assert len(staged) >= 2
    for i, lab in enumerate(staged):
        np.testing.assert_array_equal(lab, [2 * i, 2 * i + 1])
    pipe.close()  # must not wedge on the dead prefetch thread
    prod.close()


@pytest.mark.jax
def test_pipeline_fault_before_first_batch():
    boom = ValueError("corrupt header")
    pipe = StagingPipeline(_FaultyProducer(good=0, exc=boom))
    with pytest.raises(ValueError, match="corrupt header"):
        next(iter(pipe))
    pipe.close()


@pytest.mark.jax
def test_pipeline_abandoned_mid_epoch_closes_clean():
    """A consumer that stops pulling (early stopping, crash-unwind) and
    closes must not deadlock against a full prefetch queue."""
    spec = BatchSpec(batch_size=2, layout="ell", max_nnz=3)
    b = FixedShapeBatcher(spec)
    blocks = [ragged_block([1, 2], base=2 * i) for i in range(50)]

    def gen():
        for blk in blocks:
            yield from b.push(blk)

    pipe = StagingPipeline(gen())
    it = iter(pipe)
    next(it)  # stage one batch, then abandon with the queue primed
    assert pipe.close() is True  # clean join: safe to tear down sources
    assert pipe.close_timed_out is False


@pytest.mark.jax
def test_pipeline_close_does_not_wedge_on_stalled_producer():
    """close() while the upstream producer is stalled in
    uninterruptible IO must return promptly (bounded join + orphaned
    daemon thread), not block for the stall's duration."""
    import time

    spec = BatchSpec(batch_size=2, layout="ell", max_nnz=3)

    class _Stalled:
        def __iter__(self):
            b = FixedShapeBatcher(spec)
            yield from b.push(ragged_block([1, 2]))
            time.sleep(30)  # un-interruptible upstream stall
            yield from b.push(ragged_block([1, 2]))  # pragma: no cover

    pipe = StagingPipeline(_Stalled())
    it = iter(pipe)
    next(it)
    time.sleep(0.2)  # let the producer enter the stall
    t0 = time.perf_counter()
    clean = pipe.close()
    assert time.perf_counter() - t0 < 5.0
    # the orphaned producer is reported, so the caller knows NOT to tear
    # down mmap-backed sources the thread may still be reading
    assert clean is False
    assert pipe.close_timed_out is True
    # the abandoned iterator must also see a clean end, not a hang
    assert list(it) == []


class _ClosableSource:
    """Iterable batch source recording whether close() was called."""

    def __init__(self, batches):
        self._batches = batches
        self.closed = False

    def __iter__(self):
        return iter(self._batches)

    def close(self):
        self.closed = True


@pytest.mark.jax
def test_drain_close_closes_source_on_clean_join():
    from dmlc_core_tpu.staging import drain_close

    spec = BatchSpec(batch_size=2, layout="ell", max_nnz=3)
    b = FixedShapeBatcher(spec)
    src = _ClosableSource(list(b.push(ragged_block([1, 2]))))
    pipe = StagingPipeline(src)
    for _ in pipe:
        pass
    assert drain_close(pipe, src) is True
    assert src.closed is True


@pytest.mark.jax
def test_drain_close_defers_source_on_timed_out_join():
    """close_timed_out honored: an orphaned producer thread may still be
    reading the source's (mmap-backed) buffers — drain_close must NOT
    free them under it."""
    import time

    spec = BatchSpec(batch_size=2, layout="ell", max_nnz=3)

    class _StalledSource(_ClosableSource):
        def __iter__(self):
            b = FixedShapeBatcher(spec)
            yield from b.push(ragged_block([1, 2]))
            time.sleep(30)  # un-interruptible upstream stall
            yield from b.push(ragged_block([1, 2]))  # pragma: no cover

    from dmlc_core_tpu.staging import drain_close

    src = _StalledSource([])
    pipe = StagingPipeline(src)
    it = iter(pipe)
    next(it)
    time.sleep(0.2)  # let the producer enter the stall
    assert drain_close(pipe, src) is False
    assert pipe.close_timed_out is True
    assert src.closed is False, "source freed under a live reader thread"


def _fields_block(sizes, base=0, n_fields=7):
    blk = ragged_block(sizes, base)
    return RowBlock(offset=blk.offset, label=blk.label, index=blk.index,
                    value=blk.value, field=np.arange(blk.nnz) % n_fields)


@pytest.mark.parametrize("mesh_shape", [None, (4,)], ids=["one", "mesh"])
def test_staging_pipeline_carries_field_ids(mesh_shape):
    """``fields`` rides the packed DMA (one put a batch, or one a device
    on a mesh) and comes out of the unpack with the other arrays; the
    staged bytes count it."""
    import jax
    from jax.sharding import Mesh

    spec = BatchSpec(batch_size=8, layout="ell", max_nnz=4, fields=True)
    blocks = [_fields_block([3, 4, 2, 5, 1, 4], base=6 * i) for i in range(4)]
    want = list(FixedShapeBatcher(spec).batches(iter(blocks)))
    kw = {}
    if mesh_shape is not None:
        kw = {"mesh": Mesh(np.array(jax.devices()[:4]), ("data",)),
              "data_axis": "data"}
    pipe = StagingPipeline(FixedShapeBatcher(spec).batches(iter(blocks)),
                           depth=2, **kw)
    got = list(pipe)
    assert len(got) == len(want) == 3
    for dev, host in zip(got, want):
        assert set(dev) == set(host.as_dict()) and "fields" in dev
        for k, v in host.as_dict().items():
            assert np.asarray(dev[k]).tobytes() == v.tobytes(), k
    st = pipe.staging_stats()
    path = "packed_batches" if mesh_shape is None else "packed_shard_batches"
    assert st[path] == 3 and st["per_array_batches"] == 0
    assert pipe.bytes_staged == sum(
        v.nbytes for b in want for v in b.as_dict().values())
    pipe.close()
