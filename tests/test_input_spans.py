"""Where the input path's spans land in a profiler trace.

A ``StagingPipeline`` over the fused producers runs under
``jax.profiler`` on the CPU; the host plane is read back with
``ProfileData``. Host lines carry no thread names, so each line is told
by the span only it carries: the consumer's line by ``dmlc:pipeline_open``
(the pipeline is built on the test's thread), the transfer thread's by
``dmlc:host_pull``, the ring workers' by ``dmlc:ring_dispatch``, the
producers' by ``dmlc:parse``.
"""

import collections
import glob
import os

import numpy as np
import pytest

from dmlc_core_tpu.staging import (
    BatchSpec,
    StagingPipeline,
    dense_batches,
    drain_close,
    ell_batches,
)

pytestmark = pytest.mark.jax

N_ROWS = 1000
BATCH = 64
N_BATCHES = -(-N_ROWS // BATCH)


def _write_csv(path, rng):
    x = rng.normal(size=(N_ROWS, 6))
    with open(path, "w") as f:
        for i, row in enumerate(x):
            f.write("%d,%s\n" % (i % 2, ",".join("%.6f" % v for v in row)))


def _write_indexed_rowrec(rec, idx, rng):
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.io.stream import FileStream

    k = 4
    blk = RowBlock(
        offset=np.arange(N_ROWS + 1, dtype=np.int64) * k,
        label=(np.arange(N_ROWS) % 2).astype(np.float32),
        index=rng.integers(0, 64, N_ROWS * k).astype(np.uint32),
        value=rng.normal(size=N_ROWS * k).astype(np.float32),
    )
    with FileStream(rec, "w") as d, FileStream(idx, "w") as i:
        write_rowrec(d, [blk], index_stream=i)


def _traced_epoch(open_stream, trace_dir):
    """One epoch staged and drained under the profiler: {line: [(start,
    end, name)]} of the host plane's ``dmlc:*`` spans, and the batches."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        stream = open_stream()
        pipe = StagingPipeline(stream)
        n = sum(1 for _ in pipe)
        drain_close(pipe, stream)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events if e.name.startswith("dmlc:")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines, n


def _lines_with(lines, name):
    return {k for k, evs in lines.items() if any(n == name for *_, n in evs)}


def _count(lines, name, on=None):
    return sum(1 for k, evs in lines.items() if on is None or k in on
               for *_, n in evs if n == name)


def _check_placement(lines, n_batches, producers_lazy_open):
    (consumer,) = _lines_with(lines, "dmlc:pipeline_open")
    (transfer,) = _lines_with(lines, "dmlc:host_pull")
    ring = _lines_with(lines, "dmlc:ring_dispatch")
    producer = _lines_with(lines, "dmlc:parse")
    assert consumer != transfer
    assert not ring & {consumer, transfer}
    assert not producer & ({consumer, transfer} | ring)
    # the loop: one queue wait per batch and one for the end of the
    # epoch, beside (not inside) the transfer wait it always had
    assert _lines_with(lines, "dmlc:xfer_queue_wait") == {consumer}
    assert _count(lines, "dmlc:xfer_queue_wait") == n_batches + 1
    assert _count(lines, "dmlc:transfer_wait") == n_batches
    assert _count(lines, "dmlc:pipeline_open") == 1
    assert _lines_with(lines, "dmlc:pipeline_close") == {consumer}
    # ring workers: one dispatch per batch, its DMA and unpack call
    # nested inside it on the same line
    for name in ("dmlc:ring_dispatch", "dmlc:dma_put", "dmlc:unpack_call"):
        assert _lines_with(lines, name) <= ring
        assert _count(lines, name) == n_batches
    for k in ring:
        outer = [(s, e) for s, e, n in lines[k] if n == "dmlc:ring_dispatch"]
        for s, e, n in lines[k]:
            if n in ("dmlc:dma_put", "dmlc:unpack_call"):
                assert any(a <= s and e <= b for a, b in outer), (n, s, e)
    # producers: every fetch and parse on a producer thread; at least one
    # native call per batch, one read per chunk plus the end of the split
    assert _lines_with(lines, "dmlc:fetch") <= producer
    assert n_batches <= _count(lines, "dmlc:parse") <= 2 * n_batches
    assert _count(lines, "dmlc:fetch") >= 2 * len(producer)
    # constructors on the loop's thread, a lazy split open on the producer's
    opened = collections.Counter(
        k for k, evs in lines.items() for *_, n in evs
        if n == "dmlc:producer_open")
    assert opened[consumer] == len(producer)
    assert sum(opened[k] for k in producer) == (
        len(producer) if producers_lazy_open else 0)
    assert set(opened) <= {consumer} | producer


def test_csv_fan_out_spans(tmp_path):
    _write_csv(tmp_path / "a.csv", np.random.default_rng(1))
    spec = BatchSpec(batch_size=BATCH, layout="dense", num_features=6,
                     value_dtype=np.dtype(np.float32))
    uri = f"{tmp_path / 'a.csv'}?format=csv&label_column=0"
    lines, n = _traced_epoch(lambda: dense_batches(uri, spec, nthread=2),
                             tmp_path / "trace")
    assert N_BATCHES <= n <= N_BATCHES + 1  # one padded tail per sub-shard
    assert len(_lines_with(lines, "dmlc:parse")) == 2
    _check_placement(lines, n, producers_lazy_open=True)


@pytest.mark.parametrize("shuffle", ["", "&shuffle=record&seed=5"])
def test_rowrec_spans(tmp_path, shuffle):
    rec, idx = str(tmp_path / "d.rec"), str(tmp_path / "d.idx")
    _write_indexed_rowrec(rec, idx, np.random.default_rng(2))
    spec = BatchSpec(batch_size=BATCH, layout="ell", max_nnz=4,
                     value_dtype=np.dtype(np.float16))
    lines, n = _traced_epoch(
        lambda: ell_batches(f"{rec}?index={idx}{shuffle}", spec),
        tmp_path / "trace")
    assert n == N_BATCHES
    assert len(_lines_with(lines, "dmlc:parse")) == 1
    _check_placement(lines, n, producers_lazy_open=False)
