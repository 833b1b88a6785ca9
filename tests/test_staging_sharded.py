"""Packed-shard mesh staging (ISSUE 3 tentpole): the coalesced
one-DMA-per-device path must be BIT-IDENTICAL to the per-array
``NamedSharding`` path for every parser family — dense libsvm, csv,
rowrec ELL, libfm ELL — including padded tail batches where
``ntotal % world != 0``. Plus the satellites that guard it: the
unpacker-cache LRU, the non-contiguous-view layout rejection, and the
usable-CPU autodetect the parse pools size from.

Runs on the virtual 8-device CPU mesh (conftest sets
XLA_FLAGS/JAX_PLATFORMS).
"""

import os

import numpy as np
import pytest

from dmlc_core_tpu.staging import (
    Batch,
    BatchSpec,
    FixedShapeBatcher,
    StagingPipeline,
    StagingStats,
    dense_batches,
    drain_close,
    ell_batches,
    stage_batch,
)
from dmlc_core_tpu.staging.pipeline import (
    _packed_layout,
    _stage_per_array_mesh,
    unpack_cache_stats,
)

pytestmark = pytest.mark.jax

# 16 rows/batch over a 4-way data axis → 4 rows per shard; N_ROWS=41
# leaves a 9-valid-row padded tail batch (41 % 16 = 9, and 41 is odd
# against every world size in play — the ntotal % world != 0 case)
BATCH_ROWS = 16
N_ROWS = 41


def _mesh(shape=(4, 2), axes=("data", "model")):
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, axes)


def _write_libsvm(path, rng):
    with open(path, "w") as f:
        for i in range(N_ROWS):
            feats = " ".join(
                f"{j}:{rng.normal():.6f}" for j in range(6)
            )
            f.write(f"{i % 2} {feats}\n")


def _write_csv(path, rng):
    with open(path, "w") as f:
        for i in range(N_ROWS):
            f.write(
                "%d,%s\n"
                % (i % 2, ",".join(f"{rng.normal():.6f}" for _ in range(6)))
            )


def _write_libfm(path, rng):
    with open(path, "w") as f:
        for i in range(N_ROWS):
            toks = " ".join(
                f"{j}:{j * 3 + 1}:{rng.uniform():.6f}" for j in range(4)
            )
            f.write(f"{i % 2} {toks}\n")


def _write_rowrec(path, rng):
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.io.stream import FileStream

    k = 4
    offset = np.arange(N_ROWS + 1, dtype=np.int64) * k
    blk = RowBlock(
        offset=offset,
        label=(np.arange(N_ROWS) % 2).astype(np.float32),
        index=rng.integers(0, 32, N_ROWS * k).astype(np.uint32),
        value=rng.normal(size=N_ROWS * k).astype(np.float32),
    )
    with FileStream(path, "w") as f:
        write_rowrec(f, [blk])


def _streams(tmp_path, value_dtype=np.float32):
    """One (name, batch stream) per parser family; every batch carries
    ``packed`` whichever producer (fused native or generic) serves it."""
    rng = np.random.default_rng(5)
    out = []
    dense_spec = BatchSpec(
        batch_size=BATCH_ROWS, layout="dense", num_features=8,
        value_dtype=np.dtype(value_dtype),
    )
    ell_spec = BatchSpec(
        batch_size=BATCH_ROWS, layout="ell", max_nnz=4,
        value_dtype=np.dtype(value_dtype),
    )
    p = tmp_path / "g.libsvm"
    _write_libsvm(p, rng)
    out.append(("libsvm_dense", dense_batches(str(p), dense_spec)))
    p = tmp_path / "g.csv"
    _write_csv(p, rng)
    out.append(
        (
            "csv_dense",
            dense_batches(str(p) + "?format=csv&label_column=0", dense_spec),
        )
    )
    p = tmp_path / "g.rec"
    _write_rowrec(p, rng)
    out.append(("rowrec_ell", ell_batches(str(p), ell_spec)))
    p = tmp_path / "g.libfm"
    _write_libfm(p, rng)
    out.append(
        ("libfm_ell", ell_batches(str(p) + "?format=libfm", ell_spec))
    )
    return out


@pytest.mark.parametrize("mesh_shape,axes", [
    ((4, 2), ("data", "model")),   # the dryrun's 2-D dp×tp mesh
    ((8,), ("data",)),             # plain 8-way data parallel
])
def test_packed_shard_golden_equivalence(tmp_path, mesh_shape, axes):
    """Every parser family, every batch (padded tail included): the
    packed-shard path must produce bit-identical device values AND
    identical shardings to the per-array NamedSharding path."""
    mesh = _mesh(mesh_shape, axes)
    for name, stream in _streams(tmp_path):
        n_batches = 0
        rows = 0
        for batch in stream:
            assert batch.packed is not None, name
            stats = StagingStats()
            dev = stage_batch(batch, mesh=mesh, data_axis="data",
                              stats=stats)
            assert stats.packed_shard_dma is True, name
            # ONE u8 put per addressable device, never per array
            assert stats.device_puts == len(mesh.devices.flat), name
            ref = _stage_per_array_mesh(batch, mesh, "data", None)
            assert set(dev) == set(ref), name
            for k in ref:
                assert dev[k].dtype == ref[k].dtype, (name, k)
                assert dev[k].shape == ref[k].shape, (name, k)
                assert dev[k].sharding == ref[k].sharding, (name, k)
                np.testing.assert_array_equal(
                    np.asarray(dev[k]), np.asarray(ref[k]), err_msg=f"{name}:{k}"
                )
            n_batches += 1
            rows += batch.n_valid
        stream.close()
        assert rows == N_ROWS, name
        # 41 rows / 16-row batches → 3 batches, last one padded
        assert n_batches == 3, name


def test_generic_batcher_packs_and_matches_per_array(tmp_path):
    """The generic FixedShapeBatcher output (no native kernels in the
    loop at all) rides the packed-shard path too — f16 values included
    (odd itemsize against the 8-byte section alignment)."""
    from dmlc_core_tpu.data.row_block import RowBlock

    mesh = _mesh((8,), ("data",))
    spec = BatchSpec(
        batch_size=BATCH_ROWS, layout="ell", max_nnz=3,
        value_dtype=np.dtype(np.float16),
    )
    b = FixedShapeBatcher(spec)
    sizes = [2] * 19  # 19 rows → one full batch + padded tail of 3
    offset = np.zeros(len(sizes) + 1, np.int64)
    np.cumsum(sizes, out=offset[1:])
    blk = RowBlock(
        offset=offset,
        label=np.arange(len(sizes), dtype=np.float32),
        index=(np.arange(int(offset[-1]), dtype=np.uint64) % 16),
        value=np.linspace(1, 2, int(offset[-1]), dtype=np.float32),
    )
    batches = list(b.batches(iter([blk])))
    assert [x.n_valid for x in batches] == [16, 3]
    for batch in batches:
        assert batch.packed is not None
        dev = stage_batch(batch, mesh=mesh, data_axis="data")
        ref = _stage_per_array_mesh(batch, mesh, "data", None)
        for k in ref:
            assert dev[k].sharding == ref[k].sharding, k
            np.testing.assert_array_equal(
                np.asarray(dev[k]), np.asarray(ref[k]), err_msg=k
            )


def test_pipeline_mesh_packed_shard_stats(tmp_path):
    """End-to-end through StagingPipeline: the dispatch ring stages a
    mesh stream via the packed-shard path and the counters say so."""
    rng = np.random.default_rng(9)
    p = tmp_path / "p.rec"
    _write_rowrec(p, rng)
    spec = BatchSpec(batch_size=BATCH_ROWS, layout="ell", max_nnz=4)
    stream = ell_batches(str(p), spec)
    mesh = _mesh((4, 2), ("data", "model"))
    pipe = StagingPipeline(stream, mesh=mesh, data_axis="data")
    labels = []
    for dev in pipe:
        w = np.asarray(dev["weights"])
        labels.extend(np.asarray(dev["labels"])[w > 0].tolist())
    assert len(labels) == N_ROWS
    st = pipe.staging_stats()
    assert st["packed_shard_dma"] is True
    assert st["packed_shard_batches"] == 3
    assert st["per_array_batches"] == 0
    assert st["device_puts"] == 3 * 8
    assert st["dispatch_ring_depth"] >= 1
    assert pipe.io_stats()["staging"]["packed_shard_dma"] is True
    secs = pipe.stage_seconds
    assert "stage_dispatch" not in secs
    assert secs["dispatch_pack"] > 0 and secs["dispatch_put"] > 0
    drain_close(pipe, stream)


def test_shard_unpacker_compiles_collective_free(tmp_path):
    """The per-shard unpack must contain ZERO collectives: ring workers
    execute unpacks concurrently, and on backends with rendezvous-based
    collectives two concurrent collective computations deadlock (seen
    live on the CPU backend before the shard_map rewrite — a plain jit
    with pinned shardings let GSPMD insert an all-gather for the
    shard-splitting reshape)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dmlc_core_tpu.staging.pipeline import _shard_plan, _shard_unpacker

    rng = np.random.default_rng(3)
    p = tmp_path / "c.rec"
    _write_rowrec(p, rng)
    spec = BatchSpec(
        batch_size=BATCH_ROWS, layout="ell", max_nnz=4,
        value_dtype=np.dtype(np.float16),
    )
    stream = ell_batches(str(p), spec)
    batch = next(iter(stream))
    mesh = _mesh((4, 2), ("data", "model"))
    entries, stride, n_shards = _shard_plan(batch, mesh, "data")
    fn = _shard_unpacker(entries, stride, mesh, "data", "cpu")
    aval = jax.ShapeDtypeStruct(
        (n_shards * stride,), np.uint8,
        sharding=NamedSharding(mesh, P("data")),
    )
    hlo = fn.lower(aval).compile().as_text()
    for op in ("all-gather", "all-reduce", "collective-permute",
               "all-to-all"):
        assert op not in hlo, f"unpacker compiled a {op}"
    stream.close()


def test_non_divisible_batch_falls_back_per_array():
    """batch_size % n_shards != 0 can't ride the packed-shard path; the
    plan must reject it (the per-array path then fails the same way a
    direct NamedSharding put would — that contract is unchanged)."""
    from dmlc_core_tpu.staging.pipeline import _shard_plan

    mesh = _mesh((4, 2), ("data", "model"))
    spec = BatchSpec(batch_size=6, layout="dense", num_features=4)
    b = FixedShapeBatcher(spec)
    from dmlc_core_tpu.data.row_block import RowBlock

    blk = RowBlock(
        offset=np.arange(7, dtype=np.int64),
        label=np.zeros(6, np.float32),
        index=np.arange(6, dtype=np.uint64) % 4,
        value=np.ones(6, np.float32),
    )
    (batch,) = list(b.push(blk))
    assert batch.packed is not None
    assert _shard_plan(batch, mesh, "data") is None
    # unknown data axis also refuses (falls back instead of KeyError)
    assert _shard_plan(batch, mesh, "nope") is None


# -- satellite: _packed_layout contiguity guard ------------------------------


def _manual_packed_batch(reverse_labels=False):
    """Dense Batch whose arrays are hand-built views into one buffer;
    optionally with a reversed (negative-stride) labels view whose
    byte_bounds still lie inside the buffer."""
    nb = 16 * 2 * 4 + 16 * 4 + 16 * 4  # x[16,2] f32 | labels | weights
    buf = np.zeros(nb, dtype=np.uint8)
    x = buf[: 16 * 2 * 4].view(np.float32).reshape(16, 2)
    labels = buf[16 * 2 * 4 : 16 * 2 * 4 + 16 * 4].view(np.float32)
    weights = buf[16 * 2 * 4 + 16 * 4 :].view(np.float32)
    x[:] = np.arange(32).reshape(16, 2)
    labels[:] = np.arange(16)
    weights[:] = 1.0
    if reverse_labels:
        labels = labels[::-1]
    return Batch(labels=labels, weights=weights, n_valid=16, x=x,
                 packed=buf)


def test_packed_layout_rejects_negative_stride_views():
    """byte_bounds passes for a reversed view whose bytes are NOT the
    dense run [off, off+nbytes) — bitcasting it would stage garbage.
    The layout derivation must reject and force the per-array path."""
    good = _manual_packed_batch()
    assert _packed_layout(good) is not None
    bad = _manual_packed_batch(reverse_labels=True)
    assert not bad.labels.flags.c_contiguous
    assert _packed_layout(bad) is None


def test_packed_layout_rejects_noncontiguous_packed():
    batch = _manual_packed_batch()
    object.__setattr__(batch, "packed", batch.packed[::-1])
    assert _packed_layout(batch) is None


def test_packed_layout_accepts_dense_views():
    layout = _packed_layout(_manual_packed_batch())
    assert layout is not None
    assert {e[0] for e in layout} == {"x", "labels", "weights"}


def test_strided_view_batch_still_stages_correctly():
    """A batch whose arrays are NOT dense views (sliced with a step)
    must stage through the per-array path with correct values."""
    bad = _manual_packed_batch(reverse_labels=True)
    dev = stage_batch(bad)
    np.testing.assert_array_equal(
        np.asarray(dev["labels"]), bad.labels
    )


# -- satellite: unpacker-cache LRU -------------------------------------------


def test_unpack_cache_lru_bounds_and_evicts(monkeypatch, tmp_path):
    monkeypatch.setenv("DMLC_UNPACK_CACHE", "2")
    before = unpack_cache_stats()["unpack_cache_evictions"]
    # distinct layouts (distinct batch shapes) mint distinct unpackers
    for nf in (3, 5, 7, 9, 11):
        spec = BatchSpec(batch_size=8, layout="dense", num_features=nf)
        b = FixedShapeBatcher(spec)
        from dmlc_core_tpu.data.row_block import RowBlock

        blk = RowBlock(
            offset=np.arange(9, dtype=np.int64),
            label=np.zeros(8, np.float32),
            index=np.zeros(8, np.uint64),
            value=np.ones(8, np.float32),
        )
        (batch,) = list(b.push(blk))
        dev = stage_batch(batch)
        assert np.asarray(dev["x"]).shape == (8, nf)
    stats = unpack_cache_stats()
    assert stats["unpack_cache_capacity"] == 2
    assert stats["unpack_cache_size"] <= 2
    assert stats["unpack_cache_evictions"] >= before + 3
    # a re-staged layout still works after eviction (re-jits, same math)
    spec = BatchSpec(batch_size=8, layout="dense", num_features=3)
    b = FixedShapeBatcher(spec)
    from dmlc_core_tpu.data.row_block import RowBlock

    blk = RowBlock(
        offset=np.arange(9, dtype=np.int64),
        label=np.arange(8, dtype=np.float32),
        index=np.zeros(8, np.uint64),
        value=np.ones(8, np.float32),
    )
    (batch,) = list(b.push(blk))
    dev = stage_batch(batch)
    np.testing.assert_array_equal(
        np.asarray(dev["labels"]), np.arange(8, dtype=np.float32)
    )


# -- satellite: usable-CPU autodetect ----------------------------------------


def test_available_cpus_floor_and_cap():
    from dmlc_core_tpu.utils.cpus import available_cpus

    n = available_cpus()
    assert 1 <= n <= (os.cpu_count() or 1)


def test_parse_threads_env_override(monkeypatch):
    from dmlc_core_tpu.utils import cpus

    monkeypatch.setenv("DMLC_PARSE_THREADS", "3")
    assert cpus.parse_threads() == 3
    assert cpus.parse_threads(16) == 3
    monkeypatch.delenv("DMLC_PARSE_THREADS")
    # legacy alias honored here too, so the override is consistent
    # across every pool sized through parse_threads (bench, fused
    # fan-out, generic text parser)
    monkeypatch.setenv("DMLC_TPU_PARSER_THREADS", "5")
    assert cpus.parse_threads() == 5
    monkeypatch.delenv("DMLC_TPU_PARSER_THREADS")
    monkeypatch.setattr(cpus, "available_cpus", lambda: 4)
    assert cpus.parse_threads() == 4
    assert cpus.parse_threads(2) == 2
    assert cpus.parse_threads(99) == 4


def _pin_proc_cgroup(monkeypatch, tmp_path, text):
    from dmlc_core_tpu.utils import cpus

    proc = tmp_path / "proc_self_cgroup"
    proc.write_text(text)
    monkeypatch.setattr(cpus, "_PROC_SELF_CGROUP", str(proc))


def test_cgroup_quota_parsing(monkeypatch, tmp_path):
    from dmlc_core_tpu.utils import cpus

    _pin_proc_cgroup(monkeypatch, tmp_path, "0::/\n")
    v2 = tmp_path / "cpu.max"
    v2.write_text("150000 100000\n")
    monkeypatch.setattr(cpus, "_CGROUP_V2_CPU_MAX", str(v2))
    assert cpus.cgroup_cpu_quota() == pytest.approx(1.5)
    v2.write_text("max 100000\n")
    assert cpus.cgroup_cpu_quota() is None
    # v1 fallback when the v2 file is absent
    monkeypatch.setattr(cpus, "_CGROUP_V2_CPU_MAX", str(tmp_path / "nope"))
    q = tmp_path / "cpu.cfs_quota_us"
    p = tmp_path / "cpu.cfs_period_us"
    q.write_text("50000\n")
    p.write_text("100000\n")
    monkeypatch.setattr(cpus, "_CGROUP_V1_QUOTA", str(q))
    monkeypatch.setattr(cpus, "_CGROUP_V1_PERIOD", str(p))
    assert cpus.cgroup_cpu_quota() == pytest.approx(0.5)
    q.write_text("-1\n")
    assert cpus.cgroup_cpu_quota() is None


def test_cgroup_quota_found_in_own_nonroot_cgroup(monkeypatch, tmp_path):
    """Non-namespaced containers (docker --cgroupns=host, systemd
    CPUQuota slices): the quota lives at the PROCESS's cgroup path, not
    the root — /proc/self/cgroup must be consulted, and the effective
    limit is the min over the ancestor chain."""
    from dmlc_core_tpu.utils import cpus

    _pin_proc_cgroup(
        monkeypatch, tmp_path, "0::/kube.slice/pod7/container3\n"
    )
    root = tmp_path / "cg2"
    leaf = root / "kube.slice" / "pod7" / "container3"
    leaf.mkdir(parents=True)
    monkeypatch.setattr(cpus, "_CGROUP_V2_CPU_MAX", str(root / "cpu.max"))
    (leaf / "cpu.max").write_text("200000 100000\n")
    assert cpus.cgroup_cpu_quota() == pytest.approx(2.0)
    # a tighter ancestor quota wins (effective = min over the chain)
    (root / "kube.slice" / "cpu.max").write_text("50000 100000\n")
    assert cpus.cgroup_cpu_quota() == pytest.approx(0.5)
    # v1 hierarchy resolution too
    monkeypatch.setattr(cpus, "_CGROUP_V2_CPU_MAX", str(tmp_path / "no2"))
    _pin_proc_cgroup(
        monkeypatch, tmp_path,
        "4:cpu,cpuacct:/docker/abc\n0::/other\n",
    )
    v1root = tmp_path / "cg1"
    d = v1root / "docker" / "abc"
    d.mkdir(parents=True)
    (d / "cpu.cfs_quota_us").write_text("25000\n")
    (d / "cpu.cfs_period_us").write_text("100000\n")
    monkeypatch.setattr(
        cpus, "_CGROUP_V1_QUOTA", str(v1root / "cpu.cfs_quota_us")
    )
    monkeypatch.setattr(
        cpus, "_CGROUP_V1_PERIOD", str(v1root / "cpu.cfs_period_us")
    )
    assert cpus.cgroup_cpu_quota() == pytest.approx(0.25)


def test_fractional_quota_still_gets_one_thread(monkeypatch):
    from dmlc_core_tpu.utils import cpus

    monkeypatch.setattr(cpus, "cgroup_cpu_quota", lambda: 0.4)
    assert cpus.available_cpus() >= 1


def test_shuffled_gather_batches_ride_packed_shard_dma(tmp_path):
    """ISSUE 6 acceptance: shuffled batches (gather fast path) land on
    the packed-shard mesh path — packed_shard_dma latches True, one u8
    put per addressable device, zero per-array fallbacks — with device
    values bit-identical to the legacy per-record shuffle staged the
    same way."""
    from dmlc_core_tpu.io.recordio import IndexedRecordIOWriter
    from dmlc_core_tpu.io.stream import FileStream
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import encode_rows

    rng = np.random.default_rng(13)
    k = 4
    blk = RowBlock(
        offset=np.arange(N_ROWS + 1, dtype=np.int64) * k,
        label=(np.arange(N_ROWS) % 2).astype(np.float32),
        index=rng.integers(0, 32, N_ROWS * k).astype(np.uint32),
        value=rng.normal(size=N_ROWS * k).astype(np.float32),
    )
    rec = str(tmp_path / "sh.rec")
    idx = str(tmp_path / "sh.idx")
    with FileStream(rec, "w") as d, FileStream(idx, "w") as i:
        w = IndexedRecordIOWriter(d, i)
        for payload in encode_rows(blk):
            w.write_record(payload)
    spec = BatchSpec(batch_size=BATCH_ROWS, layout="ell", max_nnz=k)
    mesh = _mesh((4, 2), ("data", "model"))

    def staged(sugar=""):
        stream = ell_batches(
            f"{rec}?index={idx}&shuffle=record&seed=3{sugar}", spec
        )
        pipe = StagingPipeline(stream, mesh=mesh, data_axis="data")
        out = [
            {kk: np.asarray(v) for kk, v in dev.items()} for dev in pipe
        ]
        st = pipe.staging_stats()
        io = pipe.io_stats()
        drain_close(pipe, stream)
        return out, st, io

    got, st, io = staged()
    assert st["packed_shard_dma"] is True
    assert st["per_array_batches"] == 0
    assert st["packed_shard_batches"] == 3
    assert st["device_puts"] == 3 * 8  # one u8 DMA per device per batch
    assert io.get("gather_batches", 0) > 0
    assert io.get("gather_fallback_batches") == 0
    ref, _st, _io = staged("&legacy_shuffle=1")
    assert len(got) == len(ref) == 3
    for a, b in zip(got, ref):
        assert set(a) == set(b)
        for key in b:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
