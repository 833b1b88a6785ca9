#!/usr/bin/env python3
"""Bring-up smoke of the main path on the chip.

The path is the one in ``examples/train_criteo_rec.py``: an indexed rowrec
RecordIO shard → ``ell_batches`` (fused native ELL kernel) →
``StagingPipeline`` (one u8 DMA per batch, bitcast unpack in HBM) → jitted
``FactorizationMachine.sgd_step``. Criteo layout (13 dense + 26 categorical
fields), 2^24 hashed features, E=16, 2^20 rows from ``--seed``, batch 8192
with f16 values: one 128-step epoch.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # (2, 2) data x model mesh only

Phases, in order; a failing phase exits non-zero and no result is printed:

1. device   JAX must find a TPU (the platform found is named otherwise).
2. native   ``make -C native`` from ``fastparse.cc``; the rowrec→ELL kernel
            must load and match the source hash.
3. data     the indexed shard, written under a temporary directory.
4. train    one chip: the whole epoch, every batch on the packed single-DMA
            path, every row from the fused native producer. Four chips:
            the first 8 batches through the packed-shard path into
            ``data_parallel_step`` with ``v`` sharded on ``model``.
5. compare  one chip: the first 4 batches read back from HBM equal a plain
            ``io/recordio.py`` + ``data/rowrec.py`` decode bit for bit, and
            the first 8 losses match the same steps on the host CPU. Four
            chips: the 8 losses match a single-device run. Every loss is
            finite.

The last line of stdout is the one JSON result. Timings printed on earlier
lines are from one run; they are not a benchmark.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from itertools import islice

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

K = 39  # 13 dense + 26 categorical fields per row
LR = 0.1
CHECK_BATCHES = 4  # staged batches compared bit for bit
CHECK_STEPS = 8  # losses compared against a reference run
RTOL = 1e-3


@dataclass(frozen=True)
class Size:
    rows: int = 1 << 20
    n_features: int = 1 << 24
    embed_dim: int = 16
    batch: int = 8192


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


# -- phase 1 ------------------------------------------------------------------


def check_device(chips: int):
    import jax

    devs = jax.devices()
    require(
        devs[0].platform == "tpu",
        f"JAX found platform {devs[0].platform!r}, not 'tpu'; this smoke "
        "runs only on the chip",
    )
    require(
        len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
        f"JAX found {len(devs)}",
    )
    log(f"device: {devs[0].device_kind}, {len(devs)} device(s)")
    return devs


# -- phase 2 ------------------------------------------------------------------


def build_native() -> None:
    so = os.path.join(REPO, "native", "libdmlc_tpu_native.so")
    before = os.stat(so).st_mtime_ns if os.path.exists(so) else None
    t0 = time.perf_counter()
    from bench import ensure_native

    ensure_native()  # make -C native; refuses a stale or unloadable .so
    from dmlc_core_tpu.data import native

    with open(os.path.join(REPO, "native", "fastparse.cc"), "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()
    require(native.HAS_ELL, "native core has no rowrec->ELL kernel")
    require(
        native.source_hash() == want,
        f"native .so built from {native.source_hash()[:12]!r}, source is "
        f"{want[:12]}",
    )
    built = before is None or os.stat(so).st_mtime_ns != before
    log(
        f"native: {'built' if built else 'already current'} "
        f"({time.perf_counter() - t0:.1f} s), source {want[:12]}"
    )


# -- phase 3 ------------------------------------------------------------------


def write_shard(tmp: str, size: Size, seed: int) -> str:
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from train_criteo_rec import synth

    path = os.path.join(tmp, "criteo.rec")
    t0 = time.perf_counter()
    synth(path, rows=size.rows, n_features=size.n_features, seed=seed)
    log(
        f"data: {size.rows} rows, {os.path.getsize(path) / 1e6:.1f} MB "
        f"({time.perf_counter() - t0:.1f} s)"
    )
    return path


# -- phases 4 and 5 -----------------------------------------------------------


def _spec(size: Size):
    from dmlc_core_tpu.staging import BatchSpec

    return BatchSpec(
        batch_size=size.batch, layout="ell", max_nnz=K,
        value_dtype=np.dtype(np.float16),
    )


def _fused_stream(path: str, size: Size):
    from dmlc_core_tpu.staging import ell_batches
    from dmlc_core_tpu.staging.fused import FusedEllRowRecBatches

    stream = ell_batches(f"{path}?index={path}.idx", _spec(size))
    require(
        isinstance(stream, FusedEllRowRecBatches),
        f"ell_batches gave {type(stream).__name__}, not the fused native "
        "producer",
    )
    return stream


def _model_and_step(size: Size):
    import jax

    from dmlc_core_tpu.models import FactorizationMachine

    model = FactorizationMachine(size.n_features, embed_dim=size.embed_dim)
    return model, jax.jit(lambda p, b: model.sgd_step(p, b, lr=LR))


def _reference_losses(step, init_host, batches, device) -> np.ndarray:
    """The same steps from the same init, run on ``device``."""
    import jax

    from dmlc_core_tpu.staging import device_put

    params = device_put(init_host, device)
    losses = []
    for b in batches:
        params, loss = step(params, device_put(b, device))
        losses.append(loss)
    return np.asarray(jax.device_get(losses), np.float64)


def _check_losses(got: np.ndarray, ref: np.ndarray, what: str) -> None:
    require(np.isfinite(got).all(), f"non-finite loss: {got}")
    require(
        np.allclose(got, ref, rtol=RTOL, atol=0.0),
        f"losses differ from {what} beyond rtol {RTOL}:\n  chip {got}\n"
        f"  ref  {ref}",
    )
    worst = float(np.max(np.abs(got - ref) / np.abs(ref)))
    log(f"compare: {len(got)} losses match {what}, max rel diff {worst:.3g}")


def plain_batches(path: str, size: Size, n: int) -> list:
    """The first ``n`` batches as a plain decode gives them: RecordIO
    frames (io/recordio.py) → rowrec rows (data/rowrec.py) → ELL arrays
    laid out by hand, padded with zeros."""
    from dmlc_core_tpu.data.rowrec import decode_records
    from dmlc_core_tpu.io.recordio import RecordIOReader
    from dmlc_core_tpu.io.stream import FileStream

    B = size.batch
    with FileStream(path, "r") as f:
        blk = decode_records(islice(RecordIOReader(f), n * B))
    out = []
    for i in range(n):
        lo, hi = i * B, min((i + 1) * B, blk.size)
        nnz = np.zeros(B, np.int32)
        nnz[: hi - lo] = np.minimum(np.diff(blk.offset[lo : hi + 1]), K)
        indices = np.zeros((B, K), np.int32)
        values = np.zeros((B, K), np.float16)
        labels = np.zeros(B, np.float32)
        weights = np.zeros(B, np.float32)
        for r in range(hi - lo):
            s = int(blk.offset[lo + r])
            m = int(nnz[r])
            indices[r, :m] = blk.index[s : s + m]
            values[r, :m] = blk.value[s : s + m]
        labels[: hi - lo] = blk.label[lo:hi]
        weights[: hi - lo] = blk.weight[lo:hi]
        out.append({
            "indices": indices, "values": values, "nnz": nnz,
            "labels": labels, "weights": weights,
        })
    return out


def _check_bits(staged: list, plain: list) -> None:
    for i, (got, want) in enumerate(zip(staged, plain)):
        require(
            sorted(got) == sorted(want),
            f"batch {i} has arrays {sorted(got)}, expected {sorted(want)}",
        )
        for name, w in want.items():
            g = np.asarray(got[name])
            require(
                g.dtype == w.dtype and g.shape == w.shape,
                f"batch {i} {name}: {g.dtype}{g.shape}, expected "
                f"{w.dtype}{w.shape}",
            )
            require(
                g.tobytes() == w.tobytes(),
                f"batch {i} {name}: staged bytes differ from the plain "
                "decode",
            )
    log(f"compare: {len(staged)} staged batches bit-identical to the plain "
        "decode")


def run_one_chip(path: str, size: Size, seed: int, device) -> None:
    import jax

    from dmlc_core_tpu.staging import StagingPipeline, drain_close

    model, step = _model_and_step(size)
    params = model.init(jax.random.PRNGKey(seed))
    init_host = jax.device_get(params)
    stream = _fused_stream(path, size)
    t0 = time.perf_counter()
    pipe = StagingPipeline(stream)
    losses, kept, compile_s = [], [], 0.0
    try:
        for i, batch in enumerate(pipe):
            if i < CHECK_STEPS:
                kept.append(jax.device_get(batch))
            if i == 0:
                tc = time.perf_counter()
                compiled = step.lower(params, batch).compile()
                compile_s = time.perf_counter() - tc
            params, loss = compiled(params, batch)
            losses.append(loss)
        jax.block_until_ready((params, losses))
        epoch_s = time.perf_counter() - t0 - compile_s
        staging = pipe.staging_stats()
        rows_staged = pipe.rows_staged
    finally:
        drain_close(pipe, stream)
    losses = np.asarray(jax.device_get(losses), np.float64)
    steps = len(losses)
    mem = device.memory_stats() or {}
    log(f"train: rows {rows_staged}, steps {steps}, loss first "
        f"{losses[0]:.6f} last {losses[-1]:.6f}")
    log(f"train: compile {compile_s:.2f} s, epoch {epoch_s:.2f} s "
        f"(excluding compile), peak_bytes_in_use "
        f"{mem.get('peak_bytes_in_use', 'not reported')}")
    log(f"train: staging {json.dumps(staging, sort_keys=True)}")
    require(steps == -(-size.rows // size.batch),
            f"{steps} steps for {size.rows} rows at batch {size.batch}")
    require(rows_staged == size.rows, f"staged {rows_staged} rows")
    require(
        stream.rows_out == size.rows,
        f"fused producer emitted {stream.rows_out} of {size.rows} rows",
    )
    require(
        staging["packed_batches"] == steps
        and staging["per_array_batches"] == 0,
        "not every batch took the packed single-DMA path",
    )
    require(np.isfinite(losses).all(), "non-finite loss in the epoch")

    _check_bits(kept[:CHECK_BATCHES], plain_batches(path, size, CHECK_BATCHES))
    ref = _reference_losses(step, init_host, kept, jax.devices("cpu")[0])
    _check_losses(losses[:CHECK_STEPS], ref, "the host CPU")


def run_four_chips(path: str, size: Size, seed: int, devices) -> None:
    import jax
    from jax.sharding import PartitionSpec as P

    from dmlc_core_tpu.parallel import data_parallel_step, make_mesh
    from dmlc_core_tpu.staging import StagingPipeline, drain_close

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    log(f"mesh: {dict(mesh.shape)}, device ids "
        f"{mesh.device_ids.tolist()}")
    model, one_step = _model_and_step(size)
    step = data_parallel_step(
        lambda p, b: model.sgd_step(p, b, lr=LR), mesh,
        param_rules={"v": P(None, "model")}, donate_params=False,
    )
    params = model.init(jax.random.PRNGKey(seed))
    init_host = jax.device_get(params)
    stream = _fused_stream(path, size)
    t0 = time.perf_counter()
    pipe = StagingPipeline(stream, mesh=mesh, data_axis="data")
    losses, kept = [], []
    try:
        for batch in islice(pipe, CHECK_STEPS):
            kept.append(jax.device_get(batch))
            params, loss = step(params, batch)
            losses.append(loss)
        jax.block_until_ready((params, losses))
        secs = time.perf_counter() - t0
        staging = pipe.staging_stats()
    finally:
        drain_close(pipe, stream)
    losses = np.asarray(jax.device_get(losses), np.float64)
    v = params["v"]
    v_devices = {s.device for s in v.addressable_shards}
    log(f"train: {len(losses)} steps in {secs:.2f} s (compile included), "
        f"loss first {losses[0]:.6f} last {losses[-1]:.6f}")
    log(f"train: v sharding {v.sharding.spec} on "
        f"{sorted(d.id for d in v_devices)}, shard shape "
        f"{v.addressable_shards[0].data.shape}")
    log(f"train: staging {json.dumps(staging, sort_keys=True)}")
    require(len(losses) == CHECK_STEPS, f"only {len(losses)} batches")
    require(
        staging["packed_shard_dma"] is True
        and staging["packed_shard_batches"] >= CHECK_STEPS
        and staging["per_array_batches"] == 0,
        "not every batch took the packed-shard path",
    )
    require(v.sharding.spec == P(None, "model"),
            f"v is sharded {v.sharding.spec}")
    require(len(v_devices) == 4, f"v's shards sit on {len(v_devices)} "
            "devices, not 4")
    ref = _reference_losses(one_step, init_host, kept, devices[0])
    _check_losses(losses, ref, f"one device ({devices[0].id})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = check_device(args.chips)
    from dmlc_core_tpu.utils.compile_cache import place_compile_cache

    log(f"compile cache: {place_compile_cache()}")
    build_native()
    size = Size()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = write_shard(tmp, size, args.seed)
        if args.chips == 4:
            run_four_chips(path, size, args.seed, devices)
        else:
            run_one_chip(path, size, args.seed, devices[0])
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
