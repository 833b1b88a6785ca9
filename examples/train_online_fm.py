"""Online learning: paced generator → live RecordIO stream → jitted FM.

The writer appends fixed-K rows to a growing stream directory
(stream/writer.py: codec blocks, durable watermark commits, size
rotation); the trainer follows the manifest LIVE through the same
``create()`` factory every sealed dataset uses — windowed shuffle
inside the committed watermark, rotation as an epoch boundary, clean
EOS (docs/streaming.md).

Single process (demo):  python examples/train_online_fm.py
    spawns the generator as a thread and trains while it writes.

Two terminals (real deployment shape):
    python examples/train_online_fm.py --produce /tmp/fm_stream
    python examples/train_online_fm.py /tmp/fm_stream

Multi-worker trainers (tracker-leased micro-shards, exactly-once):
    ./dmlc-submit --cluster local --num-workers 2 \
        python examples/train_online_fm.py /tmp/fm_stream

Env knobs: DMLC_STREAM_MAX_LAG caps how far the writer may run ahead
of the slowest acked reader (docs/streaming.md); DMLC_STREAM_POLL sets
the tail poll cadence.
"""

import os
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

N_FEATURES = 1 << 12
K = 8  # nnz per row, fixed — rows pack to one flat struct
B = 256
_ROW = struct.Struct("<f" + "I" * K + "f" * K)  # label, idx[K], val[K]


def make_row(rng: np.random.Generator, w: np.ndarray) -> bytes:
    idx = rng.integers(0, N_FEATURES, K, dtype=np.uint32)
    val = rng.uniform(0, 1, K).astype(np.float32)
    label = float((w[idx] * val).sum() > 0)
    return _ROW.pack(label, *idx.tolist(), *val.tolist())


def produce(dir_path: str, rows: int = 8000, rows_per_sec: float = 4000.0):
    """The generator: paced appends with periodic durable commits and
    size rotation — each sealed shard is an ordinary indexed RecordIO
    file any offline job can read."""
    from dmlc_core_tpu.stream import StreamWriter

    rng = np.random.default_rng(0)
    w = rng.normal(size=N_FEATURES) / np.sqrt(K)
    chunk = max(1, int(rows_per_sec * 0.01))
    with StreamWriter(
        dir_path, codec="zlib", rotate_bytes=64 << 10, commit_records=200
    ) as writer:
        for i in range(rows):
            writer.append(make_row(rng, w))
            if i % chunk == chunk - 1:
                time.sleep(0.01)
    print(f"producer: {rows} rows appended, stream sealed (EOS)")


def to_batch(rows: list) -> dict:
    """Pack parsed rows into one fixed-shape ELL batch; short tails pad
    with weight 0 (weighted_mean ignores padding)."""
    import jax.numpy as jnp

    n = len(rows)
    idx = np.zeros((B, K), np.int32)
    val = np.zeros((B, K), np.float32)
    lab = np.zeros(B, np.float32)
    wgt = np.zeros(B, np.float32)
    for r, rec in enumerate(rows):
        f = _ROW.unpack(rec)
        lab[r] = f[0]
        idx[r] = f[1 : 1 + K]
        val[r] = f[1 + K :]
        wgt[r] = 1.0
    return {
        "indices": jnp.asarray(idx),
        "values": jnp.asarray(val),
        "labels": jnp.asarray(lab),
        "weights": jnp.asarray(wgt),
    }


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--produce":
        produce(sys.argv[2] if len(sys.argv) > 2 else "/tmp/fm_stream")
        return

    import jax

    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.models import FactorizationMachine

    dir_path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/fm_stream_demo"

    # under dmlc-submit, rendezvous like any dmlc worker; the stream
    # itself is shared — workers pull tracker-leased micro-shards
    worker = None
    if os.environ.get("DMLC_TRACKER_URI"):
        from dmlc_core_tpu.tracker.client import RabitWorker

        worker = RabitWorker()
        rank = worker.start()
    else:
        rank = 0
        if len(sys.argv) < 2:
            # demo mode: nobody is writing yet — spawn the generator
            import shutil
            import threading

            shutil.rmtree(dir_path, ignore_errors=True)
            os.makedirs(dir_path, exist_ok=True)
            threading.Thread(
                target=produce, args=(dir_path,), daemon=True
            ).start()

    model = FactorizationMachine(N_FEATURES, embed_dim=8)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.1))

    # the manifest URI routes create() to a live StreamSource: shuffle
    # happens in aligned windows WITHIN the committed watermark, so the
    # drain is deterministic given (seed, rotation history). Multi-
    # worker follows add &dynamic_shards=1 (leased micro-shards).
    uri = dir_path + "/manifest.json?shuffle=window&window=1024&seed=7"
    if worker is not None:
        uri += "&dynamic_shards=1"
    src = io_split.create(uri, threaded=False)

    seen, gstep, loss = 0, 0, None
    last_gen = 0
    t0 = time.monotonic()
    while True:
        chunk = src.next_batch(B)
        if chunk is None:
            break  # EOS: writer closed and every committed row drained
        rows = list(src.extract_records(chunk))
        params, loss = step(params, to_batch(rows))
        seen += len(rows)
        gstep += 1
        gen = getattr(src, "generation", 0)
        if gen != last_gen:
            # rotation = dataset switch: the sealed shard is final
            print(f"rank {rank}: rotated into generation {gen}")
            last_gen = gen
        if gstep % 10 == 0:
            print(
                f"rank {rank} step {gstep}: loss={float(loss):.4f} "
                f"rows={seen} lag={src.lag_seconds():.2f}s"
            )
    dt = time.monotonic() - t0
    loss_str = "n/a" if loss is None else f"{float(loss):.4f}"
    print(
        f"rank {rank}: stream drained — {seen} rows in {dt:.1f}s "
        f"({seen / max(dt, 1e-9):,.0f} rows/s), final loss={loss_str}"
    )
    src.close()
    if worker is not None:
        worker.shutdown()


if __name__ == "__main__":
    from dmlc_core_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
