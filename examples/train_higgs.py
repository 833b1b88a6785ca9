"""End-to-end: libsvm file → sharded parse → fixed-shape batches → TPU →
jitted logistic regression, with checkpointing.

Single host:   python examples/train_higgs.py /path/to/data.libsvm
Multi-process: launch via dmlc-submit (each rank reads its shard):
    ./dmlc-submit --cluster local --num-workers 2 \
        python examples/train_higgs.py /path/to/data.libsvm

Generates a small synthetic file when no path is given.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def synth(path: str, rows: int = 20000, d: int = 28) -> None:
    rng = np.random.default_rng(0)
    w = rng.normal(size=d)
    with open(path, "w") as f:
        for _ in range(rows):
            x = rng.normal(size=d)
            y = int(x @ w > 0)
            feats = " ".join(f"{j}:{x[j]:.5f}" for j in range(d))
            f.write(f"{y} {feats}\n")


def main() -> None:
    import jax

    from dmlc_core_tpu import data as D
    from dmlc_core_tpu.checkpoint import Checkpointer
    from dmlc_core_tpu.models import LogisticRegression
    from dmlc_core_tpu.staging import (
        BatchSpec,
        FixedShapeBatcher,
        StagingPipeline,
        drain_close,
    )

    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/higgs_demo.libsvm"
    if not os.path.exists(path):
        print(f"generating synthetic data at {path}")
        synth(path)

    # shard by worker rank when launched through dmlc-submit
    rank = int(os.environ.get("DMLC_TASK_ID", 0))
    world = int(os.environ.get("DMLC_NUM_WORKER", 1))
    d = 29
    model = LogisticRegression(num_features=d)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.5))
    ck = Checkpointer("/tmp/higgs_ckpts", keep=2, process_index=rank)

    spec = BatchSpec(batch_size=1024, layout="dense", num_features=d)
    for epoch in range(3):
        parser = D.create_parser(path, rank, world, type="libsvm")
        pipe = StagingPipeline(
            FixedShapeBatcher(spec).batches(iter(parser))
        )
        loss = None
        for batch in pipe:
            params, loss = step(params, batch)
        stats = pipe.throughput()
        loss_str = "n/a (empty shard)" if loss is None else f"{float(loss):.4f}"
        print(
            f"rank {rank} epoch {epoch}: loss={loss_str} "
            f"({stats['rows_per_sec']:,.0f} rows/s into device)"
        )
        # pipeline first, source second — and only when the teardown
        # join completed (close_timed_out): an orphaned producer thread
        # may still be reading the parser's buffers
        drain_close(pipe, parser)
        ck.save(epoch, params)
    print("latest checkpoint step:", ck.latest_step())


if __name__ == "__main__":
    from dmlc_core_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
