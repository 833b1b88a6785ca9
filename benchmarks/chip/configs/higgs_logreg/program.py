"""The system under test for HIGGS: the CSV corpus (an epoch reads the
file ``file_copies`` times, as a file list) → ``dense_batches``
(``?format=csv&label_column=0``, fanned out over ``nthread`` fused
native CSV producers) → ``StagingPipeline`` (packed single DMA) → the
jitted ``LogisticRegression.sgd_step``."""

from __future__ import annotations

import numpy as np


def batch_spec(cfg: dict, traffic: dict):
    from dmlc_core_tpu.staging import BatchSpec

    return BatchSpec(batch_size=int(traffic["batch"]), layout="dense",
                     num_features=int(cfg["features"]),
                     value_dtype=np.dtype(traffic["value_dtype"]))


def open_epoch(cfg: dict, traffic: dict, corpus: dict, seed: int, epoch: int):
    from dmlc_core_tpu.staging import dense_batches
    from dmlc_core_tpu.staging.fused import (
        FusedDenseCSVBatches,
        ShardedFusedBatches,
    )

    uri = f"{corpus['uri']}?format=csv&label_column={traffic['label_column']}"
    stream = dense_batches(uri, batch_spec(cfg, traffic),
                           nthread=int(traffic["nthread"]))
    if not isinstance(stream, (FusedDenseCSVBatches, ShardedFusedBatches)):
        raise SystemExit(f"bench: dense_batches gave {type(stream).__name__}, "
                         "not the fused native CSV producer")
    return stream


def pipeline_kwargs(cfg: dict, mesh) -> dict:
    return {}


def make_step(cfg: dict, mesh):
    import jax

    from dmlc_core_tpu.models import LogisticRegression

    model = LogisticRegression(num_features=int(cfg["features"]))
    lr = float(cfg["lr"])

    def logreg_train_step(params, batch):
        return model.sgd_step(params, batch, lr=lr)

    return jax.jit(logreg_train_step)
