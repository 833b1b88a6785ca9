"""The system under test for the libffm config: the libffm text file →
``ell_batches`` (``?format=libfm``, the fused native libfm producer fanned
out over ``nthread`` cores, field ids staged) → ``StagingPipeline``
(packed single DMA) → the jitted ``FieldAwareFM.adagrad_step``."""

from __future__ import annotations

import numpy as np

from dmlc_core_tpu.models.ffm import FieldAwareFM
from dmlc_core_tpu.staging import BatchSpec


def batch_spec(cfg: dict, traffic: dict):
    return BatchSpec(batch_size=int(traffic["batch"]), layout="ell",
                     max_nnz=int(cfg["fields"]),
                     value_dtype=np.dtype(traffic["value_dtype"]),
                     fields=bool(traffic["fields"]))


def open_epoch(cfg: dict, traffic: dict, corpus: dict, seed: int, epoch: int):
    from dmlc_core_tpu.staging import ell_batches
    from dmlc_core_tpu.staging.fused import (
        FusedEllLibFMBatches,
        ShardedFusedBatches,
    )

    spec = batch_spec(cfg, traffic)
    stream = ell_batches(corpus["uri"] + "?format=libfm", spec,
                         nthread=int(traffic["nthread"]))
    producers = getattr(stream, "_producers", [stream])
    if not (isinstance(stream, (FusedEllLibFMBatches, ShardedFusedBatches))
            and all(isinstance(p, FusedEllLibFMBatches) and p.spec.fields
                    for p in producers)):
        stream.close()
        raise SystemExit(f"bench: ell_batches gave {type(stream).__name__}, "
                         "not the fused native libfm producer with field ids")
    return stream


def pipeline_kwargs(cfg: dict, mesh) -> dict:
    return {}


def make_step(cfg: dict, mesh):
    import jax

    model = FieldAwareFM(int(cfg["n_features"]), int(cfg["fields"]),
                         k=int(cfg["latent"]), lam=float(cfg["lam"]))
    lr = float(cfg["lr"])

    def ffm_train_step(params, batch):
        return model.adagrad_step(params, batch, lr=lr)

    return jax.jit(ffm_train_step)
