"""The system under test for the Criteo FM configs: the indexed rowrec
shard → ``ell_batches`` (fused native producer) → ``StagingPipeline``
(packed single DMA, or one DMA per device on the mesh) → the jitted
``FactorizationMachine.sgd_step`` (``data_parallel_step`` on the mesh)."""

from __future__ import annotations

import numpy as np


def batch_spec(cfg: dict, traffic: dict):
    from dmlc_core_tpu.staging import BatchSpec

    return BatchSpec(batch_size=int(traffic["batch"]), layout="ell",
                     max_nnz=int(cfg["fields"]),
                     value_dtype=np.dtype(traffic["value_dtype"]))


def epoch_uri(traffic: dict, corpus: dict, seed: int, epoch: int) -> str:
    uri = f"{corpus['path']}?index={corpus['index']}"
    if traffic["read"] == "shuffle":
        uri += f"&shuffle={traffic['shuffle']}&seed={seed}&epoch={epoch}"
    return uri


def open_epoch(cfg: dict, traffic: dict, corpus: dict, seed: int, epoch: int):
    from dmlc_core_tpu.staging import ell_batches
    from dmlc_core_tpu.staging.fused import FusedEllRowRecBatches

    stream = ell_batches(epoch_uri(traffic, corpus, seed, epoch),
                         batch_spec(cfg, traffic))
    if not isinstance(stream, FusedEllRowRecBatches):
        raise SystemExit(f"bench: ell_batches gave {type(stream).__name__}, "
                         "not the fused native producer")
    return stream


def pipeline_kwargs(cfg: dict, mesh) -> dict:
    return {} if mesh is None else {"mesh": mesh, "data_axis": "data"}


def make_step(cfg: dict, mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    from dmlc_core_tpu.models import FactorizationMachine
    from dmlc_core_tpu.parallel import data_parallel_step

    model = FactorizationMachine(int(cfg["n_features"]),
                                 embed_dim=int(cfg["embed_dim"]))
    lr = float(cfg["lr"])

    def fm_train_step(params, batch):
        return model.sgd_step(params, batch, lr=lr)

    if mesh is None:
        return jax.jit(fm_train_step)
    rules = {k: P(*v) for k, v in cfg["param_specs"].items()}
    return data_parallel_step(fm_train_step, mesh, param_rules=rules,
                              donate_params=False)
