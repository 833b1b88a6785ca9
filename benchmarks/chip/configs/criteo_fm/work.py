"""Required work of one FM SGD step and of one batch's unpack, from shapes.

The count is what the step needs, whatever implements it: with l2 = 0 an
SGD step reads the batch, gathers the w and v rows of the ids in it, and
reads and writes those rows once to update them. Nothing dense over the
table is required. Per (row, field) slot with E = embed_dim:

- forward: linear term 2 FLOPs (multiply, add); x·v 1·E; the two field
  sums 2·E (add x·v, add (x·v)²); squaring x·v 1·E: 2 + 4E.
  Per row: squaring the field sum E, the difference E, the E-sum E, the
  half, the bias and the logistic loss (max, multiply, subtract, abs,
  exp, log1p, weight multiply, add): 3E + 9.
- backward: twice the forward.
- update: per slot, 1 + E parameters, a multiply and a subtract each.

Bytes: the batch once (indices, values, labels, weights; ``nnz`` is not
needed by the step), the gathered rows once, and the updated rows read
and written once: 3 · slots · (1 + E) · 4 bytes. Duplicate ids inside a
batch are counted as often as they occur; merging them is an
optimisation that the count must not assume.
"""

from __future__ import annotations

import numpy as np


def step_work(cfg: dict, traffic: dict) -> dict:
    B, K, E = int(traffic["batch"]), int(cfg["fields"]), int(cfg["embed_dim"])
    slots = B * K
    fwd = slots * (2 + 4 * E) + B * (3 * E + 9)
    flops = 3 * fwd + slots * 2 * (1 + E)
    vbytes = np.dtype(traffic["value_dtype"]).itemsize
    batch_bytes = slots * (4 + vbytes) + B * (4 + 4)
    row_bytes = 3 * slots * (1 + E) * 4
    return {"flops": flops, "bytes": batch_bytes + row_bytes}


def unpack_bytes(cfg: dict, traffic: dict) -> int:
    """Bytes one device's unpack must read and write per batch: its share
    of the packed buffer in, the same arrays out (the alignment padding
    between sections is not required)."""
    B, K = int(traffic["batch"]), int(cfg["fields"])
    vbytes = np.dtype(traffic["value_dtype"]).itemsize
    arrays = B * K * (4 + vbytes) + B * (4 + 4 + 4)  # + nnz, labels, weights
    data = int(cfg.get("mesh", {}).get("shape", [1])[0]) if cfg.get("mesh") else 1
    return 2 * arrays // data
