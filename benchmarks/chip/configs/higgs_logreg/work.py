"""Required work of one logistic-regression SGD step over a dense batch
and of one batch's unpack, from shapes.

Per row with D features: the score x·w + b is 2D + 1 FLOPs; the logistic
loss (max, multiply, subtract, abs, exp, log1p, weight multiply, add) 8;
the backward pass twice the forward; the update 2 per parameter (D + 1).
Bytes: the batch once (x, labels, weights) and the D + 1 parameters read
and written once.
"""

from __future__ import annotations

import numpy as np


def step_work(cfg: dict, traffic: dict) -> dict:
    B, D = int(traffic["batch"]), int(cfg["features"])
    fwd = B * (2 * D + 1 + 8)
    flops = 3 * fwd + 2 * (D + 1)
    xbytes = np.dtype(traffic["value_dtype"]).itemsize
    return {"flops": flops, "bytes": B * (D * xbytes + 8) + 2 * 4 * (D + 1)}


def unpack_bytes(cfg: dict, traffic: dict) -> int:
    B, D = int(traffic["batch"]), int(cfg["features"])
    xbytes = np.dtype(traffic["value_dtype"]).itemsize
    return 2 * (B * D * xbytes + B * 8)
