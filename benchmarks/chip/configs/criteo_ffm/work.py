"""Required work of one FFM AdaGrad step and of one batch's unpack, from
shapes.

The count is what the step needs, whatever implements it. Per row, with
K slots (one a field, K = m = 39) and k latent factors, libffm's pair
loop: each of the K(K−1)/2 pairs takes a k-dot (2k), its two vectors'
squared norms for the l2 term (4k) and the scale x_a x_c r (3): 6k + 3.
Per row besides: r (2K) and the logistic loss and its weight (9). The
backward takes twice the forward. The update, per slot coordinate of the
m·k a slot touches: g², G + g², √, η·g, the division and W − (6).

Bytes: the batch once (indices, values, fields, labels, weights; ``nnz``
too, which the step reads), and each slot's W and G rows (m·k floats)
read once and written once: 4 · slots · m·k · 4. Duplicate ids inside a
batch are counted as often as they occur; merging them is an
optimisation that the count must not assume.
"""

from __future__ import annotations

import numpy as np


def step_work(cfg: dict, traffic: dict) -> dict:
    B, K, k = int(traffic["batch"]), int(cfg["fields"]), int(cfg["latent"])
    slots, row = B * K, K * k
    fwd = B * (K * (K - 1) // 2 * (6 * k + 3) + 2 * K + 9)
    flops = 3 * fwd + slots * row * 6
    vbytes = np.dtype(traffic["value_dtype"]).itemsize
    batch_bytes = slots * (4 + vbytes + 4) + B * (4 + 4 + 4)
    return {"flops": flops, "bytes": batch_bytes + 4 * slots * row * 4}


def unpack_bytes(cfg: dict, traffic: dict) -> int:
    """Bytes the unpack must read and write per batch: the packed
    buffer's arrays in, the same arrays out (the alignment padding
    between sections is not required)."""
    B, K = int(traffic["batch"]), int(cfg["fields"])
    vbytes = np.dtype(traffic["value_dtype"]).itemsize
    fields = 4 if traffic.get("fields") else 0
    arrays = B * K * (4 + vbytes + fields) + B * (4 + 4 + 4)  # + nnz, labels, weights
    return 2 * arrays
