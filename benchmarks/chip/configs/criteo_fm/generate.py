"""Criteo-layout rowrec corpus from the seed.

The rows are those of ``examples/train_criteo_rec.synth`` (same draws in
the same order): 13 integer fields as features 0..12 with values in
[0, 1), 26 categorical fields hashed into [13, n_features) with value 1,
and a label from a seeded linear model. The bytes are written here
directly, vectorized: every record is a complete RecordIO frame
(magic, lrec, 12-byte rowrec header, K u32 indices, K f32 values) with
its ``key<TAB>offset`` index line, as ``data/rowrec.write_rowrec`` writes
them (tests/bench_chip checks the two byte for byte at a small size).
"""

from __future__ import annotations

import os

import numpy as np

MAGIC = 0xCED7230A
DENSE = 13
CATEGORICAL = 26
CHUNK = 1 << 16


def rows_of(cfg: dict, seed: int, rows: int):
    """(indices u32[rows, K], values f32[rows, K], labels f32[rows]) — the
    rows ``synth`` draws for this seed."""
    k = DENSE + CATEGORICAL
    n_features = int(cfg["n_features"])
    rng = np.random.default_rng(seed)
    idx = np.empty((rows, k), dtype=np.uint32)
    idx[:, :DENSE] = np.arange(DENSE)
    idx[:, DENSE:] = rng.integers(DENSE, n_features, (rows, CATEGORICAL))
    val = np.ones((rows, k), dtype=np.float32)
    val[:, :DENSE] = rng.uniform(0, 1, (rows, DENSE))
    w = rng.normal(size=n_features) / np.sqrt(k)
    labels = np.empty(rows, dtype=np.float32)
    for lo in range(0, rows, CHUNK):
        hi = min(lo + CHUNK, rows)
        logits = (w[idx[lo:hi]] * val[lo:hi]).sum(axis=1)
        labels[lo:hi] = logits > 0
    return idx, val, labels


def frames(idx: np.ndarray, val: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """u32[rows, 5 + 2K]: one complete RecordIO frame per row."""
    rows, k = idx.shape
    out = np.empty((rows, 5 + 2 * k), dtype="<u4")
    payload = 4 * (3 + 2 * k)
    out[:, 0] = MAGIC
    out[:, 1] = payload  # cflag 0: the whole record in one frame
    out[:, 2] = labels.astype("<f4").view("<u4")
    out[:, 3] = np.float32(1.0).view(np.uint32)  # weight
    out[:, 4] = k
    out[:, 5 : 5 + k] = idx
    out[:, 5 + k :] = val.view("<u4")
    # a payload word equal to the magic would need the multipart escape;
    # indices < n_features < 2^31 and values in [0, 1] can never be one
    assert not (out[:, 2:] == MAGIC).any(), "magic word inside a payload"
    return out


def generate(cfg: dict, traffic: dict, seed: int, tmpdir: str) -> dict:
    rows = int(cfg["rows"])
    path = os.path.join(tmpdir, "criteo.rec")
    idx, val, labels = rows_of(cfg, seed, rows)
    rec = frames(idx, val, labels)
    rec.tofile(path)
    stride = rec.shape[1] * 4
    with open(path + ".idx", "w") as f:
        f.write("".join(f"{i}\t{i * stride}\n" for i in range(rows)))
    return {"path": path, "index": path + ".idx", "rows": rows,
            "bytes": rows * stride}
