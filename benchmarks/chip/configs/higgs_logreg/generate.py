"""HIGGS-layout CSV corpus from the seed, vectorized.

UCI's ``HIGGS.csv`` holds a label column, then 28 feature columns, every
field written ``%.18e`` (``1.000000000000000000e+00,8.692932128906250000e-01,
-6.350818276405334473e-01,...``). Rows here are drawn from the seed: each
feature is one of ``POOL`` float32 values drawn N(0, 1) (printed once with
``%.18e``; a float32 printed with 19 significant digits parses back to the
same float32), and the label comes from a seeded linear model plus
logistic noise. Lines are assembled from the pool's bytes with one gather
and one mask per chunk, never per row in Python. An epoch reads the file
``file_copies`` times over, as a list of that many files, so that an
epoch holds about as many rows as HIGGS (``rows`` counts an epoch's).
"""

from __future__ import annotations

import os

import numpy as np

FEATURES = 28
POOL = 1 << 16
WIDTH = 26  # widest token: sign + 24 characters + separator
CHUNK = 1 << 16


def pool_of(seed: int):
    """(pool f32[POOL], true weights f64[FEATURES]) for this seed."""
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal(POOL).astype(np.float32)
    return pool, rng.standard_normal(FEATURES), rng


def _token_table(values, sep: bytes):
    """(token bytes as one WIDTH-byte item each, token lengths)."""
    toks = [(b"%.18e" % float(v)) + sep for v in values]
    table = np.zeros((len(toks), WIDTH), dtype=np.uint8)
    for i, t in enumerate(toks):
        table[i, : len(t)] = np.frombuffer(t, dtype=np.uint8)
    lens = np.array([len(t) for t in toks], dtype=np.uint8)
    return table.view(f"V{WIDTH}").ravel(), lens


def generate(cfg: dict, traffic: dict, seed: int, tmpdir: str) -> dict:
    rows = int(cfg["rows"])
    pool, w_true, rng = pool_of(seed)
    comma, comma_n = _token_table(pool, b",")
    newline, newline_n = _token_table(pool, b"\n")
    label_t, label_n = _token_table([0.0, 1.0], b",")
    cols = np.arange(WIDTH, dtype=np.uint8)
    path = os.path.join(tmpdir, "HIGGS.csv")
    written = 0
    with open(path, "wb") as f:
        for lo in range(0, rows, CHUNK):
            n = min(CHUNK, rows - lo)
            ids = rng.integers(0, POOL, (n, FEATURES), dtype=np.int64)
            logit = pool[ids].astype(np.float64) @ w_true
            label = (logit + rng.logistic(size=n) > 0).astype(np.int64)
            toks = np.empty((n, 1 + FEATURES), dtype=comma.dtype)
            lens = np.empty((n, 1 + FEATURES), dtype=np.uint8)
            toks[:, 0], lens[:, 0] = label_t[label], label_n[label]
            toks[:, 1:-1], lens[:, 1:-1] = comma[ids[:, :-1]], comma_n[ids[:, :-1]]
            toks[:, -1], lens[:, -1] = newline[ids[:, -1]], newline_n[ids[:, -1]]
            text = toks.view(np.uint8).reshape(n, 1 + FEATURES, WIDTH)[
                cols < lens[..., None]]
            text.tofile(f)
            written += text.size
    copies = int(cfg["file_copies"])
    return {"path": path, "uri": ";".join([path] * copies),
            "rows": rows * copies, "bytes": written}
