"""libffm text corpus of Criteo rows from the seed, vectorized.

Each line is ``label f:j:1 ...`` with one token for each of the 39
fields, as the Criteo Kaggle winners' converter writes libffm input
(github.com/guestwalk/kaggle-2014-criteo): field f (0-based) holds the
hashed bin j in [1, 10^6) of its (field, value) pair, and value 1.

- Integer fields 0..12: v = floor(exp(X)), X ~ N(1, 1.5^2); v > 2 is
  bucketed to floor(ln(v)^2), as the converter does.
- Categorical fields 13..38: a Zipf(``zipf_s``) rank over the field's
  published vocabulary, scrambled in the field's range (the sampler of
  ``criteo_fm_zipf``, loaded from its file).
- The bin: a splitmix64 mix of (field, bucketed value), mod
  (n_features - 1), plus 1 (the converter's md5, mod NR_BINS - 1, plus 1,
  has the same range).
- The label: 1 where the sum of the row's bins' N(0, 1/39) weights is
  above 0.

Lines are assembled from a table of every bin's token text with one
mask per chunk of rows, chunks and fields side by side on the host's
cores; no Python runs per row.
"""

from __future__ import annotations

import importlib.util
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DENSE = 13
CHUNK = 1 << 16
WIDTH = 12  # widest token: 2 field digits, ':', 6 bin digits, ':1', separator
_M64 = (1 << 64) - 1


def _zipf_generate():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "criteo_fm_zipf", "generate.py")
    spec = importlib.util.spec_from_file_location("bench_criteo_fm_zipf_generate",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ZIPF = _zipf_generate()


def mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wrapping arithmetic)."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def bins_of(field: int, values: np.ndarray, n_features: int) -> np.ndarray:
    """The hashed bin in [1, n_features) of each (field, value) pair."""
    key = (np.uint64(field + 1) << np.uint64(40)) | values.astype(np.uint64)
    return (mix(key) % np.uint64(n_features - 1) + np.uint64(1)).astype(np.int64)


def bucket(v: np.ndarray) -> np.ndarray:
    """The converter's integer bucketing: v > 2 → floor(ln(v)^2)."""
    out = v.copy()
    big = v > 2
    out[big] = np.floor(np.log(v[big].astype(np.float64)) ** 2).astype(np.int64)
    # keep a bucket apart from a small raw value of the same number (the
    # converter writes small values as 'SP<v>')
    out[~big] += 1 << 20
    return out


def rows_of(cfg: dict, seed: int, rows: int):
    """(bins i64[rows, 39], labels f32[rows]): field f's bin in column f.
    Field f draws from its own generator, seeded (seed, f), so the
    fields are drawn side by side on the host's cores."""
    vocab = [int(v) for v in cfg["vocab"]]
    n = int(cfg["n_features"])
    k = DENSE + len(vocab)
    assert int(cfg["fields"]) == k
    s = float(cfg["zipf_s"])
    bins = np.empty((rows, k), dtype=np.int64)

    def draw(f):
        rng = np.random.default_rng([seed, f])
        if f < DENSE:
            v = bucket(np.floor(np.exp(rng.normal(1.0, 1.5, rows))).astype(np.int64))
        else:
            size = vocab[f - DENSE]
            v = _ZIPF.scramble(_ZIPF.zipf_ranks(rng, size, s, rows), size)
        bins[:, f] = bins_of(f, v, n)

    with ThreadPoolExecutor(_workers()) as pool:
        list(pool.map(draw, range(k)))
    w = (np.random.default_rng([seed, k]).standard_normal(n, dtype=np.float32)
         / np.float32(math.sqrt(k)))
    labels = np.empty(rows, dtype=np.float32)
    for lo in range(0, rows, CHUNK):
        labels[lo : lo + CHUNK] = w[bins[lo : lo + CHUNK]].sum(axis=1) > 0
    return bins, labels


def _workers() -> int:
    return max(1, min(16, len(os.sched_getaffinity(0))))


def token_table(n: int):
    """(u8 [n, 9]: the digits of each bin in [0, n) then ':1 ',
    left-justified; u8 [n]: the digit counts)."""
    v = np.arange(n, dtype=np.int64)
    nd = np.ones(n, dtype=np.int64)
    for p in range(1, 9):
        nd += v >= 10**p
    assert nd.max() <= 6, "bins of more than 6 digits do not fit a token"
    at = np.arange(9) - nd[:, None]  # < 0: a digit; 0, 1, 2: ':1 '
    digit = 48 + (v[:, None] // 10 ** np.maximum(-1 - at, 0)) % 10
    tail = np.array([58, 49, 32, 0], dtype=np.int64)[np.clip(at, 0, 3)]
    return np.where(at < 0, digit, tail).astype(np.uint8), nd.astype(np.uint8)


def lines(bins: np.ndarray, labels: np.ndarray, table) -> np.ndarray:
    """The libffm text of the rows, as one u8 array (``table`` is
    ``token_table(n_features)``)."""
    text, nd = table
    rows, k = bins.shape
    toks = np.zeros((rows, 1 + k, WIDTH), dtype=np.uint8)
    lens = np.empty((rows, 1 + k), dtype=np.int64)
    toks[:, 0, 0] = 48 + labels.astype(np.uint8)
    toks[:, 0, 1] = 32
    lens[:, 0] = 2
    for f in range(k):
        head = b"%d:" % f
        toks[:, 1 + f, : len(head)] = np.frombuffer(head, np.uint8)
        toks[:, 1 + f, len(head) : len(head) + 9] = text[bins[:, f]]
        lens[:, 1 + f] = len(head) + nd[bins[:, f]] + 3
    toks[np.arange(rows), k, lens[:, k] - 1] = 10  # the line's end
    return toks[np.arange(WIDTH)[None, None, :] < lens[..., None]]


def generate(cfg: dict, traffic: dict, seed: int, tmpdir: str) -> dict:
    rows = int(cfg["rows"])
    path = os.path.join(tmpdir, "criteo.ffm")
    bins, labels = rows_of(cfg, seed, rows)
    table = token_table(int(cfg["n_features"]))
    written = 0
    with open(path, "wb") as f, ThreadPoolExecutor(_workers()) as pool:
        for text in pool.map(
                lambda lo: lines(bins[lo : lo + CHUNK], labels[lo : lo + CHUNK],
                                 table), range(0, rows, CHUNK)):
            text.tofile(f)
            written += text.size
    return {"path": path, "uri": path, "rows": rows, "bytes": written}
