"""Criteo-layout rowrec corpus with scrambled-Zipf ids over per-field
vocabularies, from the seed.

The rows have ``criteo_fm``'s layout: 13 integer fields as features
0..12 with values in [0, 1), 26 categorical fields with value 1, and a
label from a seeded linear model. Categorical field f draws a rank r
from Zipf(``zipf_s``) over its ``vocab[f]`` values (rank 0 the most
frequent), scrambles it inside the field's range by a bijective
multiplicative hash (so the hot ids are spread over the range, as YCSB's
``ScrambledZipfianGenerator`` spreads them) and adds the field's offset,
13 + the vocabularies of the fields before it, as DLRM's offsets lay the
fields out in one table. The frames and the index are written by
``criteo_fm``'s ``frames`` (loaded from its file).
"""

from __future__ import annotations

import importlib.util
import math
import os
import sys

import numpy as np

DENSE = 13
CHUNK = 1 << 16
GOLDEN = (math.sqrt(5) - 1) / 2


def _criteo_fm_generate():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "criteo_fm", "generate.py")
    spec = importlib.util.spec_from_file_location("bench_criteo_fm_generate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BASE = _criteo_fm_generate()


def offsets(vocab) -> np.ndarray:
    """First id of each categorical field: 13 + the vocabularies before it."""
    return DENSE + np.concatenate([[0], np.cumsum(vocab)[:-1]]).astype(np.int64)


def scramble(rank: np.ndarray, n: int) -> np.ndarray:
    """A bijection of [0, n): (a·r + c) mod n with a coprime to n, and
    c ≠ 0, so rank 0 (the most frequent) does not land on the field's
    first id."""
    a = max(1, int(n * GOLDEN))
    while math.gcd(a, n) != 1:
        a += 1
    c = max(1, n // 3) % n if n > 1 else 0
    return (rank.astype(np.int64) * a + c) % n


def zipf_ranks(rng: np.random.Generator, n: int, s: float, size: int) -> np.ndarray:
    """``size`` independent ranks in [0, n) with P(r) ∝ (r + 1)^-s: how
    many of each (a multinomial draw), in a random order."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -s
    counts = rng.multinomial(size, p / p.sum())
    rank = np.repeat(np.arange(n, dtype=np.int64), counts)
    rng.shuffle(rank)
    return rank


def rows_of(cfg: dict, seed: int, rows: int):
    """(indices u32[rows, K], values f32[rows, K], labels f32[rows])."""
    vocab = [int(v) for v in cfg["vocab"]]
    n_features = int(cfg["n_features"])
    assert n_features == DENSE + sum(vocab), (n_features, DENSE + sum(vocab))
    assert int(cfg["fields"]) == DENSE + len(vocab)
    k = DENSE + len(vocab)
    s = float(cfg["zipf_s"])
    rng = np.random.default_rng(seed)
    idx = np.empty((rows, k), dtype=np.uint32)
    idx[:, :DENSE] = np.arange(DENSE)
    for f, (n, off) in enumerate(zip(vocab, offsets(vocab))):
        idx[:, DENSE + f] = off + scramble(zipf_ranks(rng, n, s, rows), n)
    val = np.ones((rows, k), dtype=np.float32)
    val[:, :DENSE] = rng.uniform(0, 1, (rows, DENSE))
    w = rng.standard_normal(n_features, dtype=np.float32) / np.float32(np.sqrt(k))
    labels = np.empty(rows, dtype=np.float32)
    for lo in range(0, rows, CHUNK):
        hi = min(lo + CHUNK, rows)
        logits = (w[idx[lo:hi]] * val[lo:hi]).sum(axis=1)
        labels[lo:hi] = logits > 0
    return idx, val, labels


def distinct_per_batch(idx: np.ndarray, n_features: int, batch: int) -> float:
    """Mean count of distinct ids among a batch's slots, over the corpus's
    whole batches: one slot per distinct id finds its own stamp."""
    stamp = np.empty(n_features, dtype=np.int32)
    counts = []
    cat = idx[:, DENSE:]
    slots = np.arange(batch * cat.shape[1], dtype=np.int32)
    for lo in range(0, idx.shape[0] - batch + 1, batch):
        ids = cat[lo : lo + batch].reshape(-1)
        stamp[ids] = slots
        counts.append(DENSE + int(np.count_nonzero(stamp[ids] == slots)))
    return float(np.mean(counts)) if counts else 0.0


def generate(cfg: dict, traffic: dict, seed: int, tmpdir: str) -> dict:
    rows = int(cfg["rows"])
    path = os.path.join(tmpdir, "criteo.rec")
    idx, val, labels = rows_of(cfg, seed, rows)
    if "batch" in traffic:
        b = int(traffic["batch"])
        d = distinct_per_batch(idx, int(cfg["n_features"]), b)
        print(f"[bench] data: {d:.1f} distinct ids per batch of {b * idx.shape[1]} "
              f"slots ({100 * d / (b * idx.shape[1]):.2f}%)", file=sys.stderr,
              flush=True)
    rec = _BASE.frames(idx, val, labels)
    rec.tofile(path)
    stride = rec.shape[1] * 4
    with open(path + ".idx", "w") as f:
        f.write("".join(f"{i}\t{i * stride}\n" for i in range(rows)))
    return {"path": path, "index": path + ".idx", "rows": rows,
            "bytes": rows * stride}
