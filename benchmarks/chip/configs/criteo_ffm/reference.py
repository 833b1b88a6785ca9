"""Plain reference for the libffm config. Imports nothing of the program.

- ``init_params``: the benchmark's weights from the seed, on the device:
  W [n_features, 256] with its first fields · latent columns from
  U(0, 1) / √latent and the rest 0, G all 1 (libffm's ``init_model``;
  the program's tables carry the same 100 columns of padding).
- ``plain_batches``: the staged ELL batches as a plain reading of the
  libffm text gives them: the file cut into ``nthread`` byte ranges as an
  InputSplit cuts text, each range's lines batched on their own (its
  tail padded with zeros) and the ranges' batches interleaved round robin
  (HIGGS's reader does the same, loaded from its file); each token
  ``field:feature:value`` split by hand, ids and fields 0-based.
- ``reference_trajectory``: the field-aware FM of the paper and libffm,
  written pair by pair (φ = r Σ_{a<c} ⟨W[j_a, f_c], W[j_c, f_a]⟩ x_a x_c,
  r = 1 / Σ x², l2 λ/2 on both vectors of every pair), and AdaGrad on
  the batch-summed gradient, in ``jax.numpy`` at float32 under the
  highest matmul precision (the control: bfloat16), over the rows the
  batches touch, a block of rows at a time.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

WIDTH = 256  # table columns: fields · latent rounded up to whole lane tiles
BLOCK = 1024  # rows of a batch the reference's loss takes at a time


def _key(seed: int):
    import jax

    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def init_params(cfg: dict, seed: int, mesh=None):
    import jax
    import jax.numpy as jnp

    n, e, k = int(cfg["n_features"]), int(cfg["fields"]) * int(cfg["latent"]), int(cfg["latent"])

    def init(key):
        w = jax.random.uniform(key, (n, e), jnp.float32) * (1.0 / np.sqrt(k))
        return {"W": jnp.pad(w, ((0, 0), (0, WIDTH - e))),
                "G": jnp.ones((n, WIDTH), jnp.float32)}

    return jax.jit(init)(_key(seed))


# -- plain reader -------------------------------------------------------------------


def _higgs_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "higgs_logreg", "reference.py")
    spec = importlib.util.spec_from_file_location("bench_higgs_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_HIGGS = _higgs_reference()


def plain_batches(cfg: dict, traffic: dict, corpus: dict, seed: int, keys):
    """{(epoch, j): ELL batch dict}, padded with zeros as the staged ones."""
    B, K = int(traffic["batch"]), int(cfg["fields"])
    vdt = np.dtype(traffic["value_dtype"])
    buf = np.fromfile(corpus["path"], dtype=np.uint8)
    nl = np.flatnonzero(buf == 10)
    assert len(nl) == int(cfg["rows"]) and not (buf == 13).any()
    parts = _HIGGS._cuts(len(buf), 1, nl, int(traffic["nthread"]))
    lines = {t: _HIGGS._lines(len(buf), nl, lo, hi)
             for t, (lo, hi) in enumerate(parts)}
    order = _HIGGS.batch_order([-(-len(v) // B) for v in lines.values()])
    out = {}
    for e, j in keys:  # every epoch reads the file in the same order
        t, r = order[j]
        b = {"indices": np.zeros((B, K), np.int32),
             "values": np.zeros((B, K), vdt), "nnz": np.zeros(B, np.int32),
             "labels": np.zeros(B, np.float32), "weights": np.zeros(B, np.float32)}
        if traffic.get("fields"):
            b["fields"] = np.zeros((B, K), np.int32)
        for row, (a, z) in enumerate(lines[t][r * B : (r + 1) * B]):
            toks = buf[a:z].tobytes().split()
            b["labels"][row], b["weights"][row] = float(toks[0]), 1.0
            trip = [tok.split(b":") for tok in toks[1 : K + 1]]
            b["nnz"][row] = len(trip)
            b["indices"][row, : len(trip)] = [int(j) for _f, j, _v in trip]
            b["values"][row, : len(trip)] = [float(v) for _f, _j, v in trip]
            if "fields" in b:
                b["fields"][row, : len(trip)] = [int(f) for f, _j, _v in trip]
        out[(e, j)] = b
    return out


# -- plain model --------------------------------------------------------------------


def ffm_block_loss(w, idx, fld, val, nnz, labels, weights, m: int, k: int,
                   lam):
    """Σ weight · (logloss + l2) over a block of rows, pair by pair:
    ``w`` [U, m·k] rows, ``idx`` [b, K] rows of ``w``, ``fld`` [b, K]
    fields, ``val`` [b, K] values; slots past ``nnz`` and slots of a field
    outside [0, m) take no part (libffm skips such tokens)."""
    import jax.numpy as jnp

    b, kk = idx.shape
    present = (jnp.arange(kk)[None, :] < nnz[:, None]) & (fld >= 0) & (fld < m)
    val = jnp.where(present, val, 0)
    fld = jnp.clip(fld, 0, m - 1)
    rows = w[idx].reshape(b, kk, m, k)
    # v[d, b, a, f] = W[j_a, f, d]; q[d, b, a, c] = W[j_a, f_c, d]
    v = rows.transpose(3, 0, 1, 2)
    q = jnp.take_along_axis(v, jnp.broadcast_to(fld[None, :, None, :], (k, b, kk, kk)),
                            axis=3)
    qt = q.transpose(0, 1, 3, 2)  # qt[d, b, a, c] = W[j_c, f_a, d]
    upper = (jnp.arange(kk)[:, None] < jnp.arange(kk)[None, :])
    pair = upper[None] & present[:, :, None] & present[:, None, :]
    xx = val[:, :, None] * val[:, None, :]
    phi = jnp.sum(jnp.where(pair, jnp.sum(q * qt, axis=0) * xx, 0), axis=(1, 2))
    l2 = 0.5 * lam * jnp.sum(jnp.where(pair, jnp.sum(q * q + qt * qt, axis=0), 0),
                             axis=(1, 2))
    sq = jnp.sum(val * val, axis=1)
    s = phi / jnp.where(sq > 0, sq, 1)
    y = (labels >= 0.5).astype(s.dtype)
    per_row = jnp.maximum(s, 0) - s * y + jnp.log1p(jnp.exp(-jnp.abs(s)))
    return jnp.sum((per_row + l2) * weights)


def reference_trajectory(cfg: dict, seed: int, mesh, batches, dtype: str,
                         fault: str = "") -> dict:
    """Losses (weighted means of the objective before each step), first
    change leaf norms over lr ((p0 - p1) / lr) and change leaf norms
    (p3 - p0) of AdaGrad from the benchmark's init, in ``dtype``
    (``float32``; ``bfloat16`` is the control). ``fault="half_batch"``
    drops the second half of each batch, the mean taken over the rest."""
    import jax
    import jax.numpy as jnp

    import math

    lr, lam = float(cfg["lr"]), float(cfg["lam"])
    m, k = int(cfg["fields"]), int(cfg["latent"])
    dt = jnp.dtype(dtype)
    ids = np.unique(np.concatenate([b["indices"].ravel() for b in batches]))
    # compact rows: the touched ids first, then rows no index points at (so
    # every seed compiles the same shapes; their gradient is zero)
    rows = np.zeros(sum(b["indices"].size for b in batches), np.int32)
    rows[: len(ids)] = ids
    full = init_params(cfg, seed, mesh)
    take = jax.jit(lambda p, u: {n: a[u, : m * k] for n, a in p.items()})
    p0 = jax.device_get(take(full, rows))
    del full
    feed = []
    for b in batches:
        w = b["weights"].copy()
        if fault == "half_batch":
            w[len(w) // 2 :] = 0
        blk = math.gcd(BLOCK, len(w))
        feed.append(tuple(a.reshape(len(w) // blk, blk, *a.shape[1:]) for a in (
            np.searchsorted(ids, b["indices"]).astype(np.int32), b["fields"],
            b["values"], b["nnz"], b["labels"], w)))

    def objective(w, idx, fld, val, nnz, labels, weights):
        """Σ over the batch's rows of weight · objective."""
        return jnp.sum(jax.lax.map(
            lambda a: ffm_block_loss(w, a[0], a[1], a[2].astype(dt), a[3],
                                     a[4].astype(dt), a[5].astype(dt), m, k,
                                     jnp.asarray(lam, dt)),
            (idx, fld, val, nnz, labels, weights)))

    @jax.jit
    def step(p, idx, fld, val, nnz, labels, weights):
        total, g = jax.value_and_grad(objective)(p["W"], idx, fld, val, nnz,
                                                 labels, weights)
        acc = p["G"] + g * g
        loss = total / jnp.maximum(jnp.sum(weights.astype(dt)), 1e-9)
        return {"W": p["W"] - lr * g / jnp.sqrt(acc), "G": acc}, loss

    def norms(a, b, scale):  # over the touched rows only
        return {n: float(np.sqrt(np.sum(np.square(
            (np.asarray(a[n], np.float32)[: len(ids)]
             - np.asarray(b[n], np.float32)[: len(ids)]) / np.float32(scale),
            dtype=np.float32), dtype=np.float32))) for n in a}

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dt), p0)
        losses = []
        for i, f in enumerate(feed):
            params, loss = step(params, *f)
            losses.append(float(loss))
            if i == 0:
                g1 = norms(p0, jax.device_get(params), lr)
    return {"losses": losses, "g1": g1,
            "d3": norms(jax.device_get(params), p0, 1.0)}
