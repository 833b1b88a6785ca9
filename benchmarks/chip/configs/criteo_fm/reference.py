"""Plain reference for the Criteo FM configs. Imports nothing of the
program.

- ``init_params``: the benchmark's weights, made on the device from the
  seed in one jitted call (program and reference both start from them).
- ``plain_batches``: the staged ELL batches as a plain reading of the
  corpus gives them — RecordIO frames walked here from the index's
  offsets, rowrec payloads decoded with ``struct``/``numpy``, epoch
  order by the documented ``(seed, epoch)`` rule of indexed shuffles.
- ``reference_trajectory``: the second-order FM (Rendle 2010) and plain
  SGD in ``jax.numpy`` at float32 (the control: bfloat16), over the rows
  the batches touch: with l2 = 0 no other row has a gradient, so the
  others keep their init and add nothing to any norm (the program's
  norms run over its whole table, so a row it moves that it should not
  shows).
"""

from __future__ import annotations

import random
import struct

import numpy as np

MAGIC = 0xCED7230A
KRAND_MAGIC = 111  # indexed RecordIO shuffle: Random(111 + seed + 1000003 * epoch)


def _key(seed: int):
    import jax

    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def init_params(cfg: dict, seed: int, mesh=None):
    import jax
    import jax.numpy as jnp

    d, e = int(cfg["n_features"]), int(cfg["embed_dim"])

    def init(key):
        kw, kv = jax.random.split(key)
        return {"w": jax.random.normal(kw, (d,), jnp.float32) * 0.01,
                "v": jax.random.normal(kv, (d, e), jnp.float32) * 0.01,
                "b": jnp.zeros((), jnp.float32)}

    if mesh is None:
        return jax.jit(init)(_key(seed))
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = cfg.get("param_specs", {})
    shard = {k: NamedSharding(mesh, P(*specs.get(k, []))) for k in ("w", "v", "b")}
    return jax.jit(init, out_shardings=shard)(_key(seed))


# -- plain readers ----------------------------------------------------------------


def _offsets(index_path: str) -> np.ndarray:
    with open(index_path, "rb") as f:
        return np.array(f.read().split()[1::2], dtype=np.int64)


def _payload(buf: np.ndarray, off: int) -> bytes:
    """One record from its first frame: cflag 0 is whole; 1, 2.., 3 is a
    chain whose parts were split at a magic word (re-inserted here)."""
    parts = []
    while True:
        magic, lrec = struct.unpack_from("<II", buf, off)
        assert magic == MAGIC, f"no RecordIO magic at byte {off}"
        cflag, length = lrec >> 29, lrec & ((1 << 29) - 1)
        parts.append(bytes(buf[off + 8 : off + 8 + length]))
        off += 8 + ((length + 3) & ~3)
        if cflag in (0, 3):
            return struct.pack("<I", MAGIC).join(parts)


def epoch_order(traffic: dict, rows: int, seed: int, epoch: int) -> np.ndarray:
    if traffic["read"] != "shuffle":
        return np.arange(rows)
    assert traffic["shuffle"] == "record", traffic["shuffle"]
    perm = list(range(rows))
    random.Random(KRAND_MAGIC + seed + 1_000_003 * epoch).shuffle(perm)
    return np.asarray(perm)


def plain_batches(cfg: dict, traffic: dict, corpus: dict, seed: int, keys):
    """{(epoch, j): ELL batch dict}, padded with zeros as the staged ones."""
    B, K = int(traffic["batch"]), int(cfg["fields"])
    vdt = np.dtype(traffic["value_dtype"])
    offsets = _offsets(corpus["index"])
    buf = np.fromfile(corpus["path"], dtype=np.uint8)
    orders = {}
    out = {}
    for e, j in keys:
        if e not in orders:
            orders[e] = epoch_order(traffic, len(offsets), seed, e)
        recs = orders[e][j * B : (j + 1) * B]
        b = {"indices": np.zeros((B, K), np.int32),
             "values": np.zeros((B, K), vdt), "nnz": np.zeros(B, np.int32),
             "labels": np.zeros(B, np.float32), "weights": np.zeros(B, np.float32)}
        for r, rec in enumerate(recs):
            p = _payload(buf, int(offsets[rec]))
            label, weight, n = struct.unpack_from("<ffI", p, 0)
            m = min(n, K)
            b["indices"][r, :m] = np.frombuffer(p, "<u4", m, 12)
            b["values"][r, :m] = np.frombuffer(p, "<f4", m, 12 + 4 * n)
            b["nnz"][r], b["labels"][r], b["weights"][r] = m, label, weight
        out[(e, j)] = b
    return out


# -- plain model --------------------------------------------------------------------


def fm_loss(params, idx, val, labels, weights):
    import jax.numpy as jnp

    lin = jnp.sum(val * params["w"][idx], axis=-1)
    xv = params["v"][idx] * val[..., None]
    pair = 0.5 * jnp.sum(jnp.sum(xv, axis=1) ** 2 - jnp.sum(xv * xv, axis=1),
                         axis=-1)
    s = lin + pair + params["b"]
    y = (labels >= 0.5).astype(s.dtype)
    per_row = jnp.maximum(s, 0) - s * y + jnp.log1p(jnp.exp(-jnp.abs(s)))
    return jnp.sum(per_row * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


def reference_trajectory(cfg: dict, seed: int, mesh, batches, dtype: str,
                         fault: str = "") -> dict:
    """Losses, first-gradient leaf norms ((p0 - p1) / lr) and change leaf
    norms (p3 - p0) of plain SGD from the benchmark's init, in ``dtype``
    (``float32``; ``bfloat16`` is the control). ``fault="half_batch"``
    drops the second half of each batch, the mean taken over the rest."""
    import jax
    import jax.numpy as jnp

    lr = float(cfg["lr"])
    dt = jnp.dtype(dtype)
    ids = np.unique(np.concatenate([b["indices"].ravel() for b in batches]))
    # compact rows: the touched ids first, then rows no index points at (so
    # every seed compiles the same shapes; their gradient is zero)
    rows = np.zeros(sum(b["indices"].size for b in batches), np.int32)
    rows[: len(ids)] = ids
    full = init_params(cfg, seed, mesh)
    take = jax.jit(lambda p, u: {"w": p["w"][u], "v": p["v"][u], "b": p["b"]})
    p0 = jax.device_get(take(full, rows))
    del full
    feed = []
    for b in batches:
        w = b["weights"].copy()
        if fault == "half_batch":
            w[len(w) // 2 :] = 0
        feed.append((np.searchsorted(ids, b["indices"]).astype(np.int32),
                     b["values"], b["labels"], w))

    @jax.jit
    def step(p, idx, val, labels, weights):
        loss, g = jax.value_and_grad(fm_loss)(
            p, idx, val.astype(dt), labels.astype(dt), weights.astype(dt))
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    def norms(a, b, scale):  # over the touched rows only
        return {k: float(np.sqrt(np.sum(np.square(
            (np.atleast_1d(np.asarray(a[k], np.float32))[: len(ids)]
             - np.atleast_1d(np.asarray(b[k], np.float32))[: len(ids)])
            / np.float32(scale), dtype=np.float32), dtype=np.float32)))
            for k in a}

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
