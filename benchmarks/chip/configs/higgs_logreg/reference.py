"""Plain reference for HIGGS logistic regression. Imports nothing of the
program.

- ``init_params``: the benchmark's weights from the seed, on the device.
- ``plain_batches``: the staged dense batches as a plain reading gives
  them. An epoch reads a list of ``file_copies`` copies of the file; the
  list is cut into ``nthread`` byte ranges as an InputSplit cuts text
  (``ceil(total / n)`` bytes each, a cut inside a file moved past the
  next newline), each range yields batches of its own lines (its tail
  padded with zeros), and the ranges' batches interleave round robin, a
  range leaving the rotation when it runs out. Fields are parsed as
  float64 by numpy (C ``strtod``), then rounded to float32.
- ``reference_trajectory``: logistic regression and plain SGD in
  ``jax.numpy`` at float32 with the highest matmul precision (the
  control: bfloat16).
"""

from __future__ import annotations

import numpy as np


def _key(seed: int):
    import jax

    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def init_params(cfg: dict, seed: int, mesh=None):
    import jax
    import jax.numpy as jnp

    d = int(cfg["features"])

    def init(key):
        return {"w": jax.random.normal(key, (d,), jnp.float32) * 0.01,
                "b": jnp.zeros((), jnp.float32)}

    return jax.jit(init)(_key(seed))


# -- plain reader -------------------------------------------------------------------


def _cuts(size: int, copies: int, nl: np.ndarray, n: int):
    """Byte ranges of the ``n`` parts over ``copies`` back-to-back copies of
    the file, as an InputSplit cuts a file list: ``ceil(total / n)`` bytes
    each; a cut inside a file moves past that file's next newline."""
    total = size * copies
    step = -(-total // n)
    cuts = []
    for t in range(n + 1):
        c = min(step * t, total)
        if c % size:
            f, o = divmod(c, size)
            c = f * size + int(nl[np.searchsorted(nl, o)]) + 1
        cuts.append(c)
    return list(zip(cuts[:-1], cuts[1:]))


def _lines(size: int, nl: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """[start, end) file offsets of the lines in list bytes [lo, hi)."""
    out = [np.zeros((0, 2), np.int64)]
    for f in range(lo // size, -(-hi // size)):
        a, b = max(lo - f * size, 0), min(hi - f * size, size)
        ends = nl[np.searchsorted(nl, a) : np.searchsorted(nl, b)] + 1
        out.append(np.stack([np.concatenate([[a], ends[:-1]]), ends], 1))
    return np.concatenate(out)


def batch_order(counts):
    """(part, batch-in-part) in the order the interleave emits them."""
    out, r = [], 0
    while any(c > r for c in counts):
        out += [(t, r) for t, c in enumerate(counts) if c > r]
        r += 1
    return out


def plain_batches(cfg: dict, traffic: dict, corpus: dict, seed: int, keys):
    B, D = int(traffic["batch"]), int(cfg["features"])
    lab = int(traffic["label_column"])
    buf = np.fromfile(corpus["path"], dtype=np.uint8)
    nl = np.flatnonzero(buf == 10)
    assert len(nl) == int(cfg["rows"]) and not (buf == 13).any()
    size, copies = len(buf), int(cfg["file_copies"])
    parts = _cuts(size, copies, nl, int(traffic["nthread"]))
    lines = {t: _lines(size, nl, lo, hi) for t, (lo, hi) in enumerate(parts)}
    order = batch_order([-(-len(v) // B) for v in lines.values()])
    out = {}
    for e, j in keys:  # every epoch reads the list in the same order
        t, r = order[j]
        spans = lines[t][r * B : (r + 1) * B]
        n = len(spans)
        text = b"".join(buf[a:z].tobytes() for a, z in spans)
        cells = np.array(text.replace(b"\n", b",").split(b",")[:-1])
        rows = cells.astype(np.float64).reshape(n, D + 1).astype(np.float32)
        x = np.zeros((B, D), np.dtype(traffic["value_dtype"]))
        labels = np.zeros(B, np.float32)
        weights = np.zeros(B, np.float32)
        x[:n] = np.delete(rows, lab, axis=1)
        labels[:n] = rows[:, lab]
        weights[:n] = 1.0
        out[(e, j)] = {"x": x, "labels": labels, "weights": weights}
    return out


# -- plain model --------------------------------------------------------------------


def logreg_loss(params, x, labels, weights):
    import jax.numpy as jnp

    s = x @ params["w"] + params["b"]
    y = (labels >= 0.5).astype(s.dtype)
    per_row = jnp.maximum(s, 0) - s * y + jnp.log1p(jnp.exp(-jnp.abs(s)))
    return jnp.sum(per_row * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


def reference_trajectory(cfg: dict, seed: int, mesh, batches, dtype: str,
                         fault: str = "") -> dict:
    """As the FM's: losses, (p0 - p1) / lr and p3 - p0 leaf norms."""
    import jax
    import jax.numpy as jnp

    lr = float(cfg["lr"])
    dt = jnp.dtype(dtype)
    p0 = jax.device_get(init_params(cfg, seed))

    @jax.jit
    def step(p, x, labels, weights):
        loss, g = jax.value_and_grad(logreg_loss)(
            p, x.astype(dt), labels.astype(dt), weights.astype(dt))
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    def norms(a, b, scale):
        return {k: float(np.sqrt(np.sum(np.square(
            (np.asarray(a[k], np.float32) - np.asarray(b[k], np.float32))
            / np.float32(scale), dtype=np.float32), dtype=np.float32)))
            for k in a}

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dt), p0)
        losses = []
        for i, b in enumerate(batches):
            w = b["weights"].copy()
            if fault == "half_batch":
                w[len(w) // 2 :] = 0
            params, loss = step(params, b["x"], b["labels"], w)
            losses.append(float(loss))
            if i == 0:
                g1 = norms(p0, jax.device_get(params), lr)
    return {"losses": losses, "g1": g1,
            "d3": norms(jax.device_get(params), p0, 1.0)}
