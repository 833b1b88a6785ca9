"""The field-aware FM (models/ffm.py) against a plain pair-by-pair
reference, and its AdaGrad row update: the streaming kernel in Pallas
interpret mode against XLA's scatter path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.models import FieldAwareFM
from dmlc_core_tpu.models import ffm

pytestmark = pytest.mark.jax


def _batch(rng, b, kk, n, m, nnz=None, dup=None):
    ids = rng.integers(0, n, (b, kk)).astype(np.int32)
    if dup is not None:
        ids[:, :dup] = ids[0, :dup]  # the same ids in every row
    return {
        "indices": ids,
        "fields": rng.integers(0, m, (b, kk)).astype(np.int32),
        "values": rng.uniform(0.5, 1.5, (b, kk)).astype(np.float16),
        "nnz": (np.full(b, kk) if nnz is None else nnz).astype(np.int32),
        "labels": rng.integers(0, 2, b).astype(np.float32),
        "weights": np.where(np.arange(b) < b - 1, 1.0, 0.0).astype(np.float32),
    }


def _pair_objective(w, batch, m, k, lam):
    """Σ weight · (logloss + l2), libffm's pair loop written out."""
    ids, fld = batch["indices"], batch["fields"]
    val = jnp.asarray(batch["values"], jnp.float32)
    b, kk = ids.shape
    rows = w[ids][..., : m * k].reshape(b, kk, m, k)
    present = jnp.arange(kk)[None, :] < batch["nnz"][:, None]
    val = jnp.where(present, val, 0.0)
    total = 0.0
    for a in range(kk):
        for c in range(a + 1, kk):
            w1 = rows[jnp.arange(b), a, fld[:, c]]  # W[j_a, f_c]
            w2 = rows[jnp.arange(b), c, fld[:, a]]  # W[j_c, f_a]
            both = present[:, a] & present[:, c]
            total = total + jnp.where(
                both, jnp.sum(w1 * w2, -1) * val[:, a] * val[:, c], 0.0)
    sq = jnp.sum(val * val, axis=1)
    s = total / jnp.where(sq > 0, sq, 1.0)
    l2 = 0.0
    for a in range(kk):
        for c in range(a + 1, kk):
            w1 = rows[jnp.arange(b), a, fld[:, c]]
            w2 = rows[jnp.arange(b), c, fld[:, a]]
            both = present[:, a] & present[:, c]
            l2 = l2 + jnp.where(both, jnp.sum(w1 * w1 + w2 * w2, -1), 0.0)
    y = batch["labels"]
    ll = jnp.maximum(s, 0) - s * y + jnp.log1p(jnp.exp(-jnp.abs(s)))
    return jnp.sum((ll + 0.5 * lam * l2) * batch["weights"])


def _pair_step(params, batch, m, k, lam, lr):
    """AdaGrad on the batch-summed gradient of ``_pair_objective``."""
    total, g = jax.value_and_grad(_pair_objective)(params["W"], batch, m, k,
                                                   lam)
    acc = params["G"] + g * g
    new = {"W": params["W"] - lr * g / jnp.sqrt(acc), "G": acc}
    return new, total / jnp.maximum(jnp.sum(batch["weights"]), 1e-9)


N, M, K_LAT, B, KK = 40, 5, 3, 12, 6


@pytest.mark.parametrize("case", ["full", "ragged", "duplicates"])
def test_ffm_steps_match_the_pair_reference(case):
    """Loss, W and G after three steps on seeded random weights."""
    rng = np.random.default_rng(3)
    model = FieldAwareFM(N, M, k=K_LAT, lam=0.05)
    params = model.init(jax.random.PRNGKey(4))
    nnz = rng.integers(0, KK + 1, B) if case == "ragged" else None
    dup = 3 if case == "duplicates" else None
    batches = [_batch(rng, B, KK, N, M, nnz, dup) for _ in range(3)]
    step = jax.jit(lambda p, b: model.adagrad_step(p, b, lr=0.3))
    got, ref = params, params
    for b in batches:
        got, loss = step(got, b)
        ref, ref_loss = _pair_step(ref, b, M, K_LAT, 0.05, 0.3)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-6)
    for k in ("W", "G"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=2e-6, atol=2e-6)


def test_ffm_adagrad_squares_each_ids_summed_gradient():
    """A batch whose rows share ids: G grows by the square of each id's
    summed gradient, which is less than the sum of the slots' squares
    (the step fails this if it squares slot by slot)."""
    rng = np.random.default_rng(5)
    model = FieldAwareFM(N, M, k=K_LAT, lam=0.05)
    params = model.init(jax.random.PRNGKey(6))
    batch = _batch(rng, B, KK, N, M, dup=KK)  # every row: the same ids
    batch["labels"][:] = 1.0
    got, _ = model.adagrad_step(params, batch, lr=0.3)
    g = jax.grad(_pair_objective)(params["W"], batch, M, K_LAT, 0.05)
    np.testing.assert_allclose(np.asarray(got["G"]),
                               np.asarray(params["G"] + g * g), rtol=2e-6)
    ids, fields = batch["indices"], batch["fields"]
    per_slot = np.zeros_like(np.asarray(params["G"]))
    for r in range(B):
        one = {k: v[r : r + 1] for k, v in batch.items()}
        gs = jax.grad(_pair_objective)(params["W"], one, M, K_LAT, 0.05)
        per_slot += np.asarray(gs) ** 2
    assert np.abs(np.asarray(got["G"]) - 1 - per_slot).max() > 1e-3


def test_ffm_permuted_fields_give_the_same_step():
    """Slots are not their fields: shuffling each row's slots with their
    ids, fields and values leaves the loss and the update unchanged."""
    rng = np.random.default_rng(7)
    model = FieldAwareFM(N, M, k=K_LAT, lam=0.05)
    params = model.init(jax.random.PRNGKey(8))
    batch = _batch(rng, B, KK, N, M)
    batch["fields"] = np.stack([rng.permutation(M)[:KK] if KK <= M else
                                rng.integers(0, M, KK) for _ in range(B)])
    perm = np.stack([rng.permutation(KK) for _ in range(B)])
    shuffled = dict(batch)
    for k in ("indices", "fields", "values"):
        shuffled[k] = np.take_along_axis(batch[k], perm, axis=1)
    a, la = model.adagrad_step(params, batch, lr=0.3)
    b, lb = model.adagrad_step(params, shuffled, lr=0.3)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for k in ("W", "G"):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-6, atol=1e-7)


def test_ffm_padding_columns_never_change():
    rng = np.random.default_rng(9)
    model = FieldAwareFM(N, M, k=K_LAT, lam=0.05)
    params = model.init(jax.random.PRNGKey(10))
    assert params["W"].shape == (N, 128)
    new, _ = model.adagrad_step(params, _batch(rng, B, KK, N, M), lr=0.3)
    e = M * K_LAT
    np.testing.assert_array_equal(np.asarray(new["W"])[:, e:], 0.0)
    np.testing.assert_array_equal(np.asarray(new["G"])[:, e:], 1.0)


# -- the AdaGrad row kernel, in Pallas interpret mode ----------------------------

ROWS = ffm.KERNEL_ROWS


def _zipf_ids(rng, n, s):
    p = np.arange(1, n + 1, dtype=np.float64) ** -0.99
    return (rng.choice(n, size=s, p=p / p.sum()) * 7919) % n


# name: (table rows, slots, ids(rng, rows, slots))
KERNEL_CASES = {
    "uniform": (2 * ROWS, 300, lambda rng, n, s: rng.integers(0, n, s)),
    "zipf": (2 * ROWS, 300, _zipf_ids),
    "ragged_table": (2 * ROWS + 37, 300, lambda rng, n, s: rng.integers(0, n, s)),
    "empty_block": (3 * ROWS, 300,
                    lambda rng, n, s: rng.integers(0, ROWS, s) * (1 + 2 * (
                        rng.uniform(size=s) < 0.5)) % n),
    # more slots than one chunk of sorted ids: blocks that span chunks
    "id_chunks": (3 * ROWS + 37, 2600, lambda rng, n, s: rng.integers(0, n, s)),
    "outside": (ROWS + 37, 300,
                lambda rng, n, s: np.where(rng.uniform(size=s) < 0.1, n + 5,
                                           rng.integers(0, n, s))),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_ffm_kernel_update_matches_scatter(case):
    from jax.experimental.pallas import tpu as pltpu

    n, s, make_ids = KERNEL_CASES[case]
    rng = np.random.default_rng(12)
    ids = make_ids(rng, n, s).astype(np.int32)
    w = rng.normal(size=(n, 128)).astype(np.float32)
    g = rng.uniform(1, 2, size=(n, 128)).astype(np.float32)
    rows = rng.normal(size=(s, 128)).astype(np.float32)
    update = jax.jit(lambda *a, kernel: ffm._adagrad_rows(*a, lr=0.2,
                                                          kernel=kernel),
                     static_argnames="kernel")
    want = update(w, g, ids, rows, kernel=False)
    with pltpu.force_tpu_interpret_mode():
        got = update(w, g, ids, rows, kernel=True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    untouched = np.setdiff1d(np.arange(n), ids)
    for a, table in zip(got, (w, g)):
        np.testing.assert_array_equal(np.asarray(a)[untouched], table[untouched])


@pytest.mark.parametrize("platform,mesh_shape,width,path", [
    ("tpu", None, 256, "sparse-kernel"),
    ("cpu", None, 256, "sparse"),
    ("tpu", (2,), 256, "sparse"),
    ("tpu", None, 1 << 12, "sparse"),
], ids=["one_tpu", "cpu", "mesh", "rows_past_vmem"])
def test_ffm_update_path_rule(platform, mesh_shape, width, path):
    from dmlc_core_tpu.parallel import make_mesh

    mesh = (jax.sharding.get_abstract_mesh() if mesh_shape is None else
            make_mesh(mesh_shape, ("data",),
                      devices=jax.devices("cpu")[:2]).abstract_mesh)
    assert ffm._update_path(platform, mesh, width) == path


def test_ffm_step_kernel_path(monkeypatch):
    """The step steered onto the kernel path, as one TPU chip takes it,
    against the XLA path; one counter tick a trace names the path."""
    from jax.experimental.pallas import tpu as pltpu

    from dmlc_core_tpu.telemetry import default_registry

    rng = np.random.default_rng(13)
    model = FieldAwareFM(3 * ROWS, M, k=K_LAT, lam=0.05)
    params = model.init(jax.random.PRNGKey(14))
    batches = [_batch(rng, 64, KK, 3 * ROWS, M, dup=2) for _ in range(3)]
    ref = params
    xla = jax.jit(lambda p, b: model.adagrad_step(p, b, lr=0.3))
    for b in batches:
        ref, _ = xla(ref, b)
    rule = ffm._update_path
    monkeypatch.setattr(ffm, "_update_path", lambda _p, *a: rule("tpu", *a))
    view = default_registry().scoped("models.ffm.")
    step = jax.jit(lambda p, b: model.adagrad_step(p, b, lr=0.3))
    got = params
    with pltpu.force_tpu_interpret_mode():
        for b in batches:
            got, _ = step(got, b)
    ticks = {k: v for k, v in view.delta().items() if v}
    assert ticks == {'models.ffm.update_path{path="sparse-kernel"}': 1.0}
    for k in ("W", "G"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=1e-6, atol=1e-6)
