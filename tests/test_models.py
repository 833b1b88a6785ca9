"""Models/ops/parallel tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dmlc_core_tpu.models import (
    FactorizationMachine,
    LinearRegression,
    LogisticRegression,
)
from dmlc_core_tpu.models.fm import KERNEL_COLS
from dmlc_core_tpu.ops import ell_matvec, ell_to_dense, weighted_mean
from dmlc_core_tpu.parallel import data_parallel_step, make_mesh, shard_params
from dmlc_core_tpu.staging import BatchSpec, FixedShapeBatcher
from dmlc_core_tpu.data.row_block import RowBlock


def synth_batch(rng, batch=64, k=6, d=32, w_true=None):
    """Linearly separable ELL batch."""
    idx = np.stack(
        [rng.choice(d, size=k, replace=False) for _ in range(batch)]
    ).astype(np.int32)
    val = rng.normal(size=(batch, k)).astype(np.float32)
    if w_true is None:
        w_true = rng.normal(size=d).astype(np.float32)
    scores = (val * w_true[idx]).sum(axis=1)
    return {
        "indices": idx,
        "values": val,
        "nnz": np.full(batch, k, np.int32),
        "labels": (scores > 0).astype(np.float32),
        "weights": np.ones(batch, np.float32),
    }, w_true


# -- ops ---------------------------------------------------------------------

def test_ell_matvec_matches_dense():
    rng = np.random.default_rng(0)
    batch, _ = synth_batch(rng, batch=16, k=4, d=20)
    w = rng.normal(size=20).astype(np.float32)
    out = ell_matvec(batch["indices"], batch["values"], w)
    dense = np.zeros((16, 20), np.float32)
    for b in range(16):
        for j in range(4):
            dense[b, batch["indices"][b, j]] += batch["values"][b, j]
    np.testing.assert_allclose(np.asarray(out), dense @ w, rtol=1e-5)


def test_ell_to_dense_matches_batcher():
    blk = RowBlock(
        offset=np.array([0, 2, 3]),
        label=np.array([1.0, 0.0], np.float32),
        index=np.array([1, 1, 4], np.uint64),  # duplicate accumulates
        value=np.array([0.5, 0.25, 2.0], np.float32),
    )
    spec = BatchSpec(batch_size=2, layout="dense", num_features=8)
    (host,) = list(FixedShapeBatcher(spec).push(blk))
    spec_ell = BatchSpec(batch_size=2, layout="ell", max_nnz=2)
    (ell,) = list(FixedShapeBatcher(spec_ell).push(blk))
    dev = ell_to_dense(jnp.asarray(ell.indices), jnp.asarray(ell.values), 8)
    np.testing.assert_allclose(np.asarray(dev), host.x, rtol=1e-6)


def test_weighted_mean_masks_padding():
    per_row = jnp.array([1.0, 2.0, 100.0])
    w = jnp.array([1.0, 1.0, 0.0])
    assert float(weighted_mean(per_row, w)) == pytest.approx(1.5)


# -- models ------------------------------------------------------------------

def test_logistic_learns_separable():
    rng = np.random.default_rng(1)
    model = LogisticRegression(num_features=32)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.5))
    batch0, w_true = synth_batch(rng, batch=128, d=32)
    first_loss = float(model.loss(params, batch0))
    for _ in range(200):
        batch, _ = synth_batch(rng, batch=128, d=32, w_true=w_true)
        params, loss = step(params, batch)
    assert float(loss) < first_loss * 0.5
    test, _ = synth_batch(rng, batch=256, d=32, w_true=w_true)
    acc = float(model.accuracy(params, test))
    assert acc > 0.9, acc


def test_linear_regression_fits():
    rng = np.random.default_rng(2)
    model = LinearRegression(num_features=16)
    params = model.init(jax.random.PRNGKey(0))
    w_true = rng.normal(size=16).astype(np.float32)
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.3))
    for _ in range(100):
        batch, _ = synth_batch(rng, batch=64, k=4, d=16, w_true=w_true)
        scores = (batch["values"] * w_true[batch["indices"]]).sum(axis=1)
        batch["labels"] = scores.astype(np.float32)  # regression targets
        params, loss = step(params, batch)
    assert float(loss) < 0.05


def test_fm_loss_decreases():
    rng = np.random.default_rng(3)
    model = FactorizationMachine(num_features=32, embed_dim=4)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.2))
    batch, w_true = synth_batch(rng, batch=128, d=32)
    first = float(model.loss(params, batch))
    for _ in range(80):
        b, _ = synth_batch(rng, batch=128, d=32, w_true=w_true)
        params, loss = step(params, b)
    assert float(loss) < first


def fm_batches(rng, n, d, batch=16, dense=13, categorical=7):
    """Criteo-layout ELL batches: ids 0..dense-1 in every row (the dense
    fields), then categorical ids from a small range, so ids repeat
    within and across rows."""
    out = []
    for _ in range(n):
        cat = rng.integers(dense, d, size=(batch, categorical))
        idx = np.concatenate(
            [np.tile(np.arange(dense), (batch, 1)), cat], axis=1
        ).astype(np.int32)
        val = np.concatenate(
            [rng.normal(size=(batch, dense)), np.ones((batch, categorical))],
            axis=1,
        ).astype(np.float32)
        out.append({
            "indices": idx,
            "values": val,
            "nnz": np.full(batch, dense + categorical, np.int32),
            "labels": rng.integers(0, 2, batch).astype(np.float32),
            "weights": np.ones(batch, np.float32),
        })
    return out


def _dense_sgd(model, lr):
    """p − lr·∇p with the gradient over the whole tables."""
    return jax.jit(lambda p, b: jax.tree_util.tree_map(
        lambda x, g: x - lr * g, p, jax.grad(model.loss)(p, b)))


def _with_ids(rng, batches, make_ids):
    """``batches`` with their indices replaced by ``make_ids(rng, shape)``."""
    return [dict(b, indices=make_ids(rng, b["indices"].shape).astype(np.int32))
            for b in batches]


def _distinct(n):
    """Ids 0..n-1 each at least once, the other slots on them at random."""
    def make(rng, shape):
        ids = np.concatenate([rng.permutation(n),
                              rng.integers(0, n, int(np.prod(shape)) - n)])
        return rng.permutation(ids).reshape(shape)
    return make


def _zipf_fields(rng, shape):
    """Criteo-like rows over tiny per-field vocabularies, Zipf(0.99) ranks
    in each field's range (the dense ids 0..12 in every row)."""
    vocab = [2, 3, 5, 7, 40, 60]
    out = np.tile(np.arange(shape[1]), (shape[0], 1))
    off = 13
    for f, n in zip(range(13, shape[1]), vocab * 2):
        p = np.arange(1, n + 1, dtype=np.float64) ** -0.99
        r = rng.choice(n, size=shape[0], p=p / p.sum())
        out[:, f] = off + r
        off += n
    return out


# batch shape 16 x 20 (fm_batches): 320 slots. The sparse paths scatter
# every slot, and duplicate ids must sum as the dense formula sums them:
# one id in every slot, a few hot ids, Zipf fields, and 80, 81, 120 and
# 121 distinct ids over the 320 slots
FM_IDS = {
    "criteo": None,
    # one row takes all 320 slots' gradients: at lr 0.5 its three steps
    # are large enough that float32 rounding of the sums, in any order,
    # grows past 1e-6 (against float64, after three steps: the dense
    # formula 8.8e-7, one scatter row per slot 1.9e-5), so this case
    # steps at a tenth of that
    "one_id": lambda rng, shape: np.full(shape, 5),
    "three_ids": lambda rng, shape: np.where(
        rng.uniform(size=shape) < 0.5, rng.choice([7, 40, 99], size=shape),
        rng.integers(0, 120, size=shape)),
    "zipf": _zipf_fields,
    "distinct_80": _distinct(80),
    "distinct_81": _distinct(81),
    "distinct_120": _distinct(120),
    "distinct_121": _distinct(121),
}


@pytest.mark.parametrize("l2,mesh_shape,path,ids", [
    (0.0, None, "sparse", "criteo"),
    (1e-3, None, "dense", "criteo"),
    (0.0, (2, 2), "sparse-replicated", "criteo"),
    (0.0, None, "sparse", "one_id"),
    (0.0, None, "sparse", "three_ids"),
    (0.0, None, "sparse", "zipf"),
    (0.0, None, "sparse", "distinct_80"),
    (0.0, None, "sparse", "distinct_81"),
    (0.0, None, "sparse", "distinct_120"),
    (0.0, None, "sparse", "distinct_121"),
    (0.0, (2, 2), "sparse-replicated", "zipf"),
], ids=["sparse", "l2_dense", "mesh_2x2", "one_id", "three_ids", "zipf",
        "distinct_80", "distinct_81", "distinct_120", "distinct_121",
        "mesh_2x2_zipf"])
def test_fm_sgd_step_update_paths(l2, mesh_shape, path, ids):
    from dmlc_core_tpu.telemetry import default_registry

    lr = 0.05 if ids == "one_id" else 0.5
    d = 128
    model = FactorizationMachine(num_features=d, embed_dim=8, l2=l2)
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(8)
    batches = fm_batches(rng, 3, d)
    if FM_IDS[ids] is not None:
        batches = _with_ids(rng, batches, FM_IDS[ids])
    if mesh_shape is None or ids != "criteo":
        ref_step = _dense_sgd(model, lr)
    else:  # the mesh step against the one-device step
        ref_step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=lr)[0])
    ref = params
    for b in batches:
        ref = ref_step(ref, b)
    ref = jax.device_get(ref)

    view = default_registry().scoped("models.fm.")
    train = lambda p, b: model.sgd_step(p, b, lr=lr)  # noqa: E731
    if mesh_shape is None:
        step = jax.jit(train)
    else:
        mesh = make_mesh(mesh_shape, ("data", "model"),
                         devices=jax.devices("cpu")[:4])
        rules = {"v": P(None, "model")}
        step = data_parallel_step(train, mesh, donate_params=False,
                                  param_rules=rules)
        # placed as the step leaves them, so the step traces once
        params = shard_params(params, mesh, rules)
    got = params
    for b in batches:
        got, _loss = step(got, b)
    ticks = {k: v for k, v in view.delta().items() if v}
    assert ticks == {f'models.fm.update_path{{path="{path}"}}': 1.0}
    for k in ("w", "v", "b"):
        if path == "dense":  # the l2 step is exactly the dense formula
            np.testing.assert_array_equal(np.asarray(got[k]), ref[k])
        else:
            np.testing.assert_allclose(np.asarray(got[k]), ref[k],
                                       rtol=0, atol=1e-6)
    # rows no batch touched keep their init on the sparse paths
    untouched = np.setdiff1d(np.arange(d),
                             np.concatenate([b["indices"] for b in batches]))
    assert untouched.size
    if path != "dense":
        for k in ("w", "v"):
            np.testing.assert_array_equal(np.asarray(got[k])[untouched],
                                          np.asarray(params[k])[untouched])



# -- the one-chip row update kernel, in Pallas interpret mode ----------------

def _hot_ids(rng, n, s):
    """Zipf(0.99) ranks over the table, scrambled: a few ids take most
    slots."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -0.99
    return (rng.choice(n, size=s, p=p / p.sum()) * 7919) % n


def _skip_block(rng, n, s):
    """Ids in the first and third ``KERNEL_COLS`` blocks only."""
    ids = rng.integers(0, KERNEL_COLS, size=s)
    return np.where(rng.uniform(size=s) < 0.5, ids, ids + 2 * KERNEL_COLS)


def _table_ends(rng, n, s):
    ids = rng.integers(0, n, size=s)
    ids[::7], ids[3::11] = 0, n - 1
    return ids


# name: (table rows, E, slots, ids(rng, rows, slots))
KERNEL_CASES = {
    "uniform": (2 * KERNEL_COLS, 16, 384,
                lambda rng, n, s: rng.integers(0, n, size=s)),
    "zipf": (2 * KERNEL_COLS, 16, 300, _hot_ids),
    "ragged_table": (2 * KERNEL_COLS + 77, 16, 300,
                     lambda rng, n, s: rng.integers(0, n, size=s)),
    "empty_block": (3 * KERNEL_COLS, 16, 300, _skip_block),
    "table_ends": (KERNEL_COLS + 77, 16, 300, _table_ends),
    "embed_8": (KERNEL_COLS + 77, 8, 300, _table_ends),
    # E 8 (a 2x2 v shard): blocks of 2 * KERNEL_COLS rows, the ids in
    # several SMEM chunks
    "embed_8_blocks": (5 * KERNEL_COLS + 77, 8, 2600, _table_ends),
    # more slots than one SMEM chunk of ids: blocks that span chunks
    "id_chunks": (3 * KERNEL_COLS + 77, 16, 2600,
                  lambda rng, n, s: rng.integers(0, n, size=s)),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_fm_kernel_update_matches_scatter(case):
    from jax.experimental.pallas import tpu as pltpu

    from dmlc_core_tpu.models import fm

    n, e, s, make_ids = KERNEL_CASES[case]
    rng = np.random.default_rng(11)
    ids = make_ids(rng, n, s).astype(np.int32)
    w = rng.normal(size=n).astype(np.float32)
    v = rng.normal(size=(n, e)).astype(np.float32)
    gw = rng.normal(size=s).astype(np.float32)
    gv = rng.normal(size=(s, e)).astype(np.float32)
    lr = 0.5
    update = jax.jit(lambda *a: fm._update_rows(*a, lr=lr, kernel=True))
    with pltpu.force_tpu_interpret_mode():
        got_w, got_v = update(w, v, ids, gw, gv)
    for got, table, rows in ((got_w, w, gw), (got_v, v, gv)):
        want = np.asarray(jnp.asarray(table).at[ids].add(-lr * rows))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())
    untouched = np.setdiff1d(np.arange(n), ids)
    np.testing.assert_array_equal(np.asarray(got_v)[untouched], v[untouched])


@pytest.mark.parametrize("platform,mesh_shape,l2,embed,slots,path", [
    ("tpu", None, 0.0, 16, 8192 * 39, "sparse-kernel"),
    ("tpu", None, 0.0, 8, 300, "sparse-kernel"),
    ("cpu", None, 0.0, 16, 8192 * 39, "sparse"),
    ("tpu", (2, 2), 0.0, 16, 8192 * 39, "sparse-kernel-sharded"),
    ("tpu", None, 1e-3, 16, 8192 * 39, "dense"),
    ("cpu", (2, 2), 1e-3, 16, 8192 * 39, "dense"),
    ("tpu", None, 0.0, 128, 8192 * 39, "sparse"),
    ("tpu", None, 0.0, 16, 1 << 20, "sparse"),
    # the mesh's local [8, S] rows: 2^21 slots pass the VMEM budget
    ("tpu", (2, 2), 0.0, 16, 1 << 21, "sparse-replicated"),
    ("cpu", (2, 2), 0.0, 16, 8192 * 39, "sparse-replicated"),
    ("tpu", (4,), 0.0, 16, 8192 * 39, "sparse-replicated"),
], ids=["one_tpu", "one_tpu_small", "cpu", "mesh", "l2", "cpu_mesh_l2",
        "embed_128", "rows_past_vmem", "mesh_rows_past_vmem", "cpu_mesh",
        "mesh_no_model_axis"])
def test_fm_update_path_rule(platform, mesh_shape, l2, embed, slots, path):
    from dmlc_core_tpu.models import fm

    if mesh_shape is None:
        mesh = jax.sharding.get_abstract_mesh()
    else:
        axes = ("data", "model")[:len(mesh_shape)]
        mesh = make_mesh(mesh_shape, axes,
                         devices=jax.devices("cpu")[:4]).abstract_mesh
    assert fm._update_path(platform, mesh, l2, embed, slots) == path


def test_fm_sgd_step_kernel_path(monkeypatch):
    """The step on the kernel path (steered to it as on one TPU chip)
    against the dense formula."""
    from jax.experimental.pallas import tpu as pltpu

    from dmlc_core_tpu.models import fm
    from dmlc_core_tpu.telemetry import default_registry

    rule = fm._update_path
    monkeypatch.setattr(fm, "_update_path",
                        lambda _platform, *a: rule("tpu", *a))
    lr, d = 0.5, 200
    model = FactorizationMachine(num_features=d, embed_dim=8)
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(8)
    batches = _with_ids(rng, fm_batches(rng, 3, d), _zipf_fields)
    ref_step = _dense_sgd(model, lr)
    ref = params
    for b in batches:
        ref = ref_step(ref, b)
    view = default_registry().scoped("models.fm.")
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=lr))
    got = params
    with pltpu.force_tpu_interpret_mode():
        for b in batches:
            got, _loss = step(got, b)
    ticks = {k: v for k, v in view.delta().items() if v}
    assert ticks == {'models.fm.update_path{path="sparse-kernel"}': 1.0}
    for k in ("w", "v", "b"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("ids", ["criteo", "zipf"])
def test_fm_sgd_step_sharded_kernel_path(monkeypatch, ids):
    """The 2x2 data x model step on the sharded kernel path (steered to
    it as on a TPU mesh: each device's v shard written by the kernel)
    against the dense formula; zipf puts duplicate ids on every device."""
    from jax.experimental.pallas import tpu as pltpu

    from dmlc_core_tpu.models import fm
    from dmlc_core_tpu.telemetry import default_registry

    rule = fm._update_path
    monkeypatch.setattr(fm, "_update_path",
                        lambda _platform, *a: rule("tpu", *a))
    lr, d = 0.5, 200
    # E 16 split over model: each device's shard is [d, 8], as on the chip
    model = FactorizationMachine(num_features=d, embed_dim=16)
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(8)
    batches = fm_batches(rng, 3, d)
    if FM_IDS[ids] is not None:
        batches = _with_ids(rng, batches, FM_IDS[ids])
    ref_step = _dense_sgd(model, lr)
    ref = params
    for b in batches:
        ref = ref_step(ref, b)
    view = default_registry().scoped("models.fm.")
    mesh = make_mesh((2, 2), ("data", "model"),
                     devices=jax.devices("cpu")[:4])
    rules = {"v": P(None, "model")}
    step = data_parallel_step(lambda p, b: model.sgd_step(p, b, lr=lr),
                              mesh, donate_params=False, param_rules=rules)
    got = shard_params(params, mesh, rules)
    with pltpu.force_tpu_interpret_mode():
        for b in batches:
            got, _loss = step(got, b)
    ticks = {k: v for k, v in view.delta().items() if v}
    assert ticks == {'models.fm.update_path{path="sparse-kernel-sharded"}':
                     1.0}
    for k in ("w", "v", "b"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=0, atol=1e-6)
    untouched = np.setdiff1d(np.arange(d),
                             np.concatenate([b["indices"] for b in batches]))
    assert untouched.size
    for k in ("w", "v"):
        np.testing.assert_array_equal(np.asarray(got[k])[untouched],
                                      np.asarray(params[k])[untouched])

def test_dense_layout_forward_matches_ell():
    rng = np.random.default_rng(4)
    model = LogisticRegression(num_features=16)
    params = model.init(jax.random.PRNGKey(1))
    ell, _ = synth_batch(rng, batch=8, k=3, d=16)
    dense_x = np.zeros((8, 16), np.float32)
    for b in range(8):
        for j in range(3):
            dense_x[b, ell["indices"][b, j]] += ell["values"][b, j]
    dense = {
        "x": dense_x, "labels": ell["labels"], "weights": ell["weights"],
    }
    np.testing.assert_allclose(
        np.asarray(model.forward(params, ell)),
        np.asarray(model.forward(params, dense)),
        rtol=1e-5,
    )


# -- parallel ----------------------------------------------------------------

def test_make_mesh_shapes():
    mesh = make_mesh(devices=jax.devices("cpu"))
    assert mesh.devices.shape == (8,) and mesh.axis_names == ("data",)
    mesh2 = make_mesh((4, -1), ("data", "model"), devices=jax.devices("cpu"))
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(Exception, match="mesh shape"):
        make_mesh((3, 2), ("data", "model"), devices=jax.devices("cpu"))


def test_data_parallel_step_matches_single_device():
    rng = np.random.default_rng(5)
    model = LogisticRegression(num_features=32)
    params = model.init(jax.random.PRNGKey(0))
    batch, _ = synth_batch(rng, batch=64, d=32)

    def train(p, b):
        return model.sgd_step(p, b, lr=0.5)

    single_params, single_loss = jax.jit(train)(params, batch)
    mesh = make_mesh(devices=jax.devices("cpu"))
    spmd = data_parallel_step(train, mesh, donate_params=False)
    spmd_params, spmd_loss = spmd(params, batch)
    assert float(spmd_loss) == pytest.approx(float(single_loss), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(spmd_params["w"]), np.asarray(single_params["w"]), rtol=1e-5
    )
    # batch really lands sharded over the 8 devices
    assert len(spmd_params["w"].sharding.device_set) == 8


def test_tensor_parallel_fm_matches_replicated():
    rng = np.random.default_rng(6)
    model = FactorizationMachine(num_features=32, embed_dim=8)
    params = model.init(jax.random.PRNGKey(0))
    batch, _ = synth_batch(rng, batch=32, d=32)

    def train(p, b):
        return model.sgd_step(p, b, lr=0.1)

    ref_params, ref_loss = jax.jit(train)(params, batch)
    mesh = make_mesh((4, 2), ("data", "model"), devices=jax.devices("cpu"))
    spmd = data_parallel_step(
        train, mesh, param_rules={"v": P(None, "model")}, donate_params=False
    )
    tp_params, tp_loss = spmd(params, batch)
    assert float(tp_loss) == pytest.approx(float(ref_loss), rel=1e-4)
    np.testing.assert_allclose(
        np.asarray(tp_params["v"]), np.asarray(ref_params["v"]), rtol=1e-4
    )


# -- driver entry points -----------------------------------------------------

def test_graft_entry_compiles():
    import __graft_entry__ as ge

    fn, (params, batch) = ge.entry()
    out = jax.jit(fn)(params, batch)
    assert np.asarray(out).shape == (8,)
    assert np.all(np.isfinite(np.asarray(out)))


def test_dryrun_multichip_8():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_dryrun_multichip_odd():
    import __graft_entry__ as ge

    ge.dryrun_multichip(1)
