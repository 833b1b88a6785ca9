"""The ``criteo_fm_zipf`` corpus (scrambled Zipf ids over the Criteo
Kaggle per-field vocabularies) and the ``table_write_ms`` reader."""

import json
import os

import numpy as np
import pytest
from bench_chip_helpers import BENCH, tiny

import harness

pytestmark = pytest.mark.jax

CONFIG = os.path.join(BENCH, "configs", "criteo_fm_zipf", "config.json")
SAMPLE_ROWS = 1 << 16


def _gen():
    return harness._load(os.path.join(BENCH, "configs", "criteo_fm_zipf",
                                      "generate.py"), "bench_zipf_generate")


def _cfg(tiny_sizes=False):
    cfg = json.load(open(CONFIG))
    if tiny_sizes:
        cfg.update(tiny("criteo_fm_zipf.seq")["config"])
    return cfg


@pytest.fixture(scope="module")
def sample():
    """2^16 rows at the published vocabularies."""
    cfg = _cfg()
    idx, _val, _labels = _gen().rows_of(cfg, 2**33 + 17, SAMPLE_ROWS)
    return cfg, idx


def test_config_is_the_published_vocabularies():
    cfg = _cfg()
    assert len(cfg["vocab"]) == cfg["categorical_fields"] == 26
    assert cfg["n_features"] == cfg["dense_fields"] + sum(cfg["vocab"]) == 33762590
    assert cfg["reduced"] == ["rows"]
    t = _cfg(tiny_sizes=True)
    assert t["n_features"] == 13 + sum(t["vocab"])
    assert min(t["vocab"]) < 8 and max(t["vocab"]) >= 1000


@pytest.mark.parametrize("tiny_sizes", [False, True], ids=["published", "tiny"])
def test_every_id_lies_in_its_fields_range(sample, tiny_sizes):
    if tiny_sizes:
        cfg = _cfg(True)
        idx = _gen().rows_of(cfg, 5, 4096)[0]
    else:
        cfg, idx = sample
    assert (idx[:, :13] == np.arange(13)).all()
    off = _gen().offsets(cfg["vocab"])
    for f, n in enumerate(cfg["vocab"]):
        col = idx[:, 13 + f].astype(np.int64)
        assert col.min() >= off[f] and col.max() < off[f] + n, f
    assert idx.max() < cfg["n_features"]


@pytest.mark.parametrize("field", [0, 2, 6])  # vocab 1460, 10131227, 12517
def test_rank_frequency_slope_is_minus_s(sample, field):
    cfg, idx = sample
    assert cfg["vocab"][field] >= 1000
    counts = np.sort(np.unique(idx[:, 13 + field], return_counts=True)[1])[::-1]
    top = counts[:100].astype(np.float64)  # ranks 1..100: 40+ draws each
    slope = np.polyfit(np.log(np.arange(1, top.size + 1)), np.log(top), 1)[0]
    assert abs(slope + cfg["zipf_s"]) <= 0.1, slope


def test_hot_ids_are_scrambled_off_the_fields_start(sample):
    cfg, idx = sample
    off = _gen().offsets(cfg["vocab"])
    for f in range(len(cfg["vocab"])):
        ids, counts = np.unique(idx[:, 13 + f], return_counts=True)
        assert ids[np.argmax(counts)] != off[f], f


@pytest.mark.parametrize("n", [2, 3, 4, 24, 1460, 12517])
def test_scramble_is_a_bijection_of_the_range(n):
    got = _gen().scramble(np.arange(n), n)
    assert sorted(got.tolist()) == list(range(n))
    assert got[0] != 0


def test_same_seed_same_bytes(tmp_path, capsys):
    cfg = _cfg(True)
    paths = []
    for d in ("a", "b"):
        (tmp_path / d).mkdir()
        corpus = _gen().generate(cfg, {"batch": 256}, 2**31 + 3, str(tmp_path / d))
        paths.append((corpus["path"], corpus["index"]))
    for a, b in zip(*paths):
        assert open(a, "rb").read() == open(b, "rb").read()
    assert "distinct ids per batch" in capsys.readouterr().err


def test_frames_are_what_write_rowrec_writes(tmp_path):
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.io.stream import FileStream

    cfg = dict(_cfg(True), rows=700)
    seed = 2**32 + 11
    corpus = _gen().generate(cfg, {}, seed, str(tmp_path))
    idx, val, labels = _gen().rows_of(cfg, seed, 700)
    k = idx.shape[1]
    blk = RowBlock(offset=np.arange(701, dtype=np.int64) * k, label=labels,
                   index=idx.reshape(-1), value=val.reshape(-1))
    ref = str(tmp_path / "ref.rec")
    with FileStream(ref, "w") as f, FileStream(ref + ".idx", "w") as fi:
        write_rowrec(f, [blk], index_stream=fi)
    assert open(corpus["path"], "rb").read() == open(ref, "rb").read()
    assert open(corpus["index"]).read() == open(ref + ".idx").read()


# -- table_write_ms -----------------------------------------------------------


def _record(ops, n_features, count=10, step="jit_fm_train_step"):
    programs = {"jit_unpack": {"seconds": 0.01, "count": count}}
    if step:
        programs[step] = {"seconds": 0.5, "count": count}
    return {"step_name": "jit_fm_train_step", "cfg": {"n_features": n_features},
            "trace": {"programs": programs, "device_ops": ops}}


def _read():
    return harness.metric_reader(BENCH, "table_write_ms")


def test_table_write_ms_sums_the_one_chip_tables():
    ops = [
        ["jit_fm_train_step/%fusion.3 f32[16777216,16] fusion", 0.12],  # v scatter
        ["jit_fm_train_step/%fusion.2 f32[16777216] fusion", 0.01],  # w scatter
        ["jit_fm_train_step/%copy.7 f32[16777216,16] copy", 0.012],  # output copy
        ["jit_fm_train_step/%fusion.1 f32[319488,16] fusion", 0.03],  # rows
        ["jit_fm_train_step/%fusion.9 f32[167772160] fusion", 0.5],  # not a table
        ["jit_fm_train_step/%conditional (f32[16777216], f32[16777216,16]) "
         "conditional", 0.13],  # holds the scatters above
        ["jit_unpack/%copy.1 f32[16777216] copy", 0.5],  # another program
    ]
    got = _read()(_record(ops, 1 << 24))
    assert got == pytest.approx(1e3 * (0.12 + 0.01 + 0.012) / 10)


def test_table_write_ms_reads_the_mesh_shard():
    ops = [
        ["jit_fm_train_step/%fusion.2 f32[67108864,8] fusion", 0.11],
        ["jit_fm_train_step/%copy.8 f32[67108864,8] copy", 0.028],
        ["jit_fm_train_step/%fusion.3 f32[67108864] fusion", 0.014],
        ["jit_fm_train_step/%fusion f32[159744] fusion", 0.012],
    ]
    got = _read()(_record(ops, 1 << 26, count=4))
    assert got == pytest.approx(1e3 * (0.11 + 0.028 + 0.014) / 4)


def test_table_write_ms_is_zero_without_table_ops_and_none_without_the_step():
    ops = [["jit_fm_train_step/fusion", 0.1]]
    assert _read()(_record(ops, 1 << 24)) == 0.0
    assert _read()(_record(ops, 1 << 24, step=None)) is None
