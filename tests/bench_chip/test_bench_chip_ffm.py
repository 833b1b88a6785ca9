"""The libffm config (``configs/criteo_ffm``) on the CPU at tiny sizes:
its corpus is libffm text as the Criteo winners' converter writes it, its
cell runs ``correct`` traced and untraced, ``correct`` fails the control
and a broken timed path, and the row-update readers (``update_ms``,
``update_roofline``) read the FFM kernel and the FM's update alike."""

import json
import os

import numpy as np
import pytest
from bench_chip_helpers import BENCH, CANNED_TRACE, REPO, run_tiny, tiny
from test_bench_chip_correct import FAULTS

import harness

pytestmark = pytest.mark.jax

CELL = "criteo_ffm.libffm"


def _cell(rows=2000):
    cell = harness.Cell(BENCH, CELL)
    cell.cfg.update(tiny(CELL)["config"], rows=rows)
    return cell


def test_corpus_is_one_libffm_token_a_field(tmp_path):
    cell = _cell()
    gen = cell.module("generate")
    corpus = gen.generate(cell.cfg, {}, 2**31 + 11, str(tmp_path))
    bins, labels = gen.rows_of(cell.cfg, 2**31 + 11, 2000)
    lines = open(corpus["path"], "rb").read().split(b"\n")
    assert lines[-1] == b"" and len(lines) == 2001
    for r, line in enumerate(lines[:-1]):
        toks = line.split(b" ")
        assert toks[0] == b"%d" % labels[r]
        assert [t.split(b":") for t in toks[1:]] == [
            [b"%d" % f, b"%d" % bins[r, f], b"1"] for f in range(39)]
    n = cell.cfg["n_features"]
    assert bins.min() >= 1 and bins.max() < n


def test_integer_values_are_bucketed_as_the_converter_does():
    gen = _cell().module("generate")
    v = np.array([0, 1, 2, 3, 7, 100, 10**6])
    got = gen.bucket(v)
    assert list(got[3:]) == [int(np.log(x) ** 2) for x in v[3:]]  # 1, 3, 21, 190
    # v <= 2 stays apart from a bucket of the same number
    assert len(set(got[:3]) | set(got[3:])) == len(v)


def test_same_seed_same_text(tmp_path):
    cell = _cell()
    gen = cell.module("generate").generate
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    a = gen(cell.cfg, {}, 2**32 + 1, str(tmp_path / "a"))["path"]
    b = gen(cell.cfg, {}, 2**32 + 1, str(tmp_path / "b"))["path"]
    assert open(a, "rb").read() == open(b, "rb").read()


def _ffm_trace(n):
    """A reduced trace of the FFM step as the chip gives it: the kernel,
    the pair term and the row gather, by result type."""
    step = "jit_ffm_train_step"
    return dict(CANNED_TRACE, programs={
        step: {"seconds": 0.15, "count": 30},
        "jit_unpack": {"seconds": 0.03, "count": 30}},
        device_ops=[
            [f"{step}/%ffm_adagrad_rows.1 (f32[{n},256], f32[{n},256]) "
             "custom-call", 0.06],
            [f"{step}/%multiply_reduce_fusion f32[39,4,39,64] fusion", 0.05],
            [f"{step}/%fusion.1 f32[2496,256] fusion", 0.03],
            [f"{step}/%while.3 (s32[], f32[{n},256]) while", 0.5]])


def test_cell_runs_correct_traced_with_every_listed_metric():
    n = tiny(CELL)["config"]["n_features"]
    out = run_tiny(CELL, trace=True, hooks={"reduce": lambda _d: _ffm_trace(n)})
    assert out["correct"] is True, out["checks"]
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(out["metrics"]) == want
    assert "table_write_ms" not in want
    # the kernel alone writes the tables: 0.06 s over 30 steps
    assert out["metrics"]["update_ms"]["value"] == pytest.approx(2.0)
    cfg = harness.Cell(BENCH, CELL).cfg
    cfg.update(tiny(CELL)["config"])
    batch = tiny(CELL)["traffic"]["batch"]
    required = batch * 39 * 39 * 4 * 4 * 5  # slots · m·k floats · 4 B · 5 moves
    assert out["metrics"]["update_roofline"]["value"] == pytest.approx(
        100 * 30 * required / 819e9 / 0.06)


def test_control_fails_and_the_program_passes():
    r = harness.Run(CELL, 2**31 + 99, platform="cpu", bench_dir=BENCH,
                    overrides=tiny(CELL))
    try:
        r.setup()
        r.params = None
        r.feed.close()
        first = r.first_plain()
        ref = r.reference(first)
        limits = r.cfg["limits"]
        prog = harness.gaps(r.prog_traj, ref)
        assert all(prog[k] <= limits[k] for k in limits), prog
        for dtype, fault in (("bfloat16", ""), ("float32", "half_batch")):
            bad = harness.gaps(r.reference(first, dtype, fault), ref)
            assert any(bad[k] > limits[k] for k in limits), (dtype, fault, bad)
    finally:
        r.close()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_incorrect(fault):
    out = run_tiny(CELL, seconds=2.0, hooks=FAULTS[fault])
    assert out["correct"] is False, out["checks"]


def _read(name, record):
    return harness.metric_reader(BENCH, name)(record)


@pytest.mark.parametrize("workload,row_bytes", [
    ("criteo_ffm.libffm", 39 * 4 * 4 * 5),
    ("criteo_fm.seq", (1 + 16) * 4 * 3),
    ("criteo_fm_2x2.seq", (1 + 8) * 4 * 3),  # a device's half of v
])
def test_update_roofline_reaches_100_only_at_the_roofline(workload, row_bytes):
    cell = harness.Cell(BENCH, workload)
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    n, step = cfg["n_features"], cfg["step_name"]
    t_min = 8192 * 39 * row_bytes / 819e9

    def record(update_s, runs=100):
        return {"cfg": cfg, "traffic": traffic, "step_name": step,
                "peaks": harness.device_peaks(BENCH, "TPU v5 lite"),
                "trace": {"programs": {step: {"seconds": 1.0, "count": runs}},
                          "device_ops": [
                              [f"{step}/%k.1 f32[{n},16] custom-call", update_s],
                              [f"{step}/%fusion.2 f32[319488,16] fusion", 9.0]]}}

    assert _read("update_roofline", record(100 * t_min)) == pytest.approx(100.0)
    assert _read("update_ms", record(100 * t_min)) == pytest.approx(1e3 * t_min)
    for slower in (1.5, 10.0):
        assert _read("update_roofline", record(100 * t_min * slower)) < 100.0
    nothing = record(0.0)
    nothing["trace"]["device_ops"] = nothing["trace"]["device_ops"][1:]
    assert _read("update_ms", nothing) == 0.0
    assert _read("update_roofline", nothing) is None
    nothing["trace"]["programs"] = {}
    assert _read("update_ms", nothing) is None
