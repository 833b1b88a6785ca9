"""Required work, peaks and the shares built on them (benchmarks/chip/):
hand-counted FLOPs and bytes at tiny shapes, the table of peaks, and
shares of a roofline or a peak that reach 100% only at the roofline."""

import os

import numpy as np
import pytest
from bench_chip_helpers import BENCH, cells

import harness


def _work(workload):
    cell = harness.Cell(BENCH, workload)
    return cell, cell.module("work")


def test_fm_step_work_hand_counted():
    cell, work = _work("criteo_fm.seq")
    cfg = dict(cell.cfg, fields=3, embed_dim=2)
    w = work.step_work(cfg, {"batch": 2, "value_dtype": "float16"})
    # 6 slots: forward 6 * (2 + 4*2) + 2 rows * (3*2 + 9) = 90; backward
    # 180; update 6 slots * 2 * (1 + 2) = 36
    assert w["flops"] == 90 + 180 + 36
    # batch: 6 * (4 + 2) + 2 * (4 + 4) = 52; rows: 3 * 6 * (1 + 2) * 4 = 216
    assert w["bytes"] == 52 + 216


def test_logreg_step_work_hand_counted():
    cell, work = _work("higgs_logreg.csv")
    cfg = dict(cell.cfg, features=3)
    w = work.step_work(cfg, {"batch": 4, "value_dtype": "float32"})
    # forward 4 rows * (2*3 + 1 + 8) = 60; backward 120; update 2 * 4 = 8
    assert w["flops"] == 60 + 120 + 8
    # x 4 * 3 * 4 + labels and weights 4 * 8 + params read and written 2*4*4
    assert w["bytes"] == 48 + 32 + 32


@pytest.mark.parametrize("workload", cells())
def test_unpack_bytes_are_one_devices_arrays_in_and_out(workload, tmp_path):
    """At the timed shapes: what one device's unpack must read and write
    is its share of the staged arrays, once in and once out."""
    cell, work = _work(workload)
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    spec = cell.module("program").batch_spec(cfg, traffic)
    from dmlc_core_tpu.staging.fused import _EllSlotMixin, _FusedDenseTextBatches

    holder = type("H", (), {"spec": spec})()
    if spec.layout == "ell":
        slot = _EllSlotMixin._alloc_ell_slot(holder)
    else:
        slot = _FusedDenseTextBatches._alloc_slot(holder)
    arrays = sum(v.nbytes for v in slot[:-1])
    data = cfg["mesh"]["shape"][0] if cfg.get("mesh") else 1
    assert work.unpack_bytes(cfg, traffic) == 2 * arrays // data


def test_unknown_device_kind_is_an_error():
    assert harness.device_peaks(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks"):
        harness.device_peaks(BENCH, "TPU v99 imaginary")


def _record(workload, step_s, unpack_s, runs=100):
    """A traced window that holds ``runs`` steps and unpacks back to back,
    each taking the given device time."""
    cell, work = _work(workload)
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    chips = cell.chips
    return {
        "cfg": cfg, "traffic": traffic, "chips": chips,
        "peaks": harness.device_peaks(BENCH, "TPU v5 lite"),
        "step_name": cfg["step_name"],
        "work": {"step": work.step_work(cfg, traffic),
                 "unpack_bytes": work.unpack_bytes(cfg, traffic)},
        "trace": {"window_s": runs * (step_s / chips + unpack_s),
                  "busy_s": runs * (step_s / chips + unpack_s), "devices": chips,
                  "programs": {
                      cfg["step_name"]: {"seconds": runs * step_s / chips,
                                         "count": runs},
                      "jit_unpack": {"seconds": runs * unpack_s, "count": runs}}},
    }


def _t_min(workload):
    cell, work = _work(workload)
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    pk = harness.device_peaks(BENCH, "TPU v5 lite")
    w = work.step_work(cfg, traffic)
    step = max(w["flops"] / pk["flops_per_s"], w["bytes"] / pk["hbm_bytes_per_s"])
    return step, work.unpack_bytes(cfg, traffic) / pk["hbm_bytes_per_s"]


def _read(name, record):
    return harness.metric_reader(BENCH, name)(record)


@pytest.mark.parametrize("workload", cells())
def test_shares_reach_100_only_at_the_roofline(workload):
    step, unpack = _t_min(workload)
    # every execution at its least possible time: the unpack at 100%; the
    # whole step's share of the window is what the unpacks leave it
    at_roof = _record(workload, step, unpack)
    assert _read("unpack_roofline", at_roof) == pytest.approx(100.0)
    chips = at_roof["chips"]
    assert _read("step_mfu", at_roof) == pytest.approx(
        100.0 * step / chips / (step / chips + unpack))
    # a step spread over the chips at its least time, with no unpack, is
    # the whole window: 100%, and never above
    no_unpack = _record(workload, step, 0.0)
    assert _read("step_mfu", no_unpack) == pytest.approx(100.0)
    for slower in (1.5, 10.0):
        r = _record(workload, step * slower, unpack * slower)
        assert _read("unpack_roofline", r) < 100.0
        assert _read("step_mfu", r) < 100.0


@pytest.mark.parametrize("workload", ["criteo_fm.seq", "higgs_logreg.csv"])
def test_required_step_work_is_below_what_the_timed_step_moves(workload):
    """The required bytes of a timed step are under its arguments and
    outputs (the table is read and written whole today): the count is of
    what the step needs, not of what this implementation does."""
    cell, work = _work(workload)
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    w = work.step_work(cfg, traffic)
    if "n_features" in cfg:
        table = cfg["n_features"] * (1 + cfg["embed_dim"]) * 4
        assert w["bytes"] < 2 * table
        assert np.isclose(w["bytes"] / 1e6, 67.16, atol=0.01)
    else:
        assert w["bytes"] == traffic["batch"] * (cfg["features"] * 4 + 8) + 8 * 29
    assert os.path.exists(os.path.join(BENCH, "peaks.json"))
