"""``trace_reduce`` on a profiler trace recorded on the chip.

``benchmarks/chip/fixtures/higgs_logreg_csv.xplane.pb`` is a 0.3 s traced
window of ``higgs_logreg.csv`` on one TPU v5 lite (my chip run, PR 22).
The expected numbers were read from the trace's raw events by hand
(``inspect_trace.py`` dump, summed and swept apart from this code)."""

import os

import pytest
from bench_chip_helpers import BENCH

import harness

pytestmark = pytest.mark.jax

FIXTURE = os.path.join(BENCH, "fixtures")
WINDOW_NS = 300214980  # the 'bench:window' span
BUSY_NS = 40080469  # union of the 2162 'XLA Ops' events, clipped to it
UNPACK = (93, 39667671)  # jit_unpack executions and their summed ns
STEP = (94, 704665)  # jit_logreg_train_step
LONGEST_GAP = ("bench:step", 19697178)  # 166724046..186421224 ns, inside
# the loop's 'bench:step' span (166398198..184899637), the step's dispatch


@pytest.fixture(scope="module")
def reduced():
    tr = harness._load(os.path.join(BENCH, "trace_reduce.py"), "bench_trace_reduce")
    return tr.reduce_dir(FIXTURE, 1)


def test_window_busy_and_idle(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(WINDOW_NS * 1e-9, rel=1e-12)
    assert reduced["busy_s"] == pytest.approx(BUSY_NS * 1e-9, rel=1e-12)
    idle = sum(reduced["idle_by_label"].values())
    assert idle == pytest.approx((WINDOW_NS - BUSY_NS) * 1e-9, rel=1e-9)


def test_per_program_device_time(reduced):
    progs = reduced["programs"]
    assert set(progs) == {"jit_unpack", "jit_logreg_train_step"}
    for name, (count, ns) in (("jit_unpack", UNPACK),
                              ("jit_logreg_train_step", STEP)):
        assert progs[name]["count"] == count
        assert progs[name]["seconds"] == pytest.approx(ns * 1e-9, rel=1e-12)
    ops = reduced["device_ops"]
    assert ops[0][0] == "jit_unpack/%reshape u8[245760,4] reshape"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_gaps_are_named_by_the_host_span_they_fall_in(reduced):
    label, secs = reduced["idle_gaps"][0]
    assert label == LONGEST_GAP[0]
    assert secs == pytest.approx(LONGEST_GAP[1] * 1e-9, rel=1e-12)
    assert all(a[1] >= b[1] for a, b in zip(reduced["idle_gaps"],
                                            reduced["idle_gaps"][1:]))
    labels = {g[0] for g in reduced["idle_gaps"]}
    assert labels <= {"other"} | {
        lab for lab in labels if lab.startswith(("bench:", "dmlc:"))}


def test_readers_on_the_recorded_trace_stay_under_100(reduced):
    cell = harness.Cell(BENCH, "higgs_logreg.csv")
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    work = cell.module("work")
    record = {
        "cfg": cfg, "traffic": traffic, "chips": 1,
        "peaks": harness.device_peaks(BENCH, "TPU v5 lite"),
        "step_name": cfg["step_name"], "trace": reduced,
        "work": {"step": work.step_work(cfg, traffic),
                 "unpack_bytes": work.unpack_bytes(cfg, traffic)},
    }
    read = lambda name: harness.metric_reader(BENCH, name)(record)  # noqa: E731
    idle = read("device_idle_pct")
    assert idle == pytest.approx(100 * (1 - BUSY_NS / WINDOW_NS))
    roof = read("unpack_roofline")
    assert 0 < roof < 100
    assert roof == pytest.approx(100 * UNPACK[0] * work.unpack_bytes(cfg, traffic)
                                 / 819e9 / (UNPACK[1] * 1e-9))
    step_ms = read("step_device_ms")
    assert step_ms == pytest.approx(1e3 * STEP[1] * 1e-9 / STEP[0])
    assert 0 < read("step_mfu") < 100
