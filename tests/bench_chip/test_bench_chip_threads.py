"""``thread_spans``: spans of every host thread against device idle, and
the five readers of what it gives (``producer_busy_ms``, ``dma_put_ms``,
``unpack_call_ms``, ``queue_wait_ms``, ``idle_ring_dispatch_pct``).

The expected numbers on the recorded traces were read from their raw
events by hand: a sweep over the sorted start and end points of the XLA
ops and of the named spans, counting how many of each are open on each
elementary segment (apart from this code)."""

import os
import shutil

import pytest
from bench_chip_helpers import BENCH

import harness
import thread_spans

pytestmark = pytest.mark.jax

FIXTURE = os.path.join(BENCH, "fixtures")
# fixtures/higgs_logreg_csv.xplane.pb (measured on one v5e chip): one host line
# carries the dmlc:*/bench:* spans there, the loop's
PACK = (94, 24445678, 21385474)  # dmlc:dispatch_pack: count, ns, idle ns
STEP = (94, 168573100, 149153759)  # bench:step
IDLE_NS = 300214980 - 40080469  # window less busy (test_bench_chip_trace)


@pytest.fixture(scope="module")
def old():
    return thread_spans.reduce_dir(FIXTURE, 1)


def test_sums_counts_and_idle_overlap_on_the_recorded_trace(old):
    for name, (count, ns, idle_ns) in (("dmlc:dispatch_pack", PACK),
                                       ("bench:step", STEP)):
        assert old["spans"][name] == {
            "seconds": pytest.approx(ns * 1e-9, rel=1e-12),
            "count": count, "threads": 1}
        assert old["idle_overlap"][name] == pytest.approx(idle_ns * 1e-9,
                                                          rel=1e-12)
    # the window overlaps all of the device's idle, and no name more
    assert old["idle_overlap"]["bench:window"] == pytest.approx(
        IDLE_NS * 1e-9, rel=1e-9)
    assert all(v <= old["idle_overlap"]["bench:window"] + 1e-12
               for v in old["idle_overlap"].values())
    assert set(old["spans"]) == set(old["idle_overlap"])


# fixtures/higgs_logreg_csv.threads.xplane.pb: a 0.34 s traced window of
# higgs_logreg.csv with the input path's spans, 13 producer threads, on
# one TPU v5 lite
THREADS = os.path.join(FIXTURE, "higgs_logreg_csv.threads.xplane.pb")
# name: count, summed ns, lines, idle ns (the union over the lines)
NEW = {"dmlc:ring_dispatch": (40, 195478883, 2, 170350202),
       "dmlc:parse": (78, 708051311, 13, 281381256),
       "dmlc:unpack_call": (40, 125646269, 2, 118842053),
       "dmlc:xfer_queue_wait": (41, 1914117, 1, 1703107)}


@pytest.fixture(scope="module")
def new():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(THREADS)
    lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events])
    return pd, lines


def _on(lines, name):
    return [i for i, evs in enumerate(lines) if any(n == name for *_, n in evs)]


def test_new_spans_sit_on_their_threads(new):
    _pd, lines = new
    (loop,) = _on(lines, "bench:window")
    (transfer,) = _on(lines, "dmlc:host_pull")
    producers = _on(lines, "dmlc:parse")
    ring = _on(lines, "dmlc:ring_dispatch")
    assert len(producers) == 13 and len(ring) == 2
    assert _on(lines, "dmlc:fetch") == producers
    assert not set(producers) & {loop, transfer}
    assert not set(ring) & {loop, transfer}
    for i in ring:
        outer = [(s, e) for s, e, n in lines[i] if n == "dmlc:ring_dispatch"]
        for name in ("dmlc:dma_put", "dmlc:unpack_call"):
            inner = [(s, e) for s, e, n in lines[i] if n == name]
            assert len(inner) == len(outer)
            assert all(any(a <= s and e <= b for a, b in outer) for s, e in inner)
    assert _on(lines, "dmlc:dma_put") == ring == _on(lines, "dmlc:unpack_call")
    # the loop waits on the staged queue once per batch, inside its
    # harness span, beside the transfer wait
    assert _on(lines, "dmlc:xfer_queue_wait") == [loop]
    waits = [(s, e) for s, e, n in lines[loop] if n == "dmlc:xfer_queue_wait"]
    steps = [(s, e) for s, e, n in lines[loop] if n == "bench:input_wait"]
    assert len(waits) == len(steps) == sum(
        1 for *_, n in lines[loop] if n == "dmlc:transfer_wait")
    assert all(a <= s and e <= b for (s, e), (a, b) in zip(waits, steps))


def test_new_spans_against_idle(new):
    pd, _lines = new
    out = thread_spans.reduce_threads(pd, 1)
    for name, (count, ns, threads, idle_ns) in NEW.items():
        assert out["spans"][name] == {
            "seconds": pytest.approx(ns * 1e-9, rel=1e-12),
            "count": count, "threads": threads}
        assert out["idle_overlap"][name] == pytest.approx(idle_ns * 1e-9,
                                                          rel=1e-12)


def test_reduce_dir_carries_every_threads_spans(new, tmp_path):
    """The harness's reduction of a trace directory holds the thread keys
    beside its own, so every reader of them finds something to read."""
    pd, _lines = new
    shutil.copy(THREADS, tmp_path / "t.xplane.pb")
    tr = harness._load(os.path.join(BENCH, "trace_reduce.py"), "bench_trace_reduce")
    red = tr.reduce_dir(str(tmp_path), 1)
    assert {k: red[k] for k in ("spans", "idle_overlap")} == (
        thread_spans.reduce_threads(pd, 1))
    assert {k: v for k, v in red.items()
            if k not in ("spans", "idle_overlap")} == tr.reduce(pd, 1)
    record = {"batches": 40, "dispatched": 41, "trace": red}
    for name in WANT:
        assert harness.metric_reader(BENCH, name)(record) > 0, name


def test_a_name_on_two_lines_is_unioned_before_the_idle():
    window = (0.0, 100.0)
    spans = {"dmlc:x": [(0.0, 10.0, 0), (5.0, 15.0, 1), (95.0, 120.0, 1)],
             "dmlc:y": [(-50.0, -10.0, 0)]}
    # device 0 idle but for 8..12; device 1 idle throughout
    idle = [[(0.0, 8.0), (12.0, 100.0)], [(0.0, 100.0)]]
    out = thread_spans.overlap(window, spans, idle)
    assert out["spans"] == {"dmlc:x": {"seconds": pytest.approx(25e-9),
                                       "count": 3, "threads": 2}}
    # union 0..15 and 95..100: 8 + 3 + 5 idle on device 0, 20 on device 1
    assert out["idle_overlap"] == {"dmlc:x": pytest.approx(18e-9)}


RECORD = {"batches": 40, "dispatched": 50,
          "trace": {"window_s": 0.5,
                    "spans": {"dmlc:fetch": {"seconds": 0.1, "count": 9, "threads": 4},
                              "dmlc:parse": {"seconds": 0.3, "count": 45, "threads": 4},
                              "dmlc:dma_put": {"seconds": 0.02, "count": 40, "threads": 2},
                              "dmlc:unpack_call": {"seconds": 0.06, "count": 40, "threads": 2},
                              "dmlc:xfer_queue_wait": {"seconds": 0.01, "count": 51,
                                                       "threads": 1}},
                    "idle_overlap": {"dmlc:ring_dispatch": 0.125}}}
WANT = {"producer_busy_ms": 1e3 * 0.4 / 40, "dma_put_ms": 1e3 * 0.02 / 40,
        "unpack_call_ms": 1e3 * 0.06 / 40, "queue_wait_ms": 1e3 * 0.01 / 50,
        "idle_ring_dispatch_pct": 100 * 0.125 / 0.5}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic_and_nothing_without_its_span(name):
    read = harness.metric_reader(BENCH, name)
    assert read(RECORD) == pytest.approx(WANT[name], rel=1e-12)
    # a trace reduced without the thread keys (before they were read)
    assert read(dict(RECORD, trace={"window_s": 0.5})) is None
    if name != "idle_ring_dispatch_pct":
        assert read(dict(RECORD, batches=0, dispatched=0)) is None
    else:
        assert read(dict(RECORD, trace=dict(RECORD["trace"], window_s=0.0))) is None


def test_producer_busy_reads_either_span_alone():
    read = harness.metric_reader(BENCH, "producer_busy_ms")
    spans = {"dmlc:parse": {"seconds": 0.3, "count": 45, "threads": 4}}
    rec = dict(RECORD, trace=dict(RECORD["trace"], spans=spans))
    assert read(rec) == pytest.approx(1e3 * 0.3 / 40)
