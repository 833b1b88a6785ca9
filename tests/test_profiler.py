"""utils/profiler.py (previously untested — ISSUE 4 satellite):
annotate() degrades to a no-op without jax, trace() fails loudly without
jax, and spans feed the profiler.span_seconds duration histograms when
enabled."""

import pytest

from dmlc_core_tpu.telemetry import default_registry, tracing
from dmlc_core_tpu.utils import profiler


@pytest.fixture
def no_jax(monkeypatch):
    """Simulate a jax-less environment (the resolved-profiler cache is
    module state; None means 'import failed')."""
    monkeypatch.setattr(profiler, "_PROF", None)


@pytest.fixture
def hist_off():
    """Leave histogram enablement as the test found it."""
    yield
    profiler.enable_histograms(None)


@pytest.fixture
def trace_off():
    """Force the flight recorder off (it is on by default, and an
    enabled ring makes annotate() a recording span, not a no-op)."""
    tracing.set_enabled(False)
    yield
    tracing.set_enabled(None)


def test_annotate_is_noop_context_manager_without_jax(
    no_jax, hist_off, trace_off
):
    profiler.enable_histograms(False)
    cm = profiler.annotate("dmlc:test")
    with cm as inner:
        assert inner is None  # nullcontext yields None
    # reentrant: annotate() hands out fresh context managers
    with profiler.annotate("dmlc:test"):
        pass


def test_trace_raises_clean_runtime_error_without_jax(no_jax):
    with pytest.raises(RuntimeError, match="requires jax"):
        with profiler.trace("/tmp/nowhere"):
            pass


def test_annotate_feeds_duration_histograms_when_enabled(no_jax, hist_off):
    profiler.enable_histograms(True)
    key = 'profiler.span_seconds{span="dmlc:test_span"}'
    before = (
        default_registry()
        .snapshot()["histograms"]
        .get(key, {})
        .get("count", 0)
    )
    for _ in range(3):
        with profiler.annotate("dmlc:test_span"):
            pass
    snap = default_registry().snapshot()["histograms"][key]
    assert snap["count"] - before == 3
    assert snap["sum"] >= 0
    # disabled again: no further samples recorded
    profiler.enable_histograms(False)
    with profiler.annotate("dmlc:test_span"):
        pass
    snap2 = default_registry().snapshot()["histograms"][key]
    assert snap2["count"] - before == 3


def test_histograms_env_default(monkeypatch, hist_off):
    monkeypatch.delenv("DMLC_PROFILE_HIST", raising=False)
    profiler.enable_histograms(None)
    assert profiler.histograms_enabled() is False
    monkeypatch.setenv("DMLC_PROFILE_HIST", "1")
    # the env is resolved once: a change shows only after a reset
    assert profiler.histograms_enabled() is False
    profiler.enable_histograms(None)
    assert profiler.histograms_enabled() is True
    monkeypatch.setenv("DMLC_PROFILE_HIST", "0")
    profiler.enable_histograms(None)
    assert profiler.histograms_enabled() is False
    # explicit override beats the env
    profiler.enable_histograms(True)
    assert profiler.histograms_enabled() is True


@pytest.mark.jax
def test_annotate_with_jax_still_times_spans(hist_off):
    """With real jax present, annotate() wraps TraceAnnotation AND (when
    enabled) still observes the duration histogram."""
    pytest.importorskip("jax")
    profiler.enable_histograms(True)
    key = 'profiler.span_seconds{span="dmlc:jax_span"}'
    with profiler.annotate("dmlc:jax_span"):
        pass
    snap = default_registry().snapshot()["histograms"][key]
    assert snap["count"] >= 1


def test_span_memo_concurrent_first_annotate_race(no_jax, hist_off):
    """ISSUE 8 satellite: concurrent FIRST annotate() calls must not
    double-register a span name (last-writer-wins in the memo would
    hand different threads different histogram objects) nor mis-account
    the memo cap (racing check-then-set inserts past it). All threads
    must land their observations on ONE histogram per name."""
    import threading

    profiler.enable_histograms(True)
    profiler._SPAN_HISTS.clear()
    n_threads, n_names = 8, 16
    seen = [[None] * n_names for _ in range(n_threads)]
    gate = threading.Barrier(n_threads)

    def worker(slot):
        gate.wait()  # maximize first-annotate collisions
        for i in range(n_names):
            with profiler.annotate(f"dmlc:race_{i}"):
                pass
            seen[slot][i] = profiler._SPAN_HISTS.get(f"dmlc:race_{i}")

    threads = [
        threading.Thread(target=worker, args=(s,))
        for s in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # one memoized histogram per name, shared by every thread
    for i in range(n_names):
        hists = {id(seen[s][i]) for s in range(n_threads)}
        assert len(hists) == 1, f"name {i} double-registered"
    assert len(profiler._SPAN_HISTS) == n_names  # cap accounting exact
    # and every observation landed on that one series
    key = 'profiler.span_seconds{span="dmlc:race_0"}'
    snap = default_registry().snapshot()["histograms"][key]
    assert snap["count"] >= n_threads


def test_span_memo_bounded_on_dynamic_names(no_jax, hist_off):
    """annotate(f'step_{i}') with histograms on must not grow the memo
    dict forever — past the cap, lookups fall through to the registry
    (whose cardinality cap collapses the series). Runs LAST: it
    saturates the default registry's profiler.span_seconds family on
    purpose, so span-key assertions must precede it."""
    profiler.enable_histograms(True)
    before = len(profiler._SPAN_HISTS)
    for i in range(profiler._SPAN_MEMO_CAP + 50):
        with profiler.annotate(f"dmlc:dyn_{i}"):
            pass
    assert len(profiler._SPAN_HISTS) <= profiler._SPAN_MEMO_CAP, before
