"""Tracing/profiling hooks: host-side spans + XProf trace capture.

SURVEY §5.1: the reference's observability is wall-clock logging
(timer.h, MB/sec lines); its rebuild note asks for host-side timing plus
optional XLA/XProf trace hooks around infeed. This module provides both
without making jax a hard dependency of the data layer:

- ``annotate(name)``: a ``jax.profiler.TraceAnnotation`` when jax is
  importable (spans show up on the XProf host timeline inside any active
  trace), else a no-op context manager. Cheap enough to leave on: when
  no trace is active the annotation is a couple of TraceMe calls.
- ``trace(logdir)``: context manager around
  ``jax.profiler.start_trace/stop_trace`` — wrap any region (e.g. a
  bench epoch) and open the logdir with XProf/TensorBoard.
- span → telemetry bridge (ISSUE 4): with ``DMLC_PROFILE_HIST=1`` (or
  ``enable_histograms(True)``), every ``annotate`` span also records its
  duration into the registry histogram
  ``profiler.span_seconds{span=<name>}`` — XProf shows one trace,
  telemetry keeps the distribution across the whole run. Off by
  default: the hot loop pays nothing beyond the existing annotation.
- span → flight-recorder bridge (ISSUE 8): while the always-on trace
  ring is enabled (``DMLC_TRACE``, telemetry/tracing.py), every
  ``annotate`` span also lands on the per-thread ring as a Chrome
  trace-event — ONE call site feeds XProf, the span histogram and the
  Perfetto timeline.

StagingPipeline wires ``annotate`` around its pull/stage/wait phases, so
a trace of a training loop shows exactly where infeed time goes
(host parse vs DMA vs consumer).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional

from ..telemetry import tracing as _tracing

__all__ = ["annotate", "enable_histograms", "histograms_enabled", "trace"]


_PROF = False  # unresolved sentinel; None = jax absent


def _jax_profiler(force: bool = False):
    global _PROF
    if _PROF is False:  # resolve once — annotate() sits on the hot loop
        if not force and "jax" not in sys.modules:
            # a process that never imported jax cannot have an active
            # XProf trace, so don't pay the ~1s jax import just to
            # annotate host-side spans (dsserve servers, bench drain
            # workers, shard-lease drains are all jax-free); the
            # sentinel stays unresolved, so a later jax import is
            # picked up by the next annotate
            return None
        try:
            import jax.profiler as prof  # deferred: works without jax

            _PROF = prof
        except ImportError:
            _PROF = None
    return _PROF


# -- span duration histograms (opt-in) ----------------------------------------

_HIST_OVERRIDE: Optional[bool] = None  # enable_histograms() wins over env
_HIST_ENV: Optional[bool] = None  # resolved once; enable_histograms() clears
_SPAN_HISTS: Dict[str, object] = {}  # name -> Histogram (memoized lookup)


def histograms_enabled() -> bool:
    """Are annotate() spans feeding duration histograms? The
    ``DMLC_PROFILE_HIST`` env is read once (every span on every thread
    asks), as ``telemetry.tracing.enabled()`` reads ``DMLC_TRACE``."""
    if _HIST_OVERRIDE is not None:
        return _HIST_OVERRIDE
    global _HIST_ENV
    if _HIST_ENV is None:
        raw = os.environ.get("DMLC_PROFILE_HIST", "0")
        _HIST_ENV = raw not in ("", "0", "false")
    return _HIST_ENV


def enable_histograms(on: Optional[bool]) -> None:
    """Force span histograms on/off for this process (None restores the
    ``DMLC_PROFILE_HIST`` env default, re-read at the next span)."""
    global _HIST_OVERRIDE, _HIST_ENV
    _HIST_OVERRIDE = on
    _HIST_ENV = None


_SPAN_MEMO_CAP = 256  # span names are static call sites, not data
_SPAN_MEMO_LOCK = threading.Lock()


def _span_hist(name: str):
    hist = _SPAN_HISTS.get(name)  # lock-free fast path (GIL-atomic get)
    if hist is None:
        from ..telemetry import default_registry  # deferred: cold path only

        hist = default_registry().histogram(
            "profiler.span_seconds",
            help="annotate() span durations (secs)",
            labels={"span": name},
        )
        # the memo exists to skip the registry lock per span; dynamic
        # names (annotate(f"step_{i}")) must not grow it forever — past
        # the cap, fall through to the registry each call (whose own
        # cardinality cap collapses the series). The cap check and the
        # insert must be ONE atomic step: concurrent first-annotate
        # calls racing check-then-set could both insert (overshooting
        # the cap) and the last writer's histogram would silently
        # replace the first's — setdefault under a lock keeps exactly
        # one histogram per name and an exact cap (ISSUE 8 satellite).
        with _SPAN_MEMO_LOCK:
            if len(_SPAN_HISTS) < _SPAN_MEMO_CAP:
                hist = _SPAN_HISTS.setdefault(name, hist)
    return hist


class _TimedSpan:
    """annotate() with histograms and/or the trace ring on: enter the
    inner annotation (if any), time the region with perf_counter_ns,
    observe/record on exit — one clock read feeds both sinks."""

    __slots__ = ("_inner", "_hist", "_name", "_t0")

    def __init__(self, inner, hist, name: Optional[str]) -> None:
        self._inner = inner
        self._hist = hist
        self._name = name  # non-None = also record on the trace ring

    def __enter__(self):
        if self._inner is not None:
            self._inner.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt_ns = time.perf_counter_ns() - self._t0
        if self._hist is not None:
            self._hist.observe(dt_ns * 1e-9)
        if self._name is not None:
            _tracing.add_complete(self._name, self._t0, dt_ns)
        if self._inner is not None:
            return self._inner.__exit__(*exc)
        return False


def annotate(name: str):
    """Context manager marking a host-side span on the XProf timeline
    (no-op without jax); records the span duration into
    ``profiler.span_seconds{span=name}`` when histograms are enabled,
    and onto the flight-recorder ring (telemetry/tracing.py) while
    tracing is on — the one seam feeding all three sinks."""
    prof = _jax_profiler()
    inner = prof.TraceAnnotation(name) if prof is not None else None
    hist = _span_hist(name) if histograms_enabled() else None
    traced = _tracing.enabled()
    if hist is not None or traced:
        return _TimedSpan(inner, hist, name if traced else None)
    return inner if inner is not None else nullcontext()


@contextmanager
def trace(logdir: str):
    """Capture an XProf trace of the enclosed region into ``logdir``.

    Requires jax. View with ``tensorboard --logdir <logdir>`` (or the
    xprof CLI); host annotations from ``annotate`` appear on the host
    threads, device ops on the device timeline.
    """
    prof = _jax_profiler(force=True)
    if prof is None:
        raise RuntimeError("profiler trace requires jax")
    prof.start_trace(logdir)
    try:
        yield
    finally:
        prof.stop_trace()
