"""Host spans on every thread of a profiler trace, against device idle.

``trace_reduce`` names an idle gap by the innermost span of the loop's
own thread. The input path works on other threads (the fused producers,
the pipeline's transfer thread, the ring workers), so this module reads
every line of the ``/host:`` planes. ``reduce_threads(pd, n_devices)``
returns:

- ``spans``: for each ``bench:*`` and ``dmlc:*`` name, ``{"seconds",
  "count", "threads"}``: its durations clipped to the ``bench:window``
  span and summed over all threads, the spans overlapping the window,
  and the host lines that carry one;
- ``idle_overlap``: for each name in ``spans``, the seconds of device
  idle during which at least one thread was inside a span of that name:
  the union of the name's intervals over all threads, intersected with
  each device's idle intervals (the window less the union of its
  ``XLA Ops``), averaged over the devices as ``idle_by_label`` is.

Run as a script it prints both for a trace directory::

    python3 benchmarks/chip/thread_spans.py <trace_dir> [n_devices]
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Tuple

from trace_reduce import SPAN_PREFIXES, WINDOW, _events, _union, find_xplane

Interval = Tuple[float, float]


def host_spans(pd) -> Tuple[Interval, Dict[str, List[Tuple[float, float, int]]]]:
    """The ``bench:window`` span and, per span name, ``(start, end,
    line)`` of every ``bench:*``/``dmlc:*`` event on any host line
    (``line`` numbers the host lines in trace order)."""
    window = None
    spans: Dict[str, List[Tuple[float, float, int]]] = {}
    line_no = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for s, e, name in _events(line):
                if name == WINDOW:
                    window = (s, e)
                if name.startswith(SPAN_PREFIXES):
                    spans.setdefault(name, []).append((s, e, line_no))
            line_no += 1
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return window, spans


def device_idle(pd, window: Interval, n_devices: int = 0) -> List[List[Interval]]:
    """Per TPU device (in device order, the first ``n_devices`` when
    given), the window's intervals in which no XLA op ran."""
    lo, hi = window
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append((int(plane.name.rsplit(":", 1)[1]),
                                lines["XLA Ops"]))
    if not devices:
        raise ValueError("no TPU device plane with XLA ops in the trace")
    devices.sort(key=lambda d: d[0])
    if n_devices:
        devices = devices[:n_devices]
    idle = []
    for _n, ops in devices:
        busy = _union([(s, e) for s, e, _name in _events(ops)], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle.append([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a])
    return idle


def _intersect(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap(window: Interval, spans: Dict[str, List[Tuple[float, float, int]]],
            idle: List[List[Interval]]) -> dict:
    """``spans`` and ``idle_overlap`` (seconds) from nanosecond intervals:
    ``spans`` as ``host_spans`` gives them, ``idle`` as ``device_idle``."""
    lo, hi = window
    out_spans, out_idle = {}, {}
    for name, evs in sorted(spans.items()):
        inside = [(max(s, lo), min(e, hi), ln) for s, e, ln in evs
                  if min(e, hi) > max(s, lo)]
        if not inside:
            continue
        out_spans[name] = {
            "seconds": sum(e - s for s, e, _ln in inside) * 1e-9,
            "count": len(inside),
            "threads": len({ln for _s, _e, ln in inside}),
        }
        held = _union([(s, e) for s, e, _ln in inside], lo, hi)
        out_idle[name] = sum(_intersect(held, dev) for dev in idle) * 1e-9 / len(idle)
    return {"spans": out_spans, "idle_overlap": out_idle}


def reduce_threads(pd, n_devices: int = 0) -> dict:
    window, spans = host_spans(pd)
    return overlap(window, spans, device_idle(pd, window, n_devices))


def reduce_dir(trace_dir: str, n_devices: int = 0) -> dict:
    from jax.profiler import ProfileData

    return reduce_threads(ProfileData.from_file(find_xplane(trace_dir)), n_devices)


if __name__ == "__main__":
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    print(json.dumps(reduce_dir(sys.argv[1], n), indent=1, sort_keys=True))
