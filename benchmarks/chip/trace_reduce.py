"""Reduce a JAX profiler trace to what the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler.start_trace/stop_trace``
writes, read with ``jax.profiler.ProfileData`` (nothing else). Output:

- ``window_s``: the length of the harness's ``bench:window`` host span;
- ``busy_s``: per device, the union of the intervals in which an XLA op
  ran (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the window, averaged over the devices;
- ``programs``: per XLA module (``jit_<name>``, its ``(id)`` suffix
  dropped), the device seconds and executions inside the window, both
  averaged over the devices;
- ``device_ops``: ``[name, seconds]`` of the ops that took most device
  time (``<module>/<op> <type> <opcode>``), averaged over the devices,
  longest first;
- ``idle_gaps``: ``[label, seconds]`` of the longest idle gaps of any
  device, each named by the innermost ``bench:*`` or ``dmlc:*`` span of
  the window's own thread that holds the gap's midpoint (``other`` when
  none does); ``idle_by_label`` sums every gap by the same label;
- ``spans``, ``idle_overlap`` (``reduce_dir`` only): every host
  thread's ``bench:*``/``dmlc:*`` span seconds and their overlap with
  device idle, as ``thread_spans.reduce_threads`` gives them.

Host and device events share the trace's clock (the profiler aligns
them; tests/bench_chip checks the reduction on a trace recorded on the
chip, ``fixtures/``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench:window"
SPAN_PREFIXES = ("bench:", "dmlc:")
_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^}]*\}")
_TYPE_OP = re.compile(r"(\([^()]*\)|\S+) ([\w.-]+)\(")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, n_devices: int = 0) -> dict:
    """``reduce`` of the trace, with ``thread_spans.reduce_threads``'s
    ``spans`` and ``idle_overlap`` (every host thread) merged in."""
    from jax.profiler import ProfileData

    from thread_spans import reduce_threads  # it imports this module

    pd = ProfileData.from_file(find_xplane(trace_dir))
    out = reduce(pd, n_devices)
    out.update(reduce_threads(pd, n_devices))
    return out


def _events(line) -> List[Tuple[float, float, str]]:
    out = []
    for e in line.events:
        s = float(e.start_ns)
        out.append((s, s + float(e.duration_ns), e.name))
    return out


def _short(op: str) -> str:
    """``%fusion.2 = f32[16,8]{0,1:T(8,128)} fusion(...), kind=...`` →
    ``%fusion.2 f32[16,8] fusion``: the op, its result type and opcode."""
    lhs, _, rhs = op.partition(" = ")
    m = _TYPE_OP.match(_LAYOUT.sub("", rhs))
    return f"{lhs} {m.group(1)} {m.group(2)}" if m else lhs


def _union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _label(spans, starts, t: float) -> str:
    """Innermost span holding ``t``: of those starting at or before it,
    the latest one that has not ended."""
    i = bisect.bisect_right(starts, t)
    for s, e, name in reversed(spans[:i]):
        if e >= t:
            return name
    return "other"


def reduce(pd, n_devices: int = 0) -> dict:
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(n == WINDOW for _s, _e, n in evs):
                    window = next((s, e) for s, e, n in evs if n == WINDOW)
                    spans = sorted((s, e, n) for s, e, n in evs
                                   if n.startswith(SPAN_PREFIXES) and n != WINDOW)
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append((plane.name, lines))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane with XLA ops in the trace")
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    if n_devices:
        devices = devices[:n_devices]
    lo, hi = window
    starts = [s for s, _e, _n in spans]
    busy_total = 0.0
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for _name, lines in devices:
        op_evs = _events(lines["XLA Ops"])
        mod_evs = _events(lines["XLA Modules"]) if "XLA Modules" in lines else []
        mod_starts = [m[0] for m in mod_evs]
        for s, e, name in mod_evs:
            dt = min(e, hi) - max(s, lo)
            if dt > 0:
                p = programs.setdefault(_SUFFIX.sub("", name), [0.0, 0])
                p[0] += dt
                p[1] += 1
        for s, e, name in op_evs:
            dt = min(e, hi) - max(s, lo)
            if dt <= 0:
                continue
            i = bisect.bisect_right(mod_starts, s) - 1
            mod = _SUFFIX.sub("", mod_evs[i][2]) if i >= 0 and mod_evs[i][1] >= s else "?"
            key = f"{mod}/{_short(name)}"
            ops[key] = ops.get(key, 0.0) + dt
        busy = _union([(s, e) for s, e, _n in op_evs], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label(spans, starts, (a + b) / 2)))
    n = len(devices)
    by_label: Dict[str, float] = {}
    for dt, label in gaps:
        by_label[label] = by_label.get(label, 0.0) + dt * 1e-9 / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "devices": n,
        "programs": {k: {"seconds": v[0] * 1e-9 / n, "count": v[1] / n}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v * 1e-9 / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": [[label, dt * 1e-9] for dt, label in
                      sorted(gaps, key=lambda g: -g[0])],
        "idle_by_label": by_label,
    }
