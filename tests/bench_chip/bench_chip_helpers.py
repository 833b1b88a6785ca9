"""Shared pieces of the chip benchmark's CPU tests (benchmarks/chip/).

They run the harness on the CPU at tiny sizes: ``platform="cpu"`` skips
only the look for a chip; everything else of a run is driven. No test
here describes a TPU topology (that stays in tests/test_tpu_compile.py).
The cells come from ``BENCHMARK.json`` and each config's tiny sizes from
its ``tiny.json``, so a cell added as new files is tested with no edit
here.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# a reduced trace as trace_reduce returns it, for runs whose trace the CPU
# cannot give (it has no TPU device plane)
CANNED_TRACE = {
    "window_s": 0.5, "busy_s": 0.2, "devices": 1,
    "programs": {"jit_fm_train_step": {"seconds": 0.15, "count": 30},
                 "jit_logreg_train_step": {"seconds": 0.15, "count": 30},
                 "jit_unpack": {"seconds": 0.03, "count": 30}},
    "device_ops": [["jit_fm_train_step/fusion", 0.1]],
    "idle_gaps": [["bench:input_wait", 0.01]],
    "idle_by_label": {"bench:input_wait": 0.3},
    "spans": {"dmlc:fetch": {"seconds": 0.05, "count": 8, "threads": 2},
              "dmlc:parse": {"seconds": 0.2, "count": 32, "threads": 2},
              "dmlc:dma_put": {"seconds": 0.01, "count": 30, "threads": 2},
              "dmlc:unpack_call": {"seconds": 0.02, "count": 30, "threads": 2},
              "dmlc:xfer_queue_wait": {"seconds": 0.004, "count": 31,
                                       "threads": 1}},
    "idle_overlap": {"dmlc:ring_dispatch": 0.02},
}


def cells(root: str = REPO) -> list:
    """The workload names of ``<root>/BENCHMARK.json``, in its order."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def tiny(workload: str, bench_dir: str = BENCH) -> dict:
    """The cell's config's ``tiny.json`` (found as its code is)."""
    import harness as h

    return h.load_json(h.Cell(bench_dir, workload).find("tiny.json"))


def run_tiny(workload, seed=2**31 + 7, seconds=0.5, trace=False, hooks=None,
             bench_dir=BENCH):
    import harness as h

    hooks = dict(hooks or {})
    if trace:
        hooks.setdefault("reduce", lambda _d: CANNED_TRACE)
        hooks.setdefault("device_kind", "TPU v5 lite")
    return h.run(workload, seed, seconds, trace, platform="cpu",
                 overrides=tiny(workload, bench_dir), hooks=hooks,
                 bench_dir=bench_dir)
