"""Shared pieces of the chip benchmark's CPU tests (benchmarks/chip/).

They run the harness on the CPU at tiny sizes: ``platform="cpu"`` skips
only the look for a chip; everything else of a run is driven. No test
here describes a TPU topology (that stays in tests/test_tpu_compile.py).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY = {
    "criteo_fm": {"config": {"rows": 4096, "n_features": 1 << 12},
                  "traffic": {"batch": 256}},
    "criteo_fm_2x2": {"config": {"rows": 4096, "n_features": 1 << 12},
                      "traffic": {"batch": 256}},
    "higgs_logreg": {"config": {"rows": 4096},
                     "traffic": {"batch": 256, "nthread": 3}},
}

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
}


def tiny(workload: str) -> dict:
    return TINY[workload.split(".")[0]]


def run_tiny(workload, seed=2**31 + 7, seconds=0.5, trace=False, hooks=None,
             bench_dir=BENCH):
    import harness as h

    hooks = dict(hooks or {})
    if trace:
        hooks.setdefault("reduce", lambda _d: CANNED_TRACE)
        hooks.setdefault("device_kind", "TPU v5 lite")
    return h.run(workload, seed, seconds, trace, platform="cpu",
                 overrides=tiny(workload), hooks=hooks, bench_dir=bench_dir)
