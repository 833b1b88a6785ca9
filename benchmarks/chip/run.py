#!/usr/bin/env python3
"""Benchmark entry point (the command of BENCHMARK.json):

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on; refuses any platform but
the TPU. See ``harness.py`` and PERF.md."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# JAX's persistent compilation cache lives inside the checkout, at a fixed
# path, whatever the machine sets: set before JAX is imported, so that
# utils/compile_cache.place_compile_cache() finds and keeps it. Every
# program is cached, however quickly it compiled.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [HERE, ROOT]

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
