#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md).

Run on the chip at the cell's own size; the benchmark's own runs never
run it. For every seed: the program's first steps (the same set-up path
as a run: ``Run.setup``) against the plain float32 reference. For the
first ``--controls`` seeds also, in the program's place: the control
(the reference in bfloat16) and the half-batch fault (the second half
of each batch left out, the mean taken over the rest; on the 2x2 mesh
this is also what leaving out the exchange over ``data`` gives, since
each data replica then steps on its own half).

    python3 benchmarks/chip/calibrate.py --workload criteo_fm.seq \\
        --seeds 1 2 3 ... --controls 3 --out chiprun_out/cal.json
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path[:0] = [HERE, ROOT]

from harness import Run, gaps  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    rows = []
    for i, seed in enumerate(args.seeds):
        r = Run(args.workload, seed)
        try:
            r.setup()  # the program's first steps
            r.params = None
            r.feed.close()
            first = r.first_plain()
            ref = r.reference(first)
            row = {"seed": seed, "program": gaps(r.prog_traj, ref),
                   "losses": ref["losses"]}
            if i < args.controls:
                row["control_bf16"] = gaps(r.reference(first, "bfloat16"), ref)
                row["half_batch"] = gaps(
                    r.reference(first, "float32", "half_batch"), ref)
        finally:
            r.close()
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {}
    for kind, pick in (("program", max), ("control_bf16", min),
                       ("half_batch", min)):
        got = [row[kind] for row in rows if kind in row]
        if got:
            summary[kind] = {k: pick(g[k] for g in got) for k in got[0]}
    print(json.dumps({"summary": summary}), flush=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows,
                   "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
