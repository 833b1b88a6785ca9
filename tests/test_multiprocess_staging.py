"""Multi-process staging: the jax.distributed branch of stage_batch.

Covers `jax.make_array_from_process_local_data` (staging/pipeline.py) —
the path every sharding test elsewhere skips because the suite runs one
process over 8 virtual devices. Here two REAL processes each stage their
(part_index, num_parts) = process_shard() slice of a rowrec shard into a
global mesh-sharded batch, and a jitted global reduction proves every
row landed exactly once (the reference's rank-parameterized distributed
split test — unittest_inputsplit.cc:116-145 — lifted from threads to
processes).

Marked slow: two fresh jax imports + a distributed CPU handshake.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_ROWS = 512          # divisible by 2 parts x B_LOCAL
B_LOCAL = 128         # per-process batch rows; global batch = 256 over 8 dev
K = 7                 # uniform nnz per row -> byte-split lands on a record


WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
import jax

# tests never take the chip: the CPU pin (also in the env) must precede
# any backend or distributed initialization
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address={coord!r},
    num_processes=2,
    process_id={pid},
)
import numpy as np
from dmlc_core_tpu.parallel.mesh import make_mesh, process_shard
from dmlc_core_tpu.staging import BatchSpec, StagingPipeline, ell_batches

part, nparts = process_shard()
assert (part, nparts) == ({pid}, 2), (part, nparts)

mesh = make_mesh(axis_names=("data",))  # all 8 global devices
spec = BatchSpec(batch_size={b_local}, layout="ell", max_nnz={k})
stream = ell_batches({rec!r}, spec, part_index=part, num_parts=nparts)
pipe = StagingPipeline(stream, mesh=mesh)

total = 0.0
rows = 0
weights_sum = 0.0
for dev in pipe:
    g = dev["labels"]
    assert g.shape == ({b_local} * 2,), g.shape          # GLOBAL batch
    assert len(g.sharding.device_set) == 8               # spans the mesh
    total += float(jax.jit(lambda a: a.sum())(g))
    weights_sum += float(jax.jit(lambda a: a.sum())(dev["weights"]))
    rows += g.shape[0]
stream.close()
pipe.close()
with open({out!r} + str({pid}), "w") as f:
    f.write("%r %r %r" % (total, weights_sum, rows))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_staging_exact_cover(tmp_path):
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.io.stream import FileStream

    # labels = row ids -> the global sum is a unique fingerprint of
    # "every row exactly once"
    n = N_ROWS
    offset = np.arange(n + 1, dtype=np.int64) * K
    rng = np.random.default_rng(0)
    blk = RowBlock(
        offset=offset,
        label=np.arange(n, dtype=np.float32),
        index=rng.integers(0, 1000, n * K).astype(np.uint32),
        value=rng.normal(size=n * K).astype(np.float32),
    )
    rec = str(tmp_path / "mp.rec")
    with FileStream(rec, "w") as f:
        write_rowrec(f, [blk])

    coord = f"127.0.0.1:{_free_port()}"
    out = str(tmp_path / "proc")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"]
    )
    procs = []
    for pid in range(2):
        script = tmp_path / f"w{pid}.py"
        script.write_text(
            textwrap.dedent(
                WORKER.format(
                    repo=REPO, coord=coord, pid=pid, rec=rec,
                    b_local=B_LOCAL, k=K, out=out,
                )
            )
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    try:
        outs = [p.communicate(timeout=300) for p in procs]
    finally:
        # a worker dying early leaves its peer wedged in the collective;
        # communicate(timeout=...) does NOT kill on timeout — do it here
        # so neither process leaks holding the coordinator port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for p, (o, e) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o}\n{e}"

    expected_label_sum = float(n * (n - 1) / 2)
    for pid in range(2):
        total, weights_sum, rows = open(out + str(pid)).read().split()
        # both processes observed the same GLOBAL batches: every row
        # exactly once (label sum is the arange fingerprint), no padding
        # rows counted as real (weights sum == n)
        assert float(total) == expected_label_sum
        assert float(weights_sum) == float(n)
        assert int(rows) == n
