"""The examples/ scripts must keep running — they are the README's
quick-start and double as end-to-end smoke coverage of the public API
(the reference keeps example/*.cc building in CI via its Makefile).

Each runs as a subprocess on the CPU backend (``JAX_PLATFORMS=cpu`` in
its env, plus the same pin as a pre-import ``jax.config.update`` shim),
the pin every other subprocess test in this repo uses: tests never take
the chip. The shim also turns JAX's persistent compilation cache off —
the examples' ``__main__`` places it (utils/compile_cache.py), and a test
must not write into the repo's cache directory."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")

# pin BEFORE the example's own jax import wins the backend choice
_RUNNER = """
import runpy, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
sys.argv = sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def run_example(script, args=(), timeout=240, cwd=None, extra_env=None):
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", _RUNNER,
         os.path.join(EXAMPLES, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=cwd,
    )


def test_parameter_demo():
    proc = run_example(
        "parameter_demo.py", ["learning_rate=0.1", "name=smoke"]
    )
    assert proc.returncode == 0, proc.stderr
    assert "initialized:" in proc.stdout
    assert "Step size." in proc.stdout  # only the generated docs print this


@pytest.mark.slow
def test_train_higgs(tmp_path):
    shutil.rmtree("/tmp/higgs_ckpts", ignore_errors=True)
    try:
        proc = run_example(
            "train_higgs.py", [str(tmp_path / "higgs.libsvm")],
            cwd=str(tmp_path),
        )
        assert proc.returncode == 0, proc.stderr
        assert "epoch" in proc.stdout and "loss=" in proc.stdout
    finally:
        shutil.rmtree("/tmp/higgs_ckpts", ignore_errors=True)


@pytest.mark.slow
def test_train_criteo_rec_dynamic_shards(tmp_path):
    """DMLC_DYNAMIC_SHARDS=1: the trainer pulls tracker-leased
    micro-shards instead of its static rank shard (docs/sharding.md) —
    end-to-end through the rendezvous, the lease protocol and the
    fused staging path, with the ledger confirming every micro-shard
    was completed exactly once."""
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    shutil.rmtree("/tmp/criteo_ckpts_v2", ignore_errors=True)
    tracker = RabitTracker("127.0.0.1", 1)
    tracker.start(1)
    try:
        proc = run_example(
            "train_criteo_rec.py", [str(tmp_path / "c.rec")],
            cwd=str(tmp_path),
            extra_env={
                "DMLC_TRACKER_URI": "127.0.0.1",
                "DMLC_TRACKER_PORT": str(tracker.port),
                "DMLC_NUM_WORKER": "1",
                "DMLC_TASK_ID": "0",
                "DMLC_DYNAMIC_SHARDS": "1",
            },
        )
        assert proc.returncode == 0, proc.stderr
        assert "epoch" in proc.stdout
        summary = tracker.shards.summary()
        # 3 epochs × oversplit micro-shards, each exactly-once
        assert summary["completed"] == summary["granted"] > 0
        assert summary["duplicates"] == 0
    finally:
        tracker.close()
        shutil.rmtree("/tmp/criteo_ckpts_v2", ignore_errors=True)


@pytest.mark.slow
def test_train_criteo_rec_multihost_sgd(tmp_path):
    """Two workers under a real tracker = TRUE multi-host SGD
    (docs/collectives.md): per-step gradients allreduced by the
    collective engine, one shared update — both ranks must finish with
    BIT-IDENTICAL params (DMLC_SGD_OUT publishes each rank's final
    model; DMLC_SGD_PATH=tree pins the deterministic fold order)."""
    import numpy as np

    from dmlc_core_tpu.tracker.tracker import RabitTracker

    # generate the shard once up front: two racing workers would both
    # see the missing file and interleave their synth writes
    shutil.rmtree("/tmp/criteo_ckpts_v2", ignore_errors=True)
    proc = run_example(
        "train_criteo_rec.py", [str(tmp_path / "c.rec")],
        cwd=str(tmp_path), extra_env={"DMLC_SGD_EPOCHS": "0"},
    )
    assert proc.returncode == 0, proc.stderr
    shutil.rmtree("/tmp/criteo_ckpts_v2", ignore_errors=True)
    out = str(tmp_path / "model")
    tracker = RabitTracker("127.0.0.1", 2)
    tracker.start(2)
    try:
        env = os.environ.copy()
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.update({
            "DMLC_TRACKER_URI": "127.0.0.1",
            "DMLC_TRACKER_PORT": str(tracker.port),
            "DMLC_SGD_EPOCHS": "1",
            "DMLC_SGD_PATH": "tree",
            "DMLC_SGD_OUT": out,
        })
        procs = []
        for task in range(2):
            e = dict(env, DMLC_TASK_ID=str(task))
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _RUNNER,
                 os.path.join(EXAMPLES, "train_criteo_rec.py"),
                 str(tmp_path / "c.rec")],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=e, cwd=str(tmp_path),
            ))
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for task, p in enumerate(procs):
            assert p.returncode == 0, (
                f"worker {task} failed:\n{outs[task][-2000:]}"
            )
    finally:
        tracker.close()
    models = [np.load(f"{out}.rank{r}.npz") for r in range(2)]
    keys = sorted(models[0].files)
    assert sorted(models[1].files) == keys
    for k in keys:
        assert np.array_equal(models[0][k], models[1][k]), (
            f"param {k!r} diverged across ranks — the shared update is "
            "not shared"
        )
    # a real multi-worker run actually stepped (gradients flowed)
    assert int(models[0]["gstep"]) > 0


@pytest.mark.slow
def test_train_criteo_rec(tmp_path):
    shutil.rmtree("/tmp/criteo_ckpts", ignore_errors=True)
    try:
        proc = run_example(
            "train_criteo_rec.py", [str(tmp_path / "c.rec")],
            cwd=str(tmp_path),
        )
        assert proc.returncode == 0, proc.stderr
        assert "epoch" in proc.stdout
        # the synthetic shard publishes its count index for shuffled epochs
        assert os.path.exists(str(tmp_path / "c.rec") + ".idx")
    finally:
        shutil.rmtree("/tmp/criteo_ckpts", ignore_errors=True)
