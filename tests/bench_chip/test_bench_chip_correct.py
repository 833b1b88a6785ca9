"""``correct`` has to fail what it exists to catch. On the CPU at a tiny
size: the control (the plain reference in bfloat16 in the program's
place) fails a limit, and a run with the timed path broken underneath
reads ``correct`` false for each fault its cell can have."""

import pytest
from bench_chip_helpers import BENCH, run_tiny, tiny

import harness

pytestmark = pytest.mark.jax


@pytest.mark.parametrize("workload", ["criteo_fm.seq", "higgs_logreg.csv"])
def test_control_fails_and_the_program_passes(workload):
    r = harness.Run(workload, 2**31 + 99, platform="cpu", bench_dir=BENCH,
                    overrides=tiny(workload))
    try:
        r.setup()
        r.params = None
        r.feed.close()
        first = r.first_plain()
        ref = r.reference(first)
        limits = r.cfg["limits"]
        prog = harness.gaps(r.prog_traj, ref)
        assert all(prog[k] <= limits[k] for k in limits), prog
        for dtype, fault in (("bfloat16", ""), ("float32", "half_batch")):
            bad = harness.gaps(r.reference(first, dtype, fault), ref)
            assert any(bad[k] > limits[k] for k in limits), (dtype, fault, bad)
    finally:
        r.close()


def _unchanged(step, cfg, mesh):
    def broken(params, batch):
        _new, loss = step(params, batch)
        return params, loss

    return broken


def _half_batch(step, cfg, mesh):
    """The second half of each batch left out, the mean taken over the
    rest: also what leaving out the exchange over ``data`` gives on a
    2-way data axis (each replica steps on its own half; the state read
    back is replica 0's)."""
    import jax.numpy as jnp

    def broken(params, batch):
        w = batch["weights"]
        keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return step(params, dict(batch, weights=w * keep))

    return broken


def _altered(batch):
    """One answer altered where it is produced: the first row's label of
    every staged batch flipped."""
    lab = batch["labels"]
    return dict(batch, labels=lab.at[0].set(1.0 - lab[0]))


FAULTS = {
    "unchanged_state": {"step": _unchanged},
    "half_batch": {"step": _half_batch},
    "altered_answer": {"batch": _altered},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["criteo_fm.seq", "higgs_logreg.csv",
                                      "criteo_fm.shuffle"])
def test_a_broken_timed_path_reads_incorrect(workload, fault):
    out = run_tiny(workload, seconds=0.3, hooks=FAULTS[fault])
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["unchanged_state", "exchange_left_out",
                                   "altered_answer"])
def test_a_broken_mesh_path_reads_incorrect(fault):
    hooks = FAULTS["half_batch" if fault == "exchange_left_out" else fault]
    out = run_tiny("criteo_fm_2x2.seq", seconds=0.3, hooks=hooks)
    assert out["correct"] is False, out["checks"]
