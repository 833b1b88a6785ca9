"""The chip benchmark's harness on the CPU: it refuses the CPU, finds
configs, traffic and metrics by name, prints the contract's keys, and its
generators give the same batches through the fused path and the plain
readers."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest
from bench_chip_helpers import BENCH, REPO, cells, run_tiny, tiny

import harness

pytestmark = pytest.mark.jax

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CONFIG_FILES = ("program.py", "generate.py", "reference.py", "work.py", "tiny.json")


def test_command_refuses_the_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["command"]
    proc = subprocess.run(
        [sys.executable] + cmd[1:] + ["--workload", "criteo_fm.seq", "--seed",
                                      "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "'cpu'" in proc.stderr


def test_benchmark_json_names_files_that_exist():
    """Checks that hold for every entry, so that an appended config, cell
    or metric is held to them with no edit here."""
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    configs = {c["name"]: c for c in spec["configs"]}
    names = [w["name"] for w in spec["workloads"]]
    assert names == cells()
    for group in (list(configs), names):
        assert len(set(group)) == len(group)
        assert all(NAME.fullmatch(n) for n in group), group
    for c in configs.values():
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in spec["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        cell = harness.Cell(BENCH, w["name"])
        for fname in CONFIG_FILES:
            assert os.path.exists(cell.find(fname))
    assert {w["config"] for w in spec["workloads"]} == set(configs)
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= set(names), m["name"]
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert {w["chips"] for w in spec["workloads"]} <= {1, 4}
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("trace", [False, True])
def test_last_line_has_the_contract_keys(trace):
    out = run_tiny("criteo_fm.seq", trace=trace)
    keys = ["correct", "attempted", "failed", "metrics"]
    keys += ["breakdown"] if trace else []
    assert list(out) == keys + ["device", "checks"]
    assert out["correct"] is True
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    want = [m["name"] for m in spec["per_layer" if trace else "end_to_end"]]
    assert sorted(out["metrics"]) == sorted(want)
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"}
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    dev |= {"busy_s", "window_s"} if trace else set()
    assert set(out["device"]) == dev
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


@pytest.mark.parametrize("own_tiny", [False, True], ids=["borrowed", "own"])
def test_config_traffic_and_metric_are_found_by_name(tmp_path, own_tiny):
    """A config (its code borrowed through ``"code"``, its tiny sizes
    borrowed or its own), a traffic, a cell and a metric added as new
    files and appended entries in a copy are run with no edit to any file
    that was there."""
    copy = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "native"), tmp_path / "native")
    before = {p: open(os.path.join(copy, p), "rb").read()
              for p in _files(copy)}
    (copy / "configs" / "tiny_fm").mkdir()
    cfg = json.load(open(os.path.join(BENCH, "configs", "criteo_fm", "config.json")))
    cfg.update({"code": "criteo_fm", "embed_dim": 8})
    (copy / "configs" / "tiny_fm" / "config.json").write_text(json.dumps(cfg))
    own = {"config": {"rows": 2048, "n_features": 1 << 10},
           "traffic": {"batch": 128}}
    if own_tiny:
        (copy / "configs" / "tiny_fm" / "tiny.json").write_text(json.dumps(own))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "seq.json")))
    (copy / "traffic" / "tiny_seq.json").write_text(json.dumps(traffic))
    (copy / "metrics" / "batches_seen.py").write_text(
        "def read(r):\n    return float(r['batches'])\n")
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny_fm", "source": "t",
                            "file": "benchmarks/chip/configs/tiny_fm/config.json",
                            "reduced": [], "why": "t"})
    spec["workloads"].append({"name": "tiny_fm.seq", "config": "tiny_fm",
                              "traffic": "tiny_seq", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "batches_seen", "unit": "batches",
                              "better": "higher", "source": "program_span",
                              "layer": "dispatch and host to HBM",
                              "moves": "rows_per_s", "workloads": []})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny_fm.seq")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    assert cells(str(tmp_path))[-1] == "tiny_fm.seq"
    sizes = tiny("tiny_fm.seq", str(copy))
    assert sizes == (own if own_tiny else tiny("criteo_fm.seq"))
    untraced = run_tiny("tiny_fm.seq", seed=3, bench_dir=str(copy))
    assert untraced["correct"] is True, untraced["checks"]
    assert set(untraced["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    traced = run_tiny("tiny_fm.seq", seed=4, trace=True, bench_dir=str(copy))
    assert traced["correct"] is True, traced["checks"]
    assert set(traced["metrics"]) == {m["name"] for m in spec["per_layer"]}
    assert traced["metrics"]["batches_seen"]["value"] > 0
    assert traced["metrics"]["batches_seen"]["unit"] == "batches"
    after = {p: open(os.path.join(copy, p), "rb").read() for p in before}
    assert after == before


def _files(root):
    for d, _dirs, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                yield os.path.relpath(os.path.join(d, f), root)


@pytest.mark.parametrize("workload", cells())
def test_generators_give_the_same_batches_fused_and_plain(workload, tmp_path):
    """Two whole epochs through the fused producers equal the plain
    readers' batches bit for bit."""
    cell = harness.Cell(BENCH, workload)
    cell.cfg.update(tiny(workload)["config"])
    cell.traffic.update(tiny(workload)["traffic"])
    cfg, traffic = cell.cfg, harness.resolve_traffic(cell.traffic)
    seed = 2**33 + 5
    corpus = cell.module("generate").generate(cfg, traffic, seed, str(tmp_path))
    got = {}
    for e in range(2):
        stream = cell.module("program").open_epoch(cfg, traffic, corpus, seed, e)
        try:
            for j, b in enumerate(stream):
                got[(e, j)] = {k: np.array(v) for k, v in b.as_dict().items()}
        finally:
            stream.close()
    rows = sum(int((b["weights"] > 0).sum()) for b in got.values())
    assert rows == 2 * corpus["rows"]
    want = cell.module("reference").plain_batches(cfg, traffic, corpus, seed,
                                                  sorted(got))
    for key, b in got.items():
        assert sorted(b) == sorted(want[key]), key
        for k in b:
            assert b[k].dtype == want[key][k].dtype, (key, k)
            assert b[k].tobytes() == want[key][k].tobytes(), (key, k)
    if traffic["read"] == "shuffle":
        assert got[(0, 0)]["indices"].tobytes() != got[(1, 0)]["indices"].tobytes()


def test_criteo_generator_writes_what_write_rowrec_writes(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from train_criteo_rec import synth

    cell = harness.Cell(BENCH, "criteo_fm.seq")
    cfg = dict(cell.cfg, rows=1500, n_features=1 << 12)
    seed = 2**32 + 9
    synth(str(tmp_path / "ref.rec"), rows=1500, n_features=1 << 12, seed=seed)
    corpus = cell.module("generate").generate(cfg, {}, seed, str(tmp_path))
    assert open(corpus["path"], "rb").read() == open(tmp_path / "ref.rec", "rb").read()
    assert (open(corpus["index"]).read()
            == open(str(tmp_path / "ref.rec") + ".idx").read())


def test_higgs_generator_writes_the_uci_layout(tmp_path):
    cell = harness.Cell(BENCH, "higgs_logreg.csv")
    cfg = dict(cell.cfg, rows=300)
    corpus = cell.module("generate").generate(cfg, {}, 11, str(tmp_path))
    lines = open(corpus["path"], "rb").read().split(b"\n")
    assert lines[-1] == b"" and len(lines) == 301
    for line in lines[:-1]:
        cells = line.split(b",")
        assert len(cells) == 29
        assert cells[0] in (b"0.000000000000000000e+00", b"1.000000000000000000e+00")
        for c in cells:
            assert c == b"%.18e" % float(c)


def test_same_seed_same_corpus(tmp_path):
    cell = harness.Cell(BENCH, "higgs_logreg.csv")
    cfg = dict(cell.cfg, rows=500)
    gen = cell.module("generate").generate
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    a = gen(cfg, {}, 2**31 + 3, str(tmp_path / "a"))["path"]
    b = gen(cfg, {}, 2**31 + 3, str(tmp_path / "b"))["path"]
    assert open(a, "rb").read() == open(b, "rb").read()


@pytest.mark.parametrize("workload", cells())
def test_each_cell_runs_correct_on_the_cpu(workload):
    out = run_tiny(workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want


def test_the_run_log_prints_the_model_counters(capsys):
    out = run_tiny("criteo_fm.seq")
    assert out["correct"] is True, out["checks"]
    (line,) = [ln for ln in capsys.readouterr().err.splitlines()
               if ln.startswith("[bench] counters: ")]
    counters = json.loads(line.split(": ", 1)[1])
    assert counters['models.fm.update_path{path="sparse"}'] >= 1
