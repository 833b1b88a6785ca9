"""Data-driven chip benchmark harness (see PERF.md).

One run of one cell: ``run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``. Everything that belongs to one configuration, traffic
mix or per-layer metric lives in files of its own, found by name:

- ``configs/<config>/config.json``: sizes, source, ``reduced``,
  ``assumed``, mesh, limits of the ``correct`` comparison. Its other
  files (``program.py``: the system under test; ``generate.py``: the
  corpus; ``reference.py``: the plain readers and the plain model;
  ``work.py``: required FLOPs and bytes; ``tiny.json``: the CPU tests'
  sizes, ``{"config": {...}, "traffic": {...}}``) are looked up by
  ``Cell.find``: in the config's directory first, then in
  ``configs/<code>/`` when the config names one.
- ``traffic/<traffic>.json``: read mode, format, batch, dtype, shuffle.
- ``metrics/<metric>.py``: ``read(record) -> float | None``; nothing
  when the record holds nothing to read.

Adding to the benchmark edits no file that is there. A configuration is
a new ``configs/<config>/`` holding ``config.json`` and either its own
``program.py``, ``generate.py``, ``reference.py``, ``work.py`` and
``tiny.json`` or a ``"code"`` key that borrows those of another config;
a traffic mix is a new ``traffic/<traffic>.json``; a per-layer metric a
new ``metrics/<metric>.py``. ``BENCHMARK.json`` then gains appended
entries only: the ``configs`` entry, the ``workloads`` entry, the
``per_layer`` entry, and the new cell's name appended to the
``workloads`` list of every metric it reports. The CPU tests
(``tests/bench_chip/``) read the cells from ``BENCHMARK.json`` and run
each at its config's ``tiny.json`` sizes.

A run: refuse any platform but the TPU, place the compile cache inside
the checkout, check the native core, generate the corpus from the seed,
init the params on the device, run the first three steps (which compile
and feed the ``correct`` comparison), time the window, compare, print.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECK_STEPS = 3  # steps the reference follows
IN_FLIGHT = 2  # steps dispatched ahead of the one whose loss is awaited
SAMPLE = 4  # staged batches of the window compared with the plain readers


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


# -- finding things by name ----------------------------------------------------


class Cell:
    """One ``workloads`` entry with its configuration, traffic and code."""

    def __init__(self, bench_dir: str, name: str) -> None:
        self.bench_dir = bench_dir
        self.root = os.path.dirname(os.path.dirname(bench_dir))
        spec = load_json(os.path.join(self.root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.cfg_name = self.entry["config"]
        self.cfg = load_json(self.config_file(self.cfg_name, "config.json"))
        self.traffic = load_json(
            os.path.join(bench_dir, "traffic", self.entry["traffic"] + ".json")
        )
        self._modules: Dict[str, Any] = {}

    def config_file(self, cfg_name: str, fname: str) -> str:
        return os.path.join(self.bench_dir, "configs", cfg_name, fname)

    def find(self, fname: str) -> str:
        """``configs/<config>/<fname>``, else ``configs/<code>/<fname>``."""
        dirs = [self.cfg_name] + ([self.cfg["code"]] if "code" in self.cfg else [])
        for d in dirs:
            path = self.config_file(d, fname)
            if os.path.exists(path):
                return path
        raise SystemExit(f"bench: config {self.cfg_name!r} has no {fname}")

    def module(self, name: str):
        """The config's ``<name>.py`` as ``find`` locates it."""
        if name not in self._modules:
            path = self.find(name + ".py")
            d = os.path.basename(os.path.dirname(path))
            self._modules[name] = _load(path, f"bench_{d}_{name}")
        return self._modules[name]

    def metric_names(self, trace: bool) -> List[str]:
        out = []
        for m in self.spec["per_layer" if trace else "end_to_end"]:
            if self.name in m.get("workloads", [self.name]):
                out.append(m["name"])
        return out

    def metric_unit(self, name: str) -> str:
        for m in self.spec["end_to_end"] + self.spec["per_layer"]:
            if m["name"] == name:
                return m["unit"]
        raise KeyError(name)


def _load(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, name: str) -> Callable:
    return _load(os.path.join(bench_dir, "metrics", name + ".py"),
                 f"bench_metric_{name.replace('.', '_')}").read


# -- device, cache, native --------------------------------------------------------


def check_device(chips: int, platform: str):
    import jax

    devs = jax.devices()
    found = devs[0].platform
    if found != platform:
        raise SystemExit(
            f"bench: JAX found platform {found!r} ({len(devs)} device(s)), "
            f"not {platform!r}; this benchmark measures only on the chip"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"bench: the cell asks for {chips} chips, JAX found {len(devs)}"
        )
    return devs


def build_native(root: str) -> str:
    """``make -C native`` once per checkout; the loaded core must match
    ``fastparse.cc`` by its source hash (as ``chip_smoke.build_native``)."""
    import hashlib

    native_dir = os.path.join(root, "native")
    proc = subprocess.run(["make", "-C", native_dir], capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise SystemExit("bench: native build failed:\n"
                         + (proc.stdout + proc.stderr)[-2000:])
    from dmlc_core_tpu.data import native

    with open(os.path.join(native_dir, "fastparse.cc"), "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()
    if not native.load() or native.source_hash() != want:
        raise SystemExit("bench: the native core does not load or is stale")
    return want[:12]


_T_IMPORT = time.perf_counter()


def process_age() -> float:
    """Seconds since this process started (``/proc``), for ``setup_s``."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


COMPILES: collections.Counter = collections.Counter()
_LISTENING: List[bool] = []


def _count_compiles() -> None:
    """Count JAX's compile requests and persistent-cache misses, so that
    set-up shows what compiled and the window shows that nothing did."""
    import jax

    def listen(event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            COMPILES[event.rsplit("/", 1)[1]] += 1

    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_listener(listen)


# -- the feed: one StagingPipeline per epoch, as a training job runs them ----------


class _Counted:
    """Host batch stream that records each batch's valid rows in order."""

    def __init__(self, stream) -> None:
        self.stream = stream
        self.ring_slots = getattr(stream, "ring_slots", None)
        self.rows: collections.deque = collections.deque()

    def __iter__(self):
        for b in self.stream:
            self.rows.append(int(b.n_valid))
            yield b


_STAGES = ("host_pull", "dispatch_pack", "dispatch_put", "dispatch_slot_wait",
           "transfer_wait")


class EpochFeed:
    """Closed loop over epochs: a new producer and pipeline per epoch
    (``examples/train_criteo_rec.py``); boundaries fall where they fall."""

    def __init__(self, open_epoch: Callable, pipe_kwargs: dict) -> None:
        self.open_epoch = open_epoch
        self.pipe_kwargs = pipe_kwargs
        self.epoch = -1
        self.j = 0
        self.pipe = self.it = self.src = self.stream = None
        self.closed_stage = {k: 0.0 for k in _STAGES}
        self.closed_batches = 0
        self.epoch_rows: Dict[int, int] = {}
        self.boundaries: List[float] = []
        self.staging: List[dict] = []

    def _open(self) -> None:
        from dmlc_core_tpu.staging import StagingPipeline

        self.epoch += 1
        self.j = 0
        self.stream = self.open_epoch(self.epoch)
        self.src = _Counted(self.stream)
        self.pipe = StagingPipeline(self.src, **self.pipe_kwargs)
        self.it = iter(self.pipe)

    def _close(self, finished: bool) -> None:
        from dmlc_core_tpu.staging import drain_close

        self.staging.append(self.pipe.staging_stats())
        for k in _STAGES:
            self.closed_stage[k] += self.pipe.stage_seconds[k]
        self.closed_batches += self.pipe.batches_staged
        if finished:
            self.epoch_rows[self.epoch] = self.pipe.rows_staged
        drain_close(self.pipe, self.stream)
        self.pipe = self.it = None

    def next(self):
        t = time.perf_counter()
        crossed = False
        while True:
            if self.it is None:
                self._open()
            b = next(self.it, None)
            if b is not None:
                if crossed:
                    self.boundaries.append(time.perf_counter() - t)
                j, self.j = self.j, self.j + 1
                return self.epoch, j, b, self.src.rows.popleft()
            self._close(finished=True)
            crossed = True

    def stage_totals(self) -> Dict[str, float]:
        out = dict(self.closed_stage)
        if self.pipe is not None:
            for k in _STAGES:
                out[k] += self.pipe.stage_seconds[k]
        return out

    def batches_total(self) -> int:
        return self.closed_batches + (
            self.pipe.batches_staged if self.pipe is not None else 0)

    def close(self) -> None:
        if self.pipe is not None:
            self._close(finished=False)


# -- state readings for the comparison ------------------------------------------


def leaf_norms(a: dict, b: dict, scale: float):
    """{leaf: ||(a - b) / scale||} in float32, computed where the leaves live."""
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            (a[k].astype(jnp.float32) - b[k].astype(jnp.float32)) / scale)))
            for k in a}

    return jax.jit(norms)(a, b)


def trajectory(step, params, batches, lr: float):
    """Drive ``step`` through the first batches: the losses, the first
    gradient's leaf norms as the optimizer got it ((p0 - p1) / lr), and the
    leaf norms of the change after the steps (p3 - p0)."""
    p0 = params
    losses = []
    g1 = None
    for i, b in enumerate(batches):
        params, loss = step(params, b)
        losses.append(loss)
        if i == 0:
            g1 = leaf_norms(p0, params, lr)
    d3 = leaf_norms(params, p0, 1.0)
    return params, {"losses": losses, "g1": g1, "d3": d3}


def host_traj(t: dict) -> dict:
    import jax

    t = jax.device_get(t)
    return {"losses": [float(x) for x in t["losses"]],
            "g1": {k: float(v) for k, v in t["g1"].items()},
            "d3": {k: float(v) for k, v in t["d3"].items()}}


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared: the widest relative loss gap over the steps,
    and by the worst leaf the gap between the program's and the
    reference's norm of the first gradient and of the change, each against
    the larger of the reference leaf's norm and the median leaf's. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out of the change."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if not np.isfinite(lp).all():
        return {"loss_gap": math.inf, "grad_gap": math.inf,
                "change_gap": math.inf}
    loss_gap = float(np.max(np.abs(lp - lr_) / np.abs(lr_)))
    med_g = float(np.median(list(ref["g1"].values())))
    med_d = float(np.median(list(ref["d3"].values())))
    grad_gap = max(abs(prog["g1"][k] - r) / max(r, med_g)
                   for k, r in ref["g1"].items())
    moved = [k for k, r in ref["g1"].items() if r >= 1e-3 * med_g]
    change_gap = max(abs(prog["d3"][k] - ref["d3"][k]) / max(ref["d3"][k], med_d)
                     for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": float(grad_gap),
            "change_gap": float(change_gap)}


def batch_mismatches(staged: Dict[Any, dict], plain: Dict[Any, dict]) -> int:
    """Staged batches (read back from HBM) whose bytes differ from the
    plain readers' batch at the same (epoch, position)."""
    import jax

    bad = 0
    for key, dev in staged.items():
        got = jax.device_get(dev)
        want = plain[key]
        same = sorted(got) == sorted(want) and all(
            np.asarray(got[k]).dtype == want[k].dtype
            and np.asarray(got[k]).shape == want[k].shape
            and np.asarray(got[k]).tobytes() == want[k].tobytes()
            for k in want)
        if not same:
            bad += 1
            log(f"check: staged batch {key} differs from the plain readers")
    return bad


# -- one run -------------------------------------------------------------------


def resolve_traffic(traffic: dict) -> dict:
    """Traffic values fixed at run time (``nthread: "cpus"``)."""
    out = dict(traffic)
    if out.get("nthread") == "cpus":
        from dmlc_core_tpu.utils.cpus import available_cpus

        out["nthread"] = available_cpus()
    return out


def make_mesh(cell: Cell, devices):
    mesh = cell.cfg.get("mesh")
    if not mesh:
        return None
    from dmlc_core_tpu.parallel import make_mesh as mk

    n = int(np.prod(mesh["shape"]))
    return mk(tuple(mesh["shape"]), tuple(mesh["axes"]), devices=devices[:n])


class Run:
    """One run of one cell: set-up, window, check. ``platform``,
    ``overrides`` (config/traffic keys) and ``hooks`` (faults planted
    under the timed path, a canned trace reduction) are for the CPU tests
    and the calibration only."""

    def __init__(self, workload: str, seed: int, *, bench_dir: str = BENCH,
                 platform: str = "tpu", overrides: Optional[dict] = None,
                 hooks: Optional[dict] = None) -> None:
        self.hooks = hooks or {}
        self.seed = seed
        self.cell = cell = Cell(bench_dir, workload)
        if overrides:
            cell.cfg.update(overrides.get("config", {}))
            cell.traffic.update(overrides.get("traffic", {}))
        self.devices = check_device(cell.chips, platform)[: cell.chips]
        _count_compiles()
        from dmlc_core_tpu.utils.compile_cache import place_compile_cache

        log(f"device: {self.devices[0].device_kind}, {cell.chips} used; "
            f"compile cache {place_compile_cache()}")
        log(f"native: source {build_native(cell.root)}")
        self.prog, self.ref, self.work = (
            cell.module(n) for n in ("program", "reference", "work"))
        self.cfg, self.traffic = cell.cfg, resolve_traffic(cell.traffic)
        self.tmp = tempfile.mkdtemp(prefix="benchchip_")
        self.feed: Optional[EpochFeed] = None
        self.staged: Dict[Any, dict] = {}
        self.params = None

    def close(self) -> None:
        self.params = None
        if self.feed is not None:
            self.feed.close()
            self.feed = None
        shutil.rmtree(self.tmp, ignore_errors=True)

    def setup(self) -> None:
        """Corpus, init, and the first ``CHECK_STEPS`` steps through the
        window's own feed and call (they compile, and the reference
        follows them). Leaves the params after them in ``self.params``,
        which the window takes over (no second copy lives through it)."""
        cfg, traffic, seed = self.cfg, self.traffic, self.seed
        t = time.perf_counter()
        self.corpus = corpus = self.cell.module("generate").generate(
            cfg, traffic, seed, self.tmp)
        log(f"data: {corpus['rows']} rows, {corpus['bytes']} bytes "
            f"({time.perf_counter() - t:.2f} s)")
        self.mesh = make_mesh(self.cell, self.devices)
        params = self.ref.init_params(cfg, seed, self.mesh)
        step = self.prog.make_step(cfg, self.mesh)
        if "step" in self.hooks:
            step = self.hooks["step"](step, cfg, self.mesh)
        self.step = step
        self.alter = self.hooks.get("batch", lambda b: b)
        self.feed = EpochFeed(
            lambda e: self.prog.open_epoch(cfg, traffic, corpus, seed, e),
            self.prog.pipeline_kwargs(cfg, self.mesh))
        first = []
        for _ in range(CHECK_STEPS):
            e, j, b, _rows = self.feed.next()
            b = self.alter(b)
            self.staged[(e, j)] = b
            first.append(b)
        params, traj = trajectory(step, params, first, float(cfg["lr"]))
        self.prog_traj = host_traj(traj)
        import jax

        self.params = jax.block_until_ready(params)

    def first_plain(self) -> list:
        keys = sorted(self.staged)[:CHECK_STEPS]
        plain = self.ref.plain_batches(self.cfg, self.traffic, self.corpus,
                                       self.seed, keys)
        return [plain[k] for k in keys]

    def reference(self, batches, dtype: str = "float32", fault: str = "") -> dict:
        return self.ref.reference_trajectory(self.cfg, self.seed, self.mesh,
                                             batches, dtype, fault)

    def measure(self, seconds: float, trace: bool,
                trace_out: Optional[str] = None) -> dict:
        import jax

        self.setup()
        setup_s = process_age()
        log(f"setup: {setup_s:.3f} s; first losses {self.prog_traj['losses']}; "
            f"compile requests {COMPILES['compile_requests_use_cache']}, "
            f"cache misses {COMPILES['cache_misses']}")
        before = dict(COMPILES)
        win = self.window(seconds, trace, trace_out)
        log("window: compile requests "
            f"{COMPILES['compile_requests_use_cache'] - before.get('compile_requests_use_cache', 0)}")
        params = win.pop("params")
        jax.block_until_ready(params)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        del params
        self.feed.close()
        log(f"window: {win['steps']} steps, {win['rows']} rows in "
            f"{win['window_s']:.3f} s; {len(win['intervals'])} step intervals; "
            f"{win['boundaries']} epoch boundaries, {win['boundary_s']:.3f} s")
        log("staging: " + json.dumps(self.feed.staging[-1] if self.feed.staging
                                     else {}, sort_keys=True))
        from dmlc_core_tpu.telemetry import default_registry

        log("counters: " + json.dumps(
            default_registry().counter_values(prefix="models."), sort_keys=True))
        checks = self.check()
        dev = self.devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
        out: Dict[str, Any] = {
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": win["attempted"], "failed": win["failed"]}
        cell = self.cell
        if trace:
            record = self.record(win)
            red = record["trace"]
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["metrics"] = {}
            for name in cell.metric_names(trace=True):
                v = metric_reader(cell.bench_dir, name)(record)
                if v is not None:
                    out["metrics"][name] = {"value": v,
                                            "unit": cell.metric_unit(name)}
            out["breakdown"] = {"device_ops": red["device_ops"][:10],
                                "idle_gaps": red["idle_gaps"][:10]}
        else:
            e2e = {
                "setup_s": setup_s,
                "rows_per_s": win["rows"] / win["window_s"],
                "step_ms_p95": 1e3 * float(np.percentile(win["intervals"], 95)),
                "peak_hbm_gb": peak / 1e9,
            }
            out["metrics"] = {k: {"value": v, "unit": cell.metric_unit(k)}
                              for k, v in e2e.items()
                              if k in cell.metric_names(trace=False)}
        out["device"] = device
        out["checks"] = checks
        return out

    def window(self, seconds: float, trace: bool,
               trace_out: Optional[str]) -> dict:
        """The timed window: a closed loop, ``IN_FLIGHT`` steps ahead of
        the step whose loss it waits for; a step completes when its loss
        is seen. Rows count for steps completed inside the window."""
        import jax
        from jax.profiler import TraceAnnotation

        feed, step, alter = self.feed, self.step, self.alter
        params, self.params = self.params, None
        rng = np.random.default_rng([self.seed, 1])
        reservoir: Dict[Any, dict] = {}
        seen = 0
        inflight: collections.deque = collections.deque()
        intervals: List[float] = []
        rows = steps = attempted = failed = 0
        input_wait = 0.0
        b0 = len(feed.boundaries)
        trace_dir = os.path.join(self.tmp, "trace")
        if trace:
            # host spans (TraceMe) and device ops; no Python function tracer
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        stage0, batches0 = feed.stage_totals(), feed.batches_total()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        last = t0
        with TraceAnnotation("bench:window"):
            while True:
                ti = time.perf_counter()
                with TraceAnnotation("bench:input_wait"):
                    e, j, b, n = feed.next()
                    b = alter(b)
                input_wait += time.perf_counter() - ti
                seen += 1
                if len(reservoir) < SAMPLE:
                    reservoir[(e, j)] = b
                elif rng.integers(seen) < SAMPLE:
                    del reservoir[list(reservoir)[rng.integers(SAMPLE)]]
                    reservoir[(e, j)] = b
                with TraceAnnotation("bench:step"):
                    params, loss = step(params, b)
                del b
                attempted += 1
                inflight.append((loss, n))
                if len(inflight) <= IN_FLIGHT:
                    continue
                loss, n = inflight.popleft()
                with TraceAnnotation("bench:step_wait"):
                    loss = float(loss)
                now = time.perf_counter()
                if now > t_end:
                    break
                if not math.isfinite(loss):
                    failed += 1
                intervals.append(now - last)
                last = now
                steps += 1
                rows += n
        stage1, batches1 = feed.stage_totals(), feed.batches_total()
        if trace:
            jax.block_until_ready(params)
            jax.profiler.stop_trace()
            if trace_out:
                shutil.copytree(trace_dir, trace_out, dirs_exist_ok=True)
        jax.block_until_ready([loss for loss, _n in inflight])
        self.staged.update(reservoir)
        return {
            "params": params, "steps": steps, "rows": rows, "window_s": seconds,
            "intervals": intervals, "attempted": attempted, "failed": failed,
            "boundaries": len(feed.boundaries) - b0,
            "boundary_s": float(sum(feed.boundaries[b0:])),
            "input_wait_s": input_wait,
            "stage_s": {k: stage1[k] - stage0[k] for k in _STAGES},
            "batches": batches1 - batches0,
            "trace_dir": trace_dir if trace else None,
        }

    def record(self, win: dict) -> dict:
        """What the per-layer readers read: the window's stage seconds and
        counts, the harness's own spans, the reduced trace, the required
        work and the peaks."""
        cell, cfg, traffic = self.cell, self.cfg, self.traffic
        t = time.perf_counter()
        if "reduce" in self.hooks:
            red = self.hooks["reduce"](win["trace_dir"])
        else:
            trace_reduce = _load(os.path.join(cell.bench_dir, "trace_reduce.py"),
                                 "bench_trace_reduce")
            red = trace_reduce.reduce_dir(win["trace_dir"], len(self.devices))
        log(f"trace: reduced in {time.perf_counter() - t:.2f} s; busy "
            f"{red['busy_s']:.6f} s of {red['window_s']:.6f} s")
        return {
            "cfg": cfg, "traffic": traffic, "chips": len(self.devices),
            "peaks": device_peaks(cell.bench_dir, self.hooks.get(
                "device_kind", self.devices[0].device_kind)),
            "steps": win["steps"], "dispatched": win["attempted"],
            "batches": win["batches"], "stage_s": win["stage_s"],
            "input_wait_s": win["input_wait_s"], "trace": red,
            "step_name": cfg["step_name"],
            "work": {"step": self.work.step_work(cfg, traffic),
                     "unpack_bytes": self.work.unpack_bytes(cfg, traffic)},
        }

    def check(self) -> dict:
        """``correct``: staged batches against the plain readers, bit for
        bit; whole epochs' row counts; every batch on the configured
        staging path; the first steps' losses, first gradient and change
        against the plain float32 reference from the same init."""
        cfg, feed = self.cfg, self.feed
        t = time.perf_counter()
        keys = sorted(self.staged)
        plain = self.ref.plain_batches(cfg, self.traffic, self.corpus,
                                       self.seed, keys)
        mism = batch_mismatches(self.staged, plain)
        self.staged.clear()
        short = sum(1 for r in feed.epoch_rows.values()
                    if r != self.corpus["rows"])
        path = cfg["staging_path"] + "_batches"
        off_path = sum(s["packed_batches"] + s["packed_shard_batches"]
                       + s["per_array_batches"] - s[path] for s in feed.staging)
        ref_traj = self.reference([plain[k] for k in keys[:CHECK_STEPS]])
        g = gaps(self.prog_traj, ref_traj)
        log(f"check: reference losses {ref_traj['losses']} "
            f"({time.perf_counter() - t:.2f} s); {len(keys)} batches "
            f"compared, {len(feed.epoch_rows)} whole epochs counted")
        out = {
            "batch_mismatch": {"value": mism, "limit": 0},
            "epoch_rows_short": {"value": short, "limit": 0},
            "off_path_batches": {"value": off_path, "limit": 0},
        }
        for k in ("loss_gap", "grad_gap", "change_gap"):
            out[k] = {"value": g[k], "limit": cfg["limits"][k]}
        return out


def device_peaks(bench_dir: str, kind: str) -> dict:
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def run(workload: str, seed: int, seconds: float, trace: bool,
        trace_out: Optional[str] = None, **kw) -> dict:
    r = Run(workload, seed, **kw)
    try:
        return r.measure(seconds, trace, trace_out)
    finally:
        r.close()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="copy the profiler trace here (for reading by hand)")
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace),
              trace_out=args.trace_out)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
