"""Does host CPU work between puts starve the transfer threads on a
small host? Compare put loops with: nothing / sleep(5ms) / GIL-holding
Python spin / GIL-releasing numpy copy between puts.

No run of this on the current chip host is on record.

Second question (``--shuffle``): when the STAGED shuffled config
starves, is it the read layer? Drain the raw IndexedRecordIOSplitter
(no parse, no device) in each shuffle mode over the bench shard and
report rows/s plus the split's seek/span counters — the per-record
mode's seek storm vs the window mode's coalesced spans is visible here
without any device noise.

Third question (``--dynamic-shards``): what does tracker-leased
sharding cost when there is nothing to steal? Start a local tracker
in-process, drain the whole bench shard through DynamicShardSource
(every micro-shard leased by this one worker) and print the lease /
steal summary from both sides — the worker's lease_wait and the
ledger's granted/reclaimed/stolen — so the protocol overhead and the
straggler signal are observable outside bench's 3-process config."""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np



def spin(secs):
    t0 = time.perf_counter()
    x = 0
    while time.perf_counter() - t0 < secs:
        x += 1
    return x


def numpy_work(arr):
    # large memcpy-ish op; numpy releases the GIL for big copies
    return arr.copy()


def put_loop(bufs, n, between=None):
    import jax

    t0 = time.perf_counter()
    put_secs = 0.0
    for i in range(n):
        tp = time.perf_counter()
        d = jax.device_put(bufs[i % len(bufs)])  # noqa: L007 (raw link probe)
        jax.block_until_ready(d)
        put_secs += time.perf_counter() - tp
        if between is not None:
            between()
    dt = time.perf_counter() - t0
    return {
        "total_secs": round(dt, 3),
        "put_secs": round(put_secs, 3),
        "put_mb_per_sec": round(bufs[0].nbytes * n / put_secs / 1e6, 1),
    }


def shuffle_read_modes(fault: str = ""):
    """Raw split-layer drain per shuffle mode over the bench shard:
    rows/s + io_stats, no parse/device in the loop. Windowed modes
    (record/batch/window) drain through ``next_gather_batch`` — the
    zero-copy emission the fused staging layer consumes — so the
    gather_batches/gather_bytes counters and the gather-vs-legacy
    split-layer gap are visible here without any parse/device noise;
    ``legacy_record`` keeps the reference's per-record seek storm for
    contrast. ``fault`` is a fault:// spec (e.g.
    ``resets=2,errors=1,seed=7``): the drain then exercises the retry
    layer healing seeded faults, visible as
    retries/backoff_secs/faults_injected in the per-mode io_stats."""
    import bench
    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.io.faults import wrap_uri

    bench.ensure_rec_data()
    bench.ensure_rec_index()
    out = {}
    for label, mode, extra in (
        ("0", "0", ""),
        ("record", "record", ""),
        ("legacy_record", "record", "&legacy_shuffle=1"),
        ("batch", "batch", "&batch_size=4096"),
        (
            "window",
            "window",
            f"&window={bench.WINDOW}&merge_gap={bench.MERGE_GAP}",
        ),
    ):
        uri = (
            f"{wrap_uri(bench.REC_DATA, fault)}?index={bench.REC_INDEX}"
            f"&shuffle={mode}{extra}"
        )
        s = io_split.create(uri, type="recordio", threaded=False)
        gather = getattr(s, "supports_gather", lambda: False)()
        t0 = time.perf_counter()
        nbytes = 0
        while True:
            if gather:
                g = s.next_gather_batch(4096)
                if g is None:
                    break
                nbytes += int(g[2].sum())
            else:
                chunk = s.next_batch(4096)
                if chunk is None:
                    break
                nbytes += len(chunk)
        dt = time.perf_counter() - t0
        stats = getattr(s, "io_stats", lambda: None)() or {}
        s.close()
        out[f"shuffle_{label}"] = {
            "rows_per_sec": round(stats.get("records", 0) / dt, 1),
            "mb_per_sec": round(nbytes / dt / 1e6, 1),
            "secs": round(dt, 3),
            "gather_drain": gather,
            **stats,
        }
    return out


def dynamic_shard_drain(fault: str = ""):
    """``--dynamic-shards``: drain the bench shard through
    DynamicShardSource against a local in-process tracker (ISSUE 10).
    One worker, so every micro-shard is self-leased — the number this
    isolates is the lease protocol's overhead (round-trips, lease_wait)
    on top of the identical windowed read path, with the ledger's
    grant/reclaim/steal shape printed on exit. ``fault`` wraps the DATA
    reads in a fault:// spec, making the TTL/renew machinery visible
    (latency spikes stretch shard drains toward the lease TTL)."""
    import bench
    from dmlc_core_tpu.io import split as io_split
    from dmlc_core_tpu.io.faults import wrap_uri
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    bench.ensure_rec_data()
    bench.ensure_rec_index()
    tracker = RabitTracker("127.0.0.1", 1)
    tracker.start(1)
    prev_env = {
        k: os.environ.get(k)
        for k in ("DMLC_TRACKER_URI", "DMLC_TRACKER_PORT")
    }
    os.environ["DMLC_TRACKER_URI"] = "127.0.0.1"
    os.environ["DMLC_TRACKER_PORT"] = str(tracker.port)
    try:
        uri = (
            f"{wrap_uri(bench.REC_DATA, fault)}?index={bench.REC_INDEX}"
            "&shuffle=record&dynamic_shards=1"
        )
        s = io_split.create(uri, type="recordio", threaded=False)
        t0 = time.perf_counter()
        nbytes = 0
        while True:
            g = s.next_gather_batch(4096)
            if g is None:
                break
            nbytes += int(g[2].sum())
        dt = time.perf_counter() - t0
        stats = s.io_stats()
        s.close()
        return {
            "drain": {
                "rows_per_sec": round(stats.get("records", 0) / dt, 1),
                "mb_per_sec": round(nbytes / dt / 1e6, 1),
                "secs": round(dt, 3),
                **stats,
            },
            # the ledger's view: granted == completed and stolen == 0
            # on a healthy single-worker drain; reclaimed > 0 here
            # means shard drains outlived the lease TTL (renewal rides
            # the pulls, so that takes a genuine stall)
            "ledger": tracker.shards.summary(),
        }
    finally:
        tracker.close()
        # don't leak the dead tracker's address into the process
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def dsserve_drain(fault: str = ""):
    """``--dsserve``: drain the bench shard through the disaggregated
    preprocessing service end to end IN PROCESS (ISSUE 12): a local
    tracker, one DsServeServer thread leasing its micro-shards, and the
    ``dsserve://`` client source pulling packed slots over loopback.
    The numbers this isolates: the wire/framing overhead on top of the
    identical local pipeline (compare rows/s with ``--shuffle``'s
    window mode), the client's recv-wait profile (``dsserve_recv_wait``
    is where a network/server-bound trainer stalls), and the server's
    produce-vs-send overlap (queue_depth). ``fault`` wraps the DATA
    reads in a fault:// spec — the SERVER then rides the retry layer,
    the client only ever sees clean slots (chaos composes)."""
    import bench
    from dmlc_core_tpu.dsserve import DsServeBatches, DsServeServer
    from dmlc_core_tpu.io.faults import wrap_uri
    from dmlc_core_tpu.staging.batcher import BatchSpec
    from dmlc_core_tpu.telemetry import default_registry
    from dmlc_core_tpu.tracker.tracker import RabitTracker

    bench.ensure_rec_data()
    bench.ensure_rec_index()
    tracker = RabitTracker("127.0.0.1", 1)
    tracker.start(1)
    prev_env = {
        k: os.environ.get(k)
        for k in ("DMLC_TRACKER_URI", "DMLC_TRACKER_PORT")
    }
    os.environ["DMLC_TRACKER_URI"] = "127.0.0.1"
    os.environ["DMLC_TRACKER_PORT"] = str(tracker.port)
    server = DsServeServer(rank=1001).start()
    try:
        uri = (
            f"{wrap_uri(bench.REC_DATA, fault)}?index={bench.REC_INDEX}"
            "&shuffle=record&seed=1"
        )
        spec = BatchSpec(
            batch_size=4096, layout="ell", max_nnz=bench.REC_K
        )
        src = DsServeBatches(
            f"dsserve://127.0.0.1:{server.port}"
            + ("" if uri.startswith("/") else "/") + uri,
            spec, mode="lease",
        )
        t0 = time.perf_counter()
        rows = nbytes = slots = 0
        for b in src:
            rows += b.n_valid
            nbytes += b.packed.nbytes
            slots += 1
        dt = time.perf_counter() - t0
        stats = src.io_stats()
        src.close()
        reg = default_registry()
        wait = reg.histogram("dsserve.recv_wait_seconds").snapshot()
        return {
            "drain": {
                "rows_per_sec": round(rows / dt, 1),
                "slot_mb_per_sec": round(nbytes / dt / 1e6, 1),
                "secs": round(dt, 3),
                "rows": rows,
                "slots": slots,
                **stats,
            },
            # per-stage view: recv_wait is the trainer-side stall (the
            # dsserve_recv_wait stage on a merged timeline); the
            # server's counters show what the preprocessing side did
            "recv_wait_seconds": {
                k: wait[k]
                for k in ("count", "p50", "p90", "p99")
                if k in wait
            },
            "server": server.stats(),
            "ledger": tracker.shards.summary(),
        }
    finally:
        server.close()
        tracker.close()
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_TS_RING = None


def _start_timeseries() -> None:
    """Sample the registry every second for the run so the exit summary
    can print WINDOWED rates (last-30s rows/s + stall fractions) next
    to the cumulative totals — a long diag drain's tail behavior is
    otherwise averaged away by the whole-run numbers."""
    global _TS_RING
    from dmlc_core_tpu.telemetry import timeseries

    _TS_RING = timeseries.TimeSeriesRing(interval=1.0)
    _TS_RING.start()


def _print_windowed() -> None:
    if _TS_RING is None:
        return
    from dmlc_core_tpu.telemetry import timeseries

    _TS_RING.sample()  # reach "now" before querying
    print(timeseries.summary_line(_TS_RING.window(30.0)))


def _print_telemetry() -> None:
    """Exit dump of the process telemetry registry: every counter the
    drained layers ticked (split shape, retry/fault, staging) in one
    place — starvation diagnosis no longer means grepping the scattered
    per-mode io_stats dicts above it; the windowed line on top of it
    answers 'what was it doing at the END' (docs/observability.md)."""
    from dmlc_core_tpu.telemetry import to_json

    _print_windowed()
    print("telemetry: " + json.dumps(to_json()))


def _fetch_threads_arg() -> None:
    """``--fetch-threads N``: pin the span-fetch concurrency for the
    drains (exported as DMLC_FETCH_THREADS before any splitter is
    built; 1 = the serial baseline). Grow it across runs and watch the
    summary below — the observable version of what the AIMD ramp picks
    on its own."""
    if "--fetch-threads" not in sys.argv:
        return
    n = sys.argv[sys.argv.index("--fetch-threads") + 1]
    os.environ["DMLC_FETCH_THREADS"] = str(int(n))


def _print_fetch_summary() -> None:
    """Exit summary of the concurrent span fetcher (ISSUE 9): the peak
    concurrency the AIMD ramp actually reached plus the consumer-side
    span_wait_seconds percentiles and stream reopens — the autotune's
    chosen concurrency, observable outside bench. All zeros when every
    drain was local (the mmap fast path never engages the fetcher)."""
    from dmlc_core_tpu.io.spanfetch import fetch_threads
    from dmlc_core_tpu.telemetry import default_registry

    reg = default_registry()
    wait = reg.histogram("io.fetch.span_wait_seconds").snapshot()
    print(
        "fetch: "
        + json.dumps(
            {
                "fetch_threads": fetch_threads(),
                "concurrency_peak": reg.gauge(
                    "io.fetch.concurrency_peak"
                ).value(),
                "spans": reg.counter("io.fetch.spans").value(),
                "mb_fetched": round(
                    reg.counter("io.fetch.bytes").value() / 1e6, 2
                ),
                "reopens": reg.counter("io.fetch.reopens").value(),
                "span_wait_seconds": {
                    k: wait[k]
                    for k in ("count", "p50", "p90", "p99")
                    if k in wait
                },
            }
        )
    )


def _trace_arg():
    """``--trace <path>``: dump the flight recorder on exit (ISSUE 8)
    so the per-mode numbers above come WITH their timeline. A bare
    ``--trace`` (path forgotten, or followed by another flag) dumps to
    the recorder's default path instead of crashing."""
    if "--trace" not in sys.argv:
        return None
    i = sys.argv.index("--trace") + 1
    if i < len(sys.argv) and not sys.argv[i].startswith("--"):
        return sys.argv[i]
    return ""  # default path (tracing.default_trace_path)


def _dump_trace(path) -> None:
    if path is None:
        return
    from dmlc_core_tpu.telemetry import tracing

    out = tracing.dump(path or None)
    print(
        f"trace: {out} — the drains above as a Perfetto timeline "
        "(https://ui.perfetto.dev; stall attribution: "
        f"python -m dmlc_core_tpu.tools trace report {out})"
    )


def main():
    trace_path = _trace_arg()
    _fetch_threads_arg()
    _start_timeseries()
    if "--shuffle" in sys.argv:
        fault = ""
        if "--fault" in sys.argv:  # e.g. --fault resets=2,errors=1,seed=7
            fault = sys.argv[sys.argv.index("--fault") + 1]
        print(json.dumps(shuffle_read_modes(fault), indent=1))
        _print_fetch_summary()
        _print_telemetry()
        _dump_trace(trace_path)
        return
    if "--dynamic-shards" in sys.argv:
        fault = ""
        if "--fault" in sys.argv:  # e.g. --fault latency_ms=20,spikes=50
            fault = sys.argv[sys.argv.index("--fault") + 1]
        print(json.dumps(dynamic_shard_drain(fault), indent=1))
        _print_telemetry()
        _dump_trace(trace_path)
        return
    if "--dsserve" in sys.argv:
        fault = ""
        if "--fault" in sys.argv:  # e.g. --fault resets=2,seed=7
            fault = sys.argv[sys.argv.index("--fault") + 1]
        print(json.dumps(dsserve_drain(fault), indent=1))
        _print_telemetry()
        _dump_trace(trace_path)
        return
    import jax

    jax.local_devices()
    rng = np.random.default_rng(5)
    NB = 8060928
    bufs = [rng.integers(0, 255, NB, dtype=np.uint8) for _ in range(8)]
    big = rng.normal(size=1 << 20)  # ~8MB f64 for numpy work
    N = 20
    out = {}
    for r in range(2):
        out[f"none_{r}"] = put_loop(bufs, N)
        out[f"sleep5ms_{r}"] = put_loop(
            bufs, N, lambda: time.sleep(0.005)
        )
        out[f"pyspin5ms_{r}"] = put_loop(bufs, N, lambda: spin(0.005))
        out[f"numpy_copy_{r}"] = put_loop(
            bufs, N, lambda: numpy_work(big)
        )
        out[f"pyspin20ms_{r}"] = put_loop(bufs, N, lambda: spin(0.020))
    print(json.dumps(out, indent=1))
    _print_telemetry()
    _dump_trace(trace_path)


if __name__ == "__main__":
    main()
