"""End-to-end Criteo-style path: rowrec RecordIO → fused ELL staging →
jitted factorization machine, with checkpoint resume.

This is the RecordIO north-star pipeline (BASELINE.md): rows are stored
pre-parsed in reference-bit-compatible RecordIO frames (data/rowrec.py),
the fused native kernel scans frames straight into packed ELL batch
rings, and each batch rides one DMA into HBM.

Single host:   python examples/train_criteo_rec.py [/path/to/data.rec]
Multi-process: ./dmlc-submit --cluster local --num-workers 2 \
                   python examples/train_criteo_rec.py /path/to/data.rec

Under dmlc-submit with >1 worker this is TRUE multi-host SGD
(docs/collectives.md): each rank computes gradients over its own shard,
the per-step gradients are summed across ranks by the tracker-topology
collective engine (tracker/collective.py allreduce) together with a
contributor count (so uneven shard tails average over the ranks that
still have data), and every rank applies the identical shared update —
params stay bit-identical across ranks by construction. Fault
tolerance is rabit-style: the model is lazily checkpointed IN MEMORY
every SAVE_EVERY steps (``Collective.checkpoint``); a worker the
supervisor relaunches bootstraps params + position from a live peer
(``load_checkpoint``), replays the missed rounds through the
survivors' result caches, and rejoins the live round — final model
equal to a run with no kills (the chaos drill in tests/ pins this).

Env knobs (collective mode): DMLC_SGD_PATH=tree|ring pins the
allreduce path (default: size-based; pin ``tree`` when a chaos run
must be bit-identical to a clean one — faulted ring rounds retry over
the tree, whose float-sum fold order differs by rounding),
DMLC_SGD_OUT=<path> writes each rank's final params to
``<path>.rank<N>.npz`` (what the drill compares), DMLC_SGD_EPOCHS.

Generates a small synthetic shard when no path is given.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

N_FEATURES = 1 << 14
K = 39  # 13 dense + 26 categorical, Criteo-shaped


def synth(
    path: str, rows: int = 20000, n_features: int = N_FEATURES, seed: int = 0
) -> None:
    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.data.rowrec import write_rowrec
    from dmlc_core_tpu.io.stream import FileStream

    rng = np.random.default_rng(seed)
    idx = np.empty((rows, K), dtype=np.uint32)
    idx[:, :13] = np.arange(13)
    idx[:, 13:] = rng.integers(13, n_features, (rows, 26))
    val = np.ones((rows, K), dtype=np.float32)
    val[:, :13] = rng.uniform(0, 1, (rows, 13))
    w = rng.normal(size=n_features) / np.sqrt(K)
    logits = (w[idx] * val).sum(axis=1)
    labels = (logits > 0).astype(np.float32)
    blk = RowBlock(
        offset=np.arange(rows + 1, dtype=np.int64) * K,
        label=labels,
        index=idx.reshape(-1),
        value=val.reshape(-1),
    )
    # the sidecar index enables count-exact sharding + shuffled epochs
    # via `?index=<uri>&shuffle=1` (reference indexed_recordio semantics).
    # multi-worker launches race through synth: write to per-process temp
    # names, then atomically publish the index FIRST, so a worker that
    # sees the data file always sees a complete index (content is
    # deterministic, so concurrent publishers agree)
    tmp, itmp = f"{path}.tmp{os.getpid()}", f"{path}.idx.tmp{os.getpid()}"
    with FileStream(tmp, "w") as f, FileStream(itmp, "w") as fi:
        write_rowrec(f, [blk], index_stream=fi)
    os.replace(itmp, path + ".idx")
    os.replace(tmp, path)


def shard_sizes(n_total: int, world: int) -> list:
    """Per-rank record counts under the splitter's ceil-division count
    sharding (io/split.py reset_partition): when ``n_total % world !=
    0`` the tail ranks own fewer records, so one rank's consumed count
    is NOT a valid resume position for another."""
    nstep = -(-n_total // world)
    return [
        max(0, min((r + 1) * nstep, n_total) - min(r * nstep, n_total))
        for r in range(world)
    ]


def index_count(idx_path: str) -> int:
    with open(idx_path) as f:
        return sum(1 for line in f if line.strip())


def pack_state(params, gstep: int, epoch: int, consumed: int) -> bytes:
    """Serialize (params, data position) for the in-memory peer
    checkpoint (``Collective.checkpoint`` — rabit lazy_checkpoint): one
    npz blob a bootstrapping peer can adopt wholesale."""
    import io

    buf = io.BytesIO()
    np.savez(
        buf, gstep=gstep, epoch=epoch, consumed=consumed,
        **{"p_" + k: np.asarray(v) for k, v in params.items()},
    )
    return buf.getvalue()


def unpack_state(state: bytes):
    import io

    import jax.numpy as jnp

    z = np.load(io.BytesIO(state))
    params = {
        k[2:]: jnp.asarray(z[k]) for k in z.files if k.startswith("p_")
    }
    return params, int(z["gstep"]), int(z["epoch"]), int(z["consumed"])


def main() -> None:
    import jax

    from dmlc_core_tpu.checkpoint import Checkpointer
    from dmlc_core_tpu.models import FactorizationMachine, sgd_update
    from dmlc_core_tpu.staging import (
        BatchSpec,
        StagingPipeline,
        drain_close,
        ell_batches,
    )

    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/criteo_demo.rec"
    if not os.path.exists(path):
        print(f"generating synthetic rowrec shard at {path}")
        synth(path)

    # under dmlc-submit, join the tracker rendezvous like any dmlc
    # worker: the tracker assigns the rank we shard by (and relaunched
    # workers reclaim theirs); standalone runs shard by env/defaults
    worker = None
    if os.environ.get("DMLC_TRACKER_URI"):
        from dmlc_core_tpu.tracker.client import RabitWorker

        worker = RabitWorker()
        rank = worker.start()
        world = worker.world_size
    else:
        rank = int(os.environ.get("DMLC_TASK_ID", 0))
        world = int(os.environ.get("DMLC_NUM_WORKER", 1))
    model = FactorizationMachine(N_FEATURES, embed_dim=8)
    params = model.init(jax.random.PRNGKey(0))
    lr = 0.1
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=lr))

    # multi-worker under the tracker: true multi-host SGD — per-rank
    # gradients summed across ranks by the collective engine, one
    # shared update per step (docs/collectives.md)
    coll = None
    if worker is not None and world > 1:
        from dmlc_core_tpu.tracker.collective import Collective

        coll = Collective(worker)
        sgd_path = os.environ.get("DMLC_SGD_PATH") or None
        grad_fn = jax.jit(model.loss_and_grads)
        apply_fn = jax.jit(lambda p, g: sgd_update(p, g, lr))

    gstep, start_epoch, skip = 0, 0, 0
    ck = None
    if coll is None:
        # v2: steps are global BATCH counts with (epoch, records)
        # metadata — a fresh directory, so checkpoints from the older
        # epoch-numbered layout can't be misread as positions
        ck = Checkpointer(
            "/tmp/criteo_ckpts_v2", keep=2, process_index=rank
        )
        # resume: params + the DATA POSITION (epoch, records consumed)
        # the save recorded — a mid-epoch preemption fast-forwards into
        # the same shuffled epoch instead of replaying or skipping rows
        # (§5.4)
        start = ck.latest_step()
        if start is not None:
            gstep, params = ck.restore(start)
            pos = ck.restore_meta(start)
            if pos is not None:
                start_epoch = int(pos["epoch"])
                rec = pos["records"]
                # per-rank dict (current layout) or a bare count (older
                # checkpoints: rank 0's count — only exact when every
                # shard has the same size)
                if isinstance(rec, dict):
                    skip = int(rec.get(str(rank), 0))
                else:
                    skip = int(rec)
                print(
                    f"rank {rank}: resumed step {gstep} at epoch "
                    f"{start_epoch}, {skip} records in"
                )
            else:
                # no position recorded (crash before the sidecar
                # landed): conservative fallback — keep the params,
                # replay from epoch 0 rather than risk skipping data
                print(
                    f"rank {rank}: resumed step {gstep}; no data "
                    f"position recorded, replaying from epoch 0"
                )
    elif int(os.environ.get("DMLC_NUM_ATTEMPT", "0") or 0) > 0:
        # rabit-style relaunch: no disk restore — bootstrap params AND
        # the data position from a live peer's in-memory checkpoint,
        # then replay the missed rounds through the survivors' result
        # caches (the engine fast-forwarded its round clock to the
        # checkpoint). A fresh job (attempt 0) skips the ask: nobody
        # has state yet, and peers may not be pumping frames.
        version, state = coll.load_checkpoint()
        if state:
            params, gstep, start_epoch, skip = unpack_state(state)
            print(
                f"rank {rank}: bootstrapped from peer at version "
                f"{version} (step {gstep}, epoch {start_epoch}, "
                f"{skip} records in)"
            )

    B = 2048
    SAVE_EVERY = 4  # batches between mid-epoch position checkpoints
    spec = BatchSpec(batch_size=B, layout="ell", max_nnz=K)
    # DMLC_DYNAMIC_SHARDS=1: tracker-leased micro-shard placement
    # (docs/sharding.md) — a straggling host drains fewer shards
    # instead of gating the epoch. Needs the tracker rendezvous, and
    # resume-by-position is static-only (mid-epoch resume under
    # leasing is ledger-owned: completed micro-shards are simply not
    # re-served), so the skip fast-forward and the per-rank position
    # sidecars are skipped in this mode.
    dynamic = (
        os.environ.get("DMLC_DYNAMIC_SHARDS", "0") not in ("", "0")
        and worker is not None
    )
    # with a sidecar index, shards are count-exact and each epoch reads
    # in a fresh shuffled order (URI sugar → IndexedRecordIOSplitter);
    # without one, fall back to sequential byte-sharded reads
    has_index = os.path.exists(path + ".idx")
    sizes = shard_sizes(index_count(path + ".idx"), world) if has_index else []
    epochs = int(os.environ.get("DMLC_SGD_EPOCHS", "3"))
    for epoch in range(start_epoch, epochs):
        # shuffle=batch: permuted SPANS of batch_size records, one
        # coalesced seek per span — sequential-read throughput at
        # shuffle granularity batch_size (shuffle=1 would be the
        # reference's per-record-seek full permutation). The permutation
        # derives from (seed, epoch), so `epoch=`/`skip_records=` land a
        # resume on the exact record the crash interrupted.
        uri = (
            f"{path}?index={path}.idx&shuffle=batch&batch_size={B}"
            f"&seed=1&epoch={epoch}"
            + (
                "&dynamic_shards=1"
                if dynamic
                else (f"&skip_records={skip}" if skip else "")
            )
            if has_index
            else path
        )
        stream = ell_batches(uri, spec, part_index=rank, num_parts=world)
        pipe = StagingPipeline(stream)
        loss = None
        consumed, skip = skip, 0
        if coll is not None:
            # distributed step: allreduce [grads, have-data flag] as ONE
            # round; the flag sum says how many ranks contributed, so
            # uneven shard tails average over the ranks still holding
            # data and the epoch ends when the sum hits zero — every
            # rank agrees on both, because every rank sees the same
            # reduced vector. Ranks with exhausted shards keep calling
            # with zeros: allreduce is collective, a rank that stopped
            # calling would wedge the others.
            import jax.numpy as jnp
            from jax.flatten_util import ravel_pytree

            flat0, unravel = ravel_pytree(params)
            dim = flat0.size
            it = iter(pipe)
            while True:
                batch = next(it, None)
                if batch is not None:
                    loss, grads = grad_fn(params, batch)
                    vec = np.concatenate([
                        np.asarray(ravel_pytree(grads)[0], np.float32),
                        np.ones(1, np.float32),
                    ])
                else:
                    vec = np.zeros(dim + 1, np.float32)
                summed = coll.allreduce(vec, "sum", path=sgd_path)
                n_contrib = float(summed[-1])
                if n_contrib == 0:
                    break  # every rank drained its shard: epoch done
                params = apply_fn(
                    params, unravel(jnp.asarray(summed[:-1] / n_contrib))
                )
                gstep += 1
                if batch is not None:
                    consumed += int(
                        (np.asarray(batch["weights"]) > 0).sum()
                    )
                # in-memory peer checkpoint at span-aligned positions:
                # the replay window a relaunched peer needs is bounded
                # by SAVE_EVERY, which must stay <= DMLC_COLLECTIVE_CACHE
                if (
                    has_index and not dynamic
                    and gstep % SAVE_EVERY == 0 and consumed % B == 0
                ):
                    coll.checkpoint(
                        pack_state(params, gstep, epoch, consumed),
                        version=gstep,
                    )
        else:
            for batch in pipe:
                params, loss = step(params, batch)
                consumed += int((np.asarray(batch["weights"]) > 0).sum())
                gstep += 1
                # mid-epoch position checkpoint: only at span-aligned
                # positions (a padded tail batch is not resumable-into;
                # the epoch-end save right below covers it). Rank 0
                # writes the positions of EVERY rank, keyed by rank:
                # when ntotal % world != 0 the tail ranks' shards are
                # smaller, so rank 0's count clamped to each shard's
                # size is that rank's position (a B-multiple is never
                # strictly inside a smaller shard's tail span, and a
                # rank whose shard is already exhausted resumes at its
                # total = skip-everything).
                if (
                    has_index and not dynamic and gstep % SAVE_EVERY == 0
                    and consumed % B == 0
                ):
                    ck.save_async(
                        gstep, params,
                        meta={
                            "epoch": epoch,
                            "records": {
                                str(r): min(consumed, sizes[r])
                                for r in range(world)
                            },
                        },
                    )
        stats = pipe.throughput()
        loss_str = "n/a (empty shard)" if loss is None else f"{float(loss):.4f}"
        print(
            f"rank {rank} epoch {epoch}: loss={loss_str} "
            f"({stats['rows_per_sec']:,.0f} rows/s, "
            f"{stats['mb_per_sec']:,.0f} MB/s into device)"
        )
        # pipeline first, source second, honoring close_timed_out
        drain_close(pipe, stream)
        # epoch boundary: next resume starts the following epoch clean.
        if coll is not None:
            coll.checkpoint(
                pack_state(params, gstep, epoch + 1, 0), version=gstep
            )
        else:
            # async: the write overlaps the next epoch's training;
            # ck.save/restore/wait all drain it, and the final wait()
            # below surfaces any background write failure before we
            # declare success
            ck.save_async(
                gstep, params, meta={"epoch": epoch + 1, "records": 0}
            )
    if ck is not None:
        ck.wait()
        print("latest checkpoint step:", ck.latest_step())
    out = os.environ.get("DMLC_SGD_OUT")
    if out:
        # per-rank final params (atomic publish) — in collective mode
        # every rank's file holds the SAME bytes (the chaos drill pins
        # cross-rank AND kill-vs-clean equality on these)
        tmp = f"{out}.rank{rank}.npz.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(
                f, gstep=gstep,
                **{k: np.asarray(v) for k, v in params.items()},
            )
        os.replace(tmp, f"{out}.rank{rank}.npz")
    if coll is not None:
        coll.close()
    if worker is not None:
        worker.shutdown()


if __name__ == "__main__":
    from dmlc_core_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
