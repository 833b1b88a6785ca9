"""Factorization machine over ELL batches (the libfm-format consumer).

Second-order FM (Rendle 2010): score = w0 + Σ w_i x_i
+ ½ Σ_e [(Σ_i v_ie x_i)² - Σ_i v_ie² x_i²], computed with two embedding
gathers — the classic trick that keeps it O(B·K·E) with no D×D term. The
embedding table is the natural tensor-parallel shard target: split the E
axis over the mesh's 'model' axis (see parallel/ and __graft_entry__).

``sgd_step`` updates only the rows a batch touches when the loss has no
l2 term (see its docstring); with l2 every row has a gradient and the
step stays dense. ``w`` always takes XLA's scatter of every slot. ``v`` has
two writers (``_update_rows``): on a TPU a Pallas kernel writes the new
``v`` in one streaming pass, adding every slot's row gradient on the way
(``_add_rows``): the whole table on one chip, and on a mesh each device's
``P(None, 'model')`` shard under ``shard_map``. On other devices XLA
scatters every slot into it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.sparse import weighted_mean
from ..telemetry.registry import default_registry
from .common import bce_with_logits, sgd_update

__all__ = ["FactorizationMachine"]

Params = Dict[str, jax.Array]
Batch = Dict[str, jax.Array]


def _count_update_path(path: str) -> None:
    """One tick per trace of ``sgd_step``: which update it compiled to."""
    default_registry().counter(
        "models.fm.update_path",
        help="FM sgd_step traces by update path "
        "(sparse-kernel | sparse-kernel-sharded | sparse | "
        "sparse-replicated | dense)",
        labels={"path": path},
    ).inc()


# The kernel's table block: table rows (columns of the [E, N] view) that
# one grid step copies to its output and updates, at E = 16 (PERF.md §6).
KERNEL_COLS = 8192
# Below that E a block keeps the same bytes (512 KiB): a grid step costs
# ≈0.44 µs on a v5e whatever its width, so at E = 8 (the 2x2 mesh's v
# shard) 16384 rows a block save ≈1.8 of ≈12.4 ms a step (PERF.md §6).
_BLOCK_E = 16
_LANES = 128
# Sorted ids and their slots come into SMEM (1 MiB) this many at a time.
_ID_CHUNK = 1024
# Slots whose rows the kernel loads and rolls side by side.
_UNROLL = 4
# The [E, S] row gradients stay whole in VMEM (128 MiB on a v5e).
_ROWS_VMEM_BYTES = 32 << 20
# The mesh axis that splits v's E axis: v is placed on P(None, _V_AXIS).
_V_AXIS = "model"


def _kernel_fits(platform: str, embed_dim: int, slots: int) -> bool:
    """A TPU, a table that XLA lays out E-minor-major (E < 128, so its
    [E, N] view is a bitcast) and [E, S] row gradients that fit VMEM."""
    padded = -(-slots // _LANES) * _LANES
    return (platform == "tpu" and embed_dim < _LANES
            and padded * embed_dim * 4 <= _ROWS_VMEM_BYTES)


def _update_path(platform: str, mesh, l2: float, embed_dim: int,
                 slots: int) -> str:
    """Which update ``sgd_step`` compiles to. The kernel needs no l2 and
    ``_kernel_fits`` for the table one device writes: the whole table
    with no mesh, its E / |model| shard on a mesh with a ``model`` axis
    (``v`` placed on ``P(None, 'model')``)."""
    if l2:
        return "dense"
    if mesh.empty:
        if _kernel_fits(platform, embed_dim, slots):
            return "sparse-kernel"
        return "sparse"
    parts = mesh.shape.get(_V_AXIS)
    if (parts and embed_dim % parts == 0
            and _kernel_fits(platform, embed_dim // parts, slots)):
        return "sparse-kernel-sharded"
    return "sparse-replicated"


# -- the sorted-slot machinery both row-update kernels share ------------------
# (this one's FM add and ``ffm.py``'s AdaGrad): the ids sorted with their
# slots, each table block's bounds in the sorted slots, and the sorted
# ids (with what rides beside them) brought into the kernel in chunks.


def _sort_slots(ids):
    """(sorted ids, the slot each came from)."""
    return jax.lax.sort((ids, jax.lax.iota(jnp.int32, ids.shape[0])),
                        num_keys=1)


def _block_starts(sid, cols: int, blocks: int):
    """Where each block of ``cols`` table rows starts in the sorted ids,
    and where the last one ends: ids < 0 fall before block 0 and ids
    past the last block after it, so no block visits them."""
    return jnp.searchsorted(
        sid, cols * jax.lax.iota(jnp.int32, blocks + 1)
    ).astype(jnp.int32)


def _chunk_pad(n: int) -> int:
    """Padding that makes ``n`` sorted slots whole chunks."""
    return -(-n // _ID_CHUNK) * _ID_CHUNK - n


def _visit_sorted_slots(lo, hi, loaded, copies, visit) -> None:
    """Visit sorted slots [lo, hi) of a table block a chunk at a time.
    ``copies(first)`` gives the async copies that bring the chunk from
    sorted slot ``first`` in (skipped when it is in already: consecutive
    blocks share a chunk; ``loaded[0]`` holds the chunk that is in, -1
    before any); ``visit(a, b)`` gets the block's slots in the chunk as
    offsets [a, b) into it."""

    def chunk(c, _):
        first = c * _ID_CHUNK

        @pl.when(loaded[0] != c)
        def _():
            dmas = copies(first)
            for d in dmas:
                d.start()
            for d in dmas:
                d.wait()
            loaded[0] = c

        visit(jnp.maximum(lo, first) - first,
              jnp.minimum(hi, first + _ID_CHUNK) - first)

    last = jnp.where(hi > lo, (hi - 1) // _ID_CHUNK + 1, lo // _ID_CHUNK)
    jax.lax.fori_loop(lo // _ID_CHUNK, last, chunk, None)


def _add_rows_kernel(starts_ref, sid_hbm, perm_hbm, rows_ref, table_ref,
                     out_ref, sid_buf, perm_buf, loaded, sem):
    """One block of table rows: copy it, then add the row of every slot
    whose sorted id falls in it (``starts`` bounds the block's sorted
    slots), one lane of a 128-row tile at a time."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        loaded[0] = -1

    out_ref[...] = table_ref[...]
    lo, hi = starts_ref[i], starts_ref[i + 1]
    base = i * out_ref.shape[1]
    lane_of = jax.lax.broadcasted_iota(
        jnp.int32, (out_ref.shape[0], _LANES), 1)

    def row(j):
        """Slot j's tile offset in the block, and its row on its lane."""
        # both are ≥ 0, so masks stand for % and // (a signed % costs a
        # dozen scalar ops a slot)
        s = sid_buf[j] - base
        p = perm_buf[j]
        lane = s & (_LANES - 1)
        # the slot's row is column p of the gradients: roll its tile so
        # that the column lands on the lane of row s in the table's tile
        g = rows_ref[:, pl.ds(pl.multiple_of(p & -_LANES, _LANES), _LANES)]
        g = pltpu.roll(g, (lane - p) & (_LANES - 1), 1)
        return (pl.multiple_of(s & -_LANES, _LANES),
                jnp.where(lane_of == lane, g, 0.0))

    def add(rows):
        for at, g in rows:
            out_ref[:, pl.ds(at, _LANES)] += g

    def copies(first):
        return (pltpu.make_async_copy(sid_hbm.at[pl.ds(first, _ID_CHUNK)],
                                      sid_buf, sem.at[0]),
                pltpu.make_async_copy(perm_hbm.at[pl.ds(first, _ID_CHUNK)],
                                      perm_buf, sem.at[1]))

    def visit(a, b):
        groups = (b - a) // _UNROLL
        # a group's rows are loaded and rolled side by side before the
        # adds, which stay in order (two may hit one tile)
        jax.lax.fori_loop(0, groups, lambda t, _: add(
            [row(a + t * _UNROLL + u) for u in range(_UNROLL)]), None)
        jax.lax.fori_loop(a + groups * _UNROLL, b,
                          lambda j, _: add([row(j)]), None)

    _visit_sorted_slots(lo, hi, loaded, copies, visit)


def _add_rows(table_t, sid, perm, rows_t):
    """``table_t`` [E, N] with column ``perm[k]`` of ``rows_t`` [E, S]
    added at column ``sid[k]`` for every k (``sid`` ascending), in one
    streaming pass that writes the whole new table; equal ids add one
    after another. Ids outside [0, N) are dropped."""
    e, n = table_t.shape
    s = sid.shape[0]
    cols = KERNEL_COLS * max(1, _BLOCK_E // e)
    rows_t = jnp.pad(rows_t, ((0, 0), (0, -(-s // _LANES) * _LANES - s)))
    pad = _chunk_pad(s)
    blocks = pl.cdiv(n, cols)
    starts = _block_starts(sid, cols, blocks)
    block = pl.BlockSpec((e, cols), lambda i, _starts: (0, i))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _add_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(table_t.shape, table_t.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[hbm, hbm,
                      pl.BlockSpec(rows_t.shape, lambda i, _starts: (0, 0),
                                   pipeline_mode=pl.Buffered(1)),
                      block],
            out_specs=block,
            scratch_shapes=[pltpu.SMEM((_ID_CHUNK,), jnp.int32),
                            pltpu.SMEM((_ID_CHUNK,), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the gradients, the double-buffered in and out blocks, slack
            vmem_limit_bytes=rows_t.size * 4 + 16 * e * cols
            + (4 << 20),
        ),
        name="fm_add_rows",
    )(starts, jnp.pad(sid, (0, pad)), jnp.pad(perm, (0, pad)), rows_t,
      table_t)


def _update_rows(w, v, ids, gw, gv, lr: float, kernel: bool, mesh=None):
    """``w`` and ``v`` with −lr·(row gradient) added at every slot's id
    (equal ids sum, as the dense gradient sums them). XLA scatters every
    slot into ``w`` (a scalar row costs 9–13 ns on a v5e). With
    ``kernel`` the kernel writes the new ``v`` through the table's free
    [E, N] view, over the ids sorted with their slots: on one TPU chip
    the whole table; with a non-empty ``mesh`` each device its own
    ``P(None, 'model')`` shard under ``shard_map``, from the ids and row
    gradients the caller replicated over the mesh, so no collective
    moves them (the trace cannot see ``v``'s placement over the mesh's
    automatic axes: ``v`` placed otherwise is resharded by the
    partitioner, and stays correct). Otherwise XLA scatters every slot
    into ``v`` too."""
    if not kernel:
        return w.at[ids].add(-lr * gw), v.at[ids].add(-lr * gv)
    sid, perm = _sort_slots(ids)

    def add(v, sid, perm, gv):
        return _add_rows(v.T, sid, perm, (-lr * gv).T).T

    if mesh is not None and not mesh.empty:
        from jax.sharding import PartitionSpec as P

        shard = P(None, _V_AXIS)
        # the kernel's output type carries no mesh axes to check; each
        # shard is the same on every device of the data axis
        add = jax.shard_map(add, mesh=mesh, in_specs=(shard, P(), P(), shard),
                            out_specs=shard, check_vma=False)
    return w.at[ids].add(-lr * gw), add(v, sid, perm, gv)


def _done_on_every_device(params: Params, loss: jax.Array, mesh):
    """Return ``loss`` only once every device of ``mesh`` holds ``params``.

    After the all-gather no collective follows the update, so the
    devices finish the step at different times, while a caller waiting
    on the replicated loss reads it from one device. A caller that runs
    ahead (two steps in flight, no donation) then allocates the next
    step's tables while a slower device still holds this step's input:
    one table copy more on that device (12.1 against 9.7 GB a device on
    the 2x2 v5e mesh at 2^26 x 16). The barrier orders the loss after
    the updated tables, and the max over the whole mesh (exact: every
    device holds the same loss) makes it wait for the slowest device.
    """
    from jax.sharding import PartitionSpec as P

    params, loss = jax.lax.optimization_barrier((params, loss))
    loss = jax.shard_map(
        lambda x: jax.lax.pmax(x, mesh.axis_names),
        mesh=mesh, in_specs=P(), out_specs=P(),
    )(loss)
    return params, loss


def _scores(w_rows, v_rows, b, values, k_axis: int = 1) -> jax.Array:
    """FM scores from the gathered rows: w_rows and values [B, K], v_rows
    [B, K, E] (``k_axis=0``: [K, B] and [K, B, E])."""
    linear = jnp.sum(values * w_rows, axis=k_axis)
    xv = v_rows * values[..., None]
    sum_sq = jnp.sum(xv, axis=k_axis) ** 2  # [B, E]
    sq_sum = jnp.sum(xv**2, axis=k_axis)  # [B, E]
    pair = 0.5 * jnp.sum(sum_sq - sq_sum, axis=-1)  # [B]
    return linear + pair + b


def _rows_loss(rows: Params, batch: Batch, k_axis: int = 1) -> jax.Array:
    scores = _scores(rows["w"], rows["v"], rows["b"], batch["values"], k_axis)
    per_row = bce_with_logits(scores, batch["labels"])
    return weighted_mean(per_row, batch["weights"])


class FactorizationMachine:
    def __init__(
        self, num_features: int, embed_dim: int = 16, l2: float = 0.0
    ) -> None:
        self.num_features = num_features
        self.embed_dim = embed_dim
        self.l2 = l2

    def init(self, rng: jax.Array) -> Params:
        wkey, vkey = jax.random.split(rng)
        return {
            "w": jax.random.normal(wkey, (self.num_features,), jnp.float32)
            * 0.01,
            "v": jax.random.normal(
                vkey, (self.num_features, self.embed_dim), jnp.float32
            )
            * 0.01,
            "b": jnp.zeros((), jnp.float32),
        }

    @staticmethod
    def _rows(params: Params, idx: jax.Array) -> Params:
        """The rows ``idx`` gathers: w [*idx], v [*idx, E], and the bias."""
        return {
            "w": jnp.take(params["w"], idx, axis=0),
            "v": jnp.take(params["v"], idx, axis=0),
            "b": params["b"],
        }

    def forward(self, params: Params, batch: Batch) -> jax.Array:
        rows = self._rows(params, batch["indices"])
        return _scores(rows["w"], rows["v"], rows["b"], batch["values"])

    def loss(self, params: Params, batch: Batch) -> jax.Array:
        data_loss = _rows_loss(self._rows(params, batch["indices"]), batch)
        if self.l2:
            data_loss = data_loss + self.l2 * (
                jnp.sum(params["w"] ** 2) + jnp.sum(params["v"] ** 2)
            )
        return data_loss

    def loss_and_grads(
        self, params: Params, batch: Batch
    ) -> Tuple[jax.Array, Params]:
        """(loss, grads) without the update — see
        ``linear._LinearBase.loss_and_grads``: the half step a
        multi-host SGD loop allreduces before one shared update. The
        gradients are dense (table-shaped) whatever ``l2`` is."""
        return jax.value_and_grad(self.loss)(params, batch)

    def sgd_step(
        self, params: Params, batch: Batch, lr: float = 0.05
    ) -> Tuple[Params, jax.Array]:
        """One SGD step: p − lr·∇p.

        With ``l2 == 0`` only the rows the batch gathers have a gradient,
        so the step differentiates with respect to the gathered rows and
        adds −lr·(row gradient) into ``w`` and ``v`` at the batch's ids
        (duplicate ids sum, as the dense gradient sums them): no
        table-sized gradient is built, zeroed or subtracted. XLA
        scatters every slot into ``w``; ``v`` is written by a kernel on
        a TPU (``_add_rows``: on a mesh each device writes its
        ``P(None, 'model')`` shard) and by XLA's scatter of every slot
        elsewhere (``_update_rows``). Traced
        under a mesh (``parallel.data_parallel_step`` sets it), the ids
        and row gradients are first replicated over the mesh, so the
        partitioner all-gathers the rows instead of all-reducing a
        table-sized gradient over the data axis, and the loss is
        returned through a max over the mesh taken after the update (see
        ``_done_on_every_device``). With ``l2 > 0`` every row has a
        gradient and the step is the dense one.
        """
        mesh = jax.sharding.get_abstract_mesh()
        path = _update_path(jax.default_backend(), mesh, self.l2,
                            self.embed_dim, batch["indices"].size)
        _count_update_path(path)
        if path == "dense":
            loss_val, grads = self.loss_and_grads(params, batch)
            return sgd_update(params, grads, lr), loss_val
        # K-major rows ([K, B, E]): the row gradient's minor dimension is
        # then the batch, which the TPU tiles unpadded; B-major puts K
        # minor and pads it to 128 lanes (at the Criteo shape, K = 39, a
        # 67 MB [B, K, E] temp in HBM instead of 21 MB)
        idx = batch["indices"].T
        loss_val, g = jax.value_and_grad(_rows_loss)(
            self._rows(params, idx), dict(batch, values=batch["values"].T), 0
        )
        ids = idx.reshape(-1)
        gw = g["w"].reshape(-1)
        gv = g["v"].reshape(-1, self.embed_dim)
        if not mesh.empty:
            ids, gw, gv = jax.lax.with_sharding_constraint(
                (ids, gw, gv), jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()
                ),
            )
        w, v = _update_rows(params["w"], params["v"], ids, gw, gv, lr,
                            kernel=path.startswith("sparse-kernel"),
                            mesh=mesh)
        new = {"w": w, "v": v, "b": params["b"] - lr * g["b"]}
        if not mesh.empty:
            new, loss_val = _done_on_every_device(new, loss_val, mesh)
        return new, loss_val
