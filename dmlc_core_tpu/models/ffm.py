"""Field-aware factorization machine (libffm) over ELL batches with field ids.

Juan, Zhuang, Chin and Lin, "Field-aware Factorization Machines for CTR
Prediction", RecSys 2016; libffm (github.com/ycjuan/libffm). Each slot a
of a row holds feature j_a of field f_a with value x_a (``indices``,
``fields`` and ``values`` of a batch staged with ``BatchSpec.fields``);
feature j keeps one k-vector per field, W[j, f]: row j of the
[num_features, width] table holds W[j, 0], W[j, 1], ... in its first
num_fields · k columns. ``width`` rounds that up to whole 128-lane tiles
(156 → 256 at libffm's k = 4 on Criteo's 39 fields): a v5e lays an
[N, 156] table out E-major ([156, N] tiles), where a row is 156 scattered
words, while an [N, 256] table keeps each row in two lane tiles, so the
gather reads rows whole and the update kernel adds a slot's gradient row
with one row load and store (PERF.md §6 measured both). The padding
columns carry no weight: they start at 0 (G at 1), take no gradient and
never change.

- Score: φ = r Σ_{a<c} ⟨W[j_a, f_c], W[j_c, f_a]⟩ x_a x_c with
  r = 1 / Σ_a x_a² (libffm's instance-wise normalization); no linear
  term, no bias.
- Objective per row: logloss(y, φ) + (λ/2) Σ_{a<c} (‖W[j_a, f_c]‖² +
  ‖W[j_c, f_a]‖²).
- AdaGrad per coordinate: G += g², W −= η g / √G, with G from 1 and W
  from U(0, 1) / √k (libffm's ``init_model``).

The pair sum takes no K² slot pairs: with S[f, g] = Σ_{a: f_a = g} x_a
W[j_a, f] (each slot's k-vectors summed by the slot's field),
Σ_{a≠c} ⟨x_a W[j_a, f_c], x_c W[j_c, f_a]⟩ = Σ_{f,g} ⟨S[f, g], S[g, f]⟩
− Σ_a x_a² ‖W[j_a, f_a]‖², so φ is r/2 times that. The slot → field
selection is a one-hot product over the staged ``fields`` (no slot is
assumed to be its field), computed elementwise on the VPU (no matmul, so
no bfloat16 rounding). Every intermediate keeps the batch minor: the
TPU pads a minor dimension to 128 lanes, which k = 4 or K = 39 would
blow up 32× or 3.3×.

Departures from libffm (also in the benchmark's ``config.json``):

- minibatch updates: one AdaGrad update a step from the gradient summed
  over the batch's weighted rows, where libffm updates W after every
  pair of every instance, one instance after another;
- AdaGrad on the batch-summed gradient: a feature in several slots of a
  batch squares the sum of their gradients once (the gradient of the
  batch's objective), not each slot's;
- no GBDT-leaf fields: the Criteo winners also fed gradient-boosted tree
  leaves as features; here only the 39 raw fields.

Slots past a row's ``nnz``, and slots whose field or id lies outside the
model, take no part (libffm skips such tokens).

The update (``_adagrad_rows``): on a TPU one streaming Pallas pass
(``ffm_adagrad_rows``) writes the new W and G: the slots' row gradients,
sorted by id as ``fm._add_rows`` sorts them, come through VMEM a chunk at
a time and are summed into each table block's accumulator, then G' = G +
acc² and W' = W − η acc / √G' over the block (an untouched row has acc =
0 and keeps its values exactly). Elsewhere XLA scatters the rows into a
table-sized accumulator and applies the same elementwise update.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.registry import default_registry
from . import fm
from .common import bce_with_logits

__all__ = ["FieldAwareFM"]

Params = Dict[str, jax.Array]
Batch = Dict[str, jax.Array]

# Table rows a grid step of the kernel updates (W and G blocks of
# [ROWS, width], double-buffered in and out). At the Criteo cell's
# shapes on a v5e, 512, 1024 and 2048 rows took 14.55, 14.69 and 15.31 ms
# with the sort (PERF.md §6).
KERNEL_ROWS = 512
# The kernel's VMEM budget (128 MiB on a v5e): blocks, accumulator and
# one chunk of row gradients.
_VMEM_BYTES = 64 << 20


def _count_update_path(path: str) -> None:
    """One tick per trace of ``adagrad_step``: which update it compiled to."""
    default_registry().counter(
        "models.ffm.update_path",
        help="FFM adagrad_step traces by update path (sparse-kernel | sparse)",
        labels={"path": path},
    ).inc()


def _kernel_vmem(width: int) -> int:
    """VMEM the kernel asks for: W and G blocks in and out, double
    buffered, the accumulator and a chunk of gradient rows, and slack."""
    return (9 * KERNEL_ROWS + fm._ID_CHUNK) * width * 4 + (4 << 20)


def _update_path(platform: str, mesh, width: int) -> str:
    """The kernel on one TPU chip whose VMEM holds its blocks; XLA's
    scatter into an accumulator otherwise."""
    if (platform == "tpu" and mesh.empty
            and _kernel_vmem(width) <= _VMEM_BYTES):
        return "sparse-kernel"
    return "sparse"


def _adagrad_rows_kernel(starts_ref, sid_hbm, grad_hbm, w_ref, g_ref,
                         w_out, g_out, sid_buf, grad_buf, acc, loaded, sem,
                         *, lr: float):
    """One block of table rows: sum the row gradient of every sorted slot
    whose id falls in it into ``acc``, then the AdaGrad update of the
    block's W and G."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        loaded[0] = -1

    acc[...] = jnp.zeros(acc.shape, acc.dtype)
    base = i * acc.shape[0]

    def copies(first):
        return (pltpu.make_async_copy(
                    sid_hbm.at[pl.ds(first, fm._ID_CHUNK)], sid_buf,
                    sem.at[0]),
                pltpu.make_async_copy(
                    grad_hbm.at[pl.ds(first, fm._ID_CHUNK)], grad_buf,
                    sem.at[1]))

    def add(j, _):
        s = sid_buf[j] - base
        acc[pl.ds(s, 1), :] += grad_buf[pl.ds(j, 1), :]

    fm._visit_sorted_slots(
        starts_ref[i], starts_ref[i + 1], loaded, copies,
        lambda a, b: jax.lax.fori_loop(a, b, add, None))
    g = g_ref[...] + acc[...] * acc[...]
    g_out[...] = g
    w_out[...] = w_ref[...] - lr * acc[...] / jnp.sqrt(g)


def _adagrad_kernel(w, g, sid, rows, lr: float):
    """(W', G') of the [N, E] tables (E whole 128-lane tiles) from
    ``rows`` [S, E], the row
    gradients in the order of their ascending ids ``sid``; rows of equal
    ids sum before the update. Ids outside [0, N) are dropped."""
    n, e = w.shape
    s = sid.shape[0]
    pad = fm._chunk_pad(s)
    blocks = pl.cdiv(n, KERNEL_ROWS)
    starts = fm._block_starts(sid, KERNEL_ROWS, blocks)
    block = pl.BlockSpec((KERNEL_ROWS, e), lambda i, _starts: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda *refs: _adagrad_rows_kernel(*refs, lr=lr),
        out_shape=(jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(g.shape, g.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[hbm, hbm, block, block],
            out_specs=(block, block),
            scratch_shapes=[pltpu.SMEM((fm._ID_CHUNK,), jnp.int32),
                            pltpu.VMEM((fm._ID_CHUNK, e), jnp.float32),
                            pltpu.VMEM((KERNEL_ROWS, e), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_kernel_vmem(e),
        ),
        name="ffm_adagrad_rows",
    )(starts, jnp.pad(sid, (0, pad)), jnp.pad(rows, ((0, pad), (0, 0))), w, g)


def _adagrad_rows(w, g, ids, rows, lr: float, kernel: bool):
    """AdaGrad on the rows of ``ids``: each id's row gradients (``rows``
    [S, E], one a slot) summed into acc, G' = G + acc², W' = W − lr ·
    acc / √G'. With ``kernel`` the streaming kernel writes both tables
    over the ids sorted with their slots; otherwise XLA scatters every
    slot into a table-sized accumulator."""
    if kernel:
        sid, perm = fm._sort_slots(ids)
        return _adagrad_kernel(w, g, sid, jnp.take(rows, perm, axis=0), lr)
    acc = jnp.zeros_like(w).at[ids].add(rows, mode="drop")
    g = g + acc * acc
    return w - lr * acc / jnp.sqrt(g), g


class FieldAwareFM:
    """libffm's model: ``num_fields`` fields, ``k`` latent factors a
    field, l2 ``lam`` on every pair's two vectors (module docstring)."""

    def __init__(self, num_features: int, num_fields: int, k: int = 4,
                 lam: float = 2e-5) -> None:
        self.num_features = num_features
        self.num_fields = num_fields
        self.k = k
        self.lam = lam

    @property
    def width(self) -> int:
        """Table columns: num_fields · k rounded up to whole lane tiles."""
        return -(-self.num_fields * self.k // fm._LANES) * fm._LANES

    def init(self, rng: jax.Array) -> Params:
        """W from U(0, 1) / √k, G from 1 (libffm's ``init_model``); W's
        padding columns 0."""
        n, e = self.num_features, self.num_fields * self.k
        w = jax.random.uniform(rng, (n, e), jnp.float32) * (
            1.0 / math.sqrt(self.k))
        return {"W": jnp.pad(w, ((0, 0), (0, self.width - e))),
                "G": jnp.ones((n, self.width), jnp.float32)}

    def _slots(self, batch: Batch):
        """K-major ([K, B]) ids, field one-hots [m, K, B] and values, with
        the slots that take no part zeroed."""
        ids = batch["indices"].T
        fields = batch["fields"].T
        kk = ids.shape[0]
        valid = ((jax.lax.broadcasted_iota(jnp.int32, ids.shape, 0)
                  < batch["nnz"][None, :])
                 & (fields >= 0) & (fields < self.num_fields)
                 & (ids >= 0) & (ids < self.num_features))
        onehot = ((fields[None] == jax.lax.broadcasted_iota(
            jnp.int32, (self.num_fields, kk, 1), 0)) & valid[None])
        values = jnp.where(valid, batch["values"].T.astype(jnp.float32), 0.0)
        return ids, onehot.astype(jnp.float32), values

    def _objective(self, rows, onehot, values, batch: Batch) -> jax.Array:
        """Σ_b weight_b · (logloss_b + l2_b) over the gathered W rows
        (``rows`` [K·B, width], K-major)."""
        m, k = self.num_fields, self.k
        kk, b = values.shape
        # R[f, d, a, b] = W[j_a, f, d]: the batch minor from here on
        r = rows[:, : m * k].reshape(kk, b, m * k).transpose(2, 0, 1).reshape(
            m, k, kk, b)
        a = r * values
        # S[f, d, g, b] = Σ_{a: f_a = g} x_a W[j_a, f, d]
        s = jnp.sum(a[:, :, None] * onehot[None, None], axis=3)
        # x_a W[j_a, f_a, d], the a = c terms the pair sum leaves out
        own = jnp.sum(a * onehot[:, None], axis=0)
        pairs = 0.5 * (jnp.sum(s * s.transpose(2, 1, 0, 3), axis=(0, 1, 2))
                       - jnp.sum(own * own, axis=(0, 1)))
        sq = jnp.sum(values * values, axis=0)
        score = pairs / jnp.where(sq > 0, sq, 1.0)
        # slot a's vector for field f meets the other slots of field f
        others = jnp.sum(onehot, axis=1)[:, None] - onehot
        present = jnp.sum(onehot, axis=0)
        l2 = 0.5 * self.lam * jnp.sum(
            others * present * jnp.sum(r * r, axis=1), axis=(0, 1))
        per_row = bce_with_logits(score, batch["labels"]) + l2
        return jnp.sum(per_row * batch["weights"])

    def adagrad_step(self, params: Params, batch: Batch,
                     lr: float = 0.2) -> Tuple[Params, jax.Array]:
        """One AdaGrad step (learning rate ``lr``, libffm's η) on the
        gradient of the batch's weighted objective, summed over its rows,
        taken with respect to the gathered rows only: no table-sized
        gradient is built. Returns the new params and the weighted mean
        of the objective before the step."""
        path = _update_path(jax.default_backend(),
                            jax.sharding.get_abstract_mesh(), self.width)
        _count_update_path(path)
        ids, onehot, values = self._slots(batch)
        flat = ids.reshape(-1)
        rows = jnp.take(params["W"], flat, axis=0, mode="clip")
        total, grads = jax.value_and_grad(self._objective)(
            rows, onehot, values, batch)
        w, g = _adagrad_rows(params["W"], params["G"], flat, grads, lr,
                             kernel=path == "sparse-kernel")
        loss = total / jnp.maximum(jnp.sum(batch["weights"]), 1e-9)
        return {"W": w, "G": g}, loss
