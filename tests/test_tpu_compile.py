"""The main path's programs compile for a described TPU v5e.

Nothing runs: each program is lowered with ShapeDtypeStructs placed on
devices of a described ``v5e:2x2`` topology and compiled by the TPU
compiler installed here. A compile that passes is not a chip run; it
catches what the chip's compiler would refuse (shapes, memory, sharding)
at no chip time. The topology is described only inside the ``topo``
fixture (never at import): only one process may load libtpu, and every
xdist worker imports this file.

Sizes are the ``criteo_fm`` benchmark configuration's: Criteo layout
K=39, batch 8192, f16 values, int32 indices (what ``BatchSpec`` stages),
and an FM of 2^24 features x E=16.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dmlc_core_tpu.models import FactorizationMachine
from dmlc_core_tpu.staging.batcher import (
    Batch,
    alloc_packed_slot,
    packed_shard_layout,
)
from dmlc_core_tpu.staging.pipeline import (
    _packed_layout,
    _shard_unpacker,
    _unpacker,
)

B, K = 8192, 39
N_FEATURES, EMBED = 1 << 24, 16
HBM_BYTES = 16 * 10**9  # one v5e chip
SECTIONS = [
    ("indices", (B, K), np.int32),
    ("values", (B, K), np.float16),
    ("nnz", (B,), np.int32),
    ("labels", (B,), np.float32),
    ("weights", (B,), np.float32),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _criteo_batch_structs(sharding_for):
    return {
        name: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_for(shape))
        for name, shape, dtype in SECTIONS
    }


def _fm_structs(model, sharding_for):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return {
        name: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding_for(name))
        for name, s in shapes.items()
    }


def test_unpacker_compiles_for_one_chip(one_chip, record_property):
    buf, views = alloc_packed_slot(SECTIONS)
    layout = _packed_layout(Batch(n_valid=B, packed=buf, **views))
    assert layout is not None
    u8 = jax.ShapeDtypeStruct(buf.shape, jnp.uint8, sharding=one_chip)
    mem = _unpacker(layout, "tpu").lower(u8).compile().memory_analysis()
    # recorded, not bounded yet: the u8 reshape(-1, itemsize) pads its
    # 1-4 byte minor dim to 128 lanes (PERF.md, Open questions)
    record_property("unpacker_packed_bytes", int(buf.nbytes))
    record_property("unpacker_temp_bytes", int(mem.temp_size_in_bytes))
    print(f"_unpacker B={B} K={K}: packed {buf.nbytes} B, "
          f"temp {mem.temp_size_in_bytes} B")


def _ops(text, op):
    """(dtype, dims) of every ``op`` (or its async ``-start``) result."""
    return [
        (dt, tuple(int(x) for x in dims.split(",") if x))
        for dt, dims in re.findall(
            r"= (\w+)\[([\d,]*)\]\S* " + op + r"(?:-start)?\(", text)
    ]


def _on_tpu(mp):
    """Steer the FM step to the update it takes on a TPU (one chip or a
    mesh of them): the process's backend is the CPU here."""
    from dmlc_core_tpu.models import fm

    rule = fm._update_path
    mp.setattr(fm, "_update_path", lambda _platform, *a: rule("tpu", *a))


@pytest.fixture(scope="module")
def fm_one_chip(one_chip):
    model = FactorizationMachine(N_FEATURES, embed_dim=EMBED)
    params = _fm_structs(model, lambda _name: one_chip)
    batch = _criteo_batch_structs(lambda _shape: one_chip)
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.1))
    with pytest.MonkeyPatch.context() as mp:
        _on_tpu(mp)
        return step.lower(params, batch).compile()


def test_fm_sgd_step_fits_one_chip(fm_one_chip, record_property):
    mem = fm_one_chip.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    record_property("fm_step_bytes", int(total))
    record_property("fm_step_temp_bytes", int(mem.temp_size_in_bytes))
    print(f"FM sgd_step 2^24 x {EMBED}: argument "
          f"{mem.argument_size_in_bytes} output {mem.output_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} B")
    assert total < HBM_BYTES, mem


def test_fm_sgd_step_builds_no_table_gradient(fm_one_chip):
    # the sparse update scatters row gradients into the table: no zeroed
    # table-sized gradient (a broadcast of the table's shape) is built
    text = fm_one_chip.as_text()
    assert ("f32", (N_FEATURES, EMBED)) not in _ops(text, "broadcast")
    assert ("f32", (N_FEATURES,)) not in _ops(text, "broadcast")


def _kernel_calls(text):
    """Result (dtype, dims) of every Pallas kernel (``tpu_custom_call``)."""
    return [
        (dt, tuple(int(x) for x in dims.split(",") if x))
        for dt, dims, target in re.findall(
            r"= (\w+)\[([\d,]*)\]\S* custom-call\(.*?"
            r"custom_call_target=\"(\w+)\"", text)
        if target == "tpu_custom_call"
    ]


def _table_copies(text, n):
    """copy/transpose ops whose result is the [N, E] table or its view."""
    tables = {("f32", (n, EMBED)), ("f32", (EMBED, n))}
    return [r for op in ("copy", "transpose") for r in _ops(text, op)
            if r in tables]


def test_fm_sgd_step_writes_v_in_the_kernel(fm_one_chip):
    # one TPU chip: the kernel writes the new v, and XLA copies no table
    text = fm_one_chip.as_text()
    assert _kernel_calls(text) == [("f32", (EMBED, N_FEATURES))]
    assert not _table_copies(text, N_FEATURES)


@pytest.mark.parametrize("n", [N_FEATURES, 33_762_590],
                         ids=["seq_2e24", "zipf_kaggle"])
def test_fm_kernel_update_compiles(one_chip, n, record_property):
    from dmlc_core_tpu.models import fm

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = B * K
    update = jax.jit(lambda *a: fm._update_rows(*a, lr=0.1, kernel=True))
    compiled = update.lower(
        struct((n,), jnp.float32), struct((n, EMBED), jnp.float32),
        struct((s,), jnp.int32), struct((s,), jnp.float32),
        struct((s, EMBED), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert _kernel_calls(text) == [("f32", (EMBED, n))]
    assert not _table_copies(text, n)
    mem = compiled.memory_analysis()
    record_property(f"fm_kernel_update_temp_bytes_{n}",
                    int(mem.temp_size_in_bytes))
    print(f"FM kernel update {n} x {EMBED}: temp "
          f"{mem.temp_size_in_bytes} B")


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from dmlc_core_tpu.parallel import make_mesh

    return make_mesh((2, 2), ("data", "model"), devices=topo.devices[:4])


def test_sharded_unpack_compiles_on_four_chips(mesh_2x2):
    mesh = mesh_2x2
    n_shards = mesh.shape["data"]
    shard_entries, stride = packed_shard_layout(
        [(n, s, np.dtype(d).name) for n, s, d in SECTIONS], n_shards
    )
    u8 = jax.ShapeDtypeStruct(
        (n_shards * stride,), jnp.uint8,
        sharding=NamedSharding(mesh, P("data")),
    )
    unpack = _shard_unpacker(shard_entries, stride, mesh, "data", "tpu")
    text = unpack.lower(u8).compile().as_text()
    # shard-local by construction: no collective in the unpack
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op


@pytest.fixture(scope="module")
def fm_2x2(mesh_2x2):
    from dmlc_core_tpu.parallel import data_parallel_step

    mesh = mesh_2x2
    model = FactorizationMachine(N_FEATURES, embed_dim=EMBED)
    rules = {"v": P(None, "model")}
    params = _fm_structs(
        model, lambda name: NamedSharding(mesh, rules.get(name, P()))
    )
    batch = _criteo_batch_structs(
        lambda shape: NamedSharding(mesh, P("data", *[None] * (len(shape) - 1)))
    )
    # undonated, as the benchmark runs it: a donated v shard is the
    # kernel's output buffer, so XLA copies the shard in before the kernel
    step = data_parallel_step(
        lambda p, b: model.sgd_step(p, b, lr=0.1), mesh, param_rules=rules,
        donate_params=False,
    )
    with pytest.MonkeyPatch.context() as mp:
        _on_tpu(mp)
        return step.jitted(params, batch).lower(params, batch).compile()


def test_spmd_fm_step_fits_four_chips(fm_2x2):
    mem = fm_2x2.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < HBM_BYTES, mem


def test_spmd_fm_step_all_reduces_no_table(fm_2x2):
    # the row gradients are replicated before the scatter, so the
    # partitioner all-gathers rows instead of all-reducing a table-sized
    # gradient over 'data'
    text = fm_2x2.as_text()
    reduced = _ops(text, "all-reduce")
    assert all(int(np.prod(dims)) < N_FEATURES for _dt, dims in reduced), (
        reduced
    )
    assert _ops(text, "all-gather")


def test_spmd_fm_step_writes_v_shard_in_the_kernel(fm_2x2):
    # the 2x2 mesh: each device's kernel writes its new f32[N, 8] v shard
    # (model splits E = 16) through the free [8, N] view; XLA copies no
    # table or shard, runs no conditional, and moves nothing table-sized
    # between devices
    text = fm_2x2.as_text()
    local = EMBED // 2
    assert _kernel_calls(text) == [("f32", (local, N_FEATURES))]
    tables = {("f32", (N_FEATURES, e)) for e in (local, EMBED)}
    tables |= {("f32", (e, N_FEATURES)) for e in (local, EMBED)}
    copies = [r for op in ("copy", "transpose") for r in _ops(text, op)
              if r in tables]
    assert not copies, copies
    assert " conditional(" not in text
    moved = [r for op in ("all-gather", "all-reduce", "all-to-all")
             for r in _ops(text, op) if int(np.prod(r[1])) >= N_FEATURES]
    assert not moved, moved


def _updates(line):
    """Whether an entry line writes a table: XLA's scatter into ``w`` or
    the kernel's call that writes the ``v`` shard."""
    return "scatter-add" in line or "tpu_custom_call" in line


def test_spmd_fm_step_loss_waits_for_the_update(fm_2x2):
    # the loss comes out of an all-reduce over the whole mesh scheduled
    # after the w scatter and the v kernel, so a caller that waits on it
    # waits for every device's update
    text = fm_2x2.as_text()
    entry = text.split("\nENTRY ", 1)[1].splitlines()
    updates = [i for i, ln in enumerate(entry) if _updates(ln)]
    assert any("scatter-add" in entry[i] for i in updates)
    assert any("tpu_custom_call" in entry[i] for i in updates)
    whole = [i for i, ln in enumerate(entry)
             if " all-reduce(" in ln and "replica_groups={{0,1,2,3}}" in ln]
    assert whole and whole[-1] > updates[-1]


# -- the field-aware FM (criteo_ffm): libffm's k 4 on Criteo's 39 fields, 10^6
# hashed bins, W and G [10^6, 256] -------------------------------------------

FFM_FEATURES, FFM_FIELDS, FFM_K = 10**6, 39, 4


@pytest.fixture(scope="module")
def ffm_one_chip(one_chip):
    from dmlc_core_tpu.models import FieldAwareFM, ffm

    model = FieldAwareFM(FFM_FEATURES, FFM_FIELDS, k=FFM_K)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
              for k, s in shapes.items()}
    batch = _criteo_batch_structs(lambda _shape: one_chip)
    batch["fields"] = jax.ShapeDtypeStruct((B, K), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, b: model.adagrad_step(p, b, lr=0.2))
    rule = ffm._update_path
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ffm, "_update_path", lambda _p, *a: rule("tpu", *a))
        return step.lower(params, batch).compile()


def test_ffm_step_fits_one_chip(ffm_one_chip, record_property):
    mem = ffm_one_chip.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    record_property("ffm_step_temp_bytes", int(mem.temp_size_in_bytes))
    print(f"FFM adagrad_step 10^6 x 256: argument "
          f"{mem.argument_size_in_bytes} output {mem.output_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} B")
    assert total < HBM_BYTES, mem


def test_ffm_step_writes_both_tables_in_the_kernel(ffm_one_chip):
    # one kernel writes the new W and G; XLA builds no table-sized
    # gradient, scatters nothing into a table and copies no table
    text = ffm_one_chip.as_text()
    calls = re.findall(r"= \((.*?)\) custom-call\(.*?custom_call_target="
                       r"\"tpu_custom_call\"", text)
    table = f"f32[{FFM_FEATURES},256]"
    assert [c.count(table) for c in calls] == [2]
    for op in ("broadcast", "scatter", "copy", "transpose"):
        assert ("f32", (FFM_FEATURES, 256)) not in _ops(text, op), op
    # no intermediate has k = 4 or K = 39 lanes minor (128-lane padding)
    for dt, dims, layout in re.findall(
            r"= (f32)\[([\d,]+)\]\{([\d,]+)", text):
        dims = [int(d) for d in dims.split(",")]
        minor = dims[int(layout.split(",")[0])]
        assert not (minor in (FFM_K, K) and np.prod(dims) > 1 << 20), (
            dims, layout)
