"""The main path's programs compile for a described TPU v5e.

Nothing runs: each program is lowered with ShapeDtypeStructs placed on
devices of a described ``v5e:2x2`` topology and compiled by the TPU
compiler installed here. A compile that passes is not a chip run; it
catches what the chip's compiler would refuse (shapes, memory, sharding)
at no chip time. The topology is described only inside the ``topo``
fixture (never at import): only one process may load libtpu, and every
xdist worker imports this file.

Sizes are the chip smoke's (``chip_smoke.py``): Criteo layout K=39,
batch 8192, f16 values, int32 indices (what ``BatchSpec`` stages), and an
FM of 2^24 features x E=16.
"""

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


def test_fm_sgd_step_fits_one_chip(one_chip, record_property):
    model = FactorizationMachine(N_FEATURES, embed_dim=EMBED)
    params = _fm_structs(model, lambda _name: one_chip)
    batch = _criteo_batch_structs(lambda _shape: one_chip)
    step = jax.jit(lambda p, b: model.sgd_step(p, b, lr=0.1))
    mem = step.lower(params, batch).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    record_property("fm_step_bytes", int(total))
    print(f"FM sgd_step 2^24 x {EMBED}: argument "
          f"{mem.argument_size_in_bytes} output {mem.output_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} B")
    assert total < HBM_BYTES, mem


def test_sharded_unpack_and_spmd_step_compile_on_four_chips(topo):
    from dmlc_core_tpu.parallel import data_parallel_step, make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices[:4])
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

    model = FactorizationMachine(N_FEATURES, embed_dim=EMBED)
    rules = {"v": P(None, "model")}
    params = _fm_structs(
        model, lambda name: NamedSharding(mesh, rules.get(name, P()))
    )
    batch = _criteo_batch_structs(
        lambda shape: NamedSharding(mesh, P("data", *[None] * (len(shape) - 1)))
    )
    step = data_parallel_step(
        lambda p, b: model.sgd_step(p, b, lr=0.1), mesh, param_rules=rules,
    )
    compiled = step.jitted(params, batch).lower(params, batch).compile()
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < HBM_BYTES, mem
