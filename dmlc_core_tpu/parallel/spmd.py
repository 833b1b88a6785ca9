"""SPMD step compilation: data parallelism + optional parameter sharding.

The data-plane counterpart of the reference's control-plane-only
distribution (SURVEY §5.8): where rabit ran allreduce over the tracker's
tree/ring, here jit with NamedShardings makes XLA insert the gradient
psum over ICI. Tensor parallelism falls out of the same mechanism: give a
param a PartitionSpec with the 'model' axis and XLA shards the compute
and inserts the matching collectives.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

__all__ = ["replicate", "shard_params", "data_parallel_step"]


def replicate(tree, mesh):
    """Place a pytree fully replicated over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    # parameter placement is a one-shot transfer, not batch staging —
    # it rides the staging layer's sanctioned device_put (lint L007)
    from ..staging.pipeline import device_put

    sharding = NamedSharding(mesh, PartitionSpec())
    return device_put(tree, sharding)


def shard_params(
    params: Dict[str, Any],
    mesh,
    rules: Optional[Dict[str, Any]] = None,
):
    """Place params by name→PartitionSpec rules; unlisted params replicate.

    Example (FM embedding tensor-parallel over 'model')::

        shard_params(params, mesh, {"v": P(None, "model")})
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from ..staging.pipeline import device_put

    rules = rules or {}
    out = {}
    for name, value in params.items():
        spec = rules.get(name, PartitionSpec())
        out[name] = device_put(value, NamedSharding(mesh, spec))
    return out


def data_parallel_step(
    step_fn: Callable,
    mesh,
    data_axis: str = "data",
    param_rules: Optional[Dict[str, Any]] = None,
    donate_params: bool = True,
):
    """Compile ``step_fn(params, batch) -> (params, aux)`` for SPMD.

    - batch arrays: sharded on their leading dim over ``data_axis``
    - params: replicated, or sharded per ``param_rules`` (tensor
      parallelism); outputs keep the same shardings, so the returned
      params feed straight into the next call
    - gradient reduction: implicit — the weighted-mean loss over the
      sharded batch makes XLA emit the cross-replica psum (rabit's
      allreduce, moved into the compiler)

    The returned step's ``jitted(params, batch)`` gives the jitted
    function itself, for ahead-of-time ``.lower(...).compile()`` on
    ShapeDtypeStructs (tests/test_tpu_compile.py).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    rules = param_rules or {}

    def param_sharding(path, _leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return NamedSharding(mesh, rules.get(name, PartitionSpec()))

    def batch_sharding(_path, leaf):
        spec = PartitionSpec(data_axis, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, spec)

    def make_in_shardings(params, batch):
        p = jax.tree_util.tree_map_with_path(param_sharding, params)
        b = jax.tree_util.tree_map_with_path(batch_sharding, batch)
        return p, b

    compiled: Dict[Any, Callable] = {}

    def structure_key(params, batch, nargs: int):
        p = tuple(
            (str(path), leaf.ndim)
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        )
        b = tuple(
            (str(path), leaf.ndim)
            for path, leaf in jax.tree_util.tree_leaves_with_path(batch)
        )
        return (p, b, nargs)

    def jitted(params, batch, *args):
        """The jitted step for these arguments' structure (arrays or
        ShapeDtypeStructs — what an ahead-of-time ``.lower`` needs)."""
        # one jit per (pytree structure, ndims) so switching batch layouts
        # (ell ↔ dense) re-derives the shardings
        key = structure_key(params, batch, len(args))
        fn = compiled.get(key)
        if fn is None:
            in_shardings = make_in_shardings(params, batch)
            extra = tuple(None for _ in args)
            fn = jax.jit(
                step_fn,
                in_shardings=(*in_shardings, *extra),
                donate_argnums=(0,) if donate_params else (),
            )
            compiled[key] = fn
        return fn

    def run(params, batch, *args):
        return jitted(params, batch, *args)(params, batch, *args)

    run.jitted = jitted
    return run
