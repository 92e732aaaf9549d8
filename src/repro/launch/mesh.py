"""Production mesh construction + sharding policy.

``make_production_mesh`` is a *function* (not a module-level constant) so
importing this module never touches jax device state.  The single-pod mesh is
16x16 = 256 chips ("data", "model"); the multi-pod mesh is 2x16x16 = 512
chips ("pod", "data", "model"), with the "pod" axis proving that the config
shards across pod boundaries (DCN-crossing collectives).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.common import (
    ModelConfig,
    ShardingRules,
    logical_to_physical,
)


def make_fleet_mesh(shards: int):
    """1-D ``"fleet"`` device mesh for sharded TVM execution (DESIGN.md
    §15): shard ``p`` of a :class:`~repro.distributed.fleet.ShardedFleet`
    lives on device ``p`` and runs its own resident chunk loop under
    ``shard_map``.

    One shard needs no mesh (``None``: its one block stays on the default
    device).  More shards than attached devices is an error: the
    single-device ``vmap`` simulation is asked for by name, with
    ``mesh=None``, never substituted for a missing device.
    """
    if shards < 1:
        raise ValueError(f"a fleet needs >= 1 shard, got {shards}")
    if shards == 1:
        return None
    have = len(jax.devices())
    if have < shards:
        raise ValueError(
            f"a {shards}-shard fleet needs {shards} devices, but "
            f"{have} {jax.default_backend()} device(s) are attached; pass "
            "mesh=None to run the single-device vmap simulation"
        )
    return jax.make_mesh(
        (shards,), ("fleet",), axis_types=(jax.sharding.AxisType.Auto,)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def rules_for_mesh(
    mesh,
    base: Optional[ShardingRules] = None,
    sequence_parallel: bool = False,
) -> ShardingRules:
    """Filter logical->mesh rules down to the axes this mesh actually has,
    optionally enabling Megatron-style sequence parallelism (residual-stream
    activations sharded over 'model' between attention/MLP blocks)."""
    base = base or ShardingRules()
    if sequence_parallel:
        base = base.replace(seq="model")
    names = set(mesh.axis_names)
    out = []
    for k, v in base.rules:
        if isinstance(v, tuple):
            kept = tuple(a for a in v if a in names)
            v = kept[0] if len(kept) == 1 else (kept or None)
        elif v is not None and v not in names:
            v = None
        out.append((k, v))
    return ShardingRules(rules=tuple(out))


def data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


def batch_pspec(mesh, global_batch: int) -> P:
    """Shard batch over (pod, data) when divisible, else replicate (e.g. the
    batch=1 long-context cell)."""
    if global_batch % data_size(mesh) == 0:
        ax = data_axes(mesh)
        return P(ax[0] if len(ax) == 1 else ax)
    return P(None)


def abstract_params(cfg: ModelConfig, seed: int = 0):
    """(ShapeDtypeStruct params, logical axes) without allocating anything."""
    from ..models.model import init_model

    axes: Dict[str, tuple] = {}

    def f(key):
        p, a = init_model(cfg, key)
        axes.update(a)
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(seed))
    return shapes, axes


def param_shardings(cfg: ModelConfig, mesh, rules: ShardingRules):
    """ShapeDtypeStructs carrying NamedShardings for every parameter."""
    shapes, axes = abstract_params(cfg)
    out = {
        k: jax.ShapeDtypeStruct(
            v.shape,
            v.dtype,
            sharding=NamedSharding(mesh, logical_to_physical(axes[k], rules)),
        )
        for k, v in shapes.items()
    }
    return out, axes


def cache_specs(
    cfg: ModelConfig, mesh, B: int, max_len: int
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Sharded ShapeDtypeStructs for the decode cache.

    KV heads shard over 'model'.  Batch shards over (pod, data) when it
    divides; for batch=1 long-context the *sequence* dim of the KV cache
    shards over the data axes instead (cache sequence parallelism).
    """
    from ..models.model import init_cache

    shapes = jax.eval_shape(
        functools.partial(init_cache, cfg, B, max_len)
    )
    bspec = batch_pspec(mesh, B)
    b_ax = bspec[0] if len(bspec) else None
    seq_ax = None
    if b_ax is None and max_len % data_size(mesh) == 0:
        ax = data_axes(mesh)
        seq_ax = ax[0] if len(ax) == 1 else ax
    spec_map = {
        "lengths": P(b_ax),
        "k": P(None, b_ax, "model", seq_ax, None),
        "v": P(None, b_ax, "model", seq_ax, None),
        "ssm_conv": P(None, b_ax, None, "model"),
        # shard headdim (always divisible), not n_heads (hymba: 50 heads)
        "ssm_state": P(None, b_ax, None, "model", None),
        "enc_out": P(b_ax, None, None),
        "cross_k": P(None, b_ax, "model", None, None),
        "cross_v": P(None, b_ax, "model", None, None),
    }
    return {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=NamedSharding(mesh, spec_map[k])
        )
        for k, v in shapes.items()
    }
