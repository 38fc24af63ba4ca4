"""Tensor-parallel collective ops.

Reference: ``python/paddle/distributed/fleet/layers/mpu/mp_ops.py``
(``_c_identity``, ``_c_concat``, ``_c_split``, ``_mp_allreduce``, …). Those are
hand-placed NCCL calls with custom backward rules; the TPU-native equivalents
are *sharding annotations*: a forward identity whose backward all-reduces is
exactly what GSPMD emits when a replicated activation feeds a sharded matmul,
so in the global-view path these ops become differentiable
``with_sharding_constraint`` placements and XLA inserts the collectives.
Inside a ``shard_map`` region (per-shard view, used by the pipeline runtime and
tests) they lower to explicit ``lax`` collectives with custom VJPs — the same
dual the reference expresses with its PyLayer forward/backward pairs.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core.dispatch import defop
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.collective import Group
from paddle_tpu.distributed.mesh import get_mesh

__all__ = [
    "_c_identity",
    "_c_concat",
    "_c_split",
    "_mp_allreduce",
    "_get_mp_env",
    "mark_sharded",
    "mark_replicated",
]


def _axis_in_trace(axis: Optional[str]) -> bool:
    """True when `axis` is a bound shard_map/pmap axis in the current trace."""
    if axis is None:
        return False
    try:
        jax.lax.axis_size(axis)
        return True
    except NameError:
        return False


def _get_mp_env(group: Optional[Group] = None):
    """Resolve (mesh, mp_axis_name, world_size) for the model-parallel group.

    Order: explicit group → fleet hybrid group → a mesh axis named 'mp'/'model'.
    """
    axis = group.axis_name if group is not None else None
    if axis is None:
        from paddle_tpu.distributed.fleet import fleet as _fleet

        hcg = _fleet.get_hybrid_communicate_group()
        if hcg is not None and hcg.get_model_parallel_world_size() > 1:
            axis = hcg.get_model_parallel_group().axis_name
    mesh = get_mesh()
    if axis is None and mesh is not None:
        for cand in ("mp", "model", "tp"):
            if cand in mesh.dim_names:
                axis = cand
                break
    if axis is None:
        return None, None, 1
    world = group.nranks if group is not None else mesh.get_dim_size(axis)
    return mesh, axis, world


@defop("sharding_constraint")
def _constrain(x: Any, *, sharding: Any) -> Any:
    # Differentiable placement: under ad-tracing this is the
    # sharding_constraint primitive (transpose = same constraint); on concrete
    # arrays it reshards via device_put.
    return jax.lax.with_sharding_constraint(x, sharding)


def _merged_spec(t: Any, dim: Optional[int], axis: str) -> PartitionSpec:
    """Spec that places `axis` on `dim` (None → nowhere) while PRESERVING the
    tensor's existing placement on every other mesh axis — constraining only
    the mp axis, so dp/batch shardings survive hybrid dp+mp training."""
    ndim = t.ndim
    data = t.data if isinstance(t, Tensor) else t
    current = getattr(data, "sharding", None)
    entries: list = [None] * ndim
    if isinstance(current, NamedSharding):
        spec = list(current.spec) + [None] * (ndim - len(current.spec))
        for i, e in enumerate(spec):
            if not isinstance(e, (str, tuple, list)):
                # None or UNCONSTRAINED: neither pins this dim to a mesh
                # axis — nothing to preserve
                continue
            kept = tuple(a for a in ((e,) if isinstance(e, str) else tuple(e)) if a != axis)
            entries[i] = kept[0] if len(kept) == 1 else (kept or None)
    else:
        # unknown layout (tracer inside user jit): leave other dims free
        entries = [PartitionSpec.UNCONSTRAINED] * ndim
    if dim is not None:
        entries[dim % ndim] = axis
    return PartitionSpec(*entries)


def mark_sharded(t: Any, dim: int, group: Optional[Group] = None) -> Any:
    """Constrain tensor dim to be sharded over the mp axis (other axes kept)."""
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return t
    sharding = NamedSharding(mesh.jax_mesh(), _merged_spec(t, dim, axis))
    return _constrain(t, sharding=sharding)


def mark_replicated(t: Any, group: Optional[Group] = None) -> Any:
    """Constrain tensor to be replicated over the mp axis (other axes kept)."""
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return t
    sharding = NamedSharding(mesh.jax_mesh(), _merged_spec(t, None, axis))
    return _constrain(t, sharding=sharding)


# -- shard_map-region variants (explicit collectives with custom VJP) ---------


def _identity_fwd_allreduce_bwd(axis: str):
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f


def _allreduce_fwd_identity_bwd(axis: str):
    @jax.custom_vjp
    def f(x):
        return jax.lax.psum(x, axis)

    def fwd(x):
        return jax.lax.psum(x, axis), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f


@defop("c_identity")
def _c_identity_op(x: Any, *, axis: str) -> Any:
    return _identity_fwd_allreduce_bwd(axis)(x)


@defop("mp_allreduce")
def _mp_allreduce_op(x: Any, *, axis: str) -> Any:
    return _allreduce_fwd_identity_bwd(axis)(x)


@defop("c_concat")
def _c_concat_op(x: Any, *, axis: str) -> Any:
    # gather last dim across the group; bwd = slice out own chunk
    @jax.custom_vjp
    def f(v):
        g = jax.lax.all_gather(v, axis)  # [world, ..., d]
        return jnp.concatenate([g[i] for i in range(g.shape[0])], axis=-1)

    def fwd(v):
        return f(v), v.shape[-1]

    def bwd(d, grad):
        idx = jax.lax.axis_index(axis)
        start = idx * d
        return (jax.lax.dynamic_slice_in_dim(grad, start, d, axis=-1),)

    f.defvjp(fwd, bwd)
    return f(x)


@defop("c_split")
def _c_split_op(x: Any, *, axis: str) -> Any:
    # keep own chunk of last dim; bwd = all_gather
    @jax.custom_vjp
    def f(v):
        world = jax.lax.axis_size(axis)
        if v.shape[-1] % world != 0:
            raise ValueError(
                f"_c_split: last dim {v.shape[-1]} not divisible by mp world size {world}"
            )
        d = v.shape[-1] // world
        idx = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(v, idx * d, d, axis=-1)

    def fwd(v):
        return f(v), None

    def bwd(_, grad):
        g = jax.lax.all_gather(grad, axis)
        return (jnp.concatenate([g[i] for i in range(g.shape[0])], axis=-1),)

    f.defvjp(fwd, bwd)
    return f(x)


# -- public PyLayer-parity surface -------------------------------------------


def _c_identity(tensor: Any, group: Optional[Group] = None) -> Any:
    """Forward identity; backward all-reduce over the mp group.

    Global view: identity (GSPMD derives the grad reduction from shardings).
    """
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return tensor
    if _axis_in_trace(axis):
        return _c_identity_op(tensor, axis=axis)
    return tensor


def _mp_allreduce(tensor: Any, group: Optional[Group] = None, use_calc_stream: bool = True, use_model_parallel: bool = True, op: Any = None) -> Any:
    """Forward all-reduce; backward identity.

    Global view: a partial value only arises inside a compiled region, where
    constraining to replicated makes XLA emit the psum.
    """
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return tensor
    if _axis_in_trace(axis):
        return _mp_allreduce_op(tensor, axis=axis)
    return mark_replicated(tensor, group)


def _c_concat(tensor: Any, group: Optional[Group] = None) -> Any:
    """Gather last-dim shards into the full tensor on every rank."""
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return tensor
    if _axis_in_trace(axis):
        return _c_concat_op(tensor, axis=axis)
    return mark_replicated(tensor, group)


def _c_split(tensor: Any, group: Optional[Group] = None) -> Any:
    """Keep this rank's last-dim chunk (inverse of _c_concat)."""
    mesh, axis, world = _get_mp_env(group)
    if world == 1:
        return tensor
    if _axis_in_trace(axis):
        return _c_split_op(tensor, axis=axis)
    return mark_sharded(tensor, -1, group)
