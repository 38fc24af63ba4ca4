"""Megatron-style sequence parallelism (SP).

Reference: ``python/paddle/distributed/fleet/utils/sequence_parallel_utils.py``
(``ScatterOp:85``, ``GatherOp:97``, ``AllGatherOp:111``, ``ReduceScatterOp:127``,
``ColumnSequenceParallelLinear:427``, ``RowSequenceParallelLinear``,
``register_sequence_parallel_allreduce_hooks:192``).

TPU-native: SP is *sequence-dimension sharding over the mp axis*. The
reference's four PyLayers are the manual collective schedule around TP blocks
(scatter seq → TP region → gather seq); under GSPMD the same schedule falls out
of constraining the sequence dim sharded outside TP blocks and letting XLA
place the all-gather/reduce-scatter on ICI. Inside ``shard_map`` regions the
ops lower to explicit ``lax`` collectives with the reference's exact
forward/backward duals.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from paddle_tpu.core.dispatch import defop
from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import (
    _axis_in_trace,
    _get_mp_env,
)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.layers import Layer

__all__ = [
    "ScatterOp",
    "GatherOp",
    "AllGatherOp",
    "ReduceScatterOp",
    "scatter",
    "all_gather",
    "mark_as_sequence_parallel_parameter",
    "is_sequence_parallel_parameter",
    "register_sequence_parallel_allreduce_hooks",
    "ColumnSequenceParallelLinear",
    "RowSequenceParallelLinear",
]

_SEQ_DIM = 0  # reference keeps [s, b, h] layout inside SP regions


def _check_divisible(n: int, world: int, what: str) -> None:
    if n % world != 0:
        raise ValueError(f"{what}: sequence dim {n} not divisible by mp world size {world}")


@defop("sp_scatter")
def _scatter_op(x: Any, *, axis: str) -> Any:
    # fwd: keep own seq chunk; bwd: all-gather seq (GatherOp's forward)
    @jax.custom_vjp
    def f(v):
        world = jax.lax.axis_size(axis)
        _check_divisible(v.shape[_SEQ_DIM], world, "ScatterOp")
        idx = jax.lax.axis_index(axis)
        d = v.shape[_SEQ_DIM] // world
        return jax.lax.dynamic_slice_in_dim(v, idx * d, d, axis=_SEQ_DIM)

    def fwd(v):
        return f(v), None

    def bwd(_, g):
        return (jax.lax.all_gather(g, axis, axis=_SEQ_DIM, tiled=True),)

    f.defvjp(fwd, bwd)
    return f(x)


@defop("sp_gather")
def _gather_op(x: Any, *, axis: str) -> Any:
    # fwd: all-gather seq; bwd: slice own seq chunk (ScatterOp's forward) —
    # the dual for a *replicated* downstream gradient (reference GatherOp)
    @jax.custom_vjp
    def f(v):
        return jax.lax.all_gather(v, axis, axis=_SEQ_DIM, tiled=True)

    def fwd(v):
        return f(v), v.shape[_SEQ_DIM]

    def bwd(d, g):
        idx = jax.lax.axis_index(axis)
        return (jax.lax.dynamic_slice_in_dim(g, idx * d, d, axis=_SEQ_DIM),)

    f.defvjp(fwd, bwd)
    return f(x)


@defop("sp_all_gather")
def _all_gather_op(x: Any, *, axis: str) -> Any:
    # fwd: all-gather seq; bwd: reduce-scatter seq (ReduceScatterOp forward) —
    # the dual for per-rank partial downstream gradients (reference AllGatherOp)
    @jax.custom_vjp
    def f(v):
        return jax.lax.all_gather(v, axis, axis=_SEQ_DIM, tiled=True)

    def fwd(v):
        return f(v), None

    def bwd(_, g):
        return (jax.lax.psum_scatter(g, axis, scatter_dimension=_SEQ_DIM, tiled=True),)

    f.defvjp(fwd, bwd)
    return f(x)


@defop("sp_reduce_scatter")
def _reduce_scatter_op(x: Any, *, axis: str) -> Any:
    @jax.custom_vjp
    def f(v):
        return jax.lax.psum_scatter(v, axis, scatter_dimension=_SEQ_DIM, tiled=True)

    def fwd(v):
        return f(v), None

    def bwd(_, g):
        return (jax.lax.all_gather(g, axis, axis=_SEQ_DIM, tiled=True),)

    f.defvjp(fwd, bwd)
    return f(x)


class ScatterOp:
    """Split the sequence dim across the mp group (fwd) / gather (bwd)."""

    @staticmethod
    def apply(x: Any, group: Any = None) -> Any:
        mesh, axis, world = _get_mp_env(group)
        if world == 1:
            return x
        if _axis_in_trace(axis):
            return _scatter_op(x, axis=axis)
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import mark_sharded

        return mark_sharded(x, _SEQ_DIM, group)


class GatherOp:
    """Gather the sequence dim (fwd) / slice grads (bwd, replicated-grad dual)."""

    @staticmethod
    def apply(x: Any, group: Any = None) -> Any:
        mesh, axis, world = _get_mp_env(group)
        if world == 1:
            return x
        if _axis_in_trace(axis):
            return _gather_op(x, axis=axis)
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import mark_replicated

        return mark_replicated(x, group)


class AllGatherOp:
    """All-gather seq (fwd) / reduce-scatter grads (bwd, partial-grad dual) —
    used before the qkv/up projection in SP attention/mlp blocks."""

    @staticmethod
    def apply(x: Any, group: Any = None) -> Any:
        mesh, axis, world = _get_mp_env(group)
        if world == 1:
            return x
        if _axis_in_trace(axis):
            return _all_gather_op(x, axis=axis)
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import mark_replicated

        return mark_replicated(x, group)


class ReduceScatterOp:
    """Reduce-scatter seq (fwd) / all-gather grads (bwd) — used after the
    out/down projection."""

    @staticmethod
    def apply(x: Any, group: Any = None) -> Any:
        mesh, axis, world = _get_mp_env(group)
        if world == 1:
            return x
        if _axis_in_trace(axis):
            return _reduce_scatter_op(x, axis=axis)
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import mark_sharded

        return mark_sharded(x, _SEQ_DIM, group)


def scatter(x: Any, group: Any = None) -> Any:
    return ScatterOp.apply(x, group)


def all_gather(x: Any, group: Any = None) -> Any:
    return AllGatherOp.apply(x, group)


def mark_as_sequence_parallel_parameter(parameter: Any) -> None:
    """Tag params (layernorm etc.) whose grads need an mp-group allreduce in
    the reference's hook scheme (``:165``). Under GSPMD replicated params
    already receive reduced grads; the tag is kept for API parity/inspection."""
    parameter.sequence_parallel = True


def is_sequence_parallel_parameter(parameter: Any) -> bool:
    return getattr(parameter, "sequence_parallel", False)


def register_sequence_parallel_allreduce_hooks(model: Any, accumulation_steps: int = 1, fuse_sequence_parallel_allreduce: bool = False) -> None:
    """Reference ``:192``: hooks all-reducing tagged params' grads over mp.

    Global-view: replicated parameters contracted against seq-sharded
    activations already produce fully-reduced grads (XLA inserts the psum), so
    the hooks are no-ops; kept so reference training scripts run unchanged."""
    for p in model.parameters():
        if is_sequence_parallel_parameter(p):
            p.sequence_parallel = True


class ColumnSequenceParallelLinear(Layer):
    """ColumnParallelLinear fused with the SP boundary: input arrives
    seq-sharded, is (all-)gathered, and the matmul output stays column-sharded.
    Reference: ``sequence_parallel_utils.py:427``."""

    def __init__(
        self,
        in_features: int,
        out_features: int,
        weight_attr: Any = None,
        has_bias: bool = True,
        gather_output: bool = False,
        fuse_matmul_bias: bool = False,
        mp_group: Any = None,
        name: Optional[str] = None,
    ) -> None:
        super().__init__()
        from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import _shard_param

        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self._group = mp_group
        _, _, self.world_size = _get_mp_env(mp_group)
        if out_features % max(self.world_size, 1) != 0:
            raise ValueError(
                f"out_features ({out_features}) must be divisible by mp world size ({self.world_size})"
            )
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr)
        _shard_param(self.weight, 1, mp_group)
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            _shard_param(self.bias, 0, mp_group)
        else:
            self.bias = None

    def forward(self, x: Any) -> Any:
        x = AllGatherOp.apply(x, self._group)
        y = F.linear(x, self.weight, self.bias)
        from paddle_tpu.distributed.fleet.layers.mpu import mp_ops

        if self.gather_output:
            return mp_ops._c_concat(y, self._group)
        return mp_ops.mark_sharded(y, -1, self._group)


class RowSequenceParallelLinear(Layer):
    """RowParallelLinear fused with the SP boundary: the partial-sum output is
    reduce-scattered over the sequence dim instead of all-reduced."""

    def __init__(
        self,
        in_features: int,
        out_features: int,
        weight_attr: Any = None,
        has_bias: bool = True,
        input_is_parallel: bool = True,
        fuse_matmul_bias: bool = False,
        mp_group: Any = None,
        name: Optional[str] = None,
    ) -> None:
        super().__init__()
        from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import _shard_param

        self.in_features = in_features
        self.out_features = out_features
        self._group = mp_group
        _, _, self.world_size = _get_mp_env(mp_group)
        if in_features % max(self.world_size, 1) != 0:
            raise ValueError(
                f"in_features ({in_features}) must be divisible by mp world size ({self.world_size})"
            )
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr)
        _shard_param(self.weight, 0, mp_group)
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            _shard_param(self.bias, None, mp_group)
            mark_as_sequence_parallel_parameter(self.bias)
        else:
            self.bias = None

    def forward(self, x: Any) -> Any:
        from paddle_tpu.distributed.fleet.layers.mpu import mp_ops

        x = mp_ops.mark_sharded(x, -1, self._group)
        y = F.linear(x, self.weight)
        y = ReduceScatterOp.apply(y, self._group)
        if self.bias is not None:
            y = y + self.bias
        return y
