"""Kernel-path selection: one place deciding Pallas vs XLA-fallback.

A dispatch site's ``try``/``except`` only sees trace-time failures: a kernel
that traces but does not lower or compile fails inside the captured step,
where nothing can catch it. Kernels on the main paths are therefore REQUIRED
to compile on TPU — tests/test_tpu_aot_compile.py compiles each for a
described chip at real widths."""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional

import jax
from jax.sharding import PartitionSpec

from paddle_tpu.core.spmd import shard_group_mesh, trace_partition
from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.observability import get_registry

_logger = logging.getLogger("paddle_tpu.kernels")
_warned: set = set()
_fallbacks_total = get_registry().counter(
    "paddle_tpu_kernel_fallbacks_total",
    "Pallas kernel failures that degraded to the XLA fallback path, by kernel.",
    labelnames=("kernel",),
)
_loss_backward_total = get_registry().counter(
    "paddle_tpu_fused_loss_backward_built_total",
    "Fused loss head backward passes traced, by what dW does for the block "
    "gradient d: reads the one dX stored, or recomputes it (d over its share "
    "of device memory).",
    labelnames=("path",),
)
_routed_warned: set = set()
_routed_total = get_registry().counter(
    "paddle_tpu_kernel_partition_routed_total",
    "Dispatches that ran the XLA composition because the trace is partitioned "
    "over devices and cannot hold a bare pallas_call, by kernel.",
    labelnames=("kernel",),
)

# per-flag cached bools kept fresh by on_change listeners: pallas_enabled
# runs on EVERY kernel-path dispatch (rope calls it once per q/k tensor), so
# it must not take the flag-registry lock per op (analyzer check CC704 — the
# same _NAN_CHECK discipline core/dispatch.py uses)
_flag_cache: Dict[str, List[bool]] = {}


def _cached_flag(flag: str) -> bool:
    cell = _flag_cache.get(flag)
    if cell is None:
        cell = _flag_cache.setdefault(flag, [False])

        def _refresh(value: Any, _cell: List[bool] = cell) -> None:
            _cell[0] = bool(value)

        GLOBAL_FLAGS.on_change(flag, _refresh)
        # analysis: disable=CC704 one-time cache seeding: runs once per flag lifetime (cell-miss branch), every later call reads the cached cell
        cell[0] = bool(GLOBAL_FLAGS.get(flag))  # seeds the FLAGS_ env var
    return cell[0]


def pallas_enabled(flag: str, bare: Optional[str] = None, row_wise: bool = False) -> bool:
    """Flag on, running on a TPU backend, and in a trace the kernel can live
    in. ``bare`` is the kernel name of a site that emits its ``pallas_call``
    as is: a trace that will be partitioned over devices (``core/spmd.py``)
    cannot hold one, so there the site runs its XLA composition, which GSPMD
    splits — counted per kernel in ``paddle_tpu_kernel_partition_routed_total``
    and warned once, never silent. ``row_wise`` sites call their kernel
    through :func:`per_shard`, so they keep it under a shard group of known
    layout (the serving engine's tp mesh). A site that wraps its kernel in
    ``shard_map`` itself passes no ``bare``. Ask this LAST in a site's
    condition: it counts only a routing the site would otherwise have taken."""
    if not (_cached_flag(flag) and jax.default_backend() == "tpu"):
        return False
    partition = trace_partition()
    if bare is None or partition is None or (row_wise and shard_group_mesh() is not None):
        return True
    _routed_total.labels(kernel=bare).inc()
    if bare not in _routed_warned:
        _routed_warned.add(bare)
        _logger.warning(
            "Pallas kernel %s runs its XLA composition in this trace: it is "
            "partitioned over devices and cannot hold a bare pallas_call", bare
        )
    return False


def per_shard(kernel_fn: Callable[..., Any]) -> Callable[..., Any]:
    """A row-wise kernel (arrays in, arrays out) as the trace can hold it: as
    is on one device; under a shard group's mesh, ``shard_map``-ped with every
    operand replicated — the hidden states these kernels work on are, there —
    so each shard runs the kernel on its own copy."""
    mesh = shard_group_mesh()
    if mesh is None:
        return kernel_fn
    return jax.shard_map(
        kernel_fn, mesh=mesh, in_specs=PartitionSpec(), out_specs=PartitionSpec(),
        check_vma=False,
    )


def warn_fallback(kernel: str, exc: Exception) -> None:
    """Counted (every occurrence) when a Pallas kernel fails and the XLA
    path is used — the counter makes the degradation scrapeable. On a TPU
    backend the kernel was SUPPOSED to run: every occurrence is logged at
    ERROR with the exception (``chip_smoke.py`` fails on any count).
    Elsewhere (an injected fault on the CPU reference path) one warning."""
    _fallbacks_total.labels(kernel=kernel).inc()
    if jax.default_backend() == "tpu":
        _logger.error(
            "Pallas kernel %s failed on TPU; using XLA fallback", kernel, exc_info=exc
        )
    elif kernel not in _warned:
        _warned.add(kernel)
        _logger.warning("Pallas kernel %s failed (%s); using XLA fallback", kernel, exc)


def count_loss_backward(path: str) -> None:
    """One fused loss head backward was traced on ``path`` (``stored`` or
    ``recomputed``): a run's scrape says which one its step holds."""
    _loss_backward_total.labels(path=path).inc()


def _counts(family: str, label: str = "kernel") -> Dict[str, float]:
    values = get_registry().snapshot().get(family, {}).get("values", [])
    return {row["labels"][label]: row["value"] for row in values}


def fallback_counts() -> Dict[str, float]:
    """``{kernel: count}`` of every ``paddle_tpu_kernel_fallbacks_total``
    series that has counted (they count only under ``FLAGS_enable_metrics``)."""
    return _counts("paddle_tpu_kernel_fallbacks_total")


def loss_backward_counts() -> Dict[str, float]:
    """``{path: count}`` of ``paddle_tpu_fused_loss_backward_built_total``."""
    return _counts("paddle_tpu_fused_loss_backward_built_total", "path")


def partition_routed_counts() -> Dict[str, float]:
    """``{kernel: count}`` of ``paddle_tpu_kernel_partition_routed_total``."""
    return _counts("paddle_tpu_kernel_partition_routed_total")
