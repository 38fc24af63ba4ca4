"""Kernel-path selection: one place deciding Pallas vs XLA-fallback.

A dispatch site's ``try``/``except`` only sees trace-time failures: a kernel
that traces but does not lower or compile fails inside the captured step,
where nothing can catch it. Kernels on the main paths are therefore REQUIRED
to compile on TPU — tests/test_tpu_aot_compile.py compiles each for a
described chip at real widths."""

from __future__ import annotations

import contextlib
import logging
import sys
import threading
from typing import Any, Dict, Iterator, List

import jax

from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.observability import get_registry

_logger = logging.getLogger("paddle_tpu.kernels")
_warned: set = set()
_fallbacks_total = get_registry().counter(
    "paddle_tpu_kernel_fallbacks_total",
    "Pallas kernel failures that degraded to the XLA fallback path, by kernel.",
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


_GSPMD = threading.local()


@contextlib.contextmanager
def gspmd_trace(active: bool) -> Iterator[None]:
    """Mark the traces started under this context as partitioned by GSPMD
    (their arguments span more than one device). ``jit.to_static`` arms it
    around a first call; the serving engine's armed tp mesh counts too."""
    prev = getattr(_GSPMD, "active", False)
    _GSPMD.active = active
    try:
        yield
    finally:
        _GSPMD.active = prev


def _gspmd_partitioned() -> bool:
    if getattr(_GSPMD, "active", False):
        return True
    # sys.modules gate: the single-chip path never imports the distributed package
    tp = sys.modules.get("paddle_tpu.distributed.tp")
    return tp is not None and tp.current_tp_mesh() is not None


def pallas_enabled(flag: str, shard_mapped: bool = False) -> bool:
    """Flag on, running on a TPU backend, and in a trace the kernel can live
    in: a ``pallas_call`` has no GSPMD partitioning rule (Mosaic refuses at
    lowering, inside the captured step, where nothing can catch it), so under
    a multi-device trace only a site that wraps its kernel in ``shard_map``
    itself (``shard_mapped=True``) takes the Pallas path; the others run
    their XLA composition, which GSPMD splits."""
    if not (_cached_flag(flag) and jax.default_backend() == "tpu"):
        return False
    return shard_mapped or not _gspmd_partitioned()


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


def fallback_counts() -> Dict[str, float]:
    """``{kernel: count}`` of every ``paddle_tpu_kernel_fallbacks_total``
    series that has counted (they count only under ``FLAGS_enable_metrics``)."""
    family = get_registry().snapshot().get("paddle_tpu_kernel_fallbacks_total", {})
    return {row["labels"]["kernel"]: row["value"] for row in family.get("values", [])}
