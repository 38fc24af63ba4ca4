"""Fused linear + softmax-cross-entropy loss head, vocab-chunked.

The training loss head is the largest single item of a causal-LM step. The
plain composition materializes ``[B·S, V]`` logits, upcasts them to fp32 and
lets ``log_softmax`` allocate a third buffer, and keeps the logits for its
backward. This module computes ``cross_entropy(x @ Wᵀ, labels)`` in the style
of flash attention's online softmax, and holds at most ONE ``[N, V]`` array,
in the operand dtype, for the length of its backward:

- **forward** streams vocab blocks of ``x @ W_blockᵀ`` through VMEM keeping a
  per-token online max/sum (fp32) plus the target-class logit (gathered per
  block; ``ignore_index`` rows simply never match), then finishes with
  ``loss = logsumexp - target_logit`` reduced exactly like
  ``F.cross_entropy`` (mean over non-ignored tokens, ``max(count, 1)``); it
  leaves the ``[N]`` logsumexp behind and no logits;
- **backward** runs two Pallas kernels — the flash-attn-2 dq/dkv split, so
  each output is only ever revisited on consecutive grid steps. dX recomputes
  each block's logits from x and W and the saved logsumexp, forms
  ``d = (softmax - onehot) * dloss`` in the operand dtype, accumulates
  ``d @ W`` over vocab blocks, and WRITES the ``d`` tile to an ``[N, V]``
  array; dW reads it and is one matmul, ``xᵀ d`` accumulated over row blocks.
  What a buffer costs against what it saves, by the chip's own numbers (v5e,
  16 k tokens, 32 k vocabulary, ``H`` 4096, bf16): 1.07 GB written once and
  read once is 2.6 ms at 819 GB/s; the ``[N, H] x [H, V]`` matmul that forming
  ``d`` again costs is 4.4 TFLOP, 22.3 ms at 197 TFLOP/s. A logit is ``2·H``
  flop to recompute and 4 bytes of traffic (~960 flop at the ridge of 240
  flop a byte) to store: past ``H`` of a few hundred storing wins, and what
  recomputing buys is capacity, not time. So the choice is made from the
  capacity: where ``d`` would take more than an eighth of the device's memory
  (``_stores_d``: 16 k rows x 128 k columns of float32 are 8.6 GB), dX stores
  nothing and dW recomputes the block's ``d`` as dX does, a second matmul.
  ``d`` is the same array either way (one un-tiled contraction over ``H``,
  rounded to the operand dtype before either product reads it), so the two
  pairs agree bit for bit. Both sums stay in float32 VMEM scratch and leave
  their kernel once, in the operand dtype (no float32 ``[N, H]`` / ``[H, V]``
  in HBM). Which pair a step holds is counted at trace time in
  ``paddle_tpu_fused_loss_backward_built_total{path="stored"|"recomputed"}``;
- a ``lax.scan``-over-vocab-chunks reference with the SAME custom-VJP
  decomposition (pure jnp; ``d`` formed once a chunk) runs on CPU / in tier-1
  / as the fallback, so the numerics are pinned off-TPU. (Differentiating
  *through* a scan would stash every chunk's float32 logits, hence the custom
  VJP on both paths.)

Weight layouts: ``vocab_major=False`` is ``nn.Linear`` 's ``[H, V]``
(untied lm_head); ``vocab_major=True`` is the embedding's ``[V, H]``
(tied lm_head, the ``matmul(out, embed.weight, transpose_y=True)`` branch).
Both fuse without a transpose — only BlockSpec index maps and dot dims
change.

Selection: ``FLAGS_use_fused_loss`` + TPU backend picks the Pallas kernels.
Each kernel's (row block, vocab block) comes from the call's shapes
(``_block_geometry``: the kept block sets how often the other operand is
re-read from HBM, so it is as large as the chip's VMEM takes, and the kernel
asks Mosaic for that VMEM through ``vmem_limit_bytes``); ``kernels/autotune.py``
may time the tiles the geometry admits instead. Any
Pallas failure falls back to the scan reference through
``kernels.select.warn_fallback`` (counted in
``paddle_tpu_kernel_fallbacks_total``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.select import count_loss_backward, pallas_enabled, warn_fallback

__all__ = ["fused_linear_cross_entropy"]

NEG_INF = -1e30
# pallas_call name= of each kernel here: what a device trace calls it (stable, no shapes)
KERNEL_FWD = "fused_loss_fwd"
KERNEL_DX = "fused_loss_dx"
KERNEL_DW = "fused_loss_dw"
KERNEL_FWD_QUANT = "fused_loss_fwd_quant"
_REF_BLOCK = 512  # scan-reference vocab chunk; any value works, numerics-pinning only


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --------------------------------------------------------------------------
# shared custom-VJP shell: epilogue (reduction) + per-row grad coefficient
# --------------------------------------------------------------------------


def _build_core(engine_fwd, engine_bwd, ignore_index, reduction):
    """Wrap a (fwd, bwd) engine pair in the custom VJP both paths share.

    Engine contract (all row-count-N arrays are 1-D f32 unless noted):
    ``engine_fwd(x2, wp, lab) -> (lse, target_logit)`` and
    ``engine_bwd(x2, wp, lab, lse, gcoef) -> (dx, dw)`` with ``dx`` in
    ``x2.dtype`` ``[N, H]`` and ``dw`` in ``wp``'s dtype and layout. The
    shell owns the reduction semantics (identical to ``F.cross_entropy``)
    and the ``ignore_index`` masking, so the Pallas and scan paths cannot
    drift apart on them.
    """

    def _loss(per, valid):
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            return jnp.sum(per) / denom
        if reduction == "sum":
            return jnp.sum(per)
        return per

    @jax.custom_vjp
    def core(x2, wp, lab):
        lse, tl = engine_fwd(x2, wp, lab)
        valid = lab != ignore_index
        return _loss(jnp.where(valid, lse - tl, 0.0), valid)

    def core_fwd(x2, wp, lab):
        lse, tl = engine_fwd(x2, wp, lab)
        valid = lab != ignore_index
        loss = _loss(jnp.where(valid, lse - tl, 0.0), valid)
        # residuals: inputs + the [N] logsumexp only — never [N, V]
        return loss, (x2, wp, lab, lse)

    def core_bwd(res, g):
        x2, wp, lab, lse = res
        valid = lab != ignore_index
        g = g.astype(jnp.float32)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            g_row = (g / denom) * jnp.ones_like(lse)
        elif reduction == "sum":
            g_row = g * jnp.ones_like(lse)
        else:
            g_row = g  # [N] cotangent for reduction="none"
        gcoef = jnp.where(valid, g_row, 0.0)
        dx, dw = engine_bwd(x2, wp, lab, lse, gcoef)
        # integer labels carry no gradient (float0 cotangent)
        return dx, dw, np.zeros(lab.shape, jax.dtypes.float0)

    core.defvjp(core_fwd, core_bwd)
    return core


# --------------------------------------------------------------------------
# lax.scan reference engine (CPU / tier-1 / fallback)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_ref_core(v, h, blk, ignore_index, reduction):
    """Pure-jnp engines over vocab-major padded weights ``[nv*blk, H]``."""
    nv = (v + blk - 1) // blk

    def engine_fwd(x2, wp, lab):
        wb = wp.reshape(nv, blk, h)
        cols0 = jnp.arange(blk)
        n = x2.shape[0]

        def step(carry, inp):
            m, l, tl = carry
            wj, j = inp
            logits = jax.lax.dot_general(
                x2, wj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [N, blk]
            cols = j * blk + cols0
            logits = jnp.where((cols < v)[None, :], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            l_new = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(axis=-1)
            tl_new = tl + jnp.where(cols[None, :] == lab[:, None], logits, 0.0).sum(axis=-1)
            return (m_new, l_new, tl_new), None

        init = (
            jnp.full((n,), NEG_INF, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32),
        )
        (m, l, tl), _ = jax.lax.scan(step, init, (wb, jnp.arange(nv)))
        return m + jnp.log(l), tl

    def engine_bwd(x2, wp, lab, lse, gcoef):
        wb = wp.reshape(nv, blk, h)
        cols0 = jnp.arange(blk)

        def step(dx, inp):
            wj, j = inp
            logits = jax.lax.dot_general(
                x2, wj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            cols = j * blk + cols0
            p = jnp.exp(logits - lse[:, None])
            p = jnp.where((cols < v)[None, :], p, 0.0)  # zero-padded W rows: kill exp(-lse)
            onehot = (cols[None, :] == lab[:, None]).astype(jnp.float32)
            d = ((p - onehot) * gcoef[:, None]).astype(x2.dtype)
            dx = dx + jax.lax.dot_general(
                d, wj, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dwj = jax.lax.dot_general(
                d, x2, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return dx, dwj.astype(wp.dtype)

        dx, dwb = jax.lax.scan(
            step, jnp.zeros((x2.shape[0], h), jnp.float32), (wb, jnp.arange(nv))
        )
        return dx.astype(x2.dtype), dwb.reshape(nv * blk, h)

    return _build_core(engine_fwd, engine_bwd, ignore_index, reduction)


def _reference_path(x2, w, lab, *, v, h, ignore_index, reduction, vocab_major):
    # canonicalize to vocab-major [V, H] + zero-pad the ragged tail; both ops
    # sit OUTSIDE the custom VJP so their transposes run in reverse for dW
    wc = w if vocab_major else jnp.swapaxes(w, 0, 1)
    vp = _round_up(v, _REF_BLOCK)
    wp = jnp.pad(wc, ((0, vp - v), (0, 0))) if vp > v else wc
    core = _make_ref_core(v, h, _REF_BLOCK, ignore_index, reduction)
    return core(x2, wp, lab)


# --------------------------------------------------------------------------
# weight-only int8 lm-head variant (inference-only: no VJP)
# --------------------------------------------------------------------------


def _quant_epilogue(lse, tl, lab, ignore_index, reduction):
    """Same reduction semantics as ``_build_core``'s shell — duplicated here
    because the quantized walk is forward-only (weight-only int8 is an
    inference feature; nothing differentiates through an int8 weight)."""
    valid = lab != ignore_index
    per = jnp.where(valid, lse - tl, 0.0)
    if reduction == "mean":
        denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return jnp.sum(per) / denom
    if reduction == "sum":
        return jnp.sum(per)
    return per


def _reference_quant_path(x2, w, scale, lab, *, v, h, ignore_index, reduction, vocab_major):
    """Scan walk over int8 vocab chunks, dequantizing each chunk's LOGITS
    (``(x @ w8ᵀ) * scale_col`` — the per-output-channel scale factors out of
    the contraction, same canonical composition as ``kernels.quant``). The
    dequantized weight is never materialized."""
    wc = w if vocab_major else jnp.swapaxes(w, 0, 1)  # [V, H] int8
    vp = _round_up(v, _REF_BLOCK)
    sp = scale.astype(jnp.float32)
    if vp > v:
        wc = jnp.pad(wc, ((0, vp - v), (0, 0)))
        sp = jnp.pad(sp, (0, vp - v))
    nv = vp // _REF_BLOCK
    wb = wc.reshape(nv, _REF_BLOCK, h)
    sb = sp.reshape(nv, _REF_BLOCK)
    cols0 = jnp.arange(_REF_BLOCK)
    n = x2.shape[0]
    xf = x2.astype(jnp.float32)

    def step(carry, inp):
        m, l, tl = carry
        wj, sj, j = inp
        logits = jax.lax.dot_general(
            xf, wj.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sj[None, :]
        cols = j * _REF_BLOCK + cols0
        logits = jnp.where((cols < v)[None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l_new = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        tl_new = tl + jnp.where(cols[None, :] == lab[:, None], logits, 0.0).sum(axis=-1)
        return (m_new, l_new, tl_new), None

    init = (
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (m, l, tl), _ = jax.lax.scan(step, init, (wb, sb, jnp.arange(nv)))
    return _quant_epilogue(m + jnp.log(l), tl, lab, ignore_index, reduction)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------


def _flxent_fwd_kernel(x_ref, w_ref, lab_ref, *rest, v, blk_v, vocab_major, quantized=False):
    if quantized:
        s_ref, m_ref, l_ref, tl_ref = rest
    else:
        (m_ref, l_ref, tl_ref), s_ref = rest, None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        tl_ref[...] = jnp.zeros_like(tl_ref[...])

    x = x_ref[...]  # [blk_rows, H] native dtype — bf16 MXU, fp32 accumulation
    w = w_ref[...]
    if quantized:  # int8 weight block: upcast for the dot, scale the logits
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
    if vocab_major:  # w [blk_v, H]
        logits = jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # w [H, blk_v]
        logits = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    if s_ref is not None:
        # per-output-channel dequant factors out of the contraction: scaling
        # the logits column equals dequantizing the whole weight column
        logits = logits * s_ref[...].astype(jnp.float32)  # [1, blk_v] broadcast
    cols = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, (1, blk_v), 1)
    logits = jnp.where(cols < v, logits, NEG_INF)
    m = m_ref[...]  # [blk_rows, 1]
    m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(jnp.exp(logits - m_new), axis=-1, keepdims=True)
    m_ref[...] = m_new
    # target-class logit: ignore_index (< 0) never matches a column
    tl_ref[...] += jnp.sum(jnp.where(cols == lab_ref[...], logits, 0.0), axis=-1, keepdims=True)


def _flxent_block_d(x_ref, w_ref, lab_ref, lse_ref, gc_ref, j, *, v, blk_v, vocab_major):
    """Recompute one block's ``(softmax - onehot) * gcoef`` from the saved
    logsumexp — shared by the dX and dW kernels."""
    x = x_ref[...]
    w = w_ref[...]
    if vocab_major:
        logits = jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:
        logits = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    cols = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, (1, blk_v), 1)
    p = jnp.exp(logits - lse_ref[...])
    p = jnp.where(cols < v, p, 0.0)  # zero-padded W rows: kill exp(-lse)
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    return ((p - onehot) * gc_ref[...]).astype(x.dtype)


def _dx_step(x_ref, w_ref, lab_ref, lse_ref, gc_ref, dx_ref, d_ref, acc_ref, v, blk_v, vocab_major):
    """One (row block, vocab block) of dX; the block's ``d`` goes to ``d_ref``
    where there is one (block (i, j): written once, never revisited)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    d = _flxent_block_d(
        x_ref, w_ref, lab_ref, lse_ref, gc_ref, j, v=v, blk_v=blk_v, vocab_major=vocab_major
    )
    if d_ref is not None:
        d_ref[...] = d
    w = w_ref[...]
    if vocab_major:  # d [br, bv] @ w [bv, H]
        acc_ref[...] += jax.lax.dot_general(
            d, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # d [br, bv] @ w [H, bv]ᵀ
        acc_ref[...] += jax.lax.dot_general(
            d, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    # the float32 sum stays in VMEM; HBM sees dX once, in the operand dtype
    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _flxent_dx_kernel(
    x_ref, w_ref, lab_ref, lse_ref, gc_ref, dx_ref, acc_ref, *, v, blk_v, vocab_major
):
    _dx_step(x_ref, w_ref, lab_ref, lse_ref, gc_ref, dx_ref, None, acc_ref, v, blk_v, vocab_major)


def _flxent_dx_store_kernel(
    x_ref, w_ref, lab_ref, lse_ref, gc_ref, dx_ref, d_ref, acc_ref, *, v, blk_v, vocab_major
):
    _dx_step(x_ref, w_ref, lab_ref, lse_ref, gc_ref, dx_ref, d_ref, acc_ref, v, blk_v, vocab_major)


def _dw_step(x_ref, d, dw_ref, acc_ref, vocab_major):
    """One (vocab block, row block) of dW from the block's ``d``."""
    i = pl.program_id(1)  # row block (inner, sequential accumulation)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    x = x_ref[...]
    if vocab_major:  # dᵀ [bv, br] @ x [br, H]
        acc_ref[...] += jax.lax.dot_general(
            d, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # xᵀ [H, br] @ d [br, bv]
        acc_ref[...] += jax.lax.dot_general(
            x, d, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == pl.num_programs(1) - 1)
    def _store():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _flxent_dw_kernel(x_ref, d_ref, dw_ref, acc_ref, *, vocab_major):
    """dW over the ``d`` that dX stored: one matmul."""
    _dw_step(x_ref, d_ref[...], dw_ref, acc_ref, vocab_major)


def _flxent_dw_recompute_kernel(
    x_ref, w_ref, lab_ref, lse_ref, gc_ref, dw_ref, acc_ref, *, v, blk_v, vocab_major
):
    """dW where ``d`` was not stored: the block's logits again, a second matmul."""
    j = pl.program_id(0)  # vocab block (outer, parallel)
    d = _flxent_block_d(
        x_ref, w_ref, lab_ref, lse_ref, gc_ref, j, v=v, blk_v=blk_v, vocab_major=vocab_major
    )
    _dw_step(x_ref, d, dw_ref, acc_ref, vocab_major)


# --------------------------------------------------------------------------
# block geometry: each kernel's tile, and the VMEM it asks for, from the shapes
# --------------------------------------------------------------------------


class LossTiles(NamedTuple):
    """(row block, vocab block) of each kernel's grid step."""

    fwd: Tuple[int, int]
    dx: Tuple[int, int]
    dw: Tuple[int, int]


# (rows, vocab columns) each kernel takes where VMEM allows: past these the
# chip gains nothing (tools/loss_head_bench.py at the train cell's shapes,
# PERF.md, PR 30 and PR 37). Forward and dX keep a ROW block of x in VMEM and
# stream W past it, dW keeps a VOCAB block of its sum and streams x: the kept
# side is how often the other operand is read from HBM (128 rows: W 128 times
# a call, and the forward HBM-bound at 44 % of the MXU's peak; 512: 32 times,
# 90 %). The forward's online softmax pays per grid step, so its vocab block
# is wider. "dw" reads the d that dX stored; "dw_recompute" forms it again
# from W and holds a weight block and the float32 logits tiles besides.
_TILES = {
    "fwd": (512, 1024), "fwd_quant": (512, 1024), "dx": (512, 512),
    "dw": (512, 1024), "dw_recompute": (512, 512),
}  # by ``_vmem_need``'s name of the kernel
_SCOPED_VMEM_DEFAULT = 16 << 20  # what Mosaic gives a kernel that states no limit
_VMEM_OFF_CHIP = 128 << 20  # v5e's, for a trace with no TPU behind it (interpret mode, a described chip)
_HBM_OFF_CHIP = 16 << 30  # v5e's, likewise


def _vmem_capacity() -> int:
    """Bytes of VMEM a core of the chip has (not the 16 MiB a kernel gets by default)."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except Exception:  # noqa: BLE001 - no TPU here
        return _VMEM_OFF_CHIP


def _hbm_capacity() -> int:
    """Bytes of device memory the chip has."""
    try:
        return int(pltpu.get_tpu_info().hbm_capacity_bytes)
    except Exception:  # noqa: BLE001 - no TPU here
        return _HBM_OFF_CHIP


def _stores_d(n: int, v: int, item: int) -> bool:
    """Whether the backward forms ``d`` once and keeps it, ``[n, v]`` in the
    operand dtype, from dX to dW: where that takes at most an eighth of the
    device's memory (by the call's own rows and vocabulary, which the tiles
    are chosen from too; padding adds under a tile a side). ``d`` lives at the
    start of the backward pass, when every activation of the step is still
    held, so it gets a share and not what is free; past the share (16 k rows x
    128 k columns of float32 are 8.6 GB) dW recomputes it, which costs a
    matmul and no memory."""
    return n * v * item <= _hbm_capacity() // 8


def _dw_model(n: int, v: int, item: int) -> str:
    """``_vmem_need``'s and ``_TILES``' name of the dW that will run."""
    return "dw" if _stores_d(n, v, item) else "dw_recompute"


def _vmem_need(kernel: str, br: int, bv: int, h: int, x_item: int, w_item: int) -> int:
    """Bytes of VMEM ``kernel`` takes at a ``br`` x ``bv`` tile, as the chip's
    compiler counts them (fitted to its refusals at 24 tiles of the train
    cell's shapes, within -8 .. +25 %, plus an eighth; tests/test_tpu_aot_compile.py
    holds it to that): every blocked operand double-buffered, whole-``h`` rows of
    x and columns of W, the ``[br, 1]`` columns padded to 128 lanes, the float32
    accumulator of dX / dW with the product that is added to it, the output
    block in the operand dtype (dX's ``d`` tile too), and three float32 tiles
    of logits where the kernel forms them. The dX that stores ``d`` and the
    one-matmul dW (PR 37) were fitted the same way, by a binary search over
    ``vmem_limit_bytes`` at seven shapes (both layouts, float32, hidden 2048
    to 8192): the model reads 1.31-1.49 x the compiler's count for dX and
    1.21-1.32 x for dW, never under it."""
    x = 2 * br * h * x_item
    w = 2 * h * bv * w_item
    tile = 3 * br * bv * 4
    col = 2 * br * 128 * 4
    d = 2 * br * bv * x_item
    if kernel == "fwd":
        need = x + w + 4 * col + tile
    elif kernel == "fwd_quant":  # both operands are upcast to float32 for the dot
        need = x + w + 4 * col + tile + (br * h * 4 if x_item < 4 else 0) + h * bv * 4
    elif kernel == "dx":
        need = x + w + 3 * col + tile + 2 * br * h * 4 + 2 * br * h * x_item + d
    elif kernel == "dw":  # a float32 copy of the streamed block, transposed for the dot
        need = x + br * h * 4 + d + h * bv * 4 + 2 * h * bv * w_item
    else:  # dw_recompute
        need = x + w + 3 * col + tile + 2 * h * bv * 4 + 2 * h * bv * w_item
    return need + need // 8 + (2 << 20)


def _vmem_budget() -> int:
    return _vmem_capacity() * 3 // 4  # the rest is the compiler's own


def _fit(size: int, cap: int, unit: int) -> Tuple[int, int]:
    """``(block, blocks)`` for a dimension of ``size``: the largest multiple of
    ``unit`` between ``cap / 2`` and ``cap`` that divides it (no padding, so no
    copy of the operand), else the fewest blocks of at most ``cap``, cut evenly:
    32000 -> 125 x 256 under a cap of 512, 2100 rows -> 5 x 432 (not 4 x 512 + 52)."""
    if size <= cap:
        return _round_up(size, unit), 1
    for c in range(cap - cap % unit, cap // 2 - 1, -unit):
        if size % c == 0:
            return c, size // c
    blocks = -(-size // cap)
    return _round_up(-(-size // blocks), unit), blocks


def _grow(block: int, blocks: int, cap: int) -> int:
    """The most whole ``block``s a kernel with room for ``cap`` takes at once,
    as a divisor of their count: every kernel's tile divides the padded size."""
    return block * max(m for m in range(1, max(cap // block, 1) + 1) if blocks % m == 0)


def _block_geometry(
    n: int, v: int, h: int, x_item: int, w_item: int, quantized: bool = False
) -> LossTiles:
    """Each kernel's (row block, vocab block) from the call's shapes and the
    operands' item sizes: ``_TILES`` where the chip's VMEM (three quarters of
    it) takes that, else halved, the streamed side first (down to 256: it
    costs grid steps only), then the kept side, whose size is the flops a
    streamed byte buys. The rows are then cut into the fewest even blocks and
    the vocabulary into blocks that divide it where some do (``_fit``), shared
    by the three kernels; a kernel with room for more takes whole multiples
    (``_grow``). The kernels ask Mosaic for the VMEM their tile needs
    (``_vmem_need`` -> ``vmem_limit_bytes``): its 16 MiB default, taken as the
    budget, left 128 x 128 at every ``h >= 4096``."""
    budget = _vmem_budget()
    models = ("fwd_quant" if quantized else "fwd", "dx", _dw_model(n, v, x_item))
    caps = {}
    for kernel, model in zip(LossTiles._fields, models):
        tile = list(_TILES[model])
        kept = 1 if kernel == "dw" else 0  # dW keeps vocab columns, the others rows
        while _vmem_need(model, *tile, h, x_item, w_item) > budget and tile != [128, 128]:
            tile[1 - kept if tile[1 - kept] > 256 or tile[kept] == 128 else kept] //= 2
        caps[kernel] = tuple(tile)
    rows, row_blocks = _fit(n, min(br for br, _ in caps.values()), 16)
    cols, col_blocks = _fit(v, min(bv for _, bv in caps.values()), 128)
    return LossTiles(
        *((_grow(rows, row_blocks, br), _grow(cols, col_blocks, bv)) for br, bv in caps.values())
    )


def _admitted_tiles(h, x_item, w_item, dw):
    """Uniform tiles every kernel has the VMEM for (``dw``: the dW that will
    run, ``_vmem_need``'s name for it): the autotuner's candidates."""
    budget = _vmem_budget()
    return [
        LossTiles(*((br, bv),) * 3)
        for br in (256, 512, 1024)
        for bv in (256, 512, 1024)
        if all(_vmem_need(k, br, bv, h, x_item, w_item) <= budget for k in ("fwd", "dx", dw))
    ]


def _as_tiles(block) -> LossTiles:
    if len(block) == 2 and isinstance(block[0], int):
        return LossTiles(*((int(block[0]), int(block[1])),) * 3)
    return LossTiles(*((int(br), int(bv)) for br, bv in block))


def _fit_tiles(block, n: int, v: int):
    """``(tiles, n_pad, vp)``: a small batch is one row block in every kernel,
    and the operands are padded once, to a size every kernel's tile divides."""
    one = _round_up(n, 16)
    tiles = LossTiles(*((min(br, one), bv) for br, bv in _as_tiles(block)))
    n_pad = _round_up(n, math.lcm(*(br for br, _ in tiles)))
    vp = _round_up(v, math.lcm(*(bv for _, bv in tiles)))
    return tiles, n_pad, vp


def _params(need: int):
    """Compiler params of a kernel whose tile takes ``need`` bytes of VMEM:
    where that is over Mosaic's default scoped limit, the kernel asks for it."""
    return pltpu.CompilerParams(
        # the outer grid dimension's blocks are independent (megacore-
        # splittable); the inner one accumulates (the online softmax state, dX
        # over vocab blocks, dW over row blocks) and MUST run sequentially
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=need if need > _SCOPED_VMEM_DEFAULT else None,
    )


def _w_spec(h, blk_v, vocab_major, vocab_axis):
    """The weight's (or dW's) block, vocab block index on grid axis ``vocab_axis``."""
    if vocab_major:
        return pl.BlockSpec((blk_v, h), lambda *g: (g[vocab_axis], 0))
    return pl.BlockSpec((h, blk_v), lambda *g: (0, g[vocab_axis]))


def _run_dx(x2, wp, cols, *, v, tile, vocab_major, interpret, store_d):
    """``(dX, d)`` over padded operands: ``d`` is the ``[n_pad, vp]`` block
    gradients the kernel formed on its way, or None where it stores none;
    ``cols`` are the ``[n_pad, 1]`` labels, logsumexp and grad coefficient. Grid (row blocks, vocab blocks): a
    row block of x and its float32 dX sum stay in VMEM while W streams past."""
    n_pad, h = x2.shape
    vp = wp.shape[0] if vocab_major else wp.shape[1]
    br, bv = tile
    params = _params(_vmem_need("dx", br, bv, h, x2.dtype.itemsize, wp.dtype.itemsize))
    col = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    row = pl.BlockSpec((br, h), lambda i, j: (i, 0))
    w_spec = _w_spec(h, bv, vocab_major, 1)
    if not store_d:
        dx = pl.pallas_call(
            functools.partial(_flxent_dx_kernel, v=v, blk_v=bv, vocab_major=vocab_major),
            grid=(n_pad // br, vp // bv),
            compiler_params=params,
            in_specs=[row, w_spec, col, col, col],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((n_pad, h), x2.dtype),
            scratch_shapes=[pltpu.VMEM((br, h), jnp.float32)],
            interpret=interpret,
            name=KERNEL_DX,
        )(x2, wp, *cols)
        return dx, None
    dx, d = pl.pallas_call(
        functools.partial(_flxent_dx_store_kernel, v=v, blk_v=bv, vocab_major=vocab_major),
        grid=(n_pad // br, vp // bv),
        compiler_params=params,
        in_specs=[row, w_spec, col, col, col],
        out_specs=[row, pl.BlockSpec((br, bv), lambda i, j: (i, j))],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), x2.dtype),
            jax.ShapeDtypeStruct((n_pad, vp), x2.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((br, h), jnp.float32)],
        interpret=interpret,
        name=KERNEL_DX,
    )(x2, wp, *cols)
    return dx, d


def _run_dw(x2, wp, cols, d, *, v, tile, vocab_major, interpret):
    """dW in the padded weight's shape and dtype, from the ``d`` that
    ``_run_dx`` stored, or with ``d`` None from ``wp`` and ``cols`` again. Grid
    (vocab blocks, row blocks), the accumulation innermost (an output block may
    only be revisited on consecutive grid steps): a vocab block's float32 dW
    sum stays in VMEM while x (and ``d``) stream past."""
    n_pad, h = x2.shape
    vp = wp.shape[0] if vocab_major else wp.shape[1]
    br, bv = tile
    sizes = (br, bv, h, x2.dtype.itemsize, wp.dtype.itemsize)
    x_spec = pl.BlockSpec((br, h), lambda j, i: (i, 0))
    w_spec = _w_spec(h, bv, vocab_major, 0)
    acc = pltpu.VMEM((bv, h) if vocab_major else (h, bv), jnp.float32)
    if d is not None:
        return pl.pallas_call(
            functools.partial(_flxent_dw_kernel, vocab_major=vocab_major),
            grid=(vp // bv, n_pad // br),
            compiler_params=_params(_vmem_need("dw", *sizes)),
            in_specs=[x_spec, pl.BlockSpec((br, bv), lambda j, i: (i, j))],
            out_specs=w_spec,
            out_shape=jax.ShapeDtypeStruct(wp.shape, wp.dtype),
            scratch_shapes=[acc],
            interpret=interpret,
            name=KERNEL_DW,
        )(x2, d)
    col = pl.BlockSpec((br, 1), lambda j, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_flxent_dw_recompute_kernel, v=v, blk_v=bv, vocab_major=vocab_major),
        grid=(vp // bv, n_pad // br),
        compiler_params=_params(_vmem_need("dw_recompute", *sizes)),
        in_specs=[x_spec, w_spec, col, col, col],
        out_specs=w_spec,
        out_shape=jax.ShapeDtypeStruct(wp.shape, wp.dtype),
        scratch_shapes=[acc],
        interpret=interpret,
        name=KERNEL_DW,
    )(x2, wp, *cols)


def _pallas_engines(n_pad, v, vp, h, tiles, vocab_major, interpret, store_d):
    """``(engine_fwd, engine_bwd)`` over padded operands (``_build_core``'s
    contract). Forward and dX grid (row blocks, vocab blocks): a row block of x
    stays in VMEM while the weight streams past it, so the ROW block sets how
    often W is read from HBM; dW grids the other way round (``_run_dw``). With
    ``store_d`` each block's ``d`` is formed once, by dX, and dW is one matmul
    over it; without, dW forms it again."""

    def engine_fwd(x2, wp, lab):
        br, bv = tiles.fwd
        col = pl.BlockSpec((br, 1), lambda i, j: (i, 0))  # lab / m / l / tl
        m, l, tl = pl.pallas_call(
            functools.partial(_flxent_fwd_kernel, v=v, blk_v=bv, vocab_major=vocab_major),
            grid=(n_pad // br, vp // bv),
            compiler_params=_params(
                _vmem_need("fwd", br, bv, h, x2.dtype.itemsize, wp.dtype.itemsize)
            ),
            in_specs=[
                pl.BlockSpec((br, h), lambda i, j: (i, 0)),
                _w_spec(h, bv, vocab_major, 1),
                col,
            ],
            out_specs=[col, col, col],
            out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)] * 3,
            interpret=interpret,
            name=KERNEL_FWD,
        )(x2, wp, lab.reshape(n_pad, 1))
        return (m + jnp.log(l))[:, 0], tl[:, 0]

    def engine_bwd(x2, wp, lab, lse, gcoef):
        count_loss_backward("stored" if store_d else "recomputed")
        cols = (lab.reshape(n_pad, 1), lse.reshape(n_pad, 1), gcoef.reshape(n_pad, 1))
        kw = dict(v=v, vocab_major=vocab_major, interpret=interpret)
        dx, d = _run_dx(x2, wp, cols, tile=tiles.dx, store_d=store_d, **kw)
        return dx, _run_dw(x2, wp, cols, d, tile=tiles.dw, **kw)

    return engine_fwd, engine_bwd


@functools.lru_cache(maxsize=None)
def _make_pallas_core(
    n_pad, v, vp, h, tiles, vocab_major, interpret, store_d, ignore_index, reduction
):
    return _build_core(
        *_pallas_engines(n_pad, v, vp, h, tiles, vocab_major, interpret, store_d),
        ignore_index, reduction,
    )


def _pad_operands(x2, w, lab, n_pad, vp, ignore_index, vocab_major):
    """Rows padded with ``ignore_index`` labels, the vocabulary with zero
    weights (the kernels mask columns past ``v``)."""
    n = x2.shape[0]
    v = w.shape[0] if vocab_major else w.shape[1]
    if n_pad > n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
        lab = jnp.pad(lab, (0, n_pad - n), constant_values=ignore_index)
    if vp > v:
        w = jnp.pad(w, ((0, vp - v), (0, 0)) if vocab_major else ((0, 0), (0, vp - v)))
    return x2, w, lab


def _pallas_path(x2, w, lab, *, v, h, ignore_index, reduction, vocab_major, interpret, block):
    n = x2.shape[0]
    tiles, n_pad, vp = _fit_tiles(block, n, v)
    # padding / layout prep sits OUTSIDE the custom VJP: its transpose rules
    # slice dX and dW back to the caller's shapes automatically
    x2p, wp, labp = _pad_operands(x2, w, lab, n_pad, vp, ignore_index, vocab_major)
    core = _make_pallas_core(
        n_pad, v, vp, h, tiles, vocab_major, interpret,
        _stores_d(n, v, x2.dtype.itemsize), ignore_index, reduction,
    )
    loss = core(x2p, wp, labp)
    if reduction == "none":
        loss = loss[:n]
    return loss


@functools.lru_cache(maxsize=None)
def _make_pallas_quant_fwd(n_pad, v, vp, h, blk_rows, blk_v, vocab_major, interpret):
    """Forward-only quantized engine: the fwd kernel with a scale input."""
    col_spec = pl.BlockSpec((blk_rows, 1), lambda i, j: (i, 0))

    def engine_fwd(x2, wp, sp, lab):
        m, l, tl = pl.pallas_call(
            functools.partial(
                _flxent_fwd_kernel, v=v, blk_v=blk_v, vocab_major=vocab_major,
                quantized=True,
            ),
            grid=(n_pad // blk_rows, vp // blk_v),
            compiler_params=_params(
                _vmem_need("fwd_quant", blk_rows, blk_v, h, x2.dtype.itemsize, wp.dtype.itemsize)
            ),
            in_specs=[
                pl.BlockSpec((blk_rows, h), lambda i, j: (i, 0)),
                _w_spec(h, blk_v, vocab_major, 1),
                col_spec,
                pl.BlockSpec((1, blk_v), lambda i, j: (0, j)),
            ],
            out_specs=[col_spec, col_spec, col_spec],
            out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)] * 3,
            interpret=interpret,
            name=KERNEL_FWD_QUANT,
        )(x2, wp, lab.reshape(n_pad, 1), sp.reshape(1, vp))
        return (m + jnp.log(l))[:, 0], tl[:, 0]

    return engine_fwd


def _pallas_quant_path(
    x2, w, scale, lab, *, v, h, ignore_index, reduction, vocab_major, interpret, block
):
    n = x2.shape[0]
    tiles, n_pad, vp = _fit_tiles(block, n, v)
    x2p, wp, labp = _pad_operands(x2, w, lab, n_pad, vp, ignore_index, vocab_major)
    sp = scale.astype(jnp.float32)
    if vp > v:
        sp = jnp.pad(sp, (0, vp - v))
    engine = _make_pallas_quant_fwd(n_pad, v, vp, h, *tiles.fwd, vocab_major, interpret)
    lse, tl = engine(x2p, wp, sp, labp)
    loss = _quant_epilogue(lse, tl, labp, ignore_index, reduction)
    if reduction == "none":
        loss = loss[:n]
    return loss


# --------------------------------------------------------------------------
# block-size autotuning + public entry
# --------------------------------------------------------------------------


def _autotune_fused_loss(n, v, h, dtype, vocab_major, interpret):
    """Benchmark-pick each kernel's (row-block, vocab-block) for this loss-head
    shape (reference ``auto_tune_base.h:48``) among the tiles the geometry
    admits; the geometry's own answer when tuning is off."""
    from paddle_tpu.kernels.autotune import autotune

    itemsize = jnp.dtype(dtype).itemsize
    key = (n, v, h, str(dtype), vocab_major)
    default = _block_geometry(n, v, h, itemsize, itemsize)
    candidates = [default] + _admitted_tiles(h, itemsize, itemsize, _dw_model(n, v, itemsize))

    def build(cfg):
        xz = jnp.zeros((n, h), dtype)
        wz = jnp.zeros((v, h) if vocab_major else (h, v), dtype)
        labz = jnp.zeros((n,), jnp.int32)

        def run():
            loss, vjp_fn = jax.vjp(
                lambda a, b: _pallas_path(
                    a, b, labz, v=v, h=h, ignore_index=-100, reduction="mean",
                    vocab_major=vocab_major, interpret=interpret, block=cfg,
                ),
                xz, wz,
            )
            return vjp_fn(jnp.ones_like(loss))  # fwd + bwd: the training cost

        return run

    return autotune("fused_linear_xent", key, candidates, build, default=default)


def fused_linear_cross_entropy(
    x: jax.Array,
    weight: jax.Array,
    labels: jax.Array,
    ignore_index: int = -100,
    reduction: str = "mean",
    vocab_major: bool = False,
    interpret: bool = False,
    block: Optional[Tuple[int, int]] = None,
    weight_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """``cross_entropy(x @ Wᵀ, labels)`` without materializing the ``[N, V]`` logits.

    ``x`` ``[..., H]``; ``weight`` ``[H, V]`` (``nn.Linear``) or ``[V, H]``
    with ``vocab_major=True`` (tied embedding); ``labels`` ``[...]`` int.
    Differentiable in ``x`` and ``weight`` (custom VJP; the backward
    recomputes block logits from the saved logsumexp, once where the block
    gradients fit in device memory, see the module docstring). Loss is fp32;
    reduction semantics match ``F.cross_entropy`` (mean divides by
    ``max(#non-ignored, 1)``). ``interpret=True`` forces the Pallas path in
    interpreter mode (tests); ``block`` overrides the autotuned
    ``(row_block, vocab_block)``.

    ``weight_scale`` (``[V]`` fp32, with ``weight`` int8) switches to the
    weight-only int8 lm-head walk: each vocab chunk's logits are scaled by
    its per-channel factors inside the walk, so the dequantized weight never
    materializes. Inference-only — the quantized walk has no VJP.
    """
    if reduction not in ("mean", "sum", "none"):
        raise ValueError(f"unsupported reduction {reduction!r}")
    lead = x.shape[:-1]
    h = x.shape[-1]
    v = weight.shape[0] if vocab_major else weight.shape[1]
    n = 1
    for s in lead:
        n *= int(s)
    x2 = x.reshape(n, h)
    lab = labels.reshape(n).astype(jnp.int32)

    if weight_scale is not None:
        loss = None
        if bool(interpret) or (
            h % 128 == 0 and pallas_enabled("use_fused_loss", bare="fused_linear_xent_quant")
        ):
            blk = block if block is not None else _block_geometry(
                n, v, h, x.dtype.itemsize, weight.dtype.itemsize, quantized=True
            )
            try:
                loss = _pallas_quant_path(
                    x2, weight, weight_scale, lab, v=v, h=h,
                    ignore_index=int(ignore_index), reduction=reduction,
                    vocab_major=bool(vocab_major), interpret=bool(interpret),
                    block=blk,
                )
            except Exception as exc:  # noqa: BLE001 - scan fallback below
                warn_fallback("fused_linear_xent_quant", exc)
        if loss is None:
            loss = _reference_quant_path(
                x2, weight, weight_scale, lab, v=v, h=h,
                ignore_index=int(ignore_index), reduction=reduction,
                vocab_major=bool(vocab_major),
            )
        if reduction == "none":
            return loss.reshape(lead)
        return loss

    loss = None
    # pre-trace applicability: lane-aligned hidden (see kernels/select.py)
    if bool(interpret) or (
        h % 128 == 0 and pallas_enabled("use_fused_loss", bare="fused_linear_cross_entropy")
    ):
        blk = block if block is not None else _autotune_fused_loss(
            n, v, h, x.dtype, vocab_major, bool(interpret)
        )
        try:
            loss = _pallas_path(
                x2, weight, lab, v=v, h=h, ignore_index=int(ignore_index),
                reduction=reduction, vocab_major=bool(vocab_major),
                interpret=bool(interpret), block=blk,
            )
        except Exception as exc:  # Mosaic lowering / unsupported shape: XLA path covers it
            warn_fallback("fused_linear_cross_entropy", exc)
    if loss is None:
        loss = _reference_path(
            x2, weight, lab, v=v, h=h, ignore_index=int(ignore_index),
            reduction=reduction, vocab_major=bool(vocab_major),
        )
    if reduction == "none":
        return loss.reshape(lead)
    return loss
