"""Fused elementwise Pallas kernels: rms_norm and rotary embedding.

Reference CUDA kernels: ``paddle/phi/kernels/gpu/rms_norm_kernel``,
``fused_rope_kernel.cu`` (``fused_ops.yaml:408``). XLA fuses these patterns
reasonably; the Pallas versions exist to pin the fusion (one HBM round-trip)
and as the base for bench-driven tuning. Both are differentiable: rms_norm
via custom VJP (recompute-rstd backward), rope via its jax-level composition
being linear in (x) and trig tables.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pallas_call name= of each kernel here: what a device trace calls it (stable, no shapes)
KERNEL_RMS_FWD = "rms_norm_fwd"
KERNEL_RMS_BWD = "rms_norm_bwd"
KERNEL_ROPE_FWD = "rope_fwd"
KERNEL_ROPE_ADJOINT = "rope_adjoint"
KERNEL_RMS_RES_FWD = "rms_norm_residual_fwd"
KERNEL_RMS_RES_ADJOINT = "rms_norm_residual_adjoint"
KERNEL_LN_RES_FWD = "layer_norm_residual_fwd"
KERNEL_LN_RES_ADJOINT = "layer_norm_residual_adjoint"
KERNEL_EMBED_RMS = "embed_rms_norm"


__all__ = [
    "fused_rms_norm_pallas",
    "fused_rope_pallas",
    "rope_adjoint_pallas",
    "fused_rms_norm_residual_pallas",
    "rms_norm_residual_adjoint_pallas",
    "fused_layer_norm_residual_pallas",
    "layer_norm_residual_adjoint_pallas",
    "fused_embed_rms_norm_pallas",
]


def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[0].astype(jnp.float32)  # [blk_rows, H]
    w = w_ref[...].astype(jnp.float32)  # [H]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[0] = (x * rstd * w[None, :]).astype(y_ref.dtype)
    rstd_ref[0] = rstd[:, 0]


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref, *, eps):
    x = x_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    rstd = rstd_ref[0][:, None]
    xhat = x * rstd
    gw = g * w[None, :]
    # dx = rstd * (gw - xhat * mean(gw * xhat))
    dot = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[0] = (rstd * (gw - xhat * dot)).astype(dx_ref.dtype)
    # dw accumulates into ONE [1, h] block across the sequential TPU grid
    # (a per-block [nblk, h] partial would need an illegal (1, h) tile:
    # sublane 1 is neither 8-divisible nor equal to nblk)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[0] = jnp.zeros_like(dw_ref[0])

    dw_ref[0] += jnp.sum(g * xhat, axis=0)


@functools.lru_cache(maxsize=None)
def _make_rms(rows, h, eps, blk_rows, interpret):
    grid = (rows // blk_rows,)

    def run_fwd(x, w):
        return pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            grid=grid,
            # independent row blocks: megacore-splittable
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            in_specs=[
                pl.BlockSpec((1, blk_rows, h), lambda i: (0, i, 0)),
                pl.BlockSpec((h,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((1, blk_rows, h), lambda i: (0, i, 0)),
                pl.BlockSpec((1, blk_rows), lambda i: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, rows, h), x.dtype),
                jax.ShapeDtypeStruct((1, rows), jnp.float32),
            ],
            interpret=interpret,
            name=KERNEL_RMS_FWD,
        )(x, w)

    @jax.custom_vjp
    def core(x, w):
        y, _ = run_fwd(x, w)
        return y

    def core_fwd(x, w):
        y, rstd = run_fwd(x, w)
        return y, (x, w, rstd)

    def core_bwd(res, g):
        x, w, rstd = res
        dx, dw = pl.pallas_call(
            functools.partial(_rms_bwd_kernel, eps=eps),
            grid=grid,
            # dw accumulates across the grid in one output block: the grid
            # MUST run sequentially ("arbitrary"), never be split
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            in_specs=[
                pl.BlockSpec((1, blk_rows, h), lambda i: (0, i, 0)),
                pl.BlockSpec((h,), lambda i: (0,)),
                pl.BlockSpec((1, blk_rows), lambda i: (0, i)),
                pl.BlockSpec((1, blk_rows, h), lambda i: (0, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, blk_rows, h), lambda i: (0, i, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, rows, h), x.dtype),
                jax.ShapeDtypeStruct((1, h), jnp.float32),
            ],
            interpret=interpret,
            name=KERNEL_RMS_BWD,
        )(x, w, rstd, g)
        return dx, dw[0].astype(w.dtype)

    core.defvjp(core_fwd, core_bwd)
    return core


def fused_rms_norm_pallas(
    x: jax.Array, weight: jax.Array, epsilon: float = 1e-6, interpret: bool = False
) -> jax.Array:
    """RMSNorm over the last axis; any leading shape."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    blk = _autotune_rms_rows(rows, h, x.dtype, float(epsilon), bool(interpret))
    pad = (-rows) % blk
    x2 = x.reshape(1, rows, h)
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad), (0, 0)))
    core = _make_rms(rows + pad, h, float(epsilon), blk, bool(interpret))
    y = core(x2, weight)
    return y[0, :rows].reshape(*lead, h)


def _autotune_rms_rows(rows: int, h: int, dtype, eps: float, interpret: bool) -> int:
    """Benchmark-pick the row-block for rms_norm at this shape (reference
    ``auto_tune_base.h:48``); 128 when tuning is off."""
    from paddle_tpu.kernels.autotune import autotune

    key = (rows, h, str(dtype))

    def build(blk):
        pad = (-rows) % blk
        xz = jnp.zeros((1, rows + pad, h), dtype)
        wz = jnp.zeros((h,), dtype)
        core = _make_rms(rows + pad, h, eps, blk, interpret)
        return lambda: core(xz, wz)

    picked = autotune("fused_rms_norm", key, (128, 256, 512, 1024), build, default=128)
    return int(picked)


def _rope_kernel(x_ref, cos_ref, sin_ref, y_ref):
    x = x_ref[0, 0].astype(jnp.float32)  # [S, D]
    cos = cos_ref[0].astype(jnp.float32)  # [S, D]
    sin = sin_ref[0].astype(jnp.float32)
    d = x.shape[-1]
    x1 = x[:, : d // 2]
    x2 = x[:, d // 2 :]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    y_ref[0, 0] = (x * cos + rot * sin).astype(y_ref.dtype)


def _rope_bwd_kernel(g_ref, cos_ref, sin_ref, dx_ref):
    # y = x⊙cos + rot(x)⊙sin with rot([x1,x2]) = [-x2, x1]. The adjoint of
    # rot is unrot([v1,v2]) = [v2, -v1], so dx = g⊙cos + unrot(g⊙sin):
    #   dx1 = g1·cos1 + g2·sin2 ; dx2 = g2·cos2 − g1·sin1
    # (exact even when the two sin halves differ — no table-symmetry
    # assumption). Reference: fused_rope_grad_kernel.cu (fused_ops.yaml:408).
    g = g_ref[0, 0].astype(jnp.float32)  # [S, D]
    cos = cos_ref[0].astype(jnp.float32)
    sin = sin_ref[0].astype(jnp.float32)
    d = g.shape[-1]
    gs = g * sin
    v1 = gs[:, : d // 2]
    v2 = gs[:, d // 2 :]
    unrot = jnp.concatenate([v2, -v1], axis=-1)
    dx_ref[0, 0] = (g * cos + unrot).astype(dx_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_rope_runner(bh, s, d, interpret):
    """One (batch*head)-gridded rope-shaped pallas_call launcher, shared by
    the forward and the adjoint kernels (identical specs, different body)."""
    grid = (bh,)
    in_specs = [
        pl.BlockSpec((1, 1, s, d), lambda i: (i, 0, 0, 0)),
        pl.BlockSpec((1, s, d), lambda i: (0, 0, 0)),
        pl.BlockSpec((1, s, d), lambda i: (0, 0, 0)),
    ]
    out_spec = pl.BlockSpec((1, 1, s, d), lambda i: (i, 0, 0, 0))

    def run(kernel, name, xh, cos2, sin2):
        return pl.pallas_call(
            kernel,
            grid=grid,
            # independent (batch*head) cells
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((bh, 1, s, d), xh.dtype),
            interpret=interpret,
            name=name,
        )(xh, cos2, sin2)

    return run


@functools.lru_cache(maxsize=None)
def _make_rope(bh, s, d, interpret):
    run = _make_rope_runner(bh, s, d, interpret)

    @jax.custom_vjp
    def core(xh, cos2, sin2):
        return run(_rope_kernel, KERNEL_ROPE_FWD, xh, cos2, sin2)

    def core_fwd(xh, cos2, sin2):
        return run(_rope_kernel, KERNEL_ROPE_FWD, xh, cos2, sin2), (xh, cos2, sin2)

    def core_bwd(res, g):
        xh, cos2, sin2 = res
        dx = run(_rope_bwd_kernel, KERNEL_ROPE_ADJOINT, g, cos2, sin2)
        # Table cotangents: trig tables are constants in every real model, so
        # XLA dead-code-eliminates these sums; computed exactly for parity.
        gf = g.astype(jnp.float32)
        xf = xh.astype(jnp.float32)
        dcos = jnp.sum(gf * xf, axis=0)  # [1, S, D]
        x1 = xf[..., : d // 2]
        x2 = xf[..., d // 2 :]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        dsin = jnp.sum(gf * rot, axis=0)
        return dx, dcos.astype(cos2.dtype), dsin.astype(sin2.dtype)

    core.defvjp(core_fwd, core_bwd)
    return core


def fused_rope_pallas(
    x: jax.Array, cos: jax.Array, sin: jax.Array, interpret: bool = False
) -> jax.Array:
    """Rotate-half rotary embedding. ``x`` [B, S, H, D]; cos/sin [S, D].

    Differentiable: custom VJP with a Pallas backward kernel (the bwd is a
    rope with the rotation adjoint applied to g⊙sin).
    """
    b, s, h, d = x.shape
    xh = jnp.moveaxis(x, 2, 1).reshape(b * h, 1, s, d)  # grid over B*H
    cos2 = cos.reshape(1, s, d)
    sin2 = sin.reshape(1, s, d)
    core = _make_rope(b * h, s, d, bool(interpret))
    y = core(xh, cos2, sin2)
    return jnp.moveaxis(y.reshape(b, h, s, d), 1, 2)


def rope_adjoint_pallas(
    g: jax.Array, cos: jax.Array, sin: jax.Array, interpret: bool = False
) -> jax.Array:
    """Adjoint of :func:`fused_rope_pallas` w.r.t. ``x`` as ONE standalone
    Pallas kernel: ``dx = g⊙cos + unrot(g⊙sin)``. The framework tape's rope
    op calls this directly in its backward (no jax-level differentiation of
    any ``pallas_call`` ever happens on the train path — the fix for the r03
    "Linearization failed" fallback), so it must stay callable outside any
    AD transform. ``g`` [B, S, H, D]; cos/sin [S, D]."""
    b, s, h, d = g.shape
    gh = jnp.moveaxis(g, 2, 1).reshape(b * h, 1, s, d)
    cos2 = cos.reshape(1, s, d)
    sin2 = sin.reshape(1, s, d)
    run = _make_rope_runner(b * h, s, d, bool(interpret))
    dx = run(_rope_bwd_kernel, KERNEL_ROPE_ADJOINT, gh, cos2, sin2)
    return jnp.moveaxis(dx.reshape(b, h, s, d), 1, 2)


# ---------------------------------------------------------------------------
# Fused residual-add + norm epilogues (decode-layer fusion)
# ---------------------------------------------------------------------------
#
# The decode step's per-layer epilogue is `r = x + residual; y = norm(r)` —
# two bandwidth-bound HBM round-trips that these kernels collapse into one
# (read x/residual once, write y and the new residual stream once). Numerics
# are LOCKSTEP with the XLA composition the flag-off path runs: the residual
# add happens in the IO dtype, rms_norm accumulates fp32 and multiplies by
# the weight AFTER the downcast (exactly ``nn.functional.common.rms_norm``'s
# order). The backward is a STANDALONE adjoint kernel (rstd/mean recomputed
# from the saved residual stream) that the incubate entries' explicit tape
# GradNode calls directly — no jax AD ever sees these pallas_calls.


def _rms_res_fwd_kernel(x_ref, res_ref, w_ref, y_ref, r_ref, *, eps):
    r = x_ref[0] + res_ref[0]  # residual add in the IO dtype (XLA lockstep)
    r_ref[0] = r
    xf = r.astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    # fp32 weight multiply BEFORE the downcast — the same order as
    # _rms_fwd_kernel, so fused on/off stay bitwise-matched on TPU where the
    # unfused path runs that kernel
    y_ref[0] = (xf * rstd * w[None, :]).astype(y_ref.dtype)


def _rms_res_bwd_kernel(r_ref, w_ref, g_ref, dx_ref, dw_ref, *, eps):
    r = r_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    ms = jnp.mean(r * r, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = r * rstd
    gw = g * w[None, :]
    dot = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[0] = (rstd * (gw - xhat * dot)).astype(dx_ref.dtype)

    # dw accumulates into ONE [1, h] block across the sequential grid (the
    # same rule as _rms_bwd_kernel: a per-block partial would need an
    # illegal (1, h) sublane tile)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[0] = jnp.zeros_like(dw_ref[0])

    dw_ref[0] += jnp.sum(g * xhat, axis=0)


def _ln_res_fwd_kernel(x_ref, res_ref, w_ref, b_ref, y_ref, r_ref, *, eps):
    r = x_ref[0] + res_ref[0]
    r_ref[0] = r
    xf = r.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32)[None, :] + b_ref[...].astype(jnp.float32)[None, :]
    y_ref[0] = y.astype(y_ref.dtype)


def _ln_res_bwd_kernel(r_ref, w_ref, g_ref, dx_ref, dw_ref, db_ref, *, eps):
    r = r_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    mu = jnp.mean(r, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(r - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (r - mu) * rstd
    gw = g * w[None, :]
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[0] = (rstd * (gw - m1 - xhat * m2)).astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[0] = jnp.zeros_like(dw_ref[0])
        db_ref[0] = jnp.zeros_like(db_ref[0])

    dw_ref[0] += jnp.sum(g * xhat, axis=0)
    db_ref[0] += jnp.sum(g, axis=0)


def _row_block(kernel: str, rows: int, h: int, dtype) -> int:
    """Benchmark-pick the row block for a residual+norm kernel at this shape
    (same candidate set the plain rms_norm tune sweeps); 128 when tuning is
    off. Registered per kernel name so the fwd and adjoint shapes tune
    independently of the plain fused_rms_norm entry."""
    from paddle_tpu.kernels.autotune import autotune

    key = (rows, h, str(dtype))

    def build(blk):
        pad = (-rows) % blk
        if kernel.endswith("_bwd"):
            def run():
                g = jnp.zeros((1, rows + pad, h), dtype)
                r = jnp.zeros((1, rows + pad, h), dtype)
                w = jnp.zeros((h,), dtype)
                if kernel.startswith("fused_rms"):
                    return _rms_res_adjoint_call(g, r, w, 1e-6, blk, False)
                return _ln_res_adjoint_call(g, r, w, 1e-6, blk, False)
            return run

        def run():
            x = jnp.zeros((1, rows + pad, h), dtype)
            w = jnp.zeros((h,), dtype)
            if kernel.startswith("fused_rms"):
                return _rms_res_fwd_call(x, x, w, 1e-6, blk, False)
            return _ln_res_fwd_call(x, x, w, jnp.zeros((h,), dtype), 1e-6, blk, False)
        return run

    return int(autotune(kernel, key, (128, 256, 512, 1024), build, default=128))


def _rms_res_fwd_call(x2, res2, w, eps, blk, interpret):
    rows, h = x2.shape[1], x2.shape[2]
    spec = pl.BlockSpec((1, blk, h), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_rms_res_fwd_kernel, eps=eps),
        grid=(rows // blk,),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        in_specs=[spec, spec, pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, h), x2.dtype),
            jax.ShapeDtypeStruct((1, rows, h), x2.dtype),
        ],
        interpret=interpret,
        name=KERNEL_RMS_RES_FWD,
    )(x2, res2, w)


def _rms_res_adjoint_call(g2, r2, w, eps, blk, interpret):
    rows, h = g2.shape[1], g2.shape[2]
    spec = pl.BlockSpec((1, blk, h), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_rms_res_bwd_kernel, eps=eps),
        grid=(rows // blk,),
        # dw accumulates across the grid: sequential, never megacore-split
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        in_specs=[spec, pl.BlockSpec((h,), lambda i: (0,)), spec],
        out_specs=[spec, pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, h), g2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_RMS_RES_ADJOINT,
    )(r2, w, g2)


def _ln_res_fwd_call(x2, res2, w, b, eps, blk, interpret):
    rows, h = x2.shape[1], x2.shape[2]
    spec = pl.BlockSpec((1, blk, h), lambda i: (0, i, 0))
    wspec = pl.BlockSpec((h,), lambda i: (0,))
    return pl.pallas_call(
        functools.partial(_ln_res_fwd_kernel, eps=eps),
        grid=(rows // blk,),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        in_specs=[spec, spec, wspec, wspec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, h), x2.dtype),
            jax.ShapeDtypeStruct((1, rows, h), x2.dtype),
        ],
        interpret=interpret,
        name=KERNEL_LN_RES_FWD,
    )(x2, res2, w, b)


def _ln_res_adjoint_call(g2, r2, w, eps, blk, interpret):
    rows, h = g2.shape[1], g2.shape[2]
    spec = pl.BlockSpec((1, blk, h), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_ln_res_bwd_kernel, eps=eps),
        grid=(rows // blk,),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        in_specs=[spec, pl.BlockSpec((h,), lambda i: (0,)), spec],
        out_specs=[
            spec,
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, h), g2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_LN_RES_ADJOINT,
    )(r2, w, g2)


def _pad_rows(x, rows, pad):
    x2 = x.reshape(1, rows, x.shape[-1])
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad), (0, 0)))
    return x2


def fused_rms_norm_residual_pallas(
    x: jax.Array, residual: jax.Array, weight: jax.Array,
    epsilon: float = 1e-6, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``r = x + residual; y = rms_norm(r, weight)`` in ONE kernel.
    Returns ``(y, r)``; any leading shape, norm over the last axis."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    blk = _row_block("fused_rms_norm_residual", rows, h, x.dtype)
    pad = (-rows) % blk
    y, r = _rms_res_fwd_call(
        _pad_rows(x, rows, pad), _pad_rows(residual, rows, pad), weight,
        float(epsilon), blk, bool(interpret),
    )
    return y[0, :rows].reshape(*lead, h), r[0, :rows].reshape(*lead, h)


def rms_norm_residual_adjoint_pallas(
    g: jax.Array, r: jax.Array, weight: jax.Array,
    epsilon: float = 1e-6, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Adjoint of the norm half of :func:`fused_rms_norm_residual_pallas`
    w.r.t. its pre-norm input ``r`` (the saved residual stream) as ONE
    standalone kernel: ``(d_r, d_weight)`` given the y-cotangent ``g``.
    The residual add's adjoint is the identity, so the caller's tape node
    forwards ``d_r`` (plus any residual-stream cotangent) to both x and
    residual. rstd is recomputed from ``r`` — nothing but forward outputs is
    saved, and no jax AD transform ever touches the pallas_call."""
    h = g.shape[-1]
    lead = g.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    blk = _row_block("fused_rms_norm_residual_bwd", rows, h, g.dtype)
    pad = (-rows) % blk
    dx, dw = _rms_res_adjoint_call(
        _pad_rows(g, rows, pad), _pad_rows(r, rows, pad), weight,
        float(epsilon), blk, bool(interpret),
    )
    return dx[0, :rows].reshape(*lead, h), dw[0].astype(weight.dtype)


def fused_layer_norm_residual_pallas(
    x: jax.Array, residual: jax.Array, weight: jax.Array,
    bias: Optional[jax.Array] = None, epsilon: float = 1e-5,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``r = x + residual; y = layer_norm(r, weight, bias)`` in ONE kernel
    (fp32 accumulation). Returns ``(y, r)``."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    if bias is None:
        bias = jnp.zeros((h,), x.dtype)
    blk = _row_block("fused_layer_norm_residual", rows, h, x.dtype)
    pad = (-rows) % blk
    y, r = _ln_res_fwd_call(
        _pad_rows(x, rows, pad), _pad_rows(residual, rows, pad), weight, bias,
        float(epsilon), blk, bool(interpret),
    )
    return y[0, :rows].reshape(*lead, h), r[0, :rows].reshape(*lead, h)


def layer_norm_residual_adjoint_pallas(
    g: jax.Array, r: jax.Array, weight: jax.Array,
    epsilon: float = 1e-5, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Standalone adjoint of :func:`fused_layer_norm_residual_pallas`'s norm
    half: ``(d_r, d_weight, d_bias)`` given the y-cotangent (mean/var
    recomputed from the saved residual stream; same tape contract as
    :func:`rms_norm_residual_adjoint_pallas`)."""
    h = g.shape[-1]
    lead = g.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    blk = _row_block("fused_layer_norm_residual_bwd", rows, h, g.dtype)
    pad = (-rows) % blk
    dx, dw, db = _ln_res_adjoint_call(
        _pad_rows(g, rows, pad), _pad_rows(r, rows, pad), weight,
        float(epsilon), blk, bool(interpret),
    )
    return (
        dx[0, :rows].reshape(*lead, h),
        dw[0].astype(weight.dtype),
        db[0].astype(weight.dtype),
    )


# ---------------------------------------------------------------------------
# Fused token-gather + embedding lookup + first-layer norm (chunk-step entry)
# ---------------------------------------------------------------------------


# rows per block on both sides of the gather: a (1, H) block over [V, H] or
# [N, H] breaks the TPU rule that a block's last two dims are multiples of
# (8, 128) or the full dims; 16 is the bf16 sublane tile and legal for f32
_EMBED_GROUP = 16


def _embed_rms_kernel(ids_ref, rows_ref, w_ref, emb_ref, y_ref, *, eps):
    # ids_ref is the scalar-prefetched token vector that already steered this
    # grid cell's rows_ref block onto the aligned row group holding the
    # token's embedding row — the gather IS the BlockSpec index map, so the
    # dense [N, V] one-hot / XLA gather round-trip never materializes. One
    # cell = one token; _EMBED_GROUP consecutive cells share an output block.
    i = pl.program_id(0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_EMBED_GROUP, 1), 0)
    group = rows_ref[...].astype(jnp.float32)  # [G, H]
    # exact row select: every other term of the sum is 0
    xf = jnp.sum(
        jnp.where(sub == ids_ref[i] % _EMBED_GROUP, group, 0.0), axis=0, keepdims=True
    )
    w = w_ref[...].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    # same op order as _rms_fwd_kernel (bitwise-matched vs the unfused path)
    y = xf * jax.lax.rsqrt(ms + eps) * w[None, :]
    mine = sub == i % _EMBED_GROUP  # this token's row of the shared out block
    emb_ref[...] = jnp.where(mine, xf.astype(emb_ref.dtype), emb_ref[...])
    y_ref[...] = jnp.where(mine, y.astype(y_ref.dtype), y_ref[...])


def fused_embed_rms_norm_pallas(
    ids: jax.Array,  # [B, C] int32 token ids
    table: jax.Array,  # [V, H] embedding table
    weight: jax.Array,  # [H] first-layer rms_norm weight
    epsilon: float = 1e-6,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Chunk-step entry fusion: token-id gather + embedding row load + the
    first decoder layer's pre-attention RMSNorm in ONE dispatch. The
    scalar-prefetched ids steer the BlockSpec index map (the same trick the
    paged-attention block table plays), so each grid cell streams the
    aligned ``[_EMBED_GROUP, H]`` row group around its token HBM -> VMEM and
    writes the raw embedding (the layer loop's residual stream) plus its
    normed form. Returns ``(emb, y)``, both ``[B, C, H]`` in the table dtype.
    Inference-only (the serving step) — there is no backward; training
    embeds through the regular op."""
    b, c = ids.shape
    v, h = table.shape
    n = b * c
    n_pad = -(-n // _EMBED_GROUP) * _EMBED_GROUP
    flat = jnp.clip(ids.reshape(n).astype(jnp.int32), 0, v - 1)
    if n_pad > n:
        flat = jnp.pad(flat, (0, n_pad - n))
    rows_spec = pl.BlockSpec(
        (_EMBED_GROUP, h), lambda i, ids: (ids[i] // _EMBED_GROUP, 0)
    )
    out_spec = pl.BlockSpec((_EMBED_GROUP, h), lambda i, ids: (i // _EMBED_GROUP, 0))
    emb, y = pl.pallas_call(
        functools.partial(_embed_rms_kernel, eps=float(epsilon)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad,),
            in_specs=[rows_spec, pl.BlockSpec((h,), lambda i, ids: (0,))],
            out_specs=[out_spec, out_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), table.dtype),
            jax.ShapeDtypeStruct((n_pad, h), table.dtype),
        ],
        # consecutive cells revisit one output block: must run in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_EMBED_RMS,
    )(flat, table, weight)
    return emb[:n].reshape(b, c, h), y[:n].reshape(b, c, h)
