"""Fused functional ops (reference ``python/paddle/incubate/nn/functional/``:
fused_rms_norm, swiglu, fused_rotary_position_embedding, fused_bias_act, …).

Each maps to a composition that XLA fuses on TPU (or a Pallas kernel where
profiling says XLA's fusion is insufficient — see ``paddle_tpu.kernels``).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.registry import defop
from paddle_tpu.nn.functional.activation import swiglu  # noqa: F401
from paddle_tpu.nn.functional.common import rms_norm

__all__ = [
    "fused_rms_norm",
    "fused_layer_norm",
    "swiglu",
    "fused_rotary_position_embedding",
    "fused_bias_act",
    "fused_linear",
    "fused_bias_dropout_residual_layer_norm",
    "fused_dropout_add",
    "masked_multihead_attention",
    "block_multihead_attention",
    "block_multihead_chunk_attention",
    "block_cache_prefill",
    "block_cache_append_chunk",
    "block_cache_cow_copy",
    "BlockKVCache",
    "fused_moe",
]

from paddle_tpu.incubate.nn.functional.block_attention import (  # noqa: E402,F401
    BlockKVCache,
    block_cache_append_chunk,
    block_cache_cow_copy,
    block_cache_prefill,
    block_multihead_attention,
    block_multihead_chunk_attention,
)
from paddle_tpu.incubate.nn.functional.fused_moe import fused_moe  # noqa: E402,F401


def fused_rms_norm(
    x: Any,
    norm_weight: Any,
    norm_bias: Any = None,
    epsilon: float = 1e-6,
    begin_norm_axis: int = -1,
    bias: Any = None,
    residual: Any = None,
    quant_scale: float = -1,
    **kwargs: Any,
) -> Tuple[Any, ...]:
    """Reference ``fused_rms_norm`` (rms_norm kernel + optional bias/residual
    add). Returns (out, residual_out) like the reference when residual given."""
    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = rms_norm(x, norm_weight, epsilon)
    if norm_bias is not None:
        out = out + norm_bias
    if residual is not None:
        return out, residual_out
    return out


def fused_layer_norm(
    x: Any,
    norm_weight: Any,
    norm_bias: Any = None,
    epsilon: float = 1e-5,
    begin_norm_axis: int = -1,
    bias: Any = None,
    residual: Any = None,
    **kwargs: Any,
) -> Any:
    from paddle_tpu.nn.functional.common import layer_norm

    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = layer_norm(x, None, norm_weight, norm_bias, epsilon)
    if residual is not None:
        return out, residual_out
    return out


# -- rope: XLA composition + rotation adjoint (pure array functions) ---------

def _rope_rotate(x, use_neox):
    if use_neox:
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([-x2, x1], axis=-1)
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def _rope_broadcast_tables(x, sin, cos):
    s, c = sin, cos
    if s.ndim == 2:
        s = s[None, :, None, :]
        c = c[None, :, None, :]
    return s.astype(x.dtype), c.astype(x.dtype)


def _rope_apply_xla(x, sin, cos, use_neox):
    s, c = _rope_broadcast_tables(x, sin, cos)
    return x * c + _rope_rotate(x, use_neox) * s


def _rope_adjoint_xla(g, sin, cos, use_neox):
    """dx for y = x⊙c + rot(x)⊙s: ``g⊙c + unrot(g⊙s)`` — the rotation's
    adjoint is its inverse sign pattern (exact for asymmetric tables)."""
    s, c = _rope_broadcast_tables(g, sin, cos)
    gs = g * s
    if use_neox:
        half = g.shape[-1] // 2
        v1, v2 = gs[..., :half], gs[..., half:]
        unrot = jnp.concatenate([v2, -v1], axis=-1)
    else:
        v1 = gs[..., 0::2]
        v2 = gs[..., 1::2]
        unrot = jnp.stack([v2, -v1], axis=-1).reshape(gs.shape)
    return g * c + unrot


@defop("fused_rotary_position_embedding", tensor_method=None)
def _fused_rope_op(q, k, v, sin, cos, use_neox_rotary_style=True):
    """RoPE (reference ``fused_ops.yaml:408`` fused_rotary_position_embedding;
    kernel ``paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu``).
    Layout [B, S, H, D]; sin/cos [1, S, 1, D] (or [S, D]).

    Registered raw op = the pure-XLA composition (parity audits, infer_meta,
    and create_graph re-differentiation trace THIS, never a Pallas call);
    the serving/train entry :func:`fused_rotary_position_embedding` routes
    around the generic ``jax.vjp`` dispatch with an explicit tape node whose
    backward runs the Pallas adjoint kernel directly."""
    return tuple(
        _rope_apply_xla(t, sin, cos, use_neox_rotary_style)
        for t in (q, k, v)
        if t is not None
    )


def _rope_kernel_tables(x, sin, cos, use_neox):
    """(cos2, sin2) in the Pallas kernel's [S, D] layout when this shape is
    kernel-eligible, else None. Per-batch tables (leading dim > 1 — decode
    with ragged positions) cannot collapse to [S, D]: XLA path only."""
    if not use_neox or x.shape[-1] % 128 != 0:
        return None
    if cos.ndim == 2:
        return cos, sin
    if cos.shape[0] == 1:
        return (
            cos.reshape(cos.shape[1], cos.shape[-1]),
            sin.reshape(sin.shape[1], sin.shape[-1]),
        )
    return None


def _rope_fwd_array(x, sin, cos, use_neox):
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    tabs = _rope_kernel_tables(x, sin, cos, use_neox)
    if tabs is not None and pallas_enabled("use_pallas_fused", bare="fused_rope"):
        try:
            from paddle_tpu.kernels.fused import fused_rope_pallas

            return fused_rope_pallas(x, tabs[0], tabs[1])
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_rope", exc)
    return _rope_apply_xla(x, sin, cos, use_neox)


def _rope_bwd_array(g, sin, cos, use_neox):
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    tabs = _rope_kernel_tables(g, sin, cos, use_neox)
    if tabs is not None and pallas_enabled("use_pallas_fused", bare="fused_rope_bwd"):
        try:
            from paddle_tpu.kernels.fused import rope_adjoint_pallas

            return rope_adjoint_pallas(g, tabs[0], tabs[1])
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_rope_bwd", exc)
    return _rope_adjoint_xla(g, sin, cos, use_neox)


def _reduce_to_shape(arr, shape):
    """Sum ``arr`` down to broadcast source ``shape`` (table cotangents)."""
    while arr.ndim > len(shape):
        arr = arr.sum(axis=0)
    for ax, (have, want) in enumerate(zip(arr.shape, shape)):
        if want == 1 and have != 1:
            arr = arr.sum(axis=ax, keepdims=True)
    return arr.reshape(shape)


def fused_rotary_position_embedding(
    q: Any,
    k: Any = None,
    v: Any = None,
    sin: Any = None,
    cos: Any = None,
    position_ids: Any = None,
    use_neox_rotary_style: bool = True,
    time_major: bool = False,
    rotary_emb_base: float = 10000.0,
) -> Tuple[Any, ...]:
    """RoPE over q/k/v with an EXPLICIT tape backward.

    The generic op dispatch differentiates its forward with ``jax.vjp`` at
    record time; routed through the Pallas rope kernel's ``custom_vjp`` that
    linearization is exactly what degraded to XLA on the r03 TPU run
    ("Linearization failed to produce known values for all output primals"
    — counted in ``paddle_tpu_kernel_fallbacks_total{kernel=fused_rope}``).
    This entry instead records a manual :class:`~paddle_tpu.core.autograd.
    GradNode` (the ``recompute`` pattern): forward and backward each run
    their own standalone Pallas kernel (``fused_rope_pallas`` /
    ``rope_adjoint_pallas``) behind the usual applicability gate + XLA
    fallback, and NO jax AD transform ever sees a ``pallas_call`` — there is
    nothing left to fail linearization. ``create_graph`` re-differentiation
    goes through the registered pure-XLA raw op.
    """
    from paddle_tpu.core import autograd as _ag
    from paddle_tpu.core import dispatch as _dispatch
    from paddle_tpu.core.tensor import Tensor

    if sin is None or cos is None:
        # build sin/cos table from base
        b, s, h, d = q.shape
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        t = jnp.arange(s, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        sin = Tensor(jnp.sin(emb))
        cos = Tensor(jnp.cos(emb))

    neox = bool(use_neox_rotary_style)
    inputs = [q, k, v, sin, cos]
    arrays = [
        (t._data if isinstance(t, Tensor) else (None if t is None else jnp.asarray(t)))
        for t in inputs
    ]
    # AMP autocast parity with call_op: a custom_white/black_list naming this
    # op must still cast its tensor inputs even though dispatch is manual
    from paddle_tpu.amp.auto_cast import amp_cast_inputs, amp_enabled

    if amp_enabled():
        present = [i for i, a in enumerate(arrays) if a is not None]
        cast = amp_cast_inputs(
            "fused_rotary_position_embedding", [arrays[i] for i in present]
        )
        for i, a in zip(present, cast):
            arrays[i] = a
    xq, xk, xv, s_arr, c_arr = arrays
    in_positions = [i for i in (0, 1, 2) if arrays[i] is not None]  # q/k/v present
    out_arrays = [_rope_fwd_array(arrays[i], s_arr, c_arr, neox) for i in in_positions]

    def _diff(t: Any) -> bool:
        return (
            isinstance(t, Tensor)
            and not t.stop_gradient
            and jnp.issubdtype(jnp.dtype(t.dtype), jnp.inexact)
        )

    record = _ag.is_grad_enabled() and any(_diff(t) for t in inputs)
    node = None
    if record:
        diff_pos = [i for i, t in enumerate(inputs) if _diff(t)]
        diff_tensors = [inputs[i] for i in diff_pos]
        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_arrays]
        _flat, out_treedef = jax.tree_util.tree_flatten(tuple(out_arrays))
        # output index for each q/k/v position (outs pack only non-None)
        out_index = {pos: j for j, pos in enumerate(in_positions)}
        consts = list(arrays)  # non-diff inputs closed over as arrays

        def vjp_fn(cots: Any) -> Tuple[Any, ...]:
            # out_treedef is always set, so the sweep hands us the tuple form
            cot_list = list(cots)
            grads: List[Any] = []
            for pos in diff_pos:
                if pos in out_index:  # q/k/v: one standalone adjoint kernel
                    g = cot_list[out_index[pos]]
                    grads.append(_rope_bwd_array(g, s_arr, c_arr, neox))
                    continue
                # table cotangents (rare — tables are buffers in every real
                # model): exact sums over the XLA composition's broadcast
                total = None
                for p in in_positions:
                    g32 = cot_list[out_index[p]].astype(jnp.float32)
                    x32 = arrays[p].astype(jnp.float32)
                    term = (
                        g32 * _rope_rotate(x32, neox)
                        if pos == 3  # sin
                        else g32 * x32  # cos
                    )
                    total = term if total is None else total + term
                src = s_arr if pos == 3 else c_arr
                shape = (
                    src.shape if src.ndim != 2
                    else (1, src.shape[0], 1, src.shape[1])
                )
                red = _reduce_to_shape(total, shape).reshape(src.shape)
                grads.append(red.astype(src.dtype))
            return tuple(grads)

        def closed(*diff_arrays: Any) -> Tuple[Any, ...]:
            vals = list(consts)
            for p, arr in zip(diff_pos, diff_arrays):
                vals[p] = arr
            return tuple(
                _rope_apply_xla(vals[i], vals[3], vals[4], neox)
                for i in in_positions
            )

        node = _ag.GradNode(
            "fused_rotary_position_embedding", vjp_fn, diff_tensors, out_avals,
            fwd_fn=closed, out_treedef=out_treedef,
        )

    if _dispatch._NAN_CHECK[0]:
        _dispatch._check_nan_inf("fused_rotary_position_embedding", out_arrays)
    if _dispatch.op_stats_hook is not None:  # amp.debugging operator stats
        _dispatch.op_stats_hook("fused_rotary_position_embedding", out_arrays)
    result: List[Any] = []
    for j, _pos in enumerate(in_positions):
        t = Tensor(out_arrays[j], stop_gradient=(node is None))
        if node is not None:
            t._grad_node = node
            t._grad_output_index = j
        result.append(t)
    while len(result) < 3:
        result.append(None)
    return tuple(result[:3])


@defop("fused_bias_act", tensor_method=None)
def fused_bias_act(x, bias=None, act_method="gelu", dequant_scales=None, shift=None, smooth=None, **kwargs):
    """Reference ``fused_ops.yaml:201`` fused_bias_act."""
    if bias is not None:
        x = x + bias
    if act_method in ("gelu",):
        return jax.nn.gelu(x)
    if act_method in ("relu",):
        return jax.nn.relu(x)
    if act_method in ("swiglu", "silu"):
        if act_method == "swiglu":
            a, b = jnp.split(x, 2, axis=-1)
            return jax.nn.silu(a) * b
        return jax.nn.silu(x)
    raise ValueError(f"unsupported act_method {act_method}")


@defop("fused_linear", tensor_method=None)
def fused_linear(x, weight, bias=None, transpose_weight=False):
    w = weight.T if transpose_weight else weight
    out = jnp.matmul(x, w)
    if bias is not None:
        out = out + bias
    return out


@defop("masked_multihead_attention", tensor_method=None)
def masked_multihead_attention(q, k, v, cache_k, cache_v, seq_len, scale=None):
    """Decode-phase attention with append-to-cache — the static-shape KV-cache
    attention step (reference ``paddle/phi/ops/yaml/ops.yaml:3074``
    ``masked_multihead_attention_``, CUDA kernel
    ``paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu``).

    One new token per sequence attends to every cached position up to its
    current length; the new K/V are written into fixed-size buffers with
    ``dynamic_update_slice`` so every decode step is the SAME compiled XLA
    program (no shape growth, no recompiles — the TPU analog of the
    reference's in-place `_` op).

    Args:
      q/k/v: ``[B, 1, H, D]`` / ``[B, 1, HK, D]`` this step's post-RoPE
        projections (GQA: HK may divide H).
      cache_k/cache_v: ``[B, S_max, HK, D]`` static cache buffers.
      seq_len: int32 scalar or ``[B]`` — tokens already cached; the new token
        is written at this index.
      scale: attention scale, default ``1/sqrt(D)``.

    Returns ``(out [B, 1, H, D], cache_k', cache_v')``.
    """
    b, _, h, d = q.shape
    hk = cache_k.shape[2]
    s_max = cache_k.shape[1]
    group = h // hk
    if scale is None:
        scale = 1.0 / (d**0.5)
    lens = jnp.broadcast_to(jnp.asarray(seq_len, jnp.int32).reshape(-1), (b,))
    try:
        # concrete lengths (eager decode loops): fail loudly on overflow —
        # inside jit the write index would silently clamp onto the last slot
        concrete = np.asarray(lens)
        if (concrete >= s_max).any():
            raise ValueError(
                f"KV cache overflow: seq_len {concrete.max()} >= buffer size {s_max}"
            )
    except (jax.errors.TracerArrayConversionError, jax.errors.ConcretizationTypeError):
        pass

    def append(buf, new, ln):
        # buf [S_max, HK, D], new [1, HK, D]
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), (ln, 0, 0))

    ck = jax.vmap(append)(cache_k, k, lens)
    cv = jax.vmap(append)(cache_v, v, lens)

    qg = q.reshape(b, 1, hk, group, d).astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max, dtype=jnp.int32)
    allowed = pos[None, :] <= lens[:, None]  # include the just-written token
    logits = jnp.where(allowed[:, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, cv.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype), ck, cv


def fused_bias_dropout_residual_layer_norm(
    x: Any,
    residual: Any,
    bias: Any = None,
    ln_scale: Any = None,
    ln_bias: Any = None,
    dropout_rate: float = 0.0,
    ln_epsilon: float = 1e-5,
    training: bool = True,
    mode: str = "upscale_in_train",
) -> Any:
    from paddle_tpu.nn.functional.common import dropout, layer_norm

    if bias is not None:
        x = x + bias
    x = dropout(x, p=dropout_rate, training=training, mode=mode)
    x = x + residual
    return layer_norm(x, None, ln_scale, ln_bias, ln_epsilon)


def fused_dropout_add(x: Any, y: Any, p: float = 0.5, training: bool = True, mode: str = "upscale_in_train") -> Any:
    from paddle_tpu.nn.functional.common import dropout

    return dropout(x, p=p, training=training, mode=mode) + y


def fused_softmax_mask(x: Any, mask: Any) -> Any:
    """Reference ``fused_softmax_mask kernel``: softmax(x + mask) in one
    fused step (XLA fuses the add into the softmax)."""
    from paddle_tpu.core.dispatch import call_op

    def _impl(x, m):
        return jax.nn.softmax(x.astype(jnp.float32) + m.astype(jnp.float32), axis=-1).astype(x.dtype)

    return call_op("fused_softmax_mask", _impl, x, mask)


def fused_softmax_mask_upper_triangle(x: Any) -> Any:
    """Reference ``fused_softmax_mask_upper_triangle``: causal-masked softmax
    over the last two dims (scores [B, H, Sq, Sk])."""
    from paddle_tpu.core.dispatch import call_op

    def _impl(x):
        s_q, s_k = x.shape[-2], x.shape[-1]
        keep = jnp.tril(jnp.ones((s_q, s_k), bool))
        z = jnp.where(keep, x.astype(jnp.float32), -1e30)
        return jax.nn.softmax(z, axis=-1).astype(x.dtype)

    return call_op("fused_softmax_mask_upper_triangle", _impl, x)


__all__ += ["fused_softmax_mask", "fused_softmax_mask_upper_triangle"]


# -- fused residual-add + norm: the decode layer's epilogue pairs ------------
#
# One transformer layer's epilogue is two HBM round-trips — ``r = x +
# residual`` then ``y = norm(r)`` — issued twice per layer (post-attention
# and pre-next-layer). These entries collapse each pair into ONE Pallas
# dispatch behind the usual gate, with the XLA fallback running the EXACT op
# composition the unfused path runs (x + residual, then ``rms_norm``'s
# upcast/rsqrt/downcast/weight order, or ``layer_norm``'s no-upcast order) —
# which is what keeps fused on/off byte-identical per backend. Backward is
# the PR 9 explicit tape-GradNode pattern: a standalone adjoint kernel that
# recomputes rstd from the saved residual stream, with no jax AD transform
# ever applied over a ``pallas_call``.


def _rms_res_fwd_array(x, residual, weight, eps):
    from paddle_tpu.kernels.select import pallas_enabled, per_shard, warn_fallback

    if (
        weight.dtype == x.dtype
        and x.shape[-1] % 128 == 0
        and pallas_enabled("use_pallas_fused", bare="fused_rms_norm_residual", row_wise=True)
    ):
        try:
            from paddle_tpu.kernels.fused import fused_rms_norm_residual_pallas

            return per_shard(
                lambda x_, r_, w_: fused_rms_norm_residual_pallas(x_, r_, w_, eps)
            )(x, residual, weight)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_rms_norm_residual", exc)
    r = x + residual
    xf = r.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    out = out.astype(r.dtype)
    return out * weight, r


def _rms_res_bwd_array(g, r, weight, eps):
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    if (
        weight.dtype == g.dtype
        and g.shape[-1] % 128 == 0
        and pallas_enabled("use_pallas_fused", bare="fused_rms_norm_residual_bwd")
    ):
        try:
            from paddle_tpu.kernels.fused import rms_norm_residual_adjoint_pallas

            return rms_norm_residual_adjoint_pallas(g, r, weight, eps)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_rms_norm_residual_bwd", exc)
    r32 = r.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(r32), axis=-1, keepdims=True) + eps)
    xhat = r32 * rstd
    gw = g32 * weight.astype(jnp.float32)
    dot = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - xhat * dot)).astype(g.dtype)
    dw = jnp.sum((g32 * xhat).reshape(-1, r.shape[-1]), axis=0).astype(weight.dtype)
    return dx, dw


def _ln_res_fwd_array(x, residual, weight, bias, eps):
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    if (
        weight.dtype == x.dtype
        and x.shape[-1] % 128 == 0
        and pallas_enabled("use_pallas_fused", bare="fused_layer_norm_residual")
    ):
        try:
            from paddle_tpu.kernels.fused import fused_layer_norm_residual_pallas

            return fused_layer_norm_residual_pallas(x, residual, weight, bias, eps)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_layer_norm_residual", exc)
    # the exact nn.functional.common.layer_norm composition: stats in the IO
    # dtype (no upcast), weight multiply then bias add only when present
    r = x + residual
    mean = jnp.mean(r, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(r - mean), axis=-1, keepdims=True)
    out = (r - mean) * jax.lax.rsqrt(var + eps)
    out = out * weight
    if bias is not None:
        out = out + bias
    return out, r


def _ln_res_bwd_array(g, r, weight, eps):
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    if (
        weight.dtype == g.dtype
        and g.shape[-1] % 128 == 0
        and pallas_enabled("use_pallas_fused", bare="fused_layer_norm_residual_bwd")
    ):
        try:
            from paddle_tpu.kernels.fused import layer_norm_residual_adjoint_pallas

            return layer_norm_residual_adjoint_pallas(g, r, weight, eps)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_layer_norm_residual_bwd", exc)
    r32 = r.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    mu = jnp.mean(r32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(r32 - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (r32 - mu) * rstd
    gw = g32 * weight.astype(jnp.float32)
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - m1 - xhat * m2)).astype(g.dtype)
    h = r.shape[-1]
    dw = jnp.sum((g32 * xhat).reshape(-1, h), axis=0).astype(weight.dtype)
    db = jnp.sum(g32.reshape(-1, h), axis=0).astype(weight.dtype)
    return dx, dw, db


def _residual_norm_entry(name, x, norm_weight, norm_bias, residual, eps, is_rms):
    """Shared tape-GradNode plumbing for the two residual+norm entries.

    Outputs ``(y, residual_out)`` as Tensors. The residual add's adjoint is
    the identity, so the node hands ``d_r = norm_adjoint(dy) + d_residual_out``
    to BOTH x and residual; weight (and bias) cotangents come from the same
    standalone adjoint kernel. ``create_graph`` re-differentiation traces the
    pure-XLA ``closed`` composition — never a pallas_call.
    """
    from paddle_tpu.core import autograd as _ag
    from paddle_tpu.core import dispatch as _dispatch
    from paddle_tpu.core.tensor import Tensor

    inputs = [x, norm_weight, norm_bias, residual]
    arrays = [
        (t._data if isinstance(t, Tensor) else (None if t is None else jnp.asarray(t)))
        for t in inputs
    ]
    from paddle_tpu.amp.auto_cast import amp_cast_inputs, amp_enabled

    if amp_enabled():
        present = [i for i, a in enumerate(arrays) if a is not None]
        cast = amp_cast_inputs(name, [arrays[i] for i in present])
        for i, a in zip(present, cast):
            arrays[i] = a
    xa, wa, ba, ra = arrays
    if is_rms:
        y, r = _rms_res_fwd_array(xa, ra, wa, eps)
    else:
        y, r = _ln_res_fwd_array(xa, ra, wa, ba, eps)
    out_arrays = [y, r]

    def _diff(t: Any) -> bool:
        return (
            isinstance(t, Tensor)
            and not t.stop_gradient
            and jnp.issubdtype(jnp.dtype(t.dtype), jnp.inexact)
        )

    record = _ag.is_grad_enabled() and any(_diff(t) for t in inputs)
    node = None
    if record:
        diff_pos = [i for i, t in enumerate(inputs) if _diff(t)]
        diff_tensors = [inputs[i] for i in diff_pos]
        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_arrays]
        _flat, out_treedef = jax.tree_util.tree_flatten(tuple(out_arrays))
        consts = list(arrays)

        def vjp_fn(cots: Any) -> Tuple[Any, ...]:
            gy, gr = cots
            if gy is None:
                gy = jnp.zeros(out_avals[0].shape, out_avals[0].dtype)
            if is_rms:
                dr, dw = _rms_res_bwd_array(gy, r, wa, eps)
                db = None
            else:
                dr, dw, db = _ln_res_bwd_array(gy, r, wa, eps)
            if gr is not None:
                dr = dr + gr.astype(dr.dtype)
            by_pos = {0: dr, 1: dw, 2: db, 3: dr}
            return tuple(by_pos[p] for p in diff_pos)

        def closed(*diff_arrays: Any) -> Tuple[Any, ...]:
            vals = list(consts)
            for p, arr in zip(diff_pos, diff_arrays):
                vals[p] = arr
            rr = vals[0] + vals[3]
            if is_rms:
                xf = rr.astype(jnp.float32)
                var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                out = (xf * jax.lax.rsqrt(var + eps)).astype(rr.dtype) * vals[1]
            else:
                mu = jnp.mean(rr, axis=-1, keepdims=True)
                var = jnp.mean(jnp.square(rr - mu), axis=-1, keepdims=True)
                out = (rr - mu) * jax.lax.rsqrt(var + eps) * vals[1]
                if vals[2] is not None:
                    out = out + vals[2]
            return out, rr

        node = _ag.GradNode(
            name, vjp_fn, diff_tensors, out_avals,
            fwd_fn=closed, out_treedef=out_treedef,
        )

    if _dispatch._NAN_CHECK[0]:
        _dispatch._check_nan_inf(name, out_arrays)
    if _dispatch.op_stats_hook is not None:  # amp.debugging operator stats
        _dispatch.op_stats_hook(name, out_arrays)
    result = []
    for j, arr in enumerate(out_arrays):
        t = Tensor(arr, stop_gradient=(node is None))
        if node is not None:
            t._grad_node = node
            t._grad_output_index = j
        result.append(t)
    return tuple(result)


def fused_rms_norm_residual(
    x: Any, norm_weight: Any, residual: Any, epsilon: float = 1e-6
) -> Tuple[Any, Any]:
    """``r = x + residual; y = rms_norm(r, norm_weight)`` as ONE dispatch with
    an explicit tape backward (standalone adjoint kernel — no jax AD over the
    pallas_call). Returns ``(y, r)``; ``r`` feeds the next residual hop."""
    return _residual_norm_entry(
        "fused_rms_norm_residual", x, norm_weight, None, residual,
        float(epsilon), True,
    )


def fused_layer_norm_residual(
    x: Any, norm_weight: Any, norm_bias: Any, residual: Any,
    epsilon: float = 1e-5,
) -> Tuple[Any, Any]:
    """``r = x + residual; y = layer_norm(r, norm_weight, norm_bias)`` as ONE
    dispatch with an explicit tape backward. Returns ``(y, r)``."""
    return _residual_norm_entry(
        "fused_layer_norm_residual", x, norm_weight, norm_bias, residual,
        float(epsilon), False,
    )


def fused_embed_rms_norm(
    input_ids: Any, embed_weight: Any, norm_weight: Any, epsilon: float = 1e-6
) -> Tuple[Any, Any]:
    """Chunk-step entry fusion: token-id gather + embedding lookup + first
    decoder layer's pre-attention RMSNorm in ONE dispatch (the scalar-
    prefetched ids steer the embedding-row BlockSpec). Inference-only — the
    serving step never differentiates; training embeds through the regular
    op. Returns ``(emb, y)`` Tensors: the raw rows (residual stream seed) and
    their normed form."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    table = embed_weight._data if isinstance(embed_weight, Tensor) else jnp.asarray(embed_weight)
    w = norm_weight._data if isinstance(norm_weight, Tensor) else jnp.asarray(norm_weight)
    eps = float(epsilon)
    if (
        w.dtype == table.dtype
        and table.shape[-1] % 128 == 0
        # under the engine's tp mesh the table is vocab-parallel: the XLA
        # gather is what GSPMD can split
        and pallas_enabled("use_pallas_fused", bare="fused_embed_norm")
    ):
        try:
            from paddle_tpu.kernels.fused import fused_embed_rms_norm_pallas

            emb, y = fused_embed_rms_norm_pallas(ids, table, w, eps)
            return Tensor(emb, stop_gradient=True), Tensor(y, stop_gradient=True)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_embed_norm", exc)
    # exact unfused composition: XLA gather, then rms_norm's op order
    emb = table[ids]
    xf = emb.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = (xf * jax.lax.rsqrt(var + eps)).astype(emb.dtype) * w
    return Tensor(emb, stop_gradient=True), Tensor(y, stop_gradient=True)


__all__ += [
    "fused_rms_norm_residual",
    "fused_layer_norm_residual",
    "fused_embed_rms_norm",
]
