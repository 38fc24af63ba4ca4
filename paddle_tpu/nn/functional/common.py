"""Common nn functional ops: linear, embedding, dropout, norm layers, one_hot…

Reference: ``python/paddle/nn/functional/common.py`` / ``input.py`` / ``norm.py``
over PHI kernels (``layer_norm``, ``rms_norm``, ``embedding``, ``dropout``).
On TPU all of these are XLA-fused elementwise/reduction graphs; rms_norm also
has a Pallas fast path (see ``paddle_tpu.kernels``).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

import paddle_tpu.core.rng as _rng
from paddle_tpu.ops.registry import defop

__all__ = [
    "linear",
    "weight_only_linear",
    "embedding",
    "one_hot",
    "dropout",
    "dropout2d",
    "dropout3d",
    "alpha_dropout",
    "layer_norm",
    "rms_norm",
    "group_norm",
    "instance_norm",
    "batch_norm",
    "local_response_norm",
    "normalize",
    "cosine_similarity",
    "pixel_shuffle",
    "pixel_unshuffle",
    "channel_shuffle",
    "unfold",
    "fold",
    "bilinear",
    "label_smooth",
]


@defop("linear", tensor_method=None)
def linear(x, weight, bias=None):
    """y = x @ W (+ b). Weight layout [in, out] (paddle convention, reference
    ``python/paddle/nn/functional/common.py`` linear)."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


@defop("weight_only_linear", tensor_method=None)
def weight_only_linear(x, weight, weight_scale, bias=None):
    """y = x @ dequant(W) (+ b) with W stored int8 and per-output-channel
    fp32 scales (reference ``paddle.nn.quant.weight_only_linear``). The
    dequant happens inside the matmul (``kernels.quant.int8_weight_matmul``)
    — a bf16 copy of the weight never materializes. Inference-only."""
    from paddle_tpu.kernels.quant import int8_weight_matmul

    out = int8_weight_matmul(x, weight, weight_scale)
    if bias is not None:
        out = out + bias
    return out


@defop("embedding_fn", tensor_method=None)
def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


@defop("one_hot", tensor_method=None)
def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


def _dropout_impl(x, p, training, mode, key, broadcast_dims=()):
    if not training or p == 0.0:
        return x
    keep = 1.0 - p
    mask_shape = list(x.shape)
    for d in broadcast_dims:
        mask_shape[d] = 1
    mask = jax.random.bernoulli(key, keep, tuple(mask_shape))
    if mode in ("upscale_in_train", "dropout"):
        return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))
    # downscale_in_infer: scale at inference instead (train applies raw mask)
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


@defop("dropout_fn", tensor_method=None)
def _dropout_op(x, key, p=0.5, training=True, mode="upscale_in_train", broadcast_dims=()):
    return _dropout_impl(x, p, training, mode, key, broadcast_dims)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        return x
    bdims = ()
    if axis is not None:
        axes = axis if isinstance(axis, (list, tuple)) else [axis]
        ndim = x.ndim
        bdims = tuple(d for d in range(ndim) if d not in [a % ndim for a in axes])
    return _dropout_op(x, _rng.next_key(), p=p, training=training, mode=mode, broadcast_dims=bdims)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    if not training or p == 0.0:
        return x
    bdims = (2, 3) if data_format == "NCHW" else (1, 2)
    return _dropout_op(x, _rng.next_key(), p=p, training=training, broadcast_dims=bdims)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    if not training or p == 0.0:
        return x
    bdims = (2, 3, 4) if data_format == "NCDHW" else (1, 2, 3)
    return _dropout_op(x, _rng.next_key(), p=p, training=training, broadcast_dims=bdims)


@defop("alpha_dropout_fn", tensor_method=None)
def _alpha_dropout_op(x, key, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    a = (keep + alpha_p**2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    return a * jnp.where(mask, x, alpha_p) + b


def alpha_dropout(x, p=0.5, training=True, name=None):
    return _alpha_dropout_op(x, _rng.next_key(), p=p, training=training)


@defop("layer_norm_fn", tensor_method=None)
def layer_norm(x, normalized_shape=None, weight=None, bias=None, epsilon=1e-5):
    if normalized_shape is None:
        axes = (x.ndim - 1,)
    else:
        n = len(normalized_shape) if isinstance(normalized_shape, (list, tuple)) else 1
        axes = tuple(range(x.ndim - n, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@defop("rms_norm_fn", tensor_method=None)
def rms_norm(x, weight=None, epsilon=1e-6, upcast=True):
    """RMSNorm (reference fused ``rms_norm`` kernel,
    ``paddle/phi/kernels/gpu/rms_norm_kernel``): compute in fp32, scale, cast
    back — numerics match the fused GPU kernel's accumulate-in-float behavior.
    On TPU the Pallas fused kernel pins the single-HBM-round-trip schedule."""
    from paddle_tpu.kernels.select import pallas_enabled, per_shard, warn_fallback

    if (
        weight is not None
        and upcast  # kernel always accumulates fp32
        and weight.dtype == x.dtype  # kernel returns x.dtype; no promotion
        and x.shape[-1] % 128 == 0  # lane-aligned → guaranteed lowerable
        and pallas_enabled("use_pallas_fused", bare="fused_rms_norm", row_wise=True)
    ):
        try:
            from paddle_tpu.kernels.fused import fused_rms_norm_pallas

            return per_shard(lambda x_, w_: fused_rms_norm_pallas(x_, w_, epsilon))(x, weight)
        except Exception as exc:  # pragma: no cover - TPU-only path
            warn_fallback("fused_rms_norm", exc)
    dtype = x.dtype
    if upcast:
        x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + epsilon)
    out = out.astype(dtype)
    if weight is not None:
        out = out * weight
    return out


@defop("group_norm_fn", tensor_method=None)
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=axes, keepdims=True)
    out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    if weight is not None:
        out = out * weight.reshape(1, c, *([1] * len(spatial)))
    if bias is not None:
        out = out + bias.reshape(1, c, *([1] * len(spatial)))
    if data_format == "NHWC":
        out = jnp.moveaxis(out, 1, -1)
    return out


@defop("instance_norm_fn", tensor_method=None)
def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    c = x.shape[1]
    if weight is not None:
        out = out * weight.reshape(1, c, *([1] * (x.ndim - 2)))
    if bias is not None:
        out = out + bias.reshape(1, c, *([1] * (x.ndim - 2)))
    return out


@defop("batch_norm_fn", tensor_method=None)
def _batch_norm_op(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    epsilon=1e-5,
    data_format="NCHW",
):
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(d for d in range(x.ndim) if d != ch_axis)
    if training:
        mean = jnp.mean(x, axis=reduce_axes)
        var = jnp.var(x, axis=reduce_axes)
    else:
        mean, var = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    out = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, mean, var


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    name=None,
):
    """Functional batch norm; updates running stats in-place when training
    (matching the reference's mutable running-stat semantics)."""
    import paddle_tpu

    out, mean, var = _batch_norm_op(
        x, running_mean, running_var, weight, bias, training=training,
        epsilon=epsilon, data_format=data_format,
    )
    if training and hasattr(running_mean, "set_value"):
        with paddle_tpu.no_grad():
            running_mean.set_value(momentum * running_mean.data + (1 - momentum) * mean.detach().data)
            running_var.set_value(momentum * running_var.data + (1 - momentum) * var.detach().data)
    return out


@defop("local_response_norm_fn", tensor_method=None)
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
    ch_axis = 1 if data_format == "NCHW" else x.ndim - 1
    sq = jnp.square(x)
    moved = jnp.moveaxis(sq, ch_axis, -1)
    pad = [(0, 0)] * (moved.ndim - 1) + [(size // 2, (size - 1) // 2)]
    padded = jnp.pad(moved, pad)
    window = sum(padded[..., i : i + moved.shape[-1]] for i in range(size))
    denom = jnp.power(k + alpha * window / size, beta)
    return x / jnp.moveaxis(denom, -1, ch_axis)


@defop("normalize_fn", tensor_method=None)
def normalize(x, p=2.0, axis=1, epsilon=1e-12):
    n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p)
    return x / jnp.maximum(n, epsilon)


@defop("cosine_similarity_fn", tensor_method=None)
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


@defop("pixel_shuffle_fn", tensor_method=None)
def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
        return x.reshape(n, c // (r * r), h * r, w * r)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(n, h * r, w * r, c // (r * r))


@defop("pixel_unshuffle_fn", tensor_method=None)
def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c, h // r, r, w // r, r)
        x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
        return x.reshape(n, c * r * r, h // r, w // r)
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(n, h // r, w // r, c * r * r)


@defop("channel_shuffle_fn", tensor_method=None)
def channel_shuffle(x, groups, data_format="NCHW"):
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, groups, c // groups, h, w)
        x = jnp.transpose(x, (0, 2, 1, 3, 4))
        return x.reshape(n, c, h, w)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, groups, c // groups)
    x = jnp.transpose(x, (0, 1, 2, 4, 3))
    return x.reshape(n, h, w, c)


@defop("unfold_fn", tensor_method=None)
def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    ks = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) else [kernel_sizes] * 2
    st = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    pd = paddings if isinstance(paddings, (list, tuple)) else [paddings] * 2
    dl = dilations if isinstance(dilations, (list, tuple)) else [dilations] * 2
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(ks),
        window_strides=tuple(st),
        padding=[(pd[0], pd[0]), (pd[1], pd[1])],
        rhs_dilation=tuple(dl),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return patches.reshape(n, c * ks[0] * ks[1], -1)


@defop("fold_fn", tensor_method=None)
def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    ks = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) else [kernel_sizes] * 2
    st = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    pd = paddings if isinstance(paddings, (list, tuple)) else [paddings] * 2
    os_ = output_sizes if isinstance(output_sizes, (list, tuple)) else [output_sizes] * 2
    n, ckk, l = x.shape
    c = ckk // (ks[0] * ks[1])
    oh = (os_[0] + 2 * pd[0] - ks[0]) // st[0] + 1
    ow = (os_[1] + 2 * pd[1] - ks[1]) // st[1] + 1
    cols = x.reshape(n, c, ks[0], ks[1], oh, ow)
    out = jnp.zeros((n, c, os_[0] + 2 * pd[0], os_[1] + 2 * pd[1]), x.dtype)
    for i in range(ks[0]):
        for j in range(ks[1]):
            out = out.at[
                :, :, i : i + oh * st[0] : st[0], j : j + ow * st[1] : st[1]
            ].add(cols[:, :, i, j])
    return out[:, :, pd[0] : pd[0] + os_[0], pd[1] : pd[1] + os_[1]]


@defop("bilinear_fn", tensor_method=None)
def bilinear(x1, x2, weight, bias=None):
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


@defop("label_smooth_fn", tensor_method=None)
def label_smooth(label, prior_dist=None, epsilon=0.1):
    k = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / k
