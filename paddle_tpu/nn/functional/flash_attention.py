"""Attention APIs: flash_attention, scaled_dot_product_attention, and the
FlashMask sparse-mask variant.

Reference surface: ``python/paddle/nn/functional/flash_attention.py`` —
``flash_attention:195``, ``scaled_dot_product_attention:976``,
``flashmask_attention:1098`` (the fork's marquee feature: column-sparse mask
encoding via ``startend_row_indices [B, H, S, {1,2,4}]`` giving O(S) mask
memory; kernel plumbing ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:353``).

On TPU the fast path is a Pallas flash-attention kernel
(``paddle_tpu.kernels.flash_attention``); this module provides the API surface,
mask semantics, and an XLA fallback that XLA fuses reasonably well. The
Pallas path is selected by ``FLAGS_use_pallas_attention`` when running on TPU
with supported shapes/dtypes.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.ops.registry import defop

__all__ = [
    "flash_attention",
    "scaled_dot_product_attention",
    "flashmask_attention",
    "flash_attn_unpadded",
    "flash_attn_qkvpacked",
    "flash_attn_varlen_qkvpacked",
    "sdp_kernel",
]


def _use_pallas(q) -> bool:
    from paddle_tpu.kernels.select import pallas_enabled

    # pre-trace applicability: Mosaic-lowerable head dim (64-lane aligned) —
    # checked BEFORE tracing because a lowering failure inside a captured
    # train step cannot fall back (see kernels/select.py)
    if q.shape[-1] % 64 != 0:
        return False
    return pallas_enabled("use_pallas_attention", bare="flash_attention")


def _xla_attention(q, k, v, bias=None, causal=False, scale=None, window=None, dropout=0.0, dropout_key=None):
    """Reference attention in XLA ops. Layout: [B, S, H, D] (paddle flash
    attention layout). Computes in fp32 for softmax stability."""
    in_dtype = q.dtype
    d = q.shape[-1]
    scale = scale if scale is not None else (1.0 / (d**0.5))
    # [B, H, S, D]
    qh = jnp.moveaxis(q, 2, 1).astype(jnp.float32)
    kh = jnp.moveaxis(k, 2, 1).astype(jnp.float32)
    vh = jnp.moveaxis(v, 2, 1).astype(jnp.float32)
    # grouped-query attention: repeat kv heads
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    neg = jnp.asarray(-1e30, jnp.float32)
    if causal:
        row = jnp.arange(sq)[:, None] + (sk - sq)
        col = jnp.arange(sk)[None, :]
        logits = jnp.where(col <= row, logits, neg)
    if window is not None:
        left, right = window
        row = jnp.arange(sq)[:, None] + (sk - sq)
        col = jnp.arange(sk)[None, :]
        ok = jnp.ones((sq, sk), bool)
        if left is not None and left >= 0:
            ok = ok & (col >= row - left)
        if right is not None and right >= 0:
            ok = ok & (col <= row + right)
        logits = jnp.where(ok, logits, neg)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.moveaxis(out, 1, 2).astype(in_dtype)


@defop("flash_attention", tensor_method=None)
def _flash_attention_op(q, k, v, key=None, dropout=0.0, causal=False, scale=None):
    if _use_pallas(q) and dropout == 0.0:
        try:
            from paddle_tpu.kernels.flash_attention import flash_attention_pallas

            return flash_attention_pallas(q, k, v, causal=causal, scale=scale)
        except Exception as exc:  # pragma: no cover - TPU-only path
            from paddle_tpu.kernels.select import warn_fallback

            warn_fallback("flash_attention", exc)
    return _xla_attention(q, k, v, causal=causal, scale=scale, dropout=dropout, dropout_key=key)


def flash_attention(
    query,
    key,
    value,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """``paddle.nn.functional.flash_attention.flash_attention`` parity.

    Layout [batch, seqlen, num_heads, head_dim]; returns (out, softmax) tuple
    like the reference (softmax is None unless return_softmax).
    """
    import paddle_tpu.core.rng as _rng

    drop_key = _rng.next_key() if (dropout > 0.0 and training) else None
    out = _flash_attention_op(
        query, key, value, drop_key, dropout=dropout if training else 0.0, causal=causal
    )
    if return_softmax:
        return out, None
    return out, None


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
):
    """``scaled_dot_product_attention`` parity (reference ``flash_attention.py:976``).

    attn_mask: broadcastable additive mask [B, H, Sq, Sk] (or boolean where
    True = keep, matching paddle semantics for bool masks).
    """

    import paddle_tpu.core.rng as _rng

    drop_key = _rng.next_key() if (dropout_p > 0.0 and training) else None

    def _impl(q, k, v, mask, dkey):
        bias = None
        if mask is not None:
            if mask.dtype == jnp.bool_:
                bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
            else:
                bias = mask
        return _xla_attention(
            q, k, v, bias=bias, causal=is_causal,
            dropout=dropout_p if training else 0.0, dropout_key=dkey,
        )

    from paddle_tpu.core.dispatch import call_op

    return call_op("scaled_dot_product_attention", _impl, query, key, value, attn_mask, drop_key)


def flash_attn_unpadded(
    query,
    key,
    value,
    cu_seqlens_q,
    cu_seqlens_k,
    max_seqlen_q,
    max_seqlen_k,
    scale=1.0,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """Varlen attention (reference ``flash_attn_unpadded:593``): packed
    [total_tokens, H, D] with cu_seqlens prefix sums. Implemented via a
    document-mask attention over the packed layout — the same trick FlashMask
    encodes sparsely."""
    from paddle_tpu.core.dispatch import call_op

    def _impl(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        total_k = k.shape[0]
        # segment ids from cu_seqlens
        seg_q = jnp.cumsum(
            jnp.zeros(total_q, jnp.int32).at[cu_q[1:-1]].add(1)
        )
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[cu_k[1:-1]].add(1)
        )
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cu_q, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(cu_k, seg_k)
            mask = mask & (pos_k[None, :] <= pos_q[:, None])
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        logits = jnp.einsum("qhd,khd->hqk", qf, kf) * scale
        logits = jnp.where(mask[None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, vf)
        return out.astype(q.dtype)

    out = call_op("flash_attn_unpadded", _impl, query, key, value, cu_seqlens_q, cu_seqlens_k)
    return out, None


def flashmask_attention(
    query,
    key,
    value,
    startend_row_indices=None,
    dropout=0.0,
    causal=True,
    window_size=None,
    return_softmax_lse=False,
    return_seed_offset=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """FlashMask attention (reference ``flash_attention.py:1098`` +
    ``flash_attn_kernel.cu:353-460``).

    ``startend_row_indices``: int32 [B, H_mask, Sk, C] with C in {1, 2, 4}
    column-sparse mask encoding. For column j (a key position), the entries
    give row bounds (query positions) that are masked out:

    - C == 1, causal: rows in [start_j, Sq) are masked (downward mask; e.g.
      document masks for packed sequences).
    - C == 2, causal: rows in [start_j, end_j) are masked (e.g. sliding window
      / doc mask with global tokens).
    - C == 4, non-causal or full form: [LTS, LTE, UTS, UTE] — lower-triangle
      rows in [LTS, LTE) masked, upper-triangle rows in [UTS, UTE) masked.

    H_mask may be 1 (broadcast over heads) or num_heads.
    """
    if startend_row_indices is None:
        return flash_attention(query, key, value, dropout=dropout, causal=causal)[0]

    from paddle_tpu.core.dispatch import call_op

    def _impl(q, k, v, idx):
        if _use_pallas(q):
            try:
                from paddle_tpu.kernels.flashmask import flashmask_attention_pallas

                return flashmask_attention_pallas(q, k, v, idx, causal=causal)
            except Exception as exc:  # pragma: no cover - TPU-only path
                from paddle_tpu.kernels.select import warn_fallback

                warn_fallback("flashmask_attention", exc)
        bias = make_flashmask_bias(idx, q.shape[1], k.shape[1], causal)
        return _xla_attention(q, k, v, bias=bias, causal=causal)

    return call_op("flashmask_attention", _impl, query, key, value, startend_row_indices)


def make_flashmask_bias(startend_row_indices, sq: int, sk: int, causal: bool):
    """Densify FlashMask startend_row_indices into an additive bias
    [B, H_mask, Sq, Sk] (used by the XLA fallback and for parity tests against
    the Pallas kernel)."""
    idx = startend_row_indices  # [B, Hm, Sk, C]
    c = idx.shape[-1]
    rows = jnp.arange(sq)[:, None]  # [Sq, 1] query positions
    neg = jnp.asarray(-1e30, jnp.float32)

    def col_mask(bounds):  # bounds [B, Hm, Sk, C] → masked bool [B, Hm, Sq, Sk]
        if c == 1:
            start = bounds[..., 0]  # [B, Hm, Sk]
            masked = rows[None, None] >= start[:, :, None, :]
        elif c == 2:
            start = bounds[..., 0]
            end = bounds[..., 1]
            masked = (rows[None, None] >= start[:, :, None, :]) & (
                rows[None, None] < end[:, :, None, :]
            )
        elif c == 4:
            lts = bounds[..., 0]
            lte = bounds[..., 1]
            uts = bounds[..., 2]
            ute = bounds[..., 3]
            masked = (
                (rows[None, None] >= lts[:, :, None, :])
                & (rows[None, None] < lte[:, :, None, :])
            ) | (
                (rows[None, None] >= uts[:, :, None, :])
                & (rows[None, None] < ute[:, :, None, :])
            )
        else:
            raise ValueError(f"startend_row_indices last dim must be 1/2/4, got {c}")
        return masked

    masked = col_mask(idx)
    return jnp.where(masked, neg, 0.0)


class sdp_kernel:  # noqa: N801 - context-manager compat shim
    """Kernel-selection context (torch/paddle compat); on TPU the Pallas flag
    is the only switch."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        self._enable_flash = enable_flash

    def __enter__(self):
        from paddle_tpu.flags import set_flags

        self._prev = GLOBAL_FLAGS.get("use_pallas_attention")
        set_flags({"use_pallas_attention": self._enable_flash})
        return self

    def __exit__(self, *a):
        from paddle_tpu.flags import set_flags

        set_flags({"use_pallas_attention": self._prev})


def flash_attn_qkvpacked(
    qkv,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """Packed-QKV flash attention (reference ``flash_attn_qkvpacked``):
    ``qkv`` is ``[B, S, 3, H, D]`` (or ``[B, S, 3*H, D]``); unpacks and
    dispatches to :func:`flash_attention`."""
    if len(qkv.shape) == 4:  # [B, S, 3*H, D]
        h3 = qkv.shape[2]
        qkv = qkv.reshape([qkv.shape[0], qkv.shape[1], 3, h3 // 3, qkv.shape[3]])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    return flash_attention(
        q, k, v, dropout=dropout, causal=causal, return_softmax=return_softmax,
        training=training,
    )


def flash_attn_varlen_qkvpacked(
    qkv,
    cu_seqlens_q,
    cu_seqlens_k,
    max_seqlen_q,
    max_seqlen_k,
    scale=1.0,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """Packed-QKV varlen attention (reference ``flash_attn_varlen_qkvpacked``)
    over the unpadded [total_tokens, 3, H, D] layout."""
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    return flash_attn_unpadded(
        q, k, v, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k,
        scale=scale, dropout=dropout, causal=causal,
        return_softmax=return_softmax, training=training,
    )
