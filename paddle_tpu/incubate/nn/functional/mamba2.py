"""Mamba-2 (state-space duality) pieces as plain XLA compositions over a CHUNK
of rows with a carried state: what the serving step's recurrent sets
(``inference/paged_kv.py::RecurrentState``) and a model's cache-less forward
are both built from.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,    y_t = S_t C_t + D x_t

over a chunk of ``C`` rows is, with ``cum_t = sum_{s<=t} dt_s A`` (the log of
the decay since the chunk began),

    y_t = exp(cum_t) C_t S_0  +  sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s  +  D x_t
    S_C = exp(cum_C) S_0      +  sum_s exp(cum_C - cum_s) dt_s x_s (x) B_s

so a row whose ``dt`` is 0 neither decays the state nor adds to it: that is
how padded rows are masked (the caller zeroes their ``dt``). All arithmetic is
float32; the two contractions that touch the carried state run at ``highest``
so the float32 state is not rounded to bfloat16 on its way through the MXU.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "causal_conv_chunk", "gated_group_rms_norm", "split_conv_channels", "ssd_chunk", "ssd_chunk_slots", "ssd_sequence",
]

_STATE_PRECISION = jax.lax.Precision.HIGHEST


def causal_conv_chunk(
    x: jax.Array,  # [S, C, W] the chunk's inputs
    tail: jax.Array,  # [S, K-1, W] the K-1 inputs before the chunk (zeros: a sequence's start)
    weight: jax.Array,  # [K, W] depthwise taps; tap K-1 multiplies the current row
    bias: jax.Array,  # [W]
    q_lens: jax.Array,  # [S] valid rows of the chunk
) -> Tuple[jax.Array, jax.Array]:
    """``silu(conv1d_causal(x) + bias)`` of the chunk continuing ``tail``, and
    the tail after its ``q_lens`` valid rows (``q_lens == 0``: unchanged)."""
    k, c = weight.shape[0], x.shape[1]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # row j is input j - (K-1)
    w = weight.astype(jnp.float32)
    acc = bias.astype(jnp.float32)
    for j in range(k):
        acc = acc + padded[:, j:j + c].astype(jnp.float32) * w[j]
    rows = q_lens[:, None] + jnp.arange(k - 1, dtype=q_lens.dtype)[None, :]  # the last K-1 valid inputs
    new_tail = jnp.take_along_axis(padded, rows[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(x.dtype), new_tail.astype(tail.dtype)


def split_conv_channels(xbc: jax.Array, heads: int, head_dim: int, groups: int, state: int):
    """The conv's output ``[..., T, H P + 2 G N]`` as ``x [..., T, H, P]``, ``B`` and ``C [..., T, G, N]``."""
    lead, inner, gn = xbc.shape[:-1], heads * head_dim, groups * state
    return (xbc[..., :inner].reshape(*lead, heads, head_dim),
            xbc[..., inner:inner + gn].reshape(*lead, groups, state),
            xbc[..., inner + gn:].reshape(*lead, groups, state))


def _within_chunk(x, dt, a, b, c):
    """What a chunk computes without the carried state, from float32 ``x``,
    ``b``, ``c``: ``(y [S, C, H, P]`` of the chunk's own rows, ``cum [S, C, H]``
    the log of the decay since the chunk began, ``xs [S, C, H, P]`` each row's
    ``x`` scaled by ``dt`` and its decay to the chunk's end``)``."""
    n_rows, r = x.shape[1], x.shape[2] // b.shape[2]
    cum = jnp.cumsum(dt * a.astype(jnp.float32), axis=1)  # [S, C, H], <= 0
    causal = jnp.tril(jnp.ones((n_rows, n_rows), bool))[None, :, :, None]
    # exp only of what is kept: above the diagonal cum_t - cum_s is positive and may overflow
    decay = jnp.exp(jnp.where(causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))  # [S, t, s, H]
    cb = jnp.einsum("stgn,sugn->stug", c, b, precision=_STATE_PRECISION)
    scores = jnp.repeat(cb, r, axis=-1) * decay * dt[:, None, :, :]
    y = jnp.einsum("stuh,suhp->sthp", scores, x, precision=_STATE_PRECISION)
    to_end = dt * jnp.exp(cum[:, -1:, :] - cum)  # [S, C, H]
    return y, cum, x * to_end[..., None]


def _carry_xla(c, b, xs, decay, state):
    """The carried state's part of a chunk as XLA runs it: ``(carried [S, C,
    H, P]``, what the chunk's rows read of ``state``; the state after the
    chunk``)`` from ``decay = exp(cum_C) [S, H]``."""
    s, n_rows, g, n = c.shape
    h, p = state.shape[1], state.shape[2]
    r = h // g
    carried = jnp.einsum("stgn,sgrpn->stgrp", c, state.reshape(s, g, r, p, n),
                         precision=_STATE_PRECISION).reshape(s, n_rows, h, p)
    added = jnp.einsum("scgrp,scgn->sgrpn", xs.reshape(s, n_rows, g, r, p), b,
                       precision=_STATE_PRECISION).reshape(s, h, p, n)
    return carried, decay[:, :, None, None] * state + added


def _chunk(x, dt, a, b, c, d_skip, carry):
    """One chunk around ``carry(c, b, xs, decay) -> (carried, state after)``."""
    x, b, c = (t.astype(jnp.float32) for t in (x, b, c))
    y, cum, xs = _within_chunk(x, dt, a, b, c)
    carried, state = carry(c, b, xs, jnp.exp(cum[:, -1]))
    return y + carried * jnp.exp(cum)[..., None] + d_skip.astype(jnp.float32)[:, None] * x, state


def ssd_chunk(
    x: jax.Array,  # [S, C, H, P]
    dt: jax.Array,  # [S, C, H] float32, after softplus; 0 on rows that must not advance the state
    a: jax.Array,  # [H] negative
    b: jax.Array,  # [S, C, G, N]
    c: jax.Array,  # [S, C, G, N]
    d_skip: jax.Array,  # [H]
    state: jax.Array,  # [S, H, P, N] float32
) -> Tuple[jax.Array, jax.Array]:
    """One chunk of the recurrence from ``state``: ``(y [S, C, H, P] float32,
    the state after the chunk)``. Head ``h`` reads group ``h // (H / G)``."""
    return _chunk(x, dt, a, b, c, d_skip, lambda *rows: _carry_xla(*rows, state))


def ssd_chunk_slots(
    x: jax.Array,  # [S, C, H, P]
    dt: jax.Array,  # [S, C, H] float32; 0 on rows that must not advance the state
    a: jax.Array,
    b: jax.Array,  # [S, C, G, N]
    c: jax.Array,
    d_skip: jax.Array,
    plane: jax.Array,  # [S, H, P, N] float32: every slot's state, the caller's to give away
    live: jax.Array,  # [S] bool: the slot has rows whose dt is not 0
    fresh: jax.Array,  # [S] bool: the slot starts from ZERO state, whatever the plane holds
) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssd_chunk` over a PLANE of per-slot states that outlives the
    call (the serving step's, donated): on a TPU one Pallas kernel
    (``kernels/ssm_scan.py``) reads each slot's tile once, contracts it with
    ``c``, decays it, adds the chunk's rows and writes it back in place, and
    moves nothing for a slot that is not ``live``; elsewhere, and as the
    kernel's reference, the XLA composition."""
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    if pallas_enabled("use_pallas_fused", bare="ssm_state_scan"):
        from paddle_tpu.kernels.ssm_scan import ssm_state_scan

        try:
            return _chunk(x, dt, a, b, c, d_skip, lambda *rows: ssm_state_scan(*rows, plane, live, fresh))
        except Exception as exc:  # noqa: BLE001 - XLA composition below
            warn_fallback("ssm_state_scan", exc)
    return ssd_chunk(x, dt, a, b, c, d_skip, jnp.where(fresh[:, None, None, None], 0.0, plane))


def ssd_sequence(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B, T, H]
    a: jax.Array,
    b: jax.Array,  # [B, T, G, N]
    c: jax.Array,
    d_skip: jax.Array,
    chunk: int,
) -> jax.Array:
    """The recurrence over whole sequences from a zero state (no cache): a
    ``lax.scan`` of :func:`ssd_chunk` over chunks of ``chunk`` rows, the last
    padded with rows whose ``dt`` is 0. Returns ``y [B, T, H, P]`` float32."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    pad = -t % chunk
    parts = []
    for arr in (x, dt.astype(jnp.float32), b, c):
        arr = jnp.pad(arr, [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2))
        parts.append(jnp.moveaxis(arr.reshape(bsz, (t + pad) // chunk, chunk, *arr.shape[2:]), 1, 0))

    def step(state, inp):
        y, state = ssd_chunk(inp[0], inp[1], a, inp[2], inp[3], d_skip, state)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32), tuple(parts))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, h, p)[:, :t]


def gated_group_rms_norm(y: jax.Array, gate: jax.Array, weight: jax.Array, groups: int, eps: float) -> jax.Array:
    """``weight * rmsnorm(y * silu(gate))`` with the mean square taken over
    each of ``groups`` equal slices of the last axis; float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    shape = v.shape
    v = v.reshape(*shape[:-1], groups, shape[-1] // groups)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
    return v.reshape(shape) * weight.astype(jnp.float32)
