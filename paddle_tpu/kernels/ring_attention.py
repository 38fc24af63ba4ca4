"""Ring attention: context parallelism over a sequence-sharded mesh axis.

The reference snapshot has NO context-parallel attention (SURVEY §5.7: its
long-context strategy is FlashMask + Megatron-SP + a 'sep' axis whose
attention exchange is left to model code). This module goes beyond it: a
first-class blockwise ring attention — KV chunks rotate around the ICI ring
via ``lax.ppermute`` while each device accumulates online-softmax partial
results for its local Q chunk. Compute per step overlaps with the next
chunk's permute (XLA schedules the collective-permute concurrently), HBM
never holds more than the local chunk, and sequence length scales linearly
with the ring size.

Differentiable by construction: ``jax.grad`` through the scan + ppermute
yields the reversed ring for backward.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30

__all__ = ["ring_flash_attention"]


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Any,
    axis_name: str = "sep",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over paddle layout ``[B, S, H, D]``.

    ``q``/``k``/``v`` are global-view arrays; the sequence dim is sharded over
    ``axis_name`` inside (inputs need not be pre-sharded — shard_map partitions
    them). Ring order IS sequence order: chunk c holds positions
    ``[c*S/N, (c+1)*S/N)``. Returns the global ``[B, S, H, D]`` output sharded
    the same way.
    """
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    n = jmesh.shape[axis_name]
    b, s, h, d = q.shape
    hk = k.shape[2]
    if s % n != 0:
        raise ValueError(f"sequence length {s} not divisible by ring size {n}")
    if h % hk != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hk}")
    if scale is None:
        scale = 1.0 / (d**0.5)
    if n == 1:
        from paddle_tpu.nn.functional.flash_attention import _xla_attention

        return _xla_attention(q, k, v, causal=causal, scale=scale)
    group = h // hk
    s_local = s // n
    perm = [(i, (i + 1) % n) for i in range(n)]

    spec = P(None, axis_name, None, None)

    def local_fn(q, k, v):
        # [B, S/N, H, D] → grouped [B, HK, G, S/N, D] fp32; KV stays at its
        # unrepeated head count so each ring hop moves only unique KV bytes
        qh = jnp.moveaxis(q, 2, 1).astype(jnp.float32) * scale
        qh = qh.reshape(b, hk, group, s_local, d)
        kh = jnp.moveaxis(k, 2, 1).astype(jnp.float32)  # [B, HK, S/N, D]
        vh = jnp.moveaxis(v, 2, 1).astype(jnp.float32)
        idx = jax.lax.axis_index(axis_name)
        rows = idx * s_local + jax.lax.broadcasted_iota(jnp.int32, (s_local, 1), 0)

        def partial_attn(carry, k_cur, v_cur, src):
            acc, m, l = carry
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qh, k_cur)
            if causal:
                cols = src * s_local + jax.lax.broadcasted_iota(
                    jnp.int32, (1, s_local), 1
                )
                logits = jnp.where(cols > rows, NEG_INF, logits)
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, v_cur)
            return acc_new, m_new, l_new

        acc0 = jnp.zeros((b, hk, group, s_local, d), jnp.float32)
        m0 = jnp.full((b, hk, group, s_local, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, group, s_local, 1), jnp.float32)
        # tick 0: the local chunk, no communication
        carry0 = partial_attn((acc0, m0, l0), kh, vh, idx)

        def step(carry, t):
            k_cur, v_cur, acc, m, l = carry
            # rotate first: n-1 permutes total, none wasted
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            src = (idx - t) % n  # whose chunk we hold this tick
            acc, m, l = partial_attn((acc, m, l), k_cur, v_cur, src)
            return (k_cur, v_cur, acc, m, l), None

        (_, _, acc, m, l), _ = jax.lax.scan(
            step, (kh, vh) + carry0, jnp.arange(1, n)
        )
        l = jnp.maximum(l, 1e-30)
        out = (acc / l).reshape(b, h, s_local, d).astype(q.dtype)
        return jnp.moveaxis(out, 1, 2)  # [B, S/N, H, D]

    return jax.shard_map(
        local_fn,
        mesh=jmesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
