"""Pallas TPU paged-attention decode kernel.

Replaces the dense-gather XLA path of
``incubate/nn/functional/block_attention.py`` (reference CUDA kernel:
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``) with a
block-table-aware flash-decode kernel: each grid cell walks ONE sequence's
logical blocks, the scalar-prefetched block table steers the BlockSpec index
map so only that sequence's physical KV blocks are streamed HBM -> VMEM
(never the dense ``[B, MBS*BS, H, D]`` gather), and an online softmax
accumulates in fp32 VMEM scratch. Grouped-query attention keeps the G query
heads of one KV head together as the kernel's row dimension.

Quantized KV (``FLAGS_kv_cache_dtype=int8``): every kernel accepts optional
``k_scale``/``v_scale`` planes (``[NB, HKV, BS]`` fp32 — per block, per head,
per token slot, addressed by the SAME block ids the KV planes use), streamed
through the identical block-table-steered index map. The dequant epilogue
lives inside the block walk: int8 loads, one fp32 multiply per (BS, D) tile,
fp32 accumulate — no dequantized copy of the cache ever materializes. The
dequant composition (``x.astype(f32) * scale``) is the byte-for-byte op
sequence the XLA gather fallback applies, keeping the two paths in lockstep.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# pallas_call name= of each kernel here: what a device trace calls it (stable, no shapes)
KERNEL_DECODE = "paged_attention_decode"
KERNEL_CHUNK = "paged_attention_chunk"
KERNEL_DECODE_FUSED = "paged_attention_decode_fused"
KERNEL_CHUNK_FUSED = "paged_attention_chunk_fused"


def _dequant_tile(k_ref, v_ref, ks_ref, vs_ref):
    """The in-walk dequant epilogue shared by every paged kernel: one fp32
    multiply per (BS, D) tile against this block's per-token scale rows. The
    scale planes ride as [NB, HKV, BS, 1] (the trailing 1 keeps the (1, 1,
    bs, 1) block legal under the TPU last-two-dims tiling rule), so the
    [BS, 1] tile broadcasts over D. With no scale refs this is the plain
    fp32 upcast — the bf16 path's op sequence, untouched."""
    k = k_ref[0, 0].astype(jnp.float32)  # [BS, D]
    v = v_ref[0, 0].astype(jnp.float32)
    if ks_ref is not None:
        k = k * ks_ref[0, 0].astype(jnp.float32)  # [BS, 1] broadcast over D
        v = v * vs_ref[0, 0].astype(jnp.float32)
    return k, v


def _decode_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 (length INCLUDING current token)
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, 1, BS, D] this logical block's physical KV (one head)
    v_ref,
    *rest,  # quantized: ks_ref, vs_ref [1, 1, BS] then outputs/scratch
    scale: float,
    block_size: int,
    num_blocks: int,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ragged skip: a block whose first position is already past this
    # sequence's length contributes nothing (its p would be masked to 0), so
    # the MXU work is predicated away entirely. A fully-padded slot
    # (len == 0) never takes this branch at all — the engine's inactive batch
    # slots cost no compute, only the final zero-write below.
    @pl.when(i * block_size < lens_ref[bi])
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, D]
        k, v = _dequant_tile(k_ref, v_ref, ks_ref, vs_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G, BS]
        pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        valid = pos < lens_ref[bi]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]  # [G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # the explicit valid multiply keeps fully-masked rows at p == 0: with
        # every position masked, m_new == NEG_INF and exp(s - m_new) would be
        # 1 everywhere — silent garbage for zero-length sequences
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)  # [G, BS]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == num_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_flash_decode(
    q: jax.Array,  # [B, HQ, D]
    key_cache: jax.Array,  # [NB, HKV, BS, D]
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] length INCLUDING the current token
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash decode over the paged cache. Returns ``[B, HQ, D]``."""
    b, hq, d = q.shape
    nb, hkv, bs, _ = key_cache.shape
    mbs = block_tables.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    qg = q.reshape(b, hkv, g, d)
    quantized = k_scale is not None

    grid = (b, hkv, mbs)
    kernel = functools.partial(
        _decode_kernel, scale=float(scale), block_size=bs, num_blocks=mbs,
        quantized=quantized,
    )

    def _kv_index(bi, hi, i, tables, lens):
        # the block table steers which PHYSICAL block is streamed in; block
        # (1, 1, BS, D) tiles the (BS, D) plane of one head. Logical blocks
        # past the sequence's last in-use block are clamped onto that last
        # block: the pipeline sees the same physical index as the previous
        # grid step and skips the HBM->VMEM copy, so ragged tails (and fully
        # padded slots, which clamp to block-table entry 0) cost no DMA
        # traffic — the matching compute skip is the pl.when in the kernel.
        last = jnp.maximum((lens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    def _scale_index(bi, hi, i, tables, lens):
        # the scale plane is addressed by the SAME physical block id
        last = jnp.maximum((lens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
    ]
    operands = [qg, key_cache, value_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
        ]
        operands += [k_scale[..., None], v_scale[..., None]]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        # batch and kv-head cells are independent; the block walk accumulates
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_DECODE,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *operands)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Ragged MIXED prefill/decode kernel (chunked prefill)
# ---------------------------------------------------------------------------
#
# One grid cell serves every new token of one sequence at once: the row
# dimension packs the chunk's C token positions x the G grouped query heads
# of one KV head, so a decode row (1 valid token) and a prompt-chunk row
# (up to C tokens) are the SAME kernel — the engine's single compiled
# signature. Each packed row carries its own causal limit
# (``seq_lens + j + 1`` for chunk token j), which is what makes the batch
# ragged rather than rectangular ("Ragged Paged Attention", arxiv
# 2604.15464).


def _chunk_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 tokens cached BEFORE the chunk
    qlens_ref,  # scalar prefetch: [B] int32 valid new tokens (0 = skip row)
    q_ref,  # [1, 1, C*G, D] chunk-major packed rows (row = j*G + g)
    k_ref,  # [1, 1, BS, D] this logical block's physical KV (one head)
    v_ref,
    *rest,  # quantized: ks_ref, vs_ref [1, 1, BS] then outputs/scratch
    scale: float,
    block_size: int,
    num_blocks: int,
    group: int,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    i = pl.program_id(2)
    rows = q_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ragged skip: the LAST position any of this sequence's rows may see is
    # lens + q_lens - 1 (the chunk's final token attending to itself); blocks
    # wholly past it are predicated away — a decode row costs the same blocks
    # it did under the decode-only kernel, and an inactive slot (q_lens == 0)
    # never takes this branch at all.
    @pl.when(i * block_size < lens_ref[bi] + qlens_ref[bi])
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [C*G, D]
        k, v = _dequant_tile(k_ref, v_ref, ks_ref, vs_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [C*G, BS]
        pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1
        )
        # per-row causal limit: packed row r serves chunk token j = r // G at
        # absolute position lens + j, so it may see pos <= lens + j
        row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) // group
        valid = (pos < lens_ref[bi] + row_j + 1) & (row_j < qlens_ref[bi])
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]  # [C*G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # the explicit valid multiply keeps fully-masked rows at p == 0 (a
        # row past q_lens has every position masked: exp(s - NEG_INF) would
        # otherwise be 1 everywhere — silent garbage)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)  # [C*G, BS]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == num_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / denom  # [C*G, D]
        # rows past q_lens emitted exact zeros (their l stayed 0 -> out is
        # 0/1e-30 = 0 already via the masked p), but force it explicitly so
        # the contract does not hinge on the epsilon
        row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        out = jnp.where(row_j < qlens_ref[bi], out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_flash_chunk(
    q: jax.Array,  # [B, C, HQ, D] ragged chunk (row j valid iff j < q_lens)
    key_cache: jax.Array,  # [NB, HKV, BS, D] chunk KV ALREADY appended
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens cached BEFORE the chunk
    q_lens: jax.Array,  # [B] valid new tokens (0 = inactive slot)
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention for one mixed prefill/decode step over the paged
    cache. Returns ``[B, C, HQ, D]`` with rows past ``q_lens`` exactly 0."""
    b, c, hq, d = q.shape
    nb, hkv, bs, _ = key_cache.shape
    mbs = block_tables.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    # pack rows chunk-major per KV head: [B, C, HKV, G, D] -> [B, HKV, C*G, D]
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, hkv, c * g, d)
    quantized = k_scale is not None

    grid = (b, hkv, mbs)
    kernel = functools.partial(
        _chunk_kernel, scale=float(scale), block_size=bs, num_blocks=mbs,
        group=g, quantized=quantized,
    )

    def _kv_index(bi, hi, i, tables, lens, qlens):
        # logical blocks past the LAST in-use block (which now includes the
        # freshly appended chunk) clamp onto it: the pipeline sees the same
        # physical index as the previous grid step and skips the HBM->VMEM
        # copy, so ragged tails cost no DMA (the matching compute skip is the
        # pl.when in the kernel)
        last = jnp.maximum((lens[bi] + qlens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    def _scale_index(bi, hi, i, tables, lens, qlens):
        # the scale plane is addressed by the SAME physical block id
        last = jnp.maximum((lens[bi] + qlens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, c * g, d),
            lambda bi, hi, i, tables, lens, qlens: (bi, hi, 0, 0),
        ),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
    ]
    operands = [qg, key_cache, value_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
        ]
        operands += [k_scale[..., None], v_scale[..., None]]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, c * g, d),
                lambda bi, hi, i, tables, lens, qlens: (bi, hi, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, d), q.dtype),
        # batch and kv-head cells are independent; the block walk accumulates
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_CHUNK,
    )(
        block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        *operands,
    )
    # [B, HKV, C*G, D] -> [B, C, HQ, D]
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)


# ---------------------------------------------------------------------------
# Fused-epilogue variants: q-RoPE folded into the block walk
# ---------------------------------------------------------------------------
#
# The decode step's unfused path ropes q in a separate XLA elementwise pass —
# one extra HBM round-trip over [B, C, HQ, D] per layer just to feed the
# attention kernel. The *_fused kernels take the per-slot cos/sin rows
# (already offset-gathered, the per-batch tables the XLA path uses) as two
# extra VMEM inputs and apply the rotation to the q block in-register before
# the first dot. Numerics are LOCKSTEP with the unfused TPU path: the
# rotation is computed in q's dtype (exactly ``_rope_apply_xla`` with
# tables cast to x.dtype) and only THEN cast fp32 and scaled — so fused
# on/off stay byte-identical. KV is roped before the cache append (cache
# holds roped keys) in both modes; only q's rope moves into the kernel.


def _rope_rows(q, c, s, half):
    # neox rotate-half in q.dtype: q*cos + concat(-q2, q1)*sin
    q1 = q[..., :half]
    q2 = q[..., half:]
    rot = jnp.concatenate([-q2, q1], axis=-1)
    return q * c + rot * s


def _decode_fused_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 (length INCLUDING current token)
    q_ref,  # [1, 1, G, D] pre-rope q
    cos_ref,  # [1, 1, D] this slot's rope row
    sin_ref,
    k_ref,  # [1, 1, BS, D]
    v_ref,
    *rest,  # quantized: ks_ref, vs_ref [1, 1, BS] then outputs/scratch
    scale: float,
    block_size: int,
    num_blocks: int,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * block_size < lens_ref[bi])
    def _attend():
        d = q_ref.shape[-1]
        g_rows = q_ref.shape[2]
        # materialize the [G, D] rope rows BEFORE the arithmetic — the same
        # op order the chunk kernel and the XLA rope composition lower to
        # (a [1, D] broadcast operand contracts differently and costs bitwise
        # parity with the unfused path)
        c = jnp.broadcast_to(cos_ref[0], (g_rows, d)).astype(q_ref.dtype)
        s_t = jnp.broadcast_to(sin_ref[0], (g_rows, d)).astype(q_ref.dtype)
        q = _rope_rows(q_ref[0, 0], c, s_t, d // 2)  # [G, D] in q.dtype
        q = q.astype(jnp.float32) * scale
        k, v = _dequant_tile(k_ref, v_ref, ks_ref, vs_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        valid = pos < lens_ref[bi]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == num_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_flash_decode_fused(
    q: jax.Array,  # [B, HQ, D] PRE-rope queries
    cos: jax.Array,  # [B, 1, D] offset-gathered rope rows
    sin: jax.Array,
    key_cache: jax.Array,  # [NB, HKV, BS, D] (keys already roped on append)
    value_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """:func:`paged_flash_decode` with q-RoPE folded into the block walk —
    one dispatch replaces the rope pass + attention pair."""
    b, hq, d = q.shape
    nb, hkv, bs, _ = key_cache.shape
    mbs = block_tables.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    qg = q.reshape(b, hkv, g, d)
    quantized = k_scale is not None

    kernel = functools.partial(
        _decode_fused_kernel, scale=float(scale), block_size=bs, num_blocks=mbs,
        quantized=quantized,
    )

    def _kv_index(bi, hi, i, tables, lens):
        last = jnp.maximum((lens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    def _scale_index(bi, hi, i, tables, lens):
        last = jnp.maximum((lens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, d), lambda bi, hi, i, tables, lens: (bi, 0, 0)),
        pl.BlockSpec((1, 1, d), lambda bi, hi, i, tables, lens: (bi, 0, 0)),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
    ]
    operands = [qg, cos, sin, key_cache, value_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
        ]
        operands += [k_scale[..., None], v_scale[..., None]]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, mbs),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_DECODE_FUSED,
    )(
        block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        *operands,
    )
    return out.reshape(b, hq, d)


def _chunk_fused_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 tokens cached BEFORE the chunk
    qlens_ref,  # scalar prefetch: [B] int32 valid new tokens
    q_ref,  # [1, 1, C*G, D] chunk-major packed PRE-rope rows
    cos_ref,  # [1, C, D] this slot's offset-gathered rope rows
    sin_ref,
    k_ref,
    v_ref,
    *rest,  # quantized: ks_ref, vs_ref [1, 1, BS] then outputs/scratch
    scale: float,
    block_size: int,
    num_blocks: int,
    group: int,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    i = pl.program_id(2)
    rows = q_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * block_size < lens_ref[bi] + qlens_ref[bi])
    def _attend():
        d = q_ref.shape[-1]
        c_dim = rows // group
        # expand [C, D] rope rows to the packed [C*G, D] row layout (row =
        # j*G + g shares token j's rotation across its G query heads)
        c = jnp.broadcast_to(
            cos_ref[0][:, None, :], (c_dim, group, d)
        ).reshape(rows, d).astype(q_ref.dtype)
        s_t = jnp.broadcast_to(
            sin_ref[0][:, None, :], (c_dim, group, d)
        ).reshape(rows, d).astype(q_ref.dtype)
        q = _rope_rows(q_ref[0, 0], c, s_t, d // 2)  # [C*G, D] in q.dtype
        q = q.astype(jnp.float32) * scale
        k, v = _dequant_tile(k_ref, v_ref, ks_ref, vs_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1
        )
        row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) // group
        valid = (pos < lens_ref[bi] + row_j + 1) & (row_j < qlens_ref[bi])
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == num_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / denom
        row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        out = jnp.where(row_j < qlens_ref[bi], out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_flash_chunk_fused(
    q: jax.Array,  # [B, C, HQ, D] PRE-rope ragged chunk
    cos: jax.Array,  # [B, C, D] offset-gathered rope rows per chunk token
    sin: jax.Array,
    key_cache: jax.Array,  # [NB, HKV, BS, D] (keys already roped on append)
    value_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,  # [B] tokens cached BEFORE the chunk
    q_lens: jax.Array,  # [B] valid new tokens (0 = inactive slot)
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """:func:`paged_flash_chunk` with q-RoPE folded into the block walk —
    the decode layer's rope pass + attention collapse to ONE dispatch."""
    b, c, hq, d = q.shape
    nb, hkv, bs, _ = key_cache.shape
    mbs = block_tables.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, hkv, c * g, d)
    quantized = k_scale is not None

    kernel = functools.partial(
        _chunk_fused_kernel, scale=float(scale), block_size=bs, num_blocks=mbs,
        group=g, quantized=quantized,
    )

    def _kv_index(bi, hi, i, tables, lens, qlens):
        last = jnp.maximum((lens[bi] + qlens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    def _scale_index(bi, hi, i, tables, lens, qlens):
        last = jnp.maximum((lens[bi] + qlens[bi] + bs - 1) // bs - 1, 0)
        return (tables[bi, jnp.minimum(i, last)], hi, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, c * g, d),
            lambda bi, hi, i, tables, lens, qlens: (bi, hi, 0, 0),
        ),
        pl.BlockSpec(
            (1, c, d), lambda bi, hi, i, tables, lens, qlens: (bi, 0, 0)
        ),
        pl.BlockSpec(
            (1, c, d), lambda bi, hi, i, tables, lens, qlens: (bi, 0, 0)
        ),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
        pl.BlockSpec((1, 1, bs, d), _kv_index),
    ]
    operands = [qg, cos, sin, key_cache, value_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
            pl.BlockSpec((1, 1, bs, 1), _scale_index),
        ]
        operands += [k_scale[..., None], v_scale[..., None]]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, mbs),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, c * g, d),
                lambda bi, hi, i, tables, lens, qlens: (bi, hi, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_CHUNK_FUSED,
    )(
        block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        *operands,
    )
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)
