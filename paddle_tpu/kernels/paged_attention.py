"""The Pallas TPU paged-attention kernel.

Replaces the dense-gather XLA path of
``incubate/nn/functional/block_attention.py`` (reference CUDA kernel:
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``) with a
block-table-aware flash kernel: only a sequence's own LIVE physical KV pages
are streamed HBM -> VMEM (never the dense ``[B, MBS*BS, H, D]`` gather), in a
loop inside the kernel bounded by the sequence's length, and an online softmax
accumulates in fp32 VMEM scratch. Grouped-query attention keeps the G query
heads of one KV head together as the kernel's row dimension. One body and one
``pallas_call`` serve the engine's mixed prefill/decode step and a plain
decode step (the chunk at ``C == 1``). A second body beside it,
``paged_latent_chunk``, walks a pool of LATENT rows (multi-head latent
attention, absorbed): one row a token that is key and value of every head.

Quantized KV (``FLAGS_kv_cache_dtype=int8``): optional ``k_scale``/``v_scale``
planes (``[NB, HKV, BS]`` fp32 — per block, per head, per token slot, addressed
by the SAME block ids the KV planes use). The dequant epilogue lives inside the
page walk: int8 loads, one fp32 multiply per page tile, fp32 accumulate — no
dequantized copy of the cache ever materializes. The dequant composition
(``x.astype(f32) * scale``) is the byte-for-byte op sequence the XLA gather
fallback applies, keeping the two paths in lockstep.
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
KERNEL_CHUNK = "paged_attention_chunk"
KERNEL_CHUNK_FUSED = "paged_attention_chunk_fused"  # q-RoPE folded into the walk
KERNEL_LATENT = "paged_latent_attention_chunk"  # one latent row a token is key AND value


def _rope_rows(q, c, s, half):
    # neox rotate-half in q.dtype: q*cos + concat(-q2, q1)*sin
    q1 = q[..., :half]
    q2 = q[..., half:]
    rot = jnp.concatenate([-q2, q1], axis=-1)
    return q * c + rot * s


# ---------------------------------------------------------------------------
# Ragged MIXED prefill/decode kernel (chunked prefill): the length-bounded walk
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
#
# The grid is slots x KV-head groups, NOT pages: the pool stays in HBM and the
# cell walks its sequence's LIVE pages in a loop whose trip count is
# ``ceil(ceil((seq_lens + q_lens) / BS) / P)`` tiles of P pages. A tile's
# pages arrive by one async copy per page per plane (a page's heads are
# contiguous in ``[NB, HKV, BS, D]``) into a double-buffered VMEM scratch, the
# next tile's copies in flight under the current tile's compute. Page slots
# of the last tile past the live bound re-read the last live page (their
# positions are masked), so nothing outside the sequence's pages is touched.
# q-RoPE (``rope``) and the int8 dequant (``quantized``) are static options
# of the one body: the rotation is computed in q's dtype (exactly
# ``_rope_apply_xla`` with tables cast to x.dtype) and only THEN cast fp32
# and scaled, KV is roped before the cache append in both modes.

_KV_VMEM_BUDGET = 4 << 20  # bytes of VMEM the double-buffered page tiles may take


def _walk_geometry(hkv: int, bs: int, d: int, kv_dtype):
    """(pages per tile, KV heads per cell), from shapes alone: a tile is 128
    key positions (one lane-width of scores), and a cell takes as many of the
    operand's KV heads as keep its page buffers (two slots x K and V) inside
    the VMEM budget."""
    pages = max(1, 128 // bs)
    itemsize = jnp.dtype(kv_dtype).itemsize
    sublanes = 32 // itemsize  # rows of one (sublane, 128-lane) tile of this dtype
    per_head = 2 * 2 * pages * -(-bs // sublanes) * sublanes * d * itemsize
    heads = max(h for h in range(1, hkv + 1) if hkv % h == 0 and (h == 1 or h * per_head <= _KV_VMEM_BUDGET))
    return pages, heads


def _scale_columns(buf, slot, head, bs):
    """One head's per-token scales of a tile's pages as ``[P, BS, 1]`` columns
    over D. A page's scales lie along lanes (``buf[slot, p]`` is ``[1, HKV*BS]``,
    head-major); the rows spread over BS sublanes, masked to the head's diagonal
    and summed along lanes are the same numbers down a column, exactly."""
    pages, _, lanes = buf.shape[1:]
    lane = jax.lax.broadcasted_iota(jnp.int32, (pages, bs, lanes), 2)
    diag = lane == head * bs + jax.lax.broadcasted_iota(jnp.int32, (pages, bs, lanes), 1)
    rows = jnp.broadcast_to(buf[slot], (pages, bs, lanes))
    return jnp.sum(jnp.where(diag, rows, 0.0), axis=-1, keepdims=True)


def _walk_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 tokens cached BEFORE the chunk
    qlens_ref,  # scalar prefetch: [B] int32 valid new tokens (0 = skip slot)
    q_ref,  # [1, HG, C*G, D] chunk-major packed rows (row = j*G + g)
    *rest,  # rope: cos, sin [1, C, D]; k, v pools in HBM; quantized: scale planes
    scale: float,
    block_size: int,
    pages: int,
    group: int,
    rope: bool,
    quantized: bool,
):
    rest = list(rest)
    cos_ref, sin_ref = (rest.pop(0), rest.pop(0)) if rope else (None, None)
    pools = [rest.pop(0) for _ in range(4 if quantized else 2)]  # k, v[, ks, vs]
    o_ref, qs_ref, m_ref, l_ref, acc_ref = rest[:5]
    # one buffer per pool: [2, P, HG, BS, D] pages, [2, P, 1, HKV*BS] scale rows
    bufs, sem = rest[5:-1], rest[-1]
    bi, hj = pl.program_id(0), pl.program_id(1)
    lens, qlens = lens_ref[bi], qlens_ref[bi]
    _, hg, rows, d = q_ref.shape
    tile = pages * block_size
    # the LAST position any of this sequence's rows may see is lens + qlens - 1
    # (the chunk's final token attending to itself): the walk ends with its
    # page. An inactive slot (q_lens == 0) walks nothing at all.
    n_pages = jnp.where(qlens > 0, (lens + qlens + block_size - 1) // block_size, 0)
    n_tiles = (n_pages + pages - 1) // pages

    def copy_tile(t, slot, wait):
        """Start (or wait for) tile ``t``'s copies into buffer ``slot``: one
        per page per pool. The loops over pages here and over heads in the
        walk are ``fori_loop``s unrolled at lowering, so that their bodies are
        traced ONCE (Python loops cost the host seconds of tracing per step
        program) and the scheduler still overlaps one head's work with the next's."""

        def one_page(p, carry):
            page = tables_ref[bi, jnp.minimum(t * pages + p, n_pages - 1)]
            for pool, buf in zip(pools, bufs):
                # a page's scales ride whole (one row holds every head's)
                whole = pool.ndim == 3 or hg == pool.shape[1]
                src = pool.at[page] if whole else pool.at[page, pl.ds(hj * hg, hg)]
                cp = pltpu.make_async_copy(src, buf.at[slot, p], sem.at[slot])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(0, pages, one_page, None, unroll=True)

    @pl.when(n_pages == 0)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _attend():
        copy_tile(0, 0, wait=False)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if rope:
            # expand [C, D] rope rows to the packed [C*G, D] row layout (row =
            # j*G + g shares token j's rotation across its G query heads),
            # materialized BEFORE the arithmetic: the op order the XLA rope
            # composition lowers to
            c_dim = rows // group
            cos = jnp.broadcast_to(cos_ref[0][:, None, :], (c_dim, group, d)).reshape(rows, d).astype(q_ref.dtype)
            sin = jnp.broadcast_to(sin_ref[0][:, None, :], (c_dim, group, d)).reshape(rows, d).astype(q_ref.dtype)
        for h in range(hg):  # a few [C*G, D] ops a head, once a cell: left unrolled
            q = q_ref[0, h]
            if rope:
                q = _rope_rows(q, cos, sin, d // 2)  # in q.dtype
            qs_ref[h] = q.astype(jnp.float32) * scale

        def walk(t, carry):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _prefetch():
                copy_tile(t + 1, 1 - slot, wait=False)

            copy_tile(t, slot, wait=True)
            pos = t * tile + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
            # per-row causal limit: packed row r serves chunk token j = r // G at
            # absolute position lens + j, so it may see pos <= lens + j
            row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0) // group
            valid = (pos < lens + row_j + 1) & (row_j < qlens)

            def one_head(h, carry):
                # the in-walk dequant: fp32 upcast, then (int8) one multiply per
                # page against its per-token scale column — the op sequence of
                # the XLA gather fallback
                k = bufs[0][slot, :, h].astype(jnp.float32)  # [P, BS, D]
                v = bufs[1][slot, :, h].astype(jnp.float32)
                if quantized:
                    k = k * _scale_columns(bufs[2], slot, hj * hg + h, block_size)
                    v = v * _scale_columns(bufs[3], slot, hj * hg + h, block_size)
                k, v = k.reshape(tile, d), v.reshape(tile, d)
                s = jax.lax.dot_general(
                    qs_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )  # [C*G, P*BS]
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[h]  # [C*G, 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # the explicit valid multiply keeps fully-masked rows at p == 0 (a
                # row past q_lens has every position masked: exp(s - NEG_INF) would
                # otherwise be 1 everywhere — silent garbage)
                p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                m_ref[h] = m_new
                return carry

            return jax.lax.fori_loop(0, hg, one_head, carry, unroll=True)

        jax.lax.fori_loop(0, n_tiles, walk, None)
        # rows past q_lens emitted exact zeros already (their l stayed 0 -> 0 /
        # 1e-30), but force it so the contract does not hinge on the epsilon
        live = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group < qlens

        def finish(h, carry):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = jnp.where(live, out, 0.0).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, hg, finish, None, unroll=True)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def _paged_chunk_call(q, cos, sin, key_cache, value_cache, block_tables, seq_lens,
                      q_lens, scale, interpret, k_scale, v_scale, name):
    """The one ``pallas_call`` of the chunk kernels; ``cos is None`` = q comes
    roped, ``k_scale is None`` = a floating cache. Jitted so that a step's
    layers share ONE trace and one lowering of the kernel."""
    b, c, hq, d = q.shape
    nb, hkv, bs, _ = key_cache.shape
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    if not interpret and (d % 128 or bs % 8):
        # raised at trace time, where the dispatch site degrades to the XLA path
        raise ValueError(
            f"the page walk copies [heads, {bs}, {d}] pages out of HBM: head_dim must be "
            "a multiple of 128 lanes and block_size of 8 sublanes"
        )
    rope, quantized = cos is not None, k_scale is not None
    pages, hg = _walk_geometry(hkv, bs, d, key_cache.dtype)
    # pack rows chunk-major per KV head: [B, C, HKV, G, D] -> [B, HKV, C*G, D]
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, hkv, c * g, d)
    q_spec = pl.BlockSpec((1, hg, c * g, d), lambda bi, hj, tables, lens, qlens: (bi, hj, 0, 0))
    in_specs, operands = [q_spec], [qg]
    if rope:
        in_specs += [pl.BlockSpec((1, c, d), lambda bi, hj, tables, lens, qlens: (bi, 0, 0))] * 2
        operands += [cos, sin]
    # the pool (and the scale planes) stay in HBM: the walk copies live pages
    # only. A copy out of HBM wants a minor dimension of whole 128-lane tiles, so
    # a page's scales ride as ONE row [1, HKV*BS], padded to such a multiple
    pools = [key_cache, value_cache]
    if quantized:
        pad = -(hkv * bs) % 128
        pools += [jnp.pad(sc.reshape(nb, 1, hkv * bs), ((0, 0), (0, 0), (0, pad))) for sc in (k_scale, v_scale)]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    out = pl.pallas_call(
        functools.partial(
            _walk_kernel, scale=float(scale), block_size=bs, pages=pages, group=g,
            rope=rope, quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv // hg),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hg, c * g, d), jnp.float32),  # roped, scaled q
                pltpu.VMEM((hg, c * g, 1), jnp.float32),  # m
                pltpu.VMEM((hg, c * g, 1), jnp.float32),  # l
                pltpu.VMEM((hg, c * g, d), jnp.float32),  # acc
                *[pltpu.VMEM((2, pages, hg) + p.shape[2:], p.dtype) for p in pools[:2]],
                *[pltpu.VMEM((2, pages) + p.shape[1:], p.dtype) for p in pools[2:]],
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, d), q.dtype),
        # slot and head-group cells are independent; the page walk is inside
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32), *operands, *pools,
    )
    # [B, HKV, C*G, D] -> [B, C, HQ, D]
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)


def paged_flash_chunk(
    q: jax.Array,  # [B, C, HQ, D] ragged chunk (row j valid iff j < q_lens)
    key_cache: jax.Array,  # [NB, HKV, BS, D] chunk KV ALREADY appended (keys roped)
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens cached BEFORE the chunk
    q_lens: jax.Array,  # [B] valid new tokens (0 = inactive slot)
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    cos: Optional[jax.Array] = None,  # [B, C, D] offset-gathered rope rows per
    sin: Optional[jax.Array] = None,  # chunk token; given: q comes PRE-rope
) -> jax.Array:
    """Flash attention for one mixed prefill/decode step over the paged
    cache. Returns ``[B, C, HQ, D]`` with rows past ``q_lens`` exactly 0.
    With ``cos`` / ``sin`` q's RoPE is folded into the page walk: a decode
    layer's rope pass + attention are ONE dispatch."""
    return _paged_chunk_call(
        q, cos, sin, key_cache, value_cache, block_tables, seq_lens, q_lens,
        scale, interpret, k_scale, v_scale, KERNEL_CHUNK if cos is None else KERNEL_CHUNK_FUSED,
    )


# ---------------------------------------------------------------------------
# The walk over LATENT pages (multi-head latent attention, absorbed form)
# ---------------------------------------------------------------------------
#
# The pool holds ONE row a token, ``[c_kv | rope(k_pe) | 0]`` (``W`` lanes, a
# whole number of 128-lane tiles), which every query head reads as its key and
# whose first ``value_width`` lanes every head reads as its value: a page comes
# out of HBM once and serves QK^T and PV alike. The query rows arrive absorbed
# (``q_nope W_UK | rope(q_pe)``), roped and scaled, so the body is the plain
# online softmax of ``_walk_kernel`` with one KV "head" and ``C x heads`` packed
# rows. Both matmuls take their operands in the POOL's dtype with float32
# accumulation (a bfloat16 pool runs the MXU at its bfloat16 rate; the rows are
# 128 times as many as a grouped-query cell packs, so here the matmuls are the
# cost). Every one of the ``C x heads`` rows is computed whatever ``q_lens``
# is; rows past it come out as exact zeros.

_LATENT_ROWS = 2048  # packed query rows a cell may hold: what keeps q, the accumulator and a score tile in VMEM
_LATENT_VMEM_BYTES = 64 << 20  # stated to Mosaic: a 2048-row cell needs ~30 MiB, the default scope is 16


def _latent_walk_kernel(
    tables_ref,  # scalar prefetch: [B, MBS] int32
    lens_ref,  # scalar prefetch: [B] int32 tokens cached BEFORE the chunk
    qlens_ref,  # scalar prefetch: [B] int32 valid new tokens (0 = skip slot)
    q_ref,  # [1, 1, C*HG, W] chunk-major packed rows (row = j*HG + h), roped and scaled
    pool_ref,  # [NB, 1, BS, W] in HBM
    o_ref,  # [1, 1, C*HG, value_width]
    m_ref, l_ref, acc_ref,  # [C*HG, 1] x 2, [C*HG, value_width] float32
    buf,  # [2, P*BS, W] one tile's pages, double-buffered
    sem,
    *,
    block_size: int,
    pages: int,
    heads: int,
    value_width: int,
):
    bi = pl.program_id(0)
    lens, qlens = lens_ref[bi], qlens_ref[bi]
    rows = q_ref.shape[2]
    tile = pages * block_size
    n_pages = jnp.where(qlens > 0, (lens + qlens + block_size - 1) // block_size, 0)
    n_tiles = (n_pages + pages - 1) // pages

    def copy_tile(t, slot, wait):
        def one_page(p, carry):
            page = tables_ref[bi, jnp.minimum(t * pages + p, n_pages - 1)]
            cp = pltpu.make_async_copy(
                pool_ref.at[page, 0], buf.at[slot, pl.ds(p * block_size, block_size)], sem.at[slot]
            )
            cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(0, pages, one_page, None, unroll=True)

    @pl.when(n_pages == 0)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _attend():
        copy_tile(0, 0, wait=False)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def walk(t, carry):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _prefetch():
                copy_tile(t + 1, 1 - slot, wait=False)

            copy_tile(t, slot, wait=True)
            pos = t * tile + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
            row_j = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0) // heads
            valid = (pos < lens + row_j + 1) & (row_j < qlens)
            kv = buf[slot]  # [P*BS, W]: the tile's rows, key and value at once
            s = jax.lax.dot_general(
                q_ref[0, 0], kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [C*HG, P*BS]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new) * valid.astype(jnp.float32)  # as _walk_kernel: masked rows stay at 0
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(kv.dtype), kv[:, :value_width], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_tiles, walk, None)
        live = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads < qlens
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(live, out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_width", "interpret"))
def paged_latent_chunk(
    q: jax.Array,  # [B, C, H, W] absorbed queries, roped and SCALED (row j valid iff j < q_lens)
    pool: jax.Array,  # [NB, 1, BS, W] latent rows, the chunk's ALREADY appended
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens cached BEFORE the chunk
    q_lens: jax.Array,  # [B] valid new tokens (0 = inactive slot)
    value_width: int,  # the row's first lanes that are the value
    interpret: bool = False,
) -> jax.Array:
    """Attention of ``H`` query heads over ONE latent row a token, the row as
    key and its first ``value_width`` lanes as value. Returns ``[B, C, H,
    value_width]`` in the pool's dtype with rows past ``q_lens`` exactly 0. A
    cell takes as many heads as keep ``C x heads`` at ``_LATENT_ROWS``: all of
    them at a chunk of 16 and 128 heads, so that a slot's live pages leave HBM
    once a set; a longer chunk splits the heads over cells and reads the pages
    once a cell."""
    b, c, h, w = q.shape
    nb, one, bs, _ = pool.shape
    if one != 1 or pool.shape[-1] != w:
        raise ValueError(f"a latent pool is [NB, 1, BS, {w}], got {pool.shape}")
    sublanes = 32 // jnp.dtype(pool.dtype).itemsize
    if not interpret and (w % 128 or value_width % 128 or bs % sublanes):
        raise ValueError(
            f"the latent walk copies [{bs}, {w}] pages out of HBM: the row and its value part must be "
            f"whole 128-lane tiles and block_size a multiple of {sublanes} sublanes"
        )
    hg = max(g for g in range(1, h + 1) if h % g == 0 and (g == 1 or c * g <= _LATENT_ROWS))
    pages = max(1, 128 // bs)
    # pack rows chunk-major per head group: [B, C, H/hg, hg, W] -> [B, H/hg, C*hg, W] (no move at one group)
    qg = q.astype(pool.dtype).reshape(b, c, h // hg, hg, w).transpose(0, 2, 1, 3, 4).reshape(b, h // hg, c * hg, w)
    cell = lambda bi, gj, tables, lens, qlens: (bi, gj, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_latent_walk_kernel, block_size=bs, pages=pages, heads=hg, value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h // hg),
            in_specs=[pl.BlockSpec((1, 1, c * hg, w), cell), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, c * hg, value_width), cell),
            scratch_shapes=[
                pltpu.VMEM((c * hg, 1), jnp.float32),  # m
                pltpu.VMEM((c * hg, 1), jnp.float32),  # l
                pltpu.VMEM((c * hg, value_width), jnp.float32),  # acc
                pltpu.VMEM((2, pages * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h // hg, c * hg, value_width), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_LATENT_VMEM_BYTES
        ),
        interpret=interpret,
        name=KERNEL_LATENT,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q_lens.astype(jnp.int32), qg, pool)
    return out.reshape(b, h // hg, c, hg, value_width).transpose(0, 2, 1, 3, 4).reshape(b, c, h, value_width)
