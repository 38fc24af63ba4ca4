"""Paged (blocked) KV-cache attention for serving.

Reference: ``block_multihead_attention_`` (``fused_ops.yaml:45``, CUDA kernel
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``) — the
vLLM-style paged cache: KV lives in fixed-size physical blocks; a per-sequence
``block_table`` maps logical block index → physical block id, so sequences
grow without reserving max_seq_len per slot and freed blocks are reused.

TPU-native shape: the cache is a dense ``[num_blocks, H, block_size, D]``
array (heads OUTSIDE the token dim, so one head's physical block tiles as an
``(block_size, D)`` VMEM plane); appends are batched scatters
(``.at[phys, :, off].set``) and attention runs the Pallas paged kernel
(``kernels/paged_attention.py``: a walk over each slot's live pages) when enabled, falling
back to a dense gather with a static ``max_blocks_per_seq`` bound — all
static shapes, so the whole decode step jits once. The block allocator is
host-side Python (it runs between steps, not inside the program), mirroring
the reference where block tables are produced by the serving scheduler.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.spmd import shard_group_mesh
from paddle_tpu.testing.faults import fault_point as _fault_point


def _tp_sharded_flash_chunk(
    q: jax.Array,
    key_cache: jax.Array,
    value_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    q_lens: jax.Array,
    scale: float,
    mesh: Any,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    cos: Optional[jax.Array] = None,
    sin: Optional[jax.Array] = None,
) -> jax.Array:
    """Run the mixed ragged Pallas kernel PER SHARD over the head partition:
    a ``pallas_call`` has no SPMD partitioning rule, so under a tp mesh the
    kernel must be shard_mapped — each shard walks its own head slice of its
    own pool partition (head-parallel attention needs no communication
    inside the paged block walk; tables/lens are replicated host data).
    Quantization scale planes ([NB, KVH, BS]) partition on the SAME head
    axis as the KV planes they describe — scales are just more pool data.
    The rope rows (``cos`` / ``sin``: q comes PRE-rope) are position data
    shared by every head, so they ride replicated. ``interpret`` runs the
    per-shard kernel in Pallas interpret mode so the shard split itself is
    testable off-TPU."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.kernels.paged_attention import paged_flash_chunk

    heads = P(None, "tp", None, None)  # a pool plane [NB, KVH, BS, D]
    rope = [] if cos is None else [(cos, P(None, None, None)), (sin, P(None, None, None))]  # [B, C, D]
    scales = [] if k_scale is None else [(k_scale, P(None, "tp", None)), (v_scale, P(None, "tp", None))]
    operands = [
        (q, P(None, None, "tp", None)),  # [B, C, HQ, D]: heads split
        *rope,  # replicated position data
        (key_cache, heads),
        (value_cache, heads),
        (block_tables, P(None, None)),  # replicated host truth
        (seq_lens, P(None)),
        (q_lens, P(None)),
        *scales,
    ]

    def _shard_chunk_attend(q_l, *rest):
        rest = list(rest)
        cos_l, sin_l = (rest.pop(0), rest.pop(0)) if rope else (None, None)
        kc_l, vc_l, tables_l, lens_l, qlens_l, *scales_l = rest
        ks_l, vs_l = scales_l or (None, None)
        return paged_flash_chunk(
            q_l, kc_l, vc_l, tables_l, lens_l, qlens_l, scale=scale,
            interpret=interpret, k_scale=ks_l, v_scale=vs_l, cos=cos_l, sin=sin_l,
        )

    return jax.shard_map(
        _shard_chunk_attend,
        mesh=mesh,
        in_specs=tuple(spec for _, spec in operands),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )(*[x for x, _ in operands])


__all__ = [
    "BlockKVCache",
    "block_multihead_attention",
    "block_multihead_chunk_attention",
    "block_cache_prefill",
    "block_cache_append_chunk",
    "block_cache_cow_copy",
    "latent_chunk_attention",
]

# jax.named_scope names of the pool's writes in the engine's step body: what a
# device trace files the KV append scatters and the copy-on-write conditional
# (with the pool-sized layout copies XLA hangs on them) under
SCOPE_KV_WRITE = "kv_cache_update"
SCOPE_KV_COW = "kv_cow"


class BlockKVCache:
    """Host-side paged-cache manager: physical block pool + per-sequence block
    tables (reference: the serving scheduler that feeds ``block_tables``).

    Two allocation surfaces share the one physical free list:

    - the historical per-sequence table API (``allocate``/``free``/
      ``block_table``) used by ``generate_paged``, where a sequence owns its
      blocks exclusively; and
    - a reference-counted per-block API (``acquire_block``/``incref``/
      ``decref``) used by the prefix-cache layer
      (``inference/prefix_cache.py``), where one physical block may be mapped
      by many requests' block tables at once and is returned to the free list
      only when the last owner drops it.

    All accounting is guarded by one internal lock: the serving front end
    pumps the engine from a daemon thread while intake threads size requests
    against ``free_blocks``, so the pool's counters must never be read
    mid-update.
    """

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        num_heads: int,
        head_dim: int,
        max_blocks_per_seq: int,
        dtype: Any = jnp.bfloat16,
    ) -> None:
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        # [NB, H, BS, D]: heads OUTSIDE the token dim so a TPU kernel block
        # (one head, one physical block) tiles as (BS, D) — (8k, 128)-friendly
        self._shape = (int(num_blocks), int(num_heads), int(block_size), int(head_dim))
        self._dtype = dtype
        # device buffers are LAZY: callers that only use the host-side
        # allocator/tables (e.g. generate_paged, which owns per-layer pools)
        # never pay this HBM
        self._key_cache = None
        self._value_cache = None
        self._lock = threading.Lock()
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._tables: dict = {}  # seq id -> list of physical block ids
        self._lens: dict = {}  # seq id -> tokens stored
        self._ref: Dict[int, int] = {}  # block id -> refcount (refcounted API)

    @property
    def key_cache(self) -> Any:
        if self._key_cache is None:
            self._key_cache = jnp.zeros(self._shape, self._dtype)
        return self._key_cache

    @key_cache.setter
    def key_cache(self, v: Any) -> None:
        self._key_cache = v

    @property
    def value_cache(self) -> Any:
        if self._value_cache is None:
            self._value_cache = jnp.zeros(self._shape, self._dtype)
        return self._value_cache

    @value_cache.setter
    def value_cache(self, v: Any) -> None:
        self._value_cache = v

    # -- quantized-pool surface (FLAGS_kv_cache_dtype=int8) ------------------
    @property
    def quantized(self) -> bool:
        """True when the pool stores int8 blocks with companion scale planes."""
        return jnp.dtype(self._dtype) == jnp.int8

    @property
    def key_scale(self) -> Any:
        """Per-block-per-head-per-token fp32 scales ``[NB, H, BS]`` addressed
        by the SAME physical block ids as ``key_cache`` — every lifecycle seam
        (refcount, CoW, spill, recovery) moves cache rows and scale rows
        together. Initialized to ONES: ``quantize(zeros)`` yields ``q=0,
        scale=1``, so a fresh pool is byte-identical to a quantized empty one."""
        if getattr(self, "_key_scale", None) is None:
            self._key_scale = jnp.ones(self._shape[:3], jnp.float32)
        return self._key_scale

    @key_scale.setter
    def key_scale(self, v: Any) -> None:
        self._key_scale = v

    @property
    def value_scale(self) -> Any:
        if getattr(self, "_value_scale", None) is None:
            self._value_scale = jnp.ones(self._shape[:3], jnp.float32)
        return self._value_scale

    @value_scale.setter
    def value_scale(self, v: Any) -> None:
        self._value_scale = v

    # -- allocator ----------------------------------------------------------
    def allocate(self, seq_id: int, num_tokens: int) -> None:
        """Ensure ``seq_id`` has blocks for ``num_tokens`` more tokens."""
        _fault_point("block_pool.allocate")
        with self._lock:
            table = self._tables.setdefault(seq_id, [])
            cur = self._lens.get(seq_id, 0)
            need_blocks = -(-(cur + num_tokens) // self.block_size)
            while len(table) < need_blocks:
                if not self._free:
                    raise MemoryError("paged KV cache out of physical blocks")
                if len(table) >= self.max_blocks_per_seq:
                    raise MemoryError(
                        f"sequence {seq_id} exceeds max_blocks_per_seq={self.max_blocks_per_seq}"
                    )
                table.append(self._free.pop())
            self._lens[seq_id] = cur + num_tokens

    def free(self, seq_id: int) -> None:
        """Return a finished sequence's blocks to the pool."""
        with self._lock:
            for b in self._tables.pop(seq_id, []):
                self._free.append(b)
            self._lens.pop(seq_id, None)

    def truncate(self, seq_id: int, num_tokens: int) -> None:
        """Roll ``seq_id`` back to ``num_tokens`` stored tokens, returning
        now-unused tail blocks to the pool — the undo for a speculative or
        failed step whose ``allocate`` already ran."""
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                return
            keep = -(-num_tokens // self.block_size) if num_tokens > 0 else 0
            while len(table) > keep:
                self._free.append(table.pop())
            self._lens[seq_id] = num_tokens

    def seq_len(self, seq_id: int) -> int:
        with self._lock:
            return self._lens.get(seq_id, 0)

    def blocks_allocated(self, seq_id: Optional[int] = None) -> int:
        """Physical blocks held by ``seq_id`` (all sequences when None) —
        the public accounting surface the serving engine's admission math
        relies on. Refcounted blocks (prefix-cache layer) are not attributed
        to any sequence; use ``num_blocks - free_blocks`` for whole-pool
        occupancy."""
        with self._lock:
            if seq_id is not None:
                return len(self._tables.get(seq_id, ()))
            return sum(len(t) for t in self._tables.values())

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def block_table(self, seq_ids: Sequence[int]) -> jnp.ndarray:
        """Dense ``[B, max_blocks_per_seq]`` table (unused slots point at
        block 0; masking makes them unreachable)."""
        out = np.zeros((len(seq_ids), self.max_blocks_per_seq), np.int32)
        with self._lock:
            for i, sid in enumerate(seq_ids):
                t = self._tables.get(sid, [])
                out[i, : len(t)] = t
        return jnp.asarray(out)

    def seq_lens(self, seq_ids: Sequence[int]) -> jnp.ndarray:
        with self._lock:
            return jnp.asarray(
                [self._lens.get(s, 0) for s in seq_ids], jnp.int32
            )

    # -- refcounted per-block API (prefix-cache layer) -----------------------
    def acquire_block(self) -> int:
        """Take one physical block off the free list with refcount 1. The
        block belongs to the CALLER's accounting (a request's block table or
        a prefix-cache chain node), not to any ``seq_id`` table."""
        _fault_point("block_pool.allocate")
        with self._lock:
            if not self._free:
                raise MemoryError("paged KV cache out of physical blocks")
            blk = self._free.pop()
            self._ref[blk] = 1
            return blk

    def acquire_blocks(self, n: int) -> List[int]:
        """Atomically take ``n`` physical blocks off the free list, each
        with refcount 1 — the landing-slot reservation for a host-tier
        prefetch: either every block of the spilled chain gets a slot in
        one step or none does (no partial chain to unwind). Raises
        MemoryError with the free list untouched on a shortfall."""
        _fault_point("block_pool.allocate")
        n = int(n)
        with self._lock:
            if len(self._free) < n:
                raise MemoryError(
                    f"paged KV cache cannot reserve {n} blocks "
                    f"({len(self._free)} free)"
                )
            out = [self._free.pop() for _ in range(n)]
            for blk in out:
                self._ref[blk] = 1
            return out

    def incref(self, block: int) -> int:
        """Add one owner to a refcounted block; returns the new count."""
        with self._lock:
            cur = self._ref.get(block)
            if cur is None:
                raise ValueError(f"block {block} is not refcount-managed")
            self._ref[block] = cur + 1
            return cur + 1

    def decref(self, block: int) -> bool:
        """Drop one owner; returns True when this freed the block."""
        with self._lock:
            cur = self._ref.get(block)
            if cur is None:
                raise ValueError(f"block {block} is not refcount-managed")
            if cur <= 1:
                del self._ref[block]
                self._free.append(block)
                return True
            self._ref[block] = cur - 1
            return False

    def refcount(self, block: int) -> int:
        """Current owner count of a refcounted block (0 if unmanaged)."""
        with self._lock:
            return self._ref.get(block, 0)

    def refcounts(self) -> Dict[int, int]:
        """Snapshot of every refcount-managed block's owner count (for
        invariant checks; copied under the lock)."""
        with self._lock:
            return dict(self._ref)


def _quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token absmax int8 quantization over the head dim: each
    ``[..., D]`` row gets its own fp32 scale (``absmax / 127``; 1.0 for an
    all-zero row so dequant stays exact), so an incremental decode append
    never forces requantizing tokens already in the block. This is THE
    canonical quant composition: the write kernels, the host-tier capture
    and the recovery replay all call it, which is what makes replay
    deterministic to the byte."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _scatter_kv_rows(key_cache, value_cache, k, v, phys, off, key_scale, value_scale):
    """Write the token rows of ``k`` / ``v`` (``[B, T, H, D]``) at ``(phys, :,
    off)`` (both ``[B, T]``); rows routed to page ``NB`` are out of bounds
    and dropped. With scale planes (the int8
    pool) quantization happens INSIDE this write: the same scatter indices
    that place the int8 rows place their per-token scales, so the scale
    planes ride every lifecycle seam the KV planes do (4 arrays back, not 2)."""
    phys, off = phys.reshape(-1), off.reshape(-1)
    rows = lambda x: x.reshape((-1,) + x.shape[2:])  # noqa: E731  [B * T, H, D]
    if key_scale is not None:
        (qk, sk), (qv, sv) = _quantize_kv_rows(rows(k)), _quantize_kv_rows(rows(v))
        writes = [(key_cache, qk), (value_cache, qv), (key_scale, sk), (value_scale, sv)]
    else:
        writes = [(key_cache, rows(k).astype(key_cache.dtype)), (value_cache, rows(v).astype(value_cache.dtype))]
    return tuple(plane.at[phys, :, off].set(rows, mode="drop") for plane, rows in writes)


@jax.named_scope(SCOPE_KV_WRITE)
def block_cache_prefill(
    key_cache: jax.Array,
    value_cache: jax.Array,
    k: jax.Array,  # [B, S, H, D] prompt KV
    v: jax.Array,
    block_tables: jax.Array,  # [B, MBS]
    seq_lens: jax.Array,  # [B] prompt lengths (<= S)
    key_scale: Optional[jax.Array] = None,  # [NB, H, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """Write whole prompts into the paged cache (encoder phase of the
    reference kernel). With scale planes the write quantizes in-flight
    (returns 4 arrays)."""
    s = k.shape[1]
    nb, bs = key_cache.shape[0], key_cache.shape[2]
    t = jnp.arange(s)[None, :]  # [1, S]
    valid = t < seq_lens[:, None]  # [B, S]
    blk_idx = jnp.minimum(t // bs, block_tables.shape[1] - 1)
    off = t % bs
    phys = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # [B, S]
    # invalid positions are routed OUT OF BOUNDS and dropped by the scatter —
    # clamping them onto a real block would collide with a valid write at the
    # same slot, and duplicate-index scatter order is undefined
    phys = jnp.where(valid, phys, nb)
    return _scatter_kv_rows(
        key_cache, value_cache, k, v, phys, jnp.broadcast_to(off, phys.shape), key_scale, value_scale
    )


def block_cache_cow_copy(
    key_cache: jax.Array,  # [NB, H, BS, D]
    value_cache: jax.Array,
    src: jax.Array,  # [B] int32 physical block to fork from
    dst: jax.Array,  # [B] int32 private destination (== NB: no-op, dropped)
    key_scale: Optional[jax.Array] = None,  # [NB, H, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """Copy-on-write fork: duplicate whole physical blocks ``src`` into
    ``dst`` so a request that diverges inside a shared (refcounted) block can
    reuse its cached prefix KV without ever writing to the shared copy.

    The no-fork case is routed through the scatter's ``drop`` mode (``dst ==
    num_blocks``), so the same compiled program serves steps with and without
    forks — the fork set is data, never shape. The whole copy is skipped via
    ``lax.cond`` when no slot forks this step (the overwhelmingly common
    decode-only step pays one predicate, not a gather/scatter per layer).

    With scale planes the SAME fork copies them too (inside the one
    ``lax.cond``): a forked int8 block is bit-identical to its source, scales
    included — no requantization on CoW. Returns 4 arrays then."""
    return _cow_copy_planes(
        tuple(p for p in (key_cache, value_cache, key_scale, value_scale) if p is not None), src, dst
    )


@jax.named_scope(SCOPE_KV_COW)
def _cow_copy_planes(planes: Tuple[jax.Array, ...], src: jax.Array, dst: jax.Array) -> Tuple[jax.Array, ...]:
    """Blocks ``src`` of every plane ``[NB, ...]`` duplicated into ``dst``
    (``dst == NB``: dropped), under ONE ``lax.cond``: a paged set's fork,
    whatever planes the set has."""
    nb = planes[0].shape[0]
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    csrc = jnp.clip(src, 0, nb - 1)

    def _copy(planes):
        return tuple(p.at[dst].set(p[csrc], mode="drop") for p in planes)

    return jax.lax.cond(jnp.any(dst < nb), _copy, lambda planes: planes, planes)


@jax.named_scope(SCOPE_KV_COW)
def _fork_pages(planes: Tuple[jax.Array, ...], src: jax.Array, dst: jax.Array) -> Tuple[jax.Array, ...]:
    """:func:`_cow_copy_planes` with no ``lax.cond``: the gather and the
    scatter run every step, and a step in which no slot forks (``dst == NB``
    everywhere) drops every row. For a set whose planes the step's caller
    donates and whose append keeps the plane's layout: a conditional takes a
    copy of the whole donated plane as each branch's operand before it reads
    its predicate (two a set a step, whatever the slots do), this updates the
    plane where it lies."""
    nb = planes[0].shape[0]
    dst = jnp.asarray(dst, jnp.int32)
    csrc = jnp.clip(jnp.asarray(src, jnp.int32), 0, nb - 1)
    return tuple(p.at[dst].set(p[csrc], mode="drop") for p in planes)


@jax.named_scope(SCOPE_KV_WRITE)
def block_cache_append_chunk(
    key_cache: jax.Array,  # [NB, H, BS, D]
    value_cache: jax.Array,
    k: jax.Array,  # [B, C, H, D] up to C new tokens per sequence
    v: jax.Array,
    block_tables: jax.Array,  # [B, MBS]
    seq_lens: jax.Array,  # [B] tokens already stored (chunk writes AFTER them)
    q_lens: jax.Array,  # [B] valid new tokens this step (<= C; 0 = none)
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, H, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """Scatter a ragged chunk of new KV per sequence into its physical
    blocks: token ``j`` of sequence ``b`` lands at logical position
    ``seq_lens[b] + j``. Rows past ``q_lens`` (and masked-off slots: their
    block-table row may alias blocks owned by live sequences) are routed out
    of bounds and dropped — a decode row (``q_lens == 1``) and a prompt-chunk
    row (``q_lens == C``) ride the same scatter. With scale planes the write
    quantizes in-flight per token row (returns 4 arrays): the scale scatter
    uses the SAME out-of-bounds routing, so dropped KV rows drop their scales
    with them."""
    phys, off = _chunk_write_positions(
        k.shape[1], key_cache.shape[0], key_cache.shape[2], block_tables, seq_lens, q_lens, slot_mask
    )
    return _scatter_kv_rows(key_cache, value_cache, k, v, phys, off, key_scale, value_scale)


def _chunk_write_positions(c, nb, bs, block_tables, seq_lens, q_lens, slot_mask):
    """``(phys [B, C], off [B, C])``: the page and the row in it that chunk
    token ``j`` of each sequence is written to (position ``seq_lens + j``)."""
    j = jnp.arange(c)[None, :]  # [1, C]
    pos = seq_lens[:, None] + j  # [B, C] absolute token index
    valid = j < q_lens[:, None]
    if slot_mask is not None:
        valid = valid & slot_mask[:, None]
    blk_idx = jnp.minimum(pos // bs, block_tables.shape[1] - 1)
    off = pos % bs
    phys = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # [B, C]
    # invalid rows go OUT OF BOUNDS and are dropped by the scatter — clamping
    # them onto a real block would collide with valid writes (duplicate-index
    # scatter order is undefined), exactly the block_cache_prefill rule
    return jnp.where(valid, phys, nb), off


def _gather_chunk_attend(
    q: jax.Array,  # [B, C, HQ, D] (C == 1 for a pure decode step)
    key_cache: jax.Array,
    value_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,  # [B] tokens cached BEFORE the new rows
    attend_q: jax.Array,  # [B] valid new rows (0 = masked slot: exact zeros)
    scale: float,
    k_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """The ONE XLA dense-gather attention fallback shared by the decode and
    chunked paths: gather each sequence's physical blocks, mask each query
    row to its causal limit (``seq_lens + j + 1`` for row ``j``), fp32
    softmax. Rows past ``attend_q`` return exact zeros — lockstep with the
    Pallas kernels' skip, so slot padding never changes numerics. With scale
    planes, dequant (``x.astype(f32) * scale`` — the kernels' exact op
    composition) is applied right after the gather."""
    b, c, hq, d = q.shape
    hkv = key_cache.shape[1]
    # gather each sequence's blocks: [B, MBS, HKV, BS, D] -> [B, L, HKV, D]
    gk = jnp.moveaxis(key_cache[block_tables], 2, 3)
    gv = jnp.moveaxis(value_cache[block_tables], 2, 3)
    mbs, bs = block_tables.shape[1], key_cache.shape[2]
    L = mbs * bs
    gk = gk.reshape(b, L, hkv, d)
    gv = gv.reshape(b, L, hkv, d)
    if k_scale is not None:
        # per-token scales ride the same block-table gather as the KV rows
        gks = jnp.moveaxis(k_scale[block_tables], 2, 3).reshape(b, L, hkv)
        gvs = jnp.moveaxis(v_scale[block_tables], 2, 3).reshape(b, L, hkv)
        gk = gk.astype(jnp.float32) * gks[..., None]
        gv = gv.astype(jnp.float32) * gvs[..., None]
    if hkv != hq:
        if hq % hkv != 0:
            raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
        rep = hq // hkv
        gk = jnp.repeat(gk, rep, axis=2)
        gv = jnp.repeat(gv, rep, axis=2)
    qf = q.astype(jnp.float32) * scale  # [B, C, HQ, D]
    scores = jnp.einsum("bchd,blhd->bchl", qf, gk.astype(jnp.float32))
    pos = jnp.arange(L)[None, None, :]  # [1, 1, L]
    # query j sees cached history plus the chunk's own tokens 0..j (causal)
    limit = seq_lens[:, None] + jnp.arange(c)[None, :] + 1  # [B, C]
    mask = pos < limit[:, :, None]  # [B, C, L]
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bchl,blhd->bchd", probs, gv.astype(jnp.float32))
    # rows past attend_q (and fully-masked slots) degenerate to a uniform
    # mean over garbage in softmax — force exact zeros, matching the kernels
    row_valid = jnp.arange(c)[None, :] < attend_q[:, None]  # [B, C]
    out = jnp.where(row_valid[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def block_multihead_chunk_attention(
    q: jax.Array,  # [B, C, HQ, D] ragged chunk of new tokens per sequence
    k: jax.Array,  # [B, C, HKV, D]
    v: jax.Array,
    key_cache: jax.Array,  # [NB, HKV, BS, D]
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens already cached (EXCLUDING this chunk)
    q_lens: jax.Array,  # [B] valid new tokens this step (1 = decode row)
    scale: Optional[float] = None,
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
    cos: Optional[jax.Array] = None,  # [B, C, 1, D] offset-gathered rope rows
    sin: Optional[jax.Array] = None,  # (model layout); given: q and k come PRE-rope
):
    """One MIXED prefill/decode step over the paged cache — the chunked-
    prefill dispatch ("Ragged Paged Attention", arxiv 2604.15464): every
    batch row carries up to ``C`` new tokens; a decode row has ``q_lens ==
    1``, a prompt-chunk row up to ``C``. The chunk's KV is appended first, so
    query token ``j`` (absolute position ``seq_lens + j``) attends over every
    cached position ``<= seq_lens + j`` — causal within the chunk, full
    history before it. Rows past ``q_lens`` and masked-off slots return
    exactly zeros (lockstep with the Pallas kernel's skip).

    With ``cos`` / ``sin`` (the serving step's path) RoPE is folded in: k is
    rotated by the XLA elementwise composition (it fuses into the
    cache-append scatter) and q's rotation moves INSIDE the paged kernel's
    page walk, so a layer's rope pass + attention are one kernel dispatch.
    The XLA fallback stays in lockstep by applying the identical
    ``_rope_apply_xla`` to q before the shared dense-gather attention.

    Returns ``(out [B, C, HQ, D], key_cache, value_cache)``, plus the
    updated ``(key_scale, value_scale)`` planes when given (the int8 pool:
    quantize-on-write in the same fused append, AFTER the rope — the cache
    stores roped, quantized keys — and dequant inside the kernel's page
    walk, or the identical composition in the XLA fallback).
    """
    from paddle_tpu.incubate.nn.functional import _rope_apply_xla
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    b, c, hq, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    rope, quantized = cos is not None, key_scale is not None
    if rope:
        k = _rope_apply_xla(k, sin, cos, True)
    planes = block_cache_append_chunk(
        key_cache, value_cache, k, v, block_tables, seq_lens, q_lens,
        slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
    )
    key_cache, value_cache, *scales = planes
    key_scale, value_scale = scales or (None, None)
    attend_q = q_lens
    if slot_mask is not None:
        attend_q = jnp.where(slot_mask, attend_q, 0)
    if pallas_enabled("use_pallas_paged_attention"):
        # ragged mixed prefill/decode kernel: one grid walks each sequence's
        # live pages once, serving its decode row and its prompt-chunk rows
        # alike. The kernel is REQUIRED to compile on TPU
        # (tests/test_tpu_aot_compile.py): only a trace-time failure degrades
        # to the XLA path below. Under a tensor-parallel mesh the kernel runs
        # shard_mapped over the head partition.
        from paddle_tpu.kernels.paged_attention import paged_flash_chunk

        tp_mesh = shard_group_mesh()
        walk = dict(k_scale=key_scale, v_scale=value_scale)
        if rope:
            walk.update(cos=cos.reshape(b, c, d), sin=sin.reshape(b, c, d))
        try:
            if quantized:
                # injected dequant failure degrades THIS dispatch to the
                # XLA fallback below (counted), never the engine's
                # recovery path — the except arm swallows it
                _fault_point("quant.dequant")
            if tp_mesh is not None:
                out = _tp_sharded_flash_chunk(
                    q, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale, tp_mesh, **walk,
                )
            else:
                out = paged_flash_chunk(
                    q, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale=scale, **walk,
                )
            return (out,) + planes
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            # the label is the counter's series: the names the two entries had
            if rope:
                warn_fallback("paged_flash_chunk_fused", exc)
            else:
                warn_fallback("paged_flash_chunk", exc)
    if rope:
        # lockstep fallback: the SAME rope composition, then the shared
        # dense-gather attention
        q = _rope_apply_xla(q, sin, cos, True)
    out = _gather_chunk_attend(
        q, key_cache, value_cache, block_tables, seq_lens, attend_q, scale,
        k_scale=key_scale, v_scale=value_scale,
    )
    return (out,) + planes


def _gather_latent_attend(
    q: jax.Array,  # [B, C, H, W] absorbed, roped, scaled
    pool: jax.Array,  # [NB, 1, BS, W]
    block_tables: jax.Array,
    seq_lens: jax.Array,
    attend_q: jax.Array,  # [B] valid new rows (0: exact zeros)
    value_width: int,
) -> jax.Array:
    """The XLA composition of the latent walk: gather each sequence's rows,
    every head scores them as keys and weighs their first ``value_width``
    lanes as values; float32 softmax, rows past ``attend_q`` exact zeros."""
    b, c = q.shape[:2]
    rows = pool[block_tables][:, :, 0].reshape(b, -1, pool.shape[-1]).astype(jnp.float32)  # [B, L, W]
    scores = jnp.einsum("bchw,blw->bchl", q.astype(jnp.float32), rows)
    limit = seq_lens[:, None] + jnp.arange(c)[None, :] + 1  # [B, C]
    mask = jnp.arange(rows.shape[1])[None, None, :] < limit[:, :, None]
    probs = jax.nn.softmax(jnp.where(mask[:, :, None, :], scores, -1e30), axis=-1)
    out = jnp.einsum("bchl,blv->bchv", probs, rows[..., :value_width])
    row_valid = jnp.arange(c)[None, :] < attend_q[:, None]
    return jnp.where(row_valid[:, :, None, None], out, 0.0).astype(pool.dtype)


def latent_chunk_attention(
    q: jax.Array,  # [B, C, H, W] absorbed queries, roped and scaled
    row: jax.Array,  # [B, C, W] the chunk's latent rows (normalised latent | roped key | padding)
    pool: jax.Array,  # [NB, 1, BS, W]
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens already cached (EXCLUDING this chunk)
    q_lens: jax.Array,  # [B] valid new tokens this step (1 = decode row)
    value_width: int,
    slot_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`block_multihead_chunk_attention` for a pool of LATENT rows
    (multi-head latent attention in its absorbed form): the chunk's rows are
    appended, then every head attends over the sequence's rows, each row key
    and (its first ``value_width`` lanes) value at once. Returns ``(out [B, C,
    H, value_width], pool)``; rows past ``q_lens`` and masked slots are zeros."""
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    with jax.named_scope(SCOPE_KV_WRITE):
        phys, off = _chunk_write_positions(
            row.shape[1], pool.shape[0], pool.shape[2], block_tables, seq_lens, q_lens, slot_mask
        )
        pool = pool.at[phys.reshape(-1), 0, off.reshape(-1)].set(
            row.reshape(-1, row.shape[-1]).astype(pool.dtype), mode="drop"
        )
    attend_q = q_lens if slot_mask is None else jnp.where(slot_mask, q_lens, 0)
    if pallas_enabled("use_pallas_paged_attention", bare="paged_latent_chunk"):
        from paddle_tpu.kernels.paged_attention import paged_latent_chunk

        try:
            return paged_latent_chunk(q, pool, block_tables, seq_lens, attend_q, value_width=value_width), pool
        except Exception as exc:  # noqa: BLE001 - XLA composition below
            warn_fallback("paged_latent_chunk", exc)
    return _gather_latent_attend(q, pool, block_tables, seq_lens, attend_q, value_width), pool


def block_multihead_attention(
    q: jax.Array,  # [B, 1, HQ, D] decode query (one token per sequence)
    k: jax.Array,  # [B, 1, HKV, D] new key
    v: jax.Array,  # [B, 1, HKV, D] new value
    key_cache: jax.Array,  # [NB, HKV, BS, D]
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens already cached (EXCLUDING this one)
    scale: Optional[float] = None,
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """The reference API's name (``block_multihead_attention_``,
    ``fused_ops.yaml:45``) for one paged decode step: the ``C == 1`` case of
    :func:`block_multihead_chunk_attention`, every sequence one new token."""
    return block_multihead_chunk_attention(
        q, k, v, key_cache, value_cache, block_tables, seq_lens, jnp.ones_like(seq_lens),
        scale=scale, slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
    )
