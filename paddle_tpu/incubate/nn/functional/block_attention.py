"""Paged (blocked) KV-cache attention for serving.

Reference: ``block_multihead_attention_`` (``fused_ops.yaml:45``, CUDA kernel
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``) — the
vLLM-style paged cache: KV lives in fixed-size physical blocks; a per-sequence
``block_table`` maps logical block index → physical block id, so sequences
grow without reserving max_seq_len per slot and freed blocks are reused.

TPU-native shape: the cache is a dense ``[num_blocks, H, block_size, D]``
array (heads OUTSIDE the token dim, so one head's physical block tiles as an
``(block_size, D)`` VMEM plane); appends are batched scatters
(``.at[phys, :, off].set``) and decode attention runs the Pallas block-table
flash-decode kernel (``kernels/paged_attention.py``) when enabled, falling
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
) -> jax.Array:
    """Run the mixed ragged Pallas kernel PER SHARD over the head partition:
    a ``pallas_call`` has no SPMD partitioning rule, so under a tp mesh the
    kernel must be shard_mapped — each shard walks its own head slice of its
    own pool partition (head-parallel attention needs no communication
    inside the paged block walk; tables/lens are replicated host data).
    Quantization scale planes ([NB, KVH, BS]) partition on the SAME head
    axis as the KV planes they describe — scales are just more pool data.
    ``interpret`` runs the per-shard kernel in Pallas interpret mode so the
    shard split itself is testable off-TPU."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.kernels.paged_attention import paged_flash_chunk

    in_specs = [
        P(None, None, "tp", None),  # q [B, C, HQ, D]: heads split
        P(None, "tp", None, None),  # key_cache [NB, KVH, BS, D]
        P(None, "tp", None, None),  # value_cache
        P(None, None),  # block_tables: replicated host truth
        P(None),  # seq_lens
        P(None),  # q_lens
    ]
    operands = [q, key_cache, value_cache, block_tables, seq_lens, q_lens]
    if k_scale is not None:
        in_specs += [P(None, "tp", None), P(None, "tp", None)]
        operands += [k_scale, v_scale]

    def _shard_chunk_attend(q_l, kc_l, vc_l, tables_l, lens_l, qlens_l,
                            ks_l=None, vs_l=None):
        return paged_flash_chunk(
            q_l, kc_l, vc_l, tables_l, lens_l, qlens_l, scale=scale,
            interpret=interpret, k_scale=ks_l, v_scale=vs_l,
        )

    return jax.shard_map(
        _shard_chunk_attend,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )(*operands)

def _tp_sharded_flash_chunk_fused(
    q: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
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
) -> jax.Array:
    """:func:`_tp_sharded_flash_chunk` for the rope-fused kernel: the rope
    rows are position data shared by every head, so they ride replicated
    while q/caches (and scale planes) split over the head partition."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.kernels.paged_attention import paged_flash_chunk_fused

    in_specs = [
        P(None, None, "tp", None),  # q [B, C, HQ, D]: heads split
        P(None, None, None),  # cos [B, C, D]: replicated position data
        P(None, None, None),  # sin
        P(None, "tp", None, None),  # key_cache [NB, KVH, BS, D]
        P(None, "tp", None, None),  # value_cache
        P(None, None),  # block_tables: replicated host truth
        P(None),  # seq_lens
        P(None),  # q_lens
    ]
    operands = [q, cos, sin, key_cache, value_cache, block_tables,
                seq_lens, q_lens]
    if k_scale is not None:
        in_specs += [P(None, "tp", None), P(None, "tp", None)]
        operands += [k_scale, v_scale]

    def _shard_chunk_attend(q_l, cos_l, sin_l, kc_l, vc_l, tables_l, lens_l,
                            qlens_l, ks_l=None, vs_l=None):
        return paged_flash_chunk_fused(
            q_l, cos_l, sin_l, kc_l, vc_l, tables_l, lens_l, qlens_l,
            scale=scale, interpret=interpret, k_scale=ks_l, v_scale=vs_l,
        )

    return jax.shard_map(
        _shard_chunk_attend,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )(*operands)


__all__ = [
    "BlockKVCache",
    "block_multihead_attention",
    "block_multihead_attention_fused",
    "block_multihead_chunk_attention",
    "block_multihead_chunk_attention_fused",
    "block_cache_prefill",
    "block_cache_append",
    "block_cache_append_chunk",
    "block_cache_cow_copy",
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


@jax.named_scope(SCOPE_KV_WRITE)
def block_cache_append(
    key_cache: jax.Array,  # [NB, H, BS, D]
    value_cache: jax.Array,
    k: jax.Array,  # [B, H, D] one new token per sequence
    v: jax.Array,
    block_tables: jax.Array,  # [B, MBS]
    positions: jax.Array,  # [B] token index being written (0-based)
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, H, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """Scatter one new KV token per sequence into its physical block slot.

    With ``slot_mask``, masked-off (padded) batch slots write NOTHING: their
    block-table row may alias physical blocks owned by live sequences (the
    engine keeps evicted rows at 0), so their scatter is routed out of bounds
    and dropped instead of clobbering another sequence's KV.

    With ``key_scale``/``value_scale`` (the int8 pool), quantization happens
    INSIDE this fused write: the same scatter indices that place the int8
    rows place their per-token scales, so the scale table rides every
    lifecycle seam the KV planes do. Returns 4 arrays instead of 2."""
    nb, _h, bs, _d = key_cache.shape
    blk_idx = positions // bs
    off = positions % bs
    phys = jnp.take_along_axis(block_tables, blk_idx[:, None], axis=1)[:, 0]
    if slot_mask is not None:
        phys = jnp.where(slot_mask, phys, nb)
    if key_scale is not None:
        qk, sk = _quantize_kv_rows(k)  # [B, H, D] int8, [B, H] f32
        qv, sv = _quantize_kv_rows(v)
        key_cache = key_cache.at[phys, :, off].set(qk, mode="drop")
        value_cache = value_cache.at[phys, :, off].set(qv, mode="drop")
        key_scale = key_scale.at[phys, :, off].set(sk, mode="drop")
        value_scale = value_scale.at[phys, :, off].set(sv, mode="drop")
        return key_cache, value_cache, key_scale, value_scale
    key_cache = key_cache.at[phys, :, off].set(k.astype(key_cache.dtype), mode="drop")
    value_cache = value_cache.at[phys, :, off].set(v.astype(value_cache.dtype), mode="drop")
    return key_cache, value_cache


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
    reference kernel). Positions past ``seq_lens`` scatter into a scratch
    slot (block 0 / slot recomputed) are avoided via clamping + final mask.
    With scale planes the write quantizes in-flight (returns 4 arrays)."""
    b, s, h, d = k.shape
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
    flat_phys = phys.reshape(-1)
    flat_off = jnp.broadcast_to(off, phys.shape).reshape(-1)
    if key_scale is not None:
        qk, sk = _quantize_kv_rows(k.reshape(b * s, h, d))
        qv, sv = _quantize_kv_rows(v.reshape(b * s, h, d))
        key_cache = key_cache.at[flat_phys, :, flat_off].set(qk, mode="drop")
        value_cache = value_cache.at[flat_phys, :, flat_off].set(qv, mode="drop")
        key_scale = key_scale.at[flat_phys, :, flat_off].set(sk, mode="drop")
        value_scale = value_scale.at[flat_phys, :, flat_off].set(sv, mode="drop")
        return key_cache, value_cache, key_scale, value_scale
    flat_k = k.reshape(b * s, h, d).astype(key_cache.dtype)
    flat_v = v.reshape(b * s, h, d).astype(value_cache.dtype)
    key_cache = key_cache.at[flat_phys, :, flat_off].set(flat_k, mode="drop")
    value_cache = value_cache.at[flat_phys, :, flat_off].set(flat_v, mode="drop")
    return key_cache, value_cache


@jax.named_scope(SCOPE_KV_COW)
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
    nb = key_cache.shape[0]
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    csrc = jnp.clip(src, 0, nb - 1)

    if key_scale is not None:
        def _copy4(kv):
            kc, vc, ks, vs = kv
            kc = kc.at[dst].set(kc[csrc], mode="drop")
            vc = vc.at[dst].set(vc[csrc], mode="drop")
            ks = ks.at[dst].set(ks[csrc], mode="drop")
            vs = vs.at[dst].set(vs[csrc], mode="drop")
            return kc, vc, ks, vs

        return jax.lax.cond(
            jnp.any(dst < nb), _copy4, lambda kv: kv,
            (key_cache, value_cache, key_scale, value_scale),
        )

    def _copy(kv):
        kc, vc = kv
        kc = kc.at[dst].set(kc[csrc], mode="drop")
        vc = vc.at[dst].set(vc[csrc], mode="drop")
        return kc, vc

    return jax.lax.cond(
        jnp.any(dst < nb), _copy, lambda kv: kv, (key_cache, value_cache)
    )


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
    ``seq_lens[b] + j``. Rows past ``q_lens`` (and masked-off slots) are
    routed out of bounds and dropped — a decode row (``q_lens == 1``) and a
    prompt-chunk row (``q_lens == C``) ride the same scatter. With scale
    planes the write quantizes in-flight per token row (returns 4 arrays):
    the scale scatter uses the SAME out-of-bounds routing, so dropped KV rows
    drop their scales with them."""
    b, c, h, d = k.shape
    nb, bs = key_cache.shape[0], key_cache.shape[2]
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
    phys = jnp.where(valid, phys, nb)
    flat_phys = phys.reshape(-1)
    flat_off = off.reshape(-1)
    if key_scale is not None:
        qk, sk = _quantize_kv_rows(k.reshape(b * c, h, d))
        qv, sv = _quantize_kv_rows(v.reshape(b * c, h, d))
        key_cache = key_cache.at[flat_phys, :, flat_off].set(qk, mode="drop")
        value_cache = value_cache.at[flat_phys, :, flat_off].set(qv, mode="drop")
        key_scale = key_scale.at[flat_phys, :, flat_off].set(sk, mode="drop")
        value_scale = value_scale.at[flat_phys, :, flat_off].set(sv, mode="drop")
        return key_cache, value_cache, key_scale, value_scale
    flat_k = k.reshape(b * c, h, d).astype(key_cache.dtype)
    flat_v = v.reshape(b * c, h, d).astype(value_cache.dtype)
    key_cache = key_cache.at[flat_phys, :, flat_off].set(flat_k, mode="drop")
    value_cache = value_cache.at[flat_phys, :, flat_off].set(flat_v, mode="drop")
    return key_cache, value_cache


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
):
    """One MIXED prefill/decode step over the paged cache — the chunked-
    prefill dispatch ("Ragged Paged Attention", arxiv 2604.15464): every
    batch row carries up to ``C`` new tokens; a decode row has ``q_lens ==
    1``, a prompt-chunk row up to ``C``. The chunk's KV is appended first, so
    query token ``j`` (absolute position ``seq_lens + j``) attends over every
    cached position ``<= seq_lens + j`` — causal within the chunk, full
    history before it. Rows past ``q_lens`` and masked-off slots return
    exactly zeros (lockstep with the Pallas kernel's skip).

    Returns ``(out [B, C, HQ, D], key_cache, value_cache)``, plus the
    updated ``(key_scale, value_scale)`` planes when given (the int8 pool:
    quantize-on-write in the same fused append, dequant inside the kernel's
    block walk — or the identical composition in the XLA fallback).
    """
    b, c, hq, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = 1.0 / (d**0.5)
    quantized = key_scale is not None
    if quantized:
        key_cache, value_cache, key_scale, value_scale = block_cache_append_chunk(
            key_cache, value_cache, k, v, block_tables, seq_lens, q_lens,
            slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
        )
    else:
        key_cache, value_cache = block_cache_append_chunk(
            key_cache, value_cache, k, v, block_tables, seq_lens, q_lens,
            slot_mask=slot_mask,
        )
    attend_q = q_lens
    if slot_mask is not None:
        attend_q = jnp.where(slot_mask, attend_q, 0)
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    def _ret(out):
        if quantized:
            return out, key_cache, value_cache, key_scale, value_scale
        return out, key_cache, value_cache

    if pallas_enabled("use_pallas_paged_attention"):
        # ragged mixed prefill/decode kernel: one grid walks each sequence's
        # physical blocks once, serving its decode row and its prompt-chunk
        # rows alike. The kernel is REQUIRED to compile on TPU
        # (tests/test_tpu_aot_compile.py): only a trace-time failure degrades
        # to the XLA path below. Under a tensor-parallel mesh the kernel runs
        # shard_mapped over the head partition.
        from paddle_tpu.kernels.paged_attention import paged_flash_chunk

        tp_mesh = shard_group_mesh()
        try:
            if quantized:
                # injected dequant failure degrades THIS dispatch to the
                # XLA fallback below (counted), never the engine's
                # recovery path — the except arm swallows it
                _fault_point("quant.dequant")
            if tp_mesh is not None:
                out = _tp_sharded_flash_chunk(
                    q, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale, tp_mesh,
                    k_scale=key_scale, v_scale=value_scale,
                )
            else:
                out = paged_flash_chunk(
                    q, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale=scale,
                    k_scale=key_scale, v_scale=value_scale,
                )
            return _ret(out)
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            warn_fallback("paged_flash_chunk", exc)
    out = _gather_chunk_attend(
        q, key_cache, value_cache, block_tables, seq_lens, attend_q, scale,
        k_scale=key_scale, v_scale=value_scale,
    )
    return _ret(out)


def block_multihead_chunk_attention_fused(
    q: jax.Array,  # [B, C, HQ, D] PRE-rope ragged chunk of new tokens
    k: jax.Array,  # [B, C, HKV, D] PRE-rope new keys
    v: jax.Array,
    cos: jax.Array,  # [B, C, 1, D] offset-gathered rope rows (model layout)
    sin: jax.Array,
    key_cache: jax.Array,  # [NB, HKV, BS, D]
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens already cached (EXCLUDING this chunk)
    q_lens: jax.Array,  # [B] valid new tokens this step (1 = decode row)
    scale: Optional[float] = None,
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """:func:`block_multihead_chunk_attention` with RoPE folded in — the
    fused decode layer's attention entry (``FLAGS_use_fused_decode_layer``).

    Takes PRE-rope q/k plus the per-slot rope rows and collapses the layer's
    rope pass + attention to one kernel dispatch: k is rotated by the same
    XLA elementwise composition the unfused path uses (it fuses into the
    cache-append scatter), while q's rotation moves INSIDE the paged kernel's
    block walk. The XLA fallback stays in lockstep by applying the identical
    ``_rope_apply_xla`` to q before the shared dense-gather attention — so on
    a backend without the kernel (CPU reference), fused on/off execute the
    SAME op composition and outputs are byte-identical by construction.
    Scale planes follow the :func:`block_multihead_chunk_attention` contract
    (quantize AFTER the rope — the cache stores roped, quantized keys).
    """
    from paddle_tpu.incubate.nn.functional import _rope_apply_xla

    b, c, hq, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    quantized = key_scale is not None
    k = _rope_apply_xla(k, sin, cos, True)
    if quantized:
        key_cache, value_cache, key_scale, value_scale = block_cache_append_chunk(
            key_cache, value_cache, k, v, block_tables, seq_lens, q_lens,
            slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
        )
    else:
        key_cache, value_cache = block_cache_append_chunk(
            key_cache, value_cache, k, v, block_tables, seq_lens, q_lens,
            slot_mask=slot_mask,
        )
    attend_q = q_lens
    if slot_mask is not None:
        attend_q = jnp.where(slot_mask, attend_q, 0)
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    def _ret(out):
        if quantized:
            return out, key_cache, value_cache, key_scale, value_scale
        return out, key_cache, value_cache

    if pallas_enabled("use_pallas_paged_attention"):
        from paddle_tpu.kernels.paged_attention import paged_flash_chunk_fused

        tp_mesh = shard_group_mesh()
        cos3 = cos.reshape(b, c, d)
        sin3 = sin.reshape(b, c, d)
        try:
            if quantized:
                _fault_point("quant.dequant")
            if tp_mesh is not None:
                out = _tp_sharded_flash_chunk_fused(
                    q, cos3, sin3, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale, tp_mesh,
                    k_scale=key_scale, v_scale=value_scale,
                )
            else:
                out = paged_flash_chunk_fused(
                    q, cos3, sin3, key_cache, value_cache, block_tables,
                    seq_lens, attend_q, scale=scale,
                    k_scale=key_scale, v_scale=value_scale,
                )
            return _ret(out)
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            warn_fallback("paged_flash_chunk_fused", exc)
    # lockstep fallback: the SAME rope composition the unfused path applies,
    # then the shared dense-gather attention
    q = _rope_apply_xla(q, sin, cos, True)
    out = _gather_chunk_attend(
        q, key_cache, value_cache, block_tables, seq_lens, attend_q, scale,
        k_scale=key_scale, v_scale=value_scale,
    )
    return _ret(out)


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
    """One paged-cache decode step: append the new KV, attend over the
    sequence's blocks. Returns ``(out [B, 1, HQ, D], key_cache, value_cache)``
    — pass donated caches under jit for true in-place update (the reference
    op is declared ``inplace``) — plus the updated scale planes when given.

    ``slot_mask`` is the continuous-batching engine's ragged-batch contract:
    masked-off slots append nothing, attend over nothing (their effective
    length is forced to 0 so the ragged kernel skips them entirely), and
    return exactly zeros — in lockstep between the Pallas kernel and this XLA
    fallback so slot padding never changes numerics."""
    b, one, hq, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = 1.0 / (d**0.5)
    quantized = key_scale is not None
    if quantized:
        key_cache, value_cache, key_scale, value_scale = block_cache_append(
            key_cache, value_cache, k[:, 0], v[:, 0], block_tables, seq_lens,
            slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
        )
    else:
        key_cache, value_cache = block_cache_append(
            key_cache, value_cache, k[:, 0], v[:, 0], block_tables, seq_lens,
            slot_mask=slot_mask,
        )
    # length INCLUDING the freshly appended token; 0 for padded slots
    attend_lens = seq_lens + 1
    if slot_mask is not None:
        attend_lens = jnp.where(slot_mask, attend_lens, 0)
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    def _ret(out):
        if quantized:
            return out, key_cache, value_cache, key_scale, value_scale
        return out, key_cache, value_cache

    if pallas_enabled("use_pallas_paged_attention", bare="paged_flash_decode"):
        # block-table flash-decode kernel: streams only this sequence's
        # physical blocks HBM -> VMEM (no dense [B, MBS*BS, H, D] gather);
        # only a trace-time failure degrades to the XLA path below
        from paddle_tpu.kernels.paged_attention import paged_flash_decode

        try:
            if quantized:
                _fault_point("quant.dequant")
            out = paged_flash_decode(
                q[:, 0], key_cache, value_cache, block_tables,
                attend_lens,  # kernel masks pos < len INCLUDING this token
                scale=scale,
                k_scale=key_scale, v_scale=value_scale,
            )
            return _ret(out[:, None])
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            warn_fallback("paged_flash_decode", exc)
    # the decode step IS the C == 1 chunk: one new row per sequence whose
    # causal limit is seq_lens + 1 (attend_lens), masked slots exact zeros
    out = _gather_chunk_attend(
        q, key_cache, value_cache, block_tables, seq_lens,
        attend_lens - seq_lens, scale,
        k_scale=key_scale, v_scale=value_scale,
    )
    return _ret(out.astype(q.dtype))


def block_multihead_attention_fused(
    q: jax.Array,  # [B, 1, HQ, D] PRE-rope decode query
    k: jax.Array,  # [B, 1, HKV, D] PRE-rope new key
    v: jax.Array,  # [B, 1, HKV, D] new value
    cos: jax.Array,  # [B, 1, 1, D] offset-gathered rope rows (model layout)
    sin: jax.Array,
    key_cache: jax.Array,  # [NB, HKV, BS, D]
    value_cache: jax.Array,
    block_tables: jax.Array,  # [B, MBS] int32
    seq_lens: jax.Array,  # [B] tokens already cached (EXCLUDING this one)
    scale: Optional[float] = None,
    slot_mask: Optional[jax.Array] = None,  # [B] bool; False = padded slot
    key_scale: Optional[jax.Array] = None,  # [NB, HKV, BS] fp32 (int8 cache)
    value_scale: Optional[jax.Array] = None,
):
    """:func:`block_multihead_attention` with RoPE folded in — the pure-decode
    counterpart of :func:`block_multihead_chunk_attention_fused`.

    Takes PRE-rope q/k plus the per-slot rope rows: k is rotated by the same
    XLA elementwise composition the unfused path uses (it fuses into the
    cache-append scatter) while q's rotation moves INSIDE the flash-decode
    block walk (``paged_flash_decode_fused``). The XLA fallback applies the
    identical ``_rope_apply_xla`` to q before the shared dense-gather
    attention, so fused on/off execute the same op composition off-TPU and
    outputs are byte-identical by construction.
    """
    from paddle_tpu.incubate.nn.functional import _rope_apply_xla

    b, one, hq, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    quantized = key_scale is not None
    k = _rope_apply_xla(k, sin, cos, True)
    if quantized:
        key_cache, value_cache, key_scale, value_scale = block_cache_append(
            key_cache, value_cache, k[:, 0], v[:, 0], block_tables, seq_lens,
            slot_mask=slot_mask, key_scale=key_scale, value_scale=value_scale,
        )
    else:
        key_cache, value_cache = block_cache_append(
            key_cache, value_cache, k[:, 0], v[:, 0], block_tables, seq_lens,
            slot_mask=slot_mask,
        )
    # length INCLUDING the freshly appended token; 0 for padded slots
    attend_lens = seq_lens + 1
    if slot_mask is not None:
        attend_lens = jnp.where(slot_mask, attend_lens, 0)
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    def _ret(out):
        if quantized:
            return out, key_cache, value_cache, key_scale, value_scale
        return out, key_cache, value_cache

    if pallas_enabled("use_pallas_paged_attention", bare="paged_flash_decode_fused"):
        # rope-fused flash-decode kernel; same contract as the unfused
        # decode dispatch above
        from paddle_tpu.kernels.paged_attention import paged_flash_decode_fused

        cos3 = cos.reshape(b, 1, d)
        sin3 = sin.reshape(b, 1, d)
        try:
            if quantized:
                _fault_point("quant.dequant")
            out = paged_flash_decode_fused(
                q[:, 0], cos3, sin3, key_cache, value_cache,
                block_tables,
                attend_lens,  # kernel masks pos < len INCLUDING this token
                scale=scale,
                k_scale=key_scale, v_scale=value_scale,
            )
            return _ret(out[:, None])
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            warn_fallback("paged_flash_decode_fused", exc)
    # lockstep fallback: the SAME rope composition the unfused path applies,
    # then the shared dense-gather attention (C == 1 chunk)
    q = _rope_apply_xla(q, sin, cos, True)
    out = _gather_chunk_attend(
        q, key_cache, value_cache, block_tables, seq_lens,
        attend_lens - seq_lens, scale,
        k_scale=key_scale, v_scale=value_scale,
    )
    return _ret(out.astype(q.dtype))
