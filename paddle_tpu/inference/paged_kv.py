"""The paged-KV state of the serving step: what the engine, the models and
the paged kernel hand each other inside one compiled program.

Two things of different lifetime:

- :class:`PagedBatch`, one per STEP: which pages each slot owns, how many
  tokens it has cached, whether it is live, and how many new rows it carries.
  Every KV set of the step shares the one object.
- :class:`PagedKV`, one per KV SET (a layer; a pass x layer for a looped
  stack): the set's pool planes plus the step's batch. It owns what is done
  to a plane inside the step: the copy-on-write :meth:`~PagedKV.fork`, and
  :meth:`~PagedKV.attend` (append the step's keys and values, then walk the
  sequence's pages). A quantised pool is one whose scale planes are not
  ``None``, decided once where the pool is allocated (:meth:`PagedKV.zeros`).

Both are pytrees whose leaves flatten in the order ``key, value[, key_scale,
value_scale], block_tables, seq_lens, slot_mask, q_lens``, so a ``PagedKV``
crosses a ``jax.jit`` boundary as it is. A model takes its paged path when
its past IS a ``PagedKV`` (``isinstance``); a cache of another kind is another
class beside it, and the step does not branch on it.

- :class:`RecurrentState`, one per RECURRENT SET (a state-space block): planes
  ``[slots, ...]`` with no pages, under the same batch. Its lifetime differs:
  a page is freed when its request ends, a slot's state is ZEROED by the step
  in which the next request's first chunk arrives (``seq_lens == 0``). It owns
  :meth:`~RecurrentState.fork` (copy a slot's state) and
  :meth:`~RecurrentState.advance` (continue the conv and the scan over the
  step's rows), the counterparts of ``PagedKV``'s two.
- :class:`LatentKV`, one per LATENT SET (a multi-head-latent-attention layer):
  a PAGED set like ``PagedKV``, under the same batch, tables and copy-on-write,
  whose unit is ONE row a token, ``[normalised latent | roped shared key]``,
  that every query head reads as its key and (its first lanes) as its value.
  Its :meth:`~LatentKV.attend` takes the ABSORBED queries.
- :class:`CacheSet` is how a model's configuration tells a cache owner what
  sets it holds, in block order (``config.cache_sets``): kind, plane shapes
  and dtypes, and for a PAGED set the class that owns its planes. A model
  without it holds ``config.num_kv_sets`` paged sets of ``PagedKV``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.functional.block_attention import (
    _fork_pages,
    block_cache_cow_copy,
    block_multihead_chunk_attention,
    latent_chunk_attention,
)
from paddle_tpu.incubate.nn.functional.mamba2 import causal_conv_chunk, split_conv_channels, ssd_chunk_slots

__all__ = ["CacheSet", "LatentKV", "PAGED", "PagedBatch", "PagedKV", "RECURRENT", "RecurrentState"]

PAGED, RECURRENT = "paged", "recurrent"

# jax.named_scope names inside RecurrentState.advance
SCOPE_SSM_CONV = "ssm_conv"
SCOPE_SSM_SCAN = "ssm_scan"


@dataclasses.dataclass(frozen=True)
class CacheSet:
    """One cache set a model holds. ``planes``: ``(shape, dtype)`` of each
    plane for ONE unit of the set, a token of a page for ``PAGED`` (``(KVH,
    D)`` twice; :meth:`LatentKV.spec`) and a slot for ``RECURRENT``
    (:meth:`RecurrentState.spec`). ``owner``: the class that owns a PAGED
    set's planes (``None``: :class:`PagedKV`); a unit plane ``(H, D)`` is the
    pool plane ``[NB, H, BS, D]``."""

    kind: str
    planes: Tuple[Tuple[Tuple[int, ...], Any], ...]
    owner: Any = None

    @property
    def unit_bytes(self) -> int:
        return sum(math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in self.planes)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PagedBatch:
    """One step's ragged batch over the paged pool."""

    block_tables: jax.Array  # [S, MBS] int32 physical page of each logical one
    seq_lens: jax.Array  # [S] tokens cached BEFORE this step's rows
    slot_mask: jax.Array  # [S] bool; False = padded slot (writes and reads nothing)
    q_lens: jax.Array  # [S] valid new rows this step (1 = a decode row)

    @classmethod
    def decode(cls, block_tables: jax.Array, seq_lens: jax.Array) -> "PagedBatch":
        """Every slot live with one new token (``generate_paged``'s step)."""
        return cls(block_tables, seq_lens, jnp.ones(seq_lens.shape, bool), jnp.ones_like(seq_lens))

    def live_rows(self, chunk: int) -> jax.Array:
        """``[S * chunk]`` bool: the step's rows that are real (a live slot's first ``q_lens``)."""
        rows = jnp.arange(chunk, dtype=self.q_lens.dtype)[None, :] < self.q_lens[:, None]
        return (rows & self.slot_mask[:, None]).reshape(-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PagedKV:
    """One KV set's pool planes ``[NB, KVH, BS, D]`` (and, quantised, their
    fp32 per-token scale planes ``[NB, KVH, BS]``) under a step's batch."""

    key: jax.Array
    value: jax.Array
    key_scale: Optional[jax.Array] = None
    value_scale: Optional[jax.Array] = None
    batch: Optional[PagedBatch] = None

    @classmethod
    def zeros(cls, shape: Tuple[int, ...], dtype: Any, batch: Optional[PagedBatch] = None) -> "PagedKV":
        """An empty pool. An int8 pool gets scale planes of ONES:
        ``quantize(zeros)`` is ``q = 0, scale = 1``, so it dequantises to
        exact zeros."""
        planes = [jnp.zeros(shape, dtype) for _ in range(2)]  # two buffers: the owner may donate them
        if jnp.dtype(dtype) == jnp.int8:
            planes += [jnp.ones(shape[:3], jnp.float32) for _ in range(2)]
        return cls(*planes, batch=batch)

    @property
    def planes(self) -> Tuple[jax.Array, ...]:
        """``(key, value[, key_scale, value_scale])``: what the pool's owner
        keeps between steps."""
        return tuple(jax.tree.leaves((self.key, self.value, self.key_scale, self.value_scale)))

    def fork(self, src: jax.Array, dst: jax.Array) -> "PagedKV":
        """Copy-on-write: pages ``src`` duplicated into ``dst`` (``dst ==
        num_blocks``: no fork), scales with their blocks."""
        planes = block_cache_cow_copy(
            self.key, self.value, src, dst, key_scale=self.key_scale, value_scale=self.value_scale
        )
        return PagedKV(*planes, batch=self.batch)

    def attend(
        self,
        q: jax.Array,  # [S, C, HQ, D] PRE-rope when cos / sin are given
        k: jax.Array,  # [S, C, KVH, D]
        v: jax.Array,
        cos: Optional[jax.Array] = None,  # [S, C, 1, D] rope rows at the slots' positions
        sin: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, "PagedKV"]:
        """Append the step's keys and values at the batch's positions, then
        attend ``q`` over each slot's pages. Returns ``(out [S, C, HQ, D],
        the set with its planes updated)``."""
        b = self.batch
        out, *planes = block_multihead_chunk_attention(
            q, k, v, self.key, self.value, b.block_tables, b.seq_lens, b.q_lens,
            slot_mask=b.slot_mask, key_scale=self.key_scale, value_scale=self.value_scale,
            cos=cos, sin=sin,
        )
        return out, PagedKV(*planes, batch=b)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LatentKV:
    """One latent set's pool plane ``[NB, 1, BS, W]`` under a step's batch: a
    token's row is ``[c_kv (normalised) | rope(k_pe) | 0]``, ``W`` the two
    widths padded to whole 128-lane tiles (a page leaves HBM in such tiles,
    and the tiled layout pads the row to them anyway)."""

    rows: jax.Array
    batch: Optional[PagedBatch] = None

    @staticmethod
    def spec(latent: int, rope: int, dtype: Any) -> CacheSet:
        """What a layer of these widths keeps a token."""
        return CacheSet(PAGED, (((1, -(-(latent + rope) // 128) * 128), dtype),), LatentKV)

    @classmethod
    def zeros(cls, num_blocks: int, block_size: int, spec: CacheSet, batch: Optional[PagedBatch] = None) -> "LatentKV":
        (shape, dtype), = spec.planes
        return cls(jnp.zeros((num_blocks, shape[0], block_size, shape[1]), dtype), batch=batch)

    @property
    def planes(self) -> Tuple[jax.Array, ...]:
        """``(rows,)``: what the pool's owner keeps between steps."""
        return (self.rows,)

    def fork(self, src: jax.Array, dst: jax.Array) -> "LatentKV":
        """Copy-on-write, as :meth:`PagedKV.fork`: pages ``src`` duplicated into
        ``dst``, with no conditional around the copy (the append after it keeps
        the plane's layout, so the donated plane is updated where it lies)."""
        return LatentKV(*_fork_pages(self.planes, src, dst), batch=self.batch)

    def attend(
        self,
        q: jax.Array,  # [S, C, H, latent + rope] ABSORBED queries, roped and scaled
        row: jax.Array,  # [S, C, latent + rope] the step's rows: normalised latent | roped key
        value_width: int,  # the row's first lanes that are the value (the latent)
    ) -> Tuple[jax.Array, "LatentKV"]:
        """Append the step's rows at the batch's positions, then attend every
        head of ``q`` over each slot's rows, a row key and value at once (the
        one form for decode rows and prefill chunks alike). Returns ``(out [S,
        C, H, value_width], the set with its plane updated)``."""
        b = self.batch
        pad = self.rows.shape[-1] - row.shape[-1]
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad),))
        row = jnp.pad(row, ((0, 0),) * 2 + ((0, pad),))
        out, rows = latent_chunk_attention(
            q, row, self.rows, b.block_tables, b.seq_lens, b.q_lens, value_width, slot_mask=b.slot_mask
        )
        return out, LatentKV(rows, batch=b)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RecurrentState:
    """One state-space block's per-slot state under a step's batch: the scan's
    ``ssm [S, H, P, N]`` (float32) and the causal conv's tail ``conv [S, K-1,
    W]``, its last ``K - 1`` inputs."""

    ssm: jax.Array
    conv: jax.Array
    batch: Optional[PagedBatch] = None

    @staticmethod
    def spec(heads: int, head_dim: int, state: int, kernel: int, width: int, dtype: Any) -> CacheSet:
        """What a block of these sizes keeps a slot; the conv tail in ``dtype``."""
        return CacheSet(RECURRENT, (((heads, head_dim, state), jnp.float32), ((kernel - 1, width), dtype)))

    @classmethod
    def zeros(cls, slots: int, spec: CacheSet, batch: Optional[PagedBatch] = None) -> "RecurrentState":
        return cls(*(jnp.zeros((slots,) + tuple(shape), dtype) for shape, dtype in spec.planes), batch=batch)

    @property
    def planes(self) -> Tuple[jax.Array, ...]:
        """``(ssm, conv)``: what the owner keeps between steps."""
        return self.ssm, self.conv

    def fork(self, src: jax.Array, dst: jax.Array) -> "RecurrentState":
        """Slot ``src[i]``'s state copied into slot ``dst[i]`` (``dst[i] ==
        slots``: no fork; the scatter drops it)."""
        return RecurrentState(
            *(plane.at[dst].set(plane[src], mode="drop") for plane in self.planes), batch=self.batch
        )

    def advance(
        self,
        xbc: jax.Array,  # [S, C, W] the conv's inputs: x | B | C, before the conv
        dt: jax.Array,  # [S, C, H] float32, after softplus
        conv_weight: jax.Array,  # [K, W]
        conv_bias: jax.Array,  # [W]
        a: jax.Array,  # [H] negative
        d_skip: jax.Array,  # [H]
        groups: int,
    ) -> Tuple[jax.Array, "RecurrentState"]:
        """Continue each slot's conv and scan over the step's rows. A slot
        whose ``seq_lens`` is 0 starts from ZERO state (a request's first
        chunk: whatever the slot's last request left is dropped here, not at
        release); rows past ``q_lens`` do not advance the state (``dt``
        masked, so they neither decay nor add; the conv tail moves by
        ``q_lens`` rows only); a slot that is masked or has no rows is left
        untouched. Returns ``(y [S, C, H, P] float32, the set with its planes
        updated)``; rows past ``q_lens`` of ``y`` are garbage."""
        bt = self.batch
        heads, p, n = self.ssm.shape[1:]
        q = jnp.where(bt.slot_mask, bt.q_lens, 0)
        fresh = (q > 0) & (bt.seq_lens == 0)
        tail = jnp.where(fresh[:, None, None], 0, self.conv)
        with jax.named_scope(SCOPE_SSM_CONV):
            xbc, tail = causal_conv_chunk(xbc, tail, conv_weight, conv_bias, q)
        x, b, cc = split_conv_channels(xbc, heads, p, groups, n)
        valid = jnp.arange(xbc.shape[1], dtype=q.dtype)[None, :] < q[:, None]
        with jax.named_scope(SCOPE_SSM_SCAN):
            y, ssm = ssd_chunk_slots(x, jnp.where(valid[..., None], dt, 0.0), a, b, cc, d_skip, self.ssm, q > 0, fresh)
        return y, RecurrentState(ssm, tail, batch=bt)
