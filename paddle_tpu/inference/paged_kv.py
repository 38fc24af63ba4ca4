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
its past IS a ``PagedKV`` (``isinstance``); a cache of another kind (window
layers, latent rows, recurrent state) is another class with the same two
methods, and the step does not branch on it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.functional.block_attention import (
    block_cache_cow_copy,
    block_multihead_chunk_attention,
)

__all__ = ["PagedBatch", "PagedKV"]


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
