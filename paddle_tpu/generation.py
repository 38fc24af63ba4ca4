"""Autoregressive decoding: one compiled XLA program per (model, shape).

The reference's decode path is the inference stack's cache attention
(``paddle/phi/ops/yaml/ops.yaml:3074`` ``masked_multihead_attention_``,
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``) driven
by a Python loop; the ``generate()`` surface mirrors the PaddleNLP
GenerationMixin API. TPU-native shape: prefill + ``lax.scan`` of single-token
steps over fixed-size KV-cache buffers, the whole thing inside ONE jit — no
per-step retraces, no growing shapes, every decode step is the same program.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.nn.layer.layers import bind_param_arrays

__all__ = ["GenerationMixin"]


def _filter_logits(logits: jax.Array, temperature: float, top_k: int, top_p: float) -> jax.Array:
    """Standard sampling filters (temperature, top-k, nucleus/top-p)."""
    if temperature != 1.0:
        logits = logits / max(float(temperature), 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum_excl = jnp.cumsum(probs, axis=-1) - probs
        # smallest logit still inside the nucleus; everything below is cut
        kept_min = jnp.min(
            jnp.where(cum_excl > top_p, jnp.inf, sorted_desc), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < kept_min, -jnp.inf, logits)
    return logits


class GenerationMixin:
    """Adds ``generate()`` to a causal LM whose ``forward`` supports
    ``(input_ids, past_key_values, use_cache, cache_position)`` with
    static-cache decode semantics (see ``LlamaAttention``)."""

    # -- shared decode plumbing (one copy for generate/generate_beam) -------
    def _decode_prep(self, input_ids: Any, max_new_tokens: int,
                     eos_token_id: Optional[int], pad_token_id: Optional[int]):
        """Validate + normalize the common decode arguments. Returns
        ``(ids_array, pad_token_id)``; raises like ``generate`` always has."""
        from paddle_tpu.core.tensor import Tensor

        ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
        max_pos = getattr(getattr(self, "config", None), "max_position_embeddings", None)
        if max_pos is not None and ids.shape[1] + max_new_tokens > max_pos:
            # the decode path's dynamic rope-table slice would silently clamp
            # past the table end and emit garbage — fail loudly instead
            raise ValueError(
                f"prompt ({ids.shape[1]}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_position_embeddings ({max_pos})"
            )
        if pad_token_id is None:
            pad_token_id = eos_token_id if eos_token_id is not None else 0
        return ids, int(pad_token_id)

    def _compiled(self, cfg: tuple, build) -> Any:
        """Per-model bounded FIFO cache of compiled decode programs."""
        cache = getattr(self, "_generate_jit_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_generate_jit_cache", cache)
        if cfg not in cache and len(cache) >= 16:
            # bounded: each entry pins a compiled executable (FIFO eviction)
            cache.pop(next(iter(cache)))
        if cfg not in cache:
            cache[cfg] = build()
        return cache[cfg]

    def generate(
        self,
        input_ids: Any,
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
        seed: int = 0,
    ) -> Any:
        """Greedy or sampling decode. Returns ``[B, prompt + max_new_tokens]``
        token ids (prompt included); after ``eos_token_id`` a sequence is
        padded with ``pad_token_id`` (defaults to eos)."""
        from paddle_tpu.core.tensor import Tensor

        ids, pad_token_id = self._decode_prep(
            input_ids, max_new_tokens, eos_token_id, pad_token_id
        )
        b, prompt = ids.shape
        if max_new_tokens == 0:
            return Tensor(ids)

        cfg = (
            b, prompt, int(max_new_tokens), bool(do_sample), float(temperature),
            int(top_k), float(top_p), eos_token_id, pad_token_id,
        )
        fn = self._compiled(
            cfg,
            lambda: jax.jit(
                functools.partial(
                    self._generate_impl,
                    max_new_tokens=int(max_new_tokens),
                    do_sample=bool(do_sample),
                    temperature=float(temperature),
                    top_k=int(top_k),
                    top_p=float(top_p),
                    eos_token_id=eos_token_id,
                    pad_token_id=int(pad_token_id),
                )
            ),
        )
        named = list(self.named_parameters())
        arrays = [p._data for _, p in named]
        out = fn(arrays, ids, jax.random.PRNGKey(seed))
        return Tensor(out)

    def generate_paged(
        self,
        input_ids: Any,
        max_new_tokens: int = 32,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
    ) -> Any:
        """Greedy decode over the PAGED KV cache (reference
        ``block_multihead_attention_``): physical blocks are allocated to
        sequences as they grow and reclaimed at the end — the serving-side
        memory model, vs ``generate()``'s fixed dense buffers. The host
        allocator runs between steps; each decode step is one jitted program
        (block tables and lengths are data, so shapes never change) over the
        serving engine's own path: a ``PagedKV`` set per layer under a batch
        of one new token a slot, the chunk kernel at ``C == 1``. The pool's
        storage dtype follows ``FLAGS_kv_cache_dtype`` as the engine's does.

        This runs ONE static batch to completion (a finished sequence holds
        its slot and blocks until all are done); for mixed-length serving
        traffic use ``paddle_tpu.inference.ContinuousBatchingEngine``, which
        admits/evicts per step over a shared pool with the same numerics —
        the engine's per-sequence outputs match this method token-for-token."""
        import numpy as np

        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.flags import GLOBAL_FLAGS
        from paddle_tpu.incubate.nn.functional import BlockKVCache, block_cache_prefill
        from paddle_tpu.inference.paged_kv import PagedBatch, PagedKV

        ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, prompt = ids.shape
        if max_new_tokens <= 0:
            return Tensor(ids)
        cfg = self.config
        kvh = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        max_len = prompt + max_new_tokens
        if getattr(cfg, "max_position_embeddings", None) and max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt ({prompt}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_position_embeddings ({cfg.max_position_embeddings})"
            )
        mbs = -(-max_len // block_size)
        if num_blocks is None:
            num_blocks = b * mbs
        dtype = next(iter(self.parameters())).dtype
        kv_dtype = jnp.int8 if GLOBAL_FLAGS.get("kv_cache_dtype") == "int8" else dtype
        mgr = BlockKVCache(num_blocks, block_size, kvh, hd, mbs, dtype=dtype)
        for i in range(b):
            mgr.allocate(i, prompt)
        tables = mgr.block_table(range(b))
        lens = jnp.full((b,), prompt, jnp.int32)

        if pad_token_id is None:
            pad_token_id = eos_token_id if eos_token_id is not None else 0

        # prefill: dense forward once, then pour each layer's K/V into blocks
        import paddle_tpu

        with paddle_tpu.no_grad():
            logits, dense_caches = self(Tensor(ids), use_cache=True)
        layer_caches = []  # a KV set's planes each: (key, value[, key_scale, value_scale])
        for k_t, v_t in dense_caches:
            # paged layout [NB, H, BS, D] (see BlockKVCache)
            pool = PagedKV.zeros((num_blocks, kvh, block_size, hd), kv_dtype)
            layer_caches.append(block_cache_prefill(
                pool.key, pool.value, k_t._data, v_t._data, tables, lens,
                key_scale=pool.key_scale, value_scale=pool.value_scale,
            ))
        tok = jnp.argmax(logits._data[:, -1, :].astype(jnp.float32), axis=-1).astype(jnp.int32)
        done = tok == eos_token_id if eos_token_id is not None else jnp.zeros((b,), bool)

        named = list(self.named_parameters())
        # one compiled decode program per geometry, cached across calls
        # (re-jitting per request would pay a full XLA compile per serve)
        step_cache = getattr(self, "_paged_step_cache", None)
        if step_cache is None:
            step_cache = {}
            object.__setattr__(self, "_paged_step_cache", step_cache)
        step_key = (b, num_blocks, block_size, mbs, jnp.dtype(kv_dtype).name)
        if step_key not in step_cache and len(step_cache) >= 8:
            step_cache.pop(next(iter(step_cache)))

        @jax.jit
        def _paged_step(param_arrays, tok, caches, tables, lens):
            with bind_param_arrays(named, param_arrays):
                batch = PagedBatch.decode(tables, lens)
                pkv = [PagedKV(*planes, batch=batch) for planes in caches]
                with paddle_tpu.no_grad():
                    step_logits, new_caches = self(Tensor(tok[:, None]), past_key_values=pkv, use_cache=True)
                nxt = jnp.argmax(
                    step_logits._data[:, -1, :].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return nxt, [kv.planes for kv in new_caches]

        step = step_cache.setdefault(step_key, _paged_step)

        arrays = [p._data for _, p in named]
        out_toks = [tok]
        for _ in range(max_new_tokens - 1):
            for i in range(b):
                mgr.allocate(i, 1)
            tables = mgr.block_table(range(b))
            nxt, layer_caches = step(arrays, tok, layer_caches, tables, lens)
            lens = lens + 1
            nxt = jnp.where(done, jnp.int32(pad_token_id), nxt)
            if eos_token_id is not None:
                done = done | (nxt == eos_token_id)
            out_toks.append(nxt)
            tok = nxt
        for i in range(b):
            mgr.free(i)
        return Tensor(jnp.concatenate([ids] + [t[:, None] for t in out_toks], axis=1))

    # traced: runs once per (shape, sampling config), then pure XLA
    def _generate_impl(
        self,
        param_arrays: List[Any],
        ids: jax.Array,
        key: jax.Array,
        *,
        max_new_tokens: int,
        do_sample: bool,
        temperature: float,
        top_k: int,
        top_p: float,
        eos_token_id: Optional[int],
        pad_token_id: int,
    ) -> jax.Array:
        import paddle_tpu
        from paddle_tpu.core.tensor import Tensor

        b, prompt = ids.shape
        s_total = prompt + max_new_tokens

        def choose(logits: jax.Array, k: jax.Array) -> jax.Array:
            logits = logits.astype(jnp.float32)
            if do_sample:
                return jax.random.categorical(
                    k, _filter_logits(logits, temperature, top_k, top_p), axis=-1
                ).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        named = list(self.named_parameters())
        with bind_param_arrays(named, param_arrays):
            with paddle_tpu.no_grad():
                logits, caches = self(Tensor(ids), use_cache=True)
            key, sub = jax.random.split(key)
            tok0 = choose(logits._data[:, -1, :], sub)
            done0 = (
                tok0 == eos_token_id
                if eos_token_id is not None
                else jnp.zeros((b,), bool)
            )
            pad_spec = ((0, 0), (0, s_total - prompt), (0, 0), (0, 0))
            cks = [jnp.pad(k_t._data, pad_spec) for k_t, _ in caches]
            cvs = [jnp.pad(v_t._data, pad_spec) for _, v_t in caches]

            def body(carry, _):
                tok, cks, cvs, pos, done, key = carry
                with paddle_tpu.no_grad():
                    step_logits, new_caches = self(
                        Tensor(tok[:, None]),
                        past_key_values=[
                            (Tensor(k), Tensor(v)) for k, v in zip(cks, cvs)
                        ],
                        use_cache=True,
                        cache_position=Tensor(pos),
                    )
                key, sub = jax.random.split(key)
                nxt = choose(step_logits._data[:, -1, :], sub)
                nxt = jnp.where(done, jnp.int32(pad_token_id), nxt)
                if eos_token_id is not None:
                    done = done | (nxt == eos_token_id)
                cks2 = [c[0]._data for c in new_caches]
                cvs2 = [c[1]._data for c in new_caches]
                return (nxt, cks2, cvs2, pos + 1, done, key), nxt

            # tok0 came from the prefill logits; the scan emits each step's
            # NEWLY chosen token, so only max_new_tokens - 1 decoder steps run
            # (emitting the carry instead would pay one full forward whose
            # result is discarded)
            init = (tok0, cks, cvs, jnp.int32(prompt), done0, key)
            _, toks = jax.lax.scan(body, init, None, length=max_new_tokens - 1)
        return jnp.concatenate([ids, tok0[:, None], toks.T], axis=1)

    # -- beam search --------------------------------------------------------
    def generate_beam(
        self,
        input_ids: Any,
        max_new_tokens: int = 32,
        num_beams: int = 4,
        length_penalty: float = 0.0,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
    ) -> Any:
        """Beam-search decode (reference ``beam_search`` op +
        PaddleNLP ``BeamSearchScorer``): the whole search is ONE compiled
        scan — beams live as a folded batch axis, each step reorders the KV
        cache by backpointer, and the final sequences are reconstructed with
        the ``gather_tree`` op. Returns ``[B, prompt + max_new_tokens]``."""
        from paddle_tpu.core.tensor import Tensor

        if num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        ids, pad_token_id = self._decode_prep(
            input_ids, max_new_tokens, eos_token_id, pad_token_id
        )
        b, prompt = ids.shape
        if max_new_tokens == 0:
            return Tensor(ids)

        cfg = ("beam", b, prompt, int(max_new_tokens), int(num_beams),
               float(length_penalty), eos_token_id, pad_token_id)
        fn = self._compiled(
            cfg,
            lambda: jax.jit(
                functools.partial(
                    self._generate_beam_impl,
                    max_new_tokens=int(max_new_tokens),
                    num_beams=int(num_beams),
                    length_penalty=float(length_penalty),
                    eos_token_id=eos_token_id,
                    pad_token_id=int(pad_token_id),
                )
            ),
        )
        named = list(self.named_parameters())
        arrays = [p._data for _, p in named]
        return Tensor(fn(arrays, ids))

    def _generate_beam_impl(
        self,
        param_arrays: List[Any],
        ids: jax.Array,
        *,
        max_new_tokens: int,
        num_beams: int,
        length_penalty: float,
        eos_token_id: Optional[int],
        pad_token_id: int,
    ) -> jax.Array:
        import paddle_tpu
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.ops.parity import gather_tree

        K = num_beams
        NEG = -1e9
        b, prompt = ids.shape
        s_total = prompt + max_new_tokens

        named = list(self.named_parameters())
        with bind_param_arrays(named, param_arrays):
            with paddle_tpu.no_grad():
                logits, caches = self(Tensor(ids), use_cache=True)
            logp0 = jax.nn.log_softmax(logits._data[:, -1, :].astype(jnp.float32))
            V = logp0.shape[-1]
            scores, tok0 = jax.lax.top_k(logp0, K)  # [B, K]
            tok0 = tok0.astype(jnp.int32)
            done = (
                tok0 == eos_token_id if eos_token_id is not None
                else jnp.zeros((b, K), bool)
            )
            lens = jnp.ones((b, K), jnp.int32)
            pad_spec = ((0, 0), (0, s_total - prompt), (0, 0), (0, 0))
            # beams fold into the batch axis: [B*K, S, H, D]
            cks = [jnp.repeat(jnp.pad(k_t._data, pad_spec), K, axis=0) for k_t, _ in caches]
            cvs = [jnp.repeat(jnp.pad(v_t._data, pad_spec), K, axis=0) for _, v_t in caches]
            # one-hot pad row: a finished beam only extends by pad, score frozen
            pad_row = jnp.full((V,), NEG, jnp.float32).at[pad_token_id].set(0.0)

            def body(carry, _):
                tok, scores, done, lens, cks, cvs, pos = carry
                with paddle_tpu.no_grad():
                    step_logits, new_caches = self(
                        Tensor(tok.reshape(-1)[:, None]),
                        past_key_values=[
                            (Tensor(k), Tensor(v)) for k, v in zip(cks, cvs)
                        ],
                        use_cache=True,
                        cache_position=Tensor(pos),
                    )
                logp = jax.nn.log_softmax(
                    step_logits._data[:, -1, :].astype(jnp.float32)
                ).reshape(b, K, V)
                logp = jnp.where(done[:, :, None], pad_row[None, None, :], logp)
                cand = (scores[:, :, None] + logp).reshape(b, K * V)
                new_scores, idx = jax.lax.top_k(cand, K)
                parent = (idx // V).astype(jnp.int32)  # new beam -> old beam
                new_tok = (idx % V).astype(jnp.int32)
                flat_parent = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
                cks2 = [c[0]._data[flat_parent] for c in new_caches]
                cvs2 = [c[1]._data[flat_parent] for c in new_caches]
                done_g = jnp.take_along_axis(done, parent, axis=1)
                lens_g = jnp.take_along_axis(lens, parent, axis=1)
                lens2 = lens_g + jnp.where(done_g, 0, 1).astype(jnp.int32)
                done2 = done_g | (
                    new_tok == eos_token_id if eos_token_id is not None
                    else jnp.zeros_like(done_g)
                )
                return (new_tok, new_scores, done2, lens2, cks2, cvs2, pos + 1), (
                    new_tok, parent,
                )

            init = (tok0, scores, done, lens, cks, cvs, jnp.int32(prompt))
            (tok, scores, done, lens, _, _, _), (toks, parents) = jax.lax.scan(
                body, init, None, length=max_new_tokens - 1
            )
            # [T, B, K] with the step-0 layer (parents 0: all beams came from
            # the single prefill context)
            all_toks = jnp.concatenate([tok0[None], toks], axis=0)
            all_parents = jnp.concatenate(
                [jnp.zeros((1, b, K), jnp.int32), parents], axis=0
            )
            seqs = gather_tree(all_toks, all_parents)  # [T, B, K]
            seqs = seqs._data if hasattr(seqs, "_data") else seqs
            if length_penalty != 0.0:
                # reference BeamSearchScorer normalization: score divided by
                # ((5 + len) / 6) ** alpha over the FULL hypothesis length
                # (prompt + generated) — `lens ** alpha` over generated
                # tokens only ranks beams differently
                full_len = (prompt + lens).astype(jnp.float32)
                final = scores / jnp.power((5.0 + full_len) / 6.0, length_penalty)
            else:
                final = scores
            best = jnp.argmax(final, axis=-1)  # [B]
            best_seq = jnp.take_along_axis(
                seqs, best[None, :, None], axis=2
            )[:, :, 0]  # [T, B]
        return jnp.concatenate([ids, best_seq.T], axis=1)
