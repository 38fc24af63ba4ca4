"""Llama-2 family — the flagship model (BASELINE config #3: Llama-2 7B
pretrain, target > 2500 tokens/sec/chip on v5p).

TPU-first design decisions:
- bf16 params/activations by default; fp32 RMSNorm accumulation.
- Attention through ``nn.functional.flashmask_attention`` → Pallas kernel on
  TPU, XLA fallback elsewhere.
- GQA (num_key_value_heads < num_attention_heads) supported.
- Sharding is declarative: ``llama_shard_fn`` assigns (mesh, placements) per
  parameter for the [dp/fsdp, mp] mesh — Megatron TP layout (column-parallel
  qkv/gate/up, row-parallel o/down, vocab-parallel embedding), matching the
  reference's ``fleet/layers/mpu/mp_layers.py`` semantics but lowered through
  GSPMD instead of explicit NCCL collectives. Sequence parallelism falls out
  of sequence-dim activation constraints (``mark_activation_sharding``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.spmd import shard_group_mesh
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.generation import GenerationMixin
from paddle_tpu.incubate.nn.functional import fused_rotary_position_embedding
from paddle_tpu.inference.paged_kv import PagedKV
from paddle_tpu.ops.creation import arange
from paddle_tpu.ops.manipulation import concat, reshape

# jax.named_scope names of the model's parts: what a device trace files their
# operations (and the backward's, as transpose(jvp(<scope>))) under
SCOPE_EMBEDDING = "embedding"
SCOPE_NORM = "norm"
SCOPE_ATTENTION = "attention"
SCOPE_MLP = "mlp"
SCOPE_LM_HEAD = "lm_head"
SCOPE_LOSS_HEAD = "loss_head"


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False  # per-decoder-layer activation checkpointing
    # context parallelism: shard the SEQUENCE over the mesh's 'sep' axis and
    # run ring attention (long-context training; SURVEY §5.7)
    context_parallel: bool = False
    dtype: str = "bfloat16"

    @property
    def num_kv_sets(self) -> int:
        """KV sets a token holds (what a cache owner allocates): one a layer."""
        return self.num_hidden_layers

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab: int = 256) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128,
        )


class LlamaRotaryEmbedding(nn.Layer):
    def __init__(self, head_dim: int, max_position: int, theta: float) -> None:
        super().__init__()
        self.head_dim = head_dim
        import numpy as np

        inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
        t = np.arange(max_position, dtype=np.float32)
        freqs = np.outer(t, inv)
        emb = np.concatenate([freqs, freqs], axis=-1)
        self.register_buffer("cos_cached", Tensor(np.cos(emb)), persistable=False)
        self.register_buffer("sin_cached", Tensor(np.sin(emb)), persistable=False)

    def forward(self, seq_len: int, offset: Any = 0) -> Tuple[Tensor, Tensor]:
        if isinstance(offset, Tensor):
            # decode path: position is a traced scalar — or a [B] vector for
            # batches whose sequences sit at different lengths — so the table
            # lookup must be a dynamic lookup
            from paddle_tpu.core.dispatch import call_op
            import jax

            def sl(tab, off):
                if off.ndim == 0:
                    # true scalar (static-cache decode): one slice suffices
                    return jax.lax.dynamic_slice_in_dim(
                        tab, off.reshape(()), seq_len, axis=0
                    )
                # chunked rows: a dynamic_slice of width seq_len CLAMPS its
                # start to table_len - seq_len, which would silently rotate
                # the last chunk of a near-max-length context with wrong
                # positions — gather exact per-position rows instead (rows
                # past the table end clip to the last entry; those positions
                # are masked rows / beyond max_position anyway)
                pos = off.reshape(-1)[:, None] + jnp.arange(seq_len)[None, :]
                per = tab[jnp.clip(pos, 0, tab.shape[0] - 1)]
                return per[:, :, None, :]  # [B, s, 1, D] broadcasts over heads

            return (
                call_op("rope_table_slice", sl, self.cos_cached, offset),
                call_op("rope_table_slice", sl, self.sin_cached, offset),
            )
        return (
            self.cos_cached[offset : offset + seq_len],
            self.sin_cached[offset : offset + seq_len],
        )


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig, rotary_emb: Optional[LlamaRotaryEmbedding] = None) -> None:
        """``rotary_emb``: a table to share (every layer's holds the same
        values); by default the layer builds its own."""
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        bias = False
        self.q_proj = nn.Linear(self.hidden_size, self.num_heads * self.head_dim, bias_attr=bias)
        self.k_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=bias)
        self.v_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=bias)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, self.hidden_size, bias_attr=bias)
        self.rotary_emb = rotary_emb if rotary_emb is not None else LlamaRotaryEmbedding(
            self.head_dim, config.max_position_embeddings, config.rope_theta
        )

    def forward(
        self,
        hidden_states: Tensor,
        startend_row_indices: Optional[Tensor] = None,
        past_key_value: Optional[Tuple[Tensor, Tensor]] = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
    ) -> Any:
        b, s, _ = hidden_states.shape
        q = reshape(self.q_proj(hidden_states), [b, s, self.num_heads, self.head_dim])
        k = reshape(self.k_proj(hidden_states), [b, s, self.num_kv_heads, self.head_dim])
        v = reshape(self.v_proj(hidden_states), [b, s, self.num_kv_heads, self.head_dim])
        if cache_position is not None and past_key_value is not None:
            # static-cache decode: past is a FIXED [B, S_max, HK, D] buffer
            # pair; append this step's K/V at cache_position and attend with a
            # length mask — one compiled program for every step (reference
            # `masked_multihead_attention_` ops.yaml:3074)
            from paddle_tpu.incubate.nn.functional import masked_multihead_attention

            cos, sin = self.rotary_emb(s, cache_position)
            q, k, _ = fused_rotary_position_embedding(q, k, None, sin=sin, cos=cos)
            out, ck, cv = masked_multihead_attention(
                q, k, v, past_key_value[0], past_key_value[1], cache_position
            )
            out = self.o_proj(reshape(out, [b, s, self.num_heads * self.head_dim]))
            return (out, (ck, cv)) if use_cache else out
        offset = past_key_value[0].shape[1] if past_key_value is not None else 0
        cos, sin = self.rotary_emb(s, offset)
        q, k, _ = fused_rotary_position_embedding(q, k, None, sin=sin, cos=cos)
        if past_key_value is not None:
            k = concat([past_key_value[0], k], axis=1)
            v = concat([past_key_value[1], v], axis=1)
        new_cache = (k, v) if use_cache else None
        if (
            self.config.context_parallel
            and not use_cache
            and past_key_value is None  # ring assumes sq == sk (no prefix KV)
        ):
            from paddle_tpu.distributed.mesh import get_mesh

            mesh = get_mesh()
            if (
                mesh is not None
                and "sep" in mesh.dim_names
                and mesh.get_dim_size("sep") > 1
            ):
                if startend_row_indices is not None:
                    raise NotImplementedError(
                        "FlashMask + context parallelism is not supported; "
                        "ring attention exchanges KV blocks in ring order"
                    )
                out = F.ring_flash_attention(q, k, v, causal=True)
                out = reshape(out, [b, s, self.num_heads * self.head_dim])
                return self.o_proj(out)
        out = F.flashmask_attention(
            q, k, v, startend_row_indices=startend_row_indices, causal=True
        )
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if use_cache:
            return out, new_cache
        return out

    def forward_paged(
        self,
        hidden_states: Tensor,  # pre-normed [B, s, H] (norm fused upstream)
        past_key_value: PagedKV,  # this layer's KV set under the step's batch
        cos: Tensor,  # [B, s, 1, D] offset-gathered rope rows (shared by
        sin: Tensor,  # every layer — gathered ONCE per step by the caller)
    ) -> Tuple[Tensor, PagedKV]:
        """The paged serving step's attention: qkv projections feed the
        rope-fused paged kernel (q's rotation runs inside the page walk, k's
        fuses into the cache-append scatter), so the per-layer rope pass +
        attention are one dispatch (reference ``block_multihead_attention_``,
        fused_ops.yaml:45). Positions are ragged per slot; padded slots write
        no KV and return zeros, so the step's shape stays fixed while the
        live batch changes. Under an armed tp mesh o_proj runs the tile-split
        row-parallel matmul so its all-reduce overlaps the next tile's
        compute."""
        b, s, _ = hidden_states.shape
        q = reshape(self.q_proj(hidden_states), [b, s, self.num_heads, self.head_dim])
        k = reshape(self.k_proj(hidden_states), [b, s, self.num_kv_heads, self.head_dim])
        v = reshape(self.v_proj(hidden_states), [b, s, self.num_kv_heads, self.head_dim])
        out_a, new_past = past_key_value.attend(q._data, k._data, v._data, cos._data, sin._data)
        out_t = reshape(Tensor(out_a), [b, s, self.num_heads * self.head_dim])
        mesh = shard_group_mesh()
        if mesh is None:
            out = self.o_proj(out_t)
        else:
            from paddle_tpu.distributed.tp import row_parallel_overlap_matmul

            out = Tensor(row_parallel_overlap_matmul(out_t._data, self.o_proj.weight._data))
        return out, new_past


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig) -> None:
        super().__init__()
        self.gate_proj = nn.Linear(config.hidden_size, config.intermediate_size, bias_attr=False)
        self.up_proj = nn.Linear(config.hidden_size, config.intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size, bias_attr=False)

    def forward(self, x: Tensor) -> Tensor:
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig) -> None:
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(
        self,
        hidden_states: Tensor,
        startend_row_indices: Optional[Tensor] = None,
        past_key_value: Any = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
    ) -> Any:
        residual = hidden_states
        with jax.named_scope(SCOPE_NORM):
            h = self.input_layernorm(hidden_states)
        with jax.named_scope(SCOPE_ATTENTION):
            attn_out = self.self_attn(
                h, startend_row_indices, past_key_value, use_cache, cache_position
            )
        if use_cache:
            attn_out, cache = attn_out
        h = residual + attn_out
        residual = h
        with jax.named_scope(SCOPE_NORM):
            h = self.post_attention_layernorm(h)
        with jax.named_scope(SCOPE_MLP):
            h = self.mlp(h)
        h = residual + h
        if use_cache:
            return h, cache
        return h


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig) -> None:
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(
        self,
        input_ids: Tensor,
        startend_row_indices: Optional[Tensor] = None,
        past_key_values: Any = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
    ) -> Any:
        if past_key_values is not None and isinstance(past_key_values[0], PagedKV):
            # paged serving (the continuous-batching engine's one-signature
            # mixed ragged step, generate_paged's decode step): one typed KV
            # set per layer; every train / prefill / static-cache path stays
            # on the layer modules below
            return self._forward_paged(input_ids, past_key_values, use_cache)
        with jax.named_scope(SCOPE_EMBEDDING):
            h = self.embed_tokens(input_ids)
        new_caches = [] if use_cache else None
        use_recompute = (
            self.config.recompute
            and self.training
            and not use_cache
            and past_key_values is None
        )
        for i, layer in enumerate(self.layers):
            past = past_key_values[i] if past_key_values is not None else None
            if use_recompute:
                from paddle_tpu.distributed.fleet import recompute

                h = recompute(layer, h, startend_row_indices)
            else:
                h = layer(h, startend_row_indices, past, use_cache, cache_position)
            if use_cache:
                h, cache = h
                new_caches.append(cache)
        with jax.named_scope(SCOPE_NORM):
            h = self.norm(h)
        if use_cache:
            return h, new_caches
        return h

    def _forward_paged(
        self,
        input_ids: Tensor,
        past_key_values: Sequence[PagedKV],
        use_cache: bool,
    ) -> Any:
        """The paged serving step's layer loop, its epilogues paired into
        single kernels:

        - entry: token gather + embedding lookup + layer 0's input RMSNorm
          fuse into one scalar-prefetch kernel seeding BOTH the residual
          stream and the normed hidden;
        - rope rows gather ONCE per step (every layer's rotary buffers hold
          identical values);
        - per layer: the rope-fused paged-attention kernel (q rotates inside
          the page walk), then residual-add + post-attention norm as ONE
          kernel, the MLP, and residual-add + the NEXT layer's input norm as
          ONE kernel — the last layer pairs with the model's final norm, so
          the loop returns ``h`` already normed;
        - under an armed tp mesh the row-parallel matmuls (o_proj/down_proj)
          split into token tiles so each tile's all-reduce overlaps the next
          tile's compute (byte-identical: the split only partitions rows).

        Every fused op's XLA fallback is the plain composition (norm of the
        sum, rope then attend), which is what the dense forward computes.
        """
        from paddle_tpu.incubate.nn.functional import (
            fused_embed_rms_norm,
            fused_rms_norm_residual,
        )

        layers = list(self.layers)
        first = layers[0]
        with jax.named_scope(SCOPE_EMBEDDING):
            residual, h = fused_embed_rms_norm(
                input_ids,
                self.embed_tokens.weight,
                first.input_layernorm.weight,
                first.input_layernorm.epsilon,
            )
        s = input_ids.shape[1]
        lens = Tensor(past_key_values[0].batch.seq_lens)  # one batch, shared by every set
        with jax.named_scope(SCOPE_ATTENTION):
            cos, sin = first.self_attn.rotary_emb(s, lens)  # once per STEP
        mesh = shard_group_mesh()
        new_caches = [] if use_cache else None
        n = len(layers)
        for i, layer in enumerate(layers):
            with jax.named_scope(SCOPE_ATTENTION):
                attn_out, cache = layer.self_attn.forward_paged(h, past_key_values[i], cos, sin)
            with jax.named_scope(SCOPE_NORM):
                h, residual = fused_rms_norm_residual(
                    attn_out,
                    layer.post_attention_layernorm.weight,
                    residual,
                    layer.post_attention_layernorm.epsilon,
                )
            if mesh is None:
                with jax.named_scope(SCOPE_MLP):
                    mlp_out = layer.mlp(h)
            else:
                from paddle_tpu.distributed.tp import row_parallel_overlap_matmul

                inner = F.swiglu(layer.mlp.gate_proj(h), layer.mlp.up_proj(h))
                dw = layer.mlp.down_proj.weight
                dscale = getattr(dw, "_quant_scale", None)
                if dscale is None:
                    dw_data = dw._data
                else:
                    # weight-only int8 under tp: dequantize the LOCAL K-shard
                    # before the overlapped reduce — per-output-channel scales
                    # span the full K, so per-shard dequant-then-reduce is
                    # exact (the scale factors out of the K-sum); XLA fuses
                    # the convert into the tile matmul, no resident bf16 copy
                    dw_data = (
                        dw._data.astype(jnp.float32) * dscale[None, :]
                    ).astype(inner._data.dtype)
                mlp_out = Tensor(row_parallel_overlap_matmul(inner._data, dw_data))
            next_norm = layers[i + 1].input_layernorm if i + 1 < n else self.norm
            with jax.named_scope(SCOPE_NORM):
                h, residual = fused_rms_norm_residual(
                    mlp_out, next_norm.weight, residual, next_norm.epsilon
                )
            if use_cache:
                new_caches.append(cache)
        # h left the loop already final-normed (the last pairing used
        # self.norm's weight)
        if use_cache:
            return h, new_caches
        return h


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig) -> None:
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)
        else:
            self.lm_head = None

    def forward(
        self,
        input_ids: Tensor,
        labels: Optional[Tensor] = None,
        startend_row_indices: Optional[Tensor] = None,
        past_key_values: Any = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
    ) -> Any:
        """Causal-LM forward.

        Training contract: with ``labels`` given, the return is
        ``(loss, logits_or_None)``. When ``FLAGS_use_fused_loss`` is on (the
        default) the lm-head matmul is fused into a vocab-chunked
        cross-entropy (``F.fused_linear_cross_entropy``) and the second
        element is **None** — full ``[B, S, V]`` logits are never
        materialized, so returning them would pin the very buffer the fused
        path exists to eliminate across ``backward()``. Callers that need
        training-time logits must set ``FLAGS_use_fused_loss=False``.
        Without ``labels`` the return is ``logits`` (plus caches when
        ``use_cache``), unchanged.
        """
        out = self.llama(
            input_ids, startend_row_indices, past_key_values, use_cache, cache_position
        )
        caches = None
        if use_cache:
            out, caches = out
        if labels is not None and GLOBAL_FLAGS.get("use_fused_loss"):
            with jax.named_scope(SCOPE_LOSS_HEAD):
                if self.lm_head is not None:
                    loss = F.fused_linear_cross_entropy(
                        out, self.lm_head.weight, labels, ignore_index=-100,
                        reduction="mean",
                        weight_scale=getattr(self.lm_head.weight, "_quant_scale", None),
                    )
                else:
                    loss = F.fused_linear_cross_entropy(
                        out, self.llama.embed_tokens.weight, labels,
                        ignore_index=-100, reduction="mean", weight_vocab_major=True,
                    )
            return loss, None
        with jax.named_scope(SCOPE_LM_HEAD):
            if self.lm_head is not None:
                logits = self.lm_head(out)
            else:
                logits = paddle_tpu.matmul(out, self.llama.embed_tokens.weight, transpose_y=True)
        if labels is not None:
            # F.cross_entropy upcasts to fp32 internally (stable logsumexp)
            with jax.named_scope(SCOPE_LOSS_HEAD):
                loss = F.cross_entropy(logits, labels, ignore_index=-100, reduction="mean")
            return loss, logits
        if use_cache:
            return logits, caches
        return logits


# ---------------------------------------------------------------------------
# Sharding policy: Megatron TP + DP/FSDP over a ['dp', 'mp'] mesh
# (reference layout: mpu/mp_layers.py Column/RowParallelLinear +
# VocabParallelEmbedding; here expressed as parameter placements for GSPMD).
# ---------------------------------------------------------------------------
def llama_shard_fn(name: str, sublayer: Any, mesh: Any) -> None:
    from paddle_tpu.distributed.api import apply_placement, build_placements
    from paddle_tpu.distributed.placements import Replicate

    # the one Megatron leaf-name table, shared with the serving-TP policy
    # (distributed/tp.py tp_param_spec) so the two can never drift
    from paddle_tpu.distributed.tp import (
        COLUMN_PARALLEL_LEAVES,
        ROW_PARALLEL_LEAVES,
    )

    def put(param: Any, placements: List[Any]) -> None:
        apply_placement(param, mesh, placements)

    names = mesh.dim_names

    def plc(**kw: Any) -> List[Any]:
        return build_placements(mesh, **kw)

    cls = type(sublayer).__name__
    leaf = name.rsplit(".", 1)[-1]
    if isinstance(sublayer, nn.Embedding):
        # vocab-parallel embedding: shard vocab dim on mp; fsdp shards hidden
        put(sublayer.weight, plc(mp=0, sharding=1))
    elif isinstance(sublayer, nn.Linear):
        if leaf in COLUMN_PARALLEL_LEAVES:  # incl. lm_head: [H, V] shards V
            put(sublayer.weight, plc(mp=1, sharding=0))  # column parallel
        elif leaf in ROW_PARALLEL_LEAVES:
            put(sublayer.weight, plc(mp=0, sharding=1))  # row parallel
        else:
            put(sublayer.weight, plc(sharding=0))
        if getattr(sublayer, "bias", None) is not None:
            put(sublayer.bias, [Replicate() for _ in names])
    elif isinstance(sublayer, nn.RMSNorm):
        if sublayer.weight is not None:
            put(sublayer.weight, [Replicate() for _ in names])


def mark_activation_sharding(h: Tensor, mesh: Any, seq_parallel: bool = False) -> Tensor:
    """Constraint activations [b, s, h]: batch on dp(+sharding); sequence on mp
    when sequence-parallel (the Megatron-SP scatter, reference
    ``sequence_parallel_utils.py``) — under GSPMD this single constraint
    produces the scatter/gather pairs around TP blocks."""
    from paddle_tpu.distributed.api import shard_tensor
    from paddle_tpu.distributed.placements import Replicate, Shard

    names = mesh.dim_names
    placements: List[Any] = [Replicate() for _ in names]
    if "dp" in names:
        placements[names.index("dp")] = Shard(0)
    if "sharding" in names:
        placements[names.index("sharding")] = Shard(0)
    if seq_parallel and "mp" in names:
        placements[names.index("mp")] = Shard(1)
    return shard_tensor(h, mesh, placements)
