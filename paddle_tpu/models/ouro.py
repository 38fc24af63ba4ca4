"""Ouro (ByteDance LoopLM): a looped decoder. ONE stack of sandwich-norm
layers is run ``total_ut_steps`` times over every token; the same weights serve
every pass and the model's final norm closes each pass and feeds the next.

    x = E[tokens]
    for t in 0..T-1:
      for l in 0..L-1:
        x = x + RMS(O_l(attn(rope(Q_l h), rope(K_l h), V_l h)); g2_l),  h = RMS(x; g1_l)
        x = x + RMS(W_down_l(silu(W_gate_l u) * (W_up_l u)); g4_l),     u = RMS(x; g3_l)
      x = RMS(x; g_final)
    logits = W_head x

A token's keys and values differ from pass to pass, so the model holds
``T x L`` KV sets, ordered pass-major: set ``t * L + l`` is what layer ``l``
wrote in pass ``t``, and pass ``t`` attends to earlier tokens' keys of pass
``t`` only. ``OuroConfig.num_kv_sets`` tells a cache owner (the serving
engine) that count. Attention, MLP and rotary table are ``models/llama.py``'s
own modules.

The published ``early_exit_threshold`` of 1 switches early exit off (every
token takes every pass and the logits are the last pass's), so the exit gate
is not built.

In the serving engine's step (a paged past) a layer is ONE ``jax.jit``-wrapped
function, called ``T x L`` times with the layer's weights and the KV set of
that layer and pass: every layer has the same shapes, so a step program traces
and lowers one layer's body whatever ``T`` and ``L`` are, and the compiler
sees straight-line calls of it, each over its own cache planes (a
``fori_loop`` over planes stacked by pass would copy a plane out of the stack
and back around every append).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.generation import GenerationMixin
from paddle_tpu.inference.paged_kv import PagedKV
from paddle_tpu.models.llama import (
    SCOPE_ATTENTION,
    SCOPE_EMBEDDING,
    SCOPE_LM_HEAD,
    SCOPE_LOSS_HEAD,
    SCOPE_MLP,
    SCOPE_NORM,
    LlamaAttention,
    LlamaMLP,
    LlamaRotaryEmbedding,
)
from paddle_tpu.nn.layer.layers import bind_param_arrays, bind_quant_scales

# jax.named_scope names of what the loop adds (beside models/llama.py's):
# one pass of the stack, the norm that closes it, the two post-branch norms
SCOPE_LOOP_PASS = "loop_pass"
SCOPE_LOOP_NORM = "loop_norm"
SCOPE_SANDWICH_NORM = "sandwich_norm"


@dataclass
class OuroConfig:
    """The published Ouro-2.6B ``config.json`` keys the program reads, plus
    ``total_ut_steps``. ``max_position_embeddings`` is the length the rotary
    table is BUILT for (the published model declares 65 536 positions)."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    context_parallel: bool = False  # read by LlamaAttention; never on here
    dtype: str = "bfloat16"

    @property
    def stack_passes(self) -> int:
        """Times a step runs the stack over a token (early exit is off)."""
        return self.total_ut_steps

    @property
    def num_kv_sets(self) -> int:
        """KV sets a token holds: one per layer per pass."""
        return self.stack_passes * self.num_hidden_layers

    @staticmethod
    def tiny(vocab: int = 256, total_ut_steps: int = 4) -> "OuroConfig":
        return OuroConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=128, total_ut_steps=total_ut_steps,
        )


class OuroDecoderLayer(nn.Layer):
    """Sandwich-norm block: a norm before each branch (as Llama's) and a
    second one on the branch's OUTPUT, before the residual add."""

    def __init__(self, config: OuroConfig, rotary_emb: Optional[LlamaRotaryEmbedding] = None) -> None:
        super().__init__()
        self.self_attn = LlamaAttention(config, rotary_emb)
        self.mlp = LlamaMLP(config)
        eps = config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.input_layernorm_2 = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm_2 = nn.RMSNorm(config.hidden_size, epsilon=eps)

    def forward(
        self,
        hidden_states: Tensor,
        startend_row_indices: Optional[Tensor] = None,
        past_key_value: Any = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
        rope: Optional[Tuple[Tensor, Tensor]] = None,
    ) -> Any:
        """``rope`` = the step's offset-gathered (cos, sin) rows: with it the
        past is this set's ``PagedKV`` and attention is the rope-fused paged
        kernel (``LlamaAttention.forward_paged``)."""
        with jax.named_scope(SCOPE_NORM):
            h = self.input_layernorm(hidden_states)
        cache = None
        with jax.named_scope(SCOPE_ATTENTION):
            if rope is not None:
                attn_out, cache = self.self_attn.forward_paged(h, past_key_value, *rope)
            else:
                attn_out = self.self_attn(
                    h, startend_row_indices, past_key_value, use_cache, cache_position
                )
                if use_cache:
                    attn_out, cache = attn_out
        with jax.named_scope(SCOPE_NORM), jax.named_scope(SCOPE_SANDWICH_NORM):
            attn_out = self.input_layernorm_2(attn_out)
        h = hidden_states + attn_out
        with jax.named_scope(SCOPE_NORM):
            m = self.post_attention_layernorm(h)
        with jax.named_scope(SCOPE_MLP):
            m = self.mlp(m)
        with jax.named_scope(SCOPE_NORM), jax.named_scope(SCOPE_SANDWICH_NORM):
            m = self.post_attention_layernorm_2(m)
        h = h + m
        if use_cache:
            return h, cache
        return h


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig) -> None:
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        # every layer's rotary table holds the same values: build it once
        rotary = LlamaRotaryEmbedding(
            config.hidden_size // config.num_attention_heads,
            config.max_position_embeddings, config.rope_theta,
        )
        self.layers = nn.LayerList(
            [OuroDecoderLayer(config, rotary) for _ in range(config.num_hidden_layers)]
        )
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    # -- one pass -------------------------------------------------------------
    def _stack(
        self,
        h: Tensor,
        startend_row_indices: Optional[Tensor],
        pasts: Optional[Sequence[Any]],
        use_cache: bool,
        cache_position: Optional[Tensor],
    ) -> Tuple[Tensor, List[Any]]:
        """The stack once and the norm that closes the pass (the plain path)."""
        caches: List[Any] = []
        with jax.named_scope(SCOPE_LOOP_PASS):
            for i, layer in enumerate(self.layers):
                past = pasts[i] if pasts is not None else None
                h = layer(h, startend_row_indices, past, use_cache, cache_position)
                if use_cache:
                    h, cache = h
                    caches.append(cache)
        with jax.named_scope(SCOPE_NORM), jax.named_scope(SCOPE_LOOP_NORM):
            h = self.norm(h)
        return h, caches

    def _paged_layer_fn(self) -> Any:
        """ONE layer over a ``PagedKV`` set as a jitted function of raw
        arrays, built once per model. Every layer has the same leaves and
        shapes, so layer 0's module stands in for all of them with the
        called layer's weights (and weight-only-int8 scales) bound to it:
        the ``T x L`` calls inside a step program share one traced and
        lowered body. Weights are arguments, so nothing of an enclosing
        trace is closed over. It hands back the set's PLANES only: the batch
        is the caller's, shared by every set."""
        fn = getattr(self, "_layer_jit", None)
        if fn is None:
            template = self.layers[0]
            named = list(template.named_parameters())

            def paged_layer(arrays, scales, h, kv, cos, sin):
                quant = [(p, s) for (_n, p), s in zip(named, scales) if s is not None]
                with bind_param_arrays(named, arrays), bind_quant_scales(
                    [p for p, _s in quant], [s for _p, s in quant]
                ):
                    out, kv = template(Tensor(h), None, kv, True, None, (Tensor(cos), Tensor(sin)))
                return out._data, kv.planes

            fn = jax.jit(paged_layer)
            object.__setattr__(self, "_layer_jit", fn)
        return fn

    def _forward_paged(self, input_ids: Tensor, past_key_values: Sequence[PagedKV], use_cache: bool) -> Any:
        """The paged serving step (the engine's one-signature mixed ragged
        step, ``generate_paged``'s decode step): ``T x L`` ``PagedKV`` sets,
        pass-major, under ONE shared batch."""
        n_layers = len(self.layers)
        with jax.named_scope(SCOPE_EMBEDDING):
            h = self.embed_tokens(input_ids)._data
        batch = past_key_values[0].batch
        with jax.named_scope(SCOPE_ATTENTION):
            # once per STEP: every pass and layer rotates at the same positions
            cos, sin = self.layers[0].self_attn.rotary_emb(input_ids.shape[1], Tensor(batch.seq_lens))
        cos, sin = cos._data, sin._data
        weights = []
        for layer in self.layers:  # read once a step: every pass takes the same
            params = list(layer.parameters())
            weights.append(([p._data for p in params], [getattr(p, "_quant_scale", None) for p in params]))
        run = self._paged_layer_fn()
        new_caches: List[Any] = []
        for t in range(self.config.total_ut_steps):
            with jax.named_scope(SCOPE_LOOP_PASS):
                for i, (arrays, scales) in enumerate(weights):
                    h, planes = run(arrays, scales, h, past_key_values[t * n_layers + i], cos, sin)
                    new_caches.append(PagedKV(*planes, batch=batch))
            with jax.named_scope(SCOPE_NORM), jax.named_scope(SCOPE_LOOP_NORM):
                h = self.norm(Tensor(h))._data
        h = Tensor(h)
        return (h, new_caches) if use_cache else h

    def forward(
        self,
        input_ids: Tensor,
        startend_row_indices: Optional[Tensor] = None,
        past_key_values: Any = None,
        use_cache: bool = False,
        cache_position: Optional[Tensor] = None,
    ) -> Any:
        passes, n_layers = self.config.total_ut_steps, len(self.layers)
        if past_key_values is not None and len(past_key_values) != passes * n_layers:
            raise ValueError(
                f"a past of {len(past_key_values)} KV sets was given; {passes} passes over "
                f"{n_layers} layers hold {passes * n_layers}"
            )
        if past_key_values is not None and isinstance(past_key_values[0], PagedKV):
            return self._forward_paged(input_ids, past_key_values, use_cache)
        with jax.named_scope(SCOPE_EMBEDDING):
            h = self.embed_tokens(input_ids)
        new_caches: List[Any] = []
        for t in range(passes):
            pasts = (
                past_key_values[t * n_layers:(t + 1) * n_layers]
                if past_key_values is not None else None
            )
            h, caches = self._stack(h, startend_row_indices, pasts, use_cache, cache_position)
            new_caches.extend(caches)
        if use_cache:
            return h, new_caches
        return h


class OuroForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: OuroConfig) -> None:
        super().__init__()
        self.config = config
        self.ouro = OuroModel(config)
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
        """Logits of the LAST pass (plus the ``T x L`` caches when
        ``use_cache``); with ``labels``, ``(loss, logits)`` where the loss is
        the last pass's token-mean cross entropy (the published training
        objective also weighs the earlier passes' heads by the exit gate,
        which is not built)."""
        out = self.ouro(
            input_ids, startend_row_indices, past_key_values, use_cache, cache_position
        )
        caches = None
        if use_cache:
            out, caches = out
        with jax.named_scope(SCOPE_LM_HEAD):
            if self.lm_head is not None:
                logits = self.lm_head(out)
            else:
                logits = paddle_tpu.matmul(out, self.ouro.embed_tokens.weight, transpose_y=True)
        if labels is not None:
            with jax.named_scope(SCOPE_LOSS_HEAD):
                loss = F.cross_entropy(logits, labels, ignore_index=-100, reduction="mean")
            return loss, logits
        if use_cache:
            return logits, caches
        return logits
