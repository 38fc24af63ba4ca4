"""DeepSeek-V2 (``model_type`` deepseek_v2): a pre-norm decoder whose attention
is MULTI-HEAD LATENT (MLA) and whose MLPs, after ``first_k_dense_replace``
dense layers, are sparse-expert layers with shared experts beside the routed
ones. RMSNorm, no biases, untied head, final norm.

    x = E[tokens];  for i: x = x + attn_i(RMS(x));  x = x + mlp_i(RMS(x));  logits = W_head RMS(x)

    attn: c_q = RMS(x W_qa);  q = c_q W_qb -> H heads of (nope | rope)
          [c_kv | k_pe] = x W_kva;  c_kv = RMS(c_kv);  k_pe ONE key shared by every head
          [k_nope | v] = c_kv W_kvb -> H heads of (nope | v)
          scores = (q_nope . k_nope + rope(q_pe) . rope(k_pe)) * s, causal softmax, times v, then W_o
          rope: the YaRN table (``yarn_inv_freq``) over the ``rope`` dims, which are de-interleaved
          (pairs (2j, 2j+1) -> (j, j + rope/2)) before rotate-half, as the published code does
          s = (nope + rope)^-0.5 x m(factor, mscale_all_dim)^2,  m(f, a) = 0.1 a ln f + 1
    mlp (layer < first_k_dense_replace): W_down (silu(W_gate x) * W_up x), width ``intermediate_size``
    mlp (else): p = softmax(W_g x) in float32; a group's score is its best expert's; the ``topk_group``
          best of ``n_group`` groups are kept, the rest zeroed; top ``num_experts_per_tok`` of what is
          left; weight = p of the chosen (NOT normalised) x ``routed_scaling_factor``; a routed expert is a
          SwiGLU MLP of ``moe_intermediate_size``; plus the shared experts, ONE SwiGLU MLP of
          ``n_shared_experts x moe_intermediate_size`` on every row

WHAT A TOKEN KEEPS. Not keys and values per head but ONE row, ``[c_kv | rope(k_pe)]``
(``kv_lora_rank + qk_rope_head_dim`` values): ``config.cache_sets`` names one
:class:`~paddle_tpu.inference.paged_kv.LatentKV` set a layer. The serving step
attends in the ABSORBED form, the one form for decode rows and prefill chunks
alike: ``q' = [q_nope W_UK_h | rope(q_pe)] * s`` per head, attention of the H
heads over the latent rows as keys and their first ``kv_lora_rank`` lanes as
values, then ``W_UV_h`` per head; ``W_UK`` and ``W_UV`` are the two halves of
the ``kv_b_proj`` leaf (no second copy is kept). Without a past the model runs
the MATERIALISED form (per-head keys and values from ``c_kv W_kvb``), which is
what the published code computes; a dense ``(key, value)`` cache is not built.

AN EXPERT LAYER THAT HOLDS A SHARE, as ``models/nemotron_h.py``:
``n_routed_experts`` experts are HELD, ``first_expert ..`` of the
``n_routed_experts_total`` the router scores (``n_group`` groups of them); the
leaves are 3-D (``incubate/nn/functional/fused_moe.py::share_of_routed``).

Every leaf is MADE in ``config.dtype`` (``nn.Linear`` would make it in float32
first): at published widths one float32 copy does not fit beside the model.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.dispatch import call_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional.fused_moe import (
    SCOPE_MOE_ROUTER,
    route_softmax_group_limited,
    share_of_routed,
)
from paddle_tpu.inference.paged_kv import CacheSet, LatentKV, PagedBatch
from paddle_tpu.models.llama import SCOPE_EMBEDDING, SCOPE_LM_HEAD, SCOPE_LOSS_HEAD, SCOPE_MLP, SCOPE_NORM
from paddle_tpu.models.nemotron_h import SCOPE_MOE, SCOPE_MOE_SHARED, _Leaves
from paddle_tpu.nn import initializer as I

# jax.named_scope names of what this family adds: an attention block is ``mla``
# and inside it the query path, the latent path, the two absorbed matmuls, the
# append + page walk, and the output projection
SCOPE_MLA = "mla"
SCOPE_MLA_Q = "mla_q"
SCOPE_MLA_KV = "mla_kv"
SCOPE_MLA_ABSORB = "mla_absorb"
SCOPE_LATENT_ATTENTION = "latent_attention"
SCOPE_MLA_OUT = "mla_out"

PUBLISHED_ROPE_SCALING = {
    "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
    "mscale": 0.707, "mscale_all_dim": 0.707,
}


@dataclass
class DeepseekV2Config:
    """The published ``config.json`` keys the program reads (defaults:
    DeepSeek-V2, 236B-A21B), plus the share of the experts held here."""

    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    num_key_value_heads: int = 128  # published; the latent row has no heads
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_shared_experts: int = 2
    n_routed_experts: int = 160  # HELD here
    n_routed_experts_total: Optional[int] = None  # the router's width; None: all are held
    first_expert: int = 0
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Dict[str, Any] = field(default_factory=lambda: dict(PUBLISHED_ROPE_SCALING))
    initializer_range: float = 0.02
    max_position_embeddings: int = 163840  # a cache owner's default length; no table is built for it
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.n_routed_experts_total is None:
            self.n_routed_experts_total = self.n_routed_experts
        if self.n_routed_experts_total % self.n_group:
            raise ValueError(f"{self.n_routed_experts_total} experts do not lie in {self.n_group} equal groups")
        if not 0 <= self.first_expert <= self.n_routed_experts_total - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.n_routed_experts - 1} "
                f"are not among the router's {self.n_routed_experts_total}"
            )
        if self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {self.rope_scaling.get('type')!r}: only the published 'yarn' is built")

    @property
    def cache_sets(self) -> List[CacheSet]:
        """What the model keeps a sequence: one latent row a token a layer."""
        return [LatentKV.spec(self.kv_lora_rank, self.qk_rope_head_dim, self.dtype)] * self.num_hidden_layers

    @property
    def num_kv_sets(self) -> int:
        """PAGED sets a token holds: one per layer."""
        return self.num_hidden_layers

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5`` times the YaRN correction ``m(factor, mscale_all_dim)^2``."""
        m = yarn_mscale(self.rope_scaling["factor"], self.rope_scaling["mscale_all_dim"])
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @staticmethod
    def tiny(vocab: int = 256, layers: int = 3, held: int = 16, total: int = 16) -> "DeepseekV2Config":
        return DeepseekV2Config(
            vocab_size=vocab, hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
            num_hidden_layers=layers, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12, n_routed_experts=held,
            n_routed_experts_total=total, num_experts_per_tok=3, n_group=4, topk_group=2,
            rope_scaling=dict(PUBLISHED_ROPE_SCALING, original_max_position_embeddings=32),
            max_position_embeddings=256, dtype="float32",
        )


# -- the YaRN rotary table ------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(s, a) = 0.1 a ln s + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Dict[str, Any]) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies: dimension ``i`` turns at ``theta^(-2i/dim)`` (extrapolated)
    where it makes more than ``beta_fast`` turns over the original context, at that over ``factor``
    (interpolated) where it makes fewer than ``beta_slow``, and on a linear ramp between the two."""
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / scaling["factor"]
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_cos_sin(positions: jax.Array, config: DeepseekV2Config) -> Tuple[jax.Array, jax.Array]:
    """``(cos, sin)``, each ``positions.shape + (rope / 2,)`` float32, scaled by ``m(factor, mscale) /
    m(factor, mscale_all_dim)`` (1 at the published values). Computed from the positions: no table of
    ``max_position_embeddings`` rows is kept."""
    sc = config.rope_scaling
    inv = jnp.asarray(yarn_inv_freq(config.qk_rope_head_dim, config.rope_theta, sc))
    angles = positions.astype(jnp.float32)[..., None] * inv
    m = yarn_mscale(sc["factor"], sc["mscale"]) / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x [..., rope]`` whose pairs ``(2j, 2j+1)`` are de-interleaved to ``(j, j + rope/2)`` and then
    rotated by rotate-half; ``cos`` / ``sin`` broadcast against ``[..., rope/2]``. In float32, rounded once."""
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# -- leaves made in the configuration's dtype -------------------------------------------------

class _Linear(nn.Linear):
    """``nn.Linear`` without a bias whose weight is MADE in ``config.dtype`` (``nn.Linear`` makes it in
    float32); the forward, weight-only int8 dispatch included, is ``nn.Linear``'s."""

    def __init__(self, n_in: int, n_out: int, config: DeepseekV2Config) -> None:
        nn.Layer.__init__(self, dtype=config.dtype)
        self.in_features, self.out_features, self.bias = n_in, n_out, None
        self.weight = self.create_parameter([n_in, n_out], default_initializer=I.Normal(0.0, config.initializer_range))


class _Norm(_Leaves):
    def __init__(self, width: int, config: DeepseekV2Config) -> None:
        super().__init__(weight=((width,), I.Constant(1.0), config.dtype))
        self.epsilon = config.rms_norm_eps

    def forward(self, x: Tensor) -> Tensor:
        return F.rms_norm(x, self.weight, self.epsilon)


class DeepseekV2MLP(nn.Layer):
    """SwiGLU: the dense layers' MLP and the shared experts."""

    def __init__(self, config: DeepseekV2Config, width: int) -> None:
        super().__init__()
        self.gate_proj = _Linear(config.hidden_size, width, config)
        self.up_proj = _Linear(config.hidden_size, width, config)
        self.down_proj = _Linear(width, config.hidden_size, config)

    def forward(self, x: Tensor) -> Tensor:
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV2MoE(nn.Layer):
    def __init__(self, config: DeepseekV2Config) -> None:
        super().__init__()
        self.config = config
        d, held, width = config.hidden_size, config.n_routed_experts, config.moe_intermediate_size
        std = I.Normal(0.0, config.initializer_range)
        self.gate = _Leaves(weight=((d, config.n_routed_experts_total), std, config.dtype))
        self.experts = _Leaves(gate_proj=((held, d, width), std, config.dtype), up_proj=((held, d, width), std, config.dtype),
                               down_proj=((held, width, d), std, config.dtype))
        self.shared_experts = DeepseekV2MLP(config, config.n_shared_experts * width)

    def forward(self, u: Tensor, batch: Optional[PagedBatch] = None) -> Tensor:
        """``batch``: the serving step's, whose masked slots and rows past
        ``q_lens`` are sent to no expert."""
        cfg = self.config
        row_mask = None if batch is None else batch.live_rows(u.shape[1])

        def share(x, gate_w, w_gate, w_up, w_down):
            flat = x.reshape(-1, x.shape[-1])
            with jax.named_scope(SCOPE_MOE_ROUTER):
                chosen, weights = route_softmax_group_limited(
                    flat, gate_w, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group
                )
            out = share_of_routed(flat, chosen, weights, w_up, w_down, cfg.first_expert, row_mask, w_gate=w_gate)
            return out.reshape(x.shape)

        routed = call_op("expert_share", share, u, self.gate.weight, self.experts.gate_proj,
                         self.experts.up_proj, self.experts.down_proj)
        with jax.named_scope(SCOPE_MOE_SHARED):
            return routed + self.shared_experts(u)


class DeepseekV2Attention(nn.Layer):
    def __init__(self, config: DeepseekV2Config) -> None:
        super().__init__()
        self.config = config
        d, h = config.hidden_size, config.num_attention_heads
        self.q_a_proj = _Linear(d, config.q_lora_rank, config)
        self.q_a_layernorm = _Norm(config.q_lora_rank, config)
        self.q_b_proj = _Linear(config.q_lora_rank, h * (config.qk_nope_head_dim + config.qk_rope_head_dim), config)
        self.kv_a_proj_with_mqa = _Linear(d, config.kv_lora_rank + config.qk_rope_head_dim, config)
        self.kv_a_layernorm = _Norm(config.kv_lora_rank, config)
        self.kv_b_proj = _Linear(config.kv_lora_rank, h * (config.qk_nope_head_dim + config.v_head_dim), config)
        self.o_proj = _Linear(h * config.v_head_dim, d, config)

    def _queries(self, u: Tensor, cos: jax.Array, sin: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """``(q_nope [B, T, H, nope], rope(q_pe) [B, T, H, rope])``; ``cos`` / ``sin [B, T, rope/2]``."""
        cfg = self.config
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u)))._data
        q = q.reshape(q.shape[:2] + (cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
        return q[..., : cfg.qk_nope_head_dim], rope_interleaved(q[..., cfg.qk_nope_head_dim:], cos[:, :, None], sin[:, :, None])

    def _latent(self, u: Tensor, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """A token's row ``[c_kv (normalised) | rope(k_pe)]``, ``[B, T, kv_lora_rank + rope]``."""
        r = self.config.kv_lora_rank
        kv = self.kv_a_proj_with_mqa(u)
        return jnp.concatenate([self.kv_a_layernorm(kv[:, :, :r])._data, rope_interleaved(kv[:, :, r:]._data, cos, sin)], axis=-1)

    def _kv_b(self) -> jax.Array:
        """The ``kv_b_proj`` leaf as ``[kv_lora_rank, H, nope + v]``: ``W_UK`` and ``W_UV`` side by side."""
        cfg = self.config
        return self.kv_b_proj.weight._data.reshape(cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)

    def forward(self, u: Tensor) -> Tensor:
        """The plain path, MATERIALISED: per-head keys and values from ``c_kv W_kvb``, whole sequences."""
        cfg = self.config
        b, t, _ = u.shape
        cos, sin = yarn_cos_sin(jnp.broadcast_to(jnp.arange(t)[None, :], (b, t)), cfg)
        q_nope, q_pe = self._queries(u, cos, sin)
        row = self._latent(u, cos, sin)
        kv = jnp.einsum("btl,lhd->bthd", row[..., : cfg.kv_lora_rank], self._kv_b())
        k_nope, v = kv[..., : cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]
        scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("bthr,bsr->bhts", q_pe, row[..., cfg.kv_lora_rank:], preferred_element_type=jnp.float32)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores * cfg.softmax_scale, -1e30), axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)
        return self.o_proj(Tensor(out.reshape(b, t, -1)))

    def forward_latent(self, u: Tensor, past: LatentKV, cos: jax.Array, sin: jax.Array) -> Tuple[Tensor, LatentKV]:
        """The serving step, ABSORBED: ``W_UK`` folded into the queries and ``W_UV`` applied after the
        walk, so the heads attend over the latent rows themselves."""
        cfg = self.config
        b, t, _ = u.shape
        with jax.named_scope(SCOPE_MLA_Q):
            q_nope, q_pe = self._queries(u, cos, sin)
        with jax.named_scope(SCOPE_MLA_KV):
            row = self._latent(u, cos, sin)
        w = self._kv_b()
        with jax.named_scope(SCOPE_MLA_ABSORB):
            q_lat = jnp.einsum("bthd,lhd->bthl", q_nope, w[..., : cfg.qk_nope_head_dim], preferred_element_type=jnp.float32)
            q = (jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1) * cfg.softmax_scale).astype(row.dtype)
        with jax.named_scope(SCOPE_LATENT_ATTENTION):
            out, past = past.attend(q, row, cfg.kv_lora_rank)
        with jax.named_scope(SCOPE_MLA_ABSORB):
            out = jnp.einsum("bthl,lhd->bthd", out, w[..., cfg.qk_nope_head_dim:])
        with jax.named_scope(SCOPE_MLA_OUT):
            return self.o_proj(Tensor(out.reshape(b, t, -1))), past


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, config: DeepseekV2Config, index: int) -> None:
        super().__init__()
        self.sparse = index >= config.first_k_dense_replace
        self.self_attn = DeepseekV2Attention(config)
        self.mlp = DeepseekV2MoE(config) if self.sparse else DeepseekV2MLP(config, config.intermediate_size)
        self.input_layernorm = _Norm(config.hidden_size, config)
        self.post_attention_layernorm = _Norm(config.hidden_size, config)

    def feed_forward(self, h: Tensor, batch: Optional[PagedBatch] = None) -> Tensor:
        if self.sparse:
            with jax.named_scope(SCOPE_MOE):
                return self.mlp(h, batch)
        with jax.named_scope(SCOPE_MLP):
            return self.mlp(h)


class DeepseekV2Model(nn.Layer):
    def __init__(self, config: DeepseekV2Config) -> None:
        super().__init__()
        self.config = config
        self.embed_tokens = _Leaves(
            weight=((config.vocab_size, config.hidden_size), I.Normal(0.0, config.initializer_range), config.dtype)
        )
        self.layers = nn.LayerList([DeepseekV2DecoderLayer(config, i) for i in range(config.num_hidden_layers)])
        self.norm = _Norm(config.hidden_size, config)

    def _forward_cached(self, input_ids: Tensor, past: Sequence[LatentKV]) -> Tuple[Tensor, List[LatentKV]]:
        """The serving step: one latent set a layer under ONE shared batch. Entry and epilogues are the
        fused kernels ``models/llama.py``'s paged loop uses; the rotary rows are made ONCE a step."""
        from paddle_tpu.incubate.nn.functional import fused_embed_rms_norm, fused_rms_norm_residual

        layers = list(self.layers)
        batch = past[0].batch
        with jax.named_scope(SCOPE_EMBEDDING):
            first = layers[0].input_layernorm
            residual, h = fused_embed_rms_norm(input_ids, self.embed_tokens.weight, first.weight, first.epsilon)
        with jax.named_scope(SCOPE_MLA):
            positions = batch.seq_lens[:, None] + jnp.arange(input_ids.shape[1], dtype=batch.seq_lens.dtype)[None, :]
            cos, sin = yarn_cos_sin(positions, self.config)
        new_sets: List[LatentKV] = []
        for i, layer in enumerate(layers):
            with jax.named_scope(SCOPE_MLA):
                out, kept = layer.self_attn.forward_latent(h, past[i], cos, sin)
            new_sets.append(kept)
            norm = layer.post_attention_layernorm
            with jax.named_scope(SCOPE_NORM):
                h, residual = fused_rms_norm_residual(out, norm.weight, residual, norm.epsilon)
            out = layer.feed_forward(h, batch)
            norm = layers[i + 1].input_layernorm if i + 1 < len(layers) else self.norm
            with jax.named_scope(SCOPE_NORM):
                h, residual = fused_rms_norm_residual(out, norm.weight, residual, norm.epsilon)
        return h, new_sets  # h left the loop already final-normed

    def forward(self, input_ids: Tensor, past_key_values: Optional[Sequence[Any]] = None) -> Any:
        if past_key_values is not None:
            given = list(past_key_values)
            if len(given) != len(self.layers) or not all(type(kv) is LatentKV for kv in given):
                raise ValueError(
                    f"{len(self.layers)} layers keep one LatentKV set each; "
                    f"{[type(kv).__name__ for kv in given]} were given"
                )
            return self._forward_cached(input_ids, given)
        with jax.named_scope(SCOPE_EMBEDDING):
            h = Tensor(F.embedding(input_ids._data, self.embed_tokens.weight._data))
        for layer in self.layers:
            with jax.named_scope(SCOPE_NORM):
                u = layer.input_layernorm(h)
            with jax.named_scope(SCOPE_MLA):
                h = h + layer.self_attn(u)
            with jax.named_scope(SCOPE_NORM):
                u = layer.post_attention_layernorm(h)
            h = h + layer.feed_forward(u)
        with jax.named_scope(SCOPE_NORM):
            return self.norm(h)


class DeepseekV2ForCausalLM(nn.Layer):
    def __init__(self, config: DeepseekV2Config) -> None:
        super().__init__()
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = None if config.tie_word_embeddings else _Linear(config.hidden_size, config.vocab_size, config)

    def forward(
        self,
        input_ids: Tensor,
        labels: Optional[Tensor] = None,
        past_key_values: Optional[Sequence[Any]] = None,
        use_cache: bool = False,
    ) -> Any:
        """Logits ``[B, T, V]``. With ``past_key_values`` (one ``LatentKV`` a
        layer under one batch) the serving step, which hands the updated sets
        back beside the logits; with ``labels``, ``(loss, logits)``. The plain
        path runs on arrays and records no gradient: the block is not trained."""
        if use_cache and past_key_values is None:
            raise NotImplementedError(
                "DeepseekV2 keeps latent rows, not keys and values; no dense (key, value) cache is built. "
                "Serve it through ContinuousBatchingEngine, which hands it LatentKV sets."
            )
        out = self.model(input_ids, past_key_values)
        sets = None
        if past_key_values is not None:
            out, sets = out
        with jax.named_scope(SCOPE_LM_HEAD):
            if self.lm_head is not None:
                logits = self.lm_head(out)
            else:
                logits = Tensor(jnp.matmul(out._data, self.model.embed_tokens.weight._data.T))
        if labels is not None:
            with jax.named_scope(SCOPE_LOSS_HEAD):
                loss = F.cross_entropy(logits, labels, ignore_index=-100, reduction="mean")
            return loss, logits
        if sets is not None:
            return logits, sets
        return logits
