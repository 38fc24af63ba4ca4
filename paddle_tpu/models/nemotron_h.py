"""Nemotron-H (NVIDIA Nemotron-3-Nano, ``model_type`` nemotron_h): a hybrid
decoder whose blocks are each ONE pre-norm and ONE mixer. Which mixer block
``i`` has is ``hybrid_override_pattern[i]``: ``M`` a Mamba-2 state-space layer,
``E`` a sparse-expert layer, ``*`` causal attention. No block has both a mixer
and an MLP.

    x = E[tokens];  for i: x = x + mixer_i(RMS(x; g_i));  logits = W_head RMS(x; g_final)

    M: [z | xBC | dt] = W_in u;  xBC = silu(conv1d_causal(xBC) + b) -> x [H, P], B [G, N], C [G, N]
       dt = softplus(dt + dt_bias), A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  y_t = S_t C_t + D x_t
       out = W_out (g * rmsnorm_{d_inner/G}(y * silu(z)))
    *: GQA causal attention, scale 1/sqrt(head_dim), NO positional encoding
    E: s = sigmoid(W_g u); top-k of s + b_sel; weights s / sum(chosen s) * routed_scaling_factor;
       out = sum_chosen w_e W_down_e relu(W_up_e u)^2 + shared(u)

Three kinds of block keep three kinds of state, which
``NemotronHConfig.cache_sets`` tells a cache owner (the serving engine) in
block order: an ``M`` block one :class:`RecurrentState` (per slot, no pages),
a ``*`` block one :class:`PagedKV`, an ``E`` block none. The model takes its
serving path when it is handed such a past (the engine's one compiled step);
without a past it runs the plain forward (the scan from a zero state, in
chunks of ``chunk_size``). A dense ``(key, value)`` cache is not built.

AN EXPERT LAYER THAT HOLDS A SHARE. ``n_routed_experts`` is how many experts
this model HOLDS, ``first_expert .. first_expert + n_routed_experts - 1`` of
the ``n_routed_experts_total`` its router scores (expert parallelism's view
from one chip; by default it holds them all). It routes over all of them,
computes what its own give and leaves the rest out; the leaves are 3-D,
``[held, D, I]`` and ``[held, I, D]``
(``incubate/nn/functional/fused_moe.py::expert_share``).

Attention is a class of its own (``NemotronHAttention``), not
``LlamaAttention`` without a table: that one derives ``head_dim`` from
``hidden_size / heads`` (84 here, published 128) and rotates unconditionally.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.dispatch import call_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional.fused_moe import expert_share
from paddle_tpu.incubate.nn.functional.mamba2 import (
    causal_conv_chunk,
    gated_group_rms_norm,
    split_conv_channels,
    ssd_sequence,
)
from paddle_tpu.inference.paged_kv import PAGED, CacheSet, PagedBatch, PagedKV, RecurrentState
from paddle_tpu.models.llama import (
    SCOPE_ATTENTION,
    SCOPE_EMBEDDING,
    SCOPE_LM_HEAD,
    SCOPE_LOSS_HEAD,
    SCOPE_NORM,
)
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops.manipulation import reshape

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# jax.named_scope names of what this family adds (beside models/llama.py's);
# ssm_conv / ssm_scan open inside RecurrentState.advance, moe_router /
# moe_dispatch / moe_experts / moe_combine inside expert_share
SCOPE_SSM = "ssm_mixer"
SCOPE_SSM_GATE_NORM = "ssm_gate_norm"
SCOPE_MOE = "moe"
SCOPE_MOE_SHARED = "moe_shared"


@dataclass
class NemotronHConfig:
    """The published ``config.json`` keys the program reads (defaults: the
    30B-A3B model), plus the share of the experts held here."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 128  # HELD here
    n_routed_experts_total: Optional[int] = None  # the router's width; None: all are held
    first_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 4096  # a cache owner's default length; nothing is built for it
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.n_routed_experts_total is None:
            self.n_routed_experts_total = self.n_routed_experts
        if len(self.hybrid_override_pattern) < self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names {len(self.hybrid_override_pattern)} blocks, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown:
            raise ValueError(f"hybrid_override_pattern has unknown mixers {sorted(unknown)}")
        if not 0 <= self.first_expert <= self.n_routed_experts_total - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.n_routed_experts - 1} "
                f"are not among the router's {self.n_routed_experts_total}"
            )

    @property
    def pattern(self) -> str:
        """The mixers of the blocks that are built: the published order's first ``num_hidden_layers``."""
        return self.hybrid_override_pattern[: self.num_hidden_layers]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def cache_sets(self) -> List[CacheSet]:
        """What the model keeps a sequence, in block order (an ``E`` block keeps nothing)."""
        state = RecurrentState.spec(
            self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size, self.conv_kernel, self.conv_dim, self.dtype
        )
        page = CacheSet(PAGED, (((self.num_key_value_heads, self.head_dim), self.dtype),) * 2)
        return [state if k == MAMBA else page for k in self.pattern if k != EXPERTS]

    @property
    def num_kv_sets(self) -> int:
        """PAGED sets a token holds: one per attention block."""
        return self.pattern.count(ATTENTION)

    @staticmethod
    def tiny(vocab: int = 256, pattern: str = "MEM*EM", held: int = 8, total: int = 8) -> "NemotronHConfig":
        return NemotronHConfig(
            vocab_size=vocab, hidden_size=64, num_hidden_layers=len(pattern), hybrid_override_pattern=pattern,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
            n_groups=2, ssm_state_size=16, chunk_size=8, n_routed_experts=held, n_routed_experts_total=total,
            num_experts_per_tok=3, moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
            max_position_embeddings=128, dtype="float32",
        )


class _Leaves(nn.Layer):
    """A holder of bare leaves under the family's names (``conv1d.weight``, ``experts.up_proj``, ...)."""

    def __init__(self, **leaves: Tuple[Sequence[int], Any, Any]) -> None:
        super().__init__()
        for name, (shape, init, dtype) in leaves.items():
            setattr(self, name, self.create_parameter(list(shape), dtype=dtype, default_initializer=init))


class NemotronHMamba2Mixer(nn.Layer):
    def __init__(self, config: NemotronHConfig) -> None:
        super().__init__()
        self.config = config
        d, inner, conv, heads = config.hidden_size, config.d_inner, config.conv_dim, config.mamba_num_heads
        self.in_proj = nn.Linear(d, inner + conv + heads, bias_attr=False)
        half = 1.0 / config.conv_kernel ** 0.5
        self.conv1d = _Leaves(weight=((config.conv_kernel, conv), I.Uniform(-half, half), None),
                              bias=((conv,), I.Uniform(-half, half), None))
        self.dt_bias = self.create_parameter([heads], default_initializer=I.Uniform(-6.9, -2.3))
        self.A_log = self.create_parameter([heads], default_initializer=I.Uniform(0.0, 2.77))  # A in -1..-16
        self.D = self.create_parameter([heads], default_initializer=I.Constant(1.0))
        self.norm = _Leaves(weight=((inner,), I.Constant(1.0), None))
        self.out_proj = nn.Linear(inner, d, bias_attr=False)

    def _mix(self, zxbcdt, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, state: Optional[RecurrentState] = None):
        """From the in-projection ``[B, T, d_inner + conv + H]`` to the gated, normed ``y [B, T, d_inner]``
        (arrays in, arrays out): with a ``state`` its rows continue each slot's conv and scan and the updated
        set comes back beside ``y``; without, whole sequences run from a zero state in chunks of ``chunk_size``."""
        cfg = self.config
        inner, conv, g = cfg.d_inner, cfg.conv_dim, cfg.n_groups
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv]
        dt = jax.nn.softplus(zxbcdt[..., inner + conv:].astype(jnp.float32) + dt_bias.astype(jnp.float32))
        a = -jnp.exp(a_log.astype(jnp.float32))
        if state is not None:
            y, state = state.advance(xbc, dt, conv_w, conv_b, a, d_skip, g)
        else:
            b = xbc.shape[0]
            tail = jnp.zeros((b, cfg.conv_kernel - 1, conv), xbc.dtype)
            xbc, _ = causal_conv_chunk(xbc, tail, conv_w, conv_b, jnp.zeros((b,), jnp.int32))
            x, bb, cc = split_conv_channels(xbc, cfg.mamba_num_heads, cfg.mamba_head_dim, g, cfg.ssm_state_size)
            y = ssd_sequence(x, dt, a, bb, cc, d_skip, cfg.chunk_size)
        with jax.named_scope(SCOPE_SSM_GATE_NORM):
            y = gated_group_rms_norm(y.reshape(z.shape), z, norm_w, g, cfg.layer_norm_epsilon).astype(z.dtype)
        return y, state

    def _leaves(self) -> Tuple[Any, ...]:
        return self.conv1d.weight, self.conv1d.bias, self.dt_bias, self.A_log, self.D, self.norm.weight

    def forward(self, u: Tensor) -> Tensor:
        """The plain path: whole sequences ``[B, T, D]`` from a zero state."""
        y = call_op("mamba2_mix", lambda *arrays: self._mix(*arrays)[0], self.in_proj(u), *self._leaves())
        return self.out_proj(y)

    def forward_state(self, u: Tensor, state: RecurrentState) -> Tuple[Tensor, RecurrentState]:
        """The serving step: the chunk's rows continue each slot's state."""
        y, state = self._mix(self.in_proj(u)._data, *(p._data for p in self._leaves()), state=state)
        return self.out_proj(Tensor(y)), state


class NemotronHAttention(nn.Layer):
    """GQA causal attention at the published ``head_dim``, with no positional encoding."""

    def __init__(self, config: NemotronHConfig) -> None:
        super().__init__()
        self.num_heads, self.num_kv_heads, self.head_dim = (
            config.num_attention_heads, config.num_key_value_heads, config.head_dim
        )
        d = config.hidden_size
        self.q_proj = nn.Linear(d, self.num_heads * self.head_dim, bias_attr=False)
        self.k_proj = nn.Linear(d, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.v_proj = nn.Linear(d, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, d, bias_attr=False)

    def _qkv(self, u: Tensor) -> Tuple[Tensor, Tensor, Tensor]:
        b, s, _ = u.shape
        return (reshape(self.q_proj(u), [b, s, self.num_heads, self.head_dim]),
                reshape(self.k_proj(u), [b, s, self.num_kv_heads, self.head_dim]),
                reshape(self.v_proj(u), [b, s, self.num_kv_heads, self.head_dim]))

    def forward(self, u: Tensor) -> Tensor:
        b, s, _ = u.shape
        q, k, v = self._qkv(u)
        out = F.flashmask_attention(q, k, v, causal=True)
        return self.o_proj(reshape(out, [b, s, self.num_heads * self.head_dim]))

    def forward_paged(self, u: Tensor, past: PagedKV) -> Tuple[Tensor, PagedKV]:
        """The paged kernel's PLAIN site: keys are appended and walked as they are, no rope rows."""
        b, s, _ = u.shape
        q, k, v = self._qkv(u)
        out, past = past.attend(q._data, k._data, v._data)
        return self.o_proj(reshape(Tensor(out), [b, s, self.num_heads * self.head_dim])), past


class NemotronHMoE(nn.Layer):
    def __init__(self, config: NemotronHConfig) -> None:
        super().__init__()
        self.config = config
        d, held, width = config.hidden_size, config.n_routed_experts, config.moe_intermediate_size
        std = I.Normal(0.0, config.initializer_range)
        self.gate = _Leaves(weight=((d, config.n_routed_experts_total), std, None),
                            e_score_correction_bias=((config.n_routed_experts_total,), I.Normal(0.0, 0.05), None))
        # 3-D leaves, made in the configuration's dtype (no float32 copy of the largest leaves)
        self.experts = _Leaves(up_proj=((held, d, width), std, config.dtype), down_proj=((held, width, d), std, config.dtype))
        self.shared_experts = _SharedExpert(d, config.moe_shared_expert_intermediate_size)

    def forward(self, u: Tensor, batch: Optional[PagedBatch] = None) -> Tensor:
        """``batch``: the serving step's, whose masked slots and rows past
        ``q_lens`` are sent to no expert."""
        cfg = self.config
        row_mask = None if batch is None else batch.live_rows(u.shape[1])

        def share(x, gate_w, bias, w_up, w_down):
            out = expert_share(
                x.reshape(-1, x.shape[-1]), gate_w, bias, w_up, w_down, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.first_expert, cfg.norm_topk_prob, row_mask,
            )
            return out.reshape(x.shape)

        routed = call_op("expert_share", share, u, self.gate.weight, self.gate.e_score_correction_bias,
                         self.experts.up_proj, self.experts.down_proj)
        with jax.named_scope(SCOPE_MOE_SHARED):
            return routed + self.shared_experts(u)


class _SharedExpert(nn.Layer):
    """Non-gated squared-ReLU MLP on every token."""

    def __init__(self, hidden: int, width: int) -> None:
        super().__init__()
        self.up_proj = nn.Linear(hidden, width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden, bias_attr=False)

    def forward(self, u: Tensor) -> Tensor:
        h = F.relu(self.up_proj(u))
        return self.down_proj(h * h)


class NemotronHBlock(nn.Layer):
    """``x + mixer(norm(x))``; the residual add and the norm live in the model's loops."""

    def __init__(self, config: NemotronHConfig, kind: str) -> None:
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mixer = {MAMBA: NemotronHMamba2Mixer, ATTENTION: NemotronHAttention, EXPERTS: NemotronHMoE}[kind](config)

    def mix(self, u: Tensor, past: Any = None, batch: Optional[PagedBatch] = None) -> Tuple[Tensor, Any]:
        """The mixer on normed ``u``; with a ``past`` (or, for experts, a
        ``batch``) the serving step's form. Returns ``(out, the set updated or None)``."""
        if self.kind == MAMBA:
            with jax.named_scope(SCOPE_SSM):
                return self.mixer.forward_state(u, past) if past is not None else (self.mixer(u), None)
        if self.kind == ATTENTION:
            with jax.named_scope(SCOPE_ATTENTION):
                return self.mixer.forward_paged(u, past) if past is not None else (self.mixer(u), None)
        with jax.named_scope(SCOPE_MOE):
            return self.mixer(u, batch), None


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig) -> None:
        super().__init__()
        self.config = config
        self.embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([NemotronHBlock(config, k) for k in config.pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)

    def _forward_cached(self, input_ids: Tensor, past: Sequence[Any]) -> Tuple[Tensor, List[Any]]:
        """The serving step: one set per ``M`` and ``*`` block, in block
        order, under ONE shared batch. Entry and epilogues are the fused
        kernels ``models/llama.py``'s paged loop uses: embedding lookup with
        block 0's norm, then each residual add with the NEXT block's norm."""
        from paddle_tpu.incubate.nn.functional import fused_embed_rms_norm, fused_rms_norm_residual

        layers = list(self.layers)
        batch = past[0].batch
        with jax.named_scope(SCOPE_EMBEDDING):
            residual, h = fused_embed_rms_norm(
                input_ids, self.embeddings.weight, layers[0].norm.weight, layers[0].norm.epsilon
            )
        sets = iter(past)
        new_sets: List[Any] = []
        for i, layer in enumerate(layers):
            out, kept = layer.mix(h, None if layer.kind == EXPERTS else next(sets), batch)
            if kept is not None:
                new_sets.append(kept)
            nxt = layers[i + 1].norm if i + 1 < len(layers) else self.norm_f
            with jax.named_scope(SCOPE_NORM):
                h, residual = fused_rms_norm_residual(out, nxt.weight, residual, nxt.epsilon)
        return h, new_sets  # h left the loop already final-normed

    def forward(self, input_ids: Tensor, past_key_values: Optional[Sequence[Any]] = None) -> Any:
        if past_key_values is not None:
            given, want = list(past_key_values), self.config.cache_sets
            if [type(kv) is PagedKV for kv in given] != [cs.kind == PAGED for cs in want]:
                raise ValueError(
                    f"the blocks {self.config.pattern!r} keep {[cs.kind for cs in want]}; "
                    f"{[type(kv).__name__ for kv in given]} were given"
                )
            return self._forward_cached(input_ids, given)
        with jax.named_scope(SCOPE_EMBEDDING):
            h = self.embeddings(input_ids)
        for layer in self.layers:
            with jax.named_scope(SCOPE_NORM):
                u = layer.norm(h)
            h = h + layer.mix(u)[0]
        with jax.named_scope(SCOPE_NORM):
            return self.norm_f(h)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig) -> None:
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)
        else:
            self.lm_head = None

    def forward(
        self,
        input_ids: Tensor,
        labels: Optional[Tensor] = None,
        past_key_values: Optional[Sequence[Any]] = None,
        use_cache: bool = False,
    ) -> Any:
        """Logits ``[B, T, V]``. With ``past_key_values`` (the model's
        ``cache_sets``, typed: ``RecurrentState`` / ``PagedKV`` under one
        batch) the serving step, which hands the updated sets back beside the
        logits; with ``labels``, ``(loss, logits)``."""
        if use_cache and past_key_values is None:
            raise NotImplementedError(
                "NemotronH keeps recurrent state beside paged KV; no dense (key, value) cache is built. "
                "Serve it through ContinuousBatchingEngine, which hands it typed sets."
            )
        out = self.backbone(input_ids, past_key_values)
        sets = None
        if past_key_values is not None:
            out, sets = out
        with jax.named_scope(SCOPE_LM_HEAD):
            if self.lm_head is not None:
                logits = self.lm_head(out)
            else:
                logits = paddle_tpu.matmul(out, self.backbone.embeddings.weight, transpose_y=True)
        if labels is not None:
            with jax.named_scope(SCOPE_LOSS_HEAD):
                loss = F.cross_entropy(logits, labels, ignore_index=-100, reduction="mean")
            return loss, logits
        if sets is not None:
            return logits, sets
        return logits
