"""GPT-3-style decoder LM (BASELINE config #4: GPT-3 13B TP+PP hybrid).

Architecture per the reference's GPT implementations (used by
``test/auto_parallel/hybrid_strategy/get_gpt_model.py`` and fleet examples):
learned position embeddings, pre-LN blocks, GELU MLP (4x), causal attention.

TPU-native: attention runs through ``paddle_tpu.nn.functional.flash_attention``
(Pallas on TPU); TP placements come from ``gpt_shard_fn`` (Megatron layout);
the pipeline form is built from ``LayerDesc``s with the embedding tied to the
output projection via ``SharedLayerDesc``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor

__all__ = [
    "GPTConfig",
    "GPTModel",
    "GPTForPretraining",
    "gpt_shard_fn",
    "build_gpt_pipeline",
]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 5120
    num_layers: int = 40
    num_heads: int = 40
    max_position: int = 2048
    ffn_ratio: int = 4
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5

    @staticmethod
    def gpt3_13b() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def tiny(vocab: int = 128) -> "GPTConfig":
        return GPTConfig(
            vocab_size=vocab, hidden_size=64, num_layers=2, num_heads=4, max_position=128
        )


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.position_embeddings = nn.Embedding(config.max_position, config.hidden_size)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None) -> Tensor:
        seq = input_ids.shape[1]
        if position_ids is None:
            position_ids = paddle_tpu.arange(seq, dtype="int32").unsqueeze(0)
        h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return self.dropout(h)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        h = config.hidden_size
        self.qkv_proj = nn.Linear(h, 3 * h)
        self.out_proj = nn.Linear(h, h)
        self.dropout = config.dropout

    def forward(self, x: Tensor) -> Tensor:
        b, s, h = x.shape
        qkv = self.qkv_proj(x).reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out, _ = F.flash_attention(
            q, k, v, dropout=self.dropout, causal=True, training=self.training
        )
        return self.out_proj(out.reshape([b, s, h]))


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        h = config.hidden_size
        self.fc1 = nn.Linear(h, config.ffn_ratio * h)
        self.fc2 = nn.Linear(config.ffn_ratio * h, h)

    def forward(self, x: Tensor) -> Tensor:
        return self.fc2(F.gelu(self.fc1(x)))


class GPTBlock(nn.Layer):
    """Pre-LN decoder block."""

    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x: Tensor) -> Tensor:
        # residual add + ln_2 in ONE dispatch (tape backward runs the
        # standalone adjoint kernel); its XLA fallback is the plain
        # composition ``ln_2(x + attn_out)``
        from paddle_tpu.incubate.nn.functional import fused_layer_norm_residual

        attn_out = self.attn(self.ln_1(x))
        h2, x2 = fused_layer_norm_residual(
            attn_out, self.ln_2.weight, self.ln_2.bias, x, self.ln_2.epsilon
        )
        return x2 + self.mlp(h2)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList([GPTBlock(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None) -> Tensor:
        h = self.embeddings(input_ids, position_ids)
        for layer in self.layers:
            h = layer(h)
        return self.ln_f(h)


class GPTForPretraining(nn.Layer):
    """LM head tied to the word embedding (the SharedLayerDesc pattern in the
    pipeline form)."""

    def __init__(self, config: GPTConfig) -> None:
        super().__init__()
        self.gpt = GPTModel(config)

    def forward(
        self,
        input_ids: Tensor,
        position_ids: Optional[Tensor] = None,
        labels: Optional[Tensor] = None,
    ) -> Any:
        """Without ``labels``: ``[B, S, V]`` logits (unchanged). With
        ``labels``: ``(loss, None)`` on the fused lm-head+cross-entropy path
        (``FLAGS_use_fused_loss``, tied embedding fuses vocab-major) — logits
        are never materialized — else ``(loss, logits)``."""
        h = self.gpt(input_ids, position_ids)
        w = self.gpt.embeddings.word_embeddings.weight
        if labels is not None:
            from paddle_tpu.flags import GLOBAL_FLAGS

            if GLOBAL_FLAGS.get("use_fused_loss"):
                loss = F.fused_linear_cross_entropy(
                    h, w, labels, ignore_index=-100, reduction="mean",
                    weight_vocab_major=True,
                    weight_scale=getattr(w, "_quant_scale", None),
                )
                return loss, None
            logits = paddle_tpu.matmul(h, w, transpose_y=True)
            loss = F.cross_entropy(logits, labels, ignore_index=-100, reduction="mean")
            return loss, logits
        return paddle_tpu.matmul(h, w, transpose_y=True)


def gpt_shard_fn(name: str, sublayer: Any, mesh: Any) -> None:
    """Megatron TP placements over the 'mp' axis: qkv/fc1 column-sharded,
    out_proj/fc2 row-sharded, embeddings vocab-sharded."""
    from paddle_tpu.distributed.api import apply_placement, build_placements

    if "mp" not in mesh.dim_names or mesh.get_dim_size("mp") == 1:
        return

    def put(param: Any, dim: Optional[int]) -> None:
        apply_placement(param, mesh, build_placements(mesh, mp=dim))

    if isinstance(sublayer, GPTAttention):
        put(sublayer.qkv_proj.weight, 1)
        put(sublayer.qkv_proj.bias, 0)
        put(sublayer.out_proj.weight, 0)
        put(sublayer.out_proj.bias, None)
    elif isinstance(sublayer, GPTMLP):
        put(sublayer.fc1.weight, 1)
        put(sublayer.fc1.bias, 0)
        put(sublayer.fc2.weight, 0)
        put(sublayer.fc2.bias, None)
    elif isinstance(sublayer, nn.Embedding):
        put(sublayer.weight, 0)


def build_gpt_pipeline(config: GPTConfig, num_stages: int, **pp_kwargs: Any):
    """The PP form: LayerDescs with tied embedding head
    (reference GPT-PP models built on ``PipelineLayer``)."""
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc,
        PipelineLayer,
        SharedLayerDesc,
    )

    def head_forward(layer: GPTEmbeddings, x: Tensor) -> Tensor:
        return paddle_tpu.matmul(x, layer.word_embeddings.weight, transpose_y=True)

    descs: List[Any] = [
        SharedLayerDesc("embed", GPTEmbeddings, None, "word_embeddings.weight", config)
    ]
    descs += [LayerDesc(GPTBlock, config) for _ in range(config.num_layers)]
    descs.append(LayerDesc(nn.LayerNorm, config.hidden_size, epsilon=config.layer_norm_epsilon))
    descs.append(
        SharedLayerDesc("embed", GPTEmbeddings, head_forward, "word_embeddings.weight", config)
    )
    return PipelineLayer(
        layers=descs, num_stages=num_stages, seg_method="layer:GPTBlock", **pp_kwargs
    )
