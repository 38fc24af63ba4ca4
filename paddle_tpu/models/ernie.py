"""ERNIE 3.0 / BERT-style bidirectional encoder (BASELINE config #2:
ERNIE-3.0-base finetune, AMP O2).

Architecture (ERNIE 3.0 base = 12-layer post-LN BERT encoder with
token/position/segment embeddings + task-id embedding, pooler, classification
head). Attention is bidirectional ``scaled_dot_product_attention`` (flash path
on TPU); finetune classification mirrors the reference's
``ErnieForSequenceClassification``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor

__all__ = ["ErnieConfig", "ErnieModel", "ErnieForSequenceClassification"]


@dataclass
class ErnieConfig:
    vocab_size: int = 40000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 2048
    type_vocab_size: int = 4
    task_type_vocab_size: int = 3
    use_task_id: bool = True
    dropout: float = 0.1
    layer_norm_epsilon: float = 1e-12

    @staticmethod
    def ernie3_base() -> "ErnieConfig":
        return ErnieConfig()

    @staticmethod
    def tiny(vocab: int = 128) -> "ErnieConfig":
        return ErnieConfig(
            vocab_size=vocab, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position=128, dropout=0.0,
        )


class ErnieEmbeddings(nn.Layer):
    def __init__(self, config: ErnieConfig) -> None:
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.position_embeddings = nn.Embedding(config.max_position, config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size, config.hidden_size)
        self.use_task_id = config.use_task_id
        if config.use_task_id:
            self.task_type_embeddings = nn.Embedding(
                config.task_type_vocab_size, config.hidden_size
            )
        self.layer_norm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.dropout)

    def forward(
        self,
        input_ids: Tensor,
        token_type_ids: Optional[Tensor] = None,
        position_ids: Optional[Tensor] = None,
        task_type_ids: Optional[Tensor] = None,
    ) -> Tensor:
        seq = input_ids.shape[1]
        if position_ids is None:
            position_ids = paddle_tpu.arange(seq, dtype="int32").unsqueeze(0)
        h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        if token_type_ids is not None:
            h = h + self.token_type_embeddings(token_type_ids)
        if self.use_task_id:
            if task_type_ids is None:
                task_type_ids = paddle_tpu.zeros_like(input_ids)
            h = h + self.task_type_embeddings(task_type_ids)
        return self.dropout(self.layer_norm(h))


class ErnieSelfAttention(nn.Layer):
    def __init__(self, config: ErnieConfig) -> None:
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        self.q_proj = nn.Linear(h, h)
        self.k_proj = nn.Linear(h, h)
        self.v_proj = nn.Linear(h, h)
        self.out_proj = nn.Linear(h, h)
        self.dropout = config.dropout

    def forward(self, x: Tensor, attn_mask: Optional[Tensor] = None) -> Tensor:
        b, s, h = x.shape
        shp = [b, s, self.num_heads, self.head_dim]
        q = self.q_proj(x).reshape(shp)
        k = self.k_proj(x).reshape(shp)
        v = self.v_proj(x).reshape(shp)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout, is_causal=False,
            training=self.training,
        )
        return self.out_proj(out.reshape([b, s, h]))


class ErnieLayer(nn.Layer):
    """Post-LN encoder block (BERT convention, matching the reference's
    TransformerEncoderLayer default normalize_before=False)."""

    def __init__(self, config: ErnieConfig) -> None:
        super().__init__()
        self.attn = ErnieSelfAttention(config)
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.fc1 = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc2 = nn.Linear(config.intermediate_size, config.hidden_size)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x: Tensor, attn_mask: Optional[Tensor] = None) -> Tensor:
        # Post-LN: the norm REPLACES the residual stream, so only the normed
        # output of the fused residual-add + norm is consumed; its XLA
        # fallback is the plain composition ``ln(x + branch)``
        from paddle_tpu.incubate.nn.functional import fused_layer_norm_residual

        x, _ = fused_layer_norm_residual(
            self.dropout(self.attn(x, attn_mask)),
            self.ln_1.weight, self.ln_1.bias, x, self.ln_1.epsilon,
        )
        ffn = self.fc2(F.gelu(self.fc1(x)))
        x, _ = fused_layer_norm_residual(
            self.dropout(ffn), self.ln_2.weight, self.ln_2.bias, x,
            self.ln_2.epsilon,
        )
        return x


class ErnieModel(nn.Layer):
    def __init__(self, config: ErnieConfig) -> None:
        super().__init__()
        self.config = config
        self.embeddings = ErnieEmbeddings(config)
        self.encoder = nn.LayerList([ErnieLayer(config) for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(
        self,
        input_ids: Tensor,
        token_type_ids: Optional[Tensor] = None,
        position_ids: Optional[Tensor] = None,
        attention_mask: Optional[Tensor] = None,
        task_type_ids: Optional[Tensor] = None,
        labels: Optional[Tensor] = None,
    ) -> Tuple[Tensor, Tensor]:
        """Returns ``(sequence_output, pooled_output)``. With ``labels``
        (masked-LM pretraining; ``-100`` = unmasked/ignored) the first
        element is instead the MLM **loss** over the tied word-embedding
        head — fused vocab-chunk-wise when ``FLAGS_use_fused_loss`` is on,
        so ``[B, S, V]`` prediction scores are never materialized."""
        mask = None
        if attention_mask is not None:
            # [B, S] padding mask → additive [B, 1, 1, S]
            neg = (1.0 - attention_mask.astype("float32")) * -1e4
            mask = neg.unsqueeze(1).unsqueeze(2)
        h = self.embeddings(input_ids, token_type_ids, position_ids, task_type_ids)
        for layer in self.encoder:
            h = layer(h, mask)
        pooled = paddle_tpu.tanh(self.pooler(h[:, 0]))
        if labels is not None:
            from paddle_tpu.flags import GLOBAL_FLAGS

            w = self.embeddings.word_embeddings.weight
            if GLOBAL_FLAGS.get("use_fused_loss"):
                loss = F.fused_linear_cross_entropy(
                    h, w, labels, ignore_index=-100, reduction="mean",
                    weight_vocab_major=True,
                    weight_scale=getattr(w, "_quant_scale", None),
                )
            else:
                scores = paddle_tpu.matmul(h, w, transpose_y=True)
                loss = F.cross_entropy(scores, labels, ignore_index=-100, reduction="mean")
            return loss, pooled
        return h, pooled


class ErnieForSequenceClassification(nn.Layer):
    def __init__(self, config: ErnieConfig, num_classes: int = 2) -> None:
        super().__init__()
        self.ernie = ErnieModel(config)
        self.dropout = nn.Dropout(config.dropout)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids: Tensor, token_type_ids: Optional[Tensor] = None,
                attention_mask: Optional[Tensor] = None) -> Tensor:
        _, pooled = self.ernie(input_ids, token_type_ids, attention_mask=attention_mask)
        return self.classifier(self.dropout(pooled))
