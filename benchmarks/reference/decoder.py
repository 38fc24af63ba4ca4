"""Plain reference of a pre-norm decoder-only transformer (the Mistral/Llama
block as published: RMSNorm, rotary embeddings in the rotate-half layout, GQA
causal attention, SwiGLU MLP, untied head, token-mean cross entropy, AdamW).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks. Imports nothing of the program under test. Weights are
dictionaries of arrays in the layout ``[in, out]``:

    layer: wq wk wv wo w_gate w_up w_down norm_attn norm_mlp
    top:   embed [V, H], final_norm [H], head [H, V]

It is also what tells the harness the shape of this block (``lib/arch.py``;
``benchmarks/README.md`` has the list): the leaf table (``top_leaves``,
``layer_leaves``), the walk (``sequence_logits`` for serving,
``batch_loss_and_grads`` and ``adamw_update`` for training) and the counts
(``head_dim``, ``matmul_params``, ``attention_passes``).

``lower`` names a deliberately lower matmul precision, used only by the
controls that must come out as not correct: ``"int8"`` rounds both operands
of every matmul to 8-bit integers (per-row / per-column absmax scales),
``"bf16"`` rounds them to bfloat16. ``None`` is the reference proper.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

PRECISION = "highest"


def head_dim(cfg: Dict[str, Any]) -> int:
    """The file's ``head_dim`` where it gives one, else what the family does
    (listed under the file's ``assumed``)."""
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def top_leaves(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``{leaf: (shape, init)}`` of the leaves outside the layers; ``init`` as
    ``lib/weights.py`` lists them."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, h), "normal"), "final_norm": ((h,), "ones"), "head": ((h, v), "normal")}


def layer_leaves(cfg: Dict[str, Any], index: int) -> Dict[str, Any]:
    """The leaves of layer ``index``: here every layer has the same."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * head_dim(cfg), cfg["num_key_value_heads"] * head_dim(cfg)
    matrices = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
                "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}
    return {**{n: (s, "normal") for n, s in matrices.items()},
            "norm_attn": ((h,), "ones"), "norm_mlp": ((h,), "ones")}


def matmul_params(cfg: Dict[str, Any], depth: int) -> int:
    """Weights that a token passes through by matrix multiplication: every
    layer's projections and the output head. The embedding is a lookup."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    layer = h * (nh * hd) + 2 * h * (nkv * hd) + (nh * hd) * h + 3 * h * i
    return depth * layer + h * v


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    """Causal-attention calls, and so KV sets, that a token makes: one a layer."""
    return depth


def _round_int8(x: jax.Array, axis: int) -> jax.Array:
    """Round to 8-bit integers at an absmax scale along ``axis``; the gradient
    passes straight through (a rounding has none of its own)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return x + jax.lax.stop_gradient(jnp.round(x / scale) * scale - x)


def matmul(x: jax.Array, w: jax.Array, lower: Optional[str] = None) -> jax.Array:
    """``x [..., K] @ w [K, N]`` in float32; ``lower`` rounds the operands."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if lower == "int8":
        x, w = _round_int8(x, -1), _round_int8(w, 0)
    elif lower == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif lower is not None:
        raise ValueError(f"unknown lower precision {lower!r}")
    return jnp.matmul(x, w, precision=PRECISION)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """``x [T, heads, D]`` rotated at ``positions [T]``; rotate-half layout:
    the pair of dimension ``i`` is ``i + D/2``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q: jax.Array, k: jax.Array, v: jax.Array, lower: Optional[str] = None) -> jax.Array:
    """Causal attention of one sequence. ``q [T, Hq, D]``, ``k, v [T, Hkv, D]``;
    query head ``h`` reads key/value head ``h // (Hq / Hkv)``."""
    t, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if lower == "int8":
        q, k, v = _round_int8(q, -1), _round_int8(k, -1), _round_int8(v, -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=PRECISION) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=PRECISION)


def decoder_layer(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
                  lower: Optional[str] = None) -> jax.Array:
    """One block on one sequence ``h [T, H]`` at positions ``0..T-1``."""
    t = h.shape[0]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    pos = jnp.arange(t)
    x = rms_norm(h, w["norm_attn"], cfg["rms_norm_eps"])
    q = rope(matmul(x, w["wq"], lower).reshape(t, nh, hd), pos, cfg["rope_theta"])
    k = rope(matmul(x, w["wk"], lower).reshape(t, nkv, hd), pos, cfg["rope_theta"])
    v = matmul(x, w["wv"], lower).reshape(t, nkv, hd)
    a = attention(q, k, v, lower).reshape(t, nh * hd)
    h = h + matmul(a, w["wo"], lower)
    x = rms_norm(h, w["norm_mlp"], cfg["rms_norm_eps"])
    gate = matmul(x, w["w_gate"], lower)
    up = matmul(x, w["w_up"], lower)
    return h + matmul(jax.nn.silu(gate) * up, w["w_down"], lower)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return table.astype(jnp.float32)[tokens]


def head_logits(h: jax.Array, top: Dict[str, jax.Array], cfg: Dict[str, Any],
                lower: Optional[str] = None) -> jax.Array:
    return matmul(rms_norm(h, top["final_norm"], cfg["rms_norm_eps"]), top["head"], lower)


def forward_logits(tokens: jax.Array, weights: Dict[str, Any], cfg: Dict[str, Any],
                   lower: Optional[str] = None) -> jax.Array:
    """Logits ``[T, V]`` of one sequence ``tokens [T]``; ``weights`` holds
    ``top`` and the list ``layers``."""
    h = embed(tokens, weights["top"]["embed"])
    for w in weights["layers"]:
        h = decoder_layer(h, w, cfg, lower)
    return head_logits(h, weights["top"], cfg, lower)


class _Frozen(dict):
    """A configuration that jit can take as a static argument."""

    def __hash__(self) -> int:  # type: ignore[override]
        return hash(tuple(sorted((k, v) for k, v in self.items() if isinstance(v, (int, float, str)))))


_layer_jit = jax.jit(decoder_layer, static_argnums=(2,))
_head_jit = jax.jit(head_logits, static_argnums=(2,))


def sequence_logits(token_seqs: Sequence[Any], top: Dict[str, jax.Array],
                    layer_weights: Callable[[int], Dict[str, jax.Array]], cfg: Dict[str, Any]) -> Iterator[jax.Array]:
    """The float32 logits ``[len(seq), V]`` of each sequence, one after the
    other. The layers are walked once for all of them; ``layer_weights(i)``
    makes layer ``i``'s float32 leaves when asked, so one layer is held at a
    time. ``top`` holds its leaves in the type they are served in."""
    cfg = _Frozen(cfg)
    hidden = [embed(jnp.asarray(toks), top["embed"]) for toks in token_seqs]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        hidden = [_layer_jit(h, w, cfg) for h in hidden]
        del w
    for h in hidden:
        yield _head_jit(h, top, cfg)


def sequence_loss_sum(weights: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
                      cfg: Dict[str, Any], lower: Optional[str] = None) -> jax.Array:
    """Sum over positions of the cross entropy of one sequence (the mean over
    a batch is this, summed over rows, over the number of label positions)."""
    logits = forward_logits(tokens, weights, cfg, lower)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def adamw_update(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array, step: int, *,
                 lr: float, beta1: float, beta2: float, eps: float, weight_decay: float):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter) with bias correction,
    all float32: ``p <- p - lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``."""
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    m_hat = m / (1.0 - beta1 ** step)
    v_hat = v / (1.0 - beta2 ** step)
    p = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p)
    return p, m, v


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"), donate_argnums=(0,))
def _accumulate(acc, weights, tokens, labels, cfg_items, lower):
    cfg = dict(cfg_items)
    loss, grads = jax.value_and_grad(sequence_loss_sum)(weights, tokens, labels, cfg, lower)
    acc_loss, acc_grads = acc
    return acc_loss + loss, jax.tree_util.tree_map(jnp.add, acc_grads, grads)


def batch_loss_and_grads(weights: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
                         cfg: Dict[str, Any], lower: Optional[str] = None):
    """Token-mean loss and its gradients over a batch ``[B, T]``, one row at a
    time so that the activations of one sequence are all that is held."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    acc = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, weights))
    for row in range(tokens.shape[0]):
        acc = _accumulate(acc, weights, tokens[row], labels[row], items, lower)
    n = tokens.shape[0] * tokens.shape[1]
    loss, grads = acc
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)
