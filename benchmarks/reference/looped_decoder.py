"""Plain reference of a LOOPED sandwich-norm decoder (Ouro, ByteDance LoopLM,
``model_type`` ouro): one stack of ``num_hidden_layers`` layers is run
``total_ut_steps`` times over every token with the SAME weights, and the final
norm closes every pass and feeds the next. With RMS(x; g) = g * x /
sqrt(mean(x^2) + eps):

    x = E[tokens]
    for t in 0..T-1:
      for l in 0..L-1:
        x = x + RMS(O_l(causal_attention(rope(Q_l h), rope(K_l h), V_l h)); g2_l),  h = RMS(x; g1_l)
        x = x + RMS(W_down_l(silu(W_gate_l u) * (W_up_l u)); g4_l),                 u = RMS(x; g3_l)
      x = RMS(x; g_final)
    logits = W_head x                                     # of the last pass

A pass attends to the keys and values of the SAME pass: there is no cache
here, so that is simply causal attention inside each pass.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching. Imports nothing of the program under test; ``matmul``, ``rms_norm``,
``rope``, ``attention`` and ``adamw_update`` are ``reference/decoder.py``'s.

Departures from the published model, each also under the configuration file's
``assumed``:
- no exit gate: the published ``early_exit_threshold`` of 1 switches early
  exit off, so every token takes all ``total_ut_steps`` passes and the logits
  are the last pass's; the hidden-to-1 gate projection decides nothing and is
  not built;
- the four norm leaves a layer and their placement (before each branch and on
  each branch's output), the final norm between passes, no biases in the
  projections, ``initializer_range`` 0.02 and norms made of ones are not in
  ``config.json``; they are the family's published description;
- ``batch_loss_and_grads`` is the LAST pass's token-mean cross entropy. The
  published training objective also weighs the earlier passes' heads by the
  exit distribution; it is not in ``config.json`` and no cell trains.

Weights are dictionaries of arrays in the layout ``[in, out]``:

    layer: wq wk wv wo w_gate w_up w_down norm_attn norm_attn_out norm_mlp norm_mlp_out
    top:   embed [V, H], final_norm [H], head [H, V]

It exports what ``lib/arch.py`` lists (leaf table, walk, counts), and beside
them the bytes one serving step has to move (``step_hbm_bytes``), which
``metrics/loop_step_hbm_roofline.serve.py`` reads.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from reference import decoder as base
from reference.decoder import adamw_update, head_dim  # noqa: F401  (exported: lib/arch.py's list)

LAYER_NORMS = ("norm_attn", "norm_attn_out", "norm_mlp", "norm_mlp_out")


def passes(cfg: Dict[str, Any]) -> int:
    return int(cfg["total_ut_steps"])


top_leaves = base.top_leaves  # embed, final_norm, head: the decoder's


def layer_leaves(cfg: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Eleven leaves: the decoder's seven matrices and four norms. Every layer
    has the same, and every pass uses the same."""
    h = cfg["hidden_size"]
    matrices = {k: v for k, v in base.layer_leaves(cfg, index).items() if v[1] == "normal"}
    return {**matrices, **{n: ((h,), "ones") for n in LAYER_NORMS}}


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """The seven matrices of one layer."""
    return sum(s[0] * s[1] for s, init in layer_leaves(cfg, 0).values() if init == "normal")


def matmul_params(cfg: Dict[str, Any], depth: int) -> int:
    """Weights a token passes through by matrix multiplication, counted once
    per pass through them: the layers ``total_ut_steps`` times, the head once."""
    return passes(cfg) * depth * layer_matmul_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    """Causal-attention calls, and so KV sets, a token makes: a layer a pass."""
    return passes(cfg) * depth


def step_hbm_bytes(cfg: Dict[str, Any], depth: int, passes_run: float, live_tokens: float,
                   weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes ONE serving step has to move: the layers' matrices once per pass
    that was run, the head once, and every live token's keys and values once
    per attention call (``lib/flops.py``'s count of one call). Norm leaves,
    activations and the embedding rows are left out (they are thousands of
    times smaller), so a step that moved only this would read 100 %."""
    from lib import flops

    per_pass = depth * layer_matmul_params(cfg) * weight_bytes
    head = cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    kv = passes_run * depth * flops.paged_attention_bytes(cfg, live_tokens, kv_bytes)
    return passes_run * per_pass + head + kv


def decoder_layer(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
                  lower: Optional[str] = None) -> jax.Array:
    """One sandwich-norm block on one sequence ``h [T, H]`` at positions ``0..T-1``."""
    t, eps = h.shape[0], cfg["rms_norm_eps"]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    pos = jnp.arange(t)
    x = base.rms_norm(h, w["norm_attn"], eps)
    q = base.rope(base.matmul(x, w["wq"], lower).reshape(t, nh, hd), pos, cfg["rope_theta"])
    k = base.rope(base.matmul(x, w["wk"], lower).reshape(t, nkv, hd), pos, cfg["rope_theta"])
    v = base.matmul(x, w["wv"], lower).reshape(t, nkv, hd)
    a = base.attention(q, k, v, lower).reshape(t, nh * hd)
    h = h + base.rms_norm(base.matmul(a, w["wo"], lower), w["norm_attn_out"], eps)
    x = base.rms_norm(h, w["norm_mlp"], eps)
    gate = base.matmul(x, w["w_gate"], lower)
    up = base.matmul(x, w["w_up"], lower)
    return h + base.rms_norm(base.matmul(jax.nn.silu(gate) * up, w["w_down"], lower), w["norm_mlp_out"], eps)


def close_pass(h: jax.Array, top: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    """The final norm, which ends every pass."""
    return base.rms_norm(h, top["final_norm"], cfg["rms_norm_eps"])


def forward_logits(tokens: jax.Array, weights: Dict[str, Any], cfg: Dict[str, Any],
                   lower: Optional[str] = None) -> jax.Array:
    """Logits ``[T, V]`` of one sequence ``tokens [T]``; ``weights`` holds
    ``top`` and the list ``layers``."""
    h = base.embed(tokens, weights["top"]["embed"])
    for _ in range(passes(cfg)):
        for w in weights["layers"]:
            h = decoder_layer(h, w, cfg, lower)
        h = close_pass(h, weights["top"], cfg)
    return base.matmul(h, weights["top"]["head"], lower)


_layer_jit = jax.jit(decoder_layer, static_argnums=(2,))
_close_jit = jax.jit(close_pass, static_argnums=(2,))
_head_jit = jax.jit(lambda h, head: base.matmul(h, head))


def sequence_logits(token_seqs: Sequence[Any], top: Dict[str, jax.Array],
                    layer_weights: Callable[[int], Dict[str, jax.Array]], cfg: Dict[str, Any]) -> Iterator[jax.Array]:
    """The float32 logits ``[len(seq), V]`` of each sequence, one after the
    other. Every pass walks the layers once for all of them and asks for each
    layer's leaves again (``layer_weights(i)`` makes them from the seed), so
    one layer's float32 leaves are held at a time. ``top`` holds its leaves in
    the type they are served in."""
    cfg = base._Frozen(cfg)
    hidden = [base.embed(jnp.asarray(toks), top["embed"]) for toks in token_seqs]
    for _ in range(passes(cfg)):
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(i)
            hidden = [_layer_jit(h, w, cfg) for h in hidden]
            del w
        hidden = [_close_jit(h, top, cfg) for h in hidden]
    for h in hidden:
        yield _head_jit(h, top["head"])


def sequence_loss_sum(weights: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
                      cfg: Dict[str, Any], lower: Optional[str] = None) -> jax.Array:
    """Sum over positions of the LAST pass's cross entropy of one sequence."""
    logits = forward_logits(tokens, weights, cfg, lower)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


_loss_and_grads_jit = jax.jit(jax.value_and_grad(sequence_loss_sum), static_argnums=(3, 4))


def batch_loss_and_grads(weights: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
                         cfg: Dict[str, Any], lower: Optional[str] = None):
    """Token-mean loss of the last pass and its gradients over a batch
    ``[B, T]``, a row at a time (a departure from the published objective: see
    the top of this file)."""
    frozen = base._Frozen(cfg)
    loss = jnp.zeros((), jnp.float32)
    grads = jax.tree_util.tree_map(jnp.zeros_like, weights)
    for row in range(tokens.shape[0]):
        row_loss, row_grads = _loss_and_grads_jit(weights, tokens[row], labels[row], frozen, lower)
        loss, grads = loss + row_loss, jax.tree_util.tree_map(jnp.add, grads, row_grads)
    n = tokens.shape[0] * tokens.shape[1]
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)
