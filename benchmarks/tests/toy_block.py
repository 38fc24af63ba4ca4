"""A block that is NOT ``reference/decoder.py``'s, for ``test_seam.py`` only: it
is no configuration and stands for no model. It has what the harness could not
take before the seam: four norms a layer (sandwich), a bias at the top made of
zeros, a 3-D leaf, a leaf set that differs between even and odd layers, a
``head_dim`` that is not ``hidden_size // heads``, an init of its own, and a
stack that is walked ``cfg["passes"]`` times with the top's norm after each
pass. It exports what ``lib/arch.py`` lists, as a reference file does."""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, Sequence

import jax
import jax.numpy as jnp

from reference import decoder as base


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["head_dim"]


def near_one(key: jax.Array, shape: Any) -> jax.Array:
    """An init neither normal nor constant: uniform in [0.9, 1.1)."""
    return 0.9 + 0.2 * jax.random.uniform(key, shape, jnp.float32)


def top_leaves(cfg: Dict[str, Any]) -> Dict[str, Any]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, h), "normal"), "final_norm": ((h,), "ones"), "head": ((h, v), "normal"),
            "head_bias": ((v,), "zeros")}


def layer_leaves(cfg: Dict[str, Any], index: int) -> Dict[str, Any]:
    h, i, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["experts"]
    q, kv = cfg["num_attention_heads"] * head_dim(cfg), cfg["num_key_value_heads"] * head_dim(cfg)
    leaves = {"wq": ((h, q), "normal"), "wk": ((h, kv), "normal"), "wv": ((h, kv), "normal"), "wo": ((q, h), "normal"),
              "gain": ((h,), near_one), **{f"norm{n}": ((h,), "ones") for n in (1, 2, 3, 4)}}
    if index % 2 == 0:  # even layers: experts, every one of them on every token
        return {**leaves, "experts_up": ((e, h, i), "normal"), "experts_down": ((e, i, h), "normal")}
    return {**leaves, "w_up": ((h, i), "normal"), "w_down": ((i, h), "normal")}


def layer(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    t, eps = h.shape[0], cfg["rms_norm_eps"]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    pos = jnp.arange(t)
    x = base.rms_norm(h, w["norm1"], eps)
    q = base.rope(base.matmul(x, w["wq"]).reshape(t, nh, hd), pos, cfg["rope_theta"])
    k = base.rope(base.matmul(x, w["wk"]).reshape(t, nkv, hd), pos, cfg["rope_theta"])
    v = base.matmul(x, w["wv"]).reshape(t, nkv, hd)
    a = base.attention(q, k, v).reshape(t, nh * hd)
    h = h + base.rms_norm(base.matmul(a, w["wo"]), w["norm2"], eps)
    x = base.rms_norm(h, w["norm3"], eps)
    if "experts_up" in w:
        y = sum(base.matmul(jax.nn.silu(base.matmul(x, up)), down) for up, down in zip(w["experts_up"], w["experts_down"]))
    else:
        y = base.matmul(jax.nn.silu(base.matmul(x, w["w_up"])), w["w_down"])
    return h + base.rms_norm(y, w["norm4"], eps) * w["gain"]


def sequence_logits(token_seqs: Sequence[Any], top: Dict[str, jax.Array],
                    layer_weights: Callable[[int], Dict[str, jax.Array]], cfg: Dict[str, Any]) -> Iterator[jax.Array]:
    hidden = [base.embed(jnp.asarray(toks), top["embed"]) for toks in token_seqs]
    for _pass in range(cfg["passes"]):
        for i in range(cfg["num_hidden_layers"]):  # a layer is asked for again in every pass
            w = layer_weights(i)
            hidden = [layer(h, w, cfg) for h in hidden]
        hidden = [base.rms_norm(h, top["final_norm"], cfg["rms_norm_eps"]) for h in hidden]
    for h in hidden:
        yield base.matmul(h, top["head"]) + top["head_bias"]


def matmul_params(cfg: Dict[str, Any], depth: int) -> int:
    """Counted once per pass through a matrix: the layers ``passes`` times, the head once."""
    per_layer = [sum(_size(shape) for shape, _init in layer_leaves(cfg, i).values() if len(shape) > 1)
                 for i in range(depth)]
    return cfg["passes"] * sum(per_layer) + cfg["hidden_size"] * cfg["vocab_size"]


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    return cfg["passes"] * depth


def _size(shape: Any) -> int:
    n = 1
    for d in shape:
        n *= d
    return n
