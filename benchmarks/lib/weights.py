"""Seeded weights, made by the benchmark on the device, for the program and
for the reference alike.

Every leaf is a function of (seed, layer index, leaf name) alone, so the
reference can ask for one layer at a time after the program has been freed and
gets bit-identical values to what the program was given in one jitted call.
Matrices are normal with the configuration's ``initializer_range``; norm
weights are ones. Values are drawn in float32 and rounded once to ``dtype``
(the type they are served or trained in); the reference upcasts those same
rounded values.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_NORMS = ("norm_attn", "norm_mlp")
TOP_MATRICES = ("embed", "head")


def shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    return {
        "wq": (h, nh * hd), "wk": (h, nkv * hd), "wv": (h, nkv * hd), "wo": (nh * hd, h),
        "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h),
        "norm_attn": (h,), "norm_mlp": (h,),
        "embed": (v, h), "head": (h, v), "final_norm": (h,),
    }


def root_key(seed: int) -> jax.Array:
    """Any whole number up to and beyond 2**31 (the driver's seeds are large)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key: jax.Array, name: str, index: int, shape: Tuple[int, ...], std: float, dtype: Any):
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, index), sum(map(ord, name)) * 131 + len(name))
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _layer(key, index, cfg_items, dtype):
    cfg = dict(cfg_items)
    s = shapes(cfg)
    return {n: _leaf(key, n, index + 1, s[n], cfg["initializer_range"], dtype)
            for n in LAYER_MATRICES + LAYER_NORMS}


def _top(key, cfg_items, dtype):
    cfg = dict(cfg_items)
    s = shapes(cfg)
    return {n: _leaf(key, n, 0, s[n], cfg["initializer_range"], dtype)
            for n in TOP_MATRICES + ("final_norm",)}


def _items(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float)) and not isinstance(v, bool)))


@functools.partial(jax.jit, static_argnames=("cfg_items", "depth", "dtype"))
def _all(key, cfg_items, depth, dtype):
    return {"top": _top(key, cfg_items, dtype),
            "layers": [_layer(key, i, cfg_items, dtype) for i in range(depth)]}


_layer_jit = jax.jit(_layer, static_argnames=("index", "cfg_items", "dtype"))
_top_jit = jax.jit(_top, static_argnames=("cfg_items", "dtype"))


def all_weights(seed: int, cfg: Dict[str, Any], depth: int, dtype: str) -> Dict[str, Any]:
    """The whole model in ONE jitted call: ``{"top": {...}, "layers": [...]}``."""
    return _all(root_key(seed), _items(cfg), depth, jnp.dtype(dtype).name)


def layer_weights(seed: int, cfg: Dict[str, Any], index: int, dtype: str) -> Dict[str, jax.Array]:
    return _layer_jit(root_key(seed), index, _items(cfg), jnp.dtype(dtype).name)


def top_weights(seed: int, cfg: Dict[str, Any], dtype: str) -> Dict[str, jax.Array]:
    return _top_jit(root_key(seed), _items(cfg), jnp.dtype(dtype).name)
