"""Seeded weights, made by the benchmark on the device, for the program and
for the reference alike.

WHICH leaves a model has is the reference file's to say (``lib/arch.py``): its
``top_leaves(cfg)`` and ``layer_leaves(cfg, index)`` give ``{leaf: (shape,
init)}``, where ``init`` is ``"normal"`` (std ``initializer_range``),
``"ones"``, ``"zeros"`` or a function ``(key, shape) -> float32 array`` of the
reference file's own. Nothing here names a leaf.

Every leaf is a function of (seed, group index, leaf name) alone, so the
reference can ask for one layer at a time after the program has been freed and
gets bit-identical values to what the program was given in one jitted call.
Values are drawn in float32 and rounded once to ``dtype`` (the type they are
served or trained in); the reference upcasts those same rounded values.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp

from . import arch

Init = Union[str, Callable[[jax.Array, Tuple[int, ...]], jax.Array]]
Table = Tuple[Tuple[str, Tuple[int, ...], Init], ...]


def root_key(seed: int) -> jax.Array:
    """Any whole number up to and beyond 2**31 (the driver's seeds are large)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def leaf_key(name: str) -> int:
    """What a leaf's name folds into its group's key. Anagrams share it."""
    return sum(map(ord, name)) * 131 + len(name)


def table(leaves: Dict[str, Tuple[Tuple[int, ...], Init]]) -> Table:
    """One group's leaves as jit can take them (hashable, sorted). Raises where
    two leaves would draw the same values, or an ``init`` is none of the four."""
    by_key: Dict[int, str] = {}
    for name, (_shape, init) in leaves.items():
        other = by_key.setdefault(leaf_key(name), name)
        if other != name:
            raise ValueError(f"leaves {other!r} and {name!r} of one group get the same random key "
                             "(names that are anagrams of each other do): rename one")
        if not (init in ("normal", "ones", "zeros") or callable(init)):
            raise ValueError(f"leaf {name!r}: init {init!r} is not 'normal', 'ones', 'zeros' or a function")
    return tuple((name, tuple(int(n) for n in shape), init) for name, (shape, init) in sorted(leaves.items()))


def _top(cfg: Dict[str, Any]) -> Tuple[int, Table]:
    """A group as ``_groups`` takes it, ``(group index, table)``: the top is group 0."""
    return 0, table(arch.reference(cfg).top_leaves(cfg))


def _layer(cfg: Dict[str, Any], index: int) -> Tuple[int, Table]:
    """Layer ``index`` is group ``index + 1``."""
    return index + 1, table(arch.reference(cfg).layer_leaves(cfg, index))


def _leaf(key: jax.Array, name: str, shape: Tuple[int, ...], init: Init, std: Any, dtype: Any) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, leaf_key(name))
    if init == "normal":
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    return init(k, shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("groups", "std", "dtype"))
def _groups(key, groups, std, dtype):
    """ONE call for as many groups as are asked for."""
    out = []
    for index, leaves in groups:
        group_key = jax.random.fold_in(key, index)
        out.append({name: _leaf(group_key, name, shape, init, std, dtype) for name, shape, init in leaves})
    return out


def _make(seed: int, cfg: Dict[str, Any], groups: Any, dtype: str) -> Any:
    return _groups(root_key(seed), tuple(groups), cfg.get("initializer_range"), jnp.dtype(dtype).name)


def all_weights(seed: int, cfg: Dict[str, Any], depth: int, dtype: str) -> Dict[str, Any]:
    """The whole model in ONE jitted call: ``{"top": {...}, "layers": [...]}``.
    The layers come first and the top last in that call, as they always have:
    the order decides where the program's weights lie in device memory, and
    the other order read ``itl_p95_ms`` 0.5 % lower and ``train_tokens_per_s``
    0.12 % higher in every pair on the chip (PERF.md, PR 25)."""
    *layers, top = _make(seed, cfg, [_layer(cfg, i) for i in range(depth)] + [_top(cfg)], dtype)
    return {"top": top, "layers": layers}


def layer_weights(seed: int, cfg: Dict[str, Any], index: int, dtype: str) -> Dict[str, jax.Array]:
    return _make(seed, cfg, [_layer(cfg, index)], dtype)[0]


def top_weights(seed: int, cfg: Dict[str, Any], dtype: str) -> Dict[str, jax.Array]:
    return _make(seed, cfg, [_top(cfg)], dtype)[0]
