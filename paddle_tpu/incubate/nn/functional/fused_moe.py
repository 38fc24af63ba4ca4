"""Fused dropless MoE over ``lax.ragged_dot``.

Reference: the fused MoE kernel family
(``paddle/phi/kernels/fusion/gpu/fused_moe_kernel.cu``, exposed as
``paddle.incubate.nn.functional.fused_moe``): gate → top-k → grouped expert
GEMMs → weighted combine, with no [E, C, M] capacity buffer.

TPU-native mechanics: tokens are sorted by expert id and the two expert FFN
GEMMs run as ``jax.lax.ragged_dot`` — the Mosaic grouped-matmul primitive
that keeps the MXU busy across experts of unequal load. Dropless: every
token reaches its experts (group sizes are data-dependent, shapes stay
static at T*K). The gather/sort/scatter bookkeeping is XLA-fused around the
two ragged GEMMs.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import call_op
from paddle_tpu.core.tensor import Tensor

__all__ = [
    "collect_expert_counts", "expert_share", "fused_moe", "route_sigmoid_topk", "route_softmax_group_limited",
    "share_of_routed",
]

# jax.named_scope names inside expert_share (the model's ``moe`` scope is around them)
SCOPE_MOE_ROUTER = "moe_router"
SCOPE_MOE_DISPATCH = "moe_dispatch"
SCOPE_MOE_EXPERTS = "moe_experts"
SCOPE_MOE_COMBINE = "moe_combine"

# rows an expert may get in the programs a layer is compiled for, smallest
# first; ``T`` (every row) always closes the list, so nothing is dropped.
# Each pads every held expert's rows to the cap and runs ONE batched matmul
# ``[held, cap, M] x [held, M, I]``, which reads an expert's weights once and
# in the layout they are stored in. ``lax.ragged_dot`` was measured here first
# (PERF.md, PR 33): XLA tiles it at min(rows, 512) x 128 x 128, so 16 experts of
# one or two rows cost 1.2 ms a matmul in tile overhead, and it wants the
# weights in another layout, a 160 MB copy a layer a step.
# The first cap is the one a serving step is meant to ALWAYS take, so that a
# step costs the same whatever the routing and the mix of prefill and decode
# rows: a router favours some experts (at seeded weights a held expert draws
# 10-25 % of the rows, one block in eleven over half), so a cap of 16 rows was
# passed whenever a step held a few prefill chunks, and the inter-token tail
# moved with how many blocks of how many steps did (PERF.md, PR 33's second
# round). Where ONE expert overflows it (the block that sends half its rows to
# one expert), that expert multiplies every row and the others keep the first
# cap, at 0.1 ms more whatever its share; the caps after it are for two such
# experts in one block, so that they do not pay for every row at once.
EXPERT_CAPS = (64, 128, 256)

_COUNTS: List[List[jax.Array]] = []


@contextlib.contextmanager
def collect_expert_counts() -> Iterator[List[jax.Array]]:
    """While open, every :func:`expert_share` call appends ``int32[2]``:
    ``(assignments that landed on held experts, held experts that got at
    least one row)``. The serving step opens it around the model's forward
    and hands the sum back beside its argmaxes."""
    sink: List[jax.Array] = []
    _COUNTS.append(sink)
    try:
        yield sink
    finally:
        _COUNTS.pop()


def route_sigmoid_topk(
    x: jax.Array,  # [T, M]
    gate_w: jax.Array,  # [M, E] over ALL experts
    select_bias: jax.Array,  # [E]
    top_k: int,
    scale: float,
    norm_topk_prob: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores in float32; the ``top_k`` of ``score + select_bias`` are
    chosen; a chosen expert's weight is its score (WITHOUT the bias) over the
    sum of all ``top_k`` chosen scores, times ``scale``. ``(chosen [T, K]
    int32, weights [T, K] float32)``."""
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), picked * scale


def route_softmax_group_limited(
    x: jax.Array,  # [T, M]
    gate_w: jax.Array,  # [M, E] over ALL experts
    top_k: int,
    scale: float,
    n_group: int,
    topk_group: int,
) -> Tuple[jax.Array, jax.Array]:
    """Softmax scores in float32 over all ``E`` experts, which lie in
    ``n_group`` groups of ``E / n_group`` (a group is a device's experts); a
    group's score is its best expert's; the ``topk_group`` best groups are
    kept and the scores of the others zeroed; the ``top_k`` best of what is
    left are chosen, and a chosen expert's weight is its score, NOT normalised
    over the chosen, times ``scale`` (``group_limited_greedy``). ``(chosen [T,
    K] int32, weights [T, K] float32)``."""
    t, e = x.shape[0], gate_w.shape[1]
    scores = jax.nn.softmax(jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, groups = jax.lax.top_k(jnp.max(scores.reshape(t, n_group, e // n_group), axis=-1), topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)  # [T, G]
    picked, chosen = jax.lax.top_k(jnp.where(jnp.repeat(kept, e // n_group, axis=1), scores, 0.0), top_k)
    return chosen.astype(jnp.int32), picked * scale


def expert_share(
    x: jax.Array,  # [T, M]
    gate_w: jax.Array,  # [M, E_total]: the router over ALL experts
    select_bias: jax.Array,  # [E_total]
    w_up: jax.Array,  # [held, M, I]: experts first_expert .. first_expert + held - 1
    w_down: jax.Array,  # [held, I, M]
    top_k: int,
    scale: float,
    first_expert: int = 0,
    norm_topk_prob: bool = True,
    row_mask: Optional[jax.Array] = None,  # [T] bool: rows that are real (None: all)
    expert_caps: Sequence[int] = EXPERT_CAPS,
) -> jax.Array:
    """This chip's share of a sparse-expert layer whose router is
    :func:`route_sigmoid_topk` and whose experts are non-gated squared-ReLU:
    the routing, then :func:`share_of_routed`."""
    with jax.named_scope(SCOPE_MOE_ROUTER):
        chosen, weights = route_sigmoid_topk(x, gate_w, select_bias, top_k, scale, norm_topk_prob)
    return share_of_routed(x, chosen, weights, w_up, w_down, first_expert, row_mask, expert_caps)


def share_of_routed(
    x: jax.Array,  # [T, M]
    chosen: jax.Array,  # [T, K] int32: the experts each row chose, of ALL the router scores
    weights: jax.Array,  # [T, K] float32
    w_up: jax.Array,  # [held, M, I]: experts first_expert .. first_expert + held - 1
    w_down: jax.Array,  # [held, I, M]
    first_expert: int = 0,
    row_mask: Optional[jax.Array] = None,  # [T] bool: rows that are real (None: all)
    expert_caps: Sequence[int] = EXPERT_CAPS,
    w_gate: Optional[jax.Array] = None,  # [held, M, I]: gated experts, silu(x W_gate) * (x W_up)
) -> jax.Array:
    """This chip's share of a sparse-expert layer, the routing given as data
    (every row routed over ALL experts): compute the part of the result that
    the HELD experts give, leave out what the absent ones would have added (on
    one chip there is no exchange; the weights are what the router gave,
    normalised or not over all the chosen). An expert is ``W_down
    relu(W_up x)^2``, or with ``w_gate`` the gated ``W_down (silu(W_gate x) *
    W_up x)``. An assignment to an absent expert, or of a row ``row_mask``
    rules out (a padded slot, a row past ``q_lens``), goes to a null group
    past the last and takes up no row of any expert. Dropless; routing is data
    (``lax.switch`` picks the first of ``expert_caps`` if no held expert
    overflows it, the same with the one favoured expert on every row if only
    that one does, else the smallest cap that holds), so one compiled program
    serves every mix."""
    t, m = x.shape
    held, top_k = w_up.shape[0], chosen.shape[1]

    def activated(h, rows, gate, mm):
        """The expert's nonlinearity on its up-projection ``h`` of ``rows``."""
        return jnp.square(jax.nn.relu(h)) if gate is None else jax.nn.silu(mm(rows, gate.astype(x.dtype))) * h

    with jax.named_scope(SCOPE_MOE_DISPATCH):
        local = chosen - first_expert
        here = (local >= 0) & (local < held)
        if row_mask is not None:
            here = here & row_mask[:, None]
        group = jnp.where(here, local, held).reshape(-1)  # [T*K]; `held` is the null group
        order = jnp.argsort(group)  # stable: held groups first, in expert order
        token = (order // top_k).astype(jnp.int32)
        weight = jnp.where(here, weights, 0.0).reshape(-1)[order]
        # a compare and a sum, not a bincount: a scatter of T*K scalars is slow on the TPU
        group_sizes = jnp.sum(group[:, None] == jnp.arange(held, dtype=group.dtype)[None, :], axis=0, dtype=jnp.int32)
        n_here = jnp.sum(group_sizes)
    if _COUNTS:
        _COUNTS[-1].append(jnp.stack([n_here, jnp.sum(group_sizes > 0).astype(jnp.int32)]))

    def padded(cap: int, favoured_takes_every_row: bool = False):
        """Every held expert's rows padded to ``cap`` (no expert has more).
        With ``favoured_takes_every_row`` the held expert with the most rows is
        left out of the padding and multiplies EVERY row instead, weighted 0
        where a row did not choose it (one ``[T, M] x [M, I]`` pair: a fortieth
        of the batched matmul's weight reads more, whatever its share)."""
        def tier(_):
            sizes = group_sizes
            if favoured_takes_every_row:
                favoured = jnp.argmax(group_sizes)
                sizes = jnp.where(jnp.arange(held) == favoured, 0, group_sizes)
            with jax.named_scope(SCOPE_MOE_DISPATCH):
                # expert e's c-th row is sorted assignment starts[e] + c, where it has that many
                starts = jnp.cumsum(group_sizes) - group_sizes
                column = jnp.arange(cap, dtype=jnp.int32)[None, :]
                real = (column < sizes[:, None]).reshape(-1)
                at = jnp.minimum(starts[:, None] + column, t * top_k - 1).reshape(-1)
                rows = jnp.where(real, token[at], t)  # t: a row of zeros
                row_weight = jnp.where(real, weight[at], 0.0)
                gathered = jnp.concatenate([x, jnp.zeros((1, m), x.dtype)])[rows].reshape(held, cap, m)
            with jax.named_scope(SCOPE_MOE_EXPERTS):
                batched = lambda a, w: jnp.einsum("ecm,emi->eci", a, w)  # noqa: E731
                h = batched(gathered, w_up.astype(x.dtype))
                out = jnp.einsum("eci,eim->ecm", activated(h, gathered, w_gate, batched), w_down.astype(x.dtype))
                if favoured_takes_every_row:
                    h = jnp.matmul(x, w_up[favoured].astype(x.dtype))
                    its_gate = None if w_gate is None else w_gate[favoured]
                    every_row = jnp.matmul(activated(h, x, its_gate, jnp.matmul), w_down[favoured].astype(x.dtype))
            with jax.named_scope(SCOPE_MOE_COMBINE):
                out = out.reshape(held * cap, m).astype(jnp.float32) * row_weight[:, None]
                out = jnp.zeros((t + 1, m), jnp.float32).at[rows].add(out)[:t]
                if favoured_takes_every_row:
                    its_weight = jnp.sum(jnp.where(here & (local == favoured), weights, 0.0), axis=-1)
                    out = out + every_row.astype(jnp.float32) * its_weight[:, None]
                return out.astype(x.dtype)
        return tier

    caps = sorted({min(int(c), t) for c in expert_caps} | {t})  # an expert gets at most every row
    if len(caps) == 1:
        return padded(caps[0])(None)
    # the first cap; past it the first cap again with the ONE favoured expert on
    # every row, if no second expert overflows it; else the smallest cap that holds
    largest, second = jnp.max(group_sizes), (jnp.sort(group_sizes)[-2] if held > 1 else 0)
    tiers = [padded(caps[0]), padded(caps[0], favoured_takes_every_row=True)] + [padded(c) for c in caps[1:]]
    beyond = 1 + jnp.sum(largest > jnp.asarray(caps[:-1], jnp.int32))
    return jax.lax.switch(jnp.where(largest <= caps[0], 0, jnp.where(second <= caps[0], 1, beyond)), tiers, None)


def _fused_moe_impl(
    x: jnp.ndarray,  # [T, M]
    gate_w: jnp.ndarray,  # [M, E]
    ffn1_w: jnp.ndarray,  # [E, M, H] (or [E, M, 2H] for swiglu)
    ffn2_w: jnp.ndarray,  # [E, H, M]
    top_k: int,
    norm_topk_prob: bool,
    activation: str,
) -> jnp.ndarray:
    t, m = x.shape
    e = gate_w.shape[1]
    logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)  # [T, K]
    if norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    flat_expert = topi.reshape(-1)  # [T*K]
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    flat_weight = topv.reshape(-1)

    order = jnp.argsort(flat_expert)  # stable grouping by expert
    tok_sorted = flat_token[order]
    w_sorted = flat_weight[order]
    gathered = x[tok_sorted]  # [T*K, M]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    h = jax.lax.ragged_dot(gathered, ffn1_w.astype(x.dtype), group_sizes)
    if activation == "swiglu":
        half = h.shape[-1] // 2
        h = jax.nn.silu(h[:, :half]) * h[:, half:]
    elif activation == "gelu":
        h = jax.nn.gelu(h, approximate=False)  # erf-exact, paddle default
    elif activation == "relu":
        h = jax.nn.relu(h)
    else:
        raise ValueError(f"unsupported activation {activation!r}")
    out = jax.lax.ragged_dot(h, ffn2_w.astype(x.dtype), group_sizes)  # [T*K, M]

    out = out * w_sorted[:, None].astype(out.dtype)
    y = jnp.zeros((t, m), out.dtype).at[tok_sorted].add(out)
    return y


def fused_moe(
    x: Any,
    gate_weight: Any,
    ffn1_weight: Any,
    ffn2_weight: Any,
    moe_topk: int = 2,
    norm_topk_prob: bool = True,
    activation: str = "swiglu",
) -> Tensor:
    """Dropless fused MoE (reference ``fused_moe``): tokens ``[T, M]`` or
    ``[B, S, M]``; ``ffn1_weight [E, M, H or 2H]``, ``ffn2_weight [E, H, M]``.
    Differentiable through the eager tape."""
    xt = x if isinstance(x, Tensor) else Tensor(x)
    lead = None
    if len(xt.shape) == 3:
        lead = tuple(xt.shape[:2])
        xt = xt.reshape([lead[0] * lead[1], xt.shape[-1]])

    def fn(xa, gw, w1, w2):
        return _fused_moe_impl(
            xa, gw, w1, w2, int(moe_topk), bool(norm_topk_prob), activation
        )

    out = call_op("fused_moe", fn, xt, gate_weight, ffn1_weight, ffn2_weight)
    if lead is not None:
        out = out.reshape([lead[0], lead[1], out.shape[-1]])
    return out
