"""Plain reference of a decoder whose attention is MULTI-HEAD LATENT and whose
MLPs, after ``first_k_dense_replace`` dense layers, are sparse-expert layers
with shared experts (DeepSeek-V2, ``model_type`` deepseek_v2). Pre-norm, RMSNorm
``RMS(x; g) = g x / sqrt(mean(x^2) + eps)``, no biases, untied head:

    x = E[tokens]
    for i in 0..L-1:   x = x + attn_i(RMS(x; g_attn));   x = x + mlp_i(RMS(x; g_mlp))
    logits = W_head RMS(x; g_final)

Attention, the MATERIALISED form (``H`` heads; ``nope``, ``rope``, ``v`` the three head sizes; ``r`` =
``kv_lora_rank``):
    c_q = RMS(u W_qa; g_q);   q = c_q W_qb  ->  H x (nope | rope)
    [c_kv | k_pe] = u W_kva  (r | rope);   c_kv = RMS(c_kv; g_kv);   k_pe is ONE key shared by all heads
    [k_nope | v] = c_kv W_kvb  ->  H x (nope | v)
    scores = (q_nope . k_nope + R(q_pe) . R(k_pe)) * s,  causal softmax, times v, then W_o
    R: the ``rope`` dims are de-interleaved, pairs (2j, 2j+1) -> (j, j + rope/2), then rotate-half at the
       YaRN frequencies: f_extra_i = theta^(-2i/rope), f_inter_i = f_extra_i / factor,
       cd(n) = rope ln(original / (2 pi n)) / (2 ln theta), low = max(floor(cd(beta_fast)), 0),
       high = min(ceil(cd(beta_slow)), rope - 1), ramp_i = clip((i - low) / (high - low), 0, 1),
       inv_freq_i = f_inter_i ramp_i + f_extra_i (1 - ramp_i);  cos and sin times m(factor, mscale) /
       m(factor, mscale_all_dim), m(f, a) = 0.1 a ln f + 1
    s = (nope + rope)^-0.5 x m(factor, mscale_all_dim)^2
Dense MLP (layer < ``first_k_dense_replace``): W_down (silu(W_gate u) * W_up u), width ``intermediate_size``.
Expert layer: p = softmax(W_g u) over ALL experts; the experts lie in ``n_group`` equal groups, a group's
    score is the largest p in it; the ``topk_group`` best groups are kept and p zeroed elsewhere; the
    ``num_experts_per_tok`` largest of what is left are chosen; weight = p of the chosen (NOT normalised) x
    ``routed_scaling_factor``; expert(u) = W_down (silu(W_gate u) * W_up u) of ``moe_intermediate_size``;
    out = sum_chosen w_e expert_e(u) + shared(u), shared ONE such MLP of ``n_shared_experts`` x that width.

THE SHARE, as ``reference/hybrid_ssm_moe.py``: ``n_routed_experts`` of the run
configuration is how many experts are HELD here, ``first_expert ..`` of the
``n_routed_experts_total`` the router scores. The router keeps its width, its
groups and its k; what an absent expert would have added is left out. With
every expert held this is the uncut layer.

No absorption, no cache, no batching: per-head keys and values are made from
``c_kv W_kvb`` for the whole sequence; attention runs in blocks of 256 queries
so that ``H x T^2`` scores never exist at once (the arithmetic of a row does
not depend on the block); the experts are a ``lax.scan`` over the held ones,
each over every token. Float32 ``jax.numpy`` at ``highest``; imports nothing
of the program under test; ``matmul`` and ``rms_norm`` are ``reference/decoder.py``'s.

Weights are dictionaries of arrays, matrices in the layout ``[in, out]``:

    every layer: norm_attn norm_mlp w_qa norm_q w_qb w_kva norm_kv w_kvb wo
    dense:       w_gate w_up w_down
    experts:     router [D, total]  expert_gate expert_up [held, D, I]  expert_down [held, I, D]  shared_gate shared_up shared_down
    top:         embed [V, D], final_norm [D], head [D, V]

It exports what ``lib/arch.py`` lists (leaf table, walk, counts), the least a
latent attention call has to do (``latent_attention_least``) and the least a
serving step has to move and compute (``step_least``), which
``metrics/latent_attn_roofline.py`` and ``metrics/latent_step_roofline.serve.py`` read.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Iterator, Sequence

import jax
import jax.numpy as jnp

from reference import decoder as base
from reference.decoder import adamw_update  # noqa: F401  (exported: lib/arch.py's list)

DENSE, EXPERTS = "D", "E"
QUERY_BLOCK = 256


def kind(cfg: Dict[str, Any], index: int) -> str:
    return DENSE if index < cfg["first_k_dense_replace"] else EXPERTS


def count(cfg: Dict[str, Any], depth: int, which: str) -> int:
    """Layers of one kind among the first ``depth``."""
    return sum(kind(cfg, i) == which for i in range(depth))


def router_width(cfg: Dict[str, Any]) -> int:
    """Experts the router scores: the published count, whatever share is held."""
    return int(cfg.get("n_routed_experts_total", cfg["n_routed_experts"]))


def head_dim(cfg: Dict[str, Any]) -> int:
    """A query head's width (``nope + rope``); a value head is ``v_head_dim``."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def latent_width(cfg: Dict[str, Any]) -> int:
    """What a token keeps a layer: the latent and the shared roped key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


# -- the leaf table ------------------------------------------------------------------

top_leaves = base.top_leaves  # embed, final_norm, head: the decoder's


def layer_leaves(cfg: Dict[str, Any], index: int) -> Dict[str, Any]:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    matrices = {
        "w_qa": (d, cfg["q_lora_rank"]), "w_qb": (cfg["q_lora_rank"], h * head_dim(cfg)),
        "w_kva": (d, latent_width(cfg)), "w_kvb": (r, h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        "wo": (h * cfg["v_head_dim"], d),
    }
    if kind(cfg, index) == DENSE:
        i = cfg["intermediate_size"]
        matrices.update(w_gate=(d, i), w_up=(d, i), w_down=(i, d))
    else:
        held, i = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        s = cfg["n_shared_experts"] * i
        matrices.update(router=(d, router_width(cfg)), expert_gate=(held, d, i), expert_up=(held, d, i), expert_down=(held, i, d),
                        shared_gate=(d, s), shared_up=(d, s), shared_down=(s, d))
    norms = {"norm_attn": d, "norm_mlp": d, "norm_q": cfg["q_lora_rank"], "norm_kv": r}
    return {**{n: (s, "normal") for n, s in matrices.items()}, **{n: ((w,), "ones") for n, w in norms.items()}}


# -- the counts ----------------------------------------------------------------------

def _attention_params(cfg: Dict[str, Any]) -> int:
    """An attention block's matrices: W_kvb counts once, as the absorbed form multiplies through it."""
    return sum(math.prod(s) for n, (s, _i) in layer_leaves(cfg, 0).items() if n in ("w_qa", "w_qb", "w_kva", "w_kvb", "wo"))


def _expert_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _always_params(cfg: Dict[str, Any], which: str) -> int:
    """Matrices of a layer that every row multiplies through: all of a dense layer's, and of an expert
    layer's the attention block, the router and the shared experts."""
    d = cfg["hidden_size"]
    if which == DENSE:
        return _attention_params(cfg) + 3 * d * cfg["intermediate_size"]
    return _attention_params(cfg) + d * router_width(cfg) + cfg["n_shared_experts"] * _expert_params(cfg)


def matmul_params(cfg: Dict[str, Any], depth: int) -> float:
    """Weights a token passes through by matrix multiplication: each layer's attention block and MLP (of an
    expert layer's ``k`` chosen experts those held here IN EXPECTATION, ``k x held / total``: a uniform
    router; the chosen experts that live on other chips are no work of this chip) and the head."""
    chosen_here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)
    per = {DENSE: _always_params(cfg, DENSE), EXPERTS: _always_params(cfg, EXPERTS) + chosen_here * _expert_params(cfg)}
    return sum(per[k] * count(cfg, depth, k) for k in per) + cfg["hidden_size"] * cfg["vocab_size"]


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    """Causal-attention calls, and so latent sets, a token makes: one a layer."""
    return depth


def latent_attention_least(cfg: Dict[str, Any], row_keys: float, live_tokens: float, value_bytes: int = 2) -> Dict[str, float]:
    """The least ONE latent attention call has to do, whatever implements it (absorbed or materialised
    cost the same per pair at these widths: ``(r + rope) + r`` against ``(nope + rope) + v`` plus the
    per-token up-projection): for each of ``row_keys`` (query row, visible key) pairs every head scores the
    row (``r + rope`` multiply-adds) and weighs its value (``r``); and each of ``live_tokens`` cached
    rows leaves HBM once (``r + rope`` values; the padding lanes are no work)."""
    r, w = cfg["kv_lora_rank"], latent_width(cfg)
    return {"flops": 2.0 * row_keys * cfg["num_attention_heads"] * (w + r), "bytes": live_tokens * w * value_bytes}


def step_least(cfg: Dict[str, Any], depth: int, rows: float, row_keys: float, live_tokens: float,
               experts_hit: float, weight_bytes: int = 2, value_bytes: int = 2) -> Dict[str, float]:
    """The least ONE serving step has to do. Bytes: every held matrix once (attention blocks, dense MLP,
    routers, shared experts, head), a routed expert ONLY where it got a row (``experts_hit``: held experts
    with at least one row, summed over the expert layers), each live latent row once a set, and the step's
    ``rows`` embedding rows. Flops: two per matrix weight a row multiplies through (``matmul_params``: a
    routed expert for its expected share of the rows) and the attention calls' (``latent_attention_least``).
    Norms, rotary rows and activations are left out (thousands of times smaller)."""
    d = cfg["hidden_size"]
    weights = sum(_always_params(cfg, k) * count(cfg, depth, k) for k in (DENSE, EXPERTS)) + d * cfg["vocab_size"]
    weights += experts_hit * _expert_params(cfg)
    attn = latent_attention_least(cfg, row_keys, live_tokens, value_bytes)
    sets = attention_passes(cfg, depth)
    return {"bytes": weights * weight_bytes + sets * attn["bytes"] + rows * d * weight_bytes,
            "flops": 2.0 * matmul_params(cfg, depth) * rows + sets * attn["flops"]}


# -- the rotary table ------------------------------------------------------------------

def yarn_mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn_inv_freq(cfg: Dict[str, Any]) -> jax.Array:
    """``inv_freq [rope / 2]`` by the equations at the top, in float64 and rounded once (a position of
    8000 times a frequency one float32 step off is an angle 1e-3 off)."""
    sc, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def cd(turns: float) -> float:
        return dim * math.log(sc["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(cd(sc["beta_fast"])), 0), min(math.ceil(cd(sc["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        extra = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(extra / sc["factor"] * ramp + extra * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    sc = cfg["rope_scaling"]
    return head_dim(cfg) ** -0.5 * yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2


def rope(x: jax.Array, positions: jax.Array, cfg: Dict[str, Any]) -> jax.Array:
    """``x [T, ..., rope]`` at ``positions [T]``: de-interleave the pairs, then rotate-half."""
    sc = cfg["rope_scaling"]
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]  # [T, rope/2]
    m = yarn_mscale(sc["factor"], sc["mscale"]) / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    cos = (m * jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)).reshape(shape)
    sin = (m * jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)).reshape(shape)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)  # (2j, 2j+1) -> (j, j + rope/2)
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


# -- the parts, on one sequence u [T, D] (already normed) ---------------------------------

def mla(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    t, h = u.shape[0], cfg["num_attention_heads"]
    nope, rp, vd, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    q = base.matmul(base.rms_norm(base.matmul(u, w["w_qa"]), w["norm_q"], cfg["rms_norm_eps"]), w["w_qb"]).reshape(t, h, nope + rp)
    kv = base.matmul(u, w["w_kva"])
    c_kv = base.rms_norm(kv[:, :r], w["norm_kv"], cfg["rms_norm_eps"])
    k_pe = rope(kv[:, r:], pos, cfg)  # [T, rope]: one key for every head
    kvb = base.matmul(c_kv, w["w_kvb"]).reshape(t, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (t, h, rp))], axis=-1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, cfg)], axis=-1)

    def rows(start):
        """Causal attention of ``QUERY_BLOCK`` queries from ``start`` over every key."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=base.PRECISION) * softmax_scale(cfg)
        seen = (start + jnp.arange(block))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=base.PRECISION)

    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, h * vd)
    return base.matmul(out, w["wo"])


def swiglu_mlp(u: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    return base.matmul(jax.nn.silu(base.matmul(u, w_gate)) * base.matmul(u, w_up), w_down)


def route(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """``(chosen [T, k], weights [T, k])`` over the router's whole width, group-limited."""
    p = jax.nn.softmax(base.matmul(u, w["router"]), axis=-1)
    groups, per = cfg["n_group"], router_width(cfg) // cfg["n_group"]
    best = jnp.argsort(-jnp.max(p.reshape(-1, groups, per), axis=-1), axis=-1)[:, : cfg["topk_group"]]
    kept = jnp.sum(jax.nn.one_hot(best, groups), axis=1) > 0  # [T, groups]
    picked, chosen = jax.lax.top_k(jnp.where(jnp.repeat(kept, per, axis=1), p, 0.0), cfg["num_experts_per_tok"])
    return chosen, picked * cfg["routed_scaling_factor"]


def routed_part(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    """What the HELD experts add: one after the other, each over every token with its weight (0 where the
    token did not choose it)."""
    chosen, weights = route(u, w, cfg)
    first = int(cfg.get("first_expert", 0))

    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * swiglu_mlp(u, w_gate, w_up, w_down), None

    held = w["expert_up"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(held), w["expert_gate"], w["expert_up"], w["expert_down"]))
    return out


def feed_forward(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any], which: str) -> jax.Array:
    if which == DENSE:
        return swiglu_mlp(u, w["w_gate"], w["w_up"], w["w_down"])
    return routed_part(u, w, cfg) + swiglu_mlp(u, w["shared_gate"], w["shared_up"], w["shared_down"])


def block(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any], which: str) -> jax.Array:
    """A layer whose MLP is of kind ``which``, on one sequence ``h [T, D]`` at positions ``0..T-1``."""
    h = h + mla(base.rms_norm(h, w["norm_attn"], cfg["rms_norm_eps"]), w, cfg)
    return h + feed_forward(base.rms_norm(h, w["norm_mlp"], cfg["rms_norm_eps"]), w, cfg, which)


def forward_logits(tokens: jax.Array, weights: Dict[str, Any], cfg: Dict[str, Any]) -> jax.Array:
    """Logits ``[T, V]`` of one sequence ``tokens [T]``; ``weights`` holds ``top`` and the list ``layers``."""
    h = base.embed(tokens, weights["top"]["embed"])
    for i, w in enumerate(weights["layers"]):
        h = block(h, w, cfg, kind(cfg, i))
    return base.head_logits(h, weights["top"], cfg)


class _Frozen(base._Frozen):
    """``rope_scaling`` is a nested group: hashed by its items."""

    def __hash__(self) -> int:  # type: ignore[override]
        return hash((super().__hash__(), tuple(sorted(self["rope_scaling"].items()))))


_block_jit = jax.jit(block, static_argnums=(2, 3))  # by KIND, not by index: two programs a length
_head_jit = jax.jit(base.head_logits, static_argnums=(2,))


def _bucket(n: int) -> int:
    """The length a sequence is walked at: itself up to 1024 rows, the next multiple of 1024 above. The
    walk compiles one program a layer kind a LENGTH, and a run's sampled requests come padded to a dozen
    multiples of 256 up to 8448: nine lengths at most instead. Causal throughout, so the rows that are read
    do not see the padding."""
    return n if n <= 1024 else -(-n // 1024) * 1024


def sequence_logits(token_seqs: Sequence[Any], top: Dict[str, jax.Array],
                    layer_weights: Callable[[int], Dict[str, jax.Array]], cfg: Dict[str, Any]) -> Iterator[jax.Array]:
    """The float32 logits ``[len(seq), V]`` of each sequence, one after the other. The layers are walked
    once for all of them; ``layer_weights(i)`` makes layer ``i``'s float32 leaves when asked, so one layer
    is held at a time. ``top`` holds its leaves in the type they are served in."""
    cfg = _Frozen(cfg)
    lengths = [len(toks) for toks in token_seqs]
    hidden = [base.embed(jnp.pad(jnp.asarray(toks), (0, _bucket(n) - n)), top["embed"]) for toks, n in zip(token_seqs, lengths)]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        hidden = [_block_jit(h, w, cfg, kind(cfg, i)) for h in hidden]
        del w
    for h, n in zip(hidden, lengths):
        yield _head_jit(h, top, cfg)[:n]


def batch_loss_and_grads(*_args: Any, **_kw: Any):
    raise NotImplementedError(
        "no cell trains this configuration: at 16 bytes a parameter the cut that serves (3.15 B parameters) "
        "does not fit a chip, and the program's 3-D expert leaves have not been through the optimizer")
