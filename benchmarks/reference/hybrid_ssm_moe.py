"""Plain reference of a HYBRID decoder whose blocks are each ONE pre-norm and
ONE mixer (NVIDIA Nemotron-H / Nemotron-3-Nano, ``model_type`` nemotron_h):
the mixer of block ``i`` is what ``hybrid_override_pattern[i]`` says, ``M`` a
Mamba-2 state-space layer, ``E`` a sparse-expert layer, ``*`` causal attention.
No block has both a mixer and an MLP. With RMS(x; g) = g * x / sqrt(mean(x^2)
+ eps) and ``D`` the hidden size:

    x = E[tokens]
    for i in 0..L-1:   x = x + mixer_i(RMS(x; g_i))
    logits = W_head RMS(x; g_final)

``M`` (H heads of P, G groups, state N; ``d_inner`` = H P, conv width ``d_inner
+ 2 G N``):
    [z | xBC | dt] = W_in u
    xBC = silu(conv1d_causal(xBC, kernel K, depthwise) + b_conv)  ->  x [H, P], B [G, N], C [G, N]
    dt = softplus(dt + dt_bias),  A = -exp(A_log)                   (head h uses group h // (H / G))
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t       S [H, P, N], S_{-1} = 0
    out = W_out (g_gate * rmsnorm_{d_inner / G}(y * silu(z)))       (the norm over groups of d_inner / G)
``*``: q, k, v = W_q u, W_k u, W_v u; causal softmax(q k^T / sqrt(head_dim)) v; W_o. NO positional encoding.
``E``: s = sigmoid(W_g u) (float32); the top k of s + b_sel are chosen; weights are s at the chosen (without
    b_sel) over their sum over ALL k chosen, times ``routed_scaling_factor``;
    expert(u) = W_down relu(W_up u)^2;  out = sum_chosen w_e expert_e(u) + shared(u), shared of the same form.

THE SHARE. ``n_routed_experts`` of the run configuration is how many experts
are HELD here, experts ``first_expert .. first_expert + held - 1`` of the
``n_routed_experts_total`` the router scores (the configuration file gives the
published count under that key). The router keeps its width and its k; what an
absent expert would have added is left out, here exactly as in the program, and
the normalisation still runs over all k chosen. With every expert held this is
the uncut layer.

The state-space walk is a per-token recurrence in a ``lax.scan`` (NOT the
chunked form the program uses) and the experts are a Python loop over the held
ones. Straightforward ``jax.numpy`` in float32 at ``highest``; no kernels, no
cache, no batching. Imports nothing of the program under test; ``matmul``,
``rms_norm`` and ``attention`` are ``reference/decoder.py``'s.

Departures from the published model, each also under the configuration file's
``assumed``: no positional encoding in attention (the family's code applies
none; ``rope_theta`` and ``partial_rotary_factor`` are unused); ``dt`` is not
clamped (``time_step_*`` shape the initialisation only); ``b_sel``
(``e_score_correction_bias``) is drawn small from the seed, ``A_log``,
``dt_bias`` and ``D`` by the family's initialisation (``init_*`` below);
``rescale_prenorm_residual`` is not applied.

Weights are dictionaries of arrays, matrices in the layout ``[in, out]``:

    M: norm w_in conv_w [K, conv] conv_b dt_bias a_log d_skip gate_norm w_out
    *: norm wq wk wv wo
    E: norm router [D, total] b_sel [total] w_up [held, D, I] w_down [held, I, D] shared_up shared_down
    top: embed [V, D], final_norm [D], head [D, V]

It exports what ``lib/arch.py`` lists (leaf table, walk, counts) and the bytes
one serving step has to move (``step_hbm_bytes``), which
``metrics/hybrid_step_hbm_roofline.serve.py`` reads.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Iterator, Sequence

import jax
import jax.numpy as jnp

from reference import decoder as base
from reference.decoder import adamw_update, head_dim  # noqa: F401  (exported: lib/arch.py's list)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def kind(cfg: Dict[str, Any], index: int) -> str:
    return cfg["hybrid_override_pattern"][index]


def count(cfg: Dict[str, Any], depth: int, which: str) -> int:
    return cfg["hybrid_override_pattern"][:depth].count(which)


def d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: Dict[str, Any]) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def router_width(cfg: Dict[str, Any]) -> int:
    """Experts the router scores: the published count, whatever share is held."""
    return int(cfg.get("n_routed_experts_total", cfg["n_routed_experts"]))


# -- the family's initialisation of the leaves that are no matrices ------------------

def init_conv(key: jax.Array, shape: Any) -> jax.Array:
    """A depthwise conv's default: uniform in +-1/sqrt(kernel) (weight ``[K, conv]`` and bias alike)."""
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def init_dt_bias(key: jax.Array, shape: Any) -> jax.Array:
    """``dt`` log-uniform in the published ``time_step_min..time_step_max`` (1e-3..1e-1), floored at
    ``time_step_floor``, through the inverse of softplus."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_a_log(key: jax.Array, shape: Any) -> jax.Array:
    """``A`` = -uniform(1, 16): with ``dt`` as above the decay a token lies in exp(-1.6)..exp(-0.001)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def init_d_skip(key: jax.Array, shape: Any) -> jax.Array:
    """The family starts ``D`` at ones; drawn around one so that a layer that ignores it shows."""
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)


def init_b_sel(key: jax.Array, shape: Any) -> jax.Array:
    """The trained selection bias is not public. Small beside the scores' spread (they are sigmoids of
    logits of unit size), large beside the gap between neighbouring scores: it changes choices."""
    return 0.05 * jax.random.normal(key, shape, jnp.float32)


# -- the leaf table ------------------------------------------------------------------

top_leaves = base.top_leaves  # embed, final_norm, head: the decoder's


def layer_leaves(cfg: Dict[str, Any], index: int) -> Dict[str, Any]:
    """The leaves of block ``index``: the pattern says which mixer it has."""
    d, k = cfg["hidden_size"], kind(cfg, index)
    norm = {"norm": ((d,), "ones")}
    if k == MAMBA:
        inner, conv, heads = d_inner(cfg), conv_dim(cfg), cfg["mamba_num_heads"]
        return {**norm,
                "w_in": ((d, inner + conv + heads), "normal"), "conv_w": ((cfg["conv_kernel"], conv), init_conv),
                "conv_b": ((conv,), init_conv), "dt_bias": ((heads,), init_dt_bias), "a_log": ((heads,), init_a_log),
                "d_skip": ((heads,), init_d_skip), "gate_norm": ((inner,), "ones"), "w_out": ((inner, d), "normal")}
    if k == ATTENTION:
        q, kv = cfg["num_attention_heads"] * head_dim(cfg), cfg["num_key_value_heads"] * head_dim(cfg)
        return {**norm, "wq": ((d, q), "normal"), "wk": ((d, kv), "normal"), "wv": ((d, kv), "normal"),
                "wo": ((q, d), "normal")}
    if k == EXPERTS:
        held, width, shared = cfg["n_routed_experts"], cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
        return {**norm, "router": ((d, router_width(cfg)), "normal"), "b_sel": ((router_width(cfg),), init_b_sel),
                "w_up": ((held, d, width), "normal"), "w_down": ((held, width, d), "normal"),
                "shared_up": ((d, shared), "normal"), "shared_down": ((shared, d), "normal")}
    raise ValueError(f"block {index}: unknown mixer {k!r} in hybrid_override_pattern")


# -- the counts ----------------------------------------------------------------------

def _mixer_matmul_params(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Weights a token multiplies through in one block of each kind. An expert block: the router, the
    shared expert, and of its ``k`` chosen experts those held here IN EXPECTATION (``k x held / total``: a
    uniform router; the chosen experts that live on other chips are no work of this chip)."""
    d = cfg["hidden_size"]
    q, kv = cfg["num_attention_heads"] * head_dim(cfg), cfg["num_key_value_heads"] * head_dim(cfg)
    expert = 2 * d * cfg["moe_intermediate_size"]
    chosen_here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)
    return {
        MAMBA: d * (d_inner(cfg) + conv_dim(cfg) + cfg["mamba_num_heads"]) + d_inner(cfg) * d,
        ATTENTION: 2 * d * q + 2 * d * kv,
        EXPERTS: d * router_width(cfg) + 2 * d * cfg["moe_shared_expert_intermediate_size"] + chosen_here * expert,
    }


def matmul_params(cfg: Dict[str, Any], depth: int) -> float:
    """Weights a token passes through by matrix multiplication: each block's mixer and the head."""
    per = _mixer_matmul_params(cfg)
    return sum(per[k] * count(cfg, depth, k) for k in per) + cfg["hidden_size"] * cfg["vocab_size"]


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    """Causal-attention calls, and so KV sets, a token makes: one per ``*`` block."""
    return count(cfg, depth, ATTENTION)


def state_bytes_per_slot(cfg: Dict[str, Any], state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """Recurrent state ONE ``M`` block keeps for one sequence: ``S [H, P, N]`` and the conv's tail of
    ``K - 1`` inputs."""
    ssm = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"] * state_bytes
    return ssm + (cfg["conv_kernel"] - 1) * conv_dim(cfg) * conv_bytes


def step_hbm_bytes(cfg: Dict[str, Any], depth: int, rows: float, slots_live: float, kv_tokens_live: float,
                   experts_hit: float, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes ONE serving step has to move, the least it can: every held matrix once (mixers, routers,
    shared experts, head), a routed expert ONLY where it got a row (``experts_hit``: held experts with at
    least one row, summed over the expert blocks), each live slot's recurrent state read and written in
    every ``M`` block, each live token's keys and values once per ``*`` block, and the step's ``rows``
    embedding rows. Norms, conv leaves and activations are left out (thousands of times smaller)."""
    from lib import flops

    d = cfg["hidden_size"]
    per = _mixer_matmul_params(cfg)
    always = {**per, EXPERTS: d * router_width(cfg) + 2 * d * cfg["moe_shared_expert_intermediate_size"]}
    weights = sum(always[k] * count(cfg, depth, k) for k in always) + d * cfg["vocab_size"]
    weights += experts_hit * 2 * d * cfg["moe_intermediate_size"]
    state = 2.0 * slots_live * count(cfg, depth, MAMBA) * state_bytes_per_slot(cfg)
    kv = attention_passes(cfg, depth) * flops.paged_attention_bytes(cfg, kv_tokens_live, kv_bytes)
    return weights * weight_bytes + state + kv + rows * d * weight_bytes


# -- the mixers, on one sequence u [T, D] (already normed) ------------------------------

def mamba_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    t = u.shape[0]
    heads, p, g, n, kern = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                            cfg["ssm_state_size"], cfg["conv_kernel"])
    inner, conv = d_inner(cfg), conv_dim(cfg)
    zxbcdt = base.matmul(u, w["w_in"])
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv], zxbcdt[:, inner + conv:]
    # causal depthwise conv: tap K-1 multiplies the current token, tap 0 the one K-1 before it
    padded = jnp.concatenate([jnp.zeros((kern - 1, conv), jnp.float32), xbc], axis=0)
    xbc = sum(padded[j:j + t] * w["conv_w"][j] for j in range(kern)) + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :inner].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), heads // g, axis=1)  # [T, H, N]
    c = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [T, H]
    a = -jnp.exp(w["a_log"])  # [H]

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t, precision=base.PRECISION) + w["d_skip"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32), (x, b, c, dt))
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return base.matmul(y.reshape(t, inner) * w["gate_norm"], w["w_out"])


def attention_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    t, nh, nkv, hd = u.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = base.matmul(u, w["wq"]).reshape(t, nh, hd)
    k = base.matmul(u, w["wk"]).reshape(t, nkv, hd)
    v = base.matmul(u, w["wv"]).reshape(t, nkv, hd)
    return base.matmul(base.attention(q, k, v).reshape(t, nh * hd), w["wo"])  # no rotary embedding


def relu2_mlp(u: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    return base.matmul(jnp.square(jax.nn.relu(base.matmul(u, w_up))), w_down)


def route(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """``(chosen [T, k], weights [T, k])`` over the router's whole width."""
    scores = jax.nn.sigmoid(base.matmul(u, w["router"]))
    _, chosen = jax.lax.top_k(scores + w["b_sel"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, picked * cfg["routed_scaling_factor"]


def routed_part(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    """What the HELD experts add: a loop over them, each over every token with its weight (0 where the
    token did not choose it)."""
    chosen, weights = route(u, w, cfg)
    first = int(cfg.get("first_expert", 0))
    out = jnp.zeros_like(u)
    for e in range(w["w_up"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        out = out + w_e[:, None] * relu2_mlp(u, w["w_up"][e], w["w_down"][e])
    return out


def experts_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    return routed_part(u, w, cfg) + relu2_mlp(u, w["shared_up"], w["shared_down"])


MIXERS = {MAMBA: mamba_mixer, ATTENTION: attention_mixer, EXPERTS: experts_mixer}


def block(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any], mixer: str) -> jax.Array:
    """A block whose mixer is of kind ``mixer``, on one sequence ``h [T, D]``."""
    return h + MIXERS[mixer](base.rms_norm(h, w["norm"], cfg["layer_norm_epsilon"]), w, cfg)


def head_logits(h: jax.Array, top: Dict[str, jax.Array], cfg: Dict[str, Any]) -> jax.Array:
    return base.matmul(base.rms_norm(h, top["final_norm"], cfg["layer_norm_epsilon"]), top["head"])


def forward_logits(tokens: jax.Array, weights: Dict[str, Any], cfg: Dict[str, Any]) -> jax.Array:
    """Logits ``[T, V]`` of one sequence ``tokens [T]``; ``weights`` holds ``top`` and the list ``layers``."""
    h = base.embed(tokens, weights["top"]["embed"])
    for i, w in enumerate(weights["layers"]):
        h = block(h, w, cfg, kind(cfg, i))
    return head_logits(h, weights["top"], cfg)


_block_jit = jax.jit(block, static_argnums=(2, 3))  # by KIND, not by index: three programs a length
_head_jit = jax.jit(head_logits, static_argnums=(2,))


def _bucket(n: int) -> int:
    """The length a sequence is walked at: itself up to 256 rows, the next power of two above. The walk
    compiles one program a mixer kind a LENGTH (the scan, the loop over the held experts), 5-10 s each on
    the chip, and a run's sampled requests come padded to a dozen multiples of 256: five lengths instead.
    Causal throughout, so the rows that are read do not see the padding."""
    return n if n <= 256 else 1 << (n - 1).bit_length()


def sequence_logits(token_seqs: Sequence[Any], top: Dict[str, jax.Array],
                    layer_weights: Callable[[int], Dict[str, jax.Array]], cfg: Dict[str, Any]) -> Iterator[jax.Array]:
    """The float32 logits ``[len(seq), V]`` of each sequence, one after the other. The blocks are walked
    once for all of them; ``layer_weights(i)`` makes block ``i``'s float32 leaves when asked, so one block
    is held at a time. ``top`` holds its leaves in the type they are served in."""
    cfg = base._Frozen(cfg)
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
        "no cell trains this configuration: the program's chunked scan has no hand-written backward and its "
        "3-D expert leaves have not been through the optimizer (ROADMAP M1 / M5)")
