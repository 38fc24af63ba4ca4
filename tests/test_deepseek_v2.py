"""DeepSeek-V2 (``models/deepseek_v2.py``): multi-head latent attention over
``LatentKV`` pages and group-limited softmax-gated experts with shared experts,
held against its plain reference (``benchmarks/reference/mla_moe.py``: float32,
the MATERIALISED attention, a scan over the held experts, no cache) on seeded
random weights at a small size, through the plain forward and through the
serving engine (the ABSORBED attention over latent pages in one compiled step).

Everything runs in float32 on the CPU under matmul precision "highest"
(``conftest.py``), so program and reference differ only by the order of
float32 sums (absorbed against materialised, the grouped matmul against the
scan): logits of order 1 agree to a few 1e-6. ``TOL`` is 2e-5 of the largest
logit, far below what leaving out any leaf moves them by.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional.block_attention import _cow_copy_planes, _gather_latent_attend
from paddle_tpu.incubate.nn.functional.fused_moe import (
    collect_expert_counts,
    route_softmax_group_limited,
    share_of_routed,
)
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.paged_kv import PAGED, LatentKV, PagedBatch
from paddle_tpu.kernels.paged_attention import paged_latent_chunk
from paddle_tpu.models.deepseek_v2 import (
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
    rope_interleaved,
    yarn_cos_sin,
    yarn_inv_freq,
)
from paddle_tpu.serving import ServingFrontend

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import mla_moe as ref  # noqa: E402

TOL = 2e-5
VOCAB = 96
LEAF_OF = {  # reference leaf -> the program's parameter, inside a layer
    "norm_attn": "input_layernorm.weight", "norm_mlp": "post_attention_layernorm.weight",
    "w_qa": "self_attn.q_a_proj.weight", "norm_q": "self_attn.q_a_layernorm.weight", "w_qb": "self_attn.q_b_proj.weight",
    "w_kva": "self_attn.kv_a_proj_with_mqa.weight", "norm_kv": "self_attn.kv_a_layernorm.weight",
    "w_kvb": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight", "w_down": "mlp.down_proj.weight",
    "router": "mlp.gate.weight", "expert_gate": "mlp.experts.gate_proj", "expert_up": "mlp.experts.up_proj",
    "expert_down": "mlp.experts.down_proj", "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight", "shared_down": "mlp.shared_experts.down_proj.weight",
}


def build(seed=5, **kw):
    """A float32 model whose leaves are large enough that each one shows (norm weights not ones, a
    router whose scores differ by far more than float32 rounding)."""
    cfg = dataclasses.replace(DeepseekV2Config.tiny(vocab=VOCAB), **kw)
    paddle.seed(seed)
    model = DeepseekV2ForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.set_value(jnp.asarray(rng.uniform(0.6, 1.4, p.shape), jnp.float32))
        else:
            p.set_value(jnp.asarray(rng.normal(size=p.shape) * (0.5 if name.endswith("gate.weight") else 0.15), jnp.float32))
    return model


def ref_cfg(model):
    return dataclasses.asdict(model.config)


def ref_weights(model):
    """The program's parameters under the reference's leaf names (both keep a matrix as ``[in, out]``)."""
    p = {n: v._data for n, v in model.named_parameters()}
    cfg = ref_cfg(model)
    top = {"embed": p["model.embed_tokens.weight"], "final_norm": p["model.norm.weight"], "head": p["lm_head.weight"]}
    layers = [{leaf: p[f"model.layers.{i}.{LEAF_OF[leaf]}"] for leaf in ref.layer_leaves(cfg, i)}
              for i in range(model.config.num_hidden_layers)]
    return {"top": top, "layers": layers}


_ref_forward = jax.jit(ref.forward_logits, static_argnums=(2,))


def ref_logits(model, tokens):
    """The reference's logits of one sequence; padded to a multiple of 64 so that few lengths compile
    (causal: the padding changes no row that is read)."""
    padded = np.pad(np.asarray(tokens, np.int32), (0, -len(tokens) % 64))
    return np.asarray(_ref_forward(jnp.asarray(padded), ref_weights(model), ref._Frozen(ref_cfg(model))))[: len(tokens)]


def close(got, want):
    return np.abs(np.asarray(got) - want).max() < TOL * np.abs(want).max()


def served_gap(model, prompt, generated):
    """How far below the reference's best logit each served token lies (0: the reference's own argmax)."""
    seq = np.concatenate([prompt, np.asarray(generated, np.int32)])
    rows = ref_logits(model, seq)[len(prompt) - 1: len(prompt) - 1 + len(generated)]
    return rows.max(-1) - rows[np.arange(len(generated)), generated]


@pytest.fixture(scope="module")
def model():
    return build()


def engine(model, **kw):
    kw = {"max_slots": 3, "block_size": 16, "prompt_bucket": 64, "max_model_len": 128, **kw}
    return ContinuousBatchingEngine(model, **kw)


# -- the model --------------------------------------------------------------------------

def test_config_says_one_latent_set_a_layer_and_the_published_scale():
    cfg = DeepseekV2Config()
    sets = cfg.cache_sets
    assert len(sets) == 60 == cfg.num_kv_sets and all(cs.kind == PAGED and cs.owner is LatentKV for cs in sets)
    assert sets[0].planes == (((1, 640), "bfloat16"),)  # 512 + 64 padded to whole 128-lane tiles
    assert sets[0].unit_bytes == 1280
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and abs(cfg.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    with pytest.raises(ValueError, match="not among the router's"):
        DeepseekV2Config(n_routed_experts=20, n_routed_experts_total=160, first_expert=150)


def test_parameter_names_are_the_familys_and_every_leaf_is_made_in_the_dtype():
    paddle.seed(0)
    m = DeepseekV2ForCausalLM(dataclasses.replace(DeepseekV2Config.tiny(held=4, total=16), dtype="bfloat16"))
    params = dict(m.named_parameters())
    assert all(str(p._data.dtype) == "bfloat16" for p in params.values())  # made so, not cast: no float32 transient
    for name in ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight",
                 "model.layers.0.self_attn.q_a_proj.weight", "model.layers.0.self_attn.q_a_layernorm.weight",
                 "model.layers.0.self_attn.q_b_proj.weight", "model.layers.0.self_attn.kv_a_proj_with_mqa.weight",
                 "model.layers.0.self_attn.kv_a_layernorm.weight", "model.layers.0.self_attn.kv_b_proj.weight",
                 "model.layers.0.self_attn.o_proj.weight", "model.layers.0.mlp.gate_proj.weight",
                 "model.layers.1.mlp.gate.weight", "model.layers.1.mlp.shared_experts.down_proj.weight"):
        assert name in params, name
    assert params["model.layers.1.mlp.experts.gate_proj"].shape == [4, 64, 24]
    assert params["model.layers.1.mlp.experts.up_proj"].shape == [4, 64, 24]
    assert params["model.layers.1.mlp.experts.down_proj"].shape == [4, 24, 64]
    assert params["model.layers.1.mlp.gate.weight"].shape == [64, 16]  # the router scores all 16
    assert params["model.layers.1.mlp.shared_experts.gate_proj.weight"].shape == [64, 48]  # ONE MLP of 2 x 24
    assert "model.layers.0.mlp.gate.weight" not in params  # the leading layer is dense


def test_plain_forward_is_the_reference(model):
    ids = np.random.default_rng(0).integers(1, VOCAB, 50).astype(np.int32)
    with paddle.no_grad():
        got = model(Tensor(jnp.asarray(ids[None])))._data[0]
    assert close(got, ref_logits(model, ids))


def test_every_leaf_moves_the_logits(model):
    ids = np.random.default_rng(1).integers(1, VOCAB, 24).astype(np.int32)
    base = ref_logits(model, ids)
    for name, p in model.named_parameters():
        kept = p._data
        # not a rescaling: the norm after q_a_proj and kv_a_proj would undo one
        p.set_value(kept * 1.5 if kept.ndim == 1 else jnp.roll(kept, 1, axis=0))
        with paddle.no_grad():
            got = np.asarray(model(Tensor(jnp.asarray(ids[None])))._data[0])
        p.set_value(kept)
        assert np.abs(got - base).max() > 100 * TOL * np.abs(base).max(), name


# -- the rotary table -------------------------------------------------------------------

@pytest.mark.parametrize("position", [0, 7, 4095, 4096, 20000, 160000])
def test_the_yarn_table_is_the_formula_below_and_above_the_original_context(position):
    cfg = DeepseekV2Config()
    dim, theta, factor, original = 64, 10000.0, 40.0, 4096

    def cd(turns):
        return dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))

    low, high = max(math.floor(cd(32)), 0), min(math.ceil(cd(1)), dim - 1)
    want = []
    for i in range(dim // 2):
        extra = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    inv = yarn_inv_freq(64, 10000.0, cfg.rope_scaling)
    assert np.allclose(inv, want, rtol=1e-6)
    assert inv[0] == 1.0 and abs(inv[-1] - theta ** (-62 / 64) / 40) < 1e-9  # the fastest is kept, the slowest stretched 40 x
    cos, sin = yarn_cos_sin(jnp.asarray([position]), cfg)
    angles = np.float32(position) * np.asarray(want, np.float32)
    assert np.allclose(cos[0], np.cos(angles), atol=2e-3) and np.allclose(sin[0], np.sin(angles), atol=2e-3)
    got = np.asarray(ref.rope(jnp.ones((1, 64)), jnp.asarray([position]), ref_cfg_published()))
    assert np.allclose(got[0, :32], np.asarray(cos[0] - sin[0]), atol=1e-5)  # the reference's table is the program's


def ref_cfg_published():
    return dataclasses.asdict(DeepseekV2Config())


def test_rope_rotates_the_interleaved_pairs():
    """Pair (2j, 2j+1) is rotated by angle j and lands at (j, j + half): the published de-interleave."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    ang = jax.random.uniform(jax.random.PRNGKey(1), (5, 4))
    got = np.asarray(rope_interleaved(x, jnp.cos(ang), jnp.sin(ang)))
    for j in range(4):
        a, b, c, s = np.asarray(x[:, 2 * j]), np.asarray(x[:, 2 * j + 1]), np.cos(ang[:, j]), np.sin(ang[:, j])
        assert np.allclose(got[:, j], a * c - b * s, atol=1e-6) and np.allclose(got[:, j + 4], b * c + a * s, atol=1e-6)


# -- the expert layer ----------------------------------------------------------------------

MOE_CFG = {"hidden_size": 32, "moe_intermediate_size": 24, "n_shared_experts": 2, "n_routed_experts": 16,
           "n_routed_experts_total": 16, "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
           "routed_scaling_factor": 16.0, "first_expert": 0}


def moe_leaves(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    return {"router": n(keys[0], 32, 16) * 3, "expert_gate": n(keys[1], 16, 32, 24), "expert_up": n(keys[2], 16, 32, 24),
            "expert_down": n(keys[3], 16, 24, 32), "shared_gate": n(keys[4], 32, 48), "shared_up": n(keys[5], 32, 48), "shared_down": n(keys[6], 48, 32)}


def route(x, w):
    return route_softmax_group_limited(x, w["router"], 3, 16.0, 4, 2)


def share(x, w, lo=0, hi=16, **kw):
    chosen, weights = route(x, w)
    return share_of_routed(x, chosen, weights, w["expert_up"][lo:hi], w["expert_down"][lo:hi], first_expert=lo,
                           w_gate=w["expert_gate"][lo:hi], **kw)


def test_group_limited_routing_is_the_plain_loop():
    """Per token, in plain Python: softmax, the best expert of each of 4 groups, the 2 best groups, the
    3 best experts inside them, weights the scores themselves times 16 (they do not sum to 16)."""
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    chosen, weights = (np.asarray(a) for a in route(x, w))
    p = np.asarray(jax.nn.softmax(jnp.matmul(x, w["router"], precision="highest"), axis=-1), np.float64)
    outside = 0
    for t in range(64):
        best = sorted(range(4), key=lambda g: -p[t, 4 * g: 4 * g + 4].max())[:2]
        allowed = [e for e in range(16) if e // 4 in best]
        want = sorted(allowed, key=lambda e: -p[t, e])[:3]
        assert list(chosen[t]) == want
        assert np.allclose(weights[t], 16.0 * p[t, want], rtol=1e-5)
        outside += sorted(range(16), key=lambda e: -p[t, e])[:3] != want
    assert outside > 0  # some token's three best experts lie in a third group, and it does not get them
    assert (weights.sum(-1) < 16.0 - 1e-2).any() and (weights.sum(-1) <= 16.0).all()  # not normalised


def test_a_token_whose_best_experts_lie_in_a_fourth_group_does_not_reach_them():
    """Published shape: 8 groups, 3 kept. Experts 0, 20, 40 and 60 lead their groups; the token's fourth
    best expert, 61, beats every other expert of the three kept groups and is still not chosen."""
    logits = np.full((1, 160), -4.0, np.float32)
    logits[0, [0, 20, 40, 60, 61, 1, 21, 41]] = [5.0, 4.9, 4.8, 4.7, 4.6, 1.0, 0.9, 0.8]
    gate = jnp.asarray(np.linalg.pinv(np.ones((1, 8), np.float32)) @ logits)  # x = ones: x @ gate = logits
    chosen, weights = route_softmax_group_limited(jnp.ones((1, 8)), gate, 6, 16.0, 8, 3)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 20, 21, 40, 41]
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    assert np.allclose(np.sort(np.asarray(weights[0])), np.sort(16.0 * p[0, [0, 1, 20, 21, 40, 41]]), rtol=1e-4)
    want, _ = ref.route(jnp.ones((1, 8)), {"router": gate}, dict(MOE_CFG, n_routed_experts_total=160, n_group=8, topk_group=3,
                                                                  num_experts_per_tok=6))
    assert sorted(np.asarray(want[0]).tolist()) == [0, 1, 20, 21, 40, 41]


@pytest.mark.parametrize("tiers", [{}, {"expert_caps": ()}, {"expert_caps": (4, 32)}, {"expert_caps": (4,)}],
                         ids=["default", "every_row", "cap4_32", "cap4_overflows"])
def test_the_shares_of_all_groups_and_the_shared_experts_once_add_up_to_the_uncut_layer(tiers):
    """Guide section 4: the routed parts that the four shares give (a group of experts each: device-limited
    routing's layout), plus what every chip computes alike (the shared experts) counted ONCE, are the uncut
    reference layer. Whatever cap the routing picks for an expert's rows."""
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    whole = np.asarray(ref.feed_forward(x, w, MOE_CFG, ref.EXPERTS))
    parts = sum(share(x, w, 4 * g, 4 * g + 4, **tiers) for g in range(4))
    shared = ref.swiglu_mlp(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    assert np.abs(np.asarray(parts + shared) - whole).max() < 1e-5 * np.abs(whole).max()
    # and the reference's own share, group 1 of 4, is the program's
    held = {**w, **{k: w[k][4:8] for k in ("expert_gate", "expert_up", "expert_down")}}
    one = ref.routed_part(x, held, dict(MOE_CFG, n_routed_experts=4, first_expert=4))
    assert np.abs(np.asarray(share(x, w, 4, 8, **tiers)) - np.asarray(one)).max() < 1e-5 * np.abs(whole).max()


def test_a_favoured_gated_expert_takes_every_row_and_masked_rows_go_nowhere():
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(6), (48, 32)).at[:, 0].set(12.0)
    w["router"] = w["router"].at[0].set(0.0).at[0, 2].set(3.0)  # every row's score for expert 2 leads: it overflows the cap alone
    mask = jnp.arange(48) < 44
    chosen, _ = route(x, w)
    sizes = np.bincount(np.asarray(chosen)[:44].reshape(-1), minlength=16)
    assert sizes[2] == 44 and np.sort(sizes)[-2] <= 28
    with collect_expert_counts() as counts:
        got = share(x, w, row_mask=mask, expert_caps=(28,))
    assert np.asarray(counts[0]).tolist()[0] == 44 * 3
    want = ref.routed_part(x, w, MOE_CFG)
    assert np.abs(np.asarray(got[:44]) - np.asarray(want[:44])).max() < 1e-5 * np.abs(np.asarray(want)).max()
    assert np.abs(np.asarray(got[44:])).max() == 0.0


# -- the latent set ------------------------------------------------------------------------

S, C, H, W, VW, NB, BS, MBS = 3, 4, 4, 128, 24, 12, 8, 4


def latent_set(seed=0, seq_lens=(5, 0, 9), q_lens=(4, 2, 1), mask=(True, True, True)):
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.permutation(NB)[: S * MBS].reshape(S, MBS), jnp.int32)
    batch = PagedBatch(tables, jnp.asarray(seq_lens, jnp.int32), jnp.asarray(mask), jnp.asarray(q_lens, jnp.int32))
    return LatentKV(jnp.asarray(rng.normal(size=(NB, 1, BS, W)), jnp.float32), batch)


def test_a_latent_set_is_one_plane_and_crosses_jit_as_it_is():
    kv = latent_set()
    leaves = jax.tree.leaves(kv)
    assert [tuple(a.shape) for a in leaves] == [(NB, 1, BS, W), (S, MBS), (S,), (S,), (S,)]
    assert kv.planes == (kv.rows,)
    back = jax.jit(lambda s: s)(kv)
    assert type(back) is LatentKV and bool(jnp.array_equal(back.rows, kv.rows))
    spec = LatentKV.spec(24, 8, jnp.float32)
    empty = LatentKV.zeros(NB, BS, spec)
    assert empty.rows.shape == (NB, 1, BS, W) and float(jnp.abs(empty.rows).max()) == 0.0 and spec.unit_bytes == 4 * W


def test_fork_copies_pages_and_drops_no_fork():
    kv = latent_set()
    forked = kv.fork(jnp.asarray([3, 0, 7]), jnp.asarray([5, NB, NB]))  # dst == NB: no fork
    assert bool(jnp.array_equal(forked.rows[5], kv.rows[3]))
    untouched = [b for b in range(NB) if b != 5]
    assert bool(jnp.array_equal(forked.rows[jnp.asarray(untouched)], kv.rows[jnp.asarray(untouched)]))
    same = kv.fork(jnp.zeros((3,), jnp.int32), jnp.full((3,), NB))
    assert bool(jnp.array_equal(same.rows, kv.rows))


def test_fork_lowers_with_no_conditional_and_is_the_conditional_forks_copy():
    """The latent fork is ONE unconditional gather and scatter (a ``lax.cond`` over a donated plane costs
    a copy of the plane a branch, every step); a call in which some slots fork and the others carry
    ``dst == NB`` gives, page for page, what ``PagedKV``'s conditional fork gives for the same arguments."""
    kv = latent_set()
    fork = jax.jit(lambda kv, src, dst: kv.fork(src, dst))
    src, dst = jnp.asarray([3, 0, 7], jnp.int32), jnp.asarray([5, NB, 11], jnp.int32)
    lowered = fork.lower(kv, src, dst)
    text = lowered.as_text()
    assert "stablehlo.scatter" in text and "kv_cow" in lowered.as_text(debug_info=True)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    conditional = jax.jit(_cow_copy_planes)
    assert "stablehlo.case" in conditional.lower(kv.planes, src, dst).as_text()  # the form it is held against
    for d in (dst, jnp.full((3,), NB, jnp.int32)):
        forked = fork(kv, src, d)
        assert type(forked) is LatentKV and forked.batch is not None
        assert bool(jnp.array_equal(forked.rows, conditional(kv.planes, src, d)[0]))
    forked = fork(kv, src, dst)
    assert bool(jnp.array_equal(forked.rows[jnp.asarray([5, 11])], kv.rows[jnp.asarray([3, 7])]))
    untouched = jnp.asarray([b for b in range(NB) if b not in (5, 11)])
    assert bool(jnp.array_equal(forked.rows[untouched], kv.rows[untouched]))


def test_attend_appends_the_rows_and_is_dense_attention_over_them():
    """Against a dense causal attention over each slot's rows, written out: the row is key, its first
    lanes are value; a masked slot writes nothing and reads zeros; rows past q_lens read zeros."""
    kv = latent_set(mask=(True, True, False))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(S, C, H, 32)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(S, C, 32)), jnp.float32)
    out, new = kv.attend(q, row, VW)
    assert out.shape == (S, C, H, VW) and type(new) is LatentKV
    tables, lens, qlens = (np.asarray(a) for a in (kv.batch.block_tables, kv.batch.seq_lens, kv.batch.q_lens))
    assert bool(jnp.array_equal(new.rows[tables[2]], kv.rows[tables[2]]))  # the masked slot's pages are as they were
    for s in (0, 1):
        cached = np.asarray(new.rows[tables[s], 0]).reshape(MBS * BS, W)
        assert np.allclose(cached[lens[s]: lens[s] + qlens[s], :32], np.asarray(row[s, : qlens[s]]))
        assert np.abs(cached[lens[s]: lens[s] + qlens[s], 32:]).max() == 0.0  # the padding lanes
        for j in range(C):
            if j >= qlens[s]:
                assert np.abs(np.asarray(out[s, j])).max() == 0.0
                continue
            keys = cached[: lens[s] + j + 1]
            p = np.asarray(jax.nn.softmax(jnp.asarray(np.asarray(q[s, j]) @ keys[:, :32].T), axis=-1))
            assert np.allclose(np.asarray(out[s, j]), p @ keys[:, :VW], atol=1e-5)
    assert np.abs(np.asarray(out[2])).max() == 0.0


@pytest.mark.parametrize("chunk", [1, 16])
def test_the_latent_walk_in_interpret_mode_is_the_xla_composition(chunk):
    """Ragged q_lens, a slot with none, sequences over several tiles of pages."""
    slots, heads, width, value, nb, bs, mbs = 4, 8, 256, 128, 40, 16, 10
    rng = np.random.default_rng(chunk)
    pool = jnp.asarray(rng.normal(size=(nb, 1, bs, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb).reshape(slots, mbs), jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, chunk, heads, width)) * 0.2, jnp.float32)
    seq_lens = jnp.asarray([130, 0, 17, 60], jnp.int32)
    q_lens = jnp.asarray([1, 0, min(chunk, 5), chunk], jnp.int32)
    want = _gather_latent_attend(q, pool, tables, seq_lens, q_lens, value)
    got = paged_latent_chunk(q, pool, tables, seq_lens, q_lens, value_width=value, interpret=True)
    assert got.shape == (slots, chunk, heads, value)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert np.abs(np.asarray(got[1])).max() == 0.0 and np.abs(np.asarray(want[1])).max() == 0.0
    if chunk > 5:
        assert np.abs(np.asarray(got[2, 5:])).max() == 0.0


# -- the engine --------------------------------------------------------------------------

def test_engine_builds_the_latent_planes_the_model_names_and_the_gauges_say_what(model):
    eng = engine(model)
    c = model.config
    assert len(eng._caches) == c.num_hidden_layers == eng.stats["kv_sets"] and eng._states == []
    assert all(len(planes) == 1 and planes[0].shape == (eng.num_blocks, 1, 16, 128) for planes in eng._caches)
    per_token = c.num_hidden_layers * 128 * 4  # ONE padded row a layer, not 2 x heads x head_dim
    assert eng.stats["kv_bytes_per_token"] == per_token == eng.pool_stats()["bytes_per_token"]
    assert eng.stats["experts_held"] == 16 and eng.stats["state_sets"] == 0


def test_prefill_in_chunks_then_decode_through_latent_pages_is_the_references_full_forward(model):
    """Chunks of 16, prompts whose lengths are no multiples of 16, five requests over three slots: every
    served token is the reference's own argmax at its position, in ONE compiled step; and the logits of
    the step's own body (absorbed) are the reference's (materialised) to float32 rounding."""
    eng = engine(model)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (37, 5, 21, 50, 18)]
    ids = [eng.add_request(p, max_new_tokens=g) for p, g in zip(prompts, (6, 9, 4, 5, 7))]
    out = eng.run()
    for rid, prompt in zip(ids, prompts):
        assert served_gap(model, prompt, out[rid].generated).max() == 0.0
    assert eng.stats["step_traces"] == 1 and eng.stats["admitted"] == 5
    rows = sum(len(p) for p in prompts) + sum(len(out[r].generated) - 1 for r in ids)
    assert eng.stats["moe_rows_local"] == rows * 3 * 2  # every real row, 3 choices, 2 expert layers (all experts held)
    assert 0 < eng.stats["moe_experts_hit"] <= eng.stats["steps"] * 2 * 16
    keys = sum(n * (n + 1) // 2 for n in map(len, prompts)) + sum(
        sum(len(p) + j + 1 for j in range(len(out[r].generated) - 1)) for r, p in zip(ids, prompts))
    assert eng.stats["attn_row_keys"] == keys  # every live row's visible tokens, a layer
    pool = eng.pool_stats()
    assert pool["free"] + pool["cached_blocks"] == pool["total"]
    assert close(eng.step_logits(prompts[0]), ref_logits(model, prompts[0][:16]))


def test_a_share_of_the_experts_serves_the_references_share():
    """16 experts scored in 4 groups, group 1 (experts 4..7) held: program and reference leave out the same part."""
    model = build(n_routed_experts=4, n_routed_experts_total=16, first_expert=4)
    eng = engine(model)
    prompt = np.random.default_rng(2).integers(1, VOCAB, 19).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=5)
    out = eng.run()
    assert served_gap(model, prompt, out[rid].generated).max() == 0.0
    assert eng.stats["experts_held"] == 4 and eng.stats["moe_rows_local"] < (19 + 4) * 3 * 2


def test_prefix_reuse_maps_latent_pages_and_copy_on_write_forks_them(model):
    """A repeat of a prompt hits the cached chain (whole blocks mapped, not recomputed) and a request that
    diverges inside a shared block forks it: tokens as without the cache, and the reference's."""
    prompt = np.random.default_rng(5).integers(1, VOCAB, 53).astype(np.int32)
    cold = engine(model, enable_prefix_cache=False)
    rid = cold.add_request(prompt, max_new_tokens=6)
    want = list(cold.run()[rid].generated)
    eng = engine(model, enable_prefix_cache=True)
    tokens = []
    for _ in range(2):
        rid = eng.add_request(prompt, max_new_tokens=6)
        tokens.append(list(eng.run()[rid].generated))
    assert tokens == [want, want] and served_gap(model, prompt, want).max() == 0.0
    assert eng.stats["prompt_tokens_reused"] >= 48 and eng.prefix_cache_stats()["hit_rate"] > 0
    # diverge inside the last shared block: the fork copies the latent page, the shared one stays
    other = np.concatenate([prompt[:40], np.random.default_rng(6).integers(1, VOCAB, 9).astype(np.int32)])
    rid = eng.add_request(other, max_new_tokens=4)
    got = list(eng.run()[rid].generated)
    assert served_gap(model, other, got).max() == 0.0
    rid = eng.add_request(prompt, max_new_tokens=6)
    assert list(eng.run()[rid].generated) == want
    pool = eng.pool_stats()
    assert pool["free"] + pool["cached_blocks"] == pool["total"] and eng.stats["step_traces"] == 1


def test_recover_mid_generation_rebuilds_the_latent_planes_and_gives_the_same_tokens(model):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (23, 9)]
    sound = engine(model)
    ids = [sound.add_request(p, max_new_tokens=8) for p in prompts]
    want = {r: list(req.generated) for r, req in sound.run().items()}
    eng = engine(model)
    ids2 = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        eng.step()
    eng.recover()
    assert all(len(planes) == 1 and float(jnp.abs(planes[0]).max()) > 0 for planes in eng._caches)  # replayed
    done = {}
    while eng.has_work():
        done.update({r.req_id: r for r in eng.step()})
    assert [list(done[r].generated) for r in ids2] == [want[r] for r in ids]
    assert eng.stats["recoveries"] == 1 and eng.stats["step_traces"] == 1


def test_admission_is_by_blocks_of_latent_rows(model):
    """A pool of 6 blocks: a request that needs 4 is admitted, the next that needs 4 waits for blocks
    with a slot free, and is served once the first is done."""
    eng = engine(model, num_blocks=6, enable_prefix_cache=False)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, 50).astype(np.int32) for _ in range(2)]
    ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.step()
    assert eng.stats["admitted"] == 1 and eng.stats["admit_blocked_steps.blocks"] == 1
    out = eng.run()
    assert eng.stats["admitted"] == 2
    for rid, prompt in zip(ids, prompts):
        assert served_gap(model, prompt, out[rid].generated).max() == 0.0
    assert eng.pool_stats()["free"] == 6


def test_serves_behind_the_frontend(model):
    fe = ServingFrontend(engine(model))
    prompt = np.random.default_rng(4).integers(1, VOCAB, 20).astype(np.int32)
    handle = fe.submit(prompt, max_new_tokens=5)
    while not handle.finished:
        fe.pump()
    assert handle.outcome == "ok" and served_gap(model, prompt, list(handle.tokens())).max() == 0.0


def test_weight_only_int8_reaches_the_mlps_and_the_head_and_stays_near_the_reference():
    """The engine's option quantises ``gate_proj`` / ``up_proj`` / ``down_proj`` / ``lm_head`` layers in place;
    this model's are ``nn.Linear``s made in the configuration's dtype, so the forward dispatches on the scales."""
    model = build()
    prompt = np.random.default_rng(8).integers(1, VOCAB, 16).astype(np.int32)
    want = ref_logits(model, prompt)
    eng = engine(model, weight_only_int8=True)
    assert str(model.lm_head.weight._data.dtype) == "int8" and str(model.model.layers[1].mlp.gate.weight._data.dtype) == "float32"
    gap = np.abs(eng.step_logits(prompt) - want).max() / np.abs(want).max()
    assert 100 * TOL < gap < 0.2  # 8 bits a weight at 64-wide matrices: percents, not float32 rounding


def test_the_step_takes_one_plane_a_set(model):
    """``_step_impl``'s arguments, flat: the weights, then ONE plane a latent set, then the step's seven."""
    eng = engine(model)
    s, c, mbs = eng.max_slots, eng.prefill_chunk, eng.max_blocks_per_seq
    args = (eng._param_arrays(), eng._caches, jnp.zeros((s, c), jnp.int32), jnp.zeros((s, mbs), jnp.int32),
            jnp.zeros((s,), jnp.int32), jnp.ones((s,), jnp.int32), jnp.ones((s,), bool),
            jnp.zeros((s,), jnp.int32), jnp.full((s,), eng.num_blocks, jnp.int32))
    got = [(tuple(a.shape), str(a.dtype)) for a in jax.tree.leaves(eng._step_fn.lower(*args).args_info)]
    weights = [(tuple(p.shape), str(p._data.dtype)) for _, p in model.named_parameters()]
    sets = [((eng.num_blocks, 1, 16, 128), "float32")] * model.config.num_hidden_layers
    assert got[: len(weights) + len(sets)] == weights + sets and len(got) == len(weights) + len(sets) + 7


@pytest.mark.parametrize("option, match", [
    ({"kv_cache_dtype": "int8"}, "scale planes are per"),
    ({"kv_host_tier_bytes": 1 << 20}, "captured and landed as"),
    ({"tp": 2}, "a latent row has none"),
])
def test_options_that_cannot_carry_a_latent_set_raise_at_construction(model, option, match):
    with pytest.raises(ValueError, match=match):
        engine(model, **option)
