"""Nemotron-H (``models/nemotron_h.py``): blocks of ONE mixer each (Mamba-2,
sparse experts, attention), held against its plain reference
(``benchmarks/reference/hybrid_ssm_moe.py``: float32, a per-token recurrence,
a loop over the held experts, no cache) on seeded random weights at a small
size, through the plain forward and through the serving engine (recurrent
state beside KV pages in one compiled step).

Everything runs in float32 on the CPU under matmul precision "highest"
(``conftest.py``), so program and reference differ only by the order of
float32 sums (the chunked scan against the recurrence, the grouped matmul
against the loop): logits of order 1 agree to a few 1e-6. ``TOL`` is 1e-5 of
the largest logit, and far below what leaving out any leaf moves them by.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional.fused_moe import collect_expert_counts, expert_share, route_sigmoid_topk
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.paged_kv import PAGED, RECURRENT
from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM
from paddle_tpu.serving import ServingFrontend

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import hybrid_ssm_moe as ref  # noqa: E402

TOL = 1e-5
VOCAB = 96
LEAF_OF = {  # reference leaf -> the program's parameter, inside a block of each kind
    "M": {"norm": "norm.weight", "w_in": "mixer.in_proj.weight", "conv_w": "mixer.conv1d.weight",
          "conv_b": "mixer.conv1d.bias", "dt_bias": "mixer.dt_bias", "a_log": "mixer.A_log", "d_skip": "mixer.D",
          "gate_norm": "mixer.norm.weight", "w_out": "mixer.out_proj.weight"},
    "*": {"norm": "norm.weight", "wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
          "wv": "mixer.v_proj.weight", "wo": "mixer.o_proj.weight"},
    "E": {"norm": "norm.weight", "router": "mixer.gate.weight", "b_sel": "mixer.gate.e_score_correction_bias",
          "w_up": "mixer.experts.up_proj", "w_down": "mixer.experts.down_proj",
          "shared_up": "mixer.shared_experts.up_proj.weight", "shared_down": "mixer.shared_experts.down_proj.weight"},
}


def build(pattern="MEM*EM", seed=5, **kw):
    """A float32 model whose norm weights and ``D`` are NOT ones (a leaf left out has to show)."""
    cfg = dataclasses.replace(NemotronHConfig.tiny(vocab=VOCAB, pattern=pattern), **kw)
    paddle.seed(seed)
    model = NemotronHForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.set_value(jnp.asarray(rng.uniform(0.6, 1.4, p.shape), jnp.float32))
        elif name.endswith(".D"):
            p.set_value(jnp.asarray(rng.uniform(0.5, 1.5, p.shape), jnp.float32))
    return model


def ref_cfg(model):
    cfg = dataclasses.asdict(model.config)
    cfg["hybrid_override_pattern"] = model.config.pattern
    return cfg


def ref_weights(model):
    """The program's parameters under the reference's leaf names (both keep a matrix as ``[in, out]``)."""
    p = {n: v._data for n, v in model.named_parameters()}
    top = {"embed": p["backbone.embeddings.weight"], "final_norm": p["backbone.norm_f.weight"], "head": p["lm_head.weight"]}
    layers = [{leaf: p[f"backbone.layers.{i}.{path}"] for leaf, path in LEAF_OF[kind].items()}
              for i, kind in enumerate(model.config.pattern)]
    return {"top": top, "layers": layers}


_ref_forward = jax.jit(ref.forward_logits, static_argnums=(2,))


def ref_logits(model, tokens):
    """The reference's logits of one sequence; padded to a multiple of 64 so that few lengths compile
    (causal: the padding changes no row that is read)."""
    padded = np.pad(np.asarray(tokens, np.int32), (0, -len(tokens) % 64))
    return np.asarray(_ref_forward(jnp.asarray(padded), ref_weights(model), ref.base._Frozen(ref_cfg(model))))[: len(tokens)]


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

def test_config_reads_the_pattern_and_says_what_each_block_keeps():
    cfg = NemotronHConfig()
    assert cfg.pattern.count("M") == 23 and cfg.pattern.count("E") == 23 and cfg.pattern.count("*") == 6
    assert cfg.d_inner == 4096 and cfg.conv_dim == 6144 and cfg.n_routed_experts_total == 128
    half = dataclasses.replace(cfg, num_hidden_layers=26, n_routed_experts=16, n_routed_experts_total=128)
    assert half.pattern == "MEMEM*EMEMEM*EMEMEM*EMEMEM" and half.num_kv_sets == 3
    sets = half.cache_sets
    assert [s.kind for s in sets].count(RECURRENT) == 12 and [s.kind for s in sets].count(PAGED) == 3
    assert [s.kind for s in sets[:4]] == [RECURRENT, RECURRENT, RECURRENT, PAGED]  # M E M E M *: E keeps nothing
    # a slot's state in one M block: 64 x 64 x 128 float32 + a 3 x 6144 bf16 conv tail; a token's KV in one * block
    assert sets[0].unit_bytes == 64 * 64 * 128 * 4 + 3 * 6144 * 2 and sets[3].unit_bytes == 2 * 2 * 128 * 2
    with pytest.raises(ValueError, match="not among the router's"):
        dataclasses.replace(cfg, n_routed_experts=16, n_routed_experts_total=128, first_expert=120)
    with pytest.raises(ValueError, match="unknown mixers"):
        NemotronHConfig.tiny(pattern="M-E*")


def test_parameter_names_are_the_familys_and_expert_leaves_are_3d(model):
    names = {n: tuple(p.shape) for n, p in model.named_parameters()}
    c = model.config
    assert names["backbone.layers.0.mixer.in_proj.weight"] == (c.hidden_size, c.d_inner + c.conv_dim + c.mamba_num_heads)
    assert names["backbone.layers.0.mixer.conv1d.weight"] == (c.conv_kernel, c.conv_dim)
    assert names["backbone.layers.1.mixer.experts.up_proj"] == (8, c.hidden_size, c.moe_intermediate_size)
    assert names["backbone.layers.1.mixer.experts.down_proj"] == (8, c.moe_intermediate_size, c.hidden_size)
    assert names["backbone.layers.1.mixer.gate.e_score_correction_bias"] == (8,)
    assert names["backbone.layers.3.mixer.k_proj.weight"] == (c.hidden_size, c.num_key_value_heads * c.head_dim)
    assert {n.split(".")[3] for n in names if n.startswith("backbone.layers.")} == {"norm", "mixer"}
    assert not any("rotary" in n or "mlp" in n for n in names)  # one mixer a block, no positional table
    bf16 = NemotronHForCausalLM(dataclasses.replace(model.config, dtype="bfloat16", num_hidden_layers=2))
    assert bf16.backbone.layers[1].mixer.experts.up_proj.dtype == jnp.bfloat16  # made in the configuration's dtype


def test_plain_forward_is_the_reference(model):
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 21)).astype(np.int32)  # 21: chunks of 8, the last partial
    with paddle.no_grad():
        got = model(Tensor(toks))._data
    for row in range(2):
        assert close(got[row], ref_logits(model, toks[row]))
    with pytest.raises(NotImplementedError, match="no dense"):
        model(Tensor(toks), use_cache=True)


def test_every_leaf_moves_the_logits(model):
    """The tolerance is not slack: zeroing any one leaf moves the logits a hundred times further."""
    from paddle_tpu.nn.layer.layers import bind_param_arrays

    toks = np.random.default_rng(1).integers(0, VOCAB, (1, 12)).astype(np.int32)
    named = list(model.named_parameters())

    @jax.jit
    def forward(arrays):
        with bind_param_arrays(named, arrays), paddle.no_grad():
            return model(Tensor(toks))._data

    arrays = [p._data for _n, p in named]
    base = np.asarray(forward(arrays))
    for i, (name, _p) in enumerate(named):
        if not name.startswith("backbone.layers.") or name.endswith("A_log"):
            continue
        moved = np.abs(np.asarray(forward(arrays[:i] + [jnp.zeros_like(arrays[i])] + arrays[i + 1:])) - base).max()
        assert moved > 1e2 * TOL * np.abs(base).max(), name


# -- the expert share -------------------------------------------------------------------

def moe_leaves(total=16, d=32, width=24, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": 0.3 * jax.random.normal(k[0], (d, total)), "b_sel": 0.05 * jax.random.normal(k[1], (total,)),
            "w_up": 0.1 * jax.random.normal(k[2], (total, d, width)), "w_down": 0.1 * jax.random.normal(k[3], (total, width, d)),
            "shared_up": 0.1 * jax.random.normal(k[4], (d, 2 * width)), "shared_down": 0.1 * jax.random.normal(k[4], (2 * width, d))}


MOE_CFG = {"hidden_size": 32, "n_routed_experts": 16, "n_routed_experts_total": 16, "moe_intermediate_size": 24,
           "num_experts_per_tok": 6, "routed_scaling_factor": 2.5, "moe_shared_expert_intermediate_size": 48}


def test_selection_bias_changes_the_choice_and_not_the_weight():
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    plain, plain_w = route_sigmoid_topk(x, w["router"], jnp.zeros(16), 6, 2.5)
    biased, biased_w = route_sigmoid_topk(x, w["router"], 3.0 * w["b_sel"], 6, 2.5)
    assert not np.array_equal(np.sort(plain, -1), np.sort(biased, -1))  # it chooses
    scores = jax.nn.sigmoid(x @ w["router"])
    picked = jnp.take_along_axis(scores, biased, axis=-1)
    want = 2.5 * picked / picked.sum(-1, keepdims=True)  # the weight is the bare score, over the sum of all six
    assert np.abs(np.asarray(biased_w) - np.asarray(want)).max() < 1e-6
    assert np.abs(np.asarray(biased_w.sum(-1)) - 2.5).max() < 1e-5 and np.abs(np.asarray(plain_w.sum(-1)) - 2.5).max() < 1e-5


def test_the_six_weights_are_normalised_with_absent_experts_in_the_sum():
    """A share that holds experts 0..3 of 16 weighs its rows by score / (sum
    over all six chosen), absent ones included: less than renormalising over
    the held ones would give."""
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 32))
    held = {k: (v[:4] if k in ("w_up", "w_down") else v) for k, v in w.items()}
    got = expert_share(x, w["router"], w["b_sel"], held["w_up"], held["w_down"], 6, 2.5)
    want = ref.routed_part(x, held, dict(MOE_CFG, n_routed_experts=4))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    chosen, weights = route_sigmoid_topk(x, w["router"], w["b_sel"], 6, 2.5)
    here = np.asarray(chosen) < 4
    assert (np.asarray(jnp.where(here, weights, 0.0).sum(-1)) < 2.5 - 1e-3).any()  # part of the 2.5 went to absent experts


@pytest.mark.parametrize("tiers", [{}, {"expert_caps": ()}, {"expert_caps": (32,)}, {"expert_caps": (4, 32)}, {"expert_caps": (4,)},
                                   {"expert_caps": (4, 8, 16)}],
                         ids=["default", "every_row", "cap32", "cap4_32", "cap4_overflows", "cap4_8_16"])
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tiers):
    """Guide section 4: the routed parts that all the shares give, plus what
    every chip computes alike (the shared expert) counted ONCE, are the uncut
    reference layer. Whatever cap the routing picks for an expert's rows."""
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    whole = np.asarray(ref.experts_mixer(x, w, MOE_CFG))
    parts = sum(
        expert_share(x, w["router"], w["b_sel"], w["w_up"][2 * s: 2 * s + 2], w["w_down"][2 * s: 2 * s + 2], 6, 2.5,
                     first_expert=2 * s, **tiers)
        for s in range(8)
    )
    shared = ref.relu2_mlp(x, w["shared_up"], w["shared_down"])
    assert np.abs(np.asarray(parts + shared) - whole).max() < 1e-5 * np.abs(whole).max()
    # and the reference's own share, experts 4..5 of 16, is the program's
    one = ref.routed_part(x, {**w, "w_up": w["w_up"][4:6], "w_down": w["w_down"][4:6]},
                          dict(MOE_CFG, n_routed_experts=2, first_expert=4))
    got = expert_share(x, w["router"], w["b_sel"], w["w_up"][4:6], w["w_down"][4:6], 6, 2.5, first_expert=4, **tiers)
    assert np.abs(np.asarray(got) - np.asarray(one)).max() < 1e-5


@pytest.mark.parametrize("favoured, tier", [((2,), "the first cap, the favoured expert on every row"),
                                            ((2, 5), "the next cap: two experts overflow the first")],
                         ids=["one_favoured", "two_favoured"])
def test_a_favoured_expert_overflows_the_first_cap_and_nothing_is_dropped(favoured, tier):
    """A router that sends every row to one held expert (at seeded weights one
    block in eleven sends it over half): that expert multiplies every row, the
    others keep the first cap; two such experts take the next cap. Either way
    the share is the reference's, and masked rows stay out."""
    w = moe_leaves()
    w["b_sel"] = w["b_sel"].at[jnp.asarray(favoured)].add(5.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (48, 32))
    mask = jnp.arange(48) < 44
    chosen, _ = route_sigmoid_topk(x, w["router"], w["b_sel"], 6, 2.5)
    sizes = np.sort(np.bincount(np.asarray(chosen)[:44].reshape(-1), minlength=16))
    cap = 28
    assert (sizes[-len(favoured):] == 44).all() and sizes[-len(favoured) - 1] <= cap, (tier, sizes)
    got = expert_share(x, w["router"], w["b_sel"], w["w_up"], w["w_down"], 6, 2.5, row_mask=mask, expert_caps=(cap,))
    want = ref.routed_part(x, w, MOE_CFG)
    assert np.abs(np.asarray(got[:44]) - np.asarray(want[:44])).max() < 1e-5
    assert np.abs(np.asarray(got[44:])).max() == 0.0


def test_masked_rows_go_to_no_expert_and_the_counts_say_so():
    w = moe_leaves()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    mask = jnp.arange(64) < 5
    with collect_expert_counts() as counts:
        got = expert_share(x, w["router"], w["b_sel"], w["w_up"], w["w_down"], 6, 2.5, row_mask=mask, expert_caps=(8,))
    rows, hit = np.asarray(counts[0]).tolist()
    assert rows == 5 * 6 and 0 < hit <= 16  # 5 real rows x 6 choices, not 64 x 6: the cap of 8 rows an expert took them
    assert np.abs(np.asarray(got[5:])).max() == 0.0
    want = ref.routed_part(x, w, MOE_CFG)
    assert np.abs(np.asarray(got[:5]) - np.asarray(want[:5])).max() < 1e-5


# -- the engine --------------------------------------------------------------------------

def test_engine_allocates_both_kinds_and_the_gauges_say_what(model):
    eng = engine(model)
    c = model.config
    assert len(eng._caches) == 1 and len(eng._states) == 3 and eng.stats["kv_sets"] == 1 and eng.stats["state_sets"] == 3
    assert eng._caches[0][0].shape == (eng.num_blocks, c.num_key_value_heads, 16, c.head_dim)  # the published head_dim, not D / heads
    assert [p.shape for p in eng._states[0]] == [(3, 4, 8, 16), (3, 3, c.conv_dim)]
    per_slot = 3 * (4 * 8 * 16 * 4 + 3 * c.conv_dim * 4)
    assert eng.stats["state_bytes_per_slot"] == per_slot == eng.pool_stats()["state_bytes_per_slot"]
    assert eng.stats["kv_bytes_per_token"] == 2 * 1 * c.num_key_value_heads * c.head_dim * 4  # pages: the attention set only
    assert eng.stats["experts_held"] == 8


def test_prefill_in_chunks_then_decode_is_the_references_full_forward(model):
    """Chunks of 16, prompts whose lengths are no multiples of 16, five
    requests over three slots (so they are admitted at different steps and
    slots are reused after a request ends): every served token is the
    reference's own argmax at its position, in ONE compiled step."""
    eng = engine(model)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (37, 5, 21, 50, 18)]
    ids = [eng.add_request(p, max_new_tokens=g) for p, g in zip(prompts, (6, 9, 4, 5, 7))]
    out = eng.run()
    for rid, prompt in zip(ids, prompts):
        assert served_gap(model, prompt, out[rid].generated).max() == 0.0
    assert eng.stats["step_traces"] == 1 and eng.stats["admitted"] == 5
    rows = sum(len(p) for p in prompts) + sum(len(out[r].generated) - 1 for r in ids)
    assert eng.stats["moe_rows_local"] == rows * 3 * 2  # every real row, 3 choices, 2 expert blocks (all experts held): no padded row
    assert 0 < eng.stats["moe_experts_hit"] <= eng.stats["steps"] * 2 * 8
    pool = eng.pool_stats()
    assert pool["free"] == pool["total"]
    # the logits of the step's own body on a first chunk (what the benchmark's check reads)
    assert close(eng.step_logits(prompts[0]), ref_logits(model, prompts[0][:16]))


def test_the_scan_kernels_route_serves_the_references_tokens(model, scan_kernel_route):
    """The same five requests over three slots with ``RecurrentState.advance``
    on the route it takes on a TPU (``kernels/ssm_scan.py``, here in the
    interpreter; every other dispatch stays where the CPU puts it): slots are
    admitted at different steps, reused after a request ends (a first chunk
    over whatever the last request left) and idle in between, and every served
    token is still the reference's own argmax at its position."""
    eng = engine(model)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (37, 5, 21, 50, 18)]
    ids = [eng.add_request(p, max_new_tokens=g) for p, g in zip(prompts, (6, 9, 4, 5, 7))]
    out = eng.run()
    assert len(scan_kernel_route) == 3 and eng.stats["step_traces"] == 1  # one kernel an M block of the ONE traced step
    for rid, prompt in zip(ids, prompts):
        assert served_gap(model, prompt, out[rid].generated).max() == 0.0


def test_a_share_of_the_experts_serves_the_references_share():
    """16 experts scored, experts 4..7 held: program and reference leave out the same part."""
    model = build(pattern="MEM*", n_routed_experts=4, n_routed_experts_total=16, first_expert=4)
    eng = engine(model)
    prompt = np.random.default_rng(2).integers(1, VOCAB, 19).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=5)
    out = eng.run()
    assert served_gap(model, prompt, out[rid].generated).max() == 0.0
    assert eng.stats["experts_held"] == 4 and eng.stats["moe_rows_local"] < (19 + 4) * 3


def test_recover_mid_generation_gives_the_same_tokens(model):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (23, 9)]
    sound = engine(model)
    ids = [sound.add_request(p, max_new_tokens=8) for p in prompts]
    want = {r: list(req.generated) for r, req in sound.run().items()}
    eng = engine(model)
    ids2 = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        eng.step()
    eng.recover()  # both kinds re-allocated; the replay rebuilds every live slot's state
    assert all(float(jnp.abs(plane).max()) > 0 for planes in eng._states for plane in planes)
    done = {}
    while eng.has_work():
        done.update({r.req_id: r for r in eng.step()})
    assert [list(done[r].generated) for r in ids2] == [want[r] for r in ids]
    assert eng.stats["recoveries"] == 1 and eng.stats["step_traces"] == 1


def test_serves_behind_the_frontend(model):
    fe = ServingFrontend(engine(model))
    prompt = np.random.default_rng(4).integers(1, VOCAB, 20).astype(np.int32)
    handle = fe.submit(prompt, max_new_tokens=5)
    while not handle.finished:
        fe.pump()
    assert handle.outcome == "ok" and served_gap(model, prompt, list(handle.tokens())).max() == 0.0


# -- what cannot carry recurrent state refuses ---------------------------------------------

def test_prefix_reuse_is_skipped_and_counted_never_served_wrong(model):
    eng = engine(model, enable_prefix_cache=True)
    assert eng.prefix_cache_stats() == {"enabled": False}
    prompt = np.random.default_rng(5).integers(1, VOCAB, 40).astype(np.int32)
    tokens = []
    for _ in range(2):  # the repeat would be a two-block hit: it has to be recomputed, state and all
        rid = eng.add_request(prompt, max_new_tokens=4)
        tokens.append(list(eng.run()[rid].generated))
    assert tokens[0] == tokens[1] and served_gap(model, prompt, tokens[1]).max() == 0.0
    assert eng.stats["prefix_reuse_skipped_recurrent"] == 2 and eng.stats["prompt_tokens_reused"] == 0
    assert engine(model, enable_prefix_cache=False).stats["prefix_reuse_skipped_recurrent"] == 0


@pytest.mark.parametrize("option, match", [
    ({"spec_decode": True}, "cannot be rewound"),
    ({"kv_host_tier_bytes": 1 << 20}, "not the state at its end"),
    ({"tp": 2}, "nothing shards"),
])
def test_options_that_cannot_carry_recurrent_state_raise_at_construction(model, option, match):
    with pytest.raises(ValueError, match=match):
        engine(model, **option)
