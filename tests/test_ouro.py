"""Ouro (``models/ouro.py``): a stack of sandwich-norm layers run
``total_ut_steps`` times a token, held against its plain reference
(``benchmarks/reference/looped_decoder.py``: float32, no cache, no kernels) on
seeded random weights at a small size (hidden 64, 4 heads x 16).

Everything here runs in float32 on the CPU under matmul precision "highest"
(``conftest.py``), so program and reference differ only by the order of
float32 sums: logits of order 1 agree to a few 1e-6. ``TOL`` is 5e-5, ten
times that, and far below what either control moves them by (the wrong-pass
control and the bf16-for-float32 control both read above 1e-3; each asserts
its own margin).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import llama
from paddle_tpu.models.ouro import OuroConfig, OuroDecoderLayer, OuroForCausalLM
from paddle_tpu.serving import ServingFrontend

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import looped_decoder as ref  # noqa: E402

TOL = 5e-5
VOCAB = 96
LEAF_OF = {  # reference leaf -> the program's parameter, inside a layer
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj", "w_down": "mlp.down_proj",
    "norm_attn": "input_layernorm", "norm_attn_out": "input_layernorm_2",
    "norm_mlp": "post_attention_layernorm", "norm_mlp_out": "post_attention_layernorm_2",
}


def build(passes, layers, seed=5):
    """A float32 model whose norm weights are NOT ones (a norm left out, or
    two swapped, has to show)."""
    cfg = OuroConfig(vocab_size=VOCAB, hidden_size=64, intermediate_size=128, num_hidden_layers=layers,
                     num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=128,
                     total_ut_steps=passes, dtype="float32")
    paddle.seed(seed)
    model = OuroForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.set_value(jnp.asarray(rng.uniform(0.6, 1.4, p.shape), jnp.float32))
    return model


def ref_cfg(model):
    c = model.config
    return {"hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size, "vocab_size": c.vocab_size,
            "num_attention_heads": c.num_attention_heads, "num_key_value_heads": c.num_key_value_heads,
            "num_hidden_layers": c.num_hidden_layers, "total_ut_steps": c.total_ut_steps,
            "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta}


def ref_weights(model):
    """The program's parameters under the reference's leaf names (both keep a
    matrix as ``[in, out]``)."""
    p = {n: v._data for n, v in model.named_parameters()}
    top = {"embed": p["ouro.embed_tokens.weight"], "final_norm": p["ouro.norm.weight"], "head": p["lm_head.weight"]}
    layers = [{leaf: p[f"ouro.layers.{i}.{path}.weight"] for leaf, path in LEAF_OF.items()}
              for i in range(model.config.num_hidden_layers)]
    return {"top": top, "layers": layers}


def ref_logits(model, tokens, **kw):
    return np.asarray(ref.forward_logits(jnp.asarray(tokens), ref_weights(model), ref_cfg(model), **kw))


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n).astype(np.int32)


# -- (a) the full forward -------------------------------------------------------
@pytest.mark.parametrize("passes,layers", [(4, 2), (1, 3), (4, 3)])
def test_full_forward_matches_the_reference(passes, layers):
    model = build(passes, layers)
    toks = tokens_of(23)
    got = np.asarray(model(paddle.to_tensor(toks[None]))._data)[0]
    want = ref_logits(model, toks)
    assert got.shape == want.shape == (23, VOCAB)
    assert np.abs(want).max() > 0.5  # logits of order 1: an absolute tolerance means something
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_reference_at_bf16_fails_the_same_tolerance():
    """The control: the reference with every matmul operand rounded to bf16
    (the next precision below this test's float32) leaves the float32
    reference by far more than ``TOL``, so ``TOL`` would catch a program that
    computed in it."""
    model = build(4, 2)
    toks = tokens_of(23)
    gap = np.abs(ref_logits(model, toks, lower="bf16") - ref_logits(model, toks)).max()
    assert gap > 20 * TOL, gap


# -- (c) weight sharing, and whose modules these are ----------------------------
def test_four_passes_are_the_one_pass_body_applied_four_times():
    """``T = 4`` on the plain path equals the ``T = 1`` model's body (the
    stack, then the final norm) applied four times on the same weights: the
    passes share every weight, and nothing but the body is looped."""
    four, one = build(4, 3), build(1, 3)
    one.set_state_dict(four.state_dict())
    toks = paddle.to_tensor(tokens_of(17)[None])
    h = four.ouro.embed_tokens(toks)
    for _ in range(4):
        h, _caches = one.ouro._stack(h, None, None, False, None)
    want = np.asarray(four.lm_head(h)._data)
    np.testing.assert_allclose(np.asarray(four(toks)._data), want, atol=1e-6, rtol=0)
    # one pass alone is another model
    assert np.abs(np.asarray(one(toks)._data) - want).max() > 1e-2
    assert len(list(four.named_parameters())) == len(list(one.named_parameters())) == 3 + 3 * 11


def test_attention_mlp_and_rotary_are_llamas_own_classes():
    model = build(4, 2)
    layer = model.ouro.layers[0]
    assert isinstance(layer, OuroDecoderLayer)
    assert type(layer.self_attn) is llama.LlamaAttention and type(layer.mlp) is llama.LlamaMLP
    assert type(layer.self_attn.rotary_emb) is llama.LlamaRotaryEmbedding
    # one rotary table for the whole stack
    assert all(l.self_attn.rotary_emb is layer.self_attn.rotary_emb for l in model.ouro.layers)
    assert model.config.num_kv_sets == 8 and llama.LlamaConfig.tiny().num_kv_sets == 2
    assert model.config.stack_passes == 4


# -- generate and training run through the loop ---------------------------------
def test_generate_and_paged_generate_follow_the_reference_greedily():
    model = build(4, 2)
    prompt = tokens_of(9, seed=3)
    out = np.asarray(model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=5, do_sample=False)._data)[0]
    paged = np.asarray(model.generate_paged(paddle.to_tensor(prompt[None]), max_new_tokens=5, block_size=4)._data)[0]
    assert out.tolist() == paged.tolist() and out[:9].tolist() == prompt.tolist()
    want = ref_logits(model, out)
    # every generated token is the reference's argmax at its position, or within TOL of it
    for pos in range(8, 13):
        assert want[pos].max() - want[pos, out[pos + 1]] <= TOL


def test_to_static_training_step_of_the_loop_learns_and_matches_eager():
    model = build(4, 2)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, parameters=model.parameters())
    toks = tokens_of(2 * 17, seed=4).reshape(2, 17)
    ids, labels = paddle.to_tensor(toks[:, :-1]), paddle.to_tensor(toks[:, 1:].astype(np.int64))
    eager_loss, _ = model(ids, labels=labels)
    # the loss is the reference's last-pass token-mean cross entropy
    w, cfg = ref_weights(model), ref_cfg(model)
    want, _grads = ref.batch_loss_and_grads(w, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]), cfg)
    assert float(eager_loss) == pytest.approx(float(want), abs=TOL)

    @paddle.jit.to_static
    def step(model, opt, ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    losses = [float(step(model, opt, ids, labels)) for _ in range(4)]
    assert losses[0] == pytest.approx(float(eager_loss), abs=1e-5)
    assert losses[-1] < losses[0] - 0.05


# -- (b), (d), (e): through the serving engine ----------------------------------
class LoggedEngine:
    """A ``ContinuousBatchingEngine`` whose step also hands its LOGITS to the
    test: the engine's own ``_step_forward`` (what ``_step_impl`` takes the
    argmax of) is jitted here in its place. ``order`` permutes the KV sets the
    model is handed (the wrong-pass control)."""

    def __init__(self, model, order=None, **kw):
        self.eng = eng = ContinuousBatchingEngine(model, **kw)
        self.rows = {}  # (request id, position) -> logits row
        forward = jax.jit(eng._step_forward)

        def step_fn(params, caches, toks, tables, lens, q_lens, active, cow_src, cow_dst):
            if order is not None:
                caches = [caches[i] for i in order]
            logits, new = forward(params, caches, toks, tables, lens, q_lens, active, cow_src, cow_dst)
            if order is not None:
                back = [0] * len(order)
                for at, i in enumerate(order):
                    back[i] = new[at]
                new = back
            logits = np.asarray(logits, np.float32)
            for slot in np.flatnonzero(np.asarray(active)):
                req = eng._slot_req[slot]
                for j in range(int(q_lens[slot])):
                    self.rows[(req.req_id, int(lens[slot]) + j)] = logits[slot, j]
            return jnp.asarray(logits.argmax(-1).astype(np.int32)), new

        eng._step_fn = step_fn


def serve(model, prompts, max_new, **kw):
    logged = LoggedEngine(model, max_slots=2, block_size=4, prompt_bucket=32, max_model_len=64,
                          prefill_chunk=4, **kw)
    fe = ServingFrontend(logged.eng)
    done = []
    for prompt in prompts:  # one after the other: the second finds the first's prefix cached
        handle = fe.submit(prompt, max_new_tokens=max_new)
        while not handle.finished:
            fe.pump()
        assert handle.outcome == "ok"
        done.append((handle, np.concatenate([prompt, np.asarray(handle.tokens(), np.int32)])))
    return logged, done


def worst_gap(model, logged, done):
    worst = 0.0
    for k, (_handle, seq) in enumerate(done):
        want = ref_logits(model, seq)
        got = {pos: row for (rid, pos), row in logged.rows.items() if rid == k}
        assert got, "the engine's request ids count from 0 in submission order"
        # every position that was computed (a cached prefix is not): prompt rows and decode rows
        assert max(got) == len(seq) - 2
        worst = max(worst, max(float(np.abs(row - want[pos]).max()) for pos, row in got.items()))
    return worst


@pytest.mark.parametrize("passes,layers", [(4, 2), (1, 3)])
def test_chunked_prefill_then_paged_decode_matches_the_reference(passes, layers):
    """Prefill in chunks of 4 (a block is 4 tokens: the 14-token prompt
    crosses three block boundaries and ends inside a block), then decode
    through the pool; a second request shares the first's 10-token prefix, so
    it maps two cached blocks and copy-on-write forks the third in all
    ``T x L`` sets. Logits, not tokens, at every computed position."""
    model = build(passes, layers)
    first = tokens_of(14, seed=1)
    second = np.concatenate([first[:10], tokens_of(5, seed=2)])
    logged, done = serve(model, [first, second], max_new=6)
    eng = logged.eng
    assert eng.stats["prompt_tokens_reused"] >= 8, eng.stats  # the shared prefix was not recomputed
    assert len(eng._caches) == passes * layers == model.config.num_kv_sets
    assert worst_gap(model, logged, done) <= TOL
    assert eng.stats["loop_passes"] == passes * eng.stats["steps"] and eng.stats["kv_sets"] == passes * layers


def test_copy_on_write_moves_every_kv_set():
    """After the fork, the second request's private copy of the shared block
    equals the source block in EVERY set (rows the fork copied), while the
    second request is still live."""
    model = build(4, 2)
    first = tokens_of(14, seed=1)
    second = np.concatenate([first[:10], tokens_of(5, seed=2)])
    eng = ContinuousBatchingEngine(model, max_slots=2, block_size=4, prompt_bucket=32, max_model_len=64,
                                   prefill_chunk=4)
    eng.add_request(first, max_new_tokens=2)
    eng.run()
    eng.add_request(second, max_new_tokens=8)
    forks = []
    real = eng._dispatch

    def dispatch(toks, q_lens, active):
        forks.extend((p[0].block, p[1]) for p in eng._pending_cow if p is not None)
        return real(toks, q_lens, active)

    eng._dispatch = dispatch
    eng.step()
    assert len(forks) == 1, forks
    src, dst = forks[0]
    assert src != dst and len(eng._caches) == 8
    for kc, vc in eng._caches:  # tokens 8, 9 of the shared prefix live in the forked block's rows 0, 1
        np.testing.assert_array_equal(np.asarray(kc[dst][:, :2]), np.asarray(kc[src][:, :2]))
        np.testing.assert_array_equal(np.asarray(vc[dst][:, :2]), np.asarray(vc[src][:, :2]))
        assert np.abs(np.asarray(kc[src][:, :2])).max() > 0


def test_cache_count_and_bytes_per_token_follow_the_passes():
    four = ContinuousBatchingEngine(build(4, 3), max_slots=2, block_size=4, prompt_bucket=16, max_model_len=32)
    one = ContinuousBatchingEngine(build(1, 3), max_slots=2, block_size=4, prompt_bucket=16, max_model_len=32)
    assert len(four._caches) == 4 * len(one._caches) == 12
    assert four.pool_stats()["bytes_per_token"] == 4 * one.pool_stats()["bytes_per_token"] == 2 * 12 * 4 * 16 * 4
    assert four.stats["kv_bytes_per_token"] == four.pool_stats()["bytes_per_token"]
    # a Llama engine reads what it read: one set a layer
    tiny = llama.LlamaForCausalLM(llama.LlamaConfig.tiny())
    eng = ContinuousBatchingEngine(tiny, max_slots=2, block_size=4, prompt_bucket=16, max_model_len=32)
    assert len(eng._caches) == 2 and eng.stats["kv_sets"] == 2
    assert eng.pool_stats()["bytes_per_token"] == 2 * 2 * 2 * 16 * jnp.dtype(eng._cache_dtype).itemsize


def test_reading_a_pass_from_the_previous_passes_kv_sets_is_caught():
    """The wrong-pass control: pass 0 is handed the KV sets of pass 3 (set
    ``(t - 1) mod T`` for ``t = 0``), so from the second chunk on it attends to
    keys that another pass wrote. The same comparison as above fails by far
    more than ``TOL``."""
    model = build(4, 2)
    order = [6, 7] + list(range(2, 8))
    first = tokens_of(14, seed=1)
    logged, done = serve(model, [first], max_new=6, order=order, enable_prefix_cache=False)
    assert worst_gap(model, logged, done) > 100 * TOL


def test_a_request_held_back_for_blocks_is_counted():
    """Blocks, not slots, run out: 2 slots, but the pool holds one request's
    worst case only, so the second waits with a slot free."""
    model = build(4, 2)
    eng = ContinuousBatchingEngine(model, max_slots=2, block_size=4, num_blocks=6, prompt_bucket=16,
                                   max_model_len=24, prefill_chunk=4, enable_prefix_cache=False)
    a = eng.add_request(tokens_of(10, seed=7), max_new_tokens=8)
    b = eng.add_request(tokens_of(10, seed=8), max_new_tokens=8)
    out = eng.run()
    assert sorted(out) == sorted([a, b]) and all(r.finish_reason == "length" for r in out.values())
    assert eng.stats["admit_blocked_steps.blocks"] > 0 and eng.stats["admit_blocked_steps.slots"] == 0
    full = ContinuousBatchingEngine(model, max_slots=1, block_size=4, prompt_bucket=16, max_model_len=24,
                                    prefill_chunk=4, enable_prefix_cache=False)
    full.add_request(tokens_of(10, seed=7), max_new_tokens=4)
    full.add_request(tokens_of(10, seed=8), max_new_tokens=4)
    full.run()
    assert full.stats["admit_blocked_steps.slots"] > 0 and full.stats["admit_blocked_steps.blocks"] == 0


def test_spill_prefetch_and_recovery_move_every_kv_set():
    """The host tier and ``recover()`` walk ``T x L`` planes: a chain evicted to
    host RAM and prefetched back, and then pools rebuilt after a dispatch
    fault and replayed, still give the reference's logits at every computed
    position (a set left behind would feed a pass another step's zeros)."""
    from paddle_tpu.testing import faults

    model = build(4, 2)
    prompt = tokens_of(16, seed=9)
    logged = LoggedEngine(model, max_slots=2, block_size=4, prompt_bucket=32, max_model_len=48,
                          num_blocks=64, prefill_chunk=4, kv_host_tier_bytes=1 << 22)
    eng = logged.eng
    eng.add_request(prompt, max_new_tokens=2)
    eng.run()
    eng._cache.evict_blocks(16)  # the whole dead chain goes to the host tier
    assert eng.kv_tier_stats()["spilled_blocks"] >= 3
    assert eng._capture_block_kv(0).shape == (8, 2, 4, 4, 16)  # a spilled block: one plane pair a KV set
    logged.rows.clear()
    rid = eng.add_request(prompt, max_new_tokens=6)
    done = {}
    with faults.inject(faults.FaultPlan.single("engine.decode", 3)):
        while eng.has_work():
            for q in eng.step():
                done[q.req_id] = q
    # the replay after the fault prefetches the chain a second time: the host tier survives recovery
    assert eng.kv_tier_stats()["prefetched_blocks"] in (4, 8) and done[rid].cached_tokens == 15
    assert eng.stats["recoveries"] == 1 and len(eng._caches) == 8
    seq = np.concatenate([prompt, np.asarray(done[rid].generated, np.int32)])
    want = ref_logits(model, seq)
    got = {pos: row for (r, pos), row in logged.rows.items() if r == rid}
    assert min(got) == 15 and max(got) == len(seq) - 2
    assert max(float(np.abs(row - want[pos]).max()) for pos, row in got.items()) <= TOL
