"""The latent-attention / expert configuration (``configs/deepseek-v2.json``,
``reference/mla_moe.py``) through the seam, its arithmetic against hand counts
at the cell's shapes, its three readers on a synthetic run, and its cell's
controls at a CPU size: an altered token, a shared key left unrotated and an
expert share off by one each have to read ``correct: false``. The cell's tiny
rehearsal itself is ``test_rehearsal.py``'s."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
from lib import flops, program, weights
from lib.drivers import serve
from reference import mla_moe as mla
from tests import tiny

CELL = "deepseekv2.serve_doc"
FILE = "deepseek-v2.json"
SEED = 2**31 + 36


@pytest.fixture(autouse=True)
def float32_program():
    jax.config.update("jax_default_matmul_precision", "highest")


def raw():
    return harness.load_json(harness.HERE, "configs", FILE)


def published():
    return program.run_config(raw(), "serve")


def small():
    return program.run_config(tiny.shrink(("configs", FILE), raw()), "serve")


def test_the_file_holds_the_published_keys_and_says_what_was_cut():
    import json

    cfg = raw()
    entry = next(c for c in harness.load_json(harness.ROOT, "BENCHMARK.json")["configs"] if c["file"].endswith(FILE))
    assert cfg["source"] == entry["source"] and sorted(entry["reduced"]) == sorted(cfg["reduced"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key verbatim, but the three that are cut (which state the published value)
        row = next(json.loads(line) for line in open(catalog) if '"name": "DeepSeek-V2"' in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            got = cfg[key]["published"] if key in cfg["reduced"] else cfg[key]
            assert got == value, key
    run = published()
    assert (run["num_hidden_layers"], run["n_routed_experts"], run["vocab_size"]) == (5, 20, 12800)
    assert run["n_routed_experts_total"] == 160 and run["first_expert"] == 0 and run["n_group"] == 8
    assert {"rope_layout", "rope_table", "latent_row", "attention_form", "initializer_range"} <= set(cfg["assumed"])
    assert "64 chips" in cfg["deployment"] and "8 pipeline stages of 8 chips" in cfg["deployment"]


def test_leaf_table_arithmetic_and_counts_against_a_hand_count():
    cfg = published()
    size = [sum(int(np.prod(shape)) for shape, _init in mla.layer_leaves(cfg, i).values()) for i in range(5)]
    attention = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 128 * 128 * 5120
    norms = 2 * 5120 + 1536 + 512
    assert round(attention / 1e6, 1) == 149.2
    assert size[0] == attention + 3 * 5120 * 12288 + norms and round(size[0] / 1e6) == 338  # 149.2 + 188.7, the norms 12 K
    expert, shared, router = 3 * 5120 * 1536, 3 * 5120 * 3072, 5120 * 160
    assert size[1] == size[4] == attention + router + shared + 20 * expert + norms
    assert round((attention + router + shared) / 1e6, 1) == 197.2 and round(expert / 1e6, 1) == 23.6
    total = sum(size) + 2 * 12800 * 5120 + 5120
    assert round(total / 1e9, 2) == 3.15
    # the published model from the same table: one dense layer, 59 expert layers of 160 experts, the whole vocabulary
    whole = dict(cfg, n_routed_experts=160, vocab_size=102400)
    e_whole = sum(int(np.prod(shape)) for shape, _i in mla.layer_leaves(whole, 1).values())
    assert round((size[0] + 59 * e_whole + 2 * 102400 * 5120) / 1e9, 1) == 235.7  # "236B"
    assert (mla.count(cfg, 5, mla.DENSE), mla.count(cfg, 5, mla.EXPERTS)) == (1, 4)
    assert flops.attention_passes(cfg, 5) == 5 and flops.head_dim(cfg) == 192 and mla.latent_width(cfg) == 576
    # a token multiplies through the attention blocks, the dense MLP, routers, shared experts, the head and
    # 6 x 20 / 160 routed experts a layer
    per_dense, per_expert = attention + 3 * 5120 * 12288, attention + router + shared + 0.75 * expert
    assert mla.matmul_params(cfg, 5) == per_dense + 4 * per_expert + 5120 * 12800
    # the least ONE latent attention call does: a decode row over 4096 cached tokens, 128 heads
    least = mla.latent_attention_least(cfg, row_keys=4097, live_tokens=4097)
    assert least == {"flops": 2.0 * 4097 * 128 * (576 + 512), "bytes": 4097 * 576 * 2}
    # the least a step does: a routed expert only where it got a row; latent rows once a set; embedding rows
    idle = mla.step_least(cfg, 5, rows=0, row_keys=0, live_tokens=0, experts_hit=0)
    held = per_dense + 4 * (attention + router + shared) + 5120 * 12800
    assert idle == {"bytes": 2 * held, "flops": 0.0}
    busy = mla.step_least(cfg, 5, rows=40, row_keys=9000, live_tokens=3000, experts_hit=30)
    assert busy["bytes"] - idle["bytes"] == 30 * expert * 2 + 5 * 3000 * 576 * 2 + 40 * 5120 * 2
    assert busy["flops"] == 2.0 * mla.matmul_params(cfg, 5) * 40 + 5 * 2.0 * 9000 * 128 * 1088
    assert abs(mla.softmax_scale(cfg) - 192 ** -0.5 * 1.2608 ** 2) < 1e-5
    names = program.param_names(small(), 3)
    assert names["layers"][0]["w_gate"] == "model.layers.0.mlp.gate_proj.weight"
    assert names["layers"][1]["expert_up"] == "model.layers.1.mlp.experts.up_proj"
    assert names["layers"][2]["w_kvb"] == "model.layers.2.self_attn.kv_b_proj.weight"
    for i in range(5):
        weights.table(mla.layer_leaves(cfg, i))  # no two leaves of a layer draw the same values
    with pytest.raises(NotImplementedError, match="no cell trains"):
        mla.batch_loss_and_grads()


def test_the_walk_a_layer_at_a_time_in_query_blocks_is_the_whole_models_forward():
    cfg = small()

    class Ctx:
        seed, cell = SEED, {"dtype": "bfloat16"}

    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32) for n in (5, 16, 300)]
    got = list(serve.reference_logits(Ctx, cfg, seqs, [8, 16, 256]))  # 300 -> 512 rows: two query blocks of 256
    whole = weights.all_weights(SEED, cfg, cfg["num_hidden_layers"], "bfloat16")
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), whole)
    for toks, rows in zip(seqs, got):
        want = np.asarray(mla.forward_logits(jnp.asarray(toks), f32, cfg))
        np.testing.assert_allclose(np.asarray(rows), want, rtol=2e-4, atol=2e-5)
    assert mla._bucket(1024) == 1024 and mla._bucket(1280) == 2048 and mla._bucket(8448) == 9216


def test_the_program_built_through_the_seam_is_the_reference():
    """``lib/program.py`` builds ``DeepseekV2ForCausalLM`` from the file's ``program`` block and hands it the
    seeded leaves under the names the block gives: its plain forward is the reference's, float32."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    cfg = small()
    model = program.build_model(cfg, SEED, "float32")
    model.eval()
    c = model.config
    assert (c.n_routed_experts, c.n_routed_experts_total, c.n_group, c.topk_group, c.num_hidden_layers) == (4, 16, 4, 2, 3)
    assert c.rope_scaling["original_max_position_embeddings"] == 32 and c.rope_scaling["factor"] == 40
    whole = weights.all_weights(SEED, cfg, cfg["num_hidden_layers"], "float32")
    toks = np.random.default_rng(4).integers(0, cfg["vocab_size"], 70).astype(np.int32)  # past the original 32 positions
    with paddle.no_grad():
        got = np.asarray(model(Tensor(toks[None]))._data)[0]
    want = np.asarray(mla.forward_logits(jnp.asarray(toks), whole, cfg))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


# -- the three readers, on a synthetic run ---------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), os.path.join(harness.HERE, "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def synthetic_run():
    """Two steps of 10 ms busy inside 12 ms pumps; in each, five latent kernel events of 1 ms under the
    ``mla`` scope beside 1 ms of other ``mla`` work and 4 ms of the rest."""
    kernel = "%paged_latent_attention_chunk.{} = bf16[16,1,2048,512] custom-call(...), custom_call_target=\"tpu_custom_call\""
    ops, scopes, t = [], {}, 0.0
    for step in range(2):
        t = 0.012 * step + 0.001
        for k in range(5):
            name = kernel.format(5 * step + k)
            ops.append((name, t, t + 0.001))
            scopes[name] = "jit(_step_impl)/mla/latent_attention/pallas_call:"
            t += 0.001
        ops.append((f"%fusion.{step} = bf16[256,5120] fusion(...)", t, t + 0.001))
        scopes[ops[-1][0]] = "jit(_step_impl)/mla/mla_q/dot_general:"
        ops.append((f"%fusion.{step + 10} = bf16[256,5120] fusion(...)", t + 0.001, t + 0.005))
        scopes[ops[-1][0]] = "jit(_step_impl)/moe/moe_experts/dot_general:"
    spans = [("bench.frontend.pump", 0.012 * s, 0.012 * s + 0.012) for s in range(2)]
    lo, hi = 0.0, 0.024
    cfg = published()
    return {
        "driver": "serve", "cfg": cfg, "depth": 5, "cell": {"dtype": "bfloat16"},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"raw": {"devices": {0: ops}, "spans": spans}},
        "_program_trace": {"scopes": scopes, "spans": [], "window": (lo, hi), "ops": [(n, a, b, 0) for n, a, b in ops]},
        # 100 steps a window: 300 live pages, 48 rows and 60 000 (row, key) pairs a step; 50 experts hit
        "counters": {"engine": {"steps": 100, "attn_row_keys": 6_000_000, "paged_pages_walked": 30_000,
                                "moe_experts_hit": 5000, "prompt_tokens_computed": 4000},
                     "block_size": 16, "max_slots": 16, "prefill_chunk": 16},
        "out_tokens_in_window": 800,
        "traced_pumps": [(0.0, 0.012, 4000, 300), (0.012, 0.024, 4000, 300)],
    }


def test_the_three_readers_read_a_synthetic_run():
    run = synthetic_run()
    cfg = run["cfg"]
    assert abs(reader("latent_attn_pct.serve").read(run) - 60.0) < 1e-6  # 12 of 20 busy ms under mla
    # the kernel: 10 ms in the slice; least: 600 pages x 200 pairs a page x 128 heads x 2 x 1088 flops, 5 sets
    least = mla.latent_attention_least(cfg, 600 * 200, 600 * 16)
    want = 100 * 5 * max(least["flops"] / 197e12, least["bytes"] / 819e9) / 0.010
    assert abs(reader("latent_attn_roofline").read(run) - want) < 1e-9 and 0 < want < 100
    step = mla.step_least(cfg, 5, rows=48, row_keys=60_000, live_tokens=4800, experts_hit=50)
    want = 100 * max(step["flops"] / 197e12, step["bytes"] / 819e9) / 0.010
    assert abs(reader("latent_step_roofline.serve").read(run) - want) < 1e-9 and 0 < want < 100


def test_the_readers_read_nothing_from_a_program_without_the_counter_or_the_kernel():
    run = synthetic_run()
    del run["counters"]["engine"]["attn_row_keys"]
    assert reader("latent_attn_roofline").read(run) is None and reader("latent_step_roofline.serve").read(run) is None
    run = synthetic_run()
    run["_program_trace"]["scopes"] = {}
    run["_program_trace"]["ops"] = [(n.replace("paged_latent_attention_chunk", "fusion"), a, b, d) for n, a, b, d in run["_program_trace"]["ops"]]
    assert reader("latent_attn_pct.serve").read(run) is None and reader("latent_attn_roofline").read(run) is None
    assert reader("latent_attn_pct.serve").read({"trace": None}) is None


# -- the cell's controls, at the CPU size ---------------------------------------------------

def checks_of(notes):
    return {n["name"]: n for n in notes if n.get("note") == "check"}


@pytest.fixture
def tight_limits(monkeypatch):
    """The cell's limits on served tokens are set for bfloat16 at full width; the float32 program at this
    size serves the reference's own argmax, so here they are a thousandth of a logit."""
    real = tiny.shrink

    def shrink(parts, data):
        data = real(parts, data)
        if parts[-2] == "workloads" and "engine" in data:
            data["check"]["limits"].update(served_logit_gap_max=1e-3, served_logit_gap_mean=1e-3)
        return data

    monkeypatch.setattr(tiny, "shrink", shrink)


def test_the_sound_cell_is_correct_under_the_tight_limits(run_cell, tight_limits):
    out, notes = run_cell(CELL)
    assert out["correct"] is True, checks_of(notes)


def test_served_token_altered_where_it_is_produced_is_not_correct(run_cell, tight_limits, monkeypatch):
    from paddle_tpu.inference import ContinuousBatchingEngine

    real = ContinuousBatchingEngine._dispatch

    def altered(self, toks, q_lens, active):
        nxt = np.array(real(self, toks, q_lens, active))
        return (nxt + 1) % self.model.config.vocab_size

    monkeypatch.setattr(ContinuousBatchingEngine, "_dispatch", altered)
    out, notes = run_cell(CELL)
    assert out["correct"] is False and not checks_of(notes)["served_logit_gap_max"]["ok"]


def test_a_shared_key_cached_unrotated_is_not_correct(run_cell, monkeypatch):
    """The timed path broken underneath: the latent row's key part goes into the pages without its rotation.
    The step's own logits leave the reference's."""
    from paddle_tpu.models import deepseek_v2

    real = deepseek_v2.rope_interleaved
    monkeypatch.setattr(deepseek_v2, "rope_interleaved", lambda x, cos, sin: x if x.ndim == 3 else real(x, cos, sin))
    out, notes = run_cell(CELL)
    assert out["correct"] is False and not checks_of(notes)["step_logit_rel_rms"]["ok"]


def test_an_expert_share_off_by_one_is_not_correct(run_cell, monkeypatch):
    """The program computes experts 1..4 of 16 where the configuration (and the reference) hold 0..3: the
    step's own logits leave the reference's."""
    real = program.build_model

    def shifted(cfg, seed, dtype):
        model = real(cfg, seed, dtype)
        model.config.first_expert += 1
        return model

    monkeypatch.setattr(program, "build_model", shifted)
    out, notes = run_cell(CELL)
    assert out["correct"] is False and not checks_of(notes)["step_logit_rel_rms"]["ok"]
