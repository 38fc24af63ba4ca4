"""The looped configuration (``configs/ouro-2.6b.json``, ``reference/
looped_decoder.py``) through the seam, its cell's controls at a CPU size, and
its three per-layer readers on runs whose answers are known. The cell's tiny
rehearsal itself is ``test_rehearsal.py``'s (it runs every cell of
``BENCHMARK.json``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
from lib import flops, program, weights
from lib.drivers import serve
from reference import looped_decoder as looped
from tests import tiny

CELL = "ouro2.6b.serve_reason"
SEED = 2**31 + 26


@pytest.fixture(autouse=True)
def float32_program():
    jax.config.update("jax_default_matmul_precision", "highest")


def published():
    return program.run_config(harness.load_json(harness.HERE, "configs", "ouro-2.6b.json"), "serve")


def small():
    return program.run_config(tiny.shrink(("configs", "ouro-2.6b.json"),
                                          harness.load_json(harness.HERE, "configs", "ouro-2.6b.json")), "serve")


def test_the_file_holds_the_published_keys_and_cuts_nothing():
    raw = harness.load_json(harness.HERE, "configs", "ouro-2.6b.json")
    entry = next(c for c in harness.load_json(harness.ROOT, "BENCHMARK.json")["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and raw["reduced"] == {} and raw["source"] == entry["source"]
    want = {"num_hidden_layers": 48, "hidden_size": 2048, "intermediate_size": 5632, "num_attention_heads": 16,
            "num_key_value_heads": 16, "head_dim": 128, "vocab_size": 49152, "total_ut_steps": 4,
            "early_exit_threshold": 1, "rope_theta": 1000000, "rms_norm_eps": 1e-06, "max_position_embeddings": 65536}
    assert {k: raw[k] for k in want} == want
    assert {"exit_gate", "layer_norms", "final_norm_between_passes", "biases", "initializer_range"} <= set(raw["assumed"])


def test_leaf_table_names_and_counts():
    cfg = published()
    leaves = looped.layer_leaves(cfg, 0)
    assert len(leaves) == 11 and sum(1 for _s, init in leaves.values() if init == "ones") == 4
    assert set(cfg["program"]["params"]["layer"]) == set(leaves)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert looped.layer_matmul_params(cfg) == layer
    assert looped.matmul_params(cfg, 48) == 4 * 48 * layer + 2048 * 49152
    assert flops.attention_passes(cfg, 48) == 192 and flops.head_dim(cfg) == 128
    # a token's KV: 2 x 192 sets x 16 heads x 128 x 2 B = 1.5 MiB
    assert flops.attention_passes(cfg, 48) * flops.paged_attention_bytes(cfg, 1) == 1.5 * 2**20
    # a step's bytes: the layers once a pass, the head once, the live KV once per attention call
    step = looped.step_hbm_bytes(cfg, 48, 4, 1000)
    assert step == 4 * 48 * layer * 2 + 2048 * 49152 * 2 + 1000 * 1.5 * 2**20
    assert looped.step_hbm_bytes(cfg, 48, 1, 0) == 48 * layer * 2 + 2048 * 49152 * 2
    # the program's parameter names are the table's, at the tiny size too
    names = program.param_names(small(), 2)
    assert names["layers"][1]["norm_mlp_out"] == "ouro.layers.1.post_attention_layernorm_2.weight"
    weights.table(leaves)  # no two leaves draw the same values


def test_the_walk_a_layer_at_a_time_is_the_whole_models_forward():
    cfg = small()

    class Ctx:
        seed, cell = SEED, {"dtype": "bfloat16"}

    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32) for n in (5, 16, 11)]
    got = list(serve.reference_logits(Ctx, cfg, seqs, [8, 16, 8]))
    whole = weights.all_weights(SEED, cfg, cfg["num_hidden_layers"], "bfloat16")
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), whole)
    for toks, rows in zip(seqs, got):
        want = np.asarray(looped.forward_logits(jnp.asarray(toks), f32, cfg))
        np.testing.assert_allclose(np.asarray(rows), want, rtol=2e-4, atol=2e-5)
    # four passes are not one, and the passes share the layers' weights: one pass of a
    # stack made of the two layers four times over equals four passes of the stack of two
    once = list(serve.reference_logits(Ctx, {**cfg, "total_ut_steps": 1}, seqs[:1], [8]))[0]
    assert float(jnp.abs(once - got[0]).max()) > 1e-3
    h = looped.base.embed(jnp.asarray(seqs[0]), f32["top"]["embed"])
    for _ in range(4):
        for w in f32["layers"]:
            h = looped.decoder_layer(h, w, cfg)
        h = looped.close_pass(h, f32["top"], cfg)
    np.testing.assert_allclose(np.asarray(looped.base.matmul(h, f32["top"]["head"])), np.asarray(got[0]),
                               rtol=2e-4, atol=2e-5)


def test_the_training_walk_is_the_last_passs_cross_entropy():
    cfg = small()
    whole = weights.all_weights(SEED, cfg, 2, "float32")
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg["vocab_size"], (2, 9)).astype(np.int32)
    loss, grads = looped.batch_loss_and_grads(whole, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]), cfg)
    logp = [jax.nn.log_softmax(looped.forward_logits(jnp.asarray(r[:-1]), whole, cfg)) for r in toks]
    want = -np.mean([float(lp[i, r[i + 1]]) for lp, r in zip(logp, toks) for i in range(8)])
    assert float(loss) == pytest.approx(want, rel=1e-5)
    # every leaf of every layer gets a gradient through all four passes
    assert all(float(jnp.abs(g).max()) > 0 for layer in grads["layers"] for g in layer.values())
    low, _g = looped.batch_loss_and_grads(whole, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]), cfg, "bf16")
    assert float(low) != float(loss)


def checks_of(notes):
    return {n["name"]: n for n in notes if n.get("note") == "check"}


@pytest.mark.parametrize("path", [{"kv_cache_dtype": "int8"}, {"weight_only_int8": True}], ids=["int8_kv", "weight_only_int8"])
def test_the_cells_control_the_programs_own_int8_path_is_not_correct(run_cell, monkeypatch, path):
    real = tiny.shrink

    def with_path(parts, data):
        data = real(parts, data)
        if parts[-2] == "workloads" and "engine" in data:
            data["engine"].update(path)
        return data

    sound, sound_notes = run_cell(CELL)
    monkeypatch.setattr(tiny, "shrink", with_path)
    out, notes = run_cell(CELL)
    low, ok = checks_of(notes)["step_logit_rel_rms"], checks_of(sound_notes)["step_logit_rel_rms"]
    assert sound["correct"] is True and ok["ok"]
    assert out["correct"] is False and not low["ok"]
    assert low["value"] > 3 * ok["value"]


def test_a_pass_that_reads_another_passs_kv_sets_is_not_correct(run_cell, monkeypatch):
    """The timed path broken underneath: the model hands pass 0 the KV sets of
    the last pass. Served tokens leave the reference's best logit."""
    from paddle_tpu.models import ouro

    real = ouro.OuroModel._forward_paged

    def crossed(self, input_ids, past_key_values, use_cache):
        n = len(self.layers)
        order = list(range(len(past_key_values)))
        order[:n] = order[-n:]
        out, caches = real(self, input_ids, [past_key_values[i] for i in order], True)
        back = list(caches)
        for at, i in enumerate(order):
            back[i] = caches[at]
        return out, back

    real_shrink = tiny.shrink

    def float32_limits(parts, data):
        # the cell's served-gap limits are three times what the bf16 engine reads at full width (0.15, 1.0);
        # this float32 engine reads 0 when sound, so hold it to a hundredth of them
        data = real_shrink(parts, data)
        if parts[-2] == "workloads" and "check" in data:
            data["check"]["limits"].update(served_logit_gap_max=0.03, served_logit_gap_mean=0.005)
        return data

    monkeypatch.setattr(ouro.OuroModel, "_forward_paged", crossed)
    monkeypatch.setattr(tiny, "shrink", float32_limits)
    monkeypatch.setattr(tiny, "OUTPUT", {"min": 8, "max": 12, "median": 10})
    out, notes = run_cell(CELL)
    checks = checks_of(notes)
    assert out["correct"] is False
    assert not checks["served_logit_gap_max"]["ok"] or not checks["served_logit_gap_mean"]["ok"], {k: (v["value"], v["ok"]) for k, v in checks.items()}


# -- the readers ---------------------------------------------------------------
def synthetic_run():
    cfg = published()
    pump = ("bench.frontend.pump", 0.0, 0.100)
    ops = [("%fusion.1 = bf16[16,16,2048]{2,1,0} fusion(...)", 0.010, 0.090)]
    return {"driver": "serve", "cfg": cfg, "depth": 48, "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"raw": {"spans": [pump, ("bench.frontend.pump", 0.100, 0.200)],
                              "devices": {0: ops + [(n, a + 0.1, b + 0.1) for n, a, b in ops]}}},
            "traced_pumps": [(0.0, 0.1, 1000, 70), (0.1, 0.2, 3000, 72)],
            "counters": {"engine": {"steps": 500, "loop_passes": 2000, "admit_blocked_steps.blocks": 25,
                                    "admit_blocked_steps.slots": 100},
                         "pool": {"bytes_per_token": 1.5 * 2**20}}}


def test_loop_step_hbm_roofline_reader():
    run = synthetic_run()
    need = looped.step_hbm_bytes(run["cfg"], 48, 4, 2000)  # mean live tokens of the traced pumps
    got = harness.load_reader("loop_step_hbm_roofline.serve").read(run)
    assert got == pytest.approx(100 * (need / 819e9) / 0.080) and 30 < got < 45
    # an int8 pool halves (and a bit) the KV bytes a value, nothing else
    run["counters"]["pool"]["bytes_per_token"] = 2 * 192 * 16 * (128 + 4)
    assert harness.load_reader("loop_step_hbm_roofline.serve").read(run) < got
    # the parent's program has no such counter; a reference that does not count a step's bytes
    del run["counters"]["engine"]["loop_passes"]
    assert harness.load_reader("loop_step_hbm_roofline.serve").read(run) is None
    plain = synthetic_run()
    plain["cfg"] = dict(plain["cfg"], reference="decoder")
    assert harness.load_reader("loop_step_hbm_roofline.serve").read(plain) is None
    untraced = dict(synthetic_run(), trace=None)
    assert harness.load_reader("loop_step_hbm_roofline.serve").read(untraced) is None


def test_admit_blocked_and_sandwich_norm_readers():
    run = synthetic_run()
    assert harness.load_reader("admit_blocked_pct.serve").read(run) == pytest.approx(5.0)
    assert harness.load_reader("admit_blocked_pct.serve").read({"counters": {"engine": {"steps": 3}}}) is None
    norm = "%rms_norm_fwd.7 = bf16[16,16,2048]{2,1,0} custom-call(...)"
    mlp = "%fusion.9 = bf16[16,16,5632]{2,1,0} fusion(...)"
    traced = {"trace": {"raw": {}}, "_program_trace": {
        "scopes": {norm: "jit(_step_impl)/jit(paged_pass)/loop_pass/norm/sandwich_norm/pallas_call:",
                   mlp: "jit(_step_impl)/jit(paged_pass)/loop_pass/mlp/dot_general:"},
        "spans": [], "window": (0.0, 0.02), "ops": [(norm, 0.001, 0.002, 0), (mlp, 0.002, 0.011, 0)]}}
    assert harness.load_reader("sandwich_norm_pct.serve").read(traced) == pytest.approx(10.0)
    del traced["_program_trace"]["scopes"][norm]
    assert harness.load_reader("sandwich_norm_pct.serve").read(traced) is None
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == ["loop_step_hbm_roofline.serve", "admit_blocked_pct.serve", "sandwich_norm_pct.serve"]
    assert json.dumps(mine).count("itl_p95_ms") == 2
