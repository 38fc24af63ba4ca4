"""The hybrid configuration (``configs/nemotron-3-nano-30b-a3b.json``,
``reference/hybrid_ssm_moe.py``) through the seam, its arithmetic, and its
cell's controls at a CPU size: an altered token, a state that is not reset at
admission, and an expert share off by one each have to read ``correct:
false``. The cell's tiny rehearsal itself is ``test_rehearsal.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
from lib import flops, program, weights
from lib.drivers import serve
from reference import hybrid_ssm_moe as hybrid
from tests import tiny

CELL = "nemotron3nano.serve_chat"
FILE = "nemotron-3-nano-30b-a3b.json"
SEED = 2**31 + 33


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
    import os

    cfg = raw()
    entry = next(c for c in harness.load_json(harness.ROOT, "BENCHMARK.json")["configs"] if c["file"].endswith(FILE))
    assert cfg["source"] == entry["source"] and sorted(entry["reduced"]) == sorted(cfg["reduced"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key verbatim, but the three that are cut (which state the published value)
        row = next(json.loads(line) for line in open(catalog) if "Nemotron-3-Nano-30B" in line)
        for key, value in row["config"].items():
            got = cfg[key]["published"] if key in cfg["reduced"] else cfg[key]
            assert got == value, key
    run = published()
    assert (run["num_hidden_layers"], run["n_routed_experts"], run["vocab_size"]) == (26, 16, 16384)
    assert run["n_routed_experts_total"] == 128 and run["first_expert"] == 0
    assert run["hybrid_override_pattern"][:26] == "MEMEM*EMEMEM*EMEMEM*EMEMEM"
    assert {"positional_encoding", "state_dtype", "b_sel", "ssm_leaves", "initializer_range",
            "rescale_prenorm_residual"} <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"] and "two pipeline stages" in cfg["deployment"]


def test_leaf_table_arithmetic_and_counts():
    cfg = published()
    kinds = {k: next(i for i in range(26) if hybrid.kind(cfg, i) == k) for k in "ME*"}
    size = {k: sum(int(np.prod(shape)) for shape, _init in hybrid.layer_leaves(cfg, i).values()) for k, i in kinds.items()}
    assert round(size["M"] / 1e6, 2) == 38.74 and round(size["*"] / 1e6, 2) == 23.40 and round(size["E"] / 1e6, 2) == 179.95
    total = 12 * size["M"] + 3 * size["*"] + 11 * size["E"] + 2 * 16384 * 2688 + 2688
    assert round(total / 1e9, 2) == 2.60
    # the published model from the same table: 23 M, 6 *, 23 E with all 128 experts, the whole vocabulary
    whole = dict(cfg, n_routed_experts=128, vocab_size=131072)
    e_whole = sum(int(np.prod(shape)) for shape, _i in hybrid.layer_leaves(whole, kinds["E"]).values())
    assert round((23 * size["M"] + 6 * size["*"] + 23 * e_whole + 2 * 131072 * 2688) / 1e9, 2) == 31.58
    assert (hybrid.count(cfg, 26, "M"), hybrid.count(cfg, 26, "E"), hybrid.count(cfg, 26, "*")) == (12, 11, 3)
    assert flops.attention_passes(cfg, 26) == 3 and flops.head_dim(cfg) == 128
    assert flops.attention_passes(cfg, 26) * flops.paged_attention_bytes(cfg, 1) == 3 * 2 * 128 * 2 * 2  # 3 KB a token
    assert hybrid.state_bytes_per_slot(cfg) == 64 * 64 * 128 * 4 + 3 * 6144 * 2  # 2.13 MB a block; x 12 = 25.6 MB a slot
    # a token multiplies through the mixers, the shared experts, the head and 6 x 16 / 128 experts a block
    per_e = 2688 * 128 + 2 * 2688 * 3712 + 0.75 * 2 * 2688 * 1856
    per_m, per_a = 2688 * 10304 + 4096 * 2688, 2 * 2688 * 4096 + 2 * 2688 * 256
    assert hybrid.matmul_params(cfg, 26) == 12 * per_m + 3 * per_a + 11 * per_e + 2688 * 16384
    # the least a step moves: a routed expert only where it got a row; state read and written; KV; embedding rows
    idle = hybrid.step_hbm_bytes(cfg, 26, rows=0, slots_live=0, kv_tokens_live=0, experts_hit=0)
    assert idle == 2 * (12 * per_m + 3 * per_a + 11 * (2688 * 128 + 2 * 2688 * 3712) + 2688 * 16384)
    busy = hybrid.step_hbm_bytes(cfg, 26, rows=40, slots_live=10, kv_tokens_live=1000, experts_hit=30)
    assert busy - idle == 30 * 2 * 2688 * 1856 * 2 + 2 * 10 * 12 * hybrid.state_bytes_per_slot(cfg) + 1000 * 3072 + 40 * 2688 * 2
    names = program.param_names(small(), 6)
    assert names["layers"][1]["w_up"] == "backbone.layers.1.mixer.experts.up_proj"
    assert names["layers"][5]["wq"] == "backbone.layers.5.mixer.q_proj.weight"
    for i in range(6):
        weights.table(hybrid.layer_leaves(cfg, i))  # no two leaves of a block draw the same values
    with pytest.raises(NotImplementedError, match="no cell trains"):
        hybrid.batch_loss_and_grads()


def test_the_walk_a_block_at_a_time_is_the_whole_models_forward_and_the_seeded_leaves_lie_in_range():
    cfg = small()

    class Ctx:
        seed, cell = SEED, {"dtype": "bfloat16"}

    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32) for n in (5, 16, 11)]
    got = list(serve.reference_logits(Ctx, cfg, seqs, [8, 16, 8]))
    whole = weights.all_weights(SEED, cfg, cfg["num_hidden_layers"], "bfloat16")
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), whole)
    for toks, rows in zip(seqs, got):
        want = np.asarray(hybrid.forward_logits(jnp.asarray(toks), f32, cfg))
        np.testing.assert_allclose(np.asarray(rows), want, rtol=2e-4, atol=2e-5)
    m = f32["layers"][0]
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 0.9e-3 < dt.min() and dt.max() < 0.11  # time_step_min..time_step_max, as drawn (bf16 rounds them a little)
    assert 0.9 < float(jnp.exp(m["a_log"]).min()) and float(jnp.exp(m["a_log"]).max()) < 16.2
    assert float(jnp.abs(f32["layers"][1]["b_sel"]).max()) > 0  # a selection bias that is not zeros


def test_the_program_built_through_the_seam_is_the_reference():
    """``lib/program.py`` builds ``NemotronHForCausalLM`` from the file's ``program`` block and hands it the
    seeded leaves under the names the block gives: its plain forward is the reference's, float32."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    cfg = small()
    model = program.build_model(cfg, SEED, "float32")
    model.eval()
    assert model.config.n_routed_experts == 4 and model.config.n_routed_experts_total == 16 and model.config.pattern == "MEMEM*"
    whole = weights.all_weights(SEED, cfg, cfg["num_hidden_layers"], "float32")
    toks = np.random.default_rng(4).integers(0, cfg["vocab_size"], 19).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(model(Tensor(toks[None]))._data)[0]
    want = np.asarray(hybrid.forward_logits(jnp.asarray(toks), whole, cfg))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


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


@pytest.fixture
def dirty_slots(monkeypatch):
    """Every slot starts as if a long request had just left it: state planes full of threes, not zeros."""
    from paddle_tpu.inference import paged_kv

    def dirty(slots, spec, batch=None):
        return paged_kv.RecurrentState(*(jnp.full((slots,) + tuple(shape), 3.0, dtype) for shape, dtype in spec.planes),
                                       batch=batch)

    monkeypatch.setattr(paged_kv.RecurrentState, "zeros", staticmethod(dirty))


def test_the_reset_at_admission_is_what_cleans_a_slot(run_cell, tight_limits, dirty_slots):
    out, notes = run_cell(CELL)
    assert out["correct"] is True, checks_of(notes)


def test_a_state_that_is_not_reset_at_admission_is_not_correct(run_cell, tight_limits, monkeypatch):
    """The timed path broken underneath: a request's first chunk continues what the slot's last tenant left
    instead of starting from zero (the reset is made to see no first chunk, and the tenant is made a heavy
    one: at this size what a short request leaves moves the logits too little to change an argmax). Served
    tokens leave the reference's best logit, and the step's own logits the reference's."""
    from paddle_tpu.inference import paged_kv

    real = paged_kv.RecurrentState.advance

    def no_reset(self, *a, **kw):
        batch = self.batch
        first = (batch.seq_lens == 0) & batch.slot_mask
        left = [jnp.where(first.reshape((-1,) + (1,) * (p.ndim - 1)), 3.0, p).astype(p.dtype) for p in self.planes]
        blind = paged_kv.PagedBatch(batch.block_tables, batch.seq_lens + 1, batch.slot_mask, batch.q_lens)
        y, new = real(paged_kv.RecurrentState(*left, batch=blind), *a, **kw)
        return y, paged_kv.RecurrentState(*new.planes, batch=batch)

    monkeypatch.setattr(paged_kv.RecurrentState, "advance", no_reset)
    out, notes = run_cell(CELL)
    checks = checks_of(notes)
    assert out["correct"] is False and not checks["served_logit_gap_max"]["ok"] and not checks["step_logit_rel_rms"]["ok"]


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
