"""The seam between ``lib/`` and a configuration's reference file
(``lib/arch.py``): Mistral's seeded weights are what they were before the leaf
table moved into ``reference/decoder.py``, and a block that is not Llama's
(``tests/toy_block.py``) goes through the table, the names, the walk and the
counts with no line of ``lib/`` knowing it."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
from lib import flops, program, weights
from lib.drivers import serve
from reference import decoder
from tests import toy_block

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
TOY = {"hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "experts": 3, "vocab_size": 96, "num_hidden_layers": 3, "passes": 2,
       "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "initializer_range": 0.05, "reference": "toy_block",
       "program": {"params": {
           "embed": "tok.weight", "final_norm": "norm.weight", "head": "out.weight", "head_bias": "out.bias",
           "layer_prefix": "blocks.{i}.",
           "layer": {"wq": "q", "wk": "k", "wv": "v", "wo": "o", "gain": "gain", "norm1": "n1", "norm2": "n2",
                     "norm3": "n3", "norm4": "n4", "experts_up": "moe.up", "experts_down": "moe.down",
                     "w_up": "mlp.up", "w_down": "mlp.down"}}}}
SEED = 2**31 + 77


@pytest.fixture(autouse=True)
def toy_reference(monkeypatch):
    """``reference.toy_block`` is found where a configuration's file would be."""
    monkeypatch.setitem(sys.modules, "reference.toy_block", toy_block)
    jax.config.update("jax_default_matmul_precision", "highest")


def digest(x):
    a = np.asarray(x)
    a = a.view(np.uint16) if a.dtype.name == "bfloat16" else a
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def flat(whole):
    out = {f"top.{k}": v for k, v in whole["top"].items()}
    for i, layer in enumerate(whole["layers"]):
        out.update({f"L{i}.{k}": v for k, v in layer.items()})
    return out


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_mistrals_seeded_weights_are_bit_identical_to_the_parents(seed):
    with open(os.path.join(TESTDATA, "mistral_weights.sha256.json")) as fh:
        recorded = json.load(fh)
    want, tiny = recorded["seeds"][str(seed)], dict(recorded["tiny_widths"])
    published = program.run_config(harness.load_json(harness.HERE, "configs", "mistral-7b-v0.3.json"), "serve")
    small = dict(published, **tiny)
    for dtype in ("float32", "bfloat16"):
        got = flat(weights.all_weights(seed, small, tiny["depth"], dtype))
        assert {k: digest(v) for k, v in got.items()} == want[f"tiny_{dtype}"], dtype
    # at the published widths: 58.7 M and 134 M values
    assert digest(weights.layer_weights(seed, published, 1, "bfloat16")["w_down"]) == want["published_bfloat16"]["L1.w_down"]
    assert digest(weights.top_weights(seed, published, "bfloat16")["head"]) == want["published_bfloat16"]["top.head"]


def test_a_layer_alone_equals_the_one_calls_layer_bitwise_whatever_its_leaves():
    whole = weights.all_weights(SEED, TOY, 3, "bfloat16")
    assert set(whole["layers"][0]) - set(whole["layers"][1]) == {"experts_up", "experts_down"}
    assert set(whole["layers"][1]) - set(whole["layers"][0]) == {"w_up", "w_down"}
    assert whole["layers"][0]["experts_up"].shape == (3, 32, 48) and whole["layers"][0]["wq"].shape == (32, 64)
    for i in range(3):
        one = weights.layer_weights(SEED, TOY, i, "bfloat16")
        assert set(one) == set(whole["layers"][i]) == set(toy_block.layer_leaves(TOY, i))
        assert all(digest(one[k]) == digest(whole["layers"][i][k]) for k in one)
    top = weights.top_weights(SEED, TOY, "bfloat16")
    assert all(digest(top[k]) == digest(whole["top"][k]) for k in whole["top"])
    # the four inits: zeros, ones, normal at the file's std, and the reference file's own function
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    assert not f32(top["head_bias"]).any() and (f32(whole["layers"][2]["norm4"]) == 1).all()
    assert float(f32(whole["layers"][0]["experts_up"]).std()) == pytest.approx(0.05, rel=0.1)
    gain = f32(whole["layers"][1]["gain"])
    assert 0.89 < gain.min() < gain.max() < 1.11 and digest(gain) != digest(f32(whole["layers"][2]["gain"]))


def test_leaves_whose_names_are_anagrams_of_each_other_are_refused():
    with pytest.raises(ValueError, match="anagrams"):
        weights.table({"wk": ((4, 4), "normal"), "kw": ((4, 4), "normal")})
    with pytest.raises(ValueError, match="init"):
        weights.table({"wk": ((4, 4), "uniform")})


class StandIn:
    """A model that has parameters and nothing else."""

    class Parameter:
        def set_value(self, value):
            self.value = value

    def __init__(self, names):
        self.params = {name: self.Parameter() for name in names}

    def named_parameters(self):
        return list(self.params.items())


def test_install_weights_takes_exactly_the_tables_names():
    names = program.param_names(TOY, 3)
    assert names["top"]["head_bias"] == "out.bias" and names["layers"][2]["experts_up"] == "blocks.2.moe.up"
    assert "w_up" not in names["layers"][2] and names["layers"][1]["norm4"] == "blocks.1.n4"
    wanted = [n for group in [names["top"], *names["layers"]] for n in group.values()]
    whole = weights.all_weights(SEED, TOY, 3, "float32")
    model = StandIn(wanted)
    program.install_weights(model, TOY, whole)
    assert digest(model.params["blocks.1.mlp.down"].value) == digest(whole["layers"][1]["w_down"])
    with pytest.raises(KeyError, match="only in program"):
        program.install_weights(StandIn(wanted + ["blocks.0.extra_norm"]), TOY, whole)
    with pytest.raises(KeyError, match="only in benchmark"):
        program.install_weights(StandIn(wanted[:-1]), TOY, whole)
    # a top leaf that the configuration's file does not name, or names beside the table
    unnamed = {**TOY, "program": {"params": {k: v for k, v in TOY["program"]["params"].items() if k != "head_bias"}}}
    with pytest.raises(KeyError, match="top leaves"):
        program.param_names(unnamed, 3)


def whole_model_forward(tokens, weights_, cfg):
    """The toy block, written out again over the whole model's weights at once."""
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    top, layers = f32(weights_["top"]), f32(weights_["layers"])
    norm = lambda x, w: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["rms_norm_eps"]) * w  # noqa: E731
    t, nh, nkv, hd = len(tokens), cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pos, h = jnp.arange(t), top["embed"][tokens]
    for _ in range(cfg["passes"]):
        for w in layers:
            x = norm(h, w["norm1"])
            q = decoder.rope((x @ w["wq"]).reshape(t, nh, hd), pos, cfg["rope_theta"])
            k = decoder.rope((x @ w["wk"]).reshape(t, nkv, hd), pos, cfg["rope_theta"])
            a = decoder.attention(q, k, (x @ w["wv"]).reshape(t, nkv, hd)).reshape(t, nh * hd)
            h = h + norm(a @ w["wo"], w["norm2"])
            x = norm(h, w["norm3"])
            if "w_up" in w:
                y = jax.nn.silu(x @ w["w_up"]) @ w["w_down"]
            else:
                y = jnp.einsum("eti,eih->th", jax.nn.silu(jnp.einsum("th,ehi->eti", x, w["experts_up"])), w["experts_down"])
            h = h + norm(y, w["norm4"]) * w["gain"]
        h = norm(h, top["final_norm"])
    return h @ top["head"] + top["head_bias"]


def test_the_reference_files_walk_is_the_one_the_serving_check_takes():
    class Ctx:
        seed, cell = SEED, {"dtype": "bfloat16"}

    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, TOY["vocab_size"], n).astype(np.int32) for n in (5, 16, 11)]
    got = list(serve.reference_logits(Ctx, TOY, seqs, [8, 16, 8]))
    whole = weights.all_weights(SEED, TOY, TOY["num_hidden_layers"], "bfloat16")
    assert [g.shape for g in got] == [(5, 96), (16, 96), (11, 96)]
    for toks, rows in zip(seqs, got):
        want = np.asarray(whole_model_forward(toks, whole, TOY))
        np.testing.assert_allclose(np.asarray(rows), want, rtol=2e-4, atol=2e-5)
    # twice through the stack is not once through it
    once = list(serve.reference_logits(Ctx, {**TOY, "passes": 1}, seqs[:1], [8]))[0]
    assert float(jnp.abs(once - got[0]).max()) > 1e-3


def test_counts_follow_the_passes_and_the_files_head_dim():
    one = {**TOY, "passes": 1}
    head = TOY["hidden_size"] * TOY["vocab_size"]
    assert toy_block.matmul_params(TOY, 3) - head == 2 * (toy_block.matmul_params(one, 3) - head) > 0
    assert flops.attention_passes(TOY, 3) == 2 * flops.attention_passes(one, 3) == 6
    assert flops.head_dim(TOY) == 16 != TOY["hidden_size"] // TOY["num_attention_heads"]
    # the yardsticks read them: 4 heads x 16, not 4 x 8; KV of 2 heads x 16
    assert flops.attention_flops_fwd(TOY, 8) == 2 * 2 * 4 * 16 * 36
    assert flops.paged_attention_bytes(TOY, 100) == 2 * 100 * 2 * 16 * 2
    per_token = flops.train_flops_per_token(TOY, 3, 8)
    assert per_token == 6.0 * toy_block.matmul_params(TOY, 3) + 3.0 * 6 * flops.attention_flops_fwd(TOY, 8) / 8
    # and Mistral's are what lib/flops.py counted itself before the seam
    m = program.run_config(harness.load_json(harness.HERE, "configs", "mistral-7b-v0.3.json"), "train")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert decoder.matmul_params(m, 2) == 2 * layer + 4096 * 32768 and flops.attention_passes(m, 2) == 2
    assert flops.head_dim(m) == 128 and flops.head_dim({**m, "head_dim": 64}) == 64
    assert flops.train_flops_per_token(m, 2, 2048) == 6.0 * (2 * layer + 4096 * 32768) + 3.0 * 2 * (2 * 2 * 32 * 128 * 2048 * 2049 / 2) / 2048
