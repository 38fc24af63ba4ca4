"""CPU rehearsal of ``chip_smoke.py``'s control flow, at a tiny width.

The script has no CPU mode (it fails before any phase unless JAX reports a
TPU), so the rehearsal imports its phase functions: wrong paths, arguments
and bookkeeping are found here, for free, instead of on the chip. The
multi-chip phases run on the suite's virtual CPU devices.
"""

import importlib.util
import json
import os
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # chip_smoke.py imports its sibling __graft_entry__
    sys.path.insert(0, _ROOT)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def metrics_on():
    prior = paddle.get_flags(["FLAGS_enable_metrics"])
    paddle.set_flags({"FLAGS_enable_metrics": True})
    yield
    paddle.set_flags(prior)


_TINY_ENGINE = dict(
    max_slots=3, block_size=4, num_blocks=64, max_model_len=64, prompt_bucket=24
)
_TINY_REQUESTS = dict(prompt_lens=(3, 4, 9, 17), max_new_tokens=6)


def _lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_refuses_to_run_without_a_tpu(smoke):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu(1)


def test_tpu_place_raises_without_a_tpu_device():
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.CPUPlace().jax_device().platform == "cpu"


def test_train_phase(smoke, capsys, metrics_on):
    smoke.phase_train(LlamaConfig.tiny(), batch=2, seq=32, steps=3, dtype="float32")
    (rec,) = _lines(capsys)
    assert rec["phase"] == "train" and rec["step_compiles"] == 1
    assert len(rec["losses"]) == 4 and rec["losses"][-1] < rec["losses"][0]
    assert rec["grads_checked"] > 0


def test_serve_phase(smoke, capsys, metrics_on):
    fallbacks_before = smoke.fallback_counts()  # process-wide: other tests count too
    smoke.phase_serve(
        LlamaConfig.tiny(), dtype="float32", engine_kw=_TINY_ENGINE, int8_num_blocks=32,
        **_TINY_REQUESTS,
    )
    serve, int8 = _lines(capsys)
    assert [r["outcome"] for r in serve["requests"]] == ["ok"] * 4
    assert serve["step_compiles"] == 1 and serve["max_logit_error"] < 1e-4
    assert serve["vs_dense_generate"]["token_match_rate"] == 1.0
    assert serve["pool"]["free"] + serve["pool"]["cached_blocks"] == serve["pool"]["total"]
    assert int8["phase"] == "serve_int8_kv" and int8["tokens"] == 6
    assert smoke.fallback_counts() == fallbacks_before


def test_tp_engine_phase(smoke, capsys):
    smoke.phase_tp_engine(
        LlamaConfig.tiny(), tp=2, dtype="float32", engine_kw=_TINY_ENGINE, **_TINY_REQUESTS
    )
    (rec,) = _lines(capsys)
    assert all(m["token_match_rate"] == 1.0 for m in rec["vs_tp1"])
    assert rec["cache_shard_devices"] == [0, 1]
    assert any(s.endswith("|tp2") for s in rec["signatures"])


def test_hybrid_train_phase(smoke, capsys):
    smoke.phase_hybrid_train(
        LlamaConfig.tiny(vocab=128), n_devices=4, batch=4, seq=32, steps=2, dtype="float32"
    )
    (rec,) = _lines(capsys)
    assert rec["mesh"] == {"dp": 1, "sharding": 2, "mp": 2}
    assert rec["params_keep_named_sharding"] and rec["param_device_counts"] == [4]


def test_token_match_reports_first_divergence(smoke):
    assert smoke.token_match([1, 2, 3], [1, 2, 3]) == {
        "token_match_rate": 1.0, "first_divergence": None,
    }
    assert smoke.token_match([1, 2, 3, 4], [1, 9, 3]) == {
        "token_match_rate": 0.5, "first_divergence": 1,
    }


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from paddle_tpu.core import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert calls == []  # jax reads the variable itself: nothing is set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed)]


def test_every_trace_of_a_sharded_step_is_marked_gspmd_partitioned(monkeypatch):
    """A bare ``pallas_call`` cannot be GSPMD-partitioned (Mosaic refuses at
    lowering), so every trace of a step whose state spans devices must tell
    the kernel dispatch so — including jit's own re-trace on the second call
    (first seen on four real chips: the re-trace took the Pallas branch)."""
    import numpy as np

    import paddle_tpu.kernels.select as sel
    from __graft_entry__ import build_hybrid_train_step, hybrid_mesh, shard_batch
    from paddle_tpu.models.llama import LlamaForCausalLM

    seen = []
    real = sel.pallas_enabled
    monkeypatch.setattr(
        sel, "pallas_enabled",
        lambda flag, **kw: seen.append(sel._gspmd_partitioned()) or real(flag, **kw),
    )
    cfg = LlamaConfig(  # widths the kernel dispatch sites accept
        vocab_size=128, hidden_size=256, intermediate_size=256, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=64,
    )
    ids = np.random.default_rng(0).integers(0, 128, (4, 32)).astype(np.int32)
    for mesh, expect in ((None, False), (hybrid_mesh(4), True)):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        step = build_hybrid_train_step(model, mesh)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
        x, y = (paddle.to_tensor(ids),) * 2 if mesh is None else shard_batch(mesh, ids, ids)
        del seen[:]
        for _ in range(3):
            step(model, opt, x, y)
        assert seen and set(seen) == {expect}
