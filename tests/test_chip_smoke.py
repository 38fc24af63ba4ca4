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
    assert serve["step_compiles"] == 1
    assert serve["step_logits_vs_dense"]["max_logit_error"] < 1e-4
    assert serve["first_token_logit_gaps"] == [0.0] * 4  # every first token: dense argmax
    assert serve["vs_dense_generate"]["token_match_rate"] == 1.0
    assert serve["phase_peak_bytes"] and serve["bytes_in_use"]
    assert serve["pool"]["free"] + serve["pool"]["cached_blocks"] == serve["pool"]["total"]
    assert int8["phase"] == "serve_int8_kv" and int8["tokens"] == 6
    assert smoke.fallback_counts() == fallbacks_before


def test_tp_engine_phase(smoke, capsys):
    smoke.phase_tp_engine(
        LlamaConfig.tiny(), tp=2, dtype="float32", engine_kw=_TINY_ENGINE, **_TINY_REQUESTS
    )
    (rec,) = _lines(capsys)
    assert all(m["token_match_rate"] == 1.0 for m in rec["vs_tp1"])
    assert len(rec["step_logits_vs_tp1"]) == 2  # the two prompts that fit one chunk
    assert all(e["max_logit_error"] < 1e-4 for e in rec["step_logits_vs_tp1"])
    assert rec["first_token_logit_gaps_vs_dense"] == {"tp2": [0.0] * 4, "tp1": [0.0] * 4}
    assert rec["ran_xla_under_partitioning"] == {}  # nothing is dispatched to Pallas on CPU
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


def test_serve_gates_fail_on_a_wrong_token_or_wrong_logits(smoke, monkeypatch, metrics_on):
    """The gates are on what the engine served and on its own step's logits:
    a step that hands back shifted logits, or a stream whose first token is
    not what the dense forward ranks best, fails the phase."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    kw = dict(
        dtype="float32", engine_kw=_TINY_ENGINE, int8_num_blocks=32, **_TINY_REQUESTS
    )
    real_logits = ContinuousBatchingEngine.step_logits
    monkeypatch.setattr(
        ContinuousBatchingEngine, "step_logits", lambda self, p: real_logits(self, p) + 1.0
    )
    with pytest.raises(AssertionError, match="step logits vs dense"):
        smoke.phase_serve(LlamaConfig.tiny(), **kw)
    monkeypatch.setattr(ContinuousBatchingEngine, "step_logits", real_logits)

    real_post = smoke.post_generate

    def wrong_first_token(port, prompt, max_new_tokens):
        reply = real_post(port, prompt, max_new_tokens)
        reply["tokens"][0] = (reply["tokens"][0] + 1) % LlamaConfig.tiny().vocab_size
        return reply

    monkeypatch.setattr(smoke, "post_generate", wrong_first_token)
    with pytest.raises(AssertionError, match="served first token"):
        smoke.phase_serve(LlamaConfig.tiny(), **kw)


def test_step_logits_is_the_steps_own_body_and_leaves_the_engine_untouched():
    """``step_logits`` runs ``_step_forward`` — the function ``_step_impl``
    takes its argmax of — so its argmax IS the step's first token, and it
    neither counts as a step trace nor touches the pool."""
    import numpy as np

    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    for kv in ("bf16", "int8"):
        eng = ContinuousBatchingEngine(model, kv_cache_dtype=kv, **_TINY_ENGINE)
        prompt = np.arange(1, 5, dtype=np.int32)  # one chunk
        logits = eng.step_logits(prompt)
        assert logits.shape == (4, LlamaConfig.tiny().vocab_size)
        assert eng.stats["step_traces"] == 0 and eng.stats["steps"] == 0
        eng.add_request(prompt, max_new_tokens=2)
        (req,) = eng.run().values()
        assert req.generated[0] == int(np.argmax(logits[-1]))
        assert eng.stats["step_traces"] == 1


def _sharded_step_traces(monkeypatch, calls=3):
    """Run the hybrid step ``calls`` times on one device, then on a 4-device
    mesh, recording the trace's partition mark at every kernel dispatch."""
    import numpy as np

    import paddle_tpu.kernels.select as sel
    from __graft_entry__ import build_hybrid_train_step, hybrid_mesh, shard_batch
    from paddle_tpu.core.spmd import trace_partition
    from paddle_tpu.models.llama import LlamaForCausalLM

    seen = []
    real = sel.pallas_enabled
    monkeypatch.setattr(
        sel, "pallas_enabled",
        lambda flag, **kw: seen.append(trace_partition()) or real(flag, **kw),
    )
    cfg = LlamaConfig(  # widths the kernel dispatch sites accept
        vocab_size=128, hidden_size=256, intermediate_size=256, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=64,
    )
    ids = np.random.default_rng(0).integers(0, 128, (4, 32)).astype(np.int32)
    out = {}
    for mesh in (None, hybrid_mesh(4)):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        step = build_hybrid_train_step(model, mesh)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
        x, y = (paddle.to_tensor(ids),) * 2 if mesh is None else shard_batch(mesh, ids, ids)
        del seen[:]
        for _ in range(calls):
            step(model, opt, x, y)
        out[mesh is not None] = list(seen)
    return out


def test_every_trace_of_a_sharded_step_is_marked_partitioned(monkeypatch):
    """A bare ``pallas_call`` cannot be GSPMD-partitioned (Mosaic refuses at
    lowering), so every trace of a step whose state spans devices must tell
    the kernel dispatch so — including jit's own re-trace on the second call
    (first seen on four real chips: the re-trace took the Pallas branch)."""
    from paddle_tpu.core.spmd import LAYOUT_UNKNOWN

    seen = _sharded_step_traces(monkeypatch)
    assert seen[False] and set(seen[False]) == {None}
    assert seen[True] and set(seen[True]) == {LAYOUT_UNKNOWN}


def test_routing_under_a_partitioned_trace_is_counted_and_warned(monkeypatch, metrics_on, caplog):
    """On a TPU backend the sites would take their kernels; under a
    partitioned trace they run XLA instead — never silently: one count per
    dispatch in its own series, one WARNING per kernel, and no fallback
    count (nothing failed). The set is what ``chip_smoke.py --chips 4``
    allows its hybrid phase."""
    import logging

    import jax

    import paddle_tpu.kernels.select as sel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sel, "_routed_warned", set())
    routed0, fallbacks0 = sel.partition_routed_counts(), sel.fallback_counts()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.kernels"):
        seen = _sharded_step_traces(monkeypatch, calls=0)  # no single-device Pallas on CPU
        assert seen == {False: [], True: []}
        from paddle_tpu.core.spmd import LAYOUT_UNKNOWN, partitioned_trace

        with partitioned_trace(LAYOUT_UNKNOWN):
            assert not sel.pallas_enabled("use_pallas_fused", bare="fused_rope")
            assert not sel.pallas_enabled("use_pallas_fused", bare="fused_rope")
            assert not sel.pallas_enabled("use_pallas_fused", bare="fused_rms_norm", row_wise=True)
            assert sel.pallas_enabled("use_pallas_paged_attention")  # shard_maps itself
        assert sel.pallas_enabled("use_pallas_fused", bare="fused_rope")  # one device
    routed = {
        k: v - routed0.get(k, 0) for k, v in sel.partition_routed_counts().items()
        if v > routed0.get(k, 0)
    }
    assert routed == {"fused_rope": 2, "fused_rms_norm": 1}
    assert sel.fallback_counts() == fallbacks0
    warned = [r.getMessage() for r in caplog.records if "partitioned over devices" in r.getMessage()]
    assert len(warned) == 2 and "fused_rope" in warned[0]


def test_row_wise_kernels_run_per_shard_under_a_shard_group(monkeypatch):
    """Under the engine's tp mesh the layout is known (hidden states
    replicated), so a row-wise site keeps its kernel: ``per_shard`` wraps it
    in a replicated ``shard_map``; with no mesh armed it is the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.kernels.select as sel
    from paddle_tpu.distributed.tp import build_tp_mesh, tp_shard_context

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel = lambda x, w: x * w  # noqa: E731
    assert sel.per_shard(kernel) is kernel
    x, w = jnp.arange(8.0).reshape(2, 4), jnp.full((4,), 2.0)
    with tp_shard_context(build_tp_mesh(2)):
        assert sel.pallas_enabled("use_pallas_fused", bare="fused_rms_norm", row_wise=True)
        assert not sel.pallas_enabled("use_pallas_fused", bare="fused_embed_norm")
        out = jax.jit(lambda a, b: sel.per_shard(kernel)(a, b))(x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x * w))
    assert len(out.sharding.device_set) == 2  # ran on the mesh, replicated


def test_replacing_a_model_onto_a_mesh_retraces_the_step():
    """The partition mark is read at trace time, so it is part of the
    ``to_static`` cache key: a model moved onto a mesh in place (same Tensor
    ids) must not reuse the single-device trace, nor the reverse."""
    import numpy as np

    import paddle_tpu.distributed as dist
    from paddle_tpu.core.spmd import trace_partition

    marks = []

    @paddle.jit.to_static
    def fwd(layer, x):
        marks.append(trace_partition())
        return layer(x)

    paddle.seed(0)
    layer = paddle.nn.Linear(8, 8)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    fwd(layer, x)
    fwd(layer, x)
    assert marks == [None]
    from paddle_tpu.distributed.api import apply_placement
    from paddle_tpu.distributed.placements import Replicate

    mesh = dist.ProcessMesh(np.arange(2), ["dp"])
    ids = [id(p) for p in layer.parameters()]
    for p in layer.parameters():
        apply_placement(p, mesh, [Replicate()])
        assert len(p._data.sharding.device_set) == 2
    assert ids == [id(p) for p in layer.parameters()]  # re-placed in place
    fwd(layer, x)
    assert len(marks) == 2 and marks[1] is not None
