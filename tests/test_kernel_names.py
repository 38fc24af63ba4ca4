"""Every ``pl.pallas_call`` in ``paddle_tpu/kernels/`` carries a stable
``name=`` (what a device trace calls the kernel), and the model, the optimizer
and the engine's step body put their operations under ``jax.named_scope``s.

The kernels are lowered for the TPU platform without compiling (Mosaic's
lowering runs on any host; ``tests/test_tpu_aot_compile.py`` is where they are
compiled): the lowered text holds ``kernel_name = "<name>"`` on the custom
call. The dispatch helpers would take their CPU branch here, so each kernel's
entry is called directly.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
H, D, HEADS, SEQ, VOCAB = 256, 128, 2, 256, 512
SLOTS, CHUNK, NB, BS, MBS = 2, 16, 8, 16, 4


def _grad_all(fn, n_float):
    return jax.grad(lambda *a: fn(*a).astype(F32).sum(), argnums=tuple(range(n_float)))


def _flash():
    from paddle_tpu.kernels.flash_attention import flash_attention_pallas

    qkv = ((1, SEQ, HEADS, D), BF16)
    return _grad_all(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True), 3), (qkv, qkv, qkv)


def _fused_loss():
    from paddle_tpu.kernels.fused_loss import _pallas_path

    def fn(x, w, lab):
        return jax.grad(
            lambda x, w: _pallas_path(
                x, w, lab, v=VOCAB, h=H, ignore_index=-100, reduction="mean",
                vocab_major=False, interpret=False, block=(128, 128),
            ),
            argnums=(0, 1),
        )(x, w)

    return fn, (((SEQ, H), BF16), ((H, VOCAB), BF16), ((SEQ,), I32))


def _fused_loss_quant():
    from paddle_tpu.kernels.fused_loss import _pallas_quant_path

    def fn(x, w, s, lab):
        return _pallas_quant_path(
            x, w, s, lab, v=VOCAB, h=H, ignore_index=-100, reduction="mean",
            vocab_major=False, interpret=False, block=(128, 128),
        )

    return fn, (((SEQ, H), BF16), ((H, VOCAB), I8), ((VOCAB,), F32), ((SEQ,), I32))


def _rms():
    from paddle_tpu.kernels.fused import fused_rms_norm_pallas

    return _grad_all(lambda x, w: fused_rms_norm_pallas(x, w, 1e-6), 2), (((1, SEQ, H), BF16), ((H,), BF16))


def _rope():
    from paddle_tpu.kernels.fused import fused_rope_pallas, rope_adjoint_pallas

    qkv, tab = ((1, SEQ, HEADS, D), BF16), ((SEQ, D), F32)
    return (lambda x, g, c, s: (fused_rope_pallas(x, c, s), rope_adjoint_pallas(g, c, s))), (qkv, qkv, tab, tab)


def _rms_residual():
    from paddle_tpu.kernels.fused import fused_rms_norm_residual_pallas, rms_norm_residual_adjoint_pallas

    def fn(x, res, w, g):
        y, r = fused_rms_norm_residual_pallas(x, res, w, 1e-6)
        return y, r, rms_norm_residual_adjoint_pallas(g, r, w, 1e-6)

    x = ((1, SEQ, H), BF16)
    return fn, (x, x, ((H,), BF16), x)


def _ln_residual():
    from paddle_tpu.kernels.fused import fused_layer_norm_residual_pallas, layer_norm_residual_adjoint_pallas

    def fn(x, res, w, b, g):
        y, r = fused_layer_norm_residual_pallas(x, res, w, b, 1e-5)
        return y, r, layer_norm_residual_adjoint_pallas(g, r, w, 1e-5)

    x, w = ((1, SEQ, H), BF16), ((H,), BF16)
    return fn, (x, x, w, w, x)


def _embed_rms():
    from paddle_tpu.kernels.fused import fused_embed_rms_norm_pallas

    return (
        lambda ids, table, w: fused_embed_rms_norm_pallas(ids, table, w, 1e-6),
        (((SLOTS, CHUNK), I32), ((VOCAB, H), BF16), ((H,), BF16)),
    )


def _wo_matmul():
    from paddle_tpu.kernels.quant import _default_block, _wo_matmul_pallas

    m, k, n = 128, 256, 256
    block = _default_block(m, k, n)
    return (lambda x, w8, s: _wo_matmul_pallas(x, w8, s, block)), (((m, k), BF16), ((k, n), I8), ((n,), F32))


_POOL = ((NB, HEADS, BS, D), BF16)
_TABLES = ((SLOTS, MBS), I32)
_LENS = ((SLOTS,), I32)


def _paged_chunk():
    from paddle_tpu.kernels.paged_attention import paged_flash_chunk

    return paged_flash_chunk, (((SLOTS, CHUNK, HEADS, D), BF16), _POOL, _POOL, _TABLES, _LENS, _LENS)


def _paged_chunk_fused():
    from paddle_tpu.kernels.paged_attention import paged_flash_chunk

    cs = ((SLOTS, CHUNK, D), BF16)
    fn = lambda q, cos, sin, *rest: paged_flash_chunk(q, *rest, cos=cos, sin=sin)  # noqa: E731
    return fn, (((SLOTS, CHUNK, HEADS, D), BF16), cs, cs, _POOL, _POOL, _TABLES, _LENS, _LENS)


def _latent_chunk():
    from paddle_tpu.kernels.paged_attention import paged_latent_chunk

    fn = lambda q, pool, *rest: paged_latent_chunk(q, pool, *rest, value_width=D)  # noqa: E731
    return fn, (((SLOTS, CHUNK, HEADS, 2 * D), BF16), ((NB, 1, BS, 2 * D), BF16), _TABLES, _LENS, _LENS)


def _ssm_scan():
    from paddle_tpu.kernels.ssm_scan import ssm_state_scan

    s, h, p, n, g = SLOTS, 4, 64, 128, 2
    rows = ((s, CHUNK, g, n), F32)
    return ssm_state_scan, (rows, rows, ((s, CHUNK, h, p), F32), ((s, h), F32), ((s, h, p, n), F32),
                            ((s,), jnp.bool_), ((s,), jnp.bool_))


# kernel name -> the entry whose lowering has to hold it (one pallas_call site
# each; the rope runner is one site that two kernels share)
SITES = {
    "flash_attention_fwd": _flash, "flash_attention_dq": _flash, "flash_attention_dkv": _flash,
    "fused_loss_fwd": _fused_loss, "fused_loss_dx": _fused_loss, "fused_loss_dw": _fused_loss,
    "fused_loss_fwd_quant": _fused_loss_quant,
    "paged_attention_chunk": _paged_chunk, "paged_attention_chunk_fused": _paged_chunk_fused,
    "paged_latent_attention_chunk": _latent_chunk,
    "ssm_state_scan": _ssm_scan,
    "rms_norm_fwd": _rms, "rms_norm_bwd": _rms,
    "rope_fwd": _rope, "rope_adjoint": _rope,
    "rms_norm_residual_fwd": _rms_residual, "rms_norm_residual_adjoint": _rms_residual,
    "layer_norm_residual_fwd": _ln_residual, "layer_norm_residual_adjoint": _ln_residual,
    "embed_rms_norm": _embed_rms,
    "weight_only_int8_matmul": _wo_matmul,
}
_LOWERED = {}


def _kernel_names(builder):
    if builder not in _LOWERED:
        fn, shapes = builder()
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        # conftest pins "highest"; Mosaic refuses that on bf16 operands
        with jax.default_matmul_precision("default"):
            text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        _LOWERED[builder] = set(re.findall(r'kernel_name = "([^"]+)"', text))
    return _LOWERED[builder]


@pytest.mark.parametrize("name", sorted(SITES))
def test_lowered_kernel_carries_its_name(name):
    assert name in _kernel_names(SITES[name])


def test_every_pallas_call_site_passes_a_name_constant():
    """The 21 sites, read from the source (the paged chunk kernel, plain and
    rope-fused, is one; the latent walk, PR 36, is the eighteenth; the loss
    head's dX and dW are two each since PR 37, storing ``d`` or recomputing
    it, under the same two names; the state-space scan's carried-state
    kernel, PR 39, is the twenty-first): each ``pl.pallas_call(`` has a ``name=``
    keyword, and every name is one of the constants above."""
    import ast
    import inspect

    from paddle_tpu.kernels import flash_attention, fused, fused_loss, paged_attention, quant, ssm_scan

    sites, constants = 0, set()
    for module in (flash_attention, fused, fused_loss, paged_attention, quant, ssm_scan):
        tree = ast.parse(inspect.getsource(module))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and node.func.attr == "pallas_call":
                sites += 1
                assert any(kw.arg == "name" for kw in node.keywords), f"{module.__name__}:{node.lineno}"
        constants |= {v for k, v in vars(module).items() if k.startswith("KERNEL_")}
    assert sites == 21
    assert constants == set(SITES)
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", c) for c in constants)  # no shapes, trace-safe


def test_train_step_lowering_holds_the_model_and_optimizer_scopes(monkeypatch):
    """Forward, backward (``transpose(jvp(<scope>))``) and the update of a tiny
    Llama train step, as the lowered text's locations name them."""
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    texts = []
    real_jit = jax.jit

    def spy(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") != "staged":  # to_static's staged step
            return jitted

        def call(*args):
            texts.append(jitted.lower(*args).as_text(debug_info=True))
            return jitted(*args)

        return call

    model = LlamaForCausalLM(LlamaConfig.tiny())
    opt = paddle_tpu.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def step(model, opt, ids, labels):  # state rides in through the arguments
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle_tpu.to_tensor(np.arange(16, dtype=np.int32).reshape(2, 8) % 50)
    monkeypatch.setattr(jax, "jit", spy)
    paddle_tpu.jit.to_static(step)(model, opt, ids, ids)
    monkeypatch.undo()
    assert texts, "to_static staged no step"
    text = texts[0]
    for scope in ("embedding", "norm", "attention", "mlp", "loss_head"):
        assert f"jit(staged)/{scope}/jvp(" in text, scope
        # the tape's reverse sweep runs each node's vjp under its forward's scope
        assert f"jit(staged)/{scope}/transpose(" in text, scope
    # the update is a nested jit: its locations are relative to that call
    assert 'loc("optimizer_update/' in text
    assert "jit(staged)/transpose(" not in text  # no backward op outside a scope


def test_engine_step_lowering_holds_the_step_body_scopes():
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    eng = ContinuousBatchingEngine(model, max_slots=2, block_size=4, prompt_bucket=16)
    s, c = eng.max_slots, eng.prefill_chunk
    zeros = jnp.zeros((s,), jnp.int32)
    lowered = eng._step_fn.lower(
        eng._param_arrays(), eng._caches, jnp.zeros((s, c), jnp.int32),
        jnp.zeros((s, eng.max_blocks_per_seq), jnp.int32), zeros, zeros,
        jnp.zeros((s,), bool), zeros, zeros,
    )
    text = lowered.as_text(debug_info=True)
    for scope in ("embedding", "norm", "attention", "mlp", "lm_head", "kv_cow"):
        assert f"jit(_step_impl)/{scope}/" in text, scope
    assert 'loc("jit(_step_impl)/sample"' in text  # the call of the jitted argmax
    assert "jit(_step_impl)/attention/kv_cache_update/" in text
    assert eng.stats["step_traces"] == 1  # the lowering above traced; nothing ran
