"""The paged serving step's fused layer: its kernels and its tokens.

- the fused-epilogue kernels (residual+norm, embed+norm, rope inside the paged
  page walk) match their plain compositions — bitwise where one jit gives both
  the same op order, allclose for the adjoints vs ``jax.grad`` of the
  composition and where interpret mode contracts two programs differently;
- the engine's paged step (chunked prefill, decode, prefix-cache CoW forks,
  spec-decode rewinds) emits the tokens of the DENSE ``generate`` on the same
  prompts, through ONE compiled signature;
- the GPT / ERNIE blocks' fused residual+norm pairing is byte-identical to
  the plain composition written here, with matching grads, and the tp overlap
  matmul is byte-identical to the plain matmul.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.functional.block_attention import _gather_chunk_attend
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.kernels.fused import (
    fused_embed_rms_norm_pallas,
    fused_layer_norm_residual_pallas,
    fused_rms_norm_pallas,
    fused_rms_norm_residual_pallas,
    layer_norm_residual_adjoint_pallas,
    rms_norm_residual_adjoint_pallas,
)
from paddle_tpu.kernels.paged_attention import paged_flash_chunk
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

BS = 16  # tokens per physical block (the kernel tile)


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _dense_tokens(m, prompt, max_new):
    """The oracle: greedy ``generate`` over dense KV, no paged code at all."""
    out = m.generate(paddle.to_tensor(prompt[None, :]), max_new_tokens=max_new, do_sample=False)
    return np.asarray(out._data)[0]


# -- kernel numerics (interpret mode) ----------------------------------------

class TestResidualNormKernels:
    def test_rms_residual_fwd_matches_unfused_kernel_bitwise(self):
        """The fused kernel's op order is the EXISTING ``_rms_fwd_kernel``'s
        (f32 weight multiply before downcast) applied to ``x + residual`` —
        the on-TPU unfused composition, bitwise."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)
        y, r = fused_rms_norm_residual_pallas(x, res, w, interpret=True)
        ref_y = fused_rms_norm_pallas(x + res, w, interpret=True)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(x + res))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref_y))

    def test_rms_residual_adjoint_matches_jax_grad(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)
        g = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
        r = x + res

        def comp(r_, w_):
            xf = r_.astype(jnp.float32)
            rstd = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
            return jnp.sum((xf * rstd * w_) * g)

        dr_ref = jax.grad(comp, argnums=0)(r, w)
        dw_ref = jax.grad(comp, argnums=1)(r, w)
        dx, dw = rms_norm_residual_adjoint_pallas(g, r, w, 1e-6, interpret=True)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dr_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref), atol=1e-4)

    def test_ln_residual_fwd_and_adjoint(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((3, 128)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((3, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)
        b = jnp.asarray(rng.standard_normal(128), jnp.float32)
        g = jnp.asarray(rng.standard_normal((3, 128)), jnp.float32)
        y, r = fused_layer_norm_residual_pallas(x, res, w, b, interpret=True)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(x + res))

        def comp(r_, w_, b_):
            mu = jnp.mean(r_, -1, keepdims=True)
            var = jnp.mean((r_ - mu) ** 2, -1, keepdims=True)
            return (r_ - mu) * jax.lax.rsqrt(var + 1e-5) * w_ + b_

        np.testing.assert_allclose(
            np.asarray(y), np.asarray(comp(r, w, b)), atol=1e-5
        )
        dr_ref, dw_ref, db_ref = jax.grad(
            lambda r_, w_, b_: jnp.sum(comp(r_, w_, b_) * g), argnums=(0, 1, 2)
        )(r, w, b)
        dx, dw, db = layer_norm_residual_adjoint_pallas(g, r, w, interpret=True)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dr_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref), atol=1e-4)
        np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref), atol=1e-4)

    def test_embed_rms_gather_exact(self):
        rng = np.random.default_rng(3)
        table = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 32, (2, 5)), jnp.int32)
        emb, y = fused_embed_rms_norm_pallas(ids, table, w, interpret=True)
        np.testing.assert_array_equal(np.asarray(emb), np.asarray(table[ids]))
        ref_y = fused_rms_norm_pallas(table[ids], w, interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref_y))


def _neox_rope(x, cos, sin):
    """cos/sin broadcast against x's head dim; x.dtype arithmetic — the
    kernel's in-block op order."""
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return x * c + jnp.concatenate([-x2, x1], axis=-1) * s


class TestRopeFusedPagedAttention:
    """q-rope inside the page walk vs XLA-rope outside it. Against the same
    kernel fed roped q the two are compared INSIDE one jit each (the engine's
    one-jit step), where GQA shapes come out bitwise; against the XLA gather
    the comparison is a tolerance."""

    def _chunk_args(self, seed=0, b=3, c=4, hq=4, hkv=4, d=64, mbs=4, nb=16):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
        cos = jnp.asarray(np.cos(rng.normal(size=(b, c, d))), jnp.float32)
        sin = jnp.asarray(np.sin(rng.normal(size=(b, c, d))), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(nb)[: b * mbs].reshape(b, mbs), jnp.int32
        )
        lens = jnp.asarray(rng.integers(c, mbs * BS - c, (b,)), jnp.int32)
        q_lens = jnp.asarray([1, c, 0][:b], jnp.int32)
        return q, cos, sin, kc, vc, tables, lens, q_lens

    @staticmethod
    def _pair(q, cos, sin, kc, vc, tables, lens, q_lens):
        """(rope in the walk, rope outside then the same kernel), one jit each."""

        @jax.jit
        def inside(q, cos, sin):
            return paged_flash_chunk(q, kc, vc, tables, lens, q_lens, interpret=True, cos=cos, sin=sin)

        @jax.jit
        def outside(q, cos, sin):
            qr = _neox_rope(q, cos[:, :, None, :], sin[:, :, None, :])
            return paged_flash_chunk(qr, kc, vc, tables, lens, q_lens, interpret=True)

        return np.asarray(inside(q, cos, sin)), np.asarray(outside(q, cos, sin))

    def test_chunk_roped_matches_rope_then_gather(self):
        """The roped chunk kernel (interpret mode) against rope-then-
        ``_gather_chunk_attend``, the XLA composition the step falls back
        to. Not bitwise: the walk accumulates an online softmax over 128-key
        tiles in another order than the gather's one softmax over every
        page, and this CPU backend contracts the in-kernel rotation's
        multiply-adds differently from the outer one (the chip runs the two
        rope placements bitwise equal, PR 24); 2e-5 absolute on unit-normal
        q, k, v is ten times what either effect reads here."""
        q, cos, sin, kc, vc, tables, lens, q_lens = self._chunk_args()
        walked = paged_flash_chunk(q, kc, vc, tables, lens, q_lens, interpret=True, cos=cos, sin=sin)
        qr = _neox_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        gathered = _gather_chunk_attend(qr, kc, vc, tables, lens, q_lens, 1.0 / 64**0.5)
        np.testing.assert_allclose(np.asarray(walked), np.asarray(gathered), atol=2e-5, rtol=0)
        assert not np.asarray(walked)[2].any()  # the slot with no new rows: exact zeros

    def test_chunk_fused_gqa(self):
        a, b = self._pair(*self._chunk_args(seed=1, hq=8, hkv=2))
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)], ids=["gqa", "mha"])
    def test_decode_row_allclose_same_jit(self, hq, hkv):
        """A plain decode step is the chunk at C == 1: a handful of rows
        (G of them; one with a query head a KV head) go through the in-kernel
        rope, and XLA's FMA selection is shape-dependent for such short
        elementwise chains, so this is exact math but not bitwise vs the
        outer-rope lowering (~1 ulp: 4e-7 read here)."""
        q, cos, sin, kc, vc, tables, lens, _ = self._chunk_args(seed=2, c=1, hq=hq, hkv=hkv)
        a, b = self._pair(q, cos, sin, kc, vc, tables, lens, jnp.ones_like(lens))
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


# -- the engine's paged step against the dense forward ------------------------

class TestEnginePagedStepAgainstDense:
    def _run(self, m, prompts, budgets, **eng_kw):
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=32,
            prefill_chunk=8, max_model_len=128, **eng_kw
        )
        rids = [
            eng.add_request(p, max_new_tokens=t)
            for p, t in zip(prompts, budgets)
        ]
        out = eng.run()
        return eng, [out[r].tokens() for r in rids]

    def test_mixed_workload_matches_dense_and_one_signature(self):
        """Chunked prefill + decode, staggered budgets, more requests than
        slots: every stream is the dense ``generate``'s, ONE compiled
        signature."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (5, 12, 3, 9)
        ]
        budgets = [6, 4, 8, 5]
        eng, toks = self._run(m, prompts, budgets)
        for p, t, got in zip(prompts, budgets, toks):
            np.testing.assert_array_equal(got, _dense_tokens(m, p, t))
        assert eng.stats["step_traces"] == 1
        if hasattr(eng._step_fn, "_cache_size"):
            assert eng._step_fn._cache_size() == 1

    def test_cow_fork_warm_hit_matches_dense(self):
        """Prefix-cache CoW fork (cold, then warm with a forked partial
        block): both streams are the dense ``generate``'s."""
        m, cfg = _model(seed=42)
        rng = np.random.default_rng(42)
        prompt = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)

        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16
        )
        r_cold = eng.add_request(prompt, max_new_tokens=6)
        out_cold = eng.run()
        r_warm = eng.add_request(prompt, max_new_tokens=6)
        out_warm = eng.run()
        assert out_warm[r_warm].cached_tokens > 0
        assert eng.prefix_cache_stats()["cow_forks"] >= 1
        dense = _dense_tokens(m, prompt, 6)
        np.testing.assert_array_equal(out_cold[r_cold].tokens(), dense)
        np.testing.assert_array_equal(out_warm[r_warm].tokens(), dense)

    def test_spec_decode_rewinds_match_dense(self):
        """Speculative drafts + rewinds ride the paged step: the streams are
        the dense ``generate``'s, and the engine still speculates."""
        m, cfg = _model(seed=5)
        rng = np.random.default_rng(5)
        template = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        fill = rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)
        rep = np.concatenate([template, fill, template, fill])[:16]
        prompts = [rep, rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)]
        budgets = [20, 8]
        eng, toks = self._run(m, prompts, budgets, spec_decode=True)
        for p, t, got in zip(prompts, budgets, toks):
            np.testing.assert_array_equal(got, _dense_tokens(m, p, t))
        assert eng.stats["spec_drafted"] > 0
        assert eng.stats["step_traces"] == 1


# -- GPT / ERNIE epilogue fusion ---------------------------------------------

class TestGptErnieFusion:
    def test_gpt_forward_byte_identical_and_grads_close(self):
        """The block against the plain pre-LN composition written here."""
        from paddle_tpu.models.gpt import GPTConfig, GPTModel

        paddle.seed(0)
        g = GPTModel(GPTConfig.tiny())
        ids = paddle.to_tensor(
            np.random.default_rng(0).integers(0, 128, (2, 16)).astype(np.int64)
        )

        def plain(ids):
            h = g.embeddings(ids, None)
            for blk in g.layers:
                h = h + blk.attn(blk.ln_1(h))
                h = h + blk.mlp(blk.ln_2(h))
            return g.ln_f(h)

        def loss_and_grads(forward):
            for _, p in g.named_parameters():
                p.clear_grad()
            loss = (forward(ids) ** 2).sum()
            loss.backward()
            return float(loss), {
                n: np.asarray(p.grad._data).copy()
                for n, p in g.named_parameters()
                if p.grad is not None
            }

        np.testing.assert_array_equal(np.asarray(g(ids)._data), np.asarray(plain(ids)._data))
        l_on, g_on = loss_and_grads(g)
        l_off, g_off = loss_and_grads(plain)
        assert l_on == l_off
        assert set(g_on) == set(g_off)
        for k in g_off:
            np.testing.assert_allclose(g_on[k], g_off[k], atol=1e-5)

    def test_ernie_forward_byte_identical(self):
        """The post-LN layer against the plain composition written here."""
        from paddle_tpu.models.ernie import ErnieConfig, ErnieModel

        paddle.seed(1)
        e = ErnieModel(ErnieConfig.tiny())
        e.eval()
        x = paddle.to_tensor(
            np.random.default_rng(1).standard_normal((2, 12, e.config.hidden_size)).astype(np.float32)
        )
        for layer in e.encoder:
            h = layer.ln_1(x + layer.dropout(layer.attn(x, None)))
            ffn = layer.fc2(paddle.nn.functional.gelu(layer.fc1(h)))
            want = layer.ln_2(h + layer.dropout(ffn))
            x = layer(x)
            np.testing.assert_array_equal(np.asarray(x._data), np.asarray(want._data))


# -- tp overlap matmul --------------------------------------------------------

class TestRowParallelOverlapMatmul:
    def test_tiled_byte_identical_to_plain(self):
        from paddle_tpu.distributed.tp import row_parallel_overlap_matmul

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 6, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
        ref = np.asarray(jnp.matmul(x.reshape(24, 32), w).reshape(4, 6, 16))
        for tiles in (1, 2, 3, 4):
            out = row_parallel_overlap_matmul(x, w, tiles=tiles)
            assert out.shape == (4, 6, 16)
            np.testing.assert_array_equal(np.asarray(out), ref)

    def test_uneven_rows_fall_back_to_one_tile(self):
        from paddle_tpu.distributed.tp import row_parallel_overlap_matmul

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
        out = row_parallel_overlap_matmul(x, w, tiles=2)  # 5 % 2 != 0
        np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.matmul(x, w)))
