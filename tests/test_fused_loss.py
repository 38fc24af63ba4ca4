"""Fused linear + cross-entropy loss head: CPU-pinned numerics (scan
reference AND interpret-mode Pallas) vs the unfused ``lm_head +
F.cross_entropy`` composition, reduction/ignore_index semantics, the
``(loss, None)`` model contract, the ``FLAGS_use_fused_loss`` env seed, and
the compiled-peak-memory regression the no-materialization claim rests on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import memory as M
from paddle_tpu.flags import GLOBAL_FLAGS, FlagRegistry
from paddle_tpu.kernels.fused_loss import fused_linear_cross_entropy
from paddle_tpu.nn.functional.loss import cross_entropy

IGN = -100


def _data(n=48, h=64, v=1000, dtype=jnp.float32, seed=0, n_ignored=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, h)), dtype)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.05, dtype)
    lab = rng.integers(0, v, (n,)).astype(np.int32)
    if n_ignored:
        lab[rng.choice(n, n_ignored, replace=False)] = IGN
    return x, w, jnp.asarray(lab)


def _unfused(x, w, lab, reduction="mean"):
    return cross_entropy.raw_fn(x @ w, lab, ignore_index=IGN, reduction=reduction)


def _grads(fn, *args):
    return jax.value_and_grad(fn, argnums=(0, 1))(*args)


class TestReferenceParity:
    """The lax.scan custom-VJP reference (the CPU/tier-1 path) vs unfused."""

    @pytest.mark.parametrize("v", [1000, 512, 130])  # incl. ragged vocab tails
    def test_loss_and_grads_fp32(self, v):
        x, w, lab = _data(v=v)
        lu, gu = _grads(_unfused, x, w, lab)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        np.testing.assert_allclose(float(lf), float(lu), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gu[0]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gu[1]), rtol=1e-4, atol=1e-5)

    def test_bf16_inputs(self):
        x, w, lab = _data(h=128, v=512, dtype=jnp.bfloat16)
        lu, gu = _grads(_unfused, x, w, lab)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        assert lf.dtype == jnp.float32  # fp32 online logsumexp, fp32 loss
        np.testing.assert_allclose(float(lf), float(lu), rtol=1e-3, atol=1e-3)
        for got, ref in zip(gf, gu):
            assert got.dtype == ref.dtype  # grads land back in bf16
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                rtol=1e-2, atol=1e-2,
            )

    def test_tied_vocab_major_layout(self):
        x, w, lab = _data()
        lu, gu = _grads(_unfused, x, w, lab)
        lt, gt = _grads(
            lambda x, wv: fused_linear_cross_entropy(x, wv, lab, vocab_major=True),
            x, w.T,
        )
        np.testing.assert_allclose(float(lt), float(lu), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gt[0]), np.asarray(gu[0]), rtol=1e-4, atol=1e-5)
        # dW comes back in the embedding's [V, H] layout
        np.testing.assert_allclose(np.asarray(gt[1]), np.asarray(gu[1].T), rtol=1e-4, atol=1e-5)

    def test_all_rows_ignored(self):
        x, w, _ = _data()
        lab = jnp.full((x.shape[0],), IGN, jnp.int32)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        assert float(lf) == 0.0  # mean denominator clamps at 1, like F.cross_entropy
        assert float(_unfused(x, w, lab)) == 0.0
        assert float(jnp.abs(gf[0]).max()) == 0.0
        assert float(jnp.abs(gf[1]).max()) == 0.0

    def test_mean_denominator_counts_only_valid(self):
        x, w, lab = _data(n_ignored=0)
        lab = lab.at[:30].set(IGN)  # 18 of 48 rows contribute
        ls = fused_linear_cross_entropy(x, w, lab, reduction="sum")
        lm = fused_linear_cross_entropy(x, w, lab, reduction="mean")
        np.testing.assert_allclose(float(lm), float(ls) / 18.0, rtol=1e-5)
        np.testing.assert_allclose(float(lm), float(_unfused(x, w, lab)), rtol=1e-3)

    def test_reduction_none_shape_and_values(self):
        x, w, lab = _data()
        per = fused_linear_cross_entropy(
            x.reshape(4, 12, -1), w, lab.reshape(4, 12), reduction="none"
        )
        assert per.shape == (4, 12)
        ref = _unfused(x, w, lab, reduction="none")
        np.testing.assert_allclose(np.asarray(per).ravel(), np.asarray(ref), rtol=1e-4, atol=1e-5)


class TestPallasInterpretParity:
    """The Pallas kernels (fwd + dX + dW), interpret mode on CPU."""

    @pytest.mark.parametrize("vocab_major", [False, True])
    @pytest.mark.parametrize("v", [1000, 256])  # 1000 % 128 != 0: ragged tail
    def test_loss_and_grads(self, vocab_major, v):
        x, w, lab = _data(h=128, v=v)
        wl = w.T if vocab_major else w
        lu, gu = _grads(_unfused, x, w, lab)
        lp, gp = _grads(
            lambda x, wl: fused_linear_cross_entropy(
                x, wl, lab, vocab_major=vocab_major, interpret=True, block=(16, 128)
            ),
            x, wl,
        )
        np.testing.assert_allclose(float(lp), float(lu), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gu[0]), rtol=1e-4, atol=1e-5)
        dw = gp[1].T if vocab_major else gp[1]
        np.testing.assert_allclose(np.asarray(dw), np.asarray(gu[1]), rtol=1e-4, atol=1e-5)

    def test_bf16_and_row_padding(self):
        # 40 rows with a 16-row block: the kernel pads rows 40→48 with
        # ignore_index labels; padded rows must contribute nothing
        x, w, lab = _data(n=40, h=128, v=256, dtype=jnp.bfloat16)
        lu, gu = _grads(_unfused, x, w, lab)
        lp, gp = _grads(
            lambda x, w: fused_linear_cross_entropy(
                x, w, lab, interpret=True, block=(16, 128)
            ),
            x, w,
        )
        np.testing.assert_allclose(float(lp), float(lu), rtol=1e-3, atol=1e-3)
        for got, ref in zip(gp, gu):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                rtol=1e-2, atol=1e-2,
            )

    def test_all_ignored_interpret(self):
        x, w, _ = _data(h=128, v=256)
        lab = jnp.full((x.shape[0],), IGN, jnp.int32)
        lp, gp = _grads(
            lambda x, w: fused_linear_cross_entropy(
                x, w, lab, interpret=True, block=(16, 128)
            ),
            x, w,
        )
        assert float(lp) == 0.0
        assert float(jnp.abs(gp[0]).max()) == 0.0 and float(jnp.abs(gp[1]).max()) == 0.0


class TestModelContract:
    """Models return (loss, None) on the fused path, (loss, logits) off it."""

    def _llama(self, tie):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        cfg.tie_word_embeddings = tie
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32))
        return model, ids

    @pytest.mark.parametrize("tie", [False, True])
    def test_llama_fused_vs_unfused(self, tie):
        model, ids = self._llama(tie)
        prior = paddle.get_flags(["FLAGS_use_fused_loss"])
        try:
            paddle.set_flags({"FLAGS_use_fused_loss": True})
            loss_f, second = model(ids, labels=ids)
            assert second is None  # the contract: no [B, S, V] buffer to return
            loss_f.backward()
            head = model.lm_head.weight if not tie else model.llama.embed_tokens.weight
            assert head.grad is not None and float(head.grad.abs().sum()) > 0
            model.clear_gradients()
            paddle.set_flags({"FLAGS_use_fused_loss": False})
            loss_u, logits = model(ids, labels=ids)
            assert logits is not None
            np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-3, atol=1e-3)
        finally:
            paddle.set_flags(prior)

    def test_gpt_and_ernie_fused_paths(self):
        from paddle_tpu.models.ernie import ErnieConfig, ErnieModel
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

        prior = paddle.get_flags(["FLAGS_use_fused_loss"])
        rng = np.random.default_rng(4)
        ids = paddle.to_tensor(rng.integers(0, 128, (2, 16)).astype(np.int32))
        try:
            paddle.set_flags({"FLAGS_use_fused_loss": True})
            paddle.seed(0)
            gpt = GPTForPretraining(GPTConfig.tiny())
            loss, second = gpt(ids, labels=ids)
            assert second is None
            loss.backward()
            assert float(gpt.gpt.embeddings.word_embeddings.weight.grad.abs().sum()) > 0
            paddle.seed(0)
            ernie = ErnieModel(ErnieConfig.tiny())
            mlm = np.full((2, 16), IGN, np.int64)
            mlm[0, 3], mlm[1, 5] = 7, 9
            loss_f, pooled = ernie(ids, labels=paddle.to_tensor(mlm))
            assert tuple(pooled.shape) == (2, 64)
            paddle.set_flags({"FLAGS_use_fused_loss": False})
            loss_u, _ = ernie(ids, labels=paddle.to_tensor(mlm))
            np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-3, atol=1e-3)
        finally:
            paddle.set_flags(prior)


class TestAutotuneEntry:
    def test_entry_consults_tuner_for_blocks(self, monkeypatch):
        """When ``block`` isn't pinned, the entry asks the autotuner for the
        (row_block, vocab_block) pair (the flash_attention test pattern)."""
        from paddle_tpu.kernels import autotune as at

        seen = {}

        def fake_autotune(kernel, key, candidates, build, default, repeats=3):
            seen["kernel"], seen["key"] = kernel, key
            return (16, 128)

        monkeypatch.setattr(at, "autotune", fake_autotune)
        x, w, lab = _data(h=128, v=256)
        loss = fused_linear_cross_entropy(x, w, lab, interpret=True)
        assert np.isfinite(float(loss))
        assert seen["kernel"] == "fused_linear_xent"
        assert seen["key"][1] == 256  # vocab size in the cache key


class TestFallbackCounter:
    def test_warn_fallback_counts_per_kernel(self):
        """A Pallas failure degrading to the XLA path is scrapeable, not just
        a one-time log line."""
        from paddle_tpu.kernels import select

        prior = paddle.get_flags(["FLAGS_enable_metrics"])
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            before = select._fallbacks_total.value(kernel="flxent_probe")
            select.warn_fallback("flxent_probe", RuntimeError("boom"))
            select.warn_fallback("flxent_probe", RuntimeError("boom again"))
            assert select._fallbacks_total.value(kernel="flxent_probe") == before + 2
        finally:
            paddle.set_flags(prior)


class TestFlagEnvSeeding:
    """FLAGS_use_fused_loss seeds from the environment at first read
    (the test_observability.py pattern)."""

    def test_env_seeds_fresh_registry(self, monkeypatch):
        reg = FlagRegistry()
        reg.define("use_fused_loss", bool, True, "")
        monkeypatch.setenv("FLAGS_use_fused_loss", "false")
        assert reg.get("use_fused_loss") is False

    def test_flag_registered_with_default_on(self):
        assert isinstance(GLOBAL_FLAGS.get("use_fused_loss"), bool)


class TestCompiledMemoryRegression:
    """The no-materialization claim, enforced on what the compiler holds
    live: the jitted fused train loss's TEMPORARIES must stay below the
    unfused composition's by more than an ``[N, V]`` buffer (core/memory.py
    compiled stats, the test_memory.py methodology). It reads
    ``temp_size_in_bytes``: this backend's ``peak_memory_in_bytes`` is
    arguments + outputs only (2 361 380 = 1 181 696 + 1 179 676 + 8 for
    BOTH programs here, 30 bytes apart by an output tuple's layout), so it
    cannot see a temporary at all, which is what this test asserted on and
    failed on since the seed. For the Pallas kernels on a described v5e:
    tests/test_tpu_aot_compile.py::test_fused_loss_never_holds_the_logits."""

    def test_fused_peak_below_unfused(self):
        n, h, v = 512, 128, 4096
        x = jnp.zeros((n, h), jnp.bfloat16)
        w = jnp.zeros((h, v), jnp.bfloat16)
        lab = jnp.zeros((n,), jnp.int32)

        def unfused(x, w, lab):
            return _unfused(x, w, lab)

        def fused(x, w, lab):
            return fused_linear_cross_entropy(x, w, lab)

        def peak(fn):
            c = jax.jit(jax.value_and_grad(fn, argnums=(0, 1))).lower(x, w, lab).compile()
            return M.compiled_memory_stats(c)["temp_size_in_bytes"]

        p_unfused = peak(unfused)
        p_fused = peak(fused)
        # the unfused composition holds [N, V] logits (+ fp32 log_softmax
        # copies) live across backward; the fused path's largest loss-head
        # temp is one [N, block] chunk
        assert p_fused < p_unfused, (p_fused, p_unfused)
        # and not marginally: at this shape the gap is several [N, V] buffers
        assert p_unfused - p_fused > n * v * 2, (p_fused, p_unfused)
