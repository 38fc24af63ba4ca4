"""fused_rope backward-path tests (ROADMAP: "fix the live fused_rope
backward fallback").

The r03 TPU bench log showed the rope kernel silently degrading to XLA in
training ("Linearization failed to produce known values for all output
primals") even though the kernel carries a custom VJP — the generic op
dispatch differentiates its forward with ``jax.vjp`` at record time, and on
the TPU host's jax that linearization-over-``custom_vjp`` is what failed.
The fix routes the rope op around jax AD entirely: an explicit tape
``GradNode`` whose backward calls the standalone adjoint kernel
(``rope_adjoint_pallas``) directly. These tests pin:

- forward/backward numerics of both Pallas kernels (interpret mode) against
  the pure-XLA composition, neox AND interleaved layouts;
- the tape node's gradients (q, k, and table cotangents) against
  ``jax.grad`` of the composition;
- ``paddle_tpu_kernel_fallbacks_total`` staying FLAT across a real train
  step with the Pallas fwd+bwd kernels forced on — the acceptance criterion
  that training no longer silently pays for an XLA fallback;
- double backward (``create_graph=True``) through the registered pure-XLA
  raw op.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.functional import (
    _rope_adjoint_xla,
    _rope_apply_xla,
    fused_rotary_position_embedding,
)
from paddle_tpu.kernels.fused import fused_rope_pallas, rope_adjoint_pallas


def _tables(rng, s, d):
    cos = np.cos(rng.standard_normal((s, d))).astype(np.float32)
    sin = np.sin(rng.standard_normal((s, d))).astype(np.float32)
    return jnp.asarray(cos), jnp.asarray(sin)


class TestRopeKernels:
    def test_fused_rope_pallas_matches_composition(self):
        rng = np.random.default_rng(0)
        b, s, h, d = 2, 8, 2, 128
        x = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        cos, sin = _tables(rng, s, d)
        y = fused_rope_pallas(x, cos, sin, interpret=True)
        ref = _rope_apply_xla(x, sin, cos, True)
        assert jnp.allclose(y, ref, atol=1e-5)

    def test_rope_adjoint_pallas_matches_vjp(self):
        """The standalone backward kernel IS the composition's vjp."""
        rng = np.random.default_rng(1)
        b, s, h, d = 2, 8, 2, 128
        x = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        g = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        cos, sin = _tables(rng, s, d)
        _, vjp = jax.vjp(lambda t: _rope_apply_xla(t, sin, cos, True), x)
        dx_kernel = rope_adjoint_pallas(g, cos, sin, interpret=True)
        assert jnp.allclose(dx_kernel, vjp(g)[0], atol=1e-5)

    def test_rope_adjoint_asymmetric_tables(self):
        """The adjoint must be exact even when the two sin halves differ —
        no table-symmetry assumption."""
        rng = np.random.default_rng(2)
        b, s, h, d = 1, 4, 1, 128
        x = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        g = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        cos, sin = _tables(rng, s, d)
        sin = sin.at[:, : d // 2].mul(1.7)  # break half-symmetry
        _, vjp = jax.vjp(lambda t: _rope_apply_xla(t, sin, cos, True), x)
        assert jnp.allclose(
            rope_adjoint_pallas(g, cos, sin, interpret=True), vjp(g)[0], atol=1e-5
        )

    def test_adjoint_xla_interleaved_layout(self):
        rng = np.random.default_rng(3)
        b, s, h, d = 2, 4, 2, 8
        x = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        g = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        cos, sin = _tables(rng, s, d)
        _, vjp = jax.vjp(lambda t: _rope_apply_xla(t, sin, cos, False), x)
        assert jnp.allclose(_rope_adjoint_xla(g, sin, cos, False), vjp(g)[0], atol=1e-6)

    def test_jax_grad_through_kernel_custom_vjp(self):
        """Direct jax users (the bench preflight shape) still differentiate
        the kernel through its custom_vjp."""
        rng = np.random.default_rng(4)
        b, s, h, d = 1, 4, 2, 128
        x = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        cos, sin = _tables(rng, s, d)
        gk = jax.grad(
            lambda t: (fused_rope_pallas(t, cos, sin, interpret=True) ** 2).sum()
        )(x)
        gr = jax.grad(lambda t: (_rope_apply_xla(t, sin, cos, True) ** 2).sum())(x)
        assert jnp.allclose(gk, gr, atol=1e-4)


class TestRopeTapeNode:
    def test_tape_grads_match_composition_grad(self):
        rng = np.random.default_rng(5)
        b, s, h, d = 2, 8, 2, 128
        q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
        k = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
        q.stop_gradient = False
        k.stop_gradient = False
        cos, sin = _tables(rng, s, d)
        qo, ko, vo = fused_rotary_position_embedding(
            q, k, None, sin=paddle.to_tensor(np.asarray(sin)),
            cos=paddle.to_tensor(np.asarray(cos)),
        )
        assert vo is None
        loss = (qo * qo).sum() + (ko * ko * 0.5).sum()
        loss.backward()
        gq_ref = jax.grad(
            lambda t: (_rope_apply_xla(t, sin, cos, True) ** 2).sum()
        )(q._data)
        gk_ref = jax.grad(
            lambda t: (0.5 * _rope_apply_xla(t, sin, cos, True) ** 2).sum()
        )(k._data)
        assert jnp.allclose(q.grad._data, gq_ref, atol=1e-4)
        assert jnp.allclose(k.grad._data, gk_ref, atol=1e-4)

    def test_tape_table_cotangents(self):
        """sin/cos marked differentiable get exact grads (reduced over the
        broadcast) — matches jax.grad of the composition."""
        rng = np.random.default_rng(6)
        b, s, h, d = 2, 4, 2, 8
        q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
        q.stop_gradient = False
        cos, sin = _tables(rng, s, d)
        sin_t = paddle.to_tensor(np.asarray(sin))
        cos_t = paddle.to_tensor(np.asarray(cos))
        sin_t.stop_gradient = False
        cos_t.stop_gradient = False
        qo, _, _ = fused_rotary_position_embedding(q, None, None, sin=sin_t, cos=cos_t)
        (qo * qo).sum().backward()
        gs_ref = jax.grad(
            lambda t: (_rope_apply_xla(q._data, t, cos, True) ** 2).sum()
        )(sin)
        gc_ref = jax.grad(
            lambda t: (_rope_apply_xla(q._data, sin, t, True) ** 2).sum()
        )(cos)
        assert jnp.allclose(sin_t.grad._data, gs_ref, atol=1e-4)
        assert jnp.allclose(cos_t.grad._data, gc_ref, atol=1e-4)

    def test_no_grad_path_records_nothing(self):
        rng = np.random.default_rng(7)
        q = paddle.to_tensor(rng.standard_normal((1, 4, 1, 8)).astype(np.float32))
        cos, sin = _tables(rng, 4, 8)
        with paddle.no_grad():
            qo, _, _ = fused_rotary_position_embedding(
                q, sin=paddle.to_tensor(np.asarray(sin)),
                cos=paddle.to_tensor(np.asarray(cos)),
            )
        assert qo.stop_gradient and qo.grad_node is None

    def test_double_backward_through_raw_op(self):
        """create_graph re-differentiation goes through the registered
        pure-XLA raw op (fwd_fn) — grad-of-grad works and never needs a
        Pallas rule."""
        rng = np.random.default_rng(8)
        q = paddle.to_tensor(rng.standard_normal((1, 4, 2, 8)).astype(np.float32))
        q.stop_gradient = False
        cos, sin = _tables(rng, 4, 8)
        qo, _, _ = fused_rotary_position_embedding(
            q, sin=paddle.to_tensor(np.asarray(sin)),
            cos=paddle.to_tensor(np.asarray(cos)),
        )
        (g1,) = paddle.grad([(qo ** 3).sum()], [q], create_graph=True)
        (g2,) = paddle.grad([(g1 ** 2).sum()], [q])
        ref = jax.grad(
            lambda t: (
                jax.grad(lambda u: (_rope_apply_xla(u, sin, cos, True) ** 3).sum())(t)
                ** 2
            ).sum()
        )(q._data)
        assert jnp.allclose(g2._data, ref, atol=1e-3)


class TestRopeTrainStepFallbackFlat:
    def test_train_step_pallas_rope_no_fallbacks(self, monkeypatch):
        """Force the Pallas fwd+bwd rope kernels (interpret mode) through a
        REAL recompute+to_static train step and assert:

        - both kernels actually ran (fwd on forward+recompute-replay, the
          adjoint on backward),
        - ``paddle_tpu_kernel_fallbacks_total`` stays flat for fused_rope /
          fused_rope_bwd (the r03 regression: training silently paying for
          an XLA fallback),
        - the loss still trains.
        """
        import paddle_tpu.kernels.fused as fused
        import paddle_tpu.kernels.select as sel
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.observability import get_registry

        orig_enabled = sel.pallas_enabled
        monkeypatch.setattr(
            sel, "pallas_enabled",
            lambda flag, **kw: flag == "use_pallas_fused" or orig_enabled(flag, **kw),
        )
        fwd_calls, bwd_calls = [0], [0]
        orig_rope = fused.fused_rope_pallas
        orig_adj = fused.rope_adjoint_pallas

        def counted_rope(*a, **kw):
            fwd_calls[0] += 1
            return orig_rope(*a, interpret=True, **kw)

        def counted_adj(*a, **kw):
            bwd_calls[0] += 1
            return orig_adj(*a, interpret=True, **kw)

        monkeypatch.setattr(fused, "fused_rope_pallas", counted_rope)
        monkeypatch.setattr(fused, "rope_adjoint_pallas", counted_adj)
        monkeypatch.setattr(
            fused, "fused_rms_norm_pallas",
            functools.partial(fused.fused_rms_norm_pallas, interpret=True),
        )

        def fallback_counts():
            snap = get_registry().snapshot()
            out = {}
            for key, val in snap.items():
                name = key[0] if isinstance(key, tuple) else str(key)
                if "fallbacks" in str(name):
                    out[str(key)] = val
            return out

        before = fallback_counts()
        cfg = LlamaConfig(
            hidden_size=256, intermediate_size=256, num_hidden_layers=1,
            num_attention_heads=2, num_key_value_heads=2, vocab_size=64,
            max_position_embeddings=32, recompute=True,
        )
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=model.parameters(), multi_precision=True
        )

        @paddle.jit.to_static
        def train_step(model, opt, ids, labels):
            loss, _ = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        labels = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        first = float(train_step(model, opt, ids, labels))
        last = float(train_step(model, opt, ids, labels))

        assert fwd_calls[0] > 0, "Pallas rope forward never ran"
        assert bwd_calls[0] > 0, "Pallas rope adjoint never ran in backward"
        assert last < first, f"loss did not decrease ({first} -> {last})"
        after = fallback_counts()
        rope_deltas = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if "rope" in k
        }
        assert not any(rope_deltas.values()), (
            f"fused_rope fell back to XLA during the train step: {rope_deltas}"
        )


class TestFusedDecodeEpilogueFallbackFlat:
    """Satellite pin: the NEW fused decode-layer epilogues are counted in
    ``paddle_tpu_kernel_fallbacks_total`` per kernel label, and the CPU
    REFERENCE path (pallas ineligible by backend, so the XLA composition is
    the intended route, not a degradation) keeps every one of those counters
    flat — fwd AND tape backward."""

    LABELS = (
        "fused_rms_norm_residual",
        "fused_rms_norm_residual_bwd",
        "fused_layer_norm_residual",
        "fused_layer_norm_residual_bwd",
        "fused_embed_norm",
        "paged_flash_chunk_fused",
    )

    @staticmethod
    def _fallback_counts():
        """Flatten ``paddle_tpu_kernel_fallbacks_total`` to
        ``{kernel_label: value}``."""
        from paddle_tpu.observability import get_registry

        out = {}
        for name, data in get_registry().snapshot().items():
            if "fallbacks" not in str(name) or not isinstance(data, dict):
                continue
            for row in data.get("values", []):
                labels = row.get("labels") or {}
                out[labels.get("kernel", str(labels))] = row.get("value", 0)
        return out

    def test_cpu_reference_path_counters_flat(self):
        from paddle_tpu.incubate.nn.functional import (
            fused_embed_rms_norm,
            fused_layer_norm_residual,
            fused_rms_norm_residual,
        )

        prior = paddle.get_flags(["FLAGS_enable_metrics"])["FLAGS_enable_metrics"]
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            before = self._fallback_counts()
            rng = np.random.default_rng(0)
            x = paddle.to_tensor(rng.standard_normal((2, 4, 64)).astype(np.float32))
            res = paddle.to_tensor(rng.standard_normal((2, 4, 64)).astype(np.float32))
            w = paddle.to_tensor(np.ones(64, np.float32))
            b = paddle.to_tensor(np.zeros(64, np.float32))
            for t in (x, res, w, b):
                t.stop_gradient = False

            y, r = fused_rms_norm_residual(x, w, res)
            (y.sum() + r.sum()).backward()
            y2, r2 = fused_layer_norm_residual(x, w, b, res)
            (y2.sum() + r2.sum()).backward()

            ids = paddle.to_tensor(rng.integers(0, 16, (2, 4)).astype(np.int32))
            table = paddle.to_tensor(rng.standard_normal((16, 64)).astype(np.float32))
            emb, normed = fused_embed_rms_norm(ids, table, w.detach())
            assert emb.shape == [2, 4, 64] and normed.shape == [2, 4, 64]

            after = self._fallback_counts()
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prior})
        deltas = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if k in self.LABELS
        }
        assert not any(deltas.values()), (
            f"CPU reference path incremented fused-epilogue fallback counters: {deltas}"
        )

    def test_enabled_but_failing_kernel_increments_counter(self, monkeypatch):
        """The counter is live, not vestigial: force-enable pallas for the
        fused epilogues on CPU — the kernel path raises off-TPU, warn_fallback
        fires, and the per-kernel label moves."""
        import paddle_tpu.kernels.fused as fused
        import paddle_tpu.kernels.select as sel
        from paddle_tpu.incubate.nn.functional import fused_rms_norm_residual

        orig_enabled = sel.pallas_enabled
        monkeypatch.setattr(
            sel, "pallas_enabled",
            lambda flag, **kw: flag == "use_pallas_fused" or orig_enabled(flag, **kw),
        )

        def boom(*a, **kw):
            raise RuntimeError("no TPU in this test")

        monkeypatch.setattr(fused, "fused_rms_norm_residual_pallas", boom)
        prior = paddle.get_flags(["FLAGS_enable_metrics"])["FLAGS_enable_metrics"]
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            before = self._fallback_counts()
            rng = np.random.default_rng(1)
            x = paddle.to_tensor(rng.standard_normal((2, 128)).astype(np.float32))
            res = paddle.to_tensor(rng.standard_normal((2, 128)).astype(np.float32))
            w = paddle.to_tensor(np.ones(128, np.float32))
            fused_rms_norm_residual(x, w, res)
            after = self._fallback_counts()
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prior})
        assert after.get("fused_rms_norm_residual", 0) > before.get(
            "fused_rms_norm_residual", 0
        ), "warn_fallback never incremented the fused_rms_norm_residual label"
