"""TPU-lowering regression tests that need NO hardware.

``jax.export.export(jax.jit(fn), platforms=['tpu'])`` runs the Mosaic kernel
LOWERING on the CPU backend and raises what Pallas' lowering rules raise (an
early chip run died on an illegal ``(1, 1, blk_q)`` LSE BlockSpec that this
file would have caught statically). It does not run the chip's compiler:
VMEM limits and some layout checks fire only at compile — those are
tests/test_tpu_aot_compile.py's. Every gated Pallas kernel must export —
forward AND backward — for every configuration the framework routes to it.

Grads are taken wrt every differentiable input: the backward pass runs as
separate pallas_calls (dq vs dkv) and an unused cotangent lets DCE prune a
kernel out before Mosaic ever checks it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention_pallas
from paddle_tpu.kernels.fused import fused_rms_norm_pallas, fused_rope_pallas

B, H, HK, D = 1, 4, 2, 64


def _qkv(sq, sk, h=H, hk=H, dtype=jnp.bfloat16):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, sq, h, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, sk, hk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, sk, hk, D)), dtype)
    return q, k, v


def _export_grad(fn, *args):
    """Export fwd+bwd for TPU; grads wrt all float args."""
    argnums = tuple(
        i for i, a in enumerate(args) if jnp.issubdtype(a.dtype, jnp.floating)
    )

    def loss_and_grads(*a):
        loss = lambda *inner: fn(*inner).astype(jnp.float32).sum()  # noqa: E731
        return jax.grad(loss, argnums=argnums)(*a)

    jax.export.export(jax.jit(loss_and_grads), platforms=["tpu"])(*args)


class TestFlashAttentionExport:
    @pytest.mark.parametrize("causal", [False, True])
    def test_basic(self, causal):
        q, k, v = _qkv(256, 256)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=causal), q, k, v
        )

    def test_gqa(self):
        q, k, v = _qkv(256, 256, h=H, hk=HK)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True), q, k, v
        )

    def test_unaligned_seq(self):
        # exercises the pad-to-block path (sq=200 -> blk_q=104? no: min(128, 200->208))
        q, k, v = _qkv(200, 200)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True), q, k, v
        )

    def test_cross_attention(self):
        q, k, v = _qkv(128, 384)
        _export_grad(lambda q, k, v: flash_attention_pallas(q, k, v), q, k, v)

    @pytest.mark.parametrize("c", [1, 2, 4])
    def test_flashmask(self, c):
        sq = sk = 256
        q, k, v = _qkv(sq, sk)
        rng = np.random.default_rng(1)
        if c == 1:
            bounds = rng.integers(1, sq, (B, 1, sk, 1))
        elif c == 2:
            start = rng.integers(1, sq, (B, 1, sk, 1))
            end = np.minimum(start + rng.integers(0, 64, start.shape), sq)
            bounds = np.concatenate([start, end], axis=-1)
        else:
            lts = rng.integers(1, sq, (B, 1, sk, 1))
            lte = np.minimum(lts + 32, sq)
            uts = np.maximum(lts - 64, 0)
            ute = lts
            bounds = np.concatenate([lts, lte, uts, ute], axis=-1)
        idx = jnp.asarray(bounds, jnp.int32)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(
                q, k, v, startend_row_indices=idx, causal=True
            ),
            q, k, v,
        )

    def test_flashmask_per_head(self):
        # Hm == H (per-head mask) exercises the non-broadcast index map
        sq = sk = 256
        q, k, v = _qkv(sq, sk)
        rng = np.random.default_rng(2)
        idx = jnp.asarray(rng.integers(1, sq, (B, H, sk, 1)), jnp.int32)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(
                q, k, v, startend_row_indices=idx, causal=True
            ),
            q, k, v,
        )

    def test_bench_shape(self):
        """The exact shape class BENCH uses (12 heads, hd 128, seq 2048) —
        12 is not a multiple of 8, which is what broke the old LSE layout."""
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(1, 2048, 12, 128)), jnp.bfloat16)
        _export_grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True), q, q, q
        )


class TestFusedKernelExport:
    @pytest.mark.parametrize("shape", [(2, 256, 512), (1, 2048, 1536)])
    def test_rms_norm(self, shape):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=shape[-1:]), jnp.bfloat16)
        _export_grad(lambda x, w: fused_rms_norm_pallas(x, w, 1e-6), x, w)

    def test_rope_grad(self):
        # custom VJP: fwd AND the Pallas bwd kernel must lower for TPU
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.bfloat16)
        cs = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
        _export_grad(lambda x: fused_rope_pallas(x, cs, cs), x)


class TestFusedMoeExport:
    def test_fused_moe_lowers_for_tpu(self):
        # ragged_dot is a Mosaic grouped matmul: statically verify fwd+bwd
        # TPU lowering like the Pallas kernels
        from paddle_tpu.incubate.nn.functional.fused_moe import _fused_moe_impl

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
        gw = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(4, 32, 32)), jnp.float32)

        def loss(x, gw, w1, w2):
            return _fused_moe_impl(x, gw, w1, w2, 2, True, "swiglu").sum()

        jax.export.export(
            jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))), platforms=["tpu"]
        )(x, gw, w1, w2)
