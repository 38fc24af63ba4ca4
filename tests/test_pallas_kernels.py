"""Pallas kernel parity tests (interpret mode on CPU): flash attention fwd/bwd
vs the XLA reference, FlashMask C∈{1,2,4} vs densified-bias reference, GQA,
fused rms_norm and rope.

Mirrors the reference's OpTest analytic-grad methodology (SURVEY §4) for the
kernels that replace flash_attn_kernel.cu / rms_norm / fused_rope.
"""

import hashlib
import itertools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_attention import flash_attention_pallas
from paddle_tpu.kernels.flashmask import flashmask_attention_pallas, flashmask_maxmin
from paddle_tpu.kernels.fused import fused_rms_norm_pallas, fused_rope_pallas
from paddle_tpu.nn.functional.flash_attention import (
    _xla_attention,
    make_flashmask_bias,
)


def _qkv(b=2, sq=64, sk=64, h=4, hk=None, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    hk = hk or h
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, hk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, hk, d), jnp.float32)
    return q, k, v


class TestFlashAttentionPallas:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_matches_xla(self, causal):
        q, k, v = _qkv()
        out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
        ref = _xla_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_unaligned_seqlen(self):
        q, k, v = _qkv(sq=50, sk=70)
        out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
        ref = _xla_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(h=8, hk=2)
        out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_xla(self, causal):
        q, k, v = _qkv(b=1, sq=32, sk=32, h=2, d=16)

        def f_pallas(q, k, v):
            return flash_attention_pallas(q, k, v, causal=causal, interpret=True).sum()

        def f_ref(q, k, v):
            return _xla_attention(q, k, v, causal=causal).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4)

    def test_gqa_grads(self):
        q, k, v = _qkv(b=1, sq=32, sk=32, h=4, hk=2, d=16)

        def f_pallas(q, k, v):
            return (flash_attention_pallas(q, k, v, causal=True, interpret=True) ** 2).sum()

        def f_ref(q, k, v):
            return (_xla_attention(q, k, v, causal=True) ** 2).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4)

    def test_bf16(self):
        q, k, v = _qkv()
        out = flash_attention_pallas(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
            causal=True, interpret=True,
        )
        ref = _xla_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
        )


# --------------------------------------------------------------------------
# matmul operands follow the inputs' dtype (PR 28): bf16 tensors feed the MXU
# bf16, float32 tensors float32, and float32 results stay what they were
# --------------------------------------------------------------------------

# {causal, not} x {MHA, GQA 4:1} x {no bounds, FlashMask C = 1, 2, 4} x {sq = sk, sq < sk}
OPERAND_CASES = list(itertools.product((True, False), (4, 1), (0, 1, 2, 4), (40, 24)))
_CASE_IDS = [
    f"{'causal' if c else 'full'}-{'mha' if hk == 4 else 'gqa4'}-c{mc}-sq{sq}"
    for c, hk, mc, sq in OPERAND_CASES
]
_SK, _H, _D, _BLK = 40, 4, 32, 16  # 3 blocks of 16 a side, the last one padded
# sha256 of out, dq, dk, dv of every case at float32 inputs, recorded from the
# kernel as it stood before PR 28 (commit f5235e7) on this installation
_F32_RECORD = pathlib.Path(__file__).parent / "testdata" / "flash_attention_f32.sha256.json"


def _case_bounds(mask_c, sq, sk, seed):
    """FlashMask bounds [1, 1, Sk, C] that leave column 0 open to every row, so
    that no query row is masked out whole."""
    if not mask_c:
        return None
    rng = np.random.default_rng(seed)
    start = rng.integers(1, sq + 1, sk).astype(np.int32)
    end = np.minimum(start + rng.integers(0, 12, sk), sq).astype(np.int32)
    if mask_c == 1:
        cols = [start]
    elif mask_c == 2:
        cols = [start, end]
    else:
        uts = rng.integers(0, sq // 2, sk).astype(np.int32)
        ute = np.minimum(uts + rng.integers(0, 4, sk), sq).astype(np.int32)
        cols = [start, end, uts, ute]
    idx = np.stack(cols, -1)
    idx[0] = sq  # empty bands: [sq, sq) masks nothing
    return jnp.asarray(idx.reshape(1, 1, sk, mask_c))


def _operand_case(causal, hk, mask_c, sq, dtype):
    seed = 100 + OPERAND_CASES.index((causal, hk, mask_c, sq))
    q, k, v = (x.astype(dtype) for x in _qkv(b=1, sq=sq, sk=_SK, h=_H, hk=hk, d=_D, seed=seed))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1000), (1, sq, _H, _D), jnp.float32)
    return q, k, v, w, _case_bounds(mask_c, sq, _SK, seed)


def _out_and_grads(attend, q, k, v, w):
    """Output and the three gradients under a random float32 cotangent ``w``."""
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(w.astype(out.dtype))


def _pallas(idx, causal):
    return lambda q, k, v: flash_attention_pallas(
        q, k, v, startend_row_indices=idx, causal=causal,
        block_q=_BLK, block_k=_BLK, interpret=True,
    )


class TestFlashOperandDtype:
    # one rounding of p / ds to bf16 (2**-9 relative, averaged over a row) and
    # the bf16 outputs' own rounding (2**-9 of values up to ~4) stay under this
    # against the dense float32 reference on the same bf16 values upcast
    BF16_TOL = dict(rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("causal,hk,mask_c,sq", OPERAND_CASES, ids=_CASE_IDS)
    def test_bf16_fwd_and_grads_match_dense_f32(self, causal, hk, mask_c, sq):
        q, k, v, w, idx = _operand_case(causal, hk, mask_c, sq, jnp.bfloat16)
        got = _out_and_grads(_pallas(idx, causal), q, k, v, w)
        bias = None if idx is None else make_flashmask_bias(idx, sq, _SK, causal)
        ref = _out_and_grads(
            lambda q, k, v: _xla_attention(q, k, v, bias=bias, causal=causal),
            *(x.astype(jnp.float32) for x in (q, k, v)), w,
        )
        for name, a, b_ in zip(("out", "dq", "dk", "dv"), got, ref):
            assert a.dtype == jnp.bfloat16, name
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b_), err_msg=name, **self.BF16_TOL
            )

    @pytest.mark.parametrize("causal,hk,mask_c,sq", OPERAND_CASES, ids=_CASE_IDS)
    def test_f32_bitwise_as_before(self, causal, hk, mask_c, sq, request):
        q, k, v, w, idx = _operand_case(causal, hk, mask_c, sq, jnp.float32)
        got = _out_and_grads(_pallas(idx, causal), q, k, v, w)
        digest = hashlib.sha256(b"".join(np.asarray(a).tobytes() for a in got)).hexdigest()
        assert digest == json.loads(_F32_RECORD.read_text())[request.node.callspec.id]


def _kernel_dots(jaxpr, inside=False):
    """(operand dtypes) of every dot_general inside a pallas_call body, and the
    result avals of every pallas_call, anywhere under ``jaxpr``."""
    dots, calls = [], []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and inside:
            dots.append(tuple(v.aval.dtype for v in eqn.invars))
        if eqn.primitive.name == "pallas_call":
            calls.append(tuple((v.aval.dtype, v.aval.shape) for v in eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            d, c = _kernel_dots(sub, inside or eqn.primitive.name == "pallas_call")
            dots += d
            calls += c
    return dots, calls


class TestFlashKernelStructure:
    """What keeps a later edit from bringing the upcasts back, or from changing
    the results ``benchmarks/metrics/flash_attn_roofline.py`` tells the three
    kernels by."""

    B, S, H, HK, D = 2, 256, 8, 2, 128

    def _trace(self, dtype):
        shapes = [
            jax.ShapeDtypeStruct((self.B, self.S, h, self.D), dtype) for h in (self.H, self.HK, self.HK)
        ]
        fn = jax.grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
        return _kernel_dots(jax.make_jaxpr(fn)(*shapes).jaxpr)

    def test_bf16_inputs_no_float32_operand_pair(self):
        dots, _ = self._trace(jnp.bfloat16)
        assert len(dots) == 9  # 2 forward, 3 dq, 4 dkv
        assert all(pair == (jnp.bfloat16, jnp.bfloat16) for pair in dots), dots

    def test_float32_inputs_all_float32_operands(self):
        dots, _ = self._trace(jnp.float32)
        assert len(dots) == 9
        assert all(pair == (jnp.float32, jnp.float32) for pair in dots), dots

    @pytest.mark.parametrize(
        "sq,sk,d,itemsize,blocks",
        [
            (2048, 2048, 128, 2, (512, 512)),  # the train cell: the largest tile
            (2048, 2048, 128, 4, (512, 512)),
            (4096, 4096, 128, 2, (256, 512)),  # whole-sequence q, dO, lse, delta take 12 of 16 MiB
            (4096, 4096, 64, 2, (256, 512)),  # 64 lanes pad to 128
            (600, 600, 128, 2, (384, 384)),  # two even blocks, not 512 + 512
            (40, 40, 32, 4, (128, 128)),  # the entry then clamps a block to the sequence
            (128, 4096, 128, 2, (128, 512)),
        ],
    )
    def test_block_geometry_from_shapes(self, sq, sk, d, itemsize, blocks):
        assert fa._block_geometry(sq, sk, d, itemsize) == blocks

    def test_results_are_what_the_roofline_reader_matches(self):
        _, calls = self._trace(jnp.bfloat16)
        bhsd = (self.B, self.H, self.S, self.D)
        assert calls == [
            ((jnp.bfloat16, bhsd), (jnp.float32, bhsd[:3] + (1,))),  # forward: out, lse
            ((jnp.bfloat16, bhsd),),  # dq
            ((jnp.float32, bhsd), (jnp.float32, bhsd)),  # dk, dv per q head
        ]
        assert (fa.KERNEL_FWD, fa.KERNEL_DQ, fa.KERNEL_DKV) == (
            "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
        )


def _doc_mask_bounds(b, sk, doc_len):
    """C=1 causal document mask: tokens attend within their document."""
    starts = []
    for j in range(sk):
        doc_end = ((j // doc_len) + 1) * doc_len
        starts.append(min(doc_end, sk))
    idx = np.asarray(starts, np.int32).reshape(1, 1, sk, 1)
    return jnp.asarray(np.broadcast_to(idx, (b, 1, sk, 1)))


class TestFlashMaskPallas:
    def test_c1_document_mask(self):
        b, s = 2, 64
        q, k, v = _qkv(b=b, sq=s, sk=s)
        idx = _doc_mask_bounds(b, s, doc_len=16)
        out = flashmask_attention_pallas(q, k, v, idx, causal=True, interpret=True)
        bias = make_flashmask_bias(idx, s, s, True)
        ref = _xla_attention(q, k, v, bias=bias, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_c2_sliding_window(self):
        b, s, w = 1, 64, 16
        q, k, v = _qkv(b=b, sq=s, sk=s)
        # sliding window: for column j mask rows in [j + w, Sq)
        start = np.minimum(np.arange(s) + w, s).astype(np.int32)
        end = np.full(s, s, np.int32)
        idx = jnp.asarray(np.stack([start, end], -1).reshape(1, 1, s, 2))
        out = flashmask_attention_pallas(q, k, v, idx, causal=True, interpret=True)
        bias = make_flashmask_bias(idx, s, s, True)
        ref = _xla_attention(q, k, v, bias=bias, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_c4_bidirectional_bands(self):
        b, s = 1, 32
        q, k, v = _qkv(b=b, sq=s, sk=s, h=2, d=16)
        rng = np.random.default_rng(0)
        lts = rng.integers(0, s, s).astype(np.int32)
        lte = np.minimum(lts + rng.integers(0, 8, s), s).astype(np.int32)
        uts = rng.integers(0, s // 2, s).astype(np.int32)
        ute = np.minimum(uts + rng.integers(0, 4, s), s).astype(np.int32)
        idx = jnp.asarray(np.stack([lts, lte, uts, ute], -1).reshape(1, 1, s, 4))
        out = flashmask_attention_pallas(q, k, v, idx, causal=False, interpret=True)
        bias = make_flashmask_bias(idx, s, s, False)
        ref = _xla_attention(q, k, v, bias=bias, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_flashmask_grads(self):
        b, s = 1, 32
        q, k, v = _qkv(b=b, sq=s, sk=s, h=2, d=16)
        idx = _doc_mask_bounds(b, s, doc_len=8)

        def f_pallas(q, k, v):
            return flashmask_attention_pallas(q, k, v, idx, causal=True, interpret=True).sum()

        def f_ref(q, k, v):
            bias = make_flashmask_bias(idx, s, s, True)
            return _xla_attention(q, k, v, bias=bias, causal=True).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4)

    def test_per_head_mask(self):
        b, s, h = 1, 32, 2
        q, k, v = _qkv(b=b, sq=s, sk=s, h=h, d=16)
        idx1 = np.asarray(_doc_mask_bounds(1, s, 8))
        idx2 = np.asarray(_doc_mask_bounds(1, s, 16))
        idx = jnp.asarray(np.concatenate([idx1, idx2], axis=1))  # [1, 2, S, 1]
        out = flashmask_attention_pallas(q, k, v, idx, causal=True, interpret=True)
        bias = make_flashmask_bias(idx, s, s, True)
        ref = _xla_attention(q, k, v, bias=bias, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_maxmin_blocks(self):
        idx = _doc_mask_bounds(1, 64, 16)
        mn, mx = flashmask_maxmin(idx, block_size=16)
        assert mn.shape == (1, 1, 4, 1) and mx.shape == (1, 1, 4, 1)
        np.testing.assert_array_equal(np.asarray(mn)[0, 0, :, 0], [16, 32, 48, 64])


class TestFusedKernels:
    def test_rms_norm_fwd(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 17, 256), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (256,)) * 0.1 + 1.0
        y = fused_rms_norm_pallas(x, w, epsilon=1e-6, interpret=True)
        ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_rms_norm_grads(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 64), jnp.float32)
        w = jnp.ones((64,)) * 1.5

        def f_pallas(x, w):
            return (fused_rms_norm_pallas(x, w, interpret=True) ** 2).sum()

        def f_ref(x, w):
            y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
            return (y**2).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1))(x, w)
        gr = jax.grad(f_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]), rtol=1e-4, atol=1e-5)

    def test_rope(self):
        b, s, h, d = 2, 16, 4, 32
        x = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d), jnp.float32)
        inv = 1.0 / (10000 ** (jnp.arange(0, d, 2) / d))
        t = jnp.arange(s)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(t), jnp.cos(t)], -1)
        sin = jnp.concatenate([jnp.sin(t), jnp.sin(t)], -1)
        y = fused_rope_pallas(x, cos, sin, interpret=True)
        x1, x2 = x[..., : d // 2], x[..., d // 2 :]
        rot = jnp.concatenate([-x2, x1], -1)
        ref = x * cos[None, :, None, :] + rot * sin[None, :, None, :]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_rope_grad(self):
        # custom VJP: Pallas bwd kernel must match autodiff of the reference
        # composition — including asymmetric sin/cos halves (no table symmetry)
        b, s, h, d = 2, 8, 2, 32
        key = jax.random.PRNGKey(7)
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (b, s, h, d), jnp.float32)
        cos = jax.random.normal(k2, (s, d), jnp.float32)
        sin = jax.random.normal(k3, (s, d), jnp.float32)

        def f_pallas(x, cos, sin):
            return (fused_rope_pallas(x, cos, sin, interpret=True) ** 2).sum()

        def f_ref(x, cos, sin):
            x1, x2 = x[..., : d // 2], x[..., d // 2 :]
            rot = jnp.concatenate([-x2, x1], -1)
            y = x * cos[None, :, None, :] + rot * sin[None, :, None, :]
            return (y**2).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(x, cos, sin)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, cos, sin)
        np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]), rtol=1e-4, atol=1e-4)
        # table grads come back in the kernel's [1, S, D] layout
        np.testing.assert_allclose(
            np.asarray(gp[1]).reshape(s, d), np.asarray(gr[1]), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(gp[2]).reshape(s, d), np.asarray(gr[2]), rtol=1e-4, atol=1e-4
        )


class TestStateScanKernelStructure:
    """What keeps ``benchmarks/metrics/ssm_pct.serve.py`` counting the scan's
    carried-state kernel: the metric is the device time of operations whose
    scope path holds ``ssm_mixer``, so a ``pallas_call`` that left the scope
    (or changed its event name) would make the metric fall for no reason."""

    def _kernel_scopes(self, monkeypatch):
        """``{kernel name: [scope path of each of its pallas_calls]}`` of a small hybrid engine's step."""
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM

        config = NemotronHConfig(  # a state-space block of shapes the kernel takes: two heads of 64 a group, state 128
            vocab_size=128, hidden_size=64, num_hidden_layers=2, hybrid_override_pattern="ME", mamba_num_heads=4,
            mamba_head_dim=64, n_groups=2, ssm_state_size=128, n_routed_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32, max_position_embeddings=64,
            dtype="float32",
        )
        model = NemotronHForCausalLM(config)
        model.eval()
        eng = ContinuousBatchingEngine(model, max_slots=2, block_size=16, prompt_bucket=32, max_model_len=64)
        s, c = eng.max_slots, eng.prefill_chunk
        zeros = jnp.zeros((s,), jnp.int32)
        args = (eng._param_arrays(), eng._caches + eng._states, jnp.zeros((s, c), jnp.int32),
                jnp.zeros((s, eng.max_blocks_per_seq), jnp.int32), zeros, zeros, jnp.zeros((s,), bool), zeros, zeros)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatch takes its Pallas branch
        found = {}

        def walk(jaxpr, prefix):
            for eqn in jaxpr.eqns:
                path = "/".join(filter(None, (prefix, str(eqn.source_info.name_stack))))
                if eqn.primitive.name == "pallas_call":
                    found.setdefault(eqn.params["name"], []).append(path)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, path)  # a nested jit's scopes are relative to its call

        walk(jax.make_jaxpr(eng._step_impl)(*args).jaxpr, "")
        return found

    def test_the_kernel_sits_under_ssm_scan_inside_ssm_mixer(self, monkeypatch):
        from paddle_tpu.inference.paged_kv import SCOPE_SSM_SCAN
        from paddle_tpu.kernels.ssm_scan import KERNEL_SCAN
        from paddle_tpu.models.nemotron_h import SCOPE_SSM

        assert (KERNEL_SCAN, SCOPE_SSM, SCOPE_SSM_SCAN) == ("ssm_state_scan", "ssm_mixer", "ssm_scan")
        scopes = self._kernel_scopes(monkeypatch)
        assert len(scopes[KERNEL_SCAN]) == 1  # one M block, one kernel
        assert scopes[KERNEL_SCAN][0].split("/")[:2] == [SCOPE_SSM, SCOPE_SSM_SCAN], scopes
