"""Paged (blocked) KV-cache attention (reference ``block_multihead_attention_``
fused_ops.yaml:45 / block_multi_head_attention_kernel.cu): allocator reuse,
prefill + decode parity vs dense attention, jit/donation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.functional import (
    BlockKVCache,
    block_cache_prefill,
    block_multihead_attention,
    block_multihead_chunk_attention,
)

B, HQ, HKV, D = 2, 4, 2, 8
BS = 4  # block size


def _dense_attention(q, ks, vs, lens):
    """Reference: full attention of one query over each sequence's prefix."""
    b, hq, d = q.shape[0], q.shape[2], q.shape[3]
    rep = hq // ks.shape[2]
    k = np.repeat(ks, rep, axis=2).astype(np.float32)
    v = np.repeat(vs, rep, axis=2).astype(np.float32)
    out = np.zeros((b, 1, hq, d), np.float32)
    for i in range(b):
        L = lens[i]
        qi = q[i, 0].astype(np.float32) / np.sqrt(d)  # [H, D]
        scores = np.einsum("hd,lhd->hl", qi, k[i, :L])
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        out[i, 0] = np.einsum("hl,lhd->hd", probs, v[i, :L])
    return out


class TestAllocator:
    def test_alloc_grow_free_reuse(self):
        cache = BlockKVCache(num_blocks=8, block_size=BS, num_heads=HKV, head_dim=D,
                             max_blocks_per_seq=4)
        cache.allocate(seq_id=0, num_tokens=5)  # needs 2 blocks
        cache.allocate(seq_id=1, num_tokens=3)  # 1 block
        assert cache.free_blocks == 8 - 3
        assert cache.seq_len(0) == 5 and cache.seq_len(1) == 3
        cache.allocate(0, 4)  # 9 tokens -> 3 blocks
        assert cache.free_blocks == 8 - 4
        t = cache.block_table([0, 1])
        assert t.shape == (2, 4)
        # block ids are disjoint between live sequences
        used0 = set(np.asarray(t[0][:3]).tolist())
        used1 = {int(t[1][0])}
        assert used0.isdisjoint(used1)
        cache.free(0)
        assert cache.free_blocks == 8 - 1
        # freed blocks get reused
        cache.allocate(2, 12)
        assert cache.free_blocks == 8 - 4

    def test_pool_exhaustion_raises(self):
        cache = BlockKVCache(2, BS, HKV, D, max_blocks_per_seq=4)
        cache.allocate(0, 2 * BS)
        with pytest.raises(MemoryError):
            cache.allocate(1, 1)


class TestPagedAttention:
    def _setup(self, prompt_lens):
        rng = np.random.default_rng(3)
        S = max(prompt_lens)
        ks = rng.normal(size=(B, S + 8, HKV, D)).astype(np.float32)
        vs = rng.normal(size=(B, S + 8, HKV, D)).astype(np.float32)
        cache = BlockKVCache(num_blocks=16, block_size=BS, num_heads=HKV, head_dim=D,
                             max_blocks_per_seq=4, dtype=jnp.float32)
        for i, L in enumerate(prompt_lens):
            cache.allocate(i, L)
        tables = cache.block_table(range(B))
        kc, vc = block_cache_prefill(
            cache.key_cache, cache.value_cache,
            jnp.asarray(ks[:, :S]), jnp.asarray(vs[:, :S]),
            tables, jnp.asarray(prompt_lens, jnp.int32),
        )
        return rng, ks, vs, cache, tables, kc, vc

    def test_prefill_then_decode_matches_dense(self):
        prompt_lens = [5, 7]
        rng, ks, vs, cache, tables, kc, vc = self._setup(prompt_lens)
        # one decode step per sequence: new token at position prompt_len
        q = rng.normal(size=(B, 1, HQ, D)).astype(np.float32)
        new_k = np.stack([ks[i, prompt_lens[i]] for i in range(B)])[:, None]
        new_v = np.stack([vs[i, prompt_lens[i]] for i in range(B)])[:, None]
        out, kc, vc = block_multihead_attention(
            jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
            kc, vc, tables, jnp.asarray(prompt_lens, jnp.int32),
        )
        ref = _dense_attention(q, ks, vs, [l + 1 for l in prompt_lens])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_multi_step_decode_crosses_block_boundary(self):
        prompt_lens = [3, 2]  # appending will cross the BS=4 boundary
        rng, ks, vs, cache, tables, kc, vc = self._setup(prompt_lens)
        lens = list(prompt_lens)
        for step in range(6):  # positions 3..8 / 2..7 -> into blocks 1 and 2
            for i in range(B):
                cache.allocate(i, 1)
            tables = cache.block_table(range(B))
            q = rng.normal(size=(B, 1, HQ, D)).astype(np.float32)
            new_k = np.stack([ks[i, lens[i]] for i in range(B)])[:, None]
            new_v = np.stack([vs[i, lens[i]] for i in range(B)])[:, None]
            out, kc, vc = block_multihead_attention(
                jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
                kc, vc, tables, jnp.asarray(lens, jnp.int32),
            )
            lens = [l + 1 for l in lens]
            ref = _dense_attention(q, ks, vs, lens)
            np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5,
                                       err_msg=f"step {step}")

    def test_jit_compiles_once_with_donation(self):
        prompt_lens = [4, 4]
        rng, ks, vs, cache, tables, kc, vc = self._setup(prompt_lens)
        step = jax.jit(block_multihead_attention, donate_argnums=(3, 4))
        lens = list(prompt_lens)
        for _ in range(3):
            for i in range(B):
                cache.allocate(i, 1)
            q = rng.normal(size=(B, 1, HQ, D)).astype(np.float32)
            new_k = np.stack([ks[i, lens[i]] for i in range(B)])[:, None]
            new_v = np.stack([vs[i, lens[i]] for i in range(B)])[:, None]
            out, kc, vc = step(
                jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
                kc, vc, cache.block_table(range(B)), jnp.asarray(lens, jnp.int32),
            )
            lens = [l + 1 for l in lens]
        ref = _dense_attention(q, ks, vs, lens)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_nonshared_blocks_isolated(self):
        """Writing sequence 0's tokens never touches sequence 1's blocks."""
        prompt_lens = [4, 4]
        _, ks, vs, cache, tables, kc, vc = self._setup(prompt_lens)
        before = np.asarray(kc[np.asarray(tables[1][:1])])
        cache.allocate(0, 1)
        t2 = cache.block_table(range(B))
        new_k = jnp.ones((B, 1, HKV, D), jnp.float32)
        _, kc2, _ = block_multihead_attention(
            jnp.zeros((B, 1, HQ, D), jnp.float32), new_k, new_k,
            kc, vc, t2, jnp.asarray([4, 3], jnp.int32),
        )
        # seq 1 wrote into its own block at pos 3; seq 0 into a new block.
        # positions 0..2 of seq 1's first block are untouched
        # (cache layout [NB, H, BS, D]: token positions are axis 2)
        after = np.asarray(kc2[np.asarray(t2[1][:1])])
        np.testing.assert_array_equal(before[0, :, :3], after[0, :, :3])


class TestSlotMask:
    """Ragged-batch contract for the continuous-batching engine: masked-off
    slots append nothing, attend over nothing, return zeros — XLA fallback in
    lockstep with the Pallas kernel."""

    def _setup(self, seed=9):
        rng = np.random.default_rng(seed)
        nb, mbs = 8, 2
        q = jnp.asarray(rng.normal(size=(B, 1, HQ, D)), jnp.float32)
        k1 = jnp.asarray(rng.normal(size=(B, 1, HKV, D)), jnp.float32)
        v1 = jnp.asarray(rng.normal(size=(B, 1, HKV, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(nb, HKV, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(nb, HKV, BS, D)), jnp.float32)
        # slot 1's table row deliberately ALIASES slot 0's blocks (an evicted
        # slot's zeroed row points at block ids a live sequence may own)
        tables = jnp.asarray([[2, 3], [2, 3]], jnp.int32)
        lens = jnp.asarray([5, 3], jnp.int32)
        return q, k1, v1, kc, vc, tables, lens

    def test_masked_slot_writes_nothing_returns_zeros(self):
        q, k1, v1, kc, vc, tables, lens = self._setup()
        mask = jnp.asarray([True, False])
        out, kc2, vc2 = block_multihead_attention(
            q, k1, v1, kc, vc, tables, lens, slot_mask=mask
        )
        # slot 1 returned zeros
        assert (np.asarray(out)[1] == 0.0).all()
        assert np.abs(np.asarray(out)[0]).sum() > 0
        # slot 1's append was dropped: only slot 0's position changed
        ref_kc = np.array(kc)
        ref_kc[np.asarray(tables)[0, 5 // BS], :, 5 % BS] = np.asarray(k1)[0, 0]
        np.testing.assert_array_equal(np.asarray(kc2), ref_kc)

    def test_active_mask_all_true_matches_unmasked(self):
        q, k1, v1, kc, vc, tables, lens = self._setup(seed=10)
        tables = jnp.asarray([[2, 3], [4, 5]], jnp.int32)  # disjoint this time
        out_m, kc_m, vc_m = block_multihead_attention(
            q, k1, v1, kc, vc, tables, lens, slot_mask=jnp.asarray([True, True])
        )
        out_u, kc_u, vc_u = block_multihead_attention(
            q, k1, v1, kc, vc, tables, lens
        )
        np.testing.assert_array_equal(np.asarray(out_m), np.asarray(out_u))
        np.testing.assert_array_equal(np.asarray(kc_m), np.asarray(kc_u))

    def test_kernel_lockstep_with_xla_fallback(self, monkeypatch):
        """Same inputs + slot_mask through the Pallas kernel (interpret) and
        the XLA gather path: identical zeros for the masked slot, matching
        outputs for the live one."""
        import paddle_tpu.kernels.paged_attention as pa
        import paddle_tpu.kernels.select as sel

        q, k1, v1, kc, vc, tables, lens = self._setup(seed=11)
        mask = jnp.asarray([False, True])
        out_xla, _, _ = block_multihead_attention(
            q, k1, v1, kc, vc, tables, lens, slot_mask=mask
        )
        monkeypatch.setattr(sel, "pallas_enabled", lambda flag, **_: True)
        real = pa.paged_flash_chunk
        monkeypatch.setattr(
            pa, "paged_flash_chunk",
            lambda *a, **kw: real(*a, interpret=True, **kw),
        )
        out_k, _, _ = block_multihead_attention(
            q, k1, v1, kc, vc, tables, lens, slot_mask=mask
        )
        assert (np.asarray(out_k)[0] == 0.0).all()
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_xla), rtol=2e-5, atol=2e-5
        )


def _decode_roped(q, k, v, cos, sin, kc, vc, tables, lens, **kw):
    """One decode token a sequence through the serving step's entry: the
    chunk at ``C == 1`` with RoPE folded in (q and k come pre-rope)."""
    return block_multihead_chunk_attention(
        q, k, v, kc, vc, tables, lens, jnp.ones_like(lens), cos=cos, sin=sin, **kw
    )


class TestRopedDecodeStep:
    """The rope-folded entry at ``C == 1``. On a backend without the kernel
    it must execute the SAME op composition as rope-then-attend
    (byte-identical outputs); with the kernel forced on (interpret mode),
    numerics stay in lockstep with the XLA fallback."""

    def _setup(self, seed=13):
        rng = np.random.default_rng(seed)
        nb, mbs = 8, 2
        q = jnp.asarray(rng.normal(size=(B, 1, HQ, D)), jnp.float32)
        k1 = jnp.asarray(rng.normal(size=(B, 1, HKV, D)), jnp.float32)
        v1 = jnp.asarray(rng.normal(size=(B, 1, HKV, D)), jnp.float32)
        cos = jnp.asarray(np.cos(rng.normal(size=(B, 1, 1, D))), jnp.float32)
        sin = jnp.asarray(np.sin(rng.normal(size=(B, 1, 1, D))), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(nb, HKV, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(nb, HKV, BS, D)), jnp.float32)
        tables = jnp.asarray([[2, 3], [4, 5]], jnp.int32)
        lens = jnp.asarray([5, 3], jnp.int32)
        return q, k1, v1, cos, sin, kc, vc, tables, lens

    def test_fallback_byte_identical_to_unfused_composition(self):
        from paddle_tpu.incubate.nn.functional import _rope_apply_xla

        q, k1, v1, cos, sin, kc, vc, tables, lens = self._setup()
        out_f, kc_f, vc_f = _decode_roped(q, k1, v1, cos, sin, kc, vc, tables, lens)
        q_r = _rope_apply_xla(q, sin, cos, True)
        k_r = _rope_apply_xla(k1, sin, cos, True)
        out_u, kc_u, vc_u = block_multihead_attention(
            q_r, k_r, v1, kc, vc, tables, lens
        )
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_u))
        np.testing.assert_array_equal(np.asarray(kc_f), np.asarray(kc_u))
        np.testing.assert_array_equal(np.asarray(vc_f), np.asarray(vc_u))

    def test_kernel_lockstep_with_xla_fallback(self, monkeypatch):
        import paddle_tpu.kernels.paged_attention as pa
        import paddle_tpu.kernels.select as sel

        q, k1, v1, cos, sin, kc, vc, tables, lens = self._setup(seed=14)
        mask = jnp.asarray([False, True])
        out_xla, _, _ = _decode_roped(q, k1, v1, cos, sin, kc, vc, tables, lens, slot_mask=mask)
        monkeypatch.setattr(sel, "pallas_enabled", lambda flag, **_: True)
        real = pa.paged_flash_chunk
        monkeypatch.setattr(
            pa, "paged_flash_chunk",
            lambda *a, **kw: real(*a, interpret=True, **kw),
        )
        out_k, _, _ = _decode_roped(q, k1, v1, cos, sin, kc, vc, tables, lens, slot_mask=mask)
        assert (np.asarray(out_k)[0] == 0.0).all()
        assert np.abs(np.asarray(out_k)[1]).sum() > 0
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_xla), rtol=2e-5, atol=2e-5
        )
