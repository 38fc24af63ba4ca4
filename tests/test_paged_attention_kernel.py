"""The Pallas paged-attention kernel: interpret-mode numerics parity with the
XLA gather path, ragged lengths, GQA, and static TPU (Mosaic) lowering. A
plain decode step is the chunk kernel at ``C == 1`` (``chunk_decode_step``
below)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import paged_flash_chunk

BS = 16  # tokens per physical block


def chunk_decode_step(q, key_cache, value_cache, tables, lens, **kw):
    """One decode token a sequence (``q [B, HQ, D]``) through the chunk
    kernel at ``C == 1``. ``lens`` INCLUDES the current token, whose KV is
    already in the pool; a sequence of length 0 is an inactive slot."""
    out = paged_flash_chunk(
        q[:, None], key_cache, value_cache, tables, jnp.maximum(lens - 1, 0),
        (lens > 0).astype(jnp.int32), **kw,
    )
    return out[:, 0]


def _setup(b=3, hq=4, hkv=4, d=64, mbs=4, nb=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    key_cache = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), dtype)
    value_cache = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), dtype)
    # disjoint random block tables
    perm = rng.permutation(nb)[: b * mbs].reshape(b, mbs)
    tables = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray(rng.integers(1, mbs * BS + 1, (b,)), jnp.int32)
    return q, key_cache, value_cache, tables, lens


def _reference(q, key_cache, value_cache, tables, lens):
    """Dense-gather reference (the XLA path's math)."""
    b, hq, d = q.shape
    hkv = key_cache.shape[1]
    gk = jnp.moveaxis(key_cache[tables], 2, 3).reshape(b, -1, hkv, d)
    gv = jnp.moveaxis(value_cache[tables], 2, 3).reshape(b, -1, hkv, d)
    if hkv != hq:
        gk = jnp.repeat(gk, hq // hkv, axis=2)
        gv = jnp.repeat(gv, hq // hkv, axis=2)
    qf = q.astype(jnp.float32) / np.sqrt(d)
    s = jnp.einsum("bhd,blhd->bhl", qf, gk.astype(jnp.float32))
    mask = jnp.arange(gk.shape[1])[None, None, :] < lens[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p, gv.astype(jnp.float32)).astype(q.dtype)


class TestPagedFlashDecode:
    def test_matches_dense_gather(self):
        args = _setup()
        out = chunk_decode_step(*args, interpret=True)
        ref = _reference(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        args = _setup(hq=8, hkv=2, seed=1)
        out = chunk_decode_step(*args, interpret=True)
        ref = _reference(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_single_token_sequence(self):
        q, kc, vc, tables, _ = _setup(seed=2)
        lens = jnp.ones((q.shape[0],), jnp.int32)
        out = chunk_decode_step(q, kc, vc, tables, lens, interpret=True)
        ref = _reference(q, kc, vc, tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_shared_physical_block_between_sequences(self):
        """Two sequences may map to the SAME physical block (prefix sharing)."""
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(8, 4, BS, 64)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(8, 4, BS, 64)), jnp.float32)
        tables = jnp.asarray([[5, 1], [5, 2]], jnp.int32)  # shared block 5
        lens = jnp.asarray([20, 24], jnp.int32)
        out = chunk_decode_step(q, kc, vc, tables, lens, interpret=True)
        ref = _reference(q, kc, vc, tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        args = _setup(seed=4, dtype=jnp.bfloat16)
        out = chunk_decode_step(*args, interpret=True)
        ref = _reference(*args)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
        )

    def test_block_multihead_attention_uses_it_when_flagged(self, monkeypatch):
        """The serving entry routes to the kernel under the flag (fallback
        keeps numerics when the kernel import explodes)."""
        import paddle_tpu.incubate.nn.functional.block_attention as ba
        import paddle_tpu.kernels.select as sel

        monkeypatch.setattr(sel, "pallas_enabled", lambda flag, **_: True)
        called = {}
        import paddle_tpu.kernels.paged_attention as pa

        real = pa.paged_flash_chunk

        def spy(*a, **kw):
            called["yes"] = True
            return real(*a, interpret=True, **{k: v for k, v in kw.items() if k != "interpret"})

        monkeypatch.setattr(pa, "paged_flash_chunk", spy)
        rng = np.random.default_rng(5)
        b, hq, d, nb, mbs = 2, 4, 64, 8, 2
        q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
        kc = jnp.zeros((nb, hq, BS, d), jnp.float32)
        vc = jnp.zeros((nb, hq, BS, d), jnp.float32)
        tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        lens = jnp.asarray([3, 7], jnp.int32)
        out, kc2, vc2 = ba.block_multihead_attention(q, k, v, kc, vc, tables, lens)
        assert called.get("yes")
        # parity vs the XLA path with the kernel disabled
        monkeypatch.setattr(sel, "pallas_enabled", lambda flag, **_: False)
        out_xla, _, _ = ba.block_multihead_attention(q, k, v, kc, vc, tables, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out_xla), rtol=2e-5, atol=2e-5
        )


class TestPagedDecodeExport:
    def test_lowers_for_tpu(self):
        args = _setup(b=2, hq=8, hkv=2, d=128, mbs=8, nb=32, dtype=jnp.bfloat16)

        def fn(q, kc, vc, tables, lens):
            return chunk_decode_step(q, kc, vc, tables, lens)

        jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)

    def test_lowers_for_tpu_serving_shape(self):
        # llama-7B-ish decode: 8 seqs, 32 q heads, 32 kv heads, d=128
        args = _setup(b=8, hq=32, hkv=32, d=128, mbs=16, nb=256, dtype=jnp.bfloat16)

        def fn(q, kc, vc, tables, lens):
            return chunk_decode_step(q, kc, vc, tables, lens)

        jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def test_zero_length_sequence_yields_zeros():
    """A padded/inactive batch slot (len 0) must produce zeros, not a silent
    mean over physical block 0 (fully-masked softmax degeneracy)."""
    q, kc, vc, tables, _ = _setup(seed=7)
    lens = jnp.asarray([0, 5, 0], jnp.int32)
    out = np.asarray(chunk_decode_step(q, kc, vc, tables, lens, interpret=True))
    assert np.all(out[0] == 0.0) and np.all(out[2] == 0.0)
    assert np.abs(out[1]).sum() > 0


def test_invalid_head_geometry_raises_at_trace_time():
    """hq % hkv != 0 raises when the kernel is traced, where the dispatch
    site's try/except can still degrade to the XLA path (the lowering
    probes that used to answer False for it are gone)."""
    import pytest

    q = jnp.zeros((2, 6, 128), jnp.bfloat16)
    kc = jnp.zeros((32, 4, 16, 128), jnp.bfloat16)
    tables = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="not a multiple of kv heads"):
        jax.eval_shape(lambda *a: chunk_decode_step(*a), q, kc, kc, tables, lens)


class TestRaggedSkip:
    """The ragged decode path: unused block-table tails and fully-padded
    slots are never touched (no DMA via the clamped index map, no compute via
    the pl.when guard)."""

    def test_unused_tail_blocks_never_read(self):
        """Poison every block past each sequence's last in-use block with
        NaN: the clamped index map + predicated compute must keep the output
        bit-identical to clean caches (the old path multiplied masked
        probabilities into NaN values — 0 * NaN = NaN)."""
        rng = np.random.default_rng(11)
        b, hq, d, mbs, nb = 2, 4, 64, 4, 16
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(nb, hq, BS, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(nb, hq, BS, d)), jnp.float32)
        tables = jnp.asarray(rng.permutation(nb)[: b * mbs].reshape(b, mbs), jnp.int32)
        lens = jnp.asarray([BS + 3, 2 * BS], jnp.int32)  # tails: 2 blocks each
        clean = chunk_decode_step(q, kc, vc, tables, lens, interpret=True)
        # poison the tail blocks (logical blocks >= ceil(len/BS))
        kc_p, vc_p = np.array(kc), np.array(vc)
        for bi in range(b):
            used = -(-int(lens[bi]) // BS)
            for lb in range(used, mbs):
                kc_p[int(tables[bi, lb])] = np.nan
                vc_p[int(tables[bi, lb])] = np.nan
        out = chunk_decode_step(
            q, jnp.asarray(kc_p), jnp.asarray(vc_p), tables, lens, interpret=True
        )
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))

    def test_padded_slot_skips_even_poisoned_pool(self):
        """A len-0 slot's whole block-table row may point at junk; its output
        is exact zeros and no NaN leaks in."""
        rng = np.random.default_rng(12)
        q, kc, vc, tables, _ = _setup(seed=12)
        kc = jnp.asarray(np.full(kc.shape, np.nan, np.float32))
        vc = jnp.asarray(np.full(vc.shape, np.nan, np.float32))
        lens = jnp.zeros((q.shape[0],), jnp.int32)
        out = np.asarray(chunk_decode_step(q, kc, vc, tables, lens, interpret=True))
        assert (out == 0.0).all()


# -- ragged MIXED prefill/decode kernel (chunked prefill) ---------------------



def _chunk_reference(q, key_cache, value_cache, tables, lens, q_lens):
    """Dense-gather reference for the mixed step (the XLA chunk path's
    math): query token j of sequence b sees cached positions < lens[b]+j+1;
    rows past q_lens emit zeros."""
    b, c, hq, d = q.shape
    hkv = key_cache.shape[1]
    gk = jnp.moveaxis(key_cache[tables], 2, 3).reshape(b, -1, hkv, d)
    gv = jnp.moveaxis(value_cache[tables], 2, 3).reshape(b, -1, hkv, d)
    if hkv != hq:
        gk = jnp.repeat(gk, hq // hkv, axis=2)
        gv = jnp.repeat(gv, hq // hkv, axis=2)
    qf = q.astype(jnp.float32) / np.sqrt(d)
    s = jnp.einsum("bchd,blhd->bchl", qf, gk.astype(jnp.float32))
    L = gk.shape[1]
    limit = lens[:, None] + jnp.arange(c)[None, :] + 1  # [B, C]
    mask = jnp.arange(L)[None, None, :] < limit[:, :, None]
    s = jnp.where(mask[:, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bchl,blhd->bchd", p, gv.astype(jnp.float32))
    row_valid = jnp.arange(c)[None, :] < q_lens[:, None]
    return jnp.where(row_valid[:, :, None, None], out, 0.0).astype(q.dtype)


def _chunk_setup(b=3, c=4, hq=4, hkv=4, d=64, mbs=4, nb=16, seed=0,
                 dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), dtype)
    kc = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), dtype)
    vc = jnp.asarray(rng.normal(size=(nb, hkv, BS, d)), dtype)
    tables = jnp.asarray(rng.permutation(nb)[: b * mbs].reshape(b, mbs), jnp.int32)
    # ragged mix: a decode row (1), a full prompt chunk (c), an inactive (0)
    q_lens = jnp.asarray([1, c, 0][:b] + [1] * max(0, b - 3), jnp.int32)
    lens = jnp.asarray(rng.integers(0, mbs * BS - c, (b,)), jnp.int32)
    return q, kc, vc, tables, lens, q_lens


class TestPagedFlashChunk:
    def test_mixed_rows_match_dense_gather(self):
        args = _chunk_setup()
        out = paged_flash_chunk(*args, interpret=True)
        ref = _chunk_reference(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gqa_chunk(self):
        args = _chunk_setup(hq=8, hkv=2, seed=1)
        out = paged_flash_chunk(*args, interpret=True)
        ref = _chunk_reference(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_inactive_rows_exact_zero_even_poisoned_pool(self):
        """q_lens == 0 slots and rows past q_lens must emit EXACT zeros even
        when every pool value is NaN — the engine's padded slots."""
        q, kc, vc, tables, lens, _ = _chunk_setup(seed=2)
        kc = jnp.full_like(kc, jnp.nan)
        vc = jnp.full_like(vc, jnp.nan)
        q_lens = jnp.zeros((q.shape[0],), jnp.int32)
        out = paged_flash_chunk(q, kc, vc, tables, lens, q_lens, interpret=True)
        assert np.array_equal(np.asarray(out), np.zeros_like(np.asarray(out)))

    def test_decode_row_equals_decode_kernel(self):
        """A chunk of C == 4 with q_lens == 1 must reproduce the C == 1
        call's output for its first row — the two raggednesses agree."""
        q, kc, vc, tables, lens = _setup(seed=5)
        b, hq, d = q.shape
        c = 4
        qc = jnp.zeros((b, c, hq, d), q.dtype).at[:, 0].set(q)
        q_lens = jnp.ones((b,), jnp.int32)
        # decode semantics: the current token is ALREADY appended in the
        # pool, and `lens` EXCLUDES it — mirror that for the chunk call
        out_c = paged_flash_chunk(
            qc, kc, vc, tables, jnp.maximum(lens - 1, 0), q_lens, interpret=True
        )
        out_d = chunk_decode_step(q, kc, vc, tables, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out_c[:, 0]), np.asarray(out_d), rtol=2e-5, atol=2e-5
        )

    def test_chunk_lowers_for_tpu_serving_shape(self):
        """The engine's unified mixed step lowers for TPU at a serving
        geometry (8 slots x 16-token chunks, llama-7B-ish heads)."""
        args = _chunk_setup(b=8, c=16, hq=32, hkv=32, d=128, mbs=16, nb=256,
                            dtype=jnp.bfloat16)

        def fn(q, kc, vc, tables, lens, q_lens):
            return paged_flash_chunk(q, kc, vc, tables, lens, q_lens)

        jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


# -- the length-bounded page walk (one body: plain / rope-fused x float / int8) --

from paddle_tpu.incubate.nn.functional import _rope_apply_xla  # noqa: E402
from paddle_tpu.incubate.nn.functional.block_attention import _gather_chunk_attend  # noqa: E402

W_B, W_C, W_D, W_MBS, W_NB = 4, 4, 64, 17, 80  # max_model_len = 17 pages = 272
W_TILE = 128  # key positions of one tile of the walk: 8 pages of 16
W_FULL = W_MBS * BS
# live length = lens + q_lens; every case mixes q_lens of 0, 1 and C
WALK_CASES = {
    # live 0 (inactive), 1, block_size, block_size + 1
    "short_decode": ([0, 0, BS - 1, BS], [0, 1, 1, 1]),
    # live C from an empty cache, block_size and block_size + 1 ending a chunk, inactive with a history
    "short_chunk": ([0, BS - W_C, BS - W_C + 1, 5], [W_C, W_C, W_C, 0]),
    # one tile of pages exactly, one position more, one page more exactly, and one position past that
    "tile_edges_decode": ([W_TILE - 1, W_TILE, W_TILE + BS - 1, W_TILE + BS], [1, 1, 1, 1]),
    "tile_edges_chunk": ([W_TILE - W_C, W_TILE - W_C + 1, W_TILE + BS - W_C, 3], [W_C, W_C, W_C, 1]),
    # the full max_model_len beside length-0 slots
    "full_beside_empty": ([W_FULL - W_C, 0, W_FULL - 1, 0], [W_C, 0, 1, 0]),
}
WALK_TOL = {"f32": 2e-5, "bf16": 2e-2, "int8": 2e-5}


def _walk_setup(group, kv, seed=0):
    rng = np.random.default_rng(seed)
    hkv = 2
    hq = hkv * group
    qdt = jnp.bfloat16 if kv == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(W_B, W_C, hq, W_D)), qdt)
    ang = rng.uniform(0, 6.28, size=(W_B, W_C, 1, W_D // 2))
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1), jnp.float32)
    shape = (W_NB, hkv, BS, W_D)
    if kv == "int8":
        kc = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        vc = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(0.005, 0.02, shape[:3]), jnp.float32),
            v_scale=jnp.asarray(rng.uniform(0.005, 0.02, shape[:3]), jnp.float32),
        )
    else:
        kc, vc, scales = jnp.asarray(rng.normal(size=shape), qdt), jnp.asarray(rng.normal(size=shape), qdt), {}
    tables = jnp.asarray(rng.permutation(W_NB)[: W_B * W_MBS].reshape(W_B, W_MBS), jnp.int32)
    return q, cos, sin, kc, vc, scales, tables


@functools.lru_cache(maxsize=None)
def _walk_fns(fused, interpret=True):
    """(kernel, XLA gather reference) over the same arguments, jitted once a variant."""
    scale = 1.0 / np.sqrt(W_D)

    def kernel(q, cos, sin, kc, vc, scales, tables, lens, q_lens):
        if fused:
            return paged_flash_chunk(
                q, kc, vc, tables, lens, q_lens, scale=scale, interpret=interpret,
                cos=cos[:, :, 0], sin=sin[:, :, 0], **scales,
            )
        q = _rope_apply_xla(q, sin, cos, True)
        return paged_flash_chunk(q, kc, vc, tables, lens, q_lens, scale=scale, interpret=interpret, **scales)

    def reference(q, cos, sin, kc, vc, scales, tables, lens, q_lens):
        q = _rope_apply_xla(q, sin, cos, True)
        return _gather_chunk_attend(q, kc, vc, tables, lens, q_lens, scale, **scales)

    return jax.jit(kernel), jax.jit(reference)



@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("group", [4, 1], ids=["gqa4", "mha"])
def test_page_walk_matches_xla_gather(group, fused, kv, case):
    args = _walk_setup(group, kv, seed=len(case))
    lens, q_lens = (jnp.asarray(x, jnp.int32) for x in WALK_CASES[case])
    kernel, reference = _walk_fns(fused)
    out = np.asarray(kernel(*args, lens, q_lens), np.float32)
    ref = np.asarray(reference(*args, lens, q_lens), np.float32)
    np.testing.assert_allclose(out, ref, rtol=WALK_TOL[kv], atol=WALK_TOL[kv])
    # rows past q_lens (and whole inactive slots) are exact zeros
    dead = np.arange(W_C)[None, :] >= np.asarray(q_lens)[:, None]
    assert (out[dead] == 0.0).all()
    assert np.abs(out[~dead]).sum() > 0


@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("kv", ["f32", "int8"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_page_walk_never_reads_past_the_live_bound(fused, kv, case):
    """Every pool page that no live position uses holds NaN (int8: NaN scales),
    and the tables' tail entries point at such pages: the bounded loop and the
    prefetch of the next tile must not run one page too far."""
    q, cos, sin, kc, vc, scales, tables = _walk_setup(4, kv, seed=3)
    lens, q_lens = (np.asarray(x, np.int32) for x in WALK_CASES[case])
    live = np.zeros(W_NB, bool)
    for b in range(W_B):
        if q_lens[b]:
            live[np.asarray(tables)[b, : -(-(lens[b] + q_lens[b]) // BS)]] = True
    assert not live.all()

    def poison(x):
        x = np.array(x)
        x[~live] = np.nan
        return jnp.asarray(x)

    if kv == "int8":
        bad = (q, cos, sin, kc, vc, {k: poison(v) for k, v in scales.items()}, tables)
    else:
        bad = (q, cos, sin, poison(kc), poison(vc), scales, tables)
    kernel, reference = _walk_fns(fused)
    out = np.asarray(kernel(*bad, jnp.asarray(lens), jnp.asarray(q_lens)))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, np.asarray(kernel(q, cos, sin, kc, vc, scales, tables, lens, q_lens)))
    ref = np.asarray(reference(q, cos, sin, kc, vc, scales, tables, lens, q_lens))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_page_walk_under_the_tpu_interpreter(kv):
    """The same walk under ``pltpu.InterpretParams()``, which simulates the
    DMAs and their semaphores: every copy started is waited for."""
    from jax.experimental.pallas import tpu as pltpu

    args = _walk_setup(4, kv, seed=5)
    lens, q_lens = (jnp.asarray(x, jnp.int32) for x in WALK_CASES["tile_edges_chunk"])
    kernel, reference = _walk_fns(True, pltpu.InterpretParams())
    np.testing.assert_allclose(
        np.asarray(kernel(*args, lens, q_lens)), np.asarray(reference(*args, lens, q_lens)), rtol=2e-5, atol=2e-5
    )


def test_walk_geometry_follows_the_operand():
    """Pages a tile and heads a cell come from shapes alone: the tp=4 shard's 2
    KV heads ride whole, 32 MHA heads of bf16 fill the budget exactly, and
    float32 pages of the same count split in two."""
    from paddle_tpu.kernels.paged_attention import _walk_geometry

    assert _walk_geometry(8, 16, 128, jnp.bfloat16) == (8, 8)
    assert _walk_geometry(2, 16, 128, jnp.int8) == (8, 2)
    assert _walk_geometry(32, 16, 128, jnp.bfloat16) == (8, 32)
    assert _walk_geometry(32, 16, 128, jnp.float32) == (8, 16)
    assert _walk_geometry(8, 256, 128, jnp.bfloat16) == (1, 8)


@pytest.mark.parametrize("d, bs", [(64, 16), (128, 4)], ids=["head_dim_64", "block_4"])
def test_page_walk_refuses_pages_it_cannot_copy_at_trace_time(d, bs):
    """A copy out of HBM wants 128-lane rows and whole sublane tiles: such a
    geometry raises while the kernel is traced for the chip, where the dispatch
    site's try/except still degrades to the XLA path (interpret mode, which
    copies nothing, takes any geometry)."""
    q = jnp.zeros((2, 4, 4, d), jnp.bfloat16)
    kc = jnp.zeros((8, 2, bs, d), jnp.bfloat16)
    tables = jnp.zeros((2, 4), jnp.int32)
    lens = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128 lanes"):
        jax.eval_shape(lambda *a: paged_flash_chunk(*a), q, kc, kc, tables, lens, lens)
    jax.eval_shape(lambda *a: paged_flash_chunk(*a, interpret=True), q, kc, kc, tables, lens, lens)
