"""The typed paged-KV state (``inference/paged_kv.py``): what the engine, the
models and the paged kernel pass each other inside the compiled step.

- ``PagedKV`` / ``PagedBatch`` are pytrees whose leaves come out in the
  order the step's flat arguments always had, bf16 and int8;
- the two things a set owns (fork, append + attend) do what the positional
  tuples did, scales riding with their blocks;
- every KV set of a step shares ONE batch (192 sets at Ouro's counts);
- ``generate_paged`` runs the engine's own path: same tokens, bf16 and int8;
- the jit boundary has not moved: the lowered step's arguments, in count,
  order and donation, for Llama and Ouro;
- no positional read of a past is left in ``models/`` or the engine.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional import (
    block_cache_cow_copy,
    block_multihead_chunk_attention,
)
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.paged_kv import PagedBatch, PagedKV
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM

NB, KVH, BS, D, S, MBS = 12, 2, 4, 16, 3, 4
KV_DTYPES = pytest.mark.parametrize("kv", ["bf16", "int8"])


def _batch(rng, c=1):
    tables = jnp.asarray(rng.permutation(NB)[: S * MBS].reshape(S, MBS), jnp.int32)
    lens = jnp.asarray([5, 0, 9], jnp.int32)
    mask = jnp.asarray([True, False, True])
    q_lens = jnp.asarray([1, 0, c], jnp.int32)
    return PagedBatch(tables, lens, mask, q_lens)


def _pool(rng, kv, batch=None):
    """A pool with something in every page (so a wrong page shows)."""
    shape = (NB, KVH, BS, D)
    if kv == "int8":
        return PagedKV(
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.005, 0.02, shape[:3]), jnp.float32),
            jnp.asarray(rng.uniform(0.005, 0.02, shape[:3]), jnp.float32),
            batch=batch,
        )
    return PagedKV(
        jnp.asarray(rng.normal(size=shape), jnp.float32),
        jnp.asarray(rng.normal(size=shape), jnp.float32),
        batch=batch,
    )


# -- the pytree ----------------------------------------------------------------

@KV_DTYPES
def test_leaf_order_and_count_are_the_flat_arguments(kv):
    """key, value[, key_scale, value_scale], then the batch's block_tables,
    seq_lens, slot_mask, q_lens: 6 leaves, 8 quantised."""
    rng = np.random.default_rng(0)
    batch = _batch(rng)
    pool = _pool(rng, kv, batch)
    leaves = jax.tree.leaves(pool)
    want = [pool.key, pool.value]
    if kv == "int8":
        want += [pool.key_scale, pool.value_scale]
    want += [batch.block_tables, batch.seq_lens, batch.slot_mask, batch.q_lens]
    assert len(leaves) == (8 if kv == "int8" else 6)
    assert all(a is b for a, b in zip(leaves, want))
    assert pool.planes == tuple(want[:-4])


@KV_DTYPES
def test_round_trip_through_flatten_and_jit(kv):
    rng = np.random.default_rng(1)
    pool = _pool(rng, kv, _batch(rng))
    leaves, treedef = jax.tree.flatten(pool)
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, PagedKV) and isinstance(back.batch, PagedBatch)
    assert (back.key_scale is None) == (kv == "bf16")
    through = jax.jit(lambda p: p)(pool)  # crosses a jit boundary as it is
    assert isinstance(through, PagedKV)
    for a, b in zip(jax.tree.leaves(through), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zeros_decides_quantised_from_the_dtype():
    float_pool = PagedKV.zeros((NB, KVH, BS, D), jnp.bfloat16)
    assert float_pool.key_scale is None and len(float_pool.planes) == 2
    assert float_pool.key is not float_pool.value  # two buffers: an owner donates both
    int8_pool = PagedKV.zeros((NB, KVH, BS, D), jnp.int8)
    assert int8_pool.key.dtype == jnp.int8 and len(int8_pool.planes) == 4
    # scales of ONES: quantize(zeros) is q = 0, scale = 1
    assert int8_pool.key_scale.shape == (NB, KVH, BS) and float(int8_pool.value_scale.min()) == 1.0


# -- what a set owns -------------------------------------------------------------

@KV_DTYPES
def test_attend_is_the_chunk_entry_over_the_sets_planes(kv):
    """``PagedKV.attend`` against the functional entry called with the
    planes and the batch by hand (what the positional tuples spelled out)."""
    rng = np.random.default_rng(2)
    c, hq = 4, 4
    batch = _batch(rng, c=c)
    pool = _pool(rng, kv, batch)
    q = jnp.asarray(rng.normal(size=(S, c, hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, c, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, c, KVH, D)), jnp.float32)
    ang = rng.uniform(0, 6.28, size=(S, c, 1, D // 2))
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1), jnp.float32)
    out, new = pool.attend(q, k, v, cos, sin)
    want = block_multihead_chunk_attention(
        q, k, v, pool.key, pool.value, batch.block_tables, batch.seq_lens, batch.q_lens,
        slot_mask=batch.slot_mask, key_scale=pool.key_scale, value_scale=pool.value_scale, cos=cos, sin=sin,
    )
    assert new.batch is batch and len(new.planes) == len(pool.planes)
    for a, b in zip((out,) + new.planes, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(out)[1].any()  # the masked slot: exact zeros, nothing written
    assert np.abs(np.asarray(out)[2]).sum() > 0


def test_a_set_with_scales_dequantises_what_it_quantised():
    """Rows written through an int8 set read back as the float set's, to the
    quantisation step (absmax / 127 a row), through the same attend."""
    rng = np.random.default_rng(3)
    c, hq = 4, 4
    batch = PagedBatch(
        jnp.arange(S * MBS, dtype=jnp.int32).reshape(S, MBS), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), bool), jnp.full((S,), c, jnp.int32),
    )
    q = jnp.asarray(rng.normal(size=(S, c, hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, c, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, c, KVH, D)), jnp.float32)
    shape = (NB, KVH, BS, D)
    out_f, _ = PagedKV.zeros(shape, jnp.float32, batch).attend(q, k, v)
    out_q, new_q = PagedKV.zeros(shape, jnp.int8, batch).attend(q, k, v)
    assert new_q.key.dtype == jnp.int8 and float(new_q.key_scale.min()) < 1.0
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f), atol=0.05)
    # page 0, head 0: the first sequence's first rows, dequantised
    deq = np.asarray(new_q.key[0, 0].astype(jnp.float32) * new_q.key_scale[0, 0][:, None])
    np.testing.assert_allclose(deq, np.asarray(k[0, :BS, 0]), atol=float(np.abs(k).max()) / 127)


@KV_DTYPES
def test_fork_copies_scales_with_their_blocks(kv):
    rng = np.random.default_rng(4)
    batch = _batch(rng)
    pool = _pool(rng, kv, batch)
    src = jnp.asarray([3, 0, 7], jnp.int32)
    dst = jnp.asarray([10, NB, 11], jnp.int32)  # the middle slot does not fork
    forked = pool.fork(src, dst)
    assert forked.batch is batch
    for before, after in zip(pool.planes, forked.planes):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[10], before[3])
        np.testing.assert_array_equal(after[11], before[7])
        untouched = [i for i in range(NB) if i not in (10, 11)]
        np.testing.assert_array_equal(after[untouched], before[untouched])
    want = block_cache_cow_copy(pool.key, pool.value, src, dst, key_scale=pool.key_scale, value_scale=pool.value_scale)
    assert len(want) == len(forked.planes) == (4 if kv == "int8" else 2)
    # no slot forks: the planes come back as they went in
    idle = pool.fork(src, jnp.full((S,), NB, jnp.int32))
    for before, after in zip(pool.planes, idle.planes):
        np.testing.assert_array_equal(np.asarray(after), np.asarray(before))


# -- one batch a step ------------------------------------------------------------

def test_192_sets_share_one_batch():
    """Ouro's counts (48 layers x 4 passes) at toy width: the model is handed
    192 sets under ONE ``PagedBatch`` and hands back 192 under the same one."""
    paddle.seed(0)
    cfg = OuroConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=48,
        num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=32, total_ut_steps=4,
    )
    assert cfg.num_kv_sets == 192
    model = OuroForCausalLM(cfg)
    model.eval()
    slots, bs, mbs = 2, 4, 2
    batch = PagedBatch(
        jnp.arange(slots * mbs, dtype=jnp.int32).reshape(slots, mbs), jnp.zeros((slots,), jnp.int32),
        jnp.ones((slots,), bool), jnp.asarray([3, 1], jnp.int32),
    )
    pool = PagedKV.zeros((slots * mbs, 2, bs, 16), jnp.float32, batch)
    sets = [pool] * cfg.num_kv_sets  # the sets may share planes here: nothing is donated
    toks = Tensor(jnp.asarray([[1, 2, 3, 0], [4, 0, 0, 0]], jnp.int32))
    with paddle.no_grad():
        logits, new_sets = model(toks, past_key_values=sets, use_cache=True)
    assert list(logits.shape) == [slots, 4, cfg.vocab_size]
    assert isinstance(new_sets, list) and len(new_sets) == 192
    assert all(isinstance(kv, PagedKV) and kv.batch is batch for kv in new_sets)
    # a set's planes changed (its rows were appended); the batch did not
    assert float(jnp.abs(new_sets[0].key).sum()) > 0 and float(jnp.abs(new_sets[191].key).sum()) > 0


# -- generate_paged shares the engine's path -------------------------------------

def _llama(seed=0):
    paddle.seed(seed)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


@KV_DTYPES
def test_generate_paged_is_token_identical_to_the_engine(kv):
    """Both decode a ``PagedKV`` set a layer through the chunk path; the
    pool's dtype follows ``FLAGS_kv_cache_dtype`` in both. With a floating
    pool the streams are identical. With an int8 pool they are identical up
    to a near-tie: ``generate_paged`` prefills through the DENSE forward
    (rows attend over unquantised K and V, then the pool is written), the
    engine's prefill rows attend over what the pool stored, so the two see
    logits ~0.02 apart and a random-weight model's near-flat logits can flip
    (all 18 generated tokens agree on these prompts; with lengths 5, 8, 3
    the last two of one stream differ; floor: one stream of the three).
    (The dense ``generate`` is the independent oracle:
    tests/test_generation.py, test_engine.py, test_fused_decode_layer.py.)"""
    m = _llama(seed=6)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32) for n in (5, 12, 3)]
    prior = paddle.get_flags(["FLAGS_kv_cache_dtype"])["FLAGS_kv_cache_dtype"]
    paddle.set_flags({"FLAGS_kv_cache_dtype": kv})
    try:
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16, prefill_chunk=8)
        assert eng.kv_cache_dtype == kv and len(eng._caches[0]) == (4 if kv == "int8" else 2)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out = eng.run()
        same = total = 0
        for rid, p in zip(rids, prompts):
            alone = np.asarray(m.generate_paged(paddle.to_tensor(p[None]), max_new_tokens=6, block_size=4)._data)[0]
            served = out[rid].tokens()
            assert served.shape == alone.shape
            same, total = same + int((served[p.size:] == alone[p.size:]).sum()), total + alone.size - p.size
    finally:
        paddle.set_flags({"FLAGS_kv_cache_dtype": prior})
    assert total == 18 and (same == total if kv == "bf16" else same >= total - 6), (same, total)
    assert eng.stats["step_traces"] == 1
    step = next(iter(m._paged_step_cache))
    assert step[-1] == ("int8" if kv == "int8" else "float32")  # generate_paged's pool took the flag's dtype


# -- the jit boundary ------------------------------------------------------------

def _step_arguments(eng):
    s, c, mbs = eng.max_slots, eng.prefill_chunk, eng.max_blocks_per_seq
    args = (
        eng._param_arrays(), eng._caches, jnp.zeros((s, c), jnp.int32), jnp.zeros((s, mbs), jnp.int32),
        jnp.zeros((s,), jnp.int32), jnp.ones((s,), jnp.int32), jnp.ones((s,), bool),
        jnp.zeros((s,), jnp.int32), jnp.full((s,), eng.num_blocks, jnp.int32),
    )
    return [(tuple(a.shape), str(a.dtype)) for a in jax.tree.leaves(eng._step_fn.lower(*args).args_info)]


@KV_DTYPES
@pytest.mark.parametrize("arch", ["llama", "ouro"])
def test_lowered_step_takes_the_same_flat_arguments(arch, kv):
    """``_step_impl``'s arguments, flat: the weights in ``named_parameters``
    order, then a set at a time ``key, value[, key_scale, value_scale]``, then
    toks, tables, lens, q_lens, active, cow_src, cow_dst. The typed state is
    built inside the trace and adds none."""
    paddle.seed(0)
    if arch == "llama":
        model, sets = LlamaForCausalLM(LlamaConfig.tiny()), 2
    else:
        model, sets = OuroForCausalLM(OuroConfig.tiny()), 8
    slots, bs, nb = 2, 4, 24
    eng = ContinuousBatchingEngine(
        model, max_slots=slots, block_size=bs, prompt_bucket=8, num_blocks=nb, kv_cache_dtype=kv
    )
    cfg = model.config
    kvh, hd = cfg.num_key_value_heads, cfg.hidden_size // cfg.num_attention_heads
    mbs = eng.max_blocks_per_seq
    weights = [(tuple(p.shape), str(p._data.dtype)) for _, p in model.named_parameters()]
    pool = ((nb, kvh, bs, hd), "int8" if kv == "int8" else weights[0][1])
    one_set = [pool, pool] + ([((nb, kvh, bs), "float32")] * 2 if kv == "int8" else [])
    step = [((slots, bs), "int32"), ((slots, mbs), "int32"), ((slots,), "int32"), ((slots,), "int32"),
            ((slots,), "bool"), ((slots,), "int32"), ((slots,), "int32")]
    got = _step_arguments(eng)
    assert len(got) == len(weights) + sets * len(one_set) + 7
    assert got == weights + one_set * sets + step
    assert eng._step_impl.__name__ == "_step_impl"  # the trace scopes and benchmarks/rehearse.py read it


# -- no positional past left -----------------------------------------------------

def test_no_positional_read_of_a_past_is_left():
    root = pathlib.Path(paddle.__file__).parent
    files = sorted((root / "models").glob("*.py")) + [root / "inference" / "engine.py", root / "generation.py"]
    positional = re.compile(r"len\(past|len\(p\) in|\bc\[[4-7]\]|past_key_values?\[\d+\]\[\d+\]|\bp\[6:\]|first\[2:6\]")
    hits = [f"{f.name}:{i}: {line.strip()}" for f in files for i, line in enumerate(f.read_text().splitlines(), 1)
            if positional.search(line)]
    # the one count that stays: Ouro checks that it was given passes x layers sets
    assert [h for h in hits if "passes * n_layers" not in h and "KV sets was given" not in h] == []
