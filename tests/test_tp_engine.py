"""Tensor-parallel serving: the sharded-engine invariants on a CPU ``tp=2``
mesh (the 8-device virtual CPU split from conftest).

The contract under test (``distributed/tp.py`` + engine ``tp=``):

- ``tp=2`` greedy outputs are BYTE-IDENTICAL to ``tp=1`` across a mixed
  staggered workload — with the prefix cache and speculative decoding riding
  along unchanged (host-side state is replicated-by-construction);
- exactly ONE compile per engine under the mesh (sharding lives in input
  placements, never in shapes);
- the KV pool partition is balanced per shard — every device holds the same
  logical blocks over an equal head slice — and the host-side refcount /
  accounting churn property holds at every step boundary;
- recovery under the mesh reallocates SHARDED pools and replays to identical
  streams through the same compiled program.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="tp tests need >= 2 devices"
)


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _mixed_workload(cfg, seed=0):
    rng = np.random.default_rng(seed)
    specs = [(5, 6), (7, 4), (3, 9), (6, 2), (2, 7), (8, 5), (4, 3)]
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n, _ in specs
    ]
    return prompts, [t for _, t in specs]


def _run_engine(prompts, budgets, seed=0, **kw):
    m, _ = _model(seed)
    eng = ContinuousBatchingEngine(
        m, max_slots=3, block_size=4, prompt_bucket=16, **kw
    )
    rids = [
        eng.add_request(p, max_new_tokens=t) for p, t in zip(prompts, budgets)
    ]
    out = eng.run()
    return eng, [out[r].tokens() for r in rids]


# the shared engine-wide accounting invariant: one HOST-side allocator
# steers every shard, so this holding under the mesh is exactly the 'host
# state replicated-by-construction' claim
from conftest import assert_engine_pool_exact as _assert_pool_exact


def _assert_shards_balanced(eng, tp):
    """Device truth of the pool partition: every mesh device holds one equal
    head slice of every layer's caches — same logical blocks, same block
    size, KVH/tp heads."""
    nb, kvh, bs, hd = eng._cache_shape
    for kc, vc in eng._caches:
        for arr in (kc, vc):
            shards = {s.device.id: s.data.shape for s in arr.addressable_shards}
            assert len(shards) == tp, shards
            for shape in shards.values():
                assert tuple(shape) == (nb, kvh // tp, bs, hd), shards
    st = eng.tp_stats()
    assert st["tp_degree"] == tp and st["balanced"], st
    assert st["per_shard_cache_shape"] == [nb, kvh // tp, bs, hd], st


class TestTpValidation:
    def test_tp_must_divide_kv_heads(self):
        m, _ = _model()
        with pytest.raises(ValueError, match="KV heads"):
            # tiny config has 2 KV heads; 3 cannot shard them
            ContinuousBatchingEngine(m, max_slots=2, block_size=4, tp=3)

    def test_tp_below_one_rejected(self):
        # 0/negative must not silently take the single-chip path: tp_degree
        # feeds capacity weighting in health snapshots and bench records
        m, _ = _model()
        with pytest.raises(ValueError, match=">= 1"):
            ContinuousBatchingEngine(m, max_slots=2, block_size=4, tp=0)

    def test_tp_needs_devices(self):
        from paddle_tpu.distributed.tp import build_tp_mesh

        with pytest.raises(ValueError, match="devices"):
            build_tp_mesh(len(jax.devices()) + 2)

    def test_tp1_is_the_unsharded_engine(self):
        m, _ = _model()
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4)
        assert eng.tp_degree == 1
        assert eng._tp_mesh is None
        assert eng.tp_stats() == {"tp_degree": 1}

    def test_flag_default_reaches_engine(self):
        flags = paddle.get_flags(["FLAGS_engine_tp_degree"])
        assert flags["FLAGS_engine_tp_degree"] == 1


class TestTpByteIdentical:
    def test_mixed_workload_byte_identical_one_compile(self):
        """The acceptance test: staggered admits through 3 slots, varied
        prompt lengths and budgets — tp=2 tokens byte-equal tp=1, each
        engine compiling its step exactly once."""
        _, cfg = _model()
        prompts, budgets = _mixed_workload(cfg)
        e1, toks1 = _run_engine(prompts, budgets)
        e2, toks2 = _run_engine(prompts, budgets, tp=2)
        assert e1.stats["step_traces"] == 1, e1.stats
        assert e2.stats["step_traces"] == 1, e2.stats
        if hasattr(e2._step_fn, "_cache_size"):
            assert e2._step_fn._cache_size() == 1
        for a, b in zip(toks1, toks2):
            np.testing.assert_array_equal(a, b)
        _assert_shards_balanced(e2, 2)

    def test_spec_decode_rides_the_sharded_step(self):
        """Speculation is host-side draft + in-dispatch verification — pure
        data to the sharded program: byte-identical on the mesh, still one
        compile, same acceptance bookkeeping."""
        _, cfg = _model()
        prompts, budgets = _mixed_workload(cfg, seed=5)
        e1, toks1 = _run_engine(prompts, budgets, spec_decode=True)
        e2, toks2 = _run_engine(prompts, budgets, tp=2, spec_decode=True)
        for a, b in zip(toks1, toks2):
            np.testing.assert_array_equal(a, b)
        assert e2.stats["step_traces"] == 1
        assert e1.spec_decode_stats() == e2.spec_decode_stats()

    def test_prefix_cache_shared_by_all_shards(self):
        """One logical block id maps the shared prefix in EVERY shard's pool
        partition, so the prefix cache needs no per-shard state: warm hits
        on the mesh, byte-identical to tp=1."""
        _, cfg = _model()
        rng = np.random.default_rng(7)
        shared = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        tails = [
            rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
            for _ in range(3)
        ]
        prompts = [np.concatenate([shared, t]) for t in tails]

        def run_warm(tp):
            # cold request first so the shared prefix is REGISTERED before
            # the warm pair matches it (same-boundary admits are all cold)
            m, _ = _model()
            eng = ContinuousBatchingEngine(
                m, max_slots=3, block_size=4, prompt_bucket=16, tp=tp
            )
            r0 = eng.add_request(prompts[0], max_new_tokens=5)
            out = dict(eng.run())
            r1 = eng.add_request(prompts[1], max_new_tokens=5)
            r2 = eng.add_request(prompts[2], max_new_tokens=5)
            out.update(eng.run())
            return eng, [out[r].tokens() for r in (r0, r1, r2)]

        e1, toks1 = run_warm(1)
        e2, toks2 = run_warm(2)
        for a, b in zip(toks1, toks2):
            np.testing.assert_array_equal(a, b)
        stats = e2.prefix_cache_stats()
        assert stats["enabled"] and stats["hits"] > 0, stats
        assert e2.stats["prompt_tokens_reused"] > 0
        assert e2.stats["step_traces"] == 1


class TestTpShardAccounting:
    def test_churn_property_per_step(self):
        """Step the sharded engine manually through a staggered workload:
        after EVERY boundary the host accounting is exact AND the device
        shards stay balanced (the pool partition never skews)."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(3)
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, prompt_bucket=16, tp=2
        )
        pending = [
            (rng.integers(0, cfg.vocab_size, (int(rng.integers(2, 9)),)).astype(np.int32),
             int(rng.integers(2, 7)))
            for _ in range(6)
        ]
        for p, t in pending[:3]:
            eng.add_request(p, max_new_tokens=t)
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
            if steps == 2:
                for p, t in pending[3:]:
                    eng.add_request(p, max_new_tokens=t)
            _assert_pool_exact(eng)
            _assert_shards_balanced(eng, 2)
            assert steps < 200
        assert eng.stats["step_traces"] == 1


class TestTpRecovery:
    def test_recovery_reallocates_sharded_pools_and_replays(self):
        """An injected dispatch loss mid-workload: recover() rebuilds the
        pools COMMITTED on the same mesh partition, replays from host truth,
        and the streams come out byte-identical to the unfaulted sharded run
        — with zero extra compiles."""
        _, cfg = _model()
        prompts, budgets = _mixed_workload(cfg, seed=11)
        e_ok, toks_ok = _run_engine(prompts, budgets, seed=2, tp=2)
        m, _ = _model(seed=2)
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, prompt_bucket=16, tp=2
        )
        rids = [
            eng.add_request(p, max_new_tokens=t)
            for p, t in zip(prompts, budgets)
        ]
        with faults.inject(faults.FaultPlan.parse("engine.decode:3:InjectedFault")):
            out = eng.run()
        assert eng.stats["recoveries"] == 1
        assert eng.stats["step_traces"] == 1, eng.stats
        for rid, ref in zip(rids, toks_ok):
            np.testing.assert_array_equal(out[rid].tokens(), ref)
        _assert_shards_balanced(eng, 2)
        _assert_pool_exact(eng)


class TestTpServingHealth:
    def test_health_unit_is_the_shard_group(self):
        """The replica's health unit becomes the shard group: tp_degree in
        the router-facing health snapshot, the /healthz payload, and on the
        Replica itself."""
        from paddle_tpu.serving import ServingConfig, ServingFrontend
        from paddle_tpu.serving.cluster import Replica

        m, _ = _model()
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16, tp=2
        )
        fe = ServingFrontend(eng, ServingConfig(max_queue=4))
        health = fe.health_snapshot()
        assert health["tp_degree"] == 2
        snap = fe.snapshot()
        assert snap["tensor_parallel"]["tp_degree"] == 2
        assert snap["tensor_parallel"]["balanced"]
        assert Replica("r0", fe).tp_degree == 2

    def test_tp_stats_survives_lost_buffers(self):
        """On a donating backend a failed dispatch consumes the pools; the
        /healthz path must report the lost buffers, never raise (probing a
        broken replica is exactly when observability matters)."""
        m, _ = _model()
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16, tp=2
        )
        for kc, vc in eng._caches:
            kc.delete()
            vc.delete()
        st = eng.tp_stats()
        assert st["buffers"] == "lost" and st["tp_degree"] == 2, st
        assert st["balanced"] is None


class TestTpShardMapWrapper:
    @pytest.mark.parametrize("kv", ["float", "int8"])
    @pytest.mark.parametrize("rope", [False, True], ids=["roped_q", "rope_in_walk"])
    def test_sharded_kernel_matches_gather_reference(self, rope, kv):
        """The ONE shard_map wrapping of the Pallas mixed ragged kernel (the
        TPU path), pinned off-TPU via interpret mode: per-shard head slices
        over per-shard pool partitions (scale planes on the same head axis,
        rope rows replicated) reassemble to the XLA gather reference."""
        import jax.numpy as jnp

        from paddle_tpu.distributed.tp import build_tp_mesh
        from paddle_tpu.incubate.nn.functional import _rope_apply_xla
        from paddle_tpu.incubate.nn.functional.block_attention import (
            _gather_chunk_attend,
            _tp_sharded_flash_chunk,
        )

        rng = np.random.default_rng(13)
        B, C, HQ, HKV, D, NB, BS, MBS = 3, 4, 4, 2, 16, 24, 4, 8
        q = jnp.asarray(rng.normal(size=(B, C, HQ, D)).astype(np.float32))
        if kv == "int8":
            kc = jnp.asarray(rng.integers(-127, 128, (NB, HKV, BS, D)), jnp.int8)
            vc = jnp.asarray(rng.integers(-127, 128, (NB, HKV, BS, D)), jnp.int8)
            scales = dict(
                k_scale=jnp.asarray(rng.uniform(0.005, 0.02, (NB, HKV, BS)), jnp.float32),
                v_scale=jnp.asarray(rng.uniform(0.005, 0.02, (NB, HKV, BS)), jnp.float32),
            )
        else:
            kc = jnp.asarray(rng.normal(size=(NB, HKV, BS, D)).astype(np.float32))
            vc = jnp.asarray(rng.normal(size=(NB, HKV, BS, D)).astype(np.float32))
            scales = {}
        tables = jnp.asarray(
            rng.permutation(NB)[: B * MBS].reshape(B, MBS).astype(np.int32)
        )
        lens = jnp.asarray(np.array([5, 0, 9], np.int32))
        qlens = jnp.asarray(np.array([1, 0, 4], np.int32))  # decode + idle + chunk
        ang = rng.uniform(0, 6.28, size=(B, C, D // 2))
        cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1), jnp.float32)
        sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1), jnp.float32)
        mesh = build_tp_mesh(2)
        q_ref = q
        if rope:
            scales_and_rope = dict(scales, cos=cos, sin=sin)
            q_ref = _rope_apply_xla(q, sin[:, :, None], cos[:, :, None], True)
        else:
            scales_and_rope = scales
        out_tp = _tp_sharded_flash_chunk(
            q, kc, vc, tables, lens, qlens, 0.25, mesh, interpret=True, **scales_and_rope
        )
        out_ref = _gather_chunk_attend(q_ref, kc, vc, tables, lens, qlens, 0.25, **scales)
        np.testing.assert_allclose(
            np.asarray(out_tp), np.asarray(out_ref), rtol=2e-5, atol=2e-5
        )
        # rows past q_lens are exact zeros on both paths
        assert not np.any(np.asarray(out_tp)[1])
