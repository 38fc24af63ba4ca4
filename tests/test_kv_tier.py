"""Hierarchical KV: the host-RAM spill tier under the prefix cache.

The acceptance surface of ``inference/kv_tier.py`` + the engine's
spill/prefetch integration:

- LRU-evicted zero-ref chain blocks spill D2H into the bounded host pool
  instead of dying; a prefix match against a spilled chain prefetches its
  blocks H2D into freshly reserved pool slots, overlapped with the mixed
  ragged step (the per-slot gate), and every full cached block before the
  first divergent block maps regardless of which tier holds it — including
  the divergent block's partial via prefetch-on-write;
- byte-exact greedy parity of a multi-turn workload with the tier on vs off,
  through ONE compiled step signature either way;
- ``kv_tier.spill`` / ``kv_tier.prefetch`` fault sites: spill failure drops
  the chain (pre-tier behavior), prefetch failure degrades to recompute —
  both zero-cost when no plan is installed;
- recovery drops the in-flight prefetch set and rebuilds from host truth
  (the tier survives the lost device pools);
- budget discipline: host bytes never exceed ``FLAGS_kv_host_tier_bytes``,
  drops cascade to unreachable descendants, pinned entries never drop.

Everything runs on CPU with the tiny Llama config, same as test_engine.py.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, HostKVTier
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults

from conftest import assert_engine_pool_exact, assert_kv_tier_exact


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, tier_bytes, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prompt_bucket", 32)
    kw.setdefault("max_model_len", 48)
    return ContinuousBatchingEngine(m, kv_host_tier_bytes=tier_bytes, **kw)


def _kv(seed, shape=(2, 2, 2, 4, 16)):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32)


class TestHostKVTierUnit:
    BLOCK_NBYTES = 2 * 2 * 2 * 4 * 16 * 4  # tiny-llama f32 block

    def _tier(self, blocks=4):
        return HostKVTier(blocks * self.BLOCK_NBYTES, self.BLOCK_NBYTES)

    def test_put_lookup_roundtrip_and_budget_gauge(self):
        tier = self._tier(2)
        kv = _kv(0)
        assert tier.put(b"root", b"d1", b"tok1", kv)
        assert tier.bytes_used == self.BLOCK_NBYTES
        node = tier.lookup_pin(b"root", b"tok1")
        assert node is not None and np.array_equal(node.kv, kv)
        assert tier.lookup_pin(b"root", b"tok2") is None
        tier.unpin([node])

    def test_lru_evicts_oldest_when_over_budget(self):
        tier = self._tier(2)
        assert tier.put(b"r", b"d1", b"t1", _kv(1))
        assert tier.put(b"r", b"d2", b"t2", _kv(2))
        assert tier.put(b"r", b"d3", b"t3", _kv(3))  # evicts t1
        assert (b"r", b"t1") not in tier
        assert (b"r", b"t2") in tier and (b"r", b"t3") in tier
        s = tier.stats_snapshot()
        assert s["host_bytes"] <= s["budget_bytes"]
        assert s["dropped_blocks"] == 1 and s["spilled_blocks"] == 3

    def test_lookup_touches_lru_order(self):
        tier = self._tier(2)
        tier.put(b"r", b"d1", b"t1", _kv(1))
        tier.put(b"r", b"d2", b"t2", _kv(2))
        node = tier.lookup_pin(b"r", b"t1")  # t1 becomes MRU
        tier.unpin([node])
        tier.put(b"r", b"d3", b"t3", _kv(3))  # evicts t2, not t1
        assert (b"r", b"t1") in tier and (b"r", b"t2") not in tier

    def test_pinned_entries_never_drop(self):
        tier = self._tier(1)
        tier.put(b"r", b"d1", b"t1", _kv(1))
        node = tier.lookup_pin(b"r", b"t1")
        # over budget but everything pinned: the new spill is refused
        assert not tier.put(b"r", b"d2", b"t2", _kv(2))
        assert tier.stats_snapshot()["refused_spills"] == 1
        tier.unpin([node])
        assert tier.put(b"r", b"d2", b"t2", _kv(2))  # now t1 can go

    def test_dropping_a_parent_cascades_unreachable_descendants(self):
        tier = self._tier(8)
        tier.put(b"root", b"dA", b"tA", _kv(1))
        tier.put(b"dA", b"dB", b"tB", _kv(2))
        tier.put(b"dB", b"dC", b"tC", _kv(3))
        # make the PARENT the LRU head (children spilled later are newer
        # anyway), then force one drop: the whole subtree must leave — a
        # child whose parent digest left the tier is unreachable by any walk
        assert tier.drop_lru(1) == 3
        assert len(tier) == 0
        assert tier.stats_snapshot()["dropped_blocks"] == 3

    def test_put_same_key_is_idempotent_touch(self):
        tier = self._tier(2)
        kv = _kv(1)
        assert tier.put(b"r", b"d1", b"t1", kv)
        assert tier.put(b"r", b"d1", b"t1", _kv(9))  # same digest == same bytes
        node = tier.lookup_pin(b"r", b"t1")
        assert np.array_equal(node.kv, kv)  # first copy retained
        assert len(tier) == 1
        tier.unpin([node])

    def test_best_partial_prefers_longest_common_run(self):
        tier = self._tier(4)
        t_a = np.asarray([1, 2, 3, 4], np.int32)
        t_b = np.asarray([1, 2, 9, 9], np.int32)
        tier.put(b"r", b"dA", t_a.tobytes(), _kv(1))
        tier.put(b"r", b"dB", t_b.tobytes(), _kv(2))
        got = tier.best_partial(b"r", np.asarray([1, 2, 3, 9], np.int32))
        assert got is not None
        node, k = got
        assert node.token_bytes == t_a.tobytes() and k == 3
        tier.unpin([node])
        assert tier.best_partial(b"r", np.asarray([7, 7], np.int32)) is None


class TestSpillPrefetchCycle:
    def test_evicted_chain_spills_and_a_later_match_prefetches(self):
        m, cfg = _model(seed=60)
        rng = np.random.default_rng(60)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        r1 = eng.add_request(x, max_new_tokens=2)
        out_cold = eng.run()
        eng._cache.evict_blocks(16)  # whole dead chain -> host tier
        assert eng._cache.node_count == 0
        assert eng.kv_tier_stats()["spilled_blocks"] >= 3
        r2 = eng.add_request(x, max_new_tokens=2)
        out_warm = eng.run()
        # 16-token prompt: 3 full blocks prefetched (12) + 3-token partial
        # of the spilled block 3 via prefetch-on-write
        assert out_warm[r2].cached_tokens == 15
        assert eng.kv_tier_stats()["prefetched_blocks"] == 4
        np.testing.assert_array_equal(
            out_cold[r1].tokens(), out_warm[r2].tokens()
        )
        assert_engine_pool_exact(eng)
        assert_kv_tier_exact(eng)

    def test_multi_turn_workload_byte_identical_tier_on_vs_off(self):
        """The acceptance parity run: interleaved multi-turn conversations
        over a pool too small to retain the working set — tier-on must
        spill, prefetch, AND emit byte-identical greedy tokens, through ONE
        compiled signature, same as tier-off."""
        m, cfg = _model(seed=61)

        def drive(tier_bytes):
            rng = np.random.default_rng(61)
            eng = _engine(m, tier_bytes, num_blocks=12, max_model_len=64,
                          prompt_bucket=48)
            streams = {}
            outs = []
            for op in range(10):
                conv = int(rng.integers(0, 3))
                tail = rng.integers(0, cfg.vocab_size,
                                    (int(rng.integers(3, 8)),)).astype(np.int32)
                prev = streams.get(conv)
                prompt = tail if prev is None else np.concatenate([prev, tail])
                if prompt.size > 40:
                    prompt = tail
                rid = eng.add_request(prompt, max_new_tokens=3)
                done = eng.run()
                streams[conv] = done[rid].tokens()
                outs.append(streams[conv])
                assert_engine_pool_exact(eng)
                assert_kv_tier_exact(eng)
            # final round: force every resident chain out (spilling when the
            # tier is on), then each conversation takes one more turn — with
            # the tier on, its history comes back by prefetch; off, by
            # recompute. Same tokens either way.
            eng._cache.evict_blocks(64)
            for conv in sorted(streams):
                tail = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
                prompt = np.concatenate([streams[conv], tail])[-40:]
                rid = eng.add_request(prompt, max_new_tokens=3)
                done = eng.run()
                outs.append(done[rid].tokens())
                assert_engine_pool_exact(eng)
                assert_kv_tier_exact(eng)
            return eng, outs

        eng_on, outs_on = drive(1 << 20)
        eng_off, outs_off = drive(0)
        assert len(outs_on) == len(outs_off)
        for a, b in zip(outs_on, outs_off):
            np.testing.assert_array_equal(a, b)
        t = eng_on.kv_tier_stats()
        assert t["spilled_blocks"] > 0 and t["prefetched_blocks"] > 0
        assert eng_off.kv_tier_stats() == {"enabled": False}
        # ONE compiled step signature with the tier on or off
        assert eng_on.stats["step_traces"] == 1
        assert eng_off.stats["step_traces"] == 1

    def test_prefetch_gate_blocks_slot_until_copies_land(self):
        """A slot admitted against a spilled chain is gated: its rows stay
        out of the mixed step while the H2D copies are in flight, and the
        gate clears (poll or forced wait) before its suffix computes."""
        m, cfg = _model(seed=62)
        rng = np.random.default_rng(62)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng.add_request(x, max_new_tokens=2)
        eng.run()
        eng._cache.evict_blocks(16)
        req = eng.make_request(x, max_new_tokens=2)
        eng.enqueue(req)
        eng._admit_waiting([])  # prefetch issued here
        slot = next(i for i, r in enumerate(eng._slot_req) if r is req)
        assert eng._prefetch_wait[slot] is not None  # gate armed at admit
        marker, n_blocks, tokens = eng._prefetch_wait[slot]
        assert n_blocks == 4 and tokens == 15
        out = eng.run()  # polls/waits the gate, then computes the suffix
        assert eng._prefetch_wait[slot] is None
        assert out[req.req_id].finished
        assert_engine_pool_exact(eng)

    def test_tier_under_tensor_parallel_mesh_byte_identical(self):
        """The tier under a CPU tp=2 mesh: spill gathers the head shards
        D2H (the tier always holds the full-head view), the prefetch fold's
        ``out_shardings`` pin keeps the committed pool partition (a drifted
        sharding would compile a SECOND step executable), and tokens stay
        byte-identical to the tp=1 engine."""
        m, cfg = _model(seed=72)

        def drive(tp):
            rng = np.random.default_rng(72)
            eng = _engine(m, 1 << 20, num_blocks=64, tp=tp)
            x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
            r1 = eng.add_request(x, max_new_tokens=3)
            o1 = eng.run()
            eng._cache.evict_blocks(16)
            r2 = eng.add_request(x, max_new_tokens=3)
            o2 = eng.run()
            return eng, o1[r1].tokens(), o2[r2].tokens(), o2[r2].cached_tokens

        eng2, cold2, warm2, cached2 = drive(2)
        eng1, cold1, warm1, cached1 = drive(1)
        assert cached2 == cached1 == 15
        assert eng2.kv_tier_stats()["prefetched_blocks"] == 4
        np.testing.assert_array_equal(cold1, cold2)
        np.testing.assert_array_equal(warm1, warm2)
        np.testing.assert_array_equal(cold2, warm2)
        assert eng2.stats["step_traces"] == 1  # out_shardings held the line
        assert_engine_pool_exact(eng2)
        assert_kv_tier_exact(eng2)

    def test_tier_requires_prefix_cache(self):
        m, _cfg = _model(seed=63)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16,
            enable_prefix_cache=False, kv_host_tier_bytes=1 << 20,
        )
        assert eng.kv_tier_stats() == {"enabled": False}

    def test_host_budget_pressure_drops_lru_and_stays_within_budget(self):
        m, cfg = _model(seed=64)
        rng = np.random.default_rng(64)
        # budget of exactly 2 blocks: heavy eviction churn must drop
        bpb = 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * \
            (cfg.hidden_size // cfg.num_attention_heads) * 4 * 4  # f32, bs=4
        eng = _engine(m, 2 * bpb, num_blocks=10, max_model_len=32,
                      prompt_bucket=16)
        for _ in range(6):
            p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
            eng.add_request(p, max_new_tokens=2)
            eng.run()
            assert_kv_tier_exact(eng)
        t = eng.kv_tier_stats()
        assert t["host_bytes"] <= t["budget_bytes"] == 2 * bpb
        assert t["dropped_blocks"] > 0


class TestFaultSites:
    def test_sites_are_pinned_in_known_sites(self):
        assert "kv_tier.spill" in faults.KNOWN_SITES
        assert "kv_tier.prefetch" in faults.KNOWN_SITES

    def test_spill_fault_drops_the_chain_old_behavior(self):
        m, cfg = _model(seed=65)
        rng = np.random.default_rng(65)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        r1 = eng.add_request(x, max_new_tokens=2)
        out1 = eng.run()
        with faults.inject(faults.FaultPlan.single("kv_tier.spill", 0)):
            eng._cache.evict_blocks(1)
        assert len(eng._host_tier) == 0  # nothing half-stored
        eng._cache.evict_blocks(16)  # later spills work again
        assert len(eng._host_tier) > 0
        # the dropped block is recomputed, byte-identically
        r2 = eng.add_request(x, max_new_tokens=2)
        out2 = eng.run()
        np.testing.assert_array_equal(out1[r1].tokens(), out2[r2].tokens())
        assert_engine_pool_exact(eng)
        assert_kv_tier_exact(eng)

    def test_prefetch_fault_degrades_request_to_recompute(self):
        m, cfg = _model(seed=66)
        rng = np.random.default_rng(66)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        r1 = eng.add_request(x, max_new_tokens=3)
        out1 = eng.run()
        eng._cache.evict_blocks(16)
        with faults.inject(faults.FaultPlan.single("kv_tier.prefetch", 0)):
            r2 = eng.add_request(x, max_new_tokens=3)
            out2 = eng.run()
        assert out2[r2].cached_tokens == 0  # host match abandoned, recompute
        assert eng.kv_tier_stats()["prefetched_blocks"] == 0
        np.testing.assert_array_equal(out1[r1].tokens(), out2[r2].tokens())
        # the spilled chain is still intact for the NEXT match
        r3 = eng.add_request(x, max_new_tokens=3)
        out3 = eng.run()
        assert out3[r3].cached_tokens > 0
        np.testing.assert_array_equal(out1[r1].tokens(), out3[r3].tokens())
        assert_engine_pool_exact(eng)
        assert_kv_tier_exact(eng)

    def test_sites_are_zero_cost_when_no_plan_installed(self):
        m, cfg = _model(seed=67)
        rng = np.random.default_rng(67)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng.add_request(x, max_new_tokens=2)
        eng.run()
        eng._cache.evict_blocks(16)
        eng.add_request(x, max_new_tokens=2)
        eng.run()
        assert eng.kv_tier_stats()["spilled_blocks"] > 0
        assert eng.kv_tier_stats()["prefetched_blocks"] > 0
        # with no plan, the sites do not even count their calls
        assert faults.site_call_count("kv_tier.spill") == 0
        assert faults.site_call_count("kv_tier.prefetch") == 0


class TestRecovery:
    def test_recovery_drops_in_flight_set_and_rebuilds_from_host_truth(self):
        """A dispatch fault mid-workload: recovery rebuilds device pools,
        the host tier SURVIVES (its spilled counter does not reset), the
        in-flight prefetch gates are dropped, and the replayed stream is
        byte-identical to a fault-free run."""
        m, cfg = _model(seed=68)

        def drive(plan):
            rng = np.random.default_rng(68)
            eng = _engine(m, 1 << 20, num_blocks=64)
            x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
            eng.add_request(x, max_new_tokens=2)
            eng.run()
            eng._cache.evict_blocks(16)
            spilled = eng.kv_tier_stats()["spilled_blocks"]
            assert spilled > 0
            rid = eng.add_request(x, max_new_tokens=6)
            done = {}
            if plan is not None:
                with faults.inject(plan):
                    while eng.has_work():
                        for q in eng.step():
                            done[q.req_id] = q
            else:
                while eng.has_work():
                    for q in eng.step():
                        done[q.req_id] = q
            return eng, done[rid], spilled

        eng_f, req_f, spilled = drive(
            faults.FaultPlan.single("engine.decode", 1)
        )
        assert eng_f.stats["recoveries"] == 1
        assert all(w is None for w in eng_f._prefetch_wait)
        assert eng_f.kv_tier_stats()["spilled_blocks"] >= spilled
        eng_c, req_c, _ = drive(None)
        np.testing.assert_array_equal(req_f.tokens(), req_c.tokens())
        assert_engine_pool_exact(eng_f)
        assert_kv_tier_exact(eng_f)


class TestObservability:
    def test_tier_metrics_and_labeled_hit_split(self):
        m, cfg = _model(seed=69)
        rng = np.random.default_rng(69)
        prior = paddle.get_flags(["FLAGS_enable_metrics"])
        try:
            paddle.set_flags({"FLAGS_enable_metrics": True})
            obs.GLOBAL_METRICS.reset()
            eng = _engine(m, 1 << 20, num_blocks=64)
            x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
            eng.add_request(x, max_new_tokens=2)
            eng.run()  # cold: miss
            eng.add_request(x, max_new_tokens=2)
            eng.run()  # resident hit -> tier="hbm"
            eng._cache.evict_blocks(32)
            eng.add_request(x, max_new_tokens=2)
            eng.run()  # spilled hit -> tier="host"
            reg = obs.GLOBAL_METRICS
            hits = reg.get("prefix_cache_hits_total")
            assert hits.value(tier="hbm") == 1.0
            assert hits.value(tier="host") == 1.0
            assert reg.get("kv_tier_spilled_blocks_total").value() > 0
            assert reg.get("kv_tier_prefetched_blocks_total").value() == 4.0
            assert (
                reg.get("kv_tier_host_bytes").value()
                == eng.kv_tier_stats()["host_bytes"]
            )
            stats = eng._cache.stats_snapshot()
            assert stats["host_hits"] == 1 and stats["hits"] == 2
        finally:
            paddle.set_flags(prior)
            obs.GLOBAL_METRICS.reset()

    def test_flight_events_for_spill_and_prefetch(self):
        from paddle_tpu.observability import flight_recorder as flight

        m, cfg = _model(seed=70)
        rng = np.random.default_rng(70)
        eng = _engine(m, 1 << 20, num_blocks=64)
        x = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng.add_request(x, max_new_tokens=2)
        eng.run()
        eng._cache.evict_blocks(16)
        eng.add_request(x, max_new_tokens=2)
        eng.run()
        kinds = [e["kind"] for e in flight.GLOBAL_FLIGHT_RECORDER.snapshot()]
        assert "kv_spill" in kinds and "kv_prefetch" in kinds

    def test_healthz_kv_tier_block(self):
        from paddle_tpu.serving import ServingConfig, ServingFrontend

        m, _cfg = _model(seed=71)
        eng = _engine(m, 1 << 20, num_blocks=64)
        fe = ServingFrontend(eng, ServingConfig(max_queue=4))
        snap = fe.snapshot()
        assert snap["kv_tier"]["enabled"] is True
        assert snap["kv_tier"]["budget_bytes"] == 1 << 20
        for k in ("host_bytes", "spilled_blocks", "prefetched_blocks",
                  "dropped_blocks"):
            assert k in snap["kv_tier"]
