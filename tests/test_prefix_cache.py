"""Prefix-cache KV subsystem: content-hash block dedup with copy-on-write.

The acceptance surface of ``inference/prefix_cache.py``:

- the 200-op seeded churn property test — after EVERY admit/decode/finish/
  evict op, every refcounted block's owner count equals its live mappings
  (slot tables + pending CoW pins) plus cache chain ownership,
  ``allocated + free == total``, and no live request's table references a
  freed block;
- byte-exact token parity between cached-hit and cold-path decoding of the
  same prompt (and against a cache-disabled engine);
- copy-on-write on the first divergent block;
- LRU eviction over zero-ref chains only, under real pool pressure;
- the ``prefix_cache.match`` / ``prefix_cache.cow`` fault sites degrading to
  recompute, never to a failed request.

Everything runs on CPU with the tiny Llama config, same as test_engine.py.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.functional import BlockKVCache
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


# the churn contract: refcount truth, exact accounting, no dangling table
# entries, node/table alignment — the shared engine invariant
from conftest import assert_engine_pool_exact as _assert_invariants


class TestChurnProperty:
    def test_200_op_seeded_churn_holds_invariants_after_every_op(self):
        """Seeded admit/decode/finish/evict churn with heavy prefix sharing
        (three prompt families over a small pool) — the invariants hold
        after EVERY operation, and every request completes exactly once."""
        m, cfg = _model(seed=40)
        rng = np.random.default_rng(40)
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, num_blocks=24, prompt_bucket=16,
            max_model_len=32,
        )
        families = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (9, 6, 12)
        ]

        def make_prompt():
            fam = families[int(rng.integers(0, len(families)))]
            tail_n = int(rng.integers(0, 4))
            tail = rng.integers(0, cfg.vocab_size, (tail_n,)).astype(np.int32)
            return np.concatenate([fam, tail])[:16]

        submitted = {}
        done = {}
        cancelled = 0
        for _op in range(200):
            r = rng.random()
            if r < 0.40 and len(eng._waiting) < 6:
                rid = eng.add_request(
                    make_prompt(), max_new_tokens=int(rng.integers(1, 6))
                )
                submitted[rid] = True
            elif r < 0.85:
                if eng.has_work():
                    for req in eng.step():
                        assert req.req_id not in done, "delivered twice"
                        done[req.req_id] = req
            elif r < 0.93:
                live = [q.req_id for q in eng.live_requests()] + [
                    q.req_id for q in eng._waiting
                ]
                if live:
                    rid = int(rng.choice(live))
                    req = eng.cancel_request(rid)
                    assert req is not None and req.finished
                    done[rid] = req
                    cancelled += 1
            else:
                if eng._cache is not None:
                    eng._cache.evict_blocks(1)  # external pressure
            _assert_invariants(eng)
        while eng.has_work():
            for req in eng.step():
                assert req.req_id not in done
                done[req.req_id] = req
            _assert_invariants(eng)
        assert set(done) == set(submitted)  # exactly once, nobody lost
        assert cancelled > 0  # the churn actually exercised targeted evict
        s = eng.pool_stats()
        assert s["free"] + s["cached_blocks"] == s["total"]

    def test_200_op_churn_with_host_tier_spill_prefetch_drop(self):
        """The churn property test extended with the hierarchical-KV ops:
        submit (with multi-turn re-submissions that land on spilled chains),
        step, cancel, device-evict (which now SPILLS), and host-tier drop.
        After EVERY op: pool refcounts exact (the shared engine invariant),
        host-tier bytes <= budget, and no block live in both tiers under the
        same digest with mismatched contents."""
        from conftest import assert_kv_tier_exact

        m, cfg = _model(seed=52)
        rng = np.random.default_rng(52)
        bpb = 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * \
            (cfg.hidden_size // cfg.num_attention_heads) * 4 * 4  # f32, bs=4
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, num_blocks=20, prompt_bucket=24,
            max_model_len=40, kv_host_tier_bytes=6 * bpb,
        )
        families = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (9, 12)
        ]
        finished_streams = []

        def make_prompt():
            # half the prompts replay a finished request's stream (the
            # multi-turn shape that matches spilled generated-token chains)
            if finished_streams and rng.random() < 0.5:
                base = finished_streams[int(rng.integers(0, len(finished_streams)))]
            else:
                base = families[int(rng.integers(0, len(families)))]
            tail_n = int(rng.integers(0, 4))
            tail = rng.integers(0, cfg.vocab_size, (tail_n,)).astype(np.int32)
            return np.concatenate([base, tail])[:20]

        submitted, done = {}, {}
        for _op in range(200):
            r = rng.random()
            if r < 0.35 and len(eng._waiting) < 6:
                rid = eng.add_request(
                    make_prompt(), max_new_tokens=int(rng.integers(1, 6))
                )
                submitted[rid] = True
            elif r < 0.80:
                if eng.has_work():
                    for req in eng.step():
                        assert req.req_id not in done, "delivered twice"
                        done[req.req_id] = req
                        if len(finished_streams) < 6:
                            finished_streams.append(req.tokens())
            elif r < 0.88:
                live = [q.req_id for q in eng.live_requests()] + [
                    q.req_id for q in eng._waiting
                ]
                if live:
                    rid = int(rng.choice(live))
                    req = eng.cancel_request(rid)
                    assert req is not None and req.finished
                    done[rid] = req
            elif r < 0.96:
                eng._cache.evict_blocks(1)  # device pressure -> SPILL
            else:
                eng._host_tier.drop_lru(1)  # host pressure -> DROP
            _assert_invariants(eng)
            assert_kv_tier_exact(eng)
        while eng.has_work():
            for req in eng.step():
                assert req.req_id not in done
                done[req.req_id] = req
            _assert_invariants(eng)
            assert_kv_tier_exact(eng)
        assert set(done) == set(submitted)  # exactly once, nobody lost
        t = eng.kv_tier_stats()
        assert t["spilled_blocks"] > 0, t  # the churn actually spilled
        assert t["prefetched_blocks"] > 0, t  # ... and prefetched
        assert t["dropped_blocks"] > 0, t  # ... and dropped

    def test_churn_with_cache_disabled_matches_invariants_too(self):
        """The same machinery with FLAGS_enable_prefix_cache off: pure
        refcounted private blocks, zero cache state."""
        m, cfg = _model(seed=41)
        rng = np.random.default_rng(41)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=12, prompt_bucket=8,
            max_model_len=16, enable_prefix_cache=False,
        )
        assert eng.prefix_cache_stats() == {"enabled": False}
        for _ in range(4):
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (int(rng.integers(2, 8)),))
                .astype(np.int32),
                max_new_tokens=int(rng.integers(1, 5)),
            )
        while eng.has_work():
            eng.step()
            _assert_invariants(eng)
        assert eng.pool_stats()["free"] == eng.num_blocks  # nothing retained
        assert eng.pool_stats()["cached_blocks"] == 0


class TestHitParity:
    def test_cached_hit_decode_is_byte_identical_to_cold(self):
        """The same prompt served cold, then from the cache (full-block hits
        + CoW partial), then by a cache-disabled engine — every path emits
        byte-identical tokens."""
        m, cfg = _model(seed=42)
        rng = np.random.default_rng(42)
        prompt = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)

        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        r_cold = eng.add_request(prompt, max_new_tokens=6)
        out_cold = eng.run()
        assert out_cold[r_cold].cached_tokens == 0
        stats = eng.prefix_cache_stats()
        assert stats["misses"] >= 1 and stats["nodes"] >= 3

        r_warm = eng.add_request(prompt, max_new_tokens=6)
        out_warm = eng.run()
        # 12-token prompt over 4-token blocks: blocks 0/1 full-match (the
        # cap holds back the 12th token, so block 2 cannot full-match); the
        # 3-token remainder rides a CoW fork of cached block 2
        assert out_warm[r_warm].cached_tokens == 11
        assert eng.prefix_cache_stats()["cow_forks"] == 1
        np.testing.assert_array_equal(
            out_cold[r_cold].tokens(), out_warm[r_warm].tokens()
        )

        eng_off = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16,
            enable_prefix_cache=False,
        )
        r_off = eng_off.add_request(prompt, max_new_tokens=6)
        out_off = eng_off.run()
        np.testing.assert_array_equal(
            out_cold[r_cold].tokens(), out_off[r_off].tokens()
        )

    def test_shared_prefix_computed_once_across_requests(self):
        """N staggered requests sharing a system prompt: the shared full
        blocks are computed exactly once; warm admissions compute only their
        tails (the computed-exactly-once counter)."""
        m, cfg = _model(seed=43)
        rng = np.random.default_rng(43)
        shared = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)

        def submit():
            tail = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
            return eng.add_request(
                np.concatenate([shared, tail]), max_new_tokens=3
            )

        submit()
        eng.run()
        cold_computed = eng.stats["prompt_tokens_computed"]
        assert cold_computed == 11  # the whole first prompt

        before = eng.stats["prompt_tokens_computed"]
        rids = [submit() for _ in range(3)]
        out = eng.run()
        warm_computed = eng.stats["prompt_tokens_computed"] - before
        # each warm request computes only its 3-token tail (the 8 shared
        # tokens = 2 full blocks are mapped, never recomputed)
        assert warm_computed == 3 * 3
        assert all(out[r].cached_tokens == 8 for r in rids)
        assert eng.stats["prompt_tokens_reused"] == 3 * 8
        assert eng.prefix_cache_stats()["hit_rate"] == pytest.approx(3 / 4)

    def test_in_flight_insertion_shares_with_staggered_admissions(self):
        """A request admitted while the first is still mid-flight (but past
        the shared blocks) hits the in-flight-inserted chain nodes — sharing
        does not wait for the first request to finish."""
        m, cfg = _model(seed=44)
        rng = np.random.default_rng(44)
        shared = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        ra = eng.add_request(np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)]
        ), max_new_tokens=8)
        # drive a few steps: prefill completes, blocks inserted in-flight
        for _ in range(4):
            eng.step()
        assert any(r is not None and r.req_id == ra for r in eng._slot_req)
        rb = eng.add_request(np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)]
        ), max_new_tokens=2)
        out = eng.run()
        assert out[rb].cached_tokens == 8  # matched A's in-flight chain
        _assert_invariants(eng)


class TestCopyOnWrite:
    def test_divergent_tail_forks_and_never_writes_the_shared_block(self):
        """X cached; Y shares X's first block then diverges inside the
        second: Y must fork (CoW) and X's re-run must still be byte-exact —
        the shared block was never written by Y."""
        m, cfg = _model(seed=45)
        rng = np.random.default_rng(45)
        x = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        y = x.copy()[:11]
        y[6:] = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)  # diverge in block 1

        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        rx = eng.add_request(x, max_new_tokens=5)
        out_x = eng.run()
        forks_before = eng.prefix_cache_stats()["cow_forks"]
        ry = eng.add_request(y, max_new_tokens=5)
        out_y = eng.run()
        assert eng.prefix_cache_stats()["cow_forks"] == forks_before + 1
        assert out_y[ry].cached_tokens == 4 + 2  # block 0 + 2-token partial

        # oracle runs in a FRESH cache-off engine
        eng_off = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16,
            enable_prefix_cache=False,
        )
        r1 = eng_off.add_request(y, max_new_tokens=5)
        out_off = eng_off.run()
        np.testing.assert_array_equal(out_y[ry].tokens(), out_off[r1].tokens())

        # X again through the shared (possibly forked-from) chain: byte-exact
        rx2 = eng.add_request(x, max_new_tokens=5)
        out_x2 = eng.run()
        np.testing.assert_array_equal(
            out_x[rx].tokens(), out_x2[rx2].tokens()
        )
        _assert_invariants(eng)


class TestEviction:
    def test_lru_evicts_zero_ref_chains_only_under_pressure(self):
        """Distinct prompts through a pool too small to retain them all:
        evictions must happen, live requests never lose blocks, and every
        request completes."""
        m, cfg = _model(seed=46)
        rng = np.random.default_rng(46)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=8, prompt_bucket=8,
            max_model_len=16,
        )
        outs = {}
        for i in range(6):
            rid = eng.add_request(
                rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
                max_new_tokens=3,
            )
            while eng.has_work():
                for req in eng.step():
                    outs[req.req_id] = req
                _assert_invariants(eng)
            assert rid in outs
        assert eng.prefix_cache_stats()["evictions"] > 0
        s = eng.pool_stats()
        assert s["free"] + s["cached_blocks"] == s["total"]

    def test_evict_blocks_never_touches_referenced_nodes(self):
        """Direct pool-level check: a node mapped by a live chain ref is not
        evictable even under explicit eviction pressure."""
        pool = BlockKVCache(8, 4, 2, 8, 4, dtype=np.float32)
        cache = PrefixCache(pool, 4, bytes_per_token=1)
        t1 = np.arange(4, dtype=np.int32)
        t2 = np.arange(4, 8, dtype=np.int32)
        b1 = pool.acquire_block()
        n1 = cache.insert(None, t1, b1)
        b2 = pool.acquire_block()
        n2 = cache.insert(n1, t2, b2)
        assert n1 is not None and n2 is not None
        # release both request refs (this also drops the request's pool
        # ref): BOTH nodes are now dead and count as reclaimable headroom,
        # though the eviction walk order is leaf-first (parent pinned by
        # child until the cascade reaches it)
        cache.release([n1, n2])
        assert cache.evictable_blocks == 2
        # eviction walks leaf-first; the parent cascades into the LRU the
        # moment its last child leaves, so one pressured call drains both
        assert cache.evict_blocks(5) == 2
        assert cache.node_count == 0
        assert pool.free_blocks == 8

    def test_match_is_capped_at_prompt_len_minus_one(self):
        """A fully-cached prompt must still compute its last token — the
        first generated token comes from that position's logits."""
        pool = BlockKVCache(8, 4, 2, 8, 4, dtype=np.float32)
        cache = PrefixCache(pool, 4, bytes_per_token=1)
        toks = np.arange(8, dtype=np.int32)
        b1 = pool.acquire_block()
        n1 = cache.insert(None, toks[:4], b1)
        b2 = pool.acquire_block()
        cache.insert(n1, toks[4:], b2)
        res = cache.match(toks)  # prompt == the cached chain exactly
        # block 1 may only be reused via CoW partial (3 of its 4 tokens)
        assert len(res.nodes) == 1
        assert res.cow is not None and res.cow[2] == 3
        assert res.cached_tokens == 7  # never prompt_len

    def test_insert_dedup_returns_none_for_existing_key(self):
        pool = BlockKVCache(8, 4, 2, 8, 4, dtype=np.float32)
        cache = PrefixCache(pool, 4, bytes_per_token=1)
        toks = np.arange(4, dtype=np.int32)
        b1 = pool.acquire_block()
        assert cache.insert(None, toks, b1) is not None
        b2 = pool.acquire_block()
        assert cache.insert(None, toks, b2) is None  # caller keeps b2 private
        assert pool.refcount(b1) == 2  # owner + cache
        assert pool.refcount(b2) == 1  # owner only


class TestPartialBlockSuffixReuse:
    """The match-length contract (PR 10 follow-on): a prompt diverging
    mid-chain maps EVERY full cached block before the first divergent block
    — even when the divergent block itself is partial (a ragged prompt
    tail) — plus the divergent block's leading run via copy-on-write. The
    same lengths must hold when the chain's tail has been spilled to the
    host tier (prefetch instead of CoW). The oracle for every case:
    ``cached == min(lcp, prompt_len - 1)`` and
    ``full_blocks_mapped == cached // block_size``."""

    def _cached_chain(self, seed, n_tokens=16):
        m, cfg = _model(seed=seed)
        rng = np.random.default_rng(seed)
        x = rng.integers(0, cfg.vocab_size, (n_tokens,)).astype(np.int32)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=64, prompt_bucket=32,
            max_model_len=48, kv_host_tier_bytes=1 << 20,
        )
        r = eng.add_request(x, max_new_tokens=2)
        out = eng.run()
        return eng, cfg, rng, x, out[r].tokens()

    def test_mid_chain_divergence_with_partial_divergent_block(self):
        """x cached (4 full blocks); y = x[:13] diverging at position 10 —
        inside y's PARTIAL third block. Full blocks 0 and 1 must both map
        (8 tokens) plus the 2-token leading run of the divergent block."""
        eng, cfg, rng, x, _ = self._cached_chain(seed=53)
        y = x[:13].copy()
        y[10:] = (y[10:] + 1) % cfg.vocab_size
        res = eng._cache.match(y)
        assert len(res.nodes) == 2  # every full block before the divergence
        assert res.cow is not None and res.cow[2] == 2
        assert res.cached_tokens == 10  # == lcp, the oracle maximum
        eng._cache.release(res.nodes)
        eng._cache.release_cow_source(res.cow[0])
        eng._mgr.decref(res.cow[1])

    def test_divergence_at_partial_block_start_maps_all_preceding(self):
        eng, cfg, rng, x, _ = self._cached_chain(seed=54)
        y = x[:11].copy()
        y[8:] = (y[8:] + 1) % cfg.vocab_size  # diverges at its block's row 0
        res = eng._cache.match(y)
        assert len(res.nodes) == 2 and res.cow is None
        assert res.cached_tokens == 8
        eng._cache.release(res.nodes)

    def test_exact_prefix_ending_mid_block_maps_all_full_blocks(self):
        """y is an exact 14-token prefix of the cached stream: all 3 full
        blocks map and the partial fourth reuses 1 token via CoW — the
        held-back final token is the only one recomputed."""
        eng, cfg, rng, x, _ = self._cached_chain(seed=55)
        y = x[:14]
        res = eng._cache.match(y)
        assert len(res.nodes) == 3
        assert res.cow is not None and res.cow[2] == 1
        assert res.cached_tokens == 13  # min(lcp, plen-1)
        eng._cache.release(res.nodes)
        eng._cache.release_cow_source(res.cow[0])
        eng._mgr.decref(res.cow[1])

    def test_same_lengths_when_the_chain_tail_is_spilled(self):
        """The cross-tier half of the contract: spill the whole chain, then
        the SAME divergent-partial prompt must reuse the same token count —
        full blocks via H2D prefetch, the divergent block's leading run via
        prefetch-on-write — and decode byte-identically to a cold engine."""
        eng, cfg, rng, x, _ = self._cached_chain(seed=56)
        y = x[:13].copy()
        y[10:] = (y[10:] + 1) % cfg.vocab_size
        eng._cache.evict_blocks(16)
        assert eng._cache.node_count == 0
        ry = eng.add_request(y, max_new_tokens=3)
        out = eng.run()
        assert out[ry].cached_tokens == 10  # same oracle across tiers
        assert eng.kv_tier_stats()["prefetched_blocks"] == 3  # 2 full + partial
        eng_off = ContinuousBatchingEngine(
            eng.model, max_slots=2, block_size=4, prompt_bucket=32,
            max_model_len=48, enable_prefix_cache=False,
        )
        r_off = eng_off.add_request(y, max_new_tokens=3)
        out_off = eng_off.run()
        np.testing.assert_array_equal(out[ry].tokens(), out_off[r_off].tokens())
        _assert_invariants(eng)

    def test_multi_turn_divergence_inside_generated_chain(self):
        """Turn-2 prompt = turn-1 stream + new text: the divergence (where
        the new text begins) is mid-block, and every full block of the
        registered prompt+generated chain before it must map."""
        eng, cfg, rng, x, stream = self._cached_chain(seed=57)
        tail = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        y = np.concatenate([stream, tail])
        # the final generated token is emitted, never appended to KV, so the
        # chain registers full blocks of the first stream.size - 1 tokens
        registered = ((stream.size - 1) // 4) * 4
        res = eng._cache.match(y)
        got = len(res.nodes) * 4 + (res.cow[2] if res.cow else 0)
        assert len(res.nodes) == registered // 4
        assert res.cached_tokens == got
        eng._cache.release(res.nodes)
        if res.cow is not None:
            eng._cache.release_cow_source(res.cow[0])
            eng._mgr.decref(res.cow[1])


class TestFaultSites:
    def test_sites_are_pinned_in_known_sites(self):
        assert "prefix_cache.match" in faults.KNOWN_SITES
        assert "prefix_cache.cow" in faults.KNOWN_SITES

    def test_match_fault_degrades_to_cold_miss(self):
        """An injected prefix_cache.match fault must cost a recompute, never
        a failed request — and tokens stay byte-identical."""
        m, cfg = _model(seed=47)
        rng = np.random.default_rng(47)
        prompt = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        r1 = eng.add_request(prompt, max_new_tokens=4)
        out1 = eng.run()
        with faults.inject(faults.FaultPlan.single("prefix_cache.match", 0)):
            r2 = eng.add_request(prompt, max_new_tokens=4)
            out2 = eng.run()
        assert out2[r2].cached_tokens == 0  # lookup failed -> cold path
        np.testing.assert_array_equal(out1[r1].tokens(), out2[r2].tokens())
        _assert_invariants(eng)

    def test_cow_fault_degrades_to_recompute_of_the_partial(self):
        """An injected prefix_cache.cow fault skips the fork: full-block
        hits still apply, the ragged tail is recomputed, tokens identical."""
        m, cfg = _model(seed=48)
        rng = np.random.default_rng(48)
        prompt = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        r1 = eng.add_request(prompt, max_new_tokens=4)
        out1 = eng.run()
        with faults.inject(faults.FaultPlan.single("prefix_cache.cow", 0)):
            r2 = eng.add_request(prompt, max_new_tokens=4)
            out2 = eng.run()
        # full blocks 0/1 still hit; the 2-token partial was recomputed
        assert out2[r2].cached_tokens == 8
        assert eng.prefix_cache_stats()["cow_forks"] == 0
        np.testing.assert_array_equal(out1[r1].tokens(), out2[r2].tokens())
        _assert_invariants(eng)


def test_one_compile_with_cache_on_and_off():
    """The unified signature is independent of cache hits, misses, CoW and
    the flag itself — ONE compiled program per engine either way."""
    m, cfg = _model(seed=49)
    rng = np.random.default_rng(49)
    prompt = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    for flag in (True, False):
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=16,
            enable_prefix_cache=flag,
        )
        for _ in range(2):
            eng.add_request(prompt, max_new_tokens=3)
            eng.run()
        assert eng.stats["step_traces"] == 1, (flag, eng.stats)


def test_rope_vector_offset_near_table_end_is_exact():
    """Chunked rows slice C rope positions starting at each slot's length; a
    width-C dynamic_slice CLAMPS its start near the table end and silently
    rotates the last tokens of a near-max context with wrong positions. The
    gather path must return exact per-position rows (clipping only the
    beyond-table tail, which is always a masked row)."""
    from paddle_tpu.models.llama import LlamaRotaryEmbedding

    emb = LlamaRotaryEmbedding(8, 32, 10000.0)
    cos, sin = emb.forward(4, paddle.to_tensor(np.asarray([29], np.int32)))
    ref_c = np.asarray(emb.cos_cached.numpy())
    ref_s = np.asarray(emb.sin_cached.numpy())
    got_c = np.asarray(cos.numpy())[0, :, 0, :]
    got_s = np.asarray(sin.numpy())[0, :, 0, :]
    # positions 29, 30, 31, then 32 clipped to 31 — a clamped slice would
    # have started at 28 and shifted EVERY row off by one
    for j, p in enumerate((29, 30, 31, 31)):
        np.testing.assert_array_equal(got_c[j], ref_c[p])
        np.testing.assert_array_equal(got_s[j], ref_s[p])


def test_admission_counts_whole_dead_chains_as_reclaimable():
    """A finished request's warm chain is ALL reclaimable headroom (interior
    nodes included, reached by the eviction cascade) — a request whose need
    equals free + the whole dead chain must admit, not queue forever."""
    m, cfg = _model(seed=50)
    rng = np.random.default_rng(50)
    eng = ContinuousBatchingEngine(
        m, max_slots=1, block_size=4, num_blocks=6, prompt_bucket=16,
        max_model_len=24,
    )
    ra = eng.add_request(
        rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32), max_new_tokens=1
    )
    out = eng.run()
    assert ra in out
    s = eng.pool_stats()
    assert s["cached_blocks"] == 2 and s["cached_reusable"] == 2, s
    # B needs all 6 blocks: only free(4) + the WHOLE dead chain(2) covers it
    rb = eng.add_request(
        rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32),
        max_new_tokens=8,
    )
    for _ in range(64):  # bounded: a headroom undercount would loop forever
        done = eng.step()
        if any(r.req_id == rb for r in done):
            break
    else:
        raise AssertionError("request B never admitted/finished: "
                             f"{eng.pool_stats()} {eng.prefix_cache_stats()}")
    _assert_invariants(eng)
