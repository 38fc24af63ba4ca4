"""Speculative decoding on the one-signature engine: greedy outputs
byte-identical with speculation on vs off, exactly ONE compiled signature
with drafts riding the mixed ragged step, exact refcounted pool accounting
across accept/rewind churn, fault-degraded verification, recovery
mid-speculation, and the PR-10 follow-on — generated-token blocks
registered into the prefix cache at request finish.

Everything here runs on CPU and fast — this file is the tier-1 guard that
turns a speculation regression (token drift, rewind leak, retrace) into a
CI failure instead of a silent correctness/perf bug on TPU.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.spec_decode import NGramDrafter, count_accepted
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing.faults import FaultPlan, inject


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


from conftest import assert_engine_pool_exact


def _assert_pool_exact(eng):
    """The shared churn invariant, plus the speculation-specific bound:
    a rewound table is never shorter than the committed tokens."""
    assert_engine_pool_exact(eng)
    for slot, req in enumerate(eng._slot_req):
        if req is not None:
            assert len(eng._blocks[slot]) * eng.block_size >= eng._ntok[slot]


def _assert_drained(eng):
    _assert_pool_exact(eng)
    s = eng.pool_stats()
    assert s["free"] + s["cached_blocks"] == s["total"], s


def _repetitive_prompts(rng, cfg, n, length=16):
    """Templated prompts (boilerplate + fill, repeated) — the drafter's
    home turf, guaranteeing the spec path actually packs drafts."""
    out = []
    template = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    for _ in range(n):
        fill = rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)
        out.append(np.concatenate([template, fill, template, fill])[:length])
    return out


class TestDrafter:
    def test_cyclic_context_full_draft(self):
        d = NGramDrafter(3)
        ctx = np.tile(np.array([7, 11], np.int32), 20)
        draft = d.propose(ctx, 6)
        # the cycle continues: [7, 11, 7, 11, ...] after a trailing 11
        np.testing.assert_array_equal(draft, [7, 11, 7, 11, 7, 11])

    def test_no_recurrence_no_draft(self):
        d = NGramDrafter(3)
        ctx = np.arange(32, dtype=np.int32)  # every token unique
        assert d.propose(ctx, 4).size == 0

    def test_longest_ngram_wins_over_recency(self):
        d = NGramDrafter(3)
        # trailing 3-gram [1,2,3] occurs early (continues with 9);
        # the bare 1-gram [3] also occurs later (continues with 5)
        ctx = np.array([1, 2, 3, 9, 0, 3, 5, 0, 1, 2, 3], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 1), [9])

    def test_full_continuation_preferred_over_truncated(self):
        d = NGramDrafter(1)
        # the trailing 5 recurs at index 0 (full 3-token continuation) and
        # index 5 (only 2 tokens after it) — the full draft wins over the
        # more recent truncated one
        ctx = np.array([5, 1, 2, 3, 4, 5, 9, 5], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 3), [1, 2, 3])

    def test_short_context_and_zero_budget(self):
        d = NGramDrafter(3)
        assert d.propose(np.array([3], np.int32), 4).size == 0
        assert d.propose(np.array([3, 3, 3], np.int32), 0).size == 0

    def test_count_accepted(self):
        row = np.array([4, 5, 6, 7], np.int32)
        assert count_accepted(row, np.array([4, 5, 6], np.int32)) == 3
        assert count_accepted(row, np.array([4, 9, 6], np.int32)) == 1
        assert count_accepted(row, np.array([9], np.int32)) == 0
        assert count_accepted(row, np.empty((0,), np.int32)) == 0


class TestSpecParity:
    def test_greedy_byte_identical_on_vs_off(self):
        """The acceptance test: a mixed workload (repetitive + random
        prompts, staggered budgets, more requests than slots) produces the
        SAME greedy stream with speculation on and off, through exactly ONE
        compiled signature each, with the pool drained at the end."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        prompts = _repetitive_prompts(rng, cfg, 3) + [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (5, 9)
        ]
        budgets = [24, 18, 21, 8, 12]

        def run(spec):
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=32,
                prefill_chunk=8, max_model_len=128, spec_decode=spec,
            )
            rids = [
                eng.add_request(p, max_new_tokens=t)
                for p, t in zip(prompts, budgets)
            ]
            out = eng.run()
            return eng, [out[r].tokens() for r in rids]

        eng_off, toks_off = run(False)
        eng_on, toks_on = run(True)
        for a, b in zip(toks_off, toks_on):
            np.testing.assert_array_equal(a, b)
        # the workload genuinely speculated (drafts packed and some
        # accepted), and both engines compiled exactly once
        assert eng_on.stats["spec_drafted"] > 0
        assert eng_on.stats["spec_accepted"] > 0
        assert eng_on.stats["steps"] < eng_off.stats["steps"]
        assert eng_off.stats["step_traces"] == 1
        assert eng_on.stats["step_traces"] == 1
        if hasattr(eng_on._step_fn, "_cache_size"):
            assert eng_on._step_fn._cache_size() == 1
        _assert_drained(eng_off)
        _assert_drained(eng_on)

    def test_eos_respected_across_speculative_commits(self):
        """An eos that greedy decode emits mid-stream truncates identically
        with speculation on — even when the eos lands inside an accepted
        draft's bulk commit."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        prompt = _repetitive_prompts(rng, cfg, 1)[0]

        def run(spec, eos=None):
            eng = ContinuousBatchingEngine(
                m, max_slots=1, block_size=4, prompt_bucket=32,
                prefill_chunk=8, max_model_len=128, spec_decode=spec,
            )
            rid = eng.add_request(prompt, max_new_tokens=24, eos_token_id=eos)
            out = eng.run()
            _assert_drained(eng)
            return out[rid]

        probe = run(False)
        # pick an eos the stream actually emits past the first few tokens,
        # so with speculation it can fall inside a committed draft run
        eos = int(probe.generated[len(probe.generated) // 2])
        ref = run(False, eos=eos)
        spec = run(True, eos=eos)
        assert ref.finish_reason == spec.finish_reason
        np.testing.assert_array_equal(ref.tokens(), spec.tokens())
        assert spec.generated[-1] == eos or spec.finish_reason == "length"

    def test_churn_refcounts_exact_across_rewinds(self):
        """Seeded churn property test: shared-prefix prompts (cache hits +
        CoW forks) mixed with repetitive tails (drafts + rewinds) and
        mid-stream eos finishes — pool refcounts equal slot mappings + CoW
        pins + chain ownership after EVERY step."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(17)
        shared = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, prompt_bucket=32, num_blocks=48,
            prefill_chunk=8, max_model_len=64, spec_decode=True,
        )
        reps = _repetitive_prompts(rng, cfg, 4)
        for j in range(8):
            if j % 2 == 0:
                tail = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
                prompt = np.concatenate([shared, tail])
            else:
                prompt = reps[j // 2]
            eng.add_request(
                prompt,
                max_new_tokens=int(rng.integers(6, 20)),
                eos_token_id=int(rng.integers(0, cfg.vocab_size))
                if j % 3 == 0
                else None,
            )
        _assert_pool_exact(eng)
        while eng.has_work():
            eng.step()
            _assert_pool_exact(eng)
        # the run exercised the paths under test: drafts, rejections
        # (rewinds), and prefix-cache sharing
        assert eng.stats["spec_drafted"] > 0
        assert eng.stats["spec_rejected"] > 0
        assert eng.stats["prompt_tokens_reused"] > 0
        _assert_drained(eng)

    def test_speculation_respects_worst_case_reservation(self):
        """Drafts are capped at the remaining token budget, so a slot's KV
        can never transiently outgrow its worst-case reservation — a
        pool-exhaustion MemoryError mid-step would fail this test."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        # pool sized to the exact worst case of the admitted requests
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=12, prompt_bucket=16,
            prefill_chunk=8, max_model_len=24, spec_decode=True,
        )
        for p in _repetitive_prompts(rng, cfg, 4, length=8):
            eng.add_request(p, max_new_tokens=16)
        while eng.has_work():
            eng.step()  # MemoryError here would fail the test
            _assert_pool_exact(eng)
            for slot, req in enumerate(eng._slot_req):
                if req is not None:
                    worst = req.prompt.size + req.max_new_tokens - 1
                    assert int(eng._ntok[slot]) <= worst
        _assert_drained(eng)


class TestSpecFaults:
    def test_verify_fault_degrades_to_plain_decode(self):
        """An injected ``spec.verify`` fault must degrade that slot to
        plain decode for the step — same greedy stream, no lost tokens, no
        rewind corruption, engine fully usable after."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        prompts = _repetitive_prompts(rng, cfg, 2)

        def run(spec, plan=None):
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=32,
                prefill_chunk=8, max_model_len=128, spec_decode=spec,
            )
            rids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
            if plan is not None:
                with inject(plan):
                    out = eng.run()
            else:
                out = eng.run()
            _assert_drained(eng)
            return eng, [out[r].tokens() for r in rids]

        _, ref = run(False)
        plan = FaultPlan(
            [t for i in (0, 1, 2) for t in FaultPlan.single("spec.verify", i).triggers]
        )
        eng, faulted = run(True, plan=plan)
        for a, b in zip(ref, faulted):
            np.testing.assert_array_equal(a, b)
        # the degraded steps counted their whole draft as rejected, and the
        # engine never took the recovery path (degrade is not a failure)
        assert eng.stats["spec_drafted"] > 0
        assert eng.stats["recoveries"] == 0
        assert not eng.broken

    def test_recovery_mid_speculation_replays_to_same_tokens(self):
        """A buffers-lost dispatch failure in the middle of a speculative
        workload recovers by replaying committed host truth — the final
        streams equal the unfaulted (and unspeculated) run."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        prompts = _repetitive_prompts(rng, cfg, 2)

        def run(spec, plan=None):
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=32,
                prefill_chunk=8, max_model_len=128, spec_decode=spec,
            )
            rids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
            if plan is not None:
                with inject(plan):
                    out = eng.run()
            else:
                out = eng.run()
            _assert_drained(eng)
            return eng, [out[r].tokens() for r in rids]

        _, ref = run(False)
        # call 6 lands mid-decode (prompts prefill in 2 chunk steps each);
        # an InjectedFault at the dispatch site models donated-buffer loss
        eng, replayed = run(True, plan=FaultPlan.single("engine.decode", 6))
        for a, b in zip(ref, replayed):
            np.testing.assert_array_equal(a, b)
        assert eng.stats["recoveries"] == 1
        assert eng.stats["step_traces"] == 1  # recovery reused the program
        assert not eng.broken


class TestGeneratedBlockRegistration:
    def test_second_turn_maps_first_turns_generated_kv(self):
        """PR-10 follow-on: a finished request's full blocks of GENERATED
        tokens enter the prefix cache, so a multi-turn conversation's second
        turn (prompt = first prompt + reply + new text) maps the first
        turn's KV instead of recomputing it."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(5)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=64,
            prefill_chunk=8, max_model_len=128,
        )
        turn1 = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        r1 = eng.add_request(turn1, max_new_tokens=9)
        out1 = eng.run()
        assert eng.stats["gen_blocks_registered"] > 0
        # turn 2 replays the whole first exchange plus new user text
        turn2 = np.concatenate(
            [out1[r1].tokens(), rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)]
        )
        computed_before = eng.stats["prompt_tokens_computed"]
        r2 = eng.add_request(turn2, max_new_tokens=4)
        out2 = eng.run()
        req2 = out2[r2]
        # turn 1 stored prompt(8) + 8 appended generated tokens = 4 full
        # blocks, all of which the second turn's prompt must map
        assert req2.cached_tokens >= 16
        computed = eng.stats["prompt_tokens_computed"] - computed_before
        assert computed <= turn2.size - 16 + eng.block_size
        _assert_drained(eng)

    def test_registration_matches_speculated_stream(self):
        """With speculation on, finish-time registration hashes only
        COMMITTED tokens (rewinds happened at commit time), so a second
        turn over a speculated first turn maps byte-correct KV — greedy
        outputs still identical to the unspeculated engine."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        prompt = _repetitive_prompts(rng, cfg, 1)[0]
        tail = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)

        def two_turns(spec):
            eng = ContinuousBatchingEngine(
                m, max_slots=1, block_size=4, prompt_bucket=64,
                prefill_chunk=8, max_model_len=128, spec_decode=spec,
            )
            r1 = eng.add_request(prompt, max_new_tokens=13)
            out1 = eng.run()
            turn2 = np.concatenate([out1[r1].tokens(), tail])
            r2 = eng.add_request(turn2, max_new_tokens=6)
            out2 = eng.run()
            _assert_drained(eng)
            return out1[r1], out2[r2]

        a1, a2 = two_turns(False)
        b1, b2 = two_turns(True)
        np.testing.assert_array_equal(a1.tokens(), b1.tokens())
        np.testing.assert_array_equal(a2.tokens(), b2.tokens())
        assert b2.cached_tokens > 0


class TestSpecObservability:
    def test_metrics_counters_and_acceptance_histogram(self):
        from paddle_tpu import observability as obs

        prior = paddle.get_flags(["FLAGS_enable_metrics"])
        obs.GLOBAL_METRICS.reset()
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            m, cfg = _model(seed=3)
            rng = np.random.default_rng(9)
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=32,
                prefill_chunk=8, max_model_len=128, spec_decode=True,
            )
            for p in _repetitive_prompts(rng, cfg, 3):
                eng.add_request(p, max_new_tokens=16)
            eng.run()
            reg = obs.GLOBAL_METRICS
            s = eng.spec_decode_stats()
            assert s["drafted_tokens"] > 0
            assert (
                reg.get("spec_decode_drafted_tokens_total").value()
                == s["drafted_tokens"]
            )
            assert (
                reg.get("spec_decode_accepted_tokens_total").value()
                == s["accepted_tokens"]
            )
            assert (
                reg.get("spec_decode_rejected_tokens_total").value()
                == s["rejected_tokens"]
            )
            h = reg.get("spec_decode_acceptance_rate")
            assert h.count() == s["speculative_steps"] > 0
            assert s["accepted_tokens"] + s["rejected_tokens"] == s["drafted_tokens"]
            assert 0.0 <= s["acceptance_rate"] <= 1.0
        finally:
            paddle.set_flags(prior)

    def test_healthz_snapshot_surfaces_acceptance(self):
        from paddle_tpu.serving import ServingFrontend

        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=32,
            prefill_chunk=8, max_model_len=128, spec_decode=True,
        )
        fe = ServingFrontend(eng)
        handle = fe.submit(_repetitive_prompts(rng, cfg, 1)[0], max_new_tokens=12)
        while not handle.finished:
            fe.pump()
        snap = fe.snapshot()
        assert snap["spec_decode"]["enabled"] is True
        assert snap["spec_decode"]["drafted_tokens"] > 0
        assert 0.0 <= snap["spec_decode"]["acceptance_rate"] <= 1.0

    def test_spec_rewind_flight_events(self):
        from paddle_tpu.observability import flight_recorder as flight

        m, cfg = _model(seed=3)
        rng = np.random.default_rng(9)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, prompt_bucket=32,
            prefill_chunk=8, max_model_len=128, spec_decode=True,
        )
        for p in _repetitive_prompts(rng, cfg, 2):
            eng.add_request(p, max_new_tokens=16)
        eng.run()
        assert eng.stats["spec_rejected"] > 0
        events = [
            e
            for e in flight.get_flight_recorder().snapshot()
            if e["kind"] == "spec_rewind"
        ]
        assert events, "rejections must leave spec_rewind events in the black box"
        e = events[-1]
        assert e["drafted"] == e["accepted"] + e["rejected"]
