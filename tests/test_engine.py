"""Continuous-batching engine: the no-retrace invariant (exactly ONE
compiled signature over a mixed prefill/decode workload — chunked prefill),
token-for-token parity with per-sequence ``generate_paged`` (which shares the
engine's kernel path) and with the dense ``generate`` (which does not), and exact
refcounted block-pool accounting under adversarial admit/evict orders.

Everything here runs on CPU and fast — this file IS the tier-1 guard that
turns an engine retrace regression into a CI failure instead of a silent
TPU-only compile storm.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


from conftest import assert_engine_pool_exact as _assert_pool_exact


def _assert_drained(eng):
    """No live work: every block free or warm in the cache — never leaked."""
    _assert_pool_exact(eng)
    s = eng.pool_stats()
    assert s["free"] + s["cached_blocks"] == s["total"], s


def _reference(m, prompt, max_new, block_size, eos=None):
    """Per-sequence generate_paged oracle, truncated at eos like the engine."""
    out = np.asarray(
        m.generate_paged(
            paddle.to_tensor(prompt[None]), max_new_tokens=max_new,
            block_size=block_size, eos_token_id=eos,
        ).numpy()
    )[0]
    if eos is not None:
        gen = out[len(prompt):]
        hits = np.where(gen == eos)[0]
        if hits.size:
            out = out[: len(prompt) + hits[0] + 1]
    return out


def _dense_reference(m, prompt, max_new):
    """The independent oracle: greedy ``generate`` over dense KV (no paged
    code; ``generate_paged`` shares the engine's kernel path)."""
    out = m.generate(paddle.to_tensor(prompt[None]), max_new_tokens=max_new, do_sample=False)
    return np.asarray(out.numpy())[0]


class TestNoRetraceInvariant:
    def test_mixed_workload_exactly_one_compile_and_token_parity(self):
        """The acceptance test: staggered admits (7 requests through 3
        slots), early finishes (varied budgets), varied prompt lengths —
        exactly ONE unified step trace (chunked prefill rides the decode
        dispatch), outputs equal to running each sequence alone through
        generate_paged."""
        m, cfg = _model()
        rng = np.random.default_rng(0)
        eng = ContinuousBatchingEngine(
            m, max_slots=3, block_size=4, prompt_bucket=16
        )
        specs = [(5, 6), (7, 4), (3, 9), (6, 2), (2, 7), (8, 5), (4, 3)]
        prompts = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n, _ in specs
        ]
        rids = [
            eng.add_request(p, max_new_tokens=t)
            for p, (_, t) in zip(prompts, specs)
        ]
        out = eng.run()

        assert eng.stats["step_traces"] == 1, eng.stats
        if hasattr(eng._step_fn, "_cache_size"):  # jit-level confirmation
            assert eng._step_fn._cache_size() == 1

        for rid, p, (_, t) in zip(rids, prompts, specs):
            ref = _reference(m, p, t, block_size=4)
            np.testing.assert_array_equal(out[rid].tokens(), ref)
            np.testing.assert_array_equal(out[rid].tokens(), _dense_reference(m, p, t))

    def test_late_submits_mid_flight_no_retrace(self):
        """Requests added AFTER decoding started enter freed slots without a
        new compile — admits/evictions are data, not shapes."""
        m, cfg = _model(seed=1)
        rng = np.random.default_rng(1)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=16)
        first = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        r0 = eng.add_request(first, max_new_tokens=3)
        eng.step()
        late = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
        r1 = eng.add_request(late, max_new_tokens=5)
        out = eng.run()
        assert eng.stats["step_traces"] == 1
        np.testing.assert_array_equal(
            out[r0].tokens(), _reference(m, first, 3, block_size=4)
        )
        np.testing.assert_array_equal(
            out[r1].tokens(), _reference(m, late, 5, block_size=4)
        )

    def test_eos_finishes_early_frees_slot(self):
        m, cfg = _model(seed=2)
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        # pick an eos greedy decoding actually emits mid-stream
        probe = _reference(m, prompt, 6, block_size=4)
        eos = int(probe[len(prompt) + 2])
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
        rid = eng.add_request(prompt, max_new_tokens=6, eos_token_id=eos)
        out = eng.run()
        req = out[rid]
        assert req.finish_reason == "stop"
        assert req.generated[-1] == eos
        np.testing.assert_array_equal(
            req.tokens(), _reference(m, prompt, 6, block_size=4, eos=eos)
        )
        _assert_drained(eng)  # everything reclaimed or warm in the cache


class TestBlockPoolAccounting:
    def test_exact_after_every_step(self):
        """allocated + free == pool size after EVERY admit/evict boundary."""
        m, cfg = _model(seed=3)
        rng = np.random.default_rng(3)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=12, prompt_bucket=8,
            max_model_len=16,
        )
        for n, t in [(5, 4), (3, 6), (7, 3), (2, 5), (6, 2)]:
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                max_new_tokens=t,
            )
        _assert_pool_exact(eng)
        while eng.has_work():
            eng.step()
            _assert_pool_exact(eng)
        _assert_drained(eng)

    def test_adversarial_evict_then_admit_larger_prompt(self):
        """A large request must WAIT until a finishing sequence's blocks are
        reclaimed, then admit into them — accounting exact throughout."""
        m, cfg = _model(seed=4)
        rng = np.random.default_rng(4)
        # pool of 4 blocks x 4 tokens: A (prompt 5, +4 -> 2 blocks) leaves
        # only 2 unreserved; B (prompt 9, +4 -> 3 blocks) cannot coexist
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=4, prompt_bucket=12,
            max_model_len=16,
        )
        a = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        b = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
        ra = eng.add_request(a, max_new_tokens=4)
        rb = eng.add_request(b, max_new_tokens=4)
        saw_b_waiting = False
        out = {}
        while eng.has_work():
            for req in eng.step():
                out[req.req_id] = req
            _assert_pool_exact(eng)
            if any(r is not None and r.req_id == ra for r in eng._slot_req):
                # while A lives, B must not have been admitted (3 > 4 - 2)
                assert all(
                    r is None or r.req_id != rb for r in eng._slot_req
                )
                saw_b_waiting = True
        assert saw_b_waiting
        np.testing.assert_array_equal(
            out[ra].tokens(), _reference(m, a, 4, block_size=4)
        )
        np.testing.assert_array_equal(
            out[rb].tokens(), _reference(m, b, 4, block_size=4)
        )
        _assert_drained(eng)

    def test_failed_decode_step_rolls_back_allocator(self):
        """A transient device failure mid-step must leave the allocator in
        lockstep with the engine (mgr lengths == _ntok), so retried steps
        neither leak blocks nor break the reservation invariant."""
        m, cfg = _model(seed=8)
        rng = np.random.default_rng(8)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
        rid = eng.add_request(prompt, max_new_tokens=4)
        real, calls = eng._step_fn, []

        def flaky(*a, **k):
            if not calls:
                calls.append(1)
                raise RuntimeError("transient device failure")
            return real(*a, **k)

        eng._step_fn = flaky
        with pytest.raises(RuntimeError, match="transient"):
            eng.step()
        _assert_pool_exact(eng)
        # rolled back, not drifted: block capacity is in lockstep with _ntok
        assert len(eng._blocks[0]) * eng.block_size >= eng._ntok[0]
        out = eng.run()  # retrying serves identical tokens
        np.testing.assert_array_equal(
            out[rid].tokens(), _reference(m, prompt, 4, block_size=4)
        )
        _assert_drained(eng)

    def test_donated_buffer_loss_marks_engine_broken(self):
        """When a failed step consumed donated cache buffers (TPU), the
        engine must refuse further use instead of serving garbage KV."""
        m, cfg = _model(seed=9)
        rng = np.random.default_rng(9)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
        eng.add_request(
            rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32), max_new_tokens=4
        )
        eng._buffers_lost = lambda: True  # what a donating backend reports

        def doomed(*a, **k):
            raise RuntimeError("device died mid-step")

        eng._step_fn = doomed
        with pytest.raises(RuntimeError, match="device died"):
            eng.step()
        with pytest.raises(RuntimeError, match="build a new"):
            eng.step()
        with pytest.raises(RuntimeError, match="build a new"):
            eng.add_request(np.zeros((2,), np.int32))

    def test_reservation_prevents_mid_flight_exhaustion(self):
        """Worst-case reservation at admit means step() can never raise the
        allocator's out-of-blocks MemoryError mid-decode."""
        m, cfg = _model(seed=5)
        rng = np.random.default_rng(5)
        eng = ContinuousBatchingEngine(
            m, max_slots=4, block_size=4, num_blocks=6, prompt_bucket=8,
            max_model_len=16,
        )
        for _ in range(6):
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
                max_new_tokens=7,
            )
        while eng.has_work():
            eng.step()  # MemoryError here would fail the test
            _assert_pool_exact(eng)


class TestIntakeValidation:
    def test_rejects_prompt_over_bucket(self):
        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(m, max_slots=1, block_size=4, prompt_bucket=8)
        with pytest.raises(ValueError, match="prompt_bucket"):
            eng.add_request(np.zeros((9,), np.int32))

    def test_rejects_over_model_len(self):
        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(
            m, max_slots=1, block_size=4, prompt_bucket=8, max_model_len=12
        )
        with pytest.raises(ValueError, match="max_model_len"):
            eng.add_request(np.zeros((8,), np.int32), max_new_tokens=5)

    def test_rejects_request_larger_than_whole_pool(self):
        """A request no eviction can make room for must fail at intake, not
        sit at the FIFO head busy-looping run() forever."""
        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=2, prompt_bucket=8,
            max_model_len=16,
        )
        with pytest.raises(ValueError, match="KV blocks"):
            eng.add_request(np.zeros((8,), np.int32), max_new_tokens=8)

    def test_rejects_empty_and_zero_budget(self):
        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(m, max_slots=1, block_size=4, prompt_bucket=8)
        with pytest.raises(ValueError, match="empty"):
            eng.add_request(np.zeros((0,), np.int32))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request(np.zeros((2,), np.int32), max_new_tokens=0)


class TestEngineMetrics:
    """The observability acceptance test: metrics report exactly 2 compiles
    for a staggered mixed workload, TTFT/decode histograms are populated,
    pool gauges match ``pool_stats()`` exactly after every admit/evict, and
    recording is a no-op with metrics disabled."""

    def _flag(self):
        return paddle.get_flags(["FLAGS_enable_metrics"])["FLAGS_enable_metrics"]

    def _assert_gauges_match(self, reg, eng):
        s = eng.pool_stats()
        assert reg.get("engine_kv_blocks_allocated").value() == s["allocated"]
        assert reg.get("engine_kv_blocks_free").value() == s["free"]
        # utilization measures LIVE load: warm-but-reclaimable cached blocks
        # are headroom, not pressure
        assert reg.get("engine_kv_pool_utilization").value() == pytest.approx(
            (s["allocated"] - s["cached_reusable"]) / s["total"]
        )
        assert reg.get("engine_queue_depth").value() == len(eng._waiting)
        assert reg.get("engine_active_slots").value() == sum(
            r is not None for r in eng._slot_req
        )

    def test_staggered_workload_metrics_and_watchdog(self):
        from paddle_tpu import observability as obs

        prior = self._flag()
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            m, cfg = _model(seed=11)
            rng = np.random.default_rng(11)
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=16
            )
            reg = obs.GLOBAL_METRICS
            # staggered: 5 requests through 2 slots, budgets 2..6 so some
            # finish early and free their slot mid-flight
            specs = [(5, 4), (7, 2), (3, 6), (6, 3), (2, 5)]
            for n, t in specs:
                eng.add_request(
                    rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                    max_new_tokens=t,
                )
            assert reg.get("engine_queue_depth").value() == 5
            done = []
            while eng.has_work():
                done += eng.step()
                self._assert_gauges_match(reg, eng)  # exact after every boundary
            assert len(done) == 5

            # histograms populated: one TTFT per admit, one latency per step
            assert reg.get("engine_ttft_seconds").count() == 5
            assert reg.get("engine_ttft_seconds").sum() > 0
            assert (
                reg.get("engine_decode_step_seconds").count()
                == eng.stats["steps"]
                > 0
            )
            assert reg.get("engine_requests_admitted_total").value() == 5
            assert reg.get("engine_requests_finished_total").total() == 5
            assert reg.get("engine_requests_finished_total").value(reason="length") == 5
            assert reg.get("engine_slots_evicted_total").value() == 5
            assert reg.get("engine_kv_pool_utilization").high_water() > 0
            s = eng.pool_stats()
            assert s["free"] + s["cached_blocks"] == eng.num_blocks

            # the watchdog saw exactly the engine's ONE compiled signature
            # (chunked prefill rides the decode dispatch)
            rep = {
                k: v
                for k, v in obs.GLOBAL_WATCHDOG.report().items()
                if k.startswith("ContinuousBatchingEngine.")
            }
            assert set(rep) == {"ContinuousBatchingEngine.step"}
            assert all(r["count"] == 1 for r in rep.values())
            assert rep["ContinuousBatchingEngine.step"]["signatures"] == ["toks[2,4]"]
            assert all(r["causes"] == {"first_call": 1} for r in rep.values())
            # ... and the gated metric counter agrees: exactly 1 compile
            c = reg.get("jit_compiles_total")
            assert c.value(fn="ContinuousBatchingEngine.step", cause="first_call") == 1
            assert c.total() == 1
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prior})

    def test_disabled_recording_is_noop(self):
        from paddle_tpu import observability as obs

        prior = self._flag()
        paddle.set_flags({"FLAGS_enable_metrics": False})
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()
        try:
            m, cfg = _model(seed=12)
            rng = np.random.default_rng(12)
            eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                max_new_tokens=3,
            )
            eng.run()
            # nothing recorded anywhere in the registry
            assert obs.GLOBAL_METRICS.snapshot() == {}
            # the watchdog's own ledger stays honest even with metrics off —
            # compile counting is not hot-path recording
            assert obs.GLOBAL_WATCHDOG.counts() == {
                "ContinuousBatchingEngine.step": 1,
            }
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prior})


def test_step_returns_finished_exactly_once():
    """Finished requests are handed back only by the step() (or run()) call
    during which they finish — the engine retains no reference, so a
    step()-driven server's host memory stays bounded and a later run()
    never re-delivers stale results."""
    m, cfg = _model(seed=10)
    rng = np.random.default_rng(10)
    eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
    rid = eng.add_request(
        rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32), max_new_tokens=2
    )
    done = []
    while eng.has_work():
        done += eng.step()
    assert [r.req_id for r in done] == [rid]
    assert eng.run() == {}  # nothing retained, nothing re-delivered


def test_engine_smoke():
    """Fast tier-1 smoke: two tiny requests end-to-end, ONE compile, pool
    drained — the minimal canary for retrace/accounting regressions."""
    m, cfg = _model(seed=7)
    rng = np.random.default_rng(7)
    eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
    rids = [
        eng.add_request(
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new_tokens=3,
        )
        for n in (3, 5)
    ]
    out = eng.run()
    assert set(out) == set(rids)
    assert all(len(r.generated) == 3 for r in out.values())
    assert eng.stats["step_traces"] == 1
    _assert_drained(eng)


def test_paged_pages_walked_counts_the_live_pages_of_each_step():
    """``stats["paged_pages_walked"]``: every step adds, over its active slots,
    ceil((tokens cached + new tokens) / block_size) — what the paged kernel's
    length-bounded walk visits, against slots x max_blocks_per_seq a step."""
    m, cfg = _model(seed=11)
    rng = np.random.default_rng(11)
    eng = ContinuousBatchingEngine(m, max_slots=3, block_size=4, prompt_bucket=16)
    for n, new in ((3, 4), (9, 2), (16, 3), (5, 5)):
        eng.add_request(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), max_new_tokens=new)
    expected = 0
    real = eng._dispatch

    def spy(toks, q_lens, active):
        nonlocal expected
        expected += sum(-(-(int(eng._ntok[i]) + int(q_lens[i])) // 4) for i in range(3) if active[i])
        return real(toks, q_lens, active)

    eng._dispatch = spy
    eng.run()
    steps = eng.stats["steps"]
    assert 0 < expected == eng.stats["paged_pages_walked"] <= steps * 3 * eng.max_blocks_per_seq
