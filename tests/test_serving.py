"""SLO-aware serving frontend: weighted fair admission, deadlines at every
lifecycle stage, hysteresis load shedding, the streaming HTTP endpoint, and
the overload acceptance test — arrivals at 2x the sustainable rate must be
absorbed by explicit shedding (429 / typed ``Overloaded``), never by
unbounded queue growth or recompilation.

Everything runs on CPU with the tiny Llama config, same as test_engine.py.
"""

import http.client
import json
import socket
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.engine import InferenceRequest
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    Hysteresis,
    Overloaded,
    Priority,
    ServingConfig,
    ServingFrontend,
    WeightedFairPolicy,
    start_serving_server,
    stop_serving_server,
)
from paddle_tpu.serving.frontend import DEGRADED, NORMAL, SHEDDING
from paddle_tpu.serving.loadgen import (
    TrafficClass,
    measure_sustainable_rate,
    poisson_arrivals,
    run_open_loop,
)
from paddle_tpu.testing import faults


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _frontend(seed=0, max_queue=8, config=None, **engine_kw):
    m, cfg = _model(seed)
    engine_kw.setdefault("max_slots", 2)
    engine_kw.setdefault("block_size", 4)
    engine_kw.setdefault("prompt_bucket", 8)
    eng = ContinuousBatchingEngine(m, **engine_kw)
    fe = ServingFrontend(eng, config or ServingConfig(max_queue=max_queue))
    return fe, eng, cfg


def _drained(eng):
    """With no live work, every block is either free or retained (warm,
    reclaimable) by the prefix cache — anything else is a leak."""
    s = eng.pool_stats()
    return s["free"] + s["cached_blocks"] == s["total"]


def _prompt(rng, cfg, n=4):
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _drain(fe, handles, max_iters=500):
    done = []
    for _ in range(max_iters):
        done += fe.pump()
        if all(h.finished for h in handles):
            return done
    raise AssertionError("requests did not reach a terminal state")


@pytest.fixture
def metrics_on():
    prior = paddle.get_flags(["FLAGS_enable_metrics"])["FLAGS_enable_metrics"]
    paddle.set_flags({"FLAGS_enable_metrics": True})
    obs.GLOBAL_METRICS.reset()
    obs.GLOBAL_WATCHDOG.reset()
    yield obs.GLOBAL_METRICS
    paddle.set_flags({"FLAGS_enable_metrics": prior})


# -- hysteresis + controller -------------------------------------------------

class TestHysteresis:
    def test_latched_thresholds(self):
        g = Hysteresis(high=0.8, low=0.4)
        assert g.update(0.7) is False  # below start: stays off
        assert g.update(0.85) is True  # crossed start
        assert g.update(0.5) is True  # between stop and start: LATCHED on
        assert g.update(0.79) is True  # still latched below start
        assert g.update(0.3) is False  # below stop: released
        assert g.update(0.5) is False  # must cross start again

    def test_start_stop_must_be_ordered(self):
        with pytest.raises(ValueError, match="low"):
            Hysteresis(high=0.4, low=0.8)

    def test_controller_levels_escalate_and_release(self):
        cfg = ServingConfig(
            max_queue=10,
            degrade_queue_frac=(0.5, 0.2),
            shed_queue_frac=(0.8, 0.4),
            degrade_util=(2.0, 2.0),  # effectively disabled
            shed_util=(2.0, 2.0),
        )
        from paddle_tpu.serving.frontend import OverloadController

        c = OverloadController(cfg)
        assert c.update(0.1, 0.0, 0.0) == NORMAL
        assert c.update(0.6, 0.0, 0.0) == DEGRADED
        assert c.update(0.9, 0.0, 0.0) == SHEDDING
        assert c.update(0.6, 0.0, 0.0) == SHEDDING  # latched: 0.6 > shed stop 0.4
        assert c.update(0.3, 0.0, 0.0) == DEGRADED  # shed released, degrade latched
        assert c.update(0.1, 0.0, 0.0) == NORMAL


# -- weighted fair scheduling ------------------------------------------------

class TestWeightedFairPolicy:
    def _reqs(self, specs):
        return [
            InferenceRequest(i, np.zeros(4, np.int32), 4, None, priority=p, tenant=t)
            for i, (p, t) in enumerate(specs)
        ]

    def test_stride_shares_converge_to_weights(self):
        pol = WeightedFairPolicy({0: 2.0, 2: 1.0})
        waiting = self._reqs([(0, "a")] * 30 + [(2, "b")] * 30)
        picks = []
        for _ in range(18):
            req = pol.select(waiting, lambda r: True)
            picks.append(req.priority)
            waiting.remove(req)
        # a sustained backlog splits admissions 2:1 between the classes
        assert picks.count(0) == 12 and picks.count(2) == 6
        # ... and best-effort is never starved outright
        assert 2 in picks[:3]

    def test_tenant_round_robin_within_class(self):
        pol = WeightedFairPolicy()
        waiting = self._reqs(
            [(1, "a"), (1, "a"), (1, "a"), (1, "b"), (1, "c")]
        )
        order = []
        while waiting:
            req = pol.select(waiting, lambda r: True)
            order.append(req.tenant)
            waiting.remove(req)
        # tenants alternate before any tenant gets a second turn
        assert order[:3] in (["a", "b", "c"], ["b", "c", "a"], ["c", "a", "b"],
                             ["a", "c", "b"], ["b", "a", "c"], ["c", "b", "a"])
        assert order.count("a") == 3

    def test_no_capacity_skipping(self):
        # the fair-share winner doesn't fit -> nothing is admitted (no
        # starvation of large requests by small ones behind them)
        pol = WeightedFairPolicy()
        waiting = self._reqs([(0, "a"), (1, "b")])
        assert pol.select(waiting, lambda r: r.priority == 1) is None

    def test_positive_weights_enforced(self):
        with pytest.raises(ValueError, match="weight"):
            WeightedFairPolicy({0: 0.0})

    def test_rejoining_class_cannot_burst_through_missed_turns(self):
        # best-effort served once early, then idle while interactive builds
        # 20 turns of pass; on rejoin it must NOT win 20 consecutive turns
        pol = WeightedFairPolicy({0: 4.0, 2: 1.0})
        be = self._reqs([(2, "b")])
        assert pol.select(be, lambda r: True).priority == 2  # early turn
        inter = self._reqs([(0, "a")] * 20)
        for _ in range(20):
            req = pol.select(inter, lambda r: True)
            assert req.priority == 0
            inter.remove(req)
        mixed = self._reqs([(0, "a")] * 12 + [(2, "b")] * 12)
        picks = []
        for _ in range(10):
            req = pol.select(mixed, lambda r: True)
            picks.append(req.priority)
            mixed.remove(req)
        # rejoin is clamped to the incumbent's pass: the 4:1 share resumes
        # immediately instead of best-effort draining its stale credit
        assert picks.count(2) <= 3, picks
        assert picks[0] == 0 or picks[1] == 0, picks


# -- intake: typed errors + bounds + degradation ------------------------------

class TestIntake:
    def test_typed_intake_errors(self):
        from paddle_tpu.inference import (
            EmptyPromptError,
            IntakeError,
            InvalidTokenBudgetError,
            PromptTooLongError,
            RequestTooLongError,
            RequestUnservableError,
        )

        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(
            m, max_slots=2, block_size=4, num_blocks=2, prompt_bucket=8,
            max_model_len=16,
        )
        with pytest.raises(EmptyPromptError):
            eng.add_request(np.zeros((0,), np.int32))
        with pytest.raises(InvalidTokenBudgetError):
            eng.add_request(np.zeros((2,), np.int32), max_new_tokens=0)
        with pytest.raises(PromptTooLongError):
            eng.add_request(np.zeros((9,), np.int32))
        with pytest.raises(RequestTooLongError):
            eng.add_request(np.zeros((8,), np.int32), max_new_tokens=12)
        with pytest.raises(RequestUnservableError):
            eng.add_request(np.zeros((8,), np.int32), max_new_tokens=8)
        # every subclass is still a ValueError: pre-existing callers hold
        for exc in (EmptyPromptError, InvalidTokenBudgetError, PromptTooLongError,
                    RequestTooLongError, RequestUnservableError):
            assert issubclass(exc, IntakeError) and issubclass(exc, ValueError)

    def test_bounded_queue_rejects_with_retry_after(self, metrics_on):
        fe, eng, cfg = _frontend(seed=1, max_queue=2)
        rng = np.random.default_rng(1)
        fe.submit(_prompt(rng, cfg), max_new_tokens=3)
        fe.submit(_prompt(rng, cfg), max_new_tokens=3)
        with pytest.raises(Overloaded) as ei:
            fe.submit(_prompt(rng, cfg), max_new_tokens=3)
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after > 0
        assert metrics_on.get("serving_shed_total").value(reason="queue_full") == 1

    def test_shedding_rejects_best_effort_clamps_standard(self, metrics_on):
        # drive the controller to SHEDDING through real queue depth (the
        # gauge signal), then check all three per-class intake behaviors
        cfg_s = ServingConfig(
            max_queue=4,
            degrade_queue_frac=(0.25, 0.1),
            shed_queue_frac=(0.5, 0.25),
            degrade_max_new_tokens=2,
        )
        fe, eng, cfg = _frontend(seed=2, config=cfg_s)
        rng = np.random.default_rng(2)
        for _ in range(3):
            fe.submit(_prompt(rng, cfg), max_new_tokens=6)
        fe.pump()  # controller sees queue_frac >= 0.5 -> SHEDDING
        assert fe.controller.level == SHEDDING
        with pytest.raises(Overloaded) as ei:
            fe.submit(_prompt(rng, cfg), priority=Priority.BEST_EFFORT)
        assert ei.value.reason == "overload"
        assert metrics_on.get("serving_shed_total").value(reason="overload") == 1
        h_std = fe.submit(_prompt(rng, cfg), max_new_tokens=6,
                          priority=Priority.STANDARD)
        assert h_std.inner.max_new_tokens == 2 and h_std.degraded
        h_int = fe.submit(_prompt(rng, cfg), max_new_tokens=6,
                          priority=Priority.INTERACTIVE)
        assert h_int.inner.max_new_tokens == 6 and not h_int.degraded
        assert (
            metrics_on.get("serving_degraded_total").value(
                action="clamp_max_new_tokens"
            )
            == 1
        )
        _drain(fe, [h_std, h_int])

    def test_degraded_clamps_only_best_effort(self):
        cfg_s = ServingConfig(
            max_queue=8,
            degrade_queue_frac=(0.25, 0.1),
            shed_queue_frac=(0.9, 0.5),
            degrade_max_new_tokens=2,
        )
        fe, eng, cfg = _frontend(seed=3, config=cfg_s)
        rng = np.random.default_rng(3)
        for _ in range(3):
            fe.submit(_prompt(rng, cfg), max_new_tokens=6)
        fe.pump()
        assert fe.controller.level == DEGRADED
        h_be = fe.submit(_prompt(rng, cfg), max_new_tokens=6,
                         priority=Priority.BEST_EFFORT)
        h_std = fe.submit(_prompt(rng, cfg), max_new_tokens=6,
                          priority=Priority.STANDARD)
        assert h_be.inner.max_new_tokens == 2 and h_be.degraded
        assert h_std.inner.max_new_tokens == 6 and not h_std.degraded
        _drain(fe, [h_be, h_std])


# -- deadlines at every lifecycle stage ---------------------------------------

class TestDeadlines:
    def test_queued_expiry_sheds_before_prefill(self, metrics_on):
        fe, eng, cfg = _frontend(seed=4, max_queue=16)
        rng = np.random.default_rng(4)
        # one long request occupies both slots' worth of admissions slowly;
        # the TTL'd ones behind it expire while queued
        keeper = fe.submit(_prompt(rng, cfg), max_new_tokens=8)
        doomed = [
            fe.submit(_prompt(rng, cfg), max_new_tokens=4, ttl_s=1e-4)
            for _ in range(2)
        ]
        time.sleep(0.01)  # both TTLs are long gone
        prefills_before = eng.stats["admitted"]
        _drain(fe, [keeper] + doomed)
        for h in doomed:
            assert h.outcome == "deadline_queued"
            assert h.inner.admit_time is None  # never prefilled
            assert h.tokens() == []
        assert keeper.outcome == "ok"
        # no prefill was spent on the expired ones
        assert eng.stats["admitted"] == prefills_before + 1
        assert metrics_on.get("serving_deadline_miss_total").value(stage="queued") == 2
        assert metrics_on.get("serving_shed_total").value(reason="deadline_queued") == 2
        assert _drained(eng)

    def test_mid_decode_expiry_evicts_and_reclaims(self, metrics_on):
        fe, eng, cfg = _frontend(seed=5, max_queue=4)
        rng = np.random.default_rng(5)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=64, ttl_s=3600.0)
        fe.pump()  # admitted, first token out
        assert h.inner.admit_time is not None
        assert len(h.inner.generated) >= 1
        # force the expiry deterministically (no sleep-timing in CI)
        h.inner.deadline = time.perf_counter() - 1.0
        done = []
        while not h.finished:
            done += fe.pump()
        assert h.outcome == "deadline_decode"
        assert [d.id for d in done] == [h.id]
        assert 1 <= len(h.inner.generated) < 64  # evicted mid-generation
        assert metrics_on.get("serving_deadline_miss_total").value(stage="decode") == 1
        assert metrics_on.get("serving_shed_total").value(reason="deadline_decode") == 1
        assert _drained(eng)  # blocks reclaimed (cache retention is not a leak)

    def test_engine_level_deadline_without_frontend(self):
        # the engine enforces deadlines for direct users too
        m, cfg = _model(seed=6)
        eng = ContinuousBatchingEngine(m, max_slots=1, block_size=4, prompt_bucket=8)
        rng = np.random.default_rng(6)
        live = eng.add_request(_prompt(rng, cfg), max_new_tokens=2)
        dead = eng.add_request(
            _prompt(rng, cfg), max_new_tokens=2,
            deadline=time.perf_counter() - 1.0,
        )
        out = {}
        while eng.has_work():
            for r in eng.step():
                out[r.req_id] = r
        assert out[dead].finish_reason == "deadline" and out[dead].generated == []
        assert out[live].finish_reason == "length"

    def test_cancel_reclaims_mid_decode(self, metrics_on):
        fe, eng, cfg = _frontend(seed=7, max_queue=4)
        rng = np.random.default_rng(7)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=64)
        fe.pump()
        assert fe.cancel(h.id, reason="client_disconnect") is True
        assert h.outcome == "client_disconnect" and h.finished
        assert _drained(eng)
        assert metrics_on.get("serving_shed_total").value(reason="client_disconnect") == 1
        assert fe.cancel(h.id) is False  # already terminal: exactly once

    def test_cancel_never_touches_requests_the_frontend_does_not_own(self):
        # a direct engine user's request must survive a frontend id mix-up
        fe, eng, cfg = _frontend(seed=18, max_queue=4)
        rng = np.random.default_rng(18)
        direct = eng.add_request(_prompt(rng, cfg), max_new_tokens=3)
        assert fe.cancel(direct) is False
        # the direct request is untouched and still completes normally
        out = {}
        while eng.has_work():
            for r in eng.step():
                out[r.req_id] = r
        assert out[direct].finish_reason == "length"

    def test_tenant_metric_label_cardinality_is_bounded(self, metrics_on):
        cfg_s = ServingConfig(max_queue=64, max_tenant_labels=3)
        fe, eng, cfg = _frontend(seed=19, config=cfg_s)
        rng = np.random.default_rng(19)
        handles = [
            fe.submit(_prompt(rng, cfg), max_new_tokens=2, tenant=f"t{i}")
            for i in range(6)
        ]
        cells = metrics_on.get("serving_requests_total")._snapshot_values()
        tenants = {c["labels"]["tenant"] for c in cells}
        assert tenants == {"t0", "t1", "t2", "overflow"}
        overflow = [c for c in cells if c["labels"]["tenant"] == "overflow"]
        assert sum(c["value"] for c in overflow) == 3
        _drain(fe, handles)


# -- streaming + pump thread --------------------------------------------------

class TestStreaming:
    def test_stream_yields_all_tokens_in_order(self):
        fe, eng, cfg = _frontend(seed=8, max_queue=4)
        rng = np.random.default_rng(8)
        h = fe.submit(_prompt(rng, cfg, 5), max_new_tokens=6)
        fe.start()
        try:
            streamed = list(h.stream(timeout=30.0))
        finally:
            fe.stop()
        assert h.outcome == "ok"
        assert streamed == h.tokens() and len(streamed) == 6

    def test_transient_step_failure_does_not_brick_the_frontend(self):
        # engine.step()'s caller-retryable contract: a dispatch failure with
        # buffers intact rolls back and re-raises with the engine USABLE —
        # the pump thread must retry, not fail every live stream
        fe, eng, cfg = _frontend(seed=20, max_queue=4)
        rng = np.random.default_rng(20)
        real, tripped = eng._step_fn, []

        def flaky(*a, **k):
            if not tripped:
                tripped.append(1)
                raise RuntimeError("transient device failure")
            return real(*a, **k)

        eng._step_fn = flaky
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=4)
        fe.start()
        try:
            inner = h.result(timeout=30.0)
        finally:
            fe.stop()
        assert tripped and h.outcome == "ok"
        assert len(inner.generated) == 4
        fe.submit(_prompt(rng, cfg), max_new_tokens=2)  # still open for business

    def test_engine_permanent_failure_fails_streams_explicitly(self):
        fe, eng, cfg = _frontend(seed=9, max_queue=4, max_recoveries=0)
        rng = np.random.default_rng(9)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=8)
        plan = faults.FaultPlan.single("engine.decode", call_index=1)
        fe.start()
        try:
            with faults.inject(plan):
                inner = h.result(timeout=30.0)
        finally:
            fe.stop()
        assert h.outcome == "engine_failure"
        assert inner is h.inner
        # the frontend is now closed for business, loudly
        with pytest.raises(RuntimeError, match="build a new"):
            fe.submit(_prompt(rng, cfg))


# -- fault-injection sites ----------------------------------------------------

class TestServingFaultSites:
    def test_intake_site_fires_and_is_counted(self, metrics_on):
        fe, eng, cfg = _frontend(seed=10, max_queue=4)
        rng = np.random.default_rng(10)
        plan = faults.FaultPlan.single("serving.intake", call_index=1)
        with faults.inject(plan):
            fe.submit(_prompt(rng, cfg), max_new_tokens=2)  # call 0: clean
            with pytest.raises(faults.InjectedFault):
                fe.submit(_prompt(rng, cfg), max_new_tokens=2)  # call 1: boom
            assert faults.site_call_count("serving.intake") == 2
        assert metrics_on.get("faults_injected_total").value(site="serving.intake") == 1
        # the fault fired BEFORE any state change: nothing was queued for it
        assert eng.queue_depth() == 1

    def test_sites_are_zero_cost_when_no_plan_installed(self):
        # the cached-bool gate must be OFF and no counters accumulate when
        # no plan is installed — serving traffic pays one list read per site
        from paddle_tpu.testing.faults import _ACTIVE

        assert not _ACTIVE[0]
        fe, eng, cfg = _frontend(seed=11, max_queue=4)
        rng = np.random.default_rng(11)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=2)
        _drain(fe, [h])
        assert h.outcome == "ok"
        # no plan: sites do not even count calls
        assert faults.site_call_count("serving.intake") == 0
        assert faults.site_call_count("serving.respond") == 0

    def test_serving_sites_are_registered_for_campaigns(self):
        assert "serving.intake" in faults.KNOWN_SITES
        assert "serving.respond" in faults.KNOWN_SITES
        plan = faults.FaultPlan.sample(faults.KNOWN_SITES, 3, seed=5)
        assert faults.FaultPlan.parse(plan.spec()) == plan  # round-trips


# -- HTTP endpoint ------------------------------------------------------------

@pytest.fixture
def http_frontend():
    fe, eng, cfg = _frontend(seed=12, max_queue=4)
    srv = start_serving_server(fe, port=0)
    port = srv.server_address[1]
    yield fe, eng, cfg, port
    stop_serving_server(fe)


def _post(port, payload, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(
        "POST", "/v1/generate", json.dumps(payload),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    body = resp.read().decode()
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, body, headers


class TestServingHTTP:
    def test_streaming_generate(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        status, body, _ = _post(
            port, {"prompt": [1, 2, 3, 4], "max_new_tokens": 3,
                   "priority": "interactive", "tenant": "acme"}
        )
        assert status == 200
        lines = [json.loads(l) for l in body.strip().splitlines()]
        assert [set(l) for l in lines[:-1]] == [{"token"}] * 3
        assert lines[-1] == {"done": True, "outcome": "ok", "tokens": 3}

    def test_non_streaming_generate(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        status, body, _ = _post(
            port, {"prompt": [5, 6, 7], "max_new_tokens": 2, "stream": False}
        )
        assert status == 200
        rec = json.loads(body)
        assert rec["outcome"] == "ok" and rec["finish_reason"] == "length"
        assert len(rec["tokens"]) == 2

    def test_intake_validation_maps_to_400(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        status, body, _ = _post(port, {"prompt": list(range(99))})
        assert status == 400
        assert json.loads(body)["type"] == "PromptTooLongError"
        status, body, _ = _post(port, {"prompt": "not-a-list"})
        assert status == 400
        status, body, _ = _post(port, {"prompt": [1], "priority": "vip"})
        assert status == 400 and "priority" in json.loads(body)["error"]
        status, body, _ = _post(port, {"prompt": [1], "max_new_tokens": 0})
        assert status == 400
        assert json.loads(body)["type"] == "InvalidTokenBudgetError"

    def test_unknown_route_is_404(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/nope")
        assert conn.getresponse().status == 404
        conn.close()
        status, _, _ = _post(port, {"prompt": [1]}, timeout=10)
        assert status == 200  # sanity: the real route still works

    def test_healthz(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        snap = json.loads(resp.read().decode())
        conn.close()
        assert resp.status == 200
        assert snap["level"] in ("normal", "degraded", "shedding")
        assert snap["max_queue"] == 4

    def test_queue_full_maps_to_429_with_retry_after(self, http_frontend, metrics_on):
        fe, eng, cfg, port = http_frontend
        fe.stop()  # freeze the pump so the queue cannot drain
        rng = np.random.default_rng(12)
        for _ in range(4):
            fe.submit(_prompt(rng, cfg), max_new_tokens=2)
        status, body, headers = _post(port, {"prompt": [1, 2]})
        assert status == 429
        rec = json.loads(body)
        assert rec["reason"] == "queue_full" and rec["retry_after_s"] > 0
        assert float(headers["Retry-After"]) > 0
        assert metrics_on.get("serving_http_responses_total").value(code="429") == 1
        fe.start()  # let the fixture teardown drain cleanly

    def test_injected_respond_fault_evicts_the_request(self, http_frontend, metrics_on):
        # serving.respond with the DEFAULT InjectedFault (what a sampled
        # KNOWN_SITES campaign fires) modelling a torn client connection:
        # the handler must cancel the request so its slot + blocks return
        # to the pool, same as a real disconnect
        fe, eng, cfg, port = http_frontend
        plan = faults.FaultPlan.single("serving.respond", call_index=0)
        with faults.inject(plan):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request(
                "POST", "/v1/generate",
                json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 32}),
            )
            resp = conn.getresponse()
            resp.read()  # connection closes early; body is truncated
            conn.close()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if (
                metrics_on.get("serving_shed_total").value(reason="client_disconnect")
                == 1
                and _drained(eng)
            ):
                break
            time.sleep(0.02)
        assert metrics_on.get("serving_shed_total").value(reason="client_disconnect") == 1
        assert _drained(eng)

    def test_real_client_disconnect_never_leaks_pool_blocks(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        body = json.dumps({"prompt": [1, 2, 3, 4], "max_new_tokens": 64}).encode()
        s.sendall(
            b"POST /v1/generate HTTP/1.1\r\nHost: x\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        s.recv(256)  # read a little of the stream, then vanish
        s.close()
        # whether the request finished or was cancelled mid-stream, the pool
        # must drain back to full — a gone client cannot leak KV capacity
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            with fe._lock:
                if (
                    _drained(eng)
                    and not eng.has_work()
                ):
                    break
            time.sleep(0.02)
        assert _drained(eng)


# -- sustained-overload engine invariants (property-style churn) --------------

class TestOverloadChurnInvariants:
    def test_admit_evict_shed_churn_holds_invariants(self, metrics_on):
        """Seeded churn across every lifecycle transition — submit (mixed
        priorities/tenants, some with already-expired TTLs), pump, random
        cancels — asserting after EVERY operation: reservations never exceed
        the pool, the gauges equal engine truth, and at the end every
        accepted request reached a terminal state exactly once."""
        fe, eng, cfg = _frontend(
            seed=13, max_queue=6, max_slots=2, block_size=4,
            num_blocks=10, prompt_bucket=8, max_model_len=16,
        )
        rng = np.random.default_rng(13)
        reg = metrics_on
        accepted = {}
        terminal = {}
        rejected_at_intake = 0

        def check_invariants():
            s = eng.pool_stats()
            assert s["allocated"] + s["free"] == s["total"]
            assert int(eng._reserved.sum()) <= eng.num_blocks
            assert reg.get("engine_queue_depth").value() == eng.queue_depth()
            assert reg.get("engine_kv_blocks_allocated").value() == s["allocated"]
            assert reg.get("engine_kv_blocks_free").value() == s["free"]
            assert reg.get("serving_queue_depth").value() == eng.queue_depth()

        def note_done(handles):
            for h in handles:
                assert h.id not in terminal, "delivered twice"
                terminal[h.id] = h.outcome

        for step in range(120):
            op = rng.random()
            if op < 0.5:
                ttl = None if rng.random() < 0.6 else float(rng.choice([1e-5, 3600.0]))
                try:
                    h = fe.submit(
                        _prompt(rng, cfg, int(rng.integers(2, 7))),
                        max_new_tokens=int(rng.integers(2, 8)),
                        priority=int(rng.integers(0, 3)),
                        tenant=str(rng.choice(["a", "b", "c"])),
                        ttl_s=ttl,
                    )
                    accepted[h.id] = h
                except Overloaded:
                    rejected_at_intake += 1
            elif op < 0.85:
                note_done(fe.pump())
            else:
                live_ids = [i for i in accepted if i not in terminal]
                if live_ids:
                    rid = int(rng.choice(live_ids))
                    if fe.cancel(rid, reason="cancelled"):
                        assert accepted[rid].finished
                        terminal[rid] = accepted[rid].outcome
            check_invariants()

        while any(i not in terminal for i in accepted):
            note_done(fe.pump())
            check_invariants()

        # finished exactly once, at every lifecycle stage something was shed
        assert set(terminal) == set(accepted)
        outcomes = set(terminal.values())
        assert "ok" in outcomes
        assert "deadline_queued" in outcomes  # shed while queued
        assert "cancelled" in outcomes  # targeted eviction
        # the shed counter accounts every refusal AND every non-ok terminal
        shed_total = sum(
            v["value"]
            for v in reg.get("serving_shed_total")._snapshot_values()
        )
        non_ok = sum(1 for o in terminal.values() if o != "ok")
        assert shed_total == non_ok + rejected_at_intake
        assert _drained(eng)


# -- the overload acceptance test ---------------------------------------------

class TestOverloadAcceptance:
    def test_2x_overload_sheds_explicitly_and_keeps_one_compile(self, metrics_on):
        """ISSUE acceptance: arrivals at 2x the calibrated sustainable rate.
        The frontend must shed (Overloaded/429 paths) rather than grow the
        queue unboundedly, high-priority SLO attainment must not fall below
        best-effort's, every shed request must be accounted in
        ``serving_shed_total{reason}``, and the recompile watchdog must still
        report exactly 2 compiles for the engine."""
        fe, eng, cfg = _frontend(seed=14, max_queue=6)
        rng_seed = 14
        rate = measure_sustainable_rate(
            fe, 8, seed=rng_seed, prompt_len=(3, 7), max_new_tokens=(4, 10),
            vocab_size=cfg.vocab_size,
        )
        obs.GLOBAL_METRICS.reset()  # overload window accounting only
        mix = [
            TrafficClass("chat", Priority.INTERACTIVE, 1.0, (3, 7), (4, 10), 2.0),
            TrafficClass("batch", Priority.BEST_EFFORT, 1.0, (3, 7), (4, 10), 2.0),
        ]
        arrivals = poisson_arrivals(
            2.0 * rate, 48, mix, seed=rng_seed + 1, vocab_size=cfg.vocab_size
        )
        max_depth_seen = 0

        def bounded_queue(frontend):
            nonlocal max_depth_seen
            max_depth_seen = max(max_depth_seen, frontend.engine.queue_depth())
            assert frontend.engine.queue_depth() <= frontend.config.max_queue

        report = run_open_loop(fe, arrivals, max_wall_s=90.0, on_iteration=bounded_queue)
        assert report["undelivered_arrivals"] == 0, report

        inter = report["per_class"]["chat/interactive"]
        best = report["per_class"]["batch/best_effort"]
        total_refused = sum(
            c["rejected_at_intake"] + c["shed_after_accept"]
            for c in report["per_class"].values()
        )
        # 2x overload MUST shed: roughly half the offered work cannot finish
        assert total_refused > 0, report
        # ... explicitly, not by queue growth
        assert max_depth_seen <= fe.config.max_queue
        # priority classes actually mean something under load
        assert inter["slo_attainment"] >= best["slo_attainment"], report
        # every shed request is accounted in serving_shed_total{reason}
        shed_cells = {
            v["labels"]["reason"]: int(v["value"])
            for v in metrics_on.get("serving_shed_total")._snapshot_values()
        }
        assert sum(shed_cells.values()) == total_refused, (shed_cells, report)
        assert all(reason for reason in shed_cells)
        # the 2-compile honesty check: overload adds no compiles
        assert report["compiled_signatures_total"] == 1, report
        assert sum(report["compiles_during_run"].values()) == 0


# -- engine-level admission policy hook ---------------------------------------

class TestEngineAdmissionPolicy:
    def test_custom_policy_overrides_fifo_order(self):
        from paddle_tpu.inference import AdmissionPolicy

        class LIFO(AdmissionPolicy):
            def select(self, waiting, can_fit):
                for req in reversed(waiting):
                    if can_fit(req):
                        return req
                return None

        m, cfg = _model(seed=15)
        eng = ContinuousBatchingEngine(
            m, max_slots=1, block_size=4, prompt_bucket=8,
            admission_policy=LIFO(),
        )
        rng = np.random.default_rng(15)
        first = eng.add_request(_prompt(rng, cfg), max_new_tokens=2)
        last = eng.add_request(_prompt(rng, cfg), max_new_tokens=2)
        done = eng.step()  # one slot: LIFO admits the LAST submitted
        admitted_first = done[0].req_id if done else eng._slot_req[0].req_id
        assert admitted_first == last
        out = eng.run()
        assert set(list(out) + [d.req_id for d in done]) == {first, last}

    def test_buggy_policy_fails_loudly(self):
        from paddle_tpu.inference import AdmissionPolicy

        class Foreign(AdmissionPolicy):
            def select(self, waiting, can_fit):
                return InferenceRequest(999, np.zeros(2, np.int32), 2, None)

        m, cfg = _model(seed=16)
        eng = ContinuousBatchingEngine(
            m, max_slots=1, block_size=4, prompt_bucket=8,
            admission_policy=Foreign(),
        )
        rng = np.random.default_rng(16)
        eng.add_request(_prompt(rng, cfg), max_new_tokens=2)
        with pytest.raises(RuntimeError, match="not in the waiting queue"):
            eng.step()

    def test_cancel_request_queued_and_mid_decode(self):
        m, cfg = _model(seed=17)
        eng = ContinuousBatchingEngine(m, max_slots=1, block_size=4, prompt_bucket=8)
        rng = np.random.default_rng(17)
        running = eng.add_request(_prompt(rng, cfg), max_new_tokens=32)
        queued = eng.add_request(_prompt(rng, cfg), max_new_tokens=32)
        eng.step()
        got = eng.cancel_request(queued, reason="shed")
        assert got.req_id == queued and got.finish_reason == "shed"
        assert got.generated == []  # never admitted: no prefill spent
        got2 = eng.cancel_request(running, reason="shed")
        assert got2.req_id == running and len(got2.generated) >= 1
        assert _drained(eng)  # blocks reclaimed (cache retention is not a leak)
        assert eng.cancel_request(running) is None  # exactly once
        assert not eng.has_work()
        assert eng.run() == {}  # cancelled requests are NOT re-delivered
