"""Per-request distributed tracing + the always-on flight recorder (PR 8).

Pins the acceptance contract: a traced request through ``ServingFrontend``
yields a span tree whose queue → prefill → decode → stream phases are
properly nested under one root and sum to the observed end-to-end latency;
sampling is deterministic by seed; with ``FLAGS_trace_sample_rate=0`` the
per-request tracing surface is one cached-bool read and the recompile
watchdog still reports exactly 2 engine compiles; an injected permanent
engine failure produces a flight-recorder dump — redacted of prompt
content — readable by ``python -m paddle_tpu.observability.dump``.

Everything runs on CPU with the tiny Llama config, same as test_serving.py.
"""

import http.client
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import dump as dump_cli
from paddle_tpu.observability import flight_recorder as flightrec
from paddle_tpu.observability import tracing
from paddle_tpu.serving import (
    ServingConfig,
    ServingFrontend,
    start_serving_server,
    stop_serving_server,
)
from paddle_tpu.testing import faults


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _frontend(seed=0, max_queue=8, **engine_kw):
    m, cfg = _model(seed)
    engine_kw.setdefault("max_slots", 2)
    engine_kw.setdefault("block_size", 4)
    engine_kw.setdefault("prompt_bucket", 8)
    eng = ContinuousBatchingEngine(m, **engine_kw)
    fe = ServingFrontend(eng, ServingConfig(max_queue=max_queue))
    return fe, eng, cfg


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
def tracing_on():
    """Sample everything, deterministically, into a clean store."""
    prior = paddle.get_flags(["FLAGS_trace_sample_rate", "FLAGS_trace_seed"])
    paddle.set_flags({"FLAGS_trace_sample_rate": 1.0, "FLAGS_trace_seed": 1234})
    obs.GLOBAL_TRACER.clear()
    obs.GLOBAL_WATCHDOG.reset()
    yield obs.GLOBAL_TRACER
    paddle.set_flags(prior)
    obs.GLOBAL_TRACER.clear()


# -- traceparent + context ----------------------------------------------------

class TestTraceparent:
    def test_round_trip(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, None, sampled=True)
        header = tracing.format_traceparent(ctx)
        assert header == f"00-{'ab' * 16}-{'cd' * 8}-01"
        back = tracing.parse_traceparent(header)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.sampled is True

    def test_unsampled_flag(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, None, sampled=False)
        assert tracing.format_traceparent(ctx).endswith("-00")
        assert tracing.parse_traceparent(
            tracing.format_traceparent(ctx)
        ).sampled is False

    @pytest.mark.parametrize(
        "bad",
        [
            None,
            "",
            "garbage",
            "00-zz-cd-01",
            "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # all-zero trace id
            "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
            "00-" + "ab" * 16 + "-" + "cd" * 8,  # missing flags
        ],
    )
    def test_malformed_headers_ignored(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_incoming_header_pins_trace_id_and_sampling(self):
        t = tracing.Tracer(capacity=16, seed=1)
        hdr = f"00-{'ab' * 16}-{'cd' * 8}-01"
        ctx = t.start_trace(hdr, sample_rate=0.0)  # header overrides the coin
        assert ctx.trace_id == "ab" * 16
        assert ctx.parent_id == "cd" * 8
        assert ctx.span_id != "cd" * 8  # fresh root span for this hop
        assert ctx.sampled is True
        off = t.start_trace(f"00-{'ab' * 16}-{'cd' * 8}-00", sample_rate=1.0)
        assert off.sampled is False  # upstream said no; respect it


class TestSampling:
    def test_deterministic_by_seed(self):
        a = tracing.Tracer(capacity=16, seed=7)
        b = tracing.Tracer(capacity=16, seed=7)
        da = [a.start_trace(sample_rate=0.5) for _ in range(64)]
        db = [b.start_trace(sample_rate=0.5) for _ in range(64)]
        assert [c.sampled for c in da] == [c.sampled for c in db]
        assert [c.trace_id for c in da] == [c.trace_id for c in db]
        assert 0 < sum(c.sampled for c in da) < 64  # actually a coin
        c = tracing.Tracer(capacity=16, seed=8)
        dc = [c.start_trace(sample_rate=0.5) for _ in range(64)]
        assert [x.trace_id for x in dc] != [x.trace_id for x in da]

    def test_rate_bounds(self):
        t = tracing.Tracer(capacity=16, seed=3)
        assert not any(
            t.start_trace(sample_rate=0.0).sampled for _ in range(32)
        )
        assert all(t.start_trace(sample_rate=1.0).sampled for _ in range(32))

    def test_flag_seed_reseeds_global_tracer(self):
        prior = paddle.get_flags(["FLAGS_trace_seed"])
        try:
            paddle.set_flags({"FLAGS_trace_seed": 99})
            a = obs.GLOBAL_TRACER.start_trace(sample_rate=1.0)
            paddle.set_flags({"FLAGS_trace_seed": 99})
            b = obs.GLOBAL_TRACER.start_trace(sample_rate=1.0)
            assert a.trace_id == b.trace_id  # same seed -> same id stream
        finally:
            paddle.set_flags(prior)

    def test_partial_rate_does_not_flood_with_contextless_spans(self):
        """Collective wrappers have no request context to sample against:
        at a partial rate they must stay silent (tracing_full gate), or the
        ring would fill with unattributable spans and evict the sampled
        request trees the rate was chosen to capture."""
        from paddle_tpu.distributed import collective as coll

        prior = paddle.get_flags(["FLAGS_trace_sample_rate"])
        try:
            paddle.set_flags({"FLAGS_trace_sample_rate": 0.01})
            assert tracing.tracing_enabled() and not tracing.tracing_full()
            obs.GLOBAL_TRACER.clear()
            coll.barrier()
            assert [
                s for s in obs.GLOBAL_TRACER.spans()
                if s["name"].startswith("collective.")
            ] == []
            paddle.set_flags({"FLAGS_trace_sample_rate": 1.0})
            assert tracing.tracing_full()
            coll.barrier()
            assert [
                s["name"] for s in obs.GLOBAL_TRACER.spans()
                if s["name"].startswith("collective.")
            ] == ["collective.barrier"]
        finally:
            paddle.set_flags(prior)
            obs.GLOBAL_TRACER.clear()

    def test_env_seeding(self):
        from paddle_tpu.flags import FlagRegistry

        reg = FlagRegistry()
        reg.define("trace_sample_rate", float, 0.0, "")
        os.environ["FLAGS_trace_sample_rate"] = "0.25"
        try:
            assert reg.get("trace_sample_rate") == 0.25
        finally:
            del os.environ["FLAGS_trace_sample_rate"]


# -- span store ---------------------------------------------------------------

class TestSpanStore:
    def test_bounded_store_drops_oldest(self):
        t = tracing.Tracer(capacity=4, seed=0)
        for i in range(10):
            t.add_span(f"s{i}", start_s=0.0, end_s=1.0)
        names = [s["name"] for s in t.spans()]
        assert names == ["s6", "s7", "s8", "s9"]
        assert t.dropped == 6

    def test_span_context_manager_records_error_status(self):
        t = tracing.Tracer(capacity=16, seed=0)
        with pytest.raises(ValueError):
            with t.span("boom"):
                raise ValueError("x")
        (rec,) = t.spans()
        assert rec["status"] == "error:ValueError"

    def test_unsampled_parent_records_nothing(self):
        t = tracing.Tracer(capacity=16, seed=0)
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=False)
        with t.span("child", parent=ctx) as sp:
            sp.set_attr("k", 1)
        assert t.spans() == []
        t.add_event("e", ctx=ctx)  # unsampled events are dropped too
        assert t.records() == []

    def test_jsonl_export_and_cli_chrome_conversion(self, tmp_path):
        t = tracing.Tracer(capacity=16, seed=0)
        with t.span("parent") as sp:
            with t.span("child", parent=sp):
                pass
        p = tmp_path / "spans.jsonl"
        assert t.export_jsonl(str(p)) == 2
        lines = [json.loads(x) for x in p.read_text().splitlines()]
        assert {x["name"] for x in lines} == {"parent", "child"}
        out = tmp_path / "chrome.json"
        assert dump_cli.main([str(p), "--to-chrome", str(out)]) == 0
        trace = json.loads(out.read_text())
        assert len(trace["traceEvents"]) == 2
        assert all(ev["ph"] == "X" for ev in trace["traceEvents"])

    def test_profiler_export_merges_tracer_spans(self, tmp_path):
        import paddle_tpu.profiler as profiler

        obs.GLOBAL_TRACER.clear()
        obs.GLOBAL_TRACER.add_span("traced_phase", start_s=1.0, end_s=2.0)
        prof = profiler.Profiler()
        prof.start()
        prof.stop()
        path = tmp_path / "trace.json"
        prof.export(str(path))
        events = json.loads(path.read_text())["traceEvents"]
        assert any(ev["name"] == "traced_phase" for ev in events)
        # drained: a second export does not duplicate the span
        prof.export(str(path))
        events = json.loads(path.read_text())["traceEvents"]
        assert not any(ev["name"] == "traced_phase" for ev in events)


# -- the acceptance span tree -------------------------------------------------

class TestRequestSpanTree:
    PHASES = ("request.queue_wait", "request.prefill", "request.decode",
              "request.stream_out")

    def test_phases_nest_and_sum_to_e2e(self, tracing_on):
        fe, eng, cfg = _frontend(seed=1)
        rng = np.random.default_rng(1)
        handles = [
            fe.submit(_prompt(rng, cfg), max_new_tokens=4) for _ in range(3)
        ]
        _drain(fe, handles)
        assert all(h.outcome == "ok" for h in handles)
        for h in handles:
            tid = h.trace_ctx.trace_id
            spans = tracing_on.spans(tid)
            by_name = {s["name"]: s for s in spans}
            assert set(by_name) == {"request", *self.PHASES}
            root = by_name["request"]
            # every phase parented to the root, nested inside its interval
            for name in self.PHASES:
                s = by_name[name]
                assert s["parent_id"] == root["span_id"]
                assert s["ts_us"] >= root["ts_us"] - 1.0
                assert (
                    s["ts_us"] + s["dur_us"]
                    <= root["ts_us"] + root["dur_us"] + 1.0
                )
            # phases tile the root: their durations sum to the observed E2E
            phase_sum = sum(by_name[n]["dur_us"] for n in self.PHASES)
            assert phase_sum == pytest.approx(root["dur_us"], rel=1e-6, abs=5.0)
            # terminal outcome annotated on the root
            assert root["attrs"]["outcome"] == "ok"
            assert root["attrs"]["n_generated"] == 4
            assert by_name["request.decode"]["attrs"]["decode_steps"] >= 1

    def test_decode_steps_annotated_with_slot_membership(self, tracing_on):
        fe, eng, cfg = _frontend(seed=2)
        rng = np.random.default_rng(2)
        handles = [
            fe.submit(_prompt(rng, cfg), max_new_tokens=4) for _ in range(2)
        ]
        _drain(fe, handles)
        steps = [s for s in tracing_on.spans() if s["name"] == "engine.decode_step"]
        assert steps, "no batch-step spans recorded"
        ids = {h.id for h in handles}
        seen = set()
        for s in steps:
            assert set(s["attrs"]["slot_req_ids"].values()) <= ids
            assert s["attrs"]["n_active"] >= 1
            seen |= set(s["attrs"]["slot_req_ids"].values())
        assert seen == ids  # every request rode at least one annotated step
        # the per-request share is derived from the steps it rode: the sum
        # of all even splits equals the summed step durations
        share_total = sum(h.inner.decode_share_s for h in handles)
        step_total = sum(s["dur_us"] for s in steps) / 1e6
        assert share_total == pytest.approx(step_total, rel=1e-6)

    def test_engine_compiles_stay_at_one_with_tracing_on(self, tracing_on):
        fe, eng, cfg = _frontend(seed=3)
        rng = np.random.default_rng(3)
        handles = [
            fe.submit(_prompt(rng, cfg, n=3 + (i % 3)), max_new_tokens=3)
            for i in range(5)
        ]
        _drain(fe, handles)
        counts = obs.GLOBAL_WATCHDOG.counts()
        assert counts.get("ContinuousBatchingEngine.step") == 1

    def test_intake_rejection_still_gets_a_terminal_root_span(self, tracing_on):
        from paddle_tpu.serving import Overloaded

        fe, eng, cfg = _frontend(seed=7, max_queue=1)
        rng = np.random.default_rng(7)
        fe.submit(_prompt(rng, cfg), max_new_tokens=4)  # fills the queue
        with pytest.raises(Overloaded):
            fe.submit(_prompt(rng, cfg), max_new_tokens=4)
        sheds = [
            s for s in tracing_on.spans()
            if s["name"] == "request" and s["status"] == "shed:queue_full"
        ]
        assert len(sheds) == 1
        assert sheds[0]["attrs"]["outcome"] == "queue_full"

    def test_shed_request_still_gets_a_terminal_span_tree(self, tracing_on):
        fe, eng, cfg = _frontend(seed=4)
        rng = np.random.default_rng(4)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=64)
        assert fe.cancel(h.id)
        spans = tracing_on.spans(h.trace_ctx.trace_id)
        by_name = {s["name"]: s for s in spans}
        root = by_name["request"]
        assert root["attrs"]["outcome"] == "cancelled"
        assert root["status"] == "shed:cancelled"
        # never admitted: queue_wait + stream_out only, still tiling E2E
        assert "request.prefill" not in by_name
        phase_sum = sum(
            s["dur_us"] for n, s in by_name.items() if n != "request"
        )
        assert phase_sum == pytest.approx(root["dur_us"], rel=1e-6, abs=5.0)


class TestTracingOffPath:
    def test_off_path_is_one_cached_bool_read(self):
        assert paddle.get_flags(["FLAGS_trace_sample_rate"])[
            "FLAGS_trace_sample_rate"
        ] == 0.0
        assert not tracing.tracing_enabled()
        obs.GLOBAL_TRACER.clear()
        rng_state_before = obs.GLOBAL_TRACER._rng.getstate()
        fe, eng, cfg = _frontend(seed=5)
        rng = np.random.default_rng(5)
        h = fe.submit(_prompt(rng, cfg), max_new_tokens=4)
        _drain(fe, [h])
        assert h.outcome == "ok"
        # no context, no ids drawn, no spans stored, no shares accumulated:
        # the entire tracing surface of the request was the cached-bool gate
        assert h.trace_ctx is None
        assert h.traceparent is None
        assert h.inner.trace is None
        assert h.inner.decode_steps == 0 and h.inner.decode_share_s == 0.0
        assert obs.GLOBAL_TRACER.records() == []
        assert obs.GLOBAL_TRACER._rng.getstate() == rng_state_before

    def test_watchdog_still_reports_one_compile_with_rate_zero(self):
        obs.GLOBAL_WATCHDOG.reset()
        fe, eng, cfg = _frontend(seed=6)
        rng = np.random.default_rng(6)
        hs = [fe.submit(_prompt(rng, cfg), max_new_tokens=3) for _ in range(3)]
        _drain(fe, hs)
        counts = obs.GLOBAL_WATCHDOG.counts()
        assert counts.get("ContinuousBatchingEngine.step") == 1


# -- HTTP propagation ---------------------------------------------------------

@pytest.fixture
def http_frontend():
    fe, eng, cfg = _frontend(seed=12, max_queue=4)
    srv = start_serving_server(fe, port=0)
    port = srv.server_address[1]
    yield fe, eng, cfg, port
    stop_serving_server(fe)


def _post(port, payload, headers=None, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", "/v1/generate", json.dumps(payload), hdrs)
    resp = conn.getresponse()
    body = resp.read().decode()
    out_headers = dict(resp.getheaders())
    conn.close()
    return resp.status, body, out_headers


class TestHTTPTraceparent:
    def test_round_trip_through_the_endpoint(self, http_frontend, tracing_on):
        fe, eng, cfg, port = http_frontend
        rng = np.random.default_rng(0)
        upstream_trace = "ab" * 16
        upstream_span = "cd" * 8
        status, body, headers = _post(
            port,
            {"prompt": _prompt(rng, cfg).tolist(), "max_new_tokens": 3},
            headers={"traceparent": f"00-{upstream_trace}-{upstream_span}-01"},
        )
        assert status == 200
        # the response names the request's root span INSIDE the caller's trace
        tp = headers.get("traceparent")
        assert tp is not None
        ctx = tracing.parse_traceparent(tp)
        assert ctx.trace_id == upstream_trace
        assert ctx.span_id != upstream_span
        assert ctx.sampled is True
        # the recorded root span parents to the upstream hop's span
        spans = tracing_on.spans(upstream_trace)
        root = [s for s in spans if s["name"] == "request"][0]
        assert root["parent_id"] == upstream_span
        assert root["span_id"] == ctx.span_id
        assert {"request.queue_wait", "request.prefill", "request.decode",
                "request.stream_out"} <= {s["name"] for s in spans}

    def test_no_header_with_tracing_off_means_no_trace(self, http_frontend):
        fe, eng, cfg, port = http_frontend
        rng = np.random.default_rng(1)
        status, body, headers = _post(
            port, {"prompt": _prompt(rng, cfg).tolist(), "max_new_tokens": 2}
        )
        assert status == 200
        assert "traceparent" not in {k.lower() for k in headers}


# -- flight recorder ----------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = flightrec.FlightRecorder(capacity=8)
        for i in range(50):
            fr.record("tick", i=i)
        events = fr.snapshot()
        assert len(events) == 8
        assert [e["i"] for e in events] == list(range(42, 50))

    def test_dump_redacts_prompt_content(self, tmp_path):
        fr = flightrec.FlightRecorder(capacity=8)
        fr.record("admit", req_id=1, prompt=[5, 6, 7], prompt_len=3)
        fr.record("nested", payload={"tokens": [1, 2], "reason": "ok"})
        path = fr.dump("unit", path=str(tmp_path / "d.json"),
                       extra={"prompt": "secret text"})
        data = json.loads((tmp_path / "d.json").read_text())
        text = json.dumps(data)
        assert "secret text" not in text
        assert "[5, 6, 7]" not in text
        ev = data["events"][0]
        assert ev["prompt"] == "<redacted:3>"
        assert ev["prompt_len"] == 3  # sizes survive, content does not
        assert data["events"][1]["payload"]["tokens"] == "<redacted:2>"
        assert data["extra"]["prompt"].startswith("<redacted:")
        assert path == str(tmp_path / "d.json")

    def test_safe_dump_swallows_injected_export_fault(self, tmp_path):
        fr = flightrec.FlightRecorder(capacity=8)
        fr.record("tick")
        plan = faults.FaultPlan(
            [faults.FaultTrigger("tracing.export", 0),
             faults.FaultTrigger("tracing.export", 1)]
        )
        with faults.inject(plan):
            assert fr.safe_dump("unit", path=str(tmp_path / "x.json")) is None
            with pytest.raises(faults.InjectedFault):
                fr.dump("unit", path=str(tmp_path / "y.json"))
        assert not (tmp_path / "x.json").exists()

    def test_export_site_registered_and_zero_cost_when_empty(self):
        assert "tracing.export" in faults.KNOWN_SITES
        from paddle_tpu.testing.faults import _ACTIVE

        assert not _ACTIVE[0]
        flightrec.GLOBAL_FLIGHT_RECORDER.record("tick")
        # no plan installed: the site does not even count calls
        t = tracing.Tracer(capacity=4, seed=0)
        t.add_span("s", start_s=0.0, end_s=1.0)
        assert faults.site_call_count("tracing.export") == 0

    def test_cli_exit_codes(self, tmp_path):
        assert dump_cli.main([str(tmp_path / "missing.json")]) == 2
        empty = tmp_path / "empty.json"
        empty.write_text("")
        assert dump_cli.main([str(empty)]) == 2
        corrupt = tmp_path / "corrupt.json"
        corrupt.write_text("{not json")
        assert dump_cli.main([str(corrupt)]) == 2
        # a JSON file that is neither a flight dump nor span records
        wrong = tmp_path / "wrong.jsonl"
        wrong.write_text('{"foo": 1}\n')
        assert dump_cli.main([str(wrong)]) == 2

    def test_cli_survives_cyclic_parent_chain(self, tmp_path):
        # a corrupt/hand-edited JSONL with a self-parenting span must not
        # hang the postmortem script
        p = tmp_path / "cyclic.jsonl"
        p.write_text(
            json.dumps({"kind": "span", "name": "a", "trace_id": "t",
                        "span_id": "s1", "parent_id": "s2", "ts_us": 0.0,
                        "dur_us": 1.0, "attrs": {}}) + "\n"
            + json.dumps({"kind": "span", "name": "b", "trace_id": "t",
                          "span_id": "s2", "parent_id": "s1", "ts_us": 0.0,
                          "dur_us": 1.0, "attrs": {}}) + "\n"
        )
        assert dump_cli.main([str(p)]) == 0  # terminates

    def test_cli_module_entrypoint(self, tmp_path):
        import subprocess
        import sys

        fr = flightrec.FlightRecorder(capacity=4)
        fr.record("admit", req_id=7)
        path = str(tmp_path / "dump.json")
        fr.dump("unit", path=path)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability.dump", path],
            capture_output=True, text=True, env=env,
        )
        assert r.returncode == 0, r.stderr
        assert "reason: unit" in r.stdout
        assert "admit" in r.stdout


class TestBlackBoxOnPermanentFailure:
    def _tiny_engine(self, seed=0, **kw):
        m, cfg = _model(seed)
        kw.setdefault("max_slots", 2)
        kw.setdefault("block_size", 4)
        kw.setdefault("prompt_bucket", 16)
        return m, cfg, ContinuousBatchingEngine(m, **kw)

    def test_dump_emitted_with_failed_requests_timeline(self, tmp_path):
        prior = paddle.get_flags(["FLAGS_flight_recorder_dir"])
        paddle.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
        try:
            obs.GLOBAL_FLIGHT_RECORDER.clear()
            m, cfg, eng = self._tiny_engine(seed=23, max_recoveries=1)
            rng = np.random.default_rng(23)
            rid = eng.add_request(_prompt(rng, cfg), max_new_tokens=4)
            plan = faults.FaultPlan(
                [faults.FaultTrigger("engine.decode", i) for i in range(8)]
            )
            with faults.inject(plan):
                with pytest.raises(faults.InjectedFault):
                    eng.run()
            assert eng.broken
            dumps = sorted(tmp_path.glob("flightrec_*engine_permanent_failure*"))
            assert dumps, "permanent failure produced no flight-recorder dump"
            data = json.loads(dumps[-1].read_text())
            assert data["reason"] == "engine_permanent_failure"
            kinds = [e["kind"] for e in data["events"]]
            # the failed request's lifecycle is in the black box: its admit,
            # the injected faults, the recovery attempt, the death
            assert "admit" in kinds
            assert "fault_injected" in kinds
            assert "recovery" in kinds
            assert "engine_permanent_failure" in kinds
            admits = [e for e in data["events"] if e["kind"] == "admit"]
            assert any(e["req_id"] == rid for e in admits)
            # redaction: no prompt token content anywhere in the dump —
            # any denylisted key that made it in is a length-only marker
            for e in data["events"]:
                for key in ("prompt", "prompt_ids", "tokens", "generated"):
                    if key in e:
                        assert str(e[key]).startswith("<redacted"), e
            # the dump is readable by the CLI
            assert dump_cli.main([str(dumps[-1])]) == 0
        finally:
            paddle.set_flags(prior)

    def test_pump_death_dumps_and_survives_injected_export_fault(self, tmp_path):
        """The serving pump thread dying is the third dump seam — and an
        injected tracing.export fault during THAT dump must not change the
        failure handling (streams still fail explicitly)."""
        prior = paddle.get_flags(["FLAGS_flight_recorder_dir"])
        paddle.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
        try:
            obs.GLOBAL_FLIGHT_RECORDER.clear()
            fe, eng, cfg = _frontend(seed=30)
            rng = np.random.default_rng(30)
            h = fe.submit(_prompt(rng, cfg), max_new_tokens=32)
            fe.pump()  # admit
            # every dump attempt fails at the export site; the pump death
            # path must still fail all live streams explicitly
            plan = faults.FaultPlan(
                [faults.FaultTrigger("tracing.export", i) for i in range(4)]
            )
            with faults.inject(plan):
                fe._fail_all("unit: simulated pump death")
            assert h.finished and h.outcome == "engine_failure"
            assert not list(tmp_path.glob("flightrec_*"))  # dump failed, softly
            # without the fault the same seam produces a readable dump
            fe2, eng2, cfg2 = _frontend(seed=31)
            h2 = fe2.submit(_prompt(rng, cfg2), max_new_tokens=32)
            fe2.pump()
            fe2._fail_all("unit: simulated pump death")
            dumps = sorted(tmp_path.glob("flightrec_*serving_pump_death*"))
            assert dumps
            data = json.loads(dumps[-1].read_text())
            assert any(e["kind"] == "pump_death" for e in data["events"])
            assert dump_cli.main([str(dumps[-1])]) == 0
        finally:
            paddle.set_flags(prior)


# -- PR 23: phases inside the serving step, always-on stall accounting --------
ENGINE_PHASES = ("plan", "launch", "wait", "commit")
PHASE_KEYS = tuple(f"phase_s.{p}" for p in ENGINE_PHASES + ("deliver",))


def _phase_frontend(seed=31, n_requests=2, **engine_kw):
    """A frontend whose one compile is behind it, with work to pump."""
    fe, eng, cfg = _frontend(seed=seed, **engine_kw)
    rng = np.random.default_rng(seed)
    warm = fe.submit(_prompt(rng, cfg), max_new_tokens=2)
    _drain(fe, [warm])
    handles = [fe.submit(_prompt(rng, cfg, 6), max_new_tokens=120) for _ in range(n_requests)]
    return fe, eng, handles


class TestStepPhases:
    def test_phase_is_an_annotation_a_counter_and_at_full_rate_a_span(self, tracing_on):
        sink = {"k": 0.0}
        with tracing.phase("outer") as outer:
            with tracing.phase("inner", sink, "k", step=7, start_s=outer.start_s) as inner:
                pass
        assert inner.start_s == outer.start_s and inner.end_s <= outer.end_s
        assert sink["k"] == pytest.approx(inner.end_s - inner.start_s)
        by_name = {s["name"]: s for s in tracing_on.spans()}
        assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
        assert by_name["inner"]["trace_id"] == by_name["outer"]["trace_id"]
        assert by_name["inner"]["attrs"] == {"step": 7}
        assert by_name["outer"]["parent_id"] is None

    def test_phases_tile_the_pump(self):
        import time

        fe, eng, _handles = _phase_frontend()
        before = dict(eng.stats)
        walls = []
        for _ in range(20):
            t0 = time.perf_counter()
            fe.pump()
            walls.append(time.perf_counter() - t0)
        grown = {k: eng.stats[k] - before[k] for k in PHASE_KEYS}
        assert all(v > 0 for v in grown.values()), grown
        # the five counters tile the pumps: nothing of a pump lies outside them
        assert sum(grown.values()) == pytest.approx(sum(walls), rel=0.02)
        assert sum(grown.values()) <= sum(walls)

    def test_phases_nest_in_the_ring(self, tracing_on):
        fe, eng, _handles = _phase_frontend()
        tracing_on.clear()
        before = dict(eng.stats)
        for _ in range(20):
            fe.pump()
        grown = {k: eng.stats[k] - before[k] for k in PHASE_KEYS}
        spans = tracing_on.spans()
        by_id = {s["span_id"]: s for s in spans}
        parent_of = {
            "engine.plan": "engine.decode_step", "engine.launch": "engine.decode_step",
            "engine.wait": "engine.decode_step", "engine.commit": "engine.decode_step",
            "engine.decode_step": "frontend.pump", "frontend.deliver": "frontend.pump",
        }
        counts = {}
        for s in spans:
            counts[s["name"]] = counts.get(s["name"], 0) + 1
            if s["name"] in parent_of:
                parent = by_id[s["parent_id"]]
                assert parent["name"] == parent_of[s["name"]], (s["name"], parent["name"])
                assert parent["ts_us"] <= s["ts_us"] + 1e-3
                assert s["ts_us"] + s["dur_us"] <= parent["ts_us"] + parent["dur_us"] + 1e-3
                if s["name"].startswith("engine."):
                    assert s["attrs"]["step"] == parent["attrs"].get("step", s["attrs"]["step"])
        assert counts["frontend.pump"] == 20 and counts["engine.decode_step"] == 20
        # stored pumps and counters are the same instants: they agree exactly
        pumps_s = sum(s["dur_us"] for s in spans if s["name"] == "frontend.pump") / 1e6
        assert sum(grown.values()) == pytest.approx(pumps_s, rel=1e-6)
        assert counts["frontend.deliver"] == 40  # two stretches a pump, one counter
        for p in ENGINE_PHASES:
            assert counts[f"engine.{p}"] == 20
        # the decode step takes its instants from its children
        for s in spans:
            if s["name"] == "engine.decode_step":
                kids = sorted((k for k in spans if k["parent_id"] == s["span_id"]), key=lambda k: k["ts_us"])
                assert [k["name"] for k in kids] == [f"engine.{p}" for p in ENGINE_PHASES]
                assert kids[0]["ts_us"] == s["ts_us"]
                assert kids[-1]["ts_us"] + kids[-1]["dur_us"] == pytest.approx(s["ts_us"] + s["dur_us"], abs=1e-3)
                for a, b in zip(kids, kids[1:]):  # consecutive phases share an instant
                    assert a["ts_us"] + a["dur_us"] == pytest.approx(b["ts_us"], abs=1e-3)

    def test_rate_zero_adds_nothing_to_the_ring_and_counters_still_grow(self):
        assert not tracing.tracing_enabled()
        fe, eng, _handles = _phase_frontend(seed=32)
        obs.GLOBAL_TRACER.clear()
        before = dict(eng.stats)
        for _ in range(100):
            fe.pump()
        assert eng.stats["steps"] - before["steps"] == 100
        assert obs.GLOBAL_TRACER.records() == []
        assert all(eng.stats[k] > before[k] for k in PHASE_KEYS)

    def test_bare_engine_counts_its_four_phases_and_no_delivery(self):
        m, cfg = _model(33)
        eng = ContinuousBatchingEngine(m, max_slots=2, block_size=4, prompt_bucket=8)
        eng.add_request(_prompt(np.random.default_rng(33), cfg), max_new_tokens=6)
        eng.run()
        assert all(eng.stats[f"phase_s.{p}"] > 0 for p in ENGINE_PHASES)
        assert eng.stats["phase_s.deliver"] == 0.0 and eng.stats["stall_steps"] == 0


class TestStallAccounting:
    BASE_S, STALL_S = 0.004, 0.25

    def _slowed(self, eng, stall_at):
        """Every plan pays BASE_S in ``_dense_tables`` (so that the running
        median is far above the suite's scheduling noise); call number
        ``stall_at`` pays STALL_S more: a sleep, so wall and not CPU."""
        import time

        real, calls = eng._dense_tables, [0]

        def slow():
            calls[0] += 1
            time.sleep(self.BASE_S + (self.STALL_S if calls[0] == stall_at else 0.0))
            return real()

        eng._dense_tables = slow
        return calls

    def test_one_sleep_in_plan_is_one_host_stall(self):
        fe, eng, _handles = _phase_frontend(seed=34)
        flight = flightrec.FlightRecorder(capacity=512)
        eng._flight = flight
        self._slowed(eng, stall_at=40)
        for _ in range(60):
            fe.pump()
        events = [e for e in flight.snapshot() if e["kind"] == "step_stall"]
        assert len(events) == 1 and eng.stats["stall_steps"] == 1
        ev = events[0]
        assert eng.stats["stall_s.host"] == pytest.approx(self.STALL_S, rel=0.2)
        assert eng.stats["stall_s.device"] == pytest.approx(0.0, abs=0.02)
        assert ev["plan_s"] > self.STALL_S and ev["stall_host_s"] == pytest.approx(eng.stats["stall_s.host"], abs=1e-5)
        assert ev["wall_s"] > 5 * ev["median_wall_s"]
        # asleep, not computing: over the stretch since the previous step closed the
        # thread's CPU seconds are far below the wall seconds
        assert ev["since_close_s"] >= ev["wall_s"]
        assert ev["cpu_s"] < 0.2 * ev["since_close_s"]
        for key in ("launch_s", "wait_s", "commit_s", "deliver_s", "step"):
            assert key in ev
        # and the dump CLI's reader shows it as any other flight event
        assert "step_stall" in json.dumps(flight.snapshot())

    def test_a_busy_plan_reads_as_cpu_not_as_descheduled(self):
        import time

        fe, eng, _handles = _phase_frontend(seed=35)
        flight = flightrec.FlightRecorder(capacity=512)
        eng._flight = flight
        calls = self._slowed(eng, stall_at=-1)
        real = eng._dense_tables

        def spin():
            if calls[0] == 39:  # the 40th plan burns CPU instead of sleeping
                until = time.perf_counter() + self.STALL_S
                while time.perf_counter() < until:
                    pass
            return real()

        eng._dense_tables = spin
        for _ in range(60):
            fe.pump()
        (ev,) = [e for e in flight.snapshot() if e["kind"] == "step_stall"]
        assert ev["cpu_s"] > 0.5 * self.STALL_S and ev["cpu_s"] > 0.5 * ev["since_close_s"]

    def test_a_recovery_is_not_a_stall(self):
        fe, eng, _handles = _phase_frontend(seed=36, max_recoveries=2, recovery_backoff=0.05)
        flight = flightrec.FlightRecorder(capacity=512)
        eng._flight = flight
        self._slowed(eng, stall_at=-1)
        for _ in range(30):
            fe.pump()
        before = eng.stats["steps"]
        with faults.inject(faults.FaultPlan([faults.FaultTrigger("engine.decode", 0)])):
            fe.pump()  # dispatch dies, backoff sleeps, recover() replays, retry succeeds
        assert eng.stats["recoveries"] == 1 and eng.stats["steps"] == before + 1
        for _ in range(10):
            fe.pump()
        assert eng.stats["stall_steps"] == 0
        assert not [e for e in flight.snapshot() if e["kind"] == "step_stall"]
        assert any(e["kind"] == "recovery" for e in flight.snapshot())

    def test_recovery_dispatches_run_under_engine_recover_not_step_phases(self, tracing_on):
        fe, eng, _handles = _phase_frontend(seed=37, max_recoveries=2, recovery_backoff=0.0)
        for _ in range(3):
            fe.pump()
        tracing_on.clear()
        launch_before = eng.stats["phase_s.launch"]
        with faults.inject(faults.FaultPlan([faults.FaultTrigger("engine.decode", 0)])):
            fe.pump()
        names = [s["name"] for s in tracing_on.spans()]
        assert names.count("engine.recover") == 1
        # the replay dispatched several times; only the retried step launched under a phase
        assert names.count("engine.launch") == 1
        assert eng.stats["phase_s.launch"] > launch_before


# -- PR 38: engine.launch and engine.wait tiled into sub-phases ----------------
SUBPHASES = {
    "launch": ("launch_put", "launch_args", "launch_call"),
    "wait": ("wait_ready", "wait_fetch"),
}
SUBPHASE_SPANS = {
    "engine.launch": ("engine.launch.put", "engine.launch.args", "engine.launch.call"),
    "engine.wait": ("engine.wait.ready", "engine.wait.fetch"),
}
# the stall record's name for each sub-phase, and for its parent
STALL_FIELDS = {
    "launch_s": ("put_s", "args_s", "call_s"),
    "wait_s": ("ready_s", "fetch_s"),
}


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` while "a profile is
    taken": says it is enabled, and logs every enter and exit by name."""

    log = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


class TestSubPhases:
    @pytest.mark.parametrize("parent", sorted(SUBPHASES))
    def test_subphase_counters_tile_their_parent(self, parent):
        fe, eng, _handles = _phase_frontend(seed=41)
        keys = [f"subphase_s.{p}" for p in SUBPHASES[parent]]
        before = dict(eng.stats)
        for _ in range(40):
            fe.pump()
        assert eng.stats["steps"] - before["steps"] == 40
        grown = {k: eng.stats[k] - before[k] for k in keys}
        assert all(v > 0 for v in grown.values()), grown
        # children take their instants from each other and from the parent
        assert sum(grown.values()) == pytest.approx(
            eng.stats[f"phase_s.{parent}"] - before[f"phase_s.{parent}"], rel=1e-9
        )
        # and no child is a phase of the pump: the five phase_s keys are all there are
        assert sorted(k for k in eng.stats if k.startswith("phase_s.")) == sorted(PHASE_KEYS)

    def test_the_five_phase_keys_still_tile_the_pump(self):
        import time

        fe, eng, _handles = _phase_frontend(seed=42)
        before = dict(eng.stats)
        walls = []
        for _ in range(30):
            t0 = time.perf_counter()
            fe.pump()
            walls.append(time.perf_counter() - t0)
        phases = sum(eng.stats[k] - before[k] for k in PHASE_KEYS)
        subs = sum(eng.stats[k] - before[k] for k in eng.stats if k.startswith("subphase_s."))
        assert phases == pytest.approx(sum(walls), rel=0.02) and phases <= sum(walls)
        # the sub-phases cover launch and wait once more: counted beside the
        # phases they would tile the pump twice over
        assert subs == pytest.approx(
            sum(eng.stats[f"phase_s.{p}"] - before[f"phase_s.{p}"] for p in SUBPHASES), rel=1e-9
        )

    def test_under_a_profile_the_annotations_nest_in_their_parents_in_order(self, monkeypatch):
        fe, eng, _handles = _phase_frontend(seed=43)
        log = _RecordingAnnotation.log = []
        monkeypatch.setattr(tracing, "_TraceAnnotation", _RecordingAnnotation)
        for _ in range(3):
            fe.pump()
        monkeypatch.undo()
        prefix = tracing.PHASE_PREFIX
        want = []
        for parent, kids in SUBPHASE_SPANS.items():
            want.append(("enter", prefix + parent))
            for kid in kids:
                want += [("enter", prefix + kid), ("exit", prefix + kid)]
            want.append(("exit", prefix + parent))
        mine = [e for e in log if e[1].startswith((prefix + "engine.launch", prefix + "engine.wait"))]
        assert mine == want * 3
        # and the whole log is well nested: every exit closes the innermost open annotation
        stack = []
        for what, name in log:
            if what == "enter":
                stack.append(name)
            else:
                assert stack.pop() == name
        assert not stack

    def test_subphase_spans_are_children_of_their_parents_span(self, tracing_on):
        fe, eng, _handles = _phase_frontend(seed=44)
        tracing_on.clear()
        for _ in range(10):
            fe.pump()
        spans = tracing_on.spans()
        counts = {}
        for s in spans:
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        assert counts["frontend.pump"] == 10
        assert len(spans) == 13 * 10  # a pump is thirteen records of the ring now
        for parent, names in SUBPHASE_SPANS.items():
            parents = [s for s in spans if s["name"] == parent]
            assert len(parents) == 10
            for p in parents:
                kids = sorted((k for k in spans if k["parent_id"] == p["span_id"]), key=lambda k: k["ts_us"])
                assert [k["name"] for k in kids] == list(names)
                assert all(k["trace_id"] == p["trace_id"] and k["attrs"]["step"] == p["attrs"]["step"] for k in kids)
                assert kids[0]["ts_us"] == p["ts_us"]
                assert kids[-1]["ts_us"] + kids[-1]["dur_us"] == pytest.approx(p["ts_us"] + p["dur_us"], abs=1e-3)
                for a, b in zip(kids, kids[1:]):  # consecutive sub-phases share an instant
                    assert a["ts_us"] + a["dur_us"] == pytest.approx(b["ts_us"], abs=1e-3)

    # which call ends the sub-phase that the patched clock stretches, and
    # whose stall that is
    ENDS = {
        "put_s": ("engine.launch.args", "host"), "args_s": ("engine.launch.call", "host"),
        "call_s": ("engine.wait", "host"), "ready_s": ("engine.wait.fetch", "device"),
        "fetch_s": ("engine.commit", "device"),
    }

    @pytest.mark.parametrize("field", sorted(ENDS))
    def test_a_slow_subphase_is_one_stall_whose_parts_sum_to_launch_and_wait(self, field, monkeypatch, tmp_path, capsys):
        import time

        stall_s, stall_at = 0.25, 40
        ended_by, side = self.ENDS[field]
        fe, eng, _handles = _phase_frontend(seed=45)
        flight = flightrec.FlightRecorder(capacity=512)
        eng._flight = flight
        base = TestStallAccounting()
        base.BASE_S = 0.010  # a running median (so a stall limit, 5 x) far above the suite's noise
        base._slowed(eng, stall_at=-1)
        # the process's clock (the phases', the frontend's), with a jump of
        # stall_s inside ONE sub-phase of step stall_at
        ahead, real_clock = [0.0], time.perf_counter
        monkeypatch.setattr(time, "perf_counter", lambda: real_clock() + ahead[0])
        calls = [0]
        for mover in ("_next_phase", "_next_subphase"):
            real = getattr(eng, mover)

            def moved_on(name, key, real=real):
                if name == ended_by:
                    calls[0] += 1
                    if calls[0] == stall_at:
                        ahead[0] += stall_s
                real(name, key)

            setattr(eng, mover, moved_on)
        for _ in range(60):
            fe.pump()
        (ev,) = [e for e in flight.snapshot() if e["kind"] == "step_stall"]
        assert eng.stats["stall_steps"] == 1
        assert stall_s <= ev[field] < stall_s + 0.1
        for parent, parts in STALL_FIELDS.items():  # each rounded to a microsecond
            assert sum(ev[p] for p in parts) == pytest.approx(ev[parent], abs=3e-6)
        # the division into host and device is the parents', as before
        mine, other = (("host", "device") if side == "host" else ("device", "host"))
        assert 0.8 * stall_s <= ev[f"stall_{mine}_s"] < stall_s + 0.1
        assert ev[f"stall_{other}_s"] < 0.1 * stall_s
        assert eng.stats[f"stall_s.{mine}"] == pytest.approx(ev[f"stall_{mine}_s"], abs=1e-5)
        # the dump CLI prints the new fields as it prints any other
        path = flight.dump("stalls", path=str(tmp_path / "stalls.json"))
        assert dump_cli.main([path]) == 0
        printed = capsys.readouterr().out
        assert "step_stall" in printed and all(f'"{f}"' in printed for f in self.ENDS)

    def test_recover_times_no_subphase(self, tracing_on):
        fe, eng, _handles = _phase_frontend(seed=46, max_recoveries=2, recovery_backoff=0.0)
        for _ in range(3):
            fe.pump()
        tracing_on.clear()
        before = dict(eng.stats)
        with faults.inject(faults.FaultPlan([faults.FaultTrigger("engine.decode", 0)])):
            fe.pump()  # the dispatch dies in plan, recover() replays every live slot, the retry steps
        assert eng.stats["recoveries"] == before["recoveries"] + 1
        names = [s["name"] for s in tracing_on.spans()]
        assert names.count("engine.recover") == 1
        # the replay dispatched several times under no phase: one step's sub-phases, no more
        for kids in SUBPHASE_SPANS.values():
            for kid in kids:
                assert names.count(kid) == 1, kid
        for parent, parts in SUBPHASES.items():
            grown = sum(eng.stats[f"subphase_s.{p}"] - before[f"subphase_s.{p}"] for p in parts)
            assert grown == pytest.approx(eng.stats[f"phase_s.{parent}"] - before[f"phase_s.{parent}"], rel=1e-9)
        assert eng._phase is None and eng._subphase is None
