"""Device-time attribution (PR 17): cost profiles, host-bubble analysis,
measured comm share.

Pins the acceptance contract: with ``FLAGS_devprof_sample_rate=0`` the
profiling surface is one cached-bool read (no timeline entries, no flight
events, no extra compiles, seeded streams untouched); with rate 1 every
engine step yields a profile whose host-prep / dispatch-gap / device
segments tile the device-sync-honest step wall, whose per-category shares
sum to 1, and the engine still compiles exactly ONE step signature; the
cost-regression ledger fires when a re-trace moves flops/bytes past
tolerance; a tp=2 engine reports a measured comm share; and the dump CLI's
``--devprof`` view renders the story or exits 2, never a vacuous pass.

Everything runs on CPU with the tiny Llama config (conftest provides the
8-device virtual mesh for the tp case).
"""

import json

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import devprof
from paddle_tpu.observability import dump as dump_cli
from paddle_tpu.observability import flight_recorder as flightrec


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(seed=0, **kw):
    m, cfg = _model(seed)
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prompt_bucket", 8)
    return ContinuousBatchingEngine(m, **kw), cfg


def _run(eng, cfg, seed=0, n=3):
    rng = np.random.default_rng(seed)
    rids = [
        eng.add_request(
            rng.integers(0, cfg.vocab_size, (4 + i,)).astype(np.int32),
            max_new_tokens=3 + i,
        )
        for i in range(n)
    ]
    out = eng.run()
    return {r: out[r].tokens().tolist() for r in rids}


@pytest.fixture
def devprof_on():
    """Sample every step into clean global state; restore on teardown."""
    prior = paddle.get_flags(["FLAGS_devprof_sample_rate"])
    paddle.set_flags({"FLAGS_devprof_sample_rate": 1.0})
    obs.GLOBAL_WATCHDOG.reset()
    devprof.GLOBAL_COST_LEDGER.reset()
    devprof.drain_chrome_events()
    yield
    paddle.set_flags(prior)
    devprof.GLOBAL_COST_LEDGER.reset()
    devprof.drain_chrome_events()


# -- cost_analysis -------------------------------------------------------------

class TestNormalizeCostAnalysis:
    def test_dict_form(self):
        p = devprof.normalize_cost_analysis(
            {"flops": 100.0, "bytes accessed": 40.0, "transcendentals": 2.0}
        )
        assert p == {
            "flops": 100.0, "bytes_accessed": 40.0, "transcendentals": 2.0,
            "cost_model": "xla",
        }

    @pytest.mark.parametrize("raw", [None, "nope", [], [1, 2], {"foo": "bar"}])
    def test_missing_or_garbage_records_unavailable_with_zeros(self, raw):
        p = devprof.normalize_cost_analysis(raw)
        assert p["cost_model"] == "unavailable"
        assert p["flops"] == 0.0 and p["bytes_accessed"] == 0.0


# -- sampling gate ------------------------------------------------------------

class TestSampleGate:
    def test_off_is_one_cached_bool_read_and_no_counter_churn(self):
        assert paddle.get_flags(["FLAGS_devprof_sample_rate"])[
            "FLAGS_devprof_sample_rate"
        ] == 0.0
        assert not devprof.devprof_enabled()
        gate = devprof.SampleGate()
        assert [gate.should_sample() for _ in range(10)] == [False] * 10
        # the disabled gate never advances its stride counter, so flipping
        # the flag later starts a deterministic stride from scratch
        assert gate._n == 0

    def test_deterministic_stride(self, devprof_on):
        paddle.set_flags({"FLAGS_devprof_sample_rate": 0.25})
        gate = devprof.SampleGate()
        got = [gate.should_sample() for _ in range(8)]
        assert got == [True, False, False, False, True, False, False, False]

    def test_rate_one_samples_every_call(self, devprof_on):
        gate = devprof.SampleGate()
        assert all(gate.should_sample() for _ in range(5))


# -- off-path honesty ---------------------------------------------------------

class TestOffPath:
    def test_rate_zero_records_nothing_and_leaves_the_run_untouched(self):
        assert not devprof.devprof_enabled()
        obs.GLOBAL_WATCHDOG.reset()
        devprof.GLOBAL_COST_LEDGER.reset()
        eng, cfg = _engine(seed=7)
        flight_before = len(eng._flight.snapshot())
        toks = _run(eng, cfg, seed=7)
        assert all(len(t) > 0 for t in toks.values())
        # nothing sampled: no timeline entries, no devprof flight events,
        # no cost profiles captured, summary reports disabled
        assert len(eng._devprof_timeline) == 0
        devs = [
            e for e in eng._flight.snapshot()[flight_before:]
            if e.get("kind") in ("devprof_step", "cost_regression")
        ]
        assert devs == []
        assert devprof.GLOBAL_COST_LEDGER.snapshot()["profiles"] == {}
        assert eng.devprof_stats() == {"enabled": False, "sampled_steps": 0}
        # and the engine still compiled exactly one step signature
        assert obs.GLOBAL_WATCHDOG.counts().get(
            "ContinuousBatchingEngine.step"
        ) == 1

    def test_profiling_never_perturbs_seeded_generation(self, devprof_on):
        eng_on, cfg = _engine(seed=11)
        toks_on = _run(eng_on, cfg, seed=11)
        paddle.set_flags({"FLAGS_devprof_sample_rate": 0.0})
        eng_off, cfg = _engine(seed=11)
        toks_off = _run(eng_off, cfg, seed=11)
        assert toks_on == toks_off


# -- sampled steps ------------------------------------------------------------

class TestSampledSteps:
    def test_segments_tile_the_wall_and_shares_sum_to_one(self, devprof_on):
        eng, cfg = _engine(seed=3)
        _run(eng, cfg, seed=3)
        entries = eng._devprof_timeline.entries()
        assert len(entries) >= 3
        for e in entries:
            # device-sync-honest: consecutive perf_counter differences, so
            # the three segments tile the step wall exactly
            assert e["host_prep_s"] + e["dispatch_s"] + e["device_s"] == \
                pytest.approx(e["wall_s"], rel=1e-9, abs=1e-9)
            assert sum(e["categories"].values()) == pytest.approx(1.0, abs=1e-4)
            assert set(e["categories"]) == set(devprof.CATEGORIES)
            assert 0.0 <= e["host_bubble_fraction"] <= 1.0
            assert e["signature"].startswith("toks[")

    def test_cost_profile_captured_and_one_compile(self, devprof_on):
        eng, cfg = _engine(seed=4)
        _run(eng, cfg, seed=4)
        # exactly ONE compiled step signature even with profiling on — the
        # introspective AOT lowering must not add a trace of its own
        assert eng.stats["step_traces"] == 1
        assert obs.GLOBAL_WATCHDOG.counts().get(
            "ContinuousBatchingEngine.step"
        ) == 1
        snap = devprof.GLOBAL_COST_LEDGER.snapshot()
        profs = snap["profiles"].get("ContinuousBatchingEngine.step")
        assert profs, snap
        prof = next(iter(profs.values()))
        assert prof["cost_model"] in ("xla", "unavailable")
        if prof["cost_model"] == "xla":
            assert prof["flops"] > 0
        assert sum(prof["categories"].values()) == pytest.approx(1.0, abs=1e-6)

    def test_summary_and_flight_and_chrome_events(self, devprof_on):
        eng, cfg = _engine(seed=5)
        flight_before = len(eng._flight.snapshot())
        _run(eng, cfg, seed=5)
        st = eng.devprof_stats()
        assert st["enabled"] and st["sampled_steps"] == len(eng._devprof_timeline)
        assert sum(st["mean_category_shares"].values()) == pytest.approx(
            1.0, abs=1e-3
        )
        assert 0.0 <= st["comm_share_measured"] <= 1.0
        assert st["last"]["comm_source"] in ("wrapper", "cost_model", "none")
        devs = [
            e for e in eng._flight.snapshot()[flight_before:]
            if e.get("kind") == "devprof_step"
        ]
        assert len(devs) == st["sampled_steps"]
        assert all("categories" in e and "wall_ms" in e for e in devs)
        chrome = devprof.drain_chrome_events()
        names = {e["name"] for e in chrome}
        assert names == {
            "devprof.device_ms_by_category", "devprof.step_segments_ms"
        }
        assert all(e["ph"] == "C" for e in chrome)
        # drained means drained
        assert devprof.drain_chrome_events() == []

    def test_healthz_snapshot_carries_devprof(self, devprof_on):
        from paddle_tpu.serving import ServingConfig, ServingFrontend

        eng, cfg = _engine(seed=6)
        fe = ServingFrontend(eng, ServingConfig(max_queue=4))
        rng = np.random.default_rng(6)
        h = fe.submit(
            rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
            max_new_tokens=3,
        )
        for _ in range(200):
            fe.pump()
            if h.finished:
                break
        assert h.finished
        snap = fe.snapshot()
        assert snap["devprof"]["enabled"] is True
        assert snap["devprof"]["sampled_steps"] >= 1


# -- wrapper-measured comm override -------------------------------------------

class TestCommAttribution:
    def test_wrapper_time_overrides_the_prior(self, devprof_on):
        devprof.GLOBAL_COST_LEDGER.record(
            "f", "sig",
            {"flops": 100.0, "bytes_accessed": 10.0, "cost_model": "xla",
             "categories": {"attention": 0.3, "matmul": 0.5,
                            "collective": 0.1, "other": 0.1}},
        )
        e = devprof.record_step_profile(
            "f", "sig", t0=0.0, call_s=0.001, ret_s=0.002, sync_s=0.012,
            comm_ops={"all_reduce": 0.004},
        )
        assert e["comm_source"] == "wrapper"
        # 4ms of measured collective inside a 10ms device segment
        assert e["categories"]["collective"] == pytest.approx(0.4, abs=1e-6)
        # non-collective categories split the remainder by prior ratio
        assert e["categories"]["matmul"] == pytest.approx(
            0.6 * (0.5 / 0.9), abs=1e-6
        )
        assert sum(e["categories"].values()) == pytest.approx(1.0, abs=1e-9)

    def test_cost_model_fallback_when_window_caught_nothing(self, devprof_on):
        devprof.GLOBAL_COST_LEDGER.record(
            "g", "sig",
            {"flops": 100.0, "bytes_accessed": 10.0, "cost_model": "xla",
             "categories": {"attention": 0.2, "matmul": 0.5,
                            "collective": 0.2, "other": 0.1}},
        )
        e = devprof.record_step_profile(
            "g", "sig", t0=0.0, call_s=0.001, ret_s=0.002, sync_s=0.012,
            comm_ops={},
        )
        assert e["comm_source"] == "cost_model"
        assert e["categories"]["collective"] == pytest.approx(0.2, abs=1e-6)

    def test_no_prior_no_window_is_honestly_unattributed(self, devprof_on):
        e = devprof.record_step_profile(
            "h", "sig", t0=0.0, call_s=0.001, ret_s=0.002, sync_s=0.012,
        )
        assert e["comm_source"] == "none"
        assert e["cost_model"] == "missing"
        assert e["categories"] == {
            "attention": 0.0, "matmul": 0.0, "collective": 0.0, "other": 1.0
        }

    def test_comm_window_is_thread_local_and_disarms(self):
        assert not devprof.comm_window_armed()
        devprof.record_comm("all_reduce", 1.0)  # unarmed: dropped
        devprof.begin_comm_window()
        assert devprof.comm_window_armed()
        devprof.record_comm("all_reduce", 0.5)
        devprof.record_comm("all_reduce", 0.25)
        ops = devprof.end_comm_window()
        assert ops == {"all_reduce": 0.75}
        assert not devprof.comm_window_armed()
        assert devprof.end_comm_window() == {}


# -- cost-regression ledger ---------------------------------------------------

class TestCostLedger:
    def test_retrace_drift_past_tolerance_fires(self, devprof_on):
        led = devprof.CostLedger(drift_tolerance=0.01)
        base = {"flops": 1000.0, "bytes_accessed": 500.0, "cost_model": "xla"}
        led.record("fn", "sig-a", base)
        led.record("fn", "sig-b", {**base, "flops": 1100.0})
        assert len(led.regressions) == 1
        r = led.regressions[0]
        assert r["prev_signature"] == "sig-a" and r["signature"] == "sig-b"
        assert r["drift_flops"] == pytest.approx(0.1, abs=1e-9)

    def test_same_cost_retrace_is_quiet(self, devprof_on):
        led = devprof.CostLedger(drift_tolerance=0.01)
        base = {"flops": 1000.0, "bytes_accessed": 500.0, "cost_model": "xla"}
        led.record("fn", "sig-a", base)
        led.record("fn", "sig-b", {**base, "flops": 1005.0})
        led.record("fn", "sig-a", base)  # same-signature re-record: no drift
        assert led.regressions == []

    def test_unavailable_side_skips_drift(self, devprof_on):
        led = devprof.CostLedger(drift_tolerance=0.01)
        led.record(
            "fn", "sig-a",
            {"flops": 0.0, "bytes_accessed": 0.0, "cost_model": "unavailable"},
        )
        led.record(
            "fn", "sig-b",
            {"flops": 999.0, "bytes_accessed": 1.0, "cost_model": "xla"},
        )
        assert led.regressions == []

    def test_forced_engine_retrace_lands_in_the_global_ledger(self, devprof_on):
        """Two engines with different shape buckets are two signatures of
        the same step fn: the integration path the drift check watches."""
        eng_a, cfg = _engine(seed=8, prompt_bucket=8)
        _run(eng_a, cfg, seed=8, n=1)
        eng_b, cfg = _engine(seed=8, prompt_bucket=16, max_slots=4)
        _run(eng_b, cfg, seed=8, n=1)
        snap = devprof.GLOBAL_COST_LEDGER.snapshot()
        profs = snap["profiles"].get("ContinuousBatchingEngine.step", {})
        assert len(profs) == 2, profs
        if all(p["cost_model"] == "xla" for p in profs.values()):
            # a 2x-wider batch moved flops far past the 1% tolerance
            assert snap["regressions"], snap
            assert snap["regressions"][0]["fn"] == "ContinuousBatchingEngine.step"

    def test_unknown_signature_falls_back_to_latest(self, devprof_on):
        led = devprof.CostLedger()
        led.record("fn", "sig-a", {"flops": 1.0, "cost_model": "xla"})
        assert led.profile_for("fn", "sig-zzz")["flops"] == 1.0
        assert led.profile_for("other-fn", "sig") is None


# -- tensor-parallel measured comm share --------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
class TestTensorParallel:
    def test_tp2_reports_a_measured_comm_share(self, devprof_on):
        eng, cfg = _engine(seed=9, tp=2, max_slots=3)
        toks = _run(eng, cfg, seed=9)
        assert all(len(t) > 0 for t in toks.values())
        assert eng.stats["step_traces"] == 1
        st = eng.devprof_stats()
        assert st["sampled_steps"] >= 3
        assert 0.0 <= st["comm_share_measured"] <= 1.0
        # every sampled step names its comm provenance; GSPMD-inserted
        # all-reduces are invisible to the host wrapper, so cost_model (or
        # wrapper, if the program used explicit collectives) — never a
        # silent zero with no source
        assert st["comm_sources"]
        assert set(st["comm_sources"]) <= {"wrapper", "cost_model", "none"}
        assert st["last"]["signature"].endswith("|tp2")


# -- dump CLI -----------------------------------------------------------------

class TestDumpCLI:
    def _flight_dump_with_steps(self, tmp_path, n=3):
        rec = flightrec.FlightRecorder(capacity=64)
        for i in range(n):
            devprof.record_step_profile(
                "f", "sig", t0=float(i), call_s=i + 0.001, ret_s=i + 0.002,
                sync_s=i + 0.010, step=i, flight=rec,
            )
        return rec.dump("devprof-test", path=str(tmp_path / "flight.json"))

    def test_devprof_view_renders_steps(self, tmp_path, capsys):
        path = self._flight_dump_with_steps(tmp_path)
        assert dump_cli.main([path, "--devprof"]) == 0
        out = capsys.readouterr().out
        assert "device-time attribution — 3 sampled steps" in out
        assert "top category:" in out
        assert "mean host-bubble fraction:" in out

    def test_no_profiles_exits_2(self, tmp_path, capsys):
        rec = flightrec.FlightRecorder(capacity=8)
        rec.record("admit", rid="r1")
        path = rec.dump("no-devprof", path=str(tmp_path / "flight.json"))
        assert dump_cli.main([path, "--devprof"]) == 2
        assert "no devprof_step profiles" in capsys.readouterr().err

    def test_corrupt_profile_row_exits_2(self, tmp_path, capsys):
        path = self._flight_dump_with_steps(tmp_path, n=1)
        with open(path) as f:
            payload = json.load(f)
        del payload["events"][0]["categories"]
        with open(path, "w") as f:
            json.dump(payload, f)
        assert dump_cli.main([path, "--devprof"]) == 2
        assert "corrupt devprof_step" in capsys.readouterr().err

    def test_span_jsonl_exits_2(self, tmp_path, capsys):
        p = tmp_path / "spans.jsonl"
        p.write_text(json.dumps({"name": "s", "ts_us": 1.0}) + "\n")
        assert dump_cli.main([str(p), "--devprof"]) == 2
        assert "flight dump or incident dir" in capsys.readouterr().err

    def test_plain_view_still_prints_devprof_events(self, tmp_path, capsys):
        path = self._flight_dump_with_steps(tmp_path, n=1)
        assert dump_cli.main([path]) == 0
        assert "devprof_step" in capsys.readouterr().out


# -- profiler export merge ----------------------------------------------------

class TestProfilerExport:
    def test_export_merges_devprof_counter_tracks(self, tmp_path, devprof_on):
        from paddle_tpu import profiler

        devprof.record_step_profile(
            "f", "sig", t0=0.0, call_s=0.001, ret_s=0.002, sync_s=0.010,
        )
        prof = profiler.Profiler()
        prof.start()
        prof.stop()
        out = tmp_path / "trace.json"
        prof.export(str(out))
        events = json.loads(out.read_text())["traceEvents"]
        names = {e.get("name") for e in events}
        assert "devprof.device_ms_by_category" in names
        assert "devprof.step_segments_ms" in names
