"""Cost model v1 (reference auto_parallel/static/cost/): analytic step-time
estimates, auto_tuner ordering, Engine sanity surface, and a ranking-
correlation check against measured CPU-mesh trial times."""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel.cost_model import (
    estimate_step_time,
    rank_configs,
    validate_ranking,
)

MODEL = {
    "num_layers": 8,
    "hidden_size": 1024,
    "num_attention_heads": 16,
    "vocab_size": 32000,
    "intermediate_size": 4096,
    "seq_length": 1024,
}
TCFG = {"model_cfg": MODEL, "global_batch_size": 16, "num_gpus": 8}


def _cfg(**kw):
    base = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 1, "sharding_degree": 1,
        "sharding_stage": 1, "micro_batch_size": 2, "use_recompute": False,
        "acc_steps": 1,
    }
    base.update(kw)
    return base


class TestAnalyticProperties:
    def test_recompute_costs_more_compute(self):
        a = estimate_step_time(_cfg(), TCFG)
        b = estimate_step_time(_cfg(use_recompute=True), TCFG)
        assert b["compute_s"] > a["compute_s"]
        assert b["compute_s"] / a["compute_s"] == pytest.approx(8 / 6, rel=1e-6)

    def test_mp_adds_comm_and_divides_compute(self):
        a = estimate_step_time(_cfg(), TCFG)
        b = estimate_step_time(_cfg(mp_degree=4), TCFG)
        assert b["comm_s"] > a["comm_s"]
        assert b["compute_s"] == pytest.approx(a["compute_s"] / 4, rel=1e-6)

    def test_pp_bubble(self):
        a = estimate_step_time(_cfg(acc_steps=4), TCFG)
        b = estimate_step_time(_cfg(pp_degree=4, acc_steps=4), TCFG)
        assert a["bubble_factor"] == 1.0
        assert b["bubble_factor"] == pytest.approx((4 + 3) / 4)

    def test_dp_grad_sync_scales_with_params_not_batch(self):
        small = dict(TCFG, global_batch_size=8)
        a = estimate_step_time(_cfg(dp_degree=2), small)
        big = dict(TCFG, global_batch_size=64)
        b = estimate_step_time(_cfg(dp_degree=2), big)
        assert a["comm_s"] == pytest.approx(b["comm_s"], rel=1e-6)

    def test_dispatch_scales_with_microbatches(self):
        a = estimate_step_time(_cfg(acc_steps=1), TCFG)
        b = estimate_step_time(_cfg(acc_steps=8), TCFG)
        assert b["dispatch_s"] == pytest.approx(8 * a["dispatch_s"], rel=1e-6)


class TestRanking:
    def test_rank_configs_sorted(self):
        cfgs = [
            _cfg(use_recompute=True, acc_steps=8),
            _cfg(),
            _cfg(mp_degree=8),
        ]
        ranked = rank_configs(cfgs, TCFG)
        est = [c["cost_estimate"] for c in ranked]
        assert est == sorted(est)

    def test_auto_tuner_cost_order(self):
        from paddle_tpu.distributed.auto_tuner import AutoTuner

        t = AutoTuner(dict(TCFG, hbm_bytes=64e9, order="cost"))
        est = [c["cost_estimate"] for c in t._queue]
        assert len(est) > 4 and est == sorted(est)

    def test_engine_cost_surface(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.auto_parallel import Engine, Strategy

        paddle.seed(0)
        lin = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=lin.parameters())
        eng = Engine(lin, loss=lambda o, l: o.sum(), optimizer=opt,
                     strategy=Strategy({"recompute": {"enable": True}}))
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"], process_ids=list(range(8)))
        eng.prepare(mesh=mesh)
        cost = eng.cost(MODEL, global_batch_size=16)
        assert cost["step_time_s"] > 0 and cost["comm_s"] > 0
        # recompute reflected
        eng2 = Engine(lin, loss=lambda o, l: o.sum(), optimizer=opt)
        eng2.prepare(mesh=mesh)
        assert eng2.cost(MODEL, 16)["compute_s"] < cost["compute_s"]


class TestRankingCorrelation:
    # a wall-clock ranking of four tiny CPU programs: it passes alone and fails
    # now and then under the six loaded workers of tier-1 (CHANGES, PR 26, 28,
    # 29), where a red run hides a real failure (ROADMAP D9): not in tier-1
    @pytest.mark.slow
    def test_predicted_ranking_matches_measured_cpu_trials(self):
        """Spearman(predicted, measured) on a tiny GPT over configs differing
        in recompute and micro-batching — the two axes whose relative cost
        survives on the CPU backend (VERDICT r5 #8's 'done' bar)."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

        VOCAB, SEQ, GBS = 64, 32, 8
        model_cfg = {
            "num_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
            "vocab_size": VOCAB, "intermediate_size": 256, "seq_length": SEQ,
        }
        trial_cfgs = [
            _cfg(micro_batch_size=8, acc_steps=1),
            _cfg(micro_batch_size=8, acc_steps=1, use_recompute=True),
            _cfg(micro_batch_size=2, acc_steps=4),
            _cfg(micro_batch_size=2, acc_steps=4, use_recompute=True),
        ]
        # CPU-calibrated knobs: tiny peak so compute is visible vs overhead
        tcfg = {
            "model_cfg": model_cfg, "global_batch_size": GBS,
            "peak_flops": 2e10, "mfu": 1.0, "step_overhead": 2e-3,
        }
        predicted = [estimate_step_time(c, tcfg)["step_time_s"] for c in trial_cfgs]

        def build_timer(cfg):
            paddle.seed(0)
            gcfg = GPTConfig(
                vocab_size=VOCAB, hidden_size=64, num_layers=4, num_heads=4,
                max_position=SEQ,
            )
            m = GPTForPretraining(gcfg)
            opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
            mbs, acc = cfg["micro_batch_size"], cfg["acc_steps"]
            use_rc = cfg["use_recompute"]

            @paddle.jit.to_static
            def micro(m, opt, ids, labels):
                if use_rc:
                    from paddle_tpu.distributed.fleet import recompute

                    logits = recompute(m, ids)
                else:
                    logits = m(ids)
                loss = F.cross_entropy(
                    logits.reshape([-1, VOCAB]).astype("float32"), labels.reshape([-1])
                )
                (loss / acc).backward()
                opt.step()
                opt.clear_grad()
                return loss

            rng = np.random.default_rng(0)
            ids = paddle.to_tensor(rng.integers(0, VOCAB, (mbs, SEQ)).astype(np.int32))
            for _ in range(2 * acc):  # warmup/compile
                micro(m, opt, ids, ids)
            steps = 12 // acc  # equal dispatch count per timed block for every cfg

            def timed_step() -> float:
                t0 = time.perf_counter()
                for _ in range(steps):
                    for _ in range(acc):  # one dispatched program per microbatch
                        loss = micro(m, opt, ids, ids)
                float(loss)
                return (time.perf_counter() - t0) / steps

            return timed_step

        # Compile everything first, then time round-robin with min-over-passes:
        # sequential per-config timing lets runtime drift (allocator/thread-pool
        # warmup, a transient load spike on a shared 2-core box) land entirely
        # on one config and invert the ranking the assertion checks.
        timers = [build_timer(c) for c in trial_cfgs]
        measured = [float("inf")] * len(timers)
        for _ in range(3):
            for i, timed_step in enumerate(timers):
                measured[i] = min(measured[i], timed_step())
        rho = validate_ranking(predicted, measured)
        assert rho >= 0.5, (
            f"cost-model ranking does not track measurements: rho={rho} "
            f"predicted={predicted} measured={measured}"
        )
