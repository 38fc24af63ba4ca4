"""``correct`` has to come out false when it should: the control (training:
the reference at the next lower precision, put in the program's place;
serving: the program with an int8 path of its own switched on) at a size
the CPU holds, and a run whose timed path is broken underneath. The harness's
look for a TPU is skipped by this test's own monkeypatches only."""

import numpy as np
import pytest

import run as harness
from lib.drivers import train
from tests import tiny


@pytest.fixture(autouse=True)
def float32_program():
    """XLA's CPU matmuls are bf16-class by default; the program under test is
    float32 at this size."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")


def checks_of(notes):
    return {n["name"]: n for n in notes if n.get("note") == "check"}


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(run_cell, monkeypatch):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lambda self: None)
    out, notes = run_cell("mistral7b.train_2k")
    checks = checks_of(notes)
    assert out["correct"] is False
    assert not checks["update_norm_gap_worst_leaf"]["ok"] and checks["update_norm_gap_worst_leaf"]["value"] >= 0.99


def test_train_step_that_skips_part_of_the_batch_is_not_correct(run_cell, monkeypatch):
    real = train.build

    def build(ctx):
        obj = real(ctx)
        feed = obj["feed"]

        def half(k):  # the second half of every batch repeats the first
            ids, labels = feed(k)
            n = ids.shape[0] // 2
            import paddle_tpu as paddle

            return paddle.concat([ids[:n], ids[:n]]), paddle.concat([labels[:n], labels[:n]])

        obj["feed"] = half
        return obj

    monkeypatch.setattr(train, "build", build)
    out, notes = run_cell("mistral7b.train_2k")
    checks = checks_of(notes)
    assert out["correct"] is False
    assert not checks["loss_gap_max"]["ok"] or not checks["grad_norm_gap_worst_leaf"]["ok"]


def test_train_control_lower_precision_reference_is_not_correct(run_cell, monkeypatch):
    """The reference at bf16 (the cell is float32 at this size) in the program's place: its gradient norms leave
    the float32 reference's by more than the cell's limit."""
    import jax

    captured = {}
    real = train.reference_steps

    def both(ctx, cfg, depth, stream, steps, opt_kw, lower=None):
        captured["ref"] = real(ctx, cfg, depth, stream, steps, opt_kw)
        captured["low"] = real(ctx, cfg, depth, stream, steps, opt_kw, lower="bf16")
        captured["limits"] = ctx.cell["check"]["limits"]
        return captured["ref"]

    monkeypatch.setattr(train, "reference_steps", both)
    out, notes = run_cell("mistral7b.train_2k")
    checks = checks_of(notes)
    assert out["correct"] is True
    rows = train.compare(captured["low"], captured["ref"], captured["limits"])
    assert not all(r["ok"] for r in rows), rows
    sound = checks["grad_norm_gap_worst_leaf"]["value"]
    control = next(r["value"] for r in rows if r["name"] == "grad_norm_gap_worst_leaf")
    assert control > 3 * sound


def test_served_token_altered_where_it_is_produced_is_not_correct(run_cell, monkeypatch):
    from paddle_tpu.inference import ContinuousBatchingEngine

    real = ContinuousBatchingEngine._dispatch

    def altered(self, toks, q_lens, active):
        nxt = np.array(real(self, toks, q_lens, active))
        return (nxt + 1) % self.model.config.vocab_size

    monkeypatch.setattr(ContinuousBatchingEngine, "_dispatch", altered)
    out, notes = run_cell("mistral7b.serve_chat")
    checks = checks_of(notes)
    assert out["correct"] is False and not checks["served_logit_gap_max"]["ok"]


@pytest.mark.parametrize("path", [{"kv_cache_dtype": "int8"}, {"weight_only_int8": True}], ids=["int8_kv", "weight_only_int8"])
def test_serve_control_the_programs_own_int8_path_is_not_correct(run_cell, monkeypatch, path):
    """The control of a served model is the program with a lower-precision
    path of its own switched on: the engine's step logits leave the
    reference's by more than the limit, and by far more than the sound
    engine's do."""
    real = tiny.shrink

    def with_path(parts, data):
        data = real(parts, data)
        if parts[-2] == "workloads" and "engine" in data:
            data["engine"].update(path)
        return data

    sound, sound_notes = run_cell("mistral7b.serve_chat")
    monkeypatch.setattr(tiny, "shrink", with_path)
    out, notes = run_cell("mistral7b.serve_chat")
    low, ok = checks_of(notes)["step_logit_rel_rms"], checks_of(sound_notes)["step_logit_rel_rms"]
    assert sound["correct"] is True and ok["ok"]
    assert out["correct"] is False and not low["ok"]
    assert low["value"] > 3 * ok["value"]
