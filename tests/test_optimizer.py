"""Optimizers: convergence, parity vs hand-rolled updates, schedulers, clip, amp."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _quadratic_setup(opt_cls, **kw):
    """min ||w - target||^2 via the optimizer."""
    w = paddle.Parameter(np.zeros(4, np.float32))
    target = paddle.to_tensor(np.array([1.0, -2.0, 3.0, 0.5], np.float32))
    opt = opt_cls(parameters=[w], **kw)
    for _ in range(200):
        loss = ((w - target) * (w - target)).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return w.numpy(), target.numpy()


def test_sgd_converges():
    w, t = _quadratic_setup(paddle.optimizer.SGD, learning_rate=0.1)
    np.testing.assert_allclose(w, t, atol=1e-3)


def test_momentum_converges():
    w, t = _quadratic_setup(paddle.optimizer.Momentum, learning_rate=0.05, momentum=0.9)
    np.testing.assert_allclose(w, t, atol=1e-3)


def test_adam_converges():
    w, t = _quadratic_setup(paddle.optimizer.Adam, learning_rate=0.1)
    np.testing.assert_allclose(w, t, atol=1e-2)


def test_adamw_converges():
    w, t = _quadratic_setup(paddle.optimizer.AdamW, learning_rate=0.1, weight_decay=0.0)
    np.testing.assert_allclose(w, t, atol=1e-2)


def test_adam_matches_reference_update():
    """One Adam step vs hand-computed numpy update."""
    g = np.array([0.5, -1.0], np.float32)
    w0 = np.array([1.0, 2.0], np.float32)
    w = paddle.Parameter(w0.copy())
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=[w])
    loss = (w * paddle.to_tensor(g)).sum()
    loss.backward()
    opt.step()
    m = 0.1 * g
    v = 0.001 * g * g
    m_hat = m / (1 - 0.9)
    v_hat = v / (1 - 0.999)
    expected = w0 - 0.01 * m_hat / (np.sqrt(v_hat) + 1e-8)
    np.testing.assert_allclose(w.numpy(), expected, rtol=1e-5)


def test_adamw_decoupled_decay():
    w = paddle.Parameter(np.array([10.0], np.float32))
    opt = paddle.optimizer.AdamW(learning_rate=0.1, weight_decay=0.5, parameters=[w])
    loss = (w * 0.0).sum()  # zero gradient: only decay applies
    loss.backward()
    opt.step()
    np.testing.assert_allclose(w.numpy(), [10.0 - 0.1 * 0.5 * 10.0], rtol=1e-5)


def test_multi_precision_master_weights():
    w = paddle.Parameter(np.ones(4, np.float32))
    w._data = w._data.astype(paddle.bfloat16)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=[w], multi_precision=True)
    loss = (w.astype("float32") * 1.0).sum()
    loss.backward()
    opt.step()
    st = opt._accumulators[id(w)]
    assert "master_weight" in st
    assert str(st["master_weight"].dtype) == "float32"


def test_grad_clip_global_norm():
    w = paddle.Parameter(np.ones(2, np.float32))
    clip = nn.ClipGradByGlobalNorm(1.0)
    opt = paddle.optimizer.SGD(learning_rate=1.0, parameters=[w], grad_clip=clip)
    (w * paddle.to_tensor(np.array([30.0, 40.0], np.float32))).sum().backward()
    opt.step()
    # grad (30,40) has norm 50 -> clipped to (0.6, 0.8)
    np.testing.assert_allclose(w.numpy(), [1 - 0.6, 1 - 0.8], rtol=1e-4)


def test_lr_scheduler_step_decay():
    sched = paddle.optimizer.lr.StepDecay(learning_rate=1.0, step_size=2, gamma=0.1)
    w = paddle.Parameter(np.ones(1, np.float32))
    opt = paddle.optimizer.SGD(learning_rate=sched, parameters=[w])
    assert opt.get_lr() == pytest.approx(1.0)
    sched.step()
    sched.step()
    assert opt.get_lr() == pytest.approx(0.1)


def test_cosine_annealing():
    sched = paddle.optimizer.lr.CosineAnnealingDecay(learning_rate=1.0, T_max=10)
    sched.step(5)
    assert sched() == pytest.approx(0.5, abs=1e-6)
    sched.step(10)
    assert sched() == pytest.approx(0.0, abs=1e-6)


def test_linear_warmup():
    sched = paddle.optimizer.lr.LinearWarmup(
        learning_rate=0.8, warmup_steps=4, start_lr=0.0, end_lr=0.8
    )
    assert sched() == pytest.approx(0.0)
    sched.step()
    assert sched() == pytest.approx(0.2)
    for _ in range(5):
        sched.step()
    assert sched() == pytest.approx(0.8)


def test_optimizer_state_dict_roundtrip():
    w = paddle.Parameter(np.ones(3, np.float32), name="w0")
    opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=[w])
    (w * 2).sum().backward()
    opt.step()
    sd = opt.state_dict()
    w2 = paddle.Parameter(np.ones(3, np.float32), name="w0")
    opt2 = paddle.optimizer.Adam(learning_rate=0.1, parameters=[w2])
    opt2.set_state_dict(sd)
    st1 = opt._accumulators[id(w)]
    st2 = opt2._accumulators[id(w2)]
    np.testing.assert_allclose(np.asarray(st1["moment1"]), np.asarray(st2["moment1"]))


def test_training_loop_linear_regression():
    """End-to-end slice: Layer + loss + optimizer learns y = 2x + 1."""
    np.random.seed(0)
    x = np.random.rand(64, 1).astype(np.float32)
    y = 2 * x + 1 + 0.01 * np.random.randn(64, 1).astype(np.float32)
    model = nn.Linear(1, 1)
    opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=model.parameters())
    loss_fn = nn.MSELoss()
    for _ in range(150):
        pred = model(paddle.to_tensor(x))
        loss = loss_fn(pred, paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert model.weight.numpy()[0, 0] == pytest.approx(2.0, abs=0.1)
    assert model.bias.numpy()[0] == pytest.approx(1.0, abs=0.1)


class TestAmp:
    def test_autocast_casts_matmul(self):
        a = paddle.ones([2, 2])
        b = paddle.ones([2, 2])
        with paddle.amp.auto_cast(dtype="bfloat16"):
            out = paddle.matmul(a, b)
        assert out.dtype == paddle.bfloat16

    def test_autocast_keeps_blacklist_fp32(self):
        x = paddle.ones([4], dtype="bfloat16")
        with paddle.amp.auto_cast(dtype="bfloat16"):
            out = paddle.nn.functional.softmax(x)
        assert str(np.dtype(out.dtype)) == "float32"

    def test_amp_training_step(self):
        model = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
        x = paddle.ones([2, 4])
        with paddle.amp.auto_cast(dtype="bfloat16"):
            loss = model(x).sum()
        loss.backward()
        # grads accumulate back in fp32 (param dtype)
        assert str(np.dtype(model.weight.grad.dtype)) == "float32"
        opt.step()

    def test_o2_decorate(self):
        model = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=model.parameters())
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
        assert model[0].weight.dtype == paddle.bfloat16
        assert str(np.dtype(model[1].weight.dtype)) == "float32"  # norms excluded
        assert opt._multi_precision

    def test_grad_scaler_passthrough(self):
        scaler = paddle.amp.GradScaler(enable=False)
        w = paddle.Parameter(np.ones(1, np.float32))
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])
        loss = (w * 3).sum()
        scaler.scale(loss).backward()
        scaler.step(opt)
        np.testing.assert_allclose(w.numpy(), [0.7], rtol=1e-5)


# -- the seam between autograd and the update (PR 34) --------------------------


def _bf16_stack():
    paddle.seed(0)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8)).bfloat16()


def _train_step(model, opt, x, y):  # state rides in through the arguments
    loss = ((model(x).astype("float32") - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss


# multi_precision throughout: without a master weight ``param - lr * grad``
# promotes a bf16 parameter to the float32 of ``lr`` (so before this PR too)
_SEAM_OPTIMIZERS = {
    "sgd": lambda ps: paddle.optimizer.SGD(learning_rate=0.05, parameters=ps, multi_precision=True),
    "momentum": lambda ps: paddle.optimizer.Momentum(
        learning_rate=0.05, momentum=0.9, parameters=ps, multi_precision=True
    ),
    "adamw": lambda ps: paddle.optimizer.AdamW(
        learning_rate=0.01, weight_decay=0.01, parameters=ps, multi_precision=True
    ),
}


@pytest.mark.parametrize("name", sorted(_SEAM_OPTIMIZERS))
def test_to_static_step_matches_eager_step(name):
    """Three steps of a small bf16 stack: the traced step (the update inside the
    step's one program, every gradient behind its barrier) and the eager step
    (the update a program of its own over gradient ARGUMENTS) leave the same
    parameters, master weights and moments."""
    rng = np.random.default_rng(0)
    batches = [
        (paddle.to_tensor(rng.standard_normal((4, 16)).astype(np.float32)).astype("bfloat16"),
         paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32)))
        for _ in range(3)
    ]
    runs = {}
    for mode in ("eager", "static"):
        model = _bf16_stack()
        opt = _SEAM_OPTIMIZERS[name](model.parameters())
        step = paddle.jit.to_static(_train_step) if mode == "static" else _train_step
        losses = [float(step(model, opt, x, y)) for x, y in batches]
        runs[mode] = (model, opt, losses)

    (m_e, o_e, l_e), (m_s, o_s, l_s) = runs["eager"], runs["static"]
    np.testing.assert_allclose(l_s, l_e, rtol=2e-2)
    bf16_eps = 2.0 ** -8
    for p_e, p_s in zip(m_e.parameters(), m_s.parameters()):
        assert p_s.dtype == p_e.dtype == paddle.bfloat16
        # a bf16 parameter is one rounding of its update: a unit in the last place
        np.testing.assert_allclose(
            p_s.astype("float32").numpy(), p_e.astype("float32").numpy(), rtol=2 * bf16_eps, atol=1e-3
        )
        st_e, st_s = o_e._accumulators[id(p_e)], o_s._accumulators[id(p_s)]
        assert sorted(st_s) == sorted(st_e) and "master_weight" in st_s
        for k in st_e:
            assert st_s[k].dtype == st_e[k].dtype, k
            assert str(st_s[k].dtype) == "float32", k  # master weight and moments
            # the state reads a gradient rounded to bf16 in both, of bf16
            # activations that the step's one program may round once less: a
            # few bf16 units of the gradient; a master weight moves by lr x
            # that (Adam: by up to lr where a near-zero gradient flips sign,
            # 3 x 0.01 here, of which this allows a sixtieth)
            np.testing.assert_allclose(
                np.asarray(st_s[k], np.float32), np.asarray(st_e[k], np.float32),
                rtol=8 * bf16_eps, atol=2e-3 if k != "master_weight" else 5e-4, err_msg=k,
            )


def test_to_static_step_holds_a_barrier_between_each_gradient_and_its_update(monkeypatch):
    """The lowered train step holds one ``optimization_barrier`` a trainable
    leaf, and inside the update every gradient's ONLY reader is its barrier:
    XLA cannot fuse a leaf's update into the ``dot_general`` that makes its
    gradient (PERF.md, PR 34)."""
    import jax

    captured = []
    real_jit = jax.jit

    def spy(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") != "staged":  # to_static's staged step
            return jitted

        def call(*args):
            traced = jitted.trace(*args)
            captured.append((traced.jaxpr, traced.lower().as_text()))
            return jitted(*args)

        return call

    model = _bf16_stack()
    opt = _SEAM_OPTIMIZERS["adamw"](model.parameters())
    x = paddle.to_tensor(np.ones((4, 16), np.float32)).astype("bfloat16")
    y = paddle.to_tensor(np.zeros((4, 8), np.float32))
    monkeypatch.setattr(jax, "jit", spy)
    paddle.jit.to_static(_train_step)(model, opt, x, y)
    monkeypatch.undo()
    assert captured, "to_static staged no step"
    jaxpr, text = captured[0]
    leaves = [p for p in model.parameters() if not p.stop_gradient]
    assert len(leaves) == 4
    assert text.count("optimization_barrier") == len(leaves)
    assert "dot_general" in text

    fused = [e for e in jaxpr.jaxpr.eqns if e.params.get("name") == "fused"]
    assert len(fused) == 1, "one update program inside the step"
    inner = fused[0].params["jaxpr"].jaxpr
    grads = inner.invars[len(leaves):2 * len(leaves)]  # fused(ps, gs, sts, lr, step)
    outer_grads = fused[0].invars[len(leaves):2 * len(leaves)]
    made_by = {id(v): e.primitive.name for e in jaxpr.jaxpr.eqns for v in e.outvars}
    for g, og, p in zip(grads, outer_grads, leaves):
        assert g.aval.shape == tuple(p.shape) and str(g.aval.dtype) == "bfloat16"
        readers = [e.primitive.name for e in inner.eqns if any(v is g for v in e.invars)]
        assert readers == ["optimization_barrier"], (p.name, readers)
        assert id(og) in made_by, "a gradient made inside the step, not an argument"


def test_wgrad_update_bench_rehearses_off_the_chip(capsys):
    """``tools/wgrad_update_bench.py --rehearse``: the four programs (gradient,
    gradient + update, the same behind the barrier, update) run once a leaf at a
    tiny size on this backend and print no time."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "tools" / "wgrad_update_bench.py"
    spec = importlib.util.spec_from_file_location("wgrad_update_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    results = bench.main(["--rehearse"])
    assert len(results) == 2
    for by_case in results.values():
        assert list(by_case) == list(bench.CASES) and set(by_case.values()) == {None}
    out = capsys.readouterr().out
    assert out.count("ran (rehearsal: no time)") == 8 and " ms" not in out
