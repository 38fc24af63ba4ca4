"""Regression tests for review findings: jit RNG threading, train/eval retrace,
scaler double-unscale guard, param-group lr, group-local broadcast, p2p perms,
need_clip norm exclusion."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def test_dropout_fresh_masks_under_jit():
    drop = nn.Dropout(0.5)
    drop.train()

    @paddle.jit.to_static
    def f(x):
        return drop(x)

    x = paddle.ones([64, 64])
    m1 = f(x).numpy()
    m2 = f(x).numpy()
    assert not np.allclose(m1, m2), "compiled dropout must draw a fresh mask per call"


def test_train_eval_retraces_free_function():
    model = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.9))

    @paddle.jit.to_static
    def f(model, x):
        return model(x)

    x = paddle.ones([16, 8])
    model.train()
    out_train = f(model, x).numpy()
    model.eval()
    out_eval = f(model, x).numpy()
    # eval: dropout disabled → deterministic pass-through of linear
    expected = x.numpy() @ model[0].weight.numpy() + model[0].bias.numpy()
    np.testing.assert_allclose(out_eval, expected, rtol=1e-4)
    assert (out_train == 0).mean() > 0.5  # train mode really dropped


def test_scaler_manual_unscale_then_step():
    scaler = paddle.amp.GradScaler(enable=True, init_loss_scaling=2.0**10)
    w = paddle.Parameter(np.ones(2, np.float32))
    opt = paddle.optimizer.SGD(learning_rate=1.0, parameters=[w])
    loss = (w * paddle.to_tensor(np.array([1.0, 2.0], np.float32))).sum()
    scaler.scale(loss).backward()
    scaler.unscale_(opt)  # manual unscale for clipping
    g_after_manual = w.grad.numpy().copy()
    scaler.step(opt)  # must NOT unscale a second time
    np.testing.assert_allclose(g_after_manual, [1.0, 2.0], rtol=1e-6)
    np.testing.assert_allclose(w.numpy(), [0.0, -1.0], rtol=1e-5)


def test_param_group_learning_rates():
    w1 = paddle.Parameter(np.zeros(1, np.float32), name="slow")
    w2 = paddle.Parameter(np.zeros(1, np.float32), name="fast")
    opt = paddle.optimizer.SGD(
        learning_rate=1.0,
        parameters=[
            {"params": [w1], "learning_rate": 0.1},
            {"params": [w2], "learning_rate": 10.0},
        ],
    )
    (w1 * 1.0 + w2 * 1.0).sum().backward()
    opt.step()
    np.testing.assert_allclose(w1.numpy(), [-0.1], rtol=1e-6)
    np.testing.assert_allclose(w2.numpy(), [-10.0], rtol=1e-6)


def test_adamw_apply_decay_param_fun():
    w_decay = paddle.Parameter(np.full(1, 10.0, np.float32), name="linear_w")
    w_nodecay = paddle.Parameter(np.full(1, 10.0, np.float32), name="norm_w")
    opt = paddle.optimizer.AdamW(
        learning_rate=0.1,
        weight_decay=0.5,
        parameters=[w_decay, w_nodecay],
        apply_decay_param_fun=lambda n: "norm" not in n,
    )
    (w_decay * 0.0 + w_nodecay * 0.0).sum().backward()
    opt.step()
    assert w_decay.numpy()[0] < 10.0  # decayed
    np.testing.assert_allclose(w_nodecay.numpy(), [10.0], rtol=1e-6)  # untouched


def test_need_clip_excluded_from_global_norm():
    clip = nn.ClipGradByGlobalNorm(1.0)
    p1 = paddle.Parameter(np.ones(1, np.float32))
    p2 = paddle.Parameter(np.ones(1, np.float32))
    p2.need_clip = False
    from paddle_tpu.core.tensor import Tensor

    g1 = Tensor(np.array([0.5], np.float32))
    g2 = Tensor(np.array([100.0], np.float32))  # huge but excluded
    out = clip([(p1, g1), (p2, g2)])
    # p1's grad norm (0.5) is under the threshold → unchanged
    np.testing.assert_allclose(out[0][1].numpy(), [0.5], rtol=1e-6)
    np.testing.assert_allclose(out[1][1].numpy(), [100.0], rtol=1e-6)


def test_broadcast_subgroup_uses_local_rank():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    import paddle_tpu.distributed as dist

    devices = np.asarray(jax.devices()[:4])
    mesh = Mesh(devices, ("g",))
    group = dist.new_group(ranks=[4, 5, 6, 7], axis_name="g")

    def body(x):
        return dist.broadcast(x, src=6, group=group)

    x = jnp.arange(4, dtype=jnp.float32).reshape(4, 1)
    out = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=PartitionSpec("g"), out_specs=PartitionSpec("g"))
    )(x)
    # member at local index 2 (global rank 6) holds value 2.0
    np.testing.assert_allclose(np.asarray(out).reshape(-1), [2, 2, 2, 2])


def test_ppermute_shift():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    import paddle_tpu.distributed as dist

    devices = np.asarray(jax.devices()[:4])
    mesh = Mesh(devices, ("pp",))
    group = dist.new_group(ranks=[0, 1, 2, 3], axis_name="pp")
    perm = [(i, (i + 1) % 4) for i in range(4)]

    def body(x):
        return dist.ppermute(x, perm, group)

    x = jnp.arange(4, dtype=jnp.float32).reshape(4, 1)
    out = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=PartitionSpec("pp"), out_specs=PartitionSpec("pp"))
    )(x)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), [3, 0, 1, 2])
