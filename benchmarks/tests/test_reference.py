"""The plain reference against a two-token case worked out by hand (scalars
and ``math`` only), and its building blocks against their definitions."""

import math

import jax.numpy as jnp
import numpy as np

from reference import decoder as ref

R2 = math.sqrt(2.0)
CFG = {"hidden_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1,
       "rms_norm_eps": 0.0, "rope_theta": 10000.0}
EYE = jnp.eye(2, dtype=jnp.float32)
LAYER = {"wq": EYE, "wk": EYE, "wv": EYE, "wo": EYE, "w_gate": EYE, "w_up": EYE, "w_down": EYE,
         "norm_attn": jnp.ones(2), "norm_mlp": jnp.ones(2)}
TOP = {"embed": jnp.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]]), "final_norm": jnp.ones(2),
       "head": jnp.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])}


def silu(x):
    return x / (1.0 + math.exp(-x))


def rms(v):
    r = math.sqrt(sum(x * x for x in v) / len(v))
    return [x / r for x in v]


def by_hand():
    """tokens [0, 1]: embeddings e0 = [1, 0], e1 = [0, 1]; every matrix is the
    identity; head dim 2, so rope turns the pair (x0, x1) by `position` radians."""
    # position 0: norm(e0) = [sqrt2, 0]; q = k = v = that; rope(0) = identity;
    # one key only, so attention returns v0
    v0 = [R2, 0.0]
    h0 = [1.0 + R2, 0.0]
    n0 = rms(h0)  # [sqrt2, 0]
    h0 = [h0[0] + silu(n0[0]) * n0[0], h0[1] + silu(n0[1]) * n0[1]]
    # position 1: norm(e1) = [0, sqrt2]; q1 = k1 = rope_1([0, sqrt2]) = [-sqrt2 sin1, sqrt2 cos1]
    q1 = [-R2 * math.sin(1.0), R2 * math.cos(1.0)]
    k0, k1, v1 = [R2, 0.0], q1, [0.0, R2]
    s0 = (q1[0] * k0[0] + q1[1] * k0[1]) / R2
    s1 = (q1[0] * k1[0] + q1[1] * k1[1]) / R2
    p0 = math.exp(s0) / (math.exp(s0) + math.exp(s1))
    p1 = 1.0 - p0
    a1 = [p0 * v0[0] + p1 * v1[0], p0 * v0[1] + p1 * v1[1]]
    h1 = [0.0 + a1[0], 1.0 + a1[1]]
    n1 = rms(h1)
    h1 = [h1[0] + silu(n1[0]) * n1[0], h1[1] + silu(n1[1]) * n1[1]]
    logits = []
    for h in (h0, h1):
        n = rms(h)
        logits.append([n[0], n[1], 0.0])
    return logits


def test_two_tokens_by_hand():
    got = np.asarray(ref.forward_logits(jnp.array([0, 1]), {"top": TOP, "layers": [LAYER]}, CFG))
    np.testing.assert_allclose(got, np.array(by_hand()), rtol=1e-5, atol=1e-6)
    # and the cross entropy of predicting token 1 after token 0, token 2 after token 1
    want = -sum(row[t] - math.log(sum(math.exp(x) for x in row)) for row, t in zip(by_hand(), (1, 2)))
    loss = float(ref.sequence_loss_sum({"top": TOP, "layers": [LAYER]}, jnp.array([0, 1]), jnp.array([1, 2]), CFG))
    assert abs(loss - want) < 1e-5


def test_gqa_heads_share_their_kv_head():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(5, 4, 8)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(5, 2, 8)), jnp.float32)
    out = np.asarray(ref.attention(q, kv, kv))
    full = np.asarray(ref.attention(q, jnp.repeat(kv, 2, axis=1), jnp.repeat(kv, 2, axis=1)))
    np.testing.assert_allclose(out, full, rtol=1e-6)
    # causal: the first position sees only itself
    np.testing.assert_allclose(out[0], np.repeat(np.asarray(kv[0]), 2, axis=0), rtol=1e-5)


def test_adamw_first_step_moves_by_lr():
    p, g = jnp.array([1.0, -2.0]), jnp.array([0.5, -0.25])
    new, m, v = ref.adamw_update(p, g, jnp.zeros(2), jnp.zeros(2), 1, lr=0.1, beta1=0.9, beta2=0.999, eps=0.0, weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(new), [0.9, -1.9], rtol=1e-6)  # m_hat / sqrt(v_hat) = sign(g)
    np.testing.assert_allclose(np.asarray(m), 0.1 * np.asarray(g), rtol=1e-6)


def test_lower_precision_differs():
    rng = np.random.default_rng(1)
    x, w = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32), jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    exact = np.asarray(ref.matmul(x, w))
    for lower in ("bf16", "int8"):
        err = np.abs(np.asarray(ref.matmul(x, w, lower)) - exact).max()
        assert 0 < err < 0.5
