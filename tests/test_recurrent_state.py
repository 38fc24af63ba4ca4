"""``RecurrentState`` (``inference/paged_kv.py``), the per-slot state of a
state-space block beside ``PagedKV``, and the chunk functions under it
(``incubate/nn/functional/mamba2.py``), held against a per-token recurrence
written out here in numpy float64.

Float32 on the CPU at matmul precision "highest" (``conftest.py``): the chunked
form and the recurrence differ by the order of float32 sums, a few 1e-6 on
values of order 1; ``TOL`` is 2e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.functional.mamba2 import causal_conv_chunk, ssd_chunk, ssd_sequence
from paddle_tpu.inference.paged_kv import RECURRENT, PagedBatch, RecurrentState

TOL = 2e-5
H, P, G, N, K = 4, 8, 2, 16, 4
W = H * P + 2 * G * N
SLOTS, C = 3, 8


def leaves(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "conv_w": rng.uniform(-0.5, 0.5, (K, W)).astype(np.float32),
        "conv_b": rng.uniform(-0.5, 0.5, (W,)).astype(np.float32),
        "a": -rng.uniform(1.0, 16.0, (H,)).astype(np.float32),
        "d": rng.uniform(0.5, 1.5, (H,)).astype(np.float32),
    }


def recurrence(xbc, dt, lv):
    """One sequence, a token at a time, float64: ``(y [T, H, P], state, the conv's last K-1 inputs)``."""
    t = xbc.shape[0]
    padded = np.concatenate([np.zeros((K - 1, W)), xbc.astype(np.float64)])
    conv = sum(padded[j:j + t] * lv["conv_w"][j] for j in range(K)) + lv["conv_b"]
    act = conv / (1.0 + np.exp(-conv))
    x = act[:, :H * P].reshape(t, H, P)
    b = np.repeat(act[:, H * P:H * P + G * N].reshape(t, G, N), H // G, axis=1)
    c = np.repeat(act[:, H * P + G * N:].reshape(t, G, N), H // G, axis=1)
    state, ys = np.zeros((H, P, N)), []
    for i in range(t):
        state = np.exp(dt[i] * lv["a"])[:, None, None] * state + (dt[i][:, None] * x[i])[:, :, None] * b[i][:, None, :]
        ys.append(np.einsum("hpn,hn->hp", state, c[i]) + lv["d"][:, None] * x[i])
    return np.stack(ys), state, padded[t:t + K - 1]


def spec():
    return RecurrentState.spec(H, P, N, K, W, jnp.float32)


def batch(seq_lens, q_lens, mask=None):
    mask = np.ones(len(q_lens), bool) if mask is None else np.asarray(mask)
    return PagedBatch(jnp.zeros((len(q_lens), 1), jnp.int32), jnp.asarray(seq_lens, jnp.int32),
                      jnp.asarray(mask), jnp.asarray(q_lens, jnp.int32))


def advance(state, xbc, dt, lv):
    return state.advance(jnp.asarray(xbc), jnp.asarray(dt), jnp.asarray(lv["conv_w"]), jnp.asarray(lv["conv_b"]),
                         jnp.asarray(lv["a"]), jnp.asarray(lv["d"]), G)


def test_the_spec_says_what_a_slot_holds_and_the_planes_cross_jit():
    s = spec()
    assert s.kind == RECURRENT and [shape for shape, _ in s.planes] == [(H, P, N), (K - 1, W)]
    assert s.unit_bytes == 4 * (H * P * N + (K - 1) * W)
    state = RecurrentState.zeros(SLOTS, s, batch([0] * SLOTS, [0] * SLOTS))
    assert [p.shape for p in state.planes] == [(SLOTS, H, P, N), (SLOTS, K - 1, W)]
    flat = jax.tree.leaves(state)
    assert len(flat) == 2 + 4 and flat[0] is state.ssm and flat[1] is state.conv  # planes first, then the batch
    back = jax.jit(lambda st: st)(state)
    assert isinstance(back, RecurrentState) and back.ssm.shape == state.ssm.shape


@pytest.mark.parametrize("lengths", [(21, 8, 3), (16, 1, 9)])
def test_chunks_of_rows_continue_the_recurrence(lengths):
    """Three slots fed in chunks of C rows, the last of each partial, then
    rows of one (decode), against the token-at-a-time recurrence."""
    lv, rng = leaves(), np.random.default_rng(1)
    total = [n + 4 for n in lengths]  # 4 single rows after each prompt
    xbc = [rng.normal(0, 1, (t, W)).astype(np.float32) for t in total]
    dt = [rng.uniform(0.001, 0.1, (t, H)).astype(np.float32) for t in total]
    want = [recurrence(x, d, lv) for x, d in zip(xbc, dt)]
    state = RecurrentState.zeros(SLOTS, spec())
    done, got = [0] * SLOTS, [[] for _ in range(SLOTS)]
    while any(d < t for d, t in zip(done, total)):
        q = [min(C, n - d) if d < n else min(1, t - d) for d, n, t in zip(done, lengths, total)]
        rows_x, rows_dt = np.full((SLOTS, C, W), 7.0, np.float32), np.full((SLOTS, C, H), 0.5, np.float32)  # garbage past q
        for s in range(SLOTS):
            rows_x[s, :q[s]], rows_dt[s, :q[s]] = xbc[s][done[s]:done[s] + q[s]], dt[s][done[s]:done[s] + q[s]]
        state = RecurrentState(*state.planes, batch=batch(done, q))
        y, state = advance(state, rows_x, rows_dt, lv)
        for s in range(SLOTS):
            got[s].append(np.asarray(y[s, :q[s]]))
            done[s] += q[s]
    for s in range(SLOTS):
        y, ssm, tail = want[s]
        assert np.abs(np.concatenate(got[s]) - y).max() < TOL * max(1.0, np.abs(y).max())
        assert np.abs(np.asarray(state.ssm[s]) - ssm).max() < TOL * max(1.0, np.abs(ssm).max())
        assert np.abs(np.asarray(state.conv[s]) - tail).max() < TOL


def test_padded_rows_masked_slots_and_idle_slots_leave_state_unchanged():
    lv, rng = leaves(), np.random.default_rng(2)
    planes = (jnp.asarray(rng.normal(0, 1, (SLOTS, H, P, N)), jnp.float32),
              jnp.asarray(rng.normal(0, 1, (SLOTS, K - 1, W)), jnp.float32))
    xbc = rng.normal(0, 1, (SLOTS, C, W)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (SLOTS, C, H)).astype(np.float32)
    # slot 0: 3 valid rows; slot 1: masked (its q_lens says 5: the mask rules); slot 2: live, no rows
    _y, new = advance(RecurrentState(*planes, batch=batch([5, 5, 5], [3, 5, 0], [True, False, True])), xbc, dt, lv)
    for s in (1, 2):
        assert np.array_equal(np.asarray(new.ssm[s]), np.asarray(planes[0][s]))
        assert np.array_equal(np.asarray(new.conv[s]), np.asarray(planes[1][s]))
    # rows past q_lens change nothing: the same 3 rows followed by other garbage give the same state, bitwise
    xbc2, dt2 = xbc.copy(), dt.copy()
    xbc2[0, 3:], dt2[0, 3:] = -3.0, 9.0
    _y, again = advance(RecurrentState(*planes, batch=batch([5, 5, 5], [3, 5, 0], [True, False, True])), xbc2, dt2, lv)
    assert np.array_equal(np.asarray(again.ssm[0]), np.asarray(new.ssm[0]))
    assert np.array_equal(np.asarray(again.conv[0]), np.asarray(new.conv[0]))
    assert not np.array_equal(np.asarray(new.ssm[0]), np.asarray(planes[0][0]))
    # the conv tail moved by q_lens rows only: the last K-1 of (old tail, 3 new rows)
    assert np.array_equal(np.asarray(new.conv[0]), xbc[0, :3])


def test_a_first_chunk_starts_from_zero_whatever_the_slot_held():
    lv, rng = leaves(), np.random.default_rng(3)
    xbc = rng.normal(0, 1, (SLOTS, C, W)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (SLOTS, C, H)).astype(np.float32)
    dirty = (jnp.full((SLOTS, H, P, N), 3.0), jnp.full((SLOTS, K - 1, W), -2.0))
    q = [5, 5, 5]
    y_dirty, new_dirty = advance(RecurrentState(*dirty, batch=batch([0, 0, 7], q)), xbc, dt, lv)
    y_clean, new_clean = advance(RecurrentState.zeros(SLOTS, spec(), batch([0, 0, 7], q)), xbc, dt, lv)
    for s in (0, 1):  # seq_lens == 0: what the last request left is dropped
        assert np.array_equal(np.asarray(y_dirty[s, :5]), np.asarray(y_clean[s, :5]))
        assert np.array_equal(np.asarray(new_dirty.ssm[s]), np.asarray(new_clean.ssm[s]))
    assert not np.array_equal(np.asarray(new_dirty.ssm[2]), np.asarray(new_clean.ssm[2]))  # mid-sequence: kept


def test_fork_copies_a_slots_state_and_drops_the_no_fork_marker():
    rng = np.random.default_rng(4)
    planes = (jnp.asarray(rng.normal(0, 1, (SLOTS, H, P, N)), jnp.float32),
              jnp.asarray(rng.normal(0, 1, (SLOTS, K - 1, W)), jnp.float32))
    state = RecurrentState(*planes)
    forked = state.fork(jnp.asarray([2, 0, 0], jnp.int32), jnp.asarray([0, SLOTS, SLOTS], jnp.int32))
    for old, new in zip(planes, forked.planes):
        assert np.array_equal(np.asarray(new[0]), np.asarray(old[2]))
        assert np.array_equal(np.asarray(new[1:]), np.asarray(old[1:]))


def test_the_cacheless_sequence_scan_is_the_same_recurrence():
    lv, rng = leaves(), np.random.default_rng(5)
    t = 21
    xbc = rng.normal(0, 1, (2, t, W)).astype(np.float32)
    dt = rng.uniform(0.001, 0.1, (2, t, H)).astype(np.float32)
    act, _tail = causal_conv_chunk(jnp.asarray(xbc), jnp.zeros((2, K - 1, W)), jnp.asarray(lv["conv_w"]),
                                   jnp.asarray(lv["conv_b"]), jnp.zeros((2,), jnp.int32))
    x = act[..., :H * P].reshape(2, t, H, P)
    b = act[..., H * P:H * P + G * N].reshape(2, t, G, N)
    c = act[..., H * P + G * N:].reshape(2, t, G, N)
    y = ssd_sequence(x, jnp.asarray(dt), jnp.asarray(lv["a"]), b, c, jnp.asarray(lv["d"]), chunk=8)
    one, _state = ssd_chunk(x, jnp.asarray(dt), jnp.asarray(lv["a"]), b, c, jnp.asarray(lv["d"]), jnp.zeros((2, H, P, N)))
    for r in range(2):
        want = recurrence(xbc[r], dt[r], lv)[0]
        assert np.abs(np.asarray(y[r]) - want).max() < TOL * np.abs(want).max()
        assert np.abs(np.asarray(one[r]) - want).max() < TOL * np.abs(want).max()
