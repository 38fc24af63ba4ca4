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


# --- the carried-state kernel (kernels/ssm_scan.py) on the serving path -------------------------------------
# On a TPU ``RecurrentState.advance`` hands the state plane to one Pallas kernel; here the dispatch is steered
# onto that route with the kernel in the interpreter (``conftest.py::scan_scan_kernel_route``), and held against the
# XLA composition ``ssd_chunk``, which is the route the tests above took.

KERNEL_RTOL = 1e-5


def chunk_inputs(slots, rows, heads, head_dim, groups, state, seed):
    rng = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(rng.normal(0, 1, (slots, rows, heads, head_dim)), jnp.float32),
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (slots, rows, heads)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32),
        b=jnp.asarray(rng.normal(0, 1, (slots, rows, groups, state)), jnp.float32),
        c=jnp.asarray(rng.normal(0, 1, (slots, rows, groups, state)), jnp.float32),
        d_skip=jnp.asarray(rng.uniform(0.5, 1.5, (heads,)), jnp.float32),
        plane=jnp.asarray(rng.normal(0, 1, (slots, heads, head_dim, state)), jnp.float32),
    )


def kernel_chunk(t, dt, live, fresh, **tile):
    """``(y, the state after, carried)`` of one chunk with the kernel, in the interpreter, as its carry."""
    from paddle_tpu.incubate.nn.functional.mamba2 import _chunk
    from paddle_tpu.kernels.ssm_scan import ssm_state_scan

    seen = []

    def carry(*rows):
        seen[:] = ssm_state_scan(*rows, t["plane"], live, fresh, interpret=True, **tile)
        return seen

    y, state = _chunk(t["x"], dt, t["a"], t["b"], t["c"], t["d_skip"], carry)
    return y, state, seen[0]


@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("rows", [16, 1])
def test_the_kernel_is_the_xla_chunk_at_float32(scan_kernel_route, rows, groups):
    """Four slots: mid-sequence with every row valid, a request's first chunk
    over a plane that is not zero, a slot with some rows masked, a slot with
    no rows: ``y`` and the state against ``ssd_chunk`` at float32 tolerance,
    the idle slot's tile bit for bit."""
    from paddle_tpu.incubate.nn.functional.mamba2 import ssd_chunk_slots

    t = chunk_inputs(4, rows, 8, 8, groups, 16, seed=10 * rows + groups)
    q = np.array([rows, rows, max(rows - 5, 1), 0])
    fresh = jnp.asarray([False, True, False, False])
    dt = jnp.where(jnp.arange(rows)[None, :, None] < jnp.asarray(q)[:, None, None], t["dt"], 0.0)
    got_y, got_state = ssd_chunk_slots(t["x"], dt, t["a"], t["b"], t["c"], t["d_skip"], t["plane"],
                                       jnp.asarray(q > 0), fresh)
    want_y, want_state = ssd_chunk(t["x"], dt, t["a"], t["b"], t["c"], t["d_skip"],
                                   jnp.where(fresh[:, None, None, None], 0.0, t["plane"]))
    assert scan_kernel_route == [(4, 8, 8, 16)]
    for s in range(3):
        np.testing.assert_allclose(np.asarray(got_y[s, :q[s]]), np.asarray(want_y[s, :q[s]]), rtol=KERNEL_RTOL,
                                   atol=KERNEL_RTOL * float(np.abs(want_y).max()))
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), rtol=KERNEL_RTOL,
                               atol=KERNEL_RTOL * float(np.abs(want_state).max()))
    assert np.array_equal(np.asarray(got_state[3]), np.asarray(t["plane"][3]))
    assert np.isfinite(np.asarray(got_y)).all()  # rows past q_lens are garbage, never NaN


@pytest.mark.parametrize("cell_groups", [1, 2, 4])
def test_the_kernels_tile_does_not_change_its_numbers(cell_groups):
    """A grid cell of one, two or all four B/C groups: the same bits."""
    t = chunk_inputs(2, 16, 8, 8, 4, 16, seed=7)
    live, fresh = jnp.asarray([True, True]), jnp.asarray([False, False])
    want = kernel_chunk(t, t["dt"], live, fresh)
    got = kernel_chunk(t, t["dt"], live, fresh, cell_groups=cell_groups)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_masked_fresh_and_idle_slots_through_the_kernel(scan_kernel_route, monkeypatch):
    """``advance`` itself on the kernel route: the masked slot and the slot
    without rows keep their planes bit for bit, the first chunk drops what
    the slot held, and the live slots agree with the XLA route."""
    lv, rng = leaves(), np.random.default_rng(6)
    planes = (jnp.asarray(rng.normal(0, 1, (4, H, P, N)), jnp.float32),
              jnp.asarray(rng.normal(0, 1, (4, K - 1, W)), jnp.float32))
    xbc = rng.normal(0, 1, (4, C, W)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (4, C, H)).astype(np.float32)
    bt = batch([5, 5, 5, 0], [3, 5, 0, C], [True, False, True, True])  # live, masked, no rows, a first chunk
    y, new = advance(RecurrentState(*planes, batch=bt), xbc, dt, lv)
    from paddle_tpu.kernels import select

    with monkeypatch.context() as xla_route:
        xla_route.setattr(select, "pallas_enabled", lambda *args, **kwargs: False)
        want_y, want = advance(RecurrentState(*planes, batch=bt), xbc, dt, lv)
    for s in (1, 2):
        assert np.array_equal(np.asarray(new.ssm[s]), np.asarray(planes[0][s]))
        assert np.array_equal(np.asarray(new.conv[s]), np.asarray(planes[1][s]))
    for s, q in ((0, 3), (3, C)):
        np.testing.assert_allclose(np.asarray(y[s, :q]), np.asarray(want_y[s, :q]), rtol=KERNEL_RTOL, atol=KERNEL_RTOL)
        np.testing.assert_allclose(np.asarray(new.ssm[s]), np.asarray(want.ssm[s]), rtol=KERNEL_RTOL, atol=KERNEL_RTOL)
    clean = advance(RecurrentState.zeros(4, spec(), bt), xbc, dt, lv)[1]
    assert np.array_equal(np.asarray(new.ssm[3]), np.asarray(clean.ssm[3]))  # seq_lens == 0: started from zeros


def test_256_single_rows_through_the_kernel_do_not_drift(scan_kernel_route):
    """A decode of 256 tokens, one row a step, every step through the kernel,
    against the token-at-a-time recurrence in float64: the state is carried in
    float32 through 256 in-place updates and stays within the chunk test's
    tolerance."""
    lv, rng = leaves(), np.random.default_rng(8)
    steps = 256
    xbc = rng.normal(0, 1, (SLOTS, steps, W)).astype(np.float32)
    dt = rng.uniform(0.001, 0.1, (SLOTS, steps, H)).astype(np.float32)
    state, got = RecurrentState.zeros(SLOTS, spec()), []
    step = jax.jit(lambda planes, seq, x, d: advance(RecurrentState(*planes, batch=batch_of(seq)), x, d, lv))

    def batch_of(seq):
        return PagedBatch(jnp.zeros((SLOTS, 1), jnp.int32), seq, jnp.ones((SLOTS,), bool), jnp.ones((SLOTS,), jnp.int32))

    for i in range(steps):
        y, state = step(state.planes, jnp.full((SLOTS,), i, jnp.int32), xbc[:, i:i + 1], dt[:, i:i + 1])
        got.append(np.asarray(y[:, 0]))
    got = np.stack(got, axis=1)
    for s in range(SLOTS):
        y, ssm, _tail = recurrence(xbc[s], dt[s], lv)
        assert np.abs(got[s] - y).max() < TOL * max(1.0, np.abs(y).max())
        assert np.abs(np.asarray(state.ssm[s]) - ssm).max() < TOL * max(1.0, np.abs(ssm).max())


@pytest.mark.parametrize("live", ["000000", "100000", "000001", "011010", "101101", "111111"])
def test_idle_slots_before_between_and_after_live_ones_keep_their_tiles(live):
    """An idle slot's grid step holds a live neighbour's tile (so that its own
    is neither read nor written): whatever the pattern, and at every tile, the
    live slots get ``ssd_chunk``'s numbers and the idle ones keep theirs bit
    for bit, also where no slot is live at all."""
    from paddle_tpu.kernels.ssm_scan import _held_slots

    rows = np.array([ch == "1" for ch in live])
    held = np.asarray(_held_slots(jnp.asarray(rows)))
    assert all(held[s] == s for s in np.flatnonzero(rows))  # a live slot holds its own tile
    assert all(np.diff(np.flatnonzero(held == h)).max(initial=1) == 1 for h in set(held))  # a tile's steps are a run
    assert not rows.any() or set(held) == set(np.flatnonzero(rows))  # and no idle slot's tile is ever held
    t = chunk_inputs(len(live), 16, 8, 8, 4, 16, seed=11)
    fresh = jnp.asarray(rows & (np.arange(len(live)) % 3 == 0))
    dt = jnp.where(jnp.asarray(rows)[:, None, None], t["dt"], 0.0)
    want_y, want_state = ssd_chunk(t["x"], dt, t["a"], t["b"], t["c"], t["d_skip"],
                                   jnp.where(fresh[:, None, None, None], 0.0, t["plane"]))
    for cell_groups in (1, 4):
        got_y, state, carried = kernel_chunk(t, dt, jnp.asarray(rows), fresh, cell_groups=cell_groups)
        assert np.array_equal(np.asarray(state)[~rows], np.asarray(t["plane"])[~rows])
        assert not np.asarray(carried)[~rows].any()
        np.testing.assert_allclose(np.asarray(state)[rows], np.asarray(want_state)[rows], rtol=KERNEL_RTOL, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_y)[rows], np.asarray(want_y)[rows], rtol=KERNEL_RTOL, atol=1e-4)


def test_scan_state_bench_rehearses_off_the_chip(capsys, monkeypatch):
    """``tools/scan_state_bench.py --interpret``: every row (the XLA
    operations, the kernel, the three bodies that only size its parts) runs
    once at a tiny size in the interpreter and prints no time; then the
    kernel against ``ssd_chunk`` and both against float64, idle slots bit for
    bit."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "tools" / "scan_state_bench.py"
    spec = importlib.util.spec_from_file_location("scan_state_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr("sys.argv", ["scan_state_bench.py", "--interpret", "--slots", "4", "--heads", "4", "--head-dim", "8",
                                     "--state", "16", "--groups", "2", "--live", "3", "--cells", "1,2"])
    bench.main()
    out = capsys.readouterr().out
    assert out.count("ran (interpreter: no time)") == 1 + 4 * 2 and " ms a call" not in out
    assert "slots without rows bit for bit: True" in out and "the plane comes back bit for bit: True" in out
    gaps = [float(v) for line in out.splitlines() if "float64" in line for v in line.split()[-3::2]]
    assert len(gaps) == 4 and max(gaps) < TOL
