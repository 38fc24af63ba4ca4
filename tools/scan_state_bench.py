#!/usr/bin/env python
"""Time the state-space scan's carried-state kernel against the three XLA
operations it replaces, over tiles, on the chip.

Run by hand through the chip tool, by no benchmark cell:

    python tools/scan_state_bench.py                          # the hybrid cell's shapes
    python tools/scan_state_bench.py --live 22                # 22 of 32 slots with rows, as the chat mix keeps
    python tools/scan_state_bench.py --interpret --slots 3 --heads 4 --head-dim 8 --state 16 --groups 2

A row is ONE mixer block's call: every slot's ``[H, P, N]`` float32 state
read, contracted with ``C`` (``carried``), decayed, given the chunk's ``x (x)
B`` and written back. ``xla`` is ``mamba2.ssd_chunk``'s part of that (two
contractions at ``highest`` and a multiply-add, on a donated plane);
``kernel/<heads a cell>`` is ``kernels/ssm_scan.py`` at that tile. Beside
them, bodies that are NOT the program's and only size its parts: ``copy``
moves the tiles through VMEM and does nothing (the DMA's own pace at that
tile), ``builtin`` hands both contractions to ``dot_general`` at
``Precision.HIGHEST`` (six passes each), ``onepass`` at the default precision
(one bfloat16 pass: WRONG numbers, the least the MXU could cost); these three
take every slot as live, so with ``--live`` read ``kernel`` and ``copy``. Each row
prints milliseconds a call, the plane's bytes (one read, one write) over that
time, and the ratio to those bytes at the HBM peak.

A call's time is the host clock over ``--reps`` jitted programs of ``--chain``
calls each, every call taking the plane the last one wrote and rows of its own
(so nothing is hoisted or shared and the plane is donated through) and its
``carried`` rows added, scaled, to a running ``y`` (8 MB read and written a call
on every row alike), ended by one ``block_until_ready``. Then, on the device, one call of the kernel against
``ssd_chunk`` and both against a float64 recurrence on the host.
``--interpret`` runs every row once in Pallas' interpreter, off the chip, and
prints no time: a rehearsal of the script, not a measurement.
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.incubate.nn.functional import mamba2
from paddle_tpu.kernels import ssm_scan

PEAK_BYTES = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}  # HBM bytes/s, Google Cloud "TPU v5e"
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _copy_body(live_ref, fresh_ref, held_ref, decay_ref, c_ref, b_ref, xs_ref, s_ref, carried_ref, out_ref, **_):
    out_ref[...] = s_ref[...]
    carried_ref[...] = jnp.zeros_like(carried_ref)


def _dot_body(precision):
    """The kernel with its contractions handed to ``dot_general`` at ``precision``."""

    def body(live_ref, fresh_ref, held_ref, decay_ref, c_ref, b_ref, xs_ref, s_ref, carried_ref, out_ref, *, groups,
             heads, head_dim):
        from jax.experimental import pallas as pl

        gj, si = pl.program_id(0), pl.program_id(1)
        n, span = s_ref.shape[2], heads * head_dim
        fresh = fresh_ref[si] != 0
        for g in range(groups):
            rows, lanes = slice(g * span, (g + 1) * span), slice(g * n, (g + 1) * n)
            s0 = jnp.where(fresh, 0.0, s_ref[0, rows, :])
            carried_ref[0, :, rows] = jax.lax.dot_general(
                c_ref[0, :, lanes], s0, _NT, precision=precision, preferred_element_type=jnp.float32)
            added = jax.lax.dot_general(
                xs_ref[0, :, rows], b_ref[0, :, lanes], _TN, precision=precision, preferred_element_type=jnp.float32)
            for r in range(heads):
                local = slice(r * head_dim, (r + 1) * head_dim)
                head = slice(g * span + r * head_dim, g * span + (r + 1) * head_dim)
                out_ref[0, head, :] = decay_ref[si, (gj * groups + g) * heads + r] * s0[local] + added[local]

    return body


BODIES = {
    "kernel": None,  # the program's
    "copy": _copy_body,
    "builtin": _dot_body(jax.lax.Precision.HIGHEST),
    "onepass": _dot_body(jax.lax.Precision.DEFAULT),
}


def _xla_state_ops(c, b, xs, decay, plane, live, fresh):
    """What ``ssd_chunk`` does to the state, as ``RecurrentState.advance`` hands it over off the chip."""
    return mamba2._carry_xla(c, b, xs, decay, jnp.where(fresh[:, None, None, None], 0.0, plane))


def _chained(op, chain):
    """``chain`` calls in one program, each on the plane the last one wrote
    and on rows of its own (``c``, ``b``, ``xs`` are ``[chain, ...]``: with the
    same rows XLA would form ``x (x) B`` once for the whole chain). Every
    call's ``carried`` is consumed whole, as the program consumes it (scaled by
    the rows' decay and added to ``y``): a consumer of one element would let
    XLA drop the contraction that makes the rest. Both only on the ``xla`` row."""

    def run(c, b, xs, decay, plane, live, fresh):
        y = jnp.zeros_like(xs[0])
        for i in range(chain):
            carried, plane = op(c[i], b[i], xs[i], decay, plane, live, fresh)
            y = y + carried * decay[:, None, :, None]
        return y, plane

    return jax.jit(run, donate_argnums=(4,))


def _per_call(arr, chain):
    """``[chain, ...]``: the rows of call ``i`` are ``arr`` scaled by a factor of its own."""
    return arr[None] * (1.0 + 0.01 * jnp.arange(chain, dtype=arr.dtype)).reshape((chain,) + (1,) * arr.ndim)


def _time(fn, args, reps):
    args = list(args)
    _, args[4] = jax.block_until_ready(fn(*args))  # compile + settle
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            _, args[4] = fn(*args)
        jax.block_until_ready(args[4])
        rounds.append((time.perf_counter() - t0) / reps)
    return statistics.median(rounds)


def _kernel_op(body, cell_groups, interpret):
    """``ssm_state_scan`` (undecorated, so that each body is traced anew) with ``body`` as its kernel."""
    call = ssm_scan.ssm_state_scan.__wrapped__

    def op(*args):
        kept = ssm_scan._scan_kernel
        if body is not None:
            ssm_scan._scan_kernel = body
        try:
            return call(*args, cell_groups=cell_groups, interpret=interpret)
        finally:
            ssm_scan._scan_kernel = kept

    return op


def _inputs(a, seed):
    rng = np.random.default_rng(seed)
    s, n_rows, h, p, g, n = a.slots, a.rows, a.heads, a.head_dim, a.groups, a.state
    x = jnp.asarray(rng.normal(0, 1, (s, n_rows, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (s, n_rows, h)), jnp.float32)
    a_neg = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (s, n_rows, g, n)), jnp.float32)
    c = jnp.asarray(rng.normal(0, 1, (s, n_rows, g, n)), jnp.float32)
    d_skip = jnp.asarray(rng.uniform(0.5, 1.5, (h,)), jnp.float32)
    plane = jnp.asarray(rng.normal(0, 1, (s, h, p, n)), jnp.float32)
    live = jnp.asarray(rng.permutation(s) < (a.live or s))  # scattered: idle slots before, between and after
    fresh = live & (jnp.arange(s) == jnp.argmax(live))  # one slot starts a request
    dt = jnp.where(live[:, None, None], dt, 0.0)
    return x, dt, a_neg, b, c, d_skip, plane, live, fresh


def _float64_chunk(x, dt, a_neg, b, c, d_skip, plane, fresh, slots):
    """The per-token recurrence of ``slots`` in float64 on the host."""
    x, dt, a_neg, b, c, d_skip, plane = (np.asarray(t, np.float64) for t in (x, dt, a_neg, b, c, d_skip, plane))
    r = x.shape[2] // b.shape[2]
    ys, states = [], []
    for s in slots:
        state = np.zeros_like(plane[s]) if bool(fresh[s]) else plane[s].copy()
        rows = []
        for t in range(x.shape[1]):
            bt, ct = np.repeat(b[s, t], r, axis=0), np.repeat(c[s, t], r, axis=0)  # [H, N]
            state = np.exp(dt[s, t] * a_neg)[:, None, None] * state + (dt[s, t][:, None] * x[s, t])[:, :, None] * bt[:, None, :]
            rows.append(np.einsum("hpn,hn->hp", state, ct) + d_skip[:, None] * x[s, t])
        ys.append(np.stack(rows))
        states.append(state)
    return np.stack(ys), np.stack(states)


def _rel(got, want):
    return float(np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--rows", type=int, default=16, help="rows of the chunk (C)")
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--state", type=int, default=128, help="state size (N)")
    ap.add_argument("--groups", type=int, default=8, help="B/C groups (G)")
    ap.add_argument("--live", type=int, default=0, help="slots with rows (0: all)")
    ap.add_argument("--cells", default="1,2,4,8", help="groups a grid cell, each timed")
    ap.add_argument("--bodies", default="kernel,copy,builtin,onepass")
    ap.add_argument("--chain", type=int, default=12, help="calls a program (the cell has 12 mixer blocks)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args()

    dev = jax.devices()[0]
    if not a.interpret and dev.platform != "tpu":
        sys.exit(f"needs a TPU (found {dev.platform}); --interpret rehearses the script off the chip")
    peak = None if a.interpret else PEAK_BYTES[dev.device_kind]  # an unknown chip is an error
    heads_a_group = a.heads // a.groups
    plane_bytes = a.slots * a.heads * a.head_dim * a.state * 4
    print(f"device {dev.device_kind} x{jax.device_count()}  slots={a.slots} (live {a.live or a.slots}) C={a.rows} "
          f"H={a.heads} P={a.head_dim} N={a.state} G={a.groups}  plane {plane_bytes / 1e6:.1f} MB, read + written "
          f"{2 * plane_bytes / 1e6:.1f} MB a call" + ("" if a.interpret else
                                                       f" = {2 * plane_bytes / peak * 1e3:.3f} ms at the HBM peak"),
          flush=True)

    x, dt, a_neg, b, c, d_skip, plane, live, fresh = _inputs(a, 0)
    fresh_plane = jnp.where(fresh[:, None, None, None], 0.0, plane)
    handed = []  # what the chunk hands its carry: c, b, xs, decay (float32)

    def xla_carry(*rows):
        handed[:] = rows
        return mamba2._carry_xla(*rows, fresh_plane)

    mamba2._chunk(x, dt, a_neg, b, c, d_skip, xla_carry)  # op by op, for the operands alone
    c, b, xs, decay = handed
    want_y, want_state = jax.jit(mamba2.ssd_chunk)(x, dt, a_neg, b, c, d_skip, fresh_plane)

    chain = 1 if a.interpret else a.chain
    rows_of = tuple(_per_call(t, chain) for t in (c, b, xs))

    def report(label, op):
        if a.interpret:
            jax.block_until_ready(_chained(op, 1)(*rows_of, decay, plane + 0.0, live, fresh))
            print(f"{label:18s}  ran (interpreter: no time)", flush=True)
            return
        try:
            t = _time(_chained(op, chain), (*rows_of, decay, plane + 0.0, live, fresh), a.reps) / chain
        except Exception as e:  # noqa: BLE001 - a tile the compiler refuses is a row of the table
            print(f"{label:18s}  refused: {str(e).splitlines()[0][:140]}", flush=True)
            return
        print(f"{label:18s}  {t * 1e3:7.3f} ms a call  {2 * plane_bytes / t / 1e9:6.0f} GB/s of plane  "
              f"{t / (2 * plane_bytes / peak):5.2f} x the plane at the HBM peak", flush=True)

    report("xla", _xla_state_ops)
    for body in a.bodies.split(","):
        for cg in (int(v) for v in a.cells.split(",")):
            if a.groups % cg == 0:
                report(f"{body}/{cg * heads_a_group}", _kernel_op(BODIES[body], cg, a.interpret))

    # one call on the device: the kernel against ssd_chunk, both against float64
    kernel = _kernel_op(None, 0, a.interpret)
    got_y, got_state = jax.jit(lambda pl: mamba2._chunk(x, dt, a_neg, b, c, d_skip,
                                                        lambda *rows: kernel(*rows, pl, live, fresh)))(plane)
    rows = np.asarray(live)
    print(f"kernel against ssd_chunk on {dev.platform}: y {_rel(got_y[rows], np.asarray(want_y, np.float64)[rows]):.2e}  "
          f"state {_rel(got_state, np.asarray(want_state, np.float64)):.2e} (max gap over max value); slots without "
          f"rows bit for bit: {bool(np.array_equal(np.asarray(got_state)[~rows], np.asarray(plane)[~rows]))}")
    _, idle_state = jax.jit(kernel)(c, b, xs, decay, plane, jnp.zeros_like(live), fresh)
    print(f"no slot with rows: the plane comes back bit for bit: {bool(np.array_equal(np.asarray(idle_state), np.asarray(plane)))}")
    few = np.flatnonzero(rows)[:4]
    y64, state64 = _float64_chunk(x, dt, a_neg, b, c, d_skip, plane, np.asarray(fresh), few)
    for label, yy, ss in (("kernel", got_y, got_state), ("ssd_chunk", want_y, want_state)):
        print(f"{label:9s} against the float64 recurrence (slots {few.tolist()}): y {_rel(np.asarray(yy)[few], y64):.2e}  "
              f"state {_rel(np.asarray(ss)[few], state64):.2e}")


if __name__ == "__main__":
    main()
