#!/usr/bin/env python
"""Time the fused loss head's three Pallas kernels over block shapes, on the chip.

Run by hand through the chip tool, by no benchmark cell:

    python tools/loss_head_bench.py                       # the train cell's shapes
    python tools/loss_head_bench.py --h 2048 --v 49152    # Ouro's width
    python tools/loss_head_bench.py --recompute           # the recompute pair beside the stored-d pair
    python tools/loss_head_bench.py --interpret --n 64 --v 512 --h 128 --tiles 16,32x128,256

For each kernel (``fused_loss_fwd``, ``fused_loss_dx``, ``fused_loss_dw``) and
each (row block, vocab block) it prints the milliseconds a call, the share of
the MXU's bf16 peak on the matmuls the kernel does (forward 1; dX 2: the
block's logits again, then ``d @ W``; dW 1 over the ``d`` that dX stored), the
bytes the grid moves to and from HBM and the flops per such byte. Then the
whole head (``jax.vjp`` through ``_pallas_path``: the pads and XLA passes
around the kernels included) at the geometry's own tiles. ``--recompute`` is a
switch of this tool, not of the program: it adds the pair the program runs
where ``d`` is over its share of device memory (``dx_rc`` stores nothing,
``dw_rc`` recomputes ``d``: 2 matmuls) and the whole head on it, so before and
after come from one call on one chip. ``--contraction`` adds the alternative
that was timed and not taken (PERF.md, PR 30): a forward whose grid also tiles
the contraction over ``h``, float32 logits in a VMEM scratch.

A call's time is the host clock over ``--reps`` back-to-back calls ending in
one ``block_until_ready``; a trace reads each call ~1 ms lower (PERF.md, PR 28).
``--interpret`` runs every configuration once in Pallas' interpreter, off the
chip, and prints no time: a rehearsal of the script, not a measurement.
"""

from __future__ import annotations

import argparse
import itertools
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.kernels import fused_loss as fl
from paddle_tpu.kernels import select

PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}  # bf16, Google Cloud "TPU v5e"
MATMULS = {"fwd": 1, "dx": 2, "dw": 1, "dx_rc": 2, "dw_rc": 2, "fwd_k": 1}
VMEM_MODEL = {"dx_rc": "dx", "dw_rc": "dw_recompute"}  # _vmem_need's name of a row's kernel


def _time(fn, args, reps):
    jax.block_until_ready(fn(*args))  # compile + settle
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / reps)
    return statistics.median(rounds)


def _streamed_bytes(kernel, br, bv, n, v, h, item):
    """HBM bytes the grid reads: the operand that stays is read once, the one
    that streams once per block of the other dimension; ``d`` once, written by
    dX and read by dW, where it is stored."""
    x, w, d = n * h * item, v * h * item, n * v * item
    if kernel == "dw":
        return d + (v // bv) * x
    if kernel == "dw_rc":
        return w + (v // bv) * x
    return x + (n // br) * w + (d if kernel == "dx" else 0)


def _fwd_contraction(n, v, h, br, bv, bh, vocab_major, interpret):
    """Option 2 of ISSUE 30: grid (rows, vocab, h); x and W blocks are
    ``[br, bh]`` / ``[bh, bv]``, the logits accumulate in a float32 scratch and
    the online softmax runs at the last ``h`` step. Both operands stream."""

    def kernel(x_ref, w_ref, lab_ref, m_ref, l_ref, tl_ref, s_ref):
        j, k = pl.program_id(1), pl.program_id(2)

        @pl.when((j == 0) & (k == 0))
        def _():
            m_ref[...] = jnp.full_like(m_ref[...], fl.NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref[...])
            tl_ref[...] = jnp.zeros_like(tl_ref[...])

        @pl.when(k == 0)
        def _():
            s_ref[...] = jnp.zeros_like(s_ref[...])

        dims = (((1,), (1,)), ((), ())) if vocab_major else (((1,), (0,)), ((), ()))
        s_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32
        )

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
            logits = jnp.where(cols < v, s_ref[...], fl.NEG_INF)
            m = m_ref[...]
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            l_ref[...] = l_ref[...] * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(logits - m_new), axis=-1, keepdims=True
            )
            m_ref[...] = m_new
            tl_ref[...] += jnp.sum(
                jnp.where(cols == lab_ref[...], logits, 0.0), axis=-1, keepdims=True
            )

    col = pl.BlockSpec((br, 1), lambda i, j, k: (i, 0))
    w_spec = (
        pl.BlockSpec((bv, bh), lambda i, j, k: (j, k))
        if vocab_major
        else pl.BlockSpec((bh, bv), lambda i, j, k: (k, j))
    )

    def run(x2, wp, lab):
        return pl.pallas_call(
            kernel,
            grid=(n // br, v // bv, h // bh),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20,
            ),
            in_specs=[pl.BlockSpec((br, bh), lambda i, j, k: (i, k)), w_spec, col],
            out_specs=[col, col, col],
            out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)] * 3,
            scratch_shapes=[pltpu.VMEM((br, bv), jnp.float32)],
            interpret=interpret,
            name="fused_loss_fwd_contraction",
        )(x2, wp, lab.reshape(n, 1))

    return run


def _pairs(spec):
    """``256,512x512,1024`` -> every (row, vocab) pair; ``256,512`` -> the square."""
    rows, _, cols = spec.partition("x")
    rows = [int(r) for r in rows.split(",")]
    cols = [int(c) for c in cols.split(",")] if cols else rows
    return list(itertools.product(rows, cols))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=16384, help="tokens (rows of x)")
    ap.add_argument("--v", type=int, default=32768, help="vocabulary")
    ap.add_argument("--h", type=int, default=4096, help="hidden size")
    ap.add_argument("--dtype", default="bfloat16", choices=("bfloat16", "float32"))
    ap.add_argument("--vocab-major", action="store_true", help="tied layout, W [V, H]")
    ap.add_argument("--tiles", default="256,512,1024", help="rows[,rows..][xcols[,cols..]]")
    ap.add_argument("--kernels", default="fwd,dx,dw")
    ap.add_argument("--recompute", action="store_true",
                    help="also time the pair that stores no d (dx_rc, dw_rc) and the head on it")
    ap.add_argument("--contraction", default="", help="br:bv:bh[,..] for the h-tiled forward")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args()
    GLOBAL_FLAGS.set("enable_metrics", True)  # the program's counters count only under it

    dev = jax.devices()[0]
    if not a.interpret and dev.platform != "tpu":
        sys.exit(f"needs a TPU (found {dev.platform}); --interpret rehearses the script off the chip")
    peak = None if a.interpret else PEAK_FLOPS[dev.device_kind]  # an unknown chip is an error
    dtype = jnp.dtype(a.dtype)
    item = dtype.itemsize
    n, v, h = a.n, a.v, a.h
    print(f"device {dev.device_kind} x{jax.device_count()}  n={n} v={v} h={h} {dtype.name} "
          f"{'[V,H]' if a.vocab_major else '[H,V]'}  vmem {fl._vmem_capacity() >> 20} MiB  "
          f"hbm {fl._hbm_capacity() / 2**30:.2f} GiB  d {n * v * item / 2**30:.2f} GiB "
          f"({'stored' if fl._stores_d(n, v, item) else 'recomputed'})", flush=True)

    key = jax.random.PRNGKey(0)
    kx, kw, kl = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, h), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (v, h) if a.vocab_major else (h, v), jnp.float32) * 0.02).astype(dtype)
    lab = jax.random.randint(kl, (n,), 0, v, jnp.int32)
    lse = jnp.full((n,), 10.0, jnp.float32)
    gc = jnp.full((n,), 1.0 / n, jnp.float32)
    # what dW reads where dX stored it: any values time the same
    d = (jax.random.normal(kl, (n, v), jnp.float32) / n).astype(dtype)

    def report(kernel, tile, fn, args, streamed, vmem):
        label = f"{kernel:6s} {'x'.join(map(str, tile)):>14s}"
        if a.interpret:
            jax.block_until_ready(fn(*args))
            print(f"{label}  ran (interpreter: no time)", flush=True)
            return
        try:
            t = _time(fn, args, a.reps)
        except Exception as e:  # noqa: BLE001 - a tile the compiler refuses is a row of the table
            print(f"{label}  refused: {str(e).splitlines()[0][:120]}", flush=True)
            return
        flops = MATMULS[kernel] * 2.0 * n * v * h
        print(f"{label}  {t * 1e3:8.2f} ms  {100 * flops / t / peak:5.1f} % of peak  "
              f"{streamed / 1e9:6.2f} GB streamed ({flops / streamed:6.0f} flop/B, "
              f"{streamed / t / 1e9:5.0f} GB/s)  vmem {vmem}", flush=True)

    kernels = a.kernels.split(",")
    if a.recompute:
        kernels += [k + "_rc" for k in ("dx", "dw") if k in kernels]
    for br, bv in _pairs(a.tiles):
        if n % br or v % bv:
            continue  # the kernels alone take no padding: time tiles that divide
        tiles = fl.LossTiles((br, bv), (br, bv), (br, bv))
        kw = dict(v=v, tile=(br, bv), vocab_major=a.vocab_major, interpret=a.interpret)
        cols = (lab.reshape(n, 1), lse.reshape(n, 1), gc.reshape(n, 1))
        runs = {
            "fwd": lambda: (jax.jit(fl._pallas_engines(
                n, v, v, h, tiles, a.vocab_major, a.interpret, True)[0]), (x, w, lab)),
            "dx": lambda: (jax.jit(lambda x, w: fl._run_dx(x, w, cols, store_d=True, **kw)), (x, w)),
            "dx_rc": lambda: (jax.jit(lambda x, w: fl._run_dx(x, w, cols, store_d=False, **kw)), (x, w)),
            "dw": lambda: (jax.jit(lambda x, d: fl._run_dw(x, w, cols, d, **kw)), (x, d)),
            "dw_rc": lambda: (jax.jit(lambda x, w: fl._run_dw(x, w, cols, None, **kw)), (x, w)),
        }
        for kernel in kernels:
            report(
                kernel, (br, bv), *runs[kernel](),
                _streamed_bytes(kernel, br, bv, n, v, h, item),
                f"{fl._vmem_need(VMEM_MODEL.get(kernel, kernel), br, bv, h, item, item) >> 20} MiB",
            )

    for spec in filter(None, a.contraction.split(",")):
        br, bv, bh = (int(s) for s in spec.split(":"))
        report(
            "fwd_k", (br, bv, bh),
            jax.jit(_fwd_contraction(n, v, h, br, bv, bh, a.vocab_major, a.interpret)), (x, w, lab),
            (v // bv) * n * h * item + (n // br) * v * h * item,  # both operands stream
            "under its 64 MiB",
        )

    def head(geom):
        def run(x, w):
            loss, vjp = jax.vjp(
                lambda xx, ww: fl._pallas_path(
                    xx, ww, lab, v=v, h=h, ignore_index=-100, reduction="mean",
                    vocab_major=a.vocab_major, interpret=a.interpret, block=geom,
                ),
                x, w,
            )
            return (loss,) + vjp(jnp.ones_like(loss))

        return run

    def report_head(label):
        geom = fl._block_geometry(n, v, h, item, item)
        print(f"geometry ({label}): fwd {geom.fwd}  dx {geom.dx}  dw {geom.dw}", flush=True)
        if a.interpret:
            jax.block_until_ready(jax.jit(head(geom))(x, w))
            print(f"head ({label}): ran (interpreter: no time)")
            return
        t = _time(jax.jit(head(geom)), (x, w), a.reps)
        print(f"head ({label}: forward + dX + dW at the geometry's tiles, pads and converts included): "
              f"{t * 1e3:.2f} ms a step; 3 useful matmuls at peak: {3 * 2.0 * n * v * h / peak * 1e3:.2f} ms")

    report_head("stored d" if fl._stores_d(n, v, item) else "d over its share: recomputed")
    if a.recompute and fl._stores_d(n, v, item):
        capacity, fl._hbm_capacity = fl._hbm_capacity, lambda: 0  # no d fits: the program's other pair
        try:
            report_head("recomputed")
        finally:
            fl._hbm_capacity = capacity
    print(f"backward passes built: {select.loss_backward_counts()}")


if __name__ == "__main__":
    main()
