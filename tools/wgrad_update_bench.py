#!/usr/bin/env python
"""Time a weight gradient and its AdamW update, apart and together, on the chip.

Run by hand through the chip tool, by no benchmark cell:

    python tools/wgrad_update_bench.py                          # the train cell's MLP shapes
    python tools/wgrad_update_bench.py --weights 4096x4096,4096x1024   # the attention projections
    python tools/wgrad_update_bench.py --hlo chiprun_out/wgrad  # also write each case's optimised HLO
    python tools/wgrad_update_bench.py --rehearse               # tiny shapes, off the chip, no times

For each weight ``[k, m]`` (gradient ``x[n, k]^T @ dy[n, m]``, bf16 operands,
contraction over the ``n`` tokens) it times four programs:

    (a) wgrad            the gradient alone, rounded to bf16, as XLA compiles it
    (b) wgrad+update     the gradient and that leaf's update in one jit, free to
                         fuse: what a ``to_static`` train step handed XLA before
                         PR 34
    (c) barrier          the same with the gradient passed through
                         ``jax.lax.optimization_barrier`` before the update: what
                         ``Optimizer._run_fused`` does to every leaf since PR 34
    (d) update           the update alone, the gradient an argument

The update is the optimizer's own (``Optimizer._update_leaf`` of an ``AdamW``
with ``multi_precision``: float32 master weight and moments, bf16 parameter),
its state donated as the train step donates it. Printed: ms a call, the share
of the MXU's bf16 peak on the gradient's ``2 n k m`` flops, and GB/s on the
update's 26 bytes an element (read master and two moments, write them and the
bf16 parameter: 12 + 14) plus the gradient's 2 where it is read from HBM.

A call's time is the host clock over ``--reps`` back-to-back calls ending in
one ``block_until_ready``. ``--rehearse`` runs every program once at a tiny
size on whatever backend is there and prints no time: a rehearsal of the
script, not a measurement.
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

import paddle_tpu

PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}  # bf16, Google Cloud "TPU v5e"
PEAK_BYTES = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}  # HBM, the same page
UPDATE_BYTES = 26  # an element: f32 master + 2 f32 moments read and written, bf16 parameter written
CASES = ("wgrad", "wgrad+update", "barrier", "update")
WEIGHT_DECAY = 0.01  # the cell's (benchmarks/workloads/mistral7b.train_2k.json), with the optimizer in main()


def build_cases(opt, weight_decay):
    """The four programs over one leaf. State is (parameter, {master, moments})."""
    lr = jnp.asarray(opt.get_lr(), jnp.float32)

    def wgrad(x, dy):
        g = jax.lax.dot_general(x, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return g.astype(x.dtype)  # p.grad has the parameter's dtype

    def update(p, st, g, step):
        return opt._update_leaf(p, g, st, lr, step, weight_decay)

    def fused(p, st, x, dy, step):
        return update(p, st, wgrad(x, dy), step)

    def barrier(p, st, x, dy, step):
        return update(p, st, jax.lax.optimization_barrier(wgrad(x, dy)), step)

    return {
        "wgrad": jax.jit(wgrad),
        "wgrad+update": jax.jit(fused, donate_argnums=(0, 1)),
        "barrier": jax.jit(barrier, donate_argnums=(0, 1)),
        "update": jax.jit(update, donate_argnums=(0, 1)),
    }


def _stepper(fn, state, extra):
    """``call()`` dispatches ``fn(*state, *extra)`` once and returns what to wait
    on; a case with state (parameter, optimizer state) donates it and gets the
    new one back for its next call."""
    box = [state]

    def call():
        out = fn(*box[0], *extra)
        if box[0]:
            box[0] = out
        return out

    return call


def _time(call, reps):
    jax.block_until_ready(call())  # compile + settle
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = call()
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / reps)
    return statistics.median(rounds)


def bench_leaf(opt, n, k, m, dtype, reps, peaks, hlo_dir):
    """Seconds a call of each case over one ``[k, m]`` leaf; ``peaks`` None
    rehearses (one call a case, no time)."""
    kx, kd, kw, kg = jax.random.split(jax.random.PRNGKey(k * 31 + m), 4)
    x = jax.random.normal(kx, (n, k), jnp.float32).astype(dtype)
    dy = (jax.random.normal(kd, (n, m), jnp.float32) / n).astype(dtype)
    g = (jax.random.normal(kg, (k, m), jnp.float32) * 1e-3).astype(dtype)
    step = jnp.asarray(1, jnp.int32)
    cases = build_cases(opt, WEIGHT_DECAY)
    flops, elems = 2.0 * n * k * m, k * m
    out = {}

    for name in CASES:
        extra = {"wgrad": (x, dy), "update": (g, step)}.get(name, (x, dy, step))
        state = ()
        if name != "wgrad":  # fresh state a case: the one before donated its own
            master = jax.random.normal(kw, (k, m), jnp.float32) * 0.02
            state = (master.astype(dtype), dict(opt.init_state(master), master_weight=master))
            del master
        if hlo_dir is not None:
            hlo_dir.mkdir(parents=True, exist_ok=True)
            text = cases[name].lower(*state, *extra).compile().as_text()
            (hlo_dir / f"{name.replace('+', '_')}_{k}x{m}.hlo.txt").write_text(text)
        call = _stepper(cases[name], state, extra)
        del state
        label = f"[{k:5d},{m:5d}] {name:13s}"
        if peaks is None:
            jax.block_until_ready(call())
            out[name] = None
            print(f"{label} ran (rehearsal: no time)", flush=True)
            continue
        t = out[name] = _time(call, reps)
        if name == "update":
            moved = elems * (UPDATE_BYTES + 2)
            rate = f"{moved / t / 1e9:5.0f} GB/s ({100 * moved / t / peaks[1]:4.1f} % of the HBM peak)"
        else:
            rate = f"{100 * flops / t / peaks[0]:5.1f} % of the MXU's peak on the gradient's flops"
        print(f"{label} {t * 1e3:8.2f} ms  {rate}", flush=True)
    if peaks is not None:
        a, b, c, d = (out[name] for name in CASES)
        print(f"[{k:5d},{m:5d}] (a)+(d) {1e3 * (a + d):.2f} ms; fused (b) costs {1e3 * (b - a - d):+.2f} ms over it, "
              f"the barrier (c) {1e3 * (c - a - d):+.2f} ms; at peak: gradient {1e3 * flops / peaks[0]:.2f} ms, "
              f"update {1e3 * elems * UPDATE_BYTES / peaks[1]:.2f} ms", flush=True)
    return out


def _weights(spec):
    return [tuple(int(d) for d in w.split("x")) for w in spec.split(",")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=16384, help="tokens (the contracted rows)")
    ap.add_argument("--weights", default="4096x14336,14336x4096", help="kxm[,kxm..]: the leaves' shapes")
    ap.add_argument("--dtype", default="bfloat16", choices=("bfloat16", "float32"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--hlo", default=None, help="directory for each case's optimised HLO")
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, any backend, no times")
    a = ap.parse_args(argv)

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        sys.exit(f"needs a TPU (found {dev.platform}); --rehearse runs the script off the chip")
    # an unknown chip is an error, not a default
    peaks = None if a.rehearse else (PEAK_FLOPS[dev.device_kind], PEAK_BYTES[dev.device_kind])
    n, weights = (64, [(32, 48), (48, 32)]) if a.rehearse else (a.n, _weights(a.weights))
    dtype = jnp.dtype(a.dtype)
    print(f"device {dev.device_kind} x{jax.device_count()}  n={n} {dtype.name}  AdamW multi_precision", flush=True)

    # the parameter list only has to be non-empty: the functional core is what runs
    opt = paddle_tpu.optimizer.AdamW(
        learning_rate=1e-4, beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=WEIGHT_DECAY,
        parameters=[paddle_tpu.Parameter(jnp.zeros((1,), dtype))], multi_precision=True,
    )
    hlo_dir = None if a.hlo is None else Path(a.hlo)
    results = {(k, m): bench_leaf(opt, n, k, m, dtype, a.reps, peaks, hlo_dir) for k, m in weights}
    return results


if __name__ == "__main__":
    main()
