"""The training loop: the ``@paddle.jit.to_static`` step as users write it
(forward with fused loss, backward, AdamW with fp32 master weights), a new
seeded batch every step prepared on the host while the previous step runs,
the loss read back (a sync) every step.

Set-up builds ONE step object with its state, drives it through its first
``check.steps`` steps by the window's own call and feed, and hands that same
object to the window. After the window the program's state is freed and the
plain reference follows those first steps from the same seeded weights and
batches; ``correct`` compares losses, per-leaf gradient norms (from the
optimizer's first moment after one step) and per-leaf norms of the parameters'
change (see ``compare``).
"""

from __future__ import annotations

import gc
import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from .. import arch, program, traffic
from .. import weights as W
from ..spans import GcPauses, Spans
from ..tracing import TraceSlice


def _leaf_norms(tree: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree
    )


def _norms_by_leaf(named: Dict[str, Any], cfg: Dict[str, Any], depth: int) -> Dict[str, float]:
    """``{"L0.<leaf>": norm, ..., "top.<leaf>": norm}`` from arrays keyed by the
    program's parameter paths."""
    import jax

    names = program.param_names(cfg, depth)
    norms = jax.device_get(jax.jit(_leaf_norms)(named))
    out = {f"top.{leaf}": float(norms[path]) for leaf, path in names["top"].items()}
    for i, layer in enumerate(names["layers"]):
        out.update({f"L{i}.{leaf}": float(norms[path]) for leaf, path in layer.items()})
    return out


def _change_norms(master: Dict[str, Any], cfg: Dict[str, Any], depth: int, seed: int, dtype: str) -> Dict[str, float]:
    """Per-leaf norm of (fp32 master weight now) - (seeded initial weight),
    the initial weights made again from the seed one layer at a time."""
    import jax
    import jax.numpy as jnp

    names = program.param_names(cfg, depth)
    diff = jax.jit(lambda now, init: jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))), now, init))
    out = {}
    groups = [("top", names["top"], W.top_weights(seed, cfg, dtype))]
    groups += [(f"L{i}", names["layers"][i], W.layer_weights(seed, cfg, i, dtype)) for i in range(depth)]
    for prefix, leaf_names, init in groups:
        now = {leaf: master[path] for leaf, path in leaf_names.items()}
        got = jax.device_get(diff(now, {leaf: init[leaf] for leaf in now}))
        out.update({f"{prefix}.{leaf}": float(v) for leaf, v in got.items()})
    return out


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> Dict[str, Any]:
    """The largest |got - ref| over leaves, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        gap = abs(got[leaf] - r) / max(r, floor)
        if not math.isfinite(gap):
            return {"gap": float("inf"), "leaf": leaf}
        if gap >= worst:
            worst, where = gap, leaf
    return {"gap": worst, "leaf": where}


def reference_steps(ctx: Any, cfg: Dict[str, Any], depth: int, stream: traffic.BatchStream,
                    steps: int, opt_kw: Dict[str, float], lower: Any = None) -> Dict[str, Any]:
    """The plain reference through the first ``steps`` steps: losses, per-leaf
    norms of the first gradient and of the parameters' change."""
    import jax
    import jax.numpy as jnp

    ref = arch.reference(cfg)
    dtype = ctx.cell["dtype"]
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    params = {"top": f32(W.top_weights(ctx.seed, cfg, dtype)),
              "layers": [f32(W.layer_weights(ctx.seed, cfg, i, dtype)) for i in range(depth)]}
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    update = jax.jit(
        lambda p, g, m_, v_, step: _tree_adamw(ref, p, g, m_, v_, step, opt_kw),
        donate_argnums=(0, 2, 3), static_argnums=(4,),
    )
    losses, grad_norms = [], None
    for k in range(steps):
        ids, labels = stream.get(k)
        loss, grads = ref.batch_loss_and_grads(params, jnp.asarray(ids), jnp.asarray(labels), cfg, lower)
        losses.append(float(loss))
        if k == 0:
            grad_norms = _flatten(jax.device_get(jax.jit(_leaf_norms)(grads)))
        params, m, v = update(params, grads, m, v, k + 1)
        del grads
    init = {"top": W.top_weights(ctx.seed, cfg, dtype),
            "layers": [W.layer_weights(ctx.seed, cfg, i, dtype) for i in range(depth)]}
    change = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y.astype(jnp.float32)))), a, b))(params, init)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": _flatten(jax.device_get(change))}


def _tree_adamw(ref: Any, p: Any, g: Any, m: Any, v: Any, step: int, kw: Dict[str, float]):
    import jax

    flat_p, tree = jax.tree_util.tree_flatten(p)
    out = [ref.adamw_update(a, b, c, d, step, **kw) for a, b, c, d in
           zip(flat_p, tree.flatten_up_to(g), tree.flatten_up_to(m), tree.flatten_up_to(v))]
    return tuple(jax.tree_util.tree_unflatten(tree, [o[i] for o in out]) for i in range(3))


def _flatten(tree: Dict[str, Any]) -> Dict[str, float]:
    out = {f"top.{k}": float(v) for k, v in tree["top"].items()}
    for i, layer in enumerate(tree["layers"]):
        out.update({f"L{i}.{k}": float(v) for k, v in layer.items()})
    return out


def compare(got: Dict[str, Any], ref: Dict[str, Any], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared, beside its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
    g = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    c = worst_leaf_gap(got["change_norms"], ref["change_norms"])
    rows = [
        {"name": "loss_gap_max", "value": loss_gap, "limit": limits["loss_gap_max"]},
        {"name": "grad_norm_gap_worst_leaf", "value": g["gap"], "limit": limits["grad_norm_gap_worst_leaf"], "at": g["leaf"]},
        {"name": "update_norm_gap_worst_leaf", "value": c["gap"], "limit": limits["update_norm_gap_worst_leaf"], "at": c["leaf"]},
    ]
    for r in rows:
        r["ok"] = bool(math.isfinite(r["value"]) and r["value"] <= r["limit"])
    return rows


def build(ctx: Any) -> Dict[str, Any]:
    """The one step object with its state (model, optimizer, jitted step)."""
    import paddle_tpu as paddle

    cfg = program.run_config(ctx.config, ctx.cell["driver"])
    step_kw = ctx.cell["step"]
    model = program.build_model(cfg, ctx.seed, ctx.cell["dtype"])
    opt_kw = step_kw["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=opt_kw["lr"], beta1=opt_kw["beta1"], beta2=opt_kw["beta2"],
        epsilon=opt_kw["eps"], weight_decay=opt_kw["weight_decay"],
        parameters=model.parameters(), multi_precision=True,
    )

    @paddle.jit.to_static
    def train_step(model, opt, ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    stream = traffic.BatchStream(ctx.mix, step_kw["batch"], cfg["vocab_size"], ctx.seed)
    return {"cfg": cfg, "model": model, "opt": opt, "step": train_step, "stream": stream,
            "feed": lambda k: tuple(paddle.to_tensor(a) for a in stream.get(k))}


def one_step(obj: Dict[str, Any], spans: Spans, batch: Any, k: int):
    """The call and feed every step goes through, in set-up and in the window:
    dispatch step ``k``, prepare batch ``k + 1`` on the host while it runs, then
    read the loss back (the sync)."""
    ids, labels = batch
    with spans.span("train.dispatch"):
        loss_t = obj["step"](obj["model"], obj["opt"], ids, labels)
    with spans.span("train.feed"):
        nxt = obj["feed"](k + 1)
    with spans.span("train.sync"):
        loss = float(loss_t)
    return loss, nxt


def first_steps(ctx: Any, obj: Dict[str, Any], spans: Spans) -> Dict[str, Any]:
    """Drive the step object through the checked steps by the window's own
    call and feed, reading what the comparison needs from its state."""
    cfg, depth = obj["cfg"], obj["cfg"]["num_hidden_layers"]
    n = int(ctx.cell["check"]["steps"])
    beta1 = ctx.cell["step"]["optimizer"]["beta1"]
    losses, grad_norms = [], None
    nxt = obj["feed"](0)
    for k in range(n):
        loss, nxt = one_step(obj, spans, nxt, k)
        losses.append(loss)
        if k == 0:
            m1 = program.named_state(obj["model"], obj["opt"], "moment1")
            grad_norms = {leaf: v / (1.0 - beta1) for leaf, v in _norms_by_leaf(m1, cfg, depth).items()}
            del m1
    master = program.master_weights(obj["model"], obj["opt"])
    change = _change_norms(master, cfg, depth, ctx.seed, ctx.cell["dtype"])
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change, "next": nxt, "k": n}


def window(ctx: Any, obj: Dict[str, Any], spans: Spans, nxt: Any, k: int, trace: Any) -> Dict[str, Any]:
    seconds = float(ctx.seconds)
    slice_s = min(float(ctx.cell.get("trace_slice_s", 3.0)), seconds / 2)
    losses: List[float] = []
    t0 = time.perf_counter()
    while True:
        if trace is not None and not trace.active and not trace.done and time.perf_counter() - t0 >= seconds - slice_s:
            trace.start()
        loss, nxt = one_step(obj, spans, nxt, k)
        losses.append(loss)
        k += 1
        t = time.perf_counter()
        if t - t0 >= seconds:
            break
    if trace is not None:
        trace.stop()
    return {"t0": t0, "t1": t, "losses": losses}


def run(ctx: Any) -> Dict[str, Any]:
    import jax

    spans = Spans()
    program.enable_counters()
    compiles = program.CompileCounter()
    obj = build(ctx)
    ctx.lap("model_and_weights")
    cfg, depth = obj["cfg"], obj["cfg"]["num_hidden_layers"]
    batch, seq = obj["stream"].batch, obj["stream"].seq
    got = first_steps(ctx, obj, spans)
    ctx.lap("first_steps")
    ctx.log("first_steps", losses=got["losses"])
    compiles_before = compiles.count
    trace = TraceSlice(ctx.trace_dir) if ctx.trace else None
    gc.collect()
    pauses = GcPauses()
    ctx.mark_setup_done()
    win = window(ctx, obj, spans, got["next"], got["k"], trace)
    pauses.close()
    compiles_in_window = compiles.count - compiles_before
    elapsed = win["t1"] - win["t0"]
    steps = len(win["losses"])
    ctx.log("window", steps=steps, seconds=elapsed, **pauses.summary(win["t0"], win["t1"]))
    counters = program.kernel_counters()
    counters["watchdog"] = program.watchdog_counts()
    memory_peak = ctx.memory_peak()
    traced = trace.reduce() if trace is not None else None

    opt_kw = {k: ctx.cell["step"]["optimizer"][k] for k in ("lr", "beta1", "beta2", "eps", "weight_decay")}
    stream = obj["stream"]
    got = {k: got[k] for k in ("losses", "grad_norms", "change_norms")}
    obj.clear()
    gc.collect()  # the program's jit closures sit in reference cycles
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference_steps(ctx, cfg, depth, stream, int(ctx.cell["check"]["steps"]), opt_kw)
    ref_s = time.perf_counter() - t_ref
    rows = compare(got, ref, ctx.cell["check"]["limits"])
    if getattr(ctx, "control", None):
        low = reference_steps(ctx, cfg, depth, stream, int(ctx.cell["check"]["steps"]), opt_kw, lower=ctx.control)
        for row in compare(low, ref, ctx.cell["check"]["limits"]):
            ctx.log("control", lower=ctx.control, **row)
    finite = all(math.isfinite(x) for x in win["losses"])
    rows += [
        {"name": "nonfinite_losses_in_window", "value": sum(not math.isfinite(x) for x in win["losses"]), "limit": 0, "ok": finite},
        {"name": "compiles_in_window", "value": compiles_in_window, "limit": 0, "ok": compiles_in_window == 0},
        {"name": "kernel_fallbacks", "value": sum(counters["fallbacks"].values()), "limit": 0,
         "ok": not any(counters["fallbacks"].values())},
    ]
    ctx.log("reference", seconds=ref_s, losses=ref["losses"], program_losses=got["losses"])
    return {
        "checks": rows,
        "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in win["losses"]),
        "e2e": {"train_tokens_per_s": steps * batch * seq / elapsed},
        "memory_peak_bytes": memory_peak,
        "run": {
            "driver": "train", "cfg": cfg, "depth": depth, "batch": batch, "seq": seq, "steps": steps,
            "window_s": elapsed, "window": (win["t0"], win["t1"]), "spans": spans, "counters": counters,
            "trace": traced, "losses": win["losses"],
        },
    }
