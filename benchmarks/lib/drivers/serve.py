"""The serving loop: an open loop of seeded arrivals driving the program's
``ServingFrontend`` over its ``ContinuousBatchingEngine`` in-process, from one
thread. Each request is sent when it is DUE (never earlier; how much later is
reported as generator lateness) and every time is taken from its due time, so
a stall delays the requests behind it and that wait is counted.

The loop starts ``ramp_s`` seconds BEFORE the window (the cell's file says how
long; set-up, not measured): the same arrival process fills the engine, so the
window opens on an engine that is already serving. The window's numbers are
those of the requests due inside it and of the tokens delivered inside it.

The client side is the benchmark's own: after every pump it reads how many
tokens each live request has and stamps the new ones. The program has no
per-token timestamp of its own.

After the window closes no new request is sent; the loop keeps pumping until
every request that was due has its first token (so that the tail of time to
first token is the tail of ALL requests), cancels what is still decoding and
checks that the KV pool drained. ``correct`` then compares two things with the
plain reference, which runs once the program is freed: every served token of a
seeded sample of the finished requests (the longest among them) lies within a
limit of the reference's best logit at its position, and the logits of the
engine's own step body (``engine.step_logits``, the same engine object, read
after the window) on the first chunk of a seeded sample of the window's
prompts lie within a limit of the reference's, as a share of their size. The
step itself hands back argmaxes only, so its logits are read through that
debug surface of the program: it is what separates bf16 from the program's
int8 paths, which move served tokens little more than bf16 rounding does.

Adapted from ``paddle_tpu/serving/loadgen.py`` (``run_open_loop``), which times
from ``submit()`` and keeps no token times.
"""

from __future__ import annotations

import collections
import gc
import math
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from .. import arch, program, stats, traffic
from .. import weights as W
from ..spans import GcPauses, Spans
from ..tracing import TraceSlice


class Record:
    __slots__ = ("request", "handle", "submit_s", "stamps", "done_s", "outcome", "tokens")

    def __init__(self, request: traffic.Request) -> None:
        self.request = request
        self.handle: Any = None
        self.submit_s: Optional[float] = None
        self.stamps: List[float] = []
        self.done_s: Optional[float] = None
        self.outcome: Optional[str] = None
        self.tokens: List[int] = []


def build(ctx: Any) -> Dict[str, Any]:
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.frontend import ServingConfig

    cfg = program.run_config(ctx.config, ctx.cell["driver"])
    model = program.build_model(cfg, ctx.seed, ctx.cell["dtype"])
    model.eval()
    engine = ContinuousBatchingEngine(model, **ctx.cell["engine"])
    frontend = ServingFrontend(engine, ServingConfig(**ctx.cell.get("frontend", {})))
    return {"cfg": cfg, "model": model, "engine": engine, "frontend": frontend}


def warm_up(ctx: Any, obj: Dict[str, Any]) -> None:
    """Compile the engine's one step program on requests of this cell's own
    kind: a prompt of several chunks, a repeat of it (prefix-cache hit with a
    copy-on-write fork) and a few decode steps."""
    fe = obj["frontend"]
    rng = np.random.default_rng(12345)
    chunk = obj["engine"].prefill_chunk
    prompt = rng.integers(1, obj["cfg"]["vocab_size"], 3 * chunk + 5).astype(np.int32)
    for _ in range(2):
        handle = fe.submit(prompt, max_new_tokens=4)
        deadline = time.perf_counter() + 1100.0
        while not handle.finished:
            fe.pump()
            if time.perf_counter() > deadline:
                raise TimeoutError("warm-up request did not finish")


def _stamp(live: Dict[int, Record], now: float) -> None:
    for rec in live.values():
        n = len(rec.handle.tokens())
        if n > len(rec.stamps):
            rec.stamps.extend([now] * (n - len(rec.stamps)))


def window(ctx: Any, obj: Dict[str, Any], requests: List[traffic.Request], spans: Spans,
           trace: Any, ramp_s: float = 0.0, on_open: Any = None) -> Dict[str, Any]:
    """Drive the schedule from ``-ramp_s`` to the window's close and through
    the tail; ``on_open(t0)`` is called once, when the window's clock reaches 0."""
    fe, engine = obj["frontend"], obj["engine"]
    seconds = float(ctx.seconds)
    slice_s = min(float(ctx.cell.get("trace_slice_s", 4.0)), seconds / 2)
    tail_cap = float(ctx.cell.get("tail_cap_s", 60.0))
    records = [Record(r) for r in requests]
    pending = collections.deque(records)
    live: Dict[int, Record] = {}
    refused: List[Record] = []
    closed_at: Optional[float] = None
    opened = False
    # (start, end, live KV tokens of the decoding slots, pool blocks held by live requests), window clock
    pumps: List[Any] = []
    t0 = time.perf_counter() + float(ramp_s)

    def submit_due(now: float) -> None:
        while pending and pending[0].request.due_s <= now:
            rec = pending.popleft()
            rec.submit_s = time.perf_counter() - t0
            try:
                rec.handle = fe.submit(rec.request.prompt, max_new_tokens=rec.request.max_new_tokens)
            except (ValueError, RuntimeError) as exc:  # IntakeError, Overloaded, failed engine
                rec.outcome = f"refused:{type(exc).__name__}"
                refused.append(rec)
                continue
            live[rec.handle.id] = rec

    while True:
        now = time.perf_counter() - t0
        if not opened and now >= 0.0:
            opened = True
            if on_open is not None:
                on_open(t0)
        if closed_at is None and now >= seconds:
            # close: whatever is due is sent, then nothing more
            with spans.span("loadgen.submit"):
                submit_due(seconds)
            closed_at = now
            if trace is not None:
                trace.stop()
        if closed_at is not None:
            waiting = [r for r in live.values() if not r.stamps]
            if not waiting or now - closed_at > tail_cap:
                break
        else:
            if trace is not None and not trace.active and not trace.done and now >= seconds - slice_s:
                trace.start()
            with spans.span("loadgen.submit"):
                submit_due(now)
        if engine.has_work():
            kv_live = sum(len(r.request.prompt) + len(r.stamps) for r in live.values() if r.stamps)
            with spans.span("frontend.pump"):
                finished = fe.pump()
            stamp = time.perf_counter() - t0
            pool = engine.pool_stats()
            pumps.append((now, stamp, kv_live, pool["allocated"] - pool["cached_reusable"]))
            _stamp(live, stamp)
            for handle in finished:
                rec = live.pop(handle.id, None)
                if rec is not None:
                    rec.done_s, rec.outcome, rec.tokens = stamp, handle.outcome, list(handle.tokens())
        elif pending:
            with spans.span("loadgen.idle"):
                time.sleep(max(0.0, min(0.002, pending[0].request.due_s - now)))
    end = time.perf_counter() - t0
    # what is still decoding is cancelled; the pool has to drain
    unfinished = list(live.values())
    for rec in unfinished:
        fe.cancel(rec.handle.id, reason="window_closed")
    for _ in range(4):
        if not engine.has_work():
            break
        fe.pump()
    return {"t0": t0, "seconds": seconds, "closed_at": closed_at, "end": end, "records": records,
            "refused": refused, "unfinished": unfinished, "pumps": pumps}


def end_to_end(win: Dict[str, Any]) -> Dict[str, Any]:
    """The window's numbers: time to first token of every request DUE in the
    window (a ramp request, due before 0, only has to succeed), every gap that
    ends inside the window, every token delivered inside it."""
    seconds, close = win["seconds"], win["closed_at"]
    ttft, itl, late = [], [], []
    sent = completed = failed = 0
    for rec in win["records"]:
        if rec.submit_s is None:
            continue
        in_window = rec.request.due_s >= 0.0
        sent += in_window
        late.append(rec.submit_s - rec.request.due_s)
        bad = rec.outcome is not None and rec.outcome != "ok" and rec.outcome != "window_closed"
        if rec.handle is None or bad or not rec.stamps:
            failed += 1
            if in_window:
                ttft.append(seconds)
            continue
        if in_window:
            ttft.append(rec.stamps[0] - rec.request.due_s)
        itl.extend(b - a for a, b in zip(rec.stamps, rec.stamps[1:]) if 0.0 < b <= close)
        if rec.done_s is not None and 0.0 < rec.done_s <= close:
            completed += 1
    out_tokens = sum(1 for r in win["records"] for t in r.stamps if 0.0 < t <= close)
    return {
        "sent": sent, "completed_in_window": completed, "failed": failed, "out_tokens": out_tokens,
        "values": {
            "serve_out_tokens_per_s": out_tokens / close,
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * stats.percentile(itl, 95) if itl else None,
        },
        "beside": {
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50), "itl_p50_ms": 1e3 * stats.percentile(itl, 50) if itl else None,
            "generator_late_p50_ms": 1e3 * stats.percentile(late, 50), "generator_late_max_ms": 1e3 * max(late),
            "ttft_samples": len(ttft), "itl_samples": len(itl), "window_closed_at_s": close,
            "tail_phase_s": win["end"] - close, "unfinished_cancelled": len(win["unfinished"]),
        },
    }


def pick_sample(win: Dict[str, Any], seed: int, n: int) -> List[Record]:
    """A seeded sample of the finished requests, the longest always in it."""
    done = [r for r in win["records"] if r.outcome == "ok" and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.request.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 77])
    picks = [rest[i] for i in rng.permutation(len(rest))[: max(0, n - 1)]]
    return [longest] + picks


def probe_step_logits(engine: Any, requests: List[traffic.Request], seed: int, n: int) -> List[Any]:
    """``(first chunk of a prompt, the engine's own step logits on it)`` for a
    seeded sample of the window's prompts: float32 ``[chunk, V]`` each."""
    window = [r for r in requests if r.due_s >= 0.0]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 78])
    out = []
    for i in rng.permutation(len(window))[:n]:
        ids = np.asarray(window[i].prompt[: engine.prefill_chunk], np.int32)
        out.append((ids, np.asarray(engine.step_logits(ids), np.float32)))
    return out


def reference_logits(ctx: Any, cfg: Dict[str, Any], token_seqs: List[np.ndarray],
                     buckets: List[int]) -> Iterator[Any]:
    """The reference's logits ``[len(seq), V]`` of each sequence, one after the
    other, by the walk of the configuration's reference file
    (``sequence_logits``), which is handed the top's leaves as they are served
    (rounded once to the cell's dtype; it upcasts them) and the float32 leaves
    of one layer at a time, made again from the seed. Sequence ``i`` is padded
    to a multiple of ``buckets[i]`` (causal, so the padding changes no row that
    is read)."""
    import jax
    import jax.numpy as jnp

    dtype = ctx.cell["dtype"]
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    padded = [np.pad(np.asarray(toks, np.int32), (0, -len(toks) % bucket)) for toks, bucket in zip(token_seqs, buckets)]
    logits = arch.reference(cfg).sequence_logits(
        padded, W.top_weights(ctx.seed, cfg, dtype),
        lambda i: f32(W.layer_weights(ctx.seed, cfg, i, dtype)), cfg)
    for toks, rows in zip(token_seqs, logits):
        yield rows[: len(toks)]


def compare(ctx: Any, cfg: Dict[str, Any], sample: List[Record], probes: List[Any]) -> Dict[str, Any]:
    """Served tokens and step logits against the reference (one pass over
    both): how far below the reference's best logit each served token lies,
    and the root-mean-square gap between the engine's step logits and the
    reference's over that of the reference's logits."""
    import jax.numpy as jnp

    seqs = [np.concatenate([r.request.prompt, np.asarray(r.tokens, np.int32)]) for r in sample]
    chunks = [ids for ids, _got in probes]
    logits = reference_logits(ctx, cfg, seqs + chunks, [256] * len(seqs) + [len(c) for c in chunks])
    served = []
    for rec in sample:
        n_prompt = len(rec.request.prompt)
        rows = next(logits)[n_prompt - 1: n_prompt - 1 + len(rec.tokens)]
        took = jnp.take_along_axis(rows, jnp.asarray(rec.tokens)[:, None], axis=-1)[:, 0]
        served.append(np.asarray(jnp.max(rows, axis=-1) - took, np.float64))
    gap2 = ref2 = worst = 0.0
    for _ids, got in probes:
        want = np.asarray(next(logits), np.float64)
        gap2 += float(np.square(got - want).sum())
        ref2 += float(np.square(want).sum())
        worst = max(worst, float(np.abs(got - want).max()))
    return {"served": served, "tokens": int(sum(len(g) for g in served)),
            "lengths": [int(len(s)) for s in seqs],
            "step_logit_rel_rms": math.sqrt(gap2 / ref2) if ref2 else float("inf"),
            "step_logit_gap_max": worst, "probe_rows": int(sum(len(c) for c in chunks))}


def probe_only(ctx: Any) -> Dict[str, Any]:
    """The step-logit comparison alone, without a window (it does not depend
    on the load): what ``control.py --probe`` reads over many seeds in one
    process, for the sound engine and for its lower-precision paths."""
    import jax

    obj = build(ctx)
    cfg = obj["cfg"]
    requests = traffic.make_requests(ctx.mix, float(ctx.cell["rate_rps"]), 50.0, cfg["vocab_size"], ctx.seed)
    probes = probe_step_logits(obj["engine"], requests, ctx.seed, int(ctx.cell["check"]["probe"]))
    obj.clear()
    gc.collect()
    jax.clear_caches()
    got = compare(ctx, cfg, [], probes)
    return {k: got[k] for k in ("step_logit_rel_rms", "step_logit_gap_max", "probe_rows")}


def run(ctx: Any) -> Dict[str, Any]:
    import jax

    spans = Spans()
    program.enable_counters()
    compiles = program.CompileCounter()
    obj = build(ctx)
    ctx.lap("model_weights_engine")
    cfg, depth, engine = obj["cfg"], obj["cfg"]["num_hidden_layers"], obj["engine"]
    warm_up(ctx, obj)
    ctx.lap("warm_up")
    ramp_s = float(ctx.cell.get("ramp_s", 0.0))
    requests = traffic.make_requests(ctx.mix, float(ctx.cell["rate_rps"]), float(ctx.seconds),
                                     cfg["vocab_size"], ctx.seed, ramp_s=ramp_s)
    ctx.log("traffic", **traffic.describe(requests), rate_rps=ctx.cell["rate_rps"], ramp_s=ramp_s)
    at_open: Dict[str, Any] = {}

    def on_open(t0: float) -> None:
        at_open.update(stats=dict(engine.stats), compiles=compiles.count)
        ctx.mark_setup_done(at=t0)

    trace = TraceSlice(ctx.trace_dir) if ctx.trace else None
    gc.collect()
    pauses = GcPauses()
    win = window(ctx, obj, requests, spans, trace, ramp_s=ramp_s, on_open=on_open)
    pauses.close()
    compiles_in_window = compiles.count - at_open["compiles"]
    e2e = end_to_end(win)
    longest = sorted(win["pumps"], key=lambda p: p[0] - p[1])[:3]
    ctx.log("requests", sent=e2e["sent"], completed_in_window=e2e["completed_in_window"],
            failed=e2e["failed"], **e2e["beside"],
            **pauses.summary(win["t0"], win["t0"] + win["closed_at"]),
            longest_pumps_at_s_ms=[[p[0], 1e3 * (p[1] - p[0])] for p in longest])
    pool = engine.pool_stats()
    counters = program.kernel_counters()
    counters.update({
        "engine": {k: engine.stats[k] - at_open["stats"].get(k, 0) for k in engine.stats},
        "pool": {k: pool[k] for k in ("total", "free", "cached_blocks", "bytes_per_token") if k in pool},
        "prefix_cache": engine.prefix_cache_stats(),
        "watchdog": program.watchdog_counts(),
        "max_slots": engine.max_slots, "prefill_chunk": engine.prefill_chunk, "block_size": engine.block_size,
        "max_blocks_per_seq": engine.max_blocks_per_seq, "num_blocks": engine.num_blocks,
    })
    drained = pool["free"] + pool.get("cached_blocks", 0) == pool["total"]
    memory_peak = ctx.memory_peak()
    traced = trace.reduce() if trace is not None else None
    traced_pumps = []
    if trace is not None and trace.done:
        lo, hi = trace.t_start - win["t0"], trace.t_stop - win["t0"]
        traced_pumps = [p for p in win["pumps"] if p[0] >= lo and p[1] <= hi]
    window_pumps = [p for p in win["pumps"] if p[0] >= 0.0 and p[1] <= win["closed_at"]]

    sample = pick_sample(win, ctx.seed, int(ctx.cell["check"]["sample"]))
    t_probe = time.perf_counter()
    probes = probe_step_logits(engine, requests, ctx.seed, int(ctx.cell["check"]["probe"]))
    ctx.log("probe", seconds=time.perf_counter() - t_probe, prompts=len(probes))
    obj.clear()
    del engine
    gc.collect()  # the program's jit closures sit in reference cycles
    jax.clear_caches()
    t_ref = time.perf_counter()
    got = compare(ctx, cfg, sample, probes)
    ctx.log("reference", seconds=time.perf_counter() - t_ref, requests=len(sample), tokens=got["tokens"],
            probe_rows=got["probe_rows"], step_logit_gap_max=got["step_logit_gap_max"])
    widest = max((float(g.max()) for g in got["served"]), default=float("inf"))
    mean_gap = float(np.concatenate(got["served"]).mean()) if got["served"] else float("inf")
    limits = ctx.cell["check"]["limits"]

    def held(name: str, value: float, **more: Any) -> Dict[str, Any]:
        return {"name": name, "value": value, "limit": limits[name],
                "ok": bool(math.isfinite(value) and value <= limits[name]), **more}

    rows = [
        held("step_logit_rel_rms", got["step_logit_rel_rms"],
             over=f"{got['probe_rows']} rows of {len(probes)} prompts' first chunk"),
        held("served_logit_gap_mean", mean_gap),
        held("served_logit_gap_max", widest,
             over=f"{got['tokens']} served tokens of {len(sample)} requests, lengths {got['lengths']}"),
        {"name": "failed_requests", "value": e2e["failed"], "limit": 0, "ok": e2e["failed"] == 0},
        {"name": "compiles_in_window", "value": compiles_in_window, "limit": 0, "ok": compiles_in_window == 0},
        {"name": "kernel_fallbacks", "value": sum(counters["fallbacks"].values()), "limit": 0,
         "ok": not any(counters["fallbacks"].values())},
        {"name": "engine_recoveries", "value": counters["engine"]["recoveries"], "limit": 0,
         "ok": counters["engine"]["recoveries"] == 0},
        {"name": "kv_pool_undrained_blocks", "value": pool["total"] - pool["free"] - pool.get("cached_blocks", 0),
         "limit": 0, "ok": bool(drained)},
    ]
    return {
        "checks": rows,
        "attempted": e2e["sent"],
        "failed": e2e["failed"],
        "e2e": {k: v for k, v in e2e["values"].items() if v is not None},
        "memory_peak_bytes": memory_peak,
        "run": {
            "driver": "serve", "cfg": cfg, "depth": depth, "window_s": win["closed_at"],
            "window": (win["t0"], win["t0"] + win["closed_at"]), "spans": spans, "counters": counters,
            "trace": traced, "out_tokens_in_window": e2e["out_tokens"],
            "traced_pumps": traced_pumps, "window_pumps": window_pumps,
            "beside": e2e["beside"],
        },
    }
