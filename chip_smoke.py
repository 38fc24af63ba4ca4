#!/usr/bin/env python
"""First-run proof on the chip: the two normal paths, at Llama-2-7B width.

    python chip_smoke.py             # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: ONLY the multi-chip phase

One process (a chip belongs to one process; nothing started here needs the
device). There is no CPU mode: the moment ``jax.devices()[0].platform`` is
not ``"tpu"`` the script fails — the CPU rehearsal is the test suite's job
(tests/test_chip_smoke.py imports the phase functions at a tiny width).

Width is ``LlamaConfig.llama2_7b()`` (hidden 4096, 32 heads x 128, MLP 11008,
vocab 32000), cut BY DEPTH ONLY to fit 16 GB; weights are random, from seed 0.
Each phase prints JSON lines as it finishes. They are smoke output, not a
benchmark: no number here is a claim, and none uses a peak-FLOP/s constant.
Any phase exception, any non-zero ``paddle_tpu_kernel_fallbacks_total``
series or an unexpected compile count fails the run; nothing is caught to
carry on. On success the last stdout line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import time
import urllib.request
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# depth per phase, read off compiled.memory_analysis() in the rehearsal (see
# CHANGES.md PR 21): train holds 16 B/param (bf16 + fp32 master + two
# moments), serve holds bf16 weights plus the KV pool
TRAIN_DEPTH, SERVE_DEPTH = 2, 8
TRAIN = dict(batch=2, seq=2048, steps=4)
ENGINE = dict(  # ContinuousBatchingEngine arguments, both engine phases
    max_slots=8, block_size=16, num_blocks=2048, max_model_len=2048, prompt_bucket=128
)
REQUESTS = dict(prompt_lens=(5, 16, 37, 90), max_new_tokens=32)
INT8_NUM_BLOCKS = 512
HYBRID = dict(batch=4, seq=512, steps=2)  # at TRAIN_DEPTH
# first-step logits, paged engine math vs the dense forward, both bf16 with
# fp32 accumulation: rounding differs only inside attention, ~2**-8 per
# layer; a wrong kernel is off by the logits' own scale. The gate:
LOGIT_TOL = 2.0**-4  # x max|dense logit|


def emit(phase: str, **kv: Any) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def rebuild_native() -> str:
    """Drop whatever ``cpp/build`` holds (git ignores it, so a stale prebuilt
    library can sit there) and let the loader rebuild from ``cpp/*.cpp``.
    Runs before ``import paddle_tpu``, whose profiler loads an existing .so."""
    shutil.rmtree(os.path.join(ROOT, "cpp", "build"), ignore_errors=True)
    from paddle_tpu_native.loader import load_native

    return "native" if load_native(build=True) is not None else "pure-python"


def require_tpu(chips: int) -> Dict[str, Any]:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax found {dev.platform!r} ({dev.device_kind})"
        )
    if len(jax.devices()) < chips:
        raise RuntimeError(f"--chips {chips} but jax sees {len(jax.devices())} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def lifetime_peak() -> List[int]:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()]


def memory(peak_before: List[int]) -> Dict[str, List[Any]]:
    """Per device: bytes in use now, and the allocator's high-water mark where
    this phase raised it. The mark is the process's, not the phase's: where
    an earlier phase peaked higher, this phase's own peak is not measured
    (null)."""
    import jax

    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in jax.devices()]
    peak = [now if now > was else None for was, now in zip(peak_before, lifetime_peak())]
    return {"bytes_in_use": in_use, "phase_peak_bytes": peak}


def routed_now() -> Dict[str, float]:
    from paddle_tpu.kernels.select import partition_routed_counts

    return partition_routed_counts()


def routed_since(before: Dict[str, float]) -> Dict[str, float]:
    """Kernels whose dispatch ran the XLA composition since ``before``
    because the trace is partitioned over devices (kernels/select.py)."""
    return {
        k: v - before.get(k, 0.0) for k, v in routed_now().items() if v > before.get(k, 0.0)
    }


def at_depth(cfg: Any, depth: int) -> Any:
    import dataclasses

    return dataclasses.replace(cfg, num_hidden_layers=depth)


def build_model(cfg: Any, dtype: str) -> Any:
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    # the previous phase's model, optimizer state and pools sit in reference
    # cycles (jit closures): without a collection they still hold the HBM the
    # fp32 weight initialisation below needs
    gc.collect()
    paddle.seed(0)
    return LlamaForCausalLM(cfg).to(dtype=dtype)


def count_params(model: Any) -> int:
    return int(sum(int(np.prod(p.shape)) for p in model.parameters()))


def step_compiles(fn: str) -> int:
    from paddle_tpu.observability import GLOBAL_WATCHDOG

    return GLOBAL_WATCHDOG.counts().get(fn, 0)


def assert_grad_coverage(model: Any, ids: Any, labels: Any) -> int:
    """One jitted fwd+bwd returning the grads: every trainable parameter must
    get a non-None, non-zero one (a DCE'd backward trains nothing)."""
    import paddle_tpu as paddle

    @paddle.jit.to_static
    def grad_probe(model, ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        grads = [p.grad for p in model.parameters() if not p.stop_gradient]
        model.clear_gradients()
        return loss, grads

    _loss, grads = grad_probe(model, ids, labels)
    names = [n for n, p in model.named_parameters() if not p.stop_gradient]
    missing = [n for n, g in zip(names, grads) if g is None]
    if missing:
        raise AssertionError(f"no grad on {len(missing)} params: {missing[:5]}")
    zero = [n for n, g in zip(names, grads) if float(g.abs().sum()) == 0.0]
    if zero:
        raise AssertionError(f"zero grad on {len(zero)} params: {zero[:5]}")
    return len(names)


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------


def phase_train(cfg: Any, *, batch: int, seq: int, steps: int, dtype: str = "bfloat16") -> None:
    """The ``@paddle.jit.to_static`` train step exactly as users write it,
    default flags (fused loss, flash attention, fused rope/rms), AdamW with
    fp32 master weights, a fixed batch from seed 0."""
    import paddle_tpu as paddle

    peak0 = lifetime_peak()
    model = build_model(cfg, dtype)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), multi_precision=True
    )

    @paddle.jit.to_static
    def train_step(model, opt, ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    n_grads = assert_grad_coverage(model, ids[:1, : min(seq, 256)], labels[:1, : min(seq, 256)])

    before = step_compiles(train_step.function.__qualname__)
    t0 = time.perf_counter()
    losses = [float(train_step(model, opt, ids, labels))]  # compile + step 0
    compile_s = time.perf_counter() - t0
    step_s = []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(train_step(model, opt, ids, labels)))  # float() syncs
        step_s.append(time.perf_counter() - t0)
    compiles = step_compiles(train_step.function.__qualname__) - before

    emit(
        "train", depth=cfg.num_hidden_layers, params=count_params(model), dtype=dtype,
        batch=batch, seq=seq, tokens_per_step=batch * seq, losses=losses,
        first_call_seconds=compile_s, step_seconds=step_s,
        grads_checked=n_grads, step_compiles=compiles, **memory(peak0),
    )
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        raise AssertionError(f"loss not strictly decreasing on a fixed batch: {losses}")
    if compiles != 1:
        raise AssertionError(f"train step compiled {compiles} times, expected 1")


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------


def post_generate(port: int, prompt: List[int], max_new_tokens: int) -> Dict[str, Any]:
    """One streaming POST /v1/generate, read to its final line."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens}).encode(),
        headers={"Content-Type": "application/json"},
    )
    tokens: List[int] = []
    final: Dict[str, Any] = {}
    with urllib.request.urlopen(req, timeout=900) as resp:
        for line in resp:
            obj = json.loads(line)
            if "token" in obj:
                tokens.append(int(obj["token"]))
            else:
                final = obj
    return {"tokens": tokens, "final": final}


def token_match(ref: List[int], got: List[int]) -> Dict[str, Any]:
    """Positionwise match rate over the longer stream, and where they part."""
    n = max(len(ref), len(got))
    same = [a == b for a, b in zip(ref, got)] + [False] * (n - min(len(ref), len(got)))
    return {
        "token_match_rate": (sum(same) / n) if n else 1.0,
        "first_divergence": same.index(False) if False in same else None,
    }


def assert_drained(engine: Any) -> Dict[str, Any]:
    s = engine.pool_stats()
    if s["free"] + s.get("cached_blocks", 0) != s["total"]:
        raise AssertionError(f"KV pool did not drain: {s}")
    return {k: s[k] for k in ("total", "free", "cached_blocks") if k in s}


def dense_logits(model: Any, prompts: List[List[int]]) -> np.ndarray:
    """fp32 logits ``[R, S, V]`` of the dense, cache-free forward: one batch,
    right-padded to a whole 128-row block (causal attention: padding cannot
    reach a prompt's own rows)."""
    import paddle_tpu as paddle

    padded = np.zeros((len(prompts), -(-max(map(len, prompts)) // 128) * 128), np.int32)
    for row, p in zip(padded, prompts):
        row[: len(p)] = p
    return np.asarray(model(paddle.to_tensor(padded)).numpy(), np.float32)


def first_token_gaps(rows: np.ndarray, tokens: List[int]) -> List[float]:
    """How far below the reference's best logit each served first token sits
    (0.0: it is the reference argmax). ``rows [R, V]``: the reference logits
    of each request's last prompt position."""
    return [float(row.max() - row[tok]) for row, tok in zip(np.asarray(rows, np.float32), tokens)]


def phase_serve(
    cfg: Any, *, engine_kw: Dict[str, int], prompt_lens: Any, max_new_tokens: int,
    int8_num_blocks: int, dtype: str = "bfloat16", logit_tol: float = LOGIT_TOL,
) -> None:
    """The HTTP server over frontend over engine, default flags (paged chunk
    kernel, fused decode layer, bf16 KV), held against the dense paths on the
    same weights: the engine step's own first-chunk logits and every
    request's first served token are gated on the cache-free forward, one
    request's whole stream is compared with ``generate`` (dense cache) and
    printed; then an int8-KV engine."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.quality import step_logit_error
    from paddle_tpu.serving import ServingFrontend, start_serving_server, stop_serving_server

    peak0 = lifetime_peak()
    model = build_model(cfg, dtype)
    model.eval()
    engine = ContinuousBatchingEngine(model, **engine_kw)
    block_size, num_blocks = engine.block_size, engine.num_blocks
    frontend = ServingFrontend(engine)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).tolist() for n in prompt_lens]

    before = step_compiles("ContinuousBatchingEngine.step")
    server = start_serving_server(frontend, port=0, stream_timeout_s=900.0)
    try:
        port = server.server_address[1]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(post_generate, port, p, max_new_tokens) for p in prompts]
            replies = [f.result() for f in futures]
        wall_s = time.perf_counter() - t0
    finally:
        stop_serving_server(frontend)
    compiles = step_compiles("ContinuousBatchingEngine.step") - before
    mem = memory(peak0)  # with the model and the pool live
    for p, r in zip(prompts, replies):
        if r["final"].get("outcome") != "ok" or len(r["tokens"]) != max_new_tokens:
            raise AssertionError(f"request (prompt {len(p)}) ended {r['final']}")

    dense = dense_logits(model, prompts)
    probe = int(np.argmin([abs(len(p) - block_size) for p in prompts]))  # ~one chunk
    ids = np.asarray(prompts[probe], np.int32)
    n = min(len(ids), engine.prefill_chunk)
    err = step_logit_error(engine, ids, reference=dense[probe, :n])
    tol = logit_tol * err["max_abs_reference_logit"]
    gaps = first_token_gaps(
        np.stack([dense[i, len(p) - 1] for i, p in enumerate(prompts)]),
        [r["tokens"][0] for r in replies],
    )
    ref = model.generate(
        paddle.to_tensor(ids[None]), max_new_tokens=max_new_tokens, do_sample=False
    ).numpy()[0, len(ids):].tolist()

    emit(
        "serve", depth=cfg.num_hidden_layers, params=count_params(model), dtype=dtype,
        kv_cache_dtype=engine.kv_cache_dtype, pool_blocks=num_blocks,
        pool_tokens=num_blocks * block_size, max_slots=engine.max_slots,
        requests=[
            {"prompt_len": len(p), "outcome": r["final"].get("outcome"),
             "tokens": len(r["tokens"])}
            for p, r in zip(prompts, replies)
        ],
        first_compile_and_all_requests_seconds=wall_s, engine_steps=engine.stats["steps"],
        step_compiles=compiles, recoveries=engine.stats["recoveries"],
        step_logits_vs_dense=err, logit_tolerance=tol, first_token_logit_gaps=gaps,
        vs_dense_generate=token_match(ref, replies[probe]["tokens"]),
        pool=assert_drained(engine), **mem,
    )
    if compiles != 1 or engine.stats["step_traces"] != 1:
        raise AssertionError(f"engine step compiled {compiles} times, expected 1")
    if engine.stats["recoveries"]:
        raise AssertionError(f"engine recovered {engine.stats['recoveries']} times")
    if not err["max_logit_error"] <= tol:
        raise AssertionError(f"engine step logits vs dense: {err}, tolerance {tol}")
    if not max(gaps) <= tol:
        raise AssertionError(
            f"a served first token sits {max(gaps)} below the dense forward's best "
            f"logit (tolerance {tol}): {gaps}"
        )

    # int8 KV on a second engine: proves the in-walk dequant kernels run
    q_engine = ContinuousBatchingEngine(
        model, kv_cache_dtype="int8", **{**engine_kw, "num_blocks": int8_num_blocks}
    )
    q_engine.add_request(ids, max_new_tokens=max_new_tokens)
    t0 = time.perf_counter()
    (q_req,) = q_engine.run().values()
    emit(
        "serve_int8_kv", pool_blocks=int8_num_blocks,
        bytes_per_token=q_engine.pool_stats()["bytes_per_token"],
        tokens=len(q_req.generated), seconds=time.perf_counter() - t0,
        step_compiles=q_engine.stats["step_traces"],
        vs_bf16_engine=token_match(replies[probe]["tokens"], list(q_req.generated)),
        pool=assert_drained(q_engine),
    )
    if len(q_req.generated) != max_new_tokens or q_engine.stats["step_traces"] != 1:
        raise AssertionError("int8-KV engine did not finish its request on one compile")


# ---------------------------------------------------------------------------
# --chips 4: tensor-parallel engine, hybrid-sharded train step
# ---------------------------------------------------------------------------


def run_engine(engine: Any, prompts: List[List[int]], max_new_tokens: int) -> List[List[int]]:
    for p in prompts:
        engine.add_request(np.asarray(p, np.int32), max_new_tokens=max_new_tokens)
    out = engine.run()
    return [list(out[rid].generated) for rid in sorted(out)]


def phase_tp_engine(
    cfg: Any, *, tp: int, engine_kw: Dict[str, int], prompt_lens: Any,
    max_new_tokens: int, dtype: str = "bfloat16", logit_tol: float = LOGIT_TOL,
) -> None:
    """A ``tp``-way engine against a ``tp=1`` engine on the same seeded
    requests and weights: the step's own logits on every prompt that fits one
    chunk (vs tp=1) and every request's first token (vs the dense forward)
    are gated; whole streams are compared and printed. The dense forward and
    the tp=1 engine run first: the tp engine commits the model's parameters
    onto its mesh in place."""
    import jax

    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.quality import step_logit_error
    from paddle_tpu.observability import GLOBAL_WATCHDOG

    routed0 = routed_now()
    model = build_model(cfg, dtype)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).tolist() for n in prompt_lens]
    dense = dense_logits(model, prompts)
    last_rows = np.stack([dense[i, len(p) - 1] for i, p in enumerate(prompts)])
    tol = logit_tol * float(np.max(np.abs(last_rows)))
    del dense
    one = ContinuousBatchingEngine(model, tp=1, **engine_kw)
    ref_tokens = run_engine(one, prompts, max_new_tokens)
    one_chunk = [i for i, p in enumerate(prompts) if len(p) <= one.prefill_chunk]
    ref_logits = {i: one.step_logits(prompts[i]) for i in one_chunk}
    del one
    gc.collect()  # its pool sits in a reference cycle; device 0 needs the room

    before = step_compiles("ContinuousBatchingEngine.step")
    engine = ContinuousBatchingEngine(model, tp=tp, **engine_kw)
    t0 = time.perf_counter()
    tokens = run_engine(engine, prompts, max_new_tokens)
    wall_s = time.perf_counter() - t0
    errs = [step_logit_error(engine, prompts[i], reference=ref_logits[i]) for i in one_chunk]
    tols = [logit_tol * e["max_abs_reference_logit"] for e in errs]
    gaps = first_token_gaps(last_rows, [t[0] for t in tokens])
    gaps_tp1 = first_token_gaps(last_rows, [t[0] for t in ref_tokens])

    signatures = GLOBAL_WATCHDOG.report()["ContinuousBatchingEngine.step"]["signatures"]
    stats = engine.tp_stats()
    shard_devices = sorted(
        {s.device.id for pair in engine._caches for arr in pair for s in arr.addressable_shards}
    )
    live = dict.fromkeys((d.id for d in jax.devices()[:tp]), 0)
    for arr in jax.live_arrays():
        for s in arr.addressable_shards:
            if s.device.id in live:
                live[s.device.id] += s.data.nbytes
    # the allocator's own figure, where the backend reports one (TPU does)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()[:tp]}
    routed = routed_since(routed0)
    emit(
        "tp_engine", tp=tp, depth=cfg.num_hidden_layers, pool_blocks=engine.num_blocks,
        first_compile_and_all_requests_seconds=wall_s,
        vs_tp1=[token_match(r, t) for r, t in zip(ref_tokens, tokens)],
        step_logits_vs_tp1=errs, logit_tolerances=tols,
        first_token_logit_gaps_vs_dense={f"tp{tp}": gaps, "tp1": gaps_tp1},
        first_token_tolerance=tol,
        step_compiles=step_compiles("ContinuousBatchingEngine.step") - before,
        signatures=signatures, tp_stats=stats, cache_shard_devices=shard_devices,
        live_array_bytes=live, bytes_in_use=in_use, pool=assert_drained(engine),
        ran_xla_under_partitioning=routed,
    )
    if engine.stats["step_traces"] != 1 or not any(s.endswith(f"|tp{tp}") for s in signatures):
        raise AssertionError(f"expected one compile tagged |tp{tp}: {signatures}")
    if not stats.get("balanced"):
        raise AssertionError(f"tp shards unbalanced: {stats}")
    if len(shard_devices) != tp or not all(live.values()) or 0 in in_use.values():
        raise AssertionError(
            f"not every device holds a cache shard and live bytes: "
            f"{shard_devices} {live} {in_use}"
        )
    if not all(len(t) == max_new_tokens for t in tokens):
        raise AssertionError("a tp request did not run to its token budget")
    if not all(e["max_logit_error"] <= t for e, t in zip(errs, tols)):
        raise AssertionError(f"tp={tp} vs tp=1 step logits {errs}, tolerances {tols}")
    if not max(gaps + gaps_tp1) <= tol:
        raise AssertionError(
            f"a first token sits more than {tol} below the dense forward's best logit: "
            f"tp={tp} {gaps}, tp=1 {gaps_tp1}"
        )
    if set(routed) - TP_ROUTED:
        raise AssertionError(f"kernels routed to XLA under the tp mesh: {routed}")


def phase_hybrid_train(
    cfg: Any, *, n_devices: int, batch: int, seq: int, steps: int, dtype: str = "bfloat16"
) -> None:
    """The hybrid dp x sharding x mp step ``dryrun_multichip`` builds, on real
    devices, against the single-device loss of the same seed and batch."""
    import paddle_tpu as paddle
    from __graft_entry__ import build_hybrid_train_step, hybrid_mesh, shard_batch

    peak0, routed0 = lifetime_peak(), routed_now()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    def run(mesh: Any) -> Any:
        model = build_model(cfg, dtype)
        step = build_hybrid_train_step(model, mesh)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4, parameters=model.parameters(), multi_precision=True
        )
        if mesh is None:
            x, y = paddle.to_tensor(ids), paddle.to_tensor(labels)
        else:
            x, y = shard_batch(mesh, ids, labels)
        t0 = time.perf_counter()
        losses = [float(step(model, opt, x, y)) for _ in range(steps)]
        return model, losses, time.perf_counter() - t0

    _single, ref_losses, _ = run(None)
    del _single
    if routed_since(routed0):
        raise AssertionError(f"single-device step routed kernels: {routed_since(routed0)}")
    mesh = hybrid_mesh(n_devices)
    model, losses, wall_s = run(mesh)
    routed = routed_since(routed0)
    from jax.sharding import NamedSharding

    kept = [isinstance(p._data.sharding, NamedSharding) for p in model.parameters()]
    spread = sorted({len(p._data.sharding.device_set) for p in model.parameters()})
    emit(
        "hybrid_train", mesh=dict(zip(mesh.dim_names, mesh.shape)),
        depth=cfg.num_hidden_layers, params=count_params(model), batch=batch, seq=seq,
        losses=losses, single_device_losses=ref_losses, seconds_with_compile=wall_s,
        params_keep_named_sharding=all(kept), param_device_counts=spread,
        ran_xla_under_partitioning=routed, **memory(peak0),
    )
    if not all(np.isfinite(losses)) or not all(kept):
        raise AssertionError(f"hybrid step: losses {losses}, shardings kept {all(kept)}")
    if not np.allclose(losses, ref_losses, rtol=2e-2):
        raise AssertionError(f"sharded losses {losses} vs single-device {ref_losses}")
    if set(routed) - HYBRID_ROUTED:
        raise AssertionError(f"kernels routed to XLA under the hybrid mesh: {routed}")


# ---------------------------------------------------------------------------


# the kernels each run dispatches to Pallas, printed at 0 when they never fell
# back. One chip: the train step, then the engine step (rope and the
# pre-attention norm ride inside the fused paged kernel). With --chips 4 the
# same kernels are attempted by the tp=1 engine and the single-device step
# the multi-chip runs are compared with; that step takes plain cross entropy.
TRAIN_KERNELS = ("flash_attention", "fused_rms_norm", "fused_rope", "fused_rope_bwd")
SERVE_KERNELS = ("fused_embed_norm", "fused_rms_norm_residual", "paged_flash_chunk_fused")
KERNELS = {
    1: TRAIN_KERNELS + ("fused_linear_cross_entropy",) + SERVE_KERNELS,
    4: TRAIN_KERNELS + SERVE_KERNELS,
}
# what may run its XLA composition because the trace is partitioned over
# devices (a bare pallas_call cannot be); anything else routed fails the run.
# Under the engine's tp mesh: only the embedding entry (its table is vocab-
# parallel; the norm kernels run per shard). Under the GSPMD-partitioned
# hybrid train step: every kernel it dispatches (the layout is the compiler's;
# that step takes plain cross entropy, not the fused loss head).
TP_ROUTED = {"fused_embed_norm"}
HYBRID_ROUTED = set(TRAIN_KERNELS)


def fallback_counts(chips: int = 1) -> Dict[str, float]:
    from paddle_tpu.kernels.select import fallback_counts as counted

    return {**dict.fromkeys(KERNELS[chips], 0.0), **counted()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    native = rebuild_native()
    device = require_tpu(args.chips)  # before any phase: no CPU mode

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.models.llama import LlamaConfig

    cache_dir = enable_compile_cache()
    cache_events: Counter = Counter()  # jax's own count of persistent-cache hits/misses
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update(
            [event.rsplit("/", 1)[-1]] if "/compilation_cache/cache_" in event else []
        )
    )
    # the fallback counters only count with metrics on; autotune stays off
    paddle.set_flags({"FLAGS_enable_metrics": True})
    emit("setup", device=device, native_library=native, compile_cache=cache_dir,
         cache_entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)

    cfg = LlamaConfig.llama2_7b()
    if args.chips == 1:
        phase_train(at_depth(cfg, TRAIN_DEPTH), **TRAIN)
        phase_serve(at_depth(cfg, SERVE_DEPTH), engine_kw=ENGINE,
                    int8_num_blocks=INT8_NUM_BLOCKS, **REQUESTS)
    else:
        phase_tp_engine(at_depth(cfg, SERVE_DEPTH), tp=args.chips, engine_kw=ENGINE, **REQUESTS)
        phase_hybrid_train(at_depth(cfg, TRAIN_DEPTH), n_devices=args.chips, **HYBRID)
        # the pipeline dry run's first verdict on real devices (tiny GPT:
        # the dry run's own configuration)
        from __graft_entry__ import dryrun_pipeline

        dryrun_pipeline(args.chips)
        emit("pipeline_dryrun", devices=args.chips, verdict="ok")

    fallbacks, routed = fallback_counts(args.chips), routed_since({})
    emit("fallbacks", paddle_tpu_kernel_fallbacks_total=fallbacks,
         paddle_tpu_kernel_partition_routed_total=routed, compile_cache=dict(cache_events))
    if any(fallbacks.values()):
        raise AssertionError(f"kernels fell back to XLA on the chip: {fallbacks}")
    if args.chips == 1 and routed:
        raise AssertionError(f"kernels routed to XLA on one chip: {routed}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
