#!/usr/bin/env python
"""Headline benchmark: Llama-2-architecture causal-LM pretraining throughput,
tokens/sec/chip, full train step (fwd + bwd + AdamW) under jit.

Baseline (BASELINE.json north star): Llama-2-7B pretrain > 2500 tokens/sec/chip
on TPU v5p. The local chip is whatever the driver provides (v5e today, ~16 GB
HBM), so the model is scaled to the largest Llama-proportioned config that
trains on one chip; the metric name carries the parameter count.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_TOKENS_PER_SEC_PER_CHIP = 2500.0

def _fail_json(error: str) -> None:
    """One parseable failure line on stdout — the driver records stdout
    verbatim, so every exit path must leave a JSON record. ``status`` is the
    machine-readable field every record carries: ``"measured"`` (a real
    number) or ``"error"`` (the bench itself failed)."""
    print(
        json.dumps(
            {
                "metric": "llama_train_tokens_per_sec_per_chip",
                "value": 0.0,
                "unit": "tokens/s/chip",
                "vs_baseline": 0.0,
                "status": "error",
                "error": error[:500],
            }
        ),
        flush=True,
    )


def _count_params(model) -> int:
    return int(sum(int(np.prod(p.shape)) for p in model.parameters()))


def _preflight_pallas(platform: str, cfg, seq: int, batch: int) -> None:
    """Kill-switch: statically verify each gated Pallas kernel lowers for the
    target platform at the EXACT shapes the bench will compile, BEFORE it is
    baked into the jitted train step (a Mosaic lowering error inside jit is
    uncatchable there and would cost the whole bench run — an early run died
    exactly this way). A failing kernel flips only its own FLAGS_use_pallas_*
    off; the XLA fallback path covers it."""
    import paddle_tpu as paddle

    if platform != "tpu":
        return
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import flash_attention_pallas
    from paddle_tpu.kernels.fused import fused_rms_norm_pallas, fused_rope_pallas

    hd = cfg.hidden_size // cfg.num_attention_heads

    def check(name: str, flag: str, fn, *args) -> None:
        try:
            jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
            print(f"bench: pallas preflight ok: {name}", file=sys.stderr)
        except Exception as exc:  # noqa: BLE001
            print(
                f"bench: pallas preflight FAILED ({name}), disabling {flag}: {exc!r}"[:2000],
                file=sys.stderr,
            )
            paddle.set_flags({flag: False})

    q = jnp.zeros((1, seq, cfg.num_attention_heads, hd), jnp.bfloat16)
    kv = jnp.zeros((1, seq, cfg.num_key_value_heads, hd), jnp.bfloat16)
    check(
        "flash_attention",
        "FLAGS_use_pallas_attention",
        # grad wrt q AND k/v: the backward runs as two pallas_calls (dq, dkv)
        # and an unused dkv cotangent would let DCE prune the second kernel
        # out before Mosaic lowering ever checked it
        lambda q, k, v: jax.grad(
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2),
        )(q, k, v),
        q, kv, kv,
    )
    from paddle_tpu.kernels.paged_attention import paged_flash_decode

    bs_, mbs_, nb_ = 16, 8, 64
    pq = jnp.zeros((2, cfg.num_attention_heads, hd), jnp.bfloat16)
    pkc = jnp.zeros((nb_, cfg.num_key_value_heads, bs_, hd), jnp.bfloat16)
    ptab = jnp.zeros((2, mbs_), jnp.int32)
    plen = jnp.ones((2,), jnp.int32)
    check(
        "paged_flash_decode",
        "FLAGS_use_pallas_paged_attention",
        lambda q_, kc_, vc_, t_, l_: paged_flash_decode(q_, kc_, vc_, t_, l_),
        pq, pkc, pkc, ptab, plen,
    )
    x = jnp.zeros((2, seq, cfg.hidden_size), jnp.bfloat16)
    w = jnp.zeros((cfg.hidden_size,), jnp.bfloat16)
    rope_x = jnp.zeros((1, seq, cfg.num_attention_heads, hd), jnp.bfloat16)
    cs = jnp.zeros((1, seq, 1, hd), jnp.float32)
    # rope has a custom VJP (Pallas bwd kernel): preflight both fwd and bwd
    # lowering so the train step never hits an uncatchable Mosaic error.
    check(
        "fused_rms_norm+rope",
        "FLAGS_use_pallas_fused",
        lambda x, w, rx, c, s: (
            jax.grad(lambda x: fused_rms_norm_pallas(x, w, 1e-6).astype(jnp.float32).sum())(x),
            jax.grad(
                lambda rx: fused_rope_pallas(rx, c, s).astype(jnp.float32).sum()
            )(rx),
        ),
        x, w, rope_x, cs, cs,
    )
    from paddle_tpu.kernels.fused_loss import fused_linear_cross_entropy

    # loss head: fwd (online-logsumexp kernel) AND bwd (dX + dW kernels) at
    # the exact [B*S, H] x [H, V] shape the train step bakes in
    rows = batch * seq
    lx = jnp.zeros((rows, cfg.hidden_size), jnp.bfloat16)
    lw = jnp.zeros((cfg.hidden_size, cfg.vocab_size), jnp.bfloat16)
    ll = jnp.zeros((rows,), jnp.int32)
    check(
        "fused_linear_cross_entropy",
        "FLAGS_use_fused_loss",
        lambda lx, lw: jax.grad(
            lambda lx, lw: fused_linear_cross_entropy(lx, lw, ll), argnums=(0, 1)
        )(lx, lw),
        lx, lw,
    )


def _resolve_backend() -> str:
    """Initialise the jax backend; whatever it raises propagates (main's
    caller turns it into the failure record and a non-zero exit)."""
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    platform = jax.default_backend()
    print(f"bench: platform={platform} devices={len(jax.devices())}", file=sys.stderr)
    return platform


def _assert_grad_coverage(paddle, model, ids, labels) -> None:
    """Honesty gate (VERDICT r3): one fwd+bwd step, then assert every
    trainable parameter received a non-None, nonzero grad. The r3 bench
    measured a step whose weight grads were silently DCE'd (recompute
    regression) — this gate makes that class of failure impossible to
    benchmark. One jitted probe returning the grads explicitly (jit
    state-capture does not persist ``.grad``; eager per-op dispatch would
    cost a compile per op)."""

    @paddle.jit.to_static
    def probe(model, ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        grads = [
            p.grad for p in model.parameters() if not p.stop_gradient
        ]  # None stays None in the output tree — visible host-side
        model.clear_gradients()
        return loss, grads

    _loss, grads = probe(model, ids, labels)
    names = [n for n, p in model.named_parameters() if not p.stop_gradient]
    missing = [n for n, g in zip(names, grads) if g is None]
    assert not missing, (
        f"grad-coverage: {len(missing)} trainable params got NO grad "
        f"(training is fake): {missing[:5]}"
    )
    zero = [n for n, g in zip(names, grads) if float(g.abs().sum()) == 0.0]
    assert not zero, f"grad-coverage: zero grads on {zero[:5]}"
    print(f"bench: grad-coverage ok ({len(names)} trainable params)", file=sys.stderr)


# secondaries whose measured path dispatches kernels from paddle_tpu/kernels/
# (directly or through the serving engine's decode step) — each of their
# records carries the PG preflight verdict so a hardware run never burns its
# rare TPU window on a kernel the analyzer already knows cannot lower
_KERNEL_BEARING_METRICS = {
    "int8_decode_matmul_ms",
    "paged_decode_step_ms",
    "engine_decode_tokens_per_sec",
    "fused_decode_layer_dispatches_per_layer",
    "tp_decode_tokens_per_sec",
    "shared_prefix_ttft_speedup",
    "kv_tier_multi_turn_ttft",
    "spec_decode_tokens_per_sec",
    "engine_fault_recovery_tokens_per_sec",
    "serving_goodput_tokens_per_sec",
    "cluster_goodput_tokens_per_sec",
    "quantized_kv_decode_tokens_per_sec",
}


def _kernel_geometry_clean() -> bool:
    """PG (Pallas kernel geometry) preflight over the kernels package: rank
    discipline, in-bounds proofs, VMEM budgets, scalar-prefetch, fallback
    lockstep. In-process ``--select PG`` equivalent; an analyzer crash counts
    as NOT clean (never vacuously green)."""
    try:
        from paddle_tpu.analysis import analyze_paths

        pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)), "paddle_tpu", "kernels")
        vs = analyze_paths([pkg], select=["PG"])
        n = sum(1 for v in vs if not v.suppressed)
        if n:
            print(f"bench: PG geometry preflight: {n} finding(s)", file=sys.stderr)
        return n == 0
    except Exception as exc:  # noqa: BLE001 - preflight must never kill the bench
        print(f"bench: PG geometry preflight failed: {exc!r}", file=sys.stderr)
        return False


def main() -> None:
    platform = _resolve_backend()

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig
    if platform == "tpu":
        # ~0.5B params: Llama proportions scaled to fit one v5e chip (16G)
        # with fp32 master weights + AdamW moments; per-layer recompute keeps
        # activations flat so batch*seq can use the full MXU.
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=1536,
            intermediate_size=4096,
            num_hidden_layers=14,
            num_attention_heads=12,
            num_key_value_heads=12,
            max_position_embeddings=2048,
            recompute=True,
        )
        batch, seq, steps, warmup = 8, 2048, 10, 2
    else:  # CPU smoke mode so the script is runnable anywhere
        cfg = LlamaConfig.tiny()
        batch, seq, steps, warmup = 2, 128, 3, 1

    # pin the fused loss head explicitly (and restore on exit) so the headline
    # metric never depends on a flag value left behind by another process
    # stage — same discipline as _bench_engine_decode's attention-path pin.
    # Pinned BEFORE preflight: a failing Mosaic lowering flips it back off.
    _prior_fused_loss = paddle.get_flags(["FLAGS_use_fused_loss"])
    paddle.set_flags({"FLAGS_use_fused_loss": True})
    try:
        _main_timed(platform, paddle, cfg, batch, seq, steps, warmup)
    finally:
        paddle.set_flags(_prior_fused_loss)


def _main_timed(platform, paddle, cfg, batch, seq, steps, warmup) -> None:
    from paddle_tpu.models.llama import LlamaForCausalLM

    _preflight_pallas(platform, cfg, seq, batch)
    # record what actually ran: preflight may have flipped the pin back off
    fused_loss = bool(paddle.get_flags(["FLAGS_use_fused_loss"])["FLAGS_use_fused_loss"])
    if platform == "tpu":
        # benchmark-driven Pallas block-size selection; the A/B timing lines
        # land on stderr (autotune: flash_attention ... -> (bq, bk)).
        # The flags live in kernels.autotune, which kernel modules import
        # only lazily — register them before set_flags can see them.
        import paddle_tpu.kernels.autotune  # noqa: F401

        paddle.set_flags(
            {
                "FLAGS_kernel_autotune_verbose": True,
                "FLAGS_use_kernel_autotune": True,
            }
        )
    paddle.seed(0)
    model = LlamaForCausalLM(cfg).to(dtype="bfloat16")
    n_params = _count_params(model)
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
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    )
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    )

    # honesty gate #1: every trainable param gets a real grad (small eager step)
    probe_ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (1, min(seq, 256))).astype(np.int32)
    )
    _assert_grad_coverage(paddle, model, probe_ids, probe_ids)

    first_loss = None
    for i in range(warmup):
        l = float(train_step(model, opt, ids, labels))  # sync: compile + settle
        if i == 0:
            first_loss = l

    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = train_step(model, opt, ids, labels)
    loss_val = float(last)  # device sync
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    assert np.isfinite(loss_val), f"non-finite loss {loss_val}"
    # honesty gate #2: the optimizer must actually be learning — same batch
    # every step, so loss strictly decreases over the measured window unless
    # the step is fake.
    assert loss_val < first_loss, (
        f"loss did not decrease over {warmup + steps} same-batch steps "
        f"({first_loss} -> {loss_val}): the measured step is not training"
    )
    print(
        f"bench: loss {first_loss:.4f} -> {loss_val:.4f} over {warmup + steps} steps",
        file=sys.stderr,
    )

    # v5e peak 197 bf16 TFLOP/s; 6*N*T FLOPs/token (fwd+bwd, weight FLOPs)
    mfu = 6.0 * n_params * tokens_per_sec / 197e12 if platform == "tpu" else 0.0

    secondary = [
        _bench_ernie(paddle, platform),
        _bench_sd_unet(paddle, platform),
        _bench_resnet_pipeline(paddle, platform),
        _bench_int8_decode(paddle, platform),
        _bench_quantized_kv_decode(paddle, platform),
        _bench_paged_decode(paddle, platform),
        _bench_engine_decode(paddle, platform),
        _bench_fused_decode_layer(paddle, platform),
        _bench_tp_decode(paddle, platform),
        _bench_shared_prefix_ttft(paddle, platform),
        _bench_kv_tier_multi_turn(paddle, platform),
        _bench_spec_decode(paddle, platform),
        _bench_engine_fault_recovery(paddle, platform),
        _bench_serving_goodput(paddle, platform),
        _bench_cluster_goodput(paddle, platform),
        _bench_traced_request_breakdown(paddle, platform),
    ]
    # explicit machine-readable status on EVERY record: a secondary that
    # returned an "error" field (or skipped itself, e.g. tp under 2
    # devices) did not measure anything — trajectory tooling must never
    # average its value as a real zero
    geometry_clean = _kernel_geometry_clean()
    for rec in secondary:
        rec.setdefault(
            "status",
            "error" if "error" in rec
            else "skipped" if "skipped" in rec
            else "measured",
        )
        if rec.get("metric") in _KERNEL_BEARING_METRICS:
            rec["geometry_clean"] = geometry_clean
    print(
        json.dumps(
            {
                "metric": f"llama_{n_params / 1e9:.2f}B_train_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(tokens_per_sec / BASELINE_TOKENS_PER_SEC_PER_CHIP, 4),
                "status": "measured",
                "mfu": round(mfu, 4),
                "fused_loss": fused_loss,
                "secondary": secondary,
            }
        )
    )


def _bench_ernie(paddle, platform: str) -> dict:
    """Secondary metric (BASELINE.md config #2): ERNIE-3.0-base finetune
    step time, AMP O2 (bf16 params, fp32 master weights in AdamW)."""
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForSequenceClassification

    try:
        if platform == "tpu":
            cfg = ErnieConfig.ernie3_base()
            batch, seq, steps, warmup = 32, 128, 10, 2
        else:
            cfg = ErnieConfig.tiny()
            batch, seq, steps, warmup = 2, 16, 2, 1

        paddle.seed(0)
        model = ErnieForSequenceClassification(cfg, num_classes=2)
        opt = paddle.optimizer.AdamW(learning_rate=2e-5, parameters=model.parameters())
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")

        @paddle.jit.to_static
        def step(model, opt, ids, labels):
            with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                logits = model(ids)
                loss = paddle.nn.functional.cross_entropy(logits, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.default_rng(1)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
        labels = paddle.to_tensor(rng.integers(0, 2, (batch,)).astype(np.int64))
        for _ in range(warmup):
            float(step(model, opt, ids, labels))
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = step(model, opt, ids, labels)
        lv = float(last)
        dt = time.perf_counter() - t0
        assert np.isfinite(lv), f"non-finite ernie loss {lv}"
        return {
            "metric": "ernie3_base_finetune_step_time_ms",
            "value": round(dt / steps * 1000.0, 2),
            "unit": "ms/step",
            "batch": batch,
            "seq": seq,
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "ernie3_base_finetune_step_time_ms", "error": f"{exc!r}"[:300]}


def _bench_sd_unet(paddle, platform: str) -> dict:
    """Tertiary metric (BASELINE.md config #5): Stable-Diffusion v1.5 UNet
    inference latency through the Predictor (bf16 serving, resident weights)."""
    from paddle_tpu import inference
    from paddle_tpu.models.sd_unet import UNet2DConditionModel, UNetConfig
    from paddle_tpu.static import InputSpec

    try:
        if platform == "tpu":
            cfg = UNetConfig.sd15()
            batch, hw, ctx_len, steps, warmup = 2, 64, 77, 10, 2
        else:
            cfg = UNetConfig.tiny()
            batch, hw, ctx_len, steps, warmup = 1, 16, 8, 2, 1

        paddle.seed(0)
        model = UNet2DConditionModel(cfg)
        model.eval()
        config = inference.Config.from_layer(
            model,
            [
                InputSpec([batch, cfg.in_channels, hw, hw], "float32", name="sample"),
                InputSpec([batch], "int32", name="timestep"),
                InputSpec([batch, ctx_len, cfg.cross_attention_dim], "float32", name="context"),
            ],
        )
        if platform == "tpu":
            config.enable_mixed_precision(inference.PrecisionType.Bfloat16)
        config.enable_memory_optim(False)  # keep inputs reusable across timed runs
        predictor = inference.create_predictor(config)
        rng = np.random.default_rng(2)
        feeds = [
            rng.normal(size=(batch, cfg.in_channels, hw, hw)).astype(np.float32),
            np.full((batch,), 10, np.int32),
            rng.normal(size=(batch, ctx_len, cfg.cross_attention_dim)).astype(np.float32),
        ]
        for _ in range(warmup):
            predictor.run(feeds)
        t0 = time.perf_counter()
        for _ in range(steps):
            outs = predictor.run(feeds)
        dt = time.perf_counter() - t0
        assert np.isfinite(np.asarray(outs[0], np.float32)).all()
        return {
            "metric": "sd15_unet_inference_images_per_sec",
            "value": round(batch * steps / dt, 2),
            "unit": "images/s",
            "batch": batch,
            "latent": hw,
        }
    except Exception as exc:  # noqa: BLE001
        return {"metric": "sd15_unet_inference_images_per_sec", "error": f"{exc!r}"[:300]}


def _bench_int8_decode(paddle, platform: str) -> dict:
    """int8 vs bf16 at the decode-dominant shape (VERDICT r5 #4): a GEMV-like
    [tokens, in] x [in, out] MLP projection is HBM-bandwidth-bound at decode,
    so int8 weights (half the bytes) should approach 2x. Measures bf16
    matmul vs weight-only int8 vs true-int8 (llm.int8) through jit."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.quantization as q

    try:
        if platform == "tpu":
            tokens, d_in, d_out, iters, warm = 8, 4096, 11008, 50, 5
        else:
            tokens, d_in, d_out, iters, warm = 2, 128, 256, 3, 1
        rng = np.random.default_rng(4)
        w = paddle.to_tensor(rng.normal(size=(d_in, d_out)).astype(np.float32) / np.sqrt(d_in))
        x = paddle.to_tensor(rng.normal(size=(tokens, d_in)).astype(np.float32))
        wb = w.astype("bfloat16")
        xb = x.astype("bfloat16")
        qw, sc = q.weight_quantize(w)

        bf16_fn = jax.jit(lambda a, ww: a @ ww)
        wol_fn = jax.jit(lambda a, qq, ss: q.weight_only_linear(
            paddle.to_tensor(a), paddle.to_tensor(qq), weight_scale=paddle.to_tensor(ss)
        )._data)
        i8_fn = jax.jit(lambda a, qq, ss: q.llm_int8_linear(
            paddle.to_tensor(a), paddle.to_tensor(qq), weight_scale=paddle.to_tensor(ss)
        )._data)

        def timed(fn, *args):
            for _ in range(warm):
                fn(*args).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters * 1e3

        t_bf16 = timed(bf16_fn, xb._data, wb._data)
        t_wol = timed(wol_fn, xb._data, qw._data, sc._data)
        t_i8 = timed(i8_fn, xb._data, qw._data, sc._data)
        return {
            "metric": "int8_decode_matmul_ms",
            "bf16_ms": round(t_bf16, 4),
            "weight_only_int8_ms": round(t_wol, 4),
            "llm_int8_ms": round(t_i8, 4),
            "weight_only_speedup_vs_bf16": round(t_bf16 / t_wol, 3),
            "shape": [tokens, d_in, d_out],
        }
    except Exception as exc:  # noqa: BLE001
        return {"metric": "int8_decode_matmul_ms", "error": f"{exc!r}"[:300]}


def _bench_quantized_kv_decode(paddle, platform: str) -> dict:
    """Quantized serving (FLAGS_kv_cache_dtype=int8 + weight-only int8):
    decode throughput and EFFECTIVE KV bytes/token against the bf16 engine,
    with the measured quality delta riding the record — greedy token-match
    rate through the full paged plane and max logit error of the quantized
    projections (inference.quality, the same harness the tier-1 tolerance
    gate asserts on). A quantized config that is fast but wrong shows up
    HERE, not in an incident."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.quality import quality_delta
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, max_new = 8, 16, 128, 16, 48
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, max_new = 2, 4, 16, 4, 8

        def build():
            paddle.seed(0)
            m = LlamaForCausalLM(cfg)
            if platform == "tpu":
                m = m.to(dtype="bfloat16")
            m.eval()
            return m

        ekw = dict(max_slots=slots, block_size=bs, prompt_bucket=bucket)
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(
                0, cfg.vocab_size, (int(rng.integers(bucket // 2, bucket + 1)),)
            ).astype(np.int32)
            for _ in range(n_req)
        ]
        quality = quality_delta(build, prompts, max_new, ekw)

        def timed(quant: bool) -> tuple:
            eng = ContinuousBatchingEngine(
                build(),
                kv_cache_dtype="int8" if quant else "bf16",
                weight_only_int8=quant,
                **ekw,
            )
            for p in prompts:
                eng.add_request(p, max_new_tokens=max_new)
            t0 = time.perf_counter()
            out = eng.run()
            dt = time.perf_counter() - t0
            toks = sum(len(r.generated) for r in out.values())
            return toks / dt, eng.pool_stats(), eng.stats["step_traces"]

        tps_bf16, _, traces_bf16 = timed(False)
        tps_q, qstats, traces_q = timed(True)
        return {
            "metric": "quantized_kv_decode_tokens_per_sec",
            "value": round(tps_q, 2),
            "unit": "tokens/s",
            "kv_cache_dtype": qstats["kv_cache_dtype"],
            "weight_only_int8": True,
            "bf16_tokens_per_sec": round(tps_bf16, 2),
            "speedup_vs_bf16": round(tps_q / tps_bf16, 3),
            "kv_bytes_per_token_bf16": quality["kv_bytes_per_token_bf16"],
            "kv_bytes_per_token_quant": quality["kv_bytes_per_token_quant"],
            "kv_bytes_reduction": round(quality["kv_bytes_reduction"], 3),
            # honesty: quantization is data + placements, never shapes —
            # each configuration compiles exactly one step signature
            "one_compile_per_engine": bool(traces_bf16 == 1 and traces_q == 1),
            "quality": {
                "token_match_rate": round(quality["token_match_rate"], 4),
                "tokens_compared": quality["tokens_compared"],
                "max_logit_error": round(
                    float(quality.get("max_logit_error", 0.0)), 5
                ),
            },
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "quantized_kv_decode_tokens_per_sec", "error": f"{exc!r}"[:300]}


def _bench_paged_decode(paddle, platform: str) -> dict:
    """Paged-cache decode step: Pallas block-table flash-decode vs the XLA
    dense-gather path (VERDICT r5 #6 A/B). Serving shape: the whole paged
    decode step (append + attend) jitted, per-step latency."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.nn.functional.block_attention import (
        block_multihead_attention,
    )

    try:
        if platform == "tpu":
            b, hq, hkv, d, bs, mbs, nb, iters, warm = 16, 32, 32, 128, 16, 64, 1024, 30, 5
        else:
            b, hq, hkv, d, bs, mbs, nb, iters, warm = 2, 4, 4, 64, 16, 4, 16, 2, 1
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.bfloat16)
        kv = jnp.asarray(rng.normal(size=(b, 1, hkv, d)), jnp.bfloat16)
        kc = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), jnp.bfloat16)
        vc = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), jnp.bfloat16)
        tables = jnp.asarray(
            rng.permutation(nb)[: b * mbs].reshape(b, mbs), jnp.int32
        )
        lens = jnp.asarray(rng.integers(bs, mbs * bs - 1, (b,)), jnp.int32)
        step = jax.jit(block_multihead_attention)

        def timed(flag: bool) -> float:
            paddle.set_flags({"FLAGS_use_pallas_paged_attention": flag})
            jax.clear_caches()  # the flag is baked at trace time
            for _ in range(warm):
                out, _, _ = step(q, kv, kv, kc, vc, tables, lens)
            out.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                out, _, _ = step(q, kv, kv, kc, vc, tables, lens)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters * 1e3

        t_xla = timed(False)
        t_pallas = timed(True) if platform == "tpu" else None
        rec = {
            "metric": "paged_decode_step_ms",
            "xla_gather_ms": round(t_xla, 4),
            "batch": b, "heads": hq, "ctx": int(mbs * bs),
        }
        if t_pallas is not None:
            rec["pallas_flash_decode_ms"] = round(t_pallas, 4)
            rec["pallas_speedup_vs_gather"] = round(t_xla / t_pallas, 3)
        return rec
    except Exception as exc:  # noqa: BLE001
        return {"metric": "paged_decode_step_ms", "error": f"{exc!r}"[:300]}


def _bench_engine_decode(paddle, platform: str) -> dict:
    """Continuous-batching decode throughput: a mixed-length request stream
    through the one-signature engine (``inference.ContinuousBatchingEngine``)
    — generated tokens/sec with slots refilled as sequences finish. The
    compiled-signature count rides along as an honesty check: > 1 means the
    engine retraced mid-serve and the number is measuring compiles. Runs with
    FLAGS_enable_metrics on, so the record carries the observability snapshot
    (TTFT/decode-latency percentiles, pool-utilization high-water, and the
    recompile watchdog's per-function compile counts)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    # pin the attention path explicitly (and restore it on the way out) —
    # _bench_paged_decode toggles this flag while timing, and the value it
    # happens to leave behind would otherwise decide which kernel this
    # metric measures
    flag_name = "FLAGS_use_pallas_paged_attention"
    prior_flags = paddle.get_flags([flag_name, "FLAGS_enable_metrics"])
    use_pallas = platform == "tpu"
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, max_new = 8, 16, 128, 24, 64
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, max_new = 2, 4, 16, 4, 6

        paddle.set_flags({flag_name: use_pallas, "FLAGS_enable_metrics": True})
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()  # compile ledger counts THIS engine only
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        engine = ContinuousBatchingEngine(
            model, max_slots=slots, block_size=bs, prompt_bucket=bucket
        )
        rng = np.random.default_rng(6)

        def submit(n: int) -> None:
            for _ in range(n):
                plen = int(rng.integers(max(bucket // 4, 1), bucket + 1))
                engine.add_request(
                    rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
                    max_new_tokens=int(rng.integers(max_new // 2, max_new + 1)),
                )

        submit(2)  # warmup: compiles the unified step signature
        engine.run()
        # keep the watchdog ledger (the warmup compile IS the signature;
        # any compile past them is the retrace the honesty check exists for)
        # but zero the latency/pool metrics so percentiles cover only the
        # timed window
        obs.GLOBAL_METRICS.reset()
        submit(n_req)
        t0 = time.perf_counter()
        out = engine.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in out.values())

        wd = {
            fn: rec["count"]
            for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
            if fn.startswith("ContinuousBatchingEngine.")
        }
        ttft = obs.GLOBAL_METRICS.get("engine_ttft_seconds")
        step_h = obs.GLOBAL_METRICS.get("engine_decode_step_seconds")

        def pct(h) -> dict:
            return {
                "p50": round(h.quantile(0.5) * 1e3, 3),
                "p95": round(h.quantile(0.95) * 1e3, 3),
                "p99": round(h.quantile(0.99) * 1e3, 3),
                "count": h.count(),
            }

        return {
            "metric": "engine_decode_tokens_per_sec",
            "value": round(toks / dt, 2),
            "unit": "tokens/s",
            "requests": n_req,
            "generated_tokens": toks,
            "max_slots": slots,
            "tp_degree": engine.tp_degree,
            "attention_path": "pallas" if use_pallas else "xla_gather",
            # the watchdog's numbers, not the engine's ad-hoc counter
            "compiled_signatures": sum(wd.values()),
            "metrics": {
                "ttft_ms": pct(ttft),
                "decode_step_ms": pct(step_h),
                "kv_pool_utilization_peak": round(
                    obs.GLOBAL_METRICS.get("engine_kv_pool_utilization").high_water(), 4
                ),
                "compiles_by_fn": wd,
            },
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "engine_decode_tokens_per_sec", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior_flags)


def _bench_fused_decode_layer(paddle, platform: str) -> dict:
    """Decode-step megakernel (``FLAGS_use_fused_decode_layer``): per-layer
    dispatch count fused vs unfused from the trace-time probe (the python of
    the jitted step runs once per compile, so each armed site counts once
    per signature), byte-identity of the two token streams (the PR's
    correctness acceptance — a mismatch is recorded as an error, never as a
    throughput number), and the comm/compute story both ways: the analytic
    all-reduce share of one tp decode layer (``comm_share_analytic`` —
    row-parallel collective bytes vs MXU time at peak) NEXT TO the devprof
    measurement (``comm_share_measured`` from a profiled tp=2 fused engine,
    skipped cleanly on 1 device; ``host_bubble_fraction`` from the fused
    run's sampled steps)."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.kernels.fused import arm_dispatch_probe, disarm_dispatch_probe
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    flag = "FLAGS_use_fused_decode_layer"
    prior = paddle.get_flags([flag, "FLAGS_devprof_sample_rate"])
    metric = "fused_decode_layer_dispatches_per_layer"
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, max_new = 8, 16, 128, 16, 48
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, max_new = 2, 4, 16, 4, 6
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(0, cfg.vocab_size, (int(rng.integers(max(bucket // 4, 1), bucket + 1)),)).astype(np.int32)
            for _ in range(n_req)
        ]
        budgets = [int(rng.integers(max_new // 2, max_new + 1)) for _ in range(n_req)]

        def run(fused: bool):
            # every step device-profiled: the fused record carries a
            # MEASURED host-bubble fraction next to the analytic comm share
            paddle.set_flags({flag: fused, "FLAGS_devprof_sample_rate": 1.0})
            eng = ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, prompt_bucket=bucket
            )
            rids = [
                eng.add_request(p, max_new_tokens=t)
                for p, t in zip(prompts, budgets)
            ]
            arm_dispatch_probe()
            try:
                t0 = time.perf_counter()
                out = eng.run()
                dt = time.perf_counter() - t0
            finally:
                sites = disarm_dispatch_probe()
            toks = [out[r].tokens().tolist() for r in rids]
            ntoks = sum(len(out[r].generated) for r in rids)
            return (
                sites, toks, ntoks / dt, eng.stats["step_traces"],
                eng.devprof_stats(),
            )

        sites_f, toks_f, tps_f, traces_f, devprof_f = run(True)
        sites_u, toks_u, tps_u, traces_u, _devprof_u = run(False)

        # measured comm share: a devprof-profiled tp=2 fused engine over a
        # small slice of the same stream (skipped cleanly on 1 device —
        # there is no collective to measure). Under GSPMD the all-reduces
        # are compiler-inserted, so comm_source reports how the share was
        # attributed (wrapper timing vs cost-model prior).
        import jax as _jax

        ndev = len(_jax.devices())
        if ndev >= 2 and cfg.num_key_value_heads % 2 == 0:
            paddle.set_flags({flag: True, "FLAGS_devprof_sample_rate": 1.0})
            eng_tp = ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, prompt_bucket=bucket,
                tp=2,
            )
            for p, t in zip(prompts[:2], budgets[:2]):
                eng_tp.add_request(p, max_new_tokens=t)
            eng_tp.run()
            dp = eng_tp.devprof_stats()
            comm_share_measured = {
                "value": dp.get("comm_share_measured", 0.0),
                "comm_sources": dp.get("comm_sources", {}),
                "sampled_steps": dp.get("sampled_steps", 0),
                "tp_degree": 2,
                "status": "measured",
            }
        else:
            comm_share_measured = {
                "status": "skipped",
                "reason": f"needs >= 2 devices with shardable kv heads, "
                          f"have {ndev} device(s)",
            }
        if toks_f != toks_u:
            return {
                "metric": metric,
                "error": "fused/unfused token streams diverge — fusion is broken",
            }

        n_layers = cfg.num_hidden_layers
        step_f = ("fused:embed_norm", "fused:rope_gather")
        step_u = ("unfused:embed", "unfused:final_norm")
        per_layer_f = sum(v for k, v in sites_f.items() if k not in step_f) / n_layers
        per_layer_u = sum(v for k, v in sites_u.items() if k not in step_u) / n_layers

        # analytic tp all-reduce share of one decode layer per token:
        # row-parallel o_proj + down_proj each all-reduce [1, H] activations
        # over ICI while the column/row matmuls run on the MXU
        itemsize = 2 if platform == "tpu" else 4
        h, inter = cfg.hidden_size, cfg.intermediate_size
        ar_bytes = 2 * h * itemsize
        mm_flops = 2 * (4 * h * h + 3 * h * inter)
        t_ar = ar_bytes / 45e9  # v5e ICI ~45 GB/s per link
        t_mm = mm_flops / (197e12 if platform == "tpu" else 1e12)
        return {
            "metric": metric,
            "value": round(per_layer_f, 2),
            "unit": "dispatch sites/layer/step",
            "unfused_dispatches_per_layer": round(per_layer_u, 2),
            "dispatch_sites": {"fused": sites_f, "unfused": sites_u},
            "tokens_per_sec": {
                "fused": round(tps_f, 2), "unfused": round(tps_u, 2)
            },
            "byte_identical_fused_on_off": True,
            "compiled_signatures": {"fused": traces_f, "unfused": traces_u},
            # labeled analytic so it can never be confused with the devprof
            # MEASUREMENT next to it
            "comm_share_analytic": {
                "value": round(t_ar / (t_ar + t_mm), 4),
                "method": "analytic_estimate",
                "model": "2*H*itemsize bytes over ICI vs layer matmul FLOPs at peak",
            },
            "comm_share_measured": comm_share_measured,
            "host_bubble_fraction": (
                {
                    "value": devprof_f.get("mean_host_bubble_fraction", 0.0),
                    "sampled_steps": devprof_f.get("sampled_steps", 0),
                    "status": "measured",
                }
                if devprof_f.get("sampled_steps")
                else {"status": "skipped", "reason": "no sampled steps"}
            ),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": metric, "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_tp_decode(paddle, platform: str) -> dict:
    """Tensor-parallel decode throughput (guarded): the same mixed-length
    request stream through a single-chip engine and a ``tp``-sharded engine
    over the device mesh (``distributed/tp.py`` — head-parallel attention +
    per-device KV pool partition, Megatron MLP splits, vocab-sharded
    lm-head). Skips cleanly with fewer than 2 devices. Records per-chip and
    aggregate decode tokens/s, the all-reduce time share BOTH ways —
    ``comm_share_analytic`` (from scaling efficiency: ``1 - t1/(tp*t_tp)``,
    the gap between the observed sharded step and perfect linear scaling)
    next to devprof's ``comm_share_measured`` (per-sampled-step attribution,
    with its ``comm_source`` provenance) and ``host_bubble_fraction`` — the
    byte-identity of the sharded outputs, and the 1-compile-per-engine
    honesty field."""
    import jax as _jax

    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    metric = "tp_decode_tokens_per_sec"
    ndev = len(_jax.devices())
    if ndev < 2:
        return {"metric": metric, "skipped": f"needs >= 2 devices, have {ndev}"}
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, max_new = 8, 16, 128, 24, 64
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, max_new = 2, 4, 16, 4, 6
        # largest power-of-two shard count the KV heads and mesh support
        tp = 1
        while (
            tp * 2 <= min(8, ndev)
            and cfg.num_key_value_heads % (tp * 2) == 0
        ):
            tp *= 2
        if tp < 2:
            return {
                "metric": metric,
                "skipped": f"kv heads {cfg.num_key_value_heads} not shardable "
                           f"over {ndev} devices",
            }
        obs.GLOBAL_WATCHDOG.reset()
        prior_dp = paddle.get_flags(["FLAGS_devprof_sample_rate"])
        paddle.set_flags({"FLAGS_devprof_sample_rate": 1.0})

        def build(tp_degree: int):
            paddle.seed(0)
            model = LlamaForCausalLM(cfg)
            if platform == "tpu":
                model = model.to(dtype="bfloat16")
            model.eval()
            return ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, prompt_bucket=bucket,
                tp=tp_degree,
            )

        def run(engine) -> tuple:
            rng = np.random.default_rng(6)

            def submit(n: int) -> list:
                rids = []
                for _ in range(n):
                    plen = int(rng.integers(max(bucket // 4, 1), bucket + 1))
                    rids.append(engine.add_request(
                        rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
                        max_new_tokens=int(rng.integers(max_new // 2, max_new + 1)),
                    ))
                return rids
            submit(2)
            engine.run()  # warmup: compiles the one step signature
            rids = submit(n_req)
            t0 = time.perf_counter()
            out = engine.run()
            dt = time.perf_counter() - t0
            toks = sum(len(r.generated) for r in out.values())
            streams = [out[r].tokens().tolist() for r in rids]
            return (
                toks / dt, streams, engine.stats["step_traces"],
                engine.devprof_stats(),
            )

        try:
            tput1, streams1, compiles1, devprof1 = run(build(1))
            tput_tp, streams_tp, compiles_tp, devprof_tp = run(build(tp))
        finally:
            paddle.set_flags(prior_dp)
        # the watchdog ledger cross-checks the per-engine counters: exactly
        # one recorded step compile per engine, and none from anywhere else
        wd_steps = sum(
            rec["count"]
            for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
            if fn.startswith("ContinuousBatchingEngine.")
        )
        speedup = tput_tp / tput1 if tput1 else 0.0
        # comm share estimate: the shortfall vs perfect linear scaling of
        # the (compute-bound) sharded step — t1/t_tp == tput_tp/tput1, so
        # 1 - t1/(tp*t_tp) == 1 - tput_tp/(tp*tput1); 0 at perfect scaling
        share = max(0.0, min(1.0, 1.0 - tput_tp / (tp * tput1))) if tput1 else 0.0
        return {
            "metric": metric,
            "value": round(tput_tp, 2),
            "unit": "tokens/s",
            "tp_degree": tp,
            "per_chip_tokens_per_sec": round(tput_tp / tp, 2),
            "tp1_tokens_per_sec": round(tput1, 2),
            "speedup_vs_tp1": round(speedup, 4),
            # labeled analytic vs measured so the two can never be confused
            # downstream: the estimate infers comm from scaling shortfall,
            # the measurement attributes each sampled step's device segment
            "comm_share_analytic": {
                "value": round(share, 4),
                "method": "analytic_estimate",
                "model": "1 - tput_tp/(tp*tput1) scaling shortfall",
            },
            "comm_share_measured": (
                {
                    "value": devprof_tp.get("comm_share_measured", 0.0),
                    "comm_sources": devprof_tp.get("comm_sources", {}),
                    "sampled_steps": devprof_tp.get("sampled_steps", 0),
                    "status": "measured",
                }
                if devprof_tp.get("sampled_steps")
                else {"status": "skipped", "reason": "no sampled steps"}
            ),
            "host_bubble_fraction": {
                "tp1": devprof1.get("mean_host_bubble_fraction"),
                "tp": devprof_tp.get("mean_host_bubble_fraction"),
                "status": "measured",
            },
            "byte_identical_vs_tp1": streams_tp == streams1,
            # honesty: each engine compiled its unified step exactly once,
            # and the watchdog ledger agrees (catches stray compiles too)
            "compiles_tp1_engine": compiles1,
            "compiles_tp_engine": compiles_tp,
            "watchdog_step_compiles": wd_steps,
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": metric, "error": f"{exc!r}"[:300]}


def _bench_shared_prefix_ttft(paddle, platform: str) -> dict:
    """Prefix-cache acceptance bench (guarded): N requests share a long
    system prompt. Cold phase computes it once; the warm phase must MAP it
    (content-hash block dedup) instead of recomputing — warm TTFT below cold
    TTFT, hit rate > 0, and the prefill token-compute counter showing the
    shared prefix computed exactly once across all N requests. The 1-compile
    watchdog count rides along as the chunked-prefill honesty check."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    prior = paddle.get_flags(
        ["FLAGS_enable_metrics", "FLAGS_enable_prefix_cache"]
    )
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_warm, shared_len, tail, max_new = (
                8, 16, 256, 12, 192, 16, 16
            )
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_warm, shared_len, tail, max_new = (
                2, 4, 32, 4, 20, 3, 4
            )
        paddle.set_flags(
            {"FLAGS_enable_metrics": True, "FLAGS_enable_prefix_cache": True}
        )
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        engine = ContinuousBatchingEngine(
            model, max_slots=slots, block_size=bs, prompt_bucket=bucket
        )
        rng = np.random.default_rng(7)
        system_prompt = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)

        def submit_one():
            user = rng.integers(0, cfg.vocab_size, (tail,)).astype(np.int32)
            return engine.add_request(
                np.concatenate([system_prompt, user]), max_new_tokens=max_new
            )

        def ttfts(out):
            return sorted(
                r.admit_time - r.arrival_time for r in out.values()
            )

        # cold: ONE request computes the shared prefix (plus the engine's
        # one compile — excluded from timing by a throwaway warmup first)
        engine.add_request(
            rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
            max_new_tokens=2,
        )
        engine.run()
        computed_before = engine.stats["prompt_tokens_computed"]
        submit_one()
        cold_out = engine.run()
        cold_ttft = ttfts(cold_out)
        cold_prefix_computed = (
            engine.stats["prompt_tokens_computed"] - computed_before
        )

        # warm: N requests repeat the system prompt with distinct tails
        computed_before = engine.stats["prompt_tokens_computed"]
        for _ in range(n_warm):
            submit_one()
        warm_out = engine.run()
        warm_ttft = ttfts(warm_out)
        warm_computed = engine.stats["prompt_tokens_computed"] - computed_before

        cache = engine.prefix_cache_stats()
        wd = {
            fn: rec["count"]
            for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
            if fn.startswith("ContinuousBatchingEngine.")
        }
        # the shared prefix's full blocks were computed exactly once (by the
        # cold request); warm requests computed only tails + ragged ends
        shared_full = (shared_len // bs) * bs
        per_warm_computed = warm_computed / n_warm

        def pct(sorted_vals, q):
            if not sorted_vals:
                return 0.0
            i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
            return sorted_vals[i]

        return {
            "metric": "shared_prefix_ttft_speedup",
            "value": round(
                pct(cold_ttft, 0.5) / max(pct(warm_ttft, 0.5), 1e-9), 3
            ),
            "unit": "x (cold TTFT p50 / warm TTFT p50)",
            "cold_ttft_ms": {"p50": round(pct(cold_ttft, 0.5) * 1e3, 3),
                             "p99": round(pct(cold_ttft, 0.99) * 1e3, 3)},
            "warm_ttft_ms": {"p50": round(pct(warm_ttft, 0.5) * 1e3, 3),
                             "p99": round(pct(warm_ttft, 0.99) * 1e3, 3)},
            "shared_prefix_tokens": int(shared_len),
            "warm_requests": n_warm,
            "hit_rate": round(cache["hit_rate"], 4),
            "tokens_reused": cache["tokens_reused"],
            "bytes_saved": cache["bytes_saved"],
            "cow_forks": cache["cow_forks"],
            "prefix_computed_once": bool(
                cold_prefix_computed >= shared_full
                and per_warm_computed <= (shared_len - shared_full) + tail + bs
            ),
            "prompt_tokens_computed_per_warm_request": round(per_warm_computed, 2),
            # honesty check: chunked prefill + cache hits through ONE program
            "compiled_signatures": sum(wd.values()),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "shared_prefix_ttft_speedup", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_kv_tier_multi_turn(paddle, platform: str) -> dict:
    """Hierarchical-KV acceptance bench (guarded): warm TTFT of a seeded
    multi-turn conversation trace against a DELIBERATELY small device pool
    — the regime the host tier exists for: the conversations' chains do not
    fit HBM, so between turns they get evicted, and turn k+1 either
    recomputes its whole history (tier off) or prefetches it H2D from host
    RAM (tier on). Reports warm-TTFT p50/p99, prefix hit rate and
    spill/prefetch/drop counters across a host-cache-size sweep
    (``FLAGS_kv_host_tier_bytes`` 0 = off, then small, then ample), plus
    the 1-compile honesty check: spill and prefetch are pure data movement
    outside the traced step, so the recompile watchdog must still report
    exactly ONE compile per engine at every sweep point."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    prior = paddle.get_flags(["FLAGS_enable_metrics"])
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=2048,
            )
            slots, bs, num_blocks, bucket, max_len = 4, 16, 96, 1024, 1536
            n_convs, n_turns, turn_tail, max_new = 6, 4, 48, 32
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, num_blocks, bucket, max_len = 2, 4, 12, 40, 56
            n_convs, n_turns, turn_tail, max_new = 3, 3, 4, 3
        paddle.set_flags({"FLAGS_enable_metrics": False})
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        bytes_per_block = (
            2 * cfg.num_hidden_layers * cfg.num_key_value_heads
            * (cfg.hidden_size // cfg.num_attention_heads) * bs
            * (2 if platform == "tpu" else 4)
        )
        # the whole trace's chain working set, in blocks — "small" holds
        # about a third of it, "ample" all of it
        worst_blocks = n_convs * (
            (n_turns * (turn_tail + max_new)) // bs + 1
        )
        sweep_budgets = [0, (worst_blocks // 3) * bytes_per_block,
                         worst_blocks * bytes_per_block]

        def drive(tier_bytes):
            obs.GLOBAL_WATCHDOG.reset()
            engine = ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, num_blocks=num_blocks,
                prompt_bucket=bucket, max_model_len=max_len,
                kv_host_tier_bytes=tier_bytes,
            )
            rng = np.random.default_rng(11)
            streams = {}
            warm_ttfts = []
            # warmup: the engine's one compile, off the clock
            engine.add_request(
                rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                max_new_tokens=2,
            )
            engine.run()
            # the seeded trace: conversations interleave round-robin, so a
            # conversation's chains face the other conversations' pool
            # pressure between its own turns
            for turn in range(n_turns):
                for conv in range(n_convs):
                    tail = rng.integers(
                        0, cfg.vocab_size, (turn_tail,)
                    ).astype(np.int32)
                    prev = streams.get(conv)
                    prompt = (
                        tail if prev is None
                        else np.concatenate([prev, tail])
                    )
                    cap = min(bucket, max_len - max_new - bs)
                    if prompt.size > cap:
                        prompt = prompt[-cap:]
                    rid = engine.add_request(prompt, max_new_tokens=max_new)
                    out = engine.run()
                    streams[conv] = out[rid].tokens()
                    if turn > 0:
                        warm_ttfts.append(
                            out[rid].admit_time - out[rid].arrival_time
                        )
            warm_ttfts.sort()
            cache = engine.prefix_cache_stats()
            tier = engine.kv_tier_stats()
            wd = {
                fn: rec["count"]
                for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
                if fn.startswith("ContinuousBatchingEngine.")
            }

            def pct(q):
                if not warm_ttfts:
                    return 0.0
                i = min(len(warm_ttfts) - 1, int(q * len(warm_ttfts)))
                return warm_ttfts[i]

            lookups = cache["hits"] + cache["misses"]
            return {
                "kv_host_tier_bytes": int(tier_bytes),
                "warm_ttft_ms": {"p50": round(pct(0.5) * 1e3, 3),
                                 "p99": round(pct(0.99) * 1e3, 3)},
                "hit_rate": round(cache["hit_rate"], 4),
                "host_hit_rate": round(
                    cache["host_hits"] / lookups if lookups else 0.0, 4
                ),
                "tokens_reused": cache["tokens_reused"],
                "spilled_blocks": tier.get("spilled_blocks", 0),
                "prefetched_blocks": tier.get("prefetched_blocks", 0),
                "dropped_blocks": tier.get("dropped_blocks", 0),
                "host_bytes_peak": tier.get("host_bytes", 0),
                "compiled_signatures": sum(wd.values()),
            }

        sweep = [drive(b) for b in sweep_budgets]
        off_p50 = sweep[0]["warm_ttft_ms"]["p50"]
        best_on = min(pt["warm_ttft_ms"]["p50"] for pt in sweep[1:])
        return {
            "metric": "kv_tier_multi_turn_ttft",
            "value": round(off_p50 / max(best_on, 1e-9), 3),
            "unit": "x (tier-off warm TTFT p50 / best tier-on p50)",
            "device_pool_blocks": num_blocks,
            "trace": {"conversations": n_convs, "turns": n_turns,
                      "turn_tail_tokens": turn_tail, "max_new": max_new},
            "sweep": sweep,
            # honesty: data movement added zero compiled signatures anywhere
            "compiled_signatures_per_engine": max(
                pt["compiled_signatures"] for pt in sweep
            ),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "kv_tier_multi_turn_ttft", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_spec_decode(paddle, platform: str) -> dict:
    """Speculative-decoding acceptance bench (guarded): decode tokens/s with
    n-gram self-speculation off vs on over a REPETITIVE continuation
    workload — the regime speculation exists for (templated text, code,
    multi-turn chats, the cyclic tails greedy decode settles into).

    Construction (fully seeded, honest): phase A generates continuations
    for a pool of seeded candidate prompts, scores each result by OFFLINE
    drafter self-acceptance (would the prompt-lookup drafter have predicted
    each of the last ``span`` tokens from the tokens before it?), and keeps
    the candidates whose continuations are genuinely self-predictable —
    exactly the requests speculation targets. Phase B times the SAME
    continuation requests (prompt = candidate + its phase-A continuation,
    so decoding resumes inside the repetitive regime) through two engines,
    speculation off then on, and reports the tokens/s ratio alongside the
    honesty checks: greedy outputs byte-identical between the two runs, and
    the recompile watchdog showing exactly ONE compile per engine — drafts
    and rewinds are data on the one ``[max_slots, prefill_chunk]``
    signature, never a new program."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine, NGramDrafter
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    prior = paddle.get_flags(
        ["FLAGS_enable_metrics", "FLAGS_spec_decode_tokens",
         "FLAGS_spec_decode_ngram"]
    )
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=2048,
            )
            slots, bs, chunk, spec_k = 8, 16, 16, 8
            n_cand, probe_new, max_new, keep = 16, 160, 128, 8
            bucket, model_len = 512, 1024
        else:  # tiny CPU smoke: the same machinery with a small budget
            cfg = LlamaConfig.tiny()
            slots, bs, chunk, spec_k = 2, 4, 8, 7
            n_cand, probe_new, max_new, keep = 16, 120, 96, 6
            bucket, model_len = 192, 512
        paddle.set_flags({
            "FLAGS_enable_metrics": True,
            "FLAGS_spec_decode_tokens": spec_k,
            "FLAGS_spec_decode_ngram": 3,
        })
        obs.GLOBAL_METRICS.reset()
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        rng = np.random.default_rng(9)
        cands = [
            rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
            for _ in range(n_cand)
        ]

        def make_engine(spec_on):
            return ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, prompt_bucket=bucket,
                prefill_chunk=chunk, max_model_len=model_len,
                spec_decode=spec_on,
            )

        drafter = NGramDrafter(3)

        def self_acceptance(tokens, span=24):
            hits = 0
            for t in range(len(tokens) - span, len(tokens)):
                prop = drafter.propose(np.asarray(tokens[:t], np.int32), 1)
                hits += prop.size == 1 and int(prop[0]) == tokens[t]
            return hits / span

        # phase A (untimed): generate candidate continuations, keep the
        # self-predictable ones — the repetitive slice of the traffic
        eng0 = make_engine(False)
        rids = [eng0.add_request(p, max_new_tokens=probe_new) for p in cands]
        out0 = eng0.run()
        scored = sorted(
            ((self_acceptance(list(out0[r].tokens())), r) for r in rids),
            reverse=True,
        )
        prompts = [out0[r].tokens() for s, r in scored if s >= 0.6][:keep]
        if len(prompts) < 2:  # never run an empty workload
            prompts = [out0[r].tokens() for _, r in scored[:2]]

        def timed(spec_on):
            obs.GLOBAL_WATCHDOG.reset()  # compile ledger counts THIS engine
            eng = make_engine(spec_on)
            eng.add_request(cands[0][:4], max_new_tokens=2)
            eng.run()  # the one compile happens outside the timed window
            rids_ = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
            t0 = time.perf_counter()
            out = eng.run()
            dt = time.perf_counter() - t0
            toks = sum(len(out[r].generated) for r in rids_)
            wd = sum(
                rec["count"]
                for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
                if fn.startswith("ContinuousBatchingEngine.")
            )
            return eng, [out[r].tokens() for r in rids_], toks / dt, wd

        eng_off, toks_off, tps_off, wd_off = timed(False)
        eng_on, toks_on, tps_on, wd_on = timed(True)
        identical = all(
            np.array_equal(a, b) for a, b in zip(toks_off, toks_on)
        )
        spec = eng_on.spec_decode_stats()
        return {
            "metric": "spec_decode_tokens_per_sec",
            "value": round(tps_on, 2),
            "unit": "tokens/s (speculation on, repetitive continuation workload)",
            "speedup_vs_off": round(tps_on / tps_off, 3) if tps_off else 0.0,
            "baseline_tokens_per_sec": round(tps_off, 2),
            "acceptance_rate": round(spec["acceptance_rate"], 4),
            "drafted_tokens": spec["drafted_tokens"],
            "accepted_tokens": spec["accepted_tokens"],
            "speculative_steps": spec["speculative_steps"],
            "steps_off": eng_off.stats["steps"],
            "steps_on": eng_on.stats["steps"],
            "requests": len(prompts),
            "max_new_tokens": max_new,
            "draft_tokens_max": spec_k,
            # honesty checks: same greedy stream, same ONE compiled program
            "greedy_identical_on_vs_off": bool(identical),
            "compiled_signatures_per_engine": {"off": wd_off, "on": wd_on},
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "spec_decode_tokens_per_sec", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_engine_fault_recovery(paddle, platform: str) -> dict:
    """Fault-injection smoke (guarded): one injected decode-step fault
    mid-workload; the engine must recover — reallocate the KV pools, replay
    every live request from host truth — and finish the whole workload
    through the SAME compiled program. Records the recovered decode
    throughput and the recovery counters, so a fault-tolerance regression
    shows up in the bench record, not just in tier-1."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.testing import faults

    prior = paddle.get_flags(["FLAGS_enable_metrics"])
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, max_new = 4, 16, 128, 8, 32
        else:
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, max_new = 2, 4, 16, 4, 6

        paddle.set_flags({"FLAGS_enable_metrics": True})
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        engine = ContinuousBatchingEngine(
            model, max_slots=slots, block_size=bs, prompt_bucket=bucket
        )
        rng = np.random.default_rng(6)
        for _ in range(n_req):
            plen = int(rng.integers(max(bucket // 4, 1), bucket + 1))
            engine.add_request(
                rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
                max_new_tokens=int(rng.integers(max_new // 2, max_new + 1)),
            )
        # the fault lands mid-workload (a few dispatches in), after the
        # signature compiled — the recovery itself is what's timed
        plan = faults.FaultPlan.single("engine.decode", call_index=3)
        t0 = time.perf_counter()
        with faults.inject(plan):
            out = engine.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in out.values())
        reg = obs.GLOBAL_METRICS
        wd = {
            fn: rec["count"]
            for fn, rec in obs.GLOBAL_WATCHDOG.report().items()
            if fn.startswith("ContinuousBatchingEngine.")
        }
        assert len(out) == n_req, f"requests lost across recovery: {len(out)}/{n_req}"
        return {
            "metric": "engine_fault_recovery_tokens_per_sec",
            "value": round(toks / dt, 2),
            "unit": "tokens/s",
            "requests": n_req,
            "generated_tokens": toks,
            "faults_injected": int(reg.get("faults_injected_total").total()),
            "recoveries": int(reg.get("engine_recoveries_total").value()),
            "requests_replayed": int(reg.get("engine_requests_replayed_total").value()),
            # honesty check: recovery must REUSE the one compiled program
            "compiled_signatures": sum(wd.values()),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "engine_fault_recovery_tokens_per_sec", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_serving_goodput(paddle, platform: str) -> dict:
    """Open-loop overload bench (guarded): seeded Poisson arrivals at 2x the
    calibrated sustainable rate, a tenant/priority mix with per-class SLOs,
    through the full serving frontend (bounded intake, weighted fair
    admission, deadlines, hysteresis shedding). Reports GOODPUT — tokens of
    requests that finished inside their SLO — plus per-class SLO attainment
    and the shed/deadline accounting, with the 2-compile honesty check: an
    overload storm must be absorbed by scheduling, never by recompiling.
    Seeded arrivals make reruns comparable (the arrival schedule, class mix
    and prompt shapes all derive from the seeds below)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Priority, ServingConfig, ServingFrontend
    from paddle_tpu.serving.loadgen import (
        TrafficClass,
        measure_sustainable_rate,
        poisson_arrivals,
        run_open_loop,
    )

    prior = paddle.get_flags(["FLAGS_enable_metrics"])
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_arrivals, calib = 8, 16, 128, 96, 16
            plen, max_new, slo_s, max_queue = (16, 96), (16, 48), 8.0, 32
        else:  # tiny CPU smoke: the same machinery with a small budget
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_arrivals, calib = 2, 4, 16, 24, 6
            plen, max_new, slo_s, max_queue = (3, 8), (3, 8), 2.0, 8

        paddle.set_flags({"FLAGS_enable_metrics": True})
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()  # compile ledger counts THIS engine only
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        engine = ContinuousBatchingEngine(
            model, max_slots=slots, block_size=bs, prompt_bucket=bucket
        )
        frontend = ServingFrontend(engine, ServingConfig(max_queue=max_queue))
        rate = measure_sustainable_rate(
            frontend, calib, seed=7, prompt_len=plen, max_new_tokens=max_new,
            vocab_size=cfg.vocab_size,
        )
        # calibration traffic must not pollute the overload window's counters
        obs.GLOBAL_METRICS.reset()
        mix = [
            TrafficClass("chat", Priority.INTERACTIVE, 2.0, plen, max_new, slo_s),
            TrafficClass("app", Priority.STANDARD, 2.0, plen, max_new, slo_s),
            TrafficClass("batch", Priority.BEST_EFFORT, 1.0, plen, max_new, slo_s),
        ]
        arrivals = poisson_arrivals(
            2.0 * rate, n_arrivals, mix, seed=8, vocab_size=cfg.vocab_size
        )
        report = run_open_loop(frontend, arrivals, max_wall_s=120.0)
        reg = obs.GLOBAL_METRICS
        shed = reg.get("serving_shed_total")
        shed_by_reason = {
            v["labels"]["reason"]: int(v["value"]) for v in shed._snapshot_values()
        }
        return {
            "metric": "serving_goodput_tokens_per_sec",
            "value": report["goodput_tokens_per_sec"],
            "unit": "tokens/s",
            "offered_rate_rps": round(2.0 * rate, 2),
            "sustainable_rate_rps": round(rate, 2),
            "arrivals": n_arrivals,
            "slo_s": slo_s,
            "slo_attainment": {
                k: v["slo_attainment"] for k, v in report["per_class"].items()
            },
            "shed_total_by_reason": shed_by_reason,
            "deadline_misses": int(
                reg.get("serving_deadline_miss_total").total()
            ),
            "overload_level_peak": int(
                reg.get("serving_overload_level").high_water()
            ),
            # honesty check: overload must add ZERO compiles past the two
            # signatures calibration warmed up
            "compiled_signatures": report["compiled_signatures_total"],
            "compiles_during_overload": sum(
                report["compiles_during_run"].values()
            ),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "serving_goodput_tokens_per_sec", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)


def _bench_cluster_goodput(paddle, platform: str) -> dict:
    """Cluster-scale overload bench (guarded): three ``ServingFrontend``
    replicas behind the prefix-affinity router, seeded Poisson arrivals at
    2x the calibrated CLUSTER rate (per-replica sustainable rate x replica
    count), and ONE REPLICA KILLED MID-STORM through the ``replica.kill``
    fault site. Reports aggregate goodput, per-class SLO attainment,
    failover latency p99, salvage/re-dispatch accounting, and the affinity
    hit rate before vs after the kill (the survivors' rendezvous shares are
    untouched, so warmth should largely survive the membership change) —
    with the honesty checks: exactly one compiled signature per engine, and
    the storm window (kill included) adds ZERO compiles.

    The fleet observability layer rides along: a ClusterObserver drives the
    SLO burn-rate monitor from the router's probe loop, and the record
    carries the monitor's state timeline (time-in-WARN/PAGE across the
    kill) plus the 1-compile-per-engine proof that the whole observability
    layer — replica-scoped metrics, burn-rate sampling, incident snapshots —
    adds ZERO compiled signatures."""
    import tempfile as _tempfile

    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (
        Priority,
        ReplicaCluster,
        ReplicaRouter,
        RouterConfig,
        ServingConfig,
        ServingFrontend,
    )
    from paddle_tpu.serving.loadgen import (
        TrafficClass,
        measure_sustainable_rate,
        poisson_arrivals,
        run_cluster_open_loop,
    )
    from paddle_tpu.testing import faults

    prior = paddle.get_flags(["FLAGS_enable_metrics"])
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_arrivals, calib = 4, 16, 128, 96, 12
            plen, max_new, slo_s, max_queue = (16, 96), (16, 48), 8.0, 16
        else:  # tiny CPU smoke: the same machinery with a small budget
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_arrivals, calib = 2, 4, 16, 24, 6
            plen, max_new, slo_s, max_queue = (3, 8), (3, 8), 2.0, 8
        n_replicas, kill_frac = 3, 0.4

        paddle.set_flags({"FLAGS_enable_metrics": True})
        obs.GLOBAL_METRICS.reset()
        obs.GLOBAL_WATCHDOG.reset()  # compile ledger counts THESE engines only
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()

        # replicas share the model object (read-only at inference): identical
        # weights are what makes failover re-generation deterministic
        def factory(name):
            eng = ContinuousBatchingEngine(
                model, max_slots=slots, block_size=bs, prompt_bucket=bucket
            )
            return ServingFrontend(eng, ServingConfig(max_queue=max_queue))

        cluster = ReplicaCluster(factory, [f"r{i}" for i in range(n_replicas)])
        router = ReplicaRouter(cluster, RouterConfig())
        # fleet observability riding the probe loop: the burn-rate monitor's
        # windows are sized to the storm (the kill must register as
        # sustained within the run), the TTFT target is the workload SLO
        observer = obs.ClusterObserver(
            router,
            slo_config=obs.SLOConfig(
                ttft_p99_target_s=slo_s, goodput_target=0.9,
                shed_budget=0.1, failover_budget=0.1,
                fast_window_s=1.0, slow_window_s=4.0, min_terminals=4,
            ),
            incident_dir=_tempfile.mkdtemp(prefix="paddle_tpu_bench_incidents_"),
            incident_cooldown_s=5.0,
        )
        # per-replica capacity from ONE replica (they are identical), then
        # warm the other engines so the storm window adds no compiles
        rate = measure_sustainable_rate(
            cluster.replicas["r0"].frontend, calib, seed=7, prompt_len=plen,
            max_new_tokens=max_new, vocab_size=cfg.vocab_size,
        )
        warm_rng = np.random.default_rng(9)
        for name in list(cluster.names())[1:]:
            fe = cluster.replicas[name].frontend
            h = fe.submit(
                warm_rng.integers(0, cfg.vocab_size, (plen[0],)).astype(np.int32),
                max_new_tokens=max_new[0],
            )
            while not h.finished:
                fe.pump()
        obs.GLOBAL_METRICS.reset()  # calibration must not pollute the storm

        mix = [
            TrafficClass("chat", Priority.INTERACTIVE, 2.0, plen, max_new, slo_s),
            TrafficClass("app", Priority.STANDARD, 2.0, plen, max_new, slo_s),
            TrafficClass("batch", Priority.BEST_EFFORT, 1.0, plen, max_new, slo_s),
        ]
        offered = 2.0 * n_replicas * rate
        arrivals = poisson_arrivals(
            offered, n_arrivals, mix, seed=8, vocab_size=cfg.vocab_size
        )
        kill_at_s = arrivals[int(kill_frac * len(arrivals))].t
        state = {"killed": False, "counters_at_kill": None}

        def mid_storm(router_, now):
            if not state["killed"] and now >= kill_at_s:
                state["killed"] = True
                state["counters_at_kill"] = router_.routing_counters()
                # the kill goes through the fault SITE: the next replica
                # probe trips it, so the full death-as-routing-event path
                # (salvage, re-dispatch, failover accounting) is exercised.
                # A trigger fires at most once — no uninstall race.
                faults.install_plan(faults.FaultPlan.single("replica.kill", 0))

        report = run_cluster_open_loop(
            router, arrivals, max_wall_s=120.0, on_iteration=mid_storm
        )
        counters_end = router.routing_counters()
        before = state["counters_at_kill"] or {}
        after_delta = {k: counters_end[k] - before.get(k, 0) for k in counters_end}

        def hit_rate(c):
            tot = sum(c.values())
            return round(c.get("affinity", 0) / tot, 4) if tot else 0.0

        reg = obs.GLOBAL_METRICS
        # sum across the replica-scoped cells AND the router's unscoped
        # ones: one reason may now have one cell per replica
        shed_by_reason: dict = {}
        for v in reg.family("serving_shed_total")._snapshot_values():
            reason = v["labels"]["reason"]
            shed_by_reason[reason] = shed_by_reason.get(reason, 0) + int(v["value"])
        dead = [n for n, r in cluster.replicas.items() if r.state == "dead"]
        slo_time = observer.monitor.time_in_states()
        compiled_total = report["compiled_signatures_total"]
        return {
            "metric": "cluster_goodput_tokens_per_sec",
            "value": report["goodput_tokens_per_sec"],
            "unit": "tokens/s",
            "replicas": n_replicas,
            "offered_rate_rps": round(offered, 2),
            "sustainable_rate_per_replica_rps": round(rate, 2),
            "arrivals": n_arrivals,
            "slo_s": slo_s,
            "killed_replica": dead[0] if dead else None,
            "kill_at_s": round(kill_at_s, 3),
            "slo_attainment": {
                k: v["slo_attainment"] for k, v in report["per_class"].items()
            },
            "affinity_hit_rate": {
                "before_kill": hit_rate(before),
                "after_kill": hit_rate(after_delta),
                "overall": report["affinity_hit_rate"],
            },
            "failover_latency_p99_ms": report["failover_latency_p99_ms"],
            "failovers": report["failovers"],
            "salvaged": report["salvaged"],
            "redispatch_sheds": report["router_sheds"],
            "shed_total_by_reason": shed_by_reason,
            "replica_states": report["replica_states"],
            # the SLO monitor's view of the storm: burn-rate state timeline
            # and how long the kill held the fleet in WARN/PAGE
            "slo_monitor": {
                "final_state": observer.monitor.state_name,
                "time_in_warn_s": slo_time.get("warn", 0.0),
                "time_in_page_s": slo_time.get("page", 0.0),
                "transitions": [
                    {k: e[k] for k in ("from", "to", "signal", "burn")}
                    for e in observer.monitor.timeline
                ],
            },
            "incidents_written": len(observer.incidents),
            # honesty checks: one program per engine; a replica death is
            # absorbed by routing, never by a surviving engine recompiling —
            # and the whole fleet observability layer (scoped metrics,
            # burn-rate sampling, incident snapshots) adds ZERO signatures
            "compiled_signatures": compiled_total,
            "compiles_during_storm": sum(report["compiles_during_run"].values()),
            "one_compile_per_engine": bool(
                compiled_total == n_replicas
                and sum(report["compiles_during_run"].values()) == 0
            ),
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "cluster_goodput_tokens_per_sec", "error": f"{exc!r}"[:300]}
    finally:
        faults.install_plan(None)
        paddle.set_flags(prior)


def _bench_traced_request_breakdown(paddle, platform: str) -> dict:
    """Per-request latency attribution (guarded): run a small traced serving
    workload (FLAGS_trace_sample_rate=1, seeded) and report ONE sampled
    request's queue/prefill/decode/stream phase breakdown from its span
    tree, plus the batched-decode share attribution. The 2-compile honesty
    check confirms the tracing instrumentation added no compiled
    signatures: spans are emitted at call sites from host timestamps, never
    from inside the jitted bodies (analyzer check OB601)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingFrontend

    prior = paddle.get_flags(["FLAGS_trace_sample_rate", "FLAGS_trace_seed"])
    try:
        if platform == "tpu":
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=1024,
            )
            slots, bs, bucket, n_req, plen, max_new = 8, 16, 128, 16, 64, 48
        else:  # tiny CPU smoke: the same machinery with a small budget
            cfg = LlamaConfig.tiny()
            slots, bs, bucket, n_req, plen, max_new = 2, 4, 16, 4, 6, 6

        paddle.set_flags({"FLAGS_trace_sample_rate": 1.0, "FLAGS_trace_seed": 0})
        obs.GLOBAL_TRACER.clear()
        obs.GLOBAL_WATCHDOG.reset()  # compile ledger counts THIS engine only
        paddle.seed(0)
        rng = np.random.default_rng(0)
        model = LlamaForCausalLM(cfg)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        model.eval()
        engine = ContinuousBatchingEngine(
            model, max_slots=slots, block_size=bs, prompt_bucket=bucket
        )
        frontend = ServingFrontend(engine, ServingConfig(max_queue=2 * n_req))
        handles = [
            frontend.submit(
                rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
                max_new_tokens=max_new,
            )
            for _ in range(n_req)
        ]
        for _ in range(100_000):
            frontend.pump()
            if all(h.finished for h in handles):
                break
        assert all(h.outcome == "ok" for h in handles), [
            h.outcome for h in handles
        ]
        # pick a mid-pack request: it queued behind others AND shared its
        # decode steps, so every phase is non-trivial
        target = handles[min(len(handles) - 1, slots)]
        spans = {
            s["name"]: s for s in obs.GLOBAL_TRACER.spans(target.trace_ctx.trace_id)
        }
        root = spans["request"]
        phases_ms = {
            name.split(".", 1)[1]: round(spans[name]["dur_us"] / 1e3, 3)
            for name in ("request.queue_wait", "request.prefill",
                         "request.decode", "request.stream_out")
        }
        compiles = obs.GLOBAL_WATCHDOG.counts()
        return {
            "metric": "traced_request_breakdown",
            "value": round(root["dur_us"] / 1e3, 3),
            "unit": "ms (one sampled request, end to end)",
            "phases_ms": phases_ms,
            "phase_sum_ms": round(sum(phases_ms.values()), 3),
            "decode_steps": spans["request.decode"]["attrs"]["decode_steps"],
            "decode_batched_share_s": spans["request.decode"]["attrs"][
                "batched_share_s"
            ],
            "requests": n_req,
            # honesty check: tracing must add ZERO compiled signatures —
            # still exactly one unified prefill/decode program
            "compiled_signatures": {
                "step": compiles.get("ContinuousBatchingEngine.step", 0),
            },
        }
    except Exception as exc:  # noqa: BLE001 - secondary must never kill primary
        return {"metric": "traced_request_breakdown", "error": f"{exc!r}"[:300]}
    finally:
        paddle.set_flags(prior)
        from paddle_tpu import observability as obs

        obs.GLOBAL_TRACER.clear()


def _bench_resnet_pipeline(paddle, platform: str) -> dict:
    """Quaternary metric (BASELINE.md config #1): ResNet classification
    throughput through the REAL input pipeline — on-disk dataset, multiprocess
    DataLoader workers (shared-memory/native-ring handoff), train step under
    jit. Synthetic images (this environment has no ImageNet), but every byte
    crosses disk -> worker process -> parent -> device."""
    import shutil
    import tempfile

    from paddle_tpu.io import DataLoader
    from paddle_tpu.vision.datasets import DatasetFolder
    from paddle_tpu.vision.models.resnet import resnet18, resnet50

    tmp = tempfile.mkdtemp(prefix="bench_resnet_")
    try:
        if platform == "tpu":
            build, batch, hw, n_imgs, classes, steps, workers = resnet50, 64, 224, 512, 8, 6, 4
        else:
            build, batch, hw, n_imgs, classes, steps, workers = resnet18, 8, 32, 32, 4, 2, 2

        rng = np.random.default_rng(3)
        per = n_imgs // classes
        for c in range(classes):
            d = f"{tmp}/class_{c}"
            os.makedirs(d, exist_ok=True)
            for i in range(per):
                np.save(
                    f"{d}/{i}.npy",
                    rng.integers(0, 255, (3, hw, hw)).astype(np.uint8),
                )

        def to_float(img):
            return img.astype(np.float32) / 255.0

        ds = DatasetFolder(tmp, transform=to_float)
        loader = DataLoader(
            ds, batch_size=batch, num_workers=workers, shuffle=True,
            drop_last=True, persistent_workers=True,
        )
        paddle.seed(0)
        model = build(num_classes=classes)
        if platform == "tpu":
            model = model.to(dtype="bfloat16")
        opt = paddle.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9, parameters=model.parameters()
        )

        @paddle.jit.to_static
        def step(model, opt, x, y):
            logits = model(x)
            # F.cross_entropy upcasts to fp32 internally (stable logsumexp)
            loss = paddle.nn.functional.cross_entropy(logits, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        dt_dtype = "bfloat16" if platform == "tpu" else "float32"
        # warmup: one FULL epoch (compile + settle workers). Epochs always
        # drain completely — a mid-epoch break would tear down the persistent
        # pool and let leftover results poison the timed epoch.
        last = None
        for xb, yb in loader:
            last = step(model, opt, xb.astype(dt_dtype), yb)
        float(last)
        t0 = time.perf_counter()
        n_done = 0
        while n_done < steps:  # whole timed epochs until enough steps
            for xb, yb in loader:
                last = step(model, opt, xb.astype(dt_dtype), yb)
                n_done += 1
        lv = float(last)
        dt = time.perf_counter() - t0
        assert np.isfinite(lv), f"non-finite resnet loss {lv}"
        pool = getattr(loader, "_pool", None)
        if pool is not None:
            pool.shutdown()
        return {
            "metric": "resnet_train_images_per_sec_with_input_pipeline",
            "value": round(batch * n_done / dt, 1),
            "unit": "images/s",
            "batch": batch,
            "image": hw,
            "workers": workers,
        }
    except Exception as exc:  # noqa: BLE001
        return {"metric": "resnet_train_images_per_sec_with_input_pipeline", "error": f"{exc!r}"[:300]}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # noqa: BLE001
        import traceback

        traceback.print_exc(file=sys.stderr)
        _fail_json(f"{type(exc).__name__}: {exc}")
        sys.exit(1)
