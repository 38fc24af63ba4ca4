"""Quantization quality gate: measured accuracy delta of the quantized
serving configuration against the bf16 baseline on a seeded workload.

The quality claim a quantized deployment makes ("int8 KV + weight-only int8
serves the same tokens") is an EMPIRICAL one, so it is measured, not
asserted from algebra: the same seeded request stream runs through a bf16
engine and a quantized engine, and the delta is

- **greedy token-match rate** — the fraction of generated tokens identical
  to the bf16 engine's, end to end through the paged KV plane (append
  quant, block-walk dequant, CoW, spill/prefetch all included); and
- **max logit error** — the worst absolute logit difference of a direct
  full-forward on the same seeded prompts, isolating the weight-only int8
  projections from the KV path.

The tier-1 tolerance tests (``tests/test_quantized_kv.py``) call this
module; on the chip the benchmark's int8 controls (``benchmarks/control.py``)
hold the same paths to the float32 reference instead.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

__all__ = [
    "greedy_token_match",
    "max_logit_error",
    "quality_delta",
    "step_logit_error",
]


def _run_engine(
    build_model: Callable[[], Any],
    prompts: List[np.ndarray],
    max_new_tokens: int,
    engine_kwargs: Dict[str, Any],
) -> Dict[int, List[int]]:
    from paddle_tpu.inference import ContinuousBatchingEngine

    model = build_model()
    engine = ContinuousBatchingEngine(model, **engine_kwargs)
    for p in prompts:
        engine.add_request(np.asarray(p, np.int32), max_new_tokens=max_new_tokens)
    out = engine.run()
    return {rid: list(r.generated) for rid, r in out.items()}


def greedy_token_match(
    build_model: Callable[[], Any],
    prompts: List[np.ndarray],
    max_new_tokens: int,
    baseline_kwargs: Dict[str, Any],
    quant_kwargs: Dict[str, Any],
) -> Dict[str, Any]:
    """Run the SAME seeded workload through a baseline and a quantized
    engine (``build_model`` must re-seed, so both see identical weights) and
    return the positionwise greedy token-match rate. Sequences are compared
    id-by-id over the overlap; a quantized run that stops earlier/later
    counts every unpaired position as a mismatch — divergent early stops are
    a quality loss, not a bookkeeping artifact."""
    base = _run_engine(build_model, prompts, max_new_tokens, baseline_kwargs)
    quant = _run_engine(build_model, prompts, max_new_tokens, quant_kwargs)
    matched = total = 0
    for rid, ref in base.items():
        got = quant.get(rid, [])
        total += max(len(ref), len(got))
        matched += sum(a == b for a, b in zip(ref, got))
    return {
        "tokens_compared": total,
        "tokens_matched": matched,
        "token_match_rate": (matched / total) if total else 1.0,
    }


def max_logit_error(
    build_model: Callable[[], Any],
    prompts: List[np.ndarray],
    quantize: Optional[Callable[[Any], Any]] = None,
) -> float:
    """Worst absolute fp32 logit difference between a pristine model and a
    weight-quantized copy over a direct (cache-free) forward on the seeded
    prompts — the projection-error bound the KV path inherits. ``quantize``
    defaults to :func:`paddle_tpu.kernels.quant.quantize_module_weights`."""
    import paddle_tpu as paddle

    if quantize is None:
        from paddle_tpu.kernels.quant import quantize_module_weights as quantize

    ref_model = build_model()
    q_model = build_model()
    quantize(q_model)
    worst = 0.0
    for p in prompts:
        ids = paddle.to_tensor(np.asarray(p, np.int32)[None])
        ref = np.asarray(ref_model(ids).numpy(), np.float32)
        got = np.asarray(q_model(ids).numpy(), np.float32)
        worst = max(worst, float(np.max(np.abs(ref - got))))
    return worst


def step_logit_error(
    engine: Any, prompt: Any, reference: Optional[np.ndarray] = None
) -> Dict[str, float]:
    """Worst absolute fp32 difference between ``engine.step_logits(prompt)``
    — the engine's own step body over the paged KV plane, first chunk of
    ``prompt`` — and ``reference`` logits of the same rows (another engine's
    ``step_logits``); by default the dense, cache-free forward of
    ``engine.model`` on the same tokens. The reference's absmax comes back
    too: a tolerance is stated relative to it."""
    import paddle_tpu as paddle

    got = engine.step_logits(prompt)
    if reference is None:
        ids = np.asarray(prompt, np.int32)[: got.shape[0]]
        reference = engine.model(paddle.to_tensor(ids[None])).numpy()[0]
    ref = np.asarray(reference, np.float32)
    return {
        "max_logit_error": float(np.max(np.abs(got - ref))),
        "max_abs_reference_logit": float(np.max(np.abs(ref))),
    }


def quality_delta(
    build_model: Callable[[], Any],
    prompts: List[np.ndarray],
    max_new_tokens: int,
    engine_kwargs: Dict[str, Any],
    kv_cache_dtype: str = "int8",
    weight_only_int8: bool = True,
) -> Dict[str, Any]:
    """The full measured delta the tier-1 gate carries:
    token-match rate through the engines, max logit error through a direct
    forward, and the effective KV bytes/token of both configurations (the
    reduction factor the tentpole promises)."""
    base_kwargs = dict(engine_kwargs)
    qkw = dict(
        engine_kwargs,
        kv_cache_dtype=kv_cache_dtype,
        weight_only_int8=weight_only_int8,
    )
    match = greedy_token_match(
        build_model, prompts, max_new_tokens, base_kwargs, qkw
    )
    out: Dict[str, Any] = dict(match)
    if weight_only_int8:
        out["max_logit_error"] = max_logit_error(build_model, prompts)
    # bytes/token from throwaway engines' accounting (no steps dispatched)
    from paddle_tpu.inference import ContinuousBatchingEngine

    bpt_base = ContinuousBatchingEngine(
        build_model(), **base_kwargs
    ).pool_stats()["bytes_per_token"]
    bpt_quant = ContinuousBatchingEngine(
        build_model(), **qkw
    ).pool_stats()["bytes_per_token"]
    out["kv_bytes_per_token_bf16"] = bpt_base
    out["kv_bytes_per_token_quant"] = bpt_quant
    out["kv_bytes_reduction"] = (
        bpt_base / bpt_quant if bpt_quant else float("inf")
    )
    return out
