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

Both bench records (``bench.py``) and the tier-1 tolerance tests
(``tests/test_quantized_kv.py``) call this module, so the number the CI
gate enforces is the number the bench reports.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

__all__ = [
    "engine_first_step_logits",
    "greedy_token_match",
    "max_logit_error",
    "quality_delta",
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


def engine_first_step_logits(engine: Any, prompt: Any) -> np.ndarray:
    """fp32 logits ``[len(prompt), V]`` of ``engine``'s step math on its first
    prompt chunk: the same paged 6-tuple past, ``[max_slots, prefill_chunk]``
    token block and (under tp) armed shard group ``_step_impl`` traces, on an
    empty scratch pool of one sequence's blocks — the step itself only hands
    back argmaxes. Compared against the dense ``model(ids)`` forward this
    isolates the paged kernels from everything host-side. bf16 KV only;
    ``prompt`` is cut to one chunk."""
    import contextlib

    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer.layers import bind_param_arrays

    if engine.kv_cache_dtype != "bf16":
        raise ValueError("engine_first_step_logits compares the bf16 KV plane only")
    slots, chunk = engine.max_slots, engine.prefill_chunk
    ids = np.asarray(prompt, np.int32)[:chunk]
    n = len(ids)
    mbs = engine.max_blocks_per_seq
    toks = np.zeros((slots, chunk), np.int32)
    toks[0, :n] = ids
    tables = np.zeros((slots, mbs), np.int32)
    tables[0] = np.arange(mbs)
    q_lens = np.zeros((slots,), np.int32)
    q_lens[0] = n
    active = np.zeros((slots,), bool)
    active[0] = True
    shape = (mbs,) + tuple(engine._cache_shape[1:])
    named, model = engine._named, engine.model

    def step(arrays, toks, tables, lens, q_lens, active):
        with bind_param_arrays(named, arrays), paddle_tpu.no_grad():
            pkv = [
                (
                    Tensor(jnp.zeros(shape, engine._cache_dtype)),
                    Tensor(jnp.zeros(shape, engine._cache_dtype)),
                    Tensor(tables), Tensor(lens), Tensor(active), Tensor(q_lens),
                )
                for _ in range(engine._num_layers)
            ]
            logits, _ = model(
                Tensor(toks), past_key_values=pkv, use_cache=True,
                cache_position=Tensor(lens),
            )
        return logits._data[0].astype(jnp.float32)

    tp_ctx = (
        engine._tp_ctx(engine._tp_mesh)
        if engine._tp_mesh is not None
        else contextlib.nullcontext()
    )
    with tp_ctx:
        out = jax.jit(step)(
            [p._data for _, p in named], jnp.asarray(toks), jnp.asarray(tables),
            jnp.zeros((slots,), jnp.int32), jnp.asarray(q_lens), jnp.asarray(active),
        )
    return np.asarray(out)[:n]


def quality_delta(
    build_model: Callable[[], Any],
    prompts: List[np.ndarray],
    max_new_tokens: int,
    engine_kwargs: Dict[str, Any],
    kv_cache_dtype: str = "int8",
    weight_only_int8: bool = True,
) -> Dict[str, Any]:
    """The full measured delta a bench record (or the tier-1 gate) carries:
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
