"""Operations and bytes that the algorithm REQUIRES, from shapes alone. The
yardstick for ``*_mfu_pct`` and ``*_roofline_pct``: recomputation, padding and
masked-out work are not counted, so a kernel that does extra work reads lower,
never higher."""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any], depth: int) -> int:
    """Weights that a token passes through by matrix multiplication: every
    layer's projections and the output head. The embedding is a lookup."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    layer = h * (nh * hd) + 2 * h * (nkv * hd) + (nh * hd) * h + 3 * h * i
    return depth * layer + h * v


def attention_flops_fwd(cfg: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of causal attention for ONE sequence and ONE layer: QK^T
    and PV over the lower triangle (half of seq^2 pairs, diagonal included)."""
    nh = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // nh
    pairs = seq * (seq + 1) / 2
    return 2 * 2 * nh * hd * pairs


def train_flops_per_token(cfg: Dict[str, Any], depth: int, seq: int) -> float:
    """Forward + backward: 6 FLOPs per matmul weight per token, plus causal
    attention (backward is twice the forward), per token of a ``seq`` sequence."""
    return 6.0 * matmul_params(cfg, depth) + 3.0 * depth * attention_flops_fwd(cfg, seq) / seq


def flash_attention_cost(cfg: Dict[str, Any], batch: int, seq: int, backward: bool) -> Dict[str, float]:
    """One call over ``[batch, seq]``, one layer. Bytes: q, k, v read and o
    written once (backward: q, k, v, o, do read; dq, dk, dv written), bf16."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    fwd = batch * attention_flops_fwd(cfg, seq)
    q_bytes = batch * seq * nh * hd * 2
    kv_bytes = batch * seq * nkv * hd * 2
    if backward:
        # dQ, dK, dV and dP: four matmuls where the forward has two; the
        # recomputation of the scores is not counted
        return {"flops": 2.0 * fwd, "bytes": 4 * q_bytes + 4 * kv_bytes}
    return {"flops": fwd, "bytes": 2 * q_bytes + 2 * kv_bytes}


def paged_attention_bytes(cfg: Dict[str, Any], live_tokens: int, kv_bytes_per_value: int = 2) -> float:
    """KV bytes one layer's attention has to read for one step: every live
    token's key and value once."""
    nkv = cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * live_tokens * nkv * hd * kv_bytes_per_value


def roofline_seconds(flops: float, bytes_: float, peaks: Dict[str, float]) -> Dict[str, Any]:
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "bound": "compute" if t_f >= t_b else "memory"}
