"""Operations and bytes that the algorithm REQUIRES, from shapes alone. The
yardstick for ``*_mfu_pct`` and ``*_roofline_pct``: recomputation, padding and
masked-out work are not counted, so a kernel that does extra work reads lower,
never higher.

What is the same for every block is here: the cost of one attention call from
the head counts and ``head_dim``, and the roofline. What the block's shape
decides (the weights a token multiplies through, how many attention calls it
makes, ``head_dim`` itself) the configuration's reference file counts
(``lib/arch.py``)."""

from __future__ import annotations

from typing import Any, Dict

from . import arch


def head_dim(cfg: Dict[str, Any]) -> int:
    return arch.reference(cfg).head_dim(cfg)


def attention_passes(cfg: Dict[str, Any], depth: int) -> int:
    """Causal-attention calls, and so KV sets, that a token makes at ``depth``."""
    return arch.reference(cfg).attention_passes(cfg, depth)


def attention_flops_fwd(cfg: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of ONE causal-attention call on ONE sequence: QK^T and PV
    over the lower triangle (half of seq^2 pairs, diagonal included)."""
    pairs = seq * (seq + 1) / 2
    return 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * pairs


def train_flops_per_token(cfg: Dict[str, Any], depth: int, seq: int) -> float:
    """Forward + backward: 6 FLOPs per matmul weight per token (counted once
    per pass through it), plus every causal-attention call (backward is twice
    the forward), per token of a ``seq`` sequence."""
    matmul_params = arch.reference(cfg).matmul_params(cfg, depth)
    return 6.0 * matmul_params + 3.0 * attention_passes(cfg, depth) * attention_flops_fwd(cfg, seq) / seq


def flash_attention_cost(cfg: Dict[str, Any], batch: int, seq: int, backward: bool) -> Dict[str, float]:
    """One call over ``[batch, seq]``. Bytes: q, k, v read and o written once
    (backward: q, k, v, o, do read; dq, dk, dv written), bf16."""
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    fwd = batch * attention_flops_fwd(cfg, seq)
    q_bytes = batch * seq * nh * hd * 2
    kv_bytes = batch * seq * nkv * hd * 2
    if backward:
        # dQ, dK, dV and dP: four matmuls where the forward has two; the
        # recomputation of the scores is not counted
        return {"flops": 2.0 * fwd, "bytes": 4 * q_bytes + 4 * kv_bytes}
    return {"flops": fwd, "bytes": 2 * q_bytes + 2 * kv_bytes}


def paged_attention_bytes(cfg: Dict[str, Any], live_tokens: int, kv_bytes_per_value: int = 2) -> float:
    """KV bytes ONE attention call has to read for one step: every live
    token's key and value once."""
    return 2.0 * live_tokens * cfg["num_key_value_heads"] * head_dim(cfg) * kv_bytes_per_value


def roofline_seconds(flops: float, bytes_: float, peaks: Dict[str, float]) -> Dict[str, Any]:
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "bound": "compute" if t_f >= t_b else "memory"}
