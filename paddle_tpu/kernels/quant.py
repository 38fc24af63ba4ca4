"""Weight-only int8 projection kernel (inference serving).

Reference capability: the fork's weight-only quantization surface
(``paddle/phi/kernels/fusion/gpu/fused_weight_only_linear_pass``-adjacent
AMP/quantization layer) — lm-head and MLP projection weights stored int8
with per-output-channel fp32 scales, dequantized on the fly inside the
matmul so no bf16 copy of the weight ever materializes in HBM.

TPU-native shape: a Pallas tiled matmul over grid (M/bm, N/bn, K/bk) — int8
weight tiles stream HBM -> VMEM at half the bytes of bf16, upcast in
VMEM, fp32 MXU accumulate (``preferred_element_type``), and the scale row
multiplies once at the K-walk's end. The XLA fallback is the same op
composition (``(x_f32 @ w8_f32) * scale``) — the canonical semantics both
paths implement; CPU CI always takes it (inference-only: no tape, no
GradNode — the engine's decode step never differentiates through it).

Dispatch follows the repo's kernel discipline (PG905):
``warn_fallback``-counted degradation, autotune entry for the block geometry.
The kernel is required to compile on TPU (tests/test_tpu_aot_compile.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = [
    "quantize_weight_int8",
    "quantize_module_weights",
    "int8_weight_matmul",
]

# Model leaf names whose nn.Linear weights the engine quantizes under
# FLAGS_weight_only_int8: the MLP projections and the lm-head — attention
# projections and (tied) embeddings are excluded (an embedding weight also
# feeds the token gather, which must stay full-precision).
WEIGHT_ONLY_LEAVES = ("gate_proj", "up_proj", "down_proj", "fc1", "fc2", "lm_head")
# pallas_call name= of each kernel here: what a device trace calls it (stable, no shapes)
KERNEL_WO_MATMUL = "weight_only_int8_matmul"


def quantize_weight_int8(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel absmax quantization of a ``[K, N]``
    projection weight: returns ``(w8 [K, N] int8, scale [N] fp32)`` with
    ``w ≈ w8 * scale`` column-wise. Per-COLUMN scales are exact under both
    the K-contraction and tensor-parallel K-sharding (the scale factors out
    of the sum), which is why the row dim never gets its own scale."""
    wf = jnp.asarray(w).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)  # [N]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    w8 = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127).astype(jnp.int8)
    return w8, scale


def quantize_module_weights(model) -> list:
    """In-place weight-only int8 quantization of a model's projection
    weights (engine-applied under ``FLAGS_weight_only_int8``).

    Walks the sublayer tree, and for every layer whose attribute leaf name
    is in :data:`WEIGHT_ONLY_LEAVES` replaces ``weight._data`` with the
    int8 array and hangs the per-output-channel scales off the Parameter as
    ``_quant_scale`` — the hook ``nn.Linear.forward`` dispatches on.
    Parameters shared with any non-target layer (tied embeddings) are left
    untouched: the other consumer needs the full-precision array. Idempotent;
    returns the list of Parameters quantized (order = sublayer walk order),
    which the engine threads as extra step operands so the scales stay part
    of the ONE compiled step signature."""
    # ownership map built from the raw per-layer parameter dicts — NOT
    # named_parameters(), which dedups by id and would hide sharing
    owners: dict = {}
    for lname, layer in model.named_sublayers(include_self=True):
        leaf = lname.split(".")[-1] if lname else ""
        for p in getattr(layer, "_parameters", {}).values():
            if p is not None:
                owners.setdefault(id(p), set()).add(leaf)
    quantized = []
    for lname, layer in model.named_sublayers(include_self=True):
        leaf = lname.split(".")[-1] if lname else ""
        if leaf not in WEIGHT_ONLY_LEAVES:
            continue
        w = getattr(layer, "weight", None)
        if w is None or getattr(w, "_quant_scale", None) is not None:
            continue
        data = getattr(w, "_data", None)
        if (
            data is None
            or data.ndim != 2
            or not jnp.issubdtype(data.dtype, jnp.floating)
        ):
            continue
        if any(o not in WEIGHT_ONLY_LEAVES for o in owners.get(id(w), set())):
            continue
        w8, scale = quantize_weight_int8(data)
        w._data = w8
        w._quant_scale = scale
        quantized.append(w)
    return quantized


def _wo_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _finish():
        # one scale multiply per output tile, AFTER the K walk: dequant
        # factors out of the contraction, so this equals dequantizing the
        # whole weight first — without ever materializing it
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(
            o_ref.dtype
        )


def _wo_matmul_pallas(
    x: jax.Array,  # [M, K] activations (bf16/f32)
    w8: jax.Array,  # [K, N] int8
    scale: jax.Array,  # [N] fp32
    block: Tuple[int, int, int],
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    n = w8.shape[1]
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"geometry ({m},{k},{n}) not divisible by {block}")
    n_k = k // bk
    kernel = functools.partial(_wo_matmul_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_WO_MATMUL,
    )(x, w8, scale.reshape(1, n))


def _default_block(m: int, k: int, n: int) -> Tuple[int, int, int]:
    # MXU-friendly 128-multiples, shrunk to the actual geometry; the K tile
    # is the largest that divides K (Llama-2-7B's down projection has
    # K = 11008 = 2**8 * 43, which 512 does not divide)
    bk = next((t for t in (512, 256, 128) if k % t == 0), min(512, k))
    return (min(256, m), min(256, n), bk)


def _autotune_block(m: int, k: int, n: int, dtype: str) -> Tuple[int, int, int]:
    """Autotune entry for the weight-only matmul block geometry — disabled
    by default (FLAGS_use_kernel_autotune), TPU-only, cached per shape."""
    from paddle_tpu.kernels.autotune import autotune

    key = (m, k, n, dtype)
    candidates = [
        (bm, bn, bk)
        for bm in (128, 256, 512)
        for bn in (128, 256, 512)
        for bk in (256, 512)
        if m % bm == 0 and n % bn == 0 and k % bk == 0
    ]

    def build(cfg):
        xz = jnp.zeros((m, k), jnp.dtype(dtype))
        wz = jnp.zeros((k, n), jnp.int8)
        sz = jnp.ones((n,), jnp.float32)

        def run():
            return _wo_matmul_pallas(xz, wz, sz, cfg)

        return run

    return autotune(
        "int8_weight_matmul", key, candidates, build,
        default=_default_block(m, k, n),
    )


def int8_weight_matmul(
    x: jax.Array,  # [..., K] activations
    w8: jax.Array,  # [K, N] int8 quantized weight
    scale: jax.Array,  # [N] fp32 per-output-channel scales
    interpret: bool = False,
    block: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """``(x @ dequant(w8)) = (x @ w8) * scale`` without materializing the
    dequantized weight. Pallas on TPU, XLA composition elsewhere —
    ``warn_fallback``-counted on a trace-time kernel failure per the PG905
    dispatch discipline."""
    from paddle_tpu.kernels.select import pallas_enabled, warn_fallback

    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w8.shape[1]
    m = 1
    for s in lead:
        m *= int(s)
    x2 = x.reshape(m, k)

    if not interpret and pallas_enabled("weight_only_int8", bare="int8_weight_matmul"):
        blk = block or _autotune_block(m, k, n, str(x.dtype))
        try:
            # a geometry the block does not divide raises here, at trace time
            out = _wo_matmul_pallas(x2, w8, scale, blk)
            return out.reshape(*lead, n)
        except Exception as exc:  # noqa: BLE001 - XLA fallback below
            warn_fallback("int8_weight_matmul", exc)
    elif interpret:
        out = _wo_matmul_pallas(
            x2, w8, scale, block or _default_block(m, k, n), interpret=True
        )
        return out.reshape(*lead, n)
    # the canonical composition the kernel implements: fp32 matmul of the
    # int8 weight, one scale row multiply, cast back to the activation dtype
    out = (
        jnp.matmul(
            x2.astype(jnp.float32), w8.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scale[None, :]
    ).astype(x.dtype)
    return out.reshape(*lead, n)
