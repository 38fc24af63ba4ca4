"""Pallas TPU flash attention (forward + backward) with optional FlashMask
column-sparse masking.

Replaces the reference's CUDA flash-attention kernels
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu:353`` + patched
``third_party/flashattn``) with a TPU kernel: online-softmax tiling over KV
blocks held in VMEM, fp32 accumulation on the MXU, and a custom-VJP backward
pair (dq kernel / dkv kernel) recomputing probabilities from the saved
logsumexp — the standard flash-attention-2 decomposition.

Matmul operands follow the inputs' dtype (``_operand_dtype``): bf16 tensors
feed the MXU as they are and the probabilities and ``ds`` the kernels compute
are rounded to bf16 for their matmuls; float32 inputs keep float32 operands.
Accumulators and the whole softmax (running max, exponent, row sums, lse,
delta) are float32 either way. Block shapes come from the shapes and a VMEM
budget (``_block_geometry``): the tile, not the operands, sets the kernels'
time.

Layouts: public entry takes paddle's ``[B, S, H, D]``; kernels run
``[B, H, S, D]``. Grouped-query attention is handled by BlockSpec index maps
(kv head = q head // group), never materializing repeated KV.

The FlashMask encoding (``startend_row_indices [B, Hm, Sk, C]``, C ∈ {1,2,4})
is applied per KV block from an O(S) bounds tensor — mask memory stays linear
in sequence length, the fork's marquee property.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_MAX_BLOCK = 512  # a side of the score tile; the chip gains nothing past 512 x 512 (PERF.md, PR 28)
_VMEM_BUDGET = 16 << 20  # bytes of VMEM a kernel may take (Mosaic's default scoped limit)
# pallas_call name= of each kernel here: what a device trace calls it (stable, no shapes)
KERNEL_FWD = "flash_attention_fwd"
KERNEL_DQ = "flash_attention_dq"
KERNEL_DKV = "flash_attention_dkv"


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _block_geometry(sq: int, sk: int, d: int, itemsize: int) -> Tuple[int, int]:
    """(blk_q, blk_k) from the shapes alone. A grid cell's inner loop pays a
    fixed latency an iteration (the dependent matmul - softmax - matmul chain,
    the per-row statistics on lane-sparse [blk, 1] vectors), so the score tile
    is as large as VMEM allows, up to 512 x 512. The dKV kernel is the one that
    binds. Double-buffered, it holds q and dO of a whole sequence with lse and
    delta (their unit lane dimension pads to 128 lanes), the k and v blocks and
    the float32 dk and dv blocks; beside them two float32 accumulators and the
    tile's float32 temporaries, three by what the chip's compiler took and
    refused (tests/test_tpu_aot_compile.py holds it to that). Each sequence is
    then cut into the fewest such blocks, evenly, in multiples of 128:
    2048 -> 4 x 512, 600 -> 2 x 384 (not 512 + 512)."""
    d = _cdiv(d, 128) * 128  # a row takes whole 128-lane tiles in VMEM
    whole_seq = 2 * max(2 * sq * d * itemsize + 2 * sq * 128 * 4, 2 * sk * d * itemsize)  # or K, V: forward, dQ
    per_key = d * (2 * 2 * itemsize + 2 * 2 * 4 + 2 * 4)
    blk_q = blk_k = _MAX_BLOCK
    while whole_seq + blk_k * per_key + 3 * blk_q * blk_k * 4 > _VMEM_BUDGET and blk_k > 128:
        # q rows first: a wide blk_k is what spreads the row statistics' cost
        blk_q, blk_k = (blk_q // 2, blk_k) if blk_q > 128 else (blk_q, blk_k // 2)

    def even(s, cap):
        return _cdiv(_cdiv(s, _cdiv(s, cap)), 128) * 128

    return even(sq, blk_q), even(sk, blk_k)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def _operand_dtype(dtype) -> Any:
    """The dtype the kernels hand the MXU: bf16 as it arrives (a bf16 x bf16
    product accumulated in float32 is exactly what the upcast operands give);
    anything else float32. On the chip a float32 ``dot_general`` at the default
    precision rounds its operands to bf16 for one MXU pass anyway (PERF.md,
    PR 28); stating the operands makes that hold whatever the precision says,
    and keeps a scaled q from being rounded on its way in."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _mask_block(
    rows: jax.Array,  # [blk_q, 1] global query positions
    cols: jax.Array,  # [1, blk_k] global key positions
    sq: int,
    sk: int,
    causal: bool,
    bounds: Optional[jax.Array],  # [blk_k, C] startend_row_indices slice
) -> jax.Array:
    """True where the logit must be masked out."""
    masked = cols >= sk  # padding columns
    if causal:
        masked = masked | (cols > rows + (sk - sq))
    if bounds is not None:
        c = bounds.shape[-1]
        if c == 1:
            masked = masked | (rows >= bounds[:, 0][None, :])
        elif c == 2:
            start = bounds[:, 0][None, :]
            end = bounds[:, 1][None, :]
            masked = masked | ((rows >= start) & (rows < end))
        elif c == 4:
            lts = bounds[:, 0][None, :]
            lte = bounds[:, 1][None, :]
            uts = bounds[:, 2][None, :]
            ute = bounds[:, 3][None, :]
            masked = masked | ((rows >= lts) & (rows < lte)) | ((rows >= uts) & (rows < ute))
        else:
            raise ValueError(f"FlashMask C must be 1/2/4, got {c}")
    return masked


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, idx_ref, o_ref, lse_ref, *, sq, sk, scale, causal, blk_q, blk_k, num_kv_blocks
):
    qi = pl.program_id(2)
    mm = _operand_dtype(q_ref.dtype)
    q = q_ref[0, 0].astype(mm)  # [blk_q, D]
    # a float32 operand carries the scale into the dot; bf16 cannot hold
    # q * scale, so there the float32 logits are scaled
    prescaled = mm == jnp.float32
    if prescaled:
        q = q * scale
    d = q.shape[-1]
    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    if causal:
        # only kv blocks touching or below the diagonal contribute
        hi = jnp.minimum(((qi + 1) * blk_q + (sk - sq) + blk_k - 1) // blk_k, num_kv_blocks)
        hi = jnp.maximum(hi, 0)
    else:
        hi = num_kv_blocks

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :].astype(mm)
        v = v_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :].astype(mm)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, blk_k]
        if not prescaled:
            logits = logits * scale
        cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        bounds = None
        if idx_ref is not None:
            bounds = idx_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :]
        masked = _mask_block(rows, cols, sq, sk, causal, bounds)
        logits = jnp.where(masked, NEG_INF, logits)
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(mm), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, m_new, l

    acc0 = jnp.zeros((blk_q, d), jnp.float32)
    m0 = jnp.full((blk_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)  # fully-masked rows: avoid 0/0
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # lse is carried as [B, H, Sq, 1]: a trailing unit lane dim keeps the
    # block (1, 1, blk_q, 1) Mosaic-legal (sublane blk_q % 8 == 0, lane == 1
    # equals the array dim) — a bare [B, H, Sq] layout would need an
    # (·, ·, blk_q) block whose head dim of 1 violates the (8, 128) rule
    lse_ref[0, 0] = m + jnp.log(l)


def _run_fwd(q, k, v, idx, *, sq, sk, scale, causal, blk_q, blk_k, interpret):
    b, h, sq_pad, d = q.shape
    hk = k.shape[1]
    sk_pad = k.shape[2]
    group = h // hk
    num_kv_blocks = sk_pad // blk_k
    grid = (b, h, sq_pad // blk_q)

    in_specs = [
        pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, sk_pad, d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
        pl.BlockSpec((1, 1, sk_pad, d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
    ]
    args = [q, k, v]
    if idx is not None:
        hm = idx.shape[1]
        c = idx.shape[-1]
        in_specs.append(
            pl.BlockSpec(
                (1, 1, sk_pad, c),
                lambda bi, hi, qi: (bi, 0 if hm == 1 else hi, 0, 0),
            )
        )
        args.append(idx)
        kernel = functools.partial(
            _fwd_kernel, sq=sq, sk=sk, scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k, num_kv_blocks=num_kv_blocks,
        )
    else:
        kernel = functools.partial(
            lambda q_ref, k_ref, v_ref, o_ref, lse_ref, **kw: _fwd_kernel(
                q_ref, k_ref, v_ref, None, o_ref, lse_ref, **kw
            ),
            sq=sq, sk=sk, scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k, num_kv_blocks=num_kv_blocks,
        )

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        # every (batch, head, q-block) cell is independent — Mosaic may split
        # them across TensorCores (megacore on v4/v5p)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_FWD,
    )(*args)
    return out, lse


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, idx_ref, g_ref, lse_ref, delta_ref, dq_ref,
    *, sq, sk, scale, causal, blk_q, blk_k, num_kv_blocks
):
    qi = pl.program_id(2)
    mm = _operand_dtype(q_ref.dtype)
    q = q_ref[0, 0].astype(mm)  # [blk_q, D]
    g = g_ref[0, 0].astype(mm)
    lse = lse_ref[0, 0]  # [blk_q, 1]
    delta = delta_ref[0, 0]
    d = q.shape[-1]
    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    if causal:
        hi = jnp.minimum(((qi + 1) * blk_q + (sk - sq) + blk_k - 1) // blk_k, num_kv_blocks)
        hi = jnp.maximum(hi, 0)
    else:
        hi = num_kv_blocks

    def body(ki, dq):
        k = k_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :].astype(mm)
        v = v_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :].astype(mm)
        logits = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        bounds = None
        if idx_ref is not None:
            bounds = idx_ref[0, 0, pl.dslice(ki * blk_k, blk_k), :]
        masked = _mask_block(rows, cols, sq, sk, causal, bounds)
        p = jnp.where(masked, 0.0, jnp.exp(logits - lse))  # [blk_q, blk_k]
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq = dq + jax.lax.dot_general(
            ds.astype(mm), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dq

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((blk_q, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, idx_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, sq, sk, scale, causal, blk_q, blk_k, num_q_blocks, group
):
    ki = pl.program_id(2)
    mm = _operand_dtype(q_ref.dtype)
    k = k_ref[0, 0].astype(mm)  # [blk_k, D]
    v = v_ref[0, 0].astype(mm)
    d = k.shape[-1]
    cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
    bounds = idx_ref[0, 0] if idx_ref is not None else None  # [blk_k, C]

    if causal:
        lo = jnp.maximum((ki * blk_k - (sk - sq)) // blk_q, 0)
    else:
        lo = 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(qi * blk_q, blk_q), :].astype(mm)
        g = g_ref[0, 0, pl.dslice(qi * blk_q, blk_q), :].astype(mm)
        lse = lse_ref[0, 0, pl.dslice(qi * blk_q, blk_q), :]  # [blk_q, 1]
        delta = delta_ref[0, 0, pl.dslice(qi * blk_q, blk_q), :]
        rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)
        logits = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, blk_k]
        masked = _mask_block(rows, cols, sq, sk, causal, bounds)
        # padding rows (rows >= sq) contribute nothing: lse there is 0 and
        # exp(0-0)=1, so mask them explicitly
        masked = masked | (rows >= sq)
        p = jnp.where(masked, 0.0, jnp.exp(logits - lse))
        dv = dv + jax.lax.dot_general(
            p.astype(mm), g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_k, D]
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, blk_k]
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(mm), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    dk0 = jnp.zeros((blk_k, d), jnp.float32)
    dv0 = jnp.zeros((blk_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, num_q_blocks, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _run_bwd(q, k, v, idx, g, out, lse, *, sq, sk, scale, causal, blk_q, blk_k, interpret):
    b, h, sq_pad, d = q.shape
    hk = k.shape[1]
    sk_pad = k.shape[2]
    group = h // hk
    # [B, H, Sq, 1] — same trailing-unit-lane layout as lse (Mosaic tiling)
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    common = dict(sq=sq, sk=sk, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)

    # dq: grid over q blocks
    dq_specs = [
        pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),       # q
        pl.BlockSpec((1, 1, sk_pad, d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),  # k
        pl.BlockSpec((1, 1, sk_pad, d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),  # v
    ]
    dq_args = [q, k, v]
    if idx is not None:
        hm = idx.shape[1]
        c = idx.shape[-1]
        dq_specs.append(
            pl.BlockSpec((1, 1, sk_pad, c), lambda bi, hi, qi: (bi, 0 if hm == 1 else hi, 0, 0))
        )
        dq_args.append(idx)
        dq_kernel = functools.partial(_bwd_dq_kernel, **common, num_kv_blocks=sk_pad // blk_k)
    else:
        dq_kernel = functools.partial(
            lambda q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, **kw: _bwd_dq_kernel(
                q_ref, k_ref, v_ref, None, g_ref, lse_ref, delta_ref, dq_ref, **kw
            ),
            **common,
            num_kv_blocks=sk_pad // blk_k,
        )
    dq_specs += [
        pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),      # g
        pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),      # lse
        pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),      # delta
    ]
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, sq_pad // blk_q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
        interpret=interpret,
        name=KERNEL_DQ,
    )(*dq_args, g, lse, delta)

    # dk/dv: grid over kv blocks, one q-head at a time (GQA: accumulate
    # outside over the group's q heads to avoid in-kernel atomics)
    dkv_specs = [
        pl.BlockSpec((1, 1, sq_pad, d), lambda bi, hi, ki: (bi, hi, 0, 0)),   # q
        pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi // group, ki, 0)),  # k
        pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi // group, ki, 0)),  # v
    ]
    dkv_args = [q, k, v]
    if idx is not None:
        hm = idx.shape[1]
        c = idx.shape[-1]
        dkv_specs.append(
            pl.BlockSpec((1, 1, blk_k, c), lambda bi, hi, ki: (bi, 0 if hm == 1 else hi, ki, 0))
        )
        dkv_args.append(idx)
        dkv_kernel = functools.partial(
            _bwd_dkv_kernel, **common, num_q_blocks=sq_pad // blk_q, group=group
        )
    else:
        dkv_kernel = functools.partial(
            lambda q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref, **kw: _bwd_dkv_kernel(
                q_ref, k_ref, v_ref, None, g_ref, lse_ref, delta_ref, dk_ref, dv_ref, **kw
            ),
            **common,
            num_q_blocks=sq_pad // blk_q,
            group=group,
        )
    dkv_specs += [
        pl.BlockSpec((1, 1, sq_pad, d), lambda bi, hi, ki: (bi, hi, 0, 0)),      # g
        pl.BlockSpec((1, 1, sq_pad, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),      # lse
        pl.BlockSpec((1, 1, sq_pad, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),      # delta
    ]
    # per-q-head partial dk/dv, summed over the group afterwards
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, sk_pad // blk_k),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk_pad, d), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_DKV,
    )(*dkv_args, g, lse, delta)
    if group > 1:
        dk = dk_h.reshape(b, hk, group, sk_pad, d).sum(axis=2)
        dv = dv_h.reshape(b, hk, group, sk_pad, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# public entry (custom VJP, paddle [B, S, H, D] layout)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_flash_core(sq, sk, scale, causal, blk_q, blk_k, interpret):
    """Build the custom-VJP core for one static configuration. All static
    parameters live in this closure; the returned function takes only array
    arguments (q, k, v [B,H,S,D] and the optional FlashMask bounds)."""

    def fwd_res(q, k, v, idx):
        qp = _pad_to(q, 2, blk_q)
        kp = _pad_to(k, 2, blk_k)
        vp = _pad_to(v, 2, blk_k)
        idxp = _pad_to(idx, 2, blk_k) if idx is not None else None
        out, lse = _run_fwd(
            qp, kp, vp, idxp, sq=sq, sk=sk, scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        )
        return out, lse, (qp, kp, vp, idxp)

    @jax.custom_vjp
    def core(q, k, v, idx):
        out, _, _ = fwd_res(q, k, v, idx)
        return out[:, :, :sq]

    def core_fwd(q, k, v, idx):
        out, lse, (qp, kp, vp, idxp) = fwd_res(q, k, v, idx)
        return out[:, :, :sq], (qp, kp, vp, idxp, out, lse)

    def core_bwd(res, g):
        import numpy as np

        qp, kp, vp, idxp, outp, lse = res
        gp = _pad_to(g, 2, blk_q)
        dq, dk, dv = _run_bwd(
            qp, kp, vp, idxp, gp, outp, lse,
            sq=sq, sk=sk, scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        )
        didx = None
        if idxp is not None:
            # integer mask bounds carry no gradient (float0 cotangent)
            didx = np.zeros(idxp.shape[:2] + (sk,) + idxp.shape[3:], jax.dtypes.float0)
        return dq[:, :, :sq], dk[:, :, :sk], dv[:, :, :sk], didx

    core.defvjp(core_fwd, core_bwd)
    return core


def _autotune_blocks(q_shape, kv_heads, dtype, sq, sk, d, scale, causal, mask_c, interpret):
    """Benchmark-pick (blk_q, blk_k) for this attention shape (reference
    ``auto_tune_base.h:48``); returns ``_block_geometry``'s when tuning is off."""
    from paddle_tpu.kernels.autotune import autotune

    b, h = q_shape[0], q_shape[2]
    key = (b, h, kv_heads, sq, sk, d, str(dtype), causal, mask_c)
    candidates = [
        (bq, bk)
        for bq in (128, 256, 512)
        for bk in (128, 256, 512)
        if bq <= max(sq, 128) and bk <= max(sk, 128)
    ]

    def build(cfg):
        bq, bk = cfg
        qz = jnp.zeros((b, h, sq, d), dtype)
        kz = jnp.zeros((b, kv_heads, sk, d), dtype)
        bounds = (
            jnp.zeros((b, 1, sk, mask_c), jnp.int32) if mask_c else None
        )
        core = _make_flash_core(
            sq, sk, float(scale), bool(causal),
            min(bq, max(_cdiv(sq, 8) * 8, 8)), min(bk, max(_cdiv(sk, 8) * 8, 8)),
            bool(interpret),
        )
        return lambda: core(qz, kz, kz, bounds)

    return autotune(
        "flash_attention", key, candidates, build,
        default=_block_geometry(sq, sk, d, jnp.dtype(dtype).itemsize),
    )


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    startend_row_indices: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over paddle layout ``[B, S, H, D]`` (optionally with a
    FlashMask bounds tensor ``[B, Hm, Sk, C]``). Differentiable.

    ``block_q``/``block_k`` default to the autotuner's pick for this shape
    when ``FLAGS_use_kernel_autotune`` is on, else to ``_block_geometry``'s."""
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    if block_q is None or block_k is None:
        mask_c = 0 if startend_row_indices is None else int(startend_row_indices.shape[-1])
        tuned_q, tuned_k = _autotune_blocks(
            q.shape, k.shape[2], q.dtype, sq, sk, d, scale, causal, mask_c, interpret
        )
        block_q = block_q if block_q is not None else tuned_q
        block_k = block_k if block_k is not None else tuned_k
    blk_q = min(block_q, max(_cdiv(sq, 8) * 8, 8))
    blk_k = min(block_k, max(_cdiv(sk, 8) * 8, 8))
    qh = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    core = _make_flash_core(
        sq, sk, float(scale), bool(causal), blk_q, blk_k, bool(interpret)
    )
    out = core(qh, kh, vh, startend_row_indices)
    return jnp.moveaxis(out, 1, 2)
