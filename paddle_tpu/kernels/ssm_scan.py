"""The carried-state arithmetic of a Mamba-2 block's serving step as ONE
Pallas TPU kernel that touches each slot's state once.

Over a chunk of ``C`` rows the state-space recurrence
(``incubate/nn/functional/mamba2.py``) reads a slot's state ``S_0 [H, P, N]``
twice and writes it once:

    carried[t, h] = S_0[h] C_t                                  (the chunk's rows read the old state)
    S_C[h]        = decay[h] S_0[h] + sum_s xs[s, h] (x) B_s    (the chunk's rows enter the new one)

As XLA runs them these are three state-sized operations (two contractions
and a multiply-add: five walks of the plane). Here a grid cell holds one
slot's tile of heads in VMEM, does both contractions on it and writes it back
to the SAME plane (``input_output_aliases``): one read and one write. ``decay``
(``exp`` of the chunk's summed ``dt A``) and ``xs`` (``x`` scaled by its decay to
the chunk's end) come from the caller, which keeps everything ``[S, C, H]``-
sized as the XLA composition it was.

**A slot without rows costs no traffic.** The kernel moves at the pace of a
bare copy of the plane through VMEM (PERF.md, PR 39), so what is left to save
is the bytes: the grid step of an idle slot holds the blocks of a live
neighbour (``_held_slots``), which Pallas does not move again while the
block index stays what it was, and touches nothing.

**The contractions keep float32.** On this chip a float32 ``dot_general`` at
the default precision is ONE bfloat16 pass, which would round the state on its
way through the MXU. Each float32 operand is split into three bfloat16 parts
(``hi + mid + lo``, 24 bits of mantissa) and the six products that
``Precision.HIGHEST`` keeps (all but ``mid lo``, ``lo mid``, ``lo lo``) are
formed, with the SMALL operand's parts stacked so that they share a pass: the
parts of ``C`` stacked along the rows of one left operand (three passes over the
state's parts, where six separate matmuls would load the state six times), the
parts of ``xs`` and ``B`` stacked along the contraction (six products in ONE
pass of depth 96).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["KERNEL_SCAN", "ssm_state_scan"]

# pallas_call name=: what a device trace calls the kernel (stable, no shapes)
KERNEL_SCAN = "ssm_state_scan"

_ROWS = 16  # the chunk is padded to whole bfloat16 sublane tiles, so the stacked parts stay aligned
# a cell's state tile: from 16 heads of [64, 128] (0.5 MiB) on the cell moves at the pace of a bare copy
# through VMEM (PERF.md, PR 39); 1 MiB keeps its four buffers well under the default scoped limit
_CELL_BYTES = 1 << 20
_DEFAULT_SCOPED_VMEM = 16 << 20  # Mosaic's default scoped limit; a cell over it states its own


def _split3(v: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``v`` (float32) as three bfloat16 parts whose sum is ``v`` to 24 bits."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _scan_kernel(
    live_ref,  # scalar prefetch: [S] int32, 0 = the slot has no valid rows (tile left as it was)
    fresh_ref,  # scalar prefetch: [S] int32, 1 = a request's first chunk (the tile reads as zeros)
    held_ref,  # scalar prefetch: [S] int32, the slot whose blocks the step holds (_held_slots)
    decay_ref,  # scalar prefetch: [S, H] float32 exp(cum_C)
    c_ref,  # [1, C, groups*N] float32
    b_ref,  # [1, C, groups*N] float32
    xs_ref,  # [1, C, groups*R*P] float32
    s_ref,  # [1, groups*R*P, N] float32: the cell's heads of slot held_ref[si]
    carried_ref,  # [1, C, groups*R*P] float32, of slot si
    out_ref,  # [1, groups*R*P, N] float32, the same tile of the same plane as s_ref
    *,
    groups: int,  # B/C groups of the cell
    heads: int,  # heads a group (R)
    head_dim: int,  # P
):
    gj, si = pl.program_id(0), pl.program_id(1)
    n = s_ref.shape[2]
    c_rows = c_ref.shape[1]
    span = heads * head_dim
    nt = (((1,), (1,)), ((), ()))  # a . b^T
    tn = (((0,), (0,)), ((), ()))  # a^T . b

    @pl.when(live_ref[si] == 0)
    def _idle():
        carried_ref[...] = jnp.zeros_like(carried_ref)

        # the step holds a neighbour's tile: untouched it goes back as that slot's step left it, or will
        # leave it. Only the first step of a tile's run must fill the buffer that is written back, for the
        # run that has no live step at all (every slot idle: all of them hold slot 0's tile)
        @pl.when((si == 0) | (held_ref[jnp.maximum(si - 1, 0)] != held_ref[si]))
        def _pass_through():
            out_ref[...] = s_ref[...]

    @pl.when(live_ref[si] != 0)
    def _advance():
        fresh = fresh_ref[si] != 0
        for g in range(groups):
            rows = slice(g * span, (g + 1) * span)
            lanes = slice(g * n, (g + 1) * n)
            s0 = jnp.where(fresh, 0.0, s_ref[0, rows, :])  # [R*P, N]
            s_hi, s_mid, s_lo = _split3(s0)
            c_parts = jnp.concatenate(_split3(c_ref[0, :, lanes]), axis=0)  # [3C, N]: hi, mid, lo
            by_hi = jax.lax.dot_general(c_parts, s_hi, nt, preferred_element_type=jnp.float32)
            by_mid = jax.lax.dot_general(c_parts[:2 * c_rows], s_mid, nt, preferred_element_type=jnp.float32)
            by_lo = jax.lax.dot_general(c_parts[:c_rows], s_lo, nt, preferred_element_type=jnp.float32)
            # small terms first: (c_lo s_hi + c_mid s_mid + c_hi s_lo) + (c_mid s_hi + c_hi s_mid) + c_hi s_hi
            small = by_hi[2 * c_rows:] + by_mid[c_rows:] + by_lo
            carried_ref[0, :, rows] = small + (by_hi[c_rows:2 * c_rows] + by_mid[:c_rows]) + by_hi[:c_rows]

            x_hi, x_mid, x_lo = _split3(xs_ref[0, :, rows])  # [C, R*P] each
            b_hi, b_mid, b_lo = _split3(b_ref[0, :, lanes])  # [C, N] each
            x_parts = jnp.concatenate([x_lo, x_hi, x_mid, x_mid, x_hi, x_hi], axis=0)  # [6C, R*P]
            b_parts = jnp.concatenate([b_hi, b_lo, b_mid, b_hi, b_mid, b_hi], axis=0)  # [6C, N]
            added = jax.lax.dot_general(x_parts, b_parts, tn, preferred_element_type=jnp.float32)  # [R*P, N]
            for r in range(heads):
                head = slice(g * span + r * head_dim, g * span + (r + 1) * head_dim)
                local = slice(r * head_dim, (r + 1) * head_dim)
                decay = decay_ref[si, (gj * groups + g) * heads + r]
                out_ref[0, head, :] = decay * s0[local] + added[local]


def _cell_groups(groups: int, heads: int, head_dim: int, state: int) -> int:
    """B/C groups (of ``heads`` heads each) a grid cell holds: as many as keep
    its state tile at ``_CELL_BYTES``."""
    fit = max(1, _CELL_BYTES // (heads * head_dim * state * 4))
    return max(g for g in range(1, groups + 1) if groups % g == 0 and g <= fit)


def _held_slots(live: jax.Array) -> jax.Array:
    """``[S]`` int32: the slot whose blocks grid step ``s`` holds. A live slot
    holds its own; an idle one the next live slot's (after the last live slot:
    that one's; none live: slot 0's). Steps that hold the same blocks are
    consecutive, and Pallas moves a block only when its index changes from one
    step to the next: an idle slot's tile is neither read nor written."""
    s = live.shape[0]
    index = jnp.arange(s, dtype=jnp.int32)
    following = jax.lax.cummin(jnp.where(live, index, s), axis=0, reverse=True)
    return jnp.where(following < s, following, jnp.max(jnp.where(live, index, 0))).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cell_groups", "interpret"))
def ssm_state_scan(
    c: jax.Array,  # [S, C, G, N] float32
    b: jax.Array,  # [S, C, G, N] float32
    xs: jax.Array,  # [S, C, H, P] float32: x_s scaled by dt_s exp(cum_C - cum_s); 0 on rows that do not advance
    decay: jax.Array,  # [S, H] float32: exp(cum_C)
    state: jax.Array,  # [S, H, P, N] float32, written in place
    live: jax.Array,  # [S] bool: the slot has valid rows
    fresh: jax.Array,  # [S] bool: the slot's state reads as zeros (a request's first chunk)
    cell_groups: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``(carried [S, C, H, P], the state after the chunk)``, both float32:
    ``carried[s, t, h] = S_0[s, h] C[s, t, h // (H / G)]`` and ``S_C = decay S_0
    + sum_t xs_t (x) B_t`` with ``S_0`` the slot's tile, or zeros where
    ``fresh``. A slot that is not ``live`` keeps its tile bit for bit and its
    ``carried`` rows are zeros. ``cell_groups``: B/C groups a grid cell holds
    (0: chosen from the shapes)."""
    s, n_rows, g, n = c.shape
    h, p = state.shape[1], state.shape[2]
    r = h // g
    if state.dtype != jnp.float32 or state.shape != (s, h, p, n) or h % g:
        raise ValueError(f"state is float32 [S, H, P, N] with H a multiple of G={g}, got {state.dtype}{state.shape}")
    if not interpret and (n % 128 or (r * p) % 128):
        raise ValueError(
            f"the scan kernel's blocks are [{r * p}, {n}] state tiles and [C, {r * p}] rows: the state size "
            f"and a group's heads x head_dim must be whole 128-lane tiles"
        )
    cg = cell_groups or _cell_groups(g, r, p, n)
    if g % cg:
        raise ValueError(f"{cg} groups a cell do not divide {g} groups")
    pad = -n_rows % _ROWS
    rows = n_rows + pad
    flat = lambda arr, width: jnp.pad(  # noqa: E731 - [S, C, ...] -> [S, rows, width] float32
        arr.astype(jnp.float32).reshape(s, n_rows, width), ((0, 0), (0, pad), (0, 0))
    )
    cell_rows = cg * r * p
    # slots are the grid's INNER axis, so that the steps which hold one tile follow each other
    rows_in = lambda gj, si, live, fresh, held, decay: (held[si], 0, gj)  # noqa: E731
    rows_out = lambda gj, si, *_: (si, 0, gj)  # noqa: E731
    tile = lambda gj, si, live, fresh, held, decay: (held[si], gj, 0)  # noqa: E731
    block_bytes = cell_rows * n * 4
    need = 4 * block_bytes + 8 * (r * p) * n * 4 + (4 << 20)  # the plane's four buffers, a group's temporaries, the rows
    params = dict(dimension_semantics=("parallel", "arbitrary"))  # a tile's steps run in slot order
    if need > _DEFAULT_SCOPED_VMEM:
        params["vmem_limit_bytes"] = need
    carried, new_state = pl.pallas_call(
        functools.partial(_scan_kernel, groups=cg, heads=r, head_dim=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g // cg, s),
            in_specs=[
                pl.BlockSpec((1, rows, cg * n), rows_in),
                pl.BlockSpec((1, rows, cg * n), rows_in),
                pl.BlockSpec((1, rows, cell_rows), rows_in),
                pl.BlockSpec((1, cell_rows, n), tile),
            ],
            out_specs=[
                pl.BlockSpec((1, rows, cell_rows), rows_out),
                pl.BlockSpec((1, cell_rows, n), tile),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s, rows, h * p), jnp.float32),
            jax.ShapeDtypeStruct((s, h * p, n), jnp.float32),
        ],
        input_output_aliases={7: 1},  # the state plane (after the four prefetched scalars) is the second result
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name=KERNEL_SCAN,
    )(
        live.astype(jnp.int32), fresh.astype(jnp.int32), _held_slots(live), decay.astype(jnp.float32),
        flat(c, g * n), flat(b, g * n), flat(xs, h * p), state.reshape(s, h * p, n),
    )
    return carried[:, :n_rows].reshape(s, n_rows, h, p), new_state.reshape(s, h, p, n)
