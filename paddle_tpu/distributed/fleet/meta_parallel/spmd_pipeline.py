"""SPMD circular pipeline: the TPU-native pipeline-parallel executor.

Where the reference implements 1F1B as a per-rank Python event loop with NCCL
p2p (``meta_parallel/pipeline_parallel.py:547``, ``pp_utils/
p2p_communication.py:570``), on TPU the whole schedule is ONE compiled XLA
program: stage weights are stacked along a leading axis sharded over the
``pp`` mesh axis, and a ``lax.scan`` over pipeline ticks shifts activations
between neighbouring stages with ``lax.ppermute`` over ICI. XLA overlaps the
collective-permute with the next tick's stage compute (the same overlap the
1F1B event loop hand-codes), and ``jax.grad`` through the scan gives the
reversed schedule for backward for free.

Constraints: stages must be homogeneous (same activation shape in/out), which
holds for the decoder stacks PP is used on; embedding/head run outside the
pipelined region (they belong to first/last stages and are small).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "pipeline",
    "pipeline_interleaved",
    "pipeline_zero_bubble",
    "stack_stage_params",
    "num_pipeline_ticks",
    "num_interleaved_ticks",
    "num_zero_bubble_ticks",
    "schedule_work_model",
    "plan_pipeline_region",
    "SpmdPipelineExecutor",
]


def stack_stage_params(stage_params: Sequence[Any]) -> Any:
    """Stack S per-stage parameter pytrees into one pytree whose leaves have a
    leading stage axis (to be sharded over the ``pp`` mesh axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *stage_params)


def num_pipeline_ticks(num_microbatches: int, num_stages: int) -> int:
    return num_microbatches + num_stages - 1


def num_interleaved_ticks(num_microbatches: int, num_stages: int, num_virtual: int) -> int:
    """Ticks for the interleaved ring schedule: ``V*M + S - 1`` — the V laps
    overlap, so the fill/drain bubble is paid once (S-1 ticks) instead of per
    lap (``V*(M+S-1)`` for sequential laps). Reference analog: the interleave
    scheduler of ``PipelineParallelWithInterleave`` /
    ``pipeline_scheduler_pass/pipeline_zero_bubble.py``'s bubble math."""
    return num_virtual * num_microbatches + num_stages - 1


def pipeline(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    microbatches: Any,
    mesh: Any,
    axis_name: str = "pp",
    mb_spec: Optional[P] = None,
    checkpoint_stages: bool = False,
) -> Any:
    """Run ``stage_fn`` as an S-stage circular pipeline over ``microbatches``.

    Args:
      stage_fn: ``(params, x) -> y`` for ONE stage; ``y.shape == x.shape``.
      stacked_params: pytree with leading stage axis S on every leaf
        (see :func:`stack_stage_params`), sharded ``P(axis_name)``.
      microbatches: ``[M, microbatch...]`` array — already embedded
        activations for a decoder stack.
      mesh: ``ProcessMesh`` or ``jax.sharding.Mesh`` containing ``axis_name``.
      mb_spec: PartitionSpec for the microbatch buffer over the *other* mesh
        axes (e.g. ``P(None, 'dp', None, None)`` to keep dp sharding of the
        batch dim); must be unsharded along ``axis_name``.
      checkpoint_stages: rematerialize stage activations in backward.

    Returns: ``[M, microbatch...]`` outputs, replicated over ``axis_name``.
    """
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    if axis_name not in jmesh.shape:
        raise ValueError(f"mesh has no '{axis_name}' axis (axes: {list(jmesh.shape)})")
    S = jmesh.shape[axis_name]
    M = int(microbatches.shape[0])
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"stacked_params leading (stage) axis is {leaf.shape[0]} but the "
                f"'{axis_name}' mesh axis has {S} devices — one stage per device"
            )
    if S == 1:
        fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        return jax.vmap(lambda x: fn(params0, x))(microbatches)
    if M % S != 0:
        raise ValueError(
            f"num microbatches ({M}) should be a multiple of pipeline stages ({S}) "
            "for full utilization"
        )
    if mb_spec is None:
        mb_spec = P()
    treedef = jax.tree.structure(stacked_params)
    mapped = _build_pipeline_callable(
        stage_fn, jmesh, axis_name, S, M, treedef, mb_spec, bool(checkpoint_stages)
    )
    return mapped(stacked_params, microbatches)


@functools.lru_cache(maxsize=32)  # bounded: each entry pins its stage_fn
def _build_pipeline_callable(
    stage_fn, jmesh, axis_name, S, M, param_treedef, mb_spec, checkpoint_stages
):
    """One jitted shard_map per static pipeline configuration — rebuilding the
    closure per call would defeat jax.jit's identity-keyed cache and recompile
    the whole scan+ppermute program every eager step."""
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn
    T = num_pipeline_ticks(M, S)
    param_specs = jax.tree_util.tree_unflatten(
        param_treedef, [P(axis_name)] * param_treedef.num_leaves
    )
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def local_fn(params: Any, mb: Any) -> Any:
        params = jax.tree.map(lambda a: a[0], params)  # this device's stage
        idx = jax.lax.axis_index(axis_name)
        state = jnp.zeros_like(mb[0])
        outputs = jnp.zeros_like(mb)

        def tick(carry: Any, t: Any) -> Any:
            state, outputs = carry
            inject = jax.lax.dynamic_index_in_dim(
                mb, jnp.minimum(t, M - 1), axis=0, keepdims=False
            )
            x = jnp.where(idx == 0, inject, state)
            y = fn(params, x)
            out_t = t - (S - 1)
            safe_t = jnp.clip(out_t, 0, M - 1)
            valid = jnp.logical_and(idx == S - 1, out_t >= 0)
            cur = jax.lax.dynamic_index_in_dim(outputs, safe_t, axis=0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, cur), safe_t, 0
            )
            state = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(T))
        # replicate the last stage's result to every pp rank
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
        )
        return outputs

    # manual only over the pp axis: every other mesh axis (dp/mp/...) stays
    # automatic, so GSPMD keeps propagating batch/tensor shardings through the
    # stage compute — specs may only mention `axis_name`. Partial-manual
    # shard_map only lowers inside a jit scope, so wrap the call (a no-op
    # nesting when the caller is already tracing).
    mapped = jax.shard_map(
        local_fn,
        mesh=jmesh,
        in_specs=(param_specs, mb_spec),
        out_specs=mb_spec,
        axis_names={axis_name},
        check_vma=False,
    )
    return jax.jit(mapped)


def pipeline_interleaved(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params_sv: Any,
    microbatches: Any,
    mesh: Any,
    num_virtual: int,
    axis_name: str = "pp",
    mb_spec: Optional[P] = None,
    checkpoint_stages: bool = False,
) -> Any:
    """Interleaved circular pipeline: device s holds V parameter chunks
    (virtual stages ``v*S + s``); ONE scan drives all V laps concurrently
    over a wrapped ring, so microbatch m on lap v occupies device s exactly
    at tick ``v*M + m + s`` — no device contention for ``M >= S``, and the
    warmup/drain bubble is paid once.

    ``stacked_params_sv``: pytree with leading axes ``[S, V, ...]`` on every
    leaf (stage-major, then lap). Requires ``M >= S`` (else a lap-v microbatch
    would need its lap-(v-1) result before the ring delivers it).
    """
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    if axis_name not in jmesh.shape:
        raise ValueError(f"mesh has no '{axis_name}' axis (axes: {list(jmesh.shape)})")
    S = jmesh.shape[axis_name]
    V = int(num_virtual)
    M = int(microbatches.shape[0])
    if V < 2:
        raise ValueError("pipeline_interleaved needs num_virtual >= 2; use pipeline()")
    if M % S != 0 or M < S:
        raise ValueError(
            f"interleaved schedule needs num_microbatches ({M}) to be a multiple "
            f"of num_stages ({S}) and >= it"
        )
    for leaf in jax.tree.leaves(stacked_params_sv):
        if leaf.shape[0] != S or leaf.shape[1] != V:
            raise ValueError(
                f"stacked_params_sv leaves need leading [S={S}, V={V}] axes, "
                f"got {leaf.shape[:2]}"
            )
    if mb_spec is None:
        mb_spec = P()
    treedef = jax.tree.structure(stacked_params_sv)
    mapped = _build_interleaved_callable(
        stage_fn, jmesh, axis_name, S, V, M, treedef, mb_spec, bool(checkpoint_stages)
    )
    return mapped(stacked_params_sv, microbatches)


@functools.lru_cache(maxsize=32)
def _build_interleaved_callable(
    stage_fn, jmesh, axis_name, S, V, M, param_treedef, mb_spec, checkpoint_stages
):
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn
    T = num_interleaved_ticks(M, S, V)
    param_specs = jax.tree_util.tree_unflatten(
        param_treedef, [P(axis_name)] * param_treedef.num_leaves
    )
    ring_perm = [(i, (i + 1) % S) for i in range(S)]

    def local_fn(params: Any, mb: Any) -> Any:
        params = jax.tree.map(lambda a: a[0], params)  # [V, ...] on this device
        idx = jax.lax.axis_index(axis_name)
        state = jnp.zeros_like(mb[0])
        wrap_buf = jnp.zeros_like(mb)  # device 0: lap v inputs keyed by m
        outputs = jnp.zeros_like(mb)

        def tick(carry: Any, t: Any) -> Any:
            state, wrap_buf, outputs = carry
            # 1) bank the ring-wrapped activation (device S-1 produced it at
            #    t-1 with phase t-S): it is microbatch (t-S)%M entering lap
            #    (t-S)//M + 1 at device 0, consumed at tick ((t-S)//M+1)*M+(t-S)%M
            prod_phase = t - S
            wrap_ok = jnp.logical_and(
                jnp.logical_and(idx == 0, prod_phase >= 0),
                (prod_phase // M) < (V - 1),
            )
            slot = jnp.clip(jnp.where(prod_phase >= 0, prod_phase % M, 0), 0, M - 1)
            cur_slot = jax.lax.dynamic_index_in_dim(wrap_buf, slot, 0, keepdims=False)
            wrap_buf = jax.lax.dynamic_update_index_in_dim(
                wrap_buf, jnp.where(wrap_ok, state, cur_slot), slot, 0
            )
            # 2) my (lap, microbatch) this tick
            phase = jnp.clip(t - idx, 0, V * M - 1)
            v = phase // M
            m = phase % M
            params_v = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False), params
            )
            fresh = jax.lax.dynamic_index_in_dim(mb, m, 0, keepdims=False)
            banked = jax.lax.dynamic_index_in_dim(wrap_buf, m, 0, keepdims=False)
            x = jnp.where(idx == 0, jnp.where(v == 0, fresh, banked), state)
            y = fn(params_v, x)
            # 3) final-lap outputs leave at device S-1
            out_ok = jnp.logical_and(
                jnp.logical_and(idx == S - 1, v == V - 1), t - idx >= 0
            )
            cur_out = jax.lax.dynamic_index_in_dim(outputs, m, 0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(out_ok, y, cur_out), m, 0
            )
            # 4) ring step (wraps S-1 -> 0 for the next lap)
            state = jax.lax.ppermute(y, axis_name, ring_perm)
            return (state, wrap_buf, outputs), None

        (state, wrap_buf, outputs), _ = jax.lax.scan(
            tick, (state, wrap_buf, outputs), jnp.arange(T)
        )
        idx = jax.lax.axis_index(axis_name)
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
        )
        return outputs

    mapped = jax.shard_map(
        local_fn,
        mesh=jmesh,
        in_specs=(param_specs, mb_spec),
        out_specs=mb_spec,
        axis_names={axis_name},
        check_vma=False,
    )
    return jax.jit(mapped)


# --------------------------------------------------------------------------
# Zero-bubble schedule (reference
# ``distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py``).
#
# The reference's ZB-H1 splits each backward into an input-grad phase (on the
# p2p critical path) and a weight-grad phase scheduled into the drain bubble.
# The TPU-native expression goes further: differentiating *through* the scan
# (what ``pipeline``/``pipeline_interleaved`` do) makes every reverse ring
# tick compute remat-forward + dx + dW serially; here a custom VJP makes the
# reverse scan carry ONLY the dx chain (banking each microbatch's incoming
# cotangent), and ALL weight grads are computed after the ring drains as one
# batched ``vmap`` over microbatches — dW isn't squeezed into bubbles, it
# leaves the serialized path entirely and runs as large MXU-friendly batched
# contractions. See :func:`schedule_work_model` for the resulting tick-cost
# accounting used by the tests.
# --------------------------------------------------------------------------


def num_zero_bubble_ticks(num_microbatches: int, num_stages: int, num_virtual: int = 1) -> int:
    """Ring ticks per direction for the zero-bubble schedule — the forward
    ring and the dx-only reverse ring each take ``V*M + S - 1`` ticks (the
    interleaved ring length); the weight-grad phase adds NO ring ticks."""
    return num_virtual * num_microbatches + num_stages - 1


def schedule_work_model(schedule: str, S: int, M: int, V: int = 1) -> dict:
    """Analytic per-device work accounting for the pipeline schedules, in
    units of one stage-forward evaluation (fwd = 1; a dx-only backward with
    remat costs 2: recompute + input-grad matmuls; a full VJP with remat
    costs 3: recompute + input-grad + weight-grad).

    Returns
      ``ring_ticks``      ticks on the serialized ppermute ring (fwd + bwd)
      ``critical_path``   total serialized work units along the ring
      ``idle_work``       work units a device burns on masked (non-real) data
                          during warmup/drain — the "bubble", measured as
                          wasted compute in the lockstep SPMD schedule
      ``offring_work``    work units done outside the ring (fully batched,
                          zero bubble by construction)
    """
    if schedule in ("1f1b", "pipeline"):
        T = V * (M + S - 1)  # V sequential laps of the circular schedule
        return {
            "ring_ticks": 2 * T,
            "critical_path": T * 1 + T * 3,
            "idle_work": V * (S - 1) * (1 + 3),
            "offring_work": 0,
        }
    if schedule == "interleaved":
        T = num_interleaved_ticks(M, S, V)
        return {
            "ring_ticks": 2 * T,
            "critical_path": T * 1 + T * 3,
            "idle_work": (S - 1) * (1 + 3),
            "offring_work": 0,
        }
    if schedule == "zero_bubble":
        T = num_zero_bubble_ticks(M, S, V)
        return {
            "ring_ticks": 2 * T,
            "critical_path": T * 1 + T * 2,  # reverse ring is dx-only
            "idle_work": (S - 1) * (1 + 2),
            "offring_work": V * M * 2,  # batched remat + dW, no bubble
        }
    raise ValueError(f"unknown schedule {schedule!r}")


def pipeline_zero_bubble(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    microbatches: Any,
    mesh: Any,
    num_virtual: int = 1,
    axis_name: str = "pp",
    mb_spec: Optional[P] = None,
) -> Any:
    """Zero-bubble circular pipeline: forward identical to the (interleaved)
    ring schedule; backward = dx-only reverse ring + off-ring batched dW.

    Args match :func:`pipeline_interleaved`; ``stacked_params`` leaves carry
    ``[S, ...]`` when ``num_virtual == 1`` or ``[S, V, ...]`` when ``V > 1``.
    Activations are rematerialized in backward (zero-bubble implies
    checkpointing: only stage INPUTS are saved, once per microbatch-lap).
    """
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    if axis_name not in jmesh.shape:
        raise ValueError(f"mesh has no '{axis_name}' axis (axes: {list(jmesh.shape)})")
    S = jmesh.shape[axis_name]
    V = int(num_virtual)
    M = int(microbatches.shape[0])
    if V < 1:
        raise ValueError("num_virtual must be >= 1")
    lead = (S,) if V == 1 else (S, V)
    for leaf in jax.tree.leaves(stacked_params):
        if tuple(leaf.shape[: len(lead)]) != lead:
            raise ValueError(
                f"stacked_params leaves need leading {list(lead)} axes, got "
                f"{leaf.shape[: len(lead)]}"
            )
    if S == 1:
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        out = microbatches
        for v in range(V):
            pv = params0 if V == 1 else jax.tree.map(lambda a, v=v: a[v], params0)
            out = jax.vmap(lambda x, pv=pv: stage_fn(pv, x))(out)
        return out
    if M % S != 0 or M < S:
        raise ValueError(
            f"zero-bubble schedule needs num_microbatches ({M}) to be a "
            f"multiple of num_stages ({S}) and >= it"
        )
    if V == 1:  # normalize to the [S, V, ...] layout internally
        stacked_params = jax.tree.map(lambda a: a[:, None], stacked_params)
    if mb_spec is None:
        mb_spec = P()
    treedef = jax.tree.structure(stacked_params)
    mapped = _build_zero_bubble_callable(
        stage_fn, jmesh, axis_name, S, V, M, treedef, mb_spec
    )
    return mapped(stacked_params, microbatches)


@functools.lru_cache(maxsize=32)
def _build_zero_bubble_callable(stage_fn, jmesh, axis_name, S, V, M, param_treedef, mb_spec):
    """Custom-VJP pipeline: forward ring (+ input banking), dx-only reverse
    ring (+ cotangent banking), batched off-ring weight-grad phase. The
    reverse schedule is the forward schedule under the relabeling
    ``idx -> S-1-idx``, ``m -> M-1-m``, ``v -> V-1-v`` with the ring running
    backwards — so the two scans share their structure."""
    T = num_zero_bubble_ticks(M, S, V)
    param_specs = jax.tree_util.tree_unflatten(
        param_treedef, [P(axis_name)] * param_treedef.num_leaves
    )
    fwd_ring = [(i, (i + 1) % S) for i in range(S)]
    rev_ring = [(i, (i - 1) % S) for i in range(S)]
    # banked buffers carry one entry per (lap, microbatch) phase slot; in
    # partial-manual shard_map, specs may only mention the manual pp axis —
    # other mesh axes (dp/...) stay automatic on the trailing dims
    save_spec = P(axis_name)

    def local_fwd(params, mb):
        params = jax.tree.map(lambda a: a[0], params)  # [V, ...] on this device
        idx = jax.lax.axis_index(axis_name)
        state = jnp.zeros_like(mb[0])
        wrap_buf = jnp.zeros_like(mb)
        outputs = jnp.zeros_like(mb)
        xsave = jnp.zeros(
            (V * M,) + mb.shape[1:], mb.dtype
        )  # my stage's input per phase

        def tick(carry, t):
            state, wrap_buf, outputs, xsave = carry
            prod_phase = t - S
            wrap_ok = jnp.logical_and(
                jnp.logical_and(idx == 0, prod_phase >= 0),
                (prod_phase // M) < (V - 1),
            )
            slot = jnp.clip(jnp.where(prod_phase >= 0, prod_phase % M, 0), 0, M - 1)
            cur_slot = jax.lax.dynamic_index_in_dim(wrap_buf, slot, 0, keepdims=False)
            wrap_buf = jax.lax.dynamic_update_index_in_dim(
                wrap_buf, jnp.where(wrap_ok, state, cur_slot), slot, 0
            )
            phase = jnp.clip(t - idx, 0, V * M - 1)
            valid = jnp.logical_and(t - idx >= 0, t - idx < V * M)
            v = phase // M
            m = phase % M
            params_v = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False), params
            )
            fresh = jax.lax.dynamic_index_in_dim(mb, m, 0, keepdims=False)
            banked = jax.lax.dynamic_index_in_dim(wrap_buf, m, 0, keepdims=False)
            x = jnp.where(idx == 0, jnp.where(v == 0, fresh, banked), state)
            cur_x = jax.lax.dynamic_index_in_dim(xsave, phase, 0, keepdims=False)
            xsave = jax.lax.dynamic_update_index_in_dim(
                xsave, jnp.where(valid, x, cur_x), phase, 0
            )
            y = stage_fn(params_v, x)
            out_ok = jnp.logical_and(
                jnp.logical_and(idx == S - 1, v == V - 1), valid
            )
            cur_out = jax.lax.dynamic_index_in_dim(outputs, m, 0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(out_ok, y, cur_out), m, 0
            )
            state = jax.lax.ppermute(y, axis_name, fwd_ring)
            return (state, wrap_buf, outputs, xsave), None

        (state, wrap_buf, outputs, xsave), _ = jax.lax.scan(
            tick, (state, wrap_buf, outputs, xsave), jnp.arange(T)
        )
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
        )
        return outputs, xsave

    def local_bwd(params, xsave, g):
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis_name)
        idx_r = S - 1 - idx  # reverse-schedule stage index
        state = jnp.zeros_like(g[0])
        wrap_buf = jnp.zeros_like(g)
        dmb = jnp.zeros_like(g)
        dysave = jnp.zeros((V * M,) + g.shape[1:], g.dtype)

        def tick(carry, u):
            state, wrap_buf, dmb, dysave = carry
            # reverse wrap: device idx_r==0 (global last stage) banks the
            # cotangent ring-wrapped from idx_r==S-1 for the next reverse lap
            prod_phase = u - S
            wrap_ok = jnp.logical_and(
                jnp.logical_and(idx_r == 0, prod_phase >= 0),
                (prod_phase // M) < (V - 1),
            )
            slot = jnp.clip(jnp.where(prod_phase >= 0, prod_phase % M, 0), 0, M - 1)
            cur_slot = jax.lax.dynamic_index_in_dim(wrap_buf, slot, 0, keepdims=False)
            wrap_buf = jax.lax.dynamic_update_index_in_dim(
                wrap_buf, jnp.where(wrap_ok, state, cur_slot), slot, 0
            )
            phase_r = jnp.clip(u - idx_r, 0, V * M - 1)
            valid = jnp.logical_and(u - idx_r >= 0, u - idx_r < V * M)
            m_r = phase_r % M
            phase = V * M - 1 - phase_r  # actual (lap, microbatch) slot
            v = phase // M
            m = phase % M
            params_v = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False), params
            )
            fresh = jax.lax.dynamic_index_in_dim(g, m, 0, keepdims=False)
            banked = jax.lax.dynamic_index_in_dim(wrap_buf, m_r, 0, keepdims=False)
            v_r = phase_r // M
            dy = jnp.where(idx_r == 0, jnp.where(v_r == 0, fresh, banked), state)
            cur_dy = jax.lax.dynamic_index_in_dim(dysave, phase, 0, keepdims=False)
            dysave = jax.lax.dynamic_update_index_in_dim(
                dysave, jnp.where(valid, dy, cur_dy), phase, 0
            )
            x = jax.lax.dynamic_index_in_dim(xsave, phase, 0, keepdims=False)
            # dx-only VJP: remat the stage forward, push the cotangent
            # through the input path; dW is deliberately NOT computed here
            _, vjp_x = jax.vjp(lambda xx: stage_fn(params_v, xx), x)
            (dx,) = vjp_x(dy)
            out_ok = jnp.logical_and(
                jnp.logical_and(idx_r == S - 1, v_r == V - 1), valid
            )
            cur_dmb = jax.lax.dynamic_index_in_dim(dmb, m, 0, keepdims=False)
            dmb = jax.lax.dynamic_update_index_in_dim(
                dmb, jnp.where(out_ok, dx, cur_dmb), m, 0
            )
            state = jax.lax.ppermute(dx, axis_name, rev_ring)
            return (state, wrap_buf, dmb, dysave), None

        (state, wrap_buf, dmb, dysave), _ = jax.lax.scan(
            tick, (state, wrap_buf, dmb, dysave), jnp.arange(T)
        )
        # off-ring weight-grad phase: one batched remat+dW contraction per
        # lap over all M microbatches at once — no ring, no bubble
        xs = xsave.reshape((V, M) + xsave.shape[1:])
        dys = dysave.reshape((V, M) + dysave.shape[1:])
        per_lap = []
        for v in range(V):
            pv = jax.tree.map(lambda a, v=v: a[v], params)

            def wgrad_one(x, dy, pv=pv):
                _, vjp_p = jax.vjp(lambda q: stage_fn(q, x), pv)
                return vjp_p(dy)[0]

            contrib = jax.vmap(wgrad_one)(xs[v], dys[v])
            per_lap.append(jax.tree.map(lambda a: a.sum(0), contrib))
        dparams = jax.tree.map(lambda *leaves: jnp.stack(leaves, 0), *per_lap)
        dparams = jax.tree.map(lambda a: a[None], dparams)  # local [1, V, ...]
        dmb = jax.lax.psum(
            jnp.where(idx == 0, dmb, jnp.zeros_like(dmb)), axis_name
        )
        return dparams, dmb

    mapped_fwd = jax.jit(
        jax.shard_map(
            local_fwd,
            mesh=jmesh,
            in_specs=(param_specs, mb_spec),
            out_specs=(mb_spec, save_spec),
            axis_names={axis_name},
            check_vma=False,
        )
    )
    mapped_bwd = jax.jit(
        jax.shard_map(
            local_bwd,
            mesh=jmesh,
            in_specs=(param_specs, save_spec, mb_spec),
            out_specs=(param_specs, mb_spec),
            axis_names={axis_name},
            check_vma=False,
        )
    )

    @jax.custom_vjp
    def pzb(stacked_params, mb):
        return mapped_fwd(stacked_params, mb)[0]

    def pzb_f(stacked_params, mb):
        out, xsave = mapped_fwd(stacked_params, mb)
        return out, (stacked_params, xsave)

    def pzb_b(res, gy):
        stacked_params, xsave = res
        return mapped_bwd(stacked_params, xsave, gy)

    pzb.defvjp(pzb_f, pzb_b)
    return jax.jit(pzb)


# --------------------------------------------------------------------------
# PipelineLayer wiring: run a model's homogeneous decoder region through the
# circular executor (the reference runs 1F1B/interleave event loops instead:
# ``meta_parallel/pipeline_parallel.py:547`` / ``:1138``)
# --------------------------------------------------------------------------


def _structure_key(layer: Any) -> Any:
    """Structural fingerprint: two layers with the same key can be executed by
    one template function with swapped parameters."""
    from paddle_tpu.nn.layer.layers import Layer as _Layer

    if not isinstance(layer, _Layer):
        return None
    return (
        type(layer).__qualname__,
        tuple(
            (n, tuple(p.shape), str(p.dtype)) for n, p in layer.named_parameters()
        ),
    )


def plan_pipeline_region(pipe: Any) -> tuple:
    """Find the maximal contiguous run of structurally identical layers in a
    ``PipelineLayer`` — the homogeneous decoder stack that the SPMD circular
    pipeline executes. Returns ``(start, end)`` into ``pipe._built``;
    ``[0, start)`` is the prologue (embeddings), ``[end, len)`` the epilogue
    (final norm, lm head)."""
    keys = [_structure_key(l) for l in pipe._built]  # noqa: E741
    best = (0, 0)
    i = 0
    n = len(keys)
    while i < n:
        if keys[i] is None:
            i += 1
            continue
        j = i
        while j < n and keys[j] == keys[i]:
            j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = j
    if best[1] - best[0] < 2:
        raise ValueError(
            "PipelineLayer has no homogeneous region of >= 2 layers; the SPMD "
            "circular pipeline needs a repeated decoder block structure"
        )
    return best


class SpmdPipelineExecutor:
    """Execute a ``PipelineLayer`` with its decoder region pipelined over the
    ``pp`` mesh axis via the scan+ppermute circular schedule.

    Prologue/epilogue layers (embedding, final norm, tied lm head) run in the
    global program on every rank — they are small, and the tied-embedding
    gradient accumulation falls out of autograd because both uses reference
    the same Parameter. The region's blocks are assigned to stages in
    contiguous chunks; with ``num_virtual_pipeline_stages = V > 1`` each stage
    holds V chunks and the schedule makes V laps around the ring
    (the interleave topology of reference ``PipelineParallelWithInterleave``,
    expressed as stacked virtual stages rather than an event loop).

    Differentiable end-to-end: the pipelined region is dispatched as one op
    whose VJP is jax-derived, so ``loss.backward()`` reaches every block
    parameter as well as the prologue/epilogue ones.
    """

    def __init__(
        self,
        pipe: Any,
        mesh: Any,
        num_microbatches: int,
        axis_name: str = "pp",
        checkpoint_stages: bool = False,
        schedule: str = "auto",
    ) -> None:
        if schedule not in ("auto", "zero_bubble"):
            raise ValueError(f"schedule must be 'auto' or 'zero_bubble', got {schedule!r}")
        self._pipe = pipe
        self._mesh = mesh
        self._axis = axis_name
        self._M = int(num_microbatches)
        self._ckpt = checkpoint_stages
        self._schedule = schedule
        jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
        if axis_name not in jmesh.shape:
            raise ValueError(
                f"mesh has no '{axis_name}' axis (axes: {list(jmesh.shape)})"
            )
        self._S = int(jmesh.shape[axis_name])
        self._V = int(getattr(pipe, "_num_virtual_pipeline_stages", 1) or 1)
        start, end = plan_pipeline_region(pipe)
        self._start, self._end = start, end
        L = end - start
        if L % (self._S * self._V) != 0:
            raise ValueError(
                f"decoder region has {L} blocks, not divisible by "
                f"num_stages*virtual ({self._S}*{self._V})"
            )
        self._C = L // (self._S * self._V)  # blocks per (stage, lap) chunk
        if schedule == "zero_bubble" and self._S > 1 and (
            self._M < self._S or self._M % self._S != 0
        ):
            raise ValueError(
                f"zero_bubble schedule needs num_microbatches ({self._M}) to be "
                f"a multiple of num_stages ({self._S}) and >= it"
            )
        self._blocks = pipe._built[start:end]
        self._template = self._blocks[0]
        self._param_names = [n for n, _ in self._template.named_parameters()]
        if not self._param_names:
            raise ValueError("pipelined blocks have no parameters")

    # -- template application (pure-jax view of one block) ------------------
    def _apply_template(self, arrays: List[Any], x: Any) -> Any:
        import paddle_tpu
        from paddle_tpu.core.tensor import Tensor

        named = list(self._template.named_parameters())
        saved = [p._data for _, p in named]
        try:
            for (_n, p), a in zip(named, arrays):
                p._data = a
            with paddle_tpu.no_grad():
                y = self._template(Tensor(x))
            return y._data
        finally:
            for (_n, p), s in zip(named, saved):
                p._data = s

    def _chunk_fn(self, chunk_params: List[List[Any]], x: Any) -> Any:
        for block_arrays in chunk_params:
            x = self._apply_template(block_arrays, x)
        return x

    # -- full forward -------------------------------------------------------
    def forward(self, x: Any) -> Any:
        from paddle_tpu.core.dispatch import call_op

        pipe, M, S, V, C = self._pipe, self._M, self._S, self._V, self._C
        h = x
        for i in range(self._start):
            h = pipe._run_one(i, pipe._built[i], h)

        batch = h.shape[0]
        if batch % M != 0:
            raise ValueError(f"batch {batch} not divisible by num_microbatches {M}")
        per_block_tensors = [
            [dict(b.named_parameters())[n] for n in self._param_names]
            for b in self._blocks
        ]
        flat_params = [t for row in per_block_tensors for t in row]
        P_ = len(self._param_names)

        def stack_sv(rows, with_lap_axis):
            """[S, V, ...] (stage-major, then lap) stacking of the per-block
            parameter rows; ``with_lap_axis=False`` keeps plain [S, ...]."""
            per_sv = [
                [rows[(v * S + s) * C : (v * S + s + 1) * C] for v in range(V)]
                for s in range(S)
            ]
            if not with_lap_axis:
                return jax.tree.map(
                    lambda *xs: jnp.stack(xs, 0), *[per_sv[s][0] for s in range(S)]
                )
            lap_stacked = [
                jax.tree.map(lambda *xs: jnp.stack(xs, 0), *per_sv[s])
                for s in range(S)
            ]
            return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *lap_stacked)

        def impl(h_arr, *flat):
            rows = [list(flat[i * P_ : (i + 1) * P_]) for i in range(len(self._blocks))]
            mb = h_arr.reshape((M, batch // M) + h_arr.shape[1:])
            if self._schedule == "zero_bubble" and S > 1 and M >= S:
                mb = pipeline_zero_bubble(
                    self._chunk_fn,
                    stack_sv(rows, with_lap_axis=V > 1),
                    mb,
                    self._mesh,
                    num_virtual=V,
                    axis_name=self._axis,
                )
            elif V > 1 and S > 1 and M >= S:
                # interleaved ring: all V laps overlap in ONE scan —
                # V*M + S - 1 ticks instead of V*(M + S - 1)
                mb = pipeline_interleaved(
                    self._chunk_fn,
                    stack_sv(rows, with_lap_axis=True),
                    mb,
                    self._mesh,
                    V,
                    axis_name=self._axis,
                    checkpoint_stages=self._ckpt,
                )
            else:
                for v in range(V):
                    stage_chunks = [
                        rows[(v * S + s) * C : (v * S + s + 1) * C] for s in range(S)
                    ]
                    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *stage_chunks)
                    mb = pipeline(
                        self._chunk_fn,
                        stacked,
                        mb,
                        self._mesh,
                        axis_name=self._axis,
                        checkpoint_stages=self._ckpt,
                    )
            return mb.reshape((batch,) + mb.shape[2:])

        h = call_op("spmd_pipeline", impl, h, *flat_params)
        for i in range(self._end, len(pipe._built)):
            h = pipe._run_one(i, pipe._built[i], h)
        return h

    __call__ = forward
