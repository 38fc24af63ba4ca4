"""Tensor-parallel serving seam: the ``tp`` mesh the continuous-batching
engine shards itself over.

Reference: the fork's layer-7 distributed stack (``fleet``, ``auto_parallel``,
``ProcessGroup``) — here shaped for single-controller SPMD serving. One
engine = one shard group over a single-axis ``['tp']`` mesh:

- **Attention heads and the KV block pool partition per device.** The paged
  caches keep their ``[num_blocks, kv_heads, block_size, head_dim]`` layout
  and shard the HEAD dim, so a logical block id indexes the same slot in
  every shard's pool partition — the host-side allocator, block tables,
  prefix-cache chain hashes and refcounts stay replicated-by-construction
  (one copy on the host steering all shards), and head-parallel attention
  needs no communication inside the paged block walk.
- **MLP and projections split Megatron-style** (column-parallel
  qkv/gate/up, row-parallel o/down): GSPMD inserts exactly one all-reduce
  per layer at the row-parallel matmul.
- **The lm-head shards over vocab**; the greedy path's ``argmax`` over the
  vocab-sharded logits lowers to a sharded argmax + global max-combine
  (exact index tiebreak), preserving byte-identical outputs.

The engine stays ONE compiled signature under the mesh: sharding is carried
by the INPUT placements (committed params/caches), never by the program's
shapes, so the recompile watchdog still reports exactly one compile.

``tp_shard_context`` is a trace-time seam: the engine arms it around its
jitted dispatch so the paged-attention functional (which has no mesh
argument) can wrap the Pallas kernel in ``shard_map`` over the head shard —
a ``pallas_call`` has no SPMD partitioning rule, so without the wrapper
GSPMD would replicate the kernel; the XLA fallback path partitions under
plain GSPMD and needs no context.
"""

from __future__ import annotations

from typing import Any, ContextManager, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.core.spmd import partitioned_trace, shard_group_mesh

__all__ = [
    "COLUMN_PARALLEL_LEAVES",
    "ROW_PARALLEL_LEAVES",
    "TP_AXIS",
    "VOCAB_PARALLEL_EMBEDDINGS",
    "analytic_cost_hints",
    "build_tp_mesh",
    "current_tp_mesh",
    "kv_cache_sharding",
    "replicated",
    "row_parallel_overlap_matmul",
    "shard_model_params",
    "tp_param_spec",
    "tp_shard_context",
    "validate_tp",
]

TP_AXIS = "tp"

# THE Megatron leaf-name classification — the one placement table both the
# serving policy below and the training policy (models/llama.llama_shard_fn,
# mp axis) consume, so a new projection name (a fused qkv, an MoE expert
# linear) added here shards under both.
# Column-parallel leaves: weight [in, out] shards the OUT dim (their packed
# outputs are the per-head / per-neuron slices the next layer consumes
# shard-local); row-parallel leaves shard the IN dim — the one all-reduce
# per layer lands after their matmul. lm_head [hidden, vocab] shards vocab.
COLUMN_PARALLEL_LEAVES = (
    "q_proj", "k_proj", "v_proj", "gate_proj", "up_proj", "lm_head",
)
ROW_PARALLEL_LEAVES = ("o_proj", "down_proj")
# vocab-parallel embedding: weight [vocab, hidden] shards dim 0 — also the
# tied-embedding lm-head layout (matmul(x, W^T) contracts hidden, vocab
# stays sharded into the argmax)
VOCAB_PARALLEL_EMBEDDINGS = ("embed_tokens", "word_embeddings", "wte")


def build_tp_mesh(tp: int) -> Mesh:
    """Single-axis ``['tp']`` mesh over the first ``tp`` visible devices (on
    TPU, jax's default device order follows the physical ICI torus)."""
    devices = jax.devices()
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} exceeds the {len(devices)} visible devices"
        )
    import numpy as np

    return Mesh(np.asarray(devices[:tp], dtype=object), (TP_AXIS,))


def validate_tp(tp: int, num_heads: int, num_kv_heads: int) -> None:
    """The head-parallel contract: ``tp`` must divide the KV heads (each
    shard owns whole KV heads of the pool partition) and the query heads
    (GQA groups follow their KV head onto the same shard)."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if num_kv_heads % tp != 0:
        raise ValueError(
            f"tp={tp} does not divide num_key_value_heads={num_kv_heads}: "
            "head-parallel attention shards whole KV heads"
        )
    if num_heads % tp != 0:
        raise ValueError(
            f"tp={tp} does not divide num_attention_heads={num_heads}"
        )


def tp_param_spec(name: str, ndim: int) -> PartitionSpec:
    """Megatron placement for one named parameter on the ``['tp']`` mesh,
    by leaf-name convention (``...self_attn.q_proj.weight``). A model may
    override per-name decisions by defining ``tp_param_spec(name, ndim)``
    (see :func:`shard_model_params`). Unknown leaves replicate — always
    correct, GSPMD just keeps them whole on every shard."""
    parts = name.split(".")
    leaf = parts[-1]
    owner = parts[-2] if len(parts) >= 2 else ""
    if leaf == "weight" and ndim == 2:
        if owner in COLUMN_PARALLEL_LEAVES:
            return PartitionSpec(None, TP_AXIS)
        if owner in ROW_PARALLEL_LEAVES:
            return PartitionSpec(TP_AXIS, None)
        if owner in VOCAB_PARALLEL_EMBEDDINGS:
            return PartitionSpec(TP_AXIS, None)
    return PartitionSpec(*([None] * ndim))


def replicated(mesh: Mesh, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*([None] * ndim)))


def kv_cache_sharding(mesh: Mesh) -> NamedSharding:
    """The pool partition: ``[num_blocks, kv_heads, block_size, head_dim]``
    sharded on the HEAD dim — every shard holds the same logical blocks
    (same ids, same offsets) for its own slice of the heads."""
    return NamedSharding(mesh, PartitionSpec(None, TP_AXIS, None, None))


def shard_model_params(model: Any, mesh: Mesh) -> int:
    """Commit every named parameter onto the mesh per the Megatron policy
    (model-provided ``tp_param_spec(name, ndim)`` wins when defined);
    returns how many params got a genuinely split placement. In-place:
    serving owns the model — the engine is the unit of deployment."""
    policy = getattr(model, "tp_param_spec", None) or tp_param_spec
    n_split = 0
    for name, p in model.named_parameters():
        spec = policy(name, p._data.ndim)
        if any(ax is not None for ax in spec):
            n_split += 1
        p._data = jax.device_put(p._data, NamedSharding(mesh, spec))
    return n_split


# -- trace-time context ------------------------------------------------------
# The armed mesh IS the trace's partition mark (core/spmd.py): the kernel
# dispatch reads the same thread-local to keep bare Pallas kernels out of the
# partitioned trace, the paged-attention functional to wrap its own.
def current_tp_mesh() -> Optional[Mesh]:
    """The mesh armed by the innermost :func:`tp_shard_context` on this
    thread (None = single-chip semantics). Read at TRACE time by the paged-
    attention functional to decide the shard_map wrapping."""
    return shard_group_mesh()


def row_parallel_overlap_matmul(x: Any, weight: Any, tiles: int = 2) -> Any:
    """A row-parallel matmul (o_proj/down_proj: weight shards the IN dim)
    split into ``tiles`` independent token-row tiles — the "Tile-Level
    Activation Overlap" schedule. Under GSPMD each tile's partial matmul ends
    in its OWN all-reduce, so while tile t's collective is on the ICI wire,
    tile t+1's matmul (and the consumer of tile t-1's already-reduced rows)
    runs on the MXU — the per-layer all-reduce stops serializing against the
    whole layer. Per-row contraction is untouched by the split, so the
    result is byte-identical to the plain matmul (tile boundaries only
    partition the BATCH rows; each output row's reduction order is
    unchanged).

    ``x`` is ``[..., rows, in]`` with the leading dims flattened into rows;
    falls back to one tile when the row count doesn't split evenly (serving
    batches are padded to the slot count, which divides)."""
    import jax.numpy as jnp

    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    x2 = x.reshape(rows, x.shape[-1])
    tiles = int(tiles)
    if tiles <= 1 or rows % tiles != 0:
        out = jnp.matmul(x2, weight)
        return out.reshape(*lead, weight.shape[-1])
    step = rows // tiles
    parts = [
        jnp.matmul(x2[t * step : (t + 1) * step], weight) for t in range(tiles)
    ]
    return jnp.concatenate(parts, axis=0).reshape(*lead, weight.shape[-1])


def tp_shard_context(mesh: Optional[Mesh]) -> ContextManager[None]:
    """Arm ``mesh`` as the tensor-parallel shard group for traces started
    under this context (re-entrant; restores the previous value)."""
    return partitioned_trace(mesh)


def analytic_cost_hints(
    num_layers: int,
    hidden: int,
    intermediate: int,
    vocab: int,
    tokens: int,
    kv_len: int,
    tp: int = 1,
    dtype_bytes: int = 2,
    ici_bytes_per_s: float = 45e9,
    peak_flops_per_s: float = 197e12,
) -> dict:
    """Analytic per-category weights seeding devprof's attribution prior
    for one decode/prefill step over ``tokens`` query rows against a
    ``kv_len`` context. All weights are FLOP-denominated so the XLA cost
    model can reconcile against them: matmul and attention are literal flop
    counts (Megatron accounting — qkv+o 4h² and the gated MLP 3h·i per
    layer, plus the lm-head 2hV; attention 2·2·h·kv per layer); the
    collective weight converts the per-layer all-reduce's wire time
    (2 ramp-up·bytes/bw for a ring over ``tp`` shards) into
    flop-equivalents at peak so the three shares stay in one unit: a
    prior that devprof's measured segments are laid against."""
    matmul = float(tokens) * (
        num_layers * 2.0 * (4.0 * hidden * hidden + 3.0 * hidden * intermediate)
        + 2.0 * hidden * vocab
    )
    attention = float(tokens) * num_layers * 2.0 * 2.0 * hidden * float(kv_len)
    collective = 0.0
    if tp > 1:
        # one row-parallel all-reduce per layer (o_proj + down_proj fold
        # into the same ring pass in the overlap path): ring all-reduce
        # moves 2*(tp-1)/tp of the activation per hop
        ar_bytes = (
            num_layers * float(tokens) * hidden * dtype_bytes
            * 2.0 * (tp - 1) / tp * 2.0  # two row-parallel matmuls per layer
        )
        collective = (ar_bytes / ici_bytes_per_s) * peak_flops_per_s
    return {"attention": attention, "matmul": matmul, "collective": collective}
