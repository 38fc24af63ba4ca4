"""Continuous-batching engine over a prefix-cached, ragged paged KV pool.

The serving-grade decode path: where ``generation.py::generate_paged`` runs
one static batch to completion (a finished sequence holds its batch slot and
KV blocks until EVERY sequence is done), this engine admits new requests into
freed slots every step and reclaims a finished sequence's blocks immediately
— the scheduling model of vLLM / the reference's serving stack, shaped for
TPU: all device shapes are FIXED (max-slots batch, dense block tables,
per-slot lengths as data), so the whole mixed workload runs through exactly
ONE compiled program per (model, config):

- one unified STEP signature: ``[max_slots, chunk]`` new tokens over the
  shared block pool. A decode slot contributes one valid row; a slot still
  prefilling contributes up to ``chunk`` prompt tokens (**chunked prefill**,
  "Ragged Paged Attention" arxiv 2604.15464) — prompt chunks ride the same
  dispatch as decode rows, so a long prompt never head-of-line-blocks the
  decode batch, and the recompile watchdog reports exactly 1 signature.
  Padded slots are carried by an active mask (they write no KV, attend over
  nothing, and the ragged Pallas kernel skips their compute — see
  ``kernels/paged_attention.py``).

Admits and evictions only rewrite HOST-side numpy state (block tables,
lengths, the active mask) that is passed to the compiled step as data — the
program never retraces as the request mix changes.

**Prefix caching**: with ``FLAGS_enable_prefix_cache`` (default on), prompts
are chunked into block-aligned segments keyed by a rolling content hash, and
the longest cached prefix chain is mapped straight into an admitted
request's block table with refcounts bumped — the shared prefix is computed
once and mapped by all (``inference/prefix_cache.py``). The first divergent
block is copy-on-write: the fork is carried INTO the unified step as data
(``cow_src``/``cow_dst`` per slot), so CoW adds no compiled signature.
Eviction is LRU over zero-reference chains only — a live request can never
lose a block — and the worst-case admission reservation stays honest by
counting only non-shared blocks. At request FINISH, full blocks containing
the request's committed GENERATED tokens are registered into the cache too
(rewind-safe: speculative rewinds happen at commit time, long before
release), so a multi-turn conversation's second turn maps its first turn's
KV instead of recomputing it.

**Hierarchical KV**: with ``FLAGS_kv_host_tier_bytes`` > 0, a bounded
host-RAM tier (``inference/kv_tier.py``) sits under the prefix cache:
LRU-evicted zero-ref chain blocks are captured D2H and spilled instead of
dropped, the match walk continues across the tier boundary (including the
divergent block's partial, via prefetch-on-write), and matched spilled
chains prefetch H2D asynchronously into atomically reserved pool slots —
overlapped with the mixed ragged step through a per-slot gate: a gated
slot contributes no rows until its copies land (``is_ready`` polling at
chunk boundaries), so other slots' chunks hide the transfer. Spill and
prefetch are pure data movement outside the traced step (ONE compiled
signature holds), greedy outputs are byte-identical with the tier on or
off, and ``recover()`` drops the in-flight prefetch set while the tier
itself survives as part of the host truth replay rebuilds from.

**Speculative decoding**: with ``FLAGS_spec_decode`` (default off), a
host-side n-gram / prompt-lookup drafter (``inference/spec_decode.py``)
proposes up to K draft tokens per decode slot; the slot's step row becomes a
``1 + K``-token chunk (``[last_token, d1..dK]``) with the SAME per-row
causal ``q_lens`` semantics prompt chunks already use — drafted slots,
plain-decode slots, and prefill chunks coexist in ONE dispatch of the ONE
compiled signature (verification is pure data; the recompile watchdog still
reports exactly 1 compile per engine). The step's per-row argmax is compared
against the draft left-to-right: accepted tokens commit in bulk (their KV
was written by the very step that verified them, and the argmax after the
last accepted draft rides along as a bonus token, so a fully accepted
K-draft commits K+1 tokens for one dispatch), and the first rejection
rewinds by block-table truncation through the refcounted pool. Speculation
may transiently write into a slot's reserved headroom but never past its
worst-case admission reservation (drafts are capped at the remaining token
budget), so the admission math is untouched; greedy outputs are
byte-identical with speculation on or off.

The block allocator is host-side Python (it runs between steps, not inside
the program); admission reserves a request's worst-case PRIVATE block need
up front so a mid-flight step can never hit pool exhaustion.

**Tensor parallelism**: with ``tp > 1`` (``FLAGS_engine_tp_degree`` or the
``tp=`` kwarg) the engine shards itself over a single-axis ``['tp']`` device
mesh (``distributed/tp.py``): attention heads and the paged KV pool
partition per device along the HEAD dim (one logical block id maps to the
same slot in every shard's pool partition), projections/MLP split
Megatron-style with one all-reduce per layer, and the lm-head shards over
vocab (sharded argmax — byte-identical greedy outputs). Sharding is carried
entirely by INPUT placements (committed params and caches), so the step
still compiles exactly ONCE; the scheduler, block tables, prefix-cache
chain hashes and refcounts are host-side state and stay
replicated-by-construction — the prefix cache and speculative decoding
ride along unchanged. ``tp=1`` (the default) takes the exact single-chip
path.

**Recurrent state beside pages**: a model whose ``config.cache_sets`` names
RECURRENT sets (state-space blocks; ``inference/paged_kv.py::RecurrentState``)
gets them as ``[max_slots, ...]`` planes owned beside the pool, handed through
the step after the paged sets and donated like them; the step that carries a
request's first chunk zeroes the slot's state, and ``recover()``'s replay
rebuilds it. What would need a snapshot of that state refuses instead of
serving wrong tokens: prefix reuse is skipped and counted, and
``spec_decode``, the host KV tier and ``tp > 1`` raise at construction. A model
without ``cache_sets`` holds ``config.num_kv_sets`` paged sets and its step is
the program it always was.

**Paged sets of another shape**: a PAGED set whose ``CacheSet.owner`` is a class
beside ``PagedKV`` (``LatentKV``: one latent row a token) gets its planes from
that class (``_new_pools``), under the same block tables, admission, prefix
chains and copy-on-write; the pool's bytes a token, the ``step_logits`` scratch
pool and ``recover()`` ask the set. What knows ``(key, value)`` planes only
raises for such a set at construction: ``kv_cache_dtype="int8"``, the host KV
tier and ``tp > 1``.

Fault tolerance: because every request's prompt and generated tokens live on
the host (``InferenceRequest``), a dispatch failure that consumed the
donated KV buffers is recoverable — ``step()`` retries with backoff through
``recover()``, which rebuilds the pools (and a FRESH prefix cache: the old
chain nodes point at lost KV) and replays every live slot from host truth
through the SAME compiled program (see README "Fault tolerance"). Only
exhausted retries mark the engine permanently failed.
"""

from __future__ import annotations

import functools
import itertools
import sys
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.spmd import partitioned_trace
from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.inference.kv_tier import HostKVTier, HostNode
from paddle_tpu.incubate.nn.functional.fused_moe import collect_expert_counts
from paddle_tpu.inference.paged_kv import PAGED, RECURRENT, CacheSet, PagedBatch, PagedKV, RecurrentState
from paddle_tpu.inference.prefix_cache import ChainNode, PrefixCache, chain_digest
from paddle_tpu.inference.spec_decode import NGramDrafter, count_accepted
from paddle_tpu.observability import devprof as _devprof
from paddle_tpu.observability import flight_recorder as _flight
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.recompile import (
    CAUSE_FIRST_CALL,
    CAUSE_NEW_SHAPE_DTYPE,
    GLOBAL_WATCHDOG,
)
from paddle_tpu.testing.faults import InjectedFault, fault_point

# stall accounting (ContinuousBatchingEngine.close_step): a step is a stall
# when its wall time passes _STALL_FACTOR x the median of the last
# _STALL_WINDOW steps; the median is refreshed every _STALL_REFRESH steps
_STALL_WINDOW = 64
_STALL_FACTOR = 5.0
_STALL_REFRESH = 16

__all__ = [
    "AdmissionPolicy",
    "ContinuousBatchingEngine",
    "EmptyPromptError",
    "FIFOAdmission",
    "InferenceRequest",
    "IntakeError",
    "InvalidTokenBudgetError",
    "PromptTooLongError",
    "RequestTooLongError",
    "RequestUnservableError",
]


class IntakeError(ValueError):
    """A request rejected at intake (validation), before any device work.

    Subclasses ``ValueError`` for backward compatibility with callers that
    ``except ValueError`` around :meth:`ContinuousBatchingEngine.add_request`;
    the typed subclasses exist so a serving layer can map each failure to an
    HTTP 4xx without string-matching the message."""


class EmptyPromptError(IntakeError):
    """The prompt has zero tokens."""


class InvalidTokenBudgetError(IntakeError):
    """``max_new_tokens`` is not a positive integer."""


class PromptTooLongError(IntakeError):
    """The prompt does not fit the configured ``prompt_bucket`` intake cap."""


class RequestTooLongError(IntakeError):
    """prompt + ``max_new_tokens`` exceeds ``max_model_len``."""


class RequestUnservableError(IntakeError):
    """Worst-case KV demand exceeds the whole pool — no eviction can ever
    make room, so the request would wedge the FIFO head forever."""


def _engine_metrics() -> Dict[str, Any]:
    """Get-or-create the engine metric families (process-global: every engine
    in the process reports into the same Prometheus-style families)."""
    reg = _obs.GLOBAL_METRICS
    return {
        "ttft": reg.histogram(
            "engine_ttft_seconds",
            "Time from add_request to the request's first generated token.",
        ),
        "step": reg.histogram(
            "engine_decode_step_seconds",
            "Latency of one unified step over all active slots (incl. host sync).",
        ),
        "admitted": reg.counter(
            "engine_requests_admitted_total",
            "Requests admitted into a slot (prefill started).",
        ),
        "finished": reg.counter(
            "engine_requests_finished_total",
            "Requests finished, by finish reason.",
            labelnames=("reason",),
        ),
        "evicted": reg.counter(
            "engine_slots_evicted_total",
            "Slot evictions: a finished sequence's KV blocks reclaimed to the pool.",
        ),
        "queue": reg.gauge(
            "engine_queue_depth", "Requests waiting for a slot (FIFO)."
        ),
        "active": reg.gauge(
            "engine_active_slots", "Slots holding a live (mid-decode) request."
        ),
        "blocks_alloc": reg.gauge(
            "engine_kv_blocks_allocated", "KV pool blocks currently allocated."
        ),
        "blocks_free": reg.gauge(
            "engine_kv_blocks_free", "KV pool blocks currently free."
        ),
        "blocks_reserved": reg.gauge(
            "engine_kv_blocks_reserved",
            "Worst-case private blocks reserved by live sequences (admission guarantee).",
        ),
        "recoveries": reg.counter(
            "engine_recoveries_total",
            "Step recoveries: KV buffers reallocated and live requests "
            "replayed after a dispatch failure consumed the donated caches.",
        ),
        "replayed": reg.counter(
            "engine_requests_replayed_total",
            "Live requests re-prefilled and replayed from host-side truth "
            "during a recovery.",
        ),
        "util": reg.gauge(
            "engine_kv_pool_utilization",
            "Blocks held by LIVE work / total, 0..1 (evictable cached blocks "
            "excluded); high-water mark tracked since reset.",
        ),
        "prefill_tokens": reg.counter(
            "engine_prefill_tokens_computed_total",
            "Prompt tokens actually computed by prefill chunks (cache hits "
            "are NOT counted here — the shared-prefix honesty counter).",
        ),
        "spec_drafted": reg.counter(
            "spec_decode_drafted_tokens_total",
            "Draft tokens proposed by the speculative drafter and scored by "
            "the unified step.",
        ),
        "spec_accepted": reg.counter(
            "spec_decode_accepted_tokens_total",
            "Draft tokens the step's greedy argmax agreed with (committed in "
            "bulk; their KV was written by the verifying step itself).",
        ),
        "spec_rejected": reg.counter(
            "spec_decode_rejected_tokens_total",
            "Draft tokens discarded at the first disagreement (KV rewound by "
            "block-table truncation).",
        ),
        "spec_accept_rate": reg.histogram(
            "spec_decode_acceptance_rate",
            "Per-speculated-step acceptance fraction: accepted / drafted "
            "(1.0 = the whole draft committed).",
        ),
        "kv_bytes_per_token": reg.gauge(
            "kv_pool_bytes_per_token",
            "Effective KV-pool bytes stored per token across all layers "
            "(int8 pools count the payload plus their fp32 scale bytes).",
        ),
        "state_bytes_per_slot": reg.gauge(
            "recurrent_state_bytes_per_slot",
            "Bytes of recurrent state (scan state and conv tail, every "
            "state-space block) one slot holds; 0 for a model without any.",
        ),
        "kv_quant": reg.counter(
            "kv_quant_dequant_total",
            "Quantized-KV plane traffic attributed per successful step: "
            "'quant' counts tokens quantized on write, 'dequant' counts "
            "slot block-walks dequantizing on read. Always 0 under bf16.",
            labelnames=("op",),
        ),
    }


def _prefetch_fold(kc, vc, dst, hk, hv):
    """One prefetched block's H2D landing: write host-tier KV planes into
    pool slot ``dst`` of one layer's (key, value) pair. Jitted per engine
    with the committed pool sharding pinned as ``out_shardings`` under tp —
    ONE tiny compiled signature regardless of how many blocks land, and the
    dispatch is asynchronous: the host returns immediately and the copy
    overlaps with other slots' compute already in the device queue. Every
    later step consumes the returned arrays, so a chunk can never read a
    block the copy has not reached — the scheduler's prefetch gate is an
    overlap optimization on top of that ordering, not the correctness.

    The third output is the gate MARKER: a scalar dependent on the updated
    cache, so its readiness implies this program (and by stream order every
    earlier fold) has executed. The gate must poll this and never a cache
    array itself — the caches are donated to the next step (or next fold)
    on TPU, and polling a consumed buffer raises; the scalar is retained
    only by the gate, so nothing can ever donate it away."""
    kc = kc.at[dst].set(hk.astype(kc.dtype))
    vc = vc.at[dst].set(hv.astype(vc.dtype))
    return kc, vc, kc[dst, 0, 0, 0]


def _prefetch_fold_q(kc, vc, ks, vs, dst, hk, hv, hks, hvs):
    """Quantized-tier variant of :func:`_prefetch_fold`: a host block
    carries int8 KV planes plus their fp32 scale rows, and all four pool
    planes land in ONE program — the scale rows can never lag the payload
    they dequantize. Same marker discipline (scalar from the updated key
    plane; the scale planes are earlier outputs of the same program, so the
    marker's readiness implies theirs)."""
    kc = kc.at[dst].set(hk.astype(kc.dtype))
    vc = vc.at[dst].set(hv.astype(vc.dtype))
    ks = ks.at[dst].set(hks.astype(ks.dtype))
    vs = vs.at[dst].set(hvs.astype(vs.dtype))
    return kc, vc, ks, vs, kc[dst, 0, 0, 0]


class InferenceRequest:
    """One queued generation request and, after finishing, its result.

    ``priority`` / ``tenant`` / ``deadline`` are scheduling metadata consumed
    by admission policies and the serving layer; the engine itself only acts
    on ``deadline`` (an absolute ``time.perf_counter()`` instant): a request
    whose deadline passes while queued is shed before its prefill runs, and
    one that expires mid-decode is evicted with its blocks reclaimed —
    ``finish_reason == "deadline"`` either way."""

    def __init__(
        self,
        req_id: int,
        prompt: np.ndarray,
        max_new_tokens: int,
        eos_token_id: Optional[int],
        priority: int = 1,
        tenant: str = "default",
        deadline: Optional[float] = None,
    ) -> None:
        self.req_id = req_id
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.priority = int(priority)
        self.tenant = str(tenant)
        self.deadline = None if deadline is None else float(deadline)
        self.generated: List[int] = []
        # "stop" | "length" | "deadline" | a cancel_request() reason
        self.finish_reason: Optional[str] = None
        self.arrival_time = time.perf_counter()  # TTFT anchor
        self.admit_time: Optional[float] = None  # None until the first token
        # prompt tokens served from the prefix cache at admission (0 = cold)
        self.cached_tokens = 0
        # lifecycle timestamps the tracing layer turns into phase spans at
        # terminal time (plain floats — kept regardless of sampling)
        self.prefill_start: Optional[float] = None
        self.finish_wall: Optional[float] = None
        # sampled trace context (observability.tracing.TraceContext) set by
        # the serving frontend; None = this request is not traced
        self.trace: Optional[Any] = None
        # decode attribution: in a continuous batch a request's decode time
        # is its share of the batched steps it rode — accumulated only while
        # tracing is enabled (one cached-bool read per STEP, not per request)
        self.decode_steps = 0
        self.decode_share_s = 0.0

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (time.perf_counter() if now is None else now) >= self.deadline

    def tokens(self) -> np.ndarray:
        """Prompt + generated tokens, the ``generate_paged`` layout."""
        return np.concatenate([self.prompt, np.asarray(self.generated, np.int32)])


class AdmissionPolicy:
    """Pluggable admission order for the engine's waiting queue.

    :meth:`select` is called while a free slot exists; it returns the next
    request to admit or None to stop admitting this boundary. Contract: the
    returned request must be drawn from ``waiting`` and must satisfy
    ``can_fit`` (the engine validates both — a buggy policy fails loudly
    instead of corrupting the worst-case reservation invariant). Returning
    None even though requests fit is allowed (e.g. a pacing policy)."""

    def select(
        self,
        waiting: Sequence["InferenceRequest"],
        can_fit: Callable[["InferenceRequest"], bool],
    ) -> Optional["InferenceRequest"]:
        raise NotImplementedError


class FIFOAdmission(AdmissionPolicy):
    """Strict arrival order with no head-of-line skipping: if the head does
    not fit the pool's unreserved blocks, nothing is admitted — a large
    request can never be starved by smaller ones arriving behind it. This is
    the engine's historical default behavior."""

    def select(
        self,
        waiting: Sequence["InferenceRequest"],
        can_fit: Callable[["InferenceRequest"], bool],
    ) -> Optional["InferenceRequest"]:
        if waiting and can_fit(waiting[0]):
            return waiting[0]
        return None


class ContinuousBatchingEngine:
    """Host-side scheduler driving ONE jitted unified prefill/decode step.

    ``max_slots`` bounds the live batch; ``num_blocks`` sizes the global KV
    pool shared by all slots; ``prompt_bucket`` is the intake cap on prompt
    length (prompts are chunked — the bucket no longer shapes any compiled
    program); ``prefill_chunk`` is the chunk width ``C`` of the unified
    ``[max_slots, C]`` step (default: one KV block).
    """

    def __init__(
        self,
        model: Any,
        max_slots: int = 4,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prompt_bucket: int = 32,
        max_model_len: Optional[int] = None,
        max_recoveries: int = 2,
        recovery_backoff: float = 0.05,
        admission_policy: Optional[AdmissionPolicy] = None,
        prefill_chunk: Optional[int] = None,
        enable_prefix_cache: Optional[bool] = None,
        spec_decode: Optional[bool] = None,
        tp: Optional[int] = None,
        kv_host_tier_bytes: Optional[int] = None,
        kv_cache_dtype: Optional[str] = None,
        weight_only_int8: Optional[bool] = None,
    ) -> None:
        from paddle_tpu.incubate.nn.functional import BlockKVCache

        cfg = model.config
        self.model = model
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.prompt_bucket = int(prompt_bucket)
        self.prefill_chunk = int(prefill_chunk or self.block_size)
        if self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        self.max_model_len = int(
            max_model_len
            or getattr(cfg, "max_position_embeddings", None)
            or self.prompt_bucket * 4
        )
        if self.prompt_bucket > self.max_model_len:
            raise ValueError(
                f"prompt_bucket ({self.prompt_bucket}) exceeds max_model_len "
                f"({self.max_model_len})"
            )
        self.max_blocks_per_seq = -(-self.max_model_len // self.block_size)
        self.num_blocks = int(
            num_blocks if num_blocks is not None
            else self.max_slots * self.max_blocks_per_seq
        )

        kvh = cfg.num_key_value_heads
        hd = getattr(cfg, "head_dim", None) or cfg.hidden_size // cfg.num_attention_heads
        # KV sets a token holds, and so cache planes this engine owns: the
        # model's to say (a looped stack holds passes x layers); every seam
        # that moves "a token's KV" walks this many planes
        self._num_kv_sets = int(getattr(cfg, "num_kv_sets", cfg.num_hidden_layers))
        # a model that keeps more than paged KV says so, set by set in block
        # order (``config.cache_sets``): RECURRENT sets are ``[max_slots,
        # ...]`` planes owned beside the pool, handed through the step after
        # the paged sets and zeroed by the step that admits into a slot
        sets = list(getattr(cfg, "cache_sets", None) or ())
        self._set_kinds: List[str] = [cs.kind for cs in sets]
        self._state_specs = [cs for cs in sets if cs.kind == RECURRENT]
        if sets and self._set_kinds.count(PAGED) != self._num_kv_sets:
            raise ValueError(
                f"config.cache_sets names {self._set_kinds.count(PAGED)} paged sets, "
                f"config.num_kv_sets says {self._num_kv_sets}"
            )
        # passes of the stack a step runs (the loop_passes counter): the
        # model's to say too; once unless its config says otherwise
        self._stack_passes = int(getattr(cfg, "stack_passes", 1))
        dtype = next(iter(model.parameters())).dtype
        # cache geometry, kept so recover() can rebuild identical buffers
        # (identical shapes/dtypes/shardings -> the compiled program is reused)
        self._kvh, self._hd, self._cache_dtype = kvh, hd, dtype
        self._cache_shape = (self.num_blocks, kvh, self.block_size, hd)
        # what each PAGED set keeps a token, in block order: the model's to say
        # (a class beside PagedKV owns planes of another shape: LatentKV's one
        # latent row), else keys and values of (kvh, hd). The pool's shape, a
        # token's bytes, the scratch pool, recover() and the step's typed sets
        # ask these; what follows for the int8 pool, the host tier and tp=
        # knows PagedKV's planes only and refuses another owner below
        self._paged_specs: List[CacheSet] = [cs for cs in sets if cs.kind == PAGED] or [
            CacheSet(PAGED, (((kvh, hd), dtype),) * 2)
        ] * self._num_kv_sets
        self._paged_owners = [cs.owner or PagedKV for cs in self._paged_specs]
        self._owned_sets = any(owner is not PagedKV for owner in self._paged_owners)
        # quantized KV plane (FLAGS_kv_cache_dtype="int8"): the pool stores
        # int8 blocks plus per-block-per-head-per-token fp32 scale planes
        # [NB, KVH, BS] addressed by the SAME physical block ids — every
        # lifecycle seam (refcount, CoW, spill/prefetch, recovery replay, tp
        # head-sharding) moves cache rows and scale rows together. "bf16"
        # (the default) leaves the whole plane byte-identical to the
        # unquantized engine: no scale planes exist anywhere.
        kvd = str(
            GLOBAL_FLAGS.get("kv_cache_dtype")
            if kv_cache_dtype is None
            else kv_cache_dtype
        )
        if kvd not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bf16' or 'int8', got {kvd!r}"
            )
        self.kv_cache_dtype = kvd
        self._quant_kv = kvd == "int8"
        if self._quant_kv and self._owned_sets:
            raise ValueError(
                "kv_cache_dtype='int8' cannot serve a model whose paged sets are not PagedKV "
                "(latent rows): the scale planes are per (token, KV head) of a key / value pair"
            )
        if self._quant_kv:
            self._cache_dtype = jnp.int8
        self._scale_shape = (self.num_blocks, kvh, self.block_size)
        # weight-only int8 (FLAGS_weight_only_int8): quantize the MLP and
        # lm-head projection weights IN PLACE, before tp sharding, so the
        # per-output-channel scales are computed over the FULL contraction
        # dim — under GSPMD the replicated [N] scale row next to the sharded
        # int8 weight is then globally exact. Inference-only (serving owns
        # the model); the scales become extra step operands below.
        wq = bool(
            GLOBAL_FLAGS.get("weight_only_int8")
            if weight_only_int8 is None
            else weight_only_int8
        )
        self._wq_params: List[Any] = []
        if wq:
            from paddle_tpu.kernels.quant import quantize_module_weights

            self._wq_params = quantize_module_weights(model)
        # tensor parallelism: commit params + caches onto a ['tp'] mesh; the
        # sharding lives in input PLACEMENTS, never in shapes, so the one
        # compiled signature (and every host-side invariant) is unchanged
        self.tp = int(GLOBAL_FLAGS.get("engine_tp_degree") if tp is None else tp)
        if self.tp < 1:
            raise ValueError(f"engine tp degree must be >= 1, got {self.tp}")
        if self.tp > 1 and self._state_specs:
            raise ValueError(
                "tp > 1 cannot serve a model with recurrent state sets yet: nothing shards "
                "a state plane, the scan or an expert share over the 'tp' mesh"
            )
        if self.tp > 1 and self._owned_sets:
            raise ValueError(
                "tp > 1 cannot serve a model whose paged sets are not PagedKV (latent rows) yet: "
                "the pool is sharded over KV heads, and a latent row has none"
            )
        if self.tp > 1:
            from paddle_tpu.distributed.tp import (
                build_tp_mesh,
                kv_cache_sharding,
                shard_model_params,
                validate_tp,
            )

            validate_tp(self.tp, cfg.num_attention_heads, kvh)
            self._tp_mesh = build_tp_mesh(self.tp)
            self._cache_sharding = kv_cache_sharding(self._tp_mesh)
            # sharded zeros created directly on-device, each device only its
            # own shard: the full pool never exists anywhere (not host RAM,
            # not chip 0) — num_blocks is sized to the AGGREGATE HBM, and
            # recover() reallocates through this too. One tiny compiled
            # zeros program reused for every layer's k and v.
            self._shard_zeros = jax.jit(
                lambda: jnp.zeros(self._cache_shape, self._cache_dtype),
                out_shardings=self._cache_sharding,
            )
            if self._quant_kv:
                from jax.sharding import NamedSharding, PartitionSpec

                # scale planes shard on the SAME head axis as the caches:
                # every shard owns the scales for exactly its head slice
                self._scale_sharding = NamedSharding(
                    self._tp_mesh, PartitionSpec(None, "tp", None)
                )
                # ones, not zeros: quantize(zeros) -> q=0, scale=1, so an
                # empty quantized pool dequantizes to exact zeros
                self._shard_zeros_scale = jax.jit(
                    lambda: jnp.ones(self._scale_shape, jnp.float32),
                    out_shardings=self._scale_sharding,
                )
            else:
                self._scale_sharding = None
            # serving owns the model: params are committed onto the shard
            # group in place (Megatron column/row splits, vocab-parallel
            # embedding + lm-head)
            self._tp_split_params = shard_model_params(model, self._tp_mesh)
        else:
            self._tp_mesh = None
            self._cache_sharding = None
            self._scale_sharding = None
            self._tp_split_params = 0
        # host-side refcounted block pool; the device pool lives below
        self._mgr = BlockKVCache(
            self.num_blocks, self.block_size, kvh, hd,
            self.max_blocks_per_seq, dtype=self._cache_dtype,
        )
        self._use_prefix_cache = bool(
            GLOBAL_FLAGS.get("enable_prefix_cache")
            if enable_prefix_cache is None
            else enable_prefix_cache
        )
        # hierarchical KV: a bounded host-RAM tier under the prefix cache —
        # evicted chains spill D2H instead of dying, matches against spilled
        # chains prefetch H2D overlapped into chunked prefill. 0 = off =
        # pre-tier behavior; the tier rides the prefix cache, so it is inert
        # when the cache is disabled. The tier object SURVIVES recover()
        # (host RAM is not lost with the device pools — it is the host
        # truth recovery rebuilds from).
        tier_bytes = int(
            GLOBAL_FLAGS.get("kv_host_tier_bytes")
            if kv_host_tier_bytes is None
            else kv_host_tier_bytes
        )
        # what cannot carry recurrent state REFUSES (a prefix hit, a rewind
        # or a spilled slot would need a snapshot of the state at that token:
        # ROADMAP M5-rest). Prefix reuse is on by default, so it is skipped and
        # counted (``prefix_reuse_skipped_recurrent``, an admission that went
        # without a lookup); what has to be asked for raises.
        self._prefix_reuse_refused = bool(self._state_specs) and self._use_prefix_cache
        if self._state_specs:
            self._use_prefix_cache = False
            if tier_bytes > 0:
                raise ValueError(
                    "kv_host_tier_bytes > 0 cannot serve a model with recurrent state sets: a "
                    "spilled chain holds pages only, not the state at its end"
                )
        if tier_bytes > 0 and self._owned_sets:
            raise ValueError(
                "kv_host_tier_bytes > 0 cannot serve a model whose paged sets are not PagedKV "
                "(latent rows): a spilled block is captured and landed as (key, value) planes"
            )
        self._host_tier: Optional[HostKVTier] = None
        if tier_bytes > 0 and self._use_prefix_cache:
            self._host_tier = HostKVTier(
                tier_bytes, self._bytes_per_token() * self.block_size
            )
            # the H2D landing copy: one compiled signature per engine
            # (scalar dst + one block's [KVH, BS, D] planes), kept OFF the
            # step's watchdog ledger — prefetch is data movement, not a new
            # step signature. Donation matters on TPU (the pool must not
            # transiently double); on CPU it is a warning no-op, so skip.
            fold_kw: Dict[str, Any] = {}
            if self._cache_sharding is not None:
                # preserve the committed pool partition: a GSPMD-inferred
                # output sharding would differ from the committed inputs and
                # silently compile a SECOND step executable. The scalar gate
                # marker is replicated (it is host-polled every boundary).
                from jax.sharding import NamedSharding, PartitionSpec

                repl = NamedSharding(self._tp_mesh, PartitionSpec())
                if self._quant_kv:
                    fold_kw["out_shardings"] = (
                        self._cache_sharding, self._cache_sharding,
                        self._scale_sharding, self._scale_sharding, repl,
                    )
                else:
                    fold_kw["out_shardings"] = (
                        self._cache_sharding, self._cache_sharding, repl,
                    )
            fold_impl = _prefetch_fold_q if self._quant_kv else _prefetch_fold
            fold_donate: Tuple[int, ...] = ()
            if jax.default_backend() != "cpu":
                fold_donate = (0, 1, 2, 3) if self._quant_kv else (0, 1)
            self._fold_fn = jax.jit(
                fold_impl, donate_argnums=fold_donate, **fold_kw
            )
        # per-slot prefetch gate: (marker_array, n_blocks, tokens) while an
        # H2D prefetch is in flight — the slot contributes NO rows to the
        # mixed step until the copies land (correctness is guaranteed by
        # dataflow either way; the gate is what buys the overlap: other
        # slots' chunks run while this slot's blocks are still in transit)
        self._prefetch_wait: List[Optional[Tuple[Any, int, int]]] = (
            [None] * self.max_slots
        )
        # replica observability scope: unscoped by default (single-engine
        # processes record exactly as before); a cluster replica re-binds
        # via set_replica_scope() at replica construction. Set BEFORE the
        # prefix cache exists — _new_prefix_cache() consults the scope.
        self._flight = _flight.GLOBAL_FLIGHT_RECORDER
        self._metrics_scope: Optional[_obs.MetricScope] = None
        self.replica_name: Optional[str] = None
        self._cache = self._new_prefix_cache()
        # speculative decoding: drafts ride the step's chunk axis, so the
        # draft width is capped at prefill_chunk - 1 (one row is always the
        # real last token); a 1-wide chunk cannot carry a draft at all
        self._use_spec = bool(
            GLOBAL_FLAGS.get("spec_decode") if spec_decode is None else spec_decode
        )
        self._spec_k = min(
            int(GLOBAL_FLAGS.get("spec_decode_tokens")), self.prefill_chunk - 1
        )
        if self._spec_k < 1:
            self._use_spec = False
        if self._use_spec and self._state_specs:
            raise ValueError(
                "spec_decode cannot serve a model with recurrent state sets: a rejected draft "
                "rewinds pages by truncation, and the scan's state cannot be rewound"
            )
        self._drafter = (
            NGramDrafter(int(GLOBAL_FLAGS.get("spec_decode_ngram")))
            if self._use_spec
            else None
        )
        # ONE global paged pool shared by every layer's sequences would alias
        # writes across layers — each KV set (a layer; a layer in one pass of
        # a looped stack) owns its [NB, KVH, BS, D] pair, all indexed by the
        # SAME block tables (the reference layout).
        self._caches = self._new_pools()
        self._states = self._new_states()

        # per-slot host state (rewritten freely between steps — it is DATA to
        # the compiled step, never part of its shape)
        self._slot_req: List[Optional[InferenceRequest]] = [None] * self.max_slots
        self._blocks: List[List[int]] = [[] for _ in range(self.max_slots)]
        # leading prefix of _blocks owned by cache chain nodes (refs held);
        # invariant: _nodes[s][i].block == _blocks[s][i]
        self._nodes: List[List[ChainNode]] = [[] for _ in range(self.max_slots)]
        self._no_insert = [False] * self.max_slots  # stop chain growth (race)
        self._matched_blocks = np.zeros((self.max_slots,), np.int64)  # at admit
        self._pending_cow: List[Optional[Tuple[ChainNode, int, int]]] = (
            [None] * self.max_slots
        )
        self._ntok = np.zeros((self.max_slots,), np.int32)  # tokens in pool
        self._last_tok = np.zeros((self.max_slots,), np.int32)
        self._reserved = np.zeros((self.max_slots,), np.int64)  # worst case
        self._waiting: deque = deque()
        self._ids = itertools.count()
        self._policy: AdmissionPolicy = admission_policy or FIFOAdmission()

        self._named = list(model.named_parameters())
        self.stats = {
            "step_traces": 0, "steps": 0, "admitted": 0, "recoveries": 0,
            "prompt_tokens_computed": 0, "prompt_tokens_reused": 0,
            "spec_steps": 0, "spec_drafted": 0, "spec_accepted": 0,
            "spec_rejected": 0, "gen_blocks_registered": 0,
            # seconds inside each phase of the serving step, cumulative
            # (observability/tracing.py phase; deliver is the frontend's)
            "phase_s.plan": 0.0, "phase_s.launch": 0.0, "phase_s.wait": 0.0,
            "phase_s.commit": 0.0, "phase_s.deliver": 0.0,
            # engine.launch and engine.wait tiled into sub-phases: the three
            # launch_* sum to phase_s.launch, the two wait_* to phase_s.wait
            # (not under "phase_s.": those five keys tile the pump alone)
            "subphase_s.launch_put": 0.0, "subphase_s.launch_args": 0.0,
            "subphase_s.launch_call": 0.0, "subphase_s.wait_ready": 0.0,
            "subphase_s.wait_fetch": 0.0,
            # close_step(): steps far above the running median, and what of
            # their excess lay on the host and in the wait for the device
            "stall_steps": 0, "stall_s.host": 0.0, "stall_s.device": 0.0,
            # KV pages the paged kernel's length-bounded walk visits, a layer:
            # sum over a step's active slots of ceil((cached + new) / block)
            "paged_pages_walked": 0,
            # (query row, key) pairs an attention call of a step has to score:
            # sum over a step's live rows of the tokens each may see (row j of
            # a slot: cached + j + 1); what a walk's least work is counted from
            "attn_row_keys": 0,
            # passes of the model's stack run, summed over steps (one a step
            # unless the stack is looped); gauges: the KV sets a token holds
            # and their bytes
            "loop_passes": 0, "kv_sets": self._num_kv_sets,
            "kv_bytes_per_token": self._bytes_per_token(),
            # recurrent sets beside the pages and what one slot holds in
            # them; experts an expert layer holds (gauges: the model's own)
            "state_sets": len(self._state_specs),
            "state_bytes_per_slot": self._state_bytes_per_slot(),
            "experts_held": int(getattr(cfg, "n_routed_experts", 0)),
            # a step's expert blocks, summed: assignments that landed on held
            # experts, and held experts that got at least one row (from the
            # small vector the step of a model with expert blocks hands back)
            "moe_rows_local": 0, "moe_experts_hit": 0,
            # admissions that went without a prefix lookup because the model
            # keeps recurrent state
            "prefix_reuse_skipped_recurrent": 0,
            # steps at whose planning a waiting request was held back: for
            # want of blocks with a slot free / for want of a slot
            "admit_blocked_steps.blocks": 0, "admit_blocked_steps.slots": 0,
        }
        self._admit_blocked: Optional[str] = None  # the step being planned
        self._metrics = _engine_metrics()
        self._update_pool_gauges()
        # On donating backends (TPU) a step that fails AFTER dispatch has
        # already consumed the donated cache buffers: allocator accounting is
        # rolled back, but the KV contents are unrecoverable. step() then
        # runs recover() — reallocate the pools and replay every live slot
        # from host-side truth — up to ``max_recoveries`` times (exponential
        # ``recovery_backoff`` between attempts) before marking the engine
        # PERMANENTLY failed. On CPU (no donation) a failed step leaves the
        # buffers intact and is safely retryable by the caller, so no
        # recovery runs. ``_broken`` means permanently failed only.
        self._broken = False
        self.max_recoveries = int(max_recoveries)
        self.recovery_backoff = float(recovery_backoff)
        # finished requests awaiting delivery: survives a failed attempt so
        # a request that finished before the dispatch died is still delivered
        # exactly once by the step() that succeeds
        self._pending_done: List[InferenceRequest] = []
        # per-engine "first successful compile recorded" marker: the watchdog
        # attributes each engine instance's initial trace as first_call
        self._step_recorded = False
        donate = jax.default_backend() != "cpu"  # donation warns (no-op) on cpu
        if self._tp_mesh is not None:
            # pin the OUTPUT shardings: without this the returned caches
            # carry GSPMD-inferred sharding objects that hash differently
            # from the device_put-committed inputs, and the second step
            # would compile a second executable for the same trace — the
            # silent 2x-compile the 1-compile invariant exists to catch.
            # argmax output replicated (it is host-synced every step);
            # caches come back on exactly the pool partition they went in.
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._tp_mesh, PartitionSpec())
            cs = self._cache_sharding
            if self._quant_kv:
                ss = self._scale_sharding
                cache_sh = [(cs, cs, ss, ss)] * self._num_kv_sets
            else:
                cache_sh = [(cs, cs)] * self._num_kv_sets
            self._step_fn = jax.jit(
                self._step_impl,
                donate_argnums=(1,) if donate else (),
                out_shardings=(repl, cache_sh),
            )
        else:
            self._step_fn = jax.jit(
                self._step_impl, donate_argnums=(1,) if donate else ()
            )
        # device-time attribution (observability/devprof.py): deterministic
        # stride sampler + bounded step-timeline ring per engine; _marks is
        # non-None only while a SAMPLED step's dispatch is in flight (the
        # off path through _dispatch reads one attribute, nothing else).
        # The analytic attribution-prior hints are flop-denominated over the
        # PADDED step shape — the compiled program computes all S*C rows and
        # walks tables bounded by max_model_len, which is what the XLA cost
        # model prices too.
        self._devprof_gate = _devprof.SampleGate()
        self._devprof_timeline = _devprof.StepTimeline()
        # stall accounting (close_step): the open step's phase seconds, the
        # last _STALL_WINDOW steps' (host, wait) seconds, and the limit
        self._open_step: Optional[Tuple[float, ...]] = None
        # the serving step's open phase (None outside a step: recovery
        # replays are untimed) and the ones it has been through; likewise
        # the open phase's open sub-phase and the step's finished ones
        self._phase: Any = None
        self._phases_done: List[Any] = []
        self._subphase: Any = None
        self._subphases_done: List[Any] = []
        # where the last step's commit phase ended (perf_counter): a driver's
        # own phase starts there, so nothing lies between the two
        self.last_step_end_s: Optional[float] = None
        self._step_compiled = False
        self._step_walls: Deque[Tuple[float, float]] = deque(maxlen=_STALL_WINDOW)
        self._stall_limit: Optional[float] = None
        self._stall_age = 0
        self._close_mark: Optional[Tuple[float, float]] = None  # (perf_counter, thread_time) at the last close
        from paddle_tpu.distributed.tp import analytic_cost_hints

        self._devprof_hints = analytic_cost_hints(
            num_layers=self._num_kv_sets,  # layer bodies a step runs
            hidden=cfg.hidden_size,
            intermediate=getattr(cfg, "intermediate_size", 4 * cfg.hidden_size),
            vocab=getattr(cfg, "vocab_size", 0),
            tokens=self.max_slots * self.prefill_chunk,
            kv_len=self.max_model_len,
            tp=self.tp,
            dtype_bytes=jnp.dtype(self._cache_dtype).itemsize,
        )

    def _new_pools(self, scratch_blocks: Optional[int] = None) -> List[Tuple[Any, ...]]:
        """Every paged set's planes, zeroed: the engine's pool, or with
        ``scratch_blocks`` a scratch pool of that many blocks (``step_logits``).
        A set whose class is the model's (``CacheSet.owner``) makes its own, a
        ``PagedKV`` set is :meth:`_new_cache_pair`."""
        return [
            self._new_cache_pair(scratch_blocks) if owner is PagedKV
            else owner.zeros(scratch_blocks or self.num_blocks, self.block_size, spec).planes
            for spec, owner in zip(self._paged_specs, self._paged_owners)
        ]

    def _new_cache_pair(self, scratch_blocks: Optional[int] = None) -> Tuple[Any, ...]:
        """One layer's (key, value) pool pair — under ``kv_cache_dtype=int8``
        a (key, value, key_scale, value_scale) QUAD, the scale planes
        ``[NB, KVH, BS]`` fp32 initialized to ONES (``quantize(zeros)`` is
        ``q=0, scale=1``, so a fresh pool dequantizes to exact zeros). Under
        a tp mesh everything is committed head-sharded (``[NB, KVH/tp, ...]``
        per shard) — the pool PARTITION: every shard holds the same logical
        block ids for its own head slice, so the host-side allocator needs
        no per-shard state. Same shapes/dtypes/shardings on every call, so
        recover()'s rebuilt pools reuse the compiled program."""
        if scratch_blocks is not None:  # built inside step_logits' trace, plain
            return PagedKV.zeros((scratch_blocks,) + self._cache_shape[1:], self._cache_dtype).planes
        if self._cache_sharding is not None:
            if self._quant_kv:
                return (
                    self._shard_zeros(), self._shard_zeros(),
                    self._shard_zeros_scale(), self._shard_zeros_scale(),
                )
            return self._shard_zeros(), self._shard_zeros()
        return PagedKV.zeros(self._cache_shape, self._cache_dtype).planes

    def _new_states(self) -> List[Tuple[Any, ...]]:
        """The recurrent sets' planes, ``[max_slots, ...]`` each, zeroed."""
        return [RecurrentState.zeros(self.max_slots, spec).planes for spec in self._state_specs]

    def _state_bytes_per_slot(self) -> int:
        return sum(spec.unit_bytes for spec in self._state_specs)

    @property
    def tp_degree(self) -> int:
        """Tensor-parallel degree (1 = single-chip engine)."""
        return self.tp

    def tp_stats(self) -> Dict[str, Any]:
        """Shard-group view for health/observability: the mesh devices and
        the per-shard slice of the KV pool. Per-shard accounting is
        BALANCED by construction — every shard holds the same logical
        blocks over its equal head slice — and this reports the device
        truth so a test (or a probe) can hold the claim to the buffers."""
        if self._tp_mesh is None:
            return {"tp_degree": 1}
        kc = self._caches[0][0]
        if getattr(kc, "is_deleted", lambda: False)():
            # a donating backend's failed dispatch consumed the pools; until
            # recover() rebuilds them (or forever, once permanently broken)
            # there is no device truth — /healthz must report, never raise
            return {
                "tp_degree": self.tp,
                "devices": [d.id for d in self._tp_mesh.devices.flat],
                "split_params": self._tp_split_params,
                "per_shard_cache_shape": [],
                "balanced": None,
                "buffers": "lost",
            }
        shards = sorted(
            (s.device.id, list(s.data.shape)) for s in kc.addressable_shards
        )
        per_shard = [shape for _, shape in shards]
        return {
            "tp_degree": self.tp,
            "devices": [d.id for d in self._tp_mesh.devices.flat],
            "split_params": self._tp_split_params,
            "per_shard_cache_shape": per_shard[0] if per_shard else [],
            "balanced": all(s == per_shard[0] for s in per_shard),
        }

    def devprof_stats(self) -> Dict[str, Any]:
        """Device-time attribution summary over this engine's step-timeline
        ring (what /healthz and incident snapshots embed): mean segment
        split, mean per-category device shares, measured comm share with
        its source breakdown. ``{"enabled": False, "sampled_steps": 0}``
        while ``FLAGS_devprof_sample_rate`` is 0 — valid, never raises."""
        return _devprof.summarize_timeline(self._devprof_timeline.entries())

    def _bytes_per_token(self) -> int:
        """KV bytes across all KV sets for one token (sizes the bytes-saved
        gauge and the host tier's per-block cost). Quantized pools count the
        TRUE footprint: the int8 payload plus one fp32 scale per (token,
        head) — ``2·L·KVH·(D+4)`` vs bf16's ``2·L·KVH·2D``, a ``2D/(D+4)``
        reduction (1.94x at D=128)."""
        if self._quant_kv:
            return 2 * self._num_kv_sets * self._kvh * (self._hd + 4)
        return sum(spec.unit_bytes for spec in self._paged_specs)

    def _new_prefix_cache(self) -> Optional[PrefixCache]:
        if not self._use_prefix_cache:
            return None
        cache = PrefixCache(
            self._mgr, self.block_size, self._bytes_per_token(),
            host_tier=self._host_tier,
            capture_kv=(
                self._capture_block_kv if self._host_tier is not None else None
            ),
        )
        if self._metrics_scope is not None:
            # recover() rebuilds a fresh cache: replica attribution survives
            cache.set_replica_scope(self._metrics_scope, self._flight)
        return cache

    def _capture_block_kv(self, block: int) -> np.ndarray:
        """D2H capture of one physical block's KV across every KV set —
        ``[sets, 2, KVH, BS, D]`` — for a spill. Synchronous by design:
        the copy must complete before the block's pool reference drops and
        the slot can be reallocated and overwritten (the caller holds that
        ordering). Under tensor parallelism the head shards gather here —
        the host tier always holds the full-head view."""
        if self._quant_kv:
            # quantized capture: ONE int8 ndarray [L, 2, KVH, BS, D+4] — the
            # fp32 scale rides as 4 trailing bytes per (head, token) row, so
            # the host tier's byte budget sees the true halved footprint and
            # spill/prefetch move payload + scales as one unit
            parts = []
            for kc, vc, ks, vs in self._caches:
                kv = np.asarray(jnp.stack((kc[block], vc[block])))
                sc = np.asarray(
                    jnp.stack((ks[block], vs[block])), dtype=np.float32
                )
                sc_bytes = np.ascontiguousarray(sc[..., None]).view(np.int8)
                parts.append(np.concatenate([kv, sc_bytes], axis=-1))
            return np.stack(parts)
        parts = [
            jnp.stack((kc[block], vc[block])) for kc, vc in self._caches
        ]
        return np.asarray(jnp.stack(parts))

    # -- pool accounting -----------------------------------------------------
    def pool_stats(self) -> Dict[str, Any]:
        free = self._mgr.free_blocks
        return {
            "total": self.num_blocks,
            "free": free,
            "allocated": self.num_blocks - free,
            "kv_cache_dtype": self.kv_cache_dtype,
            "bytes_per_token": self._bytes_per_token(),
            "state_bytes_per_slot": self._state_bytes_per_slot(),
            # blocks the prefix cache retains warm but surrenders under
            # pressure: reclaimable, so admission/overload math treats them
            # as headroom, not load
            "cached_reusable": (
                self._cache.evictable_blocks if self._cache is not None else 0
            ),
            # ALL cache-owned blocks (incl. chain interiors pinned by
            # children): with no live work, free + cached_blocks == total
            "cached_blocks": (
                self._cache.node_count if self._cache is not None else 0
            ),
        }

    def prefix_cache_stats(self) -> Dict[str, Any]:
        """Hit-rate / sharing signals for the serving layer (empty when the
        prefix cache is disabled)."""
        if self._cache is None:
            return {"enabled": False}
        out: Dict[str, Any] = {"enabled": True}
        out.update(self._cache.stats_snapshot())
        return out

    def kv_tier_stats(self) -> Dict[str, Any]:
        """Host-tier view for /healthz and bench records (host counters —
        valid with metrics off; ``{"enabled": False}`` when the tier is
        off, which is also the ``FLAGS_kv_host_tier_bytes=0`` default)."""
        if self._host_tier is None:
            return {"enabled": False}
        out: Dict[str, Any] = {"enabled": True}
        out.update(self._host_tier.stats_snapshot())
        return out

    def _update_pool_gauges(self) -> None:
        """Refresh the pool/queue gauges straight from ``pool_stats()``; called
        at every admit/evict/step boundary. With metrics off this is one
        cached-bool check — the engine's hot path stays unmeasured-free."""
        if not _obs.metrics_enabled():
            return
        s = self.pool_stats()
        m = self._metrics
        m["blocks_alloc"].set(s["allocated"])
        m["blocks_free"].set(s["free"])
        m["kv_bytes_per_token"].set(s["bytes_per_token"])
        m["state_bytes_per_slot"].set(s["state_bytes_per_slot"])
        m["blocks_reserved"].set(int(self._reserved.sum()))
        live = s["allocated"] - s["cached_reusable"]
        m["util"].set(live / s["total"] if s["total"] else 0.0)
        m["queue"].set(len(self._waiting))
        m["active"].set(sum(r is not None for r in self._slot_req))
        if self._cache is not None:
            self._cache.update_shared_gauge()

    def _unreserved_free(self) -> int:
        """Blocks available to new admissions: free + evictable cached,
        minus live sequences' outstanding worst-case PRIVATE growth (shared
        mapped blocks never grow — they are counted at zero)."""
        outstanding = 0
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                private = len(self._blocks[slot]) - int(self._matched_blocks[slot])
                outstanding += int(self._reserved[slot]) - private
        reusable = self._cache.evictable_blocks if self._cache is not None else 0
        return self._mgr.free_blocks + reusable - outstanding

    def _buffers_lost(self) -> bool:
        return any(
            getattr(a, "is_deleted", lambda: False)()
            for entry in self._caches + self._states
            for a in entry
        )

    def _check_usable(self) -> None:
        if self._broken:
            raise RuntimeError(
                "engine KV state was lost and recovery is exhausted (failed "
                "steps consumed the donated cache buffers "
                f"{self.max_recoveries + 1} times); build a new "
                "ContinuousBatchingEngine"
            )

    # -- request intake ------------------------------------------------------
    def validate_request(self, prompt_ids: Any, max_new_tokens: int = 32) -> np.ndarray:
        """Validate one prompt against the engine's static limits WITHOUT
        queueing anything; returns the normalized ``int32`` prompt array.
        Raises a typed :class:`IntakeError` subclass (all are ``ValueError``)
        so a serving front end can map each failure to a 4xx status. Failing
        loudly at intake beats wedging the scheduler."""
        prompt = np.asarray(
            prompt_ids._data if hasattr(prompt_ids, "_data") else prompt_ids,
            np.int32,
        ).reshape(-1)
        if prompt.size < 1:
            raise EmptyPromptError("empty prompt")
        if max_new_tokens < 1:
            raise InvalidTokenBudgetError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size > self.prompt_bucket:
            raise PromptTooLongError(
                f"prompt ({prompt.size} tokens) exceeds prompt_bucket "
                f"({self.prompt_bucket}); configure a larger bucket"
            )
        if prompt.size + max_new_tokens > self.max_model_len:
            raise RequestTooLongError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_model_len ({self.max_model_len})"
            )
        worst = prompt.size + max_new_tokens - 1
        need = -(-worst // self.block_size)
        if need > self.num_blocks:
            # a request no eviction can ever make room for would sit at the
            # FIFO head forever and busy-loop run()
            raise RequestUnservableError(
                f"request needs {need} KV blocks worst-case "
                f"but the pool only has {self.num_blocks}"
            )
        return prompt

    def make_request(
        self,
        prompt_ids: Any,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        priority: int = 1,
        tenant: str = "default",
        deadline: Optional[float] = None,
    ) -> InferenceRequest:
        """Validate and construct (but do not queue) one request — the seam
        a serving layer uses to hold the handle it will stream from."""
        self._check_usable()
        prompt = self.validate_request(prompt_ids, max_new_tokens)
        return InferenceRequest(
            next(self._ids), prompt, max_new_tokens, eos_token_id,
            priority=priority, tenant=tenant, deadline=deadline,
        )

    def enqueue(self, req: InferenceRequest) -> int:
        """Queue a request built by :meth:`make_request`; returns its id.
        Intake stays open while the engine is mid-recovery — recovery is an
        engine-internal condition, not a caller error, so the request simply
        queues; only a PERMANENTLY failed engine (recovery exhausted)
        hard-rejects."""
        self._check_usable()
        self._waiting.append(req)
        self._update_pool_gauges()  # queue depth changed
        return req.req_id

    def add_request(
        self,
        prompt_ids: Any,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        priority: int = 1,
        tenant: str = "default",
        deadline: Optional[float] = None,
    ) -> int:
        """Queue one prompt; returns the request id. Raises a typed
        :class:`IntakeError` on prompts that can never be served (see
        :meth:`validate_request`)."""
        return self.enqueue(
            self.make_request(
                prompt_ids, max_new_tokens, eos_token_id,
                priority=priority, tenant=tenant, deadline=deadline,
            )
        )

    def has_work(self) -> bool:
        return bool(self._waiting) or any(r is not None for r in self._slot_req)

    @property
    def broken(self) -> bool:
        """True once recovery is exhausted and the engine is PERMANENTLY
        failed (a transient, caller-retryable step failure does not set
        this — see :meth:`step`)."""
        return self._broken

    def queue_depth(self) -> int:
        """Requests waiting for a slot (what the queue-depth gauge exports)."""
        return len(self._waiting)

    def prefix_chain_hash(
        self, prompt_ids: Any, max_blocks: Optional[int] = None
    ) -> str:
        """Hex digest of the prompt's block-aligned prefix chain — the same
        rolling blake2b the prefix cache keys chain nodes by, so a router
        keying on this lands requests sharing a prefix on the replica whose
        cache already holds that prefix's KV. ``max_blocks`` caps the walk
        (see :func:`~paddle_tpu.inference.prefix_cache.chain_digest`)."""
        prompt = np.asarray(
            prompt_ids._data if hasattr(prompt_ids, "_data") else prompt_ids,
            np.int32,
        ).reshape(-1)
        return chain_digest(prompt, self.block_size, max_blocks).hex()

    def mark_failed(self, why: str = "externally marked failed") -> None:
        """Administrative seam: flip the engine to PERMANENTLY failed, as if
        recovery were exhausted — every later ``step()``/intake raises. The
        cluster layer's ``replica.kill`` fault site models a whole-process
        replica death through this (the host-side results in
        ``drain_finished()`` stay salvageable, mirroring the pump-death
        seam)."""
        self._broken = True
        self._flight.record("engine_marked_failed", why=str(why)[:200])

    def live_requests(self) -> List[InferenceRequest]:
        """Requests currently holding a slot (mid-decode), slot order."""
        return [r for r in self._slot_req if r is not None]

    def set_admission_policy(self, policy: AdmissionPolicy) -> None:
        """Swap the admission policy (takes effect at the next boundary)."""
        self._policy = policy

    def set_replica_scope(
        self,
        name: str,
        scope: Optional[Any] = None,
        flight: Optional[Any] = None,
    ) -> None:
        """Re-bind this engine's observability to a replica scope, resolved
        ONCE here: every ``engine_*``/``spec_decode_*``/``prefix_cache_*``/
        ``kv_tier_*`` series it records from now on carries a
        ``replica=name`` label (rolling up into the same process-global
        families), and flight events land in a per-replica child ring teed
        into the global black box. Called by the cluster layer at replica
        construction; the per-record cost is unchanged (the same one
        cached-bool read on the metrics-off path)."""
        if scope is None:
            scope = _obs.GLOBAL_METRICS.scope(replica=name)
        if flight is None:
            flight = _flight.GLOBAL_FLIGHT_RECORDER.child(replica=name)
        self.replica_name = str(name)
        self._metrics_scope = scope
        self._metrics = scope.bind_all(_engine_metrics())
        self._flight = flight
        if self._cache is not None:
            self._cache.set_replica_scope(scope, flight)
        if self._host_tier is not None:
            self._host_tier.set_replica_scope(scope)

    def cancel_request(
        self, req_id: int, reason: str = "cancelled"
    ) -> Optional[InferenceRequest]:
        """Targeted eviction: remove ``req_id`` wherever it lives. A queued
        request is dropped before its prefill ever runs; a mid-decode one is
        evicted from its slot with its KV blocks reclaimed immediately. The
        request (``finish_reason = reason``) is returned to THIS caller and
        will NOT also be delivered by step() — exactly-once holds with the
        cancel return value as the one delivery. Returns None when the id is
        unknown (already finished and delivered, or never queued)."""
        for req in self._waiting:
            if req.req_id == req_id:
                self._waiting.remove(req)
                req.finish_reason = reason
                req.finish_wall = time.perf_counter()
                self._flight.record(
                    "shed_queued", req_id=req.req_id, reason=reason
                )
                self._metrics["finished"].labels(reason=reason).inc()
                self._update_pool_gauges()
                return req
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.req_id == req_id:
                req.finish_reason = reason
                self._release(slot, req)
                return req
        return None

    # -- the compiled program (traces exactly ONCE per engine) ---------------
    def _param_arrays(self) -> List[Any]:
        # re-read each call: weight updates after construction are served
        # without retraces (same shapes/dtypes -> same compiled program).
        # Quantized projections contribute their per-output-channel scales
        # as EXTRA operands — the count is fixed per configuration, so the
        # ONE compiled step signature is unchanged.
        return [p._data for _, p in self._named] + [
            p._quant_scale for p in self._wq_params
        ]

    def _step_impl(
        self, param_arrays, caches, toks, tables, lens, q_lens, active,
        cow_src, cow_dst,
    ):
        """The ONE program: ``toks [S, C]`` ragged new tokens per slot
        (decode rows have one valid token, prefill chunks up to C);
        ``tables [S, MBS]``; ``lens`` tokens already cached per slot;
        ``q_lens`` valid new tokens; ``active`` the slot mask; ``cow_*`` the
        copy-on-write fork set (``dst == num_blocks``: no fork). Applies
        pending CoW forks, appends the ragged chunk KV, attends, and returns
        EVERY row's greedy argmax ``[S, C]`` — row ``j`` is the model's next
        token after the row-``j`` input, which is simultaneously the decode
        output (a plain slot reads row 0), the prompt-completion output (read
        at the last valid row), and the speculative verification surface (a
        drafted slot compares rows ``0..K-1`` against its draft left-to-
        right). Rows past ``q_lens`` are garbage and never read host-side."""
        self.stats["step_traces"] += 1  # Python side: counts TRACES only
        with collect_expert_counts() as expert_counts:
            logits, new_caches = self._step_forward(
                param_arrays, caches, toks, tables, lens, q_lens, active,
                cow_src, cow_dst,
            )
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
        if expert_counts:
            # a model with expert blocks: (rows that landed on held experts,
            # held experts hit), summed over the blocks, beside the argmaxes
            return nxt, new_caches, sum(expert_counts)
        return nxt, new_caches  # nxt [S, C]: per-row argmax

    def _step_forward(
        self, param_arrays, caches, toks, tables, lens, q_lens, active,
        cow_src, cow_dst,
    ):
        """The step's body up to the logits ``[S, C, V]`` (and the new
        caches): what ``_step_impl`` takes its argmax of, and what
        :meth:`step_logits` hands back for numeric comparison."""
        import paddle_tpu
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.nn.layer.layers import (
            bind_param_arrays,
            bind_quant_scales,
        )

        n_named = len(self._named)
        weights, wq_scales = param_arrays[:n_named], param_arrays[n_named:]
        with bind_param_arrays(self._named, weights), bind_quant_scales(
            self._wq_params, wq_scales
        ):
            # one batch a step, shared by every KV set; a set is its planes
            # (scales included, when the pool is quantized) under that batch.
            # Scale planes ride the same CoW fork set as their payload: a
            # forked block gets its source's scales in the same step
            batch = PagedBatch(tables, lens, active, q_lens)
            n_kv = self._num_kv_sets
            pkv = [
                owner(*planes, batch=batch).fork(cow_src, cow_dst)
                for owner, planes in zip(self._paged_owners, caches[:n_kv])
            ]
            if self._state_specs:
                # the model takes its sets in block order; the flat arguments
                # keep the paged sets first, the recurrent ones after them
                paged = iter(pkv)
                states = (RecurrentState(*planes, batch=batch) for planes in caches[n_kv:])
                pkv = [next(paged) if kind == PAGED else next(states) for kind in self._set_kinds]
            with paddle_tpu.no_grad():
                logits, new_pkv = self.model(Tensor(toks), past_key_values=pkv, use_cache=True)
            new_pkv = sorted(new_pkv, key=lambda kv: isinstance(kv, RecurrentState))  # stable: paged first
            return logits._data, [kv.planes for kv in new_pkv]

    def step_logits(self, prompt: Any) -> np.ndarray:
        """fp32 logits ``[n, V]`` of the step's own body (``_step_forward``)
        on the first chunk of ``prompt`` (``n`` tokens, slot 0) — the step
        itself only hands back argmaxes. A debug surface for holding the
        paged path against the dense forward: it runs against an empty
        scratch pool of one sequence's blocks built inside its own trace, so
        the live pool, the request state and ``stats["step_traces"]`` are
        untouched (one extra compile, under the same shard group)."""
        slots, chunk, mbs = self.max_slots, self.prefill_chunk, self.max_blocks_per_seq
        ids = np.asarray(prompt, np.int32)[:chunk]
        n = len(ids)
        toks = np.zeros((slots, chunk), np.int32)
        toks[0, :n] = ids
        tables = np.zeros((slots, mbs), np.int32)
        tables[0] = np.arange(mbs)
        q_lens = np.zeros((slots,), np.int32)
        q_lens[0] = n
        active = np.zeros((slots,), bool)
        active[0] = True
        zeros = np.zeros((slots,), np.int32)
        no_fork = np.full((slots,), mbs, np.int32)  # dst == pool size: dropped

        with self._shard_ctx():
            out = self._step_logits_fn(
                self._param_arrays(), jnp.asarray(toks), jnp.asarray(tables),
                jnp.asarray(zeros), jnp.asarray(q_lens), jnp.asarray(active),
                jnp.asarray(zeros), jnp.asarray(no_fork),
            )
        return np.asarray(out)[:n]

    @functools.cached_property
    def _step_logits_fn(self) -> Callable[..., Any]:
        """:meth:`step_logits`' program, compiled once per engine: slot 0's
        ``[C, V]`` fp32 logits of ``_step_forward`` over a scratch pool."""
        mbs = self.max_blocks_per_seq

        def run(param_arrays, *step_args):
            caches = self._new_pools(mbs) + self._new_states()  # scratch state beside the scratch pool
            logits, _ = self._step_forward(param_arrays, caches, *step_args)
            return logits[0].astype(jnp.float32)

        return jax.jit(run)

    def _shard_ctx(self) -> Any:
        """Marks a trace started under it with the tp shard group (none on
        one chip): the kernel dispatch reads the mark at TRACE time (the
        paged-attention functional wraps its Pallas kernel in shard_map over
        the head shard); executions of an already-compiled program never
        re-enter Python."""
        return partitioned_trace(self._tp_mesh)

    # -- scheduling ----------------------------------------------------------
    def _blocks_needed(self, req: InferenceRequest) -> int:
        # tokens stored by the end: prompt + (max_new - 1) appended during
        # decode (the final generated token is emitted, never appended)
        worst = req.prompt.size + req.max_new_tokens - 1
        return -(-worst // self.block_size)

    def _can_fit(self, req: InferenceRequest) -> bool:
        need = self._blocks_needed(req)
        avail = self._unreserved_free()
        if self._cache is not None:
            matched, matched_evictable = self._cache.peek_cached_blocks(req.prompt)
            # matched blocks are mapped, not allocated — but a matched block
            # currently sitting in the evictable LRU was ALSO counted as
            # reclaimable headroom; pinning it consumes that headroom
            need -= matched
            avail -= matched_evictable
        return avail >= need

    def _alloc_private_block(self) -> int:
        """One request-private block, evicting zero-ref cached chains under
        pressure (the reservation math guarantees this succeeds for live
        slots' growth)."""
        if self._cache is not None:
            return self._cache.alloc_private_block()
        return self._mgr.acquire_block()

    def _shed_expired_queued(self, done: List[InferenceRequest]) -> None:
        """Shed queued requests whose deadline already passed — BEFORE any
        prefill is spent on them. They are delivered through the same step()
        return path as normal finishes, ``finish_reason == "deadline"``."""
        if not self._waiting:
            return
        now = time.perf_counter()
        expired = [r for r in self._waiting if r.expired(now)]
        for req in expired:
            self._waiting.remove(req)
            req.finish_reason = "deadline"
            req.finish_wall = now
            self._flight.record(
                "shed_queued", req_id=req.req_id, reason="deadline"
            )
            self._metrics["finished"].labels(reason="deadline").inc()
            done.append(req)
        if expired:
            self._update_pool_gauges()  # queue depth changed

    def _admit_waiting(self, done: List[InferenceRequest]) -> None:
        self._shed_expired_queued(done)
        self._admit_blocked = None
        while self._waiting:
            free_slots = [i for i, r in enumerate(self._slot_req) if r is None]
            if not free_slots:
                self._admit_blocked = "slots"
                return
            req = self._policy.select(tuple(self._waiting), self._can_fit)
            if req is None:
                # a slot is free and the policy admits nothing: the pool's
                # unreserved blocks do not cover what it looked at
                self._admit_blocked = "blocks"
                return
            # a buggy policy must fail loudly, not corrupt the worst-case
            # reservation invariant the pool depends on
            if req not in self._waiting:
                raise RuntimeError(
                    f"admission policy {type(self._policy).__name__} selected "
                    "a request that is not in the waiting queue"
                )
            if not self._can_fit(req):
                raise RuntimeError(
                    f"admission policy {type(self._policy).__name__} selected "
                    f"request {req.req_id} needing {self._blocks_needed(req)} "
                    f"blocks with only {self._unreserved_free()} unreserved"
                )
            self._waiting.remove(req)
            self._admit(req, free_slots[0])

    def _match_and_map(self, req: InferenceRequest, slot: int) -> None:
        """Map the longest cached prefix into ``slot``'s block table (host
        bookkeeping only — the slot's first chunk rides the NEXT unified
        step). A failing cache lookup (including an injected
        ``prefix_cache.match`` fault) degrades to a cold miss: the prompt is
        simply recomputed."""
        result = None
        if self._prefix_reuse_refused:
            self.stats["prefix_reuse_skipped_recurrent"] += 1
        if self._cache is not None:
            try:
                result = self._cache.match(req.prompt)
            except Exception as exc:  # noqa: BLE001 - lookup must never kill admission
                self._flight.record(
                    "prefix_match_failed", req_id=req.req_id,
                    error=f"{type(exc).__name__}: {exc}"[:120],
                )
        nodes = result.nodes if result is not None else []
        cached = result.cached_tokens if result is not None else 0
        cow = result.cow if result is not None else None
        self._nodes[slot] = list(nodes)
        self._blocks[slot] = [n.block for n in nodes]
        self._no_insert[slot] = False
        self._pending_cow[slot] = None
        if cow is not None:
            src_node, dst_block, partial = cow
            self._blocks[slot].append(dst_block)
            self._pending_cow[slot] = cow
            self._flight.record(
                "cow_fork", req_id=req.req_id, slot=slot,
                src_block=src_node.block, dst_block=dst_block,
                reused_tokens=partial,
            )
        if result is not None and (result.host_nodes or result.host_partial):
            cached += self._prefetch_spilled(slot, req, result)
        self._matched_blocks[slot] = len(self._nodes[slot])
        self._reserved[slot] = self._blocks_needed(req) - len(self._nodes[slot])
        self._ntok[slot] = cached
        req.cached_tokens = cached
        self.stats["prompt_tokens_reused"] += cached

    def _prefetch_spilled(
        self, slot: int, req: InferenceRequest, result: Any
    ) -> int:
        """Land a matched spilled chain back into the pool: reserve slots
        for every matched host block (full chain nodes + the divergent
        block's partial source) atomically, issue their asynchronous H2D
        copies into the per-layer pools, re-register the full blocks as
        device chain nodes, and gate the slot until the copies land. Returns
        the prompt tokens this reused (0 on ANY failure — an injected
        ``kv_tier.prefetch`` fault, allocation shortfall, or a dispatch
        error all degrade to recomputing the suffix, with the already-mapped
        device chain untouched and nothing allocated)."""
        host_nodes: List[HostNode] = list(result.host_nodes)
        host_partial: Optional[Tuple[HostNode, int]] = result.host_partial
        n_blocks = len(host_nodes) + (1 if host_partial is not None else 0)
        blocks: List[int] = []
        try:
            try:
                fault_point("kv_tier.prefetch")
                blocks = self._cache.alloc_landing_blocks(n_blocks)
                copies = list(host_nodes)
                if host_partial is not None:
                    copies.append(host_partial[0])
                marker = None
                hd = self._hd
                for hn, blk in zip(copies, blocks):
                    dst = jnp.asarray(np.int32(blk))
                    for li in range(self._num_kv_sets):
                        if self._quant_kv:
                            # packed host block [2, KVH, BS, D+4] int8: split
                            # the payload from the 4 trailing scale bytes and
                            # land all four planes in one fold program
                            kc, vc, ks, vs = self._caches[li]
                            kv = hn.kv[li]
                            hks = np.ascontiguousarray(
                                kv[0, ..., hd:]
                            ).view(np.float32)[..., 0]
                            hvs = np.ascontiguousarray(
                                kv[1, ..., hd:]
                            ).view(np.float32)[..., 0]
                            kc, vc, ks, vs, marker = self._fold_fn(
                                kc, vc, ks, vs, dst,
                                jnp.asarray(kv[0, ..., :hd]),
                                jnp.asarray(kv[1, ..., :hd]),
                                jnp.asarray(hks), jnp.asarray(hvs),
                            )
                            self._caches[li] = (kc, vc, ks, vs)
                        else:
                            kc, vc = self._caches[li]
                            kc, vc, marker = self._fold_fn(
                                kc, vc, dst,
                                jnp.asarray(hn.kv[li, 0]),
                                jnp.asarray(hn.kv[li, 1]),
                            )
                            self._caches[li] = (kc, vc)
            except Exception as exc:  # noqa: BLE001 - degrade to recompute
                for blk in blocks:  # reserved but never mapped: hand back
                    self._mgr.decref(blk)
                self._flight.record(
                    "kv_prefetch_failed", req_id=req.req_id, slot=slot,
                    blocks=n_blocks,
                    error=f"{type(exc).__name__}: {exc}"[:120],
                )
                return 0
        finally:
            # pins exist only to bridge match -> copy-issue: once the copies
            # are in the dispatch queue (jax holds its own reference to the
            # host planes) or the prefetch is abandoned, the LRU may move
            self._cache.release_host_pins(result)
        # commit phase (cannot fail): map the landed blocks into the slot's
        # table and re-register the full blocks as device chain nodes so
        # later admissions share them without another prefetch. A key that
        # re-registered concurrently keeps our copy private (same layout as
        # the in-flight insert race).
        tokens = 0
        parent = self._nodes[slot][-1] if self._nodes[slot] else None
        registering = True
        for i, hn in enumerate(host_nodes):
            blk = blocks[i]
            self._blocks[slot].append(blk)
            tokens += self.block_size
            if registering:
                node = self._cache.insert(parent, hn.tokens(), blk)
                if node is None:
                    registering = False
                else:
                    self._nodes[slot].append(node)
                    parent = node
        if host_partial is not None:
            # the divergent block's leading run, prefetched instead of
            # copy-on-write forked: the whole block landed, the request
            # overwrites it from the divergence point on — private forever
            # (its eventual content differs from the spilled source)
            self._blocks[slot].append(blocks[-1])
            tokens += host_partial[1]
        self._host_tier.mark_prefetched(n_blocks)
        self._cache.record_host_reuse(tokens)
        self._prefetch_wait[slot] = (marker, n_blocks, tokens)
        self._flight.record(
            "kv_prefetch", req_id=req.req_id, slot=slot, blocks=n_blocks,
            tokens=tokens,
        )
        return tokens

    def _poll_prefetch_gates(self, wait: bool = False) -> None:
        """Clear the prefetch gate of every slot whose H2D copies have
        landed (``wait=True`` blocks on them — the escape hatch when gated
        slots are the only work, so the engine can never stall on its own
        gate)."""
        for i in range(self.max_slots):
            pending = self._prefetch_wait[i]
            if pending is None:
                continue
            marker = pending[0]
            if wait:
                jax.block_until_ready(marker)
                ready = True
            else:
                ready = bool(getattr(marker, "is_ready", lambda: True)())
            if ready:
                self._prefetch_wait[i] = None

    def _admit(self, req: InferenceRequest, slot: int) -> None:
        # the prefill fault site moved host-side with chunked prefill: it
        # models an admission-time failure (match/map), and — like a real
        # dispatch loss — an InjectedFault here takes the recovery path
        try:
            fault_point("engine.prefill")
            self._match_and_map(req, slot)
        except BaseException:
            # broad on purpose: whatever kills admission (injected fault,
            # MemoryError from the CoW alloc, operator interrupt), the
            # partially-mapped slot must be unwound so pool accounting is
            # exactly as before this admit; step()'s retry loop classifies
            self._rollback_admit(slot)
            self._waiting.appendleft(req)  # keeps FIFO order for a retry
            raise
        req.prefill_start = time.perf_counter()
        self._slot_req[slot] = req
        self._last_tok[slot] = 0
        self.stats["admitted"] += 1
        self._flight.record(
            "admit", req_id=req.req_id, slot=slot,
            prompt_len=int(req.prompt.size), cached_tokens=int(req.cached_tokens),
            queue_depth=len(self._waiting),
        )
        self._metrics["admitted"].inc()
        self._update_pool_gauges()

    def _rollback_admit(self, slot: int) -> None:
        """Undo a partially-mapped admission so a failure leaves the pool
        accounting exactly as before."""
        if self._cache is not None:
            if self._pending_cow[slot] is not None:
                src_node, dst_block, _ = self._pending_cow[slot]
                self._cache.release_cow_source(src_node)
                self._mgr.decref(dst_block)
                if self._blocks[slot] and self._blocks[slot][-1] == dst_block:
                    self._blocks[slot].pop()
            if self._nodes[slot]:
                self._cache.release(self._nodes[slot])
        # prefetched blocks that stayed private (insert race / the partial
        # arm) sit past the node prefix: hand them back too
        for blk in self._blocks[slot][len(self._nodes[slot]):]:
            self._mgr.decref(blk)
        self._nodes[slot] = []
        self._blocks[slot] = []
        self._matched_blocks[slot] = 0
        self._pending_cow[slot] = None
        self._prefetch_wait[slot] = None
        self._reserved[slot] = 0
        self._ntok[slot] = 0

    def _release(self, slot: int, req: InferenceRequest) -> None:
        # finished requests are handed back ONLY through step()'s return
        # value (run() accumulates them); the engine keeps no reference, so
        # a long-running step()-driven server never grows host memory
        # skip chain registration under a pending CoW fork: its device copy
        # never executed, so that block's content is garbage and must not be
        # hashed into the cache
        had_pending_cow = self._pending_cow[slot] is not None
        if self._cache is not None and had_pending_cow:
            # cancelled before its first step: unpin the CoW source
            self._cache.release_cow_source(self._pending_cow[slot][0])
        self._pending_cow[slot] = None
        if not had_pending_cow:
            self._register_finished_chain(slot, req)
        nodes = self._nodes[slot]
        if self._cache is not None and nodes:
            self._cache.release(nodes)
        for blk in self._blocks[slot][len(nodes):]:
            self._mgr.decref(blk)  # private blocks free immediately
        self._nodes[slot] = []
        self._blocks[slot] = []
        self._matched_blocks[slot] = 0
        self._no_insert[slot] = False
        # a gate left by a released/cancelled slot is dropped, not waited
        # on: the in-flight copies still execute in dispatch order, and any
        # reuse of their target blocks happens in LATER dispatches that
        # consume the folded arrays — ordering keeps them safe
        self._prefetch_wait[slot] = None
        self._reserved[slot] = 0
        self._slot_req[slot] = None
        self._ntok[slot] = 0
        self._last_tok[slot] = 0
        req.finish_wall = time.perf_counter()
        self._flight.record(
            "evict", req_id=req.req_id, slot=slot,
            reason=req.finish_reason or "unknown",
            n_generated=len(req.generated),
        )
        self._metrics["evicted"].inc()
        self._metrics["finished"].labels(reason=req.finish_reason or "unknown").inc()
        self._update_pool_gauges()

    def step(self, since: Optional[float] = None) -> List[InferenceRequest]:
        """One engine iteration: reclaim/admit, then one unified
        prefill/decode step over all active slots. Returns requests that
        finished during this step — the ONLY handback: the engine keeps no
        reference to finished requests (a step()-driven server never grows
        host memory), so a later run() will not re-deliver them.

        ``since`` is the ``perf_counter`` instant at which the caller's own
        phase ended (``ServingFrontend.pump``): the first attempt's
        ``engine.plan`` phase starts there, so the phases tile the pump.

        Failure policy: a dispatch failure that left the cache buffers
        intact (no donation consumed them) re-raises immediately with host
        state rolled back — the caller may simply retry. A failure that
        consumed the donated buffers (``_buffers_lost()``; an
        :class:`InjectedFault` from a fault plan models exactly this) runs
        :meth:`recover` and retries, up to ``max_recoveries`` times with
        exponential backoff, then marks the engine permanently failed and
        re-raises."""
        self._check_usable()
        self.close_step()  # a bare driver never closed the previous step
        attempt = 0
        while True:
            try:
                self._step_attempt(None if attempt else since)
                if attempt:
                    self._open_step = None  # recovered: not a step to judge
                break
            except BaseException as exc:
                # broad on purpose: ANY dispatch failure must be classified
                # (recoverable buffers-lost vs caller-retryable) — except an
                # operator interrupt, which is never a recovery trigger and
                # must propagate NOW, not after sleep+recover+retry; if it
                # consumed donated buffers, the next step() call recovers
                if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                    raise
                # an injected dispatch fault models the donating-backend
                # failure mode (buffers consumed by the aborted dispatch),
                # so it takes the same recovery path on every backend
                recoverable = self._buffers_lost() or isinstance(exc, InjectedFault)
                if not recoverable or attempt >= self.max_recoveries:
                    self._broken = recoverable
                    if self._broken:
                        self._dump_black_box(exc)
                    raise
                attempt += 1
                time.sleep(self.recovery_backoff * (2 ** (attempt - 1)))
                try:
                    self.recover()
                except BaseException as rexc:
                    # a dispatch failure DURING recovery (device truly dead,
                    # injected or real) leaves half-rebuilt KV — permanent
                    self._broken = True
                    self._dump_black_box(rexc)
                    raise
        # deliver everything that finished during this (possibly retried)
        # step exactly once — including finishers from an attempt whose
        # dispatch later died
        return self.drain_finished()

    def _dump_black_box(self, exc: BaseException) -> None:
        """The engine just became PERMANENTLY failed: write the flight
        recorder's recent-event ring to disk so the postmortem has a
        timeline. safe_dump never raises — the original exception is what
        the caller must see."""
        self._flight.record(
            "engine_permanent_failure",
            error=f"{type(exc).__name__}: {exc}"[:200],
            live=sum(r is not None for r in self._slot_req),
            queued=len(self._waiting),
        )
        self._flight.safe_dump(
            "engine_permanent_failure",
            extra={
                "error": f"{type(exc).__name__}: {exc}"[:200],
                "stats": dict(self.stats),
                "pool": self.pool_stats(),
            },
        )

    def drain_finished(self) -> List[InferenceRequest]:
        """Hand back finished-but-undelivered requests. Normally step() is
        the only delivery path; this exists for the salvage case — a step
        whose delivery was preempted by an exception (including a PERMANENT
        engine failure) leaves complete results the host already holds, and
        they must be collectable rather than stranded. Usable on a broken
        engine; exactly-once still holds (the buffer is drained)."""
        out, self._pending_done = self._pending_done, []
        return out

    # -- the unified dispatch ------------------------------------------------
    def _dense_tables(self) -> np.ndarray:
        out = np.zeros((self.max_slots, self.max_blocks_per_seq), np.int32)
        for s, blocks in enumerate(self._blocks):
            if blocks:
                out[s, : len(blocks)] = blocks
        return out

    def _devprof_cost_thunk(
        self, toks, tables, q_lens, active, cow_src, cow_dst
    ) -> Callable[[], Any]:
        """Zero-arg thunk handing devprof the just-compiled step program's
        ``cost_analysis()``. It is an introspective AOT lowering — it re-runs
        the ``_step_impl`` Python trace and pays one extra XLA compile — so
        devprof only invokes it while ``FLAGS_devprof_sample_rate > 0``.
        The re-trace bumps ``stats["step_traces"]``; save/restore keeps the
        1-compile invariant (and the watchdog ledger it feeds) honest: this
        trace produces a throwaway executable, not a new step program.
        Lowered with the live committed arrays under the same shard context
        as the real call, so under tp the analyzed program carries the real
        GSPMD partitioning (and its inserted collectives)."""

        def thunk():
            traces_before = self.stats["step_traces"]
            try:
                with self._shard_ctx():
                    lowered = self._step_fn.lower(
                        self._param_arrays(), self._caches + self._states, jnp.asarray(toks),
                        jnp.asarray(tables), jnp.asarray(self._ntok.copy()),
                        jnp.asarray(q_lens), jnp.asarray(active),
                        jnp.asarray(cow_src), jnp.asarray(cow_dst),
                    )
                return lowered.compile().cost_analysis()
            finally:
                self.stats["step_traces"] = traces_before

        return thunk

    def _next_phase(self, name: str, key: str) -> None:
        """End the serving step's open phase and open the next at the same
        instant (observability/tracing.py ``phase``); the ended one is kept
        for the step's own accounting. A phase that ends with a sub-phase
        open ends where that one does: one clock read for both. No-op when
        none is open (recovery)."""
        cur = self._phase
        if cur is None:
            return
        cur.end_s = self._end_subphase()
        cur.__exit__(None, None, None)
        self._phases_done.append(cur)
        self._phase = _tracing.phase(name, self.stats, key, cur.step, cur.end_s).__enter__()

    def _next_subphase(self, name: str, key: str) -> None:
        """Move the open phase's sub-phase on: end the open one, or start
        where the phase did, and open ``name`` at that instant, nested in the
        phase (its annotation inside the phase's, its ring span the phase's
        child). The sub-phases of a phase tile it, as the phases do the step.
        No-op when no phase is open (recovery)."""
        cur = self._phase
        if cur is None:
            return
        at = self._end_subphase()
        self._subphase = _tracing.phase(
            name, self.stats, key, cur.step, cur.start_s if at is None else at
        ).__enter__()

    def _end_subphase(self, exc_info: Tuple[Any, Any, Any] = (None, None, None)) -> Optional[float]:
        """End the open sub-phase, if any; the instant it ended at."""
        sub, self._subphase = self._subphase, None
        if sub is None:
            return None
        sub.__exit__(*exc_info)
        self._subphases_done.append(sub)
        return sub.end_s

    def _dispatch(
        self,
        toks: np.ndarray,  # [S, C]
        q_lens: np.ndarray,  # [S]
        active: np.ndarray,  # [S] bool
    ) -> np.ndarray:
        """Run ONE unified step over the given ragged rows: grow block
        tables for the new tokens, fold in pending CoW forks, dispatch, sync,
        then advance ``_ntok`` and register freshly completed full prompt
        blocks with the prefix cache. Host token bookkeeping (emission,
        finish checks) is the caller's. On failure every block allocated for
        this step is returned, so repeated failed steps cannot drift the
        reservation invariant.

        Called from the serving step it moves that step's open phase on
        (``engine.plan`` -> ``.launch`` at the host-to-device puts, ->
        ``.wait`` at the jit call's return, -> ``.commit`` after the sync),
        and tiles the two phases where the work changes hands into
        sub-phases: ``engine.launch.put`` (the step's seven host-to-device
        conversions) -> ``.launch.args`` (the argument lists: every weight,
        every cache plane) -> ``.launch.call`` (the jit call: flattening,
        cache lookup, enqueue, its outputs wrapped and taken apart), then
        ``engine.wait.ready`` (until the result is ready on the device,
        nothing copied) -> ``.wait.fetch`` (the tokens' copy to the host).
        Called from :meth:`recover` no phase is open and nothing is timed."""
        appended: List[Tuple[int, int]] = []  # (slot, block) rollback list
        active_slots = [i for i in range(self.max_slots) if active[i]]
        cow_src = np.zeros((self.max_slots,), np.int32)
        cow_dst = np.full((self.max_slots,), self.num_blocks, np.int32)
        try:
            for i in active_slots:
                need_tokens = int(self._ntok[i]) + int(q_lens[i])
                while len(self._blocks[i]) * self.block_size < need_tokens:
                    blk = self._alloc_private_block()
                    self._blocks[i].append(blk)
                    appended.append((i, blk))
                pending = self._pending_cow[i]
                if pending is not None:
                    cow_src[i] = pending[0].block
                    cow_dst[i] = pending[1]
            tables = self._dense_tables()
            fault_point("engine.decode")
            traces_before = self.stats["step_traces"]
            self._next_phase("engine.launch", "phase_s.launch")
            self._next_subphase("engine.launch.put", "subphase_s.launch_put")
            small = (
                jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(self._ntok.copy()),
                jnp.asarray(q_lens), jnp.asarray(active),
                jnp.asarray(cow_src), jnp.asarray(cow_dst),
            )
            self._next_subphase("engine.launch.args", "subphase_s.launch_args")
            params, sets = self._param_arrays(), self._caches + self._states
            self._next_subphase("engine.launch.call", "subphase_s.launch_call")
            with self._shard_ctx():  # for the (first-call / recovery) trace
                nxt, kept, *expert_counts = self._step_fn(params, sets, *small)
                self._caches, self._states = kept[: self._num_kv_sets], kept[self._num_kv_sets:]
            # the call's arguments die where they did as temporaries of the call
            # expression: inside engine.launch, not at this function's return
            del params, sets, small
        except BaseException:
            # roll the per-step allocations back so a transient failure
            # leaves the allocator in lockstep with _ntok (retried steps
            # neither leak blocks nor break the reservation invariant);
            # pending CoW forks stay pending — a retry re-copies
            for slot, blk in appended:
                self._blocks[slot].remove(blk)
                self._mgr.decref(blk)
            raise
        if self.stats["step_traces"] > traces_before:
            self._step_compiled = True  # not a step whose time is judged
            # recorded HERE, after the jit call returned: a trace that died
            # mid-body bumped the stats counter but produced no program, and
            # the watchdog ledger must only count compiles that exist
            GLOBAL_WATCHDOG.record_compile(
                "ContinuousBatchingEngine.step",
                signature=f"toks[{self.max_slots},{self.prefill_chunk}]"
                + (f"|tp{self.tp}" if self.tp > 1 else ""),
                cause=CAUSE_FIRST_CALL
                if not self._step_recorded
                else CAUSE_NEW_SHAPE_DTYPE,
                cost_thunk=self._devprof_cost_thunk(
                    toks, tables, q_lens, active, cow_src, cow_dst
                ),
                cost_hints=self._devprof_hints,
            )
            self._step_recorded = True
        self._next_phase("engine.wait", "phase_s.wait")
        self._next_subphase("engine.wait.ready", "subphase_s.wait_ready")
        # the tokens' copy is queued behind the executable now, as np.asarray
        # alone would queue it: asked for only once the result is ready, it
        # would be a second round trip to the device after the first
        nxt.copy_to_host_async()
        nxt.block_until_ready()  # device sync: the executable is done
        self._next_subphase("engine.wait.fetch", "subphase_s.wait_fetch")
        nxt = np.asarray(nxt)  # the copy has landed: the step's tokens are real here
        self._next_phase("engine.commit", "phase_s.commit")
        if expert_counts:
            rows_local, experts_hit = np.asarray(expert_counts[0]).tolist()
            self.stats["moe_rows_local"] += rows_local
            self.stats["moe_experts_hit"] += experts_hit
        if self._quant_kv and _obs.metrics_enabled():
            # host-side attribution of the step's quantized-plane traffic:
            # every new token was quantized on write, every active slot's
            # block walk dequantized on read (one cached-bool check + two
            # counter adds per STEP — nothing per token)
            self._metrics["kv_quant"].labels(op="quant").inc(
                float(sum(int(q_lens[i]) for i in active_slots))
            )
            self._metrics["kv_quant"].labels(op="dequant").inc(
                float(len(active_slots))
            )
        for i in active_slots:
            pending = self._pending_cow[i]
            if pending is not None:
                # the fork's device copy has executed — unpin the source
                if self._cache is not None:
                    self._cache.release_cow_source(pending[0])
                self._pending_cow[i] = None
            self._ntok[i] += int(q_lens[i])
            self._extend_chain(i)
        return nxt

    def _register_finished_chain(self, slot: int, req: InferenceRequest) -> None:
        """At request FINISH, extend the slot's chain with its full blocks
        of COMMITTED generated tokens, so a multi-turn conversation's second
        turn (prompt = first turn's prompt + reply + new text) maps its
        first turn's KV instead of recomputing it. Rewind-safe by
        construction: only tokens the block table still covers are hashed —
        ``_ntok`` is the committed length, and everything a speculative
        rewind discarded is already gone by commit time, long before this
        runs. Reuses the in-flight insert machinery, so the release that
        follows drops only this request's reference and the chain stays
        warm in the LRU for the next turn's match."""
        if self._cache is None or self._no_insert[slot]:
            return
        valid = int(self._ntok[slot])
        full = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)]
        )
        bs = self.block_size
        while True:
            idx = len(self._nodes[slot])
            end = (idx + 1) * bs
            # cap at the emitted stream too: an eos inside an accepted draft
            # leaves KV past the last emitted token — valid content, but not
            # part of any prompt a next turn would replay, so never hashed
            if end > valid or end > full.size or idx >= len(self._blocks[slot]):
                return
            parent = self._nodes[slot][-1] if self._nodes[slot] else None
            node = self._cache.insert(
                parent, full[idx * bs : end], self._blocks[slot][idx]
            )
            if node is None:
                return  # identical chain already cached; keep ours private
            self._nodes[slot].append(node)
            if end > req.prompt.size:
                self.stats["gen_blocks_registered"] += 1

    def _extend_chain(self, slot: int) -> None:
        """Register this slot's freshly COMPLETED full prompt blocks as
        chain nodes (in-flight insertion: later admissions share them the
        moment they are computed). Blocks containing any generated token
        stay private until the request finishes — a live tail can still be
        rewound by speculation, so only :meth:`_register_finished_chain`
        (which runs after the last commit) ever hashes generated content."""
        if self._cache is None or self._no_insert[slot]:
            return
        req = self._slot_req[slot]
        if req is None:
            return
        plen = req.prompt.size
        bs = self.block_size
        while True:
            idx = len(self._nodes[slot])
            end = (idx + 1) * bs
            if end > plen or end > int(self._ntok[slot]):
                return
            if idx >= len(self._blocks[slot]):
                return
            parent = self._nodes[slot][-1] if self._nodes[slot] else None
            node = self._cache.insert(
                parent, req.prompt[idx * bs : end], self._blocks[slot][idx]
            )
            if node is None:
                # another request registered the same chain block first
                # (same-boundary concurrent compute); keep ours private and
                # stop extending so node/block alignment stays simple
                self._no_insert[slot] = True
                return
            self._nodes[slot].append(node)

    # -- speculative decoding ------------------------------------------------
    def _propose_draft(self, req: InferenceRequest) -> np.ndarray:
        """Host-side draft for one decode slot. The width is capped THREE
        ways: the chunk can carry ``prefill_chunk - 1`` draft rows next to
        the real last token; the request's remaining token budget bounds it
        at ``max_new - generated - 1`` (so even a fully accepted draft plus
        its bonus token lands exactly on the budget — KV never grows past
        the slot's worst-case admission reservation); and the drafter itself
        returns only what the history supports (possibly nothing — the slot
        then stays a plain decode row at zero cost)."""
        budget = req.max_new_tokens - len(req.generated) - 1
        k_max = min(self._spec_k, budget)
        if k_max < 1:
            return np.empty((0,), np.int32)
        # hand the drafter only the tail it can actually read (its search
        # window plus the n-gram lookback) — proposals are identical, but a
        # long generation no longer re-copies its whole O(context) history
        # per slot per step
        d = self._drafter
        need = d.window + d.ngram_max + 1
        gen = req.generated
        if len(gen) >= need:
            ctx = np.asarray(gen[-need:], np.int32)
        else:
            # clamp at 0: a start index going negative would wrap and slice
            # a short suffix instead of the whole prompt
            start = max(req.prompt.size - (need - len(gen)), 0)
            ctx = np.concatenate(
                [req.prompt[start:], np.asarray(gen, np.int32)]
            )
        return d.propose(ctx, k_max)

    def _commit_speculation(
        self,
        slot: int,
        req: InferenceRequest,
        row_argmax: np.ndarray,  # [C] this slot's per-row argmax
        draft: np.ndarray,
    ) -> None:
        """Verify and commit one slot's draft against the step that scored
        it. Accepted tokens commit in bulk — their KV was written by the
        very dispatch that verified them — followed by the bonus token (the
        argmax after the last accepted draft, which plain decode would have
        produced next anyway); the first rejection rewinds the block table
        to the committed length. An injected ``spec.verify`` fault degrades
        the slot to plain decode for this step: accept nothing, keep row
        0's argmax (computed from committed history only — its value does
        not depend on the draft), rewind the drafted rows. No tokens are
        lost and no accounting drifts on that path."""
        k = int(draft.size)
        base = int(self._ntok[slot]) - (1 + k)  # committed before this step
        try:
            fault_point("spec.verify")
            accepted = count_accepted(row_argmax, draft)
        except Exception as exc:  # noqa: BLE001 - degrade, never corrupt
            self._flight.record(
                "spec_verify_degraded", req_id=req.req_id, slot=slot,
                error=f"{type(exc).__name__}: {exc}"[:120],
            )
            accepted = 0
        # rewind FIRST: _ntok / block-table truth must equal the committed
        # length before any finish path below releases the slot
        self._rewind_slot(slot, req, base + 1 + accepted, drafted=k,
                          accepted=accepted)
        emit = [int(draft[j]) for j in range(accepted)]
        emit.append(int(row_argmax[accepted]))  # the bonus token
        for tok in emit:
            req.generated.append(tok)
            self._last_tok[slot] = tok
            if req.eos_token_id is not None and tok == req.eos_token_id:
                req.finish_reason = "stop"
                break
            if len(req.generated) >= req.max_new_tokens:
                req.finish_reason = "length"
                break
        self.stats["spec_steps"] += 1
        self.stats["spec_drafted"] += k
        self.stats["spec_accepted"] += accepted
        self.stats["spec_rejected"] += k - accepted
        m = self._metrics
        m["spec_drafted"].inc(k)
        m["spec_accepted"].inc(accepted)
        m["spec_rejected"].inc(k - accepted)
        m["spec_accept_rate"].observe(accepted / k)
        if req.finished:
            self._release(slot, req)
            self._pending_done.append(req)

    def _rewind_slot(
        self, slot: int, req: InferenceRequest, target_ntok: int,
        drafted: int, accepted: int,
    ) -> None:
        """Block-table rewind: discard the KV written past ``target_ntok``
        by truncating the slot's table through the refcounted pool. Chain-
        owned blocks are never touched — drafts only ever write past the
        prompt, into request-private blocks — and the stale KV left in the
        retained partial block is unreadable (every later row's attention is
        limited to positions below the committed length) and is overwritten
        in place as the sequence advances."""
        self._ntok[slot] = target_ntok
        keep = max(-(-target_ntok // self.block_size), len(self._nodes[slot]))
        freed = 0
        while len(self._blocks[slot]) > keep:
            self._mgr.decref(self._blocks[slot].pop())
            freed += 1
        if accepted < drafted:
            self._flight.record(
                "spec_rewind", req_id=req.req_id, slot=slot, drafted=drafted,
                accepted=accepted, rejected=drafted - accepted,
                blocks_freed=freed,
            )

    def spec_decode_stats(self) -> Dict[str, Any]:
        """Acceptance-rate view for /healthz, the serving goodput record and
        bench (host counters — valid with metrics off)."""
        drafted = self.stats["spec_drafted"]
        return {
            "enabled": self._use_spec,
            "drafted_tokens": drafted,
            "accepted_tokens": self.stats["spec_accepted"],
            "rejected_tokens": self.stats["spec_rejected"],
            "acceptance_rate": (
                self.stats["spec_accepted"] / drafted if drafted else 0.0
            ),
            "speculative_steps": self.stats["spec_steps"],
        }

    def _step_attempt(self, since: Optional[float] = None) -> None:
        """One admit+dispatch pass; finished requests land in
        ``_pending_done`` (never lost to an exception mid-attempt).

        The pass runs under four phases that tile it (``engine.plan`` ->
        ``.launch`` -> ``.wait`` -> ``.commit``, children of
        ``engine.decode_step``; ``_dispatch`` moves from one to the next,
        and tiles ``.launch`` and ``.wait`` into their five sub-phases):
        each is on the device trace's clock while a profile is taken, and
        always adds its seconds to ``stats["phase_s.*"]`` /
        ``["subphase_s.*"]`` (observability/tracing.py ``phase``)."""
        stats = self.stats
        step_no = stats["steps"] + 1
        self._step_compiled = False
        self.last_step_end_s = None
        done = self._phases_done = []
        subs = self._subphases_done = []
        with _tracing.phase("engine.decode_step", None, None, step_no, since) as whole:
            self._phase = _tracing.phase(
                "engine.plan", stats, "phase_s.plan", step_no, whole.start_s
            ).__enter__()
            try:
                stepped = self._step_in_phases(whole.start_s)
            finally:
                # whichever phase is open (plan, on an idle pass; commit, after
                # a step; any, with its sub-phase, under an exception) ends here
                last, self._phase = self._phase, None
                exc_info = sys.exc_info()
                last.end_s = self._end_subphase(exc_info)
                last.__exit__(*exc_info)
                done.append(last)
            if stepped is not None:
                # engine.decode_step takes its instants from its children
                whole.end_s = self.last_step_end_s = last.end_s
                self._account_riders(whole, stepped)
        if stepped is not None and not self._step_compiled:
            # what close_step() judges: the four phases' seconds, and what
            # its record splits launch and wait into: the five sub-phases'
            self._open_step = tuple(ph.end_s - ph.start_s for ph in done + subs)

    def _step_in_phases(self, now: float) -> Optional[List[Any]]:
        """The body of one pass begun at ``now``, from ``engine.plan`` (open on
        entry) to ``engine.commit`` (open on return). Returns the step's riders: its
        active slots' requests as they were before a finish released its
        slot (empty unless tracing is on), or ``None`` if nothing stepped."""
        stats = self.stats
        # mid-decode deadline expiry FIRST: evict before paying for another
        # step of this slot's compute, so the freed slot/blocks are available
        # to the admit pass below in the same boundary
        for i, req in enumerate(self._slot_req):
            if req is not None and req.expired(now):
                req.finish_reason = "deadline"
                self._release(i, req)
                self._pending_done.append(req)
        self._admit_waiting(self._pending_done)
        # prefetch gating: a slot whose host-tier blocks are still in H2D
        # flight contributes no rows this step — its chunks only ride the
        # mixed step once the copies have landed, and the copies overlap
        # with the other slots' compute meanwhile. When gated slots are the
        # ONLY live work there is nothing to overlap with: wait them out so
        # the engine can never stall on its own gate.
        self._poll_prefetch_gates()
        active_slots = [
            i for i, r in enumerate(self._slot_req)
            if r is not None and self._prefetch_wait[i] is None
        ]
        if not active_slots:
            if any(w is not None for w in self._prefetch_wait):
                self._poll_prefetch_gates(wait=True)
                active_slots = [
                    i for i, r in enumerate(self._slot_req) if r is not None
                ]
            if not active_slots:
                return None
        C = self.prefill_chunk
        toks = np.zeros((self.max_slots, C), np.int32)
        q_lens = np.zeros((self.max_slots,), np.int32)
        active = np.zeros((self.max_slots,), bool)
        prefill_tokens = pages_walked = row_keys = 0
        # slot -> draft packed into this attempt's chunk rows; LOCAL on
        # purpose: a failed dispatch retries through a fresh _step_attempt
        # that re-proposes, so no speculative state can ever go stale
        drafts: Dict[int, np.ndarray] = {}
        for i in active_slots:
            req = self._slot_req[i]
            plen = req.prompt.size
            cur = int(self._ntok[i])
            active[i] = True
            if cur < plen:  # chunked prefill row(s)
                n = min(C, plen - cur)
                toks[i, :n] = req.prompt[cur : cur + n]
                q_lens[i] = n
                prefill_tokens += n
            else:  # decode row, with the draft riding as extra chunk rows
                toks[i, 0] = self._last_tok[i]
                q_lens[i] = 1
                if self._drafter is not None and self._pending_cow[i] is None:
                    draft = self._propose_draft(req)
                    if draft.size:
                        k = int(draft.size)
                        toks[i, 1 : 1 + k] = draft
                        q_lens[i] = 1 + k
                        drafts[i] = draft
            q = int(q_lens[i])
            pages_walked += -(-(cur + q) // self.block_size)
            row_keys += q * cur + q * (q + 1) // 2
        # devprof sampling decision: one cached-bool read at rate 0 (the
        # stride counter only advances while the flag is on, and the stride
        # is deterministic — no RNG draw, seeded runs stay byte-identical)
        dp_sampled = self._devprof_gate.should_sample()
        comm_ops: Dict[str, float] = {}
        if dp_sampled:
            _devprof.begin_comm_window()
        try:
            nxt = self._dispatch(toks, q_lens, active)
        finally:
            if dp_sampled:
                comm_ops = _devprof.end_comm_window()
        stats["steps"] += 1
        stats["prompt_tokens_computed"] += prefill_tokens
        stats["paged_pages_walked"] += pages_walked
        stats["attn_row_keys"] += row_keys
        stats["loop_passes"] += self._stack_passes
        if self._admit_blocked is not None:
            stats["admit_blocked_steps." + self._admit_blocked] += 1
        if prefill_tokens:
            self._metrics["prefill_tokens"].inc(prefill_tokens)
        plan, launch, wait = self._phases_done
        t0, t1 = plan.start_s, wait.end_s
        self._metrics["step"].observe(t1 - t0)
        if dp_sampled:
            # devprof's four instants are the phases' own, no second set
            _devprof.record_step_profile(
                "ContinuousBatchingEngine.step",
                f"toks[{self.max_slots},{self.prefill_chunk}]"
                + (f"|tp{self.tp}" if self.tp > 1 else ""),
                t0, launch.start_s, launch.end_s, t1,
                comm_ops=comm_ops,
                n_active=len(active_slots),
                step=stats["steps"],
                timeline=self._devprof_timeline,
                flight=self._flight,
            )
        riders = (
            [(i, self._slot_req[i]) for i in active_slots]
            if _tracing.tracing_enabled() else []
        )
        for i in active_slots:
            req = self._slot_req[i]
            if int(self._ntok[i]) < req.prompt.size:
                continue  # prompt not fully prefilled yet: no emission
            if i in drafts:
                self._commit_speculation(i, req, nxt[i], drafts[i])
                continue
            tok = int(nxt[i, max(int(q_lens[i]) - 1, 0)])  # last valid row
            if not req.generated:
                # the prompt just completed: this is the request's FIRST
                # token (TTFT ends here, not at admission)
                req.admit_time = time.perf_counter()
                self._metrics["ttft"].observe(req.admit_time - req.arrival_time)
            req.generated.append(tok)
            self._last_tok[i] = tok
            if req.eos_token_id is not None and tok == req.eos_token_id:
                req.finish_reason = "stop"
            elif len(req.generated) >= req.max_new_tokens:
                req.finish_reason = "length"
            if req.finished:
                self._release(i, req)
                self._pending_done.append(req)
        self._update_pool_gauges()  # step advanced every active slot
        return riders

    def _account_riders(self, whole: Any, riders: List[Any]) -> None:
        """Per-request decode time in a continuous batch is a SHARE of the
        batched step it rode: accumulate the even split on every rider, and
        make the batch-step span (annotated with slot membership) a stored
        one when any rider is sampled. ``riders`` is empty with tracing off."""
        if not riders:
            return
        share = (whole.end_s - whole.start_s) / len(riders)
        any_sampled = False
        for _slot, req in riders:
            req.decode_steps += 1
            req.decode_share_s += share
            if req.trace is not None and req.trace.sampled:
                any_sampled = True
        if any_sampled:
            whole.record = True
            whole.attrs = {
                "slot_req_ids": {str(slot): req.req_id for slot, req in riders},
                "n_active": len(riders),
                "share_s": round(share, 9),
            }

    def close_step(self, deliver_s: float = 0.0) -> None:
        """Close the last step's stall accounting; whoever drives the engine
        calls it when the pump around the step is over (``ServingFrontend``
        hands in its ``frontend.deliver`` seconds). Driven bare, the next
        ``step()`` (and ``run()`` at its end) closes it with no delivery time.

        A step whose wall time (the four phases + delivery) exceeds
        ``_STALL_FACTOR`` x the median of the last ``_STALL_WINDOW`` steps is
        a stall: what its host part (plan + launch + commit + deliver) and
        its wait lie above their own medians goes to ``stats["stall_s.host"]``
        / ``["stall_s.device"]``, and ONE flight-recorder event ``step_stall``
        carries each phase's wall seconds, the sub-phases' (``put_s`` +
        ``args_s`` + ``call_s`` = ``launch_s``, ``ready_s`` + ``fetch_s`` =
        ``wait_s``: a wait that lies in ``ready_s`` is the executable's, one
        in ``fetch_s`` the copy of a finished result) and, over the stretch
        from the previous step's close to this one's, the wall seconds
        (``since_close_s``) beside the calling thread's CPU seconds
        (``cpu_s``) — wall far above CPU: the thread was descheduled or
        blocked (a shared host, a lock); wall about CPU: the program's own
        Python ran that long. Steps that compiled or recovered never get
        here. A step costs one ``thread_time`` read (a system call: the one
        dear clock here), two float adds and a deque append; the median is
        refreshed every ``_STALL_REFRESH`` steps."""
        st = self._open_step
        if st is None:
            return
        self._open_step = None
        plan_s, launch_s, wait_s, commit_s, put_s, args_s, call_s, ready_s, fetch_s = st
        host_s = plan_s + launch_s + commit_s + deliver_s
        wall_s = host_s + wait_s
        walls = self._step_walls
        limit = self._stall_limit
        mark, self._close_mark = self._close_mark, (time.perf_counter(), time.thread_time())
        if limit is not None and wall_s > limit:
            hosts = sorted(h for h, _ in walls)
            waits = sorted(w for _, w in walls)
            host_x = max(0.0, host_s - hosts[len(hosts) // 2])
            wait_x = max(0.0, wait_s - waits[len(waits) // 2])
            self.stats["stall_s.host"] += host_x
            self.stats["stall_s.device"] += wait_x
            self.stats["stall_steps"] += 1
            now = self._close_mark
            self._flight.record(
                "step_stall", step=self.stats["steps"],
                wall_s=round(wall_s, 6), median_wall_s=round(limit / _STALL_FACTOR, 6),
                plan_s=round(plan_s, 6), launch_s=round(launch_s, 6),
                wait_s=round(wait_s, 6), commit_s=round(commit_s, 6),
                deliver_s=round(deliver_s, 6),
                put_s=round(put_s, 6), args_s=round(args_s, 6), call_s=round(call_s, 6),
                ready_s=round(ready_s, 6), fetch_s=round(fetch_s, 6),
                stall_host_s=round(host_x, 6), stall_device_s=round(wait_x, 6),
                since_close_s=None if mark is None else round(now[0] - mark[0], 6),
                cpu_s=None if mark is None else round(now[1] - mark[1], 6),
            )
        walls.append((host_s, wait_s))
        self._stall_age += 1
        if self._stall_age >= _STALL_REFRESH and len(walls) >= _STALL_REFRESH:
            self._stall_age = 0
            both = sorted(h + w for h, w in walls)
            self._stall_limit = _STALL_FACTOR * both[len(both) // 2]

    def recover(self) -> None:
        """Rebuild device KV state after a dispatch failure consumed the
        donated cache buffers: reallocate the per-layer pools, reset the
        block allocator AND the prefix cache (its chain nodes point at lost
        KV), then re-prefill and replay every live slot from host-side truth
        (``InferenceRequest`` holds the prompt and every token generated so
        far). Request ids, emitted tokens, the waiting queue and pending
        finished deliveries are all preserved. Slots are re-prefilled ONE AT
        A TIME so slots sharing a prefix re-share it through the fresh cache
        (recovery can never need more blocks than the original admissions).

        The rebuilt buffers have identical shapes/dtypes, so the compiled
        program is reused — a recovery must not add compiles (the recompile
        watchdog still reports exactly 1 for this engine)."""
        from paddle_tpu.incubate.nn.functional import BlockKVCache

        live = [(i, req) for i, req in enumerate(self._slot_req) if req is not None]
        # chunked prefill means a live slot may be MID-PROMPT (no token
        # emitted yet): capture its progress before the reset so the replay
        # restores exactly the prefilled span, not the whole prompt
        prior_prefill = {
            i: int(min(self._ntok[i], req.prompt.size)) for i, req in live
        }
        t_recover = time.perf_counter()
        self._flight.record(
            "recovery", live=len(live), queued=len(self._waiting),
            recoveries=self.stats["recoveries"] + 1,
        )
        # identical shapes/dtypes/shardings (tp pools come back committed on
        # the same mesh partition) -> the compiled program is reused
        self._caches = self._new_pools()
        self._states = self._new_states()  # the replay below rebuilds every live slot's
        self._mgr = BlockKVCache(
            self.num_blocks, self.block_size, self._kvh, self._hd,
            self.max_blocks_per_seq, dtype=self._cache_dtype,
        )
        self._cache = self._new_prefix_cache()
        for i in range(self.max_slots):
            self._blocks[i] = []
            self._nodes[i] = []
            self._no_insert[i] = False
            self._pending_cow[i] = None
            # drop the in-flight prefetch set: its markers reference the
            # lost buffers. The HOST TIER ITSELF survives (host RAM was not
            # consumed) — it is part of the host truth this rebuild draws
            # from, so replayed prompts matching spilled chains prefetch
            # them into the fresh pools instead of recomputing.
            self._prefetch_wait[i] = None
        self._matched_blocks[:] = 0
        self._ntok[:] = 0
        self._last_tok[:] = 0
        self._reserved[:] = 0
        self.stats["recoveries"] += 1
        self._metrics["recoveries"].inc()

        # phase 1: re-prefill each live slot's prompt through the SAME
        # unified signature (chunked; a retrace here would be a bug and is
        # recorded so the 1-compile invariant test catches it); one slot at
        # a time so the fresh prefix cache re-deduplicates shared prefixes
        C = self.prefill_chunk
        for slot, req in live:
            self._match_and_map(req, slot)
            plen = req.prompt.size
            # a slot that never emitted replays only its prior prefill span
            # (the normal step flow finishes the prompt afterwards); a
            # decode-phase slot replays the whole prompt. The fresh cache may
            # map MORE than the prior span — cached KV is real content.
            target = plen if req.generated else prior_prefill[slot]
            while int(self._ntok[slot]) < target:
                toks = np.zeros((self.max_slots, C), np.int32)
                q_lens = np.zeros((self.max_slots,), np.int32)
                active = np.zeros((self.max_slots,), bool)
                cur = int(self._ntok[slot])
                n = min(C, target - cur)
                toks[slot, :n] = req.prompt[cur : cur + n]
                q_lens[slot] = n
                active[slot] = True
                self._dispatch(toks, q_lens, active)
            # the re-emitted first token is identical by determinism; host
            # truth is authoritative either way (the request already holds it)
            if req.generated:
                self._last_tok[slot] = req.generated[0]
            self._metrics["replayed"].inc()

        # phase 2: lockstep replay of already-generated tokens (one decode
        # row per catching-up slot per dispatch) — the KV append is the
        # effect we need; the re-emitted next tokens are discarded in favor
        # of the recorded ones
        max_replay = max((len(req.generated) - 1 for _, req in live), default=0)
        for r in range(max_replay):
            replay_slots = [i for i, req in live if len(req.generated) - 1 > r]
            toks = np.zeros((self.max_slots, C), np.int32)
            q_lens = np.zeros((self.max_slots,), np.int32)
            active = np.zeros((self.max_slots,), bool)
            for i in replay_slots:
                toks[i, 0] = self._last_tok[i]
                q_lens[i] = 1
                active[i] = True
            self._dispatch(toks, q_lens, active)
            for i in replay_slots:
                req = self._slot_req[i]
                self._last_tok[i] = req.generated[r + 1]
        if _tracing.tracing_enabled():
            _tracing.GLOBAL_TRACER.add_span(
                "engine.recover", start_s=t_recover, end_s=time.perf_counter(),
                attrs={"replayed_slots": len(live), "replay_depth": max_replay},
            )
        self._update_pool_gauges()

    def run(self) -> Dict[int, InferenceRequest]:
        """Drain the queue; returns {req_id: request} for everything that
        finished DURING this call (results from earlier direct step() calls
        were already returned by those calls)."""
        out: Dict[int, InferenceRequest] = {}
        while self.has_work():
            for req in self.step():
                out[req.req_id] = req
        self.close_step()
        return out
