"""Process-global flag registry.

TPU-native counterpart of the reference's flag system (``paddle/common/flags.cc``,
179 ``PHI_DEFINE_EXPORTED_*`` flags; registry macros ``paddle/common/flags.h:93``):
a typed registry of named flags, settable programmatically via
``paddle_tpu.set_flags`` / readable via ``get_flags``, with ``FLAGS_<name>``
environment variables honoured at first read (matching the reference's env-var
export convention).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Union


@dataclass
class _Flag:
    name: str
    type: type
    default: Any
    help: str
    value: Any = None
    env_read: bool = False


class FlagRegistry:
    """Typed flag registry; thread-safe; env ``FLAGS_<name>`` seeds the value."""

    def __init__(self) -> None:
        self._flags: Dict[str, _Flag] = {}
        self._lock = threading.RLock()
        self._listeners: Dict[str, List[Callable[[Any], None]]] = {}

    def define(self, name: str, type_: type, default: Any, help_: str = "") -> None:
        with self._lock:
            if name in self._flags:
                raise ValueError(f"flag '{name}' already defined")
            self._flags[name] = _Flag(name=name, type=type_, default=default, help=help_, value=default)

    def on_change(self, name: str, callback: Callable[[Any], None]) -> None:
        """Register a callback fired with the new value whenever ``name`` is
        set (programmatically or by env seeding at first read). Lets hot paths
        cache a flag in a plain local instead of taking the registry lock per
        read — the metrics layer's near-zero-overhead gate."""
        with self._lock:
            self._listeners.setdefault(name, []).append(callback)

    def _notify(self, flag: _Flag) -> None:
        for cb in self._listeners.get(flag.name, ()):
            cb(flag.value)

    def _coerce(self, flag: _Flag, value: Any) -> Any:
        if flag.type is bool:
            if isinstance(value, str):
                return value.strip().lower() in ("1", "true", "yes", "on")
            return bool(value)
        return flag.type(value)

    def _maybe_read_env(self, flag: _Flag) -> None:
        if not flag.env_read:
            # mark BEFORE notifying: a listener that reads the flag back
            # (re-entrant under the RLock) must not re-enter seeding
            flag.env_read = True
            env = os.environ.get(f"FLAGS_{flag.name}")
            if env is not None:
                try:
                    flag.value = self._coerce(flag, env)
                except (TypeError, ValueError) as exc:
                    # un-mark so the error re-fires on EVERY read: if the first
                    # get() happens inside someone's broad except, the flag
                    # must not silently serve its default forever after
                    flag.env_read = False
                    # env seeding happens at the first get() of the flag, which
                    # can be deep inside unrelated code — name the flag and the
                    # env var so the malformed value is findable
                    raise ValueError(
                        f"invalid value {env!r} in environment variable "
                        f"FLAGS_{flag.name} for flag '{flag.name}' "
                        f"(expected {flag.type.__name__})"
                    ) from exc
                self._notify(flag)

    def get(self, name: str) -> Any:
        with self._lock:
            if name not in self._flags:
                raise KeyError(f"unknown flag '{name}'; known flags: {sorted(self._flags)}")
            flag = self._flags[name]
            self._maybe_read_env(flag)
            return flag.value

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            if name not in self._flags:
                raise KeyError(f"unknown flag '{name}'")
            flag = self._flags[name]
            flag.env_read = True
            try:
                flag.value = self._coerce(flag, value)
            except (TypeError, ValueError) as exc:
                raise ValueError(
                    f"invalid value {value!r} for flag '{name}' "
                    f"(expected {flag.type.__name__})"
                ) from exc
            self._notify(flag)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._flags)


GLOBAL_FLAGS = FlagRegistry()


def _define_builtin_flags() -> None:
    d = GLOBAL_FLAGS.define
    d("check_nan_inf", bool, False, "Scan op outputs for NaN/Inf after every eager op (debug).")
    d("check_nan_inf_level", int, 0, "0: raise on nan/inf; 1: warn; 3: collect stats only.")
    d("eager_op_cache_size", int, 4096, "Max entries in the eager per-op compiled-executable cache.")
    d("use_pallas_attention", bool, True, "Use Pallas flash-attention kernels on TPU when applicable.")
    d("use_pallas_fused", bool, True, "Use Pallas fused rms_norm/rope kernels on TPU when applicable.")
    d("use_pallas_paged_attention", bool, True, "Use the Pallas paged-attention kernel (the block-table page walk) on TPU.")
    d("use_fused_loss", bool, True, "Fuse the lm-head matmul with softmax cross-entropy at model training-loss sites (vocab-chunked, never materializes [B,S,V] logits; Pallas on TPU, lax.scan reference elsewhere). Models return (loss, None) on this path.")
    d("benchmark", bool, False, "Block on every op (sync dispatch) for timing.")
    d("log_memory_stats", bool, False, "Log live/peak device memory stats per allocation event.")
    d("allocator_strategy", str, "xla", "Allocator backing; on TPU the XLA/PJRT allocator owns HBM.")
    d("cudnn_deterministic", bool, False, "Deterministic op selection (maps to XLA determinism flags).")
    d("embedding_deterministic", int, 0, "Deterministic embedding grad accumulation level.")
    d("init_allocated_mem", bool, False, "Compat no-op: PJRT zero-initialises buffers.")
    d("max_inflight_ops", int, 256, "Async eager dispatch depth before forcing a sync.")
    d("flash_attn_version", int, 2, "Flash-attention algorithm family for the Pallas kernels.")
    d("dist_timeout_seconds", int, 1800, "Collective watchdog timeout (comm_task_manager parity).")
    d("tracer_mkldnn_ops_on", str, "", "Compat no-op on TPU.")
    d("use_stride_kernel", bool, False, "Compat: XLA owns layouts; stride kernels do not apply.")
    # observability layer (reference: the exported-flags + profiler surface,
    # SURVEY §5.1); registered here so env seeding works before the
    # paddle_tpu.observability import runs
    d("enable_metrics", bool, False, "Record runtime metrics (counters/gauges/histograms) into the global registry; off = every recording call is a no-op.")
    d("trace_sample_rate", float, 0.0, "Head-sampling probability (0..1) for per-request distributed tracing (observability.tracing). 0 disables tracing entirely — every trace call site then costs one cached-bool read.")
    d("trace_seed", int, 0, "Seed for the global tracer's id/sampling RNG: the same seed + request sequence reproduces the same sampling decisions and span ids.")
    d("trace_buffer_size", int, 4096, "Capacity of the tracer's bounded in-process span store (newest spans win); read when a Tracer is constructed.")
    d("flight_recorder_size", int, 1024, "Ring capacity of the always-on flight recorder: how many recent structured events the black box retains for postmortem dumps.")
    d("flight_recorder_dir", str, "", "Directory for automatic flight-recorder dumps (engine permanent failure, watchdog timeout, pump death); empty = the system temp dir.")
    d("metrics_port", int, 0, "Serve Prometheus text exposition on this localhost port via observability.start_metrics_server(); 0 disables the endpoint.")
    d("max_compiles_per_fn", int, 16, "Recompile-watchdog budget: warn when one traced function RE-compiles (compiles past its first_call traces) more than this many times; 0 disables the warning.")
    # fault-tolerance layer (registered here so env seeding works before the
    # paddle_tpu.testing.faults import runs; empty = injection fully off)
    d("fault_inject_plan", str, "", "Deterministic fault-injection plan: 'site:call_index:ExceptionName' entries joined by ';' (see testing/faults.py). Empty disables injection; fault sites then cost one cached-bool read.")
    # serving front end (paddle_tpu/serving/): same opt-in localhost pattern
    # as metrics_port — nothing listens unless asked
    d("serving_port", int, 0, "Serve the streaming generation HTTP endpoint (serving.start_serving_server) on this localhost port; 0 disables the endpoint.")
    # prefix-cache KV subsystem (inference/prefix_cache.py): content-hash
    # block dedup with copy-on-write over the paged pool; read at engine
    # construction (per-engine override via the enable_prefix_cache kwarg)
    d("enable_prefix_cache", bool, True, "Reference-counted content-hash KV block dedup for the continuous-batching engine: shared prompt prefixes are computed once and mapped copy-on-write into every request that repeats them; off = every prompt recomputes from token zero.")
    # hierarchical KV tier (inference/kv_tier.py): host-RAM spill tier under
    # the prefix cache; read at engine construction (per-engine override via
    # the kv_host_tier_bytes kwarg)
    d("kv_host_tier_bytes", int, 0, "Byte budget of the host-RAM KV spill tier under the prefix cache: LRU-evicted zero-reference chain blocks spill D2H into a bounded host pool instead of dying, and a prefix match against a spilled chain prefetches its blocks H2D asynchronously, overlapped with the chunked prefill of the uncached suffix. 0 (default) disables the tier — evicted chains are simply dropped, today's behavior. Greedy outputs are byte-identical with the tier on or off.")
    # speculative decoding (inference/spec_decode.py): n-gram self-speculation
    # riding the engine's one compiled mixed ragged step; read at engine
    # construction (per-engine override via the spec_decode kwarg)
    d("spec_decode", bool, False, "Self-speculative decoding on the continuous-batching engine: an n-gram prompt-lookup drafter proposes draft tokens per decode slot; drafts ride the SAME [max_slots, prefill_chunk] compiled step as prompt chunks (verification is data — zero new compiled signatures), accepted tokens commit in bulk, the first rejection rewinds the slot's block table. Greedy outputs are byte-identical on or off.")
    d("spec_decode_ngram", int, 3, "Longest n-gram of the request's prompt+generated history the speculative drafter matches (walks down to 1); read at engine construction.")
    d("spec_decode_tokens", int, 4, "Max draft tokens proposed per slot per step, capped at prefill_chunk - 1 so the draft plus the mandatory last-token row fit the engine's compiled chunk width.")
    # quantized serving (inference/engine.py + kernels/quant.py): int8 KV
    # blocks with in-kernel dequant, and weight-only int8 projections; both
    # read at engine construction — the compiled step signature stays ONE
    # either way (dtype changes the pool buffers, never the step shape)
    d("kv_cache_dtype", str, "bf16", "Storage dtype of the paged KV block pool: 'bf16' (default; byte-identical to the unquantized path) or 'int8' (symmetric per-token absmax quant applied inside the same fused append/CoW/prefetch writes; a per-block-per-head-per-slot fp32 scale table rides the pool through every lifecycle seam — refcounts, CoW, spill/prefetch, recovery, tp head-sharding — and dequant folds into the paged attention block walk, so no dequantized copy ever materializes). Halves KV HBM and host-tier bytes; greedy quality is gated by the bench quality-delta record.")
    d("weight_only_int8", bool, False, "Weight-only int8 for the lm-head and MLP projections (inference-only): matching nn.Linear weights are quantized once host-side with per-output-channel scales, the scales ride the compiled step as extra trailing params (signature stays fixed), and matmuls dispatch to the Pallas int8xbf16 dot kernel (kernels/quant.py) with an XLA dequant-matmul fallback in numeric lockstep.")
    # tensor-parallel serving (distributed/tp.py): shard the engine's one
    # compiled step over a ['tp'] device mesh; read at engine construction
    # (per-engine override via the tp kwarg)
    d("engine_tp_degree", int, 1, "Tensor-parallel degree of the continuous-batching engine: attention heads and the paged KV block pool partition per device along a single-axis ['tp'] mesh, MLP splits Megatron-style (one all-reduce per layer), the lm-head shards over vocab. 1 = single-chip engine (byte-identical to the unsharded path). Must divide the model's KV heads; needs that many visible devices.")
    # fleet observability (observability/slo.py + aggregate.py): the SLO
    # burn-rate monitor riding the cluster router's probe loop, and the
    # coordinated incident snapshots it (and the death seams) write. Read
    # when an SLOConfig / ClusterObserver is constructed, never per tick.
    d("slo_ttft_p99_target_s", float, 1.0, "SLO target for the cluster-level TTFT p99 (seconds): the burn-rate monitor's ttft signal is the observed windowed p99 divided by this.")
    d("slo_goodput_target", float, 0.9, "SLO target fraction of terminals that finish ok INSIDE their deadline; the monitor's slo-violation burn rate is the windowed violation fraction divided by the remaining error budget (1 - target).")
    d("slo_shed_budget", float, 0.1, "Error budget for the shed rate: fraction of terminals allowed to end in any non-ok outcome before the shed burn rate reads 1.0.")
    d("slo_failover_budget", float, 0.1, "Error budget for the failover rate: re-dispatch attempts per routing dispatch allowed before the failover burn rate reads 1.0.")
    d("slo_fast_window_s", float, 5.0, "Fast burn-rate window (seconds). A state escalates only when BOTH the fast and slow windows burn past a threshold — the fast window catches the onset, the slow window proves it is sustained.")
    d("slo_slow_window_s", float, 60.0, "Slow burn-rate window (seconds); see slo_fast_window_s.")
    d("slo_warn_burn", float, 1.0, "Burn-rate threshold that latches WARN (hysteresis: releases at half this value). Burn 1.0 = consuming the error budget exactly as fast as allowed.")
    d("slo_page_burn", float, 4.0, "Burn-rate threshold that latches PAGE (hysteresis: releases at half this value); entering PAGE writes a coordinated incident snapshot.")
    d("slo_min_terminals", int, 8, "Minimum terminals inside a window before its budget-based burn rates are trusted (the ttft signal is exempt); prevents paging on the first failed request of a quiet cluster.")
    d("incident_dir", str, "", "Directory for coordinated cluster incident snapshots (observability/aggregate.py): one sub-directory per incident with every replica's flight ring, the router's routing log, sampled spans and the cluster health view. Empty = flight_recorder_dir, else the system temp dir.")
    d("incident_cooldown_s", float, 30.0, "Minimum seconds between two incident snapshots for the SAME reason (a flapping replica must not fill the disk with identical postmortems).")
    # device-time attribution (observability/devprof.py): per-step cost
    # profiles, host-bubble decomposition, measured comm share
    d("devprof_sample_rate", float, 0.0, "Fraction of engine steps profiled by the device-time attribution layer (observability/devprof.py): a sampled step is timed device-sync-honest, decomposed into host-prep / dispatch-gap / device segments, and its device time apportioned across attention/matmul/collective/other using the compile-time cost profile as the attribution prior. 0 (default) disables profiling entirely — every step then costs one cached-bool read — and deterministic stride sampling (no RNG draw) picks steps at partial rates. Rate > 0 also arms compile-time cost-profile capture (an introspective AOT lowering per compiled signature, paid once per compile).")
    d("devprof_timeline_size", int, 256, "Capacity of each engine's bounded step-timeline ring (devprof): how many recent sampled step profiles are retained for /healthz, incident snapshots and the dump CLI; newest win.")


_define_builtin_flags()


def set_flags(flags: Dict[str, Any]) -> None:
    """Set one or more global flags. Mirrors ``paddle.set_flags``."""
    for k, v in flags.items():
        GLOBAL_FLAGS.set(k.removeprefix("FLAGS_"), v)


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """Read one, several, or all global flags. Mirrors ``paddle.get_flags``."""
    if flags is None:
        names: Iterable[str] = GLOBAL_FLAGS.names()
    elif isinstance(flags, str):
        names = [flags]
    else:
        names = flags
    return {n: GLOBAL_FLAGS.get(n.removeprefix("FLAGS_")) for n in names}


def define_flag(name: str, type_: type, default: Any, help_: str = "") -> None:
    """Register a new flag (used by subsystems at import time)."""
    GLOBAL_FLAGS.define(name, type_, default, help_)
