"""jit capture: trace Tensor programs into compiled XLA executables.

TPU-native counterpart of the reference's ``paddle.jit.to_static`` + CINN
(SURVEY §3.3): where the reference intercepts bytecode (SOT) or rewrites ASTs
to build a Program, here the Tensor ops are already pure jax functions, so
**Python tracing under jax.jit is the whole capture machinery** — no bytecode
interpreter needed, and XLA plays the role of CINN/PirInterpreter.

State threading: a traced function may mutate framework state — Layer
parameters (optimizer updates), buffers (batch-norm running stats), optimizer
accumulators. ``StaticFunction`` discovers Layers/Optimizers reachable from
the call, passes their arrays as inputs, restores them as outputs, and donates
the input buffers — so a full train step (forward + loss.backward() +
opt.step()) compiles into ONE XLA program with in-place buffer reuse. This is
the analog of the reference's whole-program Program + executor path, minus the
hand-rolled interpreter.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import autograd as _ag
from paddle_tpu.core.spmd import LAYOUT_UNKNOWN, partitioned_trace, spans_devices
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability.recompile import (
    CAUSE_FIRST_CALL,
    CAUSE_MODE_FLIP,
    CAUSE_NEW_SHAPE_DTYPE,
    GLOBAL_WATCHDOG,
)

# trace failures that mean "this fragment is not capturable", not user bugs:
# a tracer leaked into Python control flow / indexing / int conversion
_TRACE_BREAK_ERRORS = (
    jax.errors.ConcretizationTypeError,  # includes TracerBoolConversionError
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerIntegerConversionError,
    jax.errors.NonConcreteBooleanIndexError,
)

__all__ = ["to_static", "StaticFunction", "not_to_static", "ignore_module"]


def _is_tensor(x: Any) -> bool:
    return isinstance(x, Tensor)


class _StateSpec:
    """The mutable framework state captured by one trace: ordered tensors
    (params/buffers) and optimizer accumulator slots."""

    def __init__(self) -> None:
        self.tensors: List[Tensor] = []
        self.optimizers: List[Any] = []
        self._seen: set = set()

    def add_tensor(self, t: Tensor) -> None:
        if id(t) not in self._seen:
            self._seen.add(id(t))
            self.tensors.append(t)

    def add_layer(self, layer: Any) -> None:
        for p in layer.parameters():
            self.add_tensor(p)
        for b in layer.buffers():
            self.add_tensor(b)

    def add_optimizer(self, opt: Any) -> None:
        if id(opt) in self._seen:
            return
        self._seen.add(id(opt))
        self.optimizers.append(opt)
        for p in opt._parameters:
            self.add_tensor(p)
        # Materialize accumulators now so they are trace inputs, not baked
        # constants (single compilation instead of two).
        for p in opt._parameters:
            if not p.stop_gradient:
                opt._state_for(p)

    def snapshot(self) -> Tuple[List[Any], List[Dict[str, Any]], Any]:
        import paddle_tpu.core.rng as _rng

        tensor_arrays = [t._data for t in self.tensors]
        opt_states = []
        for opt in self.optimizers:
            if opt._step_buf is None:
                opt._step_buf = jnp.zeros((), jnp.int32)
            acc = {}
            for p in opt._parameters:
                st = opt._accumulators.get(id(p))
                if st is not None:
                    acc[p.name] = st
            opt_states.append({"step": opt._step_buf, "acc": acc, "lr": jnp.asarray(opt.get_lr(), jnp.float32)})
        # The global PRNG key is threaded as state so random ops (dropout)
        # draw fresh masks on every call of the compiled program.
        rng_key = _rng.default_generator()._key
        return tensor_arrays, opt_states, rng_key

    def bind(self, tensor_arrays: Sequence[Any], opt_states: Sequence[Dict[str, Any]], rng_key: Any, tracing: bool) -> None:
        import paddle_tpu.core.rng as _rng

        for t, arr in zip(self.tensors, tensor_arrays):
            t._data = arr
        for opt, st in zip(self.optimizers, opt_states):
            opt._step_buf = st["step"]
            for p in opt._parameters:
                if p.name in st["acc"]:
                    opt._accumulators[id(p)] = st["acc"][p.name]
            opt._lr_array = st["lr"] if tracing else None
        _rng.default_generator()._key = rng_key

    def readback(self) -> Tuple[List[Any], List[Dict[str, Any]], Any]:
        import paddle_tpu.core.rng as _rng

        tensor_arrays = [t._data for t in self.tensors]
        opt_states = []
        for opt in self.optimizers:
            acc = {}
            for p in opt._parameters:
                st = opt._accumulators.get(id(p))
                if st is not None:
                    acc[p.name] = st
            opt_states.append({"step": opt._step_buf, "acc": acc, "lr": jnp.zeros((), jnp.float32)})
            opt._lr_array = None
        return tensor_arrays, opt_states, _rng.default_generator()._key


def _discover_state(objs: Sequence[Any]) -> _StateSpec:
    from paddle_tpu.nn.layer.layers import Layer
    from paddle_tpu.optimizer.optimizer import Optimizer

    spec = _StateSpec()
    for obj in objs:
        # unwrap optimizer wrappers (DygraphShardingOptimizer,
        # HybridParallelOptimizer) down to the stateful inner Optimizer
        while not isinstance(obj, Optimizer) and hasattr(obj, "_inner_opt"):
            obj = obj._inner_opt
        if isinstance(obj, Optimizer):
            spec.add_optimizer(obj)
    for obj in objs:
        if isinstance(obj, Layer):
            spec.add_layer(obj)
    return spec


class StaticFunction:
    """Callable wrapping a traced+compiled program cache
    (reference ``dy2static/program_translator.py`` StaticFunction parity)."""

    def __init__(self, fn: Callable, input_spec: Any = None, build_strategy: Any = None, full_graph: bool = True) -> None:
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._input_spec = input_spec
        self._cache: Dict[Any, Any] = {}
        self._bound_self = getattr(fn, "__self__", None)
        # full_graph=False is the SOT analog (reference jit/sot/translate.py:
        # guard-based capture with graph breaks): on an untraceable fragment
        # (data-dependent Python control flow) the call falls back to eager
        # for that guard key instead of raising; the key set below is the
        # guard cache, so later calls with the same signature skip the
        # doomed re-trace.
        self._full_graph = bool(full_graph)
        self._eager_keys: set = set()
        # every key ever traced (never popped, unlike _cache): the recompile
        # watchdog's attribution history — a later key differing ONLY in the
        # training tuple is a train/eval mode flip, not a new shape bucket
        self._compiled_keys: set = set()

    @property
    def function(self) -> Callable:
        return self._fn

    def __get__(self, instance: Any, owner: Any = None) -> "StaticFunction":
        if instance is None:
            return self
        # Cache the bound wrapper on the instance so the compiled-program cache
        # survives across attribute accesses.
        name = getattr(self._fn, "__name__", "forward")
        cached = instance.__dict__.get(f"__static_{name}__")
        if cached is None:
            cached = StaticFunction(
                self._fn.__get__(instance, owner), self._input_spec,
                full_graph=self._full_graph,
            )
            instance.__dict__[f"__static_{name}__"] = cached
        return cached

    def _cache_key(self, flat_in: Sequence[Any], treedef: Any, state: _StateSpec, scan_objs: Sequence[Any]) -> Any:
        from paddle_tpu.nn.layer.layers import Layer

        sig = []
        for leaf in flat_in:
            if isinstance(leaf, Tensor):
                sig.append(("T", tuple(leaf.shape), str(jnp.dtype(leaf.dtype))))
            elif isinstance(leaf, (jax.Array,)):
                sig.append(("A", tuple(leaf.shape), str(leaf.dtype)))
            else:
                sig.append(("S", repr(leaf)))
        # training flags of every reachable (sub)layer: train()/eval() bakes
        # different dropout/batch-norm programs, so mode changes must retrace.
        training = []
        for obj in scan_objs:
            if isinstance(obj, Layer):
                training.append(obj.training)
                training.extend(l.training for l in obj.sublayers())
        # last: whether state or inputs span devices. The traced body asks
        # whether GSPMD will split it (core/spmd.py), which neither the
        # shapes above nor jit's own trace key (avals) carry — a model
        # re-placed in place (same Tensor ids) must retrace
        partitioned = any(spans_devices(t._data) for t in state.tensors) or any(
            spans_devices(l._data if isinstance(l, Tensor) else l) for l in flat_in
        )
        return (treedef, tuple(sig), tuple(id(t) for t in state.tensors), tuple(training), partitioned)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)
        scan_objs = list(args) + list(kwargs.values())
        if self._bound_self is not None:
            scan_objs.append(self._bound_self)
        state = _discover_state(scan_objs)
        key = self._cache_key(leaves, treedef, state, scan_objs)
        partitioned = key[-1]

        if key in self._eager_keys:  # guard cache: known graph break
            return self._fn(*args, **kwargs)

        tensor_pos = [i for i, l in enumerate(leaves) if isinstance(l, (Tensor, jax.Array))]
        in_arrays = [leaves[i]._data if isinstance(leaves[i], Tensor) else leaves[i] for i in tensor_pos]
        state_arrays, opt_states, rng_key = state.snapshot()

        cache_miss = key not in self._cache
        if cache_miss:
            fn = self._fn

            def staged(state_arrays_, opt_states_, rng_key_, in_arrays_):
                import paddle_tpu.core.rng as _rng

                # snapshot .grad alongside ._data: a trace that fails AFTER
                # backward() has already written tracer-valued grads into the
                # live Parameters — restoring only _data would hand the
                # graph-break eager re-run (and grad accumulation) leaked
                # tracers that poison every later op
                saved = [(t, t._data, t._grad) for t in state.tensors]
                saved_opt = [
                    (opt, opt._step_buf, dict(opt._accumulators), opt._lr_array)
                    for opt in state.optimizers
                ]
                saved_rng = _rng.default_generator()._key
                try:
                    state.bind(state_arrays_, opt_states_, rng_key_, tracing=True)
                    rebuilt = list(leaves)
                    for pos, arr in zip(tensor_pos, in_arrays_):
                        orig = leaves[pos]
                        if isinstance(orig, Tensor):
                            t = Tensor(arr, stop_gradient=orig.stop_gradient)
                            rebuilt[pos] = t
                        else:
                            rebuilt[pos] = arr
                    a, k = jax.tree_util.tree_unflatten(treedef, rebuilt)
                    # runs whenever jax traces: this cache's misses and jit's
                    # own re-traces (e.g. once the optimizer state exists)
                    with partitioned_trace(LAYOUT_UNKNOWN) if partitioned else contextlib.nullcontext():
                        out = fn(*a, **k)
                    out_arrays = jax.tree_util.tree_map(
                        lambda o: o._data if isinstance(o, Tensor) else o,
                        out,
                        is_leaf=_is_tensor,
                    )
                    new_state, new_opt, new_rng = state.readback()
                    return out_arrays, new_state, new_opt, new_rng
                finally:
                    for t, d, g in saved:
                        t._data = d
                        t._grad = g
                    for opt, sb, acc, lra in saved_opt:
                        opt._step_buf = sb
                        opt._accumulators = acc
                        opt._lr_array = lra
                    _rng.default_generator()._key = saved_rng

            self._cache[key] = jax.jit(staged, donate_argnums=(0, 1))

        try:
            out_arrays, new_state, new_opt, new_rng = self._cache[key](
                state_arrays, opt_states, rng_key, in_arrays
            )
        except _TRACE_BREAK_ERRORS as exc:
            self._cache.pop(key, None)
            if self._full_graph:
                raise
            # graph break (reference SOT's fallback-to-eager): drop the doomed
            # compile-cache entry, remember the guard key, run eagerly
            import warnings

            self._eager_keys.add(key)
            warnings.warn(
                f"to_static({getattr(self._fn, '__name__', '?')}): graph break — "
                f"falling back to eager for this input signature "
                f"({type(exc).__name__}); pass full_graph=True to make this an error",
                stacklevel=2,
            )
            return self._fn(*args, **kwargs)
        except BaseException:  # any first-exec failure must uncache; see below
            if cache_miss:
                # the first execution failed past the trace-break net (XLA
                # runtime error, data-dependent check): drop the entry so a
                # retry re-traces and the watchdog records the compile —
                # otherwise the cached program serves forever uncounted
                self._cache.pop(key, None)
            raise
        # Commit mutated state back into the framework objects.
        import paddle_tpu.core.rng as _rng

        with _ag.set_grad_enabled(False):
            for t, arr in zip(state.tensors, new_state):
                t._data = arr
            for opt, st in zip(state.optimizers, new_opt):
                opt._step_buf = st["step"]
                for p in opt._parameters:
                    if p.name in st["acc"]:
                        opt._accumulators[id(p)] = st["acc"][p.name]
                opt._step_count += 1
            # the key comes back replicated over the step's mesh; committing
            # it that way would silently place every LATER tensor creation on
            # the mesh (fresh layers, exports, ... inherit 8-device
            # shardings). Round-trip the 16-byte key through host so it
            # becomes an UNCOMMITTED default-device array — compatible with
            # both later single-device work and the next sharded step.
            sharding = getattr(new_rng, "sharding", None)
            if sharding is not None and len(getattr(sharding, "device_set", ())) > 1:
                import numpy as _np

                new_rng = jnp.asarray(_np.asarray(new_rng))
            _rng.default_generator()._key = new_rng
        if cache_miss:
            # record only HERE — after the trace succeeded AND state was
            # committed: a graph break above never produced a compiled
            # program, and a RecompileBudgetWarning escalated to an error
            # (warnings-as-errors) must not be conflated with an execution
            # failure — at this point the donated buffers' replacements are
            # already committed and the cache entry stays valid
            if not self._compiled_keys:
                cause = CAUSE_FIRST_CALL
            elif any(
                k[:3] == key[:3] and k[3] != key[3] for k in self._compiled_keys
            ):
                cause = CAUSE_MODE_FLIP
            else:
                cause = CAUSE_NEW_SHAPE_DTYPE
            self._compiled_keys.add(key)
            jitted = self._cache.get(key)

            def _cost_thunk(_jitted=jitted):
                # devprof cost capture (runs only at devprof_sample_rate>0):
                # an introspective AOT lowering of the program just compiled.
                # Built from ShapeDtypeStructs, not the live arrays — argnums
                # (0, 1) are donated, so on TPU the input buffers are already
                # consumed; avals survive donation (shape/dtype metadata is
                # readable on deleted arrays) and .lower takes them directly.
                abst = lambda a: (  # noqa: E731 - local one-liner
                    jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") and hasattr(a, "dtype")
                    else a
                )
                if _jitted is None:
                    return None
                return _jitted.lower(
                    *jax.tree_util.tree_map(
                        abst, (state_arrays, opt_states, rng_key, in_arrays)
                    )
                ).compile().cost_analysis()

            GLOBAL_WATCHDOG.record_compile(
                getattr(self._fn, "__qualname__", None)
                or getattr(self._fn, "__name__", "<fn>"),
                signature=key[1],
                cause=cause,
                cost_thunk=_cost_thunk,
            )
        return jax.tree_util.tree_map(
            lambda o: Tensor(o) if isinstance(o, jax.Array) else o, out_arrays
        )

    def concrete_program(self) -> Any:  # pragma: no cover - introspection aid
        return self._cache


def to_static(
    function: Optional[Callable] = None,
    input_spec: Any = None,
    build_strategy: Any = None,
    backend: Any = None,
    full_graph: bool = True,
    **kwargs: Any,
) -> Any:
    """``paddle.jit.to_static`` parity (reference ``python/paddle/jit/api.py:195``)."""

    def deco(fn: Callable) -> StaticFunction:
        if isinstance(fn, StaticFunction):
            return fn
        from paddle_tpu.nn.layer.layers import Layer

        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec, full_graph=full_graph)
            return fn
        return StaticFunction(fn, input_spec, build_strategy, full_graph)

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn: Callable) -> Callable:
    fn.__paddle_tpu_not_to_static__ = True  # type: ignore[attr-defined]
    return fn


def ignore_module(modules: Any) -> None:
    """Compat no-op: tracing has no module blacklist needs."""
