"""Eager autograd engine: a reverse-mode tape over ``jax.vjp``.

TPU-native counterpart of the reference's dygraph autograd
(``paddle/fluid/eager``): ``GradNode`` ≈ ``egr::GradNodeBase``
(``grad_node_info.h:197``), ``backward`` ≈ ``egr::RunBackward``
(``backward.cc:105``). Instead of per-op hand-written grad kernels, each node
captures the ``vjp`` of its forward function at dispatch time (residuals live
on device, like the reference's ``TensorWrapper`` saved tensors), and backward
is a topological sweep over the node DAG with in-degree counting — the same
queue algorithm as ``RunBackward``.
"""

from __future__ import annotations

import threading
from collections import defaultdict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import contextlib

import jax
import numpy as np
from jax._src import source_info_util as _source_info  # no public reader of the name stack

from paddle_tpu.errors import InvalidArgumentError, PreconditionNotMetError

_NO_SCOPE = contextlib.nullcontext()

_grad_state = threading.local()


def current_grad_capture() -> Optional[set]:
    """The innermost active sweep's only-inputs capture set, or None when the
    active sweep (if any) is a plain ``backward()`` accumulate sweep. Nested
    sweeps (e.g. recompute's inner re-differentiation running inside an outer
    ``grad()``) use this to inherit only-inputs semantics so parameter grads
    are not side-effected through a segment boundary."""
    stack = getattr(_grad_state, "capture_stack", None)
    return stack[-1] if stack else None


def is_grad_enabled() -> bool:
    return getattr(_grad_state, "enabled", True)


def _set_enabled(value: bool) -> None:
    _grad_state.enabled = value


class set_grad_enabled:  # noqa: N801 - context-manager API parity
    def __init__(self, mode: bool) -> None:
        self._mode = bool(mode)
        self._prev: Optional[bool] = None

    def __enter__(self) -> "set_grad_enabled":
        self._prev = is_grad_enabled()
        _set_enabled(self._mode)
        return self

    def __exit__(self, *exc: Any) -> None:
        _set_enabled(self._prev if self._prev is not None else True)

    def __call__(self, fn: Callable) -> Callable:
        import functools

        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with set_grad_enabled(self._mode):
                return fn(*args, **kwargs)

        return wrapper


class no_grad(set_grad_enabled):  # noqa: N801
    """Disable gradient recording (``paddle.no_grad`` parity)."""

    def __init__(self, fn: Optional[Callable] = None) -> None:
        super().__init__(False)
        self._fn = fn

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._fn is not None:
            with set_grad_enabled(False):
                return self._fn(*args, **kwargs)
        return super().__call__(*args, **kwargs)


class enable_grad(set_grad_enabled):  # noqa: N801
    def __init__(self) -> None:
        super().__init__(True)


class GradNode:
    """One recorded op on the tape.

    Holds the ``vjp`` closure produced at dispatch, the tensors it must route
    input-gradients to, and the output avals needed to materialize zero
    cotangents for outputs that received no upstream gradient (the reference
    zero-fills via ``GradTensorHolder``).
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "input_tensors",
        "out_avals",
        "fwd_fn",
        "out_treedef",
        "released",
        "input_snapshots",
        "scope",
        "__weakref__",
    )

    def __init__(
        self,
        name: str,
        vjp_fn: Callable,
        input_tensors: Sequence[Any],
        out_avals: Sequence[jax.ShapeDtypeStruct],
        fwd_fn: Optional[Callable] = None,
        out_treedef: Any = None,
    ) -> None:
        self.name = name
        self.vjp_fn = vjp_fn
        self.input_tensors = list(input_tensors)
        self.out_avals = list(out_avals)
        # Closed forward over the differentiable primals: lets create_graph
        # re-derive the vjp *through the dispatcher* so grads are themselves
        # taped (the analog of the reference's double-grad nodes generated by
        # eager_gen for ``create_graph=True``, ``backward.cc:450``).
        self.fwd_fn = fwd_fn
        self.out_treedef = out_treedef
        self.released = False
        # the jax.named_scope path the forward ran under ("" outside any):
        # the reverse sweep runs this node's vjp under the same names, so a
        # device trace files an op's backward with its forward
        stack = _source_info.current_name_stack()
        self.scope = str(stack) if stack.stack else ""
        # Snapshot the input BUFFERS at dispatch (reference TensorWrapper
        # saved tensors): create_graph re-derives the vjp from these, so an
        # in-place mutation after the forward (including the in-place op's
        # own rebind) cannot corrupt higher-order gradients. Zero copy —
        # jax arrays are immutable; this only pins the originals.
        self.input_snapshots = (
            [getattr(t, "_data", None) for t in input_tensors]
            if fwd_fn is not None
            else None
        )

    def release(self) -> None:
        """Drop residuals after backward (unless retain_graph)."""
        self.vjp_fn = None  # type: ignore[assignment]
        self.input_tensors = []
        self.fwd_fn = None
        self.input_snapshots = None
        self.released = True

    def __repr__(self) -> str:
        return f"GradNode({self.name}, n_inputs={len(self.input_tensors)})"


def _zero_cotangent(aval: jax.ShapeDtypeStruct) -> Any:
    if np.issubdtype(np.dtype(aval.dtype), np.floating) or np.issubdtype(
        np.dtype(aval.dtype), np.complexfloating
    ):
        return jax.numpy.zeros(aval.shape, aval.dtype)
    # Integer/bool outputs take symbolic-zero (float0) cotangents under jax.vjp.
    return np.zeros(aval.shape, jax.dtypes.float0)


def _coerce_cotangent(cot: Any, aval: jax.ShapeDtypeStruct) -> Any:
    """Match the cotangent to the node's recorded output aval (dtype casts can
    arise from AMP autocast boundaries)."""
    if cot is None:
        return _zero_cotangent(aval)
    if hasattr(cot, "dtype") and cot.dtype != jax.dtypes.float0 and np.dtype(cot.dtype) != np.dtype(aval.dtype):
        cot = cot.astype(aval.dtype)
    if hasattr(cot, "shape") and tuple(cot.shape) != tuple(aval.shape):
        cot = jax.numpy.broadcast_to(cot, aval.shape)
    return cot


def _accumulate(a: Any, b: Any) -> Any:
    if a is None:
        return b
    if b is None:
        return a
    return a + b


def _is_inexact(aval: jax.ShapeDtypeStruct) -> bool:
    return np.issubdtype(np.dtype(aval.dtype), np.inexact)


def _coerce_cot_tensor(cot: Any, aval: jax.ShapeDtypeStruct) -> Any:
    """Tensor-valued cotangent coercion for create_graph sweeps: every cast /
    broadcast goes through the dispatcher so it stays differentiable."""
    from paddle_tpu.core.dispatch import call_op

    if np.dtype(cot.dtype) != np.dtype(aval.dtype):
        cot = cot.astype(aval.dtype)
    if tuple(cot.shape) != tuple(aval.shape):
        shape = tuple(aval.shape)
        cot = call_op("broadcast_to", lambda x: jax.numpy.broadcast_to(x, shape), cot)
    return cot


def _dispatch_grad(node: GradNode, cot_tensors: Sequence[Any]) -> Tuple[Any, ...]:
    """Run ``node``'s backward as a *dispatched differentiable op*.

    Re-derives the vjp from the stored closed forward and applies the Tensor
    cotangents through ``call_op``, so the returned grads carry their own
    GradNodes (higher-order tape). ``cot_tensors`` holds one Tensor per
    inexact output, in output order; integer/bool outputs take float0 zeros
    internally.
    """
    from paddle_tpu.core.dispatch import call_op

    n_in = len(node.input_tensors)
    closed = node.fwd_fn
    treedef = node.out_treedef
    avals = node.out_avals
    float_pos = {i for i, a in enumerate(avals) if _is_inexact(a)}

    def grad_fn(*arrays: Any) -> Tuple[Any, ...]:
        primals, cots_in = arrays[:n_in], arrays[n_in:]
        _, vjp = jax.vjp(closed, *primals)
        flat, it = [], iter(cots_in)
        for i, a in enumerate(avals):
            flat.append(next(it) if i in float_pos else np.zeros(a.shape, jax.dtypes.float0))
        return vjp(jax.tree_util.tree_unflatten(treedef, flat))

    # run the re-derivation on the SNAPSHOTTED buffers: the tensor objects
    # stay in the call (tape linkage routes grads to them) but their data is
    # temporarily rewound to the forward-time values, so in-place mutation
    # after the forward cannot corrupt higher-order gradients
    snaps = node.input_snapshots or []
    saved = [(t, getattr(t, "_data", None)) for t in node.input_tensors]
    try:
        for t, snap in zip(node.input_tensors, snaps):
            if snap is not None:
                t._data = snap
        with set_grad_enabled(True):
            out = call_op(node.name + "_grad", grad_fn, *node.input_tensors, *cot_tensors)
    finally:
        for t, d in saved:
            if d is not None:
                t._data = d
    return out if isinstance(out, tuple) else (out,)


def run_backward(
    tensors: Sequence[Any],
    grad_tensors: Optional[Sequence[Any]] = None,
    retain_graph: bool = False,
    create_graph: bool = False,
    grad_capture: Optional[set] = None,
) -> None:
    """Reverse sweep from ``tensors``; accumulates ``.grad`` on leaf tensors.

    Mirrors ``egr::RunBackward`` (reference ``backward.cc:105``). The active
    capture set is published via ``current_grad_capture`` for nested sweeps.
    """
    stack = getattr(_grad_state, "capture_stack", None)
    if stack is None:
        stack = _grad_state.capture_stack = []
    stack.append(grad_capture)
    try:
        _run_backward_impl(tensors, grad_tensors, retain_graph, create_graph, grad_capture)
    finally:
        stack.pop()


def _run_backward_impl(
    tensors: Sequence[Any],
    grad_tensors: Optional[Sequence[Any]],
    retain_graph: bool,
    create_graph: bool,
    grad_capture: Optional[set],
) -> None:
    """Build the in-degree map over reachable nodes, seed a ready-queue with
    the output nodes, pop/run/route until empty."""
    from paddle_tpu.core.tensor import Tensor

    import jax.numpy as jnp

    roots: List[Tensor] = [t for t in tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(roots)
    if len(grad_tensors) != len(roots):
        raise InvalidArgumentError(
            f"grad_tensors length {len(grad_tensors)} != tensors length {len(roots)}"
        )

    # node -> {output_index: cotangent}
    pending: Dict[GradNode, Dict[int, Any]] = defaultdict(dict)
    seeds: List[GradNode] = []

    for t, g in zip(roots, grad_tensors):
        if t.stop_gradient and t.grad_node is None:
            raise PreconditionNotMetError(
                "backward() called on a tensor with stop_gradient=True and no "
                "recorded graph; nothing to differentiate."
            )
        if g is None:
            if not np.issubdtype(np.dtype(t.dtype), np.floating):
                raise InvalidArgumentError(
                    f"backward() root must be floating point, got {t.dtype}"
                )
            cot = jnp.ones(t.shape, t.dtype)
            if create_graph:
                cot = Tensor(cot, stop_gradient=True)
        elif create_graph:
            # Keep the seed's own graph alive (grad-of-grad chains through it).
            cot = g if isinstance(g, Tensor) else Tensor(jnp.asarray(g), stop_gradient=True)
        else:
            cot = g.data if isinstance(g, Tensor) else jnp.asarray(g)
        node = t.grad_node
        if node is None:
            # Leaf root: accumulate directly.
            if grad_capture is None or id(t) in grad_capture:
                t._accumulate_grad(cot)
            continue
        if node.released:
            raise PreconditionNotMetError(
                "backward() through an already-freed graph; pass retain_graph=True "
                "to backward() if you need to backprop twice."
            )
        prev = pending[node].get(t.grad_output_index)
        pending[node][t.grad_output_index] = _accumulate(prev, cot)
        if node not in seeds:
            seeds.append(node)

    # --- discover reachable subgraph + consumer counts (in-degree map) -------
    dependents: Dict[GradNode, int] = defaultdict(int)
    visited = set()
    stack = list(seeds)
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        for inp in node.input_tensors:
            nxt = inp.grad_node
            if nxt is not None and not nxt.released:
                dependents[nxt] += 1
                if id(nxt) not in visited:
                    stack.append(nxt)

    ready = deque(n for n in seeds if dependents.get(n, 0) == 0)
    executed = set()

    def _mark_done(nxt: GradNode) -> None:
        """A consumer edge of nxt resolved; enqueue/skip when all resolved."""
        dependents[nxt] -= 1
        if dependents[nxt] == 0 and id(nxt) not in executed:
            if pending.get(nxt):
                ready.append(nxt)
            else:
                # No gradient ever reached this node: don't run its vjp, but
                # still resolve its own producers so they aren't orphaned.
                executed.add(id(nxt))
                inputs = nxt.input_tensors
                if not retain_graph:
                    nxt.release()
                for inp2 in inputs:
                    up = inp2.grad_node
                    if up is not None and not up.released:
                        _mark_done(up)

    def route(inp: Any, g: Any) -> None:
        """Deliver gradient g to input tensor inp (leaf accumulate or enqueue)."""
        is_zero = g is None or (
            not isinstance(g, Tensor)
            and hasattr(g, "dtype")
            and g.dtype == jax.dtypes.float0
        )
        nxt = inp.grad_node
        if is_zero:
            if nxt is not None and not nxt.released:
                _mark_done(nxt)
            return
        g = inp._apply_backward_hooks(g)
        if nxt is None:
            # only_inputs semantics (egr::Grad): under a grad() sweep, write
            # .grad solely on the requested inputs, never as a side effect.
            if grad_capture is not None:
                if id(inp) in grad_capture:
                    inp._accumulate_grad(g)
            elif not inp.stop_gradient:
                inp._accumulate_grad(g)
            return
        prev = pending[nxt].get(inp.grad_output_index)
        pending[nxt][inp.grad_output_index] = _accumulate(prev, g)
        if grad_capture is not None:
            if id(inp) in grad_capture:
                inp._accumulate_grad(g)
        elif inp.retain_grads_flag:
            inp._accumulate_grad(g)
        _mark_done(nxt)

    while ready:
        node = ready.popleft()
        if id(node) in executed:
            continue
        executed.add(id(node))
        cots_map = pending.pop(node, {})
        if create_graph:
            if node.fwd_fn is None:
                raise PreconditionNotMetError(
                    f"create_graph=True backward through op '{node.name}' which "
                    "recorded no re-differentiable forward"
                )
            cot_ts = []
            for i, aval in enumerate(node.out_avals):
                if not _is_inexact(aval):
                    continue
                c = cots_map.get(i)
                if c is None:
                    c = Tensor(jnp.zeros(aval.shape, aval.dtype), stop_gradient=True)
                else:
                    c = _coerce_cot_tensor(c, aval)
                cot_ts.append(c)
            in_grads = _dispatch_grad(node, cot_ts)
        else:
            cots = tuple(
                _coerce_cotangent(cots_map.get(i), aval)
                for i, aval in enumerate(node.out_avals)
            )
            with jax.named_scope(node.scope) if node.scope else _NO_SCOPE:
                if node.out_treedef is not None:
                    in_grads = node.vjp_fn(
                        jax.tree_util.tree_unflatten(node.out_treedef, list(cots))
                    )
                elif len(node.out_avals) == 1:
                    in_grads = node.vjp_fn(cots[0])
                else:
                    in_grads = node.vjp_fn(cots)
        if len(in_grads) != len(node.input_tensors):
            raise PreconditionNotMetError(
                f"vjp of {node.name} returned {len(in_grads)} grads for "
                f"{len(node.input_tensors)} inputs"
            )
        inputs = node.input_tensors
        if not retain_graph:
            node.release()
        for inp, g in zip(inputs, in_grads):
            route(inp, g)


def grad(
    outputs: Sequence[Any],
    inputs: Sequence[Any],
    grad_outputs: Optional[Sequence[Any]] = None,
    retain_graph: Optional[bool] = None,
    create_graph: bool = False,
    allow_unused: bool = False,
) -> List[Any]:
    """``paddle.grad`` parity: partial grads of outputs w.r.t. inputs.

    Reference: ``egr::Grad`` (``paddle/fluid/eager/backward.cc:450``) /
    general_grad. Implemented by running the tape backward with grad capture
    redirected into fresh buffers instead of ``.grad`` accumulation. With
    ``create_graph=True`` the sweep re-dispatches each node's vjp through the
    op dispatcher, so the returned grads carry their own tape and can be
    differentiated again (double grad, grad-of-grad chains).
    """
    from paddle_tpu.core.tensor import Tensor

    if retain_graph is None:
        retain_graph = create_graph
    outputs = list(outputs)
    inputs = list(inputs)
    saved = [t.grad for t in inputs]
    capture = {id(t) for t in inputs}
    try:
        for t in inputs:
            t._grad = None
        run_backward(
            outputs,
            grad_outputs,
            retain_graph=retain_graph,
            create_graph=create_graph,
            grad_capture=capture,
        )
        results: List[Optional[Tensor]] = []
        for t in inputs:
            g = t.grad
            if g is None and not allow_unused:
                raise InvalidArgumentError(
                    "an input tensor received no gradient; pass allow_unused=True "
                    "to get None for unused inputs"
                )
            results.append(g)
        return results
    finally:
        for t, g in zip(inputs, saved):
            t._grad = g
