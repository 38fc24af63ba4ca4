"""The Tensor type: a jax.Array plus autograd metadata.

TPU-native counterpart of the reference's eager Tensor
(``paddle/fluid/pybind/eager_method.cc`` surface over ``phi::DenseTensor``,
``paddle/phi/core/dense_tensor.h:37``): the device buffer is a ``jax.Array``
(PJRT buffer, async dispatch, XLA-owned layout), and autograd metadata
(``stop_gradient``, ``grad``, grad node edge) mirrors ``egr::AutogradMeta``.

Ops attach themselves as methods via ``register_tensor_method`` — the analog of
the generated pybind method table.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import autograd as _ag
from paddle_tpu.core.dtypes import convert_dtype
from paddle_tpu.errors import InvalidArgumentError, PreconditionNotMetError

_name_counter = itertools.count()


def _auto_name(prefix: str = "generated_tensor") -> str:
    return f"{prefix}_{next(_name_counter)}"


class Tensor:
    __array_priority__ = 100  # win binary-op dispatch vs numpy arrays

    def __init__(
        self,
        data: Any = None,
        dtype: Any = None,
        place: Any = None,
        stop_gradient: bool = True,
        name: Optional[str] = None,
    ) -> None:
        if isinstance(data, Tensor):
            data = data._data
        if data is None:
            data = jnp.zeros((), jnp.float32)
        if not isinstance(data, (jax.Array, jax.core.Tracer)):
            data = jnp.asarray(data, dtype=convert_dtype(dtype) if dtype else None)
        elif dtype is not None and jnp.dtype(data.dtype) != jnp.dtype(convert_dtype(dtype)):
            data = data.astype(convert_dtype(dtype))
        if place is not None and not isinstance(data, jax.core.Tracer):
            data = jax.device_put(data, place.jax_device())
        self._data = data
        self.stop_gradient = bool(stop_gradient)
        self._grad: Optional["Tensor"] = None
        self._grad_node: Optional[_ag.GradNode] = None
        self._grad_output_index: int = 0
        self.retain_grads_flag: bool = False
        self._backward_hooks: List[Callable] = []
        # bumped by in-place mutation; create_graph backward checks it
        # (reference: tensor version counters, eager/tensor_wrapper.h)
        self._version: int = 0
        self.name = name or _auto_name()
        self.persistable = False

    # -- raw buffer access ----------------------------------------------------
    @property
    def data(self) -> jax.Array:
        """The underlying jax.Array (device buffer)."""
        return self._data

    # -- metadata -------------------------------------------------------------
    @property
    def shape(self) -> List[int]:
        return list(self._data.shape)

    @property
    def dtype(self) -> Any:
        return self._data.dtype

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def size(self) -> int:
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self) -> Any:
        from paddle_tpu.core.device import CPUPlace, TPUPlace

        if isinstance(self._data, jax.core.Tracer):
            return None
        dev = next(iter(self._data.devices()))
        if dev.platform == "tpu":
            return TPUPlace(dev.id)
        return CPUPlace()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def grad_node(self) -> Optional[_ag.GradNode]:
        return self._grad_node

    @property
    def grad_output_index(self) -> int:
        return self._grad_output_index

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    # -- autograd -------------------------------------------------------------
    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, value: Optional["Tensor"]) -> None:
        self._grad = value

    def backward(self, grad_tensor: Any = None, retain_graph: bool = False) -> None:
        """Run reverse-mode autodiff from this tensor (``Tensor.backward`` parity;
        reference entry ``paddle/fluid/pybind/eager_functions.cc:145``)."""
        grads = None if grad_tensor is None else [grad_tensor]
        _ag.run_backward([self], grads, retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self._grad = None

    clear_gradient = clear_grad

    def retain_grads(self) -> None:
        self.retain_grads_flag = True

    def register_hook(self, hook: Callable) -> "_HookHandle":
        self._backward_hooks.append(hook)
        return _HookHandle(self, hook)

    def _apply_backward_hooks(self, g: Any) -> Any:
        if not self._backward_hooks:
            return g
        keep_tensor = isinstance(g, Tensor)
        gt = g if keep_tensor else Tensor(g)
        for hook in self._backward_hooks:
            out = hook(gt)
            if out is not None:
                if not isinstance(out, Tensor) and keep_tensor:
                    # under a create_graph sweep a raw-array hook result has
                    # no tape: rewrapping it would silently detach the
                    # higher-order gradient through this hook — warn once
                    # (hooks must return Tensors to stay differentiable)
                    import warnings

                    warnings.warn(
                        "a backward hook returned a raw array during a "
                        "create_graph sweep; the higher-order tape is detached "
                        "through it. Return a Tensor to keep it differentiable.",
                        stacklevel=2,
                    )
                gt = out if isinstance(out, Tensor) else Tensor(out)
        return gt if keep_tensor else gt._data

    def _accumulate_grad(self, g: Any) -> None:
        # Grads accumulate in the parameter's dtype (AMP-cast cotangents are
        # upcast here, mirroring the cast-op grad in the reference's O1 path).
        if isinstance(g, Tensor) and g.grad_node is not None:
            # create_graph sweep: preserve the grad's own tape so it can be
            # differentiated again (cast/add dispatched, not detached).
            if jnp.dtype(g.dtype) != jnp.dtype(self._data.dtype):
                g = g.astype(self._data.dtype)
            self._grad = g if self._grad is None else self._grad + g
            return
        if isinstance(g, Tensor):
            g = g._data
        if hasattr(g, "dtype") and jnp.dtype(g.dtype) != jnp.dtype(self._data.dtype):
            g = g.astype(self._data.dtype)
        if self._grad is None:
            self._grad = Tensor(g, stop_gradient=True, name=self.name + "@GRAD")
        elif self._grad.grad_node is not None:
            # Existing grad carries a tape (create_graph): add via dispatch so
            # the taped component stays differentiable.
            self._grad = self._grad + Tensor(g, stop_gradient=True)
        else:
            self._grad = Tensor(self._grad._data + g, stop_gradient=True, name=self.name + "@GRAD")

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name + ".detach")
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    # -- conversion -----------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def __array__(self, dtype: Any = None) -> np.ndarray:
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr

    def item(self, *args: int) -> Any:
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self) -> Any:
        return self.numpy().tolist()

    def astype(self, dtype: Any) -> "Tensor":
        from paddle_tpu.core.dispatch import call_op

        target = convert_dtype(dtype)
        return call_op("cast", lambda x: x.astype(target), self)

    cast = astype

    def to(self, *args: Any, **kwargs: Any) -> "Tensor":
        """``Tensor.to(device|dtype)`` subset parity."""
        from paddle_tpu.core.device import _parse

        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (a in ("cpu",) or ":" in a or a in ("tpu", "gpu")):
                place = _parse(a)
                out = Tensor(
                    jax.device_put(out._data, place.jax_device()),
                    stop_gradient=out.stop_gradient,
                    name=out.name,
                )
            else:
                out = out.astype(a)
        return out

    def cpu(self) -> "Tensor":
        return self.to("cpu")

    def clone(self) -> "Tensor":
        from paddle_tpu.core.dispatch import call_op

        return call_op("clone", lambda x: x + jnp.zeros((), x.dtype), self)

    def pin_memory(self) -> "Tensor":
        return self

    def contiguous(self) -> "Tensor":
        return self

    def is_contiguous(self) -> bool:
        return True

    # -- mutation (used by optimizers / loading under no_grad) ---------------
    def set_value(self, value: Any) -> None:
        new = value._data if isinstance(value, Tensor) else jnp.asarray(value, dtype=self.dtype)
        if tuple(new.shape) != tuple(self._data.shape):
            raise InvalidArgumentError(
                f"set_value shape mismatch: tensor {tuple(self._data.shape)} vs value {tuple(new.shape)}"
            )
        self._data = new.astype(self._data.dtype)
        self._version += 1

    def copy_(self, other: Any) -> "Tensor":
        self.set_value(other)
        return self

    def _replace_(self, new: "Tensor") -> None:
        """Adopt another tensor's buffer + tape position (in-place op support).

        When the adopting op recorded ``self`` as its input, that recording
        must keep pointing at the PRE-mutation tape position — otherwise the
        node's input would resolve to the node itself (a cycle) and the
        history feeding the in-place op would be orphaned. An alias tensor
        carries the old buffer + old grad node into the recording (the
        reference's TensorWrapper keeps the pre-bump version the same way).
        """
        node = new._grad_node
        if node is not None and not getattr(node, "released", True):
            alias: Optional[Tensor] = None
            for i, t in enumerate(node.input_tensors):
                if t is self:
                    if alias is None:
                        alias = Tensor(self._data, stop_gradient=self.stop_gradient)
                        alias._grad_node = self._grad_node
                        alias._grad_output_index = self._grad_output_index
                    node.input_tensors[i] = alias
        self._data = new._data
        self._grad_node = new._grad_node
        self._grad_output_index = new._grad_output_index
        self.stop_gradient = new.stop_gradient
        self._version += 1

    # -- indexing -------------------------------------------------------------
    def __getitem__(self, index: Any) -> "Tensor":
        from paddle_tpu.core.dispatch import call_op

        def gather(x: Any, idx: Any) -> Any:
            return x[idx]

        return call_op("getitem", gather, self, _unwrap_index(index))

    def __setitem__(self, index: Any, value: Any) -> None:
        from paddle_tpu.core.dispatch import call_op

        def scatter(x: Any, idx: Any, v: Any) -> Any:
            return x.at[idx].set(v.astype(x.dtype) if hasattr(v, "astype") else v)

        new = call_op("setitem", scatter, self, _unwrap_index(index), value)
        self._replace_(new)

    def __iter__(self) -> Any:
        for i in range(len(self)):
            yield self[i]

    # -- scalars / truthiness -------------------------------------------------
    def __bool__(self) -> bool:
        if self.size != 1:
            raise PreconditionNotMetError(
                "truth value of a multi-element Tensor is ambiguous; use .any()/.all()"
            )
        return bool(self.numpy().reshape(()))

    def __int__(self) -> int:
        return int(self.item())

    def __float__(self) -> float:
        return float(self.item())

    def __index__(self) -> int:
        return int(self.item())

    __hash__ = object.__hash__

    def __repr__(self) -> str:
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        try:
            value = np.array2string(self.numpy(), precision=6, separator=", ", threshold=64)
        except Exception:  # repr must never raise: traced/donated/deleted buffers
            value = "<traced>"
        return (
            f"Tensor(shape={self.shape}, dtype={jnp.dtype(self.dtype).name}{grad_info},\n"
            f"       {value})"
        )

    # -- dunder arithmetic: lazily bound to ops.math --------------------------
    def _binop(self, opname: str, other: Any, reverse: bool = False) -> "Tensor":
        from paddle_tpu.ops import math as _math

        fn = getattr(_math, opname)
        return fn(other, self) if reverse else fn(self, other)

    def __add__(self, o: Any) -> "Tensor":
        return self._binop("add", o)

    def __radd__(self, o: Any) -> "Tensor":
        return self._binop("add", o, True)

    def __sub__(self, o: Any) -> "Tensor":
        return self._binop("subtract", o)

    def __rsub__(self, o: Any) -> "Tensor":
        return self._binop("subtract", o, True)

    def __mul__(self, o: Any) -> "Tensor":
        return self._binop("multiply", o)

    def __rmul__(self, o: Any) -> "Tensor":
        return self._binop("multiply", o, True)

    def __truediv__(self, o: Any) -> "Tensor":
        return self._binop("divide", o)

    def __rtruediv__(self, o: Any) -> "Tensor":
        return self._binop("divide", o, True)

    def __floordiv__(self, o: Any) -> "Tensor":
        return self._binop("floor_divide", o)

    def __rfloordiv__(self, o: Any) -> "Tensor":
        return self._binop("floor_divide", o, True)

    def __mod__(self, o: Any) -> "Tensor":
        return self._binop("remainder", o)

    def __rmod__(self, o: Any) -> "Tensor":
        return self._binop("remainder", o, True)

    def __pow__(self, o: Any) -> "Tensor":
        return self._binop("pow", o)

    def __rpow__(self, o: Any) -> "Tensor":
        return self._binop("pow", o, True)

    def __matmul__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import linalg as _linalg

        return _linalg.matmul(self, o)

    def __rmatmul__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import linalg as _linalg

        return _linalg.matmul(o, self)

    def __neg__(self) -> "Tensor":
        return self._binop("multiply", -1)

    def __abs__(self) -> "Tensor":
        from paddle_tpu.ops import math as _math

        return _math.abs(self)

    def __eq__(self, o: Any) -> "Tensor":  # type: ignore[override]
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.equal(self, o)

    def __ne__(self, o: Any) -> "Tensor":  # type: ignore[override]
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.not_equal(self, o)

    def __lt__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.less_than(self, o)

    def __le__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.less_equal(self, o)

    def __gt__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.greater_than(self, o)

    def __ge__(self, o: Any) -> "Tensor":
        from paddle_tpu.ops import comparison as _cmp

        return _cmp.greater_equal(self, o)

    def __invert__(self) -> "Tensor":
        from paddle_tpu.ops import logic as _logic

        return _logic.logical_not(self)

    @property
    def T(self) -> "Tensor":  # noqa: N802
        from paddle_tpu.ops import linalg as _linalg

        return _linalg.t(self)


class _HookHandle:
    def __init__(self, tensor: Tensor, hook: Callable) -> None:
        self._tensor = tensor
        self._hook = hook

    def remove(self) -> None:
        if self._hook in self._tensor._backward_hooks:
            self._tensor._backward_hooks.remove(self._hook)


def _unwrap_index(index: Any) -> Any:
    """Pass Tensors in an index expression through as dispatch args."""
    if isinstance(index, tuple):
        return tuple(_unwrap_index(i) for i in index)
    if isinstance(index, list):
        return jnp.asarray(index)
    return index


class Parameter(Tensor):
    """A trainable Tensor (``paddle.create_parameter`` / ``EagerParamBase``)."""

    def __init__(
        self,
        data: Any = None,
        dtype: Any = None,
        name: Optional[str] = None,
        trainable: bool = True,
    ) -> None:
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name or _auto_name("param"))
        self.trainable = trainable
        self.persistable = True

    def __repr__(self) -> str:
        return "Parameter containing:\n" + super().__repr__()


# -- method registration ------------------------------------------------------
def register_tensor_method(name: str, fn: Callable) -> None:
    """Attach an op as a Tensor method (the generated-pybind-methods analog)."""
    if not hasattr(Tensor, name):
        setattr(Tensor, name, fn)
