"""jit.save / jit.load: serialized inference programs.

Reference: ``paddle.jit.save``/``load`` (``python/paddle/jit/api.py``,
``translated_layer.py``) export a Program + params; the deployment side loads
them through the inference AnalysisPredictor
(``paddle/fluid/inference/api/analysis_predictor.h:105``). TPU-native
equivalent: serialize the traced function with ``jax.export`` (a portable
StableHLO artifact with calling convention + vjp-free forward) plus a params
archive; load reconstitutes an executable ``TranslatedLayer``. The
``paddle_tpu.inference`` package builds the Predictor API on top of this.
"""

from __future__ import annotations

import json
import os
import pickle
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.tensor import Tensor

_MAGIC = b"PDTPU\x01"  # binary serialized jax.export artifact marker


def _pure_forward(layer: Any) -> Callable:
    """Functionalize a Layer: (params_dict, *input_arrays) -> output arrays."""

    def pure_forward(params_, *xs):
        saved = [(t, t._data) for t in layer.state_dict().values()]
        try:
            for k, t in layer.state_dict().items():
                t._data = params_[k]
            out = layer(*[Tensor(x) for x in xs])
            return jax.tree_util.tree_map(
                lambda o: o._data if isinstance(o, Tensor) else o,
                out,
                is_leaf=lambda o: isinstance(o, Tensor),
            )
        finally:
            for t, d in saved:
                t._data = d

    return pure_forward


def decommit_from_mesh(tree: Any) -> Any:
    """Round-trip multi-device-sharded arrays through host so they become
    uncommitted single-device arrays (mesh-agnostic). Single-device arrays
    pass through untouched — no gratuitous D2H copy."""

    def fix(a: Any) -> Any:
        sharding = getattr(a, "sharding", None)
        if sharding is not None and len(getattr(sharding, "device_set", ())) > 1:
            return jnp.asarray(np.asarray(a))
        return a

    return jax.tree_util.tree_map(fix, tree)


def specs_from_input_spec(
    input_spec: Sequence[Any], float_dtype: Any = None
) -> List[jax.ShapeDtypeStruct]:
    """Shared InputSpec→ShapeDtypeStruct conversion (save/serve use the same
    rules). ``float_dtype`` overrides the dtype of floating specs (mixed-
    precision serving)."""
    specs = []
    for s in input_spec:
        dt = jnp.dtype(getattr(s, "dtype", None) or "float32")
        if float_dtype is not None and jnp.issubdtype(dt, jnp.floating):
            dt = jnp.dtype(float_dtype)
        specs.append(jax.ShapeDtypeStruct(tuple(s.shape), dt))
    return specs


def _export_layer(layer: Any, input_spec: Sequence[Any], params: dict) -> "jax.export.Exported":
    """Export the layer's forward as a portable artifact.

    Tries a multi-platform (cpu+tpu) export first so a bundle saved on the dev
    box runs on the serving chip and vice versa; falls back to the current
    platform when an op lacks multi-platform lowering.
    """
    pure = _pure_forward(layer)
    specs = specs_from_input_spec(input_spec)
    # training may have left params sharded over a device mesh; exporting
    # mesh-placed weights records an N-device calling convention that a
    # single-device serving context cannot satisfy. Decommit to keep the
    # bundle mesh-agnostic.
    params = decommit_from_mesh(params)
    return export_fn(pure, params, specs)


def export_fn(fn: Any, params: Any, specs: Sequence[Any]) -> "jax.export.Exported":
    """Export ``fn(params, *specs)`` portably: cpu+tpu platforms first, with a
    diagnosed single-platform fallback. Grad recording is disabled for the
    trace — export must produce a vjp-free forward."""
    import sys

    from paddle_tpu.core import autograd as _ag

    with _ag.set_grad_enabled(False):
        try:
            return jax.export.export(jax.jit(fn), platforms=("cpu", "tpu"))(params, *specs)
        except Exception as exc:  # noqa: BLE001 - per-platform fallback
            print(
                f"jit.save: multi-platform export failed ({exc!r}); "
                "falling back to the current platform only"[:500],
                file=sys.stderr,
            )
            return jax.export.export(jax.jit(fn))(params, *specs)


def write_bundle(
    path: str,
    exported: "jax.export.Exported",
    state: dict,
    input_spec: Sequence[Any],
    specs: Optional[Sequence[Any]] = None,
    extra_spec: Optional[dict] = None,
) -> None:
    """Write the three bundle files (the ONE place that knows the on-disk
    format): ``.pdiparams`` pickled numpy state, ``.pdmodel`` serialized
    program, ``.pdspec`` feed/fetch signature. ``specs`` (when given) carry
    the traced input dtypes; ``input_spec`` carries the user-facing names."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({k: np.asarray(v) for k, v in state.items()}, f, protocol=4)
    with open(path + ".pdmodel", "wb") as f:
        f.write(_MAGIC + exported.serialize())
    traced = specs if specs is not None else input_spec
    spec = {
        "inputs": [
            {
                "name": getattr(orig, "name", None) or f"x{i}",
                "shape": list(s.shape),
                "dtype": str(jnp.dtype(getattr(s, "dtype", "float32"))),
            }
            for i, (orig, s) in enumerate(zip(input_spec, traced))
        ],
        "outputs": [
            {"name": f"fetch{i}", "shape": list(a.shape), "dtype": str(a.dtype)}
            for i, a in enumerate(exported.out_avals)
        ],
        "platforms": list(exported.platforms),
    }
    spec.update(extra_spec or {})
    with open(path + ".pdspec", "w") as f:
        json.dump(spec, f, indent=1)


def save(layer: Any, path: str, input_spec: Optional[Sequence[Any]] = None, **config: Any) -> None:
    """Serialize a Layer for inference.

    Writes:
      - ``<path>.pdiparams`` — pickled numpy state dict
      - ``<path>.pdmodel``   — serialized ``jax.export`` artifact (binary;
        StableHLO + calling convention), when ``input_spec`` is given
      - ``<path>.pdspec``    — JSON feed/fetch signature for the Predictor
    """
    from paddle_tpu.nn.layer.layers import Layer

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects a Layer")
    state = {k: np.asarray(v.numpy()) for k, v in layer.state_dict().items()}
    if not input_spec:
        with open(path + ".pdiparams", "wb") as f:
            pickle.dump(state, f, protocol=4)
        return
    params = {k: v._data for k, v in layer.state_dict().items()}
    exported = _export_layer(layer, input_spec, params)
    write_bundle(path, exported, state, input_spec)


class TranslatedLayer:
    """Loaded inference bundle (reference ``translated_layer.py`` parity).

    When the bundle carries a serialized program, the instance is callable:
    ``layer(x, ...)`` executes the compiled forward with the loaded params.
    """

    def __init__(
        self,
        state: dict,
        exported: Optional["jax.export.Exported"] = None,
        spec: Optional[dict] = None,
        model_text: Optional[str] = None,
    ) -> None:
        self._state = {k: Tensor(v) for k, v in state.items()}
        self._exported = exported
        self._spec = spec or {}
        self._model_text = model_text
        self._compiled: Optional[Callable] = None

    def state_dict(self) -> dict:
        return self._state

    @property
    def program_text(self) -> Optional[str]:
        if self._model_text is not None:
            return self._model_text
        if self._exported is not None:
            return str(self._exported.mlir_module())
        return None

    @property
    def input_spec(self) -> List[dict]:
        return list(self._spec.get("inputs", []))

    @property
    def output_spec(self) -> List[dict]:
        return list(self._spec.get("outputs", []))

    def __call__(self, *args: Any) -> Any:
        if self._exported is None:
            raise RuntimeError(
                "this bundle has no serialized program (saved without input_spec); "
                "only state_dict() is available"
            )
        if self._compiled is None:
            call = self._exported.call
            # params passed as an argument (NOT closed over): closure arrays
            # would be baked into the executable as constants, doubling HBM.
            self._compiled = jax.jit(lambda params_, *xs: call(params_, *xs))
        params = {k: t._data for k, t in self._state.items()}
        arrays = [a._data if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        out = self._compiled(params, *arrays)
        return jax.tree_util.tree_map(Tensor, out)


def load(path: str, **config: Any) -> TranslatedLayer:
    with open(path + ".pdiparams", "rb") as f:
        state = pickle.load(f)
    exported = None
    model_text = None
    if os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            blob = f.read()
        if blob.startswith(_MAGIC):
            exported = jax.export.deserialize(blob[len(_MAGIC):])
        else:  # pre-r4 text bundles
            model_text = blob.decode("utf-8", errors="replace")
    spec = None
    if os.path.exists(path + ".pdspec"):
        with open(path + ".pdspec") as f:
            spec = json.load(f)
    return TranslatedLayer(state, exported, spec, model_text)
