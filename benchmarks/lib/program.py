"""The seam to the system under test: build its model from a configuration
file's ``program`` block, hand it the benchmark's seeded weights, and read its
counters. Nothing here names a model or a cell; the block in the configuration
file says which class to build and how its parameters are called."""

from __future__ import annotations

import importlib
from typing import Any, Dict, List

from . import arch
from . import weights as W


def _resolve(path: str) -> Any:
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


def run_config(cfg: Dict[str, Any], driver: str) -> Dict[str, Any]:
    """The configuration as this driver runs it: a key given per driver
    (``{"published": .., "train": .., "serve": ..}``) takes this driver's value."""
    out = {}
    for k, v in cfg.items():
        out[k] = v[driver] if isinstance(v, dict) and "published" in v and driver in v else v
    return out


def build_model(cfg: Dict[str, Any], seed: int, dtype: str) -> Any:
    """The program's model at ``cfg`` (already per-driver), with the
    benchmark's weights in place of the program's own initialisation."""
    import paddle_tpu as paddle

    block = cfg["program"]
    kwargs = {field: cfg[key] for key, field in block["config_keys"].items()}
    kwargs[block["depth_key"]] = cfg["num_hidden_layers"]
    kwargs[block["dtype_key"]] = dtype
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = _resolve(block["model"])(_resolve(block["config"])(**kwargs)).to(dtype=dtype)
    install_weights(model, cfg, W.all_weights(seed, cfg, cfg["num_hidden_layers"], dtype))
    return model


def param_names(cfg: Dict[str, Any], depth: int) -> Dict[str, Any]:
    """Program parameter name of every benchmark leaf: ``{"top": {leaf: name},
    "layers": [{leaf: name}, ...]}``. The leaves are the reference file's
    table; their names come from the configuration file's ``program.params``,
    where every key beside ``layer_prefix`` and ``layer`` names a top leaf and
    ``layer`` is one map over whatever leaves layer ``i`` has."""
    names = cfg["program"]["params"]
    ref = arch.reference(cfg)
    top, table = set(names) - {"layer_prefix", "layer"}, set(ref.top_leaves(cfg))
    if top != table:
        raise KeyError(f"program.params names the top leaves {sorted(top)}, the reference has {sorted(table)}")
    return {
        "top": {leaf: names[leaf] for leaf in top},
        "layers": [
            {leaf: names["layer_prefix"].format(i=i) + names["layer"][leaf] for leaf in ref.layer_leaves(cfg, i)}
            for i in range(depth)
        ],
    }


def install_weights(model: Any, cfg: Dict[str, Any], weights: Dict[str, Any]) -> None:
    params = dict(model.named_parameters())
    names = param_names(cfg, len(weights["layers"]))
    wanted = {names["top"][k]: v for k, v in weights["top"].items()}
    for layer_names, layer in zip(names["layers"], weights["layers"]):
        wanted.update({layer_names[k]: v for k, v in layer.items()})
    if set(wanted) != set(params):
        raise KeyError(
            f"parameter names differ: only in program {sorted(set(params) - set(wanted))[:4]}, "
            f"only in benchmark {sorted(set(wanted) - set(params))[:4]}"
        )
    for name, value in wanted.items():
        params[name].set_value(value)


def enable_counters() -> None:
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_enable_metrics": True})


def kernel_counters() -> Dict[str, Dict[str, float]]:
    from paddle_tpu.kernels.select import fallback_counts, partition_routed_counts

    return {"fallbacks": dict(fallback_counts()), "routed_to_xla": dict(partition_routed_counts())}


def watchdog_counts() -> Dict[str, int]:
    from paddle_tpu.observability import GLOBAL_WATCHDOG

    return dict(GLOBAL_WATCHDOG.counts())


class CompileCounter:
    """Counts what JAX compiles or fetches from its persistent cache, by JAX's
    own monitoring events: inside the measured window both must stay at 0."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw: Any) -> None:
        if event in self.EVENTS:
            self.count += 1


def named_state(model: Any, opt: Any, key: str) -> Dict[str, Any]:
    """``{parameter path in the model: array}`` of one optimizer accumulator
    (``moment1``, ``master_weight``), from the optimizer's public ``state_dict``."""
    state = opt.state_dict()
    out = {}
    for path, p in model.named_parameters():
        v = state[f"{p.name}__{key}"]
        out[path] = v.data if hasattr(v, "data") else v
    return out


def master_weights(model: Any, opt: Any) -> Dict[str, Any]:
    """The optimizer's float32 master copy of every parameter; a parameter
    that is float32 itself has none and is its own master."""
    state = opt.state_dict()
    out = {}
    for path, p in model.named_parameters():
        v = state.get(f"{p.name}__master_weight")
        out[path] = p.data if v is None else (v.data if hasattr(v, "data") else v)
    return out
