"""Optimizer base.

Reference: ``python/paddle/optimizer/optimizer.py`` (param groups, master
weights, grad clip integration). TPU-native design: every optimizer defines a
**functional core** — ``init_state(param) -> state`` and
``update(param, grad, state, *, lr, step) -> (new_param, new_state)`` over raw
jax arrays — and the eager ``.step()`` runs one fused, jit-compiled XLA program
over all parameters (the analog of the reference's multi_tensor/fused optimizer
kernels, e.g. ``fused_adam``). The same functional core is reused by
``paddle_tpu.jit`` captured train steps and by the ZeRO sharded optimizer.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.errors import InvalidArgumentError

SCOPE_UPDATE = "optimizer_update"  # jax.named_scope of the fused update


class Optimizer:
    def __init__(
        self,
        learning_rate: Union[float, "paddle_tpu.optimizer.lr.LRScheduler"] = 0.001,
        parameters: Optional[Sequence[Any]] = None,
        weight_decay: Optional[Union[float, Any]] = None,
        grad_clip: Any = None,
        multi_precision: bool = False,
        name: Optional[str] = None,
    ) -> None:
        if parameters is None:
            raise InvalidArgumentError(
                "parameters is required in dygraph mode (pass model.parameters())"
            )
        # param groups: list of dicts {params, learning_rate?, weight_decay?}
        params = list(parameters)
        if params and isinstance(params[0], dict):
            self._param_groups = params
            self._parameters = [p for g in params for p in g["params"]]
        else:
            self._param_groups = [{"params": params}]
            self._parameters = params
        self._learning_rate = learning_rate
        self._weight_decay = self._wd_value(weight_decay)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._step_count = 0
        # Device-side step counter + lr override: these make step() traceable
        # by paddle_tpu.jit (a python-int step would be baked into the XLA
        # program as a constant).
        self._step_buf: Optional[jax.Array] = None
        self._lr_array: Optional[jax.Array] = None
        self._accumulators: Dict[int, Dict[str, jax.Array]] = {}
        self._jit_step_fn: Optional[Callable] = None

    def _param_weight_decay(self, p: Any, wd: float) -> float:
        """Per-parameter weight-decay override hook (AdamW's
        apply_decay_param_fun)."""
        return wd

    @staticmethod
    def _wd_value(weight_decay: Any) -> float:
        if weight_decay is None:
            return 0.0
        if hasattr(weight_decay, "_coeff"):  # L2Decay regularizer object
            return float(weight_decay._coeff)
        return float(weight_decay)

    # -- functional core (overridden by each algorithm) -----------------------
    def init_state(self, param: jax.Array) -> Dict[str, jax.Array]:
        return {}

    def update(
        self,
        param: jax.Array,
        grad: jax.Array,
        state: Dict[str, jax.Array],
        *,
        lr: jax.Array,
        step: jax.Array,
        weight_decay: float,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    # -- lr -------------------------------------------------------------------
    def get_lr(self) -> float:
        from paddle_tpu.optimizer.lr import LRScheduler

        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float) -> None:
        self._learning_rate = float(value)

    # -- state management -----------------------------------------------------
    def _state_for(self, p: Tensor) -> Dict[str, jax.Array]:
        key = id(p)
        if key not in self._accumulators:
            low_prec = jnp.dtype(p.dtype) in (
                jnp.dtype(jnp.bfloat16),
                jnp.dtype(jnp.float16),
            )
            if self._multi_precision and low_prec:
                # fp32 master weight AND fp32 moments (reference
                # multi_precision semantics: all accumulators in fp32).
                master = p.data.astype(jnp.float32)
                state = self.init_state(master)
                state["master_weight"] = master
            else:
                state = self.init_state(p.data)
            self._accumulators[key] = state
        return self._accumulators[key]

    def _update_leaf(
        self, param: jax.Array, grad: jax.Array, state: Dict[str, jax.Array], lr: Any, step: Any, weight_decay: float
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``update`` over one leaf as ``_state_for`` laid its state out: with a
        master weight the update runs on it in fp32 and the parameter is its
        rounding."""
        if "master_weight" not in state:
            return self.update(param, grad, state, lr=lr, step=step, weight_decay=weight_decay)
        inner = {k: v for k, v in state.items() if k != "master_weight"}
        new_master, new_state = self.update(
            state["master_weight"], grad.astype(jnp.float32), inner, lr=lr, step=step, weight_decay=weight_decay
        )
        new_state["master_weight"] = new_master
        return new_master.astype(param.dtype), new_state

    # -- the step -------------------------------------------------------------
    def step(self) -> None:
        params_grads = [(p, p.grad) for p in self._parameters if not p.stop_gradient and p.grad is not None]
        if not params_grads:
            self._advance_lr()
            return
        if self._grad_clip is not None:
            # clip over the full set (global norm spans param groups)
            params_grads = self._grad_clip(params_grads)
        if self._step_buf is None:
            self._step_buf = jnp.zeros((), jnp.int32)
        base_lr = self._lr_array if self._lr_array is not None else jnp.asarray(self.get_lr(), jnp.float32)
        step = self._step_buf + 1

        # Bucket by (group lr, group wd, per-param lr factor) so param-group
        # overrides are honored (reference: optimizer.py _param_groups).
        grad_of = {id(p): g for p, g in params_grads}
        buckets: Dict[Tuple[Optional[float], float, float], List[Tensor]] = {}
        for group in self._param_groups:
            g_lr = group.get("learning_rate")
            g_wd = group.get("weight_decay")
            wd = self._weight_decay if g_wd is None else self._wd_value(g_wd)
            for p in group["params"]:
                if id(p) not in grad_of:
                    continue
                factor = float(getattr(p, "optimize_attr", {}).get("learning_rate", 1.0))
                wd_p = self._param_weight_decay(p, wd)
                buckets.setdefault((g_lr, wd_p, factor), []).append(p)

        for (g_lr, wd, factor), params in buckets.items():
            lr = jnp.asarray(g_lr, jnp.float32) if g_lr is not None else base_lr
            if factor != 1.0:
                lr = lr * factor
            self._run_fused(params, [grad_of[id(p)] for p in params], lr, step, wd)
        self._step_buf = step
        self._step_count += 1
        self._advance_lr()

    def _run_fused(self, params: List[Tensor], grads: List[Tensor], lr: Any, step: Any, weight_decay: float) -> None:
        states = [self._state_for(p) for p in params]
        p_arrays = [p.data for p in params]
        g_arrays = [g.data for g in grads]

        if self._jit_step_fn is None:
            # what a device trace files the update's operations under
            @jax.named_scope(SCOPE_UPDATE)
            def fused(ps, gs, sts, lr_, step_, wd):
                new_ps, new_sts = [], []
                for p_, g_, st in zip(ps, gs, sts):
                    # A gradient is a materialised array before its update
                    # reads it. Traced into a to_static step, the update is
                    # otherwise XLA's to fuse into the matmul that makes the
                    # gradient, and seven update-sized buffers in VMEM leave
                    # that matmul a tile at 35-43 % of the MXU's peak (PERF.md,
                    # PR 34). Per leaf, so no gradient waits for the last one;
                    # on a jit argument (the eager step) it is nothing.
                    g_ = jax.lax.optimization_barrier(g_)
                    np_, nst = self._update_leaf(p_, g_, st, lr_, step_, wd)
                    new_ps.append(np_)
                    new_sts.append(nst)
                return new_ps, new_sts

            # One fused XLA program for the whole step, cached across calls
            # (weight_decay is static: it appears in python-level branches).
            self._jit_step_fn = jax.jit(fused, static_argnums=(5,))

        new_p_arrays, new_states = self._jit_step_fn(
            p_arrays, g_arrays, states, lr, step, weight_decay
        )
        with paddle_tpu.no_grad():
            for p, new_data, new_state in zip(params, new_p_arrays, new_states):
                p._data = new_data
                self._accumulators[id(p)] = new_state

    def _advance_lr(self) -> None:
        from paddle_tpu.optimizer.lr import LRScheduler

        if isinstance(self._learning_rate, LRScheduler) and self._learning_rate.auto_step:
            pass  # schedulers advance via user-called scheduler.step() in paddle

    def clear_grad(self, set_to_zero: bool = False) -> None:
        for p in self._parameters:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss: Tensor, startup_program: Any = None, parameters: Any = None, no_grad_set: Any = None) -> None:
        loss.backward()
        self.step()

    # -- serialization --------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        sd: Dict[str, Any] = {"_step_count": self._step_count}
        for i, p in enumerate(self._parameters):
            st = self._accumulators.get(id(p))
            if st is not None:
                for k, v in st.items():
                    sd[f"{p.name}__{k}"] = Tensor(v)
        from paddle_tpu.optimizer.lr import LRScheduler

        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state_dict: Dict[str, Any]) -> None:
        self._step_count = int(state_dict.get("_step_count", 0))
        # the bias-correction time (t in m̂ = m/(1-β₁ᵗ)) lives in the
        # device-side _step_buf, which must resume in lockstep with
        # _step_count — leaving it at zero makes a restored Adam re-run
        # warmup-sized steps and diverge from the uninterrupted trajectory
        self._step_buf = (
            jnp.asarray(self._step_count, jnp.int32) if self._step_count else None
        )
        for p in self._parameters:
            prefix = f"{p.name}__"
            st = {}
            for k, v in state_dict.items():
                if isinstance(k, str) and k.startswith(prefix):
                    st[k[len(prefix):]] = v.data if isinstance(v, Tensor) else jnp.asarray(v)
            if st:
                self._accumulators[id(p)] = st
        from paddle_tpu.optimizer.lr import LRScheduler

        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
