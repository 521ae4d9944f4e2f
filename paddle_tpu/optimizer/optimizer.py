"""Optimizers — parity with python/paddle/optimizer/ + the reference's
optimizer CUDA kernels (operators/optimizers/: sgd, momentum, adam, adamw,
lamb, lars_momentum, adagrad, adadelta, adamax, rmsprop).

Design: every optimizer exposes
  - the stateful paddle API (``step()``/``minimize()``/``clear_grad()``) for
    eager mode, and
  - a pure functional core ``_update(param, grad, state, lr) -> (param, state)``
    over raw jax arrays that the jit train-step compiler and the distributed
    sharding passes reuse — the same math runs under pjit with sharded state,
    which is how ZeRO sharding falls out of sharding specs instead of a
    program rewrite.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import InvalidArgumentError, enforce
from ..core.tensor import Parameter, Tensor, no_grad, wrap_raw
from ..nn.layer_base import Layer
from .lr import LRScheduler

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adagrad", "Adam", "AdamW", "Adamax",
    "Adadelta", "RMSProp", "Lamb", "LarsMomentum",
]


class _MasterView:
    """A Parameter stand-in whose ``_value`` is the f32 master — lets the
    decay fold and sparse-update paths run their p-based math on the
    master without changing their signatures. Forwards everything else
    (regularizer, optimize_attr, name) to the real parameter."""

    def __init__(self, p, master):
        self._p = p
        self._value = master

    def __getattr__(self, name):
        return getattr(self._p, name)


class Optimizer:
    _state_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        if parameters is None:
            from ..static.program import current_program

            if current_program() is None:
                raise InvalidArgumentError(
                    "parameters is required in eager mode (pass layer.parameters())"
                )
            parameters = []  # filled from the Program at minimize()
        if isinstance(parameters, Layer):
            parameters = parameters.parameters()
        self._parameter_list = list(parameters)
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            groups = self._parameter_list
            self._param_groups = groups
            self._parameter_list = [p for g in groups for p in g["params"]]
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._weight_decay = weight_decay
        self._multi_precision = multi_precision
        self._accumulators: Dict[int, dict] = {}
        self._global_step = 0

    # -- lr ------------------------------------------------------------------
    def lr_device_scalar(self):
        """Device scalar of the current LR, cached while the value is
        unchanged — a fresh jnp.asarray would issue one host→device
        transfer every step (constant-LR training needs exactly one).
        Shared by the compiled
        train steps (jit.TrainStep, fleet ParallelTrainStep)."""
        value = self.get_lr()
        cached = getattr(self, "_lr_dev_cache", None)
        if cached is not None and cached[0] == value:
            return cached[1]
        dev = jnp.asarray(value, jnp.float32)
        self._lr_dev_cache = (value, dev)
        return dev

    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        enforce(
            not isinstance(self._learning_rate, LRScheduler),
            "cannot set_lr when learning_rate is a scheduler",
        )
        self._learning_rate = float(value)

    def _lr_for(self, p: Parameter) -> float:
        return self.get_lr() * p.optimize_attr.get("learning_rate", 1.0)

    # -- state ---------------------------------------------------------------
    def _get_state(self, p: Parameter) -> dict:
        key = id(p)
        if key not in self._accumulators:
            self._accumulators[key] = self._init_state_for(p._value)
        return self._accumulators[key]

    def _init_state(self, value) -> dict:
        return {}

    def _init_state_for(self, value) -> dict:
        """State init honoring ``multi_precision``: for a low-precision
        float param, accumulators are built from (and the 'master' key
        holds) the f32 master — the reference multi_precision contract
        (moments and the master are f32 regardless of param dtype). All
        engines and the dygraph path share this entry point."""
        if (self._multi_precision and hasattr(value, "dtype")
                and jnp.issubdtype(value.dtype, jnp.floating)
                and value.dtype != jnp.float32):
            master = jnp.asarray(value, jnp.float32)
            st = self._init_state(master)
            st["master"] = master
            return st
        return self._init_state(value)

    # -- main entry points ---------------------------------------------------
    def step(self):
        from ..core.selected_rows import RowSparseGrad

        with no_grad():
            params_grads = [
                (p, p.grad) for p in self._parameter_list
                if p.trainable and p.grad is not None
            ]
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            self._global_step += 1
            for p, g in params_grads:
                if g is None:
                    continue
                state = self._get_state(p)
                if isinstance(g, RowSparseGrad):
                    if "master" in state:
                        # sparse multi_precision: the row update runs on
                        # the f32 master (a _Shim param view), the resident
                        # re-casts from it; a raw _update_sparse would drop
                        # the master key (Adam) or stale it (SGD)
                        master = state["master"]
                        sub = {k: v for k, v in state.items()
                               if k != "master"}
                        shim = _MasterView(p, master)
                        new_master, new_state = self._update_sparse(
                            shim, g, sub, self._lr_for(p))
                        new_state["master"] = new_master
                        p._value = new_master.astype(p._value.dtype)
                    else:
                        new_value, new_state = self._update_sparse(
                            p, g, state, self._lr_for(p))
                        p._value = new_value
                    self._accumulators[id(p)] = new_state
                    continue
                if "master" in state:
                    # multi_precision: update the f32 master, re-cast the
                    # low-precision param from it. L2 decay folds on the
                    # MASTER (same as apply_optimizer_update in the
                    # compiled engines — decay on the bf16 resident would
                    # make dygraph and compiled runs drift)
                    master = state["master"]
                    graw = g._value.astype(jnp.float32)
                    graw = self._apply_decay_to_grad(_MasterView(p, master),
                                                     graw)
                    sub = {k: v for k, v in state.items() if k != "master"}
                    new_master, new_state = self._update(
                        master, graw, sub, self._lr_for(p))
                    new_state["master"] = new_master
                    p._value = new_master.astype(p._value.dtype)
                else:
                    graw = g._value.astype(p._value.dtype) if g.dtype != p.dtype else g._value
                    graw = self._apply_decay_to_grad(p, graw)
                    new_value, new_state = self._update(
                        p._value, graw, state, self._lr_for(p)
                    )
                    p._value = new_value
                self._accumulators[id(p)] = new_state

    def _update_sparse(self, p, g, state, lr):
        """Row-sparse (SelectedRows-equivalent) update. Base fallback
        densifies — correct for every optimizer; SGD/Adam override with
        true O(touched rows) paths (reference sparse kernels:
        operators/optimizers/adam_op.h:464, sgd_op.h SelectedRows branch)."""
        graw = self._apply_decay_to_grad(p, g.to_dense().astype(p._value.dtype))
        return self._update(p._value, graw, state, lr)

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        # static mode: attach to the active Program — the Executor compiles
        # forward+backward+update into one jitted step (parity: minimize
        # appends backward + optimizer ops to the ProgramDesc).
        from ..static.program import current_program

        prog = current_program()
        if prog is not None:
            if not self._parameter_list:
                self._parameter_list = prog.all_parameters()
            prog._optimize = (self, loss)
            return [], [(p, None) for p in self._parameter_list]
        loss.backward()
        self.step()
        return [], [(p, p.grad) for p in self._parameter_list]

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def _apply_decay_to_grad(self, p: Parameter, graw):
        """L2 regularization folded into the gradient (reference semantics:
        regularizer appends the decay term before the optimizer op). AdamW
        overrides with decoupled decay."""
        wd = self._decay_coeff(p)
        if wd:
            graw = graw + wd * p._value.astype(graw.dtype)
        return graw

    def _decay_coeff(self, p: Parameter) -> float:
        reg = getattr(p, "regularizer", None)
        if reg is not None:
            return float(getattr(reg, "coeff", 0.0) or getattr(reg, "_coeff", 0.0))
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if hasattr(wd, "coeff"):
            return float(wd.coeff)
        if hasattr(wd, "_coeff"):
            return float(wd._coeff)
        return float(wd)

    # -- functional core (override) ------------------------------------------
    def _update(self, param, grad, state, lr):
        raise NotImplementedError

    # -- checkpoint ----------------------------------------------------------
    def state_dict(self) -> dict:
        out = {"global_step": self._global_step}
        for i, p in enumerate(self._parameter_list):
            st = self._accumulators.get(id(p))
            if st is None:
                continue
            for k, v in st.items():
                out[f"{p.name}__{k}"] = wrap_raw(v) if not isinstance(v, (int, float)) else v
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state_dict: dict):
        self._global_step = int(state_dict.get("global_step", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        for p in self._parameter_list:
            st = {}
            for k in self._state_names + ["master"]:
                key = f"{p.name}__{k}"
                if key in state_dict:
                    v = state_dict[key]
                    st[k] = v._value if isinstance(v, Tensor) else (
                        jnp.asarray(v) if isinstance(v, np.ndarray) else v
                    )
            if st:
                base = self._init_state_for(p._value)
                base.update(st)
                self._accumulators[id(p)] = base

    # lr scheduler passthrough
    def _append_optimize_op(self, *a, **k):  # compat no-op
        return None


class SGD(Optimizer):
    def _update(self, param, grad, state, lr):
        return param - lr * grad, state

    def _update_sparse(self, p, g, state, lr):
        if self._decay_coeff(p):
            return super()._update_sparse(p, g, state, lr)
        # duplicates are fine under scatter-add; sentinel rows drop
        vals = (lr * g.values.astype(jnp.float32)).astype(p._value.dtype)
        return p._value.at[g.rows].add(-vals, mode="drop"), state


class Momentum(Optimizer):
    _state_names = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name,
                         multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, value):
        return {"velocity": jnp.zeros_like(value)}

    def _update(self, param, grad, state, lr):
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            new_p = param - lr * (grad + self._momentum * v)
        else:
            new_p = param - lr * v
        return new_p, {"velocity": v}


class LarsMomentum(Momentum):
    """LARS (operators/optimizers/lars_momentum_op.cc parity)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=0, name=None,
                 multi_precision=False):
        super().__init__(learning_rate, momentum, parameters, False, None,
                         grad_clip, name, multi_precision)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._epsilon = epsilon
        self._exclude = exclude_from_weight_decay or []

    def _update(self, param, grad, state, lr):
        pn = jnp.sqrt(jnp.sum(param.astype(jnp.float32) ** 2))
        gn = jnp.sqrt(jnp.sum(grad.astype(jnp.float32) ** 2))
        local_lr = jnp.where(
            (pn > 0) & (gn > 0),
            lr * self._lars_coeff * pn / (gn + self._lars_wd * pn + self._epsilon),
            jnp.asarray(lr, jnp.float32),
        ).astype(param.dtype)
        v = self._momentum * state["velocity"] + local_lr * (
            grad + self._lars_wd * param
        )
        return param - v, {"velocity": v}


class Adagrad(Optimizer):
    _state_names = ["moment"]

    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, value):
        return {"moment": jnp.full_like(value, self._init_acc)}

    def _update(self, param, grad, state, lr):
        m = state["moment"] + grad * grad
        new_p = param - lr * grad / (jnp.sqrt(m) + self._epsilon)
        return new_p, {"moment": m}


class Adam(Optimizer):
    _state_names = ["moment1", "moment2", "beta1_pow", "beta2_pow"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name,
                         multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lazy = bool(lazy_mode)

    def _init_state(self, value):
        return {
            "moment1": jnp.zeros_like(value),
            "moment2": jnp.zeros_like(value),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, param, grad, state, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        new_p = param - (lr_t * m1 / (jnp.sqrt(m2) + eps)).astype(param.dtype)
        return new_p, {"moment1": m1, "moment2": m2, "beta1_pow": b1p, "beta2_pow": b2p}

    def _update_sparse(self, p, g, state, lr):
        """Sparse (SelectedRows-equivalent) Adam, both reference modes
        (operators/optimizers/adam_op.h:464):

        - ``lazy_mode=False`` (default): the merged sparse grad is
          numerically a dense grad that is zero off the touched rows, so
          moments decay everywhere and ONLY touched rows receive the
          (1-β)·g increment — bit-matches the dense path while never
          materializing the [vocab, dim] gradient;
        - ``lazy_mode=True``: moments and the parameter are read, updated,
          and written back ONLY at the looked-up rows — O(touched·dim)
          work and traffic; untouched rows keep their moments.
        Works over the MERGED gradient: duplicates must combine before the
        moment update or β-decay applies more than once."""
        if self._decay_coeff(p):
            return super()._update_sparse(p, g, state, lr)
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = g.merged()
        rows, vals = m.rows, m.values.astype(jnp.float32)
        param = p._value
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        if not self._lazy:
            m1 = (b1 * state["moment1"]).at[rows].add(
                ((1 - b1) * vals).astype(state["moment1"].dtype),
                mode="drop")
            m2 = (b2 * state["moment2"]).at[rows].add(
                ((1 - b2) * vals * vals).astype(state["moment2"].dtype),
                mode="drop")
            new_p = param - (lr_t * m1 / (jnp.sqrt(m2) + eps)).astype(
                param.dtype)
            return new_p, {"moment1": m1, "moment2": m2,
                           "beta1_pow": b1p, "beta2_pow": b2p}
        m1_r = jnp.take(state["moment1"], rows, axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        m2_r = jnp.take(state["moment2"], rows, axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        p_r = jnp.take(param, rows, axis=0, mode="fill", fill_value=0)
        m1n = b1 * m1_r + (1 - b1) * vals
        m2n = b2 * m2_r + (1 - b2) * vals * vals
        p_new = p_r - (lr_t * m1n / (jnp.sqrt(m2n) + eps)).astype(param.dtype)
        new_param = param.at[rows].set(p_new, mode="drop")
        mom1 = state["moment1"].at[rows].set(
            m1n.astype(state["moment1"].dtype), mode="drop")
        mom2 = state["moment2"].at[rows].set(
            m2n.astype(state["moment2"].dtype), mode="drop")
        return new_param, {"moment1": mom1, "moment2": mom2,
                           "beta1_pow": b1p, "beta2_pow": b2p}


class AdamW(Adam):
    """Decoupled weight decay (operators/optimizers/adamw — python side
    paddle/optimizer/adamw.py parity)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, None,
                         grad_clip, lazy_mode, multi_precision, name)
        self._coeff = float(weight_decay) if not hasattr(weight_decay, "coeff") else float(weight_decay.coeff)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _apply_decay_to_grad(self, p, graw):
        return graw  # decoupled: applied in _update via param scale

    def _decayed(self, value, g, lr):
        """Decoupled decay honoring gradient sparsity: a RowSparseGrad
        decays ONLY the rows it touches (the reference sparse adamw
        kernel applies decay inside the per-row update, so untouched
        embedding rows keep their values — a dense decay would shrink
        the whole [vocab, dim] table every step). Master and resident
        paths share this so multi_precision cannot drift. NOTE: this is
        an intentional divergence from a dense AdamW run of the same
        data (which decays every row every step) in BOTH lazy modes —
        Adam._update_sparse's dense bit-match contract covers the
        moment/update math, not the decoupled decay, which the
        reference ties to the row kernel."""
        from ..core.selected_rows import RowSparseGrad

        scale = 1.0 - lr * self._coeff
        if isinstance(g, RowSparseGrad):
            return value.at[g.merged().rows].multiply(scale, mode="drop")
        return value * scale

    def step(self):
        from ..core.selected_rows import RowSparseGrad

        with no_grad():
            params_grads = [
                (p, p.grad) for p in self._parameter_list
                if p.trainable and p.grad is not None
            ]
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            self._global_step += 1
            for p, g in params_grads:
                decay = True
                if self._apply_decay_param_fun is not None:
                    decay = self._apply_decay_param_fun(p.name)
                state = self._get_state(p)
                lr = self._lr_for(p)
                if self._lr_ratio is not None:
                    lr = lr * self._lr_ratio(p)
                if "master" in state:
                    # multi_precision: decoupled decay + update on the f32
                    # master, resident re-cast from it (base step's master
                    # branch, with AdamW's pre-scale)
                    master = state["master"]
                    if decay and self._coeff:
                        master = self._decayed(master, g, lr)
                    sub = {k: v for k, v in state.items() if k != "master"}
                    if isinstance(g, RowSparseGrad):
                        new_master, new_state = self._update_sparse(
                            _MasterView(p, master), g, sub, lr)
                    else:
                        new_master, new_state = self._update(
                            master, g._value.astype(jnp.float32), sub, lr)
                    new_state["master"] = new_master
                    p._value = new_master.astype(p._value.dtype)
                    self._accumulators[id(p)] = new_state
                    continue
                if decay and self._coeff:
                    p._value = self._decayed(p._value, g, lr)
                if isinstance(g, RowSparseGrad):
                    new_value, new_state = self._update_sparse(p, g, state, lr)
                else:
                    graw = (g._value.astype(p._value.dtype)
                            if g.dtype != p.dtype else g._value)
                    new_value, new_state = self._update(p._value, graw,
                                                        state, lr)
                p._value = new_value
                self._accumulators[id(p)] = new_state


class Adamax(Optimizer):
    _state_names = ["moment", "inf_norm", "beta1_pow"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _init_state(self, value):
        return {
            "moment": jnp.zeros_like(value),
            "inf_norm": jnp.zeros_like(value),
            "beta1_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, param, grad, state, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        b1p = state["beta1_pow"] * b1
        m = b1 * state["moment"] + (1 - b1) * grad
        u = jnp.maximum(b2 * state["inf_norm"], jnp.abs(grad) + eps)
        new_p = param - (lr / (1 - b1p)).astype(param.dtype) * m / u
        return new_p, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adadelta(Optimizer):
    _state_names = ["avg_squared_grad", "avg_squared_update"]

    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._rho = rho

    def _init_state(self, value):
        return {
            "avg_squared_grad": jnp.zeros_like(value),
            "avg_squared_update": jnp.zeros_like(value),
        }

    def _update(self, param, grad, state, lr):
        rho, eps = self._rho, self._epsilon
        asg = rho * state["avg_squared_grad"] + (1 - rho) * grad * grad
        update = grad * jnp.sqrt(state["avg_squared_update"] + eps) / jnp.sqrt(asg + eps)
        asu = rho * state["avg_squared_update"] + (1 - rho) * update * update
        return param - lr * update, {"avg_squared_grad": asg, "avg_squared_update": asu}


class RMSProp(Optimizer):
    _state_names = ["mean_square", "mean_grad", "momentum_acc"]

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _init_state(self, value):
        return {
            "mean_square": jnp.zeros_like(value),
            "mean_grad": jnp.zeros_like(value),
            "momentum_acc": jnp.zeros_like(value),
        }

    def _update(self, param, grad, state, lr):
        rho, eps = self._rho, self._epsilon
        ms = rho * state["mean_square"] + (1 - rho) * grad * grad
        mg = state["mean_grad"]
        if self._centered:
            mg = rho * mg + (1 - rho) * grad
            denom = jnp.sqrt(ms - mg * mg + eps)
        else:
            denom = jnp.sqrt(ms + eps)
        mom = self._momentum * state["momentum_acc"] + lr * grad / denom
        return param - mom, {"mean_square": ms, "mean_grad": mg, "momentum_acc": mom}


class Lamb(Optimizer):
    _state_names = ["moment1", "moment2", "beta1_pow", "beta2_pow"]

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, value):
        return {
            "moment1": jnp.zeros_like(value),
            "moment2": jnp.zeros_like(value),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, param, grad, state, lr, decay=True):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        m1_hat = m1 / (1 - b1p)
        m2_hat = m2 / (1 - b2p)
        r = m1_hat / (jnp.sqrt(m2_hat) + eps)
        if decay and self._lamb_wd:
            r = r + self._lamb_wd * param
        w_norm = jnp.sqrt(jnp.sum(param.astype(jnp.float32) ** 2))
        r_norm = jnp.sqrt(jnp.sum(r.astype(jnp.float32) ** 2))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0).astype(param.dtype)
        new_p = param - lr * trust * r
        return new_p, {"moment1": m1, "moment2": m2, "beta1_pow": b1p, "beta2_pow": b2p}

    def step(self):
        with no_grad():
            params_grads = [
                (p, p.grad) for p in self._parameter_list
                if p.trainable and p.grad is not None
            ]
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            self._global_step += 1
            for p, g in params_grads:
                graw = g._value.astype(p._value.dtype)
                decay = True
                if self._exclude_fn is not None and self._exclude_fn(p):
                    decay = False
                state = self._get_state(p)
                new_value, new_state = self._update(
                    p._value, graw, state, self._lr_for(p), decay
                )
                p._value = new_value
                self._accumulators[id(p)] = new_state
