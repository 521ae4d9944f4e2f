"""ParallelTrainStep — the fleet execution engine.

This is where the reference's meta-optimizer program rewrites
(raw_program_optimizer.py inserting c_allreduce_sum, sharding_optimizer.py
segmenting/broadcasting/reducing, tensor_parallel_optimizer.py wiring rings)
become *sharding declarations*: one jitted train step whose parameter,
optimizer-state, and batch shardings over the named mesh axes make XLA emit
exactly the collectives each strategy needs:

- **DP**: batch sharded over 'dp' → grad psum (fused, scheduled by XLA's
  latency-hiding scheduler — the hand-built Reducer bucketing of
  imperative/reducer.cc is subsumed).
- **TP**: params carry ``tp_spec`` ('mp' axis) set by the model/mp_layers →
  Megatron-style column/row sharding; XLA inserts the identity/allreduce
  pairs the reference codes as _c_identity/_mp_allreduce.
- **ZeRO (sharding_optimizer.py parity)**: stage 1 shards optimizer state
  over 'sharding'; stage 2 additionally leaves grads reduce-scattered (XLA
  folds psum+dynamic-slice into reduce-scatter); stage 3 shards the
  parameters themselves (gathered on use).
- **Recompute**: jax.checkpoint over the forward (activation checkpointing).
- **bf16/AMP O2**: two shapes, both reference semantics — default keeps
  fp32 params and casts to compute_dtype inside the step (the cast fuses
  into consumers); ``multi_precision=True`` on the optimizer (or
  ``master_weights=True`` here) keeps bf16 RESIDENT params with the f32
  master riding opt_state (reference multi_precision contract —
  checkpoints carry the masters, ZeRO shards them with the moments).
  Measured throughput-neutral on GPT-2 345M single-chip; the win is HBM
  capacity/sharding shape, not bandwidth.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.profiler import device_profile as _device_profile
from paddle_tpu.profiler import goodput as _goodput
from paddle_tpu.profiler import spans as _spans
from paddle_tpu.profiler import xla_cost as _xla_cost
from paddle_tpu.profiler.retrace import tracked_jit
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.resilience.watchdog import heartbeat as _watchdog_heartbeat
from paddle_tpu.utils import profiler as _host_profiler
from paddle_tpu.jit.functionalize import (
    functionalize,
    get_buffers,
    get_params,
    set_buffers,
    set_params,
)

__all__ = ["ParallelTrainStep", "param_partition_spec", "apply_optimizer_update"]


def _grouped_adam_update(opt, group, params, grads, opt_state, lr):
    """One fused Adam update over many small parameters.

    The per-param loop emits hundreds of [1024]-sized fusions and S(1)
    staging copies for a transformer's LN/bias vectors (profiled: ~3 ms/step
    of tiny copies on GPT-2 345M). Concatenating the group into one flat
    buffer runs the same elementwise math as ONE fusion — the multi-tensor
    equivalent of the reference's fused optimizer kernels
    (operators/optimizers/merged_adam_op.cc). Bit-identical per param:
    concat/split don't change values, and each member's OWN beta powers are
    broadcast along its slice of the flat buffer (members' step counts can
    differ when parameters join the optimizer mid-training — a scalar
    beta_pow taken from group[0] would mis-correct the others).
    """
    sizes = [int(np.prod(params[n].shape)) for n in group]
    flat = jnp.concatenate([params[n].reshape(-1) for n in group])
    gflat = jnp.concatenate(
        [grads[n].astype(params[n].dtype).reshape(-1) for n in group])
    m1 = jnp.concatenate([opt_state[n]["moment1"].reshape(-1) for n in group])
    m2 = jnp.concatenate([opt_state[n]["moment2"].reshape(-1) for n in group])
    bp = lambda key: jnp.concatenate(
        [jnp.broadcast_to(opt_state[n][key].reshape(()), (sz,))
         for n, sz in zip(group, sizes)])
    st = {"moment1": m1, "moment2": m2,
          "beta1_pow": bp("beta1_pow"), "beta2_pow": bp("beta2_pow")}
    new_flat, new_st = opt._update(flat, gflat, st, lr)
    offs = np.cumsum([0] + sizes)
    new_params, new_state = {}, {}
    for i, n in enumerate(group):
        shape = params[n].shape
        new_params[n] = new_flat[offs[i]:offs[i + 1]].reshape(shape)
        new_state[n] = {
            "moment1": new_st["moment1"][offs[i]:offs[i + 1]].reshape(shape),
            "moment2": new_st["moment2"][offs[i]:offs[i + 1]].reshape(shape),
            # per-member scalar advance (== the broadcast slice's value)
            "beta1_pow": opt_state[n]["beta1_pow"] * opt._beta1,
            "beta2_pow": opt_state[n]["beta2_pow"] * opt._beta2,
        }
    return new_params, new_state


# params at or below this numel are grouped into one fused Adam update
_GROUP_NUMEL = 65536


def _raw_tuple(x):
    """Batch-side Tensor unwrapping shared by __call__/run_steps: a lone
    array or a tuple/list of them → tuple of raw jax values."""
    return tuple(a._value if isinstance(a, Tensor) else jnp.asarray(a)
                 for a in (x if isinstance(x, (tuple, list)) else (x,)))


def master_aware_update(opt, p, g, state, lr, **kw):
    """opt._update honoring a ``master`` key in ``state`` (multi_precision):
    the update runs on the f32 master, the low-precision param is re-cast
    from the new master, and the key survives in the returned state. The
    single-param twin of apply_optimizer_update's master handling — used
    by the engines that apply updates param-by-param (jit.TrainStep,
    pipeline _tree_update)."""
    if isinstance(state, dict) and "master" in state:
        master = state["master"]
        sub = {k: v for k, v in state.items() if k != "master"}
        new_master, ns = opt._update(master, g.astype(jnp.float32), sub,
                                     lr, **kw)
        ns["master"] = new_master
        return new_master.astype(p.dtype), ns
    return opt._update(p, g.astype(p.dtype), state, lr, **kw)


def apply_optimizer_update(opt, named_params, params, grads, opt_state, lr,
                           group_small=True):
    """Functional optimizer application shared by every fleet engine.

    Replicates what ``Optimizer.step()`` does imperatively (optimizer.py):
    global-norm gradient clipping, L2 decay folded into the grad, AdamW's
    decoupled decay applied to the param, then the per-param ``_update``.
    Keeping it in one place stops the engines drifting from each other.
    Small parameters under a plain Adam take the grouped multi-tensor path
    (``_grouped_adam_update``) — pass ``group_small=False`` when optimizer
    state is dim-sharded (ZeRO): concatenating sharded moments would make
    GSPMD gather/rescatter them every step.

    Everything here (clip, decay, update, master -> resident cast) carries
    the ``optimizer`` scope in the compiled step.
    """
    with jax.named_scope("optimizer"):
        return _optimizer_update(opt, named_params, params, grads, opt_state,
                                 lr, group_small)


def _optimizer_update(opt, named_params, params, grads, opt_state, lr,
                      group_small):
    if opt._grad_clip is not None:
        from paddle_tpu.nn.clip import ClipGradByGlobalNorm, clip_grads_global_norm_raw

        if isinstance(opt._grad_clip, ClipGradByGlobalNorm):
            grads = clip_grads_global_norm_raw(grads, opt._grad_clip.clip_norm)
    # master-weight mixed precision (reference optimizer multi_precision):
    # resident params are low-precision; the f32 master rides opt_state.
    # The whole update below then runs on the f32 masters — moments,
    # decay, clip math all f32 — and the low-precision param is re-cast
    # from the new master at the end.
    masters = {n: st["master"] for n, st in opt_state.items()
               if isinstance(st, dict) and "master" in st}
    low_dtypes = {}
    if masters:
        low_dtypes = {n: params[n].dtype for n in masters}
        params = {**params, **masters}
        grads = {n: (g.astype(jnp.float32) if n in masters
                     and hasattr(g, "astype") else g)
                 for n, g in grads.items()}
        opt_state = {n: ({k: v for k, v in st.items() if k != "master"}
                         if n in masters else st)
                     for n, st in opt_state.items()}
    new_params, new_state = {}, {}
    is_adamw = type(opt).__name__ == "AdamW"
    is_lamb = type(opt).__name__ == "Lamb"
    grouped = set()
    if group_small and type(opt).__name__ == "Adam" and not opt._lazy:
        # group by (weight-decay coefficient, dtype) so the folded L2 term
        # stays uniform and jnp.concatenate never silently promotes
        # mixed-dtype members; dense ndarray grads only
        by_wd = {}
        for name, pv in params.items():
            g = grads[name]
            if (hasattr(g, "astype") and hasattr(g, "reshape")
                    and int(np.prod(pv.shape)) <= _GROUP_NUMEL):
                key = (float(opt._decay_coeff(named_params[name])),
                       str(pv.dtype))
                by_wd.setdefault(key, []).append(name)
        for (wd, _dt), group in by_wd.items():
            if len(group) < 2:
                continue
            ggrads = grads
            if wd:
                ggrads = dict(grads)
                for n in group:
                    ggrads[n] = grads[n].astype(params[n].dtype) \
                        + wd * params[n]
            np_, ns_ = _grouped_adam_update(opt, group, params, ggrads,
                                            opt_state, lr)
            new_params.update(np_)
            new_state.update(ns_)
            grouped.update(group)
    for name, pv in params.items():
        if name in grouped:
            continue
        g = grads[name].astype(pv.dtype)
        wd = opt._decay_coeff(named_params[name])
        if wd and not is_adamw:
            g = g + wd * pv
        if is_adamw and getattr(opt, "_coeff", 0.0):
            if (opt._apply_decay_param_fun is None
                    or opt._apply_decay_param_fun(name)):
                pv = pv * (1.0 - lr * opt._coeff)
        if is_lamb:
            # Lamb.step() parity: honor exclude_from_weight_decay_fn
            decay = (opt._exclude_fn is None
                     or not opt._exclude_fn(named_params[name]))
            np_, ns = opt._update(pv, g, opt_state[name], lr, decay=decay)
        else:
            np_, ns = opt._update(pv, g, opt_state[name], lr)
        new_params[name] = np_
        new_state[name] = ns
    for n in masters:
        master_new = new_params[n]
        new_state[n] = {**new_state[n], "master": master_new}
        new_params[n] = master_new.astype(low_dtypes[n])
    return new_params, new_state


def param_partition_spec(param, shape, zero_stage=0, sharding_axis="sharding",
                         mesh: Optional[Mesh] = None, shard_params=False):
    """Combine the param's tensor-parallel spec with ZeRO dim-sharding."""
    tp = list(getattr(param, "tp_spec", None) or (None,) * len(shape))
    tp = (tp + [None] * len(shape))[: len(shape)]
    if mesh is not None:
        # drop tp axes absent from (or trivial in) this mesh, and axes that
        # don't divide the dim
        tp = [
            a if (a in mesh.axis_names and mesh.shape[a] > 1
                  and shape[i] % mesh.shape[a] == 0) else None
            for i, a in enumerate(tp)
        ]
    if shard_params and mesh is not None and sharding_axis in mesh.axis_names:
        size = mesh.shape[sharding_axis]
        if size > 1:
            # shard the first dim that is divisible and not already tp-sharded
            for i, (dim, spec) in enumerate(zip(shape, tp)):
                if spec is None and dim % size == 0 and dim >= size:
                    tp[i] = sharding_axis
                    break
    return P(*tp)


class ParallelTrainStep:
    """One jitted SPMD train step over the global mesh.

    Parity notes: this object is what ``fleet.distributed_optimizer`` +
    ``CompiledProgram.with_data_parallel`` compile to. It owns on-device
    params/opt-state (sharded per strategy) and exposes sync_to_layer() for
    checkpointing, like jit.TrainStep.
    """

    def __init__(self, layer, loss_fn: Callable, optimizer, mesh: Mesh,
                 dp_axis="dp", mp_axis="mp", sharding_axis="sharding",
                 zero_stage=0, recompute=False, compute_dtype=None,
                 donate=True, extra_batch_axes=(), offload=False,
                 master_weights=None, check_finite=None,
                 guard_updates=False, remat=None, sp_axis=None,
                 fingerprint_every=None):
        self._layer = layer
        self._optimizer = optimizer
        self._loss_fn = loss_fn
        self._mesh = mesh
        self._apply = functionalize(layer, training=True)
        self._named_params = dict(layer.named_parameters())
        self._zero = zero_stage
        self._compute_dtype = compute_dtype
        self._dirty = True
        # master-weight mixed precision (reference: optimizer
        # multi_precision=True + fp16/bf16 params): resident params live in
        # compute_dtype and the f32 master rides opt_state. Kills the
        # per-step f32->bf16 cast pass (~1.4 GB read at GPT-2 345M) and
        # halves the grad/param HBM traffic outside the Adam update.
        # Defaults to the optimizer's multi_precision flag.
        if master_weights is None:
            master_weights = bool(getattr(optimizer, "_multi_precision",
                                          False))
        self._master = bool(master_weights and compute_dtype is not None
                            and jnp.issubdtype(compute_dtype, jnp.floating))

        params_host = get_params(layer)
        buffers_host = get_buffers(layer)

        # -- shardings ------------------------------------------------------
        self._param_specs = {
            name: param_partition_spec(
                self._named_params[name], v.shape, zero_stage, sharding_axis,
                mesh, shard_params=(zero_stage >= 3),
            )
            for name, v in params_host.items()
        }
        self._param_shardings = {
            n: NamedSharding(mesh, s) for n, s in self._param_specs.items()
        }

        # ZeRO offload (sharding_optimizer.py offload=True parity): optimizer
        # state lives in host DRAM ("pinned_host" memory space) between steps
        # and is streamed to device memory around the jitted update — on TPU
        # this frees HBM for params/activations the way the reference frees
        # GPU memory. The transfers happen outside the compiled step (async
        # device_put), keeping the XLA program all-device.
        self._offload = bool(offload)

        def opt_state_sharding(name, v):
            pspec = self._param_specs[name]
            st = optimizer._init_state(v)
            if self._master and jnp.issubdtype(v.dtype, jnp.floating):
                st = {**st, "master": v}  # same shape -> same sharding rule
            out = {}  # (dtype is irrelevant here — only shapes drive specs)
            for k, s in st.items():
                if hasattr(s, "shape") and s.shape == v.shape and zero_stage >= 1:
                    spec = param_partition_spec(
                        self._named_params[name], v.shape, zero_stage,
                        sharding_axis, mesh, shard_params=True,
                    )
                    out[k] = NamedSharding(mesh, spec)
                elif hasattr(s, "shape") and s.shape == v.shape:
                    out[k] = NamedSharding(mesh, pspec)
                else:
                    out[k] = NamedSharding(mesh, P())
            return out

        self._opt_shardings = {
            n: opt_state_sharding(n, v) for n, v in params_host.items()
        }
        self._opt_host_shardings = {
            n: {k: s.with_memory_kind("pinned_host") for k, s in d.items()}
            for n, d in self._opt_shardings.items()
        } if offload else None
        batch_axes = (dp_axis,) + tuple(extra_batch_axes)
        dim0 = batch_axes if len(batch_axes) > 1 else dp_axis
        # sequence/context parallelism (``sp_axis``): batch leaves with a
        # sequence dim land SHARDED over the ring axis (dim 1), so when
        # 'auto' attention promotes onto ring_attention the Q/K/V shards
        # are already rotated into place — the shard_map boundary inside
        # the step reshards nothing. The ring mesh context is a
        # trace-time global (like set_attention_impl): the most recently
        # constructed engine owns it — an engine WITHOUT sp_axis clears
        # it, so its traces can never promote onto a dead engine's mesh.
        if sp_axis is not None and sp_axis not in mesh.axis_names:
            raise ValueError(
                f"sp_axis {sp_axis!r} is not an axis of this mesh "
                f"{tuple(mesh.axis_names)}")
        self._sp_axis = sp_axis
        from paddle_tpu.ops.attention import set_ring_context

        set_ring_context(mesh, sp_axis, batch_axis=dim0)
        try:
            # per-axis collective attribution maps the compiled HLO's
            # replica_groups back to THIS mesh's named axes — the most
            # recently constructed engine's mesh describes the programs
            # compiled after it (same last-wins rule as the ring context)
            from paddle_tpu.profiler import collective_attrib

            collective_attrib.register_mesh(mesh)
        except Exception:  # noqa: BLE001 — attribution never blocks build
            pass
        if self._sp_axis is not None:
            self._batch_sharding = NamedSharding(
                mesh, P(dim0, self._sp_axis))
        else:
            self._batch_sharding = NamedSharding(mesh, P(dim0))
        repl = NamedSharding(mesh, P())
        self._repl = repl

        # -- device state ---------------------------------------------------
        def resident(v):
            if (self._master and jnp.issubdtype(v.dtype, jnp.floating)
                    and compute_dtype is not None):
                return v.astype(compute_dtype)
            return v

        self._params = {
            n: jax.device_put(resident(v), self._param_shardings[n])
            for n, v in params_host.items()
        }
        self._buffers = {n: jax.device_put(v, repl) for n, v in buffers_host.items()}
        opt_home = self._opt_host_shardings if offload else self._opt_shardings

        def init_state(v):
            if self._master and jnp.issubdtype(v.dtype, jnp.floating):
                # accumulators are built FROM the f32 master: an
                # _init_state(bf16 resident) would make bf16 moments whose
                # dtype flips to f32 after the first master-mode update —
                # breaking the run_steps scan carry and step donation
                master = jnp.asarray(v, jnp.float32)
                st = optimizer._init_state(master)
                st["master"] = master
                return st
            return optimizer._init_state(v)

        self._opt_state = {
            n: {
                k: jax.device_put(s, opt_home[n][k])
                for k, s in init_state(v).items()
            }
            for n, v in params_host.items()
        }

        opt = optimizer
        named = self._named_params
        apply = self._apply
        cd = compute_dtype

        master_mode = self._master

        def forward_loss(p, buffers, inputs, labels):
            if cd is not None and not master_mode:
                p = jax.tree_util.tree_map(
                    lambda a: a.astype(cd) if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    p,
                )
            out, new_b = apply(p, buffers, *inputs)
            loss = loss_fn(out, *labels)
            if isinstance(loss, Tensor):
                loss = loss._value
            return loss.astype(jnp.float32), new_b

        # ``remat`` supersedes the all-or-nothing ``recompute`` flag (whose
        # legacy vocabulary — False/True/'dots'/'dots_no_batch'/'nothing' —
        # still works and maps onto the same policies): 'off' | 'full' |
        # an explicit jax.checkpoint policy | 'auto', which MEASURES the
        # compiled step's peak HBM against the chip's capacity at the
        # first call (ops.remat_policy, fed by the PR 5 attribution layer)
        # and escalates dots→nothing→offload only as far as needed.
        from paddle_tpu.ops import remat_policy as _remat_policy

        if remat is None:
            remat = recompute
        self._remat = _remat_policy.normalize(remat)
        self._forward_loss_base = forward_loss

        # grouped small-param updates conflict with dim-sharded opt state
        group_small = (zero_stage == 0
                       or sharding_axis not in mesh.axis_names
                       or mesh.shape[sharding_axis] == 1)
        self._group_small = group_small

        from ...core.sanitizer import (finite_flags, jit_check_enabled,
                                       select_if_finite)

        # guard_updates (resilience.StepGuard contract): the compiled step
        # selects updated-vs-incoming state on its own finite sweep, so a
        # non-finite step never applies its update; flags are read by the
        # guard host-side instead of raising.
        self._guard_updates = bool(guard_updates)
        self._check_nan = (jit_check_enabled() if check_finite is None
                           else bool(check_finite)) or self._guard_updates
        self._nan_names: list = []
        self._last_flags = None

        # in-jit state fingerprints (resilience.integrity contract) —
        # same trace-time gate as jit.TrainStep: the fingerprint code is
        # compiled in at build time, due-ness per step rides a TRACED
        # bool, so the retrace budget is untouched
        from paddle_tpu.resilience.integrity import fingerprint_every_from_env

        if fingerprint_every is None:
            fingerprint_every = fingerprint_every_from_env()
        self._fp_every = max(0, int(fingerprint_every))
        import collections as _collections
        import os as _os

        self._fp_history: _collections.deque = _collections.deque(
            maxlen=int(_os.environ.get("PADDLE_TPU_FP_HISTORY", "64") or 64))

        def _with_fingerprint(new_params, new_buffers, new_opt, fp_due):
            from ...core.sanitizer import tree_fingerprint, zero_fingerprint

            # the state the program RETURNS (post-update, post-guarded-
            # select) — reductions over sharded leaves are global, so
            # every rank of a jax-distributed mesh computes the SAME
            # scalars by construction and divergence detection targets
            # replica worlds (independent processes, DP replicas)
            return jax.lax.cond(
                fp_due,
                lambda: tree_fingerprint(new_params, new_opt, new_buffers),
                zero_fingerprint)

        def step_fn_of(fwd):
            """The 5-arg CORE step (scan body for run_steps). The
            per-step jitted entry wraps it with the traced
            fingerprint-due argument when fingerprinting is on
            (``_wrap_fp``).

            The function's name is the compiled module's
            (``jit_train_step``), and the module's name is part of the
            persistent compile cache's key while ``jax.named_scope``
            names are not (the key is taken with debug info stripped).
            It was ``step_core`` before the scopes came: under the old
            name a cache that held the scopeless program would go on
            serving it, and a trace of it names no scope."""
            def train_step(params, buffers, opt_state, lr, batch):
                inputs, labels = batch
                (loss, new_buffers), grads = jax.value_and_grad(
                    fwd, has_aux=True)(params, buffers, inputs, labels)
                new_params, new_opt = apply_optimizer_update(
                    opt, named, params, grads, opt_state, lr,
                    group_small=group_small)
                flags = (finite_flags(self._nan_names, loss=loss, grad=grads,
                                      param=new_params)
                         if self._check_nan else None)
                if self._guard_updates and flags is not None:
                    new_params, new_buffers, new_opt = select_if_finite(
                        flags, (new_params, new_buffers, new_opt),
                        (params, buffers, opt_state))
                return new_params, new_buffers, new_opt, loss, flags

            return train_step

        self._with_fingerprint = _with_fingerprint

        self._step_fn_of = step_fn_of

        # input placement is handled by the explicit device_put in __call__
        # (batch arity varies per model, so a static in_shardings tuple
        # cannot describe it); outputs pin the persistent state's shardings
        out_shardings = (
            self._param_shardings,
            {n: repl for n in buffers_host},
            self._opt_shardings,
            repl,
            repl if self._check_nan else None,  # None output = empty subtree
        ) + ((repl,) if self._fp_every else ())  # fingerprint scalars
        self._out_shardings = out_shardings
        self._donate = donate
        if self._remat == "auto":
            # resolved against the FIRST batch's avals (remat candidates
            # are lowered+compiled and their measured peak HBM laddered
            # against the chip budget), then built once — no per-step work
            self._step_fn = None
            self._jitted = None
        else:
            self._build_jitted(_remat_policy.apply_policy(
                forward_loss, self._remat))
        self._jitted_multi = None
        self._last_step_t = None  # inter-call interval ⇒ steady-state step time

    # ----------------------------------------------------------------------
    def _wrap_fp(self, step_core):
        """Per-step jit entry: the core plus the traced fingerprint-due
        bool when fingerprinting is on (run_steps scans the CORE and
        fingerprints the final carry instead)."""
        if not self._fp_every:
            return step_core

        def step_fn(params, buffers, opt_state, lr, batch, fp_due):
            new_params, new_buffers, new_opt, loss, flags = step_core(
                params, buffers, opt_state, lr, batch)
            fp = self._with_fingerprint(new_params, new_buffers, new_opt,
                                        fp_due)
            return new_params, new_buffers, new_opt, loss, flags, fp

        return step_fn

    def _fp_args(self):
        """The trailing traced fingerprint-due argument (probe compiles
        pass False — due-ness never changes the program signature)."""
        return (jnp.asarray(False),) if self._fp_every else ()

    def _build_jitted(self, fwd):
        self._step_fn = self._step_fn_of(fwd)
        self._jitted = tracked_jit(
            self._wrap_fp(self._step_fn),
            name="fleet.train_step",
            sig_argnums=(3, 4),  # lr + batch drift; params/opt state are fixed
            donate_argnums=(0, 2) if self._donate else (),
            out_shardings=self._out_shardings,
        )

    def _candidate_jit(self, policy):
        """A plain-jit twin of the step under remat ``policy``, with the
        real out-shardings and donation so XLA's memory accounting
        matches the step that will actually run (never tracked — probe
        compiles must not pollute the attribution registry)."""
        from paddle_tpu.ops import remat_policy

        fn = self._wrap_fp(self._step_fn_of(
            remat_policy.apply_policy(self._forward_loss_base, policy)))
        return jax.jit(fn, donate_argnums=(0, 2) if self._donate else (),
                       out_shardings=self._out_shardings)

    def lower_cost(self, policy, inputs, labels):
        """XLA's own cost accounting — exact peak HBM, flops, bytes — for
        THIS engine's step compiled under remat ``policy`` (the
        measurement ``remat='auto'`` ladders on). Leaves the engine's
        live jitted step untouched; None when the candidate is
        infeasible on this backend."""
        from paddle_tpu.ops import remat_policy

        batch = (_raw_tuple(inputs), _raw_tuple(labels))
        batch = jax.device_put(batch, self._batch_shardings(batch))
        args = (self._params, self._buffers, self._opt_state,
                self._optimizer.lr_device_scalar(), batch) + self._fp_args()
        return remat_policy.program_cost(self._candidate_jit(policy), args)

    def _resolve_remat(self, lr, batch):
        """remat='auto': measure candidate policies' peak HBM on this
        call's avals (ops.remat_policy ladder) and build the jitted
        step with the winner. Runs once, before the first compile."""
        from paddle_tpu.ops import remat_policy

        args = (self._params, self._buffers, self._opt_state, lr, batch) \
            + self._fp_args()
        chosen = remat_policy.resolve(
            "fleet.train_step",
            lambda policy: remat_policy.program_cost(
                self._candidate_jit(policy), args))
        self._build_jitted(
            remat_policy.apply_policy(self._forward_loss_base, chosen))

    def _batch_shardings(self, tree):
        """Per-leaf sharding tree for one batch: with ``sp_axis`` set,
        leaves whose dim 1 can carry sequence shards (divides the ring
        size) take the (dp, sp) layout while everything else — 1-D
        per-sample leaves (e.g. NSP labels), broadcast-dim masks
        [b, 1, L, L], ragged class dims — stays dp-only; one pytree
        device_put either way. The landing layout is a placement hint
        for GSPMD (the ring's shard_map boundary reshards whatever
        arrives), so dp-only is always SAFE, just not pre-rotated."""
        if self._sp_axis is None:
            return self._batch_sharding
        dp_only = NamedSharding(self._mesh, P(self._batch_sharding.spec[0]))
        sp = self._mesh.shape[self._sp_axis]

        def leaf_sharding(a):
            shape = getattr(a, "shape", ())
            if len(shape) >= 2 and shape[1] >= sp and shape[1] % sp == 0:
                return self._batch_sharding
            return dp_only

        return jax.tree_util.tree_map(leaf_sharding, tree)

    # ----------------------------------------------------------------------
    def _record_step_metrics(self, t_enter, n_steps, n_tokens, loss,
                             compiled=False):
        """Per-step telemetry shared by ``__call__`` and ``run_steps``.

        Dispatch is async, so the wall time spent *inside* the call is
        only the host dispatch cost (``engine/dispatch_ms``). True step
        latency is taken from the interval BETWEEN calls — in steady
        state the device-bound pipeline makes inter-arrival time equal
        the device step time without ever forcing a blocking sync.
        ``loss`` is stored as a deferred device scalar; it is only
        materialized when a snapshot/JSONL export reads the gauge."""
        tel = get_telemetry()
        if not tel.enabled or not n_steps:  # empty window: nothing to time
            return
        now = time.perf_counter()
        tel.counter("engine/steps", n_steps)
        if not compiled:
            # a compiling call's host time is trace+XLA compile, not
            # dispatch — it lands in compile_ms/<name> via tracked_jit;
            # recording it here would permanently skew dispatch_ms
            # mean/max (full-stream aggregates never window out)
            tel.observe("engine/dispatch_ms", (now - t_enter) * 1e3)
        if n_tokens:
            tel.counter("engine/tokens", n_tokens)
        last = self._last_step_t
        if last is not None and now > last and not compiled:
            # ``compiled`` also drops the step interval containing the
            # (re)trace — during exactly the shape-drift pathology the
            # retrace tracker warns about, compile time must not be
            # reported as step latency. The pause filter lives in
            # observe_interval (shared with executor/step_ms; a data
            # stall between steps would otherwise land here even though
            # sync_to_layer resets the anchor around checkpoint/eval).
            dt = now - last
            if tel.observe_interval("engine/step_ms", dt * 1e3 / n_steps):
                if n_tokens:
                    tel.gauge("engine/tokens_per_s", n_tokens / dt)
        self._last_step_t = now
        if loss is not None:
            tel.gauge("engine/loss", loss)
        # inside a profiling window, counters ride the chrome timeline
        _host_profiler.add_counter_snapshot("fleet.step")

    def prefetch(self, batches, depth=2, buckets=None):
        """Wrap a ``(inputs, labels)`` batch iterator in a
        ``DevicePrefetcher`` staged onto THIS engine's batch sharding: the
        background pipeline pads/buckets each batch and issues one async
        pytree ``jax.device_put`` with the step's ``NamedSharding``, so
        every leaf lands already laid out over the mesh while the previous
        step is still running. Batches coming back are device-resident —
        ``__call__``'s device_put on them is then a no-op."""
        from paddle_tpu.io.prefetch import DevicePrefetcher

        return DevicePrefetcher(batches, depth=depth, buckets=buckets,
                                sharding=self._batch_sharding)

    def __call__(self, inputs, labels):
        _watchdog_heartbeat()
        # windowed device-profile capture boundary (no-op unless armed)
        _device_profile.step_boundary("fleet.train_step")
        t_enter = time.perf_counter()
        # goodput: the step call (h2d + dispatch; a compile inside
        # claims its own category) is productive_step wall time
        with _goodput.activity("productive_step"), \
                _spans.span("step", cat="step",
                            step=self._optimizer._global_step):
            with _spans.span("h2d", cat="h2d"):
                # ONE pytree transfer for the whole batch (single
                # dispatch; an already-sharded array — e.g. from
                # ``prefetch`` — passes through without a copy)
                batch = (_raw_tuple(inputs), _raw_tuple(labels))
                raw_in, raw_lab = jax.device_put(
                    batch, self._batch_shardings(batch))
            lr = self._optimizer.lr_device_scalar()
            if self._jitted is None:  # remat='auto': first batch's avals
                self._resolve_remat(lr, (raw_in, raw_lab))
            compiles_before = self._jitted.tracker.compiles
            opt_state = self._opt_state
            if self._offload:
                # stream host-resident optimizer state into HBM (async
                # device_put)
                opt_state = jax.tree_util.tree_map(
                    lambda s, sh: jax.device_put(s, sh)
                    if hasattr(s, "shape") else s,
                    opt_state, self._opt_shardings)
            fp_due = bool(self._fp_every) and \
                self._optimizer._global_step % self._fp_every == 0
            with _spans.span("compute", cat="compute"):
                if self._fp_every:
                    (self._params, self._buffers, new_opt, loss, flags,
                     fp) = self._jitted(self._params, self._buffers,
                                        opt_state, lr, (raw_in, raw_lab),
                                        jnp.asarray(fp_due))
                else:
                    self._params, self._buffers, new_opt, loss, flags = \
                        self._jitted(self._params, self._buffers, opt_state,
                                     lr, (raw_in, raw_lab))
        if self._fp_every and fp_due:
            from paddle_tpu.resilience.integrity import publish_fingerprint

            publish_fingerprint(self._fp_history,
                                self._optimizer._global_step, fp,
                                self._fp_every)
        if self._offload:
            # evacuate the updated state back to host DRAM, freeing HBM
            new_opt = jax.tree_util.tree_map(
                lambda s, sh: jax.device_put(s, sh)
                if hasattr(s, "shape") else s,
                new_opt, self._opt_host_shardings)
        # commit BEFORE any NaN raise: the old opt state was donated; the
        # post-step buffers are the only live ones
        self._opt_state = new_opt
        self._dirty = True
        if self._check_nan:
            self._last_flags = flags
            if not self._guard_updates:
                from ...core.sanitizer import raise_if_nonfinite

                raise_if_nonfinite(self._nan_names, flags)
        self._optimizer._global_step += 1
        self._record_step_metrics(
            t_enter, 1, int(np.prod(raw_in[0].shape)) if raw_in else 0, loss,
            compiled=self._jitted.tracker.compiles > compiles_before)
        return Tensor(loss)

    def run_steps(self, inputs, labels, step_scheduler=True):
        """Run a whole window of steps as ONE compiled program.

        ``inputs``/``labels``: tuples of arrays with a leading [n_steps]
        axis (stacked per-step batches). A ``lax.scan`` carries
        params/buffers/opt-state across the window, so per-step dispatch
        latency and host→device feeds disappear — the on-device equivalent
        of the reference Executor running a multi-step program. Returns the
        per-step losses [n_steps].

        A per-iteration ``LRScheduler`` is sampled on the host for each
        window step (the engine advances it ``n_steps-1`` times unless
        ``step_scheduler=False``, matching a per-step loop where the user
        steps it between iterations) and the [n_steps] lr array is scanned
        through — window steps see exactly the lrs the per-step path would.

        Measured on the single-chip v5e rig this is ~5% SLOWER than the
        per-step loop for GPT-2 345M (the scan body compiles worse than the
        flat step, costing more than the ~4 ms/step dispatch it saves) —
        its value is on high-dispatch-latency/multi-host rigs and for
        host-free inner loops.

        Composes with ``offload=True`` (ZeRO pinned-host optimizer state):
        the state streams into HBM ONCE before the window, the scan carries
        it on-device, and it evacuates ONCE after — the same peak-HBM
        profile as the per-step path (which also holds the full state
        device-side during each step) with the host↔device transfers
        amortized over the window; this is precisely the long-training
        shape the reference's sharding optimizer runs
        (sharding_optimizer.py:168-183 gradient-merge modes).
        """
        _watchdog_heartbeat()
        # one capture boundary per WINDOW; attribution divides by the
        # registered steps-per-call so per-step numbers stay per-step
        _device_profile.step_boundary("fleet.train_step_multi")
        t_enter = time.perf_counter()

        # the whole window — h2d, scan compile, LR sampling, dispatch —
        # lives under one step span (and one productive_step goodput
        # claim; the scan compile inside claims its own category); the
        # helper split keeps the long body at its original indentation
        with _goodput.activity("productive_step"), \
                _spans.span("step", cat="step",
                            step=self._optimizer._global_step):
            return self._run_steps_in_span(inputs, labels, step_scheduler,
                                           t_enter)

    def _run_steps_in_span(self, inputs, labels, step_scheduler, t_enter):
        with _spans.span("h2d", cat="h2d"):
            # leading [n_steps] axis is unsharded; ONE pytree transfer
            # for the whole stacked window (single dispatch instead of
            # one per array)
            spec = self._batch_sharding.spec
            win_full = NamedSharding(
                self._mesh, P(*((None,) + tuple(spec))))
            win_sharding = win_full
            window = (_raw_tuple(inputs), _raw_tuple(labels))
            if self._sp_axis is not None:
                # per-leaf, mirroring _batch_shardings: only stacked
                # leaves whose dim 2 can carry sequence shards take the
                # (None, dp, sp) spec — 1-D label leaves, broadcast-dim
                # masks, and ragged dims stay (None, dp)
                dp_only = NamedSharding(self._mesh, P(None, spec[0]))
                sp = self._mesh.shape[self._sp_axis]

                def win_leaf_sharding(a):
                    shape = getattr(a, "shape", ())
                    if (len(shape) >= 3 and shape[2] >= sp
                            and shape[2] % sp == 0):
                        return win_full
                    return dp_only

                win_sharding = jax.tree_util.tree_map(
                    win_leaf_sharding, window)
            raw_in, raw_lab = jax.device_put(window, win_sharding)
        n_steps = raw_in[0].shape[0]

        if self._step_fn is None:  # remat='auto' not yet resolved
            self._resolve_remat(
                self._optimizer.lr_device_scalar(),
                jax.tree_util.tree_map(lambda a: a[0], (raw_in, raw_lab)))
        if self._jitted_multi is None:
            step_fn = self._step_fn
            repl = self._repl
            with_fp = self._with_fingerprint

            def multi_core(params, buffers, opt_state, lrs, batches):
                def body(carry, step_in):
                    lr, batch = step_in[0], (step_in[1], step_in[2])
                    params, buffers, opt_state = carry
                    params, buffers, opt_state, loss, flags = step_fn(
                        params, buffers, opt_state, lr, batch)
                    return (params, buffers, opt_state), (loss, flags)

                (params, buffers, opt_state), (losses, flags) = jax.lax.scan(
                    body, (params, buffers, opt_state),
                    (lrs, batches[0], batches[1]))
                return params, buffers, opt_state, losses, flags

            if self._fp_every:
                # windows fingerprint the WINDOW-FINAL carry (one cond
                # after the scan, not one per scanned step) when any
                # step inside the window crossed the interval boundary
                def multi_fn(params, buffers, opt_state, lrs, batches,
                             fp_due):
                    params, buffers, opt_state, losses, flags = multi_core(
                        params, buffers, opt_state, lrs, batches)
                    fp = with_fp(params, buffers, opt_state, fp_due)
                    return params, buffers, opt_state, losses, flags, fp
            else:
                multi_fn = multi_core

            self._jitted_multi = tracked_jit(
                multi_fn,
                name="fleet.train_step_multi",
                sig_argnums=(3, 4),  # lrs + stacked batches
                donate_argnums=(0, 2) if self._donate else (),
                out_shardings=self._out_shardings,
            )
        # attribution: the windowed executable runs n_steps train steps
        # per invocation while engine/step_ms records per-step time —
        # MFU must divide the program's flops by the window length
        _xla_cost.set_steps_per_call("fleet.train_step_multi", int(n_steps))

        # per-step LR: a per-iteration scheduler is sampled host-side for
        # every window step, so the scanned steps see exactly the lr
        # sequence the per-step __call__ path would
        from ...optimizer.lr import LRScheduler

        sched = self._optimizer._learning_rate
        if isinstance(sched, LRScheduler) and step_scheduler:
            lr_list = [float(sched())]
            for _ in range(int(n_steps) - 1):
                sched.step()
                lr_list.append(float(sched()))
        else:
            lr_list = [float(self._optimizer.get_lr())] * int(n_steps)
        lrs = jnp.asarray(lr_list, jnp.float32)
        compiles_before = self._jitted_multi.tracker.compiles
        opt_state = self._opt_state
        if self._offload:
            # stream host-resident optimizer state into HBM once per window
            opt_state = jax.tree_util.tree_map(
                lambda s, sh: jax.device_put(s, sh)
                if hasattr(s, "shape") else s,
                opt_state, self._opt_shardings)
        gs = self._optimizer._global_step
        fp_due = bool(self._fp_every) and any(
            (gs + k) % self._fp_every == 0 for k in range(int(n_steps)))
        with _spans.span("compute", cat="compute"):
            if self._fp_every:
                (self._params, self._buffers, new_opt, losses, flags,
                 fp) = self._jitted_multi(
                    self._params, self._buffers, opt_state, lrs,
                    (raw_in, raw_lab), jnp.asarray(fp_due))
            else:
                self._params, self._buffers, new_opt, losses, flags = \
                    self._jitted_multi(self._params, self._buffers,
                                       opt_state, lrs, (raw_in, raw_lab))
        if self._fp_every and fp_due:
            from paddle_tpu.resilience.integrity import publish_fingerprint

            publish_fingerprint(self._fp_history,
                                gs + int(n_steps) - 1, fp, self._fp_every)
        if self._offload:
            # evacuate once per window, freeing HBM between windows
            new_opt = jax.tree_util.tree_map(
                lambda s, sh: jax.device_put(s, sh)
                if hasattr(s, "shape") else s,
                new_opt, self._opt_host_shardings)
        self._opt_state = new_opt
        if self._check_nan:
            # scan stacked the per-step flag vectors: [n_steps, k] -> all
            # steps must be finite
            window_flags = flags.all(axis=0)
            self._last_flags = window_flags
            if not self._guard_updates:
                from ...core.sanitizer import raise_if_nonfinite

                raise_if_nonfinite(self._nan_names, window_flags)
        self._optimizer._global_step += int(n_steps)
        self._dirty = True
        self._record_step_metrics(
            t_enter, int(n_steps),
            int(np.prod(raw_in[0].shape)) if raw_in else 0,
            losses[-1] if int(n_steps) else None,
            compiled=self._jitted_multi.tracker.compiles > compiles_before)
        return Tensor(losses)

    # -- resilience (StepGuard engine contract) ----------------------------
    def last_step_finite(self):
        """(ok, bad_leaf_names) of the most recent step's finite sweep."""
        from paddle_tpu.resilience.guard import finite_report

        return finite_report(self._nan_names, self._last_flags)

    @property
    def fingerprint_every(self) -> int:
        """The in-jit fingerprint interval (0 = off)."""
        return self._fp_every

    def last_fingerprint(self):
        """The newest in-jit state fingerprint as ``(step, {"sum",
        "abs_sum", "xor"})`` with host-fetched scalars, or None before
        the first one (see jit.TrainStep.last_fingerprint)."""
        if not self._fp_history:
            return None
        step, fp = self._fp_history[-1]
        return step, {k: np.asarray(v) for k, v in fp.items()}

    def fingerprint_history(self):
        """Bounded per-rank history of (step, fingerprint) pairs, oldest
        first (device scalars — fetch lazily)."""
        return list(self._fp_history)

    def snapshot_state(self):
        """Deep sharding-preserving copy of the on-device train state —
        ``resilience.guard.copy_tree`` (see it for the donation-safety
        rationale)."""
        from paddle_tpu.resilience.guard import copy_tree

        return {"params": copy_tree(self._params),
                "buffers": copy_tree(self._buffers),
                "opt_state": copy_tree(self._opt_state)}

    def restore_state(self, snap):
        """Install a snapshot (in-memory or restored from an orbax
        checkpoint): every leaf is re-laid-out onto this engine's
        shardings via fresh buffers, so the snapshot itself survives
        repeated restores across future donations."""
        self._params = {
            n: jax.device_put(jnp.copy(v) if isinstance(v, jax.Array) else v,
                              self._param_shardings[n])
            for n, v in snap["params"].items()
        }
        self._buffers = {
            n: jax.device_put(jnp.copy(v) if isinstance(v, jax.Array) else v,
                              self._repl)
            for n, v in snap["buffers"].items()
        }
        opt_home = self._opt_host_shardings if self._offload \
            else self._opt_shardings
        self._opt_state = {
            n: {k: jax.device_put(jnp.copy(s) if isinstance(s, jax.Array)
                                  else s, opt_home[n][k])
                for k, s in st.items()}
            for n, st in snap["opt_state"].items()
        }
        self._dirty = True

    def sync_to_layer(self):
        # checkpoint/eval work follows: the next inter-call interval
        # would measure that pause, not a device step — drop the anchor
        self._last_step_t = None
        if self._dirty:
            host_params = self._params
            if self._master:
                # checkpoints carry the f32 masters, not the bf16 residents
                # (reference multi_precision state_dict contract)
                host_params = {
                    n: self._opt_state[n]["master"]
                    if "master" in self._opt_state.get(n, {}) else v
                    for n, v in self._params.items()
                }
            set_params(self._layer, host_params)
            set_buffers(self._layer, self._buffers)
            for name, p in self._named_params.items():
                self._optimizer._accumulators[id(p)] = self._opt_state[name]
            self._dirty = False

    @property
    def param_specs(self):
        return dict(self._param_specs)
