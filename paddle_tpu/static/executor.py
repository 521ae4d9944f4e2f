"""Executor — parity with python/paddle/fluid/executor.py:475 over the C++
executors (framework/executor.cc:292, parallel_executor.cc:827).

``run`` compiles the Program's SSA trace into ONE jitted XLA step (forward,
and when an optimizer was attached by ``minimize``, backward + update too),
cached by (program, feed signature). Parameters and optimizer state live
on-device between runs.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import InvalidArgumentError, enforce
from ..core.tensor import Parameter, Tensor
from ..profiler import device_profile as _device_profile
from ..profiler import goodput as _goodput
from ..profiler import spans as _spans
from ..profiler import xla_cost as _xla_cost
from ..profiler.retrace import tracked_jit
from ..profiler.telemetry import get_telemetry
from ..resilience.watchdog import heartbeat as _watchdog_heartbeat
from ..utils import profiler as _host_profiler
from .program import Program, default_main_program

__all__ = ["Executor", "global_scope", "scope_guard"]


class _ScopeTensor:
    """Minimal LoDTensor facade held by a scope variable."""

    def __init__(self):
        self._array = None

    def set(self, array, place=None):
        self._array = np.asarray(array)

    def shape(self):
        return [] if self._array is None else list(self._array.shape)

    def __array__(self, dtype=None):
        a = self._array if self._array is not None else np.zeros(0)
        return a.astype(dtype) if dtype else a


class _ScopeVar:
    def __init__(self, name):
        self.name = name
        self._tensor = _ScopeTensor()

    def get_tensor(self):
        return self._tensor


class _Scope:
    """Name → variable store (reference framework::Scope, minimal eager
    form: ``var`` creates-or-gets a variable holding a host tensor)."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        if name not in self._vars:
            self._vars[name] = _ScopeVar(name)
        return self._vars[name]

    def find_var(self, name):
        return self._vars.get(name)


_global_scope = _Scope()


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield scope

    return guard()


class Executor:
    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[tuple, Any] = {}
        self._opt_states: Dict[int, dict] = {}
        self._last_run_t = None  # inter-run interval ⇒ async step time
        self._last_multi_t = None  # run_steps window interval anchor

    def close(self):
        self._cache.clear()

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name="feed",
            fetch_var_name="fetch", scope=None, return_numpy=True,
            use_program_cache=True):
        _watchdog_heartbeat()  # run boundary feeds the hang watchdog
        # windowed device-profile capture boundary (no-op unless armed)
        _device_profile.step_boundary("executor.train_step")
        # goodput: the run — feed H2D, dispatch AND the blocking numpy
        # fetch — is productive_step wall time; a fresh compile inside
        # claims its own category (nested). Helper split keeps the long
        # body at its original indentation.
        with _goodput.activity("productive_step"):
            return self._run_in_claim(program, feed, fetch_list, scope,
                                      return_numpy)

    def _run_in_claim(self, program, feed, fetch_list, scope, return_numpy):
        t_enter = time.perf_counter()
        tel = get_telemetry()
        program = program if isinstance(program, Program) else (
            getattr(program, "_program", None) or default_main_program()
        )
        feed = feed or {}
        fetch_list = fetch_list or []
        feed_raw = {}
        host = {}
        for name, v in feed.items():
            if isinstance(v, Tensor):
                feed_raw[name] = v._value
            elif isinstance(v, jax.Array):
                # already on device (e.g. train_from_dataset's async
                # prefetch) — never round-trip through host numpy
                feed_raw[name] = v
            else:
                host[name] = np.asarray(v)
        if host:
            # ONE async pytree transfer for all host-resident feed vars —
            # a per-var jnp.asarray in the loop dispatches one H2D per
            # leaf (tpu-lint R4, the regression class PR 2 eliminated)
            with _spans.span("h2d", cat="h2d"):
                feed_raw.update(jax.device_put(host))
        fetch_ids = []
        for f in fetch_list:
            if isinstance(f, Tensor):
                fetch_ids.append(id(f))
            elif isinstance(f, str):
                fetch_ids.append(id(program.vars_by_name[f]))
            else:
                raise InvalidArgumentError(f"cannot fetch {f!r}")
        t_fed = time.perf_counter()

        key = (
            id(program), tuple(sorted((n, tuple(v.shape), str(v.dtype))
                                      for n, v in feed_raw.items())),
            tuple(fetch_ids), len(program.ops),
        )
        fresh_compile = key not in self._cache
        if fresh_compile:
            tel.counter("executor/compiles")
            self._cache[key] = self._compile(program, fetch_ids)
            # the interval spanning this build (+ the XLA compile inside
            # the first runner call) is not a step — drop the anchor
            self._last_run_t = None
        runner = self._cache[key]
        with _spans.span("compute", cat="compute"):
            outs = runner(feed_raw)
        t_run = time.perf_counter()
        if tel.enabled:
            tel.counter("executor/runs")
            tel.observe("executor/feed_ms", (t_fed - t_enter) * 1e3)
            # a run() between run_steps windows invalidates the window
            # anchor (and vice versa below): an interval spanning the
            # OTHER path's work is not a step/window time and would
            # pollute the shared executor/step_ms histogram — the MFU
            # denominator — by the window-length factor
            self._last_multi_t = None
            if not fresh_compile:
                # run_ms is HOST time in the runner (dispatch + param
                # commit; near-zero on the async path) — a compiling
                # call's runner time is XLA compile, tracked separately
                # in compile_ms/executor.*. True steady-state step time
                # on the async train loop is the inter-run interval
                # (executor/step_ms), same rationale as engine/step_ms;
                # the shared pause filter lives in observe_interval.
                tel.observe("executor/run_ms", (t_run - t_fed) * 1e3)
                last = self._last_run_t
                if last is not None and t_run > last:
                    tel.observe_interval("executor/step_ms",
                                         (t_run - last) * 1e3)
            self._last_run_t = t_run
            _host_profiler.add_counter_snapshot("executor.run")
        if return_numpy:
            with _spans.span("d2h", cat="d2h"):
                res = [np.asarray(o) for o in outs]
            if tel.enabled:
                # fetch = materializing device results on the host; this
                # blocks on the program, so it also covers device time
                tel.observe("executor/fetch_ms",
                            (time.perf_counter() - t_run) * 1e3)
            return res
        return [Tensor(o) for o in outs]

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           prefetch_depth=2, prefetch_buckets=None):
        """Dataset-driven training (reference call stack §3.4:
        Executor.train_from_dataset → trainer/DeviceWorker loop,
        fluid/executor.py:1433). Iterates the dataset's parsed batches,
        builds a feed per batch from the program's feed vars ↔ slot names,
        and replays the compiled step for each. Returns the last fetch
        values (if any).

        ``prefetch_depth`` > 0 runs the parse/pad/H2D stage in a
        ``DevicePrefetcher`` background pipeline that many batches ahead
        (the reference DeviceWorker overlap, trainer.h:97), issuing ONE
        async pytree ``jax.device_put`` per feed so the transfer overlaps
        the in-flight step; ``prefetch_buckets`` (``io.ShapeBuckets`` or a
        sequence of ints) additionally pads ragged feeds into fixed shape
        buckets so the jitted step compiles once per bucket."""
        if dataset is None:
            raise InvalidArgumentError("dataset is required")
        program = program if isinstance(program, Program) else (
            getattr(program, "_program", None) or default_main_program()
        )
        fetch_list = fetch_list or []
        if thread and int(thread) > 1:
            # N parse threads already stage feeds ahead, so prefetch_depth
            # has no meaning there — but bucketing still must apply or
            # ragged feeds retrace per shape
            return self._train_multithread(program, dataset, int(thread),
                                           fetch_list, debug, print_period,
                                           prefetch_buckets=prefetch_buckets)

        from ..io.prefetch import DevicePrefetcher

        use_prefetch = bool(prefetch_depth) and int(prefetch_depth) > 0
        # with the prefetcher on, it owns the (single-pytree) device_put;
        # off, build_feed still folds the feed into ONE pytree transfer
        build_feed = self._dataset_feed_builder(program,
                                                to_device=not use_prefetch)
        src = map(build_feed, iter(dataset))
        if use_prefetch:
            src = DevicePrefetcher(src, depth=int(prefetch_depth),
                                   buckets=prefetch_buckets)
        last = None
        step = 0
        try:
            # async: keep fetches as device Tensors; materialize only when
            # printing or at the end — the loop never blocks on the device
            for feed in src:
                last = self.run(program, feed=feed, fetch_list=fetch_list,
                                return_numpy=False)
                step += 1
                self._maybe_print_fetches(step, last, fetch_list, debug,
                                          print_period)
        finally:
            if use_prefetch:
                src.close()
        if last is not None:
            last = [np.asarray(v.numpy()) for v in last]
        return last

    @staticmethod
    def _maybe_print_fetches(step, fetches, fetch_list, debug, print_period):
        """Shared step logging for the single- and multi-thread dataset
        loops (they must never drift)."""
        if debug or (fetch_list and step % print_period == 0):
            vals = ", ".join(f"{float(np.asarray(v.numpy()).ravel()[0]):.6f}"
                             for v in fetches)
            print(f"[train_from_dataset] step {step}: {vals}")

    def _dataset_feed_builder(self, program, to_device=True):
        """One shared feed builder for the single- and multi-thread dataset
        loops (they must never drift). ``to_device=True`` ends with ONE
        async pytree ``jax.device_put`` over the whole feed — a single
        dispatch instead of one per feed var, and the transfer overlaps
        the in-flight step (the reference DeviceWorker parse/H2D/compute
        overlap, trainer.h:97). ``to_device=False`` returns host numpy —
        the DevicePrefetcher pipeline owns the transfer there."""
        feed_names = list(program.feed_vars)

        tel = get_telemetry()

        def build_feed(batch):
            feed = {}
            n_bytes = 0
            for name in feed_names:
                if name in batch:
                    # a genuine dataset slot always wins — including one
                    # that happens to be named '<x>_length'
                    arr = self._slot_to_array(
                        batch[name], program.feed_vars[name],
                        program.declared_shapes.get(name))
                elif name.endswith("_length") and name[:-7] in batch:
                    # synthesized lengths: padded form alone loses the row
                    # lengths, so a feed var '<slot>_length' (with no slot
                    # of its own) receives the base slot's true lengths —
                    # clamped to the padded time dim so mask-aware programs
                    # never index past truncated rows
                    arr = self._row_lengths(batch[name[:-7]], program,
                                            name[:-7])
                else:
                    raise InvalidArgumentError(
                        f"dataset batch has no slot '{name}' for feed var "
                        f"(slots: {sorted(batch)})")
                n_bytes += getattr(arr, "nbytes", 0)
                feed[name] = arr
            if to_device:
                feed = jax.device_put(feed)  # one pytree dispatch, async
            if tel.enabled:
                tel.counter("reader/batches")
                tel.counter("reader/bytes", n_bytes)
            return feed

        return build_feed

    def _train_multithread(self, program, dataset, n_threads, fetch_list,
                           debug=False, print_period=100,
                           prefetch_buckets=None):
        """thread>1: the reference's MultiTrainer/DeviceWorker path
        (framework/trainer.h:52). N DatasetWorker threads parse + stage
        feeds concurrently; device dispatch serializes through one lock
        (one chip, and the runner's param commit is not thread-safe)."""
        import threading

        from ..framework.trainer import (DatasetWorker, MultiTrainer,
                                         shared_iterator)

        if prefetch_buckets is None:
            build_feed = self._dataset_feed_builder(program)
        else:
            from ..io.prefetch import ShapeBuckets

            buckets = (prefetch_buckets
                       if isinstance(prefetch_buckets, ShapeBuckets)
                       else ShapeBuckets(prefetch_buckets))
            host_feed = self._dataset_feed_builder(program, to_device=False)
            tel = get_telemetry()

            def build_feed(batch):
                feed, hits, misses = buckets.pad_tree(host_feed(batch))
                if tel.enabled:
                    if hits:
                        tel.counter("prefetch/bucket_hits", hits)
                    if misses:
                        tel.counter("prefetch/bucket_misses", misses)
                return jax.device_put(feed)  # one pytree dispatch
        step_count = [0]  # guarded by the dispatch lock

        def run_step(feed):
            out = self.run(program, feed=feed, fetch_list=fetch_list,
                           return_numpy=False)
            step_count[0] += 1
            self._maybe_print_fetches(step_count[0], out, fetch_list, debug,
                                      print_period)
            return out

        lock = threading.Lock()
        nb = shared_iterator(dataset)
        workers = [DatasetWorker(nb, build_feed, run_step, lock)
                   for _ in range(n_threads)]
        trainer = MultiTrainer(workers).run()
        last = next((w.last_fetch for w in reversed(trainer.workers)
                     if w.last_fetch is not None), None)
        if last is not None:
            last = [np.asarray(v.numpy()) for v in last]
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           prefetch_depth=2, prefetch_buckets=None):
        """Inference twin of train_from_dataset (fluid/executor.py:1385):
        runs a for_test clone so no optimizer update is applied. The clone
        is cached per source program — cloning per call would recompile and
        leak a cache entry every time."""
        program = program if isinstance(program, Program) else (
            getattr(program, "_program", None) or default_main_program()
        )
        if not hasattr(self, "_infer_clones"):
            self._infer_clones = {}
        # one entry per live program (strong ref prevents id aliasing),
        # replaced when the program mutated (op count changed) — keying on
        # the op count itself would pin every historical clone forever
        entry = self._infer_clones.get(id(program))
        if (entry is None or entry[0] is not program
                or entry[1] != len(program.ops)):
            entry = (program, len(program.ops), program.clone(for_test=True))
            self._infer_clones[id(program)] = entry
        return self.train_from_dataset(entry[2], dataset,
                                       scope, thread, debug, fetch_list,
                                       fetch_info, print_period,
                                       prefetch_depth, prefetch_buckets)

    @staticmethod
    def _row_lengths(slot, program, base_name):
        """True per-row lengths of a slot, clamped to the base feed var's
        padded time dim when that var is fed (truncated rows must not report
        lengths past the data)."""
        from ..io.data_feed import RaggedSlot

        if isinstance(slot, RaggedSlot):
            lens = slot.lengths().astype(np.int64)
        else:
            rows = (slot if isinstance(slot, np.ndarray)
                    else [np.asarray(r) for r in slot])
            lens = np.asarray([len(r) for r in rows], np.int64)
        base = program.feed_vars.get(base_name)
        if base is not None:
            t = Executor._pad_target(base, program.declared_shapes.get(base_name),
                                     int(lens.max()) if len(lens) else 0)
            lens = np.minimum(lens, t)
        return lens

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power-of-two ≥ n (min 16)."""
        b = 16
        while b < n:
            b *= 2
        return b

    @staticmethod
    def _pad_target(feed_var, declared, batch_max: int) -> int:
        """Time dim to pad to: the feed var's declared dim; for a dynamic
        (None/-1) dim, the batch max BUCKETED to a power of two — tracking
        each batch's exact max would give almost every batch a fresh feed
        shape and thus a fresh XLA compile."""
        shape = declared if declared is not None else list(feed_var.shape)
        if len(shape) > 1:
            d = shape[1]
            if d is not None and (not isinstance(d, int) or d > 0):
                return int(d)
        return Executor._bucket(batch_max)

    @staticmethod
    def _slot_to_array(slot, feed_var, declared=None):
        """Dense slot rows stack; ragged slots pad to the feed var's declared
        time dim (LoD → padded+mask ragged form, SURVEY §7 map). Returns
        numpy — run() moves it to device once."""
        from ..io.data_feed import RaggedSlot

        if isinstance(slot, RaggedSlot):
            t = Executor._pad_target(feed_var, declared,
                                     int(slot.lengths().max()))
            padded, _ = slot.to_padded(t)
            return padded
        if isinstance(slot, np.ndarray):
            return slot
        rows = [np.asarray(r) for r in slot]
        if rows and any(r.shape != rows[0].shape for r in rows):
            # ragged list-of-rows (InMemoryDataset form): pad
            t = Executor._pad_target(feed_var, declared,
                                     max(len(r) for r in rows))
            out = np.zeros((len(rows), t), rows[0].dtype)
            for i, r in enumerate(rows):
                out[i, : min(len(r), t)] = r[:t]
            return out
        return np.stack(rows)

    # ------------------------------------------------------------------
    def _compile(self, program: Program, fetch_ids: List[int]):
        replay = program.build_replay()
        param_items = list(program.parameters.items())

        if program._optimize is None:
            @tracked_jit(name="executor.forward", sig_argnums=(0,))
            def fwd(feed_raw, params_raw):
                env = replay(feed_raw, params_raw)
                return [env[i] for i in fetch_ids]

            def runner(feed_raw):
                params_raw = {uid: p._value for uid, p in param_items}
                return fwd(feed_raw, params_raw)

            self._last_jitted = fwd  # profiling/introspection handle
            return runner

        step, opt, check_nan, nan_names = self._make_step(
            program, fetch_ids, replay, param_items)
        jitted = tracked_jit(step, name="executor.train_step",
                             sig_argnums=(0, 3), donate_argnums=(1, 2))

        def runner(feed_raw):
            params_raw = {uid: p._value for uid, p in param_items}
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            outs, new_params, new_state, flags = jitted(
                feed_raw, params_raw, self._opt_states[id(program)], lr
            )
            # commit BEFORE any NaN raise: the jit donated the old
            # param/opt-state buffers, so the post-step values (valid, just
            # possibly non-finite) are the only live ones — leaving the
            # Parameters pointing at deleted arrays would break post-mortem
            # inspection and retries
            for uid, p in param_items:
                p._value = new_params[uid]
            self._opt_states[id(program)] = new_state
            if check_nan:
                from ..core.sanitizer import raise_if_nonfinite

                raise_if_nonfinite(nan_names, flags)
            opt._global_step += 1
            return outs

        self._last_jitted = jitted  # profiling/introspection handle
        return runner

    def _make_step(self, program: Program, fetch_ids, replay, param_items):
        """The one-train-step function shared by ``run`` (jitted directly)
        and ``run_steps`` (scanned over a window): replay forward, grad,
        clip, optimizer update, optional finite sweep."""
        from ..core.sanitizer import finite_flags, jit_check_enabled

        optimizer, loss_t = program._optimize
        loss_id = id(loss_t)
        opt = optimizer
        param_uids = [uid for uid, _ in param_items]
        check_nan = jit_check_enabled()  # snapshot at compile time
        nan_names: list = []
        if id(program) not in self._opt_states:
            self._opt_states[id(program)] = {
                uid: opt._init_state_for(p._value) for uid, p in param_items
            }
        trainable = {uid: p.trainable for uid, p in param_items}
        named = dict(param_items)

        def step(feed_raw, params_raw, opt_state, lr):
            def loss_of(pvals):
                merged = dict(params_raw)
                merged.update(pvals)
                env = replay(feed_raw, merged)
                return env[loss_id], env

            train_p = {u: v for u, v in params_raw.items() if trainable[u]}
            (loss, env), grads = jax.value_and_grad(loss_of, has_aux=True)(train_p)
            # bind computed grads to their append_backward/gradients() grad
            # vars so fetch_list can name them (static.gradients contract)
            for _uid, _g in grads.items():
                _gt = getattr(program, "_grad_map", {}).get(_uid)
                if _gt is not None:
                    env[id(_gt)] = _g
            if opt._grad_clip is not None:
                from ..nn.clip import ClipGradByGlobalNorm, clip_grads_global_norm_raw

                if isinstance(opt._grad_clip, ClipGradByGlobalNorm):
                    grads = clip_grads_global_norm_raw(grads, opt._grad_clip.clip_norm)
            new_params = dict(params_raw)
            new_state = {}
            for uid, g in grads.items():
                p = params_raw[uid]
                st = opt_state[uid]
                # multi_precision: all update math runs on the f32 master
                # (same shape as apply_optimizer_update / jit.TrainStep)
                master = st.get("master") if isinstance(st, dict) else None
                if master is not None:
                    p_eff, st = master, {k: v for k, v in st.items()
                                         if k != "master"}
                else:
                    p_eff = p
                g = g.astype(p_eff.dtype)
                wd = opt._decay_coeff(named[uid])
                if wd and type(opt).__name__ != "AdamW":
                    g = g + wd * p_eff
                if type(opt).__name__ == "AdamW" and getattr(opt, "_coeff", 0.0):
                    p_eff = p_eff * (1.0 - lr * opt._coeff)
                np_, ns = opt._update(p_eff, g, st, lr)
                if master is not None:
                    ns["master"] = np_
                    np_ = np_.astype(p.dtype)
                new_params[uid] = np_
                new_state[uid] = ns
            for uid in param_uids:
                if uid not in new_state:
                    new_state[uid] = opt_state[uid]
            # persistent-var updates recorded by ops like data_norm: the
            # post-step summary values replace the (non-trainable) params
            # so they persist across runs exactly like optimizer updates
            for uid, src_id in getattr(program, "buffer_updates",
                                       {}).items():
                if uid in new_params and src_id in env:
                    new_params[uid] = env[src_id].astype(
                        params_raw[uid].dtype)
            if check_nan:
                # uid keys -> variable names so the error locates the tensor
                pname = lambda uid: getattr(named[uid], "name", None) or str(uid)
                flags = finite_flags(
                    nan_names, loss=loss,
                    grad={pname(u): g for u, g in grads.items()},
                    param={pname(u): v for u, v in new_params.items()})
            else:
                flags = None
            return [env[i] for i in fetch_ids], new_params, new_state, flags

        return step, opt, check_nan, nan_names

    def run_steps(self, program=None, feed=None, fetch_list=None,
                  n_steps=None, return_numpy=True, step_scheduler=True):
        """Run a WINDOW of training steps as one compiled program.

        The static-graph counterpart of the fleet engine's ``run_steps``: a
        ``lax.scan`` carries params/optimizer state across ``n_steps``
        iterations, so the per-dispatch host→device latency is paid once
        per window instead of once per step.

        Feed arrays may be either per-step shaped (same batch replayed
        every step — benchmark/steady-state shape) or carry a leading
        [n_steps] axis (stacked per-step batches, detected by rank =
        declared rank + 1). A per-iteration LRScheduler is sampled
        host-side for each window step: the executor advances it
        ``n_steps - 1`` times, matching a per-step loop where the caller
        steps it BETWEEN iterations — so step the scheduler once between
        windows, or pass ``step_scheduler=False`` to manage it entirely
        yourself (same contract as the fleet engine's ``run_steps``).
        Returns the fetches stacked along a leading [n_steps] axis.

        Reference anchor: Executor.run_from_dataset's device-side
        multi-batch loop (fluid/executor.py:1433) — same idea, realized as
        one XLA program instead of a C++ trainer thread.
        """
        program = program if isinstance(program, Program) else (
            getattr(program, "_program", None) or default_main_program()
        )
        if program._optimize is None:
            raise InvalidArgumentError(
                "run_steps requires a program with an optimizer "
                "(opt.minimize(loss) recorded)")
        _watchdog_heartbeat()
        # one capture boundary per window (steps-per-call registered
        # below divides the attribution back to per-step)
        _device_profile.step_boundary("executor.run_steps")
        # goodput: the whole window call is productive_step wall time
        # (the scan compile inside claims its own category); helper
        # split keeps the body at its original indentation
        with _goodput.activity("productive_step"):
            return self._run_steps_in_claim(program, feed, fetch_list,
                                            n_steps, return_numpy,
                                            step_scheduler)

    def _run_steps_in_claim(self, program, feed, fetch_list, n_steps,
                            return_numpy, step_scheduler):
        feed = feed or {}
        if n_steps is None:
            raise InvalidArgumentError("n_steps is required")
        n_steps = int(n_steps)
        feed_raw, windowed, host = {}, {}, {}
        for name, v in feed.items():
            if isinstance(v, Tensor):
                arr = v._value
            elif isinstance(v, jax.Array):
                arr = v
            else:
                arr = np.asarray(v)  # staged host-side; one put below
                host[name] = arr
            declared = program.vars_by_name[name]
            windowed[name] = arr.ndim == len(declared.shape) + 1
            feed_raw[name] = arr
        if host:
            # ONE async pytree transfer instead of one H2D dispatch per
            # feed var (tpu-lint R4)
            with _spans.span("h2d", cat="h2d"):
                feed_raw.update(jax.device_put(host))
        fetch_ids = []
        for f in (fetch_list or []):
            if isinstance(f, Tensor):
                fetch_ids.append(id(f))
            elif isinstance(f, str):
                fetch_ids.append(id(program.vars_by_name[f]))
            else:
                raise InvalidArgumentError(f"cannot fetch {f!r}")
        key = (
            "multi", id(program), n_steps,
            tuple(sorted((n, tuple(v.shape), str(v.dtype), windowed[n])
                         for n, v in feed_raw.items())),
            tuple(fetch_ids), len(program.ops),
        )
        fresh_compile = key not in self._cache
        if fresh_compile:
            self._cache[key] = self._compile_multi(
                program, fetch_ids, n_steps, windowed)
            self._last_multi_t = None  # compile interval is not a window
        # attribution: the windowed executable runs n_steps train steps
        # per invocation; executor/step_ms below records PER-STEP time,
        # so MFU divides the program's flops by the window length
        _xla_cost.set_steps_per_call("executor.run_steps", n_steps)
        with _spans.span("compute", cat="compute"):
            outs = self._cache[key](feed_raw, step_scheduler)
        tel = get_telemetry()
        if tel.enabled:
            # steady-state per-step time from the inter-window interval
            # (dispatch is async; same rationale + shared pause filter as
            # executor/step_ms on the per-run path, which this histogram
            # deliberately shares — a window of N steps contributes its
            # interval / N)
            now = time.perf_counter()
            last = self._last_multi_t
            if last is not None and now > last and not fresh_compile \
                    and n_steps:
                tel.observe_interval("executor/step_ms",
                                     (now - last) * 1e3 / n_steps)
            self._last_multi_t = now
            self._last_run_t = None  # see run(): cross-path invalidation
        if return_numpy:
            with _spans.span("d2h", cat="d2h"):
                return [np.asarray(o) for o in outs]
        return [Tensor(o) for o in outs]

    def _compile_multi(self, program: Program, fetch_ids, n_steps, windowed):
        replay = program.build_replay()
        param_items = list(program.parameters.items())
        step, opt, check_nan, nan_names = self._make_step(
            program, fetch_ids, replay, param_items)

        def multi(feed_const, feed_win, params_raw, opt_state, lrs):
            def body(carry, xs):
                params_raw, opt_state = carry
                lr, win = xs
                merged = dict(feed_const)
                merged.update(win)
                outs, new_params, new_state, flags = step(
                    merged, params_raw, opt_state, lr)
                return (new_params, new_state), (outs, flags)

            (params_raw, opt_state), (outs, flags) = jax.lax.scan(
                body, (params_raw, opt_state), (lrs, feed_win))
            if flags is not None:
                flags = jnp.all(flags, axis=0)  # any step non-finite
            return outs, params_raw, opt_state, flags

        jitted = tracked_jit(multi, name="executor.run_steps",
                             sig_argnums=(0, 1, 4), donate_argnums=(2, 3))

        def runner(feed_raw, step_scheduler=True):
            from ..optimizer.lr import LRScheduler

            feed_const = {n: v for n, v in feed_raw.items()
                          if not windowed[n]}
            feed_win = {n: v for n, v in feed_raw.items() if windowed[n]}
            sched = opt._learning_rate
            if isinstance(sched, LRScheduler) and step_scheduler:
                lr_list = [float(sched())]
                for _ in range(n_steps - 1):
                    sched.step()
                    lr_list.append(float(sched()))
                lrs = jnp.asarray(lr_list, jnp.float32)
            else:
                lrs = jnp.full((n_steps,), float(opt.get_lr()), jnp.float32)
            params_raw = {uid: p._value for uid, p in param_items}
            outs, new_params, new_state, flags = jitted(
                feed_const, feed_win, params_raw,
                self._opt_states[id(program)], lrs)
            for uid, p in param_items:
                p._value = new_params[uid]
            self._opt_states[id(program)] = new_state
            if check_nan:
                from ..core.sanitizer import raise_if_nonfinite

                raise_if_nonfinite(nan_names, flags)
            opt._global_step += n_steps
            return outs

        self._last_jitted = jitted
        return runner
