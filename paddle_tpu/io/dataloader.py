"""DataLoader — parity with fluid/reader.py:149 +
fluid/dataloader/dataloader_iter.py:100,251 (single-process and multi-process
iteration, samplers, collate, worker_init_fn, prefetch).

TPU-first notes: worker processes produce *numpy* batches (host memory);
device transfer happens in the consumer so batches can be laid out onto the
device mesh (`device_put` with a Sharding) without an extra hop. The
multiprocess transport uses the native C ring buffer when built
(paddle_tpu/native, replacing the reference's mmap_allocator shared-memory
path) and falls back to multiprocessing queues.
"""
from __future__ import annotations

import atexit
import itertools
import multiprocessing as mp
import multiprocessing.connection  # noqa: F401  (mp.connection.wait)
import os
import pickle
import queue
import signal
import threading
import time
import traceback
from typing import Callable, Optional

import numpy as np

from ..core.tensor import Tensor, to_tensor
from ..resilience.inject import active_injector
from .collate import default_collate_fn, default_convert_fn
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler, SequenceSampler, RandomSampler

__all__ = ["DataLoader", "get_worker_info"]

_worker_info = threading.local()


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed=0):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


def get_worker_info():
    return getattr(_worker_info, "info", None)


def _worker_loop(dataset, index_queue, out_queue, collate_fn, worker_id,
                 num_workers, worker_init_fn, iterable, ring_name=None):
    _worker_info.info = WorkerInfo(worker_id, num_workers, dataset)
    ring = None
    if ring_name is not None:
        try:
            from paddle_tpu.native import ShmRing

            ring = ShmRing(ring_name)
        except Exception:
            ring = None  # fall back to the queue transport

    def emit(batch_id, err, data, tb=None):
        if ring is not None:
            from . import _shm_transport as T

            if isinstance(err, StopIteration):
                rec = T.pack(batch_id, T.STOP, None)
            elif err is not None:
                try:  # ship the real exception when picklable (queue parity)
                    rec = T.pack(batch_id, T.ERROR, (err, tb))
                except Exception:
                    rec = T.pack(batch_id, T.ERROR, (repr(err), tb))
            else:
                rec = T.pack(batch_id, T.OK, data)
            try:
                if ring.push(rec):
                    return
            except ValueError:  # batch larger than the ring: fall through
                pass
        out_queue.put((batch_id, err, data if err is None else tb))

    try:
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        if iterable:
            it = iter(dataset)
            # iterable dataset: worker w yields every num_workers-th batch
            while True:
                msg = index_queue.get()
                if msg is None:
                    break
                if msg == "__reset__":
                    # persistent_workers epoch boundary: restart the
                    # dataset iterator without respawning the process
                    it = iter(dataset)
                    continue
                batch_id, batch_size = msg
                samples = list(itertools.islice(it, batch_size))
                if not samples:
                    emit(batch_id, StopIteration(), None)
                    continue
                emit(batch_id, None, collate_fn(samples))
        else:
            while True:
                msg = index_queue.get()
                if msg is None:
                    break
                batch_id, indices = msg
                try:
                    samples = [dataset[i] for i in indices]
                    emit(batch_id, None, collate_fn(samples))
                except Exception as e:  # propagate to parent
                    emit(batch_id, e, None, traceback.format_exc())
    except KeyboardInterrupt:
        pass
    finally:
        if ring is not None:
            ring.release()


class _MultiProcessIter:
    def __init__(self, loader, persistent=False):
        self._loader = loader
        self._persistent = persistent
        self._num_workers = loader.num_workers
        self._iterable = isinstance(loader.dataset, IterableDataset)
        # spawn, not fork: the parent holds live XLA threads/locks and a
        # forked child that touches jax (e.g. via a transform) can deadlock.
        ctx = mp.get_context("spawn")
        self._index_queues = []
        # every worker writes to the first queue; a respawned worker gets
        # one of its own (see _respawn), and all that are live are read
        self._out_queues = [ctx.Queue()]
        self._worker_out = [self._out_queues[0]] * self._num_workers
        self._workers = []
        self._batches = None if self._iterable else list(iter(loader.batch_sampler))
        self._send_idx = 0
        self._rcvd_idx = 0
        self._reorder = {}
        self._done = False
        # shared-memory ring transport (native); queue is the fallback and
        # the overflow path for records larger than the ring
        self._ring = None
        ring_name = None
        if getattr(loader, "use_shared_memory", True):
            try:
                from paddle_tpu.native import ShmRing

                ring_name = f"/pt_dl_{os.getpid()}_{id(self) & 0xFFFFFF:x}"
                self._ring = ShmRing(ring_name, capacity=loader.shm_capacity,
                                     create=True)
            except Exception:
                self._ring = None
                ring_name = None
        self._ctx = ctx
        self._ring_name = ring_name
        self._respawned: set = set()  # worker slots already respawned once
        for w in range(self._num_workers):
            self._index_queues.append(ctx.Queue())
            self._workers.append(self._spawn_worker(w))
        atexit.register(self._shutdown)
        # prime the pipeline
        for _ in range(self._num_workers * max(loader.prefetch_factor, 2)):
            self._dispatch()

    def _spawn_worker(self, w):
        p = self._ctx.Process(
            target=_worker_loop,
            args=(self._loader.dataset, self._index_queues[w],
                  self._worker_out[w], self._loader.collate_fn, w,
                  self._num_workers, self._loader.worker_init_fn,
                  self._iterable, self._ring_name),
            daemon=True,
        )
        p.start()
        return p

    def _respawn(self, w):
        """Replace a crashed/killed worker ONCE (resilience retry layer):
        a fresh index queue gets every in-flight batch id the dead worker
        owned but never answered re-enqueued, so the epoch loses and
        duplicates nothing. Map-style datasets only — an iterable
        dataset's position died with the worker's iterator.

        The new worker also gets a result queue of its own. A worker
        killed while its feeder thread was writing dies holding the shared
        queue's write lock (a semaphore all writers share) and may leave
        half a record in its pipe: a successor on the same queue would
        block on that lock for good, and the epoch would end in "worker
        timed out" (seen under load). The old queue goes on being read
        only while another live worker still writes to it."""
        from ..profiler.telemetry import get_telemetry

        get_telemetry().counter("resilience/worker_respawns")
        self._respawned.add(w)
        iq = self._ctx.Queue()
        self._index_queues[w] = iq  # old queue (and its backlog) dropped
        old, self._worker_out[w] = self._worker_out[w], self._ctx.Queue()
        self._out_queues.append(self._worker_out[w])
        if not any(q is old for q in self._worker_out):
            self._out_queues.remove(old)  # whole records were drained before
        for i in range(self._rcvd_idx, self._send_idx):
            if i % self._num_workers == w and i not in self._reorder:
                iq.put((i, self._batches[i]))
        self._workers[w] = self._spawn_worker(w)

    def _dispatch(self):
        if self._iterable:
            w = self._send_idx % self._num_workers
            self._index_queues[w].put((self._send_idx, self._loader.batch_sampler.batch_size))
            self._send_idx += 1
            return
        if self._send_idx >= len(self._batches):
            return
        w = self._send_idx % self._num_workers
        self._index_queues[w].put((self._send_idx, self._batches[self._send_idx]))
        self._send_idx += 1

    def _recv_one(self, timeout_s: float) -> bool:
        """Receive one record into the reorder buffer. False on timeout
        OR on a corrupted record — a worker SIGKILLed mid-write truncates
        the mp.Queue feeder's pickle stream; treating that as no-record
        lets the caller's liveness check own the recovery (respawn)."""
        if self._ring is not None:
            # drain any queue-overflow records first (non-blocking)
            drained = False
            for out in self._out_queues:
                try:
                    while True:
                        batch_id, err, data = out.get_nowait()
                        self._reorder[batch_id] = (err, data)
                        drained = True
                except queue.Empty:
                    pass
                except (EOFError, OSError, pickle.UnpicklingError):
                    pass  # truncated record from a killed worker
            if drained:
                return True
            try:
                rec = self._ring.pop_timed(int(timeout_s * 1000))
            except TimeoutError:
                return False
            if rec is None:  # ring closed
                return False
            from . import _shm_transport as T

            batch_id, status, payload = T.unpack(rec)
            if status == T.STOP:
                self._reorder[batch_id] = (StopIteration(), None)
            elif status == T.ERROR:
                err, tb = payload
                if not isinstance(err, BaseException):
                    err = RuntimeError(err)
                self._reorder[batch_id] = (err, tb)
            else:
                self._reorder[batch_id] = (None, payload)
            return True
        # wait on every live result queue at once, then take one record
        ready = mp.connection.wait([q._reader for q in self._out_queues],
                                   timeout_s)
        for out in self._out_queues:
            if out._reader not in ready:
                continue
            try:
                batch_id, err, data = out.get(timeout=timeout_s)
            except queue.Empty:
                continue
            except (EOFError, OSError, pickle.UnpicklingError):
                # truncated record from a SIGKILLed worker; anything else
                # (ImportError from an unpicklable payload, …) must
                # propagate
                continue
            self._reorder[batch_id] = (err, data)
            return True
        return False

    # receive-poll quantum: short enough that dead-worker detection and
    # deadline checks run promptly (a 2 s quantum made respawn latency —
    # and tests exercising it — hostage to queue-timeout alignment under
    # load), long enough to stay off the hot path (a record that IS
    # coming returns immediately, the quantum only prices the idle poll)
    _POLL_S = 0.25

    def _drain_outstanding(self):
        """Receive (and discard) every dispatched-but-unread record so the
        transport is empty before an epoch reset. Stops early if workers
        died — the caller respawns in that case. The deadline is a
        monotonic-clock budget re-anchored on every received record, not
        an accumulation of poll quanta (which under-counts time spent
        inside successful receives under load)."""
        budget = self._loader.timeout or 120.0
        deadline = time.monotonic() + budget
        while self._rcvd_idx < self._send_idx:
            if self._rcvd_idx in self._reorder:
                self._reorder.pop(self._rcvd_idx)
                self._rcvd_idx += 1
                continue
            if self._recv_one(timeout_s=self._POLL_S):
                deadline = time.monotonic() + budget
                continue
            # only a SILENT quantum consults liveness/deadline — records
            # already in the transport always drain first
            if any(not w.is_alive() for w in self._workers) \
                    or time.monotonic() >= deadline:
                self._shutdown()
                return
        self._reorder.clear()

    def _reset(self):
        """persistent_workers epoch boundary: reuse the live worker pool
        and index queues — only the sampler order and the in-flight
        bookkeeping restart (the reference keeps _workers alive across
        __iter__ the same way)."""
        self._drain_outstanding()
        if self._done:
            raise RuntimeError("cannot reset a shut-down DataLoader iter")
        if self._iterable:
            # workers hold an exhausted dataset iterator — restart it
            for iq in self._index_queues:
                iq.put("__reset__")
        else:
            self._batches = list(iter(self._loader.batch_sampler))
        self._send_idx = 0
        self._rcvd_idx = 0
        self._reorder = {}
        for _ in range(self._num_workers
                       * max(self._loader.prefetch_factor, 2)):
            self._dispatch()

    def __iter__(self):
        return self

    def __next__(self):
        if not self._iterable and self._rcvd_idx >= len(self._batches):
            if not self._persistent:
                self._shutdown()
            raise StopIteration
        budget = self._loader.timeout or 120.0
        deadline = time.monotonic() + budget
        while self._rcvd_idx not in self._reorder:
            if self._recv_one(timeout_s=self._POLL_S):
                # progress re-anchors the deadline: the budget bounds
                # SILENCE, not total epoch time. Receive comes FIRST so
                # a dead worker's already-computed, already-sent results
                # are drained and delivered before its death is acted
                # on — acting on liveness while deliverable records sit
                # in the transport would discard them (and, on the
                # respawn path, recompute them).
                deadline = time.monotonic() + budget
                continue
            # nothing arrived this quantum: consult liveness. The short
            # quantum (vs the old 2 s receive timeout) is the deflake —
            # dead-worker detection latency no longer depends on a long
            # queue timeout lining up with the death under load.
            dead_slots = [w for w, p in enumerate(self._workers)
                          if not p.is_alive()]
            if dead_slots:
                # resilience retry layer: respawn each dead worker
                # ONCE and re-enqueue its unanswered batches; a
                # second death of the same slot (or any death under
                # an iterable dataset, whose stream position is
                # unrecoverable) propagates as before
                if (not self._iterable
                        and not any(w in self._respawned
                                    for w in dead_slots)):
                    for w in dead_slots:
                        self._respawn(w)
                    # the respawned worker pays spawn + re-import +
                    # recompute of re-enqueued batches — a fresh
                    # monotonic budget, not an accumulation reset, so
                    # a loaded machine still gets the full window
                    deadline = time.monotonic() + budget
                    continue
                self._shutdown()
                raise RuntimeError(
                    f"DataLoader worker slot(s) {dead_slots} exited "
                    "unexpectedly (respawn budget exhausted). Note: "
                    "workers start via spawn — datasets must be "
                    "importable (defined in a module, not __main__/REPL)."
                )
            if time.monotonic() >= deadline:
                self._shutdown()
                raise RuntimeError("DataLoader worker timed out")
        err, data = self._reorder.pop(self._rcvd_idx)
        batch_id = self._rcvd_idx
        self._rcvd_idx += 1
        if isinstance(err, StopIteration):
            if not self._persistent:
                self._shutdown()
            raise StopIteration
        if err is not None:
            self._shutdown()
            raise RuntimeError(f"DataLoader worker raised:\n{data}") from err
        inj = active_injector()
        if inj is not None and inj.worker_kill_due(batch_id):
            # fault-injection harness: SIGKILL the worker that produced
            # this batch (deterministic respawn-path exercise)
            victim = self._workers[batch_id % self._num_workers]
            if victim.is_alive():
                os.kill(victim.pid, signal.SIGKILL)
        self._dispatch()
        return _to_tensors(data, self._loader.return_list)

    def _shutdown(self):
        if self._done:
            return
        self._done = True
        for iq in self._index_queues:
            try:
                iq.put(None)
            except Exception:
                pass
        # close the ring BEFORE joining: a worker blocked in ring.push must
        # see closed (push returns False) to reach its index-queue sentinel
        if self._ring is not None:
            try:
                self._ring.close()
            except Exception:
                pass
        for p in self._workers:
            p.join(timeout=2.0)
            if p.is_alive():
                p.terminate()
        if self._ring is not None:
            try:
                self._ring.close()
                self._ring.release()
            except Exception:
                pass
            self._ring = None


def _to_tensors(batch, return_list=True):
    if isinstance(batch, np.ndarray):
        return to_tensor(batch)
    if isinstance(batch, (list, tuple)):
        return type(batch)(_to_tensors(b, return_list) for b in batch)
    if isinstance(batch, dict):
        return {k: _to_tensors(v, return_list) for k, v in batch.items()}
    if isinstance(batch, Tensor):
        return batch
    return batch


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False,
                 shm_capacity=64 << 20):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = int(num_workers)
        self.prefetch_factor = prefetch_factor
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        self.use_shared_memory = use_shared_memory
        self.shm_capacity = shm_capacity
        self.persistent_workers = bool(persistent_workers)
        self._persistent_iter: Optional[_MultiProcessIter] = None
        self._is_iterable_ds = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        else:
            self.batch_size = batch_size
            if self._is_iterable_ds:
                self.batch_sampler = _IterableBatchCfg(batch_size, drop_last)
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
                )

    def __len__(self):
        return len(self.batch_sampler)

    def __iter__(self):
        if self.num_workers > 0:
            if self.persistent_workers:
                return self._counted(self._persistent_mp_iter())
            return self._counted(_MultiProcessIter(self))
        return self._counted(self._single_process_iter())

    def _persistent_mp_iter(self):
        """Keep ONE worker pool (and its index queues) alive across
        ``__iter__`` calls — spawn respawn cost (interpreter + imports per
        worker, dominant for short epochs) is paid once; each new epoch
        just drains leftovers, reshuffles the sampler, and re-primes.

        Contract: ONE live iterator at a time (same as the reference's
        persistent_workers) — a second concurrent ``iter(loader)`` resets
        the shared pool out from under the first. Sequential epochs,
        including epochs abandoned mid-way, are fully supported."""
        it = self._persistent_iter
        if it is None or it._done:
            it = self._persistent_iter = _MultiProcessIter(self,
                                                           persistent=True)
        else:
            try:
                it._reset()
            except RuntimeError:
                # pool died mid-drain (worker crash): fall back to respawn
                it = self._persistent_iter = _MultiProcessIter(
                    self, persistent=True)
        return it

    @staticmethod
    def _counted(it):
        """Stream batches through the telemetry reader counters
        (reader/batches, reader/bytes) — the data-ingest half of the
        step-latency picture, shared by the single- and multi-process
        paths."""
        from ..profiler.telemetry import get_telemetry

        tel = get_telemetry()
        if not tel.enabled:
            yield from it
            return
        for batch in it:
            tel.counter("reader/batches")
            tel.counter("reader/bytes", _batch_nbytes(batch))
            yield batch

    def _single_process_iter(self):
        if self._is_iterable_ds:
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    yield _to_tensors(self.collate_fn(batch), self.return_list)
                    batch = []
            if batch and not self.batch_sampler.drop_last:
                yield _to_tensors(self.collate_fn(batch), self.return_list)
            return
        for indices in self.batch_sampler:
            samples = [self.dataset[i] for i in indices]
            yield _to_tensors(self.collate_fn(samples), self.return_list)


def _batch_nbytes(batch) -> int:
    """Total array bytes in a collated batch (metadata walk only)."""
    if isinstance(batch, (list, tuple)):
        return sum(_batch_nbytes(b) for b in batch)
    if isinstance(batch, dict):
        return sum(_batch_nbytes(b) for b in batch.values())
    if isinstance(batch, Tensor):
        batch = batch._value
    return int(getattr(batch, "nbytes", 0))


class _IterableBatchCfg:
    def __init__(self, batch_size, drop_last):
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __len__(self):
        raise RuntimeError("IterableDataset loader has no length")
