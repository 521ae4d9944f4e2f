"""Device-resident input pipeline: DevicePrefetcher lifecycle, shape
bucketing + retrace bounds, the single-pytree executor feed path,
persistent DataLoader workers, and the retrace-budget CI gate. (Where the
persistent compilation cache lives is tests/test_chip_smoke.py's.)"""
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io.prefetch import DevicePrefetcher, ShapeBuckets
from paddle_tpu.profiler.retrace import tracked_jit
from paddle_tpu.profiler.telemetry import get_telemetry


def _gen_batches(n, shape=(4, 8), fail_at=None, delay=0.0):
    rng = np.random.RandomState(0)
    for i in range(n):
        if fail_at is not None and i == fail_at:
            raise ValueError(f"boom at {i}")
        if delay:
            time.sleep(delay)
        yield {"x": rng.randn(*shape).astype(np.float32),
               "i": np.full((shape[0],), i, np.int64)}


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "DevicePrefetcher" and t.is_alive()]


class TestDevicePrefetcher:
    def test_yields_all_batches_in_order_on_device(self):
        pf = DevicePrefetcher(_gen_batches(7), depth=2)
        out = list(pf)
        assert len(out) == 7
        for i, b in enumerate(out):
            assert isinstance(b["x"], jax.Array)
            assert int(np.asarray(b["i"])[0]) == i
        # StopIteration drained the pipeline: the worker is gone
        assert not _prefetch_threads()

    def test_reiterating_after_exhaustion_is_empty(self):
        pf = DevicePrefetcher(_gen_batches(2))
        assert len(list(pf)) == 2
        assert list(pf) == []

    def test_clean_shutdown_mid_epoch(self):
        pf = DevicePrefetcher(_gen_batches(1000), depth=2)
        got = [next(pf) for _ in range(3)]
        assert len(got) == 3
        pf.close()
        for _ in range(50):  # worker notices the close within ~100ms
            if not _prefetch_threads():
                break
            time.sleep(0.02)
        assert not _prefetch_threads()
        with pytest.raises(StopIteration):
            next(pf)

    def test_context_manager_closes(self):
        with DevicePrefetcher(_gen_batches(100), depth=2) as pf:
            next(pf)
        assert not _prefetch_threads()

    def test_worker_exception_propagates_in_order(self):
        pf = DevicePrefetcher(_gen_batches(10, fail_at=3), depth=2)
        got = []
        with pytest.raises(ValueError, match="boom at 3"):
            for b in pf:
                got.append(b)
        # every batch before the failure was delivered, none after
        assert len(got) == 3
        assert not _prefetch_threads()

    def test_prefetch_runs_ahead_of_consumer(self):
        consumed = []
        produced = []

        def src():
            for i in range(6):
                produced.append(i)
                yield np.full((2,), i, np.float32)

        pf = DevicePrefetcher(src(), depth=3)
        consumed.append(next(pf))
        time.sleep(0.3)  # give the worker time to fill the queue
        # with depth 3 the worker staged past what the consumer took
        assert len(produced) >= 3
        pf.close()

    def test_telemetry_counters_and_h2d_histograms(self):
        tel = get_telemetry()
        before = tel.counter_value("prefetch/batches")
        h_before = tel.histogram("prefetch/h2d_bytes").count
        list(DevicePrefetcher(_gen_batches(4)))
        assert tel.counter_value("prefetch/batches") == before + 4
        h = tel.histogram("prefetch/h2d_bytes")
        assert h.count == h_before + 4
        # every staged batch carries x [4,8] f32 + i [4] i64 = 160 bytes
        assert h.min <= 160 <= h.max
        assert tel.histogram("prefetch/h2d_ms").count >= 4


class TestShapeBuckets:
    def test_pad_to_next_bucket(self):
        bk = ShapeBuckets((16, 32), axis=1, pad_value=-1)
        arr = np.ones((2, 11), np.int64)
        out, hits, misses = bk.pad_tree({"x": arr})
        assert out["x"].shape == (2, 16)
        assert (out["x"][:, 11:] == -1).all()
        assert (out["x"][:, :11] == 1).all()
        assert (hits, misses) == (1, 0)

    def test_exact_match_is_hit_oversize_is_miss(self):
        bk = ShapeBuckets((16, 32))
        out, hits, misses = bk.pad_tree(
            {"a": np.zeros((2, 32)), "b": np.zeros((2, 40))})
        assert out["a"].shape == (2, 32)
        assert out["b"].shape == (2, 40)  # never truncated
        assert (hits, misses) == (1, 1)

    def test_device_resident_leaf_pads_on_device(self):
        bk = ShapeBuckets((16,), pad_value=0)
        arr = jax.numpy.ones((2, 10), jax.numpy.float32)
        out, hits, misses = bk.pad_tree({"x": arr})
        assert isinstance(out["x"], jax.Array)  # never bounced to host
        assert out["x"].shape == (2, 16)
        assert float(out["x"][:, :10].sum()) == 20.0
        assert float(out["x"][:, 10:].sum()) == 0.0
        assert (hits, misses) == (1, 0)

    def test_multithread_dataset_loop_buckets(self, rng):
        """thread>1 path: prefetch_buckets must bound compiles too."""
        from paddle_tpu import static

        paddle.seed(3)
        main, start = static.Program(), static.Program()
        with static.program_guard(main, start):
            x = static.data("x", [4, None], "float32")
            y = static.data("y", [4, 1], "int64")
            logits = static.nn.fc(x.sum(axis=1, keepdim=True), 2)
            loss = paddle.nn.functional.cross_entropy(
                logits, y.reshape([-1]))
            paddle.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = static.Executor()
        data = [{"x": rng.randn(4, L).astype(np.float32),
                 "y": rng.randint(0, 2, (4, 1)).astype(np.int64)}
                for L in (3, 9, 14, 5, 11, 2)]
        out = exe.train_from_dataset(main, data, fetch_list=[loss],
                                     thread=2, prefetch_buckets=(16,))
        assert out is not None and np.isfinite(float(out[0]))
        # every ragged batch padded into the one bucket -> one train-step
        # signature -> exactly one compile recorded for this executor
        assert exe._last_jitted.tracker.compiles == 1

    def test_low_rank_leaves_pass_through(self):
        bk = ShapeBuckets((8,))
        labels = np.arange(4)
        out, hits, misses = bk.pad_tree({"y": labels})
        assert out["y"] is labels
        assert (hits, misses) == (0, 0)

    def test_ragged_batches_compile_once_per_bucket(self):
        """The tentpole retrace guarantee: ragged lengths through the
        prefetcher's buckets compile the jitted step exactly
        ``len(buckets)`` times."""
        buckets = (16, 32)

        @tracked_jit(name="test.bucketed_step")
        def step(x):
            return x.sum()

        def ragged():
            rng = np.random.RandomState(0)
            for L in (3, 9, 14, 17, 25, 31, 5, 20):  # drifts every batch
                yield rng.randn(2, L).astype(np.float32)

        pf = DevicePrefetcher(ragged(), depth=2, buckets=buckets)
        n = 0
        for batch in pf:
            assert batch.shape[1] in buckets
            step(batch)
            n += 1
        assert n == 8
        assert step.tracker.compiles == len(buckets)

    def test_without_buckets_every_shape_recompiles(self):
        @tracked_jit(name="test.unbucketed_step")
        def step(x):
            return x.sum()

        for L in (3, 9, 14, 17):
            step(jax.numpy.zeros((2, L)))
        assert step.tracker.compiles == 4

    def test_bucket_hit_miss_counters(self):
        tel = get_telemetry()
        h0 = tel.counter_value("prefetch/bucket_hits")
        m0 = tel.counter_value("prefetch/bucket_misses")
        src = (np.zeros((2, L), np.float32) for L in (5, 40, 12))
        list(DevicePrefetcher(src, buckets=ShapeBuckets((16,))))
        assert tel.counter_value("prefetch/bucket_hits") == h0 + 2
        assert tel.counter_value("prefetch/bucket_misses") == m0 + 1


class TestShardedPrefetch:
    def test_batches_land_with_engine_sharding(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        sharding = NamedSharding(mesh, P("dp"))
        src = (np.ones((8, 4), np.float32) * i for i in range(3))
        out = list(DevicePrefetcher(src, sharding=sharding))
        assert len(out) == 3
        for b in out:
            assert b.sharding == sharding

    def test_engine_prefetch_end_to_end(self):
        from jax.sharding import Mesh
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        engine = ParallelTrainStep(net, loss_fn=lambda out, y: (
            (out - y) ** 2).mean(), optimizer=opt, mesh=mesh)
        rng = np.random.RandomState(0)

        def batches():
            for _ in range(4):
                yield ((rng.randn(8, 8).astype(np.float32),),
                       (rng.randn(8, 4).astype(np.float32),))

        losses = [float(engine(inp, lab).numpy())
                  for inp, lab in engine.prefetch(batches(), depth=2)]
        assert len(losses) == 4
        assert losses[-1] < losses[0]  # it actually trained

    def test_jit_train_step_prefetch(self):
        from paddle_tpu import nn

        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = paddle.jit.TrainStep(
            net, loss_fn=lambda out, y: ((out - y) ** 2).mean(),
            optimizer=opt)
        rng = np.random.RandomState(0)

        def batches():
            for _ in range(3):
                yield ((rng.randn(4, 4).astype(np.float32),),
                       (rng.randn(4, 2).astype(np.float32),))

        n = 0
        for inp, lab in step.prefetch(batches()):
            step(inp, lab)
            n += 1
        assert n == 3


class TestHapiFitPrefetch:
    def test_fit_with_prefetch_matches_without(self):
        from paddle_tpu import nn
        from paddle_tpu.io.dataset import TensorDataset

        rng = np.random.RandomState(0)
        xs = rng.randn(32, 6).astype(np.float32)
        ys = rng.randint(0, 3, (32, 1)).astype(np.int64)
        ds = TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])

        def run(prefetch_depth):
            paddle.seed(11)
            net = nn.Linear(6, 3)
            model = paddle.Model(net)
            model.prepare(
                optimizer=paddle.optimizer.SGD(
                    learning_rate=0.1, parameters=net.parameters()),
                loss=nn.CrossEntropyLoss())
            model.fit(ds, batch_size=8, epochs=2, verbose=0, shuffle=False,
                      prefetch_depth=prefetch_depth)
            model._train_step.sync_to_layer()
            return {k: np.asarray(v.numpy())
                    for k, v in net.state_dict().items()}

        plain = run(0)
        pre = run(2)
        assert plain.keys() == pre.keys()
        for k in plain:
            np.testing.assert_allclose(plain[k], pre[k], rtol=1e-6)
        assert not _prefetch_threads()  # fit closed its epoch pipelines


class TestExecutorPipelineWiring:
    def test_train_from_dataset_prefetch_matches_inline(self, rng):
        """The prefetched dataset loop must train bit-identically to the
        prefetch-disabled path (same batches, same order)."""
        from paddle_tpu import static

        def build():
            paddle.seed(7)
            main, start = static.Program(), static.Program()
            with static.program_guard(main, start):
                x = static.data("x", [8, 4], "float32")
                y = static.data("y", [8, 1], "int64")
                logits = static.nn.fc(x, 2)
                loss = paddle.nn.functional.cross_entropy(
                    logits, y.reshape([-1]))
                paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = static.Executor()
            return exe, main, loss

        data = [{"x": rng.randn(8, 4).astype(np.float32),
                 "y": rng.randint(0, 2, (8, 1)).astype(np.int64)}
                for _ in range(5)]

        exe1, main1, loss1 = build()
        r1 = exe1.train_from_dataset(main1, data, fetch_list=[loss1],
                                     prefetch_depth=0)
        exe2, main2, loss2 = build()
        r2 = exe2.train_from_dataset(main2, data, fetch_list=[loss2],
                                     prefetch_depth=2)
        np.testing.assert_allclose(np.asarray(r1[0]), np.asarray(r2[0]),
                                   rtol=1e-6)

    def test_feed_builder_single_pytree_transfer(self, rng, monkeypatch):
        """Satellite: with the prefetcher disabled the feed builder issues
        ONE device_put for the whole feed dict, not one per feed var."""
        from paddle_tpu import static

        main, start = static.Program(), static.Program()
        with static.program_guard(main, start):
            static.data("x", [None, 4], "float32")
            static.data("y", [None, 1], "float32")
        exe = static.Executor()
        build = exe._dataset_feed_builder(main, to_device=True)
        calls = []
        real_put = jax.device_put
        monkeypatch.setattr(jax, "device_put",
                            lambda *a, **k: calls.append(a) or real_put(*a, **k))
        feed = build({"x": rng.randn(4, 4).astype(np.float32),
                      "y": rng.randn(4, 1).astype(np.float32)})
        assert len(calls) == 1  # one pytree dispatch for two feed vars
        assert set(feed) == {"x", "y"}
        assert all(isinstance(v, jax.Array) for v in feed.values())


from paddle_tpu.io.dataset import Dataset as _Dataset


class _IotaDataset(_Dataset):
    """Module-level so spawn workers can pickle it."""

    def __init__(self, n, width):
        self.n = n
        self.width = width

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((self.width,), i, np.float32)


class TestPersistentWorkers:
    def test_workers_survive_across_epochs(self):
        from paddle_tpu.io import DataLoader

        loader = DataLoader(_IotaDataset(16, 3), batch_size=4, num_workers=2,
                            persistent_workers=True, use_shared_memory=False)
        epochs = []
        pids = []
        for _ in range(3):
            got = sorted(float(b.numpy().ravel()[0])
                         for b in loader)
            epochs.append(got)
            pids.append(tuple(p.pid for p in
                              loader._persistent_iter._workers))
        assert all(len(e) == 4 for e in epochs)
        assert epochs[0] == epochs[1] == epochs[2]
        # THE contract: one pool, same processes, all three epochs
        assert pids[0] == pids[1] == pids[2]
        assert all(p.is_alive() for p in loader._persistent_iter._workers)
        loader._persistent_iter._shutdown()

    def test_nonpersistent_respawns(self):
        from paddle_tpu.io import DataLoader

        loader = DataLoader(_IotaDataset(8, 2), batch_size=4, num_workers=1,
                            use_shared_memory=False)
        assert len(list(loader)) == 2
        assert len(list(loader)) == 2  # fresh pool per epoch still works

    def test_persistent_reshuffles_between_epochs(self):
        from paddle_tpu.io import DataLoader

        loader = DataLoader(_IotaDataset(64, 1), batch_size=8, shuffle=True,
                            num_workers=2, persistent_workers=True,
                            use_shared_memory=False)
        e1 = [tuple(b.numpy().ravel().tolist()) for b in loader]
        e2 = [tuple(b.numpy().ravel().tolist()) for b in loader]
        flat1 = sorted(v for t in e1 for v in t)
        flat2 = sorted(v for t in e2 for v in t)
        assert flat1 == flat2 == [float(i) for i in range(64)]
        assert e1 != e2  # the sampler re-shuffled on the live pool
        loader._persistent_iter._shutdown()


class TestRetraceBudgetGate:
    def _write(self, path, records):
        with open(path, "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")

    def test_pass_and_fail(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        try:
            import check_retrace_budget as gate
        finally:
            sys.path.pop(0)
        p = str(tmp_path / "t.jsonl")
        self._write(p, [
            {"ts": 1.0, "step": 0, "tag": "bench",
             "scalars": {"counter/compile/fleet.train_step": 2}},
            {"ts": 2.0, "step": 1, "tag": "bench",
             "scalars": {"counter/compile/fleet.train_step": 3,
                         "counter/compile/jit.train_step": 1,
                         "counter/engine/steps": 500}},
        ])
        # shared gate conventions (tools/_gate.py): exit 0 pass, 1 fail
        assert gate.main([p, "--budget", "6"]) == 0
        assert gate.main([p, "--budget", "2"]) == 1
        assert gate.main([p, "--budget", "2",
                          "--ignore", "compile/fleet.train_step"]) == 0

    def test_malformed_log_errors(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        try:
            import check_retrace_budget as gate
        finally:
            sys.path.pop(0)
        p = str(tmp_path / "bad.jsonl")
        with open(p, "w") as f:
            f.write("{not json\n")
        assert gate.main([p]) == 1
