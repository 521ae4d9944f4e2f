"""PR 8: the kernel-selection and memory-policy layer.

- ``ops.tier_policy``: the paged pair's verdict file (restart-warm, a
  corrupt file never deleted). A dense call's tier is a rule
  (tests/test_attention_impls.py::TestDispatch).
- ``ops.attention``: ring attention gradients (hand-written recompute
  custom_vjp) vs the materialized core, 'auto' promotion onto a
  registered ring mesh, fallback accounting
  (``counter/attn/tier_fallbacks`` + one-shot warning).
- ``ops.remat_policy``: roofline-driven selective remat — the escalation
  ladder against a pinned HBM budget, ``remat='auto'`` end-to-end on
  jit.TrainStep / fleet.ParallelTrainStep with attribution gauges.
- ``tools/check_attribution.py``: the tier gate over bench records.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.ops import attention as att
from paddle_tpu.ops import remat_policy, tier_policy
from paddle_tpu.profiler.telemetry import get_telemetry

def _sm(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@pytest.fixture(autouse=True)
def _clean_tier_state():
    tier_policy.reset()
    att._fallback_warned.clear()
    yield
    tier_policy.reset()
    att.set_ring_context(None, None)
    att._fallback_warned.clear()


def _qkv(rng, b=2, h=2, L=32, d=8, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.randn(b, h, L, d), dtype)
    return mk(), mk(), mk()


# ---------------------------------------------------------------------------
# tier_policy: the verdict file (the paged pair's; no dense call reads it)
# ---------------------------------------------------------------------------
class TestTierCache:
    SHAPE = (1, 2, 8, 4, 4, jnp.float32, False)  # t, h, d, m, bs, dtype, int8

    @pytest.fixture(autouse=True)
    def _bench_mode(self, monkeypatch, tmp_path):
        self.cache = tmp_path / "tiers.json"
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "bench")
        monkeypatch.setenv("PADDLE_TPU_ATTN_TIER_CACHE", str(self.cache))

    def test_cache_hit_across_process_restart(self, monkeypatch):
        tier = tier_policy.select_paged(*self.SHAPE)
        (key, verdict), = json.loads(self.cache.read_text()).items()
        assert key == tier_policy.make_paged_key(*self.SHAPE)
        assert verdict["tier"] == tier and "timings_ms" in verdict

        # "restart": the in-memory registry is gone, the file remains
        tier_policy.reset()

        def boom(*a, **kw):
            raise AssertionError("restart-warm select must not re-bench")

        monkeypatch.setattr(tier_policy, "bench_paged", boom)
        assert tier_policy.select_paged(*self.SHAPE) == tier

    def test_corrupt_cache_remeasures_and_deletes_nothing(self):
        garbage = "{not json" * 3
        self.cache.write_text(garbage)
        tel = get_telemetry()
        before = tel.counter_value("attn/tier_bench")
        assert tier_policy.select_paged(*self.SHAPE) in tier_policy.PAGED_TIERS
        assert tel.counter_value("attn/tier_bench") - before == 1
        # the unreadable file is evidence, not disposable state: its bytes
        # survive both the failed load AND later verdict persistence
        assert self.cache.read_text() == garbage
        tier_policy.select_paged(1, 2, 8, 8, 4, jnp.float32, False)
        assert self.cache.read_text() == garbage


# ---------------------------------------------------------------------------
# fallback accounting: a silent reroute is counted and warned once
# ---------------------------------------------------------------------------
class TestFallbackAccounting:
    def test_heuristic_flash_misfit_counts_and_warns_once(self, monkeypatch):
        monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
        tel = get_telemetry()
        before = tel.counter_value("attn/tier_fallbacks")
        q = jnp.zeros((1, 9000, 4, 64), jnp.float32)  # 9000 % 256 != 0
        tier = lambda: att._tier(q, q, q, True, None, None, True, True)
        assert tier() == "blockwise"
        assert tel.counter_value("attn/tier_fallbacks") - before == 1
        assert len(att._fallback_warned) == 1
        # every occurrence COUNTS; the warning stays one-shot per shape
        assert tier() == "blockwise"
        assert tel.counter_value("attn/tier_fallbacks") - before == 2
        assert len(att._fallback_warned) == 1


# ---------------------------------------------------------------------------
# ring attention: gradients + auto promotion
# ---------------------------------------------------------------------------
class TestRingAttentionGrad:
    def _ring(self, causal):
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        spec = P(None, None, "sp", None)
        return _sm(lambda q, k, v: att.ring_attention(q, k, v, "sp",
                                                      causal, 512),
                   mesh, (spec, spec, spec), spec)

    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_and_grads_match_attention_core(self, rng, causal):
        q, k, v = _qkv(rng, b=2, h=2, L=64, d=8)
        cot = jnp.asarray(rng.randn(*q.shape), jnp.float32)
        out_r, vjp_r = jax.vjp(self._ring(causal), q, k, v)
        out_c, vjp_c = jax.vjp(
            lambda a, b, c: att.xla_attention(a, b, c, causal=causal),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_c),
                                   rtol=2e-5, atol=2e-5)
        for gr, gc, name in zip(vjp_r(cot), vjp_c(cot), "qkv"):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gc), rtol=2e-5, atol=2e-5,
                err_msg=f"d{name} mismatch (recompute backward)")

    def test_grad_under_jit(self, rng):
        q, k, v = _qkv(rng, b=1, h=2, L=32, d=8)
        loss = lambda a, b, c: (self._ring(True)(a, b, c) ** 2).sum()
        g = jax.jit(jax.grad(loss))(q, k, v)
        ref = jax.grad(lambda a, b, c: (att.xla_attention(
            a, b, c, causal=True) ** 2).sum())(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestRingAutoPromotion:
    def test_auto_promotes_on_registered_mesh(self, monkeypatch, rng):
        monkeypatch.setenv("PADDLE_TPU_ATTN_RING_MIN_SEQ", "64")
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        q, k, v = _qkv(rng, b=2, h=2, L=128, d=8)
        out = jax.jit(lambda a, b, c: att.dot_product_attention(
            a, b, c, causal=True))(q, k, v)
        scal = get_telemetry().scalars()
        assert scal["gauge/attn/tier.L128.d8.c"] == \
            tier_policy.TIER_IDS["ring"]
        ref = att.xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_below_threshold_keeps_single_device_tier(self, monkeypatch, rng):
        monkeypatch.setenv("PADDLE_TPU_ATTN_RING_MIN_SEQ", "8192")
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        assert not att._ring_auto_ok(128, True, None)

    def test_non_causal_and_biased_never_promote(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        assert not att._ring_auto_ok(8192, False, None)
        assert not att._ring_auto_ok(8192, True, object())

    def test_indivisible_seq_never_promotes(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ATTN_RING_MIN_SEQ", "64")
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        assert not att._ring_auto_ok(130, True, None)  # 130 % 4 != 0

    @pytest.mark.parametrize("forced", ["blockwise", "xla"])
    def test_explicit_policy_override_outranks_promotion(
            self, monkeypatch, rng, forced):
        """A tier named by ``set_attention_impl`` is the tier that runs:
        the forced-blockwise bench ablation leg depends on ring NOT
        hijacking the dispatch."""
        monkeypatch.setenv("PADDLE_TPU_ATTN_RING_MIN_SEQ", "64")
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        assert att._ring_auto_ok(128, True, None)
        q, k, v = _qkv(rng, L=128)
        att.set_attention_impl(forced)
        try:
            att.dot_product_attention(q, k, v, causal=True)
        finally:
            att.set_attention_impl("auto")
        assert get_telemetry().scalars()["gauge/attn/tier.L128.d8.c"] == \
            tier_policy.TIER_IDS[forced]

    def test_explicit_sp_axis_dispatch_publishes_ring_verdict(self, rng):
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        q, k, v = _qkv(rng, L=64)
        spec = P(None, None, "sp", None)
        f = _sm(lambda a, b, c: att.dot_product_attention(
            a, b, c, causal=True, sp_axis="sp"),
            mesh, (spec, spec, spec), spec)
        out = jax.jit(f)(q, k, v)
        assert out.shape == q.shape
        # L in the gauge key is the LOCAL shard length (64 / 4 ring hops)
        assert get_telemetry().scalars()["gauge/attn/tier.L16.d8.c"] == \
            tier_policy.TIER_IDS["ring"]

    def test_plain_engine_clears_stale_ring_context(self):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        att.set_ring_context(mesh, "sp")
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        ParallelTrainStep(net, loss_fn=nn.CrossEntropyLoss(), optimizer=opt,
                          mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)))
        # the non-sp engine owns the trace-time global now: no trace of
        # it may promote onto the dead sp engine's mesh
        assert att._ring_ctx["axis"] is None

    def test_misspelled_sp_axis_raises(self):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        with pytest.raises(ValueError, match="sp_axis"):
            ParallelTrainStep(net, loss_fn=nn.CrossEntropyLoss(),
                              optimizer=opt,
                              mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
                              sp_axis="seq")


class TestFleetSequenceParallel:
    def _build(self, sp):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                        num_heads=2, max_position_embeddings=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        if sp:
            mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "sp"))
            return ParallelTrainStep(model, loss_fn=model.loss_fn,
                                     optimizer=opt, mesh=mesh, sp_axis="sp")
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        return ParallelTrainStep(model, loss_fn=model.loss_fn,
                                 optimizer=opt, mesh=mesh)

    def test_sp_engine_matches_plain_dp(self, monkeypatch, rng):
        """Ring-sharded training (batches land pre-rotated over sp) takes
        the same loss trajectory as the plain dp engine."""
        monkeypatch.setenv("PADDLE_TPU_ATTN_RING_MIN_SEQ", "32")
        ids = rng.randint(0, 128, (2, 64)).astype(np.int32)
        labels = np.roll(ids, -1, axis=1).astype(np.int32)
        sp_engine = self._build(sp=True)
        ring_losses = [float(sp_engine((ids,), (labels,)).numpy())
                       for _ in range(3)]
        scal = get_telemetry().scalars()
        assert scal["gauge/attn/tier.L64.d16.c"] == \
            tier_policy.TIER_IDS["ring"]
        att.set_ring_context(None, None)
        dp_engine = self._build(sp=False)
        dp_losses = [float(dp_engine((ids,), (labels,)).numpy())
                     for _ in range(3)]
        np.testing.assert_allclose(ring_losses, dp_losses, rtol=2e-4,
                                   atol=2e-4)

    def test_batch_shardings_skip_indivisible_leaves(self):
        """Only leaves whose dim 1 divides the ring size take the
        (dp, sp) layout — broadcast-dim masks [b, 1, L, L], ragged class
        dims, and 1-D labels stay dp-only instead of crashing
        device_put (the ring's shard_map boundary reshards on entry, so
        dp-only landing is safe)."""
        eng = self._build(sp=True)  # ring size 4
        batch = ((np.zeros((8, 64), np.int32),         # seq leaf: (dp, sp)
                  np.zeros((8, 1, 64, 64), np.float32),  # broadcast dim 1
                  np.zeros((8, 3), np.float32)),         # 3 % 4 != 0
                 (np.zeros((8,), np.int32),))            # 1-D per-sample
        sh = eng._batch_shardings(batch)
        (s_seq, s_mask, s_ragged), (s_lab,) = sh
        assert s_seq.spec == eng._batch_sharding.spec
        dp_only = P(eng._batch_sharding.spec[0])
        assert s_mask.spec == dp_only
        assert s_ragged.spec == dp_only
        assert s_lab.spec == dp_only
        jax.device_put(batch, sh)  # must place without a divisibility error


# ---------------------------------------------------------------------------
# remat_policy: the roofline-driven escalation ladder
# ---------------------------------------------------------------------------
class TestRematPolicy:
    @pytest.fixture(autouse=True)
    def _fresh_cost_registry(self):
        from paddle_tpu.profiler import xla_cost

        xla_cost.reset()
        yield
        xla_cost.reset()

    def test_normalize_vocabulary(self):
        assert remat_policy.normalize(False) == "off"
        assert remat_policy.normalize(None) == "off"
        assert remat_policy.normalize(True) == "full"
        assert remat_policy.normalize("dots") == "dots"
        assert remat_policy.normalize("dots_no_batch") == "dots_no_batch"
        assert remat_policy.normalize("nothing") == "nothing"
        assert remat_policy.normalize("auto") == "auto"
        with pytest.raises(ValueError):
            remat_policy.normalize("everything")

    def test_apply_policy_off_is_identity(self):
        f = lambda x: x
        assert remat_policy.apply_policy(f, "off") is f
        assert remat_policy.apply_policy(f, False) is f
        assert remat_policy.apply_policy(f, "full") is not f

    def _fake_costs(self, table):
        def lower_cost(policy):
            c = table.get(policy)
            if c is None:
                return None
            peak, flops, by = c
            return {"peak_hbm_bytes": peak, "flops": flops,
                    "bytes_accessed": by}

        return lower_cost

    def test_fits_resolves_to_no_remat(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "1000")
        monkeypatch.setenv("PADDLE_TPU_REMAT_BUDGET_FRAC", "0.9")
        chosen = remat_policy.resolve("t.fits", self._fake_costs(
            {"off": (500, 1.0, 100.0)}))
        assert chosen == "off"
        scal = get_telemetry().scalars()
        assert scal["gauge/remat/t.fits"] == remat_policy.POLICY_IDS["off"]
        assert scal["gauge/remat/peak_hbm/t.fits"] == 500

    def test_memory_bound_jumps_to_nothing(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "1000")
        monkeypatch.setenv("PADDLE_TPU_REMAT_BUDGET_FRAC", "0.9")
        calls = []

        def lc(policy):
            calls.append(policy)
            # intensity 2000/2000 = 1 << CPU balance: memory-bound
            return {"off": {"peak_hbm_bytes": 2000, "flops": 2000.0,
                            "bytes_accessed": 2000.0},
                    "nothing": {"peak_hbm_bytes": 800, "flops": 2000.0,
                                "bytes_accessed": 2000.0}}.get(policy)

        assert remat_policy.resolve("t.mem", lc) == "nothing"
        assert "dots" not in calls  # memory-bound skips the dots rung
        scal = get_telemetry().scalars()
        assert scal["gauge/remat/t.mem"] == remat_policy.POLICY_IDS["nothing"]

    def test_compute_bound_tries_dots_first(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "1000")
        monkeypatch.setenv("PADDLE_TPU_REMAT_BUDGET_FRAC", "0.9")
        chosen = remat_policy.resolve("t.comp", self._fake_costs({
            # intensity 1e12/1 >> balance: compute-bound
            "off": (2000, 1e12, 1.0),
            "dots": (850, 1e12, 1.0),
            "nothing": (400, 1e12, 1.0),
        }))
        assert chosen == "dots"  # first rung that fits wins; no over-remat

    def test_nothing_fits_takes_smallest_measured(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "100")
        chosen = remat_policy.resolve("t.none", self._fake_costs({
            "off": (2000, 1.0, 100.0),
            "nothing": (1500, 1.0, 100.0),
        }))
        assert chosen == "nothing"
        scal = get_telemetry().scalars()
        assert scal["gauge/remat/peak_hbm/t.none"] == 1500

    def test_cost_analysis_off_resolves_off(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_COST_ANALYSIS", "0")

        def boom(policy):
            raise AssertionError("must not lower with cost analysis off")

        assert remat_policy.resolve("t.off", boom) == "off"

    def test_hbm_capacity_env_override(self, monkeypatch):
        from paddle_tpu.profiler.xla_cost import hbm_capacity_bytes

        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "123456")
        assert hbm_capacity_bytes() == 123456
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "not-a-number")
        assert hbm_capacity_bytes() > 0  # invalid override ignored


class TestRematEndToEnd:
    def _mlp_step(self, remat="off", seed=7):
        import paddle_tpu as paddle
        from paddle_tpu import nn

        paddle.seed(seed)
        layers = []
        for _ in range(4):
            layers += [nn.Linear(64, 64), nn.ReLU()]
        layers += [nn.Linear(64, 10)]
        net = nn.Sequential(*layers)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        return paddle.jit.TrainStep(net, loss_fn=nn.CrossEntropyLoss(),
                                    optimizer=opt, remat=remat)

    def test_train_step_auto_resolves_and_trains(self, monkeypatch, rng):
        x = rng.randn(32, 64).astype(np.float32)
        y = rng.randint(0, 10, 32).astype(np.int64)
        off_cost = self._mlp_step().lower_cost("off", (x,), (y,))
        assert off_cost is not None and off_cost["peak_hbm_bytes"] > 0
        # pin the budget below the no-remat peak: the ladder MUST engage
        monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES",
                           str(max(int(off_cost["peak_hbm_bytes"] * 0.6), 1)))
        monkeypatch.setenv("PADDLE_TPU_REMAT_BUDGET_FRAC", "1.0")
        step = self._mlp_step(remat="auto")
        losses = [float(step((x,), (y,)).numpy()) for _ in range(3)]
        assert all(np.isfinite(losses))
        assert losses[2] < losses[0]  # it still learns
        scal = get_telemetry().scalars()
        assert "gauge/remat/jit.train_step" in scal
        auto_peak = scal["gauge/remat/peak_hbm/jit.train_step"]
        assert 0 < auto_peak <= off_cost["peak_hbm_bytes"]

    def test_train_step_explicit_policies_match_off_losses(self, rng):
        # remat changes WHAT is saved, never the math: first-step losses
        # agree bitwise-ish across policies
        x = rng.randn(16, 64).astype(np.float32)
        y = rng.randint(0, 10, 16).astype(np.int64)
        base = float(self._mlp_step("off")((x,), (y,)).numpy())
        for policy in ("full", "dots", "nothing"):
            lp = float(self._mlp_step(policy)((x,), (y,)).numpy())
            assert abs(lp - base) < 1e-5, (policy, lp, base)

    def test_fleet_legacy_recompute_maps_and_lower_cost_probes(self, rng):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        paddle.seed(7)
        net = nn.Sequential(nn.Linear(16, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        eng = ParallelTrainStep(net, loss_fn=nn.CrossEntropyLoss(),
                                optimizer=opt, mesh=mesh, recompute="dots")
        assert eng._remat == "dots"  # legacy vocabulary routed through
        x = rng.randn(8, 16).astype(np.float32)
        y = rng.randint(0, 4, 8).astype(np.int64)
        cost = eng.lower_cost("nothing", (x,), (y,))
        assert cost is not None and cost["peak_hbm_bytes"] > 0
        assert np.isfinite(float(eng((x,), (y,)).numpy()))

    def test_fleet_remat_auto_publishes_gauges(self, rng):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        paddle.seed(7)
        net = nn.Sequential(nn.Linear(16, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        eng = ParallelTrainStep(net, loss_fn=nn.CrossEntropyLoss(),
                                optimizer=opt, mesh=mesh, remat="auto")
        x = rng.randn(8, 16).astype(np.float32)
        y = rng.randint(0, 4, 8).astype(np.int64)
        assert np.isfinite(float(eng((x,), (y,)).numpy()))
        assert np.isfinite(float(eng((x,), (y,)).numpy()))
        scal = get_telemetry().scalars()
        assert "gauge/remat/fleet.train_step" in scal
        assert scal["gauge/remat/peak_hbm/fleet.train_step"] > 0


# ---------------------------------------------------------------------------
# tools/check_attribution.py: the tier gate
# ---------------------------------------------------------------------------
def _bench_record(scalars):
    return json.dumps({"ts": 1.0, "step": 0, "tag": "bench/cfg",
                       "scalars": scalars}) + "\n"


class TestTierGate:
    BASE = {"gauge/compile/flops": 1e9, "gauge/compile/peak_hbm_bytes": 1e6,
            "gauge/mfu": 42.0}

    def test_attention_bearing_record_with_verdict_passes(self, tmp_path):
        import tools.check_attribution as gate

        p = tmp_path / "t.jsonl"
        p.write_text(_bench_record({
            **self.BASE, "counter/attn/calls": 12,
            "gauge/attn/tier.L8192.d64.c": 0,
            "counter/attn/tier_fallbacks": 0}))
        assert gate.main([str(p)]) == 0

    def test_non_attention_record_needs_no_tier(self, tmp_path):
        import tools.check_attribution as gate

        p = tmp_path / "t.jsonl"
        p.write_text(_bench_record(self.BASE))
        assert gate.main([str(p)]) == 0

    def test_missing_tier_verdict_fails(self, tmp_path):
        import tools.check_attribution as gate

        p = tmp_path / "t.jsonl"
        p.write_text(_bench_record({**self.BASE, "counter/attn/calls": 12}))
        assert gate.main([str(p)]) == 1

    def test_nonzero_fallbacks_fail(self, tmp_path):
        import tools.check_attribution as gate

        p = tmp_path / "t.jsonl"
        p.write_text(_bench_record({
            **self.BASE, "counter/attn/calls": 12,
            "gauge/attn/tier.L8192.d64.c": 3,
            "counter/attn/tier_fallbacks": 2}))
        assert gate.main([str(p)]) == 1

    def test_negative_tier_id_fails(self, tmp_path):
        import tools.check_attribution as gate

        p = tmp_path / "t.jsonl"
        p.write_text(_bench_record({
            **self.BASE, "counter/attn/calls": 1,
            "gauge/attn/tier.L64.d8.c": -1,
            "counter/attn/tier_fallbacks": 0}))
        assert gate.main([str(p)]) == 1
