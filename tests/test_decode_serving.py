"""Token-level LLM serving (ISSUE 12): paged KV-cache pool accounting,
paged-attention tier parity (+ int8 storage), decode-step continuous
batching with chunked-prefill admission, speculative decoding, and
drain-mid-generation with every request terminal exactly once and zero
leaked KV blocks."""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (GenRequest, KVCacheConfig,
                                          KVCachePool, RequestStatus,
                                          TokenServeConfig,
                                          TokenServingEngine,
                                          dense_greedy_reference,
                                          run_generation_streams)
from paddle_tpu.inference.serving.decode import _pool_config
from paddle_tpu.inference.serving.loadgen import summarize_generation
from paddle_tpu.jit.functionalize import get_params
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import tier_policy
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.quant import dequantize_kv, quantize_kv
from paddle_tpu.resilience.inject import clear_injector
from paddle_tpu.text.models.gpt import (GPTConfig, GPTForCausalLM,
                                        gpt_decode_fns)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    clear_injector()
    get_telemetry().reset()
    yield
    clear_injector()


def tiny_model(seed=0, **kw):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=128,
                    hidden_dropout=0.0, attention_dropout=0.0, **kw)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def tiny_draft(seed=3):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=96, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=128,
                    hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def gpt_pool(model, kv_dtype="float32", blocks=16, block=8):
    """A pool in the layout the model names for this storage type."""
    return KVCachePool(_pool_config(model.decode_spec(kv_dtype), blocks,
                                    block, kv_dtype, 0))


def make_engine(model=None, draft=None, **kw):
    model = model or tiny_model()
    defaults = dict(capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
                    kv_blocks=48, kv_block_size=8, max_seq_len=96)
    defaults.update(kw)
    return TokenServingEngine(model, TokenServeConfig(**defaults),
                              draft_model=draft), model


# ---------------------------------------------------------------------------
# Recurrent-state slots beside the pages (one cache manager)
# ---------------------------------------------------------------------------
STATE = {"ssm": ((2, 4, 8), "float32"), "conv": ((3, 12), "float32")}


def state_pool(**kw):
    d = dict(num_layers=2, num_heads=4, head_dim=8, num_kv_heads=2,
             num_blocks=8, block_size=4, layout="per_layer", state=STATE,
             state_slots=2)
    d.update(kw)
    return KVCachePool(KVCacheConfig(**d))


def falcon_engine(**kw):
    from paddle_tpu.text.models.falcon_h1 import (FalconH1ForCausalLM,
                                                  falcon_h1_tiny)

    paddle.seed(4)
    model = FalconH1ForCausalLM(falcon_h1_tiny(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, mamba_d_ssm=32, mamba_n_heads=2))
    model.eval()
    defaults = dict(capacity=16, decode_buckets=(1, 2), prefill_chunk=8,
                    kv_blocks=48, kv_block_size=8, max_seq_len=96)
    defaults.update(kw)
    return TokenServingEngine(model, TokenServeConfig(**defaults))


class TestStateSlots:
    def test_leaves_and_layout(self):
        pool = state_pool()
        # a layer's pages of its own, the key heads flattened; slot 0 is
        # the scratch slot
        assert len(pool.pages["k"]) == 2
        assert pool.pages["k"][0].shape == (8, 4, 16)
        assert pool.pages["ssm"][1].shape == (3, 2, 4, 8)
        assert pool.pages["conv"][0].shape == (3, 3, 12)
        with pytest.raises(ValueError, match="int8"):
            state_pool(dtype="int8")
        with pytest.raises(ValueError, match="state_slots"):
            state_pool(state_slots=0)

    def test_slots_in_the_leak_ledger(self):
        tel = get_telemetry()
        pool = state_pool()
        assert pool.slot(7) == 0  # holds none: the scratch slot
        assert pool.ensure(7, 5) and pool.ensure(8, 3)
        assert {pool.slot(7), pool.slot(8)} == {1, 2}
        assert pool.ensure(7, 9)  # growing keeps the slot
        acct = pool.accounting()
        assert acct["used_slots"] == 2 and acct["slot_owners"] == [7, 8]
        assert pool.state_occupancy() == 1.0
        assert tel.snapshot()["gauges"]["serve/state_slots_used"] == 2
        # blocks are left, slots are not: nothing is grabbed
        used = pool.used_blocks
        assert not pool.ensure(9, 2)
        assert pool.used_blocks == used and pool.owned(9) == []
        pool.release(7)
        assert pool.ensure(9, 2) and pool.slot(9) in (1, 2)
        pool.release(8)
        pool.release(9)
        pool.release(9)  # idempotent
        acct = pool.accounting()
        assert acct["leaked_slots"] == 0 and acct["slot_owners"] == []
        assert acct["leaked_blocks"] == 0
        gauges = tel.snapshot()["gauges"]
        assert gauges["serve/state_slots_total"] == 2
        assert gauges["serve/state_occupancy"] == 0.0

    def test_a_pool_without_state_has_no_slots(self):
        pool = KVCachePool(KVCacheConfig(2, 2, 8, num_blocks=8,
                                         block_size=4))
        assert pool.ensure(1, 4) and pool.slot(1) == 0
        assert "leaked_slots" not in pool.accounting()
        assert pool.state_occupancy() == 0.0

    @pytest.mark.parametrize("ending", ["ok", "deadline", "drained",
                                        "rejected"])
    def test_slots_go_back_through_finish_on_every_terminal_status(
            self, ending):
        kw = {"rejected": dict(capacity=1, max_running=1,
                               decode_buckets=(1,)),
              "drained": dict(drain_grace_s=0.0)}.get(ending, {})
        eng = falcon_engine(**kw)
        eng.start()
        try:
            prompt = np.arange(9, dtype=np.int32)
            if ending == "ok":
                reqs = [eng.submit(prompt, max_new_tokens=4)]
            elif ending == "deadline":
                reqs = [eng.submit(prompt, max_new_tokens=80,
                                   deadline_s=0.05)]
            elif ending == "drained":
                reqs = [eng.submit(prompt, max_new_tokens=80)
                        for _ in range(2)]
                while not any(r.generated for r in reqs):
                    time.sleep(0.01)
                assert eng.pool.accounting()["used_slots"] >= 1
            else:
                reqs = [eng.submit(prompt, max_new_tokens=30)
                        for _ in range(10)]
                assert any(r.status == RequestStatus.REJECTED for r in reqs)
            if ending != "drained":
                for r in reqs:
                    assert r.wait(300)
        finally:
            acct = eng.shutdown()
        statuses = {r.status for r in reqs}
        if ending == "ok":
            assert statuses == {RequestStatus.OK}
        elif ending == "drained":
            assert RequestStatus.DRAINED in statuses
        assert acct["unaccounted"] == [] and acct["double_terminal"] == 0
        kv = eng.kv_accounting()
        assert kv["leaked_slots"] == 0 and kv["slot_owners"] == []
        assert kv["leaked_blocks"] == 0 and kv["owners"] == []


# ---------------------------------------------------------------------------
# KV cache pool
# ---------------------------------------------------------------------------
class TestKVCachePool:
    def cfg(self, **kw):
        d = dict(num_layers=2, num_heads=2, head_dim=8, num_blocks=8,
                 block_size=4)
        d.update(kw)
        return KVCacheConfig(**d)

    def test_alloc_free_accounting(self):
        pool = KVCachePool(self.cfg())
        assert pool.config.usable_blocks == 7  # page 0 is scratch
        assert pool.ensure(1, 9)  # 3 blocks of 4
        assert pool.used_blocks == 3
        assert pool.ensure(1, 9)  # idempotent growth
        assert pool.used_blocks == 3
        assert pool.ensure(2, 4)
        assert pool.used_blocks == 4
        assert pool.release(1) == 3
        assert pool.release(1) == 0  # idempotent
        assert pool.release(2) == 1
        acct = pool.accounting()
        assert acct["leaked_blocks"] == 0 and acct["owners"] == []

    def test_no_partial_grab_on_exhaustion(self):
        pool = KVCachePool(self.cfg(num_blocks=4))  # 3 usable
        assert pool.ensure(1, 8)  # 2 blocks
        assert not pool.ensure(2, 8)  # needs 2, only 1 free: all-or-nothing
        assert pool.used_blocks == 2
        assert pool.ensure(2, 4)  # 1 block still fits

    def test_scratch_never_allocated(self):
        pool = KVCachePool(self.cfg())
        pool.ensure(1, 28)  # every usable block
        assert 0 not in pool.owned(1)
        table = pool.block_table(1, 7)
        assert 0 not in table

    def test_block_table_pads_with_scratch(self):
        pool = KVCachePool(self.cfg())
        pool.ensure(9, 5)  # 2 blocks
        t = pool.block_table(9, 6)
        assert t.shape == (6,)
        assert (t[2:] == 0).all()

    def test_telemetry_counters_and_occupancy(self):
        tel = get_telemetry()
        pool = KVCachePool(self.cfg())
        pool.ensure(1, 12)
        pool.release(1)
        snap = tel.snapshot()
        assert snap["counters"]["serve/kv_blocks_alloc"] == 3
        assert snap["counters"]["serve/kv_blocks_free"] == 3
        assert snap["gauges"]["serve/kv_occupancy"] == 0.0
        assert snap["gauges"]["serve/kv_blocks_total"] == 7

    def test_int8_pool_carries_scales(self):
        pool = KVCachePool(self.cfg(dtype="int8"))
        assert pool.pages["k"].dtype == jnp.int8
        assert pool.pages["k_scale"].shape == pool.pages["k"].shape[:-1]


class TestKVQuant:
    def test_roundtrip_close(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 3, 2, 16).astype(np.float32))
        q, s = quantize_kv(x)
        back = dequantize_kv(q, s)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert s.shape == x.shape[:-1]
        # per-head absmax int8: worst-case error is scale/2 per element
        err = np.abs(np.asarray(back) - np.asarray(x))
        bound = np.asarray(s)[..., None] * 0.51
        assert (err <= bound).all()

    def test_zero_slab_safe(self):
        q, s = quantize_kv(jnp.zeros((2, 2, 4)))
        assert np.asarray(s).min() > 0  # floored scale: no div-by-zero
        assert np.asarray(dequantize_kv(q, s)).max() == 0


# ---------------------------------------------------------------------------
# Paged attention tiers
# ---------------------------------------------------------------------------
class TestPagedAttention:
    def setup_pages(self, dtype=np.float32, quantized=False):
        rng = np.random.RandomState(0)
        B, T, H, D, bs, M = 2, 3, 2, 8, 4, 5
        N = 2 * M + 1
        k = jnp.asarray(rng.randn(N, bs, H, D).astype(dtype))
        v = jnp.asarray(rng.randn(N, bs, H, D).astype(dtype))
        tables = jnp.asarray(
            np.array([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], np.int32))
        kv_lens = jnp.asarray(np.array([11, 17], np.int32))
        q = jnp.asarray(rng.randn(B, T, H, D).astype(dtype))
        q_pos = jnp.asarray(np.stack([np.arange(8, 11),
                                      np.arange(14, 17)]).astype(np.int32))
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            return q, kq, vq, tables, q_pos, kv_lens, ks, vs
        return q, k, v, tables, q_pos, kv_lens, None, None

    def test_gather_vs_scan_parity(self):
        args = self.setup_pages()
        o1 = np.asarray(att._paged_gather_impl(*args))
        o2 = np.asarray(att._paged_scan_impl(*args))
        np.testing.assert_allclose(o1, o2, atol=1e-5)

    @pytest.mark.parametrize("T", [1, 3])
    @pytest.mark.parametrize("H, D, group", [(4, 64, 2), (8, 32, 4),
                                             (3, 64, 0), (2, 128, 0)])
    def test_scan_over_flat_pages_a_lane_group_at_a_time(self, H, D, group,
                                                         T):
        """A decode step over flat float pages of heads narrower than 128
        lanes reads them a lane group at a time (GPT's 16 heads of 64: two
        a group), never reshaped to heads of 64; a chunk of several queries
        keeps the heads as an axis. Either way the result is the scan's
        over the same pages with the heads as an axis, and the gather
        tier's."""
        rng = np.random.RandomState(3)
        B, bs, M, N = 3, 4, 5, 16
        k = jnp.asarray(rng.randn(N, bs, H * D).astype(np.float32))
        v = jnp.asarray(rng.randn(N, bs, H * D).astype(np.float32))
        assert att._lane_group(T, H, D, k, None) == (group if T == 1 else 0)
        assert att._lane_group(T, H, D, k.reshape(N, bs, H, D), None) == 0
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        tables = jnp.asarray(rng.permutation(np.arange(1, N))[:B * M]
                             .reshape(B, M).astype(np.int32))
        kv_lens = jnp.asarray(np.array([7, 20, 13], np.int32))
        q_pos = kv_lens[:, None] - T + jnp.arange(T, dtype=jnp.int32)[None]
        flat = np.asarray(att._paged_scan_impl(q, k, v, tables, q_pos,
                                               kv_lens))
        by_heads = np.asarray(att._paged_scan_impl(
            q, k.reshape(N, bs, H, D), v.reshape(N, bs, H, D), tables,
            q_pos, kv_lens))
        gathered = np.asarray(att._paged_gather_impl(q, k, v, tables, q_pos,
                                                     kv_lens))
        np.testing.assert_allclose(flat, by_heads, atol=2e-6)
        np.testing.assert_allclose(flat, gathered, atol=2e-6)

    # the scan's forms: (query heads, key heads, head width, pages flat,
    # int8 pages); at T = 1 the first takes lane groups (two heads of 64)
    SCAN_FORMS = {"lane_group": (4, 4, 64, True, False),
                  "heads_axis": (2, 2, 8, False, False),
                  "grouped": (4, 2, 8, True, False),
                  "int8": (2, 2, 8, False, True)}

    def walk_case(self, form, T, seed=11):
        """Rows of 7 and 13 cached positions and a padded row (kv_len 0,
        the scratch page's table) over a table of 8 slots of 4: the longest
        row fills 4 slots. Each live row's pages are its own; every slot
        past the fourth points at a page of its own too."""
        H, Hkv, D, flat, int8 = self.SCAN_FORMS[form]
        rng = np.random.RandomState(seed)
        bs, M = 4, 8
        N = 1 + 3 * M
        shape = (N, bs, Hkv * D) if flat else (N, bs, Hkv, D)
        k = jnp.asarray(rng.randn(*shape).astype(np.float32))
        v = jnp.asarray(rng.randn(*shape).astype(np.float32))
        tables = np.zeros((3, M), np.int32)
        tables[:2] = 1 + rng.permutation(2 * M).reshape(2, M)
        kv_lens = np.array([7, 13, 0], np.int32)
        q_pos = np.maximum(kv_lens[:, None] - T, 0) + np.arange(T)[None]
        q = jnp.asarray(rng.randn(3, T, H, D).astype(np.float32))
        ks = vs = None
        if int8:
            k, ks = quantize_kv(k)
            v, vs = quantize_kv(v)
        return (q, k, v, tables, q_pos.astype(np.int32), kv_lens, ks, vs,
                N, M)

    @staticmethod
    def whole_walk(q, k, v, tables, q_pos, kv_lens, ks, vs, N, M):
        """The same call with a row of the table's full length added: the
        walk then goes over every slot, as the parent's scan did."""
        T = q.shape[1]
        bs = k.shape[1]
        full = np.arange(N - M, N, dtype=np.int32)[None]
        return att._paged_scan_impl(
            jnp.concatenate([q, q[:1]]), k, v,
            jnp.asarray(np.concatenate([tables, full])),
            jnp.asarray(np.concatenate(
                [q_pos, (M * bs - T + np.arange(T, dtype=np.int32))[None]])),
            jnp.asarray(np.append(kv_lens, np.int32(M * bs))), ks, vs)[:3]

    @pytest.mark.parametrize("T", [1, 5])
    @pytest.mark.parametrize("form", list(SCAN_FORMS))
    def test_skipped_slots_change_nothing(self, form, T):
        """The scan stops at the longest row's last slot (4 of 8 here):
        the live rows are bit for bit what a walk over the whole table
        gives, and within rounding the gather tier's."""
        (q, k, v, tables, q_pos, kv_lens, ks, vs, N, M) = case = \
            self.walk_case(form, T)
        H, _, D, _, _ = self.SCAN_FORMS[form]
        assert att._lane_group(T, H, D, k, ks) == (
            2 if form == "lane_group" and T == 1 else 0)
        assert int(att.table_slots_live(kv_lens, k.shape[1], M,
                                        xp=np)) == 4
        args = (q, k, v, jnp.asarray(tables), jnp.asarray(q_pos),
                jnp.asarray(kv_lens), ks, vs)
        bounded = np.asarray(att._paged_scan_impl(*args))
        whole = np.asarray(self.whole_walk(*case))
        np.testing.assert_array_equal(bounded[:2], whole[:2])
        gathered = np.asarray(att._paged_gather_impl(*args))
        np.testing.assert_allclose(bounded[:2], gathered[:2], atol=1e-5)

    @pytest.mark.parametrize("form", list(SCAN_FORMS))
    def test_pages_past_the_longest_row_are_never_read(self, form):
        """Every page a table points at past the longest row's last slot
        holds NaN: a masked slot the walk still visited would carry it
        into the output (0 * NaN), so the live rows' being finite, and
        equal to the clean call's, says no such slot was visited."""
        (q, k, v, tables, q_pos, kv_lens, ks, vs, _, M) = \
            self.walk_case(form, 1)
        live = int(att.table_slots_live(kv_lens, k.shape[1], M, xp=np))
        past = np.unique(tables[:, live:])
        past = past[past != 0]  # the scratch page lies inside the walk
        assert past.size == 2 * (M - live)
        if ks is None:
            k_bad, v_bad = k.at[past].set(np.nan), v.at[past].set(np.nan)
            ks_bad, vs_bad = ks, vs
        else:  # int8 pages: a NaN scale makes the page NaN
            k_bad, v_bad = k, v
            ks_bad, vs_bad = ks.at[past].set(np.nan), vs.at[past].set(np.nan)
        rest = (jnp.asarray(tables), jnp.asarray(q_pos), jnp.asarray(kv_lens))
        clean = np.asarray(att._paged_scan_impl(q, k, v, *rest, ks, vs))
        poisoned = np.asarray(att._paged_scan_impl(q, k_bad, v_bad, *rest,
                                                   ks_bad, vs_bad))
        assert np.isfinite(poisoned[:2]).all()
        np.testing.assert_array_equal(poisoned[:2], clean[:2])

    def test_vs_dense_reference(self):
        import math
        q, k, v, tables, q_pos, kv_lens, _, _ = self.setup_pages()
        out = np.asarray(att._paged_gather_impl(q, k, v, tables, q_pos,
                                                kv_lens))
        kd = np.asarray(k)[np.asarray(tables)[0]].reshape(20, 2, 8)
        vd = np.asarray(v)[np.asarray(tables)[0]].reshape(20, 2, 8)
        qp = int(np.asarray(q_pos)[0, 1])  # query at position 9
        s = np.einsum("hd,khd->hk", np.asarray(q)[0, 1],
                      kd[:qp + 1]) / math.sqrt(8)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hk,khd->hd", p, vd[:qp + 1])
        np.testing.assert_allclose(out[0, 1], ref, atol=1e-5)

    def test_int8_close_to_f32(self):
        f32 = self.setup_pages()
        i8 = self.setup_pages(quantized=True)
        o_f = np.asarray(att._paged_gather_impl(*f32))
        o_q = np.asarray(att._paged_gather_impl(*i8))
        assert np.max(np.abs(o_f - o_q)) < 0.05
        o_qs = np.asarray(att._paged_scan_impl(*i8))
        np.testing.assert_allclose(o_q, o_qs, atol=1e-5)

    def test_stale_slots_masked(self):
        """Entries past kv_len (rejected speculative writes, padded table
        slots) must not leak into the softmax."""
        q, k, v, tables, q_pos, kv_lens, _, _ = self.setup_pages()
        poisoned = k.at[np.asarray(tables)[0, 3:]].set(1e3)  # beyond len 11
        o_clean = np.asarray(att._paged_gather_impl(q, k, v, tables, q_pos,
                                                    kv_lens))
        o_pois = np.asarray(att._paged_gather_impl(q, poisoned, v, tables,
                                                   q_pos, kv_lens))
        np.testing.assert_allclose(o_clean[0], o_pois[0], atol=1e-6)

    def test_dispatch_publishes_tier_gauge(self):
        args = self.setup_pages()
        att.paged_attention(*args[:6])
        snap = get_telemetry().snapshot()
        keys = [k for k in snap["gauges"] if k.startswith("attn/tier.paged")]
        assert keys, snap["gauges"].keys()
        assert snap["gauges"][keys[0]] in (
            tier_policy.TIER_IDS["paged_gather"],
            tier_policy.TIER_IDS["paged_scan"])
        assert snap["counters"].get("attn/tier_fallbacks", 0) == 0


class TestPagedTierPolicy:
    def test_forced_tier_wins(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "paged_scan")
        assert tier_policy.select_paged(1, 2, 8, 4, 16, jnp.float32,
                                        False) == "paged_scan"

    def test_heuristic_crossover(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ATTN_PAGED_POLICY", raising=False)
        # CPU default = heuristic: gather for small contexts, scan past
        # the materialization knee
        assert tier_policy.select_paged(1, 2, 8, 8, 16, jnp.float32,
                                        False) == "paged_gather"
        assert tier_policy.select_paged(1, 2, 8, 512, 16, jnp.float32,
                                        False) == "paged_scan"

    def test_bench_mode_measures_once_and_caches(self, monkeypatch,
                                                 tmp_path):
        cache = str(tmp_path / "tiers.json")
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "bench")
        monkeypatch.setenv("PADDLE_TPU_ATTN_TIER_CACHE", cache)
        tier_policy.reset()
        tel = get_telemetry()
        t1 = tier_policy.select_paged(1, 2, 8, 4, 4, jnp.float32, False)
        benches = tel.snapshot()["counters"].get("attn/tier_bench", 0)
        t2 = tier_policy.select_paged(1, 2, 8, 4, 4, jnp.float32, False)
        assert t1 == t2 and t1 in tier_policy.PAGED_TIERS
        assert tel.snapshot()["counters"].get("attn/tier_bench", 0) \
            == benches  # pure cache hit, no re-measure
        # restart-warm: a fresh registry re-reads the persisted verdict
        with open(cache) as f:
            data = json.load(f)
        assert any(":paged:" in k for k in data)
        tier_policy.reset()
        t3 = tier_policy.select_paged(1, 2, 8, 4, 4, jnp.float32, False)
        assert t3 == t1
        assert tel.snapshot()["counters"].get("attn/tier_bench", 0) \
            == benches

    def test_unknown_policy_falls_back(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "warp-drive")
        assert tier_policy.policy_mode() == "heuristic"


# ---------------------------------------------------------------------------
# GPT paged forward
# ---------------------------------------------------------------------------
class TestGPTDecodeFns:
    def run_paged_prefill(self, model, prompt, kv_dtype="float32", C=8):
        fwd = gpt_decode_fns(model.config, kv_dtype)
        pool = gpt_pool(model, kv_dtype)
        n = len(prompt)
        pool.ensure(1, n)
        table = jnp.asarray(pool.block_table(1, 8)[None])
        pages = pool.pages
        params = get_params(model)
        rows = []
        jfwd = jax.jit(fwd)
        for c0 in range(0, n, C):
            part = prompt[c0:c0 + C]
            pad = C - len(part)
            toks = np.concatenate([part, np.zeros(pad, np.int32)])[None]
            qpos = (c0 + np.arange(C, dtype=np.int32))[None]
            lens = np.asarray([min(c0 + C, n)], np.int32)
            logits, pages = jfwd(params, jnp.asarray(toks),
                                 jnp.asarray(qpos), pages, table,
                                 jnp.asarray(lens))
            rows.append(np.asarray(logits)[0, :C - pad if pad else C])
        return np.concatenate(rows, axis=0)

    def test_chunked_prefill_matches_dense_forward(self):
        model = tiny_model()
        rng = np.random.RandomState(1)
        prompt = rng.randint(0, 96, 19).astype(np.int32)
        paged = self.run_paged_prefill(model, prompt)
        ref = np.asarray(model(
            paddle.Tensor(prompt[None].astype(np.int64))).numpy())[0]
        np.testing.assert_allclose(paged, ref, atol=1e-4)
        assert np.array_equal(paged.argmax(-1), ref.argmax(-1))

    @pytest.mark.parametrize("kv_dtype, layout", [
        ("float32", "per_layer"), ("bfloat16", "per_layer"),
        ("int8", "stacked")])
    def test_decode_spec_names_the_layout_by_storage_type(self, kv_dtype,
                                                          layout):
        """Float pages lie a layer to an array; int8 pages keep a scale a
        token-head and stay stacked (which ``KVCacheConfig`` insists on)."""
        model = tiny_model()
        assert model.decode_spec(kv_dtype)["kv_layout"] == layout
        assert gpt_pool(model, kv_dtype).config.layout == layout

    @pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
    def test_float_pages_are_a_layers_array_with_the_heads_flat(self,
                                                                kv_dtype):
        model = tiny_model()
        c = model.config
        pool = gpt_pool(model, kv_dtype)
        assert set(pool.pages) == {"k", "v"}
        for kv in ("k", "v"):
            assert isinstance(pool.pages[kv], tuple)
            assert len(pool.pages[kv]) == c.num_layers
            for layer in pool.pages[kv]:
                assert layer.shape == (16, 8, c.hidden_size)
                assert layer.dtype == jnp.dtype(kv_dtype)

    def test_a_step_returns_the_pages_in_the_layout_it_got(self):
        """The engine hands a step's pages to the next step: a tuple of
        arrays a layer goes in and comes out, each as wide as it was."""
        model = tiny_model()
        pool = gpt_pool(model)
        zeros = jnp.zeros((1, 8), jnp.int32)  # tokens, positions, table
        _, pages = jax.jit(gpt_decode_fns(model.config, "float32"))(
            get_params(model), zeros, zeros, pool.pages, zeros,
            jnp.zeros((1,), jnp.int32))
        assert jax.tree_util.tree_structure(pages) \
            == jax.tree_util.tree_structure(pool.pages)
        assert [a.shape for a in jax.tree_util.tree_leaves(pages)] \
            == [a.shape for a in jax.tree_util.tree_leaves(pool.pages)]

    def test_int8_kv_close_to_bf16_reference(self):
        """ISSUE satellite: int8 KV storage parity against a wider
        reference — logits must stay close enough that greedy decisions
        survive on all but near-tie positions."""
        model = tiny_model()
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, 96, 17).astype(np.int32)
        ref16 = self.run_paged_prefill(model, prompt, kv_dtype="bfloat16")
        got8 = self.run_paged_prefill(model, prompt, kv_dtype="int8")
        # int8-vs-bf16 logit drift bounded well inside the logit RANGE
        span = ref16.max() - ref16.min()
        assert np.max(np.abs(got8 - ref16)) < 0.05 * float(span)


# ---------------------------------------------------------------------------
# Engine: continuous batching, parity, chunked prefill, eviction, spec
# ---------------------------------------------------------------------------
class TestTokenEngine:
    @pytest.mark.parametrize("tier", ["paged_gather", "paged_scan"])
    def test_greedy_parity_with_dense_reference(self, monkeypatch, tier):
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", tier)
        eng, model = make_engine()
        eng.start()
        try:
            rng = np.random.RandomState(7)
            prompts = [rng.randint(0, 96, n).astype(np.int32)
                       for n in (5, 19, 11, 3)]
            reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
            for r in reqs:
                assert r.wait(120)
            for p, r in zip(prompts, reqs):
                assert r.status == RequestStatus.OK
                assert [int(t) for t in r.outputs[0]] \
                    == dense_greedy_reference(model, p, 10)
            gauges = get_telemetry().snapshot()["gauges"]
            assert {v for k, v in gauges.items()
                    if k.startswith("attn/tier.paged")} \
                == {tier_policy.TIER_IDS[tier]}
        finally:
            acct = eng.shutdown()
        assert acct["unaccounted"] == [] and acct["double_terminal"] == 0
        assert eng.kv_accounting()["leaked_blocks"] == 0

    def test_eos_stops_generation(self):
        eng, model = make_engine()
        eng.start()
        try:
            rng = np.random.RandomState(7)
            p = rng.randint(0, 96, 5).astype(np.int32)
            ref = dense_greedy_reference(model, p, 30)
            eos = ref[3]
            # generation stops AT the FIRST eos occurrence (inclusive) —
            # which may be before index 3 if the greedy stream repeats
            expected = ref[:ref.index(eos) + 1]
            r = eng.submit(p, max_new_tokens=30, eos_id=int(eos))
            assert r.wait(60)
            out = [int(t) for t in r.outputs[0]]
            assert out == expected
        finally:
            eng.shutdown()

    def test_ttft_tpot_stamped(self):
        eng, _ = make_engine()
        eng.start()
        try:
            r = eng.submit(np.arange(6, dtype=np.int32), max_new_tokens=8)
            assert r.wait(60)
            assert r.ttft_ms() is not None and r.ttft_ms() >= 0
            assert r.tpot_ms() is not None and r.tpot_ms() >= 0
            s = summarize_generation([r])
            assert s["tokens_generated"] == 8
            assert "ttft_p50_ms" in s and "tpot_p99_ms" in s
        finally:
            eng.shutdown()
        snap = get_telemetry().snapshot()
        assert "serve/ttft_ms" in snap["histograms"]
        assert "serve/tpot_ms" in snap["histograms"]

    def test_chunked_prefill_never_stalls_decodes(self):
        """A long prompt admitted while another sequence decodes enters
        chunk by chunk, one chunk per scheduler iteration: the running
        sequence finishes its WHOLE generation before the long prompt
        even produces a first token — decodes were never stalled behind
        the prefill."""
        eng, _ = make_engine(prefill_chunk=4, kv_blocks=64, max_seq_len=96,
                             max_running=2, decode_buckets=(1, 2))
        eng.start()
        try:
            rng = np.random.RandomState(5)
            # short first: 1 prefill chunk, then it decodes every round
            short_r = eng.submit(rng.randint(0, 96, 3).astype(np.int32),
                                 max_new_tokens=12)
            # long second: 20 prefill chunks, interleaved 1/iteration
            long_r = eng.submit(rng.randint(0, 96, 80).astype(np.int32),
                                max_new_tokens=4)
            assert long_r.wait(120) and short_r.wait(120)
            assert long_r.status == short_r.status == RequestStatus.OK
            # interleaving proof: short's 12 decode rounds all ran while
            # the long prompt was still chunking (≥ 20 iterations)
            assert short_r.finished_at < long_r.first_token_at
        finally:
            eng.shutdown()
        assert get_telemetry().counter_value("serve/prefill_chunks") >= 21

    def test_eviction_under_pool_pressure_keeps_parity(self):
        eng, model = make_engine(kv_blocks=9, kv_block_size=8,
                                 max_seq_len=48, decode_buckets=(1, 2, 4))
        eng.start()
        try:
            rng = np.random.RandomState(7)
            prompts = [rng.randint(0, 96, 20).astype(np.int32)
                       for _ in range(3)]
            reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
            for r in reqs:
                assert r.wait(180)
            for p, r in zip(prompts, reqs):
                assert r.status == RequestStatus.OK
                assert [int(t) for t in r.outputs[0]] \
                    == dense_greedy_reference(model, p, 16)
        finally:
            eng.shutdown()
        assert get_telemetry().counter_value("serve/kv_evictions") >= 1
        assert eng.kv_accounting()["leaked_blocks"] == 0

    def test_eviction_respects_batch_exclusion(self):
        """A sequence already accepted into the round's batch must never
        be evicted by a later member's allocation — its feed was decided
        from a cache cursor the eviction would zero mid-round."""
        eng, _ = make_engine(kv_blocks=5, kv_block_size=8, max_seq_len=32)
        sched = eng._scheduler
        a = GenRequest(1, np.arange(4, dtype=np.int32), 4)
        b = GenRequest(2, np.arange(4, dtype=np.int32), 4)
        assert eng._pool.ensure(a.id, 32)  # a holds every usable block
        a.ncache = 16
        sched._running.extend([a, b])
        # excluded: b cannot steal from the in-batch member — it waits
        assert not sched._ensure_blocks(b, 8, exclude=[a])
        assert a.ncache == 16 and eng._pool.owned(a.id)
        # unexcluded (a is merely running): b may evict it
        assert sched._ensure_blocks(b, 8)
        assert a.ncache == 0 and not eng._pool.owned(a.id)

    def test_tail_decode_protects_spec_group(self):
        """The plain-decode round the spec path runs for its
        near-max_seq_len tail must not evict already-ensured spec-group
        members (the cross-round variant of the exclusion above)."""
        eng, _ = make_engine(kv_blocks=5, kv_block_size=8, max_seq_len=32)
        sched = eng._scheduler
        a = GenRequest(1, np.arange(4, dtype=np.int32), 4)
        a.ncache = 16
        b = GenRequest(2, np.arange(4, dtype=np.int32), 4)
        b.ncache = 3  # pending == 1: decode-eligible tail member
        assert eng._pool.ensure(a.id, 32)  # a (the spec group) holds all
        sched._running.extend([a, b])
        sched._decode_round([b], protect=[a])
        # b could not allocate without evicting the protected member:
        # it waits a round; a's cursor and blocks are untouched
        assert a.ncache == 16 and eng._pool.owned(a.id)
        assert b.ncache == 3 and not eng._pool.owned(b.id)

    def test_table_slot_counters(self):
        """Each prefill chunk and each decode round counts the slots its
        longest row fills (what the paged scan walks) and the table's
        width: a table of 8 slots of 8, a prompt of 20 in chunks of 8 and
        one of 5, then one decode round over both, padded to a bucket of
        4."""
        eng, _ = make_engine(kv_block_size=8, max_seq_len=64,
                             decode_buckets=(4,))
        assert eng._table_width == 8
        sched, tel = eng._scheduler, get_telemetry()
        rng = np.random.RandomState(9)
        long_r = GenRequest(1, rng.randint(0, 96, 20).astype(np.int32), 4)
        short_r = GenRequest(2, rng.randint(0, 96, 5).astype(np.int32), 4)

        def added(run):
            before = [tel.counter_value(f"serve/table_slots{s}")
                      for s in ("_live", "")]
            run()
            return [tel.counter_value(f"serve/table_slots{s}") - b
                    for s, b in zip(("_live", ""), before)]

        try:
            # chunks cache 8, 16 and 20 positions: 1, 2 and 3 slots
            for live in (1, 2, 3):
                assert added(lambda: sched._prefill_chunk(long_r)) \
                    == [live, 8]
            assert added(lambda: sched._prefill_chunk(short_r)) == [1, 8]
            assert long_r.pending == short_r.pending == 1
            # rows of 21 and 6 positions and two padded rows of 0
            assert added(lambda: sched._decode_round([long_r, short_r])) \
                == [3, 8]
            assert tel.counter_value("serve/decode_steps") == 1
        finally:
            eng.shutdown()

    def test_submit_validation(self):
        eng, _ = make_engine()
        eng.start()
        try:
            with pytest.raises(ValueError):
                eng.submit(np.zeros((2, 2), np.int32))
            with pytest.raises(ValueError):
                eng.submit(np.asarray([1.5, 2.5]))
            with pytest.raises(ValueError):  # prompt + budget > max_seq_len
                eng.submit(np.arange(90, dtype=np.int32),
                           max_new_tokens=50)
        finally:
            eng.shutdown()

    def test_capacity_rejects_explicit(self):
        eng, _ = make_engine(capacity=1, max_running=1,
                             decode_buckets=(1,))
        eng.start()
        try:
            reqs = [eng.submit(np.arange(4, dtype=np.int32),
                               max_new_tokens=30) for _ in range(12)]
            shed = [r for r in reqs if r.status == RequestStatus.REJECTED]
            assert shed, "queue bound never shed"
            for r in reqs:
                r.wait(120)
        finally:
            acct = eng.shutdown()
        assert acct["unaccounted"] == [] and acct["double_terminal"] == 0

    def test_mid_generation_deadline_sheds_and_frees(self):
        eng, _ = make_engine()
        eng.start()
        try:
            r = eng.submit(np.arange(8, dtype=np.int32),
                           max_new_tokens=60, deadline_s=0.03)
            assert r.wait(60)
            assert r.status in (RequestStatus.DEADLINE_EXCEEDED,
                                RequestStatus.OK)
        finally:
            eng.shutdown()
        assert eng.kv_accounting()["leaked_blocks"] == 0

    def test_decode_compiles_bounded_by_buckets(self):
        eng, _ = make_engine(decode_buckets=(1, 2))
        eng.start()
        try:
            rng = np.random.RandomState(0)
            for _ in range(2):  # two waves, same shapes
                reqs = [eng.submit(rng.randint(0, 96, 4).astype(np.int32),
                                   max_new_tokens=6) for _ in range(2)]
                for r in reqs:
                    assert r.wait(60)
        finally:
            eng.shutdown()
        sched = eng._scheduler
        for b, fn in sched._decode_fns.items():
            assert fn.tracker.compiles <= 1, \
                f"decode bucket {b} recompiled: {fn.tracker.compiles}"
        if sched._prefill_fn is not None:
            assert sched._prefill_fn.tracker.compiles <= 1


class TestSpeculative:
    def test_spec_output_equals_plain_greedy(self):
        model = tiny_model()
        eng, _ = make_engine(model=model, draft=tiny_draft(), spec_k=3)
        eng.start()
        try:
            rng = np.random.RandomState(7)
            prompts = [rng.randint(0, 96, n).astype(np.int32)
                       for n in (5, 13)]
            reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
            for r in reqs:
                assert r.wait(120)
            for p, r in zip(prompts, reqs):
                assert [int(t) for t in r.outputs[0]] \
                    == dense_greedy_reference(model, p, 10)
        finally:
            eng.shutdown()
        snap = get_telemetry().snapshot()
        assert snap["counters"]["serve/spec_proposed"] > 0
        rate = snap["gauges"]["serve/spec_accept_rate"]
        assert 0.0 <= rate <= 1.0
        assert snap["counters"]["serve/spec_accepted"] \
            <= snap["counters"]["serve/spec_proposed"]
        kv = eng.kv_accounting()
        assert kv["leaked_blocks"] == 0
        assert kv["draft"]["leaked_blocks"] == 0

    def test_self_draft_accepts_everything(self):
        """Draft == target ⇒ every proposal verifies: acceptance 1.0 and
        far fewer verify steps than tokens."""
        model = tiny_model()
        eng, _ = make_engine(model=model, draft=model, spec_k=3)
        eng.start()
        try:
            r = eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=12)
            assert r.wait(120)
            assert r.status == RequestStatus.OK
            assert [int(t) for t in r.outputs[0]] \
                == dense_greedy_reference(model, np.arange(7), 12)
        finally:
            eng.shutdown()
        snap = get_telemetry().snapshot()
        assert snap["gauges"]["serve/spec_accept_rate"] == 1.0
        # 12 tokens in ceil((12-1)/4)+small rounds instead of 12 steps
        assert snap["counters"]["serve/decode_steps"] <= 5

    def test_spec_requires_draft(self):
        with pytest.raises(ValueError):
            make_engine(spec_k=2)

    def test_spec_at_max_seq_len_boundary(self):
        """A request whose prompt + budget lands EXACTLY on max_seq_len:
        speculative rounds must not write k tokens past the cap (block
        table / position overflow) — the tail of the generation falls
        back to the plain decode path and the output stays greedy-exact."""
        model = tiny_model()
        eng, _ = make_engine(model=model, draft=tiny_draft(), spec_k=3,
                             max_seq_len=32, kv_blocks=16, kv_block_size=8)
        eng.start()
        try:
            prompt = np.arange(16, dtype=np.int32)
            r = eng.submit(prompt, max_new_tokens=16)  # 16 + 16 == cap
            assert r.wait(120)
            assert r.status == RequestStatus.OK, (r.status, r.detail)
            assert [int(t) for t in r.outputs[0]] \
                == dense_greedy_reference(model, prompt, 16)
        finally:
            eng.shutdown()
        assert eng.kv_accounting()["leaked_blocks"] == 0
        assert eng.kv_accounting()["draft"]["leaked_blocks"] == 0


class TestLoadgenGeneration:
    def test_run_generation_streams_summary(self):
        eng, _ = make_engine()
        eng.start()
        try:
            out = run_generation_streams(
                eng, 2, 2, lambda k: np.arange(4 + k % 3, dtype=np.int32),
                max_new_tokens=5)
        finally:
            eng.shutdown()
        assert out["by_status"] == {"ok": 4}
        assert out["tokens_generated"] == 20
        assert out["tokens_per_s"] > 0
        assert out["streams"] == 2
        assert "ttft_p99_ms" in out and out["ttft_p99_ms"] >= 0
        assert "tpot_p50_ms" in out and out["tpot_p50_ms"] >= 0


# ---------------------------------------------------------------------------
# Drain mid-generation (subprocess SIGTERM) — ISSUE satellite
# ---------------------------------------------------------------------------
_DRAIN_WORKER = textwrap.dedent("""
    import json, os, signal, sys, threading, time
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTForCausalLM(cfg); model.eval()
    eng = TokenServingEngine(model, TokenServeConfig(
        capacity=16, decode_buckets=(1, 2, 4), max_running=4,
        prefill_chunk=8, kv_blocks=128, kv_block_size=8, max_seq_len=240,
        drain_grace_s=0.05))
    eng.install_preemption().start()

    rng = np.random.RandomState(0)
    # N streams with LONG generations; the SIGTERM fires the moment a
    # stream is observably MID-decode (state-triggered, not a wall-clock
    # guess), so the short grace guarantees genuinely-partial DRAINED
    # requests whatever the host speed
    reqs = [eng.submit(rng.randint(0, 96, 10).astype(np.int32),
                       max_new_tokens=200) for _ in range(6)]
    def fire():
        while not any(3 <= len(r.generated) < 150 for r in reqs):
            time.sleep(0.002)
        os.kill(os.getpid(), signal.SIGTERM)
    threading.Thread(target=fire, daemon=True).start()
    for r in reqs:
        r.wait(30.0)
    eng.wait_drained(20.0)
    acct = eng.accounting()
    out = {
        "acct": acct,
        "kv": eng.kv_accounting(),
        "drain_reason": eng.drain_reason,
        "statuses": {r.id: r.status for r in reqs},
        "n_generated": {r.id: len(r.generated) for r in reqs},
        "outputs_present": {r.id: r.outputs is not None for r in reqs},
    }
    with open(os.environ["OUT"], "w") as f:
        json.dump(out, f)
    eng.exit_if_preempted()
    sys.exit(3)  # preemption drain never happened
""")


class TestRequestTracing:
    def test_sampled_generation_timeline(self, monkeypatch):
        """ISSUE 13: a sampled generation request exports ONE
        self-contained timeline — queue → prefill chunks → decode steps
        → terminal — under one trace id."""
        from paddle_tpu.profiler import spans

        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1")
        spans.trace_store().clear()
        # one decode bucket: the timeline needs one compiled entry per
        # kind, not the full bucket ladder's compile bill
        eng, _ = make_engine(decode_buckets=(1,))
        eng.start()
        try:
            # prompt spans 2 prefill chunks (chunk=8), then decodes
            req = eng.submit(np.arange(1, 13, dtype=np.int32),
                             max_new_tokens=4)
            assert req.wait(60) and req.status == RequestStatus.OK
        finally:
            eng.shutdown()
        traces = [t for t in spans.trace_store().snapshot()
                  if t.req_id == req.id]
        assert len(traces) == 1
        names = [n for n, _t0, _d in traces[0].events]
        assert names[0] == "submit" and names[1] == "admit"
        assert "queue" in names
        assert sum(1 for n in names if n.startswith("prefill.c8")) >= 2
        assert any(n.startswith("decode.b") for n in names)
        assert names[-1] == "terminal:ok"
        # lifecycle order: all prefill slices precede the first decode
        assert max(i for i, n in enumerate(names)
                   if n.startswith("prefill.")) < \
            min(i for i, n in enumerate(names) if n.startswith("decode."))
        evs = traces[0].chrome_events(pid=1)
        assert len({e["args"]["trace_id"] for e in evs}) == 1
        spans.trace_store().clear()


class TestDrainMidGeneration:
    def test_sigterm_mid_decode_exits_77_no_leaks(self, tmp_path):
        """ISSUE satellite: subprocess SIGTERM while N streams are
        mid-decode → every request terminal exactly once (OK with partial
        text or DRAINED), exit 77, zero leaked KV blocks."""
        out_path = str(tmp_path / "out.json")
        worker = tmp_path / "worker.py"
        worker.write_text(_DRAIN_WORKER)
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "OUT": out_path,
               "PYTHONPATH": _REPO + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        env.pop("PADDLE_TPU_INJECT", None)
        r = subprocess.run([sys.executable, str(worker)], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 77, (r.returncode, r.stderr[-2000:])
        with open(out_path) as f:
            out = json.load(f)
        acct = out["acct"]
        assert out["drain_reason"] == "preempted"
        assert acct["unaccounted"] == []
        assert acct["double_terminal"] == 0
        assert acct["submitted"] == 6
        statuses = set(out["statuses"].values())
        assert statuses <= {"ok", "drained"}
        assert "drained" in statuses  # mid-decode SIGTERM + short grace
        # at least one request was drained MID-generation, and its
        # partial text was delivered, not dropped (queued-never-admitted
        # requests drain with no output — that is their contract)
        partial = [rid for rid, s in out["statuses"].items()
                   if s == "drained" and out["n_generated"][rid] > 0]
        assert partial
        for rid in partial:
            assert out["outputs_present"][rid]
            assert out["n_generated"][rid] < 200
        # the KV ledger is clean: zero leaked blocks after the drain
        assert out["kv"]["leaked_blocks"] == 0
        assert out["kv"]["owners"] == []


# ---------------------------------------------------------------------------
# Telemetry schema contracts (ISSUE satellite)
# ---------------------------------------------------------------------------
class TestSchemaContracts:
    def validate(self, scalars):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        from check_telemetry_schema import validate_record

        return validate_record({"ts": 1.0, "step": None, "tag": "t",
                                "scalars": scalars}, 1)

    def test_new_keys_accepted(self):
        assert self.validate({
            "counter/serve/kv_blocks_alloc": 12,
            "counter/serve/kv_blocks_free": 12,
            "gauge/serve/kv_blocks_total": 16,
            "gauge/serve/kv_blocks_used": 4,
            "gauge/serve/kv_occupancy": 0.25,
            "gauge/serve/spec_accept_rate": 0.8,
            "hist/serve/ttft_ms/p99": 12.5,
            "hist/serve/tpot_ms/p50": 1.5,
            "hist/serve/decode_ms.b4/p50": 3.0,
        }) is None

    def test_negative_kv_counter_rejected(self):
        assert self.validate({"counter/serve/kv_blocks_alloc": -1})

    def test_occupancy_range(self):
        assert self.validate({"gauge/serve/kv_occupancy": 1.2})
        assert self.validate({"gauge/serve/spec_accept_rate": -0.1})

    def test_state_slot_gauges(self):
        ok = {"gauge/serve/state_slots_total": 4,
              "gauge/serve/state_slots_used": 3,
              "gauge/serve/state_occupancy": 0.75,
              "counter/serve/state_resets": 9}
        assert self.validate(ok) is None
        assert self.validate({**ok, "gauge/serve/state_occupancy": 1.5})
        assert self.validate({**ok, "gauge/serve/state_slots_used": 5})
        assert self.validate({**ok, "counter/serve/state_resets": -1})

    def test_negative_ttft_rejected(self):
        assert self.validate({"hist/serve/ttft_ms/p50": -3.0})
        assert self.validate({"hist/serve/tpot_ms/max": -1.0})

    def test_kv_cross_field_consistency(self):
        assert self.validate({"gauge/serve/kv_blocks_total": 8,
                              "gauge/serve/kv_blocks_used": 9})
        assert self.validate({"gauge/serve/kv_blocks_total": 8,
                              "gauge/serve/kv_blocks_used": 2,
                              "gauge/serve/kv_occupancy": 0.9})
        assert self.validate({"gauge/serve/kv_blocks_total": 8,
                              "gauge/serve/kv_blocks_used": 2,
                              "gauge/serve/kv_occupancy": 0.25}) is None

    def test_engine_telemetry_passes_schema(self, tmp_path):
        eng, _ = make_engine()
        eng.start()
        try:
            r = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=6)
            assert r.wait(60)
        finally:
            eng.shutdown()
        path = str(tmp_path / "tel.jsonl")
        get_telemetry().to_jsonl(path, tag="decode_test")
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        from check_telemetry_schema import validate_file

        n, err = validate_file(path, require=[
            "counter/serve/kv_blocks_alloc",
            "counter/serve/kv_blocks_free",
            "gauge/serve/kv_occupancy",
            "counter/serve/tokens_generated"])
        assert err is None, err


# ---------------------------------------------------------------------------
# Full gate (slow)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestDecodeGateEndToEnd:
    def test_check_decode_gate_passes(self):
        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools",
                                          "check_decode.py"), "--json"],
            capture_output=True, text=True, timeout=580,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["gate"] == "decode"
        assert payload["status"] == "OK"
        assert payload["kv"]["leaked_blocks"] == 0
