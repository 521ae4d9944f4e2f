"""Device profiling + source-line attribution + bottleneck verdicts +
bench-trajectory gate (profiler.hlo_attrib / device_profile / bottleneck,
tools/check_bench_trajectory.py, the _gate ports of the model/op
benchmark gates, and the utils.profiler re-entrancy satellites).

Golden fixtures live in tests/profiler_fixtures/: a handcrafted
TPU-style trace (XLA Ops lanes + a shadowing host event that must be
excluded), its CPU-style twin (no lanes — the thunk-executor fallback),
the HLO text they join against, and malformed/empty traces for the
degrade-to-warning path. The golden numbers are exact by construction:
device total 6.0 ms over wall 10 ms, compute/collective/transfer =
4.0/1.5/0.5 ms, so the tables and the reconciliation invariant are
asserted to the digit.
"""
import gzip
import json
import logging
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.profiler import (bottleneck, device_profile, get_telemetry,
                                 hlo_attrib)

FIXTURES = os.path.join(os.path.dirname(__file__), "profiler_fixtures")
TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _golden_hlo():
    with open(os.path.join(FIXTURES, "golden_hlo.txt")) as f:
        return f.read()


def _golden_trace(name="golden.trace.json.gz"):
    return hlo_attrib.load_trace(os.path.join(FIXTURES, name))


@pytest.fixture(autouse=True)
def _clean_profiler_state():
    get_telemetry().reset()
    device_profile.reset()
    yield
    get_telemetry().reset()
    device_profile.reset()


def _tiny_step(d=32, classes=10):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(d, d), nn.ReLU(), nn.Linear(d, classes))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    step = paddle.jit.TrainStep(net, loss_fn=nn.CrossEntropyLoss(),
                                optimizer=opt)
    rng = np.random.RandomState(0)
    x = rng.randn(16, d).astype(np.float32)
    y = rng.randint(0, classes, 16).astype(np.int64)
    return step, (x,), (y,)


# -- HLO parsing --------------------------------------------------------------

class TestParseHlo:
    def test_names_opcodes_sources(self):
        ops = hlo_attrib.parse_hlo_text(_golden_hlo())
        assert ops["dot.3"].opcode == "dot"
        assert ops["dot.3"].src == "model.py:10"
        assert ops["dot.3"].op_name == "jit(step)/jit(main)/dot_general"
        assert ops["tanh.4"].src == "model.py:11"
        assert ops["all-reduce.5"].opcode == "all-reduce"
        assert ops["fusion.7"].opcode == "fusion"
        # tuple-typed result: the opcode parser must skip the
        # parenthesized type, not mistake it for the operand list
        assert ops["copy-start.6"].opcode == "copy-start"
        # ROOT-prefixed and computation-internal instructions register too
        assert "add.8" in ops and "reduce.10" in ops

    def test_header_table_form_resolves_the_same_sources(self):
        # the installed jaxlib writes stack_frame_id=N per op plus
        # FileNames/FileLocations/StackFrames tables at the top of the
        # module; the recorded fixture keeps the older inline form
        with open(os.path.join(FIXTURES, "golden_hlo_frames.txt")) as f:
            framed = hlo_attrib.parse_hlo_text(f.read())
        inline = hlo_attrib.parse_hlo_text(_golden_hlo())
        assert "stack_frame_id" not in _golden_hlo()
        assert {n: (o.opcode, o.src, o.op_name) for n, o in framed.items()} \
            == {n: (o.opcode, o.src, o.op_name) for n, o in inline.items()}
        assert framed["all-reduce.5"].src == "grad.py:20"

    def test_categories(self):
        ops = hlo_attrib.parse_hlo_text(_golden_hlo())
        assert ops["dot.3"].category == "compute"
        assert ops["all-reduce.5"].category == "collective"
        assert ops["copy-start.6"].category == "transfer"
        assert ops["fusion.7"].category == "compute"

    def test_real_compiled_hlo_parses(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, w):
            return jnp.tanh(x @ w).sum()

        x = jnp.ones((16, 16))
        text = f.lower(x, x).compile().as_text()
        ops = hlo_attrib.parse_hlo_text(text)
        assert any(o.opcode == "dot" for o in ops.values())
        # at least one op carries a real source line from this file/jax
        assert any(":" in o.src and o.src != "?" for o in ops.values())


# -- golden attribution -------------------------------------------------------

class TestGoldenAttribution:
    def _report(self, trace_name="golden.trace.json.gz"):
        return hlo_attrib.attribute_trace(
            _golden_trace(trace_name), {"train.step": _golden_hlo()},
            steps={"train.step": 2}, wall_ms=10.0,
            trigger_entry="train.step")

    def test_exact_per_op_table(self):
        rep = self._report()
        att = rep.entries["train.step"]
        assert att.by_op["dot.3"] == pytest.approx(2.0)
        assert att.by_op["all-reduce.5"] == pytest.approx(1.5)
        assert att.by_op["tanh.4"] == pytest.approx(1.0)
        assert att.by_op["fusion.7"] == pytest.approx(0.7)
        assert att.by_op["copy-start.6"] == pytest.approx(0.5)
        assert att.by_op["<unattributed:rendezvous>"] == pytest.approx(0.3)
        top = att.top_ops(3)
        assert [r["op"] for r in top] == ["dot.3", "all-reduce.5", "tanh.4"]
        assert top[0]["ms_per_step"] == pytest.approx(1.0)
        assert top[0]["src"] == "model.py:10"

    def test_exact_per_line_table(self):
        rep = self._report()
        att = rep.entries["train.step"]
        assert att.by_line["model.py:10"] == pytest.approx(2.0)
        assert att.by_line["model.py:11"] == pytest.approx(1.0)
        assert att.by_line["model.py:12"] == pytest.approx(0.7)
        assert att.by_line["grad.py:20"] == pytest.approx(1.5)
        assert att.by_line["io.py:5"] == pytest.approx(0.5)

    def test_category_totals_reconcile_within_1pct(self):
        rep = self._report()
        att = rep.entries["train.step"]
        assert rep.device_total_ms == pytest.approx(6.0)
        assert att.category_ms["compute"] == pytest.approx(4.0)
        assert att.category_ms["collective"] == pytest.approx(1.5)
        assert att.category_ms["transfer"] == pytest.approx(0.5)
        assert rep.reconciliation_error() < 0.01

    def test_fractions_and_host_gap(self):
        rep = self._report()
        fr = rep.fractions("train.step")
        assert fr["compute_frac"] == pytest.approx(0.40)
        assert fr["collective_frac"] == pytest.approx(0.15)
        assert fr["transfer_frac"] == pytest.approx(0.05)
        assert fr["host_gap_frac"] == pytest.approx(0.40)
        assert sum(fr.values()) <= 1.0 + 1e-9

    def test_host_event_shadowing_hlo_name_excluded(self):
        # the python-pid "dot.3" event (99999 us) must NOT be counted:
        # XLA Ops lanes exist, so lane membership wins over name match
        rep = self._report()
        assert rep.device_total_ms < 7.0

    def test_cpu_style_trace_name_fallback(self):
        rep = self._report("golden_cpu.trace.json.gz")
        att = rep.entries["train.step"]
        assert att.by_op["dot.3"] == pytest.approx(1.0)
        # runtime bookkeeping events (ThunkExecutor waits) never match
        # HLO names, so they are excluded on the fallback path
        assert rep.device_total_ms == pytest.approx(3.0 - 0.15)

    def test_overlapping_device_time_normalizes(self):
        # wall SHORTER than device time (parallel thunks): fractions
        # scale down so the per-entry sum stays <= 1
        rep = hlo_attrib.attribute_trace(
            _golden_trace(), {"train.step": _golden_hlo()},
            steps={"train.step": 2}, wall_ms=3.0,
            trigger_entry="train.step")
        fr = rep.fractions("train.step")
        assert sum(fr.values()) <= 1.0 + 1e-9
        assert fr["host_gap_frac"] == pytest.approx(0.0)

    def test_malformed_trace_degrades_to_warning(self, caplog):
        with caplog.at_level(logging.WARNING, "paddle_tpu.profiler"):
            trace = hlo_attrib.load_trace(
                os.path.join(FIXTURES, "malformed.trace.json.gz"))
        assert trace is None
        assert any("unreadable trace" in r.message for r in caplog.records)

    def test_empty_trace_degrades_to_warning(self, caplog):
        trace = _golden_trace("empty.trace.json.gz")
        with caplog.at_level(logging.WARNING, "paddle_tpu.profiler"):
            rep = hlo_attrib.attribute_trace(
                trace, {"train.step": _golden_hlo()}, wall_ms=10.0)
        assert rep is None
        assert any("no attributable device events" in r.message
                   for r in caplog.records)

    def test_missing_logdir_degrades(self, tmp_path, caplog):
        with caplog.at_level(logging.WARNING, "paddle_tpu.profiler"):
            assert hlo_attrib.load_trace(str(tmp_path)) is None


# -- live capture e2e ---------------------------------------------------------

class TestLiveCapture:
    def test_programmatic_capture_train_step(self):
        step, inp, lab = _tiny_step()
        for _ in range(3):
            step(inp, lab)
        compiles_before = step._jitted.tracker.compiles
        assert device_profile.request_capture(steps=2)
        assert device_profile.capture_state() == "armed"
        for _ in range(4):
            step(inp, lab)
        assert device_profile.capture_state() == "idle"
        rep = device_profile.last_report()
        assert rep is not None
        assert rep["steps"]["jit.train_step"] == 2
        att = rep["entries"]["jit.train_step"]
        # category totals reconcile with device total within 1%
        cat = sum(att["category_ms"].values())
        assert cat == pytest.approx(rep["device_total_ms"], rel=0.01)
        fr = att["fractions"]
        assert 0 <= sum(fr.values()) <= 1 + 1e-6
        assert rep["top_ops"], "per-op table must not be empty"
        assert rep["top_ops"][0]["src"] != ""
        # zero retraces: arming/stopping a capture is host-side only
        assert step._jitted.tracker.compiles == compiles_before

    def test_capture_publishes_gauges_and_verdict(self):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=2)
        for _ in range(3):
            step(inp, lab)
        tel = get_telemetry()
        scal = tel.scalars()
        assert "gauge/profile/compute_frac.jit.train_step" in scal
        assert "gauge/bottleneck/jit.train_step" in scal
        assert scal["gauge/bottleneck/jit.train_step"] in (0, 1, 2, 3, 4)
        assert tel.counter_value("profile/captures") == 1

    def test_overlapping_capture_refused_and_counted(self):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=4)
        assert not device_profile.request_capture(steps=1)
        assert get_telemetry().counter_value(
            "profile/capture_skipped") == 1

    def test_env_triggered_capture(self, monkeypatch):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        device_profile.configure(every=4, steps=2)
        for _ in range(8):
            step(inp, lab)
        assert get_telemetry().counter_value("profile/captures") >= 1
        assert device_profile.last_report() is not None

    def test_jsonl_record_carries_profile_and_passes_schema(self, tmp_path):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=2)
        for _ in range(3):
            step(inp, lab)
        path = tmp_path / "t.jsonl"
        get_telemetry().to_jsonl(str(path), tag="bench/fake")
        rec = json.loads(path.read_text().strip())
        assert "profile" in rec
        assert rec["profile"]["top_ops"]
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS,
                                          "check_telemetry_schema.py"),
             str(path)], capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_chrome_export_merges_device_ops(self, tmp_path):
        from paddle_tpu.utils import profiler as host_profiler

        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=2)
        for _ in range(3):
            step(inp, lab)
        out = host_profiler.export_chrome_tracing(
            str(tmp_path / "trace.json"))
        events = json.load(open(out))["traceEvents"]
        dev = [e for e in events if e.get("cat") == "device"]
        assert dev, "device-op slices must ride the chrome export"
        assert all(e["tid"] == "device ops" for e in dev)
        # drained: a second export has no stale device ops
        out2 = host_profiler.export_chrome_tracing(
            str(tmp_path / "trace2.json"))
        events2 = json.load(open(out2))["traceEvents"]
        assert not [e for e in events2 if e.get("cat") == "device"]

    def test_reset_discards_armed_capture_tempdir(self):
        import glob

        before = set(glob.glob("/tmp/paddle_tpu_devprof_*"))
        assert device_profile.request_capture(steps=2)  # arms a tempdir
        get_telemetry().reset()  # abandons the ARMED capture
        after = set(glob.glob("/tmp/paddle_tpu_devprof_*"))
        assert after - before == set(), "armed-then-reset leaked a dir"

    def test_reset_forgets_report(self):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=1)
        for _ in range(2):
            step(inp, lab)
        assert device_profile.last_report() is not None
        get_telemetry().reset()
        assert device_profile.last_report() is None
        assert device_profile.jsonl_payload() is None


def _span_trace():
    """A hand-made trace of two served iterations, in µs: device runs over
    [100, 200) (operations [100, 140) and [150, 200): 10 idle inside it)
    and [300, 380); the scheduler's ``pt.serve.*`` spans on the host.
    Over the window [50, 450): 230 µs idle, booked below."""
    host = [("pt.serve.iter", 40, 260, 7), ("pt.serve.dispatch", 60, 90),
            ("pt.serve.fetch", 90, 210), ("pt.serve.tokens", 210, 240),
            ("pt.serve.retire", 240, 260), ("pt.serve.iter", 270, 420, 8),
            ("pt.serve.admit", 270, 290), ("pt.serve.dispatch", 290, 300),
            ("pt.serve.fetch", 300, 400), ("pt.serve.tokens", 400, 410)]
    ev = [{"ph": "M", "pid": 1, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 2, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    for s, e in ((100, 200), (300, 380)):
        ev.append({"ph": "X", "pid": 1, "tid": 1, "name": "jit_step",
                   "ts": s, "dur": e - s})
    for name, s, e in (("dot.3", 100, 140), ("tanh.4", 150, 200),
                       ("dot.3", 300, 380)):
        ev.append({"ph": "X", "pid": 1, "tid": 2, "name": name, "ts": s,
                   "dur": e - s})
    for name, s, e, *step in host:
        ev.append({"ph": "X", "pid": 2, "tid": 1, "name": name, "ts": s,
                   "dur": e - s,
                   "args": {"step": step[0]} if step else {}})
    return {"traceEvents": ev}


# idle µs of the window [50, 450) by the innermost span over it: [50, 100)
# iter 10, dispatch 30, fetch 10; [140, 150) in the run; [200, 300) fetch
# 10, tokens 30, retire 20, none 10, admit 20, dispatch 10; [380, 450)
# fetch 20, tokens 10, iter 10, none 30
SPAN_IDLE_US = {"pt.serve.iter": 20, "pt.serve.dispatch": 40,
                "pt.serve.fetch": 40, "pt.serve.tokens": 40,
                "pt.serve.retire": 20, "pt.serve.admit": 20,
                hlo_attrib.IN_STEP: 10, hlo_attrib.OUTSIDE_SPANS: 40}


class TestIdleBySpan:
    def _report(self, **kw):
        return hlo_attrib.attribute_trace(
            _span_trace(), {"serve.decode": _golden_hlo()},
            steps={"serve.decode": 2}, trigger_entry="serve.decode", **kw)

    def test_gaps_go_to_the_step_the_innermost_span_or_none(self):
        rep = self._report(wall_ms=0.4, window_us=(50, 450))
        # the spans are no device events: 170 µs of operations
        assert rep.device_total_ms == pytest.approx(0.170)
        assert rep.device_busy_ms == pytest.approx(0.170)
        assert rep.host_gap_ms == pytest.approx(0.230)
        assert rep.idle_by_span_ms == pytest.approx(
            {k: v / 1e3 for k, v in SPAN_IDLE_US.items()})
        assert sum(rep.idle_by_span_ms.values()) == pytest.approx(
            rep.host_gap_ms, rel=0.01)
        out = rep.to_dict()
        assert out["idle_by_span_ms"]["pt.serve.dispatch"] == 0.04
        assert list(out["idle_by_span_ms"])[0] in ("pt.serve.dispatch",
                                                   "pt.serve.fetch",
                                                   "pt.serve.tokens")

    def test_wall_the_window_does_not_place_is_outside_every_span(self):
        # no window: the trace's operations bound it, [100, 380): the
        # 120 µs of the wall around them are booked to no span
        rep = self._report(wall_ms=0.4)
        assert rep.host_gap_ms == pytest.approx(0.230)
        assert sum(rep.idle_by_span_ms.values()) == pytest.approx(
            rep.host_gap_ms, rel=0.01)
        assert rep.idle_by_span_ms[hlo_attrib.OUTSIDE_SPANS] \
            == pytest.approx(0.010 + 0.120)

    def test_host_gap_counts_overlapping_operations_once(self):
        trace = _span_trace()
        trace["traceEvents"].append({"ph": "X", "pid": 1, "tid": 2,
                                     "name": "tanh.4", "ts": 160,
                                     "dur": 20})
        rep = hlo_attrib.attribute_trace(
            trace, {"serve.decode": _golden_hlo()}, wall_ms=0.4,
            window_us=(50, 450))
        assert rep.device_total_ms == pytest.approx(0.190)
        assert rep.host_gap_ms == pytest.approx(0.230)  # not 0.210

    def test_chrome_slices_sit_on_the_spans_clock(self):
        from paddle_tpu.profiler import spans

        with spans.Span("serve.iter", step=7) as it:
            pass
        trace = _span_trace()
        offset = device_profile.clock_offset_us(trace, it.ts_us - 1,
                                                it.ts_us + 1)
        assert offset == pytest.approx(it.ts_us - 40)
        rep = self._report(wall_ms=0.4)
        slices = device_profile._chrome_from_trace(trace, rep, offset)
        assert sorted(e["ts"] - it.ts_us for e in slices) \
            == pytest.approx([60, 110, 260])
        # no span in both records: no slice placed by a guess
        assert device_profile.clock_offset_us(trace, 0, 1) is None
        assert device_profile._chrome_from_trace(trace, rep, None) == []

    def test_a_span_without_a_step_matches_the_first_opened(self):
        from paddle_tpu.profiler import spans

        since = spans._clock() * 1e6
        with spans.Span("h2d") as first:
            pass
        with spans.Span("h2d"):
            pass
        trace = {"traceEvents": [
            {"ph": "X", "pid": 2, "tid": 1, "name": "pt.h2d", "ts": 15,
             "dur": 1},
            {"ph": "X", "pid": 2, "tid": 1, "name": "pt.h2d", "ts": 25,
             "dur": 1}]}
        assert device_profile.clock_offset_us(
            trace, since, spans._clock() * 1e6) \
            == pytest.approx(first.ts_us - 15)

    def test_an_xplane_keeps_the_programs_spans_apart(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.profiler import spans

        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        x = jnp.ones((32, 32))
        f(x).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        with spans.Span("serve.iter", step=3):
            with spans.Span("serve.fetch"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()
        trace = hlo_attrib.load_trace(str(tmp_path))
        got = {(s["name"], s["step"])
               for s in hlo_attrib.program_spans(trace)}
        assert got == {("pt.serve.iter", 3), ("pt.serve.fetch", None)}
        ops = hlo_attrib.device_events(trace)
        assert ops and not [e for e in ops
                            if e["name"].startswith(hlo_attrib.SPAN_PREFIX)]
        assert len(hlo_attrib.module_runs(trace, ops)) == 1

    def test_a_capture_runs_without_the_python_tracer(self, monkeypatch):
        import jax

        asked = []
        start = jax.profiler.start_trace
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: asked.append(kw) or start(d, **kw))
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=1)
        for _ in range(2):
            step(inp, lab)
        assert device_profile.last_report() is not None
        assert [kw["profiler_options"].python_tracer_level
                for kw in asked] == [0]

    def test_a_live_capture_books_its_idle_time(self):
        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=2)
        for _ in range(3):
            step(inp, lab)
        rep = device_profile.last_report()
        idle = rep["idle_by_span_ms"]
        assert idle and sum(idle.values()) == pytest.approx(
            rep["host_gap_ms"], rel=0.01, abs=1e-3)
        assert set(idle) <= {"pt.step", "pt.h2d", "pt.compute",
                             "pt.compile", hlo_attrib.IN_STEP,
                             hlo_attrib.OUTSIDE_SPANS}


class TestOpsServerTrigger:
    def test_post_arms_get_reports(self):
        from paddle_tpu.profiler.ops_server import OpsServer

        step, inp, lab = _tiny_step()
        step(inp, lab)
        srv = OpsServer(0, host="127.0.0.1").start()
        try:
            port = srv.port
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/profile?steps=2",
                method="POST")
            resp = json.load(urllib.request.urlopen(req))
            assert resp["armed"] is True
            # overlap -> 409 + counted skip
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/debug/profile?steps=2",
                    method="POST"))
            assert ei.value.code == 409
            for _ in range(3):
                step(inp, lab)
            rep = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile"))
            assert rep["state"] == "idle"
            assert rep["report"]["entries"]["jit.train_step"]
            # verdict gauges ride the live /metrics scrape
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics").read().decode()
            assert "paddle_tpu_bottleneck_jit_train_step" in text.replace(
                ".", "_")
            from paddle_tpu.profiler.ops_server import parse_prometheus_text

            parse_prometheus_text(text)
        finally:
            srv.stop()

    def test_bad_steps_is_400_and_unknown_post_404(self):
        from paddle_tpu.profiler.ops_server import OpsServer

        srv = OpsServer(0, host="127.0.0.1").start()
        try:
            port = srv.port
            for path, code in (("/debug/profile?steps=abc", 400),
                               ("/nope", 404)):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{port}{path}", method="POST"))
                assert ei.value.code == code
        finally:
            srv.stop()


# -- utils.profiler re-entrancy satellites ------------------------------------

class TestProfilerReentrancy:
    def test_double_start_warns_and_noops(self, tmp_path, caplog):
        from paddle_tpu.utils import profiler as host_profiler

        with caplog.at_level(logging.WARNING, "paddle_tpu.profiler"):
            host_profiler.start_profiler(log_dir=str(tmp_path / "a"))
            host_profiler.start_profiler(log_dir=str(tmp_path / "b"))
        assert any("already live" in r.message for r in caplog.records)
        # stops pair LIFO: the first stop closes the DEGRADED inner
        # window and must leave the outer window's device trace live
        host_profiler.stop_profiler(profile_path=str(tmp_path / "t.json"))
        assert device_profile.device_trace_owner() == "utils.profiler"
        host_profiler.stop_profiler(profile_path=str(tmp_path / "t2.json"))
        # fully released: a fresh device-trace window opens again
        assert device_profile.device_trace_owner() is None

    def test_stop_without_start_never_raises(self, tmp_path):
        from paddle_tpu.utils import profiler as host_profiler

        host_profiler.stop_profiler(profile_path=str(tmp_path / "t.json"))

    def test_capture_refused_while_profiler_window_open(self, tmp_path):
        from paddle_tpu.utils import profiler as host_profiler

        host_profiler.start_profiler(log_dir=str(tmp_path / "w"))
        try:
            assert not device_profile.request_capture(steps=1)
            assert get_telemetry().counter_value(
                "profile/capture_skipped") == 1
        finally:
            host_profiler.stop_profiler(
                profile_path=str(tmp_path / "t.json"))

    def test_profiler_window_degrades_while_capture_live(self, tmp_path,
                                                         caplog):
        from paddle_tpu.utils import profiler as host_profiler

        step, inp, lab = _tiny_step()
        step(inp, lab)
        assert device_profile.request_capture(steps=50)
        step(inp, lab)  # starts the trace
        assert device_profile.capture_state() == "capturing"
        try:
            with caplog.at_level(logging.WARNING, "paddle_tpu.profiler"):
                host_profiler.start_profiler(log_dir=str(tmp_path / "w"))
            assert any("already live" in r.message for r in caplog.records)
            host_profiler.stop_profiler(
                profile_path=str(tmp_path / "t.json"))
            # the capture still owns the device trace
            assert device_profile.device_trace_owner() == "device_profile"
        finally:
            device_profile.reset()


# -- bottleneck verdicts ------------------------------------------------------

class TestBottleneckVerdicts:
    def _publish_fracs(self, tel, entry, compute=0.0, collective=0.0,
                       transfer=0.0, host_gap=0.0):
        tel.gauge(f"profile/compute_frac.{entry}", compute)
        tel.gauge(f"profile/collective_frac.{entry}", collective)
        tel.gauge(f"profile/transfer_frac.{entry}", transfer)
        tel.gauge(f"profile/host_gap_frac.{entry}", host_gap)

    def test_comm_bound(self):
        tel = get_telemetry()
        self._publish_fracs(tel, "e", compute=0.3, collective=0.6)
        out = bottleneck.publish(tel)
        assert out["e"]["verdict"] == "comm_bound"
        assert tel.scalars()["gauge/bottleneck/e"] == 2

    def test_host_vs_input_bound(self):
        tel = get_telemetry()
        self._publish_fracs(tel, "h", compute=0.2, host_gap=0.8)
        self._publish_fracs(tel, "i", compute=0.2, host_gap=0.7,
                            transfer=0.1)
        out = bottleneck.publish(tel)
        assert out["h"]["verdict"] == "host_bound"
        assert out["i"]["verdict"] == "input_bound"

    def test_device_bound_defers_to_roofline(self):
        tel = get_telemetry()
        self._publish_fracs(tel, "c", compute=0.9, host_gap=0.1)
        tel.gauge("roofline/c", 1.0)
        self._publish_fracs(tel, "m", compute=0.9, host_gap=0.1)
        tel.gauge("roofline/m", 0.0)
        out = bottleneck.publish(tel)
        assert out["c"]["verdict"] == "compute_bound"
        assert out["m"]["verdict"] == "memory_bound"

    def test_roofline_fallback_without_capture(self):
        tel = get_telemetry()
        tel.gauge("roofline/r", 0.0)
        tel.gauge("mfu/r", 12.5)
        out = bottleneck.publish(tel)
        assert out["r"]["verdict"] == "memory_bound"
        assert out["r"]["evidence"]["mfu_pct"] == 12.5

    def test_agg_surfaces_named_verdicts(self):
        from paddle_tpu.profiler import aggregate

        rank_scalars = {0: {"gauge/bottleneck/fleet.train_step": 4.0},
                        1: {"gauge/bottleneck/fleet.train_step": 0.0}}
        rows = aggregate.collect_bottlenecks(rank_scalars)
        assert rows == [
            {"entry": "fleet.train_step", "rank": 0,
             "verdict": "host_bound"},
            {"entry": "fleet.train_step", "rank": 1,
             "verdict": "compute_bound"},
        ]


# -- schema contracts ---------------------------------------------------------

class TestSchemaContracts:
    def _check(self, tmp_path, scalars, profile=None):
        rec = {"ts": 1.0, "step": 0, "tag": "t", "scalars": scalars}
        if profile is not None:
            rec["profile"] = profile
        p = tmp_path / "x.jsonl"
        p.write_text(json.dumps(rec) + "\n")
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_telemetry_schema.py"), str(p)],
            capture_output=True, text=True)
        return r.returncode, r.stdout + r.stderr

    def test_frac_bounds(self, tmp_path):
        rc, _ = self._check(tmp_path,
                            {"gauge/profile/compute_frac.e": 0.5})
        assert rc == 0
        rc, out = self._check(tmp_path,
                              {"gauge/profile/compute_frac.e": 1.5})
        assert rc == 1 and "outside [0, 1]" in out

    def test_frac_sum_cross_field(self, tmp_path):
        rc, out = self._check(tmp_path, {
            "gauge/profile/compute_frac.e": 0.7,
            "gauge/profile/host_gap_frac.e": 0.5})
        assert rc == 1 and "sum" in out
        rc, _ = self._check(tmp_path, {
            "gauge/profile/compute_frac.e": 0.7,
            "gauge/profile/host_gap_frac.e": 0.3})
        assert rc == 0

    def test_bottleneck_closed_vocabulary(self, tmp_path):
        rc, _ = self._check(tmp_path, {"gauge/bottleneck/e": 3})
        assert rc == 0
        rc, out = self._check(tmp_path, {"gauge/bottleneck/e": 7})
        assert rc == 1 and "verdict id" in out

    def test_profile_table_well_formed(self, tmp_path):
        good = {"top_ops": [{"op": "dot.3", "category": "compute",
                             "ms": 1.0, "ms_per_step": 0.5, "frac": 0.4}],
                "top_lines": [{"src": "model.py:10", "ms": 1.0}]}
        rc, _ = self._check(tmp_path, {}, profile=good)
        assert rc == 0
        bad = {"top_ops": [{"op": "dot.3", "category": "magic",
                            "ms": 1.0}], "top_lines": []}
        rc, out = self._check(tmp_path, {}, profile=bad)
        assert rc == 1 and "closed set" in out
        bad2 = {"top_ops": [{"op": "dot.3", "category": "compute",
                             "ms": -1.0}], "top_lines": []}
        rc, out = self._check(tmp_path, {}, profile=bad2)
        assert rc == 1


# -- bench trajectory gate ----------------------------------------------------

class TestBenchTrajectoryGate:
    def _run(self, *args):
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_bench_trajectory.py"), *args],
            capture_output=True, text=True)
        return r.returncode, r.stdout + r.stderr

    # every history below is synthetic: the gate's behaviour is under
    # test, not any recorded number
    _METRIC = "gpt_small_L8192_longctx_train_tokens_per_sec"

    def _synth(self, tmp_path, regress=True):
        for n, v in ((1, 100.0), (2, 104.0)):
            (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
                {"parsed": {"metric": "headline_tokens_per_sec",
                            "value": v}}))
        base = {"metric": self._METRIC, "value": 60000.0,
                "mfu_measured_pct": 41.0,
                "attribution_entry": "fleet.train_step",
                "profile_host_gap_frac": 0.10}
        other = {"metric": "lenet_mnist_dygraph_samples_per_sec",
                 "value": 18000.0}
        (tmp_path / "BENCH_extra.prev.json").write_text(
            json.dumps([other, base]))
        cand = dict(base)
        if regress:
            cand["value"] *= 0.7
            cand["profile_host_gap_frac"] = 0.62
        (tmp_path / "BENCH_extra.json").write_text(
            json.dumps([other, cand]))
        return self._METRIC

    def test_flat_history_passes(self, tmp_path):
        self._synth(tmp_path, regress=False)
        rc, out = self._run("--root", str(tmp_path))
        assert rc == 0, out
        assert out.startswith("bench trajectory: OK")

    def test_synthetic_regression_names_metric_and_suspect(self, tmp_path):
        metric = self._synth(tmp_path)
        rc, out = self._run("--root", str(tmp_path))
        assert rc == 1
        assert "FAIL" in out
        assert metric in out
        # suspect entry + the moved attribution column are both named
        assert "fleet.train_step" in out
        assert "profile_host_gap_frac" in out

    def test_best_ever_catches_slow_bleed(self, tmp_path):
        # candidate above previous but 15% below the best round
        rounds = {"BENCH_r01.json": 100.0, "BENCH_r02.json": 84.0,
                  "BENCH_r03.json": 85.0}
        for name, v in rounds.items():
            (tmp_path / name).write_text(json.dumps(
                {"parsed": {"metric": "m", "value": v}}))
        rc, out = self._run("--root", str(tmp_path))
        assert rc == 1 and "best" in out

    def test_json_contract(self, tmp_path):
        metric = self._synth(tmp_path)
        rc, out = self._run("--root", str(tmp_path), "--json")
        assert rc == 1
        doc = json.loads(out)
        assert doc["gate"] == "bench trajectory"
        assert doc["status"] == "FAIL"
        assert any(metric in f for f in doc["failures"])

    def test_removed_metric_fails(self, tmp_path):
        (tmp_path / "BENCH_extra.prev.json").write_text(json.dumps(
            [{"metric": "gone", "value": 1.0, "backend": "cpu"}]))
        (tmp_path / "BENCH_extra.json").write_text(json.dumps([]))
        rc, out = self._run("--root", str(tmp_path))
        assert rc == 1 and "gone" in out


# -- _gate ports of the model/op benchmark gates ------------------------------

class TestGatePorts:
    def test_model_gate_ok_and_json(self, tmp_path):
        rows = [{"metric": "m", "value": 10.0, "backend": "cpu"}]
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(rows))
        b.write_text(json.dumps(rows))
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_model_benchmark_result.py"),
             str(a), str(b)], capture_output=True, text=True)
        assert r.returncode == 0
        assert "model benchmark: OK —" in r.stdout
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_model_benchmark_result.py"),
             str(a), str(b), "--json"], capture_output=True, text=True)
        doc = json.loads(r.stdout)  # --json stdout is pure JSON
        assert doc["status"] == "OK" and doc["gate"] == "model benchmark"

    def test_model_gate_regression_exits_1(self, tmp_path):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(
            [{"metric": "m", "value": 10.0, "backend": "cpu"}]))
        b.write_text(json.dumps(
            [{"metric": "m", "value": 5.0, "backend": "cpu"}]))
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_model_benchmark_result.py"),
             str(a), str(b)], capture_output=True, text=True)
        assert r.returncode == 1
        assert "model benchmark: FAIL —" in r.stderr

    def test_op_gate_ok_fail_and_json(self, tmp_path):
        base = {"backend": "cpu", "cases": {"matmul": {"ms": 1.0}}}
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(base))
        b.write_text(json.dumps(
            {"backend": "cpu", "cases": {"matmul": {"ms": 1.02}}}))
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_op_benchmark_result.py"),
             str(a), str(b)], capture_output=True, text=True)
        assert r.returncode == 0 and "op benchmark: OK —" in r.stdout
        b.write_text(json.dumps(
            {"backend": "cpu", "cases": {"matmul": {"ms": 2.0}}}))
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_op_benchmark_result.py"),
             str(a), str(b), "--json"], capture_output=True, text=True)
        assert r.returncode == 1
        doc = json.loads(r.stdout)  # --json stdout is pure JSON
        assert doc["status"] == "FAIL" and "matmul" in doc["detail"]

    def test_op_gate_unreadable_input_exits_1(self, tmp_path):
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_op_benchmark_result.py"),
             str(tmp_path / "nope.json"), str(tmp_path / "nope.json")],
            capture_output=True, text=True)
        assert r.returncode == 1


# -- the bench e2e (slow): env + ops-server captures during bench_all --------

@pytest.mark.slow
class TestBenchE2E:
    def test_env_capture_during_bench_config(self, tmp_path):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PADDLE_TPU_DEVICE_PROFILE_EVERY": "8",
                    "PADDLE_TPU_DEVICE_PROFILE_STEPS": "2",
                    "PYTHONPATH": REPO})
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench_all.py"),
             "--smoke", "bert"],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        recs = [json.loads(ln) for ln in
                open(tmp_path / "TELEMETRY.jsonl") if ln.strip()]
        rec = recs[-1]
        sc = rec["scalars"]
        assert sc.get("counter/profile/captures", 0) >= 1
        fr = {k: v for k, v in sc.items()
              if k.startswith("gauge/profile/") and "_frac." in k}
        assert fr, "decomposition fractions must be recorded"
        cats = sum(v for k, v in sc.items()
                   if k.startswith("gauge/profile/")
                   and ("_frac.fleet.train_step" in k)
                   and "host_gap" not in k)
        # category fracs * wall == category ms; reconcile vs device total
        wall = sc["gauge/profile/wall_ms"]
        dev = sc["gauge/profile/device_total_ms"]
        assert cats * wall == pytest.approx(min(dev, wall), rel=0.02)
        assert sc.get("gauge/bottleneck/fleet.train_step") in (0, 1, 2,
                                                               3, 4)
        # retrace budget untouched by the capture
        assert sc.get("counter/compile/fleet.train_step", 0) <= 6
        # schema gate passes on the record with the profile table
        chk = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "check_telemetry_schema.py"),
             str(tmp_path / "TELEMETRY.jsonl")],
            capture_output=True, text=True)
        assert chk.returncode == 0, chk.stdout + chk.stderr
