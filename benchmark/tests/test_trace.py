"""The reduction from a trace to busy time, idle share and breakdown, on
the hand-made fixture (see ``make_trace_fixture.py`` for what is in it)."""
import os

import pytest

from benchmark.lib import manifest, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture.textproto")


@pytest.fixture(scope="module")
def planes():
    return trace.load(FIXTURE)


def test_planes_and_lines_as_on_the_chip(planes):
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    assert set(planes["/device:TPU:0"]) == {"Steps", "XLA Modules", "XLA Ops",
                                            "Async XLA Ops"}


def test_busy_is_a_union_over_whole_steps(planes):
    r = trace.reduce(planes)
    # runs 2 to 4 of five: 300 us, of which [70, 80) of each is idle; the
    # overlapping fusion, the enclosing module and step events and the
    # async copy add nothing
    assert r["steps"] == 3 and r["step_module"] == "jit_step_core(123)"
    assert r["window_s"] == pytest.approx(300e-6)
    assert r["busy_s"] == pytest.approx(270e-6)
    assert r["idle_share"] == pytest.approx(0.1)
    assert r["chips"] == 1


def test_breakdown_sums_layers_and_names_the_host_span(planes):
    b = trace.reduce(planes)["breakdown"]
    ops = dict(map(tuple, b["device_ops"]))
    # two fusions of one signature (two "layers") are one entry
    assert ops["fusion/kOutput bf16[8,64] x6"] == pytest.approx(180e-6)
    assert ops["fusion/kLoop f32[64] x3"] == pytest.approx(90e-6)
    assert not any(name.startswith("copy-start") for name in ops)
    assert b["idle_gaps"] == [["bench.dispatch", pytest.approx(30e-6)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_in_the_trace_reads_nothing(planes):
    host_only = {"/host:CPU": planes["/host:CPU"]}
    assert trace.reduce(host_only) is None
    read = manifest.metric_reader("device_idle_pct.train")
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert read({"trace": None, "device": cpu}) is None
    # a CPU run has no share of a chip's peak either
    mfu = manifest.metric_reader("mfu_pct.train")
    assert mfu({"device": cpu, "window_seconds": 1.0, "window_tokens": 10,
                "flops_per_token": 1.0, "chips": 1}) is None


def test_readers_on_a_chip_run(planes):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    run = {"device": tpu, "trace": trace.reduce(planes), "chips": 1,
           "window_seconds": 2.0, "window_tokens": 100_000,
           "flops_per_token": 1.97e9, "dispatch_ms": [3.0, 9.0, 4.0]}
    assert manifest.metric_reader("device_idle_pct.train")(run) == \
        pytest.approx(10.0)
    assert manifest.metric_reader("mfu_pct.train")(run) == pytest.approx(50.0)
    assert manifest.metric_reader("dispatch_ms.train")(run) == 4.0
    with pytest.raises(LookupError):
        manifest.metric_reader("mfu_pct.train")(
            {**run, "device": {**tpu, "kind": "TPU v9 imaginary"}})


def test_op_signature_drops_numbers_and_layouts():
    name = ("%fusion.205 = bf16[50304,1024]{1,0:T(8,128)(2,1)} fusion("
            "bf16[8,1024,50304]{2,1,0:T(8,128)(2,1)} %get-tuple-element.3158)"
            ", kind=kOutput, calls=%fused_computation.211")
    assert trace.op_signature(name) == "fusion/kOutput bf16[50304,1024]"
    assert trace.op_signature("not hlo at all") == "not hlo at all"
