"""The reduction from a serving trace to busy time, idle share and the
device time of a decode step and of a prefill chunk, on planes made by
hand (the shape ``lib.trace.load`` gives)."""
import pytest

from benchmark.lib import manifest, serve_trace

US = 1e3  # nanoseconds
OP = ("%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} "
      "%p.0), kind=kOutput, calls=%fused_computation.1")


def _planes(module_names):
    """Six iterations of 100 us from t = 0: a prefill run over [0, 30), a
    decode run over [40, 90), nothing else; the scheduler's two rounds
    annotated on the host over [-2, 32) and [38, 92) of each."""
    modules, ops, host = [], [], []
    for i in range(6):
        t = 100.0 * i * US
        modules += [(module_names[0], t, t + 30 * US),
                    (module_names[1], t + 40 * US, t + 90 * US)]
        ops += [(OP, t, t + 30 * US), (OP, t + 40 * US, t + 90 * US)]
        host += [("bench.prefill_chunk", t - 2 * US, t + 32 * US),
                 ("bench.decode_round", t + 38 * US, t + 92 * US)]
    return {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
            "/host:CPU": {"DecodeScheduler": host}}


@pytest.mark.parametrize("names", [
    ("jit_step(11)", "jit_step(22)"),                   # by the annotation
    ("jit_serve_prefill_c256(1)", "jit_serve_decode_b16(2)"),   # by the name
], ids=["annotation", "module_name"])
def test_runs_are_told_apart_and_busy_is_a_union(names):
    planes = _planes(names)
    if "serve" in names[0]:
        planes["/host:CPU"] = {}
    r = serve_trace.reduce(planes)
    # the first and the last run are left out: from the first decode's
    # start (40) to the last prefill's end (530), 490 us; busy is six
    # decodes less one and five prefills: 5 * 50 + 5 * 30
    assert r["window_s"] == pytest.approx(490e-6)
    assert r["busy_s"] == pytest.approx(400e-6)
    assert r["idle_share"] == pytest.approx(90 / 490)
    assert r["runs"]["decode"] == pytest.approx([0.05] * 5)
    assert r["runs"]["prefill"] == pytest.approx([0.03] * 5)
    assert r["runs"]["other"] == []
    assert serve_trace.mean_ms(r, "decode") == pytest.approx(0.05)
    assert serve_trace.mean_ms(r, "other") is None
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # a gap goes to the harness span that covers most of it, 2 us of 10
    assert set(gaps) <= ({"bench.decode_round", "bench.prefill_chunk"}
                         if planes["/host:CPU"] else
                         {"outside_harness_spans"})
    assert sum(gaps.values()) == pytest.approx(90e-6)
    assert len(r["breakdown"]["device_ops"]) == 1


def test_unannotated_runs_are_other_and_no_device_reads_nothing():
    planes = _planes(("jit_step(11)", "jit_step(22)"))
    planes["/host:CPU"] = {}
    r = serve_trace.reduce(planes)
    assert len(r["runs"]["other"]) == 10 and not r["runs"]["decode"]
    assert serve_trace.reduce({"/host:CPU": {}}) is None
    # a traced run whose steps cannot be told apart fails; it does not
    # print the other metrics and leave the kinds' out
    with pytest.raises(RuntimeError, match="decode step"):
        serve_trace.whole(r)
    assert serve_trace.whole(None) is None
    told = serve_trace.reduce(_planes(("jit_step(11)", "jit_step(22)")))
    assert serve_trace.whole(told) is told


def test_readers_on_a_chip_run_and_on_none():
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    traced = serve_trace.reduce(_planes(("jit_step(11)", "jit_step(22)")))
    run = {"device": tpu, "trace": traced, "chips": 1, "window_seconds": 2.0,
           "flops": 3.94e12, "decode_steps": 10,
           "decode_step_bytes": 819e9 * 25e-6}
    read = manifest.metric_reader
    assert read("decode_ms.serve")(run) == pytest.approx(0.05)
    assert read("prefill_ms.serve")(run) == pytest.approx(0.03)
    assert read("device_idle_pct.serve")(run) == pytest.approx(9000 / 490)
    assert read("mfu_pct.serve")(run) == pytest.approx(1.0)
    # 25 us of reading at the published bandwidth in a 50 us step
    assert read("decode_hbm_roofline_pct.serve")(run) == pytest.approx(50.0)
    for name in ("decode_ms.serve", "prefill_ms.serve", "mfu_pct.serve",
                 "device_idle_pct.serve", "decode_hbm_roofline_pct.serve"):
        assert read(name)({**run, "device": cpu}) is None
        assert read(name)({**run, "trace": None, "window_seconds": 0}) is None
