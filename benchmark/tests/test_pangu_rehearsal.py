"""The openPangu-Ultra-MoE serve cell at a tiny size on any backend:
``correct`` that the program passes and that the float8 control and the
block-table mix-up fail, the latent pool the family builds, and the five
readers of the ``mla`` and ``moe`` scopes in a serving trace, on a trace
made by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_pangu_rehearsal.py -q
"""
import time

import pytest
from jax.profiler import ProfileData

from benchmark.lib import (compare, latent_scopes, manifest, scopes,
                           serve_trace, trace)
from paddle_tpu.profiler.telemetry import get_telemetry

MANIFEST = "benchmark/tests/rehearsal_pangu/BENCHMARK.json"
CELL = "pangu-tiny.serve-closed"
REAL = "openpangu-ultra-moe-718b.serve-closed-reason"


def _job(seed, **extra):
    found = manifest.load(MANIFEST, CELL)
    cell = {**found["cell"], "chips": 1}
    return cell, {"cell": cell, "config": found["config"], "seed": seed,
                  "seconds": 1.0, "trace": False,
                  "t0": time.perf_counter(), "trace_dir": None, **extra}


@pytest.fixture(scope="module")
def control_runs():
    """The program's and the float8 control's numbers on three seeds, the
    control read over the very prompts and tokens the program served."""
    from benchmark.drivers import serve

    out = []
    for seed in (1, 2, 2**31 + 3):
        cell, job = _job(seed, controls=("float8",))
        controls = []
        inner = serve.report.note
        serve.report.note = lambda kind, **f: (
            controls.append(f["numbers"]) if kind == "control"
            else inner(kind, **f))
        try:
            result = serve.run(job)
        finally:
            serve.report.note = inner
        out.append((cell, result, controls[0]))
    return out


@pytest.mark.parametrize("i", [0, 1, 2])
def test_program_is_correct_and_control_is_not(control_runs, i):
    cell, result, control = control_runs[i]
    ok, compared = compare.verdict(result["numbers"], cell["limits"])
    assert ok and result["failed"] == 0, compared
    assert result["numbers"]["tokens_compared"] >= 50
    bad, compared = compare.verdict(control, cell["limits"])
    assert not bad, compared


def test_the_run_left_the_engines_counts_behind(control_runs):
    """The engine published its expert layers' counts when it shut down:
    what ``moe_decode_roofline_pct.serve`` and ``decode_step_bytes`` read."""
    from benchmark.families import pangu_ultra_moe_serve as family

    hit = family.experts_hit()
    assert hit is not None and 0 < hit <= 4  # four experts are held


def test_a_block_table_mixup_is_not_correct():
    from benchmark.drivers import serve

    cell, job = _job(4, fault="block_table_mixup")
    result = serve.run(job)
    ok, compared = compare.verdict(result["numbers"], cell["limits"])
    assert not ok, compared


def test_the_pool_is_latent_and_leaks_none():
    from benchmark.drivers import serve
    from benchmark.families import pangu_ultra_moe_serve as family

    found = manifest.load(MANIFEST, CELL)
    model = family.build_model(found["config"], family.weights(
        found["config"], 1, "bfloat16"))
    assert model.config.nextn_held == 0  # spec_k 0: no draft weights came
    eng = family.build_engine(model, found["cell"]["engine"])
    try:
        assert eng.max_seq_len == found["config"]["assumed"]["max_seq_len"]
        assert not eng.self_draft and not eng.spec_enabled
        assert set(eng.pool.pages) == {"latent", "moe_counts"}
        assert len(eng.pool.pages["latent"]) == 3
        assert str(eng.pool.pages["latent"][0].dtype) \
            == found["cell"]["engine"]["kv_dtype"]
        assert "kv_occupancy" in serve.engine_gauges(eng)()
    finally:
        eng.shutdown()
    assert eng.kv_accounting()["leaked_blocks"] == 0


# -- the readers, on a trace made by hand --------------------------------------

US = 1_000_000  # picoseconds
P = "jit(serve_decode_b4)/"
OPS = {  # metadata id: (HLO line, tf_op or None)
    2: ("%fusion.1 = bf16[4,64]{1,0} fusion(bf16[4,64]{1,0} %p.1), kind=kOutput, calls=%fc.1",
        P + "self_attn/dot_general:"),
    3: ("%fusion.2 = bf16[4,1,4,32]{3,2,1,0} fusion(bf16[4,1,4,16]{3,2,1,0} %p.2), kind=kOutput, calls=%fc.2",
        P + "self_attn/mla/bthn,chn->bthc/dot_general:"),
    4: ("%fusion.3 = f32[4,4,1,32]{3,2,1,0} fusion(bf16[4,32,128]{2,1,0} %p.3), kind=kOutput, calls=%fc.3",
        P + "self_attn/mla/while/body/bhts,bsc->bhtc/dot_general:"),
    5: ("%fusion.4 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p.4), kind=kCustom, calls=%fc.4",
        P + "mlp/moe/scatter:"),
    # the grouped product as the v5e's compiler names it: no scope
    9: ("%ragged-dot-none.1 = bf16[8,32]{1,0} custom-call(bf16[8,64]{1,0} %p.7)",
        "ragged-dot-none:"),
    6: ("%fusion.5 = bf16[4,128]{1,0} fusion(bf16[4,64]{1,0} %p.5), kind=kOutput, calls=%fc.5",
        P + "mlp/dot_general:"),
    7: ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p.6)", None),
}
# a decode run: 50 us, of which mla 6 + 4 and moe 15; a prefill run: 30 us,
# of which mla 12 and moe 10
DECODE = {2: (0, 10), 3: (10, 6), 4: (16, 4), 5: (20, 5), 9: (25, 10),
          6: (35, 10), 7: (45, 5)}
PREFILL = {2: (0, 8), 3: (8, 12), 5: (20, 4), 9: (24, 6)}
NAMES = {1: "jit_serve_decode_b4(2)", 8: "jit_serve_prefill_c16(1)"}


def _textproto(names, scoped=True):
    def event(meta, start_us, length_us):
        return (f"    events {{ metadata_id: {meta} offset_ps: "
                f"{int(start_us * US)} duration_ps: {int(length_us * US)} }}\n")

    def line(ident, name, events):
        return (f'  lines {{ id: {ident} name: "{name}" timestamp_ns: 1000000\n'
                + "".join(events) + "  }\n")

    metadata = "".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in names.items())
    for k, (hlo, path) in OPS.items():
        if path and not scoped:
            path = path.replace("mla/", "").replace("moe/", "").replace(
                "ragged-dot", "grouped-dot")
        stat = (f' stats {{ metadata_id: 1 str_value: "{path}" }}'
                if path else "")
        metadata += (f'  event_metadata {{ key: {k} value {{ id: {k} '
                     f'name: "{hlo}"{stat} }} }}\n')
    metadata += '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
    modules, ops = [], []
    for i in range(6):  # six iterations of 100 us: a prefill, then a decode
        t = 100.0 * i
        modules += [event(8, t, 30), event(1, t + 40, 50)]
        ops += [event(k, t + a, n) for k, (a, n) in PREFILL.items()]
        ops += [event(k, t + 40 + a, n) for k, (a, n) in DECODE.items()]
    device = line(2, "XLA Modules", modules) + line(3, "XLA Ops", ops)
    return ('planes { id: 1 name: "/device:TPU:0"\n' + metadata + device
            + '}\nplanes { id: 2 name: "/host:CPU"\n}\n')


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A checkout whose newest raw trace is the one made here."""
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    scopes._reduced.clear()
    latent_scopes._reduced.clear()

    def put(cell, text):
        d = tmp_path / ".bench_out" / cell / "trace" / "plugins" / \
            "profile" / "2026_10_05"
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(text))
        return str(d / "vm.xplane.pb")

    return put


def _run(path):
    traced = serve_trace.reduce(trace.load(path))
    return {"trace": traced, "batch_occupancy": 0.75, "kv_occupancy": 0.4,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}}


READERS = ("mla_decode_ms", "mla_prefill_ms", "moe_decode_ms",
           "mla_decode_roofline_pct", "moe_decode_roofline_pct")


def test_the_five_readers_read_the_scopes_of_a_serving_trace(checkout):
    from benchmark.families import pangu_ultra_moe_serve as family

    run = _run(checkout(REAL, _textproto(NAMES)))
    tel = get_telemetry()
    tel.reset()
    tel.counter("moe/layer_steps.decode", 40)
    tel.counter("moe/experts_hit.decode", 520)   # 13 a layer and step
    try:
        read = {n: manifest.metric_reader(n + ".serve")(run)
                for n in READERS}
    finally:
        tel.reset()
    # whole runs: five decodes and five prefills (the first prefill and the
    # last decode are left out)
    assert read["mla_decode_ms"] == pytest.approx(0.010)
    assert read["mla_prefill_ms"] == pytest.approx(0.012)
    assert read["moe_decode_ms"] == pytest.approx(0.015)
    found = manifest.load("BENCHMARK.json", REAL)
    engine = found["cell"]["engine"]
    rows = 0.75 * engine["max_running"]
    live = 0.4 * (engine["kv_blocks"] - 1) * engine["kv_block_size"]
    ops, moved = family.mla_step_work(found["config"], "bfloat16", rows, live)
    assert read["mla_decode_roofline_pct"] == pytest.approx(
        100.0 * max(ops / 197e12, moved / 819e9) / 10e-6)
    assert read["moe_decode_roofline_pct"] == pytest.approx(
        100.0 * family.moe_step_bytes(found["config"], 13.0) / 819e9 / 15e-6)


def test_the_readers_read_nothing_where_no_scope_is_named(checkout):
    """The parent's program (no ``mla``, no ``moe``, no counters), and a
    cell of another family."""
    get_telemetry().reset()
    run = _run(checkout(REAL, _textproto(NAMES, scoped=False)))
    for n in READERS:
        assert manifest.metric_reader(n + ".serve")(run) is None
    latent_scopes._reduced.clear()
    run = _run(checkout("falcon-h1-34b.serve-closed-chat",
                        _textproto(NAMES)))
    for n in ("mla_decode_roofline_pct", "moe_decode_roofline_pct"):
        assert manifest.metric_reader(n + ".serve")(run) is None
    run["device"]["platform"] = "cpu"
    assert latent_scopes.device_ms(run, "mla", "decode") is None
    assert latent_scopes.device_ms({"trace": None, "device": run["device"]},
                                   "moe", "decode") is None
