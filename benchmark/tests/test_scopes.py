"""The reduction from a raw trace to device time by scope and to the
program's host spans, on the hand-made fixture (``make_scope_fixture.py``
says what is in it), and the seven metrics that read it."""
import json
import os
import time

import pytest
from jax.profiler import ProfileData

from benchmark.lib import manifest, scopes, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "scope_fixture.textproto")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
METRICS = {  # a step of the fixture, in milliseconds
    "attention_ms.train": 0.018, "trunk_ms.train": 0.034,
    "head_loss_ms.train": 0.010, "optimizer_ms.train": 0.030,
    "unscoped_ms.train": 0.004, "h2d_ms.train": 0.002,
    "jit_call_ms.train": 0.014}


@pytest.fixture(scope="module")
def serialized():
    with open(FIXTURE) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def planes():
    return trace.load(FIXTURE)


@pytest.fixture
def checkout(tmp_path, monkeypatch, serialized):
    """A checkout whose newest raw trace is the fixture, and a stale one
    of another cell beside it."""
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    scopes._reduced.clear()

    def put(cell, stamp, data, age_s):
        d = tmp_path / ".bench_out" / cell / "trace" / "plugins" / \
            "profile" / stamp
        d.mkdir(parents=True)
        path = d / "vm.xplane.pb"
        path.write_bytes(data)
        os.utime(path, (time.time() - age_s,) * 2)
        return str(path)

    # the stale file sorts last by name: only its time says it is old
    put("other.cell", "2099_01_01", b"", age_s=600)
    return put("this.cell", "2026_10_01", serialized, age_s=0)


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/transpose(jvp(f))/head_loss/dot_general:", "head_loss"),
    ("jit(train_step)/transpose(jvp(head_loss))/dot_general:", "head_loss"),
    ("jit(train_step)/jvp(self_attn)/attention/dot_general:", "attention"),
    ("jit(train_step)/transpose(jvp(self_attn/attention))/mul:", "attention"),
    ("jit(train_step)/jvp(self_attn)/add:", "self_attn"),
    ("jit(train_step)/optimizer/jit(_where)/select_n:", "optimizer"),
    ("jit(train_step)/jit(dot_product_attention)/mul:", "unscoped"),
    ("jit(train_step)/jit(attention)/mul:", "unscoped"),
    ("jit(step_core)/transpose(jvp(bhqk,bkhd->bqhd))/dot_general:",
     "unscoped"),
    ("", "unscoped"),
])
def test_a_scope_is_a_whole_component(path, scope):
    assert scopes.scope_of(path) == scope


def test_the_stat_is_read_from_the_events_metadata(serialized):
    paths = scopes.op_paths(serialized)
    device = paths["/device:TPU:0"]
    assert len(device) == 9  # ten operations, one without the stat
    assert device["%gather.1 = bf16[8,64]{1,0} gather(bf16[512,64]{1,0} "
                  "%p.0)"] == "jit(train_step)/jvp(embed)/gather:"
    assert not any(name.startswith("%copy.1") for name in device)
    # ProfileData shows an event's own stats only: why the bytes are read
    ops = next(line for line in ProfileData.from_serialized_xspace(
        serialized).find_plane_with_name("/device:TPU:0").lines
        if line.name == "XLA Ops")
    assert all("tf_op" not in dict(e.stats) for e in ops.events)


def test_device_time_by_scope_over_whole_steps(planes, serialized):
    got = scopes.reduce(planes, scopes.op_paths(serialized))
    assert got["steps"] == 3 and got["chips"] == 1 and got["scoped"]
    assert got["ms"] == pytest.approx({
        "embed": 0.004, "self_attn": 0.010, "attention": 0.018,
        "mlp": 0.020, "head_loss": 0.010, "optimizer": 0.030,
        "unscoped": 0.004})
    # of the unscoped 4 us, 2 are the copy that has no path at all
    assert got["unnamed_ms"] == pytest.approx(0.002)
    assert got["events"] == {"embed": 3, "self_attn": 3, "attention": 6,
                             "mlp": 3, "head_loss": 6, "optimizer": 3,
                             "unscoped": 6}
    # the split is whole: it sums to every operation's time, which is the
    # busy time of lib.trace plus the one overlapping microsecond a step
    reduced = trace.reduce(planes)
    assert sum(got["ms"].values()) == pytest.approx(0.096)
    assert 1e3 * reduced["busy_s"] / reduced["steps"] == pytest.approx(0.095)
    assert got["steps"] == reduced["steps"]


def test_host_spans_and_the_gaps_under_them(planes, serialized):
    got = scopes.reduce(planes, scopes.op_paths(serialized))
    assert got["span_ms"] == pytest.approx(
        {"pt.step": 0.020, "pt.h2d": 0.002, "pt.compute": 0.014})
    assert got["span_count"] == {"pt.step": 3, "pt.h2d": 3, "pt.compute": 3}
    # [65, 70) of each step lies under pt.step and pt.compute alike: the
    # innermost is named, and the harness's bench.dispatch is not the
    # program's to name
    assert got["program_idle_gaps_ms"] == pytest.approx({"pt.compute": 0.015})
    assert scopes.span_at([("pt.a", 0, 10)], 20, 30) == \
        "outside_program_spans"


def test_newest_file_by_time_not_by_name(checkout):
    assert scopes.newest_raw_trace() == checkout
    assert scopes.newest_raw_trace(os.path.dirname(checkout)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads(name, checkout, planes, capfd):
    run = {"device": TPU, "trace": trace.reduce(planes)}
    read = manifest.metric_reader(name)
    assert read(run) == pytest.approx(METRICS[name])
    # the raw trace is reduced once, and noted once
    assert read(run) == pytest.approx(METRICS[name])
    notes = [json.loads(x) for x in capfd.readouterr().out.splitlines()]
    (note,) = [n for n in notes if n["note"] == "scopes"]
    assert note["op_ms_a_step"] == pytest.approx(0.096)
    assert note["busy_ms_a_step"] == pytest.approx(0.095)
    assert note["scopes"]["attention"]["share"] == pytest.approx(18 / 96)
    assert note["program_idle_gaps_ms"] == pytest.approx({"pt.compute": 0.015})
    # off the chip, and where the run made no trace, there is no number
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert read({**run, "device": cpu}) is None
    assert read({**run, "trace": None}) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_scopes_or_spans_reads_nothing(name, planes,
                                                         monkeypatch):
    """The parent of PR 27: every operation unscoped, no ``pt.*`` span."""
    bare = {"/device:TPU:0": planes["/device:TPU:0"],
            "/host:CPU": {"python3": [
                e for e in planes["/host:CPU"]["python3"]
                if not e[0].startswith("pt.")]}}
    got = scopes.reduce(bare, {})
    assert not got["scoped"] and got["span_ms"] == {}
    assert got["ms"]["unscoped"] == pytest.approx(0.096)
    assert got["program_idle_gaps_ms"] == pytest.approx(
        {"outside_program_spans": 0.015})
    run = {"device": TPU, "trace": {"busy_s": 1.0, "steps": 3}}
    monkeypatch.setattr(scopes, "_reduced", {"the.path": got})
    monkeypatch.setattr(scopes, "newest_raw_trace",
                        lambda root=None: "the.path")
    assert manifest.metric_reader(name)(run) is None


def test_no_device_plane_reads_nothing(planes, tmp_path, monkeypatch):
    assert scopes.reduce({"/host:CPU": planes["/host:CPU"]}, {}) is None
    # and no raw trace in the checkout at all
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    run = {"device": TPU, "trace": {"busy_s": 1.0, "steps": 3}}
    assert scopes.device_ms(run, "attention") is None
    assert scopes.span_ms(run, "pt.h2d") is None


def test_every_new_metric_lists_both_cells():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = next(m["workloads"] for m in bench["end_to_end"]
                 if m["name"] == "train_tokens_per_s")  # the training cells
    for name in METRICS:
        m = entries[name]
        assert m["workloads"] == cells and m["unit"] == "ms"
        assert (m["source"], m["better"], m["moves"]) == \
            ("device_trace", "lower", "train_tokens_per_s")
        assert os.path.exists(os.path.join(
            manifest.ROOT, "benchmark", "metrics", name + ".py"))
