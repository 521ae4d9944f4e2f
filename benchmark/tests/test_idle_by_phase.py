"""The split of a serving trace's idle device time by the token scheduler's
own phase spans (``lib.serve_spans``), on the hand-made trace of
``make_serve_spans_fixture.py`` and on the same trace without the spans,
as the program before them writes it."""
import json
import os

import pytest

from benchmark.lib import manifest, scopes, serve_spans, serve_trace, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "serve_spans_fixture.textproto")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# idle us between two runs booked to each name (the fixture's docstring)
GAP = {"fetch": 5, "tokens": 10, "retire": 5, "admit": 7, "blocks": 6,
       "arrays": 10, "dispatch": 5, serve_spans.UNSPANNED: 2}
METRICS = {"idle_in_step_ms.serve": 0.005,
           "idle_arrays_ms.serve": 3 * 0.010 / 4,
           "idle_dispatch_ms.serve": 3 * 0.005 / 4,
           "idle_fetch_ms.serve": 3 * 0.005 / 4,
           "idle_books_ms.serve": 3 * 0.028 / 4,
           "idle_unspanned_pct.serve": 100 * 6 / 170}


@pytest.fixture
def planes():
    return trace.load(FIXTURE)


@pytest.fixture
def newest(monkeypatch):
    """Point the readers at a trace file; a fresh cache of reductions."""
    monkeypatch.setattr(serve_spans, "_reduced", {})

    def use(path):
        monkeypatch.setattr(scopes, "newest_raw_trace",
                            lambda root=None: path)
    return use


def test_the_fixture_is_what_its_generator_writes(tmp_path, monkeypatch):
    from benchmark.tests import make_serve_spans_fixture as make

    monkeypatch.setattr(make.os.path, "abspath",
                        lambda _: str(tmp_path / "make.py"))
    make.main()
    with open(FIXTURE) as f:
        assert (tmp_path / "serve_spans_fixture.textproto").read_text() \
            == f.read()


def test_a_gap_between_runs_is_apportioned_by_overlap(planes):
    got = serve_spans.reduce(planes)
    assert got["chips"] == 1 and got["decode_runs"] == 4
    # three gaps of 50 us between the four whole runs, each cut into the
    # nine pieces the host's spans make of it, not booked whole to the
    # span that covers most of it (fetch, dispatch and arrays all meet
    # one gap); 5 us inside each of the four runs
    want = {name: 3 * us / 1e3 for name, us in GAP.items()}
    want[serve_spans.IN_STEP] = 4 * 5 / 1e3
    assert got["idle_ms"] == pytest.approx(want)
    assert got["idle_ms_total"] == pytest.approx(0.170)
    # spans are counted where they lie whole inside [220, 570)
    assert got["span_count"]["pt.serve.fetch"] == 3
    assert got["span_ms"]["pt.serve.fetch"] == pytest.approx(0.055)
    assert got["span_ms"]["pt.serve.iter"] == pytest.approx(0.099)


def test_the_split_is_whole_against_the_idle_share(planes):
    """In step, the four phase metrics and the unspanned share add up to
    the stretch's idle ms a decode run as ``device_idle_pct.serve``,
    ``window_s`` and the count of decode runs give it."""
    traced = serve_trace.reduce(planes)
    runs = len(traced["runs"]["decode"])
    whole = traced["idle_share"] * traced["window_s"] * 1e3 / runs
    got = serve_spans.reduce(planes)
    parts = sum(got["idle_ms"].values()) / got["decode_runs"]
    assert parts == pytest.approx(whole) and whole == pytest.approx(0.0425)


def test_the_readers_on_a_chip_run(planes, newest, capsys):
    newest(FIXTURE)
    run = {"device": TPU, "trace": serve_trace.reduce(planes)}
    read = {name: manifest.metric_reader(name)(run) for name in METRICS}
    assert read == pytest.approx(METRICS)
    in_ms = sum(v for k, v in read.items() if k.endswith("_ms.serve"))
    idle = read["idle_unspanned_pct.serve"] / 100 * 0.0425
    assert in_ms + idle == pytest.approx(0.0425)
    notes = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [n["note"] for n in notes] == ["serve_spans"]  # once a trace
    assert notes[0]["spans"]["fetch"]["count"] == 3
    assert notes[0]["spans"]["arrays"]["idle_ms_a_decode_run"] \
        == pytest.approx(0.0075)
    for name in METRICS:
        assert manifest.metric_reader(name)({**run, "device": CPU}) is None
        assert manifest.metric_reader(name)({**run, "trace": None}) is None


def test_a_program_without_the_spans_reads_nothing(planes, newest,
                                                   tmp_path):
    """The parent of the PR that opens the spans: the same device, no
    ``pt.serve.*`` event on the host."""
    with open(FIXTURE) as f:
        device_only = f.read().split("planes { id: 2")[0]
    path = tmp_path / "no_spans.textproto"
    path.write_text(device_only)
    newest(str(path))
    assert serve_spans.reduce(trace.load(str(path))) is None
    run = {"device": TPU, "trace": serve_trace.reduce(planes)}
    for name in METRICS:
        assert manifest.metric_reader(name)(run) is None


def test_each_new_metric_is_in_the_manifest_for_the_serve_cells():
    with open(os.path.join(scopes.ROOT, "BENCHMARK.json")) as f:
        found = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in METRICS:
        m = found[name]
        assert m["layer"] == "serve scheduler"
        assert m["moves"] == "serve_tokens_per_s"
        assert len(m["workloads"]) == 3
