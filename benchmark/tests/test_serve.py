"""The serve driver at a tiny size on any backend: a well-formed last line
in both loop kinds, a schedule that is a pure function of the seed, an open
loop that times from the due time, ``correct`` that the program passes and
that the control and each planted fault fail, and the counts of bytes and
operations against a hand count."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import ROOT
from benchmark.lib import compare, compare_serve, loadgen, manifest

SERVE = "benchmark/tests/rehearsal_serve/BENCHMARK.json"
CELLS = {"open": "gpt2-tiny.serve-open", "closed": "gpt2-tiny.serve-closed"}
E2E = {"open": {"rehearsal.ttft_p95_ms", "rehearsal.tpot_p95_ms",
                "rehearsal.setup_s"},
       "closed": {"rehearsal.serve_tokens_per_s", "rehearsal.tpot_p95_ms",
                  "rehearsal.setup_s"}}
# what only a chip can give is not in a CPU run's line at all
HOST_LAYERS = {"rehearsal." + n + ".serve" for n in (
    "sched_iter_ms", "sched_decode_ms", "sched_prefill_ms", "sched_host_ms",
    "batch_occupancy_pct", "kv_occupancy_pct", "tpot_p95_ms")}


def _notes(stdout: str) -> dict:
    out = {}
    for text in stdout.strip().splitlines()[:-1]:
        if text.startswith("{"):
            note = json.loads(text)
            out[note.pop("note")] = note
    return out


@pytest.mark.parametrize("loop", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(loop, trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", SERVE,
         "--workload", CELLS[loop], "--seed", str(2**31 + 5), "--seconds",
         "1.5", "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    want = HOST_LAYERS if trace else E2E[loop]
    assert set(line["metrics"]) == want
    assert all(m["value"] >= 0 for m in line["metrics"].values())
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    assert line["compared"]["token_margin_gap"]["limit"] is not None
    notes = _notes(proc.stdout)
    window = notes["window"]
    # attempted and failed add up, and nothing compiled inside the window
    assert line["attempted"] == window["attempted"] > 0
    assert window["ok"] + window["failed"] == window["attempted"]
    assert sum(window["statuses"].values()) == window["attempted"]
    assert window["compiles_in_window"] == 0
    # the engine's counter and the requests' stamps count the same tokens
    assert abs(window["tokens_generated"] - window["tokens_by_stamps"]) <= \
        0.05 * window["tokens_generated"] + 8
    assert notes["paged_tier"]["forced"] == "paged_gather"
    assert set(notes["paged_tier"]["gauges"].values()) == {"paged_gather"}
    assert notes["accounting"]["kv"]["leaked_blocks"] == 0


def _plan(loop, seed, seconds=2.0):
    found = manifest.load(SERVE, CELLS[loop])
    return found, loadgen.schedule(found["cell"]["traffic"], seed,
                                   found["config"]["vocab_size"], seconds)


@pytest.mark.parametrize("loop", sorted(CELLS))
def test_schedule_is_a_pure_function_of_the_seed(loop):
    (_, a), (_, b), (_, c) = (_plan(loop, s) for s in (2**31 + 9, 2**31 + 9,
                                                       5))
    assert a["new_tokens"] == b["new_tokens"] and a["due_s"] == b["due_s"]
    assert a["first_share"] == b["first_share"]
    assert all((x == y).all() for x, y in zip(a["prompts"], b["prompts"]))
    # another seed: the same set of lengths (and of gaps), in another order
    assert sorted(a["new_tokens"]) == sorted(c["new_tokens"])
    assert sorted(map(len, a["prompts"])) == sorted(map(len, c["prompts"]))
    assert list(map(len, a["prompts"])) != list(map(len, c["prompts"]))
    if loop == "closed":
        # the answers' lengths are a closed loop's arrivals: one order for
        # every seed, and so the first requests' shares
        assert a["new_tokens"] == c["new_tokens"]
        assert a["first_share"] == c["first_share"]
        assert sorted(a["first_share"]) == [(i + 0.5) / 8 for i in range(8)]
    else:
        assert a["new_tokens"] != c["new_tokens"]
    if loop == "open":
        n, rate = len(a["due_s"]), 20.0
        whole = np.round(-np.log1p(-(np.arange(n) + 0.5) / n) / rate, 9)
        for p in (a, c):  # all gaps but the first one drawn, which is cut
            gaps = np.round(np.diff(p["due_s"]), 9)
            assert len(p["due_s"]) == n and np.isin(gaps, whole).all()
            assert len(set(gaps)) == n - 1


def test_lengths_follow_the_file():
    found, plan = _plan("open", 3, seconds=20.0)
    spec = found["cell"]["traffic"]["prompt_tokens"]
    lens = np.array([len(p) for p in plan["prompts"]])
    assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
    assert abs(np.median(lens) - spec["median"]) <= 1
    rate = found["cell"]["traffic"]["rate_per_s"]
    assert abs(len(lens) / plan["due_s"][-1] - rate) < 0.1 * rate


class _Req:
    """A request that is done ``service_s`` after it was submitted."""

    def __init__(self, service_s):
        self._at = time.monotonic() + service_s
        self.status = "ok"

    def done(self):
        return time.monotonic() >= self._at


def _drive(loop, stall_at=None, seconds=1.0):
    found, plan = _plan(loop, 17, seconds)
    hook = (lambda i: time.sleep(0.2) if i == stall_at else None)
    budgets = []
    run = loadgen.LoadRun(found["cell"]["traffic"], plan, seconds,
                          submit=lambda prompt, new: (budgets.append(new),
                                                      _Req(0.02))[1],
                          before_submit=hook).start()
    run.join()
    return run, plan, budgets


def test_open_loop_times_from_the_due_time():
    """A generator that stalls 200 ms shows it: the stalled request and
    those that fell due behind it are submitted late, and their due times
    (from which latency is taken) do not move."""
    clean, stalled = _drive("open")[0], _drive("open", stall_at=12)[0]
    assert np.percentile(clean.lag_ms(), 95) < 60  # a busy sandbox
    assert stalled.lag_ms().max() >= 190
    assert np.percentile(stalled.lag_ms(), 95) > \
        np.percentile(clean.lag_ms(), 95) + 30
    due = lambda run: [s.due - run.t_start for s in run.sent]  # noqa: E731
    assert np.allclose(due(clean)[:30], due(stalled)[:30])
    late = [s for s in stalled.sent if s.submitted - s.due > 0.03]
    assert len(late) >= 2  # the stalled one and what queued behind it


def test_closed_loop_keeps_every_client_busy():
    run, plan, budgets = _drive("closed")
    clients = 8
    assert len(run.sent) > 3 * clients
    due = np.array([s.due - run.t_start for s in run.sent])
    for c in range(clients):  # first submissions are spread over the ramp
        assert np.isclose(due, 0.3 * c / clients, atol=1e-9).any()
    # each client's first request keeps its share of its new tokens, the
    # later ones all of theirs
    firsts = 0
    for s, new in zip(run.sent, budgets):
        c = (s.due - run.t_start) * clients / 0.3
        whole = plan["new_tokens"][s.slot]
        if abs(c - round(c)) < 1e-6 and c < clients - 0.5:
            firsts += 1
            share = plan["first_share"][int(round(c))]
            assert new == max(1, int(np.ceil(share * whole)))
        else:
            assert new == whole
    assert firsts == clients
    # client c's g-th request is entry c + g * clients, whoever is free first
    assert [s.index for s in run.sent] == list(range(len(run.sent)))
    by_client = {}
    for s in run.sent:
        by_client.setdefault(s.slot % clients, []).append(s.slot)
    n = len(plan["new_tokens"])
    assert all(slots == [(c + g * clients) % n for g in range(len(slots))]
               for c, slots in by_client.items())
    # a request lasts 20 ms, a look comes every 10: a second holds some
    # thirty a client, and the window's are those that ended inside it
    window = run.attempted()
    assert len(window) > 15 * clients
    assert all(run.at_open[0] <= s.ended < run.at_close[0] for s in window)
    assert 0 < len(run.in_flight()) <= clients
    assert not set(map(id, run.in_flight())) & set(map(id, window))


# -- correct -----------------------------------------------------------------

def _job(loop, seed, **extra):
    found = manifest.load(SERVE, CELLS[loop])
    cell = {**found["cell"], "chips": 1}
    return cell, {"cell": cell, "config": found["config"], "seed": seed,
                  "seconds": 1.0, "trace": False,
                  "t0": time.perf_counter(), "trace_dir": None, **extra}


def _verdict(cell, result):
    return compare.verdict(result["numbers"], cell["limits"])


@pytest.fixture(scope="module")
def control_runs():
    """The program's and the float8 control's numbers on three seeds, the
    control read over the very prompts and tokens the program served."""
    from benchmark.drivers import serve

    out = []
    for seed in (1, 2, 3):
        cell, job = _job("closed", seed, controls=("float8",))
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
    ok, compared = _verdict(cell, result)
    assert ok and result["failed"] == 0, compared
    assert result["numbers"]["tokens_compared"] >= 50
    bad, compared = compare.verdict(control, cell["limits"])
    assert not bad, compared


@pytest.mark.parametrize("fault", ["altered_token", "block_table_mixup"])
def test_a_planted_fault_is_not_correct(fault):
    """The rest of a run with the timed path broken underneath: a token
    altered where it is produced; one sequence's block table pointing to
    another's page."""
    from benchmark.drivers import serve

    cell, job = _job("closed", 4, fault=fault)
    result = serve.run(job)
    ok, compared = _verdict(cell, result)
    assert not ok, compared


def test_the_names_the_harness_reaches_for_are_the_programs():
    """What ``drivers/serve.py`` and ``lib/faults_serve.py`` take from the
    program beside its public surface: a PR that renames one of these fails
    here and in a traced run, not in a metric that falls silent."""
    import types

    from benchmark.drivers import serve
    from benchmark.families import gpt_serve

    found = manifest.load(SERVE, CELLS["closed"])
    model = gpt_serve.build_model(found["config"], gpt_serve.weights(
        found["config"], 1, "bfloat16"))
    eng = gpt_serve.build_engine(model, found["cell"]["engine"])
    try:
        sched = eng._scheduler
        for attr in (*serve.Annotated.NAMES, "_append_token"):
            assert callable(getattr(sched, attr)), attr
        with serve.Annotated(eng):  # puts both annotations on, and off again
            assert set(serve.Annotated.NAMES) <= set(vars(sched))
        assert not set(serve.Annotated.NAMES) & set(vars(sched))
    finally:
        eng.shutdown()
    renamed = types.SimpleNamespace(_scheduler=types.SimpleNamespace(
        _decode_round=lambda *a: None))
    with pytest.raises(AttributeError, match="_prefill_chunk"):
        serve.Annotated(renamed).__enter__()


def test_the_stamps_and_the_pool_are_where_the_harness_reads_them():
    from benchmark.drivers import serve
    from benchmark.families import gpt_serve

    found = manifest.load(SERVE, CELLS["closed"])
    config = found["config"]
    model = gpt_serve.build_model(config, gpt_serve.weights(
        config, 1, "bfloat16"))
    eng = gpt_serve.build_engine(model, found["cell"]["engine"])
    eng.start(warmup=False)
    try:
        req = eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=4,
                         eos_id=None)
        assert req.wait(120.0) and req.status == serve.OK
        assert req.first_token_at < req.last_token_at
        assert len(req.outputs[0]) == 4
        assert "queue_depth" in serve.engine_gauges(eng)()
        assert isinstance(eng.pool._owned, dict)
        assert callable(eng.pool.block_table)
        read = serve.engine_counters(found["cell"]["engine"])()
        assert read["serve/tokens_generated"] >= 4
        assert read["serve/decode_steps"] >= 3
        assert read["serve/prefill_ms.count"] >= 1
    finally:
        eng.shutdown()


def test_nothing_to_compare_is_not_correct():
    nums = compare_serve.numbers(np.zeros((0, 4)), np.zeros((0, 4), bool))
    ok, _ = compare.verdict(nums, {"token_margin_gap": 0.04})
    assert not ok


def test_sample_holds_the_longest_and_follows_the_seed():
    done = [(np.arange(5 + i), np.arange(3)) for i in range(20)]
    a, b, c = (compare_serve.sample(done, s, 6) for s in (7, 7, 8))
    assert len(a) == 6 and len(a[0][0]) == 24
    assert [len(p) for p, _ in a] == [len(p) for p, _ in b]
    assert [len(p) for p, _ in a] != [len(p) for p, _ in c]
    ids, served, mask = compare_serve.rows(a[:1], 32)
    assert mask.sum() == 3 and mask[0, 23:26].all()
    assert (served[0, 23:26] == np.arange(3)).all()
    assert (ids[0, :24] == np.arange(24)).all()


# -- counts from shapes ------------------------------------------------------

def test_decode_bytes_and_operations_against_a_hand_count():
    with open(os.path.join(ROOT, "benchmark/configs/gpt2-345m.json")) as f:
        config = json.load(f)
    from benchmark.families import gpt_serve

    # K and V: 24 layers x 1024 wide x 2 B each
    assert gpt_serve.kv_bytes_per_token(config, "bfloat16") == 98304
    # per layer: qkv 1024x3072, proj 1024x1024, fc 1024x4096, proj 4096x1024
    trunk = 24 * (3145728 + 1048576 + 4194304 + 4194304)
    small = 24 * (3072 + 1024 + 4096 + 1024 + 4 * 1024) + 2 * 1024
    head = 50304 * 1024
    assert gpt_serve.weight_bytes(config) == 2 * (trunk + small + head)
    assert gpt_serve.decode_step_bytes(config, "bfloat16", 1000) == \
        2 * (trunk + small + head) + 1000 * 98304
    # 10 positions forwarded, 55 pairs attended, 3 tokens emitted
    assert gpt_serve.forward_flops(config, 10, 55, 3) == \
        2.0 * 10 * trunk + 4.0 * 1024 * 24 * 55 + 2.0 * 3 * head


def test_served_work_counts_a_request_by_hand():
    from benchmark.drivers.serve import served_work

    # 4 prompt tokens, 5 emitted at t = 10, 11, 12, 13, 14
    r = {"prompt": np.arange(4), "emitted": np.arange(5), "first": 10.0,
         "last": 14.0}
    w = served_work([r], 9.5, 12.5)
    # the prompt (4 positions, 4 * 5 / 2 pairs) and its first token, then
    # the tokens at 11 and 12: one position each, attending to 5 and 6
    assert (w["prompt_tokens"], w["first_tokens"], w["later_tokens"]) == \
        (4, 1, 2)
    assert w["attended"] == 10 + 5 + 6
    w = served_work([r], 12.5, 20.0)  # the tokens at 13 and 14
    assert (w["prompt_tokens"], w["later_tokens"], w["attended"]) == \
        (0, 2, 7 + 8)
