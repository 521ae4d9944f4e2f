"""The parts of the compiled train step have names, and the engine's spans
are on the profiler's clock.

- ``jax.named_scope`` names (``hlo_attrib.SCOPES``) reach the compiled
  text of ``fleet.ParallelTrainStep``'s program for a GPT and a BERT,
  forward and backward, and change no operation;
- ``profiler.spans.Span`` opens a ``pt.<name>`` ``TraceAnnotation``, so a
  ``jax.profiler`` trace holds ``pt.step`` / ``pt.h2d`` / ``pt.compute``;
- ``hlo_attrib.load_trace`` reads the ``.xplane.pb`` this jaxlib writes,
  and ``attribute_trace`` books the device time by scope.
"""
import collections
import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
from paddle_tpu.profiler import get_telemetry, hlo_attrib
from paddle_tpu.profiler.hlo_attrib import SCOPES, scope_of
from paddle_tpu.text.models.bert import BertConfig, BertForPretraining
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

ENTRY = "fleet.train_step"
BATCH, SEQ, VOCAB = 2, 64, 512


@pytest.fixture(autouse=True)
def _clean_profiler_state():
    get_telemetry().reset()
    yield
    get_telemetry().reset()


def _build(family):
    """A two-layer model under the engine as the benchmark's cells build
    it (bf16 compute, f32 masters, one device), and a call of one step."""
    paddle.seed(0)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (BATCH, SEQ), dtype=np.int32)
    common = dict(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                  num_heads=2, max_position_embeddings=SEQ,
                  hidden_dropout=0.0, attention_dropout=0.0)
    if family == "gpt":
        model = GPTForCausalLM(GPTConfig(**common))
        opt = paddle.optimizer.Adam(
            learning_rate=1e-4, parameters=model.parameters(),
            multi_precision=True)
        loss_fn = lambda out, lbl: out  # noqa: E731
        labels = np.roll(ids, -1, axis=1)
        batch = ((ids, labels), (labels,))
    else:
        model = BertForPretraining(BertConfig(intermediate_size=128,
                                              **common))
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4, weight_decay=0.01,
            parameters=model.parameters(), multi_precision=True)
        loss_fn = model.loss_fn
        mlm = np.where(rng.random((BATCH, SEQ)) < 0.15, ids,
                       -100).astype(np.int32)
        batch = ((ids, np.zeros_like(ids), np.ones_like(ids)),
                 (mlm, np.array([0, 1], np.int32)))
    step = ParallelTrainStep(model, loss_fn=loss_fn, optimizer=opt,
                             mesh=mesh, zero_stage=0, recompute=False,
                             compute_dtype=jnp.dtype("bfloat16"))
    return step, lambda: step(*batch)


def _compiled_ops(step, call):
    """One step, then every instruction of the compiled program."""
    loss = float(call().numpy())
    assert np.isfinite(loss)
    text = hlo_attrib.hlo_registry().text_for(ENTRY)
    assert text and "jit_train_step" in text
    return hlo_attrib.parse_hlo_text(text)


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_compiled_step_names_its_parts_and_changes_no_operation(
        family, monkeypatch):
    step, call = _build(family)
    ops = _compiled_ops(step, call)
    forward = {scope_of(op.op_name) for op in ops.values()
               if "transpose(" not in op.op_name}
    backward = {scope_of(op.op_name) for op in ops.values()
                if "transpose(" in op.op_name}
    assert set(SCOPES) <= forward
    # the optimizer's update is differentiated by nobody
    assert set(SCOPES) - {"optimizer"} <= backward
    assert "optimizer" not in backward

    # the same build with the scopes taken out: the same operations
    compiles = step._jitted.tracker.compiles
    get_telemetry().reset()  # the registry keeps one text an entry
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_step, bare_call = _build(family)
    bare = _compiled_ops(bare_step, bare_call)
    assert {scope_of(op.op_name) for op in bare.values()} == {"unscoped"}
    assert len(bare) == len(ops)
    assert (collections.Counter(op.opcode for op in bare.values())
            == collections.Counter(op.opcode for op in ops.values()))
    assert bare_step._jitted.tracker.compiles == compiles == 1


@pytest.mark.parametrize("op_name,scope", [
    # both spellings JAX has used for a scope under a transform
    ("jit(train_step)/transpose(jvp(f))/head_loss/dot_general", "head_loss"),
    ("jit(train_step)/transpose(jvp(head_loss))/dot_general", "head_loss"),
    # the innermost known name wins
    ("jit(train_step)/jvp(self_attn)/attention/exp", "attention"),
    ("jit(train_step)/transpose(jvp(self_attn/attention))/mul", "attention"),
    ("jit(train_step)/jvp(self_attn)/attention/jit(_where)/select_n",
     "attention"),
    ("jit(train_step)/optimizer/add", "optimizer"),
    # a whole component only, and a function is not a scope
    ("jit(dot_product_attention)/mul", "unscoped"),
    ("jit(train_step)/jit(attention)/mul", "unscoped"),
    ("jit(train_step)/jvp(embedding)/gather", "unscoped"),
    ("reduce_sum", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


@pytest.fixture
def traced_steps(tmp_path):
    """Three traced steps of the tiny GPT, waited for before the trace
    stops; (step, path of the .xplane.pb)."""
    step, call = _build("gpt")
    call()
    first = step._optimizer._global_step
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            loss = call()
        float(loss.numpy())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return step, first, path


def test_spans_are_on_the_profilers_clock(traced_steps):
    from jax.profiler import ProfileData

    _, first, path = traced_steps
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    spans = collections.defaultdict(list)
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("pt."):
                spans[e.name].append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    assert {"pt.step", "pt.h2d", "pt.compute"} <= set(spans)
    assert [len(spans[n]) for n in ("pt.step", "pt.h2d", "pt.compute")] \
        == [3, 3, 3]
    # a host step can be paired with the run it dispatched
    assert [s["step"] for _, _, s in spans["pt.step"]] \
        == [first, first + 1, first + 2]
    for (lo, hi, _), h2d, compute in zip(spans["pt.step"], spans["pt.h2d"],
                                         spans["pt.compute"]):
        assert lo <= h2d[0] and h2d[1] <= compute[0] and compute[1] <= hi
        assert "step" not in h2d[2]
    # nothing compiled inside the stretch
    assert "pt.compile" not in spans


def test_compile_is_a_span():
    from paddle_tpu.profiler import spans

    step, call = _build("gpt")
    # the ring is the process's: a serving test that ran on this worker
    # before leaves `compile` spans that no `compute` span encloses
    spans.flight_recorder().clear()
    call()
    names = [e[1] for e in spans.flight_recorder().tail() if e[0] == "B"]
    assert names.index("compute") < names.index("compile")
    assert step._jitted.tracker.compiles == 1


def test_xplane_events_name_their_instruction(traced_steps):
    _, _, path = traced_steps
    trace = hlo_attrib.load_trace(path)
    ops = [e for e in trace["traceEvents"]
           if e["ph"] == "X" and "hlo_op" in e["args"]]
    assert ops
    known = hlo_attrib.parse_hlo_text(
        hlo_attrib.hlo_registry().text_for(ENTRY))
    modules = {e["args"].get("hlo_module") for e in ops}
    assert "jit_train_step" in modules
    ours = [e for e in ops if e["args"].get("hlo_module") == "jit_train_step"]
    assert all(e["name"] == e["args"]["hlo_op"] for e in ours)
    assert all(e["name"] in known for e in ours)
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in ours)
    # the directory form finds the same file
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(path))))
    assert hlo_attrib.newest_trace_path(root) == path


def test_scope_table_sums_to_the_entrys_device_time(traced_steps):
    _, _, path = traced_steps
    report = hlo_attrib.attribute_trace(
        hlo_attrib.load_trace(path),
        {ENTRY: hlo_attrib.hlo_registry().text_for(ENTRY)},
        steps={ENTRY: 3}, wall_ms=1e3)
    att = report.entries[ENTRY]
    assert set(att.scope_ms) == set(SCOPES) | {"unscoped"}
    assert sum(att.scope_ms.values()) == pytest.approx(att.device_ms)
    assert sum(ms > 0 for s, ms in att.scope_ms.items()
               if s != "unscoped") >= 4
    rows = report.to_dict()["scopes"]
    assert [r["scope"] for r in rows] == list(SCOPES) + ["unscoped"]
    assert sum(r["ms"] for r in rows) == pytest.approx(
        report.to_dict()["entries"][ENTRY]["device_ms"], abs=1e-4)
    assert sum(r["frac"] for r in rows) == pytest.approx(1.0, abs=1e-4)
    assert all(r["ms_per_step"] == pytest.approx(r["ms"] / 3, abs=1e-6)
               for r in rows)
