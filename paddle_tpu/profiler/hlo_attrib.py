"""HLO ↔ device-trace attribution: per-op / per-source-line / per-category
step-time decomposition.

The promoted, tested library form of ``tools/attribute_profile.py`` (the
one-off script the r4/r5 perf rounds ran by hand). It answers *where a
step's device time went* by joining two artifacts the framework already
produces:

- the **compiled HLO text** of every ``tracked_jit`` entry — op names,
  ``metadata={op_name=... stack_frame_id=...}`` plus the module's stack
  frame tables (``analysis.hlo.parsing.parse_stack_frames``) — captured
  at compile time into the :class:`HloRegistry` by ``xla_cost.capture``
  (full mode stores the optimized text the compile already produced; the
  default mode stores the in-hand ``Lowered`` and compiles to text only
  when a profile actually asks — never a second lowering);
- a **jax.profiler trace** covering a window of steps: the
  ``.xplane.pb`` this jaxlib writes, read through
  ``jax.profiler.ProfileData`` (per-op device durations in the "XLA Ops"
  lines of the ``/device:TPU:<n>`` planes, or the thunk-executor per-op
  events of the CPU runtime, which carry the instruction's name as their
  ``hlo_op`` stat), or a ``.trace.json.gz`` for post-hoc use.

``attribute_trace`` joins them into an :class:`AttributionReport`:
per-op and per-source-line tables, per-category totals (compute /
collective / h2d-d2h transfer), per-scope totals (the ``jax.named_scope``
names of the compiled step, :data:`SCOPES`, read from each instruction's
``op_name``), the host gap (wall time the device sat
idle inside the window: the wall less the union of the operations), that
idle time booked to the program's own ``pt.*`` span that held the host
at each moment (``idle_by_span_ms``), and per-entry fractions whose sum
is ≤ 1 by construction. ``device_profile`` drives it live; the CLI wrapper keeps
the old script's interface for post-hoc use.

Failure contract: parsing is **best-effort** — a malformed / empty /
truncated trace degrades to a warning and ``None``, never an exception
mid-training (profiling must not kill the run it is explaining).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import heapq
import json
import logging
import os
import re
import threading
from typing import Dict, List, Optional, Tuple

from ..analysis.hlo import parsing as _hloparse
from .telemetry import get_telemetry

__all__ = [
    "HloOp", "parse_hlo_text", "categorize_opcode",
    "AttributionReport", "EntryAttribution", "attribute_trace",
    "load_trace", "newest_trace_path", "device_events",
    "HloRegistry", "hlo_registry", "CATEGORIES", "SCOPES", "scope_of",
    "program_spans", "module_runs", "idle_by_span",
]

logger = logging.getLogger("paddle_tpu.profiler")

# the closed category vocabulary of the device-side decomposition; the
# host gap (wall - device busy) is the fourth, computed, category
CATEGORIES = ("compute", "collective", "transfer")

# the closed vocabulary of ``jax.named_scope`` names inside a compiled
# train step (text/models/*, ops/attention.py, fleet/engine.py); device
# time under none of them is booked to ``unscoped``
SCOPES = ("embed", "self_attn", "attention", "mlp", "head_loss", "optimizer")
UNSCOPED = "unscoped"
# the program's own host spans in a trace (``spans.Span`` opens them), and
# the two names ``idle_by_span_ms`` books the device's idle time to where
# no span of the host holds it
SPAN_PREFIX = "pt."
IN_STEP = "in_step"
OUTSIDE_SPANS = "outside_program_spans"
# what JAX wraps around a scope's name when it transforms the function
_TRANSFORMS = frozenset(("jvp", "transpose", "vmap", "checkpoint"))


def scope_of(op_name: str) -> str:
    """The innermost :data:`SCOPES` name in an HLO ``op_name`` path.

    A name counts only as a whole component of the path, bare or inside
    JAX's transform wrappers: ``jit(f)/transpose(jvp(self_attn))/attention/
    dot_general`` and ``jit(f)/jvp(self_attn/attention)/dot_general`` are
    both ``attention``; ``jit(attention)`` and ``dot_product_attention``
    are functions, not scopes."""
    found, heads, token = UNSCOPED, [], ""
    for ch in (op_name or "") + "/":
        if ch not in "()/":
            token += ch
            continue
        if ch == "(":
            heads.append(token)
        elif token in SCOPES and all(h in _TRANSFORMS for h in heads):
            found = token
        if ch == ")" and heads:
            heads.pop()
        token = ""
    return found


_COLLECTIVE_OPCODES = {
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-reduce-done",
    "all-gather-start", "all-gather-done",
    "collective-permute-start", "collective-permute-done",
    "send", "send-done", "recv", "recv-done",
}
_TRANSFER_OPCODES = {
    "copy-start", "copy-done", "infeed", "outfeed",
}


def categorize_opcode(opcode: str, name: str = "") -> str:
    """Map an HLO opcode (or, for unattributed trace events, a name stem)
    onto the closed category vocabulary."""
    op = (opcode or "").lower()
    if op in _COLLECTIVE_OPCODES:
        return "collective"
    if op in _TRANSFER_OPCODES:
        return "transfer"
    stem = re.sub(r"[.\d]+$", "", (name or "").lower())
    if stem in _COLLECTIVE_OPCODES or any(
            stem.startswith(c + "-fusion") for c in ("all-reduce",
                                                     "all-gather")):
        return "collective"
    if stem in _TRANSFER_OPCODES:
        return "transfer"
    return "compute"


@dataclasses.dataclass
class HloOp:
    """One HLO instruction's identity: where it came from in the model
    source and what it is."""

    name: str
    opcode: str = "?"
    src: str = "?"            # "file.py:123" (basename)
    op_name: str = "?"        # XLA op_name path (jit(...)/.../dot_general)

    @property
    def category(self) -> str:
        return categorize_opcode(self.opcode, self.name)


# the low-level text primitives live in analysis.hlo.parsing (shared
# with the standalone hlo-lint, which must not import the framework —
# so the dependency points this way); historic names kept
_NAME_RE = _hloparse.NAME_RE
_opcode_of = _hloparse.opcode_of


def parse_hlo_text(text: str) -> Dict[str, HloOp]:
    """``{instruction_name: HloOp}`` from optimized HLO text. Tolerant:
    lines without metadata still register (opcode + name only), so trace
    events can at least be categorized and counted."""
    ops: Dict[str, HloOp] = {}
    frames = _hloparse.parse_stack_frames(text)
    for name, body, _lineno in _hloparse.iter_instruction_lines(text):
        instr = _hloparse.HloInstr(name=name, opcode=_opcode_of(body),
                                   type_text="", body=body, line=_lineno,
                                   computation="")
        ops[name] = HloOp(name=name, opcode=instr.opcode,
                          src=_hloparse.source_of(body, frames),
                          op_name=instr.op_name())
    return ops


# -- trace loading ------------------------------------------------------------

def newest_trace_path(logdir: str) -> Optional[str]:
    """The newest capture under ``logdir``: its ``.xplane.pb`` (what the
    profiler itself writes, every event with its stats), else its
    ``.trace.json.gz``."""
    for pattern in ("*.xplane.pb", "*.trace.json.gz"):
        paths = sorted(glob.glob(
            os.path.join(logdir, "plugins", "profile", "*", pattern)))
        if paths:
            return paths[-1]
    return None


_HLO_LINE_NAME = re.compile(r"^%(\S+) = ")


def _load_xplane(path: str) -> dict:
    """An ``.xplane.pb`` as the ``traceEvents`` dict the JSON loader
    gives: a process a plane, a thread a line, one complete event for
    every operation. An operation is named by its HLO instruction: the
    event's ``hlo_op`` stat where the runtime sets it (XLA:CPU's thunk
    events), else the head of the HLO line a TPU op event is named by
    (``%fusion.5 = bf16[...] fusion(...)``). Of the host's planes the
    operations are kept, and the program's own spans (``pt.*``, opened
    by ``spans.Span`` only) in a list of their own, ``programSpans``:
    they are never device events. The Python tracer's events join
    nothing."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[dict] = []
    spans: List[dict] = []
    for pid, plane in enumerate(data.planes, start=1):
        on_device = plane.name.startswith("/device:")
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": plane.name}})
        for tid, line in enumerate(plane.lines, start=1):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": line.name}})
            for e in line.events:
                if not on_device and e.name.startswith("$"):
                    continue  # the Python tracer's frames, the bulk
                stats = dict(e.stats)
                if not on_device and e.name.startswith(SPAN_PREFIX):
                    spans.append({"name": e.name, "ts": e.start_ns / 1e3,
                                  "dur": e.duration_ns / 1e3,
                                  "pid": pid, "tid": tid,
                                  "step": stats.get("step")})
                    continue
                op = stats.get("hlo_op")
                if op is None:
                    if not on_device:
                        continue
                    m = _HLO_LINE_NAME.match(e.name)
                    op = m.group(1) if m else e.name
                args = {"hlo_op": str(op)}
                for key in ("hlo_module", "run_id"):
                    if key in stats:
                        args[key] = str(stats[key])
                events.append({"ph": "X", "name": str(op), "pid": pid,
                               "tid": tid, "ts": e.start_ns / 1e3,
                               "dur": e.duration_ns / 1e3, "args": args})
    return {"traceEvents": events, "programSpans": spans}


def load_trace(path_or_logdir: str) -> Optional[dict]:
    """The trace as a ``traceEvents`` dict, or None (with a warning) on
    any failure — missing file, truncated gzip, malformed JSON or proto."""
    path = path_or_logdir
    if os.path.isdir(path_or_logdir):
        path = newest_trace_path(path_or_logdir)
        if path is None:
            logger.warning("hlo_attrib: no .xplane.pb or .trace.json.gz "
                           "under %s — profiler produced no trace",
                           path_or_logdir)
            return None
    try:
        if path.endswith(".xplane.pb"):
            return _load_xplane(path)
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            trace = json.load(f)
        if not isinstance(trace, dict) or "traceEvents" not in trace:
            raise ValueError("no traceEvents key")
        return trace
    except Exception as e:  # noqa: BLE001 — degrade, never kill the run
        logger.warning("hlo_attrib: unreadable trace %s (%s) — skipping "
                       "attribution for this capture", path, e)
        return None


def device_events(trace: dict,
                  known_names: Optional[set] = None) -> List[dict]:
    """The per-op device events of a trace: every complete ("X") event in
    an "XLA Ops" lane of a device process (the TPU layout). When the
    trace has NO such lanes (XLA:CPU emits per-op thunk events on
    runtime threads instead), fall back to events whose name matches a
    known HLO instruction name — lane membership wins when lanes exist,
    so a host-side event that happens to shadow an HLO name can never
    pollute a real device timeline. Events read from an ``.xplane.pb``
    say themselves that they are operations (``args.hlo_op``)."""
    events = trace.get("traceEvents") or []
    procs: Dict[int, str] = {}
    op_lanes = set()
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = str(e.get("args", {}).get("name", ""))
        elif e.get("name") == "thread_name":
            lane = str(e.get("args", {}).get("name", ""))
            # not "Async XLA Ops": its events span a copy from start to
            # done, beside the operations that run meanwhile
            if "XLA Ops" in lane and "Async" not in lane:
                op_lanes.add((e["pid"], e.get("tid")))
    device_pids = {p for p, n in procs.items()
                   if "TPU" in n or "xla" in n.lower()
                   or "/device" in n.lower()}
    lanes = {(p, t) for (p, t) in op_lanes if p in device_pids}
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        if lanes:
            if (e.get("pid"), e.get("tid")) in lanes:
                out.append(e)
        elif "hlo_op" in (e.get("args") or {}) or (
                known_names and e.get("name") in known_names):
            out.append(e)
    return out


def program_spans(trace: dict) -> List[dict]:
    """The program's own ``pt.*`` spans of a trace, ``{"name", "ts",
    "dur", "pid", "tid", "step"}`` in µs on the trace's clock: the list an
    ``.xplane.pb`` was read into, or a JSON trace's complete events of
    that name."""
    if "programSpans" in trace:
        return list(trace["programSpans"])
    return [{"name": e["name"], "ts": e.get("ts", 0), "dur": e.get("dur", 0),
             "pid": e.get("pid"), "tid": e.get("tid"),
             "step": (e.get("args") or {}).get("step")}
            for e in trace.get("traceEvents") or []
            if e.get("ph") == "X"
            and str(e.get("name", "")).startswith(SPAN_PREFIX)]


def module_runs(trace: dict, events: List[dict]) -> List[Tuple[float, float]]:
    """The compiled programs' runs, ``(start, end)`` in µs: the events of
    a device's ``XLA Modules`` lane (the TPU layout), else the operations
    grouped by the run they carry (``hlo_module`` and ``run_id``, XLA:CPU's
    stats)."""
    procs, lanes = {}, set()
    for e in trace.get("traceEvents") or []:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = str(e.get("args", {}).get("name", ""))
        elif (e.get("name") == "thread_name"
              and e.get("args", {}).get("name") == "XLA Modules"):
            lanes.add((e["pid"], e.get("tid")))
    runs = [(e["ts"], e["ts"] + e.get("dur", 0))
            for e in trace.get("traceEvents") or []
            if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in lanes
            and "/device" in procs.get(e.get("pid"), "").lower()]
    if runs:
        return sorted(runs)
    by_run: Dict[tuple, List[float]] = {}
    for e in events:
        args = e.get("args") or {}
        if "run_id" not in args:
            continue
        key = (e.get("pid"), args.get("hlo_module"), args["run_id"])
        s, t = e.get("ts", 0), e.get("ts", 0) + e.get("dur", 0)
        lo_hi = by_run.setdefault(key, [s, t])
        lo_hi[0], lo_hi[1] = min(lo_hi[0], s), max(lo_hi[1], t)
    return sorted((lo, hi) for lo, hi in by_run.values())


def union(intervals, lo: float = float("-inf"),
          hi: float = float("inf")) -> List[List[float]]:
    """The merged parts of ``intervals`` that lie inside [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _complement(merged, lo: float, hi: float) -> List[Tuple[float, float]]:
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _innermost(spans: List[dict]) -> List[Tuple[float, float, str]]:
    """The stretches where some span is open, cut where any span opens or
    closes, each named by the innermost span open over it (the one
    opened last)."""
    ivs = sorted((s["ts"], s["ts"] + s["dur"], s["name"]) for s in spans
                 if s.get("dur", 0) > 0)
    points = sorted({p for s, e, _ in ivs for p in (s, e)})
    out: List[Tuple[float, float, str]] = []
    heap: list = []  # (-start, end, name): the latest opened on top
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            heapq.heappush(heap, (-ivs[i][0], ivs[i][1], ivs[i][2]))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)  # closed: dropped once it is on top
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _label(pieces, segments, default) -> List[Tuple[float, float, str]]:
    """``pieces`` (sorted, disjoint) cut by ``segments`` (sorted, disjoint
    ``(start, end, name)``): each part named by the segment over it, or
    ``default`` where none is."""
    out, j = [], 0
    for a, b in pieces:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(segments) and segments[k][0] < b:
            s, e = max(segments[k][0], a), min(segments[k][1], b)
            if s > t:
                out.append((t, s, default))
            out.append((s, e, segments[k][2]))
            t, k = e, k + 1
        if b > t:
            out.append((t, b, default))
    return out


def idle_by_span(busy, runs, spans: List[dict], lo: float,
                 hi: float) -> Dict[str, float]:
    """Every idle stretch of [lo, hi] (no interval of ``busy`` open) in
    ms: the part inside a compiled run (``runs``) goes to :data:`IN_STEP`,
    the rest, piece by piece, to the innermost span of ``spans`` open on
    the host over it, or to :data:`OUTSIDE_SPANS`. All in µs on one
    clock; the values sum to the idle time of [lo, hi]."""
    idle = _complement(union(busy, lo, hi), lo, hi)
    parts = _label(idle, [(s, e, IN_STEP) for s, e in union(runs, lo, hi)],
                   None)
    host = [(a, b) for a, b, name in parts if name is None]
    parts = [p for p in parts if p[2] is not None] + _label(
        host, _innermost(spans), OUTSIDE_SPANS)
    out: Dict[str, float] = {}
    for a, b, name in parts:
        out[name] = out.get(name, 0.0) + (b - a) / 1e3
    return out


# -- the report ---------------------------------------------------------------

@dataclasses.dataclass
class EntryAttribution:
    """One entry's slice of the window: device ms by category plus the
    per-op and per-source-line tables."""

    entry: str
    steps: int = 1
    device_ms: float = 0.0
    category_ms: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in CATEGORIES})
    by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    by_line: Dict[str, float] = dataclasses.field(default_factory=dict)
    scope_ms: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {s: 0.0 for s in SCOPES + (UNSCOPED,)})
    op_meta: Dict[str, Tuple[str, str, str]] = dataclasses.field(
        default_factory=dict)  # op -> (src, op_name, category)

    def add(self, op: str, src: str, op_name: str, category: str,
            ms: float) -> None:
        self.device_ms += ms
        self.category_ms[category] = self.category_ms.get(category, 0.0) + ms
        self.by_op[op] = self.by_op.get(op, 0.0) + ms
        self.by_line[src] = self.by_line.get(src, 0.0) + ms
        # a fusion is one instruction with one op_name, its root's: its
        # whole time goes where that says, and is not split
        self.scope_ms[scope_of(op_name)] += ms
        self.op_meta.setdefault(op, (src, op_name, category))

    def scopes(self) -> List[dict]:
        """Device time by scope, every scope of the vocabulary and
        ``unscoped``: the rows sum to ``device_ms``."""
        denom = max(self.device_ms, 1e-12)
        return [{"scope": s, "entry": self.entry, "ms": round(ms, 6),
                 "ms_per_step": round(ms / max(self.steps, 1), 6),
                 "frac": min(round(ms / denom, 6), 1.0)}
                for s, ms in self.scope_ms.items()]

    def top_ops(self, k: int = 10) -> List[dict]:
        rows = sorted(self.by_op.items(), key=lambda kv: -kv[1])[:k]
        denom = max(self.device_ms, 1e-12)
        return [{"op": op, "entry": self.entry,
                 "src": self.op_meta.get(op, ("?",))[0],
                 "op_name": self.op_meta.get(op, ("?", "?"))[1],
                 "category": self.op_meta.get(op, ("?", "?", "compute"))[2],
                 "ms": round(ms, 6),
                 "ms_per_step": round(ms / max(self.steps, 1), 6),
                 "frac": min(round(ms / denom, 6), 1.0)}
                for op, ms in rows]

    def top_lines(self, k: int = 10) -> List[dict]:
        rows = sorted(self.by_line.items(), key=lambda kv: -kv[1])[:k]
        denom = max(self.device_ms, 1e-12)
        return [{"src": src, "entry": self.entry, "ms": round(ms, 6),
                 "ms_per_step": round(ms / max(self.steps, 1), 6),
                 "frac": min(round(ms / denom, 6), 1.0)}
                for src, ms in rows]


@dataclasses.dataclass
class AttributionReport:
    """The whole window's decomposition. ``fractions(entry)`` are of the
    window WALL time, normalized so their sum (with the dominant entry's
    host gap) can never exceed 1 — the schema-gate contract."""

    wall_ms: float
    device_total_ms: float
    entries: Dict[str, EntryAttribution]
    unattributed_ms: float = 0.0
    steps: Dict[str, int] = dataclasses.field(default_factory=dict)
    trigger_entry: Optional[str] = None
    # the union of the device operations' intervals (None: the summed
    # time stands in), and the window's idle time booked to the host span
    # that held the device (``idle_by_span``)
    device_busy_ms: Optional[float] = None
    idle_by_span_ms: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def dominant_entry(self) -> Optional[str]:
        if not self.entries:
            return None
        return max(self.entries.values(), key=lambda a: a.device_ms).entry

    @property
    def host_gap_ms(self) -> float:
        """Wall time in which no device operation ran: the wall less the
        union of the operations' intervals, so that operations which
        overlap (parallel thunks, concurrent streams) count once."""
        if self.wall_ms <= 0:
            return 0.0
        busy = (self.device_total_ms if self.device_busy_ms is None
                else self.device_busy_ms)
        return max(self.wall_ms - busy, 0.0)

    def _scale(self) -> float:
        """Device-time → wall-fraction normalizer. When device lanes
        overlap (parallel thunks on CPU, concurrent streams) the summed
        device time can exceed the wall — fractions are scaled down so
        the per-entry sums stay ≤ 1."""
        if self.wall_ms <= 0 or self.device_total_ms <= self.wall_ms:
            return 1.0
        return self.wall_ms / self.device_total_ms

    def fractions(self, entry: str) -> Dict[str, float]:
        """{compute,collective,transfer}_frac (of wall) for ``entry``,
        plus host_gap_frac on the dominant entry only (the gap belongs
        to the window, not to every program in it)."""
        att = self.entries.get(entry)
        if att is None or self.wall_ms <= 0:
            return {}
        s = self._scale() / self.wall_ms
        out = {f"{c}_frac": min(max(att.category_ms.get(c, 0.0) * s, 0.0),
                                1.0)
               for c in CATEGORIES}
        if entry == self.dominant_entry:
            gap = self.host_gap_ms / self.wall_ms
            # never let rounding push the cross-field sum past 1
            gap = min(gap, max(1.0 - sum(out.values()), 0.0))
            out["host_gap_frac"] = gap
        return out

    def reconciliation_error(self) -> float:
        """|sum(category totals) - device_total| / device_total — the
        tested invariant (categories partition the device events, so
        this is ~0 up to float rounding)."""
        # unattributed events are already folded into the dominant
        # entry's categories — the entry sums alone partition the total
        cat = sum(sum(a.category_ms.values()) for a in self.entries.values())
        if self.device_total_ms <= 0:
            return 0.0
        return abs(cat - self.device_total_ms) / self.device_total_ms

    def top_ops(self, k: int = 10) -> List[dict]:
        rows: List[dict] = []
        for att in self.entries.values():
            rows.extend(att.top_ops(k))
        rows.sort(key=lambda r: -r["ms"])
        return rows[:k]

    def to_dict(self, top_k: int = 10) -> dict:
        return {
            "wall_ms": round(self.wall_ms, 6),
            "device_total_ms": round(self.device_total_ms, 6),
            "host_gap_ms": round(self.host_gap_ms, 6),
            "idle_by_span_ms": {k: round(v, 6) for k, v in sorted(
                self.idle_by_span_ms.items(), key=lambda kv: -kv[1])},
            "unattributed_ms": round(self.unattributed_ms, 6),
            "trigger_entry": self.trigger_entry,
            "dominant_entry": self.dominant_entry,
            "steps": dict(self.steps),
            "entries": {
                e: {"steps": a.steps,
                    "device_ms": round(a.device_ms, 6),
                    "device_ms_per_step": round(
                        a.device_ms / max(a.steps, 1), 6),
                    "category_ms": {c: round(v, 6)
                                    for c, v in a.category_ms.items()},
                    "fractions": self.fractions(e)}
                for e, a in self.entries.items()},
            "scopes": [r for a in self.entries.values()
                       for r in a.scopes()],
            "top_ops": self.top_ops(top_k),
            "top_lines": sorted(
                (r for a in self.entries.values()
                 for r in a.top_lines(top_k)),
                key=lambda r: -r["ms"])[:top_k],
        }


def attribute_trace(trace: dict, hlo_by_entry: Dict[str, str],
                    steps: Optional[Dict[str, int]] = None,
                    wall_ms: float = 0.0,
                    trigger_entry: Optional[str] = None,
                    default_steps: int = 1,
                    window_us: Optional[Tuple[float, float]] = None
                    ) -> Optional[AttributionReport]:
    """Join one trace with per-entry HLO texts.

    ``steps`` maps entry → step-boundary count inside the window (the
    per-step divisor); entries present in the HLO map but absent from
    ``steps`` divide by ``default_steps``. Events whose name matches no
    entry's HLO land in the dominant entry as ``<unattributed:stem>``
    rows (TPU lanes carry runtime ops the HLO never names). Returns
    ``None`` (warning logged) when the trace yields no device events —
    an empty window is a capture problem, not a 0-of-everything report.

    ``window_us`` is the capture's window on the trace's clock (the
    caller places it by a span both clocks hold); without it the window
    runs from the first operation's start to the last one's end. Its idle
    stretches are booked by ``idle_by_span`` into ``idle_by_span_ms``, and
    what the window leaves of ``host_gap_ms`` goes to
    :data:`OUTSIDE_SPANS`: the values sum to ``host_gap_ms`` wherever the
    wall holds the window.
    """
    if trace is None:
        return None
    steps = dict(steps or {})
    metas = {entry: parse_hlo_text(text)
             for entry, text in hlo_by_entry.items() if text}
    name_index: Dict[str, List[str]] = {}
    for entry, meta in metas.items():
        for name in meta:
            name_index.setdefault(name, []).append(entry)
    known = set(name_index)
    events = device_events(trace, known_names=known)
    if not events:
        logger.warning(
            "hlo_attrib: trace carries no attributable device events "
            "(no 'XLA Ops' lanes and no event matching a registered "
            "entry's HLO instruction names)")
        return None
    # dominance by matched device time decides ambiguous names later, so
    # first pass: unambiguous totals per entry
    entry_time: Dict[str, float] = {}
    for e in events:
        owners = name_index.get(e.get("name", ""))
        if owners and len(owners) == 1:
            entry_time[owners[0]] = (entry_time.get(owners[0], 0.0)
                                     + e.get("dur", 0) / 1e3)
    dominant = (max(entry_time, key=entry_time.get) if entry_time
                else (trigger_entry or (sorted(metas)[0] if metas else None)))
    report = AttributionReport(wall_ms=float(wall_ms), device_total_ms=0.0,
                               entries={}, steps=steps,
                               trigger_entry=trigger_entry)

    def _att(entry: str) -> EntryAttribution:
        a = report.entries.get(entry)
        if a is None:
            a = report.entries[entry] = EntryAttribution(
                entry=entry, steps=max(int(steps.get(entry,
                                                     default_steps)), 1))
        return a

    for e in events:
        name = e.get("name", "")
        dur_ms = e.get("dur", 0) / 1e3
        report.device_total_ms += dur_ms
        owners = name_index.get(name)
        if owners:
            entry = owners[0] if len(owners) == 1 else (
                dominant if dominant in owners else owners[0])
            op = metas[entry][name]
            _att(entry).add(name, op.src, op.op_name, op.category, dur_ms)
        elif dominant is not None:
            stem = re.sub(r"[.\d]+$", "", name)
            cat = categorize_opcode("", name)
            _att(dominant).add(f"<unattributed:{stem}>", "?", "?", cat,
                               dur_ms)
            report.unattributed_ms += dur_ms
    busy = [(e.get("ts", 0), e.get("ts", 0) + e.get("dur", 0))
            for e in events]
    report.device_busy_ms = sum(b - a for a, b in union(busy)) / 1e3
    if report.wall_ms > 0:
        lo, hi = window_us or (min(a for a, _ in busy),
                               max(b for _, b in busy))
        idle = idle_by_span(busy, module_runs(trace, events),
                            program_spans(trace), lo, hi)
        rest = report.host_gap_ms - sum(idle.values())
        if rest > 0:
            idle[OUTSIDE_SPANS] = idle.get(OUTSIDE_SPANS, 0.0) + rest
        report.idle_by_span_ms = idle
    return report


# -- the compile-time HLO registry --------------------------------------------

class HloRegistry:
    """Latest compiled-HLO artifact per tracked_jit entry, fed by
    ``xla_cost.capture`` — the "already held, no second lowering"
    contract. The NEWEST compile of an entry always wins (a retrace
    replaces the program, and attributing a trace against a dead
    program's names would be wrong even when the old artifact was the
    nicer optimized text). Bounded: one insertion-ordered store, so
    eviction really is least-recently-compiled, never the entry a
    capture is about to join against."""

    def __init__(self, max_entries: int = 32):
        self._lock = threading.Lock()
        # entry -> ("text", str) | ("lowered", Lowered); insertion order
        # == compile recency (puts re-insert at the end)
        self._store: Dict[str, tuple] = {}
        self._max = int(max_entries)
        self._compile_warned = False

    def _put(self, entry: str, kind: str, value) -> None:
        self._store.pop(entry, None)
        self._store[entry] = (kind, value)
        while len(self._store) > self._max:
            self._store.pop(next(iter(self._store)))

    def put_text(self, entry: str, text: str) -> None:
        with self._lock:
            self._put(entry, "text", text)

    def put_lowered(self, entry: str, lowered) -> None:
        with self._lock:
            self._put(entry, "lowered", lowered)

    def entries(self) -> List[str]:
        with self._lock:
            return sorted(self._store)

    def text_for(self, entry: str) -> Optional[str]:
        """The optimized HLO text for ``entry``; compiles the stored
        Lowered on demand (counted — it is the one place attribution
        pays a compile, and only because the default cost-analysis mode
        skipped the full one)."""
        with self._lock:
            kind, value = self._store.get(entry, (None, None))
        text = value if kind == "text" else None
        lowered = value if kind == "lowered" else None
        if text is not None:
            return text
        if lowered is None:
            return None
        try:
            text = lowered.compile().as_text()
        except Exception as e:  # noqa: BLE001
            if not self._compile_warned:
                self._compile_warned = True
                logger.warning("hlo_attrib: compiling stored lowering for "
                               "%r failed (%s) — attribution will miss "
                               "this entry", entry, e)
            return None
        get_telemetry().counter("profile/hlo_compiles")
        self.put_text(entry, text)
        return text

    def texts(self, entries: Optional[List[str]] = None
              ) -> Dict[str, str]:
        out = {}
        for e in (entries if entries is not None else self.entries()):
            t = self.text_for(e)
            if t:
                out[e] = t
        return out

    def reset(self) -> None:
        with self._lock:
            self._store.clear()
            self._compile_warned = False


_registry = HloRegistry()


def hlo_registry() -> HloRegistry:
    return _registry
