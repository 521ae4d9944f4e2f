"""From the run's raw trace to device time by ``jax.named_scope`` and to
the program's own host spans.

The program names the parts of its compiled step (``embed``, ``self_attn``,
``attention``, ``mlp``, ``head_loss``, ``optimizer``) and writes its host
spans into the profiler's trace as ``pt.<name>`` events. The readers in
``benchmark/metrics/`` are handed the reduced trace only, so this module
finds the raw one itself: the newest ``*.xplane.pb`` under
``<checkout>/.bench_out/*/trace/``, which the driver's traced stretch has
just written after clearing the directory. It is read once a process.

What a v5e trace holds (looked at by hand, PR 27): an ``XLA Ops`` event
is named by its whole HLO line and has no stat of its own that names its
origin; the event's *metadata* has, among ``hlo_category``, ``flops``,
``bytes_accessed`` and ``source``, the stat ``tf_op``: the HLO ``op_name``
path, ``jit(train_step)/transpose(jvp(self_attn))/attention/dot_general:``.
``jax.profiler.ProfileData`` gives an event's own stats and not its
metadata's, so times come from ``lib.trace.load`` and the one map from an
event's name to its ``tf_op`` is read from the file's bytes (protobuf's
wire format, the five fields of ``xplane.proto`` it takes).

A fusion is one event with one ``op_name``, its root's: its whole time is
booked where that says and is not split. A program without the scopes (or
the spans) gives nothing to read, and every reader returns None.
"""
from __future__ import annotations

import glob
import os
import statistics

from benchmark.lib import report, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCOPES = ("embed", "self_attn", "attention", "mlp", "head_loss", "optimizer")
UNSCOPED = "unscoped"
SPAN_PREFIX = "pt."
# what JAX wraps around a scope's name when it transforms the function
TRANSFORMS = frozenset(("jvp", "transpose", "vmap", "checkpoint"))


def scope_of(path: str) -> str:
    """The innermost known scope in an ``op_name`` path. A name counts only
    as a whole component, bare or inside JAX's transform wrappers:
    ``transpose(jvp(f))/head_loss/dot`` and ``transpose(jvp(head_loss))/dot``
    are both ``head_loss``; ``jit(dot_product_attention)`` is a function."""
    found, heads, token = UNSCOPED, [], ""
    for ch in path + "/":
        if ch not in "()/":
            token += ch
            continue
        if ch == "(":
            heads.append(token)
        elif token in SCOPES and all(h in TRANSFORMS for h in heads):
            found = token
        if ch == ")" and heads:
            heads.pop()
        token = ""
    return found


# -- the file's bytes: event name -> tf_op ------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, value


def op_paths(serialized: bytes, stat: str = "tf_op") -> dict:
    """``{plane: {event name: the event metadata's ``stat``}}``. Of
    ``xplane.proto``: XSpace.planes = 1; XPlane.name = 2, .event_metadata =
    4, .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name =
    2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (the name of another stat metadata)."""
    out = {}
    for number, plane in _fields(serialized):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(dict(_fields(v))[2])
            elif f == 5:
                entry = dict(_fields(v))
                stats[entry[1]] = dict(_fields(entry[2])).get(
                    2, b"").decode()
        paths = out.setdefault(name, {})
        for meta in events:
            event_name, path = "", None
            for f, v in _fields(meta):
                if f == 2:
                    event_name = v.decode()
                elif f == 5:
                    s = dict(_fields(v))
                    if stats.get(s.get(1)) == stat:
                        path = (s[5].decode() if 5 in s
                                else stats.get(s.get(7), ""))
            if path is not None:
                paths[event_name] = path
    return out


# -- the reduction -------------------------------------------------------------

def newest_raw_trace(root: str = None):
    """The newest raw trace of any cell under ``root``, by its file's
    time: a run clears its cell's directory before it traces."""
    found = glob.glob(os.path.join(root or ROOT, ".bench_out", "*", "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def span_at(spans: list, lo: float, hi: float) -> str:
    """The program's span that covers most of [lo, hi]; of those that cover
    as much, the innermost (the shortest)."""
    best, key = "outside_program_spans", (0.0, 0.0)
    for name, s, e in spans:
        cover = min(e, hi) - max(s, lo)
        if cover > 0 and (cover, s - e) > key:
            best, key = name, (cover, s - e)
    return best


def reduce(planes: dict, paths: dict):
    """Device time a step by scope, the program's host spans and the idle
    gaps booked to them, over the window of ``lib.trace``. None where no
    operation ran on a device."""
    host = [ev for events in planes.get(trace.HOST_PLANE, {}).values()
            for ev in events if ev[0].startswith(SPAN_PREFIX)]
    ms = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    count = dict.fromkeys(ms, 0)
    spans, gaps, steps, chips, unnamed = {}, [], [], 0, 0.0
    for pname, lines in planes.items():
        ops = lines.get(trace.OPS_LINE)
        span = trace.whole_steps_window(lines.get(trace.MODULES_LINE, []))
        if not pname.startswith(trace.DEVICE_PLANE) or not ops or not span:
            continue
        lo, hi, n, _ = span
        chips += 1
        steps.append(n)
        names = paths.get(pname, {})
        for name, s, e in ops:
            if s >= lo and e <= hi:
                scope = scope_of(names.get(name, ""))
                ms[scope] += (e - s) / 1e6
                count[scope] += 1
                if name not in names:
                    unnamed += (e - s) / 1e6
        edges = [lo] + [x for iv in trace.union(
            ((s, e) for _, s, e in ops), lo, hi) for x in iv] + [hi]
        gaps += [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                 if b > a]
        for name, s, e in host:
            if s >= lo and e <= hi:
                spans.setdefault(name, []).append((e - s) / 1e6)
    if not chips:
        return None
    n = min(steps)
    idle = {}
    for length, a, b in sorted(gaps, reverse=True)[:200]:
        name = span_at(host, a, b)
        idle[name] = idle.get(name, 0.0) + length / 1e6 / chips
    return {
        "steps": n, "chips": chips,
        "scoped": sum(count[s] for s in SCOPES) > 0,
        "ms": {s: v / chips / n for s, v in ms.items()},
        # of ``unscoped``: operations the compiler made itself (the copies
        # and slices of its memory planning), which have no path at all
        # and which no scope in the program could name
        "unnamed_ms": unnamed / chips / n,
        "events": {s: c // chips for s, c in count.items()},
        "span_ms": {name: statistics.median(v) for name, v in spans.items()},
        "span_count": {name: len(v) // chips for name, v in spans.items()},
        "program_idle_gaps_ms": idle,
    }


_reduced = {}  # path of the raw trace -> its reduction


def of_run(run: dict):
    """The reduction of the trace this run has just written, printed once
    as a note. None off the TPU and where the run made no trace."""
    traced = run.get("trace")
    if not traced or run["device"]["platform"] != "tpu":
        return None
    path = newest_raw_trace()
    if path is None:
        return None
    if path not in _reduced:
        with open(path, "rb") as f:
            paths = op_paths(f.read())
        got = _reduced[path] = reduce(trace.load(path), paths)
        if got:
            total = sum(got["ms"].values())
            report.note(
                "scopes", file=os.path.relpath(path, ROOT),
                steps=got["steps"], scoped=got["scoped"],
                scopes={s: {"ms": v, "events": got["events"][s],
                            "share": v / total if total else 0.0}
                        for s, v in got["ms"].items()},
                unnamed_ms=got["unnamed_ms"], op_ms_a_step=total,
                busy_ms_a_step=1e3 * traced["busy_s"] / traced["steps"],
                spans={name: {"median_ms": v, "in_window":
                              got["span_count"][name]}
                       for name, v in got["span_ms"].items()},
                program_idle_gaps_ms=got["program_idle_gaps_ms"])
    return _reduced[path]


def device_ms(run: dict, *scopes: str):
    """Device time a step of the events whose innermost scope is one of
    ``scopes``: the sum of their durations inside the window over its
    whole steps. None where the program names no scope at all."""
    got = of_run(run)
    if not got or not got["scoped"]:
        return None
    return sum(got["ms"][s] for s in scopes)


def span_ms(run: dict, name: str):
    """The median duration of the program's host span ``name`` inside the
    window, in milliseconds. None where the trace has no such span."""
    got = of_run(run)
    return got["span_ms"].get(name) if got else None
