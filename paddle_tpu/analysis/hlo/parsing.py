"""Structured parser over optimized HLO text.

One home for the HLO-text primitives that used to live as private
helpers in ``profiler.hlo_attrib`` (instruction/opcode split) and
``profiler.collective_attrib`` (shape bytes, replica-group forms) —
both now import from here, and the hlo-lint rules get the structure
they need (computations, operands, users, called computations) from
the same single parse.

Scope and tolerance match the profiler layer: this is a *line* parser
for the text ``Compiled.as_text()`` emits (`name = type opcode(...),
attrs, metadata={...}`), not a full HLO grammar. Unrecognized lines are
skipped; instructions missing attributes simply report them absent.
Everything here is framework-free (stdlib; numpy only lazily, for the
iota replica-group form) so ``tools/hlo_lint.py`` can load it without
importing jax.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = [
    "DTYPE_BYTES", "COLLECTIVE_OPCODES", "DONE_OPCODES",
    "HloInstr", "HloComputation", "HloModule",
    "iter_instruction_lines", "opcode_of", "opcode_and_type",
    "parse_shapes", "shape_bytes", "parse_group_sets", "parse_pairs",
    "parse_stack_frames", "source_of", "parse_module",
]

# every opcode the collective inventory claims (async halves map to
# their base op); kept aligned with hlo_attrib's category vocabulary
COLLECTIVE_OPCODES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-gather-start", "collective-permute-start",
}
# the *-done halves carry no replica_groups; the start half owns the
# instance (counting both would double every async collective)
DONE_OPCODES = {"all-reduce-done", "all-gather-done",
                "collective-permute-done"}

# dtype token -> bytes per element (token/opaque types carry no payload)
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

NAME_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_GROUPS_LITERAL_RE = re.compile(
    r"replica_groups=\{(\{[\d,\s]*\}(?:,\s*\{[\d,\s]*\})*)?\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(
    r"source_target_pairs=\{(\{[\d,\s]*\}(?:,\s*\{[\d,\s]*\})*)?\}")
_INNER_GROUP_RE = re.compile(r"\{([\d,\s]*)\}")
# the comma continuation serves branch_computations={a, b}; each name
# must NOT be followed by "=" or the list would swallow the next
# attribute's keyword ("condition=%c, body=%b" is two attributes)
_CALLED_RE = re.compile(
    r"\b(to_apply|body|condition|calls|branch_computations)="
    r"\{?%?([\w.\-]+\b(?!=)(?:,\s*%?[\w.\-]+\b(?!=))*)\}?")
_SHARDING_RE = re.compile(r"sharding=\{([^}]*)\}")
_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_META_BODY_RE = re.compile(r"metadata=\{([^}]*)\}")
_SRC_FILE_RE = re.compile(r'source_file="([^"]+)"')
_SRC_LINE_RE = re.compile(r"source_line=(\d+)")
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"^(\d+)\s+(.*)$")
_TABLE_NAMES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_DIMS_ATTR_RE = re.compile(r"\b(\w+_dims|dimensions)=\{([\d,\s]*)\}")
_COMP_HEADER_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")


def opcode_of(body: str) -> str:
    """The opcode of one instruction body (everything right of ``= ``):
    skip the result type — one token, or a parenthesized tuple type —
    then the next identifier before ``(`` is the opcode."""
    body = body.lstrip()
    if body.startswith("("):
        depth = 0
        for i, ch in enumerate(body):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    body = body[i + 1:].lstrip()
                    break
        else:
            return "?"
    else:
        parts = body.split(None, 1)
        if len(parts) < 2:
            return "?"
        body = parts[1]
    m = re.match(r"([A-Za-z][\w\-]*)\(", body)
    return m.group(1).lower() if m else "?"


def opcode_and_type(body: str) -> Tuple[str, str]:
    """(opcode, result-type text) of one instruction body. The result
    type is everything left of the opcode token (one shape, or a
    parenthesized tuple of shapes)."""
    stripped = body.lstrip()
    m = re.match(r"^(\([^)]*\)|\S+)\s+([a-z][\w\-]*)\(", stripped)
    if not m:
        return "?", ""
    return m.group(2).lower(), m.group(1)


def parse_shapes(type_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``[(dtype, dims)]`` for every array shape in a result-type text
    (one element for a plain shape, several for a tuple type).
    ``f32[]`` is a scalar: ``("f32", ())``."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_text):
        shape = tuple(int(d) for d in dims.split(",") if d.strip())
        out.append((dtype, shape))
    return out


def shape_bytes(type_text: str) -> float:
    """Byte size of one HLO result type (scalar, array, or tuple): sum
    over every ``dtype[dims]`` token. ``f32[]`` is a scalar (4 bytes)."""
    total = 0.0
    for dtype, shape in parse_shapes(type_text):
        size = DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token/opaque types carry no payload
        n = 1
        for d in shape:
            n *= d
        total += n * size
    return total


def parse_group_sets(body: str) -> Optional[List[Tuple[int, ...]]]:
    """The instruction's replica groups as explicit member tuples, from
    either the literal or the iota form; None when absent."""
    m = _GROUPS_IOTA_RE.search(body)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        total = 1
        for d in dims:
            total *= d
        # iota semantics: arange(prod(dims)).reshape(dims).transpose(perm)
        # .reshape(n_groups, group_size) — each row is one group
        import numpy as np

        arr = np.arange(total).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            arr = arr.transpose(perm)
        arr = arr.reshape(n_groups, group_size)
        return [tuple(int(v) for v in row) for row in arr]
    m = _GROUPS_LITERAL_RE.search(body)
    if m:
        inner = m.group(1) or ""
        groups = []
        for g in _INNER_GROUP_RE.findall(inner):
            members = tuple(int(v) for v in g.split(",") if v.strip())
            if members:
                groups.append(members)
        return groups
    return None


def parse_pairs(body: str) -> Optional[List[Tuple[int, int]]]:
    """A ``collective-permute``'s source_target_pairs, None when absent."""
    m = _PAIRS_RE.search(body)
    if not m:
        return None
    pairs = []
    for g in _INNER_GROUP_RE.findall(m.group(1) or ""):
        members = [int(v) for v in g.split(",") if v.strip()]
        if len(members) == 2:
            pairs.append((members[0], members[1]))
    return pairs


def iter_instruction_lines(text: str) -> Iterator[Tuple[str, str, int]]:
    """``(name, body, lineno)`` for every instruction-shaped line —
    the flat view ``profiler.hlo_attrib.parse_hlo_text`` consumes."""
    for lineno, line in enumerate(text.splitlines(), 1):
        m = NAME_RE.match(line.strip())
        if m:
            yield m.group(1), m.group(2), lineno


# -- source lines -------------------------------------------------------------
# XLA writes an op's origin in one of two forms. Older modules (and this
# repo's recorded fixtures) carry ``source_file="..." source_line=N`` inside
# each op's ``metadata={...}``. The installed jaxlib writes only
# ``stack_frame_id=N`` there, and once per module, above the computations,
# the tables that id points into:
#
#     FileNames       1 "/src/model.py"
#     FileLocations   1 {file_name_id=1 function_name_id=2 line=10 ...}
#     StackFrames     1 {file_location_id=1 parent_frame_id=1}
#
# The frame an op names is its innermost one, so frame -> location ->
# (file, line) is the op's source line.

def parse_stack_frames(text: str) -> Dict[int, str]:
    """``{stack_frame_id: "file.py:line"}`` from a module's header tables
    (empty for a module in the inline form)."""
    tables: Dict[str, Dict[int, str]] = {n: {} for n in _TABLE_NAMES}
    current = None
    for raw in text.splitlines():
        line = raw.strip()
        if line in tables:
            current = tables[line]
            continue
        m = _TABLE_ROW_RE.match(line) if current is not None else None
        if m:
            current[int(m.group(1))] = m.group(2)
        elif line:
            if current is tables["StackFrames"]:
                break  # past the header: the computations follow
            current = None
    frames: Dict[int, str] = {}
    for fid, row in tables["StackFrames"].items():
        loc = tables["FileLocations"].get(_row_int(row, "file_location_id"))
        if loc is None:
            continue
        name = tables["FileNames"].get(_row_int(loc, "file_name_id"), '"?"')
        frames[fid] = (name.strip('"').split("/")[-1] + ":"
                       + str(_row_int(loc, "line")))
    return frames


def _row_int(row: str, key: str) -> int:
    m = re.search(rf"\b{key}=(\d+)", row)
    return int(m.group(1)) if m else 0


def source_of(body: str, frames: Dict[int, str]) -> str:
    """``file.py:123`` (basename) of one instruction body, or "?":
    through ``frames`` (``parse_stack_frames``) when the metadata names a
    stack frame, else from inline ``source_file``/``source_line``."""
    mm = _META_BODY_RE.search(body)
    if not mm:
        return "?"
    md = mm.group(1)
    fid = _FRAME_ID_RE.search(md)
    if fid and frames:
        return frames.get(int(fid.group(1)), "?")
    f = _SRC_FILE_RE.search(md)
    ln = _SRC_LINE_RE.search(md)
    if not f and not ln:
        return "?"
    return ((f.group(1).split("/")[-1] if f else "?")
            + ":" + (ln.group(1) if ln else "?"))


# -- the structured view ------------------------------------------------------

@dataclasses.dataclass
class HloInstr:
    """One instruction of one computation, with the attributes the lint
    rules read. ``body`` keeps the raw text so ad-hoc attributes stay
    greppable without growing this class per rule."""

    name: str
    opcode: str
    type_text: str              # result-type text ("f32[64,64]{1,0}" / tuple)
    body: str                   # everything right of "= "
    line: int                   # 1-based line in the module text
    computation: str
    operands: Tuple[str, ...] = ()
    is_root: bool = False
    src: str = "?"              # "file.py:123" (basename) of the op's origin

    @property
    def stem(self) -> str:
        """Instruction name minus the trailing SSA counter — the stable
        identity baselines key on (``%dot.3`` and ``%dot.17`` are the
        same program point across recompiles)."""
        return re.sub(r"[.\d]+$", "", self.name)

    def shapes(self) -> List[Tuple[str, Tuple[int, ...]]]:
        return parse_shapes(self.type_text)

    def result_bytes(self) -> float:
        return shape_bytes(self.type_text)

    def called_computations(self) -> List[str]:
        """Computations this instruction invokes (``to_apply=``,
        ``body=``/``condition=`` of a while, ``calls=`` of a fusion,
        ``branch_computations={..}`` of a conditional)."""
        out = []
        for _kw, names in _CALLED_RE.findall(self.body):
            for n in names.split(","):
                n = n.strip().lstrip("%")
                if n:
                    out.append(n)
        return out

    def attr_dims(self, key: str) -> Optional[Tuple[int, ...]]:
        """An integer-set attribute (``lhs_contracting_dims``,
        ``dimensions``, ...), None when absent."""
        for k, vals in _DIMS_ATTR_RE.findall(self.body):
            if k == key:
                return tuple(int(v) for v in vals.split(",") if v.strip())
        return None

    def sharding(self) -> Optional[str]:
        m = _SHARDING_RE.search(self.body)
        return m.group(1).strip() if m else None

    def custom_call_target(self) -> Optional[str]:
        m = _CUSTOM_TARGET_RE.search(self.body)
        return m.group(1) if m else None

    def replica_groups(self) -> Optional[List[Tuple[int, ...]]]:
        return parse_group_sets(self.body)

    def op_name(self) -> str:
        mm = _META_BODY_RE.search(self.body)
        if mm:
            o = _OP_NAME_RE.search(mm.group(1))
            if o:
                return o.group(1)
        return "?"


@dataclasses.dataclass
class HloComputation:
    name: str
    instrs: List[HloInstr] = dataclasses.field(default_factory=list)
    is_entry: bool = False

    @property
    def root(self) -> Optional[HloInstr]:
        for i in self.instrs:
            if i.is_root:
                return i
        return self.instrs[-1] if self.instrs else None

    def params(self) -> List[HloInstr]:
        return [i for i in self.instrs if i.opcode == "parameter"]

    def by_name(self) -> Dict[str, HloInstr]:
        return {i.name: i for i in self.instrs}

    def users(self) -> Dict[str, List[HloInstr]]:
        """operand name -> instructions consuming it (within this
        computation — HLO operands never cross computation scopes)."""
        out: Dict[str, List[HloInstr]] = {}
        for i in self.instrs:
            for op in i.operands:
                out.setdefault(op, []).append(i)
        return out


@dataclasses.dataclass
class HloModule:
    name: str
    computations: Dict[str, HloComputation]
    entry: Optional[str] = None
    header: str = ""

    def entry_computation(self) -> Optional[HloComputation]:
        if self.entry and self.entry in self.computations:
            return self.computations[self.entry]
        return None

    def all_instrs(self) -> Iterator[HloInstr]:
        for comp in self.computations.values():
            yield from comp.instrs

    def reachable_from(self, comp_name: str) -> List[HloComputation]:
        """``comp_name`` plus every computation transitively called from
        it (fusion bodies, reducers, nested whiles)."""
        seen: List[HloComputation] = []
        names = [comp_name]
        visited = set()
        while names:
            n = names.pop()
            if n in visited or n not in self.computations:
                continue
            visited.add(n)
            comp = self.computations[n]
            seen.append(comp)
            for instr in comp.instrs:
                names.extend(instr.called_computations())
        return seen


def _operands_of(body: str, opcode: str) -> Tuple[str, ...]:
    """Operand instruction names from the opcode's argument list.
    Each top-level comma-separated argument contributes its trailing
    identifier token (``%tanh.4`` or bare ``tanh.4``; a leading shape
    like ``f32[8]{0}`` is skipped); literal arguments (``constant(0)``)
    contribute nothing."""
    idx = body.find(opcode + "(")
    if idx < 0:
        return ()
    i = idx + len(opcode)
    depth = 0
    sq = br = 0  # [..] / {..} nesting: commas inside a shape's dims or
    # layout ("f32[32,16]{1,0} %x") do NOT separate arguments
    args: List[str] = []
    cur: List[str] = []
    for ch in body[i:]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        elif ch == ")":
            depth -= 1
            if depth == 0:
                args.append("".join(cur))
                break
        elif ch == "[":
            sq += 1
        elif ch == "]":
            sq -= 1
        elif ch == "{":
            br += 1
        elif ch == "}":
            br -= 1
        elif ch == "," and depth == 1 and sq == 0 and br == 0:
            args.append("".join(cur))
            cur = []
            continue
        if depth >= 1:
            cur.append(ch)
    out = []
    for a in args:
        a = a.strip()
        if not a:
            continue
        name = None
        for tok in re.findall(r"%([\w.\-]+)", a):
            name = tok
        if name is None:
            # bare (un-%-prefixed) operand form: the last identifier
            # token that is not a shape ("f32[8]" / "(f32[8], s32[])");
            # the trailing lookahead must reject mid-token stops too, or
            # "f32[..." would yield its prefix "f3" as a phantom operand
            for tok in re.findall(
                    r"(?<![\w\[{])([A-Za-z_][\w.\-]*)(?![\w.\-\[])", a):
                name = tok
        if name is not None:
            out.append(name)
    return tuple(out)


def parse_module(text: str) -> HloModule:
    """Parse one optimized-HLO module text into computations and
    instructions. Tolerant by contract: lines that match nothing are
    skipped, so truncated or annotated dumps still parse."""
    module_name = "?"
    header = ""
    comps: Dict[str, HloComputation] = {}
    entry: Optional[str] = None
    current: Optional[HloComputation] = None
    frames = parse_stack_frames(text)
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("HloModule"):
            header = line
            parts = line.split(None, 2)
            if len(parts) > 1:
                module_name = parts[1].rstrip(",")
            continue
        if line == "}" or line == "})":
            current = None
            continue
        m = _COMP_HEADER_RE.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            name = m.group(2)
            current = comps.setdefault(name, HloComputation(name=name))
            if m.group(1):
                current.is_entry = True
                entry = name
            continue
        m = NAME_RE.match(line)
        if m and current is not None:
            name, body = m.group(1), m.group(2)
            opcode, type_text = opcode_and_type(body)
            if opcode == "?":
                opcode = opcode_of(body)
            current.instrs.append(HloInstr(
                name=name, opcode=opcode, type_text=type_text, body=body,
                line=lineno, computation=current.name,
                operands=_operands_of(body, opcode),
                is_root=line.startswith("ROOT "),
                src=source_of(body, frames)))
    if entry is None and comps:
        # single-computation dumps without an ENTRY keyword: the last
        # computation is the entry by XLA's printing convention
        entry = list(comps)[-1]
    return HloModule(name=module_name, computations=comps, entry=entry,
                     header=header)
