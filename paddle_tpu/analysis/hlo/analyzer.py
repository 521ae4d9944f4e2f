"""hlo-lint driver: findings, analysis context, and the rule loop.

:class:`HloFinding` deliberately subclasses the AST linter's ``Finding``
so the shared ratchet (``analysis.baseline``) and renderers
(``analysis.report``) work unchanged — only the field *semantics* shift:
``path`` is the compiled entry's label (or a snapshot file), ``line`` is
the 1-based line in the HLO text, and ``context`` is the instruction's
name stem (trailing SSA counter stripped — ``%dot.3`` and ``%dot.17``
are the same program point across recompiles, which is what keeps
baseline keys stable).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional

from ..analyzer import Finding
from .parsing import HloInstr, HloModule, parse_module

__all__ = ["HloFinding", "AnalysisContext", "analyze_hlo_text",
           "analyze_module"]


class HloFinding(Finding):
    """One finding over one compiled entry's optimized HLO.

    Same surface as the AST ``Finding`` (``key()`` / ``to_dict()`` /
    ``path``/``line``/``col``/``context``), so ``baseline.compare`` and
    ``report.render_*`` need no second implementation.
    """


@dataclasses.dataclass
class AnalysisContext:
    """What the rules need to know beyond the HLO text itself.

    ``entry`` labels every finding's ``path``. ``mesh_axes`` (ordered
    ``{axis: size}``) arms the mesh-aware rules H6/H7 — empty means "no
    mesh registered", and those rules stay silent rather than guess.
    ``bf16_policy`` arms H2's f32-matmul check (an f32 dot is only a
    hazard when the program was *supposed* to be bf16). Thresholds keep
    the byte/FLOP rules quiet on trivia; the CLI and the compile-time
    hook both construct one of these.
    """

    entry: str = "<hlo>"
    mesh_axes: Dict[str, int] = dataclasses.field(default_factory=dict)
    bf16_policy: bool = False
    h1_min_waste: float = 0.10   # flag dots wasting >= 10% of MXU FLOPs
    h1_min_flops: float = 1e6    # ... but only ops worth >= 1 MFLOP
    h3_min_bytes: float = float(1 << 20)   # copies/transposes >= 1 MiB
    h7_min_bytes: float = float(4 << 20)   # replicated params >= 4 MiB

    def mesh_desc(self) -> str:
        return "{" + ", ".join(f"{k}:{v}"
                               for k, v in self.mesh_axes.items()) + "}"


def make_finding(rule, ctx: AnalysisContext, instr: Optional[HloInstr],
                 message: str, line: int = 0,
                 context: Optional[str] = None) -> HloFinding:
    """One finding anchored at ``instr`` (or an explicit line for
    module-level findings). Rules funnel through here so severity/hint
    stay in the rule metadata and the key stays (entry, rule, stem)."""
    if instr is not None:
        line = instr.line
        if context is None:
            context = instr.stem
        if instr.src != "?":
            message = f"{message} [{instr.src}]"
    return HloFinding(
        rule=rule.id, severity=rule.severity, path=ctx.entry, line=line,
        col=0, message=message, hint=rule.hint,
        context=context if context is not None else "<module>")


def analyze_module(module: HloModule,
                   ctx: Optional[AnalysisContext] = None,
                   select: Optional[Iterable[str]] = None
                   ) -> List[HloFinding]:
    """Run every (selected) rule over one parsed module."""
    from .hlo_rules import HLO_RULES  # late: rules import this module

    ctx = ctx or AnalysisContext()
    chosen = set(select) if select else None
    findings: List[HloFinding] = []
    for rule in HLO_RULES.values():
        if chosen is not None and rule.id not in chosen:
            continue
        findings.extend(rule.check(module, ctx))
    findings.sort(key=lambda f: (f.line, f.rule, f.context))
    return findings


def analyze_hlo_text(text: str,
                     ctx: Optional[AnalysisContext] = None,
                     select: Optional[Iterable[str]] = None
                     ) -> List[HloFinding]:
    """Parse one optimized-HLO text and run the H-rules over it — the
    single entry point the CLI, the compile-time hook, and the tests
    share."""
    return analyze_module(parse_module(text), ctx, select)
