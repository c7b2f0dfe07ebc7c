"""Op inventory of an HLO text module, for the trace and cost auditors.

Parses either the optimized ``compiled.as_text()`` module or the
pre-optimization ``lowered.compiler_ir('hlo').as_hlo_text()`` one into
its computations and their ops. ``/*index=N*/`` tuple comments are
stripped before parsing (they break naive regexes). The auditors read
only ``opcode_counts()``: the flat opcode inventory over every
computation (entry, loop bodies, fusion bodies alike).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


@dataclasses.dataclass
class Op:
    name: str
    opcode: str


_COMMENT_RE = re.compile(r"/\*.*?\*/")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(?[a-z0-9][\w\[\]{},. ()]*?)\s+"
    r"([\w\-]+)\(")
# Optimized HLO spells a header "%name (params) -> result {"; the
# pre-optimization module from ``lowered.compiler_ir("hlo")`` spells it
# bare: "ENTRY main.9 {". Accept both — the trace auditor parses the
# pre-optimization module (the last IR that still carries opt-barrier
# ops; XLA's OptimizationBarrierExpander strips them at the very end of
# every backend pipeline).
_HEADER_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->[^{]*)?\{")


class HloModule:
    def __init__(self, text: str):
        self.computations: Dict[str, List[Op]] = {}
        self.entry: Optional[str] = None
        self._parse(text)

    def _parse(self, text: str) -> None:
        current: Optional[str] = None
        for raw in text.splitlines():
            line = _COMMENT_RE.sub("", raw.rstrip())
            if not line:
                continue
            if not line.startswith(" ") and line.endswith("{"):
                m = _HEADER_RE.match(line)
                if m:
                    current = m.group(1)
                    self.computations[current] = []
                    if line.startswith("ENTRY"):
                        self.entry = current
                continue
            if line.startswith("}"):
                continue
            if current is None:
                continue
            m = _OP_RE.match(line)
            if not m:
                continue
            name, opcode = m.groups()
            self.computations[current].append(Op(name, opcode))

    def opcode_counts(self) -> Dict[str, int]:
        """Opcode -> occurrence count over EVERY computation in the
        module (entry, loop bodies, fusion bodies alike) — the flat op
        inventory the trace auditor's barrier-survival and
        compensation-arithmetic checks run on."""
        counts: Dict[str, int] = {}
        for ops in self.computations.values():
            for op in ops:
                counts[op.opcode] = counts.get(op.opcode, 0) + 1
        return counts


def parse_hlo(hlo_text: str) -> HloModule:
    """Parse an HLO text module (optimized ``compiled.as_text()`` or the
    pre-optimization ``lowered.compiler_ir('hlo').as_hlo_text()`` form)."""
    return HloModule(hlo_text)
