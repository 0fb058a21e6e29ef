"""backend-purity: declared jax-free modules must not reach jax, even transitively.

The rule (rules.BACKEND_FREE): the fleet-side modules — router, autoscaler,
scheduler, supervisor, the jsonl/trace writers, the loadgen — must be importable
without paying for (let alone initializing) a jax backend. The failure mode is
never a literal ``import jax`` in the file; it is three hops away: module A
imports B for a dataclass, B imports C for a helper, C imports jax at top
level. Or subtler — the PARENT PACKAGE: an eager ``from .step import ...`` in
``train/__init__.py`` made every ``from train.launch import Fleet`` (the
router's and supervisor's fleet handle) execute jax's import, which is exactly
what this checker caught on the tree it first ran against.

Lazy (function-body) imports are the sanctioned escape: they defer the cost to
the call that needs it, and the graph records but does not traverse them. A
deliberately jax-reaching top-level import would carry a line pragma with its
justification; none does today.

The finding points at the first import line in the DECLARED module whose edge
begins the offending chain, and the message spells out the full chain — the
fix is usually to make one hop lazy, and the chain says which.
"""

from __future__ import annotations

from tools.graftlint import rules
from tools.graftlint.core import Checker, Finding, Module


class BackendPurity(Checker):
    name = "backend-purity"
    description = ("declared backend-free modules must not reach "
                   f"{'/'.join(rules.BACKEND_MODULES)} through any top-level "
                   "import, transitively (incl. parent-package __init__s)")

    def visit(self, module: Module, graph) -> list[Finding]:
        if not rules.matches(graph, module, rules.BACKEND_FREE):
            return []
        closure = graph.closure(module.name, skip_check=self.name)
        findings: list[Finding] = []
        reported: set[str] = set()
        for reached in sorted(closure):
            top = reached.split(".")[0]
            if top not in rules.BACKEND_MODULES or top in reported:
                continue
            reported.add(top)
            chain = graph.chain(closure, reached)
            # Attribute the finding to the first hop out of the declared
            # module (the import statement the fix will touch or make lazy).
            line = _first_hop_line(closure, chain, module.name)
            findings.append(Finding(
                path=module.path, line=line, col=1, check=self.name,
                message=(f"declared backend-free but reaches '{reached}' "
                         f"via top-level imports: {' -> '.join(chain)}")))
        return findings


def _first_hop_line(closure, chain: list[str], start: str) -> int:
    """Line (in the declared module) of the edge that leaves it first.

    Parent-package hops carry line 0 (they are implied, not written); fall back
    to 1 so the finding still lands at the top of the file.
    """
    for hop in chain[1:]:
        via, line = closure[hop]
        if via == start and line:
            return line
    return 1
