"""JL007: undonated large carry on a jit entry point (report-only).

The solver entry points thread large carry buffers — the parameter
vector ``p0``, the LBFGS ``memory`` pair, solver ``state`` — through
jit boundaries.  When the caller never reuses the input after the
call (the universal pattern for ``fit``-style entries that return the
updated carry), ``donate_argnums``/``donate_argnames`` lets XLA alias
the output into the input buffer and halves the HBM high-water mark
at the solver boundary.

This rule pins the convention: any jit root whose signature contains
a carry-named parameter that is neither static nor donated is
reported.  Report-only by default, because donation is *only* safe
when every caller treats the argument as consumed — entries whose
callers reuse the args tuple (the lm/os-lm micro-benchmark harnesses,
timing loops) must stay undonated and live in the baseline instead.
"""

from __future__ import annotations

import ast
from typing import Iterator

from sagecal_tpu.analysis.engine import Finding, Rule

# parameter names that (by repo convention) carry solver state whose
# input buffer is dead after the call
_CARRY_NAMES = frozenset({"p0", "memory", "state", "carry"})


def _positional_params(node) -> list:
    a = node.args
    return list(getattr(a, "posonlyargs", ())) + list(a.args)


class UndonatedCarry(Rule):
    id = "JL007"
    title = "jit entry threads a large carry without donate_argnums"
    report_only = True

    def check(self, graph) -> Iterator[Finding]:
        for mi in graph.modules.values():
            if mi.tree is None:
                continue
            for fi in mi.functions.values():
                if not fi.jit_root:
                    continue
                if not isinstance(fi.node, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                    continue
                params = _positional_params(fi.node)
                for idx, p in enumerate(params):
                    name = p.arg
                    if name not in _CARRY_NAMES:
                        continue
                    if name in fi.static_argnames \
                            or idx in fi.static_argnums:
                        continue
                    if name in fi.donate_argnames \
                            or idx in fi.donate_argnums:
                        continue
                    yield self.finding(
                        mi, fi.node,
                        f"jit entry `{fi.name}` threads carry `{name}` "
                        f"(arg {idx}) without donate_argnums/"
                        f"donate_argnames — donate it if callers never "
                        f"reuse the input buffer, or baseline it if "
                        f"they do",
                        symbol=fi.qualname,
                    )
