"""Named scopes of the tile program, and device time attributed to them.

``sagefit_packed`` is one jit, so a device trace of a tile lists
thousands of HLO ops and nothing else.  The solver code names its parts
with ``jax.named_scope``; the names reach every lowered op as its
``op_name`` metadata (``jit(sagefit_packed)/em/while/body/.../solve/
.../tr/.../tcg/...``) and change nothing else in the program:

=============  ==========================================================
scope          code
=============  ==========================================================
``em``         ``solvers/sage._em_phase``: the EM passes, with their
               initial full predict
``residual``   the add-back and subtract of ``em_residual_scan``'s
               cluster step (``em/residual``)
``solve``      the per-cluster solve (``em/solve``), with the solver's
               own ``rsd`` (steepest-descent warm-up), ``tr`` (the trust
               region loop), ``tcg`` (its truncated CG) and ``nu``
               (robust weights and the Student's-t nu update)
``lbfgs``      the joint LBFGS, the fused objective kernels included
``finalize``   ``solvers/sage._finalize``: the final predict and bundling
=============  ==========================================================

The multi-band programs of ``parallel/mesh.py`` name theirs the same way:

=============  ==========================================================
scope          code
=============  ==========================================================
``admm``       the consensus ADMM of ``make_admm_mesh_fn``, with ``plain``
               (the admm-0 solve of every local slot), ``xstep`` (the
               augmented solve of a later round's active slot) and
               ``zstep`` (the Z update and its collectives)
``bands``      the band programs: ``coh`` (channel average and
               coherencies) and ``residual`` (per-channel residuals)
=============  ==========================================================

:func:`scoped` names a function's ops; :func:`scope_path` reads the
scope out of an ``op_name``;
:func:`hlo_scopes` maps each instruction of an optimized HLO text to its
scope; :func:`scope_totals` sums per-op self times by scope.
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Iterable, Mapping

TILE_SCOPES = ("em", "lbfgs", "finalize")
MESH_SCOPES = ("admm", "bands")
SCOPE_NAMES = frozenset(TILE_SCOPES + MESH_SCOPES + (
    "residual", "solve", "rsd", "tr", "tcg", "nu", "plain", "xstep",
    "zstep", "coh"))
UNSCOPED = "(unscoped)"

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INST_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.+?\s([\w\-]+)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# computations that run as ops of their own: loop bodies and conditions,
# conditional branches.  Fusion bodies and reduction regions run inside
# the one op that calls them.
_CALLS_RE = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")


# a transformation wraps the scope it was applied in: vmap(tr),
# transpose(jvp(nu))
_WRAP_RE = re.compile(r"^(?:vmap|jvp|transpose|remat|checkpoint)\((.*)\)$")


def _unwrap(part: str) -> str:
    m = _WRAP_RE.match(part)
    while m:
        part = m.group(1)
        m = _WRAP_RE.match(part)
    return part


def scope_path(op_name: str) -> str:
    """The scope names in an ``op_name``, outermost first, each once,
    joined by ``/`` (``""`` when it has none): ``jit(f)/em/while/body/
    closed_call/solve/while/body/vmap(tr)/...`` -> ``em/solve/tr``.  A
    transformation applied inside a scope repeats its name
    (``lbfgs/.../transpose(jvp(lbfgs))/...``); the repeat is dropped."""
    parts = (_unwrap(p) for p in op_name.split("/"))
    return "/".join(dict.fromkeys(p for p in parts if p in SCOPE_NAMES))


def scoped(name: str) -> Callable:
    """Decorator: trace the function under ``jax.named_scope(name)``,
    looked up at each call (so a test can stub it out)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope path}`` for every instruction of an
    optimized HLO module that runs as an op of its own: those of the
    entry computation and of the loop bodies, conditions and branches
    it reaches.  An instruction whose own ``op_name`` names no scope
    (XLA-made copies, hoisted constants) takes the scope of the
    instruction that calls its computation."""
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INST_RE.match(line)
        if m:
            on = _OP_NAME_RE.search(line)
            comps[cur].append((m.group(1), scope_path(on.group(1))
                               if on else "", line))
    out: Dict[str, str] = {}
    stack = [(entry, "")] if entry in comps else []
    seen = set()
    while stack:
        comp, inherited = stack.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, scope, line in comps[comp]:
            scope = scope or inherited
            out[name] = scope
            for m in _CALLS_RE.finditer(line):
                called = ([m.group(1)] if m.group(1) else
                          [c.strip().lstrip("%") for c in
                           m.group(2).split(",")])
                stack += [(c, scope) for c in called if c in comps]
    return out


def scope_totals(names: Iterable[str], self_s: Iterable[float],
                 scope_of: Mapping[str, str], depth: int = 1) -> dict:
    """Self seconds and op executions per scope: ``{scope: {"s": float,
    "n": int}}``, the scope cut to its first ``depth`` names, and ops
    with no scope (or not in ``scope_of``) under ``"(unscoped)"``.
    ``names`` are the executed ops' instruction names (a leading ``%``
    is ignored), ``self_s`` their self times."""
    out: Dict[str, dict] = {}
    cut: Dict[str, str] = {}
    for name, s in zip(names, self_s):
        key = cut.get(name)
        if key is None:
            path = scope_of.get(name.lstrip("%"), "")
            key = "/".join(path.split("/")[:depth]) if path else UNSCOPED
            cut[name] = key
        t = out.setdefault(key, {"s": 0.0, "n": 0})
        t["s"] += s
        t["n"] += 1
    return out
