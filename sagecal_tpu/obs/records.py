"""Fixed-shape per-iteration solver trace records (jit/scan-safe).

Solver telemetry uses no host callbacks (dispatch is async, and
callbacks would break AOT + the fused Pallas path), so each solver
optionally returns an :class:`IterTrace` as an extra *pytree output*:
preallocated ``(itmax, ...)`` arrays carried through the solver's
``lax.while_loop`` / ``lax.scan`` and written at the live iteration
index.  Shapes are compile-time constants (the static ``itmax``), so the
record is scan/vmap-composable: stacking over clusters or EM passes just
adds leading axes.

Collection is opt-in per call (``collect_trace=True`` or
``SageConfig.collect_telemetry``) and *statically* gated: with the flag
off the solver builds the exact same jaxpr as before — the trace slot in
results is ``None`` (an empty pytree), i.e. zero extra jitted outputs
(regression-tested in tests/test_obs.py).

Rows past the executed iteration count keep their ``init`` fill (NaN for
cost-like fields), so host-side consumers can trim with
``~isnan(cost)`` or the solver's ``iterations`` output.
"""

from __future__ import annotations

from typing import Any, NamedTuple


class IterTrace(NamedTuple):
    """One solver run's per-iteration telemetry.

    Leading axis of every field is the iteration index (static itmax);
    trailing axes are solver-specific (e.g. the hybrid-chunk axis for LM,
    none for the joint LBFGS).  Wrappers (robust EM, SAGE's cluster scan)
    stack further axes *in front*.

    Fields:
      cost:      objective value after the iteration
      grad_norm: gradient norm used by the solver's own termination test
                 (inf-norm for LM, 2-norm for LBFGS/RTR)
      step:      step size (||dp|| for LM, accepted alpha for LBFGS,
                 ||eta|| for RTR's TR step)
      ls_evals:  cost-function evaluations consumed by the iteration's
                 line search / trial acceptance
      nu:        robust Student's-t nu in effect (constant for
                 non-robust solvers)
    """

    cost: Any
    grad_norm: Any
    step: Any
    ls_evals: Any
    nu: Any


def init_trace(itmax: int, shape=(), dtype=None) -> IterTrace:
    """NaN-filled trace of ``(itmax,) + shape`` per field (NaN marks
    never-executed iterations; ls_evals uses 0)."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    nanfill = jnp.full((itmax,) + tuple(shape), jnp.nan, dtype)
    return IterTrace(
        cost=nanfill,
        grad_norm=nanfill,
        step=nanfill,
        ls_evals=jnp.zeros((itmax,) + tuple(shape), dtype),
        nu=jnp.full((itmax,), jnp.nan, dtype),
    )


def write_trace(trace: IterTrace, i, *, cost=None, grad_norm=None,
                step=None, ls_evals=None, nu=None) -> IterTrace:
    """Write iteration ``i``'s row (traced index ok); ``None`` fields
    keep their previous value."""
    upd = {}
    for name, val in (("cost", cost), ("grad_norm", grad_norm),
                      ("step", step), ("ls_evals", ls_evals), ("nu", nu)):
        if val is not None:
            upd[name] = getattr(trace, name).at[i].set(val)
    return trace._replace(**upd)


def _reduce_chunk_axis(name, a):
    """Collapse the trailing hybrid-chunk axis NaN-awarely: total cost /
    line-search evals across chunks, worst-case grad norm / step.  Rows
    where every chunk is NaN (never executed) stay NaN."""
    import numpy as np

    finite = np.isfinite(a)
    anyf = finite.any(-1)
    if name in ("cost", "ls_evals"):
        red = np.where(finite, a, 0.0).sum(-1)
    else:
        red = np.where(finite, a, -np.inf).max(-1)
    return np.where(anyf, red, np.nan)


def sage_convergence_records(telemetry) -> list:
    """Flatten ``SageResult.telemetry`` into per-cluster convergence
    records for the JSONL event log: one dict per cluster with
    finite-filtered per-iteration cost/grad_norm/step/ls_evals/nu
    (EM passes concatenated in execution order), plus one record for the
    joint LBFGS polish (``cluster=None``).  EM passes of different
    solver modes (OS subsets, robust EM stacks) flatten independently,
    so heterogeneous trace shapes concatenate cleanly."""
    import numpy as np

    if not telemetry:
        return []
    out = []
    per_pass = []
    for tr in telemetry.get("em") or ():
        cost = np.asarray(tr.cost)  # leading axis = cluster
        M = cost.shape[0]
        flat = {}
        for name in tr._fields:
            a = np.asarray(getattr(tr, name))
            if a.ndim == cost.ndim:  # field carries the chunk axis
                a = _reduce_chunk_axis(name, a)
            flat[name] = a.reshape(M, -1)
        per_pass.append(flat)
    if per_pass:
        for m in range(per_pass[0]["cost"].shape[0]):
            cost = np.concatenate([p["cost"][m] for p in per_pass])
            keep = np.isfinite(cost)
            rec = {"cluster": m, "iterations": int(keep.sum())}
            for name in IterTrace._fields:
                vals = np.concatenate([p[name][m] for p in per_pass])[keep]
                rec[name] = [
                    float(v) if np.isfinite(v) else None for v in vals
                ]
            out.append(rec)
    lb = telemetry.get("lbfgs")
    if lb is not None:
        cost = np.asarray(lb.cost).reshape(-1)
        keep = np.isfinite(cost)
        rec = {"cluster": None, "solver": "lbfgs",
               "iterations": int(keep.sum())}
        for name in IterTrace._fields:
            vals = np.asarray(getattr(lb, name)).reshape(-1)[keep]
            rec[name] = [float(v) if np.isfinite(v) else None for v in vals]
        out.append(rec)
    return out


def trace_to_host(trace) -> dict:
    """Materialize a (possibly nested/stacked) trace pytree into plain
    nested lists for the JSONL event log; NaN rows are preserved (they
    mark unexecuted iterations)."""
    import numpy as np

    if trace is None:
        return {}
    return {
        name: np.asarray(getattr(trace, name)).tolist()
        for name in trace._fields
    }
