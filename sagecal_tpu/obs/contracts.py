"""Runtime contracts: opt-in checkify wrapping of the solver jit entries.

The static rules (sagecal_tpu/analysis) prove discipline *shapes* hold;
this module checks the *values* at runtime.  ``SAGECAL_CHECKIFY=1``
reroutes every :func:`~sagecal_tpu.obs.perf.instrumented_jit` call
through ``jax.experimental.checkify`` with NaN/div/index checks
(``float_checks | index_checks``).  A tripped check raises
:class:`ContractViolation` on the host and records a structured
``contract_violation`` event that the apps drain into their JSONL logs
(exit code 4 at the CLI, next to the existing divergence-abort 3).

Off (the default) the instrumented-jit fast path is untouched — the env
flag is read per call, nothing else changes, and solver outputs stay
bit-identical (pinned by tests/test_analysis.py).  On, expect roughly
2x trace size and a modest runtime cost from the error-state threading;
this is a debugging harness, not a production mode.

Functions checkify cannot wrap (Pallas kernels, exotic shardings) fall
back to the unchecked path once, recording a ``contract_unsupported``
event instead of failing the run.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Callable, List, Optional

from sagecal_tpu.obs.registry import get_registry, telemetry_enabled

CHECKIFY_ENV = "SAGECAL_CHECKIFY"
_TRUTHY = ("1", "true", "yes", "on")

_LOCK = threading.Lock()
# pending contract events, drained by the apps into their JSONL logs
# (bounded: a NaN-spewing loop must not grow host memory without bound)
_CONTRACT_EVENTS: List[dict] = []
_MAX_CONTRACT_EVENTS = 1024


class ContractViolation(RuntimeError):
    """A checkify contract (NaN/div/index) tripped inside a jitted fn."""

    def __init__(self, fn_name: str, detail: str):
        super().__init__(f"contract violation in `{fn_name}`: {detail}")
        self.fn_name = fn_name
        self.detail = detail


def checkify_enabled() -> bool:
    return os.environ.get(CHECKIFY_ENV, "").lower() in _TRUTHY


def checkify_active() -> bool:
    """Enabled AND at an outermost (non-traced) call.

    An instrumented entry reached from inside another trace (jit/vmap of
    a caller) must stay unchecked there: the checkify error value would
    itself be a tracer and ``err.get()`` cannot run on it.  The outer
    checked entry already covers those inner frames.
    """
    if not checkify_enabled():
        return False
    import jax.core

    return jax.core.trace_ctx.is_top_level()


def error_set():
    """NaN + div + out-of-bounds-index checks (the contract surface)."""
    from jax.experimental import checkify

    return checkify.float_checks | checkify.index_checks


def checked_jit(fn: Callable, jit_kwargs: dict) -> Callable:
    """jit(checkify(fn)) with the original static-arg declarations.

    ``checkify.checkify`` returns a ``(*args, **kwargs)``-signature
    callable, which breaks ``static_argnames`` resolution; re-wrapping
    it with ``functools.wraps(fn)`` restores the original signature so
    the jit kwargs apply unchanged.
    """
    import jax
    from jax.experimental import checkify

    checked = checkify.checkify(fn, errors=error_set())
    wrapper = functools.wraps(fn)(
        lambda *args, **kwargs: checked(*args, **kwargs))
    return jax.jit(wrapper, **jit_kwargs)


def note_violation(fn_name: str, detail: str) -> None:
    ev = {
        "fn": fn_name, "detail": detail,
        "unix_time": round(time.time(), 3),
    }
    with _LOCK:
        if len(_CONTRACT_EVENTS) < _MAX_CONTRACT_EVENTS:
            _CONTRACT_EVENTS.append(dict(ev, kind="contract_violation"))
    if telemetry_enabled():
        get_registry().counter_inc(
            "contract_violations_total", 1.0,
            help="checkify contract failures (NaN/div/index) per "
                 "instrumented function", fn=fn_name,
        )


def note_unsupported(fn_name: str, reason: str) -> None:
    """checkify could not wrap ``fn_name``; the call fell back to the
    unchecked path (recorded once per wrapper)."""
    with _LOCK:
        if len(_CONTRACT_EVENTS) < _MAX_CONTRACT_EVENTS:
            _CONTRACT_EVENTS.append({
                "kind": "contract_unsupported", "fn": fn_name,
                "detail": reason[:500],
                "unix_time": round(time.time(), 3),
            })


def raise_if_error(err, fn_name: str) -> None:
    """Host-side check of a checkify error value: record + raise."""
    msg: Optional[str] = err.get()
    if msg is None:
        return
    note_violation(fn_name, msg)
    raise ContractViolation(fn_name, msg)


def drain_contract_events() -> List[dict]:
    """Return and clear the pending contract events (app -> JSONL)."""
    with _LOCK:
        evs, _CONTRACT_EVENTS[:] = list(_CONTRACT_EVENTS), []
    return evs


def emit_contract_events(elog) -> int:
    """Drain pending contract events into an :class:`EventLog`."""
    n = 0
    for ev in drain_contract_events():
        kind = ev.pop("kind", "contract_violation")
        elog.emit(kind, **ev)
        n += 1
    return n


def reset_contract_events() -> None:
    """Clear the module-level store (tests)."""
    with _LOCK:
        _CONTRACT_EVENTS.clear()
