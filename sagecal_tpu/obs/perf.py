"""Performance observability: compile/recompile tracking, device-memory
watermarks, host-transfer auditing, and the bench regression gate.

PR 1 made the *science* observable (solver traces, ADMM residuals,
manifests, JSONL events); this module makes the *performance*
observable.  Four pieces:

- :func:`instrumented_jit` — a drop-in ``jax.jit`` replacement adopted
  by the solvers (``lm``/``robust``/``rtr``/``lbfgs``/``sage``), the
  fused RIME kernel wrappers and the device-mesh ADMM driver.  With
  telemetry off it is a single flag check on top of the plain jitted
  call (the jaxpr, output signature, and jit cache are untouched).
  With telemetry on it keys every call by an *abstract input
  signature* (pytree structure + leaf shape/dtype + static-arg
  values), AOT-compiles each new signature through
  ``.lower()``/``.compile()`` so lowering and compile wall-times are
  measured separately, pulls ``compiled.cost_analysis()`` flops/bytes,
  and feeds everything into the PR-1 metrics registry plus a compile
  event stream that apps drain into their JSONL logs.  The per-name
  compile counter IS the recompile detector: a second compile of the
  same name means a signature change (new shapes, a changed static
  config) retraced the function.
- device-memory watermarks (:func:`device_memory_snapshot`,
  :func:`record_memory_watermark`) via ``device.memory_stats()`` with
  a graceful host-RSS fallback on backends that expose no allocator
  stats (CPU), plus an on-demand
  ``jax.profiler.device_memory_profile`` dump
  (:func:`dump_memory_profile`).
- :class:`TransferAudit` — an opt-in ``jax.transfer_guard("log")``
  context (``SAGECAL_TRANSFER_AUDIT=1``) that captures the guard's
  C++ stderr lines, classifies host<->device transfers by direction,
  and surfaces them as registry counters + a ``transfer_audit`` event.
- the perf-regression gate (:func:`gate_compare`) behind
  ``sagecal-tpu diag gate``: a fresh bench JSON is compared against a
  pinned baseline with per-metric tolerances and direction semantics
  (throughput up = good, bytes/memory up = bad); any out-of-tolerance
  metric is a nonzero exit.

Everything here is host-side; nothing touches a tracer.  jax/numpy are
imported lazily so ``sagecal_tpu.obs`` stays importable before backend
selection.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from sagecal_tpu.obs.registry import get_registry, telemetry_enabled

_TRUTHY = ("1", "true", "yes", "on")
_AUDIT_ENV = "SAGECAL_TRANSFER_AUDIT"
_MEMPROF_ENV = "SAGECAL_MEMORY_PROFILE"

# ------------------------------------------------------------------ store

_LOCK = threading.Lock()
# per-function aggregates: name -> dict(compiles, lower_seconds,
# compile_seconds, flops, bytes_accessed, dispatches)
_FN_STATS: Dict[str, Dict[str, float]] = {}
# compile event stream the apps drain into their JSONL logs (bounded:
# a runaway retrace loop must not grow host memory without bound)
_COMPILE_EVENTS: List[dict] = []
_MAX_COMPILE_EVENTS = 4096
# per-phase peak-memory watermarks (bytes)
_WATERMARKS: Dict[str, float] = {}
# persistent-compilation-cache hit/miss counts observed through
# jax.monitoring ('/jax/compilation_cache/cache_hits|cache_misses'):
# a hit means XLA skipped the compile and deserialized a cached
# executable — a WARM compile; note_compile still records the (short)
# wall time, so the pair lets `diag perf` split warm from cold
_CACHE_EVENTS: Dict[str, int] = {"hits": 0, "misses": 0}
_cache_listener_installed = False


def reset_perf_stats() -> None:
    """Clear the module-level perf store (tests)."""
    with _LOCK:
        _FN_STATS.clear()
        _COMPILE_EVENTS.clear()
        _WATERMARKS.clear()
        _CACHE_EVENTS["hits"] = 0
        _CACHE_EVENTS["misses"] = 0


def perf_stats() -> Dict[str, Dict[str, float]]:
    """Per-instrumented-function aggregate snapshot."""
    with _LOCK:
        return {k: dict(v) for k, v in _FN_STATS.items()}


def drain_compile_events() -> List[dict]:
    """Return and clear the pending compile events (app -> JSONL)."""
    with _LOCK:
        evs, _COMPILE_EVENTS[:] = list(_COMPILE_EVENTS), []
    return evs


def note_compile(name: str, lower_seconds: float, compile_seconds: float,
                 flops: Optional[float] = None,
                 bytes_accessed: Optional[float] = None,
                 signature: str = "", aot: bool = True) -> dict:
    """Record one compilation of ``name`` into the registry, the
    per-function aggregates, and the compile event stream.  Public so
    code that already AOT-compiles itself (the serve executable cache)
    reports through the same channel as :func:`instrumented_jit`."""
    with _LOCK:
        st = _FN_STATS.setdefault(name, {
            "compiles": 0, "lower_seconds": 0.0, "compile_seconds": 0.0,
            "flops": 0.0, "bytes_accessed": 0.0, "dispatches": 0,
        })
        st["compiles"] += 1
        st["lower_seconds"] += lower_seconds
        st["compile_seconds"] += compile_seconds
        if flops:
            st["flops"] = float(flops)
        if bytes_accessed:
            st["bytes_accessed"] = float(bytes_accessed)
        n = st["compiles"]
        ev = {
            "fn": name, "signature": signature, "n_compiles": n,
            "lower_seconds": round(lower_seconds, 6),
            "compile_seconds": round(compile_seconds, 6),
            "flops": flops, "bytes_accessed": bytes_accessed, "aot": aot,
        }
        if len(_COMPILE_EVENTS) < _MAX_COMPILE_EVENTS:
            _COMPILE_EVENTS.append(ev)
    reg = get_registry()
    reg.counter_inc(
        "jit_compiles_total", 1.0,
        help="XLA compilations per instrumented function (a count > 1 "
             "for one fn means a recompile: new shapes or a changed "
             "static config)", fn=name,
    )
    reg.observe("jit_lower_seconds", lower_seconds,
                help="trace+lower wall-time per compilation", fn=name)
    reg.observe("jit_compile_seconds", compile_seconds,
                help="XLA compile wall-time per compilation", fn=name)
    if flops:
        reg.gauge_set("xla_cost_analysis_flops", float(flops),
                      help="compiled.cost_analysis() flops of the last "
                           "compilation", fn=name)
    if bytes_accessed:
        reg.gauge_set("xla_cost_analysis_bytes_accessed",
                      float(bytes_accessed),
                      help="compiled.cost_analysis() bytes accessed of "
                           "the last compilation", fn=name)
    return ev


def _cache_event_listener(event: str, **_kw) -> None:
    """jax.monitoring listener: count persistent-compilation-cache
    hits/misses and bump the registry so warm compiles are visible in
    scrapes without waiting for an event-log drain."""
    if event == "/jax/compilation_cache/cache_hits":
        key = "hits"
        name = "jit_persistent_cache_hits_total"
        txt = ("XLA compilations served from the persistent compilation "
               "cache (warm compiles: deserialization, no codegen)")
    elif event == "/jax/compilation_cache/cache_misses":
        key = "misses"
        name = "jit_persistent_cache_misses_total"
        txt = ("XLA compilations not found in the persistent compilation "
               "cache (cold compiles: full codegen, then written back)")
    else:
        return
    with _LOCK:
        _CACHE_EVENTS[key] += 1
    get_registry().counter_inc(name, 1.0, help=txt)


def _install_cache_listener() -> None:
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    try:
        import jax.monitoring

        jax.monitoring.register_event_listener(_cache_event_listener)
        _cache_listener_installed = True
    except Exception:
        pass


# JAX's own compile work, from jax.monitoring durations: seconds of
# jaxpr tracing, lowering and backend compilation (a persistent-cache
# load included), and the count of backend compilations
_JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
_JIT_CLOCK = {"s": 0.0, "n": 0}
_jit_listener_installed = False


def _jit_duration_listener(event: str, duration_secs: float, **_kw) -> None:
    if event in _JIT_EVENTS:
        with _LOCK:
            _JIT_CLOCK["s"] += duration_secs
            _JIT_CLOCK["n"] += event == _JIT_EVENTS[2]


def jit_clock() -> Tuple[float, int]:
    """(seconds, compilations) of JAX compile work in this process so
    far: jaxpr tracing, lowering and backend compilation, cache loads
    included.  The difference of two readings is what ran between them;
    the apps print it per tile (``jit_s``, ``jit_n``)."""
    global _jit_listener_installed
    if not _jit_listener_installed:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _jit_duration_listener)
        _jit_listener_installed = True
    with _LOCK:
        return _JIT_CLOCK["s"], _JIT_CLOCK["n"]


def compile_cache_stats() -> Dict[str, int]:
    """Persistent-compilation-cache hit/miss counts observed so far."""
    with _LOCK:
        return dict(_CACHE_EVENTS)


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the
    checkout root (found from this package's path, so every working
    directory and every process of one checkout shares it)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and install the cache-hit monitoring
    listener, so a second process compiling the same program
    deserializes the cached executable instead of re-running XLA
    codegen.  Every app entry calls this once at startup.
    The min-compile-time floor is dropped to 0 s: calibration programs
    are few and large, so caching everything is strictly a win."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _install_cache_listener()
    return path


def _cost_analysis(compiled) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from a Compiled, or (None, None).  XLA
    counts nothing inside a Pallas custom call, so the flops of a fused
    program are under-reported — record for attribution, don't
    headline."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0)) or None
        by = float(cost.get("bytes accessed", 0.0)) or None
        return flops, by
    except Exception:
        return None, None


_COLLECTIVE_RE = None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}


def _hlo_shape_bytes(shapes: str) -> int:
    import re

    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shapes):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def collective_cost_analysis(compiled) -> dict:
    """Static cross-device communication analysis of a compiled HLO.

    Parses ``compiled.as_text()`` and attributes every collective op
    (all-reduce / all-gather / all-to-all / reduce-scatter /
    collective-permute) by its OUTPUT bytes to either the steady-state
    round loop — any computation reachable from a ``while`` op's body —
    or one-time setup/teardown.  ``collective_bytes_per_round`` is the
    per-device bytes a single iteration of the round loop moves through
    collectives: the honest comms floor the transpose-reduced consensus
    z-step exists to shrink (each op is counted once per round; the mesh
    ADMM keeps its collectives out of nested inner loops).

    Returns ``{}`` when no HLO text is available (e.g. a backend without
    ``as_text``), otherwise::

        {"collective_bytes_total":     sum over every collective op,
         "collective_bytes_per_round": sum inside while-body-reachable
                                       computations,
         "collective_ops_per_round":   op count in the round loop,
         "collective_breakdown":       {op_kind: per-round bytes}}
    """
    import re

    try:
        txt = compiled.as_text()
    except Exception:
        return {}
    if not isinstance(txt, str) or not txt:
        return {}
    comp_head = re.compile(
        r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$"
    )
    coll_re = re.compile(
        r"=\s*\(?([^)=]*?)\)?\s*"
        r"(all-reduce|all-gather|all-to-all|reduce-scatter|"
        r"collective-permute)(-start)?\("
    )
    ref_re = re.compile(
        r"(?:condition|body|to_apply|calls|update_computation|select|"
        r"scatter)=%?([\w.\-]+)"
    )
    ref_set_re = re.compile(
        r"(?:called_computations|branch_computations)=\{([^}]*)\}"
    )
    while_re = re.compile(r"=\s*\(?[^)=]*\)?\s*while\(")
    colls: Dict[str, list] = {}
    refs: Dict[str, set] = {}
    while_bodies: set = set()
    cur = None
    for raw in txt.splitlines():
        line = raw.strip()
        m = comp_head.match(raw) or comp_head.match(line)
        if m:
            cur = m.group(1)
            colls.setdefault(cur, [])
            refs.setdefault(cur, set())
            continue
        if cur is None:
            continue
        cm = coll_re.search(line)
        if cm:  # "-done" halves of async pairs don't match the regex
            colls[cur].append(
                (cm.group(2), _hlo_shape_bytes(cm.group(1)))
            )
        names = set(ref_re.findall(line))
        for grp in ref_set_re.findall(line):
            names.update(
                n.strip().lstrip("%") for n in grp.split(",") if n.strip()
            )
        refs[cur].update(names)
        if while_re.search(line):
            wm = re.search(r"body=%?([\w.\-]+)", line)
            if wm:
                while_bodies.add(wm.group(1))
    # computations reachable from any while body run once per round
    reach: set = set()
    stack = [b for b in while_bodies if b in colls]
    while stack:
        c = stack.pop()
        if c in reach:
            continue
        reach.add(c)
        stack.extend(r for r in refs.get(c, ()) if r in colls)
    per_round = 0
    nops = 0
    breakdown: Dict[str, float] = {}
    total = 0
    for c, items in colls.items():
        for op, b in items:
            total += b
            if c in reach:
                per_round += b
                nops += 1
                breakdown[op] = breakdown.get(op, 0.0) + b
    return {
        "collective_bytes_total": float(total),
        "collective_bytes_per_round": float(per_round),
        "collective_ops_per_round": int(nops),
        "collective_breakdown": breakdown,
    }


# -------------------------------------------------------- instrumented_jit


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


class _InstrumentedJit:
    """Callable wrapper produced by :func:`instrumented_jit`."""

    def __init__(self, fn: Callable, name: Optional[str], jit_kwargs: dict):
        import jax

        self._fn = fn
        self.name = name or getattr(fn, "__name__", repr(fn))
        self._jitted = jax.jit(fn, **jit_kwargs)
        # kept for the SAGECAL_CHECKIFY contract path, which rebuilds
        # the jit around checkify(fn) with the same static declarations
        self._jit_kwargs = dict(jit_kwargs)
        self._checked = None
        self._checkify_broken = False
        self._static_argnums = frozenset(
            int(i) for i in _as_tuple(jit_kwargs.get("static_argnums"))
        )
        self._static_argnames = frozenset(
            _as_tuple(jit_kwargs.get("static_argnames"))
        )
        # donated buffers make the AOT executable single-shot-unsafe to
        # share with the jit cache; fall back to first-call timing there
        self._aot_ok = not any(k.startswith("donate") for k in jit_kwargs)
        # signature -> Compiled (AOT path) | None (seen, jit-cache path)
        self._compiled: Dict[Any, Any] = {}
        self.__wrapped__ = fn
        self.__doc__ = getattr(fn, "__doc__", None)

    # -- signature keying ------------------------------------------------
    def _leaf_desc(self, x) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{dtype}{tuple(shape)}"
        # dynamic python scalars are traced weak-typed: the VALUE does
        # not retrace, only the type does
        if isinstance(x, (bool, int, float, complex)):
            return f"py:{type(x).__name__}"
        return repr(x)

    def _sig_key(self, args, kwargs):
        import jax

        stat = tuple(
            (i, repr(args[i])) for i in sorted(self._static_argnums)
            if i < len(args)
        ) + tuple(
            (k, repr(kwargs[k])) for k in sorted(self._static_argnames)
            if k in kwargs
        )
        dyn_args = tuple(
            a for i, a in enumerate(args) if i not in self._static_argnums
        )
        dyn_kwargs = {
            k: v for k, v in kwargs.items() if k not in self._static_argnames
        }
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        return (stat, str(treedef), tuple(self._leaf_desc(x) for x in leaves))

    def _dyn_call_args(self, args, kwargs):
        dyn_args = tuple(
            a for i, a in enumerate(args) if i not in self._static_argnums
        )
        dyn_kwargs = {
            k: v for k, v in kwargs.items() if k not in self._static_argnames
        }
        return dyn_args, dyn_kwargs

    # -- compile paths ---------------------------------------------------
    def _aot_compile(self, sig, args, kwargs):
        t0 = time.perf_counter()
        lowered = self._jitted.lower(*args, **kwargs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        flops, by = _cost_analysis(compiled)
        note_compile(self.name, t1 - t0, t2 - t1, flops, by,
                     signature=_sig_hash(sig), aot=True)
        return compiled

    def __call__(self, *args, **kwargs):
        # contract path first: SAGECAL_CHECKIFY must catch NaNs even in
        # runs with telemetry off.  Only at the outermost entry: when
        # this wrapper is reached from inside another trace (jit/vmap of
        # a caller), the checkify error value would itself be a tracer
        # and err.get() cannot run — the outer checked entry already
        # covers those frames.
        from sagecal_tpu.obs import contracts

        if contracts.checkify_active() and not self._checkify_broken:
            try:
                if self._checked is None:
                    self._checked = contracts.checked_jit(
                        self._fn, self._jit_kwargs)
                err, out = self._checked(*args, **kwargs)
            except Exception as e:
                # checkify cannot wrap everything (Pallas kernels,
                # donated buffers, exotic shardings): record once, then
                # permanently route this wrapper unchecked
                self._checkify_broken = True
                self._checked = None
                contracts.note_unsupported(self.name, repr(e))
            else:
                contracts.raise_if_error(err, self.name)
                return out
        if not telemetry_enabled():
            return self._jitted(*args, **kwargs)
        sig = self._sig_key(args, kwargs)
        entry = self._compiled.get(sig)
        get_registry().counter_inc(
            "jit_dispatches_total", 1.0,
            help="calls into instrumented jitted functions", fn=self.name,
        )
        if entry is None and sig not in self._compiled:
            if self._aot_ok:
                try:
                    entry = self._aot_compile(sig, args, kwargs)
                except Exception:
                    entry = None
            if entry is None:
                # AOT refused (donation, exotic args): time the first
                # dispatch — compile + first execution together
                t0 = time.perf_counter()
                out = self._jitted(*args, **kwargs)
                dt = time.perf_counter() - t0
                note_compile(self.name, 0.0, dt, signature=_sig_hash(sig),
                             aot=False)
                self._compiled[sig] = None
                return out
            self._compiled[sig] = entry
        if entry is not None:
            dyn_args, dyn_kwargs = self._dyn_call_args(args, kwargs)
            try:
                return entry(*dyn_args, **dyn_kwargs)
            except Exception:
                # sharding/commitment mismatch with the AOT executable:
                # permanently route this signature through the jit cache
                self._compiled[sig] = None
        return self._jitted(*args, **kwargs)

    # passthroughs so the wrapper stays a drop-in jax.jit replacement
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def clear_cache(self) -> None:
        self._compiled.clear()
        try:
            self._jitted.clear_cache()
        except Exception:
            pass

    @property
    def compiles(self) -> int:
        """Compilations recorded under this wrapper's name (aggregated
        across wrapper instances sharing the name)."""
        return int(perf_stats().get(self.name, {}).get("compiles", 0))


def _sig_hash(sig) -> str:
    return hashlib.sha1(repr(sig).encode()).hexdigest()[:12]


def instrumented_jit(fn: Optional[Callable] = None, *,
                     name: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with compile/recompile telemetry (module docstring).

    Usable bare (``instrumented_jit(f)``), with options
    (``instrumented_jit(f, name="solver", static_argnames=("cfg",))``)
    or as a decorator factory.  All other keyword arguments pass
    through to ``jax.jit``.
    """
    if fn is None:
        def deco(f):
            return _InstrumentedJit(f, name, jit_kwargs)
        return deco
    return _InstrumentedJit(fn, name, jit_kwargs)


# ------------------------------------------------------------ device memory


def device_memory_snapshot(device=None) -> dict:
    """Current/peak device-memory bytes.  ``device.memory_stats()``
    where the backend exposes allocator stats (TPU/GPU); graceful
    fallback to host RSS (``source: host_rss``) on backends that
    return None (CPU) or raise — the numbers stay meaningful for the
    host-side pipeline stages."""
    stats = None
    kind = "unknown"
    try:
        import jax

        device = device or jax.local_devices()[0]
        kind = getattr(device, "device_kind", "unknown")
        stats = device.memory_stats()
    except Exception:
        stats = None
    if stats:
        inuse = stats.get("bytes_in_use", 0)
        return {
            "source": "device",
            "device_kind": kind,
            "bytes_in_use": int(inuse),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", inuse)),
            "bytes_limit": int(stats["bytes_limit"])
            if "bytes_limit" in stats else None,
        }
    rss = peak = 0
    try:
        import resource

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        rss = rss or peak
    return {
        "source": "host_rss",
        "device_kind": kind,
        "bytes_in_use": int(rss or peak),
        "peak_bytes_in_use": int(peak or rss),
        "bytes_limit": None,
    }


def record_memory_watermark(phase: str, device=None) -> Optional[dict]:
    """Sample the device-memory snapshot and fold its peak into the
    per-``phase`` watermark (registry gauge ``peak_device_memory_bytes``
    + the module store :func:`memory_watermarks` reads).  No-op (None)
    when telemetry is off, so hot paths call it unguarded."""
    if not telemetry_enabled():
        return None
    snap = device_memory_snapshot(device)
    peak = float(snap.get("peak_bytes_in_use") or 0)
    with _LOCK:
        if peak > _WATERMARKS.get(phase, -1.0):
            _WATERMARKS[phase] = peak
    reg = get_registry()
    prev = reg.get_gauge("peak_device_memory_bytes", phase=phase)
    if prev is None or peak > prev:
        reg.gauge_set(
            "peak_device_memory_bytes", peak,
            help="peak device (or host-RSS fallback) bytes observed per "
                 "pipeline phase", phase=phase,
        )
    reg.gauge_set("device_memory_bytes_in_use",
                  float(snap.get("bytes_in_use") or 0),
                  help="device bytes in use at the last phase sample",
                  phase=phase)
    return snap


def memory_watermarks() -> Dict[str, float]:
    """Per-phase peak bytes recorded so far (for the run-end event)."""
    with _LOCK:
        return dict(_WATERMARKS)


def dump_memory_profile(path: Optional[str] = None) -> Optional[str]:
    """Write a ``jax.profiler.device_memory_profile()`` pprof dump to
    ``path`` (default: the ``SAGECAL_MEMORY_PROFILE`` env var; no-op
    returning None when neither is set or the profiler fails)."""
    path = path or os.environ.get(_MEMPROF_ENV)
    if not path:
        return None
    try:
        import jax

        prof = jax.profiler.device_memory_profile()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "wb") as f:
            f.write(prof)
        return path
    except Exception:
        return None


# ---------------------------------------------------------- transfer audit


def transfer_audit_enabled() -> bool:
    return os.environ.get(_AUDIT_ENV, "").strip().lower() in _TRUTHY


class TransferAudit:
    """Opt-in implicit host<->device transfer audit.

    Inside the context, ``jax.transfer_guard("log")`` is active and the
    guard's C++ log lines (``guard_lib.cc`` writes straight to fd 2 —
    Python logging never sees them) are captured through an fd-level
    stderr redirect.  On exit the captured stream is replayed to the
    real stderr (nothing is swallowed), lines are classified by
    direction into :attr:`counts`, samples are kept, and registry
    counters ``transfer_guard_transfers_total{direction=...}`` are
    bumped.  ``emit(elog)`` writes one ``transfer_audit`` event.

    Disabled (``enabled=False`` / env unset) the context is a no-op, so
    apps wrap their loops unconditionally."""

    _MARKS = (
        ("host-to-device transfer:", "host_to_device"),
        ("device-to-host transfer:", "device_to_host"),
        ("device-to-device transfer:", "device_to_device"),
    )

    def __init__(self, enabled: Optional[bool] = None, max_samples: int = 20):
        self.enabled = transfer_audit_enabled() if enabled is None else enabled
        self.max_samples = max_samples
        self.counts: Dict[str, int] = {}
        self.samples: List[str] = []
        self._guard = None
        self._tmp = None
        self._saved_fd = None

    def __enter__(self) -> "TransferAudit":
        if not self.enabled:
            return self
        import jax

        self._guard = jax.transfer_guard("log")
        self._guard.__enter__()
        try:
            sys.stderr.flush()
        except Exception:
            pass
        self._tmp = tempfile.TemporaryFile()
        self._saved_fd = os.dup(2)
        os.dup2(self._tmp.fileno(), 2)
        return self

    def __exit__(self, *exc) -> bool:
        # idempotent: apps close the audit before emitting its counts
        # AND in a finally for the exception path
        if not self.enabled or self._saved_fd is None:
            return False
        try:
            sys.stderr.flush()
        except Exception:
            pass
        os.dup2(self._saved_fd, 2)
        os.close(self._saved_fd)
        self._saved_fd = None
        self._guard.__exit__(*exc)
        self._tmp.seek(0)
        text = self._tmp.read().decode("utf-8", errors="replace")
        self._tmp.close()
        if text:
            # replay: warnings and guard lines stay visible on stderr
            try:
                sys.stderr.write(text)
                sys.stderr.flush()
            except Exception:
                pass
        for line in text.splitlines():
            for mark, direction in self._MARKS:
                if mark in line:
                    self.counts[direction] = self.counts.get(direction, 0) + 1
                    if len(self.samples) < self.max_samples:
                        self.samples.append(line[line.index(mark):][:200])
                    break
        reg = get_registry()
        for direction, n in self.counts.items():
            reg.counter_inc(
                "transfer_guard_transfers_total", float(n),
                help="implicit transfers observed by the "
                     "SAGECAL_TRANSFER_AUDIT=1 jax.transfer_guard audit",
                direction=direction,
            )
        return False

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def emit(self, elog) -> None:
        """One ``transfer_audit`` JSONL event (no-op when disabled or
        the app runs without an event log)."""
        if elog is None or not self.enabled:
            return
        elog.emit("transfer_audit", counts=self.counts, total=self.total,
                  samples=self.samples)


# ----------------------------------------------- app-side emit convenience


def emit_perf_events(elog, device=None) -> None:
    """Drain pending compile events and the memory watermarks into an
    app's JSONL event log (one ``jit_compile`` event per compilation +
    one ``memory_watermark`` summary).  Safe to call with ``elog=None``
    (events stay queued for a later drain) and at any cadence."""
    if elog is None:
        return
    for ev in drain_compile_events():
        elog.emit("jit_compile", **ev)
    cache = compile_cache_stats()
    if cache.get("hits") or cache.get("misses"):
        # warm/cold split of this run's XLA compiles: hits came from the
        # persistent compilation cache (deserialize, no codegen)
        elog.emit("jit_cache_hit", hits=int(cache.get("hits", 0)),
                  misses=int(cache.get("misses", 0)))
    marks = memory_watermarks()
    if marks:
        elog.emit("memory_watermark", phases=marks,
                  snapshot=device_memory_snapshot(device))


# ----------------------------------------------------- diag perf aggregation


def aggregate_perf_events(events: List[dict]) -> dict:
    """Fold a JSONL event list into the ``diag perf`` attribution
    tables: per-function compile stats, per-phase memory watermarks,
    and transfer-audit counts."""
    fns: Dict[str, Dict[str, float]] = {}
    mem: Dict[str, float] = {}
    transfers: Dict[str, int] = {}
    cache = {"hits": 0, "misses": 0}
    snapshot = None
    for e in events:
        t = e.get("type")
        if t == "jit_cache_hit":
            for k in ("hits", "misses"):
                v = e.get(k)
                if isinstance(v, (int, float)):
                    cache[k] += int(v)
        elif t == "jit_compile":
            st = fns.setdefault(str(e.get("fn", "?")), {
                "compiles": 0, "lower_seconds": 0.0, "compile_seconds": 0.0,
                "flops": 0.0, "bytes_accessed": 0.0,
            })
            st["compiles"] += 1
            for k in ("lower_seconds", "compile_seconds"):
                v = e.get(k)
                if isinstance(v, (int, float)):
                    st[k] += float(v)
            for k in ("flops", "bytes_accessed"):
                v = e.get(k)
                if isinstance(v, (int, float)) and v:
                    st[k] = float(v)
        elif t == "memory_watermark":
            for phase, v in (e.get("phases") or {}).items():
                if isinstance(v, (int, float)):
                    mem[str(phase)] = max(mem.get(str(phase), 0.0), float(v))
            snapshot = e.get("snapshot") or snapshot
        elif t == "transfer_audit":
            for d, n in (e.get("counts") or {}).items():
                if isinstance(n, (int, float)):
                    transfers[str(d)] = transfers.get(str(d), 0) + int(n)
    return {"functions": fns, "memory": mem, "transfers": transfers,
            "compile_cache": cache, "memory_snapshot": snapshot}


def _fmt_bytes(n: Optional[float]) -> str:
    if not n:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TiB"


def format_perf_report(agg: dict) -> str:
    """Human table for ``diag perf`` from :func:`aggregate_perf_events`
    output (also used on a live :func:`perf_stats` snapshot)."""
    lines = []
    fns = agg.get("functions") or {}
    if fns:
        w = max(len(n) for n in fns) + 2
        lines.append(f"{'function':<{w}}{'compiles':>9}{'lower_s':>9}"
                     f"{'compile_s':>11}{'gflops':>10}{'bytes':>10}")
        for name in sorted(fns, key=lambda n: -fns[n]["compile_seconds"]):
            st = fns[name]
            gf = st.get("flops", 0.0) / 1e9
            lines.append(
                f"{name:<{w}}{int(st['compiles']):>9}"
                f"{st['lower_seconds']:>9.2f}{st['compile_seconds']:>11.2f}"
                f"{(f'{gf:.2f}' if gf else '-'):>10}"
                f"{_fmt_bytes(st.get('bytes_accessed')):>10}"
            )
    else:
        lines.append("no jit_compile events (run with SAGECAL_TELEMETRY=1 "
                     "and an instrumented path)")
    cache = agg.get("compile_cache") or {}
    if cache.get("hits") or cache.get("misses"):
        h, m = int(cache.get("hits", 0)), int(cache.get("misses", 0))
        lines.append(f"persistent compile cache: {h} warm (cache hit), "
                     f"{m} cold (full compile)")
    mem = agg.get("memory") or {}
    if mem:
        lines.append("memory watermarks (peak per phase):")
        for phase in sorted(mem, key=mem.get, reverse=True):
            lines.append(f"  {phase}: {_fmt_bytes(mem[phase])}")
        snap = agg.get("memory_snapshot") or {}
        if snap.get("source"):
            lines.append(f"  source: {snap['source']} "
                         f"({snap.get('device_kind', 'unknown')})")
    transfers = agg.get("transfers") or {}
    if transfers:
        tot = sum(transfers.values())
        parts = ", ".join(f"{d}={n}" for d, n in sorted(transfers.items()))
        lines.append(f"transfer audit: {tot} implicit transfers ({parts})")
    return "\n".join(lines)


# ------------------------------------------------------------------- gate

# metric direction semantics: a regression is a drop for higher-better
# metrics and a rise for lower-better ones.  Metrics not listed are
# informational and never gate.
GATE_HIGHER_BETTER = (
    "value", "vs_baseline", "vs_reference_cpu",
    "analytic_tflops_per_sec", "analytic_hbm_gb_per_sec",
    "mfu_vs_device_peak", "bw_util_vs_device_peak",
    "warm_start_speedup", "coh_bf16_iters_per_sec",
    "solves_per_sec_per_chip", "serve_batch_speedup",
    "admm_collective_bytes_reduction", "refine_outer_iters_per_sec",
    "stream_warm_speedup", "fleet_solves_per_sec_2workers",
    "hier_predict_speedup", "saturation_throughput_solves_per_sec",
    "goodput_fraction_at_saturation",
)
GATE_LOWER_BETTER = (
    "xla_cost_analysis_bytes_accessed", "peak_device_memory_bytes",
    "compile_seconds_total", "coh_bf16_xla_cost_analysis_bytes_accessed",
    "serve_p50_latency_s", "admm_collective_bytes_per_round",
    "admm_straggler_ratio", "refine_flux_err",
    "latency_to_first_solution_s", "hier_predict_max_rel_err",
    # opt-in gate (--metric shed_rate_under_overload=tol): the shed
    # rate is admission-POLICY-shaped, not pure capacity, so it is
    # direction-tagged here but left out of GATE_DEFAULT_METRICS
    "shed_rate_under_overload",
    # numerical-truth rows (bench.run_shadow_drift_bench): p99 upper
    # bounds of live cross-path gain drift — a RISE means a kernel
    # path's numerics moved away from the xla/f32 reference
    "shadow_drift_batched_vs_xla_p99",
    "shadow_drift_bf16_vs_f32_p99",
)
# the metrics gated when present in BOTH records (others opt in via
# --metric name=tol)
GATE_DEFAULT_METRICS = (
    "value", "xla_cost_analysis_bytes_accessed", "peak_device_memory_bytes",
    "warm_start_speedup", "coh_bf16_iters_per_sec",
    "coh_bf16_xla_cost_analysis_bytes_accessed",
    "solves_per_sec_per_chip", "serve_batch_speedup", "serve_p50_latency_s",
    "admm_collective_bytes_per_round", "admm_collective_bytes_reduction",
    "refine_flux_err", "refine_outer_iters_per_sec",
    "latency_to_first_solution_s", "fleet_solves_per_sec_2workers",
    "hier_predict_speedup", "hier_predict_max_rel_err",
    "saturation_throughput_solves_per_sec",
    "goodput_fraction_at_saturation",
    "shadow_drift_batched_vs_xla_p99", "shadow_drift_bf16_vs_f32_p99",
)
GATE_DEFAULT_TOLERANCE = 0.10


def gate_compare(new: dict, baseline: dict,
                 tolerances: Optional[Dict[str, float]] = None,
                 default_tol: float = GATE_DEFAULT_TOLERANCE,
                 metrics: Optional[Tuple[str, ...]] = None):
    """Compare a fresh bench record against the pinned baseline.

    Returns ``(failures, rows)``: ``failures`` is the list of
    human-readable regression strings (empty = gate passes); ``rows``
    is one ``(metric, base, new, ratio, tol, status)`` tuple per
    compared metric for the report table.  A metric is compared when
    it is numeric and non-zero in the baseline and present in the new
    record; per-metric tolerances override ``default_tol``."""
    tolerances = tolerances or {}
    names = list(metrics if metrics is not None else GATE_DEFAULT_METRICS)
    for extra in tolerances:
        if extra not in names:
            names.append(extra)
    failures, rows = [], []
    for m in names:
        b, n = baseline.get(m), new.get(m)
        if not isinstance(b, (int, float)) or not isinstance(n, (int, float)):
            continue
        if b == 0:
            continue
        tol = float(tolerances.get(m, default_tol))
        ratio = float(n) / float(b)
        if m in GATE_LOWER_BETTER:
            bad = ratio > 1.0 + tol
            direction = "rose"
        else:
            bad = ratio < 1.0 - tol
            direction = "dropped"
        status = "FAIL" if bad else "ok"
        rows.append((m, float(b), float(n), ratio, tol, status))
        if bad:
            failures.append(
                f"{m} {direction} beyond tolerance: baseline {b:g} -> "
                f"{n:g} (ratio {ratio:.3f}, tol {tol:.0%})"
            )
    return failures, rows


def format_gate_report(rows, failures) -> str:
    lines = []
    if rows:
        w = max(len(r[0]) for r in rows) + 2
        lines.append(f"{'metric':<{w}}{'baseline':>14}{'new':>14}"
                     f"{'ratio':>8}{'tol':>7}  status")
        for m, b, n, ratio, tol, status in rows:
            lines.append(f"{m:<{w}}{b:>14.6g}{n:>14.6g}{ratio:>8.3f}"
                         f"{tol:>6.0%}  {status}")
    else:
        lines.append("no comparable metrics between the two records")
        lines.append("GATE: FAIL (nothing comparable)")
        return "\n".join(lines)
    lines.append("GATE: " + ("FAIL" if failures else "PASS"))
    return "\n".join(lines)


# --------------------------------------------------------- bench history

# one line per bench run, forever: the perf trajectory the single-slot
# BENCH_BASELINE.json diff cannot hold.  Schema-versioned JSONL next to
# the repo root (or SAGECAL_BENCH_HISTORY); `diag serve` renders trend
# deltas over the last K rows against the gate direction tables above.
# v2 (PR 16): rows additionally stamp `evidence` (evidence class of the
# record, see obs/evidence.py) and carry `device_kind`; v1 rows are
# upgraded in place by tools/backfill_bench_history.py and both schemas
# stay readable forever.
BENCH_HISTORY_SCHEMA_VERSION = 2
DEFAULT_BENCH_HISTORY = "BENCH_HISTORY.jsonl"


def bench_history_path(path: Optional[str] = None) -> str:
    return path or os.environ.get("SAGECAL_BENCH_HISTORY") \
        or DEFAULT_BENCH_HISTORY


def _git_rev() -> str:
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:
        return "unknown"


def append_bench_history(rec: dict, path: Optional[str] = None) -> str:
    """Append one bench record to the history JSONL (single O_APPEND
    write — concurrent bench runs never tear lines).  Stamps schema
    version, wall-clock, git revision and a fingerprint of the bench
    config so trend rows are only compared like-for-like.  Returns the
    path written."""
    from sagecal_tpu.elastic.checkpoint import config_fingerprint

    path = bench_history_path(path)
    cfg_keys = ("mode", "shape", "iters", "batch", "dtype", "backend",
                "kernel", "device_kind", "platform")
    from sagecal_tpu.obs.evidence import record_evidence

    row = {
        "history_schema_version": BENCH_HISTORY_SCHEMA_VERSION,
        "ts": time.time(),
        "git_rev": _git_rev(),
        "config_fingerprint": config_fingerprint(
            **{k: rec.get(k) for k in cfg_keys if k in rec})[:16],
    }
    # schema v2: stamp the evidence class at measurement time (explicit
    # field wins, else derived from platform); rows where neither
    # resolves stay unstamped rather than guessed
    ev = record_evidence(rec)
    if ev is not None:
        row["evidence"] = ev
    for k, v in rec.items():
        row.setdefault(k, v)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd = os.open(path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
    try:
        os.write(fd, (json.dumps(row, default=str) + "\n").encode("utf-8"))
    finally:
        os.close(fd)
    return path


def read_bench_history(path: Optional[str] = None) -> List[dict]:
    """Every parseable row of the bench history, in file order (skips
    corrupt lines like every other JSONL reader here)."""
    path = bench_history_path(path)
    if not os.path.exists(path):
        return []
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict):
                out.append(row)
    return out


def bench_trend(history: List[dict], last_k: int = 5,
                metrics: Optional[Tuple[str, ...]] = None) -> List[dict]:
    """Trend deltas over the last K same-fingerprint runs: for each
    metric present in the newest row, the oldest-in-window -> newest
    ratio plus a direction verdict from the gate tables (``better`` /
    ``worse`` / ``flat`` / ``info``)."""
    from sagecal_tpu.obs.evidence import comparable, record_evidence

    if not history:
        return []
    newest = history[-1]
    fp = newest.get("config_fingerprint")
    # evidence refusal (PR 16): rows whose evidence class RESOLVES and
    # mismatches the newest row's are not trend-comparable (a CPU
    # fallback run must never trend against TPU rows); rows where
    # neither `evidence` nor `platform` resolves (pre-v2 / synthetic)
    # stay comparable, so legacy history keeps working
    ev_new = record_evidence(newest)
    window = [r for r in history
              if r.get("config_fingerprint") == fp
              and comparable(record_evidence(r), ev_new)][-max(last_k, 2):]
    if len(window) < 2:
        return []
    oldest = window[0]
    names = metrics if metrics is not None else tuple(
        m for m in GATE_DEFAULT_METRICS if m in newest)
    out = []
    for m in names:
        a, b = oldest.get(m), newest.get(m)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)) \
                or isinstance(a, bool) or isinstance(b, bool) or a == 0:
            continue
        ratio = float(b) / float(a)
        if m in GATE_LOWER_BETTER:
            verdict = ("better" if ratio < 0.98
                       else "worse" if ratio > 1.02 else "flat")
        elif m in GATE_HIGHER_BETTER:
            verdict = ("better" if ratio > 1.02
                       else "worse" if ratio < 0.98 else "flat")
        else:
            verdict = "info"
        out.append({
            "metric": m, "first": float(a), "last": float(b),
            "ratio": ratio, "runs": len(window), "verdict": verdict,
            "first_rev": str(oldest.get("git_rev", "?")),
            "last_rev": str(newest.get("git_rev", "?")),
        })
    return out


def format_bench_trend(trend: List[dict]) -> str:
    """Trend table for ``diag serve``."""
    if not trend:
        return "(no bench history trend: fewer than 2 comparable runs)"
    w = max(len(t["metric"]) for t in trend) + 2
    lines = [f"{'metric':<{w}}{'first':>14}{'last':>14}{'ratio':>8}"
             f"{'runs':>6}  trend"]
    for t in trend:
        lines.append(
            f"{t['metric']:<{w}}{t['first']:>14.6g}{t['last']:>14.6g}"
            f"{t['ratio']:>8.3f}{t['runs']:>6}  {t['verdict']} "
            f"({t['first_rev']} -> {t['last_rev']})")
    return "\n".join(lines)
