"""Bucketed executable cache for the serve path.

One entry per ``(BucketSpec, solver fingerprint)``: the callable that
runs the vmapped batched solve
(:func:`sagecal_tpu.solvers.batched.sagefit_packed_batch`).  Reusing
the SAME entry for every same-bucket batch is what makes the second
submission of an already-bucketed shape compile nothing — the
executable lives on the entry, and its ``perf_stats()`` record proves
it (``compiles == 1`` across N batches).

Two tiers:

1. **in-process** (always on) — a dict of named ``instrumented_jit``
   wrappers (or loaded AOT executables); the second batch of a bucket
   in THIS process is a hit.
2. **cross-worker AOT artifact store** (opt-in, ``store=``) — the
   serve/aot_store.py layer: on an in-process miss the cache first
   tries to LOAD a serialized executable some other worker already
   compiled (zero compiles, reported as a cache hit so the request
   lifecycle records ``cache_hit`` rather than ``compile``); on a
   store miss it AOT-compiles explicitly (``jit().lower().compile()``,
   attributed through :func:`~sagecal_tpu.obs.perf.note_compile` under
   the same ``serve_batch[...]`` name) and SAVES the artifact so the
   next worker joining the fleet compiles nothing.

Hit/miss counters live in two places on purpose:

- plain ints on the cache object (``hits``/``misses``/``stats()``) so
  tests and the bench can assert reuse with telemetry off;
- registry counters ``serve_executable_cache_{hits,misses}_total``
  (labelled by bucket) plus the store-tier
  ``serve_executable_cache_{aot_hits,aot_misses,aot_errors,aot_saves,
  compiles}_total`` so ``diag prom`` exports them in production and the
  fleet tests pin "worker B compiled nothing" from a metrics snapshot.

Without a store this module behaves exactly as before (the legacy
cross-process layer is the persistent XLA compilation cache,
obs/perf.py ``enable_persistent_compilation_cache``): a restarted server misses
here on first touch of each bucket but deserializes yesterday's HLO
instead of recompiling from scratch.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Tuple

from sagecal_tpu.serve.bucket import BucketSpec


class _AOTExecutable:
    """A compiled (or store-loaded) executable wrapped to look like the
    instrumented-jit entry: callable with the full
    ``sagefit_packed_batch`` signature, carrying the ``serve_batch[...]``
    ``name`` the lifecycle tracer uses for compile-time attribution.

    If a loaded executable refuses a call (device/sharding drift
    between the saving and loading worker), the wrapper permanently
    falls back to a fresh instrumented jit — slower (one compile) but
    never wrong."""

    def __init__(self, compiled, name: str, batched_fused: bool = False):
        self._compiled = compiled
        self.name = name
        self.batched_fused = batched_fused
        self._fallback: Optional[Callable] = None

    def __call__(self, *args):
        if self._fallback is not None:
            return self._fallback(*args)
        try:
            return self._compiled(*args)
        except Exception:
            from sagecal_tpu.obs.perf import instrumented_jit

            self._fallback = instrumented_jit(
                _solve_fn(self.batched_fused), name=self.name,
                donate_argnames=("p0",))
            return self._fallback(*args)


def _solve_fn(batched_fused: bool) -> Callable:
    """The batched-solve entry with the kernel path BAKED IN: the
    ``batched_fused`` flag is compile-time static (it selects between
    the batched fused Pallas grid and the vmapped paths), so each cache
    entry closes over its routing decision instead of threading a
    static argument through jit/AOT signatures."""
    import functools

    from sagecal_tpu.solvers.batched import sagefit_packed_batch

    if not batched_fused:
        return sagefit_packed_batch
    return functools.partial(sagefit_packed_batch, batched_fused=True)


class ExecutableCache:
    """Maps ``(bucket, fingerprint)`` -> the batched-solve callable,
    building (and counting) on miss; with an
    :class:`~sagecal_tpu.serve.aot_store.AOTArtifactStore` attached,
    misses consult the cross-worker artifact tier before compiling."""

    def __init__(self, store=None):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[BucketSpec, str], Callable] = {}
        self.store = store
        self.hits = 0
        self.misses = 0

    def get(self, bucket: BucketSpec, fingerprint: str) -> Callable:
        """The executable wrapper for this bucket+numerics, creating it
        on first touch.  The returned callable has the
        ``sagefit_packed_batch`` signature and donates ``p0``."""
        return self.get_with_status(bucket, fingerprint)[0]

    def get_with_status(self, bucket: BucketSpec, fingerprint: str,
                        example_args: Optional[tuple] = None,
                        batched_fused: bool = False,
                        ) -> Tuple[Callable, bool]:
        """Like :meth:`get` but also reports whether the lookup avoided
        a compile (``(fn, True)``) or must compile (``(fn, False)``) —
        the serve lifecycle tracer names its span ``cache_hit`` vs
        ``compile`` off this bit.  A store LOAD reports True: the
        request never waits on a compiler.  ``example_args`` (the
        actual batch arguments) enables the store tier — without them
        the cache can only hand back a lazy jit wrapper.
        ``batched_fused`` selects the kernel path baked into a NEW
        entry (:func:`_solve_fn`); it must be deterministic per
        (bucket, fingerprint) — :func:`sagecal_tpu.solvers.batched.
        choose_batched_path` is, because every input to its decision is
        part of the bucket or the fingerprint."""
        key = (bucket, fingerprint)
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self.hits += 1
                self._count("hits", bucket)
                return fn, True
            self.misses += 1
            self._count("misses", bucket)
            if self.store is not None and example_args is not None:
                fn, hit = self._from_store(bucket, fingerprint,
                                           example_args, batched_fused)
            else:
                fn, hit = self._lazy_jit(bucket, fingerprint,
                                         batched_fused), False
            self._entries[key] = fn
            return fn, hit

    # -- build paths ---------------------------------------------------

    @staticmethod
    def entry_name(bucket: BucketSpec, fingerprint: str) -> str:
        # named per bucket so `diag perf` attributes compile time to
        # the shape class that paid it
        return f"serve_batch[{bucket.short()}#{fingerprint[:8]}]"

    def _lazy_jit(self, bucket: BucketSpec, fingerprint: str,
                  batched_fused: bool = False) -> Callable:
        from sagecal_tpu.obs.perf import instrumented_jit

        return instrumented_jit(
            _solve_fn(batched_fused),
            name=self.entry_name(bucket, fingerprint),
            donate_argnames=("p0",),
        )

    def _from_store(self, bucket: BucketSpec, fingerprint: str,
                    example_args: tuple, batched_fused: bool = False
                    ) -> Tuple[Callable, bool]:
        """Store tier: load (zero compiles) or compile-and-save."""
        import jax

        from sagecal_tpu.obs.perf import note_compile

        batch_w = int(example_args[6].shape[0])  # p0 leading axis
        name = self.entry_name(bucket, fingerprint)
        loaded = self.store.load(bucket, fingerprint, batch_w)
        if loaded is not None:
            return _AOTExecutable(loaded, name, batched_fused), True
        jitted = jax.jit(_solve_fn(batched_fused),
                         donate_argnames=("p0",))
        t0 = time.perf_counter()
        lowered = jitted.lower(*example_args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        flops = by = None
        try:
            from sagecal_tpu.obs.perf import _cost_analysis

            flops, by = _cost_analysis(compiled)
        except Exception:
            pass
        note_compile(name, t1 - t0, t2 - t1, flops, by, aot=True)
        self._count("compiles", bucket)
        self.store.save(bucket, fingerprint, batch_w, compiled)
        return _AOTExecutable(compiled, name, batched_fused), False

    def _count(self, kind: str, bucket: BucketSpec) -> None:
        try:
            from sagecal_tpu.obs.registry import get_registry

            get_registry().counter_inc(
                f"serve_executable_cache_{kind}_total",
                help="serve bucketed-executable cache lookups "
                     f"({kind})", bucket=bucket.short())
        except Exception:
            pass

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries)}
