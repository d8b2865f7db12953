"""First-class profiling: per-phase wall-clock, annotated for traces.

The reference's only instrumentation is one ``time(0)`` print per tile
(``/root/reference/src/MS/fullbatch_mode.cpp:276,309,634-635``); SURVEY
section 5 makes ``jax.profiler`` traces + per-phase timing a first-class
feature of the rebuild.  :class:`PhaseTimer` is cheap always-on
wall-clock accounting per named phase (load / coherencies / solve /
residual / write), printed as one summary line per tile and totals at
the end of a run.  Each phase, and each tile, is also a
``jax.profiler.TraceAnnotation``, so a profiler session of the same run
(``--device-profile``, ``obs/devprof.py``) names the device's work and
idle gaps by the phase around them.  The ``tile`` annotation carries the
tile's ``perf_counter_ns`` start as metadata (``perf_ns``): one anchor
that places every ``perf_counter`` (and ``time.monotonic``) stamp of
the program on the trace's timeline.

When telemetry is enabled (``SAGECAL_TELEMETRY=1``) every phase
duration is also observed into the ``phase_seconds`` histogram of the
process-wide :func:`sagecal_tpu.obs.registry.get_registry`, so
``sagecal-tpu diag prom`` exports the same numbers Prometheus-style;
:meth:`PhaseTimer.tile_timings` hands the per-tile window to the JSONL
event log.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterator

import jax

from sagecal_tpu.obs.registry import get_registry, telemetry_enabled
from sagecal_tpu.obs.trace import get_tracer


class PhaseTimer:
    """Accumulates wall-clock per named phase across tiles.  A phase may
    hold phases of its own (``load+coh`` holds ``load`` and ``coh``)."""

    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self._tile: Dict[str, float] = {}
        self._notes: Dict[str, float] = {}

    @staticmethod
    def tile(number: int) -> jax.profiler.TraceAnnotation:
        """The ``tile`` annotation of one tile, entered at its start:
        its metadata holds the tile number and the ``perf_counter_ns``
        at which it was made, the anchor between the program's clock
        and the profiler's."""
        return jax.profiler.TraceAnnotation(
            "tile", tile=int(number), perf_ns=time.perf_counter_ns())

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        self._tile.setdefault(name, 0.0)  # printed outer phase first
        # host-side tracer span (SAGECAL_TRACE=1): the NullTracer hands
        # back a shared no-op CM, so the disabled path stays allocation-
        # free; span exits also feed the flight recorder's stall clock
        with get_tracer().span(name, kind="phase"):
            with jax.profiler.TraceAnnotation(name):
                yield
        dt = time.perf_counter() - t0
        self.totals[name] += dt
        self.counts[name] += 1
        self._tile[name] = self._tile.get(name, 0.0) + dt
        # zero-cost-off: one flag check and we're done — no import, no
        # registry lookup, no label-key allocation on the hot path
        if telemetry_enabled():
            get_registry().observe(
                "phase_seconds", dt,
                help="wall-clock seconds per named pipeline phase",
                phase=name,
            )
            from sagecal_tpu.obs.perf import record_memory_watermark

            record_memory_watermark(name)

    def tile_timings(self) -> Dict[str, float]:
        """Snapshot of the current per-tile window (does not reset) —
        the per-tile payload for the JSONL event log."""
        return dict(self._tile)

    def note(self, name: str, value) -> None:
        """Add to a per-tile number printed after the phases: an int is
        a count (``name=7``), a float seconds (``name=0.123s``)."""
        self._notes[name] = self._notes.get(name, 0) + value

    def tile_summary(self) -> str:
        """One-line per-tile breakdown of the phases, then the notes;
        resets the per-tile window."""
        s = " ".join([f"{k}={v:.2f}s" for k, v in self._tile.items()]
                     + [f"{k}={v:.3f}s" if isinstance(v, float)
                        else f"{k}={v}" for k, v in self._notes.items()])
        self._tile, self._notes = {}, {}
        return s

    def run_summary(self) -> str:
        parts = [
            f"{k}: {self.totals[k]:.2f}s/{self.counts[k]}x"
            for k in sorted(self.totals, key=self.totals.get, reverse=True)
        ]
        return "phase totals: " + ", ".join(parts)
