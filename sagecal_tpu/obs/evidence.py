"""Evidence classes: what KIND of proof stands behind each metric.

ROADMAP:34-36 complains in prose that every device-side gain since
PR 10 is "AOT-proven, not wall-clock-proven"; nothing machine-readable
distinguished the two, so a CPU-fallback bench row could be
trend-compared against round-5 TPU rows and silently pass.  This
module turns the complaint into a checked invariant:

- every bench record / baseline metric / history row carries an
  ``evidence`` class from :data:`EVIDENCE_CLASSES`, stamped at
  measurement time;
- ``diag gate`` and ``bench_trend`` call :func:`comparable` and REFUSE
  cross-evidence comparisons with an explicit message;
- ``diag evidence`` renders which headline claims are wall-clock-proven
  vs AOT-proven (:func:`proof_kind`).

Classes
-------
``tpu-wallclock``
    measured wall-clock on real TPU hardware — the only class that
    proves a speed claim end-to-end.
``cpu-wallclock``
    measured wall-clock on the CPU fallback — proves correctness and
    relative behaviour of the machinery, not device speed.
``aot-bytes``
    derived from XLA ``cost_analysis`` bytes/flops of an AOT-compiled
    program — proves the compiler *scheduled* less traffic, not that
    the device ran faster.
``aot-hlo``
    derived from inspecting compiled HLO structure (e.g. counting
    collective bytes per ADMM round) — the weakest class: proves shape
    of the program only.

``gpu-wallclock`` is reserved for the multi-backend arc (ROADMAP
item 5) and accepted everywhere classes are validated.

Stdlib-only: imported by diag paths that must not touch jax.
"""

from __future__ import annotations

from typing import Dict, Optional

EVIDENCE_CLASSES = ("tpu-wallclock", "cpu-wallclock", "gpu-wallclock",
                    "aot-bytes", "aot-hlo")

#: classes that prove a wall-clock claim (vs AOT/static proof)
WALLCLOCK_CLASSES = ("tpu-wallclock", "cpu-wallclock", "gpu-wallclock")


def is_valid(cls: Optional[str]) -> bool:
    return cls in EVIDENCE_CLASSES


def proof_kind(cls: Optional[str]) -> str:
    """"wall-clock-proven" | "AOT-proven" | "unclassified" — the
    vocabulary of ROADMAP:34-36, for ``diag evidence``."""
    if cls in WALLCLOCK_CLASSES:
        return "wall-clock-proven"
    if cls in EVIDENCE_CLASSES:
        return "AOT-proven"
    return "unclassified"


def wallclock_evidence(platform: Optional[str]) -> Optional[str]:
    """The wall-clock evidence class a timing measured on ``platform``
    earns (``jax.default_backend()`` strings), None when unknown."""
    if not platform:
        return None
    p = str(platform).lower()
    if p in ("tpu", "cpu", "gpu", "cuda", "rocm"):
        if p in ("cuda", "rocm"):
            p = "gpu"
        return f"{p}-wallclock"
    return None


def record_evidence(rec: dict) -> Optional[str]:
    """Resolve the record-level evidence class of a bench record or
    history row: an explicit ``evidence`` field wins, else derive the
    wall-clock class from ``platform``.  None when unresolvable —
    callers must treat None as *compatible with anything* (pre-v2 rows
    and synthetic test rows carry neither field)."""
    ev = rec.get("evidence")
    if is_valid(ev):
        return ev
    return wallclock_evidence(rec.get("platform"))


def metric_evidence(rec: dict, metric: str) -> Optional[str]:
    """Evidence class of one metric in a record: the per-metric
    ``evidence_classes`` override map wins (satellite benches ride
    along a TPU headline but are AOT- or CPU-proven), else the
    record-level class."""
    overrides = rec.get("evidence_classes") or {}
    ev = overrides.get(metric)
    if is_valid(ev):
        return ev
    return record_evidence(rec)


def comparable(a: Optional[str], b: Optional[str]) -> bool:
    """Whether two evidence classes may be gate/trend-compared.  Only a
    RESOLVED mismatch refuses; an unresolvable side (None) compares —
    refusing legacy rows would brick every pre-v2 history file."""
    if a is None or b is None:
        return True
    return a == b


def classify_history_row(row: dict) -> Optional[str]:
    """Backfill classifier for schema-v1 history rows (no ``evidence``
    field): all v1 rows are bench timing rows, so the class is the
    wall-clock class of their ``platform``.  Rows predating the
    platform stamp fall back on ``mode``/``backend`` hints; None when
    nothing resolves (left unclassified rather than guessed)."""
    ev = record_evidence(row)
    if ev is not None:
        return ev
    for key in ("backend", "mode"):
        ev = wallclock_evidence(row.get(key))
        if ev is not None:
            return ev
    return None


def bench_evidence_classes(platform: Optional[str]) -> Dict[str, str]:
    """The per-metric override map for a full bench record: the
    headline timing metrics inherit the record-level (platform) class,
    while satellite metrics carry the class of how THEY were actually
    measured — AOT cost-analysis, HLO inspection, or the CPU/f64
    subprocess harnesses that run regardless of headline platform
    (provenance per the ``*_note`` fields of BENCH_BASELINE.json)."""
    wall = wallclock_evidence(platform) or "cpu-wallclock"
    out: Dict[str, str] = {
        # XLA cost-analysis derived (AOT-proven: bytes/flops SCHEDULED)
        "xla_cost_analysis_bytes_accessed": "aot-bytes",
        "coh_bf16_xla_cost_analysis_bytes_accessed": "aot-bytes",
        "hier_predict_speedup": "aot-bytes",
        # compiled-HLO structure inspection (ADMM collective traffic)
        "admm_collective_bytes_per_round": "aot-hlo",
        "admm_collective_bytes_reduction": "aot-hlo",
        # harnesses that run on f64/NumPy or subprocess CPU workers
        # regardless of the headline platform (per the *_note
        # provenance prose in BENCH_BASELINE.json)
        "refine_flux_err": "cpu-wallclock",
        "refine_outer_iters_per_sec": "cpu-wallclock",
        "latency_to_first_solution_s": "cpu-wallclock",
        "stream_warm_speedup": "cpu-wallclock",
        "fleet_solves_per_sec_2workers": "cpu-wallclock",
        "hier_predict_max_rel_err": "cpu-wallclock",
        "admm_straggler_ratio": "cpu-wallclock",
        # load/capacity rows: stepped-ramp load vs subprocess CPU
        # workers (bench.run_load_bench) — honest CPU wall-clock, never
        # a device-speed claim
        "saturation_throughput_solves_per_sec": "cpu-wallclock",
        "shed_rate_under_overload": "cpu-wallclock",
        "goodput_fraction_at_saturation": "cpu-wallclock",
        # numerical-truth rows (bench.run_shadow_drift_bench): the
        # drift ratio is dtype/kernel truth, but it is measured on the
        # CPU interpret-mode kernels — a TPU MXU pass may round
        # differently, so the class is honest cpu-wallclock, never a
        # device claim
        "shadow_drift_batched_vs_xla_p99": "cpu-wallclock",
        "shadow_drift_bf16_vs_f32_p99": "cpu-wallclock",
        # wall-clock headline + serve/coherency rows follow the run's
        # platform: bench measures them on the live device
        "value": wall,
        "vs_baseline": wall,
        "analytic_tflops_per_sec": wall,
        "analytic_hbm_gb_per_sec": wall,
        "mfu_vs_device_peak": wall,
        "bw_util_vs_device_peak": wall,
        "warm_start_speedup": wall,
        "coh_bf16_iters_per_sec": wall,
        "solves_per_sec_per_chip": wall,
        "serve_batch_speedup": wall,
        "serve_p50_latency_s": wall,
        "compile_seconds_total": wall,
        "peak_device_memory_bytes": wall,
    }
    return out
