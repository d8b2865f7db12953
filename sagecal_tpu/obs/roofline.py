"""Per-``device_kind`` roofline model: peaks, intensity, per-kernel MFU.

The observability stack's hardware-truth layer: per-device peaks in
place of a single hard-coded v5e constant, and attribution below the
whole-run boundary.  Here:

- :data:`PEAK_TABLE` — editable per-``device_kind`` peaks (FLOP/s per
  dtype + HBM GB/s).  v5e is the hardware-validated entry; the ``cpu``
  entry is NOMINAL (order-of-magnitude single-core figures) and exists
  so the whole roofline machinery runs in CI on the CPU fallback; GPU
  rows slot in alongside when ROADMAP item 5 lands a second backend.
- :func:`lookup_peaks` — resolve a live ``jax.devices()[0].device_kind``
  string against the table (case-insensitive, alias-aware).  Unknown
  kinds resolve to ``None`` — callers must report "unknown device kind,
  add a PEAK_TABLE entry" rather than a silently-wrong MFU.
- :func:`classify_intensity` — arithmetic intensity (FLOP/byte) vs the
  device ridge point: compute- vs memory-bound per kernel family.
- :func:`build_report` — join a devprof per-kernel-family attribution
  (measured device seconds) against the ``instrumented_jit``
  cost-analysis ledger (per-dispatch flops/bytes) into per-family
  MFU / HBM-BW-utilization rows, ranked by wasted device time, each
  naming the ROADMAP-item-1 lever it implicates.  ``diag roofline``
  renders it; :func:`set_kernel_gauges` exports the same numbers as
  per-family registry gauges (``kernel_mfu`` / ``kernel_bw_util``).

Import-light by design (stdlib only): usable before backend selection
and inside ``diag`` without touching jax.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from sagecal_tpu.obs.registry import get_registry

# ------------------------------------------------------------ peak table

#: Canonical device peaks.  ``peak_flops`` is per-chip FLOP/s by compute
#: dtype; ``hbm_gbps`` is the memory-system bandwidth the bandwidth
#: roofline divides by.  ``nominal: True`` marks entries that are
#: order-of-magnitude placeholders (the CPU CI entry), not datasheet
#: numbers — reports carry the flag so a CPU-fallback MFU is never
#: mistaken for a hardware claim.
PEAK_TABLE: Dict[str, dict] = {
    "tpu v5e": {
        "label": "TPU v5e",
        # 197 TFLOP/s bf16 per chip (the round-5 headline denominator);
        # f32 matmuls on the v5e MXU run via multi-pass bf16 at ~half
        # the bf16 rate — the f32 row keeps same-device comparisons
        # honest, not a datasheet quote.
        "peak_flops": {"bf16": 197e12, "f32": 98.5e12},
        "hbm_gbps": 819.0,
    },
    "cpu": {
        "label": "host CPU (nominal single-core)",
        # NOMINAL figures for the single-core CI host: ~10 GFLOP/s
        # sustained scalar-ish f32 and ~10 GB/s main-memory stream.
        # They exist so the roofline machinery (lookup, intensity,
        # MFU, report, gate plumbing) is exercised end-to-end in CI;
        # never quote them as hardware truth.
        "peak_flops": {"f32": 1e10, "bf16": 1e10, "f64": 5e9},
        "hbm_gbps": 10.0,
        "nominal": True,
    },
    # ROADMAP item 5 (multi-backend): add GPU rows here, e.g.
    # "nvidia h100 80gb hbm3": {"label": "H100 SXM", "peak_flops":
    #     {"bf16": 989e12, "f32": 67e12}, "hbm_gbps": 3350.0},
}

#: device_kind strings observed in the wild -> canonical table key.
KIND_ALIASES: Dict[str, str] = {
    "tpu v5 lite": "tpu v5e",
    "tpu v5litepod": "tpu v5e",
    "cpu (unknown)": "cpu",
    "unknown": "cpu",  # CPU backend device_kind on some jaxlibs
}


def normalize_kind(device_kind: Optional[str]) -> str:
    return (device_kind or "").strip().lower()


def lookup_peaks(device_kind: Optional[str]) -> Optional[dict]:
    """The PEAK_TABLE entry for a live ``device_kind`` string, or None
    when the hardware is unknown (callers must surface that, never
    substitute a wrong peak)."""
    k = normalize_kind(device_kind)
    if not k:
        return None
    k = KIND_ALIASES.get(k, k)
    if k in PEAK_TABLE:
        return PEAK_TABLE[k]
    # tolerate vendor decorations ("TPU v5e (chips=1)")
    for key in PEAK_TABLE:
        if key in k:
            return PEAK_TABLE[key]
    return None


def peak_flops(device_kind: Optional[str],
               dtype: str = "bf16") -> Optional[float]:
    peaks = lookup_peaks(device_kind)
    if peaks is None:
        return None
    fl = peaks["peak_flops"]
    return float(fl.get(dtype) or fl.get("f32") or 0.0) or None


def peak_hbm_gbps(device_kind: Optional[str]) -> Optional[float]:
    peaks = lookup_peaks(device_kind)
    return float(peaks["hbm_gbps"]) if peaks else None


# --------------------------------------------------------- roofline math


def ridge_intensity(peaks: dict, dtype: str = "bf16") -> float:
    """FLOP/byte at the roofline ridge: above it a kernel is compute-
    bound on this device, below it memory-bound."""
    fl = peaks["peak_flops"]
    f = float(fl.get(dtype) or fl.get("f32") or 0.0)
    bw = float(peaks["hbm_gbps"]) * 1e9
    return f / bw if bw else 0.0


def classify_intensity(flops: Optional[float], bytes_accessed: Optional[float],
                       peaks: Optional[dict],
                       dtype: str = "bf16") -> dict:
    """Arithmetic intensity + compute/memory-bound verdict for one
    kernel family.  Unknown inputs degrade to ``bound: "unknown"``."""
    out = {"intensity": None, "ridge": None, "bound": "unknown"}
    if not flops or not bytes_accessed:
        return out
    out["intensity"] = float(flops) / float(bytes_accessed)
    if peaks is None:
        return out
    ridge = ridge_intensity(peaks, dtype)
    out["ridge"] = ridge
    out["bound"] = "compute-bound" if out["intensity"] >= ridge \
        else "memory-bound"
    return out


def mfu(flops_per_sec: Optional[float], device_kind: Optional[str],
        dtype: str = "bf16") -> Optional[float]:
    """Measured-vs-peak model-FLOP utilization, None when either side
    is unknown."""
    pk = peak_flops(device_kind, dtype)
    if not flops_per_sec or not pk:
        return None
    return float(flops_per_sec) / pk


def bw_util(bytes_per_sec: Optional[float],
            device_kind: Optional[str]) -> Optional[float]:
    bw = peak_hbm_gbps(device_kind)
    if not bytes_per_sec or not bw:
        return None
    return float(bytes_per_sec) / (bw * 1e9)


# ---------------------------------------------------- per-family report

#: Which ROADMAP-item-1 lever each kernel family implicates when it
#: tops the wasted-device-time ranking ("the MFU war": DMA overlap of
#: the 726 MB coherency stack, the ~65 ms dispatch floor, the 16 MB
#: VMEM ceiling forcing cluster splits).
FAMILY_LEVERS: Dict[str, str] = {
    "fused_grid": "VMEM-ceiling cluster splitting (bigger fused tiles "
                  "per grid step) + bf16 coherency stream",
    "batched_grid": "lane-major batch widening: amortize grid overhead "
                    "across serve lanes before touching the kernel",
    "xla_predict": "move predict into the fused grid (XLA predict "
                   "re-streams the 726 MB coherency stack from HBM)",
    "lbfgs_vector": "whole-solve jit: vector work is dispatch-dominated, "
                    "fuse more iterations per device program",
    "dma_infeed": "DMA/compute overlap: double-buffer the coherency "
                  "stack transfer behind the previous tile's solve",
    "other": "attribute first: grow the family classifier until this "
             "bucket is <5% of device time",
    "host_gaps": "~65 ms dispatch floor: fewer, larger device programs "
                 "(whole-solve jit amortization)",
}


def build_report(attribution: dict, ledger: Optional[Dict[str, dict]],
                 device_kind: Optional[str],
                 dtype: str = "bf16") -> dict:
    """Join a devprof attribution (measured per-family device time +
    per-module execution counts) with the cost-analysis ledger
    (per-dispatch flops/bytes per instrumented fn) into roofline rows.

    Returns ``{"device_kind", "peaks", "rows", "total_device_us",
    "attributed_us", "coverage", "dispatch"}`` where each row carries
    family, device time, share, flops/bytes (when the ledger resolves
    them), intensity/bound, MFU, BW-util and the implicated lever,
    ranked by device time (the wasted-time ordering: at 0.14% MFU
    every second of device time is ~99.9% waste, so time IS waste)."""
    from sagecal_tpu.obs.devprof import classify_kernel

    peaks = lookup_peaks(device_kind)
    fams = attribution.get("families", {})
    modules = attribution.get("modules", {})
    total_us = float(attribution.get("total_device_us", 0.0))

    # fold ledger per-dispatch flops/bytes into per-family totals using
    # the SAME classifier the trace events went through, scaled by the
    # module execution counts observed in this trace window
    fam_flops: Dict[str, float] = {}
    fam_bytes: Dict[str, float] = {}
    if ledger:
        for mod, info in modules.items():
            st = ledger.get(mod)
            if st is None:
                continue
            fam = info.get("family") or classify_kernel(mod, "")
            n = max(int(info.get("n_exec", 1)), 1)
            fl = float(st.get("flops") or 0.0)
            by = float(st.get("bytes_accessed") or 0.0)
            if fl:
                fam_flops[fam] = fam_flops.get(fam, 0.0) + fl * n
            if by:
                fam_bytes[fam] = fam_bytes.get(fam, 0.0) + by * n

    rows: List[dict] = []
    attributed_us = 0.0
    for fam, f in fams.items():
        t_us = float(f.get("time_us", 0.0))
        attributed_us += t_us
        t_s = t_us / 1e6
        fl, by = fam_flops.get(fam), fam_bytes.get(fam)
        fps = (fl / t_s) if (fl and t_s > 0) else None
        bps = (by / t_s) if (by and t_s > 0) else None
        cls = classify_intensity(fl, by, peaks, dtype)
        rows.append({
            "family": fam,
            "device_us": round(t_us, 1),
            "share": round(t_us / total_us, 4) if total_us else None,
            "events": int(f.get("events", 0)),
            "flops": fl,
            "bytes": by,
            "intensity": cls["intensity"],
            "bound": cls["bound"],
            "mfu": (fps / peaks["peak_flops"].get(dtype,
                    peaks["peak_flops"].get("f32", 0.0))
                    if (fps and peaks and peaks["peak_flops"].get(
                        dtype, peaks["peak_flops"].get("f32"))) else None),
            "bw_util": (bps / (peaks["hbm_gbps"] * 1e9)
                        if (bps and peaks) else None),
            "lever": FAMILY_LEVERS.get(fam, FAMILY_LEVERS["other"]),
            "top_ops": f.get("top_ops", [])[:3],
        })
    rows.sort(key=lambda r: -r["device_us"])

    dispatch = attribution.get("dispatch") or {}
    if dispatch.get("gap_total_us"):
        rows.append({
            "family": "host_gaps",
            "device_us": round(float(dispatch["gap_total_us"]), 1),
            "share": None,  # gaps are BETWEEN device windows, not in them
            "events": int(dispatch.get("n_gaps", 0)),
            "flops": None, "bytes": None, "intensity": None,
            "bound": "idle", "mfu": None, "bw_util": None,
            "lever": FAMILY_LEVERS["host_gaps"],
            "top_ops": [],
        })

    return {
        "device_kind": device_kind,
        "peaks": peaks,
        "dtype": dtype,
        "rows": rows,
        "total_device_us": total_us,
        "attributed_us": attributed_us,
        "coverage": (attributed_us / total_us) if total_us else 0.0,
        "dispatch": dispatch,
    }


def set_kernel_gauges(report: dict) -> None:
    """Export per-kernel-family MFU / BW-util / device-seconds gauges —
    the per-kernel replacement for the retired run-level v5e gauges."""
    reg = get_registry()
    for r in report.get("rows", []):
        fam = r["family"]
        reg.gauge_set("kernel_device_seconds", r["device_us"] / 1e6,
                      help="measured device seconds per kernel family "
                           "(device-profile attribution)", family=fam)
        if r.get("mfu") is not None:
            reg.gauge_set("kernel_mfu", float(r["mfu"]),
                          help="measured-vs-peak model-FLOP utilization "
                               "per kernel family (PEAK_TABLE peaks)",
                          family=fam)
        if r.get("bw_util") is not None:
            reg.gauge_set("kernel_bw_util", float(r["bw_util"]),
                          help="measured-vs-peak HBM bandwidth "
                               "utilization per kernel family",
                          family=fam)


def _fmt(v, pct=False, si=False) -> str:
    if v is None:
        return "-"
    if pct:
        return f"{v * 100:.2f}%"
    if si:
        for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
            if abs(v) >= div:
                return f"{v / div:.2f}{unit}"
        return f"{v:.0f}"
    return f"{v:.3g}"


def format_report(report: dict) -> str:
    """Human rendering for ``diag roofline``."""
    kind = report.get("device_kind") or "?"
    peaks = report.get("peaks")
    lines: List[str] = []
    if peaks is None:
        lines.append(
            f"roofline: UNKNOWN device kind {kind!r} — no PEAK_TABLE "
            f"entry, MFU/BW-util omitted (add one in obs/roofline.py "
            f"rather than trusting a wrong peak)")
    else:
        fl = peaks["peak_flops"]
        dtype = report.get("dtype", "bf16")
        pk = fl.get(dtype, fl.get("f32"))
        tag = " [NOMINAL CI entry, not hardware truth]" \
            if peaks.get("nominal") else ""
        lines.append(
            f"roofline: {peaks['label']} ({kind}) — peak "
            f"{_fmt(pk, si=True)}FLOP/s {dtype}, "
            f"{peaks['hbm_gbps']:.0f} GB/s HBM, ridge "
            f"{ridge_intensity(peaks, dtype):.1f} FLOP/byte{tag}")
    tot = report.get("total_device_us", 0.0)
    cov = report.get("coverage", 0.0)
    lines.append(f"device time: {tot / 1e3:.3f} ms across "
                 f"{len(report.get('rows', []))} families, "
                 f"{cov * 100:.1f}% attributed")
    hdr = (f"{'family':<14}{'device ms':>11}{'share':>8}{'flops':>9}"
           f"{'bytes':>9}{'int.':>7}{'bound':>15}{'MFU':>8}"
           f"{'BW-util':>9}  lever")
    lines.append(hdr)
    for r in report.get("rows", []):
        lines.append(
            f"{r['family']:<14}{r['device_us'] / 1e3:>11.3f}"
            f"{_fmt(r['share'], pct=True):>8}"
            f"{_fmt(r['flops'], si=True):>9}"
            f"{_fmt(r['bytes'], si=True):>9}"
            f"{_fmt(r['intensity']):>7}"
            f"{r['bound']:>15}"
            f"{_fmt(r['mfu'], pct=True):>8}"
            f"{_fmt(r['bw_util'], pct=True):>9}  {r['lever']}")
    d = report.get("dispatch") or {}
    if d:
        lines.append(
            f"dispatch gaps: {d.get('n_gaps', 0)} gaps "
            f"{d.get('gap_total_us', 0.0) / 1e3:.1f} ms total "
            f"(mean {d.get('gap_mean_us', 0.0) / 1e3:.1f} ms, "
            f"max {d.get('gap_max_us', 0.0) / 1e3:.1f} ms) across "
            f"{d.get('n_windows', 0)} device windows; "
            f"busy fraction {d.get('amortization', 0.0) * 100:.1f}% — "
            f"the whole-solve-jit amortization of the dispatch floor")
    return "\n".join(lines)
