"""Device-profiler ingestion: capture, parse, per-kernel attribution.

Everything below the dispatch boundary was invisible to the obs stack:
``obs/perf.py`` reports whole-program ``cost_analysis`` flops/bytes,
so nobody could say which PERF.md lever (DMA overlap, the ~65 ms
dispatch floor, VMEM-ceiling splits) dominates the lost 99.86% of the
0.14%-MFU headline.  This module is the hardware-truth half of PR 16:

- **Capture** — :func:`start_device_profile` / :func:`stop_device_profile`
  / :func:`device_profile` wrap ``jax.profiler.start_trace`` with an
  idempotent owner (``SAGECAL_DEVICE_PROFILE=dir`` or the apps'
  ``--device-profile`` flag): the program's one capture path, read by
  TensorBoard and by our own parser.  ``stop`` locates the newest
  emitted ``*.trace.json(.gz)`` and remembers it for flight dumps.
- **Fleet arming** — a coordinator drops an atomic JSON flag file in
  the fleet's shared out_dir (:func:`arm_fleet_profile`); the targeted
  worker's loop polls :func:`check_fleet_arm` and profiles exactly one
  claimed cycle, then renames the flag to ``.done`` with the trace
  path (:func:`complete_fleet_arm`) — one worker of a live fleet gets
  profiled without restarting anything.
- **Parse** — :func:`read_trace_events` is a zero-dependency reader for
  the Chrome-trace JSON jax emits (gzipped on real runs, plain JSON
  accepted for fixtures).  Device op events are the ``X`` events
  carrying ``args.hlo_op`` (CPU thunk runtime) or sitting on ``XLA
  Ops`` threads (TPU); ``args.hlo_module`` is ``jit_<fn>``, which is
  exactly the ``instrumented_jit`` ledger name — the join key.
- **Attribute** — :func:`attribute_trace` buckets device time into the
  kernel families of ROADMAP item 1 (fused grid, batched grid, XLA
  predict, LBFGS vector work, DMA/infeed, other), computes total
  device time as the union of per-track busy intervals, counts
  per-module executions *within the trace window* (min single-op-name
  count — ops outside any loop emit exactly once per dispatch, while
  loop-body ops emit once per iteration), and measures dispatch
  gaps between device busy windows: the per-dispatch floor and how
  far whole-solve jits amortize it.

Import-light: ``jax`` is imported inside the capture functions only,
so ``diag roofline`` can parse traces on a box with no accelerator.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

_DEVPROF_ENV = "SAGECAL_DEVICE_PROFILE"

_active_dir: Optional[str] = None
_last_trace: Optional[str] = None


# ------------------------------------------------------------- capture


def start_device_profile(log_dir: Optional[str] = None) -> Optional[str]:
    """Begin a device-profile capture (idempotent).  Returns the capture
    directory, or None when not requested."""
    global _active_dir
    if _active_dir is not None:
        return _active_dir
    log_dir = log_dir or os.environ.get(_DEVPROF_ENV)
    if not log_dir:
        return None
    os.makedirs(log_dir, exist_ok=True)
    import jax

    jax.profiler.start_trace(log_dir)
    _active_dir = log_dir
    try:
        from sagecal_tpu.obs.flight import note_activity

        note_activity(f"device_profile started: {log_dir}")
    except Exception:
        pass
    return log_dir


def stop_device_profile() -> Optional[str]:
    """Stop the capture this module started and return the path of the
    newest emitted trace file (also retained for flight dumps)."""
    global _active_dir, _last_trace
    if _active_dir is None:
        return None
    import jax

    d, _active_dir = _active_dir, None
    try:
        jax.profiler.stop_trace()
    except Exception:
        pass
    path = newest_trace_path(d)
    if path:
        _last_trace = path
        try:
            from sagecal_tpu.obs.flight import note_activity

            note_activity(f"device_profile trace: {path}")
        except Exception:
            pass
    return path


@contextlib.contextmanager
def device_profile(log_dir: Optional[str] = None) -> Iterator[Optional[str]]:
    """Exception-safe capture scope: profiles the body when requested
    (argument or ``SAGECAL_DEVICE_PROFILE``), no-op otherwise; always
    stops a capture it started, so a crash still flushes a parseable
    trace."""
    d = start_device_profile(log_dir)
    try:
        yield d
    finally:
        if d is not None:
            stop_device_profile()


def last_trace_path() -> Optional[str]:
    """Path of the newest trace captured by this process, or None —
    what flight dumps attach."""
    return _last_trace


def newest_trace_path(root: str) -> Optional[str]:
    """Newest ``*.trace.json[.gz]`` under ``root`` (jax writes
    ``<root>/plugins/profile/<timestamp>/<host>.trace.json.gz``)."""
    hits: List[str] = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        hits.extend(glob.glob(os.path.join(root, "**", pat),
                              recursive=True))
    if not hits:
        return None
    return max(hits, key=lambda p: (os.path.getmtime(p), p))


# -------------------------------------------------------- fleet arming


def _arm_path(out_dir: str, worker_id: str) -> str:
    return os.path.join(out_dir, f"device_profile_arm.{worker_id}.json")


def arm_fleet_profile(out_dir: str, worker_id: str,
                      profile_dir: Optional[str] = None) -> str:
    """Coordinator side: atomically drop the flag file that arms one
    worker of a live fleet for a single profiled cycle."""
    profile_dir = profile_dir or os.path.join(
        out_dir, f"devprof_{worker_id}")
    os.makedirs(out_dir, exist_ok=True)
    path = _arm_path(out_dir, worker_id)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"worker_id": worker_id, "profile_dir": profile_dir}, f)
    os.replace(tmp, path)
    return path


def check_fleet_arm(out_dir: str, worker_id: str) -> Optional[dict]:
    """Worker side: the arm request for this worker, or None.  A
    corrupt/partial flag reads as un-armed (the coordinator's write is
    atomic, but the shared dir may not be POSIX)."""
    path = _arm_path(out_dir, worker_id)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            req = json.load(f)
    except Exception:
        return None
    req.setdefault("profile_dir",
                   os.path.join(out_dir, f"devprof_{worker_id}"))
    req["_path"] = path
    return req


def complete_fleet_arm(req: dict, trace_path: Optional[str]) -> str:
    """Worker side: retire the arm flag to ``.done`` carrying the trace
    path, so the coordinator (and a human tailing the dir) sees where
    the capture landed and the worker never re-profiles."""
    path = req["_path"]
    done = path + ".done"
    tmp = done + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"worker_id": req.get("worker_id"),
                   "trace_path": trace_path}, f)
    os.replace(tmp, done)
    try:
        os.remove(path)
    except OSError:
        pass
    return done


# --------------------------------------------------------------- parse


def read_trace_events(path: str) -> Tuple[List[dict], Dict[str, str]]:
    """Load a Chrome-trace file (gz or plain JSON) and return
    ``(trace_events, track_names)`` where track_names maps
    ``"pid/tid"`` to ``"process name/thread name"`` from the metadata
    events — the zero-dependency half of the parser."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    procs: Dict[str, str] = {}
    threads: Dict[str, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            procs[str(e.get("pid"))] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            threads[f"{e.get('pid')}/{e.get('tid')}"] = \
                str(args.get("name", ""))
    tracks: Dict[str, str] = {}
    for key, tname in threads.items():
        pid = key.split("/", 1)[0]
        tracks[key] = f"{procs.get(pid, '')}/{tname}"
    return events, tracks


def device_op_events(events: List[dict],
                     tracks: Dict[str, str]) -> List[dict]:
    """The complete ``X`` events that represent device-op execution:
    events carrying ``args.hlo_op`` (CPU thunk runtime stamps every op)
    or sitting on an ``XLA Ops`` thread (TPU device tracks)."""
    out: List[dict] = []
    for e in events:
        if e.get("ph") != "X" or e.get("dur") is None:
            continue
        args = e.get("args") or {}
        if "hlo_op" in args:
            out.append(e)
            continue
        track = tracks.get(f"{e.get('pid')}/{e.get('tid')}", "")
        if "XLA Ops" in track:
            out.append(e)
    return out


# ------------------------------------------------------- classification

# Ordered DMA rules run on the OP name first (a transfer inside any
# module is still a transfer), then module rules — batch patterns
# before fused ones because "fused_cost_packed_batch" contains both.
_DMA_OP_RE = re.compile(
    r"infeed|outfeed|copy|transfer|dma|send|recv|reshard|host.?to.?device"
    r"|device.?to.?host", re.I)
_MODULE_RULES: List[Tuple[re.Pattern, str]] = [
    (re.compile(r"packed_batch|minibatch_batch|serve_batch|_batch\b", re.I),
     "batched_grid"),
    (re.compile(r"fused_cost|fused_predict|bench_step_fused|mosaic"
                r"|tpu_custom_call|pallas", re.I), "fused_grid"),
    (re.compile(r"predict|coherency|hier", re.I), "xla_predict"),
    (re.compile(r"lbfgs|sagefit|lm_solve|rtr_solve|bench_step_xla"
                r"|robust|solve|step", re.I), "lbfgs_vector"),
]

KERNEL_FAMILIES = ("fused_grid", "batched_grid", "xla_predict",
                   "lbfgs_vector", "dma_infeed", "other")


def classify_kernel(module: str, op: str = "") -> str:
    """Kernel family for one (hlo_module, hlo_op) pair — the single
    classifier used for both trace events and ledger names, so the
    roofline join buckets both sides identically."""
    if op and _DMA_OP_RE.search(op):
        return "dma_infeed"
    name = module or op
    for pat, fam in _MODULE_RULES:
        if pat.search(name):
            return fam
    return "other"


# --------------------------------------------------------- attribution


def _self_durations(track_events: List[Tuple[float, float, int]]
                    ) -> Dict[int, float]:
    """Exclusive (self) duration per event on ONE track: a container
    event (the CPU thunk runtime nests while-loop/fusion bodies inside
    their parent's X event) is billed only for the time not covered by
    its children, so attribution sums to the track's busy union instead
    of double-counting every level of the nesting."""
    out: Dict[int, float] = {}
    stack: List[Tuple[float, int]] = []  # (end, event index)
    for ts, dur, idx in sorted(track_events):
        end = ts + dur
        out[idx] = dur
        while stack and stack[-1][0] <= ts:
            stack.pop()
        if stack:
            parent_end, parent = stack[-1]
            out[parent] -= min(end, parent_end) - ts
        stack.append((end, idx))
    return out


def _union_us(ivals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals (µs)."""
    if not ivals:
        return 0.0
    ivals.sort()
    total = 0.0
    cur_s, cur_e = ivals[0]
    for s, e in ivals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def _merged_windows(ivals: List[Tuple[float, float]],
                    gap_threshold_us: float) -> List[Tuple[float, float]]:
    """Busy windows: intervals merged whenever the gap between them is
    below the threshold — what's left between windows is host/dispatch
    time, the quantity the ~65 ms floor lives in."""
    if not ivals:
        return []
    ivals = sorted(ivals)
    out = [list(ivals[0])]
    for s, e in ivals[1:]:
        if s - out[-1][1] <= gap_threshold_us:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def attribute_trace(path: str,
                    gap_threshold_us: float = 1000.0) -> dict:
    """Parse one trace and attribute device time to kernel families.

    Returns ``{"trace_path", "n_op_events", "total_device_us",
    "span_us", "families": {fam: {time_us, events, top_ops}},
    "modules": {mod: {time_us, n_exec, family}},
    "dispatch": {n_windows, n_gaps, gap_total_us, gap_mean_us,
    gap_p50_us, gap_max_us, amortization}}``.

    - total device time is the union of per-track busy intervals (two
      ops overlapping on different device tracks count once) — the
      denominator the ≥95%-attribution acceptance check divides by;
      family times are summed SELF durations (container events like
      the CPU runtime's while-loop/fusion wrappers are billed only for
      time not covered by their nested children), so attribution can
      only fall short of 100% via unclassifiable events, never
      overshoot from double-counting nesting levels.
    - per-module ``n_exec`` is the MIN single-op-name count within the
      module: an op outside any loop emits exactly once per dispatch,
      so its count IS the number of executions inside the trace window
      (no process-lifetime counters trusted); loop-body ops emit once
      per *iteration* and would overcount by the trip count (a 20-iter
      LBFGS ``while_loop`` measured 280x), which is why max is wrong.
      Ops on a rarely-taken conditional branch could undercount — the
      lesser error for a ledger join that scales flops by ``n_exec``.
    - dispatch gaps are measured between merged busy windows; the
      ``amortization`` ratio (busy/(busy+gaps)) is how far whole-solve
      jits have amortized the dispatch floor.
    """
    events, tracks = read_trace_events(path)
    ops = device_op_events(events, tracks)

    families: Dict[str, dict] = {}
    modules: Dict[str, dict] = {}
    mod_op_counts: Dict[str, Dict[str, int]] = {}
    fam_op_times: Dict[str, Dict[str, float]] = {}
    per_track: Dict[str, List[Tuple[float, float]]] = {}
    track_idx: Dict[str, List[Tuple[float, float, int]]] = {}
    all_ivals: List[Tuple[float, float]] = []

    for i, e in enumerate(ops):
        dur = float(e.get("dur", 0.0))
        ts = float(e.get("ts", 0.0))
        key = f"{e.get('pid')}/{e.get('tid')}"
        per_track.setdefault(key, []).append((ts, ts + dur))
        track_idx.setdefault(key, []).append((ts, dur, i))
        all_ivals.append((ts, ts + dur))

    self_us: Dict[int, float] = {}
    for tevs in track_idx.values():
        self_us.update(_self_durations(tevs))

    for i, e in enumerate(ops):
        args = e.get("args") or {}
        mod = str(args.get("hlo_module", ""))
        op = str(args.get("hlo_op", e.get("name", "")))
        dur = max(self_us.get(i, 0.0), 0.0)
        fam = classify_kernel(mod, op)

        f = families.setdefault(fam, {"time_us": 0.0, "events": 0})
        f["time_us"] += dur
        f["events"] += 1
        fam_op_times.setdefault(fam, {})
        fam_op_times[fam][op] = fam_op_times[fam].get(op, 0.0) + dur

        if mod:
            m = modules.setdefault(mod, {"time_us": 0.0, "family": fam})
            m["time_us"] += dur
            mod_op_counts.setdefault(mod, {})
            mod_op_counts[mod][op] = mod_op_counts[mod].get(op, 0) + 1

    total_us = sum(_union_us(iv) for iv in per_track.values())
    for fam, f in families.items():
        tops = sorted(fam_op_times.get(fam, {}).items(),
                      key=lambda kv: -kv[1])
        f["top_ops"] = [{"op": k, "time_us": round(v, 1)}
                        for k, v in tops[:5]]
        f["time_us"] = round(f["time_us"], 3)
    for mod, m in modules.items():
        counts = mod_op_counts.get(mod, {})
        m["n_exec"] = min(counts.values()) if counts else 1
        m["time_us"] = round(m["time_us"], 3)

    dispatch: dict = {}
    if all_ivals:
        windows = _merged_windows(all_ivals, gap_threshold_us)
        gaps = [windows[i + 1][0] - windows[i][1]
                for i in range(len(windows) - 1)]
        gaps = [g for g in gaps if g > 0]
        busy = sum(e - s for s, e in windows)
        span = windows[-1][1] - windows[0][0]
        gaps_sorted = sorted(gaps)
        dispatch = {
            "n_windows": len(windows),
            "n_gaps": len(gaps),
            "gap_total_us": round(sum(gaps), 1),
            "gap_mean_us": round(sum(gaps) / len(gaps), 1) if gaps else 0.0,
            "gap_p50_us": round(gaps_sorted[len(gaps) // 2], 1)
            if gaps else 0.0,
            "gap_max_us": round(max(gaps), 1) if gaps else 0.0,
            "amortization": round(busy / span, 4) if span > 0 else 1.0,
        }
    span_us = (max(e for _, e in all_ivals) - min(s for s, _ in all_ivals)) \
        if all_ivals else 0.0

    return {
        "trace_path": path,
        "n_op_events": len(ops),
        "total_device_us": round(total_us, 3),
        "span_us": round(span_us, 3),
        "families": families,
        "modules": modules,
        "dispatch": dispatch,
    }


# --------------------------------------------------------- ledger join


def ledger_from_perf_stats() -> Dict[str, dict]:
    """Live ledger: the in-process ``instrumented_jit`` cost-analysis
    stats keyed by trace module name (``jit_<fn>``)."""
    from sagecal_tpu.obs.perf import perf_stats

    out: Dict[str, dict] = {}
    for name, st in perf_stats().items():
        out[f"jit_{name}"] = {"flops": st.get("flops"),
                              "bytes_accessed": st.get("bytes_accessed")}
    return out


def ledger_from_events(events_path: str) -> Dict[str, dict]:
    """Offline ledger: rebuild per-fn flops/bytes from the
    ``jit_compile`` events of a JSONL event log (last compile wins,
    matching the live ledger's semantics)."""
    out: Dict[str, dict] = {}
    try:
        with open(events_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except Exception:
                    continue
                # event logs stamp the kind under "type" (events.py);
                # accept "event" too for hand-rolled ledgers
                if ev.get("type", ev.get("event")) != "jit_compile":
                    continue
                fn = ev.get("fn")
                if not fn:
                    continue
                out[f"jit_{fn}"] = {
                    "flops": ev.get("flops"),
                    "bytes_accessed": ev.get("bytes_accessed"),
                }
    except OSError:
        pass
    return out
