"""Multi-tenant calibration service: the batch scheduler.

One process serves many independent (tenant, dataset, tile) solve
requests.  Three mechanisms turn that request mix into full device
programs instead of a one-at-a-time dispatch loop:

1. **vmapped batch solves** — same-bucket requests stack into ONE
   jitted program (solvers/batched.py); solves/sec scales with the
   batch because the dispatch floor and the under-utilized small-shape
   kernels are paid once per batch, not once per request.
2. **bucketed executable cache** — requests bucket by abstract shape
   (serve/bucket.py) and numerics fingerprint; each bucket compiles
   once and every later batch of that shape reuses the executable
   (serve/cache.py proves it with hit counters + ``compiles == 1``).
3. **double-buffered prefetch** — every (tenant, dataset) stream gets
   its own io/dataset.py :class:`TilePrefetcher` with ``depth=2``, so
   the HDF5 read + host packing of the next requests overlaps the
   device solve of the current batch; prefetchers are closed (threads
   reaped) as each stream drains, and remain registered with the
   obs/flight.py crash path until then.

Scheduling is round-robin across tenants: each turn pops one request
from one tenant's queue, so a tenant with a deep queue cannot starve
the others; batches therefore interleave tenants whenever their
requests share a bucket (the executable doesn't care whose data it
solves).

Elastic: each tenant owns a namespaced CheckpointManager
(``<ckpt_dir>/tenants/<tenant>``) recording which of its requests have
completed; a preempted server re-run with ``--resume`` skips those and
drains only the remainder (results already on disk are untouched).
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from sagecal_tpu.serve.bucket import BucketSpec, bucket_of, pad_indices
from sagecal_tpu.serve.cache import ExecutableCache
from sagecal_tpu.serve.request import SolveRequest, write_result_manifest


def _merge_sage_config(cfg, req: SolveRequest):
    """Request solver knobs (None = inherit) over the service-wide
    ServeConfig defaults -> (SageConfig, numerics fingerprint)."""
    from sagecal_tpu.elastic.checkpoint import config_fingerprint
    from sagecal_tpu.solvers.sage import SageConfig

    knobs = dict(
        solver_mode=(cfg.solver_mode if req.solver_mode is None
                     else req.solver_mode),
        max_emiter=(cfg.max_emiter if req.max_emiter is None
                    else req.max_emiter),
        max_iter=cfg.max_iter if req.max_iter is None else req.max_iter,
        max_lbfgs=(cfg.max_lbfgs if req.max_lbfgs is None
                   else req.max_lbfgs),
        lbfgs_m=cfg.lbfgs_m if req.lbfgs_m is None else req.lbfgs_m,
        nulow=cfg.nulow if req.nulow is None else req.nulow,
        nuhigh=cfg.nuhigh if req.nuhigh is None else req.nuhigh,
        randomize=(cfg.randomize if req.randomize is None
                   else req.randomize),
    )
    # fused-kernel routing is service-wide, f32-only (the fullbatch
    # precedent: a fused request under use_f64 silently stays on XLA)
    use_fused = getattr(cfg, "use_fused_predict", False) \
        and not cfg.use_f64
    coh_dtype = getattr(cfg, "coh_dtype", "f32")
    scfg = SageConfig(
        collect_quality=True,     # per-request verdicts are the product
        use_fused_predict=use_fused,
        coh_dtype=coh_dtype,
        **knobs,
    )
    fp = config_fingerprint(use_f64=cfg.use_f64,
                            use_fused_predict=use_fused,
                            coh_dtype=coh_dtype, **knobs)
    return scfg, fp


class _StreamPool:
    """Bounded pool of double-buffered prefetch streams.

    One stream per (tenant, dataset, tilesz, column) request sequence,
    exactly as before — but opened lazily on first touch and capped at
    ``cap`` concurrently-open :class:`TilePrefetcher` instances
    (``cap <= 0`` = unbounded, the legacy behavior).  Above the cap the
    least-recently-used stream is CLOSED (its reader threads reaped and
    its HDF5 handle released) and transparently reopened from its
    remaining tiles when next touched; each close-for-capacity is
    counted in ``serve_prefetch_evictions_total``.  Without a cap a
    fleet worker claiming requests across many tenants×datasets holds
    one open prefetcher (threads + file handles + depth×tile buffers)
    per stream simultaneously — unbounded fleet-wide."""

    def __init__(self, cap: int):
        self.cap = int(cap)
        self.evictions = 0
        self._specs: Dict[tuple, dict] = {}
        self._open_streams: "collections.OrderedDict[tuple, dict]" = \
            collections.OrderedDict()

    def register(self, skey: tuple, t0s: List[int], dtype) -> None:
        from sagecal_tpu.io.dataset import VisDataset

        _, dpath, _tilesz, _column = skey
        ds = VisDataset(dpath, "r")
        meta = ds.meta
        ds.close()
        self._specs[skey] = {"t0s": list(t0s), "pos": 0, "meta": meta,
                             "dtype": dtype}

    def meta(self, skey: tuple):
        return self._specs[skey]["meta"]

    def next_tile(self, skey: tuple):
        """The next (t0, (data,)) of this stream, opening/reopening its
        prefetcher as needed and closing it when the stream drains."""
        st = self._open_streams.get(skey)
        if st is None:
            st = self._open(skey)
        else:
            self._open_streams.move_to_end(skey)
        spec = self._specs[skey]
        got = next(st["it"])
        spec["pos"] += 1
        if spec["pos"] >= len(spec["t0s"]):
            # drained: the iterator just consumed its sentinel; reap
            # the reader threads now instead of at run teardown
            st["pf"].close()
            self._open_streams.pop(skey, None)
        return got

    def _open(self, skey: tuple) -> dict:
        from sagecal_tpu.io.dataset import TilePrefetcher

        while self.cap > 0 and len(self._open_streams) >= self.cap:
            _vkey, vst = self._open_streams.popitem(last=False)
            vst["pf"].close()
            self.evictions += 1
            try:
                from sagecal_tpu.obs.registry import get_registry

                get_registry().counter_inc(
                    "serve_prefetch_evictions_total",
                    help="prefetch streams closed for capacity "
                         "(reopened from remaining tiles on next touch)")
            except Exception:
                pass
        spec = self._specs[skey]
        _, dpath, tilesz, column = skey
        pf = TilePrefetcher(
            dpath, spec["t0s"][spec["pos"]:],
            [dict(average_channels=True, dtype=spec["dtype"],
                  column=column)],
            tilesz, depth=2)
        st = {"pf": pf, "it": iter(pf.__enter__())}
        self._open_streams[skey] = st
        return st

    def close(self) -> None:
        for st in self._open_streams.values():
            st["pf"].close()
        self._open_streams.clear()


class _Entry:
    """One loaded, solve-ready request."""

    __slots__ = ("req", "data", "cdata", "p0", "key", "scfg", "meta",
                 "nclus", "nchunk_max", "enqueued_at", "started_at")

    def __init__(self, req, data, cdata, p0, key, scfg, meta,
                 nclus, nchunk_max):
        self.req = req
        self.data = data
        self.cdata = cdata
        self.p0 = p0
        self.key = key
        self.scfg = scfg
        self.meta = meta
        self.nclus = nclus
        self.nchunk_max = nchunk_max
        # request-lifecycle wall-clock marks (set by the scheduler)
        self.enqueued_at = 0.0
        self.started_at = 0.0


class CalibrationService:
    """Drains a request manifest through bucketed batch solves.

    ``run()`` returns a summary dict (per-request results, latency
    percentiles, executable-cache stats) used by the CLI, the bench and
    the tests."""

    def __init__(self, cfg, log=print, device=None, aot_store=None):
        self.cfg = cfg
        self.log = log
        self.device = device
        self.cache = ExecutableCache(store=aot_store)
        self._sky_cache: Dict[tuple, tuple] = {}
        self._results: List[Dict[str, Any]] = []
        self._latencies: List[float] = []
        self._diverged_abort: Optional[tuple] = None
        self._slo = None  # SLOMonitor, built in run() from cfg.slo
        # shadow-solve auditor (obs/shadow.py), built in run() iff
        # cfg.shadow_rate > 0 — with the rate at 0 no auditor object
        # exists and the dispatch path is byte-identical to a build
        # without the feature (pinned in tests/test_drift.py)
        self.shadow = None

    # -- data loading --------------------------------------------------

    def _sky(self, req: SolveRequest, ra0, dec0, dtype):
        from sagecal_tpu.io.skymodel import load_sky

        key = (os.path.abspath(req.sky_model),
               os.path.abspath(req.cluster_file),
               float(ra0), float(dec0), str(dtype))
        hit = self._sky_cache.get(key)
        if hit is None:
            hit = load_sky(req.sky_model, req.cluster_file, ra0, dec0,
                           dtype=dtype)
            self._sky_cache[key] = hit
        return hit

    def _load_entry(self, req: SolveRequest, data, meta) -> _Entry:
        """Tile data (already prefetched) -> solve-ready entry:
        coherencies, identity gains carry, per-request RNG key."""
        import zlib

        import jax.numpy as jnp

        from sagecal_tpu.core.types import identity_jones, jones_to_params
        from sagecal_tpu.solvers.sage import build_cluster_data

        dtype = np.float64 if self.cfg.use_f64 else np.float32
        cdtype = np.complex128 if self.cfg.use_f64 else np.complex64
        clusters, cdefs, shapelets = self._sky(
            req, meta.ra0, meta.dec0, dtype)
        nchunks = [cd.nchunk for cd in cdefs]
        nchunk_max = max(nchunks)
        M = len(clusters)
        N = meta.nstations
        cdata = build_cluster_data(data, clusters, nchunks,
                                   shapelets=shapelets)
        eye = jones_to_params(identity_jones(N, cdtype))
        p0 = np.asarray(
            jnp.broadcast_to(eye, (M, nchunk_max, 8 * N)).astype(dtype))
        scfg, fp = _merge_sage_config(self.cfg, req)
        # per-request key derived from the FULL request identity via the
        # shared batched-solver helper — a pure function of the request,
        # so the randomized solver stream reproduces across restarts,
        # schedulers and batch slots (the old 4-byte-prefix seed
        # collided for ids sharing a prefix, and re-deriving per
        # submission made robust solves scheduler-dependent)
        from sagecal_tpu.solvers.batched import derive_lane_keys

        lane_id = zlib.crc32(req.request_id.encode())
        key = np.asarray(derive_lane_keys(0, [lane_id])[0])
        entry = _Entry(req, data, cdata, p0, key, scfg, meta, M,
                       nchunk_max)
        return entry, fp

    # -- batch dispatch ------------------------------------------------

    def _dispatch(self, bucket: BucketSpec, fingerprint: str,
                  entries: List[_Entry], batch: int, elog,
                  padded_flush: bool) -> None:
        """Stack ``entries`` into one vmapped solve; unpack each real
        lane into its request's solutions file + result manifest."""
        import jax

        idx, valid = pad_indices(len(entries), batch)
        k = len(entries)
        t_pack = time.time()

        def stack(get):
            return jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *[get(entries[i]) for i in idx])

        data_b = stack(lambda e: e.data.replace(vis=None))
        cdata_b = stack(lambda e: e.cdata._replace(coh=None))
        vis = np.stack([np.asarray(entries[i].data.vis) for i in idx])
        coh = np.stack([np.asarray(entries[i].cdata.coh) for i in idx])
        p0 = np.stack([entries[i].p0 for i in idx])
        keys = np.stack([entries[i].key for i in idx])
        scfg = entries[0].scfg

        # kernel-path capability check on the CONCRETE stacked batch
        # (host numpy): one Pallas grid for the whole batch when it
        # passes, vmapped solo kernels / XLA otherwise.  Deterministic
        # per (bucket, fingerprint), so the executable-cache entry and
        # the static batched_fused flag always agree.
        from sagecal_tpu.solvers.batched import choose_batched_path

        kernel_path, path_reason = choose_batched_path(
            data_b, cdata_b, p0, scfg)
        batched_fused = kernel_path == "fused_batch"

        args = (data_b, cdata_b, vis.real, vis.imag, coh.real, coh.imag,
                p0, scfg, keys, np.asarray(valid, bool))
        if self.device is not None:
            args = jax.device_put(args, self.device)
        pack_s = time.time() - t_pack
        # compile time shows up either inside get_with_status (AOT
        # store path) or inside the first call of the lazy wrapper;
        # both land between `tic` and the host sync, and the perf-stats
        # delta splits compile out of execute so the lifecycle's
        # compile|cache_hit span is honest either way
        name = self.cache.entry_name(bucket, fingerprint)
        compile_before = self._compile_seconds_by_name(name)
        tic = time.time()
        fn, cache_hit = self.cache.get_with_status(
            bucket, fingerprint, example_args=args,
            batched_fused=batched_fused)
        out = fn(*args)
        # materialize on host before unpacking lanes (one sync)
        p_host = np.asarray(out.p)
        res0_host = np.asarray(out.res_0)
        res1_host = np.asarray(out.res_1)
        div_host = np.asarray(out.diverged)
        nu_host = np.asarray(out.mean_nu)
        solve_s = time.time() - tic
        compile_s = 0.0 if cache_hit else max(
            self._compile_seconds_by_name(name) - compile_before, 0.0)
        timing = {
            "t_pack": t_pack, "pack_s": pack_s, "t_exec": tic,
            "solve_s": solve_s, "cache_hit": cache_hit,
            "compile_s": min(compile_s, solve_s),
        }
        if elog is not None:
            elog.emit("serve_batch_dispatched", bucket=bucket.short(),
                      fingerprint=fingerprint[:12], size=k,
                      batch=len(idx), padded=padded_flush,
                      seconds=solve_s,
                      kernel_path=kernel_path,
                      kernel_path_reason=path_reason,
                      cache=self.cache.stats())
        # unpack over the FULL batch width with an explicit validity
        # guard: replication-padded lanes (valid[lane] is False) carry
        # a copy of some real request's data, so their solve outputs —
        # and in particular their quality structures — must never reach
        # _finish_request, or a padded tail lane could fire a spurious
        # quality_degraded / solver_diverged verdict for a request that
        # already has its real verdict from its own lane.
        lane_quality = {}
        for lane in range(len(idx)):
            if not valid[lane]:
                continue
            lane_quality[lane] = (
                None if out.quality is None else jax.tree_util.tree_map(
                    lambda x: x[lane], out.quality))
            self._finish_request(
                entries[lane], bucket, lane, len(idx),
                p_host[lane], float(res0_host[lane]),
                float(res1_host[lane]), bool(div_host[lane]),
                float(nu_host[lane]), lane_quality[lane],
                elog, timing, kernel_path, path_reason)
        if self.shadow is not None:
            # shadow audits run strictly AFTER every manifest of the
            # batch is on disk — the re-solve shares the process but
            # never the latency path of any request in flight
            for lane in range(len(idx)):
                if not valid[lane]:
                    continue
                self.shadow.audit(
                    entries[lane], bucket.short(), kernel_path,
                    path_reason, p_host[lane],
                    float(res1_host[lane]), lane_quality[lane], elog)

    @staticmethod
    def _compile_seconds_by_name(name: str) -> float:
        """Cumulative compile seconds attributed to a named executable
        entry (0.0 when perf stats are unavailable)."""
        try:
            from sagecal_tpu.obs.perf import perf_stats

            if not name:
                return 0.0
            return float(perf_stats().get(name, {}).get(
                "compile_seconds", 0.0))
        except Exception:
            return 0.0

    def _finish_request(self, entry: _Entry, bucket, lane, batch,
                        p, res0, res1, diverged, mean_nu, quality,
                        elog, timing, kernel_path: str = "xla",
                        path_reason: str = "") -> None:
        from sagecal_tpu.core.types import params_to_jones
        from sagecal_tpu.io import solutions as solio
        from sagecal_tpu.obs.quality import check_and_emit
        from sagecal_tpu.obs.registry import get_registry

        req, meta = entry.req, entry.meta
        t_unpack = time.time()
        # divergence guard, same residual-ratio policy as fullbatch
        ratio_blown = (not np.isfinite(res1) or res1 == 0.0
                       or res1 > self.cfg.res_ratio * res0)
        verdict, reasons = "ok", []
        if quality is not None:
            verdict, reasons = check_and_emit(
                elog, quality, log=self.log, tile=req.t0, app="serve",
                tenant=req.tenant, request_id=req.request_id)
        if diverged or ratio_blown:
            if verdict != "diverged" and elog is not None:
                elog.emit("solver_diverged",
                          reasons=[f"residual_ratio:{res0:.3e}->{res1:.3e}"],
                          tile=req.t0, app="serve", tenant=req.tenant,
                          request_id=req.request_id)
            verdict = "diverged"
            reasons = reasons + [f"residual_ratio:{res0:.3e}->{res1:.3e}"]

        out_path = req.out_solutions or os.path.join(
            self.cfg.out_dir, f"{req.request_id}.solutions")
        N, M, nchunk_max = meta.nstations, entry.nclus, entry.nchunk_max
        jsol = np.asarray(params_to_jones(p)).reshape(
            M * nchunk_max, N, 2, 2)
        # tmp + replace: the published solutions file is whole at
        # every instant (a reader never sees a header without its
        # solutions)
        tmp_path = f"{out_path}.tmp.{os.getpid()}"
        with open(tmp_path, "w") as fh:
            solio.write_header(
                fh, meta.freq0, meta.deltaf,
                meta.deltat * req.tilesz / 60.0, N, M, M * nchunk_max)
            solio.append_solutions(fh, jsol)
        os.replace(tmp_path, out_path)

        from sagecal_tpu.obs.trace import get_tracer

        tracer = get_tracer()
        t_write = time.time()
        queue_wait = max(entry.started_at - entry.enqueued_at, 0.0)
        result = {
            "request_id": req.request_id, "tenant": req.tenant,
            "dataset": req.dataset, "t0": req.t0, "tilesz": req.tilesz,
            "verdict": verdict, "reasons": reasons,
            "res_0": res0, "res_1": res1, "mean_nu": mean_nu,
            "bucket": bucket.short(), "batch": batch, "lane": lane,
            # which kernel actually solved this request, and why the
            # capability check chose it — the bench already stamps
            # this; operators get it per result (diag serve columns)
            "kernel_path": kernel_path,
            "kernel_path_reason": path_reason,
            "solutions": out_path,
            # wall-clock lifecycle: latency reconstructable from the
            # manifest alone, no live gauges needed
            "enqueued_at": entry.enqueued_at,
            "started_at": entry.started_at,
            "completed_at": t_write,
            "queue_wait_s": queue_wait,
            "latency_s": t_write - entry.enqueued_at,
            "trace_id": req.trace_id,
        }
        if tracer.enabled:
            result["span_id"] = tracer.allocate_span_id()
        write_result_manifest(self.cfg.out_dir, result)
        write_s = time.time() - t_write
        latency = result["latency_s"]
        self._latencies.append(latency)
        if tracer.enabled:
            self._emit_lifecycle(tracer, entry, bucket, lane, batch,
                                 verdict, timing, t_unpack, t_write,
                                 write_s, result["span_id"])
        self._results.append(result)
        reg = get_registry()
        reg.counter_inc("serve_requests_total", tenant=req.tenant,
                        verdict=verdict,
                        help="serve requests completed, by verdict")
        reg.observe("serve_request_latency_seconds",
                    result["latency_s"], tenant=req.tenant,
                    help="submit -> result-manifest latency")
        reg.observe("serve_queue_wait_seconds", queue_wait,
                    tenant=req.tenant,
                    help="enqueue -> scheduler-pop wait")
        if self._slo is not None and self._slo.enabled:
            self._slo.observe(req.tenant, result["completed_at"],
                              latency, verdict)
            self._slo.evaluate(now=result["completed_at"], elog=elog,
                               registry=reg)
        if elog is not None:
            elog.emit("request_done", **result)
        self.log(f"request {req.request_id} [{req.tenant}]: "
                 f"{verdict} residual {res0:.6f} -> {res1:.6f} "
                 f"(bucket {bucket.short()}, lane {lane}/{batch}, "
                 f"{result['latency_s']:.1f}s)")
        if verdict == "diverged" and self.cfg.abort_on_divergence \
                and self._diverged_abort is None:
            # raised after the whole batch's manifests are on disk
            self._diverged_abort = (req.request_id, req.t0, reasons)

    def _emit_lifecycle(self, tracer, entry: _Entry, bucket, lane,
                        batch, verdict, timing, t_unpack, t_write,
                        write_s, root_id) -> None:
        """One trace per request: ``serve.request`` root spanning
        enqueue -> manifest write, with the full phase chain as
        children.  Batch-shared phases (pack/compile/execute) are
        billed to every lane of the batch, marked ``shared`` with the
        batch width, so per-request traces stay self-contained while
        fleet totals divide by the batch attr.  The root records under
        the pre-allocated ``root_id`` already written into the result
        manifest — that is the pointer that lets a later process (or a
        --resume continuation) join manifest and trace."""
        req = entry.req
        tid = req.trace_id
        base = dict(request_id=req.request_id, tenant=req.tenant,
                    bucket=bucket.short(), lane=lane, batch=batch)
        # parent_id="" (not None) pins the root above any ambient span
        # stack; readers treat missing/unknown parents as roots
        tracer.add_span(
            "serve.request", t_write + write_s - entry.enqueued_at,
            parent_id="", start_unix=entry.enqueued_at, trace_id=tid,
            span_id=root_id, verdict=verdict, **base)

        def child(name, start, dur, **attrs):
            tracer.add_span(name, max(dur, 0.0), parent_id=root_id,
                            start_unix=start, trace_id=tid,
                            **dict(base, **attrs))

        child("enqueue", entry.enqueued_at,
              entry.started_at - entry.enqueued_at)
        child("schedule", entry.started_at,
              timing["t_pack"] - entry.started_at)
        child("pack", timing["t_pack"], timing["pack_s"], shared=True)
        exec_s = timing["solve_s"] - timing["compile_s"]
        if timing["cache_hit"]:
            child("cache_hit", timing["t_pack"] + timing["pack_s"], 0.0)
        else:
            child("compile", timing["t_exec"], timing["compile_s"],
                  shared=True)
        child("execute", timing["t_exec"] + timing["compile_s"], exec_s,
              shared=True)
        child("unpack", t_unpack, t_write - t_unpack)
        child("write_manifest", t_write, write_s)

    def _build_slo_monitor(self):
        """SLO specs from ``cfg.slo`` (a slo.json) or, failing that, a
        top-level ``"slos"`` key inside the request manifest."""
        from sagecal_tpu.obs.slo import SLOMonitor, load_slo_specs

        specs = {}
        if self.cfg.slo:
            specs = load_slo_specs(self.cfg.slo)
        elif self.cfg.requests and os.path.exists(self.cfg.requests):
            specs = load_slo_specs(self.cfg.requests)
        return SLOMonitor(specs)

    # -- the scheduler -------------------------------------------------

    def run(self, requests: List[SolveRequest], elog=None
            ) -> Dict[str, Any]:
        import jax

        from sagecal_tpu.elastic.checkpoint import (
            CheckpointManager, config_fingerprint,
        )
        from sagecal_tpu.obs.quality import DivergenceAbort
        from sagecal_tpu.obs.registry import get_registry

        cfg, reg = self.cfg, get_registry()
        t_start = time.time()
        os.makedirs(cfg.out_dir, exist_ok=True)
        self._slo = self._build_slo_monitor()
        shadow_owned = False
        if self.shadow is None \
                and float(getattr(cfg, "shadow_rate", 0.0) or 0.0) > 0.0:
            # a fleet worker injects its own persistent auditor before
            # run() (budget is per WORKER, not per claim cycle); the
            # standalone service builds and owns one per run
            from sagecal_tpu.obs.shadow import ShadowAuditor

            self.shadow = ShadowAuditor(
                cfg.out_dir, rate=cfg.shadow_rate,
                budget_s=float(getattr(cfg, "shadow_budget_s", 60.0)),
                seed=int(getattr(cfg, "shadow_seed", 0)),
                device=self.device, log=self.log)
            shadow_owned = True

        # -- per-tenant elastic state: which requests already finished
        tenants = list(dict.fromkeys(r.tenant for r in requests))
        by_tenant = {t: [r for r in requests if r.tenant == t]
                     for t in tenants}
        ckmgrs: Dict[str, CheckpointManager] = {}
        done_flags: Dict[str, np.ndarray] = {}
        skipped = 0
        resumed_metrics: List[tuple] = []  # (metrics_ts, state)
        for t in tenants:
            reqs = by_tenant[t]
            fp = config_fingerprint(
                app="serve", tenant=t,
                requests=[(r.request_id, os.path.abspath(r.dataset),
                           r.t0, r.tilesz, r.in_column) for r in reqs],
                use_f64=cfg.use_f64)
            flags = np.zeros(len(reqs), np.uint8)
            if cfg.resume or cfg.checkpoint_every > 0:
                mgr = CheckpointManager(
                    os.path.join(
                        cfg.checkpoint_dir
                        or os.path.join(cfg.out_dir, "serve.ckpt"),
                        "tenants", t),
                    fp, "serve", every=max(cfg.checkpoint_every, 1),
                    elog=elog, log=self.log)
                ckmgrs[t] = mgr
                if cfg.resume:
                    found = mgr.resume()
                    if found is not None:
                        rmeta, rarr, rpath = found
                        flags = np.asarray(
                            rarr["done"], np.uint8).copy()
                        n = int(flags.sum())
                        skipped += n
                        self.log(f"resume[{t}]: {n}/{len(reqs)} "
                                 f"requests already served ({rpath})")
                        if isinstance(rmeta, dict) \
                                and rmeta.get("metrics"):
                            resumed_metrics.append(
                                (float(rmeta.get("metrics_ts", 0.0)),
                                 rmeta["metrics"]))
                        if elog is not None:
                            for r, f in zip(reqs, flags):
                                if f:
                                    elog.emit("request_skipped_resume",
                                              request_id=r.request_id,
                                              tenant=t)
            done_flags[t] = flags
        if resumed_metrics and reg.enabled:
            # every tenant checkpoint snapshots the whole process-wide
            # registry, so restore only the NEWEST one: counters stay
            # monotonic across the preemption without double-counting
            _, state = max(resumed_metrics, key=lambda x: x[0])
            reg.restore_state(state)

        # -- queues (post-resume) and double-buffered prefetch streams.
        # A stream is one (tenant, dataset, tilesz, column) request
        # sequence; its prefetcher loads tiles in exactly the order the
        # round-robin will pop them.
        queues = {
            t: collections.deque(
                r for r, f in zip(by_tenant[t], done_flags[t]) if not f)
            for t in tenants}
        enqueued_at = {
            r.request_id: getattr(r, "enqueued_at", 0.0) or time.time()
            for t in tenants for r in queues[t]}
        for t in tenants:
            reg.gauge_set("serve_queue_depth", len(queues[t]),
                          tenant=t,
                          help="requests waiting in this tenant's queue")

        dtype = np.float64 if cfg.use_f64 else np.float32
        stream_t0s: Dict[tuple, List[int]] = {}
        for t in tenants:
            for r in queues[t]:
                skey = (t, os.path.abspath(r.dataset), r.tilesz,
                        r.in_column)
                stream_t0s.setdefault(skey, []).append(r.t0)
        pool = _StreamPool(getattr(cfg, "max_streams", 0))
        for skey, t0s in stream_t0s.items():
            pool.register(skey, t0s, dtype)

        pending: Dict[tuple, List[_Entry]] = collections.defaultdict(list)
        served = 0

        def mark_done(entry: _Entry) -> None:
            nonlocal served
            served += 1
            t = entry.req.tenant
            i = next(i for i, r in enumerate(by_tenant[t])
                     if r.request_id == entry.req.request_id)
            done_flags[t][i] = 1
            if t in ckmgrs:
                extra = {}
                if reg.enabled:
                    # registry snapshot rides the elastic checkpoint:
                    # a --resume restores it, so counters survive
                    # preemptions instead of silently resetting
                    extra = dict(metrics=reg.export_state(),
                                 metrics_ts=time.time())
                ckmgrs[t].update(
                    int(done_flags[t].sum()) - 1,
                    {"done": done_flags[t]},
                    requests_done=int(done_flags[t].sum()),
                    tenant=t, **extra)

        def dispatch(bkey, padded_flush):
            bucket, fp = bkey
            entries = pending.pop(bkey)
            self._dispatch(bucket, fp, entries, cfg.batch, elog,
                           padded_flush)
            for e in entries:
                mark_done(e)

        try:
            # round-robin drain: one request per tenant per turn
            alive = True
            while alive:
                alive = False
                for t in tenants:
                    if not queues[t]:
                        continue
                    alive = True
                    req = queues[t].popleft()
                    t_pop = time.time()
                    reg.gauge_set("serve_queue_depth", len(queues[t]),
                                  tenant=t)
                    skey = (t, os.path.abspath(req.dataset),
                            req.tilesz, req.in_column)
                    t0, (data,) = pool.next_tile(skey)
                    if t0 != req.t0:
                        raise RuntimeError(
                            f"prefetch order mismatch for "
                            f"{req.request_id}: got tile {t0}, "
                            f"expected {req.t0}")
                    entry, fp = self._load_entry(
                        req, data, pool.meta(skey))
                    entry.enqueued_at = enqueued_at.get(
                        req.request_id, t_start)
                    entry.started_at = t_pop
                    bkey = (bucket_of(data, entry.cdata, entry.p0), fp)
                    pending[bkey].append(entry)
                    if len(pending[bkey]) >= cfg.batch:
                        dispatch(bkey, padded_flush=False)
            # ragged flush: pad the leftovers of each bucket
            for bkey in list(pending):
                dispatch(bkey, padded_flush=True)
        finally:
            # streams drain exactly when their queues do, so on the
            # success path every stream already closed on its sentinel;
            # on an error path pool.close() reaps the still-open ones
            # (crash-flusher contract: no leaked reader threads)
            pool.close()
            if self.shadow is not None and shadow_owned:
                self.shadow.close()
            for mgr in ckmgrs.values():
                mgr.flush()
                mgr.close()
            if reg.enabled:
                # one cumulative snapshot per worker: the aggregation
                # side (obs/aggregate.py) merges the fleet's snapshots
                # and keeps the newest per worker id
                from sagecal_tpu.obs.aggregate import (
                    metrics_snapshot_path, write_metrics_snapshot,
                )

                try:
                    write_metrics_snapshot(
                        metrics_snapshot_path(cfg.out_dir),
                        registry=reg)
                except OSError:
                    pass

        wall = time.time() - t_start
        lat = sorted(self._latencies)
        p50 = lat[len(lat) // 2] if lat else 0.0
        summary = {
            "requests": len(requests), "served": served,
            "skipped_resume": skipped,
            "tenants": len(tenants), "buckets": self.cache.stats(),
            "wall_s": wall,
            "solves_per_sec": served / wall if wall > 0 else 0.0,
            "p50_latency_s": p50,
            "prefetch_evictions": pool.evictions,
            "results": self._results,
        }
        if self.shadow is not None:
            summary["shadow"] = self.shadow.stats()
        if self._slo is not None and self._slo.enabled:
            summary["slo"] = self._slo.evaluate(registry=reg)
        if elog is not None:
            elog.emit("run_done", app="serve",
                      **{k: v for k, v in summary.items()
                         if k != "results"})
        if self._diverged_abort is not None:
            rid, t0, reasons = self._diverged_abort
            raise DivergenceAbort(
                f"request {rid} (tile {t0}) diverged: "
                f"{'; '.join(reasons)}")
        if self.shadow is not None and self.shadow.exceeded \
                and getattr(cfg, "abort_on_drift", False):
            # opt-in escalation, after every manifest and the full
            # drift ledger are on disk (report-only is the default —
            # the shipped results may well be fine; the ledger exists
            # so this decision is explicit)
            raise DivergenceAbort(
                "shadow drift exceeded tolerance for request(s) "
                + ", ".join(self.shadow.exceeded)
                + "; aborting (abort_on_drift)")
        return summary
