"""Fullbatch calibration driver: the ``sagecal`` main path.

Redesign of ``run_fullbatch_calibration``
(``/root/reference/src/MS/fullbatch_mode.cpp:38-656``): per-tile loop of
load -> precalculate coherencies -> SAGE solve -> write solutions ->
residuals -> divergence guard.  The pthread/GPU pipeline orchestration
of the reference dissolves into jitted solver calls; the host side only
streams tiles and files.
"""

from __future__ import annotations

import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from sagecal_tpu.apps.config import RunConfig
from sagecal_tpu.core.types import (
    identity_jones,
    jones_to_params,
    mat_of_flat,
    params_to_jones,
)
from sagecal_tpu.io import solutions as solio
from sagecal_tpu.io.dataset import TilePrefetcher, VisDataset
from sagecal_tpu.io.skymodel import load_sky
from sagecal_tpu.ops.residual import calculate_residuals, simulate_visibilities
from sagecal_tpu.solvers.robust import whiten_uv_weights
from sagecal_tpu.solvers.sage import (
    SageConfig,
    build_cluster_data,
    build_cluster_data_withbeam,
    solve_tile,
)


def _load_ignore_list(path: Optional[str], cdefs) -> list:
    if not path:
        return []
    with open(path) as f:
        ids = {int(tok) for line in f for tok in line.split()
               if not line.strip().startswith("#") and tok.strip()}
    return [i for i, cd in enumerate(cdefs) if cd.cluster_id in ids]


def _resolve_ccid(ccid: Optional[int], cdefs) -> Optional[int]:
    """Reference cluster id (-E) -> cluster array index
    (residual.c:953-960)."""
    if ccid is None:
        return None
    for i, cd in enumerate(cdefs):
        if cd.cluster_id == ccid:
            return i
    return None


_REF_BEAM_MODES = {
    # reference -B codes (Dirac_common.h:120-140) -> (internal mode, wideband)
    0: (0, False), 1: (1, False), 2: (3, False), 3: (2, False),
    4: (1, True), 5: (3, True), 6: (2, True),
}


def _beam_setup(cfg: RunConfig, ds: VisDataset):
    """Resolve -B: returns (geom, pointing, coeff, mode, wideband) or
    None when beams are off (the doBeam dispatch of
    fullbatch_mode.cpp:371-388)."""
    if not cfg.beam_mode:
        return None
    from sagecal_tpu.ops.beam import (
        DOBEAM_ARRAY, ElementCoeffs, synthetic_dipole_coeffs,
    )

    mode, wideband = _REF_BEAM_MODES[cfg.beam_mode]
    bp = ds.load_beam()
    if bp is None:
        raise ValueError(
            f"beam mode {cfg.beam_mode} requested but dataset "
            f"{cfg.dataset} has no /beam group (station geometry)"
        )
    geom, pointing = bp
    coeff = None
    if mode != DOBEAM_ARRAY:
        if cfg.element_coeffs:
            # 'lba'/'hba'/'alo' (or a table npz) -> real coefficient
            # tables interpolated to the observing frequency; plain npz
            # -> the single-frequency loadable format
            try:
                coeff = ElementCoeffs.from_table(
                    cfg.element_coeffs, ds.meta.freq0
                )
            except (KeyError, FileNotFoundError):
                coeff = ElementCoeffs.load(cfg.element_coeffs)
        else:
            coeff = synthetic_dipole_coeffs()
    return geom, pointing, coeff, mode, wideband


def run_fullbatch(cfg: RunConfig, log=print):
    """Calibrate (or simulate) every tile of the dataset.  Returns the
    per-tile (res_0, res_1) list.

    Device split: every host stage — IO, coherency precompute,
    residuals, bookkeeping — runs under a CPU default device; each
    tile's SAGE solve crosses to the TPU as ONE packed-real jit
    dispatch (solvers/sage.py solve_tile), mirroring the reference's
    CPU-pipeline + GPU-solver split (fullbatch_mode.cpp:371-464).  The
    split was made for an earlier TPU runtime that could not move
    complex arrays; complex64 now crosses host<->device on the v5e
    (PR 21), and removing the split is queued (ROADMAP C2b).  With no
    TPU and no explicit ``JAX_PLATFORMS=cpu`` this raises
    (utils/platform.accelerator)."""
    import jax

    from sagecal_tpu.obs.perf import enable_persistent_compilation_cache
    from sagecal_tpu.utils.platform import accelerator, cpu_device

    # JAX_COMPILATION_CACHE_DIR (else the checkout's .jax_cache): a
    # restarted run deserializes yesterday's executables, not recompiling
    enable_persistent_compilation_cache()

    accel = accelerator()
    with jax.default_device(cpu_device()):
        return _run_fullbatch_host(cfg, log, accel)


def _run_fullbatch_host(cfg: RunConfig, log, accel):
    dtype = np.float64 if cfg.use_f64 else np.float32
    cdtype = np.complex128 if cfg.use_f64 else np.complex64
    ds = VisDataset(cfg.dataset, "r+")
    meta = ds.meta
    clusters, cdefs, shapelets = load_sky(
        cfg.sky_model, cfg.cluster_file, meta.ra0, meta.dec0, dtype=dtype,
        three_term_spectra=None if cfg.sky_format < 0 else bool(cfg.sky_format),
    )
    M = len(clusters)
    nchunks = [cd.nchunk for cd in cdefs]
    nchunk_max = max(nchunks)
    N = meta.nstations
    ignore_idx = _load_ignore_list(cfg.ignore_clusters_file, cdefs)
    ccid_index = _resolve_ccid(cfg.ccid, cdefs)
    beam = _beam_setup(cfg, ds)

    # initial solutions: identity or warm start (-q),
    # fullbatch_mode.cpp:206-237; simulation mode advances through the
    # file's solution intervals per tile (fullbatch_mode.cpp:562)
    jones_intervals = None
    if cfg.init_solutions:
        _, jones_intervals = solio.read_solutions(cfg.init_solutions)
        p = jnp.asarray(
            jones_to_params(jnp.asarray(jones_intervals[0], cdtype)).reshape(
                M, nchunk_max, 8 * N
            )
        )
    else:
        eye = jones_to_params(identity_jones(N, cdtype))
        p = jnp.broadcast_to(eye, (M, nchunk_max, 8 * N)).astype(dtype)
    pinit = p

    # telemetry (obs/): per-iteration solver traces ride along as extra
    # jitted outputs when SAGECAL_TELEMETRY=1; the JSONL event log gets
    # the manifest now and per-tile events in the loop below
    from sagecal_tpu.obs import RunManifest, default_event_log, telemetry_enabled
    from sagecal_tpu.obs.records import sage_convergence_records

    scfg = SageConfig(
        max_emiter=cfg.max_emiter, max_iter=cfg.max_iter,
        max_lbfgs=cfg.max_lbfgs, lbfgs_m=cfg.lbfgs_m,
        solver_mode=cfg.solver_mode,
        nulow=cfg.nulow, nuhigh=cfg.nuhigh, randomize=cfg.randomize,
        use_fused_predict=cfg.use_fused_predict and not cfg.use_f64,
        # bf16 coherency storage only exists on the fused f32 path; the
        # quality watchdog below validates the solves it produces
        coh_dtype=(cfg.coh_dtype
                   if cfg.use_fused_predict and not cfg.use_f64 else "f32"),
        collect_telemetry=telemetry_enabled(),
        # quality side outputs feed the watchdog: needed whenever
        # telemetry records them OR the run must be able to abort
        collect_quality=telemetry_enabled() or cfg.abort_on_divergence,
    )
    manifest = RunManifest.collect(
        kernel_path="fused" if scfg.use_fused_predict else "xla",
        app="fullbatch", dataset=cfg.dataset, solver_mode=cfg.solver_mode,
        tilesz=cfg.tilesz, n_clusters=M, n_stations=N,
        simulation_mode=cfg.simulation_mode, coh_dtype=scfg.coh_dtype,
    )
    elog = default_event_log(manifest=manifest)
    # crash forensics + tracing: excepthook/SIGTERM flush the event log
    # (run_aborted + flight-dump path), the flight recorder heartbeats
    # for the watch scripts, spans correlate on the manifest run_id
    from sagecal_tpu.obs.flight import (
        close_flight_recorder,
        get_flight_recorder,
        install_crash_handlers,
        note_activity,
        register_event_log,
        unregister_event_log,
    )
    from sagecal_tpu.obs.trace import close_tracer, configure_tracer, get_tracer

    install_crash_handlers()
    if elog is not None:
        register_event_log(elog)
    get_flight_recorder(run_id=manifest.run_id)
    configure_tracer(run_id=manifest.run_id)
    tracer = get_tracer()

    # elastic execution (sagecal_tpu/elastic/): checkpoint at tile
    # boundaries, resume from the newest valid checkpoint.  The RNG key
    # chain is explicit so a resumed tile sees the exact key the
    # uninterrupted run would have used.
    import jax

    rng_key = jax.random.PRNGKey(0)
    ckmgr = None
    resume_done = 0  # pairs completed (and intervals on disk) at resume
    results = []
    if cfg.simulation_mode == 0 and (cfg.resume or cfg.checkpoint_every > 0):
        from sagecal_tpu.elastic.checkpoint import (
            CheckpointManager, ResumeRefused, config_fingerprint,
        )
        import os as _os

        fingerprint = config_fingerprint(
            app="fullbatch", dataset=_os.path.abspath(cfg.dataset),
            sky_model=_os.path.abspath(cfg.sky_model),
            cluster_file=_os.path.abspath(cfg.cluster_file),
            nstations=N, ntime=meta.ntime, nchan=meta.nchan,
            freq0=meta.freq0, n_clusters=M, nchunk_max=nchunk_max,
            tilesz=cfg.tilesz, solver_mode=cfg.solver_mode,
            max_emiter=cfg.max_emiter, max_iter=cfg.max_iter,
            max_lbfgs=cfg.max_lbfgs, lbfgs_m=cfg.lbfgs_m,
            nulow=cfg.nulow, nuhigh=cfg.nuhigh, randomize=cfg.randomize,
            use_f64=cfg.use_f64, whiten=cfg.whiten,
            in_column=cfg.in_column, skip_tiles=cfg.skip_tiles,
            max_tiles=cfg.max_tiles, init_solutions=cfg.init_solutions,
        )
        ckmgr = CheckpointManager(
            cfg.checkpoint_dir or f"{cfg.out_solutions}.ckpt",
            fingerprint, "fullbatch",
            every=max(cfg.checkpoint_every, 1), elog=elog, log=log)
        if cfg.resume:
            found = ckmgr.resume()
            if found is not None:
                rmeta, rarr, rpath = found
                resume_done = int(rmeta["tiles_done"])
                p = jnp.asarray(rarr["p"])
                rng_key = jnp.asarray(rarr["rng_key"])
                results = [tuple(map(float, r))
                           for r in rarr.get("results",
                                             np.zeros((0, 2)))]
                v = None
                if _os.path.exists(cfg.out_solutions):
                    v = solio.validate_solutions(
                        cfg.out_solutions, truncate=True,
                        max_intervals=resume_done)
                if v is None or v["n_intervals"] < resume_done:
                    raise ResumeRefused(
                        f"checkpoint {rpath} records {resume_done} "
                        f"completed tiles but {cfg.out_solutions} holds "
                        f"{0 if v is None else v['n_intervals']} intact "
                        f"intervals; solution file and checkpoint "
                        f"disagree")
                log(f"resume: {resume_done} tiles from {rpath}"
                    + (" (torn interval truncated)"
                       if v["truncated"] else ""))

    sol_fh = None
    if cfg.simulation_mode == 0:
        if resume_done:
            # append-consistent re-open: the file was validated (and
            # any torn/post-checkpoint interval truncated) above
            sol_fh = open(cfg.out_solutions, "a")
        else:
            sol_fh = open(cfg.out_solutions, "w")
            solio.write_header(
                sol_fh, meta.freq0, meta.deltaf,
                meta.deltat * cfg.tilesz / 60.0,
                N, M, M * nchunk_max,
            )

    def _cdata(dat, t0, fdelta=None):
        """Cluster coherencies, beam-aware when -B is on
        (fullbatch_mode.cpp:371-388 dispatch)."""
        if beam is None:
            return build_cluster_data(dat, clusters, nchunks, fdelta=fdelta,
                                      shapelets=shapelets)
        geom, pointing, coeff, mode, wideband = beam
        # ALO (lunar) element: no terrestrial J2000 precession
        # (fullbatch_mode.cpp:335 beam.elType!=ELEM_ALO gate)
        is_alo = (cfg.element_coeffs or "").lower() == "alo"
        return build_cluster_data_withbeam(
            dat, clusters, nchunks, geom, pointing, coeff, mode,
            ds.time_jd(t0, dat.tilesz), meta.ra0, meta.dec0,
            fdelta=fdelta, wideband=wideband, shapelets=shapelets,
            precess=not is_alo,
        )

    # first-class profiling (SURVEY section 5): per-phase wall-clock
    # always on; SAGECAL_PROFILE_DIR additionally captures an XLA trace
    # and SAGECAL_TRANSFER_AUDIT=1 logs implicit host<->device transfers
    from sagecal_tpu.obs.contracts import (
        ContractViolation,
        emit_contract_events,
    )
    from sagecal_tpu.obs.perf import (
        TransferAudit,
        dump_memory_profile,
        emit_perf_events,
    )
    from sagecal_tpu.utils.profiling import PhaseTimer, trace

    timer = PhaseTimer()
    # entered by hand (not `with`) so the existing try/finally below can
    # own the exits without reindenting the whole tile loop; the finally
    # guarantees a crashed run still flushes a loadable trace
    trace_cm = trace()
    trace_dir = trace_cm.__enter__()
    if trace_dir:
        log(f"profiling: XLA trace -> {trace_dir}")
    audit = TransferAudit()
    audit.__enter__()

    # -K/-T partial reruns (MPI/main.cpp:133-139) resolved up front so
    # the prefetcher reads exactly the tiles the loop will consume;
    # resume additionally drops the pairs the checkpointed run already
    # completed (their intervals are on disk)
    pairs = [
        (i, t0) for i, t0 in enumerate(ds.tiles(cfg.tilesz))
        if i >= cfg.skip_tiles
    ]
    if cfg.max_tiles:
        pairs = pairs[: cfg.max_tiles]
    pairs = pairs[resume_done:]
    load_kw = dict(min_uvcut=cfg.min_uvcut, max_uvcut=cfg.max_uvcut,
                   dtype=dtype, column=cfg.in_column)
    specs = [dict(average_channels=False, **load_kw)]
    if not cfg.simulation_mode:
        specs.append(dict(average_channels=True, **load_kw))
    # Background-thread tile prefetch (io/dataset.py TilePrefetcher):
    # the next tile's HDF5 read + packing overlaps this tile's solve —
    # the reference's loadData-around-the-pipeline role.  The "load"
    # profiling phase therefore measures the prefetch STALL, not the
    # raw read.
    prefetch_cm = TilePrefetcher(cfg.dataset, [t0 for _, t0 in pairs],
                                 specs, cfg.tilesz, depth=1)
    # root span of the run; manual enter — the try/finally owns the exit
    run_span = tracer.span("fullbatch", kind="run", tiles=len(pairs))
    run_span.__enter__()
    try:
      prefetch = iter(prefetch_cm.__enter__())

      def _prepare(t0):
          """Load + coherency precompute for one tile.  All device
          work here is ASYNC jit dispatch, so calling this right after
          dispatching the previous tile's solve overlaps the coherency
          precompute with the device solve (the same software pipeline
          as the distributed driver; the reference's threaded per-tile
          precompute role, fullbatch_mode.cpp:371-388).  Coherencies
          depend only on u/v/w/freqs, so whitening (vis/mask-only) can
          be applied later without invalidating them."""
          t0_chk, tiles = next(prefetch)
          if t0_chk != t0:
              raise RuntimeError(
                  f"prefetch order mismatch: got tile {t0_chk}, "
                  f"expected {t0}"
              )
          full_ = tiles[0]
          data_ = None if cfg.simulation_mode else tiles[1]
          cdata_full_ = _cdata(
              full_, t0, fdelta=meta.deltaf / max(meta.nchan, 1)
          )
          cdata_ = None if cfg.simulation_mode else _cdata(data_, t0)
          return full_, data_, cdata_full_, cdata_

      def _ckpt_update(pi):
          """End-of-tile checkpoint: the tile's solution interval and
          residuals are durable, so (p, rng chain, results) at this
          boundary is a complete resume point."""
          if ckmgr is None:
              return
          ckmgr.update(
              resume_done + pi,
              {"p": np.asarray(p), "rng_key": np.asarray(rng_key),
               "results": np.asarray(results, np.float64).reshape(-1, 2)},
              tiles_done=resume_done + pi + 1, run_id=manifest.run_id,
          )

      prepared = None
      if pairs:
          with timer.phase("load+coh"):
              prepared = _prepare(pairs[0][1])
      for pi, (tile_no, t0) in enumerate(pairs):
        tic = time.time()
        tile_span = tracer.span("tile", kind="tile", tile=t0)
        tile_span.__enter__()
        full, data, cdata_full, cdata = prepared

        if cfg.simulation_mode:
            # predict / add / subtract (fullbatch_mode.cpp:536-591);
            # corrupt with the tile's own solution interval
            psim = None
            if jones_intervals is not None:
                ti = min(tile_no, jones_intervals.shape[0] - 1)
                psim = jnp.asarray(
                    jones_to_params(
                        jnp.asarray(jones_intervals[ti], cdtype)
                    ).reshape(M, nchunk_max, 8 * N)
                )
            out_vis = simulate_visibilities(
                full, cdata_full, psim, mode=cfg.simulation_mode,
                ignore_clusters=ignore_idx, ccid_index=ccid_index,
                rho=cfg.correction_rho, phase_only=cfg.phase_only_correction,
            )
            if pi + 1 < len(pairs):
                with timer.phase("load+coh"):
                    prepared = _prepare(pairs[pi + 1][1])
            ds.write_tile(t0, np.asarray(mat_of_flat(out_vis)), column="model")
            if elog is not None:
                elog.emit("tile_simulated", tile=t0,
                          seconds=time.time() - tic,
                          phase_seconds=timer.tile_timings())
            log(f"tile {t0}: simulated ({time.time()-tic:.1f}s)")
            tile_span.__exit__(None, None, None)
            continue

        if cfg.whiten:
            wts = jnp.sqrt(whiten_uv_weights(data.u, data.v, meta.freq0))
            data = data.replace(vis=data.vis * wts[None, None, :],
                                mask=data.mask * (wts[None, :] > 0))
        with timer.phase("solve"):
            # the whole SAGE/EM solve is one jit dispatch to the chip
            # (solvers/sage.py sagefit_packed)
            out = solve_tile(data, cdata, p, scfg, key=rng_key,
                             device=accel)  # async dispatch
        # overlap: next tile's load + coherency dispatch runs while the
        # device solves this tile
        if pi + 1 < len(pairs):
            with timer.phase("load+coh"):
                prepared = _prepare(pairs[pi + 1][1])
        with timer.phase("solve-wait"):
            res0, res1 = float(out.res_0), float(out.res_1)
        # divergence guard (fullbatch_mode.cpp:618-632)
        diverged = (
            not np.isfinite(res1) or res1 == 0.0 or res1 > cfg.res_ratio * res0
        )
        # out.p comes home as real numpy so all downstream eager math
        # (params_to_jones, residuals) stays on the CPU device
        p = pinit if diverged else jnp.asarray(np.asarray(out.p))
        # advance the tile RNG chain (the tile just solved used the
        # pre-advance key; a resumed run restores this chain from the
        # checkpoint, so resume == uninterrupted bit-for-bit)
        rng_key = jax.random.fold_in(rng_key, tile_no)
        if diverged:
            log(f"tile {t0}: diverged ({res0:.3e} -> {res1:.3e}), reset")

        # quality watchdog (obs/quality.py): chi^2 attribution + gain
        # health of this tile's solve -> solve_quality event + gauges,
        # escalating to quality_degraded / solver_diverged.  The
        # residual-ratio guard above joins the same verdict so
        # --abort-on-divergence covers both detectors.
        from sagecal_tpu.obs.quality import abort_if_diverged, check_and_emit

        q_verdict, q_reasons = "ok", []
        if out.quality is not None:
            # coh_dtype rides on every quality event so a degraded bf16
            # run is attributable to the precision knob at a glance
            q_verdict, q_reasons = check_and_emit(
                elog, out.quality, log=log, tile=t0, app="fullbatch",
                coh_dtype=scfg.coh_dtype,
            )
        if diverged:
            if q_verdict != "diverged" and elog is not None:
                elog.emit("solver_diverged",
                          reasons=[f"residual_ratio:{res0:.3e}->{res1:.3e}"],
                          tile=t0, app="fullbatch")
            q_verdict = "diverged"
            q_reasons = q_reasons + [
                f"residual_ratio:{res0:.3e}->{res1:.3e}"
            ]
        if cfg.abort_on_divergence:
            abort_if_diverged(elog, q_verdict, q_reasons,
                              tile=t0, app="fullbatch")

        # append solution columns (fullbatch_mode.cpp:595-605)
        jsol = np.asarray(params_to_jones(p)).reshape(M * nchunk_max, N, 2, 2)
        solio.append_solutions(sol_fh, jsol)

        if cfg.influence:
            # -i: influence function replaces the residuals
            # (fullbatch_mode.cpp:526-534 -> calculate_diagnostics_gpu)
            from sagecal_tpu.ops.diagnostics import influence_function

            infl = influence_function(full, cdata_full, p)  # host numpy
            # host-side flat -> (rows, F, 2, 2) (no device round trip)
            infl_mat = np.moveaxis(infl, -1, 0).reshape(
                infl.shape[-1], infl.shape[0], 2, 2
            )
            ds.write_tile(t0, infl_mat, column="influence")
            log(f"tile {t0}: influence diagnostics written "
                f"({time.time()-tic:.1f}s)")
            results.append((float(out.res_0), float(out.res_1)))
            _ckpt_update(pi)
            tile_span.__exit__(None, None, None)
            continue

        if cfg.per_channel and meta.nchan > 1:
            # -b: per-channel joint-LBFGS re-fit from the averaged
            # solution, residuals per channel with each channel's own
            # solution (fullbatch_mode.cpp:453-499 doChan path)
            from sagecal_tpu.solvers.batchmode import bfgsfit_minibatch

            res_np = np.empty(
                (full.vis.shape[-1], meta.nchan, 2, 2),
                np.complex128 if cfg.use_f64 else np.complex64,
            )
            for c in range(meta.nchan):
                dc = full.replace(
                    vis=full.vis[c:c + 1],
                    mask=full.mask[c:c + 1],
                    freqs=full.freqs[c:c + 1],
                )
                cc = cdata_full._replace(coh=cdata_full.coh[:, c:c + 1])
                p_c, _ = bfgsfit_minibatch(
                    dc, cc, p, itmax=cfg.max_lbfgs, lbfgs_m=cfg.lbfgs_m,
                )
                res_c = calculate_residuals(
                    dc, cc, p_c, ccid_index=ccid_index,
                    rho=cfg.correction_rho,
                    phase_only=cfg.phase_only_correction,
                )
                res_np[:, c] = np.asarray(mat_of_flat(res_c))[:, 0]
            res = res_np
        else:
            # residuals on the full-channel data, optional correction
            with timer.phase("residual"):
                res = np.asarray(mat_of_flat(calculate_residuals(
                    full, cdata_full, p, ccid_index=ccid_index,
                    rho=cfg.correction_rho,
                    phase_only=cfg.phase_only_correction,
                )))
        with timer.phase("write"):
            ds.write_tile(t0, np.asarray(res), column=cfg.out_column)
        # warm-start accounting: gains carry tile-to-tile (temporal
        # smoothness), so iterations-to-converge per tile is the
        # measured win; gauge + tile_done field feed `diag prom` and
        # the bench's warm_start_speedup
        warm_start = bool(pi > 0 or resume_done > 0
                          or cfg.init_solutions)
        iters_tile = None
        conv_recs = sage_convergence_records(out.telemetry)
        if conv_recs:
            iters_tile = int(sum(int(r.get("iterations", 0))
                                 for r in conv_recs))
            from sagecal_tpu.obs.registry import get_registry

            get_registry().gauge_set(
                "tile_iterations_to_converge", iters_tile,
                help="summed solver iterations of this tile's solve "
                     "(warm starts shrink it)", tile=str(t0),
                warm_start=str(int(warm_start)))
        if elog is not None:
            for rec in conv_recs:
                elog.emit("cluster_convergence", tile=t0, **rec)
            elog.emit(
                "tile_done", tile=t0, res0=res0, res1=res1,
                mean_nu=float(out.mean_nu), diverged=bool(diverged),
                seconds=time.time() - tic,
                warm_start=warm_start, iterations=iters_tile,
                phase_seconds=timer.tile_timings(),
            )
        log(
            f"tile {t0}: residual {res0:.6f} -> {res1:.6f} "
            f"nu {float(out.mean_nu):.1f} ({time.time()-tic:.1f}s) "
            f"[{timer.tile_summary()}]"
        )
        results.append((res0, res1))
        _ckpt_update(pi)
        note_activity("tile", name=f"tile{t0}", seconds=time.time() - tic)
        tile_span.__exit__(None, None, None)

    except ContractViolation as e:
        # SAGECAL_CHECKIFY contract tripped mid-solve: flush the
        # structured contract_violation event + a run_aborted marker
        # into the log before the CLI maps the exception to exit 4
        if elog is not None:
            emit_contract_events(elog)
            elog.emit("run_aborted", reason="contract_violation",
                      fn=e.fn_name, detail=e.detail)
            elog.close()
            elog = None
        raise
    finally:
        # always reap the worker thread + its read handle, even when the
        # solve/write raises mid-loop; same for the transfer audit (its
        # counts survive exit) and the XLA trace
        prefetch_cm.__exit__(None, None, None)
        audit.__exit__(None, None, None)
        trace_cm.__exit__(None, None, None)
        run_span.__exit__(None, None, None)
        close_tracer()  # writes trace.json alongside the span JSONL
    log(timer.run_summary())
    if elog is not None:
        emit_perf_events(elog)
        audit.emit(elog)
        # contract_unsupported markers (checkify skipped a wrapper) are
        # worth keeping even in clean runs
        emit_contract_events(elog)
        elog.emit("run_done", n_tiles=len(results),
                  phase_totals=dict(timer.totals))
        elog.close()
        unregister_event_log(elog)
    dump_memory_profile()
    if sol_fh:
        sol_fh.close()
    if ckmgr is not None:
        # persist the final boundary even with a sparse cadence, then
        # unhook from the crash handlers (run is complete)
        ckmgr.flush()
        ckmgr.close()
    ds.close()
    # success path only: leaves the final "closed" heartbeat; a crash
    # keeps the recorder alive for the excepthook's dump
    close_flight_recorder()
    return results
