"""Stochastic minibatch calibration (bandpass mode) + in-process
band-consensus ADMM.

Redesign of ``run_minibatch_calibration``
(``/root/reference/src/MS/minibatch_mode.cpp:47``) and
``run_minibatch_consensus_calibration`` (``minibatch_consensus_mode.cpp:47``):
channels split into ``bands`` mini-bands each with its own solution,
``epochs`` x ``minibatches`` passes over time with LBFGS curvature
memory persisting across batches, and (consensus mode) ADMM coupling of
the per-band solutions through frequency polynomials — the single-node
rehearsal of the distributed mesh mode, with bands in place of MPI
workers (minibatch_consensus_mode.cpp:359-363,455-606).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu.apps.config import RunConfig
from sagecal_tpu.core.types import identity_jones, jones_to_params, params_to_jones
from sagecal_tpu.io import solutions as solio
from sagecal_tpu.io.dataset import VisDataset
from sagecal_tpu.io.skymodel import load_sky
from sagecal_tpu.ops.residual import calculate_residuals
from sagecal_tpu.parallel import consensus
from sagecal_tpu.solvers.batchmode import (
    bfgsfit_minibatch,
    bfgsfit_minibatch_consensus,
)
from sagecal_tpu.solvers.sage import build_cluster_data


def _band_slices(nchan: int, bands: int):
    """Channel ranges per mini-band (minibatch_mode.cpp:355 logic:
    near-equal splits)."""
    edges = np.linspace(0, nchan, bands + 1).astype(int)
    return [(int(edges[i]), int(edges[i + 1])) for i in range(bands)]


def _band_visdata(full, c0, c1):
    """Restrict a multichannel VisData to channels [c0, c1) — the flat
    layout's channel axis is leading."""
    return full.replace(
        vis=full.vis[c0:c1],
        mask=full.mask[c0:c1],
        freqs=full.freqs[c0:c1],
    )


def run_minibatch(cfg: RunConfig, log=print):
    """Epochs x minibatches over time, one solution per mini-band.
    Returns per-band final (res_0, res_1).

    Thin exception-safe shell: the XLA trace (``SAGECAL_PROFILE_DIR``)
    and the transfer audit (``SAGECAL_TRANSFER_AUDIT=1``) are opened
    here so a crash mid-epoch still flushes a loadable trace and
    restores stderr."""
    from sagecal_tpu.obs.perf import (
        TransferAudit,
        enable_persistent_compilation_cache,
    )
    from sagecal_tpu.utils.profiling import trace

    enable_persistent_compilation_cache()
    from sagecal_tpu.utils.platform import accelerator

    accelerator()  # no TPU and no explicit CPU choice: refuse to run
    audit = TransferAudit()
    with trace(), audit:
        return _run_minibatch(cfg, log, audit)


def _run_minibatch(cfg: RunConfig, log, audit):
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
    bands = _band_slices(meta.nchan, cfg.bands)
    consensus_mode = cfg.admm_iters > 0 and cfg.bands > 1
    # bounded-staleness consensus (--consensus-staleness K): bands
    # refresh their Gram contributions on deterministic work-weighted
    # periods instead of every round; K=0 keeps periods of all-ones and
    # the unified round engine below reproduces the synchronous
    # trajectory bit-for-bit (tests/test_async_consensus.py)
    K_stale = max(int(cfg.consensus_staleness), 0)
    sdisc = float(cfg.consensus_staleness_discount)
    async_mode = consensus_mode and (K_stale > 0 or sdisc != 1.0)

    eye = jones_to_params(identity_jones(N, cdtype))
    p_bands = [
        jnp.broadcast_to(eye, (M, nchunk_max, 8 * N)).astype(dtype)
        for _ in bands
    ]
    mem_bands = [None] * len(bands)

    # consensus setup over band center frequencies
    # (minibatch_consensus_mode.cpp:359-363)
    if consensus_mode:
        bfreqs = np.asarray(
            [np.mean(meta.freqs[c0:c1]) for c0, c1 in bands]
        )
        B = consensus.setup_polynomials(
            bfreqs, meta.freq0, cfg.npoly, cfg.poly_type
        )
        if cfg.rho_file:
            # -G per-cluster regularization (read_arho_fromfile)
            from sagecal_tpu.io.skymodel import read_cluster_rho

            rho_m, _ = read_cluster_rho(cfg.rho_file, cdefs)
            rho = jnp.broadcast_to(
                jnp.asarray(rho_m, dtype), (len(bands), M)
            )
        else:
            rho = jnp.full((len(bands), M), cfg.admm_rho, dtype)
        Bii = consensus.find_prod_inverse_full(
            jnp.asarray(B, dtype), rho
        )
        K = nchunk_max * 8 * N
        Z = jnp.zeros((M, cfg.npoly, K), dtype)
        Y_bands = [jnp.zeros_like(p_bands[0]) for _ in bands]
        # the async state: per-band stored Gram terms + ages + the
        # global round counter (persists ACROSS minibatches so the
        # refresh schedule is one deterministic sequence; checkpointed
        # whole, so --resume replays it exactly)
        from sagecal_tpu.parallel.async_consensus import (
            StalenessLedger, band_active, refresh_periods,
        )

        ledger = StalenessLedger(len(bands), (M, cfg.npoly, K), dtype)

    # minibatch time ranges
    ntime = meta.ntime
    nb = max(cfg.minibatches, 1)
    tedges = np.linspace(0, ntime, nb + 1).astype(int)

    robust_nu = None
    from sagecal_tpu.solvers.sage import _ROBUST_MODES

    if cfg.solver_mode in _ROBUST_MODES:
        robust_nu = 0.5 * (cfg.nulow + cfg.nuhigh)

    # telemetry: per-minibatch progress + (consensus mode) per-ADMM-round
    # band primal residuals land in the JSONL event log
    from sagecal_tpu.obs import RunManifest, default_event_log

    manifest = RunManifest.collect(
        app="minibatch", bands=len(bands), epochs=cfg.epochs,
        minibatches=nb, consensus=consensus_mode,
        solver_mode=cfg.solver_mode, n_clusters=M, n_stations=N,
    )
    elog = default_event_log(manifest=manifest)
    # crash forensics + tracing (same lifecycle as the other apps):
    # excepthook/SIGTERM flush the event log, the flight recorder
    # heartbeats, spans correlate on the manifest run_id
    from sagecal_tpu.obs.flight import (
        close_flight_recorder,
        get_flight_recorder,
        install_crash_handlers,
        note_activity,
        register_event_log,
        unregister_event_log,
    )
    from sagecal_tpu.obs.trace import (
        close_tracer,
        configure_tracer,
        get_tracer,
        straggler_stats,
    )

    install_crash_handlers()
    if elog is not None:
        register_event_log(elog)
    get_flight_recorder(run_id=manifest.run_id)
    configure_tracer(run_id=manifest.run_id)
    tracer = get_tracer()

    # elastic execution (sagecal_tpu/elastic/): checkpoints at
    # (epoch, minibatch) boundaries carry p_bands (+ consensus Z and the
    # Y duals) AND each band's LBFGS curvature memory (``mem{bi}.*``
    # flattened-pytree entries), so a resumed run is bit-for-bit
    # identical to an uninterrupted one — the same elastic contract as
    # the fullbatch and distributed drivers (tests/test_elastic.py).
    # Checkpoints from builds that predate the memory entries still
    # resume (the memory rebuilds over the next few batches; convergent
    # but not bit-exact).
    ckmgr = None
    resume_done = 0  # completed (epoch, minibatch) steps
    if cfg.resume or cfg.checkpoint_every > 0:
        import os as _os

        from sagecal_tpu.elastic import CheckpointManager, config_fingerprint

        fingerprint = config_fingerprint(
            app="minibatch",
            dataset=_os.path.abspath(cfg.dataset),
            sky_model=_os.path.abspath(cfg.sky_model),
            cluster_file=_os.path.abspath(cfg.cluster_file),
            nstations=N, ntime=ntime, nchan=meta.nchan,
            bands=cfg.bands, epochs=cfg.epochs, minibatches=nb,
            admm_iters=cfg.admm_iters, npoly=cfg.npoly,
            poly_type=cfg.poly_type, admm_rho=cfg.admm_rho,
            consensus_staleness=cfg.consensus_staleness,
            consensus_staleness_discount=cfg.consensus_staleness_discount,
            solver_mode=cfg.solver_mode, max_lbfgs=cfg.max_lbfgs,
            lbfgs_m=cfg.lbfgs_m, nulow=cfg.nulow, nuhigh=cfg.nuhigh,
            use_f64=cfg.use_f64, in_column=cfg.in_column,
        )
        ckmgr = CheckpointManager(
            cfg.checkpoint_dir or f"{cfg.out_solutions}.ckpt",
            fingerprint, "minibatch", every=max(cfg.checkpoint_every, 1),
            elog=elog, log=log,
        )
        if cfg.resume:
            found = ckmgr.resume()
            if found is not None:
                rmeta, rarrs, _rpath = found
                resume_done = int(rmeta["steps_done"])
                p_bands = [jnp.asarray(a, dtype)
                           for a in rarrs["p_bands"]]
                if consensus_mode:
                    Z = jnp.asarray(rarrs["Z"], dtype)
                    Y_bands = [jnp.asarray(a, dtype)
                               for a in rarrs["Y_bands"]]
                    if StalenessLedger.present(rarrs):
                        # async runs: the staleness ledger (stored Gram
                        # terms + ages + round counter) is part of the
                        # trajectory — restore it so the refresh
                        # schedule continues where the killed run was
                        ledger = StalenessLedger.from_arrays(
                            rarrs, dtype=dtype)
                # LBFGS curvature memory (guarded per band: absent in
                # checkpoints from older builds, and a band that never
                # solved has none) — restoring it is what makes the
                # resumed trajectory bit-exact
                from sagecal_tpu.elastic.checkpoint import unflatten_state
                from sagecal_tpu.solvers.lbfgs import LBFGSMemory

                mem_template = LBFGSMemory.init(
                    M * nchunk_max * 8 * N, cfg.lbfgs_m, dtype)
                for bi in range(len(bands)):
                    if f"mem{bi}.0" in rarrs:
                        mem_bands[bi] = unflatten_state(
                            f"mem{bi}", rarrs, mem_template)

    def solve_band(bi, data_band, cdata_band):
        p1, mem1 = bfgsfit_minibatch(
            data_band, cdata_band, p_bands[bi],
            memory=mem_bands[bi], itmax=cfg.max_lbfgs,
            lbfgs_m=cfg.lbfgs_m, robust_nu=robust_nu,
        )
        return p1, mem1

    run_span = tracer.span("minibatch", kind="run", bands=len(bands),
                           epochs=max(cfg.epochs, 1), minibatches=nb,
                           consensus=consensus_mode)
    run_span.__enter__()
    for epoch in range(max(cfg.epochs, 1)):
        for mb in range(nb):
            step = epoch * nb + mb
            if step < resume_done:
                continue  # completed before the checkpoint we resumed
            t0, t1 = int(tedges[mb]), int(tedges[mb + 1])
            if t1 <= t0:
                continue
            tic = time.time()
            mb_span = tracer.span("batch", kind="batch", epoch=epoch,
                                  minibatch=mb)
            mb_span.__enter__()
            full = ds.load_tile(t0, t1 - t0, average_channels=False,
                                min_uvcut=cfg.min_uvcut,
                                max_uvcut=cfg.max_uvcut, dtype=dtype,
                                column=cfg.in_column)
            fd = meta.deltaf / max(meta.nchan, 1)
            if not consensus_mode:
                for bi, (c0, c1) in enumerate(bands):
                    db = _band_visdata(full, c0, c1)
                    cb = build_cluster_data(db, clusters, nchunks, fdelta=fd,
                            shapelets=shapelets)
                    p_bands[bi], mem_bands[bi] = solve_band(bi, db, cb)
            else:
                # band ADMM within this minibatch
                # (minibatch_consensus_mode.cpp:455-606)
                dbs, cbs = [], []
                for (c0, c1) in bands:
                    db = _band_visdata(full, c0, c1)
                    dbs.append(db)
                    cbs.append(build_cluster_data(db, clusters, nchunks,
                                                  fdelta=fd,
                                                  shapelets=shapelets))
                # consensus watchdog bookkeeping: per-round per-band
                # primal residuals + global dual residual trajectories
                track = (cfg.verbose or elog is not None
                         or cfg.abort_on_divergence)
                pres_traj, dual_traj = [], []
                # unlike the mesh ADMM (one jitted program, synthetic
                # attribution) this per-band loop IS host-visible, so
                # band spans are REAL wall times; blocking per band only
                # when tracing is on keeps the traced timings honest and
                # the untraced path's dispatch pipelining untouched
                band_secs = [0.0] * len(bands)
                # deterministic refresh periods from this minibatch's
                # unflagged-row counts (the straggler signal itself):
                # heavy bands refresh less often under a staleness
                # bound, so a round stops tracking the slowest band;
                # K=0 -> all-ones periods -> the synchronous loop
                band_rows = [float(jnp.sum(db.mask)) for db in dbs]
                periods = refresh_periods(band_rows, K_stale)
                if async_mode and elog is not None:
                    elog.emit("async_schedule", epoch=epoch, minibatch=mb,
                              staleness=K_stale, discount=sdisc,
                              periods=[int(x) for x in periods],
                              band_rows=band_rows,
                              round_index=ledger.round_index)
                for admm in range(cfg.admm_iters):
                    Z_old = Z
                    active = band_active(ledger.round_index, periods)
                    # a band with no stored Gram term yet must solve
                    # (cold start / first visit) — starvation-free
                    active = active | (ledger.ages < 0)
                    round_span = tracer.span("admm.round",
                                             kind="admm_round", round=admm,
                                             epoch=epoch, minibatch=mb)
                    round_span.__enter__()
                    for bi in range(len(bands)):
                        if not active[bi]:
                            continue
                        BZ = consensus.bz_for_freq(
                            Z, jnp.asarray(B[bi], dtype)
                        ).reshape(M, nchunk_max, 8 * N)
                        t_band = time.perf_counter()
                        with tracer.span("admm.band", kind="band", band=bi,
                                         lane=f"band{bi}", round=admm):
                            p1, mem1 = bfgsfit_minibatch_consensus(
                                dbs[bi], cbs[bi], p_bands[bi], Y_bands[bi],
                                BZ, rho[bi], memory=mem_bands[bi],
                                itmax=cfg.max_lbfgs, lbfgs_m=cfg.lbfgs_m,
                                robust_nu=robust_nu,
                            )
                            if tracer.enabled:
                                p1 = jax.block_until_ready(p1)
                        if tracer.enabled:
                            band_secs[bi] += time.perf_counter() - t_band
                        p_bands[bi], mem_bands[bi] = p1, mem1
                        Yhat = Y_bands[bi] + rho[bi][:, None, None] * p1
                        ledger.record(bi, consensus.accumulate_z_term(
                            jnp.asarray(B[bi], dtype),
                            Yhat.reshape(M, -1),
                        ))
                    # Z solve over EVERY band's freshest stored term,
                    # rho-discounted by age (discount**age, dropped
                    # beyond the bound); all-fresh weights are exactly
                    # 1 so the synchronous case reuses the precomputed
                    # Bii and stays bit-identical to the classic loop
                    ages_eff = np.where(active, 0, ledger.ages)
                    w_z = np.where(ages_eff < 0, 0.0,
                                   sdisc ** np.maximum(ages_eff, 0))
                    if K_stale > 0:
                        w_z = np.where(ages_eff > K_stale, 0.0, w_z)
                    if not np.any(w_z > 0):
                        w_z = np.ones_like(w_z)
                    zacc = jnp.zeros((M, cfg.npoly, nchunk_max * 8 * N),
                                     dtype)
                    for bi in range(len(bands)):
                        if w_z[bi] == 0.0:
                            continue
                        term = jnp.asarray(ledger.zterms[bi], dtype)
                        if w_z[bi] != 1.0:
                            term = jnp.asarray(w_z[bi], dtype) * term
                        zacc = zacc + term
                    if np.all(w_z == 1.0):
                        Bii_r = Bii
                    else:
                        Bii_r = consensus.find_prod_inverse_full(
                            jnp.asarray(B, dtype),
                            jnp.asarray(w_z, dtype)[:, None] * rho,
                        )
                    Z = consensus.update_global_z(zacc, Bii_r)
                    for bi in range(len(bands)):
                        if not active[bi]:
                            # an idle band keeps its dual: it did not
                            # re-solve against this round's Z, so a
                            # dual ascent step here would double-count
                            # its stale contribution
                            continue
                        BZ1 = consensus.bz_for_freq(
                            Z, jnp.asarray(B[bi], dtype)
                        ).reshape(M, nchunk_max, 8 * N)
                        Y_bands[bi] = (
                            Y_bands[bi]
                            + rho[bi][:, None, None] * (p_bands[bi] - BZ1)
                        )
                    ledger.advance()
                    round_span.__exit__(None, None, None)
                    if track:
                        # per-band scaled primal residuals (the same
                        # normalization the mesh driver logs,
                        # consensus.admm_primal_residual)
                        pres_band = [
                            float(consensus.admm_primal_residual(
                                p_bands[bi].ravel(),
                                consensus.bz_for_freq(
                                    Z, jnp.asarray(B[bi], dtype)
                                ).ravel(),
                            ))
                            for bi in range(len(bands))
                        ]
                        dres = float(consensus.admm_dual_residual(Z, Z_old))
                        pres_traj.append(pres_band)
                        dual_traj.append(dres)
                        if elog is not None:
                            elog.emit(
                                "admm_round", epoch=epoch, minibatch=mb,
                                admm_iter=admm, primal_res=pres_band,
                                dual_res=dres,
                            )
                        if cfg.verbose:
                            log(f"  admm {admm}: primal "
                                f"{sum(pres_band):.4e} dual {dres:.4e}")
                if tracer.enabled and len(bands) > 1:
                    # straggler gauges on the MEASURED per-band seconds
                    # (same gauge names as the mesh driver's attributed
                    # ones, so dashboards join across modes)
                    from sagecal_tpu.obs.registry import get_registry

                    stats = straggler_stats(band_secs)
                    reg = get_registry()
                    for bi, s in enumerate(band_secs):
                        reg.gauge_set(
                            "admm_band_seconds", s,
                            help="measured per-band seconds of this "
                                 "minibatch's band ADMM", band=str(bi))
                    reg.gauge_set(
                        "admm_straggler_ratio", stats["ratio"],
                        help="slowest/median measured band seconds of "
                             "the band ADMM")
                    reg.gauge_set(
                        "admm_band_skew", stats["skew"],
                        help="(max-mean)/mean measured band seconds")
                    if stats["detected"]:
                        if elog is not None:
                            elog.emit("straggler_detected", epoch=epoch,
                                      minibatch=mb, band=stats["argmax"],
                                      ratio=stats["ratio"],
                                      skew=stats["skew"],
                                      band_seconds=band_secs,
                                      threshold=stats["threshold"])
                        log(f"epoch {epoch} minibatch {mb}: straggler "
                            f"band {stats['argmax']} "
                            f"({stats['ratio']:.2f}x median)")
                if pres_traj:
                    # ADMM watchdog: a band whose primal residual grows
                    # away from its trajectory minimum (or goes
                    # non-finite) marks this minibatch's consensus as
                    # diverged (obs/quality.assess_consensus)
                    from sagecal_tpu.obs.quality import (
                        abort_if_diverged, assess_consensus,
                    )

                    pr = np.asarray(pres_traj)
                    du = np.tile(np.asarray(dual_traj)[:, None],
                                 (1, pr.shape[1]))
                    verdict, reasons, health = assess_consensus(
                        pr, du,
                        ages=(np.maximum(ledger.ages, 0)
                              if async_mode else None),
                        staleness=(K_stale if async_mode else None),
                    )
                    if elog is not None:
                        elog.emit(
                            "consensus_health", epoch=epoch, minibatch=mb,
                            verdict=verdict, reasons=reasons,
                            ratio=health["ratio"], trend=health["trend"],
                        )
                        if verdict == "diverged":
                            elog.emit("solver_diverged", reasons=reasons,
                                      epoch=epoch, minibatch=mb,
                                      app="minibatch")
                    if verdict != "ok":
                        log(f"consensus watchdog: {verdict} "
                            f"({', '.join(reasons)})")
                    if cfg.abort_on_divergence:
                        abort_if_diverged(elog, verdict, reasons,
                                          epoch=epoch, minibatch=mb,
                                          app="minibatch")
            note_activity("minibatch", name=f"e{epoch}mb{mb}",
                          seconds=time.time() - tic)
            mb_span.__exit__(None, None, None)
            if elog is not None:
                elog.emit("minibatch_done", epoch=epoch, minibatch=mb,
                          t0=t0, t1=t1, seconds=time.time() - tic)
            if ckmgr is not None:
                from sagecal_tpu.elastic.checkpoint import flatten_state

                arrs = {"p_bands": np.stack(
                    [np.asarray(p) for p in p_bands])}
                if consensus_mode:
                    arrs["Z"] = np.asarray(Z)
                    arrs["Y_bands"] = np.stack(
                        [np.asarray(y) for y in Y_bands])
                    if async_mode:
                        # ages + stored Gram terms + round counter: the
                        # complete async trajectory state, so --resume
                        # replays the exact refresh schedule
                        arrs.update(ledger.to_arrays())
                for bi, mem in enumerate(mem_bands):
                    if mem is not None:
                        arrs.update(flatten_state(f"mem{bi}", mem))
                ckmgr.update(step, arrs, steps_done=step + 1,
                             run_id=manifest.run_id)
            log(f"epoch {epoch} minibatch {mb}: "
                f"({time.time()-tic:.1f}s)")

    if ckmgr is not None:
        ckmgr.flush()
        ckmgr.close()
    # final residuals per band (minibatch_mode.cpp final epoch), streamed
    # tile-by-tile with the same time edges as the training loop — the
    # reference streams per tile; loading the whole observation at once
    # would defeat the tile-streaming design for realistic sizes
    fd = meta.deltaf / max(meta.nchan, 1)
    acc = [[0.0, 0.0] for _ in bands]  # per band: [sum|vis|^2, sum|res|^2]
    for mb in range(nb):
        t0, t1 = int(tedges[mb]), int(tedges[mb + 1])
        if t1 <= t0:
            continue
        full = ds.load_tile(t0, t1 - t0, average_channels=False, dtype=dtype,
                            column=cfg.in_column)
        from sagecal_tpu.core.types import mat_of_flat

        res_all = np.array(np.asarray(mat_of_flat(full.vis)), copy=True)
        for bi, (c0, c1) in enumerate(bands):
            db = _band_visdata(full, c0, c1)
            cb = build_cluster_data(db, clusters, nchunks, fdelta=fd,
                            shapelets=shapelets)
            res = calculate_residuals(db, cb, p_bands[bi])
            res_all[:, c0:c1] = np.asarray(mat_of_flat(res))
            acc[bi][0] += float(jnp.sum(jnp.abs(db.vis) ** 2))
            acc[bi][1] += float(jnp.sum(jnp.abs(res) ** 2))
        ds.write_tile(t0, res_all, column=cfg.out_column)
    results = []
    for bi in range(len(bands)):
        r0, r1 = float(np.sqrt(acc[bi][0])), float(np.sqrt(acc[bi][1]))
        results.append((r0, r1))
        if elog is not None:
            elog.emit("band_residual", band=bi, res0=r0, res1=r1)
        log(f"band {bi}: residual {r0:.4f} -> {r1:.4f}")
    if elog is not None:
        from sagecal_tpu.obs.contracts import emit_contract_events
        from sagecal_tpu.obs.perf import emit_perf_events

        # close the audit now (idempotent; the shell's exit is then a
        # no-op) so its counts land in this run's event log
        audit.__exit__(None, None, None)
        emit_perf_events(elog)
        audit.emit(elog)
        emit_contract_events(elog)
        elog.emit("run_done", n_bands=len(bands))
        elog.close()
        unregister_event_log(elog)
    run_span.__exit__(None, None, None)
    close_tracer()

    # write per-band solutions
    with open(cfg.out_solutions, "w") as fh:
        solio.write_header(fh, meta.freq0, meta.deltaf, meta.deltat / 60.0,
                           N, M, M * nchunk_max)
        for pb in p_bands:
            jsol = np.asarray(params_to_jones(pb)).reshape(
                M * nchunk_max, N, 2, 2
            )
            solio.append_solutions(fh, jsol)
    ds.close()
    # success path only: leaves the final "closed" heartbeat; a crash
    # keeps the recorder alive for the excepthook's dump
    close_flight_recorder()
    return results
