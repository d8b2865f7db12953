"""Distributed multi-band calibration driver: the ``sagecal-mpi`` binary.

Redesign of the MPI master/slave application pair
(``/root/reference/src/MPI/sagecal_master.cpp:41-1316`` /
``sagecal_slave.cpp``): one SPMD program over a ``('freq',)`` device
mesh replaces the rank-0 master + per-MS slaves.  The per-timeslot tile
loop (master :694-), metadata consistency checks (:238-287), fratio
scaling of rho (:709-723), the consensus-ADMM iteration
(:func:`sagecal_tpu.parallel.mesh.make_admm_mesh_fn`), the global-Z
solution file (:499-533, :1165-1175), per-band solution files and
residual write-back (slave :959-979) all live here; the MPI tag
protocol (proto.h) has no equivalent because the z-step psum and the
manifold-average all_gather are compiled collectives.

Multi-host: pass ``multihost=True`` to call
``jax.distributed.initialize()`` before touching devices — the same
mesh code then spans hosts over DCN (each host feeds its local bands).
"""

from __future__ import annotations

import glob
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sagecal_tpu.apps.config import RunConfig
from sagecal_tpu.core.types import (
    identity_jones,
    jones_to_params,
    params_to_jones,
)
from sagecal_tpu.io import solutions as solio
from sagecal_tpu.io.dataset import TilePrefetcher, VisDataset
from sagecal_tpu.io.skymodel import load_sky, read_cluster_rho
from sagecal_tpu.ops.rime import ST_POINT, ST_SHAPELET
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.mesh import (
    SpatialConfig,
    make_admm_mesh_fn,
    make_band_prep_fn,
    make_band_residual_fn,
    stack_for_mesh,
    stack_sources,
)
from sagecal_tpu.solvers.lm import LMConfig
from sagecal_tpu.solvers.sage import build_cluster_data


def write_global_z_header(fh, freq0_hz, npoly, nstations, nclusters, neff):
    """Global-Z solution file header (sagecal_master.cpp:515-517)."""
    fh.write("# solution file (Z) created by SAGECal\n")
    fh.write("# reference_freq(MHz) polynomial_order stations clusters "
             "effective_clusters\n")
    fh.write(f"{freq0_hz * 1e-6:.6f} {npoly} {nstations} {nclusters} {neff}\n")


def append_global_z(fh, Z, nstations, npoly, nchunk_max, flush: bool = True):
    """One timeslot's Z rows (sagecal_master.cpp:1165-1175): row p of
    N*8*Npoly values, effective-cluster columns in REVERSE order.

    Z: (M, Npoly, nchunk_max*8N) real.

    Crash-safety contract mirrors :func:`sagecal_tpu.io.solutions.
    append_solutions`: the whole timeslot is one buffered write + flush,
    so a kill between timeslots never leaves a torn interval —
    :func:`sagecal_tpu.io.solutions.validate_global_z` truncates the
    rare mid-write tear on resume."""
    M = Z.shape[0]
    n8 = 8 * nstations
    # effective cluster (m, c) -> (Npoly*8N,) with p = poly*8N + i
    Zb = np.asarray(Z).reshape(M, npoly, nchunk_max, n8)
    cols = [
        Zb[m, :, c, :].reshape(-1)
        for m in range(M) for c in range(nchunk_max)
    ]
    cols = cols[::-1]  # reverse effective-cluster ordering
    rows = npoly * n8
    buf = "".join(
        f"{p} " + " ".join(f"{col[p]:e}" for col in cols) + "\n"
        for p in range(rows)
    )
    fh.write(buf)
    if flush:
        fh.flush()


def _check_band_consistency(metas, log):
    """The master's metadata validation (sagecal_master.cpp:238-287):
    all bands must agree on N / nbase / timeslot count, and on the
    channel count (the bands' tiles are stacked at full channel
    resolution)."""
    n0, nb0, nt0 = metas[0].nstations, metas[0].nbase, metas[0].ntime
    for i, m in enumerate(metas[1:], 1):
        if (m.nstations, m.nbase) != (n0, nb0):
            raise ValueError(
                f"band {i}: station/baseline layout mismatch "
                f"({m.nstations},{m.nbase}) != ({n0},{nb0})"
            )
        if m.nchan != metas[0].nchan:
            raise ValueError(
                f"band {i}: {m.nchan} channels != {metas[0].nchan}")
        if m.ntime != nt0:
            log(f"warning: band {i} has {m.ntime} timeslots != {nt0}; "
                f"using the minimum")
    return min(m.ntime for m in metas)


def read_band_solutions(prefix, nbands, M, nchunk_max, N):
    """The last interval of each band's solution file
    ``{prefix}.band{i}`` (the files ``-p prefix`` writes) as
    (nbands, M, nchunk_max, 8N) parameters: the ``-q`` warm start of a
    multi-band run."""
    from sagecal_tpu.utils.platform import cpu_device

    out = []
    for i in range(nbands):
        path = f"{prefix}.band{i}"
        _, js = solio.read_solutions(path)
        if js.shape[1:3] != (M * nchunk_max, N):
            raise ValueError(
                f"{path}: {js.shape[1]} clusters x {js.shape[2]} stations, "
                f"expected {M * nchunk_max} x {N}")
        with jax.default_device(cpu_device()):
            out.append(np.asarray(jones_to_params(js[-1])).reshape(
                M, nchunk_max, 8 * N))
    return np.stack(out)


def _emit_admm_attribution(tracer, elog, log, t0, admm_seconds,
                           admm_start_unix, fratios, nf, nadmm, nslots,
                           plain_emiter, max_emiter, cluster_groups=1):
    """Host-side straggler attribution for one tile's mesh ADMM window.

    The whole nadmm loop is ONE jitted shard_map dispatch, so per-band /
    per-round wall time is not observable from the host; instead the
    measured dispatch->block window is distributed over per-band work
    weights (unflagged-row fractions — the same fratio that scales rho)
    and the static per-round work model
    (:func:`sagecal_tpu.parallel.admm.round_work_weights`) as SYNTHETIC
    child spans that sum exactly to the window.  Straggler gauges
    (slowest/median ratio, skew) + a ``straggler_detected`` event fire
    on the attributed seconds."""
    from sagecal_tpu.obs.registry import get_registry
    from sagecal_tpu.obs.trace import band_attribution, straggler_stats
    from sagecal_tpu.parallel.admm import round_work_weights

    weights = [float(f) for f in fratios[:nf]]
    band_secs = band_attribution(admm_seconds, weights)
    stats = straggler_stats(band_secs)
    if tracer.enabled:
        admm_id = tracer.add_span(
            "admm", admm_seconds, start_unix=admm_start_unix,
            kind="admm", tile=t0, nadmm=nadmm, nf=nf)
        # per-round weights track each round's ACTIVE slot's unflagged
        # rows (slot_rows) — a flag-skewed band's rounds bill more of
        # the measured window instead of papering over the straggler
        rsecs = band_attribution(
            admm_seconds,
            round_work_weights(nadmm, nslots, plain_emiter, max_emiter,
                               slot_rows=weights,
                               cluster_groups=cluster_groups))
        r_start = admm_start_unix
        for r, s in enumerate(rsecs):
            tracer.add_span("admm.round", s, parent_id=admm_id,
                            start_unix=r_start, round=r, tile=t0,
                            synthetic=True, attribution="round-work-model")
            r_start += s
        for b, s in enumerate(band_secs):
            tracer.add_span("admm.band", s, parent_id=admm_id,
                            start_unix=admm_start_unix, band=b, tile=t0,
                            lane=f"band{b}", synthetic=True,
                            attribution="unflagged-rows")
    reg = get_registry()
    for b, s in enumerate(band_secs):
        reg.gauge_set("admm_band_seconds", s,
                      help="attributed per-band seconds of the last "
                           "mesh ADMM window", band=str(b))
    reg.gauge_set("admm_straggler_ratio", stats["ratio"],
                  help="slowest/median attributed band seconds of the "
                       "last mesh ADMM window")
    reg.gauge_set("admm_band_skew", stats["skew"],
                  help="(max-mean)/mean attributed band seconds")
    if stats["detected"]:
        if elog is not None:
            elog.emit("straggler_detected", tile=t0, band=stats["argmax"],
                      ratio=stats["ratio"], skew=stats["skew"],
                      band_seconds=band_secs,
                      threshold=stats["threshold"])
        log(f"tile {t0}: straggler band {stats['argmax']} "
            f"({stats['ratio']:.2f}x median attributed work)")
    return band_secs, stats


def run_distributed(
    cfg: RunConfig,
    datasets: Optional[Sequence[str]] = None,
    log=print,
    multihost: bool = False,
    nadmm: Optional[int] = None,
    spatial_n0: int = 0,
    spatial_beta: float = 0.01,
    spatial_mu: float = 1e-3,
    spatial_alpha: float = 0.0,
    spatial_cadence: int = 2,
    spatial_basis: str = "shapelet",
    spatial_diffuse_id: Optional[int] = None,
    spatial_gamma: float = 0.0,
    spatial_lam: float = 0.0,
    spatial_fista_maxiter: int = 30,
    mdl: bool = False,
    global_residual: bool = False,
    adaptive_rho: bool = True,
):
    """Calibrate a multi-band observation on the device mesh.

    ``datasets``: explicit band file list, or None to expand
    ``cfg.dataset`` as a glob (the reference's ``-f 'pattern'``,
    sagecal_master.cpp:60-224 MS discovery).  Returns per-tile lists of
    (dual_res, primal_res) traces.

    ``spatial_n0 > 0`` switches on spatial regularization inside the
    ADMM loop (the master's -U path); ``spatial_basis`` selects
    shapelet or spherical-harmonic modes (master:359-397);
    ``spatial_beta <= 0`` uses the master's auto scale.

    ``spatial_diffuse_id``: cluster id whose (all-shapelet) coherencies
    are re-predicted from the diffuse-constrained spatial model — the
    find_initial_spatial / Zspat_diff / Psi chain (master:649-926,
    slave:670-698) with ``spatial_gamma``/``spatial_lam`` as
    (sp_gamma, sh_lambda).  The refresh runs between tiles (the
    reference refreshes every admm_cadence iterations inside the loop;
    we keep the whole Nadmm loop in one jit program and apply the
    refreshed coherencies to the next tile).

    ``mdl=True`` scores consensus polynomial orders 1..Npoly by
    AIC/MDL on each tile's rho-scaled solutions and logs the winner
    (the master's -M path, sagecal_master.cpp:991-993).
    """
    from sagecal_tpu.obs.perf import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    if multihost:
        jax.distributed.initialize()  # before any backend query
    from sagecal_tpu.utils.platform import accelerator

    accelerator()  # no TPU and no explicit CPU choice: refuse to run
    if datasets is None:
        datasets = sorted(glob.glob(cfg.dataset))
    if not datasets:
        raise ValueError(f"no band datasets match {cfg.dataset!r}")
    nadmm = nadmm if nadmm is not None else max(cfg.admm_iters, 2)
    dtype = np.float64 if cfg.use_f64 else np.float32

    handles: List[VisDataset] = [VisDataset(p, "r+") for p in datasets]
    open_files: List = []
    try:
        return _run_distributed_inner(
            cfg, datasets, handles, open_files, log, nadmm, dtype,
            spatial_n0, spatial_beta, spatial_mu, spatial_alpha,
            spatial_cadence, spatial_basis, spatial_diffuse_id,
            spatial_gamma, spatial_lam, mdl, spatial_fista_maxiter,
            global_residual, adaptive_rho,
        )
    finally:
        for fh in open_files:
            try:
                fh.close()
            except Exception:
                pass
        for h in handles:
            try:
                h.close()
            except Exception:
                pass


def _run_distributed_inner(
    cfg, datasets, handles, open_files, log, nadmm, dtype,
    spatial_n0, spatial_beta, spatial_mu, spatial_alpha, spatial_cadence,
    spatial_basis="shapelet", spatial_diffuse_id=None, spatial_gamma=0.0,
    spatial_lam=0.0, mdl=False, spatial_fista_maxiter=30,
    global_residual=False, adaptive_rho=True,
):
    metas = [h.meta for h in handles]
    ntime = _check_band_consistency(metas, log)
    meta0 = metas[0]
    N = meta0.nstations
    freqs = np.asarray([m.freq0 for m in metas])
    freq0 = float(np.mean(freqs))

    clusters, cdefs, shapelets = load_sky(
        cfg.sky_model, cfg.cluster_file, meta0.ra0, meta0.dec0, dtype=dtype,
        three_term_spectra=None if cfg.sky_format < 0 else bool(cfg.sky_format),
    )
    M = len(clusters)
    nchunks = [cd.nchunk for cd in cdefs]
    nchunk_max = max(nchunks)
    n8 = 8 * N

    # per-cluster rho (and spatial alpha) from the -G file when given
    if cfg.rho_file:
        rho_m, alpha_m = read_cluster_rho(
            cfg.rho_file, cdefs, spatialreg=True
        )
    else:
        rho_m = np.full((M,), cfg.admm_rho)
        alpha_m = np.full((M,), spatial_alpha)

    # pad band count to a mesh multiple with zero-weight bands
    devs = jax.devices()
    Nf = len(datasets)
    ndev = min(len(devs), Nf)
    Nf_pad = -(-Nf // ndev) * ndev
    mesh = Mesh(np.array(devs[:ndev]), ("freq",))
    log(f"distributed: {Nf} bands on {ndev} devices"
        + (f" (padded to {Nf_pad})" if Nf_pad != Nf else ""))

    B = consensus.setup_polynomials(freqs, freq0, cfg.npoly, cfg.poly_type)
    B_pad = np.concatenate(
        [B, np.tile(B[-1:], (Nf_pad - Nf, 1))], axis=0
    ) if Nf_pad != Nf else B

    spatial = None
    diffuse_idx = None
    diffuse_beta = None
    if spatial_n0 > 0:
        from sagecal_tpu.parallel.spatial import (
            basis_blocks, find_initial_spatial, phikk_matrix,
            spatial_basis_modes,
        )

        # flux-weighted cluster centroids (the master's spatial-basis
        # setup computes these from the sky model, :293-423)
        def _centroid(c):
            w = np.maximum(np.abs(np.asarray(c.sI0)), 1e-12)
            return (
                float(np.average(np.asarray(c.ll), weights=w)),
                float(np.average(np.asarray(c.mm), weights=w)),
            )

        cent = [_centroid(c) for c in clusters]
        lls = np.asarray([x[0] for x in cent])
        mms = np.asarray([x[1] for x in cent])
        # effective clusters repeat their centroid per hybrid chunk
        lle = np.repeat(lls, nchunk_max)
        mme = np.repeat(mms, nchunk_max)
        sp_modes, beta_used = spatial_basis_modes(
            lle, mme, spatial_n0,
            None if spatial_beta <= 0 else spatial_beta, spatial_basis,
        )
        diffuse_beta = beta_used if beta_used > 0 else spatial_beta
        log(f"spatial basis {spatial_basis} n0={spatial_n0} "
            f"beta={beta_used:.4g}")
        Phi = basis_blocks(sp_modes)
        Z_diff0 = None
        if spatial_diffuse_id is not None:
            if spatial_basis != "shapelet":
                raise ValueError(
                    "the diffuse constraint re-predicts coherencies "
                    "through SHAPELET products (diffuse_predict.c); use "
                    "--spatial-basis shapelet with --spatial-diffuse-id"
                )
            # diffuse target: cluster id -> index; must be all-shapelet
            ids = [cd.cluster_id for cd in cdefs]
            if spatial_diffuse_id not in ids:
                raise ValueError(
                    f"diffuse cluster id {spatial_diffuse_id} not in "
                    f"cluster file (ids {ids})"
                )
            diffuse_idx = ids.index(spatial_diffuse_id)
            Z_diff0 = find_initial_spatial(B, sp_modes, N)
        spatial = SpatialConfig(
            Phi=Phi, Phikk=phikk_matrix(Phi, lam=1e-6),
            alpha=jnp.asarray(
                np.where(alpha_m > 0, alpha_m, cfg.admm_rho), dtype
            ),
            mu=spatial_mu, cadence=spatial_cadence,
            fista_maxiter=spatial_fista_maxiter,
            Z_diff0=Z_diff0, gamma=spatial_gamma, lam_diff=spatial_lam,
        )

    # telemetry: per-band ADMM residual + rho traces ride along as extra
    # mesh outputs when SAGECAL_TELEMETRY=1, and each tile's consensus
    # run lands in the JSONL event log as one admm_round event
    from sagecal_tpu.obs import RunManifest, default_event_log, telemetry_enabled

    # per-band trajectories also feed the consensus watchdog, so an
    # abort-enabled run collects them even with telemetry off
    collect = telemetry_enabled() or cfg.abort_on_divergence

    def _build_mesh_fn(band_weights=None):
        # consensus-layer scaling knobs (parallel/consensus.
        # ConsensusConfig): transpose-reduced z-step, fine-grained
        # cluster factor groups, in-mesh staleness weighting
        ccfg = consensus.ConsensusConfig(
            zstep=cfg.consensus_zstep,
            cluster_groups=max(cfg.consensus_cluster_groups, 1),
            staleness=(cfg.consensus_staleness
                       if cfg.consensus_staleness > 0 else None),
            staleness_discount=cfg.consensus_staleness_discount,
        )
        if band_weights is not None:
            import dataclasses as _dc

            from sagecal_tpu.parallel.admm import factor_schedule

            slot_s, group_s = factor_schedule(
                nadmm, Nf_pad // ndev,
                cluster_groups=max(cfg.consensus_cluster_groups, 1),
                band_weights=band_weights, ndev=ndev,
            )
            ccfg = _dc.replace(ccfg, slot_schedule=slot_s,
                               group_schedule=group_s)
        return make_admm_mesh_fn(
            mesh, nadmm=nadmm, max_emiter=cfg.max_emiter,
            plain_emiter=max(cfg.max_emiter, 2),
            lm_config=LMConfig(itmax=cfg.max_iter),
            bb_rho=adaptive_rho, solver_mode=cfg.solver_mode,
            spatial=spatial,
            collect_trace=collect,
            consensus_cfg=ccfg,
        )

    # fine-grained rounds rebalance their slot schedule on per-band
    # unflagged-row counts, which are only known once the first tile's
    # masks are on device — defer the build to the first tile then;
    # everything else builds the program up front as before
    _want_rebalance = (
        cfg.consensus_cluster_groups > 1 and Nf_pad // ndev >= 1
        and cfg.consensus_staleness <= 0
        and cfg.consensus_staleness_discount == 1.0
    )
    fn = None if _want_rebalance else _build_mesh_fn()
    manifest = RunManifest.collect(
        app="distributed", bands=Nf, nadmm=nadmm,
        solver_mode=cfg.solver_mode, n_clusters=M, n_stations=N,
        adaptive_rho=adaptive_rho,
    )
    elog = default_event_log(manifest=manifest)
    # crash forensics + tracing (obs/flight.py, obs/trace.py): the
    # excepthook/SIGTERM handlers flush the event log with run_aborted,
    # the flight recorder heartbeats for the watch scripts, and the
    # tracer correlates spans with the manifest's run_id
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

    # elastic execution (sagecal_tpu/elastic/): per-tile checkpoints of
    # the full cross-tile carry (p_bands warm start, diffuse Zspat
    # carry, residual traces) make a SIGTERM'd run resumable bit-exactly
    # — the mesh ADMM has no RNG, so the carry IS the whole state
    ckmgr = None
    resume_state = None
    resume_done = 0
    if cfg.resume or cfg.checkpoint_every > 0:
        import os as _os

        from sagecal_tpu.elastic import (
            CheckpointManager,
            ResumeRefused,
            config_fingerprint,
        )

        fingerprint = config_fingerprint(
            app="distributed",
            datasets=[_os.path.abspath(p) for p in datasets],
            sky_model=_os.path.abspath(cfg.sky_model),
            cluster_file=_os.path.abspath(cfg.cluster_file),
            nstations=N, ntime=ntime, nbands=Nf,
            freqs=[float(f) for f in freqs],
            nadmm=nadmm, tilesz=cfg.tilesz, solver_mode=cfg.solver_mode,
            max_emiter=cfg.max_emiter, max_iter=cfg.max_iter,
            npoly=cfg.npoly, poly_type=cfg.poly_type,
            admm_rho=cfg.admm_rho, use_f64=cfg.use_f64,
            in_column=cfg.in_column, skip_tiles=cfg.skip_tiles,
            max_tiles=cfg.max_tiles, spatial_n0=spatial_n0,
            adaptive_rho=adaptive_rho,
            consensus_zstep=cfg.consensus_zstep,
            consensus_cluster_groups=cfg.consensus_cluster_groups,
            consensus_staleness=cfg.consensus_staleness,
            consensus_staleness_discount=cfg.consensus_staleness_discount,
        )
        ckmgr = CheckpointManager(
            cfg.checkpoint_dir or f"{cfg.out_solutions}.ckpt",
            fingerprint, "distributed",
            every=max(cfg.checkpoint_every, 1), elog=elog, log=log,
        )
        if cfg.resume:
            found = ckmgr.resume()
            if found is not None:
                rmeta, resume_state, rpath = found
                resume_done = int(rmeta["tiles_done"])
                # re-open the solution files append-consistently: drop
                # any torn trailing rows AND any complete intervals past
                # the checkpoint (the recomputed tile appends once)
                for path, validate in (
                    [(cfg.out_solutions, solio.validate_global_z)]
                    + [(f"{cfg.out_solutions}.band{i}",
                        solio.validate_solutions)
                       for i in range(Nf)]
                ):
                    if not _os.path.exists(path):
                        raise ResumeRefused(
                            f"checkpoint {rpath} expects solution file "
                            f"{path}, which does not exist")
                    v = validate(path, truncate=True,
                                 max_intervals=resume_done)
                    if v["n_intervals"] < resume_done:
                        raise ResumeRefused(
                            f"{path} holds {v['n_intervals']} intervals "
                            f"but checkpoint {rpath} expects "
                            f"{resume_done}")

    # solution files: global Z + per-band J (slave :959-979 analog);
    # every handle is registered with the caller's finally-block
    zfh = open(cfg.out_solutions, "a" if resume_done else "w")
    open_files.append(zfh)
    if not resume_done:
        write_global_z_header(zfh, freq0, cfg.npoly, N, M, M * nchunk_max)
    band_fhs = []
    for i, path in enumerate(datasets):
        fh = open(f"{cfg.out_solutions}.band{i}",
                  "a" if resume_done else "w")
        open_files.append(fh)
        if not resume_done:
            solio.write_header(
                fh, metas[i].freq0, metas[i].deltaf,
                metas[i].deltat * cfg.tilesz / 60.0, N, M, M * nchunk_max,
            )
        band_fhs.append(fh)

    eye = jones_to_params(identity_jones(
        N, np.complex128 if cfg.use_f64 else np.complex64))
    p_bands = jnp.broadcast_to(
        eye, (Nf_pad, M, nchunk_max, n8)
    ).astype(dtype)

    traces = []
    zdiff_carry = None
    if resume_state is not None:
        # warm-start from the checkpointed carry; restore the completed
        # tiles' residual traces so the return value covers the whole run
        p_bands = jnp.asarray(resume_state["p_bands"], dtype)
        traces = [
            (np.asarray(d), np.asarray(p))
            for d, p in zip(resume_state["traces_dual"],
                            resume_state["traces_primal"])
        ]
        if "zdiff" in resume_state:
            zdiff_carry = jnp.asarray(resume_state["zdiff"], dtype)
    elif cfg.init_solutions:
        # -q: each band starts from its own solution file, as -p writes
        # them; zero-weight padding bands keep the identity
        p0 = np.array(p_bands)
        p0[:Nf] = read_band_solutions(cfg.init_solutions, Nf, M,
                                      nchunk_max, N)
        p_bands = jnp.asarray(p0, dtype)
    tile_starts = list(range(0, ntime, cfg.tilesz))
    pairs = [(i, t0) for i, t0 in enumerate(tile_starts)
             if i >= cfg.skip_tiles]
    if cfg.max_tiles:
        pairs = pairs[: cfg.max_tiles]
    pairs = pairs[resume_done:]
    # Per-band background prefetch of the FULL-SIZE tiles at full
    # channel resolution (the final clamped partial tile loads
    # directly): each band's next tile reads while the mesh ADMM solves
    # the current one (TilePrefetcher, io/dataset.py — the fullbatch
    # loop's loadData-overlap role).  The solve's channel average is
    # made on the chip (make_band_prep_fn).
    spec = [dict(average_channels=False, min_uvcut=cfg.min_uvcut,
                 max_uvcut=cfg.max_uvcut, dtype=dtype,
                 column=cfg.in_column)]
    full_t0s = [t0 for _, t0 in pairs
                if min(cfg.tilesz, ntime - t0) == cfg.tilesz]
    prefetchers = [
        TilePrefetcher(path, full_t0s, spec, cfg.tilesz, depth=1)
        for path in datasets
    ]
    from sagecal_tpu.obs.perf import (
        TransferAudit, emit_perf_events, jit_clock,
    )
    from sagecal_tpu.utils.platform import cpu_device
    from sagecal_tpu.utils.profiling import PhaseTimer

    timer = PhaseTimer()
    audit = TransferAudit()
    audit.__enter__()

    # The band pipeline on the mesh: each device averages, predicts and
    # subtracts its own bands (parallel/mesh.py band programs).  Skies
    # whose coherencies need more than the point/extended predict keep a
    # band-by-band prediction on the host's dispatch.
    stypes = np.concatenate([np.asarray(c.stype) for c in clusters])
    per_band = None
    if diffuse_idx is not None:
        per_band = ("the diffuse cluster is re-predicted from the "
                    "spatial model")
    elif np.any(stypes == ST_SHAPELET):
        per_band = "shapelet sources need the mode table"
    if per_band:
        log(f"distributed: coherencies predicted band by band ({per_band})")
    nchan = meta0.nchan
    fdelta_chan = meta0.deltaf / max(nchan, 1)
    band_sh = NamedSharding(mesh, P("freq"))
    repl = NamedSharding(mesh, P())
    prep_fn = make_band_prep_fn(
        mesh, nchunks, fdelta_chan, with_coherencies=per_band is None,
        has_extended=bool(np.any(stypes != ST_POINT)))
    residual_fn = make_band_residual_fn(mesh,
                                        global_solution=global_residual)
    sources = (None if per_band else
               jax.device_put(stack_sources(clusters), repl))
    rho_m_dev = jax.device_put(np.asarray(rho_m, dtype), repl)
    B_dev = jax.device_put(np.asarray(B_pad, dtype), band_sh)
    # the warm start sharded as every later tile's (the previous out.p),
    # so the first dispatch compiles the program the later ones run
    p_bands = jax.device_put(p_bands, band_sh)
    f0_bands = np.asarray([m.freq0 for m in metas]
                          + [meta0.freq0] * (Nf_pad - Nf), dtype)

    def _load(t0):
        """This tile of every band at full channel resolution, stacked
        on the host; zero-weight padding bands repeat band 0, masked."""
        # clamp the tile to the COMMON timeslot range so bands with more
        # timeslots than ntime_min still produce equal row counts on the
        # final partial tile
        eff_tilesz = min(cfg.tilesz, ntime - t0)
        tiles = []
        for bi, h in enumerate(handles):
            if eff_tilesz == cfg.tilesz:
                t0_chk, (d,) = next(pf_iters[bi])
                if t0_chk != t0:
                    raise RuntimeError(
                        f"band {bi} prefetch order mismatch: "
                        f"{t0_chk} != {t0}"
                    )
                timer.note("read_s", prefetchers[bi].read_seconds(t0))
            else:
                # same kwargs as the prefetch spec so the two load
                # paths can never drift apart
                with jax.default_device(cpu_device()):
                    d = h.load_tile(t0, eff_tilesz, **spec[0])
            tiles.append(d)

        def stack(name):
            xs = [np.asarray(getattr(d, name)) for d in tiles]
            return np.stack(xs + xs[:1] * (Nf_pad - Nf))

        full = tiles[0].replace(
            **{k: stack(k) for k in ("u", "v", "w", "ant_p", "ant_q",
                                     "vis", "mask", "freqs", "time_idx")},
            freq0=freq0, deltaf=meta0.deltaf)
        full.mask[Nf:] = 0
        return full

    def _coherencies_per_band(full_host, data, zdiff):
        """Band-by-band coherencies (build_cluster_data) for the skies
        the band program does not predict."""
        data_host = jax.device_get(data)
        cdatas, cchans = [], []
        for bi in range(Nf):
            d_avg = jax.tree_util.tree_map(lambda x: x[bi], data_host)
            d_full = jax.tree_util.tree_map(lambda x: x[bi], full_host)
            c = build_cluster_data(d_avg, clusters, nchunks,
                                   shapelets=shapelets)
            cc = build_cluster_data(d_full, clusters, nchunks,
                                    fdelta=fdelta_chan, shapelets=shapelets)
            if diffuse_idx is not None and zdiff is not None:
                # re-predict the diffuse cluster from the previous
                # tile's diffuse-constrained spatial model
                # (slave:670-698; between-tiles by design, see
                # run_distributed docstring)
                from sagecal_tpu.ops.diffuse import (
                    recalculate_diffuse_coherencies,
                )
                from sagecal_tpu.parallel.spatial import bz_spatial

                Zb = bz_spatial(zdiff, B_pad[bi], N)
                c = recalculate_diffuse_coherencies(
                    d_avg, c, diffuse_idx, clusters[diffuse_idx],
                    shapelets, Zb, spatial_n0, diffuse_beta,
                )
                cc = recalculate_diffuse_coherencies(
                    d_full, cc, diffuse_idx, clusters[diffuse_idx],
                    shapelets, Zb, spatial_n0, diffuse_beta,
                    fdelta=fdelta_chan,
                )
            cdatas.append(c)
            cchans.append(cc.coh)
        # zero-weight padding bands take band 0's coherencies
        cdatas += cdatas[:1] * (Nf_pad - Nf)
        cchans += cchans[:1] * (Nf_pad - Nf)
        return stack_for_mesh(cdatas), jnp.stack(cchans)

    def _prepare(t0, zdiff):
        """Load one tile and dispatch its band program.  All device work
        here is ASYNC, so calling this between dispatching tile t's
        solve and blocking on it overlaps the next tile's read, channel
        average and coherencies with the device solve — the role of
        the reference's per-tile threaded precalculate_coherencies
        (fullbatch_mode.cpp:371-388).  ``zdiff`` may be a LAZY device
        array from the in-flight solve (the diffuse chain stays on
        device, no sync)."""
        with timer.phase("load"):
            full_host = _load(t0)
        with timer.phase("coh"):
            full, f0b = jax.device_put((full_host, f0_bands), band_sh)
            data, cdata, coh_chan, rho, fratio = prep_fn(
                full, f0b, sources, rho_m_dev)
            if per_band:
                cdata, coh_chan = _coherencies_per_band(full_host, data,
                                                        zdiff)
        return dict(full=full, data=data, cdata=cdata, coh_chan=coh_chan,
                    rho=rho, fratio=fratio)

    def _dispatch(prep, p_start):
        """Dispatch one tile's mesh ADMM (async)."""
        nonlocal fn
        if fn is None:
            # first tile: build the rebalanced fine-grained program on
            # this tile's unflagged-row fractions (padded bands get
            # zero weight -> their slots stop billing rounds)
            bw = np.zeros((Nf_pad,))
            bw[:Nf] = np.asarray(prep["fratio"])[:Nf]
            fn = _build_mesh_fn(band_weights=bw)
        t_unix, t_perf = time.time(), time.perf_counter()
        with timer.phase("solve"):
            out = fn(prep["data"], prep["cdata"], p_start, prep["rho"],
                     B_dev)
        return out, t_unix, t_perf

    pf_iters = []

    def _ckpt_update(pi):
        """End-of-tile checkpoint: everything the loop carries across
        tiles, materialized to host numpy so a later signal-time flush
        never touches the device."""
        if ckmgr is None:
            return
        arrs = {
            "p_bands": np.asarray(p_bands),
            "traces_dual": np.asarray([d for d, _ in traces]),
            "traces_primal": np.asarray([p for _, p in traces]),
        }
        if zdiff_carry is not None:
            arrs["zdiff"] = np.asarray(zdiff_carry)
        ckmgr.update(resume_done + pi, arrs,
                     tiles_done=resume_done + pi + 1,
                     run_id=manifest.run_id)

    # root span for the whole run; manual enter so the existing
    # try/finally owns the exit (tile + phase spans nest under it)
    run_span = tracer.span("distributed", kind="run", bands=Nf, ndev=ndev,
                           nadmm=nadmm)
    run_span.__enter__()
    inflight = None
    try:
      pf_iters = [iter(pf.__enter__()) for pf in prefetchers]
      jit_mark = jit_clock()
      if pairs:
          prepared = _prepare(pairs[0][1], zdiff_carry)
          inflight = _dispatch(prepared, p_bands)
      # The tile pipeline: tile t's residuals are dispatched before tile
      # t+1's solve, and fetched and written while it runs.
      for pi, (tile_no, t0) in enumerate(pairs):
        tic = time.time()
        tile_span = tracer.span("tile", kind="tile", tile=t0)
        tile_span.__enter__()
        tile_ann = timer.tile(t0)
        tile_ann.__enter__()
        out, admm_start_unix, t_dispatch = inflight
        if pi == 0:
            # where the mesh placed the bands (padding bands included)
            placed = out.p.sharding.devices_indices_map(out.p.shape)
            for d, idx in sorted(placed.items(), key=lambda kv: kv[0].id):
                band = range(Nf_pad)[idx[0]]
                log(f"distributed: {d} holds bands "
                    f"{band.start}..{band.stop - 1}")
        if diffuse_idx is not None:
            zdiff_carry = out.Zspat_diff  # lazy device array, no sync
        # overlap: prepare tile t+1 (I/O + band program dispatch) while
        # the mesh solves tile t on device
        nxt = None
        if pi + 1 < len(pairs):
            nxt = _prepare(pairs[pi + 1][1], zdiff_carry)
        # close the ADMM device window AFTER the overlap work: this is
        # the first sync on tile t's outputs, so dispatch->here is the
        # tile's measured mesh-ADMM wall-time, attributed to synthetic
        # per-band / per-round child spans + straggler gauges
        with timer.phase("solve-wait"):
            out = jax.block_until_ready(out)
        admm_seconds = time.perf_counter() - t_dispatch
        fratios = [float(f) for f in np.asarray(prepared["fratio"])]
        # outside the phases: the synthetic spans hang off the tile span
        band_secs, straggler = _emit_admm_attribution(
            tracer, elog, log, t0, admm_seconds, admm_start_unix,
            fratios, Nf, nadmm, Nf_pad // ndev,
            max(cfg.max_emiter, 2), cfg.max_emiter,
            cluster_groups=max(cfg.consensus_cluster_groups, 1))
        note_activity("tile", name=f"tile{t0}", seconds=admm_seconds)
        with timer.phase("finish"):
            if mdl:
                # AIC/MDL consensus-order scan on this tile's rho-scaled
                # solutions (the master's -M path at admm==0,
                # sagecal_master.cpp:986-993)
                from sagecal_tpu.parallel.spatial import (
                    minimum_description_length,
                )

                w = np.asarray(fratios[:Nf])
                Jst = (
                    np.asarray(out.p, np.float64)[:Nf].reshape(Nf, M, -1)
                    * w[:, None, None] * np.asarray(rho_m)[None, :, None]
                )
                aic, mdl_s, k_aic, k_mdl = minimum_description_length(
                    Jst, rho_m, freqs, freq0, weight=w,
                    Kstart=1, Kfinish=max(cfg.npoly, 2),
                )
                log(f"tile {t0} MDL: best order AIC={k_aic} MDL={k_mdl} "
                    f"(aic {np.array2string(aic, precision=2)}, "
                    f"mdl {np.array2string(mdl_s, precision=2)})")
        with timer.phase("residual"):
            # per channel, from the per-band J or, with -U, the global
            # B_f Z (sagecal_slave.cpp:861-979 use_global_solution path)
            res_dev = residual_fn(
                prepared["full"], prepared["coh_chan"],
                prepared["cdata"].chunk_map, prepared["cdata"].nchunk,
                out.p, out.Z, B_dev)
        if nxt is not None:
            inflight = _dispatch(nxt, out.p)
        with timer.phase("residual"):
            res = np.asarray(jax.device_get(res_dev))
        with timer.phase("write"):
            append_global_z(zfh, np.asarray(out.Z), N, cfg.npoly,
                            nchunk_max)
            with jax.default_device(cpu_device()):
                jsol = np.asarray(params_to_jones(
                    np.asarray(out.p)[:Nf])).reshape(
                        Nf, M * nchunk_max, N, 2, 2)
            for i in range(Nf):
                solio.append_solutions(band_fhs[i], jsol[i])
                # (F, 4, rows) -> (rows, F, 2, 2), channel by channel
                handles[i].write_tile(
                    t0, np.moveaxis(res[i], -1, 0).reshape(
                        res.shape[-1], nchan, 2, 2),
                    column=cfg.out_column)
        with timer.phase("finish"):
            # the carry of tile t: the next solve's warm start (the
            # reference keeps p), and what the checkpoint holds
            p_bands = out.p
            traces.append(
                (np.asarray(out.dual_res), np.asarray(out.primal_res))
            )
            dual, primal = traces[-1][0][-1], traces[-1][1][-1]
            _ckpt_update(pi)
            if elog is not None:
                # one event per tile = one consensus run of nadmm rounds;
                # band-resolved residuals + the rho trajectory when the
                # mesh fn was built with collect_trace
                extra = {}
                if out.primal_res_band is not None:
                    extra["primal_res_band"] = np.asarray(
                        out.primal_res_band)
                    extra["dual_res_band"] = np.asarray(out.dual_res_band)
                    extra["rho_trace"] = np.asarray(out.rho_trace)
                elog.emit(
                    "admm_round", tile=t0, nadmm=nadmm,
                    primal_res=np.asarray(out.primal_res),
                    dual_res=np.asarray(out.dual_res),
                    seconds=time.time() - tic,
                    admm_seconds=admm_seconds, band_seconds=band_secs,
                    straggler_ratio=straggler["ratio"],
                    phase_seconds=timer.tile_timings(), **extra,
                )
            if out.primal_res_band is not None:
                # consensus watchdog: per-band residual trajectories ->
                # ratio/trend/diverged (parallel.consensus.
                # consensus_health via obs.quality.assess_consensus)
                from sagecal_tpu.obs.quality import (
                    abort_if_diverged, assess_consensus,
                )

                verdict, reasons, health = assess_consensus(
                    np.asarray(out.primal_res_band),
                    np.asarray(out.dual_res_band),
                )
                if elog is not None:
                    elog.emit("consensus_health", tile=t0,
                              verdict=verdict, reasons=reasons,
                              ratio=health["ratio"],
                              trend=health["trend"])
                    if verdict == "diverged":
                        elog.emit("solver_diverged", reasons=reasons,
                                  tile=t0, app="distributed")
                if verdict != "ok":
                    log(f"tile {t0}: consensus watchdog {verdict} "
                        f"({', '.join(reasons)})")
                if cfg.abort_on_divergence:
                    abort_if_diverged(elog, verdict, reasons, tile=t0,
                                      app="distributed")
        timer.note("admm_rounds", nadmm)
        timer.note("xsteps", int(out.xsteps))
        jit_now = jit_clock()
        timer.note("jit_s", jit_now[0] - jit_mark[0])
        timer.note("jit_n", jit_now[1] - jit_mark[1])
        jit_mark = jit_now
        log(
            f"tile {t0}: dual {dual:.3e} primal {primal:.3e} "
            f"({time.time()-tic:.1f}s) [{timer.tile_summary()}]"
        )
        tile_ann.__exit__(None, None, None)
        tile_span.__exit__(None, None, None)
        prepared = nxt
      log(f"phases: {timer.run_summary()}")
      if ckmgr is not None:
          ckmgr.flush()
          ckmgr.close()
      audit.__exit__(None, None, None)
      if elog is not None:
          from sagecal_tpu.obs.contracts import emit_contract_events

          emit_perf_events(elog)
          audit.emit(elog)
          emit_contract_events(elog)
          elog.emit("run_done", n_tiles=len(traces),
                    phase_totals=dict(timer.totals))
          elog.close()
          unregister_event_log(elog)
      # end-of-run spatial-model amplitude plot (the master's PPM
      # output, sagecal_master.cpp:1198 / pngoutput.c) from the final
      # tile's Zspat — shapelet basis only (the plot evaluates the
      # image-plane shapelet series)
      if (spatial_n0 > 0 and spatial_basis == "shapelet" and pairs
              and out.Zspat is not None):
          from sagecal_tpu.utils.ppm import plot_spatial_model

          ppm_path = f"{cfg.out_solutions}.spatial.ppm"
          plot_spatial_model(
              np.asarray(out.Zspat), cfg.npoly, N, spatial_n0,
              beta=diffuse_beta or spatial_beta, path=ppm_path,
          )
          log(f"spatial model plot -> {ppm_path}")
    finally:
        # a run stopped at a tile line leaves the next tile's solve in
        # flight: let it land before its inputs' files and threads go
        if inflight is not None:
            try:
                jax.block_until_ready(inflight[0])
            except Exception as e:  # the error that stopped the run wins
                log(f"distributed: in-flight solve failed on exit: {e!r}")
        # reap every band's prefetch thread even on a mid-loop failure;
        # the audit exit is idempotent (already closed on the happy
        # path above)
        for pf in prefetchers:
            pf.__exit__(None, None, None)
        audit.__exit__(None, None, None)
        run_span.__exit__(None, None, None)
        # writes the Chrome trace (trace.json) alongside the span JSONL
        close_tracer()

    # success path only: a raise above must leave the recorder (ring)
    # alive for the excepthook's forensic dump
    close_flight_recorder()
    return traces
