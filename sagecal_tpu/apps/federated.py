"""Federated stochastic calibration driver — the ``sagecal-mpi -N``
mode end-to-end.

Redesign of the stochastic MPI pair
(``/root/reference/src/MPI/sagecal_stochastic_master.cpp`` /
``sagecal_stochastic_slave.cpp``): per solution tile, ``nadmm``
federated rounds each running ``epochs x minibatches`` consensus
minibatch-LBFGS passes over the tile's timeslots with PERSISTENT
curvature memory per band (slave:637-638, 671-855), a per-band local
z-step tied to the federated average with the alpha constraint, and a
manifold-averaging round-trip at the reference's cadence (after each
epoch block; master:347, slave:856-868).  Bands map to the mesh's
``freq`` axis — the MPI star becomes an ``all_gather`` + replicated
manifold math.

Reset protocol (CTRL_RESET, slave:1044-1066 / stochastic_master.cpp:360):
after each federated round, any band whose data cost is non-finite or
grew by more than ``reset_ratio`` over its tile-start cost resets its
solutions, duals, and LBFGS memory (``lbfgs_persist_reset``) and
rejoins from identity; when a majority of bands reset in one round the
driver logs the master's "Most slaves did not converge" warning.
"""

from __future__ import annotations

import glob
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from sagecal_tpu.apps.config import RunConfig
from sagecal_tpu.core.types import identity_jones, jones_to_params, params_to_jones
from sagecal_tpu.io import solutions as solio
from sagecal_tpu.io.dataset import VisDataset
from sagecal_tpu.io.skymodel import load_sky
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.federated import (
    FederatedState,
    init_federated_state,
    make_fed_avg_fn,
    make_federated_minibatch_fn,
)
from sagecal_tpu.solvers.sage import build_cluster_data


def _reset_band(state: FederatedState, band: int, p_init) -> FederatedState:
    """CTRL_RESET analog for one band: fresh p/Y/Z/Zbar/X and LBFGS
    memory (slave:1044-1060, lbfgs_persist_reset Dirac.h:133-136)."""
    z0 = jnp.zeros_like(state.Z[band])
    mem_b = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[band]),
                                   state.mem)
    return FederatedState(
        p=state.p.at[band].set(p_init),
        Y=state.Y.at[band].set(jnp.zeros_like(state.Y[band])),
        Z=state.Z.at[band].set(z0),
        Zbar=state.Zbar.at[band].set(z0),
        X=state.X.at[band].set(z0),
        mem=jax.tree_util.tree_map(
            lambda full, zb: full.at[band].set(zb), state.mem, mem_b
        ),
    )


def run_federated(
    cfg: RunConfig,
    datasets: Optional[Sequence[str]] = None,
    log=print,
    nadmm: int = 4,
    epochs: int = 2,
    minibatches: int = 2,
    alpha: float = 5.0,
    robust_nu: Optional[float] = None,
    reset_ratio: float = 5.0,
):
    """Run the federated stochastic mode over per-band datasets.

    Per tile of ``cfg.tilesz`` timeslots: nadmm federated rounds, each
    epochs x minibatches minibatch passes (time_per_minibatch =
    ceil(tilesz/minibatches), slave:138), then the Z -> Zavg manifold
    round-trip.  Returns per-tile lists of (dual_res trace, resets).
    """
    from sagecal_tpu.obs.perf import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    from sagecal_tpu.utils.platform import accelerator

    accelerator()  # no TPU and no explicit CPU choice: refuse to run
    if datasets is None:
        datasets = sorted(glob.glob(cfg.dataset))
    if not datasets:
        raise ValueError(f"no band datasets match {cfg.dataset!r}")
    dtype = np.float64 if cfg.use_f64 else np.float32

    handles: List[VisDataset] = [VisDataset(p, "r") for p in datasets]
    open_files: List = []
    try:
        return _run_inner(cfg, datasets, handles, open_files, log, nadmm,
                          epochs, minibatches, alpha, robust_nu,
                          reset_ratio, dtype)
    finally:
        for fh in open_files:
            try:
                fh.close()
            except Exception:
                pass
        for h in handles:
            h.close()


def _run_inner(cfg, datasets, handles, open_files, log, nadmm, epochs,
               minibatches, alpha, robust_nu, reset_ratio, dtype):
    metas = [h.meta for h in handles]
    meta0 = metas[0]
    N = meta0.nstations
    Nf = len(datasets)
    ntime = min(m.ntime for m in metas)
    freqs = np.asarray([m.freq0 for m in metas])
    freq0 = float(np.mean(freqs))

    # telemetry + crash forensics (the federated driver joins the same
    # event-log / span / heartbeat lifecycle as the other apps)
    from sagecal_tpu.obs import RunManifest, default_event_log
    from sagecal_tpu.obs.flight import (
        close_flight_recorder,
        get_flight_recorder,
        install_crash_handlers,
        note_activity,
        register_event_log,
        unregister_event_log,
    )
    from sagecal_tpu.obs.trace import close_tracer, configure_tracer, get_tracer

    manifest = RunManifest.collect(
        app="federated", bands=Nf, nadmm=nadmm, epochs=epochs,
        minibatches=minibatches, solver_mode=cfg.solver_mode,
        n_stations=N,
    )
    elog = default_event_log(manifest=manifest)
    install_crash_handlers()
    if elog is not None:
        register_event_log(elog)
    get_flight_recorder(run_id=manifest.run_id)
    configure_tracer(run_id=manifest.run_id)
    tracer = get_tracer()

    clusters, cdefs, shapelets = load_sky(
        cfg.sky_model, cfg.cluster_file, meta0.ra0, meta0.dec0, dtype=dtype,
        three_term_spectra=None if cfg.sky_format < 0 else bool(cfg.sky_format),
    )
    M = len(clusters)
    nchunks = [cd.nchunk for cd in cdefs]
    nchunk_max = max(nchunks)
    n8 = 8 * N

    devs = np.array(jax.devices()[:Nf])
    if len(devs) < Nf:
        raise ValueError(f"{Nf} bands need {Nf} devices, have {len(devs)}")
    mesh = Mesh(devs, ("freq",))
    B = consensus.setup_polynomials(freqs, freq0, cfg.npoly, cfg.poly_type)
    B = jnp.asarray(B, dtype)
    rho = jnp.full((Nf, M), cfg.admm_rho, dtype)

    step_fn = make_federated_minibatch_fn(
        mesh, itmax=cfg.max_lbfgs or 8, lbfgs_m=cfg.lbfgs_m or 7,
        alpha=alpha, robust_nu=robust_nu,
    )
    avg_fn = make_fed_avg_fn(mesh, alpha=alpha)

    eye = jones_to_params(identity_jones(
        N, np.complex128 if cfg.use_f64 else np.complex64))
    p_init = jnp.broadcast_to(eye, (M, nchunk_max, n8)).astype(dtype)

    # elastic execution (sagecal_tpu/elastic/): the whole FederatedState
    # pytree (p/Y/Z/Zbar/X + LBFGS memory) is the only cross-tile carry,
    # so per-tile checkpoints of its flattened leaves make a restart
    # resume exactly where the killed run stopped
    ckmgr = None
    resume_state = None
    resume_done = 0  # completed tiles
    if cfg.resume or cfg.checkpoint_every > 0:
        import os as _os

        from sagecal_tpu.elastic import (
            CheckpointManager,
            ResumeRefused,
            config_fingerprint,
        )

        fingerprint = config_fingerprint(
            app="federated",
            datasets=[_os.path.abspath(p) for p in datasets],
            sky_model=_os.path.abspath(cfg.sky_model),
            cluster_file=_os.path.abspath(cfg.cluster_file),
            nstations=N, ntime=ntime, nbands=Nf,
            freqs=[float(f) for f in freqs],
            nadmm=nadmm, epochs=epochs, minibatches=minibatches,
            tilesz=cfg.tilesz, npoly=cfg.npoly, poly_type=cfg.poly_type,
            admm_rho=cfg.admm_rho, alpha=alpha, robust_nu=robust_nu,
            reset_ratio=reset_ratio, max_lbfgs=cfg.max_lbfgs,
            lbfgs_m=cfg.lbfgs_m, use_f64=cfg.use_f64,
            in_column=cfg.in_column,
        )
        ckmgr = CheckpointManager(
            cfg.checkpoint_dir or f"{cfg.out_solutions}.ckpt",
            fingerprint, "federated", every=max(cfg.checkpoint_every, 1),
            elog=elog, log=log,
        )
        if cfg.resume:
            found = ckmgr.resume()
            if found is not None:
                rmeta, resume_state, rpath = found
                resume_done = int(rmeta["tiles_done"])
                for i in range(Nf):
                    path = f"{cfg.out_solutions}.band{i}"
                    if not _os.path.exists(path):
                        raise ResumeRefused(
                            f"checkpoint {rpath} expects solution file "
                            f"{path}, which does not exist")
                    v = solio.validate_solutions(
                        path, truncate=True, max_intervals=resume_done)
                    if v["n_intervals"] < resume_done:
                        raise ResumeRefused(
                            f"{path} holds {v['n_intervals']} intervals "
                            f"but checkpoint {rpath} expects "
                            f"{resume_done}")

    # per-band solution files
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

    tmb = -(-cfg.tilesz // minibatches)  # time per minibatch (slave:138)
    results = []
    state = init_federated_state(Nf, M, nchunk_max, n8, cfg.npoly,
                                 cfg.lbfgs_m or 7, dtype)
    if resume_state is not None:
        from sagecal_tpu.elastic import unflatten_state

        # the freshly-initialized state is the unflatten template (same
        # treedef); restore the carried pytree + per-tile results
        state = unflatten_state("state", resume_state, state)
        rr = resume_state["results_resets"]
        results = [
            (np.asarray(resume_state[f"results_dres.{i}"]), int(rr[i]))
            for i in range(len(rr))
        ]
    spec = dict(average_channels=True, min_uvcut=cfg.min_uvcut,
                max_uvcut=cfg.max_uvcut, dtype=dtype,
                column=cfg.in_column)

    from sagecal_tpu.parallel.mesh import stack_for_mesh

    def _ckpt_update(ti):
        """End-of-tile checkpoint: the FederatedState leaves plus the
        per-tile (dual-res trace, resets) results, host-materialized so
        a signal-time flush never touches the device."""
        if ckmgr is None:
            return
        from sagecal_tpu.elastic import flatten_state

        arrs = dict(flatten_state("state", state))
        arrs["results_resets"] = np.asarray(
            [r for _, r in results], np.int64)
        for i, (d, _) in enumerate(results):
            arrs[f"results_dres.{i}"] = np.asarray(d)
        ckmgr.update(resume_done + ti, arrs,
                     tiles_done=resume_done + ti + 1,
                     run_id=manifest.run_id)

    run_span = tracer.span("federated", kind="run", bands=Nf,
                           nadmm=nadmm, epochs=epochs)
    run_span.__enter__()
    tile_starts = list(range(0, ntime, cfg.tilesz))[resume_done:]
    for ti, t0 in enumerate(tile_starts):
        tic = time.time()
        tile_span = tracer.span("tile", kind="tile", tile=t0)
        tile_span.__enter__()
        eff = min(cfg.tilesz, ntime - t0)
        # minibatch time-slices of this tile; per-band loads + cdata
        slices = [(t0 + s, min(tmb, t0 + eff - (t0 + s)))
                  for s in range(0, eff, tmb)]
        mb_data = []
        for (s0, slen) in slices:
            ds, cs = [], []
            for h in handles:
                d = h.load_tile(s0, slen, **spec)
                d = d.replace(freq0=freq0, deltaf=meta0.deltaf)
                ds.append(d)
                cs.append(build_cluster_data(d, clusters, nchunks,
                                             shapelets=shapelets))
            mb_data.append((stack_for_mesh(ds), stack_for_mesh(cs)))

        dres_trace: List[float] = []
        resets_total = 0
        cost0 = None
        # bounded-staleness coupling (--consensus-staleness K): the
        # manifold-averaging consensus step runs every K+1 rounds, so a
        # band's local trajectory may drift up to K rounds from the
        # federated average before being pulled back — the federated
        # analog of the minibatch loop's stale Gram terms.  K=0 (the
        # default) averages every round, unchanged.
        avg_every = max(int(cfg.consensus_staleness), 0) + 1
        if avg_every > 1 and elog is not None and ti == 0:
            elog.emit("async_schedule", staleness=avg_every - 1,
                      avg_every=avg_every, nadmm=nadmm)
        for admm in range(nadmm):
            # real per-round span: the np.asarray(cost) below syncs the
            # round's device work, so the measured window is honest
            round_span = tracer.span("fed.round", kind="admm_round",
                                     round=admm, tile=t0)
            round_span.__enter__()
            for ep in range(epochs):
                for mb, (dst, cst) in enumerate(mb_data):
                    state, dres, cost = step_fn(dst, cst, state, rho, B)
                    dres_trace.append(float(dres))
            if (admm + 1) % avg_every == 0 or admm == nadmm - 1:
                # always average on the last round so the written
                # solutions reflect a coupled state
                state = avg_fn(state)
            cost_np = np.asarray(cost)
            if cost0 is None:
                cost0 = np.where(np.isfinite(cost_np), cost_np, np.inf)
            else:
                # re-base the divergence baseline for bands that were
                # reset (their from-identity restart cost would
                # otherwise trip the ratio against the old converged
                # cost0 every round, resetting them forever)
                rebase = np.isinf(cost0) & np.isfinite(cost_np)
                cost0 = np.where(rebase, cost_np, cost0)
            # CTRL_RESET analog (slave:1044-1066, res_ratio)
            bad = ~np.isfinite(cost_np) | (cost_np > reset_ratio * cost0)
            for b in np.nonzero(bad)[0]:
                log(f"tile {t0} round {admm}: band {b} diverged "
                    f"(cost {cost_np[b]:.3e}) - reset")
                if elog is not None:
                    elog.emit("band_reset", tile=t0, round=admm,
                              band=int(b), cost=float(cost_np[b]))
                state = _reset_band(state, int(b), p_init)
                cost0[b] = np.inf  # re-base on the next finite cost
                resets_total += 1
            if bad.sum() * 2 > Nf:
                # stochastic_master.cpp:360
                log(f"tile {t0} round {admm}: Most bands did not "
                    f"converge ({int(bad.sum())}/{Nf} reset)")
            round_span.__exit__(None, None, None)
            if elog is not None:
                elog.emit("fed_round", tile=t0, round=admm,
                          dual_res=dres_trace[-1] if dres_trace else None,
                          resets=int(bad.sum()))
        for i in range(Nf):
            jsol = np.asarray(params_to_jones(state.p[i])).reshape(
                M * nchunk_max, N, 2, 2
            )
            solio.append_solutions(band_fhs[i], jsol)
            band_fhs[i].flush()
        note_activity("tile", name=f"tile{t0}", seconds=time.time() - tic)
        tile_span.__exit__(None, None, None)
        if elog is not None:
            elog.emit("tile_done", tile=t0, resets=resets_total,
                      dual_res=dres_trace[-1] if dres_trace else None,
                      seconds=time.time() - tic)
        log(f"tile {t0}: dual {dres_trace[-1]:.3e} "
            f"resets {resets_total} ({time.time() - tic:.1f}s)")
        results.append((np.asarray(dres_trace), resets_total))
        _ckpt_update(ti)
    if ckmgr is not None:
        ckmgr.flush()
        ckmgr.close()
    run_span.__exit__(None, None, None)
    close_tracer()
    if elog is not None:
        elog.emit("run_done", n_tiles=len(results))
        elog.close()
        unregister_event_log(elog)
    # success path only: leaves the final "closed" heartbeat; a crash
    # keeps the recorder alive for the excepthook's dump
    close_flight_recorder()
    return results
