"""Cells of configurations whose ``app`` is ``distributed``: the
``sagecal-mpi`` path, one observation's sub-bands calibrated together by
consensus ADMM over the chips of one host.

Set-up: the seeded observation is written to disk, one file per
sub-band, and one CLI invocation starts over it, each band warm-started
(``-q``) from the gains of the tile before the first in the cycle.  Its
first tile (tracing, cache load or compile, and the solve) is the
warm-up: the window starts at that tile's line and runs whole tiles of
the same invocation until the window's seconds have passed, closing on
a tile line.  Afterwards the plain reference
(``configs/consensus_ref.py``) checks every tile of the window: each
band's residuals per channel, how far each band's solution moved the
fit from its warm start, and how close the consensus Z lies to the
polynomial fit through the band solutions.
"""

from __future__ import annotations

import gc
import math
import os
import re
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from pb import drive
from pb.gen import (
    baselines, channel_freqs, random_gains, read_solutions, station_layout,
    uvw_metres, write_sky, write_solutions,
)
from pb.trace import TileProfiler, load_events, reduce, self_times

PHASES = ("load", "coh", "solve", "solve-wait", "residual", "write",
          "finish")
# the collective ops of a device trace, by HLO instruction name
COLLECTIVE_RE = re.compile(
    r"^[%_]?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)")


class Bands:
    """One seeded observation split into sub-bands: geometry, sky,
    per-distinct-tile and per-band gains, and the band files.  The gains
    are linear in frequency plus a drift per distinct tile that every
    band shares, so a polynomial of the configuration's order ties them
    across the band."""

    def __init__(self, cfg, traffic, seed, workdir, ref):
        self.cfg, self.traffic, self.ref = cfg, traffic, ref
        N, M, NB = cfg["nstations"], cfg["nclusters"], cfg["nbands"]
        self.N, self.M, self.NB = N, M, NB
        self.T, self.K = traffic["tilesz"], traffic["distinct_tiles"]
        self.dec0, self.ra0 = cfg["dec0_rad"], 0.0
        self.deltat = cfg["deltat_s"]
        self.band_freqs = np.linspace(cfg["band_lo_hz"], cfg["band_hi_hz"],
                                      NB)
        self.f_mid = float(np.mean(self.band_freqs))
        self.chan_width = cfg["chan_width_hz"]
        self.deltaf = self.chan_width * cfg["nchan"]
        self.chan_freqs = [channel_freqs(f, self.chan_width, cfg["nchan"])
                           for f in self.band_freqs]
        ss = np.random.SeedSequence(seed % (2 ** 63))
        s_sky, s_st, s_g, s_n = (int(x[0]) for x in
                                 (c.generate_state(1) for c in ss.spawn(4)))
        sky = cfg["sky"]
        self.sky, self.clus = write_sky(
            workdir, M, s_sky, sky["spacing_deg"], sky["jitter"],
            sky["flux_jy"], self.dec0, self.f_mid)
        self.clusters = ref.read_point_sky(self.sky, self.clus)
        self.xyz = station_layout(N, cfg["extent_m"], s_st)
        self.ant_p, self.ant_q = baselines(N)
        rng = np.random.default_rng(s_g)
        base = np.eye(2)[None, None] + random_gains(M, N, rng,
                                                    traffic["gain_amp"])
        slope = random_gains(M, N, rng, traffic["slope_amp"])
        drift = [random_gains(M, N, rng, traffic["drift_amp"])
                 for _ in range(self.K)]
        frat = (self.band_freqs - self.f_mid) / self.f_mid
        # gains[k][b]: distinct tile k, band b
        self.gains = [[base + frat[b] * slope + drift[k] for b in range(NB)]
                      for k in range(self.K)]
        self.noise_seed = s_n
        self._coh = {}

    def rows_of(self, k):
        slots = np.arange(k * self.T, (k + 1) * self.T)
        u, v, w = uvw_metres(self.xyz, self.ant_p, self.ant_q, slots,
                             self.dec0, self.deltat)
        return u, v, w

    def coherencies(self, k, b):
        """Cached (M, F, rows) coherencies of distinct tile k in band b,
        on the band's channels, each smeared over one channel."""
        if (k, b) not in self._coh:
            u, v, w = (a.reshape(-1) for a in self.rows_of(k))
            self._coh[(k, b)] = self.ref.coherencies(
                self.clusters, u, v, w, self.chan_freqs[b], self.chan_width,
                self.ra0, self.dec0)
        return self._coh[(k, b)]

    def rows_ant(self):
        return np.tile(self.ant_p, self.T), np.tile(self.ant_q, self.T)

    def tile_vis(self, k, b):
        """(T, nbase, F, 2, 2) complex64: band b's model of the sky under
        the tile's gains, plus complex Gaussian noise."""
        ap, aq = self.rows_ant()
        vis = self.ref.model(self.coherencies(k, b), self.gains[k][b], ap,
                             aq)
        rng = np.random.default_rng([self.noise_seed, k, b])
        sig = self.traffic["noise_jy"]
        vis = vis + sig * (rng.standard_normal(vis.shape)
                           + 1j * rng.standard_normal(vis.shape))
        return vis.reshape(self.T, self.ant_p.size, -1, 2, 2).astype(
            np.complex64)

    def band_path(self, work, b):
        return os.path.join(work, f"band{b:02d}.h5")

    def write_bands(self, work, ntiles):
        """One HDF5 container per band (io/dataset.py layout): ``ntiles``
        tiles, tile t holding distinct tile t % K."""
        import h5py

        nb, T = self.ant_p.size, self.T
        ntime = ntiles * T
        uvw = [self.rows_of(k) for k in range(self.K)]
        for b in range(self.NB):
            F = self.chan_freqs[b].size
            distinct = [self.tile_vis(k, b) for k in range(self.K)]
            with h5py.File(self.band_path(work, b), "w") as f:
                for i, name in enumerate(("u", "v", "w")):
                    d = f.create_dataset(name, (ntime, nb), np.float64,
                                         chunks=(1, nb))
                    for t in range(ntiles):
                        d[t * T:(t + 1) * T] = uvw[t % self.K][i]
                f.create_dataset("ant_p", data=self.ant_p)
                f.create_dataset("ant_q", data=self.ant_q)
                d = f.create_dataset("vis", (ntime, nb, F, 2, 2),
                                     np.complex64, chunks=(1, nb, F, 2, 2))
                for t in range(ntiles):
                    d[t * T:(t + 1) * T] = distinct[t % self.K]
                f.create_dataset("flag", (ntime, nb, F), bool,
                                 chunks=(1, nb, F), fillvalue=False)
                f.create_dataset("freqs", data=self.chan_freqs[b])
                f.attrs["nstations"] = self.N
                f.attrs["freq0"] = float(np.mean(self.chan_freqs[b]))
                f.attrs["deltaf"] = self.deltaf
                f.attrs["deltat"] = self.deltat
                f.attrs["ra0"] = self.ra0
                f.attrs["dec0"] = self.dec0
                f.attrs["time_jd0"] = 2460000.5
        return os.path.join(work, "band*.h5")


def run(run):
    cfg, traffic, seconds, log = run.cfg, run.traffic, run.seconds, run.log
    work = run.workdir
    ref = run.reference
    obs = Bands(cfg, traffic, run.seed, work, ref)
    T = obs.T
    # the dataset holds the warm-up tile and enough tiles for a window at
    # min_tile_s a tile
    ntiles = 2 + int(math.ceil(seconds / traffic["min_tile_s"]))
    pattern = obs.write_bands(work, ntiles)
    tint = cfg["deltat_s"] * T / 60.0
    rho = os.path.join(work, "regularization_factors.txt")
    with open(rho, "w") as f:
        f.write("".join(f"{k + 1} 1 {cfg['rho']}\n"
                        for k in range(obs.M)))
    init = os.path.join(work, "init.solutions")
    # the warm-up starts each band from the gains of the tile before it
    # in the cycle, as a long observation's tile starts from its
    # predecessor's
    for b in range(obs.NB):
        write_solutions(f"{init}.band{b}", obs.gains[-1][b],
                        float(np.mean(obs.chan_freqs[b])), obs.deltaf, tint)
    win_sol = os.path.join(work, "window.solutions")
    run.mark("data", rows_per_tile=T * obs.ant_p.size, bands=obs.NB,
             tiles=ntiles)

    argv = (["-f", pattern, "-s", obs.sky, "-c", obs.clus, "-t", str(T),
             "-G", rho, "-q", init, "-p", win_sol]
            + list(cfg["solver_args"]) + list(cfg["precision_args"])
            + list(run.extra_args))
    prof = TileProfiler() if run.trace else None
    t_cli, snap = time.perf_counter(), run.clock.snapshot()
    start = {}

    def on_tile(rec):
        if not start:  # the warm-up tile's line: the window starts here
            run.end_setup()
            run.mark("warm-up", **run.clock.since(snap))
            start.update(t=rec["t"], snap=run.clock.snapshot())
        if prof is not None:
            prof.on_tile(rec["t"])
        if rec["t"] - start["t"] >= seconds and (prof is None or
                                                 prof.state == "done"):
            raise drive.WindowClosed()

    try:
        rc, tee, closed = drive.run_cli(argv, on_tile=on_tile, log=log)
    finally:
        if prof is not None:
            prof.abort()
    if not start:
        raise RuntimeError(f"the CLI ended (exit {rc}) before its first "
                           f"tile line")
    comp = run.clock.since(start["snap"])
    tiles = tee.tiles[1:]
    if not closed:
        raise RuntimeError(
            f"the dataset's {ntiles - 1} window tiles ran out after "
            f"{time.perf_counter() - start['t']:.1f} s, before the window "
            f"closed: lower min_tile_s in the traffic file")
    t0 = start["t"]
    window_s = tiles[-1]["t"] - t0
    prev = t0
    for rec in tiles:
        rec["period_s"], prev = rec["t"] - prev, rec["t"]
    log(f"tile periods: warm-up {t0 - t_cli:.4f}, window "
        + " ".join(f"{r['period_s']:.4f}" for r in tiles))
    run.window = {"seconds": window_s, "tiles": len(tiles),
                  "tile_s": window_s / len(tiles), "compile": comp,
                  "per_tile": tiles}
    run.memory_peak()
    gc.collect()  # the stopped CLI's dataset and solution handles

    if prof is not None:
        t_tr = time.perf_counter()
        dev, host = load_events(prof.xspace)
        prof.xspace = None
        run.trace_summary = reduce(dev, host, prof.window_s, PHASES)
        run.window["traced"] = tee.tiles[prof.traced_tile]
        run.window["collective_s"] = collective_seconds(dev)
        run.mark("trace read", seconds=time.perf_counter() - t_tr,
                 ops=sum(t.start.size for t in dev.values()),
                 devices=len(dev),
                 collective_s=run.window["collective_s"])
    run.shapes = {}

    # ---- the plain reference, once the window has closed
    t_ref = time.perf_counter()
    check_tiles(run, obs, win_sol, len(tiles))
    run.mark("reference", seconds=time.perf_counter() - t_ref)


def collective_seconds(dev):
    """Self seconds of the collective ops per device, averaged over the
    devices of the trace (None when it holds no device)."""
    if not dev:
        return None
    per = []
    for t in dev.values():
        selfd = self_times(t.start, t.dur) * 1e-9
        per.append(sum(s for name, s in zip(t.names, selfd.tolist())
                       if COLLECTIVE_RE.match(name)))
    return float(sum(per) / len(per))


def check_tiles(run, obs, win_sol, n):
    """Per window tile (tile 1 + i of the invocation, after the warm-up
    tile 0), from the data, the residuals, the band solutions and the
    consensus Z the app wrote:

    - resid_gap: the largest gap, over bands and channels, between a
      residual the app wrote and the reference's V - model(J_band), over
      that channel's rms of V;
    - fit_gain: the largest over bands of the rms misfit of the band's
      solution over that of its warm start (the band's previous
      solution); a solve that returns its start reads 1;
    - z_gap: the distance of the written Z from the least-squares
      polynomial fit through the written band solutions, over the fit's
      size;
    - truth_ratio: the largest over bands of the rms misfit of the
      solution over that of the gains the data were made with (the noise
      alone), on the window's first tile.

    On the first tile two controls are printed too: the reference in
    bf16 in the program's place (resid_gap_bf16), and the Z an
    unexchanged z-step would write, the fit through the first chip's
    bands alone (z_gap_unexchanged).  The numbers that the cell's limits
    name are compared; the others are printed."""
    import h5py

    ref = run.reference
    cfg = run.cfg
    args = cfg["solver_args"]
    npoly = int(args[args.index("-P") + 1])
    ptype = int(args[args.index("-Q") + 1])
    B = ref.basis(obs.band_freqs, obs.f_mid, npoly, ptype)
    j_win = np.stack([read_solutions(f"{win_sol}.band{b}")
                      for b in range(obs.NB)])  # (NB, nint, K, N, 2, 2)
    Z = ref.read_global_z(win_sol)
    if j_win.shape[1] < 1 + n or Z.shape[0] < 1 + n:
        raise RuntimeError(f"{win_sol}: {j_win.shape[1]} band and "
                           f"{Z.shape[0]} consensus intervals for the "
                           f"warm-up and {n} tiles")
    # the bands of the first chip (parallel/mesh: contiguous, equal)
    nloc = -(-obs.NB // run.cell["chips"])
    T = obs.T
    ap, aq = obs.rows_ant()
    rows = slice(None)  # no uv cut: the solve fits every row

    def band_numbers(f, b, i):
        d = 1 + i
        k = d % obs.K
        sl = slice(d * T, (d + 1) * T)
        F = obs.chan_freqs[b].size
        V = np.asarray(f["vis"][sl]).reshape(-1, F, 2, 2).astype(
            np.complex128)
        R_app = np.asarray(f["corrected"][sl]).reshape(-1, F, 2, 2)
        coh = obs.coherencies(k, b)
        J = j_win[b, d]
        if run.variant == "reference_bf16":
            # the control: the reference in bf16 in the program's place
            R_app = V - ref.model(coh, J, ap, aq, precision="bf16")
        gap, R_ref = ref.resid_gap(V, R_app, coh, J, ap, aq)
        fit = np.mean(np.abs(R_ref[rows]) ** 2)
        start = j_win[b, d - 1]
        nums = {"resid_gap": gap, "fit_gain": math.sqrt(
            fit / ref.misfit(V, coh, start, ap, aq, rows))}
        if i == 0:
            nums["truth_ratio"] = math.sqrt(
                fit / ref.misfit(V, coh, obs.gains[k][b], ap, aq, rows))
            R_bf16 = V - ref.model(coh, J, ap, aq, precision="bf16")
            nums["resid_gap_bf16"] = ref.resid_gap(V, R_bf16, coh, J, ap,
                                                   aq)[0]
        return nums

    worst, failed = {}, 0
    files = [h5py.File(obs.band_path(run.workdir, b), "r")
             for b in range(obs.NB)]
    for b in range(obs.NB):  # fill the coherency cache before the threads
        for k in range(obs.K):
            obs.coherencies(k, b)
    try:
        with ThreadPoolExecutor(8) as pool:
            for i in range(n):
                Jp = ref.params(j_win[:, 1 + i])
                nums = {"z_gap": ref.z_gap(Z[1 + i], Jp, B)}
                if i == 0:
                    nums["z_gap_unexchanged"] = ref.z_gap(
                        ref.z_fit(Jp[:nloc], B[:nloc]), Jp, B)
                for per in pool.map(lambda fb: band_numbers(*fb, i),
                                    zip(files, range(obs.NB))):
                    for name, v in per.items():
                        nums[name] = max(nums.get(name, v), v)
                for name, v in nums.items():
                    worst[name] = max(worst.get(name, v), v)
                failed += not all(run.within(name, v) for name, v in
                                  nums.items() if name in run.limits)
                run.log(f"check tile {1 + i}: " + " ".join(
                    f"{name} {v:.6e}" for name, v in nums.items()))
    finally:
        for f in files:
            f.close()
    run.attempted, run.failed = n, failed
    for name, v in worst.items():
        if name in run.limits:
            run.compare(name, v)
        else:
            run.log(f"reading {name}: {v!r} (not compared)")
