#!/usr/bin/env python3
"""Chip smoke: the calibration app's main path, once, on a TPU.

    python chip_smoke.py              # one chip: fullbatch at north-star width
    python chip_smoke.py --chips 4    # four chips: 32-band consensus ADMM
    python chip_smoke.py --rehearse   # tiny shapes on the CPU (no chip result)

One chip.  A seeded 62-station, 100-cluster, 2-channel dataset of two
60-timeslot tiles (the north-star width, BASELINE.md) is simulated with
known Jones gains and written as HDF5 + LSM sky + cluster files.  The
``sagecal`` CLI (``sagecal_tpu.apps.cli.main``) then calibrates it with
the upstream ``dosage.sh`` solver settings and the fused Pallas kernel;
tile 0 is solved again on the XLA predict path for comparison.

Four chips (``--chips 4``).  32 sub-bands with gains linear in
frequency are calibrated by the ``sagecal-mpi`` equivalent (consensus
ADMM over a ``freq`` mesh, ``dosage-mpi.sh`` settings), first over all
four chips and then over one chip, and both are compared with the
injected gains and with each other.

Every phase runs in this one process: a chip belongs to one process.
The last line of standard output is one JSON object; it is printed
only when every check passed on a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import sys
import tempfile
import time

import numpy as np

# north-star width (BASELINE.md): 62 stations x 100 clusters, 60 x 2
# per tile.  The rehearsal sizes only exist to run the control flow on
# the CPU.
MAIN = dict(nstations=62, nclusters=100, nchan=2, tilesz=60, ntiles=2)
MAIN_TINY = dict(nstations=8, nclusters=3, nchan=2, tilesz=4, ntiles=2)
# graded config 4: 32 sub-bands, at 62 stations x 10 clusters, -t 10
MESH = dict(nbands=32, nstations=62, nclusters=10, nchan=2, tilesz=10)
MESH_TINY = dict(nbands=8, nstations=16, nclusters=2, nchan=1, tilesz=4)

# dosage.sh: -t 10 -e 4 -g 2 -l 10 -m 7 -x 30 -F 1 -j 5 (BASELINE.md:18),
# with -t raised to the north-star 60
DOSAGE = ["-e", "4", "-g", "2", "-l", "10", "-m", "7", "-x", "30",
          "-F", "1", "-j", "5"]
# dosage-mpi.sh: -A 10 -P 2 -Q 2 -G <rho file> (BASELINE.md:19; the
# rest of that line is elided there).  The local solver settings are
# ours: 2 EM sweeps of 4 LM iterations (mode 1) per band, as the
# consensus x-step solves a 10-cluster sky
DOSAGE_MPI = ["-A", "10", "-P", "2", "-Q", "2", "-e", "2", "-g", "4",
              "-F", "1", "-j", "1"]

FREQ0 = 150e6
DEC0 = 0.9  # rad; the phase centre of the simulated track

# Bounds, on the flux-weighted gauge-invariant error of gauge_rel_err.
# The dosage.sh budget (4 EM sweeps of 2 iterations) was set upstream
# for a 2-cluster sky; at 100 clusters SAGE-EM converges linearly
# (tests/test_ref_anchor.py) and that budget moves the gains only part
# of the way (on the CPU at 62 stations, 100 clusters, -t 10 the error
# fell to 0.93 of the identity start; with -j 1, 0.85).  So the
# fullbatch check asks what the budget can give: every tile ends closer
# to the injected gains than the identity start, and the warm-started
# tile 1 closer than the cold tile 0.  A tight bound here would test
# the iteration budget, not the chip; fused vs XLA below is the tight
# check.  In the consensus run (10 clusters) every band must end below
# MESH_TRUTH_FRACTION of its identity start's error (0.6 at full size
# on 4 virtual CPU devices).
MESH_TRUTH_FRACTION = 0.8
# Fused vs XLA: the same f32 problem from the same key chain, differing
# only in the predict's summation order inside the LBFGS phase; f32
# rounding (1.2e-7) amplified by the iterative solve stays far below.
FUSED_XLA_BOUND = 1e-4


def log(msg):
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg):
    """A phase that cannot go on: exit with no result."""
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


FAILED = []


def check(ok, msg):
    """A checked result: logged either way; any failure withholds the
    result line at the end, after every phase has printed its numbers."""
    log(f"check {'ok' if ok else 'FAILED'}: {msg}")
    if not ok:
        FAILED.append(msg)


# ----------------------------------------------------------- data making


def write_sky(workdir, nclusters, seed):
    """LSM sky (three-term spectra, -F 1) of one point source per
    cluster, plus its cluster file.  The sources sit on a jittered grid
    0.5 deg apart around the phase centre: two directions much closer
    than that share most baselines' visibility pattern, and their
    station gains trade off (a degeneracy of the problem, not of the
    solver), which no gain comparison could see through."""
    rng = np.random.default_rng(seed)
    side = math.ceil(math.sqrt(nclusters))
    gx, gy = np.meshgrid(np.arange(side), np.arange(side))
    pick = rng.permutation(side * side)[:nclusters]
    spacing = 0.5  # deg
    dx = (gx.ravel()[pick] - (side - 1) / 2 + rng.uniform(-0.2, 0.2,
                                                         nclusters))
    dy = (gy.ravel()[pick] - (side - 1) / 2 + rng.uniform(-0.2, 0.2,
                                                         nclusters))
    dec_d = math.degrees(DEC0) + spacing * dy
    ra_h = spacing * dx / math.cos(DEC0) / 15.0
    flux = rng.uniform(8.0, 12.0, nclusters)
    sky = os.path.join(workdir, "sky.txt")
    clus = os.path.join(workdir, "sky.txt.cluster")
    with open(sky, "w") as f, open(clus, "w") as c:
        for k in range(nclusters):
            h = ra_h[k]
            sign = "-" if h < 0 else ""
            hh, rem = divmod(abs(h) * 3600.0, 3600.0)
            mm, ss = divmod(rem, 60.0)
            dd, drem = divmod(dec_d[k] * 3600.0, 3600.0)
            dm, ds = divmod(drem, 60.0)
            f.write(f"P{k} {sign}{int(hh)} {int(mm)} {ss:.4f} {int(dd)} "
                    f"{int(dm)} {ds:.4f} {flux[k]:.4f} 0 0 0 0 0 0 0 0 0 0 "
                    f"{FREQ0:.1f}\n")
            c.write(f"{k + 1} 1 P{k}\n")
    return sky, clus, flux


def simulate(path, sky, clus, nstations, ntime, nchan, jones, seed,
             freq0=FREQ0, noise=0.05):
    """make_visdata -> corrupt_and_observe with known Jones ->
    create_dataset (the serve/synthetic.py recipe)."""
    import jax.numpy as jnp

    from sagecal_tpu.core.types import C0, mat_of_flat
    from sagecal_tpu.io.dataset import create_dataset
    from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata
    from sagecal_tpu.io.skymodel import load_sky

    clusters, _, _ = load_sky(sky, clus, 0.0, DEC0, dtype=np.float32)
    data = make_visdata(nstations=nstations, tilesz=ntime, nchan=nchan,
                        freq0=freq0, dec0=DEC0, seed=seed,
                        dtype=np.float32)
    # each channel smeared over its own width, as the app predicts it;
    # the channel average then equals the app's model of the averaged
    # tile exactly (sinc(2a) = sinc(a) cos(a))
    data = corrupt_and_observe(data, clusters,
                               jones=jnp.asarray(jones, jnp.complex64),
                               noise_sigma=noise, seed=seed + 1,
                               fdelta=data.deltaf / nchan)
    nbase = data.nbase
    vis = np.asarray(mat_of_flat(data.vis))  # (rows, nchan, 2, 2)
    uvw = [np.asarray(a, np.float64).reshape(ntime, nbase) * C0
           for a in (data.u, data.v, data.w)]
    create_dataset(
        path, *uvw,
        ant_p=np.asarray(data.ant_p)[:nbase],
        ant_q=np.asarray(data.ant_q)[:nbase],
        vis=vis.reshape(ntime, nbase, nchan, 2, 2),
        flag=np.zeros((ntime, nbase, nchan), bool),
        freqs=np.asarray(data.freqs, np.float64), nstations=nstations,
        deltaf=data.deltaf, deltat=data.deltat, ra0=0.0, dec0=DEC0,
        time_jd0=2460000.5,
    )
    return np.asarray(data.ant_p)[:nbase], np.asarray(data.ant_q)[:nbase]


def random_gains(nclusters, nstations, seed, amp):
    rng = np.random.default_rng(seed)
    pert = rng.standard_normal((nclusters, nstations, 2, 2)) \
        + 1j * rng.standard_normal((nclusters, nstations, 2, 2))
    return np.eye(2)[None, None] + amp * pert


def gauge_rel_err(j_est, j_ref, ap, aq, flux):
    """Relative error of the per-direction model visibilities that the
    gains predict.  For an unpolarized point source of flux S_m the
    cluster's visibility is S_m G_pq with G_pq = J_p J_q^H; J -> J U
    (U unitary) is the exact gauge freedom and G_pq is what the data
    determine (tests/test_ref_anchor.py).  Weighting each direction by
    its flux measures the gains as calibration uses them: a faint
    direction's gains are poorly determined and matter as little."""
    ge = np.einsum("mpab,mpcb->mpac", j_est[:, ap], j_est[:, aq].conj())
    gr = np.einsum("mpab,mpcb->mpac", j_ref[:, ap], j_ref[:, aq].conj())
    w = np.asarray(flux, np.float64)[:, None, None, None]
    return float(np.linalg.norm(w * (ge - gr)) / np.linalg.norm(w * gr))


# ------------------------------------------------------------- utilities


class _Tee:
    """stdout that is both printed and kept, to read the CLI's lines."""

    def __init__(self, out):
        self.out, self.buf = out, []

    def write(self, s):
        self.buf.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()

    def text(self):
        return "".join(self.buf)


def run_cli(argv):
    """``sagecal_tpu.apps.cli.main`` in-process; returns (rc, stdout)."""
    from sagecal_tpu.apps.cli import main

    log("cli: sagecal " + " ".join(argv))
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    log(f"cli: exit {rc} in {time.perf_counter() - t0:.2f} s")
    return rc, tee.text()


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (jax.monitoring
    duration events), read as deltas around each phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = {e: 0.0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.total:
            self.total[event] += secs

    def snapshot(self):
        return dict(self.total)

    def since(self, snap):
        return {e.rsplit("/", 1)[-1]: self.total[e] - snap[e]
                for e in self.EVENTS}


_TILE_RE = re.compile(
    r"tile (\d+): residual ([-+0-9.e]+) -> ([-+0-9.e]+) .*\[(.*)\]")


def tile_lines(text):
    """[(t0, res0, res1, {phase: seconds})] from the fullbatch log."""
    out = []
    for m in _TILE_RE.finditer(text):
        phases = dict((k, float(v.rstrip("s"))) for k, v in
                      (kv.split("=") for kv in m.group(4).split()))
        out.append((int(m.group(1)), float(m.group(2)),
                    float(m.group(3)), phases))
    return out


class SolveSpy:
    """Records the abstract arguments of each packed tile solve, so the
    program the app compiled can be lowered again and inspected."""

    def __init__(self):
        from sagecal_tpu.solvers import sage

        self.sage, self.orig, self.calls = sage, sage._sagefit_packed_jit, []

    def __enter__(self):
        import jax

        def spec(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
            if isinstance(x, np.ndarray):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        def spy(*args):
            self.calls.append(jax.tree_util.tree_map(spec, args))
            return self.orig(*args)

        self.sage._sagefit_packed_jit = spy
        return self

    def __exit__(self, *exc):
        self.sage._sagefit_packed_jit = self.orig

    def lowered_text(self):
        return self.orig._jitted.lower(*self.calls[0]).as_text()


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------- phases


def device_phase(want, rehearse):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if not rehearse and d.platform != "tpu":
        fail(f"no TPU: jax.devices()[0] is {d.platform}")
    if len(devs) < want:
        fail(f"need {want} devices, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def main_path(work, seed, size, rehearse):
    from sagecal_tpu.io import solutions as solio

    clock = CompileClock()
    N, M, T = size["nstations"], size["nclusters"], size["tilesz"]
    ntime = T * size["ntiles"]
    sky, clus, flux = write_sky(work, M, seed)
    jones = random_gains(M, N, seed + 7, amp=0.1)
    h5 = os.path.join(work, "obs.h5")
    t0 = time.perf_counter()
    ap, aq = simulate(h5, sky, clus, N, ntime, size["nchan"], jones, seed)
    nrows = T * len(ap)
    log(f"main: simulated {N} stations x {M} clusters x {size['nchan']} "
        f"channels, {size['ntiles']} tiles of {T} timeslots "
        f"({nrows} rows/tile, coherency stack "
        f"{M * size['nchan'] * 4 * nrows * 8 / 1e6:.0f} MB/tile f32) "
        f"in {time.perf_counter() - t0:.1f} s")

    base = ["-d", h5, "-s", sky, "-c", clus, "-t", str(T)] + DOSAGE + [
        "--f32"]
    sol_fused = os.path.join(work, "fused.solutions")
    snap = clock.snapshot()
    with SolveSpy() as spy:
        rc, text = run_cli(base + ["-p", sol_fused, "--fused"])
    comp = clock.since(snap)
    if rc != 0:
        fail(f"fused CLI run exited {rc}")
    tiles = tile_lines(text)
    if len(tiles) != size["ntiles"]:
        fail(f"expected {size['ntiles']} solved tiles, log shows "
             f"{len(tiles)}")
    log("main: compile seconds (fused run): "
        + " ".join(f"{k}={v:.2f}" for k, v in comp.items()))
    for t, r0, r1, ph in tiles:
        solve_s = ph.get("solve", 0.0) + ph.get("solve-wait", 0.0)
        log(f"main: tile {t}: res_0={r0:.6g} res_1={r1:.6g} "
            f"solve_seconds={solve_s:.2f}"
            + (" (includes compile)" if t == 0 else ""))
        check(r1 < r0, f"tile {t}: res_1 {r1:.6g} < res_0 {r0:.6g}")

    hlo = spy.lowered_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"main: lowered sagefit_packed holds {n_kernels} tpu_custom_call "
        f"(Pallas kernel compiled, not interpreted)")
    check(rehearse or n_kernels > 0,
          "tpu_custom_call in the lowered fused tile solve")

    _, sols = solio.read_solutions(sol_fused)
    if sols.shape[:3] != (size["ntiles"], M, N):
        fail(f"solution file shape {sols.shape}")
    start = np.broadcast_to(np.eye(2), jones.shape)
    e_start = bound = gauge_rel_err(start, jones, ap, aq, flux)
    log(f"main: identity start: jones gauge rel err vs injected "
        f"{e_start:.4g}")
    for t in range(size["ntiles"]):
        e = gauge_rel_err(sols[t], jones, ap, aq, flux)
        log(f"main: tile {t}: jones gauge rel err vs injected {e:.4g} "
            f"({e / e_start:.4g} of the start's; bound {bound:.4g})")
        check(e < bound, f"tile {t}: jones error {e:.4g} < {bound:.4g}")
        bound = e

    sol_xla = os.path.join(work, "xla.solutions")
    snap = clock.snapshot()
    rc, text = run_cli(base + ["-p", sol_xla, "-T", "1"])
    comp_x = clock.since(snap)
    if rc != 0:
        fail(f"XLA CLI run exited {rc}")
    (_, r0x, r1x, phx), = tile_lines(text)
    log("main: compile seconds (XLA run): "
        + " ".join(f"{k}={v:.2f}" for k, v in comp_x.items()))
    log(f"main: tile 0 on XLA: res_0={r0x:.6g} res_1={r1x:.6g} "
        f"solve_seconds={phx.get('solve', 0) + phx.get('solve-wait', 0):.2f}"
        f" (includes compile)")
    _, sx = solio.read_solutions(sol_xla)
    d = gauge_rel_err(sols[0], sx[0], ap, aq, flux)
    log(f"main: tile 0 fused vs XLA jones gauge rel diff {d:.4g} "
        f"(bound {FUSED_XLA_BOUND:g}); res_1 fused {tiles[0][2]:.6g} "
        f"XLA {r1x:.6g}")
    check(d < FUSED_XLA_BOUND, f"fused and XLA solves agree: {d:.4g}")
    log(f"main: peak_bytes_in_use {peak_bytes()}")


@contextlib.contextmanager
def devices_cut(n):
    """The distributed app builds its mesh from jax.devices(); the
    one-chip comparison hands it the first device only."""
    import jax

    real = jax.devices

    def cut(*a, **k):
        return real(*a, **k)[:n]

    jax.devices = cut
    try:
        yield
    finally:
        jax.devices = real


def mesh_path(work, seed, size, ndev):
    import jax

    from sagecal_tpu.io import solutions as solio

    clock = CompileClock()
    nb, N, M = size["nbands"], size["nstations"], size["nclusters"]
    T = size["tilesz"]
    sky, clus, flux = write_sky(work, M, seed)
    rho = os.path.join(work, "regularization_factors.txt")
    with open(rho, "w") as f:
        f.write("".join(f"{k + 1} 1 20.0\n" for k in range(M)))
    freqs = np.linspace(115e6, 185e6, nb)
    f0 = float(np.mean(freqs))
    z0 = random_gains(M, N, seed + 11, amp=0.2)
    rng = np.random.default_rng(seed + 12)
    z1 = 0.15 * (rng.standard_normal(z0.shape)
                 + 1j * rng.standard_normal(z0.shape))
    truth = []
    t0 = time.perf_counter()
    for b in range(nb):
        jb = z0 + (freqs[b] - f0) / f0 * z1
        truth.append(jb)
        ap, aq = simulate(os.path.join(work, f"band{b:02d}.h5"), sky, clus,
                          N, T, size["nchan"], jb, seed + 100 + b,
                          freq0=freqs[b], noise=0.02)
    log(f"mesh: simulated {nb} bands of {N} stations x {M} clusters, "
        f"{T} timeslots, gains linear in frequency, in "
        f"{time.perf_counter() - t0:.1f} s")
    base = ["-f", os.path.join(work, "band*.h5"), "-s", sky, "-c", clus,
            "-t", str(T), "-G", rho, "--f32"] + DOSAGE_MPI
    errs = {}
    sols = {}
    for n in (ndev, 1):
        sol = os.path.join(work, f"z{n}.solutions")
        snap = clock.snapshot()
        t1 = time.perf_counter()
        with devices_cut(n):
            rc, text = run_cli(base + ["-p", sol])
        wall = time.perf_counter() - t1
        comp = clock.since(snap)
        if rc != 0:
            fail(f"{n}-device distributed run exited {rc}")
        for line in text.splitlines():
            if "holds bands" in line:
                log(f"mesh[{n}]: {line.strip()}")
        log(f"mesh[{n}]: wall {wall:.2f} s, compile seconds "
            + " ".join(f"{k}={v:.2f}" for k, v in comp.items()))
        band = np.stack([solio.read_solutions(f"{sol}.band{b}")[1][0]
                         for b in range(nb)])
        sols[n] = band
        e = [gauge_rel_err(band[b], truth[b], ap, aq, flux) for b in range(nb)]
        e0 = [gauge_rel_err(np.broadcast_to(np.eye(2), truth[b].shape),
                            truth[b], ap, aq, flux) for b in range(nb)]
        ratio = max(eb / e0b for eb, e0b in zip(e, e0))
        errs[n] = max(e)
        log(f"mesh[{n}]: per-band jones gauge rel err vs injected: max "
            f"{max(e):.4g} median {np.median(e):.4g}; worst band "
            f"{ratio:.4g} of its identity start (bound "
            f"{MESH_TRUTH_FRACTION:g})")
        check(ratio < MESH_TRUTH_FRACTION,
              f"{n}-device consensus gains: worst band ratio {ratio:.4g}")
    # Both runs start from the same per-band plain solves; then each
    # chip takes one x-step per ADMM round on its bands in turn (9 per
    # chip at -A 10: every band about once on four chips, 9 of 32 bands
    # on one chip), so they are two schedules, not one program on two
    # meshes.  They must agree at least as well as the worse of them
    # agrees with the truth.
    d = max(gauge_rel_err(sols[ndev][b], sols[1][b], ap, aq, flux)
            for b in range(nb))
    bound = max(errs.values())
    log(f"mesh: {ndev} devices vs 1: max per-band jones gauge rel diff "
        f"{d:.4g} (bound {bound:.4g}, the larger truth error)")
    check(d < bound, f"{ndev}-device and 1-device consensus agree: "
                     f"{d:.4g}")
    log(f"mesh: peak_bytes_in_use per device "
        f"{[(dv.memory_stats() or {}).get('peak_bytes_in_use') for dv in jax.devices()]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the consensus-ADMM mesh path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="keep the generated data here (default: a "
                         "temporary directory that is removed)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU; prints no chip result")
    args = ap.parse_args(argv)
    if args.rehearse and args.chips == 4:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    dev = device_phase(args.chips, args.rehearse)
    # the package is imported only after the device check: with no chip
    # or no repository the script must fail before it can print a result
    with contextlib.ExitStack() as stack:
        work = args.workdir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="chip_smoke_"))
        os.makedirs(work, exist_ok=True)
        if args.chips == 4:
            mesh_path(work, args.seed,
                      MESH_TINY if args.rehearse else MESH, args.chips)
        else:
            main_path(work, args.seed,
                      MAIN_TINY if args.rehearse else MAIN, args.rehearse)
    if FAILED:
        fail(f"{len(FAILED)} check(s) failed: {'; '.join(FAILED)}")
    if args.rehearse:
        log(f"rehearsal passed on {dev['platform']} at tiny shapes; "
            f"not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
