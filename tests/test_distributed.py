"""End-to-end distributed (sagecal-mpi equivalent) driver test:
multi-band synthetic observation -> mesh consensus ADMM -> global-Z
solution file + per-band solutions + residual write-back."""

import contextlib
import math
import os

import h5py
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.apps.config import RunConfig
from sagecal_tpu.apps.distributed import run_distributed
from sagecal_tpu.io import solutions as solio
from sagecal_tpu.io.dataset import simulate_dataset
from sagecal_tpu.io.simulate import random_jones
from sagecal_tpu.io.skymodel import load_sky

SKY = """P1 0 0 0.0 51 0 0.0 2.0 0 0 0 0 0 0 0 0 0 0 150e6
P2 0 2 0.0 50 30 0.0 1.0 0 0 0 0 0 0 0 0 0 0 150e6
"""
CLUSTER = "1 1 P1\n2 1 P2\n"


def _make_bands(tmp_path, Nf=4, nstations=7, ntime=2, seed=5, nchan=1):
    """Nf band datasets with gains LINEAR in frequency."""
    sky = tmp_path / "t.sky.txt"
    sky.write_text(SKY)
    (tmp_path / "t.sky.txt.cluster").write_text(CLUSTER)
    clusters, _, _ = load_sky(str(sky), str(sky) + ".cluster",
                           0.0, math.radians(51.0), dtype=np.float64)
    rng = np.random.default_rng(seed)
    M, N = 2, nstations
    eye = np.eye(2)[None, None]
    Z0 = eye + 0.2 * (rng.standard_normal((M, N, 2, 2))
                      + 1j * rng.standard_normal((M, N, 2, 2)))
    Z1 = 0.1 * (rng.standard_normal((M, N, 2, 2))
                + 1j * rng.standard_normal((M, N, 2, 2)))
    freqs = np.linspace(130e6, 170e6, Nf)
    f0 = 150e6
    paths = []
    for f in range(Nf):
        frat = (freqs[f] - f0) / f0
        jones = jnp.asarray(Z0 + frat * Z1)
        p = tmp_path / f"band{f}.h5"
        simulate_dataset(
            str(p), nstations=N, ntime=ntime, nchan=nchan,
            freq0=freqs[f], clusters=clusters, jones=jones,
            noise_sigma=1e-4, seed=seed + f, dec0=math.radians(51.0),
        )
        with h5py.File(str(p), "r+") as fh:
            fh.attrs["ra0"] = 0.0
            fh.attrs["dec0"] = math.radians(51.0)
        paths.append(str(p))
    return paths, sky


class TestDistributedDriver:
    def test_e2e_multiband(self, tmp_path, devices8):
        Nf = 4
        paths, sky = _make_bands(tmp_path, Nf=Nf)
        solf = str(tmp_path / "zsol.txt")
        cfg = RunConfig(
            dataset=str(tmp_path / "band*.h5"),
            sky_model=str(sky),
            cluster_file=str(sky) + ".cluster",
            out_solutions=solf,
            tilesz=2, max_emiter=1, max_iter=6, npoly=2,
            admm_iters=5, admm_rho=10.0, solver_mode=1,
        )
        traces = run_distributed(cfg, log=lambda *a: None)
        assert len(traces) == 1  # one tile
        dres, pres = traces[0]
        assert np.all(np.isfinite(dres)) and np.all(np.isfinite(pres))
        assert pres[-1] < 0.2, pres

        # global Z file: header + N*8*Npoly rows per tile, effective
        # clusters in reverse order (sagecal_master.cpp:1165-1175)
        lines = [ln for ln in open(solf) if not ln.startswith("#")]
        hdr = lines[0].split()
        assert int(hdr[1]) == 2 and int(hdr[2]) == 7  # Npoly, N
        body = lines[1:]
        assert len(body) == 7 * 8 * 2  # N*8*Npoly rows for the one tile
        ncols = len(body[0].split())
        assert ncols == 1 + 2  # row index + M*nchunk_max effective cols

        # per-band solution files parse with the standard reader
        for i in range(Nf):
            meta, jsol = solio.read_solutions(f"{solf}.band{i}")
            assert jsol.shape == (1, 2, 7, 2, 2)

        # residuals written back and smaller than the data
        with h5py.File(paths[0], "r") as fh:
            assert "corrected" in fh
            res = np.asarray(fh["corrected"])
            vis = np.asarray(fh["vis"])
            assert np.linalg.norm(res) < 0.35 * np.linalg.norm(vis)

    def test_band_padding_to_mesh_multiple(self, tmp_path, devices8):
        """3 bands on a mesh that wants multiples: zero-weight padding
        bands must not change the real bands' solve."""
        paths, sky = _make_bands(tmp_path, Nf=3)
        solf = str(tmp_path / "zsol.txt")
        cfg = RunConfig(
            dataset=str(tmp_path / "band*.h5"),
            sky_model=str(sky), cluster_file=str(sky) + ".cluster",
            out_solutions=solf,
            tilesz=2, max_emiter=1, max_iter=5, npoly=2,
            admm_iters=3, admm_rho=10.0, solver_mode=1,
        )
        traces = run_distributed(cfg, log=lambda *a: None)
        assert len(traces) == 1
        for i in range(3):
            assert os.path.exists(f"{solf}.band{i}")

    def test_unequal_band_lengths_clamp_to_minimum(self, tmp_path, devices8):
        """Bands with different timeslot counts: the driver must clamp
        every tile to the common minimum (the warned 'using the minimum'
        path) instead of crashing on the final partial tile."""
        import h5py as _h5
        import math as _math
        from sagecal_tpu.io.dataset import simulate_dataset as _sim
        from sagecal_tpu.io.skymodel import load_sky as _ls

        sky = tmp_path / "t.sky.txt"
        sky.write_text(SKY)
        (tmp_path / "t.sky.txt.cluster").write_text(CLUSTER)
        clusters, _, _ = _ls(str(sky), str(sky) + ".cluster",
                          0.0, _math.radians(51.0), dtype=np.float64)
        for i, nt in enumerate((3, 5)):  # unequal ntime
            p = tmp_path / f"band{i}.h5"
            _sim(str(p), nstations=7, ntime=nt, nchan=1,
                 freq0=(140e6, 160e6)[i], clusters=clusters,
                 noise_sigma=1e-4, seed=i, dec0=_math.radians(51.0))
            with _h5.File(str(p), "r+") as f:
                f.attrs["ra0"] = 0.0
                f.attrs["dec0"] = _math.radians(51.0)
        cfg = RunConfig(
            dataset=str(tmp_path / "band*.h5"),
            sky_model=str(sky), cluster_file=str(sky) + ".cluster",
            out_solutions=str(tmp_path / "z.txt"),
            tilesz=2, max_emiter=1, max_iter=4, npoly=2,
            admm_iters=2, admm_rho=10.0, solver_mode=1,
        )
        traces = run_distributed(cfg, log=lambda *a: None)
        assert len(traces) == 2  # ceil(3/2) tiles over the common range


# ---------------------------------------------------------------------------
# the band pipeline on the mesh: per-channel residuals, the solve's inputs
# made on the devices, one compile per shape

NB, NCH = 8, 2


def _np_coherencies(clusters, u, v, w, freqs, fdelta):
    """Point-source coherencies in numpy: (K, F, rows) complex, u/v/w in
    seconds, each channel smeared over ``fdelta``."""
    out = []
    for c in clusters:
        ll, mm, nn, flux = (np.asarray(x, np.float64)
                            for x in (c.ll, c.mm, c.nn, c.sI0))
        G = 2 * np.pi * (np.outer(u, ll) + np.outer(v, mm) + np.outer(w, nn))
        ph = np.exp(1j * freqs[:, None, None] * G[None])
        smear = np.abs(np.sinc(0.5 * fdelta * G / np.pi))
        out.append(np.sum(flux * smear[None] * ph, axis=-1))
    return np.stack(out)


def _band_cfg(tmp_path, **kw):
    sky = tmp_path / "t.sky.txt"
    base = dict(
        dataset=str(tmp_path / "band*.h5"), sky_model=str(sky),
        cluster_file=str(sky) + ".cluster",
        out_solutions=str(tmp_path / "zsol.txt"),
        tilesz=2, max_emiter=1, max_iter=4, npoly=2, admm_iters=3,
        admm_rho=10.0, solver_mode=1)
    return RunConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def band_run(tmp_path_factory, devices8):
    """One app run: 8 bands x 8 stations x 2 clusters x 2 channels, three
    tiles, float64."""
    tmp_path = tmp_path_factory.mktemp("bands")
    paths, sky = _make_bands(tmp_path, Nf=NB, nstations=8, ntime=6,
                             nchan=NCH)
    cfg = _band_cfg(tmp_path)
    lines = []
    traces = run_distributed(
        cfg, log=lambda *a: lines.append(" ".join(str(x) for x in a)))
    return dict(paths=paths, cfg=cfg, lines=lines, traces=traces)


def _host_inputs(paths, cfg, t0):
    """The solve's inputs as the app made them band by band on the host:
    load_tile(average_channels=True), then build_cluster_data."""
    from sagecal_tpu.io.dataset import VisDataset
    from sagecal_tpu.solvers.sage import build_cluster_data

    datas, cdatas = [], []
    with contextlib.ExitStack() as st:
        hs = [st.enter_context(VisDataset(p)) for p in paths]
        metas = [h.meta for h in hs]
        freq0 = float(np.mean([m.freq0 for m in metas]))
        clusters, cdefs, _ = load_sky(cfg.sky_model, cfg.cluster_file,
                                      metas[0].ra0, metas[0].dec0,
                                      dtype=np.float64)
        for h in hs:
            d = h.load_tile(t0, cfg.tilesz, average_channels=True).replace(
                freq0=freq0, deltaf=metas[0].deltaf)
            datas.append(d)
            cdatas.append(build_cluster_data(d, clusters,
                                             [c.nchunk for c in cdefs]))
    return metas, freq0, clusters, datas, cdatas


class TestBandPipeline:
    def test_written_residuals_are_per_channel(self, band_run):
        """Each channel's residual is V - sum_k J C(f) J^H with that
        channel's coherencies (calculate_residuals_multifreq), to 1e-4
        of the rms of V; the channel-averaged residual written into every
        channel read 1.3e-2 to 3.4e-2 here."""
        from sagecal_tpu.core.types import C0

        cfg = band_run["cfg"]
        clusters, _, _ = load_sky(cfg.sky_model, cfg.cluster_file, 0.0,
                                  math.radians(51.0), dtype=np.float64)
        worst = 0.0
        for b, path in enumerate(band_run["paths"]):
            _, jsol = solio.read_solutions(f"{cfg.out_solutions}.band{b}")
            assert jsol.shape[0] == 3
            with h5py.File(path, "r") as f:
                freqs = np.asarray(f["freqs"])
                fdelta = float(f.attrs["deltaf"]) / freqs.size
                nb = f["u"].shape[1]
                ap = np.tile(np.asarray(f["ant_p"]), cfg.tilesz)
                aq = np.tile(np.asarray(f["ant_q"]), cfg.tilesz)
                for t in range(3):
                    sl = slice(t * cfg.tilesz, (t + 1) * cfg.tilesz)
                    u, v, w = (np.asarray(f[k][sl]).reshape(-1) / C0
                               for k in ("u", "v", "w"))
                    V = np.asarray(f["vis"][sl]).reshape(-1, NCH, 2, 2)
                    R = np.asarray(f["corrected"][sl]).reshape(-1, NCH, 2, 2)
                    coh = _np_coherencies(clusters, u, v, w, freqs, fdelta)
                    J = jsol[t]  # (K, N, 2, 2)
                    g = np.einsum("krab,krcb->krac", J[:, ap],
                                  J[:, aq].conj())
                    R_ref = V - np.einsum("kfr,krac->rfac", coh, g)
                    assert R.shape[0] == cfg.tilesz * nb
                    for c in range(NCH):
                        rms = np.sqrt(np.mean(np.abs(V[:, c]) ** 2))
                        worst = max(worst, float(
                            np.max(np.abs(R[:, c] - R_ref[:, c])) / rms))
        assert worst <= 1e-4, worst

    def test_solutions_equal_the_host_averaged_pipeline(self, band_run,
                                                        devices8):
        """The first tile's written band solutions equal the mesh solve
        of the inputs the app made on the host before (load_tile's
        channel average and build_cluster_data band by band)."""
        from jax.sharding import Mesh

        from sagecal_tpu.core.types import identity_jones, jones_to_params
        from sagecal_tpu.parallel import consensus
        from sagecal_tpu.parallel.mesh import (
            make_admm_mesh_fn, stack_for_mesh,
        )
        from sagecal_tpu.solvers.lm import LMConfig

        cfg = band_run["cfg"]
        metas, freq0, _, datas, cdatas = _host_inputs(band_run["paths"],
                                                      cfg, 0)
        freqs = np.asarray([m.freq0 for m in metas])
        B = consensus.setup_polynomials(freqs, freq0, cfg.npoly,
                                        cfg.poly_type)
        fn = make_admm_mesh_fn(
            Mesh(np.array(devices8), ("freq",)), nadmm=cfg.admm_iters,
            max_emiter=cfg.max_emiter, plain_emiter=2,
            lm_config=LMConfig(itmax=cfg.max_iter), bb_rho=True,
            solver_mode=cfg.solver_mode,
            consensus_cfg=consensus.ConsensusConfig(
                zstep=cfg.consensus_zstep))
        p0 = jnp.broadcast_to(jones_to_params(identity_jones(
            8, np.complex128)), (NB, 2, 1, 64))
        out = fn(stack_for_mesh(datas), stack_for_mesh(cdatas), p0,
                 jnp.full((NB, 2), cfg.admm_rho), jnp.asarray(B))
        for b in range(NB):
            _, jsol = solio.read_solutions(f"{cfg.out_solutions}.band{b}")
            want = np.asarray(jones_to_params(jnp.asarray(jsol[0])))
            got = np.asarray(out.p[b]).reshape(want.shape)
            # the solution file prints 7 significant digits
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    def test_tile_line_counts_and_no_compile_after_the_first(self,
                                                             band_run):
        """Every tile line carries the phases and counts; xsteps is the
        mesh-wide x-step count (8 devices x (1 plain + 2 rounds)); the
        tiles after the first compile nothing."""
        tiles = [ln for ln in band_run["lines"] if ln.startswith("tile ")]
        assert len(tiles) == 3
        for i, line in enumerate(tiles):
            vals = dict(kv.split("=") for kv in
                        line[line.index("[") + 1:line.rindex("]")].split())
            # the next tile's read and band program ride on this line
            nxt = ("load", "coh", "solve", "read_s") if i < 2 else ()
            for k in nxt + ("solve-wait", "residual", "write", "finish"):
                assert k in vals, (k, line)
            assert int(vals["admm_rounds"]) == 3
            assert int(vals["xsteps"]) == NB * 3
            if i > 0:
                assert int(vals["jit_n"]) == 0, line

    def test_warm_start_reads_each_bands_last_interval(self, band_run):
        from sagecal_tpu.apps.distributed import read_band_solutions
        from sagecal_tpu.core.types import jones_to_params

        cfg = band_run["cfg"]
        p = read_band_solutions(cfg.out_solutions, NB, 2, 1, 8)
        assert p.shape == (NB, 2, 1, 64)
        for b in (0, NB - 1):
            _, jsol = solio.read_solutions(f"{cfg.out_solutions}.band{b}")
            np.testing.assert_array_equal(
                p[b], np.asarray(jones_to_params(jnp.asarray(jsol[-1])))
                .reshape(2, 1, 64))


SKY3 = SKY + "SDIF 0 1 0.0 50 45 0.0 1.0 0 0 0 0 0 0 0 1 1 0 150e6\n"
CLUSTER3 = CLUSTER + "3 1 SDIF\n"


@pytest.mark.slow
class TestDriverSpatialExtensions:
    def test_mdl_diffuse_sharmonic_driver(self, tmp_path, devices8):
        """Driver run with --mdl, a spherical-harmonic... no: shapelet
        basis + diffuse-constrained shapelet cluster + MDL logging over
        two tiles (so the between-tile diffuse refresh branch executes:
        the second tile's diffuse coherencies come from tile 1's
        Zspat_diff; master:649-926, slave:670-698, mdl.c)."""
        Nf = 4
        paths, sky = _make_bands(tmp_path, Nf=Nf, ntime=4)
        # calibration sky adds an all-shapelet diffuse cluster (the
        # simulated data does not contain it; the path under test is
        # the coherency refresh, not the astrophysics)
        sky3 = tmp_path / "t3.sky.txt"
        sky3.write_text(SKY3)
        (tmp_path / "t3.sky.txt.cluster").write_text(CLUSTER3)
        n0m, beta = 2, 2e-3
        rng = np.random.default_rng(11)
        lines = ["0 0 0 50 45 0", f"{n0m} {beta}"]
        for k, v in enumerate(rng.standard_normal(n0m * n0m)):
            lines.append(f"{k} {v}")
        (tmp_path / "SDIF.fits.modes").write_text("\n".join(lines) + "\n")

        solf = str(tmp_path / "zsol3.txt")
        cfg = RunConfig(
            dataset=str(tmp_path / "band*.h5"),
            sky_model=str(sky3),
            cluster_file=str(sky3) + ".cluster",
            out_solutions=solf,
            tilesz=2, max_emiter=1, max_iter=6, npoly=2,
            admm_iters=6, admm_rho=10.0, solver_mode=1,
        )
        logs = []
        traces = run_distributed(
            cfg, log=lambda *a: logs.append(" ".join(str(x) for x in a)),
            spatial_n0=2, spatial_beta=-1.0, spatial_mu=1e-4,
            spatial_cadence=2, spatial_basis="shapelet",
            spatial_diffuse_id=3, spatial_gamma=0.3, spatial_lam=1e-3,
            mdl=True,
        )
        assert len(traces) == 2  # two tiles -> refresh branch ran
        for dres, pres in traces:
            assert np.all(np.isfinite(dres)) and np.all(np.isfinite(pres))
        joined = "\n".join(logs)
        assert "MDL: best order" in joined
        assert "spatial basis shapelet" in joined
        # end-of-run spatial amplitude plot (master PPM output analog)
        assert os.path.exists(solf + ".spatial.ppm")
        assert open(solf + ".spatial.ppm", "rb").read(2) == b"P6"

    def test_sharmonic_basis_driver(self, tmp_path, devices8):
        """Same driver path with the spherical-harmonic basis."""
        Nf = 4
        paths, sky = _make_bands(tmp_path, Nf=Nf, ntime=2)
        solf = str(tmp_path / "zsol4.txt")
        cfg = RunConfig(
            dataset=str(tmp_path / "band*.h5"),
            sky_model=str(sky),
            cluster_file=str(sky) + ".cluster",
            out_solutions=solf,
            tilesz=2, max_emiter=1, max_iter=6, npoly=2,
            admm_iters=5, admm_rho=10.0, solver_mode=1,
        )
        logs = []
        traces = run_distributed(
            cfg, log=lambda *a: logs.append(" ".join(str(x) for x in a)),
            spatial_n0=2, spatial_mu=1e-4, spatial_cadence=2,
            spatial_basis="sharmonic",
        )
        assert len(traces) == 1
        dres, pres = traces[0]
        assert np.all(np.isfinite(dres)) and pres[-1] < 0.25
        assert "spatial basis sharmonic" in "\n".join(logs)
        # sharmonic basis -> no shapelet-series PPM plot
        assert not os.path.exists(solf + ".spatial.ppm")


@pytest.mark.slow
class TestGlobalResidualAndXFlag:
    def test_cli_global_residual_and_spatialreg(self, tmp_path, devices8):
        """-U 1 (use_global_solution residuals, slave:861-979) and
        -X lam,mu,n0,iters,cadence (MPI/main.cpp:102) through the CLI."""
        from sagecal_tpu.apps.cli import main as cli_main

        Nf = 4
        paths, sky = _make_bands(tmp_path, Nf=Nf, ntime=2)
        solf = str(tmp_path / "gsol.txt")
        rc = cli_main([
            "-d", "x.h5", "-s", str(sky), "-c", str(sky) + ".cluster",
            "-f", str(tmp_path / "band*.h5"), "-t", "2", "-e", "1",
            "-g", "6", "-A", "4", "-P", "2", "-p", solf,
            "-U", "1", "-X", "1e-3,1e-4,2,20,2",
        ])
        assert rc in (0, None)
        # residual write-back ran with the global solution and stayed
        # smaller than the raw data (the consensus fit is good here)
        with h5py.File(paths[0], "r") as fh:
            res = np.asarray(fh["corrected"])
            vis = np.asarray(fh["vis"])
            assert np.isfinite(res).all()
            assert np.linalg.norm(res) < 0.6 * np.linalg.norm(vis)
        # spatial path engaged (the -X n0=2 order): PPM plot emitted
        assert os.path.exists(solf + ".spatial.ppm")


@pytest.mark.slow
def test_distributed_hybrid_chunks(tmp_path, devices8):
    """Hybrid time-chunking (cluster-file column 2 > 1, lmfit.c:86-87)
    through the distributed driver: cluster 1 solves 2 sub-intervals of
    the tile, so the effective-cluster width is M*nchunk_max in both
    the per-band solution files and the global-Z file."""
    Nf = 4
    paths, sky = _make_bands(tmp_path, Nf=Nf, ntime=2)
    hyb = tmp_path / "h.cluster"
    hyb.write_text("1 2 P1\n2 1 P2\n")
    solf = str(tmp_path / "hsol.txt")
    cfg = RunConfig(
        dataset=str(tmp_path / "band*.h5"),
        sky_model=str(sky), cluster_file=str(hyb),
        out_solutions=solf,
        tilesz=2, max_emiter=1, max_iter=6, npoly=2,
        admm_iters=4, admm_rho=10.0, solver_mode=1,
    )
    traces = run_distributed(cfg, log=lambda *a: None)
    dres, pres = traces[0]
    assert np.all(np.isfinite(dres)) and pres[-1] < 0.3, (dres, pres)
    # M=2 clusters, nchunk_max=2 -> 4 effective columns
    meta, jsol = solio.read_solutions(f"{solf}.band0")
    assert jsol.shape == (1, 4, 7, 2, 2)
    assert np.isfinite(jsol).all()
    lines = [ln for ln in open(solf) if not ln.startswith("#")]
    ncols = len(lines[1].split())
    assert ncols == 1 + 4  # row index + M*nchunk_max effective columns


@pytest.mark.slow
def test_distributed_robust_rtr_mode(tmp_path, devices8):
    """Driver run with solver_mode=5 (robust RTR + ADMM x-steps) — the
    reference MPI slave's DEFAULT local solver
    (rtr_solve_nocuda_robust_admm, sagecal_slave.cpp:764-787)."""
    Nf = 4
    paths, sky = _make_bands(tmp_path, Nf=Nf, ntime=2)
    solf = str(tmp_path / "rrsol.txt")
    cfg = RunConfig(
        dataset=str(tmp_path / "band*.h5"),
        sky_model=str(sky), cluster_file=str(sky) + ".cluster",
        out_solutions=solf,
        tilesz=2, max_emiter=1, max_iter=5, npoly=2,
        admm_iters=4, admm_rho=10.0, solver_mode=5,
        nulow=2.0, nuhigh=30.0,
    )
    traces = run_distributed(cfg, log=lambda *a: None)
    dres, pres = traces[0]
    assert np.all(np.isfinite(dres)) and np.all(np.isfinite(pres))
    assert pres[-1] < 0.3, pres
    meta, jsol = solio.read_solutions(f"{solf}.band0")
    assert np.isfinite(jsol).all()
