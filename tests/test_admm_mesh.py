"""ADMM local solves + the mesh-parallel consensus driver.

The multi-device test replaces the reference's copy-the-MS-N-times MPI
recipe (/root/reference/test/Calibration/README.md) with 8 virtual CPU
devices: 8 sub-bands of one synthetic observation, true gains drawn from
a LOW-ORDER polynomial in frequency so the consensus constraint is
exactly satisfiable.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from sagecal_tpu.core.types import jones_to_params, params_to_jones
from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata, random_jones
from sagecal_tpu.ops.rime import point_source_batch
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.admm import admm_dual_update, admm_sagefit
from sagecal_tpu.parallel.mesh import make_admm_mesh_fn, stack_for_mesh
from sagecal_tpu.solvers.lm import LMConfig
from sagecal_tpu.solvers.sage import build_cluster_data


def _one_band(freq0, jones, seed=0, nstations=8, tilesz=2):
    data = make_visdata(
        nstations=nstations, tilesz=tilesz, nchan=1, freq0=freq0, seed=seed,
        dtype=np.float64,
    )
    clusters = [
        point_source_batch([0.0], [0.0], [2.0], f0=freq0, dtype=jnp.float64),
        point_source_batch([0.02], [-0.01], [1.0], f0=freq0, dtype=jnp.float64),
    ]
    data = corrupt_and_observe(data, clusters, jones=jones, noise_sigma=1e-4, seed=seed)
    cdata = build_cluster_data(data, clusters, [1, 1])
    return data, cdata


class TestAdmmLocal:
    def test_zero_rho_equals_plain_solve(self):
        jones = random_jones(2, 8, seed=3, amp=0.2, dtype=np.complex128)
        data, cdata = _one_band(150e6, jones)
        M, N = 2, 8
        p0 = jones_to_params(random_jones(M, N, seed=99, amp=0.0, dtype=np.complex128))[
            :, None, :
        ]
        zeros = jnp.zeros_like(p0)
        out = admm_sagefit(
            data, cdata, p0, zeros, zeros, jnp.zeros((M,)),
            max_emiter=2, lm_config=LMConfig(itmax=15),
        )
        assert float(out.res_1) < 0.2 * float(out.res_0)

    def test_large_rho_pins_solution_to_consensus(self):
        jones = random_jones(2, 8, seed=3, amp=0.2, dtype=np.complex128)
        data, cdata = _one_band(150e6, jones)
        M, N = 2, 8
        p0 = jones_to_params(jones)[:, None, :]  # start at truth
        target = jones_to_params(
            random_jones(M, N, seed=123, amp=0.1, dtype=np.complex128)
        )[:, None, :]
        zeros = jnp.zeros_like(p0)
        big_rho = jnp.full((M,), 1e8)
        out = admm_sagefit(
            data, cdata, p0, zeros, target, big_rho,
            max_emiter=1, lm_config=LMConfig(itmax=10),
        )
        err = float(jnp.max(jnp.abs(out.p - target)))
        assert err < 1e-3, err

    def test_dual_update(self):
        Y = jnp.zeros((2, 1, 16))
        p = jnp.ones((2, 1, 16))
        BZ = jnp.full((2, 1, 16), 0.5)
        rho = jnp.asarray([2.0, 4.0])
        out = admm_dual_update(Y, p, BZ, rho)
        np.testing.assert_allclose(np.asarray(out[0]), 1.0)
        np.testing.assert_allclose(np.asarray(out[1]), 2.0)


@pytest.mark.slow
class TestAdmmMesh:
    def test_consensus_admm_8_subbands(self, devices8):
        """8 sub-bands on an 8-device mesh; true gains linear in frequency
        (Npoly=2 ordinary basis spans them exactly)."""
        Nf, M, N, tilesz = 8, 2, 8, 2
        Npoly = 2
        freqs = np.linspace(120e6, 180e6, Nf)
        f0 = 150e6
        rng = np.random.default_rng(11)
        # Z_true: (M, Npoly, N, 2, 2) -> J_f = Z0 + frat * Z1
        eye = np.eye(2)[None, None]
        Z0 = eye + 0.25 * (
            rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        )
        Z1 = 0.15 * (
            rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        )
        bands = []
        p0s = []
        for f in range(Nf):
            frat = (freqs[f] - f0) / f0
            jones_f = jnp.asarray(Z0 + frat * Z1)
            data, cdata = _one_band(f0, jones_f, seed=f)  # same freq0 static
            # overwrite the channel freq to the band's actual frequency
            data = data.replace(freqs=jnp.asarray([freqs[f]], jnp.float64))
            bands.append((data, cdata))
            p0s.append(
                jones_to_params(random_jones(M, N, seed=500, amp=0.0, dtype=np.complex128))[
                    :, None, :
                ]
            )
        mesh = Mesh(np.array(devices8), ("freq",))
        B = consensus.setup_polynomials(freqs, f0, Npoly, consensus.POLY_ORDINARY)
        fn = make_admm_mesh_fn(
            mesh, nadmm=8, max_emiter=1, plain_emiter=1,
            lm_config=LMConfig(itmax=6), bb_rho=False,
        )
        data_stack = stack_for_mesh([b[0] for b in bands])
        cdata_stack = stack_for_mesh([b[1] for b in bands])
        p0 = jnp.stack(p0s)
        rho = jnp.full((Nf, M), 20.0, jnp.float64)
        out = fn(data_stack, cdata_stack, p0, rho, jnp.asarray(B))
        # dual residual must decay from its transient peak
        dres = np.asarray(out.dual_res)
        assert dres[-1] < 0.5 * np.max(dres[1:]), dres
        # final primal residual small: J_f ~ B_f Z
        assert float(out.primal_res[-1]) < 0.05, np.asarray(out.primal_res)
        # solutions reproduce the data: check residual of band 0
        data0, cdata0 = bands[0]
        from sagecal_tpu.solvers.sage import predict_full_model

        model = predict_full_model(out.p[0], cdata0, data0)
        res = float(
            jnp.linalg.norm((data0.vis - model).ravel())
            / jnp.linalg.norm(data0.vis.ravel())
        )
        assert res < 0.05, res

    def _polyband_problem(self, Nf, seed=11, N=8):
        """Nf sub-bands with gains linear in frequency (shared helper)."""
        M = 2
        freqs = np.linspace(120e6, 180e6, Nf)
        f0 = 150e6
        rng = np.random.default_rng(seed)
        eye = np.eye(2)[None, None]
        Z0 = eye + 0.25 * (
            rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        )
        Z1 = 0.15 * (
            rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        )
        bands, p0s = [], []
        for f in range(Nf):
            frat = (freqs[f] - f0) / f0
            jones_f = jnp.asarray(Z0 + frat * Z1)
            data, cdata = _one_band(f0, jones_f, seed=f, nstations=N)
            data = data.replace(freqs=jnp.asarray([freqs[f]], jnp.float64))
            bands.append((data, cdata))
            p0s.append(
                jones_to_params(
                    random_jones(M, N, seed=500, amp=0.0, dtype=np.complex128)
                )[:, None, :]
            )
        B = consensus.setup_polynomials(freqs, f0, 2, consensus.POLY_ORDINARY)
        return bands, p0s, freqs, B, M

    def test_data_multiplexing_16_subbands_on_8(self, devices8):
        """Nf=16 > ndev=8: two sub-band slots per device with the
        Scurrent rotation (sagecal_master.cpp:157-206).  Convergence bar
        matches the 8-on-8 case (each slot gets nadmm/2 solves, so give
        it 2x the rounds)."""
        from sagecal_tpu.solvers.sage import predict_full_model

        # 8 sub-bands on a 4-device mesh: same Scurrent semantics at half
        # the 8-device collective cost (suite time budget, round-3)
        bands, p0s, freqs, B, M = self._polyband_problem(8)
        mesh = Mesh(np.array(devices8[:4]), ("freq",))
        fn = make_admm_mesh_fn(
            mesh, nadmm=12, max_emiter=1, plain_emiter=1,
            lm_config=LMConfig(itmax=6), bb_rho=False,
        )
        out = fn(
            stack_for_mesh([b[0] for b in bands]),
            stack_for_mesh([b[1] for b in bands]),
            jnp.stack(p0s),
            jnp.full((8, M), 20.0, jnp.float64),
            jnp.asarray(B),
        )
        assert out.p.shape[0] == 8
        assert float(out.primal_res[-1]) < 0.05, np.asarray(out.primal_res)
        # every band's solution (including slot-1 bands) fits its data
        for f in (0, 1, 7):
            data_f, cdata_f = bands[f]
            model = predict_full_model(out.p[f], cdata_f, data_f)
            res = float(
                jnp.linalg.norm((data_f.vis - model).ravel())
                / jnp.linalg.norm(data_f.vis.ravel())
            )
            assert res < 0.05, (f, res)

    def test_rtr_admm_local_solver(self, devices8):
        """Mesh ADMM with the robust-RTR local x-step — the reference MPI
        slave's default solver (rtr_solve_nocuda_robust_admm,
        admm_solve.c:346)."""
        from sagecal_tpu.solvers.sage import (
            SM_RTR_OSRLM_RLBFGS,
            predict_full_model,
        )

        # smoke-level: the robust-RTR x-step's tCG/EM while-loops are
        # minutes-per-compile on a time-shared virtual CPU mesh (measured
        # 25+ CPU-min at 4 bands/N=8/nadmm=5), so this only verifies the
        # dispatch compiles, runs, and does not diverge; RTR solver DEPTH
        # is covered by tests/test_rtr.py on a single device
        bands, p0s, freqs, B, M = self._polyband_problem(2, N=6)
        mesh = Mesh(np.array(devices8[:2]), ("freq",))
        fn = make_admm_mesh_fn(
            mesh, nadmm=2, max_emiter=1, plain_emiter=1,
            lm_config=LMConfig(itmax=2), bb_rho=False,
            solver_mode=SM_RTR_OSRLM_RLBFGS,
        )
        out = fn(
            stack_for_mesh([b[0] for b in bands]),
            stack_for_mesh([b[1] for b in bands]),
            jnp.stack(p0s),
            jnp.full((2, M), 5.0, jnp.float64),
            jnp.asarray(B),
        )
        assert np.all(np.isfinite(np.asarray(out.p)))
        data0, cdata0 = bands[0]
        model = predict_full_model(out.p[0], cdata0, data0)
        res = float(
            jnp.linalg.norm((data0.vis - model).ravel())
            / jnp.linalg.norm(data0.vis.ravel())
        )
        assert res < 1.0, res  # no divergence; depth covered elsewhere


# ---------------------------------------------------------------------------
# the band programs and the named mesh program (4 of the 8 virtual devices)


def _bands_on_mesh(Nf=8, nchan=2, N=8, tilesz=2, flags=False, seed=3):
    """Nf full-channel band tiles stacked for the mesh (one freq0 static,
    each band's own channel frequencies), with per-band VisData views."""
    bands = []
    for b in range(Nf):
        d = make_visdata(nstations=N, tilesz=tilesz, nchan=nchan,
                         freq0=140e6 + 2e6 * b, seed=seed + b,
                         dtype=np.float64)
        rng = np.random.default_rng(seed + 100 + b)
        vis = rng.standard_normal(d.vis.shape) \
            + 1j * rng.standard_normal(d.vis.shape)
        mask = (rng.uniform(size=d.mask.shape) > 0.3) if flags else \
            np.ones(d.mask.shape, bool)
        bands.append(d.replace(vis=jnp.asarray(vis),
                               mask=jnp.asarray(mask, jnp.float64)))
    f0 = np.asarray([d.freq0 for d in bands])
    stack = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]),
        *[d.replace(freq0=150e6, deltaf=nchan * 180e3) for d in bands])
    return bands, stack, f0


def _sky(uneven=False):
    """Two clusters of 1 and 2 point sources; ``uneven``: four single
    sources and one cluster of 24, padded to 24 sources each."""
    if uneven:
        rng = np.random.default_rng(11)
        return ([point_source_batch([0.01 * k], [-0.01 * k], [1.0 + k],
                                    dtype=jnp.float64) for k in range(4)]
                + [point_source_batch(rng.uniform(-0.03, 0.03, 24),
                                      rng.uniform(-0.03, 0.03, 24),
                                      rng.uniform(0.1, 1.0, 24),
                                      dtype=jnp.float64)])
    return [point_source_batch([0.01], [0.02], [2.0], dtype=jnp.float64),
            point_source_batch([-0.02, 0.01], [0.0, 0.015], [1.0, 0.5],
                               dtype=jnp.float64)]


def _prep(devices, stack, f0, nchunks=(1, 2), sky=None):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sagecal_tpu.parallel.mesh import make_band_prep_fn, stack_sources

    sky = _sky() if sky is None else sky
    mesh = Mesh(np.array(devices), ("freq",))
    prep = make_band_prep_fn(mesh, nchunks, fdelta_chan=180e3)
    full, f0d = jax.device_put((stack, f0), NamedSharding(mesh, P("freq")))
    return mesh, full, prep(full, f0d, stack_sources(sky),
                            jnp.linspace(20.0, 10.0, len(sky)))


@pytest.mark.parametrize("uneven", [False, True])
def test_band_coherencies_equal_build_cluster_data(devices8, uneven):
    """The band program's coherencies, averaged and per channel, are
    build_cluster_data's of each band (float64, 1e-6 relative), also for
    cluster sizes so uneven that most of the padded sources are zero."""
    sky = _sky(uneven)
    nchunks = (1, 2) + (1,) * (len(sky) - 2)
    bands, stack, f0 = _bands_on_mesh()
    mesh, full, (data, cdata, coh_chan, rho, fratio) = _prep(
        devices8[:4], stack, f0, nchunks, sky)
    assert cdata.coh.sharding.spec == ("freq",)
    for b, d in enumerate(bands):
        d = d.replace(deltaf=2 * 180e3)
        avg = d.replace(vis=d.vis[:1], mask=d.mask[:1],
                        freqs=jnp.asarray([d.freq0]))
        want = build_cluster_data(avg, sky, list(nchunks))
        want_ch = build_cluster_data(d, sky, list(nchunks), fdelta=180e3)
        for got, ref in ((cdata.coh[b], want.coh),
                         (coh_chan[b], want_ch.coh)):
            ref = np.asarray(ref)
            err = np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref))
            assert err <= 1e-6, (b, err)
        np.testing.assert_array_equal(cdata.chunk_map[b], want.chunk_map)
        np.testing.assert_array_equal(cdata.nchunk[b], want.nchunk)
    np.testing.assert_array_equal(fratio, 1.0)
    np.testing.assert_allclose(
        rho, np.tile(np.linspace(20.0, 10.0, len(sky)), (8, 1)))


def test_channel_average_equals_load_tile(tmp_path, devices8):
    """The on-chip channel average is VisDataset.load_tile(
    average_channels=True)'s: the mask (flags and uv cut) exactly, the
    data on every unmasked row to float rounding."""
    from sagecal_tpu.io.dataset import VisDataset, create_dataset
    from sagecal_tpu.parallel.mesh import average_channels

    rng = np.random.default_rng(5)
    nt, nb, F = 3, 28, 4
    path = str(tmp_path / "f.h5")
    create_dataset(
        path, u=rng.uniform(-3e3, 3e3, (nt, nb)),
        v=rng.uniform(-3e3, 3e3, (nt, nb)), w=rng.uniform(-50, 50, (nt, nb)),
        ant_p=np.triu_indices(8, 1)[0], ant_q=np.triu_indices(8, 1)[1],
        vis=(rng.standard_normal((nt, nb, F, 2, 2))
             + 1j * rng.standard_normal((nt, nb, F, 2, 2))),
        flag=rng.uniform(size=(nt, nb, F)) < 0.4,
        freqs=150e6 + 180e3 * np.arange(F), nstations=8, deltaf=F * 180e3)
    for cut in (dict(), dict(min_uvcut=1000.0, max_uvcut=2500.0)):
        with VisDataset(path) as ds:
            want = ds.load_tile(0, nt, average_channels=True, **cut)
            full = ds.load_tile(0, nt, average_channels=False, **cut)
        vis, mask = jax.jit(average_channels)(full.vis, full.mask)
        np.testing.assert_array_equal(mask, want.mask)
        ok = np.asarray(want.mask)[0] > 0
        assert 0 < ok.sum() < ok.size
        rows = slice(None) if not cut else ok
        np.testing.assert_allclose(np.asarray(vis)[..., rows],
                                   np.asarray(want.vis)[..., rows],
                                   rtol=1e-12, atol=1e-12)


def test_band_residual_equals_calculate_residuals(devices8):
    """At one channel the band residual program gives each band's
    calculate_residuals, from the per-band J and from B_f Z (-U)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sagecal_tpu.ops.residual import calculate_residuals
    from sagecal_tpu.parallel.mesh import make_band_residual_fn

    bands, stack, f0 = _bands_on_mesh(nchan=1)
    mesh, full, (data, cdata, coh_chan, _, _) = _prep(devices8[:4], stack,
                                                      f0)
    sh = NamedSharding(mesh, P("freq"))
    rng = np.random.default_rng(9)
    p = rng.standard_normal((8, 2, 2, 64)) * 0.3
    Z = rng.standard_normal((2, 2, 128)) * 0.3
    B = consensus.setup_polynomials(f0, 150e6, 2, consensus.POLY_BERNSTEIN)
    for global_solution in (False, True):
        fn = make_band_residual_fn(mesh, global_solution=global_solution)
        res = fn(full, coh_chan, cdata.chunk_map, cdata.nchunk,
                 jax.device_put(p, sh), jnp.asarray(Z),
                 jax.device_put(B, sh))
        for b in (0, 5):
            d = jax.tree_util.tree_map(lambda x: x[b], data)
            cd = jax.tree_util.tree_map(lambda x: x[b], cdata)
            pb = (consensus.bz_for_freq(jnp.asarray(Z), jnp.asarray(B[b]))
                  .reshape(2, 2, 64) if global_solution else p[b])
            want = calculate_residuals(d, cd._replace(coh=coh_chan[b]), pb)
            np.testing.assert_allclose(res[b], want, rtol=1e-10,
                                       atol=1e-10)


def _xstep_problem(Nf=32, N=4):
    freqs = np.linspace(115e6, 185e6, Nf)
    bands, p0s = [], []
    for f in range(Nf):
        jones = random_jones(1, N, seed=f, amp=0.1, dtype=np.complex128)
        data = make_visdata(nstations=N, tilesz=1, nchan=1, freq0=150e6,
                            seed=f, dtype=np.float64)
        cl = [point_source_batch([0.0], [0.0], [2.0], dtype=jnp.float64)]
        data = corrupt_and_observe(data, cl, jones=jones, noise_sigma=1e-3,
                                   seed=f)
        data = data.replace(freqs=jnp.asarray([freqs[f]]))
        bands.append((data, build_cluster_data(data, cl, [1])))
        p0s.append(jones_to_params(random_jones(
            1, N, seed=500, amp=0.0, dtype=np.complex128))[:, None, :])
    B = consensus.setup_polynomials(freqs, 150e6, 2, consensus.POLY_BERNSTEIN)
    return (stack_for_mesh([b[0] for b in bands]),
            stack_for_mesh([b[1] for b in bands]), jnp.stack(p0s),
            jnp.full((Nf, 1), 20.0), jnp.asarray(B))


@pytest.mark.parametrize("ndev", [4, 1])
def test_xsteps_counts_the_slot_schedule(devices8, ndev):
    """32 bands at -A 10: the admm-0 solve of every band, then one
    active slot per device in each of the 9 later rounds: 32 + 9 x 4 =
    68 on four devices, 32 + 9 = 41 on one."""
    mesh = Mesh(np.array(devices8[:ndev]), ("freq",))
    fn = make_admm_mesh_fn(mesh, nadmm=10, max_emiter=1, plain_emiter=1,
                           lm_config=LMConfig(itmax=1))
    out = fn(*_xstep_problem())
    assert int(out.xsteps) == 32 + 9 * ndev


def test_every_op_of_the_mesh_and_band_programs_is_scoped(devices8):
    """Each op the mesh ADMM and the band programs emit that computes
    something lies under admm or bands (the arguments, their bitcasts and
    folded constants move or name data only), and the named parts are
    there: admm/plain, admm/xstep, admm/zstep, bands/coh,
    bands/residual."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sagecal_tpu.obs.scopes import MESH_SCOPES, hlo_scopes
    from sagecal_tpu.parallel.mesh import (
        make_band_prep_fn, make_band_residual_fn, stack_sources,
    )

    jax.clear_caches()  # trace anew: scopes are read at trace time
    bands, stack, f0 = _bands_on_mesh(Nf=4, nchan=2, N=4, tilesz=1)
    mesh, full, (data, cdata, coh_chan, rho, _) = _prep(
        devices8[:4], stack, f0)
    B = jnp.asarray(consensus.setup_polynomials(
        f0, 150e6, 2, consensus.POLY_BERNSTEIN))
    p0 = jnp.zeros((4, 2, 2, 32))
    admm = make_admm_mesh_fn(mesh, nadmm=2, max_emiter=1, plain_emiter=1,
                             lm_config=LMConfig(itmax=1))
    prep = make_band_prep_fn(mesh, (1, 2), fdelta_chan=180e3)
    resid = make_band_residual_fn(mesh)
    texts = [
        admm.inner_jit.lower(data, cdata, p0, rho, B).compile().as_text(),
        prep.lower(full, jax.device_put(f0, NamedSharding(mesh, P("freq"))),
                   stack_sources(_sky()), jnp.asarray([20.0, 10.0]))
        .compile().as_text(),
        resid.lower(full, coh_chan, cdata.chunk_map, cdata.nchunk, p0,
                    jnp.zeros((2, 2, 64)), B).compile().as_text(),
    ]
    trivial = {"parameter", "tuple", "get-tuple-element", "constant",
               "bitcast", "copy"}
    inst = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.+?\s([\w\-]+)\(")
    used, made, n_inst = set(), [], 0
    for text in texts:
        scopes = hlo_scopes(text)
        n_inst += len(scopes)
        outside = []
        for line in text.splitlines():
            m = inst.match(line)
            if (m and m.group(2) not in trivial and m.group(1) in scopes
                    and scopes[m.group(1)].split("/")[0] not in MESH_SCOPES):
                # an op XLA made itself carries no op_name
                (outside if "op_name=" in line else made).append(
                    line.strip()[:120])
        assert outside == [], outside[:5]
        used |= set(scopes.values())
    # ops XLA made in the entry computations carry no op_name (19 of 817
    # here, in these small programs)
    assert len(made) < 0.05 * n_inst, made
    for want in ("admm/plain", "admm/xstep", "admm/zstep", "bands/coh",
                 "bands/residual"):
        assert any(s == want or s.startswith(want + "/") for s in used), want


def test_mesh_and_band_programs_multiply_at_highest_precision(devices8):
    """Every matrix product of the mesh ADMM and the band programs is
    lowered at HIGHEST precision.  At the TPU's DEFAULT (one bf16 pass)
    the band programs' coherencies and residuals lost three digits
    (resid_gap 1.2e-2 to 1.4e-2 on the v5e); the CPU multiplies in full
    f32 either way, so only the lowering shows it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sagecal_tpu.parallel.mesh import (
        make_band_prep_fn, make_band_residual_fn, stack_sources,
    )

    bands, stack, f0 = _bands_on_mesh(Nf=4, nchan=2, N=4, tilesz=1)
    mesh, full, (data, cdata, coh_chan, rho, _) = _prep(
        devices8[:4], stack, f0)
    B = jnp.asarray(consensus.setup_polynomials(
        f0, 150e6, 2, consensus.POLY_BERNSTEIN))
    p0 = jnp.zeros((4, 2, 2, 32))
    admm = make_admm_mesh_fn(mesh, nadmm=2, max_emiter=1, plain_emiter=1,
                             lm_config=LMConfig(itmax=1))
    texts = [
        admm.inner_jit.lower(data, cdata, p0, rho, B).as_text(),
        make_band_prep_fn(mesh, (1, 2), fdelta_chan=180e3).lower(
            full, jax.device_put(f0, NamedSharding(mesh, P("freq"))),
            stack_sources(_sky()), jnp.asarray([20.0, 10.0])).as_text(),
        make_band_residual_fn(mesh).lower(
            full, coh_chan, cdata.chunk_map, cdata.nchunk, p0,
            jnp.zeros((2, 2, 64)), B).as_text(),
    ]
    for text in texts:
        dots = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert dots
        low = [ln.strip()[:160] for ln in dots
               if "precision = [HIGHEST, HIGHEST]" not in ln]
        assert low == [], low[:3]
