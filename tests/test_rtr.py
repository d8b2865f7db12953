"""RTR / NSD manifold solver tests: geometry oracles + calibration
recovery + SAGE integration of the RTR solver modes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.core.types import jones_to_params, params_to_jones
from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata, random_jones
from sagecal_tpu.ops.rime import point_source_batch
from sagecal_tpu.solvers.rtr import (
    RTRConfig,
    _g,
    _make_fns,
    _project,
    _rtr_single,
    _station_iw,
    nsd_solve,
    rtr_solve,
    rtr_solve_robust,
)
from sagecal_tpu.solvers.sage import (
    SM_NSD_RLBFGS,
    SM_RTR_OSLM_LBFGS,
    SageConfig,
    build_cluster_data,
    sagefit,
)


def _setup(nstations=8, noise=1e-4, seed=0, amp=0.25, outliers=0):
    data = make_visdata(nstations=nstations, tilesz=2, nchan=1, dtype=np.float64)
    clusters = [point_source_batch([0.0], [0.0], [2.0], dtype=jnp.float64)]
    jones = random_jones(1, nstations, seed=seed, amp=amp, dtype=np.complex128)
    data = corrupt_and_observe(data, clusters, jones=jones, noise_sigma=noise, seed=seed)
    if outliers:
        vis = np.array(data.vis)  # (F, 4, rows)
        rng = np.random.default_rng(42)
        idx = rng.choice(vis.shape[-1], outliers, replace=False)
        vis[..., idx] += 25.0 * (rng.standard_normal((1, 4, outliers))
                                 + 1j * rng.standard_normal((1, 4, outliers)))
        data = data.replace(vis=jnp.asarray(vis))
    cdata = build_cluster_data(data, clusters, [1])
    p0 = jones_to_params(random_jones(1, nstations, seed=99, amp=0.0,
                                      dtype=np.complex128))[:, None, :]
    return data, cdata, p0, jones


class TestGeometry:
    def test_projection_is_idempotent_and_horizontal(self):
        rng = np.random.default_rng(0)
        N = 6
        x = jnp.asarray(rng.standard_normal((N, 2, 2))
                        + 1j * rng.standard_normal((N, 2, 2)))
        z = jnp.asarray(rng.standard_normal((N, 2, 2))
                        + 1j * rng.standard_normal((N, 2, 2)))
        h = _project(x, z)
        h2 = _project(x, h)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=1e-8)
        # horizontality: X^H h must be Hermitian (skew part removed)
        X = np.asarray(x).reshape(2 * N, 2)
        H = np.asarray(h).reshape(2 * N, 2)
        S = np.conj(X.T) @ H
        np.testing.assert_allclose(S, np.conj(S.T), atol=1e-8)

    def test_vertical_direction_projects_to_zero(self):
        """Vertical space = X*Om with Om skew-Hermitian (the unitary
        gauge directions); projection must annihilate it."""
        rng = np.random.default_rng(1)
        N = 5
        x = jnp.asarray(rng.standard_normal((N, 2, 2))
                        + 1j * rng.standard_normal((N, 2, 2)))
        Om = rng.standard_normal((2, 2)) + 1j * rng.standard_normal((2, 2))
        Om = Om - np.conj(Om.T)  # skew-Hermitian
        X = np.asarray(x).reshape(2 * N, 2)
        v = jnp.asarray((X @ Om).reshape(N, 2, 2))
        h = _project(x, v)
        assert float(jnp.max(jnp.abs(h))) < 1e-8

    def test_metric(self):
        a = jnp.asarray([[[1.0 + 1j, 0], [0, 0]]])
        assert float(_g(a, a)) == pytest.approx(4.0)


class TestRTRSolve:
    def test_recovers_gains(self):
        data, cdata, p0, jones = _setup()
        res = rtr_solve(
            data.vis, cdata.coh[0], data.mask, data.ant_p, data.ant_q,
            cdata.chunk_map[0], p0[0],
            RTRConfig(itmax_rsd=10, itmax_rtr=20, max_inner=20),
        )
        assert float(jnp.sum(res.cost)) < 0.05 * float(jnp.sum(res.cost0)), (
            float(jnp.sum(res.cost0)), float(jnp.sum(res.cost)))

    def test_never_worse_than_start(self):
        data, cdata, p0, jones = _setup()
        # start AT the truth: solver must not degrade it
        pt = jones_to_params(jones)[:, None, :]
        res = rtr_solve(
            data.vis, cdata.coh[0], data.mask, data.ant_p, data.ant_q,
            cdata.chunk_map[0], pt[0], RTRConfig(itmax_rsd=2, itmax_rtr=5),
        )
        assert float(jnp.sum(res.cost)) <= float(jnp.sum(res.cost0)) * (1 + 1e-12)

    def test_nsd_reduces_cost(self):
        data, cdata, p0, jones = _setup()
        res = nsd_solve(
            data.vis, cdata.coh[0], data.mask, data.ant_p, data.ant_q,
            cdata.chunk_map[0], p0[0], itmax=40,
        )
        assert float(jnp.sum(res.cost)) < 0.5 * float(jnp.sum(res.cost0))

    def test_robust_rtr_with_outliers(self):
        data, cdata, p0, jones = _setup(noise=1e-3, outliers=5)
        res, nu = rtr_solve_robust(
            data.vis, cdata.coh[0], data.mask, data.ant_p, data.ant_q,
            cdata.chunk_map[0], p0[0],
            RTRConfig(itmax_rsd=8, itmax_rtr=15, max_inner=15),
            em_iters=3,
        )
        # compare recovered Jones to truth up to a global unitary via the
        # corrupted-model residual on the CLEAN rows
        jsol = params_to_jones(res.p)[0]
        jtrue = np.asarray(jones[0])
        # model from solution vs model from truth (gauge-invariant)
        from sagecal_tpu.solvers.sage import cluster_model

        m_sol = cluster_model(res.p, cdata.coh[0], cdata.chunk_map[0],
                              data.ant_p, data.ant_q)
        m_true = cluster_model(jones_to_params(jones)[:, None, :][0],
                               cdata.coh[0], cdata.chunk_map[0],
                               data.ant_p, data.ant_q)
        rel = float(jnp.linalg.norm((m_sol - m_true).ravel())
                    / jnp.linalg.norm(m_true.ravel()))
        assert rel < 0.05, rel
        assert 2.0 <= float(nu) <= 30.0


# ---------------------------------------------------------------------------
# the RSD warm-up stops early and exactly
# ---------------------------------------------------------------------------

def _scan_rsd(vis, coh, rowmask, ant_p, ant_q, x0, cfg, itmax_dyn=None):
    """The warm-up as a fixed scan of ``cfg.itmax_rsd`` iterations that
    evaluates every cost afresh and masks each acceptance by the dynamic
    bound: the oracle of the early-stopping loop.  Returns the warmed-up
    x, and per iteration whether it moved x and its Armijo halvings."""
    N = x0.shape[0]
    cost_c, grad_fn, _ = _make_fns(vis, coh, rowmask, ant_p, ant_q, None)
    iw = _station_iw(rowmask, ant_p, ant_q, N)
    rsd_bound = (
        jnp.asarray(cfg.itmax_rsd)
        if itmax_dyn is None
        else jnp.minimum(cfg.itmax_rsd, itmax_dyn + 5)
    )

    def rsd_iter(x, i):
        g = grad_fn(x, iw)
        fx = cost_c(x)
        gg = _g(g, g)
        beta0 = jnp.asarray(1.0, gg.dtype)

        def armijo_cond(st):
            k, beta = st
            return (k < 12) & (cost_c(x - beta * g) > fx - 1e-4 * beta * gg)

        def armijo_body(st):
            k, beta = st
            return k + 1, beta * 0.5

        k, beta = jax.lax.while_loop(armijo_cond, armijo_body, (0, beta0))
        improved = (cost_c(x - beta * g) < fx) & (i < rsd_bound)
        return jnp.where(improved, x - beta * g, x), (improved, k)

    return jax.lax.scan(rsd_iter, x0, jnp.arange(cfg.itmax_rsd))


def _oracle(vis, coh, rowmask, ant_p, ant_q, x0, cfg, itmax_dyn=None):
    """The scan warm-up, then the same trust region started from its x
    with no warm-up of its own: (x, cost, TRSteps, moved, halvings)."""
    x, (moved, halvings) = _scan_rsd(vis, coh, rowmask, ant_p, ant_q, x0,
                                     cfg, itmax_dyn)
    xf, _, cost, steps = _rtr_single(
        vis, coh, rowmask, ant_p, ant_q, x, cfg.replace(itmax_rsd=0), None,
        itmax_dyn)
    return xf, cost, steps, moved, halvings


def _rsd_case(nchunk=1, bright=(), dtype=np.float64):
    """The ``_setup()`` solve with ``nchunk`` hybrid chunks, the source
    100x brighter in the chunks listed in ``bright``, in ``dtype``."""
    data, _, p0, _ = _setup()
    clusters = [point_source_batch([0.0], [0.0], [2.0], dtype=jnp.float64)]
    cdata = build_cluster_data(data, clusters, [nchunk])
    cmap = cdata.chunk_map[0]
    scale = jnp.where(jnp.isin(cmap, jnp.asarray(bright, cmap.dtype)),
                      100.0, 1.0)
    cdt = np.result_type(dtype, np.complex64)
    vis = (data.vis * scale).astype(cdt)
    coh = (cdata.coh[0] * scale).astype(cdt)
    p0 = jnp.repeat(p0[0], nchunk, axis=0).astype(dtype)
    return (vis, coh, data.mask.astype(dtype), data.ant_p, data.ant_q, cmap,
            p0)


def _assert_same(a, b, dtype, rtol_f32=1e-6):
    """Equal to 1e-12 of the larger magnitude in f64 (where the loop and
    the scan agree to the bit on the CPU), to ``rtol_f32`` in f32, where
    the carried costs round apart from the scan's fresh ones."""
    rtol = 1e-12 if dtype == np.float64 else rtol_f32
    b = np.asarray(b)
    np.testing.assert_allclose(np.asarray(a), b, rtol=rtol,
                               atol=rtol * np.max(np.abs(b)))


def _lane_oracle(case, c, cfg, itmax_dyn=None):
    vis, coh, mask, ant_p, ant_q, cmap, p0 = case
    rowmask = mask * (cmap == c)[None, :].astype(mask.dtype)
    return jax.jit(lambda x0: _oracle(
        vis, coh, rowmask, ant_p, ant_q, x0, cfg, itmax_dyn))(
        params_to_jones(p0[c]))


_CFG = RTRConfig(itmax_rsd=11, itmax_rtr=20, max_inner=20)


class TestRSDStop:
    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    @pytest.mark.parametrize("bright", [False, True],
                             ids=["every_step_moves", "line_search_fails"])
    def test_matches_the_scan(self, bright, dtype):
        """Where every step moves, the loop runs all 11 iterations as the
        scan does; where the first line search fails (the source 100x
        brighter: all 13 trials rejected), it stops after one.  Either
        way the solve equals the scan's."""
        case = _rsd_case(bright=(0,) if bright else (), dtype=dtype)
        xo, cost_o, steps_o, moved, halvings = _lane_oracle(case, 0, _CFG)
        if bright:
            assert not np.any(moved) and np.all(np.asarray(halvings) == 12)
        else:
            assert np.all(moved)
        res = jax.jit(lambda p: rtr_solve(*case[:-1], p, _CFG))(case[-1])
        _assert_same(res.p[0], jones_to_params(xo), dtype)
        # the converged f32 cost sums residuals a thousandth of the model,
        # each rounded at the model's scale: 1e-3 of it is that rounding
        _assert_same(res.cost[0], cost_o, dtype, rtol_f32=1e-3)
        for k in ("tr", "tcg", "capped"):
            assert int(getattr(res.steps, k)) == int(getattr(steps_o, k)), k
        assert int(res.steps.rsd) == (1 if bright else _CFG.itmax_rsd)
        assert int(res.steps.rsd_moved) == (0 if bright else _CFG.itmax_rsd)

    def test_stops_at_the_dynamic_bound(self):
        """itmax_dynamic 2 bounds the warm-up at min(11, 2 + 5) = 7
        iterations, every one of which moves x."""
        case = _rsd_case()
        dyn = jnp.asarray(2, jnp.int32)
        xo, cost_o, _, moved, _ = _lane_oracle(case, 0, _CFG, dyn)
        assert np.asarray(moved).tolist() == [True] * 7 + [False] * 4
        res = jax.jit(lambda p, d: rtr_solve(
            *case[:-1], p, _CFG, itmax_dynamic=d))(case[-1], dyn)
        assert int(res.steps.rsd) == int(res.steps.rsd_moved) == 7
        _assert_same(res.p[0], jones_to_params(xo), np.float64)
        _assert_same(res.cost[0], cost_o, np.float64)

    def test_batched_lanes_stop_on_their_own(self):
        """Two chunks under one vmap, chunk 1 bright: lane 0 moves on
        every iteration while lane 1 stops after its first rejection,
        and each lane equals its own unbatched solve and the scan's."""
        case = _rsd_case(nchunk=2, bright=(1,))
        vis, coh, mask, ant_p, ant_q, cmap, p0 = case
        res = jax.jit(lambda p: rtr_solve(*case[:-1], p, _CFG))(p0)
        assert int(res.steps.loops) == 2
        assert int(res.steps.rsd) == _CFG.itmax_rsd + 1
        assert int(res.steps.rsd_moved) == _CFG.itmax_rsd
        for c in range(2):
            rowmask = mask * (cmap == c)[None, :].astype(mask.dtype)
            x1, _, cost1, steps1 = jax.jit(lambda x0: _rtr_single(
                vis, coh, rowmask, ant_p, ant_q, x0, _CFG, None))(
                params_to_jones(p0[c]))
            assert int(steps1.rsd) == (1 if c else _CFG.itmax_rsd)
            _assert_same(res.p[c], jones_to_params(x1), np.float64)
            _assert_same(res.cost[c], cost1, np.float64)
            xo, cost_o, _, _, _ = _lane_oracle(case, c, _CFG)
            _assert_same(res.p[c], jones_to_params(xo), np.float64)


@pytest.mark.slow
class TestSageRTRModes:
    def test_sage_rtr_mode(self):
        data, cdata, p0, _ = _setup(nstations=8)
        out = sagefit(
            data, cdata, p0,
            SageConfig(max_emiter=2, max_iter=5, max_lbfgs=10,
                       solver_mode=SM_RTR_OSLM_LBFGS),
        )
        assert float(out.res_1) < 0.2 * float(out.res_0)

    def test_sage_nsd_mode(self):
        data, cdata, p0, _ = _setup(nstations=8)
        out = sagefit(
            data, cdata, p0,
            SageConfig(max_emiter=2, max_iter=5, max_lbfgs=10,
                       solver_mode=SM_NSD_RLBFGS),
        )
        assert float(out.res_1) < 0.3 * float(out.res_0)
