import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.core.types import jones_to_params, params_to_jones, identity_jones
from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata, random_jones
from sagecal_tpu.ops.rime import point_source_batch, predict_coherencies
from sagecal_tpu.solvers.lbfgs import LBFGSMemory, lbfgs_fit
from sagecal_tpu.solvers.lm import LMConfig, lm_solve, os_lm_solve
from sagecal_tpu.solvers.robust import robust_lm_solve, update_w_and_nu


def rosenbrock(x):
    # the reference's own LBFGS oracle (test/Dirac/demo.c:95): min at 1...1
    return jnp.sum(100.0 * (x[1::2] - x[0::2] ** 2) ** 2 + (1.0 - x[0::2]) ** 2)


def test_lbfgs_rosenbrock():
    n = 20
    x0 = jnp.asarray(np.full(n, -1.2), jnp.float32)
    res = lbfgs_fit(rosenbrock, None, x0, itmax=200, M=7)
    assert float(res.cost) < 1e-3, float(res.cost)
    np.testing.assert_allclose(np.asarray(res.p), np.ones(n), atol=0.05)


def test_lbfgs_jit_compatible():
    n = 8
    fit = jax.jit(lambda x0: lbfgs_fit(rosenbrock, None, x0, itmax=100, M=5).p)
    p = fit(jnp.asarray(np.full(n, 0.5), jnp.float32))
    np.testing.assert_allclose(np.asarray(p), np.ones(n), atol=0.05)


def test_lbfgs_minibatch_memory_persists():
    # quadratic with batch-dependent data: memory threads across calls
    n = 6
    A = jnp.asarray(np.diag(np.arange(1, n + 1)), jnp.float32)

    def make_cost(shift):
        return lambda x: 0.5 * jnp.dot(x - shift, A @ (x - shift))

    mem = LBFGSMemory.init(n, M=4)
    x = jnp.ones((n,), jnp.float32) * 5.0
    for b in range(3):
        cost = make_cost(jnp.zeros(n))
        res = lbfgs_fit(cost, None, x, itmax=10, M=4, memory=mem, minibatch=True)
        x, mem = res.p, res.memory
    assert int(mem.niter) > 0
    assert float(jnp.linalg.norm(x)) < 0.5


def _simulated_single_cluster(nst=7, tilesz=2, noise=0.0, seed=3):
    d = make_visdata(nstations=nst, tilesz=tilesz, nchan=1, seed=seed)
    rng = np.random.default_rng(seed)
    S = 3
    src = point_source_batch(
        jnp.asarray(0.01 * rng.standard_normal(S), jnp.float32),
        jnp.asarray(0.01 * rng.standard_normal(S), jnp.float32),
        jnp.asarray(rng.uniform(1.0, 3.0, S), jnp.float32),
    )
    J = random_jones(1, nst, seed=seed, amp=0.2)
    obs = corrupt_and_observe(d, [src], jones=J, noise_sigma=noise, seed=seed + 1)
    coh = predict_coherencies(d.u, d.v, d.w, d.freqs, src)
    return d, obs, coh, J


def _gain_consistency_err(j_est, j_true, coh, ant_p, ant_q):
    """Compare J_p C J_q^H predictions (gauge-invariant comparison)."""
    from sagecal_tpu.core.types import corrupt_flat

    m1 = corrupt_flat(j_est, coh, ant_p, ant_q)
    m2 = corrupt_flat(j_true, coh, ant_p, ant_q)
    return float(jnp.max(jnp.abs(m1 - m2)) / jnp.max(jnp.abs(m2)))


def test_lm_recovers_jones():
    d, obs, coh, J = _simulated_single_cluster()
    nst = d.nstations
    p0 = jones_to_params(identity_jones(nst))[None]  # (1, 8N)
    chunk_map = jnp.zeros((d.rows,), jnp.int32)
    res = lm_solve(
        obs.vis, coh, obs.mask, obs.ant_p, obs.ant_q, chunk_map, p0,
        LMConfig(itmax=30),
    )
    assert float(res.cost[0]) < 1e-5 * float(res.cost0[0]), (res.cost0, res.cost)
    j_est = params_to_jones(res.p)[0]
    err = _gain_consistency_err(j_est, J[0], coh, obs.ant_p, obs.ant_q)
    assert err < 1e-2, err


def test_lm_hybrid_chunks():
    # two chunks solving two halves of the tile with different true gains
    d = make_visdata(nstations=6, tilesz=2, nchan=1, seed=11)
    rng = np.random.default_rng(11)
    src = point_source_batch(
        jnp.asarray([0.0, 0.01], jnp.float32),
        jnp.asarray([0.005, -0.01], jnp.float32),
        jnp.asarray([2.0, 1.0], jnp.float32),
    )
    coh = predict_coherencies(d.u, d.v, d.w, d.freqs, src)
    J2 = random_jones(2, 6, seed=12, amp=0.15)  # one per chunk
    from sagecal_tpu.core.types import corrupt_flat

    chunk_map = d.time_idx  # timeslot == chunk
    vis = corrupt_flat(J2, coh, d.ant_p, d.ant_q, chunk_map)
    p0 = jnp.broadcast_to(jones_to_params(identity_jones(6))[None], (2, 8 * 6))
    res = lm_solve(vis, coh, d.mask, d.ant_p, d.ant_q, chunk_map, p0, LMConfig(itmax=30))
    assert np.all(np.asarray(res.cost) < 1e-5 * np.asarray(res.cost0))


def test_os_lm_reduces_cost():
    d, obs, coh, J = _simulated_single_cluster(nst=8, tilesz=2)
    p0 = jones_to_params(identity_jones(8))[None]
    chunk_map = jnp.zeros((d.rows,), jnp.int32)
    res = os_lm_solve(
        obs.vis, coh, obs.mask, obs.ant_p, obs.ant_q, chunk_map, p0,
        LMConfig(itmax=16), nsubsets=4,
    )
    assert float(res.cost[0]) < 1e-3 * float(res.cost0[0])


def test_update_w_and_nu():
    rng = np.random.default_rng(0)
    nu_true = 4.0
    e = jnp.asarray(rng.standard_t(nu_true, 20000), jnp.float32)
    sqrt_w, nu = update_w_and_nu(e, jnp.asarray(8.0))
    w = np.asarray(sqrt_w) ** 2
    # heavy-tail points get down-weighted
    assert w[np.abs(np.asarray(e)) > 5].max() < 0.5
    assert 2.0 <= float(nu) <= 10.0


def test_robust_lm_with_outliers():
    d, obs, coh, J = _simulated_single_cluster(nst=7, tilesz=2, noise=1e-3)
    # inject gross outliers into 5% of rows (flat layout: rows on axis -1)
    rng = np.random.default_rng(9)
    vis = np.asarray(obs.vis).copy()  # (F, 4, rows)
    rows = vis.shape[-1]
    bad = rng.choice(rows, size=rows // 20, replace=False)
    vis[..., bad] += 50.0 * (rng.standard_normal((1, 4, len(bad))) + 1j)
    visj = jnp.asarray(vis)
    p0 = jones_to_params(identity_jones(7))[None]
    chunk_map = jnp.zeros((rows,), jnp.int32)
    res_r, nu = robust_lm_solve(
        visj, coh, obs.mask, obs.ant_p, obs.ant_q, chunk_map, p0,
        em_iters=3, config=LMConfig(itmax=20),
    )
    j_rob = params_to_jones(res_r.p)[0]
    err_rob = _gain_consistency_err(j_rob, J[0], coh, obs.ant_p, obs.ant_q)
    # plain LM on the same corrupted data
    res_g = lm_solve(
        visj, coh, obs.mask, obs.ant_p, obs.ant_q, chunk_map, p0, LMConfig(itmax=20)
    )
    j_gau = params_to_jones(res_g.p)[0]
    err_gau = _gain_consistency_err(j_gau, J[0], coh, obs.ant_p, obs.ant_q)
    assert err_rob < err_gau, (err_rob, err_gau)
    assert err_rob < 0.05, err_rob


def test_lbfgs_f32_no_nan_after_converged_em():
    """f32-without-x64 regression: the joint LBFGS pass starting from an
    already-converged EM solution must not NaN.  Pre-guard, a curvature
    pair with y.s underflowing to 0 stored rho = inf and poisoned every
    later two-loop direction (TPU production is f32; run in a subprocess
    so jax_enable_x64 from conftest does not mask the underflow)."""
    import os
    import subprocess
    import sys

    code = """
import numpy as np
import pytest
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sagecal_tpu.core.types import identity_jones, jones_to_params
from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata, random_jones
from sagecal_tpu.ops.rime import point_source_batch
from sagecal_tpu.solvers.sage import SM_LM_LBFGS, SageConfig, build_cluster_data, sagefit

f0 = 150e6
data = make_visdata(nstations=6, tilesz=2, nchan=1, freq0=f0, dtype=np.float32, seed=9)
clusters = [point_source_batch([0.015], [0.01], [2.0], f0=f0, dtype=jnp.float32)]
jt = random_jones(1, 6, seed=4, amp=0.1, dtype=np.complex64)
data = corrupt_and_observe(data, clusters, jones=jt, noise_sigma=0.0)
cdata = build_cluster_data(data, clusters, [1], fdelta=0.0)
p0 = jones_to_params(jnp.broadcast_to(identity_jones(6, jnp.complex64), (1, 1, 6, 2, 2)))
cfg = SageConfig(max_emiter=1, max_iter=10, max_lbfgs=15,
                 solver_mode=SM_LM_LBFGS, randomize=False)
r = sagefit(data, cdata, p0, cfg)
assert np.isfinite(float(r.res_1)), float(r.res_1)
assert float(r.res_1) < 1e-3 * float(r.res_0), float(r.res_1)
print("F32OK")
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0 and "F32OK" in r.stdout, r.stdout + r.stderr


# --- normal-equation assembly: closed-form planes + one-hot reductions ---


def _row_model_reference(pp, qq, C, mask_row, sqrt_w_row):
    """One row's weighted model reals (F*8,) from its two stations' 16
    params: the autodiff reference the assembly is checked against."""
    Jp = params_to_jones(pp)[0]
    Jq = params_to_jones(qq)[0]
    m = Jp @ C @ jnp.conj(Jq.T)
    r = jnp.stack([jnp.real(m), jnp.imag(m)], axis=-1) * mask_row[:, None, None, None]
    r = r.reshape(-1)
    if sqrt_w_row is not None:
        r = r * sqrt_w_row
    return r


def _normal_eq_reference(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, nchunk, sqrt_w):
    """(JTJ, JTe, cost) from per-row vmap(jacfwd) Jacobians scattered into
    the (nchunk, N, N, 8, 8) block grid, one row at a time."""
    from sagecal_tpu.solvers.lm import _residual_flat

    N = p_all.shape[-1] // 8
    F = vis.shape[-3]
    rows = ant_p.shape[0]
    e = _residual_flat(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_w)
    cost = jnp.zeros((nchunk,), p_all.dtype).at[chunk_map].add(jnp.sum(e * e, axis=(0, 1)))
    pblk = p_all.reshape(nchunk, N, 8)
    C = jnp.moveaxis(coh, -1, 0).reshape(rows, F, 2, 2)
    swrow = None
    if sqrt_w is not None:
        swrow = jnp.moveaxis(jnp.broadcast_to(sqrt_w, e.shape), -1, 0).reshape(rows, F * 8)
    jac = jax.vmap(jax.jacfwd(_row_model_reference, argnums=(0, 1)),
                   in_axes=(0, 0, 0, 0, None if sqrt_w is None else 0))
    Jp, Jq = jac(pblk[chunk_map, ant_p], pblk[chunk_map, ant_q], C,
                 jnp.moveaxis(mask, -1, 0), swrow)
    erow = jnp.moveaxis(e, -1, 0).reshape(rows, F * 8)
    Apq = jnp.einsum("rki,rkj->rij", Jp, Jq)
    JTJ = jnp.zeros((nchunk, N, N, 8, 8), p_all.dtype)
    JTJ = JTJ.at[chunk_map, ant_p, ant_p].add(jnp.einsum("rki,rkj->rij", Jp, Jp))
    JTJ = JTJ.at[chunk_map, ant_p, ant_q].add(Apq)
    JTJ = JTJ.at[chunk_map, ant_q, ant_p].add(jnp.swapaxes(Apq, -1, -2))
    JTJ = JTJ.at[chunk_map, ant_q, ant_q].add(jnp.einsum("rki,rkj->rij", Jq, Jq))
    JTe = jnp.zeros((nchunk, N, 8), p_all.dtype)
    JTe = JTe.at[chunk_map, ant_p].add(jnp.einsum("rki,rk->ri", Jp, erow))
    JTe = JTe.at[chunk_map, ant_q].add(jnp.einsum("rki,rk->ri", Jq, erow))
    JTJ = JTJ.transpose(0, 1, 3, 2, 4).reshape(nchunk, 8 * N, 8 * N)
    return JTJ, JTe.reshape(nchunk, 8 * N), cost


def _random_lm_problem(nchunk, F, weighted, N=7, tilesz=3, seed=5):
    """f64 rows over every station pair, some written q-before-p and one
    autocorrelation per timeslot, half of them masked."""
    rng = np.random.default_rng(seed)
    pairs = [(p, q) for p in range(N) for q in range(p + 1, N)] + [(2, 2)]
    ap = np.array([p for _ in range(tilesz) for p, _ in pairs], np.int32)
    aq = np.array([q for _ in range(tilesz) for _, q in pairs], np.int32)
    flip = rng.random(ap.size) < 0.5
    ap, aq = np.where(flip, aq, ap), np.where(flip, ap, aq)
    rows = ap.size
    cmap = np.minimum(np.repeat(np.arange(tilesz), len(pairs)) * nchunk // tilesz,
                      nchunk - 1).astype(np.int32)
    cplx = lambda *s: rng.standard_normal(s) + 1j * rng.standard_normal(s)  # noqa: E731
    coh, vis = cplx(F, 4, rows), cplx(F, 4, rows)
    mask = np.ones((F, rows))
    mask[:, rng.permutation(rows)[: rows // 2]] = 0.0
    J = np.eye(2) + 0.3 * cplx(nchunk, N, 2, 2)
    sqrt_w = rng.uniform(0.2, 1.5, (F, 8, rows)) if weighted else None
    p = jones_to_params(jnp.asarray(J))
    return (p, jnp.asarray(coh), jnp.asarray(vis), jnp.asarray(mask),
            jnp.asarray(ap), jnp.asarray(aq), jnp.asarray(cmap), nchunk,
            None if sqrt_w is None else jnp.asarray(sqrt_w))


@pytest.mark.parametrize("nchunk", [1, 3])
@pytest.mark.parametrize("F", [1, 2])
@pytest.mark.parametrize("weighted", [False, True])
def test_normal_eq_matches_autodiff_scatter_reference(nchunk, F, weighted):
    from sagecal_tpu.solvers.lm import _assemble_normal_eq

    args = _random_lm_problem(nchunk, F, weighted)
    assert args[0].dtype == jnp.float64
    got = _assemble_normal_eq(*args)
    want = _normal_eq_reference(*args)
    for name, g, w in zip(("JTJ", "JTe", "cost"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape, name
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < 1e-10, (name, err)


def test_lm_solve_admm_matches_autodiff_scatter_reference(monkeypatch):
    from sagecal_tpu.solvers import lm

    p0, coh, _, mask, ap, aq, cmap, nchunk, _ = _random_lm_problem(2, 2, False, seed=8)
    rng = np.random.default_rng(8)
    J = np.eye(2) + 0.2 * (rng.standard_normal((nchunk, 7, 2, 2))
                           + 1j * rng.standard_normal((nchunk, 7, 2, 2)))
    from sagecal_tpu.core.types import corrupt_flat

    vis = corrupt_flat(jnp.asarray(J), coh, ap, aq, cmap)
    admm = dict(admm_y=0.01 * jnp.asarray(rng.standard_normal(p0.shape)),
                admm_bz=p0 + 0.05 * jnp.asarray(rng.standard_normal(p0.shape)),
                admm_rho=jnp.asarray(2.0))

    def solve():
        return lm_solve(vis, coh, mask, ap, aq, cmap, p0, LMConfig(itmax=6), **admm)

    new = solve()

    def cost_reference(*a):
        return _normal_eq_reference(*a)[2]

    monkeypatch.setattr(lm, "_assemble_normal_eq", _normal_eq_reference)
    monkeypatch.setattr(lm, "_cost_only", cost_reference)
    old = solve()
    assert float(jnp.max(jnp.abs(new.p - p0))) > 1e-2  # the solve moved
    np.testing.assert_allclose(np.asarray(new.p), np.asarray(old.p), rtol=0, atol=1e-8)
    np.testing.assert_allclose(np.asarray(new.cost), np.asarray(old.cost), rtol=1e-10)


def test_lm_solve_assembles_without_float_scatter():
    """At one consensus band's shape (62 stations, 18,910 rows, one
    channel, ADMM terms on) the lowered solve holds no scatter into
    floating-point data: the normal equations and chunk costs are dense
    reductions, run at HIGHEST precision.  The only scatters left are the
    LU's integer pivot swaps (the jitter fallback of ``_solve_spd``)."""
    import re

    N, rows = 62, 18910
    S = jax.ShapeDtypeStruct
    f32, c64, i32 = jnp.float32, jnp.complex64, jnp.int32
    args = (S((1, 4, rows), c64), S((1, 4, rows), c64), S((1, rows), f32),
            S((rows,), i32), S((rows,), i32), S((rows,), i32), S((1, 8 * N), f32))
    admm = dict(admm_y=S((1, 8 * N), f32), admm_bz=S((1, 8 * N), f32),
                admm_rho=S((), f32))
    text = jax.jit(lambda *a, **k: lm_solve(*a, LMConfig(itmax=4), **k)).lower(
        *args, **admm).as_text()
    results = re.findall(r"stablehlo\.scatter.*?\}\) : \([^)]*\) -> (tensor<[^>]*>)",
                         text, re.S)
    assert results and all(t.endswith("xi32>") for t in results), results
    precisions = re.findall(r"precision = \[(\w+), (\w+)\]", text)
    assert text.count("stablehlo.dot_general") == len(precisions) > 0
    assert set(precisions) == {("HIGHEST", "HIGHEST")}
