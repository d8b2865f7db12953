"""End-to-end anchor vs the ACTUAL reference solver (plan of record).

Compiles the reference's CPU ``libdirac`` from the mounted read-only
checkout (see :mod:`tests.ref_oracle`) and runs ``sagefit_visibilities``
(``/root/reference/src/lib/Dirac/Dirac.h:1651``) on the same synthetic
visibilities our :func:`sagecal_tpu.solvers.sage.sagefit` solves.  Both
start from identity Jones on noiseless data; solved Jones are compared
through the unitary-ambiguity-free baseline products ``G_pq = J_p
J_q^H`` (for an unpolarized point source ``C`` is a scalar multiple of
I_2, so ``J -> J U`` with unitary ``U`` is the exact gauge freedom and
``J_p J_q^H`` is the gauge invariant the visibilities determine).

Measured anchor landscape (8 stn, tilesz 4, f64, this host):

* layout contract: feeding the TRUE Jones into the reference solver
  yields residual 5.4e-18 — every layout mapping (x row order, coh
  ``[4*M*row+4*cm+c]``, row-major re/im param packing) is exact.
* single cluster (no EM coupling): both solvers reach the optimum to
  machine precision; ref-vs-ours gauge RMS **2.9e-13** (LM mode 1) and
  **2.7e-7** (robust RTR mode 5) — under the 1e-6 BASELINE.md bar.
* two clusters: SAGE-EM converges LINEARLY in both implementations
  (measured ref res_1 at em/iter/lbfgs budgets: 3.1e-5 @ 4/25/30,
  2.6e-6 @ 8/40/60, 2.5e-8 @ 12/60/120; gauge-RMS vs truth 7.4e-3 /
  7.2e-4 / 5.4e-6), so two *different* EM schedules agree only as
  deeply as both have converged; the deep two-cluster anchor asserts
  2e-4 and documents why.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from sagecal_tpu.core.types import identity_jones, jones_to_params, params_to_jones
from sagecal_tpu.io.simulate import corrupt_and_observe, make_visdata, random_jones
from sagecal_tpu.ops.rime import point_source_batch
from sagecal_tpu.solvers.sage import (
    SM_LM_LBFGS,
    SM_OSLM_LBFGS,
    SM_RLM_RLBFGS,
    SM_RTR_OSRLM_RLBFGS,
    SageConfig,
    build_cluster_data,
    sagefit,
)

import ref_oracle

pytestmark = pytest.mark.skipif(
    ref_oracle.build_ref_lib() is None,
    reason="reference checkout or toolchain unavailable",
)


def _scene(nstations=8, tilesz=4, m=2, seed=3):
    """Noiseless f64 multi-cluster scene, identical for both solvers."""
    f0 = 150e6
    data = make_visdata(
        nstations=nstations, tilesz=tilesz, nchan=1, freq0=f0,
        dtype=np.float64, seed=seed,
    )
    rng = np.random.default_rng(seed)
    ll = rng.uniform(-0.04, 0.04, m)
    mm = rng.uniform(-0.04, 0.04, m)
    flux = rng.uniform(1.0, 4.0, m)
    clusters = [
        point_source_batch([ll[k]], [mm[k]], [flux[k]], f0=f0, dtype=jnp.float64)
        for k in range(m)
    ]
    jones_true = random_jones(m, nstations, seed=seed + 1, amp=0.12,
                              dtype=np.complex128)
    data = corrupt_and_observe(data, clusters, jones=jones_true, noise_sigma=0.0)
    # fdelta=0 so the solver coherencies match the (unsmeared) simulation
    # exactly — a nonzero smearing factor puts a floor under the residual
    # that neither solver can cross.
    cdata = build_cluster_data(data, clusters, [1] * m, fdelta=0.0)
    return data, cdata, jones_true


def _gauge_free_rms(j_a, j_b, sta1, sta2):
    """RMS over clusters/baselines of G_pq = J_p J_q^H differences."""
    ga = np.einsum("mpab,mqcb->mpqac", j_a, j_a.conj())
    gb = np.einsum("mpab,mqcb->mpqac", j_b, j_b.conj())
    d = ga[:, sta1, sta2] - gb[:, sta1, sta2]
    return float(np.sqrt(np.mean(np.abs(d) ** 2)))


def _ref_solve(data, cdata, p0_j, *, solver_mode, max_emiter, max_iter,
               max_lbfgs):
    m = cdata.coh.shape[0]
    x = np.asarray(data.vis[0], np.complex128)
    coh = np.asarray(cdata.coh[:, 0], np.complex128)
    return ref_oracle.ref_sagefit(
        np.asarray(data.u), np.asarray(data.v), np.asarray(data.w),
        x, data.nstations, data.nbase, data.tilesz,
        np.asarray(data.ant_p), np.asarray(data.ant_q),
        coh, m, p0_j, freq0=data.freq0, fdelta=0.0,
        max_emiter=max_emiter, max_iter=max_iter, max_lbfgs=max_lbfgs,
        lbfgs_m=7, linsolv=1, solver_mode=solver_mode, randomize=0,
    )


def _our_solve(data, cdata, p0_j, *, solver_mode, max_emiter, max_iter,
               max_lbfgs):
    p0 = jones_to_params(jnp.asarray(p0_j))[:, None, :]
    cfg = SageConfig(
        max_emiter=max_emiter, max_iter=max_iter, max_lbfgs=max_lbfgs,
        lbfgs_m=7, solver_mode=solver_mode, randomize=False,
    )
    result = sagefit(data, cdata, p0, cfg)
    j = np.asarray(params_to_jones(result.p[:, 0, :]), np.complex128)
    return j, float(result.res_0), float(result.res_1)


def _identity_p0(m, nstations):
    return np.broadcast_to(
        np.asarray(identity_jones(nstations, jnp.complex128)),
        (m, nstations, 2, 2),
    )


def test_layout_contract_truth_residual_zero():
    """Feeding the TRUE Jones into the reference solver must give ~zero
    residual: validates the x / coh / param layout mappings exactly."""
    data, cdata, jones_true = _scene(m=2)
    _, _, res_0, _, _ = _ref_solve(
        data, cdata, np.asarray(jones_true, np.complex128),
        solver_mode=1, max_emiter=1, max_iter=1, max_lbfgs=1,
    )
    assert res_0 < 1e-14, f"layout mismatch: truth residual {res_0}"


def test_generic_optimizer_contract_rosenbrock():
    """SURVEY §3.5 library-only contract: the reference's lbfgs_fit and
    ours both minimize the 400-dim Rosenbrock chain (the demo oracle,
    test/Dirac/demo.c) from the same start to the known minimum 1..1."""
    import jax

    n = 400

    def cost_np(p):
        return float(np.sum(100.0 * (p[1::2] - p[0::2] ** 2) ** 2
                            + (1.0 - p[0::2]) ** 2))

    def grad_np(p):
        g = np.zeros_like(p)
        a, b = p[0::2], p[1::2]
        g[1::2] = 200.0 * (b - a * a)
        g[0::2] = -400.0 * a * (b - a * a) - 2.0 * (1.0 - a)
        return g

    p0 = np.full(n, -1.2)
    p0[1::2] = 1.0
    p_ref, rv = ref_oracle.ref_lbfgs_fit(cost_np, grad_np, p0, itmax=2000,
                                         mem=11)
    assert cost_np(p_ref) < 1e-8, cost_np(p_ref)

    from sagecal_tpu.solvers.lbfgs import lbfgs_fit

    def cost_jax(p):
        return jnp.sum(100.0 * (p[1::2] - p[0::2] ** 2) ** 2
                       + (1.0 - p[0::2]) ** 2)

    fit = jax.jit(
        lambda p: lbfgs_fit(cost_jax, None, p, itmax=2000, M=11).p
    )(jnp.asarray(p0))
    ours = np.asarray(fit)
    assert cost_np(ours) < 1e-8, cost_np(ours)
    np.testing.assert_allclose(ours, p_ref, atol=1e-4)
    np.testing.assert_allclose(ours, 1.0, atol=1e-4)


@pytest.mark.slow
def test_anchor_single_cluster_lm_1e6():
    """Single-cluster LM+LBFGS: both reach the optimum to machine
    precision; Jones (gauge-invariant) RMS bar 1e-6 (measured 2.9e-13)."""
    data, cdata, _ = _scene(m=1)
    kw = dict(max_emiter=4, max_iter=30, max_lbfgs=50)
    p0 = _identity_p0(1, data.nstations)
    j_ref, _, r0, r1, _ = _ref_solve(data, cdata, p0, solver_mode=1, **kw)
    assert r1 < 1e-12 * max(r0, 1.0)
    j_our, o0, o1 = _our_solve(data, cdata, p0, solver_mode=SM_LM_LBFGS, **kw)
    assert o1 < 1e-12 * max(o0, 1.0)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    assert rms < 1e-6, f"gauge RMS vs reference {rms:.3e}"


@pytest.mark.slow
def test_anchor_single_cluster_rtr_robust_1e6():
    """Robust RTR (reference solver_mode 5): same optimum on noiseless
    data (robust cost is minimized at zero residual); measured 2.7e-7."""
    data, cdata, _ = _scene(m=1)
    kw = dict(max_emiter=4, max_iter=30, max_lbfgs=50)
    p0 = _identity_p0(1, data.nstations)
    j_ref, _, r0, r1, _ = _ref_solve(data, cdata, p0, solver_mode=5, **kw)
    assert r1 < 1e-5 * max(r0, 1.0)
    j_our, o0, o1 = _our_solve(
        data, cdata, p0, solver_mode=SM_RTR_OSRLM_RLBFGS, **kw
    )
    assert o1 < 1e-5 * max(o0, 1.0)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    assert rms < 1e-6, f"gauge RMS vs reference {rms:.3e}"


@pytest.mark.slow
def test_anchor_single_cluster_oslm_1e6():
    """Ordered-subsets LM (reference solver_mode 0, oslmfit.c): same
    optimum on noiseless data despite different subset schedules;
    measured gauge RMS 2.9e-13."""
    data, cdata, _ = _scene(m=1)
    kw = dict(max_emiter=4, max_iter=30, max_lbfgs=50)
    p0 = _identity_p0(1, data.nstations)
    j_ref, _, r0, r1, _ = _ref_solve(data, cdata, p0, solver_mode=0, **kw)
    assert r1 < 1e-10 * max(r0, 1.0)
    j_our, o0, o1 = _our_solve(data, cdata, p0, solver_mode=SM_OSLM_LBFGS,
                               **kw)
    assert o1 < 1e-10 * max(o0, 1.0)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    assert rms < 1e-6, f"gauge RMS vs reference {rms:.3e}"


@pytest.mark.slow
def test_anchor_single_cluster_robust_lm_1e6():
    """Robust IRLS-LM (reference solver_mode 2, robustlm.c): on
    noiseless data the Student's-t weighted optimum coincides with the
    Gaussian one; measured gauge RMS 2.9e-13."""
    data, cdata, _ = _scene(m=1)
    kw = dict(max_emiter=4, max_iter=30, max_lbfgs=50)
    p0 = _identity_p0(1, data.nstations)
    j_ref, _, r0, r1, _ = _ref_solve(data, cdata, p0, solver_mode=2, **kw)
    assert r1 < 1e-10 * max(r0, 1.0)
    j_our, o0, o1 = _our_solve(data, cdata, p0, solver_mode=SM_RLM_RLBFGS,
                               **kw)
    assert o1 < 1e-10 * max(o0, 1.0)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    assert rms < 1e-6, f"gauge RMS vs reference {rms:.3e}"


@pytest.mark.slow
def test_anchor_two_cluster_deep():
    """Two overlapping clusters, deep budgets: both EM schedules converge
    linearly (see module docstring for the measured ladder), so the
    anchor asserts the 2e-4 neighborhood plus deep residual reduction on
    both sides."""
    data, cdata, jones_true = _scene(m=2)
    kw = dict(max_emiter=12, max_iter=60, max_lbfgs=120)
    p0 = _identity_p0(2, data.nstations)
    j_ref, _, r0, r1, _ = _ref_solve(data, cdata, p0, solver_mode=1, **kw)
    assert r1 < 1e-6 * max(r0, 1.0)
    j_our, o0, o1 = _our_solve(data, cdata, p0, solver_mode=SM_LM_LBFGS, **kw)
    assert o1 < 1e-4 * max(o0, 1.0)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    rms_rt = _gauge_free_rms(j_ref, np.asarray(jones_true), sta1, sta2)
    rms_ot = _gauge_free_rms(j_our, np.asarray(jones_true), sta1, sta2)
    assert rms < 2e-4, (
        f"gauge RMS vs reference {rms:.3e} "
        f"(ref-vs-truth {rms_rt:.3e}, ours-vs-truth {rms_ot:.3e})"
    )


@pytest.mark.slow
def test_anchor_two_cluster_ladder_crossing():
    """Drive the overlapping-cluster EM ladder DEEP (the VERDICT's
    1e-5 crossing demand): the ref-vs-ours gauge RMS must decrease
    monotonically with budget and cross below 1e-5 at the deepest rung
    — demonstrating the 2e-4 of the fast anchor is EM depth, not a
    disagreement floor."""
    data, cdata, jones_true = _scene(m=2)
    p0 = _identity_p0(2, data.nstations)
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rungs = [
        dict(max_emiter=8, max_iter=40, max_lbfgs=60),
        dict(max_emiter=16, max_iter=80, max_lbfgs=160),
        dict(max_emiter=32, max_iter=160, max_lbfgs=400),
    ]
    rms_curve = []
    truth_curve = []
    for kw in rungs:
        j_ref, _, _, r1, _ = _ref_solve(data, cdata, p0, solver_mode=1,
                                        **kw)
        j_our, _, o1 = _our_solve(data, cdata, p0,
                                  solver_mode=SM_LM_LBFGS, **kw)
        rms_curve.append(_gauge_free_rms(j_ref, j_our, sta1, sta2))
        truth_curve.append((
            _gauge_free_rms(j_ref, np.asarray(jones_true), sta1, sta2),
            _gauge_free_rms(j_our, np.asarray(jones_true), sta1, sta2),
        ))
    msg = (f"ladder ref-vs-ours {rms_curve}, "
           f"(ref,ours)-vs-truth {truth_curve}")
    assert rms_curve[1] < rms_curve[0] and rms_curve[2] < rms_curve[1], msg
    # at full convergence the two implementations agree far below the
    # BASELINE.md 1e-6 Jones-RMS bar (measured 1.28e-10 at this rung,
    # ref res_1 9.5e-14 / ours 5.6e-13: the round-3 2e-4 figure was EM
    # depth, not a disagreement floor)
    assert rms_curve[-1] < 1e-6, msg


@pytest.mark.slow
def test_anchor_bfgsfit_joint_lbfgs():
    """``bfgsfit_visibilities`` anchor (lmfit.c:1126): the reference's
    joint LBFGS-only multi-cluster fit vs our joint LBFGS on the same
    noiseless two-cluster scene.  Both run Gaussian cost (solver_mode 1
    -> lbfgs_fit_wrapper) from identity to deep convergence."""
    import jax

    from sagecal_tpu.solvers.lbfgs import lbfgs_fit
    from sagecal_tpu.solvers.sage import predict_full_model

    data, cdata, jones_true = _scene(m=2)
    p0 = _identity_p0(2, data.nstations)
    j_ref, r0, r1, rv = ref_oracle.ref_bfgsfit(
        np.asarray(data.u), np.asarray(data.v), np.asarray(data.w),
        np.asarray(data.vis[0], np.complex128),
        data.nstations, data.nbase, data.tilesz,
        np.asarray(data.ant_p), np.asarray(data.ant_q),
        np.asarray(cdata.coh[:, 0], np.complex128), 2, p0,
        freq0=data.freq0, fdelta=0.0, max_lbfgs=500, lbfgs_m=7,
        solver_mode=1, mean_nu=2.0,
    )
    assert r1 < 1e-5 * max(r0, 1e-30), (r0, r1, rv)

    shape = (2, 1, 8 * data.nstations)

    def cost_fn(pflat):
        model = predict_full_model(pflat.reshape(shape), cdata, data)
        diff = (data.vis - model) * data.mask[..., None, :]
        return jnp.sum(jnp.real(diff) ** 2 + jnp.imag(diff) ** 2)

    pj0 = jones_to_params(jnp.asarray(p0))[:, None, :]
    fit = jax.jit(
        lambda p: lbfgs_fit(cost_fn, None, p.reshape(-1), itmax=500, M=7)
    )(pj0)
    j_our = np.asarray(
        params_to_jones(fit.p.reshape(shape)[:, 0, :]), np.complex128
    )
    sta1 = np.asarray(data.ant_p[: data.nbase])
    sta2 = np.asarray(data.ant_q[: data.nbase])
    rms = _gauge_free_rms(j_ref, j_our, sta1, sta2)
    rms_rt = _gauge_free_rms(j_ref, np.asarray(jones_true), sta1, sta2)
    rms_ot = _gauge_free_rms(j_our, np.asarray(jones_true), sta1, sta2)
    assert rms < 1e-5, (
        f"bfgsfit anchor gauge RMS {rms:.3e} "
        f"(ref-vs-truth {rms_rt:.3e}, ours-vs-truth {rms_ot:.3e})"
    )
