"""The ``spatial`` app: per-band solves -> consensus + AIC/MDL ->
FISTA spatial fit (apps/spatial.py over parallel/spatial.py), end to
end on the shared simulated-sky fixtures, plus checkpoint/resume
bit-exactness (an in-process kill simulation and the real SIGTERM
subprocess round).  The numeric oracles run in the fast tier; every
test that pays for band solves is slow-marked; the verify skill's
spatial CLI drive covers the app end to end, kill-and-resume included.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from sagecal_tpu.apps.config import SpatialConfig
from sagecal_tpu.apps.spatial import _load_bands, _solve_bands, run_spatial
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.spatial import (
    basis_blocks,
    minimum_description_length,
    phikk_matrix,
    spatial_basis_modes,
    spatial_model_apply,
    update_spatialreg_fista,
)

pytestmark = pytest.mark.spatial


def _cfg(tmp_path, **kw):
    base = dict(synthetic=3, nstations=6, tilesz=2, seed=5,
                out_prefix=str(tmp_path / "sp"), spatial_n0=2,
                npoly=2, fista_maxiter=60, use_f64=True)
    base.update(kw)
    return SpatialConfig(**base)


def test_mdl_selects_known_order():
    """Oracle: solutions generated from an exact order-3 consensus
    polynomial (plus a small noise floor) must make both AIC and MDL
    pick order 3 out of 1..4."""
    rng = np.random.default_rng(11)
    F, M, K = 8, 3, 16
    freqs = 120e6 + 5e6 * np.arange(F)
    freq0 = float(freqs.mean())
    rho = np.full((M,), 5.0)
    B = consensus.setup_polynomials(freqs, freq0, 3,
                                    consensus.POLY_BERNSTEIN)
    Z = rng.standard_normal((M, 3, K))
    J = np.einsum("fp,mpk->fmk", np.asarray(B), Z)
    Jst = (J + 1e-5 * rng.standard_normal(J.shape)) * rho[None, :, None]
    aic, mdl, k_aic, k_mdl = minimum_description_length(
        Jst, rho, freqs, freq0, Kstart=1, Kfinish=4)
    assert k_aic == 3 and k_mdl == 3, (aic, mdl)


def test_fista_recovers_exact_spatial_model():
    """Elastic-net oracle: Zbar built exactly from a sparse spatial
    model must be reproduced by the FISTA fit (model residual at the
    fitted coefficients ~ the L1 bias, tiny for small mu)."""
    rng = np.random.default_rng(3)
    M, D, G = 5, 12, 4
    modes, _ = spatial_basis_modes(
        rng.uniform(-0.05, 0.05, M), rng.uniform(-0.05, 0.05, M), 2, 0.1)
    Phi = basis_blocks(modes)  # (M, 2G, 2)
    Zs_true = (rng.standard_normal((D, 2 * G))
               + 1j * rng.standard_normal((D, 2 * G)))
    Zs_true[:, rng.choice(2 * G, G, replace=False)] = 0.0  # sparse truth
    Zbar = spatial_model_apply(jnp.asarray(Zs_true), Phi)
    Zs = update_spatialreg_fista(
        Zbar, phikk_matrix(Phi, lam=1e-9), Phi, mu=1e-8, maxiter=600)
    fit = spatial_model_apply(Zs, Phi)
    rel = (np.linalg.norm(np.asarray(fit - Zbar).ravel())
           / np.linalg.norm(np.asarray(Zbar).ravel()))
    assert rel < 1e-3, rel


@pytest.mark.slow
def test_spatial_app_end_to_end(tmp_path):
    """Full pipeline on the multiband fixture: solves converge, the MDL
    scan runs, the FISTA fit explains the consensus solutions, outputs
    land on disk.  Slow tier (band solves + compiles); the verify
    skill's spatial CLI drive covers the fast path."""
    cfg = _cfg(tmp_path)
    summary = run_spatial(cfg, log=lambda *a: None)
    assert summary["bands"] == 3 and summary["npoly"] == 2
    assert 1 <= summary["k_aic"] <= 2 and 1 <= summary["k_mdl"] <= 2
    # the same sky/gains in every band: a 4-mode basis over 2 cluster
    # centroids fits the consensus almost exactly
    assert summary["fista_fit_rel"] < 0.05
    out = np.load(f"{cfg.out_prefix}.npz")
    N = summary["nstations"]
    M = summary["nclusters"]
    assert out["J"].shape == (3, M, 8 * N)
    assert out["Zs"].shape == (2 * N * cfg.npoly, 2 * cfg.spatial_n0 ** 2)
    assert out["Z_spatial"].shape == out["Z"].shape
    with open(f"{cfg.out_prefix}.json") as f:
        assert json.load(f)["k_mdl"] == summary["k_mdl"]


@pytest.mark.slow
@pytest.mark.elastic
def test_solve_bands_resume_bit_exact(tmp_path):
    """Kill simulation without a subprocess: checkpoint every band, then
    delete the newest checkpoint (as if the run died before writing it)
    and resume — restored bands come off disk, the lost band re-solves,
    and the stacked solutions match the uninterrupted run bit-exactly."""
    from sagecal_tpu.elastic import CheckpointManager, config_fingerprint
    from sagecal_tpu.elastic.checkpoint import list_checkpoints

    cfg = _cfg(tmp_path, synthetic=2, checkpoint_every=1,
               checkpoint_dir=str(tmp_path / "ckpt"))
    datas, clusters, _ = _load_bands(cfg, lambda *a: None)
    fp = config_fingerprint(app="spatial-test")
    mgr = CheckpointManager(cfg.checkpoint_dir, fp, app="spatial",
                            every=1, keep=10)
    J_ref = _solve_bands(cfg, datas, clusters, mgr, None, lambda *a: None)
    mgr.close()
    ckpts = list_checkpoints(cfg.checkpoint_dir)
    assert len(ckpts) == 2
    os.remove(ckpts[0])  # newest: the last band's checkpoint never landed

    cfg2 = SpatialConfig(**{**cfg.__dict__, "resume": True})
    mgr2 = CheckpointManager(cfg.checkpoint_dir, fp, app="spatial",
                             every=1, keep=10)
    J_res = _solve_bands(cfg2, datas, clusters, mgr2, None,
                         lambda *a: None)
    mgr2.close()
    np.testing.assert_array_equal(J_res, J_ref)


@pytest.mark.slow
@pytest.mark.elastic
def test_resume_refuses_foreign_checkpoint(tmp_path):
    from sagecal_tpu.elastic import (
        CheckpointManager,
        ResumeRefused,
        config_fingerprint,
    )

    cfg = _cfg(tmp_path, synthetic=2, checkpoint_every=1,
               checkpoint_dir=str(tmp_path / "ckpt"))
    datas, clusters, _ = _load_bands(cfg, lambda *a: None)
    mgr = CheckpointManager(cfg.checkpoint_dir,
                            config_fingerprint(seed=1), app="spatial",
                            every=1)
    _solve_bands(cfg, datas, clusters, mgr, None, lambda *a: None)
    mgr.close()
    cfg2 = SpatialConfig(**{**cfg.__dict__, "resume": True})
    mgr2 = CheckpointManager(cfg.checkpoint_dir,
                             config_fingerprint(seed=2), app="spatial",
                             every=1)
    with pytest.raises(ResumeRefused):
        _solve_bands(cfg2, datas, clusters, mgr2, None, lambda *a: None)
    mgr2.close()


@pytest.mark.slow
@pytest.mark.elastic
def test_spatial_app_sigterm_resume_bit_exact(tmp_path):
    """The real signal path: SIGTERM the spatial app after its first
    band checkpoint lands, re-run with --resume, and compare every
    output array of the resumed run against an uninterrupted reference
    run bit-for-bit."""
    from sagecal_tpu.elastic.faultinject import (
        kill_at_checkpoint,
        run_subprocess,
    )

    def args(prefix, ckpt, resume=False):
        a = [sys.executable, "-m", "sagecal_tpu.apps.cli", "spatial",
             "--synthetic", "3", "--nstations", "6", "--seed", "5",
             "-o", str(tmp_path / prefix), "--checkpoint-every", "1",
             "--checkpoint-dir", str(tmp_path / ckpt)]
        return a + (["--resume"] if resume else [])

    env = {"JAX_PLATFORMS": "cpu"}
    rc, out, err = run_subprocess(args("ref", "ckpt_ref"), env=env)
    assert rc == 0, err

    ckpt_dir = str(tmp_path / "ckpt_cand")
    rc, out, err = kill_at_checkpoint(
        args("cand", "ckpt_cand"), ckpt_dir, n_checkpoints=1)
    if rc != 0:  # killed as intended (rc<0); finish with --resume
        rc2, out2, err2 = run_subprocess(
            args("cand", "ckpt_cand", resume=True), env=env)
        assert rc2 == 0, err2
        assert "resumed" in (out2 + err2)
    a = np.load(str(tmp_path / "ref.npz"))
    b = np.load(str(tmp_path / "cand.npz"))
    assert set(a.files) == set(b.files)
    for k in a.files:
        np.testing.assert_array_equal(b[k], a[k], err_msg=k)
