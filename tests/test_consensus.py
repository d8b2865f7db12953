"""Unit tests: consensus polynomial math + manifold averaging."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.manifold import (
    manifold_average,
    manifold_average_projectback,
    polar_unitary_2x2,
    procrustes_project,
)


class TestPolynomials:
    def test_ordinary_basis(self):
        freqs = np.array([100e6, 150e6, 200e6])
        f0 = 150e6
        B = np.asarray(consensus.setup_polynomials(freqs, f0, 3, consensus.POLY_ORDINARY))
        assert B.shape == (3, 3)
        np.testing.assert_allclose(B[:, 0], 1.0)
        frat = (freqs - f0) / f0
        np.testing.assert_allclose(B[:, 1], frat, rtol=1e-12)
        np.testing.assert_allclose(B[:, 2], frat**2, rtol=1e-12)

    def test_normalized_rows_unit_norm(self):
        freqs = np.linspace(100e6, 200e6, 8)
        B = np.asarray(
            consensus.setup_polynomials(freqs, 150e6, 4, consensus.POLY_NORMALIZED)
        )
        np.testing.assert_allclose(np.sum(B**2, axis=0), 1.0, rtol=1e-10)

    def test_bernstein_partition_of_unity(self):
        freqs = np.linspace(100e6, 200e6, 16)
        B = np.asarray(
            consensus.setup_polynomials(freqs, 150e6, 5, consensus.POLY_BERNSTEIN)
        )
        np.testing.assert_allclose(np.sum(B, axis=1), 1.0, rtol=1e-10)
        assert np.all(B >= 0.0)

    def test_rational_basis_layout(self):
        freqs = np.array([120e6, 180e6])
        f0 = 150e6
        B = np.asarray(consensus.setup_polynomials(freqs, f0, 3, consensus.POLY_RATIONAL))
        frat = (freqs - f0) / f0
        grat = f0 / freqs - 1.0
        np.testing.assert_allclose(B[:, 0], 1.0)
        np.testing.assert_allclose(B[:, 1], frat, rtol=1e-12)
        np.testing.assert_allclose(B[:, 2], grat, rtol=1e-12)


class TestProdInverse:
    def test_pseudo_inverse_property(self):
        rng = np.random.default_rng(0)
        Nf, Npoly, M = 6, 3, 4
        B = jnp.asarray(rng.standard_normal((Nf, Npoly)))
        rho = jnp.asarray(rng.uniform(0.5, 2.0, (Nf, M)))
        Bii = consensus.find_prod_inverse_full(B, rho)
        P = jnp.einsum("fm,fp,fq->mpq", rho, B, B)
        PBP = jnp.einsum("mpq,mqr,mrs->mps", P, Bii, P)
        np.testing.assert_allclose(np.asarray(PBP), np.asarray(P), atol=1e-8)

    def test_federated_alpha_regularizes(self):
        B = jnp.asarray(np.ones((1, 2)))  # rank-1 sum -> singular without alpha
        rho = jnp.ones((1, 1))
        alpha = jnp.asarray([0.5])
        Bii = consensus.find_prod_inverse_full(B, rho, alpha)
        P = jnp.einsum("fm,fp,fq->mpq", rho, B, B) + 0.5 * jnp.eye(2)[None]
        np.testing.assert_allclose(
            np.asarray(Bii[0] @ P[0]), np.eye(2), atol=1e-8
        )


class TestZUpdate:
    def test_consensus_recovers_exact_polynomial(self):
        """If J_f = B_f Z_true exactly and rho is uniform, the z-step must
        recover Z_true (least-squares consistency)."""
        rng = np.random.default_rng(1)
        Nf, Npoly, M, K = 8, 3, 2, 16
        freqs = np.linspace(100e6, 200e6, Nf)
        B = consensus.setup_polynomials(freqs, 150e6, Npoly, consensus.POLY_ORDINARY)
        Z_true = jnp.asarray(rng.standard_normal((M, Npoly, K)))
        rho = jnp.ones((Nf, M))
        J = jnp.einsum("fp,mpk->fmk", B, Z_true)  # per-freq solutions
        # z accumulation: sum_f B_f (x) (rho J_f)  (Y=0)
        z = sum(
            consensus.accumulate_z_term(B[f], rho[f][:, None] * J[f]) for f in range(Nf)
        )
        Bii = consensus.find_prod_inverse_full(B, rho)
        Z = consensus.update_global_z(z, Bii)
        np.testing.assert_allclose(np.asarray(Z), np.asarray(Z_true), atol=1e-6)

    def test_bz_for_freq(self):
        rng = np.random.default_rng(2)
        Z = jnp.asarray(rng.standard_normal((3, 2, 8)))
        B_f = jnp.asarray([1.0, 0.5])
        out = consensus.bz_for_freq(Z, B_f)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(Z[:, 0] + 0.5 * Z[:, 1]), rtol=1e-6
        )


class TestBBRho:
    def test_perfectly_correlated_deltas_update(self):
        rng = np.random.default_rng(3)
        M, K = 3, 32
        dJ = jnp.asarray(rng.standard_normal((M, K)))
        a = 5.0
        dY = a * dJ  # alphaSD = alphaMG = a, corr = 1
        rho = jnp.full((M,), 1.0)
        out = consensus.update_rho_bb(rho, jnp.full((M,), 100.0), dY, dJ)
        np.testing.assert_allclose(np.asarray(out), a, rtol=1e-5)

    def test_uncorrelated_deltas_keep_rho(self):
        M, K = 1, 4
        dY = jnp.asarray([[1.0, -1.0, 1.0, -1.0]])
        dJ = jnp.asarray([[1.0, 1.0, 1.0, 1.0]])  # orthogonal
        rho = jnp.full((M,), 7.0)
        out = consensus.update_rho_bb(rho, jnp.full((M,), 100.0), dY, dJ)
        np.testing.assert_allclose(np.asarray(out), 7.0)

    def test_upper_bound_respected(self):
        dJ = jnp.ones((1, 8))
        dY = 50.0 * dJ
        rho = jnp.full((1,), 1.0)
        out = consensus.update_rho_bb(rho, jnp.full((1,), 10.0), dY, dJ)
        np.testing.assert_allclose(np.asarray(out), 1.0)  # 50 > upper -> keep


class TestSoftThreshold:
    def test_values(self):
        z = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
        out = consensus.soft_threshold(z, 1.0)
        np.testing.assert_allclose(np.asarray(out), [-1.0, 0.0, 0.0, 0.0, 1.0])


def _rand_unitary_2x2(rng):
    a = rng.standard_normal((2, 2)) + 1j * rng.standard_normal((2, 2))
    q, r = np.linalg.qr(a)
    return q * (np.diag(r) / np.abs(np.diag(r)))[None, :]


class TestManifold:
    def test_polar_factor_is_unitary(self):
        rng = np.random.default_rng(4)
        A = jnp.asarray(
            rng.standard_normal((5, 2, 2)) + 1j * rng.standard_normal((5, 2, 2))
        )
        U = polar_unitary_2x2(A)
        eye = jnp.swapaxes(jnp.conj(U), -1, -2) @ U
        np.testing.assert_allclose(
            np.asarray(eye), np.broadcast_to(np.eye(2), (5, 2, 2)), atol=1e-6
        )

    def test_polar_factor_matches_svd(self):
        """The closed form equals U V^H of the SVD (the reference's
        zgesvd route), including near-singular blocks."""
        rng = np.random.default_rng(8)
        A = rng.standard_normal((64, 2, 2)) + 1j * rng.standard_normal(
            (64, 2, 2))
        A[0, 1] = 1e-6 * A[0, 1] + A[0, 0]  # det ~ 1e-6
        U, _, Vh = np.linalg.svd(A)
        np.testing.assert_allclose(
            np.asarray(polar_unitary_2x2(jnp.asarray(A))), U @ Vh,
            atol=1e-8)

    def test_procrustes_undoes_unitary(self):
        rng = np.random.default_rng(5)
        N = 6
        J = rng.standard_normal((2 * N, 2)) + 1j * rng.standard_normal((2 * N, 2))
        U = _rand_unitary_2x2(rng)
        J_rot = jnp.asarray(J @ U)
        out = procrustes_project(J_rot, jnp.asarray(J))
        np.testing.assert_allclose(np.asarray(out), J, atol=1e-5)

    def test_manifold_average_aligns_rotated_copies(self):
        """Per-frequency copies of one Jones set rotated by random unitaries
        must collapse to (nearly) identical blocks after averaging."""
        rng = np.random.default_rng(6)
        Nf, M, N = 5, 2, 8
        base = rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        Y = np.zeros((Nf, M, N, 2, 2), complex)
        for f in range(Nf):
            for m in range(M):
                U = _rand_unitary_2x2(rng)
                Y[f, m] = base[m] @ U
        out = np.asarray(manifold_average(jnp.asarray(Y), niter=20))
        # all frequencies should now agree with each other
        for m in range(M):
            spread = np.max(np.abs(out[:, m] - out[0:1, m]))
            assert spread < 1e-4, f"cluster {m} spread {spread}"
        # and the aligned blocks still equal base up to ONE common unitary
        A = np.conj(out[0, 0].reshape(2 * N, 2).T) @ base[0].reshape(2 * N, 2)
        U = np.asarray(polar_unitary_2x2(jnp.asarray(A)))
        np.testing.assert_allclose(
            out[0, 0].reshape(2 * N, 2) @ U, base[0].reshape(2 * N, 2), atol=1e-4
        )

    def test_projectback_returns_common_average(self):
        rng = np.random.default_rng(7)
        Nf, M, N = 4, 1, 5
        base = rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal((M, N, 2, 2))
        Y = np.zeros((Nf, M, N, 2, 2), complex)
        for f in range(Nf):
            U = _rand_unitary_2x2(rng)
            Y[f, 0] = base[0] @ U
        out = np.asarray(manifold_average_projectback(jnp.asarray(Y), niter=10))
        # each output must be unitarily equivalent to the quotient mean =
        # base; check singular values match (unitary-invariant)
        s_base = np.linalg.svd(base[0].reshape(2 * N, 2), compute_uv=False)
        for f in range(Nf):
            s_f = np.linalg.svd(out[f, 0].reshape(2 * N, 2), compute_uv=False)
            np.testing.assert_allclose(s_f, s_base, rtol=1e-3)
