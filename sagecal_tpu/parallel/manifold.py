"""Manifold (quotient) averaging over the unitary ambiguity of Jones blocks.

Redesign of ``/root/reference/src/lib/Dirac/manifold_average.c``.  A Jones
solution J is only determined up to a right-multiplied unitary U (J C J^H
is invariant for C = U C U^H in the single-cluster sense); before
averaging per-frequency solutions the master aligns them on the quotient
manifold.  The reference loops clusters on pthreads with LAPACK zgesvd on
2x2 blocks; here everything is a vmapped batch of closed-form 2x2 polar
factors, and frequency blocks are processed as one (Nf, 2N, 2) tensor.

Algorithm (manifold_average.c:60-200, per cluster):
  1. initial chain projection of every frequency block onto a reference
     block (randomized reference index when requested);
  2. ``niter`` rounds: mean block J3, then project each block J_f onto J3
     by the Procrustes rotation U = polar(J_f^H J3), J_f <- J_f U;
  3. final: recompute the mean from the projected ensemble, then apply a
     SINGLE unitary to each ORIGINAL block: Y_f <- Y_f polar(Y_f^H J3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def polar_unitary_2x2(A):
    """The unitary polar factor U V^H of trailing 2x2 complex matrices,
    in closed form: U = (A + (d/|d|) adj(A)^H) / sqrt(||A||_F^2 + 2|d|)
    with d = det A (Cayley-Hamilton on A A^H gives A A^H A + |d|^2
    A^-H = ||A||_F^2 A).  Elementwise, so it runs inside shard_map on
    any backend — the TPU's iterative SVD carries loop state whose
    varying-axes type the shard_map checker rejects."""
    a, b = A[..., 0, 0], A[..., 0, 1]
    c, e = A[..., 1, 0], A[..., 1, 1]
    d = a * e - b * c
    dabs = jnp.abs(d)
    ph = d / jnp.where(dabs > 0, dabs, 1)
    # adj(A)^H = [[conj(e), -conj(c)], [-conj(b), conj(a)]]
    adjh = jnp.stack([
        jnp.stack([jnp.conj(e), -jnp.conj(c)], -1),
        jnp.stack([-jnp.conj(b), jnp.conj(a)], -1),
    ], -2)
    fro2 = jnp.sum(jnp.abs(A) ** 2, axis=(-2, -1))
    t = jnp.sqrt(fro2 + 2 * dabs)
    t = jnp.where(t > 0, t, 1)
    return (A + ph[..., None, None] * adjh) / t[..., None, None]


def procrustes_project(J, J_ref):
    """min_U ||J_ref - J U|| over unitary U; returns J @ U.

    ``project_procrustes_block`` (manifold_average.c:266,346).
    J: (..., 2N, 2); J_ref: (..., 2N, 2).
    """
    A = jnp.swapaxes(jnp.conj(J), -1, -2) @ J_ref  # (..., 2, 2)
    return J @ polar_unitary_2x2(A)


def _jones_stack_to_blocks(Y):
    """(Nf, N, 2, 2) Jones -> (Nf, 2N, 2) tall blocks (column j of the
    block = column j of every station's Jones, stations stacked)."""
    Nf, N = Y.shape[0], Y.shape[1]
    return jnp.swapaxes(Y, 1, 2).reshape(Nf, 2 * N, 2)


def _blocks_to_jones_stack(B, N):
    Nf = B.shape[0]
    return jnp.swapaxes(B.reshape(Nf, 2, N, 2), 1, 2)


def manifold_average_cluster(Y, niter: int = 20, ref_idx: int = 0):
    """Align one cluster's per-frequency Jones sets; returns aligned Y and
    the quotient mean.

    Y: (Nf, N, 2, 2) complex.  Returns (Y_aligned, mean) with the same
    leading shapes ((Nf,N,2,2), (N,2,2)).
    """
    J = _jones_stack_to_blocks(Y)  # (Nf, 2N, 2)
    N = Y.shape[1]

    # 1. chain projection onto the reference block
    ref = J[ref_idx]
    J = procrustes_project(J, ref[None])

    # 2. iterative mean-and-project
    def one_round(J, _):
        J3 = jnp.mean(J, axis=0)
        return procrustes_project(J, J3[None]), None

    J, _ = jax.lax.scan(one_round, J, None, length=niter)

    # 3. single unitary applied to the originals
    J3 = jnp.mean(J, axis=0)
    J_orig = _jones_stack_to_blocks(Y)
    J_out = procrustes_project(J_orig, J3[None])
    return _blocks_to_jones_stack(J_out, N), _blocks_to_jones_stack(J3[None], N)[0]


def manifold_average(Y, niter: int = 20, ref_idx: int = 0):
    """``calculate_manifold_average`` (manifold_average.c:204): align
    per-frequency Jones over the unitary quotient, every cluster at once.

    Y: (Nf, M, N, 2, 2) complex -> aligned array, same shape.
    """
    aligned, _ = jax.vmap(
        lambda Ym: manifold_average_cluster(Ym, niter, ref_idx),
        in_axes=1,
        out_axes=(1, 0),
    )(Y)
    return aligned


def manifold_average_projectback(Y, niter: int = 10):
    """Federated-averaging variant (``calculate_manifold_average_projectback``,
    manifold_average.c:809): compute the quotient mean of the per-worker
    Z's and REPLACE every worker's copy with the mean projected back
    through each worker's own unitary frame.

    Y: (Nf, M, N, 2, 2) -> same shape, every frequency slot holding the
    consensus average expressed in its own frame.
    """

    def per_cluster(Ym):  # (Nf, N, 2, 2)
        J_orig = _jones_stack_to_blocks(Ym)
        _, mean = manifold_average_cluster(Ym, niter)
        mean_blk = _jones_stack_to_blocks(mean[None])[0]
        # express the mean in each worker's original frame:
        # U_f = polar(mean^H J_orig_f); out_f = mean U_f
        A = jnp.conj(mean_blk.T)[None] @ J_orig  # (Nf, 2, 2)
        out = mean_blk[None] @ polar_unitary_2x2(A)
        return _blocks_to_jones_stack(out, Ym.shape[1])

    return jax.vmap(per_cluster, in_axes=1, out_axes=1)(Y)


def extract_phases(J):
    """Phase-only reduction of a Jones stack: returns diag phase-only
    Jones exp(i*arg(diag(J))) (the role of ``extract_phases``,
    manifold_average.c:400, used for phase-only correction)."""
    d00 = J[..., 0, 0]
    d11 = J[..., 1, 1]
    p00 = jnp.exp(1j * jnp.angle(d00))
    p11 = jnp.exp(1j * jnp.angle(d11))
    z = jnp.zeros_like(p00)
    row0 = jnp.stack([p00, z], axis=-1)
    row1 = jnp.stack([z, p11], axis=-1)
    return jnp.stack([row0, row1], axis=-2)
