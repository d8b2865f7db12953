"""Consensus-ADMM polynomial math: frequency-smoothness constraints.

TPU-first re-design of ``/root/reference/src/lib/Dirac/consensus_poly.c``.
The reference runs this on the MPI master as pthread loops over clusters;
here every routine is a pure jitted array op, batched over clusters, and
the frequency sums that the master accumulated from worker messages
become ``lax.psum`` terms on a ``freq`` mesh axis (see
:mod:`sagecal_tpu.parallel.mesh`).

Conventions:
  B: (Nf, Npoly) real basis matrix, row f = basis evaluated at freqs[f]
     (the reference stores B column-major Npoly x Nf, consensus_poly.c:39).
  Z: (M, Npoly, K) global consensus variable; K = 8N (or 8N realified
     params of any shape).  The constraint is J_f ~ sum_p B[f,p] Z[:,p].
  rho: (Nf, M) per-frequency, per-cluster regularization.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# polynomial types (consensus_poly.c:21-28)
POLY_ORDINARY = 0
POLY_NORMALIZED = 1
POLY_BERNSTEIN = 2
POLY_RATIONAL = 3  # [1, (f-f0)/f0, (f0/f-1), ...]


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    """Static configuration of the consensus (Z-step) layer.

    ``zstep``:
      "grouped"  — the classic replicated z-step: every device psums the
        full basis-sized numerator (M, Npoly, K) and solves Z locally
        (parallel/mesh._zstep_grouped).
      "reduced"  — transpose-reduced scattered z-step ("Unwrapping ADMM",
        arXiv:1504.02147): the Gram numerator is ``psum_scatter``-ed over
        the solution axis so each device holds only a K/ndev shard of Z,
        the global solve is a tiny local einsum on the shard, and the
        active band's consensus target B_f Z comes back through a single
        ``all_to_all`` — per-round collective bytes drop from
        ~Npoly*M*K to ~(Npoly/ndev + M_active/M)*M*K.

    ``cluster_groups``: fine-grained consensus decomposition
      (arXiv:1603.02526) — the per-band x-step is split below band
      granularity into this many cluster factor groups, each round
      solving one (band slot, cluster group) factor node with its own
      duals.  Communication and x-step work per round scale with
      M/cluster_groups.  1 = classic whole-band rounds.

    ``staleness``: bounded-staleness rounds — contributions older than
      this many rounds are dropped from the Z solve (mesh mode: slots of
      the Scurrent rotation whose stored Yhat is older than K rounds).
      ``None`` = unbounded (the reference's multiplexed semantics).

    ``staleness_discount``: rho-discount applied per round of age to a
      stale band's Gram contribution (both the numerator term and its
      rho in the denominator), so a Z solve leans on fresh bands:
      weight = discount**age.  1.0 = no discounting.

    ``slot_schedule`` / ``group_schedule``: optional host-built static
      schedules (see :func:`sagecal_tpu.parallel.admm.factor_schedule`)
      of shape (nadmm-1,) or (nadmm-1, ndev) — per-round active slot /
      cluster group, optionally per mesh device (shard_map-level
      rebalancing: devices whose bands carry more unflagged rows get
      proportionally more visits).  ``None`` = the uniform
      Sbegin/Scurrent/Send rotation.
    """

    zstep: str = "grouped"
    cluster_groups: int = 1
    staleness: Optional[int] = None
    staleness_discount: float = 1.0
    slot_schedule: Optional[np.ndarray] = None
    group_schedule: Optional[np.ndarray] = None

    @property
    def is_default(self) -> bool:
        return (
            self.zstep == "grouped"
            and self.cluster_groups == 1
            and self.staleness is None
            and self.staleness_discount == 1.0
            and self.slot_schedule is None
            and self.group_schedule is None
        )


def setup_polynomials(freqs, f0: float, Npoly: int, ptype: int = POLY_BERNSTEIN):
    """Basis matrix B (Nf, Npoly).  Mirrors ``setup_polynomials``
    (consensus_poly.c:39-186) including the Bernstein min/max frequency
    normalization and the odd/even split of the rational type-3 basis."""
    freqs = np.asarray(freqs, np.float64)
    Nf = freqs.shape[0]
    B = np.zeros((Nf, Npoly))
    if ptype in (POLY_ORDINARY, POLY_NORMALIZED):
        frat = (freqs - f0) / f0
        B[:, 0] = 1.0
        for p in range(1, Npoly):
            B[:, p] = B[:, p - 1] * frat
        if ptype == POLY_NORMALIZED:
            nrm = np.sqrt(np.sum(B**2, axis=0))
            B = np.where(nrm[None, :] > 0, B / np.where(nrm == 0, 1, nrm)[None, :], 0.0)
    elif ptype == POLY_BERNSTEIN:
        fmax, fmin = freqs.max(), freqs.min()
        x = (freqs - fmin) / max(fmax - fmin, 1e-300)
        n = Npoly - 1
        from math import comb

        for p in range(Npoly):
            B[:, p] = comb(n, p) * x**p * (1.0 - x) ** (n - p)
    elif ptype == POLY_RATIONAL:
        B[:, 0] = 1.0
        frat = (freqs - f0) / f0
        last = frat.copy()
        for p in range(1, Npoly, 2):
            B[:, p] = last
            last = last * frat
        frat = f0 / freqs - 1.0
        last = frat.copy()
        for p in range(2, Npoly, 2):
            B[:, p] = last
            last = last * frat
    else:
        raise ValueError(f"unknown polynomial type {ptype}")
    return jnp.asarray(B)


def find_prod_inverse(B, fratio=None):
    """pinv(sum_f w_f B_f B_f^T): (Npoly, Npoly).  ``find_prod_inverse``
    (consensus_poly.c:196): weights are the per-frequency unflagged-data
    ratios."""
    Nf = B.shape[0]
    w = jnp.ones((Nf,), B.dtype) if fratio is None else jnp.asarray(fratio)
    P = jnp.einsum("f,fp,fq->pq", w, B, B)
    return jnp.linalg.pinv(P)


def find_prod_inverse_full(B, rho, alpha=None):
    """Per-cluster pinv(sum_f rho[f,m] B_f B_f^T [+ alpha_m I]): (M, Npoly,
    Npoly).  ``find_prod_inverse_full[_fed]`` (consensus_poly.c:465,547);
    the federated variant's alpha*I ties local to global Z."""
    P = jnp.einsum("fm,fp,fq->mpq", rho, B, B)
    if alpha is not None:
        Np = B.shape[1]
        P = P + alpha[:, None, None] * jnp.eye(Np, dtype=B.dtype)[None]
    return jnp.linalg.pinv(P)


def accumulate_z_term(B_f, Yrho_f):
    """One frequency's additive contribution to the z right-hand side:
    outer(B_f, Y_f + rho_f J_f).

    B_f: (Npoly,) this frequency's basis row; Yrho_f: (M, K).
    Returns (M, Npoly, K).  The master's accumulation loop
    (sagecal_master.cpp:841-852) — on a mesh this is followed by
    ``lax.psum`` over the freq axis.
    """
    return B_f[None, :, None] * Yrho_f[:, None, :]


def update_global_z(z, Bii):
    """Z = Bii applied along the Npoly axis of z: (M, Npoly, K).

    ``update_global_z_multi`` (consensus_poly.c:778): per cluster,
    Z_m = Bii_m @ z_m (Bii symmetric).
    """
    return jnp.einsum("mpq,mqk->mpk", Bii, z)


def bz_for_freq(Z, B_f):
    """The per-frequency consensus target B_f Z: (M, K) from Z (M, Npoly, K).
    What the master sends each worker per ADMM iteration
    (sagecal_master.cpp:770-800)."""
    return jnp.einsum("p,mpk->mk", B_f, Z)


def update_rho_bb(rho, rho_upper, dY, dJ, eps: float = 1e-12,
                  dj_floor: float = 1e-6):
    """Barzilai-Borwein adaptive penalty update, per cluster.

    ``update_rho_bb`` (consensus_poly.c:860-911): with deltaY = Yhat -
    Yhat_old and deltaJ = J - J_old per cluster, compute the spectral
    steps alphaSD = <dY,dY>/<dY,dJ>, alphaMG = <dY,dJ>/<dJ,dJ>, pick
    alphaMG if 2*alphaMG > alphaSD else alphaSD - alphaMG/2, and accept
    only under sufficient correlation (>0.2) and 0.001 < alpha < upper.

    rho, rho_upper: (M,); dY, dJ: (M, K) per-cluster flattened deltas.

    ``dj_floor``: per-element RMS floor on dJ below which the update is
    rejected and rho kept.  On a CONVERGED cluster dJ -> 0 while dY
    stays finite, so ``<dJ,dJ>`` passes the absolute ``eps`` check yet
    alphaMG = <dY,dJ>/<dJ,dJ> blows up toward ``rho_upper`` — a huge
    penalty jump on exactly the band that needed none, which
    destabilizes late (and especially stale/async) rounds.  Gains are
    O(1) normalized Jones params, so an absolute RMS floor is
    scale-correct here.
    """
    ip12 = jnp.sum(dY * dJ, axis=-1)
    ip11 = jnp.sum(dY * dY, axis=-1)
    ip22 = jnp.sum(dJ * dJ, axis=-1)
    safe12 = jnp.where(jnp.abs(ip12) < eps, 1.0, ip12)
    corr = ip12 / jnp.sqrt(jnp.maximum(ip11 * ip22, eps))
    alphaSD = ip11 / safe12
    alphaMG = ip12 / jnp.where(ip22 < eps, 1.0, ip22)
    alphahat = jnp.where(2.0 * alphaMG > alphaSD, alphaMG, alphaSD - 0.5 * alphaMG)
    nk = jnp.asarray(dJ.shape[-1], ip22.dtype)
    ok = (
        (ip12 > eps)
        & (ip11 > eps)
        & (ip22 > eps)
        & (ip22 > nk * (dj_floor * dj_floor))
        & (corr > 0.2)
        & (alphahat > 1e-3)
        & (alphahat < rho_upper)
    )
    return jnp.where(ok, alphahat, rho)


def slot_staleness_ages(active_slot, nslots):
    """Ages of every multiplexed slot's stored Yhat right after slot
    ``active_slot`` refreshed: slot s was last active ``(active_slot -
    s) mod nslots`` rounds ago (the Scurrent rotation of
    sagecal_master.cpp:157-206).  Returns (nslots,) int ages."""
    s = jnp.arange(nslots)
    return jnp.mod(active_slot - s, nslots)


def staleness_weights(ages, staleness=None, discount: float = 1.0,
                      dtype=None):
    """Per-contribution Z-solve weights from staleness ages.

    ``weight = discount**age`` for contributions within the bound,
    0 for contributions older than ``staleness`` rounds (``None`` =
    unbounded).  Applied to BOTH the Gram numerator term B_f (Y_f +
    rho_f J_f) and that band's rho in the denominator, this is exactly
    a rho-discount: a stale band still pulls the consensus toward its
    last solution, just with a proportionally weaker penalty.
    """
    ages = jnp.asarray(ages)
    if dtype is None:
        dtype = jnp.result_type(float)  # x64-aware default
    w = jnp.asarray(discount, dtype) ** ages.astype(dtype)
    if staleness is not None:
        w = jnp.where(ages <= staleness, w, jnp.zeros_like(w))
    return w


def soft_threshold(z, lam):
    """Elementwise soft threshold (``soft_threshold_z``,
    consensus_poly.c:1044)."""
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - lam, 0.0)


def admm_dual_residual(Z_new, Z_old):
    """Per-real-parameter dual residual ||Z_old - Z_new||/sqrt(size)
    (sagecal_master.cpp:878-885)."""
    d = (Z_new - Z_old).ravel()
    return jnp.linalg.norm(d) / jnp.sqrt(d.shape[0])


def consensus_health(
    primal_res_band,
    dual_res_band,
    trend_thresh: float = 2.0,
    eps: float = 1e-30,
    ages=None,
    staleness: Optional[int] = None,
):
    """Per-band ADMM consensus health from residual trajectories.

    ``primal_res_band``/``dual_res_band``: (nadmm, Nf) per-round,
    per-band residuals (:class:`sagecal_tpu.parallel.mesh.AdmmResult`
    of a mesh program built with per-band telemetry).  Returns
    ``(ratio (Nf,), trend (Nf,), diverged (Nf,) bool)``:

    - ``ratio``: final primal/dual residual ratio — the standard ADMM
      balance diagnostic (Boyd §3.4.1; the reference's master prints the
      two norms side by side, sagecal_master.cpp:869-885).  Large values
      mean rho is too small for that band (consensus not enforced),
      tiny values mean rho dominates the data term.
    - ``trend``: final primal residual over the trajectory minimum —
      > 1 means the band moved AWAY from consensus after its best round.
    - ``diverged``: non-finite residuals anywhere in the trajectory, or
      ``trend > trend_thresh`` (sustained growth, not a one-round blip).

    Staleness-aware criterion (bounded-staleness rounds): ``ages`` is
    the per-band age (rounds since last refresh) at the final round.  A
    band whose contribution is ``a`` rounds stale is measured against a
    Z that moved ``a`` rounds past its last solve, so its primal
    residual legitimately rides above the fresh-band envelope; its
    trend threshold is relaxed to ``trend_thresh * (1 + a)``.  A band
    older than the configured ``staleness`` bound is STARVED — the
    scheduler stopped refreshing it — and is flagged diverged outright
    (its residual trajectory is no longer evidence of anything).

    Pure array math (works on numpy or jax inputs) so the apps' host-side
    watchdog and on-device callers share one definition.
    """
    pr = jnp.asarray(primal_res_band)
    du = jnp.asarray(dual_res_band)
    ratio = pr[-1] / jnp.maximum(du[-1], eps)
    trend = pr[-1] / jnp.maximum(jnp.min(pr, axis=0), eps)
    nonfinite = ~(
        jnp.all(jnp.isfinite(pr), axis=0) & jnp.all(jnp.isfinite(du), axis=0)
    )
    thresh = jnp.asarray(trend_thresh, trend.dtype)
    if ages is not None:
        a = jnp.asarray(ages).astype(trend.dtype)
        thresh = thresh * (1.0 + a)
    diverged = nonfinite | (trend > thresh)
    if ages is not None and staleness is not None:
        starved = jnp.asarray(ages) > staleness
        diverged = diverged | starved
    return ratio, trend, diverged


def band_imbalance(band_seconds, eps: float = 1e-30):
    """Per-band work-imbalance gauges: ``(ratio, skew, argmax)``.

    ``band_seconds``: (Nf,) per-band wall-clock (real host timings in
    minibatch consensus mode, or :func:`sagecal_tpu.obs.trace.
    band_attribution` shares of the mesh ADMM window).  Returns the
    slowest/median ratio (the straggler gauge — the mesh z-step psum
    runs at the pace of the slowest band, so ratio≈1 means the SPMD
    collective wastes nothing), the relative skew ``(max-mean)/mean``,
    and the index of the slowest band.

    Pure array math (numpy or jax inputs) like :func:`consensus_health`,
    so the host-side straggler detector (obs/trace.py) and any on-device
    caller share one definition.
    """
    t = jnp.asarray(band_seconds)
    med = jnp.median(t)
    mean = jnp.mean(t)
    ratio = jnp.max(t) / jnp.maximum(med, eps)
    skew = (jnp.max(t) - mean) / jnp.maximum(mean, eps)
    return ratio, skew, jnp.argmax(t)


def admm_primal_residual(J_flat, BZ_flat):
    """Per-real-parameter primal residual ||J - BZ||/sqrt(size): how far
    one band's local solution sits from its consensus target (the
    per-slave primal norm of sagecal_master.cpp:869-876).  Pure array
    math shared by the mesh ADMM's per-band residual telemetry
    (parallel/mesh.py) and its reference tests."""
    d = (J_flat - BZ_flat).reshape(J_flat.shape[0], -1) if J_flat.ndim > 1 \
        else (J_flat - BZ_flat)[None]
    n = jnp.sqrt(jnp.asarray(d.shape[-1], d.dtype))
    out = jnp.sqrt(jnp.sum(d * d, axis=-1)) / n
    return out if J_flat.ndim > 1 else out[0]
