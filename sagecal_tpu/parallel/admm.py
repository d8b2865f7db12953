"""Consensus-constrained local calibration: the ADMM "x-step".

Redesign of ``sagefit_visibilities_admm`` (``/root/reference/src/lib/
Dirac/admm_solve.c:221``): an EM pass over clusters where each
per-cluster solve minimizes the data misfit PLUS the scaled-Lagrangian
consensus terms ``y^T (J - BZ) + rho/2 ||J - BZ||^2`` (cost contract
Dirac.h:1182-1195).  Like the reference, the local solver is dispatched
on solver mode: the CPU reference always runs robust RTR-ADMM
(admm_solve.c:346 ``rtr_solve_nocuda_robust_admm``) and the GPU
pipeline picks NSD-ADMM for ``SM_NSD_RLBFGS`` (admm_solve.c:463-467);
here LM/RTR/NSD all carry the augmented terms, so any mode works:

- ``SM_LM_LBFGS`` / ``SM_OSLM_LBFGS``: batched LM with the quadratic
  terms folded into the normal equations (lm.py).
- ``SM_RTR_OSLM_LBFGS``: plain RTR-ADMM.
- ``SM_RTR_OSRLM_RLBFGS`` (+ any robust mode except NSD): Student's-t
  robust RTR-ADMM — the reference MPI slave's default local solver.
- ``SM_NSD_RLBFGS``: robust NSD-ADMM.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu.core.types import VisData
from sagecal_tpu.solvers.lm import LMConfig, _residual_flat, lm_solve
from sagecal_tpu.solvers.robust import update_w_and_nu
from sagecal_tpu.utils.precision import true_f32
from sagecal_tpu.solvers.sage import (
    SM_LM_LBFGS,
    SM_NSD_RLBFGS,
    SM_RTR_OSLM_LBFGS,
    SM_RTR_OSRLM_RLBFGS,
    _ROBUST_MODES,
    ClusterData,
    _res_norm,
    em_residual_scan,
    predict_full_model,
)


class AdmmLocalResult(NamedTuple):
    p: jax.Array  # (M, nchunk_max, 8N)
    res_0: jax.Array
    res_1: jax.Array


@true_f32
def admm_sagefit(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    Y: jax.Array,
    BZ: jax.Array,
    rho: jax.Array,
    max_emiter: int = 1,
    lm_config: LMConfig = LMConfig(),
    robust_nu: Optional[float] = None,
    solver_mode: int = SM_LM_LBFGS,
    nulow: float = 2.0,
    nuhigh: float = 30.0,
    cluster_slice=None,
) -> AdmmLocalResult:
    """One worker's ADMM x-update for one tile.

    Args:
      p0, Y, BZ: (M, nchunk_max, 8N) real — current solution, scaled
        Lagrange multipliers, and consensus target B_f Z (the same BZ is
        applied to every hybrid chunk of a cluster, as in
        rtr_solve_robust_admm).
      rho: (M,) per-cluster penalties (already fratio-scaled by the
        caller, sagecal_master.cpp:709-723).
      robust_nu: optional Student's-t nu — when given with an LM mode,
        each cluster solve is IRLS-weighted by w = (nu+1)/(nu+e^2) from
        the residual at the incoming solution (the robust ADMM path's
        E-step); robust RTR/NSD modes run their own nu EM instead.
      solver_mode: SM_* dispatch (see module docstring).
      cluster_slice: optional ``(start, count)`` fine-grained factor
        node — only the ``count`` clusters from (dynamic) ``start`` are
        re-solved and dual-coupled this pass; the rest stay fixed but
        remain subtracted from the residual (em_residual_scan).  Only
        the sliced rows of Y/BZ/rho are read.
    """
    F, rows = data.vis.shape[-3], data.vis.shape[-1]
    nreal = rows * F * 8

    full0 = predict_full_model(p0, cdata, data)
    res_0 = _res_norm(data.vis - full0, data.mask, nreal)

    use_rtr = solver_mode in (SM_RTR_OSLM_LBFGS, SM_RTR_OSRLM_RLBFGS)
    use_nsd = solver_mode == SM_NSD_RLBFGS
    robust = solver_mode in _ROBUST_MODES
    mask8 = (
        data.mask[..., None, :]  # broadcasts over the (F, 8, rows) residual
        if (robust_nu is not None and not (use_rtr or use_nsd))
        else None
    )
    nu0 = jnp.asarray(
        robust_nu if robust_nu is not None else nulow, p0.dtype
    )

    def solve_one(xeff, coh_k, cmap_k, p_k, extras_k):
        y_k, bz_k, rho_k = extras_k
        if use_rtr or use_nsd:
            from sagecal_tpu.solvers.rtr import (
                RTRConfig,
                nsd_solve_robust,
                rtr_solve,
                rtr_solve_robust,
            )

            itmax = lm_config.itmax
            if use_nsd:
                res, _ = nsd_solve_robust(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k,
                    p_k, itmax=itmax + 15, nu0=nu0, nulow=nulow,
                    nuhigh=nuhigh,
                    admm_y=y_k, admm_bz=bz_k, admm_rho=rho_k,
                )
            elif robust:
                res, _ = rtr_solve_robust(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k,
                    p_k,
                    RTRConfig(itmax_rsd=itmax + 5, itmax_rtr=itmax + 10),
                    nu0=nu0, nulow=nulow, nuhigh=nuhigh,
                    admm_y=y_k, admm_bz=bz_k, admm_rho=rho_k,
                )
            else:
                res = rtr_solve(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k,
                    p_k,
                    RTRConfig(itmax_rsd=itmax + 5, itmax_rtr=itmax + 10),
                    admm_y=y_k, admm_bz=bz_k, admm_rho=rho_k,
                )
            return res.p, None
        if robust_nu is not None:
            ed = _residual_flat(
                p_k, coh_k, xeff, data.mask, data.ant_p, data.ant_q, cmap_k, None
            )
            sqrt_w, _ = update_w_and_nu(
                ed, jnp.asarray(robust_nu, p_k.dtype), mask=mask8
            )
        else:
            sqrt_w = None
        res = lm_solve(
            xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
            lm_config, sqrt_weights=sqrt_w,
            admm_y=y_k, admm_bz=bz_k, admm_rho=rho_k,
        )
        return res.p, None

    p = p0
    for _ in range(max_emiter):
        p, _ = em_residual_scan(data, cdata, p, (Y, BZ, rho), solve_one,
                                cluster_slice=cluster_slice)

    full1 = predict_full_model(p, cdata, data)
    res_1 = _res_norm(data.vis - full1, data.mask, nreal)
    return AdmmLocalResult(p=p, res_0=res_0, res_1=res_1)


def admm_dual_update(Y, p, BZ, rho):
    """Y <- Y + rho (J - BZ) (sagecal_slave.cpp:831): the scaled dual
    ascent step.  Shapes (M, nchunk_max, 8N); rho (M,)."""
    return Y + rho[:, None, None] * (p - BZ)


def round_work_weights(nadmm: int, nslots: int, plain_emiter: int = 2,
                       max_emiter: int = 1, slot_rows=None,
                       cluster_groups: int = 1):
    """Static per-ADMM-round work model (host-side, plain floats).

    The mesh ADMM runs its whole nadmm loop as one jitted program, so
    per-round host timing does not exist; this models each round's
    x-step solver work for wall-clock attribution (obs/trace.py):
    round 0 plain-solves ALL ``nslots`` local sub-band slots with
    ``plain_emiter`` EM passes plus the manifold alignment, rounds >= 1
    solve one active slot with ``max_emiter`` passes (the
    Sbegin/Scurrent/Send rotation — see parallel/mesh.py).  Returns
    ``nadmm`` positive weights proportional to modeled solver work;
    the z-step psum is negligible next to the x-steps (PAPERS.md,
    "Unwrapping ADMM").

    ``slot_rows``: optional per-slot UNFLAGGED-row counts (or any
    per-slot work proxy, e.g. ``nrows * fratio``).  Without it every
    slot is assumed to carry the same rows — exactly the uniformity
    that flag-skewed bands break, and that the synthetic band
    attribution would otherwise paper over: a round's solver work is
    dominated by its active slot's unflagged data, so round r >= 1 is
    weighted by slot ``(r-1) % nslots``'s rows (normalized to a mean of
    1 so the uniform case is unchanged) and round 0 by their sum.

    ``cluster_groups``: fine-grained consensus decomposition — rounds
    solve 1/cluster_groups of the clusters, so per-round x-step work
    shrinks accordingly (the group rotation is the fast axis:
    round r >= 1 is slot ``((r-1)//cluster_groups) % nslots``).
    """
    if nadmm <= 0:
        return []
    nslots = max(nslots, 1)
    if slot_rows is not None and len(slot_rows) and sum(slot_rows) > 0:
        mean = float(sum(slot_rows)) / len(slot_rows)
        rel = [float(r) / mean for r in slot_rows]
        # fold multi-band-per-slot groupings down to nslots entries
        if len(rel) != nslots:
            per = max(len(rel) // nslots, 1)
            rel = [sum(rel[s * per:(s + 1) * per]) / per
                   for s in range(nslots)]
    else:
        rel = [1.0] * nslots
    cg = max(cluster_groups, 1)
    w0 = float(sum(rel) * max(plain_emiter, 1))
    ws = [w0]
    for r in range(1, nadmm):
        s = ((r - 1) // cg) % nslots
        ws.append(float(max(max_emiter, 1)) * rel[s] / cg)
    return ws


def factor_schedule(nadmm: int, nslots: int, cluster_groups: int = 1,
                    band_weights=None, ndev: int = 1):
    """Host-built static (slot, cluster-group) schedule for the mesh
    ADMM's fine-grained rounds (parallel/mesh.py ConsensusConfig).

    Returns ``(slot_sched, group_sched)`` int arrays of shape
    ``(nadmm-1, ndev)``: round r's active sub-band slot and cluster
    group per mesh device.  The default (no ``band_weights``) is the
    uniform rotation — groups fastest, then the Sbegin/Scurrent/Send
    slot rotation — identical on every device.

    ``band_weights``: per-BAND unflagged-row counts, length
    ``nslots * ndev`` with band ``d * nslots + s`` on device d (the
    contiguous sharding of parallel/mesh.py).  When given, each device
    allocates its slot visits proportionally to ITS bands' weights
    (largest-remainder apportionment over the nadmm-1 rounds) — the
    shard_map-level rebalancing: a device whose heavy band carries 3x
    the rows of its light band visits the heavy slot ~3x as often, so
    flag-skewed bands stop starving while dead slots stop billing
    rounds.  Group rotation stays the fast axis within each device's
    visit sequence.
    """
    nrounds = max(nadmm - 1, 0)
    cg = max(cluster_groups, 1)
    nslots = max(nslots, 1)
    slot_sched = np.zeros((nrounds, ndev), np.int32)
    group_sched = np.zeros((nrounds, ndev), np.int32)
    for r in range(nrounds):
        group_sched[r, :] = r % cg
    if band_weights is None:
        for r in range(nrounds):
            slot_sched[r, :] = (r // cg) % nslots
        return slot_sched, group_sched
    w = np.asarray(band_weights, float).reshape(ndev, nslots)
    w = np.maximum(w, 1e-12)
    nvisits = (nrounds + cg - 1) // cg
    for d in range(ndev):
        share = w[d] / w[d].sum() * nvisits
        counts = np.floor(share).astype(int)
        rem = share - counts
        for s in np.argsort(-rem)[: nvisits - counts.sum()]:
            counts[s] += 1
        counts = np.maximum(counts, 1 if nvisits >= nslots else 0)
        # interleave visits (round-robin over remaining budget) so a
        # heavy slot's extra visits spread across the run
        visits = []
        left = counts.copy()
        while len(visits) < nvisits:
            for s in range(nslots):
                if left[s] > 0:
                    visits.append(s)
                    left[s] -= 1
            if left.sum() <= 0 and len(visits) < nvisits:
                visits.extend([int(np.argmax(w[d]))] * (nvisits - len(visits)))
        for r in range(nrounds):
            slot_sched[r, d] = visits[r // cg]
    return slot_sched, group_sched
