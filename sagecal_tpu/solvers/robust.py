"""Robust Student's-t noise model: EM weights and nu estimation.

Reimplements ``update_w_and_nu`` / ``update_nu`` (``/root/reference/src/
lib/Dirac/updatenu.c:136,263``) and the IRLS wrapper logic of
``rlevmar_der_single_nocuda`` (``robustlm.c``; decl Dirac.h:744): the EM
E-step computes per-residual-element weights w = (nu+1)/(nu + e^2), the
M-step is a weighted LM solve with sqrt(w)-scaled residuals, and nu is
re-estimated by a digamma-score grid search over [nulow, nuhigh]
(Nd=30 points, argmin |score|).  All of it is jit-compatible: the grid
search is a vectorized reduction, the digamma comes from
``jax.scipy.special.digamma``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma

from sagecal_tpu.solvers.lm import LMConfig, LMResult, _residual_flat, lm_solve
from sagecal_tpu.utils.precision import true_f32


def update_w_and_nu(
    ed: jax.Array,
    nu0: jax.Array,
    nulow: float = 2.0,
    nuhigh: float = 30.0,
    Nd: int = 30,
    mask: Optional[jax.Array] = None,
):
    """E-step + nu grid search (updatenu.c:136-253).

    ed: residual elements (any shape, reals).  Returns (sqrt_w, nu):
    sqrt-weights of ed's shape and the new scalar nu, chosen on a grid of
    Nd points in [nulow, nuhigh] by minimizing
    |psi((nu+1)/2) - ln((nu+1)/2) - psi(nu/2) + ln(nu/2) + mean(ln w - w) + 1|.
    ``mask`` restricts the mean to valid elements (flagged data carries
    w=1 so it stays inert downstream).
    """
    w = (nu0 + 1.0) / (nu0 + ed * ed)
    q = w - jnp.log(w)  # per-element, positive
    if mask is not None:
        mfull = jnp.broadcast_to(mask, w.shape)
        msum = jnp.maximum(jnp.sum(mfull), 1.0)
        sumq = jnp.sum(jnp.abs(q) * mfull) / msum
        w = jnp.where(mfull > 0, w, 1.0)
    else:
        sumq = jnp.mean(jnp.abs(q))
    deltanu = (nuhigh - nulow) / Nd
    grid = nulow + deltanu * jnp.arange(Nd)
    score = (
        digamma(grid * 0.5 + 0.5)
        - jnp.log((grid + 1.0) * 0.5)
        - digamma(grid * 0.5)
        + jnp.log(grid * 0.5)
        - sumq
        + 1.0
    )
    nu = grid[jnp.argmin(jnp.abs(score))]
    return jnp.sqrt(w), nu


def update_nu_aecm(
    logsumw: jax.Array,
    nu_old: jax.Array,
    p: int = 8,
    nulow: float = 2.0,
    nuhigh: float = 30.0,
    Nd: int = 30,
):
    """AECM nu update (updatenu.c:263-341): solve for nu in
    psi((nu_old+p)/2) - ln((nu_old+p)/2) - psi(nu/2) + ln(nu/2)
    + logsumw + 1 = 0, logsumw = mean(ln w_i - w_i)."""
    dgm = digamma((nu_old + p) * 0.5) - jnp.log((nu_old + p) * 0.5)
    deltanu = (nuhigh - nulow) / Nd
    grid = nulow + deltanu * jnp.arange(Nd)
    score = -digamma(grid * 0.5) + jnp.log(grid * 0.5) + logsumw + dgm + 1.0
    # keep the caller's dtype: under x64 the grid is f64 and would
    # otherwise promote an f32 EM carry (caught by the config-3 AOT test)
    return grid[jnp.argmin(jnp.abs(score))].astype(jnp.result_type(nu_old))


@true_f32
def robust_lm_solve(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    nu0: float = 2.0,
    nulow: float = 2.0,
    nuhigh: float = 30.0,
    em_iters: int = 3,
    config: LMConfig = LMConfig(),
    collect_quality: bool = False,
):
    """Robust LM: EM over (weights, nu) wrapping weighted LM solves
    (``rlevmar_der_single_nocuda``, robustlm.c; Dirac.h:744).

    Returns (LMResult, nu).

    ``collect_quality`` additionally fills the result's quality slot
    (ops/quality.py): the final weighted solve's chi^2 attribution and
    gain health, enriched with the converged nu and Student's-t weight
    statistics (histogram, down-weighted and flagged fractions).
    """
    mask8 = mask[..., None, :]  # broadcasts over the (F, 8, rows) residual

    def em_step(carry, _):
        p, nu, sqrt_w = carry
        res = lm_solve(
            vis, coh, mask, ant_p, ant_q, chunk_map, p, config,
            sqrt_weights=sqrt_w,
        )
        ed = _residual_flat(res.p, coh, vis, mask, ant_p, ant_q, chunk_map, None)
        sqrt_w_new, nu_new = update_w_and_nu(ed, nu, nulow, nuhigh, mask=mask8)
        return (res.p, nu_new, sqrt_w_new), None

    # E-step FIRST: weights from the residual at p0, so gross outliers are
    # suppressed before they can poison the first fit.  (The reference's
    # first M-step is unweighted, robustlm.c:2231-2257 — safe there only
    # because SAGE hands it a warm start from the previous tile; from a
    # cold start the unweighted fit can lock the EM into a bad basin.)
    ed0 = _residual_flat(p0, coh, vis, mask, ant_p, ant_q, chunk_map, None)
    sqrt_w0, nu_e = update_w_and_nu(
        ed0, jnp.asarray(nu0, p0.dtype), nulow, nuhigh, mask=mask8
    )
    init = (p0, nu_e, sqrt_w0)
    (p, nu, sqrt_w), _ = jax.lax.scan(em_step, init, None, length=em_iters)
    # final weighted solve with converged weights
    res = lm_solve(
        vis, coh, mask, ant_p, ant_q, chunk_map, p, config,
        sqrt_weights=sqrt_w, collect_quality=collect_quality,
    )
    if collect_quality:
        from sagecal_tpu.ops.quality import weight_stats

        hist, down, flag = weight_stats(sqrt_w, nu, mask8)
        res = res._replace(quality=res.quality._replace(
            nu=jnp.asarray(nu, p0.dtype), weight_hist=hist,
            downweighted_frac=down, flagged_frac=flag,
        ))
    return res, nu


def whiten_uv_weights(u, v, freq0):
    """uv-density pre-whitening weight for the -W option
    (``whiten_data``/``ncp_weight``, updatenu.c:341-360):
    w(d) = 1/(1 + 1.8 exp(-0.05 d)), d = sqrt(u^2+v^2) wavelengths,
    1.0 beyond 400 wavelengths."""
    ud = jnp.sqrt(u * u + v * v) * freq0
    w = 1.0 / (1.0 + 1.8 * jnp.exp(-0.05 * ud))
    return jnp.where(ud > 400.0, 1.0, w)


# jitted module entry with compile/recompile telemetry (see
# sagecal_tpu/obs/perf.py; the em_iters EM ladder is a static python
# loop, so a changed em_iters is a visible recompile)
from sagecal_tpu.obs.perf import instrumented_jit  # noqa: E402

robust_lm_solve_jit = instrumented_jit(
    robust_lm_solve, name="robust_lm_solve",
    static_argnames=("em_iters", "collect_quality"))
