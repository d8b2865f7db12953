"""Levenberg-Marquardt for per-cluster Jones solves — batched, TPU-first.

Redesign of ``clevmar_der_single_nocuda`` / ``oslevmar_der_single_nocuda``
(``/root/reference/src/lib/Dirac/clmfit.c``, contract at Dirac.h:544-559,
849-931).  The reference materializes the full (8*Nbase*tilesz x 8N)
Jacobian per cluster and runs one LM loop per hybrid chunk on pthreads.
Here the structure of the RIME is exploited instead: each residual row
(one baseline, 8F reals) depends only on the 16 parameters of its two
stations, so J^T J is assembled from per-row 16x16 blocks and J^T e from
per-row 16-vectors, formed in closed form with rows on the lane axis and
summed onto stations by one-hot matmuls — one pass over all rows for ALL
hybrid chunks at once.  The LM iterations for all chunks then run in
lock-step inside a single ``lax.while_loop``
(per-chunk damping/acceptance state, masked once a chunk terminates), and
the tiny dense (8N x 8N) solves are a vmapped Cholesky.  This removes the
reference's pthread fan-out and its per-chunk sequential loop
(lmfit.c:897-967) in one stroke.

Termination mirrors the levmar contract (Dirac.h:544-559): max
iterations, gradient inf-norm < eps1, relative step < eps2, cost < eps3;
damping update is Nielsen's: accept -> mu *= max(1/3, 1-(2*rho-1)^3),
nu=2; reject -> mu *= nu, nu *= 2.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from sagecal_tpu.core.types import (
    corrupt_flat,
    gather_jones_rows,
    params_to_jones,
    reals_of_flat,
)
from sagecal_tpu.ops.quality import SolveQuality, residual_quality
from sagecal_tpu.utils.precision import true_f32

# Row-block size for the normal-equation scan: bounds the per-block
# Jacobian planes and (RB, K) one-hot station maps so assembly memory is
# O(block), not O(rows).
_ROW_BLOCK = 8192


@struct.dataclass
class LMConfig:
    itmax: int = struct.field(pytree_node=False, default=10)
    tau: float = struct.field(pytree_node=False, default=1e-3)
    eps1: float = struct.field(pytree_node=False, default=1e-15)
    eps2: float = struct.field(pytree_node=False, default=1e-15)
    eps3: float = struct.field(pytree_node=False, default=1e-15)


class LMResult(NamedTuple):
    p: jax.Array  # (nchunk, 8N)
    cost0: jax.Array  # (nchunk,) initial cost
    cost: jax.Array  # (nchunk,) final cost
    iterations: jax.Array
    # SolveQuality (ops.quality) when collect_quality=True, else None —
    # an empty pytree, so the jitted output signature is unchanged
    quality: Optional[SolveQuality] = None


def _residual_flat(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_w):
    """Real residual elements (F, 8, rows): reals of (vis - J_p C J_q^H)
    * mask * sqrt_w, in the reference's 8-real ordering (Dirac.h:1617).

    p_all: (nchunk, 8N) real params; vis/coh flat (F, 4, rows).
    """
    model = corrupt_flat(params_to_jones(p_all), coh, ant_p, ant_q, chunk_map)
    diff = (vis - model) * mask[..., None, :]
    r = reals_of_flat(diff)  # (F, 8, rows)
    if sqrt_w is not None:
        r = r * sqrt_w
    return r


def _pad_rows(x, padr, axis=-1):
    if padr == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis % x.ndim] = (0, padr)
    return jnp.pad(x, cfg)


def _one_hot(idx, K, dtype):
    """(rows,) indices -> (rows, K) one-hot: the right operand of a
    matmul that sums rows onto K stations or station pairs."""
    return (idx[:, None] == jnp.arange(K, dtype=idx.dtype)[None, :]).astype(dtype)


def _per_chunk_sum(x, chunk_map, nchunk):
    """(rows,) -> (nchunk,): each hybrid chunk's sum, as a dense masked
    reduction (no scatter)."""
    sel = chunk_map[None, :] == jnp.arange(nchunk, dtype=chunk_map.dtype)[:, None]
    return jnp.sum(jnp.where(sel, x[None, :], 0), axis=-1)


def _pair_index(lo, hi, N):
    """Row-major index of the station pair lo <= hi in the upper triangle
    (diagonal included) of an N x N grid: 0 .. N(N+1)/2 - 1."""
    return lo * N - (lo * (lo - 1)) // 2 + (hi - lo)


def _jacobian_planes(jones, coh, wt, ap, aq, cm):
    """Weighted per-row Jacobians of the model, rows on the lane axis.

    The model m = J_p G with G = C J_q^H, and m = H J_q^H with H = J_p C,
    is linear in each station's entries, so every column of the real
    Jacobian is an entry of G or H (times i for an imaginary part, and
    conjugated for q).  With the output real (f, i, j, c) (c: re/im) and
    the station parameter (a, b, s) of J_ab (s: re/im, flat index
    4b + 2a + s, the params_to_jones order):

      dm_ij / dJp_ab = delta_ia G_bj u_s,        u = (1, i)
      dm_ij / dJq_ab = delta_ja H_ib conj(u_s)

    Returns P, Q (F, i, j, c, b, s, RB): the nonzero entries, for the p
    side at a = i and for the q side at a = j, each times the output's
    weight ``wt`` (F, 8, RB) — the mask times the robust sqrt-weight.
    """
    F, RB = coh.shape[0], coh.shape[-1]
    pa, pb, pc, pd = gather_jones_rows(jones, ap, cm)  # J_p 00, 01, 10, 11
    qa, qb, qc, qd = (jnp.conj(x) for x in gather_jones_rows(jones, aq, cm))
    c00, c01, c10, c11 = coh[:, 0], coh[:, 1], coh[:, 2], coh[:, 3]
    G = jnp.stack([  # (F, b, j, RB)
        jnp.stack([c00 * qa + c01 * qb, c00 * qc + c01 * qd], axis=1),
        jnp.stack([c10 * qa + c11 * qb, c10 * qc + c11 * qd], axis=1),
    ], axis=1)
    H = jnp.stack([  # (F, i, b, RB)
        jnp.stack([pa * c00 + pb * c10, pa * c01 + pb * c11], axis=1),
        jnp.stack([pc * c00 + pd * c10, pc * c01 + pd * c11], axis=1),
    ], axis=1)
    Gr, Gi, Hr, Hi = jnp.real(G), jnp.imag(G), jnp.real(H), jnp.imag(H)
    # [c][s]: the real (c) part of u_s g, and of conj(u_s) h
    D = jnp.stack([jnp.stack([Gr, -Gi], 3), jnp.stack([Gi, Gr], 3)], 3)
    E = jnp.stack([jnp.stack([Hr, Hi], 3), jnp.stack([Hi, -Hr], 3)], 3)
    w = wt.reshape(F, 2, 2, 2, 1, 1, RB)  # (f, i, j, c)
    P = w * jnp.transpose(D, (0, 2, 3, 1, 4, 5))[:, None]  # D (f, b, j, c, s)
    Q = w * jnp.transpose(E, (0, 1, 3, 2, 4, 5))[:, :, None]  # E (f, i, b, c, s)
    return P, Q


def _assemble_normal_eq(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, nchunk, sqrt_w):
    """Row-blocked pass -> (JTJ (nchunk,8N,8N), JTe (nchunk,8N), cost (nchunk,)).

    Sign convention: residual e = vis - model, Jacobian taken of the
    *model*, so the gradient of 0.5||e||^2 is -J^T e; we return JTe = J^T e
    (the LM step solves (JTJ + mu I) dp = JTe).

    Each residual row depends only on its two stations' 16 parameters, so
    J^T J is a sum of per-row 8x8 blocks (pp, pq, qq) — the TPU answer to
    the reference's full (8*Nbase*tilesz x 8N) Jacobian (clmfit.c).  The
    blocks are formed in closed form as (entries, rows) planes
    (:func:`_jacobian_planes`) and summed over rows by one-hot matmuls:
    the pp/qq blocks and the gradients onto (chunk, station), the pq
    blocks onto (chunk, station pair).  No scatter: a scatter-add whose
    indices repeat runs its updates one after another on the TPU.  Rows
    are processed in blocks under ``lax.scan`` so the planes and one-hot
    maps stay bounded at any tile size.
    """
    N = p_all.shape[-1] // 8
    dtype = p_all.dtype
    rows = ant_p.shape[0]
    F = vis.shape[-3]
    npair = N * (N + 1) // 2
    K1, K2 = nchunk * N, nchunk * npair

    e = _residual_flat(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_w)
    cost = _per_chunk_sum(jnp.sum(e * e, axis=(0, 1)), chunk_map, nchunk)

    wt = mask[:, None, :] if sqrt_w is None else mask[:, None, :] * sqrt_w
    wt = jnp.broadcast_to(wt, e.shape).astype(dtype)  # (F, 8, rows)
    lo = jnp.minimum(ant_p, ant_q)
    hi = jnp.maximum(ant_p, ant_q)
    pair = chunk_map * npair + _pair_index(lo, hi, N)
    swap = ant_p > ant_q
    jones = params_to_jones(p_all)

    nblk = -(-rows // _ROW_BLOCK)
    RB = -(-rows // nblk)
    padr = nblk * RB - rows

    def blocks(x):  # (..., rows) -> (nblk, ..., RB); padded rows weigh 0
        x = _pad_rows(x, padr).reshape(x.shape[:-1] + (nblk, RB))
        return jnp.moveaxis(x, -2, 0)

    def block(carry, xs):
        diag, off = carry
        coh_k, wt_k, e_k, ap, aq, cm, pair_k, swap_k = xs
        P, Q = _jacobian_planes(jones, coh_k, wt_k, ap, aq, cm)
        e6 = e_k.reshape(F, 2, 2, 2, 1, 1, RB)
        # pp: sum over (f, j, c) at a = i; qq: over (f, i, c) at a = j;
        # pq: over (f, c) with a = i on the p side and a' = j on the q side
        Pl, Pr = P[..., None, None, :], P[:, :, :, :, None, None]
        Ql, Qr = Q[..., None, None, :], Q[:, :, :, :, None, None]
        App = jnp.sum(Pl * Pr, axis=(0, 2, 3))  # (a, b, s, b', s', RB)
        Aqq = jnp.sum(Ql * Qr, axis=(0, 1, 3))
        Apq = jnp.sum(Pl * Qr, axis=(0, 3))  # (a, a', b, s, b', s', RB)
        gp = jnp.sum(P * e6, axis=(0, 2, 3))  # (a, b, s, RB)
        gq = jnp.sum(Q * e6, axis=(0, 1, 3))
        # a row with p > q lands on the pair (q, p) as its pq block's
        # transpose
        Apq = jnp.where(swap_k, jnp.transpose(Apq, (1, 0, 4, 5, 2, 3, 6)), Apq)
        Dp = jnp.concatenate([App.reshape(32, RB), gp.reshape(8, RB)])
        Dq = jnp.concatenate([Aqq.reshape(32, RB), gq.reshape(8, RB)])
        diag = (diag + Dp @ _one_hot(cm * N + ap, K1, dtype)
                + Dq @ _one_hot(cm * N + aq, K1, dtype))
        off = off + Apq.reshape(64, RB) @ _one_hot(pair_k, K2, dtype)
        return (diag, off), None

    from sagecal_tpu.utils.platform import match_vma

    xs = (blocks(coh), blocks(wt), blocks(e), blocks(ant_p), blocks(ant_q),
          blocks(chunk_map), blocks(pair), blocks(swap))
    init = (jnp.zeros((40, K1), dtype), jnp.zeros((64, K2), dtype))
    (diag, off), _ = jax.lax.scan(block, match_vma(init, e), xs)

    # pair sums -> the (N, N) grid by a static gather: the pair's block
    # above the diagonal, its transpose below, both on it
    pq = np.arange(N)
    lo_s, hi_s = np.minimum.outer(pq, pq), np.maximum.outer(pq, pq)
    grid = off.reshape(2, 2, 2, 2, 2, 2, nchunk, npair)[
        ..., _pair_index(lo_s, hi_s, N).reshape(-1)]
    upper = (pq[:, None] <= pq[None, :]).reshape(-1)
    lower = (pq[:, None] >= pq[None, :]).reshape(-1)
    grid = (jnp.where(upper, grid, 0)
            + jnp.where(lower, jnp.transpose(grid, (1, 0, 4, 5, 2, 3, 6, 7)), 0))
    grid = grid.reshape(2, 2, 2, 2, 2, 2, nchunk, N, N)
    # pp + qq blocks on the diagonal; they couple only a = a'
    App = diag[:32].reshape(2, 1, 2, 2, 2, 2, nchunk, N, 1)
    eye2 = jnp.asarray(np.eye(2).reshape(2, 2, 1, 1, 1, 1, 1, 1, 1), dtype)
    grid = grid + App * eye2 * jnp.asarray(np.eye(N), dtype)
    # (a, a', b, s, b', s', c, p, q) -> (c, p(b, a, s), q(b', a', s'))
    JTJ = jnp.transpose(grid, (6, 7, 2, 0, 3, 8, 4, 1, 5)).reshape(
        nchunk, 8 * N, 8 * N)
    JTe = jnp.transpose(diag[32:].reshape(2, 2, 2, nchunk, N),
                        (3, 4, 1, 0, 2)).reshape(nchunk, 8 * N)
    return JTJ, JTe, cost


def _cost_only(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, nchunk, sqrt_w):
    e = _residual_flat(p_all, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_w)
    return _per_chunk_sum(jnp.sum(e * e, axis=(0, 1)), chunk_map, nchunk)


def _solve_spd(A, b):
    """Batched damped-normal-equation solve via Cholesky with SVD-free
    jitter fallback (the reference offers Cholesky/QR/SVD by ``linsolv``;
    on TPU a jittered Cholesky covers the QR/SVD rescue role)."""
    n = A.shape[-1]
    eye = jnp.eye(n, dtype=A.dtype)

    def chol_solve(Ai, bi):
        L, lower = jax.scipy.linalg.cho_factor(Ai + 1e-9 * eye, lower=True)
        x = jax.scipy.linalg.cho_solve((L, lower), bi)
        ok = jnp.all(jnp.isfinite(x))
        x2 = jnp.linalg.solve(Ai + 1e-5 * eye, bi)
        return jnp.where(ok, x, x2)

    return jax.vmap(chol_solve)(A, b)


@true_f32
def lm_solve(
    vis: jax.Array,
    coh: jax.Array,
    mask: jax.Array,
    ant_p: jax.Array,
    ant_q: jax.Array,
    chunk_map: jax.Array,
    p0: jax.Array,
    config: LMConfig = LMConfig(),
    sqrt_weights: Optional[jax.Array] = None,
    itmax_dynamic: Optional[jax.Array] = None,
    admm_y: Optional[jax.Array] = None,
    admm_bz: Optional[jax.Array] = None,
    admm_rho: Optional[jax.Array] = None,
    collect_quality: bool = False,
) -> LMResult:
    """Solve min_p sum_rows ||vis - J_p C J_q^H||^2 per hybrid chunk.

    ``collect_quality``: statically enables the fixed-shape quality side
    outputs (ops/quality.py): chi^2 attribution of the final residual
    per station / baseline / chunk plus gain health of the final p.
    Attribution is of the DATA term only — in ADMM-augmented solves the
    consensus terms are excluded, so ``quality.chi2_chunk`` equals the
    reported ``cost`` exactly only for plain solves.

    ``itmax_dynamic``: optional traced iteration bound (the SAGE driver's
    weighted per-cluster iteration allocation, lmfit.c:859-882);
    ``config.itmax`` stays the static compile-time ceiling.

    ADMM augmentation (``admm_y/admm_bz`` (nchunk, 8N), ``admm_rho``
    scalar): adds ``y^T(p - bz) + rho/2 ||p - bz||^2`` to the cost — the
    consensus-constrained local solve of ``sagefit_visibilities_admm``
    (admm_solve.c:221; cost contract Dirac.h:1182-1195).  The augmented
    term is exactly quadratic, so it enters the normal equations as
    ``JTJ += rho I`` and ``JTe -= y + rho (p - bz)``.

    Args:
      vis: (F, 4, rows) complex effective data for this cluster (flat).
      coh: (F, 4, rows) complex precomputed cluster coherencies (flat).
      mask: (F, rows) flag mask.
      ant_p/ant_q: (rows,) station indices.
      chunk_map: (rows,) int32 hybrid-chunk index of each row.
      p0: (nchunk, 8N) initial parameters.
      sqrt_weights: optional (F, 8, rows)-broadcastable robust sqrt-weights.
    Returns LMResult with per-chunk solutions.
    """
    nchunk = p0.shape[0]
    args = (coh, vis, mask, ant_p, ant_q, chunk_map, nchunk, sqrt_weights)
    with_admm = admm_y is not None
    if with_admm:
        rho = jnp.asarray(admm_rho, p0.dtype)

        def aug_cost(p, c):
            d = p - admm_bz
            return c + jnp.sum(admm_y * d, axis=-1) + 0.5 * rho * jnp.sum(d * d, axis=-1)

        # JTe carries the HALF-gradient convention (grad of sum(e*e) is
        # -2*JTe), so the augmented terms enter at half strength too:
        # gradient 0.5*y + 0.5*rho*(p-bz), Hessian 0.5*rho*I — exactly the
        # reference's factors (rtr_solve_robust_admm.c:680-689,941-942).
        def aug_grad(p):
            return 0.5 * (admm_y + rho * (p - admm_bz))

    else:

        def aug_cost(p, c):
            return c

        def aug_grad(p):
            return jnp.zeros_like(p)

    JTJ, JTe, cost0 = _assemble_normal_eq(p0, *args)
    cost0 = aug_cost(p0, cost0)
    # mu_0 = tau * max(diag(JTJ)) per chunk (levmar init)
    diag0 = jnp.diagonal(JTJ, axis1=-2, axis2=-1)
    mu0 = config.tau * jnp.max(diag0, axis=-1)

    it_bound = (
        jnp.asarray(config.itmax)
        if itmax_dynamic is None
        else jnp.minimum(config.itmax, itmax_dynamic)
    )

    def cond(st):
        it, p, cost, mu, nu, done = st
        return (it < it_bound) & (~jnp.all(done))

    def body(st):
        it, p, cost, mu, nu, done = st
        JTJ, JTe, _ = _assemble_normal_eq(p, *args)
        JTe = JTe - aug_grad(p)
        n8 = p.shape[-1]
        damp = mu + 0.5 * rho if with_admm else mu
        A = JTJ + damp[:, None, None] * jnp.eye(n8, dtype=p.dtype)[None]
        dp = _solve_spd(A, JTe)
        pnew = p + dp
        cost_new = aug_cost(pnew, _cost_only(pnew, *args))
        # gain ratio (cost - cost_new) / (dp.(damp*dp + JTe)): the
        # predicted decrease of the (possibly ADMM-augmented) quadratic
        # model must use the same damping the step was solved with —
        # damp = mu + rho/2 in consensus solves — or the ratio
        # misestimates and mu adaptation drifts for large rho
        denom = jnp.sum(dp * (damp[:, None] * dp + JTe), axis=-1)
        gain = (cost - cost_new) / jnp.where(denom == 0.0, 1e-30, denom)
        accept = (gain > 0.0) & jnp.isfinite(cost_new) & (~done)
        fac = jnp.maximum(1.0 / 3.0, 1.0 - (2.0 * gain - 1.0) ** 3)
        mu_acc = mu * fac
        mu_rej = mu * nu
        p1 = jnp.where(accept[:, None], pnew, p)
        cost1 = jnp.where(accept, cost_new, cost)
        mu1 = jnp.where(done, mu, jnp.where(accept, mu_acc, mu_rej))
        nu1 = jnp.where(done, nu, jnp.where(accept, 2.0, 2.0 * nu))
        # termination (per chunk)
        g_inf = jnp.max(jnp.abs(JTe), axis=-1)
        small_step = jnp.linalg.norm(dp, axis=-1) <= config.eps2 * (
            jnp.linalg.norm(p1, axis=-1) + config.eps2
        )
        done1 = done | (g_inf <= config.eps1) | small_step | (cost1 <= config.eps3)
        return it + 1, p1, cost1, mu1, nu1, done1

    from sagecal_tpu.utils.platform import match_vma

    nu0 = jnp.full((nchunk,), 2.0, p0.dtype)
    done0 = jnp.zeros((nchunk,), bool)
    it, p, cost, _, _, _ = jax.lax.while_loop(
        cond, body,
        match_vma((jnp.asarray(0), p0, cost0, mu0, nu0, done0), p0),
    )
    quality = None
    if collect_quality:
        e1 = _residual_flat(
            p, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_weights
        )
        quality = residual_quality(
            e1, p, ant_p, ant_q, chunk_map, nchunk
        )
    return LMResult(p=p, cost0=cost0, cost=cost, iterations=it,
                    quality=quality)


@true_f32
def os_lm_solve(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    config: LMConfig = LMConfig(),
    sqrt_weights: Optional[jax.Array] = None,
    nsubsets: int = 4,
    key: Optional[jax.Array] = None,
    collect_quality: bool = False,
) -> LMResult:
    """Ordered-subsets accelerated LM (``oslevmar_der_single_nocuda``,
    Dirac.h:907): each outer iteration runs one LM pass on a random subset
    of rows.  Subsets are realized as masks (static shapes) — rows outside
    the active subset get zero mask, so every pass touches all rows but
    only the subset contributes; per-subset cost is rescaled by the subset
    fraction, mirroring the reference's per-subset normal equations.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    rows = vis.shape[-1]
    perm = jax.random.permutation(key, rows)
    subset_of_row = jnp.zeros((rows,), jnp.int32).at[perm].set(
        jnp.arange(rows, dtype=jnp.int32) % nsubsets
    )
    sub_cfg = LMConfig(
        itmax=max(1, config.itmax // nsubsets),
        tau=config.tau, eps1=config.eps1, eps2=config.eps2, eps3=config.eps3,
    )
    p = p0
    cost0 = None
    for s in range(nsubsets):
        m_s = mask * (subset_of_row == s)[None, :].astype(mask.dtype)
        res = lm_solve(
            vis, coh, m_s, ant_p, ant_q, chunk_map, p, sub_cfg, sqrt_weights,
        )
        p = res.p
        if cost0 is None:
            cost0 = res.cost0 * nsubsets
    final_cost = _cost_only(
        p, coh, vis, mask, ant_p, ant_q, chunk_map, p0.shape[0], sqrt_weights
    )
    quality = None
    if collect_quality:
        # attribution of the FULL-mask residual at the final p (each
        # subset pass only ever saw its own rows; quality reports the
        # solver's final objective over all of them)
        e1 = _residual_flat(
            p, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_weights
        )
        quality = residual_quality(
            e1, p, ant_p, ant_q, chunk_map, p0.shape[0]
        )
    return LMResult(p=p, cost0=cost0, cost=final_cost,
                    iterations=jnp.asarray(config.itmax),
                    quality=quality)


# Jitted module entries (obs/perf.py): inside the packed SAGE solve
# these solvers are traced as part of one big jit; the standalone
# wrappers below are for eager callers (tests, notebooks, partial
# pipelines) and record compile/recompile + cost-analysis telemetry
# under SAGECAL_TELEMETRY=1.  A changed LMConfig is a new static
# signature, i.e. a visible recompile.
from sagecal_tpu.obs.perf import instrumented_jit  # noqa: E402

lm_solve_jit = instrumented_jit(
    lm_solve, name="lm_solve",
    static_argnames=("collect_quality",))
os_lm_solve_jit = instrumented_jit(
    os_lm_solve, name="os_lm_solve",
    static_argnames=("nsubsets", "collect_quality"))
