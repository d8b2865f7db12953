"""Riemannian Trust Region + Nesterov steepest descent on the Jones
quotient manifold.

Redesign of ``/root/reference/src/lib/Dirac/rtr_solve.c`` (ICASSP'13
solver; entry ``rtr_solve_nocuda`` decl Dirac.h:1132), the robust
variants (``rtr_solve_robust.c``) and ``nsd_solve_nocuda_robust``
(rtr_solve_robust.c:1878).  The reference evaluates cost/gradient/
Hessian with pthread scatter-add loops guarded by per-station mutexes;
here the Euclidean gradient and the Hessian-vector product come from
``jax.grad`` / ``jax.jvp`` of the one jitted cost function, the
per-station scatter is an XLA ``segment-sum`` (race-free by
construction), and hybrid chunks solve in lock-step under ``vmap``.

Faithfully reproduced structure (rtr_solve.c:1208-1556):
- solution space: X in C^{2N x 2} (station-stacked Jones columns),
  quotient by the right unitary U(2) ambiguity;
- metric  g(eta, gamma) = 2 Re trace(eta^H gamma)  (fns_g, :323);
- horizontal projection  z - X Om  with  Om M + M Om = X^H z - z^H X,
  M = X^H X, solved as a 4x4 Sylvester system (fns_proj, :340);
- retraction R(x, eta) = x + eta (fns_R, :419 — additive, not QR);
- per-station gradient normalization by inverse baseline counts,
  scaled to max 1 (fns_fcount, :99-180);
- RSD (Armijo) warmup iterations, then TR with truncated CG
  (tcg_solve, :887): theta=1, kappa=0.1, eta1=1e-4, eta2=0.99,
  alpha1=0.25, alpha2=3.5, Delta_bar=min(f0, 0.01), Delta0=Delta_bar/8,
  rho regularization f0*1e-6;
- NSD: Nesterov acceleration theta_{k+1}=2/(1+sqrt(1+4/theta_k^2)) with
  adaptive Barzilai-Borwein-style step and growth/shrink 1.01/0.5
  (rtr_solve_robust.c:2020-2085).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import struct

from sagecal_tpu.core.types import params_to_jones, jones_to_params
from sagecal_tpu.obs.scopes import scoped
from sagecal_tpu.utils.precision import true_f32


@struct.dataclass
class RTRConfig:
    itmax_rsd: int = struct.field(pytree_node=False, default=2)
    itmax_rtr: int = struct.field(pytree_node=False, default=10)
    max_inner: int = struct.field(pytree_node=False, default=10)
    theta: float = struct.field(pytree_node=False, default=1.0)
    kappa: float = struct.field(pytree_node=False, default=0.1)
    eta1: float = struct.field(pytree_node=False, default=1e-4)
    eta2: float = struct.field(pytree_node=False, default=0.99)
    alpha1: float = struct.field(pytree_node=False, default=0.25)
    alpha2: float = struct.field(pytree_node=False, default=3.5)
    epsilon: float = struct.field(pytree_node=False, default=1e-12)


class TRSteps(NamedTuple):
    """Trust-region work of a solve, as int32 counts: the TR loops run,
    their TR steps and tCG steps, the loops that ended at their
    iteration bound rather than at the gradient tolerance, and the RSD
    warm-up iterations run ahead of them and those that moved x.
    Always returned (fixed shape), so the timed program reports its own
    work."""

    loops: jax.Array
    tr: jax.Array
    tcg: jax.Array
    capped: jax.Array
    rsd: jax.Array
    rsd_moved: jax.Array


def no_tr_steps() -> TRSteps:
    """The counts of a solve that runs no trust-region loop."""
    z = jnp.zeros((), jnp.int32)
    return TRSteps(z, z, z, z, z, z)


def sum_tr_steps(steps: TRSteps) -> TRSteps:
    """Sum stacked counts over their leading axis."""
    return TRSteps(*(jnp.sum(a, axis=0, dtype=jnp.int32) for a in steps))


class RTRResult(NamedTuple):
    p: jax.Array  # (nchunk, 8N)
    cost0: jax.Array  # (nchunk,)
    cost: jax.Array  # (nchunk,)
    # SolveQuality (ops.quality) when collect_quality=True, else None —
    # an empty pytree, so the jitted output signature is unchanged
    quality: Optional[tuple] = None
    # TRSteps summed over chunks (and robust rounds)
    steps: Optional[TRSteps] = None


# ---------------------------------------------------------------------------
# geometry: metric, projection
# ---------------------------------------------------------------------------

def _g(eta, gamma):
    """Metric 2*Re<eta, gamma> on (N, 2, 2) tangent arrays (fns_g)."""
    return 2.0 * jnp.sum(jnp.real(jnp.conj(eta) * gamma))


def _project(x, z):
    """Horizontal projection z - X Om (fns_proj, rtr_solve.c:340).

    x, z: (N, 2, 2) station Jones stacks; the 2Nx2 matrix view is
    X[2s+r, c] = x[s, r, c].
    """
    N = x.shape[0]
    X = x.reshape(2 * N, 2)
    Z = z.reshape(2 * N, 2)
    M = jnp.conj(X.T) @ X  # (2, 2)
    R = jnp.conj(X.T) @ Z
    R = R - jnp.conj(R.T)  # X^H Z - Z^H X
    eye = jnp.eye(2, dtype=x.dtype)
    A = jnp.kron(eye, M) + jnp.kron(M.T, eye)  # acts on vec_colmajor(Om)
    b = R.T.reshape(-1)  # column-major vec of R
    u = jnp.linalg.solve(A + 1e-12 * jnp.eye(4, dtype=x.dtype), b)
    Om = u.reshape(2, 2).T  # back from column-major
    out = Z - X @ Om
    return out.reshape(N, 2, 2)


# ---------------------------------------------------------------------------
# cost / gradient / hessian-vector (per chunk lane)
# ---------------------------------------------------------------------------

def _model_rows(x, coh, ant_p, ant_q):
    from sagecal_tpu.core.types import corrupt_flat

    return corrupt_flat(x, coh, ant_p, ant_q)


def _make_fns(vis, coh, rowmask, ant_p, ant_q, sqrt_w, admm=None):
    """Build (cost, grad, hess) closures for one chunk lane.

    vis/coh: flat (F, 4, rows) complex; rowmask: (F, rows) —
    already restricted to this chunk's rows; sqrt_w: optional robust
    sqrt-weights broadcastable against (F, 4, rows).

    ``admm``: optional (Yc, BZc, rho) consensus terms ((N,2,2) complex
    Lagrange multipliers / target, scalar penalty): the augmented cost
    ``Re tr(Y^H (X-BZ)) + rho/2 ||X-BZ||^2`` of the ADMM solvers
    (rtr_solve_robust_admm.c:199-215).  Following the reference, the
    ADMM gradient terms ``0.5 Y + 0.5 rho (X-BZ)`` and Hessian term
    ``0.5 rho eta`` are added AFTER the per-station iw normalization of
    the data gradient (rtr_solve_robust_admm.c:680-689,941-942) and
    before projection.
    """

    def admm_cost(x):
        if admm is None:
            return jnp.asarray(0.0, vis.real.dtype)
        Yc, BZc, rho = admm
        d = x - BZc
        return jnp.sum(jnp.real(jnp.conj(Yc) * d)) + 0.5 * rho * jnp.sum(
            jnp.real(d) ** 2 + jnp.imag(d) ** 2
        )

    def cost_c(x):
        res = (vis - _model_rows(x, coh, ant_p, ant_q)) * rowmask[..., None, :]
        if sqrt_w is not None:
            res = res * sqrt_w
        return jnp.sum(jnp.real(res) ** 2 + jnp.imag(res) ** 2) + admm_cost(x)

    def data_cost_c(x):
        res = (vis - _model_rows(x, coh, ant_p, ant_q)) * rowmask[..., None, :]
        if sqrt_w is not None:
            res = res * sqrt_w
        return jnp.sum(jnp.real(res) ** 2 + jnp.imag(res) ** 2)

    def cost_ri(xri):
        return data_cost_c(jax.lax.complex(xri[..., 0], xri[..., 1]))

    def egrad(x):
        """DATA Euclidean gradient in the fns convention:
        0.5*(df/dre + i df/dim) so that df along eta = g(egrad, eta).
        ADMM terms are added separately (un-iw-weighted)."""
        xri = jnp.stack([jnp.real(x), jnp.imag(x)], axis=-1)
        gri = jax.grad(cost_ri)(xri)
        return 0.5 * jax.lax.complex(gri[..., 0], gri[..., 1])

    def grad_fn(x, iw):
        """Weighted, projected Riemannian gradient (fns_fgrad)."""
        g = egrad(x) * iw[:, None, None]
        if admm is not None:
            Yc, BZc, rho = admm
            g = g + 0.5 * (Yc + rho * (x - BZc))
        return _project(x, g)

    def hess_fn(x, eta, iw):
        """Projected directional derivative of the weighted Euclidean
        gradient (fns_fhess): jvp through egrad."""

        def weg(xx):
            return egrad(xx) * iw[:, None, None]

        # jvp over complex inputs: drive through the re/im stacking
        def weg_ri(xri):
            out = weg(jax.lax.complex(xri[..., 0], xri[..., 1]))
            return jnp.stack([jnp.real(out), jnp.imag(out)], axis=-1)

        xri = jnp.stack([jnp.real(x), jnp.imag(x)], axis=-1)
        tri = jnp.stack([jnp.real(eta), jnp.imag(eta)], axis=-1)
        _, dri = jax.jvp(weg_ri, (xri,), (tri,))
        h = jax.lax.complex(dri[..., 0], dri[..., 1])
        if admm is not None:
            h = h + 0.5 * admm[2] * eta
        return _project(x, h)

    return cost_c, grad_fn, hess_fn


def _station_iw(rowmask, ant_p, ant_q, N):
    """Inverse baseline-count weights, scaled to max 1
    (fns_fcount, rtr_solve.c:99-180).  rowmask: (F, rows)."""
    good = (jnp.sum(rowmask, axis=0) > 0).astype(rowmask.dtype)
    cnt = jnp.zeros((N,), rowmask.dtype).at[ant_p].add(good).at[ant_q].add(good)
    iw = jnp.where(cnt > 0, 1.0 / jnp.maximum(cnt, 1), 0.0)
    mx = jnp.max(iw)
    return jnp.where(mx > 0, iw / mx, iw)


# ---------------------------------------------------------------------------
# truncated CG (tcg_solve, rtr_solve.c:887-1080)
# ---------------------------------------------------------------------------

@scoped("tcg")
def _tcg(x, grad, Delta, hess, cfg: RTRConfig):
    N = x.shape[0]
    zeros = jnp.zeros_like(x)
    r = grad
    r_r = _g(r, r)
    norm_r0 = jnp.sqrt(r_r)
    z = r
    z_r = _g(z, r)
    delta = -z
    state = dict(
        j=jnp.asarray(0), eta=zeros, Heta=zeros, r=r, delta=delta,
        e_Pe=jnp.asarray(0.0), e_Pd=_g(zeros, delta), d_Pd=z_r, z_r=z_r,
        stop=jnp.asarray(False),
    )

    Deltasq = Delta * Delta

    def cond(s):
        return (s["j"] < cfg.max_inner) & (~s["stop"])

    def body(s):
        Hxd = hess(x, s["delta"])
        d_Hd = _g(s["delta"], Hxd)
        alpha = s["z_r"] / jnp.where(d_Hd == 0.0, 1e-30, d_Hd)
        e_Pe_new = s["e_Pe"] + 2.0 * alpha * s["e_Pd"] + alpha * alpha * s["d_Pd"]

        # negative curvature or TR boundary -> tau step and stop
        hit = (d_Hd <= 0.0) | (e_Pe_new >= Deltasq)
        disc = s["e_Pd"] ** 2 + s["d_Pd"] * (Deltasq - s["e_Pe"])
        tau = (-s["e_Pd"] + jnp.sqrt(jnp.maximum(disc, 0.0))) / jnp.where(
            s["d_Pd"] == 0.0, 1e-30, s["d_Pd"]
        )
        step = jnp.where(hit, tau, alpha)
        eta_new = s["eta"] + step * s["delta"]
        Heta_new = s["Heta"] + step * Hxd

        r_new = s["r"] + alpha * Hxd
        r_r_new = _g(r_new, r_new)
        norm_r = jnp.sqrt(r_r_new)
        # linear/superlinear convergence test
        kconv = norm_r <= norm_r0 * jnp.minimum(norm_r0**cfg.theta, cfg.kappa)
        stop = hit | kconv

        z_new = r_new  # identity preconditioner
        z_r_new = _g(z_new, r_new)
        beta = z_r_new / jnp.where(s["z_r"] == 0.0, 1e-30, s["z_r"])
        delta_new = -z_new + beta * s["delta"]
        e_Pd_new = beta * (s["e_Pd"] + step * s["d_Pd"])
        d_Pd_new = z_r_new + beta * beta * s["d_Pd"]

        return dict(
            j=s["j"] + 1,
            eta=eta_new, Heta=Heta_new,
            r=jnp.where(stop, s["r"], r_new),
            delta=delta_new,
            e_Pe=jnp.where(hit, s["e_Pe"], e_Pe_new),
            e_Pd=e_Pd_new, d_Pd=d_Pd_new, z_r=z_r_new,
            stop=stop,
        )

    from sagecal_tpu.utils.platform import match_vma

    out = jax.lax.while_loop(cond, body, match_vma(state, grad))
    return out["eta"], out["Heta"], out["j"]


# ---------------------------------------------------------------------------
# single-chunk RTR / NSD
# ---------------------------------------------------------------------------

def _rtr_single(
    vis, coh, rowmask, ant_p, ant_q, x0, cfg: RTRConfig, sqrt_w, itmax_dyn=None,
    admm=None,
):
    """``itmax_dyn``: optional traced base iteration budget; the RSD/TR
    bounds become min(static, dyn+5)/min(static, dyn+10), matching the
    reference's this_itermax+5/+10 call-site offsets (lmfit.c:936).
    ``admm``: optional (Yc, BZc, rho) consensus augmentation
    (rtr_solve_nocuda_robust_admm, rtr_solve_robust_admm.c)."""
    N = x0.shape[0]
    cost_c, grad_fn, hess_fn = _make_fns(
        vis, coh, rowmask, ant_p, ant_q, sqrt_w, admm
    )
    iw = _station_iw(rowmask, ant_p, ant_q, N)
    rsd_bound = (
        jnp.asarray(cfg.itmax_rsd)
        if itmax_dyn is None
        else jnp.minimum(cfg.itmax_rsd, itmax_dyn + 5)
    )
    rtr_bound = (
        jnp.asarray(cfg.itmax_rtr)
        if itmax_dyn is None
        else jnp.minimum(cfg.itmax_rtr, itmax_dyn + 10)
    )

    def hess(x, eta):
        return hess_fn(x, eta, iw)

    fx0 = cost_c(x0)

    # ---- RSD warmup with Armijo backtracking (armijostep) -------------
    # A rejected step leaves x unchanged, and with it every later
    # iteration: the loop ends there, or at the dynamic bound.  Each
    # trial cost is evaluated once and the accepted one carried as fx.
    def rsd_cond(s):
        i, _, _, moved = s
        return (i < rsd_bound) & moved

    def rsd_body(s):
        i, x, fx, _ = s
        g = grad_fn(x, iw)
        gg = _g(g, g)

        def armijo_cond(st):
            k, beta, f_try = st
            return (k < 12) & (f_try > fx - 1e-4 * beta * gg)

        def armijo_body(st):
            k, beta, _ = st
            beta = beta * 0.5
            return k + 1, beta, cost_c(x - beta * g)

        _, beta, f_try = jax.lax.while_loop(
            armijo_cond, armijo_body,
            (0, jnp.asarray(1.0, gg.dtype), cost_c(x - g)))
        improved = f_try < fx
        return (i + 1, jnp.where(improved, x - beta * g, x),
                jnp.where(improved, f_try, fx), improved)

    from sagecal_tpu.utils.platform import match_vma

    with jax.named_scope("rsd"):
        n_rsd, x, fx, moved = jax.lax.while_loop(
            rsd_cond, rsd_body,
            match_vma((jnp.asarray(0, jnp.int32), x0, fx0,
                       jnp.asarray(True)), x0))

    Delta_bar = jnp.minimum(fx, 0.01)
    Delta0 = Delta_bar * 0.125
    rho_reg0 = fx * 1e-6

    def tr_cond(s):
        return (s["k"] < rtr_bound) & (~s["stop"])

    def tr_body(s):
        x, fx, Delta = s["x"], s["fx"], s["Delta"]
        g = grad_fn(x, iw)
        eta, Heta, cg_j = _tcg(x, g, Delta, hess, cfg)
        x_prop = x + eta  # fns_R: additive retraction
        fx_prop = cost_c(x_prop)
        rhonum = fx - fx_prop
        rhoden = -_g(g, eta) - 0.5 * _g(Heta, eta)
        rho_reg = jnp.maximum(1.0, fx) * rho_reg0
        rho = (rhonum + rho_reg) / jnp.where(
            rhoden + rho_reg == 0.0, 1e-30, rhoden + rho_reg
        )
        model_dec = rhoden > 0.0
        accept = (rho > cfg.eta1) & model_dec & (fx_prop < fx)
        Delta_new = jnp.where(
            rho < cfg.eta1,
            Delta * cfg.alpha1,
            jnp.where(
                (rho > cfg.eta2) & model_dec,
                jnp.minimum(Delta * cfg.alpha2, Delta_bar),
                Delta,
            ),
        )
        x1 = jnp.where(accept, x_prop, x)
        fx1 = jnp.where(accept, fx_prop, fx)
        gnorm = jnp.sqrt(_g(g, g))
        return dict(
            k=s["k"] + 1, x=x1, fx=fx1, Delta=Delta_new,
            stop=gnorm < cfg.epsilon, cg=s["cg"] + cg_j.astype(jnp.int32),
        )

    state0 = dict(k=jnp.asarray(0, jnp.int32), x=x, fx=fx, Delta=Delta0,
                  stop=jnp.asarray(False), cg=jnp.asarray(0, jnp.int32))
    with jax.named_scope("tr"):
        out = jax.lax.while_loop(tr_cond, tr_body, match_vma(state0, x))
    steps = TRSteps(loops=jnp.ones((), jnp.int32), tr=out["k"],
                    tcg=out["cg"],
                    capped=(~out["stop"]).astype(jnp.int32),
                    rsd=n_rsd, rsd_moved=n_rsd - (~moved).astype(jnp.int32))
    # guard: never return something worse than the input
    better = out["fx"] <= fx0
    xf = jnp.where(better, out["x"], x0)
    return xf, fx0, jnp.where(better, out["fx"], fx0), steps


def _nsd_single(
    vis, coh, rowmask, ant_p, ant_q, x0, itmax, sqrt_w, itmax_dyn=None,
    admm=None,
):
    """Nesterov accelerated manifold descent
    (nsd_solve_nocuda_robust, rtr_solve_robust.c:1878-2090).
    ``itmax_dyn``: traced bound, effective limit min(itmax, dyn+15)
    (the reference's this_itermax+15 call-site offset, lmfit.c:953).
    ``admm``: optional (Yc, BZc, rho) consensus augmentation
    (nsd_solve_cuda_robust_admm_fl's CPU analog)."""
    N = x0.shape[0]
    cost_c, grad_fn, hess_fn = _make_fns(
        vis, coh, rowmask, ant_p, ant_q, sqrt_w, admm
    )
    iw = _station_iw(rowmask, ant_p, ant_q, N)
    bound = (
        jnp.asarray(itmax)
        if itmax_dyn is None
        else jnp.minimum(itmax, itmax_dyn + 15)
    )
    fx0 = cost_c(x0)

    g0 = grad_fn(x0, iw)
    h0 = hess_fn(x0, x0, iw)
    hnrm = jnp.sqrt(jnp.sum(jnp.abs(h0) ** 2))
    t0 = jnp.maximum(1.0 / jnp.where(hnrm == 0.0, 1e30, hnrm), 1e-6)

    def body(carry, i):
        x, z, g, t, theta, done = carry
        done = done | (i >= bound)
        x_prop = x
        z_prop = z
        x1 = z - t * g
        gn = jnp.sqrt(jnp.sum(jnp.abs(g) ** 2))
        xn = jnp.sqrt(jnp.sum(jnp.abs(x1) ** 2))
        done1 = done | (gn * t / jnp.maximum(1.0, xn) < 1e-6)
        theta1 = 2.0 / (1.0 + jnp.sqrt(1.0 + 4.0 / (theta * theta)))
        z1 = (2.0 - theta1) * x1 - (1.0 - theta1) * x_prop
        g_old = g
        g1 = grad_fn(z1, iw)
        ydiff = z_prop - z1
        gdiff = g_old - g1
        ydn = jnp.sqrt(jnp.sum(jnp.abs(ydiff) ** 2))
        dot = jnp.sum(
            jnp.real(ydiff) * jnp.real(gdiff) + jnp.imag(ydiff) * jnp.imag(gdiff)
        )
        bad = jnp.isnan(dot) | jnp.isinf(dot)
        t_hat = 0.5 * ydn * ydn / jnp.maximum(jnp.abs(dot), 1e-30)
        t1 = jnp.minimum(1.01 * t, jnp.maximum(0.5 * t, t_hat))
        done2 = done1 | bad
        keep = lambda a, b: jnp.where(done2, a, b)
        carry1 = (
            keep(x, x1), keep(z, z1), keep(g, g1), keep(t, t1),
            keep(theta, theta1), done2,
        )
        return carry1, None

    from sagecal_tpu.utils.platform import match_vma

    (x, _, _, _, _, _), _ = jax.lax.scan(
        body,
        match_vma(
            (x0, x0, g0, t0, jnp.asarray(1.0, t0.dtype), jnp.asarray(False)),
            x0,
        ),
        jnp.arange(itmax),
    )
    fx = cost_c(x)
    better = fx <= fx0
    return (jnp.where(better, x, x0), fx0, jnp.where(better, fx, fx0),
            no_tr_steps())


# ---------------------------------------------------------------------------
# public, chunk-batched entry points
# ---------------------------------------------------------------------------

def _quality_of(p, vis, coh, mask, ant_p, ant_q, chunk_map,
                sqrt_w=None, nu=None):
    """Quality bundle (ops/quality.py) at the final solution ``p``.

    Uses the LM residual path (lm._residual_flat) — for rows of chunk c
    it evaluates the same J_p C J_q^H model with chunk c's parameters the
    per-lane RTR cost uses, so ``chi2_chunk`` equals the solver's final
    per-chunk DATA cost exactly.  ADMM consensus terms are excluded
    (``RTRResult.cost`` includes them when ``admm_*`` is given)."""
    from sagecal_tpu.ops.quality import residual_quality
    from sagecal_tpu.solvers.lm import _residual_flat

    e = _residual_flat(p, coh, vis, mask, ant_p, ant_q, chunk_map, sqrt_w)
    return residual_quality(
        e, p, ant_p, ant_q, chunk_map, p.shape[0],
        nu=nu, sqrt_w=sqrt_w, mask8=mask[..., None, :],
        weight_dof=2.0,  # RTR robust weights are (nu+2)/(nu+e^2)
    )


def _chunked(solver):
    def run(
        vis, coh, mask, ant_p, ant_q, chunk_map, p0, *args,
        admm_y=None, admm_bz=None, admm_rho=None, **kwargs,
    ):
        nchunk = p0.shape[0]
        x0 = params_to_jones(p0)  # (nchunk, N, 2, 2)

        if admm_y is not None:
            # param-space duals/targets -> complex Jones stacks; the real
            # dot y.(p-bz) equals Re tr(Y^H (X-BZ)) elementwise
            Yc = params_to_jones(admm_y)  # (nchunk, N, 2, 2)
            BZc = params_to_jones(admm_bz)
            rho = jnp.broadcast_to(
                jnp.asarray(admm_rho, p0.dtype), (nchunk,)
            )

            def lane(c, x0_c, y_c, bz_c, r_c):
                rowmask = mask * (chunk_map == c)[None, :].astype(mask.dtype)
                return solver(
                    vis, coh, rowmask, ant_p, ant_q, x0_c, *args,
                    admm=(y_c, bz_c, r_c), **kwargs,
                )

            xf, c0, c1, steps = jax.vmap(lane)(
                jnp.arange(nchunk), x0, Yc, BZc, rho)
        else:

            def lane(c, x0_c):
                rowmask = mask * (chunk_map == c)[None, :].astype(mask.dtype)
                return solver(
                    vis, coh, rowmask, ant_p, ant_q, x0_c, *args, **kwargs
                )

            xf, c0, c1, steps = jax.vmap(lane)(jnp.arange(nchunk), x0)
        return RTRResult(p=jones_to_params(xf), cost0=c0, cost=c1,
                         steps=sum_tr_steps(steps))

    return run


@true_f32
def rtr_solve(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    config: RTRConfig = RTRConfig(),
    sqrt_weights: Optional[jax.Array] = None,
    itmax_dynamic=None,
    admm_y=None, admm_bz=None, admm_rho=None,
    collect_quality: bool = False,
) -> RTRResult:
    """Batched-over-chunks RTR solve (``rtr_solve_nocuda``, Dirac.h:1132).

    Args mirror :func:`sagecal_tpu.solvers.lm.lm_solve`; ``sqrt_weights``
    optional (F, 4, rows)-broadcastable robust sqrt-weights;
    ``itmax_dynamic`` optional traced per-call iteration budget (the
    SAGE driver's weighted allocation).  ``admm_y/admm_bz`` (nchunk, 8N)
    + scalar ``admm_rho`` switch on the consensus-augmented cost
    (``rtr_solve_nocuda_admm``/``..._robust_admm``, decl
    Dirac.h:1182-1195).  ``collect_quality`` statically enables the
    fixed-shape quality side outputs (:func:`_quality_of`; data term
    only under ADMM).
    """
    out = _chunked(_rtr_single)(
        vis, coh, mask, ant_p, ant_q, chunk_map, p0, config, sqrt_weights,
        itmax_dynamic, admm_y=admm_y, admm_bz=admm_bz, admm_rho=admm_rho,
    )
    if collect_quality:
        out = out._replace(quality=_quality_of(
            out.p, vis, coh, mask, ant_p, ant_q, chunk_map,
            sqrt_w=sqrt_weights))
    return out


@true_f32
def nsd_solve(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    itmax: int = 10,
    sqrt_weights: Optional[jax.Array] = None,
    itmax_dynamic=None,
    admm_y=None, admm_bz=None, admm_rho=None,
    collect_quality: bool = False,
) -> RTRResult:
    """Batched Nesterov steepest descent (``nsd_solve_nocuda_robust``,
    Dirac.h:1166); ADMM-augmented when ``admm_y/admm_bz/admm_rho`` given
    (``nsd_solve_nocuda_robust_admm``, decl Dirac.h:1207-1224).
    ``collect_quality`` as in :func:`rtr_solve`."""
    out = _chunked(_nsd_single)(
        vis, coh, mask, ant_p, ant_q, chunk_map, p0, itmax, sqrt_weights,
        itmax_dynamic, admm_y=admm_y, admm_bz=admm_bz, admm_rho=admm_rho,
    )
    if collect_quality:
        out = out._replace(quality=_quality_of(
            out.p, vis, coh, mask, ant_p, ant_q, chunk_map,
            sqrt_w=sqrt_weights))
    return out


@scoped("nu")
def _robust_weights_and_nu(
    vis, coh, mask, ant_p, ant_q, chunk_map, p, nu, nulow, nuhigh
):
    """Per-baseline Student's-t weights w = (nu+2)/(nu + max_elem |e|^2)
    — the reference's LIVE variant using the max over the four complex
    residual elements with an AECM p=2 nu update
    (rtr_solve_robust.c:258, update_nu(...,2,...) at :374; the 8-variate
    sum form on :257 is commented out there)."""
    from sagecal_tpu.core.types import corrupt_flat, params_to_jones as _p2j
    from sagecal_tpu.solvers.robust import update_nu_aecm

    x = _p2j(p)  # (nchunk, N, 2, 2)
    model = corrupt_flat(x, coh, ant_p, ant_q, chunk_map)
    res = (vis - model) * mask[..., None, :]
    e2 = jnp.max(
        jnp.real(res) ** 2 + jnp.imag(res) ** 2, axis=-2
    )  # (F, rows): max over the 4 complex elements
    w = (nu + 2.0) / (nu + e2)
    w = jnp.where(mask > 0, w, 1.0)
    msum = jnp.maximum(jnp.sum(mask), 1.0)
    logsumw = jnp.sum((jnp.log(w) - w) * mask) / msum
    nu1 = update_nu_aecm(logsumw, nu, p=2, nulow=nulow, nuhigh=nuhigh)
    return jnp.sqrt(w)[..., None, :], nu1


@true_f32
def rtr_solve_robust(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    config: RTRConfig = RTRConfig(),
    nu0=2.0, nulow: float = 2.0, nuhigh: float = 30.0,
    em_iters: int = 2,
    itmax_dynamic=None,
    admm_y=None, admm_bz=None, admm_rho=None,
    collect_quality: bool = False,
):
    """Student's-t EM wrapping RTR (``rtr_solve_nocuda_robust``,
    Dirac.h:1145): E-step per-baseline weights (see
    :func:`_robust_weights_and_nu`), M-step a weighted RTR solve.
    ``nu0`` may be a traced value (the SAGE driver carries nu across EM
    passes, lmfit.c:940-947).  With ``admm_*`` given this is
    ``rtr_solve_nocuda_robust_admm`` (rtr_solve_robust_admm.c:1427),
    the reference MPI slave's default local solver.
    Returns (RTRResult, nu).

    ``collect_quality`` fills the result's quality slot with the chi^2
    attribution and weight statistics of the FINAL post-loop weight
    re-estimate (the same weights the returned nu is estimated from) —
    the weighted objective at the converged solution, not the last EM
    stage's stale-weight cost."""

    def em(carry, _):
        p, nu = carry
        sqrt_w, nu1 = _robust_weights_and_nu(
            vis, coh, mask, ant_p, ant_q, chunk_map, p, nu, nulow, nuhigh
        )
        out = rtr_solve(
            vis, coh, mask, ant_p, ant_q, chunk_map, p, config,
            sqrt_weights=sqrt_w, itmax_dynamic=itmax_dynamic,
            admm_y=admm_y, admm_bz=admm_bz, admm_rho=admm_rho,
        )
        return (out.p, nu1), (out.cost0, out.cost, out.steps)

    from sagecal_tpu.utils.platform import match_vma

    (p, nu), (c0s, c1s, steps) = jax.lax.scan(
        em, match_vma((p0, jnp.asarray(nu0, p0.dtype)), p0), None,
        length=em_iters
    )
    steps = sum_tr_steps(steps)
    # re-estimate nu from the FINAL solution (the reference updates the
    # weights/nu once more after the loop, rtr_solve_robust.c:1625)
    sqrt_w_f, nu = _robust_weights_and_nu(
        vis, coh, mask, ant_p, ant_q, chunk_map, p, nu, nulow, nuhigh
    )
    quality = None
    if collect_quality:
        quality = _quality_of(
            p, vis, coh, mask, ant_p, ant_q, chunk_map,
            sqrt_w=sqrt_w_f, nu=nu)
    return RTRResult(p=p, cost0=c0s[0], cost=c1s[-1],
                     quality=quality, steps=steps), nu


@true_f32
def nsd_solve_robust(
    vis, coh, mask, ant_p, ant_q, chunk_map, p0,
    itmax: int = 10,
    nu0=2.0, nulow: float = 2.0, nuhigh: float = 30.0,
    em_iters: int = 2,
    itmax_dynamic=None,
    admm_y=None, admm_bz=None, admm_rho=None,
    collect_quality: bool = False,
):
    """Robust Nesterov descent (``nsd_solve_nocuda_robust``,
    rtr_solve_robust.c:1878): the same Student's-t EM around
    :func:`nsd_solve`, with nu re-estimated from the residual after each
    solve (rtr_solve_robust.c:2104-2105).  With ``admm_*`` given this is
    the NSD-ADMM local solver (``nsd_solve_nocuda_robust_admm``, decl
    Dirac.h:1207).  Returns (RTRResult, nu).  ``collect_quality`` as in
    :func:`rtr_solve_robust` (final-weight attribution)."""

    def em(carry, _):
        p, nu = carry
        sqrt_w, nu1 = _robust_weights_and_nu(
            vis, coh, mask, ant_p, ant_q, chunk_map, p, nu, nulow, nuhigh
        )
        out = nsd_solve(
            vis, coh, mask, ant_p, ant_q, chunk_map, p, itmax,
            sqrt_weights=sqrt_w, itmax_dynamic=itmax_dynamic,
            admm_y=admm_y, admm_bz=admm_bz, admm_rho=admm_rho,
        )
        return (out.p, nu1), (out.cost0, out.cost, out.steps)

    from sagecal_tpu.utils.platform import match_vma

    (p, nu), (c0s, c1s, steps) = jax.lax.scan(
        em, match_vma((p0, jnp.asarray(nu0, p0.dtype)), p0), None,
        length=em_iters
    )
    steps = sum_tr_steps(steps)
    # final-solution nu re-estimate (rtr_solve_robust.c:2104)
    sqrt_w_f, nu = _robust_weights_and_nu(
        vis, coh, mask, ant_p, ant_q, chunk_map, p, nu, nulow, nuhigh
    )
    quality = None
    if collect_quality:
        quality = _quality_of(
            p, vis, coh, mask, ant_p, ant_q, chunk_map,
            sqrt_w=sqrt_w_f, nu=nu)
    return RTRResult(p=p, cost0=c0s[0], cost=c1s[-1],
                     quality=quality, steps=steps), nu


# jitted module entries with compile/recompile telemetry (obs/perf.py)
from sagecal_tpu.obs.perf import instrumented_jit  # noqa: E402

rtr_solve_jit = instrumented_jit(
    rtr_solve, name="rtr_solve",
    static_argnames=("collect_quality",))
nsd_solve_jit = instrumented_jit(
    nsd_solve, name="nsd_solve",
    static_argnames=("itmax", "collect_quality"))
rtr_solve_robust_jit = instrumented_jit(
    rtr_solve_robust, name="rtr_solve_robust",
    static_argnames=("em_iters", "collect_quality"))
