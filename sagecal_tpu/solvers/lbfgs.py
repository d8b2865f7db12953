"""LBFGS with persistent minibatch memory — jit-compiled, lax control flow.

Re-design of the reference's CPU LBFGS (``/root/reference/src/lib/Dirac/
lbfgs.c``): the generic cost/grad callback contract of ``lbfgs_fit``
(Dirac.h:158-178) becomes "any jax-traceable ``cost_fn(p)->scalar`` /
``grad_fn(p)->(n,)``"; the pthread-parallel gradient evaluation becomes
whatever XLA parallelism lives inside those callables; the hand-rolled
circular y/s store (``persistent_data_t``, Dirac.h:84-110) becomes the
:class:`LBFGSMemory` pytree, carried across minibatches by the caller
(the functional analog of ``lbfgs_persist_init/reset/clear``).

Faithfully reproduced behaviors:
- two-loop recursion over an M-slot circular store, newest-first ordering
  (``mult_hessian``, lbfgs.c:33-113);
- Armijo backtracking with c=1e-4, halving, max 15 halvings
  (``linesearch_backtrack``, lbfgs.c:444-475);
- minibatch mode (lbfgs.c:717-953): skip storing the (s,y) pair on the
  first iteration after a batch switch; trust-region regularization
  ``y += 1e-6 s`` when ||g|| > 1e-3; online gradient-variance step-size
  control ``alphabar = 10/(1 + sum|avg_sq| / ((niter-1)*||g||))``
  (lbfgs.c:796-824) with Welford-style running average across batches.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from sagecal_tpu.utils.precision import true_f32
from flax import struct

CLM_STOP_THRESH = 1e-9
CLM_EPSILON = 1e-12


@struct.dataclass
class LBFGSMemory:
    """Persistent LBFGS state (pytree version of ``persistent_data_t``)."""

    s: jax.Array  # (M, n) parameter differences
    y: jax.Array  # (M, n) gradient differences
    rho: jax.Array  # (M,) 1/(y.s)
    vacant: jax.Array  # int32 next slot to fill
    nfilled: jax.Array  # int32 number of valid pairs
    niter: jax.Array  # int32 global iteration count (across batches)
    running_avg: jax.Array  # (n,) online mean of batch gradients
    running_avg_sq: jax.Array  # (n,) online sum of squared deviations

    @staticmethod
    def init(n: int, M: int = 7, dtype=jnp.float32) -> "LBFGSMemory":
        return LBFGSMemory(
            s=jnp.zeros((M, n), dtype),
            y=jnp.zeros((M, n), dtype),
            rho=jnp.zeros((M,), dtype),
            vacant=jnp.zeros((), jnp.int32),
            nfilled=jnp.zeros((), jnp.int32),
            niter=jnp.zeros((), jnp.int32),
            running_avg=jnp.zeros((n,), dtype),
            running_avg_sq=jnp.zeros((n,), dtype),
        )

    def reset(self) -> "LBFGSMemory":
        """``lbfgs_persist_reset`` equivalent (Dirac.h:133-136)."""
        return LBFGSMemory.init(self.s.shape[1], self.s.shape[0], self.s.dtype)


def _two_loop_direction(g: jax.Array, mem: LBFGSMemory) -> jax.Array:
    """-H_k g via the two-loop recursion with masked circular slots."""
    M = mem.s.shape[0]
    k = jnp.arange(M)
    # slot index of the (k+1)-th most recent pair
    newest_first = jnp.mod(mem.vacant - 1 - k, M)
    valid = k < mem.nfilled  # (M,) newest-first validity
    s = mem.s[newest_first]  # (M, n) newest first
    y = mem.y[newest_first]
    rho = mem.rho[newest_first]

    def loop1(carry, inp):
        q = carry
        s_i, y_i, rho_i, ok = inp
        alpha_i = jnp.where(ok, rho_i * jnp.dot(s_i, q), 0.0)
        q = q - alpha_i * y_i
        return q, alpha_i

    q, alphas = jax.lax.scan(loop1, g, (s, y, rho, valid))
    # initial Hessian scaling gamma = s.y / y.y of the newest pair
    y0 = y[0]
    s0 = s[0]
    yy = jnp.dot(y0, y0)
    gamma = jnp.where(
        (mem.nfilled > 0) & (yy > 0.0), jnp.dot(s0, y0) / jnp.maximum(yy, 1e-30), 1.0
    )
    r = gamma * q

    def loop2(carry, inp):
        r = carry
        s_i, y_i, rho_i, alpha_i, ok = inp
        beta = jnp.where(ok, rho_i * jnp.dot(y_i, r), 0.0)
        r = r + s_i * jnp.where(ok, alpha_i - beta, 0.0)
        return r, None

    # oldest -> newest: reverse the newest-first arrays
    r, _ = jax.lax.scan(
        loop2, r, (s[::-1], y[::-1], rho[::-1], alphas[::-1], valid[::-1])
    )
    return -r


ARMIJO_C = 1e-4  # sufficient-decrease constant (lbfgs.c:444-475)


def _armijo_bad(f_new, fold, alpha, product):
    """The (shared) sufficient-decrease rejection test.  ``product`` =
    ARMIJO_C * p.g, computed ONCE per iteration so the fused first-trial
    accept and the halving loop apply bit-identical arithmetic."""
    return jnp.isnan(f_new) | (f_new > fold + alpha * product)


def _armijo_rest(cost_fn, x, p, a0, fold, f_a0, product):
    """Armijo halving loop (lbfgs.c:444-475: at most 15 halvings) with
    the first trial's cost ``f_a0`` already in hand.  Returns
    ``(alpha, halvings)``."""

    def cond(st):
        ci, alpha, fnew = st
        return (ci < 15) & _armijo_bad(fnew, fold, alpha, product)

    def body(st):
        ci, alpha, _ = st
        alpha = alpha * 0.5
        return ci + 1, alpha, cost_fn(x + alpha * p)

    ci, alpha, _ = jax.lax.while_loop(cond, body, (0, a0, f_a0))
    return alpha, ci


class LBFGSResult(NamedTuple):
    p: jax.Array
    memory: LBFGSMemory
    cost: jax.Array
    gradnorm: jax.Array
    iterations: jax.Array


@true_f32
def lbfgs_fit(
    cost_fn: Callable,
    grad_fn: Optional[Callable],
    p0: jax.Array,
    itmax: int = 50,
    M: int = 7,
    memory: Optional[LBFGSMemory] = None,
    minibatch: bool = False,
    vg_fn: Optional[Callable] = None,
) -> LBFGSResult:
    """Generic LBFGS fit (``lbfgs_fit``, Dirac.h:175 / lbfgs.c:479,717).

    ``minibatch=True`` reproduces ``lbfgs_fit_minibatch``: pass the
    ``memory`` returned from the previous batch's call; curvature pairs,
    iteration counts, and gradient-variance statistics persist.  With
    ``minibatch=False`` and no memory this is the full-batch fit (fresh
    memory, alphabar=1).

    ``vg_fn(p) -> (cost, grad)`` overrides the default fused
    value-and-grad.  Callers whose gradient CANNOT be obtained by
    differentiating ``cost_fn`` must pass it: under ``shard_map`` a
    ``psum``'d cost transposes to a device-local cotangent, so
    ``value_and_grad(cost_fn)`` yields each device only its shard's
    gradient — the correct global gradient is
    ``psum(value_and_grad(local_cost)(p))`` (solvers/sharded.py).
    """
    n = p0.shape[0]
    # fused value+gradient: the reverse pass shares its forward with the
    # cost, so (f, g) together cost ~one gradient — carrying f through
    # the loop then saves the cost_fn(x) re-evaluation Armijo would
    # otherwise make every iteration (one full pass over the coherency
    # stack on the calibration cost)
    if vg_fn is None:
        if grad_fn is None:
            vg_fn = jax.value_and_grad(cost_fn)
        else:
            def vg_fn(x):
                return cost_fn(x), grad_fn(x)
    fresh = memory is None
    if fresh:
        memory = LBFGSMemory.init(n, M, p0.dtype)

    f0, g0 = vg_fn(p0)
    gradnrm0 = jnp.linalg.norm(g0)

    # minibatch batch-switch bookkeeping (lbfgs.c:794-826): runs once per
    # call, before the iteration loop, iff a previous batch ran.
    if minibatch:
        batch_changed = memory.niter > 0
        niter1 = memory.niter + 1

        def upd(mem):
            g_min_rold = g0 - mem.running_avg
            ravg = mem.running_avg + g_min_rold / niter1.astype(p0.dtype)
            g_min_rnew = g0 - ravg
            ravg_sq = mem.running_avg_sq + g_min_rold * g_min_rnew
            return mem.replace(running_avg=ravg, running_avg_sq=ravg_sq)

        memory = jax.tree_util.tree_map(
            lambda a, b: jnp.where(batch_changed, a, b), upd(memory), memory
        )
        alphabar = jnp.where(
            batch_changed,
            10.0
            / (
                1.0
                + jnp.sum(jnp.abs(memory.running_avg_sq))
                / (jnp.maximum(memory.niter, 1).astype(p0.dtype) * jnp.maximum(gradnrm0, 1e-30))
            ),
            1.0,
        )
    else:
        batch_changed = jnp.asarray(False)
        alphabar = jnp.asarray(1.0, p0.dtype)

    def cond(state):
        ck, x, f, g, gradnrm, mem, done = state
        return (ck < itmax) & (~done)

    def body(state):
        ck, x, f, g, gradnrm, mem, done = state
        pk = _two_loop_direction(g, mem)
        # Evaluate value_and_grad AT the first Armijo trial point: when
        # the full step passes the sufficient-decrease test (the common
        # case once the inverse-Hessian scale is warm), the iteration
        # costs ONE fused (f, g) pass — ~2 cost-equivalents — instead
        # of trial + separate value_and_grad (~3).  The accepted step
        # matches the plain backtracking search in every case (shared
        # _armijo_bad predicate, same product); only the evaluation
        # count changes.  On reject, fall back to the cost-only
        # halving loop and take (f, g) at the accepted alpha.
        a0 = jnp.asarray(alphabar, x.dtype)
        x_t = x + a0 * pk
        f_t, g_t = vg_fn(x_t)
        product = ARMIJO_C * jnp.dot(pk, g)
        first_ok = ~_armijo_bad(f_t, f, a0, product)

        # the cond also returns the iteration's cost evaluations: no
        # caller reads them, but they keep the lowered program (and so
        # its compile-cache key) the one every earlier release built
        def accept_first(_):
            return a0, f_t, g_t, jnp.ones((), x.dtype)

        def backtrack(_):
            alpha, halvings = _armijo_rest(cost_fn, x, pk, a0, f, f_t, product)
            fb, gb = vg_fn(x + alpha * pk)
            # first trial + each halving + the fused re-eval at alpha
            return alpha, fb, gb, 2.0 + halvings.astype(x.dtype)

        alphak, f1, g1, _ = jax.lax.cond(first_ok, accept_first,
                                         backtrack, None)
        step_ok = jnp.isfinite(alphak) & (jnp.abs(alphak) >= CLM_EPSILON)
        x1 = x + alphak * pk
        gradnrm1 = jnp.linalg.norm(g1)
        grad_ok = jnp.isfinite(gradnrm1) & (gradnrm1 > CLM_STOP_THRESH)

        # store the curvature pair unless this is the first iteration of a
        # changed batch (lbfgs.c:849-880)
        store = step_ok & ~(batch_changed & (ck == 0))
        sk = x1 - x
        yk = g1 - g
        yk = yk + jnp.where(gradnrm1 > 1e-3, 1e-6, 0.0) * sk  # lbfgs.c:871-874
        # Positive-curvature guard (f32 robustness): near a converged
        # point y.s can underflow to 0 (or go negative on a noisy
        # Armijo step); storing rho = 1/(y.s) = inf then poisons every
        # later two-loop direction with inf*0 = NaN.  Require
        # y.s > eps*|y||s| (relative, scale-free) before storing, with
        # eps the machine epsilon OF THE RUNNING DTYPE — so f64 runs
        # keep reference-equivalent behavior (lbfgs.c stores every
        # pair; f64 eps only rejects pairs that are non-positive to
        # machine precision) while f32 stays protected.
        ys = jnp.dot(yk, sk)
        curv_eps = jnp.finfo(yk.dtype).eps
        curv_ok = ys > curv_eps * jnp.linalg.norm(yk) * jnp.linalg.norm(sk)
        store = store & curv_ok  # NaN/inf ys already fail curv_ok
        rho_k = jnp.where(curv_ok, 1.0 / jnp.maximum(ys, 1e-38), 0.0)
        slot = mem.vacant

        def do_store(mem):
            return mem.replace(
                s=mem.s.at[slot].set(sk),
                y=mem.y.at[slot].set(yk),
                rho=mem.rho.at[slot].set(rho_k),
                vacant=jnp.mod(slot + 1, mem.s.shape[0]),
                nfilled=jnp.minimum(mem.nfilled + 1, mem.s.shape[0]),
            )

        mem1 = jax.tree_util.tree_map(
            lambda a, b: jnp.where(store, a, b), do_store(mem), mem
        )
        # niter counts every iteration across batches (lbfgs.c:793)
        mem1 = mem1.replace(niter=mem.niter + 1)
        # only advance when the step was usable
        x_next = jnp.where(step_ok, x1, x)
        f_next = jnp.where(step_ok, f1, f)
        g_next = jnp.where(step_ok, g1, g)
        gradnrm_next = jnp.where(step_ok, gradnrm1, gradnrm)
        done_next = (~step_ok) | (~grad_ok)
        return (ck + 1, x_next, f_next, g_next, gradnrm_next, mem1,
                done_next)

    from sagecal_tpu.utils.platform import match_vma

    start_done = ~(jnp.isfinite(gradnrm0) & (gradnrm0 > CLM_STOP_THRESH))
    ck, x, f, g, gradnrm, mem, _ = jax.lax.while_loop(
        cond, body,
        match_vma((jnp.asarray(0), p0, f0, g0, gradnrm0, memory,
                   start_done), p0),
    )
    return LBFGSResult(p=x, memory=mem, cost=f, gradnorm=gradnrm,
                       iterations=ck)


def _bdot(a, b):
    """Per-lane dot: (B, n) x (B, n) -> (B,)."""
    return jnp.einsum("bn,bn->b", a, b)


def _bnorm(a):
    return jnp.sqrt(_bdot(a, a))


def _bexpand(mask, leaf):
    """(B,) predicate broadcast against a (B, ...) carry leaf."""
    return mask.reshape((mask.shape[0],) + (1,) * (leaf.ndim - 1))


def batched_memory(B: int, n: int, M: int = 7,
                   dtype=jnp.float32) -> LBFGSMemory:
    """Fresh :class:`LBFGSMemory` with every leaf carrying a leading
    batch axis ``B`` — the per-lane curvature store of
    :func:`lbfgs_fit_batched`."""
    one = LBFGSMemory.init(n, M, dtype)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (B,) + x.shape), one)


def _two_loop_direction_batched(g: jax.Array, mem: LBFGSMemory) -> jax.Array:
    """Per-lane -H_k g: the two-loop recursion of
    :func:`_two_loop_direction` with a leading batch axis on g (B, n)
    and on every memory leaf.  Per-lane circular indexing is a
    take_along_axis gather; the scan runs over the M slot axis with all
    lanes in lock-step (exactly what vmap of the solo recursion
    builds)."""
    Bsz, Mslots, _ = mem.s.shape
    k = jnp.arange(Mslots)
    newest_first = jnp.mod(mem.vacant[:, None] - 1 - k[None, :], Mslots)
    valid = k[None, :] < mem.nfilled[:, None]  # (B, M) newest-first
    s = jnp.take_along_axis(mem.s, newest_first[:, :, None], axis=1)
    y = jnp.take_along_axis(mem.y, newest_first[:, :, None], axis=1)
    rho = jnp.take_along_axis(mem.rho, newest_first, axis=1)

    def loop1(q, inp):
        s_i, y_i, rho_i, ok = inp  # (B, n), (B, n), (B,), (B,)
        alpha_i = jnp.where(ok, rho_i * _bdot(s_i, q), 0.0)
        return q - alpha_i[:, None] * y_i, alpha_i

    q, alphas = jax.lax.scan(
        loop1, g, (s.swapaxes(0, 1), y.swapaxes(0, 1), rho.T, valid.T))
    y0, s0 = y[:, 0], s[:, 0]
    yy = _bdot(y0, y0)
    gamma = jnp.where(
        (mem.nfilled > 0) & (yy > 0.0),
        _bdot(s0, y0) / jnp.maximum(yy, 1e-30), 1.0)
    r = gamma[:, None] * q

    def loop2(r, inp):
        s_i, y_i, rho_i, alpha_i, ok = inp
        beta = jnp.where(ok, rho_i * _bdot(y_i, r), 0.0)
        return r + s_i * jnp.where(ok, alpha_i - beta, 0.0)[:, None], None

    r, _ = jax.lax.scan(
        loop2, r,
        (s[:, ::-1].swapaxes(0, 1), y[:, ::-1].swapaxes(0, 1),
         rho[:, ::-1].T, alphas[::-1], valid[:, ::-1].T))
    return -r


def _armijo_rest_batched(cost_fn, x, p, a0, fold, f_a0, product, live):
    """Per-lane Armijo halving (vmap semantics of :func:`_armijo_rest`):
    each lane halves while ITS OWN test fails, frozen once it passes;
    the loop runs until no live lane is still failing.  ``live`` masks
    out lanes that already accepted the first trial (or finished the
    outer loop) so a pathological frozen lane cannot spin the batch."""

    def bad(ci, alpha, fnew):
        return live & (ci < 15) & _armijo_bad(fnew, fold, alpha, product)

    def cond(st):
        ci, alpha, fnew = st
        return jnp.any(bad(ci, alpha, fnew))

    def body(st):
        ci, alpha, fnew = st
        b = bad(ci, alpha, fnew)
        alpha1 = jnp.where(b, alpha * 0.5, alpha)
        f1 = cost_fn(x + alpha1[:, None] * p)
        return (jnp.where(b, ci + 1, ci), alpha1,
                jnp.where(b, f1, fnew))

    _, alpha, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros(a0.shape, jnp.int32), a0, f_a0))
    return alpha


@true_f32
def lbfgs_fit_batched(
    cost_fn: Callable,
    p0: jax.Array,
    itmax: int = 50,
    M: int = 7,
    memory: Optional[LBFGSMemory] = None,
    minibatch: bool = False,
    vg_fn: Optional[Callable] = None,
) -> LBFGSResult:
    """``B`` independent LBFGS fits advancing in lock-step so EVERY cost
    and gradient evaluation is ONE batched call — the driver for the
    batched fused objective kernel (``ops.rime_kernel.
    fused_cost_packed_batch``), where a vmap of :func:`lbfgs_fit` would
    fall back to B solo kernel dispatches.

    ``cost_fn``: (B, n) -> (B,) per-lane costs; lanes MUST be
    independent (lane b's cost depends only on row b — that is what
    makes the default pullback-of-ones gradient per-lane exact).
    ``p0``: (B, n).  ``memory``: per-lane :class:`LBFGSMemory`
    (leading B on every leaf, see :func:`batched_memory`).

    Per-lane semantics match ``jax.vmap(lbfgs_fit)`` (same predicates,
    same masked-carry advancement — a lane whose own termination fires
    freezes while the others run), but not bit-identically: batched
    reductions re-associate, like the rest of the serve batch path."""
    B, n = p0.shape
    if vg_fn is None:
        def vg_fn(x):
            costs, pull = jax.vjp(cost_fn, x)
            (g,) = pull(jnp.ones_like(costs))
            return costs, g
    if memory is None:
        memory = batched_memory(B, n, M, p0.dtype)

    f0, g0 = vg_fn(p0)
    gradnrm0 = _bnorm(g0)

    if minibatch:
        batch_changed = memory.niter > 0  # (B,)
        niter1 = memory.niter + 1

        def upd(mem):
            g_min_rold = g0 - mem.running_avg
            ravg = (mem.running_avg
                    + g_min_rold / niter1.astype(p0.dtype)[:, None])
            g_min_rnew = g0 - ravg
            ravg_sq = mem.running_avg_sq + g_min_rold * g_min_rnew
            return mem.replace(running_avg=ravg, running_avg_sq=ravg_sq)

        memory = jax.tree_util.tree_map(
            lambda a, b: jnp.where(_bexpand(batch_changed, a), a, b),
            upd(memory), memory)
        alphabar = jnp.where(
            batch_changed,
            10.0 / (
                1.0
                + jnp.sum(jnp.abs(memory.running_avg_sq), axis=-1)
                / (jnp.maximum(memory.niter, 1).astype(p0.dtype)
                   * jnp.maximum(gradnrm0, 1e-30))
            ),
            1.0,
        )
    else:
        batch_changed = jnp.zeros((B,), bool)
        alphabar = jnp.ones((B,), p0.dtype)

    def cond(state):
        ck, x, f, g, gradnrm, mem, done = state
        return jnp.any((ck < itmax) & (~done))

    def body(state):
        ck, x, f, g, gradnrm, mem, done = state
        active = (ck < itmax) & (~done)
        pk = _two_loop_direction_batched(g, mem)
        a0 = jnp.asarray(alphabar, x.dtype)
        x_t = x + a0[:, None] * pk
        f_t, g_t = vg_fn(x_t)
        product = ARMIJO_C * _bdot(pk, g)
        first_ok = ~_armijo_bad(f_t, f, a0, product)
        need_bt = active & ~first_ok

        def accept_all(_):
            return a0, f_t, g_t

        def backtrack_some(_):
            alpha = _armijo_rest_batched(
                cost_fn, x, pk, a0, f, f_t, product, need_bt)
            fb, gb = vg_fn(x + alpha[:, None] * pk)
            f1 = jnp.where(need_bt, fb, f_t)
            g1 = jnp.where(need_bt[:, None], gb, g_t)
            return alpha, f1, g1

        # one REAL branch (traced-scalar cond): the all-accept common
        # case costs exactly one fused (f, g) pass, like the solo path
        alphak, f1, g1 = jax.lax.cond(
            jnp.any(need_bt), backtrack_some, accept_all, None)
        step_ok = jnp.isfinite(alphak) & (jnp.abs(alphak) >= CLM_EPSILON)
        x1 = x + alphak[:, None] * pk
        gradnrm1 = _bnorm(g1)
        grad_ok = jnp.isfinite(gradnrm1) & (gradnrm1 > CLM_STOP_THRESH)

        store = step_ok & ~(batch_changed & (ck == 0))
        sk = x1 - x
        yk = g1 - g
        yk = yk + jnp.where(gradnrm1 > 1e-3, 1e-6, 0.0)[:, None] * sk
        ys = _bdot(yk, sk)
        curv_eps = jnp.finfo(yk.dtype).eps
        curv_ok = ys > curv_eps * _bnorm(yk) * _bnorm(sk)
        store = store & curv_ok
        rho_k = jnp.where(curv_ok, 1.0 / jnp.maximum(ys, 1e-38), 0.0)
        slot = mem.vacant  # (B,)
        bidx = jnp.arange(B)

        def do_store(mem):
            return mem.replace(
                s=mem.s.at[bidx, slot].set(sk),
                y=mem.y.at[bidx, slot].set(yk),
                rho=mem.rho.at[bidx, slot].set(rho_k),
                vacant=jnp.mod(slot + 1, mem.s.shape[1]),
                nfilled=jnp.minimum(mem.nfilled + 1, mem.s.shape[1]),
            )

        mem1 = jax.tree_util.tree_map(
            lambda a, b: jnp.where(_bexpand(store, a), a, b),
            do_store(mem), mem)
        mem1 = mem1.replace(niter=mem.niter + 1)
        # frozen lanes keep their whole carry (the vmap-of-while mask)
        mem_next = jax.tree_util.tree_map(
            lambda a, b: jnp.where(_bexpand(active, a), a, b), mem1, mem)
        adv = active & step_ok
        x_next = jnp.where(adv[:, None], x1, x)
        f_next = jnp.where(adv, f1, f)
        g_next = jnp.where(adv[:, None], g1, g)
        gradnrm_next = jnp.where(adv, gradnrm1, gradnrm)
        done_next = jnp.where(active, (~step_ok) | (~grad_ok), done)
        return (jnp.where(active, ck + 1, ck), x_next, f_next, g_next,
                gradnrm_next, mem_next, done_next)

    from sagecal_tpu.utils.platform import match_vma

    start_done = ~(jnp.isfinite(gradnrm0) & (gradnrm0 > CLM_STOP_THRESH))
    ck, x, f, g, gradnrm, mem, _ = jax.lax.while_loop(
        cond, body,
        match_vma((jnp.zeros((B,), jnp.int32), p0, f0, g0, gradnrm0,
                   memory, start_done), p0),
    )
    return LBFGSResult(p=x, memory=mem, cost=f, gradnorm=gradnrm,
                       iterations=ck)


# jitted module entry with compile/recompile telemetry (obs/perf.py):
# cost_fn/grad_fn are static (hashed by identity — a new closure is a
# new signature), as are the compile-time loop bounds
from sagecal_tpu.obs.perf import instrumented_jit  # noqa: E402

# The iteration carry — start params and (for minibatch resumes) the
# LBFGS memory — is DONATED: both are consumed, never reused by any
# caller, and at production size p0 alone is ~M*8N floats per tile, so
# the donation saves one carry-size HBM copy per dispatch (jaxlint
# JL007 pins this convention).  Callers must not touch the donated
# buffers after the call; pass a fresh/host array per solve.
lbfgs_fit_jit = instrumented_jit(
    lbfgs_fit, name="lbfgs_fit",
    donate_argnames=("p0", "memory"),
    static_argnames=("cost_fn", "grad_fn", "itmax", "M", "minibatch",
                     "vg_fn"))
