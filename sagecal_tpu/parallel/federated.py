"""Federated-averaging distributed mode: local consensus with a global
quotient-manifold average.

Redesign of the stochastic MPI pair
(``/root/reference/src/MPI/sagecal_stochastic_master.cpp`` /
``sagecal_stochastic_slave.cpp``): unlike the standard consensus mode,
the master never solves for Z — each worker keeps a LOCAL Z_f, and per
round the master only (1) averages the workers' Z on the unitary
quotient manifold and projects the mean back into each worker's frame
(``calculate_manifold_average_projectback``, stochastic_master.cpp:347),
and (2) workers tie their local Z to that average with an alpha-weighted
constraint and Lagrange multiplier X (federated pseudo-inverse with
+alpha*I, ``find_prod_inverse_full_fed``, consensus_poly.c:547;
allocations stochastic_slave.cpp:455-470).

On the mesh, the average is an ``all_gather`` of the (M, Npoly, K)
locals + replicated manifold math, and everything else stays local to
the ``freq`` shard.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sagecal_tpu.core.types import jones_to_params, params_to_jones
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.admm import admm_sagefit
from sagecal_tpu.parallel.manifold import manifold_average_projectback
from sagecal_tpu.solvers.lm import LMConfig


class FederatedResult(NamedTuple):
    p: jax.Array  # (Nf, M, nchunk_max, 8N)
    Z: jax.Array  # (Nf, M, Npoly, K) per-worker local consensus
    dual_res: jax.Array  # (nadmm,)


def _flat(x):
    return x.reshape(x.shape[:-2] + (-1,))


def _unflat(x, nchunk, n8):
    return x.reshape(x.shape[:-1] + (nchunk, n8))


def _fed_zavg(Z_local, axis_name, niter=10):
    """all_gather local Z's and replace each with the quotient-manifold
    mean projected into its own frame.  Z_local: (M, Npoly, K).

    CRITICAL detail from the reference: the master passes N*Npoly as the
    station count (stochastic_master.cpp:347), i.e. each cluster's FULL
    (2*N*Npoly x 2) coefficient stack is aligned by ONE unitary per
    (cluster, worker) — per-coefficient alignment would polar-factor the
    near-singular high-order blocks and inject junk rotations."""
    gath = jax.lax.all_gather(Z_local, axis_name)  # (Nf, M, Npoly, K)
    Nf, M, Npoly, K = gath.shape
    jones = params_to_jones(gath.reshape(Nf, M, Npoly * K))  # (Nf, M, Npoly*K/8, 2, 2)
    avg = manifold_average_projectback(jones, niter=niter)
    out = jones_to_params(avg)
    idx = jax.lax.axis_index(axis_name)
    return out.reshape(Nf, M, Npoly, K)[idx].astype(Z_local.dtype)


def make_federated_mesh_fn(
    mesh: Mesh,
    nadmm: int,
    axis_name: str = "freq",
    max_emiter: int = 1,
    plain_emiter: int = 2,
    lm_config: LMConfig = LMConfig(),
    alpha: float = 1.0,
    avg_cadence: int = 1,
):
    """Build the jitted federated calibration function.

    fn(data_stack, cdata_stack, p0 (Nf,M,nchunk,8N), rho (Nf,M),
       B (Nf, Npoly)) -> FederatedResult.  The local iteration mirrors
    the stochastic slave: x-step with (Y, B_f Z_f), local z-step
    z_f = pinv(rho_f B_f B_f^T + alpha I)(B_f (x) (Y + rho J) + alpha
    Zbar - X), dual updates for both Y (consensus) and X (federation).
    """

    def local_loop(data, cdata, p0, rho, B_f):
        M, nchunk_max, n8 = p0.shape
        K = nchunk_max * n8
        Npoly = B_f.shape[0]
        dtype = p0.dtype
        alpha_v = jnp.full((M,), alpha, dtype)

        # local federated pseudo-inverse: rho_f B_f B_f^T + alpha I
        P_loc = jnp.einsum("m,p,q->mpq", rho, B_f, B_f)
        P_loc = P_loc + alpha_v[:, None, None] * jnp.eye(Npoly, dtype=dtype)[None]
        Bii = jnp.linalg.pinv(P_loc)

        def zstep_local(Yhat_flat, Zbar, X):
            z = consensus.accumulate_z_term(B_f, Yhat_flat)  # (M, Npoly, K)
            z = z + alpha_v[:, None, None] * Zbar - X
            return consensus.update_global_z(z, Bii)

        # round 0: plain solve, init local Z
        zeros = jnp.zeros_like(p0)
        r0 = admm_sagefit(
            data, cdata, p0, zeros, zeros, jnp.zeros_like(rho),
            max_emiter=plain_emiter, lm_config=lm_config,
        )
        p = r0.p
        Yhat = rho[:, None, None] * p
        Zbar0 = jnp.zeros((M, Npoly, K), dtype)
        X = jnp.zeros((M, Npoly, K), dtype)
        Z = zstep_local(_flat(Yhat), Zbar0, X)
        Zbar = _fed_zavg(Z, axis_name)
        X = X + alpha_v[:, None, None] * (Z - Zbar)
        BZ = _unflat(consensus.bz_for_freq(Z, B_f), nchunk_max, n8)
        Y = Yhat - rho[:, None, None] * BZ

        def one_iter(carry, it):
            p, Y, Z, Zbar, X = carry
            BZ = _unflat(consensus.bz_for_freq(Z, B_f), nchunk_max, n8)
            loc = admm_sagefit(
                data, cdata, p, Y, BZ, rho,
                max_emiter=max_emiter, lm_config=lm_config,
            )
            p1 = loc.p
            Yhat = Y + rho[:, None, None] * p1
            Z1 = zstep_local(_flat(Yhat), Zbar, X)
            # federated averaging every avg_cadence rounds
            do_avg = (it % avg_cadence) == 0
            Zavg = _fed_zavg(Z1, axis_name)
            Zbar1 = jnp.where(do_avg, Zavg, Zbar)
            X1 = jnp.where(
                do_avg, X + alpha_v[:, None, None] * (Z1 - Zbar1), X
            )
            BZ1 = _unflat(consensus.bz_for_freq(Z1, B_f), nchunk_max, n8)
            Y1 = Yhat - rho[:, None, None] * BZ1
            # mean local-Z change across workers (replicated output)
            dres = jax.lax.pmean(
                consensus.admm_dual_residual(Z1, Z), axis_name
            )
            return (p1, Y1, Z1, Zbar1, X1), dres

        (p, Y, Z, Zbar, X), dres = jax.lax.scan(
            one_iter, (p, Y, Z, Zbar, X), jnp.arange(1, nadmm)
        )
        dres = jnp.concatenate([jnp.zeros((1,), dres.dtype), dres])
        return p[None], Z[None], dres

    fspec = P(axis_name)
    rspec = P()
    ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

    @jax.jit
    def fn(data_stack, cdata_stack, p0, rho, B):
        if p0.shape[0] != ndev:
            raise ValueError(
                f"sub-band axis {p0.shape[0]} != mesh size {ndev}"
            )
        sm = jax.shard_map(
            lambda d, c, p, r, b: local_loop(
                jax.tree_util.tree_map(lambda x: x[0], d),
                jax.tree_util.tree_map(lambda x: x[0], c),
                p[0], r[0], b[0],
            ),
            mesh=mesh,
            in_specs=(fspec, fspec, fspec, fspec, fspec),
            out_specs=(fspec, fspec, rspec),
            check_vma=True,
        )
        p, Z, dres = sm(data_stack, cdata_stack, p0, rho, B)
        return FederatedResult(p=p, Z=Z, dual_res=dres)

    return fn


class FederatedState(NamedTuple):
    """Carried state of the stochastic federated mode — every leaf has a
    leading band axis (Nf,) sharded over the mesh.  The pytree analog of
    the stochastic slave's Z/Zavg/X/Y/pfreq/persistent-LBFGS allocations
    (sagecal_stochastic_slave.cpp:441-470, 637-638)."""

    p: jax.Array       # (Nf, M, nchunk_max, 8N) per-band solutions
    Y: jax.Array       # (Nf, M, nchunk_max, 8N) consensus duals
    Z: jax.Array       # (Nf, M, Npoly, K) per-band local consensus
    Zbar: jax.Array    # (Nf, M, Npoly, K) federated average (per frame)
    X: jax.Array       # (Nf, M, Npoly, K) federation duals
    mem: object        # LBFGSMemory with (Nf,)-leading leaves


def init_federated_state(Nf, M, nchunk_max, n8, npoly, lbfgs_m, dtype):
    from sagecal_tpu.solvers.lbfgs import LBFGSMemory

    K = nchunk_max * n8
    zeros_p = jnp.zeros((Nf, M, nchunk_max, n8), dtype)
    zeros_z = jnp.zeros((Nf, M, npoly, K), dtype)
    mem1 = LBFGSMemory.init(M * K, lbfgs_m, dtype)
    mem = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (Nf,) + x.shape).copy(), mem1
    )
    from sagecal_tpu.core.types import identity_jones, jones_to_params

    N = n8 // 8
    eye = jones_to_params(identity_jones(
        N, jnp.complex64 if dtype == jnp.float32 else jnp.complex128))
    p0 = jnp.broadcast_to(eye, (Nf, M, nchunk_max, n8)).astype(dtype)
    return FederatedState(p=p0, Y=zeros_p, Z=zeros_z, Zbar=zeros_z,
                          X=zeros_z, mem=mem)


def make_federated_minibatch_fn(
    mesh: Mesh,
    axis_name: str = "freq",
    itmax: int = 10,
    lbfgs_m: int = 7,
    alpha: float = 1.0,
    robust_nu=None,
):
    """One federated-stochastic minibatch round as a jitted mesh
    program: per band, the consensus minibatch LBFGS x-step with
    PERSISTENT memory (bfgsfit_minibatch_consensus,
    robust_batchmode_lbfgs.c:1504), Y ascent, and the local federated
    z-step z = pinv(rho B B^T + alpha I)(B(Y + rho J) + alpha Zbar - X)
    (stochastic_slave.cpp:756-850).  The federated average itself is
    :func:`make_fed_avg_fn` — called at the reference's cadence (after
    each epoch block, :856-860), not per minibatch.

    fn(data_stack, cdata_stack, state, rho (Nf, M), B (Nf, Npoly))
      -> (state, dual_res (replicated), data_cost (Nf,))
    """
    from sagecal_tpu.solvers.batchmode import bfgsfit_minibatch_consensus

    def local_step(data, cdata, st, rho, B_f):
        M, nchunk_max, n8 = st.p.shape
        K = nchunk_max * n8
        Npoly = B_f.shape[0]
        dtype = st.p.dtype
        alpha_v = jnp.full((M,), alpha, dtype)

        BZ = _unflat(consensus.bz_for_freq(st.Z, B_f), nchunk_max, n8)
        p1, mem1 = bfgsfit_minibatch_consensus(
            data, cdata, st.p, st.Y, BZ, rho, memory=st.mem,
            itmax=itmax, lbfgs_m=lbfgs_m, robust_nu=robust_nu,
        )
        Yhat = st.Y + rho[:, None, None] * p1

        P_loc = jnp.einsum("m,p,q->mpq", rho, B_f, B_f)
        P_loc = P_loc + alpha_v[:, None, None] * jnp.eye(
            Npoly, dtype=dtype)[None]
        Bii = jnp.linalg.pinv(P_loc)
        z = consensus.accumulate_z_term(B_f, _flat(Yhat))
        z = z + alpha_v[:, None, None] * st.Zbar - st.X
        Z1 = consensus.update_global_z(z, Bii)

        BZ1 = _unflat(consensus.bz_for_freq(Z1, B_f), nchunk_max, n8)
        Y1 = Yhat - rho[:, None, None] * BZ1
        dres = jax.lax.pmean(
            consensus.admm_dual_residual(Z1, st.Z), axis_name
        )
        from sagecal_tpu.solvers.batchmode import _data_cost

        cost = _data_cost(p1.reshape(-1), data, cdata,
                          (M, nchunk_max, n8), robust_nu)
        st1 = st._replace(p=p1, Y=Y1, Z=Z1, mem=mem1)
        # re-add the local (length-1) band axis for the fspec outputs
        st1 = jax.tree_util.tree_map(lambda x: x[None], st1)
        return st1, dres, cost[None]

    fspec = P(axis_name)
    rspec = P()

    @jax.jit
    def fn(data_stack, cdata_stack, state, rho, B):
        sm = jax.shard_map(
            lambda d, c, s, r, b: local_step(
                jax.tree_util.tree_map(lambda x: x[0], d),
                jax.tree_util.tree_map(lambda x: x[0], c),
                jax.tree_util.tree_map(lambda x: x[0], s),
                r[0], b[0],
            ),
            mesh=mesh,
            in_specs=(fspec, fspec, fspec, fspec, fspec),
            out_specs=(fspec, rspec, fspec),
            check_vma=True,
        )
        st_l, dres, cost = sm(data_stack, cdata_stack, state, rho, B)
        # shard_map strips/re-adds the band axis; state leaves keep (Nf,)
        return st_l, dres, cost

    return fn


def make_fed_avg_fn(mesh: Mesh, axis_name: str = "freq",
                    alpha: float = 1.0, niter: int = 10):
    """Federated averaging round: Zbar <- manifold average of all bands'
    Z projected back per frame; X <- X + alpha (Z - Zbar)
    (stochastic_master.cpp:347, slave:856-868)."""

    fspec = P(axis_name)

    def local(st):
        st0 = jax.tree_util.tree_map(lambda x: x[0], st)
        M = st0.Z.shape[0]
        alpha_v = jnp.asarray(alpha, st0.Z.dtype)
        Zbar = _fed_zavg(st0.Z, axis_name, niter=niter)
        X1 = st0.X + alpha_v * (st0.Z - Zbar)
        st1 = st0._replace(Zbar=Zbar, X=X1)
        return jax.tree_util.tree_map(lambda x: x[None], st1)

    @jax.jit
    def fn(state):
        return jax.shard_map(
            local, mesh=mesh, in_specs=(fspec,), out_specs=fspec,
            check_vma=True,
        )(state)

    return fn
