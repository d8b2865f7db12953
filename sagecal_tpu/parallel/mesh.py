"""Mesh-parallel consensus ADMM: frequencies on a device mesh axis.

This replaces the reference's MPI master/worker star
(``/root/reference/src/MPI/sagecal_master.cpp`` /
``sagecal_slave.cpp``, p2p tags ``proto.h:24-59``) with a single SPMD
program over a ``jax.sharding.Mesh``:

- each device along the ``freq`` axis owns one OR MORE sub-bands'
  visibility tiles — the reference's "one MPI worker per group of MS";
- the ADMM x-step (:func:`sagecal_tpu.parallel.admm.admm_sagefit`) runs
  independently per shard, dispatched on solver mode (LM / robust RTR /
  NSD with the ADMM-augmented cost) like ``admm_solve.c:221``;
- the master's Z-update ``z = sum_f B_f (x) (Y_f + rho_f J_f)`` is a
  ``lax.psum`` over the freq axis (sagecal_master.cpp:841-852 was a
  recv+accumulate loop), and ``Bii = pinv(sum_f rho_f B_f B_f^T)`` is a
  psum of small (Npoly, Npoly) terms followed by a replicated pinv;
- the manifold-averaging alignment at the first iteration becomes an
  ``all_gather`` of (M, N, 2, 2) Jones blocks (small) + replicated math.

Data multiplexing (more sub-bands than devices): with Nf = G * ndev the
leading sub-band axis shards into contiguous groups of G per device
(the reference assigns contiguous MS lists per worker,
sagecal_master.cpp:60-224).  ADMM iteration ``it`` solves local group
slot ``it % G`` — the ``Sbegin/Scurrent/Send`` rotation of
sagecal_master.cpp:157-206 / README.md:139-141 — while the z-step psums
the STORED ``Yhat = Y + rho J`` of every sub-band (stale for inactive
slots, exactly the reference's multiplexed semantics where only the
active MS's Y refreshes per iteration).

Iteration protocol (matches slave/master handshake order,
sagecal_slave.cpp:727-895):
  admm 0:  plain (unaugmented) solve of ALL local slots; align J across
           sub-bands on the quotient manifold; Yhat = rho*J; z-step;
           Y = Yhat - rho*BZ.
  admm>0:  augmented solve of the active slot with (Y, BZ);
           Yhat = Y + rho*J; z-step with the NEW J; dual update against
           the NEW consensus, Y = Yhat - rho*BZ_new; optional
           Barzilai-Borwein rho update every other iteration
           (consensus_poly.c:860-911, cadence at sagecal_slave.cpp:899).

Multi-host scaling: build the Mesh over ``jax.devices()`` spanning
hosts (``jax.distributed.initialize``); the same psum/all_gather ride
ICI inside a slice and DCN across — no code change, matching SURVEY.md
section 5's mapping.

The sub-band pipeline around the ADMM runs on the same mesh: the band
programs of :func:`make_band_prep_fn` (channel average, coherencies)
and :func:`make_band_residual_fn` (per-channel residuals) compute each
band on the device that holds it.  Every op of these programs is named
(``obs/scopes.py``): ``admm`` with ``plain`` (the admm-0 solves),
``xstep`` (the augmented solve of the active slot) and ``zstep`` (the Z
update and its collectives), and ``bands`` with ``coh`` and
``residual``.  All three trace under HIGHEST matmul precision
(``utils/precision.true_f32``): at the TPU's default single bf16 pass
the predicted coherencies and residuals lose three digits (resid_gap
1.2e-2 to 1.4e-2 on the v5e, against the plain reference).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sagecal_tpu.core.types import VisData, jones_to_params, params_to_jones
from sagecal_tpu.obs.perf import instrumented_jit
from sagecal_tpu.obs.scopes import scoped
from sagecal_tpu.parallel import consensus
from sagecal_tpu.parallel.admm import admm_sagefit, factor_schedule
from sagecal_tpu.parallel.manifold import manifold_average
from sagecal_tpu.solvers.lm import LMConfig
from sagecal_tpu.solvers.sage import (
    SM_LM_LBFGS,
    ClusterData,
    predict_full_model,
)
from sagecal_tpu.utils.precision import true_f32


class AdmmResult(NamedTuple):
    p: jax.Array  # (Nf, M, nchunk_max, 8N) per-band solutions
    Y: jax.Array  # (Nf, M, nchunk_max, 8N) duals
    Z: jax.Array  # (M, Npoly, nchunk_max*8N) consensus variable
    rho: jax.Array  # (Nf, M) final penalties
    dual_res: jax.Array  # (nadmm,) dual residual trace
    primal_res: jax.Array  # (nadmm,) mean primal residual ||J - BZ||
    Zspat: Optional[jax.Array] = None  # (2*Npoly*N*nchunk?, 2G) spatial model
    spat_res: Optional[jax.Array] = None  # (nadmm,) ||Z - Zbar|| trace
    Zspat_diff: Optional[jax.Array] = None  # (D, 2G) diffuse-constraint model
    # telemetry (collect_trace=True only; see sagecal_tpu.obs):
    primal_res_band: Optional[jax.Array] = None  # (nadmm, Nf) per-band ||J-BZ||
    dual_res_band: Optional[jax.Array] = None  # (nadmm, Nf) rho||B dZ|| per band
    rho_trace: Optional[jax.Array] = None  # (nadmm, Nf, M) penalty trajectory
    # int32: band x-step solves run, summed over the mesh (the admm-0
    # solve of every local slot plus one active slot per later round)
    xsteps: Optional[jax.Array] = None


class SpatialConfig(NamedTuple):
    """Spatial-regularization coupling for the mesh ADMM loop
    (the master's Zbar/Zspat/X machinery, sagecal_master.cpp:887-930).

    Phi: (Meff, 2G, 2) per-effective-cluster spatial basis blocks
      (:func:`sagecal_tpu.parallel.spatial.build_spatial_basis`);
    Phikk: (2G, 2G) = sum_k Phi_k Phi_k^H + lambda I;
    alpha: (M,) per-cluster spatial coupling strengths (the -G file's
      alpha column);
    mu: L1 strength; cadence: run the FISTA update every this many ADMM
    iterations (-O admm_cadence); fista_maxiter: inner FISTA steps.

    Diffuse-sky constraint (sagecal_master.cpp:908-926, fista.c:131):
    when ``Z_diff0`` is given (the ``find_initial_spatial`` model), the
    FISTA step carries the extra term Psi^H(Zs - Zdiff) +
    gamma/2 ||Zs - Zdiff||^2, and each cadence also updates
      Zdiff <- (Zdiff0 + 0.5 Psi + 0.5 gamma Zs) / (1 + 0.5 gamma + lam_diff)
      Psi   <- Psi + gamma (Zs - Zdiff)
    The resulting Zdiff (AdmmResult.Zspat_diff) is what the diffuse
    cluster's coherencies are re-predicted from (sagecal_slave.cpp:670,
    ops/diffuse.recalculate_diffuse_coherencies).
    """

    Phi: jax.Array
    Phikk: jax.Array
    alpha: jax.Array
    mu: float = 1e-3
    cadence: int = 2
    fista_maxiter: int = 30
    Z_diff0: Optional[jax.Array] = None
    gamma: float = 0.0
    lam_diff: float = 0.0


def _flat(x):
    return x.reshape(x.shape[:-2] + (-1,))


def _unflat(x, nchunk, n8):
    return x.reshape(x.shape[:-1] + (nchunk, n8))


def _zstep_grouped(Yhat_flat, rho, B_g, axis_name, federated_alpha=None,
                   z_extra=None, weights=None):
    """psum z accumulation + replicated Bii + Z update.

    Yhat_flat (G, M, K); rho (G, M); B_g (G, Npoly) — all local
    sub-bands contribute (vmapped accumulate, summed locally, then
    psum'd across the mesh).  ``z_extra``: optional replicated
    (M, Npoly, K) addition to the accumulated z (the spatial-reg
    ``alpha Zbar - X`` term, sagecal_master.cpp:855-872).
    ``weights``: optional per-local-slot (G,) staleness discounts
    applied to both the numerator terms and the rho denominator
    (consensus.staleness_weights) — identical on every device since the
    slot rotation is."""
    terms = jax.vmap(consensus.accumulate_z_term)(B_g, Yhat_flat)
    if weights is not None:
        terms = weights[:, None, None, None] * terms
    z_local = jnp.sum(terms, axis=0)
    z = jax.lax.psum(z_local, axis_name)
    if z_extra is not None:
        z = z + z_extra
    if weights is not None:
        P_term = jnp.einsum("g,gm,gp,gq->mpq", weights, rho, B_g, B_g)
    else:
        P_term = jnp.einsum("gm,gp,gq->mpq", rho, B_g, B_g)
    P_sum = jax.lax.psum(P_term, axis_name)
    if federated_alpha is not None:
        Np = B_g.shape[-1]
        P_sum = P_sum + federated_alpha[:, None, None] * jnp.eye(
            Np, dtype=P_sum.dtype
        )[None]
    Bii = jnp.linalg.pinv(P_sum)
    return consensus.update_global_z(z, Bii)


def _zbar_blocks_of_z(Z, M, Npoly, nchunk, n8):
    """Param-space Z (M, Npoly, nchunk*n8) -> complex spatial blocks
    (M*nchunk, 2*N*Npoly, 2) — the master's Z->Zbar reshaping
    (sagecal_master.cpp:889-906); hybrid chunks become separate
    effective clusters as in the reference."""
    N = n8 // 8
    J = params_to_jones(Z.reshape(M, Npoly, nchunk, n8))
    X = jnp.transpose(J, (0, 2, 1, 3, 4, 5))  # (M, nchunk, Npoly, N, 2, 2)
    return X.reshape(M * nchunk, Npoly * N * 2, 2)


def _z_of_zbar_blocks(Xb, M, Npoly, nchunk, n8):
    """Inverse of :func:`_zbar_blocks_of_z`."""
    N = n8 // 8
    J = Xb.reshape(M, nchunk, Npoly, N, 2, 2)
    J = jnp.transpose(J, (0, 2, 1, 3, 4, 5))  # (M, Npoly, nchunk, N, 2, 2)
    return jones_to_params(J).reshape(M, Npoly, nchunk * n8)


def make_admm_mesh_fn(
    mesh: Mesh,
    nadmm: int,
    axis_name: str = "freq",
    max_emiter: int = 1,
    plain_emiter: int = 2,
    lm_config: LMConfig = LMConfig(),
    use_manifold_align: bool = True,
    bb_rho: bool = False,
    rho_upper: float = 1e3,
    solver_mode: int = SM_LM_LBFGS,
    robust_nu: Optional[float] = None,
    spatial: Optional[SpatialConfig] = None,
    collect_trace: bool = False,
    consensus_cfg: Optional[consensus.ConsensusConfig] = None,
):
    """Build the jitted mesh-wide ADMM calibration function.

    The returned fn takes leading-axis-``Nf`` stacks (sharded over the
    ``freq`` mesh axis; Nf must be a multiple of the mesh size — pad
    with zero-weight bands otherwise):
      fn(data_stack: VisData pytree with (Nf, ...) leaves,
         cdata_stack: ClusterData pytree (Nf, ...),
         p0: (Nf, M, nchunk_max, 8N), rho: (Nf, M), B: (Nf, Npoly))
    and returns an :class:`AdmmResult`.  The whole Nadmm loop runs in one
    jit/shard_map program.

    ``solver_mode``/``robust_nu`` select the local x-step solver the way
    ``sagefit_visibilities_admm`` dispatches (see
    :func:`sagecal_tpu.parallel.admm.admm_sagefit`).

    ``spatial``: optional :class:`SpatialConfig` — couples the consensus
    Z to a smooth spatial model across directions, INSIDE the ADMM
    iteration at the reference's cadence (sagecal_master.cpp:855-930):
    the z-step gains ``+ alpha Zbar - X`` with a federated ``+alpha I``
    in the Bii inverse, and every ``cadence`` iterations the spatial
    model Zspat is re-fit by FISTA, Zbar <- Zspat Phi, and the Lagrange
    multiplier X steps by ``alpha (Z - Zbar)``.  All spatial state is
    replicated across the mesh (it is master-side math in the
    reference — tiny compared to the sharded x-steps).

    ``collect_trace``: statically enables ADMM telemetry — the result
    additionally carries per-band primal/dual residual norms and the
    full rho trajectory per iteration (``primal_res_band`` /
    ``dual_res_band`` (nadmm, Nf), ``rho_trace`` (nadmm, Nf, M)); the
    Barzilai-Borwein penalty adaptation is exactly what these exist to
    monitor.  Off (default) the jitted signature is unchanged.

    ``consensus_cfg``: optional :class:`sagecal_tpu.parallel.consensus.
    ConsensusConfig` selecting the consensus round structure — the
    transpose-reduced scattered z-step, fine-grained cluster factor
    groups, per-device slot schedules, and in-mesh bounded-staleness
    weighting.  ``None`` (default) keeps the classic grouped rounds and
    emits the exact original program.
    """

    ccfg = (consensus_cfg if consensus_cfg is not None
            else consensus.ConsensusConfig())
    if ccfg.zstep not in ("grouped", "reduced"):
        raise ValueError(f"unknown zstep {ccfg.zstep!r}")
    cg = max(int(ccfg.cluster_groups), 1)
    fine = cg > 1
    use_staleness = (
        ccfg.staleness is not None or ccfg.staleness_discount != 1.0
    )
    if use_staleness and (fine or ccfg.slot_schedule is not None
                          or ccfg.group_schedule is not None):
        raise ValueError(
            "in-mesh bounded staleness composes with the uniform "
            "whole-band rotation only; fine-grained / rebalanced "
            "staleness is the minibatch async-consensus path"
        )
    reduced = ccfg.zstep == "reduced"
    if reduced and ccfg.group_schedule is not None:
        gs = np.asarray(ccfg.group_schedule)
        if gs.ndim == 2 and not np.all(gs == gs[:, :1]):
            raise ValueError(
                "reduced z-step needs a device-uniform group schedule "
                "(the incremental Gram delta rows must align across "
                "the mesh)"
            )
    # full Z is needed replicated every round for the spatial coupling
    # and the per-band residual telemetry; there the reduced mode keeps
    # the scattered solve but all_gathers Z back per round (still far
    # below the grouped psum of the full numerator).
    zmode = "grouped" if not reduced else (
        "reduced_gather" if (spatial is not None or collect_trace)
        else "reduced_scatter"
    )
    # with fixed rho, no staleness discounts and no federated alpha the
    # Bii denominator never changes — hoist its psum out of the round
    # loop entirely (the grouped path psums it every round).
    den_static = (
        reduced and not bb_rho and not use_staleness and spatial is None
    )
    have_sched = (
        fine or ccfg.slot_schedule is not None
        or ccfg.group_schedule is not None
    )
    ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

    def _fit(data, cdata, p, Y, BZ, rho_m, emiter, cluster_slice=None):
        return admm_sagefit(
            data, cdata, p, Y, BZ, rho_m,
            max_emiter=emiter, lm_config=lm_config,
            solver_mode=solver_mode, robust_nu=robust_nu,
            cluster_slice=cluster_slice,
        )

    @scoped("admm")
    @true_f32
    def local_loop(data: VisData, cdata: ClusterData, p0, rho, B_g):
        # all array leaves carry the local sub-band group axis G
        G, M, nchunk_max, n8 = p0.shape
        K = nchunk_max * n8
        Npoly = B_g.shape[-1]
        zeros_g = jnp.zeros_like(p0[0])
        if M % cg != 0:
            raise ValueError(
                f"cluster_groups {cg} must divide the cluster count {M}"
            )
        Mg = M // cg
        if reduced:
            if K % ndev != 0:
                raise ValueError(
                    f"reduced z-step needs the solution size {K} "
                    f"divisible by the mesh size {ndev}; use "
                    "zstep='grouped'"
                )
            Ks = K // ndev
        if have_sched:
            # host-built static (slot, group) schedule, one column per
            # mesh device (shard_map-level rebalancing)
            slot_np, group_np = factor_schedule(
                nadmm, G, cluster_groups=cg, ndev=ndev
            )
            if ccfg.slot_schedule is not None:
                s = np.asarray(ccfg.slot_schedule, np.int32)
                slot_np = np.broadcast_to(
                    s[:, None] if s.ndim == 1 else s, (nadmm - 1, ndev)
                )
            if ccfg.group_schedule is not None:
                s = np.asarray(ccfg.group_schedule, np.int32)
                group_np = np.broadcast_to(
                    s[:, None] if s.ndim == 1 else s, (nadmm - 1, ndev)
                )
            slot_arr = jnp.asarray(slot_np, jnp.int32)
            group_arr = jnp.asarray(group_np, jnp.int32)

        # ---- admm 0: plain solve of every local slot -------------------
        def plain_one(n, inp):
            d_g, c_g, p_g, rho_g = inp
            r = _fit(d_g, c_g, p_g, zeros_g, zeros_g,
                     jnp.zeros_like(rho_g), plain_emiter)
            return n + 1, r.p

        with jax.named_scope("plain"):
            nx0, p = jax.lax.scan(plain_one, jnp.int32(0),
                                  (data, cdata, p0, rho))

        if use_manifold_align:
            # master-side unitary-ambiguity fix over ALL Nf sub-bands
            # (sagecal_master.cpp:826-838)
            jones = params_to_jones(p)  # (G, M, nchunk, N, 2, 2)
            gath = jax.lax.all_gather(jones, axis_name)  # (ndev, G, ...)
            nd_, G_, Mm = gath.shape[0], gath.shape[1], gath.shape[2]
            gflat = gath.reshape(nd_ * G_, Mm, -1, 2, 2)
            aligned = manifold_average(gflat, niter=20)
            idx = jax.lax.axis_index(axis_name)
            own = aligned.reshape((nd_, G_) + aligned.shape[1:])[idx]
            p = jones_to_params(own.reshape(jones.shape)).astype(p0.dtype)

        Yhat = rho[:, :, None, None] * p  # Y=0 so Yhat = rho*J

        use_spatial = spatial is not None
        if use_spatial:
            M_ = p0.shape[1]
            K = nchunk_max * n8
            Zbar_flat0 = jnp.zeros((M_, B_g.shape[-1], K), p0.dtype)
            Xsp0 = jnp.zeros_like(Zbar_flat0)
            D = 2 * (n8 // 8) * B_g.shape[-1]
            twoG = spatial.Phikk.shape[0]
            Zspat0 = jnp.zeros((D, twoG), jnp.complex64 if p0.dtype == jnp.float32
                               else jnp.complex128)
            alpha_sp = spatial.alpha.astype(p0.dtype)
            use_diff = spatial.Z_diff0 is not None
            if use_diff:
                Zdiff0_c = jnp.asarray(spatial.Z_diff0, Zspat0.dtype)

            def spatial_update(Z, Xsp, Zdiff, Psi):
                """FISTA re-fit + Zbar/X updates (cadenced), optionally
                with the diffuse constraint (master:908-926)."""
                from sagecal_tpu.parallel.spatial import (
                    spatial_model_apply, update_spatialreg_fista,
                )

                Zbar_c = _zbar_blocks_of_z(Z, M_, B_g.shape[-1], nchunk_max, n8)
                Zs = update_spatialreg_fista(
                    Zbar_c, spatial.Phikk.astype(Zspat0.dtype),
                    spatial.Phi.astype(Zspat0.dtype),
                    spatial.mu, maxiter=spatial.fista_maxiter,
                    Z_diff=Zdiff if use_diff else None,
                    Psi=Psi if use_diff else None,
                    gamma=spatial.gamma if use_diff else 0.0,
                )
                if use_diff:
                    # Zdiff prox + Psi ascent (master:919-926)
                    g = spatial.gamma
                    Zdiff = (Zdiff0_c + 0.5 * Psi + 0.5 * g * Zs) / (
                        1.0 + 0.5 * g + spatial.lam_diff
                    )
                    Psi = Psi + g * (Zs - Zdiff)
                Zbar_new_c = spatial_model_apply(Zs, spatial.Phi.astype(Zs.dtype))
                Zbar_new = _z_of_zbar_blocks(
                    Zbar_new_c, M_, B_g.shape[-1], nchunk_max, n8
                ).astype(p0.dtype)
                Zerr = Z - Zbar_new
                Xsp_new = Xsp + alpha_sp[:, None, None] * Zerr
                sres = jnp.linalg.norm(Zerr.ravel()) / Zerr.size
                return Zbar_new, Xsp_new, Zs, sres, Zdiff, Psi

        def bz_of(Z_, g):
            return _unflat(
                consensus.bz_for_freq(Z_, B_g[g]), nchunk_max, n8
            )

        # ---- round-0 consensus -----------------------------------------
        if zmode == "grouped":
            with jax.named_scope("zstep"):
                Z = _zstep_grouped(_flat(Yhat), rho, B_g, axis_name)
        else:
            # transpose reduction (arXiv:1504.02147): the basis-sized
            # Gram numerator lives psum_scatter'd over the solution
            # axis, so each device solves only its K/ndev shard of Z and
            # per-round collectives carry Gram deltas, never full
            # (M, Npoly, K) stacks.
            with jax.named_scope("zstep"):
                B_full = jax.lax.all_gather(B_g, axis_name, axis=0,
                                            tiled=True)

            def _num_scatter(Yhat_flat, weights=None):
                terms = jax.vmap(consensus.accumulate_z_term)(
                    B_g, Yhat_flat
                )
                if weights is not None:
                    terms = weights[:, None, None, None] * terms
                z_local = jnp.sum(terms, axis=0)
                return jax.lax.psum_scatter(
                    z_local, axis_name, scatter_dimension=2, tiled=True
                )

            def _den_inv(rho_cur, weights=None, federated_alpha=None):
                if weights is not None:
                    P_term = jnp.einsum(
                        "g,gm,gp,gq->mpq", weights, rho_cur, B_g, B_g
                    )
                else:
                    P_term = jnp.einsum(
                        "gm,gp,gq->mpq", rho_cur, B_g, B_g
                    )
                P_sum = jax.lax.psum(P_term, axis_name)
                if federated_alpha is not None:
                    P_sum = P_sum + federated_alpha[:, None, None] * \
                        jnp.eye(Npoly, dtype=P_sum.dtype)[None]
                return jnp.linalg.pinv(P_sum)

            def a2a_bz(Zsh_, slot_row, group_row, g):
                """Active consensus target B_f Z from the sharded Z:
                every device computes the partial on ITS K-shard for
                EVERY device's active (slot, group) factor, and one
                all_to_all hands each device its own band's rows back
                in shard order."""
                if slot_row is None:
                    band_ids = jnp.arange(ndev) * G + g
                else:
                    band_ids = jnp.arange(ndev) * G + slot_row
                rows = B_full[band_ids]  # (ndev, Npoly)
                if group_row is None:
                    starts = jnp.zeros((ndev,), jnp.int32)
                else:
                    starts = (group_row * Mg).astype(jnp.int32)

                def part(brow, st):
                    blk = jax.lax.dynamic_slice(
                        Zsh_, (st, jnp.int32(0), jnp.int32(0)),
                        (Mg, Npoly, Ks),
                    )
                    return jnp.einsum("p,mpk->mk", brow, blk)

                partials = jax.vmap(part)(rows, starts)  # (ndev,Mg,Ks)
                got = jax.lax.all_to_all(
                    partials, axis_name, split_axis=0, concat_axis=0,
                    tiled=True,
                )
                bz = jnp.moveaxis(got, 0, 1).reshape(Mg, K)
                return _unflat(bz, nchunk_max, n8)

            with jax.named_scope("zstep"):
                num_shard = _num_scatter(_flat(Yhat))
                Bii0 = _den_inv(rho)
                Zsh = consensus.update_global_z(num_shard, Bii0)
                Z = jax.lax.all_gather(Zsh, axis_name, axis=2, tiled=True)

        BZ_all = jax.vmap(lambda g: bz_of(Z, g))(jnp.arange(G))
        Y = Yhat - rho[:, :, None, None] * BZ_all

        def band_residuals(p_cur, Z_new, Z_old, rho_cur):
            """Per-local-band primal ||J - BZ|| and dual rho||B dZ||
            norms (both /sqrt(n), the scaling of the scalar pres)."""
            BZn = jax.vmap(lambda g: bz_of(Z_new, g))(jnp.arange(G))
            BZo = jax.vmap(lambda g: bz_of(Z_old, g))(jnp.arange(G))
            pr = _flat(p_cur - BZn)  # (G, M, K)
            rn = jnp.sqrt(jnp.asarray(pr[0].size, pr.dtype))
            prn = jnp.sqrt(jnp.sum(pr * pr, axis=(1, 2))) / rn
            dd = _flat(rho_cur[:, :, None, None] * (BZn - BZo))
            ddn = jnp.sqrt(jnp.sum(dd * dd, axis=(1, 2))) / rn
            return prn, ddn

        # ---- admm > 0: rotate over local slots -------------------------
        def one_iter(carry, it):
            p, Y, Zc, rho, Yhat_all, Yhat_prev, p_prev, spstate, nx = carry
            if have_sched:
                slot_row = jax.lax.dynamic_index_in_dim(
                    slot_arr, it - 1, keepdims=False
                )
                group_row = jax.lax.dynamic_index_in_dim(
                    group_arr, it - 1, keepdims=False
                )
                did = jax.lax.axis_index(axis_name)
                g = slot_row[did]
                c0 = group_row[did] * Mg
            else:
                slot_row = group_row = None
                g = (it - 1) % G  # active local slot (Scurrent rotation)
                c0 = 0
            csl = (c0, Mg) if fine else None
            i0 = jnp.int32(0)  # index dtype anchor for dynamic updates

            def sl(x):
                """Active cluster-factor rows (fine-grained consensus
                decomposition, arXiv:1603.02526); identity for
                whole-band rounds."""
                if not fine:
                    return x
                return jax.lax.dynamic_slice_in_dim(x, c0, Mg, axis=0)

            d_g = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, g, keepdims=False),
                data,
            )
            c_g = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, g, keepdims=False),
                cdata,
            )
            p_g = p[g]
            Y_g = Y[g]
            rho_g = rho[g]
            if use_staleness:
                ages = consensus.slot_staleness_ages(g, G)
                w = consensus.staleness_weights(
                    ages, ccfg.staleness, ccfg.staleness_discount,
                    dtype=p0.dtype,
                )
            else:
                w = None
            if zmode == "grouped":
                Z = Zc
                BZ_g = bz_of(Z, g)
            elif zmode == "reduced_gather":
                Z, Zsh, num_shard = Zc
                BZ_g = bz_of(Z, g)
            else:
                Zsh, num_shard = Zc
                BZ_g = a2a_bz(Zsh, slot_row, group_row, g)  # active rows
                if fine:
                    pad = jnp.zeros((M,) + BZ_g.shape[1:], BZ_g.dtype)
                    BZ_g = jax.lax.dynamic_update_slice(
                        pad, BZ_g, (c0, i0, i0)
                    )
            with jax.named_scope("xstep"):
                loc = _fit(d_g, c_g, p_g, Y_g, BZ_g, rho_g, max_emiter,
                           cluster_slice=csl)
            p1_g = loc.p
            if fine:
                Yhat_act = sl(Y_g) + sl(rho_g)[:, None, None] * sl(p1_g)
                Yhat_all1 = jax.lax.dynamic_update_slice(
                    Yhat_all, Yhat_act[None], (g, c0, i0, i0)
                )
            else:
                Yhat_act = Y_g + rho_g[:, None, None] * p1_g
                Yhat_all1 = Yhat_all.at[g].set(Yhat_act)
            p1 = p.at[g].set(p1_g)
            if use_spatial:
                Zbar_flat, Xsp = spstate[0], spstate[1]
                z_extra = alpha_sp[:, None, None] * Zbar_flat - Xsp
            with jax.named_scope("zstep"):
                if zmode == "grouped":
                    if use_spatial:
                        Z1 = _zstep_grouped(
                            _flat(Yhat_all1), rho, B_g, axis_name,
                            federated_alpha=alpha_sp, z_extra=z_extra,
                            weights=w,
                        )
                    else:
                        Z1 = _zstep_grouped(_flat(Yhat_all1), rho, B_g,
                                            axis_name, weights=w)
                    Zc1 = Z1
                    BZ1_g = bz_of(Z1, g)
                    BZ1_act = sl(BZ1_g)
                    dres = consensus.admm_dual_residual(Z1, Z)
                else:
                    if use_staleness:
                        num_shard1 = _num_scatter(_flat(Yhat_all1), weights=w)
                    else:
                        # incremental transpose reduction: only the active
                        # (slot, group) factor's Yhat moved this round, so
                        # only its basis-outer-product delta crosses the
                        # mesh (the group schedule is device-uniform, so
                        # the Mg delta rows align across devices)
                        old_act = (
                            jax.lax.dynamic_slice(
                                Yhat_all, (g, c0, i0, i0),
                                (1, Mg, nchunk_max, n8),
                            )[0]
                            if fine else Yhat_all[g]
                        )
                        delta = consensus.accumulate_z_term(
                            B_g[g], _flat(Yhat_act - old_act)
                        )
                        dsh = jax.lax.psum_scatter(
                            delta, axis_name, scatter_dimension=2, tiled=True
                        )
                        if fine:
                            cur = jax.lax.dynamic_slice(
                                num_shard, (c0, i0, i0), (Mg, Npoly, Ks)
                            )
                            num_shard1 = jax.lax.dynamic_update_slice(
                                num_shard, cur + dsh, (c0, i0, i0)
                            )
                        else:
                            num_shard1 = num_shard + dsh
                    if den_static:
                        Bii = Bii0
                    else:
                        Bii = _den_inv(
                            rho, weights=w,
                            federated_alpha=alpha_sp if use_spatial else None,
                        )
                    num_solve = num_shard1
                    if use_spatial:
                        did_z = jax.lax.axis_index(axis_name)
                        num_solve = num_solve + jax.lax.dynamic_slice_in_dim(
                            z_extra, did_z * Ks, Ks, axis=2
                        )
                    Zsh1 = consensus.update_global_z(num_solve, Bii)
                    if zmode == "reduced_gather":
                        Z1 = jax.lax.all_gather(Zsh1, axis_name, axis=2,
                                                tiled=True)
                        BZ1_g = bz_of(Z1, g)
                        BZ1_act = sl(BZ1_g)
                        dres = consensus.admm_dual_residual(Z1, Z)
                        Zc1 = (Z1, Zsh1, num_shard1)
                    else:
                        BZ1_act = a2a_bz(Zsh1, slot_row, group_row, g)
                        dd = (Zsh1 - Zsh).ravel()
                        dres = jnp.sqrt(
                            jax.lax.psum(jnp.sum(dd * dd), axis_name)
                        ) / jnp.sqrt(jnp.asarray(M * Npoly * K, dd.dtype))
                        Zc1 = (Zsh1, num_shard1)
            if use_spatial:
                # cadenced spatial re-fit (sagecal_master.cpp:887-930)
                do_sp = (it % spatial.cadence) == 0
                spstate1 = jax.lax.cond(
                    do_sp,
                    lambda args: spatial_update(
                        args[0], args[1][1], args[1][4], args[1][5]
                    ),
                    lambda args: args[1],
                    (Z1, spstate),
                )
            else:
                spstate1 = spstate
            if fine:
                Ynew_act = Yhat_act - sl(rho_g)[:, None, None] * BZ1_act
                Y1 = jax.lax.dynamic_update_slice(
                    Y, Ynew_act[None], (g, c0, i0, i0)
                )
            else:
                Y1 = Y.at[g].set(Yhat_act - rho_g[:, None, None] * BZ1_act)
            pr = _flat((sl(p1_g) if fine else p1_g) - BZ1_act)
            pres = jax.lax.pmean(
                jnp.linalg.norm(pr.ravel()) / jnp.sqrt(pr.size), axis_name
            )
            if bb_rho:
                if fine:
                    dY = _flat(Yhat_act) - _flat(sl(Yhat_prev[g]))
                    dJ = _flat(sl(p1_g)) - _flat(sl(p_prev[g]))
                    rho_new_act = consensus.update_rho_bb(
                        sl(rho_g),
                        jnp.full((Mg,), rho_upper, rho_g.dtype), dY, dJ,
                    )
                    visit = (it - 1) // (G * cg)
                    upd = jnp.where(visit % 2 == 1, rho_new_act,
                                    sl(rho_g))
                    rho1 = jax.lax.dynamic_update_slice(
                        rho, upd[None], (g, c0)
                    )
                else:
                    dY = _flat(Yhat_act) - _flat(Yhat_prev[g])
                    dJ = _flat(p1_g) - _flat(p_prev[g])
                    rho_new_g = consensus.update_rho_bb(
                        rho_g, jnp.full_like(rho_g, rho_upper), dY, dJ
                    )
                    # BB cadence: update every other visit to this slot
                    # (sagecal_slave.cpp:899)
                    visit = (it - 1) // G
                    rho1 = rho.at[g].set(
                        jnp.where(visit % 2 == 1, rho_new_g, rho_g)
                    )
            else:
                rho1 = rho
            if fine:
                Yhat_prev1 = jax.lax.dynamic_update_slice(
                    Yhat_prev, Yhat_act[None], (g, c0, i0, i0)
                )
                p_prev1 = jax.lax.dynamic_update_slice(
                    p_prev, sl(p1_g)[None], (g, c0, i0, i0)
                )
            else:
                Yhat_prev1 = Yhat_prev.at[g].set(Yhat_act)
                p_prev1 = p_prev.at[g].set(p1_g)
            sres_out = spstate1[3] if use_spatial else jnp.zeros((), p0.dtype)
            ys = (dres, pres, sres_out)
            if collect_trace:
                prn, ddn = band_residuals(p1, Z1, Z, rho1)
                ys = ys + (prn, ddn, rho1)
            return (p1, Y1, Zc1, rho1, Yhat_all1, Yhat_prev1, p_prev1,
                    spstate1, nx + 1), ys

        spstate0 = (
            (Zbar_flat0, Xsp0, Zspat0, jnp.zeros((), p0.dtype),
             Zdiff0_c if use_spatial and use_diff else Zspat0,
             jnp.zeros_like(Zspat0))
            if use_spatial
            else jnp.zeros((), p0.dtype)
        )
        if zmode == "grouped":
            Zc0 = Z
        elif zmode == "reduced_gather":
            Zc0 = (Z, Zsh, num_shard)
        else:
            Zc0 = (Zsh, num_shard)
        init = (p, Y, Zc0, rho, Yhat, Yhat, p, spstate0, nx0)
        if collect_trace:
            # iteration-0 rows: residuals of the plain solve vs the first
            # consensus (dual term is 0 by construction, dZ = 0)
            prn0, _ = band_residuals(p, Z, Z, rho)
            rho0 = rho
        carry, ys = jax.lax.scan(one_iter, init, jnp.arange(1, nadmm))
        (p, Y, Zc, rho, _, _, _, spstate, nx) = carry
        if zmode == "grouped":
            Z = Zc
        elif zmode == "reduced_gather":
            Z = Zc[0]
        else:
            # one-time reassembly of the replicated consensus result
            with jax.named_scope("zstep"):
                Z = jax.lax.all_gather(Zc[0], axis_name, axis=2, tiled=True)
        (dres, pres, sres) = ys[:3]
        dres = jnp.concatenate([jnp.zeros((1,), dres.dtype), dres])
        pres = jnp.concatenate([jnp.zeros((1,), pres.dtype), pres])
        sres = jnp.concatenate([jnp.zeros((1,), sres.dtype), sres])
        Zspat_out = spstate[2] if use_spatial else jnp.zeros((1, 1), jnp.complex64)
        Zdiff_out = (
            spstate[4] if use_spatial and use_diff
            else jnp.zeros((1, 1), jnp.complex64)
        )
        out = (p, Y, Z, rho, dres, pres, Zspat_out, sres, Zdiff_out,
               jax.lax.psum(nx, axis_name))
        if collect_trace:
            prn_t, ddn_t, rho_t = ys[3:]
            prn_t = jnp.concatenate([prn0[None], prn_t])
            ddn_t = jnp.concatenate([jnp.zeros_like(prn0)[None], ddn_t])
            rho_t = jnp.concatenate([rho0[None], rho_t])
            out = out + (prn_t, ddn_t, rho_t)
        return out

    fspec = P(axis_name)
    rspec = P()
    out_specs = (fspec, fspec, rspec, fspec, rspec, rspec, rspec, rspec,
                 rspec, rspec)
    if collect_trace:
        # band-axis telemetry shards on axis 1 (axis 0 is the iteration)
        bspec = P(None, axis_name)
        out_specs = out_specs + (bspec, bspec, bspec)

    @instrumented_jit(name="mesh.admm")
    def fn(data_stack, cdata_stack, p0, rho, B):
        Nf = p0.shape[0]
        if Nf % ndev != 0:
            raise ValueError(
                f"sub-band count {Nf} must be a multiple of the mesh size "
                f"{ndev}; pad with zero-weight bands (rho=0, mask=0) first"
            )
        sm = jax.shard_map(
            local_loop,
            mesh=mesh,
            in_specs=(fspec, fspec, fspec, fspec, fspec),
            out_specs=out_specs,
            check_vma=True,
        )
        out = sm(data_stack, cdata_stack, p0, rho, B)
        p, Y, Z, rho_f, dres, pres, Zspat, sres, Zdiff, nx = out[:10]
        extra = {}
        if collect_trace:
            extra = dict(primal_res_band=out[10], dual_res_band=out[11],
                         rho_trace=out[12])
        return AdmmResult(
            p=p, Y=Y, Z=Z, rho=rho_f, dual_res=dres, primal_res=pres,
            Zspat=Zspat, spat_res=sres, Zspat_diff=Zdiff, xsteps=nx,
            **extra,
        )

    def traced_fn(data_stack, cdata_stack, p0, rho, B):
        # host-side dispatch span AROUND the jitted program (never
        # inside it — jaxlint JL002 territory).  Dispatch is async, so
        # this span covers trace/compile + enqueue only; the caller owns
        # the block_until_ready that closes the device window and the
        # per-band attribution over it (apps/distributed.py).
        from sagecal_tpu.obs.trace import get_tracer

        tr = get_tracer()
        if not tr.enabled:
            return fn(data_stack, cdata_stack, p0, rho, B)
        with tr.span("mesh.admm.dispatch", kind="collective",
                     nf=int(p0.shape[0]), ndev=ndev, nadmm=nadmm,
                     async_dispatch=True):
            return fn(data_stack, cdata_stack, p0, rho, B)

    # AOT hook for the comms bench / regression gate: .lower(*args)
    # .compile() on this handle feeds obs.perf.collective_cost_analysis
    # without executing the program
    traced_fn.inner_jit = fn
    return traced_fn


def stack_for_mesh(items):
    """Stack a list of per-frequency pytrees on a new leading axis for
    sharding over the ``freq`` mesh axis.  Static (non-pytree) fields
    must be identical across items."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)


# ---------------------------------------------------------------------------
# the sub-band pipeline on the mesh


def average_channels(vis, mask):
    """One band's channel average on the device that holds it: the
    solver input ``VisDataset.load_tile(average_channels=True)`` makes on
    the host (data.cpp:665-700).  A row keeps the mean of its unmasked
    channels when more than half of them are unmasked; otherwise it is
    masked and reads 0.

    vis (F, 4, rows) complex, mask (F, rows) -> (1, 4, rows), (1, rows).
    The mask equals load_tile's.  So does the data on every unmasked
    row; on a row that only the uv cut masks, load_tile keeps the mean
    of the unflagged channels where this keeps 0, and no solve reads a
    masked row."""
    F = vis.shape[0]
    good = mask > 0
    ngood = jnp.sum(good, axis=0)  # (rows,)
    ok = ngood > F // 2
    wsum = jnp.sum(jnp.where(good[:, None, :], vis, 0), axis=0)
    x = jnp.where(ok[None, :],
                  wsum / jnp.maximum(ngood, 1)[None, :].astype(wsum.dtype),
                  0)
    return x[None], ok.astype(mask.dtype)[None]


def stack_sources(clusters):
    """The sky's clusters padded to one source count with zero-flux
    sources (``pad_source_batch``, as ``build_cluster_data``'s batched
    path does) and stacked: every leaf (M, S)."""
    from sagecal_tpu.ops.rime import pad_source_batch

    smax = max(int(c.ll.shape[0]) for c in clusters)
    padded = [pad_source_batch(c, smax) for c in clusters]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def make_band_prep_fn(mesh: Mesh, nchunks: Sequence[int], fdelta_chan: float,
                      with_coherencies: bool = True,
                      has_extended: bool = False, axis_name: str = "freq"):
    """Build the jitted per-tile band program: each device averages the
    channels of its own bands and predicts their coherencies.

    ``prep(full, band_freq0, sources, rho_m)``:
      full: VisData with (Nf, ...) leaves sharded over ``axis_name``, the
        tile at full channel resolution; its ``deltaf`` static is the
        bandwidth the solve's coherencies smear over;
      band_freq0: (Nf,) each band's centre frequency (the averaged
        channel's frequency when a band has more than one channel);
      sources: :func:`stack_sources` of the sky, replicated;
      rho_m: (M,) per-cluster penalties, replicated.
    Returns ``(data, cdata, coh_chan, rho, fratio)``, all sharded as
    :func:`make_admm_mesh_fn`'s ``fn`` takes them: the channel-averaged
    VisData; its ClusterData; the per-channel coherencies (Nf, M, F, 4,
    rows) for the residuals, smeared over one channel (``fdelta_chan``);
    ``fratio`` (Nf,) the unflagged share of each band's averaged rows
    and ``rho = fratio * rho_m`` (sagecal_master.cpp:709-723).  With
    ``with_coherencies=False`` (skies the caller predicts band by band)
    cdata and coh_chan are None.

    Clusters are predicted vmapped, padded to one source count, as
    ``build_cluster_data``'s batched path; zero-flux padding sources add
    nothing."""
    from sagecal_tpu.ops.rime import ShapeletTable, _predict_coherencies

    nchunks = tuple(int(n) for n in nchunks)
    spec = P(axis_name)

    def coherencies(d, freqs, sources, fdelta):
        empty = ShapeletTable.empty(d.u.dtype)
        return jax.vmap(lambda s: _predict_coherencies(
            d.u, d.v, d.w, freqs, s, empty, float(fdelta), 32,
            has_extended, False, 0.0, 0.0))(sources)

    def one_band(d, f0, sources, rho_m):
        if d.vis.shape[0] > 1:
            vis, mask = average_channels(d.vis, d.mask)
            avg = d.replace(vis=vis, mask=mask,
                            freqs=f0[None].astype(d.freqs.dtype))
        else:
            avg = d
        fratio = jnp.mean(avg.mask)
        rho = (fratio * rho_m).astype(rho_m.dtype)
        if not with_coherencies:
            return avg, None, None, rho, fratio
        coh = coherencies(d, avg.freqs, sources, d.deltaf)
        coh_chan = (coh if d.vis.shape[0] == 1 else
                    coherencies(d, d.freqs, sources, fdelta_chan))
        cmap = jnp.stack([
            jnp.minimum(d.time_idx // -(-d.tilesz // n), n - 1)
            .astype(jnp.int32) for n in nchunks])
        nchunk = jnp.asarray(nchunks, jnp.int32)
        return avg, ClusterData(coh, cmap, nchunk), coh_chan, rho, fratio

    @scoped("bands")
    @scoped("coh")
    @true_f32
    def local(full, band_freq0, sources, rho_m):
        return jax.vmap(one_band, in_axes=(0, 0, None, None))(
            full, band_freq0, sources, rho_m)

    # no collectives: each device computes its own bands alone (the
    # coherency scan's zero carry is not typed as varying over the mesh)
    sm = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, P(), P()),
                       out_specs=spec, check_vma=False)

    @instrumented_jit(name="bands.coh")
    def prep(full, band_freq0, sources, rho_m):
        return sm(full, band_freq0, sources, rho_m)

    return prep


def make_band_residual_fn(mesh: Mesh, global_solution: bool = False,
                          axis_name: str = "freq"):
    """Build the jitted per-tile residual program: each device computes
    ``V - sum_k J_k C_k J_k^H`` per channel for its own bands
    (``calculate_residuals_multifreq``, residual.c:940).

    ``residual(full, coh_chan, chunk_map, nchunk, p, Z, B)``: the
    full-channel VisData stack and the per-channel coherencies of
    :func:`make_band_prep_fn`, the solve's chunk maps and counts, the
    per-band solutions ``p`` (Nf, M, nchunk_max, 8N), all sharded; ``Z``
    (M, Npoly, K) replicated and ``B`` (Nf, Npoly) sharded.  With
    ``global_solution`` the residuals use the consensus ``B_f Z`` in
    place of ``p`` (the -U path, sagecal_slave.cpp:861-979).  Returns
    (Nf, F, 4, rows) complex, sharded."""
    spec = P(axis_name)

    def one_band(d, coh, cmap, nchunk, p, b, Z):
        if global_solution:
            p = consensus.bz_for_freq(Z, b).reshape(p.shape)
        return d.vis - predict_full_model(p, ClusterData(coh, cmap, nchunk),
                                          d)

    @scoped("bands")
    @scoped("residual")
    @true_f32
    def local(full, coh_chan, cmap, nchunk, p, Z, B):
        return jax.vmap(one_band, in_axes=(0, 0, 0, 0, 0, 0, None))(
            full, coh_chan, cmap, nchunk, p, B, Z)

    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, P(), spec),
        out_specs=spec, check_vma=False)

    @instrumented_jit(name="bands.residual")
    def residual(full, coh_chan, chunk_map, nchunk, p, Z, B):
        return sm(full, coh_chan, chunk_map, nchunk, p, Z, B)

    return residual
