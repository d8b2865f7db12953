"""SAGE/EM calibration driver: expectation over clusters, per-cluster solves.

Redesign of ``sagefit_visibilities`` (``/root/reference/src/lib/Dirac/
lmfit.c:777-1083``).  The EM structure is kept — clusters are solved
sequentially against the residual with all other cluster models removed
(the data dependency is fundamental to SAGE) — but it runs as a
``lax.scan`` over a *stacked, padded* cluster axis inside one jit: the
residual visibilities are the scan carry, the per-cluster LM/robust
solves are the lock-step batched solvers of :mod:`sagecal_tpu.solvers.lm`,
and hybrid time chunks are solved simultaneously (not looped as in
lmfit.c:897-967).  The reference's two-GPU cluster pipeline
(lmfit_cuda.c:451-551) has no analog because nothing here is
device-specific — XLA owns scheduling.

Reproduced reference behaviors:
- weighted LM-iteration allocation across clusters by previous cost
  reduction, alternating with equal allocation when ``randomize`` is on
  (lmfit.c:859-882, 986-1009): itermax becomes a traced per-cluster bound
  of the LM while_loop;
- robust solves only on the final EM iteration for the LM-family modes,
  with the mean Student's-t nu carried to the joint LBFGS
  (lmfit.c:915-935, 1011-1025);
- final joint LBFGS over all 8*N*Mt parameters, Gaussian
  ``sum(e^2)`` or robust ``sum(log(1+e^2/nu))`` cost
  (lbfgs_fit_wrapper / lbfgs_fit_robust_wrapper; robust_lbfgs.c:61-76),
  with gradients by autodiff instead of the hand-written threaded
  gradient (robust_lbfgs.c:155+);
- res_0/res_1 = ||data - full model|| / n bookkeeping and the
  "worse-than-initial" signal (lmfit.c:1049-1052, return -1).

Solver modes mirror Dirac.h:1607-1613.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from sagecal_tpu.core.types import VisData, corrupt_flat, params_to_jones
from sagecal_tpu.obs.perf import instrumented_jit
from sagecal_tpu.ops.rime import SourceBatch, predict_coherencies
from sagecal_tpu.solvers.lbfgs import lbfgs_fit
from sagecal_tpu.solvers.lm import LMConfig, lm_solve, os_lm_solve
from sagecal_tpu.solvers.robust import robust_lm_solve
from sagecal_tpu.utils.precision import true_f32

# solver modes (values match Dirac.h:1607-1613)
SM_OSLM_LBFGS = 0
SM_LM_LBFGS = 1
SM_RLM_RLBFGS = 2
SM_OSLM_OSRLM_RLBFGS = 3
SM_RTR_OSLM_LBFGS = 4
SM_RTR_OSRLM_RLBFGS = 5
SM_NSD_RLBFGS = 6

_ROBUST_MODES = (SM_RLM_RLBFGS, SM_OSLM_OSRLM_RLBFGS, SM_RTR_OSRLM_RLBFGS, SM_NSD_RLBFGS)


@struct.dataclass
class SageConfig:
    max_emiter: int = struct.field(pytree_node=False, default=3)
    max_iter: int = struct.field(pytree_node=False, default=10)
    max_lbfgs: int = struct.field(pytree_node=False, default=10)
    lbfgs_m: int = struct.field(pytree_node=False, default=7)
    solver_mode: int = struct.field(pytree_node=False, default=SM_LM_LBFGS)
    nulow: float = struct.field(pytree_node=False, default=2.0)
    nuhigh: float = struct.field(pytree_node=False, default=30.0)
    randomize: bool = struct.field(pytree_node=False, default=True)
    em_rounds_robust: int = struct.field(pytree_node=False, default=2)
    # Optional elementwise box bound |p_i| <= param_bound on the joint
    # LBFGS pass: 0 disables (plain LBFGS).  The reference ships the
    # same bounded optimizer as a public API (lbfgsb_fit, Dirac.h:1843;
    # demo test/Dirac/demo.c:90); bounding the solved gain parameters is
    # its natural calibration use (runaway-gain containment).
    param_bound: float = struct.field(pytree_node=False, default=0.0)
    # Route the joint-LBFGS cost through the fused Pallas RIME kernel
    # (ops/rime_kernel.py) — one pass over the coherency stack per
    # evaluation vs the XLA predict's multiple buffer-scale
    # intermediates.  f32 data only.
    use_fused_predict: bool = struct.field(pytree_node=False, default=False)
    # Coherency-stack storage dtype on the fused path: "f32" (default)
    # or "bf16" (halves the dominant HBM stream; the kernel upcasts at
    # the VMEM load and accumulates in f32 — ~3 significant digits of
    # coherency precision, a throughput knob validated by the quality
    # watchdog, NOT for the final 1e-6-bar solve).  Ignored on the XLA
    # path.
    coh_dtype: str = struct.field(pytree_node=False, default="f32")
    # Static ceiling multiplier for the weighted per-cluster iteration
    # allocation (lmfit.c:859-882): a high-error cluster may be granted up
    # to iter_budget_cap * max_iter iterations by the -R weighting.  The
    # reference has no static ceiling (this_itermax+5/+10/+15,
    # lmfit.c:936-953), but on TPU the RSD warmup is a static-length scan
    # and the TR/NSD loops carry compile-time bounds, so the ceiling is an
    # intentional compile-time/runtime tradeoff: raise it if profiling
    # shows clusters exhausting their dynamic budget.
    iter_budget_cap: int = struct.field(pytree_node=False, default=3)
    # Collect per-iteration solver telemetry (obs.records.IterTrace) from
    # every per-cluster solve and the joint LBFGS, returned in
    # SageResult.telemetry.  Static: off builds the exact same jaxpr as
    # before (telemetry slots are None = empty pytrees).
    collect_telemetry: bool = struct.field(pytree_node=False, default=False)
    # Collect fixed-shape solution-quality side outputs (ops/quality.py):
    # per-cluster SolveQuality from the FINAL EM pass's solves (leading
    # cluster axis) plus a whole-solution bundle at the returned
    # parameters, in SageResult.quality.  Same static-gate contract as
    # collect_telemetry: off builds the identical jaxpr.
    collect_quality: bool = struct.field(pytree_node=False, default=False)


class ClusterData(NamedTuple):
    """Stacked per-cluster arrays crossing into jit (all static shapes).

    ``coh`` uses the canonical flat layout (see
    :mod:`sagecal_tpu.core.types`): rows minor-most so the TPU (8, 128)
    tile pads only the rows tail — the trailing-2x2 layout of round 2
    measured a 64x padding blow-up (726 MB logical -> 46.47 GB
    allocation) at the 62-station/100-cluster shape.
    """

    coh: jax.Array  # (M, F, 4, rows) complex cluster coherencies
    chunk_map: jax.Array  # (M, rows) int32 row -> hybrid chunk
    nchunk: jax.Array  # (M,) int32 actual chunk counts


class SageResult(NamedTuple):
    p: jax.Array  # (M, nchunk_max, 8N) solved parameters
    res_0: jax.Array  # initial residual norm / n
    res_1: jax.Array  # final residual norm / n
    mean_nu: jax.Array
    diverged: jax.Array  # bool, res_1 > res_0 (the reference's -1 return)
    # {"em": tuple of per-EM-pass IterTrace pytrees (leading cluster
    # axis), "lbfgs": joint-LBFGS IterTrace} when
    # config.collect_telemetry, else None (empty pytree — jitted output
    # signature unchanged)
    telemetry: Optional[dict] = None
    # {"em": SolveQuality stacked over clusters from the final EM pass,
    # "final": whole-solution SolveQuality (chi^2 attribution of the
    # full residual at the returned p + gain health)} when
    # config.collect_quality, else None (same empty-pytree contract)
    quality: Optional[dict] = None


def build_cluster_data(
    data: VisData, clusters: Sequence[SourceBatch], nchunks: Sequence[int],
    fdelta: Optional[float] = None,
    shapelets=None,
) -> ClusterData:
    """Precompute coherencies + chunk maps (host-side, once per tile).

    Equivalent of ``precalculate_coherencies`` for all clusters
    (predict.c:503; stored layout ``coh`` Dirac.h / fullbatch_mode.cpp:371).

    ``shapelets``: sky-global :class:`ShapeletTable` (from
    ``io.skymodel.load_sky``) for clusters containing ST_SHAPELET
    sources; those clusters take the per-cluster path.
    """
    if fdelta is None:
        fdelta = data.deltaf
    if shapelets is not None:
        from sagecal_tpu.ops.rime import ST_SHAPELET as _ST_SH

        shap_flags = [
            bool(np.any(np.asarray(c.stype) == _ST_SH)) for c in clusters
        ]
        if any(shap_flags):
            # Split: shapelet-containing clusters take the per-cluster
            # path (they need the mode table); everything else keeps the
            # batched path — one diffuse cluster must not collapse a
            # 100-cluster point sky back to 100 separate dispatches.
            plain_idx = [i for i, f in enumerate(shap_flags) if not f]
            shap_idx = [i for i, f in enumerate(shap_flags) if f]
            plain_cd = build_cluster_data(
                data, [clusters[i] for i in plain_idx],
                [nchunks[i] for i in plain_idx], fdelta,
            ) if plain_idx else None
            from sagecal_tpu.ops.rime import resolve_source_flags

            coh_parts = {}
            for i in shap_idx:
                has_ext, has_sh = resolve_source_flags(
                    clusters[i], shapelets)
                coh_parts[i] = predict_coherencies(
                    data.u, data.v, data.w, data.freqs, clusters[i],
                    fdelta, shapelets=shapelets,
                    has_extended=has_ext, has_shapelet=has_sh,
                )
            for j, i in enumerate(plain_idx):
                coh_parts[i] = plain_cd.coh[j]
            coh = jnp.stack([coh_parts[i] for i in range(len(clusters))])
            cmaps = []
            for nch in nchunks:
                tilechunk = -(-data.tilesz // nch)
                cmaps.append(jnp.minimum(
                    data.time_idx // tilechunk, nch - 1).astype(jnp.int32))
            return ClusterData(
                coh=coh,
                chunk_map=jnp.stack(cmaps),
                nchunk=jnp.asarray(list(nchunks), jnp.int32),
            )
    sizes = [int(c.ll.shape[0]) for c in clusters]
    smax, total = max(sizes), sum(sizes)
    if smax * len(clusters) <= 4 * total and len(clusters) > 1:
        # Batched path: pad every cluster to smax sources (zero-flux
        # no-op padding with pad_source_batch's f0>0 / shapelet_idx=-1
        # invariants) and evaluate clusters vmapped in BLOCKS instead
        # of M separate jit dispatches (measured: the per-cluster loop
        # dominated the app's "coherencies" phase at 100 clusters).
        # Blocking bounds the vmapped intermediates' memory at
        # BLOCK x the single-cluster working set.  Falls back to the
        # loop when padding would waste >4x the source count (heavily
        # skewed skies).  Source-type flags are computed HOST-side:
        # under vmap the stype tracer would defeat predict_coherencies'
        # point-source fast path and its shapelet guard.
        from sagecal_tpu.ops.rime import (
            ST_POINT, ST_SHAPELET, ShapeletTable, _predict_coherencies,
            pad_source_batch,
        )

        stypes = np.concatenate([np.asarray(c.stype) for c in clusters])
        if bool(np.any(stypes == ST_SHAPELET)):
            raise ValueError(
                "SourceBatch contains ST_SHAPELET sources but no "
                "ShapeletTable was supplied — they would silently "
                "predict as point sources"
            )
        has_ext = bool(np.any(stypes != ST_POINT))
        empty_tab = ShapeletTable.empty(data.u.dtype)

        # NOTE: a fresh wrapper per build_cluster_data call — the shared
        # "coherency_block" perf name aggregates them, so per-tile
        # retraces of this closure show up as a growing compile count
        @instrumented_jit(name="coherency_block")
        def _block(u, v, w, freqs, stacked):
            return jax.vmap(
                lambda s: _predict_coherencies(
                    u, v, w, freqs, s, empty_tab, float(fdelta), 32,
                    has_ext, False, 0.0, 0.0,
                )
            )(stacked)

        BLOCK = 16
        padded = [pad_source_batch(c, smax) for c in clusters]
        parts = []
        for i in range(0, len(padded), BLOCK):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *padded[i:i + BLOCK]
            )
            parts.append(
                _block(data.u, data.v, data.w, data.freqs, stacked)
            )
        coh = jnp.concatenate(parts, axis=0)
    else:
        from sagecal_tpu.ops.rime import resolve_source_flags

        flags = [resolve_source_flags(src, shapelets) for src in clusters]
        coh = jnp.stack([
            predict_coherencies(data.u, data.v, data.w, data.freqs, src,
                                fdelta, shapelets=shapelets,
                                has_extended=he, has_shapelet=hs)
            for src, (he, hs) in zip(clusters, flags)
        ])
    cmaps = []
    for nch in nchunks:
        tilechunk = -(-data.tilesz // nch)  # ceil
        cmaps.append(
            jnp.minimum(data.time_idx // tilechunk, nch - 1).astype(jnp.int32)
        )
    return ClusterData(
        coh=coh,
        chunk_map=jnp.stack(cmaps),
        nchunk=jnp.asarray(list(nchunks), jnp.int32),
    )


def build_cluster_data_withbeam(
    data: VisData,
    clusters: Sequence[SourceBatch],
    nchunks: Sequence[int],
    geom,
    pointing,
    coeff,
    beam_mode: int,
    time_jd,
    ra0: float,
    dec0: float,
    fdelta: Optional[float] = None,
    wideband: bool = False,
    shapelets=None,
    precess: bool = True,
) -> ClusterData:
    """Beam-aware tile precompute: per cluster, evaluate the station beam
    toward each source and fold it into the coherencies
    (``precalculate_coherencies_withbeam``, predict_withbeam.c:552; the
    per-source/station/time/freq beam precompute of :487-510).

    ``geom``/``pointing``/``coeff``: see :mod:`sagecal_tpu.ops.beam`;
    ``time_jd``: (tilesz,) Julian dates of the tile's timeslots; source
    (ra, dec) are recovered from the batches' direction cosines about
    (ra0, dec0).

    ``precess``: precess source and pointing directions from J2000 to
    the tile's mid-time epoch before the az/el conversion — the app's
    ``precess_source_locations`` step (fullbatch_mode.cpp:335-338,
    data.cpp:1616-1645; skipped for the lunar ALO element, matching
    ``beam.elType!=ELEM_ALO``)."""
    from sagecal_tpu.ops.beam import beam_jones, predict_coherencies_withbeam
    from sagecal_tpu.ops.transforms import (
        get_precession_params, lmn_to_radec, precess_radec_equatorial,
    )

    if fdelta is None:
        fdelta = data.deltaf
    Tr = None
    if precess:
        jd = np.asarray(time_jd)
        Tr = get_precession_params(float(jd[len(jd) // 2]))
        pra, pdec = precess_radec_equatorial(pointing.ra0, pointing.dec0, Tr)
        bra, bdec = precess_radec_equatorial(
            pointing.b_ra0, pointing.b_dec0, Tr
        )
        pointing = pointing._replace(
            ra0=float(pra), dec0=float(pdec),
            b_ra0=float(bra), b_dec0=float(bdec),
        )
    cohs = []
    cmaps = []
    for src, nch in zip(clusters, nchunks):
        ra, dec = lmn_to_radec(np.asarray(src.ll), np.asarray(src.mm), ra0, dec0)
        if Tr is not None:
            ra, dec = precess_radec_equatorial(ra, dec, Tr)
        B = beam_jones(
            geom, pointing, coeff, ra, dec, np.asarray(time_jd),
            jnp.asarray(data.freqs), mode=beam_mode, wideband=wideband,
        ).astype(data.vis.dtype)
        cohs.append(
            predict_coherencies_withbeam(
                data.u, data.v, data.w, data.freqs, src, B,
                data.time_idx, data.ant_p, data.ant_q, fdelta,
                shapelets=shapelets,
            )
        )
        tilechunk = -(-data.tilesz // nch)
        cmap = jnp.minimum(data.time_idx // tilechunk, nch - 1).astype(jnp.int32)
        cmaps.append(cmap)
    return ClusterData(
        coh=jnp.stack(cohs),
        chunk_map=jnp.stack(cmaps),
        nchunk=jnp.asarray(list(nchunks), jnp.int32),
    )


def cluster_model(p_k, coh_k, cmap_k, ant_p, ant_q):
    """One cluster's corrupted model J_p C J_q^H: flat (F, 4, rows).

    p_k: (nchunk, 8N); coh_k: (F, 4, rows); cmap_k: (rows,)."""
    return corrupt_flat(params_to_jones(p_k), coh_k, ant_p, ant_q, cmap_k)


def predict_full_model(p_all, cdata: ClusterData, data: VisData):
    """sum_k J C J^H over all clusters (``minimize_viz_full_pth``,
    lmfit.c:692), flat (F, 4, rows).

    TPU-first formulation: instead of a sequential ``lax.scan`` over
    clusters, every per-cluster/per-row gain component is broadcast into
    an (M, rows) array by a one-hot station MATMUL (MXU work; an XLA
    gather here measured ~100 ms/op with a far worse scatter transpose
    in the backward pass), and the sum over clusters becomes sixteen
    fused multiply-reduce contractions ``einsum("kr,kfr->fr")`` — fully
    parallel over clusters, no 100-step sequential dependency in the
    joint-LBFGS gradient (the reference's threaded equivalent is
    minimize_viz_full_pth + the robust_lbfgs.c:155 gradient loops).
    """
    jones = params_to_jones(p_all)  # (M, nchunk, N, 2, 2)
    M, nchunk, N = jones.shape[0], jones.shape[1], jones.shape[2]
    cmap = cdata.chunk_map  # (M, rows)
    rdt = jnp.real(jones).dtype
    # components row-major: (M, nchunk, N, 4) -> (M*nchunk*4, N)
    tab = jnp.moveaxis(jones.reshape(M * nchunk, N, 4), 1, 2).reshape(
        M * nchunk * 4, N
    )

    def gains(ant):
        """All 4 components for every (cluster, row): 4x (M, rows)."""
        oh = (ant[None, :] == jnp.arange(N, dtype=ant.dtype)[:, None]).astype(rdt)
        v = jax.lax.complex(jnp.real(tab) @ oh, jnp.imag(tab) @ oh)
        v = v.reshape(M, nchunk, 4, -1)  # (M, nchunk, 4, rows)
        if nchunk == 1:
            g = v[:, 0]
        else:
            sel = jax.nn.one_hot(cmap, nchunk, axis=1, dtype=rdt)  # (M, nchunk, rows)
            g = jnp.einsum("mcr,mcir->mir", sel, v)
        return g[:, 0], g[:, 1], g[:, 2], g[:, 3]

    pa, pb, pc, pd = gains(data.ant_p)
    qa, qb, qc, qd = gains(data.ant_q)
    qa, qb, qc, qd = jnp.conj(qa), jnp.conj(qb), jnp.conj(qc), jnp.conj(qd)
    c00 = cdata.coh[:, :, 0, :]  # (M, F, rows)
    c01 = cdata.coh[:, :, 1, :]
    c10 = cdata.coh[:, :, 2, :]
    c11 = cdata.coh[:, :, 3, :]

    def contract(coef, w):
        # (M, rows) x (M, F, rows) -> (F, rows), reduced over clusters
        return jnp.einsum("kr,kfr->fr", coef, w)

    # V = J_p (C J_q^H) factored in two stages: W_aj = sum_b C_ab qconj_jb
    # reads the coherency stack ONCE (the 16-term single-stage expansion
    # re-read each C component four times — ~2x the HBM traffic of this
    # form, measured on chip), then V_ij = sum_ma Jp_ia W_aj.
    q = lambda g: g[:, None, :]  # (M, rows) -> (M, 1, rows) vs (M, F, rows)
    w00 = c00 * q(qa) + c01 * q(qb)
    w01 = c00 * q(qc) + c01 * q(qd)
    w10 = c10 * q(qa) + c11 * q(qb)
    w11 = c10 * q(qc) + c11 * q(qd)
    v00 = contract(pa, w00) + contract(pb, w10)
    v01 = contract(pa, w01) + contract(pb, w11)
    v10 = contract(pc, w00) + contract(pd, w10)
    v11 = contract(pc, w01) + contract(pd, w11)
    return jnp.stack([v00, v01, v10, v11], axis=-2)


def em_residual_scan(data: VisData, cdata: ClusterData, p_all, extras, solve_one,
                     cluster_slice=None):
    """One SAGE expectation pass: scan clusters with the residual as carry
    (the add-back / solve / subtract structure of lmfit.c:876-986).

    ``solve_one(xeff, coh_k, cmap_k, p_k, extras_k) -> (p_new_k, aux_k)``
    runs the per-cluster maximization against ``xeff`` = residual with
    this cluster's current model restored.  ``extras``: pytree of arrays
    with leading cluster axis (or None).  Returns (p_new (M,...), aux).

    ``cluster_slice``: optional ``(start, count)`` — solve only the
    ``count`` clusters beginning at (dynamic) index ``start``, holding
    the rest fixed.  The initial residual still subtracts the FULL model
    (fixed clusters stay subtracted throughout, exactly as if their
    scan steps ran with a no-op solver), so a sliced pass is the
    fine-grained consensus factor-node update of parallel/mesh.py:
    per-round work scales with ``count`` while the physics stays whole.
    """

    def cluster_step(xres, inp):
        coh_k, cmap_k, p_k, extras_k = inp
        model_old = cluster_model(p_k, coh_k, cmap_k, data.ant_p, data.ant_q)
        xeff = xres + model_old
        p_new, aux = solve_one(xeff, coh_k, cmap_k, p_k, extras_k)
        model_new = cluster_model(p_new, coh_k, cmap_k, data.ant_p, data.ant_q)
        return xeff - model_new, (p_new, aux)

    xres0 = data.vis - predict_full_model(p_all, cdata, data)
    xs = (cdata.coh, cdata.chunk_map, p_all, extras)
    if cluster_slice is not None:
        start, count = cluster_slice
        xs = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, count, axis=0),
            xs,
        )
    _, (p_new, aux) = jax.lax.scan(cluster_step, xres0, xs)
    if cluster_slice is not None:
        p_new = jax.lax.dynamic_update_slice_in_dim(
            p_all, p_new, cluster_slice[0], axis=0
        )
    return p_new, aux


def _res_norm(res, mask, nreal):
    # res flat (..., F, 4, rows); mask (..., F, rows)
    r = res * mask[..., None, :]
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)) / nreal


def _make_fused_joint_cost(data, cdata, M, nchunk_max, n8, robust, mean_nu,
                           coh_dtype="f32"):
    """Joint-LBFGS cost through the fused OBJECTIVE kernel
    (ops/rime_kernel.py): predict, masked residual, Student's-t (or
    Gaussian) weighting and the scalar reduction all happen in ONE pass
    over the coherency stack — neither the model nor the residual ever
    round-trips HBM, forward or backward.  The packed/padded arrays are
    built ONCE here (they are constants of the LBFGS loop).  f32 only:
    the kernel computes in float32.  ``coh_dtype="bf16"`` stores the
    coherency stack as bfloat16 (halved HBM stream, f32 accumulation —
    SageConfig.coh_dtype rationale)."""
    from sagecal_tpu.ops.rime_kernel import (
        FULL_CLUSTER_TILE, MAX_GRID_ROWS, fused_cost_packed_chunked,
        fused_cost_packed_hybrid_chunked, pack_gain_tables,
        pack_predict_inputs, pad_to,
    )

    if jnp.real(data.vis).dtype != jnp.float32:
        raise ValueError(
            "use_fused_predict requires float32 data (the Pallas kernel "
            "computes in f32); run with f64 disabled or use the XLA path"
        )
    if coh_dtype not in ("f32", "bf16"):
        raise ValueError(f"coh_dtype must be 'f32' or 'bf16', got "
                         f"{coh_dtype!r}")
    # FULL_CLUSTER_TILE (128) is the largest tile whose BACKWARD kernel
    # fits the v5e 16 MB scoped-VMEM limit at ~100 clusters, and rows
    # are chunked so each Mosaic grid stays short — the hardware-proven
    # production configuration (PERF.md).
    mp = pad_to(M, 8)
    vis_ri, mask_p, coh_ri, antp, antq, cmap = pack_predict_inputs(
        data.vis, data.mask, cdata.coh, data.ant_p, data.ant_q,
        cdata.chunk_map if nchunk_max > 1 else None, FULL_CLUSTER_TILE,
        max_rows=MAX_GRID_ROWS,
    )
    if coh_dtype == "bf16":
        coh_ri = coh_ri.astype(jnp.bfloat16)
    coh_c = jax.lax.stop_gradient(coh_ri)
    nu_c = mean_nu if robust else None

    def cost_fn(pflat):
        jones = params_to_jones(
            pflat.reshape(M, nchunk_max, n8).astype(jnp.float32)
        )  # (M, nchunk, N, 2, 2)
        if nchunk_max > 1:
            tre, tim = pack_gain_tables(jones, mp)
            return fused_cost_packed_hybrid_chunked(
                tre, tim, coh_c, antp, antq, vis_ri, mask_p, cmap,
                nchunk_max, nu_c, FULL_CLUSTER_TILE, MAX_GRID_ROWS,
            )
        tre, tim = pack_gain_tables(jones[:, 0], mp)
        return fused_cost_packed_chunked(
            tre, tim, coh_c, antp, antq, vis_ri, mask_p, nu_c,
            FULL_CLUSTER_TILE, MAX_GRID_ROWS,
        )

    return cost_fn


def _make_fused_joint_cost_batch(data, cdata, B, M, n8, robust, mean_nu_b,
                                 coh_dtype="f32", valid=None):
    """Batched joint-LBFGS cost: the fused objective for B lanes in ONE
    Pallas grid (``ops.rime_kernel.fused_cost_packed_batch``), the lane
    axis folded into the MXU contraction.  ``data``/``cdata`` leaves
    carry a leading batch axis; all lanes must share ``ant_p``/``ant_q``
    (checked host-side by the router) — the kernel reads lane 0's copy.
    ``mean_nu_b``: (B,) per-lane Student's-t nu (traced; EM refinements
    never recompile).  ``valid``: optional (B,) lane mask zeroing padded
    lanes' cost and cotangent (pack_cost_inputs_batch docstring).
    nchunk_max == 1 only; f32 data only; ``coh_dtype="bf16"`` halves the
    dominant coherency HBM stream with f32 accumulation."""
    from sagecal_tpu.ops.rime_kernel import (
        FULL_CLUSTER_TILE, MAX_GRID_ROWS, fused_cost_packed_batch,
        pack_cost_inputs_batch, pack_gain_tables_batch, pad_to,
    )

    if jnp.real(data.vis).dtype != jnp.float32:
        raise ValueError(
            "the batched fused path requires float32 data (the Pallas "
            "kernel computes in f32); run with f64 disabled or use the "
            "XLA path"
        )
    if coh_dtype not in ("f32", "bf16"):
        raise ValueError(f"coh_dtype must be 'f32' or 'bf16', got "
                         f"{coh_dtype!r}")
    mp = pad_to(M, 8)
    vis_ri, mask_p, coh_ri, antp, antq = pack_cost_inputs_batch(
        data.vis, data.mask, cdata.coh, data.ant_p[0], data.ant_q[0],
        FULL_CLUSTER_TILE, max_rows=MAX_GRID_ROWS, valid=valid,
    )
    if coh_dtype == "bf16":
        coh_ri = coh_ri.astype(jnp.bfloat16)
    coh_c = jax.lax.stop_gradient(coh_ri)
    nu_c = mean_nu_b if robust else None

    def cost_fn(pflat_b):
        # (B, M*8N) -> (B,) per-lane costs, one grid for the whole batch
        jones = params_to_jones(
            pflat_b.reshape(B, M, n8).astype(jnp.float32)
        )  # (B, M, N, 2, 2)
        tre, tim = pack_gain_tables_batch(jones, mp)
        return fused_cost_packed_batch(
            tre, tim, coh_c, antp, antq, vis_ri, mask_p, nu_c,
            FULL_CLUSTER_TILE, MAX_GRID_ROWS,
        )

    return cost_fn


def _em_phase(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    config: SageConfig,
    key: jax.Array,
):
    """The SAGE expectation passes of :func:`sagefit` — per-cluster
    solves and nu estimation, NO joint LBFGS and no finalization.
    Returns ``(p, mean_nu, res_0, em_traces, em_quality)``.  Factored
    out so :func:`sagefit_batched_fused` can vmap the per-cluster EM
    machinery per lane while replacing the joint-LBFGS phase with one
    batched fused kernel loop."""
    M = cdata.coh.shape[0]
    F, rows = data.vis.shape[-3], data.vis.shape[-1]
    nreal = rows * F * 8
    mode = config.solver_mode
    robust = mode in _ROBUST_MODES

    lmcfg = LMConfig(itmax=config.max_iter)
    total_iter = M * config.max_iter
    iter_bar = int(math.ceil((0.80 / M) * total_iter))

    full0 = predict_full_model(p0, cdata, data)
    res_vis0 = data.vis - full0
    res_0 = _res_norm(res_vis0, data.mask, nreal)

    def _nerr_of(res):
        # relative cost decrease -> iteration weighting (lmfit.c:971-979)
        c0 = jnp.sum(res.cost0)
        c1 = jnp.sum(res.cost)
        return jnp.where(c0 > 0.0, jnp.maximum((c0 - c1) / c0, 0.0), 0.0)

    collect = config.collect_telemetry
    collect_q = config.collect_quality

    def em_iteration(p_all, nerr, nus_in, weighted, em_idx, key):
        """One EM pass over clusters via :func:`em_residual_scan`."""
        last_em = em_idx == config.max_emiter - 1
        # quality side outputs only on the final pass: earlier iterates
        # are discarded, so attributing them would just burn reductions
        want_q = collect_q and last_em

        def _aux_of(res, nu_k):
            aux = (_nerr_of(res), nu_k)
            if collect:
                aux = aux + (res.trace,)
            if want_q:
                aux = aux + (res.quality,)
            return aux

        use_robust = robust and last_em
        # OS acceleration on non-final EM passes (lmfit.c:906-934)
        use_os = (
            mode in (SM_OSLM_LBFGS, SM_RLM_RLBFGS, SM_OSLM_OSRLM_RLBFGS)
            and not last_em
        )
        key, sub = jax.random.split(key)
        subkeys = jax.random.split(sub, M)

        def solve_one(xeff, coh_k, cmap_k, p_k, extras_k):
            nerr_k, key_k, nu_prev = extras_k
            itermax = jnp.where(
                weighted,
                (0.20 * nerr_k * total_iter).astype(jnp.int32) + iter_bar,
                config.max_iter,
            )
            # static ceilings sized from the max weighted budget the -R
            # allocation can grant (iter_budget_cap * max_iter), not bare
            # max_iter — otherwise the weighted-allocation feature would
            # no-op in RTR/NSD modes (see SageConfig.iter_budget_cap)
            iter_cap = config.max_iter * config.iter_budget_cap
            if mode == SM_RTR_OSLM_LBFGS:
                # RTR every EM pass, weighted budget (lmfit.c:936:
                # this_itermax+5 RSD, +10 TR)
                from sagecal_tpu.solvers.rtr import RTRConfig, rtr_solve

                res = rtr_solve(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    RTRConfig(itmax_rsd=iter_cap + 5,
                              itmax_rtr=iter_cap + 10),
                    itmax_dynamic=itermax,
                    collect_trace=collect, collect_quality=want_q,
                )
                return res.p, _aux_of(res, jnp.asarray(config.nulow, p_all.dtype))
            if mode == SM_RTR_OSRLM_RLBFGS:
                # nu carried across EM passes (lmfit.c:940-947 sets
                # robust_nu only at ci==0 and lets it persist)
                from sagecal_tpu.solvers.rtr import RTRConfig, rtr_solve_robust

                res, nu_k = rtr_solve_robust(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    RTRConfig(itmax_rsd=iter_cap + 5,
                              itmax_rtr=iter_cap + 10),
                    nu0=nu_prev, nulow=config.nulow, nuhigh=config.nuhigh,
                    em_iters=config.em_rounds_robust,
                    itmax_dynamic=itermax,
                    collect_trace=collect, collect_quality=want_q,
                )
                return res.p, _aux_of(res, nu_k.astype(p_all.dtype))
            if mode == SM_NSD_RLBFGS:
                # robust NSD with nu estimation (rtr_solve_robust.c:2104)
                from sagecal_tpu.solvers.rtr import nsd_solve_robust

                res, nu_k = nsd_solve_robust(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    itmax=iter_cap + 15,
                    nu0=nu_prev, nulow=config.nulow, nuhigh=config.nuhigh,
                    em_iters=config.em_rounds_robust,
                    itmax_dynamic=itermax,
                    collect_trace=collect, collect_quality=want_q,
                )
                return res.p, _aux_of(res, nu_k.astype(p_all.dtype))
            if use_robust:
                res, nu_k = robust_lm_solve(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    nu0=config.nulow, nulow=config.nulow, nuhigh=config.nuhigh,
                    em_iters=config.em_rounds_robust,
                    config=LMConfig(itmax=config.max_iter),
                    collect_trace=collect, collect_quality=want_q,
                )
            elif use_os:
                res = os_lm_solve(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    lmcfg, nsubsets=2, key=key_k, collect_trace=collect,
                    collect_quality=want_q,
                )
                nu_k = jnp.asarray(config.nulow, p_all.dtype)
            else:
                res = lm_solve(
                    xeff, coh_k, data.mask, data.ant_p, data.ant_q, cmap_k, p_k,
                    lmcfg, itmax_dynamic=itermax, collect_trace=collect,
                    collect_quality=want_q,
                )
                nu_k = jnp.asarray(config.nulow, p_all.dtype)
            return res.p, _aux_of(res, nu_k)

        p_new, aux = em_residual_scan(
            data, cdata, p_all, (nerr, subkeys, nus_in), solve_one
        )
        nerr_new, nus = aux[0], aux[1]
        tr = aux[2] if collect else None  # IterTrace, leading cluster axis
        # SolveQuality with leading cluster axis on the final pass
        qual = aux[-1] if want_q else None
        total = jnp.sum(nerr_new)
        nerr_norm = jnp.where(total > 0.0, nerr_new / total, nerr_new)
        return p_new, nerr_norm, nus, key, tr, qual

    p = p0
    nerr = jnp.zeros((M,), p0.dtype)
    weighted = jnp.asarray(False)
    nus = jnp.full((M,), config.nulow, p0.dtype)
    em_traces = []
    em_quality = None
    for em in range(config.max_emiter):
        p, nerr, nus, key, tr, qual = em_iteration(
            p, nerr, nus, weighted, em, key)
        if collect:
            em_traces.append(tr)
        if qual is not None:
            em_quality = qual
        if config.randomize:
            weighted = ~weighted
    mean_nu = jnp.clip(jnp.mean(nus), config.nulow, config.nuhigh)
    return p, mean_nu, res_0, em_traces, em_quality


def _finalize(
    data: VisData,
    cdata: ClusterData,
    p: jax.Array,
    res_0: jax.Array,
    mean_nu: jax.Array,
    config: SageConfig,
    lbfgs_trace,
    em_traces,
    em_quality,
) -> SageResult:
    """Final full-model residual plus telemetry/quality bundling — the
    tail of :func:`sagefit` after the joint LBFGS, shared with the
    batched fused driver (vmapped per lane there)."""
    robust = config.solver_mode in _ROBUST_MODES
    collect = config.collect_telemetry
    collect_q = config.collect_quality
    F, rows = data.vis.shape[-3], data.vis.shape[-1]
    nreal = rows * F * 8
    n8 = p.shape[2]

    full1 = predict_full_model(p, cdata, data)
    res_1 = _res_norm(data.vis - full1, data.mask, nreal)
    telemetry = (
        {"em": tuple(em_traces), "lbfgs": lbfgs_trace} if collect else None
    )
    quality = None
    if collect_q:
        # whole-solution bundle: chi^2 of the FULL residual (all cluster
        # models subtracted) attributed per station/baseline, plus gain
        # health over every (cluster, chunk) lane.  No hybrid-chunk
        # structure exists for the joint residual, so chi2_chunk is the
        # single total.
        from sagecal_tpu.core.types import reals_of_flat
        from sagecal_tpu.ops.quality import (
            SolveQuality, chi2_scatter, gain_health, row_chi2,
        )

        e = reals_of_flat((data.vis - full1) * data.mask[..., None, :])
        row = row_chi2(e)
        chi2_st, chi2_bl, chi2_ch = chi2_scatter(
            row, data.ant_p, data.ant_q, jnp.zeros_like(data.ant_p),
            n8 // 8, 1,
        )
        nonfinite, amp, amp_sp, ph_sp, dep = gain_health(p)
        final_q = SolveQuality(
            chi2_station=chi2_st, chi2_baseline=chi2_bl,
            chi2_chunk=chi2_ch, nonfinite_count=nonfinite,
            station_amp=amp, station_amp_spread=amp_sp,
            station_phase_spread=ph_sp, identity_departure=dep,
            nu=mean_nu if robust else None,
        )
        quality = {"em": em_quality, "final": final_q}
    return SageResult(
        p=p, res_0=res_0, res_1=res_1, mean_nu=mean_nu,
        diverged=res_1 > res_0, telemetry=telemetry, quality=quality,
    )


@true_f32
def sagefit(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    config: SageConfig = SageConfig(),
    key: Optional[jax.Array] = None,
) -> SageResult:
    """One tile's SAGE calibration.  ``p0``: (M, nchunk_max, 8N)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    M = cdata.coh.shape[0]
    nchunk_max = p0.shape[1]
    n8 = p0.shape[2]
    robust = config.solver_mode in _ROBUST_MODES
    collect = config.collect_telemetry

    p, mean_nu, res_0, em_traces, em_quality = _em_phase(
        data, cdata, p0, config, key)

    # ---- joint LBFGS over all parameters (lmfit.c:1019-1037) ----
    if config.max_lbfgs > 0:
        pflat0 = p.reshape(-1)

        if config.use_fused_predict:
            cost_fn = _make_fused_joint_cost(
                data, cdata, M, nchunk_max, n8, robust, mean_nu,
                config.coh_dtype,
            )
        else:

            def cost_fn(pflat):
                pa = pflat.reshape(M, nchunk_max, n8)
                model = predict_full_model(pa, cdata, data)
                diff = (data.vis - model) * data.mask[..., None, :]
                e2 = jnp.real(diff) ** 2 + jnp.imag(diff) ** 2
                if robust:
                    return jnp.sum(jnp.log1p(e2 / mean_nu))
                return jnp.sum(e2)

        if config.param_bound > 0.0:
            from sagecal_tpu.solvers.lbfgsb import lbfgsb_fit

            bnd = jnp.asarray(config.param_bound, pflat0.dtype)
            fitb = lbfgsb_fit(
                cost_fn, None, pflat0, lb=-bnd, ub=bnd,
                itmax=config.max_lbfgs, M=config.lbfgs_m,
            )
            p = fitb.p.reshape(M, nchunk_max, n8)
            lbfgs_trace = None  # bounded path not instrumented
        else:
            fit = lbfgs_fit(
                cost_fn, None, pflat0, itmax=config.max_lbfgs,
                M=config.lbfgs_m, collect_trace=collect,
            )
            p = fit.p.reshape(M, nchunk_max, n8)
            lbfgs_trace = fit.trace
    else:
        lbfgs_trace = None

    return _finalize(data, cdata, p, res_0, mean_nu, config, lbfgs_trace,
                     em_traces, em_quality)


@true_f32
def sagefit_batched_fused(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    config: SageConfig = SageConfig(),
    keys: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
) -> SageResult:
    """B independent tile solves whose joint-LBFGS phase runs as ONE
    batched fused Pallas kernel loop instead of B vmapped solo solves.

    The EM phase (per-cluster LM/robust solves) is the existing
    machinery vmapped per lane (:func:`_em_phase`); the joint LBFGS —
    the hot loop that dominates serve latency — then advances all lanes
    in lock-step through :func:`sagecal_tpu.solvers.lbfgs.
    lbfgs_fit_batched`, so every cost/gradient evaluation is one
    ``fused_cost_packed_batch`` grid with the lane axis folded into the
    MXU contraction (ops/rime_kernel.py section comment).

    Layout contract (solvers/batched.py): every ``data``/``cdata`` leaf
    carries a leading batch axis B; all lanes share the SAME baseline
    geometry (``ant_p``/``ant_q`` — the serve bucket guarantees this,
    and :func:`sagecal_tpu.solvers.batched.choose_batched_path` checks
    it host-side before routing here); ``p0`` is (B, M, 1, 8N) —
    nchunk_max must be 1.  ``keys``: (B, 2) per-lane PRNG keys.
    ``valid``: optional (B,) lane mask — replication-padded lanes still
    run the EM phase on their (finite, replicated) data, but their mask
    plane is zeroed in the batched cost pack so they contribute exactly
    zero cost and zero cotangent to the LBFGS phase (the ragged-lane
    guard; their lanes go inert after the first iteration and the
    results are discarded host-side as before)."""
    B, M, nchunk_max, n8 = p0.shape
    if nchunk_max != 1:
        raise ValueError(
            "sagefit_batched_fused requires nchunk_max == 1 (the batched "
            "kernel has no hybrid-chunk selection); use the vmapped path"
        )
    if config.param_bound > 0.0 or config.collect_telemetry:
        raise ValueError(
            "batched fused path supports neither param_bound nor "
            "telemetry traces; use the vmapped path"
        )
    if keys is None:
        keys = jax.random.split(jax.random.PRNGKey(0), B)
    robust = config.solver_mode in _ROBUST_MODES

    # quality side outputs (collect_quality) vmap straight through —
    # only telemetry traces are excluded (guarded above)
    p_b, mean_nu_b, res_0_b, _, em_q = jax.vmap(
        lambda d, c, p, k: _em_phase(d, c, p, config, k)
    )(data, cdata, p0, keys)

    if config.max_lbfgs > 0:
        from sagecal_tpu.solvers.lbfgs import lbfgs_fit_batched

        cost_fn = _make_fused_joint_cost_batch(
            data, cdata, B, M, n8, robust, mean_nu_b, config.coh_dtype,
            valid,
        )
        fit = lbfgs_fit_batched(
            cost_fn, p_b.reshape(B, -1), itmax=config.max_lbfgs,
            M=config.lbfgs_m,
        )
        p_b = fit.p.reshape(B, M, nchunk_max, n8)

    return jax.vmap(
        lambda d, c, p, r0, mn, eq: _finalize(d, c, p, r0, mn, config,
                                              None, [], eq)
    )(data, cdata, p_b, res_0_b, mean_nu_b, em_q)


# ------------------------------------------------ packed device boundary


def sagefit_packed(
    data: VisData,
    cdata: ClusterData,
    vis_re: jax.Array,
    vis_im: jax.Array,
    coh_re: jax.Array,
    coh_im: jax.Array,
    p0: jax.Array,
    config: SageConfig = SageConfig(),
    key: Optional[jax.Array] = None,
) -> SageResult:
    """The whole tile solve behind a REAL-array jit boundary.

    ``sagefit`` is fully traceable; this wrapper takes ``data`` with
    ``vis=None`` and ``cdata`` with ``coh=None`` plus separate re/im
    leaves (``(F, 4, rows)`` / ``(M, F, 4, rows)``, rows minor-most so
    TPU tiling pads nothing) and rebuilds the complex arrays INSIDE the
    trace, so ``jax.jit(sagefit_packed)`` dispatches the full SAGE/EM
    tile solve — EM passes, per-cluster solvers, joint LBFGS, nu
    estimation — to the TPU as ONE program.

    The re/im split was made for an earlier TPU runtime that could not
    move complex arrays between host and device.  On the v5e with
    today's runtime complex64 crosses both ways (PR 21, chip_smoke
    probe), so the split is no longer needed; its removal is queued
    (ROADMAP C2b).

    Matmul precision comes from the ``true_f32`` decorator on
    ``sagefit`` and every other solver entry (utils/precision.py)."""
    vis = jax.lax.complex(vis_re, vis_im)
    coh = jax.lax.complex(coh_re, coh_im)
    return sagefit(
        data.replace(vis=vis), cdata._replace(coh=coh), p0, config, key
    )


# instrumented jit (obs/perf.py): with SAGECAL_TELEMETRY=1 every new
# abstract input signature — a new tile shape or a changed static
# SageConfig — is visible as a recorded compile with lowering/compile
# wall-time and cost_analysis() flops/bytes; telemetry off is the plain
# jax.jit call.  ``p0`` (the tile's warm-start carry) is DONATED:
# solve_tile rebuilds it from numpy per call and the apps thread the
# RESULT p forward, never the input buffer (jaxlint JL007 convention).
_sagefit_packed_jit = instrumented_jit(sagefit_packed, name="sagefit_packed",
                                       donate_argnames=("p0",))


def solve_tile(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    config: SageConfig = SageConfig(),
    key: Optional[jax.Array] = None,
    device=None,
) -> SageResult:
    """Host convenience around :func:`sagefit_packed`: splits re/im on
    the host (numpy views, no eager device ops) and dispatches the
    jitted packed solve; on CPU this is the same math as ``sagefit``.

    ``device``: explicit target (the TPU chip while the rest of the
    pipeline runs host-side under a CPU default device — the fullbatch
    split).  Every input leaf is device_put there, including
    previously host-committed template arrays."""
    vis = np.asarray(data.vis)
    coh = np.asarray(cdata.coh)
    args = (data.replace(vis=None), cdata._replace(coh=None),
            vis.real, vis.imag, coh.real, coh.imag,
            np.asarray(p0), config, key)
    if device is not None:
        args = jax.device_put(args, device)
    return _sagefit_packed_jit(*args)
