"""Rows-sharded (data-parallel) joint calibration over a device mesh.

The reference never shards a single solve — one cluster solve always
fits one machine, and scale comes from tiling time and splitting
frequency (SURVEY §2.5).  On TPU the natural extra axis is the DATA
axis: visibility rows (baseline x time) shard across devices, the
per-shard robust cost and its gradient reduce with ``lax.psum``, and
the joint LBFGS iterates on replicated parameters — gradients are sums
over baselines (the structure the reference's ``mderiv.cu`` gradient
kernels exploit per-thread), so the collective is one scalar + one
(8*N*M,) vector per evaluation, riding ICI.

This is the TPU-native path to a SINGLE tile too large for one chip's
HBM (e.g. SKA-scale 512 stations x hundreds of clusters: the coherency
stack shards with the rows axis).

``shard_map`` with full varying-manual-axes checking; the LBFGS loop
runs replicated on every device (its work is O(M*8N) — negligible
against the sharded model evaluation).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sagecal_tpu.core.types import VisData
from sagecal_tpu.obs.perf import instrumented_jit
from sagecal_tpu.ops.quality import SolveQuality, chi2_scatter, gain_health
from sagecal_tpu.solvers.lbfgs import lbfgs_fit
from sagecal_tpu.solvers.sage import ClusterData, predict_full_model


def _row_spec(leaf, name: str, rows: int, axis_name: str):
    """PartitionSpec sharding the (minor-most) rows axis of a named
    per-row field.  Specs are built per FIELD NAME, never by matching
    dimension sizes — a non-row leaf whose last dim coincidentally
    equals the row count (e.g. ``nchunk`` of shape (M,) when M == rows)
    must stay replicated or the psum'd cost/grad would be wrong."""
    if leaf.shape[-1] != rows:
        raise ValueError(
            f"per-row field {name!r} must be rows-minor with "
            f"shape[-1]=={rows}, got {leaf.shape}"
        )
    return P(*([None] * (leaf.ndim - 1)), axis_name)


# The per-row fields of each container (rows minor-most, core/types.py).
# Single source of truth for both sharding specs and row padding.
_VIS_ROW_FIELDS = ("u", "v", "w", "ant_p", "ant_q", "vis", "mask",
                   "time_idx")
_CDATA_ROW_FIELDS = ("coh", "chunk_map")


def _build_specs(data: VisData, cdata: ClusterData, rows: int,
                 axis_name: str):
    """Spec pytrees for (VisData, ClusterData) with exactly the known
    per-row fields sharded (``_VIS_ROW_FIELDS`` / ``_CDATA_ROW_FIELDS``).
    freqs (F,) and nchunk (M,) stay replicated."""
    data_specs = data.replace(freqs=P(), **{
        f: _row_spec(getattr(data, f), f, rows, axis_name)
        for f in _VIS_ROW_FIELDS})
    cdata_specs = cdata._replace(nchunk=P(), **{
        f: _row_spec(getattr(cdata, f), f, rows, axis_name)
        for f in _CDATA_ROW_FIELDS})
    return data_specs, cdata_specs


def pad_rows_to(data: VisData, cdata: ClusterData, mult: int):
    """Pad the rows axis to a multiple of ``mult`` with masked rows
    (zero coherency, zero mask -> zero contribution everywhere)."""
    rows = data.vis.shape[-1]
    rowsp = -(-rows // mult) * mult
    pr = rowsp - rows
    if pr == 0:
        return data, cdata

    def pad_last(x):
        cfg = [(0, 0)] * (x.ndim - 1) + [(0, pr)]
        return jnp.pad(x, cfg)

    data = data.replace(**{
        f: pad_last(getattr(data, f)) for f in _VIS_ROW_FIELDS})
    cdata = cdata._replace(**{
        f: pad_last(getattr(cdata, f)) for f in _CDATA_ROW_FIELDS})
    return data, cdata


def make_sharded_joint_fn(
    data,
    cdata,
    p_shape: tuple,
    mesh: Mesh,
    axis_name: str = "rows",
    itmax: int = 30,
    lbfgs_m: int = 7,
    robust_nu: Optional[float] = None,
    collect_quality: bool = False,
):
    """Build the jitted rows-sharded joint-LBFGS program.

    ``data``/``cdata`` may be real arrays OR ``jax.ShapeDtypeStruct``
    pytrees (only shapes/dtypes are read here) — the latter enables AOT
    ``.lower().compile()`` at scale without materializing the arrays
    (the graded-config memory checks, tests/test_graded_shapes.py).
    Returns ``fn(data, cdata, p0) -> (p, cost, iterations)``, or
    ``(p, cost, iterations, quality)`` with ``collect_quality`` — a
    static build parameter, so the two variants are distinct programs
    and the disabled path's signature is untouched.  ``quality`` is an
    :class:`sagecal_tpu.ops.quality.SolveQuality` whose chi^2
    attribution uses the joint objective density (``e^2``, or
    ``log1p(e^2/nu)`` on the robust path) so the station/baseline sums
    and the total reproduce ``cost`` exactly; the per-shard scatters are
    psum'd across the mesh, the same one-collective-per-reduction
    pattern as the solve itself.
    """
    ndev = mesh.devices.size
    rows = data.vis.shape[-1]
    assert rows % ndev == 0, (rows, ndev)
    shp = tuple(p_shape)

    data_specs, cdata_specs = _build_specs(data, cdata, rows, axis_name)

    def local_fit(data_l, cdata_l, p0_l):
        def local_cost(pflat):
            pa = pflat.reshape(shp)
            model = predict_full_model(pa, cdata_l, data_l)
            diff = (data_l.vis - model) * data_l.mask[..., None, :]
            e2 = jnp.real(diff) ** 2 + jnp.imag(diff) ** 2
            if robust_nu is not None:
                return jnp.sum(jnp.log1p(e2 / robust_nu))
            return jnp.sum(e2)

        def cost_fn(pflat):
            return jax.lax.psum(local_cost(pflat), axis_name)

        # The gradient must be psum'd EXPLICITLY: differentiating through
        # a psum'd cost transposes the psum into a device-local
        # cotangent, so value_and_grad(cost_fn) would hand each device
        # only its own shard's gradient — per-device LBFGS trajectories
        # then diverge, and the data-dependent Armijo while_loop executes
        # different psum counts per device (an XLA collective-rendezvous
        # deadlock).  One psum of the (value, grad) tuple per evaluation
        # keeps every device on the identical global iterate.
        def vg_fn(pflat):
            return jax.lax.psum(
                jax.value_and_grad(local_cost)(pflat), axis_name
            )

        fit = lbfgs_fit(cost_fn, None, p0_l.reshape(-1), itmax=itmax,
                        M=lbfgs_m, vg_fn=vg_fn)
        pf = fit.p.reshape(shp)
        if not collect_quality:
            return pf, fit.cost, fit.iterations
        # objective density of the final iterate, scattered per station/
        # baseline on each shard's local rows, then psum'd — sums equal
        # fit.cost exactly (it is the same reduction, reassociated)
        model = predict_full_model(pf, cdata_l, data_l)
        diff = (data_l.vis - model) * data_l.mask[..., None, :]
        e2 = jnp.real(diff) ** 2 + jnp.imag(diff) ** 2
        dens = jnp.log1p(e2 / robust_nu) if robust_nu is not None else e2
        row = jnp.sum(dens, axis=(-3, -2))  # (rows_local,)
        n_st = shp[-1] // 8
        chi2_st, chi2_bl, chi2_tot = chi2_scatter(
            row, data_l.ant_p, data_l.ant_q,
            jnp.zeros_like(data_l.ant_p), n_st, 1,
        )
        chi2_st, chi2_bl, chi2_tot = jax.lax.psum(
            (chi2_st, chi2_bl, chi2_tot), axis_name
        )
        nonfinite, amp, amp_sp, ph_sp, dep = gain_health(pf)
        quality = SolveQuality(
            chi2_station=chi2_st, chi2_baseline=chi2_bl,
            chi2_chunk=chi2_tot, nonfinite_count=nonfinite,
            station_amp=amp, station_amp_spread=amp_sp,
            station_phase_spread=ph_sp, identity_departure=dep,
        )
        return pf, fit.cost, fit.iterations, quality

    out_specs = (P(), P(), P())
    if collect_quality:
        # replicated specs for exactly the fields local_fit fills; the
        # rest stay None (empty pytree) and need no spec
        out_specs = out_specs + (SolveQuality(
            chi2_station=P(), chi2_baseline=P(), chi2_chunk=P(),
            nonfinite_count=P(), station_amp=P(), station_amp_spread=P(),
            station_phase_spread=P(), identity_departure=P(),
        ),)
    fn = jax.shard_map(
        local_fit,
        mesh=mesh,
        in_specs=(data_specs, cdata_specs, P()),
        out_specs=out_specs,
    )
    return instrumented_jit(fn, name="sharded_joint_fit")


def sharded_joint_fit(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    mesh: Mesh,
    axis_name: str = "rows",
    itmax: int = 30,
    lbfgs_m: int = 7,
    robust_nu: Optional[float] = None,
    collect_quality: bool = False,
):
    """Joint LBFGS over all clusters with rows sharded over ``mesh``.

    ``p0``: (M, nchunk, 8N).  Returns (p, cost, iterations) with ``p``
    replicated — plus a psum'd :class:`SolveQuality` as a fourth element
    when ``collect_quality`` (see :func:`make_sharded_joint_fn`).  Rows
    must divide evenly by the mesh size — use :func:`pad_rows_to` first.
    """
    fn = make_sharded_joint_fn(
        data, cdata, p0.shape, mesh, axis_name=axis_name, itmax=itmax,
        lbfgs_m=lbfgs_m, robust_nu=robust_nu,
        collect_quality=collect_quality,
    )
    from sagecal_tpu.obs.trace import get_tracer

    tr = get_tracer()
    if not tr.enabled:
        return fn(data, cdata, p0)
    # host-side collective-section span around the dispatch (never
    # inside the jitted program).  Unlike the mesh ADMM there is no
    # prepare/solve pipeline to overlap here, so blocking inside the
    # span is safe and makes it cover real device wall-time.
    with tr.span("sharded_joint_fit", kind="collective",
                 ndev=int(mesh.devices.size),
                 rows=int(data.vis.shape[-1])):
        return jax.block_until_ready(fn(data, cdata, p0))
