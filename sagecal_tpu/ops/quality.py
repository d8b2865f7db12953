"""On-device calibration-quality reductions: fixed-shape side outputs.

The reference surfaces solution quality only as scattered printfs (the
per-cluster chi^2 inside ``sagefit_visibilities``, the Student's-t nu
after each EM pass) and post-hoc influence maps (``-i``,
ops/diagnostics.py).  This module turns those signals into FIXED-SHAPE
arrays computed *inside* the jitted solves so they ride out of
jit/scan/while_loop as auxiliary pytree outputs: no host callbacks,
no data-dependent shapes, statically gated (``collect_quality=False``
keeps every slot ``None``, an empty pytree, so the jitted output
signature is unchanged and enabling quality can never cost a recompile
of the disabled path).

Three reduction families:

- **chi^2 attribution** (:func:`row_chi2` + :func:`chi2_scatter`): the
  solver's own squared-residual objective, re-scattered per station /
  per baseline / per chunk.  The invariants (pinned in
  tests/test_quality.py) are exact in exact arithmetic:
  ``chi2_chunk`` == the solver's final per-chunk cost,
  ``sum(chi2_baseline) == sum(chi2_chunk)``, and
  ``sum(chi2_station) == 2 * sum(chi2_chunk)`` (every baseline row
  charges both of its stations).
- **robust-noise statistics** (:func:`weight_stats`): a fixed-bin
  histogram of the normalized Student's-t weights, the effectively
  down-weighted fraction, and the flagged fraction — the observable form
  of the reference's IRLS weights (``update_w_and_nu``).
- **gain health** (:func:`gain_health`): NaN/Inf sentinels, per-station
  amplitude and its spread across chunk lanes, circular phase spread,
  and departure-from-identity (a warm start that drifts far from its
  initialization is the round-5 bf16 divergence signature).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from sagecal_tpu.core.types import params_to_jones

# Fixed weight-histogram bin count: part of the jitted output shape, so
# it is a module constant, not a runtime knob.
WEIGHT_HIST_BINS = 16
# A normalized Student's-t weight below this marks the visibility as
# effectively down-weighted (w = (nu+1)/(nu+e^2) scaled to [0, 1]).
DOWNWEIGHT_THRESH = 0.5


class SolveQuality(NamedTuple):
    """Fixed-shape quality side outputs of one solve.

    Every field is Optional: a solver fills the subset it can see
    (robust solvers add nu/weight stats, gain health needs only ``p``)
    and leaves the rest ``None`` — ``None`` is an empty pytree, so any
    statically-chosen subset keeps a fixed jitted signature.
    """

    chi2_station: Optional[jax.Array] = None   # (N,)
    chi2_baseline: Optional[jax.Array] = None  # (N, N) upper-ish sparse
    chi2_chunk: Optional[jax.Array] = None     # (nchunk,) == solver cost
    nonfinite_count: Optional[jax.Array] = None    # () count in p
    station_amp: Optional[jax.Array] = None        # (N,) mean |J|_F/sqrt2
    station_amp_spread: Optional[jax.Array] = None   # (N,) std over lanes
    station_phase_spread: Optional[jax.Array] = None  # (N,) circular
    identity_departure: Optional[jax.Array] = None    # (N,) mean |J-I|
    nu: Optional[jax.Array] = None             # () final Student's-t nu
    weight_hist: Optional[jax.Array] = None    # (WEIGHT_HIST_BINS,)
    downweighted_frac: Optional[jax.Array] = None  # () of unflagged
    flagged_frac: Optional[jax.Array] = None       # () of all elements


def row_chi2(e: jax.Array) -> jax.Array:
    """Per-row chi^2 of a flat real residual block.

    ``e``: (..., F, 8, rows) real — exactly what
    :func:`sagecal_tpu.solvers.lm._residual_flat` produces (mask and
    sqrt-weights already applied, so this is the solver's own objective
    density).  Returns (..., rows)."""
    return jnp.sum(e * e, axis=(-3, -2))


def chi2_scatter(
    row: jax.Array,
    ant_p: jax.Array,
    ant_q: jax.Array,
    chunk_map: jax.Array,
    n_stations: int,
    n_chunks: int,
):
    """Scatter a per-row chi^2 density to stations / baselines / chunks.

    ``row``: (rows,); ``ant_p``/``ant_q``/``chunk_map``: (rows,) int.
    ``n_stations``/``n_chunks`` are static (from parameter shapes).
    Returns ``(chi2_station (N,), chi2_baseline (N, N),
    chi2_chunk (n_chunks,))``.  Padded/masked rows contribute exactly
    zero (their residual is zero), so scattering them anywhere is safe.
    """
    dt = row.dtype
    chi2_station = (
        jnp.zeros((n_stations,), dt)
        .at[ant_p].add(row)
        .at[ant_q].add(row)
    )
    chi2_baseline = jnp.zeros((n_stations, n_stations), dt).at[
        ant_p, ant_q
    ].add(row)
    chi2_chunk = jnp.zeros((n_chunks,), dt).at[chunk_map].add(row)
    return chi2_station, chi2_baseline, chi2_chunk


def weight_stats(sqrt_w: jax.Array, nu: jax.Array, mask8: jax.Array,
                 dof: float = 1.0):
    """Student's-t weight statistics for one solve.

    ``sqrt_w``: sqrt of the IRLS weights w = (nu+dof)/(nu+e^2),
    broadcastable against the (F, 8, rows) residual; ``mask8``:
    broadcastable 0/1 validity.  ``dof`` is the weight numerator offset
    — 1 for the LM family's per-element weights
    (solvers/robust.update_w_and_nu), 2 for the RTR family's
    max-over-elements weights (solvers/rtr._robust_weights_and_nu).
    Returns ``(weight_hist (WEIGHT_HIST_BINS,), downweighted_frac (),
    flagged_frac ())`` — the histogram is of the weights normalized by
    their maximum (nu+dof)/nu to [0, 1] and counts only unflagged
    elements."""
    dt = sqrt_w.dtype
    w = sqrt_w * sqrt_w
    wn = jnp.clip(w * (nu / (nu + dof)), 0.0, 1.0)
    m = jnp.broadcast_to(jnp.asarray(mask8, dt), wn.shape)
    idx = jnp.clip(
        (wn * WEIGHT_HIST_BINS).astype(jnp.int32), 0, WEIGHT_HIST_BINS - 1
    )
    hist = jnp.zeros((WEIGHT_HIST_BINS,), dt).at[idx.reshape(-1)].add(
        m.reshape(-1)
    )
    n_valid = jnp.maximum(jnp.sum(m), 1.0)
    downweighted = jnp.sum(m * (wn < DOWNWEIGHT_THRESH)) / n_valid
    flagged = 1.0 - jnp.sum(m) / m.size
    return hist, downweighted, flagged


def gain_health(p: jax.Array):
    """Gain-health metrics of a parameter block.

    ``p``: (..., 8N) real Jones parameters; all leading axes (clusters,
    hybrid chunk lanes) are treated as lanes and reduced, giving
    per-station summaries.  Returns ``(nonfinite_count (),
    station_amp (N,), station_amp_spread (N,),
    station_phase_spread (N,), identity_departure (N,))``.

    - amplitude: Frobenius norm / sqrt(2) of each 2x2 Jones (1.0 for
      identity); spread is the std across lanes.
    - phase spread: circular (1 - |mean resultant|) of the J00 phase
      across lanes — 0 for coherent lanes, -> 1 for uniformly scattered.
    - identity departure: mean ||J - I||_F / sqrt(2) across lanes; large
      values on a warm start mean the solution ran away from its
      initialization.

    Non-finite parameters are counted, then sanitized to zero before the
    summaries so a single NaN station cannot NaN-poison every reduction.
    """
    dt = p.dtype
    nonfinite = jnp.sum(~jnp.isfinite(p)).astype(dt)
    J = params_to_jones(jnp.where(jnp.isfinite(p), p, 0.0))
    lanes = J.reshape((-1,) + J.shape[-3:])  # (L, N, 2, 2)
    amp = jnp.sqrt(
        jnp.sum(jnp.abs(lanes) ** 2, axis=(-2, -1)) / 2.0
    )  # (L, N)
    station_amp = jnp.mean(amp, axis=0)
    station_amp_spread = jnp.std(amp, axis=0)
    phase = jnp.angle(lanes[..., 0, 0])  # (L, N)
    resultant = jnp.abs(
        jnp.mean(jax.lax.complex(jnp.cos(phase), jnp.sin(phase)), axis=0)
    )
    station_phase_spread = 1.0 - resultant
    eye = jnp.eye(2, dtype=lanes.dtype)
    dep = jnp.sqrt(
        jnp.sum(jnp.abs(lanes - eye) ** 2, axis=(-2, -1)) / 2.0
    )
    identity_departure = jnp.mean(dep, axis=0)
    return (nonfinite, station_amp.astype(dt),
            station_amp_spread.astype(dt),
            station_phase_spread.astype(dt), identity_departure.astype(dt))


def residual_quality(
    e: jax.Array,
    p: jax.Array,
    ant_p: jax.Array,
    ant_q: jax.Array,
    chunk_map: jax.Array,
    n_chunks: int,
    nu: Optional[jax.Array] = None,
    sqrt_w: Optional[jax.Array] = None,
    mask8: Optional[jax.Array] = None,
    weight_dof: float = 1.0,
) -> SolveQuality:
    """One-call quality bundle for the LM-family solvers.

    ``e``: the final (F, 8, rows) real residual (weights applied);
    ``p``: (..., 8N) final parameters.  Robust solvers additionally pass
    ``nu``/``sqrt_w``/``mask8`` (and ``weight_dof``, see
    :func:`weight_stats`) to fill the weight statistics."""
    n_stations = p.shape[-1] // 8
    row = row_chi2(e)
    chi2_st, chi2_bl, chi2_ch = chi2_scatter(
        row, ant_p, ant_q, chunk_map, n_stations, n_chunks
    )
    nonfinite, amp, amp_sp, ph_sp, dep = gain_health(p)
    q = SolveQuality(
        chi2_station=chi2_st, chi2_baseline=chi2_bl, chi2_chunk=chi2_ch,
        nonfinite_count=nonfinite, station_amp=amp,
        station_amp_spread=amp_sp, station_phase_spread=ph_sp,
        identity_departure=dep,
    )
    if nu is not None and sqrt_w is not None:
        hist, down, flag = weight_stats(
            sqrt_w, nu,
            mask8 if mask8 is not None else jnp.ones_like(sqrt_w),
            dof=weight_dof,
        )
        q = q._replace(nu=jnp.asarray(nu, e.dtype), weight_hist=hist,
                       downweighted_frac=down, flagged_frac=flag)
    return q
