"""Plain reference of multi-band consensus calibration (sagecal-mpi), in
numpy float64.  It imports nothing of the program; the measurement
equation of each band is ``rime_point_ref.py``'s, loaded by path.

What a consensus run must give, written from its published description
(Yatawatta 2015, "Distributed radio interferometric calibration";
SAGECal's consensus_poly.c for the frequency bases):

- per band f and channel c, the residual the program writes is
  V_fc - sum_k c_k(f_c) J_fkp J_fkq^H, J_f the band's written solution
  and c_k(f_c) the cluster's coherency at the channel's frequency,
  smeared over one channel;
- the consensus Z (per cluster, Npoly x 8N real parameters) ties the
  band solutions to a polynomial in frequency: J_f ~ sum_p B_fp Z_p.
  With equal penalties for every band, the Z it converges to is the
  least-squares fit of that polynomial through the band solutions.

Solution parameters are real 8-vectors per station in the order of the
solution files: Re, Im of J00, J10, J01, J11.
"""

from __future__ import annotations

import importlib.util
import math
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "rime_point_ref", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "rime_point_ref.py"))
_rime = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_rime)

C0 = _rime.C0
coherencies = _rime.coherencies
read_point_sky = _rime.read_point_sky
model = _rime.model

POLY_ORDINARY, POLY_NORMALIZED, POLY_BERNSTEIN = 0, 1, 2


def basis(freqs, f0, npoly, ptype):
    """(Nf, npoly) frequency basis B_fp: ordinary powers of (f - f0)/f0,
    the same with unit-norm columns, or Bernstein polynomials of degree
    npoly - 1 on x = (f - fmin)/(fmax - fmin)."""
    freqs = np.asarray(freqs, np.float64)
    B = np.empty((freqs.size, npoly))
    if ptype in (POLY_ORDINARY, POLY_NORMALIZED):
        x = (freqs - f0) / f0
        for p in range(npoly):
            B[:, p] = x ** p
        if ptype == POLY_NORMALIZED:
            B /= np.linalg.norm(B, axis=0)
    elif ptype == POLY_BERNSTEIN:
        x = (freqs - freqs.min()) / (freqs.max() - freqs.min())
        n = npoly - 1
        for p in range(npoly):
            B[:, p] = math.comb(n, p) * x ** p * (1.0 - x) ** (n - p)
    else:
        raise ValueError(f"reference: polynomial type {ptype}")
    return B


def params(jones):
    """(..., N, 2, 2) complex -> (..., 8N) real, the solution order."""
    z = np.stack([jones[..., 0, 0], jones[..., 1, 0], jones[..., 0, 1],
                  jones[..., 1, 1]], axis=-1)
    s = np.stack([z.real, z.imag], axis=-1)
    return s.reshape(s.shape[:-3] + (-1,))


def read_global_z(path):
    """The consensus solution file -> (intervals, K, npoly, 8N): a header
    line 'freq0 npoly N clusters effective_clusters', then per interval
    npoly * 8N rows 'p value...' with row p = poly * 8N + parameter and
    the effective clusters' columns in reverse order."""
    header, rows = None, []
    with open(path) as f:
        for line in f:
            s = line.strip()
            if not s or s.startswith("#"):
                continue
            if header is None:
                header = s.split()
                continue
            rows.append([float(x) for x in s.split()[1:]])
    npoly, N = int(header[1]), int(header[2])
    a = np.asarray(rows, np.float64)
    per = npoly * 8 * N
    nint = a.shape[0] // per
    a = a[: nint * per].reshape(nint, npoly, 8 * N, -1)[..., ::-1]
    return np.moveaxis(a, 3, 1)  # (nint, K, npoly, 8N)


def z_fit(J, B):
    """Least-squares Z (K, npoly, 8N) of J_f ~ sum_p B_fp Z_p through the
    band solutions J (Nf, K, 8N)."""
    Nf, K, P8 = J.shape
    Z, *_ = np.linalg.lstsq(B, J.reshape(Nf, K * P8), rcond=None)
    return np.moveaxis(Z.reshape(B.shape[1], K, P8), 0, 1)


def z_gap(Z, J, B):
    """How far the written consensus Z lies from the least-squares fit
    through the written band solutions, relative to the fit's size."""
    Zf = z_fit(J, B)
    return float(np.linalg.norm(Z - Zf) / np.linalg.norm(Zf))


def resid_gap(V, R_app, coh, jones, ant_p, ant_q, precision="f64"):
    """The largest gap, over channels, between a written residual and
    V - model(J), over that channel's rms of V.  V, R_app (rows, F, 2,
    2); coh (K, F, rows) at the channels' frequencies."""
    R_ref = V - model(coh, jones, ant_p, ant_q, precision=precision)
    gaps = [np.max(np.abs(R_app[:, c] - R_ref[:, c]))
            / math.sqrt(np.mean(np.abs(V[:, c]) ** 2))
            for c in range(V.shape[1])]
    return float(max(gaps)), R_ref


def misfit(V, coh, jones, ant_p, ant_q, rows):
    """Mean squared misfit of a solution on the fitted rows."""
    return float(np.mean(np.abs((V - model(coh, jones, ant_p, ant_q))[rows])
                         ** 2))
