"""ctypes harness around the REFERENCE CPU solver library (the anchor).

Builds ``libdirac_ref.so`` from the read-only reference checkout's CPU
source list (``/root/reference/src/lib/Dirac/CMakeLists.txt:8-94``; the
same objects the reference's non-CUDA ``add_library(dirac SHARED ...)``
compiles) and exposes ``sagefit_visibilities``
(``/root/reference/src/lib/Dirac/Dirac.h:1651``) to the tests.  This is
the plan-of-record end-to-end anchor (SURVEY.md §4, BASELINE.md): run the
ACTUAL reference solver on the same synthetic visibilities our framework
solves and diff the Jones solutions.

Nothing here copies reference code — the reference sources are compiled
from their mounted location into a gitignored build directory and called
through their public C API, exactly as a reference user would link
``-ldirac``.

Layout contracts verified against the reference sources:
  * ``x``: ``Nbase*tilesz`` rows x 8 doubles [XX XY YX YY] x (re, im)
    (``Dirac.h:1617-1618``);
  * ``coh``: ``complex double[4*M*row + 4*cluster + comp]``, components
    row-major [C00 C01 C10 C11] (``lmfit.c:101-105``);
  * per-station solver params: 8 doubles, the ROW-MAJOR 2x2 Jones
    re/im-interleaved [J00 J01 J10 J11] (``lmfit.c:90-97`` with the
    row-major ``amb()`` product at ``lmfit.c:37-43``) — note this is the
    in-memory solver order, NOT the solution-file S-order.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

REF_DIRAC = "/root/reference/src/lib/Dirac"
BUILD_DIR = os.path.join(os.path.dirname(__file__), "..", "native", "refbuild")
LIB_PATH = os.path.abspath(os.path.join(BUILD_DIR, "libdirac_ref.so"))

# The CPU (non-CUDA) object list from the reference's
# src/lib/Dirac/CMakeLists.txt (common `objects` + cpu `extra_objects`).
_CPU_OBJECTS = [
    "admm_solve", "clmfit", "manifold_average", "mdl", "myblas",
    "rtr_solve", "rtr_solve_robust_admm", "updatenu", "fista",
    "baseline_utils", "pngoutput",
    "lmfit", "consensus_poly", "lbfgs", "robust_batchmode_lbfgs",
    "robust_lbfgs", "robustlm", "rtr_solve_robust", "lbfgsb",
]
_BLAS = "/lib/x86_64-linux-gnu/libblas.so.3"
_LAPACK = "/lib/x86_64-linux-gnu/liblapack.so.3"


def build_ref_lib() -> str | None:
    """Compile + link the reference Dirac CPU library.  Returns the .so
    path, or None when the toolchain/reference/BLAS is unavailable (the
    anchor tests skip in that case)."""
    if os.path.exists(LIB_PATH):
        return LIB_PATH
    if not (os.path.isdir(REF_DIRAC) and os.path.exists(_BLAS)):
        return None
    os.makedirs(BUILD_DIR, exist_ok=True)
    objs = []
    try:
        for name in _CPU_OBJECTS:
            obj = os.path.join(BUILD_DIR, name + ".o")
            if not os.path.exists(obj):
                subprocess.run(
                    ["gcc", "-O2", "-fPIC", "-c",
                     os.path.join(REF_DIRAC, name + ".c"),
                     "-I", REF_DIRAC, "-o", obj],
                    check=True, capture_output=True, timeout=300,
                )
            objs.append(obj)
        subprocess.run(
            ["gcc", "-shared", "-o", LIB_PATH, *objs,
             _LAPACK, _BLAS, "-lpng", "-lpthread", "-lm"],
            check=True, capture_output=True, timeout=300,
        )
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError):
        return None
    return LIB_PATH


class BaselineT(ctypes.Structure):
    """``baseline_t`` (Dirac_common.h:190-196)."""
    _fields_ = [("sta1", ctypes.c_int), ("sta2", ctypes.c_int),
                ("flag", ctypes.c_ubyte)]


_PD = ctypes.POINTER(ctypes.c_double)


class ClusSourceT(ctypes.Structure):
    """``clus_source_t`` (Dirac_common.h:173-187).  The precomputed-
    coherency solver path reads only ``nchunk`` and ``p`` (lmfit.c:86-87;
    the reference's own MIC wrapper builds dummy structs the same way,
    lmfit.c:1223-1228); all other fields stay NULL/0."""
    _fields_ = [
        ("N", ctypes.c_int), ("id", ctypes.c_int),
        ("ll", _PD), ("mm", _PD), ("nn", _PD), ("sI", _PD),
        ("sQ", _PD), ("sU", _PD), ("sV", _PD),
        ("ra", _PD), ("dec", _PD),
        ("stype", ctypes.POINTER(ctypes.c_ubyte)),
        ("ex", ctypes.POINTER(ctypes.c_void_p)),
        ("nchunk", ctypes.c_int),
        ("p", ctypes.POINTER(ctypes.c_int)),
        ("sI0", _PD), ("sQ0", _PD), ("sU0", _PD), ("sV0", _PD),
        ("f0", _PD), ("spec_idx", _PD), ("spec_idx1", _PD),
        ("spec_idx2", _PD),
    ]


def load_lib():
    path = build_ref_lib()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    lib.sagefit_visibilities.restype = ctypes.c_int
    return lib


_COST_CB = ctypes.CFUNCTYPE(ctypes.c_double, _PD, ctypes.c_int,
                            ctypes.c_void_p)
_GRAD_CB = ctypes.CFUNCTYPE(None, _PD, _PD, ctypes.c_int, ctypes.c_void_p)


def ref_lbfgs_fit(cost, grad, p0, itmax=100, mem=7):
    """The reference's generic cost/grad-callback optimizer contract
    (``lbfgs_fit``, Dirac.h:175; demo oracle test/Dirac/demo.c):
    ``cost(p)->float`` and ``grad(p)->array`` are Python callables."""
    lib = load_lib()
    assert lib is not None
    m = len(p0)
    # copy=True: the C solver writes the solution into this buffer; a
    # no-copy pass-through would mutate the CALLER's p0 in place
    p = np.array(p0, np.float64, copy=True)

    @_COST_CB
    def c_cost(pp, mm, adata):
        arr = np.ctypeslib.as_array(pp, shape=(mm,))
        return float(cost(arr))

    @_GRAD_CB
    def c_grad(pp, gg, mm, adata):
        arr = np.ctypeslib.as_array(pp, shape=(mm,))
        g = np.ctypeslib.as_array(gg, shape=(mm,))
        g[:] = np.asarray(grad(arr), np.float64)

    lib.lbfgs_fit.restype = ctypes.c_int
    rv = lib.lbfgs_fit(c_cost, c_grad, p.ctypes.data_as(_PD),
                       ctypes.c_int(m), ctypes.c_int(itmax),
                       ctypes.c_int(mem), None, None)
    return p, rv


def ref_bfgsfit(
    u, v, w, x, nstations, nbase, tilesz, sta1, sta2, coh, m,
    p0, *, freq0=150e6, fdelta=180e3, uvmin=0.0, nthreads=1,
    max_lbfgs=20, lbfgs_m=7, solver_mode=2, mean_nu=5.0,
):
    """Run the reference ``bfgsfit_visibilities`` (Dirac.h:1683,
    lmfit.c:1126): the joint LBFGS-only multi-cluster fit — the
    joint LBFGS's per-iteration work (full-model predict + gradient
    over all 8*N*M parameters; robust Student's-t cost when
    solver_mode is one of the R-LBFGS modes).  Shapes as in
    :func:`ref_sagefit`.  Returns (jones, res_0, res_1, retval)."""
    lib = load_lib()
    assert lib is not None
    rows = nbase * tilesz
    assert x.shape == (4, rows) and coh.shape == (m, 4, rows)

    uu = np.ascontiguousarray(u, np.float64)
    vv = np.ascontiguousarray(v, np.float64)
    ww = np.ascontiguousarray(w, np.float64)
    xr = np.empty((rows, 8), np.float64)
    xr[:, 0::2] = x.real.T
    xr[:, 1::2] = x.imag.T
    xr = np.ascontiguousarray(xr.reshape(-1))

    barr = (BaselineT * rows)()
    for i in range(rows):
        barr[i].sta1 = int(sta1[i])
        barr[i].sta2 = int(sta2[i])
        barr[i].flag = 0

    coh_ref = np.ascontiguousarray(
        np.transpose(coh, (2, 0, 1)), np.complex128
    )

    n8 = 8 * nstations
    carr = (ClusSourceT * m)()
    pidx = (ctypes.c_int * m)()
    for cm in range(m):
        pidx[cm] = n8 * cm
        carr[cm].nchunk = 1
        carr[cm].p = ctypes.cast(
            ctypes.byref(pidx, cm * ctypes.sizeof(ctypes.c_int)),
            ctypes.POINTER(ctypes.c_int),
        )

    pp = np.empty((m, nstations, 4, 2), np.float64)
    flat = p0.reshape(m, nstations, 4)
    pp[..., 0] = flat.real
    pp[..., 1] = flat.imag
    pp = np.ascontiguousarray(pp.reshape(-1))

    res_0 = ctypes.c_double(0.0)
    res_1 = ctypes.c_double(0.0)
    as_pd = lambda a: a.ctypes.data_as(_PD)
    lib.bfgsfit_visibilities.restype = ctypes.c_int
    rv = lib.bfgsfit_visibilities(
        as_pd(uu), as_pd(vv), as_pd(ww), as_pd(xr),
        ctypes.c_int(nstations), ctypes.c_int(nbase), ctypes.c_int(tilesz),
        barr, carr,
        coh_ref.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(m), ctypes.c_int(m),
        ctypes.c_double(freq0), ctypes.c_double(fdelta),
        as_pd(pp), ctypes.c_double(uvmin), ctypes.c_int(nthreads),
        ctypes.c_int(max_lbfgs), ctypes.c_int(lbfgs_m),
        ctypes.c_int(128), ctypes.c_int(solver_mode),
        ctypes.c_double(mean_nu),
        ctypes.byref(res_0), ctypes.byref(res_1),
    )
    sol = pp.reshape(m, nstations, 4, 2)
    jones = (sol[..., 0] + 1j * sol[..., 1]).reshape(m, nstations, 2, 2)
    return jones, res_0.value, res_1.value, rv


def ref_sagefit(
    u, v, w, x, nstations, nbase, tilesz, sta1, sta2, coh, m,
    p0, *, freq0=150e6, fdelta=180e3, uvmin=0.0, nthreads=2,
    max_emiter=3, max_iter=10, max_lbfgs=10, lbfgs_m=7, linsolv=1,
    solver_mode=1, nulow=2.0, nuhigh=30.0, randomize=0,
):
    """Run the reference ``sagefit_visibilities`` (Dirac.h:1651).

    Args (numpy, float64/complex128, our canonical shapes):
      u, v, w: (rows,) in wavelength-seconds (multiplied by freq0 here,
        matching the reference's 1/c-then-*freq scaling).
      x: (4, rows) complex visibilities [XX XY YX YY].
      sta1, sta2: (rows,) int station indices.
      coh: (M, 4, rows) complex cluster coherencies.
      p0: (M, N, 2, 2) complex initial Jones.

    Returns (jones, mean_nu, res_0, res_1, retval):
      jones: (M, N, 2, 2) complex solved Jones (one chunk per cluster).
    """
    lib = load_lib()
    assert lib is not None
    rows = nbase * tilesz
    assert x.shape == (4, rows) and coh.shape == (m, 4, rows)

    uu = np.ascontiguousarray(u, np.float64)
    vv = np.ascontiguousarray(v, np.float64)
    ww = np.ascontiguousarray(w, np.float64)

    # x: row-major rows x [re, im]x4
    xr = np.empty((rows, 8), np.float64)
    xr[:, 0::2] = x.real.T
    xr[:, 1::2] = x.imag.T
    xr = np.ascontiguousarray(xr.reshape(-1))

    barr = (BaselineT * rows)()
    for i in range(rows):
        barr[i].sta1 = int(sta1[i])
        barr[i].sta2 = int(sta2[i])
        barr[i].flag = 0

    # coh[4*M*row + 4*cm + comp]
    coh_ref = np.ascontiguousarray(
        np.transpose(coh, (2, 0, 1)), np.complex128
    )  # (rows, M, 4)

    n8 = 8 * nstations
    carr = (ClusSourceT * m)()
    pidx = (ctypes.c_int * m)()
    for cm in range(m):
        pidx[cm] = n8 * cm
        carr[cm].nchunk = 1
        carr[cm].p = ctypes.cast(
            ctypes.byref(pidx, cm * ctypes.sizeof(ctypes.c_int)),
            ctypes.POINTER(ctypes.c_int),
        )

    # p: per cluster, per station: row-major J re/im interleaved
    pp = np.empty((m, nstations, 4, 2), np.float64)
    jr = p0.reshape(m, nstations, 2, 2)
    flat = jr.reshape(m, nstations, 4)  # row-major J00,J01,J10,J11
    pp[..., 0] = flat.real
    pp[..., 1] = flat.imag
    pp = np.ascontiguousarray(pp.reshape(-1))

    mean_nu = ctypes.c_double(0.0)
    res_0 = ctypes.c_double(0.0)
    res_1 = ctypes.c_double(0.0)

    as_pd = lambda a: a.ctypes.data_as(_PD)
    rv = lib.sagefit_visibilities(
        as_pd(uu), as_pd(vv), as_pd(ww), as_pd(xr),
        ctypes.c_int(nstations), ctypes.c_int(nbase), ctypes.c_int(tilesz),
        barr, carr,
        coh_ref.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(m), ctypes.c_int(m),
        ctypes.c_double(freq0), ctypes.c_double(fdelta),
        as_pd(pp), ctypes.c_double(uvmin), ctypes.c_int(nthreads),
        ctypes.c_int(max_emiter), ctypes.c_int(max_iter),
        ctypes.c_int(max_lbfgs), ctypes.c_int(lbfgs_m),
        ctypes.c_int(128), ctypes.c_int(linsolv),
        ctypes.c_int(solver_mode),
        ctypes.c_double(nulow), ctypes.c_double(nuhigh),
        ctypes.c_int(randomize),
        ctypes.byref(mean_nu), ctypes.byref(res_0), ctypes.byref(res_1),
    )

    sol = pp.reshape(m, nstations, 4, 2)
    jones = (sol[..., 0] + 1j * sol[..., 1]).reshape(m, nstations, 2, 2)
    return jones, mean_nu.value, res_0.value, res_1.value, rv
