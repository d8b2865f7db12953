"""Fused RIME predict Pallas kernel — the hot op of joint calibration.

Computes ``V(f, r) = sum_m Jp^m(r) C^m(f, r) Jq^m(r)^H`` (the full-model
predict of ``minimize_viz_full_pth``, ``/root/reference/src/lib/Dirac/
lmfit.c:692``; CUDA analog ``predict_model.cu``) in ONE pass over the
coherency stack.

Why a kernel: the XLA formulation in :func:`sagecal_tpu.solvers.sage.
predict_full_model` (one-hot gain matmuls + sixteen multiply-reduce
contractions) materializes ~15 buffer-scale intermediates in HBM —
measured 95 ms per forward at the north-star shape (62 stn / 100
clusters / 60 ts x 2 ch), an effective 8 GB/s against the 726 MB
coherency stack vs the chip's 819 GB/s.  The fused kernel streams each
coherency block through VMEM exactly once.

Grid design: ONE grid dimension over row tiles.  The full cluster axis
rides inside each block — at the north-star shape a (104, 2, 8, 512)
f32 coherency block is 3.4 MB, comfortably inside VMEM — so the forward
writes each output block exactly once (no cross-step accumulation) and
the kernel body is straight-line VPU/MXU code:

1. build the station one-hot selectors from the tile's antenna indices,
2. expand per-row gains with one MXU matmul per 2x2 component
   ``(Mp, NPAD) @ (NPAD, T)`` (component-major tables: no sublane
   reshapes anywhere in the nc=1 kernel bodies),
3. evaluate the 2x2 RIME products ``Jp (C Jq^H)`` as component
   arithmetic on ``(Mp, T)`` vregs, reduce over clusters, store.

The backward kernel has the same structure and accumulates gain-table
cotangents across row tiles (``dtab += dJ @ onehot^T`` — the reference's
``mderiv.cu`` role); both are wired into :func:`fused_predict_packed`
with ``jax.custom_vjp``.  Gradients flow to the gain tables only: the
solver never differentiates w.r.t. coherencies (per-tile constants, like
the reference's precalculated ``coh`` array).

On top of the predict, :func:`fused_cost_packed` fuses the ENTIRE
objective — predict, masked residual, Student's-t (or Gaussian)
weighting, and the scalar reduction — into the same single pass, so a
``value_and_grad`` never streams a model-sized buffer to or from HBM
(see the "fused objective" section below).

Everything crosses the kernel boundary as REAL arrays (re/im packed on
a leading axis): Mosaic kernels take no complex operands, and packed
reals keep every buffer's minor-most axis long (rows), so the TPU
(8, 128) tiling pads nothing (core/types.py layout rationale).
Gain tables and outputs are f32; ``coh_ri`` may be f32 or bfloat16 —
bf16 planes are upcast to f32 at the VMEM load (``_load_coh_planes``),
halving the dominant HBM stream at ~3 significant digits of coherency
precision (a throughput knob, not the production default).

Layout contracts:
  tab_re/tab_im: (4, Mp*nc, NPAD) component-major gain tables — plane k
    holds 2x2 component k (row-major [J00, J01, J10, J11]) for every
    (cluster, chunk) row ``m*nc + c``; Mp = clusters padded to a
    multiple of 8 (sublane alignment), NPAD = stations padded to 128.
  coh_ri: (Mp, F, 8, rowsp) packed coherencies, component axis
    [re XX, re XY, re YX, re YY, im XX, im XY, im YX, im YY].
  ant_p/ant_q: (1, rowsp) int32 station index per row.
  output model_ri: (F, 8, rowsp), same component packing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NPAD = 128  # station axis padded to one MXU/VPU lane tile
DEF_TILE = 512  # rows per grid step


def _use_interpret() -> bool:
    """Pallas interpret mode runs the kernels on the CPU (tests); on a
    TPU they compile.  No other backend runs them."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for the TPU and interpret only "
            f"on the CPU; the default backend is {backend!r}")
    return backend == "cpu"


def _sel_dot(t, oh):
    """Selection matmul ``t @ oh`` (``oh`` 0/1 one-hot) in exact f32.

    The TPU MXU multiplies f32 as bf16 passes by default, rounding
    every selected value to ~3 digits (measured 3.7e-3 rel error at
    the kernel output on the v5e) — enough to diverge warm-started
    calibration tiles.  Precision.HIGHEST restores exact f32 (1e-7).
    A 2-pass hi/lo split (exact selections, 4.8e-6 rel) was tried and
    MEASURED SLOWER whole-bench (28.8 vs 32.7 it/s): the VPU
    decomposition costs more than the four MXU passes it saves, on
    either operand size.  Mosaic does not support Precision.HIGH."""
    return jnp.dot(t, oh, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _expand_gains(tabre_ref, tabim_ref, oh, mp, T, nc=1, cmap=None):
    """(4, Mp*nc, NPAD) component-major tables x (NPAD, T) one-hot ->
    4 re + 4 im (Mp, T) per-row gain components, one MXU selection per
    component (see _sel_dot) — NO sublane reshapes in the nc=1 path
    (kept Mosaic-friendly on purpose: minor-dim relayouts are a prime
    suspect in the remote-compile stall documented in the verify
    skill).

    ``nc > 1`` is the reference's hybrid time-chunk mode (one solution
    per chunk of the tile, lmfit.c:86-87): the tables carry one row
    block per (cluster, chunk) and ``cmap`` (Mp, T) selects each row's
    chunk — a static unrolled select over the (small) chunk count."""
    re, im = [], []
    if nc == 1:
        for k in range(4):
            re.append(_sel_dot(tabre_ref[k], oh))
            im.append(_sel_dot(tabim_ref[k], oh))
        return re, im
    sels = [(cmap == c).astype(jnp.float32) for c in range(nc)]  # (Mp, T)
    for k in range(4):
        g_re = _sel_dot(tabre_ref[k], oh)
        g_im = _sel_dot(tabim_ref[k], oh)
        gr = g_re.reshape(mp, nc, T)  # leading-dim split only
        gi = g_im.reshape(mp, nc, T)
        acc_r = acc_i = 0.0
        for c in range(nc):
            acc_r = acc_r + sels[c] * gr[:, c, :]
            acc_i = acc_i + sels[c] * gi[:, c, :]
        re.append(acc_r)
        im.append(acc_i)
    return re, im


def _rowsum_dot(a, b):
    """(Mp', T) x (NPAD, T) -> (Mp', NPAD), contracting T — dot_general
    with the contraction on the trailing dims so no transpose op is
    ever materialized.  Precision.HIGHEST, NOT the _sel_dot hi/lo
    trick: here the split would run on the big (Mp, T) cotangent
    operand, and the VPU decomposition costs more than the four MXU
    passes it saves (measured 27.4 vs 32.7 it/s whole-bench on the
    v5e).  HIGHEST keeps the accumulated gain-table cotangents exact
    f32."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _chunk_route(dj, mp, T, nc, sels):
    """Route one component's per-row cotangent (Mp, T) to its
    per-(cluster, chunk) rows (Mp*nc, T) for the hybrid mode.
    ``sels``: pre-computed chunk-selector masks (hoisted by the caller
    so the 16 uses per backward body don't re-trace nc compares)."""
    if nc == 1:
        return dj
    parts = [(sels[c] * dj)[:, None, :] for c in range(nc)]
    return jnp.concatenate(parts, axis=1).reshape(mp * nc, T)


def _cjqh(c_re, c_im, q_re, q_im):
    """A = C Jq^H on (Mp, T) components: A_aj = sum_b C_ab conj(Jq_jb);
    2x2 index ab = 2a+b.  Shared by the forward products and by the
    backward pass (which caches A for the cotangent contractions)."""
    a_re, a_im = {}, {}
    for a in range(2):
        for j in range(2):
            re = im = 0.0
            for b in range(2):
                cr, ci = c_re[2 * a + b], c_im[2 * a + b]
                qr, qi = q_re[2 * j + b], q_im[2 * j + b]
                re = re + cr * qr + ci * qi
                im = im + ci * qr - cr * qi
            a_re[a, j], a_im[a, j] = re, im
    return a_re, a_im


def _jp_a(p_re, p_im, a_re, a_im):
    """V = Jp A: V_ij = sum_a Jp_ia A_aj.  Returns the 8 packed planes
    [reXX..reYY, imXX..imYY] BEFORE the cluster reduction."""
    v_re, v_im = [None] * 4, [None] * 4
    for i in range(2):
        for j in range(2):
            re = im = 0.0
            for a in range(2):
                pr, pi = p_re[2 * i + a], p_im[2 * i + a]
                ar, ai = a_re[a, j], a_im[a, j]
                re = re + pr * ar - pi * ai
                im = im + pr * ai + pi * ar
            v_re[2 * i + j], v_im[2 * i + j] = re, im
    return v_re, v_im


def _rime_products(c_re, c_im, p_re, p_im, q_re, q_im):
    """V = Jp (C Jq^H) expanded on (Mp, T) components."""
    a_re, a_im = _cjqh(c_re, c_im, q_re, q_im)
    return _jp_a(p_re, p_im, a_re, a_im)


def _onehots(antp_ref, antq_ref, T):
    n_iota = jax.lax.broadcasted_iota(jnp.int32, (NPAD, T), 0)
    ohp = (n_iota == antp_ref[:]).astype(jnp.float32)
    ohq = (n_iota == antq_ref[:]).astype(jnp.float32)
    return ohp, ohq


def _load_coh_planes(coh_ref, f):
    """Load one frequency's 4 re + 4 im coherency planes, upcasting to
    f32 at the VMEM load so a bfloat16 coherency stack (halved HBM
    stream — the bandwidth-bound knob) computes in full f32."""
    c_re = [coh_ref[:, f, k, :].astype(jnp.float32) for k in range(4)]
    c_im = [coh_ref[:, f, 4 + k, :].astype(jnp.float32) for k in range(4)]
    return c_re, c_im


def _fwd_store(coh_ref, out_ref, p_re, p_im, q_re, q_im, F):
    # per-plane (1, T) slice stores — no stack/concatenate relayouts
    for f in range(F):
        c_re, c_im = _load_coh_planes(coh_ref, f)
        v_re, v_im = _rime_products(c_re, c_im, p_re, p_im, q_re, q_im)
        for k in range(4):
            out_ref[f, k:k + 1, :] = jnp.sum(v_re[k], axis=0, keepdims=True)
            out_ref[f, 4 + k:5 + k, :] = jnp.sum(v_im[k], axis=0,
                                                 keepdims=True)


def _fwd_kernel(antp_ref, antq_ref, tabre_ref, tabim_ref, coh_ref, out_ref,
                *, F, MP, T):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T)
    _fwd_store(coh_ref, out_ref, p_re, p_im, q_re, q_im, F)


def _fwd_kernel_hybrid(antp_ref, antq_ref, cmap_ref, tabre_ref, tabim_ref,
                       coh_ref, out_ref, *, F, MP, T, NC):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    cmap = cmap_ref[:]
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T, NC, cmap)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T, NC, cmap)
    _fwd_store(coh_ref, out_ref, p_re, p_im, q_re, q_im, F)


def _shape_args(tab_re, coh_ri, tile, nc):
    four, mrows, npad = tab_re.shape
    Mp, F, _, rowsp = coh_ri.shape
    assert four == 4 and npad == NPAD and mrows == Mp * nc and Mp % 8 == 0
    assert rowsp % tile == 0, (rowsp, tile)
    return Mp, F, rowsp, rowsp // tile


def _row_spec(tile):
    return pl.BlockSpec((1, tile), lambda r: (0, r), memory_space=pltpu.VMEM)


def _tab_spec(nrows):
    # component-major (4, Mp*nc, NPAD)
    return pl.BlockSpec((4, nrows, NPAD), lambda r: (0, 0, 0),
                        memory_space=pltpu.VMEM)


def _coh_spec(Mp, F, tile):
    return pl.BlockSpec((Mp, F, 8, tile), lambda r: (0, 0, 0, r),
                        memory_space=pltpu.VMEM)


def _cmap_spec(Mp, tile):
    return pl.BlockSpec((Mp, tile), lambda r: (0, r),
                        memory_space=pltpu.VMEM)


def _fused_predict_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q, *, tile,
                            nc=1, cmap=None):
    Mp, F, rowsp, R = _shape_args(tab_re, coh_ri, tile, nc)
    if nc == 1:
        kernel = functools.partial(_fwd_kernel, F=F, MP=Mp, T=tile)
        specs = [_row_spec(tile), _row_spec(tile),
                 _tab_spec(Mp), _tab_spec(Mp), _coh_spec(Mp, F, tile)]
        args = (ant_p, ant_q, tab_re, tab_im, coh_ri)
    else:
        kernel = functools.partial(_fwd_kernel_hybrid, F=F, MP=Mp, T=tile,
                                   NC=nc)
        specs = [_row_spec(tile), _row_spec(tile), _cmap_spec(Mp, tile),
                 _tab_spec(Mp * nc), _tab_spec(Mp * nc),
                 _coh_spec(Mp, F, tile)]
        args = (ant_p, ant_q, cmap, tab_re, tab_im, coh_ri)
    return pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=specs,
        out_specs=pl.BlockSpec((F, 8, tile), lambda r: (0, 0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((F, 8, rowsp), jnp.float32),
        interpret=_use_interpret(),
    )(*args)


# ---------------------------------------------------------------- backward


def _g_from_ref(g_ref):
    """Predict-kernel cotangent source: the upstream model cotangent is
    an HBM buffer streamed in per grid step; read frequency f's 4 re +
    4 im (1, T) planes."""
    def g_of(f, c_re, c_im, a_re, a_im):
        del c_re, c_im, a_re, a_im
        return ([g_ref[f, k:k + 1, :] for k in range(4)],
                [g_ref[f, 4 + k:5 + k, :] for k in range(4)])
    return g_of


def _bwd_accumulate(coh_ref, g_of, p_re, p_im, q_re, q_im, F, MP, T):
    """Per-row gain cotangents dJp/dJq (4 x (MP, T) re/im each),
    accumulated over freq.  ``g_of(f, c_re, c_im, a_re, a_im)`` supplies
    frequency f's model cotangent as 4 re + 4 im (1, T) planes — either
    read from an HBM cotangent buffer (predict kernel, :func:`_g_from_
    ref`) or formed in-register from the residual (objective kernel,
    which never materializes the model or residual in HBM)."""
    djp_re = [jnp.zeros((MP, T), jnp.float32) for _ in range(4)]
    djp_im = [jnp.zeros((MP, T), jnp.float32) for _ in range(4)]
    djq_re = [jnp.zeros((MP, T), jnp.float32) for _ in range(4)]
    djq_im = [jnp.zeros((MP, T), jnp.float32) for _ in range(4)]

    for f in range(F):
        c_re, c_im = _load_coh_planes(coh_ref, f)
        a_re, a_im = _cjqh(c_re, c_im, q_re, q_im)  # reused by g_of
        g_re, g_im = g_of(f, c_re, c_im, a_re, a_im)

        # dJp_ia += sum_j g_ij * conj(A_aj)
        for i in range(2):
            for a in range(2):
                re = im = 0.0
                for j in range(2):
                    gr, gi = g_re[2 * i + j], g_im[2 * i + j]
                    ar, ai = a_re[a, j], a_im[a, j]
                    re = re + gr * ar + gi * ai
                    im = im + gi * ar - gr * ai
                djp_re[2 * i + a] = djp_re[2 * i + a] + re
                djp_im[2 * i + a] = djp_im[2 * i + a] + im

        # dA_aj = sum_i conj(Jp_ia) g_ij ; dJq_jb += sum_a conj(dA_aj) C_ab
        da_re, da_im = {}, {}
        for a in range(2):
            for j in range(2):
                re = im = 0.0
                for i in range(2):
                    pr, pi = p_re[2 * i + a], p_im[2 * i + a]
                    gr, gi = g_re[2 * i + j], g_im[2 * i + j]
                    re = re + pr * gr + pi * gi
                    im = im + pr * gi - pi * gr
                da_re[a, j], da_im[a, j] = re, im
        for j in range(2):
            for b in range(2):
                re = im = 0.0
                for a in range(2):
                    dr, di = da_re[a, j], da_im[a, j]
                    cr, ci = c_re[2 * a + b], c_im[2 * a + b]
                    re = re + dr * cr + di * ci
                    im = im + dr * ci - di * cr
                djq_re[2 * j + b] = djq_re[2 * j + b] + re
                djq_im[2 * j + b] = djq_im[2 * j + b] + im

    return (djp_re, djp_im), (djq_re, djq_im)


def _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, MP, T, nc=1,
               cmap=None):
    """Scatter per-row gain cotangents to table rows, one component at
    a time: dtab[k] += dJ_k (Mp*nc, T) contracted with the one-hot over
    T (dot_general on trailing dims — no transpose op), accumulated
    over row tiles via the revisited (4, Mp*nc, NPAD) output block."""
    r = pl.program_id(0)
    sels = (None if nc == 1 else
            [(cmap == c).astype(jnp.float32) for c in range(nc)])
    for k in range(4):
        dre = (_rowsum_dot(_chunk_route(djp[0][k], MP, T, nc, sels), ohp)
               + _rowsum_dot(_chunk_route(djq[0][k], MP, T, nc, sels), ohq))
        dim = (_rowsum_dot(_chunk_route(djp[1][k], MP, T, nc, sels), ohp)
               + _rowsum_dot(_chunk_route(djq[1][k], MP, T, nc, sels), ohq))

        @pl.when(r == 0)
        def _init(dre=dre, dim=dim, k=k):
            dtabre_ref[k] = dre
            dtabim_ref[k] = dim

        @pl.when(r != 0)
        def _acc(dre=dre, dim=dim, k=k):
            dtabre_ref[k] = dtabre_ref[k] + dre
            dtabim_ref[k] = dtabim_ref[k] + dim


def _bwd_kernel(antp_ref, antq_ref, tabre_ref, tabim_ref, coh_ref, g_ref,
                dtabre_ref, dtabim_ref, *, F, MP, T):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T)
    djp, djq = _bwd_accumulate(coh_ref, _g_from_ref(g_ref), p_re, p_im,
                               q_re, q_im, F, MP, T)
    _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, MP, T)


def _bwd_kernel_hybrid(antp_ref, antq_ref, cmap_ref, tabre_ref, tabim_ref,
                       coh_ref, g_ref, dtabre_ref, dtabim_ref,
                       *, F, MP, T, NC):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    cmap = cmap_ref[:]
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T, NC, cmap)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T, NC, cmap)
    djp, djq = _bwd_accumulate(coh_ref, _g_from_ref(g_ref), p_re, p_im,
                               q_re, q_im, F, MP, T)
    _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, MP, T, NC, cmap)


def _fused_predict_bwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q, g_ri,
                            *, tile, nc=1, cmap=None):
    Mp, F, rowsp, R = _shape_args(tab_re, coh_ri, tile, nc)
    mrows = Mp * nc
    g_spec = pl.BlockSpec((F, 8, tile), lambda r: (0, 0, r),
                          memory_space=pltpu.VMEM)
    if nc == 1:
        kernel = functools.partial(_bwd_kernel, F=F, MP=Mp, T=tile)
        specs = [_row_spec(tile), _row_spec(tile),
                 _tab_spec(Mp), _tab_spec(Mp),
                 _coh_spec(Mp, F, tile), g_spec]
        args = (ant_p, ant_q, tab_re, tab_im, coh_ri, g_ri)
    else:
        kernel = functools.partial(_bwd_kernel_hybrid, F=F, MP=Mp, T=tile,
                                   NC=nc)
        specs = [_row_spec(tile), _row_spec(tile), _cmap_spec(Mp, tile),
                 _tab_spec(Mp * nc), _tab_spec(Mp * nc),
                 _coh_spec(Mp, F, tile), g_spec]
        args = (ant_p, ant_q, cmap, tab_re, tab_im, coh_ri, g_ri)
    return pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=specs,
        out_specs=[_tab_spec(mrows), _tab_spec(mrows)],
        out_shape=[
            jax.ShapeDtypeStruct((4, mrows, NPAD), jnp.float32),
            jax.ShapeDtypeStruct((4, mrows, NPAD), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(*args)


# ------------------------------------------------------------ public API

# Capability flag for the sky-model refinement path (sagecal_tpu/refine):
# the fused kernel's backward emits gain-table cotangents ONLY — it has
# no coherency cotangent, so sky-parameter gradients cannot flow through
# it.  Refinement must route its predict through the XLA path
# (solvers.sage.predict_full_model / ops.rime.predict_coherencies);
# requesting a coherency gradient here raises FusedSkyGradientError via
# sky_constant() instead of silently returning zeros.
FUSED_COHERENCY_COTANGENT = False

# Machine-checkable form of the same contract: the argument(s) whose
# cotangent the capability flag governs.  jaxlint's JL013
# (cotangent-completeness) accepts a None cotangent slot for any
# custom_vjp argument named here while the flag is False, and reports
# the pair as a broken promise if the flag is ever flipped True without
# the backward actually producing the cotangent.
FUSED_COHERENCY_COTANGENT_ARGS = ("coh_ri",)


class FusedSkyGradientError(NotImplementedError):
    """A caller requested coherency (sky-parameter) gradients through
    the fused Pallas kernel, whose backward pass only produces gain
    cotangents.  Silent-zero cotangents are never returned."""


@jax.custom_vjp
def sky_constant(coh_ri):
    """Identity marking ``coh_ri`` a solver constant on the fused path.

    Forward is a no-op.  Reverse-mode differentiation THROUGH this op —
    i.e. any request for a coherency/sky cotangent from the fused
    kernels — raises :class:`FusedSkyGradientError` at backward-trace
    time instead of fabricating a silent zero (the hazard the refine
    subsystem's finite-difference pins would otherwise miss).  Gain-only
    differentiation never touches the backward rule, so every solver
    path is unaffected."""
    return coh_ri


def _sky_constant_fwd(coh_ri):
    return coh_ri, None


def _sky_constant_bwd(_, g):
    raise FusedSkyGradientError(
        "gradients w.r.t. coherencies are not implemented by the fused "
        "Pallas kernel (its backward emits gain-table cotangents only); "
        "route sky-model refinement through the XLA predict path "
        "(refine.objective / solvers.sage.predict_full_model) instead "
        "of the fused objective"
    )


sky_constant.defvjp(_sky_constant_fwd, _sky_constant_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_predict_packed(tab_re, tab_im, coh_ri, ant_p, ant_q,
                         tile=DEF_TILE):
    """Full-model RIME predict, packed-real layout (module docstring).

    Differentiable w.r.t. ``tab_re``/``tab_im`` only — coherencies are
    per-tile constants in every solver path (the chunked wrappers guard
    them with :func:`sky_constant`, which raises on any coherency
    cotangent request rather than returning silent zeros)."""
    return _fused_predict_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                   tile=tile)


def _vjp_fwd(tab_re, tab_im, coh_ri, ant_p, ant_q, tile):
    out = _fused_predict_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                  tile=tile)
    return out, (tab_re, tab_im, coh_ri, ant_p, ant_q)


def _vjp_bwd(tile, res, g_ri):
    tab_re, tab_im, coh_ri, ant_p, ant_q = res
    dre, dim = _fused_predict_bwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, g_ri, tile=tile
    )
    return dre, dim, None, None, None


fused_predict_packed.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def fused_predict_packed_hybrid(tab_re, tab_im, coh_ri, ant_p, ant_q, cmap,
                                nc, tile=DEF_TILE):
    """Hybrid-chunk variant (reference nchunk > 1, lmfit.c:86-87):
    ``tab_re/tab_im`` are component-major (4, Mp*nc, NPAD) with one
    row per (cluster, chunk) in each component plane, ``cmap``
    (Mp, rowsp) int32 selects each row's chunk.  ``nc`` is static.
    Differentiable w.r.t. ``tab_re``/``tab_im`` ONLY — a coherency
    cotangent request raises through :func:`sky_constant` at the
    chunked wrappers (never silent zeros)."""
    return _fused_predict_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                   tile=tile, nc=nc, cmap=cmap)


def _vjp_fwd_h(tab_re, tab_im, coh_ri, ant_p, ant_q, cmap, nc, tile):
    out = _fused_predict_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                  tile=tile, nc=nc, cmap=cmap)
    return out, (tab_re, tab_im, coh_ri, ant_p, ant_q, cmap)


def _vjp_bwd_h(nc, tile, res, g_ri):
    tab_re, tab_im, coh_ri, ant_p, ant_q, cmap = res
    dre, dim = _fused_predict_bwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, g_ri, tile=tile, nc=nc,
        cmap=cmap,
    )
    return dre, dim, None, None, None, None


fused_predict_packed_hybrid.defvjp(_vjp_fwd_h, _vjp_bwd_h)


# On-chip VMEM budget (round-5 hardware findings, v5e): the kernel
# body's scoped stack scales with Mp * tile against a 16 MB scoped-vmem
# limit.  At the north-star cluster count (Mp=104) the FORWARD needs
# tile <= 256 (512 -> 20.9 MB FAILS, 256 -> ~10.5 MB ok) and the
# BACKWARD — which carries 16 (Mp, T) cotangent accumulators — needs
# tile <= 128 (256 -> 19.7 MB FAILS).  128 is the safe production tile
# for any differentiated path at full cluster count.  The OBJECTIVE
# kernels below add only (F, 8, tile) vis + (F, tile) mask blocks and a
# (1, tile) accumulator on top of the predict footprint (~80 KB at
# F=2, tile=128 — noise next to the 16 (Mp, T) cotangent accumulators),
# so the same tile bounds hold.  Large row counts
# are CHUNKED at the XLA level (lax.map) to keep each Mosaic grid
# short.  Pass big arrays as jit ARGUMENTS, not closure constants: a
# constant is embedded in the program and lengthens every compile.
FULL_CLUSTER_TILE = 128
MAX_GRID_ROWS = 32768  # rows per lax.map chunk


def _chunk_plan(rowsp: int, tile: int, max_rows: int):
    """(n_chunks, chunk) for splitting ``rowsp`` rows, or None when one
    grid suffices.  Single copy of the math shared by both chunked
    wrappers; chunked_rowsp() pads so the validation always holds."""
    max_rows = _tile_floor(max_rows, tile)
    if rowsp <= max_rows:
        return None
    n = -(-rowsp // max_rows)
    chunk = rowsp // n
    if chunk * n != rowsp or chunk % tile:
        raise ValueError(
            f"rowsp={rowsp} must be n_chunks*chunk with chunk a multiple "
            f"of tile={tile}; pad with chunked_rowsp()")
    return n, chunk


def _map_row_chunks(one, n, chunk, F, rowsp):
    assert n * chunk == rowsp, (
        f"chunk plan must cover the row axis exactly: "
        f"{n} * {chunk} != {rowsp}"
    )
    out = jax.lax.map(one, jnp.arange(n))        # (n, F, 8, chunk)
    return out.transpose(1, 2, 0, 3).reshape(F, 8, rowsp)


def fused_predict_packed_chunked(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                 tile=FULL_CLUSTER_TILE,
                                 max_rows=MAX_GRID_ROWS):
    """Full-model predict for row counts too long for one Mosaic grid.

    Splits the row axis into ``n = ceil(rowsp / max_rows)`` equal chunks
    (caller pads ``rowsp`` to ``n * chunk`` with ``chunked_rowsp``) and
    ``lax.map``s the fused kernel over them — one kernel compile at a
    known-good grid length, reused across chunks and LBFGS iterations.
    Gradients flow to the gain tables through the map like the unchunked
    call."""
    _, F, _, rowsp = coh_ri.shape
    plan = _chunk_plan(rowsp, tile, max_rows)
    # coherencies are constants of the solve on BOTH branches: the same
    # sky_constant guard (raise on coherency cotangent, not silent
    # zeros) keeps the plan-None and chunked paths identical
    coh_ri = sky_constant(coh_ri)
    # antenna index maps are integer data constants: stop_gradient is
    # the identity on them, and makes the backward's None cotangent
    # slots statically provable (JL013) — no cotangent ever requested
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    if plan is None:
        return fused_predict_packed(tab_re, tab_im, coh_ri,
                                    ant_p, ant_q, tile)
    n, chunk = plan

    def one(i):
        c = jax.lax.dynamic_slice_in_dim(coh_ri, i * chunk, chunk, axis=3)
        p = jax.lax.dynamic_slice_in_dim(ant_p, i * chunk, chunk, axis=1)
        q = jax.lax.dynamic_slice_in_dim(ant_q, i * chunk, chunk, axis=1)
        return fused_predict_packed(tab_re, tab_im, c, p, q, tile)

    return _map_row_chunks(one, n, chunk, F, rowsp)


def fused_predict_packed_hybrid_chunked(tab_re, tab_im, coh_ri, ant_p,
                                        ant_q, cmap, nc,
                                        tile=FULL_CLUSTER_TILE,
                                        max_rows=MAX_GRID_ROWS):
    """Hybrid-chunk (nc > 1) analog of fused_predict_packed_chunked:
    ``cmap`` (Mp, rowsp) is sliced along the row axis with the other
    per-row arrays."""
    _, F, _, rowsp = coh_ri.shape
    plan = _chunk_plan(rowsp, tile, max_rows)
    coh_ri = sky_constant(coh_ri)
    # integer data constants (see fused_predict_packed_chunked)
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    cmap = jax.lax.stop_gradient(cmap)
    if plan is None:
        return fused_predict_packed_hybrid(
            tab_re, tab_im, coh_ri, ant_p, ant_q, cmap, nc, tile)
    n, chunk = plan

    def one(i):
        c = jax.lax.dynamic_slice_in_dim(coh_ri, i * chunk, chunk, axis=3)
        p = jax.lax.dynamic_slice_in_dim(ant_p, i * chunk, chunk, axis=1)
        q = jax.lax.dynamic_slice_in_dim(ant_q, i * chunk, chunk, axis=1)
        cm = jax.lax.dynamic_slice_in_dim(cmap, i * chunk, chunk, axis=1)
        return fused_predict_packed_hybrid(
            tab_re, tab_im, c, p, q, cm, nc, tile)

    return _map_row_chunks(one, n, chunk, F, rowsp)


def _tile_floor(max_rows: int, tile: int) -> int:
    """Largest tile multiple <= max_rows — both chunking functions
    derive the chunk bound this way so chunked_rowsp() output always
    satisfies fused_predict_packed_chunked()'s validation."""
    if max_rows < tile:
        raise ValueError(f"max_rows={max_rows} smaller than tile={tile}")
    return max_rows - max_rows % tile


def chunked_rowsp(rows: int, tile: int = FULL_CLUSTER_TILE,
                  max_rows: int = MAX_GRID_ROWS) -> int:
    """Smallest padded row count that is n equal tile-aligned chunks of
    at most ``max_rows`` rows (n chosen minimal)."""
    max_rows = _tile_floor(max_rows, tile)
    rowsp = pad_to(rows, tile)
    if rowsp <= max_rows:
        return rowsp
    n = -(-rowsp // max_rows)
    # ceil(rowsp/n) <= max_rows (from n's definition) and max_rows is a
    # tile multiple, so the tile-padded chunk stays <= max_rows; and
    # chunk >= rowsp/n > (n-1)*max_rows/n means the consumer recomputes
    # the same n from chunk*n.
    return pad_to(-(-rowsp // n), tile) * n


# ---------------------------------------------------- fused objective
#
# One grid pass that streams each coherency block through VMEM once and
# emits per-tile PARTIAL COSTS directly: predict Jp C Jq^H, residual
# (vis - model) * mask, Student's-t weighting log1p(e^2 / nu) (Gaussian
# e^2 as the nu -> inf degenerate case), reduced on-chip into a
# revisited (1, tile) accumulator block.  Compared with the predict
# kernel + XLA cost, this removes TWO buffer-scale HBM streams per
# value_and_grad: the forward never writes model_ri and the backward
# re-forms the residual cotangent in-register instead of reading a
# model-sized upstream cotangent buffer.  nu crosses the boundary as a
# (1, 1) f32 SMEM scalar so a traced nu (the EM's mean_nu) does not
# recompile the kernel; ``robust`` is static (Gaussian skips the
# transcendental entirely).


def _vis_spec(F, tile):
    return pl.BlockSpec((F, 8, tile), lambda r: (0, 0, r),
                        memory_space=pltpu.VMEM)


def _mask_spec(F, tile):
    return pl.BlockSpec((F, tile), lambda r: (0, r),
                        memory_space=pltpu.VMEM)


def _nu_spec():
    return pl.BlockSpec((1, 1), lambda r: (0, 0), memory_space=pltpu.SMEM)


def _residual_planes(vis_ref, mask_ref, f, v_re, v_im):
    """Masked residual d = (vis - sum_m V) * mask for frequency f as
    4 complex-component (d_re, d_im) (1, T) plane pairs, formed from
    the per-cluster products without ever storing the model."""
    m = mask_ref[f:f + 1, :]
    out = []
    for k in range(4):
        d_re = (vis_ref[f, k:k + 1, :]
                - jnp.sum(v_re[k], axis=0, keepdims=True)) * m
        d_im = (vis_ref[f, 4 + k:5 + k, :]
                - jnp.sum(v_im[k], axis=0, keepdims=True)) * m
        out.append((d_re, d_im))
    return m, out


def _obj_partial(coh_ref, vis_ref, mask_ref, nu, robust,
                 p_re, p_im, q_re, q_im, F, T):
    """Per-lane partial cost (1, T) for one row tile: sum over freq and
    complex components of e2 (Gaussian) or log1p(e2/nu) (robust), with
    e2 the squared masked residual.  Padded rows/clusters carry zero
    mask/coherency, so they contribute exactly 0."""
    part = jnp.zeros((1, T), jnp.float32)
    for f in range(F):
        c_re, c_im = _load_coh_planes(coh_ref, f)
        v_re, v_im = _rime_products(c_re, c_im, p_re, p_im, q_re, q_im)
        _, d = _residual_planes(vis_ref, mask_ref, f, v_re, v_im)
        for k in range(4):
            d_re, d_im = d[k]
            e2 = d_re * d_re + d_im * d_im
            part = part + (jnp.log1p(e2 / nu) if robust else e2)
    return part


def _obj_store(cost_ref, part):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        cost_ref[:, :] = part

    @pl.when(r != 0)
    def _acc():
        cost_ref[:, :] = cost_ref[:, :] + part


def _obj_fwd_kernel(antp_ref, antq_ref, tabre_ref, tabim_ref, coh_ref,
                    vis_ref, mask_ref, nu_ref, cost_ref, *, F, MP, T,
                    robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T)
    part = _obj_partial(coh_ref, vis_ref, mask_ref, nu_ref[0, 0], robust,
                        p_re, p_im, q_re, q_im, F, T)
    _obj_store(cost_ref, part)


def _obj_fwd_kernel_hybrid(antp_ref, antq_ref, cmap_ref, tabre_ref,
                           tabim_ref, coh_ref, vis_ref, mask_ref, nu_ref,
                           cost_ref, *, F, MP, T, NC, robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    cmap = cmap_ref[:]
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T, NC, cmap)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T, NC, cmap)
    part = _obj_partial(coh_ref, vis_ref, mask_ref, nu_ref[0, 0], robust,
                        p_re, p_im, q_re, q_im, F, T)
    _obj_store(cost_ref, part)


def _g_from_residual(vis_ref, mask_ref, nu, robust, p_re, p_im):
    """Objective-kernel cotangent source: re-form the model from the
    cached A = C Jq^H (no HBM traffic), take the residual, and emit the
    model cotangent of the scalar cost in-register:
      g = -2 * mask * d              (Gaussian,  d(e2)/d(model))
      g = -2 * mask * d / (nu + e2)  (robust, d(log1p(e2/nu))/d(model))
    The upstream scalar cost cotangent is applied OUTSIDE the kernel."""
    def g_of(f, c_re, c_im, a_re, a_im):
        del c_re, c_im
        v_re, v_im = _jp_a(p_re, p_im, a_re, a_im)
        m, d = _residual_planes(vis_ref, mask_ref, f, v_re, v_im)
        g_re, g_im = [], []
        for k in range(4):
            d_re, d_im = d[k]
            if robust:
                w = 2.0 / (nu + d_re * d_re + d_im * d_im)
            else:
                w = 2.0
            g_re.append(-w * m * d_re)
            g_im.append(-w * m * d_im)
        return g_re, g_im
    return g_of


def _obj_bwd_kernel(antp_ref, antq_ref, tabre_ref, tabim_ref, coh_ref,
                    vis_ref, mask_ref, nu_ref, dtabre_ref, dtabim_ref,
                    *, F, MP, T, robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T)
    g_of = _g_from_residual(vis_ref, mask_ref, nu_ref[0, 0], robust,
                            p_re, p_im)
    djp, djq = _bwd_accumulate(coh_ref, g_of, p_re, p_im, q_re, q_im,
                               F, MP, T)
    _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, MP, T)


def _obj_bwd_kernel_hybrid(antp_ref, antq_ref, cmap_ref, tabre_ref,
                           tabim_ref, coh_ref, vis_ref, mask_ref, nu_ref,
                           dtabre_ref, dtabim_ref, *, F, MP, T, NC, robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    cmap = cmap_ref[:]
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, MP, T, NC, cmap)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, MP, T, NC, cmap)
    g_of = _g_from_residual(vis_ref, mask_ref, nu_ref[0, 0], robust,
                            p_re, p_im)
    djp, djq = _bwd_accumulate(coh_ref, g_of, p_re, p_im, q_re, q_im,
                               F, MP, T)
    _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, MP, T, NC, cmap)


def _fused_cost_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                         mask_p, nu_arr, *, robust, tile, nc=1, cmap=None):
    Mp, F, rowsp, R = _shape_args(tab_re, coh_ri, tile, nc)
    assert vis_ri.shape == (F, 8, rowsp) and mask_p.shape == (F, rowsp)
    if nc == 1:
        kernel = functools.partial(_obj_fwd_kernel, F=F, MP=Mp, T=tile,
                                   robust=robust)
        specs = [_row_spec(tile), _row_spec(tile),
                 _tab_spec(Mp), _tab_spec(Mp), _coh_spec(Mp, F, tile),
                 _vis_spec(F, tile), _mask_spec(F, tile), _nu_spec()]
        args = (ant_p, ant_q, tab_re, tab_im, coh_ri, vis_ri, mask_p,
                nu_arr)
    else:
        kernel = functools.partial(_obj_fwd_kernel_hybrid, F=F, MP=Mp,
                                   T=tile, NC=nc, robust=robust)
        specs = [_row_spec(tile), _row_spec(tile), _cmap_spec(Mp, tile),
                 _tab_spec(Mp * nc), _tab_spec(Mp * nc),
                 _coh_spec(Mp, F, tile),
                 _vis_spec(F, tile), _mask_spec(F, tile), _nu_spec()]
        args = (ant_p, ant_q, cmap, tab_re, tab_im, coh_ri, vis_ri,
                mask_p, nu_arr)
    part = pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, tile), lambda r: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, tile), jnp.float32),
        interpret=_use_interpret(),
    )(*args)
    # final lane reduction of the (1, tile) accumulator happens in XLA:
    # tile floats, not a buffer-scale stream
    return jnp.sum(part)


def _fused_cost_bwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                         mask_p, nu_arr, *, robust, tile, nc=1, cmap=None):
    Mp, F, rowsp, R = _shape_args(tab_re, coh_ri, tile, nc)
    mrows = Mp * nc
    if nc == 1:
        kernel = functools.partial(_obj_bwd_kernel, F=F, MP=Mp, T=tile,
                                   robust=robust)
        specs = [_row_spec(tile), _row_spec(tile),
                 _tab_spec(Mp), _tab_spec(Mp), _coh_spec(Mp, F, tile),
                 _vis_spec(F, tile), _mask_spec(F, tile), _nu_spec()]
        args = (ant_p, ant_q, tab_re, tab_im, coh_ri, vis_ri, mask_p,
                nu_arr)
    else:
        kernel = functools.partial(_obj_bwd_kernel_hybrid, F=F, MP=Mp,
                                   T=tile, NC=nc, robust=robust)
        specs = [_row_spec(tile), _row_spec(tile), _cmap_spec(Mp, tile),
                 _tab_spec(Mp * nc), _tab_spec(Mp * nc),
                 _coh_spec(Mp, F, tile),
                 _vis_spec(F, tile), _mask_spec(F, tile), _nu_spec()]
        args = (ant_p, ant_q, cmap, tab_re, tab_im, coh_ri, vis_ri,
                mask_p, nu_arr)
    return pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=specs,
        out_specs=[_tab_spec(mrows), _tab_spec(mrows)],
        out_shape=[
            jax.ShapeDtypeStruct((4, mrows, NPAD), jnp.float32),
            jax.ShapeDtypeStruct((4, mrows, NPAD), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _fused_cost(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                nu_arr, robust, tile):
    return _fused_cost_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                vis_ri, mask_p, nu_arr, robust=robust,
                                tile=tile)


def _cost_vjp_fwd(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                  nu_arr, robust, tile):
    out = _fused_cost_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                               vis_ri, mask_p, nu_arr, robust=robust,
                               tile=tile)
    return out, (tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                 nu_arr)


def _cost_vjp_bwd(robust, tile, res, gbar):
    tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_arr = res
    dre, dim = _fused_cost_bwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_arr,
        robust=robust, tile=tile,
    )
    # the kernel emits d(cost)/d(tab); scale by the upstream scalar
    # cotangent here (one scalar-times-table op, not a kernel input)
    return (gbar * dre, gbar * dim, None, None, None, None, None, None)


_fused_cost.defvjp(_cost_vjp_fwd, _cost_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _fused_cost_hybrid(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                       mask_p, nu_arr, cmap, nc, robust, tile):
    return _fused_cost_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                vis_ri, mask_p, nu_arr, robust=robust,
                                tile=tile, nc=nc, cmap=cmap)


def _cost_vjp_fwd_h(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                    nu_arr, cmap, nc, robust, tile):
    out = _fused_cost_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                               vis_ri, mask_p, nu_arr, robust=robust,
                               tile=tile, nc=nc, cmap=cmap)
    return out, (tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                 nu_arr, cmap)


def _cost_vjp_bwd_h(nc, robust, tile, res, gbar):
    (tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_arr,
     cmap) = res
    dre, dim = _fused_cost_bwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_arr,
        robust=robust, tile=tile, nc=nc, cmap=cmap,
    )
    return (gbar * dre, gbar * dim, None, None, None, None, None, None,
            None)


_fused_cost_hybrid.defvjp(_cost_vjp_fwd_h, _cost_vjp_bwd_h)


def _nu_cell(nu):
    """nu as the kernel's (1, 1) f32 SMEM cell.  ``nu=None`` (Gaussian)
    passes 1.0, which the kernel never reads (``robust`` is static)."""
    if nu is None:
        return jnp.ones((1, 1), jnp.float32)
    return jnp.asarray(nu, jnp.float32).reshape(1, 1)


def fused_cost_packed(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                      mask_p, nu=None, tile=DEF_TILE):
    """Scalar calibration objective in one fused pass (section comment
    above): ``sum log1p(|((vis - Jp C Jq^H) * mask)|^2 / nu)`` when
    ``nu`` is given (Student's-t / robust), ``sum |...|^2`` when ``nu``
    is None (Gaussian).  ``nu`` may be a traced scalar (the EM's
    mean_nu).  Differentiable w.r.t. ``tab_re``/``tab_im`` only, via a
    backward kernel that never materializes the model or residual in
    HBM."""
    robust = nu is not None
    # data constants of the solve: stop_gradient (identity for values)
    # makes the backward's None cotangent slots statically provable
    # (JL013) — differentiation w.r.t. these args is never requested
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    vis_ri = jax.lax.stop_gradient(vis_ri)
    mask_p = jax.lax.stop_gradient(mask_p)
    nu_arr = jax.lax.stop_gradient(_nu_cell(nu))
    return _fused_cost(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                       mask_p, nu_arr, robust, tile)


def fused_cost_packed_hybrid(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                             mask_p, cmap, nc, nu=None, tile=DEF_TILE):
    """Hybrid-chunk (nc > 1) objective: tables carry one row block per
    (cluster, chunk), ``cmap`` (Mp, rowsp) selects each row's chunk."""
    robust = nu is not None
    # data constants of the solve (see fused_cost_packed)
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    vis_ri = jax.lax.stop_gradient(vis_ri)
    mask_p = jax.lax.stop_gradient(mask_p)
    cmap = jax.lax.stop_gradient(cmap)
    nu_arr = jax.lax.stop_gradient(_nu_cell(nu))
    return _fused_cost_hybrid(tab_re, tab_im, coh_ri, ant_p, ant_q,
                              vis_ri, mask_p, nu_arr, cmap, nc,
                              robust, tile)


def fused_cost_packed_chunked(tab_re, tab_im, coh_ri, ant_p, ant_q,
                              vis_ri, mask_p, nu=None,
                              tile=FULL_CLUSTER_TILE,
                              max_rows=MAX_GRID_ROWS):
    """Fused objective for row counts too long for one Mosaic grid:
    per-row arrays are sliced into equal tile-aligned chunks (see
    fused_predict_packed_chunked) and the per-chunk scalar costs summed.
    vis/mask stay stop_gradient data constants; coherencies go through
    the sky_constant guard (raise on a sky-gradient request, matching
    the predict wrappers — never silent zeros)."""
    _, F, _, rowsp = coh_ri.shape
    plan = _chunk_plan(rowsp, tile, max_rows)
    nu_arr = jax.lax.stop_gradient(_nu_cell(nu))
    robust = nu is not None
    coh_ri = sky_constant(coh_ri)
    # integer data constants (see fused_cost_packed)
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    if plan is None:
        return _fused_cost(tab_re, tab_im, coh_ri,
                           ant_p, ant_q, jax.lax.stop_gradient(vis_ri),
                           jax.lax.stop_gradient(mask_p), nu_arr, robust,
                           tile)
    n, chunk = plan

    def one(i):
        c = jax.lax.dynamic_slice_in_dim(coh_ri, i * chunk, chunk, axis=3)
        p = jax.lax.dynamic_slice_in_dim(ant_p, i * chunk, chunk, axis=1)
        q = jax.lax.dynamic_slice_in_dim(ant_q, i * chunk, chunk, axis=1)
        v = jax.lax.dynamic_slice_in_dim(vis_ri, i * chunk, chunk, axis=2)
        m = jax.lax.dynamic_slice_in_dim(mask_p, i * chunk, chunk, axis=1)
        return _fused_cost(tab_re, tab_im, c, p, q,
                           jax.lax.stop_gradient(v),
                           jax.lax.stop_gradient(m), nu_arr, robust, tile)

    return jnp.sum(jax.lax.map(one, jnp.arange(n)))


def fused_cost_packed_hybrid_chunked(tab_re, tab_im, coh_ri, ant_p, ant_q,
                                     vis_ri, mask_p, cmap, nc, nu=None,
                                     tile=FULL_CLUSTER_TILE,
                                     max_rows=MAX_GRID_ROWS):
    """Hybrid-chunk (nc > 1) analog of fused_cost_packed_chunked."""
    _, F, _, rowsp = coh_ri.shape
    plan = _chunk_plan(rowsp, tile, max_rows)
    nu_arr = jax.lax.stop_gradient(_nu_cell(nu))
    robust = nu is not None
    coh_ri = sky_constant(coh_ri)
    # integer data constants (see fused_cost_packed)
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    cmap = jax.lax.stop_gradient(cmap)
    if plan is None:
        return _fused_cost_hybrid(
            tab_re, tab_im, coh_ri, ant_p, ant_q,
            jax.lax.stop_gradient(vis_ri), jax.lax.stop_gradient(mask_p),
            nu_arr, cmap, nc, robust, tile)
    n, chunk = plan

    def one(i):
        c = jax.lax.dynamic_slice_in_dim(coh_ri, i * chunk, chunk, axis=3)
        p = jax.lax.dynamic_slice_in_dim(ant_p, i * chunk, chunk, axis=1)
        q = jax.lax.dynamic_slice_in_dim(ant_q, i * chunk, chunk, axis=1)
        v = jax.lax.dynamic_slice_in_dim(vis_ri, i * chunk, chunk, axis=2)
        m = jax.lax.dynamic_slice_in_dim(mask_p, i * chunk, chunk, axis=1)
        cm = jax.lax.dynamic_slice_in_dim(cmap, i * chunk, chunk, axis=1)
        return _fused_cost_hybrid(
            tab_re, tab_im, c, p, q,
            jax.lax.stop_gradient(v), jax.lax.stop_gradient(m), nu_arr,
            cm, nc, robust, tile)

    return jnp.sum(jax.lax.map(one, jnp.arange(n)))


# ---------------------------------------------- batched fused objective
#
# One Pallas grid evaluating the fused objective for a BATCH of lanes
# (independent same-shape solves — the serve path's tenants).  The lane
# axis is folded into the GEMM M dimension: batched gain tables are
# (4, B*Mp, NPAD) lane-major, so the one-hot selection matmuls become
# (B*Mp, NPAD) @ (NPAD, T) — B times the MXU rows of a solo dispatch
# per pass, instead of B separate grids of tiny 2x2 arithmetic.  All
# the solo (rows, T)-plane helpers (_expand_gains, _load_coh_planes,
# _rime_products, _bwd_accumulate, _bwd_store) are reused unchanged
# with rows := B*Mp; only the residual/cost stage is lane-aware:
# per-lane cluster reduction via a leading-dim (B*Mp, T) -> (B, Mp, T)
# reshape (a pure sublane view — no minor-dim relayout), per-lane
# masked residual against (B, T) vis planes, per-lane partial costs
# accumulated into a (B, rowsp) output.  The backward forms each
# lane's residual cotangent in-register and broadcasts it back across
# the lane's Mp cluster rows, then the solo accumulate/scatter path
# runs unchanged on (B*Mp, T) planes.
#
# Capability contract (enforced host-side by solvers.batched):
#   - nc == 1 only (no hybrid time chunks on the batched path);
#   - ant_p/ant_q SHARED across lanes (one (1, rowsp) plane — a serve
#     bucket guarantees identical baseline geometry);
#   - per-lane nu crosses as a (B, NPAD) f32 plane (column-replicated
#     scalar per lane; a traced EM mean_nu never recompiles);
#   - VMEM: the backward carries 16 (B*Mp, T) accumulators, so the
#     solo tile bound applies with B*Mp in the cluster-row position
#     (B*Mp <~ 104 at tile 128 on the v5e — the serve shapes' 8-row
#     cluster blocks allow B up to 13 at full tile).
#
# Ragged-lane guard: replication-padded lanes are neutralized by
# zeroing their mask plane at pack time (``valid``), which makes their
# cost exactly 0.0 and their gain cotangent exactly 0 — the padded
# lane cannot perturb the batch and is discarded host-side.


def _shape_args_batch(tab_re, coh_ri, vis_ri, mask_p, tile):
    four, mrows, npad = tab_re.shape
    B, F, eight, rowsp = vis_ri.shape
    assert four == 4 and npad == NPAD and eight == 8
    assert mrows % B == 0, (mrows, B)
    Mp = mrows // B
    assert coh_ri.shape == (mrows, F, 8, rowsp), (coh_ri.shape, vis_ri.shape)
    assert mask_p.shape == (B, F, rowsp)
    assert Mp % 8 == 0 and rowsp % tile == 0, (Mp, rowsp, tile)
    return B, Mp, F, rowsp, rowsp // tile


def _bvis_spec(B, F, tile):
    return pl.BlockSpec((B, F, 8, tile), lambda r: (0, 0, 0, r),
                        memory_space=pltpu.VMEM)


def _bmask_spec(B, F, tile):
    return pl.BlockSpec((B, F, tile), lambda r: (0, 0, r),
                        memory_space=pltpu.VMEM)


def _bnu_spec(B):
    return pl.BlockSpec((B, NPAD), lambda r: (0, 0),
                        memory_space=pltpu.VMEM)


def _lane_sum(plane, B, MP, T):
    """Per-lane cluster reduction: (B*MP, T) product plane -> (B, T).
    Leading-dim reshape only (a sublane-order view, Mosaic-safe like
    the hybrid path's (mp, nc, T) split)."""
    return jnp.sum(plane.reshape(B, MP, T), axis=1)


def _lane_bcast(g, B, MP, T):
    """Inverse routing for the backward: a lane's (B, T) residual
    cotangent replicated across its MP cluster rows -> (B*MP, T), so
    the solo _bwd_accumulate arithmetic applies unchanged."""
    return jnp.broadcast_to(g[:, None, :], (B, MP, T)).reshape(B * MP, T)


def _residual_planes_batch(vis_ref, mask_ref, f, v_re, v_im, B, MP, T):
    """Per-lane masked residual d = (vis - sum_m V) * mask for
    frequency f: 4 complex-component (d_re, d_im) (B, T) plane pairs."""
    m = mask_ref[:, f, :]  # (B, T)
    out = []
    for k in range(4):
        d_re = (vis_ref[:, f, k, :] - _lane_sum(v_re[k], B, MP, T)) * m
        d_im = (vis_ref[:, f, 4 + k, :] - _lane_sum(v_im[k], B, MP, T)) * m
        out.append((d_re, d_im))
    return m, out


def _obj_partial_batch(coh_ref, vis_ref, mask_ref, nu_ref, robust,
                       p_re, p_im, q_re, q_im, B, F, MP, T):
    """Per-lane partial cost (B, T) for one row tile (the batched
    analog of _obj_partial; nu broadcasts per lane as a (B, 1) column
    against the (B, T) residual planes)."""
    part = jnp.zeros((B, T), jnp.float32)
    nu = nu_ref[:, 0:1] if robust else None
    for f in range(F):
        c_re, c_im = _load_coh_planes(coh_ref, f)
        v_re, v_im = _rime_products(c_re, c_im, p_re, p_im, q_re, q_im)
        _, d = _residual_planes_batch(vis_ref, mask_ref, f, v_re, v_im,
                                      B, MP, T)
        for k in range(4):
            d_re, d_im = d[k]
            e2 = d_re * d_re + d_im * d_im
            part = part + (jnp.log1p(e2 / nu) if robust else e2)
    return part


def _obj_fwd_kernel_batch(antp_ref, antq_ref, tabre_ref, tabim_ref,
                          coh_ref, vis_ref, mask_ref, nu_ref, cost_ref,
                          *, B, F, MP, T, robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, B * MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, B * MP, T)
    # each grid step owns its own (B, tile) output block — no revisit
    cost_ref[:, :] = _obj_partial_batch(
        coh_ref, vis_ref, mask_ref, nu_ref, robust,
        p_re, p_im, q_re, q_im, B, F, MP, T)


def _g_from_residual_batch(vis_ref, mask_ref, nu_ref, robust, p_re, p_im,
                           B, MP, T):
    """Batched objective cotangent source: per-lane g planes (the solo
    _g_from_residual weights, per lane) broadcast back across each
    lane's cluster rows so _bwd_accumulate consumes (B*MP, T) planes."""
    def g_of(f, c_re, c_im, a_re, a_im):
        del c_re, c_im
        v_re, v_im = _jp_a(p_re, p_im, a_re, a_im)
        m, d = _residual_planes_batch(vis_ref, mask_ref, f, v_re, v_im,
                                      B, MP, T)
        g_re, g_im = [], []
        for k in range(4):
            d_re, d_im = d[k]
            if robust:
                w = 2.0 / (nu_ref[:, 0:1] + d_re * d_re + d_im * d_im)
            else:
                w = 2.0
            g_re.append(_lane_bcast(-w * m * d_re, B, MP, T))
            g_im.append(_lane_bcast(-w * m * d_im, B, MP, T))
        return g_re, g_im
    return g_of


def _obj_bwd_kernel_batch(antp_ref, antq_ref, tabre_ref, tabim_ref,
                          coh_ref, vis_ref, mask_ref, nu_ref,
                          dtabre_ref, dtabim_ref, *, B, F, MP, T, robust):
    ohp, ohq = _onehots(antp_ref, antq_ref, T)
    p_re, p_im = _expand_gains(tabre_ref, tabim_ref, ohp, B * MP, T)
    q_re, q_im = _expand_gains(tabre_ref, tabim_ref, ohq, B * MP, T)
    g_of = _g_from_residual_batch(vis_ref, mask_ref, nu_ref, robust,
                                  p_re, p_im, B, MP, T)
    djp, djq = _bwd_accumulate(coh_ref, g_of, p_re, p_im, q_re, q_im,
                               F, B * MP, T)
    _bwd_store(dtabre_ref, dtabim_ref, djp, djq, ohp, ohq, B * MP, T)


def _fused_cost_batch_fwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                               vis_ri, mask_p, nu_rows, *, robust, tile):
    B, Mp, F, rowsp, R = _shape_args_batch(tab_re, coh_ri, vis_ri, mask_p,
                                           tile)
    kernel = functools.partial(_obj_fwd_kernel_batch, B=B, F=F, MP=Mp,
                               T=tile, robust=robust)
    part = pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=[_row_spec(tile), _row_spec(tile),
                  _tab_spec(B * Mp), _tab_spec(B * Mp),
                  _coh_spec(B * Mp, F, tile),
                  _bvis_spec(B, F, tile), _bmask_spec(B, F, tile),
                  _bnu_spec(B)],
        out_specs=pl.BlockSpec((B, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, rowsp), jnp.float32),
        interpret=_use_interpret(),
    )(ant_p, ant_q, tab_re, tab_im, coh_ri, vis_ri, mask_p, nu_rows)
    # per-lane final reduction in XLA: B*rowsp floats, not buffer-scale
    return jnp.sum(part, axis=-1)


def _fused_cost_batch_bwd_impl(tab_re, tab_im, coh_ri, ant_p, ant_q,
                               vis_ri, mask_p, nu_rows, *, robust, tile):
    B, Mp, F, rowsp, R = _shape_args_batch(tab_re, coh_ri, vis_ri, mask_p,
                                           tile)
    kernel = functools.partial(_obj_bwd_kernel_batch, B=B, F=F, MP=Mp,
                               T=tile, robust=robust)
    return pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=[_row_spec(tile), _row_spec(tile),
                  _tab_spec(B * Mp), _tab_spec(B * Mp),
                  _coh_spec(B * Mp, F, tile),
                  _bvis_spec(B, F, tile), _bmask_spec(B, F, tile),
                  _bnu_spec(B)],
        out_specs=[_tab_spec(B * Mp), _tab_spec(B * Mp)],
        out_shape=[
            jax.ShapeDtypeStruct((4, B * Mp, NPAD), jnp.float32),
            jax.ShapeDtypeStruct((4, B * Mp, NPAD), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(ant_p, ant_q, tab_re, tab_im, coh_ri, vis_ri, mask_p, nu_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _fused_cost_batch(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                      mask_p, nu_rows, robust, tile):
    return _fused_cost_batch_fwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_rows,
        robust=robust, tile=tile)


def _cost_vjp_fwd_b(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                    nu_rows, robust, tile):
    out = _fused_cost_batch_fwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_rows,
        robust=robust, tile=tile)
    return out, (tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p,
                 nu_rows)


def _cost_vjp_bwd_b(robust, tile, res, gbar):
    tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_rows = res
    dre, dim = _fused_cost_batch_bwd_impl(
        tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri, mask_p, nu_rows,
        robust=robust, tile=tile)
    # the kernel emits d(cost_b)/d(tab); the upstream cotangent is now
    # PER LANE (B,) — scale each lane's Mp-row table block outside the
    # kernel (one row-broadcast multiply, not a kernel input)
    B = vis_ri.shape[0]
    Mp = tab_re.shape[1] // B
    scale = jnp.repeat(gbar, Mp)[None, :, None]  # (1, B*Mp, 1)
    return (scale * dre, scale * dim, None, None, None, None, None, None)


_fused_cost_batch.defvjp(_cost_vjp_fwd_b, _cost_vjp_bwd_b)


def _nu_rows(nu, B):
    """Per-lane nu as the batched kernel's (B, NPAD) f32 VMEM plane
    (column-replicated).  ``nu=None`` (Gaussian) passes ones, which the
    kernel never reads (``robust`` is static).  Scalar nu broadcasts to
    every lane; a (B,) array carries each lane's EM mean_nu."""
    if nu is None:
        return jnp.ones((B, NPAD), jnp.float32)
    nu = jnp.asarray(nu, jnp.float32)
    return jnp.broadcast_to(nu.reshape(-1, 1) if nu.ndim else nu,
                            (B, NPAD))


def fused_cost_packed_batch(tab_re, tab_im, coh_ri, ant_p, ant_q, vis_ri,
                            mask_p, nu=None, tile=FULL_CLUSTER_TILE,
                            max_rows=MAX_GRID_ROWS):
    """Per-lane calibration objectives for a batch of lanes in ONE fused
    grid (section comment above): returns the (B,) vector of per-lane
    costs ``sum log1p(|((vis_b - Jp_b C_b Jq_b^H) * mask_b)|^2 / nu_b)``
    (robust; Gaussian ``sum |...|^2`` when ``nu`` is None).

    Layout: ``tab_re/tab_im`` (4, B*Mp, NPAD) lane-major batched tables
    (:func:`pack_gain_tables_batch`); ``coh_ri`` (B*Mp, F, 8, rowsp)
    f32 or bf16; ``ant_p/ant_q`` (1, rowsp) SHARED across lanes;
    ``vis_ri`` (B, F, 8, rowsp); ``mask_p`` (B, F, rowsp); ``nu`` a
    scalar or (B,) per-lane array (may be traced).  Differentiable
    w.r.t. the tables only; the per-lane upstream cotangent is applied
    as a row-block scale outside the kernel.  Rows beyond one Mosaic
    grid are chunked exactly like the solo wrapper (per-chunk (B,)
    costs summed)."""
    B = vis_ri.shape[0]
    rowsp = coh_ri.shape[-1]
    plan = _chunk_plan(rowsp, tile, max_rows)
    nu_arr = jax.lax.stop_gradient(_nu_rows(nu, B))
    robust = nu is not None
    coh_ri = sky_constant(coh_ri)
    # integer data constants (see fused_cost_packed)
    ant_p = jax.lax.stop_gradient(ant_p)
    ant_q = jax.lax.stop_gradient(ant_q)
    if plan is None:
        return _fused_cost_batch(
            tab_re, tab_im, coh_ri, ant_p, ant_q,
            jax.lax.stop_gradient(vis_ri), jax.lax.stop_gradient(mask_p),
            nu_arr, robust, tile)
    n, chunk = plan

    def one(i):
        c = jax.lax.dynamic_slice_in_dim(coh_ri, i * chunk, chunk, axis=3)
        p = jax.lax.dynamic_slice_in_dim(ant_p, i * chunk, chunk, axis=1)
        q = jax.lax.dynamic_slice_in_dim(ant_q, i * chunk, chunk, axis=1)
        v = jax.lax.dynamic_slice_in_dim(vis_ri, i * chunk, chunk, axis=3)
        m = jax.lax.dynamic_slice_in_dim(mask_p, i * chunk, chunk, axis=2)
        return _fused_cost_batch(tab_re, tab_im, c, p, q,
                                 jax.lax.stop_gradient(v),
                                 jax.lax.stop_gradient(m), nu_arr, robust,
                                 tile)

    return jnp.sum(jax.lax.map(one, jnp.arange(n)), axis=0)


# --------------------------------------------------- packing conveniences


def pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def pack_gain_tables(jones, mp: int):
    """(M, N, 2, 2) — or (M, nc, N, 2, 2) hybrid — complex Jones ->
    component-major (tab_re, tab_im) of shape (4, mp*nc, NPAD) f32:
    plane k holds component k (row-major [J00, J01, J10, J11]) for
    every (cluster, chunk) row ``m*nc + c``."""
    if jones.ndim == 5:
        M, nc, N = jones.shape[0], jones.shape[1], jones.shape[2]
    else:
        M, nc, N = jones.shape[0], 1, jones.shape[1]
    if N > NPAD:
        raise ValueError(
            f"fused RIME kernel supports at most NPAD={NPAD} stations, "
            f"got N={N}; use the XLA predict path (or the rows-sharded "
            f"solver) for larger arrays"
        )
    flat = jones.reshape(M * nc, N, 4)  # row-major J00, J01, J10, J11
    tab = jnp.transpose(flat, (2, 0, 1))  # (4, M*nc, N)
    tab = jnp.pad(tab, ((0, 0), (0, nc * (mp - M)), (0, NPAD - N)))
    return (jnp.real(tab).astype(jnp.float32),
            jnp.imag(tab).astype(jnp.float32))


def pack_predict_inputs(vis, mask, coh, ant_p, ant_q, chunk_map=None,
                        tile=DEF_TILE, max_rows=None):
    """Pad/pack complex (F, 4, rows) visibilities, (M, F, 4, rows)
    coherencies, mask and antenna indices into the kernel's layout
    contract: rows padded to a multiple of ``tile`` (or to equal
    tile-aligned ``max_rows`` chunks for the chunked kernels, when
    given), clusters padded to a multiple of 8, re/im concatenated on
    the component axis, ant indices as (1, rowsp) int32.  Returns
    (vis_ri, mask_p, coh_ri, antp, antq, cmap_or_None).  jnp-based: use
    inside jit (padded regions carry zero coherency and zero mask, so
    they contribute nothing to any cost or gradient)."""
    M, rows = coh.shape[0], coh.shape[-1]
    mp = pad_to(M, 8)
    rowsp = (chunked_rowsp(rows, tile, max_rows) if max_rows
             else pad_to(rows, tile))
    pad_r = rowsp - rows
    coh_ri = jnp.concatenate(
        [jnp.real(coh), jnp.imag(coh)], axis=-2
    ).astype(jnp.float32)
    coh_ri = jnp.pad(coh_ri, ((0, mp - M), (0, 0), (0, 0), (0, pad_r)))
    vis_ri = jnp.concatenate(
        [jnp.real(vis), jnp.imag(vis)], axis=-2
    ).astype(jnp.float32)
    vis_ri = jnp.pad(vis_ri, ((0, 0), (0, 0), (0, pad_r)))
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, pad_r)))
    antp = jnp.pad(ant_p.astype(jnp.int32)[None, :], ((0, 0), (0, pad_r)))
    antq = jnp.pad(ant_q.astype(jnp.int32)[None, :], ((0, 0), (0, pad_r)))
    cmap = None
    if chunk_map is not None:
        cmap = jnp.pad(chunk_map.astype(jnp.int32),
                       ((0, mp - M), (0, pad_r)))
    return vis_ri, mask_p, coh_ri, antp, antq, cmap


def pack_gain_tables_batch(jones_b, mp: int):
    """(B, M, N, 2, 2) complex Jones -> lane-major component-major
    batched tables (tab_re, tab_im) of shape (4, B*mp, NPAD) f32: lane
    b's cluster block occupies rows [b*mp, (b+1)*mp) of every component
    plane (nc=1 only — the batched kernel has no hybrid-chunk mode)."""
    B, M, N = jones_b.shape[0], jones_b.shape[1], jones_b.shape[2]
    if N > NPAD:
        raise ValueError(
            f"fused RIME kernel supports at most NPAD={NPAD} stations, "
            f"got N={N}; use the XLA predict path for larger arrays"
        )
    flat = jones_b.reshape(B, M, N, 4)  # row-major J00, J01, J10, J11
    tab = jnp.transpose(flat, (3, 0, 1, 2))  # (4, B, M, N)
    tab = jnp.pad(tab, ((0, 0), (0, 0), (0, mp - M), (0, NPAD - N)))
    tab = tab.reshape(4, B * mp, NPAD)
    return (jnp.real(tab).astype(jnp.float32),
            jnp.imag(tab).astype(jnp.float32))


def pack_cost_inputs_batch(vis_b, mask_b, coh_b, ant_p, ant_q,
                           tile=FULL_CLUSTER_TILE, max_rows=MAX_GRID_ROWS,
                           valid=None):
    """Pad/pack a batch of same-shape lanes into the batched objective
    kernel's layout contract: complex ``vis_b`` (B, F, 4, rows) ->
    ``vis_ri`` (B, F, 8, rowsp); ``mask_b`` (B, F, rows) -> ``mask_p``
    (B, F, rowsp); complex ``coh_b`` (B, M, F, 4, rows) -> ``coh_ri``
    (B*mp, F, 8, rowsp) lane-major; SHARED ``ant_p/ant_q`` (rows,) ->
    (1, rowsp) int32.  ``valid`` (B,) optionally zeroes whole lanes'
    masks — the replication-padded ragged-lane guard: a zeroed lane's
    cost and gain cotangent are exactly 0 through the kernel (Gaussian
    0, robust log1p(0)), so padded lanes cannot perturb the batch.
    jnp-based: use inside jit.  Returns (vis_ri, mask_p, coh_ri, antp,
    antq)."""
    B, M, rows = coh_b.shape[0], coh_b.shape[1], coh_b.shape[-1]
    mp = pad_to(M, 8)
    rowsp = chunked_rowsp(rows, tile, max_rows)
    pad_r = rowsp - rows
    coh_ri = jnp.concatenate(
        [jnp.real(coh_b), jnp.imag(coh_b)], axis=-2
    ).astype(jnp.float32)
    coh_ri = jnp.pad(
        coh_ri, ((0, 0), (0, mp - M), (0, 0), (0, 0), (0, pad_r))
    ).reshape(B * mp, coh_b.shape[2], 8, rowsp)
    vis_ri = jnp.concatenate(
        [jnp.real(vis_b), jnp.imag(vis_b)], axis=-2
    ).astype(jnp.float32)
    vis_ri = jnp.pad(vis_ri, ((0, 0), (0, 0), (0, 0), (0, pad_r)))
    mask_p = jnp.pad(mask_b.astype(jnp.float32),
                     ((0, 0), (0, 0), (0, pad_r)))
    if valid is not None:
        mask_p = mask_p * jnp.asarray(valid, jnp.float32)[:, None, None]
    antp = jnp.pad(ant_p.astype(jnp.int32)[None, :], ((0, 0), (0, pad_r)))
    antq = jnp.pad(ant_q.astype(jnp.int32)[None, :], ((0, 0), (0, pad_r)))
    return vis_ri, mask_p, coh_ri, antp, antq


def unpack_gain_grads_batch(dre, dim, B: int, M: int, N: int):
    """Inverse of :func:`pack_gain_tables_batch` for cotangents:
    (4, B*mp, NPAD) pair -> (B, M, N, 2, 2) re/im arrays."""
    mp = dre.shape[1] // B
    out = []
    for d in (dre, dim):
        d = d.reshape(4, B, mp, NPAD)[:, :, :M, :N]
        out.append(jnp.transpose(d, (1, 2, 3, 0)).reshape(B, M, N, 2, 2))
    return out[0], out[1]


def unpack_gain_grads(dre, dim, M: int, N: int):
    """Inverse of :func:`pack_gain_tables` for cotangents:
    (4, mp*nc, NPAD) pair -> complex-as-pair (M, N, 2, 2) re/im
    arrays (nc=1 tables)."""
    dre = jnp.transpose(dre[:, :M, :N], (1, 2, 0)).reshape(M, N, 2, 2)
    dim = jnp.transpose(dim[:, :M, :N], (1, 2, 0)).reshape(M, N, 2, 2)
    return dre, dim


# Instrumented jitted entry for eager callers and bench: ``tile`` and
# ``max_rows`` are compile-time grid parameters, so changing either is
# a visible recompile in the obs/perf compile counter.
from sagecal_tpu.obs.perf import instrumented_jit  # noqa: E402

fused_predict_packed_chunked_jit = instrumented_jit(
    fused_predict_packed_chunked, name="fused_predict_packed_chunked",
    static_argnames=("tile", "max_rows"))

fused_cost_packed_chunked_jit = instrumented_jit(
    fused_cost_packed_chunked, name="fused_cost_packed_chunked",
    static_argnames=("tile", "max_rows"))
