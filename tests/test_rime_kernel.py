"""Fused RIME Pallas kernel vs a direct einsum oracle (values + grads).

Runs the kernel in interpreter mode on CPU (the TPU compiles the same
kernel); the oracle evaluates ``V = sum_m Jp C Jq^H`` densely from the
same packed inputs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sagecal_tpu.ops.rime_kernel import (
    NPAD,
    fused_predict_packed,
    pack_gain_tables,
    pad_to,
    unpack_gain_grads,
)

TILE, MC = 128, 8  # cluster axis padded to a multiple of 8 (sublanes)


def _random_problem(seed=0, M=3, N=6, F=2, rows=200):
    rng = np.random.default_rng(seed)
    mp = pad_to(M, MC)
    rowsp = pad_to(rows, TILE)
    jones = rng.standard_normal((M, N, 2, 2)) + 1j * rng.standard_normal(
        (M, N, 2, 2)
    )
    coh = rng.standard_normal((M, F, 4, rows)) + 1j * rng.standard_normal(
        (M, F, 4, rows)
    )
    ant_p = rng.integers(0, N - 1, rows)
    ant_q = ant_p + rng.integers(1, N - ant_p)  # p < q < N
    coh_ri = np.zeros((mp, F, 8, rowsp), np.float32)
    coh_ri[:M, :, :4, :rows] = coh.real
    coh_ri[:M, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    return jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp


def _oracle_model(jones, coh, ant_p, ant_q):
    """V_ij(f, r) = sum_m sum_ab Jp_ia C_ab conj(Jq_jb)."""
    jp = jones[:, ant_p]  # (M, rows, 2, 2)
    jq = jones[:, ant_q]
    c = np.moveaxis(coh, -1, 1).reshape(coh.shape[0], -1, coh.shape[1], 2, 2)
    # c: (M, rows, F, 2, 2)
    v = np.einsum("mria,mrfab,mrjb->frij", jp, c, jq.conj())
    return v.reshape(coh.shape[1], -1, 4).transpose(0, 2, 1)  # (F, 4, rows)


def test_forward_matches_oracle():
    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp = _random_problem()
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    out = fused_predict_packed(
        tab_re, tab_im, jnp.asarray(coh_ri), jnp.asarray(antp),
        jnp.asarray(antq), TILE,
    )
    out = np.asarray(out)
    rows = coh.shape[-1]
    want = _oracle_model(jones, coh, ant_p, ant_q)
    np.testing.assert_allclose(out[:, :4, :rows], want.real, rtol=0, atol=2e-4)
    np.testing.assert_allclose(out[:, 4:, :rows], want.imag, rtol=0, atol=2e-4)
    # padded rows carry zero coherencies -> zero model
    np.testing.assert_array_equal(out[:, :, rows:], 0.0)


def test_gradients_match_autodiff_oracle():
    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp = _random_problem(
        seed=1
    )
    M, N = jones.shape[0], jones.shape[1]
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((coh.shape[1], 8, rowsp)), jnp.float32)
    coh_j = jnp.asarray(coh_ri)
    antp_j, antq_j = jnp.asarray(antp), jnp.asarray(antq)

    def loss_kernel(tab_re, tab_im):
        m = fused_predict_packed(tab_re, tab_im, coh_j, antp_j, antq_j,
                                 TILE)
        return jnp.sum(w * m) + jnp.sum(jnp.cos(m) * w)

    def loss_xla(tab_re, tab_im):
        """Same math as the kernel, in plain XLA, from the same packing."""
        tab = (tab_re + 1j * tab_im)[:, :M, :N]  # (4, M, N)
        jns = jnp.transpose(tab, (1, 2, 0)).reshape(M, N, 2, 2)
        jp = jns[:, antp_j[0, :]]  # (M, rowsp, 2, 2)
        jq = jns[:, antq_j[0, :]]
        c = jax.lax.complex(coh_j[:M, :, :4, :], coh_j[:M, :, 4:, :])
        c = jnp.moveaxis(c, -1, 1).reshape(M, rowsp, c.shape[1], 2, 2)
        v = jnp.einsum("mria,mrfab,mrjb->frij", jp, c, jq.conj())
        v = v.reshape(c.shape[2], rowsp, 4).transpose(0, 2, 1)
        m = jnp.concatenate([jnp.real(v), jnp.imag(v)], axis=1)
        return jnp.sum(w * m) + jnp.sum(jnp.cos(m) * w)

    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    gk = jax.grad(loss_kernel, argnums=(0, 1))(tab_re, tab_im)
    gx = jax.grad(loss_xla, argnums=(0, 1))(tab_re, tab_im)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gx[0]),
                               rtol=0, atol=5e-3)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gx[1]),
                               rtol=0, atol=5e-3)
    # padded table rows/cols receive zero gradient
    dre, dim = unpack_gain_grads(*gk, M, N)
    assert np.all(np.isfinite(np.asarray(dre)))
    np.testing.assert_array_equal(np.asarray(gk[0])[:, M:, :], 0.0)
    np.testing.assert_array_equal(np.asarray(gk[0])[:, :, N:], 0.0)


@pytest.mark.parametrize("F", [1, 2])
def test_forward_multi_freq_shapes(F):
    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp = _random_problem(
        seed=3, F=F, rows=130,
    )
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    out = fused_predict_packed(
        tab_re, tab_im, jnp.asarray(coh_ri), jnp.asarray(antp),
        jnp.asarray(antq), TILE,
    )
    assert out.shape == (F, 8, rowsp)
    want = _oracle_model(jones, coh, ant_p, ant_q)
    np.testing.assert_allclose(
        np.asarray(out)[:, :4, : coh.shape[-1]], want.real, atol=2e-4
    )


def test_hybrid_chunks_match_oracle():
    """nchunk>1 (reference hybrid solutions, lmfit.c:86-87): per-row
    chunk selection of gains, values + grads vs the dense oracle."""
    from sagecal_tpu.ops.rime_kernel import fused_predict_packed_hybrid

    rng = np.random.default_rng(5)
    M, N, F, rows, nc = 3, 6, 2, 200, 3
    mp = pad_to(M, MC)
    rowsp = pad_to(rows, TILE)
    jones = rng.standard_normal((M, nc, N, 2, 2)) + 1j * rng.standard_normal(
        (M, nc, N, 2, 2)
    )
    coh = rng.standard_normal((M, F, 4, rows)) + 1j * rng.standard_normal(
        (M, F, 4, rows)
    )
    ant_p = rng.integers(0, N - 1, rows)
    ant_q = ant_p + rng.integers(1, N - ant_p)
    cmap_full = rng.integers(0, nc, (M, rows)).astype(np.int32)

    coh_ri = np.zeros((mp, F, 8, rowsp), np.float32)
    coh_ri[:M, :, :4, :rows] = coh.real
    coh_ri[:M, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    cmap = np.zeros((mp, rowsp), np.int32)
    cmap[:M, :rows] = cmap_full

    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    w = jnp.asarray(rng.standard_normal((F, 8, rowsp)), jnp.float32)
    coh_j, antp_j, antq_j = map(jnp.asarray, (coh_ri, antp, antq))
    cmap_j = jnp.asarray(cmap)

    def loss_kernel(tre, tim):
        m = fused_predict_packed_hybrid(tre, tim, coh_j, antp_j, antq_j,
                                        cmap_j, nc, TILE)
        return jnp.sum(w * m * m)

    out = fused_predict_packed_hybrid(tab_re, tab_im, coh_j, antp_j,
                                      antq_j, cmap_j, nc, TILE)

    # dense oracle with per-(cluster,row) chunk gain selection
    jp = jones[np.arange(M)[:, None], cmap_full, ant_p[None, :]]  # (M,rows,2,2)
    jq = jones[np.arange(M)[:, None], cmap_full, ant_q[None, :]]
    c = np.moveaxis(coh, -1, 1).reshape(M, rows, F, 2, 2)
    v = np.einsum("mria,mrfab,mrjb->frij", jp, c, jq.conj())
    want = v.reshape(F, rows, 4).transpose(0, 2, 1)
    got = np.asarray(out)
    np.testing.assert_allclose(got[:, :4, :rows], want.real, atol=3e-4)
    np.testing.assert_allclose(got[:, 4:, :rows], want.imag, atol=3e-4)

    # grads: kernel custom-vjp vs autodiff of an XLA replica of the
    # same packed computation
    def loss_xla(tre, tim):
        tab = (tre + 1j * tim)[:, : M * nc, :N].reshape(4, M, nc, N)
        jns = jnp.transpose(tab, (1, 2, 3, 0)).reshape(M, nc, N, 2, 2)
        cm = jnp.asarray(cmap_full)
        jp_ = jns[jnp.arange(M)[:, None], cm, jnp.asarray(ant_p)[None, :]]
        jq_ = jns[jnp.arange(M)[:, None], cm, jnp.asarray(ant_q)[None, :]]
        cc = jax.lax.complex(coh_j[:M, :, :4, :rows],
                             coh_j[:M, :, 4:, :rows])
        cc = jnp.moveaxis(cc, -1, 1).reshape(M, rows, F, 2, 2)
        vv = jnp.einsum("mria,mrfab,mrjb->frij", jp_, cc, jq_.conj())
        vv = vv.reshape(F, rows, 4).transpose(0, 2, 1)
        m = jnp.concatenate([jnp.real(vv), jnp.imag(vv)], axis=1)
        m = jnp.pad(m, ((0, 0), (0, 0), (0, rowsp - rows)))
        return jnp.sum(w * m * m)

    gk = jax.grad(loss_kernel, argnums=(0, 1))(tab_re, tab_im)
    gx = jax.grad(loss_xla, argnums=(0, 1))(tab_re, tab_im)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gx[0]),
                               atol=5e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gx[1]),
                               atol=5e-2, rtol=1e-3)


@pytest.mark.parametrize(
    "nchunks",
    [pytest.param([1, 1], id="plain"),
     pytest.param([2, 1], id="hybrid", marks=pytest.mark.slow)],
)
def test_sagefit_fused_joint_pass_matches_xla(nchunks):
    """SageConfig(use_fused_predict=True): the joint-LBFGS pass through
    the kernel lands on the same solution as the XLA predict path (f32,
    small scene; the hybrid chunk-map case is slow-marked — interpret
    mode pays a second large compile)."""
    from sagecal_tpu.core.types import identity_jones, jones_to_params
    from sagecal_tpu.io.simulate import (
        corrupt_and_observe, make_visdata, random_jones,
    )
    from sagecal_tpu.ops.rime import point_source_batch
    from sagecal_tpu.solvers.sage import (
        SM_LM_LBFGS, SageConfig, build_cluster_data, sagefit,
    )

    f0 = 150e6
    data = make_visdata(nstations=6, tilesz=2, nchan=1, freq0=f0,
                        dtype=np.float32, seed=2)
    clusters = [
        point_source_batch([0.02], [0.01], [2.0], f0=f0,
                           dtype=jnp.float32),
        point_source_batch([-0.01], [0.02], [1.5], f0=f0,
                           dtype=jnp.float32),
    ]
    jt = random_jones(2, 6, seed=3, amp=0.1, dtype=np.complex64)
    data = corrupt_and_observe(data, clusters, jones=jt, noise_sigma=0.0)
    cdata = build_cluster_data(data, clusters, nchunks, fdelta=0.0)
    ncm = max(nchunks)
    p0 = jones_to_params(
        jnp.broadcast_to(identity_jones(6, jnp.complex64),
                         (2, ncm, 6, 2, 2))
    )
    base = dict(max_emiter=2, max_iter=10, max_lbfgs=15,
                solver_mode=SM_LM_LBFGS, randomize=False)
    r_xla = sagefit(data, cdata, p0, SageConfig(**base))
    r_fus = sagefit(data, cdata, p0,
                    SageConfig(use_fused_predict=True, **base))
    assert float(r_fus.res_1) < 0.2 * float(r_fus.res_0)
    np.testing.assert_allclose(float(r_fus.res_1), float(r_xla.res_1),
                               rtol=5e-3, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_fus.p), np.asarray(r_xla.p),
                               atol=5e-3)


def test_chunked_matches_unchunked():
    """fused_predict_packed_chunked (the big-row production path: each
    Mosaic grid kept short, lax.map over row chunks — round-5 hardware
    finding: compile time grows with grid length and Mp*tile VMEM-caps
    at 16 MB) must match the single-grid kernel in values and gain-table
    gradients."""
    from sagecal_tpu.ops.rime_kernel import (
        chunked_rowsp,
        fused_predict_packed_chunked,
    )

    max_rows = 4 * TILE
    rows = 9 * TILE + 37  # forces 3 chunks after padding
    rowsp = chunked_rowsp(rows, TILE, max_rows)
    assert rowsp % TILE == 0 and rowsp >= rows
    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, _ = _random_problem(
        seed=3, rows=rows
    )
    coh_ri = np.zeros((mp, coh.shape[1], 8, rowsp), np.float32)
    coh_ri[:3, :, :4, :rows] = coh.real
    coh_ri[:3, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args = (jnp.asarray(coh_ri), jnp.asarray(antp), jnp.asarray(antq))

    ref = fused_predict_packed(tab_re, tab_im, *args, TILE)
    got = fused_predict_packed_chunked(tab_re, tab_im, *args, TILE, max_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    g_ref = jax.grad(
        lambda a, b: jnp.sum(fused_predict_packed(a, b, *args, TILE) ** 2),
        argnums=(0, 1),
    )(tab_re, tab_im)
    g_got = jax.grad(
        lambda a, b: jnp.sum(
            fused_predict_packed_chunked(a, b, *args, TILE, max_rows) ** 2
        ),
        argnums=(0, 1),
    )(tab_re, tab_im)
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-3)


def test_chunked_rowsp_values():
    from sagecal_tpu.ops.rime_kernel import chunked_rowsp

    # short rows: plain tile padding
    assert chunked_rowsp(1000, 128, 512) == 1024
    # north-star rows: 4 equal chunks of 28416 (R=111 at tile 256)
    assert chunked_rowsp(113460, 256, 32768) == 113664
    assert chunked_rowsp(113460, 256, 32768) % 4 == 0


def test_bf16_coherencies_close_to_f32():
    """The kernel upcasts bfloat16 coherency planes to f32 at the VMEM
    load (_load_coh_planes) — the bandwidth-bound production knob.
    bf16 carries ~3 significant digits; the result must track the f32
    kernel to that precision, and gradients must flow."""
    import ml_dtypes

    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp = _random_problem(
        seed=7
    )
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args32 = (jnp.asarray(coh_ri), jnp.asarray(antp), jnp.asarray(antq))
    args16 = (jnp.asarray(coh_ri.astype(ml_dtypes.bfloat16)),) + args32[1:]

    ref = np.asarray(fused_predict_packed(tab_re, tab_im, *args32, TILE))
    got = np.asarray(fused_predict_packed(tab_re, tab_im, *args16, TILE))
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 2e-2

    g32 = jax.grad(
        lambda a, b: jnp.sum(fused_predict_packed(a, b, *args32, TILE) ** 2),
        argnums=(0, 1),
    )(tab_re, tab_im)
    g16 = jax.grad(
        lambda a, b: jnp.sum(fused_predict_packed(a, b, *args16, TILE) ** 2),
        argnums=(0, 1),
    )(tab_re, tab_im)
    for a, b in zip(g32, g16):
        s = np.abs(np.asarray(a)).max()
        assert np.abs(np.asarray(a) - np.asarray(b)).max() / s < 3e-2


def test_hybrid_chunked_matches_unchunked():
    """Hybrid (nc>1) chunked wrapper must match the single-grid hybrid
    kernel — cmap slices ride with the per-row arrays."""
    from sagecal_tpu.ops.rime_kernel import (
        chunked_rowsp,
        fused_predict_packed_hybrid,
        fused_predict_packed_hybrid_chunked,
    )

    nc, max_rows = 2, 4 * TILE
    rows = 7 * TILE + 19
    rowsp = chunked_rowsp(rows, TILE, max_rows)
    rng = np.random.default_rng(11)
    M, N, F = 3, 6, 2
    mp = pad_to(M, MC)
    jones = rng.standard_normal((M, nc, N, 2, 2)) + 1j * rng.standard_normal(
        (M, nc, N, 2, 2)
    )
    coh = rng.standard_normal((M, F, 4, rows)) + 1j * rng.standard_normal(
        (M, F, 4, rows)
    )
    ant_p = rng.integers(0, N - 1, rows)
    ant_q = ant_p + rng.integers(1, N - ant_p)
    coh_ri = np.zeros((mp, F, 8, rowsp), np.float32)
    coh_ri[:M, :, :4, :rows] = coh.real
    coh_ri[:M, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    cmap = np.zeros((mp, rowsp), np.int32)
    cmap[:, :rows] = rng.integers(0, nc, rows)[None, :]
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args = (jnp.asarray(coh_ri), jnp.asarray(antp), jnp.asarray(antq),
            jnp.asarray(cmap))

    ref = fused_predict_packed_hybrid(tab_re, tab_im, *args, nc, TILE)
    got = fused_predict_packed_hybrid_chunked(
        tab_re, tab_im, *args, nc, TILE, max_rows
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    g_ref = jax.grad(
        lambda a, b: jnp.sum(
            fused_predict_packed_hybrid(a, b, *args, nc, TILE) ** 2
        ),
        argnums=(0, 1),
    )(tab_re, tab_im)
    g_got = jax.grad(
        lambda a, b: jnp.sum(
            fused_predict_packed_hybrid_chunked(
                a, b, *args, nc, TILE, max_rows) ** 2
        ),
        argnums=(0, 1),
    )(tab_re, tab_im)
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-3)


# --------------------------------------------------- fused objective kernel


def _cost_problem(seed=0, M=3, N=6, F=2, rows=200, drop=0.15):
    """Packed problem + visibilities and a mask with random drops (and
    zeros on every padded row)."""
    (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp,
     rowsp) = _random_problem(seed=seed, M=M, N=N, F=F, rows=rows)
    rng = np.random.default_rng(seed + 100)
    vis_ri = np.zeros((F, 8, rowsp), np.float32)
    vis_ri[:, :, :rows] = rng.standard_normal((F, 8, rows))
    mask_p = np.zeros((F, rowsp), np.float32)
    mask_p[:, :rows] = (rng.random((F, rows)) > drop).astype(np.float32)
    return (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp,
            vis_ri, mask_p)


def _xla_cost(tab_re, tab_im, coh_j, antp_j, antq_j, vis_j, mask_j,
              M, N, nu):
    """The solver's XLA cost from the same packed inputs (sage.py
    joint-cost math: residual -> per-complex-component |.|^2 ->
    Student's-t log1p or Gaussian sum)."""
    rowsp = coh_j.shape[-1]
    tab = (tab_re + 1j * tab_im)[:, :M, :N]
    jns = jnp.transpose(tab, (1, 2, 0)).reshape(M, N, 2, 2)
    jp = jns[:, antp_j[0, :]]
    jq = jns[:, antq_j[0, :]]
    c = jax.lax.complex(coh_j[:M, :, :4, :], coh_j[:M, :, 4:, :])
    c = jnp.moveaxis(c, -1, 1).reshape(M, rowsp, c.shape[1], 2, 2)
    v = jnp.einsum("mria,mrfab,mrjb->frij", jp, c, jq.conj())
    v = v.reshape(c.shape[2], rowsp, 4).transpose(0, 2, 1)
    model = jnp.concatenate([jnp.real(v), jnp.imag(v)], axis=1)
    d = (vis_j - model) * mask_j[:, None, :]
    e2 = d[:, :4, :] ** 2 + d[:, 4:, :] ** 2
    if nu is None:
        return jnp.sum(e2)
    return jnp.sum(jnp.log1p(e2 / nu))


@pytest.mark.parametrize("nu", [None, 5.0], ids=["gaussian", "robust"])
def test_fused_cost_and_grad_match_xla(nu):
    """Acceptance bar: fused objective cost AND gain-table gradient
    within 1e-5 relative of the XLA cost from identical packed inputs
    (Gaussian and Student's-t nu=5), with masked and padded rows."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed

    (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp,
     vis_ri, mask_p) = _cost_problem(seed=4)
    M, N = jones.shape[0], jones.shape[1]
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    coh_j, antp_j, antq_j, vis_j, mask_j = map(
        jnp.asarray, (coh_ri, antp, antq, vis_ri, mask_p))

    def ck(a, b):
        return fused_cost_packed(a, b, coh_j, antp_j, antq_j, vis_j,
                                 mask_j, nu, TILE)

    def cx(a, b):
        return _xla_cost(a, b, coh_j, antp_j, antq_j, vis_j, mask_j,
                         M, N, nu)

    vk, gk = jax.value_and_grad(ck, argnums=(0, 1))(tab_re, tab_im)
    vx, gx = jax.value_and_grad(cx, argnums=(0, 1))(tab_re, tab_im)
    assert abs(float(vk) - float(vx)) / abs(float(vx)) <= 1e-5
    for a, b in zip(gk, gx):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() / np.abs(b).max() <= 1e-5
        # padded table rows/cols receive zero gradient
        np.testing.assert_array_equal(a[:, M:, :], 0.0)
        np.testing.assert_array_equal(a[:, :, N:], 0.0)


def test_fused_cost_fully_masked_rows_contribute_zero():
    """A fully-masked problem costs exactly 0 (robust: log1p(0)=0), and
    the padded-row tail beyond `rows` never contributes."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed

    (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp,
     vis_ri, mask_p) = _cost_problem(seed=5)
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args = tuple(map(jnp.asarray, (coh_ri, antp, antq, vis_ri)))
    zero_mask = jnp.zeros_like(jnp.asarray(mask_p))
    for nu in (None, 5.0):
        c = fused_cost_packed(tab_re, tab_im, *args, zero_mask, nu, TILE)
        assert float(c) == 0.0
    # padded tail: replicating garbage visibilities beyond `rows` does
    # not change the cost (their mask is 0)
    rows = coh.shape[-1]
    vis_bad = np.array(vis_ri)
    vis_bad[:, :, rows:] = 1e6
    c_ref = fused_cost_packed(tab_re, tab_im, *args, jnp.asarray(mask_p),
                              5.0, TILE)
    c_bad = fused_cost_packed(tab_re, tab_im, args[0], args[1], args[2],
                              jnp.asarray(vis_bad), jnp.asarray(mask_p),
                              5.0, TILE)
    assert float(c_ref) == float(c_bad)


@pytest.mark.parametrize("rows", [TILE, TILE + 1, 130],
                         ids=["exact-tile", "tile+1", "short"])
def test_fused_cost_row_padding_edges(rows):
    """Mp (cluster) and rowsp (row) padding edges: exact-tile rows,
    one-over-tile, and short rows all match the XLA cost."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed

    (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp,
     vis_ri, mask_p) = _cost_problem(seed=6, M=5, rows=rows)
    M, N = jones.shape[0], jones.shape[1]
    assert mp == 8 and mp > M  # cluster axis genuinely padded
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    coh_j, antp_j, antq_j, vis_j, mask_j = map(
        jnp.asarray, (coh_ri, antp, antq, vis_ri, mask_p))
    ck = fused_cost_packed(tab_re, tab_im, coh_j, antp_j, antq_j,
                           vis_j, mask_j, 5.0, TILE)
    cx = _xla_cost(tab_re, tab_im, coh_j, antp_j, antq_j, vis_j, mask_j,
                   M, N, 5.0)
    assert abs(float(ck) - float(cx)) / abs(float(cx)) <= 1e-5


def test_fused_cost_chunked_matches_unchunked():
    from sagecal_tpu.ops.rime_kernel import (
        chunked_rowsp,
        fused_cost_packed,
        fused_cost_packed_chunked,
    )

    max_rows = 4 * TILE
    rows = 9 * TILE + 37
    rowsp = chunked_rowsp(rows, TILE, max_rows)
    (jones, coh, ant_p, ant_q, _, _, _, mp,
     _) = _random_problem(seed=8, rows=rows)
    rng = np.random.default_rng(9)
    F = coh.shape[1]
    coh_ri = np.zeros((mp, F, 8, rowsp), np.float32)
    coh_ri[:3, :, :4, :rows] = coh.real
    coh_ri[:3, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    vis_ri = np.zeros((F, 8, rowsp), np.float32)
    vis_ri[:, :, :rows] = rng.standard_normal((F, 8, rows))
    mask_p = np.zeros((F, rowsp), np.float32)
    mask_p[:, :rows] = 1.0
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args = tuple(map(jnp.asarray, (coh_ri, antp, antq, vis_ri, mask_p)))

    for nu in (None, 5.0):
        ref = jax.value_and_grad(
            lambda a, b: fused_cost_packed(a, b, *args, nu, TILE),
            argnums=(0, 1))(tab_re, tab_im)
        got = jax.value_and_grad(
            lambda a, b: fused_cost_packed_chunked(
                a, b, *args, nu, TILE, max_rows),
            argnums=(0, 1))(tab_re, tab_im)
        np.testing.assert_allclose(float(got[0]), float(ref[0]),
                                   rtol=1e-6)
        for r, g in zip(ref[1], got[1]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-6)


def test_fused_cost_hybrid_matches_xla():
    """nc>1 objective: per-row chunk gain selection, cost + grads vs
    the XLA cost with the same cmap-selected gains."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed_hybrid

    rng = np.random.default_rng(12)
    M, N, F, rows, nc = 3, 6, 2, 200, 3
    mp = pad_to(M, MC)
    rowsp = pad_to(rows, TILE)
    jones = rng.standard_normal((M, nc, N, 2, 2)) + 1j * rng.standard_normal(
        (M, nc, N, 2, 2))
    coh = rng.standard_normal((M, F, 4, rows)) + 1j * rng.standard_normal(
        (M, F, 4, rows))
    ant_p = rng.integers(0, N - 1, rows)
    ant_q = ant_p + rng.integers(1, N - ant_p)
    cmap_full = rng.integers(0, nc, (M, rows)).astype(np.int32)
    coh_ri = np.zeros((mp, F, 8, rowsp), np.float32)
    coh_ri[:M, :, :4, :rows] = coh.real
    coh_ri[:M, :, 4:, :rows] = coh.imag
    antp = np.zeros((1, rowsp), np.int32)
    antq = np.zeros((1, rowsp), np.int32)
    antp[0, :rows] = ant_p
    antq[0, :rows] = ant_q
    cmap = np.zeros((mp, rowsp), np.int32)
    cmap[:M, :rows] = cmap_full
    vis_ri = np.zeros((F, 8, rowsp), np.float32)
    vis_ri[:, :, :rows] = rng.standard_normal((F, 8, rows))
    mask_p = np.zeros((F, rowsp), np.float32)
    mask_p[:, :rows] = 1.0

    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    coh_j, antp_j, antq_j, vis_j, mask_j, cmap_j = map(
        jnp.asarray, (coh_ri, antp, antq, vis_ri, mask_p, cmap))

    def ck(a, b):
        return fused_cost_packed_hybrid(a, b, coh_j, antp_j, antq_j,
                                        vis_j, mask_j, cmap_j, nc, 5.0,
                                        TILE)

    def cx(a, b):
        tab = (a + 1j * b)[:, : M * nc, :N].reshape(4, M, nc, N)
        jns = jnp.transpose(tab, (1, 2, 3, 0)).reshape(M, nc, N, 2, 2)
        cm = jnp.asarray(cmap_full)
        jp = jns[jnp.arange(M)[:, None], cm, jnp.asarray(ant_p)[None, :]]
        jq = jns[jnp.arange(M)[:, None], cm, jnp.asarray(ant_q)[None, :]]
        cc = jax.lax.complex(coh_j[:M, :, :4, :rows],
                             coh_j[:M, :, 4:, :rows])
        cc = jnp.moveaxis(cc, -1, 1).reshape(M, rows, F, 2, 2)
        vv = jnp.einsum("mria,mrfab,mrjb->frij", jp, cc, jq.conj())
        vv = vv.reshape(F, rows, 4).transpose(0, 2, 1)
        model = jnp.concatenate([jnp.real(vv), jnp.imag(vv)], axis=1)
        model = jnp.pad(model, ((0, 0), (0, 0), (0, rowsp - rows)))
        d = (vis_j - model) * mask_j[:, None, :]
        e2 = d[:, :4, :] ** 2 + d[:, 4:, :] ** 2
        return jnp.sum(jnp.log1p(e2 / 5.0))

    vk, gk = jax.value_and_grad(ck, argnums=(0, 1))(tab_re, tab_im)
    vx, gx = jax.value_and_grad(cx, argnums=(0, 1))(tab_re, tab_im)
    assert abs(float(vk) - float(vx)) / abs(float(vx)) <= 1e-5
    for a, b in zip(gk, gx):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() / np.abs(b).max() <= 1e-5


def test_fused_objective_entry_matches_solver_residual():
    """ops.residual.fused_objective (the production eager entry) agrees
    with the XLA predict + residual + robust-sum path on VisData."""
    from sagecal_tpu.core.types import jones_to_params
    from sagecal_tpu.io.simulate import (
        corrupt_and_observe, make_visdata, random_jones,
    )
    from sagecal_tpu.ops.residual import fused_objective
    from sagecal_tpu.ops.rime import point_source_batch
    from sagecal_tpu.solvers.sage import build_cluster_data, predict_full_model

    f0 = 150e6
    data = make_visdata(nstations=6, tilesz=2, nchan=1, freq0=f0,
                        dtype=np.float32, seed=13)
    clusters = [
        point_source_batch([0.02], [0.01], [2.0], f0=f0, dtype=jnp.float32),
        point_source_batch([-0.01], [0.02], [1.5], f0=f0, dtype=jnp.float32),
    ]
    jt = random_jones(2, 6, seed=14, amp=0.2, dtype=np.complex64)
    data = corrupt_and_observe(data, clusters, jones=jt, noise_sigma=0.05,
                               seed=15)
    cdata = build_cluster_data(data, clusters, [1, 1], fdelta=0.0)
    p = jones_to_params(jt)[:, None, :]

    model = predict_full_model(p, cdata, data)
    d = (data.vis - model) * data.mask
    e2 = jnp.real(d) ** 2 + jnp.imag(d) ** 2
    for nu, want in ((None, jnp.sum(e2)),
                     (5.0, jnp.sum(jnp.log1p(e2 / 5.0)))):
        got = fused_objective(data, cdata, p, nu=nu)
        assert (abs(float(got) - float(want)) / abs(float(want))
                <= 1e-5)


def test_donated_lbfgs_entry_bit_identical_and_consumes_input():
    """lbfgs_fit_jit donates its carry (p0, memory): the solve must be
    bit-identical to an undonated jit of the same solver, and the
    donated input buffer must actually be consumed."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed
    from sagecal_tpu.solvers.lbfgs import lbfgs_fit, lbfgs_fit_jit

    (jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp,
     vis_ri, mask_p) = _cost_problem(seed=21)
    args = tuple(map(jnp.asarray, (coh_ri, antp, antq, vis_ri, mask_p)))
    nparam = int(np.prod((4, mp, NPAD)))

    def cost_fn(p):
        tre = p[:nparam].reshape(4, mp, NPAD)
        tim = p[nparam:].reshape(4, mp, NPAD)
        return fused_cost_packed(tre, tim, *args, 5.0, TILE)

    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    p0_host = np.concatenate(
        [np.asarray(tab_re).ravel(), np.asarray(tab_im).ravel()])

    plain = jax.jit(
        lbfgs_fit,
        static_argnames=("cost_fn", "grad_fn", "itmax", "M", "minibatch",
                         "vg_fn"))
    p_ref = jnp.asarray(p0_host)
    r_ref = plain(cost_fn, None, p_ref, itmax=5, M=3)

    p_don = jnp.asarray(p0_host)
    r_don = lbfgs_fit_jit(cost_fn, None, p_don, itmax=5, M=3)

    np.testing.assert_array_equal(np.asarray(r_don.p), np.asarray(r_ref.p))
    np.testing.assert_array_equal(np.asarray(r_don.cost),
                                  np.asarray(r_ref.cost))
    np.testing.assert_array_equal(np.asarray(r_don.memory.s),
                                  np.asarray(r_ref.memory.s))
    # the donated buffer is gone; the undonated one survives
    assert p_don.is_deleted()
    assert not p_ref.is_deleted()


def test_sky_gradient_fails_loudly():
    """Gradients w.r.t. the coherency stack through the chunked fused
    wrappers must raise FusedSkyGradientError — never return silent
    zeros (the backward only emits gain-table cotangents; sky-model
    refinement must route through the XLA predict path)."""
    from sagecal_tpu.ops.rime_kernel import (
        FUSED_COHERENCY_COTANGENT,
        FusedSkyGradientError,
        fused_cost_packed_chunked,
        fused_predict_packed_chunked,
    )

    assert FUSED_COHERENCY_COTANGENT is False
    jones, coh, ant_p, ant_q, coh_ri, antp, antq, mp, rowsp = _random_problem(
        seed=7
    )
    tab_re, tab_im = pack_gain_tables(jnp.asarray(jones), mp)
    args = (jnp.asarray(antp), jnp.asarray(antq))
    coh_j = jnp.asarray(coh_ri)

    # gain gradients still work (guard must not affect them)
    g = jax.grad(lambda a: jnp.sum(
        fused_predict_packed_chunked(a, tab_im, coh_j, *args, TILE,
                                     rowsp) ** 2))(tab_re)
    assert np.all(np.isfinite(np.asarray(g)))

    with pytest.raises(FusedSkyGradientError):
        jax.grad(lambda c: jnp.sum(
            fused_predict_packed_chunked(tab_re, tab_im, c, *args, TILE,
                                         rowsp) ** 2))(coh_j)

    vis_ri = jnp.asarray(
        np.random.default_rng(8).standard_normal(
            (coh.shape[1], 8, rowsp)), jnp.float32)
    mask_p = jnp.ones((coh.shape[1], rowsp), jnp.float32)
    with pytest.raises(FusedSkyGradientError):
        jax.grad(lambda c: fused_cost_packed_chunked(
            tab_re, tab_im, c, *args, vis_ri, mask_p, 5.0, TILE,
            rowsp))(coh_j)


# ------------------------------------------------ batched fused objective


def _batched_cost_problem(B=3, seed=30, M=3, N=6, F=2, rows=200):
    """B same-shape lanes SHARING baseline geometry (the batched
    kernel's layout contract) with per-lane Jones/coherencies/vis/mask;
    returns complex host arrays, ready for pack_*_batch."""
    rng = np.random.default_rng(seed)
    ant_p = rng.integers(0, N - 1, rows)
    ant_q = ant_p + rng.integers(1, N - ant_p)
    jones_b = rng.standard_normal((B, M, N, 2, 2)) + 1j * (
        rng.standard_normal((B, M, N, 2, 2)))
    coh_b = rng.standard_normal((B, M, F, 4, rows)) + 1j * (
        rng.standard_normal((B, M, F, 4, rows)))
    vis_b = rng.standard_normal((B, F, 4, rows)) + 1j * (
        rng.standard_normal((B, F, 4, rows)))
    mask_b = (rng.random((B, F, rows)) > 0.15).astype(np.float32)
    return jones_b, coh_b, vis_b, mask_b, ant_p, ant_q


def _pack_batch(jones_b, coh_b, vis_b, mask_b, ant_p, ant_q, valid=None):
    from sagecal_tpu.ops.rime_kernel import (
        pack_cost_inputs_batch, pack_gain_tables_batch,
    )

    M = coh_b.shape[1]
    mp = pad_to(M, MC)
    vis_ri, mask_p, coh_ri, antp, antq = pack_cost_inputs_batch(
        jnp.asarray(vis_b, jnp.complex64), jnp.asarray(mask_b),
        jnp.asarray(coh_b, jnp.complex64), jnp.asarray(ant_p),
        jnp.asarray(ant_q), TILE,
        valid=None if valid is None else jnp.asarray(valid))
    tre, tim = pack_gain_tables_batch(jnp.asarray(jones_b, jnp.complex64),
                                      mp)
    return tre, tim, coh_ri, antp, antq, vis_ri, mask_p, mp


@pytest.mark.parametrize("nu", [None, 5.0], ids=["gaussian", "robust"])
def test_batched_fused_cost_and_grad_match_vmapped_xla(nu):
    """Acceptance bar (batched): per-lane cost AND batched-table
    gradient of the one-grid batched objective within 1e-5 relative of
    the per-lane XLA cost evaluated from identical packed inputs."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed_batch

    B, M, N = 3, 3, 6
    jones_b, coh_b, vis_b, mask_b, ant_p, ant_q = _batched_cost_problem(
        B=B, seed=31, M=M, N=N)
    tre, tim, coh_ri, antp, antq, vis_ri, mask_p, mp = _pack_batch(
        jones_b, coh_b, vis_b, mask_b, ant_p, ant_q)
    w = jnp.asarray(np.random.default_rng(32).uniform(0.5, 1.5, B),
                    jnp.float32)

    def ck(a, b):
        return fused_cost_packed_batch(a, b, coh_ri, antp, antq, vis_ri,
                                       mask_p, nu, TILE)

    def lane_x(a, b, lane):
        s = slice(lane * mp, (lane + 1) * mp)
        return _xla_cost(a[:, s, :], b[:, s, :], coh_ri[s], antp, antq,
                         vis_ri[lane], mask_p[lane], M, N, nu)

    # per-lane values
    vk = np.asarray(ck(tre, tim))
    assert vk.shape == (B,)
    for lane in range(B):
        vx = float(lane_x(tre, tim, lane))
        assert abs(float(vk[lane]) - vx) / abs(vx) <= 1e-5

    # batched-table gradient of a per-lane-weighted total (the serve
    # backward applies per-lane upstream cotangents the same way)
    gk = jax.grad(lambda a, b: jnp.sum(w * ck(a, b)),
                  argnums=(0, 1))(tre, tim)
    gx = jax.grad(
        lambda a, b: sum(w[lane] * lane_x(a, b, lane)
                         for lane in range(B)),
        argnums=(0, 1))(tre, tim)
    for a, b in zip(gk, gx):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() / np.abs(b).max() <= 1e-5
        # padded cluster rows / station columns receive zero gradient
        for lane in range(B):
            np.testing.assert_array_equal(
                a[:, lane * mp + M:(lane + 1) * mp, :], 0.0)
        np.testing.assert_array_equal(a[:, :, N:], 0.0)


def test_batched_fused_padded_lanes_zero_cost_and_cotangent():
    """The replication-padded ragged-lane guard: a lane zeroed via
    ``valid`` costs EXACTLY 0 (Gaussian and robust) and contributes an
    exactly-zero gain cotangent, while the real lanes are bit-identical
    to the same pack without the guard."""
    from sagecal_tpu.ops.rime_kernel import fused_cost_packed_batch

    B, M = 3, 3
    jones_b, coh_b, vis_b, mask_b, ant_p, ant_q = _batched_cost_problem(
        B=B, seed=33, M=M)
    # lane 1 is the replicated pad
    valid = np.array([True, False, True])
    packed_v = _pack_batch(jones_b, coh_b, vis_b, mask_b, ant_p, ant_q,
                           valid=valid)
    packed_r = _pack_batch(jones_b, coh_b, vis_b, mask_b, ant_p, ant_q)
    tre, tim, coh_ri, antp, antq, vis_ri_v, mask_v, mp = packed_v
    vis_ri_r, mask_r = packed_r[5], packed_r[6]

    for nu in (None, 5.0):
        cv = np.asarray(fused_cost_packed_batch(
            tre, tim, coh_ri, antp, antq, vis_ri_v, mask_v, nu, TILE))
        cr = np.asarray(fused_cost_packed_batch(
            tre, tim, coh_ri, antp, antq, vis_ri_r, mask_r, nu, TILE))
        assert float(cv[1]) == 0.0  # exactly zero, not merely small
        np.testing.assert_array_equal(cv[[0, 2]], cr[[0, 2]])

        gv = jax.grad(
            lambda a, b: jnp.sum(fused_cost_packed_batch(
                a, b, coh_ri, antp, antq, vis_ri_v, mask_v, nu, TILE)),
            argnums=(0, 1))(tre, tim)
        for g in gv:
            np.testing.assert_array_equal(
                np.asarray(g)[:, mp:2 * mp, :], 0.0)


def _batched_solve_problem(B=3, N=5, M=2, F=2, tilesz=2, seed=40):
    """B small same-geometry tiles as batched VisData/ClusterData plus
    (B, M, 1, 8N) f32 initial gains — the sagefit_packed_batch layout."""
    from sagecal_tpu.core.types import VisData
    from sagecal_tpu.solvers.sage import ClusterData

    nbase = N * (N - 1) // 2
    rows = nbase * tilesz
    pp, qq = np.triu_indices(N, 1)
    ant_p = np.tile(pp, tilesz).astype(np.int32)
    ant_q = np.tile(qq, tilesz).astype(np.int32)
    time_idx = np.repeat(np.arange(tilesz), nbase).astype(np.int32)

    def lane(s):
        r = np.random.default_rng(s)
        coh = (r.normal(size=(M, F, 4, rows))
               + 1j * r.normal(size=(M, F, 4, rows))).astype(np.complex64)
        vis = (r.normal(size=(F, 4, rows))
               + 1j * r.normal(size=(F, 4, rows))).astype(np.complex64)
        mask = np.ones((F, rows), np.float32)
        mask[:, ::7] = 0.0
        p0 = (np.tile(np.array([1, 0, 0, 0, 0, 0, 1, 0], np.float32), N)
              [None, None, :].repeat(M, 0)
              + 0.05 * r.normal(size=(M, 1, 8 * N)).astype(np.float32))
        return coh, vis, mask, p0

    lanes = [lane(seed + i) for i in range(B)]

    def mk(vis, mask, coh):
        data = VisData(
            u=jnp.zeros(rows, jnp.float32), v=jnp.zeros(rows, jnp.float32),
            w=jnp.zeros(rows, jnp.float32), ant_p=jnp.asarray(ant_p),
            ant_q=jnp.asarray(ant_q), vis=jnp.asarray(vis),
            mask=jnp.asarray(mask),
            freqs=jnp.full((F,), 150e6, jnp.float32),
            time_idx=jnp.asarray(time_idx), tilesz=tilesz, nbase=nbase,
            nstations=N)
        cdata = ClusterData(coh=jnp.asarray(coh),
                            chunk_map=jnp.zeros((M, rows), jnp.int32),
                            nchunk=jnp.ones((M,), jnp.int32))
        return data, cdata

    pairs = [mk(v, m, c) for c, v, m, _ in lanes]
    data_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                    *[p[0] for p in pairs])
    cdata_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[p[1] for p in pairs])
    p0_b = jnp.asarray(np.stack([l[3] for l in lanes]))
    return data_b, cdata_b, p0_b


@pytest.mark.parametrize("mode", [1, 3], ids=["gaussian", "robust"])
def test_batched_fused_solve_matches_vmapped_xla(mode):
    """Solve-level parity: the batched-fused route of
    sagefit_packed_batch agrees with the vmapped XLA route on gains and
    residuals (the routing the serve dispatch bakes into its cache
    entries)."""
    from sagecal_tpu.solvers.batched import (
        choose_batched_path, sagefit_packed_batch,
    )
    from sagecal_tpu.solvers.sage import SageConfig

    data_b, cdata_b, p0_b = _batched_solve_problem(seed=41)
    B = p0_b.shape[0]
    cfg = SageConfig(max_emiter=1, max_iter=2, max_lbfgs=6,
                     solver_mode=mode, use_fused_predict=True)
    path, reason = choose_batched_path(data_b, cdata_b, p0_b, cfg)
    assert path == "fused_batch", reason
    keys = jax.random.split(jax.random.PRNGKey(5), B)
    vr, vi = jnp.real(data_b.vis), jnp.imag(data_b.vis)
    cr, ci = jnp.real(cdata_b.coh), jnp.imag(cdata_b.coh)
    d0 = data_b.replace(vis=None)
    c0 = cdata_b._replace(coh=None)
    out_f = sagefit_packed_batch(d0, c0, vr, vi, cr, ci, p0_b, cfg, keys,
                                 batched_fused=True)
    out_x = sagefit_packed_batch(d0, c0, vr, vi, cr, ci, p0_b,
                                 cfg.replace(use_fused_predict=False),
                                 keys)
    assert float(jnp.max(jnp.abs(out_f.p - out_x.p))) <= 1e-4
    assert float(jnp.max(jnp.abs(out_f.res_1 - out_x.res_1))) <= 1e-5
    # both routes count each lane's solver steps (LM modes: no TR loops)
    for out in (out_f, out_x):
        assert {k: v.shape for k, v in out.steps.items()} == {
            k: (B,) for k in out.steps}
        assert int(jnp.sum(out.steps["tr_loops"])) == 0
        it = out.steps["lbfgs_iters"]
        assert bool(jnp.all((it > 0) & (it <= cfg.max_lbfgs)))


def test_batched_solve_donated_bit_identical_and_consumes_input():
    """sagefit_packed_batch_jit donates the batch gains carry: the
    batched-fused solve must be bit-identical to an undonated call of
    the same route, and the donated buffer must be consumed."""
    import functools

    from sagecal_tpu.solvers.batched import (
        sagefit_packed_batch, sagefit_packed_batch_jit,
    )
    from sagecal_tpu.solvers.sage import SageConfig

    data_b, cdata_b, p0_b = _batched_solve_problem(seed=42)
    B = p0_b.shape[0]
    cfg = SageConfig(max_emiter=1, max_iter=1, max_lbfgs=4,
                     solver_mode=1, use_fused_predict=True)
    keys = jax.random.split(jax.random.PRNGKey(6), B)
    vr, vi = jnp.real(data_b.vis), jnp.imag(data_b.vis)
    cr, ci = jnp.real(cdata_b.coh), jnp.imag(cdata_b.coh)
    d0 = data_b.replace(vis=None)
    c0 = cdata_b._replace(coh=None)

    plain = jax.jit(functools.partial(sagefit_packed_batch,
                                      batched_fused=True))
    p_ref = jnp.array(p0_b)
    r_ref = plain(d0, c0, vr, vi, cr, ci, p_ref, cfg, keys)

    p_don = jnp.array(p0_b)
    r_don = sagefit_packed_batch_jit(d0, c0, vr, vi, cr, ci, p_don, cfg,
                                     keys, batched_fused=True)

    np.testing.assert_array_equal(np.asarray(r_don.p), np.asarray(r_ref.p))
    np.testing.assert_array_equal(np.asarray(r_don.res_1),
                                  np.asarray(r_ref.res_1))
    assert p_don.is_deleted()
    assert not p_ref.is_deleted()


def test_batched_bucket_zero_recompile_across_batch_widths():
    """Same-bucket batches with different REAL lane counts (a full
    bucket, then a ragged one replication-padded to the same width)
    reuse ONE batched-fused executable: cache counters show a single
    miss and the instrumented entry a single compile."""
    from sagecal_tpu.obs.perf import perf_stats, reset_perf_stats
    from sagecal_tpu.obs.registry import telemetry
    from sagecal_tpu.serve.bucket import bucket_of, pad_indices
    from sagecal_tpu.serve.cache import ExecutableCache
    from sagecal_tpu.solvers.batched import (
        choose_batched_path, derive_lane_keys,
    )
    from sagecal_tpu.solvers.sage import SageConfig

    width = 2
    data_b, cdata_b, p0_b = _batched_solve_problem(B=width, seed=43)
    cfg = SageConfig(max_emiter=1, max_iter=1, max_lbfgs=4,
                     solver_mode=1, use_fused_predict=True)
    path, reason = choose_batched_path(data_b, cdata_b, p0_b, cfg)
    assert path == "fused_batch", reason

    data0 = jax.tree_util.tree_map(lambda x: x[0], data_b)
    cdata0 = jax.tree_util.tree_map(lambda x: x[0], cdata_b)
    bucket = bucket_of(data0, cdata0, np.asarray(p0_b[0]))
    vr, vi = jnp.real(data_b.vis), jnp.imag(data_b.vis)
    cr, ci = jnp.real(cdata_b.coh), jnp.imag(cdata_b.coh)
    d0 = data_b.replace(vis=None)
    c0 = cdata_b._replace(coh=None)

    def dispatch(fn, idx, valid):
        take = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.asarray(x)[np.asarray(idx)]), t)
        keys = derive_lane_keys(0, np.asarray(idx, np.uint32))
        out = fn(take(d0), take(c0), take(vr), take(vi), take(cr),
                 take(ci), jnp.asarray(np.asarray(p0_b)[np.asarray(idx)]),
                 cfg, keys, jnp.asarray(valid))
        np.asarray(out.p)

    reset_perf_stats()
    cache = ExecutableCache()
    with telemetry():
        # full bucket: 2 real lanes
        fn, hit = cache.get_with_status(bucket, "fp", batched_fused=True)
        assert not hit
        dispatch(fn, [0, 1], [True, True])
        # ragged bucket: 1 real lane replication-padded to the width
        idx, valid = pad_indices(1, width)
        fn2, hit2 = cache.get_with_status(bucket, "fp",
                                          batched_fused=True)
        assert hit2 and fn2 is fn
        dispatch(fn2, idx, valid)
    assert cache.stats() == {"hits": 1, "misses": 1, "entries": 1}
    batch_entries = {k: v for k, v in perf_stats().items()
                     if k.startswith("serve_batch[")}
    assert len(batch_entries) == 1
    (name, st), = batch_entries.items()
    assert st["compiles"] == 1, \
        f"{name} recompiled across same-bucket batch widths: {st}"
