"""Ahead-of-time compiles of the Pallas kernels for a described v5e chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2).  These
tests lower the main path's kernels at north-star widths (62 stations,
100 clusters, 60 timeslots x 2 channels, tile 128) and assert what
interpret-mode tests cannot: Mosaic accepts the kernel
(``tpu_custom_call`` in the compiled HLO) and the program fits the
chip's 16 GB of HBM.

Only one process may load libtpu at a time, so the topology is
described inside a module fixture (never at import) and every compile
runs in the test's own process; keep these tests in this one file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from sagecal_tpu.ops import rime_kernel
from sagecal_tpu.ops.rime_kernel import (
    FULL_CLUSTER_TILE,
    NPAD,
    chunked_rowsp,
    fused_cost_packed_batch,
    fused_cost_packed_chunked,
    fused_predict_packed_chunked,
    pad_to,
)

HBM_BYTES = 16e9  # TPU v5e: 16 GB of HBM per chip
NSTATIONS, NCLUSTERS, NCHAN, TILESZ = 62, 100, 2, 60
ROWS = NSTATIONS * (NSTATIONS - 1) // 2 * TILESZ  # 113,460


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile ``fn`` for one described v5e chip, in 32-bit mode, with
    the kernels compiled (not interpreted) and the persistent cache
    off."""
    from jax.experimental.compilation_cache import compilation_cache

    # JAX_PLATFORMS=cpu makes _use_interpret() True; the chip compiles
    monkeypatch.setattr(rime_kernel, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                for s in shapes]
        # the suite runs x64 for the f64 reference path; the chip's
        # programs are f32 and Mosaic takes no 64-bit grid indices
        with jax.enable_x64(False):
            return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)


def _assert_chip_program(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel did not lower to a Mosaic custom call"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit the chip"


def _solo_shapes():
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    mp = pad_to(NCLUSTERS, 8)
    rowsp = chunked_rowsp(ROWS, FULL_CLUSTER_TILE)
    return dict(
        tab=sds((4, mp, NPAD), f32),
        coh=sds((mp, NCHAN, 8, rowsp), f32),
        ant=sds((1, rowsp), jnp.int32),
        vis=sds((NCHAN, 8, rowsp), f32),
        mask=sds((NCHAN, rowsp), f32),
    )


def test_fused_objective_value_and_grad(tpu_compile):
    """The LBFGS body: value_and_grad of the fused robust objective."""
    s = _solo_shapes()

    def f(tre, tim, coh, antp, antq, vis, mask):
        return jax.value_and_grad(
            lambda a, b: fused_cost_packed_chunked(
                a, b, coh, antp, antq, vis, mask, nu=5.0),
            argnums=(0, 1))(tre, tim)

    compiled = tpu_compile(f, s["tab"], s["tab"], s["coh"], s["ant"],
                           s["ant"], s["vis"], s["mask"])
    _assert_chip_program(compiled)


def test_fused_predict_forward(tpu_compile):
    """The fused predict J_p C J_q^H summed over the 100 clusters."""
    s = _solo_shapes()

    def f(tre, tim, coh, antp, antq):
        return fused_predict_packed_chunked(tre, tim, coh, antp, antq)

    compiled = tpu_compile(f, s["tab"], s["tab"], s["coh"], s["ant"],
                           s["ant"])
    _assert_chip_program(compiled)


def test_batched_objective_at_104_rows(tpu_compile):
    """The serve bucket's batched grid at its shipped table bound:
    13 lanes x 8 padded clusters = 104 rows (KERNEL_VMEM_TABLE.json)."""
    from sagecal_tpu.solvers.batched import batch_rows_bound

    lanes, mp = 13, 8
    assert lanes * mp == 104 <= batch_rows_bound("f32")
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    rows = NSTATIONS * (NSTATIONS - 1) // 2 * 10
    rowsp = chunked_rowsp(rows, FULL_CLUSTER_TILE)

    def f(tre, tim, coh, antp, antq, vis, mask):
        return jax.value_and_grad(
            lambda a, b: jnp.sum(fused_cost_packed_batch(
                a, b, coh, antp, antq, vis, mask,
                nu=np.full((lanes,), 5.0, np.float32))),
            argnums=(0, 1))(tre, tim)

    compiled = tpu_compile(
        f, sds((4, lanes * mp, NPAD), f32), sds((4, lanes * mp, NPAD), f32),
        sds((lanes * mp, NCHAN, 8, rowsp), f32), sds((1, rowsp), jnp.int32),
        sds((1, rowsp), jnp.int32), sds((lanes, NCHAN, 8, rowsp), f32),
        sds((lanes, NCHAN, rowsp), f32))
    _assert_chip_program(compiled)
