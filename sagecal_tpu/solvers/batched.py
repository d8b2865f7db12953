"""Batched (vmapped) solver entry points for the multi-tenant serve
path (:mod:`sagecal_tpu.serve`).

Production traffic is thousands of independent (field, epoch, sub-band)
calibration requests; dispatching them one ``solve_tile`` at a time
leaves the chip idle between programs and pays the dispatch floor per
request.  These entries ``jax.vmap`` a whole *batch* of same-shape
solves — gains carry, LBFGS curvature memory, RNG keys all grow a
leading batch axis — into ONE device program over the existing packed
entries (``solvers/sage.sagefit_packed``, ``solvers/batchmode``), so
solves/sec scales with the batch instead of with dispatch count.

Layout contract (the serve bucketer produces exactly this):

- every array leaf of ``data``/``cdata`` and every packed re/im array
  carries a leading batch axis ``B``;
- static metadata (tilesz, nbase, nstations, freq0, ...) is SHARED
  across the batch — that is what a serve *bucket* means;
- ``p0`` is ``(B, M, nchunk_max, 8N)`` and is DONATED: the serve layer
  rebuilds it from host numpy per submission and threads the RESULT
  gains forward, never the input buffer (jaxlint JL007 convention,
  same as the single-solve entry);
- padded lanes of a ragged last bucket REPLICATE real entries
  round-robin (finite math, no degenerate all-masked solves); their
  results are discarded on the host.

vmap of the solver's ``lax.while_loop``s masks per-lane updates once a
lane's own termination test fires, so a batched solve is bit-close
(<= 1e-5, tests/test_serve.py) to the K sequential solves — not
bit-identical, because batched reductions may re-associate.
"""

from __future__ import annotations

import json
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from sagecal_tpu.core.types import VisData
from sagecal_tpu.obs.perf import instrumented_jit
from sagecal_tpu.solvers.batchmode import bfgsfit_minibatch
from sagecal_tpu.solvers.lbfgs import LBFGSMemory
from sagecal_tpu.solvers.sage import (
    ClusterData,
    SageConfig,
    SageResult,
    sagefit_batched_fused,
    sagefit_packed,
)

# VMEM ceiling of the batched fused BACKWARD kernel: its in-register
# accumulators are sixteen (B*Mp, tile) f32 planes, so B*Mp is bounded
# exactly like the solo kernel's padded cluster count at tile 128 (the
# hardware-proven FULL_CLUSTER_TILE configuration — ops/rime_kernel.py
# batched section comment).  LAST-RESORT fallback only: the live bound
# comes from the banked VMEM table (KERNEL_VMEM_TABLE.json, regenerated
# by tools/kernel_vmem_table.py from the symbolic footprint model) via
# :func:`batch_rows_bound` — the model admits MORE rows for bf16
# coherencies (the bf16 operand block halves) where this constant is
# the conservative f32 value.
_BATCH_ROWS_MAX = 104

# (path, mtime) -> parsed table; the serve path calls
# choose_batched_path per bucket, so the table read must not be a
# per-call disk hit
_TABLE_CACHE: dict = {}


def _vmem_table_path() -> str:
    override = os.environ.get("SAGECAL_KERNEL_VMEM_TABLE")
    if override:
        return override
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "KERNEL_VMEM_TABLE.json")


def _load_vmem_table():
    path = _vmem_table_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    key = (path, mtime)
    if _TABLE_CACHE.get("key") == key:
        return _TABLE_CACHE["table"]
    try:
        with open(path, "r") as fh:
            table = json.load(fh)
    except (OSError, ValueError):
        return None
    _TABLE_CACHE["key"] = key
    _TABLE_CACHE["table"] = table
    return table


def batch_rows_bound(coh_dtype: str = "f32",
                     tile: Optional[int] = None) -> int:
    """Row bound (B*Mp) of the batched fused backward kernel.

    Resolution order: ``$SAGECAL_KERNEL_VMEM_TABLE`` / the banked
    repo-root ``KERNEL_VMEM_TABLE.json`` (written by
    ``tools/kernel_vmem_table.py``), then a live
    :mod:`sagecal_tpu.analysis.kernelmodel` computation, then the
    hardware-proven f32 constant.  ``coh_dtype="bf16"`` legitimately
    admits more rows than f32 — the coherency VMEM block halves."""
    table = _load_vmem_table()
    if table is not None:
        try:
            t = tile if tile is not None else int(
                table["constants"]["FULL_CLUSTER_TILE"])
            return int(table["batch_rows_max"][coh_dtype][str(t)])
        except (KeyError, TypeError, ValueError):
            pass
    try:
        from sagecal_tpu.analysis.kernelmodel import load_model
        from sagecal_tpu.ops.rime_kernel import FULL_CLUSTER_TILE
        model = load_model()
        return int(model.batch_rows_max(
            tile if tile is not None else FULL_CLUSTER_TILE, coh_dtype))
    except Exception:
        return _BATCH_ROWS_MAX


def _batch_axes(tree):
    """An ``in_axes`` pytree mapping every array leaf of ``tree`` to
    axis 0 (None leaves — the stripped complex slots — stay None)."""
    return jax.tree_util.tree_map(lambda _: 0, tree)


def derive_lane_keys(seed: int, lane_ids) -> jax.Array:
    """Stable per-lane PRNG keys from lane IDENTITIES, not submission
    order: ``key_i = fold_in(PRNGKey(seed), lane_ids[i])``.

    Hoisted out of the dispatch loop (the serve layer used to re-split a
    fresh key per submission) so a request's randomized solver stream —
    OS-LM subset draws, robust nu estimation order — is a function of
    the request itself and reproduces identically whichever scheduler,
    worker or batch slot executes it."""
    base = jax.random.PRNGKey(seed)
    ids = jnp.asarray(np.asarray(lane_ids), jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)


def choose_batched_path(data, cdata, p0, config: SageConfig):
    """Host-side capability check routing a batch to the best kernel
    path — the batched analog of the solo chunked-fallback machinery.

    Returns ``(path, reason)`` with path one of:

    - ``"fused_batch"`` — one Pallas grid for the whole batch
      (:func:`sagecal_tpu.solvers.sage.sagefit_batched_fused`);
    - ``"fused"`` — vmapped solo fused kernels (capability shortfall is
      batch-specific: hybrid chunks, unshared baselines, VMEM bound);
    - ``"xla"`` — vmapped XLA predict (fused path disabled or unusable).

    All checks are CONCRETE (host numpy) — call before jit dispatch.
    ``data``/``cdata`` leaves carry the leading batch axis; ``p0`` is
    (B, M, nchunk_max, 8N)."""
    from sagecal_tpu.ops.rime_kernel import NPAD, pad_to

    if not config.use_fused_predict:
        return "xla", "fused predict disabled in config"
    B, M, nchunk_max, n8 = p0.shape
    if np.asarray(p0).dtype != np.float32:
        return "xla", "fused kernels require float32 parameters/data"
    if n8 // 8 > NPAD:
        return "xla", f"N={n8 // 8} exceeds the kernel's NPAD={NPAD}"
    if config.param_bound > 0.0:
        return "xla", "param_bound uses the (XLA-only) bounded LBFGS"
    if nchunk_max > 1:
        return "fused", "hybrid time chunks: batched kernel is nc==1 only"
    ant_p = np.asarray(data.ant_p)
    ant_q = np.asarray(data.ant_q)
    if not (np.all(ant_p == ant_p[:1]) and np.all(ant_q == ant_q[:1])):
        return "fused", "lanes do not share baseline geometry"
    rows_max = batch_rows_bound(coh_dtype=config.coh_dtype)
    if B * pad_to(M, 8) > rows_max:
        return "fused", (
            f"B*Mp={B * pad_to(M, 8)} exceeds the backward kernel's "
            f"VMEM accumulator bound ({rows_max}, "
            f"coh_dtype={config.coh_dtype})")
    return "fused_batch", "all batched-kernel capability checks passed"


def sagefit_packed_batch(
    data: VisData,
    cdata: ClusterData,
    vis_re: jax.Array,
    vis_im: jax.Array,
    coh_re: jax.Array,
    coh_im: jax.Array,
    p0: jax.Array,
    config: SageConfig = SageConfig(),
    keys: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    batched_fused: bool = False,
) -> SageResult:
    """``B`` independent tile solves as one vmapped device program.

    Same REAL-boundary contract as :func:`sagefit_packed`, with a
    leading batch axis on every array: ``vis_*`` is ``(B, F, 4, rows)``,
    ``coh_*`` is ``(B, M, F, 4, rows)``, ``p0`` is
    ``(B, M, nchunk_max, 8N)`` and ``keys`` is ``(B, 2)`` (one PRNG key
    per lane, so randomized OS subsets stay independent per request;
    derive them from request identity with :func:`derive_lane_keys`).
    Returns a :class:`SageResult` whose leaves all carry the batch axis.

    ``batched_fused`` (STATIC; set it from :func:`choose_batched_path`)
    routes the joint-LBFGS phase through the batched fused Pallas kernel
    (:func:`sagecal_tpu.solvers.sage.sagefit_batched_fused`) instead of
    vmapping B solo solves; ``valid`` (B,) then pins replication-padded
    lanes to exactly zero cost/cotangent in that phase.  On the vmapped
    paths ``valid`` is ignored — padded lanes run replicated finite
    solves whose results the host discards, as before.
    """
    if keys is None:
        keys = jax.random.split(jax.random.PRNGKey(0), vis_re.shape[0])
    if batched_fused:
        vis = jax.lax.complex(vis_re, vis_im)
        coh = jax.lax.complex(coh_re, coh_im)
        return sagefit_batched_fused(
            data.replace(vis=vis), cdata._replace(coh=coh), p0, config,
            keys, valid,
        )

    def one(d, cd, vr, vi, cr, ci, p, k):
        return sagefit_packed(d, cd, vr, vi, cr, ci, p, config, k)

    return jax.vmap(
        one,
        in_axes=(_batch_axes(data), _batch_axes(cdata), 0, 0, 0, 0, 0, 0),
    )(data, cdata, vis_re, vis_im, coh_re, coh_im, p0, keys)


# the serve executable cache wraps per-bucket jits itself (serve/cache.py
# keys them by abstract signature + config fingerprint); this module-level
# entry is the library surface for direct use and for the bench, named so
# its compiles are attributable in `diag perf`.  The batch gains carry is
# donated, exactly like the single-solve entry's p0.
sagefit_packed_batch_jit = instrumented_jit(
    sagefit_packed_batch, name="sagefit_packed_batch",
    static_argnames=("batched_fused",),
    donate_argnames=("p0",))


def lbfgs_minibatch_batch(
    data: VisData,
    cdata: ClusterData,
    p0: jax.Array,
    memory: Optional[LBFGSMemory] = None,
    itmax: int = 10,
    lbfgs_m: int = 7,
    robust_nu: Optional[float] = None,
) -> Tuple[jax.Array, LBFGSMemory]:
    """``B`` independent minibatch joint-LBFGS steps as one program.

    vmap of :func:`sagecal_tpu.solvers.batchmode.bfgsfit_minibatch`:
    ``p0`` is ``(B, M, nchunk_max, 8N)`` and ``memory`` (when resuming a
    stream) is an :class:`LBFGSMemory` whose every leaf carries the
    batch axis — each tenant's curvature pairs persist independently
    across its minibatches.  Returns ``(p_new, memory)`` with batched
    leaves; thread both into the next call (donated — rebuild from the
    results, not the inputs).
    """
    B = p0.shape[0]
    if memory is None:
        n = int(p0.shape[1] * p0.shape[2] * p0.shape[3])
        one_mem = LBFGSMemory.init(n, lbfgs_m, p0.dtype)
        memory = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (B,) + x.shape), one_mem)

    def one(d, cd, p, mem):
        return bfgsfit_minibatch(d, cd, p, memory=mem, itmax=itmax,
                                 lbfgs_m=lbfgs_m, robust_nu=robust_nu)

    return jax.vmap(
        one,
        in_axes=(_batch_axes(data), _batch_axes(cdata), 0,
                 _batch_axes(memory)),
    )(data, cdata, p0, memory)


lbfgs_minibatch_batch_jit = instrumented_jit(
    lbfgs_minibatch_batch, name="lbfgs_minibatch_batch",
    static_argnames=("itmax", "lbfgs_m", "robust_nu"),
    donate_argnames=("p0", "memory"))
