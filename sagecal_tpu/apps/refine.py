"""``sagecal-tpu refine``: differentiable sky-model refinement.

Outer LBFGS over the free sky parameters (``--free-flux 0:0,1:2`` etc.)
around the inner gain solve, gradients through the inner fixed point
(``sagecal_tpu/refine/``).  Two input modes:

- dataset mode: one vis.h5 tile + sky/cluster files — refines the
  catalog values of the freed parameters against the data;
- ``--synthetic N``: an N-station simulated sky with known ground
  truth; one flux is perturbed by ``--perturb`` and refined back
  (the smoke/bench/test mode — the result JSON carries the true-flux
  relative error).

Elastic: ``--checkpoint-every K`` writes the full outer state (theta,
LBFGS curvature memory, warm-start gains) every K outer iterations;
``--resume`` continues bit-exactly from the newest checkpoint
(fingerprint-checked, exit 5 on mismatch).  Every outer iteration also
appends one JSON line to ``<out>.trace.jsonl`` and emits a
``refine_iter`` event.

XLA predict path only: requesting the fused kernel here fails loudly
at config time (refine.objective.require_xla_predict).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Tuple

import numpy as np

from sagecal_tpu.apps.config import RefineConfig


def parse_keys(text: str) -> List[Tuple[int, int]]:
    """'0:0,1:2' -> [(0, 0), (1, 2)] (cluster:index pairs)."""
    out = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        c, _, s = part.partition(":")
        out.append((int(c), int(s)))
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="sagecal-tpu refine",
        description="Differentiable sky-model refinement: outer LBFGS "
        "over sky parameters around the inner calibration solve.")
    ap.add_argument("-d", "--dataset", default="",
                    help="input vis.h5 dataset (one tile)")
    ap.add_argument("-s", "--sky", default="", help="sky model file")
    ap.add_argument("-c", "--clusters", default="",
                    help="cluster file (defaults to <sky>.cluster)")
    ap.add_argument("-o", "--out", default="refine-out",
                    help="output prefix (<out>.json/.npz/.trace.jsonl)")
    ap.add_argument("-t", "--tilesz", type=int, default=2)
    ap.add_argument("--free-flux", default="0:0",
                    help="free fluxes, 'cluster:source' comma list")
    ap.add_argument("--free-spec", default="",
                    help="free spectral indices, 'cluster:source' list")
    ap.add_argument("--free-pos", default="",
                    help="free (ll,mm) positions, 'cluster:source' list")
    ap.add_argument("--free-modes", default="",
                    help="free shapelet modes, 'cluster:flat_mode' list")
    ap.add_argument("--outer-iters", type=int, default=10)
    ap.add_argument("-m", "--lbfgs-m", type=int, default=7)
    ap.add_argument("--gradient", choices=("implicit", "unrolled"),
                    default="implicit",
                    help="gradient route through the inner solve: IFT "
                    "adjoint at the fixed point, or truncated unrolling")
    ap.add_argument("--tol", type=float, default=0.0,
                    help=">0 stops when the outer gradient norm drops "
                    "below it")
    ap.add_argument("--inner-iters", type=int, default=12)
    ap.add_argument("--cg-iters", type=int, default=32)
    ap.add_argument("--damping", type=float, default=1e-6)
    ap.add_argument("--adjoint-cg-iters", type=int, default=64)
    ap.add_argument("--adjoint-matvec", choices=("hvp", "jtj"),
                    default="hvp",
                    help="IFT adjoint Hessian: exact HVP or Gauss-Newton")
    ap.add_argument("--ridge", type=float, default=1e-2,
                    help="inner gain-prior strength (breaks the "
                    "flux/gain scale degeneracy)")
    ap.add_argument("--synthetic", type=int, default=0, metavar="N",
                    help="refine a perturbed N-station simulated sky "
                    "instead of a dataset")
    ap.add_argument("--perturb", type=float, default=1.15,
                    help="flux perturbation factor for --synthetic")
    ap.add_argument("--noise-sigma", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--fused", action="store_true",
                    help="rejected: refinement needs coherency "
                    "cotangents the fused kernel cannot produce")
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("-V", "--verbose", action="store_true")
    return ap


def config_from_args(args) -> RefineConfig:
    return RefineConfig(
        dataset=args.dataset, sky_model=args.sky,
        cluster_file=args.clusters or (args.sky + ".cluster"
                                       if args.sky else ""),
        out_prefix=args.out, tilesz=args.tilesz,
        free_flux=args.free_flux, free_spec=args.free_spec,
        free_pos=args.free_pos, free_modes=args.free_modes,
        outer_iters=args.outer_iters, lbfgs_m=args.lbfgs_m,
        gradient=args.gradient, tol=args.tol,
        inner_iters=args.inner_iters, cg_iters=args.cg_iters,
        damping=args.damping, adjoint_cg_iters=args.adjoint_cg_iters,
        adjoint_matvec=args.adjoint_matvec, ridge=args.ridge,
        synthetic=args.synthetic, perturb=args.perturb,
        noise_sigma=args.noise_sigma, seed=args.seed,
        resume=args.resume, checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, use_f64=not args.f32,
        verbose=args.verbose)


def _build_problem(cfg: RefineConfig, spec, log):
    """(RefineProblem, true_flux or None).  Synthetic mode simulates a
    known sky and perturbs one flux; dataset mode loads one tile plus
    the sky catalog."""
    from sagecal_tpu.refine import RefineProblem

    dtype = np.float64 if cfg.use_f64 else np.float32
    if cfg.synthetic > 0:
        from sagecal_tpu.data import make_sky, perturb_flux

        sky = make_sky(nstations=cfg.synthetic, tilesz=cfg.tilesz,
                       noise_sigma=cfg.noise_sigma, seed=cfg.seed,
                       shapelet_n0=2 if cfg.free_modes else 0,
                       spectral=bool(cfg.free_spec), dtype=dtype)
        c0, s0 = parse_keys(cfg.free_flux)[0] if cfg.free_flux else (0, 0)
        clusters = perturb_flux(sky, factor=cfg.perturb,
                                cluster=c0, source=s0)
        true_flux = float(sky.true_flux[c0][s0])
        log(f"synthetic sky: {cfg.synthetic} stations, flux "
            f"({c0},{s0}) perturbed x{cfg.perturb:.3f} "
            f"(true {true_flux:.4f})")
        problem = RefineProblem(
            data=sky.data, clusters=clusters,
            tables=sky.shapelet_tables, spec=spec, ridge=cfg.ridge)
        return problem, true_flux
    from sagecal_tpu.io.dataset import VisDataset
    from sagecal_tpu.io.skymodel import load_sky

    with VisDataset(cfg.dataset) as ds:
        meta = ds.meta
        data = ds.load_tile(0, cfg.tilesz, dtype=dtype)
    clusters, _, shapelets = load_sky(
        cfg.sky_model, cfg.cluster_file, meta.ra0, meta.dec0, dtype=dtype)
    tables = ([shapelets] * len(clusters)
              if shapelets is not None else None)
    problem = RefineProblem(data=data, clusters=clusters, tables=tables,
                            spec=spec, ridge=cfg.ridge)
    return problem, None


def run_refine_app(cfg: RefineConfig, log=print) -> dict:
    """Run one refinement to completion; returns the result summary."""
    from sagecal_tpu.utils.platform import accelerator

    accelerator()  # no TPU and no explicit CPU choice: refuse to run

    from sagecal_tpu.elastic import (
        CheckpointManager,
        config_fingerprint,
        flatten_state,
        unflatten_state,
    )
    from sagecal_tpu.obs import RunManifest, default_event_log
    from sagecal_tpu.refine import SkySpec, require_xla_predict, run_refine
    from sagecal_tpu.solvers.lbfgs import LBFGSMemory

    require_xla_predict(False)
    spec = SkySpec(flux=parse_keys(cfg.free_flux),
                   spec=parse_keys(cfg.free_spec),
                   pos=parse_keys(cfg.free_pos),
                   modes=parse_keys(cfg.free_modes))
    problem, true_flux = _build_problem(cfg, spec, log)
    theta0 = spec.theta0(problem.clusters, problem.tables)

    manifest = RunManifest.collect(
        kernel_path="xla", app="refine", nparams=spec.nparams,
        gradient=cfg.gradient, outer_iters=cfg.outer_iters,
        out_prefix=cfg.out_prefix)
    elog = default_event_log(manifest=manifest)
    fingerprint = config_fingerprint(
        app="refine", dataset=cfg.dataset, sky=cfg.sky_model,
        clusters=cfg.cluster_file, synthetic=cfg.synthetic,
        seed=cfg.seed, perturb=cfg.perturb, tilesz=cfg.tilesz,
        spec=repr(spec), gradient=cfg.gradient,
        inner_iters=cfg.inner_iters, cg_iters=cfg.cg_iters,
        ridge=cfg.ridge, use_f64=cfg.use_f64)
    ckpt_dir = cfg.checkpoint_dir or f"{cfg.out_prefix}.ckpt"
    every = cfg.checkpoint_every or (1 if cfg.resume else 0)
    manager = None
    if every > 0 or cfg.resume:
        manager = CheckpointManager(ckpt_dir, fingerprint, app="refine",
                                    every=max(every, 1), elog=elog,
                                    log=log if cfg.verbose else None)

    start_iter = 0
    p_start = None
    memory = None
    theta_resume = None
    if cfg.resume and manager is not None:
        found = manager.resume()
        if found is not None:
            meta, arrays, path = found
            start_iter = int(meta["tile_index"]) + 1
            theta_resume = arrays["theta"]
            p_start = arrays["p_warm"]
            template = LBFGSMemory.init(
                int(theta0.shape[0]), cfg.lbfgs_m, theta0.dtype)
            memory = unflatten_state("mem", arrays, template)
            log(f"resumed at outer iteration {start_iter} from {path}")

    trace_path = f"{cfg.out_prefix}.trace.jsonl"
    out_dir = os.path.dirname(os.path.abspath(cfg.out_prefix))
    os.makedirs(out_dir, exist_ok=True)
    trace_fh = open(trace_path, "a" if start_iter > 0 else "w")

    def on_iteration(it, theta, mem, p_warm, entry):
        if true_flux is not None:
            entry["flux_err"] = abs(
                float(theta[0]) - true_flux) / abs(true_flux)
        trace_fh.write(json.dumps(entry) + "\n")
        trace_fh.flush()
        if elog is not None:
            elog.emit("refine_iter", **{k: v for k, v in entry.items()
                                        if k != "theta"})
        if manager is not None:
            manager.update(it, {"theta": theta, "p_warm": p_warm,
                                **flatten_state("mem", mem)})
        if cfg.verbose:
            log(f"outer {it}: cost {entry['cost']:.6e} "
                f"gradnorm {entry['gradnorm']:.3e}")

    t0 = time.perf_counter()
    try:
        res = run_refine(
            problem, theta0=theta_resume, outer_iters=cfg.outer_iters,
            lbfgs_m=cfg.lbfgs_m, gradient=cfg.gradient,
            inner_iters=cfg.inner_iters, cg_iters=cfg.cg_iters,
            damping=cfg.damping,
            adjoint_cg_iters=cfg.adjoint_cg_iters,
            adjoint_matvec=cfg.adjoint_matvec, tol=cfg.tol,
            p_start=p_start, memory=memory, start_iter=start_iter,
            on_iteration=on_iteration)
    finally:
        trace_fh.close()
        if manager is not None:
            manager.flush()
            manager.close()
    wall = time.perf_counter() - t0

    summary = {
        "app": "refine",
        "nparams": spec.nparams,
        "gradient": cfg.gradient,
        "outer_iters": res.iterations,
        "cost": res.cost,
        "gradnorm": res.gradnorm,
        "theta": np.asarray(res.theta).tolist(),
        "wall_s": wall,
        "outer_iters_per_sec": res.iterations / max(wall, 1e-9),
    }
    if true_flux is not None:
        summary["true_flux"] = true_flux
        summary["flux_err"] = abs(
            float(res.theta[0]) - true_flux) / abs(true_flux)
    with open(f"{cfg.out_prefix}.json", "w") as f:
        json.dump(summary, f, indent=2)
    np.savez(f"{cfg.out_prefix}.npz",
             theta=np.asarray(res.theta), p=np.asarray(res.p))
    if elog is not None:
        elog.emit("refine_done", **{k: v for k, v in summary.items()
                                    if k != "theta"})
        elog.close()
    msg = (f"refine: {res.iterations} outer iterations in {wall:.1f}s, "
           f"cost {res.cost:.4e}, gradnorm {res.gradnorm:.3e}")
    if true_flux is not None:
        msg += f", flux rel err {summary['flux_err']:.2e}"
    log(msg)
    return summary


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    args = build_parser().parse_args(argv)
    if args.fused:
        from sagecal_tpu.refine import require_xla_predict

        try:
            require_xla_predict(True)
        except ValueError as e:
            print(f"sagecal-tpu refine: {e}", file=sys.stderr)
            return 2
    cfg = config_from_args(args)
    if cfg.synthetic <= 0 and not cfg.dataset:
        build_parser().error("--dataset (or --synthetic N) is required")
    if cfg.use_f64:
        import jax

        jax.config.update("jax_enable_x64", True)
    from sagecal_tpu.elastic import ResumeRefused

    try:
        run_refine_app(cfg)
    except ResumeRefused as e:
        print(f"sagecal-tpu refine: {e}", file=sys.stderr)
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main())
