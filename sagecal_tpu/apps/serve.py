"""``sagecal-tpu serve``: drain a multi-tenant request manifest through
the batch calibration service (sagecal_tpu/serve/).

Device split follows fullbatch: every host stage (request parsing,
HDF5 prefetch, coherency precompute, manifest writes) runs under a CPU
default device; each bucketed batch crosses to the accelerator as ONE
vmapped packed-real jit dispatch.

Exit codes: 0 success; 3 a request diverged under
``--abort-on-divergence``; 5 ``--resume`` refused (foreign checkpoint).
"""

from __future__ import annotations

import argparse
import sys

from sagecal_tpu.apps.config import ServeConfig


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="sagecal-tpu serve",
        description="Multi-tenant batch calibration service: bucketed "
        "vmapped solves over a JSON request manifest.")
    ap.add_argument("--requests", default="",
                    help="request manifest (JSON); see serve/request.py "
                    "for the schema")
    ap.add_argument("--out-dir", default="serve-out",
                    help="per-request solutions + result manifests")
    ap.add_argument("--batch", type=int, default=8,
                    help="lanes per bucketed batch solve (a bucket "
                    "dispatches when this many same-shape requests "
                    "accumulate; the ragged tail pads by replication)")
    ap.add_argument("--synthetic", type=int, default=0, metavar="N",
                    help="ignore --requests and serve N synthetic "
                    "requests (smoke/bench mode; datasets are simulated "
                    "under --out-dir)")
    ap.add_argument("--tenants", type=int, default=2,
                    help="tenant count for --synthetic")
    ap.add_argument("-e", "--max-emiter", type=int, default=3)
    ap.add_argument("-g", "--max-iter", type=int, default=2)
    ap.add_argument("-l", "--max-lbfgs", type=int, default=10)
    ap.add_argument("-m", "--lbfgs-m", type=int, default=7)
    ap.add_argument("-j", "--solver-mode", type=int, default=3)
    ap.add_argument("-L", "--nulow", type=float, default=2.0)
    ap.add_argument("-H", "--nuhigh", type=float, default=30.0)
    ap.add_argument("-R", "--no-randomize", action="store_true")
    ap.add_argument("--f32", action="store_true",
                    help="solve in float32 (TPU-native precision)")
    ap.add_argument("--fused", action="store_true",
                    help="route batch solves' joint-LBFGS through the "
                    "fused Pallas kernels — ONE batched grid per bucket "
                    "when the capability checks pass (solvers/batched."
                    "choose_batched_path), vmapped solo kernels or XLA "
                    "otherwise.  Requires --f32; ignored under f64")
    ap.add_argument("--coh-dtype", choices=("f32", "bf16"), default="f32",
                    help="coherency-stack storage dtype on the fused "
                    "paths (bf16 halves the dominant HBM stream, f32 "
                    "accumulation)")
    ap.add_argument("--abort-on-divergence", action="store_true")
    ap.add_argument("--shadow-rate", type=float, default=0.0,
                    help="fraction of requests to shadow re-solve on "
                    "the XLA/f32 reference path after their manifests "
                    "land, appending drift records to "
                    "<out-dir>/drift.jsonl (obs/shadow.py); 0 = off, "
                    "bit-identical to no feature")
    ap.add_argument("--shadow-budget-s", type=float, default=120.0,
                    help="wall-clock budget for shadow re-solves; "
                    "sampled requests past it are skipped + counted")
    ap.add_argument("--shadow-seed", type=int, default=0,
                    help="sampler seed: same seed -> same sampled "
                    "request ids, independent of scheduling")
    ap.add_argument("--abort-on-drift", action="store_true",
                    help="escalate a drift-tolerance breach "
                    "(obs/shadow.DRIFT_TOLERANCES) from report-only to "
                    "a run abort (exit 3) after the drain")
    ap.add_argument("--resume", action="store_true",
                    help="skip requests a previous (preempted) server "
                    "run already completed (per-tenant checkpoints)")
    ap.add_argument("--slo", default="",
                    help="per-tenant SLO specs (slo.json; obs/slo.py). "
                    "Report-only: burn-rate alerts + serve_slo_* gauges; "
                    "falls back to a 'slos' key in the request manifest")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--aot-store", default="",
                    help="cross-worker AOT executable artifact store "
                    "directory (serve/aot_store.py); workers joining a "
                    "warm store compile nothing")
    ap.add_argument("--max-streams", type=int, default=0,
                    help="cap on concurrently open prefetch streams; "
                    "LRU-evicted above the cap (0 = unbounded)")
    ap.add_argument("-V", "--verbose", action="store_true")
    return ap


def config_from_args(args) -> ServeConfig:
    return ServeConfig(
        requests=args.requests, out_dir=args.out_dir, batch=args.batch,
        max_emiter=args.max_emiter, max_iter=args.max_iter,
        max_lbfgs=args.max_lbfgs, lbfgs_m=args.lbfgs_m,
        solver_mode=args.solver_mode, nulow=args.nulow,
        nuhigh=args.nuhigh, randomize=not args.no_randomize,
        abort_on_divergence=args.abort_on_divergence,
        resume=args.resume, checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, use_f64=not args.f32,
        use_fused_predict=args.fused, coh_dtype=args.coh_dtype,
        verbose=args.verbose, slo=args.slo, aot_store=args.aot_store,
        max_streams=args.max_streams, shadow_rate=args.shadow_rate,
        shadow_budget_s=args.shadow_budget_s,
        shadow_seed=args.shadow_seed,
        abort_on_drift=args.abort_on_drift)


def run_serve(cfg: ServeConfig, requests=None, log=print):
    """Serve ``requests`` (or ``cfg.requests`` manifest) to completion;
    returns the service summary dict."""
    import jax

    from sagecal_tpu.obs.perf import enable_persistent_compilation_cache
    from sagecal_tpu.utils.platform import accelerator, cpu_device

    enable_persistent_compilation_cache()
    accel = accelerator()
    with jax.default_device(cpu_device()):
        return _run_serve_host(cfg, requests, log, accel)


def _run_serve_host(cfg: ServeConfig, requests, log, accel):
    from sagecal_tpu.obs import RunManifest, default_event_log
    from sagecal_tpu.obs.flight import (
        close_flight_recorder,
        get_flight_recorder,
        install_crash_handlers,
        register_event_log,
        unregister_event_log,
    )
    from sagecal_tpu.obs.perf import emit_perf_events
    from sagecal_tpu.obs.trace import close_tracer, configure_tracer
    from sagecal_tpu.serve.request import load_requests
    from sagecal_tpu.serve.service import CalibrationService

    if requests is None:
        requests = load_requests(cfg.requests)
    # manifest stamps the CONFIGURED routing intent; the path each batch
    # actually executed is recorded per dispatch in the
    # ``serve_batch_dispatched`` events (kernel_path / kernel_path_reason)
    fused_intent = (getattr(cfg, "use_fused_predict", False)
                    and not cfg.use_f64)
    manifest = RunManifest.collect(
        kernel_path="fused" if fused_intent else "xla", app="serve",
        requests=len(requests),
        tenants=len({r.tenant for r in requests}), batch=cfg.batch,
        out_dir=cfg.out_dir)
    elog = default_event_log(manifest=manifest)
    install_crash_handlers()
    if elog is not None:
        register_event_log(elog)
    get_flight_recorder(run_id=manifest.run_id)
    # request-lifecycle tracing (SAGECAL_TRACE=1): run-level spans join
    # the event stream on run_id; each request writes its own trace
    configure_tracer(run_id=manifest.run_id)
    store = None
    if getattr(cfg, "aot_store", ""):
        from sagecal_tpu.serve.aot_store import AOTArtifactStore

        store = AOTArtifactStore(cfg.aot_store)
    service = CalibrationService(cfg, log=log, device=accel,
                                 aot_store=store)
    try:
        summary = service.run(requests, elog=elog)
    finally:
        close_tracer()
        if elog is not None:
            emit_perf_events(elog)
            elog.close()
            unregister_event_log(elog)
    log(f"served {summary['served']}/{summary['requests']} requests "
        f"({summary['skipped_resume']} resumed-skipped) in "
        f"{summary['wall_s']:.1f}s — "
        f"{summary['solves_per_sec']:.2f} solves/s, "
        f"p50 latency {summary['p50_latency_s']:.1f}s, "
        f"buckets {summary['buckets']}")
    close_flight_recorder()
    return summary


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    args = build_parser().parse_args(argv)
    cfg = config_from_args(args)
    requests = None
    if args.synthetic > 0:
        from sagecal_tpu.serve.request import load_requests
        from sagecal_tpu.serve.synthetic import make_synthetic_workload

        path = make_synthetic_workload(cfg.out_dir, args.synthetic,
                                       n_tenants=args.tenants)
        cfg.requests = path
        requests = load_requests(path)
    elif not cfg.requests:
        build_parser().error("--requests (or --synthetic N) is required")

    from sagecal_tpu.elastic import ResumeRefused
    from sagecal_tpu.obs.quality import DivergenceAbort

    try:
        run_serve(cfg, requests=requests)
    except DivergenceAbort as e:
        print(f"sagecal-tpu serve: {e}", file=sys.stderr)
        return 3
    except ResumeRefused as e:
        print(f"sagecal-tpu serve: {e}", file=sys.stderr)
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main())
