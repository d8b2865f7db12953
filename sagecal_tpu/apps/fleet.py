"""``sagecal-tpu fleet``: multi-worker serve mesh over a shared
filesystem work queue (sagecal_tpu/fleet/).

Two roles share one entry point:

- ``--role coordinator`` (default) seeds the queue from the request
  manifest, spawns ``--workers`` worker subprocesses, watches the
  lease files, and prints the merged fleet summary;
- ``--role worker`` (normally spawned BY the coordinator, but valid
  standalone — point any number of hosts at the same queue directory)
  runs the claim-solve-complete loop.

Workers share compiled executables through the cross-worker AOT
artifact store: only the first worker to touch a bucket compiles.

Exit codes: 0 queue fully drained; 4 requests left undrained.
"""

from __future__ import annotations

import argparse
import os
import sys

from sagecal_tpu.apps.config import FleetConfig


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="sagecal-tpu fleet",
        description="Coordinator + N workers draining a shared "
        "filesystem work queue with atomic lease files.")
    ap.add_argument("--requests", default="",
                    help="request manifest (JSON; serve/request.py)")
    ap.add_argument("--out-dir", default="fleet-out")
    ap.add_argument("--queue-dir", default="",
                    help="shared queue directory "
                    "(default <out-dir>/queue)")
    ap.add_argument("--aot-store", default="",
                    help="shared AOT artifact store "
                    "(default <out-dir>/aot-store)")
    ap.add_argument("--workers", type=int, default=2,
                    help="worker subprocesses the coordinator spawns")
    ap.add_argument("--role", choices=("coordinator", "worker"),
                    default="coordinator")
    ap.add_argument("--worker-id", default="",
                    help="stable worker identity (worker role)")
    ap.add_argument("--batch", type=int, default=4,
                    help="max requests claimed (and vmapped) per cycle")
    ap.add_argument("--lease-ttl", type=float, default=30.0,
                    help="lease expiry; a killed worker's claims "
                    "requeue after this many seconds")
    ap.add_argument("--poll", type=float, default=0.2,
                    help="idle queue poll period (s)")
    ap.add_argument("--max-idle", type=float, default=10.0,
                    help="worker exits after this long with nothing "
                    "claimable")
    ap.add_argument("--large-stations", type=int, default=0,
                    help="requests with >= this many stations are "
                    "placed on sharded_joint_fit across all local "
                    "devices (0 = always use batch lanes)")
    ap.add_argument("--overload-policy",
                    choices=("shed", "degrade", "off"),
                    default="degrade",
                    help="admission action while a tenant's SLO "
                    "shed_burn threshold is tripped")
    ap.add_argument("--degrade-emiter", type=int, default=1)
    ap.add_argument("--degrade-lbfgs", type=int, default=4)
    ap.add_argument("--max-streams", type=int, default=8,
                    help="cap on concurrently open prefetch streams "
                    "per worker (LRU-evicted above)")
    ap.add_argument("--synthetic", type=int, default=0, metavar="N",
                    help="ignore --requests and seed N synthetic "
                    "requests (coordinator role)")
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
                    help="route workers' batch solves through the fused "
                    "Pallas kernels — one batched grid per bucket when "
                    "the capability checks pass.  Requires --f32; "
                    "ignored under f64")
    ap.add_argument("--coh-dtype", choices=("f32", "bf16"), default="f32",
                    help="coherency-stack storage dtype on the fused "
                    "paths (bf16 halves the dominant HBM stream, f32 "
                    "accumulation)")
    ap.add_argument("--slo", default="",
                    help="per-tenant SLO specs (slo.json); also drives "
                    "admission control deadlines; falls back to a "
                    "'slos' key in the request manifest")
    ap.add_argument("--shadow-rate", type=float, default=0.0,
                    help="fraction of requests each worker shadow "
                    "re-solves on the XLA/f32 reference path after "
                    "their manifests land, appending drift records to "
                    "the shared <out-dir>/drift.jsonl (obs/shadow.py)")
    ap.add_argument("--shadow-budget-s", type=float, default=120.0,
                    help="per-worker wall-clock budget for shadow "
                    "re-solves; sampled requests past it are skipped "
                    "and counted")
    ap.add_argument("--shadow-seed", type=int, default=0,
                    help="sampler seed: same seed -> same sampled "
                    "request ids fleet-wide, whichever worker claims")
    ap.add_argument("--abort-on-drift", action="store_true",
                    help="workers escalate a drift-tolerance breach "
                    "from report-only to an abort")
    ap.add_argument("-V", "--verbose", action="store_true")
    ap.add_argument("--no-timeline", action="store_true",
                    help="disable the coordinator's live timeline "
                    "sampler (obs/timeline.py timeline.jsonl) and the "
                    "report-only autoscale recommender")
    ap.add_argument("--max-respawns", type=int, default=2,
                    help="per-worker budget for respawning CRASHED "
                    "workers (nonzero exit with work left); clean "
                    "exits never respawn")
    ap.add_argument("--elastic-workers", action="store_true",
                    help="act on the autoscale recommender: spawn/"
                    "retire one worker per recommendation change, "
                    "clamped to [--min-workers, --max-workers].  "
                    "Retire = SIGTERM -> the worker's existing "
                    "lease-release path.  Off: report-only")
    ap.add_argument("--min-workers", type=int, default=1)
    ap.add_argument("--max-workers", type=int, default=0,
                    help="elastic ceiling (0 = max(--workers, "
                    "--min-workers))")
    ap.add_argument("--open-loop", action="store_true",
                    help="arrivals keep landing after workers start "
                    "(load harness): workers ignore the all-done exit "
                    "and hold on until --max-idle or SIGTERM")
    ap.add_argument("--profile-worker", default="", metavar="WID",
                    help="coordinator: arm worker WID for a one-cycle "
                    "device-profile capture by dropping the devprof "
                    "flag file in the shared out-dir — the targeted "
                    "worker of a LIVE fleet profiles its next claimed "
                    "cycle, no restart (obs/devprof.py; the retired "
                    "flag's .done file records the trace path)")
    ap.add_argument("--profile-dir", default="",
                    help="capture directory for --profile-worker "
                    "(default <out-dir>/devprof_<WID>)")
    return ap


def config_from_args(args) -> FleetConfig:
    return FleetConfig(
        requests=args.requests, out_dir=args.out_dir,
        queue_dir=args.queue_dir, aot_store=args.aot_store,
        workers=args.workers, role=args.role,
        worker_id=args.worker_id, batch=args.batch,
        lease_ttl_s=args.lease_ttl, poll_s=args.poll,
        max_idle_s=args.max_idle,
        large_stations=args.large_stations,
        overload_policy=args.overload_policy,
        degrade_emiter=args.degrade_emiter,
        degrade_lbfgs=args.degrade_lbfgs,
        max_streams=args.max_streams,
        max_emiter=args.max_emiter, max_iter=args.max_iter,
        max_lbfgs=args.max_lbfgs, lbfgs_m=args.lbfgs_m,
        solver_mode=args.solver_mode, nulow=args.nulow,
        nuhigh=args.nuhigh, randomize=not args.no_randomize,
        use_f64=not args.f32, use_fused_predict=args.fused,
        coh_dtype=args.coh_dtype, verbose=args.verbose, slo=args.slo,
        timeline=not args.no_timeline,
        max_respawns=args.max_respawns,
        elastic_workers=args.elastic_workers,
        min_workers=args.min_workers, max_workers=args.max_workers,
        open_loop=args.open_loop, shadow_rate=args.shadow_rate,
        shadow_budget_s=args.shadow_budget_s,
        shadow_seed=args.shadow_seed,
        abort_on_drift=args.abort_on_drift)


def _obs_setup(cfg, role: str):
    """RunManifest + event log + crash handlers + tracer, mirroring
    the serve app."""
    from sagecal_tpu.obs import RunManifest, default_event_log
    from sagecal_tpu.obs.flight import (
        get_flight_recorder, install_crash_handlers, register_event_log,
    )
    from sagecal_tpu.obs.trace import configure_tracer

    manifest = RunManifest.collect(
        kernel_path="xla", app="fleet", role=role,
        out_dir=cfg.out_dir)
    # fleet/load runs default the event log INTO the out-dir (rather
    # than the CWD) so every record family of one run lands in one
    # auditable directory; SAGECAL_EVENT_LOG still overrides, and the
    # spawned workers inherit the same resolution via --out-dir
    path = None
    if not os.environ.get("SAGECAL_EVENT_LOG") and cfg.out_dir:
        path = os.path.join(cfg.out_dir, "sagecal_events.jsonl")
    elog = default_event_log(manifest=manifest, path=path)
    install_crash_handlers()
    if elog is not None:
        register_event_log(elog)
    get_flight_recorder(run_id=manifest.run_id)
    configure_tracer(run_id=manifest.run_id)
    return elog


def _obs_teardown(elog) -> None:
    from sagecal_tpu.obs.flight import (
        close_flight_recorder, unregister_event_log,
    )
    from sagecal_tpu.obs.perf import emit_perf_events
    from sagecal_tpu.obs.trace import close_tracer

    close_tracer()
    if elog is not None:
        emit_perf_events(elog)
        elog.close()
        unregister_event_log(elog)
    close_flight_recorder()


def run_worker(cfg: FleetConfig, log=print):
    """One worker's whole life: the host pipeline runs under a CPU
    default device, batches cross to the accelerator (serve split)."""
    import jax

    from sagecal_tpu.fleet.worker import FleetWorker
    from sagecal_tpu.obs.perf import enable_persistent_compilation_cache
    from sagecal_tpu.utils.platform import accelerator, cpu_device

    enable_persistent_compilation_cache()
    accel = accelerator()
    elog = _obs_setup(cfg, "worker")
    try:
        with jax.default_device(cpu_device()):
            return FleetWorker(cfg, log=log, device=accel).run(elog=elog)
    finally:
        _obs_teardown(elog)


def run_coordinator(cfg: FleetConfig, requests=None, log=print):
    from sagecal_tpu.fleet.coordinator import FleetCoordinator
    from sagecal_tpu.serve.request import load_requests

    if requests is None:
        requests = load_requests(cfg.requests)
    elog = _obs_setup(cfg, "coordinator")
    try:
        return FleetCoordinator(cfg, log=log).run(requests, elog=elog)
    finally:
        _obs_teardown(elog)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    args = build_parser().parse_args(argv)
    cfg = config_from_args(args)
    if args.profile_worker:
        # drop the arm flag BEFORE any work starts, so a worker spawned
        # by this very coordinator (or one already alive on the shared
        # dir) sees it on its next claim
        from sagecal_tpu.obs.devprof import arm_fleet_profile

        path = arm_fleet_profile(cfg.out_dir, args.profile_worker,
                                 args.profile_dir or None)
        print(f"fleet: armed device profile for worker "
              f"{args.profile_worker} ({path})")
    if cfg.role == "worker":
        if not (cfg.queue_dir or cfg.out_dir):
            build_parser().error("--queue-dir (or --out-dir) required")
        run_worker(cfg)
        return 0
    from sagecal_tpu.fleet.coordinator import check_worker_count
    from sagecal_tpu.utils.platform import host_only

    try:
        check_worker_count(cfg)
    except ValueError as e:
        build_parser().error(str(e))
    host_only()  # the workers own the chip; seeding runs on the host
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
    summary = run_coordinator(cfg, requests=requests)
    return 0 if summary.get("drained") else 4


if __name__ == "__main__":
    sys.exit(main())
