"""One traced run of the consensus cell, then the device time of the
traced tile by named scope: each op event is placed in the program
(XLA module) running around it on its device, and its scope read from
that program's optimized HLO (obs.scopes.hlo_scopes), lowered again
from the arguments of its last call.

    python3 perfbench/tools/consensus_scopes.py <seed> <out.json> [run.py options]
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
sys.path.insert(1, ROOT)
import importlib.util  # noqa: E402

spec = importlib.util.spec_from_file_location(
    "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
runmod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runmod)

import numpy as np  # noqa: E402
import pb.trace as pbt  # noqa: E402

kept = {}
_orig_load = pbt.load_events


def load_events(xspace):
    kept["xspace"] = xspace
    return _orig_load(xspace)


pbt.load_events = load_events

from sagecal_tpu.apps import distributed as D  # noqa: E402

calls = {}


def capture(name, factory):
    def make(*a, **k):
        fn = factory(*a, **k)

        def call(*args):
            calls[name] = (fn, args)
            return fn(*args)

        return call
    return make


D.make_admm_mesh_fn = capture("fn", D.make_admm_mesh_fn)
D.make_band_prep_fn = capture("prep", D.make_band_prep_fn)
D.make_band_residual_fn = capture("residual", D.make_band_residual_fn)

seed = int(sys.argv[1])
out_path = sys.argv[2]
rc = runmod.main(["--workload", "consensus.c10x32.t10", "--seed", str(seed),
                  "--seconds", "30", "--trace", "1"] + sys.argv[3:])
res = runmod.LAST_RESULT

from jax.profiler import ProfileData  # noqa: E402

from sagecal_tpu.obs.scopes import hlo_scopes  # noqa: E402

t = time.perf_counter()
maps = {}
for name, (fn, args) in calls.items():
    jit = getattr(fn, "inner_jit", fn)
    text = jit.lower(*args).compile().as_text()
    mod = text.split("\n", 1)[0].split()[1].rstrip(",")
    maps[mod] = hlo_scopes(text)
print(f"lowered again in {time.perf_counter() - t:.1f} s: "
      f"{ {m: len(s) for m, s in maps.items()} }", flush=True)

pd = ProfileData.from_serialized_xspace(kept["xspace"])
totals, per_dev_busy, unknown = {}, [], {}
for plane in pd.planes:
    if not pbt.DEVICE_PLANE_RE.match(plane.name):
        continue
    mods, ops = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            mods += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events]
        elif line.name == pbt.OPS_LINE:
            ops += [(e.name.partition(" = ")[0].lstrip("%"), e.start_ns,
                     e.duration_ns) for e in line.events]
    mods.sort()
    starts = np.asarray([m[0] for m in mods], np.float64)
    st = np.asarray([o[1] for o in ops], np.float64)
    du = np.asarray([o[2] for o in ops], np.float64)
    selfd = pbt.self_times(st, du) * 1e-9
    bs, be = pbt.busy_blocks(st, du)
    per_dev_busy.append(float(np.sum(be - bs)) * 1e-9)
    for (name, s0, _), sd in zip(ops, selfd.tolist()):
        i = int(np.searchsorted(starts, s0, side="right")) - 1
        mod = mods[i][2] if i >= 0 and s0 < mods[i][1] else "(no module)"
        key = mod.split("(")[0]
        smap = maps.get(key)
        if smap is None:
            unknown[key] = unknown.get(key, 0.0) + sd / 4
            scope = f"[{key}]"
        else:
            path = smap.get(name, "")
            scope = "/".join(path.split("/")[:2]) or "(unscoped)"
        totals[scope] = totals.get(scope, 0.0) + sd
ndev = max(len(per_dev_busy), 1)
totals = {k: v / ndev for k, v in sorted(totals.items(), key=lambda kv: -kv[1])}
busy = sum(per_dev_busy) / ndev
admm = sum(v for k, v in totals.items() if k.startswith("admm"))
summary = {"rc": rc, "correct": res and res["correct"],
           "busy_s": busy, "per_device_busy_s": per_dev_busy,
           "scope_s": totals, "admm_share": admm / busy if busy else None,
           "modules": sorted(maps), "unmapped_modules_s": unknown,
           "result": res}
os.makedirs(os.path.dirname(out_path), exist_ok=True)
with open(out_path, "w") as f:
    json.dump(summary, f, indent=1, default=str)
print(json.dumps({k: summary[k] for k in ("busy_s", "scope_s", "admm_share",
                                          "unmapped_modules_s")},
                 default=str), flush=True)
print(json.dumps(res), flush=True)
