"""Per-layer measurement from inside the program: the named scopes of the
tile program (obs/scopes.py), its step counts (SageResult.steps), and
the host phases, counters and profiler anchor of the tile line
(utils/profiling.py, obs/perf.jit_clock, io/dataset.TilePrefetcher,
apps/fullbatch.py)."""

import contextlib
import io
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.obs.scopes import (
    TILE_SCOPES, UNSCOPED, hlo_scopes, scope_path, scope_totals,
)

# the lofar62_dd100 configuration's rehearsal shape and solver line
# (perfbench/configs/lofar62_dd100.json: -e 4 -g 2 -l 10 -m 7 -j 5 --f32
# --fused), 8 stations x 3 clusters x 2 channels
NST, NCLUS, TILESZ = 8, 3, 4


def _tile(seed=17):
    from sagecal_tpu.core.types import jones_to_params
    from sagecal_tpu.io.simulate import (
        corrupt_and_observe, make_visdata, random_jones,
    )
    from sagecal_tpu.ops.rime import point_source_batch
    from sagecal_tpu.solvers.sage import build_cluster_data

    rng = np.random.default_rng(seed)
    data = make_visdata(nstations=NST, tilesz=TILESZ, nchan=2,
                        freq0=150e6, dtype=np.float32)
    cl = [point_source_batch([rng.uniform(-0.04, 0.04)],
                             [rng.uniform(-0.04, 0.04)],
                             [rng.uniform(1, 3)], f0=150e6,
                             dtype=jnp.float32)
          for _ in range(NCLUS)]
    jones = random_jones(NCLUS, NST, seed=2, amp=0.1, dtype=np.complex64)
    data = corrupt_and_observe(data, cl, jones=jones, noise_sigma=1e-3)
    cdata = build_cluster_data(data, cl, [1] * NCLUS)
    p0 = np.asarray(jones_to_params(random_jones(
        NCLUS, NST, seed=5, amp=0.0, dtype=np.complex64)))[:, None, :]
    return data, cdata, p0.astype(np.float32)


def _config(**kw):
    from sagecal_tpu.solvers.sage import SM_RTR_OSRLM_RLBFGS, SageConfig

    base = dict(max_emiter=4, max_iter=2, max_lbfgs=10, lbfgs_m=7,
                solver_mode=SM_RTR_OSRLM_RLBFGS, use_fused_predict=True)
    return SageConfig(**{**base, **kw})


def _compiled_text(data, cdata, p0, config):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from sagecal_tpu.solvers.sage import sagefit_packed

    jax.clear_caches()  # trace anew: scopes are read at trace time
    # and compile anew: the persistent cache, which an app run earlier in
    # this process turns on, keys a program without its op metadata
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    vis, coh = np.asarray(data.vis), np.asarray(cdata.coh)
    try:
        return jax.jit(sagefit_packed, static_argnames=("config",)).lower(
            data.replace(vis=None), cdata._replace(coh=None), vis.real,
            vis.imag, coh.real, coh.imag, p0,
            config=config).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def tile():
    return _tile()


@pytest.fixture(scope="module")
def scoped_text(tile):
    return _compiled_text(*tile, _config())


_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.+?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# instructions that move or name data and compute nothing
_TRIVIAL = {"parameter", "tuple", "get-tuple-element", "constant",
            "bitcast", "copy"}


def _instructions(text):
    """{name: (opcode, own op_name or None)} over the whole module."""
    out = {}
    for line in text.splitlines():
        m = _INST.match(line)
        if m:
            on = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), on.group(1) if on else None)
    return out


# ---------------------------------------------------------------- scopes


def test_scope_path_reads_scopes_through_transformations():
    assert scope_path("jit(sagefit_packed)/em/while/body/closed_call/solve/"
                      "while/body/vmap(tr)/while/body/tcg/dot_general") \
        == "em/solve/tr/tcg"
    assert scope_path("transpose(jvp(lbfgs))/while/body/mul") == "lbfgs"
    assert scope_path("jit(f)/lbfgs/while/body/transpose(jvp(lbfgs))/"
                      "mul") == "lbfgs"
    assert scope_path("jit(solve)/lu") == ""  # a jitted helper, not a scope
    assert scope_path("jit(sagefit_packed)/complex") == ""


def test_hlo_scopes_inherit_from_the_calling_loop():
    text = """HloModule m

%body (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %g = s32[] get-tuple-element(%p), index=0
  %c = s32[] copy(%g)
  %a = s32[] add(%c, %c), metadata={op_name="jit(f)/em/while/body/tr/add"}
  ROOT %t = (s32[]) tuple(%a)
}

%cond (p: (s32[])) -> pred[] {
  %p = (s32[]) parameter(0)
  ROOT %lt = pred[] compare(%p, %p), direction=LT
}

%fused (x: s32[]) -> s32[] {
  %x = s32[] parameter(0)
  ROOT %n = s32[] negate(%x), metadata={op_name="jit(f)/lbfgs/neg"}
}

ENTRY %main (x: s32[]) -> s32[] {
  %x = s32[] parameter(0)
  %t0 = (s32[]) tuple(%x)
  %w = (s32[]) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(f)/em/while"}
  %r = s32[] get-tuple-element(%w), index=0
  ROOT %f = s32[] fusion(%r), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/finalize/neg"}
}
"""
    got = hlo_scopes(text)
    assert got["a"] == "em/tr"
    assert got["c"] == "em"  # no op_name: the loop's scope
    assert got["lt"] == "em"
    assert got["f"] == "finalize"
    assert got["x"] == ""
    assert "n" not in got  # inside a fusion: runs as part of %f


def test_scope_totals_sum_to_the_self_time():
    rng = np.random.default_rng(3)
    names = [f"%op.{i % 7}" for i in range(500)]
    self_s = rng.uniform(0, 1e-3, 500).tolist()
    scope_of = {"op.0": "em/solve/tr", "op.1": "em/residual",
                "op.2": "lbfgs", "op.3": "finalize", "op.4": "em",
                "op.5": ""}  # op.6 unknown
    top = scope_totals(names, self_s, scope_of)
    assert set(top) == {"em", "lbfgs", "finalize", UNSCOPED}
    assert sum(t["s"] for t in top.values()) == pytest.approx(
        sum(self_s), rel=1e-12)
    assert sum(t["n"] for t in top.values()) == 500
    assert top[UNSCOPED]["n"] == sum(n in ("%op.5", "%op.6")
                                     for n in names)
    two = scope_totals(names, self_s, scope_of, depth=2)
    assert set(two) == {"em/solve", "em/residual", "em", "lbfgs",
                        "finalize", UNSCOPED}
    assert two["em/solve"]["s"] + two["em/residual"]["s"] + \
        two["em"]["s"] == pytest.approx(top["em"]["s"], rel=1e-12)


def test_every_op_of_the_tile_program_is_scoped(scoped_text):
    """Each instruction the program emitted (it carries an op_name) runs
    under em, lbfgs or finalize, but the inputs and their re/im rebuild;
    instructions XLA made without one are few, and those inside the
    solve's loops take the loop's scope."""
    scopes = hlo_scopes(scoped_text)
    inst = _instructions(scoped_text)
    top = {n: s.split("/")[0] for n, s in scopes.items()}
    outside = [n for n, t in top.items() if t not in TILE_SCOPES
               and inst[n][1] is not None and inst[n][0] != "parameter"
               and not inst[n][1].endswith("sagefit_packed)/complex")]
    assert outside == []
    made = [n for n, t in top.items() if t not in TILE_SCOPES
            and inst[n][1] is None and inst[n][0] not in _TRIVIAL]
    assert len(made) < 0.02 * len(scopes), made
    used = {s for s in scopes.values()}
    for want in ("em", "em/residual", "em/solve/rsd", "em/solve/tr",
                 "em/solve/tr/tcg", "em/solve/nu", "lbfgs", "finalize"):
        assert want in used, want


def _strip_metadata(text):
    """The computations of an HLO text without their metadata (op names
    and stack frames, whose tables precede the computations)."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return re.sub(r",?\s*metadata=\{[^}]*\}", "",
                  "\n".join(lines[first:]))


def test_scopes_change_only_metadata(tile, scoped_text, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(*tile, _config())
    assert not any(scope_path(on) for on in _OP_NAME.findall(plain))
    assert _strip_metadata(plain) == _strip_metadata(scoped_text)


# ---------------------------------------------------------- step counts


def test_step_counts_match_the_executed_steps(tile, monkeypatch):
    """SageResult.steps, always returned, count the steps the program
    runs, as seen from outside its carry: a host callback on every tCG
    solve (one per TR step, with its inner steps), on every RTR gradient
    (one per RSD and per TR step) and on every LBFGS direction (one per
    iteration)."""
    from sagecal_tpu.solvers import lbfgs, rtr
    from sagecal_tpu.solvers.sage import sagefit

    seen = {"tcg": 0, "cg": 0, "grad": 0, "lbfgs": 0}
    tcg_plain, make_fns_plain = rtr._tcg, rtr._make_fns
    two_loop_plain = lbfgs._two_loop_direction

    def bump(name):
        def callback(_):
            seen[name] += 1
        return callback

    def note_tcg(j):
        seen["tcg"] += int(np.size(j))
        seen["cg"] += int(np.sum(j))

    def tcg(*args):
        out = tcg_plain(*args)
        jax.debug.callback(note_tcg, out[2])
        return out

    def make_fns(*args, **kwargs):
        cost_c, grad_fn, hess_fn = make_fns_plain(*args, **kwargs)

        def counted_grad(x, iw):
            jax.debug.callback(bump("grad"), x)
            return grad_fn(x, iw)
        return cost_c, counted_grad, hess_fn

    def two_loop(g, mem):
        jax.debug.callback(bump("lbfgs"), g)
        return two_loop_plain(g, mem)

    monkeypatch.setattr(rtr, "_tcg", tcg)
    monkeypatch.setattr(rtr, "_make_fns", make_fns)
    monkeypatch.setattr(lbfgs, "_two_loop_direction", two_loop)

    data, cdata, p0 = tile
    cfg = _config(use_fused_predict=False, max_lbfgs=4)
    jax.clear_caches()  # trace anew through the counting wrappers
    res = jax.jit(sagefit, static_argnames=("config",))(
        data, cdata, jnp.asarray(p0), config=cfg)
    steps = {k: int(v) for k, v in res.steps.items()}
    jax.effects_barrier()
    assert steps["tr_steps"] == seen["tcg"] > 0
    assert steps["tcg_steps"] == seen["cg"] > 0
    assert steps["rsd_steps"] == seen["grad"] - seen["tcg"] > 0
    assert steps["lbfgs_iters"] == seen["lbfgs"] > 0
    # passes x clusters x robust rounds x chunks, each one TR loop
    assert steps["tr_loops"] == cfg.max_emiter * NCLUS * \
        cfg.em_rounds_robust
    assert 0 <= steps["tr_capped"] <= steps["tr_loops"]
    # the warm-up ahead of each TR loop runs at least one iteration and
    # at most its static ceiling, and moves x on some of them
    itmax_rsd = cfg.max_iter * cfg.iter_budget_cap + 5
    assert steps["rsd_steps"] <= steps["tr_loops"] * itmax_rsd
    assert 0 <= steps["rsd_moved"] <= steps["rsd_steps"]


def test_modes_without_a_trust_region_count_zero(tile):
    from sagecal_tpu.solvers.sage import SM_LM_LBFGS, sagefit

    data, cdata, p0 = tile
    cfg = _config(use_fused_predict=False, solver_mode=SM_LM_LBFGS,
                  max_emiter=1, max_lbfgs=2)
    res = jax.jit(sagefit, static_argnames=("config",))(
        data, cdata, jnp.asarray(p0), config=cfg)
    assert {k: int(v) for k, v in res.steps.items()} == {
        "tr_loops": 0, "tr_steps": 0, "tcg_steps": 0, "tr_capped": 0,
        "rsd_steps": 0, "rsd_moved": 0,
        "lbfgs_iters": int(res.steps["lbfgs_iters"])}
    assert 0 < int(res.steps["lbfgs_iters"]) <= 2


# ------------------------------------------------------------ tile line


def test_tile_summary_prints_phases_then_counts():
    from sagecal_tpu.utils.profiling import PhaseTimer

    t = PhaseTimer()
    with t.phase("load+coh"):
        with t.phase("load"):
            pass
        with t.phase("coh"):
            pass
    t.note("read_s", 0.25)
    t.note("tr_steps", 12)
    t.note("tr_steps", 3)
    line = t.tile_summary()
    keys = [kv.split("=")[0] for kv in line.split()]
    assert keys == ["load+coh", "load", "coh", "read_s", "tr_steps"]
    assert "read_s=0.250s" in line and "tr_steps=15" in line
    assert t.tile_summary() == ""


def test_tile_anchor_places_phases_on_the_trace_clock():
    """The tile annotation's perf_ns maps a phase's perf_counter start
    onto the profiler's timeline within 1 ms."""
    from jax._src.lib import _profiler
    from jax.profiler import ProfileData

    from sagecal_tpu.utils.profiling import PhaseTimer

    timer = PhaseTimer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    session = _profiler.ProfilerSession(opts)
    try:
        with timer.tile(7):
            time.sleep(0.01)
            t_phase = time.perf_counter_ns()
            with timer.phase("solve-wait"):
                time.sleep(0.02)
    finally:
        xspace = session.stop()
    tiles, phase = [], None
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "tile":
                    tiles.append((e.start_ns, dict(e.stats)))
                elif e.name == "solve-wait":
                    phase = e.start_ns
    assert len(tiles) == 1 and phase is not None
    start_ns, stats = tiles[0]
    assert int(stats["tile"]) == 7
    offset = int(stats["perf_ns"]) - start_ns
    assert abs((phase + offset) - t_phase) < 1e6


def test_jit_clock_counts_compilations():
    from sagecal_tpu.obs.perf import jit_clock

    x = np.arange(5.0)
    s0, n0 = jit_clock()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)(x).block_until_ready()
    s1, n1 = jit_clock()
    assert n1 == n0 + 1 and s1 > s0


def test_prefetcher_times_each_read_once(tmp_path):
    from test_apps import _make_dataset

    from sagecal_tpu.io.dataset import TilePrefetcher, VisDataset

    path = str(tmp_path / "pf.h5")
    _make_dataset(path, ntime=6, nchan=1)
    with VisDataset(path, "r") as ds:
        t0s = list(ds.tiles(2))
    spec = [dict(average_channels=False, dtype=np.float64)]
    with TilePrefetcher(path, t0s, spec, 2, depth=1) as pf:
        seen = [t0 for t0, _ in pf]
        reads = [pf.read_seconds(t0) for t0 in seen]
        again = [pf.read_seconds(t0) for t0 in seen]
    assert seen == t0s
    assert all(r > 0 for r in reads) and again == [0.0] * len(seen)


class _StampedOut(io.StringIO):
    """stdout that stamps perf_counter at every tile line."""

    def __init__(self):
        super().__init__()
        self.lines = []

    def write(self, s):
        for line in s.splitlines():
            if line.startswith("tile "):
                self.lines.append((time.perf_counter(), line))
        return super().write(s)


def test_tile_line_phases_cover_the_tile_period(tmp_path):
    """CLI run at rehearsal size: the top-level phases of each tile after
    the first cover at least 95% of the period between its tile line
    and the one before, and the line carries the solver's counts.  Every
    tile but the last runs its residual and write with the next tile's
    solve already dispatched (``ahead=1``)."""
    from test_apps import CLUSTER, SKY, _make_dataset

    from sagecal_tpu.apps.cli import main
    from sagecal_tpu.io.simulate import random_jones

    (tmp_path / "t.sky.txt").write_text(SKY)
    (tmp_path / "t.sky.txt.cluster").write_text(CLUSTER)
    dsp = tmp_path / "d.h5"
    _make_dataset(dsp, ntime=8, jones=random_jones(
        2, 7, seed=6, amp=0.1, dtype=np.complex128))
    out = _StampedOut()
    with contextlib.redirect_stdout(out):
        rc = main(["-d", str(dsp), "-s", str(tmp_path / "t.sky.txt"),
                   "-c", str(tmp_path / "t.sky.txt.cluster"),
                   "-p", str(tmp_path / "sol.txt"), "-t", "2",
                   "-e", "2", "-g", "2", "-l", "4", "-j", "5", "--f32"])
    assert rc == 0
    assert len(out.lines) == 4
    top = ("load+coh", "solve", "solve-wait", "finish", "residual",
           "write")
    for (t_prev, _), (t, line) in zip(out.lines, out.lines[1:]):
        vals = dict(kv.split("=") for kv in
                    line[line.index("[") + 1:line.rindex("]")].split())
        covered = sum(float(vals[k].rstrip("s")) for k in top if k in vals)
        # phases print to 10 ms: allow that rounding on each of them
        assert covered + 0.005 * len(top) >= 0.95 * (t - t_prev), line
        prepared = ("load", "coh", "read_s") if "load+coh" in vals else ()
        for k in prepared + ("tr_steps", "tcg_steps", "tr_capped",
                             "rsd_steps", "rsd_moved", "lbfgs_iters",
                             "jit_s", "jit_n"):
            assert k in vals, (k, line)
        assert int(vals["tr_steps"]) > 0
    ahead = [re.search(r" ahead=(\d+) ", line) for _, line in out.lines]
    assert [int(m.group(1)) for m in ahead] == [1, 1, 1, 0], out.lines
