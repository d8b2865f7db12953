"""jaxlint static analysis (sagecal_tpu/analysis) + checkify contracts.

Four layers, mirroring the subsystem:

- per-rule fixture tests: every rule JL001-JL006 (+ JL900) has a
  committed should-fire fixture that fails the gate and a must-not-fire
  fixture exercising the precision carve-outs (identity checks, static
  metadata reads, the conditional-dtype idiom, size= escape hatches);
- call-graph reachability: the repo's real wrap forms (decorator
  factories, call-site wraps, jit(shard_map(f)) chasing) mark the right
  functions jit-reachable;
- gate mechanics: pragma suppression (and the un-suppressed variant
  failing), baseline round-trip/partition, the CLI exit codes, and the
  acceptance gate — the analyzer over the installed ``sagecal_tpu``
  must be clean with an empty baseline in under 10 s;
- runtime contracts: ``SAGECAL_CHECKIFY=1`` turns an injected NaN gain
  into a ``ContractViolation`` + ``contract_violation`` event (unit and
  fullbatch-CLI end-to-end, exit 4), and with checkify off the solver
  outputs are bit-identical to a plain ``jax.jit`` of the same solver.
"""

import json
import math
import os
import re

import numpy as np
import pytest

from sagecal_tpu.analysis import baseline as baseline_mod
from sagecal_tpu.analysis import cli as lint_cli
from sagecal_tpu.analysis.callgraph import build_callgraph, collect_files
from sagecal_tpu.analysis.engine import analyze_paths, default_rules

pytestmark = pytest.mark.lint

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "jaxlint")
PKGDIR = os.path.dirname(
    os.path.abspath(__import__("sagecal_tpu").__file__))


def fx(name: str) -> str:
    return os.path.join(FIXDIR, name)


def rules_fired(paths, rules=None):
    findings, _, _ = analyze_paths(
        paths if isinstance(paths, list) else [paths], rules)
    return findings


# ----------------------------------------------------------- rule fixtures


FIRE_CASES = [
    ("JL001", "jl001_fire.py", 3),
    ("JL002", "jl002_fire.py", 4),
    ("JL003", "jl003_fire.py", 2),
    ("JL004", os.path.join("solvers", "jl004_fire.py"), 2),
    ("JL005", "jl005_fire.py", 4),
    ("JL006", "jl006_fire.py", 2),
    ("JL007", "jl007_fire.py", 3),
    ("JL008", os.path.join("fleet", "jl008_fire.py"), 3),
    ("JL009", "jl009_fire.py", 2),
    ("JL010", os.path.join("fleet", "jl010_fire.py"), 2),
    ("JL011", "jl011_fire.py", 2),
    ("JL012", os.path.join("solvers", "jl012_fire.py"), 3),
    ("JL013", "jl013_fire.py", 3),
    ("JL014", "jl014_fire.py", 3),
    ("JL015", "jl015_fire.py", 3),
    ("JL016", os.path.join("fleet", "jl016_fire.py"), 2),
    ("JL900", "jl900_fixture.py", 2),
]

CLEAN_CASES = [
    ("JL001", "jl001_clean.py"),
    ("JL002", "jl002_clean.py"),
    ("JL003", "jl003_clean.py"),
    ("JL004", os.path.join("solvers", "jl004_clean.py")),
    ("JL005", "jl005_clean.py"),
    ("JL007", "jl007_clean.py"),
    ("JL008", os.path.join("fleet", "jl008_clean.py")),
    ("JL009", "jl009_clean.py"),
    ("JL010", os.path.join("fleet", "jl010_clean.py")),
    ("JL011", "jl011_clean.py"),
    ("JL012", os.path.join("solvers", "jl012_clean.py")),
    ("JL013", "jl013_clean.py"),
    ("JL014", "jl014_clean.py"),
    ("JL015", "jl015_clean.py"),
    ("JL016", os.path.join("fleet", "jl016_clean.py")),
]


class TestRuleFixtures:
    @pytest.mark.parametrize("rule,fixture,expected", FIRE_CASES)
    def test_should_fire(self, rule, fixture, expected):
        findings = rules_fired(fx(fixture))
        hits = [f for f in findings if f.rule == rule]
        assert len(hits) == expected, findings
        # ...and ONLY that rule fires on its fixture (cross-rule
        # contamination would make the fixtures ambiguous)
        assert {f.rule for f in findings} == {rule}, findings

    @pytest.mark.parametrize("rule,fixture", CLEAN_CASES)
    def test_must_not_fire(self, rule, fixture):
        findings = rules_fired(fx(fixture))
        assert [f for f in findings if f.rule == rule] == [], findings

    def test_jl900_honors_noqa_and_all(self):
        findings = rules_fired(fx("jl900_fixture.py"))
        flagged = {f.symbol for f in findings if f.rule == "JL900"}
        # json + Optional dead; os kept by noqa, sys kept by __all__,
        # List kept by a use inside an annotation
        assert flagged == {"json", "Optional"}
        assert all(f.report_only for f in findings if f.rule == "JL900")

    def test_gate_fails_on_fire_fixture(self):
        # acceptance: a committed fixture fails the gate un-suppressed
        rc = lint_cli.main([fx("jl001_fire.py")])
        assert rc == 1

    def test_report_only_does_not_gate(self):
        rc = lint_cli.main([fx("jl900_fixture.py")])
        assert rc == 0

    def test_jl012_report_only_with_baselined_why(self, tmp_path):
        # JL012 never gates on its own ...
        fire = fx(os.path.join("solvers", "jl012_fire.py"))
        assert lint_cli.main([fire]) == 0
        findings = [f for f in rules_fired(fire) if f.rule == "JL012"]
        assert findings and all(f.report_only for f in findings)
        # ... and the deliberate-case discipline is a baseline record
        # carrying a `why` (the shipped tree is currently clean under
        # JL012, so the mechanism is pinned on the fixture)
        bl_path = str(tmp_path / "bl.json")
        baseline_mod.save_baseline(bl_path, findings)
        data = json.load(open(bl_path))
        data["findings"][0]["why"] = ("deliberate: storage-precision "
                                      "equality is the intent")
        with open(bl_path, "w") as f:
            json.dump(data, f)
        baseline_mod.save_baseline(bl_path, findings)
        data2 = json.load(open(bl_path))
        assert [r for r in data2["findings"] if r.get("why")]


class TestCallGraph:
    def test_reachability_through_real_wrap_forms(self):
        g = build_callgraph(collect_files([fx("jl_callgraph.py")]))
        names = {q.rsplit(".", 1)[-1]: q for q in g.functions}
        # decorator factory: @instrumented_jit(name=...)
        assert g.functions[names["block"]].jit_root
        # call-site wrap through shard_map chasing: jit(shard_map(f))
        assert g.functions[names["local_fit"]].jit_root
        # transitive: helper is referenced by both roots
        assert names["helper"] in g.reachable
        assert names["local_fit"] in g.reachable
        assert names["block"] in g.reachable
        # and plain host code stays out
        assert names["host_only_report"] not in g.reachable

    def test_statics_merge_across_wrap_sites(self):
        g = build_callgraph(collect_files([fx("jl003_clean.py")]))
        fi = next(f for f in g.functions.values() if f.name == "fit")
        assert {"collect_trace", "robust"} <= fi.static_argnames
        pos = next(f for f in g.functions.values()
                   if f.name == "positional")
        assert 1 in pos.static_argnums and len(pos.wrap_sites) == 2

    def test_donates_collected_across_wrap_forms(self):
        g = build_callgraph(collect_files([fx("jl007_clean.py")]))
        by_name = {f.name: f for f in g.functions.values()}
        # decorator: @partial(jax.jit, donate_argnums=(0, 2))
        assert by_name["fit"].donate_argnums == {0, 2}
        # call-site wrap: jax.jit(_step, donate_argnames=("state",))
        assert by_name["_step"].donate_argnames == {"state"}
        # statics and donates stay separate sets
        assert by_name["unrolled"].static_argnames == {"carry"}
        assert by_name["unrolled"].donate_argnames == set()

    def test_repo_graph_sees_the_solver_entries(self):
        _, stats, g = analyze_paths([PKGDIR], rules=[])
        roots = {q.rsplit(".", 1)[-1] for q, f in g.functions.items()
                 if f.jit_root}
        assert {"lm_solve", "os_lm_solve", "lbfgs_fit"} <= roots
        assert stats["jit_reachable"] > 100


class TestPragmasAndBaseline:
    def test_pragma_file_is_clean(self):
        assert rules_fired(fx("jl_pragma.py")) == []

    def test_unsuppressed_variant_fires(self, tmp_path):
        # strip the pragmas -> the same code must fail the gate
        src = open(fx("jl_pragma.py")).read()
        stripped = re.sub(r"#\s*jaxlint:[^\n]*", "", src)
        p = tmp_path / "unsuppressed.py"
        p.write_text(stripped)
        fired = {f.rule for f in rules_fired(str(p))}
        assert {"JL001", "JL006"} <= fired
        assert lint_cli.main([str(p)]) == 1

    def test_baseline_round_trip_and_partition(self, tmp_path):
        findings = rules_fired(fx("jl001_fire.py"))
        bl_path = str(tmp_path / "bl.json")
        baseline_mod.save_baseline(bl_path, findings)
        bl = baseline_mod.load_baseline(bl_path)
        new, old = baseline_mod.partition(findings, bl)
        assert new == [] and len(old) == len(findings)
        # a finding outside the baseline is new
        extra = rules_fired(fx("jl006_fire.py"))
        new2, old2 = baseline_mod.partition(findings + extra, bl)
        assert {f.rule for f in new2} == {"JL006"}
        assert len(old2) == len(findings)

    def test_baseline_preserves_why_on_rewrite(self, tmp_path):
        # a justification attached to a deliberate finding survives
        # --update-baseline rewrites
        findings = rules_fired(fx("jl001_fire.py"))
        bl_path = str(tmp_path / "bl.json")
        baseline_mod.save_baseline(bl_path, findings)
        data = json.load(open(bl_path))
        data["findings"][0]["why"] = "deliberate: fixture reason"
        with open(bl_path, "w") as f:
            json.dump(data, f)
        baseline_mod.save_baseline(bl_path, findings)
        data2 = json.load(open(bl_path))
        whys = [r.get("why") for r in data2["findings"] if r.get("why")]
        assert whys == ["deliberate: fixture reason"]

    def test_cli_baseline_gate(self, tmp_path, capsys):
        bl = str(tmp_path / "bl.json")
        target = fx("jl003_fire.py")
        assert lint_cli.main([target]) == 1
        assert lint_cli.main([target, "--baseline", bl,
                              "--update-baseline"]) == 0
        capsys.readouterr()
        # same findings, now grandfathered
        assert lint_cli.main([target, "--baseline", bl]) == 0
        out = capsys.readouterr().out
        assert "[baselined]" in out and "0 new" in out


class TestCLI:
    def test_json_format(self, capsys):
        rc = lint_cli.main([fx("jl005_fire.py"), "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["summary"]["total"] == 4
        assert all(f["rule"] == "JL005" for f in payload["findings"])

    def test_list_rules(self, capsys):
        assert lint_cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("JL001", "JL002", "JL003", "JL004", "JL005",
                    "JL006", "JL007", "JL008", "JL009", "JL010",
                    "JL011", "JL012", "JL013", "JL014", "JL015",
                    "JL016", "JL900"):
            assert rid in out
        assert "report-only" in out

    def test_rule_selection_and_unknown_rule(self, capsys):
        assert lint_cli.main([fx("jl001_fire.py"),
                              "--rules", "JL006"]) == 0
        assert lint_cli.main([fx("jl001_fire.py"),
                              "--rules", "JL042"]) == 2

    def test_package_gate_is_clean_and_fast(self):
        # THE acceptance gate: the shipped tree has zero gate findings,
        # every report-only finding is recorded in the committed
        # baseline (known-and-decided, e.g. JL007 carries whose callers
        # reuse the args tuple), and the full-package run stays under
        # the CI budget.  The budget is a pre-commit-usability bound:
        # ~4 s idle, so 25 s still catches an accidentally quadratic
        # rule while surviving the 2-3x slowdown of the full suite's
        # subprocess tests sharing the cores.
        findings, stats, _ = analyze_paths([PKGDIR])
        gate = [f for f in findings if not f.report_only]
        assert gate == [], gate
        repo_root = os.path.dirname(PKGDIR)
        known = set(baseline_mod.load_baseline(
            os.path.join(repo_root, "jaxlint_baseline.json")))

        def rel_key(f):
            # the committed baseline stores repo-relative paths; this
            # test analyzes with an absolute PKGDIR
            rel = os.path.relpath(f.path, repo_root).replace(os.sep, "/")
            return (f.rule, rel, f.symbol, f.message)

        undecided = [f for f in findings
                     if f.report_only and rel_key(f) not in known]
        assert undecided == [], undecided
        assert stats["elapsed_seconds"] < 25.0, stats

    def test_module_entry_points_agree(self):
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-m", "sagecal_tpu.analysis", PKGDIR],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------- runtime contracts


def _lm_problem(seed=3, nst=5):
    import jax.numpy as jnp

    from sagecal_tpu.core.types import identity_jones, jones_to_params
    from sagecal_tpu.io.simulate import (
        corrupt_and_observe,
        make_visdata,
        random_jones,
    )
    from sagecal_tpu.ops.rime import point_source_batch, predict_coherencies
    from sagecal_tpu.solvers.lm import LMConfig

    d = make_visdata(nstations=nst, tilesz=2, nchan=1, seed=seed)
    src = point_source_batch([0.01], [0.01], [2.0])
    J = random_jones(1, nst, seed=seed, amp=0.2)
    obs = corrupt_and_observe(d, [src], jones=J, noise_sigma=0.05,
                              seed=seed + 1)
    coh = predict_coherencies(d.u, d.v, d.w, d.freqs, src)
    p0 = jones_to_params(identity_jones(nst))[None]
    chunk_map = jnp.zeros((d.rows,), jnp.int32)
    return (obs.vis, coh, obs.mask, obs.ant_p, obs.ant_q, chunk_map, p0,
            LMConfig(itmax=8))


class TestContracts:
    def test_nan_raises_and_emits_event(self, monkeypatch):
        import jax.numpy as jnp

        from sagecal_tpu.obs.contracts import (
            ContractViolation,
            drain_contract_events,
            reset_contract_events,
        )
        from sagecal_tpu.obs.perf import instrumented_jit

        reset_contract_events()
        calls = []

        @instrumented_jit(name="contract_probe",
                          static_argnames=("double",))
        def f(x, double: bool = False):
            calls.append(1)
            y = jnp.sum(x) * (2.0 if double else 1.0)
            return y / x.shape[0]

        x = jnp.arange(4.0)
        monkeypatch.setenv("SAGECAL_CHECKIFY", "1")
        clean = f(x, double=True)
        assert np.isfinite(float(clean))
        with pytest.raises(ContractViolation) as ei:
            f(x.at[0].set(jnp.nan), double=True)
        assert ei.value.fn_name == "contract_probe"
        evs = drain_contract_events()
        assert [e["kind"] for e in evs] == ["contract_violation"]
        assert evs[0]["fn"] == "contract_probe"
        assert "nan" in evs[0]["detail"]

    def test_off_path_bit_identical_to_plain_jit(self, monkeypatch):
        import jax

        from sagecal_tpu.solvers.lm import lm_solve, lm_solve_jit

        monkeypatch.delenv("SAGECAL_CHECKIFY", raising=False)
        args = _lm_problem()
        ref_fn = jax.jit(
            lm_solve, static_argnames=("collect_quality",))
        ref = ref_fn(*args)
        out = lm_solve_jit(*args)
        # bit-identical, not allclose: the contract layer must not
        # perturb the unchecked path at all
        np.testing.assert_array_equal(np.asarray(out.p),
                                      np.asarray(ref.p))
        np.testing.assert_array_equal(np.asarray(out.cost),
                                      np.asarray(ref.cost))

    def test_checkify_on_matches_off_when_clean(self, monkeypatch):
        from sagecal_tpu.solvers.lm import lm_solve_jit

        args = _lm_problem(seed=7)
        monkeypatch.delenv("SAGECAL_CHECKIFY", raising=False)
        off = lm_solve_jit(*args)
        monkeypatch.setenv("SAGECAL_CHECKIFY", "1")
        on = lm_solve_jit(*args)
        np.testing.assert_allclose(np.asarray(on.p), np.asarray(off.p),
                                   rtol=1e-6)

    def test_fullbatch_nan_gain_e2e(self, tmp_path, monkeypatch):
        """Acceptance: SAGECAL_CHECKIFY=1 + an injected NaN gain ->
        contract_violation event in the JSONL log + CLI exit 4."""
        from sagecal_tpu.apps.cli import main as cli_main
        from sagecal_tpu.io import solutions as solio
        from sagecal_tpu.obs.contracts import reset_contract_events
        from sagecal_tpu.obs.events import read_events
        from test_apps import SKY, _make_dataset

        reset_contract_events()
        sky = tmp_path / "t.sky.txt"
        sky.write_text(SKY)
        (tmp_path / "t.sky.txt.cluster").write_text("1 1 P1\n2 1 P2\n")
        dsp = tmp_path / "d.h5"
        _make_dataset(dsp)
        # warm-start solutions with a NaN gain in cluster 0, station 0
        jones = np.tile(np.eye(2), (2, 7, 1, 1)).astype(np.complex128)
        jones[0, 0, 0, 0] = np.nan
        init = tmp_path / "init.txt"
        with open(init, "w") as fh:
            solio.write_header(fh, 150e6, 0.0, 1.0, 7, 2, 2)
            solio.append_solutions(fh, jones)
        elog_path = tmp_path / "events.jsonl"
        monkeypatch.setenv("SAGECAL_CHECKIFY", "1")
        monkeypatch.setenv("SAGECAL_TELEMETRY", "1")
        monkeypatch.setenv("SAGECAL_EVENT_LOG", str(elog_path))
        rc = cli_main([
            "-d", str(dsp), "-s", str(sky),
            "-p", str(tmp_path / "sol.txt"), "-q", str(init),
            "-t", "4", "-e", "2", "-g", "6", "-l", "15", "-j", "1",
        ])
        assert rc == 4
        events = read_events(str(elog_path))
        kinds = [e["type"] for e in events]
        assert "contract_violation" in kinds, kinds
        abort = [e for e in events if e["type"] == "run_aborted"]
        assert abort and abort[0]["reason"] == "contract_violation"

    def test_fullbatch_clean_run_with_checkify(self, tmp_path,
                                               monkeypatch):
        """A finite warm start under SAGECAL_CHECKIFY=1 completes."""
        from sagecal_tpu.apps.config import RunConfig
        from sagecal_tpu.apps.fullbatch import run_fullbatch
        from test_apps import SKY, _make_dataset

        sky = tmp_path / "t.sky.txt"
        sky.write_text(SKY)
        (tmp_path / "t.sky.txt.cluster").write_text("1 1 P1\n2 1 P2\n")
        dsp = tmp_path / "d.h5"
        _make_dataset(dsp)
        monkeypatch.setenv("SAGECAL_CHECKIFY", "1")
        cfg = RunConfig(
            dataset=str(dsp), sky_model=str(sky),
            cluster_file=str(sky) + ".cluster",
            out_solutions=str(tmp_path / "sol.txt"),
            tilesz=4, max_emiter=2, max_iter=6, max_lbfgs=15,
            solver_mode=1,
        )
        results = run_fullbatch(cfg, log=lambda *a: None)
        assert len(results) == 1
        r0, r1 = results[0]
        assert math.isfinite(r0) and math.isfinite(r1)
