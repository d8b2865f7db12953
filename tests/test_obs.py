"""Telemetry subsystem tests: metrics registry semantics, JSONL
event-log round trips, run manifests, ADMM residual telemetry vs
pure-python references, and the diag CLI over the event log."""

import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.obs.events import (
    EventLog,
    RunManifest,
    default_event_log,
    read_events,
    validate_manifest,
)
from sagecal_tpu.obs.registry import (
    MetricsRegistry,
    NullRegistry,
    get_registry,
    telemetry,
    telemetry_enabled,
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram_semantics(self):
        reg = MetricsRegistry()
        reg.counter_inc("tiles_total")
        reg.counter_inc("tiles_total", 2.0)
        reg.counter_inc("tiles_total", 1.0, app="fullbatch")
        assert reg.get_counter("tiles_total") == 3.0
        assert reg.get_counter("tiles_total", app="fullbatch") == 1.0
        assert reg.get_counter("never_touched") == 0.0

        reg.gauge_set("rho", 5.0, cluster="0")
        reg.gauge_set("rho", 7.0, cluster="0")  # gauges overwrite
        assert reg.get_gauge("rho", cluster="0") == 7.0
        assert reg.get_gauge("rho", cluster="1") is None

        for v in (0.003, 0.02, 0.02, 4.0):
            reg.observe("phase_seconds", v, phase="predict")
        snap = reg.snapshot()
        h = snap["histograms"]['phase_seconds{phase="predict"}']
        assert h["count"] == 4
        assert h["sum"] == pytest.approx(4.043)
        assert h["min"] == pytest.approx(0.003)
        assert h["max"] == pytest.approx(4.0)

    def test_prometheus_export_format(self):
        reg = MetricsRegistry()
        reg.counter_inc("solves_total", 2, help="completed solves")
        reg.gauge_set("last_res", 0.25)
        reg.observe("phase_seconds", 0.02, phase="solve")
        reg.observe("phase_seconds", 40.0, phase="solve")
        text = reg.to_prometheus()
        lines = text.splitlines()
        assert "# HELP solves_total completed solves" in lines
        assert "# TYPE solves_total counter" in lines
        assert "solves_total 2" in lines
        assert "last_res 0.25" in lines
        assert "# TYPE phase_seconds histogram" in lines
        # cumulative bucket counts: one obs <= 0.05, both <= +Inf
        assert 'phase_seconds_bucket{phase="solve",le="0.05"} 1' in lines
        assert 'phase_seconds_bucket{phase="solve",le="+Inf"} 2' in lines
        assert 'phase_seconds_count{phase="solve"} 2' in lines

    def test_disabled_registry_is_noop(self):
        with telemetry(False):
            assert not telemetry_enabled()
            reg = get_registry()
            assert isinstance(reg, NullRegistry)
            assert not reg.enabled
            reg.counter_inc("x")
            reg.gauge_set("y", 1.0)
            reg.observe("z", 1.0)
            assert reg.get_counter("x") == 0.0
            assert reg.snapshot() == {
                "counters": {}, "gauges": {}, "histograms": {}
            }
        with telemetry(True):
            assert telemetry_enabled()
            assert get_registry().enabled

    def test_clear(self):
        reg = MetricsRegistry()
        reg.counter_inc("a")
        reg.observe("b", 1.0)
        reg.clear()
        assert reg.get_counter("a") == 0.0
        assert reg.to_prometheus() == ""


# ---------------------------------------------------------------------------
# event log + run manifest
# ---------------------------------------------------------------------------


class TestEvents:
    def test_jsonl_round_trip(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with EventLog(p, run_id="r1") as log:
            log.emit("tile_done", tile=0, res0=1.5,
                     phase=np.float32(0.25),
                     arr=np.arange(3), nested={"k": jnp.float32(2.0)})
            log.emit("run_done", n_tiles=1)
        evs = read_events(p)
        assert [e["type"] for e in evs] == ["tile_done", "run_done"]
        assert all(e["run_id"] == "r1" for e in evs)
        e = evs[0]
        assert e["tile"] == 0 and e["res0"] == 1.5
        assert e["phase"] == pytest.approx(0.25)
        assert e["arr"] == [0, 1, 2]
        assert e["nested"]["k"] == 2.0
        # every line parses as standalone JSON
        for line in open(p):
            json.loads(line)

    def test_read_events_skips_corrupt_lines(self, tmp_path):
        p = tmp_path / "ev.jsonl"
        p.write_text('{"type": "a", "ts": 1.0}\n'
                     "\n"
                     '{"type": "b", "ts"\n'  # truncated (crashed run)
                     '{"type": "c", "ts": 2.0}\n')
        evs = read_events(str(p))
        assert [e["type"] for e in evs] == ["a", "c"]

    def test_manifest_collect_and_validate(self):
        m = RunManifest.collect(kernel_path="xla", app="test", tilesz=4)
        d = m.to_dict()
        assert validate_manifest(d) == []
        assert d["platform"] == "cpu"  # conftest forces the CPU backend
        assert d["num_devices"] >= 1
        assert d["backend_error"] is None
        assert d["extra"]["app"] == "test" and d["extra"]["tilesz"] == 4
        assert d["kernel_path"] == "xla"
        assert len(d["run_id"]) == 12
        json.dumps(d)  # must be JSON-serializable as-is

    def test_validate_manifest_problems(self):
        bad = {"schema_version": 999, "num_devices": "eight"}
        problems = validate_manifest(bad)
        assert any("missing key: run_id" in p for p in problems)
        assert any("schema_version" in p for p in problems)
        assert any("num_devices" in p for p in problems)

    def test_manifest_is_first_event(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        m = RunManifest.collect()
        with EventLog(p, manifest=m) as log:
            log.emit("tile_done", tile=0)
        evs = read_events(p)
        assert evs[0]["type"] == "run_manifest"
        assert evs[0]["run_id"] == m.run_id
        assert evs[1]["run_id"] == m.run_id
        assert validate_manifest(evs[0]) == []

    def test_default_event_log_gating(self, tmp_path, monkeypatch):
        with telemetry(False):
            assert default_event_log() is None
        monkeypatch.setenv("SAGECAL_EVENT_LOG", str(tmp_path / "e.jsonl"))
        with telemetry(True):
            log = default_event_log()
            assert log is not None
            log.emit("x")
            log.close()
        assert read_events(str(tmp_path / "e.jsonl"))[0]["type"] == "x"


# ---------------------------------------------------------------------------
# ADMM residual telemetry vs pure-python references
# ---------------------------------------------------------------------------


class TestAdmmResidualReferences:
    def test_primal_residual_flat_matches_numpy(self):
        rng = np.random.default_rng(5)
        J = rng.standard_normal(48)
        BZ = rng.standard_normal(48)
        from sagecal_tpu.parallel import consensus

        got = float(consensus.admm_primal_residual(
            jnp.asarray(J), jnp.asarray(BZ)))
        want = np.linalg.norm(J - BZ) / math.sqrt(J.size)
        assert got == pytest.approx(want, rel=1e-6)

    def test_primal_residual_batched_matches_numpy(self):
        rng = np.random.default_rng(6)
        J = rng.standard_normal((3, 16))
        BZ = rng.standard_normal((3, 16))
        from sagecal_tpu.parallel import consensus

        got = np.asarray(consensus.admm_primal_residual(
            jnp.asarray(J), jnp.asarray(BZ)))
        want = np.linalg.norm(J - BZ, axis=-1) / math.sqrt(16)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_dual_residual_matches_numpy(self):
        rng = np.random.default_rng(7)
        Z0 = rng.standard_normal((2, 3, 4))
        Z1 = rng.standard_normal((2, 3, 4))
        from sagecal_tpu.parallel import consensus

        got = float(consensus.admm_dual_residual(
            jnp.asarray(Z1), jnp.asarray(Z0)))
        want = np.linalg.norm((Z1 - Z0).ravel()) / math.sqrt(Z0.size)
        assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.slow
class TestAdmmMeshTrace:
    def test_residual_trace_consistent_with_returned_state(self, devices8):
        """collect_trace=True mesh run: per-band traces must agree with a
        pure-python recomputation from the returned (p, Z) state."""
        from jax.sharding import Mesh

        from sagecal_tpu.core.types import jones_to_params
        from sagecal_tpu.io.simulate import random_jones
        from sagecal_tpu.parallel import consensus
        from sagecal_tpu.parallel.mesh import make_admm_mesh_fn, stack_for_mesh
        from sagecal_tpu.solvers.lm import LMConfig
        from test_admm_mesh import _one_band

        Nf, M, N = 8, 2, 8
        nadmm = 4
        freqs = np.linspace(120e6, 180e6, Nf)
        f0 = 150e6
        jones = random_jones(M, N, seed=3, amp=0.2, dtype=np.complex128)
        bands = []
        for f in range(Nf):
            data, cdata = _one_band(f0, jones, seed=f)
            data = data.replace(freqs=jnp.asarray([freqs[f]], jnp.float64))
            bands.append((data, cdata))
        p0 = jnp.stack([
            jones_to_params(
                random_jones(M, N, seed=500, amp=0.0, dtype=np.complex128)
            )[:, None, :]
            for _ in range(Nf)
        ])
        mesh = Mesh(np.array(devices8), ("freq",))
        B = consensus.setup_polynomials(freqs, f0, 2, consensus.POLY_ORDINARY)
        fn = make_admm_mesh_fn(
            mesh, nadmm=nadmm, max_emiter=1, plain_emiter=1,
            lm_config=LMConfig(itmax=4), bb_rho=False, collect_trace=True,
        )
        rho0 = jnp.full((Nf, M), 10.0, jnp.float64)
        out = fn(stack_for_mesh([b[0] for b in bands]),
                 stack_for_mesh([b[1] for b in bands]),
                 p0, rho0, jnp.asarray(B))
        prn = np.asarray(out.primal_res_band)
        ddn = np.asarray(out.dual_res_band)
        rho_t = np.asarray(out.rho_trace)
        assert prn.shape == (nadmm, Nf)
        assert ddn.shape == (nadmm, Nf)
        assert rho_t.shape == (nadmm, Nf, M)
        # bb_rho off: the penalty trajectory is constant
        np.testing.assert_allclose(rho_t, 10.0)
        # iteration 0 is the plain solve vs the first consensus: dual 0
        np.testing.assert_allclose(ddn[0], 0.0)
        assert np.all(np.isfinite(prn)) and np.all(prn >= 0)
        # the last trace row is recomputable from the returned p and Z
        for f in range(Nf):
            BZ = consensus.bz_for_freq(out.Z, jnp.asarray(B[f], out.Z.dtype))
            want = float(consensus.admm_primal_residual(
                out.p[f].reshape(-1), BZ.reshape(-1)))
            assert prn[-1, f] == pytest.approx(want, rel=1e-6)
        # the scalar primal trace is the band mean of the per-band trace
        np.testing.assert_allclose(
            np.asarray(out.primal_res)[1:], prn[1:].mean(axis=1), rtol=1e-6)


# ---------------------------------------------------------------------------
# diag CLI
# ---------------------------------------------------------------------------


class TestDiagCli:
    def test_manifest_write_and_validate(self, tmp_path, capsys):
        from sagecal_tpu.obs.diag import main

        out = str(tmp_path / "m.json")
        assert main(["manifest", "--out", out]) == 0
        assert validate_manifest(json.load(open(out))) == []
        assert main(["validate", out]) == 0
        assert "valid manifest" in capsys.readouterr().out

    def test_validate_rejects_bad_manifest(self, tmp_path, capsys):
        from sagecal_tpu.obs.diag import main

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema_version": 999}))
        assert main(["validate", str(bad)]) == 1
        assert "missing key" in capsys.readouterr().err

    def test_events_summary_and_validate_jsonl(self, tmp_path, capsys):
        from sagecal_tpu.obs.diag import main

        p = str(tmp_path / "ev.jsonl")
        with EventLog(p, manifest=RunManifest.collect()) as log:
            log.emit("admm_round", tile=0, primal_res=[0.2, 0.1],
                     dual_res=[0.05, 0.02])
            log.emit("tile_done", tile=0,
                     phase_seconds={"predict": 0.5, "solve": 1.5})
        # validate finds the run_manifest event inside the JSONL
        assert main(["validate", p]) == 0
        assert main(["events", p]) == 0
        out = capsys.readouterr().out
        assert "3 events" in out
        assert "admm_round: 1" in out
        assert "dual_res max=0.05" in out
        assert "1 done, 2.00s in phases" in out

    def test_prom_reingests_events(self, tmp_path, capsys):
        from sagecal_tpu.obs.diag import main

        p = str(tmp_path / "ev.jsonl")
        with EventLog(p) as log:
            log.emit("tile_done", tile=0, phase_seconds={"solve": 2.0})
            log.emit("bench_result", value=123.0, fused_kernel=False)
        assert main(["prom", "--events", p]) == 0
        out = capsys.readouterr().out
        assert 'phase_seconds_sum{phase="solve"} 2' in out
        assert 'bench_lbfgs_iters_per_second{kernel="xla"} 123' in out

    def test_main_cli_dispatches_diag(self, tmp_path, capsys):
        from sagecal_tpu.apps.cli import main

        out = str(tmp_path / "m.json")
        assert main(["diag", "manifest", "--out", out]) == 0
        assert validate_manifest(json.load(open(out))) == []


# ---------------------------------------------------------------------------
# end-to-end: fullbatch app writes the event log
# ---------------------------------------------------------------------------


@pytest.mark.telemetry
class TestFullbatchTelemetry:
    def test_event_log_contents(self, tmp_path, monkeypatch):
        from sagecal_tpu.apps.config import RunConfig
        from sagecal_tpu.apps.fullbatch import run_fullbatch
        from test_apps import CLUSTER, SKY, _make_dataset
        from sagecal_tpu.io.simulate import random_jones

        sky = tmp_path / "t.sky.txt"
        sky.write_text(SKY)
        (tmp_path / "t.sky.txt.cluster").write_text(CLUSTER)
        dsp = tmp_path / "d.h5"
        jones = random_jones(2, 7, seed=3, amp=0.15, dtype=np.complex128)
        _make_dataset(dsp, jones=jones)
        evp = str(tmp_path / "run.jsonl")
        monkeypatch.setenv("SAGECAL_EVENT_LOG", evp)
        cfg = RunConfig(
            dataset=str(dsp), sky_model=str(sky),
            cluster_file=str(tmp_path / "t.sky.txt.cluster"),
            out_solutions=str(tmp_path / "sol.txt"),
            tilesz=4, max_emiter=1, max_iter=3, max_lbfgs=4, solver_mode=1,
        )
        with telemetry(True):
            run_fullbatch(cfg, log=lambda *a: None)

        evs = read_events(evp)
        by_type = {}
        for e in evs:
            by_type.setdefault(e["type"], []).append(e)

        # manifest header with app metadata + platform info
        man = by_type["run_manifest"][0]
        assert validate_manifest(man) == []
        assert man["extra"]["app"] == "fullbatch"
        assert man["platform"] == "cpu"
        run_id = man["run_id"]
        assert all(e["run_id"] == run_id for e in evs)

        # per-tile phase timings
        tiles = by_type["tile_done"]
        assert len(tiles) == 1
        t = tiles[0]
        assert t["res1"] <= t["res0"]
        assert "predict" in t["phase_seconds"] or t["phase_seconds"]
        assert all(v >= 0 for v in t["phase_seconds"].values())
        assert by_type["run_done"][0]["n_tiles"] == 1


@pytest.mark.telemetry
class TestTelemetryOffIsDefaultOff:
    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("SAGECAL_TELEMETRY", raising=False)
        from sagecal_tpu.obs import registry

        monkeypatch.setattr(registry, "_enabled", None)
        assert not registry.telemetry_enabled()
        monkeypatch.setenv("SAGECAL_TELEMETRY", "1")
        assert registry.telemetry_enabled()
        monkeypatch.setenv("SAGECAL_TELEMETRY", "off")
        assert not registry.telemetry_enabled()
