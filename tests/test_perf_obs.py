"""Performance-observability tests (obs/perf.py): instrumented_jit
compile/recompile tracking, the zero-cost-off invariant, device-memory
watermark fallback on CPU, the transfer-guard audit, the `diag perf` /
`diag gate` CLI surfaces, and the rime_kernel chunk-plan contract the
round-5 advice asked to pin down."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu.obs import diag
from sagecal_tpu.obs.events import EventLog, read_events
from sagecal_tpu.obs.perf import (
    TransferAudit,
    aggregate_perf_events,
    device_memory_snapshot,
    drain_compile_events,
    emit_perf_events,
    format_gate_report,
    format_perf_report,
    gate_compare,
    instrumented_jit,
    memory_watermarks,
    note_compile,
    perf_stats,
    record_memory_watermark,
    reset_perf_stats,
)
from sagecal_tpu.obs.registry import get_registry, telemetry

pytestmark = [pytest.mark.perf, pytest.mark.telemetry]


@pytest.fixture(autouse=True)
def _clean_perf_store():
    reset_perf_stats()
    yield
    reset_perf_stats()


# ---------------------------------------------------------------------------
# instrumented_jit: compile tracking + recompile detection
# ---------------------------------------------------------------------------


class TestInstrumentedJit:
    def test_single_compile_and_reuse(self):
        @instrumented_jit(name="double")
        def f(x):
            return 2.0 * x

        x = jnp.arange(8.0)
        with telemetry(True):
            a = f(x)
            b = f(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        st = perf_stats()["double"]
        assert st["compiles"] == 1
        assert f.compiles == 1

    def test_recompile_on_changed_static_config(self):
        # the acceptance criterion: a deliberate static-config change is
        # visible as a compile count of 2 for the same function name
        @instrumented_jit(name="scaled", static_argnames=("k",))
        def f(x, k=1):
            return float(k) * x

        x = jnp.arange(4.0)
        with telemetry(True):
            f(x, k=1)
            f(x, k=1)  # same signature: cached
            f(x, k=3)  # changed static config: recompile
        assert perf_stats()["scaled"]["compiles"] == 2

    def test_recompile_on_changed_shape(self):
        @instrumented_jit(name="sq")
        def f(x):
            return x * x

        with telemetry(True):
            f(jnp.arange(4.0))
            f(jnp.arange(8.0))
        assert perf_stats()["sq"]["compiles"] == 2

    def test_flax_config_change_is_a_recompile(self):
        # solver configs are flax structs with every field static
        # (pytree_node=False): a changed config must retrace
        from sagecal_tpu.solvers.lm import LMConfig

        @instrumented_jit(name="cfgfn", static_argnames=("cfg",))
        def f(x, cfg=LMConfig()):
            return x * cfg.tau

        x = jnp.arange(4.0)
        with telemetry(True):
            f(x, cfg=LMConfig())
            f(x, cfg=LMConfig(itmax=2))
        assert perf_stats()["cfgfn"]["compiles"] == 2

    def test_python_scalar_values_do_not_retrace(self):
        @instrumented_jit(name="shift")
        def f(x, s):
            return x + s

        x = jnp.arange(4.0)
        with telemetry(True):
            f(x, 1.0)
            f(x, 2.5)  # same abstract signature: value is traced
        assert perf_stats()["shift"]["compiles"] == 1

    def test_off_is_passthrough_and_untracked(self):
        @instrumented_jit(name="offfn")
        def f(x):
            return x + 1.0

        with telemetry(False):  # explicit: CI runs with the env var set
            out = f(jnp.arange(4.0))
        np.testing.assert_allclose(np.asarray(out), np.arange(4.0) + 1.0)
        assert "offfn" not in perf_stats()

    def test_output_signature_matches_plain_jit_when_off(self):
        # zero-cost-off acceptance: the wrapper must not change jitted
        # output structure, dtype, or values relative to jax.jit
        def g(x):
            return {"y": x * 2.0, "n": (x.sum(), x - 1.0)}

        plain = jax.jit(g)
        inst = instrumented_jit(g, name="sigfn")
        x = jnp.arange(6.0).reshape(2, 3)
        a, b = plain(x), inst(x)
        ta = jax.tree_util.tree_structure(a)
        tb = jax.tree_util.tree_structure(b)
        assert ta == tb
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            assert la.shape == lb.shape and la.dtype == lb.dtype
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb))

    def test_on_off_results_identical(self):
        @instrumented_jit(name="onoff")
        def f(x):
            return jnp.sin(x) + x

        x = jnp.linspace(0.0, 1.0, 16)
        off = np.asarray(f(x))
        with telemetry(True):
            on = np.asarray(f(x))
        np.testing.assert_allclose(off, on)

    def test_compile_events_and_registry(self):
        @instrumented_jit(name="evfn")
        def f(x):
            return x * 3.0

        with telemetry(True):
            reg = get_registry()
            f(jnp.arange(4.0))
            evs = drain_compile_events()
            assert any(e["fn"] == "evfn" for e in evs)
            ev = [e for e in evs if e["fn"] == "evfn"][0]
            assert ev["n_compiles"] == 1
            assert ev["lower_seconds"] >= 0.0
            assert ev["compile_seconds"] > 0.0
            assert reg.get_counter("jit_compiles_total", fn="evfn") == 1.0

    def test_static_argnums_positional(self):
        @instrumented_jit(name="posstat", static_argnums=(1,))
        def f(x, n):
            return x[:n]

        with telemetry(True):
            a = f(jnp.arange(8.0), 3)
            b = f(jnp.arange(8.0), 3)
            c = f(jnp.arange(8.0), 5)
        assert a.shape == (3,) and b.shape == (3,) and c.shape == (5,)
        assert perf_stats()["posstat"]["compiles"] == 2


# ---------------------------------------------------------------------------
# device memory watermarks
# ---------------------------------------------------------------------------


class TestDeviceMemory:
    def test_snapshot_cpu_fallback(self):
        # CPU backends return None from memory_stats(): the snapshot
        # must degrade to host RSS, not crash or zero out
        snap = device_memory_snapshot()
        assert snap["source"] in ("device", "host_rss")
        assert snap["bytes_in_use"] > 0
        assert snap["peak_bytes_in_use"] > 0

    def test_watermark_records_and_maxes(self):
        with telemetry(True):
            s1 = record_memory_watermark("solve")
            s2 = record_memory_watermark("solve")
        assert s1 is not None and s2 is not None
        marks = memory_watermarks()
        assert "solve" in marks and marks["solve"] > 0
        reg = get_registry()
        # gauge folded to the max of both samples under telemetry(True)?
        # registry swaps on telemetry() exit — the module store is the
        # durable record
        assert marks["solve"] == max(
            s1["peak_bytes_in_use"], s2["peak_bytes_in_use"]
        )

    def test_watermark_off_is_none(self):
        with telemetry(False):
            assert record_memory_watermark("idle") is None
        assert memory_watermarks() == {}


# ---------------------------------------------------------------------------
# transfer audit
# ---------------------------------------------------------------------------


class TestTransferAudit:
    def test_disabled_is_noop(self):
        with TransferAudit(enabled=False) as audit:
            jnp.arange(4.0) + 1
        assert audit.total == 0

    def test_captures_implicit_transfers(self):
        # python-scalar promotion inside an op is the reliable implicit
        # host->device transfer the guard logs (an explicit jnp.asarray
        # does not trip it)
        with telemetry(True):
            with TransferAudit(enabled=True) as audit:
                x = jnp.arange(8)
                (x + 1).block_until_ready()
        assert audit.total >= 1
        assert audit.counts.get("host_to_device", 0) >= 1
        assert audit.samples

    def test_emit_event(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        with telemetry(True):
            with TransferAudit(enabled=True) as audit:
                (jnp.arange(4) + 1).block_until_ready()
            elog = EventLog(path)
            audit.emit(elog)
            elog.close()
        evs = [e for e in read_events(path) if e["type"] == "transfer_audit"]
        assert len(evs) == 1
        assert evs[0]["total"] == audit.total

    def test_exit_is_idempotent(self):
        audit = TransferAudit(enabled=True)
        with audit:
            pass
        audit.__exit__(None, None, None)  # second exit must not blow up


# ---------------------------------------------------------------------------
# events -> aggregation -> diag perf
# ---------------------------------------------------------------------------


class TestPerfEventsAndDiag:
    def _make_log(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with telemetry(True):
            @instrumented_jit(name="agfn")
            def f(x):
                return x * 2.0

            f(jnp.arange(4.0))
            f(jnp.arange(6.0))
            record_memory_watermark("solve")
            elog = EventLog(path)
            emit_perf_events(elog)
            elog.close()
        return path

    def test_emit_and_aggregate(self, tmp_path):
        path = self._make_log(tmp_path)
        evs = read_events(path)
        agg = aggregate_perf_events(evs)
        assert agg["functions"]["agfn"]["compiles"] == 2
        assert agg["memory"].get("solve", 0) > 0
        report = format_perf_report(agg)
        assert "agfn" in report and "solve" in report

    def test_diag_perf_cli(self, tmp_path, capsys):
        path = self._make_log(tmp_path)
        rc = diag.main(["perf", path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "agfn" in out

    def test_diag_perf_cli_empty_is_failure(self, tmp_path, capsys):
        path = str(tmp_path / "empty.jsonl")
        with telemetry(True):
            elog = EventLog(path)
            elog.emit("run_done")
            elog.close()
        rc = diag.main(["perf", path])
        assert rc == 1

    def test_note_compile_external_channel(self):
        # code that AOT-compiles itself reports through note_compile;
        # it must land in the same aggregates
        with telemetry(True):
            note_compile("bench_step_fused", 0.5, 2.0, 1e9, 2e8)
        st = perf_stats()["bench_step_fused"]
        assert st["compiles"] == 1
        assert st["flops"] == 1e9 and st["bytes_accessed"] == 2e8


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------


BASE = {
    "value": 32.7,
    "platform": "tpu",
    "xla_cost_analysis_bytes_accessed": 1.0e9,
    "peak_device_memory_bytes": 2.0e9,
}


class TestGate:
    def test_baseline_vs_itself_passes(self):
        failures, rows = gate_compare(dict(BASE), dict(BASE))
        assert failures == []
        assert all(r[5] == "ok" for r in rows)
        assert "GATE: PASS" in format_gate_report(rows, failures)

    def test_20pct_throughput_regression_fails(self):
        new = dict(BASE, value=BASE["value"] * 0.8)
        failures, rows = gate_compare(new, BASE)
        assert len(failures) == 1
        assert "value" in failures[0]
        assert "GATE: FAIL" in format_gate_report(rows, failures)

    def test_20pct_memory_rise_fails(self):
        new = dict(BASE, peak_device_memory_bytes=2.4e9)
        failures, _ = gate_compare(new, BASE)
        assert len(failures) == 1
        assert "peak_device_memory_bytes" in failures[0]

    def test_improvement_passes(self):
        new = dict(BASE, value=BASE["value"] * 1.5,
                   xla_cost_analysis_bytes_accessed=0.5e9)
        failures, _ = gate_compare(new, BASE)
        assert failures == []

    def test_within_tolerance_passes(self):
        new = dict(BASE, value=BASE["value"] * 0.95)
        failures, _ = gate_compare(new, BASE)
        assert failures == []

    def test_per_metric_tolerance_override(self):
        new = dict(BASE, value=BASE["value"] * 0.75)
        failures, _ = gate_compare(new, BASE, tolerances={"value": 0.30})
        assert failures == []

    def test_missing_metric_is_skipped(self):
        new = {"value": 32.7}
        failures, rows = gate_compare(new, BASE)
        assert failures == []
        assert [r[0] for r in rows] == ["value"]

    def test_diag_gate_cli_roundtrip(self, tmp_path, capsys):
        b = tmp_path / "base.json"
        n = tmp_path / "new.json"
        b.write_text(json.dumps(BASE))
        n.write_text(json.dumps(dict(BASE, value=BASE["value"] * 0.8)))
        assert diag.main(["gate", str(b), "--baseline", str(b)]) == 0
        assert diag.main(["gate", str(n), "--baseline", str(b)]) == 1
        out = capsys.readouterr().out
        assert "GATE: FAIL" in out

    def test_diag_gate_evidence_mismatch_refuses(self, tmp_path, capsys):
        # a cpu-wallclock run vs tpu-wallclock pins: the old behaviour
        # was a silent SKIP (exit 0) — now the gate REFUSES loudly
        # (exit 2) so CI can't mistake "wrong hardware" for "passed"
        b = tmp_path / "base.json"
        n = tmp_path / "new.json"
        b.write_text(json.dumps(BASE))
        n.write_text(json.dumps(dict(BASE, platform="cpu",
                                     value=BASE["value"] * 0.5)))
        assert diag.main(["gate", str(n), "--baseline", str(b)]) == 2
        err = capsys.readouterr().err
        assert "REFUSED" in err and "evidence-class mismatch" in err
        assert "cpu-wallclock" in err and "tpu-wallclock" in err
        # --strict forces the comparison and catches the regression
        assert diag.main(["gate", str(n), "--baseline", str(b),
                          "--strict"]) == 1

    def test_diag_gate_explicit_evidence_field_refuses(self, tmp_path,
                                                       capsys):
        # an explicit evidence field wins over platform derivation:
        # same platform, different proof class -> still refused
        b = tmp_path / "base.json"
        n = tmp_path / "new.json"
        b.write_text(json.dumps(dict(BASE, evidence="tpu-wallclock")))
        n.write_text(json.dumps(dict(BASE, evidence="aot-bytes")))
        assert diag.main(["gate", str(n), "--baseline", str(b)]) == 2
        assert "REFUSED" in capsys.readouterr().err

    def test_diag_gate_per_metric_evidence_exclusion(self, tmp_path,
                                                     capsys):
        # matching record-level classes, but one metric's override
        # mismatches: that metric is dropped (with a note) and its
        # regression does NOT fail the gate; everything else still gates
        b = tmp_path / "base.json"
        n = tmp_path / "new.json"
        b.write_text(json.dumps(dict(
            BASE, evidence_classes={"peak_device_memory_bytes":
                                    "aot-bytes"})))
        n.write_text(json.dumps(dict(
            BASE, peak_device_memory_bytes=100 * 2.0e9,
            evidence_classes={"peak_device_memory_bytes":
                              "tpu-wallclock"})))
        assert diag.main(["gate", str(n), "--baseline", str(b)]) == 0
        out = capsys.readouterr().out
        assert "peak_device_memory_bytes excluded" in out
        assert "GATE: PASS" in out

    def test_pinned_repo_baseline_gates_itself(self, capsys):
        base = os.path.join(os.path.dirname(__file__), os.pardir,
                            "BENCH_BASELINE.json")
        assert diag.main(["gate", base, "--baseline", base]) == 0
        assert "GATE: PASS" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# profiling satellites: chunk-plan contract
# ---------------------------------------------------------------------------


class TestChunkPlanContract:
    def test_map_row_chunks_covers_rows_exactly(self):
        # round-5 advice closed in PR 1: the assert is live — verify it
        from sagecal_tpu.ops.rime_kernel import _chunk_plan, _map_row_chunks

        plan = _chunk_plan(512, tile=128, max_rows=256)
        assert plan == (2, 256)
        with pytest.raises(AssertionError):
            _map_row_chunks(lambda i: jnp.zeros((1, 8, 128)), 2, 128, 1, 512)

    def test_chunk_plan_rejects_uneven_rows(self):
        from sagecal_tpu.ops.rime_kernel import _chunk_plan

        with pytest.raises(ValueError):
            _chunk_plan(640, tile=128, max_rows=512)
