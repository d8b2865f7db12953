"""Observing a solve does not change it: a run with SAGECAL_TELEMETRY=1
hands the same SageConfig to ``solve_tile`` as a run without it, and
writes bit-identical solutions and residuals.  Driven through the CLI
at rehearsal size, once per solver mode (each reaches a different
solver call site of the EM phase), and through one window of the
stream calibrator."""

import shutil

import h5py
import numpy as np
import pytest

from sagecal_tpu.apps import cli
from sagecal_tpu.apps import fullbatch
from sagecal_tpu.io.simulate import random_jones
from sagecal_tpu.obs import registry

NST = 7
SOLVE_TILE = fullbatch.solve_tile


@pytest.fixture(scope="module")
def scene(tmp_path_factory):
    """Sky, cluster file and a one-tile dataset with gains to solve for."""
    from test_apps import CLUSTER, SKY, _make_dataset

    d = tmp_path_factory.mktemp("scene")
    (d / "t.sky.txt").write_text(SKY)
    (d / "t.sky.txt.cluster").write_text(CLUSTER)
    _make_dataset(d / "d.h5", nstations=NST, ntime=2,
                  jones=random_jones(2, NST, seed=6, amp=0.1,
                                     dtype=np.complex128))
    return d


def _set_telemetry(monkeypatch, tmp_path, on):
    monkeypatch.setattr(registry, "_enabled", None)
    if on:
        monkeypatch.setenv("SAGECAL_TELEMETRY", "1")
        monkeypatch.setenv("SAGECAL_EVENT_LOG",
                           str(tmp_path / "events.jsonl"))
    else:
        monkeypatch.delenv("SAGECAL_TELEMETRY", raising=False)
        monkeypatch.delenv("SAGECAL_EVENT_LOG", raising=False)
    assert registry.telemetry_enabled() == on


def _h5_arrays(path):
    out = {}
    with h5py.File(path, "r") as f:
        f.visititems(lambda name, obj: out.__setitem__(name, obj[()])
                     if isinstance(obj, h5py.Dataset) else None)
    return out


def _cli_run(scene, run_dir, extra, monkeypatch):
    """One CLI run on a fresh copy of the dataset: (configs handed to
    solve_tile, solutions text, every HDF5 array after the run)."""
    run_dir.mkdir()
    monkeypatch.chdir(run_dir)
    shutil.copy(scene / "d.h5", run_dir / "d.h5")
    configs = []

    def spy(data, cdata, p0, config, **kw):
        configs.append(config)
        return SOLVE_TILE(data, cdata, p0, config, **kw)

    monkeypatch.setattr(fullbatch, "solve_tile", spy)
    argv = ["-d", str(run_dir / "d.h5"),
            "-s", str(scene / "t.sky.txt"),
            "-c", str(scene / "t.sky.txt.cluster"),
            "-p", str(run_dir / "sol.txt"), "-t", "2",
            "-e", "2", "-g", "2", "-l", "4", "--abort-on-divergence",
            *extra]
    assert cli.main(argv) == 0
    return (configs, (run_dir / "sol.txt").read_text(),
            _h5_arrays(run_dir / "d.h5"))


CLI_CASES = [
    *[pytest.param(["-j", str(j), "--f32", "--fused"], id=f"j{j}-f32-fused")
      for j in range(7)],
    pytest.param(["-j", "5"], id="j5-f64"),
    pytest.param(["-j", "5", "--f32", "--fused", "-W"], id="j5-whiten"),
]


def _stream_run(scene, run_dir, monkeypatch):
    """One window of the stream calibrator: (its cold and warm configs,
    the configs handed to solve_tile, the solutions text)."""
    from sagecal_tpu.apps.config import StreamConfig
    from sagecal_tpu.fleet.stream import StreamCalibrator
    from sagecal_tpu.solvers import sage

    run_dir.mkdir()
    monkeypatch.chdir(run_dir)
    configs = []

    def spy(data, cdata, p0, config, **kw):
        configs.append(config)
        return SOLVE_TILE(data, cdata, p0, config, **kw)

    monkeypatch.setattr(sage, "solve_tile", spy)
    cfg = StreamConfig(dataset=str(scene / "d.h5"),
                       sky_model=str(scene / "t.sky.txt"),
                       cluster_file=str(scene / "t.sky.txt.cluster"),
                       out_dir=str(run_dir / "out"), window=2, hop=1,
                       max_windows=1, max_emiter=2, max_iter=2,
                       max_lbfgs=4, solver_mode=1, warm_emiter=1,
                       warm_lbfgs=2, checkpoint_every=0)
    cal = StreamCalibrator(cfg, log=lambda *a: None)
    summary = cal.run()
    assert summary["solved"] == 1
    return (cal._sage_configs(), configs,
            open(summary["solutions"]).read())


@pytest.mark.parametrize("case", [*CLI_CASES, pytest.param(
    None, id="stream-window")])
def test_an_observed_run_is_the_same_run(case, scene, tmp_path,
                                         monkeypatch):
    runs = {}
    for on in (False, True):
        _set_telemetry(monkeypatch, tmp_path, on)
        run_dir = tmp_path / ("on" if on else "off")
        if case is None:
            runs[on] = _stream_run(scene, run_dir, monkeypatch)
        else:
            runs[on] = _cli_run(scene, run_dir, case, monkeypatch)
    off, on = runs[False], runs[True]
    if case is None:
        (cold_off, warm_off), solved_off, sol_off = off
        (cold_on, warm_on), solved_on, sol_on = on
        assert cold_on == cold_off and warm_on == warm_off
        assert solved_off == [cold_off] and solved_on == solved_off
        assert sol_on == sol_off
        return
    configs_off, sol_off, h5_off = off
    configs_on, sol_on, h5_on = on
    assert len(configs_off) == 1 and configs_on == configs_off
    assert sol_on == sol_off
    assert h5_on.keys() == h5_off.keys()
    for name, arr in h5_off.items():
        np.testing.assert_array_equal(h5_on[name], arr, err_msg=name)
