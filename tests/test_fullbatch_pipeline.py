"""The fullbatch tile loop is software-pipelined by one solve
(apps/fullbatch.py): the next tile's solve is dispatched as soon as this
tile's gains are final, before this tile's residual, so the host
residual and write run while the chip solves.  Driven through the CLI at
rehearsal size, with the solve and the residual spied on."""

import numpy as np
import pytest

from sagecal_tpu.apps import cli
from sagecal_tpu.apps import fullbatch
from sagecal_tpu.core.types import identity_jones, jones_to_params
from sagecal_tpu.io.simulate import random_jones

NTILES, NST = 4, 7


@pytest.fixture()
def spied(tmp_path, monkeypatch):
    """A 4-tile dataset with gains to solve for, and the ordered list of
    (call, p) the CLI run makes of solve_tile and calculate_residuals."""
    from test_apps import CLUSTER, SKY, _make_dataset

    (tmp_path / "t.sky.txt").write_text(SKY)
    (tmp_path / "t.sky.txt.cluster").write_text(CLUSTER)
    _make_dataset(tmp_path / "d.h5", nstations=NST, ntime=2 * NTILES,
                  jones=random_jones(2, NST, seed=6, amp=0.1,
                                     dtype=np.complex128))
    calls = []

    def spy(name, fn, p_pos):
        def wrapped(*args, **kw):
            calls.append((name, np.asarray(args[p_pos])))
            return fn(*args, **kw)
        return wrapped

    monkeypatch.setattr(fullbatch, "solve_tile",
                        spy("solve", fullbatch.solve_tile, 2))
    monkeypatch.setattr(fullbatch, "calculate_residuals",
                        spy("residual", fullbatch.calculate_residuals, 2))
    argv = ["-d", str(tmp_path / "d.h5"), "-s", str(tmp_path / "t.sky.txt"),
            "-c", str(tmp_path / "t.sky.txt.cluster"),
            "-p", str(tmp_path / "sol.txt"), "-t", "2",
            "-e", "2", "-g", "2", "-l", "4", "-j", "5", "--f32"]
    return argv, calls


def _pinit():
    eye = np.asarray(jones_to_params(identity_jones(NST, np.complex64)))
    return np.broadcast_to(eye, (2, 1, 8 * NST))


def test_next_solve_is_dispatched_before_the_residual(spied, capsys):
    argv, calls = spied
    assert cli.main(argv) == 0
    order = [name for name, _ in calls]
    want = ["solve", "solve"]
    for _ in range(NTILES - 2):
        want += ["residual", "solve"]
    want += ["residual", "residual"]
    assert order == want
    # each later solve starts from the gains of the tile before it
    solves = [p for name, p in calls if name == "solve"]
    residuals = [p for name, p in calls if name == "residual"]
    np.testing.assert_array_equal(solves[0], _pinit())
    for t in range(1, NTILES):
        assert not np.array_equal(solves[t], _pinit())
        np.testing.assert_array_equal(solves[t], residuals[t - 1])
    assert capsys.readouterr().out.count(" ahead=1 ") == NTILES - 1


def test_diverged_tile_dispatches_the_next_solve_from_pinit(
        spied, monkeypatch, capsys):
    argv, calls = spied
    config_from_args = cli.config_from_args

    def tiny_ratio(args):
        cfg = config_from_args(args)
        cfg.res_ratio = 1e-12  # every solve "diverges"
        return cfg

    monkeypatch.setattr(cli, "config_from_args", tiny_ratio)
    assert cli.main(argv) == 0
    out = capsys.readouterr().out
    assert "tile 0: diverged" in out
    solves = [p for name, p in calls if name == "solve"]
    assert len(solves) == NTILES
    np.testing.assert_array_equal(solves[1], _pinit())
    # the diverged tile's residual is computed with the reset gains too
    np.testing.assert_array_equal(calls[2][1], _pinit())
