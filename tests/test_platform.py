"""Device selection and the compile cache: no quiet CPU fallback, and a
cache path that depends on nothing but the environment and the checkout.
"""

import os
import subprocess
import sys
import types

import pytest

import jax

from sagecal_tpu.apps.config import RunConfig, ServeConfig, StreamConfig
from sagecal_tpu.obs import perf
from sagecal_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cpu_not_chosen(monkeypatch):
    """As if JAX_PLATFORMS were unset: the tests themselves run with it
    set to cpu, which is the one case where accelerator() may say None."""
    monkeypatch.setattr(platform, "cpu_chosen", lambda: False)


def _no_backend(*a, **k):
    raise RuntimeError("Unable to initialize backend 'tpu'")


def _gpu_only(*a, **k):
    return [types.SimpleNamespace(platform="gpu", device_kind="H100")]


def test_cpu_chosen_under_the_test_environment():
    assert platform.cpu_chosen()
    assert platform.accelerator() is None


@pytest.mark.parametrize("devices", [_no_backend, _gpu_only],
                         ids=["no-backend", "no-tpu"])
def test_accelerator_raises_without_a_tpu(cpu_not_chosen, monkeypatch,
                                          devices):
    monkeypatch.setattr(jax, "devices", devices)
    with pytest.raises(RuntimeError):
        platform.accelerator()


def test_accelerator_returns_the_tpu(cpu_not_chosen, monkeypatch):
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [tpu])
    assert platform.accelerator() is tpu


@pytest.mark.parametrize("app", ["fullbatch", "serve", "stream"])
def test_apps_refuse_to_start_a_cpu_run(cpu_not_chosen, monkeypatch,
                                        tmp_path, app):
    """With no TPU the apps raise before reading any input or writing
    any output, instead of solving on the host."""
    monkeypatch.setattr(jax, "devices", _gpu_only)
    monkeypatch.chdir(tmp_path)
    missing = str(tmp_path / "missing.h5")
    if app == "fullbatch":
        from sagecal_tpu.apps.fullbatch import run_fullbatch as run

        cfg = RunConfig(dataset=missing, out_solutions="sol.txt")
    elif app == "serve":
        from sagecal_tpu.apps.serve import run_serve as run

        cfg = ServeConfig(requests=missing, out_dir=str(tmp_path / "out"))
    else:
        from sagecal_tpu.apps.stream import run_stream as run

        cfg = StreamConfig(dataset=missing, out_dir=str(tmp_path / "out"))
    with pytest.raises(RuntimeError, match="no TPU"):
        run(cfg, log=lambda *a: None)
    assert sorted(os.listdir(tmp_path)) == []


def test_fleet_refuses_more_tpu_workers_than_one(monkeypatch):
    from sagecal_tpu.fleet.coordinator import check_worker_count

    cfg = types.SimpleNamespace(workers=2, max_workers=0)
    check_worker_count(cfg)  # JAX_PLATFORMS=cpu: workers share the host
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="one host runs one worker"):
        check_worker_count(cfg)
    check_worker_count(types.SimpleNamespace(workers=1, max_workers=1))


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert perf.compile_cache_dir() == str(tmp_path)


def test_compile_cache_default_is_the_checkout_root(tmp_path):
    """Two working directories, one checkout: one cache path, and the
    helper sets it as JAX's cache directory."""
    code = ("from sagecal_tpu.obs.perf import "
            "enable_persistent_compilation_cache as e; import jax; "
            "p = e(); assert jax.config.jax_compilation_cache_dir == p; "
            "print(p)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    seen = set()
    for cwd in (tmp_path, REPO):
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             check=True)
        seen.add(out.stdout.strip())
    assert seen == {os.path.join(REPO, ".jax_cache")}
