"""Test configuration: hermetic 8-virtual-device CPU JAX.

Multi-device tests use JAX's host-platform device emulation in place of
the reference's copy-the-MS-N-times MPI recipe
(/root/reference/test/Calibration/README.md steps 1-4).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# 8 virtual devices time-share this host's core(s): shards reach
# collectives far apart in wall-clock, and XLA CPU's rendezvous would
# abort the process after ~40 s (observed with the robust-RTR ADMM
# x-step).  Raise the limits for the whole suite.
from sagecal_tpu.utils.platform import with_cpu_collective_flags  # noqa: E402

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = with_cpu_collective_flags(flags)
# The apps point JAX's persistent compilation cache at the checkout; the
# suite, and every app process a test starts, leaves it off.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

# The reference CPU path is double precision throughout (SURVEY.md hard
# part (c)); tests validate the f64 semantics on CPU while f32/bf16 is
# the TPU production dtype.
jax.config.update("jax_enable_x64", True)

import faulthandler  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end test")


# Per-test hang watchdog.  The XLA collective limits above are an
# escape hatch of LAST resort (7200 s); without a per-test bound a hung
# collective takes two hours to surface.  faulthandler's timer fires
# even while the main thread is blocked inside native XLA code (where a
# SIGALRM-based timeout would never run Python): it dumps every
# thread's traceback and hard-exits, turning a silent hang into a
# diagnosis.  The dump goes to a real file on disk — NOT stderr, which
# pytest's fd-level capture redirects into an unlinked temp file that
# the hard exit would discard.  Budget: fast tests get 600 s each (the
# whole fast suite is budgeted <10 min, so any single test near 600 s
# is already broken); slow-marked deep runs get 3600 s.
_WATCHDOG_LOG = os.path.join(os.path.dirname(__file__), os.pardir,
                             ".pytest_watchdog.log")
_watchdog_file = open(_WATCHDOG_LOG, "w")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    limit = 3600.0 if item.get_closest_marker("slow") else 600.0
    _watchdog_file.seek(0)
    _watchdog_file.truncate()
    _watchdog_file.write(
        f"watchdog armed for {item.nodeid} (limit {limit:.0f} s); if a "
        "traceback follows, the test hung and the run was killed\n")
    _watchdog_file.flush()
    faulthandler.dump_traceback_later(limit, exit=True, file=_watchdog_file)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
