"""Device selection shared by the app entry points.

Solves run on the TPU.  The host pipeline (IO, coherency precompute,
residuals) runs under a CPU default device, and each solve crosses to
the chip that :func:`accelerator` returns.  The only other way to run
is on the CPU, chosen explicitly with ``JAX_PLATFORMS=cpu`` as the
tests do; nothing falls back to the CPU on its own.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# N virtual devices time-share the host's cores, so SPMD shards can
# legitimately arrive at a collective minutes apart (e.g. a heavy robust
# RTR x-step on a single-core host); XLA CPU's default collective
# rendezvous terminates the process after ~40 s.  Raise the limits
# whenever the virtual-device mesh is forced.  The installed jaxlib
# knows all three (it aborts the process on an unknown XLA flag).
CPU_COLLECTIVE_FLAGS = (
    "--xla_cpu_collective_timeout_seconds=7200",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=600",
    "--xla_cpu_collective_call_terminate_timeout_seconds=7200",
)


def cpu_chosen() -> bool:
    """True when the CPU platform was chosen explicitly
    (``JAX_PLATFORMS=cpu`` or ``jax_platforms`` set to ``cpu``)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def accelerator():
    """The TPU device that solves run on, or None when the CPU was
    chosen explicitly (:func:`cpu_chosen`).  Any other case raises: a
    missing chip is an error, never a quiet CPU run."""
    if cpu_chosen():
        return None
    import jax

    dev = jax.devices()[0]  # RuntimeError when no backend initializes
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}); set JAX_PLATFORMS=cpu to run on the "
            f"host on purpose")
    return dev


def host_only() -> None:
    """Pin this process's JAX to the CPU.  For a parent whose children
    own the chip (the fleet coordinator, the load harness): a process
    that initializes the TPU holds every chip of its host.  Sets the
    config, not ``JAX_PLATFORMS``, so the children still see the TPU."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def cpu_device():
    """The first host CPU device (the host pipeline's default device)."""
    import jax

    return jax.devices("cpu")[0]


def with_cpu_collective_flags(flags: str) -> str:
    """``flags`` (an XLA_FLAGS string) plus :data:`CPU_COLLECTIVE_FLAGS`
    that it does not already set."""
    for f in CPU_COLLECTIVE_FLAGS:
        if f.split("=")[0] not in flags:
            flags = flags + " " + f
    return flags.strip()


def ensure_cpu_devices(n_devices: int) -> None:
    """Force the CPU platform with >= `n_devices` virtual host devices,
    even if jax was already initialized on another platform or with a
    smaller device count."""
    flags = os.environ.get("XLA_FLAGS", "")
    # rewrite (not just append) any preset count smaller than requested
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m and int(m.group(1)) < n_devices:
        flags = re.sub(
            _COUNT_FLAG + r"=\d+", f"{_COUNT_FLAG}={n_devices}", flags
        )
    elif not m:
        flags = f"{flags} {_COUNT_FLAG}={n_devices}"
    os.environ["XLA_FLAGS"] = with_cpu_collective_flags(flags)

    import jax

    jax.config.update("jax_platforms", "cpu")

    def _count():
        try:
            devs = jax.devices()
        except RuntimeError:
            return 0
        return len(devs) if devs and devs[0].platform == "cpu" else 0

    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        pass  # backend already initialized; cleared + retried below
    if _count() < n_devices:
        import jax.extend.backend as jeb

        jeb.clear_backends()
        jax.config.update("jax_num_cpu_devices", n_devices)
        if _count() < n_devices:
            raise RuntimeError(
                f"could not create {n_devices} virtual CPU devices "
                f"(got {_count()}); XLA_FLAGS={os.environ.get('XLA_FLAGS')}"
            )


def match_vma(tree, ref):
    """Promote every array leaf of ``tree`` to carry (at least) the
    varying-manual-axes of ``ref``.

    Inside ``shard_map(..., check_vma=True)`` (the default the framework
    now runs with), loop carries initialized from constants (zeros,
    identity Jones, False flags) are inferred as replicated while the
    loop bodies produce shard-varying outputs, which the type checker
    rightly rejects.  This helper inserts the
    ``jax.lax.pcast(..., to='varying')`` casts the checker asks for —
    and is a no-op outside shard_map (empty vma) or when already
    varying, so library solvers stay usable in both worlds."""
    import jax
    import jax.tree_util as jtu

    try:
        ref_vma = jax.typeof(ref).vma
    except Exception:
        return tree
    if not ref_vma:
        return tree

    def fix(x):
        try:
            missing = tuple(n for n in ref_vma if n not in jax.typeof(x).vma)
        except Exception:
            return x
        if not missing:
            return x
        return jax.lax.pcast(x, missing, to="varying")

    return jtu.tree_map(fix, tree)
