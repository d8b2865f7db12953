"""Runtime utilities: platform guards, profiling, structured logging."""

from sagecal_tpu.utils.platform import (  # noqa: F401
    accelerator,
    cpu_device,
    ensure_cpu_devices,
)
