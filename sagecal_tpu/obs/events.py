"""Run manifests and the JSONL event log.

Every app run can emit a structured, append-only event stream: one JSON
object per line, first line a :class:`RunManifest` snapshot (platform,
device kind, precision config, kernel path), then per-tile / per-round
events (phase timings, convergence records, ADMM residual traces, bench
outcomes).  The log is plain JSONL so it greps/joins with standard
tools and round-trips losslessly through :func:`read_events`.

Everything here is host-side and host-callback-free: jitted solver code
returns telemetry as auxiliary pytree outputs (obs/records.py) and the
app feeds them to an :class:`EventLog` after the solve returns.

Enable with ``SAGECAL_TELEMETRY=1``; pick the path with
``SAGECAL_EVENT_LOG=/path/to/run.jsonl`` (default
``./sagecal_events.jsonl``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional

SCHEMA_VERSION = 1

# manifest keys that must be present for a manifest to validate
_REQUIRED_MANIFEST_KEYS = (
    "schema_version", "run_id", "platform", "device_kind", "num_devices",
    "jax_version", "jaxlib_version", "x64_enabled",
)


def writer_identity() -> str:
    """This process's stable writer identity, stamped on every emitted
    record so the offline auditor (obs/ledger.py) can attribute lines
    in a shared O_APPEND file to their writer and detect per-writer
    sequence holes.  ``<worker>@<pid>``: the fleet worker name when
    ``SAGECAL_WORKER_ID`` is set (coordinator-spawned workers), else a
    pid-derived stand-in.  The part before ``@`` is the writer's clock
    domain (one wall clock per process; a respawned worker is a new
    domain instance but shares the worker-name prefix)."""
    wid = os.environ.get("SAGECAL_WORKER_ID", "").strip()
    pid = os.getpid()
    return f"{wid or 'p%d' % pid}@{pid}"


def _jsonable(x):
    """Best-effort conversion of numpy/jax scalars and arrays to plain
    JSON types (events must never fail to serialize)."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    # numpy / jax array-likes (incl. 0-d scalars) — no hard dependency
    # on either package at import time
    item = getattr(x, "item", None)
    tolist = getattr(x, "tolist", None)
    try:
        if tolist is not None and getattr(x, "ndim", 0) > 0:
            return _jsonable(tolist())
        if item is not None:
            return _jsonable(item())
    except Exception:
        pass
    return repr(x)


@dataclasses.dataclass
class RunManifest:
    """What ran, where, and how — the header record of every event log.

    ``collect()`` is tolerant of a broken accelerator plugin: a backend
    query failure is RECORDED (``backend_error`` set, device fields
    "unknown") instead of raised, so the manifest survives exactly the
    failure modes it exists to document (a backend that does not
    initialize)."""

    schema_version: int = SCHEMA_VERSION
    run_id: str = ""
    created_unix: float = 0.0
    argv: List[str] = dataclasses.field(default_factory=list)
    pid: int = 0
    platform: str = "unknown"
    device_kind: str = "unknown"
    num_devices: int = 0
    jax_version: str = "unknown"
    jaxlib_version: str = "unknown"
    x64_enabled: bool = False
    kernel_path: str = "xla"  # "xla" | "fused"
    backend_error: Optional[str] = None
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @classmethod
    def collect(cls, kernel_path: str = "xla", run_id: Optional[str] = None,
                **extra) -> "RunManifest":
        m = cls(
            run_id=run_id or uuid.uuid4().hex[:12],
            created_unix=time.time(),
            argv=list(sys.argv),
            pid=os.getpid(),
            kernel_path=kernel_path,
            env={
                k: v for k, v in os.environ.items()
                if k.startswith("SAGECAL_") or k in ("JAX_PLATFORMS",)
            },
            extra={k: _jsonable(v) for k, v in extra.items()},
        )
        try:
            import jax

            m.jax_version = jax.__version__
            try:
                import jaxlib

                m.jaxlib_version = jaxlib.__version__
            except Exception:
                pass
            m.x64_enabled = bool(jax.config.jax_enable_x64)
            devs = jax.devices()
            m.platform = devs[0].platform if devs else "none"
            m.device_kind = devs[0].device_kind if devs else "none"
            m.num_devices = len(devs)
        except Exception as e:  # wedged/failed backend: record, don't raise
            m.backend_error = f"{type(e).__name__}: {e}"
        return m

    def to_dict(self) -> dict:
        return _jsonable(dataclasses.asdict(self))


def validate_manifest(d: dict) -> List[str]:
    """Return a list of problems (empty = valid manifest dict)."""
    problems = []
    for k in _REQUIRED_MANIFEST_KEYS:
        if k not in d:
            problems.append(f"missing key: {k}")
    if d.get("schema_version") not in (None, SCHEMA_VERSION):
        problems.append(
            f"schema_version {d.get('schema_version')} != {SCHEMA_VERSION}"
        )
    if "num_devices" in d and not isinstance(d["num_devices"], int):
        problems.append("num_devices not an int")
    return problems


class EventLog:
    """Append-only JSONL event sink.

    Each :meth:`emit` writes one line ``{"ts": ..., "run_id": ...,
    "type": <type>, ...fields}`` as a SINGLE ``os.write`` on an
    ``O_APPEND`` fd — POSIX appends of one buffer never interleave, so
    concurrent writers (multi-process distributed / multihost runs)
    sharing one file cannot corrupt each other's lines.  There is no
    userspace buffering, so a crashed run keeps every event up to the
    crash.  Usable as a context manager."""

    def __init__(self, path: str, run_id: Optional[str] = None,
                 manifest: Optional[RunManifest] = None):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._fd: Optional[int] = os.open(
            path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
        if manifest is not None and not manifest.run_id:
            manifest.run_id = uuid.uuid4().hex[:12]
        self.run_id = run_id or (
            manifest.run_id if manifest is not None else uuid.uuid4().hex[:12]
        )
        self.writer = writer_identity()
        self._seq = 0
        if manifest is not None:
            self.emit("run_manifest", **manifest.to_dict())

    @property
    def closed(self) -> bool:
        return self._fd is None

    def emit(self, type: str, **fields) -> None:
        fd = self._fd
        if fd is None:
            return
        rec = {"ts": time.time(), "run_id": self.run_id, "type": type}
        for k, v in fields.items():
            if k not in rec:
                rec[k] = _jsonable(v)
        # audit stamps go LAST so the byte layout existing consumers
        # key on (ts/run_id/type prefix, then caller fields) is
        # unchanged: writer identity + a per-writer sequence number
        # (hole detection) + a monotonic reading (ordering within a
        # writer survives wall-clock steps)
        if "writer" not in rec:
            rec["writer"] = self.writer
        if "mono" not in rec:
            rec["mono"] = time.monotonic()
        if "seq" not in rec:
            rec["seq"] = self._seq
            self._seq += 1
        os.write(fd, (json.dumps(rec) + "\n").encode("utf-8"))

    def close(self) -> None:
        fd, self._fd = self._fd, None
        if fd is not None:
            os.close(fd)

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_events(path: str) -> List[dict]:
    """Load every event of a JSONL log (skips blank/corrupt lines rather
    than failing — a killed run may leave a truncated last line)."""
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def iter_events(path: str) -> Iterator[dict]:
    for e in read_events(path):
        yield e


def expand_event_paths(path: str) -> List[str]:
    """Resolve an event-log argument to the set of JSONL files it names:
    a directory expands to its ``*.jsonl`` files (plus per-process
    ``*.jsonl.<pid>`` siblings); a file expands to itself plus any
    ``<file>.<pid>`` companions written by
    ``SAGECAL_EVENT_LOG_PER_PROCESS=1`` runs."""
    import glob as _glob

    if os.path.isdir(path):
        out = sorted(_glob.glob(os.path.join(path, "*.jsonl")))
        out += sorted(p for p in _glob.glob(os.path.join(path, "*.jsonl.*"))
                      if p.rsplit(".", 1)[-1].isdigit())
        return out
    out = [path] if os.path.exists(path) else []
    out += sorted(p for p in _glob.glob(path + ".*")
                  if p.rsplit(".", 1)[-1].isdigit())
    return out


def read_events_merged(path: str) -> List[dict]:
    """Read + merge events from every file :func:`expand_event_paths`
    resolves, in stable timestamp order (the ``diag``-side merge for
    per-process suffixed logs)."""
    events: List[dict] = []
    for p in expand_event_paths(path):
        events.extend(read_events(p))
    events.sort(key=lambda e: float(e.get("ts", 0.0)))
    return events


def default_event_log(manifest: Optional[RunManifest] = None,
                      path: Optional[str] = None) -> Optional[EventLog]:
    """The app-side entry: an :class:`EventLog` at ``SAGECAL_EVENT_LOG``
    (or ``./sagecal_events.jsonl``) when telemetry is enabled, else
    None — callers guard every emit with ``if log is not None``.

    ``SAGECAL_EVENT_LOG_PER_PROCESS=1`` suffixes the path with the pid
    (one file per writer; ``diag events`` merges the companions) for
    multihost launchers that cannot share an O_APPEND fd safely, e.g.
    on network filesystems where append atomicity is not guaranteed."""
    from sagecal_tpu.obs.registry import _TRUTHY, telemetry_enabled

    if not telemetry_enabled():
        return None
    path = path or os.environ.get("SAGECAL_EVENT_LOG") or "sagecal_events.jsonl"
    per_proc = os.environ.get(
        "SAGECAL_EVENT_LOG_PER_PROCESS", "").strip().lower() in _TRUTHY
    if per_proc:
        path = f"{path}.{os.getpid()}"
    return EventLog(path, manifest=manifest)
