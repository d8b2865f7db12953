#!/usr/bin/env python
"""Regenerate (or staleness-check) ``KERNEL_VMEM_TABLE.json``.

The table is the banked output of the symbolic VMEM footprint model
(``sagecal_tpu/analysis/kernelmodel.py``): per-family feasible tiles,
the derived ``FULL_CLUSTER_TILE``, and the per-dtype batched row
bounds that ``solvers/batched.py::batch_rows_bound`` reads at runtime
instead of hardcoded constants.  It is fingerprinted with the sha256
of ``ops/rime_kernel.py`` so ``diag kernelcheck`` (and this tool's
``--check``) can prove the artifact matches the kernels it claims to
describe.

Usage::

    python tools/kernel_vmem_table.py            # rewrite (atomic)
    python tools/kernel_vmem_table.py --check    # exit 1 if stale

Stdlib + the model only — safe in the lint/CI environment (no jax).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from sagecal_tpu.analysis.kernelmodel import (  # noqa: E402
    DEFAULT_BACKEND, load_model)

DEFAULT_OUT = os.path.join(_REPO_ROOT, "KERNEL_VMEM_TABLE.json")


def render(backend: str = DEFAULT_BACKEND) -> str:
    table = load_model().build_table(backend)
    return json.dumps(table, indent=2, sort_keys=True) + "\n"


def write_atomic(path: str, text: str) -> None:
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".kernel_vmem_table.",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            fh.write(text)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Regenerate or check KERNEL_VMEM_TABLE.json")
    parser.add_argument("--out", default=DEFAULT_OUT,
                        help="artifact path (default: repo root)")
    parser.add_argument("--backend", default=DEFAULT_BACKEND,
                        help="ceiling table entry")
    parser.add_argument("--check", action="store_true",
                        help="verify the artifact matches the model; "
                             "exit 1 (and write nothing) if stale")
    args = parser.parse_args(argv)
    text = render(args.backend)
    if args.check:
        try:
            with open(args.out, "r") as fh:
                banked = fh.read()
        except OSError:
            print("STALE: %s missing — run tools/kernel_vmem_table.py"
                  % args.out, file=sys.stderr)
            return 1
        if banked != text:
            print("STALE: %s does not match the kernel model — run "
                  "tools/kernel_vmem_table.py" % args.out,
                  file=sys.stderr)
            return 1
        print("fresh: %s" % args.out)
        return 0
    write_atomic(args.out, text)
    print("wrote %s" % args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
