"""jit_s.fullbatch: JAX compile work during the traced tile, as the
program prints it on the tile line (``jit_s``): jaxpr tracing, lowering
and backend compilation, persistent-cache loads included
(``obs/perf.jit_clock``).  A program that prints no such number gives
no value."""


def read(run):
    return run.window["traced"]["phases"].get("jit_s")
