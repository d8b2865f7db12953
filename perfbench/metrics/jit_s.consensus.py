"""jit_s.consensus: JAX compile work during the traced tile, as the
program prints it on the distributed tile line (``jit_s``): jaxpr
tracing, lowering and backend compilation of the mesh and band
programs, persistent-cache loads included (``obs/perf.jit_clock``).  A
program that prints no such number gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("jit_s")
