"""xsteps.consensus: the band x-step solves the traced tile's mesh ADMM
ran, summed over the chips: the program's own count, printed on the
tile line as ``xsteps`` (``parallel/mesh.AdmmResult.xsteps``).  A
program that prints no such count gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("xsteps")
