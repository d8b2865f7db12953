"""rsd_steps.fullbatch: the steepest-descent (RSD) warm-up iterations
the traced tile's solve ran ahead of its trust-region loops, summed over
EM passes, clusters, robust rounds and chunks: the program's own count,
printed on the tile line as ``rsd_steps``
(``solvers/sage.SageResult.steps``).  A program that prints no such
count gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("rsd_steps")
