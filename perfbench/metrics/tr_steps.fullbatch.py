"""tr_steps.fullbatch: the trust-region steps of the traced tile's
solve, summed over EM passes, clusters, robust rounds and chunks: the
program's own count, printed on the tile line as ``tr_steps``
(``solvers/sage.SageResult.steps``).  A program that prints no such
count gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("tr_steps")
