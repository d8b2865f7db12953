"""coh_s.fullbatch: the PhaseTimer's 'coh' phase of the traced tile:
the host coherency build of the next tile (``solvers/sage.
build_cluster_data``), nested in 'load+coh'.  A program without the
phase gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("coh")
