"""idle_share.consensus: 1 - (union of device-op intervals) / (traced
tile's wall), in percent, averaged over the chips of the trace."""


def read(run):
    s = run.trace_summary
    if not s or not s["devices"] or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
