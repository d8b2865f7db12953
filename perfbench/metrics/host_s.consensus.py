"""host_s.consensus: the traced tile's wall period (between its tile
line and the one before) less the PhaseTimer's solve-wait: what the
host pipeline adds to the devices' work.  A program without the phase
gives no value."""


def read(run):
    r = run.window["traced"]
    wait = r["phases"].get("solve-wait")
    return None if wait is None else r["period_s"] - wait
