"""device_wait_s.consensus: the PhaseTimer's 'solve-wait' of the traced
tile: how long the host waited for the mesh ADMM after its own
overlapped work (the next tile's read and band program, dispatched
before it).  A program without the phase gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("solve-wait")
