"""residual_s.consensus: the PhaseTimer's 'residual' phase of the traced
tile: the per-channel residual program dispatched and its result
fetched.  A program without the phase gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("residual")
