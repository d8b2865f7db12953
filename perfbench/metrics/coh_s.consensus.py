"""coh_s.consensus: the PhaseTimer's 'coh' phase of the traced tile: the
next tile's bands stacked, put on the chips and their band program
dispatched.  A program without the phase gives no value."""


def read(run):
    return run.window["traced"]["phases"].get("coh")
