"""collective_s.consensus: self seconds of the collective ops
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute)
in the traced tile, per chip, averaged over the chips
(pb/app_distributed.collective_seconds)."""


def read(run):
    return run.window.get("collective_s")
