"""Device: share of the traced slice in which no operation ran on the
chip, in %."""

from chipbench import readings


def read(run):
    return readings.idle_share(run)
