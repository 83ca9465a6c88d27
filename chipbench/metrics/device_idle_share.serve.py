"""Per cent of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the window."""
from chipbench import stats


def read(rec):
    return stats.idle_share(rec)
