"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of device-op intervals / window), in percent."""


def read(run):
    r = run.reduction
    return None if r is None else 100.0 * r.idle_share
