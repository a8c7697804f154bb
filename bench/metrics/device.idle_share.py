"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, averaged over chips."""


def read(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
