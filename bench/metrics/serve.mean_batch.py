"""Requests answered per scheduler batch over the window (``ServeStats``)."""


def read(run):
    st = run["stats"]
    return st["served"] / st["batches"] if st["batches"] else None
