"""Exact-expansion wall time per batch (``QueryStats.phase2_s``, dispatch
to collected answers), over the window."""


def read(run):
    st = run["stats"]
    return 1e3 * st["query.phase2_s"] / st["batches"] if st["batches"] \
        else None
