"""Planner + filter-cascade wall time per batch (``QueryStats.phase1_s``,
which ends in a host read of the verdicts, so it holds the cascade's device
time), over the window."""


def read(run):
    st = run["stats"]
    return 1e3 * st["query.phase1_s"] / st["batches"] if st["batches"] \
        else None
