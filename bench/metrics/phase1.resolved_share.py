"""Share of the window's jobs (DNF terms) the filter cascade settled
without the exact search: ``(filter_false + filter_true) / n_jobs``."""


def read(run):
    st = run["stats"]
    if not st["query.n_jobs"]:
        return None
    settled = st["query.filter_false"] + st["query.filter_true"]
    return 100.0 * settled / st["query.n_jobs"]
