"""Expansion rounds per phase-2 chunk over the window
(``QueryStats.exact_rounds / exact_chunks``): a chunk runs until its
slowest job meets or its frontier empties.  Nothing where no chunk ran or
the stats do not count chunks."""


def read(run):
    st = run["stats"]
    if not st.get("query.exact_chunks"):
        return None
    return st["query.exact_rounds"] / st["query.exact_chunks"]
