"""Plan compile wall time per batch (``QueryStats.plan_s``, the total of
the ``query.plan`` spans around ``tdr_query.compile_queries``), over the
window.  Nothing where the stats do not hold the total."""


def read(run):
    st = run["stats"]
    if "query.plan_s" not in st or not st["batches"]:
        return None
    return 1e3 * st["query.plan_s"] / st["batches"]
