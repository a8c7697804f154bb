"""Mean time a request waited in the server's queue, from its submit to
the start of its batch's ``serve.batch`` span (``ServeStats.queue_wait_s``
over ``served``), over the window.  Nothing where the stats do not hold
the counter."""


def read(run):
    st = run["stats"]
    if "queue_wait_s" not in st or not st["served"]:
        return None
    return 1e3 * st["queue_wait_s"] / st["served"]
