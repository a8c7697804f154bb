"""Share of the traced window in which no op ran on the device while the
server's scheduler thread was in a ``serve.batch`` or ``serve.coalesce``
span: the idle time the server's host work causes
(``bench.spans.idle_under``; averaged over chips as
``device.idle_share``).  Nothing where the trace holds no such span."""


def read(run):
    tr = run["trace"]
    share = None if tr is None else tr.get("host_bound_idle_share")
    return None if share is None else 100.0 * share
