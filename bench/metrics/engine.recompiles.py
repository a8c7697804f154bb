"""Compiled variants added on the hot path during the window
(``engine.jit_cache_entries`` delta); the server's warmup promises 0."""


def read(run):
    return run["recompiles"]
