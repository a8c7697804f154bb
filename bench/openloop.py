"""Open-loop load: requests sent on a fixed schedule, timed from when due.

The arrivals are those of ``benchmarks/serving.py``'s Poisson open loop,
with the count fixed: ``round(rate · seconds)`` arrival times drawn
uniformly over the window and sorted, which is a Poisson process
conditioned on its count, so every seed offers the same amount of work.
Unlike that loop, each request's latency runs from the moment it was
*due*, not from when the sender got round to it: a stall of the sender or
the server is charged to every request it delays.  How late the sender ran
is reported beside the latencies.
"""
from __future__ import annotations

import concurrent.futures
import threading
import time

import numpy as np

OK, SHED, ERROR, NEVER = 0, 1, 2, 3


def arrivals(rng: np.random.Generator, rate: float,
             seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson process of
    ``rate`` per second conditioned on ``round(rate · seconds)`` arrivals."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


class Run:
    """Per-request record of one window; times are seconds from its start."""

    def __init__(self, due: np.ndarray, seconds: float, wait_s: float):
        n = due.shape[0]
        self.due = due
        self.seconds = seconds
        self.wait_s = wait_s
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.answer = np.full(n, -1, np.int8)
        self.status = np.full(n, NEVER, np.int8)
        self.t0 = 0.0
        self.t_end = 0.0
        self._frozen = False
        self._lock = threading.Lock()

    def freeze(self) -> "Run":
        """Stop recording: an answer that comes after the run gave up on
        it stays ``NEVER``."""
        with self._lock:
            self._frozen = True
        return self

    def _finish(self, i: int, fut) -> None:
        with self._lock:
            if self._frozen:
                return
            self.done[i] = time.perf_counter() - self.t0
            if fut.cancelled() or fut.exception() is not None:
                self.status[i] = ERROR
            else:
                self.answer[i] = bool(fut.result())
                self.status[i] = OK

    def latency_s(self) -> np.ndarray:
        """Due-to-answer time of every request; a request that failed or
        never came is charged until the run gave up on it."""
        give_up = self.seconds + self.wait_s - self.due
        return np.where(self.status == OK, self.done - self.due, give_up)

    def answered_per_s(self) -> float:
        """Answered requests over the time from the window's start to the
        last answer, or to the window's end if that comes later."""
        ok = self.status == OK
        if not ok.any():
            return 0.0
        return float(ok.sum()) / max(self.seconds, float(self.done[ok].max()))

    def completion_groups(self, gap_s: float = 0.02) -> list[np.ndarray]:
        """Answered requests grouped by when they completed: a group ends
        where the next answer came more than ``gap_s`` later."""
        ok = np.flatnonzero(self.status == OK)
        ok = ok[np.argsort(self.done[ok], kind="stable")]
        if ok.size == 0:
            return []
        cut = np.flatnonzero(np.diff(self.done[ok]) > gap_s) + 1
        return np.split(ok, cut)

    def lateness_ms(self) -> dict:
        late = (self.sent - self.due)[~np.isnan(self.sent)] * 1e3
        if late.size == 0:
            return {}
        return {"p50": float(np.percentile(late, 50)),
                "p99": float(np.percentile(late, 99)),
                "max": float(late.max())}


def drive(submit, due: np.ndarray, seconds: float, shed_exc,
          wait_s: float = 60.0, span=None) -> Run:
    """Send request ``i`` at ``due[i]`` through ``submit(i)`` (which
    returns a future, or raises ``shed_exc`` when admission control sheds
    it), then wait for the answers until ``wait_s`` past the window.
    ``span(name)`` — a context manager — marks the sender's waits and
    sends on the profiler's timeline when a trace is taken."""
    run = Run(due, seconds, wait_s)
    futs = []
    run.t0 = time.perf_counter()
    for i in range(due.shape[0]):
        delay = run.t0 + due[i] - time.perf_counter()
        if delay > 0:
            if span is None:
                time.sleep(delay)
            else:
                with span("bench.wait"):
                    time.sleep(delay)
        run.sent[i] = time.perf_counter() - run.t0
        try:
            if span is None:
                fut = submit(i)
            else:
                with span("bench.submit"):
                    fut = submit(i)
        except shed_exc:
            run.status[i] = SHED
            continue
        fut.add_done_callback(lambda f, i=i: run._finish(i, f))
        futs.append(fut)
    left = run.t0 + seconds + wait_s - time.perf_counter()
    concurrent.futures.wait(futs, timeout=max(left, 0.0))
    run.t_end = time.perf_counter() - run.t0
    return run.freeze()
