"""Reduction of a profiler trace to the device's busy time and the kernels'.

A trace is read into plain ``Event`` rows (plane, line, name, start, end in
ns) and reduced here, by code kept with the benchmark, so every PR computes
the same numbers the same way:

* busy — the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each TPU plane), clipped to the window the
  benchmark marked with its ``bench.window`` span; averaged over devices;
* per kernel — calls and summed device time of the ops a kernel's
  matcher picks out by name;
* the breakdown — the device ops that took most time of their own (less
  the ops nested in them), and the idle time between device ops grouped
  by what the host was doing then (the innermost host span that covers
  the middle of each gap).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
LABELLED_GAPS = 2000   # longest idle gaps labelled by host activity


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load_xplane(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns)))
    return out


def load_events(path: str) -> list[Event]:
    """Events kept as JSON rows ``[plane, line, name, start_ns, end_ns]``
    (the recorded trace the tests reduce)."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def window(events: list[Event]) -> tuple[float, float]:
    """The benchmark's own ``bench.window`` span on the host."""
    spans = [e for e in events if e.name == WINDOW_SPAN
             and not e.plane.startswith(DEVICE_PLANE)]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: list[Event]) -> dict[str, list[Event]]:
    """Device op events per TPU plane."""
    out = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PLANE) and e.line == OPS_LINE:
            out[e.plane].append(e)
    return dict(out)


def op_name(name: str) -> str:
    """An op event's HLO instruction name: the trace gives the whole
    instruction text (``%lane_matmul.12 = s32[...] custom-call(...)``)."""
    return name.split(" = ", 1)[0].strip()


def self_times(evs: list[Event], lo: float, hi: float) -> list[float]:
    """Each op's own time in ``[lo, hi]``: its span less the ops nested
    in it (a while loop holds its body's ops on the same line)."""
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i].start_ns, -evs[i].end_ns))
    own = [0.0] * len(evs)
    stack: list[int] = []
    for i in order:
        e = evs[i]
        while stack and (evs[stack[-1]].end_ns <= e.start_ns
                         or evs[stack[-1]].end_ns < e.end_ns):
            stack.pop()
        span = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
        own[i] += span
        if stack:
            own[stack[-1]] -= span
        stack.append(i)
    return own


def merged(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Union of ``[start, end]`` rows clipped to ``[lo, hi]``, as disjoint
    sorted rows."""
    if intervals.size == 0:
        return np.zeros((0, 2))
    iv = np.clip(intervals, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if iv.size == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    run_end = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > run_end[:-1]]
    starts = iv[new, 0]
    ends = np.r_[run_end[np.flatnonzero(new)[1:] - 1], run_end[-1]]
    return np.stack([starts, ends], axis=1)


def _innermost(host: list[Event], t: np.ndarray) -> list[str]:
    """Name of the shortest host span covering each time in ``t``
    (``"no host span"`` where none does)."""
    best = [("no host span", np.inf)] * t.shape[0]
    by_line = collections.defaultdict(list)
    for e in host:
        by_line[(e.plane, e.line)].append(e)
    for (_, line), evs in by_line.items():
        s = np.array([e.start_ns for e in evs])
        order = np.argsort(s, kind="stable")
        s = s[order]
        en = np.array([evs[i].end_ns for i in order])
        names = [evs[i].name for i in order]
        hi = np.searchsorted(s, t, side="right")
        for k in range(t.shape[0]):
            for j in range(hi[k] - 1, max(hi[k] - 64, 0) - 1, -1):
                dur = en[j] - s[j]
                if en[j] >= t[k] and dur < best[k][1]:
                    best[k] = (f"{line}: {names[j]}", dur)
    return [b[0] for b in best]


def summarize(events: list[Event], kernels: dict | None = None,
              top: int = 10) -> dict:
    """Busy and window seconds, idle share, per-kernel time and the
    breakdown of one traced window.  ``kernels`` maps a kernel's name to
    a predicate on device op names."""
    lo, hi = window(events)
    per_dev = device_ops(events)
    if not per_dev:
        raise ValueError("trace holds no device op events")
    busy, gaps = [], []
    op_time = collections.Counter()
    kern = {k: [0, 0.0] for k in (kernels or {})}
    for evs in per_dev.values():
        iv = np.array([(e.start_ns, e.end_ns) for e in evs])
        m = merged(iv, lo, hi)
        busy.append(float((m[:, 1] - m[:, 0]).sum()))
        edges = np.r_[lo, m.ravel(), hi].reshape(-1, 2)
        gaps.append(edges[edges[:, 1] > edges[:, 0]])
        for e, own in zip(evs, self_times(evs, lo, hi)):
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            name = op_name(e.name)
            op_time[name] += own * 1e-9
            for k, match in (kernels or {}).items():
                if match(name):
                    kern[k][0] += 1
                    kern[k][1] += (min(e.end_ns, hi)
                                   - max(e.start_ns, lo)) * 1e-9
    n_dev = len(per_dev)
    host = [e for e in events if not e.plane.startswith(DEVICE_PLANE)
            and e.end_ns > lo and e.start_ns < hi and e.name != WINDOW_SPAN]
    g = np.concatenate(gaps)
    g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")]
    idle = collections.Counter()
    if g.shape[0] > LABELLED_GAPS:
        rest = g[LABELLED_GAPS:]
        idle[f"gaps under {(rest[0, 1] - rest[0, 0]) * 1e-3:.0f} us"] = (
            float((rest[:, 1] - rest[:, 0]).sum()) * 1e-9 / n_dev)
        g = g[:LABELLED_GAPS]
    if g.size:
        labels = _innermost(host, (g[:, 0] + g[:, 1]) / 2)
        for lab, (a, b) in zip(labels, g):
            idle[lab] += (b - a) * 1e-9 / n_dev
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) * 1e-9 / n_dev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n_dev,
        "kernels": {k: {"calls": c, "seconds": s / n_dev}
                    for k, (c, s) in kern.items()},
        "breakdown": {
            "device_ops": [[n, s / n_dev]
                           for n, s in op_time.most_common(top)],
            "idle_gaps": [[n, s] for n, s in idle.most_common(top)],
        },
    }
