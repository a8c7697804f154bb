#!/usr/bin/env python3
"""The server's own spans and counters in one traced window of a cell.

    python3 bench/spans.py --workload pa8k.bool-true --seed 7 --seconds 51 \
        [--keep-batches 2 --out trace_spans.json]

``bench/cell.py --trace 1`` reduces its trace to the harness's numbers and
deletes it.  This runs the same window (``cell.Serving``) but keeps the
events, to read what the program's spans (``repro.utils.spans``) show:

* the cell's per-layer metrics, and those of ``bench/metrics`` that read
  the spans and counters and that ``bench/cell.py`` does not feed yet
  (``METRICS``);
* the device's idle seconds split by what the scheduler thread was in:
  a batch or the coalescing of one (host-bound), waiting for work, or
  neither; and the idle seconds under each program span;
* the window's latencies and rate, as ``bench/cell.py --trace 0`` takes
  them, for the cost of tracing;
* with ``--keep-batches N``, the events from the window's start to the
  end of its N-th ``serve.batch`` as JSON rows (``trace.load_events``),
  with the window span cut to match.

Prints one JSON line.  Like ``bench/cell.py`` it exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cell as cell_mod, openloop, spec  # noqa: E402
from bench import trace  # noqa: E402

#: spans the server opens on its scheduler thread (``serve.*``) and in
#: ``tdr_query`` (``query.*``)
SPANS = ("serve.wait_for_work", "serve.coalesce", "serve.batch",
         "query.plan", "query.phase1", "query.phase2",
         "query.phase2.dispatch", "query.phase2.collect", "serve.fanout")
HOST_BOUND = ("serve.batch", "serve.coalesce")   # the server has work
WAITING = ("serve.wait_for_work",)               # the server has none
#: readers in ``bench/metrics`` of the spans and counters below
METRICS = ("serve.queue_wait_ms", "query.plan_ms_per_batch",
           "phase2.rounds_per_chunk", "device.host_bound_idle_share")
#: stat-delta keys those readers take (``ServeStats`` and, under
#: ``query.``, ``QueryStats`` attributes)
COUNTERS = ("queue_wait_s", "query.plan_s", "query.exact_chunks",
            "query.exact_rounds")


def counters(server) -> dict:
    """``COUNTERS`` as the server holds them; a program without one leaves
    it out (its reader then reads nothing)."""
    out = {}
    for key in COUNTERS:
        obj, attr = server.stats, key
        if key.startswith("query."):
            obj, attr = server.stats.query_stats, key[len("query."):]
        if hasattr(obj, attr):
            out[key] = getattr(obj, attr)
    return out


def _covered_before(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted rows ``iv`` that lies before each time
    in ``t``."""
    if iv.shape[0] == 0:
        return np.zeros(t.shape)
    cum = np.r_[0.0, np.cumsum(iv[:, 1] - iv[:, 0])]
    k = np.searchsorted(iv[:, 0], t, side="right")   # rows begun by t
    last = np.maximum(k - 1, 0)
    return np.where(k > 0, cum[last] + np.minimum(t, iv[last, 1])
                    - iv[last, 0], 0.0)


def idle_under(events: list[trace.Event], names) -> float | None:
    """Share of the window in which no op runs on a device while a host
    span named in ``names`` is open, averaged over devices as
    ``trace.summarize``'s ``idle_share`` is; None where no such span is
    open in the window (a program without the spans)."""
    lo, hi = trace.window(events)
    names = set(names)
    iv = np.array([(e.start_ns, e.end_ns) for e in events
                   if e.name in names
                   and not e.plane.startswith(trace.DEVICE_PLANE)])
    spans = trace.merged(iv.reshape(-1, 2), lo, hi)
    if spans.shape[0] == 0:
        return None
    per_dev = trace.device_ops(events)
    if not per_dev:
        raise ValueError("trace holds no device op events")
    open_ns = float((spans[:, 1] - spans[:, 0]).sum())
    idle_ns = 0.0
    for evs in per_dev.values():
        busy = trace.merged(np.array([(e.start_ns, e.end_ns) for e in evs]),
                            lo, hi)
        overlap = (_covered_before(spans, busy[:, 1])
                   - _covered_before(spans, busy[:, 0])).sum()
        idle_ns += open_ns - float(overlap)
    return idle_ns / len(per_dev) / (hi - lo)


def idle_split(events: list[trace.Event], summary: dict) -> dict:
    """The window's device idle seconds: host-bound (under ``serve.batch``
    or ``serve.coalesce``), waiting for work, the rest; and under each
    program span (nested spans overlap, so those do not add up)."""
    win = summary["window_s"]

    def seconds(names):
        share = idle_under(events, names)
        return None if share is None else share * win

    split = {"idle_s": summary["idle_share"] * win,
             "host_bound_s": seconds(HOST_BOUND)}
    if split["host_bound_s"] is not None:
        # a server that never waited in the window opened no wait span
        split["waiting_s"] = seconds(WAITING) or 0.0
        split["rest_s"] = (split["idle_s"] - split["host_bound_s"]
                           - split["waiting_s"])
    split["under_span_s"] = {n: seconds([n]) for n in SPANS}
    return split


def cut(events: list[trace.Event], batches: int) -> list[trace.Event]:
    """The events from the window's start to the end of its ``batches``-th
    ``serve.batch`` span, the window span cut to that end."""
    lo, hi = trace.window(events)
    ends = sorted(e.end_ns for e in events if e.name == "serve.batch"
                  and lo <= e.start_ns < hi)
    if len(ends) < batches:
        raise ValueError(f"the window holds {len(ends)} batches, "
                         f"not {batches}")
    end = ends[batches - 1]
    out = [e for e in events if e.start_ns < end and e.end_ns > lo
           and e.name != trace.WINDOW_SPAN]
    win = next(e for e in events if e.name == trace.WINDOW_SPAN)
    return [trace.Event(win.plane, win.line, win.name, lo, end)] + out


def run(cell: spec.Cell, seed: int, seconds: float, keep_batches: int = 0,
        device_kind: str | None = None,
        wait_s: float = cell_mod.WAIT_S) -> dict:
    """One traced window of ``cell``: the line ``main`` prints, and under
    ``"events"`` the kept cut (``keep_batches`` > 0).  ``device_kind`` and
    ``wait_s`` stand in for the chip's and the minute's wait off the
    chip, as in ``cell.run``."""
    import jax

    due = cell_mod.schedule(float(cell.rate["rate_per_s"]), seconds)
    srv = cell_mod.Serving(cell, seed, due.shape[0])
    before = counters(srv.server)
    win = srv.window(srv.queries, due, seconds, trace=True, wait_s=wait_s)
    after = counters(srv.server)
    lanes = srv.server.config.exact_chunk
    srv.close()
    events = trace.load_xplane(win["trace_dir"])
    shutil.rmtree(win["trace_dir"])

    summary = trace.summarize(events, kernels=cell_mod.KERNELS)
    summary["host_bound_idle_share"] = idle_under(events, HOST_BOUND)
    rec, q, g = win["rec"], srv.queries, srv.g
    lat = rec.latency_s()
    # the context ``cell.run`` hands the readers, with ``COUNTERS`` added
    ctx = {"stats": {**win["stats"],
                     **{k: after[k] - before[k] for k in after}},
           "recompiles": win["recompiles"], "latency_s": lat,
           "trace": summary, "n_vertices": g.n_vertices,
           "n_edges": g.n_edges, "lanes": lanes,
           "classes": cell_mod.label_classes(srv.warm, g.n_labels) + 1,
           "device_kind": device_kind or jax.devices()[0].device_kind}
    metrics = {k: m["value"]
               for k, m in cell_mod.layer_metrics(cell, ctx).items()}
    for name in METRICS:
        value = spec.metric_reader(name)(ctx)
        if value is not None:
            metrics[name] = value
    answered = rec.status == openloop.OK
    out = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "metrics": metrics,
        "idle": idle_split(events, summary),
        "window_s": summary["window_s"], "busy_s": summary["busy_s"],
        "idle_share": summary["idle_share"],
        "idle_gaps": summary["breakdown"]["idle_gaps"],
        "traced_e2e": {"p50_ms": cell_mod.percentile_ms(lat, 50),
                       "p95_ms": cell_mod.percentile_ms(lat, 95),
                       "qps": rec.answered_per_s()},
        "wrong_answers": int((answered & (rec.answer != q.truth)).sum()),
        "unanswered": int((~answered).sum()),
        "stats_delta": ctx["stats"],
    }
    if keep_batches:
        out["events"] = cut(events, keep_batches)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-batches", type=int, default=0)
    ap.add_argument("--out", help="where the kept events go (JSON rows)")
    args = ap.parse_args(argv)
    if args.keep_batches and not args.out:
        ap.error("--keep-batches needs --out")

    cell = spec.cell(args.workload)
    why = cell_mod.require_chip(cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 3
    cell_mod.enable_compile_cache()
    out = run(cell, args.seed, args.seconds, args.keep_batches)
    events = out.pop("events", None)
    if events is not None:
        with open(args.out, "w") as f:
            json.dump([[e.plane, e.line, e.name, e.start_ns, e.end_ns]
                       for e in events], f)
        out["kept_events"] = len(events)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
