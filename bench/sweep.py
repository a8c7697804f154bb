#!/usr/bin/env python3
"""Find a cell's knee: one warm server, a window at each offered rate.

    python3 bench/sweep.py --workload pa8k.bool-true --seed 3 \
        --seconds 51 --rates 1,2,3,4

Prints one JSON line per rate (p50/p95 from due time, p50 by quarter of
the window, the latency's growth over the window, completed rate, shed
and late requests, the sender's lateness, jobs per batch, and the cell's
per-layer metrics that need no trace) and stops after the first rate it
does not sustain.  A rate is sustained when every request is answered,
the backlog left at the window's end drains within half a window, and
the queue does not grow: the least-squares slope of latency over due
time is at most 0.1 (a second more wait for every ten seconds of
window).  Batches take seconds here, so the slope is read over every
request of the window rather than from a few quarters.  The knee is the
highest rate sustained; a cell offers four fifths of it (``--write-cell``
records both in ``bench/cells/<cell>.json``).  Every window gets queries
of its own, so the result cache takes none of the work.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cell as cell_mod, openloop, spec  # noqa: E402

GROWTH = 0.1   # most latency slope (s per s of due time) still sustained


def window_row(srv, cell, q, due, seconds: float, wait_s: float) -> dict:
    """One window's record, summarised."""
    win = srv.window(q, due, seconds, wait_s=wait_s)
    rec = win["rec"]
    lat = rec.latency_s()
    ok = rec.status == openloop.OK
    quarter = np.minimum((4 * due / seconds).astype(int), 3)
    by_q = [float(np.median(lat[quarter == k])) * 1e3
            if (quarter == k).any() else float("nan") for k in range(4)]
    drain = rec.t_end - seconds
    slope = float(np.polyfit(due, lat, 1)[0]) if due.shape[0] > 1 else 0.0
    return {
        "requests": int(due.shape[0]), "qps": rec.answered_per_s(),
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "p50_by_quarter_ms": by_q, "latency_slope": slope,
        "shed": int((rec.status == openloop.SHED).sum()),
        "not_ok": int((~ok).sum()),
        "wrong": int((ok & (rec.answer != q.truth)).sum()),
        "drain_s": drain,
        "lateness_ms": rec.lateness_ms(),
        "batch_jobs": cell_mod.batch_jobs(
            rec, q, srv.g.n_labels, srv.server.config.max_jobs),
        "per_layer": cell_mod.layer_metrics(cell, {
            "stats": win["stats"], "recompiles": win["recompiles"],
            "latency_s": lat, "trace": None}),
        "sustained": bool(ok.all() and drain <= seconds / 2
                          and slope <= GROWTH)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the arrivals of each rate's window")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s, ascending")
    ap.add_argument("--wait", type=float, default=cell_mod.WAIT_S,
                    help="seconds past a window its answers are waited for")
    ap.add_argument("--keep-going", action="store_true",
                    help="run every rate, past the first not sustained")
    ap.add_argument("--write-cell", action="store_true",
                    help="record the knee and 4/5 of it as the cell's rate "
                         "in bench/cells/<workload>.json")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    why = cell_mod.require_chip(cell.chips)
    if why:
        print(f"sweep: {why}", file=sys.stderr)
        return 3
    cell_mod.enable_compile_cache()

    rates = [float(r) for r in args.rates.split(",")]
    dues = [openloop.arrivals(cell_mod.rng(args.seed + i, "arrivals"), r,
                              args.seconds) for i, r in enumerate(rates)]
    srv = cell_mod.Serving(cell, args.seed, sum(d.shape[0] for d in dues))
    print(json.dumps({"setup": srv.setup, "backend": srv.backend,
                      "traffic": srv.traffic_info}), flush=True)
    off = 0
    rows = []
    for rate, due in zip(rates, dues):
        q = srv.queries.take(slice(off, off + due.shape[0]))
        off += due.shape[0]
        rows.append({"rate_per_s": rate, **window_row(
            srv, cell, q, due, args.seconds, args.wait)})
        print(json.dumps(rows[-1]), flush=True)
        if not rows[-1]["sustained"] and not args.keep_going:
            break   # past the knee: the backlog grows or outlasts the window
    srv.close()
    held = [r["rate_per_s"] for r in rows if r["sustained"]]
    if args.write_cell and held:
        path = spec.BENCH / "cells" / f"{cell.name}.json"
        rec_ = spec.load_json(path)
        rec_["knee_per_s"] = max(held)
        rec_["rate_per_s"] = round(0.8 * max(held), 2)
        rec_["sweep"] = (f"seed {args.seed}, {args.seconds:g} s windows at "
                         f"{args.rates} req/s; sustained up to {max(held)}")
        with open(path, "w") as f:
            f.write(json.dumps(rec_, indent=2) + "\n")
        print(json.dumps({"cell_file": rec_}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
