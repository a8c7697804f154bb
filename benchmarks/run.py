"""Benchmark harness entry point — one module per paper table/figure,
plus the ``serving`` load-generator suite over ``repro.launch.serve``.

``PYTHONPATH=src python -m benchmarks.run [--scale smoke|small|full]``
prints ``name,us_per_call,derived`` CSV rows (paper-table mapping and the
engine layering live in ARCHITECTURE.md; roofline terms come from
launch/dryrun.py, not from here).

``--backends segment,pallas`` sweeps the packed-word engine backends for
the modules that support it (queries, kernels); ``--json PATH`` addition-
ally writes machine-readable per-row records
``{name, us_per_call, derived, backend, scale}`` — tableIII rows also
carry the executor counters ``rounds``, ``corridor_occ`` (mean |V'|/V of
the corridor-compacted expansion), and the ``phase1_us``/``phase2_us``
wall split — so the perf trajectory is tracked across PRs (see
BENCH_queries.json at the repo root; ``benchmarks.guard`` is the CI
regression gate over those rows).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys

from repro.core import engine as engine_mod
from repro.launch import compile_cache

from . import (common, fleet, index_cost, kernels_bench, lcr_bench,
               queries, recovery, scalability, serving, synthetic_sweeps,
               updates)

MODULES = [
    ("tableIII", queries),
    ("tableIV", index_cost),
    ("tableV", lcr_bench),
    ("fig4-5", synthetic_sweeps),
    ("fig6", scalability),
    ("kernels", kernels_bench),
    ("serving", serving),
    ("fleet", fleet),
    ("updates", updates),
    ("recovery", recovery),
]


def collect(scale: str, only: str = "", backends: list | None = None,
            skip: str = "") -> list:
    """Run the selected modules; returns records (dicts, one per CSV row).

    ``only``/``skip`` are comma-separated lists of substrings matched
    against the module names (skip wins — e.g. the nightly full run
    excludes the multi-process ``fleet`` module, which has its own
    saturation job); ``backends`` sweeps engine backends where
    supported.
    """
    tokens = [t for t in (only or "").split(",") if t]
    skips = [t for t in (skip or "").split(",") if t]
    records = []
    for name, mod in MODULES:
        if tokens and not any(t in name for t in tokens):
            continue
        if any(t in name for t in skips):
            continue
        supports = "backend" in inspect.signature(mod.run).parameters
        sweep = (backends or [None]) if supports else [None]
        for be in sweep:
            label = be or engine_mod.resolve_backend("auto")
            try:
                kw = {"scale": scale}
                if be is not None:
                    kw["backend"] = be
                rows = mod.run(**kw)
            except Exception as e:  # noqa
                rows = [(f"{name}/ERROR", 0, repr(e)[:120])]
            for row in rows:
                rec = {
                    "name": row[0],
                    "us_per_call": row[1],
                    "derived": row[2] if len(row) > 2 else "",
                    "backend": label if supports else "n/a",
                    "scale": scale,
                }
                if len(row) > 3 and isinstance(row[3], dict):
                    # executor counters (rounds, corridor occupancy,
                    # phase-1/phase-2 split) ride along per row
                    rec.update(row[3])
                records.append(rec)
    return records


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="smoke",
                    choices=sorted(common.SCALES))
    ap.add_argument("--only", default="",
                    help="comma-separated substrings of module names")
    ap.add_argument("--skip", default="",
                    help="comma-separated substrings of module names "
                         "to exclude (applied after --only)")
    ap.add_argument("--backends", default="",
                    help="comma-separated engine backends to sweep "
                         "(e.g. segment,pallas); default: engine default")
    ap.add_argument("--json", default="",
                    help="also write per-row JSON records to this path")
    args = ap.parse_args()

    backends = [b for b in args.backends.split(",") if b] or None
    records = collect(args.scale, args.only, backends, skip=args.skip)

    print("name,us_per_call,backend,derived")
    for r in records:
        print(f"{r['name']},{r['us_per_call']},{r['backend']},{r['derived']}",
              flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}")
    failed = [r["name"] for r in records if r["name"].endswith("/ERROR")]
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
