#!/usr/bin/env python3
"""Smoke run of the TDR query server on a TPU, checked against the oracles.

    python3 chip_smoke.py               # legs A and B on one chip
    python3 chip_smoke.py --four-chips  # sharded build + query on 4 chips

Each leg drives the served path a user calls — ``tdr_build.build_index`` →
``serve.QueryServer`` → ``warmup`` → ``submit(kind=...)`` — on a graph made
from ``--seed`` by the repo's own generators, and checks every answer
against the ``dfs_baseline`` oracles:

* leg A, the Pallas path: preferential attachment, V=8192, out-degree 4,
  8 labels, default ``TDRConfig``, ``backend="pallas"``; every query kind,
  then one live update and bool queries on the updated graph;
* leg B, a deployment-sized index on the segment backend: Erdős–Rényi,
  V=2^18, out-degree 4, 16 labels (the preferential-attachment generator
  is a per-vertex Python loop, O(V^2) at this size).

``--four-chips`` runs only the vertex-sharded build and query over a
4-device mesh on leg B's graph, against the single-device build and the
oracle.

Every leg prints one JSON line of smoke timings and counts (not metrics:
one cold run, host clock).  The last line is
``{"ok": true, "device": {...}}`` only if every check passed.  Off the TPU
the script exits non-zero before doing any work.  Engine fallbacks
(``"engine: ..."`` warnings) are errors for the whole run.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# query mix per leg: kind -> count (witness/count run one query per call)
LEG_A = dict(name="A", graph="pa", n_vertices=8192, n_labels=8,
             backend="pallas",
             mix={"bool": 64, "dist": 16, "witness": 8, "count": 8,
                  "rpq": 16},
             update=(16, 4), after_update=32,
             kernels=("bitset_matmul", "block_sparse_matmul",
                      "lane_matmul_edges", "way_filter"))
LEG_B = dict(name="B", graph="er", n_vertices=1 << 18, n_labels=16,
             backend="segment",
             mix={"bool": 32, "dist": 8, "rpq": 8, "witness": 4,
                  "count": 4},
             update=None, after_update=0, kernels=())
AVG_DEGREE = 4.0
COUNT_HOPS = 4
# job-bucket budget of the smoke's server: keeps warmup to a few buckets
MAX_JOBS = 32


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (listeners registered once per process)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def make_graph(kind: str, n_vertices: int, n_labels: int, seed: int):
    from repro.core import graph as graph_mod

    if kind == "pa":
        return graph_mod.preferential_attachment(n_vertices, AVG_DEGREE,
                                                 n_labels, seed=seed)
    return graph_mod.erdos_renyi(n_vertices, AVG_DEGREE, n_labels,
                                 seed=seed)


def make_queries(g, mix: dict, seed: int) -> list:
    """``(kind, u, v, pattern-or-regex)`` per the leg's mix, from the
    serving module's query pool (AND / OR / NOT mixes) and seeded regexes:
    a union-star that lowers onto the DNF planner and ordered regexes that
    run the NFA product executor."""
    import numpy as np

    from repro.core import pattern as pat
    from repro.core import rpq
    from repro.launch import serve

    rng = np.random.default_rng(seed)
    n = max(mix.values()) * 4
    pool = serve.mixed_pool(g, n, seed=seed)
    single = [q for q in pool if len(pat.to_dnf(q[2])) == 1]
    out = []
    for kind, count in mix.items():
        for i in range(count):
            if kind == "count":
                u, v, p = single[i % len(single)]
            elif kind == "rpq":
                u, v = (int(x) for x in rng.integers(g.n_vertices, size=2))
                a, b, c = (int(x) for x in rng.choice(
                    g.n_labels, size=3, replace=False))
                p = rpq.parse([f"(l{a} | l{b})*", f"l{a} . (l{b} | l{c})*",
                               f"(l{a} . l{b})+", f"l{c}* . l{a}"][i % 4])
            else:
                u, v, p = pool[(i * 7 + len(out)) % len(pool)]
            out.append((kind, int(u), int(v), p))
    return out


def oracle_ok(g, kind: str, u: int, v: int, p, got) -> bool:
    """One answer against the ``dfs_baseline`` oracles."""
    from repro.core import dfs_baseline as dfs
    from repro.core import semiring

    if kind == "bool":
        return bool(got) == dfs.answer_pcr(g, u, v, p)
    if kind == "dist":
        return int(got) == dfs.shortest_pcr(g, u, v, p)
    if kind == "witness":
        want = dfs.shortest_pcr(g, u, v, p)
        if want < 0:
            return got is None
        return len(got) == want and dfs.verify_witness(g, u, v, p, got)
    if kind == "count":
        return int(got) == dfs.count_routes(g, u, v, p, hops=COUNT_HOPS,
                                            cap=semiring.COUNT_CAP)
    return bool(got) == dfs.answer_rpq(g, u, v, p)


def serve_and_check(server, g, queries: list) -> tuple[dict, dict]:
    """Submit every query, then check each answer; returns per-kind
    (queries, mismatches) counts."""
    futs = [server.submit(u, v, p, kind=kind, hops=COUNT_HOPS)
            for kind, u, v, p in queries]
    n, bad = {}, {}
    for (kind, u, v, p), fut in zip(queries, futs):
        got = fut.result(timeout=1200)
        n[kind] = n.get(kind, 0) + 1
        bad[kind] = bad.get(kind, 0) + (not oracle_ok(g, kind, u, v, p,
                                                      got))
    return n, bad


def random_update(g, n_add: int, n_del: int, seed: int):
    """``n_add`` absent edges to insert and ``n_del`` present ones to
    delete, drawn from ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    present = edge_set(g)
    added = []
    while len(added) < n_add:
        e = tuple(int(x) for x in (rng.integers(g.n_vertices),
                                   rng.integers(g.n_vertices),
                                   rng.integers(g.n_labels)))
        if e[0] != e[1] and e not in present and e not in added:
            added.append(e)
    pick = rng.choice(g.n_edges, size=n_del, replace=False)
    removed = [(int(g.src[i]), int(g.indices[i]), int(g.labels[i]))
               for i in pick]
    return added, removed


def edge_set(g) -> set:
    return set(zip(g.src.tolist(), g.indices.tolist(), g.labels.tolist()))


def progress(leg: str, what: str, seconds: float) -> None:
    print(f"chip_smoke: leg {leg}: {what} {seconds:.1f} s", file=sys.stderr,
          flush=True)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_leg(spec: dict, seed: int, meter: CompileMeter | None = None,
            n_vertices: int | None = None) -> dict:
    """Build, serve and check one leg; returns its report (``ok`` is the
    verdict).  ``n_vertices`` shrinks the graph (the CPU test)."""
    from repro.core import engine as engine_mod
    from repro.core import tdr_build
    from repro.kernels import ops
    from repro.launch import serve

    v_n = n_vertices or spec["n_vertices"]
    kinv0 = dict(ops.KERNEL_INVOCATIONS)
    comp0 = meter.snapshot() if meter else None
    t = time.perf_counter()
    g = make_graph(spec["graph"], v_n, spec["n_labels"], seed)
    queries = make_queries(g, spec["mix"], seed + 1)
    gen_s = time.perf_counter() - t

    t = time.perf_counter()
    idx = tdr_build.build_index(g, tdr_build.TDRConfig(),
                                backend=spec["backend"])
    idx.h_vtx.block_until_ready()
    build_s = time.perf_counter() - t
    progress(spec["name"], "build", build_s)
    eng = idx.engine(spec["backend"])

    report = {"leg": spec["name"], **device_info(),
              "backend": eng.backend, "interpret": eng.interpret,
              "V": g.n_vertices, "E": g.n_edges}
    with serve.QueryServer(idx, backend=spec["backend"],
                           max_jobs=MAX_JOBS) as server:
        report["index_bytes"] = {
            k: server.memory_stats()[k]
            for k in ("dense_bytes", "compressed_bytes")}
        t = time.perf_counter()
        sample = [(u, v, p) for kind, u, v, p in queries
                  if kind != "rpq"]
        report["jit_variants"] = server.warmup(sample)
        warmup_s = time.perf_counter() - t
        progress(spec["name"], "warmup", warmup_s)

        n0 = engine_mod.jit_cache_entries()
        t = time.perf_counter()
        n, bad = serve_and_check(server, g, queries)
        query_s = time.perf_counter() - t
        progress(spec["name"], "queries and oracle", query_s)
        report["recompiles_in_window"] = engine_mod.jit_cache_entries() - n0

        update_s = 0.0
        if spec["update"]:
            added, removed = random_update(g, *spec["update"], seed + 2)
            t = time.perf_counter()
            server.submit_update(added, removed)
            update_s = time.perf_counter() - t
            # the oracle runs on the leg's own graph with the update applied,
            # which must also be the edge set the server now serves
            g2 = g.apply_updates(added, removed).graph
            want = (edge_set(g) - set(removed)) | set(added)
            served = edge_set(server.index.graph)
            report["graph_after_update_ok"] = edge_set(g2) == want == served
            after = make_queries(g2, {"bool": spec["after_update"]},
                                 seed + 3)
            n2, bad2 = serve_and_check(server, g2, after)
            n["bool_after_update"] = n2["bool"]
            bad["bool_after_update"] = bad2["bool"]
    report["queries"] = n
    report["mismatches"] = bad
    report["peak_bytes_in_use"] = peak_bytes()
    report["kernel_invocations"] = {
        k: ops.KERNEL_INVOCATIONS[k] - kinv0.get(k, 0)
        for k in ("bitset_matmul", "lane_matmul", "lane_matmul_edges",
                  "block_sparse_matmul", "way_filter")}
    report["smoke_timing_s"] = {"graph_and_queries": gen_s,
                                "build": build_s, "warmup": warmup_s,
                                "queries_and_oracle": query_s,
                                "update": update_s}
    if meter:
        report["compile"] = CompileMeter.delta(comp0, meter.snapshot())
    # interpret mode routes closures dense, so the sparse kernel is only
    # required where the kernels really compile
    cold = [] if eng.interpret else [
        k for k in spec["kernels"] if not report["kernel_invocations"][k]]
    report["ok"] = (not any(bad.values()) and not cold
                    and report.get("graph_after_update_ok", True)
                    and report["recompiles_in_window"] == 0
                    and eng.backend == spec["backend"])
    return report


def run_four_chips(seed: int, n_vertices: int | None = None) -> dict:
    """Vertex-sharded build and query over a 4-device mesh vs the
    single-device build, the meshless ``answer_batch`` and the oracle."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import dfs_baseline as dfs
    from repro.core import distributed, tdr_build, tdr_query

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    spec = LEG_B
    v_n = n_vertices or spec["n_vertices"]
    t = time.perf_counter()
    g = make_graph(spec["graph"], v_n, spec["n_labels"], seed)
    queries = [(u, v, p) for _, u, v, p in make_queries(
        g, {"bool": 16}, seed + 1)]
    progress("four-chip", "graph and queries", time.perf_counter() - t)
    cfg = tdr_build.TDRConfig()

    t = time.perf_counter()
    ref = tdr_build.build_index(g, cfg, backend="segment")
    ref.h_vtx.block_until_ready()
    single_s = time.perf_counter() - t
    progress("four-chip", "single-device build", single_s)
    t = time.perf_counter()
    got = tdr_build.build_index(g, cfg, mesh=mesh)
    got.h_vtx.block_until_ready()
    sharded_s = time.perf_counter() - t
    progress("four-chip", "sharded build", sharded_s)
    planes = ("h_vtx", "h_lab", "v_vtx", "v_lab", "n_out", "n_in", "push",
              "pop", "g_count")
    differ = [f for f in planes
              if not np.array_equal(np.asarray(getattr(got, f)),
                                    np.asarray(getattr(ref, f)))]

    t = time.perf_counter()
    # "full": one phase-2 shape per chunk, so few compiles on the mesh
    sharded = distributed.answer_batch(got, queries, mesh=mesh,
                                       backend="segment", exact_mode="full")
    query_s = time.perf_counter() - t
    progress("four-chip", "sharded queries", query_s)
    meshless = tdr_query.answer_batch(ref, queries, backend="segment",
                                      exact_mode="full")
    want = np.array([dfs.answer_pcr(g, u, v, p) for u, v, p in queries])
    mem = [d.memory_stats() or {} for d in devs[:4]]
    return {"leg": "four_chips", **device_info(), "V": g.n_vertices,
            "E": g.n_edges, "planes_differ": differ,
            "queries": len(queries),
            "mismatch_vs_oracle": int((sharded != want).sum()),
            "mismatch_vs_meshless": int((sharded != meshless).sum()),
            "bytes_in_use": [m.get("bytes_in_use") for m in mem],
            "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
            "smoke_timing_s": {"single_build": single_s,
                               "sharded_build": sharded_s,
                               "sharded_query": query_s},
            "ok": (not differ and bool((sharded == want).all())
                   and bool((sharded == meshless).all()))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded build + query on 4 chips")
    ap.add_argument("--deadline", type=float, default=1100.0,
                    help="seconds after which a hung run dumps every "
                         "thread's stack and exits")
    args = ap.parse_args(argv)

    # a hang (device start-up, a collective) ends the process itself, with
    # a stack trace, instead of holding the chip until it is killed
    faulthandler.dump_traceback_later(args.deadline, exit=True,
                                      file=sys.__stderr__)
    try:
        return run(args)
    finally:
        faulthandler.cancel_dump_traceback_later()


def run(args) -> int:
    import jax

    t = time.perf_counter()
    platform = jax.devices()[0].platform
    print(f"chip_smoke: {len(jax.devices())} {platform} device(s) up in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    warnings.filterwarnings("error", message="engine: ")
    meter = CompileMeter()

    legs = ([lambda: run_four_chips(args.seed)] if args.four_chips
            else [lambda: run_leg(LEG_A, args.seed, meter),
                  lambda: run_leg(LEG_B, args.seed, meter)])
    ok = True
    for leg in legs:
        report = leg()
        print(json.dumps(report), flush=True)
        ok = ok and report["ok"]
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
