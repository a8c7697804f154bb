#!/usr/bin/env python3
"""Run one benchmark cell of the TDR query server on the chip.

    python3 bench/cell.py --workload pa8k.bool-true --seed 7 --seconds 30 \
        --trace 0

A cell is a deployment (``bench/configs``) under a query mix
(``bench/traffic``) at the rate its sweep found (``bench/cells``), all
named by ``BENCHMARK.json``.  One run:

1. exits non-zero unless JAX sees a TPU with the chips the cell needs;
2. keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR``, else in
   ``<checkout>/.jax_cache``;
3. makes the graph, the query set, their order and the arrival times
   from a fixed seed (the same work in every run); ``--seed`` draws the
   names of the labels, on the graph's edges and in the queries alike;
4. builds the index (the program picks its backend), starts a
   ``QueryServer`` with the deployment's settings and warms it up;
5. sends the window's requests open loop (``bench.openloop``) through
   ``QueryServer.submit(kind="bool", block=False)``; with ``--trace 1``
   the window is traced and the per-layer metrics are read;
6. checks every answer against the query's known truth (true or false:
   a mix holds both), and a seeded sample against the plain reference
   (``bench.reference``);
7. prints one JSON line of run facts, then the result as the last line.

``--control`` runs the mix's control instead: the program with one of its
guarantees broken (``bench.controls``), which must come out not correct.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path

T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import graphs, openloop, reference, spec  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench import traffic_gen  # noqa: E402

WAIT_S = 60.0          # how long past the window an answer is waited for
STREAMS = {"graph": 0, "traffic": 1, "arrivals": 2, "sample": 3,
           "order": 4}
# The graph, the query set, their order and the arrival times are drawn
# from this fixed seed; ``--seed`` renames the labels (``relabel``) and
# draws the reference sample.  A phase-2 batch lasts as long as its
# slowest query, so any order drawn from ``--seed`` changed each batch's
# make-up and with it the work; renamed labels leave the work as it is.
CONTENT_SEED = 0


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``); elsewhere
    since this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose; any whole ``seed`` works."""
    return np.random.default_rng(
        [abs(int(seed)), int(seed < 0), STREAMS[stream]])


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (as ``chip_smoke.py`` counts them)."""

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


def require_chip(chips: int) -> str | None:
    """Why this process cannot run the cell here, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU; JAX found {devs[0].platform}"
    if len(devs) < chips:
        return f"needs {chips} chips; JAX found {len(devs)}"
    return None


def enable_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else a fixed checkout path;
    every compile is kept, however short."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class EngineWarnings:
    """Records every ``engine:`` fallback warning, from any thread."""

    def __init__(self):
        self.seen: dict[str, int] = {}
        self._lock = threading.Lock()
        self._show = warnings.showwarning
        warnings.filterwarnings("always", message="engine: ")
        warnings.showwarning = self._record

    def _record(self, message, category, filename, lineno, file=None,
                line=None):
        text = str(message)
        if not text.startswith("engine: "):
            return self._show(message, category, filename, lineno, file,
                              line)
        with self._lock:
            self.seen[text] = self.seen.get(text, 0) + 1


def patterns_for(q: traffic_gen.Queries, n_labels: int) -> dict:
    """The program's pattern object for each (family, a, b) of the mix."""
    from repro.core import pattern as pat

    make = {"AND": pat.all_of, "OR": pat.any_of, "NOT": pat.none_of,
            "LCR": lambda labs: pat.lcr(labs, n_labels)}
    out = {}
    for f, a, b in set(zip(q.fam.tolist(), q.a.tolist(), q.b.tolist())):
        out[(f, a, b)] = make[traffic_gen.FAMILIES[f]]([a, b])
    return out


def stats_snapshot(server) -> dict:
    st = server.stats
    qs = st.query_stats
    out = {k: getattr(st, k) for k in (
        "submitted", "served", "batches", "jobs", "cache_hits",
        "dedup_hits", "rejected", "unpinned_batches", "overflow_batches")}
    out.update({f"query.{k}": getattr(qs, k) for k in (
        "n_queries", "n_jobs", "filter_false", "filter_true", "exact_jobs",
        "plan_lookups", "plan_misses", "phase1_s", "phase2_s")})
    return out


def percentile_ms(lat_s: np.ndarray, q: float) -> float:
    return float(np.percentile(lat_s, q)) * 1e3


def _program():
    """The system under test (imported only once a run starts)."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.core import engine, graph, tdr_build
    from repro.kernels import ops
    from repro.launch import serve
    return engine, graph, tdr_build, ops, serve


def relabel(g: graphs.Csr, rg: graphs.Csr, q: traffic_gen.Queries,
            seed: int):
    """``g``, its reverse ``rg`` and the queries ``q`` with the labels
    renamed by a permutation that ``seed`` draws: the same graph and
    queries up to the names of labels, so the same answers and work."""
    name = rng(seed, "order").permutation(g.n_labels).astype(np.int32)
    a, b = name[q.a], name[q.b]

    def renamed(x: graphs.Csr) -> graphs.Csr:
        return dataclasses.replace(x, labels=name[x.labels])

    return renamed(g), renamed(rg), traffic_gen.Queries(
        q.u, q.v, q.fam, np.minimum(a, b), np.maximum(a, b), q.truth,
        q.info)


class Serving:
    """What set-up builds: the graph, the queries, a warm server."""

    def __init__(self, cell: spec.Cell, seed: int, n_window: int,
                 control: bool = False):
        """``n_window`` queries for the window, in ``seed``'s order."""
        engine, graph, tdr_build, ops, serve = _program()
        from bench import controls

        cfg, mix = cell.config, cell.traffic
        if mix["kind"] != "bool":
            raise ValueError(f"mix kind {mix['kind']!r}: only bool is "
                             "generated")
        self.cell, self.seed = cell, seed
        self.meter = CompileMeter()
        self.engine_warnings = EngineWarnings()
        self.kinv0 = dict(ops.KERNEL_INVOCATIONS)
        if control:
            controls.apply(mix["control"])
        self.setup = {}

        t = time.perf_counter()
        g = graphs.make(cfg, rng(CONTENT_SEED, "graph"))
        rg = g.reverse()
        self.setup["graph_s"] = time.perf_counter() - t

        t = time.perf_counter()
        n_warm = int(mix["warmup_queries"])
        q = traffic_gen.generate(g, rg, mix, n_window + n_warm,
                                 rng(CONTENT_SEED, "traffic"))
        self.traffic_info = q.info
        self.g, self.rg, q = relabel(g, rg, q, seed)
        self.warm = q.take(slice(0, n_warm))
        self.queries = q.take(slice(n_warm, None))
        self.patterns = patterns_for(q, self.g.n_labels)
        self.setup["traffic_s"] = time.perf_counter() - t

        t = time.perf_counter()
        g = self.g
        self.index = tdr_build.build_index(
            graph.Graph(g.n_vertices, g.n_labels, g.indptr.astype(np.int32),
                        g.indices, g.labels),
            tdr_build.TDRConfig(**cfg["tdr"]))
        self.index.h_vtx.block_until_ready()
        self.setup["build_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.server = serve.QueryServer(self.index,
                                        serve.ServeConfig(**cfg["serve"]))
        self.server.start()
        self.backend = self.index.engine(self.server.config.backend).backend
        self.jit_variants = self.server.warmup(self.requests(self.warm))
        self.setup["warmup_s"] = time.perf_counter() - t

    def requests(self, q: traffic_gen.Queries) -> list:
        return [(u, v, self.patterns[(f, a, b)]) for u, v, f, a, b in zip(
            q.u.tolist(), q.v.tolist(), q.fam.tolist(), q.a.tolist(),
            q.b.tolist())]

    def window(self, q: traffic_gen.Queries, due: np.ndarray,
               seconds: float, trace: bool = False,
               wait_s: float = WAIT_S) -> dict:
        """Send ``q[i]`` at ``due[i]``; the record, stat deltas and (traced)
        the reduced trace of the window."""
        import jax

        engine, _, _, ops, serve = _program()
        reqs = self.requests(q)
        kind = self.cell.traffic["kind"]
        server = self.server

        def submit(i):
            u, v, p = reqs[i]
            return server.submit(u, v, p, kind=kind, block=False)

        kinv0 = dict(ops.KERNEL_INVOCATIONS)
        jit0 = engine.jit_cache_entries()
        comp0 = self.meter.snapshot()
        before = stats_snapshot(server)
        out = {"setup_s": process_age_s()}
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                rec = openloop.drive(submit, due, seconds, serve.QueueFull,
                                     wait_s,
                                     span=jax.profiler.TraceAnnotation)
            jax.profiler.stop_trace()
            out["trace_dir"] = trace_dir
        else:
            rec = openloop.drive(submit, due, seconds, serve.QueueFull,
                                 wait_s)
        after = stats_snapshot(server)
        out.update(
            rec=rec, stats={k: after[k] - before[k] for k in after},
            recompiles=engine.jit_cache_entries() - jit0,
            compile={k: self.meter.snapshot()[k] - comp0[k] for k in comp0},
            kernel_invocations={
                k: ops.KERNEL_INVOCATIONS[k] - kinv0.get(k, 0)
                for k in ops.KERNEL_INVOCATIONS})
        return out

    def close(self) -> None:
        self.server.stop()
        del self.server, self.index
        gc.collect()


def reduce_trace(trace_dir: str) -> dict:
    """Reduce the window's trace, then delete it."""
    tr = trace_mod.summarize(trace_mod.load_xplane(trace_dir),
                             kernels=KERNELS)
    for root, dirs, files in os.walk(trace_dir, topdown=False):
        for name in files:
            os.unlink(os.path.join(root, name))
        for name in dirs:
            os.rmdir(os.path.join(root, name))
    os.rmdir(trace_dir)
    return tr


def layer_metrics(cell: spec.Cell, ctx: dict) -> dict:
    """The cell's per-layer metrics that find something to read in
    ``ctx`` (the window's stat deltas, recompiles, latencies and reduced
    trace)."""
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def batch_jobs(rec: openloop.Run, q: traffic_gen.Queries, n_labels: int,
               max_jobs: int) -> dict:
    """Jobs (DNF terms) per scheduler batch, inferred from the answers:
    a batch's futures are resolved together, batches seconds apart.  A
    batch ``at_cap`` had no room for one more request of two terms."""
    terms = np.bincount(reference.terms(q.fam, q.a, q.b, n_labels)[0],
                        minlength=len(q))
    jobs = np.array([terms[g].sum() for g in rec.completion_groups()])
    if jobs.size == 0:
        return {}
    return {"batches": int(jobs.size), "mean": float(jobs.mean()),
            "max": int(jobs.max()),
            "at_cap": int((jobs >= max_jobs - 1).sum())}


def schedule(rate: float, seconds: float) -> np.ndarray:
    """The window's due times, the same in every run."""
    return openloop.arrivals(rng(CONTENT_SEED, "arrivals"), rate, seconds)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        control: bool = False, device_kind: str | None = None,
        wait_s: float = WAIT_S) -> dict:
    """One run of ``cell``; returns ``{"result": ..., "info": ...}`` where
    ``result`` is the line the benchmark prints last.  ``device_kind``
    and ``wait_s`` stand in for the chip's and the minute's wait in tests
    off the chip."""
    import jax

    due = schedule(float(cell.rate["rate_per_s"]), seconds)
    srv = Serving(cell, seed, due.shape[0], control)
    win = srv.window(srv.queries, due, seconds, trace, wait_s)
    rec, q, g = win["rec"], srv.queries, srv.g

    devices = jax.devices()[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    kind = device_kind or devices[0].device_kind
    info = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "control": cell.traffic["control"] if control else None,
        "backend": srv.backend, "rate_per_s": float(cell.rate["rate_per_s"]),
        "requests": int(due.shape[0]), "traffic": srv.traffic_info,
        "setup": {**srv.setup, "setup_s": win["setup_s"]},
        "jit_variants_at_warmup": srv.jit_variants,
        "recompiles_in_window": win["recompiles"],
        "compile": srv.meter.snapshot(), "compile_in_window": win["compile"],
        "kernel_invocations_in_window": win["kernel_invocations"],
        "engine_warnings": srv.engine_warnings.seen,
        "sender_lateness_ms": rec.lateness_ms(),
        "memory_peak_bytes": int(peak), "stats_delta": win["stats"],
        "window_wall_s": rec.t_end,
    }
    _, _, _, ops, _ = _program()
    info["kernel_invocations"] = {
        k: ops.KERNEL_INVOCATIONS[k] - srv.kinv0.get(k, 0)
        for k in ops.KERNEL_INVOCATIONS}
    classes = label_classes(srv.warm, g.n_labels) + 1
    lanes = srv.server.config.exact_chunk
    info["batch_jobs"] = batch_jobs(rec, q, g.n_labels,
                                    srv.server.config.max_jobs)
    srv.close()
    tr = reduce_trace(win["trace_dir"]) if trace else None

    # ---- correctness: every answer against its known truth, a sample
    # against the plain reference ----
    answered = rec.status == openloop.OK
    wrong = int((answered & (rec.answer != q.truth)).sum())
    never = int(np.isin(rec.status, (openloop.NEVER, openloop.ERROR)).sum())
    ids = np.flatnonzero(answered)
    n_check = min(ids.size, int(cell.rate["reference_sample"]))
    pick = np.sort(rng(seed, "sample").choice(ids, n_check, replace=False))
    t = time.perf_counter()
    ref = reference.answer(g, srv.rg, q.u[pick], q.v[pick], q.fam[pick],
                           q.a[pick], q.b[pick])
    info["reference_checked"] = int(n_check)
    info["reference_s"] = time.perf_counter() - t
    compared = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "reference_disagreements": {
            "value": int((ref != rec.answer[pick].astype(bool)).sum()),
            "limit": 0},
        "unanswered": {"value": never, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    shed = int((rec.status == openloop.SHED).sum())
    info["shed"] = shed
    if trace:
        ctx = {"stats": win["stats"], "recompiles": win["recompiles"],
               "latency_s": rec.latency_s(), "trace": tr, "n_vertices": g.n_vertices,
               "n_edges": g.n_edges, "lanes": lanes, "classes": classes,
               "device_kind": kind}
        metrics = layer_metrics(cell, ctx)
        info["trace"] = {k: tr[k] for k in ("kernels", "idle_share",
                                            "devices")}
    else:
        lat = rec.latency_s()
        e2e = {"p50_ms": percentile_ms(lat, 50),
               "p95_ms": percentile_ms(lat, 95),
               "qps": rec.answered_per_s(),
               "setup_s": win["setup_s"]}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": int(due.shape[0]),
              "failed": shed + never, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    result["compared"] = compared
    return {"result": result, "info": info}


#: device-op matchers of the kernels whose rooflines are metrics
KERNELS = {
    # the phase-2 class expansion: ``bitset_matmul`` is ``lane_matmul``
    # with op="or"; its custom call is named after the jitted wrapper
    "bitset_matmul": lambda name: name.startswith("%lane_matmul"),
}


def label_classes(warm: traffic_gen.Queries, n_labels: int) -> int:
    """Labels the warmup sample requires or forbids: the special classes
    the server pins (``pin_labels``); phase 2 runs one more, the neutral
    class."""
    used = set()
    for f, a, b in zip(warm.fam.tolist(), warm.a.tolist(), warm.b.tolist()):
        if traffic_gen.FAMILIES[f] == "LCR":
            return n_labels   # an LCR term forbids every other label
        used.update((a, b))
    return len(used)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the mix's control (must come out not correct)")
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    why = require_chip(cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 3
    enable_compile_cache()
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              control=args.control)
    print(json.dumps(out["info"]), flush=True)
    res = out["result"]
    for name, c in res["compared"].items():
        print(f"bench: compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: nothing of it may print after the result
    os._exit(rc)
