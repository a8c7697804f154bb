"""The server's spans and counters: a small ``QueryServer`` traced off the
chip, ``bench.spans.idle_under`` on hand-made events, the readers of the
span and counter metrics on hand-made runs, and a trace recorded on a TPU
v5e (the start of a traced ``pa8k.bool-true`` window through its second
batch)."""
import glob
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cell_mod, spans, spec  # noqa: E402
from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:"
RECORDED = ROOT / "bench" / "recorded" / "trace_pa8k_bool_true_spans.json"
QUERY_SPANS = ("query.plan", "query.phase1", "query.phase2",
               "query.phase2.dispatch", "query.phase2.collect")


def ev(plane, line, name, start, end):
    return tr.Event(plane, line, name, float(start), float(end))


# ---------------------------------------------- the program, traced on CPU
@pytest.fixture(scope="module")
def traced_server(tmp_path_factory):
    """Two bursts of requests through a warm server on a 256-vertex PA
    graph, traced: the server's events, its stats before and after, and
    the ``serve.batch`` events' args read from the trace file."""
    from repro.core import graph as G, pattern as pat, tdr_build
    from repro.launch import serve

    g = G.random_graph("pa", 256, 4.0, 8, seed=5)
    index = tdr_build.build_index(g, tdr_build.TDRConfig())
    rng = np.random.default_rng(5)
    make = (pat.all_of, pat.any_of, pat.none_of)
    queries = []
    for i in range(40):
        u, v = (int(x) for x in rng.integers(g.n_vertices, size=2))
        labs = rng.choice(g.n_labels, size=2, replace=False).tolist()
        queries.append((u, v, make[i % 3](labs)))
    server = serve.QueryServer(index, serve.ServeConfig(max_jobs=16))
    server.start()
    try:
        server.warmup(queries[:16])
        before = spans.counters(server)
        qs = server.stats.query_stats
        t_before = (qs.phase1_s, qs.phase2_s, server.stats.batches,
                    server.stats.served)
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(trace_dir)
        for burst in (queries[16:28], queries[28:]):
            futs = [server.submit(u, v, p) for u, v, p in burst]
            for f in futs:
                f.result(timeout=300)
            time.sleep(0.2)   # the scheduler goes back to waiting
        jax.profiler.stop_trace()
        after = spans.counters(server)
        t_after = (qs.phase1_s, qs.phase2_s, server.stats.batches,
                   server.stats.served)
    finally:
        server.stop()
    events = tr.load_xplane(trace_dir)
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    args = [dict(e.stats) for p in jax.profiler.ProfileData.from_file(
        path).planes for line in p.lines for e in line.events
        if e.name == "serve.batch"]
    delta = {k: after[k] - before[k] for k in after}
    delta.update(zip(("phase1_s", "phase2_s", "batches", "served"),
                     np.subtract(t_after, t_before).tolist()))
    return events, delta, args


def _by_name(events, name):
    return [e for e in events if e.name == name]


def test_every_span_of_the_served_path_is_traced(traced_server):
    events, delta, _ = traced_server
    names = {e.name for e in events}
    assert set(spans.SPANS) <= names, set(spans.SPANS) - names
    assert len(_by_name(events, "serve.batch")) == delta["batches"] >= 2


def test_query_spans_nest_inside_a_batch_on_the_scheduler_thread(
        traced_server):
    events, _, _ = traced_server
    batches = _by_name(events, "serve.batch")
    for name in QUERY_SPANS + ("serve.fanout",):
        for e in _by_name(events, name):
            assert any(b.plane == e.plane and b.line == e.line
                       and b.start_ns <= e.start_ns
                       and e.end_ns <= b.end_ns for b in batches), e


def test_batch_span_names_its_sequence_number_and_request_ids(
        traced_server):
    _, delta, args = traced_server
    args = sorted(args, key=lambda a: a["batch"])
    assert [a["batch"] for a in args] == list(
        range(args[0]["batch"], args[0]["batch"] + len(args)))
    ranges = [tuple(int(x) for x in a["rids"].split("-")) for a in args]
    # FIFO: each batch takes the ids after the last one's
    for (_, last), (first, _) in zip(ranges, ranges[1:]):
        assert first == last + 1
    assert sum(a["requests"] for a in args) == delta["served"]
    assert sum(b - a + 1 for a, b in ranges) == delta["served"]
    assert all(a["jobs"] >= a["requests"] for a in args)


@pytest.mark.parametrize("field,name", [("phase1_s", "query.phase1"),
                                        ("phase2_s", "query.phase2"),
                                        ("query.plan_s", "query.plan")])
def test_span_totals_match_the_traced_durations(traced_server, field, name):
    events, delta, _ = traced_server
    traced_s = sum(e.end_ns - e.start_ns for e in _by_name(events, name))
    assert traced_s > 0
    assert delta[field] == pytest.approx(traced_s * 1e-9, rel=0.05)


def test_counters_of_the_traced_bursts(traced_server):
    _, delta, _ = traced_server
    assert delta["queue_wait_s"] > 0
    assert delta["query.exact_chunks"] >= 1
    # a chunk runs at least one round
    assert delta["query.exact_rounds"] >= delta["query.exact_chunks"]


# ------------------------------------------------- idle_under, hand-made
def synthetic():
    """Window 0-1000; device 0 busy 100-300, 480-620, 900-1000; device 1
    busy 0-500.  The scheduler thread: coalescing 50-120, a batch 250-700,
    waiting 700-1200 (past the window's end)."""
    return [
        ev("/host:CPU", "python", tr.WINDOW_SPAN, 0, 1000),
        ev(DEV + "0", tr.OPS_LINE, "fusion.1", 100, 200),
        ev(DEV + "0", tr.OPS_LINE, "fusion.2", 150, 300),
        ev(DEV + "0", tr.OPS_LINE, "%lane_matmul.3 = s32[8] custom-call()",
           480, 620),
        ev(DEV + "0", tr.OPS_LINE, "fusion.3", 900, 1100),
        ev(DEV + "1", tr.OPS_LINE, "fusion.1", 0, 500),
        ev("/host:CPU", "tdr-serve", "serve.coalesce", 50, 120),
        ev("/host:CPU", "tdr-serve", "serve.batch", 250, 700),
        ev("/host:CPU", "tdr-serve", "query.phase2", 260, 690),
        ev("/host:CPU", "tdr-serve", "serve.wait_for_work", 700, 1200),
        ev("/host:CPU", "python", "bench.wait", 0, 1000),
    ]


def test_idle_under_is_device_idle_time_inside_the_spans():
    events = synthetic()
    # device 0 idle under the spans: 50-100, 300-480, 620-700; device 1:
    # 500-700 (its ops cover the coalescing)
    assert spans.idle_under(events, spans.HOST_BOUND) == pytest.approx(
        (50 + 180 + 80 + 200) / 2 / 1000)
    assert spans.idle_under(events, ["serve.batch"]) == pytest.approx(
        (180 + 80 + 200) / 2 / 1000)
    # clipped at the window's end: device 0 idle 700-900, device 1 all
    assert spans.idle_under(events, spans.WAITING) == pytest.approx(
        (200 + 300) / 2 / 1000)
    s = tr.summarize(events)
    host = spans.idle_under(events, spans.HOST_BOUND)
    waiting = spans.idle_under(events, spans.WAITING)
    assert 0 <= host <= s["idle_share"]
    assert host + waiting <= s["idle_share"] + 1e-12


def test_idle_under_reads_nothing_without_the_spans():
    events = [e for e in synthetic() if not e.name.startswith("serve.")]
    assert spans.idle_under(events, spans.HOST_BOUND) is None
    assert spans.idle_under(synthetic(), ["no.such.span"]) is None


def test_idle_split_and_cut():
    events = synthetic()
    split = spans.idle_split(events, tr.summarize(events))
    assert split["host_bound_s"] == pytest.approx(255e-9)
    assert split["waiting_s"] == pytest.approx(250e-9)
    assert split["rest_s"] == pytest.approx(split["idle_s"] - 505e-9)
    assert split["under_span_s"]["query.phase2"] == pytest.approx(
        (180 + 70 + 190) / 2 * 1e-9)
    assert split["under_span_s"]["query.plan"] is None
    busy = [e for e in events if e.name != "serve.wait_for_work"]
    split = spans.idle_split(busy, tr.summarize(busy))
    assert split["waiting_s"] == 0.0
    assert split["rest_s"] == pytest.approx(split["idle_s"] - 255e-9)
    bare = [e for e in events if not e.name.startswith("serve.")]
    assert set(spans.idle_split(bare, tr.summarize(bare))) == {
        "idle_s", "host_bound_s", "under_span_s"}
    part = spans.cut(events, 1)
    assert tr.window(part) == (0.0, 700.0)
    assert all(e.start_ns < 700 for e in part)
    with pytest.raises(ValueError):
        spans.cut(events, 2)


# ------------------------------------------------ readers, hand-made runs
PARENT_STATS = {"served": 8, "batches": 2, "query.phase1_s": 0.01,
                "query.phase2_s": 8.0}


@pytest.mark.parametrize("name,run,want", [
    ("serve.queue_wait_ms", {"stats": {**PARENT_STATS,
                                       "queue_wait_s": 6.0}}, 750.0),
    ("serve.queue_wait_ms", {"stats": PARENT_STATS}, None),
    ("serve.queue_wait_ms", {"stats": {**PARENT_STATS, "served": 0,
                                       "queue_wait_s": 0.0}}, None),
    ("query.plan_ms_per_batch", {"stats": {**PARENT_STATS,
                                           "query.plan_s": 0.005}}, 2.5),
    ("query.plan_ms_per_batch", {"stats": PARENT_STATS}, None),
    ("phase2.rounds_per_chunk", {"stats": {
        **PARENT_STATS, "query.exact_rounds": 26,
        "query.exact_chunks": 2}}, 13.0),
    ("phase2.rounds_per_chunk", {"stats": {
        **PARENT_STATS, "query.exact_rounds": 0,
        "query.exact_chunks": 0}}, None),
    ("phase2.rounds_per_chunk", {"stats": PARENT_STATS}, None),
    ("device.host_bound_idle_share",
     {"trace": {"idle_share": 0.1, "host_bound_idle_share": 0.04}}, 4.0),
    ("device.host_bound_idle_share",
     {"trace": {"idle_share": 0.1, "host_bound_idle_share": None}}, None),
    ("device.host_bound_idle_share", {"trace": {"idle_share": 0.1}}, None),
    ("device.host_bound_idle_share", {"trace": None}, None),
])
def test_span_metric_readers(name, run, want):
    got = spec.metric_reader(name)(run)
    assert got == (None if want is None else pytest.approx(want))


def test_every_span_metric_has_a_reader():
    for name in spans.METRICS:
        assert (ROOT / "bench" / "metrics" / f"{name}.py").is_file()


# -------------------------------------------------- recorded on the chip
def test_recorded_chip_trace_holds_the_server_spans():
    events = tr.load_events(str(RECORDED))
    names = {e.name for e in events}
    assert {"serve.wait_for_work", "serve.coalesce", "serve.batch",
            "serve.fanout"} | set(QUERY_SPANS) <= names
    s = tr.summarize(events, kernels=cell_mod.KERNELS)
    host = spans.idle_under(events, spans.HOST_BOUND)
    assert 0 <= host <= s["idle_share"]
    k = s["kernels"]["bitset_matmul"]
    assert k["calls"] > 0 and 0 < k["seconds"] <= s["busy_s"]
