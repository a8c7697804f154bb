"""The chip benchmark's harness off the chip: the manifest and the files it
names, the traffic generator, the plain reference against the program's
DFS oracle, the open loop, the kernel byte count, and the refusal to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import graphs, kernel_work, openloop, reference, spec  # noqa: E402
from bench import traffic_gen  # noqa: E402

MAN = spec.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keys_names_and_units():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./\-]+", p)
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    names = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    assert "setup_s" in {m["name"] for m in MAN["end_to_end"]}
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = spec.cell(name, MAN)
    for key in ("family", "n_vertices", "avg_degree", "n_labels", "tdr",
                "serve", "source"):
        assert key in cell.config
    assert cell.traffic["kind"] == "bool"
    assert cell.rate["rate_per_s"] > 0 and cell.rate["reference_sample"] > 0
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert callable(spec.metric_reader(m["name"]))
        for w in m.get("workloads", CELLS):
            assert w in CELLS
            assert m["moves"] in {x["name"] for x in spec.cell(
                w, MAN).end_to_end}
        layers = {x["layer"] for x in MAN["per_layer"]
                  if x["layer"].lower() == m["layer"].lower()}
        assert len(layers) == 1   # one spelling per layer


def _small(family: str, seed: int, n: int = 300, labels: int = 6):
    g = graphs.make({"family": family, "n_vertices": n, "avg_degree": 2.0,
                     "n_labels": labels}, np.random.default_rng(seed))
    return g, g.reverse()


@pytest.mark.parametrize("family", ["er", "pa"])
@pytest.mark.parametrize("mix", ["bool-true", "bool-false"])
def test_traffic_deterministic_true_to_its_answers_and_distinct(family, mix):
    g, rg = _small(family, 3)
    m = spec.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")
    one = traffic_gen.generate(g, rg, m, 400, np.random.default_rng(9))
    two = traffic_gen.generate(g, rg, m, 400, np.random.default_rng(9))
    other = traffic_gen.generate(g, rg, m, 400, np.random.default_rng(10))
    cols = ("u", "v", "fam", "a", "b", "truth")
    assert all(np.array_equal(getattr(one, c), getattr(two, c))
               for c in cols)
    assert not all(np.array_equal(getattr(one, c), getattr(other, c))
                   for c in cols)
    assert len(one) == 400
    keys = set(zip(*(getattr(one, c).tolist() for c in cols[:5])))
    assert len(keys) == 400                       # no (u, v, pattern) twice
    assert (one.a < one.b).all()
    assert one.truth.sum() == round(400 * m["true_share"])
    walked = [traffic_gen.FAMILIES.index(f)
              for f, i in one.info.get("true", {}).items()
              if i["drawn"] == "walk"]
    uniform = ~(one.truth & np.isin(one.fam, walked))
    assert (one.u != one.v)[uniform].all()
    ans = reference.answer(g, rg, one.u, one.v, one.fam, one.a, one.b)
    assert np.array_equal(ans, one.truth)
    if m["false_reachable"]:
        # a false query's u reaches v once the labels are ignored
        f = ~one.truth
        k = int(f.sum())
        assert k > 0 and reference.holds(
            g, rg, one.u[f], one.v[f], np.zeros((k, g.n_labels), np.int8),
            np.zeros(k, np.int8)).all()


def test_pivot_certifies_only_true_draws():
    g, rg = _small("er", 5, n=400)
    piv = traffic_gen.Pivot(g, rg)
    rng = np.random.default_rng(1)
    n = 2000
    u, v = rng.integers(0, 400, n), rng.integers(0, 400, n)
    fam = rng.integers(0, 4, n)
    a = rng.integers(0, 6, n)
    b = (a + rng.integers(1, 6, n)) % 6
    a, b = np.minimum(a, b), np.maximum(a, b)
    cert = piv.certify(u, v, fam, a, b, 6)
    ans = reference.answer(g, rg, u, v, fam, a, b)
    assert cert.any() and not (cert & ~ans).any()


def _dfs_answers(g, queries):
    from repro.core import dfs_baseline
    from repro.core import graph as G
    from repro.core import pattern as pat

    pg = G.Graph(g.n_vertices, g.n_labels, g.indptr.astype(np.int32),
                 g.indices, g.labels)
    make = [pat.all_of, pat.any_of, pat.none_of,
            lambda labs: pat.lcr(labs, g.n_labels)]
    return np.array([dfs_baseline.answer_pcr(pg, u, v, make[f]([a, b]))
                     for u, v, f, a, b in queries])


@pytest.mark.parametrize("family", ["er", "pa"])
def test_copied_generators_draw_like_the_programs(family):
    from repro.core import graph as G

    want = G.random_graph(family, 120, 3.0, 5, seed=4)
    got = graphs.make({"family": family, "n_vertices": 120,
                       "avg_degree": 3.0, "n_labels": 5},
                      np.random.default_rng(4))
    for col in ("indptr", "indices", "labels"):
        assert np.array_equal(getattr(got, col), getattr(want, col))


@pytest.mark.parametrize("family,seed", [("er", 0), ("er", 1), ("pa", 0),
                                         ("pa", 1)])
def test_reference_matches_dfs_oracle_on_small_graphs(family, seed):
    g, rg = _small(family, seed, n=80, labels=4)
    rng = np.random.default_rng(seed + 7)
    n = 500
    q = np.stack([rng.integers(0, 80, n), rng.integers(0, 80, n),
                  rng.integers(0, 4, n), rng.integers(0, 4, n),
                  rng.integers(1, 4, n)], axis=1)
    q[:, 4] = (q[:, 3] + q[:, 4]) % 4
    q[:25, 1] = q[:25, 0]                             # u == v cases
    got = reference.answer(g, rg, *q.T)
    assert np.array_equal(got, _dfs_answers(g, q.tolist()))
    assert 0 < got.mean() < 1


def test_reference_matches_dfs_oracle_on_fig2():
    from repro.core import graph as G

    f2 = G.fig2_example()
    g = graphs.from_edges(f2.n_vertices, f2.n_labels, f2.src, f2.indices,
                          f2.labels)
    q = [(u, v, f, a, b) for u in range(10) for v in range(10)
         for f in range(4) for a in range(5) for b in range(a + 1, 5)]
    got = reference.answer(g, g.reverse(), *np.array(q).T)
    assert np.array_equal(got, _dfs_answers(g, q))


def test_open_loop_times_from_due_and_fixes_the_count():
    from concurrent.futures import Future

    due = openloop.arrivals(np.random.default_rng(0), 200.0, 0.25)
    assert due.shape == (50,) and (np.diff(due) >= 0).all()
    assert np.array_equal(due, openloop.arrivals(np.random.default_rng(0),
                                                 200.0, 0.25))

    class Shed(Exception):
        pass

    def submit(i):
        if i == 3:
            raise Shed
        f = Future()
        if i != 4:
            f.set_result(i % 2 == 0)
        return f

    rec = openloop.drive(submit, due, 0.25, Shed, wait_s=0.1)
    assert rec.status[3] == openloop.SHED
    assert rec.status[4] == openloop.NEVER
    ok = rec.status == openloop.OK
    assert ok.sum() == 48
    assert np.array_equal(rec.answer[ok], (np.flatnonzero(ok) % 2 == 0))
    lat = rec.latency_s()
    assert (lat[ok] >= 0).all()
    assert lat[3] == pytest.approx(0.35 - due[3])     # charged till give-up
    assert rec.answered_per_s() == pytest.approx(
        48 / max(0.25, rec.done[ok].max()))


def test_tail_reader_reads_the_window_p95_or_nothing():
    read = spec.metric_reader("serve.p95_ms")
    lat = np.arange(1, 101, dtype=float)
    assert read({"latency_s": lat}) == pytest.approx(
        np.percentile(lat, 95) * 1e3)
    assert read({"stats": {}}) is None


def test_seed_renames_labels_and_keeps_answers():
    from bench import cell as cell_mod

    g, rg = _small("pa", 3)
    m = spec.load_json(ROOT / "bench" / "traffic" / "bool-true.json")
    q = traffic_gen.generate(g, rg, m, 200, np.random.default_rng(9))
    g1, rg1, q1 = cell_mod.relabel(g, rg, q, 2**31 + 7)
    assert np.array_equal(
        g1.labels, cell_mod.relabel(g, rg, q, 2**31 + 7)[0].labels)
    assert not np.array_equal(
        g1.labels, cell_mod.relabel(g, rg, q, 2**31 + 8)[0].labels)
    for x, y in ((g1, g), (rg1, rg)):          # the same edges, renamed
        assert np.array_equal(x.indptr, y.indptr)
        assert np.array_equal(x.indices, y.indices)
        assert sorted(np.bincount(x.labels).tolist()) == sorted(
            np.bincount(y.labels).tolist())
    assert (q1.a < q1.b).all() and np.array_equal(q1.u, q.u)
    assert np.array_equal(q1.fam, q.fam)
    assert np.array_equal(reference.answer(g1, rg1, q1.u, q1.v, q1.fam,
                                           q1.a, q1.b), q.truth)
    assert np.array_equal(cell_mod.schedule(3.0, 20.0),
                          cell_mod.schedule(3.0, 20.0))


def test_batches_are_answers_that_complete_together():
    rec = openloop.Run(np.zeros(6), 1.0, 0.0)
    rec.status[:] = openloop.OK
    rec.status[4] = openloop.SHED
    rec.done[:] = [2.0, 0.5, 2.001, 0.501, np.nan, 3.5]
    groups = [g.tolist() for g in rec.completion_groups()]
    assert groups == [[1, 3], [0, 2], [5]]


def test_kernel_bytes_count_work_not_layout():
    v, lanes, e, c = 8192, 32, 32768, 9
    one = kernel_work.bitset_matmul_bytes(c, v, lanes, e, c)
    assert one == c * 8 * v * lanes + 8 * e          # one sweep of a round
    assert kernel_work.bitset_matmul_bytes(2 * c, v, lanes, e, c) == 2 * one
    share = kernel_work.roofline_share(819e6, 1e-3, "TPU v5 lite")
    assert share == pytest.approx(100.0)
    with pytest.raises(KeyError):
        kernel_work.peaks("TPU v9 imaginary")


def test_cell_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/cell.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "needs a TPU" in res.stderr


def test_benchmark_files_alone_cannot_reach_the_program(tmp_path):
    """A checkout of only the manifest and its paths holds no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); from bench import cell; "
         "cell._program()"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0 and "repro" in res.stderr
