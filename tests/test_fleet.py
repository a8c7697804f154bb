"""Replicated serving fleet: shared-log multi-reader semantics, the
in-process follower (``QueryServer.follow``), and router placement.

The reader-visibility contract under test: a ``deltalog.LogReader``
yields exactly the records a recovering writer would replay as
committed — complete, CRC-valid, dense-LSN — in order, each exactly
once, across concurrent appends, torn in-flight tails (fault-injected
mid-write crashes), and ``truncate_upto`` compaction.  On top of that,
a follower replica must serve answers equal to the DFS oracle at its
*exact* applied LSN, and the multi-process fleet (subprocess replicas,
SIGKILL, re-spawn) is exercised end to end by ``tests/fleet_check.py``.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import faultinject
from repro.core import deltalog, dfs_baseline, graph as G
from repro.core import pattern as pat, tdr_build
from repro.launch import fleet as fleet_mod, serve
from repro.launch.router import FleetRouter

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)
N_V, N_L = 24, 4


def R(*rows):
    """Edge rows as the int64 ``[N, 3]`` arrays the log stores."""
    return np.asarray(rows, dtype=np.int64).reshape(-1, 3)


def lsns(recs):
    return [lsn for lsn, _, _ in recs]


# ------------------------------------------------------- reader basics
def test_reader_tails_exactly_once(tmp_path):
    """Two independent readers over one log each see every committed
    record exactly once, in order, as the writer appends."""
    log = deltalog.DeltaLog(str(tmp_path / "wal"))
    r1 = deltalog.LogReader(str(tmp_path / "wal"))
    r2 = deltalog.LogReader(str(tmp_path / "wal"))
    assert r1.poll() == [] and r2.poll() == []
    log.append(R((0, 1, 0)), R())
    log.append(R((1, 2, 1)), R((0, 1, 0)))
    got1 = r1.poll()
    assert lsns(got1) == [1, 2]
    assert np.array_equal(got1[1][1], R((1, 2, 1)))
    assert np.array_equal(got1[1][2], R((0, 1, 0)))
    assert r1.poll() == []          # nothing new: cursor advanced
    log.append(R((2, 3, 2)), R())
    assert lsns(r1.poll()) == [3]
    # the second reader was never polled: it now sees all three at once
    assert lsns(r2.poll()) == [1, 2, 3]
    # max_records bounds a poll without losing records
    r3 = deltalog.LogReader(str(tmp_path / "wal"))
    assert lsns(r3.poll(max_records=2)) == [1, 2]
    assert lsns(r3.poll()) == [3]
    log.close()


def test_reader_seek_and_after_lsn(tmp_path):
    log = deltalog.DeltaLog(str(tmp_path / "wal"))
    for i in range(4):
        log.append(R((i, i + 1, 0)), R())
    r = deltalog.LogReader(str(tmp_path / "wal"), after_lsn=2)
    assert lsns(r.poll()) == [3, 4]
    r.seek(1)       # re-deliver (the failed-apply rewind path)
    assert lsns(r.poll()) == [2, 3, 4]
    log.close()


def test_reader_concurrent_writer_two_tails(tmp_path):
    """Concurrent writer + two tailing readers: each reader sees the
    dense committed sequence in order, records only ever at or at most
    one past the writer's ack frontier (an fsync'd append whose
    ``append`` call hasn't returned yet)."""
    path = str(tmp_path / "wal")
    log = deltalog.DeltaLog(path)
    n_total, acked = 60, []

    def writer():
        for i in range(n_total):
            lsn = log.append(R((i % N_V, (i + 1) % N_V, i % N_L)), R())
            acked.append(lsn)
            if i % 7 == 0:
                time.sleep(0.001)

    seen = {0: [], 1: []}
    errs = []

    def tail(k):
        r = deltalog.LogReader(path)
        try:
            while len(seen[k]) < n_total:
                for lsn, _, _ in r.poll():
                    frontier = len(acked)
                    assert lsn <= frontier + 1, \
                        f"reader saw lsn {lsn}, writer acked {frontier}"
                    seen[k].append(lsn)
        except Exception as exc:  # noqa: BLE001 — re-raised in the test
            errs.append(exc)

    threads = [threading.Thread(target=tail, args=(k,)) for k in seen]
    for t in threads:
        t.start()
    writer()
    for t in threads:
        t.join(timeout=60)
    log.close()
    assert not errs, errs
    assert seen[0] == list(range(1, n_total + 1))
    assert seen[1] == list(range(1, n_total + 1))


# ------------------------------------------------- torn tails, faults
def _ops_per(tmp_path, n_appends):
    """Mutating-I/O ops for ``DeltaLog() + n appends`` (deterministic)."""
    plan = faultinject.FaultPlan(kind="count")
    with faultinject.inject(plan):
        log = deltalog.DeltaLog(str(tmp_path / "probe.wal"))
        for i in range(n_appends):
            log.append(R((i, i + 1, 0)), R())
    log.close()
    return plan.count


def test_reader_never_yields_torn_tail(tmp_path):
    """A writer crash mid-append leaves a torn record on disk; no poll
    ever yields it — and after writer recovery (which truncates the
    tear) the reader picks up the *recommitted* LSN exactly once."""
    path = str(tmp_path / "wal")
    # crash on the first mutating op of the 3rd append: its torn write
    plan = faultinject.FaultPlan(nth=_ops_per(tmp_path, 2) + 1,
                                 kind="kill", partial_frac=0.5)
    with faultinject.inject(plan):
        log = deltalog.DeltaLog(path)
        log.append(R((0, 1, 0)), R())
        log.append(R((1, 2, 1)), R())
        with pytest.raises(OSError):
            log.append(R((2, 3, 2)), R())
    assert plan.fired
    r = deltalog.LogReader(path)
    assert lsns(r.poll()) == [1, 2]     # the torn lsn-3 is invisible
    assert r.poll() == []               # reads as "in progress", waits
    # writer recovery truncates the tear and commits a different lsn 3
    log2 = deltalog.DeltaLog(path)
    assert log2.last_lsn == 2
    log2.append(R((9, 10, 3)), R())
    got = r.poll()
    assert lsns(got) == [3]
    assert np.array_equal(got[0][1], R((9, 10, 3)))
    log2.close()


def test_reader_torn_mid_append_window(tmp_path):
    """Polls racing a single in-flight append: whatever prefix of the
    record bytes is visible, the reader reports nothing new rather than
    garbage (simulated by truncating a copy at every byte length)."""
    path = str(tmp_path / "wal")
    log = deltalog.DeltaLog(path)
    log.append(R((0, 1, 0)), R())
    base_len = os.path.getsize(path)
    log.append(R((1, 2, 1), (2, 3, 2)), R((0, 1, 0)))
    full = open(path, "rb").read()
    log.close()
    torn = str(tmp_path / "torn.wal")
    for cut in range(base_len, len(full)):
        with open(torn, "wb") as f:
            f.write(full[:cut])
        r = deltalog.LogReader(torn)
        assert lsns(r.poll()) == [1], f"cut at {cut} bytes"


def test_reader_detects_mid_log_corruption(tmp_path):
    """A payload-CRC failure *behind* later records can't be an
    in-flight append: typed ``LogCorrupt``, never bad data."""
    path = str(tmp_path / "wal")
    log = deltalog.DeltaLog(path)
    hdr = os.path.getsize(path)
    log.append(R((0, 1, 0)), R())
    first_end = os.path.getsize(path)
    log.append(R((1, 2, 1)), R())
    log.close()
    data = bytearray(open(path, "rb").read())
    data[first_end - 3] ^= 0xFF         # flip a byte in record 1's payload
    with open(path, "wb") as f:
        f.write(bytes(data))
    r = deltalog.LogReader(path)
    with pytest.raises(deltalog.LogCorrupt):
        r.poll()
    assert hdr < first_end              # sanity: we hit a payload byte


def test_reader_pop_tail_retreat_is_corrupt(tmp_path):
    """``pop_tail`` under an active reader violates append-is-commit:
    a tip retreat below the cursor raises ``LogCorrupt``."""
    path = str(tmp_path / "wal")
    log = deltalog.DeltaLog(path)
    log.append(R((0, 1, 0)), R())
    lsn = log.append(R((1, 2, 1)), R())
    r = deltalog.LogReader(path)
    assert lsns(r.poll()) == [1, 2]
    log.pop_tail(lsn)
    with pytest.raises(deltalog.LogCorrupt):
        r.poll()
    log.close()


# ----------------------------------------------------------- compaction
def test_reader_cursor_survives_compaction(tmp_path):
    """``truncate_upto`` at/behind the cursor is invisible to the
    reader; past the cursor it raises ``LogCompactedPast`` so the
    replica re-bootstraps from a snapshot."""
    path = str(tmp_path / "wal")
    log = deltalog.DeltaLog(path)
    for i in range(6):
        log.append(R((i, i + 1, 0)), R())
    r = deltalog.LogReader(path)
    assert lsns(r.poll(max_records=4)) == [1, 2, 3, 4]
    log.truncate_upto(3)                # behind the cursor: harmless
    assert lsns(r.poll()) == [5, 6]
    log.append(R((6, 7, 0)), R())
    assert lsns(r.poll()) == [7]
    # a reader still at lsn 2 needed records the compaction dropped
    behind = deltalog.LogReader(path, after_lsn=2)
    with pytest.raises(deltalog.LogCompactedPast):
        behind.poll()
    # fresh attach: probe succeeds on a compacted log (no cursor check),
    # base_lsn tells the caller which snapshot generation it needs
    fresh = deltalog.LogReader(path)
    assert fresh.base_lsn == 3
    fresh.seek(3)
    assert lsns(fresh.poll()) == [4, 5, 6, 7]
    log.close()


# ------------------------------------------------- in-process follower
@pytest.mark.parametrize("backend", ["segment"])
def test_follower_tails_and_stamps_exact_lsn(backend, tmp_path):
    """A ``QueryServer.follow`` replica over a shared store applies the
    writer's published sequence, answers with the oracle of the graph
    *at its stamped read LSN*, blocks consistent reads via
    ``wait_for_lsn``, and refuses local writes."""
    d = str(tmp_path / "store")
    rng = np.random.default_rng(3)
    g = G.random_graph("er", N_V, 2.0, N_L, seed=3)
    idx = tdr_build.build_index(g, CFG, backend=backend)
    fleet_mod.init_store(idx, d)
    writer = fleet_mod.FleetWriter(d)
    srv = serve.QueryServer.follow(d, backend=backend, poll_s=0.01)
    srv.start()
    try:
        with pytest.raises(RuntimeError):
            srv.submit_update([(0, 1, 0)], [])
        graphs = [g]
        qs = []
        for i in range(6):
            u, v = int(rng.integers(N_V)), int(rng.integers(N_V))
            labs = rng.choice(N_L, size=2, replace=False).tolist()
            qs.append((u, v, [pat.all_of(labs), pat.any_of(labs),
                              pat.none_of(labs)][i % 3]))
        for step in range(4):
            add, rem = [], []
            for _ in range(2):
                u, v = int(rng.integers(N_V)), int(rng.integers(N_V))
                if u != v:
                    add.append((u, v, int(rng.integers(N_L))))
            lsn = writer.publish(add, rem)
            graphs.append(writer.graph)
            assert srv.wait_for_lsn(lsn, timeout=60), \
                f"follower stuck below lsn {lsn}"
            for u, v, p in qs:
                ans, alsn = srv.submit(u, v, p,
                                       with_lsn=True).result(timeout=60)
                assert alsn >= lsn
                want = dfs_baseline.answer_pcr(graphs[alsn], u, v, p)
                assert ans == want, (step, u, v, ans, want)
        assert srv.stats.applied_lsn == writer.last_lsn
    finally:
        srv.stop()
        writer.close()


@pytest.mark.parametrize("backend", ["segment"])
def test_follower_survives_writer_compaction(backend, tmp_path):
    """The writer checkpoints + compacts; a follower that is behind the
    compaction point re-bootstraps from the new snapshot and keeps
    serving (the ``LogCompactedPast`` → ``_refollow`` path)."""
    d = str(tmp_path / "store")
    g = G.random_graph("er", N_V, 2.0, N_L, seed=5)
    idx = tdr_build.build_index(g, CFG, backend=backend)
    fleet_mod.init_store(idx, d)
    writer = fleet_mod.FleetWriter(d)
    for i in range(3):
        writer.publish([(i, i + 10, i % N_L)], [])
    cur = tdr_build.build_index(writer.graph, CFG, layout=idx.disc,
                                backend=backend)
    assert writer.checkpoint(cur) == 3
    # the log is truncated only up to the *previous* snapshot (kept as
    # a corruption fallback): the base advances on the next checkpoint
    assert writer.log.base_lsn == 0
    for i in range(3):
        writer.publish([(i + 3, i + 13, i % N_L)], [])
    cur = tdr_build.build_index(writer.graph, CFG, layout=idx.disc,
                                backend=backend)
    assert writer.checkpoint(cur) == 6
    assert writer.log.base_lsn == 3     # records <= 3 really dropped
    # a follower attaching *after* compaction must pick the new snapshot
    srv = serve.QueryServer.follow(d, backend=backend, poll_s=0.01)
    srv.start()
    try:
        lsn = writer.publish([(20, 21, 0)], [])
        assert srv.wait_for_lsn(lsn, timeout=60)
        ans, alsn = srv.submit(20, 21, pat.any_of([0]),
                               with_lsn=True).result(timeout=60)
        assert alsn >= lsn and ans is True or ans == \
            dfs_baseline.answer_pcr(writer.graph, 20, 21, pat.any_of([0]))
    finally:
        srv.stop()
        writer.close()


# ------------------------------------------------------ process fleet
@pytest.mark.slow
def test_fleet_subprocess_sigkill_smoke():
    """Real multi-process fleet: ``tests/fleet_check.py`` runs router +
    3 replica processes, SIGKILLs a replica and the writer mid-stream,
    and asserts every answer equals the DFS oracle at its read LSN
    (also the CI ``fleet`` job's standalone leg)."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(here, "fleet_check.py"),
         "segment"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "fleet check OK" in r.stdout


class _FakeReplica:
    """Stands in for a replica process that reported ``ready``."""

    def __init__(self, name, platform):
        self.name, self.platform = name, platform
        self.ready = self.alive = True
        self.stopped = False

    def stop(self):
        self.stopped = True


@pytest.mark.parametrize("other", [False, True], ids=["same", "other"])
def test_fleet_start_refuses_replica_on_other_platform(other, monkeypatch,
                                                       tmp_path):
    """A replica is its own process: on an accelerator it may come up on
    the CPU.  ``Fleet.start`` compares each replica's reported platform
    with the parent's and refuses a mismatch."""
    import jax
    want = jax.devices()[0].platform
    reps = [_FakeReplica("replica-1", want),
            _FakeReplica("replica-2", "elsewhere" if other else want)]
    fl = fleet_mod.Fleet(str(tmp_path), n=2)
    monkeypatch.setattr(fl, "_spawn_locked", lambda: reps.pop(0))
    monkeypatch.setattr(fl, "_monitor_loop", lambda: None)
    if other:
        with pytest.raises(RuntimeError, match="replica-2=elsewhere"):
            fl.start(ready_timeout_s=5)
        assert all(r.stopped for r in fl._members)
    else:
        fl.start(ready_timeout_s=5)
        fl.stop()
