"""A whole benchmark run off the chip, at a size a test can hold: sound it
comes out correct; under each mix's control, and with the timed path
broken underneath, it comes out not correct."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cell_mod, spec  # noqa: E402


def tiny(name: str) -> spec.Cell:
    """A deployment and a mix from their files (``<config>.<mix>``), cut to
    256 vertices, one job bucket and one second at 40 requests per second;
    the per-layer and end-to-end metrics of the manifest's first cell."""
    config, mix = name.split(".", 1)
    first = spec.cell(spec.manifest()["workloads"][0]["name"])
    return spec.Cell(
        name=name, chips=1,
        config=dict(spec.load_json(spec.BENCH / "configs" / f"{config}.json"),
                    n_vertices=256, serve={"max_jobs": 16}),
        traffic=dict(spec.load_json(spec.BENCH / "traffic" / f"{mix}.json"),
                     warmup_queries=16, probe_draws=512),
        rate={"rate_per_s": 40, "reference_sample": 64},
        end_to_end=first.end_to_end, per_layer=first.per_layer)


def run(name: str, control: bool = False) -> dict:
    out = cell_mod.run(tiny(name), 3_000_000_019, 1.0, trace=False,
                       control=control, device_kind="TPU v5 lite",
                       wait_s=2.0)
    res = out["result"]
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "compared"
    assert res["attempted"] == 40
    return res


@pytest.fixture
def program(monkeypatch):
    """The program's modules; whatever a test patches is put back, and
    programs compiled under a patch are dropped."""
    from repro.core import tdr_query
    from repro.launch import serve

    for mod, attr in ((tdr_query, "_bidi_loop"), (tdr_query, "answer_plan"),
                      (serve.QueryServer, "_serve_batch")):
        monkeypatch.setattr(mod, attr, getattr(mod, attr))
    yield tdr_query, serve
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("name", ["pa8k.bool-true", "er256k.bool-true",
                                  "er256k.bool-false"])
def test_sound_run_is_correct(name, program):
    res = run(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"p50_ms", "p95_ms", "qps", "setup_s"}
    assert 0 < m["p50_ms"]["value"] <= m["p95_ms"]["value"]
    assert m["qps"]["value"] > 0 and m["setup_s"]["value"] > 0


@pytest.mark.parametrize("name", ["pa8k.bool-true", "er256k.bool-false"])
def test_control_is_not_correct(name, program):
    res = run(name, control=True)
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_answer_altered_where_produced_is_not_correct(program):
    tdr_query, _ = program
    answer_plan = tdr_query.answer_plan

    def flip_first(*a, **kw):
        ans = answer_plan(*a, **kw)
        ans[:1] = ~ans[:1]
        return ans

    tdr_query.answer_plan = flip_first
    res = run("pa8k.bool-true")
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_every_answer_true_is_not_correct(program):
    """A phase 2 that sets too many bits, or a meet test that always
    fires, answers true where the mix's false reads are false."""
    tdr_query, _ = program
    answer_plan = tdr_query.answer_plan

    def all_true(*a, **kw):
        ans = answer_plan(*a, **kw)
        ans[:] = True
        return ans

    tdr_query.answer_plan = all_true
    res = run("pa8k.bool-true")
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0
    assert res["compared"]["reference_disagreements"]["value"] > 0


def test_expansion_that_keeps_its_state_is_not_correct(program):
    """Phase 2 returns its starting frontiers: no round ever runs."""
    tdr_query, _ = program
    loop = tdr_query._bidi_loop

    def unchanged(f0, b0, push_f, push_b, cor_w, sup_need, max_rounds):
        return loop(f0, b0, push_f, push_b, cor_w, sup_need, 0)

    tdr_query._bidi_loop = unchanged
    jax.clear_caches()
    res = run("pa8k.bool-true")
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_half_of_each_batch_left_out_is_not_correct(program):
    _, serve = program
    serve_batch = serve.QueryServer._serve_batch

    def half(self, batch):
        serve_batch(self, batch[:(len(batch) + 1) // 2])

    serve.QueryServer._serve_batch = half
    res = run("pa8k.bool-true")
    assert not res["correct"]
    assert res["compared"]["unanswered"]["value"] > 0
    assert res["failed"] > 0


def test_sample_is_seeded():
    rng = cell_mod.rng(3_000_000_019, "sample")
    again = cell_mod.rng(3_000_000_019, "sample")
    assert np.array_equal(rng.integers(0, 1 << 30, 8),
                          again.integers(0, 1 << 30, 8))
    assert not np.array_equal(
        cell_mod.rng(-3, "sample").integers(0, 1 << 30, 8),
        cell_mod.rng(3, "sample").integers(0, 1 << 30, 8))
