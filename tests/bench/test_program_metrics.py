"""The per-layer metrics read from the program's own spans and counters
(RoundRecord's span durations and counters, repro.trace): each reader on
a hand-made context, nothing from a program that records none, and the
program's count of useful local steps against the benchmark's own."""
from types import SimpleNamespace as R

import pytest

import benchtiny
from benchtiny import REPO

import run as harness  # noqa: E402  (bench/, put on the path by benchtiny)

NEW = ("ingest.stage_s", "ingest.place_s", "ingest.ring_empty_frac",
       "round.host_self_s")


def _ctx(records):
    return {"records": records, "useful_steps": 3, "computed_steps": 12,
            "samples": 1000, "window_s": 2.0, "chips": 1,
            "trace": {"busy_s": 0.5, "window_s": 2.0, "kernel_s": {},
                      "collective_exposed_s": 0.0}}


def test_program_span_readers():
    read = harness.layer_readers(list(NEW))
    recs = [R(seconds=0.5, ingest_host_seconds=0.15,
              ingest_device_seconds=0.0, sync_seconds=0.33,
              ingest_stage_seconds=0.6, ingest_place_seconds=0.02,
              ingest_ready=0),
            R(seconds=0.46, ingest_host_seconds=0.0,
              ingest_device_seconds=0.0, sync_seconds=0.45,
              ingest_stage_seconds=0.4, ingest_place_seconds=0.04,
              ingest_ready=1)]
    ctx = _ctx(recs)
    assert read["ingest.stage_s"](ctx) == pytest.approx(0.5)
    assert read["ingest.place_s"](ctx) == pytest.approx(0.03)
    assert read["ingest.ring_empty_frac"](ctx) == pytest.approx(0.5)
    # (0.5 - 0.15 - 0.33 + 0.46 - 0.0 - 0.45) / 2
    assert read["round.host_self_s"](ctx) == pytest.approx(0.015)
    recs[1].ingest_ready = 2
    ctx = _ctx([recs[1]])
    assert read["ingest.ring_empty_frac"](ctx) == 0.0


def test_program_span_readers_find_nothing_in_an_older_program():
    """A program without the spans (its records lack the fields) gives
    no value, and no error."""
    read = harness.layer_readers(list(NEW))
    old = [R(seconds=0.5, ingest_host_seconds=0.15,
             ingest_device_seconds=0.0)]
    for name in NEW:
        assert read[name](_ctx(old)) is None, name


def test_new_metrics_are_declared_for_the_cell():
    import json
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert m["moves"] == "samples_per_s"
        assert m["workloads"] == ["resnet18.paper"]
        assert (REPO / "bench" / "layer_metrics" / f"{name}.py").is_file()


def test_program_step_counts_equal_the_benchmark_count(tmp_path):
    """The program's own count of unmasked local steps and of the local
    steps it computes (RoundRecord.steps_useful / steps_computed, the
    staged mask's sum and size) equals the benchmark's independent copy
    of the batch rule, round by round: local.useful_step_frac may be
    read from either."""
    root = benchtiny.make_root(tmp_path, traffic={"dirichlet_alpha": 0.2})
    task = benchtiny.build(root, 2 ** 31 + 5)
    try:
        recs = [task.run_round(t) for t in range(6)]
    finally:
        task.close()
    assert [r.steps_useful for r in recs] == \
        [task.useful_steps(t) for t in range(6)]
    assert all(r.steps_computed == task.computed_steps() for r in recs)
    assert any(r.steps_useful < r.steps_computed for r in recs)  # padding
