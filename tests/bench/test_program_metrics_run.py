"""The per-layer metrics read from the program's count of the client-
steps its local scan computes and of the bytes each round places
(RoundRecord.steps_run / ingest_placed_bytes): each reader on a hand-
made context and on a tiny run, nothing from a program that records
neither, and both declared for the cell."""
import json
from types import SimpleNamespace as R

import pytest

import benchtiny
from benchtiny import CELL, REPO

import run as harness  # noqa: E402  (bench/, put on the path by benchtiny)

NEW = ("local.run_useful_frac", "ingest.placed_mb")


def _ctx(records):
    return {"records": records, "useful_steps": 3, "computed_steps": 12,
            "samples": 1000, "window_s": 2.0, "chips": 1,
            "trace": {"busy_s": 0.5, "window_s": 2.0, "kernel_s": {},
                      "collective_exposed_s": 0.0}}


def test_step_and_byte_readers():
    read = harness.layer_readers(list(NEW))
    recs = [R(steps_useful=14, steps_run=16, steps_computed=30,
              ingest_placed_bytes=30_790),
            R(steps_useful=12, steps_run=15, steps_computed=30,
              ingest_placed_bytes=30_790)]
    assert read["local.run_useful_frac"](_ctx(recs)) == \
        pytest.approx(26 / 31)
    assert read["ingest.placed_mb"](_ctx(recs)) == pytest.approx(0.03079)


def test_step_and_byte_readers_find_nothing_in_an_older_program():
    read = harness.layer_readers(list(NEW))
    old = [R(seconds=0.5, steps_useful=14, steps_computed=30,
             ingest_host_seconds=0.15)]
    for name in NEW:
        assert read[name](_ctx(old)) is None, name


def test_step_and_byte_metrics_are_declared_for_the_cell():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert m["moves"] == "samples_per_s"
        assert m["workloads"] == ["resnet18.paper"]
        assert m["source"] == "program_counter"
        assert (REPO / "bench" / "layer_metrics" / f"{name}.py").is_file()
    assert declared["local.run_useful_frac"]["layer"] == "local training"
    assert declared["ingest.placed_mb"]["layer"] == "ingest"


def test_step_and_byte_readers_on_a_tiny_run(tmp_path):
    """On the tiny cell (ragged Dirichlet clients, an array source): the
    run share lies above the staged share, since skipped and narrowed
    steps leave fewer padded client-steps, and the round places its
    sample indices, mask and ids, not pixels."""
    root = benchtiny.make_root(tmp_path, traffic={"dirichlet_alpha": 0.2})
    task = benchtiny.build(root, 2 ** 31 + 7)
    try:
        recs = [task.run_round(t) for t in range(6)]
    finally:
        task.close()
    read = harness.layer_readers(list(NEW), root)
    ctx = _ctx(recs)
    staged = sum(task.useful_steps(t) for t in range(6)) / (
        6 * task.computed_steps())
    run_frac = read["local.run_useful_frac"](ctx)
    assert staged < run_frac <= 1.0
    k, m = task.traffic.clients_per_round, task.max_steps
    b = task.traffic.batch_size
    assert read["ingest.placed_mb"](ctx) == pytest.approx(
        (k * m * b * 4 + k * m + k * 4) / 1e6)
