"""The benchmark harness (bench/run.py) without a TPU: discovery by name,
the useful-step count against the masks the ingest pipeline stages, the
result's schema, and the refusals."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import benchtiny
from benchtiny import CELL, REPO

import run as harness  # noqa: E402  (bench/, put on the path by benchtiny)


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_cell_config_traffic_and_metric_found_by_name(tmp_path):
    root = benchtiny.make_root(tmp_path)
    before = _digests(root)
    (root / "bench" / "layer_metrics" / "dummy.answer.py").write_text(
        "def read(ctx):\n    return ctx['samples'] * 2\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "dummy.answer", "unit": "samples",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "samples_per_s",
                              "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell(CELL, root)
    assert cell["config_data"]["name"] == "tiny-lenet"
    assert cell["traffic_path"].name == "tiny.json"
    assert cell["task_path"].name == "vision.py"
    assert set(cell["limits"]) >= {"loss_gap", "grad_gap", "change_gap"}
    names = [m["name"] for m in cell["per_layer"]]
    assert "dummy.answer" in names
    # metrics listed for other cells only are not this cell's
    assert "batched_epilogue_roofline" not in names
    readers = harness.layer_readers(names, root)
    assert readers["dummy.answer"]({"samples": 21}) == 42
    # adding them changed no file that was there
    after = _digests(root)
    assert {k: after[k] for k in before} == before


def test_repository_cells_resolve():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell["traffic_path"].is_file()
        assert cell["task_path"].is_file()
        harness.layer_readers([m["name"] for m in cell["per_layer"]])


def test_tiny_run_schema_on_cpu(tmp_path):
    root = benchtiny.make_root(tmp_path)
    result = harness.run(CELL, 2 ** 31 + 11, 0.5, False, root=root,
                         allow_cpu=True)
    assert result["correct"] is True
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s", "round_s.p90",
                                      "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for v in result["compared"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(result)


def test_useful_steps_equal_staged_masks(tmp_path):
    """The benchmark's count of unmasked local steps, from its copy of
    the batch rule, equals what the program's ingest pipeline stages as
    masks, round by round."""
    root = benchtiny.make_root(tmp_path, traffic={"dirichlet_alpha": 0.2})
    task = benchtiny.build(root, 5)
    staged = []
    inner = task.trainer._cohort_round

    def spy(state, params, batches, masks, ids):
        staged.append(int(masks.sum()))
        return inner(state, params, batches, masks, ids)

    task.trainer._cohort_round = spy
    try:
        for t in range(6):
            task.run_round(t)
    finally:
        task.close()
    assert staged == [task.useful_steps(t) for t in range(6)]
    assert task.computed_steps() >= max(staged)
    assert any(s < task.computed_steps() for s in staged)   # padding seen


def _cli(root: Path, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "resnet18.paper", "--seed", "3", "--seconds", "1", "--trace", "0",
         *extra], cwd=root, env=env, capture_output=True, text=True,
        timeout=240)


def test_cli_refuses_a_cpu():
    proc = _cli(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_cli_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files is no checkout of the program: no result."""
    import shutil
    root = tmp_path / "bare"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(REPO / p, root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(root)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_percentile_matches_numpy():
    import numpy as np
    xs = [0.5, 0.1, 0.9, 0.3, 0.7, 0.2, 0.4]
    assert harness.percentile(xs, 90) == pytest.approx(np.percentile(xs, 90))


def test_layer_metric_readers():
    """Each per-layer reader on a hand-made context."""
    from types import SimpleNamespace as R
    names = [p.stem for p in (REPO / "bench" / "layer_metrics").glob("*.py")]
    read = harness.layer_readers(names)
    recs = [R(ingest_host_seconds=0.2, ingest_device_seconds=0.05),
            R(ingest_host_seconds=0.0, ingest_device_seconds=0.05)]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"records": recs, "useful_steps": 3, "computed_steps": 12,
           "samples": 1000, "window_s": 2.0, "flops_per_sample": 1e6,
           "epilogue_bytes": 1e6, "peaks": peaks, "chips": 1,
           "trace": {"busy_s": 0.5, "window_s": 2.0,
                     "kernel_s": {"batched_epilogue": 0.004},
                     "collective_exposed_s": 0.0},
           "scope_s": {"fl.local": 0.4, "fl.server": 0.01}}
    assert read["ingest.wait_s"](ctx) == pytest.approx(0.15)
    assert read["local.useful_step_frac"](ctx) == pytest.approx(0.25)
    assert read["device.idle_frac"](ctx) == pytest.approx(0.75)
    # 1e9 FLOP over 2 s of a 1e12 FLOP/s chip
    assert read["round.mfu"](ctx) == pytest.approx(0.05)
    # 1 ms at peak per round, 2 ms spent per round
    assert read["batched_epilogue_roofline"](ctx) == pytest.approx(50.0)
    # device seconds of a scope, per round of the window
    assert read["local.device_s"](ctx) == pytest.approx(0.2)
    assert read["server.device_s"](ctx) == pytest.approx(0.005)
    ctx["scope_s"] = {"fl.local": 0.0}
    assert read["local.device_s"](ctx) is None
    assert read["server.device_s"](ctx) is None
    ctx["trace"]["kernel_s"] = {"batched_epilogue": 0.0}
    assert read["batched_epilogue_roofline"](ctx) is None
    ctx.update(chips=4, epilogue_bytes=None)
    assert read["batched_epilogue_roofline"](ctx) is None


def test_epilogue_calls_found_by_kernel_name():
    """The epilogue's Mosaic calls are told apart by the kernel function
    that their serialized body names, not by the instruction's name."""
    import base64
    vision = harness.load_module(REPO / "bench" / "tasks" / "vision.py",
                                 "task_vision_names")

    def call(name, body):
        blob = base64.b64encode(b"MLIR\x00kernel.py\x00" + body + b"\x00")
        return (f'  %{name} = (f32[8,128]) custom-call(%a, %b), '
                f'custom_call_target="tpu_custom_call", backend_config='
                f'{{"custom_call_config":{{"body":"{blob.decode()}",'
                f'"serialization_format":"1"}}}}')

    text = "\n".join([
        call("round_fn.2", b"_batched_epilogue_kernel"),
        call("round_fn.3", b"_batched_epilogue_kernel"),
        call("fused_dots_flat.1", b"_reduce_kernel"),
        call("round_fn.4", b"_dequant_batched_epilogue_kernel"),
        "  %fusion.1 = f32[8] fusion(%a), kind=kLoop, "
        "calls=%batched_epilogue_like"])
    assert vision.epilogue_calls(text) == {"round_fn.2", "round_fn.3"}
