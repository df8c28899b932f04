"""A tiny copy of the benchmark for its tests: the repository's bench/
copied into a temporary checkout beside a link to src/, plus a tiny
LeNet-5 configuration, traffic mix and cell that run on the CPU in
seconds."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))

import models  # noqa: E402

TINY_MODEL = {"family": "lenet5", "image_size": 32, "channels": 3,
              "num_classes": 4}
TINY_TRAFFIC = {"num_clients": 8, "clients_per_round": 4, "batch_size": 8,
                "local_epochs": 1, "dirichlet_alpha": 0.5,
                "population_seed": 0, "samples_per_class": 16,
                "image_noise": 0.35, "eta_l": 0.1, "eta_g": 0.1,
                "lam": 1.0, "shard_clients": False}
CELL = "tiny.cell"


def make_root(tmp: Path, traffic=None, chips: int = 1,
              limits_from: str = "resnet18.paper") -> Path:
    """A checkout in ``tmp`` whose BENCHMARK.json holds one tiny cell,
    held to the limits of the repository's cell ``limits_from``."""
    root = tmp / "checkout"
    root.mkdir()
    (root / "src").symlink_to(REPO / "src")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    (bench / "configs" / "tiny-lenet.json").write_text(json.dumps({
        "name": "tiny-lenet", "task": "vision", "model": TINY_MODEL,
        "params": models.param_count(TINY_MODEL), "reduced": []}))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {**TINY_TRAFFIC, **(traffic or {})}))
    limits = json.loads((REPO / "bench" / "workloads" /
                         f"{limits_from}.json").read_text())["limits"]
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"limits": limits}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": CELL, "config": "tiny-lenet",
                          "traffic": "tiny", "chips": chips, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
