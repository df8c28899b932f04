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
NS = 1000   # picoseconds per nanosecond


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


def build(root: Path, seed: int, cell: str = CELL):
    """The task of ``cell`` in the checkout ``root``, built from its
    files for ``seed`` as a run builds it."""
    import run as harness
    harness.setup_paths(root)
    found = harness.find_cell(cell, root)
    task_mod, traffic = harness.load_task(found)
    return task_mod.build(found["config_data"], traffic, seed)


def xspace(device_ops, host_spans):
    """A text-proto XSpace: ``device_ops`` maps a chip index to
    (name, start_ns, end_ns) op events; ``host_spans`` is a list of
    (name, start_ns, end_ns) on one host thread."""
    meta, ids = [], {}

    def mid(name):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta.append(name)
        return ids[name]

    def events(evs):
        return "\n".join(
            f"events {{ metadata_id: {mid(n)} offset_ps: {s * NS} "
            f"duration_ps: {(e - s) * NS} }}" for n, s, e in evs)

    def metadata():
        return "\n".join(
            f'event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} '
            f'name: {json.dumps(n)} }} }}' for n in meta)

    planes = []
    for chip, ops in device_ops.items():
        ids.clear()
        meta.clear()
        body = events(ops)
        planes.append(f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
                      f'{body} }} {metadata()} }}')
    ids.clear()
    meta.clear()
    body = events(host_spans)
    planes.append(f'planes {{ id: 99 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python3" timestamp_ns: 0 {body} }} '
                  f'{metadata()} }}')
    import jax
    return jax.profiler.ProfileData.from_text_proto("\n".join(planes))
